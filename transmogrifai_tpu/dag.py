"""DAG computation and fit/transform scheduling — the TPU-native re-design of
FitStagesUtil (reference: core/src/main/scala/com/salesforce/op/utils/stages/
FitStagesUtil.scala:173-304).

``compute_dag`` layers stages by distance-to-result exactly like the reference's
``computeDAG``; ``fit_dag`` fits estimators layer-by-layer then applies the
layer's transformers.  Where the reference bulk-applies row closures in a single
RDD map (applyOpTransformations:96) and persists every K Spark stages to break
Catalyst (:134-165), we simply apply column transforms — device-resident
columns stay in HBM and XLA fuses the ops; no persistence hacks are needed
(SURVEY.md §2.6 P5).
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence, Tuple

from .columns import ColumnBatch
from .features import Feature
from .stages.base import Estimator, PipelineStage, Transformer, TransformerModel
from .stages.generator import FeatureGeneratorStage

StageLayer = List[PipelineStage]


def compute_dag(result_features: Sequence[Feature]) -> List[StageLayer]:
    """Layer stages by max distance to any result feature, deepest first
    (≙ FitStagesUtil.computeDAG).  FeatureGeneratorStages are excluded — raw
    data generation is the reader's job."""
    dist: Dict[PipelineStage, int] = {}
    for f in result_features:
        for stage, d in f.parent_stages().items():
            if dist.get(stage, -1) < d:
                dist[stage] = d
    layers: Dict[int, StageLayer] = {}
    for stage, d in dist.items():
        if isinstance(stage, FeatureGeneratorStage):
            continue
        layers.setdefault(d, []).append(stage)
    out = [sorted(layers[d], key=lambda s: s.uid) for d in sorted(layers, reverse=True)]
    return [l for l in out if l]


def dag_stages(dag: List[StageLayer]) -> List[PipelineStage]:
    return [s for layer in dag for s in layer]


def prune_batch(batch: ColumnBatch, remaining_stages, keep_names) -> ColumnBatch:
    """Release columns no remaining stage consumes (HBM liveness — the TPU
    analog of the reference's persist/unpersist discipline): a device-resident
    intermediate like a hashed text block is GBs at scale, and holding it
    alive past its last consumer is what out-of-memories a 16 GB chip."""
    needed = set(keep_names)
    for s in remaining_stages:
        needed.update(f.name for f in s.input_features)
    drop = [n for n in batch.names() if n not in needed]
    return batch.drop(drop) if drop else batch


def fit_layer(batch: ColumnBatch, layer: StageLayer,
              fit: Optional[Callable[[Estimator, ColumnBatch], Transformer]]
              = None) -> Tuple[ColumnBatch, List[Transformer]]:
    """Fit all estimators of a layer, then apply every transformer of the layer
    (≙ fitAndTransformLayer, FitStagesUtil.scala:253).  ``fit(stage, batch)``
    fits an estimator where ``stage.fit(batch)`` should not be called bare."""
    fitted: List[Transformer] = []
    for stage in layer:
        if isinstance(stage, Estimator):
            model = fit(stage, batch) if fit else stage.fit(batch)
            fitted.append(model)
        elif isinstance(stage, Transformer):
            fitted.append(stage)
        else:
            raise TypeError(f"stage {stage} is neither Transformer nor Estimator")
    for t in fitted:
        batch = t.transform_batch(batch)
    return batch, fitted


def fit_dag(batch: ColumnBatch, dag: List[StageLayer]) -> Tuple[ColumnBatch, List[StageLayer]]:
    """Fit + transform the whole DAG (≙ fitAndTransformDAG:213).  Returns the
    transformed batch and the fitted DAG (same layering, estimators replaced by
    their models)."""
    fitted_dag: List[StageLayer] = []
    for layer in dag:
        batch, fitted = fit_layer(batch, layer)
        fitted_dag.append(list(fitted))
    return batch, fitted_dag


def apply_dag(batch: ColumnBatch, dag: List[StageLayer],
              up_to_feature: Optional[Feature] = None) -> ColumnBatch:
    """Apply an already-fitted DAG (≙ applyTransformationsDAG,
    OpWorkflowCore.scala:321)."""
    for layer in dag:
        for t in layer:
            if not isinstance(t, Transformer):
                raise TypeError(
                    f"DAG contains unfitted estimator {t}; fit the workflow first")
            batch = t.transform_batch(batch)
            if up_to_feature is not None and any(
                    f.name == up_to_feature.name for f in t.output_features):
                return batch
    return batch


def cut_dag(dag: List[StageLayer], selector) -> Tuple[List[StageLayer], List[StageLayer], List[StageLayer]]:
    """Split the DAG into (before, during, after) relative to a ModelSelector
    for workflow-level cross-validation (≙ FitStagesUtil.cutDAG:304-356).

    Reference semantics: label leakage flows only through stages that consume
    BOTH a response and a non-response input (SanityChecker and friends), so
    'during' — the sub-DAG refit inside every fold — is the selector's
    ancestor DAG from the first such label-consuming layer onward
    (``firstCVTSIndex``, FitStagesUtil.scala:333-337).  Everything upstream of
    that layer ('before') is fit once on the full data, even estimators,
    exactly as the reference does; side branches feeding other result features
    also stay in 'before' (the ``nonMSDAG - CVTSDAG`` rule, :344-349)."""
    sel_layer_idx = None
    for i, layer in enumerate(dag):
        if any(s is selector for s in layer):
            sel_layer_idx = i
            break
    if sel_layer_idx is None:
        return dag, [], []

    # the selector's own ancestor DAG, deepest-first, selector layer dropped
    anc_layers = compute_dag(selector.output_features)
    if anc_layers and any(s is selector for s in anc_layers[-1]):
        anc_layers = anc_layers[:-1]

    def consumes_label_and_features(stage) -> bool:
        ins = stage.input_features
        return (any(f.is_response for f in ins)
                and any(not f.is_response for f in ins))

    first = next((i for i, layer in enumerate(anc_layers)
                  if any(consumes_label_and_features(s) for s in layer)), -1)
    during_stages = (set() if first < 0 else
                     {s for layer in anc_layers[first:] for s in layer})

    # side branches consuming a 'during' output must follow it into 'during':
    # leaving them in 'before' would run them ahead of their producer.  One
    # forward pass suffices — layers are topologically ordered.
    during_out = {f.name for s in during_stages for f in s.output_features}
    for layer in dag[:sel_layer_idx]:
        for s in layer:
            if s not in during_stages and any(
                    f.name in during_out for f in s.input_features):
                during_stages.add(s)
                during_out.update(f.name for f in s.output_features)

    before = [[s for s in layer if s not in during_stages]
              for layer in dag[:sel_layer_idx]]
    during = [[s for s in layer if s in during_stages]
              for layer in dag[:sel_layer_idx]]
    after = dag[sel_layer_idx:]
    return ([l for l in before if l], [l for l in during if l], after)

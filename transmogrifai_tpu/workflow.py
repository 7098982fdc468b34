"""Workflow — the user-facing DAG container and fitted model (reference:
core/src/main/scala/com/salesforce/op/OpWorkflow.scala:207,234,344,382-458,
OpWorkflowCore.scala:52, OpWorkflowModel.scala:184-394,
OpWorkflowModelWriter.scala:76, OpWorkflowModelReader.scala).

``train`` reconstructs the stage DAG from the result features, generates raw
data through the reader (optionally filtered by RawFeatureFilter), fits the
DAG layer-by-layer, and returns a ``WorkflowModel`` whose transformer DAG is a
pure column program (the reference's persist-every-K Catalyst hacks are
unnecessary — HBM residency + XLA fusion replace them, SURVEY.md §2.6 P5).
"""

from __future__ import annotations

import contextlib
import json
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .columns import Column, ColumnBatch
from .dag import apply_dag, compute_dag, cut_dag, dag_stages, fit_dag, fit_layer
from .features import Feature
from .readers.base import DataReader, Reader
from .stages.base import (ColumnWired, Estimator, PipelineStage, Transformer,
                          TransformerModel)
from .stages.generator import FeatureGeneratorStage
from .stages.serialization import (feature_to_json, kind_by_name,
                                   stage_fitted_arrays, stage_from_json,
                                   stage_to_json)
from .types import Prediction

MODEL_JSON = "op-model.json"
PARAMS_NPZ = "params.npz"
#: Rows below which ``train`` starts no transfers ahead of the fit: a tiny
#: workflow would pay dispatch latency for nothing.
PREFETCH_MIN_ROWS = 100_000



def _fit_stage(st: Estimator, batch: ColumnBatch) -> Transformer:
    """``st.fit(batch)`` in a fit phase, under the span
    ``transform.fit.<class>`` — but for the selector and SanityChecker,
    whose fits open ``selector.*`` and ``sanity.fit`` themselves."""
    from .preparators.sanity_checker import SanityChecker
    from .selector import ModelSelector
    from .telemetry import span
    if isinstance(st, (ModelSelector, SanityChecker)):
        return st.fit(batch)
    with span(f"transform.fit.{type(st).__name__}", rows=len(batch),
              inputs=len(st.input_features)):
        return st.fit(batch)


class _WorkflowCore:
    """Shared between Workflow and WorkflowModel (≙ OpWorkflowCore.scala:52)."""

    def __init__(self):
        self.reader: Optional[Reader] = None
        self.result_features: Tuple[Feature, ...] = ()
        self.raw_features: List[Feature] = []
        self.blacklisted: List[Feature] = []
        self.parameters: Dict[str, Any] = {}
        self._input_batch: Optional[ColumnBatch] = None

    # -- input wiring ------------------------------------------------------
    def set_reader(self, reader: Reader):
        self.reader = reader
        return self

    def set_input_records(self, records: Sequence[Dict[str, Any]],
                          key_fn=None):
        self.reader = DataReader(records=list(records), key_fn=key_fn)
        return self

    def set_input_batch(self, batch: ColumnBatch):
        self._input_batch = batch
        return self

    def set_parameters(self, params: Dict[str, Any]):
        self.parameters = dict(params)
        return self

    # -- raw data ----------------------------------------------------------
    def generate_raw_data(self) -> ColumnBatch:
        """≙ OpWorkflow.generateRawData:234."""
        if self._input_batch is not None:
            return self._input_batch
        if self.reader is None:
            raise ValueError("no reader or input batch set — call set_reader/"
                             "set_input_records/set_input_batch first")
        raw = [f for f in self.raw_features
               if f.name not in {b.name for b in self.blacklisted}]
        return self.reader.generate_batch(raw)

    def _collect_features(self):
        feats: Dict[str, Feature] = {}
        for rf in self.result_features:
            for f in rf.all_features():
                feats[f.uid] = f
        self.raw_features = sorted(
            (f for f in feats.values() if f.is_raw), key=lambda f: f.name)
        return feats


class Workflow(_WorkflowCore):
    """≙ OpWorkflow."""

    def __init__(self):
        super().__init__()
        self._workflow_cv = False
        self._raw_feature_filter = None
        self._model_stages: Dict[str, TransformerModel] = {}
        self._sanitizers: Dict[str, bool] = {}

    def set_result_features(self, *features: Feature) -> "Workflow":
        """≙ setResultFeatures: reconstruct the stage DAG (OpWorkflow.scala:207)."""
        self.result_features = tuple(features)
        self._collect_features()
        self._validate_stages()
        return self

    def with_workflow_cv(self) -> "Workflow":
        """≙ withWorkflowCV (OpWorkflowCore.scala:104): refit the feature
        stages feeding the model selector inside each CV fold."""
        self._workflow_cv = True
        return self

    def with_sanitizers(self, nan_check: bool = False,
                        purity_check: bool = True,
                        serialization_check: bool = True) -> "Workflow":
        """Opt-in discipline checks during train (sanitizer.py — the analog
        of the reference's closure-serializability validation and of JVM
        sanitizers): ``nan_check`` turns on jax_debug_nans for the whole fit;
        ``purity_check`` asserts every fitted transformer is deterministic;
        ``serialization_check`` asserts every stage JSON-round-trips."""
        self._sanitizers = {"nan": nan_check, "purity": purity_check,
                            "serialization": serialization_check}
        return self

    def apply_stage_params(self, op_params) -> "Workflow":
        """Per-stage-class hyperparameter injection from OpParams
        (≙ OpWorkflow.setStageParameters, OpWorkflow.scala:178-199).  Entries
        matching no stage warn — a typo'd class name must not silently train
        with defaults."""
        import warnings

        stages = dag_stages(compute_dag(self.result_features))
        for match, kv in (op_params.stage_params or {}).items():
            hit = False
            for st in stages:
                cls_name = type(st).__name__
                if cls_name == match or cls_name.startswith(match):
                    hit = True
                    for k, v in kv.items():
                        st.set(k, v)
            if not hit:
                warnings.warn(
                    f"stageParams entry {match!r} matched no stage in the "
                    f"workflow (stages: "
                    f"{sorted({type(s).__name__ for s in stages})})",
                    stacklevel=2)
        return self

    def apply_racing_params(self, racing) -> "Workflow":
        """Push OpParams.racing ({enabled, eta, minSurvivors}) onto every
        ModelSelector's validator — racing is a validator behavior, not a
        stage hyper-parameter, so it rides its own channel instead of
        stageParams."""
        if not racing:
            return self
        for st in dag_stages(compute_dag(self.result_features)):
            v = getattr(st, "validator", None)
            if v is None or not hasattr(v, "racing"):
                continue
            if "enabled" in racing:
                v.racing = bool(racing["enabled"])
            if "eta" in racing:
                v.racing_eta = float(racing["eta"])
            if "minSurvivors" in racing:
                v.racing_min_survivors = int(racing["minSurvivors"])
        return self

    def with_raw_feature_filter(self, **kw) -> "Workflow":
        """≙ withRawFeatureFilter (OpWorkflow.scala:538)."""
        from .filters import RawFeatureFilter
        self._raw_feature_filter = RawFeatureFilter(**kw)
        return self

    def with_model_stages(self, model: "WorkflowModel") -> "Workflow":
        """≙ withModelStages (OpWorkflow.scala:471): reuse fitted stages with
        matching uids for partial retraining."""
        for layer in model.fitted_dag:
            for st in layer:
                self._model_stages[st.uid.replace("_model", "")] = st
        return self

    def _apply_blacklist(self):
        """≙ setBlacklist (OpWorkflow.scala:117): remove blacklisted raw
        features from every stage's inputs; stages that lose all inputs die
        and their outputs cascade to downstream consumers."""
        dead = {f.uid for f in self.blacklisted}
        if not dead:
            return
        dag = compute_dag(self.result_features)
        for layer in dag:  # deepest-first = closest to raw data
            for st in layer:
                if not st.input_features:
                    continue
                new_inputs = tuple(f for f in st.input_features
                                   if f.uid not in dead)
                if not new_inputs:
                    for out in st.output_features:
                        dead.add(out.uid)
                    continue
                if len(new_inputs) != len(st.input_features):
                    st.input_features = new_inputs
                    for out in st.output_features:
                        out.parents = new_inputs
        lost = [f.name for f in self.result_features if f.uid in dead]
        if lost:
            raise ValueError(
                f"RawFeatureFilter removed all inputs of result feature(s) "
                f"{lost}; relax the filter thresholds or protect features")

    def _validate_stages(self):
        """≙ OpWorkflow stage validation :277-335 — distinct uids and
        stage-type sanity."""
        dag = compute_dag(self.result_features)
        seen = set()
        for st in dag_stages(dag):
            if st.uid in seen:
                raise ValueError(f"duplicate stage uid {st.uid}")
            seen.add(st.uid)
            if not isinstance(st, (Transformer, Estimator)):
                raise TypeError(f"stage {st} is neither Transformer nor Estimator")

    # -- training ----------------------------------------------------------
    def train(self, resume_from: Optional[str] = None) -> "WorkflowModel":
        """≙ OpWorkflow.train:344.

        The whole fit runs under a train-scoped ``FailureLog`` (ambient, so
        compiled-segment demotions, validator candidate skips and device
        fallbacks report into it from any depth/thread); the log is exposed
        on the returned model as ``model.failure_log``.

        ``resume_from`` names a sweep-checkpoint directory: completed
        selector candidates are flushed there after each candidate family,
        and a restarted train pointed at the same directory replays them
        instead of re-fitting (resumptions appear in the failure log with
        action ``resumed``).  For the dynamic extent of the call SIGTERM/
        SIGINT request a graceful stop at the next candidate boundary; the
        sweep flushes a final checkpoint and the call raises
        ``TrainingPreempted`` (carrying ``resume_from`` and the failure
        log) instead of dying mid-write."""
        from .checkpoint import (SweepCheckpoint, TrainingPreempted,
                                 preemption_guard, use_sweep_checkpoint)
        from .profiling import PhaseTimer, device_peak_bytes
        from .resilience import FailureLog, record_failure, use_failure_log
        from .sanitizer import (audit_dag_purity, audit_stage_serialization,
                                nan_guard)
        from .telemetry import REGISTRY, publish_train_profile, span

        timer = PhaseTimer()
        flog = FailureLog()
        sweep_cp = None
        if resume_from is not None:
            sweep_cp = SweepCheckpoint(resume_from)
        train_span = None       # stays None when no tracer is installed
        try:
            with span("workflow.train",
                      resumed=bool(sweep_cp is not None and len(sweep_cp))
                      ) as train_span, \
                    use_failure_log(flog), preemption_guard("train"), \
                    use_sweep_checkpoint(sweep_cp):
                if sweep_cp is not None and len(sweep_cp):
                    record_failure(
                        "train", "resumed",
                        f"sweep checkpoint with {len(sweep_cp)} completed "
                        "candidate(s)", point="checkpoint.load",
                        resume_from=sweep_cp.path)
                return self._train_guarded(timer, flog)
        except TrainingPreempted as e:
            e.failure_log = flog
            raise
        finally:
            # what each device has held at most so far, fullest or not: on a
            # mesh the shards' peaks differ where the work does
            REGISTRY.gauge("mesh.device_peak_bytes").set(device_peak_bytes())
            if train_span is not None:
                publish_train_profile(train_span)

    def _train_guarded(self, timer, flog) -> "WorkflowModel":
        """Body of ``train`` — runs with the failure log, preemption guard
        and sweep checkpoint already ambient."""
        from .sanitizer import (audit_dag_purity, audit_stage_serialization,
                                nan_guard)
        # the poison-data firewall (quality.py) brackets ingestion: the
        # ambient config lets readers quarantine malformed records per-row
        # (instead of raising mid-file), and the post-assembly screen drops
        # NaN/Inf rows before anything ships to the device.  Past
        # maxQuarantineFraction, training aborts with DataQualityError —
        # never silently fits on a fraction of the data.
        from .quality import QualityConfig, screen_batch, use_quality
        qcfg = QualityConfig.resolve(self.parameters.get("quality"))
        with timer.phase("read"):
            if qcfg.enabled:
                with use_quality(qcfg):
                    batch = self.generate_raw_data()
                batch = screen_batch(batch, self.raw_features, qcfg,
                                     stage="train")
            else:
                batch = self.generate_raw_data()
        from .ops.text_profile import host_pool
        rff_results = None
        # one pool for the prologue's host work over rows: the string walks
        # and RawFeatureFilter's distributions start on it side by side, and
        # the filter joins its own where its rules first need them; open
        # through the fit, where a staged model's column wires start as it
        # is fitted.  Large batches only: a tiny one is done before a thread
        # has started.
        with (host_pool(len(self.raw_features))
              if len(batch) >= PREFETCH_MIN_ROWS
              else contextlib.nullcontext()) as pool:
            with timer.phase("prefetch"):
                self._prefetch_text_profiles(batch, pool)
            if self._raw_feature_filter is not None:
                with timer.phase("rff"):
                    batch, dropped, rff_results = \
                        self._raw_feature_filter.filter_batch(
                            batch, self.raw_features)
                    self.blacklisted = dropped
                    self._apply_blacklist()
            dag = compute_dag(self.result_features)
            if self._sanitizers.get("serialization"):
                audit_stage_serialization(dag_stages(dag))
            raw_batch = batch if self._sanitizers.get("purity") else None
            with nan_guard(self._sanitizers.get("nan", False)):
                if self._workflow_cv:
                    batch, fitted_dag = self._fit_with_workflow_cv(
                        batch, dag, timer)
                else:
                    batch, fitted_dag = self._fit_plain(batch, dag, timer,
                                                        pool)
        if raw_batch is not None:
            audit_dag_purity(fitted_dag, raw_batch)
        model = WorkflowModel(
            result_features=self.result_features,
            fitted_dag=fitted_dag,
            raw_features=self.raw_features,
            blacklisted=self.blacklisted,
            parameters=self.parameters,
            rff_results=rff_results)
        model.reader = self.reader
        model._input_batch = self._input_batch
        model.train_batch = batch
        model.app_metrics = timer.app_metrics("train")
        model.failure_log = flog
        return model

    def _prefetch_text_profiles(self, batch, pool=None) -> None:
        """Start up front what a training run will need of its raw columns.
        Large batches only: tiny workflows would pay dispatch latency for
        nothing.

        On every backend, where the train has a ``pool``
        (``ops.text_profile.host_pool``) and a RawFeatureFilter: the
        filter's distributions (``RawFeatureFilter.start_distributions``),
        a job a predictor held as an array or as strings, which
        ``filter_batch`` joins where its rules need them — they hide host
        work behind host work, not the link.

        On an accelerator, also: every text column of a hashing vectorizer
        or a pivot profiled ONCE (``ops.text_profile.profile_columns``:
        walked natively by row range on a few of the pool's threads,
        interned in that walk at the stage's ``max_cardinality``, a pivot's
        without a cap, so that the fit finds ``values(cap)`` cached, its
        token ids packed for the stage's ``num_hashes`` by the same
        workers; the filter's job for such a column starts when its walk
        is whole), the packed words handed to the link from this thread in
        feature order, and the bf16-wire copies of numeric raw columns +
        the label.  The async host→device transfers then overlap
        RawFeatureFilter + fit host work instead of serializing after it
        (the TPU analog of the reference keeping row work on executors,
        SmartTextVectorizer.scala:80); the CPU has no slow link to hide."""
        if len(batch) < PREFETCH_MIN_ROWS:
            return
        import jax

        from .columns import to_device_f32
        from .ops.text_profile import profile_columns
        from .telemetry import span
        accelerator = jax.default_backend() != "cpu"
        columns = self._profiled_columns(batch) if accelerator else []
        started = None
        if pool is not None and self._raw_feature_filter is not None:
            started = self._raw_feature_filter.start_distributions(
                batch, self.raw_features, pool,
                walked=[name for name, _ in columns])
        if not accelerator:
            return
        try:
            with span("prefetch.text_profiles", rows=len(batch),
                      columns=len(columns)):
                names = [name for name, _ in columns]
                for prof, (name, (_, _, num_hashes)) in zip(
                        profile_columns([triple for _, triple in columns],
                                        pool, names), columns):
                    if num_hashes:
                        prof.prefetch(num_hashes)
                    if started is not None:
                        started.walked(name)
            # numeric raw columns + label: the weakref transfer cache makes
            # these THE copies every later consumer (frontier _prep,
            # vectorizer fits, selector y) reuses
            with span("prefetch.numeric"):
                for f in self.raw_features:
                    col = batch.get(f.name)
                    if col is None or col.is_host_object():
                        continue
                    v = col.values
                    # a vector a column: a coordinate's triples cross as
                    # their stage's wire makes them
                    if (isinstance(v, np.ndarray) and v.ndim == 1
                            and v.dtype in (np.float32, np.float64)):
                        to_device_f32(v, exact=f.is_response)
        except Exception as e:  # noqa: BLE001 — prefetch must never break
            # train, but a dead prefetch means the host link no longer hides
            # behind RFF/fit work — observable, not invisible
            from .resilience import record_failure
            record_failure("workflow.prefetch", "swallowed", e,
                           point="workflow.prefetch")

    def _profiled_columns(self, batch):
        """[(feature name, (column, cap, num_hashes))] of the string columns
        a hashing vectorizer or a pivot of this workflow reads: what
        ``profile_columns`` walks ahead of their fits."""
        from .ops.categorical import OneHotEstimator
        from .ops.text import HashingVectorizer, SmartTextVectorizer
        columns = []
        for st in dag_stages(compute_dag(self.result_features)):
            if isinstance(st, OneHotEstimator):
                cap = -1        # a pivot counts every value
            elif isinstance(st, (SmartTextVectorizer, HashingVectorizer)):
                cap = st.get("max_cardinality")  # None: no interning
            else:
                continue
            for f in st.input_features:
                col = batch.get(f.name)
                if col is None or not col.is_host_object():
                    continue
                vals = col.values
                if len(vals) and not isinstance(
                        next((v for v in vals if v is not None), ""), str):
                    continue    # token lists take the legacy path
                columns.append(
                    (f.name, (col, cap, int(st.get("num_hashes") or 0))))
        return columns

    def _fit_plain(self, batch, dag, timer=None, pool=None):
        """Fit the DAG with DEFERRED transform application: estimators fit
        layer-by-layer as before, but fitted transforms apply lazily — each
        run of pending transforms compiles into ONE fused XLA program
        (ScoreProgram with staged stages) the moment a downstream estimator
        needs their outputs.  The whole vectorizer layer + combiner becomes
        a single program instead of one dispatch/compile per stage — the fit
        path's analog of the reference's single bulk row map
        (FitStagesUtil.scala:96).

        With the train's prologue ``pool``, a fitted model whose host
        prologue is made column by column (``ColumnWired``) starts one job
        an input column on it at once, behind the fits that follow; the
        flush that applies the model joins them."""
        import itertools

        from .compiled import ScoreProgram
        from .dag import prune_batch
        from .profiling import PhaseTimer
        from .selector import ModelSelector
        from .telemetry import span
        timer = timer or PhaseTimer()
        fitted_dag = []
        # columns that outlive the DAG: raw inputs (label profile, re-scoring),
        # result outputs (evaluate), and the row key
        keep = ({f.name for f in self.raw_features}
                | {f.name for f in self.result_features} | {"key"})
        pending: List[Transformer] = []      # fitted, not yet applied
        pending_out: set = set()

        def flush(b, remaining=()):
            """Apply pending transforms as one fused program, then release
            every column no remaining consumer needs — a deferred flush must
            not extend intermediate liveness past what the eager layer-by-
            layer fit had (e.g. the combined feature vector must be GONE
            from HBM before the selector's CV grid runs)."""
            if not pending:
                return b
            with span("transform.apply", stages=len(pending)):
                prog = ScoreProgram(
                    [[m] for m in pending],
                    [f.name for m in pending for f in m.output_features])
                b = prog(b, keep_intermediate=True)
                pending.clear()
                pending_out.clear()
                return prune_batch(b, remaining, keep)

        for i, layer in enumerate(dag):
            new_layer = []
            for st in layer:
                if st.uid in self._model_stages:
                    new_layer.append(self._model_stages[st.uid])
                else:
                    new_layer.append(st)
            kinds = sorted({type(s).__name__ for s in new_layer})
            tag = ("selector" if any(isinstance(s, ModelSelector)
                                     for s in new_layer)
                   else "fit:" + "+".join(kinds))
            with timer.phase(tag):
                models = []
                for j, st in enumerate(new_layer):
                    if isinstance(st, Estimator):
                        if any(f.name in pending_out
                               for f in st.input_features):
                            batch = flush(batch, itertools.chain(
                                new_layer[j:],
                                (s for l in dag[i + 1:] for s in l)))
                        m = _fit_stage(st, batch)
                        if pool is not None and isinstance(m, ColumnWired):
                            m.start_wires(batch, pool)
                    elif isinstance(st, Transformer):
                        m = st
                    else:
                        raise TypeError(
                            f"stage {st} is neither Transformer nor Estimator")
                    models.append(m)
                    pending.append(m)
                    pending_out.update(f.name for f in m.output_features)
            fitted_dag.append(models)
            batch = prune_batch(
                batch, itertools.chain(
                    pending, (s for l in dag[i + 1:] for s in l)), keep)
        with timer.phase("fit:apply_tail"):
            batch = flush(batch)
        return batch, fitted_dag

    def _fit_with_workflow_cv(self, batch, dag, timer=None):
        """≙ OpWorkflow.fitStages workflow-CV branch :411-457: cut the DAG at
        the model selector, fit 'before' once, refit 'during' inside each fold."""
        from .profiling import PhaseTimer
        from .selector import ModelSelector
        timer = timer or PhaseTimer()
        selector = None
        for st in dag_stages(dag):
            if isinstance(st, ModelSelector):
                selector = st
                break
        if selector is None:
            return self._fit_plain(batch, dag, timer)
        before, during, after = cut_dag(dag, selector)
        fitted_dag = []
        for layer in before:
            with timer.phase(
                    "fit:" + "+".join(sorted({type(s).__name__
                                              for s in layer}))):
                batch, fitted = fit_layer(batch, layer, _fit_stage)
            fitted_dag.append(fitted)
        # 'during' estimators are refit per fold by the validator; fit them on
        # the full data first (the final model's feature stages) so every
        # 'after' stage — selector or side branch, in any within-layer order —
        # sees its inputs materialized
        for dl in during:
            with timer.phase(
                    "fit:" + "+".join(sorted({type(s).__name__
                                              for s in dl}))):
                batch, f2 = fit_layer(batch, dl, _fit_stage)
            fitted_dag.append(f2)
        for layer in after:
            new_layer = []
            for st in layer:
                if st is selector:
                    with timer.phase("selector"):
                        model = selector.fit(batch, in_fold_dag=during)
                        new_layer.append(model)
                        batch = model.transform_batch(batch)
                else:
                    tag = "fit:" + type(st).__name__
                    with timer.phase(tag):
                        if isinstance(st, Estimator):
                            m = st.fit(batch)
                        else:
                            m = st
                        batch = m.transform_batch(batch)
                    new_layer.append(m)
            fitted_dag.append(new_layer)
        return batch, fitted_dag

    # -- loading -----------------------------------------------------------
    @staticmethod
    def load_model(path: str) -> "WorkflowModel":
        return WorkflowModel.load(path)


class WorkflowModel(_WorkflowCore):
    """≙ OpWorkflowModel: the fitted DAG."""

    def __init__(self, result_features: Sequence[Feature] = (),
                 fitted_dag: Optional[List[List[Transformer]]] = None,
                 raw_features: Sequence[Feature] = (),
                 blacklisted: Sequence[Feature] = (),
                 parameters: Optional[Dict[str, Any]] = None,
                 rff_results=None):
        super().__init__()
        self.result_features = tuple(result_features)
        self.fitted_dag = fitted_dag or []
        self.raw_features = list(raw_features)
        self.blacklisted = list(blacklisted)
        self.parameters = dict(parameters or {})
        self.rff_results = rff_results
        self.train_batch: Optional[ColumnBatch] = None
        self.app_metrics = None     # AppMetrics from train() (profiling.py)
        self.failure_log = None     # FailureLog from train() (resilience.py)
        self.baselines = None       # ModelBaselines from load() (lifecycle)

    # -- access ------------------------------------------------------------
    @property
    def stages(self) -> List[Transformer]:
        return [s for layer in self.fitted_dag for s in layer]

    def get_stage(self, uid: str) -> Transformer:
        for s in self.stages:
            if s.uid == uid or s.uid == uid + "_model":
                return s
        raise KeyError(uid)

    @property
    def selected_model(self):
        from .selector import SelectedModel
        for s in self.stages:
            if isinstance(s, SelectedModel):
                return s
        return None

    # -- scoring -----------------------------------------------------------
    def score_program(self):
        """The fitted DAG compiled for repeated scoring: host prologue →
        ONE jitted XLA program over the device-resident middle → host
        epilogue (≙ the reference's bulk applyOpTransformations row map,
        FitStagesUtil.scala:96, minus the persist-every-K hacks).  Cached on
        the model; jit re-uses the executable across calls with one compile
        per input shape."""
        if getattr(self, "_score_program", None) is None:
            from .compiled import ScoreProgram
            self._score_program = ScoreProgram(
                self.fitted_dag, [f.name for f in self.result_features])
        return self._score_program

    def score(self, batch: Optional[ColumnBatch] = None,
              keep_raw_features: bool = False,
              keep_intermediate_features: bool = False) -> ColumnBatch:
        """≙ OpWorkflowModel.score:255 — apply the whole fitted transformer
        DAG and return the result-feature columns."""
        from .telemetry import span
        if batch is None:
            batch = self.generate_raw_data()
        with span("workflow.score", rows=len(batch)):
            scored = self.score_program()(
                batch, keep_intermediate=keep_intermediate_features)
        names = [f.name for f in self.result_features if f.name in scored]
        if keep_intermediate_features:
            return scored
        keep = list(names)
        if keep_raw_features:
            keep = [f.name for f in self.raw_features if f.name in scored] + keep
        if "key" in scored:
            keep = ["key"] + keep
        return scored.select([n for n in dict.fromkeys(keep)])

    def score_fn(self):
        """≙ scoreFn: returns a callable batch → scored batch with the DAG
        precomputed."""
        return lambda batch: self.score(batch)

    def evaluate(self, evaluator, label_feature: Optional[Feature] = None,
                 batch: Optional[ColumnBatch] = None) -> Dict[str, Any]:
        """≙ OpWorkflowModel.evaluate:320."""
        if batch is None:
            batch = self.generate_raw_data()
        label = label_feature
        if label is None:
            # the label the model actually trained on — the selector's first
            # input (e.g. an INDEXED text response), not the raw string column
            sm = self.selected_model
            if sm is not None and sm.input_features:
                label = sm.input_features[0]
        if label is None:
            label = next(
                (f for f in self.raw_features if f.is_response), None)
        if label is None:
            raise ValueError(
                "evaluate: no response feature in the model's raw features — "
                "pass label_feature explicitly")
        try:
            scored = self.score_program()(batch)
        except KeyError as e:
            raise ValueError(
                f"evaluate: column {e.args[0]!r} required by the DAG is "
                "missing from the scoring data — evaluation needs labelled "
                "rows (use score() for label-free data)") from e
        has_intermediate = False
        if label.name not in scored:
            # a DAG-computed label (e.g. an indexed text response) may live in
            # an intermediate column the lean score pass dropped
            scored = self.score_program()(batch, keep_intermediate=True)
            has_intermediate = True
        if label.name not in scored:
            raise ValueError(
                f"evaluate: response column {label.name!r} is not present in "
                "the scoring data — evaluation needs labelled rows (use "
                "score() for label-free data)")
        pred_f = next(
            (f for f in self.result_features if f.kind is Prediction), None)
        if pred_f is None:
            # fallback: any dict-valued (Prediction-shaped) result column
            if not has_intermediate:
                scored = self.score_program()(batch, keep_intermediate=True)
            pred_f = next(
                (f for f in self.result_features
                 if f.name in scored and isinstance(scored[f.name].values, dict)),
                None)
        if pred_f is None:
            raise ValueError(
                "evaluate: no Prediction-typed result feature on this model; "
                f"result features: {[f.name for f in self.result_features]}")
        pred_col = scored[pred_f.name]
        import jax
        if any(isinstance(v, jax.Array) for v in pred_col.values.values()):
            # device-resident scores (the compiled score program keeps them in
            # HBM): run the whole metric panel as device reductions — only
            # scalars cross the host link
            import jax.numpy as jnp
            y_dev = jnp.asarray(
                np.asarray(scored[label.name].values, dtype=np.float32))
            dev_out = dict(pred_col.values)
            em = evaluator.evaluate_all_device(
                y_dev, dev_out, jnp.ones_like(y_dev))
            if em is not None:
                return em.to_json()
        y = np.asarray(scored[label.name].values, dtype=np.float64)
        pred = {k: np.asarray(v) for k, v in pred_col.values.items()}
        for opt in ("probability", "rawPrediction"):
            pred.setdefault(opt, None)
        return evaluator.evaluate_all(y, pred).to_json()

    def score_and_evaluate(self, evaluator, **kw):
        return self.score(**kw), self.evaluate(evaluator)

    def compute_data_up_to(self, feature: Feature,
                           batch: Optional[ColumnBatch] = None) -> ColumnBatch:
        """≙ computeDataUpTo (OpWorkflowCore.scala:299)."""
        if batch is None:
            batch = self.generate_raw_data()
        return apply_dag(batch, self.fitted_dag, up_to_feature=feature)

    # -- insights ----------------------------------------------------------
    def summary(self) -> Dict[str, Any]:
        """≙ OpWorkflowModel.summary: ModelInsights JSON."""
        from .insights import ModelInsights
        return ModelInsights.extract(self).to_json()

    def summary_pretty(self) -> str:
        from .insights import ModelInsights
        return ModelInsights.extract(self).pretty()

    # -- persistence (≙ OpWorkflowModelWriter.toJson) -----------------------
    def save(self, path: str, overwrite: bool = True,
             aot: Optional[bool] = None):
        """Atomically write the model bundle to ``path``.

        The bundle is staged in a temp sibling directory, checksummed into
        a ``MANIFEST.json``, fsynced and renamed into place — a crash mid-
        save can never leave a torn bundle at ``path``.  With
        ``overwrite=False`` a non-empty ``path`` raises ``FileExistsError``
        instead of being replaced.

        Unless opted out (``aot=False`` / ``--no-aot`` /
        ``TRANSMOGRIFAI_NO_AOT=1``), the fused scoring programs are AOT-
        compiled across the serving padding ladder and shipped inside the
        bundle as digest-covered serialized executables (see aot.py) — a
        fresh process then serves its first score without invoking XLA."""
        from .aot import abi_stamp, aot_enabled, export_bundle
        from .checkpoint import atomic_bundle_write
        manifest_extra: Dict[str, Any] = {"kind": "workflow-model"}
        do_aot = aot_enabled() if aot is None else (bool(aot) and aot_enabled())
        with atomic_bundle_write(path, overwrite=overwrite,
                                 manifest_extra=manifest_extra) as tmp:
            self._write_bundle_files(tmp)
            if do_aot:
                n = export_bundle(self, tmp)
                if n:
                    # read by atomic_bundle_write at successful exit — the
                    # stamp lands in MANIFEST only when export worked
                    manifest_extra["aot"] = {"abi": abi_stamp(),
                                             "executables": n}

    def _write_bundle_files(self, path: str) -> None:
        all_feats: Dict[str, Feature] = {}
        for rf in self.result_features:
            for f in rf.all_features():
                all_feats[f.uid] = f
        stages_json, arrays = [], {}
        for layer_i, layer in enumerate(self.fitted_dag):
            for st in layer:
                d = stage_to_json(st)
                d["layer"] = layer_i
                d["outputFeatures"] = [f.uid for f in st.output_features]
                stages_json.append(d)
                arrays.update(stage_fitted_arrays(st))
        # raw generator stages (for schema/lineage); blacklisted raw features
        # were rewired out of the DAG and have no lineage to persist
        raw_json = []
        for f in self.raw_features:
            if f.uid not in all_feats:
                continue
            st = f.origin_stage
            if isinstance(st, FeatureGeneratorStage):
                d = {"uid": st.uid, "name": st.name,
                     "type": f.kind.__name__,
                     "isResponse": f.is_response,
                     "outputFeature": f.uid}
                if st.get("aggregate_window_ms") is not None:
                    d["aggregateWindowMs"] = int(st.get("aggregate_window_ms"))
                if st.extract_source:
                    d["extractSource"] = st.extract_source
                elif st.has_custom_extract:
                    import warnings
                    warnings.warn(
                        f"feature {st.name!r} has a custom extract function "
                        "with no source text; the reloaded model will fall "
                        "back to by-name record lookup — pass "
                        "FeatureBuilder.extract(fn, source='<expr over r>') "
                        "to persist it (≙ FeatureBuilderMacros source capture)",
                        stacklevel=3)
                raw_json.append(d)
        manifest = {
            "uid": "OpWorkflowModel",
            "resultFeaturesUids": [f.uid for f in self.result_features],
            "blacklistedFeaturesUids": [f.uid for f in self.blacklisted],
            "rawFeatures": raw_json,
            "allFeatures": [feature_to_json(f) for f in all_feats.values()],
            "stages": stages_json,
            "parameters": self.parameters,
            "rawFeatureFilterResults": (
                self.rff_results.to_json() if self.rff_results is not None else None),
        }
        with open(os.path.join(path, MODEL_JSON), "w") as fh:
            json.dump(manifest, fh, indent=2, default=str)
        np.savez_compressed(os.path.join(path, PARAMS_NPZ), **arrays)
        # training-time drift baselines (lifecycle/baselines.py): the
        # retained train batch sketches into baselines.json, digest-covered
        # by the bundle manifest.  A model with no train batch (loaded and
        # re-saved) simply ships without baselines — drift monitoring then
        # reports itself disabled for that bundle.
        try:
            from .lifecycle.baselines import build_baselines
            baselines = build_baselines(self)
            if baselines is not None:
                baselines.save(path)
        except Exception as e:  # noqa: BLE001 — baselines are observability,
            #                     never a reason to fail a model save
            from .resilience import record_failure
            record_failure("workflow.save", "swallowed", e,
                           point="checkpoint.save", detail="baselines.json")
        # the data-quality schema contract (quality.py): raw feature kinds,
        # nullability and training-range hints, digest-covered like every
        # bundle file.  Serving enforces it at assembly; a failed write
        # degrades serving to a re-derived contract, never fails the save.
        try:
            from .quality import RawSchema
            RawSchema.derive(self.raw_features,
                             batch=getattr(self, "train_batch",
                                           None)).save(path)
        except Exception as e:  # noqa: BLE001 — same rule as baselines
            from .resilience import record_failure
            record_failure("workflow.save", "swallowed", e,
                           point="checkpoint.save", detail="schema.json")
        from .telemetry import active_tracer, write_telemetry_summary
        if active_tracer() is not None:
            # traced run: bundle the run's timeline summary next to the
            # model (digested into MANIFEST.json like every bundle file)
            try:
                write_telemetry_summary(os.path.join(path, "telemetry.json"))
            except Exception as e:  # noqa: BLE001 — diagnostics only
                from .resilience import record_failure
                record_failure("workflow.save", "swallowed", e,
                               point="checkpoint.save")

    @staticmethod
    def load(path: str) -> "WorkflowModel":
        """≙ OpWorkflowModelReader: stages → features → model.

        ``path`` may be a single bundle directory or a checkpoint root
        containing versioned ``ckpt-NNNNNN`` bundles — in the latter case
        the newest bundle that passes verification is loaded (corrupt ones
        are skipped with a recorded failure).  Bundles with a
        ``MANIFEST.json`` are digest- and version-verified
        (``CorruptModelError`` / ``ModelVersionError`` name the offending
        file); legacy bundles without one still load, with a warning."""
        from .checkpoint import (CorruptModelError, find_latest_valid,
                                 is_bundle_dir, verify_bundle)
        from .resilience import record_failure
        if not os.path.isdir(path):
            raise FileNotFoundError(
                f"model directory {path!r} does not exist")
        if not is_bundle_dir(path):
            path = find_latest_valid(path)
        manifest_meta = verify_bundle(path)
        if manifest_meta is None:
            import warnings
            warnings.warn(
                f"model bundle {path!r} has no MANIFEST.json (saved by a "
                "pre-checkpointing build); loading without integrity "
                "verification", stacklevel=2)
            record_failure("checkpoint", "degraded",
                           "legacy bundle without MANIFEST",
                           point="checkpoint.load", bundle=path)
        json_path = os.path.join(path, MODEL_JSON)
        if not os.path.exists(json_path):
            raise CorruptModelError(path, MODEL_JSON,
                                    "model description file is missing")
        with open(json_path) as fh:
            manifest = json.load(fh)
        npz_path = os.path.join(path, PARAMS_NPZ)
        if os.path.exists(npz_path):
            arrays = dict(np.load(npz_path, allow_pickle=False))
        elif manifest_meta is not None and \
                PARAMS_NPZ in (manifest_meta.get("files") or {}):
            raise CorruptModelError(path, PARAMS_NPZ,
                                    "fitted-parameter file is missing")
        else:
            # legacy bundles may legitimately have no arrays
            arrays = {}

        # 1. rebuild stages
        stages_by_uid: Dict[str, PipelineStage] = {}
        layers: Dict[int, List[PipelineStage]] = {}
        for d in manifest["stages"]:
            st = stage_from_json(d, arrays)
            stages_by_uid[d["uid"]] = st
            layers.setdefault(d["layer"], []).append(st)
        # raw feature generators
        raw_gens: Dict[str, FeatureGeneratorStage] = {}
        for d in manifest["rawFeatures"]:
            gen = FeatureGeneratorStage(
                name=d["name"], kind=kind_by_name(d["type"]), uid=d["uid"],
                aggregate_window_ms=d.get("aggregateWindowMs"),
                extract_source=d.get("extractSource"))
            raw_gens[d["uid"]] = gen

        # 2. rebuild features
        feats: Dict[str, Feature] = {}
        feat_json = {d["uid"]: d for d in manifest["allFeatures"]}

        def build_feature(uid: str) -> Feature:
            if uid in feats:
                return feats[uid]
            d = feat_json[uid]
            parents = tuple(build_feature(p) for p in d.get("parents", ()))
            origin = None
            if d.get("originStage"):
                origin = (stages_by_uid.get(d["originStage"])
                          or raw_gens.get(d["originStage"]))
            f = Feature(d["name"], kind_by_name(d["type"]), d["isResponse"],
                        origin, parents, uid=uid)
            feats[uid] = f
            return f

        for uid in feat_json:
            build_feature(uid)

        # 3. wire stage inputs/outputs
        for d in manifest["stages"]:
            st = stages_by_uid[d["uid"]]
            st.input_features = tuple(feats[u] for u in d["inputFeatures"])
            outs = tuple(feats[u] for u in d.get("outputFeatures", ()))
            if outs:
                st._output = outs[0] if len(outs) == 1 else outs
                for f in outs:
                    f.origin_stage = st
        for d in manifest["rawFeatures"]:
            gen = raw_gens[d["uid"]]
            f = feats[d["outputFeature"]]
            gen._output = f
            f.origin_stage = gen

        fitted_dag = [layers[i] for i in sorted(layers)]
        model = WorkflowModel(
            result_features=tuple(feats[u] for u in manifest["resultFeaturesUids"]),
            fitted_dag=fitted_dag,
            raw_features=[f for f in feats.values() if f.is_raw and
                          f.origin_stage is not None],
            blacklisted=[feats[u] for u in manifest.get("blacklistedFeaturesUids", ())
                         if u in feats],
            parameters=manifest.get("parameters") or {})
        # 4. training-time drift baselines ride along when present;
        # manifested bundles without them predate the lifecycle subsystem —
        # they load and serve fine, drift monitoring just stays off
        try:
            from .lifecycle.baselines import load_baselines
            model.baselines = load_baselines(path)
        except Exception as e:  # noqa: BLE001 — corrupt baselines degrade
            #                     to disabled monitoring, never a load error
            record_failure("checkpoint", "degraded", e,
                           point="checkpoint.load", bundle=path,
                           detail="unreadable baselines.json")
        if model.baselines is None and manifest_meta is not None:
            record_failure("checkpoint", "degraded",
                           "bundle has no baselines.json (pre-lifecycle "
                           "build); drift monitoring disabled",
                           point="checkpoint.load", bundle=path)
        # the schema contract rides along; bundles that predate it (or with
        # an unreadable schema.json) get a contract re-derived from the
        # rebuilt raw features — serving always has one to enforce
        from .quality import RawSchema
        model.raw_schema = RawSchema.for_model(model, path)
        # 5. AOT executables (formatVersion 2 bundles): deserialize straight
        # into the score program — mismatch/corruption degrades to JIT
        from .aot import install_bundle
        model.aot_executables = install_bundle(model, path)
        # 6. fleet registry: stamp the score program with its model-content
        # family so shapes the bundle did not ship (or a bundle with no AOT
        # artifacts at all — e.g. exported on another platform) still
        # install published executables instead of compiling
        from . import aot_registry
        if aot_registry.registry_enabled():
            model.score_program().registry_family = \
                aot_registry.model_family_digest(path)
        return model

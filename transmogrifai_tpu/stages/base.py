"""Stage abstractions — the TPU-native re-design of OpPipelineStage[0-4,N]
(reference: features/src/main/scala/com/salesforce/op/stages/OpPipelineStages.scala:55)
and the Unary/Binary/Sequence Transformer/Estimator bases
(features/.../stages/base/*).

Differences from the reference, by design:
  * Stages operate on *columns* (dense arrays), not rows.  A ``Transformer``
    maps a ``ColumnBatch`` to its output ``Column`` as a pure function; when
    every input column is device-resident the function is jax-traceable, so a
    whole DAG layer fuses into one XLA program (replacing
    FitStagesUtil.applyOpTransformations' bulk row map, FitStagesUtil.scala:96).
  * ``Estimator.fit`` returns a fitted ``TransformerModel``; fits are XLA
    reductions (moments, histograms, top-K) rather than Spark jobs.
  * Arity is data, not types: ``set_input(*features)`` + ``in_kinds``
    validation replaces OpPipelineStage1..4/N.
"""

from __future__ import annotations

import inspect
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Type

from ..columns import Column, ColumnBatch
from ..features import Feature, make_uid
from ..types import FeatureType


class PipelineStage:
    """Base of all stages (≙ OpPipelineStageBase).

    Subclass contract:
      * class attrs ``in_kinds`` (tuple of FeatureType classes or None for any,
        or None to skip validation) and ``out_kind``.
      * constructor params are the stage's hyper-parameters; they are captured
        automatically for serialization (≙ ctor-args-via-reflection JSON,
        OpPipelineStageReaderWriter.scala).
    """

    in_kinds: Optional[Tuple] = None
    out_kind: Type[FeatureType] = FeatureType
    num_outputs: int = 1

    def __init__(self, **params):
        self.uid = params.pop("uid", None) or make_uid(type(self).__name__)
        self._params: Dict[str, Any] = dict(params)
        self.input_features: Tuple[Feature, ...] = ()
        self._output: Optional[Any] = None

    # ---- params ------------------------------------------------------------
    def get(self, name: str, default=None):
        return self._params.get(name, default)

    def set(self, name: str, value) -> "PipelineStage":
        self._params[name] = value
        return self

    @property
    def params(self) -> Dict[str, Any]:
        return dict(self._params)

    @property
    def operation_name(self) -> str:
        return type(self).__name__

    # ---- wiring ------------------------------------------------------------
    def set_input(self, *features: Feature) -> "PipelineStage":
        self._check_input_kinds(features)
        self.input_features = tuple(features)
        self._output = None
        return self

    def _check_input_kinds(self, features: Sequence[Feature]):
        if self.in_kinds is None:
            return
        if len(self.in_kinds) != len(features) and Ellipsis not in self.in_kinds:
            raise ValueError(
                f"{self.operation_name} expects {len(self.in_kinds)} inputs, "
                f"got {len(features)}")
        for i, f in enumerate(features):
            want = (self.in_kinds[i] if i < len(self.in_kinds)
                    and self.in_kinds[i] is not Ellipsis else self.in_kinds[-2]
                    if Ellipsis in self.in_kinds else None)
            if want is not None and not issubclass(f.kind, want):
                raise TypeError(
                    f"{self.operation_name} input {i} ({f.name!r}) must be "
                    f"{want.__name__}, got {f.kind.__name__}")

    def output_name(self) -> str:
        base = "-".join(f.name for f in self.input_features[:3]) or "out"
        return f"{base}_{self.operation_name}_{self.uid[-6:]}"

    # stages that legitimately consume the label (models, sanity checker)
    # mark their outputs as predictors (≙ AllowLabelAsInput trait,
    # OpPipelineStages.scala); everything else propagates response-ness
    allow_label_as_input: bool = False

    def output_is_response(self) -> bool:
        # ≙ reference default outputIsResponse = inputs.exists(_.isResponse)
        if self.allow_label_as_input:
            return False
        return any(f.is_response for f in self.input_features)

    def make_output_features(self) -> Any:
        feats = tuple(
            Feature(name=self.output_name() if self.num_outputs == 1
                    else f"{self.output_name()}_{i}",
                    kind=self.out_kind_at(i),
                    is_response=self.output_is_response(),
                    origin_stage=self, parents=self.input_features)
            for i in range(self.num_outputs))
        return feats[0] if self.num_outputs == 1 else feats

    def out_kind_at(self, i: int) -> Type[FeatureType]:
        return self.out_kind

    def get_output(self) -> Any:
        if not self.input_features and not _is_generator(self):
            raise ValueError(f"{self.operation_name}: set_input before get_output")
        if self._output is None:
            self._output = self.make_output_features()
        return self._output

    @property
    def output_features(self) -> Tuple[Feature, ...]:
        out = self.get_output()
        return out if isinstance(out, tuple) else (out,)

    # ---- serialization -----------------------------------------------------
    def ctor_args(self) -> Dict[str, Any]:
        return dict(self._params)

    def to_json(self) -> Dict[str, Any]:
        from .serialization import stage_to_json
        return stage_to_json(self)

    def save_extra(self) -> Tuple[Dict[str, Any], Dict[str, Any]]:
        """Hook for stages with nested state (e.g. SelectedModel's wrapped
        best model): return (json_dict, named arrays) persisted alongside the
        stage. Counterpart of ``load_extra``."""
        return {}, {}

    def load_extra(self, extra_json: Dict[str, Any], arrays: Dict[str, Any]) -> None:
        pass

    def __repr__(self):
        return f"{self.operation_name}({self.uid})"


def _is_generator(stage) -> bool:
    from .generator import FeatureGeneratorStage
    return isinstance(stage, FeatureGeneratorStage)


class Transformer(PipelineStage):
    """A fitted/stateless column function (≙ OpTransformer,
    OpPipelineStages.scala:526).

    ``transform(batch)`` returns the output Column (or tuple of Columns for
    multi-output stages).  If ``is_device_op`` is True and all inputs are
    device-resident, the workflow may trace it under jit.
    """

    is_device_op: bool = True
    # stages whose transform splits into host prologue + traceable body
    # (see transform_staged) — lets ScoreProgram fuse string-input stages
    # into device segments
    supports_staging: bool = False

    def transform(self, batch: ColumnBatch) -> Any:
        raise NotImplementedError

    def transform_staged(self, batch: ColumnBatch):
        """Host-prologue / device-body split for XLA program fusion.

        Returns ``(wire, fn)`` — ``wire`` maps names to compact arrays
        computed on host (token ids, vocab codes, packed presence; the ONLY
        data the body may read besides fitted constants) and ``fn(wire) →
        Column`` is jax-traceable — or None when no staged form applies to
        this batch.  ScoreProgram uses it to pull host-input transforms
        into fused device segments, so a whole vectorizer layer compiles
        into ONE XLA program instead of one dispatch per stage (SURVEY
        §2.6 P5; ≙ applyOpTransformations' single bulk row map,
        FitStagesUtil.scala:96).  The body must derive row counts from
        wire shapes, never close over them."""
        return None

    def input_columns(self, batch: ColumnBatch) -> List[Column]:
        return [batch[f.name] for f in self.input_features]

    def transform_batch(self, batch: ColumnBatch) -> ColumnBatch:
        out = self.transform(batch)
        feats = self.output_features
        if not isinstance(out, tuple):
            out = (out,)
        assert len(out) == len(feats), (
            f"{self.operation_name} returned {len(out)} columns for "
            f"{len(feats)} outputs")
        return batch.with_columns({f.name: c for f, c in zip(feats, out)})

    def transform_row(self, row: Dict[str, FeatureType]) -> Any:
        """Row-level transform for local scoring.  Default: build a length-1
        batch and take row 0 (stages may override with a direct value path)."""
        from ..columns import column_from_values, Column as _C
        import numpy as np
        cols = {}
        for f in self.input_features:
            v = row[f.name]
            val = v.value if isinstance(v, FeatureType) else v
            cols[f.name] = column_from_values(f.kind, [val])
        batch = ColumnBatch(cols, 1)
        out = self.transform(batch)
        feats = self.output_features
        if not isinstance(out, tuple):
            out = (out,)
        res = {f.name: c.row_value(0) for f, c in zip(feats, out)}
        return res if len(res) > 1 else next(iter(res.values()))


class TransformerModel(Transformer):
    """A fitted transformer produced by an Estimator (≙ the *Model classes).

    Fitted state lives in ``self.fitted`` — a dict of numpy/jax arrays and
    plain values, checkpointable as a pytree leaf set.
    """

    def __init__(self, **params):
        fitted = params.pop("fitted", None)
        super().__init__(**params)
        self.fitted: Dict[str, Any] = fitted or {}
        self.metadata: Dict[str, Any] = {}


class ColumnWired:
    """A staged model whose host prologue is made input column by input
    column: ``column_wire(i, col)`` gives the wire entries of input ``i``
    from that column alone (numpy, no jax), and ``transform_staged(batch,
    parts)`` builds its wire from them.  So a train's prologue pool (an
    ``ops.text_profile.HostPool``) can make each column's wire as a job from
    the moment the model is fitted (``start_wires``) and the flush that
    needs them take the jobs' results (``take_wires``); everywhere else —
    scoring, serving, a small train — ``transform_staged(batch)`` makes them
    here, one after another, by the same function."""

    # (pool, the input Columns the jobs read, a future a column) while jobs
    # are out; never saved, and dropped when taken
    _wire_jobs = None

    def column_wire(self, i: int, col: Column) -> Optional[Dict[str, Any]]:
        """Input ``i``'s wire entries from ``col``, or None where the staged
        form does not apply to such a column."""
        raise NotImplementedError

    def column_wires(self, batch: ColumnBatch) -> Optional[List[Dict]]:
        """Every input's ``column_wire`` in order; None at the first that
        has none."""
        parts = []
        for i, f in enumerate(self.input_features):
            part = self.column_wire(i, batch[f.name])
            if part is None:
                return None
            parts.append(part)
        return parts

    def start_wires(self, batch: ColumnBatch, pool) -> None:
        """One job on ``pool`` an input column of ``batch``, each under the
        span ``transform.stage_wires.<class>`` on the worker that runs it.
        Nothing is started unless every input is in ``batch``."""
        from functools import partial

        cols = [batch.get(f.name) for f in self.input_features]
        if any(c is None for c in cols):
            return
        self._wire_jobs = (pool, cols, [
            pool.submit(partial(self._column_job, i, c))
            for i, c in enumerate(cols)])

    def _column_job(self, i: int, col: Column) -> Optional[Dict[str, Any]]:
        from ..telemetry import span
        with span("transform.stage_wires." + type(self).__name__,
                  column=self.input_features[i].name, rows=len(col)) as sp:
            part = self.column_wire(i, col)
            if sp is not None and part is not None:
                sp.attrs["wire_bytes"] = int(sum(
                    getattr(v, "nbytes", 0) for v in part.values()))
            return part

    def take_wires(self, batch: ColumnBatch) -> Optional[List]:
        """What the jobs ``start_wires`` left made of ``batch``'s inputs, in
        the inputs' order (joined through the pool, so that the waits count
        in ``prologue.wait_s``; what a job raised is raised here), and the
        jobs dropped from the model.  None where none were started or
        ``batch`` holds other columns under those names: the caller then
        makes the wire itself."""
        from ..telemetry import REGISTRY
        jobs, self._wire_jobs = self._wire_jobs, None
        if jobs is None:
            return None
        pool, cols, futures = jobs
        if any(batch.get(f.name) is not c
               for f, c in zip(self.input_features, cols)):
            for future in futures:
                future.cancel()
            return None
        parts = [pool.join(future) for future in futures]
        REGISTRY.counter("transform.wires_ahead").inc(
            sum(p is not None for p in parts))
        return parts


class Estimator(PipelineStage):
    """Fits on a batch to produce a TransformerModel (≙ OpEstimator).

    ``fit`` must return a model wired to the same inputs/outputs.
    """

    def fit(self, batch: ColumnBatch) -> TransformerModel:
        raise NotImplementedError

    def _finalize_model(self, model: TransformerModel) -> TransformerModel:
        model.uid = self.uid + "_model"
        model.input_features = self.input_features
        model._output = self._output  # share output feature nodes
        model.num_outputs = self.num_outputs
        return model


class LambdaTransformer(Transformer):
    """Wrap a batch-level function columns → Column (≙ Unary/Binary/...
    LambdaTransformer).  ``fn`` receives the input Columns positionally."""

    def __init__(self, fn: Callable[..., Column], out_kind: Type[FeatureType],
                 name: Optional[str] = None, is_device_op: bool = True, **params):
        super().__init__(**params)
        self.fn = fn
        self.out_kind = out_kind
        self.is_device_op = is_device_op
        self._name = name

    @property
    def operation_name(self) -> str:
        return self._name or f"Lambda[{getattr(self.fn, '__name__', 'fn')}]"

    def transform(self, batch: ColumnBatch) -> Column:
        return self.fn(*self.input_columns(batch))

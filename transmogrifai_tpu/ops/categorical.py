"""Categorical vectorizers (reference: core/.../stages/impl/feature/
OpOneHotVectorizer.scala, OpStringIndexer.scala, OpIndexToString.scala).

One-hot pivot: fit finds the top-K values per feature by count (min support),
transform maps strings → fixed vocabulary ids on host (numpy hash-map lookup),
then one-hot expansion is a pure device op.  Static shapes: the vocab is
resolved at fit time, so the transform jits (SURVEY.md §7 hard part (c)).
"""

from __future__ import annotations

from collections import Counter
from typing import Dict, List, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from ..columns import Column, ColumnBatch
from ..stages.base import (ColumnWired, Estimator, Transformer,
                           TransformerModel)
from ..types import Integral, OPVector, Real, RealNN, Text
from ..vector_meta import (NULL_INDICATOR, OTHER_INDICATOR, VectorColumnMeta,
                           VectorMeta)


def _col_strings(col: Column) -> np.ndarray:
    """Host view of a text-ish column as object array of str|None."""
    if col.is_host_object():
        return col.values
    vals = np.asarray(col.values).astype(str)
    if col.mask is not None:
        out = vals.astype(object)
        out[~np.asarray(col.mask)] = None
        return out
    return vals.astype(object)


def top_values_by_count(counts, top_k: int, min_support: int):
    """Reference top-value selection (SmartTextVectorizer.scala:97-100,
    OpOneHotVectorizer): drop values below ``min_support``, order by
    (count desc, value asc), take ``top_k``.  The returned ORDER is the
    pivot column layout — most frequent value first."""
    eligible = [(v, c) for v, c in counts.items() if c >= min_support]
    eligible.sort(key=lambda vc: (-vc[1], vc[0]))
    return [v for v, _ in eligible[:top_k]]


def encode_with_vocab(values: np.ndarray, vocab: Dict[str, int], other_id: int) -> np.ndarray:
    """strings → int ids; None→other_id+1 (null slot)."""
    null_id = other_id + 1
    out = np.full(len(values), other_id, dtype=np.int32)
    for i, v in enumerate(values):
        if v is None:
            out[i] = null_id
        else:
            out[i] = vocab.get(v, other_id)
    return out


def encode_column(col: Column, vocab: Dict[str, int], other_id: int) -> np.ndarray:
    """``encode_with_vocab`` through the cached one-pass column profile:
    the per-row dict probe collapses to one small table lookup over the
    interned codes (native/textprof.cpp)."""
    if not col.is_host_object():
        return encode_with_vocab(_col_strings(col), vocab, other_id)
    from .text_profile import column_profile
    iv = column_profile(col).values(-1)
    if not iv.uniq:    # all-null column
        return np.full(len(iv.codes), other_id + 1, np.int32)
    table = np.fromiter((vocab.get(v, other_id) for v in iv.uniq), np.int32,
                        count=len(iv.uniq))
    return np.where(iv.codes < 0, np.int32(other_id + 1),
                    table[np.maximum(iv.codes, 0)]).astype(np.int32)


class OneHotModel(ColumnWired, TransformerModel):
    out_kind = OPVector
    is_device_op = False  # host vocab lookup, then device one-hot
    supports_staging = True

    def column_wire(self, i: int, col: Column):
        """``ids{i}``: input ``i`` vocab-encoded through its cached column
        profile (uint8 where the ids fit); None for a column not held as
        strings."""
        if not col.is_host_object():
            return None
        vocab: Dict[str, int] = self.fitted["vocabs"][
            self.input_features[i].name]
        ids = encode_column(col, vocab, len(vocab))
        return {f"ids{i}": ids.astype(np.uint8) if len(vocab) + 1 < 256
                else ids}

    def transform_staged(self, batch: ColumnBatch, parts=None):
        """Host prologue: vocab-encode each feature (``column_wire``, or
        ``parts`` made by it already).  Device body: one-hot expand +
        concat — fuses into the surrounding XLA program."""
        track_other = self.get("track_other", True)
        track_nulls = self.get("track_nulls", True)
        parts = self.column_wires(batch) if parts is None else parts
        if parts is None or None in parts:
            return None
        wire = {k: v for part in parts for k, v in part.items()}
        plan = []
        for i, f in enumerate(self.input_features):
            other_id = len(self.fitted["vocabs"][f.name])
            cols = list(range(other_id))
            if track_other:
                cols.append(other_id)
            if track_nulls:
                cols.append(other_id + 1)
            plan.append((f"ids{i}", np.asarray(cols, np.int32)))
        n = len(batch)
        meta = self.fitted["meta"]

        def body(w):
            outs = []
            for key, cols in plan:
                if len(cols):
                    ids = jnp.asarray(w[key]).astype(jnp.int32)
                    outs.append((ids[:, None] == jnp.asarray(cols)[None, :]
                                 ).astype(jnp.float32))
                else:
                    outs.append(jnp.zeros((w[key].shape[0], 0), jnp.float32))
            return Column(OPVector,
                          jnp.concatenate(outs, axis=1) if outs else
                          jnp.zeros((n, 0), jnp.float32), meta=meta)

        return wire, body

    def transform(self, batch: ColumnBatch) -> Column:
        outs = []
        track_other = self.get("track_other", True)
        track_nulls = self.get("track_nulls", True)
        for f in self.input_features:
            vocab: Dict[str, int] = self.fitted["vocabs"][f.name]
            other_id = len(vocab)
            ids = encode_column(batch[f.name], vocab, other_id)
            # full encoding always has [vocab..., OTHER, NULL]; select only the
            # slots this model tracks so columns stay aligned with the meta
            cols = list(range(other_id))
            if track_other:
                cols.append(other_id)
            if track_nulls:
                cols.append(other_id + 1)
            # ship the narrowest id dtype and expand on DEVICE — a host-built
            # [N, width] f32 block costs width×4 bytes/row over the slow link
            if cols:
                wire = (ids.astype(np.uint8) if other_id + 1 < 256 else ids)
                onehot = (jnp.asarray(wire).astype(jnp.int32)[:, None]
                          == jnp.asarray(np.asarray(cols, np.int32))[None, :]
                          ).astype(jnp.float32)
            else:
                onehot = jnp.zeros((len(ids), 0), jnp.float32)
            outs.append(onehot)
        return Column(OPVector, jnp.concatenate(outs, axis=1) if outs else
                      jnp.zeros((len(batch), 0)), meta=self.fitted["meta"])


class OneHotEstimator(Estimator):
    """Pivot top-K categorical values into indicator columns with OTHER and
    null slots (≙ OpOneHotVectorizer/OneHotEstimator)."""

    out_kind = OPVector

    def __init__(self, top_k: int = 20, min_support: int = 10,
                 track_nulls: bool = True, track_other: bool = True,
                 max_pct_cardinality: float = 1.0, **params):
        super().__init__(top_k=top_k, min_support=min_support,
                         track_nulls=track_nulls, track_other=track_other,
                         max_pct_cardinality=max_pct_cardinality, **params)

    def fit(self, batch: ColumnBatch) -> TransformerModel:
        vocabs: Dict[str, Dict[str, int]] = {}
        cols_meta: List[VectorColumnMeta] = []
        top_k, min_support = self.get("top_k"), self.get("min_support")
        for f in self.input_features:
            col = batch[f.name]
            if col.is_host_object():
                from .text_profile import column_profile
                counts = column_profile(col).values(-1).value_counts()
            else:
                counts = Counter(
                    v for v in _col_strings(col) if v is not None)
            top = top_values_by_count(counts, top_k, min_support)
            vocab = {v: i for i, v in enumerate(top)}
            vocabs[f.name] = vocab
            for v in top:
                cols_meta.append(VectorColumnMeta(
                    f.name, f.kind.__name__, indicator_value=v))
            if self.get("track_other", True):
                cols_meta.append(VectorColumnMeta(
                    f.name, f.kind.__name__, indicator_value=OTHER_INDICATOR))
            if self.get("track_nulls", True):
                cols_meta.append(VectorColumnMeta(
                    f.name, f.kind.__name__, indicator_value=NULL_INDICATOR))
        meta = VectorMeta(self.output_name(), cols_meta)
        return self._finalize_model(OneHotModel(
            fitted={"vocabs": vocabs, "meta": meta}, **self.params))


class StringIndexerModel(TransformerModel):
    out_kind = RealNN
    is_device_op = False

    def transform(self, batch: ColumnBatch) -> Column:
        (f,) = self.input_features
        vocab = self.fitted["vocab"]
        strings = _col_strings(batch[f.name])
        handle = self.get("handle_invalid", "noFilter")
        unseen = len(vocab)
        ids = np.zeros(len(strings), np.int64)
        mask = np.ones(len(strings), bool)
        for i, v in enumerate(strings):
            if v is None or v not in vocab:
                if handle == "error" and v is not None:
                    raise ValueError(f"unseen label {v!r}")
                ids[i] = unseen
            else:
                ids[i] = vocab[v]
        return Column(RealNN, ids.astype(np.float32))


class StringIndexer(Estimator):
    """Text → ordinal index by descending frequency (≙ OpStringIndexer;
    'NoFilter' variant maps unseen to an extra bucket)."""

    out_kind = RealNN

    def __init__(self, handle_invalid: str = "noFilter", **params):
        super().__init__(handle_invalid=handle_invalid, **params)

    def fit(self, batch: ColumnBatch) -> TransformerModel:
        (f,) = self.input_features
        strings = _col_strings(batch[f.name])
        counts = Counter(v for v in strings if v is not None)
        # Spark orders by freq desc, then value asc
        ordered = sorted(counts.items(), key=lambda kv: (-kv[1], kv[0]))
        vocab = {v: i for i, (v, _) in enumerate(ordered)}
        model = StringIndexerModel(fitted={"vocab": vocab}, **self.params)
        model.metadata["labels"] = [v for v, _ in ordered]
        return self._finalize_model(model)


class IndexToString(Transformer):
    """Ordinal index → original label (≙ OpIndexToString)."""

    out_kind = Text
    is_device_op = False

    def __init__(self, labels: Sequence[str], **params):
        super().__init__(labels=list(labels), **params)

    def transform(self, batch: ColumnBatch) -> Column:
        (f,) = self.input_features
        labels = self.get("labels")
        ids = np.asarray(batch[f.name].values).astype(int)
        vals = np.array([labels[i] if 0 <= i < len(labels) else None
                         for i in ids], dtype=object)
        return Column(Text, vals)

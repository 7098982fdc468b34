"""Per-type map vectorizers — the specialized family the generic MapVectorizer
does not cover (reference: core/.../stages/impl/feature/
SmartTextMapVectorizer.scala:61, TextMapPivotVectorizer.scala,
MultiPickListMapVectorizer.scala, DateMapToUnitCircleVectorizer.scala,
GeolocationMapVectorizer.scala, TextMapNullEstimator.scala,
TextMapLenEstimator.scala).

All are sequence estimators: they accept any number of map features and emit
one combined OPVector.  Fit discovers each map's key set host-side (strings
never reach the device); transform lowers to a dense [N, D] block whose width
is fixed at fit time, so the scoring path stays static-shape for XLA.
"""

from __future__ import annotations

from collections import Counter
from typing import Any, Dict, List, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from .categorical import top_values_by_count
from .map_profile import key_order, string_map_expansion
from ..columns import Column, ColumnBatch, indicator_2d
from ..stages.base import ColumnWired, Estimator, TransformerModel
from ..types import OPVector
from ..vector_meta import (NULL_INDICATOR, OTHER_INDICATOR, VectorColumnMeta,
                           VectorMeta)
from .dates import _period_fraction
from .text import TextStats, hash_tokens_to_counts, tokenize_text


def _map_values(col) -> List[Dict[str, Any]]:
    return [v if isinstance(v, dict) else {} for v in col.values]


def _discover_keys(maps: List[Dict[str, Any]], max_keys: int,
                   allow_list=None, block_list=None) -> List[str]:
    counts: Counter = Counter()
    for m in maps:
        counts.update(m.keys())
    return _cut_keys(counts, max_keys, allow_list, block_list)


def _cut_keys(in_dict: Dict[str, int], max_keys: int, allow_list=None,
              block_list=None) -> List[str]:
    """The first ``max_keys`` keys of ``map_profile.key_order`` (rows that
    hold the key, descending, then the key), laid out by key."""
    block = set(block_list or ())
    return sorted(k for k in key_order(in_dict)[:max_keys]
                  if (allow_list is None or k in allow_list)
                  and k not in block)


class TextMapStats:
    """Per-key TextStats monoid (≙ SmartTextMapVectorizer.TextMapStats)."""

    def __init__(self, key_stats: Optional[Dict[str, TextStats]] = None):
        self.key_stats: Dict[str, TextStats] = key_stats or {}

    def combine(self, other: "TextMapStats") -> "TextMapStats":
        out = dict(self.key_stats)
        for k, s in other.key_stats.items():
            out[k] = out[k].combine(s) if k in out else s
        return TextMapStats(out)

    @staticmethod
    def of_maps(maps: List[Dict[str, Any]], max_card: int) -> "TextMapStats":
        ks: Dict[str, TextStats] = {}
        for m in maps:
            for k, v in m.items():
                st = ks.setdefault(k, TextStats())
                if v is None:
                    continue
                s = str(v)
                if len(st.value_counts) <= max_card:
                    st.value_counts[s] += 1
                st.length_counts[len(s)] += 1
        return TextMapStats(ks)


# ---------------------------------------------------------------------------
# SmartTextMapVectorizer
# ---------------------------------------------------------------------------

class SmartTextMapVectorizerModel(TransformerModel):
    out_kind = OPVector
    is_device_op = False

    def transform(self, batch: ColumnBatch) -> Column:
        n = len(batch)
        num_hashes = self.get("num_hashes")
        track_nulls = self.get("track_nulls", True)
        blocks: List[np.ndarray] = []
        for f in self.input_features:
            maps = _map_values(batch[f.name])
            per_key = self.fitted["per_feature"][f.name]
            for k in per_key["keys"]:
                strat = per_key["strategies"][k]
                if strat == "pivot":
                    vocab = per_key["vocabs"][k]
                    width = len(vocab) + 2  # OTHER + null
                    col = np.zeros((n, width), np.float32)
                    for i, m in enumerate(maps):
                        v = m.get(k)
                        if v is None:
                            col[i, width - 1] = 1.0
                        else:
                            col[i, vocab.get(str(v), len(vocab))] = 1.0
                    blocks.append(col)
                elif strat == "ignore":
                    if track_nulls:
                        blocks.append(indicator_2d(
                            m.get(k) is None for m in maps))
                else:  # hash
                    token_lists = [tokenize_text(None if m.get(k) is None
                                                 else str(m.get(k)))
                                   for m in maps]
                    h = hash_tokens_to_counts(token_lists, num_hashes)
                    if track_nulls:
                        nulls = indicator_2d(m.get(k) is None for m in maps)
                        h = np.concatenate([h, nulls], axis=1)
                    blocks.append(h)
        arr = (np.concatenate(blocks, axis=1) if blocks
               else np.zeros((n, 0), np.float32))
        return Column(OPVector, jnp.asarray(arr), meta=self.fitted["meta"])


class SmartTextMapVectorizer(Estimator):
    """Cardinality-adaptive per-key text-map vectorization
    (≙ SmartTextMapVectorizer.scala:61): per map key, a TextStats pass decides
    pivot one-hot (≤ max_cardinality uniques), ignore (≤1 unique), or
    tokenize+hash."""

    out_kind = OPVector

    def __init__(self, max_cardinality: int = 30, top_k: int = 20,
                 min_support: int = 10, num_hashes: int = 512,
                 track_nulls: bool = True, max_keys: int = 100, **params):
        super().__init__(max_cardinality=max_cardinality, top_k=top_k,
                         min_support=min_support, num_hashes=num_hashes,
                         track_nulls=track_nulls, max_keys=max_keys, **params)

    def fit(self, batch: ColumnBatch) -> TransformerModel:
        max_card = self.get("max_cardinality")
        cols_meta: List[VectorColumnMeta] = []
        per_feature: Dict[str, Dict[str, Any]] = {}
        for f in self.input_features:
            maps = _map_values(batch[f.name])
            keys = _discover_keys(maps, self.get("max_keys", 100))
            stats = TextMapStats.of_maps(maps, max_card)
            strategies: Dict[str, str] = {}
            vocabs: Dict[str, Dict[str, int]] = {}
            kindname = f.kind.__name__
            for k in keys:
                st = stats.key_stats.get(k, TextStats())
                if st.cardinality <= max_card:
                    # the reference pivots even single-value keys
                    # (SmartTextVectorizer.scala:92-96)
                    strategies[k] = "pivot"
                    top = top_values_by_count(st.value_counts,
                                              self.get("top_k"),
                                              self.get("min_support"))
                    vocab = {v: i for i, v in enumerate(top)}
                    vocabs[k] = vocab
                    for v in top:
                        cols_meta.append(VectorColumnMeta(
                            f.name, kindname, grouping=k, indicator_value=v))
                    cols_meta.append(VectorColumnMeta(
                        f.name, kindname, grouping=k,
                        indicator_value=OTHER_INDICATOR))
                    cols_meta.append(VectorColumnMeta(
                        f.name, kindname, grouping=k,
                        indicator_value=NULL_INDICATOR))
                elif st.length_std_dev < self.get("min_length_std_dev", 0.0):
                    # ID-like key: high cardinality, near-constant value
                    # length (off by default, like the scalar SmartText)
                    strategies[k] = "ignore"
                    if self.get("track_nulls", True):
                        cols_meta.append(VectorColumnMeta(
                            f.name, kindname, grouping=k,
                            indicator_value=NULL_INDICATOR))
                else:
                    strategies[k] = "hash"
                    for j in range(self.get("num_hashes")):
                        cols_meta.append(VectorColumnMeta(
                            f.name, kindname, grouping=k,
                            descriptor_value=f"hash_{j}"))
                    if self.get("track_nulls", True):
                        cols_meta.append(VectorColumnMeta(
                            f.name, kindname, grouping=k,
                            indicator_value=NULL_INDICATOR))
            per_feature[f.name] = {"keys": keys, "strategies": strategies,
                                   "vocabs": vocabs}
        meta = VectorMeta(self.output_name(), cols_meta)
        model = SmartTextMapVectorizerModel(
            fitted={"per_feature": per_feature, "meta": meta}, **self.params)
        model.metadata["strategies"] = {
            f: dict(d["strategies"]) for f, d in per_feature.items()}
        return self._finalize_model(model)


# ---------------------------------------------------------------------------
# TextMapPivotVectorizer
# ---------------------------------------------------------------------------

class TextMapPivotVectorizerModel(ColumnWired, TransformerModel):
    out_kind = OPVector
    is_device_op = False     # host ids from the map's expansion, then device
    supports_staging = True  # one-hots fused into the transform program

    def column_wire(self, i: int, col: Column):
        """``ids{i}``: uint8 ``[N, K]`` (int32 past 255 slots) of input
        ``i``'s K fitted keys, a key's vocabulary position, OTHER or null
        in every row, read from the column's cached expansion
        (``map_profile.string_map_expansion``); None for a column not held
        as objects."""
        if not col.is_host_object():
            return None
        f = self.input_features[i]
        per_key = self.fitted["per_feature"][f.name]
        exp = string_map_expansion(col, f.name)
        at = exp.key_index()
        widest = max([len(v) + 2 for v in per_key["vocabs"].values()] + [0])
        dtype = np.uint8 if widest <= 256 else np.int32
        ids = np.empty((len(col), len(per_key["keys"])), dtype)
        for j, k in enumerate(per_key["keys"]):
            vocab = per_key["vocabs"][k]
            other = len(vocab)
            if k not in at:
                ids[:, j] = other + 1
                continue
            # a distinct value's slot, then null last: a row's id -1 takes it
            slot = np.fromiter(
                (vocab.get(u, other) for u in exp.uniq[at[k]]), dtype,
                count=len(exp.uniq[at[k]]))
            ids[:, j] = np.append(slot, dtype(other + 1))[exp.ids[at[k]]]
        return {f"ids{i}": ids}

    def transform_staged(self, batch: ColumnBatch, parts=None):
        """Host prologue: every input's ``column_wire`` (or ``parts`` made
        by it already).  Device body: each key's one-hot over its vocabulary,
        OTHER and null, concatenated in the fitted order."""
        parts = self.column_wires(batch) if parts is None else parts
        if parts is None or None in parts:
            return None
        wire = {k: v for part in parts for k, v in part.items()}
        plan = []
        for i, f in enumerate(self.input_features):
            per_key = self.fitted["per_feature"][f.name]
            plan.append((f"ids{i}", [len(per_key["vocabs"][k]) + 2
                                     for k in per_key["keys"]]))
        n = len(batch)
        meta = self.fitted["meta"]

        def body(w):
            outs = []
            for name, widths in plan:
                ids = jnp.asarray(w[name]).astype(jnp.int32)
                for j, width in enumerate(widths):
                    outs.append((ids[:, j][:, None] == jnp.arange(
                        width, dtype=jnp.int32)[None, :]).astype(jnp.float32))
            return Column(OPVector, jnp.concatenate(outs, axis=1) if outs
                          else jnp.zeros((n, 0), jnp.float32), meta=meta)

        return wire, body

    def transform(self, batch: ColumnBatch) -> Column:
        wire, body = self.transform_staged(batch)
        return body({k: jnp.asarray(v) for k, v in wire.items()})


class TextMapPivotVectorizer(Estimator):
    """Always-pivot per-key text-map vectorizer (≙ TextMapPivotVectorizer.scala):
    every key gets top-K one-hot + OTHER + null, no hashing fallback.  Keys
    and value counts come from the column's one expansion
    (``map_profile.string_map_expansion``), which the model's wire reads
    too."""

    out_kind = OPVector

    def __init__(self, top_k: int = 20, min_support: int = 10,
                 track_nulls: bool = True, max_keys: int = 100, **params):
        super().__init__(top_k=top_k, min_support=min_support,
                         track_nulls=track_nulls, max_keys=max_keys, **params)

    def fit(self, batch: ColumnBatch) -> TransformerModel:
        cols_meta: List[VectorColumnMeta] = []
        per_feature: Dict[str, Dict[str, Any]] = {}
        for f in self.input_features:
            exp = string_map_expansion(batch[f.name], f.name)
            at = exp.key_index()
            keys = _cut_keys(dict(zip(exp.keys, exp.in_dict.tolist())),
                             self.get("max_keys", 100))
            vocabs: Dict[str, Dict[str, int]] = {}
            kindname = f.kind.__name__
            for k in keys:
                top = top_values_by_count(exp.value_counts(at[k]),
                                          self.get("top_k"),
                                          self.get("min_support"))
                vocab = {v: i for i, v in enumerate(top)}
                vocabs[k] = vocab
                for v in top:
                    cols_meta.append(VectorColumnMeta(
                        f.name, kindname, grouping=k, indicator_value=v))
                cols_meta.append(VectorColumnMeta(
                    f.name, kindname, grouping=k,
                    indicator_value=OTHER_INDICATOR))
                cols_meta.append(VectorColumnMeta(
                    f.name, kindname, grouping=k,
                    indicator_value=NULL_INDICATOR))
            per_feature[f.name] = {"keys": keys, "vocabs": vocabs}
        meta = VectorMeta(self.output_name(), cols_meta)
        return self._finalize_model(TextMapPivotVectorizerModel(
            fitted={"per_feature": per_feature, "meta": meta}, **self.params))


# ---------------------------------------------------------------------------
# MultiPickListMapVectorizer
# ---------------------------------------------------------------------------

class MultiPickListMapVectorizerModel(TransformerModel):
    out_kind = OPVector
    is_device_op = False

    def transform(self, batch: ColumnBatch) -> Column:
        n = len(batch)
        blocks: List[np.ndarray] = []
        for f in self.input_features:
            maps = _map_values(batch[f.name])
            per_key = self.fitted["per_feature"][f.name]
            for k in per_key["keys"]:
                vocab = per_key["vocabs"][k]
                width = len(vocab) + 2  # OTHER + null
                col = np.zeros((n, width), np.float32)
                for i, m in enumerate(maps):
                    s = m.get(k)
                    if not s:
                        col[i, width - 1] = 1.0
                        continue
                    for v in s:
                        j = vocab.get(str(v))
                        if j is not None:
                            col[i, j] = 1.0
                        else:
                            col[i, len(vocab)] = 1.0
                blocks.append(col)
        arr = (np.concatenate(blocks, axis=1) if blocks
               else np.zeros((n, 0), np.float32))
        return Column(OPVector, jnp.asarray(arr), meta=self.fitted["meta"])


class MultiPickListMapVectorizer(Estimator):
    """Per-key multi-hot over set values (≙ MultiPickListMapVectorizer.scala)."""

    out_kind = OPVector

    def __init__(self, top_k: int = 20, min_support: int = 10,
                 track_nulls: bool = True, max_keys: int = 100, **params):
        super().__init__(top_k=top_k, min_support=min_support,
                         track_nulls=track_nulls, max_keys=max_keys, **params)

    def fit(self, batch: ColumnBatch) -> TransformerModel:
        cols_meta: List[VectorColumnMeta] = []
        per_feature: Dict[str, Dict[str, Any]] = {}
        for f in self.input_features:
            maps = _map_values(batch[f.name])
            keys = _discover_keys(maps, self.get("max_keys", 100))
            vocabs: Dict[str, Dict[str, int]] = {}
            kindname = f.kind.__name__
            for k in keys:
                cnt: Counter = Counter()
                for m in maps:
                    for v in (m.get(k) or ()):
                        cnt[str(v)] += 1
                top = top_values_by_count(cnt, self.get("top_k"),
                                          self.get("min_support"))
                vocab = {v: i for i, v in enumerate(top)}
                vocabs[k] = vocab
                for v in top:
                    cols_meta.append(VectorColumnMeta(
                        f.name, kindname, grouping=k, indicator_value=v))
                cols_meta.append(VectorColumnMeta(
                    f.name, kindname, grouping=k,
                    indicator_value=OTHER_INDICATOR))
                cols_meta.append(VectorColumnMeta(
                    f.name, kindname, grouping=k,
                    indicator_value=NULL_INDICATOR))
            per_feature[f.name] = {"keys": keys, "vocabs": vocabs}
        meta = VectorMeta(self.output_name(), cols_meta)
        return self._finalize_model(MultiPickListMapVectorizerModel(
            fitted={"per_feature": per_feature, "meta": meta}, **self.params))


# ---------------------------------------------------------------------------
# DateMapToUnitCircleVectorizer
# ---------------------------------------------------------------------------

class DateMapToUnitCircleVectorizerModel(TransformerModel):
    out_kind = OPVector
    is_device_op = False

    def transform(self, batch: ColumnBatch) -> Column:
        n = len(batch)
        period = self.get("time_period", "HourOfDay")
        blocks: List[np.ndarray] = []
        for f in self.input_features:
            maps = _map_values(batch[f.name])
            for k in self.fitted["per_feature"][f.name]:
                vals = np.array([float(m.get(k) or 0) for m in maps])
                present = np.array([m.get(k) is not None for m in maps])
                frac = np.asarray(_period_fraction(vals, period))
                ang = 2 * np.pi * frac
                blocks.append(np.stack(
                    [np.where(present, np.sin(ang), 0.0),
                     np.where(present, np.cos(ang), 0.0)],
                    axis=1).astype(np.float32))
        arr = (np.concatenate(blocks, axis=1) if blocks
               else np.zeros((n, 0), np.float32))
        return Column(OPVector, jnp.asarray(arr), meta=self.fitted["meta"])


class DateMapToUnitCircleVectorizer(Estimator):
    """Per-key date → (sin, cos) unit-circle encoding
    (≙ DateMapToUnitCircleVectorizer.scala; default period HourOfDay)."""

    out_kind = OPVector

    def __init__(self, time_period: str = "HourOfDay", max_keys: int = 100,
                 **params):
        super().__init__(time_period=time_period, max_keys=max_keys, **params)

    def fit(self, batch: ColumnBatch) -> TransformerModel:
        cols_meta: List[VectorColumnMeta] = []
        per_feature: Dict[str, List[str]] = {}
        period = self.get("time_period", "HourOfDay")
        for f in self.input_features:
            maps = _map_values(batch[f.name])
            keys = _discover_keys(maps, self.get("max_keys", 100))
            per_feature[f.name] = keys
            for k in keys:
                for fn_name in ("sin", "cos"):
                    cols_meta.append(VectorColumnMeta(
                        f.name, f.kind.__name__, grouping=k,
                        descriptor_value=f"{fn_name}({period})"))
        meta = VectorMeta(self.output_name(), cols_meta)
        return self._finalize_model(DateMapToUnitCircleVectorizerModel(
            fitted={"per_feature": per_feature, "meta": meta}, **self.params))


# ---------------------------------------------------------------------------
# GeolocationMapVectorizer
# ---------------------------------------------------------------------------

class GeolocationMapVectorizerModel(TransformerModel):
    out_kind = OPVector
    is_device_op = False

    def transform(self, batch: ColumnBatch) -> Column:
        n = len(batch)
        track_nulls = self.get("track_nulls", True)
        blocks: List[np.ndarray] = []
        for f in self.input_features:
            maps = _map_values(batch[f.name])
            per_key = self.fitted["per_feature"][f.name]
            for k in per_key["keys"]:
                fill = np.asarray(per_key["fills"][k], np.float32)
                col = np.zeros((n, 4 if track_nulls else 3), np.float32)
                for i, m in enumerate(maps):
                    v = m.get(k)
                    if v:
                        col[i, :3] = np.asarray(list(v)[:3], np.float32)
                    else:
                        col[i, :3] = fill
                        if track_nulls:
                            col[i, 3] = 1.0
                blocks.append(col)
        arr = (np.concatenate(blocks, axis=1) if blocks
               else np.zeros((n, 0), np.float32))
        return Column(OPVector, jnp.asarray(arr), meta=self.fitted["meta"])


class GeolocationMapVectorizer(Estimator):
    """Per-key (lat, lon, accuracy) with mean fill + null indicator
    (≙ GeolocationMapVectorizer.scala)."""

    out_kind = OPVector

    def __init__(self, track_nulls: bool = True, max_keys: int = 100,
                 default_location: Optional[Sequence[float]] = None, **params):
        super().__init__(track_nulls=track_nulls, max_keys=max_keys,
                         default_location=default_location, **params)

    def fit(self, batch: ColumnBatch) -> TransformerModel:
        cols_meta: List[VectorColumnMeta] = []
        per_feature: Dict[str, Dict[str, Any]] = {}
        default = self.get("default_location")
        for f in self.input_features:
            maps = _map_values(batch[f.name])
            keys = _discover_keys(maps, self.get("max_keys", 100))
            fills: Dict[str, np.ndarray] = {}
            kindname = f.kind.__name__
            for k in keys:
                vals = [list(m[k])[:3] for m in maps if m.get(k)]
                # plain float lists: fitted nested dicts must stay JSON-safe
                if default is not None:
                    fills[k] = [float(x) for x in list(default)[:3]]
                else:
                    fills[k] = ([float(x) for x in
                                 np.mean(np.asarray(vals, np.float32), axis=0)]
                                if vals else [0.0, 0.0, 0.0])
                for d in ("lat", "lon", "accuracy"):
                    cols_meta.append(VectorColumnMeta(
                        f.name, kindname, grouping=k, descriptor_value=d))
                if self.get("track_nulls", True):
                    cols_meta.append(VectorColumnMeta(
                        f.name, kindname, grouping=k,
                        indicator_value=NULL_INDICATOR))
            per_feature[f.name] = {"keys": keys, "fills": fills}
        meta = VectorMeta(self.output_name(), cols_meta)
        return self._finalize_model(GeolocationMapVectorizerModel(
            fitted={"per_feature": per_feature, "meta": meta}, **self.params))


# ---------------------------------------------------------------------------
# TextMapNullEstimator / TextMapLenEstimator
# ---------------------------------------------------------------------------

class TextMapNullModel(TransformerModel):
    out_kind = OPVector
    is_device_op = False

    def transform(self, batch: ColumnBatch) -> Column:
        n = len(batch)
        blocks: List[np.ndarray] = []
        for f in self.input_features:
            maps = _map_values(batch[f.name])
            for k in self.fitted["per_feature"][f.name]:
                blocks.append(indicator_2d(m.get(k) is None for m in maps))
        arr = (np.concatenate(blocks, axis=1) if blocks
               else np.zeros((n, 0), np.float32))
        return Column(OPVector, jnp.asarray(arr), meta=self.fitted["meta"])


class TextMapNullEstimator(Estimator):
    """Per-key null indicators only (≙ TextMapNullEstimator.scala)."""

    out_kind = OPVector

    def __init__(self, max_keys: int = 100, **params):
        super().__init__(max_keys=max_keys, **params)

    def fit(self, batch: ColumnBatch) -> TransformerModel:
        cols_meta: List[VectorColumnMeta] = []
        per_feature: Dict[str, List[str]] = {}
        for f in self.input_features:
            maps = _map_values(batch[f.name])
            keys = _discover_keys(maps, self.get("max_keys", 100))
            per_feature[f.name] = keys
            for k in keys:
                cols_meta.append(VectorColumnMeta(
                    f.name, f.kind.__name__, grouping=k,
                    indicator_value=NULL_INDICATOR))
        meta = VectorMeta(self.output_name(), cols_meta)
        return self._finalize_model(TextMapNullModel(
            fitted={"per_feature": per_feature, "meta": meta}, **self.params))


class TextMapLenModel(TransformerModel):
    out_kind = OPVector
    is_device_op = False

    def transform(self, batch: ColumnBatch) -> Column:
        n = len(batch)
        blocks: List[np.ndarray] = []
        for f in self.input_features:
            maps = _map_values(batch[f.name])
            for k in self.fitted["per_feature"][f.name]:
                lens = np.fromiter(
                    (0.0 if m.get(k) is None else float(len(str(m[k])))
                     for m in maps), np.float32)
                blocks.append(lens.reshape(-1, 1))
        arr = (np.concatenate(blocks, axis=1) if blocks
               else np.zeros((n, 0), np.float32))
        return Column(OPVector, jnp.asarray(arr), meta=self.fitted["meta"])


class TextMapLenEstimator(Estimator):
    """Per-key text value lengths (≙ TextMapLenEstimator.scala)."""

    out_kind = OPVector

    def __init__(self, max_keys: int = 100, **params):
        super().__init__(max_keys=max_keys, **params)

    def fit(self, batch: ColumnBatch) -> TransformerModel:
        cols_meta: List[VectorColumnMeta] = []
        per_feature: Dict[str, List[str]] = {}
        for f in self.input_features:
            maps = _map_values(batch[f.name])
            keys = _discover_keys(maps, self.get("max_keys", 100))
            per_feature[f.name] = keys
            for k in keys:
                cols_meta.append(VectorColumnMeta(
                    f.name, f.kind.__name__, grouping=k,
                    descriptor_value="textLen"))
        meta = VectorMeta(self.output_name(), cols_meta)
        return self._finalize_model(TextMapLenModel(
            fitted={"per_feature": per_feature, "meta": meta}, **self.params))

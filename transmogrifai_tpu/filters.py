"""RawFeatureFilter — pre-training data hygiene (reference:
core/src/main/scala/com/salesforce/op/filters/RawFeatureFilter.scala:137-486,
FeatureDistribution.scala:58 with fillRate:94, jsDivergence,
relativeFillRate/Ratio; results in RawFeatureFilterResults.scala).

Computes per-raw-feature fill rates and value histograms on the training data
(and optionally a scoring set), then drops features whose fill rate is too
low, whose train/score fill rates diverge, whose distributions diverge
(Jensen-Shannon), or whose null pattern correlates with the label.  Histogram
reductions are vectorised; text features hash into bins like the reference.
"""

from __future__ import annotations

from concurrent.futures import Future
from dataclasses import dataclass, field
from functools import partial
from typing import (Any, Callable, Dict, Iterator, List, Optional, Sequence,
                    Tuple)

import numpy as np

import zlib

from .columns import Column, ColumnBatch
from .features import Feature
from .telemetry import REGISTRY, span
from .types import is_map_kind, is_numeric_kind, is_text_kind


@dataclass
class FeatureDistribution:
    """≙ FeatureDistribution.scala:58."""

    name: str
    key: Optional[str] = None           # map key (map features expand per key)
    count: int = 0
    nulls: int = 0
    distribution: np.ndarray = field(default_factory=lambda: np.zeros(0))
    summary: Dict[str, float] = field(default_factory=dict)

    @property
    def fill_rate(self) -> float:
        """≙ fillRate:94."""
        return 0.0 if self.count == 0 else 1.0 - self.nulls / self.count

    def relative_fill_rate(self, other: "FeatureDistribution") -> float:
        return abs(self.fill_rate - other.fill_rate)

    def relative_fill_ratio(self, other: "FeatureDistribution") -> float:
        a, b = self.fill_rate, other.fill_rate
        mn, mx = min(a, b), max(a, b)
        return float("inf") if mn == 0 else mx / mn

    def js_divergence(self, other: "FeatureDistribution") -> float:
        """Jensen-Shannon divergence of the binned distributions."""
        p, q = self.distribution, other.distribution
        if p.size == 0 or q.size == 0 or p.size != q.size:
            return 0.0
        ps, qs = p.sum(), q.sum()
        if ps == 0 or qs == 0:
            return 0.0
        p = p / ps
        q = q / qs
        m = 0.5 * (p + q)

        def kl(a, b):
            mask = a > 0
            return float(np.sum(a[mask] * np.log2(a[mask] / b[mask])))

        return 0.5 * kl(p, m) + 0.5 * kl(q, m)

    def to_json(self) -> Dict[str, Any]:
        return {"name": self.name, "key": self.key, "count": self.count,
                "nulls": self.nulls, "fillRate": self.fill_rate,
                "distribution": self.distribution.tolist(),
                "summary": self.summary}

    @staticmethod
    def from_json(d: Dict[str, Any]) -> "FeatureDistribution":
        # fillRate is derived from count/nulls and not read back
        return FeatureDistribution(
            d["name"], key=d.get("key"), count=int(d.get("count", 0)),
            nulls=int(d.get("nulls", 0)),
            distribution=np.asarray(d.get("distribution") or [],
                                    dtype=np.float64),
            summary={k: float(v)
                     for k, v in (d.get("summary") or {}).items()})


@dataclass
class FeatureSketch:
    """Mergeable per-feature distribution sketch for sharded / streamed data
    (≙ StreamingHistogram.java + FeatureDistribution's monoid `reduce`):
    numeric values go into a Ben-Haim/Tom-Tov streaming histogram (merges
    without a shared binning), text hashes into fixed bins (trivially
    mergeable)."""

    name: str
    key: Optional[str] = None
    count: int = 0
    nulls: int = 0
    histogram: Optional[Any] = None      # StreamingHistogram (numeric kinds)
    text_counts: Optional[np.ndarray] = None  # [text_bins] (text kinds)

    @property
    def fill_rate(self) -> float:
        """≙ FeatureDistribution.fill_rate (count = rows seen, nulls ⊆)."""
        return 0.0 if self.count == 0 else 1.0 - self.nulls / self.count

    def merge(self, other: "FeatureSketch") -> "FeatureSketch":
        assert (self.name, self.key) == (other.name, other.key)
        hist = None
        if self.histogram is not None or other.histogram is not None:
            from .utils.stats import StreamingHistogram
            a = self.histogram or StreamingHistogram()
            b = other.histogram or StreamingHistogram()
            hist = a.merge(b)
        tc = None
        if self.text_counts is not None or other.text_counts is not None:
            za = self.text_counts if self.text_counts is not None else 0.0
            zb = other.text_counts if other.text_counts is not None else 0.0
            tc = za + zb
        return FeatureSketch(self.name, self.key, self.count + other.count,
                             self.nulls + other.nulls, hist, tc)

    def to_json(self) -> Dict[str, Any]:
        return {"name": self.name, "key": self.key, "count": int(self.count),
                "nulls": int(self.nulls),
                "histogram": (self.histogram.to_json()
                              if self.histogram is not None else None),
                "textCounts": ([float(x) for x in self.text_counts]
                               if self.text_counts is not None else None)}

    @staticmethod
    def from_json(d: Dict[str, Any]) -> "FeatureSketch":
        hist = None
        if d.get("histogram") is not None:
            from .utils.stats import StreamingHistogram
            hist = StreamingHistogram.from_json(d["histogram"])
        tc = (np.asarray(d["textCounts"], dtype=np.float64)
              if d.get("textCounts") is not None else None)
        return FeatureSketch(d["name"], d.get("key"), int(d.get("count", 0)),
                             int(d.get("nulls", 0)), hist, tc)

    def to_distribution(self, bins: int) -> FeatureDistribution:
        if self.text_counts is not None:
            dist = np.asarray(self.text_counts, dtype=np.float64)
        elif self.histogram is not None:
            dist = self.histogram.to_fixed_bins(bins)
        else:
            dist = np.zeros(bins)
        return FeatureDistribution(self.name, key=self.key, count=self.count,
                                   nulls=self.nulls, distribution=dist)


def compute_sketches(raw_features: Sequence[Feature], batch: ColumnBatch,
                     max_bins: int = 64, text_bins: int = 100
                     ) -> Dict[Tuple[str, Optional[str]], FeatureSketch]:
    """Per-feature mergeable sketches over one shard/micro-batch.  Combine
    shards with ``merge_sketches``; finalize with ``FeatureSketch
    .to_distribution`` — distributions then combine across shards/streams the
    way the reference merges StreamingHistograms (StreamingHistogram.java:269)."""
    from .utils.stats import StreamingHistogram

    out: Dict[Tuple[str, Optional[str]], FeatureSketch] = {}
    for f in raw_features:
        col = batch.get(f.name)
        if col is None:
            continue
        n = len(col)
        kind = f.kind
        if is_map_kind(kind):
            keys = sorted({k for m in col.values if m for k in m})
            for k in keys:
                vals = [m.get(k) if m else None for m in col.values]
                out[(f.name, k)] = _sketch_of(
                    f.name, k, vals, kind, max_bins, text_bins)
            # whole-map presence sketch — also the per-shard row count that
            # merge_sketches uses to pad keys absent from a shard
            out[(f.name, None)] = FeatureSketch(
                f.name, None, n,
                int(sum(1 for m in col.values if not m)),
                text_counts=np.zeros(text_bins))
            continue
        if _list_held_as_array(col):
            present = _value_presence(col)
            out[(f.name, None)] = FeatureSketch(
                f.name, None, n, int((~present).sum()),
                text_counts=_array_item_bins(np.asarray(col.values), present,
                                             text_bins))
            continue
        vals = (list(col.values) if col.is_host_object()
                else np.asarray(col.values))
        if not col.is_host_object() and col.mask is not None:
            vals = np.where(np.asarray(col.mask), vals, np.nan)
        out[(f.name, None)] = _sketch_of(
            f.name, None, vals, kind, max_bins, text_bins)
    return out


def _sketch_of(name, key, vals, kind, max_bins, text_bins) -> FeatureSketch:
    from .types import map_value_kind
    from .utils.stats import StreamingHistogram

    n = len(vals)
    vkind = map_value_kind(kind) if is_map_kind(kind) else kind
    if isinstance(vals, list) or not is_numeric_kind(vkind):
        _python_rows().inc(n)
    if is_numeric_kind(vkind):
        arr = np.asarray(
            [float(v) if isinstance(v, (int, float, np.floating, np.integer))
             and not isinstance(v, bool) else
             (1.0 if v is True else 0.0 if v is False else np.nan)
             for v in vals] if isinstance(vals, list) else vals,
            dtype=np.float64)
        finite = np.isfinite(arr)
        hist = StreamingHistogram(max_bins).update_all(arr[finite])
        return FeatureSketch(name, key, n, int((~finite).sum()),
                             histogram=hist)
    counts = np.zeros(text_bins)
    nulls = 0
    for v in vals:
        # same emptiness convention as _value_presence: None/""/[]/{} are null
        if v is None or (isinstance(v, float) and np.isnan(v)) or (
                hasattr(v, "__len__") and len(v) == 0):
            nulls += 1
            continue
        for item in (v if isinstance(v, (list, set, frozenset, tuple))
                     else [v]):
            counts[_stable_text_bin(item, text_bins)] += 1.0
    return FeatureSketch(name, key, n, nulls, text_counts=counts)


def merge_sketches(a: Dict, b: Dict) -> Dict:
    """Monoid merge of two shards' sketch maps.  A map key absent from one
    shard is padded with that shard's row count as nulls (taken from the
    feature's whole-map sketch) so per-key counts/fill rates stay exact."""
    def _pad(sk: FeatureSketch, side: Dict) -> FeatureSketch:
        if sk.key is None:
            return sk
        base = side.get((sk.name, None))
        if base is None or base.count == 0:
            return sk
        missing = FeatureSketch(sk.name, sk.key, base.count, base.count)
        if sk.histogram is not None:
            from .utils.stats import StreamingHistogram
            missing.histogram = StreamingHistogram(sk.histogram.max_bins)
        if sk.text_counts is not None:
            missing.text_counts = np.zeros_like(sk.text_counts)
        return sk.merge(missing)

    out: Dict = {}
    for k in set(a) | set(b):
        if k in a and k in b:
            out[k] = a[k].merge(b[k])
        elif k in a:
            out[k] = _pad(a[k], b)
        else:
            out[k] = _pad(b[k], a)
    return out


_HIST_FNS: Dict[int, Any] = {}


def _python_rows():
    """Counter of the rows a distribution or a sketch walked in Python."""
    return REGISTRY.counter("rff.python_rows")


def _sharded_numeric_hist(mesh, arr, keep, lo, hi, bins: int) -> np.ndarray:
    """np.histogram over [lo, hi] with the COUNT REDUCTION sharded over the
    mesh 'data' axis (XLA inserts the psum).  Bin indices are computed on
    host in float64 with np.histogram's own edge semantics, so the
    distributions are bit-identical with the mesh on or off — a float32
    device binning would move edge-adjacent large-magnitude values (epoch
    timestamps) across bins and make drop decisions mesh-dependent."""
    import jax
    import jax.numpy as jnp

    from .parallel.mesh import data_sharding

    edges = np.linspace(lo, hi, bins + 1)
    idx = np.searchsorted(edges, arr, side="right") - 1
    idx = np.where(arr == hi, bins - 1, idx)        # last bin is inclusive
    valid = keep & (idx >= 0) & (idx < bins)
    idx = np.where(valid, idx, 0).astype(np.int32)

    fn = _HIST_FNS.get(bins)
    if fn is None:
        @jax.jit
        def fn(i, m):
            with jax.named_scope("rff.distributions"):
                oh = (i[:, None] == jnp.arange(bins)[None, :]
                      ).astype(jnp.float32)
                return jnp.sum(oh * m.astype(jnp.float32)[:, None], axis=0)
        _HIST_FNS[bins] = fn
    i = jax.device_put(jnp.asarray(idx), data_sharding(mesh, 1))
    m = jax.device_put(jnp.asarray(valid), data_sharding(mesh, 1))
    return np.asarray(fn(i, m)).astype(np.float64)


# Rows a block of the numpy passes over a column.  A pass over a whole column
# of millions of rows makes temporaries of tens of MB each (the float64 copy,
# the finite ones, the kept values), which the allocator maps and the kernel
# faults in anew for every pass; a block's stay in cache and are reused.
# Who still walks by blocks: the range and the histogram of a numeric column
# that ``_numdist`` cannot read as it is stored (no toolchain, another dtype)
# and the label's sums in ``_CentredLabel``.  The native passes do not: they
# read a column in place, one value at a time, and make no temporary.
_BLOCK_ROWS = 1 << 16


def _numdist(values, present):
    """native/numdist.cpp where it can pass over ``values`` (and the mask
    ``present``) as they are stored, with no GIL held; None where it cannot
    — no toolchain, or a column that is not a 1-D float64, float32, int64
    or int32 array — and the numpy passes by blocks stand."""
    if not (isinstance(values, np.ndarray) and values.ndim == 1
            and values.dtype.kind in "fi" and values.dtype.itemsize in (4, 8)
            and values.dtype.isnative and values.flags.aligned):
        return None
    if present is not None and not (
            isinstance(present, np.ndarray) and present.dtype == np.bool_
            and present.shape == values.shape):
        return None
    from .native import load
    return load("numdist")


def _finite_blocks(values: np.ndarray, present: Optional[np.ndarray]
                   ) -> Iterator[np.ndarray]:
    """The present, finite values of a numeric column as float64, a block
    of rows at a time: the numpy form of what native/numdist.cpp reads in
    place, and the plain statement its tests compare it with."""
    for s in range(0, len(values), _BLOCK_ROWS):
        x = np.asarray(values[s:s + _BLOCK_ROWS], dtype=np.float64)
        keep = np.isfinite(x)
        if present is not None:
            keep &= present[s:s + _BLOCK_ROWS]
        yield x[keep]


def _finite_range(values: np.ndarray, present: Optional[np.ndarray]
                  ) -> Optional[Tuple[float, float]]:
    """(min, max) of the present, finite values; None where there is none."""
    native = _numdist(values, present)
    if native is not None:
        return native.range(values, present)
    lo, hi = np.inf, -np.inf
    for x in _finite_blocks(values, present):
        if x.size:
            lo, hi = min(lo, x.min()), max(hi, x.max())
    return (float(lo), float(hi)) if lo <= hi else None


def _finite_histogram(values: np.ndarray, present: Optional[np.ndarray],
                      lo: float, hi: float, bins: int) -> np.ndarray:
    """``np.histogram``'s counts of the present, finite values over ``bins``
    equal bins of [lo, hi], as float64: by native/numdist.cpp against the
    edges numpy itself would make (so the counts are numpy's exactly), or by
    numpy over blocks of rows."""
    native = _numdist(values, present) if 0 < hi - lo < np.inf else None
    if native is not None:
        REGISTRY.counter("rff.native_columns").inc()
        return native.histogram(values, present,
                                np.linspace(lo, hi, bins + 1))
    REGISTRY.counter("rff.numpy_columns").inc()
    h = np.zeros(bins)
    for x in _finite_blocks(values, present):
        h += np.histogram(x, bins=bins, range=(lo, hi))[0]
    return h


def _array_item_bins(values: np.ndarray, present: np.ndarray,
                     text_bins: int) -> np.ndarray:
    """Hashed-item histogram of a list-valued column held as an array
    ([N, K], or [N]): the bins the row-by-row branch of ``_histogram_of``
    gives the same rows as lists, from each DISTINCT value hashed once and
    weighted by its count — no Python over rows.  Floats are told apart
    by their bits, as their strings tell 0.0 from -0.0."""
    h = np.zeros(text_bins)
    values = values.reshape(len(values), -1)
    for j in range(values.shape[1]):
        col = np.ascontiguousarray(values[:, j][present])
        bits = col.view(f"u{col.itemsize}") if col.dtype.kind == "f" else col
        uniq, counts = np.unique(bits, return_counts=True)
        uniq = uniq.view(col.dtype)
        bins = np.fromiter((_stable_text_bin(u, text_bins)
                            for u in uniq.tolist()), np.int64, len(uniq))
        h += np.bincount(bins, weights=counts, minlength=text_bins)
    return h


def _list_held_as_array(col: Column) -> bool:
    """A column of lists (a Geolocation's triples) stored as one array."""
    return not col.is_host_object() and not is_numeric_kind(col.kind) \
        and not isinstance(col.values, dict)


def _stable_text_bin(item, text_bins: int) -> int:
    """Process-stable hash bin (crc32, not Python's randomized hash()) so
    sketches/distributions built in different processes stay mergeable and
    train-vs-score comparable."""
    return zlib.crc32(str(item).encode("utf-8")) % text_bins


def _value_presence(col: Column) -> np.ndarray:
    if col.is_host_object():
        if is_text_kind(col.kind):
            # cached one-pass profile (ops/text_profile.py) — the same scan
            # the vectorizers reuse, so RFF costs no extra column walk
            from .ops.text_profile import column_profile
            return column_profile(col).presence
        exp = _expansion(col)
        if exp is not None:
            return exp.nonempty
        # None, and anything of length 0 ("", [], {}, an empty set), is null
        return np.array([v is not None and not (
            hasattr(v, "__len__") and len(v) == 0) for v in col.values],
            dtype=bool)
    if col.mask is not None:
        return np.asarray(col.mask)
    return np.ones(len(col), dtype=bool)


def _expansion(col: Column):
    """The cached one-walk expansion of a string-valued map or a set column
    (``ops.map_profile``) where it says what the row walk would, else
    None: RawFeatureFilter then walks the rows."""
    from .ops.map_profile import (is_set_kind, is_string_map, set_expansion,
                                  string_map_expansion)
    if not col.is_host_object():
        return None
    if is_string_map(col.kind):
        exp = string_map_expansion(col)
    elif is_set_kind(col.kind):
        exp = set_expansion(col)
    else:
        return None
    return exp if exp.inexact == 0 else None


def _value_bins(uniq: Sequence[str], weights: np.ndarray,
                text_bins: int) -> np.ndarray:
    """The hashed-value histogram of the row walk's text branch from each
    distinct value, binned once and weighted by its count."""
    bins = np.fromiter((_stable_text_bin(u, text_bins) for u in uniq),
                       np.int64, count=len(uniq))
    return np.bincount(bins, weights=np.asarray(weights, np.float64),
                       minlength=text_bins)


def numeric_ranges(feature: Feature, col: Column
                   ) -> Dict[Optional[str], Tuple[float, float]]:
    """Per-(feature[, map-key]) numeric (min, max) — the reference's Summary
    pass.  Train + score ranges merge so BOTH sides bin identically; without a
    shared range a pure mean shift produces near-identical histogram shapes
    and JS divergence never fires."""
    kind = feature.kind
    out: Dict[Optional[str], Tuple[float, float]] = {}

    def rng_of(vals):
        arr = np.asarray(
            [float(v) if isinstance(v, (int, float, np.integer, np.floating))
             and not isinstance(v, bool) else np.nan for v in vals],
            dtype=np.float64)
        arr = arr[np.isfinite(arr)]
        if not arr.size:
            return None
        return float(arr.min()), float(arr.max())

    if is_map_kind(kind):
        from .types import map_value_kind
        if not is_numeric_kind(map_value_kind(kind)):
            return out
        from .ops.map_profile import map_expansion
        exp = map_expansion(col)
        if exp is not None:
            # cached one-pass expansion (bool-free: bools fall through to
            # the Python path below, where rng_of treats them as NaN)
            for j, k in enumerate(exp.keys):
                v = exp.vals[:, j]
                v = v[np.isfinite(v)]
                if v.size:
                    out[k] = (float(v.min()), float(v.max()))
            return out
        keys = sorted({k for m in col.values if m for k in m})
        for k in keys:
            r = rng_of([m.get(k) if m else None for m in col.values])
            if r is not None:
                out[k] = r
        return out
    if is_numeric_kind(kind) and not col.is_host_object():
        r = _finite_range(np.asarray(col.values), None if col.mask is None
                          else np.asarray(col.mask))
        if r is not None:
            out[None] = r
    elif is_numeric_kind(kind):
        r = rng_of(list(col.values))
        if r is not None:
            out[None] = r
    return out


def merge_ranges(a: Dict, b: Dict) -> Dict:
    out = dict(a)
    for k, (lo, hi) in b.items():
        if k in out:
            out[k] = (min(out[k][0], lo), max(out[k][1], hi))
        else:
            out[k] = (lo, hi)
    return out


def compute_distribution(feature: Feature, col: Column, bins: int,
                         text_bins: int,
                         ranges: Optional[Dict] = None
                         ) -> List[FeatureDistribution]:
    """Per-feature histogram(s).  Maps expand per key (≙ PreparedFeatures).
    ``ranges`` pins the numeric binning range per key (shared train/score
    Summary)."""
    n = len(col)
    present = _value_presence(col)
    out = []
    kind = feature.kind
    ranges = ranges or {}
    if is_map_kind(kind):
        from .types import map_value_kind
        vkind = map_value_kind(kind)
        exp = None
        if is_numeric_kind(vkind):
            from .ops.map_profile import map_expansion
            exp = map_expansion(col)
        if exp is not None:
            idx = exp.key_index()
            for k in sorted(exp.keys):
                j = idx[k]
                sub_present = exp.present[:, j]
                dist = _histogram_of(exp.vals[:, j], sub_present, vkind,
                                     bins, text_bins,
                                     value_range=ranges.get(k))
                out.append(FeatureDistribution(
                    feature.name, key=k, count=n,
                    nulls=int((~sub_present).sum()), distribution=dist))
            if not exp.keys:
                out.append(FeatureDistribution(feature.name, count=n, nulls=n,
                                               distribution=np.zeros(bins)))
            return out
        exp = _expansion(col)
        if exp is not None:
            # a string-valued map from its one expansion: a key's values
            # binned once each, weighted by their rows
            idx = exp.key_index()
            for k in sorted(exp.keys):
                j = idx[k]
                out.append(FeatureDistribution(
                    feature.name, key=k, count=n,
                    nulls=n - int(exp.counts[j].sum()),
                    distribution=_value_bins(exp.uniq[j], exp.counts[j],
                                             text_bins)))
            if not exp.keys:
                out.append(FeatureDistribution(feature.name, count=n, nulls=n,
                                               distribution=np.zeros(bins)))
            return out
        keys = sorted({k for m in col.values if m for k in m})
        for k in keys:
            vals = [m.get(k) if m else None for m in col.values]
            sub_present = np.array([v is not None for v in vals])
            # histogram by the map's VALUE kind: a RealMap's values are
            # numeric and must bin numerically, not hash as text
            dist = _histogram_of(vals, sub_present, vkind, bins, text_bins,
                                 value_range=ranges.get(k))
            out.append(FeatureDistribution(
                feature.name, key=k, count=n,
                nulls=int((~sub_present).sum()), distribution=dist))
        if not keys:
            out.append(FeatureDistribution(feature.name, count=n, nulls=n,
                                           distribution=np.zeros(bins)))
        return out
    if is_text_kind(kind) and col.is_host_object():
        # hashed whole-value bins straight from the cached column profile
        from .ops.text_profile import column_profile
        dist = column_profile(col).crc_hist(text_bins)
    elif _list_held_as_array(col):
        dist = _array_item_bins(np.asarray(col.values), present, text_bins)
    elif (exp := _expansion(col)) is not None:
        # a set column from its one expansion: every item, duplicates too
        dist = _value_bins(exp.uniq, exp.items, text_bins)
    else:
        dist = _histogram_of(list(np.asarray(col.values, dtype=object))
                             if col.is_host_object() else np.asarray(col.values),
                             present, kind, bins, text_bins,
                             value_range=ranges.get(None))
    out.append(FeatureDistribution(feature.name, count=n,
                                   nulls=int((~present).sum()),
                                   distribution=dist))
    return out


def _histogram_of(vals, present: np.ndarray, kind, bins: int,
                  text_bins: int, value_range=None) -> np.ndarray:
    if is_numeric_kind(kind):
        if isinstance(vals, list):
            _python_rows().inc(len(vals))
            vals = np.asarray(
                [float(v) if (v is not None and not isinstance(v, str))
                 else np.nan for v in vals], dtype=np.float64)
        if value_range is None:
            value_range = _finite_range(vals, present)
            if value_range is None:
                return np.zeros(bins)
        lo, hi = value_range
        if lo == hi:
            hi = lo + 1.0
        # multi-device: the binning reduction runs as one GSPMD program with
        # rows sharded over 'data' (≙ RawFeatureFilter's executor-side
        # FeatureDistribution reduce, RawFeatureFilter.scala:137)
        from .parallel.mesh import maybe_data_mesh
        mesh = maybe_data_mesh(int(vals.size))
        if mesh is not None:
            arr = np.asarray(vals, dtype=np.float64)
            keep = present & np.isfinite(arr)
            if not keep.any():
                return np.zeros(bins)
            return _sharded_numeric_hist(mesh, arr, keep, lo, hi, bins)
        return _finite_histogram(vals, present, lo, hi, bins)
    # text-ish: hash values into text_bins (≙ text hashed into bins)
    _python_rows().inc(len(vals))
    h = np.zeros(text_bins)
    for v, p in zip(vals, present):
        if not p or v is None:
            continue
        for item in (v if isinstance(v, (list, set, tuple)) else [v]):
            h[_stable_text_bin(item, text_bins)] += 1.0
    return h


class _CentredLabel:
    """The label about its mean, made once a filter: what the correlation
    of every feature's presence with the label shares."""

    def __init__(self, values):
        y = np.asarray(values, dtype=np.float64)
        self.centred = y - y.mean() if y.size else y
        self.sum_squares = float(np.einsum("i,i->", self.centred,
                                           self.centred))

    def correlation_with(self, presence: np.ndarray) -> float:
        """Pearson correlation of a 0/1 vector with the label; NaN where
        either is constant.  For a 0/1 vector the centred products add up
        to the sum of the centred label over the rows that are 1, and its
        own squares to n·p·(1 − p)."""
        presence = np.asarray(presence, dtype=bool)
        n, ones = presence.size, int(np.count_nonzero(presence))
        if not self.sum_squares > 0 or ones in (0, n):
            return float("nan")
        over_ones = sum(float(self.centred[s:s + _BLOCK_ROWS][
            presence[s:s + _BLOCK_ROWS]].sum())
            for s in range(0, n, _BLOCK_ROWS))
        return over_ones / np.sqrt(ones * (1.0 - ones / n)
                                   * self.sum_squares)


@dataclass
class RawFeatureFilterResults:
    """≙ RawFeatureFilterResults."""

    train_distributions: List[FeatureDistribution] = field(default_factory=list)
    score_distributions: List[FeatureDistribution] = field(default_factory=list)
    dropped: List[str] = field(default_factory=list)
    dropped_map_keys: Dict[str, List[str]] = field(default_factory=dict)
    reasons: Dict[str, List[str]] = field(default_factory=dict)

    def to_json(self) -> Dict[str, Any]:
        return {
            "rawFeatureDistributions": [d.to_json() for d in self.train_distributions],
            "scoringFeatureDistributions": [d.to_json() for d in self.score_distributions],
            "featuresDropped": self.dropped,
            "mapKeysDropped": self.dropped_map_keys,
            "exclusionReasons": self.reasons,
        }


_COUNTERS = ("rff.python_rows", "rff.native_columns", "rff.numpy_columns")


def _predictors(batch, score_batch, raw_features
                ) -> Iterator[Tuple[Feature, Column, Optional[Column]]]:
    """(feature, its train column, its score column or None) of every
    predictor in ``batch``, in the features' order."""
    for f in raw_features:
        if f.name in batch and not f.is_response:
            yield f, batch[f.name], (
                score_batch[f.name] if score_batch is not None
                and f.name in score_batch else None)


def _passes_without_gil(f: Feature, col: Column) -> bool:
    """Whether ``col``'s distribution holds no GIL while it passes over
    rows, so that it may run beside other host work: an array (a numeric
    column's native range and histogram, a list-valued array's
    ``np.unique``), strings (the native walk, or its cached profile), a
    string-valued map or a set column (the native walk of
    ``ops.map_profile``, or its cached expansion).  Numeric maps and other
    Python objects are walked in Python (``rff.python_rows``) and stay with
    the thread that filters."""
    from .ops.map_profile import is_set_kind, is_string_map
    if col.is_host_object():
        return (is_text_kind(f.kind) or is_string_map(f.kind)
                or is_set_kind(f.kind))
    return not isinstance(col.values, dict)


class _StartedDistributions:
    """What ``RawFeatureFilter.start_distributions`` left on a pool (an
    ``ops.text_profile.HostPool``) for one ``filter_batch``: the batches
    and a future a feature."""

    def __init__(self, batch, score_batch, pool):
        self.batch, self.score_batch = batch, score_batch
        self.jobs: Dict[str, Future] = {}
        self._pool = pool
        self._held: Dict[str, Callable[[], Any]] = {}

    def add(self, name: str, job: Callable[[], Any], held: bool) -> None:
        if held:
            self._held[name] = job
        else:
            self.jobs[name] = self._pool.submit(job)

    def walked(self, name: str) -> None:
        """The caller has profiled the string column ``name``."""
        job = self._held.pop(name, None)
        if job is not None:
            self.jobs[name] = self._pool.submit(job)

    def join(self, job: Future):
        """What ``job`` returned (or raised), the wait the pool's to count."""
        return self._pool.join(job)

    def cancel(self) -> None:
        for job in self.jobs.values():
            job.cancel()


class RawFeatureFilter:
    """≙ RawFeatureFilter.scala: configurable thresholds, train + optional
    scoring reader."""

    def __init__(self, min_fill_rate: float = 0.001,
                 max_fill_difference: float = 0.9,
                 max_fill_ratio_diff: float = 20.0,
                 max_js_divergence: float = 0.9,
                 max_correlation: float = 0.95,
                 bins: int = 100, text_bins: int = 255,
                 score_reader=None, protected_features: Sequence[str] = ()):
        self.min_fill_rate = float(min_fill_rate)
        self.max_fill_difference = float(max_fill_difference)
        self.max_fill_ratio_diff = float(max_fill_ratio_diff)
        self.max_js_divergence = float(max_js_divergence)
        self.max_correlation = float(max_correlation)
        self.bins = int(bins)
        self.text_bins = int(text_bins)
        self.score_reader = score_reader
        self.protected = set(protected_features)
        self._started: Optional[_StartedDistributions] = None

    def start_distributions(self, batch: ColumnBatch,
                            raw_features: Sequence[Feature], pool,
                            walked: Sequence[str] = ()
                            ) -> "_StartedDistributions":
        """Start on ``pool`` (an ``ops.text_profile.HostPool``) what
        ``filter_batch(batch, raw_features)`` will join: the score batch
        read, and one job a predictor whose distribution passes over rows
        with no GIL held (``_passes_without_gil``).  A column named in
        ``walked`` is a string, map or set column the caller walks itself:
        its job waits until the caller says ``walked(name)`` on what is
        returned, so that the column is walked once.  Every other predictor
        — and a
        ``walked`` one never released — is computed by ``filter_batch``
        itself, as it is when nothing was started."""
        from .native import load
        from .parallel.mesh import maybe_data_mesh
        for module in ("mapprof", "numdist", "textprof"):
            load(module)        # built and imported once, before the threads
        score_batch = self._score_batch(raw_features)
        started = _StartedDistributions(batch, score_batch, pool)
        on_mesh = any(maybe_data_mesh(len(b)) is not None
                      for b in (batch, score_batch) if b is not None)
        for f, col, score_col in _predictors(batch, score_batch,
                                             raw_features):
            if not all(_passes_without_gil(f, c)
                       for c in (col, score_col) if c is not None):
                continue
            # a histogram binned on the mesh is a device program: its
            # dispatch stays with the thread that joins, fed by the job's range
            started.add(f.name, partial(
                self._feature_distributions, f, col, score_col,
                histograms=not (on_mesh and is_numeric_kind(f.kind))),
                held=f.name in walked)
        self._started = started
        return started

    def _score_batch(self, raw_features) -> Optional[ColumnBatch]:
        if self.score_reader is None:
            return None
        return self.score_reader.generate_batch(
            [f for f in raw_features if not f.is_response])

    def filter_batch(self, batch: ColumnBatch, raw_features: Sequence[Feature]
                     ) -> Tuple[ColumnBatch, List[Feature], RawFeatureFilterResults]:
        """≙ generateFilteredRaw:486: returns (clean batch, dropped features,
        results).  What ``start_distributions`` started for this ``batch``
        is joined here, where the rules first need it; the rest, or all of
        it where nothing was started, is computed here by the same
        function."""
        started, self._started = self._started, None
        if started is not None and started.batch is not batch:
            started.cancel()
            started = None
        results = RawFeatureFilterResults()
        label: Optional[_CentredLabel] = None
        label_name = next((f.name for f in raw_features if f.is_response), None)
        if label_name and label_name in batch:
            label = _CentredLabel(batch[label_name].values)

        score_batch = (started.score_batch if started is not None
                       else self._score_batch(raw_features))
        for name in _COUNTERS:      # at 0 where nothing counts
            REGISTRY.counter(name)
        with span("rff.distributions", features=len(raw_features)):
            per_feature = self._distributions(batch, score_batch,
                                              raw_features, results, started)
        with span("rff.decide", features=len(per_feature)):
            for f, fdists, sdists in per_feature:
                self._decide(f, fdists, sdists, batch, label, results)
            return self._clean(batch, raw_features, results)

    def _distributions(self, batch, score_batch, raw_features, results,
                       started=None):
        """[(feature, its train distributions, its score distributions)] of
        every predictor in ``batch``, in the features' order; both lists
        also go into ``results``.  A predictor ``started`` has a job for is
        waited for (what the job raised is raised here), any other is
        computed on this thread."""
        per_feature = []
        for f, col, score_col in _predictors(batch, score_batch,
                                             raw_features):
            job = started.jobs.get(f.name) if started is not None else None
            ranges, dists = (
                self._feature_distributions(f, col, score_col) if job is None
                else started.join(job))     # raises what the job raised
            if dists is None:           # binned on the mesh, from this thread
                _, dists = self._feature_distributions(f, col, score_col,
                                                       ranges)
            fdists, sdists = dists
            results.train_distributions.extend(fdists)
            results.score_distributions.extend(sdists)
            per_feature.append((f, fdists, sdists))
        return per_feature

    def _feature_distributions(self, f, col, score_col, ranges=None,
                               histograms=True):
        """(ranges, (train distributions, score distributions)) of one
        predictor: the one function a distribution is computed by, whether a
        pool's worker runs it or the thread that filters.  ``ranges``: the
        Summary ranges where a job has found them; ``histograms`` False:
        the ranges alone, None for the rest."""
        with span("rff.feature", feature=f.name, kind=f.kind.__name__,
                  rows=len(col)):
            if ranges is None:
                # shared Summary range over BOTH readers so train and score
                # bin identically (≙ Summary.scala) — a mean shift must move
                # mass to different bins, or JS divergence can never see it
                ranges = numeric_ranges(f, col)
                if score_col is not None:
                    ranges = merge_ranges(ranges,
                                          numeric_ranges(f, score_col))
            if not histograms:
                return ranges, None
            fdists = compute_distribution(f, col, self.bins, self.text_bins,
                                          ranges=ranges)
            sdists: List[FeatureDistribution] = []
            if score_col is not None:
                sdists = compute_distribution(f, score_col, self.bins,
                                              self.text_bins, ranges=ranges)
            return ranges, (fdists, sdists)

    def _decide(self, f, fdists, sdists, batch, label, results) -> None:
        """Record in ``results`` whether ``f`` (or some of its map keys) is
        dropped, and why."""
        if f.name in self.protected:
            return

        reasons: List[str] = []
        # minimum fill rate (≙ minFill)
        if all(d.fill_rate < self.min_fill_rate for d in fdists):
            reasons.append(
                f"fill rate {fdists[0].fill_rate:.4f} < minFillRate")
        # null-label correlation (leakage through missingness)
        if label is not None:
            corr = label.correlation_with(_value_presence(batch[f.name]))
            if np.isfinite(corr) and abs(corr) > self.max_correlation:
                reasons.append(
                    f"null-label correlation {corr:.4f} > max")

        # train-vs-score distribution shift, compared PER KEY for maps
        # (≙ getFeaturesToExclude pairing distributions by (name, key));
        # shifted map keys drop individually, the whole feature drops
        # only when every key fails
        sd_by_key = {d.key: d for d in sdists}
        shifted_keys: List[str] = []
        for d in fdists:
            sd = sd_by_key.get(d.key)
            if sd is None:
                continue
            kreasons = []
            if d.relative_fill_rate(sd) > self.max_fill_difference:
                kreasons.append("fill rate difference train/score too large")
            if d.relative_fill_ratio(sd) > self.max_fill_ratio_diff:
                kreasons.append("fill rate ratio train/score too large")
            js = d.js_divergence(sd)
            if js > self.max_js_divergence:
                kreasons.append(f"JS divergence {js:.4f} > max")
            if not kreasons:
                continue
            if d.key is None:
                reasons.extend(kreasons)
            else:
                shifted_keys.append(d.key)
                results.reasons[f"{f.name}[{d.key}]"] = kreasons
        all_keys = [d.key for d in fdists if d.key is not None]
        if shifted_keys:
            results.dropped_map_keys[f.name] = shifted_keys
            if len(shifted_keys) == len(all_keys):
                reasons.append("every map key failed train/score checks")
        if reasons:
            results.dropped.append(f.name)
            results.reasons[f.name] = reasons + \
                results.reasons.get(f.name, [])

    def _clean(self, batch, raw_features, results):
        """``batch`` without the dropped features and map keys."""
        dropped = set(results.dropped)
        dropped_features = [f for f in raw_features if f.name in dropped]
        clean = batch.drop(results.dropped)
        # strip dropped keys out of surviving map columns (≙ generateFilteredRaw
        # cleaning map values of excluded keys)
        for name, keys in results.dropped_map_keys.items():
            if name in dropped or name not in clean:
                continue
            kset = set(keys)
            col = clean[name]
            vals = np.empty(len(col), dtype=object)
            for i, m in enumerate(col.values):
                vals[i] = ({k: v for k, v in m.items() if k not in kset}
                           if m else m)
            clean = clean.with_column(name, Column(col.kind, vals))
        return clean, dropped_features, results

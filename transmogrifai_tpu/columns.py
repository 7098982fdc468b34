"""Columnar data representation — the TPU-native replacement for Spark
DataFrames (reference layer 0).

A ``ColumnBatch`` is an ordered mapping of feature name → ``Column``.  Numeric
columns live as dense device arrays plus a presence mask (``Option[T]`` →
(values, mask), cf. SURVEY.md §7.1); strings/lists/maps live host-side as numpy
object arrays until a fitted vectorizer lowers them to device arrays.  All
device-side stage transforms are pure functions over these arrays, so the whole
transform DAG jits into one XLA program.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, List, Optional, Sequence, Type

import numpy as np

from .types import (
    Binary, Date, DateList, DateTime, DateTimeList, FeatureType, Geolocation,
    Integral, MultiPickList, OPList, OPMap, OPNumeric, OPSet, OPVector,
    Prediction, Real, RealNN, Text, TextList, is_map_kind, is_numeric_kind,
    is_text_kind,
)
from .vector_meta import VectorMeta


_DEVICE_CACHE: Dict[int, Any] = {}   # id(host arr) → (weakref, device arr, lossless)
_DEVICE_CACHE_BYTES = [0]
# HBM the cache may pin (FIFO-evicted beyond this; override via env)
_DEVICE_CACHE_CAP = int(__import__("os").environ.get(
    "TRANSMOGRIFAI_DEVICE_CACHE_BYTES", 2 << 30))

# feature matrices at/above this element count store as bf16 on accelerators
_MATRIX_BF16_ELEMS = 1 << 26       # 64M elements = 256 MB in f32


def shed_device_cache() -> int:
    """Release every cached host→device transfer — the RSS watchdog's
    soft-watermark shedder.  The cache only saves re-transfers (columns are
    immutable; a dropped entry re-ships over the link on next use), so
    under host memory pressure its device bytes AND the host references
    pinning the source arrays go first.  Returns the bytes released."""
    released = _DEVICE_CACHE_BYTES[0]
    _DEVICE_CACHE.clear()
    _DEVICE_CACHE_BYTES[0] = 0
    return max(0, int(released))


def device_matrix(values):
    """Feature matrix for device compute: device-resident f32/bf16 arrays
    pass through untouched (bf16 is STORAGE — every consumer accumulates in
    f32, with the operand converts fused into its matmuls); anything else
    transfers via the f32 wire path."""
    import jax
    import jax.numpy as jnp

    if isinstance(values, jax.Array) and values.dtype in (jnp.float32,
                                                          jnp.bfloat16):
        return values
    return to_device_f32(values)


def feature_matrix_dtype(n_elems: int):
    """Storage dtype for a device-resident feature matrix of ``n_elems``.

    On accelerators, large matrices store as bf16 — the TPU-native
    storage/compute split (bf16 storage, f32 MXU accumulation): counts and
    one-hot indicators are exactly representable, real-valued features were
    already bf16-quantized by the host wire, and every downstream matmul
    upcasts its operands into f32 accumulation.  Halving residency is what
    lets two copies of a wide transmogrified matrix (raw + checked) coexist
    with the CV working set on a 16 GB chip.  Opt out with
    TRANSMOGRIFAI_MATRIX_F32=1; CPU backends always store f32."""
    import os

    import jax
    import jax.numpy as jnp

    if (n_elems >= _MATRIX_BF16_ELEMS
            and jax.default_backend() != "cpu"
            and os.environ.get("TRANSMOGRIFAI_MATRIX_F32") != "1"):
        return jnp.bfloat16
    return jnp.float32


def pack_bits(arr) -> np.ndarray:
    """Boolean/0-1 array → packed uint8 wire, a bit a row, in bit PLANES:
    with W = ceil(n / 8) words, row r is bit ``r // W`` of word ``r % W``.
    The device unpack is then eight shift+masks of the whole wire laid end
    to end (``[8, W]`` flattened, the words along the lanes).  (Eight
    consecutive rows a word would need a ``[W, 8] → [n]`` reshape on the
    device, which the chip tiles to 128 lanes: 403 MB of temporaries and,
    fused into its consumers, four minutes of compile at six million rows;
    PERF.md §6.)"""
    bits = np.asarray(arr).astype(bool).reshape(-1)
    words = -(-bits.size // 8)
    if bits.size != 8 * words:
        bits = np.concatenate([bits, np.zeros(8 * words - bits.size, bool)])
    return np.packbits(bits.reshape(8, words), axis=0,
                       bitorder="little").reshape(-1)


def unpack_bits_device(words, n: int, shape=None):
    """Device-side inverse of ``pack_bits`` → float32 0/1 array of ``n``
    elements (optionally reshaped).  Traceable."""
    import jax.numpy as jnp

    planes = (words.astype(jnp.int32)[None, :]
              >> jnp.arange(8, dtype=jnp.int32)[:, None]) & 1      # [8, W]
    flat = planes.reshape(-1)[:n].astype(jnp.float32)
    return flat if shape is None else flat.reshape(shape)


def to_device_f32(values, exact: bool = False) -> Any:
    """Host→device transfer of real-valued bulk data for compute.

    On accelerator backends the WIRE format is bf16 — half the bytes over the
    host link — while everything downstream accumulates in
    f32 on device (the standard TPU bf16-storage/f32-accumulate discipline).
    Exact for 0/1 masks and small integers; float features lose bits beyond
    bf16's 8-bit mantissa, which is noise relative to feature measurement
    error.  Opt out with TRANSMOGRIFAI_WIRE_F32=1.  CPU backends (tests,
    goldens) always transfer exact f32.

    ``exact=True`` marks value-critical data (sample/fold weights, labels):
    the bf16 wire is used only when it is verified lossless for the actual
    array contents (0/1 fold masks, small integers); otherwise the transfer
    falls back to exact f32.

    Large arrays are cached (weakref-keyed on the host buffer) so a column
    used by several stages — vectorizer fit, compiled transform, evaluate —
    ships over the link ONCE per batch rather than once per consumer.
    Columns are treated as immutable throughout the framework; in-place
    mutation of a transferred array is not supported.
    """
    import os
    import weakref

    import jax
    import jax.numpy as jnp

    if isinstance(values, jax.Array):
        return values if values.dtype == jnp.float32 else values.astype(
            jnp.float32)
    arr = np.asarray(values)
    big = arr.size >= (1 << 16) and arr.dtype in (np.float32, np.float64)
    if big:
        ent = _DEVICE_CACHE.get(id(arr))
        # a cached bf16-wire entry only satisfies an exact request when the
        # transfer was verified lossless at insertion time
        if ent is not None and ent[0]() is arr and (not exact or ent[2]):
            return ent[1]
    lossless = True
    use_bf16 = (big and jax.default_backend() != "cpu"
                and os.environ.get("TRANSMOGRIFAI_WIRE_F32") != "1")
    if use_bf16:
        import ml_dtypes
        wire = arr.astype(ml_dtypes.bfloat16)
        if exact:
            lossless = bool(np.array_equal(
                wire.astype(np.float32), arr.astype(np.float32)))
            use_bf16 = lossless
        else:
            lossless = False     # unverified; conservative for exact reuse
    if use_bf16:
        dev = jax.device_put(wire).astype(jnp.float32)
    else:
        lossless = True
        dev = jnp.asarray(arr, jnp.float32)
    if big:
        from .profiling import add_host_link_bytes
        add_host_link_bytes(wire.nbytes if use_bf16 else arr.size * 4)
        key = id(arr)
        nbytes = int(dev.size) * 4

        def _drop(_r, _k=key, _b=nbytes):
            if _DEVICE_CACHE.pop(_k, None) is not None:
                _DEVICE_CACHE_BYTES[0] -= _b

        try:
            ref = weakref.ref(arr, _drop)
        except TypeError:  # pragma: no cover — un-weakref-able array subtype
            return dev
        # replacing an entry (e.g. exact request over a cached lossy wire):
        # release the old bytes so the counter stays truthful
        prev = _DEVICE_CACHE.pop(key, None)
        if prev is not None:
            _DEVICE_CACHE_BYTES[0] -= int(prev[1].size) * 4
        while (_DEVICE_CACHE_BYTES[0] + nbytes > _DEVICE_CACHE_CAP
               and _DEVICE_CACHE):
            oldest = next(iter(_DEVICE_CACHE))   # dicts preserve insertion order
            _, old, _ = _DEVICE_CACHE.pop(oldest)
            _DEVICE_CACHE_BYTES[0] -= int(old.size) * 4
        _DEVICE_CACHE[key] = (ref, dev, lossless)
        _DEVICE_CACHE_BYTES[0] += nbytes
    return dev


class RaggedList:
    """A column of lists of int64 (a ``DateList``'s epoch milliseconds) held
    as two arrays, the form a join produces: ``values`` every row's items in
    row order, ``offsets`` [N + 1] where each row's start (row i is
    ``values[offsets[i]:offsets[i + 1]]``).  A row without a list is an
    empty one, as ``OPList`` reads None.  Read as a sequence it gives each
    row as a Python list; ``np.asarray`` of it is the object array of lists
    that the row-by-row consumers read."""

    __slots__ = ("values", "offsets", "__weakref__")

    def __init__(self, values, offsets):
        self.values = np.ascontiguousarray(values, dtype=np.int64)
        self.offsets = np.ascontiguousarray(offsets, dtype=np.int64)
        if (self.offsets.ndim != 1 or self.offsets.size == 0
                or self.offsets[0] != 0
                or self.offsets[-1] != self.values.size
                or (np.diff(self.offsets) < 0).any()):
            raise ValueError("offsets must rise from 0 to len(values)")

    @classmethod
    def from_lists(cls, lists) -> "RaggedList":
        """The object-held form (lists, tuples, arrays, ``OPList``s or
        None, a row each) in one pass over its rows."""
        rows = [getattr(v, "value", v) if isinstance(v, FeatureType) else v
                for v in lists]
        lengths = np.fromiter((0 if v is None else len(v) for v in rows),
                              np.int64, len(rows))
        offsets = np.zeros(len(rows) + 1, np.int64)
        np.cumsum(lengths, out=offsets[1:])
        values = np.fromiter(
            (x for v in rows if v is not None for x in v), np.int64,
            int(offsets[-1]))
        return cls(values, offsets)

    def __len__(self) -> int:
        return self.offsets.size - 1

    def lengths(self) -> np.ndarray:
        return np.diff(self.offsets)

    def __getitem__(self, i):
        if isinstance(i, (int, np.integer)):
            i = int(i) + (len(self) if i < 0 else 0)
            return self.values[self.offsets[i]:self.offsets[i + 1]].tolist()
        return self.take(np.arange(len(self))[i])

    def __iter__(self):
        o = self.offsets.tolist()
        for a, b in zip(o[:-1], o[1:]):
            yield self.values[a:b].tolist()

    def take(self, idx) -> "RaggedList":
        """The rows ``idx`` (integers or a boolean mask), in that order."""
        idx = np.asarray(idx)
        if idx.dtype == bool:
            idx = np.flatnonzero(idx)
        starts = self.offsets[:-1][idx]
        lengths = self.offsets[1:][idx] - starts
        offsets = np.zeros(len(idx) + 1, np.int64)
        np.cumsum(lengths, out=offsets[1:])
        at = np.arange(offsets[-1], dtype=np.int64) + np.repeat(
            starts - offsets[:-1], lengths)
        return RaggedList(self.values[at], offsets)

    def __array__(self, dtype=None, copy=None):
        out = np.empty(len(self), dtype=object)
        out[:] = list(self)
        return out if dtype is None else out.astype(dtype)

    def __eq__(self, other) -> bool:
        return (isinstance(other, RaggedList)
                and np.array_equal(self.offsets, other.offsets)
                and np.array_equal(self.values, other.values))

    __hash__ = None


def ragged_of(col: "Column") -> RaggedList:
    """``col``'s lists as a ``RaggedList``: its values where it is held so,
    else its object-held lists converted once and kept on the Column (which
    is immutable throughout the framework)."""
    if isinstance(col.values, RaggedList):
        return col.values
    held = getattr(col, "_ragged", None)
    if held is None:
        held = RaggedList.from_lists(col.values)
        try:
            object.__setattr__(col, "_ragged", held)
        except Exception:  # pragma: no cover — exotic column subtype
            pass
    return held


@dataclass
class Column:
    """A typed column of N rows.

    Storage by kind:
      * numeric kinds   — ``values``: float32/int64 array [N]; ``mask``: bool [N]
                        (True = present).  RealNN/Prediction are mask-free.
      * text kinds      — ``values``: numpy object array [N] of str | None (host).
      * OPVector        — ``values``: float32 array [N, D]; ``meta``: VectorMeta.
      * Geolocation     — ``values``: float32 [N, 3]; ``mask``: bool [N].
      * lists/sets      — ``values``: numpy object array [N] of list/set (host);
                        a DateList's may be a ``RaggedList`` instead.
      * maps            — ``values``: numpy object array [N] of dict (host).
      * Prediction      — ``values``: dict with 'prediction' [N] and optionally
                        'probability' [N, C], 'rawPrediction' [N, C] arrays.
    """

    kind: Type[FeatureType]
    values: Any
    mask: Optional[Any] = None
    meta: Optional[VectorMeta] = None

    def __len__(self) -> int:
        if isinstance(self.values, dict):
            return len(self.values["prediction"])
        return len(self.values)

    @property
    def is_device(self) -> bool:
        """True if values are dense arrays usable inside jit."""
        if isinstance(self.values, dict):
            return True
        if isinstance(self.values, RaggedList):
            return False
        return not (isinstance(self.values, np.ndarray) and self.values.dtype == object)

    def row_value(self, i: int) -> FeatureType:
        """Materialize row ``i`` as a typed value (local-scoring/test path)."""
        k = self.kind
        if k is Prediction or (isinstance(self.values, dict)):
            d = {"prediction": float(np.asarray(self.values["prediction"])[i])}
            for base in ("probability", "rawPrediction"):
                if base in self.values:
                    row = np.asarray(self.values[base])[i]
                    for j, v in enumerate(row):
                        d[f"{base}_{j}"] = float(v)
            return Prediction(d)
        if issubclass(k, OPVector):
            from .sparse.matrix import SparseMatrix
            if isinstance(self.values, SparseMatrix):
                return OPVector(list(self.values.dense_rows([i])[0].tolist()))
            return OPVector(list(np.asarray(self.values)[i].tolist()))
        if issubclass(k, Geolocation) and not self.is_host_object():
            if self.mask is not None and not bool(np.asarray(self.mask)[i]):
                return Geolocation()
            return Geolocation(list(np.asarray(self.values)[i].tolist()))
        if self.is_host_object() or isinstance(self.values, RaggedList):
            return k(self.values[i])
        v = np.asarray(self.values)[i]
        if self.mask is not None and not bool(np.asarray(self.mask)[i]):
            return k(None)
        if issubclass(k, (Integral,)):
            return k(int(v))
        if issubclass(k, Binary):
            return k(bool(v))
        return k(float(v))

    def is_host_object(self) -> bool:
        return isinstance(self.values, np.ndarray) and self.values.dtype == object


def _full_mask(n: int) -> np.ndarray:
    return np.ones(n, dtype=bool)


def indicator_2d(flags: Iterable) -> np.ndarray:
    """[N, 1] float32 indicator block from truthy flags — shape-safe at N==0
    (a list-comprehension ``np.array([[1.0] if ...])`` collapses to shape (0,)
    on empty input and breaks axis-1 concatenation)."""
    arr = np.fromiter((1.0 if f else 0.0 for f in flags), np.float32)
    return arr.reshape(-1, 1)


def numeric_column(kind: Type[FeatureType], values: Iterable, n: Optional[int] = None) -> Column:
    """Build a numeric column from python values with Nones.

    A value the kind cannot coerce raises a ``ValueError`` naming the kind,
    the offending row and the value (with ``violation_kind`` set to the
    quality.py taxonomy), so a poison record in a batch is attributable to
    its row instead of surfacing as a bare ``float()`` traceback."""
    vals = list(values)
    n = len(vals) if n is None else n
    mask = np.array([v is not None for v in vals], dtype=bool)
    if issubclass(kind, (Date, DateTime)) or issubclass(kind, Integral):
        cast, zero, dtype = int, 0, np.int64
    elif issubclass(kind, Binary):
        cast, zero, dtype = bool, False, bool
    else:
        cast, zero, dtype = float, np.nan, np.float32
    try:
        arr = np.array([zero if v is None else cast(v) for v in vals],
                       dtype=dtype)
    except (TypeError, ValueError) as e:
        bad_row = None
        for i, v in enumerate(vals):
            if v is None:
                continue
            try:
                cast(v)
            except (TypeError, ValueError):
                bad_row = i
                break
        err = ValueError(
            f"{kind.__name__} column: non-coercible value at row "
            f"{bad_row}: {str(vals[bad_row])[:60]!r}" if bad_row is not None
            else f"{kind.__name__} column: non-coercible value ({e})")
        err.violation_kind = "NonCoercibleValue"  # quality.py taxonomy
        raise err from e
    if kind.non_nullable and not mask.all():
        bad = int((~mask).sum())
        err = ValueError(f"{kind.__name__} column has {bad} empty values")
        err.violation_kind = "MissingRequiredField"  # quality.py taxonomy
        raise err
    return Column(kind, arr, mask=None if kind.non_nullable else mask)


def text_column(kind: Type[FeatureType], values: Iterable) -> Column:
    arr = np.array([None if v is None or v == "" else str(v) for v in values], dtype=object)
    return Column(kind, arr)


def object_column(kind: Type[FeatureType], values: Iterable) -> Column:
    return Column(kind, np.array(list(values) + [None], dtype=object)[:-1])


def vector_column(values, meta: VectorMeta) -> Column:
    return Column(OPVector, values, meta=meta)


def column_from_values(kind: Type[FeatureType], values: Iterable) -> Column:
    """Dispatch on kind to build the right storage."""
    if is_numeric_kind(kind):
        return numeric_column(kind, values)
    if is_text_kind(kind):
        return text_column(kind, values)
    if issubclass(kind, OPVector):
        vals = [np.asarray(v.value if isinstance(v, OPVector) else v,
                           dtype=np.float32)
                for v in values if v is not None and not (
                    isinstance(v, (list, tuple)) and len(v) == 0)]
        rows = list(values)
        dim = len(vals[0]) if vals else 0
        arr = np.zeros((len(rows), dim), dtype=np.float32)
        for i, v in enumerate(rows):
            data = v.value if isinstance(v, OPVector) else v
            if data is None or len(data) == 0:
                continue  # missing vector → zero row (lenient, like fills)
            arr[i, :] = np.asarray(data, dtype=np.float32)
        return Column(OPVector, arr)
    if issubclass(kind, DateList):
        return Column(kind, RaggedList.from_lists(values))
    return object_column(kind, values)


class ColumnBatch:
    """Ordered name → Column mapping; the working set of a workflow run."""

    def __init__(self, columns: Optional[Dict[str, Column]] = None, length: Optional[int] = None):
        self._cols: Dict[str, Column] = dict(columns or {})
        self._length = length
        if self._length is None and self._cols:
            self._length = len(next(iter(self._cols.values())))

    def __len__(self) -> int:
        return self._length or 0

    def __contains__(self, name: str) -> bool:
        return name in self._cols

    def __getitem__(self, name: str) -> Column:
        return self._cols[name]

    def get(self, name: str) -> Optional[Column]:
        return self._cols.get(name)

    def names(self) -> List[str]:
        return list(self._cols)

    def items(self):
        return self._cols.items()

    def with_column(self, name: str, col: Column) -> "ColumnBatch":
        new = dict(self._cols)
        new[name] = col
        return ColumnBatch(new, self._length if self._length is not None else len(col))

    def with_columns(self, cols: Dict[str, Column]) -> "ColumnBatch":
        new = dict(self._cols)
        new.update(cols)
        n = self._length
        if n is None and cols:
            n = len(next(iter(cols.values())))
        return ColumnBatch(new, n)

    def select(self, names: Sequence[str]) -> "ColumnBatch":
        return ColumnBatch({n: self._cols[n] for n in names}, self._length)

    def drop(self, names: Sequence[str]) -> "ColumnBatch":
        drop = set(names)
        return ColumnBatch({n: c for n, c in self._cols.items() if n not in drop}, self._length)

    def take_rows(self, idx: np.ndarray) -> "ColumnBatch":
        """Row subset (host-side gather; used by splitters/CV on small data)."""
        from .sparse.matrix import SparseMatrix
        out: Dict[str, Column] = {}
        for name, c in self._cols.items():
            if isinstance(c.values, dict):
                vals = {k: np.asarray(v)[idx] for k, v in c.values.items()}
            elif isinstance(c.values, SparseMatrix):
                vals = c.values.take_rows(idx)   # stays sparse end-to-end
            elif isinstance(c.values, RaggedList):
                vals = c.values.take(idx)
            else:
                vals = np.asarray(c.values)[idx]
            mask = None if c.mask is None else np.asarray(c.mask)[idx]
            out[name] = Column(c.kind, vals, mask=mask, meta=c.meta)
        return ColumnBatch(out, int(len(idx)))

    def row(self, i: int) -> Dict[str, FeatureType]:
        return {name: c.row_value(i) for name, c in self._cols.items()}

"""Text vectorization (reference: core/.../stages/impl/feature/
SmartTextVectorizer.scala:61, TextTokenizer.scala, OpHashingTF.scala,
OPCollectionHashingVectorizer.scala, TextLenTransformer.scala).

TPU design: tokenization + hashing happen host-side at transform time (strings
never reach the device); the hashed term-frequency matrix is the device-side
product.  Hashing uses a stable 32-bit FNV-1a (vectorizable, seed-stable across
processes — unlike Python's ``hash``).  The SmartTextVectorizer decision
(cardinality ≤ max → pivot one-hot, else hash) is made at fit time from a
single-pass TextStats reduction, so transform shapes are static for jit.
"""

from __future__ import annotations

import functools
import re
from collections import Counter
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..columns import Column, ColumnBatch
from ..stages.base import Estimator, Transformer, TransformerModel
from ..types import OPVector, Real, Text, TextList
from ..vector_meta import (NULL_INDICATOR, OTHER_INDICATOR, VectorColumnMeta,
                           VectorMeta)
from .categorical import _col_strings, top_values_by_count

_TOKEN_RE = re.compile(r"[A-Za-z0-9_']+")

def fnv1a_32(s: str) -> int:
    """Stable 32-bit FNV-1a string hash (host-side hashing-trick backbone)."""
    h = 2166136261
    for b in s.encode("utf-8"):
        h = ((h ^ b) * 16777619) & 0xFFFFFFFF
    return h


def tokenize_text(s: Optional[str], min_token_length: int = 1,
                  to_lowercase: bool = True) -> List[str]:
    """Simple language-agnostic tokenizer (≙ TextTokenizer with the default
    Lucene analyzer: lowercase + split on non-alphanumerics)."""
    if s is None:
        return []
    if to_lowercase:
        s = s.lower()
    return [t for t in _TOKEN_RE.findall(s) if len(t) >= min_token_length]


def hash_tokens_flat(token_lists: Sequence[Sequence[str]], num_hashes: int
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """Tokens → (lens [N] int32, flat bucket ids [total] int32).

    Vectorized host prologue (SURVEY §7 hard part (b)): tokens flatten to one
    array, each DISTINCT token hashes once (np.unique + inverse codes)."""
    n = len(token_lists)
    lens = np.fromiter((len(t) for t in token_lists), np.int32, count=n)
    total = int(lens.sum())
    if not total:
        return lens, np.zeros(0, np.int32)
    flat = np.empty(total, dtype=object)
    pos = 0
    for toks in token_lists:
        flat[pos:pos + len(toks)] = toks
        pos += len(toks)
    # np.unique on the object array directly: astype(str) would allocate a
    # fixed-width U<longest-token> copy (one huge token → OOM)
    uniq, codes = np.unique(flat, return_inverse=True)
    buckets = np.fromiter((fnv1a_32(t) % num_hashes for t in uniq),
                          np.int64, count=len(uniq))
    return lens, buckets[codes].astype(np.int32)


def hash_tokens_to_counts(token_lists: Sequence[Sequence[str]], num_hashes: int,
                          binary: bool = False) -> np.ndarray:
    """Hashing trick: token lists → [N, num_hashes] term-frequency matrix
    (host path; counts land via one ``np.add.at`` scatter)."""
    lens, flat = hash_tokens_flat(token_lists, num_hashes)
    return _counts_from_flat(lens, flat, num_hashes, binary)


def _counts_from_flat(lens: np.ndarray, flat: np.ndarray, num_hashes: int,
                      binary: bool) -> np.ndarray:
    out = np.zeros((len(lens), num_hashes), dtype=np.float32)
    if not flat.size:
        return out
    rows = np.repeat(np.arange(len(lens)), lens)
    if binary:
        # dedupe (row, bucket) pairs on int64 keys and write the indicator
        # into the single output buffer — the old `(out > 0).astype(...)`
        # allocated a SECOND dense [N, H] copy just to threshold it, pure
        # waste whenever empty-token rows leave most of the matrix zero
        keys = np.unique(rows.astype(np.int64) * num_hashes + flat)
        out[keys // num_hashes, keys % num_hashes] = 1.0
        return out
    np.add.at(out, (rows, flat), 1.0)
    return out


def strings_to_hash_flat(strings: Sequence[Optional[str]], num_hashes: int
                         ) -> Tuple[np.ndarray, np.ndarray]:
    """Strings → (lens [N] int32, flat bucket ids [total] int32) in ONE
    native pass (tokenize + FNV + modulo, native/fasttok.cpp) — the host
    prologue of the hashing trick without per-token Python objects.  Rows the
    native tokenizer defers (non-ASCII content: unicode case folding must
    match Python's) are spliced back from the pure-Python path."""
    from ..native import load
    native = load("fasttok")
    if native is None:
        return hash_tokens_flat(
            [tokenize_text(s) for s in strings], num_hashes)
    lens, buckets, fallback = native.tokenize_hash(list(strings), num_hashes, 1)
    if not fallback:
        return lens, buckets
    fb_tok = {i: np.asarray([fnv1a_32(t) % num_hashes
                             for t in tokenize_text(strings[i])], np.int32)
              for i in fallback}
    out_lens = lens.copy()
    pieces: List[np.ndarray] = []
    pos = 0
    for i, L in enumerate(lens):
        if L < 0:
            out_lens[i] = len(fb_tok[i])
            pieces.append(fb_tok[i])
        elif L:
            pieces.append(buckets[pos:pos + L])
            pos += L
    flat = (np.concatenate(pieces).astype(np.int32) if pieces
            else np.zeros(0, np.int32))
    return out_lens, flat


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _scatter_counts_device(ids, lens_padded, n, num_hashes, binary):
    """Flat bucket ids (+1 sentinel row/bin of padding) → [n, H] counts
    materialized in HBM — the hashed matrix never exists on the host, so the
    (slow) host link carries token ids instead of a dense [N, H] block."""
    rows = jnp.repeat(jnp.arange(n + 1), lens_padded,
                      total_repeat_length=ids.shape[0])
    counts = jnp.zeros((n + 1, num_hashes + 1), jnp.float32)
    counts = counts.at[rows, ids].add(1.0)
    counts = counts[:n, :num_hashes]
    return (counts > 0).astype(jnp.float32) if binary else counts


# bytes the chip gives a ``[cap, 3]`` int32 intermediate (it tiles the 3 to
# 128 lanes: 512 B a word) above which the ids unpack lane by lane instead
_STACKED_UNPACK_BYTES = 1 << 28


def _unpack_ids3(words, lens_padded):
    """Packed words [cap] + tokens a row [n + 1] (the last entry is the
    padding's) → (rows, ids) of every token slot, both flat [3 * cap].  The
    scatter that follows adds, so the order of the pairs is free.

    Short wires (the Criteo cells' one-token values: 65,536 words a column)
    stack the three lanes and repeat the rows by their lengths, in token
    order.  The stack is a ``[cap, 3]`` intermediate that the chip tiles to
    128 lanes, 42 times its bytes — 25.8 GB at 50 M words, which no chip
    holds — so a long wire (free text) goes lane by lane, rows and ids only
    ever flat: first every word's low id, then its middle one, then its high
    one.  Slot ``3 w + l`` lies in the row whose end it has not passed: a
    row's end at that slot is marked at ``l * cap + w`` (one scatter), and
    one cumulative sum over words counts the ends before each word.  Which
    form runs follows from the wire's static length alone; the benchmark
    has cells on both sides (PERF.md §6, PR 34)."""
    cap = words.shape[0]
    lanes = [words & 0x3FF, (words >> 10) & 0x3FF, (words >> 20) & 0x3FF]
    if cap * 512 <= _STACKED_UNPACK_BYTES:
        rows = jnp.repeat(jnp.arange(lens_padded.shape[0]), lens_padded,
                          total_repeat_length=3 * cap)
        return rows, jnp.stack(lanes, axis=1).reshape(-1)
    ends = jnp.cumsum(lens_padded)[:-1]
    at = jnp.where(ends < 3 * cap, ends % 3 * cap + ends // 3, 3 * cap)
    marks = jnp.zeros((3 * cap,), jnp.int32).at[at].add(1, mode="drop")
    low, mid, high = marks[:cap], marks[cap:2 * cap], marks[2 * cap:]
    ended = low + mid + high
    row0 = jnp.cumsum(ended) - ended + low
    row1 = row0 + mid
    return (jnp.concatenate([row0, row1, row1 + high]),
            jnp.concatenate(lanes))


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _scatter_counts_packed(words, lens_padded, n, num_hashes, binary):
    """Packed-wire variant: each int32 word carries THREE 10-bit bucket ids
    (token order preserved), tripling the effective host-link bandwidth of
    the hashing trick — the ids unpack with two shifts on device."""
    rows, ids = _unpack_ids3(words, lens_padded)
    counts = jnp.zeros((n + 1, num_hashes + 1), jnp.float32)
    with jax.named_scope("text.hash_counts"):
        counts = counts.at[rows, ids].add(1.0)
    counts = counts[:n, :num_hashes]
    return (counts > 0).astype(jnp.float32) if binary else counts


def _size_class(n: int, floor: int = 1024) -> int:
    """Smallest {2^k, 1.5·2^k} >= n — tighter than pure powers of two (at
    most a third of the wire is padding, instead of a half) while keeping
    the jit-recompile count bounded at two shapes per octave.  Measured on
    free text (two columns, 83 tokens a row; the counters ``text.tokens`` /
    ``text.token_slots``): 18.6 % of the id slots shipped are padding at
    1,048,576, 1,572,864 and 2,097,152 rows alike (PERF.md §5, PR 34)."""
    if n <= floor:
        return floor
    k = int(np.ceil(np.log2(n)))
    for cap in ((1 << (k - 1)) + (1 << (k - 2)), 1 << k):
        if cap >= n:
            return cap
    return 1 << k


def _pack_ids3(flat: np.ndarray, num_hashes: int) -> np.ndarray:
    """Bucket ids (< 1024) → int32 words of three 10-bit lanes, padded with
    the sentinel bin ``num_hashes`` to a full final word."""
    total = int(flat.size)
    w = (total + 2) // 3
    ids = np.full(3 * w, num_hashes, dtype=np.int32)
    ids[:total] = flat
    return (ids[0::3] | (ids[1::3] << 10) | (ids[2::3] << 20)).astype(
        np.int32)


def hash_counts_on_device(token_lists: Sequence[Sequence[str]],
                          num_hashes: int, binary: bool = False,
                          dtype=None):
    """Device-resident hashing trick: ship (lens, flat bucket ids) — a few
    bytes per TOKEN — and scatter-add the [N, H] count matrix in HBM.  The
    wire cost drops ~H/avg_tokens-fold vs shipping the dense counts (at 1M
    rows x 512 bins that is 6 GB → ~25 MB over the host link).  Flat
    length pads to the next power of two so jit recompiles stay bounded.
    ``dtype`` (e.g. bf16 at scale — counts ≤ 256 are exact) sets storage."""
    lens, flat = hash_tokens_flat(token_lists, num_hashes)
    return device_counts_from_flat(lens, flat, num_hashes, binary, dtype)


def device_counts_from_flat(lens: np.ndarray, flat: np.ndarray,
                            num_hashes: int, binary: bool = False,
                            dtype=None, device_ids=None):
    n = len(lens)
    total = int(flat.size)
    if num_hashes < 1024:
        # packed wire: 3 ids per int32 word (sentinel bin fits 10 bits)
        if device_ids is None:
            words = _pack_ids3(flat, num_hashes)
            cap = _size_class(words.size)
            words_p = np.full(cap, _sentinel3(num_hashes), dtype=np.int32)
            words_p[:words.size] = words
            device_ids = jnp.asarray(words_p)
        cap = int(device_ids.shape[0])
        lens_p = np.append(lens, np.int32(3 * cap - total)).astype(np.int32)
        out = _scatter_counts_packed(device_ids, jnp.asarray(lens_p),
                                     n, num_hashes, bool(binary))
    else:
        cap = 1 << max(10, int(np.ceil(np.log2(max(total, 1)))))
        ids_p = np.full(cap, num_hashes, dtype=np.int32)     # sentinel bin
        ids_p[:total] = flat
        lens_p = np.append(lens, np.int32(cap - total)).astype(np.int32)
        out = _scatter_counts_device(jnp.asarray(ids_p), jnp.asarray(lens_p),
                                     n, num_hashes, bool(binary))
    return out if dtype is None or out.dtype == dtype else out.astype(dtype)


def _sentinel3(num_hashes: int) -> np.int32:
    """An int32 word whose three 10-bit lanes all hold the sentinel bin."""
    return np.int32(num_hashes | (num_hashes << 10) | (num_hashes << 20))


# device assembly kicks in when the dense block would exceed this many
# elements (16 MB of f32) — below it, host numpy + one bf16-wire transfer
# in the combiner is cheaper than per-block dispatch latency
_DEVICE_ASSEMBLE_ELEMS = 1 << 22

# hash spaces at/above this width vectorize SPARSE by default (the dense
# [N, num_hashes] block at 4096+ columns starts to dominate memory while
# its density collapses); override per stage with sparse_hashing=True/False
SPARSE_MIN_HASHES = 4096


def _one_hot_on_device(ids: np.ndarray, width: int, dtype=jnp.float32):
    # narrowest wire dtype — the host link, not the expand, is the cost
    wire = ids.astype(np.uint8) if width < 256 else ids.astype(np.int32)
    idsd = jnp.asarray(wire).astype(jnp.int32)
    return (idsd[:, None] == jnp.arange(width)[None, :]).astype(dtype)


class TextTokenizer(Transformer):
    """Text → TextList of tokens (≙ TextTokenizer.scala)."""

    in_kinds = (Text,)
    out_kind = TextList
    is_device_op = False

    def __init__(self, min_token_length: int = 1, to_lowercase: bool = True, **params):
        super().__init__(min_token_length=min_token_length,
                         to_lowercase=to_lowercase, **params)

    def transform(self, batch: ColumnBatch) -> Column:
        (f,) = self.input_features
        strings = _col_strings(batch[f.name])
        toks = np.empty(len(strings), dtype=object)
        for i, s in enumerate(strings):
            toks[i] = tokenize_text(s, self.get("min_token_length", 1),
                                    self.get("to_lowercase", True))
        return Column(TextList, toks)


class TextLenTransformer(Transformer):
    """Text length feature (≙ TextLenTransformer.scala)."""

    out_kind = Real
    is_device_op = False

    def transform(self, batch: ColumnBatch) -> Column:
        (f,) = self.input_features
        strings = _col_strings(batch[f.name])
        vals = np.array([0.0 if s is None else float(len(s)) for s in strings],
                        np.float32)
        mask = np.array([s is not None for s in strings])
        return Column(Real, vals, mask=mask)


class HashingVectorizerModel(TransformerModel):
    out_kind = OPVector
    is_device_op = False

    def transform(self, batch: ColumnBatch) -> Column:
        from ..columns import feature_matrix_dtype

        num_hashes = self.get("num_hashes")
        binary = self.get("binary", False)
        n = len(batch)
        # output width: shared hash space folds every feature into ONE block
        width = (num_hashes if self.get("shared_hash_space", False)
                 else num_hashes * len(self.input_features))
        n_elems = n * width
        on_device = n_elems >= _DEVICE_ASSEMBLE_ELEMS
        dtype = feature_matrix_dtype(n_elems)
        blocks = []
        for f in self.input_features:
            col = batch[f.name]
            if col.is_host_object() and len(col.values) and isinstance(
                    next((v for v in col.values if v is not None), ""), list):
                lens, flat = hash_tokens_flat(
                    [v or [] for v in col.values], num_hashes)
            else:
                from .text_profile import column_profile
                prof = column_profile(col)
                lens, flat = prof.buckets(num_hashes)
                if on_device:
                    blocks.append(device_counts_from_flat(
                        lens, flat, num_hashes, binary=binary, dtype=dtype,
                        device_ids=prof.device_ids(num_hashes)))
                    continue
            blocks.append(
                device_counts_from_flat(lens, flat, num_hashes,
                                        binary=binary, dtype=dtype)
                if on_device else
                _counts_from_flat(lens, flat, num_hashes, binary))
        if on_device:
            arr = (sum(blocks) if self.get("shared_hash_space", False)
                   else jnp.concatenate(blocks, axis=1))
            return Column(OPVector, arr, meta=self.fitted["meta"])
        if self.get("shared_hash_space", False):
            arr = np.sum(blocks, axis=0)
        else:
            arr = np.concatenate(blocks, axis=1)
        return Column(OPVector, jnp.asarray(arr), meta=self.fitted["meta"])


class HashingVectorizer(Estimator):
    """Token/text hashing vectorizer (≙ OpHashingTF +
    OPCollectionHashingVectorizer): each feature hashed into its own (or a
    shared) ``num_hashes``-wide space."""

    out_kind = OPVector

    def __init__(self, num_hashes: int = 512, binary: bool = False,
                 shared_hash_space: bool = False, **params):
        super().__init__(num_hashes=num_hashes, binary=binary,
                         shared_hash_space=shared_hash_space, **params)

    def fit(self, batch: ColumnBatch) -> TransformerModel:
        cols_meta = []
        n_blocks = 1 if self.get("shared_hash_space") else len(self.input_features)
        feats = (self.input_features[:1] if self.get("shared_hash_space")
                 else self.input_features)
        for f in feats:
            for j in range(self.get("num_hashes")):
                cols_meta.append(VectorColumnMeta(
                    f.name, f.kind.__name__, descriptor_value=f"hash_{j}"))
        meta = VectorMeta(self.output_name(), cols_meta)
        return self._finalize_model(HashingVectorizerModel(
            fitted={"meta": meta}, **self.params))


class TextStats:
    """Single-pass text cardinality statistics monoid
    (≙ SmartTextVectorizer.TextStats, SmartTextVectorizer.scala:182-230)."""

    def __init__(self, value_counts: Optional[Counter] = None,
                 length_counts: Optional[Counter] = None):
        self.value_counts = value_counts or Counter()
        self.length_counts = length_counts or Counter()

    @property
    def cardinality(self) -> int:
        return len(self.value_counts)

    @property
    def length_std_dev(self) -> float:
        """Standard deviation of the FULL (cleaned) value lengths — exactly
        the reference's TextStats.lengthStdDev (SmartTextVectorizer.scala:
        126 builds lengthCounts from text.length, :190-193 the stddev);
        drives the ID-like Ignore branch."""
        n = sum(self.length_counts.values())
        if n == 0:
            return 0.0
        mean = sum(l * c for l, c in self.length_counts.items()) / n
        var = sum(c * (l - mean) ** 2 for l, c in self.length_counts.items()) / n
        return var ** 0.5

    def combine(self, other: "TextStats") -> "TextStats":
        return TextStats(self.value_counts + other.value_counts,
                         self.length_counts + other.length_counts)

    @staticmethod
    def of_column(strings: np.ndarray, max_card: int) -> "TextStats":
        vc, lc = Counter(), Counter()
        for s in strings:
            if s is None:
                continue
            if len(vc) <= max_card:
                vc[s] += 1
            lc[len(s)] += 1
        return TextStats(vc, lc)


class SmartTextVectorizerModel(TransformerModel):
    out_kind = OPVector
    is_device_op = False
    supports_staging = True

    def transform_staged(self, batch: ColumnBatch):
        """Host prologue: cached column profiles → compact wire (packed
        token words, per-row lens, vocab codes, null bits).  Device body:
        scatter-add hash counts + one-hot pivots + null indicators, concat —
        traceable, so the whole block fuses into the surrounding program."""
        from ..columns import (feature_matrix_dtype, pack_bits,
                               unpack_bits_device)
        from .categorical import encode_column
        from .text_profile import column_profile

        if self.fitted.get("sparse"):
            return None          # sparse representation assembles host-side
        num_hashes = self.get("num_hashes")
        if num_hashes >= 1024:
            return None          # packed 10-bit wire only
        n = len(batch)
        strategies = self.fitted["strategies"]
        track_nulls = self.get("track_nulls", True)
        est_width = sum(
            num_hashes if strategies[f.name] == "hash" else 32
            for f in self.input_features)
        dtype = feature_matrix_dtype(n * est_width)
        wire: Dict[str, Any] = {}
        plan: List[Tuple[str, Any, Tuple[Optional[str], ...]]] = []
        for i, f in enumerate(self.input_features):
            col = batch[f.name]
            if not col.is_host_object():
                return None      # exotic residency: eager path
            strat = strategies[f.name]
            prof = column_profile(col)
            if strat == "pivot":
                vocab = self.fitted["vocabs"][f.name]
                other = len(vocab)
                ids = encode_column(col, vocab, other)
                wire[f"ids{i}"] = (ids.astype(np.uint8) if other + 1 < 256
                                   else ids)
                plan.append(("pivot", other + 2, (f"ids{i}",)))
            elif strat == "ignore":
                if track_nulls:
                    wire[f"null{i}"] = pack_bits(prof.null)
                    plan.append(("null", None, (f"null{i}",)))
            else:
                words = prof.device_ids(num_hashes)
                total = prof.tokens
                cap = int(words.shape[0])
                wire[f"words{i}"] = words
                wire[f"lens{i}"] = np.append(
                    prof.tok_lens, np.int32(3 * cap - total)).astype(np.int32)
                nk = None
                if track_nulls:
                    nk = f"null{i}"
                    wire[nk] = pack_bits(prof.null)
                plan.append(("hash", num_hashes, (f"words{i}", f"lens{i}", nk)))
        meta = self.fitted["meta"]

        def body(w):
            blocks = []
            for kind, info, keys in plan:
                if kind == "pivot":
                    ids = jnp.asarray(w[keys[0]]).astype(jnp.int32)
                    blocks.append((ids[:, None] == jnp.arange(info)[None, :]
                                   ).astype(dtype))
                elif kind == "null":
                    blocks.append(unpack_bits_device(
                        w[keys[0]], n)[:, None].astype(dtype))
                else:
                    words, lens_p = w[keys[0]], w[keys[1]]
                    h = info
                    rows, ids = _unpack_ids3(words, lens_p)
                    nr = lens_p.shape[0] - 1
                    counts = jnp.zeros((nr + 1, h + 1), jnp.float32)
                    with jax.named_scope("text.hash_counts"):
                        counts = counts.at[rows, ids].add(1.0)
                    counts = counts[:nr, :h].astype(dtype)
                    if keys[2] is not None:
                        counts = jnp.concatenate(
                            [counts,
                             unpack_bits_device(w[keys[2]], nr)[:, None]
                             .astype(dtype)],
                            axis=1)
                    blocks.append(counts)
            if not blocks:
                return Column(OPVector, jnp.zeros((n, 0), jnp.float32),
                              meta=meta)
            return Column(OPVector, jnp.concatenate(blocks, axis=1), meta=meta)

        return wire, body

    def _transform_sparse(self, batch: ColumnBatch) -> Column:
        """Fused hashed-text -> device SparseMatrix: the flat bucket stream
        dedupes host-side and ships as COO entries — the dense
        [N, num_hashes] matrix is NEVER materialized, so peak memory scales
        with nnz instead of rows x num_hashes.  Pivot/null blocks ride along
        as (tiny) dense blocks folded into the same entry stream."""
        from ..sparse.transform import combine_blocks, sparse_from_hash_flat
        from .categorical import encode_column
        from .text_profile import column_profile

        num_hashes = self.get("num_hashes")
        n = len(batch)
        strategies = self.fitted["strategies"]
        track_nulls = self.get("track_nulls", True)
        blocks: List[Any] = []
        for f in self.input_features:
            strat = strategies[f.name]
            prof = column_profile(batch[f.name])
            if strat == "pivot":
                vocab = self.fitted["vocabs"][f.name]
                other = len(vocab)
                ids = encode_column(batch[f.name], vocab, other)
                width = other + 2  # OTHER + null
                blocks.append(np.asarray(
                    ids[:, None] == np.arange(width)[None, :], np.float32))
            elif strat == "ignore":
                if track_nulls:
                    blocks.append(prof.null.astype(np.float32)[:, None])
            else:  # hash
                lens, flat = prof.buckets(num_hashes)
                blocks.append(sparse_from_hash_flat(
                    lens, flat, num_hashes, record=False))
                if track_nulls:
                    blocks.append(prof.null.astype(np.float32)[:, None])
        sm = combine_blocks(blocks, n)
        return Column(OPVector, sm, meta=self.fitted["meta"])

    def transform(self, batch: ColumnBatch) -> Column:
        from ..columns import feature_matrix_dtype
        from .text_profile import column_profile

        if self.fitted.get("sparse"):
            return self._transform_sparse(batch)
        num_hashes = self.get("num_hashes")
        n = len(batch)
        strategies = self.fitted["strategies"]
        est_width = sum(
            num_hashes if strategies[f.name] == "hash" else 32
            for f in self.input_features)
        on_device = n * est_width >= _DEVICE_ASSEMBLE_ELEMS
        dtype = feature_matrix_dtype(n * est_width)
        blocks = []
        for f in self.input_features:
            strat = strategies[f.name]
            prof = column_profile(batch[f.name])
            if strat == "pivot":
                from .categorical import encode_column
                vocab = self.fitted["vocabs"][f.name]
                other = len(vocab)
                ids = encode_column(batch[f.name], vocab, other)
                width = other + 2  # OTHER + null
                blocks.append(
                    _one_hot_on_device(ids, width, dtype) if on_device else
                    np.asarray(ids[:, None] == np.arange(width)[None, :],
                               np.float32))
            elif strat == "ignore":
                if self.get("track_nulls", True):
                    blocks.append(
                        jnp.asarray(prof.null)[:, None].astype(dtype)
                        if on_device else
                        prof.null.astype(np.float32)[:, None])
            else:  # hash
                lens, flat = prof.buckets(num_hashes)
                if on_device:
                    h = device_counts_from_flat(
                        lens, flat, num_hashes, dtype=dtype,
                        device_ids=prof.device_ids(num_hashes))
                    if self.get("track_nulls", True):
                        h = jnp.concatenate(
                            [h, jnp.asarray(prof.null)[:, None].astype(dtype)],
                            axis=1)
                else:
                    h = _counts_from_flat(lens, flat, num_hashes, False)
                    if self.get("track_nulls", True):
                        h = np.concatenate(
                            [h, prof.null.astype(np.float32)[:, None]], axis=1)
                blocks.append(h)
        if on_device and blocks:
            return Column(OPVector, jnp.concatenate(blocks, axis=1),
                          meta=self.fitted["meta"])
        arr = (np.concatenate(blocks, axis=1) if blocks
               else np.zeros((len(batch), 0), np.float32))
        return Column(OPVector, jnp.asarray(arr), meta=self.fitted["meta"])


class SmartTextVectorizer(Estimator):
    """Cardinality-adaptive text vectorization (≙ SmartTextVectorizer.scala:61):
    one TextStats pass; per feature, cardinality ≤ max_cardinality → pivot
    one-hot (like categorical); else value-length stddev below
    ``min_length_std_dev`` (ID-like; branch off by default) → ignore; else
    tokenize+hash."""

    out_kind = OPVector

    def __init__(self, max_cardinality: int = 30, top_k: int = 20,
                 min_support: int = 10, num_hashes: int = 512,
                 track_nulls: bool = True, auto_detect_languages: bool = False,
                 min_length_std_dev: float = 0.0,
                 sparse_hashing: Any = "auto", **params):
        # sparse_hashing: "auto" -> sparse when num_hashes >= SPARSE_MIN_HASHES
        # and any feature hashes; True/False force/forbid the sparse output
        super().__init__(max_cardinality=max_cardinality, top_k=top_k,
                         min_support=min_support, num_hashes=num_hashes,
                         track_nulls=track_nulls,
                         auto_detect_languages=auto_detect_languages,
                         min_length_std_dev=min_length_std_dev,
                         sparse_hashing=sparse_hashing, **params)

    def fit(self, batch: ColumnBatch) -> TransformerModel:
        from collections import Counter

        from .text_profile import column_profile

        strategies: Dict[str, str] = {}
        vocabs: Dict[str, Dict[str, int]] = {}
        cols_meta: List[VectorColumnMeta] = []
        max_card = self.get("max_cardinality")
        for f in self.input_features:
            # ONE cached native pass serves the TextStats fit reduction, the
            # transform's tokenize+hash, and RawFeatureFilter's stats
            prof = column_profile(batch[f.name])
            iv = prof.values(max_card)
            stats = TextStats(Counter(iv.value_counts()),
                              Counter(prof.length_counts()))
            if stats.cardinality <= max_card:
                # card <= maxCardinality -> pivot (the reference pivots even
                # single-value columns; SmartTextVectorizer.scala:92-96)
                strategies[f.name] = "pivot"
                top = top_values_by_count(stats.value_counts,
                                          self.get("top_k"),
                                          self.get("min_support"))
                vocab = {v: i for i, v in enumerate(top)}
                vocabs[f.name] = vocab
                for v in top:
                    cols_meta.append(VectorColumnMeta(
                        f.name, f.kind.__name__, indicator_value=v))
                cols_meta.append(VectorColumnMeta(
                    f.name, f.kind.__name__, indicator_value=OTHER_INDICATOR))
                cols_meta.append(VectorColumnMeta(
                    f.name, f.kind.__name__, indicator_value=NULL_INDICATOR))
            elif stats.length_std_dev < self.get("min_length_std_dev", 0.0):
                # ID-like: high cardinality with near-constant token length
                # (SmartTextVectorizer.scala:94 Ignore branch; off by default
                # like the reference's MinTextLengthStdDev = 0)
                strategies[f.name] = "ignore"
                if self.get("track_nulls", True):
                    cols_meta.append(VectorColumnMeta(
                        f.name, f.kind.__name__, indicator_value=NULL_INDICATOR))
            else:
                strategies[f.name] = "hash"
                for j in range(self.get("num_hashes")):
                    cols_meta.append(VectorColumnMeta(
                        f.name, f.kind.__name__, descriptor_value=f"hash_{j}"))
                if self.get("track_nulls", True):
                    cols_meta.append(VectorColumnMeta(
                        f.name, f.kind.__name__, indicator_value=NULL_INDICATOR))
        meta = VectorMeta(self.output_name(), cols_meta)
        mode = self.get("sparse_hashing", "auto")
        use_sparse = (any(s == "hash" for s in strategies.values())
                      and (mode is True
                           or (mode == "auto" and self.get("num_hashes")
                               >= SPARSE_MIN_HASHES)))
        model = SmartTextVectorizerModel(
            fitted={"strategies": strategies, "vocabs": vocabs, "meta": meta,
                    "sparse": use_sparse},
            **self.params)
        model.metadata["strategies"] = dict(strategies)
        model.metadata["sparse"] = use_sparse
        return self._finalize_model(model)


class TextListVectorizer(HashingVectorizer):
    """TextList → hashed vector (tokens already split)."""

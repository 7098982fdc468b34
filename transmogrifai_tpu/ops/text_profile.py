"""One-walk text column profile shared by every host consumer of a text
column (reference parity targets: RawFeatureFilter's presence + hashed value
distribution RawFeatureFilter.scala:137, SmartTextVectorizer's TextStats fit
pass SmartTextVectorizer.scala:80-123, OpHashingTF's tokenize+hash transform).

The transmogrification hot path used to rescan each text column once per
consumer — a Python-object walk over millions of cells each time.  Here one
native walk (native/textprof.cpp ``profile``) reads the column's object
array in place — no list copy of it is ever made — and computes the
*parameter-free* per-row products, cached on the Column instance:

* ``null``/``empty``/``lengths``  — presence + TextStats length stats
* ``crc``      — full zlib crc32 per value; rebin with ``% text_bins`` for
  any RawFeatureFilter configuration
* ``tok_lens``/``tok_hash`` — tokens per row + full 32-bit FNV-1a per
  token; rebucket with ``% num_hashes`` for any hash width

Value interning (``values(cap)``) is the only cap-dependent product and is
cached per cap.  A caller that knows the cap before the column is first
walked (``column_profile(col, cap)``: ``profile_columns`` for a training
batch) gets it from that same walk; any other ``values(cap)`` that no cached
interning answers walks the column once more (native ``intern``, in place
too).  A score batch is never interned unless a consumer asks.

The native walk holds the GIL only while it fetches a block of rows' utf-8
pointers; hashing, tokenising and the intern table run with it released.
``profile_columns`` therefore walks a batch's columns side by side on a
few threads, as the cores the process may use allow
(``os.sched_getaffinity``).

The counters ``text_profile.scan`` (columns walked), ``.fused_intern``
(interned by that walk), ``.intern.hit`` / ``.intern.miss`` (``values(cap)``
answered from the cache / by another walk) and the gauge
``text_profile.workers`` say which of this happened; ``text.tokens`` /
``text.token_slots`` count the tokens packed for the device and the id slots
shipped for them (``text.pack_ids`` is the span), and
``text.rows_python_tokenized`` the rows the native walk left to the Python
tokenizer (span ``text.python_tokenize``).  All consumers fall
back to pure Python when the native toolchain is absent — identical
results, slower.
"""

from __future__ import annotations

import os
import zlib
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from ..telemetry import REGISTRY, span


@dataclass
class InternedValues:
    """First-occurrence-ordered distinct values with counts and row codes.

    ``codes``: -1 null, -2 seen only after the freeze cap, else index into
    ``uniq``.  ``frozen`` is True when the TextStats freeze engaged (counts
    stopped accumulating; ``uniq`` holds cap+1 values).
    """

    uniq: List[str]
    counts: np.ndarray       # int64[U]
    codes: np.ndarray        # int32[N]
    cap: int
    frozen: bool

    def value_counts(self) -> Dict[str, int]:
        return {v: int(c) for v, c in zip(self.uniq, self.counts)}


@dataclass
class TextProfile:
    null: np.ndarray         # bool[N]
    empty: np.ndarray        # bool[N]
    lengths: np.ndarray      # int32[N] (code points; 0 for null)
    crc: np.ndarray          # uint32[N] (0 for null)
    tok_lens: np.ndarray     # int32[N]
    tok_hash: np.ndarray     # uint32[total] full FNV-1a per token
    _interned: Dict[int, InternedValues] = field(default_factory=dict)
    _strings: Optional[np.ndarray] = None   # kept for lazy interning
    _device_packed: Dict[int, object] = field(default_factory=dict)

    @property
    def presence(self) -> np.ndarray:
        """Present = non-null and non-empty (filters._value_presence)."""
        return ~(self.null | self.empty)

    def crc_hist(self, text_bins: int) -> np.ndarray:
        """Hashed whole-value distribution over present rows — exactly
        filters._histogram_of's text branch (crc32 % text_bins)."""
        bins = (self.crc[self.presence] % np.uint32(text_bins)).astype(
            np.int64)
        return np.bincount(bins, minlength=text_bins).astype(np.float64)

    def length_counts(self) -> Dict[int, int]:
        """≙ TextStats.length_counts (lengths of all non-null values)."""
        ls = self.lengths[~self.null]
        if not ls.size:
            return {}
        uniq, cnt = np.unique(ls, return_counts=True)
        return {int(l): int(c) for l, c in zip(uniq, cnt)}

    def buckets(self, num_hashes: int) -> Tuple[np.ndarray, np.ndarray]:
        """(lens int32[N], flat bucket ids int32[total]) for the hashing
        trick at any ``num_hashes`` — one modulo over the cached full
        hashes instead of a re-tokenize."""
        return (self.tok_lens,
                (self.tok_hash % np.uint32(num_hashes)).astype(np.int32))

    def device_ids(self, num_hashes: int):
        """Packed token-bucket ids resident on device (3 × 10-bit ids per
        int32 word; ops/text.py pack/scatter pair), cached per hash width.
        ``prefetch`` starts the async host→device transfer early so the
        slow link overlaps RFF/fit host work instead of serializing after
        it.  That holds for the TRANSFER alone: the modulo, the packing and
        the pad run on the calling thread (span ``text.pack_ids``), and at
        free text's size they are what the device waits for — 4.55 s of the
        11.9 s of ``prefetch.text_profiles`` in a 19.9 s train of 2,097,152
        rows of 83 tokens, the device idle for all 11.9 s (PERF.md §5,
        PR 34); on the Criteo cells' one-token values it is 0.1 s.  None
        when the width needs the unpacked path."""
        if num_hashes >= 1024:
            return None
        dev = self._device_packed.get(num_hashes)
        if dev is None:
            import jax

            from .text import _pack_ids3, _sentinel3, _size_class
            with span("text.pack_ids", num_hashes=num_hashes) as sp:
                _, flat = self.buckets(num_hashes)
                words = _pack_ids3(flat, num_hashes)
                cap = _size_class(words.size)
                wp = np.full(cap, _sentinel3(num_hashes), np.int32)
                wp[:words.size] = words
                dev = jax.device_put(wp)      # async; consumers queue on it
                if sp is not None:
                    sp.attrs.update(tokens=int(flat.size),
                                    words=int(words.size), capacity=cap)
            from ..profiling import add_host_link_bytes
            add_host_link_bytes(wp.nbytes)
            REGISTRY.counter("text.tokens").inc(int(flat.size))
            REGISTRY.counter("text.token_slots").inc(3 * cap)
            self._device_packed[num_hashes] = dev
        return dev

    def prefetch(self, num_hashes: int) -> None:
        try:
            self.device_ids(num_hashes)
        except Exception:  # pragma: no cover — prefetch is best-effort
            pass

    def values(self, cap: int = -1) -> InternedValues:
        """Interned distinct values; ``cap`` >= 0 applies the TextStats
        freeze semantics (ops/text.py TextStats.of_column), cap < 0 counts
        exactly (OneHotEstimator's Counter).

        A cached interning is reused across cap requests whenever the
        results are provably identical: a non-frozen capped run equals the
        exact run, and an exact run with U distinct values equals any
        capped run with cap >= U (the freeze never engages)."""
        iv = self._interned.get(cap)
        if iv is None:
            iv = next((c for c in self._interned.values() if not c.frozen
                       and (cap < 0 or len(c.uniq) <= cap)), None)
        if iv is not None:
            REGISTRY.counter("text_profile.intern.hit").inc()
            return iv
        REGISTRY.counter("text_profile.intern.miss").inc()
        self._interned[cap] = _intern(self._strings, cap)
        return self._interned[cap]


def _py_scan(strings: Sequence, min_token_len: int = 1) -> TextProfile:
    """Pure-Python scan — same products as native/textprof.cpp scan()."""
    from .text import fnv1a_32, tokenize_text

    n = len(strings)
    null = np.zeros(n, bool)
    empty = np.zeros(n, bool)
    lengths = np.zeros(n, np.int32)
    crc = np.zeros(n, np.uint32)
    tok_lens = np.zeros(n, np.int32)
    hashes: List[int] = []
    for i, s in enumerate(strings):
        if s is None:
            null[i] = True
            continue
        lengths[i] = len(s)
        b = s.encode("utf-8")
        if not b:
            empty[i] = True
        crc[i] = zlib.crc32(b)
        toks = tokenize_text(s, min_token_len)
        tok_lens[i] = len(toks)
        hashes.extend(fnv1a_32(t) for t in toks)
    return TextProfile(null, empty, lengths, crc, tok_lens,
                       np.asarray(hashes, np.uint32))


def _py_intern(strings: Sequence, cap: int) -> InternedValues:
    table: Dict[str, int] = {}
    uniq: List[str] = []
    counts: List[int] = []
    codes = np.empty(len(strings), np.int32)
    for i, s in enumerate(strings):
        if s is None:
            codes[i] = -1
            continue
        # TextStats freeze (of_column): counting — inserts and increments
        # alike — happens only while the table holds <= cap distinct values
        can_count = cap < 0 or len(uniq) <= cap
        j = table.get(s)
        if j is not None:
            codes[i] = j
            if can_count:
                counts[j] += 1
            continue
        if not can_count:
            codes[i] = -2
            continue
        j = len(uniq)
        table[s] = j
        uniq.append(s)
        counts.append(1)
        codes[i] = j
    return InternedValues(uniq, np.asarray(counts, np.int64), codes, cap,
                          frozen=cap >= 0 and len(uniq) > cap)


def _interned_values(uniq, counts, codes, cap: int) -> InternedValues:
    return InternedValues(uniq, counts, codes, cap,
                          frozen=cap >= 0 and len(uniq) > cap)


def _intern(strings: np.ndarray, cap: int) -> InternedValues:
    from ..native import load

    native = load("textprof")
    if native is None:
        return _py_intern(strings, cap)
    return _interned_values(*native.intern(strings, cap), cap)


def _object_column(strings) -> np.ndarray:
    """``strings`` as the 1-D object array the native walk reads in place:
    itself when it is one already."""
    if isinstance(strings, np.ndarray) and strings.dtype == object \
            and strings.ndim == 1:
        return strings
    arr = np.empty(len(strings), dtype=object)
    arr[:] = strings
    return arr


def _splice_fallback(strings, lens, hashes, fallback, min_token_len):
    """Non-ASCII rows (``lens`` -1): the Python tokenizer's hashes spliced
    in at each row's place, for exact unicode case-folding parity."""
    from .text import fnv1a_32, tokenize_text

    rows = [[fnv1a_32(t) for t in tokenize_text(strings[i], min_token_len)]
            for i in fallback]
    counts = np.fromiter(map(len, rows), np.int64, count=len(rows))
    lens = lens.copy()
    lens[fallback] = 0
    at = np.cumsum(lens) - lens          # where each row's hashes start
    hashes = np.insert(
        hashes, np.repeat(at[fallback], counts),
        np.fromiter((h for r in rows for h in r), np.uint32,
                    count=int(counts.sum())))
    lens[fallback] = counts
    return lens, hashes


def scan_strings(strings, min_token_len: int = 1,
                 cap: Optional[int] = None) -> TextProfile:
    """Profile a string sequence (one native walk when available).  With a
    ``cap`` the same walk also interns the values, as ``values(cap)`` would
    by a second one."""
    from ..native import load

    strings = _object_column(strings)
    native = load("textprof")
    REGISTRY.counter("text_profile.scan").inc()
    if native is None:
        prof = _py_scan(strings, min_token_len)
    else:
        d = native.profile(strings, min_token_len, cap)
        lens, hashes = d["tok_lens"], d["tok_hash"]
        if d["fallback"].size:
            with span("text.python_tokenize", rows=int(d["fallback"].size)):
                lens, hashes = _splice_fallback(strings, lens, hashes,
                                                d["fallback"], min_token_len)
            REGISTRY.counter("text.rows_python_tokenized").inc(
                int(d["fallback"].size))
        prof = TextProfile(d["null"], d["empty"], d["lengths"], d["crc"],
                           lens, hashes)
        if cap is not None:
            prof._interned[cap] = _interned_values(
                d["uniq"], d["counts"], d["codes"], cap)
            REGISTRY.counter("text_profile.fused_intern").inc()
    prof._strings = strings
    return prof


def column_profile(col, cap: Optional[int] = None) -> TextProfile:
    """Profile of a text-kind Column, computed once and cached on the
    instance (Columns are immutable throughout the framework).  ``cap``
    matters only to the call that walks the column: it then interns too."""
    prof = getattr(col, "_text_profile", None)
    if prof is None:
        from .categorical import _col_strings
        prof = scan_strings(_col_strings(col), cap=cap)
        try:
            object.__setattr__(col, "_text_profile", prof)
        except Exception:  # pragma: no cover — exotic column subtype
            pass
    return prof


# Phase one of a walk holds the GIL for about a fifth of it, so past four or
# five walks at once the rest queue for it: on a 13-core and on a 30-core
# host four workers were as fast as eight and faster than one a core
# (PERF.md §5).
_MAX_WORKERS = 4


def pool_size(columns: int) -> int:
    """Worker threads ``profile_columns`` walks that many columns on: the
    cores this process may run on, up to ``_MAX_WORKERS``."""
    return min(columns, len(os.sched_getaffinity(0)), _MAX_WORKERS)


def profile_columns(columns: Sequence[Tuple[object, Optional[int]]]
                    ) -> Iterator[TextProfile]:
    """``column_profile(col, cap)`` of every (column, cap) pair, yielded in
    order, the columns walked side by side on ``pool_size`` worker threads,
    so the caller works on a profile while later columns are still walked.
    One worker is a plain loop."""
    from ..native import load

    workers = pool_size(len(columns))
    REGISTRY.gauge("text_profile.workers").set(workers)
    if workers <= 1:
        for col, cap in columns:
            yield column_profile(col, cap)
        return
    load("textprof")        # built and imported once, before the threads
    with ThreadPoolExecutor(workers) as pool:
        yield from pool.map(lambda cc: column_profile(*cc), columns)

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
pointers; hashing, tokenising and the intern table run with it released, and
so does the packing of the token ids into the words the device reads
(native ``pack_ids3``).  ``profile_columns`` therefore spreads a batch's
host text work over a few threads, as the cores the process may use allow
(``os.sched_getaffinity``), and its unit of work is a ROW RANGE of a column
that ends in its share of the packed wire: a short column is one range, a
long one is walked in order until its interning is settled (the *head*) and
as contiguous ranges side by side from there (the *tail*), and every range
then packs its own words into the column's one buffer.

The counters ``text_profile.scan`` (columns walked), ``.fused_intern``
(interned by that walk), ``.intern.hit`` / ``.intern.miss`` (``values(cap)``
answered from the cache / by another walk) count COLUMNS, however a column
was cut; each walk is a span ``prefetch.walk`` on the thread that runs it,
whose ``kind`` (``head``, ``range``, ``whole``) says how.  The jobs of a
train's prologue run on one ``HostPool``, which accounts for all of them:
``prologue.queue_s``, ``prologue.wait_s``, ``prologue.workers``.
``text.tokens`` / ``text.token_slots`` count
the tokens packed for the device and the id slots shipped for them
(``text.pack_ids`` is the span of a piece's packing; ``text.pack_native`` /
``text.pack_numpy`` say which code packed a column), and
``text.rows_python_tokenized`` the rows the native walk left to the Python
tokenizer (span ``text.python_tokenize``).  All consumers fall
back to pure Python when the native toolchain is absent — identical
results, slower.
"""

from __future__ import annotations

import os
import threading
import time
import zlib
from collections import deque
from concurrent.futures import (FIRST_COMPLETED, Future, ThreadPoolExecutor,
                                wait)
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass, field
from functools import partial
from typing import (Callable, Dict, Generator, Iterator, List, Optional,
                    Sequence, Tuple)

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
    # full FNV-1a per token, uint32, in row order: one piece a row range the
    # column was walked as (``tok_hash`` joins them for who wants one array)
    hash_pieces: List[np.ndarray]
    _interned: Dict[int, InternedValues] = field(default_factory=dict)
    _strings: Optional[np.ndarray] = None   # kept for lazy interning
    _host_words: Dict[int, np.ndarray] = field(default_factory=dict)
    _device_packed: Dict[int, object] = field(default_factory=dict)

    @property
    def tokens(self) -> int:
        return sum(int(p.size) for p in self.hash_pieces)

    @property
    def tok_hash(self) -> np.ndarray:
        """uint32[tokens]: the pieces joined, once."""
        if len(self.hash_pieces) != 1:
            self.hash_pieces = [np.concatenate(self.hash_pieces)]
        return self.hash_pieces[0]

    @property
    def presence(self) -> np.ndarray:
        """Present = non-null and non-empty (filters._value_presence)."""
        return ~(self.null | self.empty)

    def crc_hist(self, text_bins: int) -> np.ndarray:
        """Hashed whole-value distribution over present rows — exactly
        filters._histogram_of's text branch (crc32 % text_bins)."""
        present, h = self.presence, np.zeros(text_bins)
        for s in range(0, len(present), BLOCK_ROWS):    # temporaries in cache
            crc = self.crc[s:s + BLOCK_ROWS][present[s:s + BLOCK_ROWS]]
            h += np.bincount(crc % np.uint32(text_bins), minlength=text_bins)
        return h

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

    def pack_jobs(self, num_hashes: int
                  ) -> Tuple[np.ndarray, List[Callable[[], None]]]:
        """The column's packed token wire for ``num_hashes`` < 1024 (3 ×
        10-bit ids per int32 word, padded with sentinel words to its size
        class; ops/text.py ``_pack_ids3`` / ``_size_class`` / ``_sentinel3``
        define it) as a host buffer and the jobs that fill it: one a piece
        of ``hash_pieces``, each writing the words that start inside its
        piece (native ``pack_ids3``, the GIL released), so they may run side
        by side on any threads.  The buffer is whole when all have run.
        Without the native module one job packs the column in numpy."""
        from ..native import load
        from .text import _pack_ids3, _sentinel3, _size_class

        native = load("textprof")
        tokens = self.tokens
        out = np.empty(_size_class((tokens + 2) // 3), np.int32)
        attrs = dict(num_hashes=num_hashes, capacity=int(out.size))
        if native is None:
            REGISTRY.counter("text.pack_numpy").inc()

            def pack_all() -> None:
                with span("text.pack_ids", tokens=tokens, **attrs) as sp:
                    words = _pack_ids3(self.buckets(num_hashes)[1],
                                       num_hashes)
                    out[:words.size] = words
                    out[words.size:] = _sentinel3(num_hashes)
                    if sp is not None:
                        sp.attrs.update(words=int(words.size))
            return out, [pack_all]
        REGISTRY.counter("text.pack_native").inc()
        pieces = list(self.hash_pieces)

        def pack_piece(k: int, offset: int) -> None:
            # the lanes its last word has past the piece: the column's next
            # tokens, wherever they lie
            carry = [p[:2] for p in pieces[k + 1:] if p.size][:2]
            carry = (np.concatenate(carry)[:2] if carry
                     else np.empty(0, np.uint32))
            with span("text.pack_ids", tokens=int(pieces[k].size),
                      **attrs) as sp:
                words = native.pack_ids3(pieces[k], num_hashes, offset, out,
                                         carry, k == len(pieces) - 1)
                if sp is not None:
                    sp.attrs.update(words=words)

        offsets = np.cumsum([0] + [p.size for p in pieces[:-1]])
        return out, [partial(pack_piece, k, int(at))
                     for k, at in enumerate(offsets)]

    def device_ids(self, num_hashes: int):
        """Packed token-bucket ids resident on device (3 × 10-bit ids per
        int32 word; ops/text.py pack/scatter pair), cached per hash width.
        ``prefetch`` starts the async host→device transfer early so the
        slow link overlaps RFF/fit host work instead of serializing after
        it.  The words come from ``pack_jobs``: ``profile_columns`` has run
        them on its workers for the width a training batch asked for, and
        this call then only hands the buffer to the link; for a width nobody
        packed ahead (a score batch, the eager vectorizer paths) they run
        here, on the calling thread.  In numpy on the calling thread this
        was what the device waited for on free text — 4.07 s of the 10.9 s
        of ``prefetch.text_profiles`` in a 17.3 s train of 1,835,008 rows of
        83 tokens (PERF.md §6, PR 35).  None when the width needs the
        unpacked path."""
        if num_hashes >= 1024:
            return None
        dev = self._device_packed.get(num_hashes)
        if dev is None:
            import jax

            from ..profiling import add_host_link_bytes
            words = self._host_words.pop(num_hashes, None)
            if words is None:
                words, jobs = self.pack_jobs(num_hashes)
                for job in jobs:
                    job()
            dev = jax.device_put(words)       # async; consumers queue on it
            add_host_link_bytes(words.nbytes)
            REGISTRY.counter("text.tokens").inc(self.tokens)
            REGISTRY.counter("text.token_slots").inc(3 * int(words.size))
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
                       [np.asarray(hashes, np.uint32)])


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


def _splice_fallback(native, strings, lens, hashes, fallback, min_token_len):
    """Non-ASCII rows (``lens`` -1) tokenized as the Python tokenizer does
    (ops/text.py ``tokenize_text``: ``str.lower()``, then maximal runs of
    [a-z0-9_'], every other character a separator), for exact unicode
    case-folding parity, and their hashes spliced in at each row's place.
    Only the lowering needs Python: lowered, and with each character that is
    still not ASCII turned into a separator, a row is ASCII and the native
    walk hashes its tokens."""
    lowered = np.empty(len(fallback), dtype=object)
    lowered[:] = [strings[i].lower().encode("ascii", "replace").decode()
                  for i in fallback]
    d = native.profile(lowered, min_token_len)
    counts, theirs = d["tok_lens"], d["tok_hash"]
    lens = lens.copy()
    lens[fallback] = 0
    at = np.cumsum(lens) - lens          # where each row's hashes start
    # stretches of the walk's hashes and the rows' own, in turn.  What the
    # splice costs is this one fresh copy of the range's hashes (0.8 s for a
    # column's 573 MB on the chip's host; np.insert costs the same)
    parts, done = [], 0
    for cut, a, b in zip(at[fallback], np.cumsum(counts) - counts,
                         np.cumsum(counts)):
        parts += [hashes[done:cut], theirs[a:b]]
        done = cut
    lens[fallback] = counts
    return lens, np.concatenate(parts + [hashes[done:]])


ROW_FIELDS = ("null", "empty", "lengths", "crc", "tok_lens")


def _walk(native, strings: np.ndarray, min_token_len: int,
          cap: Optional[int], frozen: Optional[List[str]] = None,
          until_frozen: bool = False) -> dict:
    """One native walk of ``strings`` — a column or a row range of one —
    as ``native.profile`` returns it, the rows it left to the Python
    tokenizer spliced in.  ``until_frozen``: the walk ends with the block in
    which the capped table froze, and the per-row arrays with it.
    ``frozen``: the values of a table an earlier range froze; the walk only
    looks them up (``codes``)."""
    d = native.profile(strings, min_token_len, cap, frozen, until_frozen)
    if d["rows"] < len(strings):
        for f in ROW_FIELDS + ("codes",):
            d[f] = d[f][:d["rows"]]
    if d["fallback"].size:
        with span("text.python_tokenize", rows=int(d["fallback"].size)):
            d["tok_lens"], d["tok_hash"] = _splice_fallback(
                native, strings, d["tok_lens"], d["tok_hash"], d["fallback"],
                min_token_len)
        REGISTRY.counter("text.rows_python_tokenized").inc(
            int(d["fallback"].size))
    return d


def _profile_of(walks: List[dict], strings: np.ndarray,
                cap: Optional[int]) -> TextProfile:
    """The profile of a column from the walks of its row ranges, in row
    order: per-row products joined, token hashes left in pieces, the
    interning the first walk's (the only one that counted) with every
    range's codes."""
    def joined(f):
        return (walks[0][f] if len(walks) == 1
                else np.concatenate([w[f] for w in walks]))

    prof = TextProfile(*map(joined, ROW_FIELDS),
                       [w["tok_hash"] for w in walks])
    if cap is not None:
        prof._interned[cap] = _interned_values(
            walks[0]["uniq"], walks[0]["counts"], joined("codes"), cap)
        REGISTRY.counter("text_profile.fused_intern").inc()
    REGISTRY.counter("text_profile.scan").inc()
    prof._strings = strings
    return prof


def scan_strings(strings, min_token_len: int = 1,
                 cap: Optional[int] = None) -> TextProfile:
    """Profile a string sequence (one native walk when available).  With a
    ``cap`` the same walk also interns the values, as ``values(cap)`` would
    by a second one."""
    from ..native import load

    strings = _object_column(strings)
    native = load("textprof")
    if native is None:
        REGISTRY.counter("text_profile.scan").inc()
        prof = _py_scan(strings, min_token_len)
        prof._strings = strings
        return prof
    return _profile_of([_walk(native, strings, min_token_len, cap)], strings,
                       cap)


def column_profile(col, cap: Optional[int] = None) -> TextProfile:
    """Profile of a text-kind Column, computed once and cached on the
    instance (Columns are immutable throughout the framework).  ``cap``
    matters only to the call that walks the column: it then interns too."""
    prof = getattr(col, "_text_profile", None)
    if prof is None:
        from .categorical import _col_strings
        prof = _remember(col, scan_strings(_col_strings(col), cap=cap))
    return prof


def _remember(col, prof: TextProfile) -> TextProfile:
    try:
        object.__setattr__(col, "_text_profile", prof)
    except Exception:  # pragma: no cover — exotic column subtype
        pass
    return prof


# Phase one of a walk holds the GIL for about a fifth of it on one-token
# values, so past four or five walks at once the rest queue for it: on a
# 13-core and on a 30-core host four workers were as fast as eight and faster
# than one a core (PERF.md §6, PR 30).  Phase one is per ROW, so on free
# text's long rows it is a hundredth of a walk and ranges scale further: a
# column of 1,835,008 rows of 78 tokens took 2.34 s as 4 ranges on 4 workers
# and 1.71 s as 13 on 13 (a 13-core host; PERF.md §6, PR 35).  Four is kept:
# the one pool serves both kinds of column, and telling them apart is a
# scheduler that weighs a walk by the GIL share its head observed (ROADMAP
# S7).
_MAX_WORKERS = 4

# Rows a block of the native walk (native/textprof.cpp BLOCK_ROWS): a head
# ends on a block's edge, and ranges are cut there.
BLOCK_ROWS = 65536

# The smallest row range worth a work item of its own, in blocks; a column
# is cut only where its tail gives two such.  A walk of one-token values is
# bound by the GIL, not by cores, so cutting it buys nothing and costs the
# head's serial block, the extra items and the join: 26 columns of 786,432
# rows (12 blocks) took 0.45–0.48 s whole and 0.54–0.73 s cut at 4 blocks
# into 64 walks, on the same 4 workers (a 13-core host; PERF.md §6, PR 35).
# At 6 such a column stays whole, and free text's 28 blocks still give one
# range a worker.
MIN_RANGE_BLOCKS = 6


def pool_size(items: int) -> int:
    """Walks and packs ``profile_columns`` keeps under way at once for that
    many work items: the cores this process may run on, up to
    ``_MAX_WORKERS``."""
    return min(items, len(os.sched_getaffinity(0)), _MAX_WORKERS)


_JOB = threading.local()        # the HostPool job running on this thread


def _queued_s() -> float:
    """Seconds the ``HostPool`` job running on this thread was ready before
    a worker took it up; 0 on a thread that runs no such job."""
    return getattr(_JOB, "queued_s", 0.0)


def _union_s(intervals: List[Tuple[float, float]]) -> float:
    """Seconds covered by at least one of the (start, end) intervals."""
    total, reach = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b > reach:
            total += b - max(a, reach)
            reach = b
    return total


class HostPool:
    """The worker threads of one batch's host prologue and the one
    accounting of every job on them, whoever submits it: the walks and
    packs of ``profile_columns`` and RawFeatureFilter's distributions.  Of
    each job it notes when it was ready, when a worker started it and when
    it ended, and keeps in ``telemetry.REGISTRY``, with or without a tracer:

    * counter ``prologue.queue_s``: wall seconds in which a ready job was
      held back by a width — the pool's, every thread busy, or a
      submitter's (``_run_on`` keeps at most ``pool_size`` walks out).  The
      union over jobs, added when the pool closes; a job handed to a free
      worker when it is ready was held back by nothing.
    * counter ``prologue.wait_s``: seconds the calling thread was blocked
      on the pool's jobs (``wait``, ``join``); what it computes itself is
      not waiting.
    * gauge ``prologue.workers``: the pool's threads."""

    def __init__(self, workers: int):
        self.workers = workers
        self._threads = ThreadPoolExecutor(workers,
                                           thread_name_prefix="prologue")
        self._lock = threading.Lock()
        self._out = 0           # jobs submitted and not ended
        self._held: List[Tuple[float, float]] = []
        REGISTRY.counter("prologue.wait_s")
        REGISTRY.gauge("prologue.workers").set(workers)

    def submit(self, fn: Callable[[], object],
               ready: Optional[float] = None) -> Future:
        """Run ``fn`` on a worker.  ``ready``: the ``time.monotonic()`` at
        which the job became ready, where its submitter has held it back
        since; None: it is ready now."""
        with self._lock:
            now = time.monotonic()
            ready = now if ready is None else ready
            held = ready if self._out >= self.workers else None
            if held is None and ready < now:
                self._held.append((ready, now))
            self._out += 1
        future = self._threads.submit(self._run, fn, ready, held)
        future.add_done_callback(partial(self._dropped, held))
        return future

    def _run(self, fn, ready: float, held: Optional[float]):
        start = time.monotonic()
        _JOB.queued_s = start - ready
        try:
            return fn()
        finally:
            _JOB.queued_s = 0.0
            with self._lock:
                self._out -= 1
                if held is not None:
                    self._held.append((held, start))

    def _dropped(self, held: Optional[float], future: Future) -> None:
        """A job cancelled before a worker took it: ready until now."""
        if future.cancelled():
            with self._lock:
                self._out -= 1
                if held is not None:
                    self._held.append((held, time.monotonic()))

    def wait(self, futures) -> set:
        """Those of ``futures`` that are done, once one is."""
        t = time.monotonic()
        done = wait(futures, return_when=FIRST_COMPLETED).done
        REGISTRY.counter("prologue.wait_s").inc(time.monotonic() - t)
        return done

    def join(self, future: Future):
        """``future.result()``: what the job returned or raised."""
        if future.done():
            return future.result()
        t = time.monotonic()
        try:
            return future.result()
        finally:
            REGISTRY.counter("prologue.wait_s").inc(time.monotonic() - t)

    def close(self) -> None:
        """The threads shut down, the jobs no worker took dropped, and the
        seconds jobs were held back added to ``prologue.queue_s``."""
        self._threads.shutdown(cancel_futures=True)
        with self._lock:
            held, self._held = self._held, []
        REGISTRY.counter("prologue.queue_s").inc(_union_s(held))

    def __enter__(self) -> "HostPool":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


@contextmanager
def host_pool(free_jobs: int) -> Iterator[Optional[HostPool]]:
    """The ``HostPool`` of one batch's host prologue, opened once by who
    runs it (``Workflow.train``): ``profile_columns`` keeps its
    ``pool_size`` walks on it, bound by the GIL as they are, and
    ``free_jobs`` more items that hold no GIL while they pass over rows
    (RawFeatureFilter's distributions) take the cores beyond.  None on one
    core: every item then runs on the thread that asks for it."""
    workers = min(len(os.sched_getaffinity(0)), _MAX_WORKERS + free_jobs)
    if workers < 2:
        yield None
        return
    with HostPool(workers) as pool:
        yield pool


def _ranges(start: int, rows: int, most: int) -> List[Tuple[int, int]]:
    """Rows [start, rows) cut into at most ``most`` contiguous ranges of
    whole blocks (the last ends with the column), none under
    ``MIN_RANGE_BLOCKS`` unless it is the only one."""
    blocks = -(-(rows - start) // BLOCK_ROWS)
    count = max(1, min(most, blocks // MIN_RANGE_BLOCKS))
    edges = [start + (blocks * k // count) * BLOCK_ROWS
             for k in range(count)] + [rows]
    return list(zip(edges[:-1], edges[1:]))


def _planned_walks(rows: int, cap: Optional[int]) -> int:
    """Walks ``_column_plan`` cuts a column of that many rows into when its
    table freezes in its first block (all a plan can know before the walk):
    what ``pool_size`` is asked for."""
    head = 0 if cap is None else BLOCK_ROWS
    if (cap is not None and cap < 0) \
            or rows < head + 2 * MIN_RANGE_BLOCKS * BLOCK_ROWS:
        return 1
    return (head > 0) + len(_ranges(head, rows, _MAX_WORKERS))


Jobs = List[Callable[[], object]]


def _walking(column, kind: str, rows: Optional[int], fn: Callable, *args,
             **kw):
    """``fn(*args, **kw)``, one walk of ``column`` — ``whole``, its ``head``
    or a tail ``range`` — under the span ``prefetch.walk`` on the thread
    that runs it.  ``rows`` None: a head's, known when it has frozen."""
    with span("prefetch.walk", column=column, kind=kind, rows=rows,
              queued_s=_queued_s()) as sp:
        out = fn(*args, **kw)
        if sp is not None and rows is None:
            sp.attrs["rows"] = out["rows"]
        return out


def _column_plan(col, cap: Optional[int], num_hashes: Optional[int],
                 workers: int, column=None
                 ) -> Generator[Jobs, list, TextProfile]:
    """``column_profile(col, cap)`` and, for a ``num_hashes`` the packed
    wire serves, the column's host words, as steps of jobs: every job of a
    step may run beside the others, the step's results (in the jobs' order)
    are sent back in, and the profile is returned at the end.  ``column``
    names the column in its walks' spans.

    A long column is walked by row range.  The *head*: whole blocks from row
    0, in order, until the interning is settled — no rows when no cap is
    asked, every row when the table never freezes (an exact count, or fewer
    distinct values than the cap: the one walk of a short column).  The
    *tail*: the rows left, as contiguous ranges that only look the head's
    frozen values up, so that they are independent of each other; their
    per-row products join in row order and equal the one walk's exactly.
    Then each range packs its own words."""
    from ..native import load
    from .categorical import _col_strings

    prof = getattr(col, "_text_profile", None)
    if prof is None:
        strings = _object_column(_col_strings(col))
        native = load("textprof")
        rows = len(strings)
        if native is None or _planned_walks(rows, cap) == 1:
            walks = yield [partial(_walking, column, "whole", rows,
                                   scan_strings, strings, cap=cap)]
            prof = walks[0]
        else:
            walks = []
            if cap is not None:
                walks = yield [partial(_walking, column, "head", None, _walk,
                                       native, strings, 1, cap,
                                       until_frozen=True)]
            done = walks[0]["rows"] if walks else 0
            if done < rows:
                frozen = walks[0]["uniq"] if walks else None
                walks += yield [partial(_walking, column, "range", b - a,
                                        _walk, native, strings[a:b], 1, cap,
                                        frozen)
                                for a, b in _ranges(done, rows, workers)]
            prof = _profile_of(walks, strings, cap)
        _remember(col, prof)
    if num_hashes and num_hashes < 1024 \
            and num_hashes not in prof._device_packed:
        words, jobs = prof.pack_jobs(num_hashes)
        yield jobs
        prof._host_words[num_hashes] = words
    return prof


def _run_here(plan: Generator[Jobs, list, TextProfile]) -> TextProfile:
    """``plan``'s jobs one after another on the calling thread."""
    results = None
    while True:
        try:
            jobs = plan.send(results)
        except StopIteration as stop:
            return stop.value
        results = [job() for job in jobs]


def _run_on(pool: HostPool,
            plans: List[Generator[Jobs, list, TextProfile]],
            width: int) -> Iterator[TextProfile]:
    """Every plan's jobs on ``pool``, at most ``width`` of them out at once
    (the pool may be wider, and others' work on it), all plans under way
    together, each plan's profile yielded in the plans' order as soon as it
    is whole.  The plans themselves advance on the calling thread, between
    the waits; a job the width holds back is handed to the pool with the
    time it became ready, for ``prologue.queue_s``."""
    held = deque()      # (plan, job, the callable, ready since) not on the pool
    running = {}        # future -> (plan, job) it is
    step = {}           # plan -> [results so far, jobs still out]
    whole = {}          # plan -> its profile

    def advance(i: int, results: Optional[list]) -> None:
        try:
            jobs = plans[i].send(results)
        except StopIteration as stop:
            whole[i] = stop.value
            return
        step[i] = [[None] * len(jobs), len(jobs)]
        for k, job in enumerate(jobs):
            if held or len(running) >= width:
                held.append((i, k, job, time.monotonic()))
            else:
                running[pool.submit(job)] = (i, k)

    def submit() -> None:
        while held and len(running) < width:
            i, k, job, ready = held.popleft()
            running[pool.submit(job, ready)] = (i, k)

    try:
        for i in range(len(plans)):
            advance(i, None)
        for i in range(len(plans)):
            while i not in whole:
                submit()
                for done in pool.wait(running):
                    j, k = running.pop(done)
                    step[j][0][k] = done.result()   # raises what the job did
                    step[j][1] -= 1
                    if not step[j][1]:
                        advance(j, step.pop(j)[0])
            submit()        # the caller may take its time with a profile
            yield whole.pop(i)
    finally:
        for future in running:      # a caller that stopped early, or a raise
            future.cancel()


def profile_columns(columns: Sequence[Tuple[object, Optional[int],
                                            Optional[int]]],
                    pool: Optional[HostPool] = None,
                    names: Optional[Sequence[str]] = None
                    ) -> Iterator[TextProfile]:
    """``column_profile(col, cap)`` of every (column, cap, num_hashes)
    triple, yielded in order, each with its packed words for ``num_hashes``
    ready on the host (``TextProfile.device_ids`` then only starts the
    transfer), the work spread over ``pool_size`` worker threads by row
    range (``_column_plan``), so the caller works on a profile while later
    columns are still walked.  The threads are ``pool``'s (``host_pool``,
    which a train opens for its whole prologue), or a ``HostPool`` of its
    own for the length of the call.  One worker is a plain loop.  ``names``
    (the columns' positions by default) name them in their walks' spans."""
    from ..native import load

    walks = sum(_planned_walks(len(col), cap) for col, cap, _ in columns
                if getattr(col, "_text_profile", None) is None)
    workers = pool_size(max(walks, len(columns)))
    plans = [_column_plan(col, cap, num_hashes, workers, name)
             for (col, cap, num_hashes), name in zip(
                 columns, range(len(columns)) if names is None else names)]
    if workers <= 1:
        yield from map(_run_here, plans)
        return
    load("textprof")        # built and imported once, before the threads
    with (nullcontext(pool) if pool is not None
          else HostPool(workers)) as threads:
        yield from _run_on(threads, plans, workers)

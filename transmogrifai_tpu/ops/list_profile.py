"""One walk of a date-list column, or of a URL or e-mail column's strings,
shared by every host consumer of it.

A ``DateList`` column is held ragged (``columns.RaggedList``: every row's
dates in one int64 array and the offsets of each row's first; an object
array of lists is converted once, ``columns.ragged_of``).  Its walk
(``date_profile``: native/listprof.cpp ``date_lists``, the GIL released)
gives each row's least and greatest date, what ``DateListVectorizer``'s
``SinceFirst`` and ``SinceLast`` pivots ship, and the hashed-item histogram
of RawFeatureFilter's text branch at a ``text_bins`` (zlib's crc32 of each
date's decimal string, as ``filters._stable_text_bin`` bins it), so the
filter reads its distribution from the same walk.

A URL column's hosts and an e-mail column's domains (``domain_values``:
native ``domains``) are interned by one walk, as ``text_specialized``'s
``url_domain`` and ``email_parts`` give them (a row the native rules do not
cover is handed to those functions): an ``InternedValues`` of the derived
values, so that a pivot of the domains fits and makes its ids from arrays
(``ops.categorical``), as it does for a string column.

Both are cached on the Column (columns are immutable throughout the
framework).  A walk is the span ``prefetch.list_walk`` (attrs ``column``,
``kind`` ``dates`` / ``domain``, ``rows``, ``values``) on the thread that
runs it: a worker of a train's prologue pool, where ``Workflow`` starts them
in its prefetch, else whoever first reads the column.  Counters
``list_profile.columns`` and ``list_profile.values`` (the dates walked, or
the rows a domain was found in) count what was walked.  Without the native helper the
same products come from numpy and Python, identical.
"""

from __future__ import annotations

import threading
import zlib
from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np

from ..columns import Column, RaggedList, ragged_of
from ..telemetry import REGISTRY, span
from .text_profile import InternedValues

URL_KIND, EMAIL_KIND = 0, 1

# one walk a column at a time: a pool job and a consumer on the calling
# thread may ask for the same column at once (``_LOCK`` only hands out the
# columns' own locks, so that two columns walk side by side)
_LOCK = threading.Lock()


def _column_lock(col: Column) -> threading.Lock:
    with _LOCK:
        lock = getattr(col, "_walk_lock", None)
        if lock is None:
            lock = threading.Lock()
            object.__setattr__(col, "_walk_lock", lock)
        return lock


@dataclass
class DateListProfile:
    """What one walk of a date-list column gives."""

    lengths: np.ndarray           # int64[N]: dates a row
    lo: np.ndarray                # int64[N]: least date (0 when empty)
    hi: np.ndarray                # int64[N]: greatest date (0 when empty)
    hist: Optional[np.ndarray]    # float64[text_bins]: crc32 bins of dates,
    #                               None where no histogram was asked for

    @property
    def present(self) -> np.ndarray:
        """A row with at least one date (an empty list is null)."""
        return self.lengths > 0


def _count(values: int) -> None:
    REGISTRY.counter("list_profile.columns").inc()
    REGISTRY.counter("list_profile.values").inc(values)


def _walk_dates(ragged: RaggedList, text_bins: int) -> DateListProfile:
    from ..native import load
    native = load("listprof")
    lengths = ragged.lengths()
    if native is not None:
        lo, hi, hist = native.date_lists(ragged.values, ragged.offsets,
                                         text_bins)
    else:
        lo = np.zeros(len(lengths), np.int64)
        hi = np.zeros(len(lengths), np.int64)
        full = lengths > 0
        if full.any():
            starts = ragged.offsets[:-1][full]
            lo[full] = np.minimum.reduceat(ragged.values, starts)
            hi[full] = np.maximum.reduceat(ragged.values, starts)
        hist = np.zeros(text_bins)
        if text_bins:
            uniq, counts = np.unique(ragged.values, return_counts=True)
            bins = np.fromiter(
                (zlib.crc32(str(v).encode("utf-8")) % text_bins
                 for v in uniq.tolist()), np.int64, len(uniq))
            hist = np.bincount(bins, weights=counts, minlength=text_bins
                               ).astype(np.float64)
    return DateListProfile(lengths, lo, hi, hist if text_bins else None)


def date_profile(col: Column, text_bins: int = 0,
                 name: Optional[str] = None) -> DateListProfile:
    """The walk of a date-list column, with RawFeatureFilter's histogram at
    ``text_bins`` where that is not 0; a cached walk answers every request
    it covers (one with the same ``text_bins``, or any for none)."""
    with _column_lock(col):
        held: Dict[int, DateListProfile] = getattr(col, "_date_profiles",
                                                   None) or {}
        prof = held.get(text_bins) or (
            next(iter(held.values()), None) if not text_bins else None)
        if prof is not None:
            return prof
        ragged = ragged_of(col)
        with span("prefetch.list_walk", column=name, kind="dates",
                  rows=len(ragged), values=int(ragged.values.size)):
            prof = _walk_dates(ragged, int(text_bins))
        _count(int(ragged.values.size))
        held[int(text_bins)] = prof
        try:
            object.__setattr__(col, "_date_profiles", held)
        except Exception:  # pragma: no cover — exotic column subtype
            pass
        return prof


def domain_kind(kind) -> Optional[int]:
    """The walk of a feature kind whose domain is what a pivot counts: URL
    or Email, else None."""
    from ..types import URL, Email
    if issubclass(kind, URL):
        return URL_KIND
    if issubclass(kind, Email):
        return EMAIL_KIND
    return None


def _python_domain(which: int):
    from .text_specialized import email_parts, url_domain
    if which == URL_KIND:
        return url_domain
    return lambda s: email_parts(s)[1]


def _first_seen(uniq: List[str], counts: np.ndarray, codes: np.ndarray
                ) -> InternedValues:
    """An interning laid out in the order each value is first seen in the
    rows, what a walk of the derived strings would give."""
    rows = np.flatnonzero(codes >= 0)
    first = np.full(len(uniq), len(codes), np.int64)
    np.minimum.at(first, codes[rows], rows)
    order = np.argsort(first, kind="stable")
    place = np.empty(len(uniq), np.int32)
    place[order] = np.arange(len(uniq), dtype=np.int32)
    codes = np.where(codes >= 0, place[np.maximum(codes, 0)], codes)
    return InternedValues([uniq[i] for i in order.tolist()],
                          np.asarray(counts, np.int64)[order],
                          codes.astype(np.int32), -1, frozen=False)


def _walk_domains(strings: np.ndarray, which: int) -> InternedValues:
    from ..native import load
    native = load("listprof")
    domain_of = _python_domain(which)
    if native is None:
        uniq, counts, codes = [], [], np.full(len(strings), -3, np.int32)
    else:
        uniq, counts, codes = native.domains(strings, which)
        counts = list(counts.tolist())
    left = np.flatnonzero(codes == -3)
    if not left.size:
        return InternedValues(uniq, np.asarray(counts, np.int64), codes, -1,
                              frozen=False)
    # the rows urllib's own rules decide, interned beside the walk's
    table = {v: i for i, v in enumerate(uniq)}
    for i in left.tolist():
        d = domain_of(strings[i])
        if d is None:
            codes[i] = -1
            continue
        j = table.get(d)
        if j is None:
            j = table[d] = len(uniq)
            uniq.append(d)
            counts.append(0)
        counts[j] += 1
        codes[i] = j
    return _first_seen(uniq, np.asarray(counts, np.int64), codes)


def domain_values(col: Column, kind, name: Optional[str] = None
                  ) -> InternedValues:
    """The interned domains of a URL or Email column (``domain_kind``), one
    walk, cached on the Column."""
    which = domain_kind(kind)
    if which is None:
        raise TypeError(f"{kind.__name__} has no domain")
    with _column_lock(col):
        held: Dict[int, InternedValues] = getattr(col, "_domains", None) or {}
        iv = held.get(which)
        if iv is not None:
            return iv
        from .categorical import _col_strings
        from .text_profile import _object_column
        strings = _object_column(_col_strings(col))
        with span("prefetch.list_walk", column=name, kind="domain",
                  rows=len(strings)) as sp:
            iv = _walk_domains(strings, which)
            found = int(np.count_nonzero(iv.codes >= 0))
            if sp is not None:
                sp.attrs["values"] = found
        _count(found)
        held[which] = iv
        try:
            object.__setattr__(col, "_domains", held)
        except Exception:  # pragma: no cover — exotic column subtype
            pass
        return iv


def domain_column(iv: InternedValues, kind) -> Column:
    """The derived column of an interning: each row's domain (None where it
    has none), an object array made by one gather."""
    table = np.empty(len(iv.uniq) + 1, dtype=object)
    table[:-1] = iv.uniq
    table[-1] = None
    return Column(kind, table[np.where(iv.codes >= 0, iv.codes, -1)])

"""One-pass columnar expansions of map and set columns, cached on the Column
instance (native/mapprof.cpp; reference analogs: the per-key map expansion in
OPMapVectorizer.scala and RawFeatureFilter's PreparedFeatures).

Every host consumer of a RealMap/IntegralMap-like column — RawFeatureFilter
ranges + histograms, MapVectorizer fit fills + transform — used to walk the
million-dict object array independently.  ``map_expansion`` walks it ONCE
(native when available) into dense arrays all consumers share.

Columns containing bools or non-numeric values return ``None`` and callers
keep their exact Python paths (bool handling differs per consumer in pinned
ways; see filters.numeric_ranges vs filters._histogram_of).

A string-valued map (``string_map_expansion``) and a column of sets
(``set_expansion``) are walked the same way into interned values and their
counts, which RawFeatureFilter's distributions and the fits and wires of
``TextMapPivotVectorizer`` and ``MultiPickListVectorizer`` read.  A train's
prologue pool walks them as jobs in its prefetch phase
(``Workflow._prefetch_text_profiles``).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np


@dataclass
class MapExpansion:
    keys: List[str]           # first-occurrence order
    vals: np.ndarray          # float64[N, K], NaN where absent/None
    present: np.ndarray       # bool[N, K]  (value present and not None)
    in_dict: np.ndarray       # int64[K]    (key in dict, even if value None)
    nonempty: np.ndarray      # bool[N]     (row is a non-empty dict)

    def key_index(self) -> Dict[str, int]:
        return {k: j for j, k in enumerate(self.keys)}


def _py_expand(maps) -> Optional[MapExpansion]:
    n = len(maps)
    key_ids: Dict[str, int] = {}
    cols: List[np.ndarray] = []
    pres: List[np.ndarray] = []
    in_dict: List[int] = []
    nonempty = np.zeros(n, bool)
    for i, m in enumerate(maps):
        if m is None:
            continue
        if not isinstance(m, dict):
            return None
        if m:
            nonempty[i] = True
        for k, v in m.items():
            if not isinstance(k, str):
                return None
            j = key_ids.get(k)
            if j is None:
                j = len(cols)
                key_ids[k] = j
                cols.append(np.full(n, np.nan))
                pres.append(np.zeros(n, bool))
                in_dict.append(0)
            in_dict[j] += 1
            if v is None:
                continue
            if isinstance(v, bool) or not isinstance(
                    v, (int, float, np.integer, np.floating)):
                return None
            cols[j][i] = float(v)
            pres[j][i] = True
    K = len(cols)
    vals = (np.stack(cols, axis=1) if K else np.zeros((n, 0)))
    present = (np.stack(pres, axis=1) if K else np.zeros((n, 0), bool))
    return MapExpansion(list(key_ids), vals, present,
                        np.asarray(in_dict, np.int64), nonempty)


def expand_maps(maps) -> Optional[MapExpansion]:
    from ..native import load

    native = load("mapprof")
    if native is None:
        return _py_expand(maps)
    try:
        keys, vals, present, in_dict, nonempty = native.expand(list(maps))
    except TypeError:
        return None     # bool / non-numeric values → exact Python paths
    return MapExpansion(list(keys), vals, present.astype(bool), in_dict,
                        nonempty.astype(bool))


_MISS = object()


def _remember(col, attr: str, value):
    try:
        object.__setattr__(col, attr, value)
    except Exception:  # pragma: no cover — exotic column subtype
        pass
    return value


def map_expansion(col) -> Optional[MapExpansion]:
    """Cached columnar expansion of a map Column (None when the values are
    not purely numeric — callers fall back to their Python paths)."""
    cached = getattr(col, "_map_expansion", _MISS)
    if cached is _MISS:
        cached = _remember(col, "_map_expansion", expand_maps(col.values))
    return cached


# ---------------------------------------------------------------------------
# String-valued maps (PickListMap and its kin) and sets (MultiPickList)
# ---------------------------------------------------------------------------

def key_order(in_dict: Dict[str, int]) -> List[str]:
    """Map keys by the rows that hold them (descending), then by key: the
    one order a map's keys are cut and laid out in."""
    return sorted(in_dict, key=lambda k: (-in_dict[k], k))


@dataclass
class StringMapExpansion:
    """One walk of a string-valued map column: its keys in ``key_order``,
    and for every key the rows' interned values (``str(v)`` of a value that
    is no str) with their counts."""
    keys: List[str]
    in_dict: np.ndarray       # int64[K]: rows whose dict holds the key
    ids: List[np.ndarray]     # int32[N] a key: into uniq[k], -1 absent/None
    uniq: List[List[str]]     # a key's distinct values, first seen first
    counts: List[np.ndarray]  # int64[U] a key: rows holding the value
    nonempty: np.ndarray      # bool[N]: the row is a non-empty dict
    # rows that are neither None nor a dict (read as empty maps) and values
    # that are lists, tuples or sets (interned as their str): where there
    # are any, RawFeatureFilter walks the rows as it always has
    inexact: int

    def key_index(self) -> Dict[str, int]:
        return {k: j for j, k in enumerate(self.keys)}

    def value_counts(self, j: int) -> Dict[str, int]:
        return dict(zip(self.uniq[j], self.counts[j].tolist()))


@dataclass
class SetExpansion:
    """One walk of a column of sets: every row's distinct values (by their
    str) as compressed rows, interned once for the column."""
    offsets: np.ndarray       # int64[N + 1]
    ids: np.ndarray           # int32[nnz]: a value once a row, into uniq
    uniq: List[str]           # first seen first
    counts: np.ndarray        # int64[U]: rows holding the value
    items: np.ndarray         # int64[U]: items, duplicates in a row counted
    nonempty: np.ndarray      # bool[N]: the row holds an item
    inexact: int              # frozensets and rows of another type

    def value_counts(self) -> Dict[str, int]:
        return dict(zip(self.uniq, self.counts.tolist()))

    def rows(self) -> np.ndarray:
        """int32[nnz]: the row of every entry of ``ids``."""
        return np.repeat(np.arange(len(self.offsets) - 1, dtype=np.int32),
                         np.diff(self.offsets))


def _ordered(keys, in_dict, ids, uniq, counts, nonempty, inexact
             ) -> StringMapExpansion:
    by_key = dict(zip(keys, range(len(keys))))
    order = [by_key[k] for k in key_order(dict(zip(keys, in_dict)))]
    return StringMapExpansion(
        [keys[j] for j in order], np.asarray(in_dict, np.int64)[order],
        [ids[j] for j in order], [list(uniq[j]) for j in order],
        [np.asarray(counts[j], np.int64) for j in order],
        np.asarray(nonempty).astype(bool), int(inexact))


def _py_expand_strings(maps) -> StringMapExpansion:
    n = len(maps)
    key_ids: Dict[str, int] = {}
    ids: List[np.ndarray] = []
    tables: List[Dict[str, int]] = []
    uniq: List[List[str]] = []
    counts: List[List[int]] = []
    in_dict: List[int] = []
    nonempty = np.zeros(n, bool)
    inexact = 0
    for i, m in enumerate(maps):
        if m is None:
            continue
        if not isinstance(m, dict):
            inexact += 1
            continue
        nonempty[i] = bool(m)
        for k, v in m.items():
            j = key_ids.get(k)
            if j is None:
                j = key_ids[k] = len(ids)
                ids.append(np.full(n, -1, np.int32))
                tables.append({})
                uniq.append([])
                counts.append([])
                in_dict.append(0)
            in_dict[j] += 1
            if v is None:
                continue
            inexact += isinstance(v, (list, tuple, set))
            s = v if isinstance(v, str) else str(v)
            vid = tables[j].get(s)
            if vid is None:
                vid = tables[j][s] = len(uniq[j])
                uniq[j].append(s)
                counts[j].append(0)
            counts[j][vid] += 1
            ids[j][i] = vid
    return _ordered(list(key_ids), in_dict, ids, uniq, counts, nonempty,
                    inexact)


def _py_expand_sets(rows) -> SetExpansion:
    n = len(rows)
    table: Dict[str, int] = {}
    uniq: List[str] = []
    counts: List[int] = []
    items: List[int] = []
    offsets = np.zeros(n + 1, np.int64)
    ids: List[int] = []
    nonempty = np.zeros(n, bool)
    inexact = 0
    for i, s in enumerate(rows):
        if s is None:
            continue
        inexact += not isinstance(s, (list, tuple, set))
        seen: List[int] = []
        for v in s:
            v = v if isinstance(v, str) else str(v)
            vid = table.get(v)
            if vid is None:
                vid = table[v] = len(uniq)
                uniq.append(v)
                counts.append(0)
                items.append(0)
            items[vid] += 1
            if vid not in seen:
                seen.append(vid)
        for vid in seen:
            counts[vid] += 1
        ids.extend(seen)
        offsets[i + 1] = len(seen)
        nonempty[i] = len(s) > 0
    return SetExpansion(np.cumsum(offsets), np.asarray(ids, np.int32), uniq,
                        np.asarray(counts, np.int64),
                        np.asarray(items, np.int64), nonempty, inexact)


def expand_string_maps(maps) -> StringMapExpansion:
    """``StringMapExpansion`` of ``maps`` (native/mapprof.cpp, or the same
    walk in Python where it cannot read them: no toolchain, a key that is no
    str)."""
    from ..native import load

    native = load("mapprof")
    if native is not None:
        try:
            return _ordered(*native.expand_strings(maps))
        except (TypeError, UnicodeError):
            pass
    return _py_expand_strings(maps)


def expand_sets(rows) -> SetExpansion:
    """``SetExpansion`` of ``rows`` (native/mapprof.cpp, or the same walk in
    Python where it cannot read them: no toolchain, a frozenset or a row of
    another type)."""
    from ..native import load

    native = load("mapprof")
    if native is not None:
        try:
            offsets, ids, uniq, counts, items, nonempty = \
                native.expand_sets(rows)
            return SetExpansion(offsets, ids, list(uniq), counts, items,
                                nonempty.astype(bool), 0)
        except (TypeError, UnicodeError):
            pass
    return _py_expand_sets(rows)


def _expanded(col, attr: str, kind: str, make, name: Optional[str]):
    from ..telemetry import REGISTRY, span
    cached = getattr(col, attr, None)
    if cached is None:
        with span("prefetch.map_walk", column=name, kind=kind,
                  rows=len(col)) as sp:
            cached = _remember(col, attr, make(col.values))
            keys = len(cached.keys) if kind == "map" else 1
            if sp is not None:
                sp.attrs["keys"] = keys
        REGISTRY.counter("map_profile.columns").inc()
        REGISTRY.counter("map_profile.keys").inc(keys)
    return cached


def string_map_expansion(col, name: Optional[str] = None
                         ) -> StringMapExpansion:
    """The ``StringMapExpansion`` of a string-valued map Column, walked once
    and cached on it; the walk is the span ``prefetch.map_walk`` (attrs
    ``column``, ``kind`` map, ``rows``, ``keys``) wherever it runs."""
    return _expanded(col, "_string_map_expansion", "map", expand_string_maps,
                     name)


def set_expansion(col, name: Optional[str] = None) -> SetExpansion:
    """The ``SetExpansion`` of a Column of sets, walked once and cached on
    it (span ``prefetch.map_walk``, ``kind`` set)."""
    return _expanded(col, "_set_expansion", "set", expand_sets, name)


def is_string_map(kind) -> bool:
    """A map kind whose values are strings (PickListMap, TextMap and their
    kin): what ``string_map_expansion`` reads, every map kind whose values
    are no numbers and no collections."""
    from ..types import (OPCollection, is_map_kind, is_numeric_kind,
                         map_value_kind)
    if not is_map_kind(kind):
        return False
    vk = map_value_kind(kind)
    return not (is_numeric_kind(vk) or issubclass(vk, OPCollection))


def is_set_kind(kind) -> bool:
    from ..types import OPSet
    return issubclass(kind, OPSet)

"""Plain reference of the ``yelp_review_maps`` configuration: the feature
matrix ``transmogrify`` is stated to make of the joined review, business and
member (configs/yelp_review_maps.json), SanityChecker's statistics and rules
with the Cramér's V rule on every group of pivot columns (a key of a map is a
group of its own, a set's pivot one group), then ``common.sweep`` over the
two linear families.  Imports nothing of the program.

Blocks of columns in the order transmogrify lays them out (its groups sorted
by name, the features of a group in the schema's order): ``is_open`` (value,
null); the six pivots (business_id, user_id, address, city, state,
postal_code: top-k indicators, OTHER, null); the two dates (sin and cos of
the four circular periods, null); the coordinate (latitude, longitude,
accuracy, null); the seventeen counts (value, null); ``attributes`` and
``hours``, key by key (the first ``max_keys`` keys by the rows that hold
them, descending, then by key, laid out by key), each key a pivot of its
values; ``categories`` and ``elite``, each a membership pivot (top-k
indicators of the values in a row's set, OTHER, null); the two ratings
(value, null); the name (512 hashed term counts, null).

A value of a map or a set is its ``str``; a set's value counts once a row.
The circular periods, the geolocation fill and ``OpLinearSVC``'s squared
hinge are the program's departures from upstream, noted in
``reference/nyc_taxi_typed.py`` and ``reference/amazon_polarity_text.py``,
whose pieces this imports.
"""

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from . import common, plain
from .amazon_polarity_text import column_tokens, svc_family
from .criteo_mixed import mode_filled
from .nyc_taxi_typed import geo_filled, unit_circle

PIVOTS = ("business_id", "user_id", "address", "city", "state",
          "postal_code")
DATES = ("date", "yelping_since")
GEOS = ("location",)
INTEGRALS = ("business_review_count", "user_review_count", "fans", "useful",
             "funny", "cool", "compliment_hot", "compliment_more",
             "compliment_profile", "compliment_cute", "compliment_list",
             "compliment_note", "compliment_plain", "compliment_cool",
             "compliment_funny", "compliment_writer", "compliment_photos")
BINARIES = ("is_open",)
MAPS = ("attributes", "hours")
SETS = ("categories", "elite")
REALS = ("business_stars", "average_stars")
TEXTS = ("name",)
MAX_KEYS = 100          # TextMapPivotVectorizer's default
WORKERS = 6
FAMILIES = {"OpLogisticRegression": common.logistic_family,
            "OpLinearSVC": svc_family}
question = common.winner_question


def top_slots(counts, values, top_k, min_support):
    """slot [U] int32 of every distinct value: its place among the top_k
    values by (count desc, value asc) with at least ``min_support`` rows,
    else OTHER (= the number kept); and that number."""
    order = sorted((j for j in range(len(values))
                    if counts[j] >= min_support),
                   key=lambda j: (-counts[j], values[j]))[:top_k]
    slot = np.full(len(values), len(order), np.int32)
    slot[order] = np.arange(len(order), dtype=np.int32)
    return slot, len(order)


def map_pivots(maps, top_k, min_support):
    """[(ids [N] int32, width)] of a map column's keys in the order laid out:
    a row's slot of the key's value (OTHER past the top values, null where
    the key is absent or its value None)."""
    n = len(maps)
    in_dict, seen = {}, {}
    for i, m in enumerate(maps):
        if not m:
            continue
        for k, v in m.items():
            in_dict[k] = in_dict.get(k, 0) + 1
            if v is None:
                continue
            table, rows, codes = seen.setdefault(k, ({}, [], []))
            v = v if isinstance(v, str) else str(v)
            code = table.get(v)
            if code is None:
                code = table[v] = len(table)
            rows.append(i)
            codes.append(code)
    keys = sorted(sorted(in_dict, key=lambda k: (-in_dict[k], k))[:MAX_KEYS])
    out = []
    for k in keys:
        table, rows, codes = seen.get(k, ({}, [], []))
        codes = np.asarray(codes, np.int64)
        slot, kept = top_slots(np.bincount(codes, minlength=len(table)),
                               list(table), top_k, min_support)
        ids = np.full(n, kept + 1, np.int32)                 # null
        ids[np.asarray(rows, np.int64)] = slot[codes]
        out.append((ids, kept + 2))
    return out


def set_pivot(sets, top_k, min_support):
    """(rows [P] int32, slots [P] int32, null [N] bool, width) of a set
    column: a (row, slot) pair for each distinct value of a row's set, OTHER
    for a value past the top ones (a pair per such value: the indicator is
    set, not added)."""
    n = len(sets)
    table, rows, codes = {}, [], []
    null = np.ones(n, bool)
    for i, s in enumerate(sets):
        if not s:
            continue
        null[i] = False
        for v in {v if isinstance(v, str) else str(v) for v in s}:
            code = table.get(v)
            if code is None:
                code = table[v] = len(table)
            rows.append(i)
            codes.append(code)
    codes = np.asarray(codes, np.int64)
    slot, kept = top_slots(np.bincount(codes, minlength=len(table)),
                           list(table), top_k, min_support)
    return (np.asarray(rows, np.int32), slot[codes].astype(np.int32), null,
            kept + 2)


def numeric_blocks(data, config, precision):
    """The float32 host columns of the matrix in three runs: before the
    six pivots (``is_open``), between them and the maps (dates, coordinate,
    counts), after the sets (ratings)."""
    t = config["transmogrify"]
    n = len(data["label"])
    every = np.ones(n, bool)
    nothing = np.zeros(n, np.float32)
    first = []
    for name in BINARIES:
        first += [np.asarray(data[name], np.float32), nothing]
    middle = []
    for name in DATES:
        for p in t["circular_date_periods"]:
            middle += unit_circle(data[name], p)
        middle.append(nothing)
    for name in GEOS:
        middle += geo_filled(data[name], data[name + ".present"],
                             precision.wire)
    for name in INTEGRALS:
        middle += mode_filled(data[name], every, precision.wire)
    last = []
    for name in REALS:
        last += plain.mean_filled(data[name], every, precision.wire)
    return first, middle, last


def host_parts(data, config):
    """The pivots, the maps' key pivots and the sets' pairs, each worked out
    on a thread of its own."""
    t = config["transmogrify"]
    k, s = t["top_k"], t["min_support"]
    jobs = ([(plain.pivot_ids, data[c], k, s) for c in PIVOTS]
            + [(map_pivots, data[c], k, s) for c in MAPS]
            + [(set_pivot, data[c], k, s) for c in SETS])
    with ThreadPoolExecutor(min(WORKERS, len(os.sched_getaffinity(0)))
                            ) as pool:
        done = list(pool.map(lambda j: j[0](*j[1:]), jobs))
    pivots = done[:len(PIVOTS)]
    keys = [p for m in done[len(PIVOTS):len(PIVOTS) + len(MAPS)] for p in m]
    sets = done[len(PIVOTS) + len(MAPS):]
    return pivots, keys, sets


def feature_matrix(data, config, precision, parts):
    """The stored matrix, built on the device block of rows by block of rows
    from compact host columns."""
    import jax
    import jax.numpy as jnp
    pivots, keys, sets = parts
    H = config["transmogrify"]["num_hashes"]
    n = len(data["label"])
    first, middle, last = numeric_blocks(data, config, precision)
    storage = plain.jnp_dtype(common.storage_of(config, precision))
    width = (len(first) + sum(w for _, w in pivots) + len(middle)
             + sum(w for _, w in keys) + sum(s[3] for s in sets) + len(last)
             + len(TEXTS) * (H + 1))
    bounds = plain.BlockedMatrix.bounds_for(n, width)
    size = bounds[0][1]
    set_widths = [s[3] for s in sets]
    # a set's pairs and a text's tokens a block, padded to one length
    set_parts, text_parts = [], []
    for a, b in bounds:
        blk = []
        for rows, slots, _, _ in sets:
            lo, hi = np.searchsorted(rows, [a, b])
            blk.append((rows[lo:hi] - a, slots[lo:hi]))
        set_parts.append(blk)
        text_parts.append([column_tokens(data[c][a:b], H) for c in TEXTS])
    most_set = max([len(r) for blk in set_parts for r, _ in blk] + [1])
    most_tok = max([len(r) for blk in text_parts for r, _, _ in blk] + [1])

    def onehots(ids, widths):
        return [(i[:, None] == jnp.arange(w)[None, :]).astype(jnp.float32)
                for i, w in zip(ids, widths)]

    @jax.jit
    def block(piv, keyed, pairs, set_nulls, toks, text_nulls, v1, v2, v3):
        cols = [v1.T] + onehots(piv, [w for _, w in pivots]) + [v2.T]
        cols += onehots(keyed, [w for _, w in keys])
        for (r, s), null, w in zip(pairs, set_nulls, set_widths):
            m = jnp.zeros((size, w), jnp.float32).at[r, s].set(
                1.0, mode="drop")
            cols.append(m.at[:, w - 1].set(null))
        cols.append(v3.T)
        for (r, k), null in zip(toks, text_nulls):
            cols.append(jnp.zeros((size, H), jnp.float32).at[r, k].add(
                1.0, mode="drop"))
            cols.append(null[:, None])
        return jnp.concatenate(cols, axis=1).astype(storage)

    def padded(x, length, fill):
        out = np.full(length, fill, np.int32)
        out[:len(x)] = x
        return out

    blocks = []
    for (a, b), sp, tp in zip(bounds, set_parts, text_parts):
        pad = size - (b - a)            # one shape for every block

        def rows_of(cols):
            return np.stack([np.pad(c[a:b], (0, pad)) for c in cols])

        blk = block(
            [np.pad(i[a:b], (0, pad)) for i, _ in pivots],
            [np.pad(i[a:b], (0, pad)) for i, _ in keys],
            [(padded(r, most_set, size), padded(s, most_set, 0))
             for r, s in sp],
            [np.pad(s[2][a:b].astype(np.float32), (0, pad)) for s in sets],
            [(padded(r, most_tok, size), padded(k, most_tok, 0))
             for r, k, _ in tp],
            [np.pad(null.astype(np.float32), (0, pad)) for _, _, null in tp],
            rows_of(first), rows_of(middle), rows_of(last))
        blocks.append(blk[:b - a])
    return plain.BlockedMatrix(blocks, bounds)


def groups_of(config, parts):
    """Column ranges of every pivot group, in the matrix's order: the six
    pivots, each map key, each set."""
    t = config["transmogrify"]
    pivots, keys, sets = parts
    at = len(BINARIES) * 2
    out = []
    for _, w in pivots:
        out.append(range(at, at + w))
        at += w
    at += len(DATES) * (2 * len(t["circular_date_periods"]) + 1) \
        + len(GEOS) * 4 + len(INTEGRALS) * 2
    for w in [w for _, w in keys] + [s[3] for s in sets]:
        out.append(range(at, at + w))
        at += w
    return out


def cramers_v(table):
    """Cramér's V of a label-class by column table of counts; NaN where it
    has under two rows or columns with any count."""
    table = table[table.sum(axis=1) > 0][:, table.sum(axis=0) > 0]
    if min(table.shape) < 2:
        return float("nan")
    total = table.sum()
    expected = np.outer(table.sum(axis=1), table.sum(axis=0)) / total
    chi2 = ((table - expected) ** 2 / expected).sum()
    return float(np.sqrt(chi2 / (total * (min(table.shape) - 1))))


def contingency(M, y):
    """[C, D] float64: the column sums of ``M`` over the rows of each of the
    label's classes, in float32 at ``highest`` a block, float64 across."""
    import jax
    import jax.numpy as jnp
    classes = np.unique(y)

    @jax.jit
    def sums(xb, yb):
        onehot = (yb[:, None] == jnp.asarray(classes, jnp.float32)[None, :]
                  ).astype(jnp.float32)
        return jnp.dot(onehot.T, xb.astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST)

    table = np.zeros((len(classes), M.d))
    for (a, b), xb in zip(M.bounds, M.blocks):
        table += np.asarray(sums(xb, jnp.asarray(y[a:b], jnp.float32)),
                            np.float64)
    return table


def sanity(M, y, groups, sc):
    """(statistics [5, D], columns kept): the per-column rules of
    ``plain.sanity_keep`` and the group rule, over SanityChecker's sample."""
    idx = plain.sanity_sample(len(y), sc)
    rows = np.arange(len(y)) if idx is None else np.sort(idx)
    S = M if idx is None else M.take_rows(rows)
    stats = plain.column_stats(S, y[rows])
    kept = set(plain.sanity_keep(stats, sc).tolist())
    table = contingency(S, y[rows])
    for cols in groups:
        v = cramers_v(table[:, list(cols)])
        if np.isfinite(v) and v > sc["max_cramers_v"]:
            kept -= set(cols)
    keep = np.asarray(sorted(kept), np.int64)
    return stats, keep if len(keep) else np.arange(stats.shape[1])


def rff_dropped(data, config):
    """Raw features RawFeatureFilter drops: fill rate under the minimum,
    where a map's fill rate is each key's (it goes when every key's is
    under) and a set's or a string's a row with a value; the numbers are
    present in every row."""
    floor = config["raw_feature_filter"]["min_fill_rate"]
    n = len(data["label"])
    out = [c for c in PIVOTS + TEXTS + SETS
           if sum(bool(v) for v in data[c]) / n < floor]
    for c in MAPS:
        filled = {}
        for m in data[c]:
            for k, v in (m or {}).items():
                filled[k] = filled.get(k, 0) + (v is not None)
        if all(f / n < floor for f in filled.values()):
            out.append(c)
    return out


def reference(data, config, precision, ask, seed=0):
    parts = host_parts(data, config)
    M = feature_matrix(data, config, precision, parts)
    y = data["label"]
    sc = config["sanity_checker"]
    stats, keep = sanity(M, y, groups_of(config, parts), sc)
    # common.sweep applies SanityChecker's per-column rules itself and has
    # no group rule: it is handed the columns kept here with those rules
    # set where they drop nothing, and fits over exactly these columns
    inert = dict(sc, max_correlation=np.inf, min_correlation=0.0,
                 min_variance=-np.inf)
    out = common.sweep(M.take_columns(keep), y,
                       dict(config, sanity_checker=inert), precision, ask,
                       FAMILIES)
    out["stats"], out["kept"] = stats, keep
    out["rff_dropped"] = sorted(rff_dropped(data, config))
    return out

"""Plain reference of the ``stackoverflow_accepted`` configuration: the
feature matrix ``transmogrify`` is stated to make of a question joined to
its asker (configs/stackoverflow_accepted.json), SanityChecker's statistics
and rules with the Cramér's V rule on every group of pivot columns, then
``common.sweep`` over the two linear families.  Imports nothing of the
program.

Blocks of columns in the order transmogrify lays them out (its groups sorted
by name, the features of a group in the schema's order, a URL after the
group's own): the pivots of ``owner_id`` and of the website's domain (top-k
indicators, OTHER, null); ``created`` and ``joined`` (sin and cos of the four
circular periods, null); the three histories (SinceLast, null); ``tags``, a
membership pivot (top-k indicators of the tags in a row, OTHER, null); the
title and the location (512 hashed term counts, null).

The two rules this configuration adds, from their statement:

* a URL's domain is the hostname of a url whose scheme is http, https or
  ftp, as Python's ``urllib.parse.urlsplit`` reads it (lower-cased, the
  user, password and port taken off), and null for any other value;
* a history's ``SinceLast`` is the days from ``reference_ms`` to the row's
  latest date, ``(reference_ms - latest) / 86,400,000`` in float64 rounded
  once to float32, 0 with the null indicator set for an empty list.

The circular periods and ``OpLinearSVC``'s squared hinge are the program's
departures from upstream, noted in ``reference/nyc_taxi_typed.py`` and
``reference/amazon_polarity_text.py``, whose pieces this imports.
"""

import os
from concurrent.futures import ThreadPoolExecutor
from urllib.parse import urlsplit

import numpy as np

from . import common, plain
from .amazon_polarity_text import column_tokens, svc_family
from .nyc_taxi_typed import unit_circle
from .yelp_review_maps import cramers_v, contingency, set_pivot

PIVOTS = ("owner_id",)
URLS = ("website",)
DATES = ("created", "joined")
LISTS = ("badge_dates", "question_dates", "answer_dates")
SETS = ("tags",)
TEXTS = ("title", "location")
MS_DAY = 86400000
WORKERS = 6
FAMILIES = {"OpLogisticRegression": common.logistic_family,
            "OpLinearSVC": svc_family}
question = common.winner_question


def url_host(value):
    """The domain rule: the hostname of a valid http / https / ftp url."""
    if not value:
        return None
    try:
        parts = urlsplit(value)
    except ValueError:
        return None
    if parts.scheme not in ("http", "https", "ftp"):
        return None
    return parts.hostname or None


def domains(urls):
    """Object array of each row's domain (None where it has none), the rule
    applied once a distinct value."""
    present = np.fromiter((u is not None for u in urls), bool, len(urls))
    uniq, inv = np.unique(urls[present].astype(str), return_inverse=True)
    hosts = np.asarray([url_host(u) for u in uniq.tolist()] + [None],
                       dtype=object)
    out = np.full(len(urls), None, dtype=object)
    out[present] = hosts[inv]
    return out


def since_last(values, offsets, reference_ms):
    """(days [N] float32, null [N] float32) of one history: ``values`` every
    row's dates in row order, ``offsets`` [N + 1] where each row's start."""
    lengths = np.diff(offsets)
    full = lengths > 0
    latest = np.zeros(len(lengths), np.int64)
    if full.any():
        latest[full] = np.maximum.reduceat(values, offsets[:-1][full])
    days = ((np.int64(reference_ms) - latest) / MS_DAY).astype(np.float32)
    days[~full] = 0.0
    return days, (~full).astype(np.float32)


def numeric_columns(data, config):
    """The float32 host columns between the pivots and the tags."""
    t = config["transmogrify"]
    n = len(data["label"])
    nothing = np.zeros(n, np.float32)
    cols = []
    for name in DATES:
        for p in t["circular_date_periods"]:
            cols += unit_circle(data[name], p)
        cols.append(nothing)
    for name in LISTS:
        cols += since_last(data[name].values, data[name].offsets,
                           t["reference_ms"])
    return cols


def host_parts(data, config):
    """The two pivots and the tags' pairs, each on a thread of its own."""
    t = config["transmogrify"]
    k, s = t["top_k"], t["min_support"]
    jobs = ([(plain.pivot_ids, data[c], k, s) for c in PIVOTS]
            + [(lambda u, k_, s_: plain.pivot_ids(domains(u), k_, s_),
                data[c], k, s) for c in URLS]
            + [(set_pivot, data[c], k, s) for c in SETS])
    with ThreadPoolExecutor(min(WORKERS, len(os.sched_getaffinity(0)))
                            ) as pool:
        done = list(pool.map(lambda j: j[0](*j[1:]), jobs))
    return done[:len(PIVOTS) + len(URLS)], done[len(PIVOTS) + len(URLS):]


def feature_matrix(data, config, precision, parts):
    """The stored matrix, built on the device block of rows by block of rows
    from compact host columns."""
    import jax
    import jax.numpy as jnp
    pivots, sets = parts
    H = config["transmogrify"]["num_hashes"]
    n = len(data["label"])
    middle = numeric_columns(data, config)
    storage = plain.jnp_dtype(common.storage_of(config, precision))
    width = (sum(w for _, w in pivots) + len(middle)
             + sum(s[3] for s in sets) + len(TEXTS) * (H + 1))
    bounds = plain.BlockedMatrix.bounds_for(n, width)
    size = bounds[0][1]
    set_widths = [s[3] for s in sets]
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

    @jax.jit
    def block(piv, vals, pairs, set_nulls, toks, text_nulls):
        cols = [(i[:, None] == jnp.arange(w)[None, :]).astype(jnp.float32)
                for i, (_, w) in zip(piv, pivots)]
        cols.append(vals.T)
        for (r, s), null, w in zip(pairs, set_nulls, set_widths):
            m = jnp.zeros((size, w), jnp.float32).at[r, s].set(
                1.0, mode="drop")
            cols.append(m.at[:, w - 1].set(null))
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
        blk = block(
            [np.pad(i[a:b], (0, pad)) for i, _ in pivots],
            np.stack([np.pad(c[a:b], (0, pad)) for c in middle]),
            [(padded(r, most_set, size), padded(s, most_set, 0))
             for r, s in sp],
            [np.pad(s[2][a:b].astype(np.float32), (0, pad)) for s in sets],
            [(padded(r, most_tok, size), padded(k, most_tok, 0))
             for r, k, _ in tp],
            [np.pad(null.astype(np.float32), (0, pad)) for _, _, null in tp])
        blocks.append(blk[:b - a])
    return plain.BlockedMatrix(blocks, bounds)


def groups_of(config, parts):
    """Column ranges of every pivot group, in the matrix's order: the two
    pivots, then the tags."""
    t = config["transmogrify"]
    pivots, sets = parts
    out, at = [], 0
    for _, w in pivots:
        out.append(range(at, at + w))
        at += w
    at += len(DATES) * (2 * len(t["circular_date_periods"]) + 1) \
        + 2 * len(LISTS)
    for s in sets:
        out.append(range(at, at + s[3]))
        at += s[3]
    return out


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
    where a string is filled by a value, a set and a history by one item;
    the dates are present in every row."""
    floor = config["raw_feature_filter"]["min_fill_rate"]
    n = len(data["label"])
    out = [c for c in PIVOTS + URLS + TEXTS + SETS
           if sum(bool(v) for v in data[c]) / n < floor]
    return out + [c for c in LISTS
                  if np.count_nonzero(np.diff(data[c].offsets)) / n < floor]


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

"""Plain reference of the ``nyc_taxi_typed`` configuration: the feature
matrix ``transmogrify`` is stated to make of the seventeen typed predictors
(configs/nyc_taxi_typed.json), SanityChecker's statistics and rules with the
Cramér's V rule on a group of pivot columns, then ``common.sweep`` over the
two linear families.  Imports nothing of the program.

Blocks of columns in the order transmogrify lays them out (its groups sorted
by name, the features of a group in the schema's order): the six pivots
(medallion, hack_license, vendor_id, rate_code, store_and_fwd_flag,
payment_type: top-k indicators, OTHER, null), the two dates (sin and cos of
the four circular periods, null), the two coordinates (latitude, longitude,
accuracy, null), the two counts (value, null), the five amounts (value,
null).

Departures from upstream's description, all the program's and kept here
because the reference follows the configuration as the program states it:

* a circular period is a fixed span of milliseconds counted from the epoch
  (``ops/dates.py``): a day, a week that starts on Monday, a "month" of
  30.44 days and a "year" of 365.2425 days.  Upstream's
  DateToUnitCircleTransformer reads the calendar's own fields (the hour of
  the day, the day of the week, of the month, of the year, each in UTC), so
  its month and year phases follow the calendar and these drift against it.
* Cramér's V is computed from the contingency table of the SAMPLE's rows
  (label class by pivot slot, empty rows and columns left out, chi-square
  against the product of the margins, V = sqrt(chi2 / (n min(r - 1, c - 1))))
  and a group over ``max_cramers_v`` loses every column.  Upstream also
  applies its association-rule confidence check there; the configuration
  leaves that at its default, which never fires.
* ``OpLinearSVC`` is the squared hinge under FISTA (see
  ``reference/amazon_polarity_text.py``, whose ``svc_family`` this imports).
"""

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from . import common, plain
from .amazon_polarity_text import svc_family
from .criteo_mixed import mode_filled

IDS = ("medallion", "hack_license")
PICKLISTS = ("vendor_id", "rate_code", "store_and_fwd_flag", "payment_type")
PIVOTS = IDS + PICKLISTS
DATES = ("pickup_datetime", "dropoff_datetime")
GEOS = ("pickup", "dropoff")
INTEGRALS = ("passenger_count", "trip_time_in_secs")
REALS = ("trip_distance", "fare_amount", "surcharge", "mta_tax",
         "tolls_amount")
MS_DAY = 86400000
# period -> (shift, length) in milliseconds; the epoch was a Thursday
PERIODS = {"HourOfDay": (0, MS_DAY),
           "DayOfWeek": (3 * MS_DAY, 7 * MS_DAY),
           "DayOfMonth": (0, int(30.44 * MS_DAY)),
           "DayOfYear": (0, int(365.2425 * MS_DAY))}
WORKERS = 6
FAMILIES = {"OpLogisticRegression": common.logistic_family,
            "OpLinearSVC": svc_family}
question = common.winner_question


def unit_circle(ms, period):
    """Date column -> (sine, cosine) float32 of 2 pi x the fraction of the
    period elapsed, worked in int64 and float64."""
    shift, length = PERIODS[period]
    angle = 2.0 * np.pi * (((np.asarray(ms, np.int64) + shift) % length)
                           / length)
    return [np.sin(angle).astype(np.float32), np.cos(angle).astype(np.float32)]


def geo_filled(xyz, present, wire):
    """Coordinate column -> (latitude, longitude, accuracy, null indicator):
    the missing take the mean of the present, all as carried on the wire."""
    fill = (xyz[present].mean(axis=0, dtype=np.float64).astype(np.float32)
            if present.any() else np.zeros(3, np.float32))
    v = plain.round_through(np.where(present[:, None], xyz, 0.0), wire)
    return [np.where(present, v[:, j], fill[j]) for j in range(3)] + [
        (~present).astype(np.float32)]


def numeric_columns(data, config, precision):
    """The float32 host columns after the pivots, in the matrix's order."""
    t = config["transmogrify"]
    n = len(data["label"])
    nothing = np.zeros(n, np.float32)
    every = np.ones(n, bool)
    cols = []
    with ThreadPoolExecutor(min(WORKERS, len(os.sched_getaffinity(0)))
                            ) as pool:       # numpy lets go of the lock
        circles = iter(list(pool.map(
            lambda job: unit_circle(data[job[0]], job[1]),
            [(name, p) for name in DATES
             for p in t["circular_date_periods"]])))
    for name in DATES:
        for _ in t["circular_date_periods"]:
            cols += next(circles)
        cols.append(nothing)
    for name in GEOS:
        cols += geo_filled(data[name], data[name + ".present"],
                           precision.wire)
    for name in INTEGRALS:
        cols += mode_filled(data[name], every, precision.wire)
    for name in REALS:
        cols += plain.mean_filled(data[name], every, precision.wire)
    return cols


def pivots_of(data, config):
    """[(ids, width)] of the six pivoted columns."""
    t = config["transmogrify"]
    with ThreadPoolExecutor(min(WORKERS, len(os.sched_getaffinity(0)))
                            ) as pool:
        return list(pool.map(lambda name: plain.pivot_ids(
            data[name], t["top_k"], t["min_support"]), PIVOTS))


def feature_matrix(data, config, precision, pivots):
    """The stored matrix, built on the device block of rows by block of rows
    from compact host columns."""
    import jax
    import jax.numpy as jnp
    n = len(data["label"])
    nums = numeric_columns(data, config, precision)
    storage = plain.jnp_dtype(common.storage_of(config, precision))

    @jax.jit
    def block(ids, vals):
        cols = [(i[:, None] == jnp.arange(w)[None, :]).astype(jnp.float32)
                for i, (_, w) in zip(ids, pivots)]
        return jnp.concatenate(cols + [vals.T], axis=1).astype(storage)

    width = sum(w for _, w in pivots) + len(nums)
    bounds = plain.BlockedMatrix.bounds_for(n, width)
    size = bounds[0][1]
    blocks = []
    for a, b in bounds:
        pad = size - (b - a)            # one shape for every block
        blk = block([np.pad(i[a:b], (0, pad)) for i, _ in pivots],
                    np.stack([np.pad(c[a:b], (0, pad)) for c in nums]))
        blocks.append(blk[:b - a])
    return plain.BlockedMatrix(blocks, bounds)


def cramers_v(ids, width, y):
    """Cramér's V of a pivot's slots with the label's classes over the rows
    given; NaN where the table has under two rows or columns."""
    classes, yi = np.unique(y, return_inverse=True)
    table = np.bincount(yi * width + ids, minlength=len(classes) * width
                        ).reshape(len(classes), width).astype(np.float64)
    table = table[table.sum(axis=1) > 0][:, table.sum(axis=0) > 0]
    if min(table.shape) < 2:
        return float("nan")
    total = table.sum()
    expected = np.outer(table.sum(axis=1), table.sum(axis=0)) / total
    chi2 = ((table - expected) ** 2 / expected).sum()
    return float(np.sqrt(chi2 / (total * (min(table.shape) - 1))))


def sanity(M, y, pivots, sc):
    """(statistics [5, D], columns kept): the per-column rules of
    ``plain.sanity_keep`` and the group rule, over SanityChecker's sample."""
    idx = plain.sanity_sample(len(y), sc)
    rows = np.arange(len(y)) if idx is None else np.sort(idx)
    stats = plain.column_stats(M if idx is None else M.take_rows(rows),
                               y[rows])
    kept = set(plain.sanity_keep(stats, sc).tolist())
    at = 0
    for ids, width in pivots:
        v = cramers_v(ids[rows], width, y[rows])
        if np.isfinite(v) and v > sc["max_cramers_v"]:
            kept -= set(range(at, at + width))
        at += width
    keep = np.asarray(sorted(kept), np.int64)
    return stats, keep if len(keep) else np.arange(stats.shape[1])


def rff_dropped(data, config):
    """Raw features RawFeatureFilter drops: fill rate under the minimum (a
    missing or empty string, a coordinate of (0, 0)); every other field is
    present in every row."""
    floor = config["raw_feature_filter"]["min_fill_rate"]
    n = len(data["label"])
    dropped = [c for c in PIVOTS if sum(bool(v) for v in data[c]) / n < floor]
    return dropped + [c for c in GEOS
                      if data[c + ".present"].mean() < floor]


def about(M, centre):
    """``M`` with the constant ``centre[j]`` taken from column j, as stored."""
    import jax.numpy as jnp
    c = jnp.asarray(centre, jnp.float32)
    return plain.BlockedMatrix(
        [(b.astype(jnp.float32) - c).astype(b.dtype) for b in M.blocks],
        M.bounds)


def reference(data, config, precision, ask, seed=0):
    import jax.numpy as jnp
    pivots = pivots_of(data, config)
    M = feature_matrix(data, config, precision, pivots)
    y = data["label"]
    sc = config["sanity_checker"]
    stats, keep = sanity(M, y, pivots, sc)
    # The fits see every coordinate about a constant, the stored value
    # nearest its mean, and the intercept is put back after: a standardised
    # column does not change with such a shift, but ``plain.logistic_fista``
    # takes a variance as E[x^2] - mean^2 in float32, which a latitude (40.75
    # +- 0.03) does not survive.  Stored values within a factor of two of the
    # constant leave differences that are stored exactly.
    width = sum(w for _, w in pivots)
    t = config["transmogrify"]
    at = width + len(DATES) * (2 * len(t["circular_date_periods"]) + 1)
    centre = np.zeros(stats.shape[1])
    for g in range(len(GEOS)):
        centre[at + 4 * g:at + 4 * g + 3] = stats[0, at + 4 * g:at + 4 * g + 3]
    centre = np.asarray(jnp.asarray(centre, M.blocks[0].dtype), np.float64)
    # common.sweep applies SanityChecker's per-column rules itself and has
    # no group rule: it is handed the columns kept here with those rules
    # set where they drop nothing, and fits over exactly these columns
    inert = dict(sc, max_correlation=np.inf, min_correlation=0.0,
                 min_variance=-np.inf)
    M = about(M, centre).take_columns(keep)
    out = common.sweep(M, y, dict(config, sanity_checker=inert), precision,
                       ask, FAMILIES)
    if "coef" in out:
        out["intercept"] -= float(np.dot(out["coef"], centre[keep]))
    out["stats"], out["kept"] = stats, keep
    out["rff_dropped"] = sorted(rff_dropped(data, config))
    return out

"""User program of the ``nyc_taxi_typed`` configuration.

The NYC Taxi & Limousine Commission's 2013 trip records (``trip_data`` joined
to ``trip_fare``) under the task of the TDSP "NYC Taxi Trips" walkthrough,
``tipped`` = ``tip_amount > 0``, with every predictor typed as upstream
TransmogrifAI types such a field: two ``ID``s, four ``PickList``s, two
``DateTime``s, two ``Integral``s, a ``Real``, four ``Currency`` amounts and
two ``Geolocation``s, through ``transmogrify``'s defaults (pivots of the top
20 values, the four circular date periods, mean- and mode-fills, null
indicators), RawFeatureFilter, SanityChecker and
BinaryClassificationModelSelector's 3-fold cross-validation over the default
grids of the two linear families.

There is no network, so ``make_data`` draws the rows from the seed with the
parameters of ``configs/nyc_taxi_typed.json`` (``generator``).  It imports
nothing of the program: the reference reads the same host arrays.  Every
array it returns has one entry a row.  ``build`` hands the program fresh
objects over COPIES of them, so that no cache keyed on a Column or an array
survives from train to train.
"""

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

CHUNK_ROWS = 262144       # part of the generator: a chunk has draws of its own
IDS = ("medallion", "hack_license")
PICKLISTS = ("vendor_id", "rate_code", "store_and_fwd_flag", "payment_type")
DATES = ("pickup_datetime", "dropoff_datetime")
INTEGRALS = ("passenger_count", "trip_time_in_secs")
REALS = ("trip_distance",)
CURRENCIES = ("fare_amount", "surcharge", "mta_tax", "tolls_amount")
GEOS = ("pickup", "dropoff")
MILES_A_DEGREE = 69.05          # of latitude; a degree of longitude is
_COS_NYC = 0.7576               # cos(40.75 degrees) of that


def _mix64(x):
    """uint64 that depends on ``x`` alone (splitmix64's finaliser)."""
    x = (x.astype(np.uint64) + np.uint64(0x9E3779B97F4A7C15))
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def _hex_levels(count, salt):
    """``count`` level names of 32 hex digits (the source's MD5 form), each a
    function of its rank and the column's salt alone."""
    rank = np.arange(count, dtype=np.uint64) * np.uint64(2) + np.uint64(
        salt << 32)
    hi, lo = _mix64(rank).tolist(), _mix64(rank + np.uint64(1)).tolist()
    return np.asarray([f"{a:016X}{b:016X}" for a, b in zip(hi, lo)],
                      dtype=object)


def _power_ranks(rng, n, count, exponent):
    """Ranks 0..count-1 with P(rank) ~ (rank + 1) ** -exponent, exponent
    under 1: the inverse of the continuous law's distribution function."""
    e = 1.0 - exponent
    x = ((count + 1.0) ** e - 1.0) * rng.random(n) + 1.0
    return np.minimum((x ** (1.0 / e)).astype(np.int64) - 1, count - 1)


def _choice(rng, n, weights):
    """Index drawn with the stated weights (normalised here)."""
    edges = np.cumsum(np.asarray(weights, np.float64))
    return np.minimum(np.searchsorted(edges / edges[-1], rng.random(n),
                                      side="right"), len(weights) - 1)


def _take(levels, idx, missing=None):
    col = np.asarray(levels, dtype=object)[idx]
    if missing is not None:
        col[missing] = None
    return col


def _chunk(seed, k, n, g, levels, intercept):
    """Rows ``k * CHUNK_ROWS`` onward, ``n`` of them, from draws of their
    own.  With ``intercept`` None only the card's logit without it."""
    rng = np.random.default_rng([seed, k])
    data = {}

    for name in IDS:
        p = g[name]
        data[name] = _take(levels[name],
                           _power_ranks(rng, n, p["levels"], p["exponent"]))
    cmt = rng.random(n) < g["vendor_cmt_share"]
    data["vendor_id"] = _take(["VTS", "CMT"], cmt.astype(np.int64))
    # the source's VTS records leave the flag blank
    data["store_and_fwd_flag"] = _take(
        ["N", "Y"], (rng.random(n) < g["store_and_fwd_yes_share"]
                     ).astype(np.int64), missing=~cmt)

    # where: a mixture of places, the drop-off the pick-up's own place or a
    # draw of its own
    places = g["places"]
    lat0 = np.asarray([p["lat"] for p in places])
    lon0 = np.asarray([p["lon"] for p in places])
    sigma = np.asarray([p["sigma_miles"] for p in places]) / MILES_A_DEGREE
    airport = np.asarray([p["airport"] for p in places], bool)
    borough = np.asarray([p["borough"] for p in places])
    jfk = np.asarray([p["name"] == "JFK" for p in places])
    a = _choice(rng, n, [p["pickup_weight"] for p in places])
    b = np.where(rng.random(n) < g["same_place_share"], a,
                 _choice(rng, n, [p["dropoff_weight"] for p in places]))
    at = {}
    for name, pick in (("pickup", a), ("dropoff", b)):
        at[name] = (lat0[pick] + sigma[pick] * rng.standard_normal(n),
                    lon0[pick] + sigma[pick] / _COS_NYC * rng.standard_normal(n))

    # when: a week of the year, a day of the week, an hour of the day by
    # their profiles, a second of the hour; Monday first
    dow = _choice(rng, n, g["day_of_week_profile"])
    hour = _choice(rng, n, g["hour_of_day_profile"])
    day = rng.integers(0, g["weeks"], size=n) * 7 + dow
    second = day * 86400 + hour * 3600 + rng.integers(0, 3600, size=n)
    pickup_ms = g["first_monday_ms"] + second * 1000

    # how far and how long: the street distance between the two points and
    # the hour's speed, each with its own log-normal noise
    dy = (at["dropoff"][0] - at["pickup"][0]) * MILES_A_DEGREE
    dx = (at["dropoff"][1] - at["pickup"][1]) * MILES_A_DEGREE * _COS_NYC
    miles = np.maximum(
        g["street_factor"] * np.hypot(dx, dy)
        * rng.lognormal(0.0, g["route_sigma"], size=n), g["least_miles"])
    miles = np.round(miles, 2)
    mph = np.asarray(g["speed_mph_profile"])[hour] * rng.lognormal(
        0.0, g["speed_sigma"], size=n)
    seconds = np.clip(np.rint(miles / mph * 3600.0 + g["boarding_seconds"]),
                      g["least_seconds"], g["most_seconds"]).astype(np.int64)
    flies = airport[a] | airport[b]

    # how paid: the share of cards moves with vendor, distance, hour, place
    # and airport
    card = g["card"]
    logit = (card["vendor"] * np.where(cmt, 1.0, -1.0)
             + card["log_miles"] * (np.log(miles) - np.log(card["miles_at_0"]))
             + card["hour"] * np.cos(2.0 * np.pi * (hour - card["hour_peak"])
                                     / 24.0)
             + np.asarray([p["card_logit"] for p in places])[a]
             + card["airport"] * flies)
    if intercept is None:
        return logit

    data["pickup_datetime"] = pickup_ms
    data["dropoff_datetime"] = pickup_ms + seconds * 1000
    data["passenger_count"] = _choice(rng, n, g["passenger_count_shares"]
                                      ).astype(np.int64)
    data["trip_time_in_secs"] = seconds
    data["trip_distance"] = miles.astype(np.float32)

    # the tariff: rate 2 is the flat fare between JFK and Manhattan, the
    # rare codes are drawn; a metered fare is the flag drop and 50 cents a
    # unit, a unit a fifth of a mile or a minute of slow traffic
    to_jfk = ((jfk[a] & (borough[b] == "Manhattan"))
              | (jfk[b] & (borough[a] == "Manhattan")))
    rare = g["rate_code_rare_shares"]
    code = np.where(to_jfk & (rng.random(n) < g["jfk_flat_share"]), 2, 1)
    pick = _choice(rng, n, [1.0 - sum(rare.values())] + list(rare.values()))
    code = np.where(pick > 0, np.asarray([1] + [int(c) for c in rare])[pick], code)
    data["rate_code"] = _take([str(c) for c in range(7)], code)
    q = g["slow_share"]
    units = np.floor(5.0 * miles * (1.0 - q) + seconds / 60.0 * q)
    fare = g["flag_drop"] + 0.5 * units
    fare = np.where(code == 5, np.maximum(np.round(
        fare * rng.lognormal(g["negotiated_log_mean"], g["negotiated_sigma"],
                             size=n) * 2.0) / 2.0, g["flag_drop"]), fare)
    fare = np.where(code == 2, g["jfk_flat_fare"], fare)
    data["fare_amount"] = fare.astype(np.float32)
    night = (hour >= 20) | (hour < 6)
    rush = (dow < 5) & (hour >= 16) & (hour < 20)
    data["surcharge"] = np.where(night, 0.5, np.where(rush, 1.0, 0.0)
                                 ).astype(np.float32)
    untaxed = np.isin(code, g["untaxed_rate_codes"]) | (
        rng.random(n) < g["untaxed_share"])
    data["mta_tax"] = np.where(untaxed, 0.0, 0.5).astype(np.float32)
    crosses = borough[a] != borough[b]
    tolled = rng.random(n) < np.where(
        flies, g["toll_share"]["airport"],
        np.where(crosses, g["toll_share"]["cross_borough"],
                 g["toll_share"]["other"]))
    toll = np.asarray(g["toll_amounts"])[_choice(rng, n,
                                                 g["toll_amount_weights"])]
    data["tolls_amount"] = np.where(tolled, toll, 0.0).astype(np.float32)

    is_card = rng.random(n) < 1.0 / (1.0 + np.exp(-(logit + intercept)))
    other = g["payment_other_shares"]
    pick = _choice(rng, n, [1.0 - sum(other.values())] + list(other.values()))
    names = ["CRD", "CSH"] + list(other)
    pay = np.where(pick > 0, pick + 1, np.where(is_card, 0, 1))
    data["payment_type"] = _take(names, pay)
    tip = np.asarray([g["tipped_share"][c] for c in names])[pay]
    data["label"] = (rng.random(n) < tip).astype(np.float32)

    # the source's (0, 0) coordinates: read as missing
    lost = rng.random(n) < g["zero_pickup_share"]
    gone = {"pickup": lost,
            "dropoff": np.where(lost, rng.random(n) < g["zero_both_share"],
                                rng.random(n) < g["zero_dropoff_share"])}
    for name in GEOS:
        here = ~gone[name]
        xyz = np.zeros((n, 3), np.float32)
        xyz[:, 0] = np.where(here, at[name][0], 0.0)
        xyz[:, 1] = np.where(here, at[name][1], 0.0)
        xyz[:, 2] = np.where(here, g["accuracy"], 0.0)
        data[name] = xyz
        data[name + ".present"] = here
    return data


def make_data(rows, seed, params):
    """Host arrays of one data set, all drawn from ``seed``: ``label``
    float32; the six string columns as object arrays of str (a missing
    ``store_and_fwd_flag`` is None); the two dates as int64 epoch
    milliseconds; the two counts as int64; the five amounts as float32; the
    two coordinates as float32 ``[N, 3]`` (latitude, longitude, accuracy)
    with ``<name>.present`` False where the source holds (0, 0).  Chunks of
    ``CHUNK_ROWS`` rows are drawn side by side on a few threads, each from
    ``default_rng([seed, chunk])``: the same data on any number of threads.
    The card's intercept is the one that gives the stated share on the first
    chunk's rows."""
    g = params["generator"]
    levels = {name: _hex_levels(g[name]["levels"], j + 1)
              for j, name in enumerate(IDS)}
    logit = _chunk(seed, 0, min(CHUNK_ROWS, rows), g, levels, None)
    lo, hi = -20.0, 20.0
    for _ in range(50):
        mid = 0.5 * (lo + hi)
        share = np.mean(1.0 / (1.0 + np.exp(-(logit + mid))))
        lo, hi = (mid, hi) if share < g["card"]["share"] else (lo, mid)
    intercept = 0.5 * (lo + hi)
    starts = range(0, rows, CHUNK_ROWS)
    data = {}
    with ThreadPoolExecutor(min(8, len(os.sched_getaffinity(0)))) as pool:
        chunks = pool.map(lambda s: _chunk(
            seed, s // CHUNK_ROWS, min(CHUNK_ROWS, rows - s), g, levels,
            intercept), starts)
        for s, chunk in zip(starts, chunks):
            for name, values in chunk.items():
                if name not in data:
                    data[name] = np.empty((rows,) + values.shape[1:],
                                          values.dtype)
                data[name][s:s + len(values)] = values
    return data


def build(data, params):
    """A new user's train: fresh Workflow, features and ColumnBatch over
    copies of the host arrays.  Returns the workflow."""
    from transmogrifai_tpu import types as T
    from transmogrifai_tpu.columns import Column, ColumnBatch
    from transmogrifai_tpu.features import features_from_schema
    from transmogrifai_tpu.models.linear import (OpLinearSVC,
                                                 OpLogisticRegression)
    from transmogrifai_tpu.ops.transmogrify import transmogrify
    from transmogrifai_tpu.selector import (BinaryClassificationModelSelector,
                                            ModelCandidate, grid)
    from transmogrifai_tpu.workflow import Workflow

    # The cell is the typed table through ONE fused program.  A program
    # whose date or coordinate vectorizer has no staged form would run it
    # between compiled segments and walk every coordinate in Python, tens of
    # minutes a train at this size: it cannot run this configuration, and
    # says so at once instead.
    from transmogrifai_tpu.ops.dates import DateToUnitCircleModel
    from transmogrifai_tpu.ops.geo import GeolocationVectorizerModel
    for model in (DateToUnitCircleModel, GeolocationVectorizerModel):
        if not model.supports_staging:
            raise SystemExit(
                f"nyc_taxi_typed needs a staged {model.__name__} "
                "(transform_staged): this program has none")

    n = len(data["label"])
    cols = {"label": Column(T.RealNN, data["label"].copy())}
    schema = {"label": T.RealNN}
    typed = ([(c, T.ID) for c in IDS] + [(c, T.PickList) for c in PICKLISTS]
             + [(c, T.DateTime) for c in DATES]
             + [(c, T.Integral) for c in INTEGRALS]
             + [(c, T.Real) for c in REALS]
             + [(c, T.Currency) for c in CURRENCIES])
    for name, kind in typed:
        values = data[name].copy()
        cols[name] = Column(kind, values, None if values.dtype == object
                            else np.ones(n, bool))
        schema[name] = kind
    for name in GEOS:
        cols[name] = Column(T.Geolocation, data[name].copy(),
                            data[name + ".present"].copy())
        schema[name] = T.Geolocation
    batch = ColumnBatch(cols, n)

    t = params["transmogrify"]
    label, predictors = features_from_schema(schema, response="label")
    fv = transmogrify(
        predictors, top_k=t["top_k"], min_support=t["min_support"],
        num_hashes=t["num_hashes"],
        max_categorical_cardinality=t["max_categorical_cardinality"],
        track_nulls=t["track_nulls"])
    sc = params["sanity_checker"]
    checked = label.sanity_check(
        fv, remove_bad_features=True, max_correlation=sc["max_correlation"],
        min_correlation=sc["min_correlation"],
        min_variance=sc["min_variance"], max_cramers_v=sc["max_cramers_v"],
        sample_upper_limit=sc["sample_upper_limit"], seed=sc["sample_seed"])
    estimators = {"OpLogisticRegression": OpLogisticRegression,
                  "OpLinearSVC": OpLinearSVC}
    models = []
    for family, p in params["selector"].items():
        axes = {k: v for k, v in p.items() if isinstance(v, list)}
        models.append(ModelCandidate(
            estimators[family](),
            grid(**axes, max_iter=[p["max_iter"]]), family))
    selector = BinaryClassificationModelSelector(
        num_folds=params["folds"], seed=params["fold_seed"], models=models)
    selector.set_input(label, checked)
    pred = selector.get_output()
    return (Workflow().set_input_batch(batch).set_result_features(pred)
            .with_raw_feature_filter(
                min_fill_rate=params["raw_feature_filter"]["min_fill_rate"]))

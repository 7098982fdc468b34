"""User program of the ``yelp_review_maps`` configuration.

The Yelp Open Dataset's reviews, each joined to its business and to its
reviewer, under a recommender's task: will this member rate this business
well (``stars >= 4``), from what is known before the review is written.  The
predictors are typed as a TransmogrifAI user types such a join: the
business's ``attributes`` and ``hours`` as ``PickListMap``s, its
``categories`` and the reviewer's ``elite`` years as ``MultiPickList``s, the
two ids as ``ID``, the address fields as ``Street``/``City``/``State``/
``PostalCode``, the name as ``Text``, the coordinates as one ``Geolocation``,
two ``Real`` ratings, seventeen ``Integral`` counts, ``is_open`` as
``Binary`` and two ``DateTime``s; through ``transmogrify``'s defaults,
RawFeatureFilter, SanityChecker and BinaryClassificationModelSelector's
3-fold cross-validation over the default grids of the two linear families.

There is no network, so ``make_data`` draws the rows from the seed with the
parameters of ``configs/yelp_review_maps.json`` (``generator``).  It imports
nothing of the program: the reference reads the same host arrays.  Every
array it returns has one entry a row, and every row's map and list is an
object of its own, as a JSON reader makes them.  ``build`` hands the program
fresh objects over COPIES of the arrays, so that no cache keyed on a Column
or an array survives from train to train.
"""

import numpy as np

IDS = ("business_id", "user_id")
CATEGORICALS = (("address", "Street"), ("city", "City"), ("state", "State"),
                ("postal_code", "PostalCode"))
TEXTS = ("name",)
GEOS = ("location",)
REALS = ("business_stars", "average_stars")
COMPLIMENTS = ("compliment_hot", "compliment_more", "compliment_profile",
               "compliment_cute", "compliment_list", "compliment_note",
               "compliment_plain", "compliment_cool", "compliment_funny",
               "compliment_writer", "compliment_photos")
INTEGRALS = ("business_review_count", "user_review_count", "fans", "useful",
             "funny", "cool") + COMPLIMENTS
BINARIES = ("is_open",)
DATES = ("date", "yelping_since")
MAPS = ("attributes", "hours")
SETS = ("categories", "elite")
DAYS = ("Monday", "Tuesday", "Wednesday", "Thursday", "Friday", "Saturday",
        "Sunday")
MS_DAY = 86400000
_ID_ALPHABET = np.frombuffer(
    b"ABCDEFGHIJKLMNOPQRSTUVWXYZabcdefghijklmnopqrstuvwxyz0123456789-_",
    np.uint8)


def _mix64(x):
    """uint64 that depends on ``x`` alone (splitmix64's finaliser)."""
    x = (x.astype(np.uint64) + np.uint64(0x9E3779B97F4A7C15))
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def _ids(ranks, salt):
    """22-character ids of the source's alphabet, each a function of its
    rank and the column's salt alone."""
    ranks = np.asarray(ranks, np.uint64)
    words = [_mix64(ranks * np.uint64(3) + np.uint64(j) + np.uint64(salt << 40))
             for j in range(3)]
    chars = np.empty((len(ranks), 22), np.uint8)
    for c in range(22):
        w = words[c // 10]
        chars[:, c] = _ID_ALPHABET[((w >> np.uint64(6 * (c % 10)))
                                    & np.uint64(63)).astype(np.int64)]
    return chars.view("S22").ravel().astype(str).astype(object)


def _power_ranks(rng, n, count, exponent):
    """Ranks 0..count-1 with P(rank) ~ (rank + 1) ** -exponent, exponent
    under 1: the inverse of the continuous law's distribution function."""
    e = 1.0 - exponent
    x = ((count + 1.0) ** e - 1.0) * rng.random(n) + 1.0
    return np.minimum((x ** (1.0 / e)).astype(np.int64) - 1, count - 1)


def _zipf(rng, n, count, exponent):
    """Ranks 0..count-1 with P(rank) proportional to (rank + 1) ** -exponent
    exactly (a bounded Zipf law of any exponent)."""
    w = np.arange(1, count + 1, dtype=np.float64) ** -float(exponent)
    return _choice(rng, n, w)


def _choice(rng, n, weights):
    """Index drawn with the stated weights (normalised here)."""
    edges = np.cumsum(np.asarray(weights, np.float64))
    return np.minimum(np.searchsorted(edges / edges[-1], rng.random(n),
                                      side="right"), len(weights) - 1)


def _objects(items, n):
    """An object array of ``n`` entries from an iterable, one entry an item
    (a list stays one entry)."""
    return np.fromiter(items, dtype=object, count=n)


def _flags_strings(flags):
    """Every combination of the named True/False flags as the source writes
    a nested attribute: "{'garage': False, 'street': True}"; combination c
    sets flag j where bit j of c is 1."""
    return ["{" + ", ".join(f"'{f}': {bool(c >> j & 1)}"
                            for j, f in enumerate(flags)) + "}"
            for c in range(1 << len(flags))]


def _attributes(rng, count, g):
    """Per business: its attributes dict (None where the source has none),
    and the label's part from them.  Keys and values as the source spells
    them; a nested attribute is the string of a dict of flags."""
    spec = g["attributes"]
    has = rng.random(count) >= g["attributes_missing_share"]
    table = [dict() if h else None for h in has]
    effect = np.zeros(count)
    for key, a in spec.items():
        here = has & (rng.random(count) < a["share"])
        rows = np.flatnonzero(here)
        if "flags" in a:
            names = list(a["flags"])
            bits = rng.random((len(rows), len(names))) < np.asarray(
                [a["flags"][f] for f in names])
            code = bits.astype(np.int64) @ (1 << np.arange(len(names)))
            values = np.asarray(_flags_strings(names), dtype=object)[code]
            for f, w in a.get("label", {}).items():
                effect[rows] += w * bits[:, names.index(f)]
        else:
            pick = _choice(rng, len(rows), a["weights"])
            values = np.asarray(a["values"], dtype=object)[pick]
            for v, w in a.get("label", {}).items():
                effect[rows] += w * (values == v)
        for i, v in zip(rows.tolist(), values.tolist()):
            table[i][key] = v
    return table, effect


def _hours(rng, count, g):
    """Per business: its hours dict, a day to "H:M-H:M" (None where the
    source has none).  A business keeps one pattern on weekdays and on each
    weekend day the same or another; rare patterns are drawn at random."""
    h = g["hours"]
    has = rng.random(count) >= h["missing_share"]
    patterns = list(h["patterns"])

    def draw(n):
        pick = _choice(rng, n, list(h["patterns"].values()) + [h["rare_share"]])
        opens = rng.integers(5, 13, n)
        span = rng.integers(6, 16, n)
        minute = np.asarray(["0", "15", "30", "45"], dtype=object)[
            rng.integers(0, 4, n)]
        rare = [f"{a}:{m}-{(a + s) % 24}:{m}" for a, s, m in
                zip(opens.tolist(), span.tolist(), minute.tolist())]
        return np.where(pick < len(patterns),
                        np.asarray(patterns + [""], dtype=object)[
                            np.minimum(pick, len(patterns))],
                        np.asarray(rare, dtype=object))

    week = draw(count)
    days = {}
    for d, day in enumerate(DAYS):
        own = draw(count) if d >= 5 else week
        same = rng.random(count) < (h["weekend_same_share"] if d >= 5 else 1.0)
        open_ = rng.random(count) < h["open_share"][d]
        days[day] = (np.where(same, week, own), open_)
    table = []
    for i in range(count):
        if not has[i]:
            table.append(None)
            continue
        table.append({day: v[i] for day, (v, o) in days.items() if o[i]})
    return table


def _categories(rng, count, g):
    """Per business: its list of distinct category names (None where the
    source has none), and the label's part from them."""
    c = g["categories"]
    named = list(c["named"])
    total = c["names"]
    names = np.asarray(named + [f"Category {j:04d}" for j in
                                range(len(named), total)], dtype=object)
    k = np.minimum(1 + rng.poisson(c["extra_mean"], count), c["most"])
    draws = _zipf(rng, int(k.sum()), total, c["exponent"])
    effect_of = np.zeros(total)
    for name, w in c["label"].items():
        effect_of[named.index(name)] = w
    starts = np.r_[0, np.cumsum(k)]
    missing = rng.random(count) < c["missing_share"]
    table, effect = [], np.zeros(count)
    for i in range(count):
        if missing[i]:
            table.append(None)
            continue
        mine = list(dict.fromkeys(draws[starts[i]:starts[i + 1]].tolist()))
        effect[i] = effect_of[mine].sum()
        table.append(names[mine].tolist())
    return table, effect


def _places(rng, count, g):
    """Per business: (address, city, state, postal code, latitude,
    longitude) from the metro areas of ``generator.metros``."""
    metros = g["metros"]
    m = _choice(rng, count, [x["share"] for x in metros])
    city_rank = _zipf(rng, count, g["cities_a_metro"], g["city_exponent"])
    stems, ends = g["city_stems"], g["city_ends"]
    city = np.empty(count, object)
    state = np.empty(count, object)
    postal = np.empty(count, object)
    lat = np.empty(count)
    lon = np.empty(count)
    zone = rng.integers(0, 40, count)
    for j, x in enumerate(metros):
        here = m == j
        r = city_rank[here]
        city[here] = [x["name"] if k == 0 else
                      stems[(k * 7 + j) % len(stems)] + ends[k % len(ends)]
                      for k in r.tolist()]
        state[here] = x["state"]
        z = (r * 11 + zone[here]) % 100
        if x["postal"] == "ca":
            postal[here] = [f"T{5 + q % 4}{chr(65 + q % 26)} {q % 10}"
                            f"{chr(75 + q % 13)}{q % 7}" for q in z.tolist()]
        else:
            postal[here] = [f"{x['zip3']}{q:02d}" for q in z.tolist()]
        lat[here] = x["lat"] + x["sigma_deg"] * rng.standard_normal(here.sum())
        lon[here] = x["lon"] + x["sigma_deg"] * rng.standard_normal(here.sum())
    # the rare states of the source, a business or a few each
    odd = rng.random(count) < g["other_state_share"]
    state[odd] = np.asarray(g["other_states"], dtype=object)[
        rng.integers(0, len(g["other_states"]), odd.sum())]
    postal[rng.random(count) < g["postal_missing_share"]] = None
    streets = np.asarray(g["streets"], dtype=object)
    number = np.minimum(rng.lognormal(6.5, 1.3, count).astype(np.int64) + 1,
                        99999)
    street = _zipf(rng, count, len(streets), 0.8)
    address = np.asarray([f"{a} {s}" for a, s in
                          zip(number.tolist(), streets[street].tolist())],
                         dtype=object)
    address[rng.random(count) < g["address_missing_share"]] = None
    return address, city, state, postal, lat, lon


def _surnames(count, syllables):
    """``count`` made-up family names of two or three syllables, each a
    function of its rank alone."""
    k = len(syllables)
    out = []
    for r in range(count):
        parts = [syllables[r % k], syllables[r // k % k]]
        if r >= k * k:
            parts.append(syllables[r // (k * k) % k])
        out.append("".join(parts).capitalize())
    return out


def _names(rng, count, g):
    """Business names: the chains of ``generator.names.chains`` with their
    shares, the rest two or three words of the stated lists, the first of
    them an owner's name ("Kalo's") with owned_share."""
    n = g["names"]
    chains = list(n["chains"])
    pick = _choice(rng, count, list(n["chains"].values())
                   + [1.0 - sum(n["chains"].values())])
    owners = np.asarray([f"{w}'s" for w in _surnames(
        n["owners"], n["syllables"])], dtype=object)
    first = np.where(
        rng.random(count) < n["owned_share"],
        owners[_zipf(rng, count, len(owners), n["owner_exponent"])],
        np.asarray(n["first"], dtype=object)[
            _zipf(rng, count, len(n["first"]), 0.7)])
    second = np.asarray(n["second"], dtype=object)[
        _zipf(rng, count, len(n["second"]), 0.7)]
    third = np.asarray(n["third"] + [""], dtype=object)[
        _zipf(rng, count, len(n["third"]) + 1, 0.5)]
    own = [f"{a} {b} {c}".strip() for a, b, c in
           zip(first.tolist(), second.tolist(), third.tolist())]
    return np.where(pick < len(chains),
                    np.asarray(chains + [""], dtype=object)[
                        np.minimum(pick, len(chains))],
                    np.asarray(own, dtype=object))


def _ms(date_text):
    return int(np.datetime64(date_text, "ms").astype(np.int64))


def make_data(rows, seed, params):
    """Host arrays of one data set, all drawn from ``seed``: ``label``
    float32; the two ids, the name and the address fields as object arrays
    of str (None where missing); ``attributes`` and ``hours`` as object
    arrays of dicts (or None), ``categories`` and ``elite`` as object arrays
    of lists of str (or None): every row's its own object; the ratings as
    float32; the counts as int64; ``is_open`` as bool; the two dates as int64
    epoch milliseconds; ``location`` as float32 ``[N, 3]`` (latitude,
    longitude, accuracy) with ``location.present``.  A business's fields and
    a member's are drawn once each and repeated in every review of theirs."""
    g = params["generator"]
    rng = np.random.default_rng(seed)
    B, U = g["businesses"], g["users"]
    b = _power_ranks(rng, rows, B, g["business_exponent"])
    u = _power_ranks(rng, rows, U, g["user_exponent"])
    ub, bi = np.unique(b, return_inverse=True)      # the businesses reviewed
    uu, ui = np.unique(u, return_inverse=True)      # and the members
    nb, nu = len(ub), len(uu)

    # the businesses
    address, city, state, postal, lat, lon = _places(rng, nb, g)
    name = _names(rng, nb, g)
    quality = rng.standard_normal(nb)
    s = g["business_stars"]
    stars = np.clip(np.round((s["mean"] + s["sigma"] * quality) * 2) / 2,
                    1.0, 5.0).astype(np.float32)
    b_reviews = np.maximum(rng.lognormal(g["business_reviews_log_mean"],
                                         g["business_reviews_log_sigma"],
                                         nb).astype(np.int64), 5)
    is_open = rng.random(nb) < g["open_share"]
    attrs, attr_effect = _attributes(rng, nb, g)
    hours = _hours(rng, nb, g)
    cats, cat_effect = _categories(rng, nb, g)

    # the members
    m = g["members"]
    avg = np.clip(np.round(rng.normal(m["average_stars_mean"],
                                      m["average_stars_sigma"], nu), 2),
                  1.0, 5.0).astype(np.float32)
    u_reviews = np.maximum(rng.lognormal(m["reviews_log_mean"],
                                         m["reviews_log_sigma"],
                                         nu).astype(np.int64), 1)
    counts = {"user_review_count": u_reviews}
    for c in ("useful", "funny", "cool", "fans") + COMPLIMENTS:
        share = m["per_review"][c]
        counts[c] = np.floor(u_reviews * share * rng.lognormal(
            0.0, m["count_sigma"], nu)).astype(np.int64)
    first_ms, last_ms = _ms(m["first_day"]), _ms(m["last_day"])
    since = first_ms + (rng.random(nu) ** m["since_skew"] * (
        last_ms - first_ms)).astype(np.int64)
    elite = rng.random(nu) < m["elite_share"]
    years = [str(y) for y in range(m["elite_first_year"],
                                   m["elite_last_year"] + 1)]
    start = rng.integers(0, len(years), nu)
    span = np.minimum(rng.geometric(m["elite_stop_share"], nu),
                      len(years) - start)
    elite_lists = [years[a:a + k] if e else [] for a, k, e in
                   zip(start.tolist(), span.tolist(), elite.tolist())]

    # the reviews: when, and the label
    date = since[ui] + (rng.random(rows) ** g["review_date_skew"] * (
        last_ms - since[ui])).astype(np.int64)
    lab = g["label"]
    logit = (lab["business_stars"] * (stars[bi] - s["mean"])
             + lab["average_stars"] * (avg[ui] - m["average_stars_mean"])
             + lab["quality"] * quality[bi]
             + attr_effect[bi] + cat_effect[bi]
             + lab["elite"] * elite[ui])
    lo, hi = -20.0, 20.0
    for _ in range(50):
        mid = 0.5 * (lo + hi)
        share = np.mean(1.0 / (1.0 + np.exp(-(logit + mid))))
        lo, hi = (mid, hi) if share < lab["positive_share"] else (lo, mid)
    positive = rng.random(rows) < 1.0 / (1.0 + np.exp(-(logit + 0.5 * (lo + hi))))

    xyz = np.zeros((rows, 3), np.float32)
    xyz[:, 0], xyz[:, 1], xyz[:, 2] = lat[bi], lon[bi], g["accuracy"]
    data = {
        "label": positive.astype(np.float32),
        "business_id": _ids(ub, 1)[bi], "user_id": _ids(uu, 2)[ui],
        "name": name[bi], "address": address[bi], "city": city[bi],
        "state": state[bi], "postal_code": postal[bi],
        "location": xyz, "location.present": np.ones(rows, bool),
        "business_stars": stars[bi], "average_stars": avg[ui],
        "business_review_count": b_reviews[bi],
        "is_open": is_open[bi],
        "date": date, "yelping_since": since[ui],
        "attributes": _objects((None if attrs[i] is None else dict(attrs[i])
                                for i in bi.tolist()), rows),
        "hours": _objects((None if hours[i] is None else dict(hours[i])
                           for i in bi.tolist()), rows),
        "categories": _objects((None if cats[i] is None else list(cats[i])
                                for i in bi.tolist()), rows),
        "elite": _objects((list(elite_lists[i]) for i in ui.tolist()), rows),
    }
    for c, v in counts.items():
        data[c] = v[ui]
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

    # The cell is the joined table through ONE fused program.  A program
    # whose map pivot or set pivot has no staged form would run it between
    # compiled segments and walk every row's dict or list in Python, minutes
    # a train at this size: it cannot run this configuration, and says so
    # at once instead.
    from transmogrifai_tpu.ops.collections import MultiPickListVectorizerModel
    from transmogrifai_tpu.ops.map_vectorizers import \
        TextMapPivotVectorizerModel
    for model in (TextMapPivotVectorizerModel, MultiPickListVectorizerModel):
        if not model.supports_staging:
            raise SystemExit(
                f"yelp_review_maps needs a staged {model.__name__} "
                "(transform_staged): this program has none")

    n = len(data["label"])
    every = np.ones(n, bool)
    cols = {"label": Column(T.RealNN, data["label"].copy())}
    schema = {"label": T.RealNN}
    typed = ([(c, T.ID) for c in IDS] + [(c, T.Text) for c in TEXTS]
             + [(c, getattr(T, k)) for c, k in CATEGORICALS]
             + [(c, T.Real) for c in REALS]
             + [(c, T.Integral) for c in INTEGRALS]
             + [(c, T.Binary) for c in BINARIES]
             + [(c, T.DateTime) for c in DATES]
             + [(c, T.PickListMap) for c in MAPS]
             + [(c, T.MultiPickList) for c in SETS])
    for name, kind in typed:
        values = data[name].copy()
        cols[name] = Column(kind, values, None if values.dtype == object
                            else every.copy())
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

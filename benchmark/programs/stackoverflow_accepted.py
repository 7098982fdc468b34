"""User program of the ``stackoverflow_accepted`` configuration.

The Stack Exchange Data Dump's stackoverflow.com questions (Posts with
PostTypeId 1), each joined to what is known of its asker when the question
is posted: from Users the asker's website, location and creation date, and
three event histories, as a join produces them, one list a row: the dates of
the asker's badges (Badges), earlier questions and earlier answers (Posts).
The task is a Q&A site's triage: will the question get an accepted answer
(``AcceptedAnswerId`` present).  The predictors are typed as a
TransmogrifAI user types such a join: the title and the location as
``Text``, the tags as a ``MultiPickList``, the question's and the asker's
creation as ``DateTime``, the asker's id as ``ID``, the website as a
``URL`` and the three histories as ``DateList``s; through ``transmogrify``'s
defaults, RawFeatureFilter, SanityChecker and
BinaryClassificationModelSelector's 3-fold cross-validation over the default
grids of the two linear families.

There is no network, so ``make_data`` draws the rows from the seed with the
parameters of ``configs/stackoverflow_accepted.json`` (``generator``).  It
imports nothing of the program: the reference reads the same host arrays.
Every array has one entry a row; a history is two arrays as a join makes
it (``DateLists``: every row's dates in row order and where each row's
start), sliced by rows as the others are.  ``build`` hands the program
fresh objects over COPIES of the arrays, so that no cache keyed on a Column
or an array survives from train to train.
"""

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from .amazon_polarity_text import vocabulary

TEXTS = ("title", "location")
TAGS = ("tags",)
DATES = ("created", "joined")
IDS = ("owner_id",)
URLS = ("website",)
LISTS = ("badge_dates", "question_dates", "answer_dates")
MS_DAY = 86400000
CHUNK_ROWS = 65536        # part of the generator: a chunk has draws of its own
_WIDE = 16                # bytes a title word is laid out in: letters, space


class DateLists:
    """One history of every row as a join makes it: ``values`` every row's
    dates (int64 epoch milliseconds) in row order, ``offsets`` [rows + 1]
    where each row's start.  Sliced by rows it gives those rows' lists, so
    that it reads as one entry a row like the other arrays."""

    def __init__(self, values, offsets):
        self.values, self.offsets = values, offsets

    def __len__(self):
        return len(self.offsets) - 1

    def __getitem__(self, rows):
        a, b, step = rows.indices(len(self))
        if step != 1:
            raise IndexError("a history is sliced by a run of rows")
        b = max(a, b)
        lo, hi = self.offsets[a], self.offsets[b]
        return DateLists(self.values[lo:hi], self.offsets[a:b + 1] - lo)

    def copy(self):
        return DateLists(self.values.copy(), self.offsets.copy())

    def lengths(self):
        return np.diff(self.offsets)


def _ms(date_text):
    return int(np.datetime64(date_text, "ms").astype(np.int64))


def _choice(rng, n, weights):
    """Index drawn with the stated weights (normalised here)."""
    edges = np.cumsum(np.asarray(weights, np.float64))
    return np.minimum(np.searchsorted(edges / edges[-1], rng.random(n),
                                      side="right"), len(weights) - 1)


def _power_ranks(rng, n, count, exponent):
    """Ranks 0..count-1 with P(rank) ~ (rank + 1) ** -exponent, exponent
    under 1: the inverse of the continuous law's distribution function."""
    e = 1.0 - exponent
    x = ((count + 1.0) ** e - 1.0) * rng.random(n) + 1.0
    return np.minimum((x ** (1.0 / e)).astype(np.int64) - 1, count - 1)


def _zipf_ranks(rng, n, count, exponent):
    """Ranks 0..count-1 with P(rank) proportional to (rank + 1) ** -exponent
    (a bounded Zipf law; the continuous law's inverse for exponent 1)."""
    if abs(exponent - 1.0) < 1e-9:
        return np.minimum(np.exp(rng.random(n) * np.log(count + 1.0)
                                 ).astype(np.int64) - 1, count - 1)
    return _power_ranks(rng, n, count, exponent)


def _names(count, named, stem):
    """``count`` names: the stated ones first, then ``stem`` and the rank."""
    return np.asarray(list(named) + [f"{stem}{r}" for r in
                                     range(len(named), count)], dtype=object)


def _words_text(rng, lens, vocab_size, words, capital=True):
    """One string a row of ``lens`` words each, every word's rank a bounded
    Zipf draw over the vocabulary (``amazon_polarity_text.vocabulary``'s
    spellings), the first capitalised: list of str."""
    total = int(lens.sum())
    rank = np.minimum(np.exp(rng.random(total) * np.log(vocab_size + 1.0)
                             ).astype(np.int32), vocab_size)
    wide = words.view(np.complex128).ravel()[rank].view(np.uint8).reshape(
        total, _WIDE).copy()
    last = np.cumsum(lens) - 1
    wide[:, _WIDE - 1] = 32
    wide[last, _WIDE - 1] = 10
    if capital:
        wide[last - lens + 1, 0] -= 32
    flat = wide.ravel()
    return flat[flat != 0].tobytes().decode("ascii").split("\n")[:-1], rank


def _websites(rng, ranks, g):
    """A website a user of the stated ranks (None for most), as users write
    them: by ``forms`` weights a url with a scheme, a host alone, upper case,
    a port, a path, or text that is no url; a host by a bounded Zipf law
    over ``hosts`` (the first ones named), some of them international."""
    w = g["website"]
    n = len(ranks)
    out = np.full(n, None, dtype=object)
    has = rng.random(n) < w["share"]
    k = int(has.sum())
    hosts = _zipf_ranks(rng, k, w["hosts"], w["host_exponent"])
    named = w["named_hosts"]
    idn = rng.random(k) < w["international_share"]
    host = [named[h] if h < len(named) else
            (f"bücher{h}.de" if i else f"site{h}.{w['tlds'][h % len(w['tlds'])]}")
            for h, i in zip(hosts.tolist(), idn.tolist())]
    forms = list(w["forms"])
    form = _choice(rng, k, list(w["forms"].values()))
    junk = w["not_urls"]
    urls = []
    for h, f, r in zip(host, form.tolist(), ranks[has].tolist()):
        shape = forms[f]
        if shape == "not_url":
            urls.append(junk[r % len(junk)])
            continue
        urls.append(shape.replace("{host}", h).replace(
            "{HOST}", h.upper()).replace("{user}", f"u{r}"))
    out[has] = urls
    return out


def _lengths(rng, n, spec, scale):
    """Dates a row of one history: none with ``empty_share``, else a
    log-normal draw of ``log_mean`` + the asker's ``scale`` (natural log),
    held to 1..``most``."""
    draw = rng.lognormal(spec["log_mean"] + scale, spec["log_sigma"], n)
    k = np.clip(np.rint(draw), 1, spec["most"]).astype(np.int64)
    return np.where(rng.random(n) < spec["empty_share"], 0, k)


def _chunk(seed, k, rows, g, users, words):
    """Rows ``rows`` of the table from draws of their own: the question's
    date, title, tags and the asker's three histories up to the date."""
    rng = np.random.default_rng([seed, 1, k])
    n = len(rows)
    joined, engaged = users["joined"][rows], users["engaged"][rows]
    last = _ms(g["last_day"])
    created = joined + (rng.random(n) ** g["question_date_skew"] * (
        last - joined)).astype(np.int64)
    t = g["title"]
    lens = np.clip(np.rint(rng.lognormal(
        np.log(t["mean_tokens"]) - 0.5 * t["sigma"] ** 2, t["sigma"], n)),
        t["least"], t["most"]).astype(np.int64)
    title, _ = _words_text(rng, lens, t["vocabulary"], words)
    tg = g["tags"]
    count = 1 + _choice(rng, n, tg["count_weights"])
    drawn = _zipf_ranks(rng, int(count.sum()), tg["tags"], tg["exponent"])
    # a tag once a question, in the order drawn
    row = np.repeat(np.arange(n), count)
    keep = np.sort(np.unique(row * tg["tags"] + drawn, return_index=True)[1])
    row, drawn = row[keep], drawn[keep]
    effect = np.zeros(tg["tags"])
    for name, w in tg["label"].items():
        effect[tg["named"].index(name)] = w
    tag_effect = np.bincount(row, weights=effect[drawn], minlength=n)
    names = users["tag_names"][drawn].tolist()
    ends = np.cumsum(np.bincount(row, minlength=n)).tolist()
    tag_lists = [names[a:b] for a, b in zip([0] + ends[:-1], ends)]
    out = {"created": created, "title": title, "tags": tag_lists,
           "tag_effect": tag_effect}
    h = g["histories"]
    age_days = (created - joined) / MS_DAY
    for name in LISTS:
        spec = h[name]
        scale = spec["engaged"] * engaged + spec["age"] * np.log1p(age_days)
        k_ = _lengths(rng, n, spec, scale)
        k_[created <= joined] = 0
        at = np.repeat(np.arange(n), k_)
        span = (created - joined)[at]
        out[name + ".values"] = joined[at] + (rng.random(len(at)) * span
                                              ).astype(np.int64)
        out[name + ".lengths"] = k_
    return out


def make_data(rows, seed, params):
    """Host arrays of one data set, all drawn from ``seed``: ``label``
    float32; ``owner_id``, ``title``, ``location`` and ``website`` object
    arrays of str (None where missing); ``tags`` an object array of lists of
    str; ``created`` and ``joined`` int64 epoch milliseconds; each history
    a ``DateLists``.  An asker's fields are drawn once and repeated in
    every question of theirs; row chunks are drawn side by side on a few
    threads, each from ``default_rng([seed, 1, chunk])``: the same data on
    any number of threads."""
    g = params["generator"]
    rng = np.random.default_rng([seed, 0])
    u = _power_ranks(rng, rows, g["users"], g["user_exponent"])
    uu, ui = np.unique(u, return_inverse=True)          # the askers
    nu = len(uu)
    first = _ms(g["first_day"])
    last = _ms(g["last_day"])
    joined = first + (rng.random(nu) ** g["joined_skew"] * (
        last - first)).astype(np.int64)
    engaged = rng.standard_normal(nu)
    loc = g["location"]
    places = _names(loc["places"], loc["named"], "Town ")
    where = places[_zipf_ranks(rng, nu, loc["places"], loc["exponent"])]
    where[rng.random(nu) >= loc["share"]] = None
    site = _websites(rng, uu, g)
    tg = g["tags"]
    users = {"joined": joined[ui], "engaged": engaged[ui],
             "tag_names": _names(tg["tags"], tg["named"], "tag-")}
    words = vocabulary(g["title"]["vocabulary"])

    starts = list(range(0, rows, CHUNK_ROWS))
    with ThreadPoolExecutor(min(8, len(os.sched_getaffinity(0)))) as pool:
        chunks = list(pool.map(lambda a: _chunk(
            seed, a // CHUNK_ROWS, np.arange(a, min(a + CHUNK_ROWS, rows)),
            g, users, words), starts))
    created = np.concatenate([c["created"] for c in chunks])
    data = {
        "owner_id": np.asarray([str(x) for x in (uu * 7919 % g["users"]
                                                 + 1).tolist()],
                               dtype=object)[ui],
        "title": np.empty(rows, dtype=object),
        "tags": np.empty(rows, dtype=object),
        "created": created, "joined": joined[ui],
        "location": where[ui], "website": site[ui],
    }
    at = 0
    for c in chunks:
        b = at + len(c["created"])
        data["title"][at:b] = c["title"]
        data["tags"][at:b] = c["tags"]
        at = b
    for name in LISTS:
        lengths = np.concatenate([c[name + ".lengths"] for c in chunks])
        offsets = np.zeros(rows + 1, np.int64)
        np.cumsum(lengths, out=offsets[1:])
        data[name] = DateLists(np.concatenate(
            [c[name + ".values"] for c in chunks]), offsets)

    # the label: a logistic draw on the asker's engagement, the question's
    # year, the tags, a website and the first question
    lab = g["label"]
    years = (created - first) / (365.2425 * MS_DAY)
    has_site = np.asarray([s is not None for s in site.tolist()])[ui]
    first_question = data["question_dates"].lengths() == 0
    logit = (lab["engaged"] * engaged[ui] + lab["year"] * years
             + np.concatenate([c["tag_effect"] for c in chunks])
             + lab["website"] * has_site
             + lab["first_question"] * first_question)
    lo, hi = -20.0, 20.0
    for _ in range(50):
        mid = 0.5 * (lo + hi)
        share = np.mean(1.0 / (1.0 + np.exp(-(logit + mid))))
        lo, hi = (mid, hi) if share < lab["positive_share"] else (lo, mid)
    p = 1.0 / (1.0 + np.exp(-(logit + 0.5 * (lo + hi))))
    data["label"] = (rng.random(rows) < p).astype(np.float32)
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

    # The cell is the joined table through ONE fused program, its date
    # lists held as a join makes them.  A program without a ragged date list
    # or a staged DateListVectorizerModel would walk every row's list in
    # Python between compiled segments, minutes a train at this size: it
    # cannot run this configuration, and says so at once instead.
    from transmogrifai_tpu import columns
    from transmogrifai_tpu.ops.dates import DateListVectorizerModel
    if not (hasattr(columns, "RaggedList")
            and DateListVectorizerModel.supports_staging):
        raise SystemExit(
            "stackoverflow_accepted needs ragged date lists and a staged "
            "DateListVectorizerModel (transform_staged): this program has "
            "none")

    n = len(data["label"])
    every = np.ones(n, bool)
    cols = {"label": Column(T.RealNN, data["label"].copy())}
    schema = {"label": T.RealNN}
    typed = ([(c, T.Text) for c in TEXTS] + [(c, T.MultiPickList) for c in TAGS]
             + [(c, T.DateTime) for c in DATES] + [(c, T.ID) for c in IDS]
             + [(c, T.URL) for c in URLS])
    for name, kind in typed:
        values = data[name].copy()
        cols[name] = Column(kind, values, None if values.dtype == object
                            else every.copy())
        schema[name] = kind
    for name in LISTS:
        lists = data[name].copy()
        cols[name] = Column(T.DateList, columns.RaggedList(lists.values,
                                                           lists.offsets))
        schema[name] = T.DateList
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

"""User program of the ``amazon_polarity_text`` configuration.

Amazon Review Polarity (Zhang, Zhao & LeCun 2015): a polarity label, a review
title and a review text, through upstream TransmogrifAI's defaults:
``transmogrify`` (SmartTextVectorizer finds both columns far over
``max_categorical_cardinality`` and hashes each into its own 512 buckets of
term counts, with a null indicator), RawFeatureFilter, SanityChecker, and
BinaryClassificationModelSelector's 3-fold cross-validation over the default
grids of the two linear families, ``OpLogisticRegression`` and
``OpLinearSVC``.

There is no network, so ``make_data`` draws the rows from the seed with the
parameters of ``configs/amazon_polarity_text.json`` (``generator``).  It
imports nothing of the program: the reference reads the same host arrays.
Every array it returns has one entry a row.  ``build`` hands the program
fresh objects over COPIES of them, so that no cache keyed on a Column or an
array survives from train to train.
"""

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

TEXTS = ("title", "text")
CHUNK_ROWS = 65536        # part of the generator: a chunk has draws of its own
MAX_WORD = 12
_WIDE = 16                # bytes a token is laid out in: letters, mark, space
_MARK, _GAP = MAX_WORD, MAX_WORD + 1
_SPACE, _NEWLINE = 32, 10


def _mix64(x):
    """uint64 that depends on ``x`` alone (splitmix64's finaliser)."""
    x = (x.astype(np.uint64) + np.uint64(0x9E3779B97F4A7C15))
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def vocabulary(size):
    """uint8 [size + 1, 16]: every word type's letters by rank, zero past the
    word's end (row 0 is unused).  A word's spelling is a function of its
    rank: its length is 1 + floor(log2(rank)^2 / 50) + (0 to 3, from the
    rank's hash), held to 2..12, so that frequent words are short; its
    letters are the base-26 digits of the rank's hash.  Two ranks may share a
    spelling; they are then one word type."""
    rank = np.arange(size + 1, dtype=np.uint64)
    h = _mix64(rank)
    length = (1 + np.floor(np.log2(np.maximum(rank, 1).astype(np.float64))
                           ** 2 / 50.0).astype(np.int64)
              + (h >> np.uint64(60)).astype(np.int64) % 4)
    length = np.clip(length, 2, MAX_WORD)
    words = np.zeros((size + 1, _WIDE), np.uint8)
    for j in range(MAX_WORD):
        words[:, j] = np.where(j < length,
                               97 + (h % np.uint64(26)).astype(np.uint8), 0)
        h //= np.uint64(26)
    return words


def polarity(rank, g):
    """+1 / -1 / 0 of every rank: the ranks at ``positive_slot`` and
    ``negative_slot`` of every ``polar_period`` are the polar word types."""
    slot = rank % g["polar_period"]
    return ((slot == g["positive_slot"]).astype(np.int8)
            - (slot == g["negative_slot"]).astype(np.int8))


def _lengths(rng, n, mean, sigma, most):
    """Tokens a value: a log-normal draw of the stated mean, rounded, held to
    1..most."""
    draw = rng.lognormal(np.log(mean) - 0.5 * sigma * sigma, sigma, size=n)
    return np.clip(np.rint(draw), 1, most).astype(np.int64)


def _column(rng, positive, lens, g, words):
    """The strings of one text column for one chunk of rows: list of str.

    Every token's rank is a bounded Zipf draw; a token whose polarity is
    against its row's class moves, with probability ``polar_flip``, to the
    sibling rank of the other polarity.  A word is followed by a punctuation
    mark with probability ``punctuation_rate`` and by a space (the value's
    last by a line feed, on which the chunk's one buffer is split); its first
    letter is a capital where it opens the value or with probability
    ``capital_rate``, and the whole word with ``all_capitals_rate``."""
    total = int(lens.sum())
    rank = np.minimum(
        np.exp(rng.random(total) * np.log(g["vocabulary"] + 1.0)
               ).astype(np.int32), g["vocabulary"])
    sign = polarity(rank, g)
    flip = (sign != 0) & ((sign > 0) != np.repeat(positive, lens)) & (
        rng.random(total) < g["polar_flip"])
    rank += flip * sign * (g["negative_slot"] - g["positive_slot"])

    # 16 bytes a token, gathered as one item: the letters, a mark, the gap
    wide = words.view(np.complex128).ravel()[rank].view(np.uint8).reshape(
        total, _WIDE)
    marks = np.frombuffer(g["punctuation"].encode("ascii"), np.uint8)
    u = rng.random(total)
    wide[:, _MARK] = np.where(
        u < g["punctuation_rate"],
        marks[(u * (len(marks) / g["punctuation_rate"])).astype(np.int64)
              % len(marks)], 0)
    last = np.cumsum(lens) - 1
    wide[:, _GAP] = _SPACE
    wide[last, _GAP] = _NEWLINE
    u = rng.random(total)
    shout = np.flatnonzero(u > 1.0 - g["all_capitals_rate"])
    capital = u < g["capital_rate"]
    capital[last - lens + 1] = True
    capital[shout] = False
    wide[capital, 0] -= 32
    letters = wide[shout, :MAX_WORD]
    wide[shout, :MAX_WORD] = np.where(letters > 0, letters - 32, 0)
    flat = wide.ravel()
    return flat[flat != 0].tobytes().decode("ascii").split("\n")[:-1]


def _splice_non_ascii(rng, values, rows, g):
    """One letter inside a word of each of ``rows`` becomes a non-ASCII
    character of ``non_ascii``: the word splits there, or folds to an ASCII
    letter where the lower-cased character has one."""
    for i, k in zip(rows, rng.integers(0, len(g["non_ascii"]),
                                       size=len(rows))):
        s = values[i]
        inner = [p for p in range(1, len(s) - 1)
                 if s[p - 1].isalpha() and s[p].isalpha()
                 and s[p + 1].isalpha()]
        p = inner[len(inner) // 2] if inner else len(s) // 2
        values[i] = s[:p] + g["non_ascii"][k] + s[p + 1:]


def _chunk(seed, k, n, g, words):
    """Rows ``k * CHUNK_ROWS`` onward, ``n`` of them, from draws of their
    own: (label, title, text)."""
    rng = np.random.default_rng([seed, k])
    positive = rng.random(n) < g["positive_share"]
    out = {}
    for name in TEXTS:
        p = g[name]
        lens = _lengths(rng, n, p["mean_tokens"], p["sigma"], g["max_tokens"])
        out[name] = _column(rng, positive, lens, g, words)
    odd = np.flatnonzero(rng.random(n) < g["non_ascii_row_share"])
    in_title = rng.random(len(odd)) < g["non_ascii_in_title_share"]
    _splice_non_ascii(rng, out["title"], odd[in_title], g)
    _splice_non_ascii(rng, out["text"], odd[~in_title], g)
    for i in np.flatnonzero(rng.random(n) < g["missing_title_share"]):
        out["title"][i] = None
    return positive.astype(np.float32), out["title"], out["text"]


def make_data(rows, seed, params):
    """Host arrays of one data set, all drawn from ``seed``: ``label``
    float32, ``title`` and ``text`` object arrays of str (a missing title is
    None).  Chunks of ``CHUNK_ROWS`` rows are drawn side by side on a few
    threads, each from ``default_rng([seed, chunk])``: the same data on any
    number of threads."""
    g = params["generator"]
    words = vocabulary(g["vocabulary"])
    data = {"label": np.empty(rows, np.float32),
            "title": np.empty(rows, dtype=object),
            "text": np.empty(rows, dtype=object)}
    starts = range(0, rows, CHUNK_ROWS)
    with ThreadPoolExecutor(min(8, len(os.sched_getaffinity(0)))) as pool:
        chunks = pool.map(lambda a: _chunk(
            seed, a // CHUNK_ROWS, min(CHUNK_ROWS, rows - a), g, words),
            starts)
        for a, (label, title, text) in zip(starts, chunks):
            b = a + len(label)
            data["label"][a:b] = label
            data["title"][a:b] = title
            data["text"][a:b] = text
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

    n = len(data["label"])
    cols = {"label": Column(T.RealNN, data["label"].copy())}
    schema = {"label": T.RealNN}
    for name in TEXTS:
        cols[name] = Column(T.Text, data[name].copy())
        schema[name] = T.Text
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
        min_variance=sc["min_variance"],
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

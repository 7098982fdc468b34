"""User program of the ``criteo_mixed`` configuration.

The Criteo Display Advertising Challenge table (click label, 13 nullable
integer counts, 26 nullable categoricals hashed to 8 hex digits) through
upstream TransmogrifAI's defaults: ``transmogrify`` (the integers mode-filled
with null indicators; SmartTextVectorizer pivots the categoricals of at most
30 values and hashes the others into 512 buckets each), RawFeatureFilter,
SanityChecker, and BinaryClassificationModelSelector's 3-fold
cross-validation over the default logistic-regression grid.

There is no network, so ``make_data`` draws the rows from the seed with the
parameters of ``configs/criteo_mixed.json`` (``generator``, ``cardinalities``,
``click_share``).  It imports nothing of the program: the reference reads the
same host arrays.  ``build`` hands the program fresh objects over COPIES of
them, so that no cache keyed on a Column or an array survives from train to
train.
"""

import numpy as np

INTS = tuple(f"I{j}" for j in range(1, 14))
CATS = tuple(f"C{j}" for j in range(1, 27))
_MASK = np.uint64(0xFFFFFFFF)


def _mix(rank, col):
    """uint32 that depends on column and rank alone (murmur3's finaliser)."""
    h = (rank.astype(np.uint64) * np.uint64(2654435761)
         + np.uint64((col + 1) * 0x9E3779B1)) & _MASK
    h ^= h >> np.uint64(16)
    h = (h * np.uint64(0x85EBCA6B)) & _MASK
    h ^= h >> np.uint64(13)
    h = (h * np.uint64(0xC2B2AE35)) & _MASK
    return h ^ (h >> np.uint64(16))


def _zipf_ranks(rng, n, cardinality):
    """Ranks 1..cardinality with P(rank) ~ 1 / rank: the floor of
    (cardinality + 1) ** u, u uniform."""
    x = (cardinality + 1.0) ** rng.random(n)
    return np.minimum(x.astype(np.int64), cardinality)


def make_data(rows, seed, params):
    """Host arrays of one data set, all drawn from ``seed``."""
    g = params["generator"]
    rng = np.random.default_rng(seed)
    n = rows
    data = {}
    logit = np.zeros(n, np.float64)
    sign = 1.0
    for j, name in enumerate(INTS):
        scale = g["integer_log_scale"][j]
        v = np.floor(np.expm1(rng.exponential(scale, size=n)))
        v = np.minimum(v, 2.0 ** 24).astype(np.float32)
        present = rng.random(n) >= g["integer_null_rates"][j]
        data[name] = np.where(present, v, np.float32(np.nan))
        data[name + ".present"] = present
        z = (np.log1p(v) - scale) / scale
        logit += sign * (g["integer_weights"][j] * np.where(present, z, 0.0)
                         + g["missing_weight"] * ~present)
        sign = -sign
    for j, name in enumerate(CATS):
        rank = _zipf_ranks(rng, n, params["cardinalities"][j])
        present = rng.random(n) >= g["categorical_null_rates"][j]
        uniq, inv = np.unique(_mix(rank, j), return_inverse=True)
        names = np.asarray([f"{h:08x}" for h in uniq.tolist()], dtype=object)
        col = names[inv]
        col[~present] = None
        data[name] = col
        effect = np.sqrt(3.0) * (2.0 * _mix(rank, j + 64) / 2.0 ** 32 - 1.0)
        known = present & (rank <= g["levels_with_effect"])
        logit += sign * (g["categorical_weight"] * np.where(known, effect, 0.0)
                         + g["missing_weight"] * ~present)
        sign = -sign
    lo, hi = -20.0, 20.0               # intercept for the stated click share
    for _ in range(60):
        mid = 0.5 * (lo + hi)
        share = np.mean(1.0 / (1.0 + np.exp(-(logit + mid))))
        lo, hi = (mid, hi) if share < params["click_share"] else (lo, mid)
    p = 1.0 / (1.0 + np.exp(-(logit + 0.5 * (lo + hi))))
    data["label"] = (rng.random(n) < p).astype(np.float32)
    return data


def build(data, params):
    """A new user's train: fresh Workflow, features and ColumnBatch over
    copies of the host arrays.  Returns the workflow."""
    from transmogrifai_tpu import types as T
    from transmogrifai_tpu.columns import Column, ColumnBatch
    from transmogrifai_tpu.features import features_from_schema
    from transmogrifai_tpu.models.linear import OpLogisticRegression
    from transmogrifai_tpu.ops.transmogrify import transmogrify
    from transmogrifai_tpu.selector import (BinaryClassificationModelSelector,
                                            ModelCandidate, grid)
    from transmogrifai_tpu.workflow import Workflow

    n = len(data["label"])
    cols = {"label": Column(T.RealNN, data["label"].copy())}
    schema = {"label": T.RealNN}
    for name in INTS:               # int64 and a mask, as the CSV reader's
        present = data[name + ".present"].copy()
        cols[name] = Column(T.Integral, np.where(present, data[name], 0.0)
                            .astype(np.int64), present)
        schema[name] = T.Integral
    for name in CATS:
        cols[name] = Column(T.Text, data[name].copy())
        schema[name] = T.Text
    batch = ColumnBatch(cols, n)

    t = params["transmogrify"]
    lr = params["selector"]["OpLogisticRegression"]
    label, predictors = features_from_schema(schema, response="label")
    fv = transmogrify(
        predictors, top_k=t["top_k"], min_support=t["min_support"],
        num_hashes=t["num_hashes"],
        max_categorical_cardinality=t["max_categorical_cardinality"],
        track_nulls=t["track_nulls"])
    checked = label.sanity_check(fv, remove_bad_features=True)
    selector = BinaryClassificationModelSelector(
        num_folds=params["folds"], seed=params["fold_seed"],
        models=[ModelCandidate(
            OpLogisticRegression(),
            grid(reg_param=lr["reg_param"],
                 elastic_net_param=lr["elastic_net_param"],
                 max_iter=[lr["max_iter"]]),
            "OpLogisticRegression")])
    selector.set_input(label, checked)
    pred = selector.get_output()
    return (Workflow().set_input_batch(batch).set_result_features(pred)
            .with_raw_feature_filter(
                min_fill_rate=params["raw_feature_filter"]["min_fill_rate"]))

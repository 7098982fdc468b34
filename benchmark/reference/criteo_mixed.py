"""Plain reference of the ``criteo_mixed`` configuration: the feature matrix
``transmogrify`` is stated to make (configs/criteo_mixed.json), then
``common.sweep``.  Imports nothing of the program."""

import numpy as np

from . import common, plain

INTS = tuple(f"I{j}" for j in range(1, 14))
CATS = tuple(f"C{j}" for j in range(1, 27))
FAMILIES = {"OpLogisticRegression": common.logistic_family}
question = common.winner_question


def mode_filled(values, present, wire):
    """Integral column -> (filled [N], null indicator [N]): absent cells take
    the most frequent present value (the smallest of them), all as carried on
    the wire."""
    fill = np.float32(0.0)
    if present.any():
        uniq, counts = np.unique(values[present], return_counts=True)
        fill = np.float32(uniq[np.argmax(counts)])
    v = plain.round_through(np.where(present, values, fill), wire)
    return v, (~present).astype(np.float32)


def feature_matrix(data, config, precision):
    """The stored matrix, built on the device block of rows by block of rows
    from compact host columns.  Blocks of columns in the order transmogrify
    lays them out: the integers (value, null), then every categorical in
    turn: pivoted (top-k indicators, OTHER, null) where it has at most
    ``max_categorical_cardinality`` distinct values, else hashed (512 counts,
    null).  Integer values as carried on the wire, the whole as stored."""
    import jax
    import jax.numpy as jnp
    t = config["transmogrify"]
    n = len(data["label"])
    H = t["num_hashes"]
    ints = [c for name in INTS for c in mode_filled(
        data[name], data[name + ".present"], precision.wire)]
    plan, pivots, texts = [], [], []
    for name in CATS:
        col = data[name]
        distinct = len({v for v in col if v is not None})
        if distinct <= t["max_categorical_cardinality"]:
            plan.append(("pivot", len(pivots)))
            pivots.append(plain.pivot_ids(col, t["top_k"], t["min_support"]))
        else:
            plan.append(("hash", len(texts)))
            texts.append(plain.hashed_tokens(col, H))
    storage = plain.jnp_dtype(common.storage_of(config, precision))

    @jax.jit
    def block(vals, ids, toks, nulls):
        rows = vals.shape[1]
        cols = [vals.T]
        for kind, k in plan:
            if kind == "pivot":
                cols.append((ids[k][:, None] == jnp.arange(pivots[k][1])
                             [None, :]).astype(jnp.float32))
            else:
                r, b = toks[k]
                cols.append(jnp.zeros((rows, H), jnp.float32).at[r, b].add(
                    1.0, mode="drop"))
                cols.append(nulls[k][:, None])
        return jnp.concatenate(cols, axis=1).astype(storage)

    width = (len(ints) + sum(w for _, w in pivots) + len(texts) * (H + 1))
    bounds = plain.BlockedMatrix.bounds_for(n, width)
    size = bounds[0][1]
    most = max([int(np.diff(np.searchsorted(rows, [a, b]))[0])
                for rows, _, _ in texts for a, b in bounds] + [1])
    blocks = []
    for a, b in bounds:
        pad = size - (b - a)            # one shape for every block
        toks = []
        for rows, buckets, _ in texts:
            lo, hi = np.searchsorted(rows, [a, b])
            r = np.full(most, size, np.int32)
            k = np.zeros_like(r)
            r[:hi - lo], k[:hi - lo] = rows[lo:hi] - a, buckets[lo:hi]
            toks.append((r, k))
        blk = block(
            np.stack([np.pad(c[a:b], (0, pad)) for c in ints]),
            [np.pad(i[a:b], (0, pad)) for i, _ in pivots],
            toks,
            [np.pad(null[a:b].astype(np.float32), (0, pad))
             for _, _, null in texts])
        blocks.append(blk[:b - a])
    return plain.BlockedMatrix(blocks, bounds)


def rff_dropped(data, config):
    """Raw features RawFeatureFilter drops: fill rate under the minimum."""
    floor = config["raw_feature_filter"]["min_fill_rate"]
    n = len(data["label"])
    dropped = [c for c in CATS
               if sum(v is not None for v in data[c]) / n < floor]
    return dropped + [c for c in INTS
                      if data[c + ".present"].mean() < floor]


def reference(data, config, precision, ask, seed=0):
    M = feature_matrix(data, config, precision)
    out = common.sweep(M, data["label"], config, precision, ask, FAMILIES)
    out["rff_dropped"] = sorted(rff_dropped(data, config))
    return out

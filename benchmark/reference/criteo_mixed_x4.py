"""Plain reference of the ``criteo_mixed_x4`` configuration: the question and
the feature matrix of ``criteo_mixed`` over all the rows, with the row blocks
kept on the devices the host has.

At 786,432 rows the stored matrix is 15.5 GB in bfloat16 and its kept-column
copy 13.9 GB, more than one chip holds, so block ``i`` of ``B`` lives on
device ``i * devices // B`` (plain ``jax.device_put`` of a block's inputs to
one device; a jitted piece runs where its block lives).  The reference knows
nothing of how the program partitions its rows: every reduction here is a sum
over row blocks, added on the host in the order of the rows.  The blocks'
partial sums of one device are added there in float32, the devices' sums are
pulled and added on the host in float32, and the proximal step is taken on
the host in float32.  Each device's blocks are worked from a thread of their
own: a jitted piece compiles once for every device it runs on, and the
devices' compiles and work then run side by side.  With one device visible
every block lives on it: the same code, and the rehearsal's and the tests' way.

Imports nothing of the program."""

import math
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from . import common, plain
from .criteo_mixed import CATS, INTS, mode_filled, rff_dropped

question = common.winner_question


def devices_for(config):
    """The devices the blocks are spread over: as many of the host's as the
    configuration has row shards."""
    import jax
    return jax.devices()[:int(config["partitions"]["row_shards"])]


def side_by_side(work, groups):
    """[work(g) for g in groups], each on a thread of its own."""
    if len(groups) < 2:
        return [work(g) for g in groups]
    with ThreadPoolExecutor(len(groups)) as pool:
        return list(pool.map(work, groups))


def feature_matrix(data, config, precision, devices):
    """``criteo_mixed.feature_matrix`` with each block built and kept on one
    of ``devices``: the columns, their order, the fills, pivots and hashes
    are those of the whole table."""
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

    def build(i):
        a, b = bounds[i]
        pad = size - (b - a)            # one shape for every block
        toks = []
        for rows, buckets, _ in texts:
            lo, hi = np.searchsorted(rows, [a, b])
            r = np.full(most, size, np.int32)
            k = np.zeros_like(r)
            r[:hi - lo], k[:hi - lo] = rows[lo:hi] - a, buckets[lo:hi]
            toks.append((r, k))
        args = jax.device_put(
            (np.stack([np.pad(c[a:b], (0, pad)) for c in ints]),
             [np.pad(j[a:b], (0, pad)) for j, _ in pivots],
             toks,
             [np.pad(null[a:b].astype(np.float32), (0, pad))
              for _, _, null in texts]),
            devices[i * len(devices) // len(bounds)])
        return block(*args)[:b - a]

    # block i lives on device i * devices // blocks: a device's blocks are
    # neighbours, built in the order of the rows
    per_device = {}
    for i in range(len(bounds)):
        per_device.setdefault(i * len(devices) // len(bounds), []).append(i)
    built = side_by_side(lambda idx: [build(i) for i in idx],
                         list(per_device.values()))
    blocks = [blk for part in built for blk in part]
    return plain.BlockedMatrix(blocks, bounds)


def _by_device(M, *per_block):
    """[(device, [(block, the block's entry of every list given), ...])]:
    the blocks grouped by where they live, in the order of the rows."""
    groups = {}
    for entry in zip(M.blocks, *per_block):
        dev = next(iter(entry[0].devices()))
        groups.setdefault(dev, []).append(entry)
    return list(groups.items())


def _host_sum(parts):
    """The devices' partial sums, pulled and added in float32."""
    total = None
    for p in parts:
        p = [np.asarray(x, np.float32) for x in p]
        total = p if total is None else [s + x for s, x in zip(total, p)]
    return total


def logistic_fista(M, y, weights, l2, l1, max_iter, tol, low=False):
    """``plain.logistic_fista`` for blocks that live on several devices: the
    same problem, the same iteration (standardised features, FISTA with
    adaptive restart from zero, step 1 / (0.25 sigma^2 + l2), sigma^2 by 16
    power iterations from the uniform vector, stopped after ``max_iter``
    iterations or once the largest move is at most ``tol``), the same
    per-block pieces (``plain._kernels``).  Returns (coef [L, D],
    intercept [L]) un-scaled to the raw features, float64."""
    import jax
    import jax.numpy as jnp
    moments, xs_mv, xs_tmv, loss_grad = plain._kernels(low)
    L, d = weights.shape[0], M.d
    f32 = np.float32
    wsum = weights.sum(axis=1, dtype=np.float64).astype(f32)
    groups = [(dev, [(xb, *jax.device_put(
        (y[a:b].astype(f32), weights[:, a:b].astype(f32),
         (weights[:, a:b] / wsum[:, None]).astype(f32)), dev))
        for xb, (a, b) in blocks])
        for dev, blocks in _by_device(M, M.bounds)]

    def over_devices(piece, *state):
        """Σ over blocks of ``piece(block, *state)``: a device's blocks added
        on the device, the devices' sums on the host."""
        def on_device(group):
            dev, blocks = group
            on_dev = jax.device_put(state, dev)
            acc = None
            for blk in blocks:
                out = piece(blk, *on_dev)
                acc = out if acc is None else [s + x
                                               for s, x in zip(acc, out)]
            return acc
        return _host_sum(side_by_side(on_device, groups))

    mean, m2 = over_devices(lambda blk: moments(blk[0], blk[3]))
    scale = np.sqrt(np.maximum(m2 - mean * mean, f32(1e-12)))

    def gram_mv(V):
        return over_devices(
            lambda blk, V, mean, scale: [xs_tmv(
                blk[0], blk[3] * xs_mv(blk[0], V, mean, scale), mean, scale)],
            V, mean, scale)[0]

    V = np.full((L, d), 1.0 / math.sqrt(d), f32)
    for _ in range(16):
        U = gram_mv(V)
        V = U / (np.linalg.norm(U, axis=1, keepdims=True) + f32(1e-12))
    sigma_sq = np.sum(V * gram_mv(V), axis=1)

    l2 = np.asarray(l2, f32)
    l1 = np.asarray(l1, f32)
    step = (1.0 / np.maximum(f32(0.25) * sigma_sq + l2, f32(1e-12))
            )[:, None].astype(f32)
    wsum_j = jnp.asarray(wsum)

    def grad_piece(blk, C, b, mean, scale, wsum_d):
        xb, yy, w, _ = blk
        lin = xs_mv(xb, C, mean, scale) + b[:, None]
        _, glin = loss_grad(lin, yy, w, wsum_d)
        return [xs_tmv(xb, glin, mean, scale), jnp.sum(glin, axis=1)]

    coef = np.zeros((L, d), f32)
    icpt = np.zeros((L,), f32)
    z_c, z_i = coef, icpt
    t = np.ones((L,), f32)
    live = np.ones(L, bool)
    for _ in range(int(max_iter)):
        g_c, g_i = over_devices(grad_piece, z_c, z_i, mean, scale, wsum_j)
        g_c = g_c + l2[:, None] * z_c
        u = z_c - step * g_c
        new_c = (np.sign(u) * np.maximum(np.abs(u) - step * l1[:, None],
                                         f32(0.0))).astype(f32)
        new_i = z_i - step[:, 0] * g_i
        t_new = f32(0.5) * (1.0 + np.sqrt(1.0 + 4.0 * t * t)).astype(f32)
        beta = (t - 1.0) / t_new
        restart = np.sum((z_c - new_c) * (new_c - coef), axis=1) > 0.0
        beta = np.where(restart, f32(0.0), beta).astype(f32)
        t_new = np.where(restart, f32(1.0), t_new).astype(f32)
        zc_next = new_c + beta[:, None] * (new_c - coef)
        zi_next = new_i + beta * (new_i - icpt)
        delta = np.max(np.abs(new_c - coef), axis=1) + np.abs(new_i - icpt)
        coef = np.where(live[:, None], new_c, coef)
        icpt = np.where(live, new_i, icpt)
        z_c = np.where(live[:, None], zc_next, z_c)
        z_i = np.where(live, zi_next, z_i)
        t = np.where(live, t_new, t)
        live = live & (delta > tol)
        if not live.any():
            break
    raw = coef / scale
    return (np.asarray(raw, np.float64),
            np.asarray(icpt - np.sum(mean * raw, axis=1), np.float64))


def logistic_family(M, y, folds, p, precision, refit):
    """``common.logistic_family`` over blocks on several devices: every grid
    point's AuPR on every fold's validation rows and, with ``refit``, that
    point's fit on all rows."""
    n = len(y)
    grid = common.grid_points(p)
    lanes = [(va, g) for va in folds for g in grid]
    weights = np.ones((len(lanes) + (refit is not None), n), np.float32)
    for lane, (va, _) in enumerate(lanes):
        weights[lane, va] = 0.0
    points = [g for _, g in lanes] + ([refit] if refit is not None else [])
    l2, l1 = zip(*(common.l2_l1(g["reg_param"],
                                g.get("elastic_net_param", 0.0))
                   for g in points))
    coef, icpt = logistic_fista(
        M, y, weights, np.asarray(l2), np.asarray(l1), p["max_iter"],
        p["tol"], low=precision.low_matmul)
    S = plain.margins(M, coef, icpt, low=precision.low_matmul)
    G = len(grid)
    cv = [{"params": g,
           "per_fold": [plain.aupr(y[va], S[va, f * G + i])
                        for f, va in enumerate(folds)]}
          for i, g in enumerate(grid)]
    fit = {}
    if refit is not None:
        fit = {"coef": coef[-1], "intercept": float(icpt[-1]),
               "train_auroc": plain.auroc(y, S[:, -1])}
    return cv, fit


FAMILIES = {"OpLogisticRegression": logistic_family}


def reference(data, config, precision, ask, seed=0):
    M = feature_matrix(data, config, precision, devices_for(config))
    out = common.sweep(M, data["label"], config, precision, ask, FAMILIES)
    out["rff_dropped"] = sorted(rff_dropped(data, config))
    return out

"""Plain reference of the ``amazon_polarity_text`` configuration: the
1,026-column matrix ``transmogrify`` is stated to make of a review's title
and text (configs/amazon_polarity_text.json), then ``common.sweep`` over the
two linear families.  Imports nothing of the program.

The tokenizer's rule, as stated: a value is lower-cased (Python's
``str.lower``, so a character whose lower case is or holds an ASCII letter
gives that letter), its tokens are the runs of ``[A-Za-z0-9_']``, a token's
bucket is FNV-1a 32 of its bytes modulo ``num_hashes``, a bucket holds the
count of its tokens.  Upstream's default analyzer (Lucene's standard
tokenizer) also splits on the apostrophe and drops nothing else that these
values hold; that departure is ``ops/text.py``'s and is kept here.

``svc_family`` minimises the SQUARED hinge with an L2 penalty by FISTA, as
``models/linear.py`` does.  Upstream's ``OpLinearSVC`` (Spark's LinearSVC)
minimises the hinge itself by OWLQN: that departure is the program's, noted
here because the reference follows the configuration as the program states
it, not upstream's solver.
"""

import math
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from . import common, plain

TEXTS = ("title", "text")
SQUARED_HINGE_CURVATURE = 2.0
TOKEN_ROWS = 32768     # rows tokenised at a time, WORKERS pieces side by side
WORKERS = 8
question = common.winner_question


def column_tokens(strings, num_hashes):
    """(row [T], bucket [T], null [N]) of every token of a block of one text
    column, in no stated order.  The ASCII values go through
    ``plain.hashed_tokens`` together; a value with a character outside ASCII
    goes through the stated rule on its own, so that one such value does not
    send the block down the row-by-row path."""
    n = len(strings)
    null = np.fromiter((s is None for s in strings), bool, count=n)
    odd = [i for i in range(n)
           if strings[i] is not None and not strings[i].isascii()]
    ascii_only = strings
    if odd:
        ascii_only = strings.copy()
        ascii_only[odd] = None
    rows, buckets, _ = plain.hashed_tokens(ascii_only, num_hashes)
    more_rows, more_buckets = [], []
    for i in odd:
        for t in plain.TOKEN_RE.findall(strings[i].lower()):
            more_rows.append(i)
            more_buckets.append(plain.fnv1a_32(t) % num_hashes)
    return (np.r_[rows, np.asarray(more_rows, np.int64)].astype(np.int32),
            np.r_[buckets, np.asarray(more_buckets, np.int64)].astype(
                np.int32), null)


def feature_matrix(data, config, precision):
    """The stored matrix, built on the device block of rows by block of rows.
    Columns in the order transmogrify lays them out: for the title and then
    the text, 512 counts and the null indicator.  Counts are summed in
    float32 and then stored."""
    import jax
    import jax.numpy as jnp
    H = config["transmogrify"]["num_hashes"]
    n = len(data["label"])
    storage = plain.jnp_dtype(common.storage_of(config, precision))
    bounds = plain.BlockedMatrix.bounds_for(n, len(TEXTS) * (H + 1))
    size = bounds[0][1]
    # every block's columns tokenised in pieces of TOKEN_ROWS rows, side by
    # side: numpy releases the interpreter lock, and a piece's scratch
    # arrays stay in the tens of MB
    pieces = [(name, a, s, min(s + TOKEN_ROWS, b)) for a, b in bounds
              for name in TEXTS for s in range(a, b, TOKEN_ROWS)]
    with ThreadPoolExecutor(min(WORKERS, len(os.sched_getaffinity(0)))
                            ) as pool:
        done = pool.map(lambda p: column_tokens(data[p[0]][p[2]:p[3]], H),
                        pieces)
        parts = {}
        for (name, a, s, _), (rows, buckets, null) in zip(pieces, done):
            parts.setdefault((a, name), []).append((rows + (s - a), buckets,
                                                    null))
    tokens = [[tuple(np.concatenate(x) for x in zip(*parts[a, name]))
               for name in TEXTS] for a, _ in bounds]
    most = max([len(r) for blk in tokens for r, _, _ in blk] + [1])

    @jax.jit
    def block(toks, nulls):
        cols = []
        for (r, k), null in zip(toks, nulls):
            cols.append(jnp.zeros((size, H), jnp.float32).at[r, k].add(
                1.0, mode="drop"))
            cols.append(null[:, None])
        return jnp.concatenate(cols, axis=1).astype(storage)

    blocks = []
    for (a, b), blk in zip(bounds, tokens):
        toks = []
        for rows, buckets, _ in blk:
            r = np.full(most, size, np.int32)       # past the block: dropped
            k = np.zeros(most, np.int32)
            r[:len(rows)], k[:len(rows)] = rows, buckets
            toks.append((r, k))
        nulls = [np.pad(null.astype(np.float32), (0, size - (b - a)))
                 for _, _, null in blk]
        blocks.append(block(toks, nulls)[:b - a])
    return plain.BlockedMatrix(blocks, bounds)


def squared_hinge_fista(M, y, weights, l2, max_iter, tol, low=False):
    """L2-penalised squared-hinge fit for L lanes at once, each with its own
    row weights [L, N] and penalty: minimise mean max(0, 1 - s (Xs w + b))^2
    + l2/2 |w|^2, s = +1 for a label over 0.5 and -1 otherwise, on features
    standardised by the lane's weighted mean and population deviation (floor
    1e-6), the intercept unpenalised, by FISTA with adaptive restart from
    zero, step 1 / (2 sigma^2 + l2), sigma^2 by 16 power iterations from the
    uniform vector, stopped after ``max_iter`` iterations or once the
    largest coefficient move is at most ``tol``.  Returns (coef [L, D],
    intercept [L]) un-scaled to the raw features."""
    import jax
    import jax.numpy as jnp
    moments, xs_mv, xs_tmv, _ = plain._kernels(low)

    @jax.jit
    def hinge_grad(lin, sb, wb, wsum):          # -> d loss / d lin [L, nb]
        viol = jnp.maximum(0.0, 1.0 - sb * lin)
        return wb * (-2.0 * viol * sb) / wsum[:, None]

    L, d = weights.shape[0], M.d
    sb = [jnp.asarray(np.where(y[a:b] > 0.5, 1.0, -1.0), jnp.float32)
          for a, b in M.bounds]
    wb = [jnp.asarray(weights[:, a:b], jnp.float32) for a, b in M.bounds]
    wsum = jnp.asarray(weights.sum(axis=1, dtype=np.float64), jnp.float32)
    wn = [w / wsum[:, None] for w in wb]

    m1 = m2 = 0.0
    for xb, w in zip(M.blocks, wn):
        a, b = moments(xb, w)
        m1, m2 = m1 + a, m2 + b
    mean = m1
    scale = jnp.sqrt(jnp.maximum(m2 - mean * mean, 1e-12))

    def gram_mv(V):
        out = 0.0
        for xb, w in zip(M.blocks, wn):
            out = out + xs_tmv(xb, w * xs_mv(xb, V, mean, scale), mean, scale)
        return out

    V = jnp.full((L, d), 1.0 / math.sqrt(d), jnp.float32)
    for _ in range(16):
        U = gram_mv(V)
        V = U / (jnp.linalg.norm(U, axis=1, keepdims=True) + 1e-12)
    sigma_sq = jnp.sum(V * gram_mv(V), axis=1)

    l2 = jnp.asarray(l2, jnp.float32)
    step = (1.0 / jnp.maximum(SQUARED_HINGE_CURVATURE * sigma_sq + l2,
                              1e-12))[:, None]

    def smooth_grad(C, b):
        g_c, g_b = l2[:, None] * C, 0.0
        for xb, ss, w in zip(M.blocks, sb, wb):
            glin = hinge_grad(xs_mv(xb, C, mean, scale) + b[:, None], ss, w,
                              wsum)
            g_c = g_c + xs_tmv(xb, glin, mean, scale)
            g_b = g_b + jnp.sum(glin, axis=1)
        return g_c, g_b

    coef = jnp.zeros((L, d), jnp.float32)
    icpt = jnp.zeros((L,), jnp.float32)
    z_c, z_i = coef, icpt
    t = jnp.ones((L,), jnp.float32)
    live = np.ones(L, bool)
    for _ in range(int(max_iter)):
        g_c, g_i = smooth_grad(z_c, z_i)
        new_c = z_c - step * g_c
        new_i = z_i - step[:, 0] * g_i
        t_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
        beta = (t - 1.0) / t_new
        restart = jnp.sum((z_c - new_c) * (new_c - coef), axis=1) > 0.0
        beta = jnp.where(restart, 0.0, beta)
        t_new = jnp.where(restart, 1.0, t_new)
        zc_next = new_c + beta[:, None] * (new_c - coef)
        zi_next = new_i + beta * (new_i - icpt)
        delta = np.asarray(jnp.max(jnp.abs(new_c - coef), axis=1)
                           + jnp.abs(new_i - icpt))
        keep = jnp.asarray(live)
        coef = jnp.where(keep[:, None], new_c, coef)
        icpt = jnp.where(keep, new_i, icpt)
        z_c = jnp.where(keep[:, None], zc_next, z_c)
        z_i = jnp.where(keep, zi_next, z_i)
        t = jnp.where(keep, t_new, t)
        live &= delta > tol
        if not live.any():
            break
    raw = coef / scale
    return (np.asarray(raw, np.float64),
            np.asarray(icpt - jnp.sum(mean * raw, axis=1), np.float64))


def svc_family(M, y, folds, p, precision, refit):
    """The linear SVC family's answers: every grid point's AuPR (of the raw
    margins) on every fold's validation rows, and with ``refit`` (a grid
    point) that point's fit on all rows.  Returns (panel entries, refit
    answers)."""
    n = len(y)
    grid = common.grid_points(p)
    lanes = [(va, g) for va in folds for g in grid]
    weights = np.ones((len(lanes) + (refit is not None), n), np.float32)
    for lane, (va, _) in enumerate(lanes):
        weights[lane, va] = 0.0
    points = [g for _, g in lanes] + ([refit] if refit is not None else [])
    coef, icpt = squared_hinge_fista(
        M, y, weights, np.asarray([g["reg_param"] for g in points]),
        p["max_iter"], p["tol"], low=precision.low_matmul)
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


FAMILIES = {"OpLogisticRegression": common.logistic_family,
            "OpLinearSVC": svc_family}


def rff_dropped(data, config):
    """Raw features RawFeatureFilter drops: fill rate under the minimum,
    where a filled value is one that is neither missing nor empty."""
    floor = config["raw_feature_filter"]["min_fill_rate"]
    n = len(data["label"])
    return [c for c in TEXTS if sum(bool(v) for v in data[c]) / n < floor]


def reference(data, config, precision, ask, seed=0):
    M = feature_matrix(data, config, precision)
    out = common.sweep(M, data["label"], config, precision, ask, FAMILIES)
    out["rff_dropped"] = sorted(rff_dropped(data, config))
    return out

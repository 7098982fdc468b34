"""Plain reference pieces shared by the configurations' references.

Straightforward numpy (float64 on the host) and ``jax.numpy`` (float32 at
``highest`` matmul precision, in row blocks) implementations of the semantics
the configurations state.  Nothing here imports the program, and nothing here
takes anything the program has made.

``Precision`` carries the value precision of a run of the reference:
``stated`` is what the configuration states (on an accelerator a bfloat16
wire, float32 accumulation); ``control`` is the next one down (there a
saturating float8_e4m3fn wire and storage, bfloat16 matmul operands and
accumulation)
and is what the comparison has to fail.
"""

import math
import re
from dataclasses import dataclass

import numpy as np

TOKEN_RE = re.compile(r"[A-Za-z0-9_']+")
BLOCK_ROWS = 65536
BLOCK_ELEMS = 1 << 28


@dataclass(frozen=True)
class Precision:
    name: str
    wire: str            # dtype real values are rounded through on the wire
    low_matmul: bool     # bf16 operands and accumulation in the matmuls
    platform: str

    @staticmethod
    def stated(platform):
        """What the configuration states: on an accelerator real values
        cross the host link as bfloat16; a CPU backend carries exact
        float32 and stores float32 (``columns.to_device_f32``)."""
        return Precision("stated", "float32" if platform == "cpu"
                         else "bfloat16", False, platform)

    @staticmethod
    def control(platform):
        """The nearest precision below the stated one."""
        return Precision("control", "bfloat16" if platform == "cpu"
                         else "float8_e4m3fn", True, platform)


def round_through(x, dtype_name):
    """float32 values of ``x`` after a round trip through ``dtype_name``,
    saturating: a value beyond the type's range takes its largest finite
    value (a count of a million is 448 in float8_e4m3fn, not NaN)."""
    import ml_dtypes
    dt = {"bfloat16": ml_dtypes.bfloat16,
          "float8_e4m3fn": ml_dtypes.float8_e4m3fn,
          "float32": np.float32}[dtype_name]
    top = float(ml_dtypes.finfo(dt).max)
    return np.clip(np.asarray(x, np.float32), -top, top).astype(dt).astype(
        np.float32)


# --------------------------------------------------------------------------
# feature matrix pieces (host)
# --------------------------------------------------------------------------

def fnv1a_32(token):
    h = 2166136261
    for b in token.encode("utf-8"):
        h = ((h ^ b) * 16777619) & 0xFFFFFFFF
    return h


_TOKEN_BYTE = np.zeros(256, bool)
for _c in "abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789_'":
    _TOKEN_BYTE[ord(_c)] = True


def hashed_tokens(strings, num_hashes):
    """(row [T], bucket [T], null [N]) of every token of a text column, in
    row order: lower-case, tokens are runs of [A-Za-z0-9_'], bucket = FNV-1a
    32 of the token's bytes modulo ``num_hashes``.  ASCII columns go through
    numpy over the bytes of the whole column; anything else row by row."""
    n = len(strings)
    null = np.fromiter((s is None for s in strings), bool, count=n)
    present = np.flatnonzero(~null)
    joined = "\n".join(strings[present].tolist()).lower()
    if not joined.isascii():
        rows, cols, bucket_of = [], [], {}
        for i in present:
            for t in TOKEN_RE.findall(strings[i].lower()):
                b = bucket_of.get(t)
                if b is None:
                    b = bucket_of[t] = fnv1a_32(t) % num_hashes
                rows.append(i)
                cols.append(b)
        return np.asarray(rows, np.int64), np.asarray(cols, np.int64), null
    b = np.frombuffer(joined.encode("ascii"), np.uint8)
    tok = np.r_[False, _TOKEN_BYTE[b], False]
    start = np.flatnonzero(tok[1:-1] & ~tok[:-2])
    end = np.flatnonzero(tok[1:-1] & ~tok[2:]) + 1
    line = np.searchsorted(np.flatnonzero(b == 10), start)
    h = np.full(len(start), 2166136261, np.uint64)
    for j in range(int((end - start).max()) if len(start) else 0):
        live = start + j < end
        h[live] = ((h[live] ^ b[start[live] + j]) * np.uint64(16777619)
                   ) & np.uint64(0xFFFFFFFF)
    return present[line], (h % np.uint64(num_hashes)).astype(np.int64), null


def pivot_ids(values, top_k, min_support):
    """(ids [N] int32, width): the slot of every row among the top-k values
    by (count desc, value asc) with at least ``min_support`` rows, then
    OTHER, then null; ``width`` = k + 2 indicator columns."""
    present = np.fromiter((v is not None for v in values), bool,
                          count=len(values))
    uniq, inv, counts = np.unique(values[present].astype(str),
                                  return_inverse=True, return_counts=True)
    order = sorted((i for i in range(len(uniq)) if counts[i] >= min_support),
                   key=lambda i: (-counts[i], uniq[i]))[:top_k]
    slot = np.full(len(uniq), len(order), np.int32)      # OTHER
    slot[order] = np.arange(len(order))
    ids = np.full(len(values), len(order) + 1, np.int32)  # null
    ids[present] = slot[inv]
    return ids, len(order) + 2


def mean_filled(values, present, wire):
    """Real column -> (filled [N], null indicator [N]): absent cells take the
    mean of the present ones, all as carried on the wire."""
    v = round_through(np.where(present, values, 0.0), wire)
    fill = np.float32(v[present].astype(np.float64).mean()) if present.any() \
        else np.float32(0.0)
    return np.where(present, v, fill), (~present).astype(np.float32)


# --------------------------------------------------------------------------
# SanityChecker statistics (host, float64 across blocks)
# --------------------------------------------------------------------------

def column_stats(M, y):
    """[5, D] float64: mean, variance (ddof 1), min, max, Pearson correlation
    with ``y``, of the stored matrix.  Block sums on the device in float32
    (products at ``highest``), accumulated across blocks in float64 on the
    host; moments about a first-pass mean."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def first(xb):
        x = xb.astype(jnp.float32)
        return x.sum(axis=0), x.min(axis=0), x.max(axis=0)

    @jax.jit
    def second(xb, yc, m32):
        xc = xb.astype(jnp.float32) - m32
        return (jnp.sum(xc * xc, axis=0), _dot(yc[None, :], xc, False)[0],
                xc.sum(axis=0))

    n, d = M.n, M.d
    s1 = np.zeros(d)
    mn = np.full(d, np.inf)
    mx = np.full(d, -np.inf)
    for xb in M.blocks:
        a, lo, hi = (np.asarray(v, np.float64) for v in first(xb))
        s1 += a
        mn, mx = np.minimum(mn, lo), np.maximum(mx, hi)
    mean = s1 / n
    ym = float(np.mean(y, dtype=np.float64))
    m32 = jnp.asarray(mean, jnp.float32)
    sxx, sxy, sx = np.zeros(d), np.zeros(d), np.zeros(d)
    for (a, b), xb in zip(M.bounds, M.blocks):
        yc = jnp.asarray(y[a:b] - ym, jnp.float32)
        p, q, r = (np.asarray(v, np.float64) for v in second(xb, yc, m32))
        sxx, sxy, sx = sxx + p, sxy + q, sx + r
    resid = mean - np.asarray(m32, np.float64)   # the mean rounded to f32
    sxy = sxy - resid * float(np.sum(y.astype(np.float64) - ym))
    sxx = np.maximum(sxx - 2 * resid * sx + n * resid * resid, 0.0)
    var = sxx / max(n - 1, 1)
    syy = float(np.sum((y.astype(np.float64) - ym) ** 2))
    corr = sxy / np.maximum(np.sqrt(sxx * syy), 1e-12)
    return np.stack([mean, var, mn, mx, corr])


def sanity_keep(stats, sc):
    """Columns SanityChecker keeps under the configuration's rules."""
    var, corr = stats[1], np.abs(stats[4])
    bad = (np.isfinite(corr) & ((corr > sc["max_correlation"])
                                | (corr < sc["min_correlation"]))
           ) | (var < sc["min_variance"])
    keep = np.flatnonzero(~bad)
    return keep if len(keep) else np.arange(stats.shape[1])


def sanity_sample(n, sc):
    """Row sample SanityChecker reads (None: every row): all rows up to the
    limit, else a draw without replacement from numpy's
    default_rng(sample_seed)."""
    limit = int(sc["sample_upper_limit"])
    if n <= limit:
        return None
    return np.random.default_rng(int(sc["sample_seed"])).choice(
        n, size=limit, replace=False)


# --------------------------------------------------------------------------
# metrics (host, float64)
# --------------------------------------------------------------------------

def aupr(y, scores):
    """Area under the PR curve, threshold-grouped, trapezoid over recall with
    a (0, 1) point in front (MLlib's)."""
    order = np.argsort(-scores, kind="stable")
    s, yy = scores[order], y[order] > 0.5
    tp = np.cumsum(yy, dtype=np.float64)
    fp = np.cumsum(~yy, dtype=np.float64)
    last = np.r_[s[1:] != s[:-1], True]
    tp, fp = tp[last], fp[last]
    if not len(tp) or tp[-1] <= 0:
        return 0.0
    precision = np.r_[1.0, tp / np.maximum(tp + fp, 1e-12)]
    recall = np.r_[0.0, tp / tp[-1]]
    return float(np.sum(np.diff(recall) * (precision[1:] + precision[:-1]) / 2))


def auroc(y, scores):
    """Mann-Whitney AUC with midranks."""
    from_sorted = np.argsort(scores, kind="stable")
    s, pos = scores[from_sorted], y[from_sorted] > 0.5
    neg_before = np.concatenate([[0.0], np.cumsum(~pos, dtype=np.float64)])
    left = np.searchsorted(s, s, side="left")
    right = np.searchsorted(s, s, side="right")
    below = neg_before[left]
    same = neg_before[right] - neg_before[left]
    n_pos, n_neg = float(pos.sum()), float((~pos).sum())
    if n_pos * n_neg <= 0:
        return 0.0
    return float(np.sum(pos * (below + 0.5 * same)) / (n_pos * n_neg))


def cv_folds(n, folds, seed):
    """Validation row sets of the k folds: numpy default_rng(seed)
    permutation of the rows, cut into k contiguous parts."""
    perm = np.random.default_rng(seed).permutation(n)
    return np.array_split(perm, folds)


# --------------------------------------------------------------------------
# elastic-net logistic regression (device, row blocks)
# --------------------------------------------------------------------------

def jnp_dtype(name):
    import jax.numpy as jnp
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
            "float8_e4m3fn": jnp.float8_e4m3fn}[name]


class BlockedMatrix:
    """[N, D] matrix on the device in row blocks, values as stored."""

    def __init__(self, blocks, bounds):
        self.blocks, self.bounds = list(blocks), list(bounds)
        self.n, self.d = self.bounds[-1][1], int(self.blocks[0].shape[1])

    @staticmethod
    def bounds_for(n, d=1):
        """Row blocks of at most BLOCK_ELEMS cells of ``d`` columns, a
        multiple of 8,192 rows and at most 131,072."""
        rows = min(2 * BLOCK_ROWS, max(8192, BLOCK_ELEMS // d // 8192 * 8192))
        return [(a, min(a + rows, n)) for a in range(0, n, rows)]

    def take_columns(self, keep):
        import jax.numpy as jnp
        if len(keep) == self.d:
            return self
        k = jnp.asarray(keep)
        return BlockedMatrix([b[:, k] for b in self.blocks], self.bounds)

    def take_rows(self, idx):
        """The rows ``idx`` (sorted here), as a matrix of its own."""
        import jax.numpy as jnp
        idx = np.sort(np.asarray(idx))
        parts = [blk[jnp.asarray(idx[(idx >= a) & (idx < b)] - a)]
                 for (a, b), blk in zip(self.bounds, self.blocks)]
        X = jnp.concatenate(parts)
        bounds = self.bounds_for(len(idx), self.d)
        return BlockedMatrix([X[a:b] for a, b in bounds], bounds)


def _dot(a, b, low):
    import jax
    import jax.numpy as jnp
    if low:
        return jnp.dot(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                       preferred_element_type=jnp.bfloat16
                       ).astype(jnp.float32)
    return jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)


def _kernels(low):
    """Jitted per-block pieces, lanes batched on the last axis."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def moments(xb, wb):                       # wb [L, nb]
        x = xb.astype(jnp.float32)
        return _dot(wb, x, low), _dot(wb, x * x, low)

    @jax.jit
    def xs_mv(xb, V, mean, scale):             # V [L, D] -> [L, nb]
        v = V / scale
        return _dot(xb, v.T, low).T - jnp.sum(mean * v, axis=1)[:, None]

    @jax.jit
    def xs_tmv(xb, U, mean, scale):            # U [L, nb] -> [L, D]
        return (_dot(U, xb, low)
                - mean * jnp.sum(U, axis=1)[:, None]) / scale

    @jax.jit
    def loss_grad(lin, yb, wb, wsum):          # -> loss sums [L], glin [L, nb]
        ls = jax.nn.softplus(jnp.where(yb > 0.5, -lin, lin))
        p = jax.nn.sigmoid(lin)
        return (jnp.sum(wb * ls, axis=1) / wsum,
                wb * (p - yb) / wsum[:, None])

    return moments, xs_mv, xs_tmv, loss_grad


def logistic_fista(M, y, weights, l2, l1, max_iter, tol, low=False):
    """Elastic-net logistic regression for L lanes at once, each with its own
    row weights [L, N] and penalties: minimise mean log-loss(Xs w + b)
    + l2/2 |w|^2 + l1 |w|_1 on features standardised by the lane's weighted
    mean and population deviation (floor 1e-6), by FISTA with adaptive
    restart from zero, step 1 / (0.25 sigma^2 + l2), sigma^2 by 16 power
    iterations from the uniform vector, stopped after ``max_iter`` iterations
    or once the largest coefficient move is at most ``tol``.  Returns
    (coef [L, D], intercept [L]) un-scaled to the raw features."""
    import jax.numpy as jnp
    moments, xs_mv, xs_tmv, loss_grad = _kernels(low)
    L, d = weights.shape[0], M.d
    yb = [jnp.asarray(y[a:b], jnp.float32) for a, b in M.bounds]
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
    l1 = jnp.asarray(l1, jnp.float32)
    step = (1.0 / jnp.maximum(0.25 * sigma_sq + l2, 1e-12))[:, None]

    def smooth_grad(C, b):
        g_c, g_b = l2[:, None] * C, 0.0
        for xb, yy, w in zip(M.blocks, yb, wb):
            lin = xs_mv(xb, C, mean, scale) + b[:, None]
            _, glin = loss_grad(lin, yy, w, wsum)
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
        u = z_c - step * g_c
        new_c = jnp.sign(u) * jnp.maximum(jnp.abs(u) - step * l1[:, None], 0.0)
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


def margins(M, coef, intercept, low=False):
    """[N, L] float64 margins X coef + intercept."""
    import jax.numpy as jnp
    C = jnp.asarray(coef, jnp.float32).T
    b = jnp.asarray(intercept, jnp.float32)
    return np.concatenate(
        [np.asarray(_dot(xb, C, low) + b[None, :], np.float64)
         for xb in M.blocks])

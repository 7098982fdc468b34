"""Sharded fit kernels — the CV grid and stat reductions as single GSPMD
programs over the (data × model) mesh.

Design (SURVEY.md §2.6): the reference fans out k×Σ|grid| Spark jobs from a
JVM thread pool (OpValidator.scala:320-349).  Here the whole grid is ONE XLA
program: the data matrix is row-sharded over 'data' (gradients reduce via
psum-style collectives XLA inserts automatically), and the candidate axis is
``vmap``-ed then sharded over 'model' — every TPU core trains its slice of
candidates simultaneously on its slice of rows.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import DATA_AXIS, MODEL_AXIS, candidate_sharding, data_sharding, replicated_sharding


# --------------------------------------------------------------------------
# stat reductions (P2): one pass, collectives inserted by XLA
# --------------------------------------------------------------------------

def sharded_col_stats(X, y, mesh: Mesh):
    """Column moments + label correlation with rows sharded over 'data'
    (≙ SanityChecker colStats on executors, SanityChecker.scala:575)."""

    @functools.partial(
        jax.jit,
        in_shardings=(data_sharding(mesh, 2), data_sharding(mesh, 1)),
        out_shardings=replicated_sharding(mesh))
    def _stats(X, y):
        n = X.shape[0]
        mean = jnp.mean(X, axis=0)
        var = jnp.var(X, axis=0)
        ym = jnp.mean(y)
        yc = y - ym
        Xc = X - mean
        cov = yc @ Xc
        denom = jnp.sqrt(jnp.sum(Xc * Xc, axis=0) * jnp.sum(yc * yc))
        corr = cov / jnp.maximum(denom, 1e-12)
        return jnp.stack([mean, var, corr])

    return _stats(X, y)


# --------------------------------------------------------------------------
# grid-parallel logistic regression (P3)
# --------------------------------------------------------------------------

def _fista_logreg_fixed(X, y, l2, l1, n_iter: int):
    """Fixed-iteration FISTA for binary logistic (uniform work per candidate →
    perfectly vmappable).  Returns (coef [D], intercept)."""
    n, d = X.shape

    def obj_grad(w, b):
        logits = X @ w + b
        p = jax.nn.sigmoid(logits)
        g = (p - y) / n
        return X.T @ g + l2 * w, jnp.sum(g)

    # Lipschitz bound: 0.25 * max row-sum bound via matmul-free estimate
    L = 0.25 * jnp.sum(X * X) / n + l2
    step = 1.0 / jnp.maximum(L, 1e-12)

    def prox(u):
        return jnp.sign(u) * jnp.maximum(jnp.abs(u) - step * l1, 0.0)

    def body(_, state):
        w, b, zw, zb, t = state
        gw, gb = obj_grad(zw, zb)
        w_new = prox(zw - step * gw)
        b_new = zb - step * gb
        t_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
        beta = (t - 1.0) / t_new
        return (w_new, b_new,
                w_new + beta * (w_new - w), b_new + beta * (b_new - b), t_new)

    z = jnp.zeros((d,), X.dtype)
    w, b, *_ = jax.lax.fori_loop(
        0, n_iter, body, (z, jnp.zeros((), X.dtype), z,
                          jnp.zeros((), X.dtype), jnp.ones((), X.dtype)))
    return w, b


@functools.lru_cache(maxsize=None)
def _grid_fitter(mesh: Mesh, n_iter: int):
    @functools.partial(
        jax.jit,
        in_shardings=(data_sharding(mesh, 2), data_sharding(mesh, 1),
                      candidate_sharding(mesh), candidate_sharding(mesh)),
        out_shardings=(candidate_sharding(mesh, 2), candidate_sharding(mesh, 1),
                       candidate_sharding(mesh, 1)))
    def fit(X, y, l2s, l1s):
        def one(l2, l1):
            w, b = _fista_logreg_fixed(X, y, l2, l1, n_iter)
            # train AuROC-surrogate: accuracy on the fly (cheap candidate score)
            pred = (X @ w + b) > 0
            acc = jnp.mean((pred == (y > 0.5)).astype(jnp.float32))
            return w, b, acc

        return jax.vmap(one)(l2s, l1s)

    return fit


def fit_logreg_grid_sharded(X, y, l2s, l1s, mesh: Mesh, n_iter: int = 50):
    """Train a whole regularisation grid in one sharded XLA program.
    Returns (coefs [G, D], intercepts [G], train accuracy [G])."""
    return _grid_fitter(mesh, n_iter)(
        jnp.asarray(X), jnp.asarray(y), jnp.asarray(l2s), jnp.asarray(l1s))


# --------------------------------------------------------------------------
# sharded tree ensembles (P1 × P3): rows over 'data', trees over 'model'
# --------------------------------------------------------------------------

def _mesh_platform(mesh: Mesh) -> str:
    return mesh.devices.flat[0].platform


def sharded_forest_fit(mesh: Mesh, *, task: str = "classification",
                       max_depth: int = 3, n_bins: int = 8,
                       features_per_node: "Optional[int]" = None):
    """Forest fit as one GSPMD program: the binned matrix + per-row stats are
    row-sharded over 'data' (the histogram one-hot contractions inside
    ``fit_tree`` contract the row axis, so XLA inserts the psum all-reduces —
    ≙ Spark's per-partition histogram merge), and the tree axis is vmapped then
    sharded over 'model'.  Returns the jitted fitter
    ``(B, splits, base_stats, boot [K, N], masks [K, D], keys [K])
    → TreeArrays [K, T]``.  ``features_per_node`` enables per-NODE feature
    subsetting from each tree's key (same semantics as the local fitters —
    per-TREE masks cannot learn cross-subset interactions).
    The class count is implied by the stats layout: ``base_stats`` is
    ``[count, onehot(y)]`` for classification, ``[count, y, y²]`` for
    regression (see ``fit_forest``)."""
    from ..models.trees import fit_tree, mxu_dtype_for

    impurity = "gini" if task == "classification" else "variance"
    hist_dtype = mxu_dtype_for(_mesh_platform(mesh))

    @functools.partial(
        jax.jit,
        in_shardings=(data_sharding(mesh, 2), replicated_sharding(mesh),
                      data_sharding(mesh, 2),
                      NamedSharding(mesh, P(MODEL_AXIS, DATA_AXIS)),
                      NamedSharding(mesh, P(MODEL_AXIS, None)),
                      NamedSharding(mesh, P(MODEL_AXIS))),
        out_shardings=NamedSharding(mesh, P(MODEL_AXIS)))
    def fit(B, splits, base_stats, boot, masks, keys):
        def one(bw, fm, k_):
            return fit_tree(B, splits, base_stats * bw[:, None], fm,
                            impurity=impurity, max_depth=max_depth,
                            n_bins=n_bins, min_instances=jnp.float32(1.0),
                            min_gain=jnp.float32(0.0), lam=jnp.float32(1.0),
                            hist_dtype=hist_dtype, node_feature_key=k_,
                            features_per_node=features_per_node)

        return jax.vmap(one)(boot, masks, keys)

    return fit


def sharded_gbt_round(mesh: Mesh, *, task: str = "classification",
                      max_depth: int = 3, n_bins: int = 8):
    """One boosting round over the mesh: grad/hess on row-sharded data, one
    tree fit (histogram reductions ride ICI psums), margin update in place.
    The round math is ``models.trees.gbt_round_body`` — the same function the
    local fitter jits — so weighting/hessian fixes propagate to both paths.
    Returns the jitted
    ``(B, splits, X, y, w0, margin, min_instances, min_gain, lam, eta)
    → (margin', TreeArrays)``."""
    from ..models.trees import gbt_round_body, mxu_dtype_for

    hist_dtype = mxu_dtype_for(_mesh_platform(mesh))
    repl = replicated_sharding(mesh)

    @functools.partial(
        jax.jit,
        in_shardings=(data_sharding(mesh, 2), repl,
                      data_sharding(mesh, 2), data_sharding(mesh, 1),
                      data_sharding(mesh, 1), data_sharding(mesh, 1),
                      repl, repl, repl, repl),
        out_shardings=(data_sharding(mesh, 1), repl))
    def round_fn(B, splits, X, y, w0, margin, min_instances, min_gain,
                 lam, eta):
        fmask = jnp.ones((B.shape[1],)) > 0
        return gbt_round_body(B, splits, X, y, w0, margin, fmask,
                              min_instances, min_gain, lam, eta, task=task,
                              max_depth=max_depth, n_bins=n_bins,
                              hist_dtype=hist_dtype)

    return round_fn


# --------------------------------------------------------------------------
# full sharded training step
# --------------------------------------------------------------------------

def sharded_train_step(mesh: Mesh, n_iter: int = 8):
    """One compiled end-to-end train step over the mesh:

      raw [N, D] rows (sharded over 'data')
        → standardize (psum moments)
        → sanity mask (variance filter as a static-shape multiply)
        → CV-grid logistic fit (vmapped over 'model'-sharded candidates)
        → per-candidate scores → argmax winner

    Mirrors OpWorkflow.train's layer flow with every Spark job fused into one
    XLA program.  Returns the jitted function.
    """

    @functools.partial(
        jax.jit,
        in_shardings=(data_sharding(mesh, 2), data_sharding(mesh, 1),
                      candidate_sharding(mesh), candidate_sharding(mesh)),
        out_shardings=replicated_sharding(mesh))
    def step(X, y, l2s, l1s):
        # feature engineering: standardize (collective moments over 'data')
        mean = jnp.mean(X, axis=0)
        var = jnp.var(X, axis=0)
        Xs = (X - mean) / jnp.sqrt(jnp.maximum(var, 1e-12))
        # sanity-checker-lite: zero out degenerate columns (static shape)
        keep = (var > 1e-10).astype(X.dtype)
        Xs = Xs * keep
        # grid fit over candidates
        def one(l2, l1):
            w, b = _fista_logreg_fixed(Xs, y, l2, l1, n_iter)
            p = jax.nn.sigmoid(Xs @ w + b)
            ls = -jnp.mean(y * jnp.log(p + 1e-9) + (1 - y) * jnp.log(1 - p + 1e-9))
            return w, b, ls

        ws, bs, losses = jax.vmap(one)(l2s, l1s)
        best = jnp.argmin(losses)
        return ws[best], bs[best], losses

    return step

"""Pure, jittable full-batch solvers for linear-family models.

The reference trains its linear models through Spark MLlib's distributed
L-BFGS/OWLQN (wrapped at core/.../impl/classification/OpLogisticRegression.scala:46
etc.).  On TPU the whole design changes: the data matrix lives in HBM, the
gradient is one [N,D]x[D,C] matmul on the MXU, and we run an accelerated
proximal-gradient (FISTA) loop under ``lax.while_loop`` — fully jittable and
``vmap``-able over hyper-parameter grids and CV folds, which is what makes the
ModelSelector grid data-parallel (SURVEY.md §2.6 P3).

All solvers share the signature convention::

    fit_*(X, y, sample_weight, l2, l1, ...) -> params dict of arrays

with static shapes only, so a grid of (fold, reg, elastic-net) candidates can
be trained as one ``vmap``'d XLA program.
"""

from __future__ import annotations

import contextlib
import functools
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from transmogrifai_tpu.models import onepass
from transmogrifai_tpu.sparse.matrix import (sp_matmat, sp_matvec,
                                             sp_rmatmat, sp_rmatvec)


# --------------------------------------------------------------------------
# losses: value-and-grad of the smooth part, given margins/logits
# --------------------------------------------------------------------------

def _logistic_loss_grad(logits: jnp.ndarray, y01: jnp.ndarray, w: jnp.ndarray):
    """Binary logistic.  logits [N], y01 [N] in {0,1}, w [N] sample weights.
    Returns (mean loss, dloss/dlogits [N])."""
    ls = jax.nn.softplus(jnp.where(y01 > 0.5, -logits, logits))
    wsum = jnp.sum(w)
    return (jnp.sum(w * ls) / wsum,
            w * onepass.POINTWISE["logistic"](logits, y01) / wsum)


def _softmax_loss_grad(logits: jnp.ndarray, yoh: jnp.ndarray, w: jnp.ndarray):
    """Multinomial.  logits [N,C], yoh one-hot [N,C]."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    p = jnp.exp(logp)
    wsum = jnp.sum(w)
    loss = -jnp.sum(w * jnp.sum(yoh * logp, axis=-1)) / wsum
    return loss, (w[:, None] * (p - yoh)) / wsum


def _squared_loss_grad(pred: jnp.ndarray, y: jnp.ndarray, w: jnp.ndarray):
    r = pred - y
    wsum = jnp.sum(w)
    return 0.5 * jnp.sum(w * r * r) / wsum, w * r / wsum


def _squared_hinge_loss_grad(margin: jnp.ndarray, ypm: jnp.ndarray, w: jnp.ndarray):
    """Squared hinge for linear SVC.  ypm [N] in {-1,+1}."""
    viol = jnp.maximum(0.0, 1.0 - ypm * margin)
    wsum = jnp.sum(w)
    return (jnp.sum(w * viol * viol) / wsum,
            w * onepass.POINTWISE["squared_hinge"](margin, ypm) / wsum)


def _poisson_loss_grad(eta: jnp.ndarray, y: jnp.ndarray, w: jnp.ndarray):
    """Poisson deviance with log link: loss = mean(exp(eta) - y*eta)."""
    mu = jnp.exp(jnp.clip(eta, -30.0, 30.0))
    wsum = jnp.sum(w)
    return jnp.sum(w * (mu - y * eta)) / wsum, w * (mu - y) / wsum


def _gamma_loss_grad(eta: jnp.ndarray, y: jnp.ndarray, w: jnp.ndarray):
    """Gamma deviance with log link: loss = mean(y*exp(-eta) + eta)."""
    inv_mu = jnp.exp(jnp.clip(-eta, -30.0, 30.0))
    wsum = jnp.sum(w)
    return jnp.sum(w * (y * inv_mu + eta)) / wsum, w * (1.0 - y * inv_mu) / wsum


LOSSES = {
    "logistic": _logistic_loss_grad,
    "softmax": _softmax_loss_grad,
    "squared": _squared_loss_grad,
    "squared_hinge": _squared_hinge_loss_grad,
    "poisson": _poisson_loss_grad,
    "gamma": _gamma_loss_grad,
}

# Lipschitz constant of d²loss/dlogits² (per-row bound), used for the FISTA
# step size together with the spectral norm of X.  Exp-link losses (poisson,
# gamma) have unbounded curvature, so fista_fit runs a backtracking line
# search for them instead of trusting a constant bound.
_LOSS_CURVATURE = {
    "logistic": 0.25,
    "softmax": 0.5,
    "squared": 1.0,
    "squared_hinge": 2.0,
    "poisson": 1.0,   # initial guess only — backtracking shrinks as needed
    "gamma": 1.0,
}

_BACKTRACK_LOSSES = frozenset({"poisson", "gamma"})


class FitResult(NamedTuple):
    coef: jnp.ndarray       # [D, C]
    intercept: jnp.ndarray  # [C]
    n_iter: jnp.ndarray     # scalar int
    objective: jnp.ndarray  # final objective value


def _exact_dot(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """``a @ b`` of a [D] vector of means with coefficients, in float32 on
    every platform: at the chip's default precision both would be rounded to
    bfloat16 first, and a mean of 40.75 times a coefficient of hundreds
    would lose the intercept's digits.  On the CPU it is ``a @ b``."""
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


class _Folds(NamedTuple):
    """Per-fold operands of the dense products, each [F, ...]: row weights,
    the same over their sum, the standardisation moments and the mean about
    the products' pivot."""
    w: jnp.ndarray
    wn: jnp.ndarray
    mean: jnp.ndarray
    off: jnp.ndarray
    scale: jnp.ndarray


def _folds(fold_weights: jnp.ndarray, mean: jnp.ndarray, scale: jnp.ndarray,
           pivot: Optional[jnp.ndarray]) -> _Folds:
    w = fold_weights.astype(jnp.float32)
    off = mean if pivot is None else mean - pivot
    return _Folds(w, w / jnp.sum(w, axis=1, keepdims=True), mean, off, scale)


def _over_lanes(fn: Callable, folds, grid_points: int) -> Callable:
    """``fn(fold, *lane_args)`` for every lane at once, lane ``l`` being grid
    point ``l % grid_points`` of fold ``l // grid_points``.  Mapped as
    [F] x [G], as the per-lane fits were, so that XLA batches the lanes'
    products into one product per pass as it did."""
    def call(*lane_args):
        n_folds = jax.tree_util.tree_leaves(folds)[0].shape[0]
        split = jax.tree_util.tree_map(
            lambda a: a.reshape((n_folds, grid_points) + a.shape[1:]),
            lane_args)
        out = jax.vmap(lambda fold, args: jax.vmap(
            lambda *a: fn(fold, *a))(*args))(folds, split)
        return jax.tree_util.tree_map(
            lambda o: o.reshape((n_folds * grid_points,) + o.shape[2:]), out)
    return call


def _lane_fns(mv: Callable, tmv: Callable, target: jnp.ndarray,
              loss: str) -> Tuple[Callable, Callable]:
    """(grad, value) of one lane from its products: ``grad(fold, z_c, z_i)``
    is (Xs^T g, the sum of g) for g the loss's derivative at Xs z_c + z_i,
    ``value(fold, c, i)`` the loss at Xs c + i.  ``mv(coef, fold)`` and
    ``tmv(g, fold)`` are Xs coef and Xs^T g."""
    loss_fn = LOSSES[loss]

    def grad(fold, z_c, z_i):
        _, glin = loss_fn(mv(z_c, fold) + z_i, target, fold.w)
        return tmv(glin, fold), jnp.sum(glin, axis=0)

    def value(fold, coef, intercept):
        return loss_fn(mv(coef, fold) + intercept, target, fold.w)[0]

    return grad, value


def _dense_lanes(X: jnp.ndarray, pivot: Optional[jnp.ndarray],
                 target: jnp.ndarray, folds: _Folds, *, loss: str,
                 grid_points: int, one_pass: bool):
    """(grad, value, power) of the implicitly standardized dense matrix
    Xs = (X - mean)/scale for every lane of a stage, each taking and giving
    lane-stacked arrays: ``grad(z_c, z_i)`` -> (Xs^T g, Σ g), ``value(c, i)``
    -> the loss, ``power(v)`` [F, D] -> Xs^T diag(wn) Xs v, a fold a lane.

    ``one_pass`` (``onepass.engages``): ``grad`` and ``power`` make one
    pass over X for every lane (``onepass.grad``); otherwise two XLA
    products, X v and X^T g.  ``value`` is always the XLA product: it runs
    for the final objective, and in the backtracking losses, which the
    kernel does not compute."""
    # the subtraction is written where a product reads it, inside the
    # loops' bodies, so that it fuses into the product's operand as the
    # storage dtype's conversion does; hoisted, it would be a float32 copy
    about = (lambda: X) if pivot is None else (lambda: X - pivot)

    def mv(coef, fold):
        """Xs @ coef without materializing Xs ([N] or [N, C])."""
        v = coef / (fold.scale[:, None] if coef.ndim == 2 else fold.scale)
        return about() @ v - _exact_dot(fold.off, v)

    def tmv(glin, fold):
        """Xs^T @ glin ([D] or [D, C])."""
        if glin.ndim == 2:
            sg = jnp.sum(glin, axis=0)
            num = about().T @ glin - fold.off[:, None] * sg[None, :]
            return num / fold.scale[:, None]
        return (about().T @ glin - fold.off * jnp.sum(glin)) / fold.scale

    def power_lane(fold, v):
        vs = v / fold.scale
        u = fold.wn * (about() @ vs - _exact_dot(fold.off, vs))   # Xs @ v [N]
        return (about().T @ u - fold.off * jnp.sum(u)) / fold.scale

    lane_grad, lane_value = _lane_fns(mv, tmv, target, loss)
    value = _over_lanes(lane_value, folds, grid_points)
    if not one_pass:
        return (_over_lanes(lane_grad, folds, grid_points), value,
                _over_lanes(power_lane, folds, 1))
    wsum = jnp.sum(folds.w, axis=1)

    def kernel(v, b, form, g_pts):
        off = jnp.repeat(folds.off, g_pts, axis=0)
        scale = jnp.repeat(folds.scale, g_pts, axis=0)
        vs = v / scale
        c = jax.vmap(_exact_dot)(off, vs)
        acc, gsum = onepass.grad(X, vs, c, b, pivot, target, folds.w, wsum,
                                 form=form, grid_points=g_pts)
        return (acc - off * gsum[:, None]) / scale, gsum

    def grad(z_c, z_i):
        return kernel(z_c, z_i, loss, grid_points)

    def power(v):
        return kernel(v, jnp.zeros(v.shape[:1], jnp.float32), onepass.POWER,
                      1)[0]

    return grad, value, power


@jax.named_scope("linear.lipschitz")
def _spectral_norm_sq(power: Callable, folds: int, d: int,
                      iters: int = 16) -> jnp.ndarray:
    """[F] λ_max of each fold's Xs^T diag(wn) Xs by power iteration, every
    fold a lane of one ``power`` ([F, D] -> [F, D]): one shared
    HBM-resident X serves every lane, and Xs is never materialized."""
    v = jnp.full((folds, d), 1.0 / jnp.sqrt(d), jnp.float32)
    unit = jax.vmap(lambda u: u / (jnp.linalg.norm(u) + 1e-12))
    v = jax.lax.fori_loop(0, iters, lambda _, v: unit(power(v)), v)
    return jax.vmap(jnp.vdot)(v, power(v))


def _loss_target(loss: str, y: jnp.ndarray, n_classes: int) -> jnp.ndarray:
    if loss == "softmax":
        return jax.nn.one_hot(y.astype(jnp.int32), n_classes,
                              dtype=jnp.float32)
    if loss == "squared_hinge":
        return jnp.where(y > 0.5, 1.0, -1.0).astype(jnp.float32)
    return y.astype(jnp.float32)


@jax.named_scope("linear.fista")
def _fista_loop(grad: Callable, value: Callable, l2: jnp.ndarray,
                l1: jnp.ndarray, *, loss: str, d: int, n_classes: int,
                fit_intercept: bool, max_iter: int, tol: float,
                sigma_sq: jnp.ndarray) -> FitResult:
    """The FISTA iteration of every lane of a stage at once, shared by the
    dense and sparse fitters.  ``l2``, ``l1`` and ``sigma_sq`` are [L]; the
    data matrix enters ONLY through ``grad(z_c, z_i)`` -> (Xs^T g, Σ g) and
    ``value(c, i)`` -> the mean loss, each over lane-stacked arrays, so
    that one call can serve every lane in one pass over X.

    The loop runs while any lane is active; an inactive lane's state is
    held, as ``vmap`` of a ``while_loop`` holds it, so every lane makes the
    iterations it made fitted alone.  Returns [L, ...]-stacked leaves."""
    C = n_classes
    lanes = l2.shape[0]
    L = _LOSS_CURVATURE[loss] * sigma_sq + l2
    step0 = 1.0 / jnp.maximum(L, 1e-12)
    backtrack = loss in _BACKTRACK_LOSSES

    shape = (lanes, d, C) if C > 1 else (lanes, d)
    b_shape = (lanes, C) if C > 1 else (lanes,)

    def per(x, like):
        """A lane's scalar against an array of that lane."""
        return x.reshape(x.shape + (1,) * (like.ndim - 1))

    def total(x):
        return jnp.sum(x, axis=tuple(range(1, x.ndim)))

    def peak(x):
        return jnp.max(jnp.abs(x), axis=tuple(range(1, x.ndim)))

    def smooth_val(coef, intercept):
        """Value of the smooth part (loss + l2 ridge)."""
        return value(coef, intercept) + 0.5 * l2 * total(coef * coef)

    def prox(u, s):
        return jnp.sign(u) * jnp.maximum(jnp.abs(u) - per(s * l1, u), 0.0)

    def active(k, delta):
        return jnp.logical_and(k < max_iter, delta > tol)

    def cond(state):
        k, _, _, _, _, _, _, delta = state
        return jnp.any(active(k, delta))

    def body(state):
        k, coef, intercept, z_c, z_i, t, step, delta = state
        g_c, g_i = grad(z_c, z_i)
        g_c = g_c + per(l2, z_c) * z_c

        def attempt(s):
            nc = prox(z_c - per(s, g_c) * g_c, s)
            ni = z_i - per(s, g_i) * g_i if fit_intercept else z_i
            return nc, ni

        if backtrack:
            # Beck–Teboulle backtracking: shrink the step until the smooth
            # part is majorized by its quadratic model at z (exp-link losses
            # have unbounded curvature, so the fixed bound is unreliable)
            f_z = smooth_val(z_c, z_i)

            def sufficient(s):
                nc, ni = attempt(s)
                dc = nc - z_c
                di = ni - z_i
                quad = (f_z + total(dc * g_c) + total(di * g_i)
                        + (total(dc * dc) + total(di * di)) / (2.0 * s))
                return smooth_val(nc, ni) <= quad + 1e-12

            def shrinking(bs):
                _, ok, it = bs
                return jnp.logical_and(~ok, it < 30)

            def bt_body(bs):
                s, ok, it = bs
                go = shrinking(bs)
                s = jnp.where(go, s * 0.5, s)
                return (s, jnp.where(go, sufficient(s), ok),
                        jnp.where(go, it + 1, it))

            step, _, _ = jax.lax.while_loop(
                lambda bs: jnp.any(shrinking(bs)), bt_body,
                (step, sufficient(step), jnp.zeros((lanes,), jnp.int32)))

        new_c, new_i = attempt(step)
        t_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
        beta = (t - 1.0) / t_new
        # adaptive restart on non-descent direction
        restart = total((z_c - new_c) * (new_c - coef)) > 0.0
        beta = jnp.where(restart, 0.0, beta)
        t_new = jnp.where(restart, 1.0, t_new)
        zc_next = new_c + per(beta, new_c) * (new_c - coef)
        zi_next = new_i + per(beta, new_i) * (new_i - intercept)
        delta_new = peak(new_c - coef) + peak(new_i - intercept)
        new = (k + 1, new_c, new_i, zc_next, zi_next, t_new, step, delta_new)
        held = active(k, delta)
        return tuple(jnp.where(per(held, n), n, o) for n, o in zip(new, state))

    init = (jnp.zeros((lanes,), jnp.int32), jnp.zeros(shape, jnp.float32),
            jnp.zeros(b_shape, jnp.float32), jnp.zeros(shape, jnp.float32),
            jnp.zeros(b_shape, jnp.float32), jnp.ones((lanes,), jnp.float32),
            step0.astype(jnp.float32),
            jnp.full((lanes,), jnp.inf, jnp.float32))
    k, coef, intercept, *_ = jax.lax.while_loop(cond, body, init)
    obj = smooth_val(coef, intercept) + l1 * total(jnp.abs(coef))
    return FitResult(coef, intercept.reshape(lanes, -1), k, obj)


@functools.partial(
    jax.jit,
    static_argnames=("loss", "fit_intercept", "max_iter", "n_classes",
                     "one_pass"))
def fista_fit(X: jnp.ndarray, y: jnp.ndarray, sample_weight: jnp.ndarray,
              l2: jnp.ndarray, l1: jnp.ndarray, *, loss: str = "logistic",
              fit_intercept: bool = True, max_iter: int = 100,
              tol: float = 1e-6, n_classes: int = 1,
              mean: Optional[jnp.ndarray] = None,
              scale: Optional[jnp.ndarray] = None,
              sigma_sq: Optional[jnp.ndarray] = None,
              pivot: Optional[jnp.ndarray] = None,
              one_pass: bool = False) -> FitResult:
    """Accelerated proximal gradient with adaptive restart.

    minimises  mean_loss(Xs w + b) + l2/2 ||w||² + l1 ||w||₁  (no penalty on b)
    where Xs = (X - mean)/scale is the IMPLICITLY standardized matrix when
    ``mean``/``scale`` are given — the standardized copy is never
    materialized.  The returned coefficients live in the standardized basis
    (caller un-scales, matching Spark ML's internal-standardization
    contract).  One fit: the one-lane case of ``linear_grid_fit``'s loop.

    ``sigma_sq`` (λ_max of the weighted Gram) is computed here when absent.
    A fit that computes its own is a single fit, not a grid lane — a
    candidate fitted alone — and its operations carry the scope
    ``linear.refit`` in the device trace.

    ``pivot`` [D] (``column_pivot``; with ``mean``) has every product read X
    about it: Xs = ((X - pivot) - (mean - pivot))/scale, the same matrix.  A
    column whose mean is hundreds of its deviations (a latitude: 40.75 +-
    0.03) otherwise loses its standardized values in the rounding of
    40.75 * v - mean * v, in float32 and, the coefficient rounded to
    bfloat16, far sooner on the chip.

    ``one_pass`` is what ``onepass.engages`` says of ``X`` (the caller asks
    it where X is placed): one pass of X an iteration instead of two.
    """
    n, d = X.shape
    C = n_classes
    target = _loss_target(loss, y, C)

    std = scale is not None
    mu = mean if std else jnp.zeros((d,), jnp.float32)
    sc = scale if std else jnp.ones((d,), jnp.float32)
    pivot = pivot if std else None
    folds = _folds(sample_weight[None], mu[None], sc[None], pivot)
    grad, value, power = _dense_lanes(X, pivot, target, folds, loss=loss,
                                      grid_points=1, one_pass=one_pass)
    lane = lambda a: jnp.asarray(a, jnp.float32).reshape(1)   # noqa: E731
    with (jax.named_scope("linear.refit") if sigma_sq is None
          else contextlib.nullcontext()):
        if sigma_sq is None:
            sigma_sq = _spectral_norm_sq(power, 1, d)
        res = _fista_loop(grad, value, lane(l2), lane(l1), loss=loss, d=d,
                          n_classes=C, fit_intercept=fit_intercept,
                          max_iter=max_iter, tol=tol,
                          sigma_sq=lane(sigma_sq))
    return FitResult(*(leaf[0] for leaf in res))


@functools.partial(jax.jit, static_argnames=("fit_intercept",))
def ridge_fit(X: jnp.ndarray, y: jnp.ndarray, sample_weight: jnp.ndarray,
              l2: jnp.ndarray, *, fit_intercept: bool = True) -> FitResult:
    """Closed-form weighted ridge regression via normal equations (the l1=0
    fast path for OpLinearRegression): one X^T X matmul on the MXU + a [D,D]
    Cholesky solve."""
    n, d = X.shape
    w = sample_weight.astype(jnp.float32)
    wsum = jnp.sum(w)
    if fit_intercept:
        xm = (w @ X) / wsum
        ym = jnp.sum(w * y) / wsum
        Xc = X - xm
        yc = y - ym
    else:
        Xc, yc = X, y
    Xw = Xc * w[:, None]
    A = (Xc.T @ Xw) / wsum + l2 * jnp.eye(d, dtype=jnp.float32)
    b = (Xw.T @ yc) / wsum
    coef = jax.scipy.linalg.solve(A, b, assume_a="pos")
    intercept = (ym - xm @ coef) if fit_intercept else jnp.zeros((), jnp.float32)
    resid = yc - Xc @ coef
    obj = 0.5 * jnp.sum(w * resid * resid) / wsum + 0.5 * l2 * jnp.sum(coef * coef)
    return FitResult(coef, jnp.atleast_1d(intercept), jnp.zeros((), jnp.int32), obj)


@functools.partial(jax.jit, static_argnames=("n_classes",))
def naive_bayes_fit(X: jnp.ndarray, y: jnp.ndarray, sample_weight: jnp.ndarray,
                    smoothing: jnp.ndarray, *, n_classes: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Multinomial naive Bayes (≙ OpNaiveBayes): class-conditional log
    likelihoods from per-class feature sums.  Expects non-negative features.
    Returns (log_prior [C], log_prob [C, D])."""
    yoh = jax.nn.one_hot(y.astype(jnp.int32), n_classes, dtype=jnp.float32)  # [N,C]
    w = sample_weight.astype(jnp.float32)
    cls_count = (w @ yoh)                                 # [C]
    feat_count = (yoh * w[:, None]).T @ jnp.maximum(X, 0.0)  # [C,D]
    log_prior = jnp.log(cls_count + 1e-12) - jnp.log(jnp.sum(cls_count) + 1e-12)
    sm = feat_count + smoothing
    log_prob = jnp.log(sm) - jnp.log(jnp.sum(sm, axis=1, keepdims=True))
    return log_prior, log_prob


@functools.partial(
    jax.jit,
    static_argnames=("loss", "fit_intercept", "standardization", "max_iter",
                     "n_classes", "one_pass"))
def linear_grid_fit(X: jnp.ndarray, y: jnp.ndarray, fold_weights: jnp.ndarray,
                    l2s: jnp.ndarray, l1s: jnp.ndarray, *,
                    loss: str = "logistic", fit_intercept: bool = True,
                    standardization: bool = True, max_iter: int = 100,
                    tol: float = 1e-6, n_classes: int = 1,
                    one_pass: bool = False) -> FitResult:
    """The whole (fold × grid-point) CV matrix as ONE XLA program.

    ``fold_weights`` [F, N] are per-fold row weights (weight 0 == row held
    out), so every candidate shares the single HBM-resident ``X`` — CV folds
    are weight masks, not slices, which kills both the host↔device ping-pong
    and the per-fold-shape recompiles.  ``l2s``/``l1s`` [G] give the penalty
    grid.  Standardisation moments are computed once per fold and shared by
    the grid points.  Returns a FitResult with [F, G, ...]-stacked leaves.

    The F·G fits are the lanes of one loop (``_fista_loop``), lane ``l``
    grid point ``l % G`` of fold ``l // G``, so that one pass over X can
    serve them all: with ``one_pass`` (what ``onepass.engages`` says of X)
    an iteration reads X once for every lane, else XLA batches the lanes'
    products into two passes.

    ≙ the reference's thread-pool fan-out of k×Σ|grid| Spark jobs
    (OpValidator.scala:320-349), re-expressed as lanes (SURVEY §2.6 P3).
    """
    d = X.shape[1]
    n_folds, grid_points = fold_weights.shape[0], l2s.shape[0]
    pivot = column_pivot(X) if standardization else None
    # the products read X about it only where the columns are centred: a
    # shift is neutral there and nowhere else
    product_pivot = pivot if fit_intercept else None
    if standardization:
        mean, scale = jax.vmap(lambda w: standardize_moments(
            X, w, center=fit_intercept, pivot=pivot))(fold_weights)
    else:
        mean = jnp.zeros((n_folds, d), jnp.float32)
        scale = jnp.ones((n_folds, d), jnp.float32)
    folds = _folds(fold_weights, mean, scale, product_pivot)
    grad, value, power = _dense_lanes(
        X, product_pivot, _loss_target(loss, y, n_classes), folds, loss=loss,
        grid_points=grid_points, one_pass=one_pass)
    # λ_max of a fold's weighted Gram is grid-independent: computed once a
    # fold and shared by its grid points
    sigma_sq = _spectral_norm_sq(power, n_folds, d)
    lanes = lambda a: jnp.tile(a.astype(jnp.float32), n_folds)  # noqa: E731
    res = _fista_loop(grad, value, lanes(l2s), lanes(l1s), loss=loss, d=d,
                      n_classes=n_classes, fit_intercept=fit_intercept,
                      max_iter=max_iter, tol=tol,
                      sigma_sq=jnp.repeat(sigma_sq, grid_points))
    res = _over_lanes(lambda fold, r: unscale_params(
        r, fold.mean, fold.scale, n_classes), folds, grid_points)(res)
    return jax.tree_util.tree_map(
        lambda a: a.reshape((n_folds, grid_points) + a.shape[1:]), res)


@functools.partial(
    jax.jit, static_argnames=("fit_intercept", "standardization"))
def ridge_grid_fit(X: jnp.ndarray, y: jnp.ndarray, fold_weights: jnp.ndarray,
                   l2s: jnp.ndarray, *, fit_intercept: bool = True,
                   standardization: bool = True) -> FitResult:
    """Closed-form ridge over the (fold × l2-grid) matrix in one program
    (the l1=0 fast path of the OpLinearRegression grid).

    Works on per-fold Gram statistics of ONE shared matrix: when an
    intercept is fit, X is first shifted by its global column means (a single
    [N, D] copy total — algebraic Gram centering of raw data would
    catastrophically cancel in f32 for large-mean features), then each fold's
    (X^T W X)/s is one matmul; the residual per-fold centering and the
    standardization now act on O(variance)-magnitude Gram entries, which is
    numerically safe."""
    d = X.shape[1]
    if fit_intercept:
        g = jnp.mean(X, axis=0)
        X = X - g
    else:
        g = jnp.zeros((d,), jnp.float32)

    def one_fold(w):
        s = jnp.sum(w)
        Xw = X * w[:, None]                      # fold-local scratch [N, D]
        G = (X.T @ Xw) / s                       # (X^T W X)/s  [D, D]
        p = (X.T @ (w * y)) / s                  # (X^T W y)/s  [D]
        m = (w @ X) / s                          # weighted mean [D]
        ym = jnp.sum(w * y) / s
        yy = jnp.sum(w * y * y) / s
        if standardization:
            var = jnp.diagonal(G) - m * m
            scale = jnp.sqrt(jnp.maximum(var, 1e-12))
        else:
            scale = jnp.ones((d,), jnp.float32)
        if fit_intercept:
            # center by the weighted mean: Gc = G - m m^T, bc = p - m*ym
            Gc = G - jnp.outer(m, m)
            bc = p - m * ym
            y0 = ym
            mean_u = m
        else:
            Gc, bc, y0 = G, p, jnp.zeros((), jnp.float32)
            mean_u = jnp.zeros((d,), jnp.float32)
        # standardized basis: A = D^-1 Gc D^-1, b = D^-1 bc
        A0 = Gc / (scale[:, None] * scale[None, :])
        b = bc / scale

        def one_pt(l2):
            A = A0 + l2 * jnp.eye(d, dtype=jnp.float32)
            coef = jax.scipy.linalg.solve(A, b, assume_a="pos")
            obj = 0.5 * (yy - y0 * y0 - 2.0 * b @ coef + coef @ (A0 @ coef)
                         ) + 0.5 * l2 * jnp.sum(coef * coef)
            res = FitResult(coef, jnp.atleast_1d(y0),
                            jnp.zeros((), jnp.int32), obj)
            res = unscale_params(res, mean_u, scale, 1)
            # undo the global shift: predictions are X@coef + (b - g@coef)
            return FitResult(res.coef, res.intercept - g @ res.coef,
                             res.n_iter, res.objective)

        return jax.vmap(one_pt)(l2s)

    # lax.map (not vmap) over folds: the weighted Gram scratch Xw is [N, D]
    # per fold — batching folds would materialize an [F, N, D] operand
    # (~3.7 GiB at the 11M-row scale this path exists for)
    return jax.lax.map(one_fold, fold_weights)


_PIVOT_ROWS = 4096
_PIVOT_FAR = 16.0


def column_pivot(X: jnp.ndarray) -> jnp.ndarray:
    """[D] float32: for a column far from 0 for its spread (its first rows'
    mean over ``_PIVOT_FAR`` of their deviations) that mean, as the matrix's
    dtype holds it; 0 for every other column.  What is computed about it
    keeps the digits such a column would lose about 0.  A stored value
    within a factor of two of a pivot of its own dtype leaves an exact
    difference, so the chip's bfloat16 products read it as they read the
    column itself; and a pivot of 0 leaves a column's arithmetic as it
    was."""
    head = X[:_PIVOT_ROWS].astype(jnp.float32)
    mean = jnp.mean(head, axis=0)
    far = jnp.abs(mean) > _PIVOT_FAR * jnp.std(head, axis=0)
    return jnp.where(far, mean.astype(X.dtype).astype(jnp.float32), 0.0)


def standardize_moments(X: jnp.ndarray, sample_weight: jnp.ndarray,
                        center: bool, pivot: Optional[jnp.ndarray] = None
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Weighted standardisation moments (mean, scale) — consumers apply them
    IMPLICITLY inside their matvecs; the standardized matrix itself is never
    materialized (a per-(fold × grid) copy of X would dominate HBM)."""
    w = sample_weight / jnp.sum(sample_weight)
    # moments about the pivot: E[x^2] - mean^2 itself cancels to nothing in
    # float32, and sooner under the chip's bfloat16 products, for a column
    # whose mean is hundreds of its deviations (a latitude: 40.75 +- 0.03)
    if pivot is None:
        pivot = column_pivot(X)
    Xc = X - pivot
    shift = w @ Xc
    mean = pivot + shift
    var = w @ (Xc * Xc) - shift * shift
    scale = jnp.sqrt(jnp.maximum(var, 1e-12))
    mu = mean if center else jnp.zeros_like(mean)
    return mu, scale


def standardize(X: jnp.ndarray, sample_weight: jnp.ndarray,
                center: bool) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Weighted feature standardisation (Spark ML standardizes internally and
    un-scales the coefficients; we do the same).  Returns (Xs, mean, scale)."""
    mu, scale = standardize_moments(X, sample_weight, center)
    return (X - mu) / scale, mu, scale


# --------------------------------------------------------------------------
# sparse (flat-COO) fitters: same FISTA loop, matvecs via take + segment_sum
# --------------------------------------------------------------------------

def _sp_col_scale(values, indices, row_ids, wn, n_cols):
    """Weighted per-column scale sqrt(E[x²] - E[x]²) from COO entries only.

    Sparse standardization is SCALE-ONLY (Spark's ``withMean=False``
    convention for sparse vectors): subtracting the mean would densify
    every row, defeating the representation.  Absent columns have
    variance 0 and clamp to scale 1e-6-ish — their coefficients stay 0.
    """
    mean = sp_rmatvec(values, indices, row_ids, wn, n_cols=n_cols)
    ex2 = sp_rmatvec(values * values, indices, row_ids, wn, n_cols=n_cols)
    var = jnp.maximum(ex2 - mean * mean, 0.0)
    return jnp.sqrt(jnp.maximum(var, 1e-12))


@jax.named_scope("linear.lipschitz")
def _sp_spectral_norm_sq(values, indices, row_ids, wn, scale,
                         n_rows: int, n_cols: int,
                         iters: int = 16) -> jnp.ndarray:
    """λ_max of Xs^T diag(wn) Xs for the implicitly scaled sparse matrix."""
    v = jnp.full((n_cols,), 1.0 / jnp.sqrt(n_cols), jnp.float32)

    def mv(v):
        u = wn * sp_matvec(values, indices, row_ids, v / scale, n_rows=n_rows)
        return sp_rmatvec(values, indices, row_ids, u, n_cols=n_cols) / scale

    def body(_, v):
        u = mv(v)
        return u / (jnp.linalg.norm(u) + 1e-12)

    v = jax.lax.fori_loop(0, iters, body, v)
    return jnp.vdot(v, mv(v))


@functools.partial(
    jax.jit,
    static_argnames=("loss", "fit_intercept", "standardization", "max_iter",
                     "n_classes", "n_rows", "n_cols"))
def sparse_linear_grid_fit(values, indices, row_ids, y, fold_weights,
                           l2s, l1s, *, n_rows: int, n_cols: int,
                           loss: str = "logistic", fit_intercept: bool = True,
                           standardization: bool = True, max_iter: int = 100,
                           tol: float = 1e-6, n_classes: int = 1) -> FitResult:
    """``linear_grid_fit`` for a flat-COO matrix: the whole (fold × grid) CV
    block as one XLA program, with every lane sharing the single device-
    resident entry stream — nothing in the program is ever [N, n_cols].

    Pad entries (value 0.0) and zero-weight pad rows both contribute
    nothing to any segment sum, so the ladder padding is exact here just
    like in the dense weighted path.  Standardization is scale-only (see
    ``_sp_col_scale``); coefficients are returned un-scaled.
    """
    C = n_classes
    target = _loss_target(loss, y, C)
    zeros_d = jnp.zeros((n_cols,), jnp.float32)
    n_folds, grid_points = fold_weights.shape[0], l2s.shape[0]
    w = fold_weights.astype(jnp.float32)
    wn = w / jnp.sum(w, axis=1, keepdims=True)
    if standardization:
        scale = jax.vmap(lambda wn: _sp_col_scale(values, indices, row_ids,
                                                  wn, n_cols))(wn)
    else:
        scale = jnp.ones((n_folds, n_cols), jnp.float32)
    sigma_sq = jax.vmap(lambda wn, scale: _sp_spectral_norm_sq(
        values, indices, row_ids, wn, scale, n_rows, n_cols))(wn, scale)
    zeros = jnp.zeros((n_folds, n_cols), jnp.float32)
    folds = _Folds(w, wn, zeros, zeros, scale)

    def xs_mv(coef, fold):
        if coef.ndim == 2:
            return sp_matmat(values, indices, row_ids,
                             coef / fold.scale[:, None], n_rows=n_rows)
        return sp_matvec(values, indices, row_ids, coef / fold.scale,
                         n_rows=n_rows)

    def xs_tmv(glin, fold):
        if glin.ndim == 2:
            return sp_rmatmat(values, indices, row_ids, glin,
                              n_cols=n_cols) / fold.scale[:, None]
        return sp_rmatvec(values, indices, row_ids, glin,
                          n_cols=n_cols) / fold.scale

    grad, value = (_over_lanes(fn, folds, grid_points)
                   for fn in _lane_fns(xs_mv, xs_tmv, target, loss))
    lanes = lambda a: jnp.tile(a.astype(jnp.float32), n_folds)  # noqa: E731
    res = _fista_loop(grad, value, lanes(l2s), lanes(l1s), loss=loss,
                      d=n_cols, n_classes=C, fit_intercept=fit_intercept,
                      max_iter=max_iter, tol=tol,
                      sigma_sq=jnp.repeat(sigma_sq, grid_points))
    res = _over_lanes(lambda fold, r: unscale_params(
        r, zeros_d, fold.scale, C), folds, grid_points)(res)
    return jax.tree_util.tree_map(
        lambda a: a.reshape((n_folds, grid_points) + a.shape[1:]), res)


def sparse_fista_fit(sm, y, sample_weight, l2: float, l1: float, *,
                     loss: str = "logistic", fit_intercept: bool = True,
                     standardization: bool = True, max_iter: int = 100,
                     tol: float = 1e-6, n_classes: int = 1) -> FitResult:
    """Single-point sparse fit: the G=1, F=1 slice of the grid program (one
    code path to test, and the single-fit case replays the grid executable
    when shapes match).  ``sm`` is a ``sparse.matrix.SparseMatrix``."""
    w = jnp.asarray(sample_weight, jnp.float32)
    res = sparse_linear_grid_fit(
        sm.values, sm.indices, sm.row_ids, jnp.asarray(y), w[None, :],
        jnp.asarray([l2], jnp.float32), jnp.asarray([l1], jnp.float32),
        n_rows=sm.n_rows, n_cols=sm.n_cols, loss=loss,
        fit_intercept=fit_intercept, standardization=standardization,
        max_iter=max_iter, tol=tol, n_classes=n_classes)
    return FitResult(res.coef[0, 0], res.intercept[0, 0],
                     res.n_iter[0, 0], res.objective[0, 0])


def unscale_params(res: FitResult, mean: jnp.ndarray, scale: jnp.ndarray,
                   n_classes: int) -> FitResult:
    if n_classes > 1:
        coef = res.coef / scale[:, None]
        intercept = res.intercept - _exact_dot(mean, coef)
    else:
        coef = res.coef / scale
        intercept = res.intercept - jnp.atleast_1d(_exact_dot(mean, coef))
    return FitResult(coef, intercept, res.n_iter, res.objective)

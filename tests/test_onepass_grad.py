"""The one-pass gradient kernel (``models/onepass.py``) and the lane-explicit
FISTA loop it runs in.

(a) The kernel, run by the Pallas TPU interpreter, against the same two
    products written in XLA with the operands rounded as the kernel rounds
    them: every loss it computes, several widths, a tail block with NaN in
    the rows past the target, one fold or two, one to eight grid points a
    fold, with and without a pivot.
(b) ``linear_grid_fit`` with its fold × grid lanes explicit against the
    nested-``vmap`` form it replaced (kept here as the oracle): the same
    iterations on every lane, the same coefficients to float32 rounding.
(c) The rule that picks the kernel, without a chip, and the counters that
    record which path each program took.
"""

import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from transmogrifai_tpu.models import onepass, solvers
from transmogrifai_tpu.telemetry import REGISTRY

COUNTERS = ("linear.onepass_programs", "linear.twopass_programs",
            "linear.loop_iterations")


# --------------------------------------------------------------------------
# (a) the kernel against the two products
# --------------------------------------------------------------------------

def rounded_products(X, vs, c, b, pivot, target, weights, wsum, form,
                     grid_points):
    """The kernel's arithmetic as two XLA products: X about the pivot and
    the lanes' coefficients rounded to bfloat16, float32 sums."""
    n = target.shape[0]
    xp = X[:n].astype(jnp.float32)
    if pivot is not None:
        xp = xp - pivot
    xp = xp.astype(jnp.bfloat16)
    z = jnp.dot(vs.astype(jnp.bfloat16), xp.T,
                preferred_element_type=jnp.float32)
    z = (z - c[:, None]) + b[:, None]
    w = jnp.repeat(weights, grid_points, axis=0)
    ws = jnp.repeat(wsum, grid_points)[:, None]
    if form == onepass.POWER:
        g = (w / ws) * z
    else:
        g = w * onepass.POINTWISE[form](z, target[None, :]) / ws
    acc = jnp.dot(g.astype(jnp.bfloat16), xp,
                  preferred_element_type=jnp.float32)
    return acc, jnp.sum(g, axis=1)


def kernel_case(rng, n, d, folds, grid_points, form, pivot, tail):
    """Operands of one call; ``tail`` rows of NaN past the target's rows."""
    lanes = folds * grid_points
    X = rng.standard_normal((n + tail, d)).astype(np.float32)
    X[n:] = np.nan
    target = (rng.random(n) < 0.4).astype(np.float32)
    if form == "squared_hinge":
        target = 2.0 * target - 1.0
    weights = (rng.random((folds, n)) < 0.7).astype(np.float32)
    return dict(
        X=jnp.asarray(X).astype(jnp.bfloat16),
        vs=jnp.asarray(rng.standard_normal((lanes, d)) * 0.1, jnp.float32),
        c=jnp.asarray(rng.standard_normal(lanes), jnp.float32),
        b=jnp.asarray(rng.standard_normal(lanes), jnp.float32),
        pivot=(jnp.asarray(rng.standard_normal(d), jnp.float32) if pivot
               else None),
        target=jnp.asarray(target), weights=jnp.asarray(weights),
        wsum=jnp.asarray(weights.sum(axis=1)))


def run_kernel(args, form, grid_points):
    with pltpu.force_tpu_interpret_mode():
        return jax.jit(functools.partial(
            onepass.grad, form=form, grid_points=grid_points))(**args)


FORMS = ("logistic", "squared_hinge", onepass.POWER)
LANES = ((1, 1), (1, 3), (1, 8), (2, 1), (2, 3), (2, 8))


@pytest.mark.parametrize("pivot", [True, False], ids=["pivot", "no_pivot"])
@pytest.mark.parametrize("d", [81, 300, 1025])
@pytest.mark.parametrize("form", FORMS)
def test_kernel_equals_the_two_products(form, d, pivot, monkeypatch):
    """Blocks of 128 rows (the smallest) over 421 rows: three whole blocks
    and a tail whose rows past the target are NaN in the source, so a mask
    that let one through would leave NaN in both results."""
    monkeypatch.setattr(onepass, "_BLOCK_BYTES", 1)
    assert onepass.block_rows(d, 2) == 128
    folds, grid_points = LANES[(FORMS.index(form) * 2 + d) % len(LANES)]
    rng = np.random.default_rng(d + 7 * FORMS.index(form) + pivot)
    args = kernel_case(rng, 421, d, folds, grid_points, form, pivot, 91)
    acc, gsum = run_kernel(args, form, grid_points)
    want_acc, want_gsum = rounded_products(form=form,
                                           grid_points=grid_points, **args)
    assert acc.shape == (folds * grid_points, d)
    assert np.all(np.isfinite(np.asarray(acc)))
    np.testing.assert_allclose(np.asarray(acc), np.asarray(want_acc),
                               rtol=2e-5, atol=2e-5 * float(
                                   jnp.max(jnp.abs(want_acc))))
    np.testing.assert_allclose(np.asarray(gsum), np.asarray(want_gsum),
                               rtol=2e-5, atol=1e-6)


@pytest.mark.parametrize("folds,grid_points", LANES)
def test_every_lane_reads_its_folds_weights(folds, grid_points):
    """Each lane's rows weighted by its own fold, at the block size the
    width gives (one block, shorter than the rows it holds)."""
    rng = np.random.default_rng(100 * folds + grid_points)
    args = kernel_case(rng, 1000, 300, folds, grid_points, "logistic",
                       True, 24)
    acc, gsum = run_kernel(args, "logistic", grid_points)
    want_acc, want_gsum = rounded_products(form="logistic",
                                           grid_points=grid_points, **args)
    np.testing.assert_allclose(np.asarray(acc), np.asarray(want_acc),
                               rtol=2e-5, atol=2e-5 * float(
                                   jnp.max(jnp.abs(want_acc))))
    np.testing.assert_allclose(np.asarray(gsum), np.asarray(want_gsum),
                               rtol=2e-5, atol=1e-6)


# --------------------------------------------------------------------------
# (b) the lanes explicit against the nested vmap they replaced
# --------------------------------------------------------------------------

def nested_vmap_grid_fit(X, y, fold_weights, l2s, l1s, *, loss,
                         fit_intercept, standardization, max_iter, tol,
                         n_classes):
    """``linear_grid_fit`` as it was: one FISTA loop a lane, the lanes a
    ``vmap`` over grid points inside a ``vmap`` over folds."""
    C, d = n_classes, X.shape[1]
    loss_fn = solvers.LOSSES[loss]
    target = solvers._loss_target(loss, y, C)
    pivot = solvers.column_pivot(X) if standardization else None
    pivot = pivot if fit_intercept else None
    about = (lambda: X) if pivot is None else (lambda: X - pivot)

    def one_fold(w):
        if standardization:
            mean, scale = solvers.standardize_moments(
                X, w, center=fit_intercept, pivot=solvers.column_pivot(X))
        else:
            mean, scale = jnp.zeros((d,)), jnp.ones((d,))
        off = mean if pivot is None else mean - pivot
        wn = w / jnp.sum(w)

        def mv(v):
            vs = v / scale
            u = wn * (about() @ vs - solvers._exact_dot(off, vs))
            return (about().T @ u - off * jnp.sum(u)) / scale

        def unit(_, v):
            u = mv(v)
            return u / (jnp.linalg.norm(u) + 1e-12)

        v = jnp.full((d,), 1.0 / jnp.sqrt(d), jnp.float32)
        v = jax.lax.fori_loop(0, 16, unit, v)
        sigma_sq = jnp.vdot(v, mv(v))

        def xs_mv(coef):
            v = coef / (scale[:, None] if coef.ndim == 2 else scale)
            return about() @ v - solvers._exact_dot(off, v)

        def xs_tmv(glin):
            if glin.ndim == 2:
                return (about().T @ glin - off[:, None] * jnp.sum(
                    glin, axis=0)[None, :]) / scale[:, None]
            return (about().T @ glin - off * jnp.sum(glin)) / scale

        def one_pt(l2, l1):
            step0 = 1.0 / jnp.maximum(
                solvers._LOSS_CURVATURE[loss] * sigma_sq + l2, 1e-12)
            shape = (d, C) if C > 1 else (d,)
            b_shape = (C,) if C > 1 else ()

            def body(state):
                k, coef, b, z_c, z_i, t, step, _ = state
                _, glin = loss_fn(xs_mv(z_c) + z_i, target, w)
                g_c = xs_tmv(glin) + l2 * z_c
                g_i = jnp.sum(glin, axis=0)
                u = z_c - step * g_c
                nc = jnp.sign(u) * jnp.maximum(jnp.abs(u) - step * l1, 0.0)
                ni = z_i - step * g_i if fit_intercept else z_i
                t_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
                beta = (t - 1.0) / t_new
                restart = jnp.sum((z_c - nc) * (nc - coef)) > 0.0
                beta = jnp.where(restart, 0.0, beta)
                t_new = jnp.where(restart, 1.0, t_new)
                delta = jnp.max(jnp.abs(nc - coef)) + jnp.max(
                    jnp.abs(jnp.atleast_1d(ni - b)))
                return (k + 1, nc, ni, nc + beta * (nc - coef),
                        ni + beta * (ni - b), t_new, step, delta)

            init = (jnp.zeros((), jnp.int32), jnp.zeros(shape),
                    jnp.zeros(b_shape), jnp.zeros(shape), jnp.zeros(b_shape),
                    jnp.ones(()), step0, jnp.full((), jnp.inf))
            k, coef, b, *_ = jax.lax.while_loop(
                lambda s: jnp.logical_and(s[0] < max_iter, s[7] > tol),
                body, init)
            res = solvers.FitResult(coef, jnp.atleast_1d(b), k, 0.0)
            return solvers.unscale_params(res, mean, scale, C)

        return jax.vmap(one_pt)(l2s, l1s)

    return jax.vmap(one_fold)(fold_weights)


def fit_problem(loss, n=2000, d=24, seed=3):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, d)).astype(np.float32)
    X[:, 3] = 40.75 + 0.03 * X[:, 3]          # a column with a pivot
    beta = rng.standard_normal(d)
    margin = (X - X.mean(axis=0)) / X.std(axis=0) @ beta / 4.0
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-margin))).astype(np.float32)
    if loss == "softmax":
        y = y + (rng.random(n) < 0.3)
    if loss == "squared":
        y = (margin + rng.standard_normal(n)).astype(np.float32)
    weights = (rng.random((3, n)) < 0.67).astype(np.float32)
    return (jnp.asarray(X), jnp.asarray(y, jnp.float32),
            jnp.asarray(weights))


PENALTIES = (jnp.asarray([0.0, 0.01, 0.1, 1.0], jnp.float32),
             jnp.asarray([0.0, 0.001, 0.0, 0.01], jnp.float32))


@pytest.mark.parametrize("fit_intercept,standardization",
                         [(True, True), (False, True), (True, False)])
@pytest.mark.parametrize("loss", ["logistic", "squared_hinge", "squared",
                                  "softmax"])
def test_lanes_fit_as_the_nested_vmap_did(loss, fit_intercept,
                                          standardization):
    X, y, weights = fit_problem(loss)
    kw = dict(loss=loss, fit_intercept=fit_intercept,
              standardization=standardization, max_iter=60, tol=1e-5,
              n_classes=3 if loss == "softmax" else 1)
    got = solvers.linear_grid_fit(X, y, weights, *PENALTIES, **kw)
    want = jax.jit(functools.partial(nested_vmap_grid_fit, **kw))(
        X, y, weights, *PENALTIES)
    assert got.coef.shape == want.coef.shape
    assert np.array_equal(np.asarray(got.n_iter), np.asarray(want.n_iter))
    np.testing.assert_allclose(np.asarray(got.coef), np.asarray(want.coef),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got.intercept),
                               np.asarray(want.intercept),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("loss", ["logistic", "squared_hinge"])
def test_one_pass_program_fits_as_the_two_pass_one(loss):
    """The whole grid program with its gradients and power iteration
    through the kernel (interpreted): the kernel rounds X and the
    coefficients to bfloat16, as the chip's products do and the CPU's do
    not, so the two agree to that rounding, not bit for bit."""
    X, y, weights = fit_problem(loss, n=700, d=10, seed=5)
    kw = dict(loss=loss, max_iter=40, tol=1e-6)
    two = solvers.linear_grid_fit(X, y, weights, *PENALTIES, **kw)
    with pltpu.force_tpu_interpret_mode():
        one = solvers.linear_grid_fit(X, y, weights, *PENALTIES,
                                      one_pass=True, **kw)
        program = str(jax.make_jaxpr(functools.partial(
            solvers.linear_grid_fit, one_pass=True, **kw))(
                X, y, weights, *PENALTIES))
    # the FISTA loop, the power iteration and its last product
    assert program.count("pallas_call") == 3
    scale = float(np.max(np.abs(np.asarray(two.coef))))
    assert np.max(np.abs(np.asarray(one.coef) - np.asarray(two.coef))) \
        < 2e-2 * scale
    np.testing.assert_allclose(np.asarray(one.intercept),
                               np.asarray(two.intercept), rtol=2e-2)


# --------------------------------------------------------------------------
# (c) the rule and the counters
# --------------------------------------------------------------------------

def described(platform="tpu", devices=1, major_to_minor=(1, 0)):
    """What the rule reads of a matrix: its rank, devices and layout."""
    return types.SimpleNamespace(
        ndim=2, shape=(196608, 8815),
        sharding=types.SimpleNamespace(device_set=tuple(
            types.SimpleNamespace(platform=platform, id=i)
            for i in range(devices))),
        format=types.SimpleNamespace(layout=types.SimpleNamespace(
            major_to_minor=major_to_minor)))


def test_the_rule_takes_a_column_major_matrix_on_one_tpu():
    X = described()
    assert onepass.engages(X, "logistic", 1)
    assert onepass.engages(X, "squared_hinge", 1)


@pytest.mark.parametrize("why", ["cpu_array", "cpu_described", "mesh",
                                 "eight_tpus", "row_major", "softmax",
                                 "classes", "squared", "poisson", "gamma",
                                 "sparse"])
def test_the_rule_keeps_the_xla_products(why):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from transmogrifai_tpu.sparse.matrix import SparseMatrix
    loss, classes = "logistic", 1
    if why == "cpu_array":
        X = jnp.ones((64, 5))
    elif why == "cpu_described":
        X = described(platform="cpu")
    elif why == "mesh":            # conftest's eight host devices
        mesh = Mesh(np.asarray(jax.devices()[:8]), ("data",))
        X = jax.device_put(np.ones((64, 5), np.float32),
                           NamedSharding(mesh, PartitionSpec("data")))
        assert len(X.sharding.device_set) == 8
    elif why == "eight_tpus":
        X = described(devices=8)
    elif why == "row_major":
        X = described(major_to_minor=(0, 1))
    elif why == "sparse":
        X = SparseMatrix.from_dense(np.eye(4, dtype=np.float32))
    else:
        X = described()
        loss = {"softmax": "softmax", "classes": "logistic",
                "squared": "squared", "poisson": "poisson",
                "gamma": "gamma"}[why]
        classes = 3 if why in ("softmax", "classes") else 1
    assert not onepass.engages(X, loss, classes)


def moved(before):
    after = REGISTRY.counters()
    return {k: after.get(k, 0) - before.get(k, 0) for k in COUNTERS}


def grid_fit(X, y, fold_weights):
    from transmogrifai_tpu.models.linear import OpLogisticRegression
    return OpLogisticRegression(max_iter=30).fit_arrays_grid(
        X, y, fold_weights, [{"reg_param": 0.01}, {"reg_param": 0.1}])


def test_a_two_pass_program_is_counted_once():
    X, y, weights = fit_problem("logistic", n=300, d=6)
    before = REGISTRY.counters()
    fitted = grid_fit(X, y, weights)
    assert all(k in REGISTRY.counters() for k in COUNTERS)  # created at 0
    longest = max(f["n_iter"] for row in fitted for f in row)
    assert moved(before) == {"linear.onepass_programs": 0,
                             "linear.twopass_programs": 1,
                             "linear.loop_iterations": longest}


def test_a_one_pass_program_is_counted_once(monkeypatch):
    from transmogrifai_tpu.models import linear
    X, y, weights = fit_problem("logistic", n=300, d=6)
    monkeypatch.setattr(linear.onepass, "engages", lambda *a: True)
    before = REGISTRY.counters()
    with pltpu.force_tpu_interpret_mode():
        fitted = grid_fit(X, y, weights)
    longest = max(f["n_iter"] for row in fitted for f in row)
    assert moved(before) == {"linear.onepass_programs": 1,
                             "linear.twopass_programs": 0,
                             "linear.loop_iterations": longest}


def test_a_single_fit_is_counted_once():
    from transmogrifai_tpu.models.linear import OpLinearSVC
    X, y, _ = fit_problem("squared_hinge", n=300, d=6)
    before = REGISTRY.counters()
    fitted = OpLinearSVC(reg_param=0.1, max_iter=25).fit_arrays(
        np.asarray(X), np.asarray(y))
    assert moved(before) == {"linear.onepass_programs": 0,
                             "linear.twopass_programs": 1,
                             "linear.loop_iterations": fitted["n_iter"]}


def test_a_sparse_program_is_neither():
    from transmogrifai_tpu.sparse.matrix import SparseMatrix
    X, y, weights = fit_problem("logistic", n=300, d=6)
    before = REGISTRY.counters()
    grid_fit(SparseMatrix.from_dense(np.asarray(X)), y, weights)
    assert moved(before) == dict.fromkeys(COUNTERS, 0)

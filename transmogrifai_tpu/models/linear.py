"""Linear-family models — the TPU-native re-design of the reference's Spark
MLlib wrappers (core/.../stages/impl/classification/OpLogisticRegression.scala:46,
OpLinearSVC.scala, OpNaiveBayes.scala, OpMultilayerPerceptronClassifier.scala,
core/.../impl/regression/OpLinearRegression.scala,
OpGeneralizedLinearRegression.scala).

Each estimator's hyper-parameters mirror the Spark ML params that the
reference's DefaultSelectorParams grids sweep (DefaultSelectorParams.scala:36-68).
The fits are single fused XLA programs (see models/solvers.py).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..columns import device_matrix, to_device_f32
from ..sparse.matrix import SparseMatrix
from . import onepass
from .base import PredictionModel, PredictorEstimator
from .solvers import (FitResult, fista_fit, linear_grid_fit, naive_bayes_fit,
                      ridge_fit, ridge_grid_fit, sparse_fista_fit,
                      sparse_linear_grid_fit, standardize, unscale_params)


def _n_classes(y) -> int:
    if not len(y):
        return 2
    import jax
    if isinstance(y, jax.Array):
        # reduce on device: np.max on a device array round-trips the whole
        # column over the host link vs one d2h scalar here
        return int(jnp.max(y)) + 1
    return int(np.max(y)) + 1


def _count_program(one_pass: bool, n_iter) -> None:
    """A dense FISTA program dispatched: through the one-pass kernel or the
    two XLA products (``onepass.engages``), and the iterations of its
    longest lane.  All three counters exist from the first program on."""
    from ..telemetry import REGISTRY
    names = ("linear.onepass_programs", "linear.twopass_programs")
    counters = [REGISTRY.counter(name) for name in names]
    counters[0 if one_pass else 1].inc()
    REGISTRY.counter("linear.loop_iterations").inc(int(np.max(n_iter)))


def _dense_fista_fit(X, y, w, l2, l1, *, loss: str, n_classes: int,
                     **kw) -> FitResult:
    """``fista_fit`` of one dense matrix, counted as a program."""
    one = onepass.engages(X, loss, n_classes)
    res = fista_fit(X, y, w, jnp.float32(l2), jnp.float32(l1), loss=loss,
                    n_classes=n_classes, one_pass=one, **kw)
    _count_program(one, res.n_iter)
    return res


def _grouped_grid_fit(est, X, y, fold_weights, grids, *, loss: str,
                      n_classes: int, l2l1, fitted_extra: Dict[str, Any]):
    """Shared (fold × grid) batched fit for the linear family: grid points are
    grouped by their static config (max_iter/intercept/standardization/tol)
    and each group trains as one nested-vmap XLA program over
    (fold_weights [F,N]) × (l2s, l1s [G]).  Returns fitted dicts [F][G]."""
    from collections import defaultdict
    K, G = fold_weights.shape[0], len(grids)
    out: list = [[None] * G for _ in range(K)]
    groups = defaultdict(list)
    for gi, p in enumerate(grids):
        m = {**est._params, **p}
        groups[(int(m.get("max_iter", 100)), bool(m.get("fit_intercept", True)),
                bool(m.get("standardization", True)),
                float(m.get("tol", 1e-6)))].append(gi)
    sparse = isinstance(X, SparseMatrix)
    Xj = X if sparse else device_matrix(X)
    yj = jnp.asarray(y, jnp.float32)
    Wj = to_device_f32(fold_weights, exact=True)
    nc = 1 if n_classes <= 2 else n_classes
    for (max_iter, fit_intercept, standardization, tol), gidx in groups.items():
        pens = [l2l1({**est._params, **grids[gi]}) for gi in gidx]
        l2s = jnp.asarray([p[0] for p in pens], jnp.float32)
        l1s = jnp.asarray([p[1] for p in pens], jnp.float32)
        if not sparse:
            # mesh sweeps with a 'model' axis wider than 1: lay the penalty
            # grid out over that axis (candidate_sharding) instead of
            # replicating it, so each model-column of devices solves its own
            # slice of the grid (SURVEY §2.6 P3) — the mesh rides in on X's
            # sharding, no extra fit-signature plumbing
            from ..parallel.mesh import candidate_mesh_for, candidate_sharding
            cmesh = candidate_mesh_for(Xj, len(gidx))
            if cmesh is not None:
                import jax as _jax
                csh = candidate_sharding(cmesh)
                l2s = _jax.device_put(l2s, csh)
                l1s = _jax.device_put(l1s, csh)
        # all grid dispatch goes through the registry seam (aot_registry):
        # a registry hit runs an installed executable with zero traces and
        # zero compiles; a miss runs the ordinary jit call and publishes a
        # fresh build for the rest of the fleet
        from ..aot import pretrace_mode
        from ..aot_registry import grid_call, grid_compile
        if sparse:
            label = "linear.sparse_grid_fit"
            g_fn = sparse_linear_grid_fit
            g_args = (Xj.values, Xj.indices, Xj.row_ids, yj, Wj, l2s, l1s)
            g_statics = dict(n_rows=Xj.n_rows, n_cols=Xj.n_cols, loss=loss,
                             fit_intercept=fit_intercept,
                             standardization=standardization,
                             max_iter=max_iter, tol=tol, n_classes=nc)
        elif loss == "squared" and all(p[1] == 0.0 for p in pens):
            label = "linear.ridge_grid_fit"
            g_fn = ridge_grid_fit
            g_args = (Xj, yj, Wj, l2s)
            g_statics = dict(fit_intercept=fit_intercept,
                             standardization=standardization)
        else:
            label = "linear.grid_fit"
            g_fn = linear_grid_fit
            g_args = (Xj, yj, Wj, l2s, l1s)
            g_statics = dict(loss=loss, fit_intercept=fit_intercept,
                             standardization=standardization,
                             max_iter=max_iter, tol=tol, n_classes=nc,
                             one_pass=onepass.engages(Xj, loss, nc))
        if pretrace_mode():
            # background pre-trace: registry hit → deserialize the
            # executable now (the real fit below dispatches it directly);
            # miss → lower+compile into the persistent cache and publish
            grid_compile(label, g_fn, g_args, static_kwargs=g_statics)
            continue
        res = grid_call(label, g_fn, g_args, static_kwargs=g_statics)
        coef = np.asarray(res.coef)
        inter = np.asarray(res.intercept)
        n_it = np.asarray(res.n_iter)
        if g_fn is linear_grid_fit:
            _count_program(g_statics["one_pass"], n_it)
        for j, gi in enumerate(gidx):
            for k in range(K):
                out[k][gi] = {"coef": coef[k, j], "intercept": inter[k, j],
                              "n_iter": int(n_it[k, j]), **fitted_extra}
    return out


def _np_sigmoid(z: np.ndarray) -> np.ndarray:
    return 1.0 / (1.0 + np.exp(-np.clip(z, -60.0, 60.0)))


def _np_softmax(z: np.ndarray) -> np.ndarray:
    z = z - np.max(z, axis=-1, keepdims=True)
    e = np.exp(z)
    return e / np.sum(e, axis=-1, keepdims=True)


def _binary_outputs(margin: np.ndarray) -> Dict[str, np.ndarray]:
    """Prediction triple from binary margins.  Pure numpy on purpose: scoring
    is elementwise host work; eager JAX dispatch here costs device round-trips
    per CV candidate (the fits are the device programs, not this)."""
    margin = np.asarray(margin, dtype=np.float32)
    p1 = _np_sigmoid(margin)
    prob = np.stack([1.0 - p1, p1], axis=1)
    raw = np.stack([-margin, margin], axis=1)
    return {"prediction": (p1 > 0.5).astype(np.float32),
            "probability": prob, "rawPrediction": raw}


@functools.partial(jax.jit, static_argnames=("kind", "full", "family"))
@jax.named_scope("score.linear")
def _linear_device_scores(Xd, coef, intercept, *, kind: str, full: bool,
                          family: str = "gaussian"):
    """One fused program for the whole device-score chain — the eager
    version dispatched 4-7 separate tiny executables (matmul, sigmoid,
    greater, stack, ...) per call, each paying dispatch latency (and a
    first-time executable load)."""
    return _scores_from_linear(Xd @ coef, intercept, kind=kind, full=full,
                               family=family)


@functools.partial(jax.jit, static_argnames=("kind", "full", "family"))
@jax.named_scope("score.linear")
def _scores_from_linear(lin, intercept, *, kind: str, full: bool,
                        family: str = "gaussian"):
    """Score-chain tail given the linear predictor ``lin = X @ coef`` — the
    shared seam that lets the sparse path swap in a segment-sum matvec
    while keeping the post-processing program identical to the dense one."""
    if kind == "multinomial":
        logits = lin + intercept
        out = {"prediction": jnp.argmax(logits, axis=1).astype(jnp.float32),
               "probability": jax.nn.softmax(logits, axis=-1)}
        if full:
            out["rawPrediction"] = logits
        return out
    margin = lin + (intercept[0] if intercept.ndim else intercept)
    if kind == "binary":
        p1 = jax.nn.sigmoid(margin)
        out = {"prediction": (margin > 0).astype(jnp.float32), "scores": p1}
        if full:
            out["probability"] = jnp.stack([1.0 - p1, p1], axis=1)
            out["rawPrediction"] = jnp.stack([-margin, margin], axis=1)
        return out
    if kind == "svc":
        out = {"prediction": (margin > 0).astype(jnp.float32),
               "scores": margin}
        if full:
            out["rawPrediction"] = jnp.stack([-margin, margin], axis=1)
        return out
    if kind == "glm":
        eta = jnp.clip(margin, -30.0, 30.0)
        pred = {"poisson": jnp.exp, "gamma": jnp.exp,
                "binomial": jax.nn.sigmoid,
                "gaussian": lambda e: e}[family](eta)
        return {"prediction": pred}
    return {"prediction": margin}


class LinearPredictionModel(PredictionModel):
    """Fitted linear model.  ``fitted``: coef [D] or [D,C], intercept,
    kind ∈ {binary, multinomial, regression, svc}."""

    def device_scores(self, Xd, full: bool = False) -> Dict[str, Any]:
        """Device-resident scoring: returns small per-row device arrays so
        only scalars/metric results ever cross the (slow) host link.  The CV
        loop uses the minimal set ({'prediction', 'scores'|'probability'});
        ``full=True`` mirrors ``predict_arrays``' key set exactly (probability
        + rawPrediction) so the Prediction schema is residency-independent."""
        kind = self.fitted["kind"]
        if isinstance(Xd, SparseMatrix):
            # margin via segment-sum matvec; identical post-processing
            return _scores_from_linear(
                Xd @ jnp.asarray(self.fitted["coef"]),
                jnp.asarray(self.fitted["intercept"]), kind=kind,
                full=bool(full), family=self.fitted.get("family", "gaussian"))
        return _linear_device_scores(
            Xd, jnp.asarray(self.fitted["coef"]),
            jnp.asarray(self.fitted["intercept"]), kind=kind,
            full=bool(full), family=self.fitted.get("family", "gaussian"))

    def predict_arrays(self, X) -> Dict[str, np.ndarray]:
        coef = np.asarray(self.fitted["coef"], dtype=np.float32)
        intercept = np.asarray(self.fitted["intercept"], dtype=np.float32)
        kind = self.fitted["kind"]
        lin = np.asarray(X @ coef) if isinstance(X, SparseMatrix) else X @ coef
        if kind == "multinomial":
            logits = lin + intercept
            prob = _np_softmax(logits)
            return {"prediction": np.argmax(logits, axis=1).astype(np.float32),
                    "probability": prob, "rawPrediction": logits}
        margin = lin + (intercept[0] if intercept.ndim else intercept)
        if kind == "binary":
            return _binary_outputs(margin)
        if kind == "svc":
            raw = np.stack([-margin, margin], axis=1)
            return {"prediction": (margin > 0).astype(np.float32),
                    "probability": None, "rawPrediction": raw}
        return {"prediction": margin.astype(np.float32)}


class OpLogisticRegression(PredictorEstimator):
    """≙ OpLogisticRegression (elastic-net logistic; binary or multinomial)."""

    model_cls = LinearPredictionModel
    # every reduction in the solvers is sample-weighted (sum(w·)/sum(w)), so
    # zero-weight padding rows leave the fit exact — lets the sweep pad N up
    # a ladder to reuse compiled executables across nearby dataset sizes
    weighted_pad_exact = True
    supports_pretrace = True

    def __init__(self, reg_param: float = 0.0, elastic_net_param: float = 0.0,
                 max_iter: int = 100, tol: float = 1e-6,
                 fit_intercept: bool = True, standardization: bool = True, **kw):
        super().__init__(reg_param=reg_param, elastic_net_param=elastic_net_param,
                         max_iter=max_iter, tol=tol, fit_intercept=fit_intercept,
                         standardization=standardization, **kw)

    def fit_arrays(self, X, y, sample_weight=None) -> Dict[str, Any]:
        n, d = X.shape
        w = jnp.ones(n, jnp.float32) if sample_weight is None else jnp.asarray(sample_weight)
        C = _n_classes(y)
        reg = float(self.get("reg_param", 0.0))
        en = float(self.get("elastic_net_param", 0.0))
        l1, l2 = reg * en, reg * (1.0 - en)
        loss = "logistic" if C <= 2 else "softmax"
        nc = 1 if C <= 2 else C
        if isinstance(X, SparseMatrix):
            res = sparse_fista_fit(
                X, jnp.asarray(y), w, l2, l1, loss=loss,
                fit_intercept=self.get("fit_intercept", True),
                standardization=self.get("standardization", True),
                max_iter=int(self.get("max_iter", 100)),
                tol=float(self.get("tol", 1e-6)), n_classes=nc)
            return {"coef": np.asarray(res.coef),
                    "intercept": np.asarray(res.intercept),
                    "kind": "binary" if C <= 2 else "multinomial",
                    "n_classes": C, "n_iter": int(res.n_iter)}
        Xj = jnp.asarray(X)
        if self.get("standardization", True):
            Xs, mean, scale = standardize(Xj, w, center=self.get("fit_intercept", True))
        else:
            Xs, mean, scale = Xj, jnp.zeros(d), jnp.ones(d)
        res = _dense_fista_fit(Xs, jnp.asarray(y), w, l2, l1, loss=loss,
                               n_classes=nc,
                               fit_intercept=self.get("fit_intercept", True),
                               max_iter=int(self.get("max_iter", 100)),
                               tol=float(self.get("tol", 1e-6)))
        res = unscale_params(res, mean, scale, nc)
        return {"coef": np.asarray(res.coef), "intercept": np.asarray(res.intercept),
                "kind": "binary" if C <= 2 else "multinomial",
                "n_classes": C, "n_iter": int(res.n_iter)}

    def fit_arrays_grid(self, X, y, fold_weights, grids):
        C = _n_classes(y)

        def l2l1(m):
            reg = float(m.get("reg_param", 0.0))
            en = float(m.get("elastic_net_param", 0.0))
            return reg * (1.0 - en), reg * en

        return _grouped_grid_fit(
            self, X, y, fold_weights, grids,
            loss="logistic" if C <= 2 else "softmax", n_classes=C, l2l1=l2l1,
            fitted_extra={"kind": "binary" if C <= 2 else "multinomial",
                          "n_classes": C})


class OpLinearSVC(PredictorEstimator):
    """≙ OpLinearSVC (squared-hinge linear SVM; binary, no probabilities)."""

    model_cls = LinearPredictionModel
    weighted_pad_exact = True   # see OpLogisticRegression
    supports_pretrace = True

    def __init__(self, reg_param: float = 0.0, max_iter: int = 100,
                 tol: float = 1e-6, fit_intercept: bool = True,
                 standardization: bool = True, **kw):
        super().__init__(reg_param=reg_param, max_iter=max_iter, tol=tol,
                         fit_intercept=fit_intercept, standardization=standardization, **kw)

    def fit_arrays(self, X, y, sample_weight=None) -> Dict[str, Any]:
        n, d = X.shape
        w = jnp.ones(n, jnp.float32) if sample_weight is None else jnp.asarray(sample_weight)
        if isinstance(X, SparseMatrix):
            res = sparse_fista_fit(
                X, jnp.asarray(y), w, float(self.get("reg_param", 0.0)), 0.0,
                loss="squared_hinge",
                fit_intercept=self.get("fit_intercept", True),
                standardization=self.get("standardization", True),
                max_iter=int(self.get("max_iter", 100)),
                tol=float(self.get("tol", 1e-6)))
            return {"coef": np.asarray(res.coef),
                    "intercept": np.asarray(res.intercept),
                    "kind": "svc", "n_classes": 2, "n_iter": int(res.n_iter)}
        Xj = jnp.asarray(X)
        if self.get("standardization", True):
            Xs, mean, scale = standardize(Xj, w, center=self.get("fit_intercept", True))
        else:
            Xs, mean, scale = Xj, jnp.zeros(d), jnp.ones(d)
        res = _dense_fista_fit(Xs, jnp.asarray(y), w,
                               float(self.get("reg_param", 0.0)), 0.0,
                               loss="squared_hinge", n_classes=1,
                               fit_intercept=self.get("fit_intercept", True),
                               max_iter=int(self.get("max_iter", 100)),
                               tol=float(self.get("tol", 1e-6)))
        res = unscale_params(res, mean, scale, 1)
        return {"coef": np.asarray(res.coef), "intercept": np.asarray(res.intercept),
                "kind": "svc", "n_classes": 2, "n_iter": int(res.n_iter)}

    def fit_arrays_grid(self, X, y, fold_weights, grids):
        return _grouped_grid_fit(
            self, X, y, fold_weights, grids, loss="squared_hinge", n_classes=2,
            l2l1=lambda m: (float(m.get("reg_param", 0.0)), 0.0),
            fitted_extra={"kind": "svc", "n_classes": 2})


class OpLinearRegression(PredictorEstimator):
    """≙ OpLinearRegression (elastic-net least squares; closed-form ridge when
    l1 = 0)."""

    model_cls = LinearPredictionModel
    weighted_pad_exact = True   # see OpLogisticRegression
    supports_pretrace = True

    def __init__(self, reg_param: float = 0.0, elastic_net_param: float = 0.0,
                 max_iter: int = 100, tol: float = 1e-6,
                 fit_intercept: bool = True, standardization: bool = True, **kw):
        super().__init__(reg_param=reg_param, elastic_net_param=elastic_net_param,
                         max_iter=max_iter, tol=tol, fit_intercept=fit_intercept,
                         standardization=standardization, **kw)

    def fit_arrays(self, X, y, sample_weight=None) -> Dict[str, Any]:
        n, d = X.shape
        w = jnp.ones(n, jnp.float32) if sample_weight is None else jnp.asarray(sample_weight)
        reg = float(self.get("reg_param", 0.0))
        en = float(self.get("elastic_net_param", 0.0))
        l1, l2 = reg * en, reg * (1.0 - en)
        if isinstance(X, SparseMatrix):
            res = sparse_fista_fit(
                X, jnp.asarray(y), w, l2, l1, loss="squared",
                fit_intercept=self.get("fit_intercept", True),
                standardization=self.get("standardization", True),
                max_iter=int(self.get("max_iter", 100)),
                tol=float(self.get("tol", 1e-6)))
            return {"coef": np.asarray(res.coef),
                    "intercept": np.asarray(res.intercept),
                    "kind": "regression", "n_iter": int(res.n_iter)}
        Xj, yj = jnp.asarray(X), jnp.asarray(y)
        if self.get("standardization", True):
            Xs, mean, scale = standardize(Xj, w, center=self.get("fit_intercept", True))
        else:
            Xs, mean, scale = Xj, jnp.zeros(d), jnp.ones(d)
        if l1 == 0.0:
            res = ridge_fit(Xs, yj, w, jnp.float32(l2),
                            fit_intercept=self.get("fit_intercept", True))
        else:
            res = _dense_fista_fit(Xs, yj, w, l2, l1, loss="squared",
                                   n_classes=1,
                                   fit_intercept=self.get("fit_intercept",
                                                          True),
                                   max_iter=int(self.get("max_iter", 100)),
                                   tol=float(self.get("tol", 1e-6)))
        res = unscale_params(res, mean, scale, 1)
        return {"coef": np.asarray(res.coef), "intercept": np.asarray(res.intercept),
                "kind": "regression", "n_iter": int(res.n_iter)}

    def fit_arrays_grid(self, X, y, fold_weights, grids):
        def l2l1(m):
            reg = float(m.get("reg_param", 0.0))
            en = float(m.get("elastic_net_param", 0.0))
            return reg * (1.0 - en), reg * en

        return _grouped_grid_fit(
            self, X, y, fold_weights, grids, loss="squared", n_classes=2,
            l2l1=l2l1, fitted_extra={"kind": "regression"})


class OpGeneralizedLinearRegression(PredictorEstimator):
    """≙ OpGeneralizedLinearRegression: families gaussian/binomial/poisson/gamma
    (log/identity/logit links as in the reference grid
    BinaryClassificationModelSelector.scala / DefaultSelectorParams.scala:56-65)."""

    weighted_pad_exact = True   # see OpLogisticRegression
    supports_pretrace = True

    def __init__(self, family: str = "gaussian", link: Optional[str] = None,
                 reg_param: float = 0.0, max_iter: int = 50, tol: float = 1e-6,
                 fit_intercept: bool = True, **kw):
        super().__init__(family=family, link=link, reg_param=reg_param,
                         max_iter=max_iter, tol=tol, fit_intercept=fit_intercept, **kw)

    def fit_arrays(self, X, y, sample_weight=None) -> Dict[str, Any]:
        n, d = X.shape
        w = jnp.ones(n, jnp.float32) if sample_weight is None else jnp.asarray(sample_weight)
        family = self.get("family", "gaussian")
        loss = {"gaussian": "squared", "binomial": "logistic",
                "poisson": "poisson", "gamma": "gamma"}.get(family)
        if loss is None:
            raise ValueError(f"unsupported GLM family {family!r}")
        if isinstance(X, SparseMatrix):
            res = sparse_fista_fit(
                X, jnp.asarray(y), w, float(self.get("reg_param", 0.0)), 0.0,
                loss=loss, fit_intercept=self.get("fit_intercept", True),
                max_iter=int(self.get("max_iter", 50)),
                tol=float(self.get("tol", 1e-6)))
            return {"coef": np.asarray(res.coef),
                    "intercept": np.asarray(res.intercept),
                    "kind": "glm", "family": family,
                    "n_iter": int(res.n_iter)}
        Xj, yj = jnp.asarray(X), jnp.asarray(y)
        Xs, mean, scale = standardize(Xj, w, center=self.get("fit_intercept", True))
        res = _dense_fista_fit(Xs, yj, w, float(self.get("reg_param", 0.0)),
                               0.0, loss=loss, n_classes=1,
                               fit_intercept=self.get("fit_intercept", True),
                               max_iter=int(self.get("max_iter", 50)),
                               tol=float(self.get("tol", 1e-6)))
        res = unscale_params(res, mean, scale, 1)
        return {"coef": np.asarray(res.coef), "intercept": np.asarray(res.intercept),
                "kind": "glm", "family": family, "n_iter": int(res.n_iter)}

    def fit_arrays_grid(self, X, y, fold_weights, grids):
        family = self.get("family", "gaussian")
        loss = {"gaussian": "squared", "binomial": "logistic",
                "poisson": "poisson", "gamma": "gamma"}[family]
        return _grouped_grid_fit(
            self, X, y, fold_weights, grids, loss=loss, n_classes=2,
            l2l1=lambda m: (float(m.get("reg_param", 0.0)), 0.0),
            fitted_extra={"kind": "glm", "family": family})


class GLMPredictionModel(LinearPredictionModel):
    """≙ GeneralizedLinearRegressionModel.predict: apply the family's inverse
    link g⁻¹(η) to the linear predictor (exp for poisson/gamma log link,
    sigmoid for binomial logit; identity for gaussian)."""

    _INVERSE_LINK = {
        "poisson": lambda eta: np.exp(np.clip(eta, -30.0, 30.0)),
        "gamma": lambda eta: np.exp(np.clip(eta, -30.0, 30.0)),
        "binomial": lambda eta: 1.0 / (1.0 + np.exp(-np.clip(eta, -30.0, 30.0))),
        "gaussian": lambda eta: eta,
    }

    def predict_arrays(self, X) -> Dict[str, np.ndarray]:
        coef = np.asarray(self.fitted["coef"], dtype=np.float32)
        intercept = np.asarray(self.fitted["intercept"], dtype=np.float32)
        lin = np.asarray(X @ coef) if isinstance(X, SparseMatrix) else X @ coef
        eta = lin + (intercept[0] if intercept.ndim else intercept)
        inv = self._INVERSE_LINK[self.fitted.get("family", "gaussian")]
        return {"prediction": inv(eta).astype(np.float32)}


OpGeneralizedLinearRegression.model_cls = GLMPredictionModel


class NaiveBayesModel(PredictionModel):
    """Fitted multinomial NB: log_prior [C], log_prob [C,D]."""

    def device_scores(self, Xd, full: bool = False) -> Dict[str, Any]:
        logits = (jnp.maximum(Xd, 0.0) @ jnp.asarray(self.fitted["log_prob"]).T
                  + jnp.asarray(self.fitted["log_prior"]))
        prob = jax.nn.softmax(logits, axis=-1)
        out = {"prediction": jnp.argmax(logits, axis=1).astype(jnp.float32),
               "probability": prob}
        if prob.shape[1] == 2:
            out["scores"] = prob[:, 1]
        if full:
            out["rawPrediction"] = logits
        return out

    def predict_arrays(self, X: np.ndarray) -> Dict[str, np.ndarray]:
        log_prior = np.asarray(self.fitted["log_prior"])
        log_prob = np.asarray(self.fitted["log_prob"])
        logits = np.maximum(X, 0.0) @ log_prob.T + log_prior
        prob = _np_softmax(logits)
        return {"prediction": np.argmax(logits, axis=1).astype(np.float32),
                "probability": prob, "rawPrediction": logits}


class OpNaiveBayes(PredictorEstimator):
    """≙ OpNaiveBayes (multinomial, smoothing=1.0 default)."""

    model_cls = NaiveBayesModel

    def __init__(self, smoothing: float = 1.0, **kw):
        super().__init__(smoothing=smoothing, **kw)

    def fit_arrays(self, X, y, sample_weight=None) -> Dict[str, Any]:
        n = X.shape[0]
        w = jnp.ones(n, jnp.float32) if sample_weight is None else jnp.asarray(sample_weight)
        C = _n_classes(y)
        log_prior, log_prob = naive_bayes_fit(
            jnp.asarray(X), jnp.asarray(y), w,
            jnp.float32(self.get("smoothing", 1.0)), n_classes=C)
        return {"log_prior": np.asarray(log_prior), "log_prob": np.asarray(log_prob),
                "kind": "naive_bayes", "n_classes": C}


class MLPClassificationModel(PredictionModel):
    """Fitted MLP: list of (W, b) per layer."""

    def device_scores(self, Xd, full: bool = False) -> Dict[str, Any]:
        h = Xd
        n_layers = self.fitted["n_layers"]
        for i in range(n_layers):
            h = h @ jnp.asarray(self.fitted[f"W{i}"]) + jnp.asarray(self.fitted[f"b{i}"])
            if i < n_layers - 1:
                h = jax.nn.relu(h)
        prob = jax.nn.softmax(h, axis=-1)
        out = {"prediction": jnp.argmax(h, axis=1).astype(jnp.float32),
               "probability": prob}
        if prob.shape[1] == 2:
            out["scores"] = prob[:, 1]
        if full:
            out["rawPrediction"] = h
        return out

    def predict_arrays(self, X: np.ndarray) -> Dict[str, np.ndarray]:
        h = np.asarray(X, dtype=np.float32)
        n_layers = self.fitted["n_layers"]
        for i in range(n_layers):
            W = np.asarray(self.fitted[f"W{i}"])
            b = np.asarray(self.fitted[f"b{i}"])
            h = h @ W + b
            if i < n_layers - 1:
                h = np.maximum(h, 0.0)
        logits = h
        prob = _np_softmax(logits)
        return {"prediction": np.argmax(logits, axis=1).astype(np.float32),
                "probability": prob, "rawPrediction": logits}


class OpMultilayerPerceptronClassifier(PredictorEstimator):
    """≙ OpMultilayerPerceptronClassifier: small feed-forward net, full-batch
    Adam (the reference uses L-BFGS on a sigmoid net; relu+adam is the
    TPU-idiomatic equivalent)."""

    model_cls = MLPClassificationModel

    def __init__(self, hidden_layers=(10,), max_iter: int = 200,
                 step_size: float = 0.05, seed: int = 42, **kw):
        super().__init__(hidden_layers=tuple(hidden_layers), max_iter=max_iter,
                         step_size=step_size, seed=seed, **kw)

    def fit_arrays(self, X, y, sample_weight=None) -> Dict[str, Any]:
        import optax
        n, d = X.shape
        C = _n_classes(y)
        sizes = [d] + list(self.get("hidden_layers", (10,))) + [C]
        key = jax.random.PRNGKey(int(self.get("seed", 42)))
        params = []
        for i in range(len(sizes) - 1):
            key, k1 = jax.random.split(key)
            W = jax.random.normal(k1, (sizes[i], sizes[i + 1]),
                                  jnp.float32) * jnp.sqrt(2.0 / sizes[i])
            params.append((W, jnp.zeros(sizes[i + 1], jnp.float32)))
        Xj = jnp.asarray(X)
        yj = jnp.asarray(y, dtype=jnp.int32)
        w = jnp.ones(n, jnp.float32) if sample_weight is None else jnp.asarray(sample_weight)

        def forward(params, x):
            h = x
            for i, (W, b) in enumerate(params):
                h = h @ W + b
                if i < len(params) - 1:
                    h = jax.nn.relu(h)
            return h

        def loss_fn(params):
            logits = forward(params, Xj)
            ls = optax.softmax_cross_entropy_with_integer_labels(logits, yj)
            return jnp.sum(w * ls) / jnp.sum(w)

        opt = optax.adam(float(self.get("step_size", 0.05)))
        state = opt.init(params)

        @jax.jit
        def step(params, state):
            loss, grads = jax.value_and_grad(loss_fn)(params)
            updates, state = opt.update(grads, state)
            return optax.apply_updates(params, updates), state, loss

        for _ in range(int(self.get("max_iter", 200))):
            params, state, loss = step(params, state)
        fitted: Dict[str, Any] = {"kind": "mlp", "n_layers": len(params),
                                  "n_classes": C}
        for i, (W, b) in enumerate(params):
            fitted[f"W{i}"] = np.asarray(W)
            fitted[f"b{i}"] = np.asarray(b)
        return fitted

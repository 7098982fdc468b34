"""Splitters and validators — the TPU-native re-design of the reference tuning
package (core/src/main/scala/com/salesforce/op/stages/impl/tuning/:
DataSplitter.scala, DataBalancer.scala, DataCutter.scala, OpValidator.scala:91,
OpCrossValidation.scala:42, OpTrainValidationSplit.scala).

Where the reference fan-outs k × Σ|grid| Spark jobs over a JVM thread pool
(OpValidator.scala:320-349), here each candidate fit is a compiled XLA program
over HBM-resident fold slices; homogeneous hyper-parameter grids additionally
vectorise via the models' array-level fit functions (SURVEY.md §2.6 P3).
Reference defaults preserved: NumFolds=3, Parallelism=8, stratify=false
(OpValidator.scala:372-378).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import logging

import numpy as np

from .columns import ColumnBatch
from .evaluators import OpEvaluatorBase
from .resilience import (AllCandidatesFailed, active_failure_log,
                         maybe_inject, record_failure)

logger = logging.getLogger(__name__)

# batched-metric fast-path fallbacks already logged, one per model family
# PER VALIDATE — a silent fallback could hide a real fitted-state corruption
# behind the (correct but slow) per-candidate path.
# Scoped per-validate (reset by ``Validator.validate``): a module-lifetime
# set would suppress the note for every later train in the same process
# (lifecycle retrains, pool workers), exactly the runs where a NEW
# corruption could appear.  The FailureLog record stays unconditional.
_logged_fallback_families = set()


def _reset_logged_fallbacks() -> None:
    _logged_fallback_families.clear()


def _log_metric_fallback(family: str, exc: BaseException) -> None:
    record_failure(family, "fallback", exc, point="selector.batched_metrics")
    if family not in _logged_fallback_families:
        _logged_fallback_families.add(family)
        # warning, not debug: the default root logger must surface it
        logger.warning("batched grid-metric fast path fell back to the "
                       "per-candidate path for %s: %r", family, exc)


# --------------------------------------------------------------------------
# splitters
# --------------------------------------------------------------------------

@dataclass
class SplitterSummary:
    """Metadata recorded by preValidationPrepare (≙ SplitterSummary)."""
    splitter: str = ""
    info: Dict[str, Any] = field(default_factory=dict)


class Splitter:
    """≙ tuning/Splitter.scala: optional test-holdout + per-class preparation."""

    def __init__(self, seed: int = 42, reserve_test_fraction: float = 0.0):
        self.seed = int(seed)
        self.reserve_test_fraction = float(reserve_test_fraction)
        self.summary: Optional[SplitterSummary] = None

    def split(self, batch: ColumnBatch, label: str) -> Tuple[ColumnBatch, ColumnBatch]:
        n = len(batch)
        rng = np.random.default_rng(self.seed)
        perm = rng.permutation(n)
        n_test = int(round(n * self.reserve_test_fraction))
        return batch.take_rows(perm[n_test:]), batch.take_rows(perm[:n_test])

    def pre_validation_prepare(self, batch: ColumnBatch, label: str) -> ColumnBatch:
        self.summary = SplitterSummary(type(self).__name__)
        return batch

    def validation_prepare(self, batch: ColumnBatch, label: str) -> ColumnBatch:
        return batch

    def validation_prepare_weights(self, y: np.ndarray,
                                   w: np.ndarray) -> np.ndarray:
        """Weight-space variant of ``validation_prepare`` for the static-shape
        CV path: adjust per-row training weights (0 == excluded) instead of
        materialising a resampled batch — keeps one HBM-resident X with no
        per-fold reshapes."""
        return w


class DataSplitter(Splitter):
    """≙ DataSplitter: plain random split, no rebalancing."""


class DataBalancer(Splitter):
    """≙ DataBalancer.scala: resample a binary label towards a minimum
    ``sample_fraction`` of the minority class, capped at
    ``max_training_sample`` rows.

    Reference semantics reproduced exactly (DataBalancer.scala:76-160):

    * already balanced (minority fraction ≥ ``sample_fraction``) → no
      resampling; only a global down-sample when the data exceeds the cap;
    * minority below the cap's share → UP-sample it by the largest integer
      multiplier from {100, 50, 10, 5, 4, 3, 2} that stays under both the
      target fraction and the cap (with replacement), then down-sample the
      majority to hit the fraction;
    * otherwise down-sample BOTH classes to the capped size at the target
      fraction.
    """

    def __init__(self, sample_fraction: float = 0.1,
                 max_training_sample: int = 1_000_000, seed: int = 42,
                 reserve_test_fraction: float = 0.0):
        super().__init__(seed, reserve_test_fraction)
        self.sample_fraction = float(sample_fraction)
        self.max_training_sample = int(max_training_sample)

    @staticmethod
    def get_proportions(small: float, big: float, sample_f: float,
                        max_training_sample: int) -> Tuple[float, float]:
        """(downSample, upSample) fractions (≙ getProportions,
        DataBalancer.scala:84-115)."""

        def check_up(mult: int) -> bool:
            return (mult * small * (1.0 - sample_f) < sample_f * big
                    and max_training_sample * sample_f > small * mult)

        if small < max_training_sample * sample_f:
            up = next((float(m) for m in (100, 50, 10, 5, 4, 3, 2)
                       if check_up(m)), 1.0)
            down = (small * up / sample_f - small * up) / big
            return down, up
        up = (max_training_sample * sample_f) / small
        down = (1.0 - sample_f) * max_training_sample / big
        return down, up

    def _plan(self, y: np.ndarray) -> Dict[str, Any]:
        """≙ estimate (DataBalancer.scala:130-175): decide fractions and
        record the DataBalancerSummary fields."""
        pos = int((y > 0.5).sum())
        neg = int(len(y) - pos)
        total = max(pos + neg, 1)
        sample_f = self.sample_fraction
        is_pos_small = pos < neg
        small, big = (pos, neg) if is_pos_small else (neg, pos)
        if small / total >= sample_f:
            frac = (self.max_training_sample / total
                    if self.max_training_sample < total else 1.0)
            plan = {"balanced": True, "fraction": frac,
                    "is_pos_small": is_pos_small, "up": 0.0, "down": frac}
        else:
            down, up = self.get_proportions(small, big, sample_f,
                                            self.max_training_sample)
            plan = {"balanced": False, "is_pos_small": is_pos_small,
                    "up": up, "down": down}
        self.summary = SplitterSummary("DataBalancer", {
            "positiveLabels": pos, "negativeLabels": neg,
            "desiredFraction": sample_f,
            "upSamplingFraction": 0.0 if plan["balanced"] else plan["up"],
            "downSamplingFraction": plan["down"]})
        return plan

    def pre_validation_prepare(self, batch, label):
        self._plan(np.asarray(batch[label].values, dtype=np.float64))
        return batch

    def validation_prepare(self, batch, label):
        """Physically resample rows (≙ rebalance, DataBalancer.scala:
        sample with replacement for up > 1, plain subsample otherwise)."""
        y = np.asarray(batch[label].values, dtype=np.float64)
        plan = self._plan(y)
        rng = np.random.default_rng(self.seed)
        n = len(y)
        if plan["balanced"]:
            if plan["fraction"] >= 1.0:
                return batch
            keep = np.flatnonzero(rng.random(n) < plan["fraction"])
            return batch.take_rows(keep)
        small_mask = ((y > 0.5) == plan["is_pos_small"])
        small_idx = np.flatnonzero(small_mask)
        big_idx = np.flatnonzero(~small_mask)
        big_keep = big_idx[rng.random(len(big_idx)) < plan["down"]]
        up = plan["up"]
        if up > 1.0:
            # with replacement at rate `up` ≈ per-row Poisson(up) copies
            reps = rng.poisson(up, len(small_idx))
            small_keep = np.repeat(small_idx, reps)
        elif up == 1.0:
            small_keep = small_idx
        else:
            small_keep = small_idx[rng.random(len(small_idx)) < up]
        keep = np.concatenate([small_keep, big_keep])
        rng.shuffle(keep)
        return batch.take_rows(keep)

    def validation_prepare_weights(self, y, w):
        """Weight-space variant for the static-shape CV path: up-sampling
        becomes a per-row Poisson weight multiplier (the bootstrap analog of
        sampling with replacement); down-sampling zeroes a random subset."""
        idx = np.flatnonzero(w > 0)
        if not len(idx):
            return w
        plan = self._plan_cached(y, idx)
        rng = np.random.default_rng(self.seed)
        out = np.zeros_like(w)
        if plan["balanced"]:
            if plan["fraction"] >= 1.0:
                return w
            keep = idx[rng.random(len(idx)) < plan["fraction"]]
            out[keep] = w[keep]
            return out
        small_mask = ((y[idx] > 0.5) == plan["is_pos_small"])
        small_idx = idx[small_mask]
        big_idx = idx[~small_mask]
        big_keep = big_idx[rng.random(len(big_idx)) < plan["down"]]
        out[big_keep] = w[big_keep]
        up = plan["up"]
        if up > 1.0:
            reps = rng.poisson(up, len(small_idx)).astype(w.dtype)
            out[small_idx] = w[small_idx] * reps
        elif up == 1.0:
            out[small_idx] = w[small_idx]
        else:
            small_keep = small_idx[rng.random(len(small_idx)) < up]
            out[small_keep] = w[small_keep]
        return out

    def _plan_cached(self, y: np.ndarray, idx: np.ndarray) -> Dict[str, Any]:
        return self._plan(np.asarray(y, dtype=np.float64)[idx])


class DataCutter(Splitter):
    """≙ DataCutter.scala: multiclass — keep at most ``max_label_categories``
    labels each with fraction ≥ ``min_label_fraction``; drop other rows and
    record dropped labels."""

    def __init__(self, max_label_categories: int = 100,
                 min_label_fraction: float = 0.0, seed: int = 42,
                 reserve_test_fraction: float = 0.0):
        super().__init__(seed, reserve_test_fraction)
        self.max_label_categories = int(max_label_categories)
        self.min_label_fraction = float(min_label_fraction)
        self.labels_kept: List[float] = []
        self.labels_dropped: List[float] = []

    def pre_validation_prepare(self, batch, label):
        y = np.asarray(batch[label].values, dtype=np.float64)
        vals, counts = np.unique(y, return_counts=True)
        frac = counts / max(len(y), 1)
        order = np.argsort(-counts, kind="mergesort")
        keep = [v for i, v in zip(order, vals[order])
                if frac[i] >= self.min_label_fraction][:self.max_label_categories]
        keep_set = set(keep)
        self.labels_kept = sorted(keep_set)
        self.labels_dropped = sorted(set(vals.tolist()) - keep_set)
        self.summary = SplitterSummary("DataCutter", {
            "labelsKept": self.labels_kept, "labelsDropped": self.labels_dropped})
        return batch

    def validation_prepare(self, batch, label):
        if not self.labels_dropped:
            return batch
        y = np.asarray(batch[label].values, dtype=np.float64)
        mask = np.isin(y, np.asarray(self.labels_kept))
        return batch.take_rows(np.flatnonzero(mask))

    def validation_prepare_weights(self, y, w):
        if not self.labels_dropped:
            return w
        mask = np.isin(y, np.asarray(self.labels_kept))
        return np.where(mask, w, 0.0).astype(w.dtype)


# --------------------------------------------------------------------------
# validators
# --------------------------------------------------------------------------

_GRID_MARGINS_JIT = None


def _grid_margins(X, C, b):
    """[N, K] linear margins for K candidates in one dispatch; bf16 feature
    storage converts inside the matmul (f32 accumulation), nothing [N, D]
    materializes."""
    global _GRID_MARGINS_JIT
    if _GRID_MARGINS_JIT is None:
        import jax
        import jax.numpy as jnp

        @jax.jit
        def fn(X, C, b):
            return jnp.einsum("nd,kd->nk", X, C,
                              preferred_element_type=jnp.float32) + b[None, :]
        _GRID_MARGINS_JIT = fn
    return _GRID_MARGINS_JIT(X, C, b)


_MULTI_PRED_JIT = None


def _multinomial_pred_grid(X, C3, B):
    """[N, K] argmax class predictions for K multinomial candidates in one
    dispatch (coef stack [K, C, D], intercepts [K, C]).  Softmax is
    monotone per row, so argmax over raw margins reproduces each model's
    prediction exactly."""
    global _MULTI_PRED_JIT
    if _MULTI_PRED_JIT is None:
        import jax
        import jax.numpy as jnp

        @jax.jit
        def fn(X, C3, B):
            m = jnp.einsum("nd,kdc->nkc", X, C3,
                           preferred_element_type=jnp.float32) + B[None]
            return jnp.argmax(m, axis=-1).astype(jnp.int32)
        _MULTI_PRED_JIT = fn
    return _MULTI_PRED_JIT(X, C3, B)


# fit-program row-count canonicalization (ISSUE 4 compile reuse): pad N up a
# geometric ladder with zero-weight rows so re-trains at nearby sizes hit the
# SAME compiled fit executable.  Zero-weight padding is exact for the linear
# solvers (every reduction is weight-normalized — see
# models/solvers.linear_grid_fit); tree fitters bin features with unweighted
# quantiles, so only estimators declaring ``weighted_pad_exact`` opt in.
_FIT_PAD_FLOOR = 4096
_FIT_PAD_STEP = 1.25
_FIT_PAD_QUANTUM = 256


def _fit_pad_rows(n: int) -> int:
    """Smallest ladder rung >= n.  n <= the floor returns n unchanged, so
    small fixtures (and every tier-1 test) keep bit-identical shapes."""
    if n <= _FIT_PAD_FLOOR:
        return int(n)
    rung = _FIT_PAD_FLOOR
    while rung < n:
        rung = int(-(-int(rung * _FIT_PAD_STEP) // _FIT_PAD_QUANTUM)
                   * _FIT_PAD_QUANTUM)
    return rung


def _fit_padding_enabled() -> bool:
    """Shape canonicalization only pays off with a persistent compile cache
    to hit, so it rides the TRANSMOGRIFAI_COMPILE_CACHE opt-in."""
    import os
    cc = os.environ.get("TRANSMOGRIFAI_COMPILE_CACHE")
    return bool(cc) and cc != "0"


_FOLD_MASK_FNS: Dict[int, Any] = {}

# uint8 fold-assignment sentinels: 255 = "in no validation fold" (a TVS row
# outside the held-out slice — it trains in every fold), 254 = "zero-weight
# pad row" (mesh device-divisibility quantum / ladder rung — it belongs to
# NO fold, training or validation)
_NO_FOLD = 255
_PAD_FOLD = 254


def _fold_masks_from_assignment(assign, n_folds: int):
    """[N] uint8 validation-fold assignment → (train weights [F, N],
    validation masks [F, N]) built ON DEVICE: the host link carries one
    byte per row instead of the materialized masks.  A sharded assignment
    propagates its row sharding into the masks (axis 1), so the mesh path
    never materializes [F, N] weights on the host."""
    import jax
    import jax.numpy as jnp

    fn = _FOLD_MASK_FNS.get(n_folds)
    if fn is None:
        @jax.jit
        def fn(a):
            f = jnp.arange(n_folds, dtype=jnp.int32)[:, None]
            ai = a.astype(jnp.int32)[None, :]
            tr = ((ai != f) & (ai != _PAD_FOLD)).astype(jnp.float32)
            return tr, (ai == f).astype(jnp.float32)
        _FOLD_MASK_FNS[n_folds] = fn
    return fn(assign)


@dataclass
class ModelCandidate:
    """One estimator + its hyper-parameter grid (≙ (estimator, Array[ParamMap]))."""
    estimator: Any                      # PredictorEstimator (unwired is fine)
    grid: List[Dict[str, Any]] = field(default_factory=lambda: [{}])
    name: Optional[str] = None

    @property
    def model_name(self) -> str:
        return self.name or type(self.estimator).__name__


@dataclass
class ValidatedCandidate:
    model_name: str
    params: Dict[str, Any]
    metric_values: List[float]
    candidate_index: int = 0   # identity: two candidates may share a name
    # successive halving pruned this grid point after the fold-0 screen:
    # metric_values holds the fold-0 metric only and the point is excluded
    # from final winner selection (full-k-fold means only)
    raced_out: bool = False

    @property
    def mean_metric(self) -> float:
        vals = [v for v in self.metric_values if np.isfinite(v)]
        return float(np.mean(vals)) if vals else float("nan")


@dataclass
class ValidationResult:
    best: ModelCandidate                 # winning estimator with params applied
    best_params: Dict[str, Any]
    best_metric: float
    all_results: List[ValidatedCandidate]
    validation_type: str
    metric_name: str
    is_larger_better: bool


class OpValidator:
    """Base validator (≙ OpValidator.scala:91).

    ``validate`` fits every (candidate × grid-point) on each train split and
    scores on the held-out split with ``evaluator``; individual fit failures
    are tolerated (CHANGELOG 0.6.x: "robust to failing models") — a failed fit
    contributes NaN for that split and the candidate is skipped if it never
    succeeds.
    """

    validation_type = "validator"

    def __init__(self, evaluator: OpEvaluatorBase, seed: int = 42,
                 stratify: bool = False, parallelism: int = 8,
                 racing: Optional[bool] = None,
                 racing_eta: Optional[float] = None,
                 racing_min_survivors: Optional[int] = None):
        self.evaluator = evaluator
        self.seed = int(seed)
        self.stratify = bool(stratify)
        self.parallelism = int(parallelism)
        # successive-halving sweep racing (ISSUE 4): None defers to
        # DefaultSelectorParams so OpParams/selector factories can retune
        # the fleet-wide defaults without touching every validator ctor
        self.racing = racing
        self.racing_eta = racing_eta
        self.racing_min_survivors = racing_min_survivors
        # per-family (folds, rows, lanes) of the last batched fit block —
        # the selector's winner refit reuses the SAME compiled executable
        self.family_fit_meta: Dict[str, Dict[str, Any]] = {}

    def _racing_config(self) -> Tuple[bool, float, int]:
        """(enabled, eta, min_survivors) with DefaultSelectorParams filling
        unset knobs.  Lazy import: selector.py imports this module."""
        from .selector import DefaultSelectorParams as P
        enabled = (self.racing if self.racing is not None
                   else bool(getattr(P, "RACING", True)))
        eta = float(self.racing_eta if self.racing_eta is not None
                    else getattr(P, "RACING_ETA", 3.0))
        mins = int(self.racing_min_survivors
                   if self.racing_min_survivors is not None
                   else getattr(P, "RACING_MIN_SURVIVORS", 2))
        return bool(enabled), max(eta, 1.0 + 1e-9), max(mins, 1)

    # -- split generation -------------------------------------------------
    def splits(self, y: np.ndarray) -> List[Tuple[np.ndarray, np.ndarray]]:
        raise NotImplementedError

    def _stratified_perm(self, y: np.ndarray, rng) -> np.ndarray:
        """Interleave per-class shuffled indices so every contiguous cut is
        label-balanced (≙ stratifyKFolds, OpCrossValidation.scala:184)."""
        order = []
        for v in np.unique(y):
            idx = np.flatnonzero(y == v)
            rng.shuffle(idx)
            order.append(idx)
        # round-robin interleave
        out = []
        iters = [iter(ix) for ix in order]
        while iters:
            nxt = []
            for it in iters:
                try:
                    out.append(next(it))
                    nxt.append(it)
                except StopIteration:
                    pass
            iters = nxt
        return np.asarray(out, dtype=np.int64)

    def _maybe_mesh(self, n_rows: int, pad: bool = False):
        """Shared data-axis mesh policy (parallel.mesh.maybe_data_mesh).
        ``pad=True`` lets the sweep take the mesh on non-divisible row counts
        (the sweep appends zero-weight pad rows, which is exact for
        ``weighted_pad_exact`` families)."""
        from .parallel.mesh import maybe_data_mesh
        return maybe_data_mesh(n_rows, pad=pad)

    def _record_grid_metrics_batched(self, cand, ci, fitted_grid, X, y_dev,
                                     va_masks_dev, record) -> bool:
        """Score a LINEAR family's whole (fold × grid) block with ONE matmul
        + ONE vmapped metric program + deferred scalars — K per-candidate
        metric dispatches (each a link round trip of queue latency) collapse
        to a single pair.  AUC metrics are rank-invariant, so raw margins
        replace per-model sigmoid scores exactly.  Returns False when the
        family/evaluator has no batched form (caller keeps the per-candidate
        path)."""
        import jax
        import jax.numpy as jnp

        if (self.evaluator is None
                or type(self.evaluator).evaluate_masked_grid
                is OpEvaluatorBase.evaluate_masked_grid):
            return False
        F = len(va_masks_dev)
        G = len(cand.grid)
        kinds = {fitted.get("kind") if isinstance(fitted, dict) else None
                 for row in fitted_grid for fitted in row}
        if kinds <= {"forest", "gbt"}:
            return self._record_tree_grid_metrics(cand, ci, fitted_grid, X,
                                                  y_dev, va_masks_dev, record)
        panel_input = getattr(self.evaluator, "grid_panel_input", "scores")
        multinomial = kinds == {"multinomial"}
        if multinomial and panel_input != "predictions":
            return False    # C margin columns don't collapse to one score
        coefs, intercepts = [], []
        for f in range(F):
            for gi in range(G):
                fitted = fitted_grid[f][gi]
                if not isinstance(fitted, dict) or "coef" not in fitted:
                    return False
                c = fitted["coef"]
                if multinomial:
                    if (fitted.get("kind") != "multinomial"
                            or getattr(c, "ndim", 0) != 2):
                        return False
                elif (fitted.get("kind") not in ("binary", "svc",
                                                 "regression")
                        or getattr(c, "ndim", 1) != 1):
                    return False
                coefs.append(c)
                intercepts.append(fitted.get("intercept", 0.0))
        try:
            from .sparse.matrix import SparseMatrix
            if multinomial:
                # multinomial coef is stored [D, C] (see LinearPredictionModel)
                C3 = jnp.stack([jnp.asarray(c, jnp.float32) for c in coefs])
                B = jnp.stack([jnp.asarray(i, jnp.float32).reshape(-1)
                               for i in intercepts])       # [F*G, C]
                if isinstance(X, SparseMatrix):
                    K_, D_, Cc = C3.shape
                    M = jnp.transpose(C3, (1, 0, 2)).reshape(D_, K_ * Cc)
                    m = (X @ M).reshape(X.shape[0], K_, Cc) + B[None]
                    S = jnp.argmax(m, axis=-1).astype(jnp.int32)
                else:
                    S = _multinomial_pred_grid(X, C3, B)   # [N, F*G] int32
            else:
                C = jnp.stack([jnp.asarray(c, jnp.float32) for c in coefs])
                b = jnp.stack([jnp.asarray(i, jnp.float32).reshape(-1)[0]
                               for i in intercepts])
                if isinstance(X, SparseMatrix):
                    # sparse margins: one sp_matmat over the COO entry
                    # stream — the dense einsum would need the [N, D] matrix
                    # that never materializes on the sparse path
                    S = (X @ C.T) + b[None, :]             # [N, F*G]
                else:
                    S = _grid_margins(X, C, b)             # [N, F*G]
                if panel_input == "predictions":
                    if kinds <= {"binary", "svc"}:
                        # hard class ids: p1 > 0.5  <=>  margin > 0
                        S = (S > 0).astype(jnp.int32)
                    elif kinds != {"regression"}:
                        return False
                    # regression margins ARE the predictions — use as-is
            # the whole (fold × grid) metric panel as ONE program when the
            # evaluator supports it — masks stay [F, N] (no per-grid-point
            # mask HBM duplication in the near-capacity regime), and the F
            # per-fold dispatches + eager S slices collapse into one
            per_fold = None
            try:
                W = (jnp.stack(list(va_masks_dev))
                     if not hasattr(va_masks_dev, "ndim") else va_masks_dev)
                panel = self.evaluator.evaluate_masked_fold_grid(
                    y_dev, S.reshape(S.shape[0], F, G), W)
                if (panel is not None
                        and getattr(panel, "shape", ()) == (F, G)):
                    per_fold = list(panel)
            except Exception as panel_exc:  # noqa: BLE001 — e.g. HBM OOM on
                # the fused [N, F, G] panel; the per-fold loop below needs
                # only 1/F of that score memory at a time, so degrade to it
                # instead of abandoning the batched path entirely
                record_failure(cand.model_name, "degraded", panel_exc,
                               point="selector.fused_panel")
            if per_fold is None:
                # per-fold fallback: one grid-metric program per fold,
                # sharing the fold's single [N] validation mask
                per_fold = []
                for f in range(F):
                    vals = self.evaluator.evaluate_masked_grid(
                        y_dev, S[:, f * G:(f + 1) * G], va_masks_dev[f])
                    if vals is None or getattr(vals, "shape", (0,)) != (G,):
                        return False   # wrong-shape result must not record
                    per_fold.append(vals)
            for f in range(F):
                for gi, params in enumerate(cand.grid):
                    record(cand, ci, gi, params, per_fold[f][gi])
            return True
        except Exception as e:  # noqa: BLE001 — optimization only; fall back
            _log_metric_fallback(cand.model_name, e)
            return False

    def _record_tree_grid_metrics(self, cand, ci, fitted_grid, X, y_dev,
                                  va_masks_dev, record) -> bool:
        """Tree-family analog of the batched linear metrics: within each
        (fold, tree-shape) group, the members' tree stacks concatenate and
        ONE blocked walk produces per-member leaf SUMS — rank-equivalent to
        each candidate's probability (gini leaves sum to 1 per tree) or GBT
        margin (positive affine in the leaf sum), so the AUC metrics match
        the per-candidate path.  Replaces one predict+metric dispatch chain
        per (fold × grid point) with one per (fold × shape group)."""
        from collections import defaultdict

        import jax.numpy as jnp

        from .models.trees import predict_trees_sum_grouped

        F = len(va_masks_dev)
        G = len(cand.grid)
        panel_input = getattr(self.evaluator, "grid_panel_input", "scores")
        groups = defaultdict(list)
        for f in range(F):
            for gi in range(G):
                fitted = fitted_grid[f][gi]
                if not isinstance(fitted, dict) or fitted.get("kind") not in (
                        "forest", "gbt"):
                    return False
                task = fitted.get("task", "classification")
                if task == "regression":
                    if panel_input != "predictions":
                        return False   # scores evaluator on regression leaves
                elif fitted["kind"] == "forest" and fitted.get(
                        "n_classes", 2) != 2 and panel_input != "predictions":
                    return False   # multiclass forest needs a prediction panel
                shp = tuple(np.shape(fitted["feature"]))
                if len(shp) != 2:
                    return False
                groups[(f, fitted["kind"], shp,
                        int(fitted["max_depth"]))].append((gi, fitted))
        try:
            results = {}
            for (f, kind, _shp, md), members in groups.items():
                K = len(members)
                feat = jnp.concatenate(
                    [jnp.asarray(m["feature"]) for _, m in members])
                thr = jnp.concatenate(
                    [jnp.asarray(m["threshold"]) for _, m in members])
                lf = jnp.concatenate(
                    [jnp.asarray(m["is_leaf"]) for _, m in members])
                lv = jnp.concatenate(
                    [jnp.asarray(m["leaf"]) for _, m in members])
                sums = predict_trees_sum_grouped(X, feat, thr, lf, lv,
                                                 md + 1, K)   # [N, K, V]
                task = members[0][1].get("task", "classification")
                if kind == "forest":
                    if task == "regression":
                        # mean leaf value IS the prediction — exact
                        S = sums[..., 0] / float(_shp[0])
                    elif panel_input == "predictions":
                        # argmax of summed per-class leaf mass == argmax of
                        # the normalized mean probs (positive scaling)
                        S = jnp.argmax(sums, axis=-1).astype(jnp.int32)
                    else:
                        S = sums[..., 1]
                else:
                    import jax
                    eta = jnp.asarray([float(m["eta"]) for _, m in members],
                                      jnp.float32)
                    base = jnp.asarray([float(m["base"]) for _, m in members],
                                       jnp.float32)
                    margin = base[None, :] + eta[None, :] * sums[..., 0]
                    if task == "regression":
                        S = margin                  # prediction, exact
                    elif panel_input == "predictions":
                        # sigmoid(margin) > 0.5  <=>  margin > 0
                        S = (margin > 0).astype(jnp.int32)
                    else:
                        # reproduce the per-candidate path's sigmoid(margin)
                        # EXACTLY — raw sums rank identically in exact math,
                        # but f32 sigmoid saturation creates tie groups the
                        # raw sums would not, shifting AUC on confidently-
                        # separated data
                        S = jax.nn.sigmoid(margin)
                vals = self.evaluator.evaluate_masked_grid(
                    y_dev, S, va_masks_dev[f])
                if vals is None or getattr(vals, "shape", (0,)) != (K,):
                    return False
                for j, (gi, _) in enumerate(members):
                    results[(f, gi)] = vals[j]
            for f in range(F):
                for gi, params in enumerate(cand.grid):
                    record(cand, ci, gi, params, results[(f, gi)])
            return True
        except Exception as e:  # noqa: BLE001 — optimization only; fall back
            _log_metric_fallback(cand.model_name, e)
            return False

    # -- main entry -------------------------------------------------------
    def validate(self, candidates: Sequence[ModelCandidate], batch: ColumnBatch,
                 label: str, features: str,
                 in_fold_dag: Optional[List[List[Any]]] = None,
                 splitter: Optional[Splitter] = None) -> ValidationResult:
        """Run the sweep with degrade-to-surviving-mesh recovery: a mid-sweep
        device loss (typed ``DeviceLostError``/``TransferStallError`` or a
        runtime UNAVAILABLE/DEVICE_LOST) shrinks the supervisor's
        surviving-device cap, rebuilds the mesh policy over the survivors
        (``maybe_data_mesh`` consults the cap, re-padding to the new device
        quantum), and re-enters the sweep — which resumes from the
        ``SweepCheckpoint`` candidate boundary, replaying already-scored
        families instead of refitting them.  Bounded by
        TRANSMOGRIFAI_SWEEP_RECOVERIES (0 with ``--no-supervisor``: the
        error propagates unchanged).

        Classified device-memory exhaustion (``is_memory_exhaustion``:
        RESOURCE_EXHAUSTED / allocator messages — deliberately disjoint
        from device loss) takes the OTHER recovery: the deterministic
        shrink ladder (halve streaming chunks → partition the candidate
        grid → collapse the model axis → per-candidate fallback), one rung
        per retry, resuming from the same checkpoint.  Bounded by
        TRANSMOGRIFAI_OOM_RECOVERIES; an exhausted ladder raises typed
        ``MemoryExhaustedError`` with the attempted plan attached."""
        from .parallel import hostgroup as _hostgroup
        from .parallel import memory as _memory
        from .parallel import supervisor as _supervisor
        from .telemetry import span
        # inside a multi-process host group the sweep span carries the rank
        # so merged traces attribute each sweep lane to its host
        _hg_attrs = {}
        if _hostgroup.hostgroup_env_present():
            _hg_attrs = {"hostgroup_rank": _hostgroup.current_rank(),
                         "hostgroup_world": _hostgroup.group_world_size()}
        # the one-per-family fallback warning is scoped to THIS validate:
        # a second train in the same process surfaces its own fallbacks
        _reset_logged_fallbacks()
        from .obsv import BOARD
        attempt = 0
        oom_attempt = 0
        while True:
            self._sweep_attempt = attempt
            self._oom_attempt = oom_attempt
            # control-plane seam: the retry loop is the coarse boundary —
            # /statusz shows which recovery lane the sweep is in
            BOARD.publish(phase="sweep", sweepAttempt=attempt,
                          oomAttempt=oom_attempt,
                          candidateFamilies=len(candidates),
                          gridPoints=sum(len(c.grid) for c in candidates))
            # the RSS watchdog's hard watermark surfaces HERE, on the
            # governed thread, where a typed error can be handled — not as
            # a kernel OOM-kill of an arbitrary victim
            _memory.check_host_pressure()
            try:
                with span("selector.sweep", candidates=len(candidates),
                          validation_type=self.validation_type,
                          grid_points=sum(len(c.grid) for c in candidates),
                          attempt=attempt, oom_attempt=oom_attempt,
                          **_hg_attrs):
                    return self._validate_impl(candidates, batch, label,
                                               features,
                                               in_fold_dag=in_fold_dag,
                                               splitter=splitter)
            except Exception as e:  # noqa: BLE001 — classify, maybe recover
                if _supervisor.is_device_loss(e):
                    if attempt >= _supervisor.max_sweep_recoveries():
                        raise
                    _supervisor.note_sweep_device_loss(e, attempt=attempt,
                                                       stage="validator")
                    attempt += 1
                    continue
                if _memory.is_memory_exhaustion(e):
                    if not _memory.memory_governor_enabled():
                        raise   # --no-memory-governor: propagate unchanged
                    if oom_attempt >= _memory.max_oom_recoveries():
                        raise _memory.as_memory_exhausted(e) from e
                    _memory.note_sweep_memory_exhaustion(
                        e, attempt=oom_attempt, stage="validator")
                    oom_attempt += 1
                    continue
                raise

    def _validate_impl(self, candidates: Sequence[ModelCandidate],
                       batch: ColumnBatch, label: str, features: str,
                       in_fold_dag: Optional[List[List[Any]]] = None,
                       splitter: Optional[Splitter] = None
                       ) -> ValidationResult:
        """Run the CV/TVS grid.

        The fast path (no in-fold DAG) keeps ONE data matrix in HBM and turns
        folds into per-row weight masks, so each candidate family trains its
        whole (fold × grid) block as a single batched XLA program
        (``fit_arrays_grid``) with zero fold-shape recompiles — the TPU
        re-design of the reference's k×Σ|grid| Spark-job fan-out
        (OpValidator.scala:320-349).  ``splitter.validation_prepare_weights``
        applies Balancer/Cutter preparation to each fold's *training* rows
        (scoring stays on the untouched validation slice), matching the
        reference flow.
        """
        import copy

        from .dag import apply_dag, fit_dag

        y_all = np.asarray(batch[label].values, dtype=np.float64)
        splits = self.splits(y_all)

        # -- successive-halving racing plan (ISSUE 4) ----------------------
        # Screen the full grid on fold 0 only, prune to the top 1/eta per
        # family (floored at min_survivors), run the remaining folds for
        # survivors only.  The parity guard keeps any family whose survivor
        # floor covers its whole grid on the exact full-CV path — tiny grids
        # are bit-identical to an unraced sweep.
        racing_on, racing_eta, racing_min_surv = self._racing_config()
        # racing runs on the mesh-sharded path too: round A/B fits are the
        # same batched programs with a fold-sliced weight block, and GSPMD
        # shards them identically — no single-device carve-out needed
        race_path_ok = not in_fold_dag and len(splits) >= 2
        if racing_on and not race_path_ok:
            # the flag is on by default — say WHY this sweep runs unraced
            # instead of silently ignoring it (ISSUE 4 satellite)
            reason = ("in-fold DAG refits feature stages per fold"
                      if in_fold_dag else
                      "single train/validation split (racing needs >= 2 "
                      "folds)")
            record_failure("validator", "degraded",
                           f"racing disabled: {reason}",
                           point="selector.racing",
                           validation_type=self.validation_type)

        def _survivor_count(G: int) -> int:
            return max(racing_min_surv, int(np.ceil(G / racing_eta)))

        raced_flags = [racing_on and race_path_ok
                       and _survivor_count(len(c.grid)) < len(c.grid)
                       for c in candidates]

        def _racing_sig(ci: int) -> Dict[str, Any]:
            if not raced_flags[ci]:
                return {"enabled": False}
            return {"enabled": True, "eta": racing_eta,
                    "minSurvivors": racing_min_surv}

        results: Dict[Tuple[str, int], ValidatedCandidate] = {}
        # device-scalar metrics are recorded lazily and pulled host-side in
        # ONE stacked transfer at the end — a per-candidate float() costs a
        # full host-link round trip each
        deferred: List[Tuple[Any, list]] = []

        # resumable sweep: candidates already completed in the ambient sweep
        # checkpoint replay their scores instead of re-fitting.  Fast path
        # only — the in-fold-DAG path accumulates each candidate's metrics
        # across several fold groups, so a per-family snapshot would persist
        # half-filled metric lists.
        from .checkpoint import (SweepCheckpoint, TrainingPreempted,
                                 active_sweep_checkpoint, shutdown_requested)
        sweep_cp = None if in_fold_dag else active_sweep_checkpoint()
        sweep_sigs: List[str] = []
        replayed: set = set()
        preempted: List[str] = []
        if sweep_cp is not None:
            for ci, cand in enumerate(candidates):
                sig = SweepCheckpoint.candidate_signature(
                    cand.model_name, ci, cand.grid, racing=_racing_sig(ci))
                sweep_sigs.append(sig)
                stored = sweep_cp.results_for(sig)
                if stored is None:
                    continue
                replayed.add(ci)
                for gi, r in enumerate(stored):
                    key = (cand.model_name, ci * 10000 + gi)
                    results[key] = ValidatedCandidate(
                        cand.model_name, dict(r.get("params") or {}),
                        [float(v) for v in (r.get("metricValues") or [])],
                        candidate_index=ci,
                        raced_out=bool(r.get("racedOut", False)))
                record_failure(cand.model_name, "resumed",
                               f"replayed {len(stored)} grid point(s) from "
                               "sweep checkpoint", point="checkpoint.load",
                               candidate_index=ci)
        live = [ci for ci in range(len(candidates)) if ci not in replayed]
        _REPLAYED = object()     # sentinel fitted_grid: scores came from cp
        _PREEMPTED = object()    # sentinel fitted_grid: stop won the boundary

        def record(cand, ci, gi, params, metric):
            key = (cand.model_name, ci * 10000 + gi)
            if key not in results:
                results[key] = ValidatedCandidate(
                    cand.model_name, dict(params), [], candidate_index=ci)
            vals = results[key].metric_values
            if isinstance(metric, jax.Array):
                vals.append(float("nan"))      # patched by the batched pull
                deferred.append((metric, (vals, len(vals) - 1)))
            else:
                vals.append(float(metric))

        def make_model(cand, params, fitted):
            est = cand.estimator
            return est.model_cls(fitted=fitted, **{**est._params, **params})

        def device_metric(cand, params, fitted, X_dev, y_dev, w_dev):
            """Score a candidate entirely on device (see metrics_device);
            None → caller falls back to the host path.  Device scalars are
            returned as-is (defer=True) and pulled in one batch afterwards."""
            try:
                model = make_model(cand, params, fitted)
                if not hasattr(model, "device_scores"):
                    return None
                return self.evaluator.evaluate_masked(
                    y_dev, model.device_scores(X_dev), w_dev, defer=True)
            except Exception:  # noqa: BLE001
                return None

        def host_metric(cand, params, fitted, X_va, y_va):
            try:
                maybe_inject("selector.candidate_metric", key=cand.model_name)
                model = make_model(cand, params, fitted)
                pred = model.predict_arrays(X_va)
                return self.evaluator.evaluate(y_va, pred)
            except Exception as e:  # noqa: BLE001 — candidate robustness
                from .parallel.memory import is_memory_exhaustion
                from .parallel.supervisor import is_device_loss
                if is_device_loss(e) or is_memory_exhaustion(e):
                    raise   # sweep-level recovery, not a NaN score
                record_failure(cand.model_name, "skipped", e,
                               point="selector.candidate_metric",
                               params=dict(params))
                return float("nan")

        # (X, fold splits) groups: shared X across folds normally; per-fold X
        # when feature stages must be refit inside the fold (leakage guard,
        # ≙ OpCrossValidation.validate:87-147 DAG copy+refit).  A generator so
        # only one fold's full-size matrix is resident at a time.
        def _col_values(b):
            """Feature matrix in its native residency: device arrays stay on
            device (the host link is the bottleneck on real TPU hardware);
            sparse matrices pass through — densifying one here is exactly
            the [N, num_hashes] blow-up the representation avoids."""
            v = b[features].values
            if isinstance(v, (jax.Array, SparseMatrix)):
                return v
            return np.asarray(v, dtype=np.float32)

        def fold_groups():
            if not live:
                # every candidate replayed from the sweep checkpoint — no
                # data matrix, fold masks, or device transfers needed
                return
            if in_fold_dag:
                from .telemetry import span as _span
                for f, (tr_idx, va_idx) in enumerate(splits):
                    with _span("selector.fold_fit", fold=f, in_fold_dag=True):
                        dag_copy = [[copy.deepcopy(s) for s in layer]
                                    for layer in in_fold_dag]
                        _, fitted_dag = fit_dag(batch.take_rows(tr_idx),
                                                dag_copy)
                        full = apply_dag(batch, fitted_dag)
                    yield _col_values(full), [(tr_idx, va_idx)]
            else:
                yield _col_values(batch), splits

        import jax
        import jax.numpy as jnp

        from .sparse.matrix import SparseMatrix

        def drain_deferred():
            """Pull every pending device-scalar metric in one stacked
            transfer (falling back to per-metric pulls on failure).  Called
            at the end of the grid, and before each sweep-checkpoint flush —
            a flushed family's metric values must be real numbers, not the
            NaN placeholders the batched pull would patch later."""
            if not deferred:
                return
            try:
                vals = np.asarray(jnp.stack([m for m, _ in deferred]))
            except Exception as e:  # noqa: BLE001 — candidate robustness: one
                # bad candidate's runtime failure must not kill the whole
                # grid; fall back to per-metric pulls (failed ones stay NaN)
                record_failure("validator", "degraded", e,
                               point="selector.metric_pull",
                               fallback="per-metric pulls")
                vals = []
                for m, _ in deferred:
                    try:
                        vals.append(float(m))
                    except Exception as e2:  # noqa: BLE001
                        record_failure("validator", "skipped", e2,
                                       point="selector.metric_pull")
                        vals.append(float("nan"))
            for v, (lst, i) in zip(vals, (slot for _, slot in deferred)):
                lst[i] = float(v)
            deferred.clear()

        def checkpoint_family(ci, cand, fitted_grid):
            """Persist one completed candidate family into the ambient sweep
            checkpoint (atomic flush).  A checkpoint-write failure degrades —
            the sweep's correctness never depends on its durability."""
            entry = []
            for gi in range(len(cand.grid)):
                r = results.get((cand.model_name, ci * 10000 + gi))
                if r is not None:
                    entry.append({"params": r.params,
                                  "metricValues": r.metric_values,
                                  "racedOut": r.raced_out})
            try:
                sweep_cp.record_candidate(
                    sweep_sigs[ci], cand.model_name, ci, entry,
                    fitted_grid=fitted_grid
                    if isinstance(fitted_grid, list) else None)
                sweep_cp.flush()
                from .obsv import BOARD
                BOARD.publish(lastCheckpointFamily=cand.model_name)
            except Exception as e:  # noqa: BLE001
                record_failure(cand.model_name, "degraded", e,
                               point="checkpoint.save",
                               fallback="sweep continues unpersisted")

        # reuse the label column's own buffer so the weakref-keyed transfer
        # cache shares ONE host→device shipment with SanityChecker/evaluate
        y32 = np.asarray(batch[label].values, dtype=np.float32)
        # shape of the fold-weight mask used for the batched fits — the final
        # refit reuses it to hit the SAME compiled executable (shape-keyed)
        self.last_fit_shape = None if in_fold_dag else (len(splits), len(y32))
        self.family_fit_meta = {}
        if not live:
            # fully-replayed sweep: no grid executable was compiled this
            # process, so the winner refit must take the plain fit path
            self.last_fit_shape = None
            self.last_mesh = None
        from .columns import to_device_f32
        from .telemetry import REGISTRY, span
        # zero-weight row padding (mesh divisibility quantum, ladder rungs)
        # is exact only for families that declare it — one non-exact family
        # in the grid keeps the whole shared matrix unpadded
        pad_exact_all = all(getattr(c.estimator, "weighted_pad_exact", False)
                            for c in candidates)
        for X, fsplits in fold_groups():
            is_sparse = isinstance(X, SparseMatrix)
            N = X.shape[0]
            # one device data plane (ISSUE 19): sparse matrices shard over
            # the mesh 'data' axis like dense ones — entries sort by row,
            # partition at device row boundaries, pad to a common per-device
            # nnz rung (DeviceTable).  Global row_ids let GSPMD insert the
            # collectives; the segment-sum fitters tolerate the zero pads
            # exactly (value 0.0 addends at an in-range row).
            # everything the sweep lays over the devices, under one span: the
            # matrix, the label, the fold assignment and the masks
            with span("selector.place") as place:
                mesh = self._maybe_mesh(N, pad=pad_exact_all)
                self.last_mesh = mesh
                if (mesh is None and not pad_exact_all
                        and self._maybe_mesh(N, pad=True) is not None):
                    # honest degrade: the mesh WAS viable (pad-divisible) but a
                    # mixed grid (some family not weighted_pad_exact) pinned
                    # the matrix unpadded and indivisible — record it so bench
                    # aux and operators see single-device as a degrade, not a
                    # choice
                    record_failure(
                        "sweep", "degraded",
                        RuntimeError(
                            f"N={N} indivisible and grid mixes non-pad-exact "
                            f"families: sweep falls back to single device"),
                        point="selector.mesh", fallback="single_device")
                    REGISTRY.counter("selector.mesh_degraded").inc()
                from .parallel import (data_axis_size, data_sharding,
                                       pad_rows_for, stream_to_device)
                from .parallel import memory as _mem
                _plan_chunk = None   # preflight-chosen streaming chunk bytes
                N_fit = N
                relayout_bytes = 0   # moved on the device to suit the mesh
                if mesh is not None:
                    # multi-device: row-shard the matrix over the mesh 'data'
                    # axis and let GSPMD insert the collectives inside every
                    # batched fit/metric program (SURVEY §2.6 P1/P3 on the REAL
                    # path). Row count pads up to the device-divisible quantum
                    # — and, with the compile cache on, up to the fit-shape
                    # ladder rung — with zero-weight rows; one padded matrix
                    # serves every family (all are weighted_pad_exact whenever
                    # N_fit > N).
                    extent = data_axis_size(mesh)
                    N_fit = N + pad_rows_for(N, mesh)
                    if _fit_padding_enabled() and pad_exact_all:
                        rung = _fit_pad_rows(N)
                        N_fit = max(N_fit, -(-rung // extent) * extent)
                    if N_fit > N and not pad_exact_all:
                        N_fit = N   # divisible N, mixed families: no ladder pad
                    if _mem.memory_governor_enabled():
                        # preflight (ISSUE 15): estimate the padded-rung ×
                        # dtype × grid-width × fold-panel footprint against the
                        # per-device budget and choose chunk bytes (and grid
                        # partitioning, read back by the fit bodies) BEFORE the
                        # first transfer — the 11M-row regime stops discovering
                        # OOM by dying in batched_device_put
                        plan = _mem.plan_sweep_memory(
                            rows=N_fit,
                            cols=(int(X.shape[1])
                                  if is_sparse or getattr(X, "ndim", 1) == 2
                                  else 1),
                            folds=len(fsplits),
                            grid_width=max((len(c.grid) for c in candidates),
                                           default=1),
                            devices=int(mesh.devices.size),
                            # a device-resident matrix stays as it is stored
                            dtype_bytes=(int(X.dtype.itemsize)
                                         if isinstance(X, jax.Array) else 4),
                            nnz=int(X.nnz) if is_sparse else None)
                        _plan_chunk = plan.chunk_bytes
                    if is_sparse:
                        # COO entries stream by nnz range under the same chunk
                        # budget (DeviceTable dispatch inside
                        # stream_to_device); empty pad rows own no entries, so
                        # the nnz-rung pads are the only on-device synthesis
                        X = stream_to_device(X, mesh, pad_to=N_fit,
                                             chunk_bytes=_plan_chunk)
                    elif isinstance(X, jax.Array):
                        # already device-resident (the fused transform's
                        # output): kept in the dtype it is stored in, as on
                        # one device — a bfloat16 matrix stays bfloat16 and
                        # the fit programs accumulate in float32; a float32
                        # copy would double the bytes a chip holds and reads.
                        # What a cast, a pad or a change of layout moves on
                        # the device is counted (mesh.relayout_bytes)
                        want = data_sharding(mesh, 2)
                        Xj = X
                        if X.dtype not in (jnp.float32, jnp.bfloat16):
                            Xj = X.astype(jnp.float32)
                        if N_fit > N:
                            Xj = jnp.pad(Xj, ((0, N_fit - N), (0, 0)))
                        if Xj is not X or not X.sharding.is_equivalent_to(
                                want, X.ndim):
                            relayout_bytes = int(Xj.nbytes)
                        X = jax.device_put(Xj, want)
                    else:
                        # chunked host→device streaming: assemble each device's
                        # row shard from bounded host slices so peak staging is
                        # O(TRANSMOGRIFAI_DEVICE_CHUNK_BYTES), not O(dataset) —
                        # the one-shot device_put staged the whole matrix
                        X = stream_to_device(np.asarray(X, dtype=np.float32),
                                             mesh, pad_to=N_fit,
                                             chunk_bytes=_plan_chunk)
                    if N_fit > N and not is_sparse:
                        # tree families quantile-bin over the true rows only —
                        # keeps padded split points identical to unpadded ones
                        # (sparse grids are linear-only: no binning to protect)
                        from .models.trees import register_real_rows
                        register_real_rows(X, N)
                elif not isinstance(X, jax.Array) and not is_sparse:
                    # ONE host→device transfer shared by every candidate family
                    # — the host link is the scarce resource
                    X = to_device_f32(X)
                is_dev = isinstance(X, jax.Array) or is_sparse
                y_dev = None
                if is_dev:
                    # exact wire (bf16 only when verified lossless), shared
                    # with every other consumer of the same label buffer
                    y_dev = (stream_to_device(y32, mesh, pad_to=N_fit,
                                              chunk_bytes=_plan_chunk)
                             if mesh is not None else
                             to_device_f32(y32, exact=True))
                X_host = None if is_dev else X   # lazy d2h only if a fallback needs it
                va_slices = [va for _, va in fsplits]
                va_masks_dev = []
                assign = np.full(N_fit, _NO_FOLD, np.uint8)
                if N_fit > N:
                    assign[N:] = _PAD_FOLD   # pad rows join NO fold, ever
                for f, (_, va_idx) in enumerate(fsplits):
                    assign[va_idx] = f
                # dense per-fold weight rows only materialize when a splitter
                # may modify them (or the host path needs them below)
                W_rows = []
                neutral = splitter is None or (
                    type(splitter).validation_prepare_weights
                    is Splitter.validation_prepare_weights)
                if not neutral or not (is_dev and len(fsplits) < _PAD_FOLD):
                    neutral = True
                    for f, (tr_idx, _) in enumerate(fsplits):
                        w = np.zeros(N, np.float32)
                        w[tr_idx] = 1.0
                        if splitter is not None:
                            w2 = splitter.validation_prepare_weights(y_all, w)
                            neutral = neutral and w2 is w
                            w = w2
                        W_rows.append(w)
                if is_dev and neutral and len(fsplits) < _PAD_FOLD:
                    # fold masks from ONE [N] uint8 assignment shipped over the
                    # link — 1 byte/row instead of (folds+1)×4 bytes/row of
                    # train + validation masks.  On the mesh the assignment is
                    # row-sharded first so the [F, N] masks materialize
                    # directly with the fit programs' expected sharding.
                    aj = jnp.asarray(assign)
                    if mesh is not None:
                        aj = jax.device_put(aj, data_sharding(mesh, 1))
                    Wd, VAd = _fold_masks_from_assignment(aj, len(fsplits))
                    W = Wd
                    va_masks_dev = [VAd[f] for f in range(len(fsplits))]
                else:
                    W = np.stack(W_rows)
                    if is_dev:
                        for va_idx in va_slices:
                            vm = np.zeros(N, np.float32)
                            vm[va_idx] = 1.0
                            if mesh is not None:
                                # pad tail streams in as zeros — never
                                # validated
                                vmj = stream_to_device(vm, mesh, pad_to=N_fit,
                                                       chunk_bytes=_plan_chunk)
                            else:
                                vmj = to_device_f32(vm)  # 0/1 mask: bf16 exact
                            va_masks_dev.append(vmj)
                    if mesh is not None:
                        W = stream_to_device(W, mesh, row_axis=1, pad_to=N_fit,
                                             chunk_bytes=_plan_chunk)
                    else:
                        # one shared transfer; family fits see a no-op
                        # conversion. exact=True: bf16 wire only when verified
                        # lossless (0/1 fold masks; balancer keep/drop weights)
                        # — custom splitters may emit arbitrary weights, which
                        # go exact f32
                        W = to_device_f32(W, exact=True)
                n_dev = 1 if mesh is None else int(mesh.devices.size)
                REGISTRY.gauge("mesh.devices").set(n_dev)
                if mesh is not None:
                    REGISTRY.counter("mesh.relayout_bytes").inc(relayout_bytes)
                if place is not None:
                    place.attrs.update(
                        rows=int(N), pad_rows=int(N_fit - N), devices=n_dev,
                        dtype=str(getattr(X, "dtype", "")),
                        relayout_bytes=relayout_bytes,
                        bytes_placed=sum(
                            int(getattr(a, "nbytes", 0))
                            for a in (X, y_dev, W, *va_masks_dev)))
            # fit-shape canonicalization (ISSUE 4 compile reuse): one shared
            # zero-weight-row-padded copy of (X, y) serves every pad-exact
            # family, so nearby row counts land on the same ladder rung and
            # hit the persistent compile cache.  The mesh path already folded
            # its ladder rung into N_fit during streaming, so this separate
            # padded copy exists only off-mesh.
            pad_rows = 0
            X_pad = y_pad = None
            if (_fit_padding_enabled() and mesh is None
                    and any(getattr(c.estimator, "weighted_pad_exact", False)
                            for c in candidates)):
                pad_rows = _fit_pad_rows(N) - N
            if pad_rows:
                if is_sparse:
                    # empty rows own no COO entries and carry weight 0 —
                    # exact for the weight-normalized sparse fitters
                    X_pad = X.pad_rows(N + pad_rows)
                    y_pad = jnp.pad(y_dev, (0, pad_rows))
                elif is_dev:
                    X_pad = jnp.pad(X, ((0, pad_rows), (0, 0)))
                    y_pad = jnp.pad(y_dev, (0, pad_rows))
                else:
                    X_pad = np.pad(X, ((0, pad_rows), (0, 0)))
                    y_pad = np.pad(y32, (0, pad_rows))
                if not is_sparse:
                    # tree families quantile-bin over the true rows only —
                    # keeps padded split points identical to unpadded ones
                    from .models.trees import register_real_rows
                    register_real_rows(X_pad, N)

            def _pad_weight_cols(Wblk):
                if isinstance(Wblk, np.ndarray):
                    return np.pad(Wblk, ((0, 0), (0, pad_rows)))
                return jnp.pad(Wblk, ((0, 0), (0, pad_rows)))

            # concurrent pre-trace (aot.py): lower+compile each supporting
            # family's grid programs on a background thread NOW, so by the
            # time the fit pool below reaches them the persistent compile
            # cache already holds the executables and
            # new_compiles_during_train collapses into overlapped wall time.
            # Compile-only — sweep winners are bitwise unaffected.
            from .aot import pretrace_enabled, pretrace_submit
            if pretrace_enabled():
                for ci, cand in enumerate(candidates):
                    if (ci in replayed or not getattr(
                            cand.estimator, "supports_pretrace", False)):
                        continue
                    use_pad = bool(pad_rows) and getattr(
                        cand.estimator, "weighted_pad_exact", False)
                    Xf = X_pad if use_pad else X
                    yf = (y_pad if use_pad
                          else y_dev if y_dev is not None else y32)

                    def _submit(Wblk, grid, est=cand.estimator, Xf=Xf,
                                yf=yf, name=cand.model_name):
                        Wf = _pad_weight_cols(Wblk) if use_pad else Wblk
                        pretrace_submit(
                            name, lambda: est.pretrace_arrays_grid(
                                Xf, yf, Wf, grid))
                    if raced_flags[ci]:
                        # round A (full grid, fold 0) is certain; round B's
                        # survivor subset is data-dependent — pre-trace a
                        # same-sized prefix as a best-effort shape/static
                        # match (a miss just forfeits the overlap)
                        _submit(W[:1], cand.grid)
                        _submit(W, cand.grid[:_survivor_count(
                            len(cand.grid))])
                    else:
                        _submit(W, cand.grid)

            # control-plane progress: candidate-fit boundaries feed the
            # /statusz board (current family + grid point) and the per-unit
            # EWMA behind its ETA.  _fits_left is per round (A, then B).
            _fits_left = [0]

            def fit_candidate(cand, Wblk, grid):
                # per-candidate trace span: worker threads have no span of
                # their own, so this parents under the orchestrating
                # selector.sweep span even through the thread pool
                import time as _time

                from .obsv import BOARD
                from .telemetry import span as _span
                BOARD.publish(candidate=cand.model_name,
                              candidateGrid=len(grid),
                              candidateFolds=int(len(Wblk)))
                t0 = _time.perf_counter()
                with _span("selector.candidate_fit", model=cand.model_name,
                           grid=len(grid), folds=int(len(Wblk))):
                    out = _fit_candidate_body(cand, Wblk, grid)
                _fits_left[0] = max(0, _fits_left[0] - 1)
                BOARD.note_unit(_time.perf_counter() - t0,
                                remaining_units=_fits_left[0])
                return out

            def _fit_candidate_body(cand, Wblk, grid):
                from .parallel import memory as _memq
                from .telemetry import span as _span
                use_pad = bool(pad_rows) and getattr(
                    cand.estimator, "weighted_pad_exact", False)
                Xf = X_pad if use_pad else X
                yf = (y_pad if use_pad
                      else y_dev if y_dev is not None else y32)
                Wf = _pad_weight_cols(Wblk) if use_pad else Wblk
                try:
                    maybe_inject("selector.candidate_fit", key=cand.model_name)
                    # chaos seam for mid-sweep device loss during a fit; the
                    # key carries the sweep attempt so the post-recovery
                    # retry is not re-killed by a sticky injector decision
                    maybe_inject(
                        "supervisor.device_loss",
                        key=f"{cand.model_name}:fit:"
                            f"a{getattr(self, '_sweep_attempt', 0)}")
                    # chaos seam for a mid-sweep allocator OOM; keyed by the
                    # memory-ladder attempt for the same reason — the
                    # shrunken retry must not be re-killed
                    maybe_inject(
                        "memory.device_oom",
                        key=f"{cand.model_name}:fit:"
                            f"o{getattr(self, '_oom_attempt', 0)}")
                    if _memq.per_candidate_fallback():
                        # memory ladder's last rung: no batched grid program
                        # at all — the per-(fold, point) working set is the
                        # smallest the sweep can make
                        raise MemoryError(
                            "memory ladder: per-candidate fallback")
                    parts = _memq.grid_partitions()
                    if parts > 1 and len(grid) > 1:
                        # memory ladder rung 2+ (or the preflight plan):
                        # split the batched (fold × grid) program into grid
                        # sub-batches so each program's lane working set
                        # shrinks with the partition count
                        sub = -(-len(grid) // min(parts, len(grid)))
                        outs = [cand.estimator.fit_arrays_grid(
                                    Xf, yf, Wf, grid[i:i + sub])
                                for i in range(0, len(grid), sub)]
                        out = [[fit for o in outs for fit in o[f]]
                               for f in range(len(outs[0]))]
                    else:
                        out = cand.estimator.fit_arrays_grid(Xf, yf, Wf,
                                                             grid)
                    self.family_fit_meta[cand.model_name] = {
                        "folds": len(out), "rows": int(Xf.shape[0]),
                        "real_rows": int(N), "lanes": len(grid),
                        # ladder copy OR mesh-streamed quantum/rung padding
                        "padded": int(Xf.shape[0]) > int(N)}
                    return out
                except Exception as e:  # noqa: BLE001
                    # a lost device is NOT a bad candidate: per-point refits
                    # on a dead mesh would fail K×|grid| more times — let the
                    # sweep-level recovery rebuild the surviving mesh instead
                    from .parallel.supervisor import is_device_loss
                    if is_device_loss(e):
                        raise
                    # allocator exhaustion is not a bad candidate either —
                    # unless the ladder already reached its last rung, where
                    # per-point refits ARE the recovery
                    if (_memq.is_memory_exhaustion(e)
                            and not _memq.per_candidate_fallback()):
                        raise
                    # batched fit failed as a block — retry per point so one
                    # bad candidate can't take down the family (≙ Try-wrapped
                    # fits in OpValidator.getSummary).  Per-point refits run
                    # unpadded: exactness beats executable reuse on a path
                    # that is already degraded.
                    record_failure(cand.model_name, "degraded", e,
                                   point="selector.candidate_fit",
                                   fallback="per-point refits")
                    self.family_fit_meta.pop(cand.model_name, None)
                    fitted_grid = []
                    for f in range(len(Wblk)):
                        with _span("selector.fold_fit",
                                   model=cand.model_name, fold=f,
                                   degraded=True):
                            row = []
                            for gi, params in enumerate(grid):
                                try:
                                    maybe_inject("selector.candidate_fit",
                                                 key=cand.model_name)
                                    est = copy.deepcopy(cand.estimator)
                                    for k, v in params.items():
                                        est.set(k, v)
                                    # mesh path: X carries streamed pad rows,
                                    # so pair it with the matching padded
                                    # sharded label/weight vectors
                                    yfb = y_dev if mesh is not None else y32
                                    row.append(est.fit_arrays(
                                        X, yfb, sample_weight=Wblk[f]))
                                except Exception as e2:  # noqa: BLE001
                                    if is_device_loss(e2):
                                        raise
                                    record_failure(
                                        cand.model_name, "skipped", e2,
                                        point="selector.candidate_fit",
                                        fold=f, grid_index=gi)
                                    row.append(None)
                        fitted_grid.append(row)
                    return fitted_grid

            # candidate families fit concurrently on a thread pool (≙ the
            # reference's Futures fan-out, OpValidator.scala:320-349 +
            # `parallelism` :106).  Device execution serializes on the TPU
            # stream; the win is overlapping the XLA *compiles* of the
            # per-family batched programs, which dominate first-run wall.
            # At very large N the families' HBM working sets no longer fit
            # side by side (each TREE family budgets ~6 GiB of one-hot
            # space) — fit sequentially so peak = max, not sum.  Grids with
            # no HBM-heavy family keep the compile-overlap pool at any N.
            import os as _os

            def fit_or_skip(icand):
                """Candidate boundary: replay beats fit, and a requested
                graceful stop (signal or injected preemption) wins over
                starting new work."""
                ci, cand = icand
                if ci in replayed:
                    return _REPLAYED
                if shutdown_requested(key=cand.model_name):
                    preempted.append(cand.model_name)
                    return _PREEMPTED
                if raced_flags[ci]:
                    # successive-halving round A: full grid, fold 0 only
                    return fit_candidate(cand, W[:1], cand.grid)
                return fit_candidate(cand, W, cand.grid)

            serial_rows = int(_os.environ.get(
                "TRANSMOGRIFAI_SERIAL_FIT_ROWS", 4_000_000))
            n_workers = min(self.parallelism, len(candidates))
            if N >= serial_rows and any(
                    getattr(c.estimator, "hbm_heavy", False)
                    for c in candidates):
                n_workers = 1
            indexed = list(enumerate(candidates))
            _fits_left[0] = len(indexed)
            from .obsv import BOARD
            BOARD.publish(round="A", fitsQueued=len(indexed))
            if n_workers > 1:
                from concurrent.futures import ThreadPoolExecutor
                with ThreadPoolExecutor(max_workers=n_workers) as pool:
                    fitted_grids = list(pool.map(fit_or_skip, indexed))
            else:
                fitted_grids = [fit_or_skip(ic) for ic in indexed]

            va_cache: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}

            def va_slice(f, va_idx):
                """Pulled validation slice, cached per FOLD so every
                fallback candidate shares one transfer."""
                if f not in va_cache:
                    nonlocal X_host
                    if is_sparse:
                        # the slice STAYS sparse: sparse-capable models
                        # consume the COO stream in predict_arrays; models
                        # without a sparse path fail loudly (__array__
                        # raises) and the resilience layer skips them
                        xv = X.take_rows(np.asarray(va_idx))
                    elif is_dev:
                        # gather ONLY the validation slice on device, then
                        # pull — the full matrix is folds-times bigger and
                        # the link is the bottleneck.  Cast bf16-stored
                        # matrices to f32 on device first: numpy kernels on
                        # ml_dtypes bf16 are limited/slow on host
                        xv = np.asarray(jnp.take(
                            X, jnp.asarray(va_idx), axis=0
                        ).astype(jnp.float32))
                    else:
                        if X_host is None:
                            X_host = np.asarray(X)
                        xv = X_host[va_idx]
                    va_cache[f] = (xv, y32[va_idx])
                return va_cache[f]

            def score_block(cand, ci, fitted_grid, fold_offset, n_folds,
                            rec):
                """Score a fitted (n_folds × grid) block against validation
                folds [fold_offset, fold_offset + n_folds) — batched fast
                path first, device/host per-candidate fallback otherwise.
                ``rec`` lets racing remap a survivor sub-grid's local
                indices back to the family's full grid."""
                BOARD.publish(scoring=cand.model_name,
                              foldOffset=fold_offset, foldCount=n_folds)
                # chaos seam: a device lost between fitting and scoring —
                # fires AFTER earlier families checkpointed, so the recovery
                # sweep demonstrably replays them from the SweepCheckpoint
                maybe_inject(
                    "supervisor.device_loss",
                    key=f"{cand.model_name}:score:"
                        f"a{getattr(self, '_sweep_attempt', 0)}")
                maybe_inject(
                    "memory.device_oom",
                    key=f"{cand.model_name}:score:"
                        f"o{getattr(self, '_oom_attempt', 0)}")
                masks = va_masks_dev[fold_offset:fold_offset + n_folds]
                if (is_dev and self._record_grid_metrics_batched(
                        cand, ci, fitted_grid, X, y_dev, masks, rec)):
                    return
                for f_local in range(n_folds):
                    f = fold_offset + f_local
                    va_idx = va_slices[f]
                    for gi, params in enumerate(cand.grid):
                        fitted = fitted_grid[f_local][gi]
                        if fitted is None:
                            rec(cand, ci, gi, params, float("nan"))
                            continue
                        metric = None
                        if is_dev:
                            metric = device_metric(cand, params, fitted,
                                                   X, y_dev,
                                                   va_masks_dev[f])
                        if metric is None:
                            metric = host_metric(cand, params, fitted,
                                                 *va_slice(f, va_idx))
                        rec(cand, ci, gi, params, metric)

            # round A: raced families score their fold-0 screen; unraced
            # families score (and checkpoint) their full CV block exactly
            # as an unraced sweep would
            for ci, cand in enumerate(candidates):
                fitted_grid = fitted_grids[ci]
                if fitted_grid is _REPLAYED or fitted_grid is _PREEMPTED:
                    continue
                if raced_flags[ci]:
                    score_block(cand, ci, fitted_grid, 0, 1, record)
                    continue
                score_block(cand, ci, fitted_grid, 0, len(fsplits), record)
                if sweep_cp is not None:
                    drain_deferred()
                    checkpoint_family(ci, cand, fitted_grid)

            # round B: rank each raced family's fold-0 screen in the
            # evaluator's direction, prune past the survivor floor, then fit
            # + score ONLY the survivors on the remaining folds — the
            # (folds-1) × (grid - survivors) fits never run
            race_live = [ci for ci in range(len(candidates))
                         if raced_flags[ci]
                         and fitted_grids[ci] is not _REPLAYED
                         and fitted_grids[ci] is not _PREEMPTED]
            if race_live:
                drain_deferred()   # ranking needs numbers, not deferred slots
                sign = 1.0 if self.evaluator.is_larger_better else -1.0
                _raced_out: Dict[str, int] = {}

                def prune(ci, cand):
                    G = len(cand.grid)
                    S = _survivor_count(G)

                    def keyf(gi):
                        r = results.get((cand.model_name, ci * 10000 + gi))
                        v = (r.metric_values[0]
                             if r and r.metric_values else float("nan"))
                        return sign * v if np.isfinite(v) else -np.inf

                    # deterministic: ties and NaNs break by grid position
                    order = sorted(range(G), key=lambda gi: (-keyf(gi), gi))
                    for gi in order[S:]:
                        r = results.get((cand.model_name, ci * 10000 + gi))
                        if r is not None:
                            r.raced_out = True
                    from .telemetry import event as _event
                    _event("selector.racing.prune", model=cand.model_name,
                           grid=G, survivors=S, pruned=G - S)
                    _raced_out[cand.model_name] = G - S
                    BOARD.publish(racedOut=dict(_raced_out))
                    return sorted(order[:S])

                survivors_by_ci = {ci: prune(ci, candidates[ci])
                                   for ci in race_live}

                def sub_candidate(ci):
                    cand = candidates[ci]
                    return ModelCandidate(
                        cand.estimator,
                        [dict(cand.grid[g]) for g in survivors_by_ci[ci]],
                        cand.model_name)

                def fit_survivors(ci):
                    cand = candidates[ci]
                    if shutdown_requested(key=cand.model_name):
                        preempted.append(cand.model_name)
                        return _PREEMPTED
                    sub = sub_candidate(ci)
                    return fit_candidate(sub, W[1:], sub.grid)

                _fits_left[0] = len(race_live)
                BOARD.publish(round="B", fitsQueued=len(race_live))
                if n_workers > 1 and len(race_live) > 1:
                    from concurrent.futures import ThreadPoolExecutor
                    with ThreadPoolExecutor(
                            max_workers=min(n_workers,
                                            len(race_live))) as pool:
                        fitted_b = list(pool.map(fit_survivors, race_live))
                else:
                    fitted_b = [fit_survivors(ci) for ci in race_live]

                from .profiling import record_racing
                rest = len(fsplits) - 1
                for ci, fb in zip(race_live, fitted_b):
                    cand = candidates[ci]
                    if fb is _PREEMPTED:
                        continue
                    survivors = survivors_by_ci[ci]

                    def rec(_c, _ci, gi_local, params, metric,
                            _map=survivors, _cand=cand, _i=ci):
                        record(_cand, _i, _map[gi_local], params, metric)

                    score_block(sub_candidate(ci), ci, fb, 1, rest, rec)
                    record_racing(rest * (len(cand.grid) - len(survivors)),
                                  len(cand.grid) - len(survivors))
                    if sweep_cp is not None:
                        drain_deferred()
                        checkpoint_family(ci, cand, None)

        if preempted:
            # graceful stop honored at a candidate boundary: everything
            # completed so far is drained + flushed (per family, above);
            # hand the caller the resume point instead of dying mid-write
            drain_deferred()
            raise TrainingPreempted(
                "selector sweep stopped before candidate(s) "
                + ", ".join(sorted(set(preempted))),
                resume_from=sweep_cp.path if sweep_cp is not None else None)

        drain_deferred()   # ONE pull for every device-scalar metric left

        all_results = list(results.values())
        sign = 1.0 if self.evaluator.is_larger_better else -1.0
        # raced-out points carry a fold-0 screen mean only; comparing that
        # against survivors' full-k-fold means would be apples-to-oranges,
        # so they are excluded from winner selection (kept in all_results
        # for the summary). If racing somehow pruned everything that
        # finished, fall back to the full list rather than fail the sweep.
        scored = [(sign * r.mean_metric, r) for r in all_results
                  if np.isfinite(r.mean_metric) and not r.raced_out]
        if not scored:
            scored = [(sign * r.mean_metric, r) for r in all_results
                      if np.isfinite(r.mean_metric)]
        if not scored:
            # aggregate error with per-candidate causes from the failure log
            # — "nothing survived" alone is undebuggable at 3am
            causes: Dict[str, str] = {}
            for ev in active_failure_log().events:
                if ev.point.startswith("selector.") and ev.cause:
                    causes.setdefault(ev.stage, ev.cause)
            for cand in candidates:
                causes.setdefault(cand.model_name,
                                  "no finite validation metric")
            raise AllCandidatesFailed(
                "all model candidates failed validation", causes)
        best_score, best_res = max(scored, key=lambda t: t[0])
        best_cand = candidates[best_res.candidate_index]
        import copy as _c
        best_est = _c.deepcopy(best_cand.estimator)
        for k, v in best_res.params.items():
            best_est.set(k, v)
        return ValidationResult(
            best=ModelCandidate(best_est, [dict(best_res.params)], best_res.model_name),
            best_params=dict(best_res.params),
            best_metric=best_res.mean_metric,
            all_results=all_results,
            validation_type=self.validation_type,
            metric_name=self.evaluator.default_metric,
            is_larger_better=self.evaluator.is_larger_better)


class OpCrossValidation(OpValidator):
    """k-fold CV (≙ OpCrossValidation.scala:42); default 3 folds."""

    validation_type = "CrossValidation"

    def __init__(self, num_folds: int = 3, evaluator: Optional[OpEvaluatorBase] = None,
                 seed: int = 42, stratify: bool = False, parallelism: int = 8,
                 **kw):
        super().__init__(evaluator, seed, stratify, parallelism, **kw)
        self.num_folds = int(num_folds)

    def splits(self, y: np.ndarray):
        n = len(y)
        rng = np.random.default_rng(self.seed)
        perm = self._stratified_perm(y, rng) if self.stratify else rng.permutation(n)
        folds = np.array_split(perm, self.num_folds)
        out = []
        for i in range(self.num_folds):
            va = folds[i]
            tr = np.concatenate([folds[j] for j in range(self.num_folds) if j != i])
            out.append((tr, va))
        return out


class OpTrainValidationSplit(OpValidator):
    """single split (≙ OpTrainValidationSplit); default 75/25."""

    validation_type = "TrainValidationSplit"

    def __init__(self, train_ratio: float = 0.75,
                 evaluator: Optional[OpEvaluatorBase] = None, seed: int = 42,
                 stratify: bool = False, parallelism: int = 8, **kw):
        super().__init__(evaluator, seed, stratify, parallelism, **kw)
        self.train_ratio = float(train_ratio)

    def splits(self, y: np.ndarray):
        n = len(y)
        rng = np.random.default_rng(self.seed)
        perm = self._stratified_perm(y, rng) if self.stratify else rng.permutation(n)
        n_tr = int(round(n * self.train_ratio))
        return [(perm[:n_tr], perm[n_tr:])]

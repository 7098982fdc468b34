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

import copy
import logging
import time
from collections import defaultdict
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field, replace
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .aot import pretrace_enabled, pretrace_submit
from .checkpoint import (SweepCheckpoint, TrainingPreempted,
                         active_sweep_checkpoint, shutdown_requested)
from .columns import ColumnBatch, to_device_f32
from .dag import apply_dag, fit_dag
from .evaluators import OpEvaluatorBase
from .models.trees import predict_trees_sum_grouped, register_real_rows
from .obsv import BOARD
from .parallel import (data_sharding, hostgroup, maybe_data_mesh, memory,
                       pad_rows_for, stream_to_device, supervisor)
from .profiling import record_racing
from .resilience import (AllCandidatesFailed, active_failure_log,
                         maybe_inject, record_failure)
from .sparse.matrix import SparseMatrix
from .telemetry import event, span

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
        @jax.jit
        def fn(X, C3, B):
            m = jnp.einsum("nd,kdc->nkc", X, C3,
                           preferred_element_type=jnp.float32) + B[None]
            return jnp.argmax(m, axis=-1).astype(jnp.int32)
        _MULTI_PRED_JIT = fn
    return _MULTI_PRED_JIT(X, C3, B)


_FOLD_MASK_FNS: Dict[int, Any] = {}

# uint8 fold-assignment sentinels: 255 = "in no validation fold" (a TVS row
# outside the held-out slice — it trains in every fold), 254 = "zero-weight
# pad row" (the mesh's device-divisibility quantum — it belongs to NO fold,
# training or validation)
_NO_FOLD = 255
_PAD_FOLD = 254


def _fold_masks_from_assignment(assign, n_folds: int):
    """[N] uint8 validation-fold assignment → (train weights [F, N],
    validation masks [F, N]) built ON DEVICE: the host link carries one
    byte per row instead of the materialized masks.  A sharded assignment
    propagates its row sharding into the masks (axis 1), so the mesh path
    never materializes [F, N] weights on the host."""
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
    # how the sweep's last fold group lay on the devices (arrays dropped) and,
    # per family, the folds and lanes of its last batched fit: the winner's
    # refit asks the placement for arrays of exactly that layout and so runs
    # the program the sweep compiled
    placement: Optional["Placement"] = None
    fit_meta: Dict[str, Dict[str, int]] = field(default_factory=dict)


# --------------------------------------------------------------------------
# the sweep's stages: plan -> place -> fit -> score
# --------------------------------------------------------------------------

# From this many rows on, a grid with an ``hbm_heavy`` family fits its
# families one after another: side by side their HBM working sets (each TREE
# family budgets ~6 GiB of one-hot space) no longer fit, and sequential fits
# make the peak the max, not the sum.
_SERIAL_FROM_ROWS = 4_000_000

_REPLAYED = object()     # in place of a fitted grid: scores came from the cp
_PREEMPTED = object()    # in place of a fitted grid: a stop won the boundary


def _survivor_count(G: int, eta: float, min_survivors: int) -> int:
    return max(min_survivors, int(np.ceil(G / eta)))


@dataclass(frozen=True)
class SweepPlan:
    """What one attempt of ``validate`` decides before it touches the matrix.

    Racing (successive halving): the full grid is screened on fold 0 only,
    each family is pruned to ``survivor_count(G)`` and the remaining folds run
    for survivors only.  A family whose survivor floor covers its whole grid
    is not raced — tiny grids are bit-identical to an unraced sweep.  Racing
    runs on the mesh too: rounds A and B are the same batched programs with a
    fold-sliced weight block, and GSPMD shards them identically.

    Replay: families the ambient sweep checkpoint holds under an unchanged
    signature (model, position, grid, racing configuration) are not fitted
    again.  Fast path only — the in-fold-DAG path accumulates a candidate's
    metrics over several fold groups, so a per-family snapshot would persist
    half-filled metric lists."""
    splits: List[Tuple[np.ndarray, np.ndarray]]
    in_fold_dag: Optional[List[List[Any]]]
    racing_eta: float
    racing_min_survivors: int
    raced_flags: Tuple[bool, ...]
    # families fit concurrently on a thread pool (≙ the reference's Futures
    # fan-out, OpValidator.scala:320-349 + `parallelism` :106).  Device
    # execution serializes on the TPU stream; the win is overlapping the XLA
    # *compiles* of the per-family batched programs
    n_workers: int
    checkpoint: Optional[SweepCheckpoint]
    signatures: Tuple[str, ...]               # () without a checkpoint
    replayed: Dict[int, List[Dict[str, Any]]]  # candidate index -> stored
    # the recovery attempts key the chaos seams, so that a retry is not
    # killed again by a sticky injector decision
    attempt: int = 0
    oom_attempt: int = 0

    def survivor_count(self, G: int) -> int:
        return _survivor_count(G, self.racing_eta, self.racing_min_survivors)


def plan_sweep(validator: "OpValidator", candidates: Sequence[ModelCandidate],
               y_all: np.ndarray, in_fold_dag=None, attempt: int = 0,
               oom_attempt: int = 0) -> SweepPlan:
    splits = validator.splits(y_all)
    racing_on, eta, min_surv = validator._racing_config()
    race_path_ok = not in_fold_dag and len(splits) >= 2
    if racing_on and not race_path_ok:
        # the flag is on by default — say WHY this sweep runs unraced
        reason = ("in-fold DAG refits feature stages per fold"
                  if in_fold_dag else
                  "single train/validation split (racing needs >= 2 "
                  "folds)")
        record_failure("validator", "degraded",
                       f"racing disabled: {reason}",
                       point="selector.racing",
                       validation_type=validator.validation_type)
    raced = tuple(racing_on and race_path_ok
                  and _survivor_count(len(c.grid), eta, min_surv) < len(c.grid)
                  for c in candidates)
    cp = None if in_fold_dag else active_sweep_checkpoint()
    sigs: List[str] = []
    replayed: Dict[int, List[Dict[str, Any]]] = {}
    if cp is not None:
        for ci, cand in enumerate(candidates):
            sigs.append(SweepCheckpoint.candidate_signature(
                cand.model_name, ci, cand.grid,
                racing=({"enabled": True, "eta": eta,
                         "minSurvivors": min_surv} if raced[ci]
                        else {"enabled": False})))
            stored = cp.results_for(sigs[-1])
            if stored is not None:
                replayed[ci] = stored
    n_workers = min(validator.parallelism, len(candidates))
    if len(y_all) >= _SERIAL_FROM_ROWS and any(
            getattr(c.estimator, "hbm_heavy", False) for c in candidates):
        n_workers = 1
    return SweepPlan(splits, in_fold_dag, eta, min_surv, raced, n_workers,
                     cp, tuple(sigs), replayed, attempt, oom_attempt)


@dataclass
class Placement:
    """Where the sweep's arrays lie: the one place that decides padding,
    dtype and sharding, for the sweep and for the winner's refit after it.

    On a mesh the matrix is row-sharded over the 'data' axis and GSPMD
    inserts the collectives inside every batched fit/metric program (SURVEY
    §2.6 P1/P3 on the REAL path); the row count pads up to the
    device-divisible quantum with zero-weight rows, which is exact for
    ``weighted_pad_exact`` families, and one padded matrix serves them all.
    Sparse matrices shard like dense ones: entries sort by row, partition at
    device row boundaries and pad to a common per-device nnz rung
    (DeviceTable); the segment-sum fitters tolerate the zero pads exactly."""
    N: int                              # real rows
    N_fit: int                          # rows of every placed array
    mesh: Any = None
    is_sparse: bool = False
    chunk_bytes: Optional[int] = None   # the preflight's streaming budget
    X: Any = None
    y: Any = None                       # [N_fit] float32 on the device
    W: Any = None                       # [folds, N_fit] training weights
    va_masks: Sequence[Any] = ()        # per fold, [N_fit] on the device
    va_slices: Sequence[np.ndarray] = ()
    y_host: Optional[np.ndarray] = None
    _va_rows: Dict[int, Tuple[Any, np.ndarray]] = field(default_factory=dict)

    def descriptor(self) -> "Placement":
        """This layout without its arrays, for ``ValidationResult``."""
        return replace(self, X=None, y=None, W=None, va_masks=(),
                       va_slices=(), y_host=None, _va_rows={})

    def lay_matrix(self, X) -> Tuple[Any, int]:
        """``X`` over the mesh, padded to ``N_fit`` rows, and the bytes a
        cast, a pad or a change of layout moved on the device."""
        moved = 0
        sparse = isinstance(X, SparseMatrix)
        if isinstance(X, jax.Array):
            # already device-resident (the fused transform's output): kept in
            # the dtype it is stored in, as on one device — a bfloat16 matrix
            # stays bfloat16 and the fit programs accumulate in float32; a
            # float32 copy would double the bytes a chip holds and reads
            want = data_sharding(self.mesh, 2)
            Xj = X
            if X.dtype not in (jnp.float32, jnp.bfloat16):
                Xj = X.astype(jnp.float32)
            if self.N_fit > self.N:
                Xj = jnp.pad(Xj, ((0, self.N_fit - self.N), (0, 0)))
            if Xj is not X or not X.sharding.is_equivalent_to(want, X.ndim):
                moved = int(Xj.nbytes)
            X = jax.device_put(Xj, want)
        else:
            # chunked host→device streaming: each device's row shard is
            # assembled from bounded host slices, so peak staging is
            # O(TRANSMOGRIFAI_DEVICE_CHUNK_BYTES), not O(dataset).  COO
            # entries stream by nnz range under the same budget; empty pad
            # rows own no entries
            X = stream_to_device(
                X if sparse else np.asarray(X, dtype=np.float32),
                self.mesh, pad_to=self.N_fit, chunk_bytes=self.chunk_bytes)
        if self.N_fit > self.N and not sparse:
            # tree families quantile-bin over the true rows only — keeps
            # padded split points identical to unpadded ones (sparse grids
            # are linear-only: no binning to protect)
            register_real_rows(X, self.N)
        return X, moved

    def refit_arrays(self, X, y, folds: int):
        """Full-data ``(X, y)`` and all-ones weights ``[folds, N_fit]`` laid
        out as the sweep's batched fits were (rows, pad rows at weight 0,
        dtype, shardings): the jit cache keys on all of them, so a mismatch
        would compile the whole batched program again.  None when the rows
        are not the sweep's (a Balancer/Cutter resampled the train set)."""
        if X.shape[0] != self.N:
            return None
        pad = self.N_fit - self.N
        # all-ones fold weights materialize ON DEVICE — zero wire bytes
        W = jnp.ones((folds, self.N_fit), jnp.float32)
        if self.mesh is None:
            return X, y, W        # one device never pads
        if pad:
            W = W.at[:, -pad:].set(0.0)
        X, _ = self.lay_matrix(X)
        y = jax.device_put(jnp.pad(jnp.asarray(y, jnp.float32), (0, pad)),
                           data_sharding(self.mesh, 1))
        return X, y, jax.device_put(W, data_sharding(self.mesh, 2, row_axis=1))

    def validation_rows(self, f: int) -> Tuple[Any, np.ndarray]:
        """Fold ``f``'s validation slice on the host for the per-candidate
        fallback, pulled once per FOLD so every fallback candidate shares
        one transfer."""
        if f not in self._va_rows:
            va_idx = self.va_slices[f]
            if self.is_sparse:
                # the slice STAYS sparse: sparse-capable models consume the
                # COO stream in predict_arrays; models without a sparse path
                # fail loudly (__array__ raises) and are skipped
                xv = self.X.take_rows(np.asarray(va_idx))
            else:
                # gather ONLY the validation slice on device, then pull — the
                # full matrix is folds-times bigger and the link is the
                # bottleneck.  bf16-stored matrices cast to f32 on device
                # first: numpy kernels on ml_dtypes bf16 are slow on host
                xv = np.asarray(jnp.take(
                    self.X, jnp.asarray(va_idx), axis=0).astype(jnp.float32))
            self._va_rows[f] = (xv, self.y_host[va_idx])
        return self._va_rows[f]


def place(X, y32: np.ndarray, fsplits, candidates: Sequence[ModelCandidate],
          splitter: Optional[Splitter] = None,
          y_all: Optional[np.ndarray] = None) -> Placement:
    """Lay one fold group over the devices, under the span
    ``selector.place``: the matrix, the label, the fold weights and the
    validation masks.  ``splitter.validation_prepare_weights`` applies
    Balancer/Cutter preparation to each fold's *training* rows; scoring
    stays on the untouched validation slice."""
    from .telemetry import REGISTRY
    N = X.shape[0]
    # zero-weight row padding is exact only for families that declare it —
    # one non-exact family in the grid keeps the whole shared matrix unpadded
    pad_exact_all = all(getattr(c.estimator, "weighted_pad_exact", False)
                        for c in candidates)
    with span("selector.place") as sp:
        mesh = maybe_data_mesh(N, pad=pad_exact_all)
        if (mesh is None and not pad_exact_all
                and maybe_data_mesh(N, pad=True) is not None):
            # honest degrade: the mesh WAS viable (pad-divisible) but a mixed
            # grid pinned the matrix unpadded and indivisible — operators see
            # single-device as a degrade, not a choice
            record_failure(
                "sweep", "degraded",
                RuntimeError(
                    f"N={N} indivisible and grid mixes non-pad-exact "
                    f"families: sweep falls back to single device"),
                point="selector.mesh", fallback="single_device")
            REGISTRY.counter("selector.mesh_degraded").inc()
        p = Placement(N=N, N_fit=N, mesh=mesh,
                      is_sparse=isinstance(X, SparseMatrix),
                      va_slices=[va for _, va in fsplits], y_host=y32)
        relayout_bytes = 0
        if mesh is not None:
            p.N_fit = N + pad_rows_for(N, mesh)
            if memory.memory_governor_enabled():
                # preflight: estimate rows × dtype × grid-width × fold-panel
                # against the per-device budget and choose chunk bytes (and
                # grid partitioning, read back by the fits) BEFORE the first
                # transfer, so that a large sweep does not discover OOM by
                # dying in a device_put
                p.chunk_bytes = memory.plan_sweep_memory(
                    rows=p.N_fit,
                    cols=(int(X.shape[1])
                          if p.is_sparse or getattr(X, "ndim", 1) == 2
                          else 1),
                    folds=len(fsplits),
                    grid_width=max((len(c.grid) for c in candidates),
                                   default=1),
                    devices=int(mesh.devices.size),
                    # a device-resident matrix stays as it is stored
                    dtype_bytes=(int(X.dtype.itemsize)
                                 if isinstance(X, jax.Array) else 4),
                    nnz=int(X.nnz) if p.is_sparse else None).chunk_bytes
            p.X, relayout_bytes = p.lay_matrix(X)
            p.y = stream_to_device(y32, mesh, pad_to=p.N_fit,
                                   chunk_bytes=p.chunk_bytes)
        else:
            # ONE host→device transfer shared by every candidate family; the
            # label goes over an exact wire (bf16 only when verified
            # lossless), shared with every other consumer of its buffer
            p.X = (X if isinstance(X, jax.Array) or p.is_sparse
                   else to_device_f32(X))
            p.y = to_device_f32(y32, exact=True)
        p.W, p.va_masks = _fold_weights(p, fsplits, splitter, y_all)
        n_dev = 1 if mesh is None else int(mesh.devices.size)
        REGISTRY.gauge("mesh.devices").set(n_dev)
        if mesh is not None:
            REGISTRY.counter("mesh.relayout_bytes").inc(relayout_bytes)
        if sp is not None:
            sp.attrs.update(
                rows=int(N), pad_rows=int(p.N_fit - N), devices=n_dev,
                dtype=str(getattr(p.X, "dtype", "")),
                relayout_bytes=relayout_bytes,
                bytes_placed=sum(int(getattr(a, "nbytes", 0))
                                 for a in (p.X, p.y, p.W, *p.va_masks)))
    return p


def _fold_weights(p: Placement, fsplits, splitter, y_all):
    """(training weights [F, N_fit], per-fold validation masks) on the
    device, sharded by row under a mesh."""
    F, mesh = len(fsplits), p.mesh
    neutral = splitter is None or (
        type(splitter).validation_prepare_weights
        is Splitter.validation_prepare_weights)
    # dense per-fold weight rows only materialize when a splitter may modify
    # them
    W_rows = []
    if not (neutral and F < _PAD_FOLD):
        neutral = True
        for tr_idx, _ in fsplits:
            w = np.zeros(p.N, np.float32)
            w[tr_idx] = 1.0
            if splitter is not None:
                w2 = splitter.validation_prepare_weights(y_all, w)
                neutral = neutral and w2 is w
                w = w2
            W_rows.append(w)
    if neutral and F < _PAD_FOLD:
        # fold masks from ONE [N] uint8 assignment shipped over the link —
        # 1 byte/row instead of (folds+1)×4 bytes/row of train + validation
        # masks.  On the mesh the assignment is row-sharded first so the
        # [F, N] masks materialize directly with the fit programs' expected
        # sharding.
        assign = np.full(p.N_fit, _NO_FOLD, np.uint8)
        assign[p.N:] = _PAD_FOLD   # pad rows join NO fold, ever
        for f, (_, va_idx) in enumerate(fsplits):
            assign[va_idx] = f
        aj = jnp.asarray(assign)
        if mesh is not None:
            aj = jax.device_put(aj, data_sharding(mesh, 1))
        W, VA = _fold_masks_from_assignment(aj, F)
        return W, [VA[f] for f in range(F)]
    masks = []
    for va_idx in p.va_slices:
        vm = np.zeros(p.N, np.float32)
        vm[va_idx] = 1.0
        # under a mesh the pad tail streams in as zeros — never validated;
        # a 0/1 mask is exact on the bf16 wire
        masks.append(stream_to_device(vm, mesh, pad_to=p.N_fit,
                                      chunk_bytes=p.chunk_bytes)
                     if mesh is not None else to_device_f32(vm))
    W = np.stack(W_rows)
    if mesh is not None:
        return stream_to_device(W, mesh, row_axis=1, pad_to=p.N_fit,
                                chunk_bytes=p.chunk_bytes), masks
    # one shared transfer; family fits see a no-op conversion.  exact=True:
    # bf16 wire only when verified lossless (0/1 fold masks; balancer
    # keep/drop weights) — custom splitters may emit arbitrary weights, which
    # go exact f32
    return to_device_f32(W, exact=True), masks


def pretrace_families(p: Placement, plan: SweepPlan,
                      candidates: Sequence[ModelCandidate]) -> None:
    """Concurrent pre-trace (aot.py): lower+compile each supporting family's
    grid programs on a background thread NOW, so that by the time the fits
    reach them the persistent compile cache already holds the executables.
    Compile-only — sweep winners are bitwise unaffected."""
    if not pretrace_enabled():
        return
    for ci, cand in enumerate(candidates):
        if (ci in plan.replayed or not getattr(
                cand.estimator, "supports_pretrace", False)):
            continue

        def submit(Wblk, grid, est=cand.estimator, name=cand.model_name,
                   X=p.X, y=p.y):
            pretrace_submit(name, lambda: est.pretrace_arrays_grid(
                X, y, Wblk, grid))
        if plan.raced_flags[ci]:
            # round A (full grid, fold 0) is certain; round B's survivor
            # subset is data-dependent — pre-trace a same-sized prefix as a
            # best-effort shape/static match (a miss just forfeits the
            # overlap)
            submit(p.W[:1], cand.grid)
            submit(p.W, cand.grid[:plan.survivor_count(len(cand.grid))])
        else:
            submit(p.W, cand.grid)


def fit_family(p: Placement, cand: ModelCandidate, Wblk, grid,
               plan: SweepPlan) -> Tuple[list, bool]:
    """One family's (fold × grid) block over the placed arrays as ONE batched
    program → (``fitted[fold][point]``, whether the batched program ran).
    A block that fails is retried point by point, so one bad candidate cannot
    take down its family (≙ Try-wrapped fits in OpValidator.getSummary); a
    failed point is None."""
    name = cand.model_name
    try:
        maybe_inject("selector.candidate_fit", key=name)
        # chaos seams for a mid-sweep device loss and allocator OOM
        maybe_inject("supervisor.device_loss",
                     key=f"{name}:fit:a{plan.attempt}")
        maybe_inject("memory.device_oom",
                     key=f"{name}:fit:o{plan.oom_attempt}")
        if memory.per_candidate_fallback():
            # memory ladder's last rung: no batched grid program at all —
            # the per-(fold, point) working set is the smallest the sweep
            # can make
            raise MemoryError("memory ladder: per-candidate fallback")
        parts = memory.grid_partitions()
        if parts > 1 and len(grid) > 1:
            # memory ladder rung 2+ (or the preflight plan): split the
            # batched program into grid sub-batches so each program's lane
            # working set shrinks with the partition count
            sub = -(-len(grid) // min(parts, len(grid)))
            outs = [cand.estimator.fit_arrays_grid(p.X, p.y, Wblk,
                                                   grid[i:i + sub])
                    for i in range(0, len(grid), sub)]
            return [[fit for o in outs for fit in o[f]]
                    for f in range(len(outs[0]))], True
        return cand.estimator.fit_arrays_grid(p.X, p.y, Wblk, grid), True
    except Exception as e:  # noqa: BLE001
        # a lost device is NOT a bad candidate: per-point refits on a dead
        # mesh would fail K×|grid| more times — let the sweep-level recovery
        # rebuild the surviving mesh instead
        if supervisor.is_device_loss(e):
            raise
        # allocator exhaustion is not a bad candidate either — unless the
        # ladder already reached its last rung, where per-point refits ARE
        # the recovery
        if (memory.is_memory_exhaustion(e)
                and not memory.per_candidate_fallback()):
            raise
        record_failure(name, "degraded", e, point="selector.candidate_fit",
                       fallback="per-point refits")
    fitted_grid = []
    for f in range(len(Wblk)):
        with span("selector.fold_fit", model=name, fold=f, degraded=True):
            row = []
            for gi, params in enumerate(grid):
                try:
                    maybe_inject("selector.candidate_fit", key=name)
                    est = copy.deepcopy(cand.estimator)
                    for k, v in params.items():
                        est.set(k, v)
                    row.append(est.fit_arrays(p.X, p.y,
                                              sample_weight=Wblk[f]))
                except Exception as e2:  # noqa: BLE001
                    if supervisor.is_device_loss(e2):
                        raise
                    record_failure(name, "skipped", e2,
                                   point="selector.candidate_fit",
                                   fold=f, grid_index=gi)
                    row.append(None)
        fitted_grid.append(row)
    return fitted_grid, False


def fit_round(round_name: str, jobs: Sequence[Any], p: Placement,
              plan: SweepPlan, fit_meta: Dict[str, Dict[str, int]],
              preempted: List[str]) -> list:
    """Fit one round's families, on the pool or one after another.  A job is
    ``(candidate, weight block)`` or ``_REPLAYED``; the answer per job is the
    fitted grid, ``_REPLAYED``, or ``_PREEMPTED`` where a requested graceful
    stop (signal or injected preemption) won over starting new work.
    ``fit_meta`` learns the folds and lanes of each family's batched fit."""
    from .telemetry import REGISTRY
    left = [len(jobs)]   # feeds the /statusz board's ETA, per round
    BOARD.publish(round=round_name, fitsQueued=len(jobs))

    def one(job):
        if job is _REPLAYED:
            return _REPLAYED
        cand, Wblk = job
        if shutdown_requested(key=cand.model_name):
            preempted.append(cand.model_name)
            return _PREEMPTED
        BOARD.publish(candidate=cand.model_name,
                      candidateGrid=len(cand.grid),
                      candidateFolds=int(len(Wblk)))
        t0 = time.perf_counter()
        # one a family a round: a round of two families counts two
        REGISTRY.counter("selector.family_rounds").inc()
        # worker threads have no span of their own, so this parents under
        # the orchestrating selector.sweep span even through the pool
        with span("selector.candidate_fit", model=cand.model_name,
                  grid=len(cand.grid), folds=int(len(Wblk))):
            fitted, batched = fit_family(p, cand, Wblk, cand.grid, plan)
        if batched:
            fit_meta[cand.model_name] = {"folds": len(fitted),
                                         "lanes": len(cand.grid)}
        else:
            fit_meta.pop(cand.model_name, None)
        left[0] = max(0, left[0] - 1)
        BOARD.note_unit(time.perf_counter() - t0, remaining_units=left[0])
        return fitted

    workers = min(plan.n_workers, len(jobs))
    if workers > 1:
        with ThreadPoolExecutor(max_workers=workers) as pool:
            return list(pool.map(one, jobs))
    return [one(job) for job in jobs]


def _make_model(cand, params, fitted):
    est = cand.estimator
    return est.model_cls(fitted=fitted, **{**est._params, **params})


def _device_metric(evaluator, cand, params, fitted, X, y, w):
    """Score a candidate entirely on device (see metrics_device); None →
    caller falls back to the host path.  Device scalars are returned as-is
    (defer=True) and pulled in one batch afterwards."""
    try:
        model = _make_model(cand, params, fitted)
        if not hasattr(model, "device_scores"):
            return None
        return evaluator.evaluate_masked(y, model.device_scores(X), w,
                                         defer=True)
    except Exception:  # noqa: BLE001
        return None


def _host_metric(evaluator, cand, params, fitted, X_va, y_va):
    try:
        maybe_inject("selector.candidate_metric", key=cand.model_name)
        pred = _make_model(cand, params, fitted).predict_arrays(X_va)
        return evaluator.evaluate(y_va, pred)
    except Exception as e:  # noqa: BLE001 — candidate robustness
        if supervisor.is_device_loss(e) or memory.is_memory_exhaustion(e):
            raise   # sweep-level recovery, not a NaN score
        record_failure(cand.model_name, "skipped", e,
                       point="selector.candidate_metric",
                       params=dict(params))
        return float("nan")


def score_block(validator: "OpValidator", p: Placement, plan: SweepPlan,
                cand: ModelCandidate, ci: int, fitted_grid, fold_offset: int,
                n_folds: int, rec) -> None:
    """Score a fitted (n_folds × grid) block against validation folds
    [fold_offset, fold_offset + n_folds): the batched panel first, then per
    candidate on the device, then per candidate on the host.  ``rec`` lets
    racing remap a survivor sub-grid's local indices back to the family's
    full grid."""
    BOARD.publish(scoring=cand.model_name, foldOffset=fold_offset,
                  foldCount=n_folds)
    # chaos seam: a device lost between fitting and scoring — fires AFTER
    # earlier families checkpointed, so the recovery sweep demonstrably
    # replays them from the SweepCheckpoint
    maybe_inject("supervisor.device_loss",
                 key=f"{cand.model_name}:score:a{plan.attempt}")
    maybe_inject("memory.device_oom",
                 key=f"{cand.model_name}:score:o{plan.oom_attempt}")
    masks = p.va_masks[fold_offset:fold_offset + n_folds]
    if validator._record_grid_metrics_batched(cand, ci, fitted_grid, p.X,
                                              p.y, masks, rec):
        return
    for f_local, mask in enumerate(masks):
        for gi, params in enumerate(cand.grid):
            fitted = fitted_grid[f_local][gi]
            if fitted is None:
                rec(cand, ci, gi, params, float("nan"))
                continue
            metric = _device_metric(validator.evaluator, cand, params,
                                    fitted, p.X, p.y, mask)
            if metric is None:
                metric = _host_metric(
                    validator.evaluator, cand, params, fitted,
                    *p.validation_rows(fold_offset + f_local))
            rec(cand, ci, gi, params, metric)


class ResultBook:
    """The sweep's scores: one ``ValidatedCandidate`` per (family, grid
    point), in the order first recorded — replayed families first."""

    def __init__(self, plan: SweepPlan, candidates: Sequence[ModelCandidate]):
        self.plan = plan
        self.results: Dict[Tuple[str, int], ValidatedCandidate] = {}
        # device-scalar metrics are recorded lazily and pulled host-side in
        # ONE stacked transfer — a per-candidate float() costs a full
        # host-link round trip each
        self._deferred: List[Tuple[Any, list, int]] = []
        for ci, stored in plan.replayed.items():
            cand = candidates[ci]
            for gi, r in enumerate(stored):
                self.results[(cand.model_name, ci * 10000 + gi)] = \
                    ValidatedCandidate(
                        cand.model_name, dict(r.get("params") or {}),
                        [float(v) for v in (r.get("metricValues") or [])],
                        candidate_index=ci,
                        raced_out=bool(r.get("racedOut", False)))
            record_failure(cand.model_name, "resumed",
                           f"replayed {len(stored)} grid point(s) from "
                           "sweep checkpoint", point="checkpoint.load",
                           candidate_index=ci)

    def get(self, cand, ci: int, gi: int) -> Optional[ValidatedCandidate]:
        return self.results.get((cand.model_name, ci * 10000 + gi))

    def record(self, cand, ci, gi, params, metric) -> None:
        r = self.get(cand, ci, gi)
        if r is None:
            r = self.results[(cand.model_name, ci * 10000 + gi)] = \
                ValidatedCandidate(cand.model_name, dict(params), [],
                                   candidate_index=ci)
        if isinstance(metric, jax.Array):
            r.metric_values.append(float("nan"))   # patched by ``drain``
            self._deferred.append((metric, r.metric_values,
                                   len(r.metric_values) - 1))
        else:
            r.metric_values.append(float(metric))

    def drain(self) -> None:
        """Pull every pending device-scalar metric in one stacked transfer
        (falling back to per-metric pulls on failure).  Called at the end of
        the grid, before ranking, and before each sweep-checkpoint flush — a
        flushed family's metric values must be real numbers, not the NaN
        placeholders the batched pull would patch later."""
        if not self._deferred:
            return
        try:
            vals = np.asarray(jnp.stack([m for m, _, _ in self._deferred]))
        except Exception as e:  # noqa: BLE001 — candidate robustness: one
            # bad candidate's runtime failure must not kill the whole grid;
            # fall back to per-metric pulls (failed ones stay NaN)
            record_failure("validator", "degraded", e,
                           point="selector.metric_pull",
                           fallback="per-metric pulls")
            vals = []
            for m, _, _ in self._deferred:
                try:
                    vals.append(float(m))
                except Exception as e2:  # noqa: BLE001
                    record_failure("validator", "skipped", e2,
                                   point="selector.metric_pull")
                    vals.append(float("nan"))
        for v, (_, lst, i) in zip(vals, self._deferred):
            lst[i] = float(v)
        self._deferred.clear()

    def checkpoint_family(self, ci: int, cand, fitted_grid) -> None:
        """Persist one completed candidate family into the ambient sweep
        checkpoint (atomic flush), if there is one.  A checkpoint-write
        failure degrades — the sweep's correctness never depends on its
        durability."""
        cp = self.plan.checkpoint
        if cp is None:
            return
        self.drain()
        found = (self.get(cand, ci, gi) for gi in range(len(cand.grid)))
        entry = [{"params": r.params, "metricValues": r.metric_values,
                  "racedOut": r.raced_out} for r in found if r is not None]
        try:
            cp.record_candidate(
                self.plan.signatures[ci], cand.model_name, ci, entry,
                fitted_grid=fitted_grid
                if isinstance(fitted_grid, list) else None)
            cp.flush()
            BOARD.publish(lastCheckpointFamily=cand.model_name)
        except Exception as e:  # noqa: BLE001
            record_failure(cand.model_name, "degraded", e,
                           point="checkpoint.save",
                           fallback="sweep continues unpersisted")


def prune_raced(book: ResultBook, plan: SweepPlan,
                candidates: Sequence[ModelCandidate], race_live: List[int],
                larger_better: bool) -> Dict[int, List[int]]:
    """Rank each raced family's fold-0 screen in the evaluator's direction
    and mark what falls past the survivor floor ``raced_out``: the
    (folds-1) × (grid - survivors) fits never run.  → the surviving grid
    indices per candidate index, ascending."""
    book.drain()   # ranking needs numbers, not deferred slots
    sign = 1.0 if larger_better else -1.0
    survivors: Dict[int, List[int]] = {}
    raced_out: Dict[str, int] = {}
    for ci in race_live:
        cand = candidates[ci]
        G = len(cand.grid)
        S = plan.survivor_count(G)

        def keyf(gi):
            r = book.get(cand, ci, gi)
            v = r.metric_values[0] if r and r.metric_values else float("nan")
            return sign * v if np.isfinite(v) else -np.inf

        # deterministic: ties and NaNs break by grid position
        order = sorted(range(G), key=lambda gi: (-keyf(gi), gi))
        for gi in order[S:]:
            r = book.get(cand, ci, gi)
            if r is not None:
                r.raced_out = True
        event("selector.racing.prune", model=cand.model_name, grid=G,
              survivors=S, pruned=G - S)
        raced_out[cand.model_name] = G - S
        BOARD.publish(racedOut=dict(raced_out))
        survivors[ci] = sorted(order[:S])
    return survivors


def choose_winner(book: ResultBook, candidates: Sequence[ModelCandidate],
                  validator: "OpValidator") -> ValidationResult:
    book.drain()   # ONE pull for every device-scalar metric left
    evaluator = validator.evaluator
    all_results = list(book.results.values())
    sign = 1.0 if evaluator.is_larger_better else -1.0
    # raced-out points carry a fold-0 screen mean only; comparing that
    # against survivors' full-k-fold means would be apples-to-oranges, so
    # they are excluded from winner selection (kept in all_results for the
    # summary). If racing somehow pruned everything that finished, fall back
    # to the full list rather than fail the sweep.
    scored = [(sign * r.mean_metric, r) for r in all_results
              if np.isfinite(r.mean_metric) and not r.raced_out]
    if not scored:
        scored = [(sign * r.mean_metric, r) for r in all_results
                  if np.isfinite(r.mean_metric)]
    if not scored:
        # aggregate error with per-candidate causes from the failure log —
        # "nothing survived" alone is undebuggable at 3am
        causes: Dict[str, str] = {}
        for ev in active_failure_log().events:
            if ev.point.startswith("selector.") and ev.cause:
                causes.setdefault(ev.stage, ev.cause)
        for cand in candidates:
            causes.setdefault(cand.model_name, "no finite validation metric")
        raise AllCandidatesFailed(
            "all model candidates failed validation", causes)
    _, best = max(scored, key=lambda t: t[0])
    best_est = copy.deepcopy(candidates[best.candidate_index].estimator)
    for k, v in best.params.items():
        best_est.set(k, v)
    return ValidationResult(
        best=ModelCandidate(best_est, [dict(best.params)], best.model_name),
        best_params=dict(best.params),
        best_metric=best.mean_metric,
        all_results=all_results,
        validation_type=validator.validation_type,
        metric_name=evaluator.default_metric,
        is_larger_better=evaluator.is_larger_better)


def _fold_groups(plan: SweepPlan, batch: ColumnBatch, features: str):
    """(X, fold splits) groups: one shared X across folds normally; per-fold
    X when feature stages must be refit inside the fold (leakage guard,
    ≙ OpCrossValidation.validate:87-147 DAG copy+refit).  A generator, so
    only one fold's full-size matrix is resident at a time.  The matrix keeps
    its residency: device arrays stay on device (the host link is the
    bottleneck on real TPU hardware) and sparse matrices pass through —
    densifying one here is exactly the [N, num_hashes] blow-up the
    representation avoids."""
    def values(b):
        v = b[features].values
        if isinstance(v, (jax.Array, SparseMatrix)):
            return v
        return np.asarray(v, dtype=np.float32)

    if len(plan.replayed) == len(plan.raced_flags):
        # every candidate replayed from the sweep checkpoint — no data
        # matrix, fold masks, or device transfers needed
        return
    if not plan.in_fold_dag:
        yield values(batch), plan.splits
        return
    for f, (tr_idx, va_idx) in enumerate(plan.splits):
        with span("selector.fold_fit", fold=f, in_fold_dag=True):
            dag_copy = [[copy.deepcopy(s) for s in layer]
                        for layer in plan.in_fold_dag]
            _, fitted_dag = fit_dag(batch.take_rows(tr_idx), dag_copy)
            full = apply_dag(batch, fitted_dag)
        yield values(full), [(tr_idx, va_idx)]


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
        # sweep racing: None defers to DefaultSelectorParams so
        # OpParams/selector factories can retune the fleet-wide defaults
        # without touching every validator ctor
        self.racing = racing
        self.racing_eta = racing_eta
        self.racing_min_survivors = racing_min_survivors

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

    def _record_grid_metrics_batched(self, cand, ci, fitted_grid, X, y_dev,
                                     va_masks_dev, record) -> bool:
        """Score a LINEAR family's whole (fold × grid) block with ONE matmul
        + ONE vmapped metric program + deferred scalars — K per-candidate
        metric dispatches (each a link round trip of queue latency) collapse
        to a single pair.  AUC metrics are rank-invariant, so raw margins
        replace per-model sigmoid scores exactly.  Returns False when the
        family/evaluator has no batched form (caller keeps the per-candidate
        path)."""
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
        # inside a multi-process host group the sweep span carries the rank
        # so merged traces attribute each sweep lane to its host
        _hg_attrs = {}
        if hostgroup.hostgroup_env_present():
            _hg_attrs = {"hostgroup_rank": hostgroup.current_rank(),
                         "hostgroup_world": hostgroup.group_world_size()}
        # the one-per-family fallback warning is scoped to THIS validate:
        # a second train in the same process surfaces its own fallbacks
        _reset_logged_fallbacks()
        attempt = 0
        oom_attempt = 0
        while True:
            # control-plane seam: the retry loop is the coarse boundary —
            # /statusz shows which recovery lane the sweep is in
            BOARD.publish(phase="sweep", sweepAttempt=attempt,
                          oomAttempt=oom_attempt,
                          candidateFamilies=len(candidates),
                          gridPoints=sum(len(c.grid) for c in candidates))
            # the RSS watchdog's hard watermark surfaces HERE, on the
            # governed thread, where a typed error can be handled — not as
            # a kernel OOM-kill of an arbitrary victim
            memory.check_host_pressure()
            try:
                with span("selector.sweep", candidates=len(candidates),
                          validation_type=self.validation_type,
                          grid_points=sum(len(c.grid) for c in candidates),
                          attempt=attempt, oom_attempt=oom_attempt,
                          **_hg_attrs):
                    return self._validate_impl(candidates, batch, label,
                                               features,
                                               in_fold_dag=in_fold_dag,
                                               splitter=splitter,
                                               attempt=attempt,
                                               oom_attempt=oom_attempt)
            except Exception as e:  # noqa: BLE001 — classify, maybe recover
                if supervisor.is_device_loss(e):
                    if attempt >= supervisor.max_sweep_recoveries():
                        raise
                    supervisor.note_sweep_device_loss(e, attempt=attempt,
                                                       stage="validator")
                    attempt += 1
                    continue
                if memory.is_memory_exhaustion(e):
                    if not memory.memory_governor_enabled():
                        raise   # --no-memory-governor: propagate unchanged
                    if oom_attempt >= memory.max_oom_recoveries():
                        raise memory.as_memory_exhausted(e) from e
                    memory.note_sweep_memory_exhaustion(
                        e, attempt=oom_attempt, stage="validator")
                    oom_attempt += 1
                    continue
                raise

    def _validate_impl(self, candidates: Sequence[ModelCandidate],
                       batch: ColumnBatch, label: str, features: str,
                       in_fold_dag: Optional[List[List[Any]]] = None,
                       splitter: Optional[Splitter] = None,
                       attempt: int = 0, oom_attempt: int = 0
                       ) -> ValidationResult:
        """One attempt at the CV/TVS grid: plan, then for each fold group
        place → fit → score, racing in two rounds, then the winner.

        The fast path (no in-fold DAG) keeps ONE data matrix in HBM and turns
        folds into per-row weight masks, so each candidate family trains its
        whole (fold × grid) block as a single batched XLA program
        (``fit_arrays_grid``) with zero fold-shape recompiles — the TPU
        re-design of the reference's k×Σ|grid| Spark-job fan-out
        (OpValidator.scala:320-349).
        """
        y_all = np.asarray(batch[label].values, dtype=np.float64)
        plan = plan_sweep(self, candidates, y_all, in_fold_dag, attempt,
                          oom_attempt)
        book = ResultBook(plan, candidates)
        # reuse the label column's own buffer so the weakref-keyed transfer
        # cache shares ONE host→device shipment with SanityChecker/evaluate
        y32 = np.asarray(batch[label].values, dtype=np.float32)
        placement = None
        fit_meta: Dict[str, Dict[str, int]] = {}
        preempted: List[str] = []
        for X, fsplits in _fold_groups(plan, batch, features):
            placement = p = place(X, y32, fsplits, candidates, splitter,
                                  y_all)
            pretrace_families(p, plan, candidates)
            # round A: raced families fit and score their fold-0 screen;
            # unraced families fit, score and checkpoint their full block
            # exactly as an unraced sweep would
            fitted = fit_round(
                "A", [_REPLAYED if ci in plan.replayed else
                      (cand, p.W[:1] if plan.raced_flags[ci] else p.W)
                      for ci, cand in enumerate(candidates)],
                p, plan, fit_meta, preempted)
            for ci, cand in enumerate(candidates):
                if fitted[ci] is _REPLAYED or fitted[ci] is _PREEMPTED:
                    continue
                if plan.raced_flags[ci]:
                    score_block(self, p, plan, cand, ci, fitted[ci], 0, 1,
                                book.record)
                    continue
                score_block(self, p, plan, cand, ci, fitted[ci], 0,
                            len(fsplits), book.record)
                book.checkpoint_family(ci, cand, fitted[ci])
            # round B: prune each raced family past its survivor floor, then
            # fit + score ONLY the survivors on the remaining folds
            race_live = [ci for ci in range(len(candidates))
                         if plan.raced_flags[ci]
                         and fitted[ci] is not _REPLAYED
                         and fitted[ci] is not _PREEMPTED]
            if not race_live:
                continue
            survivors = prune_raced(book, plan, candidates, race_live,
                                    self.evaluator.is_larger_better)
            subs = {ci: ModelCandidate(
                        candidates[ci].estimator,
                        [dict(candidates[ci].grid[g]) for g in survivors[ci]],
                        candidates[ci].model_name) for ci in race_live}
            fitted_b = fit_round("B", [(subs[ci], p.W[1:])
                                       for ci in race_live],
                                 p, plan, fit_meta, preempted)
            rest = len(fsplits) - 1
            for ci, fb in zip(race_live, fitted_b):
                if fb is _PREEMPTED:
                    continue
                cand, kept = candidates[ci], survivors[ci]

                def rec(_c, _ci, gi_local, params, metric,
                        _cand=cand, _i=ci, _map=kept):
                    book.record(_cand, _i, _map[gi_local], params, metric)

                score_block(self, p, plan, subs[ci], ci, fb, 1, rest, rec)
                record_racing(rest * (len(cand.grid) - len(kept)),
                              len(cand.grid) - len(kept))
                book.checkpoint_family(ci, cand, None)
        if preempted:
            # graceful stop honored at a candidate boundary: everything
            # completed so far is drained + flushed (per family, above);
            # hand the caller the resume point instead of dying mid-write
            book.drain()
            raise TrainingPreempted(
                "selector sweep stopped before candidate(s) "
                + ", ".join(sorted(set(preempted))),
                resume_from=(plan.checkpoint.path
                             if plan.checkpoint is not None else None))
        result = choose_winner(book, candidates, self)
        if placement is not None:
            result.placement = placement.descriptor()
            result.fit_meta = fit_meta
        return result


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

"""ModelSelector — cross-validated model selection (reference:
core/src/main/scala/com/salesforce/op/stages/impl/selector/ModelSelector.scala:114,143,
factories BinaryClassificationModelSelector.scala:60-133,
MultiClassificationModelSelector.scala, RegressionModelSelector.scala:61,
grids DefaultSelectorParams.scala:36-68).

``fit``: prepare data (splitter), run the validator over every
(model × grid-point), re-fit the winner on the full prepared train split,
evaluate all evaluators, and return a ``SelectedModel`` carrying the
``ModelSelectorSummary`` — the exact reference flow, with Spark-job fan-out
replaced by compiled per-candidate XLA fits.
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from .checkpoint import active_sweep_checkpoint
from .columns import Column, ColumnBatch
from .evaluators import (Evaluators, OpBinaryClassificationEvaluator,
                         OpEvaluatorBase, OpMultiClassificationEvaluator,
                         OpRegressionEvaluator)
from .models.base import PredictionModel, PredictorEstimator, extract_xy
from .resilience import record_failure
from .stages.base import Estimator
from .telemetry import span
from .tuning import (DataBalancer, DataCutter, DataSplitter, ModelCandidate,
                     OpCrossValidation, OpTrainValidationSplit, OpValidator,
                     Splitter, ValidationResult)
from .types import OPVector, Prediction, RealNN


class DefaultSelectorParams:
    """≙ DefaultSelectorParams.scala:36-68 — the pinned reference grid values."""

    MAX_DEPTH = [3, 6, 12]
    MAX_BIN = [32]
    MIN_INSTANCES_PER_NODE = [10, 100]
    MIN_INFO_GAIN = [0.001, 0.01, 0.1]
    REGULARIZATION = [0.001, 0.01, 0.1, 0.2]
    MAX_ITER_LIN = [50]
    MAX_ITER_TREE = [20]
    ELASTIC_NET = [0.1, 0.5]
    MAX_TREES = [50]
    SUBSAMPLE_RATE = [1.0]
    STEP_SIZE = [0.1]
    IMPURITY_CLASS = ["gini"]
    IMPURITY_REG = ["variance"]
    TOL = [1e-6]
    NB_SMOOTHING = [1.0]
    XGB_NUM_ROUND = [100]
    XGB_ETA = [0.1, 0.3]
    XGB_MIN_CHILD_WEIGHT = [1.0, 5.0, 10.0]

    # sweep racing (successive halving, Jamieson & Talwalkar 2016): screen
    # the full grid on fold 0 only, keep the top ceil(G/η) (≥ MIN_SURVIVORS)
    # per family, run the remaining folds for survivors only.  Families whose
    # grid can't shrink past the floor run full CV — bit-identical to the
    # unraced sweep.
    RACING = True
    RACING_ETA = 3.0
    RACING_MIN_SURVIVORS = 2


def grid(**param_lists) -> List[Dict[str, Any]]:
    """Cartesian product of param lists (≙ ParamGridBuilder)."""
    keys = list(param_lists)
    return [dict(zip(keys, vals))
            for vals in itertools.product(*(param_lists[k] for k in keys))]


class RandomParamBuilder:
    """≙ RandomParamBuilder: random search over param distributions."""

    def __init__(self, seed: int = 42):
        self._rng = np.random.default_rng(seed)
        self._specs: List[tuple] = []

    def uniform(self, name: str, low: float, high: float) -> "RandomParamBuilder":
        self._specs.append((name, "uniform", low, high))
        return self

    def exponential(self, name: str, low: float, high: float) -> "RandomParamBuilder":
        self._specs.append((name, "exp", low, high))
        return self

    def choice(self, name: str, values: Sequence[Any]) -> "RandomParamBuilder":
        self._specs.append((name, "choice", list(values), None))
        return self

    def build(self, n: int) -> List[Dict[str, Any]]:
        out = []
        for _ in range(n):
            d = {}
            for name, kind, a, b in self._specs:
                if kind == "uniform":
                    d[name] = float(self._rng.uniform(a, b))
                elif kind == "exp":
                    d[name] = float(np.exp(self._rng.uniform(np.log(a), np.log(b))))
                else:
                    d[name] = a[self._rng.integers(len(a))]
            out.append(d)
        return out


@dataclass
class ModelEvaluation:
    model_name: str
    params: Dict[str, Any]
    metric_values: Dict[str, float]
    # pruned by sweep racing after the fold-0 screen: metric_values hold the
    # screen metric (not a full-CV mean) and the point never competed for best
    raced_out: bool = False


@dataclass
class ModelSelectorSummary:
    """≙ ModelSelectorSummary (selector/ModelSelectorSummary.scala)."""

    validation_type: str = ""
    validation_parameters: Dict[str, Any] = field(default_factory=dict)
    data_prep_parameters: Dict[str, Any] = field(default_factory=dict)
    data_prep_results: Dict[str, Any] = field(default_factory=dict)
    evaluation_metric: str = ""
    problem_type: str = ""
    best_model_uid: str = ""
    best_model_name: str = ""
    best_model_type: str = ""
    validation_results: List[ModelEvaluation] = field(default_factory=list)
    train_evaluation: Dict[str, Any] = field(default_factory=dict)
    holdout_evaluation: Optional[Dict[str, Any]] = None

    def to_json(self) -> Dict[str, Any]:
        return {
            "validationType": self.validation_type,
            "validationParameters": self.validation_parameters,
            "dataPrepParameters": self.data_prep_parameters,
            "dataPrepResults": self.data_prep_results,
            "evaluationMetric": self.evaluation_metric,
            "problemType": self.problem_type,
            "bestModelUID": self.best_model_uid,
            "bestModelName": self.best_model_name,
            "bestModelType": self.best_model_type,
            "validationResults": [
                {"modelName": r.model_name, "modelParameters": r.params,
                 "metricValues": r.metric_values,
                 **({"racedOut": True} if r.raced_out else {})}
                for r in self.validation_results],
            "trainEvaluation": self.train_evaluation,
            "holdoutEvaluation": self.holdout_evaluation,
        }


class SelectedModel(PredictionModel):
    """The winning fitted model (≙ SelectedModel, ModelSelector.scala:207).
    Delegates prediction to the wrapped best model; carries the summary."""

    def __init__(self, **params):
        self._best_model: Optional[PredictionModel] = params.pop("best_model", None)
        super().__init__(**params)
        self.summary: Optional[ModelSelectorSummary] = None

    @property
    def best_model(self) -> PredictionModel:
        return self._best_model

    def predict_arrays(self, X: np.ndarray) -> Dict[str, np.ndarray]:
        return self._best_model.predict_arrays(X)

    def supports_device_scores(self) -> bool:
        inner = self._best_model
        if inner is None:
            return False
        sup = getattr(inner, "supports_device_scores", None)
        return sup() if sup is not None else hasattr(inner, "device_scores")

    def device_scores(self, Xd, full: bool = False):
        return self._best_model.device_scores(Xd, full=full)

    def ctor_args(self) -> Dict[str, Any]:
        return dict(self._params)

    # -- nested-model persistence (wrapped best model saved inline) -------
    def save_extra(self):
        if self._best_model is None:
            return {}, {}
        check = getattr(self._best_model, "check_serializable", None)
        if check is not None:
            check()  # e.g. ExternalModel without an importable predict spec
        from .models import MODEL_REGISTRY  # ensure class is resolvable

        def _is_arr(v):
            import jax
            return isinstance(v, (np.ndarray, np.generic, jax.Array))

        inner = self._best_model
        j = {"bestModelClass": type(inner).__name__,
             "bestModelParams": {k: v for k, v in inner._params.items()
                                 if isinstance(v, (str, int, float, bool, list, tuple))
                                 or v is None},
             "bestFittedJson": {k: v for k, v in inner.fitted.items()
                                if not _is_arr(v)}}
        arrays = {f"best/{k}": np.asarray(v) for k, v in inner.fitted.items()
                  if _is_arr(v)}
        return j, arrays

    def load_extra(self, extra_json, arrays):
        from .models import MODEL_REGISTRY
        cls = MODEL_REGISTRY[extra_json["bestModelClass"]]
        fitted = dict(extra_json.get("bestFittedJson") or {})
        for k, v in arrays.items():
            if k.startswith("best/"):
                fitted[k[len("best/"):]] = v
        self._best_model = cls(fitted=fitted,
                               **(extra_json.get("bestModelParams") or {}))


class ModelSelector(Estimator):
    """≙ ModelSelector.scala:114-191."""

    in_kinds = (RealNN, OPVector)
    out_kind = Prediction
    allow_label_as_input = True
    problem_type = "Unknown"

    def __init__(self, validator: OpValidator, splitter: Optional[Splitter],
                 models: Sequence[ModelCandidate],
                 evaluators: Sequence[OpEvaluatorBase] = (),
                 model_types_to_use: Optional[Sequence[str]] = None, **kw):
        super().__init__(**kw)
        self.validator = validator
        self.splitter = splitter
        self.models = list(models)
        if model_types_to_use is not None:
            # ≙ setModelsToTry/modelTypesToUse (BinaryClassificationModelSelector.scala)
            wanted = set(model_types_to_use)
            known = {c.model_name for c in self.models}
            unknown = wanted - known
            if unknown:
                raise ValueError(
                    f"model_types_to_use: unknown model types {sorted(unknown)}; "
                    f"available: {sorted(known)}")
            self.models = [c for c in self.models if c.model_name in wanted]
        self.evaluators = list(evaluators)
        self.holdout_eval: Optional[Dict[str, Any]] = None

    def output_name(self) -> str:
        return f"{self.input_features[0].name}_prediction_{self.uid[-6:]}"

    def output_is_response(self) -> bool:
        return False

    # -- the selector flow -----------------------------------------------
    def find_best_estimator(self, batch: ColumnBatch,
                            in_fold_dag=None) -> ValidationResult:
        label = self.input_features[0].name
        features = self.input_features[1].name
        return self.validator.validate(self.models, batch, label, features,
                                       in_fold_dag=in_fold_dag,
                                       splitter=self.splitter)

    def _refit_reusing_grid_executable(self, result, X, y):
        """Final full-data refit through the SAME batched (fold × grid)
        program the CV already compiled: the sweep's placement lays ``(X, y)``
        and all-ones fold weights out as the winning family's last batched
        fit was (racing may have ended it on fewer folds and a survivor-sized
        grid), so jax's executable cache hits and the refit costs F·G
        redundant cheap fits instead of compiling + loading a fresh
        single-fit program.  Returns None (→ caller falls back to
        ``fit_arrays``) when the family ran no batched fit, the rows differ
        (e.g. a Balancer resampled the train set) or anything goes wrong."""
        cand = next((c for c in self.models
                     if c.model_name == result.best.model_name), None)
        meta = result.fit_meta.get(result.best.model_name)
        if cand is None or not cand.grid or meta is None:
            return None
        try:
            arrays = result.placement.refit_arrays(X, y, meta["folds"])
            if arrays is None:
                return None
            grids = [dict(result.best_params)] * meta["lanes"]
            return cand.estimator.fit_arrays_grid(*arrays, grids)[0][0]
        except Exception as e:  # noqa: BLE001 — reuse is an optimization only
            record_failure(self.uid, "degraded", e,
                           point="selector.refit_reuse",
                           fallback="fresh single-fit program")
            return None

    def _evaluate_all(self, model, X, y) -> Dict[str, Any]:
        """All-evaluator panel; device reductions when X is device-resident."""
        import jax
        import jax.numpy as jnp

        from .sparse.matrix import SparseMatrix

        out: Dict[str, Any] = {}
        dev_out = y_dev = w_dev = None
        if (isinstance(X, (jax.Array, SparseMatrix))
                and hasattr(model, "device_scores")):
            try:
                dev_out = model.device_scores(X, full=True)
                y_dev = jnp.asarray(y, jnp.float32)
                w_dev = jnp.ones_like(y_dev)
            except Exception as e:  # noqa: BLE001 — fall back to host
                record_failure(self.uid, "fallback", e,
                               point="selector.evaluate_device",
                               fallback="host predict path")
                dev_out = None
        pred = None
        for ev in self.evaluators:
            em = None
            if dev_out is not None:
                try:
                    em = ev.evaluate_all_device(y_dev, dev_out, w_dev)
                except Exception as e:  # noqa: BLE001
                    record_failure(self.uid, "fallback", e,
                                   point="selector.evaluate_device",
                                   evaluator=ev.name)
                    em = None
            if em is None:
                if pred is None:
                    pred = model.predict_arrays(X)
                em = ev.evaluate_all(y, pred)
            out[ev.name] = em.to_json()
        return out

    def fit(self, batch: ColumnBatch, in_fold_dag=None) -> SelectedModel:
        label_f, feats_f = self.input_features
        label = label_f.name
        holdout = None
        if self.splitter is not None:
            if self.splitter.reserve_test_fraction > 0:
                # reserve a test holdout before any CV/preparation; the winner
                # is evaluated on it (≙ Splitter.split + holdoutEvaluation)
                batch, holdout = self.splitter.split(batch, label)
            batch = self.splitter.pre_validation_prepare(batch, label)
        result = self.find_best_estimator(batch, in_fold_dag=in_fold_dag)
        train_batch = batch
        if self.splitter is not None:
            train_batch = self.splitter.validation_prepare(batch, label)
        best_est: PredictorEstimator = result.best.estimator
        X, y = extract_xy(train_batch, label_f, feats_f)
        with span("selector.winner_refit", model=result.best.model_name):
            fitted = self._refit_reusing_grid_executable(result, X, y)
            if fitted is None:
                fitted = best_est.fit_arrays(X, y)
        best_model = best_est.model_cls(fitted=fitted, **best_est._params)

        # evaluate all evaluators on the training data (≙ trainEvaluation) —
        # on device when possible: pulling 1M-row prediction vectors over the
        # host link costs more than the whole grid's compute
        with span("selector.evaluate", split="train"):
            train_eval = self._evaluate_all(best_model, X, y)

        holdout_eval = None
        if holdout is not None and len(holdout):
            Xh, yh = extract_xy(holdout, label_f, feats_f)
            with span("selector.evaluate", split="holdout"):
                holdout_eval = self._evaluate_all(best_model, Xh, yh)
            self.holdout_eval = holdout_eval

        summary = ModelSelectorSummary(
            validation_type=result.validation_type,
            validation_parameters={
                "seed": self.validator.seed, "stratify": self.validator.stratify,
                "parallelism": self.validator.parallelism,
                **({"numFolds": self.validator.num_folds}
                   if isinstance(self.validator, OpCrossValidation) else
                   {"trainRatio": self.validator.train_ratio}
                   if isinstance(self.validator, OpTrainValidationSplit) else {}),
                "racing": dict(zip(("enabled", "eta", "minSurvivors"),
                                   self.validator._racing_config()))},
            data_prep_parameters=(
                {} if self.splitter is None else {
                    k: v for k, v in vars(self.splitter).items()
                    if isinstance(v, (int, float, str, bool))}),
            data_prep_results=(
                {} if self.splitter is None or self.splitter.summary is None
                else self.splitter.summary.info),
            evaluation_metric=result.metric_name,
            problem_type=self.problem_type,
            best_model_uid=best_est.uid,
            best_model_name=result.best.model_name,
            best_model_type=type(best_est).__name__,
            validation_results=[
                ModelEvaluation(r.model_name, r.params,
                                {result.metric_name: r.mean_metric},
                                raced_out=r.raced_out)
                for r in result.all_results],
            train_evaluation=train_eval,
            holdout_evaluation=holdout_eval,
        )

        model = SelectedModel(best_model=best_model, **self._params)
        model.summary = summary
        model.metadata["summary"] = summary.to_json()
        model.fitted = {"best_model_class": type(best_model).__name__,
                        "best_metric": float(result.best_metric)}

        # seal the sweep checkpoint with the winner: a later resume of an
        # already-finished sweep sees every candidate replayed AND which one
        # won, so restart cost is one full-data refit, not a re-sweep
        cp = active_sweep_checkpoint()
        if cp is not None:
            try:
                cp.set_winner(result.best.model_name, result.best_params,
                              float(result.best_metric))
            except Exception as e:  # noqa: BLE001 — durability is best-effort
                record_failure("selector", "degraded", e,
                               point="checkpoint.save",
                               fallback="winner not persisted")
        return self._finalize_model(model)


# --------------------------------------------------------------------------
# factories with reference-default model grids
# --------------------------------------------------------------------------

def _lr_candidates(p=DefaultSelectorParams) -> ModelCandidate:
    from .models.linear import OpLogisticRegression
    return ModelCandidate(
        OpLogisticRegression(),
        grid(reg_param=p.REGULARIZATION, elastic_net_param=p.ELASTIC_NET,
             max_iter=p.MAX_ITER_LIN),
        "OpLogisticRegression")


def _rf_classifier(p=DefaultSelectorParams) -> ModelCandidate:
    from .models.trees import OpRandomForestClassifier
    return ModelCandidate(
        OpRandomForestClassifier(),
        grid(max_depth=p.MAX_DEPTH, min_instances_per_node=p.MIN_INSTANCES_PER_NODE,
             min_info_gain=p.MIN_INFO_GAIN, num_trees=p.MAX_TREES,
             max_bins=p.MAX_BIN),
        "OpRandomForestClassifier")


def _gbt_classifier(p=DefaultSelectorParams) -> ModelCandidate:
    from .models.trees import OpGBTClassifier
    return ModelCandidate(
        OpGBTClassifier(),
        grid(max_depth=p.MAX_DEPTH, min_instances_per_node=p.MIN_INSTANCES_PER_NODE,
             min_info_gain=p.MIN_INFO_GAIN, max_iter=p.MAX_ITER_TREE,
             max_bins=p.MAX_BIN),
        "OpGBTClassifier")


def _svc_candidates(p=DefaultSelectorParams) -> ModelCandidate:
    from .models.linear import OpLinearSVC
    return ModelCandidate(
        OpLinearSVC(),
        grid(reg_param=p.REGULARIZATION, max_iter=p.MAX_ITER_LIN),
        "OpLinearSVC")


def _linreg_candidates(p=DefaultSelectorParams) -> ModelCandidate:
    from .models.linear import OpLinearRegression
    return ModelCandidate(
        OpLinearRegression(),
        grid(reg_param=p.REGULARIZATION, elastic_net_param=p.ELASTIC_NET,
             max_iter=p.MAX_ITER_LIN),
        "OpLinearRegression")


def _rf_regressor(p=DefaultSelectorParams) -> ModelCandidate:
    from .models.trees import OpRandomForestRegressor
    return ModelCandidate(
        OpRandomForestRegressor(),
        grid(max_depth=p.MAX_DEPTH, min_instances_per_node=p.MIN_INSTANCES_PER_NODE,
             min_info_gain=p.MIN_INFO_GAIN, num_trees=p.MAX_TREES,
             max_bins=p.MAX_BIN),
        "OpRandomForestRegressor")


def _gbt_regressor(p=DefaultSelectorParams) -> ModelCandidate:
    from .models.trees import OpGBTRegressor
    return ModelCandidate(
        OpGBTRegressor(),
        grid(max_depth=p.MAX_DEPTH, min_instances_per_node=p.MIN_INSTANCES_PER_NODE,
             min_info_gain=p.MIN_INFO_GAIN, max_iter=p.MAX_ITER_TREE,
             max_bins=p.MAX_BIN),
        "OpGBTRegressor")


def _compact_models(linear_cls, forest_cls) -> List[ModelCandidate]:
    """Fast starter grid (linear reg sweep + one compact forest) for generated
    apps and demos; the full reference default grids stay the constructor
    default of every selector."""
    return [
        ModelCandidate(linear_cls(), grid(reg_param=[0.01, 0.1]),
                       linear_cls.__name__),
        ModelCandidate(forest_cls(),
                       grid(num_trees=[20], max_depth=[6]),
                       forest_cls.__name__),
    ]


class BinaryClassificationModelSelector(ModelSelector):
    """≙ BinaryClassificationModelSelector.scala:60-133 — defaults: LR, RF,
    GBT, LinearSVC on; NB/DT/XGB off; 3-fold CV on AuPR; DataSplitter."""

    problem_type = "BinaryClassification"

    def __init__(self, num_folds: int = 3, seed: int = 42,
                 validation_metric: Optional[OpEvaluatorBase] = None,
                 splitter: Optional[Splitter] = None,
                 models: Optional[Sequence[ModelCandidate]] = None,
                 stratify: bool = False, parallelism: int = 8,
                 use_train_validation_split: bool = False,
                 train_ratio: float = 0.75, **kw):
        ev = validation_metric or Evaluators.BinaryClassification.auPR()
        validator = (OpTrainValidationSplit(train_ratio, ev, seed, stratify, parallelism)
                     if use_train_validation_split
                     else OpCrossValidation(num_folds, ev, seed, stratify, parallelism))
        if models is None:
            models = [_lr_candidates(), _rf_classifier(), _gbt_classifier(),
                      _svc_candidates()]
        evaluators = [OpBinaryClassificationEvaluator()]
        super().__init__(validator, splitter if splitter is not None else DataSplitter(seed),
                         models, evaluators, **kw)

    @staticmethod
    def compact_models() -> List[ModelCandidate]:
        from .models.linear import OpLogisticRegression
        from .models.trees import OpRandomForestClassifier
        return _compact_models(OpLogisticRegression, OpRandomForestClassifier)


class MultiClassificationModelSelector(ModelSelector):
    """≙ MultiClassificationModelSelector — defaults: LR, RF; DataCutter;
    3-fold CV on F1."""

    problem_type = "MultiClassification"

    def __init__(self, num_folds: int = 3, seed: int = 42,
                 validation_metric: Optional[OpEvaluatorBase] = None,
                 splitter: Optional[Splitter] = None,
                 models: Optional[Sequence[ModelCandidate]] = None,
                 stratify: bool = False, parallelism: int = 8, **kw):
        ev = validation_metric or Evaluators.MultiClassification.f1()
        validator = OpCrossValidation(num_folds, ev, seed, stratify, parallelism)
        if models is None:
            models = [_lr_candidates(), _rf_classifier()]
        evaluators = [OpMultiClassificationEvaluator()]
        super().__init__(validator, splitter if splitter is not None else DataCutter(seed=seed),
                         models, evaluators, **kw)

    @staticmethod
    def compact_models() -> List[ModelCandidate]:
        from .models.linear import OpLogisticRegression
        from .models.trees import OpRandomForestClassifier
        return _compact_models(OpLogisticRegression, OpRandomForestClassifier)


class RegressionModelSelector(ModelSelector):
    """≙ RegressionModelSelector.scala:61 — defaults: LinReg, RF, GBT;
    DataSplitter; 3-fold CV on RMSE."""

    problem_type = "Regression"

    def __init__(self, num_folds: int = 3, seed: int = 42,
                 validation_metric: Optional[OpEvaluatorBase] = None,
                 splitter: Optional[Splitter] = None,
                 models: Optional[Sequence[ModelCandidate]] = None,
                 parallelism: int = 8, **kw):
        ev = validation_metric or Evaluators.Regression.rmse()
        validator = OpCrossValidation(num_folds, ev, seed, False, parallelism)
        if models is None:
            models = [_linreg_candidates(), _rf_regressor(), _gbt_regressor()]
        evaluators = [OpRegressionEvaluator()]
        super().__init__(validator, splitter if splitter is not None else DataSplitter(seed),
                         models, evaluators, **kw)

    @staticmethod
    def compact_models() -> List[ModelCandidate]:
        from .models.linear import OpLinearRegression
        from .models.trees import OpRandomForestRegressor
        return _compact_models(OpLinearRegression, OpRandomForestRegressor)


def _combiner_best_metric(m, larger_better: bool) -> float:
    """Best validation metric of one selector's summary, for ensemble
    weighting.  Non-finite values (NaN/inf fold metrics of failed or
    diverged candidates) are excluded from the ranking — but never
    silently: each drop records a ``degraded`` FailureLog note naming the
    candidate and metric, so a candidate that NaN-ed its way out of the
    weighting is visible in the log instead of vanishing."""
    metric = m.summary.evaluation_metric
    vals = []
    for r in m.summary.validation_results:
        v = r.metric_values.get(metric, np.nan)
        if np.isfinite(v):
            vals.append(v)
        else:
            record_failure("combiner", "degraded",
                           f"non-finite {metric}={v} for candidate "
                           f"{r.model_name}; excluded from ensemble "
                           "weighting",
                           point="selector.nonfinite_metric",
                           model=r.model_name, metric=metric)
    if not vals:
        return 0.5
    return max(vals) if larger_better else min(vals)


class SelectedModelCombiner(Estimator):
    """≙ SelectedModelCombiner: weighted-average ensemble of two selectors'
    winners, weights ∝ validation metric."""

    in_kinds = (RealNN, OPVector)
    out_kind = Prediction
    allow_label_as_input = True

    def __init__(self, selector1: ModelSelector, selector2: ModelSelector, **kw):
        super().__init__(**kw)
        self.selector1 = selector1
        self.selector2 = selector2

    def fit(self, batch: ColumnBatch) -> "CombinedModel":
        label_f, feats_f = self.input_features
        self.selector1.set_input(label_f, feats_f)
        self.selector2.set_input(label_f, feats_f)
        m1 = self.selector1.fit(batch)
        m2 = self.selector2.fit(batch)
        larger_better = self.selector1.validator.evaluator.is_larger_better

        # weight by each selector's best validation metric; for
        # smaller-is-better metrics (RMSE, Error) weight inversely
        b1 = _combiner_best_metric(m1, larger_better)
        b2 = _combiner_best_metric(m2, larger_better)
        if larger_better:
            w1, w2 = abs(b1), abs(b2)
        else:
            w1, w2 = 1.0 / max(abs(b1), 1e-12), 1.0 / max(abs(b2), 1e-12)
        tot = (w1 + w2) or 1.0
        model = CombinedModel(model1=m1, model2=m2, w1=w1 / tot, w2=w2 / tot)
        return self._finalize_model(model)


class CombinedModel(PredictionModel):
    def __init__(self, **params):
        self.model1 = params.pop("model1", None)
        self.model2 = params.pop("model2", None)
        self.w1 = params.pop("w1", 0.5)
        self.w2 = params.pop("w2", 0.5)
        super().__init__(**params)

    def predict_arrays(self, X: np.ndarray) -> Dict[str, np.ndarray]:
        p1 = self.model1.predict_arrays(X)
        p2 = self.model2.predict_arrays(X)
        if p1.get("probability") is not None and p2.get("probability") is not None:
            prob = self.w1 * np.asarray(p1["probability"]) + \
                self.w2 * np.asarray(p2["probability"])
            return {"prediction": np.argmax(prob, axis=1).astype(np.float32),
                    "probability": prob, "rawPrediction": np.log(prob + 1e-12)}
        pred = self.w1 * np.asarray(p1["prediction"]) + \
            self.w2 * np.asarray(p2["prediction"])
        return {"prediction": pred}

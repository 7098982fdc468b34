"""transmogrifai_tpu — a TPU-native AutoML framework for structured data.

A from-scratch re-design of Salesforce TransmogrifAI (Scala/Spark) on JAX/XLA:
typed features with lineage, a compiled stage DAG, automatic per-type
vectorization, sanity checking / leakage detection, cross-validated model
selection over linear and tree-ensemble models trained data-parallel on the
TPU mesh, evaluators, model insights, and a serializable workflow model.
"""

import os as _os

# Persistent XLA compilation cache: fitted-grid / tree programs are large and
# their compiles dominate cold-start wall time; caching them on disk makes
# every run after the first pay execution cost only (the TPU analog of the
# JVM/Spark warm-start the reference relies on).
#
# The directory is decided HERE, once, and no other code path re-points it
# (the cache path is part of jax's cache key, so a directory that moves
# never hits):
#   1. JAX_COMPILATION_CACHE_DIR set — jax reads it itself; the operator
#      placed the cache and children inherit the variable.
#   2. TRANSMOGRIFAI_COMPILE_CACHE=<dir> — <dir>/<JAX_PLATFORMS or default>,
#      every program cached (0 s floor); also opts in to background
#      pre-tracing (aot.pretrace_enabled), with or without (1).
#   3. neither — DEFAULT_COMPILE_CACHE_DIR, one fixed git-ignored directory
#      in the checkout, 0.1 s floor.
# TRANSMOGRIFAI_COMPILE_CACHE=0 / TRANSMOGRIFAI_COMPILATION_CACHE=0 leave
# jax's cache configuration untouched.
DEFAULT_COMPILE_CACHE_DIR = _os.path.join(
    _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
    ".jax_cache")

_cc = _os.environ.get("TRANSMOGRIFAI_COMPILE_CACHE")
if _cc != "0" and (_cc or _os.environ.get(
        "TRANSMOGRIFAI_COMPILATION_CACHE", "1") != "0"):
    import jax as _jax

    if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        _plat = ((_os.environ.get("JAX_PLATFORMS") or "default")
                 .split(",")[0].strip() or "default")
        _jax.config.update(
            "jax_compilation_cache_dir",
            _os.path.join(_cc, _plat) if _cc else DEFAULT_COMPILE_CACHE_DIR)
    # cache even small programs: a warm train run launches ~90 distinct
    # executables and re-compiling the sub-second ones still costs multiple
    # seconds of wall per run
    _jax.config.update("jax_persistent_cache_min_compile_time_secs",
                       0.0 if _cc else 0.1)

# compile-vs-execute counters (profiling.compile_stats) ride jax.monitoring's
# process-global listeners; registering costs nothing until a compile fires
try:
    from .profiling import install_compile_listeners as _icl
    _icl()
except Exception:  # pragma: no cover — diagnostics only
    pass

from . import types
from .aggregators import CustomMonoidAggregator, MonoidAggregator
from .columns import Column, ColumnBatch
from .features import Feature, FeatureBuilder, features_from_schema
from .stages import (Estimator, FeatureGeneratorStage, PipelineStage,
                     Transformer, TransformerModel)
from .vector_meta import VectorColumnMeta, VectorMeta

__version__ = "0.1.0"

__all__ = [
    "types", "Column", "ColumnBatch", "Feature", "FeatureBuilder",
    "features_from_schema", "PipelineStage", "Transformer", "Estimator",
    "TransformerModel", "FeatureGeneratorStage", "VectorMeta",
    "VectorColumnMeta", "MonoidAggregator", "CustomMonoidAggregator",
    # lazy (heavy) exports, see __getattr__:
    "Workflow", "WorkflowModel", "BinaryClassificationModelSelector",
    "MultiClassificationModelSelector", "RegressionModelSelector",
    "Evaluators", "OpParams", "OpWorkflowRunner", "OpApp", "RunType",
    "ModelInsights", "RecordInsightsLOCO", "RecordInsightsCorr",
    "RawFeatureFilter",
    "score_function", "transmogrify",
    "RetryPolicy", "FailureLog", "FaultInjector", "InjectedFault",
    "WatchdogTimeout", "AllCandidatesFailed", "run_with_deadline",
    "use_failure_log", "inject_faults",
    "CheckpointError", "CorruptModelError", "ModelVersionError",
    "TrainingPreempted", "SweepCheckpoint", "verify_bundle",
    "atomic_bundle_write", "preemption_guard", "shutdown_requested",
    "Tracer", "use_tracer", "active_tracer", "span", "current_span_id",
    "MetricsRegistry", "telemetry_summary",
]

_LAZY = {
    "Workflow": ("workflow", "Workflow"),
    "WorkflowModel": ("workflow", "WorkflowModel"),
    "BinaryClassificationModelSelector": ("selector", "BinaryClassificationModelSelector"),
    "MultiClassificationModelSelector": ("selector", "MultiClassificationModelSelector"),
    "RegressionModelSelector": ("selector", "RegressionModelSelector"),
    "Evaluators": ("evaluators", "Evaluators"),
    "OpParams": ("params", "OpParams"),
    "OpWorkflowRunner": ("runner", "OpWorkflowRunner"),
    "OpApp": ("runner", "OpApp"),
    "RunType": ("runner", "RunType"),
    "ModelInsights": ("insights", "ModelInsights"),
    "RecordInsightsLOCO": ("record_insights", "RecordInsightsLOCO"),
    "RecordInsightsCorr": ("record_insights", "RecordInsightsCorr"),
    "RawFeatureFilter": ("filters", "RawFeatureFilter"),
    "score_function": ("local", "score_function"),
    "transmogrify": ("ops.transmogrify", "transmogrify"),
    "RetryPolicy": ("resilience", "RetryPolicy"),
    "FailureLog": ("resilience", "FailureLog"),
    "FaultInjector": ("resilience", "FaultInjector"),
    "InjectedFault": ("resilience", "InjectedFault"),
    "WatchdogTimeout": ("resilience", "WatchdogTimeout"),
    "AllCandidatesFailed": ("resilience", "AllCandidatesFailed"),
    "run_with_deadline": ("resilience", "run_with_deadline"),
    "use_failure_log": ("resilience", "use_failure_log"),
    "inject_faults": ("resilience", "inject_faults"),
    "CheckpointError": ("checkpoint", "CheckpointError"),
    "CorruptModelError": ("checkpoint", "CorruptModelError"),
    "ModelVersionError": ("checkpoint", "ModelVersionError"),
    "TrainingPreempted": ("checkpoint", "TrainingPreempted"),
    "SweepCheckpoint": ("checkpoint", "SweepCheckpoint"),
    "verify_bundle": ("checkpoint", "verify_bundle"),
    "atomic_bundle_write": ("checkpoint", "atomic_bundle_write"),
    "preemption_guard": ("checkpoint", "preemption_guard"),
    "shutdown_requested": ("checkpoint", "shutdown_requested"),
    "Tracer": ("telemetry", "Tracer"),
    "use_tracer": ("telemetry", "use_tracer"),
    "active_tracer": ("telemetry", "active_tracer"),
    "span": ("telemetry", "span"),
    "current_span_id": ("telemetry", "current_span_id"),
    "MetricsRegistry": ("telemetry", "MetricsRegistry"),
    "telemetry_summary": ("telemetry", "telemetry_summary"),
}


def __getattr__(name):
    # Lazy imports of heavier submodules to keep `import transmogrifai_tpu` fast.
    if name in _LAZY:
        import importlib
        mod_name, attr = _LAZY[name]
        mod = importlib.import_module(f".{mod_name}", __name__)
        return getattr(mod, attr)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

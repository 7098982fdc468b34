"""What one timed ``Workflow.train()`` produced, pulled to the host, and
whether it stayed on the chip and on the compiled path.  This is the only
file of the benchmark besides ``programs/`` that reads the program's objects."""

import numpy as np

BAD_ACTIONS = ("skipped", "demoted", "degraded", "fallback", "swallowed",
               "outage")
FALLBACK_COUNTERS = ("aot_registry.call_fallbacks",
                     "aot_registry.install_failures", "aot.fallback")


def extract(model):
    """Host-side answers of one train: SanityChecker's statistics and kept
    columns, RawFeatureFilter's drops, the fold x grid panel, the winner and
    its refit."""
    from transmogrifai_tpu.dag import dag_stages
    sane = [s for s in dag_stages(model.fitted_dag)
            if hasattr(getattr(s, "summary", None), "correlations_with_label")]
    if len(sane) != 1:
        raise RuntimeError(f"expected one SanityChecker, found {len(sane)}")
    sm = sane[0].summary
    sel = model.selected_model
    summary = sel.summary
    metric = summary.evaluation_metric
    cv = [{"family": r.model_name, "params": dict(r.params),
           "metric": float(r.metric_values[metric]),
           "raced_out": bool(r.raced_out)}
          for r in summary.validation_results]
    fitted = getattr(sel.best_model, "fitted", {}) or {}
    best_params = {k: v for k, v in sel.best_model._params.items()
                   if isinstance(v, (bool, int, float, str))}
    out = {
        "stats": np.asarray([sm.means, sm.variances, sm.mins, sm.maxs,
                             sm.correlations_with_label], np.float64),
        "kept": np.asarray(sane[0].fitted["indices_to_keep"], np.int64),
        "rff_dropped": sorted(getattr(f, "name", str(f))
                              for f in (model.blacklisted or [])),
        "cv": cv,
        "larger_better": metric not in ("Error", "RootMeanSquaredError",
                                        "LogLoss"),
        "winner": {"family": summary.best_model_name, "params": best_params,
                   "metric": float(sel.fitted["best_metric"])},
        "coef": (np.asarray(fitted["coef"], np.float64)
                 if "coef" in fitted else None),
        "intercept": (float(np.asarray(fitted["intercept"]).ravel()[0])
                      if "intercept" in fitted else None),
    }
    train = summary.train_evaluation or {}
    binary = next(iter(train.values()), {}) if train else {}
    out["train_auroc"] = float(binary.get("AuROC", float("nan")))
    return out


def counters():
    from transmogrifai_tpu.telemetry import REGISTRY
    c = REGISTRY.counters()
    return {k: c.get(k, 0) for k in FALLBACK_COUNTERS}


def left_the_path(model, ambient_log, counters_before, platform):
    """Reasons this train counts as failed, as ``chip_smoke.py`` checks them:
    an event in a failure log, an AOT/JIT fallback counter that moved, a
    native helper on its Python path, a memory shrink, another platform."""
    import jax
    from transmogrifai_tpu import native
    from transmogrifai_tpu.parallel.memory import memory_aux
    why = []
    events = sorted({(e.action, e.point or e.stage)
                     for log in (model.failure_log, ambient_log)
                     for e in log.events if e.action in BAD_ACTIONS})
    if events:
        why.append(f"failure log: {events}")
    moved = {k: v - counters_before[k] for k, v in counters().items()
             if v != counters_before[k]}
    if moved:
        why.append(f"fallback counters moved: {moved}")
    if native.fallback_reasons():
        why.append(f"native fallbacks: {native.fallback_reasons()}")
    aux = memory_aux()
    if aux["shrink_level"] or aux["shrinks_total"]:
        why.append(f"memory governor shrank the sweep: {aux['shrink_level']}")
    if jax.devices()[0].platform != platform:
        why.append(f"ran on {jax.devices()[0].platform}, not {platform}")
    return why

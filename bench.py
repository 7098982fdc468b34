"""Headline benchmarks: AutoML ``OpWorkflow.train()`` wall-clock on TPU.

``python bench.py`` is a LAUNCHER that never opens a jax backend: a chip
belongs to one process at a time.  It asks the supervisor's subprocess probe
what a fresh process gets (no accelerator -> non-zero exit, nothing printed
as a result), then runs each cell in a child that owns the chip for its
lifetime (``python bench.py --cell NAME``); cells that are inherently
several processes (serve_cold_start, serve_scaleout, mesh_sweep,
text_sparse_mesh) put one child on the chip at a time, training included.
A failed child fails its cell and the run.  ``python bench.py --cpu-smoke``
is the separately named CPU correctness run at reduced sizes.

Every cell prints ONE JSON line
``{"metric": ..., "value": N, "unit": ..., "vs_baseline": ratio,
"device": {"platform", "kind", "count"}}``:

1. **dense** (BASELINE.md north star): N x 28 dense real features at
   HIGGS-realistic difficulty (best-model AuROC ~0.8, matching real HIGGS —
   the round-2 synthetic was near-separable at 0.98, which flatters every
   solver), 3-fold CV over {4 LR, RF, GBT} through the real
   Workflow/ModelSelector API, then final refit + train evaluation.
2. **transmog** (the reference's flagship path, Transmogrifier.scala:92 +
   SmartTextVectorizer.scala:61): N rows of mixed raw types — 3 free-text
   columns through SmartTextVectorizer's 512-bin hashing path, 2 PickLists
   through top-K one-hot, a 3-key RealMap expansion, 4 Reals with 20% nulls —
   with RawFeatureFilter on, into a small LR selector.  Its cost profile
   (host tokenization/hashing, pivot fits, null tracking) is completely
   different from the dense path and was unmeasured before round 3.

3. **score** (≙ OpWorkflowModel.score:255): rows/s of the
   compiled scoring path on a FRESH 1M-row batch at transmogrified width —
   host prologue honestly re-paid, predictions forced to materialize.

vs_baseline: ratio of the measured local-proxy wall to ours (>1 = we are
faster).  The reference publishes no numbers (BASELINE.md); the proxies are
measured by scripts/measure_baseline.py with the reference's parallelism=8
honored via a process pool (OpValidator.scala:372-378) and recorded in
BASELINE_MEASURED.json.  Ratios only apply at the pinned workload sizes on an
accelerator; `--cpu-smoke` runs report 1.0.

4. **text_sparse** (ISSUE 7 tentpole): high-cardinality hashed text through
   the sparse COO path — 100k hashed columns whose dense [N, num_hashes]
   matrix never materializes.  Reports nnz/density and the process peak RSS
   against the dense-equivalent footprint.

5. **selector_smoke** (ISSUE 7 satellite): small multiclass + regression
   selector sweeps proving both ride the racing + fused-metric-panel hot
   path (zero per-candidate fallbacks).

6. **serve_cold_start** (ISSUE 9 tentpole): fresh-process time-to-first-score
   from a bundle carrying AOT-serialized executables vs the same bundle
   forced onto the JIT path — `new_compiles_at_serve` must be 0 on the AOT
   run.

7. **multi_tenant** (ISSUE 16 tentpole): one TenantRegistry over six
   per-tenant bundles with ``max_active=3`` and a deterministic skewed
   popularity sequence — aggregate rows/s with LRU activation/eviction
   churn in the measured wall, plus cold-tenant first-score latency and
   activation/eviction counts in the aux.

Env knobs: BENCH_ROWS (dense rows), BENCH_TRANSMOG_ROWS, BENCH_SCORE_ROWS,
BENCH_SPARSE_ROWS, BENCH_SPARSE_HASHES, BENCH_SPARSE_MESH_ROWS,
BENCH_COLD_START_ROWS, BENCH_TENANT_REQUESTS, BENCH_WORKLOAD
(dense|transmog|score|text_sparse|text_sparse_mesh|selector_smoke|
serving_chaos|serve_cold_start|serve_scaleout|multi_tenant|all,
default all).
"""

import json
import os
import sys
import threading
import time

import numpy as np

DENSE_D = 28


def make_data(n: int, d: int = DENSE_D, seed: int = 0):
    """HIGGS-difficulty synthetic: linear signal damped to sqrt(d) scale plus
    mild interactions, unit noise — best-model AuROC lands near 0.80 like the
    real HIGGS benchmark (calibrated against sklearn LR/GBT)."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    w = rng.normal(size=d).astype(np.float32) / np.sqrt(d)
    logits = (X @ w + 0.35 * (X[:, 0] * X[:, 1]) - 0.25 * (X[:, 2] ** 2)
              + 0.1 + 0.3 * np.sin(2 * X[:, 3]))
    y = (logits + rng.normal(size=n).astype(np.float32) > 0).astype(np.float32)
    return X, y


def make_transmog_columns(n: int, seed: int = 1):
    """Mixed-type raw columns for the transmogrification workload.

    Returns (cols dict for ColumnBatch, schema dict) — built columnar to keep
    generation out of the measured window.
    """
    from transmogrifai_tpu import types as T
    from transmogrifai_tpu.columns import Column, column_from_values

    rng = np.random.default_rng(seed)
    vocab = np.asarray([f"tok{i}" for i in range(50_000)])
    common = np.asarray([f"word{i}" for i in range(40)])

    def text_col(p_null=0.2, lo=4, hi=9):
        lens = rng.integers(lo, hi, size=n)
        toks = vocab[rng.integers(0, len(vocab), size=(n, hi))]
        salt = common[rng.integers(0, len(common), size=(n, 2))]
        out = np.empty(n, dtype=object)
        null = rng.random(n) < p_null
        for i in range(n):
            if null[i]:
                out[i] = None
            else:
                out[i] = " ".join(np.concatenate([salt[i], toks[i, :lens[i]]]))
        return out, null

    t1, _ = text_col()
    t2, _ = text_col()
    t3, _ = text_col(p_null=0.3, lo=3, hi=6)

    cats1 = np.asarray([f"c{i}" for i in range(20)])
    cats2 = np.asarray([f"k{i}" for i in range(50)])
    c1_idx = rng.integers(0, len(cats1), size=n)
    c1 = cats1[c1_idx].astype(object)
    c1[rng.random(n) < 0.1] = None
    c2 = cats2[rng.integers(0, len(cats2), size=n)].astype(object)
    c2[rng.random(n) < 0.2] = None

    rvals = rng.normal(size=(n, 4)).astype(np.float32)
    rnull = rng.random((n, 4)) < 0.2

    mvals = rng.normal(size=(n, 3)).astype(np.float32)
    mkeys = ("a", "b", "c")
    mpresent = rng.random((n, 3)) < 0.8
    rmap = np.empty(n, dtype=object)
    for i in range(n):
        rmap[i] = {k: float(mvals[i, j]) for j, k in enumerate(mkeys)
                   if mpresent[i, j]}

    logits = (0.8 * (c1_idx % 3 == 0).astype(np.float32)
              + np.where(rnull[:, 0], 0.0, rvals[:, 0])
              + 0.5 * np.where(mpresent[:, 0], mvals[:, 0], 0.0))
    y = (logits + rng.normal(size=n).astype(np.float32) > 0.4).astype(np.float32)

    cols = {
        "label": Column(T.RealNN, y),
        "text1": column_from_values(T.Text, t1),
        "text2": column_from_values(T.Text, t2),
        "text3": column_from_values(T.Text, t3),
        "cat1": column_from_values(T.PickList, c1),
        "cat2": column_from_values(T.PickList, c2),
        "rmap": Column(T.RealMap, rmap),
    }
    for j in range(4):
        vals = [None if rnull[i, j] else float(rvals[i, j]) for i in range(n)]
        cols[f"r{j}"] = column_from_values(T.Real, vals)
    schema = {"label": T.RealNN, "text1": T.Text, "text2": T.Text,
              "text3": T.Text, "cat1": T.PickList, "cat2": T.PickList,
              "rmap": T.RealMap, "r0": T.Real, "r1": T.Real, "r2": T.Real,
              "r3": T.Real}
    return cols, schema


def _phase_split(model):
    """Host/device phase split from the train PhaseTimer:
    feature_engineering_s = non-selector fit layers, selector_s = the CV
    grid layer, rff_s = RawFeatureFilter.  Selector wall absorbs queued
    device work (the in-order stream syncs when metrics are pulled)."""
    am = getattr(model, "app_metrics", None)
    if am is None:
        return {}
    fe = sum(p.wall_s for p in am.phases if p.name.startswith("fit:"))
    sel_phases = [p for p in am.phases if p.name == "selector"]
    sel = sum(p.wall_s for p in sel_phases)
    # compile-vs-execute attribution (ISSUE 4): seconds the selector phase
    # spent inside XLA backend compilation, from the jax.monitoring listener
    sel_compile = sum(p.compile_s or 0.0 for p in sel_phases)
    rff = sum(p.wall_s for p in am.phases if p.name == "rff")
    link = {}
    for p in am.phases:
        if p.host_link_bytes:
            key = ("feature_engineering" if p.name.startswith("fit:")
                   else p.name)
            link[key] = link.get(key, 0) + p.host_link_bytes
    return {"feature_engineering_s": round(fe, 2),
            "selector_s": round(sel, 2),
            "selector_compile_s": round(sel_compile, 2),
            "selector_execute_s": round(max(sel - sel_compile, 0.0), 2),
            "rff_s": round(rff, 2),
            "host_link_mb_by_phase": {k: round(v / 1e6, 1)
                                      for k, v in link.items()}}


def _telemetry_aux(tracer, top_n: int = 8):
    """Compact telemetry block for the bench aux (ISSUE 5 satellite): top
    slowest trace spans + the unified compile/racing counters, so every
    BENCH_*.json is a self-describing perf record."""
    from transmogrifai_tpu.telemetry import REGISTRY
    full = REGISTRY.snapshot()
    snap = full["gauges"]
    out = {"compile": {k.split(".", 1)[1]: snap[k] for k in snap
                       if k.startswith("compile.")},
           "racing": {k.split(".", 1)[1]: snap[k] for k in snap
                      if k.startswith("racing.")},
           "host_link_bytes": snap.get("host_link.bytes", 0),
           # mesh streaming gauges (ISSUE 10): device/chunk layout + peak
           # host staging so HBM-pressure regressions show in artifacts
           "mesh": {k.split(".", 1)[1]: snap[k] for k in snap
                    if k.startswith("mesh.")},
           # DeviceTable sparse shipments (ISSUE 19): rows/nnz over the
           # link, ladder pad entries, shards — next to the dense mesh.*
           # family they extend
           "device_table": {k.split(".", 1)[1]: snap[k] for k in snap
                            if k.startswith("device_table.")},
           # honest degrade path: "sharded" when the sweep actually ran on
           # a multi-device mesh this process, else "single_device" (the
           # selector.mesh degraded FailureLog note says WHY, when forced)
           "path": ("sharded" if snap.get("mesh.devices", 0)
                    and snap.get("mesh.devices", 0) > 1 else "single_device"),
           "host_to_device_bytes_total": full["counters"].get(
               "host_to_device_bytes_total", 0)}
    if tracer is not None:
        out["span_count"] = len(tracer)
        out["slowest_spans"] = [
            {"name": s.name, "seconds": round(s.duration_s, 4),
             "status": s.status}
            for s in tracer.slowest(top_n)]
    return out


def _memory_aux():
    """Memory-governor block for the bench aux (ISSUE 15 satellite): the
    preflight plan, any shrink-ladder activity and the host peak RSS, so
    OOM-pressure regressions (and the plan that avoided them) live in
    every BENCH_*.json."""
    from transmogrifai_tpu.parallel.memory import memory_aux
    return dict(memory_aux(), peak_rss_mb=_peak_rss_mb())


def _registry_aux():
    """Compiled-program-registry block (ISSUE 18): hit/miss/publish counts
    and on-disk size, so every BENCH_*.json records how much of the run's
    compile bill the fleet registry absorbed (read next to
    new_compiles_during_train)."""
    from transmogrifai_tpu.aot_registry import registry_stats
    s = registry_stats()
    return {k: s[k] for k in ("enabled", "root", "hits", "misses",
                              "publishes", "evictions", "shared_hits",
                              "bytes")}


# Dense bf16 peak FLOP/s of ONE chip, keyed by `jax.devices()[0].device_kind`
# (source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16).  Used
# only to place the bench programs on a roofline — achieved numbers are the
# measurement.  A device that is not in the table is an error, not a default.
PEAK_FLOPS = {"TPU v5 lite": 1.97e14}


def peak_flops(device_kind: str) -> float:
    try:
        return PEAK_FLOPS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peak FLOP/s recorded for device kind {device_kind!r}; add "
            f"it to bench.PEAK_FLOPS with its source (known: "
            f"{sorted(PEAK_FLOPS)})") from None


def _baseline(key):
    try:
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "BASELINE.json")) as fh:
            return (json.load(fh).get("published") or {}).get(key)
    except Exception:
        return None


def dense_workflow(N: int):
    """The dense cell's user program (also Phase A of chip_smoke.py):
    N x 28 RealNN -> transmogrify -> sanity_check -> 3-fold CV over
    {4 LR, RF(20 trees, depth 6), GBT(20 rounds, depth 3)}.
    Returns (workflow, batch, selector, candidates)."""
    from transmogrifai_tpu.columns import Column, ColumnBatch
    from transmogrifai_tpu.features import FeatureBuilder
    from transmogrifai_tpu.models.linear import OpLogisticRegression
    from transmogrifai_tpu.models.trees import (OpGBTClassifier,
                                                OpRandomForestClassifier)
    from transmogrifai_tpu.ops.transmogrify import transmogrify
    from transmogrifai_tpu.selector import (BinaryClassificationModelSelector,
                                            ModelCandidate, grid)
    from transmogrifai_tpu.types import RealNN
    from transmogrifai_tpu.workflow import Workflow

    D = DENSE_D
    X, y = make_data(N, D)

    label = FeatureBuilder.RealNN("label").as_response()
    feats = [FeatureBuilder.RealNN(f"f{i}").as_predictor() for i in range(D)]
    fv = transmogrify(feats)
    checked = label.sanity_check(fv, remove_bad_features=True)

    models = [
        ModelCandidate(OpLogisticRegression(),
                       grid(reg_param=[0.001, 0.01, 0.1, 0.2],
                            elastic_net_param=[0.1], max_iter=[50]),
                       "OpLogisticRegression"),
        ModelCandidate(OpRandomForestClassifier(),
                       grid(num_trees=[20], max_depth=[6],
                            min_instances_per_node=[10]),
                       "OpRandomForestClassifier"),
        ModelCandidate(OpGBTClassifier(),
                       grid(max_iter=[20], max_depth=[3],
                            min_instances_per_node=[10]),
                       "OpGBTClassifier"),
    ]
    fams = os.environ.get("BENCH_FAMILIES", "").strip()
    if fams:  # debugging knob: e.g. BENCH_FAMILIES=lr,gbt
        want = {f.strip().lower() for f in fams.split(",") if f.strip()}
        key = {"OpLogisticRegression": "lr", "OpRandomForestClassifier": "rf",
               "OpGBTClassifier": "gbt"}
        unknown = want - set(key.values())
        if unknown:
            sys.exit(f"BENCH_FAMILIES: unknown families {sorted(unknown)}; "
                     f"valid: {sorted(set(key.values()))}")
        models = [m for m in models if key[m.model_name] in want]
        if not models:
            sys.exit("BENCH_FAMILIES selected no candidates")
    selector = BinaryClassificationModelSelector(models=models)
    selector.set_input(label, checked)
    pred = selector.get_output()

    cols = {"label": Column(RealNN, y)}
    for i in range(D):
        cols[f"f{i}"] = Column(RealNN, X[:, i])
    batch = ColumnBatch(cols, N)

    wf = Workflow().set_input_batch(batch).set_result_features(pred)
    return wf, batch, selector, models


def family_cv_metrics(model, selector):
    """Per-family best CV metric: a silently-degraded tree
    fitter must show up even when LR wins.  "Best" follows the validation
    evaluator's direction, not a max assumption."""
    larger_better = bool(selector.validator.evaluator.is_larger_better)
    fam = {}
    for r in model.selected_model.summary.validation_results:
        v = next(iter(r.metric_values.values()), None)
        if v is not None and (r.model_name not in fam
                              or (v > fam[r.model_name]) == larger_better):
            fam[r.model_name] = round(float(v), 4)
    return fam, larger_better


def run_dense(N: int, on_accel: bool, platform: str):
    from transmogrifai_tpu.evaluators import Evaluators

    D = DENSE_D
    wf, batch, selector, models = dense_workflow(N)
    filtered = len(models) < 3

    from transmogrifai_tpu.profiling import (new_compile_count, racing_stats,
                                             reset_racing_stats)
    reset_racing_stats()
    nc0 = new_compile_count()
    from transmogrifai_tpu.telemetry import Tracer, use_tracer
    tracer = Tracer(run_name=f"bench:dense:{N}")
    t0 = time.time()
    with use_tracer(tracer):
        model = wf.train()
    wall = time.time() - t0
    # compiles that actually reached the backend during train — with the
    # persistent cache warm, a second consecutive run reports ~0 here
    new_compiles = new_compile_count() - nc0
    fits_saved = racing_stats()["cv_fits_saved"]

    metrics = model.evaluate(Evaluators.BinaryClassification.auROC(),
                             batch=batch)
    n_cands = sum(len(c.grid) for c in models)
    fam, larger_better = family_cv_metrics(model, selector)
    baseline = _baseline("higgs1m_train_wall_s")
    lpt8 = _baseline("higgs1m_8core_lpt_s")
    # the published baseline covers the FULL candidate set only
    at_ref = on_accel and N == 1_000_000 and not filtered
    vs = (baseline / wall) if (baseline and at_ref) else 1.0
    phases = _phase_split(model)
    return {
        "metric": f"OpWorkflow.train wall (HIGGS-like {N}x{D}, 3-fold CV, "
                  f"{n_cands} candidates, {platform})",
        "value": round(wall, 2),
        "unit": "s",
        "vs_baseline": round(vs, 3),
        "aux": {
            "train_auroc": round(float(metrics["AuROC"]), 4),
            "best_model": model.selected_model.summary.best_model_name,
            "rows": N, "features": D, "platform": platform,
            "cv_fits": 3 * n_cands - fits_saved,
            "cv_fits_saved_by_racing": fits_saved,
            "new_compiles_during_train": new_compiles,
            "cv_fit_rows_per_s": round(
                (3 * n_cands - fits_saved) * (2 * N / 3) / wall),
            "family_cv_metrics": fam,
            "metric_larger_better": larger_better,
            # the proxy re-scheduled on 8 workers (reference parallelism=8,
            # hardware this host lacks) — the conservative comparison
            "vs_baseline_8core_lpt": (round(lpt8 / wall, 3)
                                      if (lpt8 and at_ref) else None),
            **phases,
            "telemetry": _telemetry_aux(tracer),
            "memory": _memory_aux(),
            "registry": _registry_aux(),
        },
    }


def transmog_workflow(N: int):
    """The transmog cell's user program (also Phase B of chip_smoke.py):
    mixed text/picklist/map/real columns -> transmogrify -> sanity_check ->
    LR selector, RawFeatureFilter on.  Returns (workflow, batch, schema)."""
    from transmogrifai_tpu.columns import ColumnBatch
    from transmogrifai_tpu.features import features_from_schema
    from transmogrifai_tpu.models.linear import OpLogisticRegression
    from transmogrifai_tpu.ops.transmogrify import transmogrify
    from transmogrifai_tpu.selector import (BinaryClassificationModelSelector,
                                            ModelCandidate, grid)
    from transmogrifai_tpu.workflow import Workflow

    cols, schema = make_transmog_columns(N)
    batch = ColumnBatch(cols, N)

    label, predictors = features_from_schema(schema, response="label")
    fv = transmogrify(predictors)
    checked = label.sanity_check(fv, remove_bad_features=True)
    selector = BinaryClassificationModelSelector(models=[
        ModelCandidate(OpLogisticRegression(),
                       grid(reg_param=[0.01, 0.1], max_iter=[50]),
                       "OpLogisticRegression")])
    selector.set_input(label, checked)
    pred = selector.get_output()

    wf = (Workflow().set_input_batch(batch).set_result_features(pred)
          .with_raw_feature_filter(min_fill_rate=0.01))
    return wf, batch, schema


def run_transmog(N: int, on_accel: bool, platform: str):
    from transmogrifai_tpu.evaluators import Evaluators

    wf, batch, schema = transmog_workflow(N)

    from transmogrifai_tpu.telemetry import Tracer, use_tracer
    tracer = Tracer(run_name=f"bench:transmog:{N}")
    t0 = time.time()
    with use_tracer(tracer):
        model = wf.train()
    wall = time.time() - t0

    metrics = model.evaluate(Evaluators.BinaryClassification.auROC(),
                             batch=batch)
    fv_width = None
    try:
        # width from the fitted coefficients (the feature matrix itself is
        # liveness-pruned from the train batch once the selector consumed it)
        fv_width = int(np.asarray(
            model.selected_model.best_model.fitted["coef"]).shape[0])
    except Exception:
        pass
    baseline = _baseline("transmog1m_train_wall_s")
    lpt8 = _baseline("transmog1m_8core_lpt_s")
    at_ref = on_accel and N == 1_000_000
    vs = (baseline / wall) if (baseline and at_ref) else 1.0
    phases = _phase_split(model)
    return {
        "metric": f"OpWorkflow.train wall (transmogrification {N} rows: "
                  f"3 text->hash512 + 2 picklist + realmap + 4 real w/nulls, "
                  f"RFF on, {platform})",
        "value": round(wall, 2),
        "unit": "s",
        "vs_baseline": round(vs, 3),
        "aux": {
            "train_auroc": round(float(metrics["AuROC"]), 4),
            "rows": N, "platform": platform,
            "feature_vector_width": fv_width,
            "raw_features": len(schema) - 1,
            "vs_baseline_8core_lpt": (round(lpt8 / wall, 3)
                                      if (lpt8 and at_ref) else None),
            **phases,
            "telemetry": _telemetry_aux(tracer),
            "memory": _memory_aux(),
            "registry": _registry_aux(),
        },
    }


def run_score(N: int, on_accel: bool, platform: str):
    """Scoring-path throughput: rows/s of
    ``WorkflowModel.score()`` at transmogrified width (~1.6k columns), warm —
    the number behind compiled.py's one-XLA-program design
    (≙ OpWorkflowModel.score:255 over FitStagesUtil's bulk row map)."""
    from transmogrifai_tpu.columns import ColumnBatch
    from transmogrifai_tpu.features import features_from_schema
    from transmogrifai_tpu.models.linear import OpLogisticRegression
    from transmogrifai_tpu.ops.transmogrify import transmogrify
    from transmogrifai_tpu.selector import (BinaryClassificationModelSelector,
                                            ModelCandidate, grid)
    from transmogrifai_tpu.workflow import Workflow

    cols, schema = make_transmog_columns(N)
    batch = ColumnBatch(cols, N)
    label, predictors = features_from_schema(schema, response="label")
    fv = transmogrify(predictors)
    checked = label.sanity_check(fv, remove_bad_features=True)
    selector = BinaryClassificationModelSelector(models=[
        ModelCandidate(OpLogisticRegression(),
                       grid(reg_param=[0.01], max_iter=[50]),
                       "OpLogisticRegression")])
    selector.set_input(label, checked)
    pred = selector.get_output()
    model = (Workflow().set_input_batch(batch).set_result_features(pred)
             .train())
    pred_name = next(f.name for f in model.result_features)

    fv_width = int(np.asarray(
        model.selected_model.best_model.fitted["coef"]).shape[0])
    # warm-up scores once (compiles + profile caches), then measure a fresh
    # batch so the host prologue (tokenize/encode) is honestly re-paid —
    # repeated scoring of THE SAME batch would hit the column profile cache
    model.score(batch=batch)
    cols2, _ = make_transmog_columns(N, seed=7)
    batch2 = ColumnBatch(cols2, N)
    from transmogrifai_tpu.profiling import host_link_bytes
    link0 = host_link_bytes()
    t0 = time.time()
    scored = model.score(batch=batch2)
    # force materialization of the predictions (async dispatch lies)
    float(np.asarray(scored[pred_name].values["prediction"][:8]).sum())
    wall = time.time() - t0
    rows_per_s = round(N / wall)
    proxy = _baseline("score1m_rows_per_s")
    at_ref = on_accel and N == 1_000_000
    return {
        "metric": f"WorkflowModel.score throughput (transmogrified width "
                  f"{fv_width}, {N} rows, warm, {platform})",
        "value": rows_per_s,
        "unit": "rows/s",
        "vs_baseline": (round(rows_per_s / proxy, 3)
                        if (proxy and at_ref) else 1.0),
        "aux": {"rows": N, "wall_s": round(wall, 2),
                "feature_vector_width": fv_width, "platform": platform,
                "host_link_mb": round((host_link_bytes() - link0) / 1e6, 1)},
    }


def _peak_rss_mb():
    import resource
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def make_sparse_text_columns(n: int, vocab_size: int = 30_000, seed: int = 3):
    """Label-correlated token rows over a large vocabulary (disjoint
    positive/negative halves) + one dense real column."""
    rng = np.random.default_rng(seed)
    half = vocab_size // 2
    vpos = np.asarray([f"pos{i}" for i in range(half)])
    vneg = np.asarray([f"neg{i}" for i in range(half)])
    y = rng.integers(0, 2, n)
    toks_pos = vpos[rng.integers(0, half, size=(n, 8))]
    toks_neg = vneg[rng.integers(0, half, size=(n, 8))]
    txt = np.where(y[:, None] == 1, toks_pos, toks_neg)
    records = [{"label": float(y[i]), "txt": " ".join(txt[i]),
                "x0": float(v)}
               for i, v in enumerate(rng.normal(size=n))]
    return records, y


def run_text_sparse(N: int, on_accel: bool, platform: str):
    """Sparse hashed-text workload: train + score in ONE process with peak
    memory bounded by nnz, not rows x num_hashes (the dense-equivalent
    matrix at the default 100k hash columns would be ``N * 400KB``)."""
    from transmogrifai_tpu.dag import apply_dag
    from transmogrifai_tpu.features import FeatureBuilder
    from transmogrifai_tpu.models.linear import OpLogisticRegression
    from transmogrifai_tpu.ops.transmogrify import transmogrify
    from transmogrifai_tpu.selector import (BinaryClassificationModelSelector,
                                            ModelCandidate, grid)
    from transmogrifai_tpu.profiling import (install_compile_listeners,
                                             new_compile_count,
                                             racing_stats)
    from transmogrifai_tpu.sparse.transform import (reset_sparse_stats,
                                                    sparse_stats)
    from transmogrifai_tpu.workflow import Workflow

    num_hashes = int(os.environ.get("BENCH_SPARSE_HASHES", "100000"))
    records, y = make_sparse_text_columns(N)

    label = FeatureBuilder.RealNN("label").as_response()
    txt = FeatureBuilder.Text("txt").as_predictor()
    x0 = FeatureBuilder.Real("x0").as_predictor()
    fv = transmogrify([txt, x0], num_hashes=num_hashes)
    grid_pts = grid(reg_param=[0.01, 0.1], max_iter=[50])
    selector = BinaryClassificationModelSelector(models=[
        ModelCandidate(OpLogisticRegression(), grid_pts,
                       "OpLogisticRegression")])
    selector.set_input(label, fv)
    pred = selector.get_output()

    reset_sparse_stats()
    install_compile_listeners()
    nc0 = new_compile_count()
    wf = Workflow().set_input_records(records).set_result_features(pred)
    t0 = time.time()
    model = wf.train()
    train_wall = time.time() - t0
    stats = sparse_stats()
    new_compiles = new_compile_count() - nc0
    fits_saved = racing_stats()["cv_fits_saved"]
    n_cands = len(grid_pts)

    # compiled scoring in the SAME process — the acceptance bar is one
    # process training AND scoring with nnz-bounded peak memory
    batch = model.generate_raw_data()
    prog = model.score_program()
    t0 = time.time()
    scored = prog(batch)
    pred_vals = np.asarray(scored[pred.name].values["prediction"])
    score_wall = time.time() - t0
    acc = float((pred_vals == y).mean())

    peak_mb = _peak_rss_mb()
    dense_equiv_mb = N * num_hashes * 4 / 1e6
    return {
        "metric": f"OpWorkflow.train wall (sparse text {N} rows x "
                  f"{num_hashes} hashed cols, 3-fold CV LR grid, {platform})",
        "value": round(train_wall, 2),
        "unit": "s",
        "vs_baseline": 1.0,
        "aux": {
            "rows": N, "num_hashes": num_hashes, "platform": platform,
            "train_accuracy": round(acc, 4),
            "best_model": model.selected_model.summary.best_model_name,
            "score_wall_s": round(score_wall, 2),
            "score_rows_per_s": round(N / max(score_wall, 1e-9)),
            "nnz_total": stats["nnz_total"],
            "density": round(stats["density"], 6),
            "peak_rss_mb": round(peak_mb, 1),
            "dense_equivalent_mb": round(dense_equiv_mb, 1),
            "rss_vs_dense_equivalent": round(peak_mb / dense_equiv_mb, 4),
            # mesh-scaling instrumentation (ISSUE 19): same contract as the
            # dense workload so run_text_sparse_mesh can curve rows/s vs
            # device count and pin winner parity across shardings
            "cv_fits": 3 * n_cands - fits_saved,
            "cv_fits_saved_by_racing": fits_saved,
            "new_compiles_during_train": new_compiles,
            "cv_fit_rows_per_s": round(
                (3 * n_cands - fits_saved) * (2 * N / 3)
                / max(train_wall, 1e-9)),
            "degraded_mesh_notes": len(
                [e for e in model.failure_log.events
                 if e.action == "degraded"
                 and e.point in ("selector.racing", "selector.mesh")]),
            "telemetry": _telemetry_aux(None),
            "memory": _memory_aux(),
            "registry": _registry_aux(),
        },
    }


def run_serving_chaos(on_accel: bool, platform: str):
    """Closed-loop chaos SLO drill (ISSUE 8): the scripts/chaos_slo.py
    harness at bench scale — N concurrent clients against the real HTTP
    server with serving.batch/serving.reload faults injected.  The metric
    is accepted-request p99; the aux carries the full outcome contract
    (every request 2xx/429/503, breaker demote + half-open recovery) so a
    serving-robustness regression shows up in the bench artifact."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "scripts"))
    from chaos_slo import run_chaos_slo

    clients = int(os.environ.get("BENCH_CHAOS_CLIENTS", "32"))
    requests = int(os.environ.get("BENCH_CHAOS_REQUESTS", "10"))
    t0 = time.perf_counter()
    summary = run_chaos_slo(clients=clients, requests_per_client=requests,
                            batch_fault_rate=0.08, reload_fault_rate=0.25,
                            seed=0, request_deadline_s=15.0)
    wall = time.perf_counter() - t0
    return {"metric": f"serving chaos SLO accepted p99 "
                      f"({clients} clients x {requests} reqs, "
                      f"8%/25% faults) [{platform}]",
            "value": summary["acceptedP99S"], "unit": "s",
            "vs_baseline": 0.0,
            "aux": {"passed": summary["passed"],
                    "checks": summary["checks"],
                    "outcomes": summary["outcomes"],
                    "faults_fired": summary["faultsFired"],
                    "breaker_transitions": summary["breakerTransitions"],
                    "failure_summary": summary["failureSummary"],
                    "storm_seconds": summary["stormSeconds"],
                    "wall_seconds": round(wall, 2)}}


# fresh-process serve probe: loads the bundle, scores ONE record, reports
# compile/trace activity.  Run as `python -c` so the measured process has
# nothing warm — no jax client, no caches, no imported modules.
_COLD_START_CHILD = r"""
import json, sys, time
t0 = time.time()
from transmogrifai_tpu.serving.engine import ScoringEngine
from transmogrifai_tpu.profiling import (install_compile_listeners,
                                         new_compile_count)
from transmogrifai_tpu.compiled import trace_count
install_compile_listeners()  # count compiles from the very first dispatch
eng = ScoringEngine(sys.argv[1], max_batch=int(sys.argv[2]), linger_ms=0.0)
out = eng.score_record({"age": 31.0, "income": 5000.0, "city": "ny"})
first = time.time() - t0
stats = eng.stats()
eng.close()
import jax
print(json.dumps({"first_score_s": round(first, 3),
                  "new_compiles": new_compile_count(),
                  "traces": trace_count(),
                  "aot_executables": stats.get("aot_executables", 0),
                  "device": {"platform": jax.devices()[0].platform,
                             "kind": jax.devices()[0].device_kind,
                             "count": len(jax.devices())}}))
"""


def step_cold_start_train(bundle: str) -> dict:
    """Child step of serve_cold_start: train the small LR model and save the
    AOT bundle, in a process that then exits and frees the chip."""
    from transmogrifai_tpu.features import FeatureBuilder
    from transmogrifai_tpu.models.linear import OpLogisticRegression
    from transmogrifai_tpu.ops.transmogrify import transmogrify
    from transmogrifai_tpu.selector import (BinaryClassificationModelSelector,
                                            ModelCandidate, grid)
    from transmogrifai_tpu.workflow import Workflow

    n = int(os.environ.get("BENCH_COLD_START_ROWS", "2000"))
    rng = np.random.default_rng(5)
    cities = ("ny", "sf", "la", "chi")
    records = []
    for i in range(n):
        age = float(rng.normal(40, 10))
        income = float(rng.normal(5000, 1000))
        records.append({
            "label": float(age / 40.0 + rng.normal() > 1.0),
            "age": age, "income": income,
            "city": cities[int(rng.integers(0, len(cities)))]})

    label = FeatureBuilder.RealNN("label").as_response()
    preds = [FeatureBuilder.Real("age").as_predictor(),
             FeatureBuilder.Real("income").as_predictor(),
             FeatureBuilder.PickList("city").as_predictor()]
    fv = transmogrify(preds)
    sel = BinaryClassificationModelSelector(models=[
        ModelCandidate(OpLogisticRegression(),
                       grid(reg_param=[0.01], max_iter=[30]),
                       "OpLogisticRegression")])
    sel.set_input(label, fv)
    wf = (Workflow().set_input_records(records)
          .set_result_features(sel.get_output()))
    model = wf.train()
    t0 = time.time()
    model.save(bundle)
    return {"rows_trained": n, "save_wall_s": round(time.time() - t0, 2)}


def run_serve_cold_start(launch: "Launch"):
    """Serve cold start (ISSUE 9 tentpole): train + save a bundle carrying
    AOT-serialized executables, then measure fresh-process time-to-first-score
    twice — once installing the shipped executables, once forced onto the JIT
    path (TRANSMOGRIFAI_NO_AOT=1).  The headline is the AOT number; the aux
    carries `new_compiles_at_serve` (the acceptance bar: 0) and the JIT
    baseline wall so the killed compile time is visible in the artifact.

    Orchestrated from the launcher: three children, one on the chip at a
    time (train+save, AOT serve, JIT serve)."""
    import shutil
    import tempfile

    max_batch = int(os.environ.get("BENCH_COLD_START_MAX_BATCH", "64"))
    out_dir = tempfile.mkdtemp(prefix="bench-cold-start-")
    try:
        bundle = os.path.join(out_dir, "model")
        trained = launch.child(["--step", "cold_start_train", bundle])
        aot = launch.child(["-c", _COLD_START_CHILD, bundle, str(max_batch)],
                           script=False)
        jit = launch.child(["-c", _COLD_START_CHILD, bundle, str(max_batch)],
                           script=False, env={"TRANSMOGRIFAI_NO_AOT": "1"})
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)
    return {
        "metric": f"serve cold start: fresh-process time to first score "
                  f"(AOT bundle, max_batch={max_batch}, {launch.platform})",
        "value": aot["first_score_s"],
        "unit": "s",
        "vs_baseline": 1.0,
        "device": aot["device"],
        "aux": {
            "rows_trained": trained["rows_trained"],
            "platform": launch.platform,
            "new_compiles_at_serve": aot["new_compiles"],
            "traces_at_serve": aot["traces"],
            "aot_executables": aot["aot_executables"],
            "cold_start_noaot_s": jit["first_score_s"],
            "noaot_new_compiles": jit["new_compiles"],
            "noaot_traces": jit["traces"],
            "speedup_vs_jit": round(
                jit["first_score_s"] / max(aot["first_score_s"], 1e-9), 2),
            "save_wall_s": trained["save_wall_s"],
        },
    }


def run_multi_tenant(on_accel: bool, platform: str):
    """Multi-tenant serving (ISSUE 16 tentpole): one TenantRegistry over a
    model root of per-tenant bundles, driven by a deterministic skewed
    popularity sequence with ``max_active`` below the tenant count — so the
    LRU activation/eviction churn is part of the measured wall, exactly as
    a consolidation deployment would pay it.  Headline: aggregate rows/s
    across all tenants.  Aux: cold-tenant first-score latency (activation +
    first batch), activation/eviction counts, per-tenant request mix."""
    import shutil
    import tempfile

    from transmogrifai_tpu.features import FeatureBuilder
    from transmogrifai_tpu.models.linear import OpLogisticRegression
    from transmogrifai_tpu.ops.transmogrify import transmogrify
    from transmogrifai_tpu.selector import (BinaryClassificationModelSelector,
                                            ModelCandidate, grid)
    from transmogrifai_tpu.serving.tenants import TenantRegistry
    from transmogrifai_tpu.workflow import Workflow

    n_train = int(os.environ.get("BENCH_TENANT_TRAIN_ROWS", "1000"))
    requests = int(os.environ.get(
        "BENCH_TENANT_REQUESTS", "600" if on_accel else "240"))
    rows_per_request = 8
    rng = np.random.default_rng(9)
    cities = ("ny", "sf", "la", "chi")
    records = []
    for i in range(n_train):
        age = float(rng.normal(40, 10))
        income = float(rng.normal(5000, 1000))
        records.append({
            "label": float(age / 40.0 + rng.normal() > 1.0),
            "age": age, "income": income,
            "city": cities[int(rng.integers(0, len(cities)))]})
    label = FeatureBuilder.RealNN("label").as_response()
    preds = [FeatureBuilder.Real("age").as_predictor(),
             FeatureBuilder.Real("income").as_predictor(),
             FeatureBuilder.PickList("city").as_predictor()]
    sel = BinaryClassificationModelSelector(models=[
        ModelCandidate(OpLogisticRegression(),
                       grid(reg_param=[0.01], max_iter=[30]),
                       "OpLogisticRegression")])
    sel.set_input(label, transmogrify(preds))
    model = (Workflow().set_input_records(records)
             .set_result_features(sel.get_output()).train())

    tenants = [f"tenant-{i}" for i in range(6)]
    # skewed popularity, worst-case for a 3-slot LRU: the tail tenants
    # almost always re-activate from disk
    weights = [0.40, 0.25, 0.15, 0.10, 0.06, 0.04]
    max_active = 3
    root = tempfile.mkdtemp(prefix="bench-tenants-")
    try:
        control = os.path.join(root, ".control")  # dotted: not a tenant
        model.save(control)
        for t in tenants:
            shutil.copytree(control, os.path.join(root, t))
        seq = np.random.default_rng(7).choice(
            len(tenants), size=requests, p=weights)
        batch = [{"age": 30.0 + i, "income": 4000.0 + 100.0 * i,
                  "city": cities[i % len(cities)]}
                 for i in range(rows_per_request)]
        registry = TenantRegistry(root, max_batch=32, queue_bound=256,
                                  max_active=max_active,
                                  memory_budget_bytes=1 << 30)
        try:
            t0 = time.perf_counter()
            registry.engine_for(tenants[0]).score_record(
                batch[0], timeout_s=300.0)
            cold_first_score_s = time.perf_counter() - t0

            mix = dict.fromkeys(tenants, 0)
            t0 = time.perf_counter()
            for idx in seq:
                registry.engine_for(tenants[idx]).score_records(
                    batch, timeout_s=300.0)
                mix[tenants[idx]] += 1
            storm_wall = time.perf_counter() - t0
            status = registry.status()
        finally:
            registry.close()
    finally:
        shutil.rmtree(root, ignore_errors=True)
    rows_scored = requests * rows_per_request
    activations = sum(info["activations"]
                      for info in status["tenants"].values())
    evictions = sum(info["evictions"]
                    for info in status["tenants"].values())
    return {
        "metric": f"multi-tenant serving: aggregate throughput, "
                  f"{len(tenants)} tenants / max_active={max_active}, "
                  f"skewed popularity ({platform})",
        "value": round(rows_scored / max(storm_wall, 1e-9), 1),
        "unit": "rows/s",
        "vs_baseline": 1.0,
        "aux": {
            "platform": platform,
            "tenants": len(tenants),
            "max_active": max_active,
            "popularity": weights,
            "requests": requests,
            "rows_per_request": rows_per_request,
            "storm_wall_s": round(storm_wall, 3),
            "cold_tenant_first_score_s": round(cold_first_score_s, 3),
            "activations": activations,
            "evictions": evictions,
            "request_mix": mix,
            "tenants_active_at_end": status["tenantsActive"],
        },
    }


def step_scaleout_train(bundle: str, max_batch: str) -> dict:
    """Child step of serve_scaleout: train the numeric-only model and save
    its AOT bundle with the ladder raised to the request size."""
    from transmogrifai_tpu.features import FeatureBuilder
    from transmogrifai_tpu.models.linear import OpLogisticRegression
    from transmogrifai_tpu.ops.transmogrify import transmogrify
    from transmogrifai_tpu.selector import (BinaryClassificationModelSelector,
                                            ModelCandidate, grid)
    from transmogrifai_tpu.workflow import Workflow

    # numeric-only model: the serving data plane (wire decode, batching,
    # HTTP) is the thing under test, so feature extraction stays trivial —
    # a PickList would put host-side dict/string work back on the hot path
    rng = np.random.default_rng(11)
    records = []
    for _ in range(4000):
        x1 = float(rng.normal())
        x2 = float(rng.uniform(0, 10))
        records.append({"y": float(x1 + 0.2 * x2 + rng.normal() * 0.3 > 1.0),
                        "x1": x1, "x2": x2})
    y = FeatureBuilder.RealNN("y").as_response()
    preds = [FeatureBuilder.Real("x1").as_predictor(),
             FeatureBuilder.Real("x2").as_predictor()]
    sel = BinaryClassificationModelSelector(models=[
        ModelCandidate(OpLogisticRegression(),
                       grid(reg_param=[0.01], max_iter=[30]),
                       "OpLogisticRegression")])
    sel.set_input(y, transmogrify(preds))
    model = (Workflow().set_input_records(records)
             .set_result_features(sel.get_output()).train())
    os.environ["TRANSMOGRIFAI_AOT_LADDER_MAX"] = max_batch
    model.save(bundle)
    return {"rows_trained": len(records)}


def run_serve_scaleout(launch: "Launch"):
    """Serving scale-out (ISSUE 12 tentpole): closed-loop load against the
    SO_REUSEPORT worker pool on the columnar wire format, swept over client
    concurrency.  Three measurements share one AOT bundle and artifact:

    * ``json_single``   — 1 worker, JSON list bodies (the standing path,
      the honest control);
    * ``columnar_single`` — 1 worker, packed columnar bodies (wire-format
      win in isolation);
    * ``columnar_pool`` — N workers, columnar (the headline: target >=10x
      the standing warm-score throughput at accepted-p99 < 10ms).

    The headline picks the best sweep point that holds the 10ms p99 SLO;
    every point is recorded in the aux so a miss is visible, not hidden.

    Orchestrated from the launcher, which only generates load: a child
    trains + saves and exits, then each pool's workers own the chips (one
    worker per chip — the pool refuses more)."""
    import shutil
    import tempfile
    import urllib.error
    import urllib.request

    from transmogrifai_tpu.serving import wire
    from transmogrifai_tpu.serving.pool import ServingPool

    workers_requested = int(os.environ.get("BENCH_SCALEOUT_WORKERS", "2"))
    # an accelerator host serves one worker per chip; a CPU host timeshares
    workers = (min(workers_requested, launch.device_count)
               if launch.on_accel else workers_requested)
    batch = int(os.environ.get("BENCH_SCALEOUT_BATCH", "2048"))
    seconds = float(os.environ.get("BENCH_SCALEOUT_SECONDS", "6"))
    max_batch = int(os.environ.get("BENCH_SCALEOUT_MAX_BATCH", str(batch)))
    sweep = [int(c) for c in os.environ.get(
        "BENCH_SCALEOUT_CLIENTS", "1,2,4").split(",") if c.strip()]
    slo_s = 0.010

    out_dir = tempfile.mkdtemp(prefix="bench-scaleout-")
    bundle = os.path.join(out_dir, "model")
    try:
        # the bundle is for one-chip workers: export it where one chip is
        # visible, or its ABI stamp (device count) keeps every worker's AOT
        # executables from installing
        from transmogrifai_tpu.parallel.supervisor import single_chip_env
        launch.child(["--step", "scaleout_train", bundle, str(max_batch)],
                     env=single_chip_env(0) if launch.on_accel else None)
    except BaseException:
        shutil.rmtree(out_dir, ignore_errors=True)
        raise
    rng = np.random.default_rng(12)

    # one request body per wire format, built once outside the timed loop
    xs1 = rng.normal(size=batch)
    xs2 = rng.uniform(0, 10, size=batch)
    reqs = [{"x1": float(xs1[i]), "x2": float(xs2[i])}
            for i in range(batch)]
    json_body = json.dumps(reqs).encode()
    col_body = wire.encode_records(reqs)

    def percentile(values, q):
        if not values:
            return 0.0
        xs = sorted(values)
        import math
        return xs[min(len(xs) - 1, max(0, math.ceil(q * len(xs)) - 1))]

    def storm(port, body, ctype, clients):
        stop_at = time.monotonic() + seconds
        lock = threading.Lock()
        lat, errors = [], {}
        rows_ok = [0]

        def client():
            url = f"http://127.0.0.1:{port}/v1/score"
            while time.monotonic() < stop_at:
                t0 = time.perf_counter()
                klass = None
                try:
                    rq = urllib.request.Request(
                        url, data=body, headers={"Content-Type": ctype})
                    with urllib.request.urlopen(rq, timeout=60.0) as r:
                        r.read()
                        ok = 200 <= r.status < 300
                except urllib.error.HTTPError as e:
                    e.read()
                    ok, klass = False, str(e.code)
                except Exception as e:  # noqa: BLE001 — closed loop: any
                    ok, klass = False, type(e).__name__  # error is counted
                dt = time.perf_counter() - t0
                with lock:
                    if ok:
                        lat.append(dt)
                        rows_ok[0] += batch
                    else:
                        errors[klass] = errors.get(klass, 0) + 1

        threads = [threading.Thread(target=client, daemon=True)
                   for _ in range(clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=seconds + 120.0)
        wall = time.perf_counter() - t0
        return {"clients": clients,
                "rows_per_s": round(rows_ok[0] / wall) if wall else 0,
                "accepted_p99_s": round(percentile(lat, 0.99), 5),
                "accepted_p50_s": round(percentile(lat, 0.50), 5),
                "requests_ok": len(lat), "errors": errors,
                "wall_s": round(wall, 2)}

    def measure(n_workers, body, ctype):
        pool = ServingPool(
            bundle, workers=n_workers, max_batch=max_batch,
            queue_bound=batch * max(max(sweep), 4) * 4,
            request_deadline_s=60.0,
            # static admission: AIMD tuned for record traffic would clamp
            # the very first multi-thousand-row batch and shed the storm
            overload={"adaptive": False, "latency_target_ms": 1000.0},
            run_dir=os.path.join(out_dir, f"pool-{n_workers}-{ctype[-8:]}"))
        try:
            pool.start()
            # where the workers say they compute, not where the launcher's
            # probe expected them to (the pool already refused a mismatch
            # with what IT resolved)
            devices = [w["device"] for w in pool.status()["workerList"]]
            if any(d["platform"] != launch.platform for d in devices):
                raise CellFailed(
                    f"serve_scaleout: pool workers report {devices}, the "
                    f"launcher probed {launch.platform}")
            # one warm round-trip per worker-count so the first timed
            # request doesn't pay connection setup
            storm_points = []
            _ = storm(pool.port, body, ctype, 1)
            for clients in sweep:
                storm_points.append(storm(pool.port, body, ctype, clients))
        finally:
            pool.stop(grace_s=30.0)
        within = [p for p in storm_points if p["accepted_p99_s"] <= slo_s
                  and p["requests_ok"] > 0]
        best = (max(within, key=lambda p: p["rows_per_s"]) if within
                else max(storm_points, key=lambda p: p["rows_per_s"]))
        return {"best": best, "slo_met": bool(within),
                "sweep": storm_points, "worker_devices": devices}

    try:
        json_single = measure(1, json_body, "application/json")
        col_single = measure(1, col_body, wire.CONTENT_TYPE)
        col_pool = (measure(workers, col_body, wire.CONTENT_TYPE)
                    if workers > 1 else col_single)
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)

    standing = 57_000.0  # BENCH_STANDING warm model.score rows/s (r5)
    try:
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "BENCH_STANDING.json")) as fh:
            runs = json.load(fh).get("runs", [])
        if runs:
            standing = float(
                runs[-1]["workloads"]["score"]["value"]) or standing
    except (OSError, KeyError, ValueError, TypeError):
        pass

    head = col_pool["best"]
    return {
        "metric": f"serve scale-out: columnar {workers}-worker pool "
                  f"throughput at p99<10ms ({batch}-row requests, "
                  f"{launch.platform})",
        "value": head["rows_per_s"],
        "unit": "rows/s",
        "vs_baseline": round(head["rows_per_s"] / standing, 2),
        "device": launch.device,
        "aux": {
            "workers": workers, "workers_requested": workers_requested,
            "slo_met": col_pool["slo_met"],
            "standing_warm_score_rows_per_s": standing,
            "batch_rows": batch, "max_batch": max_batch,
            "seconds_per_point": seconds, "client_sweep": sweep,
            "columnar_pool": col_pool,
            "columnar_single": col_single,
            "json_single_control": json_single,
            "columnar_vs_json_single": round(
                col_single["best"]["rows_per_s"]
                / max(json_single["best"]["rows_per_s"], 1), 2),
            # honest note: this container timeshares every worker AND the
            # load generator on the same core count; on a real multi-core
            # host the pool points spread across cores instead
            "cpu_count": os.cpu_count(),
        },
    }


def run_selector_smoke(on_accel: bool, platform: str):
    """Multiclass + regression selector sweeps on the fused-panel hot path:
    counts selector.batched_metrics fallback events (must be 0) so a
    regression that silently demotes either family to the per-candidate
    path shows up in the bench artifact."""
    from transmogrifai_tpu.columns import Column, ColumnBatch
    from transmogrifai_tpu.features import FeatureBuilder
    from transmogrifai_tpu.selector import (MultiClassificationModelSelector,
                                            RegressionModelSelector)
    from transmogrifai_tpu.types import RealNN
    from transmogrifai_tpu.workflow import Workflow

    n = int(os.environ.get("BENCH_SELECTOR_SMOKE_ROWS", "4000"))
    d = 16
    rng = np.random.default_rng(11)

    def train(selector_cls, y, X, models):
        label = FeatureBuilder.RealNN("label").as_response()
        feats = [FeatureBuilder.RealNN(f"f{i}").as_predictor()
                 for i in range(d)]
        from transmogrifai_tpu.ops.transmogrify import transmogrify
        fv = transmogrify(feats)
        sel = selector_cls(models=models)
        sel.set_input(label, fv)
        cols = {"label": Column(RealNN, y.astype(np.float32))}
        for i in range(d):
            cols[f"f{i}"] = Column(RealNN, X[:, i].astype(np.float32))
        batch = ColumnBatch(cols, n)
        wf = (Workflow().set_input_batch(batch)
              .set_result_features(sel.get_output()))
        t0 = time.time()
        model = wf.train()
        return model, time.time() - t0

    def fallbacks(model):
        # train() scopes its own FailureLog on the returned model
        return sum(1 for e in model.failure_log.to_json()
                   if e.get("point") == "selector.batched_metrics")

    C = 4
    ym = rng.integers(0, C, n)
    centers = rng.normal(size=(C, d)) * 2.5
    Xm = (centers[ym] + rng.normal(size=(n, d))).astype(np.float32)

    w = rng.normal(size=d).astype(np.float32)
    Xr = rng.normal(size=(n, d)).astype(np.float32)
    yr = Xr @ w + 0.3 * rng.normal(size=n).astype(np.float32)

    mc_model, mc_wall = train(
        MultiClassificationModelSelector, ym, Xm,
        MultiClassificationModelSelector.compact_models())
    reg_model, reg_wall = train(RegressionModelSelector, yr, Xr,
                                RegressionModelSelector.compact_models())
    fb = fallbacks(mc_model) + fallbacks(reg_model)
    mc_sum = mc_model.selected_model.summary
    reg_sum = reg_model.selected_model.summary
    return {
        "metric": f"multiclass+regression selector smoke wall "
                  f"({n} rows x {d}, compact grids, {platform})",
        "value": round(mc_wall + reg_wall, 2),
        "unit": "s",
        "vs_baseline": 1.0,
        "aux": {
            "rows": n, "platform": platform,
            "multiclass_wall_s": round(mc_wall, 2),
            "multiclass_best_model": mc_sum.best_model_name,
            "regression_wall_s": round(reg_wall, 2),
            "regression_best_model": reg_sum.best_model_name,
            "batched_metric_fallbacks": fb,
        },
    }


def _mesh_point_env(launch: "Launch", k: int) -> dict:
    """Child environment that makes a fresh process see exactly ``k``
    devices: forced host devices on the CPU backend; on an accelerator one
    chip (restricted from outside the program) or all of them — a point is
    never labelled with a count the child cannot be held to."""
    env = {"TRANSMOGRIFAI_TPU_MESH": "1" if k > 1 else "0"}
    if not launch.on_accel:
        env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={k} "
                            + os.environ.get("XLA_FLAGS", ""))
    elif k == 1:
        from transmogrifai_tpu.parallel.supervisor import single_chip_env
        env.update(single_chip_env(0))
    elif k != launch.device_count:
        raise CellFailed(
            f"BENCH_MESH_DEVICES point {k}: this host shows "
            f"{launch.device_count} {launch.platform} devices and a child "
            f"can be held to 1 or to all of them")
    return env


def _mesh_counts(launch: "Launch"):
    default = f"1,{launch.device_count}" if launch.on_accel else "1,8"
    counts = [int(c) for c in os.environ.get(
        "BENCH_MESH_DEVICES", default).split(",") if c.strip()]
    return sorted(set(counts))


def _host_cores() -> int:
    try:
        return len(os.sched_getaffinity(0))
    except AttributeError:
        return os.cpu_count() or 1


def _mesh_point(launch: "Launch", k: int, cell: str, env: dict) -> dict:
    rec = launch.child(["--cell", cell], env={**_mesh_point_env(launch, k),
                                              **env})
    if rec["device"]["count"] != k:
        raise CellFailed(f"mesh point {k}: the child saw "
                         f"{rec['device']['count']} devices")
    return rec


def _curve_aux(launch: "Launch", counts, points) -> dict:
    """What both device-count curves report about their points: winner
    parity, the rows/s ratio of the widest point to the narrowest, and —
    on the CPU backend — that forced host devices share the cores."""
    host_cores = _host_cores()
    base, top = points[str(counts[0])], points[str(counts[-1])]
    speedup = None
    if base["cv_fit_rows_per_s"] and top["cv_fit_rows_per_s"]:
        speedup = round(top["cv_fit_rows_per_s"]
                        / base["cv_fit_rows_per_s"], 3)
    return {
        "platform": launch.platform, "host_cores": host_cores,
        "device_counts": counts, "points": points,
        "winner_parity": len({p["winner"] for p in points.values()}) == 1,
        "speedup_max_vs_min_devices": speedup,
        "simulated_mesh": not launch.on_accel,
        "note": (None if launch.on_accel or host_cores >= max(counts)
                 else f"forced host devices share {host_cores} core(s); "
                      "rows/s scaling requires real parallel hardware"),
    }


def run_mesh_sweep(launch: "Launch"):
    """`cv_fit_rows_per_s` vs device-count curve for the mesh-sharded sweep
    (ISSUE 10).  Each point runs the dense CV grid in a fresh child process
    that sees exactly K devices (see `_mesh_point_env`), with
    TRANSMOGRIFAI_TPU_MESH forced on for K > 1 and racing live on every
    point.  On the CPU backend the curve is honest about its substrate:
    forced host devices TIMESHARE the host's cores, so scaling past
    `host_cores` measures GSPMD overhead, not speedup."""
    N = launch.rows("BENCH_MESH_ROWS", 1_000_000, 65_536)
    counts = _mesh_counts(launch)
    fams = os.environ.get("BENCH_MESH_FAMILIES", "lr")
    points = {}
    for k in counts:
        rec = _mesh_point(launch, k, "dense",
                          {"BENCH_ROWS": str(N), "BENCH_FAMILIES": fams})
        aux = rec["aux"]
        points[str(k)] = {
            "wall_s": rec["value"], "device": rec["device"],
            "cv_fit_rows_per_s": aux.get("cv_fit_rows_per_s"),
            "winner": aux.get("best_model"),
            "cv_fits_saved_by_racing": aux.get("cv_fits_saved_by_racing"),
            "path": (aux.get("telemetry") or {}).get("path"),
            "mesh": (aux.get("telemetry") or {}).get("mesh"),
            "host_to_device_bytes_total": (aux.get("telemetry") or {}).get(
                "host_to_device_bytes_total"),
        }
    aux = _curve_aux(launch, counts, points)
    top = points[str(counts[-1])]
    return {
        "metric": f"mesh-sharded CV sweep rows/s curve (dense {N} rows, "
                  f"families={fams}, devices={counts}, {launch.platform})",
        "value": top["cv_fit_rows_per_s"] or 0,
        "unit": "rows/s",
        "vs_baseline": aux["speedup_max_vs_min_devices"] or 0.0,
        "device": top["device"],
        "aux": {"rows": N, **aux},
    }


def run_text_sparse_mesh(launch: "Launch"):
    """`cv_fit_rows_per_s` vs device-count curve for the MESH-SHARDED SPARSE
    sweep (ISSUE 19 headline): each point runs the hashed-text text_sparse
    workload in a fresh child that sees exactly K devices, with
    TRANSMOGRIFAI_TPU_MESH forced for K > 1 — the DeviceTable entry stream
    is what makes K > 1 possible at all for COO payloads.  Winner parity
    across shardings is pinned in the aux, along with each point's
    `device_table.*` telemetry and nnz-based memory plan.  A second phase
    trains the same sparse model twice against one fresh fleet registry
    (fresh processes, single device) and reports both runs' compile counts
    and the second run's registry hits.  The persistent compile cache stays
    where the environment put it, so `new_compiles_during_train` of the
    first run is only cold when that cache is."""
    import tempfile

    N = launch.rows("BENCH_SPARSE_MESH_ROWS", 100_000, 5_000)
    counts = _mesh_counts(launch)
    size = {"BENCH_SPARSE_ROWS": str(N)}

    points = {}
    for k in counts:
        rec = _mesh_point(launch, k, "text_sparse", size)
        aux = rec["aux"]
        points[str(k)] = {
            "wall_s": rec["value"], "device": rec["device"],
            "cv_fit_rows_per_s": aux.get("cv_fit_rows_per_s"),
            "winner": aux.get("best_model"),
            "cv_fits_saved_by_racing": aux.get("cv_fits_saved_by_racing"),
            "degraded_mesh_notes": aux.get("degraded_mesh_notes"),
            "nnz_total": aux.get("nnz_total"),
            "device_table": (aux.get("telemetry") or {}).get("device_table"),
            "path": (aux.get("telemetry") or {}).get("path"),
            "memory_plan": (aux.get("memory") or {}).get("plan"),
        }
    # registry phase: first run publishes, then a FRESH process re-trains
    # and its grid-fit programs install from the fleet registry.  Single
    # device (the registry seam serves unsharded programs; sharded leaves go
    # through GSPMD layouts the publish side never saw).
    registry = {}
    if os.environ.get("BENCH_SPARSE_REGISTRY", "1") != "0":
        with tempfile.TemporaryDirectory(prefix="bench-sparse-reg-") as root:
            reg_env = {**size, "TRANSMOGRIFAI_AOT_REGISTRY": root}
            first = _mesh_point(launch, 1, "text_sparse", reg_env)["aux"]
            second = _mesh_point(launch, 1, "text_sparse", reg_env)["aux"]
            registry = {
                "first_new_compiles_during_train":
                    first.get("new_compiles_during_train"),
                "second_new_compiles_during_train":
                    second.get("new_compiles_during_train"),
                "second_registry": second.get("registry"),
            }

    aux = _curve_aux(launch, counts, points)
    top = points[str(counts[-1])]
    return {
        "metric": f"mesh-sharded SPARSE CV sweep rows/s curve "
                  f"(hashed text {N} rows, devices={counts}, "
                  f"{launch.platform})",
        "value": top["cv_fit_rows_per_s"] or 0,
        "unit": "rows/s",
        "vs_baseline": aux["speedup_max_vs_min_devices"] or 0.0,
        "device": top["device"],
        "aux": {"rows": N, **aux, "registry_warm": registry},
    }


def last_json_line(stdout: str):
    """The last JSON result line of a bench process' stdout (shared with
    scripts/run_scale_bench.py)."""
    return next((ln for ln in reversed(stdout.splitlines())
                 if ln.startswith("{")), None)


class CellFailed(Exception):
    """A cell (or one of its children) did not produce a result."""


def _rows(env: str, default_accel: int, default_cpu: int, on_accel: bool):
    v = os.environ.get(env, "").strip()
    if not v:
        return default_accel if on_accel else default_cpu
    try:
        r = int(float(v))
    except (ValueError, OverflowError):
        sys.exit(f"{env}={v!r} is not a usable row count")
    if r < 1000:
        sys.exit(f"{env}={r} too small (need >= 1000)")
    return r


class Launch:
    """What the launcher knows about the device — from the supervisor's
    subprocess probe, because the launcher itself never opens a backend (a
    chip belongs to one process, and every child needs it) — and how it
    starts a child that owns the chip for its lifetime."""

    def __init__(self, verdict):
        self.platform = verdict.platform
        self.device_count = verdict.device_count
        self.device = {"platform": verdict.platform,
                       "kind": verdict.device_kind,
                       "count": verdict.device_count}
        self.on_accel = verdict.platform != "cpu"

    def rows(self, env, default_accel, default_cpu):
        return _rows(env, default_accel, default_cpu, self.on_accel)

    def child(self, argv, *, script=True, env=None) -> dict:
        """Run one child to its end; returns its last JSON stdout line.  The
        platform is pinned in its environment, so a child that cannot get
        the probed device fails instead of continuing on the CPU."""
        from transmogrifai_tpu.parallel.supervisor import run_supervised
        cmd = [sys.executable] + (
            [os.path.abspath(__file__)] if script else []) + list(argv)
        r = run_supervised(
            cmd, timeout_s=int(os.environ.get("BENCH_CHILD_TIMEOUT_S",
                                              "2400")),
            grace_s=30.0,
            env={**os.environ, "JAX_PLATFORMS": self.platform, **(env or {})})
        line = last_json_line(r.stdout)
        if r.rc != 0 or not line:
            what = " ".join(a for a in argv[:2] if "\n" not in a)
            raise CellFailed(f"child `{what}` rc={r.rc}"
                             f"{' (timed out)' if r.timed_out else ''}: "
                             f"{(r.stderr or '')[-2000:]}")
        return json.loads(line)


# cells that run in ONE process which owns the chip for its lifetime
DEVICE_CELLS = {
    "dense": lambda a, p: run_dense(
        _rows("BENCH_ROWS", 1_000_000, 100_000, a), a, p),
    "transmog": lambda a, p: run_transmog(
        _rows("BENCH_TRANSMOG_ROWS", 1_000_000, 20_000, a), a, p),
    "score": lambda a, p: run_score(
        _rows("BENCH_SCORE_ROWS", 1_000_000, 20_000, a), a, p),
    "text_sparse": lambda a, p: run_text_sparse(
        _rows("BENCH_SPARSE_ROWS", 100_000, 5_000, a), a, p),
    "selector_smoke": run_selector_smoke,
    "serving_chaos": run_serving_chaos,
    "multi_tenant": run_multi_tenant,
}
# cells the launcher orchestrates: several processes, one on the chip at a
# time (a train+save child that exits, then the serving / per-point children)
ORCHESTRATED_CELLS = {
    "mesh_sweep": run_mesh_sweep,
    "text_sparse_mesh": run_text_sparse_mesh,
    "serve_cold_start": run_serve_cold_start,
    "serve_scaleout": run_serve_scaleout,
}
STEPS = {"cold_start_train": step_cold_start_train,
         "scaleout_train": step_scaleout_train}
CELL_ORDER = ("dense", "transmog", "score", "text_sparse", "selector_smoke",
              "mesh_sweep", "text_sparse_mesh", "serving_chaos",
              "serve_cold_start", "serve_scaleout", "multi_tenant")


def run_in_this_process(kind: str, name: str, args) -> int:
    """`--cell NAME` / `--step NAME ARGS`: the process that owns the chip.
    Prints one JSON line naming the device it ran on; any failure is the
    process's failure (traceback, exit 1) — nothing is retried."""
    import jax
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if kind == "step":
        rec = STEPS[name](*args)
    else:
        rec = DEVICE_CELLS[name](dev.platform != "cpu", dev.platform)
    print(json.dumps({**rec, "device": device}), flush=True)
    return 0


def probe(cpu_smoke: bool):
    """The pre-flight probe: what does a FRESH process get from jax?  Run in
    a subprocess (the supervisor's, SIGTERM->SIGKILL escalation on a hung
    init, deterministic BENCH_PROBE_BACKOFFS schedule) so the launcher stays
    off the backend.  The measuring path expects an accelerator; the
    separately named `--cpu-smoke` correctness run pins the CPU."""
    from transmogrifai_tpu.parallel.supervisor import probe_with_backoff
    if cpu_smoke:
        return probe_with_backoff(key="bench-probe", platform="cpu")
    return probe_with_backoff(key="bench-probe", expect_accelerator=True)


def launcher(cpu_smoke: bool) -> int:
    verdict = probe(cpu_smoke)
    if not verdict.ok:
        # no chip -> no record on stdout and a non-zero exit; the outage
        # record (when a destination is configured) says what was tried
        from transmogrifai_tpu.parallel.supervisor import \
            maybe_write_outage_record
        rec_path = maybe_write_outage_record(
            what=f"accelerator backend unavailable (bench probe: "
                 f"{verdict.status}, {verdict.cause})",
            context="bench.py pre-flight probe; nothing was measured",
            attempts=verdict.attempts,
            will_update="rerun bench.py when the accelerator answers")
        sys.stderr.write(
            f"bench: pre-flight probe says {verdict.status} "
            f"({verdict.cause}); nothing was measured"
            + (f"; outage record at {rec_path}" if rec_path else "")
            + ".  `python bench.py --cpu-smoke` is the CPU correctness "
              "run.\n")
        return 3
    launch = Launch(verdict)
    if cpu_smoke:
        # keep the correctness run bounded: reduced rows unless the operator
        # pinned sizes explicitly
        os.environ.setdefault("BENCH_ROWS", "20000")
        os.environ.setdefault("BENCH_TRANSMOG_ROWS", "10000")
        os.environ.setdefault("BENCH_SCORE_ROWS", "10000")
    workload = os.environ.get("BENCH_WORKLOAD", "all").strip() or "all"
    if workload != "all" and workload not in CELL_ORDER:
        sys.exit(f"BENCH_WORKLOAD={workload!r}: unknown cell; valid: "
                 f"{', '.join(CELL_ORDER)}, all")
    records, failed = {}, []
    for name in CELL_ORDER:
        if workload not in (name, "all"):
            continue
        try:
            if name in DEVICE_CELLS:
                rec = launch.child(["--cell", name])
            else:
                rec = ORCHESTRATED_CELLS[name](launch)
        except CellFailed as e:
            sys.stderr.write(f"bench cell {name} FAILED: {e}\n")
            failed.append(name)
            continue
        records[name] = rec
        print(json.dumps({"cell": name, **rec}), flush=True)
    if workload == "all":
        # compact last line: one value per cell, the dense CV-grid wall as
        # the headline; the full records are the lines above
        head = records.get("dense") or {"value": 0, "unit": "failed",
                                        "vs_baseline": 0.0}
        print(json.dumps({
            "metric": "bench aggregate [headline: dense train wall]",
            "value": head["value"], "unit": head["unit"],
            "vs_baseline": head["vs_baseline"], "device": launch.device,
            "aux": {"cells": {n: {"value": r["value"], "unit": r["unit"]}
                              for n, r in records.items()},
                    "failed": failed}}), flush=True)
        if not failed:
            path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "BENCH_STANDING.json")
            try:
                hist = []
                if os.path.exists(path):
                    with open(path) as fh:
                        hist = json.load(fh).get("runs", [])
                hist.append({"utc": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                                  time.gmtime()),
                             "platform": launch.platform,
                             "device": launch.device, "workloads": records})
                with open(path, "w") as fh:
                    json.dump({"runs": hist[-20:]}, fh, indent=1)
            except (OSError, ValueError) as e:
                # the standing artifact is a convenience; the results are
                # already on stdout
                sys.stderr.write(f"bench: could not update {path}: {e}\n")
    return 1 if failed else 0


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        description="bench launcher: probes the device from a subprocess, "
                    "then runs each cell (BENCH_WORKLOAD, default all) in "
                    "processes that own the chip one at a time")
    ap.add_argument("--cpu-smoke", action="store_true",
                    help="explicit CPU correctness run at reduced sizes; "
                         "its records say platform cpu and are not "
                         "measurements of anything a user deploys")
    ap.add_argument("--cell", choices=sorted(DEVICE_CELLS),
                    help="run ONE cell in this process (what the launcher "
                         "starts; also the entry for run_scale_bench.py)")
    ap.add_argument("--step", nargs="+", metavar=("NAME", "ARG"),
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.cell:
        return run_in_this_process("cell", args.cell, ())
    if args.step:
        return run_in_this_process("step", args.step[0], args.step[1:])
    return launcher(args.cpu_smoke)


if __name__ == "__main__":
    sys.exit(main())

"""Chip smoke: the README user program, once, on the accelerator.

    python chip_smoke.py

drives the AutoML main path in ONE process (a chip has one owner) through the
entry points a user calls:

  A  dense sweep      dense_workflow — 1,000,000 x 28 RealNN ->
                      transmogrify -> sanity_check -> 3-fold CV over 4 LR +
                      RF(20 trees, depth 6) + GBT(20 rounds, depth 3) ->
                      Workflow.train() -> evaluate() -> score()
  B  transmogrify     transmog_workflow — 100,000 rows of text /
                      picklist / map / real columns, RawFeatureFilter on, LR
                      selector -> train -> score -> save -> load -> re-score
  C  serve            save() of the phase-A model -> start_server(bundle,
                      port=0) in this process -> POST /v1/score, GET /metrics,
                      GET /healthz

and fails (non-zero exit, phase named) unless every phase ran on the chip, on
the compiled path, complete: see the ``require`` calls.  The row counts sit on
the large side of every size-gated accelerator branch (mesh >= 262,144 rows,
train-start prefetch >= 100,000 rows, bf16 matrix storage >= 64M elements,
device hash-count assembly >= 4M elements).

It refuses to run unless ``jax.devices()[0].platform == "tpu"`` and never
sets the platform itself.  The last stdout line is one JSON object with
exactly these keys, the device as jax reports it:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

``"ok": true`` is printed only when every check passed; a phase that failed
on the chip ends with ``"ok": false`` and exit code 1; off the accelerator
nothing ran and no result is printed.  The line before it, ``summary {...,
"claim": null}``, carries the per-phase walls and compile counters.  No
number it prints is a benchmark result.

    JAX_PLATFORMS=cpu python chip_smoke.py --cpu-reference

is the separately named CPU correctness run that produced CPU_REFERENCE below.
It prints no result line.
"""

import argparse
import contextlib
import json
import math
import os
import shutil
import sys
import tempfile
import time
import urllib.request

ROWS_A = 1_000_000
ROWS_B = 100_000

# What `JAX_PLATFORMS=cpu python chip_smoke.py --cpu-reference` printed at
# ROWS_A / ROWS_B with the seeds baked into make_data /
# make_transmog_columns (this sandbox, jax 0.9.0, XLA:CPU, f32 + host
# paths; PR 21).  The chip must land inside AUROC_BAND of these and may log
# no failure event the CPU run did not.
CPU_REFERENCE = {
    "A": {"auroc": 0.8142, "winner": "OpLogisticRegression",
          "failure_events": []},
    "B": {"auroc": 0.8419, "failure_events": []},
    "C": {"failure_events": []},
}
# bf16 feature storage and bf16 histogram contractions move a train-set AuROC
# in the third decimal; a family that silently degraded moves it in the second
AUROC_BAND = 0.01

BAD_ACTIONS = ("skipped", "demoted", "degraded", "fallback", "swallowed",
               "outage")
FAMILIES = ("OpLogisticRegression", "OpRandomForestClassifier",
            "OpGBTClassifier")


class SmokeFailure(Exception):
    pass


def require(cond, phase, what):
    if not cond:
        raise SmokeFailure(f"phase {phase}: {what}")


def say(msg):
    print(msg, flush=True)


def jax_device():
    """The device as jax reports it."""
    import jax
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def result_line(ok, device):
    """The last stdout line: exactly ``ok`` and ``device`` — the driver
    refuses any other shape."""
    return json.dumps({"ok": bool(ok), "device": {
        "platform": str(device["platform"]), "kind": str(device["kind"]),
        "count": int(device["count"])}})


@contextlib.contextmanager
def phase(name, report):
    """One phase: its wall and compile counters, printed and kept in the
    report, and its own ambient failure log.  ``train()`` installs a log of
    its own (``model.failure_log``); everything else — ``score()``,
    ``evaluate()``, ``save()``, ``load()``, the serving engine — records
    into whatever log is ambient, which is this one."""
    from transmogrifai_tpu.profiling import compile_stats
    from transmogrifai_tpu.resilience import FailureLog, use_failure_log
    c0, t0 = compile_stats(), time.time()
    rec = report.setdefault(name, {})
    say(f"--- phase {name} ---")
    with use_failure_log(FailureLog()) as log:
        yield rec, log
    c1 = compile_stats()
    rec["wall_s"] = round(time.time() - t0, 1)
    rec["compile"] = {k: round(c1[k] - c0[k], 1) for k in c1}
    say(f"phase {name}: wall {rec['wall_s']} s, compile {rec['compile']}")


def bad_events(*logs):
    return sorted({(e.action, e.point or e.stage)
                   for log in logs for e in log.events
                   if e.action in BAD_ACTIONS})


def check_failure_logs(name, rec, *logs):
    """No log of this phase may hold an event the CPU run does not: a
    family skipped, a fused program demoted to eager stages, an AOT
    executable that gave way to JIT, an export that was swallowed."""
    events = bad_events(*logs)
    rec["failure_events"] = [list(e) for e in events]
    allowed = {tuple(e) for e in CPU_REFERENCE[name]["failure_events"]}
    extra = [e for e in events if e not in allowed]
    require(not extra, name,
            f"failure log holds events the CPU run does not: {extra}; "
            f"full logs: {[log.to_json() for log in logs]}")


def check_auroc(auroc, name, rec, reference):
    rec["auroc"] = round(float(auroc), 4)
    require(math.isfinite(auroc), name, f"AuROC is {auroc}")
    if reference:
        return
    want = CPU_REFERENCE[name]["auroc"]
    require(abs(auroc - want) <= AUROC_BAND, name,
            f"AuROC {auroc:.4f} outside {want} +- {AUROC_BAND} (CPU "
            "reference)")


def check_memory(name, rec, plan_before):
    """Shrink level 0 so far, and the plan this phase's sweep made — None
    when it made none (only the mesh-sharded sweep plans)."""
    from transmogrifai_tpu.parallel.memory import last_plan, memory_aux
    aux = memory_aux()
    plan = last_plan()
    rec["memory"] = {"shrink_level": aux["shrink_level"],
                     "shrinks_total": aux["shrinks_total"],
                     "device_budget_bytes": aux["device_budget_bytes"],
                     "plan": (plan.to_json() if plan is not None
                              and plan is not plan_before else None)}
    require(aux["shrink_level"] == 0 and not aux["shrinks_total"], name,
            f"memory governor shrank the sweep: {aux}")


def check_aot_counters(name, rec):
    """No AOT executable, installed from a bundle or from the registry, has
    failed to install or given way to JIT so far in this process."""
    from transmogrifai_tpu.telemetry import REGISTRY
    counters = REGISTRY.counters()
    rec["aot"] = {k: counters.get(k, 0) for k in (
        "aot_registry.installs", "aot_registry.call_fallbacks",
        "aot_registry.install_failures", "aot.fallback")}
    require(rec["aot"]["aot_registry.installs"] > 0, name,
            "no AOT executable was installed")
    require(not any(v for k, v in rec["aot"].items()
                    if k != "aot_registry.installs"), name,
            f"an AOT executable gave way to JIT: {rec['aot']}")


def check_bundle_aot(name, rec, bundle, installed):
    """Every rung of the serving ladder was exported, and every exported
    executable installed: a warm or serialize failure swallowed for one rung
    would otherwise pass as long as some other executable loaded."""
    import glob

    from transmogrifai_tpu import aot
    metas = glob.glob(os.path.join(bundle, aot.AOT_DIR_PREFIX + "*",
                                   aot.AOT_META_NAME))
    require(len(metas) == 1, name, f"bundle holds AOT indexes {metas}")
    with open(metas[0]) as fh:
        exported = json.load(fh)["executables"]
    rows = sorted({e["rows"] for e in exported})
    rec["aot_exported"], rec["aot_installed"] = len(exported), installed
    rec["aot_rows"] = rows
    ladder = aot.ladder_sizes(int(os.environ.get(
        "TRANSMOGRIFAI_AOT_LADDER_MAX", aot._DEFAULT_LADDER_MAX)))
    missing = [r for r in ladder if r not in rows]
    require(not missing, name,
            f"ladder rungs {missing} were not exported (bundle has {rows})")
    require(installed == len(exported), name,
            f"{len(exported)} executables exported, {installed} installed")


DENSE_D = 28


def make_data(n: int, d: int = DENSE_D, seed: int = 0):
    """HIGGS-difficulty synthetic: linear signal damped to sqrt(d) scale plus
    mild interactions, unit noise — best-model AuROC lands near 0.80 like the
    real HIGGS benchmark (calibrated against sklearn LR/GBT)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    w = rng.normal(size=d).astype(np.float32) / np.sqrt(d)
    logits = (X @ w + 0.35 * (X[:, 0] * X[:, 1]) - 0.25 * (X[:, 2] ** 2)
              + 0.1 + 0.3 * np.sin(2 * X[:, 3]))
    y = (logits + rng.normal(size=n).astype(np.float32) > 0).astype(np.float32)
    return X, y


def make_transmog_columns(n: int, seed: int = 1):
    """Mixed-type raw columns for the transmogrification workload.

    Returns (cols dict for ColumnBatch, schema dict).
    """
    import numpy as np

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


def dense_workflow(N: int):
    """Phase A's user program:
    N x 28 RealNN -> transmogrify -> sanity_check -> 3-fold CV over
    {4 LR, RF(20 trees, depth 6), GBT(20 rounds, depth 3)}.
    Returns (workflow, batch, selector)."""
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
    selector = BinaryClassificationModelSelector(models=models)
    selector.set_input(label, checked)
    pred = selector.get_output()

    cols = {"label": Column(RealNN, y)}
    for i in range(D):
        cols[f"f{i}"] = Column(RealNN, X[:, i])
    batch = ColumnBatch(cols, N)

    wf = Workflow().set_input_batch(batch).set_result_features(pred)
    return wf, batch, selector


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
    return fam


def transmog_workflow(N: int):
    """Phase B's user program:
    mixed text/picklist/map/real columns -> transmogrify -> sanity_check ->
    LR selector, RawFeatureFilter on.  Returns (workflow, batch)."""
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
    return wf, batch


def predictions(scored, pred_name):
    import numpy as np
    vals = scored[pred_name].values
    return (np.asarray(vals["prediction"]),
            np.asarray(vals["probability"], dtype=np.float64))


def phase_a(rows, report, reference):
    """Dense sweep; returns (model, batch, pred_name) for phase C."""
    import numpy as np

    from transmogrifai_tpu.evaluators import Evaluators
    from transmogrifai_tpu.parallel.memory import last_plan
    from transmogrifai_tpu.telemetry import REGISTRY

    with phase("A", report) as (rec, log):
        plan_before = last_plan()
        wf, batch, selector = dense_workflow(rows)
        model = wf.train()
        auroc = model.evaluate(Evaluators.BinaryClassification.auROC(),
                               batch=batch)["AuROC"]
        pred_name = next(f.name for f in model.result_features)
        pred, prob = predictions(model.score(), pred_name)

        fam = family_cv_metrics(model, selector)
        rec.update(rows=rows, family_cv_metrics=fam,
                   winner=model.selected_model.summary.best_model_name,
                   mesh_devices=REGISTRY.snapshot()["gauges"].get(
                       "mesh.devices", 0))
        say(f"A: winner {rec['winner']}, CV metrics {fam}, "
            f"mesh devices {rec['mesh_devices']}")
        for name in FAMILIES:
            require(name in fam and math.isfinite(fam[name]), "A",
                    f"family {name} has no finite CV metric: {fam}")
        require(pred.shape == (rows,) and np.isfinite(prob).all(), "A",
                f"score() gave shape {pred.shape}, finite "
                f"{bool(np.isfinite(prob).all())}")
        check_failure_logs("A", rec, model.failure_log, log)
        check_auroc(auroc, "A", rec, reference)
        check_memory("A", rec, plan_before)
    return model, batch, pred_name


def phase_b(rows, report, reference, tmp):
    import numpy as np

    from transmogrifai_tpu.evaluators import Evaluators
    from transmogrifai_tpu.parallel.memory import last_plan
    from transmogrifai_tpu.workflow import WorkflowModel

    with phase("B", report) as (rec, log):
        plan_before = last_plan()
        wf, batch = transmog_workflow(rows)
        model = wf.train()
        auroc = model.evaluate(Evaluators.BinaryClassification.auROC(),
                               batch=batch)["AuROC"]
        pred_name = next(f.name for f in model.result_features)
        pred, prob = predictions(model.score(), pred_name)
        require(pred.shape == (rows,) and np.isfinite(prob).all(), "B",
                f"score() gave shape {pred.shape}, finite "
                f"{bool(np.isfinite(prob).all())}")

        bundle = os.path.join(tmp, "transmog-model")
        model.save(bundle)
        loaded = WorkflowModel.load(bundle)
        pred2, prob2 = predictions(
            loaded.set_input_batch(batch).score(), pred_name)
        drift = float(np.abs(prob - prob2).max())
        rec.update(rows=rows, loaded_vs_memory_max_abs=drift,
                   feature_vector_width=int(np.asarray(
                       model.selected_model.best_model.fitted["coef"]
                   ).shape[0]))
        check_bundle_aot("B", rec, bundle, loaded.aot_executables)
        check_aot_counters("B", rec)
        say(f"B: width {rec['feature_vector_width']}, "
            f"{rec['aot_exported']} AOT executables exported, "
            f"{rec['aot_installed']} installed, aot {rec['aot']}, "
            f"loaded-vs-memory max |dp| {drift:.2e}")
        require(np.array_equal(pred, pred2) and drift <= 1e-6, "B",
                f"loaded model disagrees with the in-memory one: "
                f"{int((pred != pred2).sum())} labels, max |dp| {drift}")
        check_failure_logs("B", rec, model.failure_log, log)
        check_auroc(auroc, "B", rec, reference)
        check_memory("B", rec, plan_before)


def metric_value(text, name):
    """Value of the un-labelled sample of family ``name`` in /metrics text."""
    for line in text.splitlines():
        if line.startswith(f"transmogrifai_serving_{name} "):
            return float(line.split(" # ")[0].split()[-1])
    raise SmokeFailure(f"phase C: /metrics has no {name}")


def phase_c(model, batch, pred_name, report, tmp):
    import numpy as np

    from transmogrifai_tpu.serving.server import start_server

    with phase("C", report) as (rec, log):
        bundle = os.path.join(tmp, "dense-model")
        model.save(bundle)
        server, _ = start_server(bundle, port=0)
        try:
            url = f"http://127.0.0.1:{server.port}"

            def call(path, body=None):
                req = urllib.request.Request(
                    url + path,
                    data=None if body is None else json.dumps(body).encode(),
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=120) as r:
                    return r.status, r.read().decode()

            # reference on a small input: the same rows through the
            # in-process compiled score path of the model that was saved
            n = 37
            names = [f"f{i}" for i in range(28)]
            cols = {c: np.asarray(batch[c].values[:n]) for c in names}
            records = [{c: float(cols[c][i]) for c in names}
                       for i in range(n)]
            from transmogrifai_tpu.serving.engine import records_to_batch
            want = predictions(model.score(batch=records_to_batch(
                model.raw_features, records)), pred_name)[1][:, 1]

            got = []
            status, body = call("/v1/score", records[0])
            require(status == 200, "C", f"single POST -> {status}")
            got.append(json.loads(body)["result"])
            for lo, hi in ((1, 2), (2, 9), (9, n)):
                status, body = call("/v1/score", records[lo:hi])
                require(status == 200, "C", f"list POST -> {status}")
                got.extend(json.loads(body)["results"])
            served = np.asarray(
                [r[pred_name]["probability_1"] for r in got], np.float64)
            drift = float(np.abs(served - want).max())
            require(served.shape == (n,) and np.isfinite(served).all()
                    and drift <= 1e-5, "C",
                    f"served probabilities: shape {served.shape}, max |dp| "
                    f"vs model.score() {drift}")

            metrics = call("/metrics")[1]
            health = json.loads(call("/healthz")[1])
            stats = server.engine.stats()
            rec.update(
                requests=4, rows=n, served_vs_score_max_abs=drift,
                health=health["health"],
                aot_executables=stats["aot_executables"],
                compiled_path_active=stats["compiled_path_active"],
                serving={k: metric_value(metrics, k) for k in (
                    "fallback_batches_total", "online_traces_total",
                    "breaker_demoted_batches_total")})
            check_bundle_aot("C", rec, bundle, rec["aot_executables"])
            check_aot_counters("C", rec)
            say(f"C: {rec['aot_exported']} AOT executables exported, "
                f"{rec['aot_installed']} installed, health {rec['health']}, "
                f"serving {rec['serving']}, aot {rec['aot']}, max |dp| "
                f"{drift:.2e}")
            require(not any(rec["serving"].values()), "C",
                    f"serving left the compiled path: {rec['serving']}")
            require(rec["health"] == "SERVING"
                    and rec["compiled_path_active"], "C",
                    f"health {health}")
        finally:
            server.drain_and_close(timeout_s=30.0)
        check_failure_logs("C", rec, log)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu-reference", action="store_true",
                    help="run the same phases on the CPU backend and print "
                         "the reference values; prints no result line")
    args = ap.parse_args(argv)

    import jax
    device = jax_device()
    want = "cpu" if args.cpu_reference else "tpu"
    if device["platform"] != want:
        sys.stderr.write(
            f"chip_smoke: needs platform {want!r}, jax found {device}; "
            "nothing was run\n")
        return 2

    # the package decides the compile-cache directory at import
    from importlib import metadata

    import transmogrifai_tpu  # noqa: F401
    from transmogrifai_tpu.native import fallback_reasons
    versions = {}
    for dist in ("jax", "jaxlib", "libtpu"):
        try:
            versions[dist] = metadata.version(dist)
        except metadata.PackageNotFoundError:
            versions[dist] = None
    say(f"device {device}; {versions}; compile cache "
        f"{jax.config.jax_compilation_cache_dir}")
    python_paths = fallback_reasons()
    say(f"native modules on the Python path: {python_paths or 'none'}")

    report = {"device": device, "versions": versions,
              "compile_cache_dir": jax.config.jax_compilation_cache_dir}
    tmp = tempfile.mkdtemp(prefix="chip-smoke-")
    t0 = time.time()
    try:
        require(not python_paths, "start",
                f"native modules fell back to Python: {python_paths}")
        model, batch, pred_name = phase_a(ROWS_A, report, args.cpu_reference)
        phase_b(ROWS_B, report, args.cpu_reference, tmp)
        phase_c(model, batch, pred_name, report, tmp)
        # whatever was recorded outside every phase's log (between phases,
        # or by a background thread that outlived its phase)
        from transmogrifai_tpu.resilience import DEFAULT_LOG
        require(not bad_events(DEFAULT_LOG), "end",
                f"the process-default failure log holds "
                f"{DEFAULT_LOG.to_json()}")
    except SmokeFailure as e:
        sys.stderr.write(f"chip_smoke FAILED — {e}\n")
        if not args.cpu_reference:
            say(result_line(False, device))
        return 1
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    report["wall_s"] = round(time.time() - t0, 1)
    report["compile_cache_dir_at_exit"] = \
        jax.config.jax_compilation_cache_dir

    if args.cpu_reference:
        say("CPU_REFERENCE " + json.dumps({
            k: {"failure_events": report[k]["failure_events"],
                **({"auroc": report[k]["auroc"]} if k != "C" else {}),
                **({"winner": report[k]["winner"]} if k == "A" else {})}
            for k in ("A", "B", "C")}))
        say("CPU reference run complete: " + json.dumps(report))
        return 0
    out_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "chip_smoke_report.json"), "w") as fh:
        json.dump(report, fh, indent=1)
    say("summary " + json.dumps({
        "phases": {k: {"wall_s": report[k]["wall_s"],
                       "compile": report[k]["compile"]}
                   for k in ("A", "B", "C")},
        "wall_s": report["wall_s"], "claim": None}))
    say(result_line(True, device))
    return 0


if __name__ == "__main__":
    sys.exit(main())

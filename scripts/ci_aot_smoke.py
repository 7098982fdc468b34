"""CI smoke for AOT-serialized executables (ISSUE 9): train a tiny model,
save a bundle carrying serialized executables, then serve it from a FRESH
subprocess and require the first score to arrive with ZERO new XLA compiles
and ZERO traces — the cold-start compile wall is gone, not just amortized.

Usage:
    python scripts/ci_aot_smoke.py run OUT_DIR        # train + save + serve
    python scripts/ci_aot_smoke.py validate OUT_DIR   # assert the summary

``run`` writes OUT_DIR/aot-smoke.json with the child's measurements (first
score wall, compile/trace counts, installed-executable count) plus a JIT
control run of the SAME bundle (TRANSMOGRIFAI_NO_AOT=1) proving the
zero-compile result comes from the shipped executables, not a warm disk
cache masking the assert.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np

# runnable as `python scripts/ci_aot_smoke.py` from the repo root
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

SUMMARY_NAME = "aot-smoke.json"

# fresh-process serve probe.
# The compile listeners install before the engine exists so every backend
# compile in this process is observed.
_CHILD = r"""
import json, sys, time
t0 = time.time()
from transmogrifai_tpu.serving.engine import ScoringEngine
from transmogrifai_tpu.profiling import (install_compile_listeners,
                                         compile_stats, new_compile_count)
from transmogrifai_tpu.compiled import trace_count
install_compile_listeners()
eng = ScoringEngine(sys.argv[1], max_batch=16, linger_ms=0.0)
out, _version = eng.score_record({"x1": 0.4, "x2": 3.0, "cat": "a"})
first = time.time() - t0
stats = eng.stats()
eng.close()
print(json.dumps({
    "first_score_s": round(first, 3),
    "new_compiles": new_compile_count(),
    "backend_compiles": int(compile_stats()["backend_compiles"]),
    "traces": trace_count(),
    "aot_executables": stats.get("aot_executables", 0),
    "warmup_traces": stats["counters"].get("warmup_traces_total", 0),
    "result_keys": sorted(out),
}))
"""


def _make_records(n, seed=7):
    rng = np.random.default_rng(seed)
    recs = []
    for i in range(n):
        x1 = float(rng.normal())
        x2 = float(rng.uniform(0, 10))
        recs.append({
            "y": 1.0 if (x1 + 0.2 * x2 + rng.normal() * 0.3) > 1.0 else 0.0,
            "x1": x1, "x2": x2, "cat": ["a", "b", "c"][i % 3],
        })
    return recs


def _serve_fresh(bundle, no_aot):
    env = dict(os.environ)
    env.pop("TRANSMOGRIFAI_NO_AOT", None)
    if no_aot:
        env["TRANSMOGRIFAI_NO_AOT"] = "1"
    p = subprocess.run([sys.executable, "-c", _CHILD, bundle],
                       capture_output=True, text=True, env=env, timeout=600)
    line = next((ln for ln in reversed(p.stdout.splitlines())
                 if ln.startswith("{")), None)
    if p.returncode != 0 or not line:
        sys.stderr.write(p.stderr[-3000:])
        raise SystemExit(f"serve child failed (rc={p.returncode})")
    return json.loads(line)


def run(out_dir):
    from transmogrifai_tpu import types as T
    from transmogrifai_tpu.features import features_from_schema
    from transmogrifai_tpu.models.linear import OpLogisticRegression
    from transmogrifai_tpu.ops.transmogrify import transmogrify
    from transmogrifai_tpu.selector import (
        BinaryClassificationModelSelector, ModelCandidate, grid)
    from transmogrifai_tpu.workflow import Workflow

    os.makedirs(out_dir, exist_ok=True)
    # a compile cache makes the JIT control run resemble production (PR 4
    # behavior) — the AOT assert must hold even against that warm baseline
    os.environ.setdefault("TRANSMOGRIFAI_COMPILE_CACHE",
                          os.path.join(out_dir, "compile-cache"))

    schema = {"y": T.RealNN, "x1": T.Real, "x2": T.Real, "cat": T.PickList}
    y, predictors = features_from_schema(schema, response="y")
    fv = transmogrify(predictors)
    checked = y.sanity_check(fv, remove_bad_features=True)
    sel = BinaryClassificationModelSelector(models=[
        ModelCandidate(OpLogisticRegression(),
                       grid(reg_param=[0.01, 0.1]),
                       "OpLogisticRegression")])
    sel.set_input(y, checked)
    wf = (Workflow().set_input_records(_make_records(200))
          .set_result_features(sel.get_output()))
    model = wf.train()

    bundle = os.path.join(out_dir, "model")
    os.environ["TRANSMOGRIFAI_AOT_LADDER_MAX"] = "16"
    t0 = time.time()
    model.save(bundle)
    save_wall = time.time() - t0

    aot = _serve_fresh(bundle, no_aot=False)
    jit = _serve_fresh(bundle, no_aot=True)
    summary = {"saveWallS": round(save_wall, 2), "bundle": bundle,
               "aot": aot, "jit": jit}
    with open(os.path.join(out_dir, SUMMARY_NAME), "w") as fh:
        json.dump(summary, fh, indent=2)
    print(json.dumps(summary, indent=2))
    return 0


def validate(out_dir):
    with open(os.path.join(out_dir, SUMMARY_NAME)) as fh:
        s = json.load(fh)
    aot, jit = s["aot"], s["jit"]
    # the acceptance bar: a fresh process scores its first record without a
    # single XLA compile OR trace — the executables shipped in the bundle
    assert aot["aot_executables"] > 0, \
        f"no AOT executables installed: {aot}"
    assert aot["new_compiles"] == 0, \
        f"fresh-process serve compiled {aot['new_compiles']} programs"
    assert aot["backend_compiles"] == 0, \
        f"backend compiled {aot['backend_compiles']} programs"
    assert aot["traces"] == 0, f"serve traced {aot['traces']} programs"
    assert aot["warmup_traces"] == 0, \
        f"engine warmup traced {aot['warmup_traces']} programs"
    assert aot["result_keys"], "first score returned no result fields"
    # the JIT control run of the SAME bundle must have traced — otherwise
    # something else (not the shipped executables) absorbed the compiles
    # and this smoke is not testing what it claims to
    assert jit["aot_executables"] == 0, f"JIT control installed AOT: {jit}"
    assert jit["traces"] > 0, \
        f"JIT control run traced nothing ({jit}) — assert is vacuous"
    print(f"OK: first score in {aot['first_score_s']}s with "
          f"{aot['aot_executables']} shipped executables, 0 compiles, "
          f"0 traces (JIT control: {jit['traces']} traces, "
          f"{jit['first_score_s']}s)")
    return 0


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "run":
        sys.exit(run(sys.argv[2]))
    if len(sys.argv) == 3 and sys.argv[1] == "validate":
        sys.exit(validate(sys.argv[2]))
    sys.exit(f"usage: {sys.argv[0]} run OUT_DIR | validate OUT_DIR")

"""Closed-loop chaos harness for the device-runtime supervisor (train side).

The serving control plane has ``chaos_slo.py``; this is the same discipline
for device-runtime outages on the training path.  It injects, via the
``supervisor.*`` injection points and the probe chaos preludes, the faults
such an outage produces — a native init hang, a SIGTERM-ignoring hung
process, a dead probe child, a stalled host→device chunk, and a mid-sweep
device loss — and asserts the supervision contract:

* a hung init resolves to a TYPED outage verdict within the
  timeout+grace watchdog budget (never an unbounded stall);
* a SIGTERM-ignoring child is reclaimed by the SIGKILL escalation and is
  actually gone afterwards — zero hung processes survive the harness;
* the heartbeat trips AVAILABLE→DEGRADED→OUTAGE under consecutive probe
  kills, writes the standardized outage record, and records the recovery —
  every transition lands in the failure log and telemetry;
* a stalled transfer chunk surfaces as ``TransferStallError`` (typed),
  not a hang;
* a mid-sweep device loss degrades to the surviving mesh and the resumed
  sweep selects the IDENTICAL winner (name + params) as an uninterrupted
  run, replaying checkpointed families instead of refitting them.

Artifacts written to ``--out-dir``: ``outcomes.jsonl`` (one line per
scenario), ``metrics.txt`` (final telemetry snapshot), ``summary.json``
(the verdict, also printed), ``trace-chaos-train.json`` and the
``OUTAGE_*.json`` record the heartbeat produced.  Exit 0 on a clean pass,
1 on any contract violation.

Usage:
    python scripts/chaos_train.py --out-dir /tmp/chaos_train \
        [--seed 0] [--probe-timeout-s 2] [--grace-s 3] [--rows 560]
"""

import argparse
import json
import os
import sys
import time

# the mesh-degrade scenario needs the virtual 8-device CPU topology; must be
# set before jax initializes (mirrors tests/conftest.py)
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# runnable as `python scripts/chaos_train.py` from the repo root
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402


class _FakeClock:
    """Deterministic heartbeat clock: the breaker's reset timeout elapses
    when the scenario says so, not wall time."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _two_family_sweep(n, seed, resume_from=None):
    """Two LR families with widely-separated regularisation (reduction-order
    float noise on a shrunken mesh cannot flip the winner); LR_A checkpoints
    before LR_B scores, so a device loss at LR_B's scoring proves replay."""
    from transmogrifai_tpu.columns import Column, ColumnBatch
    from transmogrifai_tpu.features import FeatureBuilder
    from transmogrifai_tpu.models.linear import OpLogisticRegression
    from transmogrifai_tpu.ops.transmogrify import transmogrify
    from transmogrifai_tpu.selector import (
        BinaryClassificationModelSelector, ModelCandidate, grid)
    from transmogrifai_tpu.types import RealNN
    from transmogrifai_tpu.workflow import Workflow

    d = 6
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2] > 0).astype(np.float32)
    label = FeatureBuilder.RealNN("label").as_response()
    feats = [FeatureBuilder.RealNN(f"f{i}").as_predictor() for i in range(d)]
    checked = label.sanity_check(transmogrify(feats),
                                 remove_bad_features=True)
    sel = BinaryClassificationModelSelector(models=[
        ModelCandidate(OpLogisticRegression(),
                       grid(reg_param=[0.001, 3.0], max_iter=[25]), "LR_A"),
        ModelCandidate(OpLogisticRegression(),
                       grid(reg_param=[10.0, 30.0], max_iter=[25]), "LR_B"),
    ])
    sel.set_input(label, checked)
    pred = sel.get_output()
    cols = {"label": Column(RealNN, y)}
    for i in range(d):
        cols[f"f{i}"] = Column(RealNN, X[:, i])
    wf = Workflow().set_input_batch(ColumnBatch(cols, n)) \
                   .set_result_features(pred)
    model = wf.train(resume_from=resume_from)
    s = model.selected_model.summary
    competed = [r for r in s.validation_results if not r.raced_out
                and np.isfinite(r.metric_values[s.evaluation_metric])]
    best = max(competed, key=lambda r: r.metric_values[s.evaluation_metric])
    return s.best_model_name, dict(best.params), model.failure_log


def run_chaos_train(*, seed=0, probe_timeout_s=2.0, grace_s=3.0, rows=560,
                    out_dir=None):
    """Run the harness; returns the summary dict (``summary["passed"]`` is
    the verdict).  Importable — the chaos test suite and the weekly CI job
    drive exactly this loop."""
    import jax
    jax.config.update("jax_platforms", "cpu")

    from transmogrifai_tpu.parallel import make_mesh, stream_to_device
    from transmogrifai_tpu.parallel import supervisor as sup
    from transmogrifai_tpu.resilience import (FailureLog, FaultInjector,
                                              inject_faults,
                                              use_failure_log)
    from transmogrifai_tpu.telemetry import REGISTRY, Tracer, use_tracer

    budget_s = probe_timeout_s + grace_s + 30.0   # + spawn/reap overhead
    tracer = Tracer(run_name="chaos-train")
    flog = FailureLog()
    outcomes = []
    sup.reset_surviving_devices()

    def row(scenario, **kw):
        r = {"scenario": scenario, **kw}
        outcomes.append(r)
        return r

    with use_tracer(tracer), use_failure_log(flog):
        # -- 1. native init hang → typed outage within the watchdog budget
        t0 = time.monotonic()
        v = sup.probe_devices(timeout_s=probe_timeout_s, grace_s=grace_s,
                              chaos="hang", key="chaos-init-hang")
        hang_wall = time.monotonic() - t0
        row("init_hang", status=v.status, cause=v.cause,
            wall_s=round(hang_wall, 1), within_budget=hang_wall <= budget_s)

        # -- 2. SIGTERM-ignoring hung process reclaimed by SIGKILL
        t0 = time.monotonic()
        r = sup.run_supervised(
            [sys.executable, "-c", sup.CHAOS_PRELUDES["hang_ignore_sigterm"]],
            timeout_s=probe_timeout_s, grace_s=grace_s)
        kill_wall = time.monotonic() - t0
        try:
            os.kill(r.pid, 0)
            reclaimed = False
        except OSError:
            reclaimed = True
        row("sigterm_ignored", rc=r.rc, escalated=r.escalated,
            reclaimed=reclaimed, wall_s=round(kill_wall, 1),
            within_budget=kill_wall <= budget_s)

        # -- 3. probe child dies → outage verdict, not an exception
        v_die = sup.probe_devices(timeout_s=probe_timeout_s, chaos="die",
                                  key="chaos-probe-die")
        row("probe_kill", status=v_die.status, cause=v_die.cause)

        # -- 4. heartbeat trips to OUTAGE under consecutive probe kills,
        #       writes the standardized record, recovers when probes heal
        clk = _FakeClock()
        hb = sup.Heartbeat(probe=lambda: sup.probe_devices(
                               timeout_s=60, platform="cpu",
                               key="chaos-heartbeat"),
                           interval_s=10.0, failure_threshold=2,
                           reset_timeout_s=30.0, clock=clk,
                           outage_dir=out_dir,
                           context="chaos_train.py heartbeat scenario")
        outages_before = REGISTRY.counter("supervisor.outages_total").value
        with inject_faults(FaultInjector(
                fail_keys={"supervisor.heartbeat": ["1", "2"]}, seed=seed)):
            states = [(hb.tick().status, hb.state)]      # 0: healthy
            states.append((hb.tick().status, hb.state))  # 1: killed → DEGRADED
            states.append((hb.tick().status, hb.state))  # 2: killed → OUTAGE
            clk.t += 31.0                 # breaker reset timeout elapses
            states.append((hb.tick().status, hb.state))  # 3: healed
        hb_actions = [e.action for e in flog
                      if e.point == "supervisor.heartbeat"]
        records = [f for f in os.listdir(out_dir)
                   if f.startswith("OUTAGE_")] if out_dir else []
        rec_ok = False
        if records:
            rec = json.load(open(os.path.join(out_dir, records[0])))
            rec_ok = set(rec) == set(sup.OUTAGE_RECORD_KEYS)
        row("heartbeat", states=[s for _, s in states],
            actions=hb_actions, outage_record=records[:1],
            record_schema_ok=rec_ok,
            outages_total_delta=REGISTRY.counter(
                "supervisor.outages_total").value - outages_before)

        # -- 5. stalled host→device chunk → typed TransferStallError
        mesh = make_mesh(min(8, len(jax.devices())))
        X = np.ones((64, 4), np.float32)
        with inject_faults(FaultInjector(
                rates={"supervisor.chunk_stall": 1.0}, seed=seed)):
            try:
                stream_to_device(X, mesh)
                stall = "no-error"
            except sup.TransferStallError as e:
                stall = "typed"
                stall_classified = sup.is_device_loss(e)
            except Exception as e:  # noqa: BLE001 — contract violation
                stall = f"untyped: {type(e).__name__}"
                stall_classified = False
        row("chunk_stall", outcome=stall,
            classifies_as_device_loss=stall_classified)

        # -- 6. mid-sweep device loss → surviving-mesh resume, same winner
        os.environ["TRANSMOGRIFAI_TPU_MESH"] = "1"
        import tempfile
        sweep_dir = os.path.join(out_dir or tempfile.mkdtemp(
            prefix="chaos-train-"), "sweep")
        try:
            w0, p0, _ = _two_family_sweep(rows, seed)
            sup.reset_surviving_devices()
            degrades_before = REGISTRY.counter(
                "supervisor.mesh_degrades_total").value
            with inject_faults(FaultInjector(
                    fail_keys={"supervisor.device_loss": ["LR_B:score:a0"]},
                    seed=seed)) as inj:
                w1, p1, sweep_log = _two_family_sweep(
                    rows, seed, resume_from=sweep_dir)
            sweep_actions = [(e.action, e.point) for e in sweep_log]
            row("mesh_degrade",
                baseline_winner=w0, recovered_winner=w1,
                same_winner=(w1 == w0 and p1 == p0),
                device_cap=sup.device_cap(),
                loss_fired=("supervisor.device_loss",
                            "LR_B:score:a0") in inj.fired,
                degrade_recorded=("degraded",
                                  "supervisor.device_loss") in sweep_actions,
                resumed_from_checkpoint=any(
                    a == "resumed" for a, _ in sweep_actions),
                mesh_degrades_delta=REGISTRY.counter(
                    "supervisor.mesh_degrades_total").value - degrades_before)
        finally:
            sup.reset_surviving_devices()
            os.environ.pop("TRANSMOGRIFAI_TPU_MESH", None)

    by = {r["scenario"]: r for r in outcomes}
    checks = {
        "init_hang_typed_outage_within_budget":
            by["init_hang"]["status"] == "outage"
            and by["init_hang"]["cause"] == "hang"
            and by["init_hang"]["within_budget"],
        "sigterm_ignoring_child_reclaimed":
            by["sigterm_ignored"]["rc"] == 124
            and by["sigterm_ignored"]["escalated"]
            and by["sigterm_ignored"]["reclaimed"]
            and by["sigterm_ignored"]["within_budget"],
        "probe_kill_is_outage": by["probe_kill"]["status"] == "outage",
        "heartbeat_trips_and_recovers":
            by["heartbeat"]["states"] == ["available", "degraded",
                                          "outage", "available"]
            and "outage" in by["heartbeat"]["actions"]
            and "recovered" in by["heartbeat"]["actions"]
            and by["heartbeat"]["outages_total_delta"] >= 1,
        "outage_record_schema_ok": (by["heartbeat"]["record_schema_ok"]
                                    or out_dir is None),
        "chunk_stall_typed": by["chunk_stall"]["outcome"] == "typed"
            and by["chunk_stall"]["classifies_as_device_loss"],
        "degrade_resume_same_winner": by["mesh_degrade"]["same_winner"]
            and by["mesh_degrade"]["loss_fired"],
        "sweep_ran_on_surviving_mesh": by["mesh_degrade"]["device_cap"] == 7,
        "every_degrade_recorded": by["mesh_degrade"]["degrade_recorded"]
            and by["mesh_degrade"]["mesh_degrades_delta"] >= 1,
        "resume_replayed_checkpoint":
            by["mesh_degrade"]["resumed_from_checkpoint"],
    }
    summary = {
        "passed": all(checks.values()),
        "checks": checks,
        "seed": seed,
        "probeTimeoutS": probe_timeout_s,
        "graceS": grace_s,
        "watchdogBudgetS": budget_s,
        "rows": rows,
        "failureSummary": flog.summary(),
    }
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, "outcomes.jsonl"), "w") as fh:
            for r in outcomes:
                fh.write(json.dumps(r) + "\n")
        with open(os.path.join(out_dir, "metrics.txt"), "w") as fh:
            json.dump(REGISTRY.snapshot(), fh, indent=2)
        with open(os.path.join(out_dir, "summary.json"), "w") as fh:
            json.dump(summary, fh, indent=2)
        tracer.export_chrome_trace(
            os.path.join(out_dir, "trace-chaos-train.json"))
    return summary


def run_chaos_hostgroup(*, out_dir, seed=0, rows=560):
    """Lost-host drill (ISSUE 14): drive the ci_hostgroup_smoke harness —
    2-process group vs single-process control, SIGKILL rank 1 mid-sweep,
    relaunch at world 1, checkpoint resume, identical winner — and fold its
    checks into the chaos summary contract."""
    import subprocess
    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "ci_hostgroup_smoke.py")
    env = dict(os.environ,
               HOSTGROUP_SMOKE_ROWS=str(rows),
               HOSTGROUP_SMOKE_SEED=str(seed))
    os.makedirs(out_dir, exist_ok=True)
    checks = {}
    for phase in ("run", "validate"):
        r = subprocess.run([sys.executable, script, phase, out_dir],
                           env=env, capture_output=True, text=True,
                           timeout=1200)
        checks[f"hostgroup_{phase}_rc0"] = r.returncode == 0
        if r.returncode != 0:
            print(r.stdout[-4000:], file=sys.stderr)
            print(r.stderr[-4000:], file=sys.stderr)
            break
    smoke_path = os.path.join(out_dir, "hostgroup_smoke.json")
    checks["hostgroup_outage_artifact"] = False
    if os.path.exists(smoke_path):
        with open(smoke_path) as fh:
            smoke = json.load(fh)
        rec = (smoke.get("chaos") or {}).get("outageRecord")
        checks["hostgroup_outage_artifact"] = isinstance(rec, dict)
    summary = {"passed": all(checks.values()), "checks": checks,
               "seed": seed, "rows": rows, "mode": "hostgroup"}
    with open(os.path.join(out_dir, "summary.json"), "w") as fh:
        json.dump(summary, fh, indent=2)
    return summary


def run_chaos_oom(*, out_dir, seed=0, rows=560):
    """Device-memory-pressure drill (ISSUE 15): drive the ci_memory_smoke
    harness — tiny-budget preflight plan, OOM-vs-device-loss classifier
    disjointness, injected mid-sweep OOM walking the shrink-and-retry
    ladder to the identical winner with zero worker deaths — and fold its
    checks into the chaos summary contract."""
    import subprocess
    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "ci_memory_smoke.py")
    env = dict(os.environ,
               MEMORY_SMOKE_ROWS=str(rows),
               MEMORY_SMOKE_SEED=str(seed))
    os.makedirs(out_dir, exist_ok=True)
    checks = {}
    for phase in ("run", "validate"):
        r = subprocess.run([sys.executable, script, phase, out_dir],
                           env=env, capture_output=True, text=True,
                           timeout=1200)
        checks[f"oom_{phase}_rc0"] = r.returncode == 0
        if r.returncode != 0:
            print(r.stdout[-4000:], file=sys.stderr)
            print(r.stderr[-4000:], file=sys.stderr)
            break
    smoke_path = os.path.join(out_dir, "memory-smoke.json")
    checks["oom_drill_converged"] = False
    if os.path.exists(smoke_path):
        with open(smoke_path) as fh:
            smoke = json.load(fh)
        drill = smoke.get("drill") or {}
        checks["oom_drill_converged"] = bool(
            drill.get("same_winner") and drill.get("device_cap") is None)
    summary = {"passed": all(checks.values()), "checks": checks,
               "seed": seed, "rows": rows, "mode": "oom"}
    with open(os.path.join(out_dir, "summary.json"), "w") as fh:
        json.dump(summary, fh, indent=2)
    return summary


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out-dir", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--probe-timeout-s", type=float, default=2.0)
    ap.add_argument("--grace-s", type=float, default=3.0)
    ap.add_argument("--rows", type=int, default=560,
                    help="sweep rows; must divide by 8 AND 7 so the mesh "
                         "forms before and after the injected device loss")
    ap.add_argument("--mode", choices=("full", "hostgroup", "oom"),
                    default="full",
                    help="'full' runs the in-process supervisor drills; "
                         "'hostgroup' runs the multi-process lost-host "
                         "drill (real ranks, SIGKILL, relaunch, resume); "
                         "'oom' runs the memory-governor pressure drill "
                         "(injected device OOM, shrink ladder, same winner)")
    args = ap.parse_args(argv)
    if args.mode == "hostgroup":
        summary = run_chaos_hostgroup(out_dir=args.out_dir, seed=args.seed,
                                      rows=args.rows)
    elif args.mode == "oom":
        summary = run_chaos_oom(out_dir=args.out_dir, seed=args.seed,
                                rows=args.rows)
    else:
        summary = run_chaos_train(
            seed=args.seed, probe_timeout_s=args.probe_timeout_s,
            grace_s=args.grace_s, rows=args.rows, out_dir=args.out_dir)
    print(json.dumps(summary, indent=2))
    if not summary["passed"]:
        failing = [k for k, ok in summary["checks"].items() if not ok]
        print(f"chaos train FAILED: {failing}", file=sys.stderr)
        return 1
    print("chaos train passed", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""CI smoke for the device-runtime supervisor (ISSUE 11): prove, in one
process, that a native init hang cannot stall a job —

* an injected init hang (probe child that never returns) resolves to a
  TYPED ``outage`` verdict within the timeout+grace watchdog deadline,
  instead of stalling the job until the CI-level timeout shoots it;
* a SIGTERM-ignoring hung child — the process shape plain SIGTERM cannot
  kill — is reclaimed by the SIGKILL
  escalation, and ZERO hung processes survive the run;
* a healthy probe still reads ``available`` with a device inventory (the
  verdict machinery distinguishes, it doesn't just always say outage);
* the standardized outage record (``supervisor.OUTAGE_RECORD_KEYS``)
  lands as a CI artifact next to this smoke record.

Usage:
    python scripts/ci_supervisor_smoke.py run OUT_DIR       # probe + record
    python scripts/ci_supervisor_smoke.py validate OUT_DIR  # parse + assert
"""

import json
import os
import sys
import time

# runnable as `python scripts/ci_supervisor_smoke.py` from the repo root
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TIMEOUT_S = float(os.environ.get("SUPERVISOR_SMOKE_TIMEOUT_S", "3"))
GRACE_S = float(os.environ.get("SUPERVISOR_SMOKE_GRACE_S", "3"))
# spawn + child-import overhead on top of the supervision deadline itself
BUDGET_S = TIMEOUT_S + GRACE_S + 30.0


def run(out_dir):
    from transmogrifai_tpu.parallel import supervisor as sup

    os.makedirs(out_dir, exist_ok=True)

    # 1. injected init hang → typed outage verdict within the deadline
    t0 = time.monotonic()
    hang = sup.probe_devices(timeout_s=TIMEOUT_S, grace_s=GRACE_S,
                             chaos="hang", key="ci-init-hang")
    hang_wall = time.monotonic() - t0

    # 2. SIGTERM-ignoring child (the un-killable round-5 shape) reclaimed
    t0 = time.monotonic()
    r = sup.run_supervised(
        [sys.executable, "-c", sup.CHAOS_PRELUDES["hang_ignore_sigterm"]],
        timeout_s=TIMEOUT_S, grace_s=GRACE_S)
    kill_wall = time.monotonic() - t0
    try:
        os.kill(r.pid, 0)
        hung_processes = 1
    except OSError:
        hung_processes = 0

    # 3. healthy probe still reads available (non-vacuous verdicts)
    healthy = sup.probe_devices(timeout_s=120, platform="cpu",
                                key="ci-healthy")

    # 4. the standardized outage record, from the hang's own timeline
    rec_path = sup.maybe_write_outage_record(
        what="injected init hang (CI supervisor smoke)",
        context="scripts/ci_supervisor_smoke.py: probe child pinned in an "
                "infinite sleep before touching jax",
        attempts=hang.attempts,
        mitigations=("probe_devices returned a typed outage verdict; "
                     "no process outlived the SIGTERM->SIGKILL escalation",),
        will_update="n/a — synthetic outage, resolved by construction",
        path=os.path.join(out_dir, "outage-record.json"))

    record = {
        "timeout_s": TIMEOUT_S, "grace_s": GRACE_S, "budget_s": BUDGET_S,
        "hang_verdict": hang.to_json(), "hang_wall_s": round(hang_wall, 2),
        "sigterm_ignored": {"rc": r.rc, "timed_out": r.timed_out,
                            "escalated": r.escalated, "pid": r.pid,
                            "wall_s": round(kill_wall, 2)},
        "hung_processes": hung_processes,
        "healthy_verdict": healthy.to_json(),
        "outage_record": os.path.basename(rec_path) if rec_path else None,
    }
    path = os.path.join(out_dir, "supervisor-smoke.json")
    with open(path, "w") as fh:
        fh.write(json.dumps(record) + "\n")
    print(f"wrote {path}: hang -> {hang.status} in {hang_wall:.1f}s, "
          f"sigkill escalated={r.escalated}, hung processes "
          f"{hung_processes}, healthy -> {healthy.status} "
          f"({healthy.device_count} {healthy.platform} devices)")
    return 0


def validate(out_dir):
    from transmogrifai_tpu.parallel.supervisor import OUTAGE_RECORD_KEYS

    with open(os.path.join(out_dir, "supervisor-smoke.json")) as fh:
        record = json.loads(fh.readline())

    # the injected hang became a typed verdict, within the watchdog budget
    hv = record["hang_verdict"]
    assert hv["status"] == "outage" and hv["cause"] == "hang", hv
    assert record["hang_wall_s"] <= record["budget_s"], record
    assert hv["attempts"] and hv["attempts"][0]["result"] == "hang", hv

    # SIGTERM was ignored, SIGKILL reclaimed, nothing survived
    sk = record["sigterm_ignored"]
    assert sk["rc"] == 124 and sk["timed_out"], sk
    assert sk["escalated"], "SIGTERM sufficed — the escalation ran vacuously"
    assert sk["wall_s"] <= record["budget_s"], sk
    assert record["hung_processes"] == 0, record

    # the healthy probe is a real verdict, not a constant
    hl = record["healthy_verdict"]
    assert hl["status"] == "available", hl
    assert hl["deviceCount"] >= 1 and hl["devices"], hl
    assert hl["latencyS"] > 0, hl

    # the outage-record artifact exists and carries exactly the stable keys
    assert record["outage_record"], record
    with open(os.path.join(out_dir, record["outage_record"])) as fh:
        rec = json.load(fh)
    assert set(rec) == set(OUTAGE_RECORD_KEYS), sorted(rec)
    assert rec["timeline_utc"] and \
        rec["timeline_utc"][0]["result"] == "hang", rec

    print(f"OK: injected hang -> typed outage in {record['hang_wall_s']}s "
          f"(budget {record['budget_s']}s), SIGKILL escalation reclaimed "
          f"the SIGTERM-ignoring child, 0 hung processes, outage record "
          f"schema-exact")
    return 0


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "run":
        sys.exit(run(sys.argv[2]))
    if len(sys.argv) == 3 and sys.argv[1] == "validate":
        sys.exit(validate(sys.argv[2]))
    sys.exit(f"usage: {sys.argv[0]} run OUT_DIR | validate OUT_DIR")

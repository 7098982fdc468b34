"""CI smoke for the cross-host resilient runtime (ISSUE 14): prove, with
real local processes, that multi-process training is loss-proof —

* a 2-process host group (real ``jax.distributed`` init over the gloo CPU
  collectives, per-rank heartbeats, init/done barriers) selects the SAME
  winner as the single-process control — multi-host changes the runtime,
  never the model;
* every rank's trace export shares ONE trace id (the launcher propagates a
  W3C traceparent to each rank) and ``merge_traces`` labels the lanes by
  rank;
* SIGKILLing rank 1 mid-sweep — right after its first candidate family
  checkpoints — is detected, the survivors abort via the posted group
  abort / preemption guard, the launcher relaunches at world size 1, the
  resumed sweep replays the checkpoint, and the winner is IDENTICAL;
* the loss writes the standardized outage record
  (``supervisor.OUTAGE_RECORD_KEYS``) and ZERO worker processes survive the
  harness.

Usage:
    python scripts/ci_hostgroup_smoke.py run OUT_DIR       # launch groups
    python scripts/ci_hostgroup_smoke.py validate OUT_DIR  # parse + assert
"""

import json
import os
import sys
import time

# runnable as `python scripts/ci_hostgroup_smoke.py` from the repo root
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

ROWS = int(os.environ.get("HOSTGROUP_SMOKE_ROWS", "560"))
SEED = int(os.environ.get("HOSTGROUP_SMOKE_SEED", "0"))
#: per-generation boot budget: 2 jax imports + distributed init on a busy
#: CI box; the drain grace must outlast one candidate family so a
#: preempted survivor checkpoints before exiting
BOOT_S = float(os.environ.get("HOSTGROUP_SMOKE_BOOT_S", "300"))
GRACE_S = float(os.environ.get("HOSTGROUP_SMOKE_GRACE_S", "90"))

_WORKER = os.path.join(_REPO, "scripts", "hostgroup_worker.py")


def _launch(tag, out_dir, hosts, *, env=None, distributed=True):
    from transmogrifai_tpu.parallel import hostgroup
    run_dir = os.path.join(out_dir, tag)
    ckpt = os.path.join(run_dir, "ckpt")
    cmd = [sys.executable, _WORKER, "--rows", str(ROWS),
           "--seed", str(SEED), "--ckpt-base", ckpt]
    t0 = time.monotonic()
    res = hostgroup.launch_hosts(
        cmd, hosts, run_dir=run_dir, boot_timeout=BOOT_S,
        liveness_timeout=30.0, grace_s=GRACE_S, max_relaunches=1,
        preflight=False, distributed=distributed, env=env)
    dones = {}
    for gen in range(res.generations):
        for rank in range(hosts):
            p = hostgroup.done_path(run_dir, rank, gen)
            if os.path.exists(p):
                with open(p) as fh:
                    dones[f"rank{rank}-gen{gen}"] = json.load(fh)
    return {"tag": tag, "result": res.to_json(), "dones": dones,
            "wallS": round(time.monotonic() - t0, 2), "runDir": run_dir}


def _live_worker_pids(run_dir):
    """Worker pids (from heartbeat/done markers) still alive — must be
    none after the launcher returns."""
    pids = set()
    for sub in ("hb", "done", "ready"):
        d = os.path.join(run_dir, sub)
        if not os.path.isdir(d):
            continue
        for f in os.listdir(d):
            try:
                with open(os.path.join(d, f)) as fh:
                    pid = json.load(fh).get("pid")
            except (OSError, ValueError):
                continue
            if pid:
                try:
                    os.kill(int(pid), 0)
                    pids.add(int(pid))
                except OSError:
                    pass
    return sorted(pids)


def run(out_dir):
    os.makedirs(out_dir, exist_ok=True)
    record = {"rows": ROWS, "seed": SEED}

    # 1. single-process control winner (same worker, world of 1)
    record["control"] = _launch("control", out_dir, 1, distributed=False)

    # 2. clean 2-process group: real jax.distributed over gloo
    record["clean"] = _launch("clean", out_dir, 2)

    # traceparent propagation: every rank's export shares one trace id and
    # merge_traces labels the lanes by rank
    from transmogrifai_tpu.telemetry import merge_traces
    clean_dir = record["clean"]["runDir"]
    traces = sorted(os.path.join(clean_dir, f)
                    for f in os.listdir(clean_dir)
                    if f.startswith("trace-rank"))
    merged = merge_traces(traces,
                          out_path=os.path.join(out_dir, "trace-merged.json"))
    trace_ids = {e["args"]["traceId"]
                 for e in merged["traceEvents"]
                 if e.get("ph") == "X" and e["args"].get("traceId")}
    labels = [e["args"]["name"] for e in merged["traceEvents"]
              if e.get("name") == "process_name"]
    record["trace"] = {"files": len(traces),
                       "traceIds": sorted(trace_ids),
                       "labels": labels}

    # 3. lost-host drill: rank 1 SIGKILLs itself after its first family
    #    checkpoints; survivors abort, group relaunches at world 1, resumes
    record["chaos"] = _launch(
        "chaos", out_dir, 2,
        env={"HOSTGROUP_WORKER_DIE_RANK": "1",
             "HOSTGROUP_WORKER_DIE_GEN": "0"})
    chaos_dir = record["chaos"]["runDir"]
    record["chaos"]["orphans"] = _live_worker_pids(chaos_dir)
    record["clean"]["orphans"] = _live_worker_pids(clean_dir)
    outage_path = os.path.join(chaos_dir, "OUTAGE_hostgroup_gen0.json")
    record["chaos"]["outageRecord"] = \
        json.load(open(outage_path)) if os.path.exists(outage_path) else None
    abort_path = os.path.join(chaos_dir, "abort.gen0.json")
    record["chaos"]["abort"] = \
        json.load(open(abort_path)) if os.path.exists(abort_path) else None

    with open(os.path.join(out_dir, "hostgroup_smoke.json"), "w") as fh:
        json.dump(record, fh, indent=2)
    print(json.dumps({k: v for k, v in record.items()
                      if k in ("control", "clean", "chaos")}, indent=2,
                     default=str)[:4000])
    return 0


def validate(out_dir):
    with open(os.path.join(out_dir, "hostgroup_smoke.json")) as fh:
        r = json.load(fh)
    control, clean, chaos = r["control"], r["clean"], r["chaos"]

    def winner(scenario, key):
        d = scenario["dones"].get(key) or {}
        return d.get("winner"), d.get("params")

    w_control = winner(control, "rank0-gen0")
    checks = {
        "control_completed": control["result"]["ok"]
        and w_control[0] is not None,
        "clean_completed": clean["result"]["ok"]
        and clean["result"]["generations"] == 1,
        "clean_same_winner_all_ranks":
            winner(clean, "rank0-gen0") == w_control
            and winner(clean, "rank1-gen0") == w_control,
        "clean_distributed_init_ran": all(
            (clean["dones"].get(f"rank{k}-gen0") or {}).get("traceId")
            for k in (0, 1)),
        "one_trace_id_across_ranks": len(r["trace"]["traceIds"]) == 1
        and r["trace"]["files"] == 2,
        "merged_trace_labels_ranks":
            any("[rank 0]" in l for l in r["trace"]["labels"])
            and any("[rank 1]" in l for l in r["trace"]["labels"]),
        "chaos_relaunched_once": chaos["result"]["ok"]
        and chaos["result"]["relaunches"] == 1
        and chaos["result"]["finalWorld"] == 1
        and chaos["result"]["generations"] == 2,
        "chaos_lost_rank1_gen0": [
            (l["rank"], l["generation"])
            for l in chaos["result"]["losses"]] == [(1, 0)],
        "chaos_resumed_same_winner":
            winner(chaos, "rank0-gen1") == w_control,
        "abort_posted": (chaos.get("abort") or {}).get("lost") == [1],
        "outage_record_schema_ok": _outage_schema_ok(
            chaos.get("outageRecord")),
        "zero_orphans": chaos["orphans"] == [] and clean["orphans"] == [],
    }
    print(json.dumps(checks, indent=2))
    if not all(checks.values()):
        failed = [k for k, v in checks.items() if not v]
        print(f"FAILED: {failed}", file=sys.stderr)
        return 1
    print("hostgroup smoke: all checks passed")
    return 0


def _outage_schema_ok(rec):
    from transmogrifai_tpu.parallel.supervisor import OUTAGE_RECORD_KEYS
    return isinstance(rec, dict) and set(rec) == set(OUTAGE_RECORD_KEYS)


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "run":
        sys.exit(run(sys.argv[2]))
    if len(sys.argv) == 3 and sys.argv[1] == "validate":
        sys.exit(validate(sys.argv[2]))
    sys.exit(f"usage: {sys.argv[0]} run OUT_DIR | validate OUT_DIR")

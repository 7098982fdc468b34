"""Run the dense bench at HIGGS scale points (4M / 8M / 11M — the
BASELINE.json north star) and write an artifact (git-ignored
``chiprun_out/`` by default).

Each size runs twice in fresh processes: the first pays any XLA compiles for
the new shapes ("cold"), the second measures the steady state ("warm").
Partial results are flushed after every run so a TPU-worker crash still
leaves an artifact.

DEFAULT PATH (ISSUE 10): the combined full grid runs IN ONE PROCESS with
mesh sharding forced on (TRANSMOGRIFAI_TPU_MESH=1) and chunked host→device
streaming, so the dataset is bounded by aggregate HBM across the mesh and
transfer staging is O(TRANSMOGRIFAI_DEVICE_CHUNK_BYTES) — the regime that
used to kill a single worker.

FALLBACK (--subprocess-ladder): the retired PER-FAMILY subprocess isolation
— each candidate family's CV grid in a fresh process
over identical data with an automated budget/cache retry ladder, scalar CV
metrics merged into one full-grid record.  Kept for single-device hardware
or post-mortems, no longer the default.

Usage: python scripts/run_scale_bench.py [--subprocess-ladder] [out.json] [sizes...]
"""

import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import last_json_line  # noqa: E402

# retry ladder for a crashed family run: progressively tighter HBM budgets
# (device-transfer cache cap, tree-histogram budget).  NOTE (ISSUE 15): the
# default mesh path now degrades IN-PROCESS via the memory governor's
# shrink-and-retry ladder (parallel/memory.py) — this env ladder survives
# only for the --subprocess-ladder fallback, where each step costs a fresh
# process and a re-paid feature-engineering pass.
_LADDER = [
    {"TRANSMOGRIFAI_DEVICE_CACHE_BYTES": str(256 << 20),
     "TRANSMOGRIFAI_TREE_BUDGET_GB": "4"},
    {"TRANSMOGRIFAI_DEVICE_CACHE_BYTES": str(128 << 20),
     "TRANSMOGRIFAI_TREE_BUDGET_GB": "3"},
    {"TRANSMOGRIFAI_DEVICE_CACHE_BYTES": str(64 << 20),
     "TRANSMOGRIFAI_TREE_BUDGET_GB": "2"},
]


def _run_bench(n, extra_env, timeout_s=3600):
    # cold/warm semantics rely on exactly ONE process per run, which is what
    # `bench.py --cell` is: the dense cell in a process that owns the chip
    env = {**os.environ, "BENCH_ROWS": str(n), **extra_env}
    # supervised child: SIGTERM→SIGKILL escalation reclaims a bench whose
    # native init hung (plain subprocess timeout leaves the hang alive);
    # rc=124 keeps the ladder's historical timeout convention
    from transmogrifai_tpu.parallel.supervisor import run_supervised
    r = run_supervised([sys.executable, os.path.join(ROOT, "bench.py"),
                        "--cell", "dense"],
                       timeout_s=timeout_s, grace_s=30.0, env=env, cwd=ROOT)
    rec = {"rc": r.rc, "proc_wall_s": round(r.wall_s, 1)}
    if r.escalated:
        rec["escalated_sigkill"] = True
    line = last_json_line(r.stdout)
    if line:
        rec["result"] = json.loads(line)
        # hoist the memory-governor block (plan, shrink level, peak RSS) so
        # scanning a scale artifact for OOM pressure doesn't require digging
        # through each run's full aux
        mem = (rec["result"].get("aux") or {}).get("memory")
        if mem:
            rec["memory"] = mem
    if r.rc != 0:
        rec["stderr_tail"] = ("timeout" if r.timed_out
                              else (r.stderr or ""))[-2000:]
    return rec


def _per_family(n, flush):
    """Each family's grid in its own process with the budget ladder; the
    parent merges scalars into one full-grid record."""
    fams = {}
    for fam in ("lr", "rf", "gbt"):
        for step, budgets in enumerate(_LADDER):
            rec = _run_bench(n, {"BENCH_FAMILIES": fam, **budgets})
            rec["ladder_step"] = step
            fams[fam] = rec
            flush()
            print(json.dumps({"family": fam, **rec})[:2000], flush=True)
            if rec["rc"] == 0:
                break
    ok = all(r["rc"] == 0 for r in fams.values())
    merged = {"rows": n, "phase": "per_family_isolated",
              "rc": 0 if ok else 1, "families": fams}
    if ok:
        # model name → (metric, source family key), sourced from whichever
        # process reported it — no hardcoded class-name table, so a renamed
        # or additional candidate cannot raise StopIteration here
        cv, src = {}, {}
        larger_better = True
        for fam_key, r in fams.items():
            aux = r["result"]["aux"]
            larger_better = bool(aux.get("metric_larger_better", True))
            for name, v in (aux.get("family_cv_metrics") or {}).items():
                cv[name], src[name] = v, fam_key
        merged["family_cv_metrics"] = cv
        if not cv:
            merged["rc"] = 1
            merged["note"] = ("family processes reported no CV metrics; "
                              "winner merge skipped")
            return merged
        # best per the validation evaluator's own direction (AuPR is
        # larger-better, but e.g. a regression RMSE selector is not)
        winner = (max if larger_better else min)(cv, key=cv.get)
        merged["winner"] = winner
        merged["metric_larger_better"] = larger_better
        # the winning family's process already refit its winner on the full
        # matrix and evaluated train AuROC — that IS the full grid's outcome
        merged["train_auroc"] = fams[src[winner]]["result"]["aux"][
            "train_auroc"]
        merged["combined_wall_s"] = round(sum(
            r["result"]["value"] for r in fams.values()), 2)
        merged["note"] = ("full grid as three isolated family processes "
                          "(identical data; winner selected across all "
                          "candidates); combined_wall_s = sum of family "
                          "walls, each re-paying feature engineering")
    return merged


def main():
    argv = list(sys.argv[1:])
    use_ladder = "--subprocess-ladder" in argv
    if use_ladder:
        argv.remove("--subprocess-ladder")
    out_path = argv[0] if argv else os.path.join(ROOT, "chiprun_out",
                                                 "scale_bench.json")
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    sizes = ([int(float(a)) for a in argv[1:]]
             or [4_000_000, 8_000_000, 11_000_000])
    out = {"workload": "dense HIGGS-difficulty (bench.py run_dense)",
           "path": "subprocess_ladder" if use_ladder else "mesh_sharded",
           "runs": []}

    def flush():
        with open(out_path, "w") as fh:
            json.dump(out, fh, indent=2)

    for n in sizes:
        combined_ok = False
        for phase in ("cold", "warm"):
            extra = {}
            if use_ladder:
                if n >= 8_000_000:
                    # cumulative HBM residency is what kills the worker at
                    # 10M+: shrink the
                    # host→device transfer cache so stale raw-column copies
                    # evict, and lower the tree histogram budget below the
                    # near-capacity trigger
                    extra = dict(_LADDER[0])
            else:
                # one-process mesh-sharded sweep (ISSUE 10): force the mesh
                # on regardless of the row threshold and stream the matrix
                # over in bounded chunks — resident data scales with
                # aggregate HBM, staging with the chunk budget
                extra = {"TRANSMOGRIFAI_TPU_MESH": "1"}
                extra.setdefault("TRANSMOGRIFAI_DEVICE_CHUNK_BYTES",
                                 os.environ.get(
                                     "TRANSMOGRIFAI_DEVICE_CHUNK_BYTES",
                                     str(256 << 20)))
            rec = {"rows": n, "phase": phase, **_run_bench(n, extra)}
            out["runs"].append(rec)
            flush()
            print(json.dumps(rec)[:2000], flush=True)
            if rec["rc"] != 0:
                print(f"size {n} {phase} failed", flush=True)
            elif phase == "warm":
                combined_ok = True
        if not combined_ok:
            if not use_ladder:
                print(f"size {n}: mesh-sharded run failed; re-run with "
                      "--subprocess-ladder for per-family isolation",
                      flush=True)
                continue
            print(f"size {n}: combined grid failed; isolating families",
                  flush=True)
            merged = _per_family(n, flush)
            out["runs"].append(merged)
            flush()
            print(json.dumps(merged)[:2000], flush=True)


if __name__ == "__main__":
    main()

#!/usr/bin/env python
"""Diff a fresh bench run against the standing perf record.

``bench.py`` appends every aggregate run to ``BENCH_STANDING.json``; this
script is the regression gate between the two: it compares a fresh run's
per-workload headline (wall seconds or rows/s, direction-aware) and the
stability counters that historically precede a wall regression
(``new_compiles_during_train``, ``selector_compile_s``, memory shrink
level) against the newest standing run, within tolerances, and exits 1 on
any regression.  CI runs it as a non-blocking step with the report
uploaded as an artifact, so a perf cliff is visible on the PR without a
flaky runner blocking merges.

Usage::

    python scripts/bench_compare.py fresh.log            # bench stdout
    python scripts/bench_compare.py fresh.json           # aggregate record
    python scripts/bench_compare.py fresh.log --tolerance 0.25 \
        --report bench_compare_report.json

The fresh input may be the bench's raw stdout (one ``"cell"``-tagged record
per line), a single record, or a standing-format document
(``{"runs": [...]}`` — newest run is used).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, List, Optional

DEFAULT_STANDING = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "BENCH_STANDING.json")

#: Aux counters gated in absolute terms: any increase past the allowance
#: is a regression even when the wall squeaked under tolerance.
AUX_ABSOLUTE_ALLOWANCE = {
    # warm-path invariant: training must not compile more than the
    # standing run did (a couple of slack compiles for grid jitter)
    "new_compiles_during_train": 2,
    # shrink level > standing means the run hit the memory ladder harder
    "memory_shrink_level": 0,
}

#: Aux counters gated relatively (same tolerance as the headline).
AUX_RELATIVE_HIGHER_IS_WORSE = (
    "selector_compile_s",
    "peak_staging_bytes",
    "host_peak_rss_bytes",
)


def last_json_line(text: str) -> Optional[Dict[str, Any]]:
    for line in reversed(text.splitlines()):
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            doc = json.loads(line)
        except ValueError:
            continue
        if isinstance(doc, dict):
            return doc
    return None


def load_workloads(path: str) -> Dict[str, Dict[str, Any]]:
    """Fresh input (stdout log / aggregate record / standing doc) → the
    ``{workload: record}`` map."""
    with open(path) as fh:
        text = fh.read()
    try:
        doc = json.loads(text)
    except ValueError:
        # bench stdout: one full record per cell (tagged "cell"), then a
        # compact aggregate that carries values only
        cells = {}
        for line in text.splitlines():
            if line.startswith("{"):
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if isinstance(rec, dict) and "cell" in rec:
                    cells[rec["cell"]] = rec
        if cells:
            return cells
        doc = last_json_line(text)
    if not isinstance(doc, dict):
        raise SystemExit(f"no JSON record found in {path!r}")
    if "runs" in doc:                      # standing-format document
        runs = doc.get("runs") or []
        if not runs:
            raise SystemExit(f"{path!r} has no runs")
        return runs[-1].get("workloads") or {}
    aux = doc.get("aux") or {}
    if "workloads" in aux:                 # bench aggregate record
        return aux["workloads"]
    if "workloads" in doc:
        return doc["workloads"]
    if "value" in doc:                     # single-workload record
        return {"headline": doc}
    raise SystemExit(f"unrecognized bench record shape in {path!r}")


def load_standing(path: str) -> Dict[str, Dict[str, Any]]:
    if not os.path.exists(path):
        return {}
    try:
        with open(path) as fh:
            runs = json.load(fh).get("runs") or []
    except (OSError, ValueError) as e:
        raise SystemExit(f"standing record {path!r} unreadable: {e}")
    return (runs[-1].get("workloads") or {}) if runs else {}


def _higher_is_better(unit: str) -> bool:
    # wall-style units regress upward, throughput units regress downward
    return "/s" in (unit or "")


def compare(fresh: Dict[str, Dict[str, Any]],
            standing: Dict[str, Dict[str, Any]],
            tolerance: float) -> Dict[str, Any]:
    findings: List[Dict[str, Any]] = []
    compared = 0
    for name in sorted(set(fresh) & set(standing)):
        f, s = fresh[name], standing[name]
        fv, sv = f.get("value"), s.get("value")
        if isinstance(fv, (int, float)) and isinstance(sv, (int, float)) \
                and sv > 0:
            compared += 1
            hib = _higher_is_better(str(f.get("unit") or s.get("unit")))
            ratio = fv / sv
            regressed = (ratio < 1.0 - tolerance if hib
                         else ratio > 1.0 + tolerance)
            findings.append({
                "workload": name, "kind": "headline",
                "unit": f.get("unit"), "fresh": fv, "standing": sv,
                "ratio": round(ratio, 4),
                "direction": "higher-better" if hib else "lower-better",
                "regressed": regressed})
        faux = f.get("aux") or {}
        saux = s.get("aux") or {}
        for key, allow in AUX_ABSOLUTE_ALLOWANCE.items():
            fa, sa = faux.get(key), saux.get(key)
            if isinstance(fa, (int, float)) and isinstance(sa, (int, float)):
                compared += 1
                findings.append({
                    "workload": name, "kind": f"aux:{key}",
                    "fresh": fa, "standing": sa, "allowance": allow,
                    "regressed": fa > sa + allow})
        for key in AUX_RELATIVE_HIGHER_IS_WORSE:
            fa, sa = faux.get(key), saux.get(key)
            if isinstance(fa, (int, float)) and isinstance(sa, (int, float)) \
                    and sa > 0:
                compared += 1
                findings.append({
                    "workload": name, "kind": f"aux:{key}",
                    "fresh": fa, "standing": sa,
                    "ratio": round(fa / sa, 4),
                    "regressed": fa / sa > 1.0 + tolerance})
    regressions = [f for f in findings if f["regressed"]]
    return {"tolerance": tolerance, "compared": compared,
            "freshWorkloads": sorted(fresh),
            "standingWorkloads": sorted(standing),
            "findings": findings,
            "regressions": regressions,
            "ok": not regressions and compared > 0}


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("fresh", help="fresh bench output: stdout log, "
                                 "aggregate JSON record, or standing-format "
                                 "document")
    p.add_argument("--standing", default=DEFAULT_STANDING,
                   help="standing perf record (default: repo "
                        "BENCH_STANDING.json)")
    p.add_argument("--tolerance", type=float, default=0.15,
                   help="relative tolerance on headline + relative aux "
                        "comparisons (default 0.15)")
    p.add_argument("--report", help="also write the comparison report JSON "
                                    "here (CI artifact)")
    args = p.parse_args(argv)

    fresh = load_workloads(args.fresh)
    standing = load_standing(args.standing)
    report = compare(fresh, standing, args.tolerance)
    if args.report:
        with open(args.report, "w") as fh:
            json.dump(report, fh, indent=2)
    if not standing:
        print(f"bench_compare: no standing record at {args.standing}; "
              "nothing to gate against")
        return 0
    if report["compared"] == 0:
        print("bench_compare: no overlapping workloads between fresh and "
              "standing runs")
        return 0
    for f in report["findings"]:
        mark = "REGRESSED" if f["regressed"] else "ok"
        extra = (f" ratio={f['ratio']}" if "ratio" in f
                 else f" allowance={f.get('allowance')}")
        print(f"[{mark:>9}] {f['workload']}/{f['kind']}: "
              f"fresh={f['fresh']} standing={f['standing']}{extra}")
    if report["regressions"]:
        print(f"bench_compare: {len(report['regressions'])} regression(s) "
              f"past tolerance {args.tolerance}")
        return 1
    print(f"bench_compare: {report['compared']} comparison(s) within "
          f"tolerance {args.tolerance}")
    return 0


if __name__ == "__main__":
    sys.exit(main())

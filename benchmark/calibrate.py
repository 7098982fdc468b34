#!/usr/bin/env python3
"""Readings the limits of ``limits/<workload>.json`` are set from, and the
verdict those limits give on each.

    python3 benchmark/calibrate.py --workload <name> --seeds 1,2,3 [--control 1] [--faults 1]

For every seed, in one process: the data, ONE ``Workflow.train()`` at the
cell's own size through the harness's own call, the program's state dropped,
then the plain reference as the configuration states it (the lower reading:
the program against the reference) and, with ``--control 1``, the reference
at the nearest precision below put in the program's place (the upper reading:
the control against the reference).  With ``--faults 1`` also the reference
over half of the rows, put in the program's place.  Each is judged by
``run.verdict`` against the cell's own limits, as a benchmark run is:
``correct`` says what came out.  One JSON line a seed on standard output.
Not part of a benchmark run; needs the accelerator like one.
"""

import argparse
import json
import os
import sys
import time

from run import ROOT, Cell, device_record, drop_program_state, load_json, \
    one_train, say, verdict


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=1)
    ap.add_argument("--faults", type=int, default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    import jax
    from benchmark.reference import common, plain
    cell = Cell(load_json("BENCHMARK.json"), args.workload)
    device, _ = device_record(jax, cell.entry["chips"])
    if device["platform"] == "cpu":
        say(f"no accelerator: jax reports {device}")
        return 3
    platform = device["platform"]
    stated = plain.Precision.stated(platform)
    for seed in (int(s) for s in args.seeds.split(",")):
        data = cell.program.make_data(cell.rows, seed, cell.config)
        rec = one_train(cell, data, platform)
        out = {"workload": args.workload, "seed": seed, "rows": cell.rows,
               "platform": platform, "train_s": rec["wall_s"],
               "why_failed": rec["why_failed"], "correct": {}}
        p = rec["produced"]
        if p is not None:
            drop_program_state()
            ask = cell.reference.question(p)
            t = time.monotonic()
            ref = cell.reference.reference(data, cell.config, stated, ask,
                                           seed=seed)
            out["reference_s"] = time.monotonic() - t
            stand_ins = {"program": p}
            if args.control:
                stand_ins["control"] = common.as_produced(
                    cell.reference.reference(
                        data, cell.config, plain.Precision.control(platform),
                        ask, seed=seed), p, cell.config)
            if args.faults:
                half = {k: v[:cell.rows // 2] for k, v in data.items()}
                stand_ins["half_rows"] = common.as_produced(
                    cell.reference.reference(half, cell.config, stated, ask,
                                             seed=seed), p, cell.config)
            for name, answer in stand_ins.items():
                ok, compared = verdict(cell, [answer], ref)
                out["correct"][name] = ok
                out[name] = {k: c["value"] for k, c in compared.items()}
            out["winner"] = p["winner"]
            out["kept"] = int(len(p["kept"]))
            out["cv"] = [[r["params"], r["metric"], r["raced_out"]]
                         for r in p["cv"]]
            out["ref_cv"] = [[r["params"], r["per_fold"]] for r in ref["cv"]]
        print(json.dumps(out), flush=True)
        del data, rec, p
    return 0


if __name__ == "__main__":
    sys.exit(main())

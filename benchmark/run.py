#!/usr/bin/env python3
"""One cell of BENCHMARK.json, once, in this process.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>
    python3 benchmark/run.py --selftest

A cell is a configuration (``configs/<config>.json``, the user program
``programs/<config>.py``, the plain reference ``reference/<config>.py``)
under a traffic mix (``traffic/<mix>.json``).  Set-up makes the data from
``--seed`` and runs the first ``Workflow.train()`` of the process at the
cell's own shapes; the window then starts trains back to back while it is
younger than ``--seconds`` and finishes the one in flight.  Every train is a
new user's train: a fresh Workflow over copies of the seed's host arrays.
After the window the program's state is dropped and the reference is run once
over the same data; what every train of the window produced is compared with
it, each number against its limit (``limits/<workload>.json``).

The last line of standard output is the result: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``, and
``compared`` last.  Off an accelerator nothing is measured and no result is
printed (exit 3); ``--selftest`` is the CPU rehearsal and prints no device
metric.
"""

import time

T0 = time.monotonic()            # process start, as near as Python gives it

import argparse
import contextlib
import gc
import glob
import importlib
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NO_ACCELERATOR = 3


def say(*a):
    print(*a, file=sys.stderr, flush=True)


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as fh:
        return json.load(fh)


def by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"BENCHMARK.json has no {what} named {name!r}")


class Cell:
    """A cell's files, found by the names BENCHMARK.json gives.  ``rows`` and
    ``limits`` stand in for the configuration's rows and the cell's limits
    file: the rehearsal's and the tests' way to a size a CPU can hold
    (``fixtures/cpu_cells.json``)."""

    def __init__(self, manifest, name, rows=None, limits=None):
        self.name = name
        self.entry = by_name(manifest["workloads"], name, "workload")
        cfg = by_name(manifest["configs"], self.entry["config"], "config")
        self.config = load_json(cfg["file"])
        self.traffic = load_json("benchmark", "traffic",
                                 self.entry["traffic"] + ".json")
        if (self.traffic["job"], self.traffic["arrival"]) != (
                "train", "back_to_back"):
            raise SystemExit(f"traffic/{self.entry['traffic']}.json asks for "
                             "a job or an arrival this generator has not")
        self.rows = int(rows or self.traffic.get("rows")
                        or self.config["rows"])
        self.limits = (load_json("benchmark", "limits", name + ".json")
                       if limits is None else limits)
        self.program = importlib.import_module(
            "benchmark.programs." + self.entry["config"])
        self.reference = importlib.import_module(
            "benchmark.reference." + self.entry["config"])
        self.end_to_end = [m for m in manifest["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in manifest["per_layer"]
                          if name in m.get("workloads", [name])]


def cpu_cells():
    """{cell: its rows and limits on the CPU} for ``--selftest`` and
    ``tests/``: BENCHMARK.json's cells at a size a CPU can hold."""
    cells = {}
    for path in sorted(glob.glob(os.path.join(HERE, "fixtures",
                                              "cpu_cells*.json"))):
        cells.update(load_json(path)["cells"])
    return cells


def device_record(jax, chips):
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}, devs[:max(chips, 1)]


def memory_peak(devs):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks)) if peaks else 0


def one_train(cell, data, platform, tracer=None):
    """A new user's train, ended by pulling what it produced to the host.
    Returns what the window keeps of it."""
    import jax
    from benchmark import produced
    from transmogrifai_tpu import profiling
    from transmogrifai_tpu.resilience import FailureLog, use_failure_log
    from transmogrifai_tpu.telemetry import use_tracer

    rec = {"phases": [], "why_failed": [], "produced": None}
    before = produced.counters()
    c0, l0 = profiling.compile_stats(), profiling.host_link_bytes()
    t0 = time.monotonic()
    model = None
    try:
        with use_failure_log(FailureLog()) as ambient, (
                use_tracer(tracer) if tracer is not None
                else contextlib.nullcontext()):
            model = cell.program.build(data, cell.config).train()
            rec["produced"] = produced.extract(model)
            jax.block_until_ready([
                v for _, c in model.train_batch.items()
                for v in (c.values.values() if isinstance(c.values, dict)
                          else [c.values]) if isinstance(v, jax.Array)])
        rec["why_failed"] = produced.left_the_path(model, ambient, before,
                                                   platform)
        rec["phases"] = [{"name": p.name, "wall_s": p.wall_s,
                          "compile_s": p.compile_s}
                         for p in model.app_metrics.phases]
    except Exception as e:                      # a train that raised failed
        import traceback
        traceback.print_exc()
        rec["why_failed"] = [f"raised {type(e).__name__}: {e}"]
    rec["wall_s"] = time.monotonic() - t0
    c1 = profiling.compile_stats()
    rec["compiles"] = c1["backend_compiles"] - c0["backend_compiles"]
    rec["compile_s"] = c1["compile_s"] - c0["compile_s"]
    rec["link_bytes"] = profiling.host_link_bytes() - l0
    del model
    gc.collect()
    return rec


def drop_program_state():
    """Free what the program still holds on the device between users."""
    import jax
    from transmogrifai_tpu.columns import shed_device_cache
    gc.collect()
    shed_device_cache()
    jax.clear_caches()
    gc.collect()


def required_work(cell, rows, ref, won):
    """Operations and bytes ONE train needs, from ``work/<family>.py``."""
    storage = cell.config["precision"]["matrix_storage"].split()[0]
    shape = dict(cell.config, rows=rows,
                 columns=int(ref["stats"].shape[1]),
                 kept_columns=int(len(ref["kept"])),
                 storage_bytes={"bfloat16": 2, "float32": 4}[storage])
    ops = nbytes = 0.0
    for family in cell.config["work"]:
        mod = importlib.import_module("benchmark.work." + family)
        o, b = mod.required(shape, won == mod.FAMILY)
        ops, nbytes = ops + o, nbytes + b
    return {"ops": ops, "bytes": nbytes}


def verdict(cell, answers, ref):
    """Every answer (what a train produced, or a stand-in in that shape)
    compared with the reference ``ref``, each number against its limit.
    Returns (correct, compared): the worst reading of each number."""
    from benchmark.reference import common
    worst = {}
    for p in answers:
        for k, v in common.compare(p, ref, cell.config).items():
            worst[k] = max(worst.get(k, 0.0), v)
    compared = {}
    for k, v in worst.items():
        if k not in cell.limits:
            raise SystemExit(f"limits/{cell.name}.json has no limit for {k}")
        compared[k] = {"value": v, "limit": cell.limits[k]}
    ok = bool(compared) and all(
        c["value"] <= c["limit"] for c in compared.values())
    return ok, compared


def judge(cell, trains, data, seed, platform):
    """The reference once per question asked, every finished train compared.
    Returns (correct, compared, a reference answer)."""
    from benchmark.reference import plain
    precision = plain.Precision.stated(platform)
    asked, ref = {}, None
    for t in trains:
        p = t["produced"]
        if p is None:
            continue
        ask = cell.reference.question(p)
        key = json.dumps(ask, sort_keys=True)
        if key not in asked:
            t0 = time.monotonic()
            asked[key] = (cell.reference.reference(
                data, cell.config, precision, ask, seed=seed), [])
            say(f"reference ({key}): {time.monotonic() - t0:.1f} s")
        asked[key][1].append(p)
    ok, compared = bool(asked), {}
    for ref, answers in asked.values():
        good, part = verdict(cell, answers, ref)
        ok = ok and good
        for k, c in part.items():
            if k not in compared or c["value"] > compared[k]["value"]:
                compared[k] = c
    return ok, compared, ref


def set_up(cell, seed, platform):
    """Data from the seed and the first train of the process at the cell's
    shapes.  Returns (data, what set-up compiled, seconds since the process
    started)."""
    from transmogrifai_tpu import profiling
    rows = cell.rows
    t = time.monotonic()
    data = cell.program.make_data(rows, seed, cell.config)
    gc.collect()
    gc.freeze()     # the data is not the collector's to walk
    say(f"data: {rows} rows from seed {seed} in {time.monotonic() - t:.1f} s")
    c0 = profiling.compile_stats()
    warm = one_train(cell, data, platform)
    c1 = profiling.compile_stats()
    if warm["why_failed"]:
        raise SystemExit(f"the warm-up train failed: {warm['why_failed']}")
    setup = {"compile_s": c1["compile_s"] - c0["compile_s"],
             "compiles": c1["backend_compiles"] - c0["backend_compiles"],
             "cache_misses": c1["cache_misses"] - c0["cache_misses"],
             "train_s": warm["wall_s"]}
    setup_s = time.monotonic() - T0
    say(f"set-up: {setup_s:.1f} s, warm-up train {warm['wall_s']:.1f} s, "
        f"{setup}")
    return data, setup, setup_s


def traced_train(cell, data, platform):
    """One train under the profiler and the program's own tracer.  Returns
    (the train, the trace's reduction or None, seconds spent writing the
    trace out, which belong to no train)."""
    import jax
    from benchmark import trace as trace_mod
    from transmogrifai_tpu.telemetry import Tracer
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level, opts.host_tracer_level = 0, 1
    opts.enable_hlo_proto = False
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    tracer = Tracer("bench")
    try:
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        anchor = time.monotonic()
        with jax.profiler.TraceAnnotation(trace_mod.WINDOW_ANNOTATION):
            rec = one_train(cell, data, platform, tracer)
        t = time.monotonic()
        jax.profiler.stop_trace()
        spans = [(s.name, tracer.t0_mono + s.start_s,
                  tracer.t0_mono + (s.end_s or s.start_s))
                 for s in tracer.spans]
        path = trace_mod.newest_xplane(trace_dir)
        reduced = path and trace_mod.reduce_file(path, host_spans=spans,
                                                 anchor_s=anchor)
        return rec, reduced, time.monotonic() - t
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def window(cell, data, platform, seconds, trace):
    """Trains back to back while the window is younger than ``seconds``; the
    one in flight is finished and at least one runs.  With ``trace`` the
    first one is traced.  Returns (trains, the window's seconds, the trace's
    reduction)."""
    trains, traced, untimed = [], None, 0.0
    start = time.monotonic()
    while not trains or time.monotonic() - start < seconds:
        if trace and not trains:
            rec, traced, untimed = traced_train(cell, data, platform)
        else:
            rec = one_train(cell, data, platform)
        trains.append(rec)
        say(f"train {len(trains)}: {rec['wall_s']:.2f} s "
            f"{rec['why_failed'] or ''}")
    return trains, time.monotonic() - start - untimed, traced


def layer_metrics(cell, ctx):
    """Every per-layer metric of the cell whose reader finds something."""
    out = {}
    for m in cell.per_layer:
        reader = importlib.import_module("benchmark.layer_metrics."
                                         + m["name"])
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(manifest, name, seed, seconds, trace, require_chip=True,
             rows=None, limits=None):
    """Drive one cell; returns the result as a dict (None: no accelerator)."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    import jax
    cell = Cell(manifest, name, rows, limits)
    device, devs = device_record(jax, cell.entry["chips"])
    on_chip = device["platform"] != "cpu"
    if require_chip and not (on_chip
                             and device["count"] >= cell.entry["chips"]):
        say(f"no accelerator for {name}: jax reports {device}")
        return None
    peaks = load_json("benchmark", "peaks.json").get(device["kind"])
    if peaks is None and on_chip:
        raise SystemExit(f"peaks.json has no device kind {device['kind']!r}")

    platform = device["platform"]
    data, setup, setup_s = set_up(cell, seed, platform)
    trains, window_s, traced = window(cell, data, platform, seconds, trace)
    peak = memory_peak(devs)
    done = [t for t in trains if t["produced"] is not None]
    train_wall_s = window_s / len(done) if done else None

    drop_program_state()
    ok, compared, ref = judge(cell, trains, data, seed, platform)
    failed = [t for t in trains if t["why_failed"]]
    for t in failed:
        say(f"failed train: {t['why_failed']}")

    if trace:
        work = ref and required_work(cell, cell.rows, ref,
                                     done[0]["produced"]["winner"]["family"])
        metrics = layer_metrics(cell, {
            "trains": done, "setup": setup, "trace": traced, "work": work,
            "peaks": peaks, "memory_peak_bytes": peak if on_chip else None,
            "train_wall_s": train_wall_s if on_chip else None})
    else:
        values = {"train_wall_s": train_wall_s, "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end
                   if values.get(m["name"]) is not None}
    if on_chip:
        device["memory_peak_bytes"] = peak
    result = {"correct": ok and not failed, "attempted": len(trains),
              "failed": len(failed), "metrics": metrics, "device": device}
    if traced:
        device["busy_s"], device["window_s"] = (traced["busy_s"],
                                                traced["window_s"])
        result["breakdown"] = {"device_ops": traced["device_ops"],
                               "idle_gaps": traced["idle_gaps"]}
    result["compared"] = compared
    for k, c in compared.items():
        say(f"compared {k} {c['value']:.6g} limit {c['limit']:.6g}"
            + ("" if c["value"] <= c["limit"] else "  OVER"))
    return result


def selftest():
    """The rehearsal that needs no chip: the trace reduction against a plane
    built by hand, each work function against a count made by hand, and every
    cell end to end at a tiny size on the CPU."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from benchmark import trace as trace_mod
    want = load_json("benchmark", "fixtures", "two_ops.expected.json")
    with open(os.path.join(HERE, "fixtures", "two_ops.xspace.txt")) as fh:
        got = trace_mod.reduce_text(
            fh.read(), host_spans=[tuple(s) for s in want["host_spans"]],
            anchor_s=want["anchor_s"])
    for key in ("busy_s", "window_s"):
        assert abs(got[key] - want[key]) < 1e-12, (key, got[key], want[key])
    for key in ("device_ops", "idle_gaps"):
        assert [[k, round(v, 12)] for k, v in got[key]] == want[key], (
            key, got[key])
    say("selftest: trace reduction agrees with the hand count")

    cases = [c for path in sorted(glob.glob(os.path.join(
        HERE, "fixtures", "work_expected*.json"))) for c in load_json(path)]
    for case in cases:
        mod = importlib.import_module("benchmark.work." + case["family"])
        got = mod.required(case["shape"], case["won"])
        for g, w in zip(got, (case["ops"], case["bytes"])):
            assert abs(g - w) <= 1e-12 * w, (case, got)
    say("selftest: work functions agree with the hand counts")

    manifest = load_json("BENCHMARK.json")
    for name, tiny in cpu_cells().items():
        for trace in (0, 1):
            res = run_cell(manifest, name, 2 ** 31 + 7, 0, trace,
                           require_chip=False, rows=tiny["rows"],
                           limits=tiny["limits"])
            assert res["correct"], res
            say(f"selftest: {name} --trace {trace} ran on "
                f"{res['device']['platform']}: correct, "
                f"{sorted(res['metrics'])}; no device metric is printed "
                "off the chip")
    print("selftest ok")
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--selftest", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    if args.selftest:
        return selftest()
    if not args.workload:
        ap.error("--workload is required")
    manifest = load_json("BENCHMARK.json")
    seconds = (manifest["run_seconds"] if args.seconds is None
               else args.seconds)
    result = run_cell(manifest, args.workload, args.seed, seconds,
                      bool(args.trace))
    if result is None:
        return NO_ACCELERATOR
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

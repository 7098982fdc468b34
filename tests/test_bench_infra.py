"""Bench infrastructure: the launcher's pre-flight probe, one-process-per-
chip cells, and the scale bench's per-family merge.  The measuring path
must FAIL without a chip — no CPU record, non-zero exit — and a failed child
must fail the run; both are pinned here."""

import importlib.util
import json
import os
import subprocess
import sys

from transmogrifai_tpu.parallel.supervisor import OUTAGE_RECORD_KEYS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_TPU_PROBE = ('import json; print(json.dumps({"platform": "tpu", '
                '"device_kind": "TPU v5 lite", "devices": ["TPU_0"], '
                '"matmul_finite": True}))')
_CPU_PROBE = ('import json; print(json.dumps({"platform": "cpu", '
                '"device_kind": "cpu", "devices": ["CpuDevice(id=0)"], '
                '"matmul_finite": True}))')


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    return m


def _fake_children(monkeypatch, script_for):
    """Every child the launcher starts (the supervisor's Popen seam, so the
    real SIGTERM->SIGKILL escalation path runs) becomes
    ``python -c script_for(cmd)``."""
    orig_popen = subprocess.Popen

    def fake_popen(cmd, **kw):
        assert cmd[0] == sys.executable
        return orig_popen([sys.executable, "-c", script_for(cmd)], **kw)

    monkeypatch.setattr(subprocess, "Popen", fake_popen)


def _json_lines(text):
    return [json.loads(ln) for ln in text.splitlines() if ln.startswith("{")]


def test_probe_outage_is_a_nonzero_exit_with_no_record(monkeypatch, capsys,
                                                       tmp_path):
    bench = _load("bench_probe_test", os.path.join(ROOT, "bench.py"))
    # a probe subprocess that sleeps forever must be classified as a hang
    # within the configured timeout, once per backoff entry — and then the
    # run FAILS: no CPU fallback, nothing that parses as a result
    monkeypatch.setenv("BENCH_PROBE_TIMEOUT_S", "1")
    monkeypatch.setenv("BENCH_PROBE_BACKOFFS", "0,0")
    outage = tmp_path / "outage.json"
    monkeypatch.setenv("BENCH_OUTAGE_RECORD", str(outage))
    started = []

    def script_for(cmd):
        started.append(cmd)
        return "import time; time.sleep(30)"

    _fake_children(monkeypatch, script_for)
    assert bench.main([]) != 0
    assert _json_lines(capsys.readouterr().out) == []
    assert len(started) == 2, "only the two probes ran — no cell, no retry"
    rec = json.loads(outage.read_text())
    assert set(OUTAGE_RECORD_KEYS) <= set(rec)
    assert [t["result"] for t in rec["timeline_utc"]] == ["hang", "hang"]


def test_probe_that_resolves_cpu_is_not_measured(monkeypatch, capsys):
    bench = _load("bench_probe_test2", os.path.join(ROOT, "bench.py"))
    monkeypatch.setenv("BENCH_PROBE_BACKOFFS", "0")
    monkeypatch.delenv("BENCH_OUTAGE_RECORD", raising=False)
    monkeypatch.delenv("TRANSMOGRIFAI_OUTAGE_DIR", raising=False)
    started = []

    def script_for(cmd):
        started.append(cmd)
        return _CPU_PROBE

    _fake_children(monkeypatch, script_for)
    # jax answered, but on the CPU: the measuring path refuses it
    assert bench.main([]) != 0
    assert _json_lines(capsys.readouterr().out) == []
    assert len(started) == 1


def test_cells_run_in_children_pinned_to_the_probed_platform(monkeypatch,
                                                             capsys):
    bench = _load("bench_probe_test3", os.path.join(ROOT, "bench.py"))
    monkeypatch.setenv("BENCH_PROBE_BACKOFFS", "0")
    monkeypatch.setenv("BENCH_WORKLOAD", "selector_smoke")
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    cell = ('import json, os; print(json.dumps({"metric": "m", "value": 1, '
            '"unit": "s", "vs_baseline": 1.0, "aux": {"pinned": '
            'os.environ["JAX_PLATFORMS"]}}))')
    _fake_children(monkeypatch,
                   lambda cmd: cell if "--cell" in cmd else _TPU_PROBE)
    assert bench.main([]) == 0
    (rec,) = _json_lines(capsys.readouterr().out)
    # a child that cannot get the chip must fail, not continue on the CPU
    assert rec["aux"]["pinned"] == "tpu"


def test_failed_child_fails_the_cell_and_the_run(monkeypatch, capsys):
    bench = _load("bench_probe_test4", os.path.join(ROOT, "bench.py"))
    monkeypatch.setenv("BENCH_PROBE_BACKOFFS", "0")
    monkeypatch.setenv("BENCH_WORKLOAD", "serve_cold_start")
    steps = []

    def script_for(cmd):
        if "--step" in cmd:
            steps.append(cmd)
            return "import sys; sys.exit(7)"
        return _TPU_PROBE

    _fake_children(monkeypatch, script_for)
    assert bench.main([]) == 1
    out = capsys.readouterr()
    assert _json_lines(out.out) == []
    assert "serve_cold_start FAILED" in out.err and "rc=7" in out.err
    assert len(steps) == 1, "the failed step is not retried"


def test_launcher_imports_leave_the_backend_alone():
    # a chip belongs to one process: the launcher (and the pool parent it
    # hosts for serve_scaleout) must be able to import everything it uses
    # without initializing a jax backend
    code = ("import bench\n"
            "from transmogrifai_tpu.parallel import supervisor\n"
            "from transmogrifai_tpu.serving import pool, wire\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge.backends_are_initialized()\n")
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]


def test_unknown_device_kind_has_no_peak():
    bench = _load("bench_peak_test", os.path.join(ROOT, "bench.py"))
    assert bench.peak_flops("TPU v5 lite") == 1.97e14
    try:
        bench.peak_flops("TPU v9000")
    except KeyError as e:
        assert "TPU v9000" in str(e)
    else:
        raise AssertionError("an unknown device kind must not get a peak")


def test_last_json_line():
    bench = _load("bench_json_test", os.path.join(ROOT, "bench.py"))
    out = "noise\n{\"a\": 1}\nmore noise\n{\"b\": 2}\ntail"
    assert json.loads(bench.last_json_line(out)) == {"b": 2}
    assert bench.last_json_line("no json here") is None


def test_scale_bench_per_family_merge(monkeypatch):
    rsb = _load("rsb_test", os.path.join(ROOT, "scripts",
                                         "run_scale_bench.py"))

    def fake_run_bench(n, extra_env, timeout_s=3600):
        fam = extra_env["BENCH_FAMILIES"]
        # rf crashes at the default budget and recovers one ladder step down
        if fam == "rf" and extra_env.get(
                "TRANSMOGRIFAI_TREE_BUDGET_GB") == "4":
            return {"rc": 1, "proc_wall_s": 5.0, "stderr_tail": "UNAVAILABLE"}
        metric = {"lr": ("OpLogisticRegression", 0.80),
                  "rf": ("OpRandomForestClassifier", 0.84),
                  "gbt": ("OpGBTClassifier", 0.82)}[fam]
        return {"rc": 0, "proc_wall_s": 10.0,
                "result": {"value": 7.0, "unit": "s",
                           "aux": {"family_cv_metrics": {metric[0]: metric[1]},
                                   "train_auroc": metric[1] + 0.01}}}

    monkeypatch.setattr(rsb, "_run_bench", fake_run_bench)
    merged = rsb._per_family(1000, lambda: None)
    assert merged["rc"] == 0
    assert merged["winner"] == "OpRandomForestClassifier"
    assert merged["train_auroc"] == 0.85
    assert merged["combined_wall_s"] == 21.0
    assert merged["families"]["rf"]["ladder_step"] == 1
    assert len(merged["family_cv_metrics"]) == 3

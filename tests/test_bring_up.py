"""What can be pinned about chip bring-up without a chip (ISSUE 21):

* AOT executables load over the devices they were compiled for, so a host
  that shows several devices runs them instead of quietly giving way to JIT;
* the persistent compile cache stays where the environment put it — through
  package import, a runner train with a checkpoint location, a registry
  ``configure`` and the environments built for pool / host-group children —
  and is one fixed in-checkout directory when the environment says nothing;
* ``chip_smoke.py`` refuses to run off the accelerator;
* the serving pool gives worker *k* chip *k*, refuses more workers than
  chips, refuses a CPU fallback on a host that has an accelerator, and
  checks where each worker says it computes."""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from test_aux_subsystems import make_records, train_small_model  # noqa: E402

from transmogrifai_tpu import compiled  # noqa: E402
from transmogrifai_tpu.parallel import supervisor as sup  # noqa: E402
from transmogrifai_tpu.profiling import compile_stats  # noqa: E402
from transmogrifai_tpu.serving.engine import records_to_batch  # noqa: E402
from transmogrifai_tpu.serving.pool import ServingPool  # noqa: E402
from transmogrifai_tpu.telemetry import REGISTRY  # noqa: E402
from transmogrifai_tpu.workflow import WorkflowModel  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _counter(name):
    return REGISTRY.snapshot()["counters"].get(name, 0)


def test_aot_load_runs_on_a_multi_device_host(tmp_path):
    """save -> load -> score with more than one device visible: every
    shipped executable installs and is CALLED — zero fallbacks to JIT."""
    assert len(jax.devices()) > 1, "conftest forces several host devices"
    model = train_small_model(make_records(120))[0].train()
    bundle = str(tmp_path / "model")
    model.save(bundle)
    before = {k: _counter(k) for k in (
        "aot_registry.installs", "aot_registry.call_fallbacks",
        "aot_registry.install_failures", "aot.fallback")}
    loaded = WorkflowModel.load(bundle)
    assert loaded.aot_executables > 0
    assert _counter("aot_registry.installs") > before["aot_registry.installs"]
    records = [{"x1": 0.4, "x2": 3.0, "cat": "a"}] * 4   # a ladder size
    pred = next(f.name for f in loaded.result_features)

    def score(m):
        scored = m.score(batch=records_to_batch(m.raw_features, records))
        return np.asarray(scored[pred].values["probability"])

    np.testing.assert_array_equal(score(loaded), score(model))
    for k in ("aot_registry.call_fallbacks", "aot_registry.install_failures",
              "aot.fallback"):
        assert _counter(k) == before[k], k
    events = [e for e in loaded.failure_log.events
              if e.action in ("degraded", "fallback")] \
        if getattr(loaded, "failure_log", None) else []
    assert events == []


def test_export_rebuilds_a_cache_loaded_program_before_serializing(
        tmp_path, monkeypatch):
    """XLA:CPU: an executable jax LOADED from the persistent compile cache
    serializes into a payload that fails at its first call.  A program first
    dispatched before ``save()`` may be one, so the export builds it again."""
    from transmogrifai_tpu import aot_registry
    assert not aot_registry.cache_loads_reserialize()    # this is the CPU
    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_persistent_cache_min_compile_time_secs)
    records = make_records(120)
    try:
        jax.config.update("jax_compilation_cache_dir",
                          str(tmp_path / "xla-cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        aot_registry._reset_jax_compile_cache()
        model = train_small_model(records)[0].train()
        pred = next(f.name for f in model.result_features)
        batch = records_to_batch(model.raw_features, records[:37])

        def score(m):
            return np.asarray(m.score(batch=batch)[pred].values["probability"])
        want = score(model)          # compiles the 37-row program: disk cache
        # fresh-process simulation: in-memory executables gone (jit's, the
        # score program's and the process-wide table's), disk entries not —
        # the next dispatch of the 37-row program is a cache LOAD
        jax.clear_caches()
        model._score_program = None
        monkeypatch.setattr(compiled, "SHARED_EXECUTABLES",
                            compiled._SharedExecutables(capacity=64))
        hits = compile_stats()["cache_hits"]
        np.testing.assert_array_equal(score(model), want)
        assert compile_stats()["cache_hits"] > hits, \
            "precondition: the pre-save dispatch was a cache load"
        bundle = str(tmp_path / "model")
        model.save(bundle)
        fallbacks = _counter("aot.fallback")
        loaded = WorkflowModel.load(bundle)
        assert loaded.aot_executables > 0
        np.testing.assert_array_equal(score(loaded), want)   # CALLS it
        assert _counter("aot.fallback") == fallbacks
    finally:
        jax.config.update("jax_compilation_cache_dir", saved[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          saved[1])
        aot_registry._reset_jax_compile_cache()


_CACHE_CHILD = r"""
import json, os, sys
sys.path.insert(0, os.path.join(sys.argv[1], "tests"))
import jax
import transmogrifai_tpu
seen = {"import": jax.config.jax_compilation_cache_dir}

from test_aux_subsystems import make_records, train_small_model
from transmogrifai_tpu.params import OpParams
from transmogrifai_tpu.runner import OpWorkflowRunner, RunType
out = sys.argv[2]
if sys.argv[3] == "train":
    wf, _ = train_small_model(make_records(120))
    OpWorkflowRunner(wf).run(RunType.TRAIN, OpParams(
        model_location=os.path.join(out, "model"),
        checkpoint_location=os.path.join(out, "ckpt")))
seen["runner_train"] = jax.config.jax_compilation_cache_dir

from transmogrifai_tpu import aot_registry
aot_registry.configure(root=os.path.join(out, "registry"))
seen["registry_configure"] = jax.config.jax_compilation_cache_dir

from transmogrifai_tpu.serving.pool import ServingPool
pool = ServingPool(os.path.join(out, "model"), workers=1,
                   run_dir=os.path.join(out, "pool"))
pool._device_env = pool._resolve_device_env()
env = pool._worker_env(pool.slots[0])
seen["pool_child_env"] = {k: env.get(k) for k in (
    "JAX_COMPILATION_CACHE_DIR", "TRANSMOGRIFAI_COMPILE_CACHE")}
seen["default"] = transmogrifai_tpu.DEFAULT_COMPILE_CACHE_DIR
seen["entries_under_ckpt"] = [
    os.path.join(d, f) for d, _, fs in os.walk(out) for f in fs
    if "compile-cache" in d]
print(json.dumps(seen))
"""


def _run_cache_child(tmp_path, cache_env, train):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR",
                        "TRANSMOGRIFAI_COMPILE_CACHE",
                        "TRANSMOGRIFAI_COMPILATION_CACHE",
                        "TRANSMOGRIFAI_AOT_REGISTRY")}
    env.update(cache_env)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    p = subprocess.run([sys.executable, "-c", _CACHE_CHILD, REPO,
                        str(tmp_path), "train" if train else "no-train"],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_compile_cache_stays_where_the_environment_put_it(tmp_path):
    placed = str(tmp_path / "placed-cache")
    seen = _run_cache_child(tmp_path / "run",
                            {"JAX_COMPILATION_CACHE_DIR": placed}, train=True)
    for step in ("import", "runner_train", "registry_configure"):
        assert seen[step] == placed, (step, seen[step])
    assert seen["pool_child_env"]["JAX_COMPILATION_CACHE_DIR"] == placed
    assert seen["pool_child_env"]["TRANSMOGRIFAI_COMPILE_CACHE"] is None
    assert seen["entries_under_ckpt"] == []
    assert os.listdir(placed), "the train's programs were cached there"


def test_compile_cache_defaults_to_one_fixed_directory_in_the_checkout(
        tmp_path):
    # (the runner train is exercised in the placed-cache test above; here
    # only what import, configure and the child env decide)
    seen = _run_cache_child(tmp_path / "run", {}, train=False)
    fixed = os.path.join(REPO, ".jax_cache")
    assert seen["default"] == fixed
    for step in ("import", "runner_train", "registry_configure"):
        assert seen[step] == fixed, (step, seen[step])
    assert seen["pool_child_env"] == {"JAX_COMPILATION_CACHE_DIR": None,
                                      "TRANSMOGRIFAI_COMPILE_CACHE": None}
    assert seen["entries_under_ckpt"] == []
    # git ignores it
    with open(os.path.join(REPO, ".gitignore")) as fh:
        assert ".jax_cache/" in fh.read().split()


def test_chip_smoke_refuses_to_run_off_the_accelerator(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       env=env, cwd=str(tmp_path), capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0
    assert "nothing was run" in p.stderr
    # no result line, no phase started
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    assert "phase" not in p.stdout


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    """The driver also runs the script with nothing else of the repo next
    to it: it must fail there, whatever the platform."""
    import shutil
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), str(tmp_path))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run([sys.executable, "chip_smoke.py", "--cpu-reference"],
                       env=env, cwd=str(tmp_path), capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


def test_chip_smoke_result_line_has_exactly_the_contract_keys(
        monkeypatch, capsys, tmp_path):
    """The driver refuses any last line but ``{"ok", "device": {"platform",
    "kind", "count"}}``: the walls and ``"claim": null`` go on the summary
    line before it, and a failed phase ends with ``"ok": false``."""
    import json
    monkeypatch.syspath_prepend(REPO)
    import chip_smoke
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    for ok in (True, False):
        assert json.loads(chip_smoke.result_line(ok, device)) == {
            "ok": ok, "device": device}

    # main() with the device faked and the phases stubbed out
    def stub(name):
        def run(*args):
            report = next(a for a in args if isinstance(a, dict))
            report[name] = {"wall_s": 0.0, "compile": {}}
            return None, None, None
        return run
    # other tests of this process have left their drills in the default log
    from transmogrifai_tpu import resilience
    monkeypatch.setattr(resilience, "DEFAULT_LOG", resilience.FailureLog())
    monkeypatch.setattr(chip_smoke, "jax_device", lambda: dict(device))
    monkeypatch.setattr(chip_smoke, "__file__",
                        str(tmp_path / "chip_smoke.py"))
    for name in "abc":
        monkeypatch.setattr(chip_smoke, f"phase_{name}", stub(name.upper()))
    assert chip_smoke.main([]) == 0
    lines = capsys.readouterr().out.splitlines()
    assert json.loads(lines[-1]) == {"ok": True, "device": device}
    assert lines[-2].startswith("summary ")
    assert json.loads(lines[-2][len("summary "):])["claim"] is None
    assert (tmp_path / "chiprun_out" / "chip_smoke_report.json").exists()

    def failing(*args):
        raise chip_smoke.SmokeFailure("phase A: injected")
    monkeypatch.setattr(chip_smoke, "phase_a", failing)
    assert chip_smoke.main([]) == 1
    out = capsys.readouterr().out.splitlines()
    assert json.loads(out[-1]) == {"ok": False, "device": device}


class TestPoolDevices:
    def _pool(self, tmp_path, workers):
        return ServingPool(str(tmp_path / "model"), workers=workers,
                           run_dir=str(tmp_path / "pool"))

    def _probe(self, monkeypatch, **verdict):
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        monkeypatch.setattr(
            sup, "probe_devices",
            lambda **kw: sup.ProbeVerdict(**verdict))

    def test_worker_k_gets_chip_k_with_the_platform_pinned(
            self, tmp_path, monkeypatch):
        self._probe(monkeypatch, status=sup.AVAILABLE, platform="tpu",
                    device_kind="TPU v5 lite", device_count=4)
        pool = self._pool(tmp_path, 3)
        pool._device_env = pool._resolve_device_env()
        envs = [pool._worker_env(s) for s in pool.slots]
        assert [e["TPU_VISIBLE_DEVICES"] for e in envs] == ["0", "1", "2"]
        assert {e["JAX_PLATFORMS"] for e in envs} == {"tpu"}

    def test_more_workers_than_chips_is_refused(self, tmp_path, monkeypatch):
        self._probe(monkeypatch, status=sup.AVAILABLE, platform="tpu",
                    device_kind="TPU v5 lite", device_count=1)
        with pytest.raises(ValueError, match="one worker per chip"):
            self._pool(tmp_path, 2).start()

    def test_outage_probe_fails_the_pool(self, tmp_path, monkeypatch):
        self._probe(monkeypatch, status=sup.OUTAGE, cause="hang")
        with pytest.raises(RuntimeError, match="outage"):
            self._pool(tmp_path, 1).start()

    def test_cpu_pin_in_the_environment_needs_no_probe(self, tmp_path,
                                                       monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        monkeypatch.setattr(sup, "probe_devices", lambda **kw: 1 / 0)
        pool = self._pool(tmp_path, 2)
        assert pool._resolve_device_env() == [{}, {}]

    def test_cpu_fallback_on_an_accelerator_host_fails_the_pool(
            self, tmp_path, monkeypatch):
        """Unpinned, a probe child that cannot have the chip (this process
        or another holds it) continues on the CPU; on a host that has an
        accelerator the pool must refuse, not pin its workers to the CPU."""
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        monkeypatch.setattr(sup, "accelerator_expected", lambda: True)
        asked = {}

        def probe(**kw):
            asked.update(kw)
            return sup.ProbeVerdict(
                status=sup.DEGRADED if kw["expect_accelerator"]
                else sup.AVAILABLE, platform="cpu", device_count=1,
                cause="accelerator expected but probe resolved cpu")
        monkeypatch.setattr(sup, "probe_devices", probe)
        with pytest.raises(RuntimeError, match="degraded"):
            self._pool(tmp_path, 1).start()
        assert asked["expect_accelerator"] is True

    def test_a_worker_on_another_platform_fails_the_pool(self, tmp_path,
                                                         monkeypatch):
        """The worker writes where it computes into its ready file; the pool
        compares that with what it resolved."""
        import threading
        import time
        self._probe(monkeypatch, status=sup.AVAILABLE, platform="tpu",
                    device_kind="TPU v5 lite", device_count=1)
        pool = self._pool(tmp_path, 1)
        pool._device_env = pool._resolve_device_env()
        slot = pool.slots[0]
        ready = os.path.join(pool.run_dir, "worker-0.ready.json")
        with open(ready, "w") as fh:
            json.dump({"workerId": "0", "pid": 1, "port": 1, "adminPort": 2,
                       "device": {"platform": "cpu", "kind": "cpu",
                                  "count": 1}}, fh)
        with pytest.raises(RuntimeError, match="serves from 'cpu'"):
            pool._wait_ready(slot, time.monotonic() + 5)


class TestAcceleratorExpected:
    def test_a_pinned_platform_decides(self, monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", "tpu")
        assert sup.accelerator_expected() is True
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        assert sup.accelerator_expected() is False

    def test_unpinned_it_is_the_device_nodes(self, monkeypatch):
        import glob
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        monkeypatch.setattr(glob, "glob", lambda pat: [])
        assert sup.accelerator_expected() is False
        monkeypatch.setattr(
            glob, "glob",
            lambda pat: ["/dev/vfio/0"] if pat.startswith("/dev/vfio") else [])
        assert sup.accelerator_expected() is True

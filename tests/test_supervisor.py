"""Device-runtime supervisor (ISSUE 11): hang-proof probes, heartbeat
state machine, watchdog-abandonment accounting, and degrade-to-surviving-
mesh sweep recovery.

The fast tests drive the heartbeat/state machine with injected probes and a
fake clock (zero subprocesses, zero sleeps); the probe tests use real child
processes with chaos preludes (die / hang); the SIGTERM-ignoring reclaim
proof is slow-marked; the mesh-degrade test runs a real two-family sweep on
the conftest 8-virtual-device mesh and asserts the surviving-mesh resume
reaches the same winner as an uninterrupted run, replaying the checkpointed
family instead of refitting it.
"""

import json
import os
import sys
import time

import numpy as np
import pytest

import jax

from transmogrifai_tpu.parallel import supervisor as sup
from transmogrifai_tpu.resilience import (FailureLog, FaultInjector,
                                          WatchdogTimeout, inject_faults,
                                          run_with_deadline,
                                          use_failure_log)
from transmogrifai_tpu.telemetry import REGISTRY, Tracer, use_tracer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

needs_mesh = pytest.mark.skipif(len(jax.devices()) < 8,
                                reason="needs 8 virtual devices")


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _verdict(status, cause=""):
    return sup.ProbeVerdict(status=status, platform="cpu", device_count=1,
                            cause=cause)


# --------------------------------------------------------------------------
# supervised child runs
# --------------------------------------------------------------------------

class TestRunSupervised:
    def test_normal_child(self):
        r = sup.run_supervised([sys.executable, "-c", "print('ok-42')"],
                               timeout_s=60)
        assert r.rc == 0 and "ok-42" in r.stdout
        assert not r.timed_out and not r.escalated

    def test_hung_child_killed_within_budget(self):
        t0 = time.monotonic()
        r = sup.run_supervised(
            [sys.executable, "-c", "import time; time.sleep(600)"],
            timeout_s=1.0, grace_s=2.0)
        wall = time.monotonic() - t0
        assert r.rc == 124 and r.timed_out
        # SIGTERM sufficed — no escalation needed for a plain sleep
        assert not r.escalated
        assert wall < 30, wall

    @pytest.mark.slow
    def test_sigterm_ignoring_child_reclaimed_by_sigkill(self):
        """The native-hang failure mode: plain SIGTERM does not kill the hung
        process — only the SIGKILL escalation reclaims it, within the
        timeout+grace watchdog budget."""
        code = sup.CHAOS_PRELUDES["hang_ignore_sigterm"]
        t0 = time.monotonic()
        r = sup.run_supervised([sys.executable, "-c", code],
                               timeout_s=3.0, grace_s=3.0)
        wall = time.monotonic() - t0
        assert r.rc == 124 and r.timed_out
        assert r.escalated, "SIGTERM should have been ignored"
        assert wall < 60, wall
        # the child is actually gone (kill(pid, 0) raises once reaped)
        with pytest.raises(OSError):
            os.kill(r.pid, 0)


# --------------------------------------------------------------------------
# probes
# --------------------------------------------------------------------------

class TestProbe:
    def test_available_on_cpu(self):
        v = sup.probe_devices(timeout_s=120, platform="cpu", key="t-avail")
        assert v.status == sup.AVAILABLE and v.ok
        assert v.platform == "cpu"
        assert v.device_count >= 1 and v.devices
        assert v.latency_s > 0
        assert v.attempts and v.attempts[0]["result"] == "cpu"

    def test_dead_child_is_outage(self):
        v = sup.probe_devices(timeout_s=60, chaos="die", key="t-die")
        assert v.status == sup.OUTAGE and not v.ok
        assert "rc=17" in v.cause
        assert v.attempts[0]["result"] == "error"

    def test_hung_child_is_outage_within_budget(self):
        t0 = time.monotonic()
        v = sup.probe_devices(timeout_s=1.0, grace_s=2.0, chaos="hang",
                              key="t-hang")
        assert v.status == sup.OUTAGE
        assert v.cause == "hang"
        assert v.attempts[0]["result"] == "hang"
        assert time.monotonic() - t0 < 30

    def test_expect_accelerator_cpu_is_degraded(self):
        v = sup.probe_devices(timeout_s=120, platform="cpu",
                              expect_accelerator=True, key="t-deg")
        assert v.status == sup.DEGRADED
        assert v.platform == "cpu"

    def test_injected_probe_fault_is_outage(self):
        with inject_faults(FaultInjector(
                fail_keys={"supervisor.probe": ["boom"]})):
            v = sup.probe_devices(timeout_s=60, key="boom")
        assert v.status == sup.OUTAGE
        assert "injected fault" in v.cause

    def test_backoff_retries_then_succeeds(self):
        """First probe killed by the injector, second succeeds — the
        verdict accumulates both attempts and the sleep schedule was the
        deterministic one."""
        slept = []
        with inject_faults(FaultInjector(
                fail_keys={"supervisor.probe": ["p:0"]})):
            v = sup.probe_with_backoff(timeout_s=120, backoffs=[0, 7],
                                       sleep=slept.append, key="p",
                                       platform="cpu")
        assert v.status == sup.AVAILABLE
        assert len(v.attempts) == 2
        assert v.attempts[0]["result"] == "injected"
        assert slept == [7]

    def test_all_attempts_fail_is_outage(self):
        with inject_faults(FaultInjector(
                fail_keys={"supervisor.probe": ["q:0", "q:1", "q:2"]})):
            v = sup.probe_with_backoff(timeout_s=60, backoffs=[0, 0, 0],
                                       sleep=lambda s: None, key="q")
        assert v.status == sup.OUTAGE
        assert len(v.attempts) == 3


# --------------------------------------------------------------------------
# outage records
# --------------------------------------------------------------------------

class TestOutageRecord:
    def test_schema_is_the_stable_key_set(self, tmp_path):
        attempts = [{"wall_s": 150.0, "result": "hang", "from": "13:04",
                     "to": "13:06", "every_s": 45}]
        path = str(tmp_path / "OUTAGE_test.json")
        sup.write_outage_record(path, what="w", context="c",
                                timeline=sup.outage_timeline(attempts),
                                mitigations=["m1"], will_update="u")
        rec = json.loads(open(path).read())
        assert set(rec) == set(sup.OUTAGE_RECORD_KEYS)
        tl = rec["timeline_utc"][0]
        assert set(tl) == {"from", "to", "every_s", "result"}
        assert tl["result"] == "hang"

    def test_maybe_write_uses_env_dir(self, tmp_path, monkeypatch):
        monkeypatch.setenv("TRANSMOGRIFAI_OUTAGE_DIR", str(tmp_path))
        p = sup.maybe_write_outage_record(what="w", context="c")
        assert p and os.path.dirname(p) == str(tmp_path)
        assert json.loads(open(p).read())["what"] == "w"

    def test_maybe_write_noop_without_destination(self, monkeypatch):
        monkeypatch.delenv("TRANSMOGRIFAI_OUTAGE_DIR", raising=False)
        monkeypatch.delenv("BENCH_OUTAGE_RECORD", raising=False)
        assert sup.maybe_write_outage_record(what="w") is None


# --------------------------------------------------------------------------
# heartbeat state machine (fake clock + injected probes, zero subprocesses)
# --------------------------------------------------------------------------

class TestHeartbeat:
    def _hb(self, verdicts, clk=None, **kw):
        seq = iter(verdicts)
        kw.setdefault("interval_s", 10.0)
        kw.setdefault("max_interval_s", 80.0)
        kw.setdefault("failure_threshold", 2)
        kw.setdefault("reset_timeout_s", 30.0)
        return sup.Heartbeat(probe=lambda: next(seq),
                             clock=clk or FakeClock(), **kw)

    def test_trip_and_recover_transitions(self):
        clk = FakeClock()
        hb = self._hb([_verdict(sup.AVAILABLE),
                       _verdict(sup.OUTAGE, "hang"),
                       _verdict(sup.OUTAGE, "hang"),
                       _verdict(sup.AVAILABLE)], clk=clk)
        log = FailureLog()
        with use_failure_log(log):
            hb.tick()
            assert hb.state == sup.AVAILABLE and hb.state_code() == 0
            hb.tick()   # first failure: breaker still closed → DEGRADED
            assert hb.state == sup.DEGRADED and hb.state_code() == 1
            hb.tick()   # second consecutive failure trips the breaker
            assert hb.state == sup.OUTAGE and hb.state_code() == 2
            assert hb.breaker.current_state() != hb.breaker.CLOSED
            clk.advance(31.0)   # past reset_timeout_s: probe is granted
            hb.tick()
            assert hb.state == sup.AVAILABLE
            assert hb.breaker.current_state() == hb.breaker.CLOSED
        actions = [e.action for e in log]
        assert "degraded" in actions
        assert "outage" in actions
        assert "recovered" in actions
        # the state gauge reads through to the live state
        assert REGISTRY.gauge("supervisor.state").value == 0

    def test_outage_writes_standard_record(self, tmp_path):
        hb = self._hb([_verdict(sup.OUTAGE, "hang")] * 2,
                      outage_dir=str(tmp_path))
        with use_failure_log(FailureLog()):
            hb.tick()
            hb.tick()
        assert hb.state == sup.OUTAGE
        recs = [f for f in os.listdir(tmp_path) if f.startswith("OUTAGE_")]
        assert len(recs) == 1
        rec = json.loads(open(tmp_path / recs[0]).read())
        assert set(rec) == set(sup.OUTAGE_RECORD_KEYS)

    def test_backoff_doubles_and_resets(self):
        hb = self._hb([_verdict(sup.OUTAGE)] * 4 + [_verdict(sup.AVAILABLE)])
        with use_failure_log(FailureLog()):
            assert hb.next_interval_s() == 10.0
            hb.tick()
            assert hb.next_interval_s() == 20.0
            hb.tick()
            assert hb.next_interval_s() == 40.0
            hb.tick()
            assert hb.next_interval_s() == 80.0
            hb.tick()
            assert hb.next_interval_s() == 80.0   # capped at max_interval_s
            hb.tick()                             # success
            assert hb.next_interval_s() == 10.0   # schedule resets
        assert hb.state == sup.AVAILABLE

    def test_probe_exception_counts_as_outage(self):
        def broken():
            raise RuntimeError("probe machinery broke")
        hb = sup.Heartbeat(probe=broken, failure_threshold=1,
                           clock=FakeClock())
        with use_failure_log(FailureLog()):
            v = hb.tick()
        assert v.status == sup.OUTAGE
        assert "probe machinery broke" in v.cause
        assert hb.state == sup.OUTAGE   # threshold 1 trips immediately

    def test_injected_heartbeat_fault(self):
        hb = self._hb([_verdict(sup.AVAILABLE)] * 3, failure_threshold=5)
        with use_failure_log(FailureLog()), inject_faults(FaultInjector(
                fail_keys={"supervisor.heartbeat": ["1"]})):
            assert hb.tick().status == sup.AVAILABLE   # tick 0
            assert hb.tick().status == sup.OUTAGE      # tick 1: injected
            assert hb.tick().status == sup.AVAILABLE   # tick 2
        assert hb.state == sup.AVAILABLE

    def test_background_thread_start_stop(self):
        hb = self._hb([_verdict(sup.AVAILABLE)] * 1000, interval_s=0.01,
                      max_interval_s=0.01)
        hb.start()
        deadline = time.time() + 5.0
        while hb.last_verdict is None and time.time() < deadline:
            time.sleep(0.01)
        hb.stop()
        assert hb.last_verdict is not None
        assert hb.state == sup.AVAILABLE


# --------------------------------------------------------------------------
# watchdog abandonment accounting (satellite c)
# --------------------------------------------------------------------------

class TestWatchdogAccounting:
    def test_abandonment_counts_and_records(self):
        c0 = REGISTRY.counter("watchdog.abandoned_total").value
        log = FailureLog()
        with use_failure_log(log):
            with pytest.raises(WatchdogTimeout):
                run_with_deadline(time.sleep, 0.05, 1.5, description="nap")
        assert REGISTRY.counter("watchdog.abandoned_total").value == c0 + 1
        notes = [e for e in log if e.action == "degraded"
                 and e.point == "watchdog.abandoned"]
        assert notes and "nap" in notes[0].cause

    def test_fast_call_leaves_no_trace(self):
        c0 = REGISTRY.counter("watchdog.abandoned_total").value
        assert run_with_deadline(lambda: 7, 5.0) == 7
        assert REGISTRY.counter("watchdog.abandoned_total").value == c0


# --------------------------------------------------------------------------
# multihost telemetry (satellite b)
# --------------------------------------------------------------------------

class TestMultihostTelemetry:
    def test_init_span_and_gauges_on_degrade(self, monkeypatch):
        from transmogrifai_tpu.parallel.multihost import init_distributed
        # a world-size-bearing var > 1: a bare job id no longer counts as
        # cluster evidence (PR 14 auto-detect change)
        monkeypatch.setenv("SLURM_NTASKS", "2")
        tracer = Tracer(run_name="t")
        log = FailureLog()
        with use_tracer(tracer), use_failure_log(log), inject_faults(
                FaultInjector(rates={"multihost.init": 1.0})):
            assert init_distributed() is False
        assert any(s.name == "multihost.init" for s in tracer.spans)
        assert REGISTRY.gauge("multihost.initialized").value == 0
        assert REGISTRY.gauge("multihost.process_count").value == 1
        assert any(e.action == "degraded" and e.point == "multihost.init"
                   for e in log)


# --------------------------------------------------------------------------
# device-loss classification + surviving-device cap
# --------------------------------------------------------------------------

class TestDeviceLoss:
    def test_typed_errors_classify(self):
        assert sup.is_device_loss(sup.DeviceLostError("gone"))
        assert sup.is_device_loss(sup.TransferStallError("stuck"))
        assert sup.is_device_loss(RuntimeError("UNAVAILABLE: socket closed"))
        assert sup.is_device_loss(RuntimeError("DEVICE_LOST during launch"))

    def test_ordinary_failures_do_not(self):
        # OOM / compile errors must keep their per-candidate degrade path
        assert not sup.is_device_loss(RuntimeError("RESOURCE_EXHAUSTED"))
        assert not sup.is_device_loss(ValueError("bad hyper-parameter"))
        assert not sup.is_device_loss(RuntimeError("jaxlib error"))

    def test_cap_shrinks_and_resets(self):
        sup.reset_surviving_devices()
        try:
            n = len(jax.devices())
            assert sup.device_cap() is None
            assert sup.effective_device_count(n) == n
            cap = sup.mark_device_loss()
            assert cap == n - 1
            assert sup.effective_device_count(n) == n - 1
            assert REGISTRY.gauge("supervisor.device_cap").value == n - 1
        finally:
            sup.reset_surviving_devices()
        assert sup.effective_device_count(8) == 8

    @needs_mesh
    def test_surviving_cap_shrinks_data_mesh(self, monkeypatch):
        from transmogrifai_tpu.parallel import maybe_data_mesh
        monkeypatch.setenv("TRANSMOGRIFAI_TPU_MESH", "1")
        sup.reset_surviving_devices()
        try:
            m8 = maybe_data_mesh(80, pad=True)
            assert m8 is not None and m8.devices.size == 8
            sup.mark_device_loss()
            m7 = maybe_data_mesh(80, pad=True)
            assert m7 is not None and m7.devices.size == 7
        finally:
            sup.reset_surviving_devices()

    @needs_mesh
    def test_surviving_cap_collapses_model_axis(self, monkeypatch):
        """8 devices at model width 2 → 7 survivors: the width no longer
        divides, so the recovery mesh collapses to data-only instead of
        refusing to build."""
        from transmogrifai_tpu.parallel import maybe_data_mesh
        monkeypatch.setenv("TRANSMOGRIFAI_TPU_MESH", "1")
        monkeypatch.setenv("TRANSMOGRIFAI_TPU_MESH_MODEL", "2")
        sup.reset_surviving_devices()
        try:
            m8 = maybe_data_mesh(80, pad=True)
            assert dict(m8.shape)["model"] == 2
            sup.mark_device_loss()
            m7 = maybe_data_mesh(70, pad=True)
            assert m7.devices.size == 7
            assert dict(m7.shape)["model"] == 1
        finally:
            sup.reset_surviving_devices()


# --------------------------------------------------------------------------
# chunk-stall deadline in streaming
# --------------------------------------------------------------------------

@needs_mesh
class TestChunkStall:
    def test_injected_stall_is_typed_error(self):
        from transmogrifai_tpu.parallel import make_mesh, stream_to_device
        mesh = make_mesh(8)
        X = np.ones((64, 4), np.float32)
        with inject_faults(FaultInjector(
                rates={"supervisor.chunk_stall": 1.0})):
            with pytest.raises(sup.TransferStallError):
                stream_to_device(X, mesh)
        # a stall classifies as device loss → sweep-level recovery applies
        assert sup.is_device_loss(sup.TransferStallError("x"))

    def test_clean_stream_unaffected(self, monkeypatch):
        from transmogrifai_tpu.parallel import make_mesh, stream_to_device
        monkeypatch.setenv("TRANSMOGRIFAI_CHUNK_DEADLINE_S", "30")
        mesh = make_mesh(8)
        X = np.arange(64 * 4, dtype=np.float32).reshape(64, 4)
        Xs = stream_to_device(X, mesh)
        np.testing.assert_array_equal(np.asarray(Xs), X)


# --------------------------------------------------------------------------
# degrade-to-surviving-mesh sweep recovery (the tentpole proof)
# --------------------------------------------------------------------------

def _two_family_sweep(n, resume_from=None):
    """LR-only two-family sweep (distinct names → distinct checkpoint
    signatures); returns (winner_name, winner_params, failure_log)."""
    from transmogrifai_tpu.columns import Column, ColumnBatch
    from transmogrifai_tpu.features import FeatureBuilder
    from transmogrifai_tpu.models.linear import OpLogisticRegression
    from transmogrifai_tpu.ops.transmogrify import transmogrify
    from transmogrifai_tpu.selector import (
        BinaryClassificationModelSelector, ModelCandidate, grid)
    from transmogrifai_tpu.types import RealNN
    from transmogrifai_tpu.workflow import Workflow

    d = 6
    rng = np.random.default_rng(0)
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2] > 0).astype(np.float32)
    label = FeatureBuilder.RealNN("label").as_response()
    feats = [FeatureBuilder.RealNN(f"f{i}").as_predictor() for i in range(d)]
    checked = label.sanity_check(transmogrify(feats),
                                 remove_bad_features=True)
    # widely-separated regularisation so reduction-order float noise on a
    # shrunken mesh cannot flip the winner
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


@needs_mesh
class TestSweepRecovery:
    N = 560   # divisible by 8 AND 7: the mesh forms before and after loss

    def test_device_loss_resumes_on_surviving_mesh_same_winner(
            self, monkeypatch, tmp_path):
        monkeypatch.setenv("TRANSMOGRIFAI_TPU_MESH", "1")
        sup.reset_surviving_devices()
        try:
            w0, p0, _ = _two_family_sweep(self.N)

            sup.reset_surviving_devices()
            degrades0 = REGISTRY.counter(
                "supervisor.mesh_degrades_total").value
            # a device dies while LR_B scores — AFTER LR_A checkpointed, so
            # the recovery sweep must replay LR_A and refit only LR_B on
            # the 7-device surviving mesh
            with inject_faults(FaultInjector(
                    fail_keys={"supervisor.device_loss":
                               ["LR_B:score:a0"]})) as inj:
                w1, p1, log = _two_family_sweep(
                    self.N, resume_from=str(tmp_path / "sweep"))
            assert ("supervisor.device_loss", "LR_B:score:a0") in inj.fired
            assert sup.device_cap() == 7   # the mesh actually shrank
            assert REGISTRY.counter(
                "supervisor.mesh_degrades_total").value == degrades0 + 1

            assert w1 == w0
            assert p1 == p0
            actions = [(e.action, e.point) for e in log]
            # the loss was recorded as a degrade with the supervisor point
            assert ("degraded", "supervisor.device_loss") in actions
            # LR_A came back from the checkpoint, not a refit
            assert any(e.action == "resumed" for e in log)
        finally:
            sup.reset_surviving_devices()

    def test_no_supervisor_propagates_device_loss(self, monkeypatch,
                                                  tmp_path):
        monkeypatch.setenv("TRANSMOGRIFAI_TPU_MESH", "1")
        monkeypatch.setenv("TRANSMOGRIFAI_SUPERVISOR", "0")
        sup.reset_surviving_devices()
        try:
            assert sup.max_sweep_recoveries() == 0
            from transmogrifai_tpu.resilience import InjectedFault
            with inject_faults(FaultInjector(
                    fail_keys={"supervisor.device_loss":
                               ["LR_B:score:a0"]})):
                with pytest.raises(InjectedFault):
                    _two_family_sweep(self.N,
                                      resume_from=str(tmp_path / "sweep"))
            assert sup.device_cap() is None   # no silent mesh shrink
        finally:
            sup.reset_surviving_devices()


# --------------------------------------------------------------------------
# params / CLI wiring
# --------------------------------------------------------------------------

class TestParamsWiring:
    def test_supervisor_params_roundtrip(self):
        from transmogrifai_tpu.params import OpParams
        p = OpParams.from_json({"supervisorParams": {"enabled": False,
                                                     "probeTimeoutS": 60}})
        assert p.supervisor == {"enabled": False, "probeTimeoutS": 60}
        assert p.to_json()["supervisorParams"]["probeTimeoutS"] == 60

    def test_env_knob_defaults(self, monkeypatch):
        for v in ("TRANSMOGRIFAI_SUPERVISOR", "TRANSMOGRIFAI_PROBE_TIMEOUT_S",
                  "TRANSMOGRIFAI_PROBE_BACKOFFS", "BENCH_PROBE_TIMEOUT_S",
                  "BENCH_PROBE_BACKOFFS", "TRANSMOGRIFAI_SWEEP_RECOVERIES",
                  "TRANSMOGRIFAI_CHUNK_DEADLINE_S"):
            monkeypatch.delenv(v, raising=False)
        assert sup.supervisor_enabled()
        assert sup.probe_timeout_s() == 150.0
        assert sup.probe_backoffs() == [0.0, 45.0, 120.0]
        assert sup.max_sweep_recoveries() == 1
        assert sup.chunk_deadline_s() is None
        # legacy BENCH_* knobs still honored (bench dedupe contract)
        monkeypatch.setenv("BENCH_PROBE_TIMEOUT_S", "33")
        monkeypatch.setenv("BENCH_PROBE_BACKOFFS", "0,5")
        assert sup.probe_timeout_s() == 33.0
        assert sup.probe_backoffs() == [0.0, 5.0]

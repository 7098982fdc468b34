"""Cross-host resilient runtime (ISSUE 14): supervised multi-process
launcher, host heartbeats + liveness state machine, deadline-guarded
barriers, per-host shard streaming, and lost-host relaunch.

The fast tests drive the barrier and the liveness machine with a fake
clock (zero subprocesses, zero sleeps); the launcher tests use real child
processes that only import the jax-free ``hostgroup`` module, so they run
in ~a second; the shard-streaming tests prove the per-process slice path
is bitwise-equal to the single-shot path on the conftest virtual mesh.
"""

import json
import os
import signal
import sys
import textwrap
import time

import numpy as np
import pytest

import jax

from transmogrifai_tpu.parallel import hostgroup as hg
from transmogrifai_tpu.parallel import (make_mesh, process_row_range,
                                        stream_to_device)
from transmogrifai_tpu.parallel import supervisor as sup
from transmogrifai_tpu.resilience import FailureLog, use_failure_log
from transmogrifai_tpu.telemetry import REGISTRY

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

needs_mesh = pytest.mark.skipif(len(jax.devices()) < 8,
                                reason="needs 8 virtual devices")


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.t += dt


# --------------------------------------------------------------------------
# deadline-guarded barrier (fake clock)
# --------------------------------------------------------------------------

class TestBarrierSync:
    def test_all_ranks_arrive(self, tmp_path):
        d = str(tmp_path)
        clk = FakeClock()
        # rank 1 already arrived (its marker is on disk); rank 0's wait
        # completes without burning any clock
        hg._atomic_write_json(hg._barrier_file(d, "b", 0, 1),
                              {"rank": 1, "pid": 0, "wallS": 0.0})
        waited = hg.barrier_sync("b", 10.0, rank=0, world=2, run_dir=d,
                                 generation=0, clock=clk, sleep=clk.sleep)
        assert waited == 0.0

    def test_missing_rank_times_out_typed_within_deadline(self, tmp_path):
        d = str(tmp_path)
        clk = FakeClock()
        log = FailureLog()
        before = REGISTRY.counter("hostgroup.barrier_timeouts_total").value
        with use_failure_log(log):
            with pytest.raises(hg.HostLostError) as ei:
                hg.barrier_sync("work", 2.0, rank=0, world=2, run_dir=d,
                                generation=0, clock=clk, sleep=clk.sleep)
        assert ei.value.missing == [1]
        assert ei.value.barrier == "work"
        assert clk.t <= 2.0 + 0.06     # one poll past the deadline, max
        assert log.summary() == {"host_lost": 1}
        assert log.by_action("host_lost")[0].point == "hostgroup.barrier"
        after = REGISTRY.counter("hostgroup.barrier_timeouts_total").value
        assert after == before + 1

    def test_posted_abort_trips_immediately(self, tmp_path):
        d = str(tmp_path)
        clk = FakeClock()
        hg.write_abort(d, 0, [1], "rank 1 lost (exit)")
        with pytest.raises(hg.HostLostError) as ei:
            hg.barrier_sync("work", 1000.0, rank=0, world=2, run_dir=d,
                            generation=0, clock=clk, sleep=clk.sleep)
        assert ei.value.missing == [1]
        assert clk.t == 0.0            # no deadline burned

    def test_generations_do_not_cross_talk(self, tmp_path):
        d = str(tmp_path)
        clk = FakeClock()
        # gen-0 arrivals and a gen-0 abort must be invisible to gen 1
        hg.barrier_sync("b", 5.0, rank=0, world=1, run_dir=d, generation=0,
                        clock=clk, sleep=clk.sleep)
        hg.write_abort(d, 0, [0], "stale")
        waited = hg.barrier_sync("b", 5.0, rank=0, world=1, run_dir=d,
                                 generation=1, clock=clk, sleep=clk.sleep)
        assert waited == 0.0

    def test_outside_group_without_run_dir_raises(self, monkeypatch):
        monkeypatch.delenv(hg.ENV_RUN_DIR, raising=False)
        with pytest.raises(ValueError, match="run_dir"):
            hg.barrier_sync("b", 1.0, rank=0, world=1)


# --------------------------------------------------------------------------
# host liveness state machine (fake clock)
# --------------------------------------------------------------------------

class TestHostLiveness:
    def test_loss_and_recovery_transitions(self, tmp_path):
        d = str(tmp_path)
        clk = FakeClock(1000.0)
        outage = str(tmp_path / "OUTAGE_test.json")
        log = FailureLog()
        lv = hg.HostLiveness(d, 2, timeout_s=5.0, clock=clk,
                             outage_path=outage, context="unit test group")
        for r in (0, 1):
            hg.write_host_heartbeat(d, r, seq=0, wall=clk.t)
        with use_failure_log(log):
            assert lv.tick()["state"] == "available"
            # rank 1 goes silent past the budget; rank 0 keeps beating
            clk.t += 6.0
            hg.write_host_heartbeat(d, 0, seq=1, wall=clk.t)
            out = lv.tick()
            assert out["state"] == "degraded"
            assert out["lost"] == [1]
            assert REGISTRY.gauge("hostgroup.alive").value == 1
            # heartbeat resumes → recovery recorded, state available
            hg.write_host_heartbeat(d, 1, seq=1, wall=clk.t)
            hg.write_host_heartbeat(d, 0, seq=2, wall=clk.t)
            assert lv.tick()["state"] == "available"
        assert log.summary() == {"host_lost": 1, "host_recovered": 1}
        assert lv.losses and lv.losses[0]["rank"] == 1

    def test_outage_record_matches_r5_schema(self, tmp_path):
        d = str(tmp_path)
        clk = FakeClock()
        outage = str(tmp_path / "OUTAGE_test.json")
        lv = hg.HostLiveness(d, 1, timeout_s=1.0, clock=clk,
                             outage_path=outage)
        hg.write_host_heartbeat(d, 0, seq=0, wall=0.0)
        lv.tick()
        clk.t = 5.0
        with use_failure_log(FailureLog()):
            assert lv.tick()["state"] == "outage"
        with open(outage) as fh:
            rec = json.load(fh)
        assert set(rec) == set(sup.OUTAGE_RECORD_KEYS)
        assert "no heartbeat" in rec["what"]

    def test_boot_window_counts_alive(self, tmp_path):
        # a rank that has never beaten is alive while inside the budget
        clk = FakeClock()
        lv = hg.HostLiveness(str(tmp_path), 2, timeout_s=10.0, clock=clk)
        clk.t = 3.0
        out = lv.tick()
        assert out["state"] == "available"
        assert out["alive"] == [0, 1]

    def test_stale_generation_heartbeats_ignored(self, tmp_path):
        d = str(tmp_path)
        clk = FakeClock()
        lv = hg.HostLiveness(d, 1, timeout_s=2.0, generation=1, clock=clk)
        # a gen-0 heartbeat (pre-relaunch leftover) must not feed gen 1
        hg.write_host_heartbeat(d, 0, seq=9, generation=0, wall=0.0)
        clk.t = 5.0
        with use_failure_log(FailureLog()):
            assert lv.tick()["lost"] == [0]


# --------------------------------------------------------------------------
# multihost auto-detect + gauge truth (satellites 1 + 2)
# --------------------------------------------------------------------------

class TestMultihostDetect:
    @pytest.fixture(autouse=True)
    def _clean_env(self, monkeypatch):
        from transmogrifai_tpu.parallel import multihost
        for v in multihost._CLUSTER_ENV_VARS:
            monkeypatch.delenv(v, raising=False)
        monkeypatch.delenv("SLURM_JOB_ID", raising=False)
        monkeypatch.setattr(jax.distributed, "is_initialized",
                            lambda: False, raising=False)

    def test_job_id_alone_is_not_cluster_evidence(self, monkeypatch):
        # regression: a single-node `srun python train.py` carries
        # SLURM_JOB_ID; auto-detect must not probe for a coordinator on it
        from transmogrifai_tpu.parallel import multihost
        monkeypatch.setenv("SLURM_JOB_ID", "1234")
        assert multihost._cluster_env_present() is False
        called = []
        monkeypatch.setattr(jax.distributed, "initialize",
                            lambda **kw: called.append(kw))
        assert multihost.init_distributed() is False
        assert called == []

    @pytest.mark.parametrize("var", ["SLURM_NTASKS", "SLURM_NPROCS",
                                     "OMPI_COMM_WORLD_SIZE", "PMI_SIZE"])
    def test_world_size_above_one_arms_detection(self, monkeypatch, var):
        from transmogrifai_tpu.parallel import multihost
        monkeypatch.setenv(var, "2")
        assert multihost._cluster_env_present() is True

    def test_world_size_of_one_does_not_arm(self, monkeypatch):
        from transmogrifai_tpu.parallel import multihost
        monkeypatch.setenv("SLURM_NTASKS", "1")
        assert multihost._cluster_env_present() is False

    def test_coordinator_address_still_arms(self, monkeypatch):
        from transmogrifai_tpu.parallel import multihost
        monkeypatch.setenv("COORDINATOR_ADDRESS", "10.0.0.1:1234")
        assert multihost._cluster_env_present() is True

    def test_explicit_failure_sets_process_count_gauge(self, monkeypatch):
        # the gauge must read known truth (1) on EVERY exit path, including
        # the explicit-coordinator raise
        from transmogrifai_tpu.parallel import multihost

        def boom(**kw):
            raise RuntimeError("coordinator unreachable")
        monkeypatch.setattr(jax.distributed, "initialize", boom)
        REGISTRY.gauge("multihost.process_count").set(777)
        with pytest.raises(RuntimeError, match="coordinator unreachable"):
            multihost.init_distributed("10.0.0.1:1234", num_processes=2,
                                       process_id=0)
        assert REGISTRY.gauge("multihost.process_count").value == 1


# --------------------------------------------------------------------------
# per-host shard streaming
# --------------------------------------------------------------------------

@needs_mesh
class TestProcessShardStreaming:
    def test_row_offset_slice_bitwise_equal(self):
        mesh = make_mesh(8)
        n = 40
        X = np.arange(n * 3, dtype=np.float32).reshape(n, 3)
        lo, hi = process_row_range(mesh, n)
        assert (lo, hi) == (0, n)   # single process addresses every shard
        full = stream_to_device(X, mesh)
        sliced = stream_to_device(X[lo:hi], mesh, row_offset=lo,
                                  global_rows=n)
        assert jax.numpy.array_equal(full, sliced)

    def test_row_offset_with_padding(self):
        mesh = make_mesh(8)
        n, pad_to = 37, 40
        X = np.arange(n * 2, dtype=np.float32).reshape(n, 2)
        lo, hi = process_row_range(mesh, n, pad_to=pad_to)
        full = stream_to_device(X, mesh, pad_to=pad_to)
        sliced = stream_to_device(X[lo:hi], mesh, row_offset=lo,
                                  global_rows=n, pad_to=pad_to)
        assert jax.numpy.array_equal(full, sliced)

    def test_uncovered_shard_raises_typed(self):
        mesh = make_mesh(8)
        n = 40
        X = np.arange(n * 3, dtype=np.float32).reshape(n, 3)
        # a slice that misses device 0's shard must fail loudly, never
        # silently misalign rows
        with pytest.raises(ValueError, match="process_row_range"):
            stream_to_device(X[8:], mesh, row_offset=8, global_rows=n)

    def test_slice_exceeding_global_rows_raises(self):
        mesh = make_mesh(8)
        X = np.zeros((16, 2), np.float32)
        with pytest.raises(ValueError, match="global row space"):
            stream_to_device(X, mesh, row_offset=8, global_rows=16)


# --------------------------------------------------------------------------
# classification + env plumbing
# --------------------------------------------------------------------------

class TestClassification:
    def test_host_lost_error_is_device_loss(self):
        assert sup.is_device_loss(hg.HostLostError("rank 1 gone"))
        assert sup.is_device_loss(
            RuntimeError("hostgroup.host_lost: rank 2 silent"))

    def test_knob_defaults_and_env_overrides(self, monkeypatch):
        monkeypatch.delenv("TRANSMOGRIFAI_HOSTGROUP_BEAT_S", raising=False)
        assert hg.beat_interval_s() == 1.0
        monkeypatch.setenv("TRANSMOGRIFAI_HOSTGROUP_BEAT_S", "0.25")
        assert hg.beat_interval_s() == 0.25
        monkeypatch.setenv("TRANSMOGRIFAI_HOSTGROUP_LIVENESS_S", "7")
        assert hg.liveness_timeout_s() == 7.0

    def test_env_contract(self, monkeypatch):
        monkeypatch.delenv(hg.ENV_RANK, raising=False)
        assert not hg.hostgroup_env_present()
        monkeypatch.setenv(hg.ENV_RANK, "2")
        monkeypatch.setenv(hg.ENV_WORLD, "4")
        monkeypatch.setenv(hg.ENV_GENERATION, "1")
        monkeypatch.setenv(hg.ENV_RUN_DIR, "/tmp/hg")
        assert hg.hostgroup_env_present()
        assert hg.current_rank() == 2
        assert hg.group_world_size() == 4
        assert hg.group_generation() == 1


# --------------------------------------------------------------------------
# the launcher, with real (jax-free, fast) child processes
# --------------------------------------------------------------------------

_CHILD_OK = textwrap.dedent("""
    import sys
    sys.path.insert(0, {repo!r})
    from transmogrifai_tpu.parallel import hostgroup
    hg = hostgroup.maybe_init_hostgroup(distributed=False)
    hg.barrier("work", timeout_s=30)
    hg.mark_done({{"gen": hg.generation, "world": hg.world}})
    hg.close()
""")

_CHILD_DIE = textwrap.dedent("""
    import os, signal, sys, time
    sys.path.insert(0, {repo!r})
    from transmogrifai_tpu.parallel import hostgroup
    hg = hostgroup.maybe_init_hostgroup(distributed=False)
    if hg.generation == 0 and hg.rank == 1:
        time.sleep(0.3)
        os.kill(os.getpid(), signal.SIGKILL)
    try:
        hg.barrier("work", timeout_s=30)
    except hostgroup.HostLostError:
        hg.close(state="aborted")
        sys.exit(hostgroup.EXIT_HOST_LOST)
    hg.mark_done({{"gen": hg.generation, "world": hg.world}})
    hg.close()
""")


class TestLaunchHosts:
    def test_clean_group_completes(self, tmp_path):
        res = hg.launch_hosts(
            [sys.executable, "-c", _CHILD_OK.format(repo=REPO)], 2,
            run_dir=str(tmp_path), boot_timeout=120, liveness_timeout=10,
            grace_s=5, preflight=False, distributed=False)
        assert res.ok and res.reason == "completed"
        assert res.generations == 1 and res.relaunches == 0
        for r in (0, 1):
            with open(hg.done_path(str(tmp_path), r, 0)) as fh:
                assert json.load(fh)["world"] == 2

    def test_lost_rank_relaunches_at_shrunken_world(self, tmp_path):
        d = str(tmp_path)
        res = hg.launch_hosts(
            [sys.executable, "-c", _CHILD_DIE.format(repo=REPO)], 2,
            run_dir=d, boot_timeout=120, liveness_timeout=8, grace_s=5,
            preflight=False, distributed=False, max_relaunches=1)
        assert res.ok and res.relaunches == 1
        assert res.final_world == 1 and res.generations == 2
        assert [(l["rank"], l["generation"]) for l in res.losses] == [(1, 0)]
        # gen-1 survivor ran at world 1 and completed
        with open(hg.done_path(d, 0, 1)) as fh:
            assert json.load(fh)["world"] == 1
        # the loss adjudication is durable: abort + standard outage record
        assert hg.read_abort(d, 0)["lost"] == [1]
        with open(os.path.join(d, "OUTAGE_hostgroup_gen0.json")) as fh:
            rec = json.load(fh)
        assert set(rec) == set(sup.OUTAGE_RECORD_KEYS)
        # zero orphans: every recorded worker pid is gone
        for sub in ("hb", "done", "ready"):
            sdir = os.path.join(d, sub)
            for f in os.listdir(sdir) if os.path.isdir(sdir) else ():
                with open(os.path.join(sdir, f)) as fh:
                    pid = json.load(fh).get("pid")
                if pid:
                    with pytest.raises(OSError):
                        os.kill(int(pid), 0)

    def test_relaunch_budget_exhausted_reports_failure(self, tmp_path):
        res = hg.launch_hosts(
            [sys.executable, "-c", _CHILD_DIE.format(repo=REPO)], 2,
            run_dir=str(tmp_path), boot_timeout=120, liveness_timeout=8,
            grace_s=5, preflight=False, distributed=False, max_relaunches=0)
        assert not res.ok
        assert res.losses and res.reason != "completed"

    def test_traceparent_propagates_one_trace_id(self, tmp_path):
        child = textwrap.dedent("""
            import json, os, sys
            sys.path.insert(0, {repo!r})
            from transmogrifai_tpu.parallel import hostgroup
            from transmogrifai_tpu.telemetry import TraceContext
            hg = hostgroup.maybe_init_hostgroup(distributed=False)
            ctx = TraceContext.from_env()
            hg.mark_done({{"traceId": ctx.trace_id if ctx else None}})
            hg.close()
        """).format(repo=REPO)
        d = str(tmp_path)
        res = hg.launch_hosts([sys.executable, "-c", child], 2, run_dir=d,
                              boot_timeout=120, liveness_timeout=10,
                              grace_s=5, preflight=False, distributed=False)
        assert res.ok
        ids = set()
        for r in (0, 1):
            with open(hg.done_path(d, r, 0)) as fh:
                ids.add(json.load(fh)["traceId"])
        assert len(ids) == 1 and None not in ids

"""Cross-host resilient runtime: a supervised multi-process host group.

The reference's cross-executor story is Spark's driver/executor runtime —
lost executors are detected by driver heartbeats and their tasks re-run
elsewhere.  This module is that story for the jax_graft port: N ranked
worker *processes* (one per host; in CI, N local processes over the
multi-process CPU backend) under one supervising launcher, with host loss a
recoverable, observable event instead of a silent collective hang.

Four cooperating pieces:

* ``launch_hosts(cmd, n)`` — the launcher.  Spawns ``cmd`` once per rank
  under the ``run_supervised`` conventions (per-rank log/ready files in a
  run dir, ``start_new_session`` process groups, SIGTERM→grace→SIGKILL
  drain, zero orphans), pre-flighted by the subprocess device probe so a
  native init hang becomes a typed verdict before any rank exists.  Ranks find each other through ``TRANSMOGRIFAI_HOSTGROUP_*`` env
  vars (rank, world size, run dir, coordinator address, generation).

* rank-side init — ``maybe_init_hostgroup()`` is the one call worker code
  makes: it starts the host heartbeat, selects the CPU collectives backend
  (gloo) when needed, runs ``multihost.init_distributed`` against the
  group coordinator, and synchronizes on the ``init`` barrier before
  reporting ready.

* cross-host liveness — every rank heartbeats a per-rank file;
  :class:`HostLiveness` extends the supervisor's device-level
  AVAILABLE/DEGRADED/OUTAGE state machine to host granularity
  (``hostgroup.alive``/``hostgroup.state`` gauges, ``host_lost``/
  ``host_recovered`` failure-log actions, outage records through the
  supervisor's shared writer).  ``barrier_sync(name, timeout_s)`` is
  the deadline-guarded rendezvous: a rank that never arrives surfaces as a
  typed :class:`HostLostError` on every survivor within the deadline — no
  Python-level collective can hang silently.  (Native collectives already
  in flight are reclaimed by the launcher's SIGTERM→SIGKILL drain, the
  only reclaim that works on hung native code.)

* lost-host recovery — when a rank dies (exit or stale heartbeat), the
  launcher writes an abort file (survivors' barriers trip immediately),
  drains the survivors, and relaunches the group at the shrunken world
  size with ``generation+1``.  Ranks resume from their durable
  ``SweepCheckpoint``s, so the relaunched sweep replays completed families
  instead of refitting them — winner parity with an uninterrupted run is
  asserted in ``scripts/ci_hostgroup_smoke.py``.

This module deliberately avoids importing jax at module scope (like
``supervisor``): the launcher itself must stay importable and responsive
even when the accelerator runtime is the thing that is wedged.
"""

from __future__ import annotations

import json
import os
import re
import signal
import socket
import subprocess
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence

from ..resilience import record_failure
from ..telemetry import (REGISTRY, TRACEPARENT_ENV, TraceContext,
                         current_trace_context, event, span)
from .supervisor import (AVAILABLE, DEGRADED, OUTAGE, _STATE_CODES,
                         accelerator_expected, maybe_write_outage_record,
                         probe_devices, supervisor_enabled)

# -- the rank-side contract: env vars the launcher exports ------------------
ENV_RANK = "TRANSMOGRIFAI_HOSTGROUP_RANK"
ENV_WORLD = "TRANSMOGRIFAI_HOSTGROUP_WORLD"
ENV_RUN_DIR = "TRANSMOGRIFAI_HOSTGROUP_RUN_DIR"
ENV_COORDINATOR = "TRANSMOGRIFAI_HOSTGROUP_COORDINATOR"
ENV_GENERATION = "TRANSMOGRIFAI_HOSTGROUP_GENERATION"
ENV_DISTRIBUTED = "TRANSMOGRIFAI_HOSTGROUP_DISTRIBUTED"

#: Exit code a rank uses when it aborted because a PEER was lost (barrier
#: abort / HostLostError / graceful preemption during a drain).  The
#: launcher must not count such an exit as a loss of that rank itself —
#: it stays in the relaunch set.  (BSD EX_TEMPFAIL: try again.)
EXIT_HOST_LOST = 75


class HostLostError(RuntimeError):
    """A peer rank was lost (never arrived at a barrier / abort posted).

    Typed so sweeps can classify it with ``supervisor.is_device_loss`` and
    so survivors exit with :data:`EXIT_HOST_LOST` instead of an anonymous
    traceback."""

    def __init__(self, message: str, *, missing: Sequence[int] = (),
                 barrier: str = ""):
        super().__init__(message)
        self.missing = list(missing)
        self.barrier = barrier


# --------------------------------------------------------------------------
# env knobs (params/runner ride these like supervisorParams does)
# --------------------------------------------------------------------------

def _float_env(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except (TypeError, ValueError):
        return default


def beat_interval_s() -> float:
    """Host heartbeat write period (TRANSMOGRIFAI_HOSTGROUP_BEAT_S)."""
    return max(0.05, _float_env("TRANSMOGRIFAI_HOSTGROUP_BEAT_S", 1.0))


def liveness_timeout_s() -> float:
    """Silence budget before a host counts as lost
    (TRANSMOGRIFAI_HOSTGROUP_LIVENESS_S)."""
    return max(0.1, _float_env("TRANSMOGRIFAI_HOSTGROUP_LIVENESS_S", 15.0))


def barrier_timeout_s() -> float:
    """Default ``barrier_sync`` deadline (TRANSMOGRIFAI_HOSTGROUP_BARRIER_S)."""
    return max(0.1, _float_env("TRANSMOGRIFAI_HOSTGROUP_BARRIER_S", 120.0))


def init_timeout_s() -> float:
    """``jax.distributed`` init watchdog (TRANSMOGRIFAI_HOSTGROUP_INIT_S)."""
    return max(1.0, _float_env("TRANSMOGRIFAI_HOSTGROUP_INIT_S", 60.0))


def hostgroup_env_present() -> bool:
    """Is this process a rank of a launched host group?"""
    return bool(os.environ.get(ENV_RANK)) and bool(os.environ.get(ENV_RUN_DIR))


def current_rank() -> int:
    try:
        return int(os.environ.get(ENV_RANK, "0"))
    except ValueError:
        return 0


def group_world_size() -> int:
    try:
        return max(1, int(os.environ.get(ENV_WORLD, "1")))
    except ValueError:
        return 1


def group_run_dir() -> Optional[str]:
    return os.environ.get(ENV_RUN_DIR) or None


def group_generation() -> int:
    try:
        return int(os.environ.get(ENV_GENERATION, "0"))
    except ValueError:
        return 0


# --------------------------------------------------------------------------
# shared-file plumbing (heartbeats, barriers, ready/done markers)
# --------------------------------------------------------------------------

def _atomic_write_json(path: str, payload: Dict[str, Any]) -> None:
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as fh:
        json.dump(payload, fh, default=str)
    os.replace(tmp, path)


def _read_json(path: str) -> Optional[Dict[str, Any]]:
    try:
        with open(path) as fh:
            return json.load(fh)
    except (OSError, ValueError):
        return None   # mid-replace / not yet written


def _hb_path(run_dir: str, rank: int) -> str:
    return os.path.join(run_dir, "hb", f"rank-{rank}.json")


def write_host_heartbeat(run_dir: str, rank: int, *, seq: int,
                         generation: int = 0, state: str = AVAILABLE,
                         wall: Optional[float] = None) -> None:
    _atomic_write_json(_hb_path(run_dir, rank), {
        "rank": int(rank), "pid": os.getpid(), "seq": int(seq),
        "generation": int(generation), "state": state,
        "wallS": float(time.time() if wall is None else wall)})


def read_host_heartbeat(run_dir: str, rank: int) -> Optional[Dict[str, Any]]:
    return _read_json(_hb_path(run_dir, rank))


def ready_path(run_dir: str, rank: int, generation: int = 0) -> str:
    return os.path.join(run_dir, "ready", f"rank-{rank}.gen{generation}.json")


def done_path(run_dir: str, rank: int, generation: int = 0) -> str:
    return os.path.join(run_dir, "done", f"rank-{rank}.gen{generation}.json")


def _abort_path(run_dir: str, generation: int) -> str:
    return os.path.join(run_dir, f"abort.gen{generation}.json")


def write_abort(run_dir: str, generation: int, lost: Sequence[int],
                reason: str) -> None:
    """Post a group abort: every survivor's ``barrier_sync`` raises a typed
    :class:`HostLostError` on its next poll instead of burning its full
    deadline."""
    _atomic_write_json(_abort_path(run_dir, generation), {
        "generation": int(generation), "lost": [int(r) for r in lost],
        "reason": reason, "wallS": time.time()})


def read_abort(run_dir: str, generation: int) -> Optional[Dict[str, Any]]:
    return _read_json(_abort_path(run_dir, generation))


class HostBeat:
    """Background writer of this rank's heartbeat file — the host-level
    analog of the supervisor's device heartbeat, minus the probe: liveness
    of the *process* is the signal, the launcher/rank-0 judges it."""

    def __init__(self, run_dir: str, rank: int, *,
                 interval_s: Optional[float] = None, generation: int = 0):
        self.run_dir = run_dir
        self.rank = rank
        self.generation = generation
        self.interval_s = interval_s if interval_s is not None \
            else beat_interval_s()
        self.state = AVAILABLE
        self.seq = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def beat(self) -> None:
        self.seq += 1
        write_host_heartbeat(self.run_dir, self.rank, seq=self.seq,
                             generation=self.generation, state=self.state)

    def start(self) -> "HostBeat":
        if self._thread is not None:
            return self
        self.beat()   # first beat synchronously: launcher sees us promptly

        def _loop():
            while not self._stop.wait(self.interval_s):
                try:
                    self.beat()
                except Exception as e:  # noqa: BLE001 — beats best-effort
                    record_failure("hostgroup", "swallowed", e,
                                   point="hostgroup.beat", rank=self.rank)

        self._thread = threading.Thread(target=_loop, daemon=True,
                                        name=f"hostgroup-beat-{self.rank}")
        self._thread.start()
        return self

    def stop(self, state: str = "stopped") -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2 * self.interval_s + 1.0)
            self._thread = None
        try:   # final beat records the terminal state for post-mortems
            self.state = state
            self.beat()
        except Exception:  # noqa: BLE001
            pass


class HostLiveness:
    """Host-level AVAILABLE/DEGRADED/OUTAGE state machine over the ranks'
    heartbeat files — the supervisor ``Heartbeat`` discipline lifted from
    device to host granularity.  ``tick()`` is the synchronous unit (fully
    fake-clock testable); transitions land as ``host_lost`` /
    ``host_recovered`` failure-log actions, ``hostgroup.alive`` /
    ``hostgroup.state`` gauges, and a standard outage record per loss."""

    def __init__(self, run_dir: str, world: int, *,
                 timeout_s: Optional[float] = None, generation: int = 0,
                 clock=time.time, outage_path: Optional[str] = None,
                 context: str = ""):
        self.run_dir = run_dir
        self.world = world
        self.generation = generation
        self.timeout_s = timeout_s if timeout_s is not None \
            else liveness_timeout_s()
        self.clock = clock
        self.outage_path = outage_path
        self.context = context or f"host group under {run_dir}"
        self.t0 = clock()
        self.last_wall: Dict[int, float] = {}
        self.status: Dict[int, Optional[bool]] = {r: None
                                                  for r in range(world)}
        self.losses: List[Dict[str, Any]] = []

    # -- one supervision step ---------------------------------------------
    def tick(self, ranks: Optional[Sequence[int]] = None) -> Dict[str, Any]:
        now = self.clock()
        watch = list(ranks) if ranks is not None else list(range(self.world))
        alive, lost = [], []
        for r in watch:
            hb = read_host_heartbeat(self.run_dir, r)
            if hb is not None and int(hb.get("generation", 0)) == \
                    self.generation:
                try:
                    self.last_wall[r] = float(hb.get("wallS", 0.0))
                except (TypeError, ValueError):
                    pass
            last = self.last_wall.get(r)
            silent = (now - last) if last is not None else (now - self.t0)
            is_alive = last is not None and silent <= self.timeout_s
            if last is None and silent <= self.timeout_s:
                alive.append(r)   # boot window: not yet beaten, in budget
                continue
            prev = self.status.get(r)
            if prev is not False and not is_alive:
                self._host_lost(r, silent_s=silent)
            elif prev is False and is_alive:
                self._host_recovered(r, silent_s=silent)
            self.status[r] = is_alive
            (alive if is_alive else lost).append(r)
        state = AVAILABLE if not lost else (OUTAGE if not alive else DEGRADED)
        REGISTRY.gauge("hostgroup.alive").set(len(alive))
        REGISTRY.gauge("hostgroup.state").set(_STATE_CODES[state])
        return {"state": state, "alive": alive, "lost": lost, "wall": now}

    def _host_lost(self, rank: int, *, silent_s: float) -> None:
        record_failure("hostgroup", "host_lost",
                       f"rank {rank} silent {silent_s:.1f}s "
                       f"(budget {self.timeout_s:g}s)",
                       point="hostgroup.liveness", rank=rank,
                       generation=self.generation)
        REGISTRY.counter("hostgroup.host_losses_total").inc()
        event("hostgroup.host_lost", rank=rank, silent_s=round(silent_s, 2),
              generation=self.generation)
        loss = {"rank": rank, "generation": self.generation,
                "silentS": round(silent_s, 2), "wall": self.clock()}
        self.losses.append(loss)
        maybe_write_outage_record(
            what=f"host rank {rank} lost: no heartbeat for "
                 f"{silent_s:.1f}s (budget {self.timeout_s:g}s)",
            context=self.context,
            attempts=[{"from": _iso(self.t0), "to": _iso(self.clock()),
                       "every_s": self.timeout_s,
                       "result": f"rank {rank} heartbeat silent; "
                                 f"host declared lost"}],
            mitigations=("survivors aborted via barrier deadline/abort file",
                         "launcher relaunches the group at the shrunken "
                         "world size, resuming sweep checkpoints"),
            will_update="on relaunch: hostgroup.relaunches_total increments "
                        "and a new generation boots",
            path=self.outage_path)

    def _host_recovered(self, rank: int, *, silent_s: float) -> None:
        record_failure("hostgroup", "host_recovered",
                       f"rank {rank} heartbeat resumed",
                       point="hostgroup.liveness", rank=rank,
                       generation=self.generation)
        REGISTRY.counter("hostgroup.host_recoveries_total").inc()
        event("hostgroup.host_recovered", rank=rank,
              generation=self.generation)


def _iso(wall: float) -> str:
    try:
        return time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime(wall))
    except (OverflowError, OSError, ValueError):
        return str(wall)


# --------------------------------------------------------------------------
# deadline-guarded barrier
# --------------------------------------------------------------------------

def _barrier_file(run_dir: str, name: str, generation: int,
                  rank: int) -> str:
    safe = re.sub(r"[^A-Za-z0-9_.-]+", "_", name) or "barrier"
    return os.path.join(run_dir, "barrier",
                        f"{safe}.gen{generation}.rank{rank}.json")


def barrier_sync(name: str, timeout_s: Optional[float] = None, *,
                 rank: Optional[int] = None, world: Optional[int] = None,
                 run_dir: Optional[str] = None,
                 generation: Optional[int] = None, poll_s: float = 0.05,
                 clock=time.monotonic, sleep=time.sleep) -> float:
    """Rendezvous all ranks on ``name`` with a hard deadline.

    Arrival is a per-rank file under the run dir; a rank that never arrives
    surfaces on every waiting survivor as a typed :class:`HostLostError`
    naming the missing ranks within ``timeout_s`` — never a silent hang.
    A posted group abort (:func:`write_abort`) trips the barrier
    immediately, so survivors do not burn the full deadline once the
    launcher has already adjudicated the loss.  ``clock``/``sleep`` are
    injectable for fake-clock tests.  Returns the wait in (clock) seconds.
    """
    rank = current_rank() if rank is None else rank
    world = group_world_size() if world is None else world
    run_dir = group_run_dir() if run_dir is None else run_dir
    generation = group_generation() if generation is None else generation
    if run_dir is None:
        raise ValueError("barrier_sync needs a run_dir (not in a host group"
                         " and none passed)")
    timeout_s = barrier_timeout_s() if timeout_s is None else timeout_s
    _atomic_write_json(_barrier_file(run_dir, name, generation, rank),
                       {"rank": rank, "pid": os.getpid(),
                        "wallS": time.time()})
    t0 = clock()
    deadline = t0 + timeout_s
    with span("hostgroup.barrier", barrier=name, rank=rank, world=world,
              generation=generation, timeout_s=float(timeout_s)):
        while True:
            ab = read_abort(run_dir, generation)
            if ab is not None:
                missing = [int(r) for r in ab.get("lost", [])]
                raise HostLostError(
                    f"barrier {name!r} aborted: host(s) {missing} lost "
                    f"({ab.get('reason', 'no reason recorded')})",
                    missing=missing, barrier=name)
            missing = [r for r in range(world)
                       if not os.path.exists(
                           _barrier_file(run_dir, name, generation, r))]
            if not missing:
                waited = clock() - t0
                event("hostgroup.barrier_ok", barrier=name, rank=rank,
                      wait_s=round(waited, 3))
                return waited
            if clock() >= deadline:
                record_failure(
                    "hostgroup", "host_lost",
                    f"barrier {name!r} deadline {timeout_s:g}s: "
                    f"rank(s) {missing} never arrived",
                    point="hostgroup.barrier", rank=rank, barrier=name,
                    missing=",".join(map(str, missing)))
                REGISTRY.counter("hostgroup.barrier_timeouts_total").inc()
                raise HostLostError(
                    f"barrier {name!r} timed out after {timeout_s:g}s: "
                    f"rank(s) {missing} never arrived (world {world})",
                    missing=missing, barrier=name)
            sleep(poll_s)


# --------------------------------------------------------------------------
# rank-side context
# --------------------------------------------------------------------------

class HostGroup:
    """This rank's view of the group: identity, heartbeat, barriers and the
    ready/done markers the launcher (and smokes) consume."""

    def __init__(self, rank: int, world: int, run_dir: str, *,
                 generation: int = 0, coordinator: Optional[str] = None,
                 beat_interval: Optional[float] = None,
                 distributed: bool = False):
        self.rank = rank
        self.world = world
        self.run_dir = run_dir
        self.generation = generation
        self.coordinator = coordinator
        self.distributed = distributed
        self._beat = HostBeat(run_dir, rank, interval_s=beat_interval,
                              generation=generation)

    def barrier(self, name: str,
                timeout_s: Optional[float] = None) -> float:
        return barrier_sync(name, timeout_s, rank=self.rank,
                            world=self.world, run_dir=self.run_dir,
                            generation=self.generation)

    def mark_ready(self, extra: Optional[Dict[str, Any]] = None) -> None:
        _atomic_write_json(
            ready_path(self.run_dir, self.rank, self.generation),
            {"rank": self.rank, "pid": os.getpid(), "wallS": time.time(),
             "generation": self.generation,
             "distributed": self.distributed, **(extra or {})})

    def mark_done(self, payload: Optional[Dict[str, Any]] = None) -> None:
        _atomic_write_json(
            done_path(self.run_dir, self.rank, self.generation),
            {"rank": self.rank, "pid": os.getpid(), "wallS": time.time(),
             "generation": self.generation, **(payload or {})})

    def close(self, state: str = "stopped") -> None:
        self._beat.stop(state=state)


def maybe_init_hostgroup(*, distributed: Optional[bool] = None,
                         init_timeout: Optional[float] = None,
                         barrier_timeout: Optional[float] = None
                         ) -> Optional[HostGroup]:
    """Join the ambient host group, if this process is a rank of one.

    No-op (returns None) outside a launched group, so library code calls it
    unconditionally.  Inside one: starts the heartbeat, initializes
    ``jax.distributed`` against the group coordinator (CPU collectives
    backend selected first, so CI's multi-process CPU group runs real
    cross-process collectives), synchronizes the ``init`` barrier, and
    writes the ready marker the launcher's boot deadline watches.  Raises
    :class:`HostLostError` if a peer never reaches init — callers should
    exit :data:`EXIT_HOST_LOST` so the launcher keeps this rank in the
    relaunch set."""
    if not hostgroup_env_present():
        return None
    rank, world = current_rank(), group_world_size()
    run_dir, generation = group_run_dir(), group_generation()
    coordinator = os.environ.get(ENV_COORDINATOR) or None
    if distributed is None:
        distributed = os.environ.get(ENV_DISTRIBUTED, "1") != "0"
    distributed = bool(distributed and world > 1 and coordinator)
    hg = HostGroup(rank, world, run_dir, generation=generation,
                   coordinator=coordinator, distributed=distributed)
    hg._beat.start()
    REGISTRY.gauge("hostgroup.rank").set(rank)
    REGISTRY.gauge("hostgroup.world_size").set(world)
    REGISTRY.gauge("hostgroup.generation").set(generation)
    try:
        with span("hostgroup.init", rank=rank, world=world,
                  generation=generation, distributed=distributed):
            if distributed:
                from . import multihost
                multihost.ensure_cpu_collectives()
                multihost.init_distributed(
                    coordinator_address=coordinator, num_processes=world,
                    process_id=rank,
                    timeout_s=init_timeout if init_timeout is not None
                    else init_timeout_s())
            hg.barrier("init", timeout_s=barrier_timeout)
            hg.mark_ready()
    except BaseException:
        hg.close(state="init-failed")
        raise
    return hg


# --------------------------------------------------------------------------
# the launcher
# --------------------------------------------------------------------------

def _rank_obs_port(base: int, rank: int) -> int:
    """Control-plane port for ``rank`` given the configured base port.

    The launcher keeps ``base`` for its merged panel; rank ``r`` serves on
    ``base + 1 + r`` (rank 0 may share the launcher's host, so it cannot
    reuse ``base``).  ``launch_hosts`` exports the final per-rank value in
    the child env — ranks consume ``TRANSMOGRIFAI_OBS_PORT`` as-is and
    never offset themselves."""
    return int(base) + 1 + int(rank)


def _http_get(url: str, timeout_s: float = 1.0) -> Optional[str]:
    """Best-effort control-plane poll; None on any failure (a dead rank is
    a data point for ``hostgroup_rank_up``, not an error)."""
    import urllib.request
    try:
        with urllib.request.urlopen(url, timeout=timeout_s) as resp:
            return resp.read().decode("utf-8", "replace")
    except Exception:  # noqa: BLE001 — refused/timeout/garbage all mean down
        return None


def _start_merged_panel(base_port: int,
                        panel: Dict[str, Any]) -> Optional[Any]:
    """Launcher-side admin endpoint: polls every live rank's per-rank
    control plane at scrape time and re-serves ONE merged view —
    ``/metrics`` is the launcher registry plus ``hostgroup_rank_up{rank=}``
    plus every answering rank's exposition merged under a ``rank`` label
    (``merge_worker_metrics``); ``/statusz`` nests each rank's own statusz
    under ``ranks``.  ``panel`` is the launcher's mutable
    ``{"world", "generation"}`` state, updated per generation."""
    from ..obsv import maybe_start_obs_server, render_registry_metrics, \
        statusz_snapshot

    def _poll(endpoint: str) -> List[Any]:
        out = []
        for r in range(int(panel.get("world", 0))):
            body = _http_get(
                f"http://127.0.0.1:{_rank_obs_port(base_port, r)}"
                f"{endpoint}", timeout_s=panel.get("pollTimeoutS", 1.0))
            out.append((r, body))
        return out

    def merged_metrics() -> str:
        from ..serving.pool import merge_worker_metrics
        polled = _poll("/metrics")
        up = ["# HELP hostgroup_rank_up 1 if the rank's control plane "
              "answered the launcher's last poll",
              "# TYPE hostgroup_rank_up gauge"]
        texts = []
        for r, body in polled:
            up.append(f'hostgroup_rank_up{{rank="{r}"}} '
                      f'{1 if body is not None else 0}')
            if body is not None:
                texts.append((str(r), body))
        parts = [render_registry_metrics(), "\n".join(up) + "\n"]
        if texts:
            parts.append(merge_worker_metrics(texts, label="rank"))
        return "".join(parts)

    def merged_statusz() -> Dict[str, Any]:
        doc = statusz_snapshot()
        doc["role"] = "launcher"
        doc["world"] = int(panel.get("world", 0))
        doc["generation"] = int(panel.get("generation", 0))
        ranks: Dict[str, Any] = {}
        for r, body in _poll("/statusz"):
            if body is None:
                ranks[str(r)] = {"up": False}
                continue
            try:
                ranks[str(r)] = {"up": True, **json.loads(body)}
            except ValueError:
                ranks[str(r)] = {"up": True}
        doc["ranks"] = ranks
        return doc

    return maybe_start_obs_server(base_port, metrics_fn=merged_metrics,
                                  statusz_fn=merged_statusz)


def _free_port() -> int:
    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    try:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]
    finally:
        s.close()


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def _signal_group(proc: subprocess.Popen, sig: int) -> None:
    """Signal the child's whole process group (it was started with
    ``start_new_session=True``), falling back to the pid."""
    try:
        os.killpg(os.getpgid(proc.pid), sig)
    except (OSError, ProcessLookupError):
        try:
            proc.send_signal(sig)
        except (OSError, ProcessLookupError):
            pass


def _drain(procs: Dict[int, subprocess.Popen], grace_s: float,
           poll_s: float = 0.05) -> Dict[int, int]:
    """SIGTERM→grace→SIGKILL every still-running child; reap all.  The
    same escalation ``run_supervised`` applies, across the group — zero
    orphans is the postcondition."""
    for proc in procs.values():
        if proc.poll() is None:
            _signal_group(proc, signal.SIGTERM)
    deadline = time.monotonic() + max(0.0, grace_s)
    while time.monotonic() < deadline and \
            any(p.poll() is None for p in procs.values()):
        time.sleep(poll_s)
    escalated = [r for r, p in procs.items() if p.poll() is None]
    for r in escalated:
        _signal_group(procs[r], signal.SIGKILL)
        record_failure("hostgroup", "escalated",
                       f"rank {r} ignored SIGTERM for {grace_s:g}s",
                       point="hostgroup.drain", rank=r)
    rcs = {}
    for r, p in procs.items():
        try:
            rcs[r] = p.wait(timeout=10.0)
        except subprocess.TimeoutExpired:   # unkillable (D-state); record
            record_failure("hostgroup", "swallowed",
                           f"rank {r} survived SIGKILL reap window",
                           point="hostgroup.drain", rank=r)
            rcs[r] = -signal.SIGKILL
    return rcs


@dataclass
class HostGroupResult:
    """Outcome of one ``launch_hosts`` supervision: per-generation world
    sizes, every loss event, the final ranks' exit codes."""

    ok: bool
    world_size: int
    final_world: int
    generations: int
    relaunches: int
    run_dir: str
    wall_s: float
    losses: List[Dict[str, Any]] = field(default_factory=list)
    rank_rcs: Dict[int, Optional[int]] = field(default_factory=dict)
    preflight: Optional[Dict[str, Any]] = None
    reason: str = ""

    def to_json(self) -> Dict[str, Any]:
        return {"ok": self.ok, "worldSize": self.world_size,
                "finalWorld": self.final_world,
                "generations": self.generations,
                "relaunches": self.relaunches, "runDir": self.run_dir,
                "wallS": round(self.wall_s, 2), "losses": self.losses,
                "rankRcs": {str(k): v for k, v in self.rank_rcs.items()},
                "preflight": self.preflight, "reason": self.reason}


def launch_hosts(cmd: Sequence[str], hosts: int, *,
                 run_dir: Optional[str] = None,
                 env: Optional[Dict[str, str]] = None,
                 boot_timeout: float = 240.0,
                 beat_interval: Optional[float] = None,
                 liveness_timeout: Optional[float] = None,
                 grace_s: float = 15.0, max_relaunches: int = 1,
                 poll_s: float = 0.2, preflight: Optional[bool] = None,
                 distributed: bool = True,
                 coordinator_host: str = "127.0.0.1") -> HostGroupResult:
    """Run ``cmd`` as an ``hosts``-rank supervised group; recover host loss.

    Every generation: pick a fresh coordinator port, spawn one ranked child
    per host (rank identity via ``TRANSMOGRIFAI_HOSTGROUP_*``; one child
    trace context per rank so all spans share the launcher's trace id),
    wait for the per-rank ready files under ``boot_timeout``, then monitor
    child liveness (process exit + heartbeat staleness).  On a loss: post
    the group abort, write the standard outage record, drain survivors
    under SIGTERM→SIGKILL, and — budget permitting — relaunch at the
    shrunken world size with ``generation+1`` so ranks resume their sweep
    checkpoints.  Returns when a generation completes cleanly (every rank
    exits 0) or the relaunch budget is exhausted; zero children survive
    this call in any outcome."""
    if hosts < 1:
        raise ValueError(f"hosts must be >= 1, got {hosts}")
    cmd = list(cmd)
    if run_dir is None:
        import tempfile
        run_dir = tempfile.mkdtemp(prefix="hostgroup-")
    run_dir = os.path.abspath(run_dir)
    os.makedirs(run_dir, exist_ok=True)
    liveness_budget = liveness_timeout if liveness_timeout is not None \
        else liveness_timeout_s()
    t_start = time.monotonic()
    result = HostGroupResult(ok=False, world_size=hosts, final_world=hosts,
                             generations=0, relaunches=0, run_dir=run_dir,
                             wall_s=0.0)

    # pre-flight: the PR-11 subprocess probe — a wedged accelerator runtime
    # (a native init hang) becomes a typed verdict BEFORE any rank exists,
    # instead of N ranks hanging in init.  Run from a launcher that already
    # owns the chip, the probe child cannot have it and reports an outage
    # (platform pinned) or, unpinned, the CPU — degraded on a host that has
    # an accelerator, and the launch aborts: the launcher must stay off the
    # backend.
    if preflight is None:
        preflight = supervisor_enabled()
    if preflight:
        verdict = probe_devices(key="hostgroup-preflight",
                                expect_accelerator=accelerator_expected())
        result.preflight = verdict.to_json()
        if not verdict.ok:
            result.reason = (f"preflight probe: {verdict.status} "
                             f"({verdict.cause})")
            maybe_write_outage_record(
                what="host group launch aborted by pre-flight probe "
                     f"({verdict.cause})",
                context=f"launch_hosts(hosts={hosts}) under {run_dir}",
                attempts=verdict.attempts,
                mitigations=("typed verdict before any rank spawned; "
                             "no stuck multi-process init",),
                will_update="on operator action; relaunch re-probes",
                path=os.path.join(run_dir, "OUTAGE_hostgroup_preflight.json"))
            result.wall_s = time.monotonic() - t_start
            return result

    parent_ctx = current_trace_context() or TraceContext.new()
    base_env = dict(os.environ)
    if preflight and verdict.platform:
        # ranks get the platform the probe found, pinned: a rank that cannot
        # have it (the launcher, or a sibling rank on this host, owns the
        # chip) then fails in init instead of training on the CPU
        base_env.setdefault("JAX_PLATFORMS", verdict.platform)
    if env:
        base_env.update({str(k): str(v) for k, v in env.items()})
    # children must resolve the package wherever the launcher did
    base_env["PYTHONPATH"] = _repo_root() + (
        os.pathsep + base_env["PYTHONPATH"]
        if base_env.get("PYTHONPATH") else "")
    # every rank shares the launcher's compiled-program registry: rank 0's
    # publishes warm ranks 1..N-1, and a relaunch after a lost host resumes
    # without re-paying compiles.  The compile-cache variables
    # (JAX_COMPILATION_CACHE_DIR / TRANSMOGRIFAI_COMPILE_CACHE) are inherited
    # as they stand.
    from ..aot_registry import registry_root
    _reg = registry_root()
    if _reg:
        base_env.setdefault("TRANSMOGRIFAI_AOT_REGISTRY", _reg)

    # training control plane: when an obs port is configured the launcher
    # keeps the base port for the merged rank panel and deals each child
    # rank its own port below (base+1+rank)
    from ..obsv import (FlightRecorder, active_recorder, blackbox_note,
                        install_recorder, obs_port_from_env)
    obs_base = obs_port_from_env()
    panel_state: Dict[str, Any] = {"world": hosts, "generation": 0}
    obs_panel = _start_merged_panel(obs_base, panel_state) \
        if obs_base else None
    # the launcher is the process that adjudicates host loss, so it needs
    # its own flight recorder for the per-generation loss dump (ranks each
    # carry theirs; a SIGKILLed rank writes nothing)
    own_recorder = None
    if obs_base and active_recorder() is None:
        own_recorder = install_recorder(FlightRecorder())

    world = hosts
    generation = 0
    procs: Dict[int, subprocess.Popen] = {}
    logs: List[Any] = []
    try:
        while True:
            result.generations = generation + 1
            result.final_world = world
            panel_state["world"] = world
            panel_state["generation"] = generation
            REGISTRY.gauge("hostgroup.world_size").set(world)
            REGISTRY.gauge("hostgroup.generation").set(generation)
            port = _free_port()
            coordinator = f"{coordinator_host}:{port}"
            _atomic_write_json(os.path.join(run_dir, "world.json"),
                               {"worldSize": world, "generation": generation,
                                "coordinator": coordinator,
                                "traceId": parent_ctx.trace_id})
            procs = {}
            with span("hostgroup.generation", generation=generation,
                      world=world):
                for rank in range(world):
                    child_env = dict(base_env)
                    child_env.update({
                        ENV_RANK: str(rank), ENV_WORLD: str(world),
                        ENV_RUN_DIR: run_dir,
                        ENV_GENERATION: str(generation),
                        ENV_COORDINATOR: coordinator,
                        ENV_DISTRIBUTED: "1" if distributed else "0",
                        TRACEPARENT_ENV:
                            parent_ctx.child().to_traceparent()})
                    if obs_base:
                        child_env["TRANSMOGRIFAI_OBS_PORT"] = \
                            str(_rank_obs_port(obs_base, rank))
                    if beat_interval is not None:
                        child_env["TRANSMOGRIFAI_HOSTGROUP_BEAT_S"] = \
                            str(beat_interval)
                    log_fh = open(os.path.join(run_dir,
                                               f"rank-{rank}.log"), "ab")
                    logs.append(log_fh)
                    procs[rank] = subprocess.Popen(
                        cmd, stdout=log_fh, stderr=subprocess.STDOUT,
                        env=child_env, start_new_session=True)
                    event("hostgroup.spawn", rank=rank, pid=procs[rank].pid,
                          generation=generation)

                outcome = _supervise_generation(
                    procs, run_dir, world, generation,
                    boot_timeout=boot_timeout,
                    liveness_budget=liveness_budget, grace_s=grace_s,
                    poll_s=poll_s)
            result.rank_rcs = {r: p.poll() for r, p in procs.items()}
            if outcome["completed"]:
                result.ok = True
                result.reason = "completed"
                REGISTRY.gauge("hostgroup.state").set(
                    _STATE_CODES[AVAILABLE])
                return result
            result.losses.extend(outcome["losses"])
            new_world = world - len(outcome["losses"])
            if new_world >= 1 and result.relaunches < max_relaunches:
                result.relaunches += 1
                blackbox_note("hostgroup.relaunch",
                              generation=generation + 1, world=new_world)
                REGISTRY.counter("hostgroup.relaunches_total").inc()
                record_failure(
                    "hostgroup", "relaunched",
                    f"generation {generation} lost "
                    f"{len(outcome['losses'])} host(s); relaunching at "
                    f"world={new_world}",
                    point="hostgroup.launch", generation=generation,
                    world=new_world)
                event("hostgroup.relaunch", generation=generation + 1,
                      world=new_world)
                world = new_world
                generation += 1
                continue
            result.reason = (f"host loss at generation {generation} "
                             f"(survivors {new_world}, relaunch budget "
                             f"{max_relaunches} spent)")
            return result
    finally:
        if own_recorder is not None:
            install_recorder(None)
        if obs_panel is not None:
            obs_panel.stop()
        # zero orphans, in every outcome — kill anything still breathing
        stragglers = {r: p for r, p in procs.items() if p.poll() is None}
        if stragglers:
            _drain(stragglers, grace_s=0.0)
        for fh in logs:
            try:
                fh.close()
            except OSError:
                pass
        result.wall_s = time.monotonic() - t_start
        _atomic_write_json(os.path.join(run_dir, "result.json"),
                           result.to_json())


def _supervise_generation(procs: Dict[int, subprocess.Popen], run_dir: str,
                          world: int, generation: int, *,
                          boot_timeout: float, liveness_budget: float,
                          grace_s: float, poll_s: float) -> Dict[str, Any]:
    """Boot-wait + monitor one generation.  Returns ``{"completed": bool,
    "losses": [...]}`` — on loss, the abort is posted and every survivor
    drained before returning."""
    liveness = HostLiveness(
        run_dir, world, timeout_s=max(liveness_budget, boot_timeout),
        generation=generation, context=f"launch_hosts generation "
                                       f"{generation} under {run_dir}",
        outage_path=os.path.join(
            run_dir, f"OUTAGE_hostgroup_gen{generation}.json"))
    boot_deadline = time.monotonic() + boot_timeout
    booted = False
    completed: set = set()
    losses: List[Dict[str, Any]] = []

    def _lose(rank: int, rc: Optional[int], kind: str) -> None:
        last = liveness.last_wall.get(rank)
        silent = (time.time() - last) if last else None
        losses.append({"rank": rank, "generation": generation, "rc": rc,
                       "kind": kind,
                       "silentS": round(silent, 2) if silent else None})
        record_failure("hostgroup", "host_lost",
                       f"rank {rank} {kind} (rc={rc}) at generation "
                       f"{generation}",
                       point="hostgroup.launch", rank=rank, rc=rc,
                       kind=kind, generation=generation)
        REGISTRY.counter("hostgroup.host_losses_total").inc()
        event("hostgroup.host_lost", rank=rank, rc=rc, kind=kind,
              generation=generation)

    while True:
        now = time.monotonic()
        abort_posted = read_abort(run_dir, generation) is not None
        for rank, proc in procs.items():
            rc = proc.poll()
            if rc is None or rank in completed or \
                    any(l["rank"] == rank for l in losses):
                continue
            if rc == 0:
                completed.add(rank)
            elif rc == EXIT_HOST_LOST and abort_posted:
                pass   # survivor aborting on a peer loss we adjudicated
            else:
                _lose(rank, rc, "exit")
        if not booted:
            ready = [r for r in range(world)
                     if os.path.exists(ready_path(run_dir, r, generation))]
            if len(ready) == world:
                booted = True
                liveness.timeout_s = liveness_budget
                event("hostgroup.booted", generation=generation,
                      world=world)
            elif now >= boot_deadline and not losses:
                # the init-hang shape at group scope: rank(s) wedged before
                # ready — reclaim them (SIGTERM→SIGKILL) and call it a loss
                for rank in range(world):
                    if rank not in ready and procs[rank].poll() is None:
                        _drain({rank: procs[rank]}, grace_s)
                        _lose(rank, procs[rank].poll(), "boot-hang")
                if not losses:   # every laggard exited 0?? treat as hang
                    _lose(min(r for r in range(world) if r not in ready),
                          None, "boot-hang")
        if booted and not losses:
            st = liveness.tick(ranks=[r for r in range(world)
                                      if r not in completed])
            for rank in st["lost"]:
                proc = procs.get(rank)
                if proc is not None and proc.poll() is None:
                    # alive but silent past budget: hung — reclaim it
                    _drain({rank: proc}, grace_s)
                    _lose(rank, proc.poll(), "hang")
        if losses:
            lost_ranks = [l["rank"] for l in losses]
            write_abort(run_dir, generation, lost_ranks,
                        reason=f"rank(s) {lost_ranks} lost "
                               f"({losses[0]['kind']})")
            REGISTRY.gauge("hostgroup.state").set(_STATE_CODES[
                OUTAGE if len(lost_ranks) >= world else DEGRADED])
            # the launcher is the process that adjudicated the loss, so it
            # dumps the flight recorder here — BEFORE the outage record,
            # which then references the dump (a SIGKILLed rank writes
            # nothing, and a survivor wedged in a dead collective may never
            # reach its own except path)
            from ..obsv import blackbox_note, dump_blackbox
            for l in losses:
                blackbox_note("hostgroup.host_lost", loss=dict(l))
            dump_blackbox(
                reason=f"HostLostError: rank(s) {lost_ranks} lost "
                       f"({losses[0]['kind']}, rc={losses[0]['rc']})",
                path=os.path.join(run_dir,
                                  f"blackbox-launcher-gen{generation}.json"))
            maybe_write_outage_record(
                what=f"host(s) {lost_ranks} lost at generation "
                     f"{generation} (world {world}): "
                     f"{losses[0]['kind']}, rc={losses[0]['rc']}",
                context=f"launch_hosts generation {generation} under "
                        f"{run_dir}",
                attempts=[{"from": _iso(time.time()), "to": _iso(time.time()),
                           "every_s": poll_s,
                           "result": f"rank {l['rank']} {l['kind']} "
                                     f"(rc={l['rc']})"} for l in losses],
                mitigations=("abort posted: survivors' barriers raise typed "
                             "HostLostError instead of hanging",
                             "survivors drained under SIGTERM->SIGKILL",
                             "relaunch at shrunken world resumes sweep "
                             "checkpoints"),
                will_update="hostgroup.relaunches_total increments when the "
                            "shrunken generation boots",
                path=liveness.outage_path)
            _drain(procs, grace_s)
            return {"completed": False, "losses": losses}
        if len(completed) == world:
            return {"completed": True, "losses": []}
        time.sleep(poll_s)

"""Device-runtime supervisor — hang-proof probes, heartbeat, outage records.

The failure mode this module exists for:
``jax.devices()`` / distributed init can HANG in native code with no error
raised, and plain SIGTERM does not kill the hung process — only SIGKILL
does.  ``resilience.run_with_deadline``'s thread watchdog can *raise* on the
hang but cannot *reclaim* the thread, so anything that must actually free
the resources has to live in a child process the parent can escalate-kill.
This module is that discipline as a subsystem (the serving pool and the
host-group launcher start their children through it):

* ``run_supervised`` — run a child under a SIGTERM→SIGKILL escalation
  deadline (the ``timeout -k`` shape, as a library call).
* ``probe_devices`` / ``probe_with_backoff`` — a fresh child runs
  ``jax.devices()`` + a tiny compiled matmul and reports a structured
  :class:`ProbeVerdict` (available / degraded / outage, device inventory,
  probe latency).  This is the reference's RawFeatureFilter philosophy
  (validate before you commit compute) applied to hardware.
* ``Heartbeat`` — a background re-probe loop on a deterministic backoff
  schedule feeding a ``CircuitBreaker``, driving the
  AVAILABLE / DEGRADED / OUTAGE state machine exported through telemetry
  gauges and FailureLog actions (``outage`` / ``recovered``).
* ``write_outage_record`` — the standardized outage-record writer
  (``OUTAGE_RECORD_KEYS``).
* surviving-device tracking + ``is_device_loss`` — on a mid-sweep device
  failure the validator shrinks the mesh policy to the surviving devices
  (``mark_device_loss``) and resumes from the sweep checkpoint; typed
  errors (``DeviceLostError``, ``TransferStallError``) classify what is a
  device-runtime loss versus an ordinary candidate failure.

No jax import at module scope: the whole point of the probe is deciding
whether touching the backend is safe, so the supervisor itself must load
without initializing it.
"""

from __future__ import annotations

import itertools
import json
import os
import subprocess
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence

from ..resilience import (CircuitBreaker, InjectedFault, maybe_inject,
                          record_failure)

# -- state machine states (also ProbeVerdict statuses) ----------------------
AVAILABLE = "available"
DEGRADED = "degraded"
OUTAGE = "outage"
_STATE_CODES = {AVAILABLE: 0, DEGRADED: 1, OUTAGE: 2}


class DeviceLostError(RuntimeError):
    """A device participating in the active mesh was lost mid-run."""


class TransferStallError(RuntimeError):
    """A host→device transfer chunk exceeded its deadline (hung link)."""


# --------------------------------------------------------------------------
# knobs (env-driven so params/runner ride them like meshParams does)
# --------------------------------------------------------------------------

def supervisor_enabled() -> bool:
    """Kill switch: TRANSMOGRIFAI_SUPERVISOR=0 (or --no-supervisor) turns
    off sweep recovery; probes stay callable (they are just subprocesses)."""
    return os.environ.get("TRANSMOGRIFAI_SUPERVISOR") != "0"


def probe_timeout_s() -> float:
    """Per-probe deadline (TRANSMOGRIFAI_PROBE_TIMEOUT_S; the legacy
    BENCH_PROBE_TIMEOUT_S is honored so round-5 operator scripts keep
    working; default 150s)."""
    for var in ("TRANSMOGRIFAI_PROBE_TIMEOUT_S", "BENCH_PROBE_TIMEOUT_S"):
        v = os.environ.get(var)
        if v:
            try:
                return max(1.0, float(v))
            except ValueError:
                pass
    return 150.0


def probe_backoffs() -> List[float]:
    """Deterministic pre-probe backoff schedule in seconds
    (TRANSMOGRIFAI_PROBE_BACKOFFS / legacy BENCH_PROBE_BACKOFFS,
    default "0,45,120" — the round-5 schedule)."""
    for var in ("TRANSMOGRIFAI_PROBE_BACKOFFS", "BENCH_PROBE_BACKOFFS"):
        v = os.environ.get(var)
        if v:
            try:
                return [max(0.0, float(b)) for b in v.split(",") if b != ""]
            except ValueError:
                pass
    return [0.0, 45.0, 120.0]


def chunk_deadline_s() -> Optional[float]:
    """Per-chunk host→device transfer deadline
    (TRANSMOGRIFAI_CHUNK_DEADLINE_S; None/unset = no watchdog — the
    default, because a per-chunk watchdog thread costs ~50µs per chunk)."""
    v = os.environ.get("TRANSMOGRIFAI_CHUNK_DEADLINE_S")
    if not v:
        return None
    try:
        s = float(v)
    except ValueError:
        return None
    return s if s > 0 else None


def max_sweep_recoveries() -> int:
    """How many degrade-to-surviving-mesh resumes one sweep may attempt
    (TRANSMOGRIFAI_SWEEP_RECOVERIES, default 1); 0 when the supervisor is
    disabled — device-loss errors then propagate like any other."""
    if not supervisor_enabled():
        return 0
    try:
        return max(0, int(os.environ.get("TRANSMOGRIFAI_SWEEP_RECOVERIES",
                                         "1")))
    except ValueError:
        return 1


# --------------------------------------------------------------------------
# surviving-device tracking
# --------------------------------------------------------------------------

_SURVIVOR_LOCK = threading.Lock()
_DEVICE_CAP: Optional[int] = None    # None = all visible devices


def device_cap() -> Optional[int]:
    """Current surviving-device cap (None = no loss recorded)."""
    with _SURVIVOR_LOCK:
        return _DEVICE_CAP


def effective_device_count(n_visible: int) -> int:
    """Devices the mesh policy may use: the visible count clamped by the
    surviving-device cap (``maybe_data_mesh`` consults this, so the whole
    process degrades to the surviving mesh after ``mark_device_loss``)."""
    cap = device_cap()
    n = int(n_visible)
    return n if cap is None else max(1, min(n, cap))


def mark_device_loss(lost: int = 1) -> int:
    """Record the loss of ``lost`` device(s); returns the new cap.  jax's
    client cannot drop a device from an initialized backend, so the cap is
    how "the surviving mesh" is expressed: every subsequent
    ``maybe_data_mesh`` builds over the first ``cap`` devices only."""
    global _DEVICE_CAP
    with _SURVIVOR_LOCK:
        if _DEVICE_CAP is None:
            import jax   # lazy: only reached once a device already failed
            _DEVICE_CAP = len(jax.devices())
        _DEVICE_CAP = max(1, _DEVICE_CAP - max(1, int(lost)))
        cap = _DEVICE_CAP
    try:
        from ..telemetry import REGISTRY
        REGISTRY.gauge("supervisor.device_cap").set(cap)
    except Exception:  # noqa: BLE001 — bookkeeping must not mask the loss
        pass
    return cap


def reset_surviving_devices() -> None:
    """Clear the cap (tests; operator action after hardware recovers)."""
    global _DEVICE_CAP
    with _SURVIVOR_LOCK:
        _DEVICE_CAP = None


def is_device_loss(e: BaseException) -> bool:
    """Classify an exception as a device-runtime loss (vs an ordinary
    candidate/data failure).  Conservative on purpose: a compile error or
    OOM must keep its existing per-candidate degrade path — shrinking the
    mesh would not help and retrying the sweep would not converge."""
    if isinstance(e, (DeviceLostError, TransferStallError)):
        return True
    if type(e).__name__ == "HostLostError":
        return True   # hostgroup peer loss (name-matched: no circular import)
    s = str(e)
    if "supervisor.device_loss" in s or "supervisor.chunk_stall" in s \
            or "hostgroup.host_lost" in s:
        return True   # injected chaos markers (InjectedFault carries point)
    return ("UNAVAILABLE" in s or "DEVICE_LOST" in s
            or "device lost" in s.lower())


def note_sweep_device_loss(e: BaseException, *, attempt: int = 0,
                           stage: str = "validator") -> int:
    """One observable bundle per mid-sweep device loss: failure-log
    ``degraded``, ``supervisor.mesh_degrades_total`` counter, a
    ``supervisor.mesh_degrade`` telemetry event, and the shrunken
    surviving-device cap (returned)."""
    record_failure(stage, "degraded", e, point="supervisor.device_loss",
                   attempt=attempt, fallback="surviving-mesh resume")
    cap = mark_device_loss()
    try:
        from ..telemetry import REGISTRY, event
        REGISTRY.counter("supervisor.mesh_degrades_total").inc()
        event("supervisor.mesh_degrade", attempt=attempt, device_cap=cap,
              cause=f"{type(e).__name__}: {e}"[:200])
        from ..obsv import blackbox_note
        blackbox_note("supervisor.device_loss", attempt=attempt,
                      device_cap=cap,
                      cause=f"{type(e).__name__}: {e}"[:200])
    except Exception:  # noqa: BLE001
        pass
    return cap


# --------------------------------------------------------------------------
# supervised child processes (SIGTERM → SIGKILL escalation)
# --------------------------------------------------------------------------

@dataclass
class SupervisedResult:
    """Outcome of one supervised child run.  ``rc`` is 124 on deadline
    (the ``timeout(1)`` convention the scale-bench ladder already spoke);
    ``escalated`` means SIGTERM was ignored and SIGKILL reclaimed it."""

    rc: int
    stdout: str
    stderr: str
    wall_s: float
    timed_out: bool = False
    escalated: bool = False
    pid: int = 0


def run_supervised(cmd: Sequence[str], *, timeout_s: float,
                   grace_s: float = 10.0,
                   env: Optional[Dict[str, str]] = None,
                   cwd: Optional[str] = None,
                   traceparent: Optional[str] = None) -> SupervisedResult:
    """Run ``cmd`` under a SIGTERM→SIGKILL escalation deadline.

    On deadline: SIGTERM, wait ``grace_s``, then SIGKILL — the only kill
    that reliably works on a native-hung jax init.  The
    child is always reaped before returning (no zombies), and pipes are
    drained after the kill so a chatty child cannot deadlock the parent.

    The child inherits a trace context through ``TRANSMOGRIFAI_TRACEPARENT``
    (from ``traceparent`` when given, else the caller's ambient span) so a
    traced child nests under the triggering span across the process
    boundary; the run itself is recorded as a ``supervisor.child`` span."""
    from ..telemetry import (TRACEPARENT_ENV, TraceContext,
                             current_trace_context, span)
    parent_ctx = (TraceContext.parse(traceparent) if traceparent
                  else current_trace_context())
    child_ctx = parent_ctx.child() if parent_ctx else None
    env = dict(os.environ if env is None else env)
    if child_ctx is not None:
        env[TRACEPARENT_ENV] = child_ctx.to_traceparent()
    t0 = time.time()
    with span("supervisor.child", ctx=child_ctx,
              argv0=os.path.basename(str(cmd[0]))) as sp:
        p = subprocess.Popen(list(cmd), stdout=subprocess.PIPE,
                             stderr=subprocess.PIPE, text=True, env=env,
                             cwd=cwd, start_new_session=True)
        timed_out = escalated = False
        try:
            out, err = p.communicate(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            timed_out = True
            p.terminate()
            try:
                out, err = p.communicate(timeout=max(0.1, grace_s))
            except subprocess.TimeoutExpired:
                escalated = True
                p.kill()
                out, err = p.communicate()
        rc = 124 if timed_out else int(p.returncode)
        if sp is not None:
            sp.attrs.update(pid=p.pid, rc=rc, timed_out=timed_out,
                            escalated=escalated)
    return SupervisedResult(rc=rc, stdout=out or "", stderr=err or "",
                            wall_s=time.time() - t0, timed_out=timed_out,
                            escalated=escalated, pid=p.pid)


# --------------------------------------------------------------------------
# availability probes
# --------------------------------------------------------------------------

#: What the probe child actually does — ``jax.devices()`` (the call that
#: hangs during an outage) plus a tiny compiled matmul (the call that
#: proves dispatch works, not just enumeration).  It reports what a FRESH
#: process gets: run from a process that already owns the chip, the child
#: cannot have it — jax then fails (``JAX_PLATFORMS`` pinned: outage) or
#: continues on the CPU (unpinned: ``platform == "cpu"``, which
#: ``expect_accelerator`` reads as degraded).
_PROBE_CHILD = """\
import json
import jax
devs = jax.devices()
import jax.numpy as jnp
x = jnp.arange(256.0 * 256.0, dtype=jnp.float32).reshape(256, 256)
s = float(jnp.matmul(x, x).sum())
print(json.dumps({"platform": devs[0].platform,
                  "device_kind": devs[0].device_kind,
                  "devices": [str(d) for d in devs],
                  "matmul_finite": s == s}))
"""


def single_chip_env(chip: int) -> Dict[str, str]:
    """Child-environment entries that make a fresh process see exactly TPU
    chip ``chip`` of this host (libtpu reads them at start-up; other
    backends ignore them).  How a parent that stays off the backend gives
    each child its own chip — a chip belongs to one process at a time."""
    return {"TPU_VISIBLE_DEVICES": str(int(chip)),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1"}


def accelerator_expected() -> bool:
    """Should a fresh process on this host get an accelerator?  Yes when
    ``JAX_PLATFORMS`` pins one, or when the host shows an accelerator's
    device nodes (what libtpu itself looks for: ``/dev/accel*``, or
    ``/dev/vfio/<n>`` on v5e and later).  A launcher passes this as
    ``expect_accelerator`` to its probe: with the platform unpinned, a child
    that cannot have the chip — this process or another holds it —
    continues on the CPU, and the probe must call that degraded instead of
    handing the children a CPU pin.  An operator who wants the CPU on such
    a host says ``JAX_PLATFORMS=cpu``."""
    import glob
    pinned = (os.environ.get("JAX_PLATFORMS") or "").split(",")[0].strip()
    if pinned:
        return pinned != "cpu"
    return bool(glob.glob("/dev/accel*") or glob.glob("/dev/vfio/[0-9]*"))


#: Chaos preludes prepended to the probe child — the injection surface the
#: train-side chaos harness and CI smoke use to fake the init-hang failure
#: modes in a real subprocess (``hang_ignore_sigterm`` is the mode plain
#: SIGTERM cannot kill; only the SIGKILL escalation reclaims it).
CHAOS_PRELUDES = {
    "die": "import sys\nsys.exit(17)\n",
    "hang": "import time\nwhile True:\n    time.sleep(3600)\n",
    "hang_ignore_sigterm": ("import signal, time\n"
                            "signal.signal(signal.SIGTERM, signal.SIG_IGN)\n"
                            "while True:\n    time.sleep(3600)\n"),
}


def _utc_hhmm(t: float) -> str:
    return time.strftime("%H:%M", time.gmtime(t))


@dataclass
class ProbeVerdict:
    """Structured availability verdict from a subprocess-isolated probe."""

    status: str                      # available | degraded | outage
    platform: Optional[str] = None
    device_kind: Optional[str] = None
    device_count: int = 0
    devices: List[str] = field(default_factory=list)
    latency_s: float = 0.0
    cause: str = ""
    escalated: bool = False          # SIGKILL was needed to reclaim a probe
    attempts: List[Dict[str, Any]] = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return self.status == AVAILABLE

    def to_json(self) -> Dict[str, Any]:
        return {"status": self.status, "platform": self.platform,
                "deviceKind": self.device_kind,
                "deviceCount": self.device_count, "devices": self.devices,
                "latencyS": round(self.latency_s, 3), "cause": self.cause,
                "escalated": self.escalated, "attempts": self.attempts}


def probe_devices(timeout_s: Optional[float] = None, *,
                  grace_s: float = 10.0, chaos: Optional[str] = None,
                  platform: Optional[str] = None,
                  expect_accelerator: bool = False,
                  key: Any = "probe") -> ProbeVerdict:
    """Probe device-runtime availability in a FRESH child process under the
    SIGTERM→SIGKILL escalation deadline.

    A hung init surfaces as ``status="outage", cause="hang"`` within
    ``timeout_s + grace_s`` instead of stalling the caller forever; a
    reachable runtime reports its platform + device inventory; a CPU
    fallback when ``expect_accelerator`` is set reads as ``degraded``.
    ``chaos`` prepends a :data:`CHAOS_PRELUDES` failure mode to the child."""
    timeout_s = probe_timeout_s() if timeout_s is None else float(timeout_s)
    t0 = time.time()
    try:
        maybe_inject("supervisor.probe", key=key)
    except InjectedFault as e:
        attempt = {"wall_s": 0.0, "result": "injected",
                   "from": _utc_hhmm(t0), "to": _utc_hhmm(t0)}
        return ProbeVerdict(status=OUTAGE, cause=str(e), attempts=[attempt])
    code = CHAOS_PRELUDES.get(chaos or "", "") + _PROBE_CHILD
    env = dict(os.environ)
    if platform:
        env["JAX_PLATFORMS"] = platform
    r = run_supervised([sys.executable, "-c", code], timeout_s=timeout_s,
                       grace_s=grace_s, env=env)
    attempt: Dict[str, Any] = {"wall_s": round(r.wall_s, 1),
                               "from": _utc_hhmm(t0),
                               "to": _utc_hhmm(time.time())}
    if r.timed_out:
        attempt["result"] = "hang"
        return ProbeVerdict(status=OUTAGE, cause="hang",
                            latency_s=r.wall_s, escalated=r.escalated,
                            attempts=[attempt])
    if r.rc != 0:
        attempt["result"] = "error"
        attempt["tail"] = r.stderr.strip()[-300:]
        return ProbeVerdict(status=OUTAGE,
                            cause=f"probe child exited rc={r.rc}",
                            latency_s=r.wall_s, attempts=[attempt])
    line = next((ln for ln in reversed(r.stdout.splitlines())
                 if ln.startswith("{")), None)
    if not line:
        attempt["result"] = "no-verdict"
        return ProbeVerdict(status=DEGRADED,
                            cause="probe child printed no verdict line",
                            latency_s=r.wall_s, attempts=[attempt])
    info = json.loads(line)
    plat = info.get("platform")
    attempt["result"] = plat
    status = AVAILABLE
    cause = ""
    if expect_accelerator and plat == "cpu":
        status = DEGRADED
        cause = "accelerator expected but probe resolved cpu"
    return ProbeVerdict(status=status, platform=plat,
                        device_kind=info.get("device_kind"),
                        device_count=len(info.get("devices") or []),
                        devices=list(info.get("devices") or []),
                        latency_s=r.wall_s, cause=cause, attempts=[attempt])


def probe_with_backoff(timeout_s: Optional[float] = None,
                       backoffs: Optional[Sequence[float]] = None, *,
                       sleep: Callable[[float], None] = time.sleep,
                       key: Any = "probe",
                       **probe_kw) -> ProbeVerdict:
    """Retry :func:`probe_devices` on the deterministic backoff schedule
    until the runtime answers (available or degraded); the final verdict
    accumulates every attempt, so an outage verdict carries the full
    timeline for the outage record."""
    backoffs = list(probe_backoffs() if backoffs is None else backoffs)
    attempts: List[Dict[str, Any]] = []
    verdict = None
    for i, backoff_s in enumerate(backoffs or [0.0]):
        if backoff_s:
            sleep(backoff_s)
        verdict = probe_devices(timeout_s, key=f"{key}:{i}", **probe_kw)
        for a in verdict.attempts:
            attempts.append({**a, "every_s": backoff_s})
        if verdict.status != OUTAGE:
            break
    verdict.attempts = attempts
    try:
        from ..telemetry import REGISTRY
        REGISTRY.counter("supervisor.probes_total").inc(len(attempts))
        REGISTRY.gauge("supervisor.last_probe_latency_s").set(
            round(verdict.latency_s, 3))
    except Exception:  # noqa: BLE001
        pass
    return verdict


# --------------------------------------------------------------------------
# standardized outage records
# --------------------------------------------------------------------------

#: The stable schema every outage record carries, so dashboards and
#: post-mortems parse all of them alike.
OUTAGE_RECORD_KEYS = ("what", "context", "probe", "timeline_utc",
                      "mitigations_landed_this_round", "will_update")

_PROBE_DESC = ("fresh-process `jax.devices()` + 256x256 matmul-sum under a "
               "SIGTERM->SIGKILL escalation deadline "
               "(parallel/supervisor.py probe_devices)")


def outage_timeline(attempts: Sequence[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Probe attempts → the ``timeline_utc`` entries of the record shape."""
    out = []
    for a in attempts:
        out.append({"from": a.get("from", ""), "to": a.get("to", ""),
                    "every_s": a.get("every_s", 0),
                    "result": a.get("result", "")})
    return out


def write_outage_record(path: str, *, what: str, context: str = "",
                        probe: str = _PROBE_DESC,
                        timeline: Optional[Sequence[Dict[str, Any]]] = None,
                        mitigations: Sequence[str] = (),
                        will_update: str = "",
                        blackbox: Optional[str] = None) -> Dict[str, Any]:
    """Atomically write one outage record (``OUTAGE_RECORD_KEYS``); returns
    the record dict.  When the training control plane has dumped a
    flight-recorder ``blackbox.json`` this run, the record points at it
    (additive ``blackbox`` key — the key set stays intact otherwise)."""
    rec = {"what": what, "context": context, "probe": probe,
           "timeline_utc": list(timeline or []),
           "mitigations_landed_this_round": list(mitigations),
           "will_update": will_update}
    if blackbox is None:
        try:
            from ..obsv import last_blackbox_path
            blackbox = last_blackbox_path()
        except Exception:  # noqa: BLE001
            blackbox = None
    if blackbox:
        rec["blackbox"] = blackbox
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(rec, fh, indent=2)
    os.replace(tmp, path)
    return rec


def default_outage_path() -> Optional[str]:
    """Where unprompted outage records land: $TRANSMOGRIFAI_OUTAGE_DIR
    (one file per UTC day), else nowhere (None) — library code must never
    scribble into an unconfigured working directory."""
    d = os.environ.get("TRANSMOGRIFAI_OUTAGE_DIR")
    if not d:
        return None
    return os.path.join(d, time.strftime("OUTAGE_%Y%m%d.json", time.gmtime()))


def maybe_write_outage_record(*, what: str, context: str = "",
                              attempts: Sequence[Dict[str, Any]] = (),
                              mitigations: Sequence[str] = (),
                              will_update: str = "",
                              path: Optional[str] = None) -> Optional[str]:
    """The shared writer every outage site routes through (bench fallback,
    heartbeat trips, CI smoke): writes to ``path`` or the env-configured
    default; returns the path written, or None when no destination is
    configured (the caller's stdout record still happens)."""
    path = path or os.environ.get("BENCH_OUTAGE_RECORD") \
        or default_outage_path()
    if not path:
        return None
    try:
        write_outage_record(path, what=what, context=context,
                            timeline=outage_timeline(attempts),
                            mitigations=mitigations,
                            will_update=will_update)
    except Exception as e:  # noqa: BLE001 — the record is best-effort
        record_failure("supervisor", "swallowed", e,
                       point="supervisor.outage_record")
        return None
    return path


# --------------------------------------------------------------------------
# heartbeat supervision
# --------------------------------------------------------------------------

class Heartbeat:
    """Background device-runtime supervision: re-probe on a deterministic
    backoff schedule, feed a :class:`CircuitBreaker`, drive the
    AVAILABLE/DEGRADED/OUTAGE state machine.

    * probe ``available`` → breaker success; state AVAILABLE.
    * probe ``degraded`` (cpu fallback etc.) → breaker success (the runtime
      answered) but state DEGRADED.
    * probe ``outage`` → breaker failure; state DEGRADED until the breaker
      trips, OUTAGE once it opens.  The OUTAGE transition records an
      ``outage`` FailureLog action, bumps ``supervisor.outages_total`` and
      writes a standardized outage record; recovery records ``recovered``.

    The default probe is a FRESH child.  A chip belongs to one process, so
    started from the process that is training on the chip the child cannot
    have it: with ``JAX_PLATFORMS`` pinned the probe reads ``outage`` for
    as long as the train holds the device, unpinned it reads ``cpu``.  On
    an accelerator host the heartbeat therefore only tells the truth from a
    process that stays off the backend (a launcher, a pool parent).

    The probe interval doubles per consecutive failure (``interval_s`` →
    ``max_interval_s``) and resets on success.  Every collaborator (probe
    callable, clock, breaker) is injectable, so the state machine tests run
    on a fake clock with zero subprocesses; ``tick()`` is the synchronous
    unit the thread loop repeats."""

    def __init__(self, probe: Optional[Callable[[], ProbeVerdict]] = None, *,
                 interval_s: float = 300.0, max_interval_s: float = 1800.0,
                 multiplier: float = 2.0,
                 breaker: Optional[CircuitBreaker] = None,
                 failure_threshold: int = 2, reset_timeout_s: float = 600.0,
                 clock: Callable[[], float] = time.monotonic,
                 outage_dir: Optional[str] = None,
                 context: str = "device-runtime heartbeat"):
        from ..telemetry import REGISTRY
        self._registry = REGISTRY
        self._probe = probe if probe is not None else (
            lambda: probe_devices(key="heartbeat"))
        self.interval_s = float(interval_s)
        self.max_interval_s = float(max_interval_s)
        self.multiplier = max(1.0, float(multiplier))
        self.breaker = breaker if breaker is not None else CircuitBreaker(
            "device_runtime", failure_threshold=failure_threshold,
            min_calls=max(2 * failure_threshold, 4),
            reset_timeout_s=reset_timeout_s, clock=clock,
            registry=self._registry)
        self.context = context
        self.outage_dir = (outage_dir
                           or os.environ.get("TRANSMOGRIFAI_OUTAGE_DIR"))
        self.state = AVAILABLE
        self.last_verdict: Optional[ProbeVerdict] = None
        self._consecutive_failures = 0
        self._ticks = 0
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._registry.gauge("supervisor.state", self.state_code)

    # -- inspection --------------------------------------------------------
    def state_code(self) -> int:
        return _STATE_CODES[self.state]

    def next_interval_s(self) -> float:
        """Deterministic backoff: interval × multiplier^consecutive-failures,
        capped at ``max_interval_s``."""
        with self._lock:
            n = self._consecutive_failures
        return min(self.max_interval_s,
                   self.interval_s * self.multiplier ** n)

    # -- one synchronous supervision step ----------------------------------
    def tick(self) -> ProbeVerdict:
        with self._lock:
            tick_no = self._ticks
            self._ticks += 1
        try:
            maybe_inject("supervisor.heartbeat", key=tick_no)
            v = self._probe()
        except InjectedFault as e:
            v = ProbeVerdict(status=OUTAGE, cause=str(e))
        except Exception as e:  # noqa: BLE001 — a broken probe IS an outage
            v = ProbeVerdict(status=OUTAGE,
                             cause=f"{type(e).__name__}: {e}")
        self.last_verdict = v
        self._registry.counter("supervisor.probes_total").inc()
        self._registry.gauge("supervisor.last_probe_latency_s").set(
            round(v.latency_s, 3))
        # advance the breaker's open→half-open edge lazily (same contract as
        # call sites using allow()): the heartbeat IS the recovery probe
        self.breaker.allow()
        if v.status == OUTAGE:
            self.breaker.record_failure(v.cause)
            with self._lock:
                self._consecutive_failures += 1
        else:
            self.breaker.record_success()
            with self._lock:
                self._consecutive_failures = 0
        if v.status == OUTAGE:
            tripped = self.breaker.current_state() != CircuitBreaker.CLOSED
            new = OUTAGE if tripped else DEGRADED
        elif v.status == DEGRADED:
            new = DEGRADED
        else:
            new = AVAILABLE
        if new != self.state:
            self._transition(new, v)
        return v

    def _transition(self, new: str, v: ProbeVerdict) -> None:
        old, self.state = self.state, new
        try:
            from ..telemetry import event
            event("supervisor.transition", from_state=old, to_state=new,
                  cause=(v.cause or v.status)[:200])
        except Exception:  # noqa: BLE001
            pass
        if new == OUTAGE:
            record_failure("supervisor", "outage", v.cause or "probe outage",
                           point="supervisor.heartbeat",
                           breaker=self.breaker.name)
            self._registry.counter("supervisor.outages_total").inc()
            try:
                from ..obsv import blackbox_note
                blackbox_note("supervisor.outage",
                              cause=(v.cause or v.status)[:200],
                              from_state=old)
            except Exception:  # noqa: BLE001
                pass
            maybe_write_outage_record(
                what="device runtime unavailable (heartbeat breaker open)",
                context=self.context, attempts=v.attempts,
                mitigations=("heartbeat degraded the process to the "
                             "surviving/CPU path; see failure log",),
                will_update="recovery transition appends to the failure log",
                path=(os.path.join(self.outage_dir,
                                   time.strftime("OUTAGE_%Y%m%d.json",
                                                 time.gmtime()))
                      if self.outage_dir else None))
        elif new == AVAILABLE:
            record_failure("supervisor", "recovered",
                           f"device runtime recovered from {old}",
                           point="supervisor.heartbeat")
        else:
            record_failure("supervisor", "degraded",
                           v.cause or "probe degraded",
                           point="supervisor.heartbeat")

    # -- background loop ---------------------------------------------------
    def start(self) -> "Heartbeat":
        with self._lock:
            if self._thread is not None:
                return self
            self._stop.clear()
            self._thread = threading.Thread(target=self._loop, daemon=True,
                                            name="supervisor-heartbeat")
            self._thread.start()
        return self

    def _loop(self) -> None:
        while not self._stop.is_set():
            try:
                self.tick()
            except Exception:  # noqa: BLE001 — supervision must not die
                pass
            self._stop.wait(self.next_interval_s())

    def stop(self, timeout_s: float = 5.0) -> None:
        self._stop.set()
        with self._lock:
            t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout=timeout_s)


# monotone chunk sequence for streaming's chunk-stall injection keys: keys
# never repeat across sweep recovery attempts, so a sticky fail_keys entry
# kills the FIRST attempt's chunk and lets the resume stream cleanly
_CHUNK_SEQ = itertools.count()


def next_chunk_key() -> int:
    return next(_CHUNK_SEQ)

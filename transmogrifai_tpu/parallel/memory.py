"""Memory governance for the training/streaming paths (ISSUE 15).

Scale runs used to die the same way: the device ran out of memory inside
``batched_device_put`` and a human re-launched with a smaller hand-picked
budget.  This module makes the runtime walk that ladder itself, in four
pieces:

* **Budget discovery** — per-device capacity from
  ``TRANSMOGRIFAI_DEVICE_MEM_BYTES`` (operator override / ``memoryParams``
  mirror) or ``device.memory_stats()`` where the backend reports it
  (guarded: CPU backends usually return nothing).
* **Preflight planning** — before any ``stream_to_device``/``device_put``,
  :func:`plan_sweep_memory` estimates the padded-ladder-rung × dtype ×
  grid-width × fused-fold-panel footprint (plus an XLA temp headroom
  factor) against the budget and picks the streaming chunk bytes and a
  candidate-grid partitioning up front — OOM becomes a plan, not a crash.
* **Typed classification** — :func:`is_memory_exhaustion` is the sibling of
  ``supervisor.is_device_loss``: a conservative string/errtype matrix
  (RESOURCE_EXHAUSTED, "out of memory", allocator messages) that NEVER
  overlaps device loss, producing :class:`MemoryExhaustedError` with the
  attempted plan attached.  The two classifiers route to different
  recoveries: device loss shrinks the mesh; memory exhaustion shrinks the
  *work* via the degrade ladder below.
* **Shrink-and-retry ladder + host watchdog** — on classified OOM the sweep
  walks a deterministic degrade ladder (halve streaming chunk bytes →
  partition the candidate grid into sub-batches → collapse the model axis →
  per-candidate fallback), each step a ``degraded`` FailureLog note and a
  ``memory.shrink`` telemetry event, resuming from the ``SweepCheckpoint``.
  :class:`RssWatchdog` is the host-side analog: soft watermark sheds
  pretrace queues and device-transfer caches, hard watermark raises typed
  :class:`HostMemoryPressure` instead of letting the kernel OOM-killer
  choose a victim.

Everything here reads the environment per call (the ``memoryParams`` →
``TRANSMOGRIFAI_*`` mirror in ``runner.py`` composes with operator
overrides), and every collaborator of the watchdog (clock, RSS reader,
shedders) is injectable so the state machine tests run on a fake clock.
"""

from __future__ import annotations

import os
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence

from ..resilience import InjectedFault, maybe_inject, record_failure

# headroom multiplying the analytic footprint estimate: XLA temporaries,
# fusion scratch, and the double-buffered staging copies are real bytes the
# formula cannot see
_DEFAULT_HEADROOM = 1.5
# ladder steps, in the order the shrink-and-retry walks them
LADDER_STEPS = ("halve_chunk_bytes", "partition_grid",
                "collapse_model_axis", "per_candidate_fallback")


class MemoryExhaustedError(RuntimeError):
    """Typed device-memory exhaustion, carrying the plan that was being
    attempted when the allocator gave up — the post-mortem starts with
    ``e.plan`` instead of a grep through allocator spew."""

    def __init__(self, message: str, plan: Optional["MemoryPlan"] = None):
        super().__init__(message)
        self.plan = plan


class HostMemoryPressure(RuntimeError):
    """Host RSS crossed the hard watermark: typed, raised by governed code
    (via :func:`check_host_pressure`) before the kernel OOM-killer picks a
    victim for us."""


# --------------------------------------------------------------------------
# enablement + budget discovery
# --------------------------------------------------------------------------

def memory_governor_enabled() -> bool:
    """Preflight planning + shrink-and-retry are ON by default
    (TRANSMOGRIFAI_MEMORY_GOVERNOR=0 / ``--no-memory-governor`` opt out)."""
    return os.environ.get("TRANSMOGRIFAI_MEMORY_GOVERNOR", "1") != "0"


def memory_headroom() -> float:
    """XLA-temp headroom factor applied to the analytic footprint estimate
    (TRANSMOGRIFAI_MEMORY_HEADROOM, default 1.5)."""
    try:
        v = float(os.environ.get("TRANSMOGRIFAI_MEMORY_HEADROOM",
                                 str(_DEFAULT_HEADROOM)))
    except ValueError:
        return _DEFAULT_HEADROOM
    return v if v >= 1.0 else _DEFAULT_HEADROOM


def device_memory_budget() -> Optional[int]:
    """Per-device memory budget in bytes: the operator override
    (TRANSMOGRIFAI_DEVICE_MEM_BYTES, mirrored from
    ``memoryParams.deviceMemBytes``) wins; otherwise the backend's own
    ``memory_stats()`` limit where reported (TPU/GPU runtimes do, CPU
    usually doesn't); ``None`` = unknown, the planner passes through."""
    v = os.environ.get("TRANSMOGRIFAI_DEVICE_MEM_BYTES")
    if v:
        try:
            n = int(float(v))
            return n if n > 0 else None
        except ValueError:
            pass
    try:
        import jax
        stats = jax.local_devices()[0].memory_stats()
        if stats:
            for key in ("bytes_limit", "bytes_reservable_limit"):
                lim = stats.get(key)
                if lim:
                    return int(lim)
    except Exception:  # noqa: BLE001 — unknown budget is a valid answer
        pass
    return None


def max_oom_recoveries() -> int:
    """How many degrade-ladder steps one sweep may take on classified OOM
    (TRANSMOGRIFAI_OOM_RECOVERIES, default = the full ladder); 0 when the
    governor is off — memory errors then propagate like any other."""
    if not memory_governor_enabled():
        return 0
    try:
        return max(0, int(os.environ.get("TRANSMOGRIFAI_OOM_RECOVERIES",
                                         str(len(LADDER_STEPS)))))
    except ValueError:
        return len(LADDER_STEPS)


# --------------------------------------------------------------------------
# typed classification (sibling of supervisor.is_device_loss)
# --------------------------------------------------------------------------

# allocator/runtime phrasings that mean "the device ran out of memory" —
# conservative on purpose: a bad hyper-parameter or a compile error must
# keep its per-candidate degrade path, and NOTHING here may overlap the
# device-loss matrix (UNAVAILABLE / DEVICE_LOST), which routes to the
# surviving-mesh recovery instead
_OOM_MARKERS = (
    "RESOURCE_EXHAUSTED",
    "resource exhausted",
    "out of memory",
    "oom when allocating",
    "failed to allocate",
    "allocation failure",
    "exceeds the memory available",
    "memory.device_oom",   # injected chaos marker (InjectedFault str)
)


def is_memory_exhaustion(e: BaseException) -> bool:
    """Classify an exception as device-memory exhaustion (vs an ordinary
    candidate failure OR a device loss).  The shrink-and-retry ladder only
    fires on these; everything else keeps its existing path."""
    if isinstance(e, MemoryExhaustedError):
        return True
    if isinstance(e, MemoryError):
        return True
    from .supervisor import is_device_loss
    if is_device_loss(e):
        return False   # disjoint by construction: mesh shrink, not ladder
    s = str(e).lower()
    return any(m.lower() in s for m in _OOM_MARKERS)


# --------------------------------------------------------------------------
# preflight planning
# --------------------------------------------------------------------------

@dataclass
class MemoryPlan:
    """What the sweep is about to ask of each device, and what the planner
    chose about it.  Attached to :class:`MemoryExhaustedError` and recorded
    in bench ``aux.memory`` so failed attempts document themselves."""

    rows: int                      # padded ladder-rung row count
    cols: int
    folds: int                     # fused fold panels
    grid_width: int                # widest candidate family grid
    devices: int
    dtype_bytes: int
    headroom: float
    device_budget: Optional[int]   # bytes per device; None = unknown
    est_device_bytes: int          # estimated per-device peak footprint
    chunk_bytes: int               # chosen streaming chunk budget
    grid_parts: int = 1            # candidate-grid sub-batches
    shrinks: List[str] = field(default_factory=list)  # ladder steps applied
    nnz: Optional[int] = None      # sparse payload: real COO entry count

    def fits(self) -> bool:
        return (self.device_budget is None
                or self.est_device_bytes <= self.device_budget)

    def to_json(self) -> Dict[str, Any]:
        return {"rows": self.rows, "cols": self.cols, "folds": self.folds,
                "gridWidth": self.grid_width, "devices": self.devices,
                "dtypeBytes": self.dtype_bytes, "headroom": self.headroom,
                "deviceBudgetBytes": self.device_budget,
                "estDeviceBytes": self.est_device_bytes,
                "chunkBytes": self.chunk_bytes,
                "gridParts": self.grid_parts,
                "fits": self.fits(), "shrinks": list(self.shrinks),
                "nnz": self.nnz}


_PLAN_LOCK = threading.Lock()
_LAST_PLAN: Optional[MemoryPlan] = None


def last_plan() -> Optional[MemoryPlan]:
    """The most recent preflight plan (bench aux, error attachment)."""
    with _PLAN_LOCK:
        return _LAST_PLAN


def estimate_sweep_device_bytes(*, rows: int, cols: int, folds: int,
                                grid_width: int, devices: int,
                                dtype_bytes: int = 4,
                                headroom: Optional[float] = None,
                                nnz: Optional[int] = None) -> int:
    """Analytic per-device footprint of one fused sweep: the row-sharded
    matrix shard, the fold weight/validation panels ((2·folds+1) row
    vectors: train masks, validation masks, labels), and the per-lane
    working set of the batched (fold × grid) fit programs (coefficients +
    metric panels per lane), all under the XLA-temp headroom factor.

    ``nnz`` marks a sparse COO payload: the resident matrix is then the
    ladder-rounded entry capacity × 3 flat components (value/col/row, one
    dtype word each), not ``rows × cols`` — the dense-equivalent estimate
    over-counts hashed-text matrices by orders of magnitude and would
    shrink the plan for memory the sweep never allocates."""
    devices = max(1, int(devices))
    h = memory_headroom() if headroom is None else max(1.0, float(headroom))
    if nnz is not None:
        from ..sparse.matrix import nnz_capacity
        per = -(-int(nnz) // devices)
        matrix = devices * nnz_capacity(per) * 3
    else:
        matrix = rows * cols
    panels = (2 * folds + 1) * rows
    lanes = grid_width * folds * (cols + 8)
    return int((matrix + panels) * dtype_bytes * h / devices
               + lanes * dtype_bytes * h)


def plan_sweep_memory(*, rows: int, cols: int, folds: int, grid_width: int,
                      devices: int = 1, dtype_bytes: int = 4,
                      budget: Optional[int] = None,
                      chunk_bytes: Optional[int] = None,
                      nnz: Optional[int] = None) -> MemoryPlan:
    """Choose chunk bytes and grid partitioning BEFORE the first transfer.

    Deterministic: the same shapes and budget always produce the same plan.
    The chunk budget halves until two staging buffers (double buffering)
    fit comfortably beside the resident estimate; when the resident
    estimate itself exceeds the device budget the candidate grid splits
    into sub-batches (halving the per-lane working set per step) — the
    same degrade the runtime ladder applies reactively, applied up front.
    Applied ladder shrinks (:func:`grid_partitions` etc.) fold in so a
    post-OOM replan starts from the degraded state, not from scratch."""
    from .streaming import device_chunk_bytes
    if budget is None:
        budget = device_memory_budget()
    base_chunk = chunk_bytes if chunk_bytes is not None \
        else device_chunk_bytes()
    chunk = effective_chunk_bytes(base_chunk)
    parts = grid_partitions()
    shrinks = []
    est = estimate_sweep_device_bytes(
        rows=rows, cols=cols, folds=folds,
        grid_width=-(-grid_width // parts), devices=devices,
        dtype_bytes=dtype_bytes, nnz=nnz)
    if budget is not None:
        # two chunk-sized staging buffers live beside the resident set
        # during streaming; keep them under a quarter of the budget
        while chunk > (1 << 20) and 2 * chunk > budget // 4:
            chunk //= 2
            shrinks.append("halve_chunk_bytes")
        while est > budget and parts < max(1, grid_width):
            parts *= 2
            shrinks.append("partition_grid")
            est = estimate_sweep_device_bytes(
                rows=rows, cols=cols, folds=folds,
                grid_width=-(-grid_width // parts), devices=devices,
                dtype_bytes=dtype_bytes, nnz=nnz)
    plan = MemoryPlan(rows=int(rows), cols=int(cols), folds=int(folds),
                      grid_width=int(grid_width), devices=int(devices),
                      dtype_bytes=int(dtype_bytes),
                      headroom=memory_headroom(), device_budget=budget,
                      est_device_bytes=int(est), chunk_bytes=int(chunk),
                      grid_parts=int(parts), shrinks=shrinks,
                      nnz=None if nnz is None else int(nnz))
    global _LAST_PLAN
    with _PLAN_LOCK:
        _LAST_PLAN = plan
    try:
        from ..telemetry import REGISTRY, event
        REGISTRY.gauge("memory.plan_bytes").set(plan.est_device_bytes)
        REGISTRY.gauge("memory.chunk_bytes").set(plan.chunk_bytes)
        if budget is not None:
            REGISTRY.gauge("memory.budget_bytes").set(budget)
        if shrinks or not plan.fits():
            event("memory.plan", **plan.to_json())
    except Exception:  # noqa: BLE001 — planning must not fail the sweep
        pass
    return plan


def estimate_batch_bytes(rows: int, features: int,
                         dtype_bytes: int = 4) -> int:
    """Serving-side footprint estimate of one scoring batch (the admission
    controller's memory signal): rows × feature width × dtype under the
    same headroom factor the training planner uses."""
    return int(rows * max(1, int(features)) * dtype_bytes
               * memory_headroom())


# --------------------------------------------------------------------------
# the degrade ladder (process-ambient, like the surviving-device cap)
# --------------------------------------------------------------------------

_LADDER_LOCK = threading.Lock()
_SHRINK_LEVEL = 0


def shrink_level() -> int:
    """Ladder rungs applied so far this process (0 = unpressured)."""
    with _LADDER_LOCK:
        return _SHRINK_LEVEL


def reset_memory_degrade() -> None:
    """Clear the ladder (tests; operator action after pressure clears)."""
    global _SHRINK_LEVEL
    with _LADDER_LOCK:
        _SHRINK_LEVEL = 0


def _level() -> int:
    with _LADDER_LOCK:
        return _SHRINK_LEVEL


def effective_chunk_bytes(base: int) -> int:
    """Streaming chunk budget under the ladder: every rung ≥1 halves it
    once more (rung 1 halves, rung 2 quarters, ...), floor 1MB — the
    deepest rungs keep shrinking staging while they also shrink work."""
    lvl = _level()
    if lvl <= 0:
        return int(base)
    return max(1 << 20, int(base) >> lvl)


def grid_partitions() -> int:
    """Candidate-grid sub-batches (rung ≥2 doubles per rung: one batched
    (fold × grid) program becomes 2, 4, ... smaller ones)."""
    lvl = _level()
    return 1 if lvl < 2 else 1 << (lvl - 1)


def model_axis_collapsed() -> bool:
    """Rung ≥3: give the model axis's devices back to the data axis so
    each candidate lane spans more HBM."""
    return _level() >= 3


def per_candidate_fallback() -> bool:
    """Rung ≥4 (last resort): skip the batched grid programs entirely and
    refit per (fold, grid point) — smallest possible working set."""
    return _level() >= 4


def note_sweep_memory_exhaustion(e: BaseException, *, attempt: int = 0,
                                 stage: str = "validator") -> int:
    """One observable bundle per mid-sweep OOM: failure-log ``degraded``
    at point ``memory.device_oom``, the ``memory.shrinks_total`` counter,
    a ``memory.shrink`` telemetry event naming the ladder step taken, and
    the new shrink level (returned)."""
    global _SHRINK_LEVEL
    with _LADDER_LOCK:
        _SHRINK_LEVEL += 1
        lvl = _SHRINK_LEVEL
    step = LADDER_STEPS[min(lvl, len(LADDER_STEPS)) - 1]
    record_failure(stage, "degraded", e, point="memory.device_oom",
                   attempt=attempt, fallback=f"memory ladder: {step}")
    try:
        from ..telemetry import REGISTRY, event
        REGISTRY.counter("memory.shrinks_total").inc()
        REGISTRY.gauge("memory.shrink_level").set(lvl)
        event("memory.shrink", attempt=attempt, level=lvl, step=step,
              cause=f"{type(e).__name__}: {e}"[:200])
        from ..obsv import blackbox_note
        blackbox_note("memory.shrink", attempt=attempt, level=lvl,
                      step=step, cause=f"{type(e).__name__}: {e}"[:200])
    except Exception:  # noqa: BLE001
        pass
    return lvl


def as_memory_exhausted(e: BaseException) -> MemoryExhaustedError:
    """Wrap a classified allocator error into the typed form with the
    attempted plan attached (idempotent for already-typed errors)."""
    if isinstance(e, MemoryExhaustedError):
        if e.plan is None:
            e.plan = last_plan()
        return e
    return MemoryExhaustedError(
        f"device memory exhausted: {type(e).__name__}: {e}",
        plan=last_plan())


# --------------------------------------------------------------------------
# host-side RSS watchdog
# --------------------------------------------------------------------------

def _read_rss_bytes() -> int:
    """Current RSS from /proc/self/statm (pages × page size); 0 when the
    proc filesystem is unavailable (macOS tests inject a reader)."""
    try:
        with open("/proc/self/statm") as fh:
            pages = int(fh.read().split()[1])
        return pages * os.sysconf("SC_PAGE_SIZE")
    except Exception:  # noqa: BLE001
        return 0


def _default_shedders() -> Sequence[Callable[[], int]]:
    """What soft pressure is allowed to drop: queued (not-yet-started)
    background pre-traces, and the host→device transfer cache.  Both are
    pure performance state — correctness never depends on either."""
    def shed_pretrace() -> int:
        from ..aot import pretrace_shed
        return pretrace_shed()

    def shed_device_cache() -> int:
        from ..columns import shed_device_cache
        return shed_device_cache()

    return (shed_pretrace, shed_device_cache)


def _env_bytes(name: str) -> Optional[int]:
    v = os.environ.get(name)
    if not v:
        return None
    try:
        n = int(float(v))
        return n if n > 0 else None
    except ValueError:
        return None


class RssWatchdog:
    """Heartbeat-style host-memory supervision with two watermarks.

    * below soft → state ``ok``;
    * RSS ≥ ``soft_bytes`` → state ``soft``: run the shedders (pretrace
      queue, device-transfer cache), record a ``shed`` FailureLog note and
      bump ``memory.host_soft_total`` — once per excursion, not per tick;
    * RSS ≥ ``hard_bytes`` → state ``hard``: record ``degraded``, bump
      ``memory.host_hard_total``, and trip the pressure flag —
      :func:`check_host_pressure` (called at sweep boundaries) then raises
      typed :class:`HostMemoryPressure` on the *governed* thread, where it
      can be handled, instead of letting the kernel OOM-killer act;
    * falling back below soft records ``recovered`` and clears the trip.

    Every collaborator (clock, RSS reader, shedders) is injectable and
    ``tick()`` is the synchronous unit the daemon loop repeats, mirroring
    ``supervisor.Heartbeat`` so the transition tests run on a fake clock
    with zero threads.  Gauges: ``memory.host_rss_bytes``,
    ``memory.watchdog_state`` (0 ok / 1 soft / 2 hard)."""

    _STATE_CODES = {"ok": 0, "soft": 1, "hard": 2}

    def __init__(self, *, soft_bytes: Optional[int] = None,
                 hard_bytes: Optional[int] = None,
                 interval_s: float = 10.0,
                 rss_reader: Callable[[], int] = _read_rss_bytes,
                 clock: Callable[[], float] = time.monotonic,
                 shedders: Optional[Sequence[Callable[[], int]]] = None):
        from ..telemetry import REGISTRY
        self._registry = REGISTRY
        self.soft_bytes = (soft_bytes if soft_bytes is not None
                           else _env_bytes("TRANSMOGRIFAI_HOST_MEM_SOFT_BYTES"))
        self.hard_bytes = (hard_bytes if hard_bytes is not None
                           else _env_bytes("TRANSMOGRIFAI_HOST_MEM_HARD_BYTES"))
        self.interval_s = float(interval_s)
        self._rss = rss_reader
        self._clock = clock
        self._shedders = (shedders if shedders is not None
                          else _default_shedders())
        self.state = "ok"
        self.tripped = False
        self.last_rss = 0
        self._ticks = 0
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._registry.gauge("memory.watchdog_state",
                             lambda: self._STATE_CODES[self.state])

    # -- one synchronous supervision step ----------------------------------
    def tick(self) -> str:
        with self._lock:
            tick_no = self._ticks
            self._ticks += 1
        rss = 0
        try:
            maybe_inject("memory.host_pressure", key=tick_no)
            rss = int(self._rss())
        except InjectedFault:
            # injected chaos: behave exactly as a hard-watermark reading
            rss = (self.hard_bytes if self.hard_bytes is not None
                   else (self.soft_bytes or 0) + 1)
        self.last_rss = rss
        self._registry.gauge("memory.host_rss_bytes").set(rss)
        if self.hard_bytes is not None and rss >= self.hard_bytes:
            new = "hard"
        elif self.soft_bytes is not None and rss >= self.soft_bytes:
            new = "soft"
        else:
            new = "ok"
        if new != self.state:
            self._transition(new, rss)
        return self.state

    def _transition(self, new: str, rss: int) -> None:
        old, self.state = self.state, new
        try:
            from ..telemetry import event
            event("memory.watchdog", from_state=old, to_state=new,
                  rss_bytes=rss)
        except Exception:  # noqa: BLE001
            pass
        if new == "hard":
            self.tripped = True
            record_failure("memory", "degraded",
                           f"host RSS {rss} >= hard watermark "
                           f"{self.hard_bytes}",
                           point="memory.host_pressure", rss_bytes=rss)
            self._registry.counter("memory.host_hard_total").inc()
        elif new == "soft":
            shed = self._run_shedders()
            record_failure("memory", "shed",
                           f"host RSS {rss} >= soft watermark "
                           f"{self.soft_bytes}; shed {shed} bytes of "
                           "caches/queues",
                           point="memory.host_pressure", rss_bytes=rss,
                           shed_bytes=shed)
            self._registry.counter("memory.host_soft_total").inc()
        else:
            self.tripped = False
            record_failure("memory", "recovered",
                           f"host RSS {rss} back below the soft watermark",
                           point="memory.host_pressure", rss_bytes=rss)

    def _run_shedders(self) -> int:
        total = 0
        for shed in self._shedders:
            try:
                total += int(shed() or 0)
            except Exception:  # noqa: BLE001 — shedding is best-effort
                pass
        return total

    def check(self) -> None:
        """Raise typed :class:`HostMemoryPressure` if the hard watermark
        tripped and has not recovered — the governed-thread half of the
        watchdog (sweep boundaries call this via
        :func:`check_host_pressure`)."""
        if self.tripped:
            raise HostMemoryPressure(
                f"host RSS {self.last_rss} crossed the hard watermark "
                f"{self.hard_bytes} bytes")

    # -- background loop ---------------------------------------------------
    def start(self) -> "RssWatchdog":
        with self._lock:
            if self._thread is not None:
                return self
            self._stop.clear()
            self._thread = threading.Thread(target=self._loop, daemon=True,
                                            name="memory-rss-watchdog")
            self._thread.start()
        return self

    def _loop(self) -> None:
        while not self._stop.is_set():
            try:
                self.tick()
            except Exception:  # noqa: BLE001 — supervision must not die
                pass
            self._stop.wait(self.interval_s)

    def stop(self, timeout_s: float = 5.0) -> None:
        self._stop.set()
        with self._lock:
            t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout=timeout_s)


_WATCHDOG_LOCK = threading.Lock()
_WATCHDOG: Optional[RssWatchdog] = None


def install_watchdog(wd: Optional[RssWatchdog]) -> None:
    """Make ``wd`` the process-ambient watchdog (runner start/stop)."""
    global _WATCHDOG
    with _WATCHDOG_LOCK:
        _WATCHDOG = wd


def check_host_pressure() -> None:
    """Sweep-boundary hook: raises :class:`HostMemoryPressure` when the
    ambient watchdog's hard watermark has tripped; no-op otherwise."""
    with _WATCHDOG_LOCK:
        wd = _WATCHDOG
    if wd is not None:
        wd.check()


def watchdog_interval_s() -> float:
    """Background watchdog cadence (TRANSMOGRIFAI_RSS_WATCHDOG_S, default
    0 = no background thread; the watermarks still work synchronously for
    an explicitly-constructed watchdog)."""
    try:
        return float(os.environ.get("TRANSMOGRIFAI_RSS_WATCHDOG_S", "0"))
    except ValueError:
        return 0.0


def memory_aux() -> Dict[str, Any]:
    """Bench/artifact block: the plan that ran, the budget it ran under,
    and what the ladder did — so every BENCH attempt documents itself."""
    plan = last_plan()
    out: Dict[str, Any] = {
        "governor_enabled": memory_governor_enabled(),
        "device_budget_bytes": device_memory_budget(),
        "plan": plan.to_json() if plan is not None else None,
        "shrink_level": shrink_level(),
    }
    try:
        from ..telemetry import REGISTRY
        snap = REGISTRY.snapshot()
        out["shrinks_total"] = snap["counters"].get(
            "memory.shrinks_total", 0)
        # prefer the watchdog's last observation; fall back to a direct
        # read so artifacts document RSS even when no watchdog is running
        out["host_rss_bytes"] = (snap["gauges"].get("memory.host_rss_bytes")
                                 or _read_rss_bytes() or None)
    except Exception:  # noqa: BLE001
        pass
    return out

"""Profiling / observability — the OpSparkListener equivalent (reference:
utils/src/main/scala/com/salesforce/op/utils/spark/OpSparkListener.scala:62:
per-stage executor run time, GC time, IO bytes, cumulative metrics, and
AppMetrics delivered to completion handlers).

TPU translation (SURVEY §5): per-phase wall-clock + device memory stats from
``jax.local_devices()[0].memory_stats()``, optional ``jax.profiler`` trace
capture, all emitted as structured JSON.
"""

from __future__ import annotations

import bisect
import contextlib
import json
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

# -- host-link transfer accounting (≙ the listener's IO byte counters) ------
# Incremented at the transfer chokepoints (columns.to_device_f32 cache
# misses, packed token-id prefetch, fused-program wire args); PhaseTimer
# snapshots it per phase.  TRACKED transfers only — implicit jit-arg copies
# of small arrays are not counted.
_HOST_LINK_BYTES = [0]


def add_host_link_bytes(n: int) -> None:
    _HOST_LINK_BYTES[0] += int(n)


def host_link_bytes() -> int:
    return _HOST_LINK_BYTES[0]


# -- compile-vs-execute attribution (ISSUE 4) -------------------------------
# jax.monitoring streams every backend compile (and, with a persistent
# compilation cache configured, every cache hit/miss) through process-global
# listeners.  The counters below let PhaseTimer split a phase's wall into
# "seconds spent inside XLA compilation" vs everything else, and let the
# bench count NEW programs built this process (persistent-cache misses when
# the cache is on, raw backend compiles otherwise).
_COMPILE_DURATION_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

_COMPILE_LOCK = threading.Lock()
_COMPILE_INSTALL_LOCK = threading.Lock()
_COMPILE_STATS = {"compile_s": 0.0, "backend_compiles": 0,
                  "cache_hits": 0, "cache_misses": 0}
_COMPILE_LISTENERS_INSTALLED = [False]
# cache hits seen by THIS thread: jax fires the event synchronously on the
# compiling thread, so a before/after read brackets exactly the compiles a
# call made (aot_registry uses it to tell a cache-LOADED executable from one
# built here)
_THREAD_CACHE_HITS = threading.local()


def install_compile_listeners() -> bool:
    """Register the jax.monitoring listeners feeding ``compile_stats``.
    Idempotent.  Called from package import; also from the accessors so a
    bare ``import profiling`` works.
    Registration is double-checked under an install lock: jax.monitoring has
    no dedup, so two racing callers registering the same listeners would
    double-count every compile second from then on."""
    if _COMPILE_LISTENERS_INSTALLED[0]:
        return True
    from jax import monitoring

    def _on_duration(event: str, duration: float, **kw) -> None:
        if event == _COMPILE_DURATION_EVENT:
            with _COMPILE_LOCK:
                _COMPILE_STATS["compile_s"] += float(duration)
                _COMPILE_STATS["backend_compiles"] += 1

    def _on_event(event: str, **kw) -> None:
        if event == _CACHE_HIT_EVENT:
            with _COMPILE_LOCK:
                _COMPILE_STATS["cache_hits"] += 1
            _THREAD_CACHE_HITS.n = thread_cache_hits() + 1
        elif event == _CACHE_MISS_EVENT:
            with _COMPILE_LOCK:
                _COMPILE_STATS["cache_misses"] += 1

    with _COMPILE_INSTALL_LOCK:
        if _COMPILE_LISTENERS_INSTALLED[0]:
            return True
        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
        _COMPILE_LISTENERS_INSTALLED[0] = True
    return True


def thread_cache_hits() -> int:
    """Persistent-compile-cache hits the calling thread has taken so far."""
    return getattr(_THREAD_CACHE_HITS, "n", 0)


def compile_stats() -> Dict[str, float]:
    install_compile_listeners()
    return dict(_COMPILE_STATS)


def compile_seconds() -> float:
    install_compile_listeners()
    return float(_COMPILE_STATS["compile_s"])


def new_compile_count() -> int:
    """Programs newly BUILT this process.  With a persistent compilation
    cache configured this is the miss count (a hit retrieves a prior build —
    its small backend_compile_duration is retrieval, not compilation);
    without one every backend compile is a fresh build."""
    install_compile_listeners()
    import jax
    if jax.config.jax_compilation_cache_dir:
        return int(_COMPILE_STATS["cache_misses"])
    return int(_COMPILE_STATS["backend_compiles"])


# -- selector racing accounting (ISSUE 4) -----------------------------------
# Fold-fits the successive-halving sweep did NOT run (pruned grid points ×
# remaining folds).  Reset at bench-workload boundaries.
RACING_STATS = {"cv_fits_saved": 0, "families_raced": 0, "points_pruned": 0}


def record_racing(fits_saved: int, points_pruned: int) -> None:
    RACING_STATS["cv_fits_saved"] += int(fits_saved)
    RACING_STATS["families_raced"] += 1
    RACING_STATS["points_pruned"] += int(points_pruned)


def racing_stats() -> Dict[str, int]:
    return dict(RACING_STATS)


def reset_racing_stats() -> None:
    for k in RACING_STATS:
        RACING_STATS[k] = 0


# -- XLA program cost registry -----------------------------------------------
# When TRANSMOGRIFAI_COST_ANALYSIS=1, the dominant compiled programs record
# their XLA cost analysis (flops / bytes accessed) here, once per program
# name; bench.py turns them into achieved-FLOP/s roofline fields.
PROGRAM_COSTS: Dict[str, Dict[str, Any]] = {}

# name → jax Lowered, captured inline at near-zero cost and resolved to a
# PROGRAM_COSTS entry by flush_program_costs() OUTSIDE any timed wall
_PENDING_COSTS: Dict[str, Any] = {}


def cost_analysis_enabled() -> bool:
    return os.environ.get("TRANSMOGRIFAI_COST_ANALYSIS") == "1"


def record_program_cost(name: str, jitted_fn, args=(), kwargs=None) -> None:
    """Best-effort XLA cost analysis of ``jitted_fn`` at ``args``' shapes.
    Only the cheap ``lower()`` trace happens here (a Lowered holds shapes,
    not argument buffers); the compile()+cost_analysis() pass is deferred to
    ``flush_program_costs`` so enabling TRANSMOGRIFAI_COST_ANALYSIS=1 does
    not add analysis time inside a caller's timed wall."""
    if (not cost_analysis_enabled() or name in PROGRAM_COSTS
            or name in _PENDING_COSTS):
        return
    try:
        _PENDING_COSTS[name] = jitted_fn.lower(*args, **(kwargs or {}))
    except Exception:  # noqa: BLE001 — diagnostics must never break a fit
        pass


def flush_program_costs() -> None:
    """Resolve pending lowerings into PROGRAM_COSTS entries.  The explicit
    compile() hits the in-process/persistent compile cache (the caller
    already executed the program), so the cost is one analysis pass, not a
    recompile.  Call after the timed region ends."""
    while _PENDING_COSTS:
        name, lowered = _PENDING_COSTS.popitem()
        if name in PROGRAM_COSTS:
            continue
        try:
            ca = lowered.compile().cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            PROGRAM_COSTS[name] = {
                "flops": float(ca.get("flops", 0.0)),
                "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
            }
        except Exception:  # noqa: BLE001 — diagnostics only
            pass


def clear_program_costs() -> None:
    """Reset both resolved and pending cost records (workload boundaries)."""
    PROGRAM_COSTS.clear()
    _PENDING_COSTS.clear()


class LatencyHistogram:
    """Thread-safe latency sketch for the serving layer: fixed log-spaced
    bucket counters (Prometheus-style cumulative buckets) plus exact
    count/sum.  Quantiles interpolate inside the winning bucket — a bounded
    ~5% relative error, no per-observation storage, O(1) record."""

    # 100 µs → ~100 s, ×1.3 per bucket: 54 bounds
    _BOUNDS = tuple(1e-4 * (1.3 ** i) for i in range(54))

    def __init__(self):
        self._lock = threading.Lock()
        self._counts = [0] * (len(self._BOUNDS) + 1)
        self._count = 0
        self._sum = 0.0
        self._min: Optional[float] = None
        self._max: Optional[float] = None
        # OpenMetrics exemplars: last {traceId, value} per bucket plus the
        # overall last — a p99 spike in Prometheus links to a concrete trace
        self._bucket_exemplars: Dict[int, Dict[str, Any]] = {}
        self._last_exemplar: Optional[Dict[str, Any]] = None

    def observe(self, seconds: float,
                trace_id: Optional[str] = None) -> None:
        """Record one observation.  Every mutation — bucket increment,
        count/sum, min/max — happens under the instance lock, so concurrent
        server threads never lose an update.  ``trace_id`` (when the request
        carried one) is remembered as the bucket's exemplar."""
        s = float(seconds)
        i = bisect.bisect_left(self._BOUNDS, s)
        with self._lock:
            self._counts[i] += 1
            self._count += 1
            self._sum += s
            if self._min is None or s < self._min:
                self._min = s
            if self._max is None or s > self._max:
                self._max = s
            if trace_id:
                ex = {"traceId": trace_id, "value": s}
                self._bucket_exemplars[i] = ex
                self._last_exemplar = ex

    def exemplar(self, slowest: bool = False) -> Optional[Dict[str, Any]]:
        """The exemplar to attach to a rendered sample: the last traced
        observation, or with ``slowest=True`` the one from the highest
        occupied bucket (the trace a p99 spike points at).  None when no
        traced observation has landed yet."""
        with self._lock:
            if not self._bucket_exemplars:
                return None
            if slowest:
                return dict(self._bucket_exemplars[
                    max(self._bucket_exemplars)])
            return dict(self._last_exemplar) \
                if self._last_exemplar else None

    @property
    def count(self) -> int:
        with self._lock:
            return self._count

    @property
    def sum(self) -> float:
        with self._lock:
            return self._sum

    def quantile(self, q: float) -> Optional[float]:
        """q-quantile estimate.  Empty → None; q<=0 → exact min; q>=1 →
        exact max; bucket-interpolated results are clamped into [min, max],
        so a single observation returns that exact value for any q."""
        with self._lock:
            total = self._count
            counts = list(self._counts)
            mn, mx = self._min, self._max
        if total == 0:
            return None
        if q <= 0.0:
            return mn
        if q >= 1.0:
            return mx
        target = q * total
        seen = 0.0
        est = self._BOUNDS[-1]
        for i, c in enumerate(counts):
            if c == 0:
                continue
            lo = self._BOUNDS[i - 1] if i > 0 else 0.0
            hi = self._BOUNDS[i] if i < len(self._BOUNDS) else lo * 1.3
            if seen + c >= target:
                frac = (target - seen) / c
                est = lo + (hi - lo) * frac
                break
            seen += c
        return min(max(est, mn), mx)

    def snapshot(self) -> Dict[str, Optional[float]]:
        return {"count": self.count, "sum": round(self.sum, 6),
                "p50": self.quantile(0.50), "p95": self.quantile(0.95),
                "p99": self.quantile(0.99)}


@dataclass
class PhaseMetrics:
    """≙ StageMetrics (OpSparkListener.scala)."""
    name: str
    wall_s: float
    device_bytes_in_use: Optional[int] = None
    peak_bytes_in_use: Optional[int] = None
    host_link_bytes: Optional[int] = None
    compile_s: Optional[float] = None   # XLA compile seconds inside the phase

    def to_json(self) -> Dict[str, Any]:
        return {"name": self.name, "wallSeconds": round(self.wall_s, 4),
                "deviceBytesInUse": self.device_bytes_in_use,
                "peakBytesInUse": self.peak_bytes_in_use,
                "hostLinkBytes": self.host_link_bytes,
                "compileSeconds": (None if self.compile_s is None
                                   else round(self.compile_s, 4))}


@dataclass
class AppMetrics:
    """≙ AppMetrics (OpSparkListener.scala:146 MetricJsonLike)."""
    app_tag: Optional[str]
    total_wall_s: float
    phases: List[PhaseMetrics] = field(default_factory=list)

    def to_json(self) -> Dict[str, Any]:
        return {"appTag": self.app_tag,
                "totalWallSeconds": round(self.total_wall_s, 4),
                "phases": [p.to_json() for p in self.phases]}

    def log_pretty(self) -> str:
        lines = [f"App metrics{f' [{self.app_tag}]' if self.app_tag else ''}: "
                 f"{self.total_wall_s:.2f}s total"]
        for p in self.phases:
            mem = (f", {p.peak_bytes_in_use / 2**20:.0f} MiB peak"
                   if p.peak_bytes_in_use else "")
            lines.append(f"  {p.name}: {p.wall_s:.2f}s{mem}")
        return "\n".join(lines)


def _device_memory() -> Dict[str, Optional[int]]:
    try:
        import jax
        stats = jax.local_devices()[0].memory_stats() or {}
        return {"bytes_in_use": stats.get("bytes_in_use"),
                "peak_bytes_in_use": stats.get("peak_bytes_in_use")}
    except Exception:
        return {"bytes_in_use": None, "peak_bytes_in_use": None}


class PhaseTimer:
    """Collects per-phase timings; nested phases are recorded flat."""

    def __init__(self):
        self.phases: List[PhaseMetrics] = []
        self._t0 = time.time()

    @contextlib.contextmanager
    def phase(self, name: str):
        # late import: telemetry imports profiling, so the reverse edge must
        # stay out of module load.  span() is a no-op without a tracer.
        from .obsv import BOARD
        from .telemetry import span as _span
        t0 = time.time()
        link0 = host_link_bytes()
        compile0 = compile_seconds()
        # training control plane: the phase boundary is the coarsest
        # progress seam — /statusz shows it live.  A dict merge, no span.
        BOARD.publish(phase=name)
        try:
            with _span(f"phase.{name}"):
                yield
        finally:
            mem = _device_memory()
            self.phases.append(PhaseMetrics(
                name, time.time() - t0,
                device_bytes_in_use=mem["bytes_in_use"],
                peak_bytes_in_use=mem["peak_bytes_in_use"],
                host_link_bytes=host_link_bytes() - link0,
                compile_s=compile_seconds() - compile0))
            BOARD.publish(phase=f"{name}:done",
                          phaseWallS=round(time.time() - t0, 3))

    def app_metrics(self, tag: Optional[str] = None) -> AppMetrics:
        return AppMetrics(tag, time.time() - self._t0, list(self.phases))


@contextlib.contextmanager
def profiler_trace(log_dir: str):
    """Wrap a block in a jax.profiler trace (≙ the listener's event capture);
    view with tensorboard or xprof."""
    import jax
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()

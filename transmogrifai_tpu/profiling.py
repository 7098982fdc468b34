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
_TRACE_DURATION_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_DURATION_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE_DURATION_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
# the three steps a jit call pays before its first dispatch, by the event
# jax times each with; the value is the step's name in ``program_stats``
# rows (``<step>s``, ``<step>_s``) and in the ``jit.<step>`` trace events
_JIT_STEPS = {_TRACE_DURATION_EVENT: "trace", _LOWER_DURATION_EVENT: "lower",
              _COMPILE_DURATION_EVENT: "compile"}

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
# per-program table behind ``program_stats()``: what each jitted function
# (jax's ``fun_name``) cost this process to trace, lower and compile or load
_PROGRAM_STATS: Dict[str, Dict[str, Any]] = {}
# jit steps OPEN on this thread, outermost first: jax announces a step's
# start (a scalar event) and its duration (at its end) on the thread that
# runs it, so a step that ends while another is open ran INSIDE that one —
# a jit traced inside another trace, an eager op compiled while tracing.
# Its seconds are already in the outer step's, so only the outermost step
# adds seconds to the table or becomes a trace event.
_OPEN_JIT_STEPS = threading.local()


def _program_name(kw: Dict[str, Any]) -> str:
    """jax names a trace by the function (``traced``) and its lowering and
    compile by the module (``jit(traced)``): one name, the module's."""
    name = str(kw.get("fun_name", "?"))
    return name if "(" in name else f"jit({name})"


def _program_row(fun_name: str) -> Dict[str, Any]:
    row = _PROGRAM_STATS.get(fun_name)
    if row is None:
        row = _PROGRAM_STATS[fun_name] = {
            "traces": 0, "trace_s": 0.0, "lowers": 0, "lower_s": 0.0,
            "compiles": 0, "compile_s": 0.0, "cache_hits": 0, "nested": 0}
    return row


def install_compile_listeners() -> bool:
    """Register the jax.monitoring listeners feeding ``compile_stats`` and
    ``program_stats``.  Idempotent.  Called from package import; also from
    the accessors so a bare ``import profiling`` works.
    Registration is double-checked under an install lock: jax.monitoring has
    no dedup, so two racing callers registering the same listeners would
    double-count every compile second from then on."""
    if _COMPILE_LISTENERS_INSTALLED[0]:
        return True
    from jax import monitoring

    def _on_step_start(event: str, value: float, **kw) -> None:
        if event in _JIT_STEPS:
            stack = getattr(_OPEN_JIT_STEPS, "stack", None)
            if stack is None:
                stack = _OPEN_JIT_STEPS.stack = []
            stack.append((event, _program_name(kw), thread_cache_hits()))

    def _on_duration(event: str, duration: float, **kw) -> None:
        step = _JIT_STEPS.get(event)
        if step is None:
            return
        seconds = float(duration)
        if event == _COMPILE_DURATION_EVENT:
            with _COMPILE_LOCK:
                _COMPILE_STATS["compile_s"] += seconds
                _COMPILE_STATS["backend_compiles"] += 1
        stack = getattr(_OPEN_JIT_STEPS, "stack", None)
        opened = stack.pop() if stack else None
        if opened is not None and opened[0] != event:
            # starts and ends out of step (a listener installed mid-call):
            # forget what is open rather than nest under a stale entry
            del stack[:]
            opened = None
        if stack:
            with _COMPILE_LOCK:
                _program_row(stack[0][1])["nested"] += 1
            return
        fun_name = _program_name(kw)
        cache_hit = None
        if step == "compile" and opened is not None:
            cache_hit = thread_cache_hits() > opened[2]
        with _COMPILE_LOCK:
            row = _program_row(fun_name)
            row[step + "s"] += 1
            row[step + "_s"] += seconds
            row["cache_hits"] += bool(cache_hit)
        # telemetry imports this module, so the edge back stays out of
        # module load; event() is a no-op without a tracer
        from .telemetry import event as _event
        _event("jit." + step, fun_name=fun_name, seconds=seconds,
               cache_hit=cache_hit)

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
        monitoring.register_scalar_listener(_on_step_start)
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


def program_stats() -> Dict[str, Dict[str, Any]]:
    """{fun_name: traces, trace_s, lowers, lower_s, compiles, compile_s,
    cache_hits, nested} since the process started: the jit steps jax timed,
    by the function jax names them for (``jit(traced)``, ``_col_stats``).  A
    compile that loaded from the persistent cache counts as a compile and as
    a cache hit.  ``nested`` counts the steps that ran inside one of this
    program's own (their seconds are in this row's already, and in no row of
    their own), so the seconds of all rows add up to wall time spent in jit
    machinery; ``compile_stats()`` keeps every backend compile, nested or
    not.  Two snapshots bracket a stretch of work: subtract them."""
    install_compile_listeners()
    with _COMPILE_LOCK:
        return {k: dict(v) for k, v in _PROGRAM_STATS.items()}


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


def device_peak_bytes() -> List[Optional[int]]:
    """``peak_bytes_in_use`` of every local device, in ``jax.local_devices()``
    order; None where the backend reports no memory statistics (the CPU's)."""
    try:
        import jax
        return [(d.memory_stats() or {}).get("peak_bytes_in_use")
                for d in jax.local_devices()]
    except Exception:
        return []


class PhaseTimer:
    """Collects per-phase timings; nested phases are recorded flat.  Walls
    are read from ``time.monotonic()``, the clock ``telemetry.Tracer`` stamps
    its spans with: a phase and the ``phase.<name>`` span it opens agree, and
    neither moves when the wall clock is stepped."""

    def __init__(self):
        self.phases: List[PhaseMetrics] = []
        self._t0 = time.monotonic()

    @contextlib.contextmanager
    def phase(self, name: str):
        # late import: telemetry imports profiling, so the reverse edge must
        # stay out of module load.  span() is a no-op without a tracer.
        from .obsv import BOARD
        from .telemetry import span as _span
        t0 = time.monotonic()
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
                name, time.monotonic() - t0,
                device_bytes_in_use=mem["bytes_in_use"],
                peak_bytes_in_use=mem["peak_bytes_in_use"],
                host_link_bytes=host_link_bytes() - link0,
                compile_s=compile_seconds() - compile0))
            BOARD.publish(phase=f"{name}:done",
                          phaseWallS=round(time.monotonic() - t0, 3))

    def app_metrics(self, tag: Optional[str] = None) -> AppMetrics:
        return AppMetrics(tag, time.monotonic() - self._t0, list(self.phases))


# profiler_trace() contexts open right now: while there is one, every span
# the ambient tracer opens is also written into the profiler's own trace
_PROFILER_TRACES_OPEN = [0]


def span_annotation(name: str):
    """A ``jax.profiler.TraceAnnotation`` named like the span being opened,
    when a ``profiler_trace`` is open; ``None`` (and no jax import) when none
    is.  ``Tracer.span`` enters it beside the span."""
    if not _PROFILER_TRACES_OPEN[0]:
        return None
    import jax
    return jax.profiler.TraceAnnotation(name)


@contextlib.contextmanager
def profiler_trace(log_dir: str):
    """Wrap a block in a jax.profiler trace (≙ the listener's event capture);
    view with tensorboard or xprof.  Every span the ambient tracer opens
    inside the block is also written as a ``TraceAnnotation`` of the same
    name, so the program's spans lie in the ``.xplane.pb`` itself, on the
    host plane over the device operations they caused."""
    import jax
    jax.profiler.start_trace(log_dir)
    _PROFILER_TRACES_OPEN[0] += 1
    try:
        yield
    finally:
        _PROFILER_TRACES_OPEN[0] -= 1
        jax.profiler.stop_trace()

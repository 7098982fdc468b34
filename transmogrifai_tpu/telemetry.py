"""Unified telemetry — structured trace spans + a central metrics registry.

The runtime story used to be scattered: compile stats, racing counters,
host-link bytes, serving latency histograms and the FailureLog each lived in
their own ad-hoc global with no shared run context.  This module gives every
run one measurement substrate, in the style of Dapper/OpenTelemetry span
trees and Chrome ``chrome://tracing`` timelines:

* ``Tracer`` — thread-safe producer of nested spans.  ``tracer.span(name,
  **attrs)`` is a context manager recording monotonic wall times, a span id,
  the parent span id, a status (``ok``/``error``) and attributes.  Parenting
  is per-thread (each thread nests its own spans); a worker thread with no
  open span of its own parents to the innermost open span of the thread that
  installed the tracer — so the validator's thread-pool candidate fits nest
  under the orchestrating ``selector.sweep`` span.
* ``use_tracer(tracer)`` — the ambient run context, mirroring
  ``resilience.use_failure_log``: deep code calls the module-level
  ``span(...)`` / ``event(...)`` helpers, which no-op (near-zero cost) when
  no tracer is installed.
* ``MetricsRegistry`` — named ``Counter``s, ``Gauge``s and
  ``LatencyHistogram``s behind one namespace.  The process-default
  ``REGISTRY`` absorbs and re-exports today's scattered sources
  (``profiling.compile_stats``, ``profiling.racing_stats``,
  ``profiling.host_link_bytes``) as read-through gauges, so one
  ``snapshot()`` answers "what did this process compile/prune/transfer".
* Exports — ``tracer.export_chrome_trace(path)`` writes Perfetto-loadable
  Chrome trace-event JSON; ``telemetry_summary()`` builds the
  ``telemetry.json`` bundled next to saved models and into bench aux;
  ``render_trace_summary()`` prints the top-N slowest-spans table behind the
  ``transmogrifai_tpu trace-summary`` subcommand.

Span ids correlate with the failure layer: ``resilience.FailureLog.record``
stamps the recording thread's active span id into each event's detail, and
``FaultInjector`` remembers the span each injected fault fired inside — a
chaos-test failure points at the exact span.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import json
import os
import re
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterable, List, Optional, Union

from .profiling import (LatencyHistogram, compile_stats, host_link_bytes,
                        program_stats, racing_stats, span_annotation)

__all__ = [
    "Span", "Tracer", "TraceContext", "TRACEPARENT_ENV", "use_tracer",
    "active_tracer", "span", "event", "current_span_id",
    "current_trace_context", "Counter", "Gauge", "MetricsRegistry",
    "REGISTRY", "LatencyHistogram", "span_profile", "subtree",
    "publish_train_profile", "telemetry_summary",
    "write_telemetry_summary", "render_trace_summary", "load_trace",
    "merge_traces",
]


# --------------------------------------------------------------------------
# W3C trace context
# --------------------------------------------------------------------------

#: Env var carrying the parent ``traceparent`` into supervised children
#: (probe subprocesses, chaos children, pool workers, lifecycle retrains).
TRACEPARENT_ENV = "TRANSMOGRIFAI_TRACEPARENT"

_TRACEPARENT_RE = re.compile(
    r"^00-([0-9a-f]{32})-([0-9a-f]{16})-([0-9a-f]{2})$")

#: Hard cap on accepted header length — anything longer is dropped without
#: even running the regex (oversized headers must never cost a 500).
_TRACEPARENT_MAX_LEN = 64


@dataclass(frozen=True)
class TraceContext:
    """A W3C trace-context position: the 128-bit ``trace_id`` every span in
    one distributed request shares, plus the 64-bit ``span_id`` of the
    current position in the tree (both lowercase hex).  Frozen — deriving a
    child position returns a new instance."""

    trace_id: str
    span_id: str
    flags: int = 1          # 01 = sampled; we always record

    @staticmethod
    def new() -> "TraceContext":
        """A fresh root context (random 128-bit trace / 64-bit span id)."""
        return TraceContext(trace_id=os.urandom(16).hex(),
                            span_id=os.urandom(8).hex())

    def child(self) -> "TraceContext":
        """Same trace, fresh span id — the position handed to a callee."""
        return TraceContext(trace_id=self.trace_id,
                            span_id=os.urandom(8).hex(),
                            flags=self.flags)

    def to_traceparent(self) -> str:
        """Serialize as a W3C ``traceparent`` header value."""
        return f"00-{self.trace_id}-{self.span_id}-{self.flags:02x}"

    @staticmethod
    def parse(header: Optional[str]) -> Optional["TraceContext"]:
        """Strict W3C parse.  Malformed, oversized, wrong-version or
        all-zero-id headers return None — callers fall back to a fresh
        context; a bad header must never break a request."""
        if not header or not isinstance(header, str):
            return None
        header = header.strip()
        if len(header) > _TRACEPARENT_MAX_LEN:
            return None
        # no .lower(): the W3C grammar is lowercase-only, and uppercase hex
        # is specified as invalid rather than normalizable
        m = _TRACEPARENT_RE.match(header)
        if m is None:
            return None
        trace_id, span_id, flags = m.group(1), m.group(2), m.group(3)
        if trace_id == "0" * 32 or span_id == "0" * 16:
            return None
        return TraceContext(trace_id=trace_id, span_id=span_id,
                            flags=int(flags, 16))

    @staticmethod
    def from_env() -> Optional["TraceContext"]:
        """Parse the context a parent process exported for us, if any."""
        return TraceContext.parse(os.environ.get(TRACEPARENT_ENV))


# --------------------------------------------------------------------------
# spans
# --------------------------------------------------------------------------

@dataclass
class Span:
    """One timed unit of work in the trace tree."""

    name: str
    span_id: str
    parent_id: Optional[str]
    start_s: float              # monotonic, relative to the tracer's epoch
    end_s: Optional[float] = None
    status: str = "ok"          # "ok" | "error"
    attrs: Dict[str, Any] = field(default_factory=dict)
    thread: int = 0
    start_wall_s: float = 0.0   # absolute wall clock at span start
    trace_id: str = ""          # W3C 128-bit trace id (hex)
    w3c_id: str = ""            # W3C 64-bit span id (hex)
    links: List[Dict[str, str]] = field(default_factory=list)

    @property
    def duration_s(self) -> float:
        return (self.end_s if self.end_s is not None else self.start_s) \
            - self.start_s

    def context(self) -> TraceContext:
        """This span's position as a propagatable TraceContext."""
        return TraceContext(trace_id=self.trace_id, span_id=self.w3c_id)

    def to_json(self) -> Dict[str, Any]:
        out = {"name": self.name, "spanId": self.span_id,
               "parentId": self.parent_id,
               "startS": round(self.start_s, 6),
               "durationS": round(self.duration_s, 6),
               "status": self.status, "attrs": dict(self.attrs),
               "thread": self.thread,
               "startWallS": round(self.start_wall_s, 3),
               "traceId": self.trace_id, "w3cSpanId": self.w3c_id}
        if self.links:
            out["links"] = [dict(l) for l in self.links]
        return out


def _proc_label(run_name: str, worker_id, rank) -> str:
    """Perfetto process-lane label: run name plus whichever identities
    apply — serving-pool worker id and/or host-group rank."""
    label = run_name
    if worker_id is not None:
        label += f" [worker {worker_id}]"
    if rank is not None:
        label += f" [rank {rank}]"
    return label


_NO_ANNOTATION = contextlib.nullcontext()


class Tracer:
    """Thread-safe span collector.  See module docstring for the parenting
    rule; all mutation happens under one lock, so concurrent serving/
    validator threads can record freely."""

    #: Default span ring-buffer bound: a serving process records forever,
    #: so the completed-span store must not grow without bound.
    DEFAULT_MAX_SPANS = 65536

    def __init__(self, run_name: str = "run", *,
                 max_spans: Optional[int] = None,
                 parent: Optional[TraceContext] = None,
                 worker_id: Optional[str] = None,
                 rank: Optional[int] = None):
        self.run_name = run_name
        self._lock = threading.Lock()
        self._ids = itertools.count(1)
        # completed spans, finish order; bounded ring (oldest dropped first)
        self._spans: "collections.deque[Span]" = collections.deque()
        self._stacks: Dict[int, List[Span]] = {}   # open spans per thread
        self._install_thread: Optional[int] = None
        self.t0_mono = time.monotonic()
        self.t0_wall = time.time()
        if max_spans is None:
            try:
                max_spans = int(os.environ.get(
                    "TRANSMOGRIFAI_TRACE_MAX_SPANS", self.DEFAULT_MAX_SPANS))
            except ValueError:
                max_spans = self.DEFAULT_MAX_SPANS
        self.max_spans = max(1, max_spans)
        self._dropped = 0
        self._drop_noted = False
        self.parent_ctx = parent
        self.worker_id = worker_id
        # host-group rank (multi-process training); like worker_id it rides
        # the exports so merge_traces can label one lane per host
        self.rank = rank
        # every span this tracer records shares one trace id unless an
        # explicit per-request ctx overrides it
        self.trace_id = parent.trace_id if parent else os.urandom(16).hex()
        self._root_w3c = parent.span_id if parent else os.urandom(8).hex()

    def root_context(self) -> TraceContext:
        """The tracer-level context new work inherits when no request
        context is active (the parent ctx we were seeded with, else the
        tracer's own root position)."""
        if self.parent_ctx is not None:
            return self.parent_ctx
        return TraceContext(trace_id=self.trace_id, span_id=self._root_w3c)

    @property
    def spans_dropped(self) -> int:
        with self._lock:
            return self._dropped

    def _record_locked(self, sp: Span) -> int:
        """Append a completed span, evicting the oldest past the bound.
        Caller holds ``self._lock``; returns how many spans were evicted
        (the drop NOTE must be emitted after the lock is released —
        ``record_failure`` re-enters this tracer via ``current_span_id``)."""
        self._spans.append(sp)
        dropped = 0
        while len(self._spans) > self.max_spans:
            self._spans.popleft()
            dropped += 1
        self._dropped += dropped
        return dropped

    def _note_drops(self, dropped: int) -> None:
        """Post-lock bookkeeping for evicted spans: bump the global drop
        counter and, on the FIRST drop this tracer sees, record a degraded
        note so operators learn the trace is now a ring, not a log."""
        if dropped <= 0:
            return
        REGISTRY.counter("telemetry.spans_dropped_total").inc(dropped)
        with self._lock:
            first = not self._drop_noted
            self._drop_noted = True
        if first:
            try:
                # lazy import — telemetry must stay import-light here
                from .resilience import record_failure
                record_failure(
                    "telemetry", "degraded", "span ring buffer full",
                    point="tracer.max_spans", run_name=self.run_name,
                    max_spans=self.max_spans)
            except Exception:  # noqa: BLE001 — never fail a span close
                pass

    # -- parenting ---------------------------------------------------------
    def _parent(self, tid: int) -> Optional[Span]:
        stack = self._stacks.get(tid)
        if stack:
            return stack[-1]
        if self._install_thread is not None:
            root = self._stacks.get(self._install_thread)
            if root:
                return root[-1]
        return None

    def current_span(self) -> Optional[Span]:
        """The calling thread's innermost open span (falling back to the
        install thread's — the span a worker's work is logically inside)."""
        with self._lock:
            return self._parent(threading.get_ident())

    def current_span_id(self) -> Optional[str]:
        s = self.current_span()
        return s.span_id if s is not None else None

    # -- recording ---------------------------------------------------------
    @contextlib.contextmanager
    def span(self, name: str, *, ctx: Optional[TraceContext] = None,
             links: Optional[List[TraceContext]] = None, **attrs):
        """Record one span.  ``ctx`` pins the span to an explicit W3C trace
        position (request-scoped tracing across processes); ``links`` record
        causally-related-but-not-parent contexts (a batch span links every
        request it coalesced).  Without ``ctx`` the span rides the tracer's
        own trace id with a fresh 64-bit position."""
        tid = threading.get_ident()
        with self._lock:
            # the start read under the lock that finds the parent: a span
            # never starts before the parent another thread opened for it
            now = time.monotonic() - self.t0_mono
            parent = self._parent(tid)
            sp = Span(name=name, span_id=f"s{next(self._ids)}",
                      parent_id=parent.span_id if parent else None,
                      start_s=now, attrs=dict(attrs), thread=tid,
                      start_wall_s=time.time(),
                      trace_id=ctx.trace_id if ctx else self.trace_id,
                      w3c_id=ctx.span_id if ctx else os.urandom(8).hex(),
                      links=[{"traceId": l.trace_id, "spanId": l.span_id}
                             for l in (links or [])])
            self._stacks.setdefault(tid, []).append(sp)
        try:
            # inside profiling.profiler_trace the span also goes into the
            # profiler's own trace, under its name
            with span_annotation(name) or _NO_ANNOTATION:
                yield sp
        except BaseException as e:
            sp.status = "error"
            sp.attrs.setdefault("error", f"{type(e).__name__}: {e}")
            raise
        finally:
            sp.end_s = time.monotonic() - self.t0_mono
            with self._lock:
                stack = self._stacks.get(tid, [])
                for i in range(len(stack) - 1, -1, -1):
                    if stack[i] is sp:      # robust to interleaved exits
                        del stack[i]
                        break
                dropped = self._record_locked(sp)
            self._note_drops(dropped)

    def event(self, name: str, *, ctx: Optional[TraceContext] = None,
              **attrs) -> Span:
        """A zero-duration marker span (e.g. a racing prune decision)."""
        tid = threading.get_ident()
        with self._lock:
            now = time.monotonic() - self.t0_mono
            parent = self._parent(tid)
            sp = Span(name=name, span_id=f"s{next(self._ids)}",
                      parent_id=parent.span_id if parent else None,
                      start_s=now, end_s=now, attrs=dict(attrs), thread=tid,
                      start_wall_s=time.time(),
                      trace_id=ctx.trace_id if ctx else self.trace_id,
                      w3c_id=ctx.span_id if ctx else os.urandom(8).hex())
            dropped = self._record_locked(sp)
        self._note_drops(dropped)
        return sp

    @property
    def spans(self) -> List[Span]:
        """Completed spans (finish order); open spans are not included."""
        with self._lock:
            return list(self._spans)

    def __len__(self) -> int:
        with self._lock:
            return len(self._spans)

    # -- export ------------------------------------------------------------
    def to_json(self) -> Dict[str, Any]:
        return {"runName": self.run_name, "t0WallS": round(self.t0_wall, 3),
                "traceId": self.trace_id, "pid": os.getpid(),
                "workerId": self.worker_id, "rank": self.rank,
                "spansDropped": self.spans_dropped,
                "spans": [s.to_json() for s in self.spans]}

    def export_chrome_trace(self, path: str) -> str:
        """Write the trace in Chrome trace-event JSON ("X" complete events,
        microsecond timestamps) — loadable in Perfetto / chrome://tracing.
        Span ids and parent ids ride in ``args`` so the span tree survives
        the round trip (``load_trace`` reads them back).  Alongside the span
        events the export carries ``process_name`` metadata and a
        ``clock_sync`` event anchored at ``t0_wall`` — two independently
        exported traces align on a shared wall-clock timeline in Perfetto
        even without ``merge_traces``."""
        pid = os.getpid()
        proc_label = _proc_label(self.run_name, self.worker_id, self.rank)
        events: List[Dict[str, Any]] = [
            {"name": "process_name", "ph": "M", "pid": pid, "tid": 0,
             "args": {"name": proc_label}},
            # wall-clock anchor: issue_ts is the absolute wall time (µs) at
            # the tracer epoch (ts=0), so cross-process merges re-align by
            # shifting each file's events onto one wall timeline
            {"name": "clock_sync", "ph": "c", "pid": pid, "tid": 0,
             "ts": 0.0,
             "args": {"sync_id": self.trace_id,
                      "issue_ts": round(self.t0_wall * 1e6, 1)}},
        ]
        for s in self.spans:
            args = {"spanId": s.span_id, "parentId": s.parent_id,
                    "status": s.status, "traceId": s.trace_id,
                    "w3cSpanId": s.w3c_id, **s.attrs}
            if s.links:
                args["links"] = [dict(l) for l in s.links]
            events.append({
                "name": s.name, "cat": s.name.split(".", 1)[0], "ph": "X",
                "ts": round(s.start_s * 1e6, 1),
                "dur": round(max(s.duration_s, 0.0) * 1e6, 1),
                "pid": pid, "tid": s.thread, "args": args})
        doc = {"traceEvents": events, "displayTimeUnit": "ms",
               "otherData": {"runName": self.run_name,
                             "t0WallS": round(self.t0_wall, 3),
                             "traceId": self.trace_id, "pid": pid,
                             "workerId": self.worker_id, "rank": self.rank,
                             "spansDropped": self.spans_dropped}}
        with open(path, "w") as fh:
            json.dump(doc, fh, default=str)
        return path

    def slowest(self, top_n: int = 10) -> List[Span]:
        return sorted(self.spans, key=lambda s: -s.duration_s)[:top_n]


# --------------------------------------------------------------------------
# ambient tracer (mirrors resilience.use_failure_log)
# --------------------------------------------------------------------------

# Process-global stack, NOT thread-local: the validator's candidate fits run
# on a thread pool and must record into the tracer their orchestrating
# train() installed.  Concurrent *independent* traced runs in one process
# should pass explicit tracers instead.
_TRACER_STACK: List[Tracer] = []
_TRACER_LOCK = threading.Lock()


def active_tracer() -> Optional[Tracer]:
    """The innermost installed tracer, or None (spans become no-ops)."""
    with _TRACER_LOCK:
        return _TRACER_STACK[-1] if _TRACER_STACK else None


@contextlib.contextmanager
def use_tracer(tracer: Tracer):
    """Install ``tracer`` as the ambient tracer for the dynamic extent."""
    with _TRACER_LOCK:
        _TRACER_STACK.append(tracer)
        if tracer._install_thread is None:
            tracer._install_thread = threading.get_ident()
    try:
        yield tracer
    finally:
        with _TRACER_LOCK:
            for i in range(len(_TRACER_STACK) - 1, -1, -1):
                if _TRACER_STACK[i] is tracer:
                    del _TRACER_STACK[i]
                    break


@contextlib.contextmanager
def span(name: str, *, ctx: Optional[TraceContext] = None,
         links: Optional[List[TraceContext]] = None, **attrs):
    """Record a span on the ambient tracer; a no-op (one attribute check)
    when tracing is off — instrumentation sites pay nothing by default."""
    tracer = active_tracer()
    if tracer is None:
        yield None
        return
    with tracer.span(name, ctx=ctx, links=links, **attrs) as sp:
        yield sp


def event(name: str, *, ctx: Optional[TraceContext] = None,
          **attrs) -> Optional[Span]:
    """Record a zero-duration marker on the ambient tracer (None when off)."""
    tracer = active_tracer()
    if tracer is None:
        return None
    return tracer.event(name, ctx=ctx, **attrs)


def current_span_id() -> Optional[str]:
    """The calling thread's active span id on the ambient tracer, or None.
    ``resilience.FailureLog`` uses this to correlate failures with spans."""
    tracer = active_tracer()
    if tracer is None:
        return None
    return tracer.current_span_id()


def current_trace_context() -> Optional[TraceContext]:
    """The W3C position to propagate to a callee or child process right
    now: the innermost open span's context on the ambient tracer (falling
    back to the tracer root), else the context a parent process exported
    via ``TRANSMOGRIFAI_TRACEPARENT``, else None."""
    tracer = active_tracer()
    if tracer is not None:
        sp = tracer.current_span()
        if sp is not None and sp.trace_id and sp.w3c_id:
            return sp.context()
        return tracer.root_context()
    return TraceContext.from_env()


# --------------------------------------------------------------------------
# metrics registry
# --------------------------------------------------------------------------

class Counter:
    """Monotonic thread-safe counter.  ``inc(trace_id=...)`` remembers the
    last incrementing trace as an OpenMetrics exemplar (shed counters link
    a 429 spike straight to a concrete request trace)."""

    __slots__ = ("name", "_value", "_lock", "_exemplar")

    def __init__(self, name: str):
        self.name = name
        self._value = 0
        self._lock = threading.Lock()
        self._exemplar: Optional[Dict[str, Any]] = None

    def inc(self, n: Union[int, float] = 1,
            trace_id: Optional[str] = None) -> None:
        with self._lock:
            self._value += n
            if trace_id:
                self._exemplar = {"traceId": trace_id, "value": n}

    def exemplar(self) -> Optional[Dict[str, Any]]:
        with self._lock:
            return dict(self._exemplar) if self._exemplar else None

    @property
    def value(self) -> Union[int, float]:
        with self._lock:
            return self._value


class Gauge:
    """Point-in-time value: either set explicitly or read through a
    callback (for absorbing external sources like ``compile_stats``)."""

    __slots__ = ("name", "_value", "_fn", "_lock")

    def __init__(self, name: str, fn: Optional[Callable[[], Any]] = None):
        self.name = name
        self._value: Any = 0
        self._fn = fn
        self._lock = threading.Lock()

    def set(self, v: Any) -> None:
        with self._lock:
            self._value = v

    @property
    def value(self) -> Any:
        if self._fn is not None:
            try:
                return self._fn()
            except Exception:  # noqa: BLE001 — a dead source reads as 0
                return 0
        with self._lock:
            return self._value


class MetricsRegistry:
    """Central named-metric namespace: counters, gauges, latency
    histograms.  ``counter``/``gauge``/``histogram`` are get-or-create, so
    call sites never race on registration; ``snapshot()`` renders the whole
    registry as one JSON-safe dict."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: Dict[str, Counter] = {}
        self._gauges: Dict[str, Gauge] = {}
        self._histograms: Dict[str, LatencyHistogram] = {}

    def counter(self, name: str) -> Counter:
        with self._lock:
            c = self._counters.get(name)
            if c is None:
                c = self._counters[name] = Counter(name)
            return c

    def gauge(self, name: str,
              fn: Optional[Callable[[], Any]] = None) -> Gauge:
        with self._lock:
            g = self._gauges.get(name)
            if g is None:
                g = self._gauges[name] = Gauge(name, fn)
            elif fn is not None:
                g._fn = fn
            return g

    def histogram(self, name: str) -> LatencyHistogram:
        with self._lock:
            h = self._histograms.get(name)
            if h is None:
                h = self._histograms[name] = LatencyHistogram()
            return h

    def counters(self) -> Dict[str, Union[int, float]]:
        with self._lock:
            items = list(self._counters.items())
        return {k: c.value for k, c in items}

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            counters = list(self._counters.items())
            gauges = list(self._gauges.items())
            hists = list(self._histograms.items())
        return {
            "counters": {k: c.value for k, c in counters},
            "gauges": {k: g.value for k, g in gauges},
            "histograms": {k: h.snapshot() for k, h in hists},
        }


def _default_registry() -> MetricsRegistry:
    reg = MetricsRegistry()
    # read-through gauges over the legacy profiling globals: ONE namespace
    # re-exports every scattered counter without moving its source of truth
    # (jax.monitoring listeners keep writing into profiling._COMPILE_STATS)
    reg.gauge("compile.compile_s", lambda: compile_stats()["compile_s"])
    reg.gauge("compile.backend_compiles",
              lambda: compile_stats()["backend_compiles"])
    reg.gauge("compile.cache_hits", lambda: compile_stats()["cache_hits"])
    reg.gauge("compile.cache_misses",
              lambda: compile_stats()["cache_misses"])
    # the same listener's per-program table (a dict: JSON exports carry it,
    # the Prometheus text skips what is no number)
    reg.gauge("compile.programs", program_stats)
    reg.gauge("racing.cv_fits_saved",
              lambda: racing_stats()["cv_fits_saved"])
    reg.gauge("racing.families_raced",
              lambda: racing_stats()["families_raced"])
    reg.gauge("racing.points_pruned",
              lambda: racing_stats()["points_pruned"])
    reg.gauge("host_link.bytes", host_link_bytes)

    def _sparse_stat(key):
        def read():
            # lazy import: telemetry must not pull jax at module import
            from .sparse.transform import sparse_stats
            return sparse_stats()[key]
        return read

    reg.gauge("sparse.nnz_total", _sparse_stat("nnz_total"))
    reg.gauge("sparse.matrices", _sparse_stat("matrices"))
    reg.gauge("sparse.density", _sparse_stat("density"))

    def _dt_stat(key):
        def read():
            # lazy import: telemetry must not pull jax at module import
            from .parallel.device_table import device_table_stats
            return device_table_stats()[key]
        return read

    # one device data plane (ISSUE 19): DeviceTable sparse shipments —
    # logical rows shipped, real COO entries over the link, ladder pad
    # entries synthesized on-device, per-device shards assembled
    reg.gauge("device_table.tables", _dt_stat("tables"))
    reg.gauge("device_table.rows", _dt_stat("rows"))
    reg.gauge("device_table.nnz_streamed", _dt_stat("nnz_streamed"))
    reg.gauge("device_table.pad_entries", _dt_stat("pad_entries"))
    reg.gauge("device_table.shards", _dt_stat("shards"))

    def _stream_stat(key):
        def read():
            # lazy import: telemetry must not pull jax at module import
            from .parallel.streaming import streaming_stats
            return streaming_stats()[key]
        return read

    # mesh streaming (ISSUE 10): mesh.devices / mesh.chunk_bytes are set by
    # maybe_data_mesh / stream_to_device; peak staging + streamed pad rows
    # read through the streamer's own stats.  host_to_device_bytes_total is
    # a plain counter the streamer increments per chunk.
    reg.gauge("mesh.devices")
    reg.gauge("mesh.chunk_bytes")
    reg.counter("host_to_device_bytes_total")
    reg.gauge("mesh.peak_staging_bytes", _stream_stat("peak_staging_bytes"))
    reg.gauge("mesh.stream_chunks", _stream_stat("chunks"))
    reg.gauge("mesh.pad_rows_streamed", _stream_stat("pad_rows"))

    # device-runtime supervision (ISSUE 11): the heartbeat sets
    # supervisor.state (0 available / 1 degraded / 2 outage) and bumps the
    # outage/probe counters; watchdog.abandoned_total counts zombie worker
    # threads run_with_deadline left behind (the failure mode only the
    # subprocess supervisor can actually reclaim); multihost gauges are set
    # by init_distributed.
    reg.gauge("supervisor.state")
    reg.gauge("supervisor.last_probe_latency_s")
    reg.counter("supervisor.probes_total")
    reg.counter("supervisor.outages_total")
    reg.counter("supervisor.mesh_degrades_total")
    reg.counter("watchdog.abandoned_total")
    reg.gauge("multihost.process_count")
    reg.gauge("multihost.initialized")

    def _device_cap():
        # lazy import: telemetry must not pull jax at module import
        from .parallel.supervisor import device_cap
        c = device_cap()
        return -1 if c is None else c

    reg.gauge("supervisor.device_cap", _device_cap)
    return reg


#: Process-default registry.  Serving engines create their own instance per
#: engine (counters reset with the engine); train/bench report through this.
REGISTRY = _default_registry()


# --------------------------------------------------------------------------
# summaries + CLI rendering
# --------------------------------------------------------------------------

#: Names of the zero-duration events ``profiling``'s jit listener records
#: under the span that caused a trace, a lowering, or a compile or cache load.
JIT_EVENTS = ("jit.trace", "jit.lower", "jit.compile")


def _clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def span_profile(spans: Iterable[Span]) -> Dict[str, Dict[str, Any]]:
    """``{name: count, total_s, self_s, jit_s}`` over closed ``spans``.

    ``total_s`` adds the durations of the spans of that name.  ``self_s`` is
    a span's duration less the part of it its children cover: the children's
    union, clipped to the span, children on other threads included.  Every
    instant of a root span goes to exactly one span under it — the part of a
    child that outlives its parent goes to nobody, and where two siblings
    overlap (pool threads) the overlap is the one's that started first — so
    the self seconds of a subtree add up to its root's duration.  ``jit_s``
    is the seconds of the ``jit.*`` events recorded directly under spans of
    that name: how much of their time went into tracing, lowering, and
    compiling or loading programs."""
    spans = [s for s in spans if s.end_s is not None]
    ids = {s.span_id for s in spans}
    children: Dict[Optional[str], List[Span]] = {}
    for s in spans:
        parent = s.parent_id if s.parent_id in ids else None
        children.setdefault(parent, []).append(s)
    table: Dict[str, Dict[str, Any]] = {}

    def row(name: str) -> Dict[str, Any]:
        return table.setdefault(name, {"count": 0, "total_s": 0.0,
                                       "self_s": 0.0, "jit_s": 0.0})

    # (span, the stretches of time that are this span's to give away)
    todo = [(s, [(s.start_s, s.end_s)]) for s in children.get(None, [])]
    while todo:
        s, own = todo.pop()
        r = row(s.name)
        r["count"] += 1
        r["total_s"] += s.duration_s
        self_s = sum(b - a for a, b in own)
        reach = float("-inf")       # how far the siblings so far have got
        for c in sorted(children.get(s.span_id, []),
                        key=lambda c: (c.start_s, c.end_s)):
            if c.name in JIT_EVENTS:
                r["jit_s"] += float(c.attrs.get("seconds") or 0.0)
            # siblings come by start, so [c.start_s, reach) is taken already
            theirs = _clip(own, max(c.start_s, reach), c.end_s)
            reach = max(reach, c.end_s)
            self_s -= sum(b - a for a, b in theirs)
            todo.append((c, theirs))
        r["self_s"] += self_s
    return table


def subtree(spans: Iterable[Span], root: Span) -> List[Span]:
    """``root`` and the spans of ``spans`` that descend from it."""
    spans = list(spans)
    by_parent: Dict[Optional[str], List[Span]] = {}
    for s in spans:
        by_parent.setdefault(s.parent_id, []).append(s)
    out, todo = [root], [root.span_id]
    while todo:
        kids = by_parent.get(todo.pop(), [])
        out.extend(kids)
        todo.extend(k.span_id for k in kids)
    return out


def publish_train_profile(root: Span) -> None:
    """Set the gauge ``train.span_profile`` to the ``span_profile`` of the
    closed ``workflow.train`` span ``root`` and what ran under it: what
    ``telemetry.json``, ``GET /traces`` and the benchmark's readers read.
    Called only when a tracer gave ``train`` a span."""
    tracer = active_tracer()
    if tracer is not None:
        REGISTRY.gauge("train.span_profile").set(
            span_profile(subtree(tracer.spans, root)))


def telemetry_summary(tracer: Optional[Tracer] = None,
                      registry: Optional[MetricsRegistry] = None,
                      top_n: int = 15) -> Dict[str, Any]:
    """The ``telemetry.json`` payload: top slowest spans (with tree
    context), per-name counts, total, self and jit seconds (``span_profile``)
    and the full metrics snapshot.  Bundled next to saved models and
    embedded in bench aux."""
    tracer = tracer if tracer is not None else active_tracer()
    registry = registry if registry is not None else REGISTRY
    out: Dict[str, Any] = {"metrics": registry.snapshot()}
    if tracer is not None:
        spans = tracer.spans
        by_name: Dict[str, Dict[str, Any]] = {
            name: {"count": r["count"], "totalS": round(r["total_s"], 6),
                   "maxS": 0.0, "errors": 0,
                   "selfS": round(r["self_s"], 6),
                   "jitS": round(r["jit_s"], 6)}
            for name, r in span_profile(spans).items()}
        for s in spans:
            agg = by_name[s.name]
            agg["maxS"] = round(max(agg["maxS"], s.duration_s), 6)
            agg["errors"] += int(s.status == "error")
        out["trace"] = {
            "runName": tracer.run_name,
            "spanCount": len(spans),
            "slowestSpans": [s.to_json() for s in tracer.slowest(top_n)],
            "byName": by_name,
        }
    return out


def write_telemetry_summary(path: str,
                            tracer: Optional[Tracer] = None,
                            registry: Optional[MetricsRegistry] = None
                            ) -> str:
    with open(path, "w") as fh:
        json.dump(telemetry_summary(tracer, registry), fh, indent=2,
                  default=str)
    return path


def load_trace(path: str) -> List[Dict[str, Any]]:
    """Read spans back from either export format: Chrome trace-event JSON
    (``traceEvents`` with span ids in ``args``) or ``Tracer.to_json()``
    (``spans``).  Returns a list of span dicts with name/spanId/parentId/
    durationS/status keys."""
    with open(path) as fh:
        doc = json.load(fh)
    if isinstance(doc, dict) and "spans" in doc:
        return list(doc["spans"])
    events = (doc or {}).get("traceEvents", []) if isinstance(doc, dict) \
        else []
    spans = []
    for ev in events:
        if ev.get("ph") != "X":
            continue
        args = ev.get("args") or {}
        spans.append({"name": ev.get("name", "?"),
                      "spanId": args.get("spanId"),
                      "parentId": args.get("parentId"),
                      "startS": float(ev.get("ts", 0.0)) / 1e6,
                      "durationS": float(ev.get("dur", 0.0)) / 1e6,
                      "status": args.get("status", "ok"),
                      "traceId": args.get("traceId", ""),
                      "w3cSpanId": args.get("w3cSpanId", ""),
                      "links": args.get("links") or [],
                      "attrs": {k: v for k, v in args.items()
                                if k not in ("spanId", "parentId", "status",
                                             "traceId", "w3cSpanId",
                                             "links")}})
    return spans


# --------------------------------------------------------------------------
# cross-process trace assembly
# --------------------------------------------------------------------------

def merge_traces(paths: Iterable[str],
                 out_path: Optional[str] = None) -> Dict[str, Any]:
    """Align per-process trace exports onto one wall-clock-anchored Perfetto
    timeline.  Accepts both export formats (chrome trace-event JSON with an
    ``otherData.t0WallS`` anchor, and ``Tracer.to_json()`` native files).
    The earliest ``t0WallS`` across files becomes the merged epoch; each
    file's events are shifted by its anchor delta and its pid remapped to a
    stable per-file index so Perfetto renders one process lane per worker
    (labelled via ``process_name`` metadata with the worker id)."""
    docs: List[Dict[str, Any]] = []
    for p in paths:
        with open(p) as fh:
            doc = json.load(fh)
        if not isinstance(doc, dict):
            continue
        if "spans" in doc:          # native Tracer.to_json() format
            t0 = float(doc.get("t0WallS", 0.0))
            events = []
            for s in doc["spans"]:
                args = {"spanId": s.get("spanId"),
                        "parentId": s.get("parentId"),
                        "status": s.get("status", "ok"),
                        "traceId": s.get("traceId", ""),
                        "w3cSpanId": s.get("w3cSpanId", ""),
                        **(s.get("attrs") or {})}
                if s.get("links"):
                    args["links"] = s["links"]
                events.append({
                    "name": s.get("name", "?"),
                    "cat": str(s.get("name", "?")).split(".", 1)[0],
                    "ph": "X",
                    "ts": round(float(s.get("startS", 0.0)) * 1e6, 1),
                    "dur": round(
                        max(float(s.get("durationS", 0.0)), 0.0) * 1e6, 1),
                    "pid": int(doc.get("pid", 0)),
                    "tid": s.get("thread", 0), "args": args})
            other = {"runName": doc.get("runName", "run"), "t0WallS": t0,
                     "traceId": doc.get("traceId", ""),
                     "pid": doc.get("pid", 0),
                     "workerId": doc.get("workerId"),
                     "rank": doc.get("rank")}
        else:
            events = [e for e in doc.get("traceEvents", [])
                      if e.get("ph") == "X"]
            other = dict(doc.get("otherData") or {})
        docs.append({"path": p, "events": events, "other": other,
                     "t0": float(other.get("t0WallS", 0.0) or 0.0)})
    if not docs:
        merged: Dict[str, Any] = {"traceEvents": [],
                                  "displayTimeUnit": "ms",
                                  "otherData": {"merged": True, "files": []}}
        if out_path:
            with open(out_path, "w") as fh:
                json.dump(merged, fh, default=str)
        return merged

    anchor = min(d["t0"] for d in docs)
    events: List[Dict[str, Any]] = []
    files_meta = []
    for idx, d in enumerate(docs):
        shift_us = (d["t0"] - anchor) * 1e6
        worker_id = d["other"].get("workerId")
        rank = d["other"].get("rank")
        run_name = d["other"].get("runName", "run")
        label = _proc_label(run_name, worker_id, rank)
        events.append({"name": "process_name", "ph": "M", "pid": idx,
                       "tid": 0, "args": {"name": label}})
        events.append({"name": "clock_sync", "ph": "c", "pid": idx,
                       "tid": 0, "ts": round(shift_us, 1),
                       "args": {"sync_id": d["other"].get("traceId", ""),
                                "issue_ts": round(d["t0"] * 1e6, 1)}})
        for ev in d["events"]:
            ev = dict(ev)
            ev["ts"] = round(float(ev.get("ts", 0.0)) + shift_us, 1)
            ev["pid"] = idx
            events.append(ev)
        files_meta.append({"path": d["path"], "runName": run_name,
                           "workerId": worker_id, "rank": rank,
                           "originalPid": d["other"].get("pid"),
                           "t0WallS": d["t0"]})
    merged = {"traceEvents": events, "displayTimeUnit": "ms",
              "otherData": {"merged": True, "t0WallS": anchor,
                            "files": files_meta}}
    if out_path:
        with open(out_path, "w") as fh:
            json.dump(merged, fh, default=str)
    return merged


def render_trace_summary(path: str, top_n: int = 10) -> str:
    """The ``trace-summary`` subcommand's table: top-N slowest spans with
    their depth-in-tree, duration, status and attributes."""
    spans = load_trace(path)
    if not spans:
        return f"{path}: no spans"
    by_id = {s.get("spanId"): s for s in spans if s.get("spanId")}

    def depth(s: Dict[str, Any]) -> int:
        d, seen = 0, set()
        while s.get("parentId") and s["parentId"] in by_id \
                and s["parentId"] not in seen:
            seen.add(s["parentId"])
            s = by_id[s["parentId"]]
            d += 1
        return d

    rows = sorted(spans, key=lambda s: -float(s.get("durationS", 0.0)))
    rows = rows[:top_n]
    name_w = max(len("span"),
                 max(len(s.get("name", "?")) + 2 * depth(s) for s in rows))
    lines = [f"{path}: {len(spans)} span(s); top {len(rows)} by duration",
             f"{'span'.ljust(name_w)}  {'seconds':>10}  {'status':<6}  attrs"]
    for s in rows:
        nm = "  " * depth(s) + s.get("name", "?")
        attrs = s.get("attrs") or {}
        attr_s = " ".join(f"{k}={v}" for k, v in sorted(attrs.items()))
        if len(attr_s) > 60:
            attr_s = attr_s[:57] + "..."
        lines.append(f"{nm.ljust(name_w)}  "
                     f"{float(s.get('durationS', 0.0)):>10.4f}  "
                     f"{s.get('status', 'ok'):<6}  {attr_s}")
    return "\n".join(lines)

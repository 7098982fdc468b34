"""Training control plane — live introspection for in-flight train runs.

Serving has been fully observable since PR 5 (/metrics with exemplars,
distributed tracing), but a running *train* exposed nothing until it
finished or died: a failed scale run had to be reconstructed after the
fact from per-rank heartbeat files and partial logs.  This module
is the train-side control plane (ROADMAP item 3):

* ``ProgressBoard`` — a lock-free snapshot object the sweep's *existing*
  seams publish into (``OpValidator.validate`` attempt loops,
  ``PhaseTimer.phase``, the memory/supervisor retry paths).  Publishing is
  a dict merge under a small lock at coarse boundaries — candidate-fit
  start/finish, fold, prune, phase — never new instrumentation in inner
  loops.  Readers get the current dict by reference, no lock.
* ``ObsServer`` — a stdlib ``ThreadingHTTPServer`` the runner starts for
  ``train`` / ``lifecycle`` / ``train-hosts`` runs when an obs port is
  configured (``--obs-port`` / ``obsParams.port`` /
  ``TRANSMOGRIFAI_OBS_PORT``; off by default, zero sockets and zero new
  spans when off).  ``GET /metrics`` renders ``telemetry.REGISTRY`` as
  Prometheus text (the serving renderer's conventions), ``GET /statusz``
  returns the live sweep JSON (phase, candidate, fold, raced-out set,
  memory plan + shrink level, supervisor state, EWMA-based ETA), and
  ``GET /traces`` returns the PR-13 telemetry summary.
* ``FlightRecorder`` — a bounded ring (``TRANSMOGRIFAI_BLACKBOX_SPANS``
  cap) of progress events, retry notes and metric deltas, dumped
  atomically as ``blackbox.json`` (same tmp + ``os.replace`` convention as
  ``write_outage_record``) on ``DataQualityError`` /
  ``MemoryExhaustedError`` / ``HostLostError`` / unhandled exception /
  SIGTERM, with the FailureLog tail and last span summaries attached — a
  crash postmortem starts with the last minute of telemetry instead of
  archaeology.  The outage record references the dump.

Cross-host: inside a host group each rank serves on its own port (the
launcher exports ``base + 1 + rank`` per child and keeps ``base`` for
itself), and the launcher polls rank ``/metrics``, re-serving one merged
panel via ``merge_worker_metrics(label="rank")`` plus a
``hostgroup_rank_up{rank=...}`` family — replacing heartbeat-file-only
visibility.
"""

from __future__ import annotations

import collections
import json
import os
import re
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Callable, Dict, List, Optional

from .resilience import record_failure
from .telemetry import (REGISTRY, MetricsRegistry, active_tracer,
                        telemetry_summary)

#: Default flight-recorder ring capacity (entries, not bytes).
DEFAULT_BLACKBOX_CAP = 512

#: blackbox.json schema tag — bump on shape changes so postmortem tooling
#: can dispatch.
BLACKBOX_SCHEMA = "transmogrifai_blackbox_v1"

#: Top-level keys every blackbox.json carries (the CI smoke validates this).
BLACKBOX_KEYS = ("schema", "reason", "error", "utc", "pid", "rank", "cap",
                 "entries", "counterDeltas", "progress", "failureLogTail",
                 "spanSummaries")

_METRIC_PREFIX = "transmogrifai_train"


def _utc() -> str:
    return time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())


# --------------------------------------------------------------------------
# progress board
# --------------------------------------------------------------------------

class ProgressBoard:
    """Latest-wins progress snapshot: publishers merge fields under a small
    lock at coarse seam boundaries; readers take the current dict by
    reference with no lock (the dict is never mutated after the swap, so a
    reader can serialize it while the next publish builds a fresh one).

    ``note_unit`` maintains the per-fold/per-fit EWMA that backs the
    ``/statusz`` ETA."""

    def __init__(self, ewma_alpha: float = 0.3):
        self._lock = threading.Lock()
        self._snap: Dict[str, Any] = {}
        self._seq = 0
        self._ewma_alpha = float(ewma_alpha)
        self._ewma_s: Optional[float] = None

    def publish(self, **fields: Any) -> Dict[str, Any]:
        with self._lock:
            self._seq += 1
            snap = dict(self._snap)
            snap.update(fields)
            snap["seq"] = self._seq
            snap["updatedUtc"] = _utc()
            snap["updatedMono"] = time.monotonic()
            self._snap = snap
        rec = active_recorder()
        if rec is not None:
            rec.note("progress", **fields)
        return snap

    def note_unit(self, duration_s: float,
                  remaining_units: Optional[int] = None) -> None:
        """Feed one completed work unit (a candidate fit, a fold block)
        into the EWMA; with ``remaining_units`` the board publishes an
        ``etaS`` estimate."""
        a = self._ewma_alpha
        with self._lock:
            self._ewma_s = (float(duration_s) if self._ewma_s is None
                            else a * float(duration_s)
                            + (1.0 - a) * self._ewma_s)
            ewma = self._ewma_s
        fields: Dict[str, Any] = {"unitEwmaS": round(ewma, 3)}
        if remaining_units is not None:
            fields["remainingUnits"] = int(remaining_units)
            fields["etaS"] = round(ewma * max(0, int(remaining_units)), 3)
        self.publish(**fields)

    def snapshot(self) -> Dict[str, Any]:
        return self._snap   # reference to an immutable-by-convention dict

    @property
    def seq(self) -> int:
        return self._seq

    def reset(self) -> None:
        with self._lock:
            self._snap = {}
            self._seq = 0
            self._ewma_s = None


#: Process-default board — the sweep seams publish here; /statusz reads it.
BOARD = ProgressBoard()


# --------------------------------------------------------------------------
# Prometheus rendering over a MetricsRegistry
# --------------------------------------------------------------------------

_NAME_RE = re.compile(r"[^a-zA-Z0-9_]")


def _sanitize(name: str) -> str:
    out = _NAME_RE.sub("_", name)
    if out and out[0].isdigit():
        out = "_" + out
    return out


def _is_num(v: Any) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool)


def render_registry_metrics(registry: Optional[MetricsRegistry] = None,
                            prefix: str = _METRIC_PREFIX) -> str:
    """One ``MetricsRegistry`` as Prometheus text exposition — the same
    ``# HELP`` / ``# TYPE`` / sample conventions the serving renderer uses,
    with dotted registry names flattened to underscore metric names.
    Histograms render as summaries (quantile samples + ``_sum``/``_count``)
    so the scrape stays cheap and the log-bucket internals stay private."""
    registry = registry if registry is not None else REGISTRY
    snap = registry.snapshot()
    lines: List[str] = []

    for name in sorted(snap["counters"]):
        v = snap["counters"][name]
        if not _is_num(v):
            continue
        n = f"{prefix}_{_sanitize(name)}"
        lines.append(f"# HELP {n} Counter {name} (telemetry registry)")
        lines.append(f"# TYPE {n} counter")
        lines.append(f"{n} {v}")
    for name in sorted(snap["gauges"]):
        v = snap["gauges"][name]
        if v is None:
            v = 0
        if not _is_num(v):
            continue
        n = f"{prefix}_{_sanitize(name)}"
        lines.append(f"# HELP {n} Gauge {name} (telemetry registry)")
        lines.append(f"# TYPE {n} gauge")
        lines.append(f"{n} {v}")
    for name in sorted(snap["histograms"]):
        h = snap["histograms"][name]
        if not isinstance(h, dict):
            continue
        n = f"{prefix}_{_sanitize(name)}_seconds"
        lines.append(f"# HELP {n} Latency summary {name} "
                     "(telemetry registry)")
        lines.append(f"# TYPE {n} summary")
        for q, key in (("0.5", "p50"), ("0.95", "p95"), ("0.99", "p99")):
            qv = h.get(key)
            if _is_num(qv):
                lines.append(f'{n}{{quantile="{q}"}} {qv}')
        lines.append(f"{n}_sum {h.get('sum', 0)}")
        lines.append(f"{n}_count {h.get('count', 0)}")
    return "\n".join(lines) + "\n"


# --------------------------------------------------------------------------
# /statusz assembly
# --------------------------------------------------------------------------

_T0 = time.monotonic()


def statusz_snapshot(board: Optional[ProgressBoard] = None,
                     registry: Optional[MetricsRegistry] = None
                     ) -> Dict[str, Any]:
    """The live ``/statusz`` JSON: the board's sweep progress plus the
    memory / supervisor / hostgroup state read through the registry's
    gauges at snapshot time (the gauges lazy-import their sources, so this
    never pulls jax before the run itself did)."""
    board = board if board is not None else BOARD
    registry = registry if registry is not None else REGISTRY
    snap = registry.snapshot()
    g, c = snap["gauges"], snap["counters"]
    out: Dict[str, Any] = {
        "utc": _utc(),
        "pid": os.getpid(),
        "uptimeS": round(time.monotonic() - _T0, 3),
        "progress": board.snapshot(),
        "memory": {
            "shrinkLevel": g.get("memory.shrink_level", 0),
            "shrinksTotal": c.get("memory.shrinks_total", 0),
        },
        "supervisor": {
            "state": g.get("supervisor.state", 0),
            "probesTotal": c.get("supervisor.probes_total", 0),
            "outagesTotal": c.get("supervisor.outages_total", 0),
            "lastProbeLatencyS": g.get("supervisor.last_probe_latency_s", 0),
        },
    }
    from .parallel import hostgroup
    if hostgroup.hostgroup_env_present():
        out["hostgroup"] = {
            "rank": hostgroup.current_rank(),
            "worldSize": hostgroup.group_world_size(),
            "generation": int(os.environ.get(
                "TRANSMOGRIFAI_HOSTGROUP_GENERATION", "0") or 0),
        }
    rec = active_recorder()
    if rec is not None:
        out["blackbox"] = {"cap": rec.cap, "entries": len(rec),
                          "lastDump": rec.last_dump_path}
    return out


# --------------------------------------------------------------------------
# flight recorder
# --------------------------------------------------------------------------

def blackbox_cap() -> int:
    try:
        return max(8, int(os.environ.get("TRANSMOGRIFAI_BLACKBOX_SPANS",
                                         str(DEFAULT_BLACKBOX_CAP))))
    except ValueError:
        return DEFAULT_BLACKBOX_CAP


def default_blackbox_path() -> str:
    """Where the crash dump lands: ``TRANSMOGRIFAI_BLACKBOX_PATH`` wins;
    inside a host group the rank writes ``blackbox-rank<r>.json`` into the
    shared run dir (next to heartbeats, so the launcher can collect it);
    ``TRANSMOGRIFAI_OUTAGE_DIR`` is next; the working directory is last —
    the recorder only exists when the operator opted into the control
    plane, so the run is explicitly configured."""
    p = os.environ.get("TRANSMOGRIFAI_BLACKBOX_PATH")
    if p:
        return p
    run_dir = os.environ.get("TRANSMOGRIFAI_HOSTGROUP_RUN_DIR")
    if run_dir:
        from .parallel.hostgroup import current_rank
        return os.path.join(run_dir, f"blackbox-rank{current_rank()}.json")
    d = os.environ.get("TRANSMOGRIFAI_OUTAGE_DIR")
    if d:
        return os.path.join(d, "blackbox.json")
    return os.path.join(os.getcwd(), "blackbox.json")


class FlightRecorder:
    """Bounded in-memory ring of control-plane events plus a one-shot
    atomic crash dump.  ``note()`` is a deque append under a lock —
    publishers are the same coarse seams that feed the ``ProgressBoard``,
    so the hot path never sees it."""

    def __init__(self, cap: Optional[int] = None,
                 registry: Optional[MetricsRegistry] = None,
                 failure_tail: int = 32, span_tail: int = 32):
        self.cap = cap if cap is not None else blackbox_cap()
        self.registry = registry if registry is not None else REGISTRY
        self.failure_tail = int(failure_tail)
        self.span_tail = int(span_tail)
        self._ring: "collections.deque" = collections.deque(maxlen=self.cap)
        self._lock = threading.Lock()
        # metric deltas are relative to recorder install, so the dump shows
        # what THIS run did, not the process's lifetime totals
        try:
            self._baseline = dict(self.registry.counters())
        except Exception:  # noqa: BLE001 — a broken gauge source must not
            #               keep the recorder from starting
            self._baseline = {}
        self.last_dump_path: Optional[str] = None

    def note(self, kind: str, **fields: Any) -> None:
        e = {"tUtc": _utc(), "kind": str(kind)}
        e.update(fields)
        with self._lock:
            self._ring.append(e)

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)

    def entries(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._ring)

    def counter_deltas(self) -> Dict[str, Any]:
        try:
            cur = self.registry.counters()
        except Exception:  # noqa: BLE001
            return {}
        return {k: v - self._baseline.get(k, 0)
                for k, v in sorted(cur.items())
                if v != self._baseline.get(k, 0)}

    def payload(self, reason: str,
                error: Optional[BaseException] = None) -> Dict[str, Any]:
        from .resilience import active_failure_log
        tracer = active_tracer()
        spans: List[Dict[str, Any]] = []
        if tracer is not None:
            for s in tracer.spans[-self.span_tail:]:
                spans.append({"name": s.name,
                              "startS": round(s.start_s, 4),
                              "durationS": round(s.duration_s, 4),
                              "status": s.status})
        tail = [e.to_json()
                for e in active_failure_log().events[-self.failure_tail:]]
        rank = None
        if os.environ.get("TRANSMOGRIFAI_HOSTGROUP_RANK") is not None:
            from .parallel.hostgroup import current_rank
            rank = current_rank()
        return {
            "schema": BLACKBOX_SCHEMA,
            "reason": str(reason),
            "error": (f"{type(error).__name__}: {error}"
                      if error is not None else None),
            "utc": _utc(),
            "pid": os.getpid(),
            "rank": rank,
            "cap": self.cap,
            "entries": self.entries(),
            "counterDeltas": self.counter_deltas(),
            "progress": BOARD.snapshot(),
            "failureLogTail": tail,
            "spanSummaries": spans,
        }

    def dump(self, path: Optional[str] = None, *, reason: str,
             error: Optional[BaseException] = None) -> Optional[str]:
        """Atomically write ``blackbox.json`` (tmp sibling + ``os.replace``
        — the ``write_outage_record`` convention).  Best-effort: a full
        disk must not mask the crash being recorded."""
        path = path or default_blackbox_path()
        try:
            doc = self.payload(reason, error)
            d = os.path.dirname(os.path.abspath(path))
            os.makedirs(d, exist_ok=True)
            tmp = path + ".tmp"
            with open(tmp, "w") as fh:
                json.dump(doc, fh, indent=2, default=str)
            os.replace(tmp, path)
        except Exception as e:  # noqa: BLE001
            record_failure("obsv", "swallowed", e, point="obsv.blackbox",
                           path=path)
            return None
        self.last_dump_path = path
        return path


_RECORDER: Optional[FlightRecorder] = None
_RECORDER_LOCK = threading.Lock()
_LAST_DUMP: Optional[str] = None


def install_recorder(rec: Optional[FlightRecorder]
                     ) -> Optional[FlightRecorder]:
    """Install (or, with ``None``, remove) the process-wide recorder.
    Returns what was installed.  Either way the remembered dump path is
    cleared — ``last_blackbox_path`` is scoped to one recorder's
    lifetime, so an outage record never points at a previous run's
    blackbox."""
    global _RECORDER, _LAST_DUMP
    with _RECORDER_LOCK:
        _RECORDER = rec
        _LAST_DUMP = None
    return rec


def active_recorder() -> Optional[FlightRecorder]:
    return _RECORDER


def blackbox_note(kind: str, **fields: Any) -> None:
    """The one-liner deep seams use (memory shrinks, supervisor retries,
    host losses).  A single global read when the control plane is off."""
    rec = _RECORDER
    if rec is not None:
        rec.note(kind, **fields)


def dump_blackbox(reason: str, error: Optional[BaseException] = None,
                  path: Optional[str] = None) -> Optional[str]:
    """Dump the installed recorder's ring (no-op → None when the control
    plane is off).  Remembers the path so the outage record can point at
    it."""
    global _LAST_DUMP
    rec = _RECORDER
    if rec is None:
        return None
    out = rec.dump(path, reason=reason, error=error)
    if out is not None:
        _LAST_DUMP = out
    return out


def last_blackbox_path() -> Optional[str]:
    """The most recent dump this process wrote, if any — referenced from
    outage records."""
    rec = _RECORDER
    if rec is not None and rec.last_dump_path:
        return rec.last_dump_path
    return _LAST_DUMP


# --------------------------------------------------------------------------
# admin HTTP server
# --------------------------------------------------------------------------

#: Live servers (tests assert this is empty when the plane is off).
_ACTIVE_SERVERS: List["ObsServer"] = []


class ObsServer:
    """The admin endpoint: ``/metrics`` (Prometheus text), ``/statusz``
    (live JSON), ``/traces`` (telemetry summary), ``/healthz``.  One
    daemonized ``ThreadingHTTPServer``; ``port=0`` binds an ephemeral port
    (tests).  ``metrics_fn`` / ``statusz_fn`` override the defaults — the
    hostgroup launcher serves its merged rank panel through them."""

    def __init__(self, port: int, *, host: str = "127.0.0.1",
                 registry: Optional[MetricsRegistry] = None,
                 board: Optional[ProgressBoard] = None,
                 metrics_fn: Optional[Callable[[], str]] = None,
                 statusz_fn: Optional[Callable[[], Dict[str, Any]]] = None,
                 prefix: str = _METRIC_PREFIX):
        self.host = host
        self.port = int(port)
        self.registry = registry if registry is not None else REGISTRY
        self.board = board if board is not None else BOARD
        self.metrics_fn = metrics_fn
        self.statusz_fn = statusz_fn
        self.prefix = prefix
        self._httpd: Optional[ThreadingHTTPServer] = None
        self._thread: Optional[threading.Thread] = None

    # -- request handling --------------------------------------------------
    def _metrics_text(self) -> str:
        if self.metrics_fn is not None:
            return self.metrics_fn()
        return render_registry_metrics(self.registry, prefix=self.prefix)

    def _statusz_doc(self) -> Dict[str, Any]:
        if self.statusz_fn is not None:
            return self.statusz_fn()
        return statusz_snapshot(self.board, self.registry)

    def _traces_doc(self) -> Dict[str, Any]:
        return telemetry_summary(active_tracer(), self.registry)

    def _make_handler(self):
        server = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):  # silence per-request noise
                pass

            def _send(self, code: int, body: bytes, ctype: str) -> None:
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):  # noqa: N802 — http.server API
                path = self.path.split("?", 1)[0].rstrip("/") or "/"
                try:
                    if path == "/metrics":
                        self._send(200, server._metrics_text().encode(),
                                   "text/plain; version=0.0.4")
                    elif path == "/statusz":
                        body = json.dumps(server._statusz_doc(), indent=2,
                                          default=str).encode()
                        self._send(200, body, "application/json")
                    elif path == "/traces":
                        body = json.dumps(server._traces_doc(), indent=2,
                                          default=str).encode()
                        self._send(200, body, "application/json")
                    elif path in ("/", "/healthz"):
                        self._send(200, b"ok\n", "text/plain")
                    else:
                        self._send(404, b"not found\n", "text/plain")
                except BrokenPipeError:
                    pass
                except Exception as e:  # noqa: BLE001 — a scrape bug must
                    #                     never touch the run it watches
                    record_failure("obsv", "swallowed", e,
                                   point="obsv.server", path=path)
                    try:
                        self._send(500, f"{e}\n".encode(), "text/plain")
                    except Exception:  # noqa: BLE001
                        pass

        return Handler

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> "ObsServer":
        self._httpd = ThreadingHTTPServer((self.host, self.port),
                                          self._make_handler())
        self._httpd.daemon_threads = True
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(target=self._httpd.serve_forever,
                                        name=f"obs-server:{self.port}",
                                        kwargs={"poll_interval": 0.2},
                                        daemon=True)
        self._thread.start()
        _ACTIVE_SERVERS.append(self)
        return self

    def stop(self) -> None:
        httpd, self._httpd = self._httpd, None
        if httpd is None:
            return
        try:
            httpd.shutdown()
            httpd.server_close()
        finally:
            if self in _ACTIVE_SERVERS:
                _ACTIVE_SERVERS.remove(self)

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"


def active_servers() -> List[ObsServer]:
    return list(_ACTIVE_SERVERS)


def obs_port_from_env() -> int:
    """The configured admin port; 0/unset = control plane off."""
    try:
        return int(os.environ.get("TRANSMOGRIFAI_OBS_PORT", "0") or 0)
    except ValueError:
        return 0


def obs_enabled() -> bool:
    return obs_port_from_env() > 0


def maybe_start_obs_server(port: Optional[int] = None,
                           **kw: Any) -> Optional[ObsServer]:
    """Start the admin server when a port is configured; None (and a
    recorded degradation, never a raised error) otherwise or on a bind
    failure — observability must not fail the run it watches."""
    port = port if port is not None else obs_port_from_env()
    if not port or port <= 0:
        return None
    try:
        return ObsServer(port, **kw).start()
    except OSError as e:
        record_failure("obsv", "degraded", e, point="obsv.server",
                       port=port,
                       fallback="run continues without admin endpoint")
        return None

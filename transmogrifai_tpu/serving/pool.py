"""SO_REUSEPORT worker pool: N scoring processes behind one port.

The single-process server tops out on the GIL, not the device (ROADMAP
item 1: warm score flat at ~57k rows/s across five bench rounds).  AOT
bundles (PR 9) made horizontal scale cheap — a fresh worker deserializes
the shipped executables and scores with zero compiles — so the pool is
the straightforward unix answer:

* every worker binds the SAME ``(host, port)`` with ``SO_REUSEPORT``; the
  kernel load-balances accepted connections across them (no userspace
  proxy on the hot path),
* each worker is a full single-process server (engine + continuous
  batcher + overload control plane), sharing nothing but the verified
  bundle path — admission and breaker state stay correct per-worker,
* each worker also binds a private ephemeral ADMIN port (same handler:
  ``/healthz`` ``/readyz`` ``/metrics``) that the parent probes and
  scrapes — traffic and control never contend for a socket,
* the parent supervisor health-checks workers, restarts crashed ones
  (SIGTERM → grace → SIGKILL escalation on stop, the
  ``parallel/supervisor.run_supervised`` conventions), and serves
  aggregated ``/metrics`` on its own admin port: counters sum across
  workers, gauges max-merge, and per-worker samples carry a
  ``worker_id`` label while family names stay unchanged.

Crash/failover story: when a worker dies, its pending accept backlog is
lost but every OTHER worker's listening socket keeps accepting — clients
see at worst a connection reset on in-flight requests to the dead worker,
never a 5xx from survivors (the chaos harness kills a worker mid-storm
and asserts exactly this).
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request
from typing import Any, Dict, List, Optional, Tuple

_METRIC_PREFIX = "transmogrifai_serving"


# --------------------------------------------------------------------------
# metrics aggregation
# --------------------------------------------------------------------------

def _fmt(v: float) -> str:
    if v == int(v) and abs(v) < 1e15:
        return str(int(v))
    return f"{v:.6g}"


def _escape_label_value(v: str) -> str:
    """Prometheus label-value escaping: backslash first, then quote and
    newline — a worker_id (or any label) containing ``"`` or ``\\``
    survives the text round-trip instead of corrupting the exposition."""
    return (str(v).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _with_worker_label(labels: str, worker_id: str,
                       label: str = "worker_id") -> str:
    """``{a="b"}`` or ``""`` → same labels plus ``label`` (default
    ``worker_id``; the tenant registry merges with ``tenant``)."""
    tag = f'{label}="{_escape_label_value(worker_id)}"'
    if not labels:
        return "{" + tag + "}"
    inner = labels[1:-1].strip()
    return "{" + (f"{tag},{inner}" if inner else tag) + "}"


def _find_label_close(line: str, brace: int) -> int:
    """Index of the ``}`` closing the label set opened at ``brace``,
    honouring quoted values with ``\\"``/``\\\\`` escapes (a value may
    contain ``}``); -1 when unterminated."""
    i = brace + 1
    in_quote = False
    while i < len(line):
        ch = line[i]
        if in_quote:
            if ch == "\\":
                i += 2
                continue
            if ch == '"':
                in_quote = False
        elif ch == '"':
            in_quote = True
        elif ch == "}":
            return i
        i += 1
    return -1


def _parse_exposition(text: str):
    """Prometheus text exposition → ordered ``{family: {"type", "help",
    "samples": [(sample_name, labels, value, exemplar)]}}``.  Summary
    ``_sum`` / ``_count`` samples resolve to their base family.
    ``exemplar`` is the verbatim OpenMetrics suffix (`` # {...} v``) or
    ``""`` — the merge re-emits it so trace links survive aggregation."""
    families: Dict[str, Dict[str, Any]] = {}
    order: List[str] = []

    def fam(name: str) -> Dict[str, Any]:
        if name not in families:
            families[name] = {"type": "untyped", "help": "", "samples": []}
            order.append(name)
        return families[name]

    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        if line.startswith("# HELP "):
            rest = line[len("# HELP "):]
            name, _, help_ = rest.partition(" ")
            fam(name)["help"] = help_
            continue
        if line.startswith("# TYPE "):
            rest = line[len("# TYPE "):]
            name, _, type_ = rest.partition(" ")
            fam(name)["type"] = type_.strip()
            continue
        if line.startswith("#"):
            continue
        # split off an OpenMetrics exemplar (`value # {labels} exval`)
        # BEFORE locating the label braces: the exemplar carries its own
        # brace pair that a naive rfind("}") would mistake for the end of
        # the sample's label set
        exemplar = ""
        ex_at = line.find(" # {")
        if ex_at >= 0:
            exemplar = line[ex_at + 1:]
            line = line[:ex_at].rstrip()
        brace = line.find("{")
        if brace >= 0:
            close = _find_label_close(line, brace)
            if close < 0:
                continue  # malformed sample: skip, don't fail the scrape
            sample_name = line[:brace]
            labels = line[brace:close + 1]
            value_s = line[close + 1:].strip()
        else:
            sample_name, _, value_s = line.partition(" ")
            labels = ""
        try:
            value = float(value_s)
        except ValueError:
            continue
        base = sample_name
        for suffix in ("_sum", "_count"):
            if base.endswith(suffix) and base[:-len(suffix)] in families \
                    and families[base[:-len(suffix)]]["type"] == "summary":
                base = base[:-len(suffix)]
                break
        fam(base)["samples"].append((sample_name, labels, value, exemplar))
    return families, order


def merge_worker_metrics(worker_texts: List[Tuple[str, str]],
                         label: str = "worker_id") -> str:
    """Merge per-worker ``/metrics`` payloads into one exposition.

    ``worker_texts`` is ``[(worker_id, exposition_text), ...]``; ``label``
    names the per-source label (``worker_id`` for pool workers, ``tenant``
    for the tenant registry — pool-level aggregation preserves inner
    labels, so worker-level ``tenant`` labels survive a second merge).
    Per family (names unchanged, so existing dashboards keep working):

    * **counters**: one aggregate sample per label-set (sum across
      workers) plus one sample per worker with a ``worker_id`` label,
    * **gauges**: aggregate = max across workers (right for states,
      limits and depth-style gauges; a sum would fabricate a state), plus
      per-worker labeled samples,
    * **summaries**: ``_sum``/``_count`` sum across workers; quantile
      samples can't be merged without the raw streams, so they appear
      per-worker only (with ``worker_id`` + ``quantile`` labels).

    Family order follows the first worker, then families only later
    workers expose."""
    parsed = [(wid, *_parse_exposition(text)) for wid, text in worker_texts]
    order: List[str] = []
    for _wid, _families, worker_order in parsed:
        for name in worker_order:
            if name not in order:
                order.append(name)
    lines: List[str] = []
    for name in order:
        type_ = "untyped"
        help_ = ""
        for _wid, families, _o in parsed:
            f = families.get(name)
            if f is not None:
                type_ = f["type"] if f["type"] != "untyped" else type_
                help_ = f["help"] or help_
        if help_:
            lines.append(f"# HELP {name} {help_}")
        lines.append(f"# TYPE {name} {type_}")
        # aggregate per (sample_name, labels) across workers; exemplars
        # can't be summed, so the aggregate sample carries the last
        # non-empty one seen (a trace link survives the merge)
        agg: Dict[Tuple[str, str], float] = {}
        agg_ex: Dict[Tuple[str, str], str] = {}
        agg_order: List[Tuple[str, str]] = []
        per_worker: List[str] = []
        for wid, families, _o in parsed:
            f = families.get(name)
            if f is None:
                continue
            for sample_name, labels, value, exemplar in f["samples"]:
                is_quantile = type_ == "summary" and not (
                    sample_name.endswith("_sum")
                    or sample_name.endswith("_count"))
                ex_suffix = f" {exemplar}" if exemplar else ""
                per_worker.append(
                    f"{sample_name}"
                    f"{_with_worker_label(labels, wid, label=label)} "
                    f"{_fmt(value)}{ex_suffix}")
                if is_quantile:
                    continue  # no cross-worker quantile merge
                key = (sample_name, labels)
                if key not in agg:
                    agg[key] = 0.0
                    agg_order.append(key)
                if type_ == "gauge":
                    agg[key] = max(agg[key], value)
                else:
                    agg[key] += value
                if exemplar:
                    agg_ex[key] = exemplar
        for sample_name, labels in agg_order:
            key = (sample_name, labels)
            ex = agg_ex.get(key, "")
            lines.append(f"{sample_name}{labels} {_fmt(agg[key])}"
                         f"{' ' + ex if ex else ''}")
        lines.extend(per_worker)
    return "\n".join(lines) + "\n"


# --------------------------------------------------------------------------
# worker process entry
# --------------------------------------------------------------------------

def _atomic_write_json(path: str, payload: Dict[str, Any]) -> None:
    tmp = f"{path}.tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f)
    os.replace(tmp, path)


def worker_main(config_path: str) -> int:
    """One pool worker: full engine + continuous batcher, a
    ``SO_REUSEPORT`` traffic server on the shared port and a private admin
    server on an ephemeral port, draining cleanly on SIGTERM."""
    import contextlib

    import jax

    from ..checkpoint import preemption_guard, shutdown_requested
    from ..telemetry import TraceContext, Tracer, use_tracer
    from .overload import OverloadConfig
    from .server import ScoringHTTPServer
    from .engine import ScoringEngine

    with open(config_path) as f:
        cfg = json.load(f)
    worker_id = str(cfg["workerId"])
    overload = (OverloadConfig(**cfg["overload"])
                if cfg.get("overload") else None)
    # distributed tracing (opt-in via traceDir): the worker records every
    # request/batch span into its own tracer, seeded from the parent's
    # TRANSMOGRIFAI_TRACEPARENT when the pool exported one, and writes
    # trace-worker-<id>.json on drain — `trace-merge` (and the pool's
    # /traces endpoint) assemble the per-worker files into one timeline
    trace_dir = cfg.get("traceDir")
    tracer = None
    if trace_dir:
        os.makedirs(trace_dir, exist_ok=True)
        tracer = Tracer(run_name=f"serve-worker-{worker_id}",
                        parent=TraceContext.from_env(),
                        worker_id=worker_id)
    with preemption_guard("serve-worker"), \
            (use_tracer(tracer) if tracer is not None
             else contextlib.nullcontext()):
        engine = None
        registry = None
        if cfg.get("modelRoot"):
            # multi-tenant worker: every worker loads the full registry —
            # tenants activate lazily per worker, so a worker only pays
            # for the tenants the kernel actually routes to it
            from .tenants import TenantRegistry
            registry = TenantRegistry(
                cfg["modelRoot"],
                max_batch=int(cfg.get("maxBatch", 64)),
                queue_bound=int(cfg.get("queueBound", 256)),
                reload_poll_s=float(cfg.get("reloadPollS", 0.0)),
                overload=overload,
                max_active=cfg.get("tenantMaxActive"),
                memory_budget_bytes=cfg.get("tenantMemoryBudgetBytes"))
            served = f"{len(registry.tenants())} tenants"
        else:
            engine = ScoringEngine(
                cfg["modelLocation"],
                max_batch=int(cfg.get("maxBatch", 64)),
                queue_bound=int(cfg.get("queueBound", 256)),
                reload_poll_s=float(cfg.get("reloadPollS", 0.0)),
                overload=overload)
            served = engine.model_version
        traffic = ScoringHTTPServer(
            engine, host=cfg["host"], port=int(cfg["port"]),
            request_deadline_s=cfg.get("requestDeadlineS", 30.0),
            reuse_port=True, wire_format=cfg.get("wireFormat", "auto"),
            registry=registry)
        admin = ScoringHTTPServer(
            engine, host=cfg["host"], port=0,
            request_deadline_s=cfg.get("requestDeadlineS", 30.0),
            wire_format=cfg.get("wireFormat", "auto"),
            registry=registry)
        for srv, tag in ((traffic, "traffic"), (admin, "admin")):
            threading.Thread(target=srv.serve_forever,
                             name=f"worker-{worker_id}-{tag}",
                             daemon=True).start()
        _atomic_write_json(
            os.path.join(cfg["runDir"], f"worker-{worker_id}.ready.json"),
            {"workerId": worker_id, "pid": os.getpid(),
             "port": traffic.port, "adminPort": admin.port,
             # where this worker's engine computes — the pool checks it
             # against what it resolved
             "device": {"platform": jax.devices()[0].platform,
                        "kind": jax.devices()[0].device_kind,
                        "count": len(jax.devices())}})
        print(f"worker {worker_id} serving {served} on "
              f":{traffic.port} (admin :{admin.port})", flush=True)
        try:
            while not shutdown_requested("serve-worker"):
                time.sleep(0.1)
        finally:
            traffic.draining = True
            admin.draining = True
            if registry is not None:
                registry.close(timeout_s=30.0)
            else:
                engine.close(drain=True, timeout_s=30.0)
            traffic.shutdown()
            traffic.server_close()
            admin.shutdown()
            admin.server_close()
            if tracer is not None:
                try:
                    tracer.export_chrome_trace(os.path.join(
                        trace_dir, f"trace-worker-{worker_id}.json"))
                except OSError:
                    pass  # trace export must not fail the drain
    return 0


# --------------------------------------------------------------------------
# the pool supervisor
# --------------------------------------------------------------------------

class _WorkerSlot:
    def __init__(self, worker_id: int, config_path: str, log_path: str):
        self.worker_id = worker_id
        self.config_path = config_path
        self.log_path = log_path
        self.proc: Optional[subprocess.Popen] = None
        self.ready: Optional[Dict[str, Any]] = None
        self.probe_failures = 0
        self.restarts = 0

    @property
    def alive(self) -> bool:
        return self.proc is not None and self.proc.poll() is None


class ServingPool:
    """Spawn, supervise and aggregate N ``SO_REUSEPORT`` workers.

    The parent holds no engine and serves no traffic: it writes one
    config file per worker, spawns them as ``python -m
    transmogrifai_tpu.serving.pool --worker <config>`` (each in its own
    session, stdout+stderr to a per-worker log), restarts any that die or
    fail ``health_probes_fatal`` consecutive admin ``/healthz`` probes,
    and exposes pool status + merged metrics."""

    def __init__(self, model_location: Optional[str], *, workers: int = 2,
                 host: str = "127.0.0.1", port: int = 0,
                 max_batch: int = 64, queue_bound: int = 256,
                 request_deadline_s: Optional[float] = 30.0,
                 reload_poll_s: float = 0.0,
                 overload: Optional[Dict[str, Any]] = None,
                 wire_format: str = "auto",
                 run_dir: Optional[str] = None,
                 health_poll_s: float = 1.0,
                 health_probes_fatal: int = 3,
                 worker_boot_timeout_s: float = 180.0,
                 max_restarts: int = 20,
                 trace_dir: Optional[str] = None,
                 model_root: Optional[str] = None,
                 tenant_max_active: Optional[int] = None,
                 tenant_memory_budget_bytes: Optional[int] = None):
        if workers < 1:
            raise ValueError("workers must be >= 1")
        if bool(model_location) == bool(model_root):
            raise ValueError("exactly one of model_location (single "
                             "bundle) or model_root (multi-tenant) is "
                             "required")
        self.model_location = model_location
        self.model_root = model_root
        self.workers = int(workers)
        self.host = host
        # all workers share ONE concrete port: resolve the ephemeral
        # request up front so every bind targets the same number
        self.port = int(port) or free_port(host)
        self.health_poll_s = float(health_poll_s)
        self.health_probes_fatal = int(health_probes_fatal)
        self.worker_boot_timeout_s = float(worker_boot_timeout_s)
        self.max_restarts = int(max_restarts)
        self.run_dir = run_dir or tempfile.mkdtemp(
            prefix="transmogrifai-pool-")
        os.makedirs(self.run_dir, exist_ok=True)
        self.trace_dir = trace_dir
        if self.trace_dir:
            os.makedirs(self.trace_dir, exist_ok=True)
        self._stopping = False
        self._lock = threading.Lock()
        self._restarts_total = 0
        self._worker_cfg = {
            "modelLocation": model_location, "host": host,
            "port": self.port, "maxBatch": int(max_batch),
            "queueBound": int(queue_bound),
            "requestDeadlineS": request_deadline_s,
            "reloadPollS": float(reload_poll_s),
            "overload": dict(overload) if overload else None,
            "wireFormat": wire_format, "runDir": self.run_dir,
            "traceDir": self.trace_dir,
            "modelRoot": model_root,
            "tenantMaxActive": tenant_max_active,
            "tenantMemoryBudgetBytes": tenant_memory_budget_bytes}
        self.slots = [self._make_slot(i) for i in range(self.workers)]
        self._device_env: List[Dict[str, str]] = []  # per worker, by start()
        self._platform: Optional[str] = None  # what every worker must report
        self._supervisor: Optional[threading.Thread] = None

    # -- spawning ----------------------------------------------------------
    def _make_slot(self, worker_id: int) -> _WorkerSlot:
        config_path = os.path.join(self.run_dir,
                                   f"worker-{worker_id}.json")
        _atomic_write_json(config_path,
                           dict(self._worker_cfg, workerId=worker_id))
        return _WorkerSlot(worker_id, config_path,
                           os.path.join(self.run_dir,
                                        f"worker-{worker_id}.log"))

    def _resolve_device_env(self) -> List[Dict[str, str]]:
        """Where each worker computes, as child-environment entries.  The
        pool parent stays off the backend (a chip belongs to one process),
        so it asks the supervisor's subprocess probe what a fresh process
        gets.  On an accelerator worker *k* owns chip *k*, with the platform
        pinned so a worker that cannot have its chip fails at boot instead
        of serving from the CPU; more workers than chips is refused.  With
        ``JAX_PLATFORMS=cpu`` in the environment the operator chose the CPU
        backend and the workers just inherit it."""
        pinned = (os.environ.get("JAX_PLATFORMS") or "").split(",")[0].strip()
        if pinned == "cpu":
            self._platform = "cpu"
            return [{} for _ in self.slots]
        from ..parallel.supervisor import (accelerator_expected,
                                           probe_devices, single_chip_env)
        verdict = probe_devices(key="serving-pool",
                                expect_accelerator=accelerator_expected())
        if not verdict.ok:
            raise RuntimeError(
                f"serving pool: device probe says {verdict.status} "
                f"({verdict.cause}) — is this process (or another) holding "
                "the accelerator?  Set JAX_PLATFORMS=cpu to serve from the "
                "CPU on purpose")
        self._platform = verdict.platform
        if verdict.platform == "cpu":
            return [{"JAX_PLATFORMS": "cpu"} for _ in self.slots]
        if self.workers > verdict.device_count:
            raise ValueError(
                f"serving pool: {self.workers} workers but this host shows "
                f"{verdict.device_count} {verdict.platform} device(s); a "
                "chip belongs to one process, so the pool serves one worker "
                "per chip")
        return [{"JAX_PLATFORMS": verdict.platform, **single_chip_env(k)}
                for k in range(self.workers)]

    def _worker_env(self, slot: _WorkerSlot) -> Dict[str, str]:
        env = dict(os.environ)
        root = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
        # every worker installs from the pool's compiled-program registry:
        # N-worker boot then costs at most the ONE compile the first
        # publisher paid, not N re-derivations (aot_registry.py).  The
        # compile-cache variables are inherited as they stand.
        from ..aot_registry import registry_root
        reg = registry_root()
        if reg:
            env.setdefault("TRANSMOGRIFAI_AOT_REGISTRY", reg)
        env.update(self._device_env[slot.worker_id])
        return env

    def _spawn(self, slot: _WorkerSlot) -> None:
        ready_path = os.path.join(self.run_dir,
                                  f"worker-{slot.worker_id}.ready.json")
        if os.path.exists(ready_path):
            os.unlink(ready_path)
        slot.ready = None
        slot.probe_failures = 0
        env = self._worker_env(slot)
        # seed the worker's root span from the pool's ambient trace so
        # worker-side spans land on the same trace_id as the spawner
        from ..telemetry import TRACEPARENT_ENV, current_trace_context
        ctx = current_trace_context()
        if ctx is not None:
            env[TRACEPARENT_ENV] = ctx.child().to_traceparent()
        log = open(slot.log_path, "ab")
        try:
            # own session: SIGTERM/SIGKILL hit exactly this worker, and a
            # dying parent shell doesn't take the pool down with it
            # (run_supervised conventions)
            slot.proc = subprocess.Popen(
                [sys.executable, "-m", "transmogrifai_tpu.serving.pool",
                 "--worker", slot.config_path],
                stdout=log, stderr=subprocess.STDOUT, env=env,
                start_new_session=True)
        finally:
            log.close()

    def _wait_ready(self, slot: _WorkerSlot, deadline: float) -> None:
        ready_path = os.path.join(self.run_dir,
                                  f"worker-{slot.worker_id}.ready.json")
        while time.monotonic() < deadline:
            if os.path.exists(ready_path):
                try:
                    with open(ready_path) as f:
                        slot.ready = json.load(f)
                except (OSError, ValueError):
                    pass  # mid-rename; retry
            if slot.ready is not None:
                got = slot.ready.get("device", {}).get("platform")
                if got != self._platform:
                    raise RuntimeError(
                        f"worker {slot.worker_id} serves from {got!r}, the "
                        f"pool resolved {self._platform!r} "
                        f"(log: {slot.log_path})")
                return
            if slot.proc is not None and slot.proc.poll() is not None:
                raise RuntimeError(
                    f"worker {slot.worker_id} exited rc="
                    f"{slot.proc.returncode} before ready "
                    f"(log: {slot.log_path}):\n{self._log_tail(slot)}")
            time.sleep(0.05)
        raise RuntimeError(
            f"worker {slot.worker_id} not ready within "
            f"{self.worker_boot_timeout_s}s (log: {slot.log_path}):\n"
            f"{self._log_tail(slot)}")

    def _log_tail(self, slot: _WorkerSlot, nbytes: int = 2000) -> str:
        try:
            with open(slot.log_path, "rb") as f:
                f.seek(max(0, os.path.getsize(slot.log_path) - nbytes))
                return f.read().decode(errors="replace")
        except OSError:
            return "<no log>"

    def start(self) -> "ServingPool":
        """Spawn every worker, wait until all are ready, start the
        supervisor thread.  Raises (after killing stragglers) if any
        worker fails to boot."""
        self._device_env = self._resolve_device_env()
        deadline = time.monotonic() + self.worker_boot_timeout_s
        try:
            for slot in self.slots:
                self._spawn(slot)
            for slot in self.slots:
                self._wait_ready(slot, deadline)
        except BaseException:
            self.stop(grace_s=2.0)
            raise
        self._supervisor = threading.Thread(
            target=self._supervise_loop, name="pool-supervisor",
            daemon=True)
        self._supervisor.start()
        return self

    # -- supervision -------------------------------------------------------
    def _probe(self, slot: _WorkerSlot) -> bool:
        if not slot.ready:
            return False
        url = (f"http://{self.host}:{slot.ready['adminPort']}/healthz")
        try:
            with urllib.request.urlopen(url, timeout=2.0) as resp:
                return resp.status == 200
        except (urllib.error.URLError, OSError, TimeoutError):
            return False

    def _restart(self, slot: _WorkerSlot, reason: str) -> None:
        from ..resilience import record_failure
        with self._lock:
            if self._stopping:
                return
            if self._restarts_total >= self.max_restarts:
                record_failure("serving", "degraded",
                               f"worker {slot.worker_id} down ({reason}) "
                               "but restart budget exhausted",
                               point="serving.pool")
                return
            self._restarts_total += 1
            slot.restarts += 1
        record_failure("serving", "recovered",
                       f"restarting worker {slot.worker_id}: {reason}",
                       point="serving.pool")
        if slot.proc is not None and slot.proc.poll() is None:
            try:
                slot.proc.kill()
            except OSError:
                pass
        if slot.proc is not None:
            try:
                slot.proc.wait(timeout=10.0)
            except subprocess.TimeoutExpired:
                pass
        self._spawn(slot)
        with self._lock:
            aborted = self._stopping
        if aborted:
            # stop() ran between the budget check and the spawn: the new
            # worker is ours to reap — terminate it now rather than orphan
            # a process stop() never saw
            if slot.proc is not None:
                try:
                    slot.proc.terminate()
                    slot.proc.wait(timeout=10.0)
                except (OSError, subprocess.TimeoutExpired):
                    try:
                        slot.proc.kill()
                        slot.proc.wait(timeout=5.0)
                    except (OSError, subprocess.TimeoutExpired):
                        pass
            return
        try:
            self._wait_ready(
                slot, time.monotonic() + self.worker_boot_timeout_s)
        except RuntimeError as e:
            record_failure("serving", "degraded", e, point="serving.pool")

    def _supervise_loop(self) -> None:
        while not self._stopping:
            time.sleep(self.health_poll_s)
            if self._stopping:
                return
            for slot in self.slots:
                if self._stopping:
                    return
                if not slot.alive:
                    rc = slot.proc.returncode if slot.proc else None
                    self._restart(slot, f"process exited rc={rc}")
                    continue
                if self._probe(slot):
                    slot.probe_failures = 0
                elif slot.ready:
                    slot.probe_failures += 1
                    if slot.probe_failures >= self.health_probes_fatal:
                        self._restart(
                            slot,
                            f"{slot.probe_failures} consecutive health "
                            "probe failures")

    # -- status / metrics --------------------------------------------------
    def status(self) -> Dict[str, Any]:
        st = {"port": self.port, "workers": self.workers,
              "alive": sum(1 for s in self.slots if s.alive),
              "restartsTotal": self._restarts_total,
              "runDir": self.run_dir,
              "workerList": [
                  {"workerId": s.worker_id, "alive": s.alive,
                   "pid": (s.ready or {}).get("pid"),
                   "adminPort": (s.ready or {}).get("adminPort"),
                   "device": (s.ready or {}).get("device"),
                   "restarts": s.restarts} for s in self.slots]}
        if self.model_root:
            st["modelRoot"] = self.model_root
            st["tenants"] = self.tenant_states()
        return st

    def tenant_states(self) -> Dict[str, Any]:
        """Per-tenant state across the pool, scraped (best effort) from
        each worker's admin ``/healthz``.  A tenant's pool-level state is
        the worst any worker reports (QUARANTINED > ACTIVE > INACTIVE):
        activation is lazy per worker, so a tenant can be cold on one
        worker and quarantined on another — the operator wants the bad
        news."""
        rank = {"INACTIVE": 0, "ACTIVE": 1, "QUARANTINED": 2}
        merged: Dict[str, Any] = {}
        for slot in self.slots:
            if not (slot.alive and slot.ready):
                continue
            url = (f"http://{self.host}:{slot.ready['adminPort']}/healthz")
            try:
                with urllib.request.urlopen(url, timeout=2.0) as resp:
                    payload = json.loads(resp.read().decode())
            except (urllib.error.URLError, OSError, TimeoutError,
                    ValueError):
                continue
            for tenant, info in (payload.get("tenants") or {}).items():
                seen = merged.get(tenant)
                if seen is None or (rank.get(info.get("state"), 0)
                                    > rank.get(seen.get("state"), 0)):
                    merged[tenant] = info
        return merged

    def scrape_worker(self, slot: _WorkerSlot) -> Optional[str]:
        if not (slot.alive and slot.ready):
            return None
        url = f"http://{self.host}:{slot.ready['adminPort']}/metrics"
        try:
            with urllib.request.urlopen(url, timeout=5.0) as resp:
                return resp.read().decode()
        except (urllib.error.URLError, OSError, TimeoutError):
            return None

    def metrics(self) -> str:
        """Merged per-worker metrics plus the pool's own families."""
        texts = []
        for slot in self.slots:
            text = self.scrape_worker(slot)
            if text is not None:
                texts.append((str(slot.worker_id), text))
        merged = merge_worker_metrics(texts) if texts else ""
        p = _METRIC_PREFIX
        lines = [
            f"# HELP {p}_pool_workers Configured pool size",
            f"# TYPE {p}_pool_workers gauge",
            f"{p}_pool_workers {self.workers}",
            f"# HELP {p}_pool_workers_alive Workers currently running",
            f"# TYPE {p}_pool_workers_alive gauge",
            f"{p}_pool_workers_alive "
            f"{sum(1 for s in self.slots if s.alive)}",
            f"# HELP {p}_pool_worker_restarts_total Worker restarts "
            "performed by the supervisor",
            f"# TYPE {p}_pool_worker_restarts_total counter",
            f"{p}_pool_worker_restarts_total {self._restarts_total}",
            f"# HELP {p}_pool_worker_up Per-worker liveness",
            f"# TYPE {p}_pool_worker_up gauge"]
        lines.extend(
            f'{p}_pool_worker_up{{worker_id="{s.worker_id}"}} '
            f'{1 if s.alive else 0}' for s in self.slots)
        return merged + "\n".join(lines) + "\n"

    # -- shutdown ----------------------------------------------------------
    def stop(self, grace_s: float = 30.0) -> None:
        """SIGTERM every worker (graceful drain), escalate to SIGKILL
        after ``grace_s``, reap everything (run_supervised conventions:
        children are always reaped, never orphaned)."""
        with self._lock:
            self._stopping = True
        for slot in self.slots:
            if slot.alive:
                try:
                    slot.proc.terminate()
                except OSError:
                    pass
        deadline = time.monotonic() + grace_s
        for slot in self.slots:
            if slot.proc is None:
                continue
            remaining = max(0.1, deadline - time.monotonic())
            try:
                slot.proc.wait(timeout=remaining)
            except subprocess.TimeoutExpired:
                try:
                    slot.proc.kill()
                except OSError:
                    pass
                try:
                    slot.proc.wait(timeout=10.0)
                except subprocess.TimeoutExpired:
                    pass
        if self._supervisor is not None:
            self._supervisor.join(timeout=5.0)


# --------------------------------------------------------------------------
# parent admin server + CLI entry
# --------------------------------------------------------------------------

def _make_admin_server(pool: ServingPool, host: str, port: int):
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    class _AdminHandler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, fmt, *args):  # noqa: D102
            pass

        def _reply(self, code: int, body: bytes, content_type: str) -> None:
            self.send_response(code)
            self.send_header("Content-Type", content_type)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            try:
                self.wfile.write(body)
            except (BrokenPipeError, ConnectionResetError):
                pass

        def do_GET(self):  # noqa: N802
            if self.path == "/metrics":
                self._reply(200, pool.metrics().encode(),
                            "text/plain; version=0.0.4")
            elif self.path in ("/healthz", "/workers"):
                st = pool.status()
                code = 200 if st["alive"] == st["workers"] else 503
                if self.path == "/healthz":
                    code = 200 if st["alive"] > 0 else 503
                self._reply(code, json.dumps(st).encode(),
                            "application/json")
            elif self.path == "/traces":
                traces = []
                if pool.trace_dir and os.path.isdir(pool.trace_dir):
                    for name in sorted(os.listdir(pool.trace_dir)):
                        if not (name.startswith("trace-")
                                and name.endswith(".json")):
                            continue
                        p = os.path.join(pool.trace_dir, name)
                        try:
                            st_ = os.stat(p)
                        except OSError:
                            continue
                        traces.append({"name": name, "sizeBytes": st_.st_size,
                                       "mtimeS": st_.st_mtime})
                self._reply(200, json.dumps(
                    {"traceDir": pool.trace_dir,
                     "traces": traces}).encode(), "application/json")
            else:
                self._reply(404, json.dumps(
                    {"error": f"unknown path {self.path}"}).encode(),
                    "application/json")

    class _AdminServer(ThreadingHTTPServer):
        daemon_threads = True
        allow_reuse_address = True

    return _AdminServer((host, port), _AdminHandler)


def pool_serve_main(model_location: Optional[str], *, workers: int,
                    host: str = "127.0.0.1", port: int = 8180,
                    admin_port: int = 0, max_batch: int = 64,
                    queue_bound: int = 256,
                    request_deadline_s: Optional[float] = 30.0,
                    reload_poll_s: float = 10.0,
                    overload: Optional[Dict[str, Any]] = None,
                    wire_format: str = "auto",
                    trace_dir: Optional[str] = None,
                    model_root: Optional[str] = None,
                    tenant_max_active: Optional[int] = None,
                    tenant_memory_budget_bytes: Optional[int] = None
                    ) -> int:
    """Blocking entry point for ``serve --workers N``: run the pool until
    SIGTERM/SIGINT, then drain every worker and exit 0."""
    from ..checkpoint import preemption_guard, shutdown_requested
    with preemption_guard("serve-pool"):
        pool = ServingPool(
            model_location, workers=workers, host=host, port=port,
            max_batch=max_batch, queue_bound=queue_bound,
            request_deadline_s=request_deadline_s,
            reload_poll_s=reload_poll_s, overload=overload,
            wire_format=wire_format, trace_dir=trace_dir,
            model_root=model_root, tenant_max_active=tenant_max_active,
            tenant_memory_budget_bytes=tenant_memory_budget_bytes).start()
        admin = _make_admin_server(pool, host, admin_port)
        threading.Thread(target=admin.serve_forever, name="pool-admin",
                         daemon=True).start()
        print(f"serving pool on http://{host}:{pool.port} "
              f"(workers={workers}, max_batch={max_batch}, "
              f"admin=http://{host}:{admin.server_address[1]})", flush=True)
        try:
            while not shutdown_requested("serve-pool"):
                time.sleep(0.2)
        finally:
            print("draining pool...", flush=True)
            pool.stop()
            admin.shutdown()
            admin.server_close()
    return 0


def free_port(host: str = "127.0.0.1") -> int:
    """An ephemeral port every worker can then SO_REUSEPORT-bind.  The
    probe socket sets SO_REUSEPORT too, so the number stays biddable."""
    with socket.socket() as s:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEPORT, 1)
        s.bind((host, 0))
        return s.getsockname()[1]


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        description="pool worker entry (internal; use `transmogrifai "
                    "serve --workers N` instead)")
    parser.add_argument("--worker", metavar="CONFIG_JSON",
                        help="run one pool worker from a config file")
    args = parser.parse_args(argv)
    if args.worker:
        return worker_main(args.worker)
    parser.error("--worker CONFIG_JSON is required")
    return 2


if __name__ == "__main__":
    raise SystemExit(main())

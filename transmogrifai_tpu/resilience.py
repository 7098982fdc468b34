"""Resilience — policy-driven failure handling for the execution layer.

The reference system survives messy *data* (SanityChecker, RawFeatureFilter);
this module makes the *execution* layer survive messy infrastructure.
Device init can hang in native code with no error raised, and before this
module a single failing
grid candidate, poisoned micro-batch, or flaky device dispatch aborted an
entire ``train()`` or streaming-score run while ~20 ad-hoc silent ``except
Exception`` blocks hid the rest.  Four pieces replace that:

* ``RetryPolicy`` — exponential backoff with deterministic jitter and an
  optional per-attempt deadline; ``policy.call(fn)`` retries transient
  failures and records every retry in the active ``FailureLog``.
* ``run_with_deadline`` — a watchdog that runs a risky (device-touching)
  call in a worker thread and raises ``WatchdogTimeout`` when it does not
  return in time, so a native hang cannot stall the host loop.
* ``FailureLog`` — every swallowed / retried / degraded / dead-lettered
  event is recorded with the stage uid, injection-point name and cause.
  ``Workflow.train`` exposes the log on the returned model; the streaming
  runner exposes it on the run result.  The ambient log (``use_failure_log``)
  lets deep code (compiled-program demotions, device-dispatch fallbacks,
  multihost init) report without threading a handle through every call.
* ``FaultInjector`` — a chaos-test harness with named injection points
  (``selector.candidate_fit``, ``streaming.batch``, ...).  Decisions are a
  pure function of (seed, point, key), so a given seed reproduces the exact
  same failure set — and therefore the exact same failure log — on every run.
"""

from __future__ import annotations

import hashlib
import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import (Any, Callable, Dict, Iterable, List, Optional, Sequence,
                    Tuple)


# --------------------------------------------------------------------------
# errors
# --------------------------------------------------------------------------

class InjectedFault(RuntimeError):
    """Raised by FaultInjector at an armed injection point."""


class WatchdogTimeout(TimeoutError):
    """A watchdogged call did not return before its deadline.

    The worker thread is abandoned (daemonized): native hangs cannot be
    interrupted from Python, so the only safe recovery is to stop waiting
    and degrade."""


class AllCandidatesFailed(RuntimeError):
    """Every (model × grid-point) candidate of a selector sweep failed.

    Carries the per-candidate causes so the aggregate error is actionable
    instead of a bare "nothing survived"."""

    def __init__(self, message: str, causes: Optional[Dict[str, str]] = None):
        self.causes = dict(causes or {})
        if self.causes:
            detail = "; ".join(f"{k}: {v}" for k, v in
                               sorted(self.causes.items()))
            message = f"{message} — per-candidate causes: {detail}"
        super().__init__(message)


# --------------------------------------------------------------------------
# failure log
# --------------------------------------------------------------------------

def _format_cause(cause: Any) -> str:
    if cause is None:
        return ""
    if isinstance(cause, BaseException):
        return f"{type(cause).__name__}: {cause}"
    return str(cause)


@dataclass
class FailureEvent:
    """One swallowed / retried / degraded execution event."""

    seq: int
    stage: str              # stage uid / model name / subsystem
    action: str             # see FailureLog.ACTIONS
    cause: str              # "ExcType: message" (or free text)
    point: str = ""         # injection-point / site name, e.g. "streaming.batch"
    attempt: int = 0        # retry attempt number (0 = not a retry)
    detail: Dict[str, Any] = field(default_factory=dict)
    time_s: float = 0.0     # wall clock; excluded from signature()

    def to_json(self) -> Dict[str, Any]:
        d = {"seq": self.seq, "stage": self.stage, "action": self.action,
             "cause": self.cause, "point": self.point,
             "attempt": self.attempt, "time": self.time_s}
        if self.detail:
            d["detail"] = dict(self.detail)
        return d


class FailureLog:
    """Append-only, thread-safe record of degradation events.

    Worker threads (the validator's candidate pool, watchdog workers) record
    into the same log the orchestrating call installed, so a train run's log
    is complete even though fits fan out."""

    ACTIONS = ("retried",      # transient failure, will try again
               "skipped",      # unit of work abandoned, sweep continues
               "dead_letter",  # exhausted retries, routed to the DLQ
               "demoted",      # stage moved off the compiled/device path
               "degraded",     # optimization abandoned, slower path taken
               "fallback",     # alternate implementation used
               "swallowed",    # best-effort side work failed silently before
               "resumed",      # unit of work replayed from a checkpoint
               "preempted",    # graceful stop requested mid-run
               "reloaded",     # serving swapped in a newer model version
               "promoted",     # lifecycle candidate won the holdout gate
               "rejected",     # lifecycle candidate lost; incumbent kept
               "shed",         # admission control rejected work up front
               "quarantined",  # data-quality firewall excluded a record/row
               "evicted",      # size-capped store dropped an entry (GC)
               "breaker_open",       # circuit breaker tripped: calls skipped
               "breaker_half_open",  # breaker probing for recovery
               "breaker_closed",     # breaker recovered: calls flow again
               "outage",       # device runtime declared down (supervisor)
               "recovered",    # device runtime back after outage/degrade
               "host_lost",      # host-group rank dead / heartbeat silent
               "host_recovered",  # host-group rank heartbeat resumed
               "relaunched",   # host group rebooted at shrunken world size
               "escalated",    # SIGTERM ignored; SIGKILL reclaimed it
               "tenant.activated",    # multi-tenant: bundle loaded on demand
               "tenant.evicted",      # multi-tenant: LRU/budget unload
               "tenant.quarantined",  # multi-tenant: bundle parked as toxic
               "tenant.reactivated",  # multi-tenant: quarantine probe passed
               "tenant.removed")      # multi-tenant: bundle dir disappeared

    def __init__(self):
        self._events: List[FailureEvent] = []
        self._lock = threading.Lock()

    def record(self, stage: str, action: str, cause: Any = None, *,
               point: str = "", attempt: int = 0, **detail) -> FailureEvent:
        if action not in self.ACTIONS:
            raise ValueError(f"unknown failure action {action!r}; "
                             f"expected one of {self.ACTIONS}")
        if "span_id" not in detail:
            # correlate with the ambient trace: the span this failure was
            # recorded inside.  Safe for chaos determinism — signature()
            # excludes detail.  Late import: telemetry imports profiling only.
            from .telemetry import current_span_id
            sid = current_span_id()
            if sid is not None:
                detail["span_id"] = sid
        with self._lock:
            ev = FailureEvent(seq=len(self._events), stage=str(stage),
                              action=action, cause=_format_cause(cause),
                              point=point, attempt=int(attempt),
                              detail=dict(detail), time_s=time.time())
            self._events.append(ev)
            return ev

    @property
    def events(self) -> List[FailureEvent]:
        with self._lock:
            return list(self._events)

    def by_action(self, action: str) -> List[FailureEvent]:
        return [e for e in self.events if e.action == action]

    def by_stage(self, stage: str) -> List[FailureEvent]:
        return [e for e in self.events if e.stage == stage]

    def summary(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for e in self.events:
            out[e.action] = out.get(e.action, 0) + 1
        return out

    def signature(self) -> List[Tuple[str, str, str, str, int]]:
        """The deterministic projection of the log: everything except wall
        time and seq.  Two runs with the same seed/injector must produce
        equal signatures (the acceptance contract for chaos tests).  Sorted
        so thread-pool completion order cannot reorder it."""
        return sorted((e.stage, e.point, e.action, e.cause, e.attempt)
                      for e in self.events)

    def to_json(self) -> List[Dict[str, Any]]:
        return [e.to_json() for e in self.events]

    def extend(self, other: "FailureLog") -> None:
        for e in other.events:
            self.record(e.stage, e.action, e.cause, point=e.point,
                        attempt=e.attempt, **e.detail)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)

    def __iter__(self):
        return iter(self.events)

    def __repr__(self) -> str:
        return f"FailureLog({self.summary() or 'empty'})"


# Ambient log: a process-global stack (NOT thread-local — the validator's
# candidate fits run on a thread pool and must report into the log their
# orchestrating train() installed).  Concurrent *independent* runs in one
# process should pass explicit logs instead.
_LOG_STACK: List[FailureLog] = []
_LOG_LOCK = threading.Lock()
DEFAULT_LOG = FailureLog()


def active_failure_log() -> FailureLog:
    """The innermost installed log, or the process-default catch-all."""
    with _LOG_LOCK:
        return _LOG_STACK[-1] if _LOG_STACK else DEFAULT_LOG


@contextmanager
def use_failure_log(log: FailureLog):
    """Install ``log`` as the ambient failure log for the dynamic extent."""
    with _LOG_LOCK:
        _LOG_STACK.append(log)
    try:
        yield log
    finally:
        with _LOG_LOCK:
            # remove the last occurrence (robust to interleaved exits)
            for i in range(len(_LOG_STACK) - 1, -1, -1):
                if _LOG_STACK[i] is log:
                    del _LOG_STACK[i]
                    break


def record_failure(stage: str, action: str, cause: Any = None, *,
                   point: str = "", attempt: int = 0, **detail) -> FailureEvent:
    """Record into the ambient log — the one-liner deep code uses."""
    return active_failure_log().record(stage, action, cause, point=point,
                                       attempt=attempt, **detail)


# --------------------------------------------------------------------------
# deterministic hashing (shared by jitter and fault decisions)
# --------------------------------------------------------------------------

def _stable_uniform(*parts: Any) -> float:
    """Uniform [0, 1) as a pure function of the parts — independent of
    PYTHONHASHSEED, process, platform and call order."""
    h = hashlib.sha256("|".join(str(p) for p in parts).encode()).digest()
    return int.from_bytes(h[:8], "big") / float(1 << 64)


# --------------------------------------------------------------------------
# watchdog
# --------------------------------------------------------------------------

def run_with_deadline(fn: Callable[..., Any], timeout_s: Optional[float],
                      *args, description: str = "", **kwargs) -> Any:
    """Run ``fn`` with a deadline; raise ``WatchdogTimeout`` if it blows it.

    The call runs in a daemon worker thread and the caller waits at most
    ``timeout_s``.  A call that never returns (a native hang in device init
    or dispatch) is *abandoned*, not
    interrupted: Python cannot cancel native code, so the worker leaks by
    design and the host loop stays alive.  An abandoned worker that later
    completes drops its result/exception instead of pinning it in memory,
    and records the orphaned completion into the FailureLog that was ambient
    at call time.  Worker exceptions re-raise in the caller with the
    worker's own traceback attached.  ``timeout_s=None`` runs inline."""
    if timeout_s is None:
        return fn(*args, **kwargs)
    box: Dict[str, Any] = {}
    done = threading.Event()
    state_lock = threading.Lock()
    abandoned = False
    # captured NOW: by the time an abandoned worker finishes, the caller's
    # use_failure_log() context may have exited
    log = active_failure_log()
    label = description or getattr(fn, "__name__", "call")

    def target():
        err: Optional[BaseException] = None
        try:
            value = fn(*args, **kwargs)
        except BaseException as e:  # noqa: BLE001 — re-raised in the caller
            err, value = e, None
        with state_lock:
            orphaned = abandoned
            if not orphaned:
                if err is None:
                    box["value"] = value
                else:
                    box["error"] = err
        done.set()
        if orphaned:
            # the caller gave up long ago: do NOT keep the (possibly large)
            # result alive; leave an audit trail instead
            try:
                log.record("watchdog", "swallowed",
                           err if err is not None else
                           "worker completed after its deadline; "
                           "result dropped",
                           point="watchdog.orphan", description=label)
            except Exception:  # noqa: BLE001 — never crash an orphan thread
                pass

    worker = threading.Thread(target=target, daemon=True,
                              name=f"watchdog:{label}")
    worker.start()
    if not done.wait(timeout_s):
        with state_lock:
            # re-check under the lock: the worker may have delivered between
            # the wait timing out and us abandoning it
            if "value" not in box and "error" not in box:
                abandoned = True
        if abandoned:
            # zombie threads accumulate during a runtime outage: make every
            # abandonment observable (counter + failure-log note) instead of
            # silent.  Only the subprocess supervisor can actually RECLAIM a
            # native hang — this records that we could not.
            try:
                from .telemetry import REGISTRY
                REGISTRY.counter("watchdog.abandoned_total").inc()
            except Exception:  # noqa: BLE001 — never mask the timeout
                pass
            try:
                log.record("watchdog", "degraded",
                           f"{label} worker thread abandoned after "
                           f"{timeout_s:g}s (native hang; thread leaked)",
                           point="watchdog.abandoned", description=label)
            except Exception:  # noqa: BLE001
                pass
            raise WatchdogTimeout(
                f"{label} exceeded its "
                f"{timeout_s:g}s deadline; worker thread abandoned (native "
                "hangs cannot be interrupted from Python)")
    if "error" in box:
        err = box["error"]
        raise err.with_traceback(err.__traceback__)
    return box.get("value")


# --------------------------------------------------------------------------
# retry policy
# --------------------------------------------------------------------------

@dataclass
class RetryPolicy:
    """Exponential backoff with deterministic jitter and optional deadline.

    ``call(fn)`` runs ``fn`` up to ``max_attempts`` times.  Each attempt may
    additionally be watchdogged (``timeout_s``), so a hanging attempt counts
    as a failed attempt instead of stalling the loop forever.  Every retry is
    recorded in the supplied (or ambient) ``FailureLog``; the final failure
    propagates to the caller, which decides skip / dead-letter / raise."""

    max_attempts: int = 3
    base_delay_s: float = 0.05
    max_delay_s: float = 2.0
    multiplier: float = 2.0
    jitter: float = 0.25            # ± fraction of the nominal delay
    timeout_s: Optional[float] = None    # per-attempt watchdog deadline
    retry_on: Tuple[type, ...] = (Exception,)
    seed: int = 0                   # jitter determinism

    def delay_for(self, attempt: int, key: Any = "") -> float:
        """Backoff before retry #``attempt`` (1-based), deterministic in
        (seed, key, attempt)."""
        nominal = min(self.base_delay_s * self.multiplier ** (attempt - 1),
                      self.max_delay_s)
        if self.jitter <= 0:
            return nominal
        u = _stable_uniform(self.seed, "retry-jitter", key, attempt)
        return nominal * (1.0 + self.jitter * (2.0 * u - 1.0))

    def call(self, fn: Callable[[], Any], *, stage: str = "",
             point: str = "", key: Any = "", log: Optional[FailureLog] = None,
             sleep: Callable[[float], None] = time.sleep,
             description: str = "") -> Any:
        # `is None`, not truthiness — an empty FailureLog is falsy via __len__
        log = active_failure_log() if log is None else log
        last: Optional[BaseException] = None
        for attempt in range(1, max(1, self.max_attempts) + 1):
            try:
                return run_with_deadline(fn, self.timeout_s,
                                         description=description or point)
            except self.retry_on as e:  # noqa: PERF203
                last = e
                if attempt >= self.max_attempts:
                    raise
                log.record(stage or point or "retry", "retried", e,
                           point=point, attempt=attempt, key=str(key))
                sleep(self.delay_for(attempt, key=key))
        raise last  # pragma: no cover — loop always returns or raises


# --------------------------------------------------------------------------
# circuit breaker
# --------------------------------------------------------------------------

class CircuitOpenError(RuntimeError):
    """The breaker is open: the protected call was skipped outright.

    Carries ``retry_after_s`` — how long until the breaker will grant a
    recovery probe — so admission layers can surface an honest
    ``Retry-After`` instead of a guess."""

    def __init__(self, message: str, retry_after_s: float = 0.0):
        super().__init__(message)
        self.retry_after_s = float(retry_after_s)


class CircuitBreaker:
    """Thread-safe closed → open → half-open breaker with deterministic
    recovery probes.

    * **closed** — outcomes feed a sliding window.  The breaker opens on
      ``failure_threshold`` consecutive failures, or when the window holds
      at least ``min_calls`` outcomes and the failure fraction reaches
      ``failure_rate``.
    * **open** — ``allow()`` refuses every call until ``reset_timeout_s``
      has elapsed (``retry_after_s()`` says how long is left).
    * **half-open** — after the reset timeout, exactly ``half_open_probes``
      calls are granted as recovery probes (deterministic: a fixed permit
      count, no randomness).  If every probe succeeds the breaker closes
      and the window clears; any probe failure re-opens it for another
      full ``reset_timeout_s``.

    Transitions are recorded into the ambient ``FailureLog``
    (``breaker_open`` / ``breaker_half_open`` / ``breaker_closed``), as
    telemetry events (``breaker.transition``), and — when a registry is
    supplied — as per-breaker counters plus a state gauge
    (0 closed / 1 half-open / 2 open)."""

    CLOSED = "closed"
    OPEN = "open"
    HALF_OPEN = "half_open"
    _STATE_CODES = {CLOSED: 0, HALF_OPEN: 1, OPEN: 2}

    def __init__(self, name: str, *, window: int = 20,
                 failure_threshold: int = 5, failure_rate: float = 0.5,
                 min_calls: int = 10, reset_timeout_s: float = 30.0,
                 half_open_probes: int = 1,
                 clock: Callable[[], float] = time.monotonic,
                 registry: Optional[Any] = None):
        self.name = str(name)
        self.window = max(1, int(window))
        self.failure_threshold = max(1, int(failure_threshold))
        self.failure_rate = float(failure_rate)
        self.min_calls = max(1, int(min_calls))
        self.reset_timeout_s = float(reset_timeout_s)
        self.half_open_probes = max(1, int(half_open_probes))
        self._clock = clock
        self._lock = threading.Lock()
        self._state = self.CLOSED
        self._outcomes: List[bool] = []   # sliding window, True = failure
        self._consecutive_failures = 0
        self._opened_at = 0.0
        self._probe_permits = 0
        self._probe_successes = 0
        self._last_cause = ""
        self._registry = registry
        if registry is not None:
            registry.gauge(f"breaker.{self.name}.state", self.state_code)

    # -- state inspection --------------------------------------------------
    def state_code(self) -> int:
        return self._STATE_CODES[self.current_state()]

    def current_state(self) -> str:
        """The externally-visible state.  An open breaker whose reset
        timeout has elapsed reads as half-open (the next ``allow()`` will
        grant a probe) without mutating anything."""
        with self._lock:
            if (self._state == self.OPEN
                    and self._clock() - self._opened_at
                    >= self.reset_timeout_s):
                return self.HALF_OPEN
            return self._state

    def retry_after_s(self) -> float:
        """Seconds until the breaker will grant a recovery probe (0 when
        not open)."""
        with self._lock:
            if self._state != self.OPEN:
                return 0.0
            return max(0.0, self._opened_at + self.reset_timeout_s
                       - self._clock())

    def snapshot(self) -> Dict[str, Any]:
        state = self.current_state()
        with self._lock:
            failures = sum(self._outcomes)
            return {"name": self.name, "state": state,
                    "window_calls": len(self._outcomes),
                    "window_failures": failures,
                    "consecutive_failures": self._consecutive_failures,
                    "last_cause": self._last_cause,
                    "retry_after_s": (max(
                        0.0, self._opened_at + self.reset_timeout_s
                        - self._clock())
                        if self._state == self.OPEN else 0.0)}

    # -- the protocol ------------------------------------------------------
    def allow(self) -> bool:
        """May this call proceed?  Open→half-open happens here (lazily, on
        the first call after the reset timeout)."""
        with self._lock:
            if self._state == self.CLOSED:
                return True
            if self._state == self.OPEN:
                if (self._clock() - self._opened_at
                        < self.reset_timeout_s):
                    return False
                self._transition(self.HALF_OPEN,
                                 f"reset timeout {self.reset_timeout_s:g}s "
                                 "elapsed")
                self._probe_permits = self.half_open_probes
                self._probe_successes = 0
            # half-open: grant the remaining probe permits, refuse the rest
            if self._probe_permits > 0:
                self._probe_permits -= 1
                return True
            return False

    def record_success(self) -> None:
        with self._lock:
            self._consecutive_failures = 0
            if self._state == self.HALF_OPEN:
                self._probe_successes += 1
                if self._probe_successes >= self.half_open_probes:
                    self._transition(
                        self.CLOSED,
                        f"{self._probe_successes} recovery probe(s) "
                        "succeeded")
                    self._outcomes.clear()
                    self._last_cause = ""
                return
            if self._state == self.CLOSED:
                self._push_outcome(False)

    def record_failure(self, cause: Any = None) -> None:
        with self._lock:
            self._last_cause = _format_cause(cause)
            if self._state == self.HALF_OPEN:
                self._open(f"recovery probe failed: {self._last_cause}")
                return
            if self._state == self.OPEN:
                return   # already open; nothing new to learn
            self._push_outcome(True)
            self._consecutive_failures += 1
            failures = sum(self._outcomes)
            if self._consecutive_failures >= self.failure_threshold:
                self._open(f"{self._consecutive_failures} consecutive "
                           f"failures; last: {self._last_cause}")
            elif (len(self._outcomes) >= self.min_calls
                    and failures / len(self._outcomes)
                    >= self.failure_rate):
                self._open(f"failure rate {failures}/{len(self._outcomes)} "
                           f">= {self.failure_rate:g}; last: "
                           f"{self._last_cause}")

    def call(self, fn: Callable[[], Any]) -> Any:
        """Run ``fn`` under the breaker: raise ``CircuitOpenError`` without
        calling it when open, otherwise report its outcome."""
        if not self.allow():
            raise CircuitOpenError(
                f"breaker {self.name!r} is open "
                f"(last: {self._last_cause or 'unknown'})",
                retry_after_s=self.retry_after_s())
        try:
            result = fn()
        except BaseException as e:
            self.record_failure(e)
            raise
        self.record_success()
        return result

    # -- internals (call with self._lock held) -----------------------------
    def _push_outcome(self, failed: bool) -> None:
        self._outcomes.append(failed)
        if len(self._outcomes) > self.window:
            del self._outcomes[:len(self._outcomes) - self.window]

    def _open(self, reason: str) -> None:
        self._opened_at = self._clock()
        self._probe_permits = 0
        self._probe_successes = 0
        self._transition(self.OPEN, reason)

    def _transition(self, to: str, reason: str) -> None:
        frm, self._state = self._state, to
        action = {self.OPEN: "breaker_open",
                  self.HALF_OPEN: "breaker_half_open",
                  self.CLOSED: "breaker_closed"}[to]
        try:
            active_failure_log().record(
                "breaker", action, reason, point=f"breaker.{self.name}",
                breaker=self.name)
        except Exception:  # noqa: BLE001 — bookkeeping must not break calls
            pass
        try:
            from .telemetry import event
            event("breaker.transition", breaker=self.name,
                  from_state=frm, to_state=to, reason=reason)
        except Exception:  # noqa: BLE001
            pass
        if self._registry is not None:
            try:
                self._registry.counter(
                    f"breaker.{self.name}.{to}_total").inc()
            except Exception:  # noqa: BLE001
                pass


# --------------------------------------------------------------------------
# adaptive concurrency limit (AIMD)
# --------------------------------------------------------------------------

class AdaptiveConcurrencyLimit:
    """AIMD admission limit driven by observed batch latency vs. a target.

    Every completed batch calls ``observe(latency_s)``: latencies at or
    under ``target_latency_s`` grow the limit additively (``increase`` per
    observation); latencies over it shrink the limit multiplicatively
    (``decrease`` factor) — the TCP-congestion-control shape, which
    converges to the deepest queue the backend can drain within the
    latency target.  The limit is clamped to ``[min_limit, max_limit]``;
    ``max_limit`` is the static ceiling (the old ``queue_bound``) that
    still backstops the adaptive signal."""

    def __init__(self, *, target_latency_s: float, max_limit: int,
                 min_limit: int = 4, increase: float = 1.0,
                 decrease: float = 0.75,
                 initial: Optional[int] = None):
        if max_limit < 1:
            raise ValueError("max_limit must be >= 1")
        self.target_latency_s = float(target_latency_s)
        self.max_limit = int(max_limit)
        self.min_limit = max(1, min(int(min_limit), self.max_limit))
        self.increase = float(increase)
        self.decrease = float(decrease)
        if not 0.0 < self.decrease < 1.0:
            raise ValueError("decrease must be in (0, 1)")
        self._limit = float(initial if initial is not None
                            else self.max_limit)
        self._limit = min(max(self._limit, self.min_limit), self.max_limit)
        self._lock = threading.Lock()
        self._observations = 0
        self._decreases = 0

    @property
    def limit(self) -> int:
        with self._lock:
            return int(self._limit)

    def observe(self, latency_s: float) -> int:
        """Feed one batch latency; returns the updated limit."""
        with self._lock:
            self._observations += 1
            if latency_s <= self.target_latency_s:
                self._limit = min(self.max_limit,
                                  self._limit + self.increase)
            else:
                self._decreases += 1
                self._limit = max(self.min_limit,
                                  self._limit * self.decrease)
            return int(self._limit)

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {"limit": int(self._limit),
                    "min_limit": self.min_limit,
                    "max_limit": self.max_limit,
                    "target_latency_s": self.target_latency_s,
                    "observations": self._observations,
                    "decreases": self._decreases}


# --------------------------------------------------------------------------
# fault injection
# --------------------------------------------------------------------------

class FaultInjector:
    """Deterministic chaos harness over named injection points.

    Production code calls ``maybe_inject(point, key=...)`` at its risky
    sites; with no injector installed that is a no-op attribute check.  A
    test installs an injector (``with inject_faults(FaultInjector(...))``)
    and selected (point, key) pairs raise ``InjectedFault``.

    Decisions are *sticky and pure*: whether (point, key) fails is a hash of
    (seed, point, key) against the point's rate — the same key fails on
    every retry (so retry exhaustion and dead-lettering are exercised) and
    the same seed reproduces the identical failure set on every run.

    ``rates``     — point → probability in [0, 1] that a key fails;
    ``fail_keys`` — point → explicit keys that always fail (deterministic
                    acceptance tests: "kill candidate 'LR' and batch 1")."""

    def __init__(self, rates: Optional[Dict[str, float]] = None,
                 fail_keys: Optional[Dict[str, Iterable[Any]]] = None,
                 seed: int = 0):
        self.rates = {k: float(v) for k, v in (rates or {}).items()}
        self.fail_keys = {p: {str(k) for k in ks}
                          for p, ks in (fail_keys or {}).items()}
        self.seed = int(seed)
        self.fired: List[Tuple[str, str]] = []   # every raise, in order
        # parallel to ``fired``: the ambient span id each fault fired
        # inside (None when tracing was off) — chaos failures point at the
        # exact span in the trace timeline
        self.fired_spans: List[Optional[str]] = []
        self._auto_counts: Dict[str, int] = {}
        self._lock = threading.Lock()

    def should_fail(self, point: str, key: Any = None) -> bool:
        if key is None:
            with self._lock:
                key = self._auto_counts.get(point, 0)
                self._auto_counts[point] = key + 1
        key = str(key)
        if key in self.fail_keys.get(point, ()):
            return True
        rate = self.rates.get(point, 0.0)
        if rate <= 0.0:
            return False
        return _stable_uniform(self.seed, point, key) < rate

    def check(self, point: str, key: Any = None) -> None:
        """Raise ``InjectedFault`` when (point, key) is armed."""
        if self.should_fail(point, key):
            from .telemetry import current_span_id
            sid = current_span_id()
            with self._lock:
                self.fired.append((point, str(key)))
                self.fired_spans.append(sid)
            err = InjectedFault(
                f"injected fault at {point!r} (key={key!r})")
            err.span_id = sid
            raise err

    # -- installation ------------------------------------------------------
    def install(self) -> "FaultInjector":
        global _INJECTOR
        _INJECTOR = self
        return self

    def uninstall(self) -> None:
        global _INJECTOR
        if _INJECTOR is self:
            _INJECTOR = None

    def __enter__(self) -> "FaultInjector":
        return self.install()

    def __exit__(self, *exc) -> None:
        self.uninstall()


_INJECTOR: Optional[FaultInjector] = None


def maybe_inject(point: str, key: Any = None) -> None:
    """Injection-point hook: no-op unless a FaultInjector is installed."""
    inj = _INJECTOR
    if inj is not None:
        inj.check(point, key)


@contextmanager
def inject_faults(injector: FaultInjector):
    """Install ``injector`` for the dynamic extent (restores the previous)."""
    global _INJECTOR
    prev = _INJECTOR
    _INJECTOR = injector
    try:
        yield injector
    finally:
        _INJECTOR = prev


# Injection points wired through the execution layer.  Keys are stable
# identifiers (candidate model name, micro-batch index, stage uid) so chaos
# decisions survive retries and reorderings.
INJECTION_POINTS = {
    "selector.candidate_fit": "one (model × grid) candidate family fit",
    "selector.candidate_metric": "scoring one fitted candidate",
    "streaming.batch": "scoring one streaming micro-batch",
    "compiled.segment": "executing one fused device segment",
    "multihost.init": "jax distributed runtime initialization",
    "checkpoint.save": "committing a model/sweep bundle (after data write, "
                       "before atomic rename)",
    "checkpoint.load": "verifying a bundle's manifest + digests on load",
    "preemption": "a candidate/batch boundary's graceful-stop check",
    "serving.batch": "scoring one coalesced serving micro-batch",
    "serving.reload": "hot-swapping a newer model version into the engine",
    "lifecycle.retrain": "starting a policy-triggered lifecycle retrain",
    "lifecycle.promote": "committing a lifecycle promotion decision (after "
                         "the holdout gate, before the bundle write)",
    "supervisor.probe": "one subprocess-isolated device availability probe",
    "supervisor.heartbeat": "one heartbeat supervision tick",
    "supervisor.chunk_stall": "one host->device streaming chunk transfer "
                              "(fires as a stalled/hung link)",
    "supervisor.device_loss": "a device dropping out of the active mesh "
                              "mid-sweep (fit or scoring)",
    "memory.device_oom": "a device allocator exhausting HBM mid-sweep "
                         "(fires as RESOURCE_EXHAUSTED; routes to the "
                         "shrink-and-retry ladder, never the mesh shrink)",
    "memory.host_pressure": "one host RSS watchdog tick (fires as a "
                            "hard-watermark reading)",
}

"""OpWorkflowRunner / OpApp — run-type dispatch and CLI harness (reference:
core/src/main/scala/com/salesforce/op/OpWorkflowRunner.scala:296-365 and
OpApp.scala:130-213).

Run types: Train / Score / StreamingScore / Features / Evaluate — the same
five (OpWorkflowRunner.scala:358-365).  Profiling hooks replace
OpSparkListener: per-phase wall-clock + device memory stats collected into
``AppMetrics`` and delivered to completion callbacks.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterable, List, Optional

import numpy as np

from .checkpoint import (TrainingPreempted, preemption_guard,
                         shutdown_requested, write_json_atomic)
from .params import OpParams
from .profiling import AppMetrics, PhaseTimer
from .resilience import (FailureLog, RetryPolicy, maybe_inject,
                         use_failure_log)
from .workflow import Workflow, WorkflowModel


class RunType:
    TRAIN = "train"
    SCORE = "score"
    STREAMING_SCORE = "streamingScore"
    FEATURES = "features"
    EVALUATE = "evaluate"
    SERVE = "serve"
    LIFECYCLE = "lifecycle"

    ALL = (TRAIN, SCORE, STREAMING_SCORE, FEATURES, EVALUATE, SERVE,
           LIFECYCLE)


@dataclass
class OpWorkflowRunnerResult:
    """≙ OpWorkflowRunnerResult variants."""
    run_type: str
    model_summary: Optional[Dict[str, Any]] = None
    metrics: Optional[Dict[str, Any]] = None
    scores_location: Optional[str] = None
    app_metrics: Optional[AppMetrics] = None
    failure_log: Optional[FailureLog] = None
    # streaming micro-batches that exhausted their retries:
    # [{"index", "error", "batch"}] — the batch rides along for reprocessing
    dead_letters: List[Dict[str, Any]] = field(default_factory=list)
    # the run's Tracer when telemetryParams enabled tracing (telemetry.py)
    tracer: Optional[Any] = None


class OpWorkflowRunner:
    """≙ OpWorkflowRunner.scala:296."""

    def __init__(self, workflow: Optional[Workflow] = None,
                 train_reader=None, score_reader=None,
                 evaluator=None, evaluation_feature=None,
                 features_to_compute=None,
                 retry_policy: Optional[RetryPolicy] = None,
                 failure_log: Optional[FailureLog] = None,
                 dead_letter_max: int = 256):
        # score / streaming-score / evaluate / features run types load a
        # saved model and need no workflow; only train requires one
        self.workflow = workflow
        self.train_reader = train_reader
        self.score_reader = score_reader
        self.evaluator = evaluator
        self.evaluation_feature = evaluation_feature
        self.features_to_compute = features_to_compute
        # resilience: transient streaming-batch failures retry per policy;
        # exhausted batches dead-letter instead of killing the stream.
        # The DLQ is bounded (a persistently-failing stream would otherwise
        # grow it without limit): past ``dead_letter_max`` the OLDEST entry
        # is evicted — its index stays in the failure log even though the
        # batch payload is gone
        self.retry_policy = retry_policy
        self.failure_log = failure_log
        self.dead_letter_max = max(1, int(dead_letter_max))
        self._completion_callbacks: List[Callable[[AppMetrics], None]] = []

    def add_application_completion_handler(self, fn: Callable[[AppMetrics], None]):
        """≙ addApplicationCompletionHandler (OpWorkflowRunner.scala:300)."""
        self._completion_callbacks.append(fn)

    # -- dispatch (≙ run:296-316) -----------------------------------------
    def run(self, run_type: str, params: OpParams) -> OpWorkflowRunnerResult:
        # telemetryParams: traceDir turns the whole run into a traced run —
        # every phase/selector/checkpoint span lands in one tracer, exported
        # as Chrome-trace JSON + telemetry.json when the run finishes
        import contextlib

        from .telemetry import Tracer, use_tracer
        # aotParams: the "enabled" knob is a process-wide kill switch —
        # train stops exporting executables into bundles, load stops
        # installing them (JIT path everywhere)
        ap = params.aot or {}
        if ap.get("enabled") is False:
            from .aot import set_aot_enabled
            set_aot_enabled(False)
        if ap.get("ladderMax") is not None:
            os.environ["TRANSMOGRIFAI_AOT_LADDER_MAX"] = str(ap["ladderMax"])
        # registryParams: configure the compiled-program registry (root,
        # byte budgets, kill switch).  When no root is pinned anywhere it
        # defaults next to the sweep checkpoints (see the run-type blocks),
        # so a standing host accumulates its own warm registry
        rp = params.registry or {}
        from .aot_registry import configure as configure_registry
        configure_registry(
            root=rp.get("root"),
            enabled=(bool(rp["enabled"]) if rp.get("enabled") is not None
                     else None),
            cap_bytes=rp.get("capBytes"),
            keep_min=rp.get("keepMin"),
            cache_cap_bytes=rp.get("cacheCapBytes"))
        # meshParams: the mesh decision is made per-fit from the environment
        # (parallel/mesh.py), so the per-run knobs ride the env knobs
        mp = params.mesh or {}
        if mp.get("enabled") is not None:
            os.environ["TRANSMOGRIFAI_TPU_MESH"] = \
                "1" if mp["enabled"] else "0"
        if mp.get("modelWidth") is not None:
            os.environ["TRANSMOGRIFAI_TPU_MESH_MODEL"] = str(mp["modelWidth"])
        if mp.get("chunkBytes") is not None:
            os.environ["TRANSMOGRIFAI_DEVICE_CHUNK_BYTES"] = \
                str(mp["chunkBytes"])
        if mp.get("minRows") is not None:
            os.environ["TRANSMOGRIFAI_TPU_MESH_MIN_ROWS"] = \
                str(mp["minRows"])
        # supervisorParams: same pattern — the supervisor reads the process
        # env per call, so run-scoped knobs ride the env knobs
        sup = params.supervisor or {}
        if sup.get("enabled") is not None:
            os.environ["TRANSMOGRIFAI_SUPERVISOR"] = \
                "1" if sup["enabled"] else "0"
        if sup.get("probeTimeoutS") is not None:
            os.environ["TRANSMOGRIFAI_PROBE_TIMEOUT_S"] = \
                str(sup["probeTimeoutS"])
        if sup.get("probeBackoffs") is not None:
            b = sup["probeBackoffs"]
            os.environ["TRANSMOGRIFAI_PROBE_BACKOFFS"] = \
                ",".join(str(x) for x in b) \
                if isinstance(b, (list, tuple)) else str(b)
        if sup.get("chunkDeadlineS") is not None:
            os.environ["TRANSMOGRIFAI_CHUNK_DEADLINE_S"] = \
                str(sup["chunkDeadlineS"])
        if sup.get("sweepRecoveries") is not None:
            os.environ["TRANSMOGRIFAI_SWEEP_RECOVERIES"] = \
                str(sup["sweepRecoveries"])
        if sup.get("outageDir") is not None:
            os.environ["TRANSMOGRIFAI_OUTAGE_DIR"] = str(sup["outageDir"])
        if sup.get("heartbeatS") is not None:
            os.environ["TRANSMOGRIFAI_HEARTBEAT_S"] = str(sup["heartbeatS"])
        # hostgroupParams: cross-host liveness knobs ride the env the same
        # way (hostgroup.py reads them per call, so launcher-exported values
        # and per-run overrides compose)
        hg_params = params.hostgroup or {}
        if hg_params.get("beatIntervalS") is not None:
            os.environ["TRANSMOGRIFAI_HOSTGROUP_BEAT_S"] = \
                str(hg_params["beatIntervalS"])
        if hg_params.get("livenessTimeoutS") is not None:
            os.environ["TRANSMOGRIFAI_HOSTGROUP_LIVENESS_S"] = \
                str(hg_params["livenessTimeoutS"])
        if hg_params.get("barrierTimeoutS") is not None:
            os.environ["TRANSMOGRIFAI_HOSTGROUP_BARRIER_S"] = \
                str(hg_params["barrierTimeoutS"])
        if hg_params.get("initTimeoutS") is not None:
            os.environ["TRANSMOGRIFAI_HOSTGROUP_INIT_S"] = \
                str(hg_params["initTimeoutS"])
        if hg_params.get("distributed") is not None:
            os.environ["TRANSMOGRIFAI_HOSTGROUP_DISTRIBUTED"] = \
                "1" if hg_params["distributed"] else "0"
        # memoryParams: the governor reads the env per call (preflight plan
        # per fold group, ladder per retry), so run-scoped knobs ride the
        # env knobs exactly like the supervisor's
        memp = params.memory or {}
        if memp.get("enabled") is not None:
            os.environ["TRANSMOGRIFAI_MEMORY_GOVERNOR"] = \
                "1" if memp["enabled"] else "0"
        if memp.get("deviceMemBytes") is not None:
            os.environ["TRANSMOGRIFAI_DEVICE_MEM_BYTES"] = \
                str(memp["deviceMemBytes"])
        if memp.get("headroom") is not None:
            os.environ["TRANSMOGRIFAI_MEMORY_HEADROOM"] = \
                str(memp["headroom"])
        if memp.get("oomRecoveries") is not None:
            os.environ["TRANSMOGRIFAI_OOM_RECOVERIES"] = \
                str(memp["oomRecoveries"])
        if memp.get("hostSoftBytes") is not None:
            os.environ["TRANSMOGRIFAI_HOST_MEM_SOFT_BYTES"] = \
                str(memp["hostSoftBytes"])
        if memp.get("hostHardBytes") is not None:
            os.environ["TRANSMOGRIFAI_HOST_MEM_HARD_BYTES"] = \
                str(memp["hostHardBytes"])
        if memp.get("watchdogIntervalS") is not None:
            os.environ["TRANSMOGRIFAI_RSS_WATCHDOG_S"] = \
                str(memp["watchdogIntervalS"])
        # qualityParams: the firewall resolves QualityConfig from the env
        # at each ingestion point (workflow read, reader screen, serving
        # engine), so run-scoped knobs ride the env like the blocks above
        qp = params.quality or {}
        if qp.get("policy") is not None:
            os.environ["TRANSMOGRIFAI_QUALITY_POLICY"] = str(qp["policy"])
        if qp.get("maxQuarantineFraction") is not None:
            os.environ["TRANSMOGRIFAI_MAX_QUARANTINE_FRACTION"] = \
                str(qp["maxQuarantineFraction"])
        if qp.get("enabled") is not None:
            os.environ["TRANSMOGRIFAI_QUALITY"] = \
                "1" if qp["enabled"] else "0"
        # obsParams (ISSUE 20): the training control plane — admin HTTP
        # endpoint + crash flight recorder.  Off by default; the env knob
        # composes with the per-rank port a host-group launcher exported
        obsp = params.obs or {}
        if obsp.get("port") is not None:
            os.environ["TRANSMOGRIFAI_OBS_PORT"] = str(obsp["port"])
        if obsp.get("blackboxSpans") is not None:
            os.environ["TRANSMOGRIFAI_BLACKBOX_SPANS"] = \
                str(obsp["blackboxSpans"])
        if obsp.get("blackboxPath") is not None:
            os.environ["TRANSMOGRIFAI_BLACKBOX_PATH"] = \
                str(obsp["blackboxPath"])
        tele = params.telemetry or {}
        trace_dir = tele.get("traceDir")
        enabled = bool(tele.get("enabled", trace_dir is not None))
        # telemetryParams.traceparent (or the TRANSMOGRIFAI_TRACEPARENT a
        # supervising parent exported) joins this run's spans — including a
        # lifecycle retrain — to the caller's distributed trace
        parent = None
        if enabled:
            from .telemetry import TraceContext
            tp = tele.get("traceparent")
            parent = (TraceContext.parse(str(tp)) if tp
                      else TraceContext.from_env())
        # inside a host-group rank the tracer carries the rank so per-rank
        # exports merge into one labelled multi-host timeline (trace-merge)
        from .parallel import hostgroup as _hostgroup
        hg_rank = _hostgroup.current_rank() \
            if _hostgroup.hostgroup_env_present() else None
        tracer = Tracer(run_name=f"run:{run_type}", parent=parent,
                        rank=hg_rank) if enabled else None
        ctx = use_tracer(tracer) if tracer is not None \
            else contextlib.nullcontext()
        # opt-in heartbeat supervision for the whole run: background
        # re-probes feed the device-runtime breaker + AVAILABLE/DEGRADED/
        # OUTAGE gauges while the run is in flight.  The probe is a fresh
        # child; on an accelerator this process owns the chip, so the child
        # reports outage/cpu for as long as the run holds it (see Heartbeat)
        hb = None
        try:
            hb_interval = float(os.environ.get("TRANSMOGRIFAI_HEARTBEAT_S",
                                               "0"))
        except ValueError:
            hb_interval = 0.0
        if hb_interval > 0:
            from .parallel.supervisor import Heartbeat, supervisor_enabled
            if supervisor_enabled():
                hb = Heartbeat(interval_s=hb_interval).start()
        # host-side RSS watchdog (ISSUE 15): runs whenever the governor is
        # on, a watermark is configured, and a cadence is set — sheds
        # pretrace queues/transfer caches at the soft watermark, trips the
        # typed HostMemoryPressure flag at the hard one
        wd = None
        from .parallel import memory as _memory
        wd_interval = _memory.watchdog_interval_s()
        if (wd_interval > 0 and _memory.memory_governor_enabled()
                and (os.environ.get("TRANSMOGRIFAI_HOST_MEM_SOFT_BYTES")
                     or os.environ.get("TRANSMOGRIFAI_HOST_MEM_HARD_BYTES"))):
            wd = _memory.RssWatchdog(interval_s=wd_interval).start()
            _memory.install_watchdog(wd)
        # training control plane (ISSUE 20): when an obs port is configured
        # for a train/lifecycle run, start the admin endpoint (/metrics,
        # /statusz, /traces) and install the flight recorder.  Both are
        # no-ops when TRANSMOGRIFAI_OBS_PORT is unset — no socket, no
        # recorder, no new spans.
        obs_server = None
        recorder = None
        if run_type in (RunType.TRAIN, RunType.LIFECYCLE):
            from . import obsv
            if obsv.obs_enabled():
                recorder = obsv.install_recorder(obsv.FlightRecorder())
                obs_server = obsv.maybe_start_obs_server()
                obsv.BOARD.publish(runType=run_type, phase="starting",
                                   pid=os.getpid())
        hg = None
        guard = None
        # the outer guard only wraps the run types the control plane
        # covers — serve/score keep their own signal handling untouched.
        # Re-entrant with the nested train/lifecycle guards (shared flag).
        guard_ctx = (preemption_guard(run_type)
                     if run_type in (RunType.TRAIN, RunType.LIFECYCLE)
                     else contextlib.nullcontext())
        try:
            with ctx, guard_ctx as guard:
                # inside a launch_hosts rank: join the host group (start the
                # heartbeat, optionally init jax.distributed, pass the init
                # barrier) before dispatch; post this rank's done file after
                hg = _hostgroup.maybe_init_hostgroup()
                result = self._run_dispatch(run_type, params)
                if hg is not None:
                    hg.mark_done({"runType": run_type, "ok": True})
        except BaseException as e:
            # crash flight recorder: DataQualityError / MemoryExhaustedError
            # / HostLostError / anything else unhandled dumps the last ring
            # of telemetry before the error propagates
            if recorder is not None:
                from . import obsv
                obsv.dump_blackbox(reason=type(e).__name__, error=e)
            raise
        finally:
            # a graceful SIGTERM stop never reaches the except arm (the
            # guard converts it into a drained, successful result) — dump
            # the ring here so the preemption postmortem exists too
            if recorder is not None and guard is not None \
                    and guard.stop_requested:
                from . import obsv
                obsv.dump_blackbox(
                    reason="preempted",
                    error=RuntimeError(guard.reason or "graceful stop"))
            if obs_server is not None:
                obs_server.stop()
            if recorder is not None:
                from . import obsv
                obsv.install_recorder(None)
            if hg is not None:
                hg.close()
            if hb is not None:
                hb.stop()
            if wd is not None:
                _memory.install_watchdog(None)
                wd.stop()
        if tracer is not None:
            result.tracer = tracer
            if trace_dir:
                self._export_telemetry(tracer, trace_dir, run_type, result,
                                       rank=hg_rank)
        return result

    def _run_dispatch(self, run_type: str,
                      params: OpParams) -> OpWorkflowRunnerResult:
        timer = PhaseTimer()
        with timer.phase(f"run:{run_type}"):
            if run_type == RunType.TRAIN:
                result = self._train(params, timer)
            elif run_type == RunType.SCORE:
                result = self._score(params, timer)
            elif run_type == RunType.STREAMING_SCORE:
                result = self._streaming_score(params, timer)
            elif run_type == RunType.FEATURES:
                result = self._features(params, timer)
            elif run_type == RunType.EVALUATE:
                result = self._evaluate(params, timer)
            elif run_type == RunType.SERVE:
                result = self._serve(params, timer)
            elif run_type == RunType.LIFECYCLE:
                result = self._lifecycle(params, timer)
            else:
                raise ValueError(f"unknown run type {run_type!r}; "
                                 f"expected one of {RunType.ALL}")
        metrics = timer.app_metrics(tag=params.custom_tag_name)
        result.app_metrics = metrics
        for cb in self._completion_callbacks:
            cb(metrics)
        return result

    @staticmethod
    def _export_telemetry(tracer, trace_dir: str, run_type: str,
                          result: OpWorkflowRunnerResult,
                          rank: "Optional[int]" = None) -> None:
        """Write <trace_dir>/trace-<run_type>.json (Chrome trace events,
        Perfetto-loadable) and telemetry.json (summary).  Inside a
        host-group rank the filenames carry the rank so N ranks sharing one
        trace_dir never clobber each other (``trace-merge`` stitches them).
        Best-effort: a full disk must not fail a finished run."""
        from .telemetry import write_telemetry_summary
        suffix = "" if rank is None else f"-rank{rank}"
        try:
            os.makedirs(trace_dir, exist_ok=True)
            trace_path = os.path.join(
                trace_dir, f"trace-{run_type}{suffix}.json")
            tracer.export_chrome_trace(trace_path)
            write_telemetry_summary(
                os.path.join(trace_dir, f"telemetry{suffix}.json"), tracer)
            if isinstance(result.metrics, dict):
                result.metrics["traceFile"] = trace_path
        except Exception as e:  # noqa: BLE001 — diagnostics only
            from .resilience import record_failure
            record_failure("runner.telemetry", "swallowed", e,
                           point="runner.telemetry", trace_dir=trace_dir)

    # -- run types --------------------------------------------------------
    def _train(self, params: OpParams, timer: PhaseTimer) -> OpWorkflowRunnerResult:
        """≙ :163-196: train, save model + summary."""
        if self.workflow is None:
            raise ValueError(
                "run-type 'train' needs a Workflow — construct the runner "
                "with OpWorkflowRunner(workflow, ...)")
        if self.train_reader is not None:
            self.workflow.set_reader(self.train_reader)
        if params.stage_params:
            self.workflow.apply_stage_params(params)
        if params.racing:
            self.workflow.apply_racing_params(params.racing)
        # with a checkpoint location, the selector sweep persists completed
        # candidates under <location>/selector-sweep — a rerun of the same
        # command resumes instead of restarting
        resume_from = None
        if params.checkpoint_location:
            resume_from = os.path.join(params.checkpoint_location,
                                       "selector-sweep")
            # default the compiled-program registry next to the sweep state:
            # the checkpoint dir outlives /tmp, so every re-train (and every
            # pool worker / lifecycle retrain pointed at the same location)
            # installs executables instead of compiling.  The persistent XLA
            # compile cache stays where package import put it.
            from .aot_registry import configure as configure_registry
            from .aot_registry import registry_allowed, registry_root
            if registry_allowed() and registry_root() is None:
                configure_registry(root=os.path.join(
                    params.checkpoint_location, "registry"))
        try:
            with timer.phase("train"):
                model = self.workflow.train(resume_from=resume_from)
        except TrainingPreempted as e:
            # graceful preemption is an outcome, not a crash: report the
            # resume point so the orchestrator can relaunch the same command
            return OpWorkflowRunnerResult(
                RunType.TRAIN,
                metrics={"preempted": True, "reason": str(e),
                         "resumeFrom": e.resume_from},
                failure_log=e.failure_log)
        summary = None
        if params.model_location:
            with timer.phase("save"):
                model.save(params.model_location)
        with timer.phase("summary"):
            summary = model.summary()
            if params.model_location:
                with open(os.path.join(params.model_location,
                                       "model-summary.json"), "w") as fh:
                    json.dump(summary, fh, indent=2, default=str)
        return OpWorkflowRunnerResult(
            RunType.TRAIN, model_summary=summary,
            failure_log=getattr(model, "failure_log", None))

    def _load_model(self, params: OpParams) -> WorkflowModel:
        if not params.model_location:
            raise ValueError("model_location is required")
        model = WorkflowModel.load(params.model_location)
        if self.score_reader is not None:
            model.set_reader(self.score_reader)
        elif self.workflow is not None and self.workflow.reader is not None:
            # no dedicated scoring reader: score the app's data source (the
            # reference's OpApp subclasses usually pass an explicit
            # scoringReader; falling back keeps `--run-type score` working
            # out of the box for generated starter apps)
            model.set_reader(self.workflow.reader)
        return model

    def _score(self, params: OpParams, timer: PhaseTimer) -> OpWorkflowRunnerResult:
        """≙ :204-223: load model, score, optionally evaluate, write scores."""
        model = self._load_model(params)
        with timer.phase("score"):
            scored = model.score()
        metrics = None
        if self.evaluator is not None:
            with timer.phase("evaluate"):
                metrics = model.evaluate(self.evaluator)
        loc = params.write_location
        if loc:
            with timer.phase("write"):
                os.makedirs(loc, exist_ok=True)
                _write_scores(scored, os.path.join(loc, "scores.jsonl"))
        if metrics is not None and params.metrics_location:
            os.makedirs(params.metrics_location, exist_ok=True)
            with open(os.path.join(params.metrics_location, "metrics.json"),
                      "w") as fh:
                json.dump(metrics, fh, indent=2, default=str)
        return OpWorkflowRunnerResult(RunType.SCORE, metrics=metrics,
                                      scores_location=loc)

    def _streaming_score(self, params: OpParams, timer: PhaseTimer) -> OpWorkflowRunnerResult:
        """≙ :225-263: micro-batch scoring loop over a streaming reader
        (host loop feeding the compiled score fn, SURVEY §2.6 P6).

        Resilient: each batch retries per ``self.retry_policy`` (exponential
        backoff; optional per-attempt watchdog deadline so a native hang
        cannot stall the stream), and a batch that exhausts its retries is
        routed to the result's dead-letter list — the stream continues.
        Every retry and dead-letter lands in the result's ``failure_log``."""
        model = self._load_model(params)
        if self.score_reader is None or not hasattr(self.score_reader, "stream"):
            raise ValueError("streaming score requires a StreamingReader")
        if hasattr(self.score_reader, "set_raw_features"):
            self.score_reader.set_raw_features(
                [f for f in model.raw_features if not f.is_response])
        score_fn = model.score_fn()
        policy = self.retry_policy or RetryPolicy(
            max_attempts=3, base_delay_s=0.02, max_delay_s=0.5)
        flog = self.failure_log if self.failure_log is not None else FailureLog()
        dead_letters: List[Dict[str, Any]] = []
        evicted_count = 0

        def dead_letter(entry: Dict[str, Any]) -> None:
            # bounded DLQ: oldest-first eviction past dead_letter_max, so a
            # persistently failing stream cannot grow memory without limit
            nonlocal evicted_count
            dead_letters.append(entry)
            if len(dead_letters) <= self.dead_letter_max:
                return
            victim = dead_letters.pop(0)
            if evicted_count == 0:
                flog.record("streaming", "degraded",
                            f"dead-letter queue reached its bound "
                            f"({self.dead_letter_max}); evicting oldest "
                            "entries — reprocess from the failure log",
                            point="streaming.batch",
                            first_evicted_index=victim["index"])
            evicted_count += 1
            from .telemetry import REGISTRY
            REGISTRY.counter("streaming.dead_letters_evicted_total").inc()
        loc = params.write_location
        if loc:
            os.makedirs(loc, exist_ok=True)
        # durable stream position: scores_<j>.jsonl is written BEFORE the
        # offsets file advances to j+1, so a crash between the two re-scores
        # batch j into the same file (idempotent) instead of losing it
        offsets_path = None
        next_batch = 0
        if params.checkpoint_location:
            os.makedirs(params.checkpoint_location, exist_ok=True)
            offsets_path = os.path.join(params.checkpoint_location,
                                        "stream-offsets.json")
            if os.path.exists(offsets_path):
                try:
                    with open(offsets_path) as fh:
                        next_batch = int(json.load(fh).get("nextBatch", 0))
                except (OSError, ValueError) as e:
                    flog.record("streaming", "degraded", e,
                                point="checkpoint.load",
                                fallback="restart from batch 0")
            if next_batch:
                flog.record("streaming", "resumed",
                            f"offsets file: {next_batch} batch(es) already "
                            "scored", point="checkpoint.load",
                            next_batch=next_batch)
        n_batches = 0
        was_preempted = False
        # double-buffered pipeline (SURVEY §2.6 P6): scoring dispatches
        # asynchronously on the device, so batch i computes while the host
        # serializes batch i-1's results — the d2h pull in _write_scores is
        # the host stage of the pipeline
        pending = None  # (index, scored)

        def flush():
            nonlocal pending
            if pending is not None:
                j, prev = pending
                if loc:
                    with timer.phase(f"write_{j}"):
                        _write_scores(prev,
                                      os.path.join(loc, f"scores_{j}.jsonl"))
                if offsets_path:
                    write_json_atomic(offsets_path, {"nextBatch": j + 1})
            pending = None

        # ambient quality config: StreamingReader micro-batches assemble
        # through Reader.generate_batch, which screens records against the
        # run's policy — a poison record quarantines per-row (typed
        # violation in the failure log) instead of dead-lettering its
        # whole micro-batch after retries
        from .quality import QualityConfig, use_quality
        qcfg = QualityConfig.resolve(params.quality)
        quality_scope = (use_quality(qcfg) if qcfg.enabled
                         else contextlib.nullcontext())
        try:
            with use_failure_log(flog), preemption_guard("streaming"), \
                    quality_scope:
                for i, batch in enumerate(self.score_reader.stream()):
                    if i < next_batch:
                        continue   # already scored by a previous run
                    if shutdown_requested(key=f"batch-{i}"):
                        # graceful stop at the batch boundary: the finally
                        # below flushes the last scored batch + its offset
                        was_preempted = True
                        break

                    def attempt(b=batch, j=i):
                        maybe_inject("streaming.batch", key=j)
                        return score_fn(b)

                    try:
                        with timer.phase(f"batch_{i}"):
                            scored = policy.call(
                                attempt, stage="streaming",
                                point="streaming.batch", key=i, log=flog,
                                description=f"streaming batch {i}")
                    except Exception as e:  # noqa: BLE001 — dead-letter
                        flog.record("streaming", "dead_letter", e,
                                    point="streaming.batch", batch_index=i,
                                    attempt=policy.max_attempts)
                        dead_letter(
                            {"index": i,
                             "error": f"{type(e).__name__}: {e}",
                             "batch": batch})
                        # persist the predecessor before moving on so a
                        # later crash cannot lose it
                        flush()
                        continue
                    flush()
                    pending = (i, scored)
                    n_batches += 1
        finally:
            # a mid-stream failure must not lose the last scored batch
            flush()
        return OpWorkflowRunnerResult(
            RunType.STREAMING_SCORE, scores_location=loc,
            metrics={"batches": n_batches,
                     "skippedBatches": next_batch,
                     "preempted": was_preempted,
                     "deadLetterBatches": [d["index"] for d in dead_letters],
                     "deadLettersEvicted": evicted_count,
                     "failures": flog.summary()},
            failure_log=flog, dead_letters=dead_letters)

    def _features(self, params: OpParams, timer: PhaseTimer) -> OpWorkflowRunnerResult:
        """≙ :265: computeDataUpTo a feature and write it."""
        model = self._load_model(params)
        feature = self.features_to_compute
        with timer.phase("features"):
            batch = model.compute_data_up_to(feature)
        loc = params.write_location
        if loc:
            os.makedirs(loc, exist_ok=True)
            _write_scores(batch, os.path.join(loc, "features.jsonl"))
        return OpWorkflowRunnerResult(RunType.FEATURES, scores_location=loc)

    def _evaluate(self, params: OpParams, timer: PhaseTimer) -> OpWorkflowRunnerResult:
        """≙ :272-285."""
        model = self._load_model(params)
        with timer.phase("evaluate"):
            metrics = model.evaluate(self.evaluator, self.evaluation_feature)
        if params.metrics_location:
            os.makedirs(params.metrics_location, exist_ok=True)
            with open(os.path.join(params.metrics_location, "metrics.json"),
                      "w") as fh:
                json.dump(metrics, fh, indent=2, default=str)
        return OpWorkflowRunnerResult(RunType.EVALUATE, metrics=metrics)

    def _serve(self, params: OpParams, timer: PhaseTimer
               ) -> OpWorkflowRunnerResult:
        """Online scoring: block inside the HTTP serve loop until
        SIGTERM/SIGINT, then drain and return.  Serving knobs ride in
        ``params.serving`` (see ``OpParams``)."""
        from .serving.overload import OverloadConfig
        from .serving.server import serve_main
        sv = params.serving or {}
        model_root = sv.get("modelRoot")
        if bool(params.model_location) == bool(model_root):
            raise ValueError("run-type 'serve' needs exactly one of "
                             "--model-location (single bundle) or "
                             "servingParams.modelRoot (multi-tenant)")
        workers = int(sv.get("workers", 1))
        with timer.phase("serve"):
            if workers > 1:
                import dataclasses

                from .serving.pool import pool_serve_main
                pool_serve_main(
                    params.model_location, workers=workers,
                    host=sv.get("host", "127.0.0.1"),
                    port=int(sv.get("port", 8180)),
                    admin_port=int(sv.get("adminPort", 0)),
                    max_batch=int(sv.get("maxBatch", 64)),
                    queue_bound=int(sv.get("queueBound", 256)),
                    request_deadline_s=sv.get("requestDeadlineS", 30.0),
                    reload_poll_s=float(sv.get("reloadPollS", 10.0)),
                    overload=dataclasses.asdict(
                        OverloadConfig.from_params(sv)),
                    wire_format=sv.get("wireFormat", "auto"),
                    model_root=model_root,
                    tenant_max_active=sv.get("tenantMaxActive"),
                    tenant_memory_budget_bytes=sv.get(
                        "tenantMemoryBudgetBytes"))
                # pool workers resolve the firewall policy from the env set
                # by run() (qualityParams.policy → TRANSMOGRIFAI_QUALITY_
                # POLICY), so no kwarg threading is needed here
            else:
                serve_main(params.model_location,
                           host=sv.get("host", "127.0.0.1"),
                           port=int(sv.get("port", 8180)),
                           max_batch=int(sv.get("maxBatch", 64)),
                           linger_ms=float(sv.get("lingerMs", 2.0)),
                           queue_bound=int(sv.get("queueBound", 256)),
                           request_deadline_s=sv.get("requestDeadlineS",
                                                     30.0),
                           reload_poll_s=float(sv.get("reloadPollS", 10.0)),
                           overload=OverloadConfig.from_params(sv),
                           wire_format=sv.get("wireFormat", "auto"),
                           model_root=model_root,
                           tenant_max_active=sv.get("tenantMaxActive"),
                           tenant_memory_budget_bytes=sv.get(
                               "tenantMemoryBudgetBytes"),
                           quality_policy=(params.quality or {}).get(
                               "policy"))
        return OpWorkflowRunnerResult(RunType.SERVE)

    def _lifecycle(self, params: OpParams, timer: PhaseTimer
                   ) -> OpWorkflowRunnerResult:
        """Drift-gated retrain loop over a versioned checkpoint root.
        Knobs ride in ``params.lifecycle`` (see ``OpParams``); the live
        feed is the runner's ``score_reader``, holdout defaults to the
        train reader."""
        if self.workflow is None:
            raise ValueError("run-type 'lifecycle' needs a workflow")
        if not params.model_location:
            raise ValueError("run-type 'lifecycle' needs --model-location")
        from .lifecycle.service import lifecycle_main
        with timer.phase("lifecycle"):
            result = lifecycle_main(
                self.workflow, params.model_location,
                evaluator=self.evaluator,
                live_reader=self.score_reader,
                holdout_reader=self.train_reader or self.workflow.reader,
                config=params.lifecycle or {})
        return OpWorkflowRunnerResult(RunType.LIFECYCLE, metrics=result)


def _write_scores(batch, path: str):
    n = len(batch)
    with open(path, "w") as fh:
        for i in range(n):
            row = {}
            for name, col in batch.items():
                if isinstance(col.values, dict):
                    row[name] = {k: np.asarray(v)[i].tolist()
                                 for k, v in col.values.items()}
                else:
                    v = np.asarray(col.values)[i]
                    row[name] = v.tolist() if hasattr(v, "tolist") else v
            fh.write(json.dumps(row, default=str) + "\n")


class OpApp:
    """≙ OpApp.scala: CLI arg parsing → runner dispatch.

    Subclasses implement ``build_workflow()`` and optionally the readers.
    """

    def build_workflow(self) -> Workflow:
        raise NotImplementedError

    def make_runner(self) -> OpWorkflowRunner:
        return OpWorkflowRunner(self.build_workflow())

    def parse_args(self, argv: Optional[List[str]] = None):
        """≙ OpApp.parseArgs (scopt, OpApp.scala:130-176)."""
        p = argparse.ArgumentParser(description=type(self).__name__)
        p.add_argument("--run-type", required=True, choices=RunType.ALL)
        p.add_argument("--model-location")
        p.add_argument("--read-location")
        p.add_argument("--write-location")
        p.add_argument("--metrics-location")
        p.add_argument("--checkpoint-location",
                       help="directory for sweep checkpoints + streaming "
                            "offsets; rerunning the same command resumes")
        p.add_argument("--param-location",
                       help="json file of OpParams")
        p.add_argument("--no-racing", action="store_true",
                       help="run the full fold x grid sweep instead of "
                            "successive-halving racing")
        p.add_argument("--racing-eta", type=float,
                       help="racing reduction factor (keep top 1/eta per "
                            "family after the fold-0 screen)")
        p.add_argument("--racing-min-survivors", type=int,
                       help="never race a family below this many surviving "
                            "grid points")
        p.add_argument("--trace-dir",
                       help="trace this run and write Chrome-trace JSON + "
                            "telemetry.json into this directory")
        p.add_argument("--traceparent",
                       help="W3C traceparent header value joining this run "
                            "to the caller's distributed trace (defaults "
                            "to $TRANSMOGRIFAI_TRACEPARENT)")
        p.add_argument("--no-aot", action="store_true",
                       help="disable AOT-serialized executables: train "
                            "saves JIT-only bundles, load/serve recompiles "
                            "instead of installing shipped executables")
        p.add_argument("--registry-root",
                       help="compiled-program registry directory (default: "
                            "<checkpoint-location>/registry, or "
                            "$TRANSMOGRIFAI_AOT_REGISTRY); train publishes "
                            "executables into it, every fresh train / "
                            "worker / tenant installs from it")
        p.add_argument("--no-registry", action="store_true",
                       help="disable the compiled-program registry (no "
                            "publish, no install; pre-registry compile "
                            "behavior)")
        p.add_argument("--mesh", action="store_true",
                       help="force the mesh-sharded CV sweep on regardless "
                            "of the row-count heuristic")
        p.add_argument("--no-mesh", action="store_true",
                       help="disable mesh sharding (single-device sweep)")
        p.add_argument("--mesh-model-width", type=int,
                       help="width of the model axis carved out of the "
                            "device mesh (grid candidates shard over it)")
        p.add_argument("--mesh-chunk-bytes", type=int,
                       help="host->device streaming chunk budget in bytes "
                            "(peak host staging stays <= 2x this)")
        p.add_argument("--no-supervisor", action="store_true",
                       help="disable device-runtime supervision: no "
                            "degrade-to-surviving-mesh sweep recovery, no "
                            "heartbeat; device errors propagate unchanged")
        p.add_argument("--no-memory-governor", action="store_true",
                       help="disable memory governance: no preflight "
                            "device-memory planning, no OOM shrink-and-"
                            "retry ladder, no RSS watchdog; allocator "
                            "errors propagate unchanged")
        p.add_argument("--device-mem-bytes", type=int,
                       help="per-device memory budget the preflight "
                            "planner plans against (overrides "
                            "device.memory_stats() discovery)")
        p.add_argument("--hosts", type=int, default=1,
                       help="launch this command across N supervised local "
                            "processes (ranked host group with heartbeats, "
                            "jax.distributed init, lost-host relaunch); "
                            "1 = run in-process")
        p.add_argument("--hosts-run-dir",
                       help="host-group run directory (heartbeats, logs, "
                            "outage records); default: a temp dir")
        p.add_argument("--quality-policy",
                       choices=["strict", "coerce", "quarantine", "off"],
                       help="data-quality firewall policy: strict rejects "
                            "any schema violation, coerce (default) "
                            "repairs what it can and rejects only "
                            "non-coercible/non-finite values, quarantine "
                            "tolerates only unknown fields, off disables "
                            "the firewall")
        p.add_argument("--max-quarantine-fraction", type=float,
                       help="abort training with DataQualityError when "
                            "more than this fraction of rows is "
                            "quarantined (default 0.1)")
        p.add_argument("--no-quality", action="store_true",
                       help="disable the data-quality firewall entirely "
                            "(schema screening, quarantine accounting and "
                            "non-finite guards)")
        p.add_argument("--obs-port", type=int,
                       help="training control plane: serve GET /metrics, "
                            "/statusz and /traces on this port while the "
                            "run is in flight, and arm the crash flight "
                            "recorder (blackbox.json).  Inside a host "
                            "group the launcher keeps this port for the "
                            "merged rank panel and rank r serves on "
                            "port+1+r.  Unset/0 = off (no socket, no "
                            "recorder)")
        return p.parse_args(argv)

    def main(self, argv: Optional[List[str]] = None) -> OpWorkflowRunnerResult:
        args = self.parse_args(argv)
        params = (OpParams.load(args.param_location)
                  if args.param_location else OpParams())
        if args.model_location:
            params.model_location = args.model_location
        if args.write_location:
            params.write_location = args.write_location
        if args.metrics_location:
            params.metrics_location = args.metrics_location
        if args.checkpoint_location:
            params.checkpoint_location = args.checkpoint_location
        if args.read_location:
            from .params import ReaderParams
            params.reader_params.setdefault("default", ReaderParams()).path = \
                args.read_location
        if args.no_racing:
            params.racing["enabled"] = False
        if args.racing_eta is not None:
            params.racing["eta"] = args.racing_eta
        if args.racing_min_survivors is not None:
            params.racing["minSurvivors"] = args.racing_min_survivors
        if args.trace_dir:
            params.telemetry["traceDir"] = args.trace_dir
        if args.traceparent:
            params.telemetry["traceparent"] = args.traceparent
        if args.no_aot:
            params.aot["enabled"] = False
        if args.registry_root:
            params.registry["root"] = args.registry_root
        if args.no_registry:
            params.registry["enabled"] = False
        if args.mesh or args.no_mesh:
            params.mesh["enabled"] = bool(args.mesh and not args.no_mesh)
        if args.mesh_model_width is not None:
            params.mesh["modelWidth"] = args.mesh_model_width
        if args.mesh_chunk_bytes is not None:
            params.mesh["chunkBytes"] = args.mesh_chunk_bytes
        if args.no_supervisor:
            params.supervisor["enabled"] = False
        if args.no_memory_governor:
            params.memory["enabled"] = False
        if args.device_mem_bytes is not None:
            params.memory["deviceMemBytes"] = args.device_mem_bytes
        if args.quality_policy is not None:
            params.quality["policy"] = args.quality_policy
        if args.max_quarantine_fraction is not None:
            params.quality["maxQuarantineFraction"] = \
                args.max_quarantine_fraction
        if args.no_quality:
            params.quality["enabled"] = False
        if args.obs_port is not None:
            params.obs["port"] = args.obs_port
        from .parallel import hostgroup
        hosts = max(1, int(args.hosts or params.hostgroup.get("hosts", 1)))
        if hosts > 1 and not hostgroup.hostgroup_env_present():
            # launcher role: fan this same command out as N ranked worker
            # processes and supervise them (each rank re-enters main() with
            # the host-group env set and takes the in-process branch)
            import sys
            child = list(sys.argv) if argv is None else [sys.argv[0]] + \
                list(argv)
            hg_params = params.hostgroup or {}
            # training control plane: the launcher owns the base obs port
            # (merged rank panel); launch_hosts exports base+1+rank to each
            # child, so every rank's own endpoint is reachable too
            obs_port = (params.obs or {}).get("port")
            if obs_port:
                os.environ["TRANSMOGRIFAI_OBS_PORT"] = str(obs_port)
            res = hostgroup.launch_hosts(
                [sys.executable] + child, hosts,
                run_dir=args.hosts_run_dir or hg_params.get("runDir"),
                boot_timeout=float(hg_params.get("bootTimeoutS", 240.0)),
                grace_s=float(hg_params.get("graceS", 15.0)),
                max_relaunches=int(hg_params.get("maxRelaunches", 1)),
                liveness_timeout=hg_params.get("livenessTimeoutS"),
                beat_interval=hg_params.get("beatIntervalS"),
                distributed=bool(hg_params.get("distributed", True)))
            out = OpWorkflowRunnerResult(
                run_type=args.run_type, metrics={"hostgroup": res.to_json()})
            if not res.ok:
                raise SystemExit(1)
            return out
        runner = self.make_runner()
        try:
            return runner.run(args.run_type, params)
        except hostgroup.HostLostError:
            if hostgroup.hostgroup_env_present():
                # survivor abort: exit with the benign host-lost code so the
                # launcher relaunches the group instead of counting a failure
                raise SystemExit(hostgroup.EXIT_HOST_LOST)
            raise

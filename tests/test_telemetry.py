"""Tests for the unified telemetry layer (ISSUE 5): span trees and
parenting, the ambient ``use_tracer`` context, Chrome-trace export +
``trace-summary`` rendering, the central MetricsRegistry, LatencyHistogram
quantile edge cases, compile-listener install idempotence, span <-> failure
correlation (FailureLog / FaultInjector), and an end-to-end traced train
producing the nested ``workflow.train > ... > selector.sweep`` timeline."""

import json
import threading
import time

import pytest

from test_aux_subsystems import make_records
from transmogrifai_tpu import profiling
from transmogrifai_tpu import types as T
from transmogrifai_tpu.features import features_from_schema
from transmogrifai_tpu.models.linear import OpLogisticRegression
from transmogrifai_tpu.models.trees import OpRandomForestClassifier
from transmogrifai_tpu.ops.transmogrify import transmogrify
from transmogrifai_tpu.profiling import LatencyHistogram
from transmogrifai_tpu.resilience import (FailureLog, FaultInjector,
                                          inject_faults, use_failure_log)
from transmogrifai_tpu.selector import (BinaryClassificationModelSelector,
                                        ModelCandidate, grid)
from transmogrifai_tpu.telemetry import (REGISTRY, MetricsRegistry, Tracer,
                                         active_tracer, current_span_id,
                                         event, load_trace,
                                         render_trace_summary, span,
                                         telemetry_summary, use_tracer,
                                         write_telemetry_summary)
from transmogrifai_tpu.workflow import Workflow


# --------------------------------------------------------------------------
# span tree mechanics
# --------------------------------------------------------------------------

class TestSpanTree:
    def test_nesting_ids_and_parents(self):
        tr = Tracer("t")
        with tr.span("outer", kind="test") as outer:
            with tr.span("inner") as inner:
                assert inner.parent_id == outer.span_id
            assert outer.parent_id is None
        spans = tr.spans
        # finish order: inner closes first
        assert [s.name for s in spans] == ["inner", "outer"]
        assert spans[1].attrs == {"kind": "test"}
        assert all(s.status == "ok" for s in spans)
        assert all(s.end_s is not None and s.duration_s >= 0.0
                   for s in spans)
        assert len({s.span_id for s in spans}) == 2

    def test_exception_marks_error_and_propagates(self):
        tr = Tracer("t")
        with pytest.raises(ValueError):
            with tr.span("boom"):
                raise ValueError("nope")
        (s,) = tr.spans
        assert s.status == "error"
        assert "ValueError" in s.attrs["error"]
        assert s.end_s is not None     # closed despite the raise

    def test_event_is_zero_duration_child(self):
        tr = Tracer("t")
        with tr.span("parent") as p:
            ev = tr.event("mark", n=3)
        assert ev.parent_id == p.span_id
        assert ev.duration_s == 0.0 and ev.attrs == {"n": 3}
        assert ev in tr.spans

    def test_sibling_spans_share_parent(self):
        tr = Tracer("t")
        with tr.span("root") as root:
            with tr.span("a"):
                pass
            with tr.span("b"):
                pass
        a, b = [s for s in tr.spans if s.name in "ab"]
        assert a.parent_id == root.span_id == b.parent_id

    def test_current_span_id_tracks_innermost(self):
        tr = Tracer("t")
        assert tr.current_span_id() is None
        with tr.span("outer") as o:
            assert tr.current_span_id() == o.span_id
            with tr.span("inner") as i:
                assert tr.current_span_id() == i.span_id
            assert tr.current_span_id() == o.span_id
        assert tr.current_span_id() is None

    def test_slowest_orders_by_duration(self):
        tr = Tracer("t")
        with tr.span("slow"):
            time.sleep(0.02)
        with tr.span("fast"):
            pass
        names = [s.name for s in tr.slowest(2)]
        assert names[0] == "slow"


class TestCrossThreadParenting:
    def test_worker_thread_parents_under_install_thread_span(self):
        """A pool worker with no open span of its own must nest under the
        innermost open span of the thread that installed the tracer — the
        rule that puts candidate fits under ``selector.sweep``."""
        tr = Tracer("t")
        got = {}

        def worker():
            with tr.span("child"):
                got["parent"] = tr.spans  # not yet closed; read after join

        with use_tracer(tr):
            with tr.span("orchestrator") as orch:
                t = threading.Thread(target=worker)
                t.start()
                t.join()
        child = next(s for s in tr.spans if s.name == "child")
        assert child.parent_id == orch.span_id
        assert child.thread != orch.thread

    def test_worker_own_stack_wins_over_install_thread(self):
        tr = Tracer("t")
        tr._install_thread = threading.get_ident()
        with tr.span("main_open"):
            done = threading.Event()

            def worker():
                with tr.span("w_outer") as wo:
                    with tr.span("w_inner") as wi:
                        assert wi.parent_id == wo.span_id
                done.set()

            t = threading.Thread(target=worker)
            t.start()
            t.join()
            assert done.is_set()


# --------------------------------------------------------------------------
# ambient tracer
# --------------------------------------------------------------------------

class TestAmbientTracer:
    def test_module_span_noops_without_tracer(self):
        assert active_tracer() is None
        with span("nothing", x=1) as sp:
            assert sp is None
        assert event("nothing") is None
        assert current_span_id() is None

    def test_use_tracer_installs_and_removes(self):
        tr = Tracer("ambient")
        with use_tracer(tr) as got:
            assert got is tr and active_tracer() is tr
            with span("via_module", k="v") as sp:
                assert sp is not None
                assert current_span_id() == sp.span_id
            ev = event("marker")
            assert ev is not None and ev in tr.spans
        assert active_tracer() is None
        names = [s.name for s in tr.spans]
        assert names == ["via_module", "marker"]

    def test_nested_tracers_innermost_wins(self):
        a, b = Tracer("a"), Tracer("b")
        with use_tracer(a):
            with use_tracer(b):
                assert active_tracer() is b
                with span("inner"):
                    pass
            assert active_tracer() is a
        assert [s.name for s in b.spans] == ["inner"]
        assert a.spans == []


# --------------------------------------------------------------------------
# exports
# --------------------------------------------------------------------------

class TestExports:
    def _traced(self):
        tr = Tracer("export-test")
        with tr.span("workflow.train", rows=10):
            with tr.span("selector.sweep", candidates=1):
                tr.event("selector.racing.prune", pruned=5)
        return tr

    def test_chrome_trace_roundtrip(self, tmp_path):
        tr = self._traced()
        path = tr.export_chrome_trace(str(tmp_path / "trace.json"))
        with open(path) as fh:
            doc = json.load(fh)
        assert doc["otherData"]["runName"] == "export-test"
        evs = doc["traceEvents"]
        # X span events plus the process_name ("M") and clock_sync ("c")
        # metadata prelude
        assert {e["ph"] for e in evs} <= {"X", "M", "c"}
        xs = [e for e in evs if e["ph"] == "X"]
        assert {e["name"] for e in xs} == {"workflow.train",
                                           "selector.sweep",
                                           "selector.racing.prune"}
        # span tree survives via args
        spans = load_trace(path)
        by_name = {s["name"]: s for s in spans}
        assert (by_name["selector.sweep"]["parentId"]
                == by_name["workflow.train"]["spanId"])
        assert by_name["workflow.train"]["attrs"]["rows"] == 10

    def test_load_trace_reads_tracer_json_too(self, tmp_path):
        tr = self._traced()
        path = str(tmp_path / "native.json")
        with open(path, "w") as fh:
            json.dump(tr.to_json(), fh)
        spans = load_trace(path)
        assert {s["name"] for s in spans} == {"workflow.train",
                                              "selector.sweep",
                                              "selector.racing.prune"}

    def test_render_trace_summary_table(self, tmp_path):
        tr = self._traced()
        path = tr.export_chrome_trace(str(tmp_path / "trace.json"))
        out = render_trace_summary(path, top_n=5)
        assert "workflow.train" in out
        assert "  selector.sweep" in out      # indented one level
        assert "seconds" in out and "status" in out

    def test_trace_summary_cli(self, tmp_path, capsys):
        from transmogrifai_tpu import cli
        tr = self._traced()
        path = tr.export_chrome_trace(str(tmp_path / "trace.json"))
        assert cli.main(["trace-summary", path, "--top", "3"]) == 0
        out = capsys.readouterr().out
        assert "workflow.train" in out and "3 span(s)" in out

    def test_telemetry_summary_shape(self, tmp_path):
        tr = self._traced()
        summ = telemetry_summary(tr)
        assert set(summ) == {"metrics", "trace"}
        assert summ["trace"]["runName"] == "export-test"
        assert summ["trace"]["spanCount"] == 3
        by = summ["trace"]["byName"]
        assert by["workflow.train"]["count"] == 1
        assert by["workflow.train"]["errors"] == 0
        # the default registry's read-through gauges ride along
        assert "compile.compile_s" in summ["metrics"]["gauges"]
        path = write_telemetry_summary(str(tmp_path / "telemetry.json"), tr)
        with open(path) as fh:
            assert json.load(fh)["trace"]["spanCount"] == 3


# --------------------------------------------------------------------------
# metrics registry
# --------------------------------------------------------------------------

class TestMetricsRegistry:
    def test_counter_get_or_create_and_inc(self):
        reg = MetricsRegistry()
        c = reg.counter("hits")
        assert reg.counter("hits") is c
        c.inc()
        c.inc(4)
        assert c.value == 5
        assert reg.counters() == {"hits": 5}

    def test_gauge_set_and_callback(self):
        reg = MetricsRegistry()
        g = reg.gauge("depth")
        g.set(7)
        assert g.value == 7
        src = {"v": 3}
        cb = reg.gauge("live", fn=lambda: src["v"])
        assert cb.value == 3
        src["v"] = 9
        assert cb.value == 9

    def test_gauge_callback_failure_reads_zero(self):
        reg = MetricsRegistry()

        def dead():
            raise RuntimeError("source gone")

        assert reg.gauge("dead", fn=dead).value == 0

    def test_histogram_and_snapshot(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat")
        assert isinstance(h, LatencyHistogram)
        assert reg.histogram("lat") is h
        h.observe(0.5)
        reg.counter("n").inc()
        reg.gauge("g").set(2)
        snap = reg.snapshot()
        assert snap["counters"] == {"n": 1}
        assert snap["gauges"] == {"g": 2}
        assert snap["histograms"]["lat"]["count"] == 1

    def test_default_registry_reexports_profiling_globals(self):
        snap = REGISTRY.snapshot()["gauges"]
        for name in ("compile.compile_s", "compile.backend_compiles",
                     "compile.cache_hits", "compile.cache_misses",
                     "racing.cv_fits_saved", "racing.families_raced",
                     "racing.points_pruned", "host_link.bytes"):
            assert name in snap
        # read-through: the source of truth stays in profiling
        assert (snap["compile.backend_compiles"]
                == profiling.compile_stats()["backend_compiles"])


# --------------------------------------------------------------------------
# LatencyHistogram edge cases + thread safety (satellite 2)
# --------------------------------------------------------------------------

class TestLatencyHistogramEdges:
    def test_empty_quantile_is_none(self):
        h = LatencyHistogram()
        for q in (0.0, 0.5, 1.0):
            assert h.quantile(q) is None
        assert h.count == 0 and h.sum == 0.0

    def test_single_observation_every_quantile_is_it(self):
        h = LatencyHistogram()
        h.observe(0.0125)
        for q in (0.0, 0.25, 0.5, 0.99, 1.0):
            assert h.quantile(q) == pytest.approx(0.0125)

    def test_q0_is_min_q1_is_max(self):
        h = LatencyHistogram()
        for v in (0.001, 0.02, 0.3):
            h.observe(v)
        assert h.quantile(0.0) == pytest.approx(0.001)
        assert h.quantile(-1.0) == pytest.approx(0.001)
        assert h.quantile(1.0) == pytest.approx(0.3)
        assert h.quantile(2.0) == pytest.approx(0.3)

    def test_interpolated_quantiles_clamped_to_observed_range(self):
        h = LatencyHistogram()
        for v in (0.010, 0.011, 0.012, 0.013):
            h.observe(v)
        for q in (0.1, 0.5, 0.9):
            est = h.quantile(q)
            assert 0.010 <= est <= 0.013

    def test_concurrent_observe_is_lossless(self):
        h = LatencyHistogram()
        per_thread, n_threads = 500, 8

        def hammer():
            for _ in range(per_thread):
                h.observe(0.005)

        threads = [threading.Thread(target=hammer)
                   for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert h.count == per_thread * n_threads
        assert h.sum == pytest.approx(0.005 * per_thread * n_threads)
        snap = h.snapshot()
        assert snap["count"] == per_thread * n_threads


# --------------------------------------------------------------------------
# compile-listener install idempotence (satellite 1)
# --------------------------------------------------------------------------

class TestCompileListenerIdempotence:
    @pytest.fixture
    def fake_monitoring(self, monkeypatch):
        """Count registrations instead of actually registering (the real
        listeners are already installed process-wide)."""
        from jax import monitoring
        calls = {"duration": 0, "event": 0, "scalar": 0}
        monkeypatch.setattr(
            monitoring, "register_scalar_listener",
            lambda fn: calls.__setitem__("scalar", calls["scalar"] + 1))
        monkeypatch.setattr(
            monitoring, "register_event_duration_secs_listener",
            lambda fn: calls.__setitem__("duration", calls["duration"] + 1))
        monkeypatch.setattr(
            monitoring, "register_event_listener",
            lambda fn: calls.__setitem__("event", calls["event"] + 1))
        was = profiling._COMPILE_LISTENERS_INSTALLED[0]
        profiling._COMPILE_LISTENERS_INSTALLED[0] = False
        yield calls
        profiling._COMPILE_LISTENERS_INSTALLED[0] = was

    def test_double_install_registers_once(self, fake_monitoring):
        assert profiling.install_compile_listeners() is True
        assert profiling.install_compile_listeners() is True
        assert fake_monitoring == {"duration": 1, "event": 1, "scalar": 1}

    def test_concurrent_install_registers_once(self, fake_monitoring):
        barrier = threading.Barrier(8)

        def race():
            barrier.wait()
            profiling.install_compile_listeners()

        threads = [threading.Thread(target=race) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert fake_monitoring == {"duration": 1, "event": 1, "scalar": 1}
        assert profiling._COMPILE_LISTENERS_INSTALLED[0]


# --------------------------------------------------------------------------
# span <-> failure correlation
# --------------------------------------------------------------------------

class TestFailureCorrelation:
    def test_record_inside_span_carries_span_id(self):
        tr, log = Tracer("t"), FailureLog()
        with use_tracer(tr), use_failure_log(log):
            with tr.span("risky") as sp:
                ev = log.record("stage", "swallowed", ValueError("x"),
                                point="p")
        assert ev.detail["span_id"] == sp.span_id

    def test_record_without_tracer_has_no_span_id(self):
        log = FailureLog()
        ev = log.record("stage", "swallowed", ValueError("x"))
        assert "span_id" not in ev.detail

    def test_explicit_span_id_not_overwritten(self):
        tr, log = Tracer("t"), FailureLog()
        with use_tracer(tr), tr.span("open"):
            ev = log.record("stage", "swallowed", span_id="mine")
        assert ev.detail["span_id"] == "mine"

    def test_span_ids_do_not_perturb_chaos_signature(self):
        """signature() excludes detail, so traced and untraced runs of the
        same failure sequence stay signature-equal (chaos determinism)."""
        traced, plain = FailureLog(), FailureLog()
        tr = Tracer("t")
        with use_tracer(tr), tr.span("s"):
            traced.record("stage", "degraded", ValueError("x"), point="p")
        plain.record("stage", "degraded", ValueError("x"), point="p")
        assert traced.signature() == plain.signature()
        assert "span_id" in traced.events[0].detail
        assert "span_id" not in plain.events[0].detail


# --------------------------------------------------------------------------
# end-to-end: traced train / chaos correlation (integration)
# --------------------------------------------------------------------------

def _traced_workflow(records, models=None, racing=None):
    schema = {"y": T.RealNN, "x1": T.Real, "x2": T.Real, "cat": T.PickList,
              "sparse": T.Real}
    y, predictors = features_from_schema(schema, response="y")
    fv = transmogrify(predictors)
    checked = y.sanity_check(fv, remove_bad_features=True)
    sel = BinaryClassificationModelSelector(models=models or [
        ModelCandidate(OpLogisticRegression(),
                       grid(reg_param=[0.001, 0.01, 0.1, 0.2],
                            elastic_net_param=[0.1, 0.5]),
                       "OpLogisticRegression")])
    if racing is not None:
        sel.validator.racing = racing
    sel.set_input(y, checked)
    recs = [{k: (1.0 if k == "y" and v else 0.0) if k == "y" else v
             for k, v in r.items()} for r in records]
    return (Workflow().set_input_records(recs)
            .set_result_features(sel.get_output()))


def _parent_chain(spans_by_id, sp):
    names = []
    while sp is not None:
        names.append(sp.name)
        sp = spans_by_id.get(sp.parent_id)
    return names


class TestTracedTrain:
    @pytest.fixture(scope="class")
    def traced_run(self, tmp_path_factory):
        tmp = tmp_path_factory.mktemp("traced")
        records = make_records(200)
        tracer = Tracer(run_name="test-train")
        with use_tracer(tracer):
            model = _traced_workflow(records, racing=True).train()
            model.save(str(tmp / "model"))
        return tracer, model, tmp

    def test_workflow_phases_have_spans(self, traced_run):
        tracer, _, _ = traced_run
        names = {s.name for s in tracer.spans}
        assert "workflow.train" in names
        assert "selector.sweep" in names
        assert any(n.startswith("phase.") for n in names)

    def test_selector_sweep_nests_under_workflow_train(self, traced_run):
        tracer, _, _ = traced_run
        by_id = {s.span_id: s for s in tracer.spans}
        sweep = next(s for s in tracer.spans if s.name == "selector.sweep")
        chain = _parent_chain(by_id, sweep)
        assert "workflow.train" in chain
        assert tracer.spans and all(s.status == "ok"
                                    for s in tracer.spans
                                    if s.name == "workflow.train")

    def test_per_candidate_fit_spans_recorded(self, traced_run):
        tracer, _, _ = traced_run
        fits = [s for s in tracer.spans
                if s.name == "selector.candidate_fit"]
        assert fits
        assert {s.attrs.get("model") for s in fits} == {
            "OpLogisticRegression"}
        # pool-thread fits still nest under the sweep
        by_id = {s.span_id: s for s in tracer.spans}
        assert any("selector.sweep" in _parent_chain(by_id, s)
                   for s in fits)

    def test_racing_prune_event_recorded(self, traced_run):
        tracer, _, _ = traced_run
        prunes = [s for s in tracer.spans
                  if s.name == "selector.racing.prune"]
        assert prunes
        # 8-point grid, eta=3, min_survivors=2 -> 5 pruned
        assert prunes[0].attrs["pruned"] == 5

    def test_checkpoint_save_span_recorded(self, traced_run):
        tracer, _, _ = traced_run
        saves = [s for s in tracer.spans if s.name == "checkpoint.save"]
        assert saves and saves[0].status == "ok"

    def test_telemetry_json_bundled_with_model(self, traced_run):
        _, _, tmp = traced_run
        path = tmp / "model" / "telemetry.json"
        assert path.exists()
        doc = json.loads(path.read_text())
        assert "metrics" in doc and "trace" in doc
        assert doc["trace"]["spanCount"] > 0

    def test_chrome_export_of_real_train_parses(self, traced_run):
        tracer, _, tmp = traced_run
        path = tracer.export_chrome_trace(str(tmp / "trace.json"))
        spans = load_trace(path)
        names = {s["name"] for s in spans}
        assert "workflow.train" in names and "selector.sweep" in names
        out = render_trace_summary(path, top_n=5)
        assert "workflow.train" in out

    def test_score_span_recorded(self, traced_run):
        tracer, model, _ = traced_run
        with use_tracer(tracer):
            model.score()
        scores = [s for s in tracer.spans if s.name == "workflow.score"]
        assert scores and scores[-1].attrs["rows"] == 200


class TestChaosSpanCorrelation:
    def test_injected_fault_carries_firing_span_id(self):
        """Acceptance: a FaultInjector fault during a traced chaos train
        yields a FailureLog entry carrying the id of the span it fired
        inside, and the injector remembers the same span."""
        records = make_records(120)
        models = [
            ModelCandidate(OpLogisticRegression(), grid(reg_param=[0.01]),
                           "OpLogisticRegression"),
            ModelCandidate(OpRandomForestClassifier(num_trees=5,
                                                    max_depth=3),
                           grid(min_info_gain=[0.001]),
                           "OpRandomForestClassifier"),
        ]
        injector = FaultInjector(
            fail_keys={"selector.candidate_fit": ["OpLogisticRegression"]})
        tracer = Tracer(run_name="chaos")
        with use_tracer(tracer), inject_faults(injector):
            model = _traced_workflow(records, models=models).train()

        assert injector.fired
        assert len(injector.fired_spans) == len(injector.fired)
        fired_sids = [sid for sid in injector.fired_spans if sid is not None]
        assert fired_sids, "faults fired outside any span"
        all_ids = {s.span_id: s for s in tracer.spans}
        for sid in fired_sids:
            assert sid in all_ids
            assert all_ids[sid].name.startswith("selector.")

        degraded = model.failure_log.by_action("degraded")
        assert degraded
        correlated = [e for e in degraded if "span_id" in e.detail]
        assert correlated, "degraded events must carry their span id"
        assert any(e.detail["span_id"] in fired_sids for e in correlated)

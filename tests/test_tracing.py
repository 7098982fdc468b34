"""Distributed tracing: context propagation, trace assembly, exemplars.

Covers the tracing acceptance criteria: W3C ``traceparent`` round-trip and
strict parsing (malformed/oversized headers fall back to a fresh context and
never 500), response identity headers on every status code, batch spans
linking every coalesced request under concurrent mixed JSON+columnar
traffic, child-process propagation through ``run_supervised`` (including the
SIGKILL escalation path), the span ring buffer + drop counter, clock-sync
metadata in Chrome exports, wall-clock-aligned ``merge_traces``, OpenMetrics
exemplars on /metrics, and exemplar/escaping preservation through
``merge_worker_metrics``."""

import json
import os
import re
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from transmogrifai_tpu.features import FeatureBuilder
from transmogrifai_tpu.models.linear import OpLogisticRegression
from transmogrifai_tpu.ops.transmogrify import transmogrify
from transmogrifai_tpu.selector import (BinaryClassificationModelSelector,
                                        ModelCandidate, grid)
from transmogrifai_tpu.serving import wire
from transmogrifai_tpu.serving.server import start_server
from transmogrifai_tpu.telemetry import (TRACEPARENT_ENV, REGISTRY,
                                         TraceContext, Tracer,
                                         current_trace_context, load_trace,
                                         merge_traces, use_tracer)
from transmogrifai_tpu.workflow import Workflow


# --------------------------------------------------------------------------
# TraceContext: W3C traceparent round-trip + strict parsing
# --------------------------------------------------------------------------

class TestTraceContext:
    def test_new_and_roundtrip(self):
        ctx = TraceContext.new()
        assert re.fullmatch(r"[0-9a-f]{32}", ctx.trace_id)
        assert re.fullmatch(r"[0-9a-f]{16}", ctx.span_id)
        header = ctx.to_traceparent()
        assert re.fullmatch(r"00-[0-9a-f]{32}-[0-9a-f]{16}-[0-9a-f]{2}",
                            header)
        back = TraceContext.parse(header)
        assert back == ctx

    def test_child_keeps_trace_id(self):
        ctx = TraceContext.new()
        kid = ctx.child()
        assert kid.trace_id == ctx.trace_id
        assert kid.span_id != ctx.span_id

    @pytest.mark.parametrize("header", [
        None, "", "garbage", "00-abc-def-01",
        "00-" + "g" * 32 + "-" + "0" * 16 + "-01",       # non-hex
        "00-" + "0" * 32 + "-" + "1" * 16 + "-01",       # all-zero trace
        "00-" + "1" * 32 + "-" + "0" * 16 + "-01",       # all-zero span
        "00-" + "A" * 32 + "-" + "1" * 16 + "-01",       # uppercase hex
        "00-" + "1" * 32 + "-" + "1" * 16 + "-01" + "-extra",
        "x" * 4096,                                      # oversized
    ])
    def test_parse_rejects_malformed(self, header):
        assert TraceContext.parse(header) is None

    def test_parse_tolerates_whitespace(self):
        ctx = TraceContext.new()
        assert TraceContext.parse(f"  {ctx.to_traceparent()}  ") == ctx

    def test_from_env(self, monkeypatch):
        ctx = TraceContext.new()
        monkeypatch.setenv(TRACEPARENT_ENV, ctx.to_traceparent())
        assert TraceContext.from_env() == ctx
        monkeypatch.setenv(TRACEPARENT_ENV, "not-a-traceparent")
        assert TraceContext.from_env() is None

    def test_current_trace_context_env_fallback(self, monkeypatch):
        ctx = TraceContext.new()
        monkeypatch.setenv(TRACEPARENT_ENV, ctx.to_traceparent())
        assert current_trace_context() == ctx

    def test_current_trace_context_from_open_span(self):
        tr = Tracer("ctx-test")
        with use_tracer(tr):
            with tr.span("outer") as sp:
                cur = current_trace_context()
                assert cur.trace_id == tr.trace_id
                assert cur.span_id == sp.w3c_id


# --------------------------------------------------------------------------
# ring buffer + drop accounting (satellite: bounded tracer)
# --------------------------------------------------------------------------

class TestRingBuffer:
    def test_default_bound(self):
        assert Tracer.DEFAULT_MAX_SPANS == 65536
        assert Tracer("t").max_spans == 65536

    def test_env_override(self, monkeypatch):
        monkeypatch.setenv("TRANSMOGRIFAI_TRACE_MAX_SPANS", "7")
        assert Tracer("t").max_spans == 7

    def test_drops_oldest_and_counts(self):
        tr = Tracer("ring", max_spans=3)
        before = REGISTRY.counter("telemetry.spans_dropped_total").value
        for i in range(8):
            tr.event(f"e{i}")
        assert len(tr.spans) == 3
        assert [s.name for s in tr.spans] == ["e5", "e6", "e7"]
        assert tr.spans_dropped == 5
        after = REGISTRY.counter("telemetry.spans_dropped_total").value
        assert after - before == 5
        assert tr.to_json()["spansDropped"] == 5

    def test_drop_while_ambient_does_not_deadlock(self):
        # record_failure -> current_span_id() re-enters the ambient tracer;
        # the first-drop degraded note must run outside the tracer lock
        tr = Tracer("ring-ambient", max_spans=2)
        with use_tracer(tr):
            for i in range(6):
                with tr.span(f"s{i}"):
                    pass
        assert tr.spans_dropped >= 1


# --------------------------------------------------------------------------
# chrome export metadata + cross-process merge
# --------------------------------------------------------------------------

class TestExportAndMerge:
    def _trace(self, run_name, worker_id=None, parent=None):
        tr = Tracer(run_name, worker_id=worker_id, parent=parent)
        with tr.span("serving.request"):
            tr.event("serving.batch")
        return tr

    def test_export_has_clock_sync_and_process_name(self, tmp_path):
        tr = self._trace("meta-test", worker_id="3")
        path = tr.export_chrome_trace(str(tmp_path / "t.json"))
        with open(path) as fh:
            doc = json.load(fh)
        evs = doc["traceEvents"]
        meta = [e for e in evs if e["ph"] == "M"]
        assert meta and meta[0]["name"] == "process_name"
        assert "worker 3" in meta[0]["args"]["name"]
        sync = [e for e in evs if e["ph"] == "c"]
        assert len(sync) == 1
        assert sync[0]["args"]["sync_id"] == tr.trace_id
        assert sync[0]["args"]["issue_ts"] == pytest.approx(
            tr.t0_wall * 1e6, rel=1e-6)
        assert doc["otherData"]["workerId"] == "3"
        assert doc["otherData"]["traceId"] == tr.trace_id

    def test_span_ids_survive_chrome_roundtrip(self, tmp_path):
        parent = TraceContext.new()
        tr = self._trace("ids", parent=parent)
        assert tr.trace_id == parent.trace_id
        path = tr.export_chrome_trace(str(tmp_path / "t.json"))
        spans = load_trace(path)
        assert all(s["traceId"] == parent.trace_id for s in spans)
        assert all(s["w3cSpanId"] for s in spans)

    def test_merge_aligns_clocks_and_remaps_pids(self, tmp_path):
        t0 = self._trace("w0", worker_id="0")
        t1 = self._trace("w1", worker_id="1")
        # force distinct anchors: pretend worker 1 started 2s later
        t1.t0_wall = t0.t0_wall + 2.0
        p0 = t0.export_chrome_trace(str(tmp_path / "trace-worker-0.json"))
        p1 = t1.export_chrome_trace(str(tmp_path / "trace-worker-1.json"))
        out = str(tmp_path / "merged.json")
        merged = merge_traces([p0, p1], out_path=out)
        with open(out) as fh:
            assert json.load(fh)["otherData"]["merged"] is True
        xs = [e for e in merged["traceEvents"] if e["ph"] == "X"]
        assert {e["pid"] for e in xs} == {0, 1}
        w0 = [e for e in xs if e["pid"] == 0]
        w1 = [e for e in xs if e["pid"] == 1]
        # worker 1's events sit ~2s later on the merged timeline
        offset = min(e["ts"] for e in w1) - min(e["ts"] for e in w0)
        assert offset == pytest.approx(2e6, rel=0.25)
        files = merged["otherData"]["files"]
        assert [f["workerId"] for f in files] == ["0", "1"]

    def test_merge_reads_native_tracer_json(self, tmp_path):
        tr = self._trace("native", worker_id="5")
        path = str(tmp_path / "native.json")
        with open(path, "w") as fh:
            json.dump(tr.to_json(), fh)
        merged = merge_traces([path])
        names = {e["name"] for e in merged["traceEvents"]
                 if e["ph"] == "X"}
        assert names == {"serving.request", "serving.batch"}

    def test_rank_labels_exports_and_merge(self, tmp_path):
        # host-group ranks: the rank rides the export and merge_traces
        # labels one lane per host with it
        parent = TraceContext.new()
        paths = []
        for rank in (0, 1):
            tr = Tracer("sweep", parent=parent.child(), rank=rank)
            with tr.span("selector.sweep"):
                pass
            assert tr.to_json()["rank"] == rank
            paths.append(tr.export_chrome_trace(
                str(tmp_path / f"trace-rank{rank}.json")))
        merged = merge_traces(paths)
        labels = [e["args"]["name"] for e in merged["traceEvents"]
                  if e.get("name") == "process_name"]
        assert any("[rank 0]" in l for l in labels)
        assert any("[rank 1]" in l for l in labels)
        assert [f["rank"] for f in merged["otherData"]["files"]] == [0, 1]
        # one trace id across every rank's spans (launcher propagation)
        ids = {e["args"]["traceId"]
               for e in merged["traceEvents"] if e["ph"] == "X"}
        assert ids == {parent.trace_id}


# --------------------------------------------------------------------------
# run_supervised: child-env propagation (satellite: supervised children)
# --------------------------------------------------------------------------

_CHILD_ECHO = ("import os; "
               "print(os.environ.get('TRANSMOGRIFAI_TRACEPARENT', ''))")


class TestSupervisedPropagation:
    def test_child_env_from_ambient_span(self):
        from transmogrifai_tpu.parallel.supervisor import run_supervised
        tr = Tracer("sup-test")
        with use_tracer(tr):
            with tr.span("trigger"):
                r = run_supervised([sys.executable, "-c", _CHILD_ECHO],
                                   timeout_s=60)
        assert r.rc == 0
        child = TraceContext.parse(r.stdout.strip())
        assert child is not None
        assert child.trace_id == tr.trace_id
        # the run is recorded as a supervisor.child span on the same trace
        sup = [s for s in tr.spans if s.name == "supervisor.child"]
        assert len(sup) == 1
        assert sup[0].trace_id == tr.trace_id
        assert sup[0].attrs["rc"] == 0
        assert sup[0].w3c_id == child.span_id

    def test_explicit_traceparent_wins(self):
        from transmogrifai_tpu.parallel.supervisor import run_supervised
        ctx = TraceContext.new()
        r = run_supervised([sys.executable, "-c", _CHILD_ECHO],
                           timeout_s=60, traceparent=ctx.to_traceparent())
        child = TraceContext.parse(r.stdout.strip())
        assert child is not None and child.trace_id == ctx.trace_id

    def test_no_context_no_env(self):
        from transmogrifai_tpu.parallel.supervisor import run_supervised
        env = {k: v for k, v in os.environ.items()
               if k != TRACEPARENT_ENV}
        r = run_supervised([sys.executable, "-c", _CHILD_ECHO],
                           timeout_s=60, env=env)
        assert r.stdout.strip() == ""

    def test_propagation_survives_sigkill_escalation(self):
        from transmogrifai_tpu.parallel.supervisor import run_supervised
        code = ("import os, signal, sys, time\n"
                "signal.signal(signal.SIGTERM, signal.SIG_IGN)\n"
                "print(os.environ.get('TRANSMOGRIFAI_TRACEPARENT', ''))\n"
                "sys.stdout.flush()\n"
                "while True:\n    time.sleep(3600)\n")
        tr = Tracer("sup-kill")
        with use_tracer(tr):
            with tr.span("trigger"):
                r = run_supervised([sys.executable, "-c", code],
                                   timeout_s=2.0, grace_s=0.5)
        assert r.timed_out and r.escalated and r.rc == 124
        child = TraceContext.parse(r.stdout.strip())
        assert child is not None and child.trace_id == tr.trace_id
        sup = [s for s in tr.spans if s.name == "supervisor.child"]
        assert sup[0].attrs["escalated"] is True


# --------------------------------------------------------------------------
# HTTP server: identity headers + batch links (tentpole end-to-end)
# --------------------------------------------------------------------------

def _train():
    rng = np.random.default_rng(0)
    records = [{"y": float(i % 2), "x": float(rng.normal()) + (i % 2)}
               for i in range(120)]
    label = FeatureBuilder.RealNN("y").as_response()
    x = FeatureBuilder.Real("x").as_predictor()
    sel = BinaryClassificationModelSelector(models=[
        ModelCandidate(OpLogisticRegression(), grid(reg_param=[0.01]), "LR")])
    sel.set_input(label, transmogrify([x]))
    pred = sel.get_output()
    return (Workflow().set_input_records(records)
            .set_result_features(pred).train())


@pytest.fixture(scope="module")
def bundle(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("tracing") / "model")
    _train().save(path)
    return path


@pytest.fixture()
def traced_server(bundle):
    tracer = Tracer("serve-test")
    with use_tracer(tracer):
        srv, thread = start_server(bundle, port=0, max_batch=8,
                                   queue_bound=64)
        try:
            yield srv, tracer
        finally:
            srv.engine.close()
            srv.shutdown()
            srv.server_close()
            thread.join(timeout=10)


def _post(port, body, headers, path="/v1/score", timeout=60):
    req = urllib.request.Request(f"http://127.0.0.1:{port}{path}",
                                 data=body, headers=headers)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, r.read(), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, e.read(), dict(e.headers)


def _post_json(port, payload, extra_headers=None, timeout=60):
    headers = {"Content-Type": "application/json"}
    headers.update(extra_headers or {})
    return _post(port, json.dumps(payload).encode(), headers,
                 timeout=timeout)


class TestServerPropagation:
    TP = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"

    def test_client_traceparent_adopted(self, traced_server):
        srv, tracer = traced_server
        code, _, hdrs = _post_json(srv.port, {"x": 1.0},
                                   {"traceparent": self.TP})
        assert code == 200
        assert hdrs["X-Request-Id"] == "ab" * 16
        back = TraceContext.parse(hdrs["traceparent"])
        assert back is not None and back.trace_id == "ab" * 16
        # the server's span is a CHILD: same trace, new span id
        assert back.span_id != "cd" * 8
        # the span closes after the reply is written: give the handler
        # thread a moment under a loaded runner
        deadline = time.monotonic() + 5.0
        while True:
            req_spans = [s for s in tracer.spans
                         if s.name == "serving.request"]
            if req_spans or time.monotonic() > deadline:
                break
            time.sleep(0.01)
        assert any(s.trace_id == "ab" * 16 for s in req_spans)

    def test_fresh_context_when_absent(self, traced_server):
        srv, _ = traced_server
        code, _, hdrs = _post_json(srv.port, {"x": 1.0})
        assert code == 200
        ctx = TraceContext.parse(hdrs["traceparent"])
        assert ctx is not None
        assert hdrs["X-Request-Id"] == ctx.trace_id

    @pytest.mark.parametrize("bad", ["nonsense", "00-zz-zz-zz",
                                     "00-" + "0" * 32 + "-" + "0" * 16
                                     + "-00", "y" * 5000])
    def test_malformed_traceparent_never_500(self, traced_server, bad):
        srv, _ = traced_server
        code, body, hdrs = _post_json(srv.port, {"x": 1.0},
                                      {"traceparent": bad})
        assert code == 200
        assert json.loads(body)  # still a real scoring response
        assert TraceContext.parse(hdrs["traceparent"]) is not None

    def test_error_responses_carry_identity(self, traced_server):
        srv, _ = traced_server
        # 400: malformed JSON body
        code, _, hdrs = _post(srv.port, b"{not json",
                              {"Content-Type": "application/json",
                               "traceparent": self.TP})
        assert code == 400
        assert hdrs["X-Request-Id"] == "ab" * 16
        assert TraceContext.parse(hdrs["traceparent"]) is not None
        # 404: unknown path
        code, _, hdrs = _post(srv.port, b"{}",
                              {"Content-Type": "application/json"},
                              path="/nope")
        assert code == 404 and "X-Request-Id" in hdrs
        assert TraceContext.parse(hdrs["traceparent"]) is not None

    def test_batch_span_links_mixed_concurrent_clients(self, traced_server):
        srv, tracer = traced_server
        n_json, n_col = 6, 4
        ctxs = [TraceContext.new() for _ in range(n_json + n_col)]
        results = [None] * (n_json + n_col)

        def json_client(i):
            results[i] = _post_json(
                srv.port, {"x": float(i)},
                {"traceparent": ctxs[i].to_traceparent()})

        def col_client(i):
            body = wire.encode_records([{"x": float(i)}])
            results[i] = _post(
                srv.port, body,
                {"Content-Type": wire.CONTENT_TYPE,
                 "traceparent": ctxs[i].to_traceparent()})

        threads = ([threading.Thread(target=json_client, args=(i,))
                    for i in range(n_json)]
                   + [threading.Thread(target=col_client, args=(i,))
                      for i in range(n_json, n_json + n_col)])
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert all(r is not None and r[0] == 200 for r in results)
        linked = set()
        for s in tracer.spans:
            if s.name in ("serving.batch", "serving.execute"):
                for link in s.links:
                    linked.add(link["traceId"])
        # EVERY client's trace shows up as a link on some batch span
        assert {c.trace_id for c in ctxs} <= linked
        # batch spans adopt the trace of one of their coalesced requests
        batch = [s for s in tracer.spans if s.name == "serving.batch"
                 and s.links]
        assert batch
        assert all(s.trace_id in {l["traceId"] for l in s.links}
                   for s in batch)


# --------------------------------------------------------------------------
# /metrics exemplars + merge_worker_metrics escaping (satellites)
# --------------------------------------------------------------------------

_EXEMPLAR_RE = re.compile(
    r' # \{trace_id="([0-9a-f]{32})"\} [0-9.eE+-]+$')


class TestMetricsExemplars:
    def test_latency_summary_carries_exemplar(self, traced_server):
        from transmogrifai_tpu.serving.server import render_metrics
        srv, _ = traced_server
        tp = TraceContext.new()
        code, _, _ = _post_json(srv.port, {"x": 1.0},
                                {"traceparent": tp.to_traceparent()})
        assert code == 200
        text = render_metrics(srv.engine)
        lines = [ln for ln in text.splitlines()
                 if _EXEMPLAR_RE.search(ln)]
        assert lines, f"no exemplar lines in:\n{text}"
        traced = {_EXEMPLAR_RE.search(ln).group(1) for ln in lines}
        assert tp.trace_id in traced

    def test_histogram_exemplar_api(self):
        from transmogrifai_tpu.profiling import LatencyHistogram
        h = LatencyHistogram()
        assert h.exemplar() is None
        h.observe(0.010, trace_id="aa" * 16)
        h.observe(0.500, trace_id="bb" * 16)
        h.observe(0.020, trace_id="cc" * 16)
        assert h.exemplar()["traceId"] == "cc" * 16
        assert h.exemplar(slowest=True)["traceId"] == "bb" * 16

    def test_counter_exemplar(self):
        from transmogrifai_tpu.telemetry import Counter
        c = Counter("shed_total")
        assert c.exemplar() is None
        c.inc(trace_id="dd" * 16)
        assert c.exemplar() == {"traceId": "dd" * 16, "value": 1}


class TestMergeWorkerMetrics:
    def _merge(self, texts):
        from transmogrifai_tpu.serving.pool import merge_worker_metrics
        return merge_worker_metrics(texts)

    def test_label_values_with_quotes_and_backslashes(self):
        # label values containing '"' and '\' must survive the re-labeling
        text = ('# TYPE demo counter\n'
                'demo{path="C:\\\\tmp\\\\x",msg="say \\"hi\\""} 3\n')
        merged = self._merge([('w"0\\', text)])
        # worker label is escaped, original labels intact
        assert 'worker_id="w\\"0\\\\"' in merged
        assert 'path="C:\\\\tmp\\\\x"' in merged
        assert 'msg="say \\"hi\\""' in merged
        # aggregate line still parses to the right value
        agg = [ln for ln in merged.splitlines()
               if ln.startswith("demo{") and "worker_id" not in ln]
        assert agg and agg[0].rstrip().endswith(" 3")

    def test_exemplars_preserved(self):
        ex = ' # {trace_id="' + "ee" * 16 + '"} 0.25'
        text = ('# TYPE transmogrifai_serving_shed_total counter\n'
                f'transmogrifai_serving_shed_total 2{ex}\n')
        merged = self._merge([("0", text), ("1", text)])
        per_worker = [ln for ln in merged.splitlines()
                      if 'worker_id="0"' in ln]
        assert any(ln.endswith(ex) for ln in per_worker)
        agg = [ln for ln in merged.splitlines()
               if ln.startswith("transmogrifai_serving_shed_total ")]
        assert len(agg) == 1
        assert agg[0].endswith(ex.lstrip())
        assert agg[0].split(" # ")[0] == "transmogrifai_serving_shed_total 4"

    def test_brace_inside_label_value_not_split(self):
        text = ('# TYPE demo counter\n'
                'demo{msg="a } b"} 1\n')
        merged = self._merge([("0", text)])
        assert 'msg="a } b"' in merged
        assert 'worker_id="0"' in merged

"""Spans inside the host prologue, jit events under the span that fired them,
the self-time table, the benchmark's readers of both, the device scope names
and the profiler annotations (ISSUE 25).  All on the CPU: counts, names and
parents, never a time."""

import glob
import importlib
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from transmogrifai_tpu import profiling, telemetry
from transmogrifai_tpu import types as T
from transmogrifai_tpu.columns import Column, ColumnBatch
from transmogrifai_tpu.features import features_from_schema
from transmogrifai_tpu.models.linear import OpLogisticRegression
from transmogrifai_tpu.ops.transmogrify import transmogrify
from transmogrifai_tpu.selector import (BinaryClassificationModelSelector,
                                        ModelCandidate, grid)
from transmogrifai_tpu.telemetry import (REGISTRY, Span, Tracer, span_profile,
                                         subtree, telemetry_summary,
                                         use_tracer)
from transmogrifai_tpu.workflow import Workflow


# --------------------------------------------------------------------------
# (a) self time, against spans made by hand
# --------------------------------------------------------------------------

def S(name, sid, parent, start, end, thread=0, **attrs):
    return Span(name=name, span_id=sid, parent_id=parent, start_s=start,
                end_s=end, thread=thread, attrs=attrs)


HAND_MADE = {
    # root 10 s; a and b cover 2 + 3 of it
    "children_apart": (
        [S("root", "r", None, 0, 10), S("a", "a", "r", 1, 3),
         S("b", "b", "r", 5, 8)],
        {"root": 5.0, "a": 2.0, "b": 3.0}),
    # a [1, 4] and b [3, 6] overlap on [3, 4]: the overlap is a's, who
    # started first; the root keeps what neither covers
    "children_overlap": (
        [S("root", "r", None, 0, 10), S("a", "a", "r", 1, 4),
         S("b", "b", "r", 3, 6, thread=7)],
        {"root": 5.0, "a": 3.0, "b": 2.0}),
    # a pool thread's span is the child of the span that caused it
    "child_on_another_thread": (
        [S("root", "r", None, 0, 10), S("fit", "f", "r", 2, 9, thread=7),
         S("inner", "i", "f", 3, 5, thread=7)],
        {"root": 3.0, "fit": 5.0, "inner": 2.0}),
    # c ends 2 s after its parent: only the part inside the parent counts
    "child_outlives_parent": (
        [S("root", "r", None, 0, 10), S("c", "c", "r", 8, 12),
         S("d", "d", "c", 9, 11)],
        {"root": 8.0, "c": 1.0, "d": 1.0}),
    # b lies wholly inside a sibling that started before it: nothing is left
    "sibling_inside_sibling": (
        [S("root", "r", None, 0, 10), S("a", "a", "r", 1, 9),
         S("b", "b", "r", 2, 3, thread=7)],
        {"root": 2.0, "a": 8.0, "b": 0.0}),
    # two spans of one name add up; an event is a child of no length
    "same_name_twice_and_an_event": (
        [S("root", "r", None, 0, 10), S("a", "a1", "r", 0, 2),
         S("a", "a2", "r", 4, 7), S("note", "e", "a2", 5, 5)],
        {"root": 5.0, "a": 5.0, "note": 0.0}),
}


@pytest.mark.parametrize("case", sorted(HAND_MADE))
def test_self_seconds_match_the_hand_count(case):
    spans, want = HAND_MADE[case]
    table = span_profile(spans)
    assert {k: round(v["self_s"], 9) for k, v in table.items()} == want
    root = spans[0]
    assert sum(v["self_s"] for v in table.values()) == pytest.approx(
        root.duration_s)
    for name, row in table.items():
        mine = [s for s in spans if s.name == name]
        assert row["count"] == len(mine)
        assert row["total_s"] == pytest.approx(
            sum(s.duration_s for s in mine))


def test_jit_seconds_go_to_the_span_the_event_lies_under():
    spans = [S("root", "r", None, 0, 10), S("a", "a", "r", 1, 4),
             S("jit.trace", "e1", "a", 2, 2, fun_name="jit(f)", seconds=0.5),
             S("jit.compile", "e2", "a", 3, 3, fun_name="jit(f)",
               seconds=0.25),
             S("jit.lower", "e3", "r", 6, 6, fun_name="jit(g)", seconds=0.125),
             S("selector.prune", "e4", "r", 7, 7, seconds=99.0)]
    table = span_profile(spans)
    assert table["a"]["jit_s"] == 0.75
    assert table["root"]["jit_s"] == 0.125
    assert sum(r["jit_s"] for r in table.values()) == 0.875
    assert table["jit.trace"] == {"count": 1, "total_s": 0.0, "self_s": 0.0,
                                  "jit_s": 0.0}


def test_open_spans_and_strangers_are_left_out_of_a_subtree():
    spans = [S("root", "r", None, 0, 10), S("a", "a", "r", 1, 3),
             S("other", "o", None, 0, 50), S("b", "b", "o", 1, 2)]
    assert [s.span_id for s in subtree(spans, spans[0])] == ["r", "a"]
    still_open = S("open", "x", "r", 4, None)
    assert "open" not in span_profile(spans + [still_open])


def test_summary_by_name_gains_self_and_jit_and_keeps_its_keys():
    tr = Tracer("t")
    with tr.span("outer"):
        with tr.span("inner"):
            pass
        tr.event("jit.compile", fun_name="jit(f)", seconds=0.5,
                 cache_hit=True)
    by_name = telemetry_summary(tr)["trace"]["byName"]
    assert set(by_name["outer"]) == {"count", "totalS", "maxS", "errors",
                                     "selfS", "jitS"}
    assert by_name["outer"]["jitS"] == 0.5
    assert by_name["outer"]["selfS"] <= by_name["outer"]["totalS"]
    assert by_name["inner"]["count"] == 1


# --------------------------------------------------------------------------
# (b) a small train under a tracer
# --------------------------------------------------------------------------

# span -> the phase it runs under.  A flush runs in whichever phase first
# needs the pending transforms: the vectorizers' under fit:SanityChecker, the
# SanityCheckerModel's column slice under selector, so a transform.* span
# only has to lie under SOME phase.
PROLOGUE_SPANS = {
    "transform.apply": "phase.",
    "transform.stage_wires": "phase.",
    "transform.wire": "phase.",
    "transform.first_call": "phase.",
    "sanity.fit": "phase.fit:SanityChecker",
    "sanity.stage": "phase.fit:SanityChecker",
    "sanity.stats": "phase.fit:SanityChecker",
    "sanity.contingency": "phase.fit:SanityChecker",
    "sanity.rules": "phase.fit:SanityChecker",
    "sanity.summary": "phase.fit:SanityChecker",
    "rff.distributions": "phase.rff",
    "rff.decide": "phase.rff",
}


def small_workflow(num_hashes, rows=240, seed=3):
    """Two reals, a pick list and a hashed text column; RawFeatureFilter,
    SanityChecker and a 2-point LR grid.  ``num_hashes`` sets the width of
    the feature vector and nothing else."""
    rng = np.random.default_rng(seed)
    x1 = rng.normal(size=rows).astype(np.float32)
    x2 = rng.normal(size=rows).astype(np.float32)
    y = (x1 + 0.5 * x2 + rng.normal(scale=0.5, size=rows) > 0
         ).astype(np.float32)
    words = np.asarray([f"w{i}" for i in range(400)], dtype=object)
    txt = np.empty(rows, dtype=object)
    for i in range(rows):
        txt[i] = " ".join(words[rng.integers(0, 400, size=4)])
    cat = np.asarray(["a", "b", "c"], dtype=object)[rng.integers(0, 3, rows)]
    schema = {"y": T.RealNN, "x1": T.Real, "x2": T.Real, "cat": T.PickList,
              "txt": T.Text}
    batch = ColumnBatch({
        "y": Column(T.RealNN, y), "x1": Column(T.Real, x1),
        "x2": Column(T.Real, x2), "cat": Column(T.PickList, cat),
        "txt": Column(T.Text, txt)}, rows)
    label, predictors = features_from_schema(schema, response="y")
    fv = transmogrify(predictors, num_hashes=num_hashes)
    checked = label.sanity_check(fv, remove_bad_features=True)
    sel = BinaryClassificationModelSelector(models=[ModelCandidate(
        OpLogisticRegression(), grid(reg_param=[0.01, 0.1]),
        "OpLogisticRegression")])
    sel.set_input(label, checked)
    return (Workflow().set_input_batch(batch)
            .set_result_features(sel.get_output())
            .with_raw_feature_filter(min_fill_rate=0.001))


def traced_train(num_hashes):
    tracer = Tracer(f"hashes-{num_hashes}")
    with use_tracer(tracer):
        model = small_workflow(num_hashes).train()
    return tracer, model


@pytest.fixture(scope="module")
def narrow():
    return traced_train(8)


def chain(spans, sp):
    by_id = {s.span_id: s for s in spans}
    names = []
    while sp is not None:
        names.append(sp.name)
        sp = by_id.get(sp.parent_id)
    return names


@pytest.mark.parametrize("name", sorted(PROLOGUE_SPANS))
def test_prologue_span_is_there_closed_and_under_its_phase(narrow, name):
    spans = narrow[0].spans
    found = [s for s in spans if s.name == name]
    assert found, sorted({s.name for s in spans})
    for sp in found:
        assert sp.end_s is not None and sp.status == "ok"
        up = chain(spans, sp)
        assert up[-1] == "workflow.train"
        assert any(n.startswith(PROLOGUE_SPANS[name]) for n in up), up


def test_transform_children_lie_under_transform_apply(narrow):
    spans = narrow[0].spans
    for sp in spans:
        if sp.name.startswith("transform.fit."):    # a fit, not a flush
            assert chain(spans, sp)[1].startswith("phase.fit:")
        elif sp.name.startswith("transform.") \
                and sp.name != "transform.apply":
            assert "transform.apply" in chain(spans, sp)
        if sp.name.startswith("sanity.") and sp.name != "sanity.fit":
            assert chain(spans, sp)[1] == "sanity.fit"


def test_a_second_call_of_one_program_is_a_dispatch(narrow):
    """``transform.first_call`` is the call of a fresh ``jax.jit(traced)``;
    the same ScoreProgram called again at the same shapes only dispatches."""
    model = narrow[1]
    tracer = Tracer("score")
    with use_tracer(tracer):
        model.score()
        model.score()
    names = [s.name for s in tracer.spans if s.name.startswith("transform.")]
    assert "transform.first_call" in names
    assert "transform.dispatch" in names
    assert names.index("transform.first_call") < names.index(
        "transform.dispatch")


def test_a_second_train_traces_and_lowers_and_compiles_nothing(narrow):
    """The process holds the fused programs of the first train
    (``compiled.SHARED_EXECUTABLES``): a second user's train of the same
    content pays ``jit.trace`` and ``jit.lower`` under its
    ``transform.first_call`` spans and no ``jit.compile``."""
    tracer, _ = traced_train(8)
    first_calls = [s for s in tracer.spans
                   if s.name == "transform.first_call"]
    assert len(first_calls) >= 2
    assert all(s.attrs["shared"] is True for s in first_calls)
    under = [e for e in jit_events(tracer)
             if e.parent_id in {s.span_id for s in first_calls}]
    for sp in first_calls:
        mine = [e.name for e in under if e.parent_id == sp.span_id]
        assert mine == ["jit.trace", "jit.lower"], mine
    assert {e.attrs["fun_name"] for e in under} == {"jit(traced)"}
    keys = [s for s in tracer.spans if s.name == "transform.program_key"]
    assert len(keys) == len(first_calls)
    assert {s.parent_id for s in keys} == {s.span_id for s in first_calls}
    # the first train of the module paid the compiles, unshared
    paid = [s for s in narrow[0].spans if s.name == "transform.first_call"]
    assert len(paid) == len(first_calls)
    assert any(e.name == "jit.compile" for e in jit_events(narrow[0])
               if e.parent_id in {s.span_id for s in paid})


def test_span_count_does_not_grow_with_the_width_of_the_table(narrow):
    wide_tracer, wide_model = traced_train(192)
    narrow_tracer, narrow_model = narrow

    def width(model):
        vec = [c for _, c in model.train_batch.items()
               if getattr(c.values, "ndim", 0) == 2]
        return max(c.values.shape[1] for c in vec)

    def counts(tracer):
        out = {}
        for s in tracer.spans:
            if not s.name.startswith("jit."):   # a warm process traces less
                out[s.name] = out.get(s.name, 0) + 1
        return out

    assert counts(wide_tracer) == counts(narrow_tracer)
    kept = [len(m.get_stage(st.uid).fitted["indices_to_keep"])
            for m in (narrow_model, wide_model)
            for st in m.stages if type(st).__name__ == "SanityCheckerModel"]
    assert kept[0] <= 20 and kept[1] >= 150, kept


def test_train_publishes_the_profile_of_its_own_subtree(narrow):
    tracer = Tracer("again")
    with use_tracer(tracer):
        with tracer.span("not.the.train"):
            pass
        small_workflow(8).train()
    profile = REGISTRY.gauge("train.span_profile").value
    assert "not.the.train" not in profile
    assert profile["workflow.train"]["count"] == 1
    for name in PROLOGUE_SPANS:
        assert profile[name]["count"] >= 1, name
    train = next(s for s in tracer.spans if s.name == "workflow.train")
    assert sum(r["self_s"] for r in profile.values()) == pytest.approx(
        train.duration_s)
    assert set(profile["sanity.fit"]) == {"count", "total_s", "self_s",
                                          "jit_s"}
    # the registry's exports carry the table; the Prometheus text skips it
    assert telemetry_summary(tracer)["metrics"]["gauges"][
        "train.span_profile"] == profile
    from transmogrifai_tpu.obsv import render_registry_metrics
    assert "span_profile" not in render_registry_metrics()


def test_without_a_tracer_no_span_is_made_and_no_profile_set(monkeypatch):
    class NoSpan:
        def __init__(self, *a, **kw):
            raise AssertionError("a Span was made with no tracer installed")

    sentinel = {"untouched": True}
    REGISTRY.gauge("train.span_profile").set(sentinel)
    monkeypatch.setattr(telemetry, "Span", NoSpan)
    monkeypatch.setattr(telemetry, "span_profile", NoSpan)
    assert telemetry.active_tracer() is None
    model = small_workflow(8).train()
    assert model.selected_model is not None
    assert REGISTRY.gauge("train.span_profile").value is sentinel


def test_prefetch_spans_open_where_the_prefetch_runs(monkeypatch):
    """``_prefetch_text_profiles`` does nothing on the CPU or under 100,000
    rows: run it alone as an accelerator host would, on a batch that small."""
    from transmogrifai_tpu import workflow as workflow_mod
    wf = small_workflow(8)
    batch = wf.generate_raw_data()
    monkeypatch.setattr(workflow_mod, "PREFETCH_MIN_ROWS", 1)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    tracer = Tracer("prefetch")
    with use_tracer(tracer), tracer.span("phase.prefetch"):
        wf._prefetch_text_profiles(batch)
    monkeypatch.undo()
    got = {s.name: chain(tracer.spans, s) for s in tracer.spans}
    assert got["prefetch.text_profiles"] == ["prefetch.text_profiles",
                                             "phase.prefetch"]
    assert got["prefetch.numeric"] == ["prefetch.numeric", "phase.prefetch"]


def test_a_ranged_prefetch_leaves_what_the_text_metrics_read(monkeypatch):
    """A prefetch over a column long enough to be walked by row range, as an
    accelerator host runs it under a traced train: ``text.pack_ids`` (its
    attrs on every piece) and ``prefetch.text_profiles`` are in
    ``train.span_profile``, so ``text_pack_s`` and ``text_profile_s`` read
    numbers; the wire's counters move by what one walk and one numpy pack
    moved them by; the walks' spans say how the column was cut."""
    from transmogrifai_tpu import workflow as workflow_mod
    from transmogrifai_tpu.ops import text_profile as tp
    from transmogrifai_tpu.ops.text import (SmartTextVectorizer,
                                            _size_class)
    from transmogrifai_tpu.features import FeatureBuilder
    from transmogrifai_tpu.native import load
    if load("textprof") is None:
        pytest.skip("no native toolchain")
    rows = 3 * tp.BLOCK_ROWS                # a head and two ranges
    values = np.asarray([f"w{i} x{i % 5} y" for i in range(97)],
                        dtype=object)[np.arange(rows) % 97]
    whole = tp.scan_strings(values.copy())
    capacity = _size_class(-(-whole.tokens // 3))
    batch = ColumnBatch({"txt": Column(T.Text, values)}, rows)
    st = SmartTextVectorizer(num_hashes=64, max_cardinality=7)
    st.set_input(FeatureBuilder.Text("txt").as_predictor())
    wf = Workflow().set_input_batch(batch).set_result_features(
        st.get_output())
    monkeypatch.setattr(tp, "MIN_RANGE_BLOCKS", 1)
    monkeypatch.setattr(os, "sched_getaffinity", lambda pid: set(range(4)))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    names = ("text.tokens", "text.token_slots", "text.pack_native",
             "text.pack_numpy", "text_profile.scan")
    before = {k: REGISTRY.counters().get(k, 0) for k in names}
    link = profiling.host_link_bytes()
    tracer = Tracer("ranged-prefetch")
    with use_tracer(tracer):
        with tracer.span("workflow.train") as root:
            wf._prefetch_text_profiles(batch)
        telemetry.publish_train_profile(root)
    monkeypatch.undo()
    moved = {k: REGISTRY.counters().get(k, 0) - v for k, v in before.items()}
    assert moved == {"text.tokens": whole.tokens,
                     "text.token_slots": 3 * capacity, "text.pack_native": 1,
                     "text.pack_numpy": 0, "text_profile.scan": 1}
    assert profiling.host_link_bytes() - link == 4 * capacity
    walks = [s for s in tracer.spans if s.name == "prefetch.walk"]
    assert sorted(s.attrs["kind"] for s in walks) == ["head", "range",
                                                      "range"]
    assert {s.attrs["column"] for s in walks} == {"txt"}
    assert sum(s.attrs["rows"] for s in walks) == rows
    packs = [s for s in tracer.spans if s.name == "text.pack_ids"]
    assert len(packs) == 3
    assert all(set(s.attrs) == {"tokens", "words", "capacity", "num_hashes"}
               and s.attrs["capacity"] == capacity
               and s.attrs["num_hashes"] == 64 for s in packs)
    assert sum(s.attrs["tokens"] for s in packs) == whole.tokens
    assert sum(s.attrs["words"] for s in packs) == -(-whole.tokens // 3)
    (prefetch,) = [s for s in tracer.spans
                   if s.name == "prefetch.text_profiles"]
    assert prefetch.attrs == {"rows": rows, "columns": 1}
    assert REGISTRY.gauge("prologue.workers").value == 3 and all(
        chain(tracer.spans, s)[1] == "prefetch.text_profiles"
        for s in packs + walks)
    table = REGISTRY.gauge("train.span_profile").value
    assert table["text.pack_ids"]["count"] == 3
    for metric in ("text_pack_s", "text_profile_s"):
        reader = importlib.import_module(f"benchmark.layer_metrics.{metric}")
        assert reader.read({"trace": True}) >= 0.0      # a number, not None


def test_phase_timer_reads_the_monotonic_clock(monkeypatch):
    import time
    monkeypatch.setattr(time, "time", lambda: (_ for _ in ()).throw(
        AssertionError("PhaseTimer read the wall clock")))
    timer = profiling.PhaseTimer()
    with timer.phase("p"):
        pass
    monkeypatch.undo()
    assert timer.phases[0].wall_s >= 0.0
    assert timer.app_metrics("t").total_wall_s >= timer.phases[0].wall_s


# --------------------------------------------------------------------------
# (c) jit events
# --------------------------------------------------------------------------

def jit_events(tracer):
    return [s for s in tracer.spans if s.name in telemetry.JIT_EVENTS]


def test_a_fresh_jit_lands_its_three_events_under_the_open_span():
    def fresh_under_a(x):
        return x * 2.0 + 1.0

    x = jnp.ones(7)
    jax.block_until_ready(x)
    tracer = Tracer("jit")
    totals0 = profiling.compile_stats()
    rows0 = profiling.program_stats()
    with use_tracer(tracer):
        with tracer.span("a") as a:
            jax.jit(fresh_under_a)(x)
        with tracer.span("b") as b:
            pass
    mine = [e for e in jit_events(tracer)
            if e.attrs["fun_name"] == "jit(fresh_under_a)"]
    assert [e.name for e in mine] == ["jit.trace", "jit.lower", "jit.compile"]
    assert {e.parent_id for e in mine} == {a.span_id}
    assert not [e for e in jit_events(tracer) if e.parent_id == b.span_id]
    assert all(e.attrs["seconds"] > 0 for e in mine)
    assert mine[2].attrs["cache_hit"] in (False, True)
    assert mine[0].attrs["cache_hit"] is None

    row = profiling.program_stats()["jit(fresh_under_a)"]
    assert "jit(fresh_under_a)" not in rows0
    assert (row["traces"], row["lowers"], row["compiles"]) == (1, 1, 1)
    assert row["trace_s"] == mine[0].attrs["seconds"]
    assert row["compile_s"] == mine[2].attrs["seconds"]
    # the totals the benchmark reads count what they counted before
    totals1 = profiling.compile_stats()
    compiles = [e for e in jit_events(tracer) if e.name == "jit.compile"]
    assert totals1["backend_compiles"] - totals0["backend_compiles"] == len(
        compiles)
    assert totals1["compile_s"] - totals0["compile_s"] == pytest.approx(
        sum(e.attrs["seconds"] for e in compiles))
    assert REGISTRY.snapshot()["gauges"]["compile.programs"][
        "jit(fresh_under_a)"] == row
    assert telemetry.span_profile(tracer.spans)["a"]["jit_s"] == pytest.approx(
        sum(e.attrs["seconds"] for e in jit_events(tracer)
            if e.parent_id == a.span_id))


def test_two_threads_with_spans_of_their_own_get_their_own_events():
    x = jnp.ones(5)
    jax.block_until_ready(x)
    tracer = Tracer("threads")
    ids = {}
    barrier = threading.Barrier(2)

    def work(tag):
        def fn(v):
            return v * 3.0 - 1.0
        fn.__name__ = f"fresh_on_{tag}"
        with tracer.span(f"thread.{tag}") as sp:
            ids[tag] = sp.span_id
            barrier.wait()
            jax.jit(fn)(x)
            barrier.wait()      # both spans are open while both compile

    with use_tracer(tracer), tracer.span("install"):
        threads = [threading.Thread(target=work, args=(t,))
                   for t in ("one", "two")]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    for tag in ("one", "two"):
        mine = [e for e in jit_events(tracer)
                if e.attrs["fun_name"] == f"jit(fresh_on_{tag})"]
        assert [e.name for e in mine] == ["jit.trace", "jit.lower",
                                          "jit.compile"]
        assert {e.parent_id for e in mine} == {ids[tag]}


def test_a_pool_thread_without_a_span_falls_under_the_installing_thread():
    x = jnp.ones(3)
    jax.block_until_ready(x)
    tracer = Tracer("pool")

    def fresh_on_a_pool_thread(v):
        return v - 4.0

    with use_tracer(tracer), tracer.span("orchestrator") as sp:
        t = threading.Thread(
            target=lambda: jax.jit(fresh_on_a_pool_thread)(x))
        t.start()
        t.join()
    mine = [e for e in jit_events(tracer)
            if e.attrs["fun_name"] == "jit(fresh_on_a_pool_thread)"]
    assert len(mine) == 3 and {e.parent_id for e in mine} == {sp.span_id}


def test_a_jit_traced_inside_another_trace_is_not_counted_twice():
    @jax.jit
    def nested_inner(v):
        return jnp.tanh(v) + 1.0

    def nested_outer(v):
        return nested_inner(v) * nested_inner(v + 1.0)

    x = jnp.ones(9)
    jax.block_until_ready(x)
    tracer = Tracer("nested")
    with use_tracer(tracer), tracer.span("a"):
        jax.jit(nested_outer)(x)
    names = [e.attrs["fun_name"] for e in jit_events(tracer)
             if e.name == "jit.trace"]
    assert "jit(nested_outer)" in names
    assert "jit(nested_inner)" not in names
    rows = profiling.program_stats()
    assert "jit(nested_inner)" not in rows
    outer = rows["jit(nested_outer)"]
    assert outer["traces"] == 1 and outer["nested"] >= 1
    # the outer trace's seconds hold the inner one's: the sum of the events
    # is the time the thread spent in jit steps, each second once
    traced = [e for e in jit_events(tracer)
              if e.attrs["fun_name"] == "jit(nested_outer)"]
    assert sum(e.attrs["seconds"] for e in traced) == pytest.approx(
        outer["trace_s"] + outer["lower_s"] + outer["compile_s"])


def test_no_tracer_no_event_and_the_table_still_fills():
    def fresh_without_tracer(v):
        return v + 2.0

    assert telemetry.active_tracer() is None
    jax.jit(fresh_without_tracer)(jnp.ones(2))
    row = profiling.program_stats()["jit(fresh_without_tracer)"]
    assert (row["traces"], row["compiles"]) == (1, 1)


# --------------------------------------------------------------------------
# (d) the benchmark's readers
# --------------------------------------------------------------------------

def reader(name):
    return importlib.import_module("benchmark.layer_metrics." + name)


PROFILE = {
    "workflow.train": {"count": 1, "total_s": 5.0, "self_s": 0.1,
                       "jit_s": 0.0},
    "transform.apply": {"count": 2, "total_s": 1.25, "self_s": 0.05,
                        "jit_s": 0.0},
    "transform.first_call": {"count": 2, "total_s": 0.75, "self_s": 0.75,
                             "jit_s": 0.5},
    "sanity.fit": {"count": 1, "total_s": 0.875, "self_s": 0.0,
                   "jit_s": 0.0},
    "sanity.stats": {"count": 1, "total_s": 0.5, "self_s": 0.5,
                     "jit_s": 0.125},
    "selector.winner_refit": {"count": 1, "total_s": 0.375, "self_s": 0.375,
                              "jit_s": 0.0625},
}
TRACE = {"busy_s": 3.0, "window_s": 5.0, "device_ops": [], "idle_gaps": [
    ["sanity.rules", 0.5], ["transform.first_call", 0.25],
    ["phase.prefetch", 0.125], ["phase.fit:SanityChecker", 0.0625],
    ["rff.distributions", 0.03125], ["phase.read", 0.015625],
    ["selector.sweep", 0.5], ["phase.selector", 0.25],
    ["unattributed", 0.125]]}
BY_HAND = {"prologue_idle_s": 0.984375, "transform_s": 1.25,
           "sanity_s": 0.875, "refit_s": 0.375, "train_jit_s": 0.6875}


@pytest.fixture
def profile_gauge():
    gauge = REGISTRY.gauge("train.span_profile")
    before = gauge.value
    gauge.set(PROFILE)
    yield gauge
    gauge.set(before)


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_reader_returns_the_hand_count(profile_gauge, name):
    assert reader(name).read({"trace": TRACE, "trains": []}) == BY_HAND[name]


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_reader_reads_nothing_without_a_trace_or_a_profile(profile_gauge,
                                                           name):
    assert reader(name).read({"trace": None, "trains": []}) is None
    profile_gauge.set(0)            # what a gauge nobody set reads
    got = reader(name).read({"trace": TRACE, "trains": []})
    assert got is None or name == "prologue_idle_s"
    profile_gauge.set({"workflow.train": PROFILE["workflow.train"]})
    got = reader(name).read({"trace": TRACE, "trains": []})
    assert got is None or name in ("prologue_idle_s", "train_jit_s")


def test_readers_say_what_benchmark_json_says():
    import json
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    entries = {m["name"]: m for m in manifest["per_layer"]}
    for name in BY_HAND:
        mod, entry = reader(name), entries[name]
        assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
            entry["layer"], entry["unit"], entry["source"], entry["moves"])
        assert entry["workloads"] == ["mixed_sweep", "mixed_sweep_x4",
                                      "text_sweep", "typed_sweep",
                                      "maps_sweep"]
        assert entry["better"] == "lower"


# --------------------------------------------------------------------------
# (e) scope names in the compiled programs
# --------------------------------------------------------------------------

def hlo(jitted, *args, **kw):
    return jitted.lower(*args, **kw).compile().as_text()


X = np.linspace(-1.0, 1.0, 64 * 5, dtype=np.float32).reshape(64, 5)
Y = (np.arange(64) % 2).astype(np.float32)
FOLDS = np.ones((2, 64), np.float32)
PENALTIES = np.asarray([0.1, 0.2], np.float32)


def scope_cases():
    from transmogrifai_tpu import metrics_device
    from transmogrifai_tpu.models import solvers
    from transmogrifai_tpu.models.linear import _linear_device_scores
    from transmogrifai_tpu.preparators import sanity_checker
    scores = np.ones((64, 2, 3), np.float32)
    return {
        "col_stats": (lambda: hlo(sanity_checker._col_stats, X, Y),
                      ["sanity.col_stats"]),
        "col_stats_with_contingency": (
            lambda: hlo(sanity_checker._col_stats_with_contingency, X, Y,
                        np.asarray([0, 1], np.int32),
                        np.asarray([0.0, 1.0], np.float32)),
            ["sanity.col_stats", "sanity.contingency"]),
        "linear_grid_fit": (
            lambda: hlo(solvers.linear_grid_fit, X, Y, FOLDS, PENALTIES,
                        PENALTIES, loss="logistic"),
            ["linear.lipschitz", "linear.fista"]),
        "single_fit": (
            lambda: hlo(solvers.fista_fit, X, Y, FOLDS[0], np.float32(0.1),
                        np.float32(0.1)),
            ["linear.refit", "linear.lipschitz", "linear.fista"]),
        "panel_aupr": (
            lambda: hlo(metrics_device.masked_aupr_fold_grid, Y, scores,
                        FOLDS), ["panel.aupr"]),
        "panel_auroc": (
            lambda: hlo(metrics_device.masked_auroc_fold_grid, Y, scores,
                        FOLDS), ["panel.auroc"]),
        "score_linear": (
            lambda: hlo(_linear_device_scores, X, np.ones(5, np.float32),
                        np.ones(1, np.float32), kind="binary", full=True),
            ["score.linear"]),
    }


@pytest.mark.parametrize("case", sorted(scope_cases()))
def test_compiled_program_holds_its_scope_names(case):
    text, scopes = scope_cases()[case]
    text = text()
    for scope in scopes:
        assert scope in text, (case, scope)
    if case == "linear_grid_fit":
        assert "linear.refit" not in text     # a grid lane is no refit


PANEL_PROGRAMS = {          # case: (function, scores' shape, masks)
    "aupr_fold_grid": ("masked_aupr_fold_grid", (64, 2, 3), FOLDS),
    "auroc_fold_grid": ("masked_auroc_fold_grid", (64, 2, 3), FOLDS),
    "aupr_grid": ("masked_aupr_grid", (64, 3), FOLDS[0]),
    "auroc_grid": ("masked_auroc_grid", (64, 3), FOLDS[0]),
    "aupr_grid_mask_a_candidate": ("masked_aupr_grid", (64, 2), FOLDS),
    "auroc_grid_mask_a_candidate": ("masked_auroc_grid", (64, 2), FOLDS),
}


@pytest.mark.parametrize("case", sorted(PANEL_PROGRAMS))
def test_panel_program_sorts_once_and_gathers_nothing(case):
    """The panel finds its tie groups by a comparison with the neighbour and
    a scan (PR 32).  A data-dependent gather is what a TPU pays 12.5 ns an
    element for, and ``searchsorted(s, s)`` was ⌈log2(rows+1)⌉ of them in a
    ``while``: this is the guard against a later edit bringing one back."""
    from transmogrifai_tpu import metrics_device
    function, shape, masks = PANEL_PROGRAMS[case]
    scores = np.linspace(-1.0, 1.0, int(np.prod(shape)),
                         dtype=np.float32).reshape(shape)
    text = hlo(getattr(metrics_device, function), Y, scores, masks)
    assert ("panel.aupr" if "aupr" in function else "panel.auroc") in text
    counts = {op: text.count(f" {op}(") for op in ("gather", "while", "sort")}
    assert counts == {"gather": 0, "while": 0, "sort": 1}


def test_fused_transform_names_each_stage_by_class_and_kind(narrow):
    from transmogrifai_tpu.compiled import ScoreProgram, _stage_scope
    model = narrow[1]
    stages = [st for st in model.stages if st.is_device_op
              or st.supports_staging]
    prog = ScoreProgram([[st] for st in stages],
                        [f.name for st in stages for f in st.output_features])
    batch = small_workflow(8).generate_raw_data()
    prog(batch, keep_intermediate=True)
    texts = [jitted.lower(jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
        prog._input_specs[key])).compile().as_text()
        for key, (jitted, _) in prog._jitted.items()]
    assert texts
    joined = "\n".join(texts)
    scopes = {_stage_scope(st) for st in stages}
    found = {s for s in scopes if s in joined}
    assert {s.split(".")[1] for s in found} >= {
        "SanityCheckerModel", "VectorsCombiner"}, (scopes, found)
    for st in stages:       # a uid is a process counter: never in a scope
        assert st.uid not in _stage_scope(st)
        assert _stage_scope(st).startswith("transform.")


# --------------------------------------------------------------------------
# (f) spans as annotations inside profiler_trace
# --------------------------------------------------------------------------

def host_event_names(log_dir):
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert paths, os.listdir(log_dir)
    names = set()
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            names.update(e.name for e in line.events)
    return names


def test_spans_lie_in_the_profile_inside_profiler_trace_only(tmp_path):
    tracer = Tracer("annotated")
    with use_tracer(tracer):
        with tracer.span("span.before.the.profile"):
            jnp.ones(4).block_until_ready()
        with profiling.profiler_trace(str(tmp_path)):
            with tracer.span("span.inside.the.profile"):
                jnp.ones(4).block_until_ready()
        assert profiling.span_annotation("x") is None
        with tracer.span("span.after.the.profile"):
            pass
    names = host_event_names(str(tmp_path))
    assert "span.inside.the.profile" in names
    assert "span.before.the.profile" not in names
    assert "span.after.the.profile" not in names
    assert {s.name for s in tracer.spans} - set(telemetry.JIT_EVENTS) == {
        "span.before.the.profile", "span.inside.the.profile",
        "span.after.the.profile"}

"""The prologue pool and the fit phase traced where their work runs (ISSUE
39): one accounting of every job on the train's ``HostPool`` (when a job was
ready, started and ended: ``prologue.queue_s``, ``prologue.wait_s``,
``prologue.workers``), a ``prefetch.walk`` span on the worker that runs each
walk, a ``transform.fit.<class>`` span around each estimator's fit, and the
benchmark's readers of them.  All on the CPU: counts, names, threads and
bounds, never a time."""

import importlib
import json
import os
import threading
import time

import jax
import numpy as np
import pytest

from transmogrifai_tpu import telemetry
from transmogrifai_tpu import types as T
from transmogrifai_tpu import workflow as workflow_mod
from transmogrifai_tpu.columns import Column, ColumnBatch
from transmogrifai_tpu.features import features_from_schema
from transmogrifai_tpu.models.linear import OpLogisticRegression
from transmogrifai_tpu.native import load
from transmogrifai_tpu.ops import text_profile as tp
from transmogrifai_tpu.ops.transmogrify import transmogrify
from transmogrifai_tpu.preparators.sanity_checker import SanityChecker
from transmogrifai_tpu.selector import (BinaryClassificationModelSelector,
                                        ModelCandidate, ModelSelector, grid)
from transmogrifai_tpu.stages.base import Estimator
from transmogrifai_tpu.telemetry import (REGISTRY, MetricsRegistry, Tracer,
                                         use_tracer)
from transmogrifai_tpu.workflow import Workflow

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
READERS = ("prologue_wait_s", "prologue_queue_s", "stage_fit_s")


def reader(name):
    return importlib.import_module("benchmark.layer_metrics." + name)


def counters(*names):
    return {n: REGISTRY.counters().get(n, 0) for n in names}


def moved(before):
    return {n: REGISTRY.counters().get(n, 0) - v for n, v in before.items()}


def chain(spans, sp):
    by_id = {s.span_id: s for s in spans}
    names = []
    while sp is not None:
        names.append(sp.name)
        sp = by_id.get(sp.parent_id)
    return names


def small_workflow(rows=600, seed=3, text_rows=None):
    """Two reals, a count, a pick list and a hashed text column;
    RawFeatureFilter, SanityChecker and a 2-point LR grid.  ``text_rows``:
    the text column's values repeat with that period (a long column of
    short values walks in milliseconds)."""
    rng = np.random.default_rng(seed)
    x1 = rng.normal(size=rows).astype(np.float32)
    x2 = rng.normal(size=rows)
    y = (x1 + 0.5 * x2 + rng.normal(scale=0.5, size=rows) > 0
         ).astype(np.float32)
    words = np.asarray([f"w{i}" for i in range(400)], dtype=object)
    period = text_rows or rows
    txt = np.empty(period, dtype=object)
    txt[:] = [" ".join(words[rng.integers(0, 400, size=4)])
              for _ in range(period)]
    txt = txt[np.arange(rows) % period]
    cat = np.asarray(["a", "b", "c"], dtype=object)[rng.integers(0, 3, rows)]
    schema = {"y": T.RealNN, "x1": T.Real, "x2": T.Real, "n": T.Integral,
              "cat": T.PickList, "txt": T.Text}
    batch = ColumnBatch({
        "y": Column(T.RealNN, y),
        "x1": Column(T.Real, x1, rng.random(rows) > 0.1),
        "x2": Column(T.Real, x2),
        "n": Column(T.Integral, rng.integers(0, 30, rows),
                    np.ones(rows, bool)),
        "cat": Column(T.PickList, cat), "txt": Column(T.Text, txt)}, rows)
    label, predictors = features_from_schema(schema, response="y")
    checked = label.sanity_check(transmogrify(predictors, num_hashes=8),
                                 remove_bad_features=True)
    sel = BinaryClassificationModelSelector(models=[ModelCandidate(
        OpLogisticRegression(), grid(reg_param=[0.01, 0.1]),
        "OpLogisticRegression")])
    sel.set_input(label, checked)
    return (Workflow().set_input_batch(batch)
            .set_result_features(sel.get_output())
            .with_raw_feature_filter(min_fill_rate=0.001))


def traced_prologue(wf, batch, pool):
    """What ``Workflow._train_guarded`` runs on its pool, as an accelerator
    host runs it, under the phases' spans; returns the tracer."""
    tracer = Tracer("prologue")
    with use_tracer(tracer), tracer.span("workflow.train"):
        with tracer.span("phase.prefetch"):
            wf._prefetch_text_profiles(batch, pool)
        with tracer.span("phase.rff"):
            wf._raw_feature_filter.filter_batch(batch, wf.raw_features)
    return tracer


@pytest.fixture
def accelerator_host(monkeypatch):
    """Ranges of one block on four cores, a batch of any size a large one,
    and jax telling the prefetch it runs on an accelerator."""
    if load("textprof") is None:
        pytest.skip("no native toolchain")
    monkeypatch.setattr(tp, "MIN_RANGE_BLOCKS", 1)
    monkeypatch.setattr(os, "sched_getaffinity", lambda pid: set(range(4)))
    monkeypatch.setattr(workflow_mod, "PREFETCH_MIN_ROWS", 500)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


# --------------------------------------------------------------------------
# (a) the pool's accounting
# --------------------------------------------------------------------------

def test_each_walk_is_a_span_on_the_worker_that_runs_it(accelerator_host):
    """A ranged text column, a pick list and the filter's jobs on one pool
    of four workers: one ``prefetch.walk`` a walk (a head and two ranges,
    a whole pick list), each on a worker thread with its kind, rows and
    time queued; the filter's jobs on workers too; the calling thread's
    waits no longer than the walls of the spans it waited in."""
    rows = 3 * tp.BLOCK_ROWS
    wf = small_workflow(rows=rows, text_rows=97)
    batch = wf.generate_raw_data()
    before = counters("prologue.wait_s", "prologue.queue_s")
    with tp.host_pool(len(wf.raw_features)) as pool:
        assert pool.workers == 4
        tracer = traced_prologue(wf, batch, pool)
    spent = moved(before)
    spans, here = tracer.spans, threading.get_ident()
    walks = [s for s in spans if s.name == "prefetch.walk"]
    assert sorted((s.attrs["column"], s.attrs["kind"]) for s in walks) == [
        ("cat", "whole"), ("txt", "head"), ("txt", "range"),
        ("txt", "range")]
    assert all(set(s.attrs) == {"column", "kind", "rows", "queued_s"}
               and s.attrs["queued_s"] >= 0.0 and s.thread != here
               for s in walks)
    assert sum(s.attrs["rows"] for s in walks
               if s.attrs["column"] == "txt") == rows
    assert {chain(spans, s)[1] for s in walks} == {"prefetch.text_profiles"}
    jobs = [s for s in spans if s.name == "rff.feature"]
    assert len(jobs) == 5 and here not in {s.thread for s in jobs}
    assert REGISTRY.gauge("prologue.workers").value == 4
    (prefetch,) = [s for s in spans if s.name == "prefetch.text_profiles"]
    (joined,) = [s for s in spans if s.name == "rff.distributions"]
    assert 0.0 <= spent["prologue.wait_s"] <= (prefetch.duration_s
                                                + joined.duration_s)
    assert spent["prologue.queue_s"] >= 0.0


def test_a_job_handed_to_a_free_worker_waits_in_no_queue():
    before = counters("prologue.queue_s", "prologue.wait_s")
    with tp.HostPool(2) as pool:
        job = pool.submit(lambda: time.sleep(0.02) or 7)
        assert pool.join(job) == 7
        waited = moved(before)["prologue.wait_s"]
        assert pool.join(job) == 7          # done: no wait counted
    assert moved(before) == {"prologue.queue_s": 0.0,
                             "prologue.wait_s": waited}
    assert waited > 0.0


def test_jobs_past_the_width_wait_and_are_counted_once():
    """Six jobs of 20 ms on two workers: four wait, side by side; the union
    of their waits is more than 0 and no more than the call's wall."""
    before = counters("prologue.queue_s")
    t = time.monotonic()
    with tp.HostPool(2) as pool:
        futures = [pool.submit(lambda: time.sleep(0.02)) for _ in range(6)]
        for f in futures:
            pool.join(f)
    wall = time.monotonic() - t
    assert 0.0 < moved(before)["prologue.queue_s"] <= wall


def test_a_job_held_by_its_submitter_waits_from_when_it_was_ready():
    before = counters("prologue.queue_s")
    with tp.HostPool(2) as pool:
        pool.join(pool.submit(lambda: None, time.monotonic() - 0.25))
    assert moved(before)["prologue.queue_s"] >= 0.25


@pytest.mark.parametrize("intervals,covered", [
    ([], 0.0),
    ([(0.0, 1.0)], 1.0),
    ([(0.0, 1.0), (2.0, 3.0)], 2.0),
    ([(0.0, 2.0), (1.0, 3.0)], 3.0),            # overlapping: once
    ([(1.0, 3.0), (0.0, 4.0), (2.0, 2.5)], 4.0),  # nested, out of order
    ([(0.0, 1.0), (1.0, 2.0)], 2.0),            # touching
])
def test_the_queue_adds_the_union_of_the_waits(intervals, covered):
    assert tp._union_s(intervals) == covered


def test_a_job_cancelled_before_it_ran_leaves_the_count_whole():
    gate = threading.Event()
    with tp.HostPool(2) as pool:
        running = [pool.submit(gate.wait) for _ in range(2)]
        queued = pool.submit(lambda: None)
        assert queued.cancel()
        gate.set()
        for f in running:
            pool.join(f)
        assert pool._out == 0
        assert pool.join(pool.submit(lambda: 3)) == 3


# --------------------------------------------------------------------------
# (b) the fit phase
# --------------------------------------------------------------------------

@pytest.mark.parametrize("cv", ["plain", "workflow_cv"])
def test_each_estimators_fit_is_a_span_under_its_phase(cv):
    """One ``transform.fit.<class>`` a vectorizer, under the ``phase.fit:``
    span that names its class, with its rows and inputs; none for the
    selector or SanityChecker, which open their own."""
    wf = small_workflow()
    if cv == "workflow_cv":
        wf = wf.with_workflow_cv()
    tracer = Tracer("fits")
    with use_tracer(tracer):
        wf.train()
    spans = tracer.spans
    fits = [s for s in spans if s.name.startswith("transform.fit.")]
    estimators = [st for st in workflow_mod.dag_stages(
        workflow_mod.compute_dag(wf.result_features))
        if isinstance(st, Estimator)
        and not isinstance(st, (ModelSelector, SanityChecker))]
    assert sorted(s.name for s in fits) == sorted(
        f"transform.fit.{type(st).__name__}" for st in estimators)
    assert len(estimators) >= 3
    inputs = {type(st).__name__: len(st.input_features) for st in estimators}
    for s in fits:
        phase = chain(spans, s)[1]
        cls = s.name[len("transform.fit."):]
        assert phase.startswith("phase.fit:") and cls in phase
        assert s.attrs == {"rows": 600, "inputs": inputs[cls]}
    assert not [s for s in spans if s.name in (
        "transform.fit.SanityChecker",
        "transform.fit.BinaryClassificationModelSelector",
        "transform.fit.ModelSelector")]
    assert [s for s in spans if s.name == "sanity.fit"]
    profile = REGISTRY.gauge("train.span_profile").value
    assert reader("stage_fit_s").read({"trace": True}) == pytest.approx(
        sum(s.duration_s for s in fits))
    assert sum(r["count"] for n, r in profile.items()
               if n.startswith("transform.fit.")) == len(fits)


# --------------------------------------------------------------------------
# (c) every name the prologue opens is one prologue_idle_s counts
# --------------------------------------------------------------------------

# the worker spans of a text column's packing keep their names:
# ``text_pack_s`` reads ``text.pack_ids`` by name
KEPT_NAMES = ("text.pack_ids", "text.python_tokenize")


def prologue_span_names(spans):
    """Names of the spans (not jit events) that lie under a phase of the
    prologue, on any thread."""
    phases = ("phase.read", "phase.prefetch", "phase.rff", "phase.fit:")
    out = set()
    for s in spans:
        up = chain(spans, s)
        if s.name.startswith("jit.") or s.name.startswith("phase."):
            continue
        if any(n.startswith(phases) for n in up[1:]):
            out.add(s.name)
    return out


def test_every_span_the_prologue_opens_is_one_its_idle_reader_counts(
        accelerator_host, monkeypatch):
    spans_of = reader("prologue_idle_s").SPANS
    wf = small_workflow(rows=3 * tp.BLOCK_ROWS, text_rows=97)
    batch = wf.generate_raw_data()
    with tp.host_pool(len(wf.raw_features)) as pool:
        walked = traced_prologue(wf, batch, pool).spans
    monkeypatch.undo()          # the whole train, on the CPU, with its pool
    monkeypatch.setattr(os, "sched_getaffinity", lambda pid: set(range(4)))
    monkeypatch.setattr(workflow_mod, "PREFETCH_MIN_ROWS", 500)
    tracer = Tracer("train")
    with use_tracer(tracer):
        small_workflow().train()
    names = prologue_span_names(walked) | prologue_span_names(tracer.spans)
    assert {"prefetch.walk", "prefetch.text_profiles", "rff.feature",
            "rff.distributions", "sanity.fit", "transform.apply"} <= names
    assert any(n.startswith("transform.fit.") for n in names)
    assert sorted(n for n in names - set(KEPT_NAMES)
                  if not n.startswith(spans_of)) == []


# --------------------------------------------------------------------------
# (d) off: counters and nothing else
# --------------------------------------------------------------------------

def test_without_a_tracer_the_pool_writes_its_counters_and_no_span(
        accelerator_host, monkeypatch):
    class NoSpan:
        def __init__(self, *a, **kw):
            raise AssertionError("a Span was made with no tracer installed")

    gauges = []
    real_set = telemetry.Gauge.set
    monkeypatch.setattr(telemetry, "Span", NoSpan)
    monkeypatch.setattr(telemetry.Gauge, "set", lambda self, v: (
        gauges.append(self.name), real_set(self, v)))
    assert telemetry.active_tracer() is None
    wf = small_workflow(rows=3 * tp.BLOCK_ROWS, text_rows=97)
    batch = wf.generate_raw_data()
    before = counters("prologue.wait_s", "prologue.queue_s")
    with tp.host_pool(len(wf.raw_features)) as pool:
        wf._prefetch_text_profiles(batch, pool)
        _, _, got = wf._raw_feature_filter.filter_batch(batch,
                                                        wf.raw_features)
    dag = workflow_mod.compute_dag(wf.result_features)
    vectorizer = next(st for st in workflow_mod.dag_stages(dag)
                      if isinstance(st, Estimator)
                      and not isinstance(st, (ModelSelector, SanityChecker)))
    assert workflow_mod._fit_stage(vectorizer, batch) is not None
    assert gauges == ["prologue.workers"]
    assert set(moved(before)) == {"prologue.wait_s", "prologue.queue_s"}
    assert all(v >= 0.0 for v in moved(before).values())
    assert len(got.train_distributions) == 5


# --------------------------------------------------------------------------
# (e) the readers
# --------------------------------------------------------------------------

@pytest.fixture
def registry(monkeypatch):
    """A registry of its own, as a fresh process has it."""
    fresh = MetricsRegistry()
    monkeypatch.setattr(telemetry, "REGISTRY", fresh)
    return fresh


TRAINS = [{}, {}, {}]       # three trains in the window, one in set-up


@pytest.mark.parametrize("name,counter", [
    ("prologue_wait_s", "prologue.wait_s"),
    ("prologue_queue_s", "prologue.queue_s")])
def test_a_counter_reader_divides_by_the_trains_of_the_process(
        registry, name, counter):
    assert reader(name).read({"trains": TRAINS}) is None
    registry.counter(counter).inc(3.0)
    assert reader(name).read({"trains": TRAINS}) == 0.75
    assert reader(name).read({"trains": []}) is None


def test_the_fit_reader_adds_the_fits_of_the_profile(registry):
    profile = {
        "workflow.train": {"count": 1, "total_s": 5.0, "self_s": 1.0,
                           "jit_s": 0.0},
        "transform.fit.IntegralVectorizer": {"count": 2, "total_s": 0.5,
                                             "self_s": 0.5, "jit_s": 0.0},
        "transform.fit.OneHotEstimator": {"count": 1, "total_s": 0.25,
                                          "self_s": 0.25, "jit_s": 0.0},
        "transform.first_call": {"count": 1, "total_s": 2.0,
                                 "self_s": 2.0, "jit_s": 1.0},
        "sanity.fit": {"count": 1, "total_s": 1.0, "self_s": 1.0,
                       "jit_s": 0.0}}
    gauge = registry.gauge("train.span_profile")
    assert reader("stage_fit_s").read({"trace": True}) is None   # unset
    gauge.set(profile)
    assert reader("stage_fit_s").read({"trace": True}) == 0.75
    assert reader("stage_fit_s").read({"trace": None}) is None
    gauge.set({"workflow.train": profile["workflow.train"]})     # the parent
    assert reader("stage_fit_s").read({"trace": True}) is None


@pytest.mark.parametrize("name", READERS)
def test_a_reader_says_what_benchmark_json_says(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    entry = {m["name"]: m for m in manifest["per_layer"]}[name]
    mod = reader(name)
    assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
        entry["layer"], entry["unit"], entry["source"], entry["moves"])
    assert entry["workloads"] == ["mixed_sweep", "mixed_sweep_x4",
                                  "text_sweep", "typed_sweep", "maps_sweep"]
    assert entry["better"] == "lower"
    # these three, and the map walks' reader after them
    assert [m["name"] for m in manifest["per_layer"][-4:-1]] == list(READERS)

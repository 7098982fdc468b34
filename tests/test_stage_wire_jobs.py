"""A staged stage's host prologue as one job an input column on a train's
prologue pool (``stages.base.ColumnWired``): the pivot's, the circular
date's and the coordinate's wire from jobs against the inline
``transform_staged`` wire, byte for byte; the fused transform's output on
both paths; the jobs of a train, started as each model is fitted and joined
at the flush, counted by ``transform.wires_ahead``; a replaced column, a job
that raises, and what a fitted model keeps.  All on the CPU: bytes, counts,
names and parents, never a time."""

import os
import threading

import jax
import numpy as np
import pytest

from benchmark import run
from benchmark.reference import common, plain
from transmogrifai_tpu import types as T
from transmogrifai_tpu import workflow as workflow_mod
from transmogrifai_tpu.columns import Column, ColumnBatch
from transmogrifai_tpu.compiled import ScoreProgram
from transmogrifai_tpu.features import features_from_schema
from transmogrifai_tpu.ops import text_profile as tp
from transmogrifai_tpu.ops.categorical import OneHotEstimator
from transmogrifai_tpu.ops.dates import DateToUnitCircleVectorizer
from transmogrifai_tpu.ops.geo import GeolocationVectorizer
from transmogrifai_tpu.resilience import FailureLog, use_failure_log
from transmogrifai_tpu.stages.base import ColumnWired
from transmogrifai_tpu.telemetry import REGISTRY, Tracer, use_tracer

ROWS = 5003
MANIFEST = run.load_json("BENCHMARK.json")
SEED = 2 ** 31 + 40


def ahead():
    return REGISTRY.counters().get("transform.wires_ahead", 0)


def columns(rows=ROWS, seed=40):
    """Two pivots (a few levels with nulls; 300 levels, past a uint8 id),
    two dates (masked with dates before 1970; no mask), two coordinates
    (an array with (0, 0) rows masked; objects with None and empty
    lists)."""
    rng = np.random.default_rng(seed)
    few = np.asarray(["a", "b", "c", None], dtype=object)[
        rng.integers(0, 4, rows)]
    many = np.asarray([f"v{i}" for i in range(300)], dtype=object)[
        rng.integers(0, 300, rows)]
    when = rng.integers(-2 ** 41, 2 ** 41, size=rows)
    later = 1356912000000 + rng.integers(0, 31536000000, size=rows)
    xyz = np.c_[40.75 + 0.03 * rng.standard_normal(rows),
                -73.98 + 0.04 * rng.standard_normal(rows),
                np.ones(rows)].astype(np.float32)
    here = rng.random(rows) > 0.05
    xyz[~here] = 0.0
    held = np.empty(rows, dtype=object)
    for i in range(rows):
        held[i] = (xyz[i, ::-1].astype(np.float64).tolist() if i % 7
                   else ([] if i % 2 else None))
    cols = {"few": Column(T.PickList, few), "many": Column(T.PickList, many),
            "when": Column(T.DateTime, when, rng.random(rows) > 0.1),
            "later": Column(T.DateTime, later, None),
            "at": Column(T.Geolocation, xyz, here),
            "from": Column(T.Geolocation, held)}
    kinds = {n: c.kind for n, c in cols.items()}
    return ColumnBatch(cols, rows), kinds


@pytest.fixture(scope="module")
def fitted():
    """The three vectorizers fitted on ``columns``: a pivot of both string
    columns (300 levels kept, so the second one's ids need int32), the two
    dates, the two coordinates."""
    batch, kinds = columns()
    _, feats = features_from_schema(dict(kinds, y=T.RealNN), response="y")
    by = {f.name: f for f in feats}
    pivot = OneHotEstimator(top_k=400, min_support=1)
    pivot.set_input(by["few"], by["many"])
    circle = DateToUnitCircleVectorizer()
    circle.set_input(by["when"], by["later"])
    coords = GeolocationVectorizer()
    coords.set_input(by["at"], by["from"])
    models = {"pivot": pivot.fit(batch), "dates": circle.fit(batch),
              "coords": coords.fit(batch)}
    return batch, models


@pytest.fixture
def pool(monkeypatch):
    monkeypatch.setattr(os, "sched_getaffinity", lambda pid: set(range(6)))
    with tp.host_pool(6) as p:
        yield p


def same_wire(a, b):
    assert list(a) == list(b)
    for k in a:
        assert a[k].dtype == b[k].dtype and a[k].shape == b[k].shape, k
        assert a[k].tobytes() == b[k].tobytes(), k


# --------------------------------------------------------------------------
# (a) the wire from jobs is the inline wire, byte for byte
# --------------------------------------------------------------------------

@pytest.mark.parametrize("which", ["pivot", "dates", "coords"])
def test_the_wire_from_jobs_is_the_inline_wire(fitted, pool, which):
    batch, models = fitted
    model = models[which]
    assert isinstance(model, ColumnWired)
    inline, body = model.transform_staged(batch)
    tracer = Tracer("jobs")
    with use_tracer(tracer):
        model.start_wires(batch, pool)
        parts = model.take_wires(batch)
    joined, body2 = model.transform_staged(batch, parts)
    same_wire(inline, joined)
    assert np.array_equal(np.asarray(body(inline).values),
                          np.asarray(body2(joined).values))
    # one span a column, on a worker, with the column's own bytes
    jobs = [s for s in tracer.spans
            if s.name == "transform.stage_wires." + type(model).__name__]
    names = [f.name for f in model.input_features]
    assert sorted(s.attrs["column"] for s in jobs) == sorted(names)
    for s in jobs:
        i = names.index(s.attrs["column"])
        assert s.attrs["rows"] == ROWS and s.thread != threading.get_ident()
        assert s.attrs["wire_bytes"] == sum(
            v.nbytes for v in model.column_wire(i, batch[names[i]]).values())


def test_the_wires_cover_both_id_widths_both_masks_and_both_holdings(
        fitted):
    """What (a) compares: uint8 and int32 ids, a date with and without a
    mask, a coordinate held as an array and as objects."""
    batch, models = fitted
    ids = models["pivot"].transform_staged(batch)[0]
    assert (ids["ids0"].dtype, ids["ids1"].dtype) == (np.uint8, np.int32)
    assert len(models["pivot"].fitted["vocabs"]["many"]) == 300
    dates = models["dates"].transform_staged(batch)[0]
    assert sorted(dates) == ["day0", "day1", "ms0", "ms1", "null0"]
    assert batch["at"].is_device and batch["from"].is_host_object()
    coords = models["coords"].transform_staged(batch)[0]
    assert sorted(coords) == sorted(
        ["fills"] + [f"{p}{i}" for i in (0, 1)
                     for p in ("lat", "lon", "accuracy", "null")])


def test_the_fused_output_is_equal_on_both_paths(fitted, pool):
    batch, models = fitted
    stages = list(models.values())
    names = [m.output_features[0].name for m in stages]
    inline = ScoreProgram([stages], names)(batch)
    before = ahead()
    for m in stages:
        m.start_wires(batch, pool)
    tracer = Tracer("fused")
    with use_tracer(tracer):
        joined = ScoreProgram([stages], names)(batch)
    assert ahead() - before == 6
    for n in names:
        assert np.array_equal(np.asarray(inline[n].values),
                              np.asarray(joined[n].values))
    # the calling thread joined: no stage's prologue ran on it
    assert not [s for s in tracer.spans
                if s.name.startswith("transform.stage_wires.")
                and s.thread == threading.get_ident()]
    assert all(m._wire_jobs is None for m in stages)


# --------------------------------------------------------------------------
# (b) the jobs of a train
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def typed_cell():
    tiny = run.cpu_cells()["typed_sweep"]
    cell = run.Cell(MANIFEST, "typed_sweep", tiny["rows"], tiny["limits"])
    return cell, cell.program.make_data(cell.rows, SEED, cell.config)


def typed_train(cell, data, tracer=None):
    before = ahead()
    rec = run.one_train(cell, data, "cpu", tracer)
    jax.clear_caches()
    assert not rec["why_failed"], rec["why_failed"]
    return rec, ahead() - before


def test_a_typed_train_makes_its_ten_wires_ahead(typed_cell, monkeypatch):
    """With a pool up, each of the ten input columns' wire is a job started
    when its model is fitted, under the span open on the training thread
    then; the flush joins them (``transform.stage_wires`` on the training
    thread has no stage child); what the train produced, compared with the
    reference, is the inline train's to the last digit."""
    cell, data = typed_cell
    inline, counted = typed_train(cell, data)
    assert counted == 0
    monkeypatch.setattr(workflow_mod, "PREFETCH_MIN_ROWS", 1000)
    monkeypatch.setattr(os, "sched_getaffinity", lambda pid: set(range(6)))
    tracer = Tracer("typed")
    rec, counted = typed_train(cell, data, tracer)
    assert counted == 10
    spans = tracer.spans
    by_id = {s.span_id: s for s in spans}
    (train,) = [s for s in spans if s.name == "workflow.train"]
    jobs = [s for s in spans if s.name.startswith("transform.stage_wires.")]
    assert sorted((s.name.rsplit(".", 1)[1], s.attrs["column"])
                  for s in jobs) == sorted(
        [("OneHotModel", c) for c in ("medallion", "hack_license",
                                      "vendor_id", "rate_code",
                                      "store_and_fwd_flag", "payment_type")]
        + [("DateToUnitCircleModel", c) for c in ("pickup_datetime",
                                                  "dropoff_datetime")]
        + [("GeolocationVectorizerModel", c) for c in ("pickup",
                                                       "dropoff")])
    (flush,) = [s for s in spans if s.name == "transform.stage_wires"
                and s.attrs["stages"] > 1]
    for s in jobs:
        parent = by_id[s.parent_id]
        assert s.thread != train.thread and parent.thread == train.thread
        # the span open on the training thread when a worker took the job
        # up: the fit phase, a later fit, or the flush itself
        assert parent.name.startswith(("phase.fit:", "transform.",
                                       "workflow.train"))
        assert parent.start_s <= s.start_s <= flush.end_s
        assert s.attrs["rows"] == cell.rows and s.attrs["wire_bytes"] > 0
    ref = cell.reference.reference(
        data, cell.config, plain.Precision.stated("cpu"),
        cell.reference.question(inline["produced"]), seed=SEED)
    ok, want = run.verdict(cell, [inline["produced"]], ref)
    assert ok
    assert run.verdict(cell, [rec["produced"]], ref) == (ok, want)


def test_no_pool_no_job(typed_cell, monkeypatch):
    """One core: the pool is None and every wire is made at the flush."""
    cell, data = typed_cell
    monkeypatch.setattr(workflow_mod, "PREFETCH_MIN_ROWS", 1000)
    monkeypatch.setattr(os, "sched_getaffinity", lambda pid: {0})
    tracer = Tracer("one core")
    _, counted = typed_train(cell, data, tracer)
    assert counted == 0
    stages = [s for s in tracer.spans
              if s.name.startswith("transform.stage_wires.")]
    assert len(stages) == 3 and {s.thread for s in stages} == {
        threading.get_ident()}


@pytest.mark.parametrize("other", ["mixed_sweep", "text_sweep"])
def test_the_criteo_and_text_trains_start_no_wire_job(other, monkeypatch):
    """Their staged stage is ``SmartTextVectorizerModel``, which hands its
    packed words to the link from the calling thread: with a pool up, no
    job, the counter there and 0."""
    tiny = run.cpu_cells()[other]
    small = run.Cell(MANIFEST, other, 2048, tiny["limits"])
    selector = {k: dict(v, **{a: v[a][:1] for a in common.grid_keys(v)},
                        max_iter=2)
                for k, v in small.config["selector"].items()}
    small.config = dict(small.config, selector=selector)
    data = small.program.make_data(2048, SEED, small.config)
    monkeypatch.setattr(workflow_mod, "PREFETCH_MIN_ROWS", 1000)
    monkeypatch.setattr(os, "sched_getaffinity", lambda pid: set(range(6)))
    _, counted = typed_train(small, data)
    assert counted == 0 and "transform.wires_ahead" in REGISTRY.counters()


def test_a_fitted_model_keeps_no_job_after_the_train(typed_cell,
                                                     monkeypatch):
    cell, data = typed_cell
    monkeypatch.setattr(workflow_mod, "PREFETCH_MIN_ROWS", 1000)
    monkeypatch.setattr(os, "sched_getaffinity", lambda pid: set(range(6)))
    model = cell.program.build(data, cell.config).train()
    wired = [s for s in model.stages if isinstance(s, ColumnWired)]
    assert sorted(type(s).__name__ for s in wired) == [
        "DateToUnitCircleModel", "GeolocationVectorizerModel", "OneHotModel"]
    assert all(s._wire_jobs is None for s in wired)
    jax.clear_caches()


# --------------------------------------------------------------------------
# (c) a replaced column, a job that raises
# --------------------------------------------------------------------------

def test_a_replaced_column_gets_the_inline_wire(fitted, pool):
    """The jobs read the fitted batch's columns; a flush over a batch that
    holds another Column under one of those names makes every wire of the
    stage itself, from the columns it is given, and counts none ahead."""
    batch, models = fitted
    model = models["dates"]
    moved = Column(T.DateTime, batch["when"].values + 86_400_000 * 3 + 17,
                   batch["when"].mask)
    other = batch.with_column("when", moved)
    model.start_wires(batch, pool)
    before = ahead()
    tracer = Tracer("replaced")
    name = model.output_features[0].name
    with use_tracer(tracer):
        got = ScoreProgram([[model]], [name])(other)
    assert ahead() == before and model._wire_jobs is None
    (inline,) = [s for s in tracer.spans
                 if s.name == "transform.stage_wires.DateToUnitCircleModel"]
    assert inline.thread == threading.get_ident()
    want = ScoreProgram([[model]], [name])(other)
    stale = ScoreProgram([[model]], [name])(batch)
    assert np.array_equal(np.asarray(got[name].values),
                          np.asarray(want[name].values))
    assert not np.array_equal(np.asarray(got[name].values),
                              np.asarray(stale[name].values))
    assert model.take_wires(other) is None


def demotion(model, batch, name, raise_when, pool=None):
    """Score ``model`` with its ``column_wire`` raising where
    ``raise_when()`` says, its wires started on ``pool`` first where one is
    given; returns (output, the failure log's events, stages demoted, host
    stages counted, the threads ``column_wire`` ran on)."""
    real = type(model).column_wire
    calls = []

    def column_wire(self, i, col):
        calls.append(threading.get_ident())
        if raise_when():
            raise ArithmeticError("no wire")
        return real(self, i, col)
    model.column_wire = column_wire.__get__(model)
    if pool is not None:
        model.start_wires(batch, pool)
    try:
        log = FailureLog()
        before = REGISTRY.counters().get("transform.host_stages", 0)
        prog = ScoreProgram([[model]], [name])
        with use_failure_log(log):
            out = prog(batch)
        events = [(e.stage, e.action, e.point, e.cause) for e in log.events]
        return (np.asarray(out[name].values), events, set(prog._demoted),
                REGISTRY.counters()["transform.host_stages"] - before,
                calls)
    finally:
        del model.column_wire


def test_a_job_that_raises_demotes_the_stage_as_an_inline_raise_does(
        fitted, pool):
    batch, models = fitted
    model = models["coords"]
    name = model.output_features[0].name
    want = np.asarray(ScoreProgram([[model]], [name])(batch)[name].values)
    main = threading.get_ident()

    first = []
    inline = demotion(model, batch, name,
                      lambda: not first and not first.append(1))
    jobs = demotion(model, batch, name,
                    lambda: threading.get_ident() != main, pool)
    assert model._wire_jobs is None
    for got in (inline, jobs):
        out, events, demoted, host_stages, _ = got
        assert np.array_equal(out, want)
        assert demoted == {model.uid} and host_stages == 1
    assert inline[1] == jobs[1] and len(jobs[1]) == 1
    assert {t != main for t in jobs[4]} == {True, False}


# --------------------------------------------------------------------------
# (d) a date's wire in one native pass, numpy's bytes
# --------------------------------------------------------------------------

def numpy_day_and_ms(ms):
    from transmogrifai_tpu.ops import dates
    day, rest = np.divmod(np.asarray(ms, np.int64), dates._MS_DAY)
    return (day % dates._DAY_CYCLE).astype(np.int32), rest.astype(np.int32)


@pytest.mark.parametrize("held", ["contiguous", "strided", "int32", "empty"])
def test_a_dates_wire_is_numpys_byte_for_byte(held, monkeypatch):
    """native/datewire.cpp against the numpy form it replaces: the ends of
    int64, dates before 1970, the edges of a day, and drawn ones; a view
    with a stride; a dtype it does not read (numpy takes it); and numpy
    alone where no toolchain is."""
    from transmogrifai_tpu import native
    from transmogrifai_tpu.ops import dates
    module = native.load("datewire")
    if module is None:
        pytest.skip("no native toolchain")
    assert (module.MS_DAY, module.DAY_CYCLE) == (dates._MS_DAY,
                                                 dates._DAY_CYCLE)
    assert "datewire" in native.MODULES
    rng = np.random.default_rng(41)
    info = np.iinfo(np.int64)
    ms = np.r_[np.asarray([0, 1, -1, dates._MS_DAY - 1, dates._MS_DAY,
                           -dates._MS_DAY, -dates._MS_DAY - 1, info.max,
                           info.min, info.min + 1], np.int64),
               rng.integers(info.min, info.max, size=20000, dtype=np.int64),
               rng.integers(-2 ** 45, 2 ** 45, size=20000)]
    ms = {"contiguous": ms, "strided": ms[::3],
          "int32": ms.astype(np.int32), "empty": ms[:0]}[held]
    want = numpy_day_and_ms(ms)
    got = dates._day_and_ms(ms)
    monkeypatch.setattr(native, "load", lambda name: None)
    alone = dates._day_and_ms(ms)
    for a, b, c in zip(got, want, alone):
        assert a.dtype == b.dtype == c.dtype == np.int32
        assert a.tobytes() == np.ascontiguousarray(b).tobytes() \
            == c.tobytes()
    with pytest.raises(TypeError):
        module.day_and_ms(ms.astype(np.float64))


def test_a_first_load_from_many_threads_builds_once(tmp_path, monkeypatch):
    """The first ``native.load`` of a module can come from several of a
    pool's workers at once (the two dates' jobs of a fresh checkout): every
    one gets the module, built once, and nothing falls back."""
    import sys
    from concurrent.futures import ThreadPoolExecutor

    from transmogrifai_tpu import native
    if native.load("datewire") is None:
        pytest.skip("no native toolchain")
    monkeypatch.setattr(native, "_build_dir", lambda: str(tmp_path))
    monkeypatch.delitem(native._CACHE, "datewire")
    monkeypatch.delitem(sys.modules, "_datewire", raising=False)
    monkeypatch.setattr(native, "_REASONS", {})
    with ThreadPoolExecutor(8) as pool:
        got = list(pool.map(lambda _: native.load("datewire"), range(8)))
    assert all(m is not None and m is got[0] for m in got)
    assert native._REASONS == {}
    assert [p.suffix for p in tmp_path.iterdir()] == [".so"]

"""RawFeatureFilter's distributions as jobs that hold no GIL (ISSUE 38): the
native range and histogram against numpy's, count for count; a feature's
distribution from a pool's worker against the same function run by the
caller; and the start in the prefetch phase and the join in ``filter_batch``
of a train.  All on the CPU: counts, names, parents and equality, never a
time."""

import os
import threading

import jax
import numpy as np
import pytest

from transmogrifai_tpu import filters, native
from transmogrifai_tpu import types as T
from transmogrifai_tpu import workflow as workflow_mod
from transmogrifai_tpu.columns import Column, ColumnBatch
from transmogrifai_tpu.features import features_from_schema
from transmogrifai_tpu.models.linear import OpLogisticRegression
from transmogrifai_tpu.ops import text_profile as tp
from transmogrifai_tpu.ops.transmogrify import transmogrify
from transmogrifai_tpu.selector import (BinaryClassificationModelSelector,
                                        ModelCandidate, grid)
from transmogrifai_tpu.telemetry import REGISTRY, Tracer, use_tracer
from transmogrifai_tpu.workflow import Workflow

BINS = 100
ROWS = 2 * filters._BLOCK_ROWS + 4099        # two blocks and a ragged third


def counters(*names):
    return {n: REGISTRY.counters().get(n, 0) for n in names}


def moved(before):
    return {n: REGISTRY.counters().get(n, 0) - v for n, v in before.items()}


# --------------------------------------------------------------------------
# (a) the range and the counts, against numpy over the whole column
# --------------------------------------------------------------------------

def plain_range_and_histogram(values, present, value_range=None):
    """Today's numpy path as one statement over the whole column: the
    float64 copy, the finite and present values, ``np.histogram``."""
    x = np.asarray(values, dtype=np.float64)
    keep = np.isfinite(x)
    if present is not None:
        keep &= present
    x = x[keep]
    found = (float(x.min()), float(x.max())) if x.size else None
    if value_range is None and found is None:
        return None, np.zeros(BINS)
    lo, hi = value_range or found
    if lo == hi:
        hi = lo + 1.0
    return found, np.histogram(x, bins=BINS, range=(lo, hi))[0].astype(
        np.float64)


def _on_the_edges(dtype):
    """Values ON the edges numpy makes for [0, 700], the top one included,
    next to them by an ulp, and others; NaN and both infinities for a
    float."""
    rng = np.random.default_rng(38)
    edges = np.linspace(0.0, 700.0, BINS + 1)
    values = np.concatenate([
        np.repeat(edges, 40), np.nextafter(edges, np.inf),
        np.nextafter(edges, -np.inf), [0.0, 700.0, 700.0],
        rng.uniform(0.0, 700.0, ROWS - 42 * (BINS + 1) - 3)])
    rng.shuffle(values)
    values = values.astype(dtype)
    if values.dtype.kind == "f":
        values[::977], values[5::1201], values[7::1301] = \
            np.nan, np.inf, -np.inf
    return values


def _mask(rows=ROWS):
    return np.random.default_rng(39).random(rows) > 0.02


COLUMNS = {
    # name: () -> (values, present, the range a score batch pinned or None)
    **{f"{np.dtype(d).name} {how}": (lambda d=d, how=how: (
        _on_the_edges(d), _mask() if how == "masked" else None, None))
       for d in (np.float64, np.float32, np.int64, np.int32)
       for how in ("masked", "whole")},
    "one value": lambda: (np.full(ROWS, 2.5, np.float32), _mask(), None),
    "one integer": lambda: (np.full(ROWS, -7, np.int64), None, None),
    "all null": lambda: (_on_the_edges(np.float64), np.zeros(ROWS, bool),
                         None),
    "nothing finite": lambda: (np.full(ROWS, np.nan, np.float32), None, None),
    "empty": lambda: (np.zeros(0, np.float64), np.zeros(0, bool), None),
    "pinned wider": lambda: (_on_the_edges(np.float32), _mask(),
                             (-3.0, 1234.5)),
    "pinned narrower": lambda: (_on_the_edges(np.float64), _mask(),
                                (100.0, 350.0)),
    "pinned to one value": lambda: (_on_the_edges(np.int32), None,
                                    (350.0, 350.0)),
    "epoch ms": lambda: (1356912000000 + np.random.default_rng(40).integers(
        0, 31536000000, size=ROWS), _mask(), None),
    "int64 beyond 2**53": lambda: (
        (1 << 53) + np.random.default_rng(41).integers(
            -5000, 5000, size=ROWS) * 3 + 1, _mask(), None),
    "a map's key": lambda: (np.random.default_rng(42).normal(
        size=(ROWS, 3))[:, 1], np.stack([_mask()] * 3, axis=1)[:, 2], None),
    "one row": lambda: (np.asarray([4.0]), None, None),
}


@pytest.fixture(params=["native", "numpy"])
def helper(request, monkeypatch):
    """The distribution with native/numdist.cpp, and with ``native.load``
    finding no toolchain."""
    if request.param == "numpy":
        monkeypatch.setattr(native, "load", lambda name: None)
    elif native.load("numdist") is None:
        pytest.skip("no native toolchain")
    return request.param


@pytest.mark.parametrize("column", sorted(COLUMNS))
def test_a_numeric_distribution_is_numpys_count_for_count(column, helper):
    values, present, pinned = COLUMNS[column]()
    found, hist = plain_range_and_histogram(values, present, pinned)
    before = counters("rff.native_columns", "rff.numpy_columns")
    assert filters._finite_range(values, present) == found
    got = filters._histogram_of(
        values, np.ones(len(values), bool) if present is None else present,
        T.Real, BINS, 255, value_range=pinned)
    assert got.dtype == np.float64 and np.array_equal(got, hist)
    binned = 0 if found is None and pinned is None else 1
    assert moved(before) == {"rff.native_columns": binned * (helper == "native"),
                             "rff.numpy_columns": binned * (helper == "numpy")}


def test_the_helper_refuses_what_it_cannot_read_and_numpy_takes_it():
    """A dtype, a mask or a range the native passes are not written for
    goes to numpy's; handed to the helper all the same, it raises."""
    numdist = native.load("numdist")
    if numdist is None:
        pytest.skip("no native toolchain")
    x = np.arange(10.0)
    for values, present in [(x.astype(np.float16), None),
                            (x.astype(np.uint8), None),
                            (x.astype(">f8"), None), (x.reshape(2, 5), None),
                            (x, np.ones(10, np.uint8)), (list(x), None)]:
        assert filters._numdist(values, present) is None
        with pytest.raises(TypeError):
            numdist.range(values, present)
    with pytest.raises(ValueError):
        numdist.range(x, np.ones(9, bool))
    for edges in ([1.0, 1.0], [2.0, 1.0], [-1e308, 1e308], [0.0, np.nan]):
        with pytest.raises(ValueError):
            numdist.histogram(x, None, np.asarray(edges))
    with pytest.raises(TypeError):
        numdist.histogram(x, None, np.asarray([0, 9]))
    before = counters("rff.native_columns", "rff.numpy_columns")
    assert np.array_equal(
        filters._finite_histogram(x.astype(np.float16), None, 0.0, 9.0, 3),
        [3.0, 3.0, 4.0])
    assert moved(before) == {"rff.native_columns": 0, "rff.numpy_columns": 1}
    assert "numdist" in native.MODULES


# --------------------------------------------------------------------------
# (b) a feature's distribution from a pool against the caller's own
# --------------------------------------------------------------------------

def typed_batch(rows=3000, seed=5, shift=0.0):
    """A float32 amount with a mask, an int64 date, a count with no mask,
    two string columns, a coordinate held as [N, 3], a real map and an
    object-held real (both walked in Python), and a label."""
    rng = np.random.default_rng(seed)
    amount = rng.gamma(2.0, 6.0, size=rows).astype(np.float32) + shift
    amount[::97] = np.nan
    xyz = np.zeros((rows, 3), np.float32)
    here = rng.random(rows) > 0.05
    xyz[:, 0] = np.where(here, 40.75 + rng.integers(0, 50, rows) * 0.01, 0.0)
    xyz[:, 1] = np.where(here, -74.0 + rng.integers(0, 40, rows) * 0.01, 0.0)
    xyz[:, 2] = np.where(here, 4.0, 0.0)
    levels = np.asarray(["CSH", "CRD", "DIS", None], dtype=object)
    loose = np.empty(rows, dtype=object)
    loose[:] = [None if i % 11 == 0 else float(i % 13) for i in range(rows)]
    maps = np.empty(rows, dtype=object)
    maps[:] = [{"a": float(i % 7), "b": float(i % 3) + shift} if i % 5
               else None for i in range(rows)]
    schema = {"y": T.RealNN, "amount": T.Currency, "when": T.DateTime,
              "count": T.Integral, "pay": T.PickList, "who": T.ID,
              "at": T.Geolocation, "m": T.RealMap, "loose": T.Real}
    cols = {
        "y": Column(T.RealNN, (rng.random(rows) < 0.4).astype(np.float32)),
        "amount": Column(T.Currency, amount, rng.random(rows) > 0.1),
        "when": Column(T.DateTime, 1356912000000 + rng.integers(
            0, 31536000000, size=rows), np.ones(rows, bool)),
        "count": Column(T.Integral, rng.integers(0, 9, size=rows)),
        "pay": Column(T.PickList, levels[rng.integers(0, 4, rows)]),
        "who": Column(T.ID, np.asarray(
            [f"{i % 211:08X}" for i in range(rows)], dtype=object)),
        "at": Column(T.Geolocation, xyz, here),
        "m": Column(T.RealMap, maps),
        "loose": Column(T.Real, loose)}
    label, predictors = features_from_schema(schema, response="y")
    return ColumnBatch(cols, rows), [label] + list(predictors)


class BatchReader:
    """A score reader that hands out one batch, and counts how often."""

    def __init__(self, batch):
        self.batch, self.reads = batch, 0

    def generate_batch(self, raw_features):
        self.reads += 1
        return self.batch


@pytest.mark.parametrize("score", ["no score batch", "a shifted score batch"])
def test_jobs_on_a_pool_give_what_the_caller_computes(score):
    """``start_distributions`` then ``filter_batch`` against ``filter_batch``
    alone: the results equal to the digit; the arrays and the strings as
    jobs (a coordinate through ``_array_item_bins`` among them), the map and
    the Python objects inline; the score batch read once, at the start."""
    batch, raw = typed_batch()
    reader = BatchReader(typed_batch(seed=6, shift=400.0)[0]) \
        if score.startswith("a") else None
    kw = dict(max_js_divergence=0.5, score_reader=reader)
    names = ("rff.python_rows",)
    before = counters(*names)
    tracer = Tracer("alone")
    with use_tracer(tracer):
        _, dropped, alone = filters.RawFeatureFilter(**kw).filter_batch(
            batch, raw)
    inline = [s for s in tracer.spans if s.name == "rff.feature"]
    assert len(inline) == 8 and {s.thread for s in inline} == {
        threading.get_ident()}
    python_rows = moved(before)["rff.python_rows"]
    assert python_rows > 0

    batch, raw = typed_batch()
    rff = filters.RawFeatureFilter(**kw)
    before, reads = counters(*names), reader.reads if reader else 0
    threads = set()
    real = rff._feature_distributions

    def spy(f, *a, **k):
        threads.add((f.name, threading.get_ident()))
        return real(f, *a, **k)

    rff._feature_distributions = spy
    with tp.HostPool(3) as pool:
        started = rff.start_distributions(batch, raw, pool)
        assert sorted(started.jobs) == ["amount", "at", "count", "pay",
                                        "when", "who"]
        if reader:
            assert reader.reads == reads + 1
        _, dropped_too, joined = rff.filter_batch(batch, raw)
    assert joined.to_json() == alone.to_json()
    assert [f.name for f in dropped_too] == [f.name for f in dropped]
    assert ("amount" in alone.dropped) == bool(reader)
    assert moved(before) == {"rff.python_rows": python_rows}
    if reader:
        assert reader.reads == reads + 1
    here = threading.get_ident()
    assert {n for n, t in threads if t == here} == {"m", "loose"}
    assert {n for n, t in threads if t != here} == set(started.jobs)
    assert rff._started is None


def test_many_jobs_on_more_threads_than_cores_lose_no_count():
    """Forty numeric columns and a dozen of strings as jobs on 24 threads
    with the interpreter switching every 10 microseconds: the distributions
    are the caller's own and every counter moved by exactly its columns."""
    import sys
    rows = 20000

    def batch_and_features():
        schema, cols = {"y": T.RealNN}, {"y": Column(T.RealNN, np.zeros(
            rows, np.float32))}
        for j in range(40):
            schema[f"x{j}"] = T.Real
            cols[f"x{j}"] = Column(T.Real, np.random.default_rng(j).normal(
                size=rows).astype(np.float32 if j % 2 else np.float64))
        for j in range(12):
            schema[f"s{j}"] = T.PickList
            cols[f"s{j}"] = Column(T.PickList, np.asarray(
                [f"v{(i * (j + 2)) % 53}" for i in range(rows)], dtype=object))
        label, predictors = features_from_schema(schema, response="y")
        return ColumnBatch(cols, rows), [label] + list(predictors)

    alone = filters.RawFeatureFilter().filter_batch(*batch_and_features())[2]
    names = ("rff.native_columns", "rff.numpy_columns", "text_profile.scan")
    batch, raw = batch_and_features()
    rff = filters.RawFeatureFilter()
    before, interval = counters(*names), sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with tp.HostPool(24) as pool:
            started = rff.start_distributions(batch, raw, pool)
            for job in started.jobs.values():
                job.result(timeout=120)
            got = rff.filter_batch(batch, raw)[2]
    finally:
        sys.setswitchinterval(interval)
    binned = moved(before)
    assert binned.pop("rff.native_columns") + binned.pop(
        "rff.numpy_columns") == 40
    assert binned == {"text_profile.scan": 12} and len(started.jobs) == 52
    assert got.to_json() == alone.to_json()


def test_what_was_started_for_another_batch_is_not_joined():
    batch, raw = typed_batch()
    other, _ = typed_batch(seed=9)
    rff = filters.RawFeatureFilter()
    here = []
    real = rff._feature_distributions
    with tp.HostPool(2) as pool:
        started = rff.start_distributions(other, raw, pool)
        rff._feature_distributions = lambda f, *a, **k: (
            here.append((f.name, threading.get_ident())) or real(f, *a, **k))
        _, _, got = rff.filter_batch(batch, raw)
    # the six jobs started for the other batch are left; all eight here
    assert len(started.jobs) == 6 and len(here) == 8
    assert {t for _, t in here} == {threading.get_ident()}
    assert got.to_json() == filters.RawFeatureFilter().filter_batch(
        *typed_batch())[2].to_json()


def test_a_walked_column_waits_for_its_walk_and_one_never_released_is_inline():
    batch, raw = typed_batch()
    rff = filters.RawFeatureFilter()
    before = counters("text_profile.scan")
    inline = []
    real = rff._feature_distributions
    with tp.HostPool(2) as pool:
        started = rff.start_distributions(batch, raw, pool,
                                          walked=["pay", "who"])
        rff._feature_distributions = lambda f, *a, **k: (
            inline.append(f.name) or real(f, *a, **k))
        assert "pay" not in started.jobs and "who" not in started.jobs
        tp.column_profile(batch["pay"], 30)         # the caller's walk
        started.walked("pay")
        started.walked("pay")
        started.jobs["pay"].result(timeout=60)
        _, _, got = rff.filter_batch(batch, raw)
    assert moved(before) == {"text_profile.scan": 2}
    assert sorted(started.jobs) == ["amount", "at", "count", "pay", "when"]
    assert sorted(inline) == ["loose", "m", "who"]
    assert got.to_json() == filters.RawFeatureFilter().filter_batch(
        *typed_batch())[2].to_json()


# --------------------------------------------------------------------------
# (c) a train: started in the prefetch phase, joined by the filter
# --------------------------------------------------------------------------

def typed_workflow(rows=600, seed=3, score_reader=None, **rff):
    """Two reals (one masked), a count, a pick list and a hashed text
    column; RawFeatureFilter, SanityChecker and a 2-point LR grid."""
    rng = np.random.default_rng(seed)
    x1 = rng.normal(size=rows).astype(np.float32)
    x2 = rng.normal(size=rows)
    y = (x1 + 0.5 * x2 + rng.normal(scale=0.5, size=rows) > 0
         ).astype(np.float32)
    words = np.asarray([f"w{i}" for i in range(400)], dtype=object)
    txt = np.empty(rows, dtype=object)
    txt[:] = [" ".join(words[rng.integers(0, 400, size=4)])
              for _ in range(rows)]
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
            .with_raw_feature_filter(score_reader=score_reader, **rff))


RFF_COUNTERS = ("rff.native_columns", "rff.numpy_columns", "rff.python_rows",
                "text_profile.scan")


@pytest.fixture
def large(monkeypatch):
    """A batch of 600 rows counts as a large one."""
    monkeypatch.setattr(workflow_mod, "PREFETCH_MIN_ROWS", 500)


def test_a_large_train_computes_every_distribution_off_its_thread(
        large, monkeypatch):
    """On the CPU backend (no walks ahead of the fits, no link to hide) the
    filter's jobs start all the same: every predictor a job, none inline;
    each job's ``rff.feature`` under the span open on the thread that
    started it and on a worker thread; ``rff.distributions`` the calling
    thread's wait, once, so ``rff_s`` adds no thread-seconds; the results
    those of the same train on one core and of ``filter_batch`` alone."""
    if native.load("numdist") is None:
        pytest.skip("no native toolchain")
    monkeypatch.setattr(os, "sched_getaffinity", lambda pid: set(range(6)))
    before = counters(*RFF_COUNTERS)
    tracer = Tracer("jobs")
    with use_tracer(tracer):
        model = typed_workflow().train()
    assert moved(before) == {
        "rff.native_columns": 3, "rff.numpy_columns": 0, "rff.python_rows": 0,
        "text_profile.scan": 2}
    assert REGISTRY.gauge("prologue.workers").value == 6
    spans = tracer.spans
    by_id = {s.span_id: s for s in spans}
    features = [s for s in spans if s.name == "rff.feature"]
    assert sorted(s.attrs["feature"] for s in features) == [
        "cat", "n", "txt", "x1", "x2"]
    assert all(set(s.attrs) == {"feature", "kind", "rows"}
               and s.attrs["rows"] == 600 for s in features)
    (train,) = [s for s in spans if s.name == "workflow.train"]
    (dist,) = [s for s in spans if s.name == "rff.distributions"]
    (decide,) = [s for s in spans if s.name == "rff.decide"]
    assert dist.thread == decide.thread == train.thread
    for s in features:
        parent = by_id[s.parent_id]
        assert s.thread != train.thread and parent.thread == train.thread
        # a job a worker starts between two phases (``PhaseTimer`` reads
        # the devices' memory after a phase's span closes) is the train's
        assert parent.name in ("phase.prefetch", "phase.rff",
                               "rff.distributions", "workflow.train")
        assert parent.start_s <= s.start_s
    assert not [s for s in spans if s.name.startswith("prefetch.")]
    profile = REGISTRY.gauge("train.span_profile").value
    assert profile["rff.distributions"]["count"] == 1
    assert profile["rff.distributions"]["total_s"] == pytest.approx(
        dist.duration_s)
    assert profile["rff.feature"]["count"] == 5
    from benchmark.layer_metrics import rff_s
    assert rff_s.read({"trace": True}) == pytest.approx(
        dist.duration_s + decide.duration_s)

    monkeypatch.setattr(os, "sched_getaffinity", lambda pid: {0})
    before = counters(*RFF_COUNTERS)
    tracer = Tracer("one core")
    with use_tracer(tracer):
        one_core = typed_workflow().train()
    assert moved(before) == {
        "rff.native_columns": 3, "rff.numpy_columns": 0, "rff.python_rows": 0,
        "text_profile.scan": 2}
    inline = [s for s in tracer.spans if s.name == "rff.feature"]
    assert len(inline) == 5 and {s.thread for s in inline} == {
        threading.get_ident()}
    wf = typed_workflow()
    alone = wf._raw_feature_filter.filter_batch(wf.generate_raw_data(),
                                                wf.raw_features)[2]
    assert model.rff_results.to_json() == one_core.rff_results.to_json() \
        == alone.to_json()
    assert len(alone.train_distributions) == 5


def test_a_small_train_starts_nothing(monkeypatch):
    opened = []
    real = tp.host_pool
    monkeypatch.setattr(tp, "host_pool",
                        lambda n: opened.append(n) or real(n))
    tracer = Tracer("small")
    with use_tracer(tracer):
        typed_workflow().train()
    inline = [s for s in tracer.spans if s.name == "rff.feature"]
    assert len(inline) == 5 and {s.thread for s in inline} == {
        threading.get_ident()} and not opened


def test_a_job_that_raises_surfaces_from_the_filter(large, monkeypatch):
    monkeypatch.setattr(os, "sched_getaffinity", lambda pid: set(range(4)))
    real = filters.compute_distribution
    raised_on = []

    def broken(feature, *a, **kw):
        if feature.name == "n":
            raised_on.append(threading.get_ident())
            raise ArithmeticError("no distribution of n")
        return real(feature, *a, **kw)

    monkeypatch.setattr(filters, "compute_distribution", broken)
    wf = typed_workflow()
    with pytest.raises(ArithmeticError, match="no distribution of n"):
        wf.train()
    assert raised_on and raised_on[0] != threading.get_ident()
    assert wf._raw_feature_filter._started is None


def test_a_score_reader_drops_what_it_dropped_before(large, monkeypatch):
    """The score batch is read once, in the prefetch phase; both range jobs
    feed both histograms; the shifted real is dropped for its JS
    divergence, with or without a pool."""
    monkeypatch.setattr(os, "sched_getaffinity", lambda pid: set(range(4)))
    shifted = typed_workflow(seed=4).generate_raw_data()
    x2 = shifted["x2"]
    shifted = shifted.with_column("x2", Column(x2.kind, x2.values + 50.0))
    reader = BatchReader(shifted)
    tracer = Tracer("score reader")
    with use_tracer(tracer):
        model = typed_workflow(score_reader=reader,
                               max_js_divergence=0.5).train()
    jobs = [s for s in tracer.spans if s.name == "rff.feature"]
    assert len(jobs) == 5 and threading.get_ident() not in {
        s.thread for s in jobs}
    assert reader.reads == 1
    assert model.rff_results.dropped == ["x2"]
    assert [f.name for f in model.blacklisted] == ["x2"]
    assert len(model.rff_results.score_distributions) == 5
    wf = typed_workflow(score_reader=BatchReader(shifted),
                        max_js_divergence=0.5)
    alone = wf._raw_feature_filter.filter_batch(wf.generate_raw_data(),
                                                wf.raw_features)[2]
    assert model.rff_results.to_json() == alone.to_json()


def test_on_an_accelerator_a_strings_job_follows_its_walk(large,
                                                          monkeypatch):
    """Told it is on an accelerator, the prefetch walks the pick list and
    the text column on the train's pool and releases each one's job when its
    profile is whole: two walks, not four, and every predictor a job."""
    monkeypatch.setattr(os, "sched_getaffinity", lambda pid: set(range(6)))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    wf = typed_workflow()
    batch = wf.generate_raw_data()
    before = counters(*RFF_COUNTERS, "text_profile.fused_intern")
    tracer = Tracer("accelerator")
    with use_tracer(tracer), tracer.span("phase.prefetch"), \
            tp.host_pool(len(wf.raw_features)) as pool:
        wf._prefetch_text_profiles(batch, pool)
        started = wf._raw_feature_filter._started
        assert sorted(started.jobs) == ["cat", "n", "txt", "x1", "x2"]
        _, _, got = wf._raw_feature_filter.filter_batch(batch,
                                                        wf.raw_features)
    monkeypatch.undo()
    assert moved(before) == {
        "rff.native_columns": 3 * (native.load("numdist") is not None),
        "rff.numpy_columns": 3 * (native.load("numdist") is None),
        "rff.python_rows": 0, "text_profile.scan": 2,
        "text_profile.fused_intern": 2}
    (prefetch,) = [s for s in tracer.spans
                   if s.name == "prefetch.text_profiles"]
    assert prefetch.attrs["columns"] == 2
    walks = [s for s in tracer.spans if s.name == "prefetch.walk"]
    assert sorted((s.attrs["column"], s.attrs["kind"]) for s in walks) == [
        ("cat", "whole"), ("txt", "whole")]
    assert threading.get_ident() not in {s.thread for s in walks}
    wf = typed_workflow()
    assert got.to_json() == wf._raw_feature_filter.filter_batch(
        wf.generate_raw_data(), wf.raw_features)[2].to_json()


def test_under_a_mesh_the_job_finds_the_range_and_the_caller_dispatches(
        large, monkeypatch):
    """With the data mesh up a numeric histogram is a device program: the
    job returns the Summary range alone and ``filter_batch`` dispatches
    ``_sharded_numeric_hist`` from its own thread; counts as without a
    mesh."""
    monkeypatch.setattr(os, "sched_getaffinity", lambda pid: set(range(4)))
    dispatched = []
    real = filters._sharded_numeric_hist

    def spy(*a, **kw):
        dispatched.append(threading.get_ident())
        return real(*a, **kw)

    monkeypatch.setattr(filters, "_sharded_numeric_hist", spy)
    monkeypatch.setenv("TRANSMOGRIFAI_TPU_MESH", "1")
    wf = typed_workflow(rows=640)
    batch = wf.generate_raw_data()
    before = counters(*RFF_COUNTERS)
    tracer = Tracer("mesh")
    with use_tracer(tracer), tp.host_pool(len(wf.raw_features)) as pool:
        wf._prefetch_text_profiles(batch, pool)
        _, _, sharded = wf._raw_feature_filter.filter_batch(batch,
                                                            wf.raw_features)
    assert dispatched == [threading.get_ident()] * 3
    assert moved(before) == {
        "rff.native_columns": 0, "rff.numpy_columns": 0, "rff.python_rows": 0,
        "text_profile.scan": 2}
    by_feature = {}
    for s in tracer.spans:
        if s.name == "rff.feature":
            by_feature.setdefault(s.attrs["feature"], []).append(
                s.thread == threading.get_ident())
    assert {k: sorted(v) for k, v in by_feature.items()} == {
        "x1": [False, True], "x2": [False, True], "n": [False, True],
        "cat": [False], "txt": [False]}
    monkeypatch.setenv("TRANSMOGRIFAI_TPU_MESH", "0")
    wf = typed_workflow(rows=640)
    assert sharded.to_json() == wf._raw_feature_filter.filter_batch(
        wf.generate_raw_data(), wf.raw_features)[2].to_json()


def test_the_walks_keep_to_their_width_on_a_wider_pool(monkeypatch):
    """``profile_columns`` on a pool it was given runs at most ``pool_size``
    of its jobs at once, however many threads the pool has, and leaves the
    pool open."""
    if native.load("textprof") is None:
        pytest.skip("no native toolchain")
    monkeypatch.setattr(tp, "_MAX_WORKERS", 2)
    monkeypatch.setattr(os, "sched_getaffinity", lambda pid: set(range(8)))
    lock, out, most = threading.Lock(), [0], [0]
    real = tp.scan_strings

    def counted(*a, **kw):
        with lock:
            out[0] += 1
            most[0] = max(most[0], out[0])
        try:
            return real(*a, **kw)
        finally:
            with lock:
                out[0] -= 1

    monkeypatch.setattr(tp, "scan_strings", counted)
    rows = 20000
    cols = [Column(T.Text, np.asarray(
        [f"v{(i * (j + 3)) % 997} w" for i in range(rows)], dtype=object))
        for j in range(9)]
    before = counters("prologue.queue_s")
    with tp.host_pool(8) as pool:
        profs = list(tp.profile_columns([(c, 30, 64) for c in cols], pool))
        assert REGISTRY.gauge("prologue.workers").value == pool.workers > 2
        assert pool.submit(lambda: 7).result(timeout=60) == 7
    assert 1 <= most[0] <= 2 and out[0] == 0
    # nine walks on a width of two: the width held some back
    assert moved(before)["prologue.queue_s"] > 0.0
    assert [p.tokens for p in profs] == [2 * rows] * 9

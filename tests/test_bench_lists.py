"""``stackoverflow_accepted`` / ``lists_sweep``: Stack Overflow questions
joined to their asker's history, the asker's website as a ``URL`` and three
event histories as ``DateList``s held as a join makes them, through
``transmogrify`` and both default linear families, at a size a CPU can hold
(the rows of ``fixtures/cpu_cells_lists_sweep.json``).

(a) the program against the configuration's own plain reference, every number
under the fixture's CPU limits through ``run.verdict``, and the control over
one; (b) broken paths make ``correct`` false: a history's first date shipped
for its last, an empty history read as a date, a website's whole url pivoted
for its domain; (c) the spans, counters and scopes of a train whose prologue
pool walks the histories and the website; (d) the new reader; (e) the
configuration's file and the generator; (f) a program without ragged date
lists or their staged pivot refused at once; (g) the cell's part of ``run.py
--selftest``.
"""

import importlib
import json
import os

import numpy as np
import pytest

import jax

from benchmark import run
from benchmark.reference import common, plain

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "lists_sweep"
CONFIG = "stackoverflow_accepted"
SEED = 2 ** 31 + 33
MANIFEST = run.load_json("BENCHMARK.json")
TINY = run.cpu_cells()[CELL]
STATED = plain.Precision.stated("cpu")
COUNTERS = ("transform.host_stages", "rff.python_rows", "pivot.python_values",
            "list_profile.columns", "list_profile.values",
            "transform.wires_ahead")
HISTORIES = ("badge_dates", "question_dates", "answer_dates")


def reader(name):
    return importlib.import_module("benchmark.layer_metrics." + name)


def over(compared):
    return sorted(k for k, c in compared.items() if c["value"] > c["limit"])


def counted(train):
    from transmogrifai_tpu.telemetry import REGISTRY
    before = {k: REGISTRY.counters().get(k, 0) for k in COUNTERS}
    out = train()
    after = REGISTRY.counters()
    return out, {k: after.get(k, 0) - before[k] for k in COUNTERS}


@pytest.fixture(scope="module")
def cell():
    return run.Cell(MANIFEST, CELL, TINY["rows"], TINY["limits"])


@pytest.fixture(scope="module")
def data(cell):
    return cell.program.make_data(cell.rows, SEED, cell.config)


@pytest.fixture(scope="module")
def traced(cell, data):
    """One train under the program's tracer, its prologue on a pool of six
    workers as a train of 100,000 rows or more has it, with what it
    counted."""
    from transmogrifai_tpu import workflow
    from transmogrifai_tpu.telemetry import REGISTRY, Tracer
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(workflow, "PREFETCH_MIN_ROWS", 1000)
        mp.setattr(os, "sched_getaffinity", lambda pid: set(range(6)))
        tracer = Tracer("lists")
        rec, counts = counted(lambda: run.one_train(cell, data, "cpu",
                                                    tracer))
    jax.clear_caches()
    assert not rec["why_failed"], rec["why_failed"]
    rec["counted"] = counts
    rec["spans"] = list(tracer.spans)
    rec["profile"] = REGISTRY.gauge("train.span_profile").value
    return rec


@pytest.fixture(scope="module")
def references(cell, data):
    """The reference's answers, once a question."""
    asked = {}

    def answer(produced):
        ask = cell.reference.question(produced)
        key = json.dumps(ask, sort_keys=True)
        if key not in asked:
            asked[key] = cell.reference.reference(data, cell.config, STATED,
                                                  ask, seed=SEED)
        return asked[key]
    return answer


# (a) ----------------------------------------------------------------------

def test_program_agrees_with_its_reference(cell, data, traced, references):
    p = traced["produced"]
    ok, compared = run.verdict(cell, [p], references(p))
    assert ok, compared
    assert set(compared) == set(TINY["limits"])
    pivots, sets = cell.reference.host_parts(data, cell.config)
    widths = [w for _, w in pivots] + [s[3] for s in sets]
    assert p["stats"].shape == (5, sum(widths) + 18 + 6 + 2 * 513)
    assert {r["family"] for r in p["cv"]} == {"OpLogisticRegression",
                                              "OpLinearSVC"}
    assert len(p["cv"]) == 12 and p["rff_dropped"] == []
    # a model, not the empty one, whose constant score reads the share of
    # positives
    share = float(data["label"].mean())
    assert p["winner"]["metric"] > share + 0.03


def test_control_fails_the_cells_limits(cell, data, traced, references):
    p = traced["produced"]
    low = cell.reference.reference(
        data, cell.config, plain.Precision.control("cpu"),
        cell.reference.question(p), seed=SEED)
    ok, control = run.verdict(
        cell, [common.as_produced(low, p, cell.config)], references(p))
    assert not ok and "stats_gap" in over(control), control


def test_the_reference_states_its_two_rules(cell):
    ref = cell.reference
    assert ref.url_host("HTTPS://User@Host.IO:8080/p") == "host.io"
    assert ref.url_host("host.io") is None
    assert ref.url_host("http://[::1") is None
    day = 86400000
    values = np.array([5, 1, 9, 7, 7], np.int64) * day
    days, null = ref.since_last(values, np.array([0, 3, 3, 5]), 10 * day)
    assert days.tolist() == [1.0, 0.0, 3.0]
    days, _ = ref.since_last(np.array([day + 1]), np.array([0, 1]), 0)
    assert days[0] == np.float32(-(day + 1) / day)
    assert null.tolist() == [0.0, 1.0, 0.0]


# (b) ----------------------------------------------------------------------

def first_date_for_the_last(mp):
    """A history's SinceLast shipped from its first date."""
    from transmogrifai_tpu.ops import list_profile as lp
    walk = lp._walk_dates

    def broken(ragged, text_bins):
        prof = walk(ragged, text_bins)
        prof.hi = prof.lo
        return prof
    mp.setattr(lp, "_walk_dates", broken)


def empty_history_read_as_a_date(mp):
    """An empty history read as a list whose last date is 0."""
    from transmogrifai_tpu.ops import list_profile as lp
    walk = lp._walk_dates

    def broken(ragged, text_bins):
        prof = walk(ragged, text_bins)
        prof.lengths = np.maximum(prof.lengths, 1)
        return prof
    mp.setattr(lp, "_walk_dates", broken)


def whole_url_pivoted(mp):
    """A website's whole url pivoted where its domain is stated."""
    from transmogrifai_tpu.ops import list_profile as lp
    from transmogrifai_tpu.ops.text_profile import _py_intern
    mp.setattr(lp, "_walk_domains",
               lambda strings, which: _py_intern(strings, -1))


@pytest.mark.parametrize("fault", [first_date_for_the_last,
                                   empty_history_read_as_a_date,
                                   whole_url_pivoted])
def test_broken_path_is_not_correct(cell, data, fault, monkeypatch):
    """The whole run_cell path on the CPU with one fault in the program:
    every train ends, and the verdict is not correct."""
    fault(monkeypatch)
    res = run.run_cell(MANIFEST, CELL, SEED, 0, 0, require_chip=False,
                       rows=TINY["rows"], limits=TINY["limits"])
    jax.clear_caches()
    assert res["failed"] == 0 and not res["correct"], res["compared"]


# (c) ----------------------------------------------------------------------

def test_spans_and_counters_of_a_pooled_train(cell, traced):
    spans = traced["spans"]
    by_id = {s.span_id: s for s in spans}
    walks = {s.attrs["column"]: s for s in spans
             if s.name == "prefetch.list_walk"}
    assert set(walks) == set(HISTORIES) | {"website"}
    assert {c: w.attrs["kind"] for c, w in walks.items()} == dict(
        {h: "dates" for h in HISTORIES}, website="domain")
    assert all(w.attrs["rows"] == cell.rows for w in walks.values())
    (joins,) = [s for s in spans if s.name == "prefetch.lists"]
    assert joins.attrs["columns"] == 4 and joins.attrs["rows"] == cell.rows
    assert joins.attrs["values"] == sum(w.attrs["values"]
                                        for w in walks.values())
    assert by_id[joins.parent_id].name == "phase.prefetch"
    # each walk on a worker, under the phase open when it started
    assert all(by_id[w.parent_id].name == "phase.prefetch"
               for w in walks.values())
    assert {w.thread for w in walks.values()} - {joins.thread}
    jobs = [s for s in spans
            if s.name == "transform.stage_wires.DateListVectorizerModel"]
    assert sorted(s.attrs["column"] for s in jobs) == sorted(HISTORIES)
    # a history crosses as 4 bytes and a null bit a row
    assert all(s.attrs["wire_bytes"] == 4 * cell.rows + -(-cell.rows // 8)
               for s in jobs)
    c = traced["counted"]
    assert c["list_profile.columns"] == 4
    assert c["list_profile.values"] == joins.attrs["values"]
    assert c["transform.host_stages"] == 0
    assert c["rff.python_rows"] == 0
    assert c["pivot.python_values"] == 0
    # owner_id's and the website's pivots, the two dates, the three
    # histories and the tags: their wires made by the pool's jobs
    assert c["transform.wires_ahead"] == 8
    assert "transform.host_stage" not in {s.name for s in spans}
    assert traced["profile"]["prefetch.lists"]["count"] == 1


def test_without_a_pool_the_consumers_walk_and_nothing_is_joined(cell, data):
    """A train under 100,000 rows opens no pool: each column is walked where
    first read (RawFeatureFilter, the pivot's fit) and ``prefetch.lists`` is
    not there; the counters read as with a pool."""
    from transmogrifai_tpu.telemetry import Tracer
    tracer = Tracer("inline")
    rec, counts = counted(lambda: run.one_train(cell, data, "cpu", tracer))
    jax.clear_caches()
    assert not rec["why_failed"]
    names = [s.name for s in tracer.spans]
    assert "prefetch.lists" not in names
    assert names.count("prefetch.list_walk") == 4
    assert counts["list_profile.columns"] == 4
    assert counts["transform.host_stages"] == 0
    assert counts["rff.python_rows"] == 0
    assert counts["pivot.python_values"] == 0


def test_the_history_scope_is_in_the_fused_program(cell, data):
    from transmogrifai_tpu.compiled import _stage_scope
    from transmogrifai_tpu.dag import dag_stages
    from transmogrifai_tpu.ops.dates import DateListVectorizerModel
    model = cell.program.build(data, cell.config).train()
    (st,) = [s for s in dag_stages(model.fitted_dag)
             if isinstance(s, DateListVectorizerModel)]
    batch = cell.program.build(data, cell.config)._input_batch
    scope = _stage_scope(st)
    assert scope == "transform.DateListVectorizerModel.OPVector"
    wire, body = st.transform_staged(batch)

    def scoped(w):
        with jax.named_scope(scope):
            return body(w).values
    assert scope in jax.jit(scoped).lower(wire).compile().as_text()


# (d) ----------------------------------------------------------------------

def test_reader_reports_nothing_where_there_is_nothing(monkeypatch):
    from transmogrifai_tpu import telemetry
    monkeypatch.setattr(telemetry, "REGISTRY", telemetry.MetricsRegistry())
    mod = reader("list_profile_s")
    assert mod.read({"trains": [], "trace": None}) is None
    # a traced run of a program that has no such span (the parent)
    telemetry.REGISTRY.gauge("train.span_profile").set(
        {"workflow.train": {"count": 1, "total_s": 1.0, "self_s": 1.0,
                            "jit_s": 0.0}})
    assert mod.read({"trains": [{}],
                     "trace": {"busy_s": 1.0, "window_s": 2.0}}) is None


def test_reader_reads_what_the_program_set(monkeypatch, traced):
    from transmogrifai_tpu import telemetry
    reg = telemetry.MetricsRegistry()
    monkeypatch.setattr(telemetry, "REGISTRY", reg)
    reg.gauge("train.span_profile").set(
        {"prefetch.lists": {"count": 1, "total_s": 0.5, "self_s": 0.5,
                            "jit_s": 0.0}})
    ctx = {"trains": [{}], "trace": {"busy_s": 1.0, "window_s": 2.0}}
    assert reader("list_profile_s").read(ctx) == 0.5
    assert reader("list_profile_s").read(dict(ctx, trace=None)) is None
    reg.gauge("train.span_profile").set(traced["profile"])
    assert reader("list_profile_s").read(ctx) == \
        traced["profile"]["prefetch.lists"]["total_s"] >= 0.0


def test_reader_and_appended_cell_say_what_benchmark_json_says():
    entries = {m["name"]: m for m in MANIFEST["per_layer"]}
    mod, m = reader("list_profile_s"), entries["list_profile_s"]
    assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
        m["layer"], m["unit"], m["source"], m["moves"])
    assert m["workloads"] == [CELL] and m["better"] == "lower"
    assert MANIFEST["per_layer"][-1] is m
    x4_only = {"mesh_devices", "place_s", "relayout_MB", "sweep_mfu_x4",
               "chip_rows_per_s"}
    for name, e in entries.items():
        assert (CELL in e["workloads"]) == (name not in x4_only), name
        assert e["workloads"][-1] == CELL or name in x4_only, name
    assert [w["name"] for w in MANIFEST["workloads"]][-1] == CELL
    assert [c["name"] for c in MANIFEST["configs"]][-1] == CONFIG
    # one chip's cell: the four-chip share of the benchmark is unmoved
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1


# (e) ----------------------------------------------------------------------

def test_configuration_keeps_every_default_and_states_its_cuts():
    from transmogrifai_tpu.ops.dates import DateListVectorizer
    from transmogrifai_tpu.ops.transmogrify import TransmogrifierDefaults as T
    cfg = run.load_json("benchmark", "configs", CONFIG + ".json")
    maps = run.load_json("benchmark", "configs", "yelp_review_maps.json")
    entry = run.by_name(MANIFEST["configs"], CONFIG, "config")
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["reduced"] == cfg["reduced"] == ["rows", "model_types",
                                                  "columns"]
    assert set(cfg["reduced_note"]) == set(cfg["reduced"])
    for said in ("Body", "AboutMe", "Score", "ViewCount", "Reputation"):
        assert said in cfg["reduced_note"]["columns"], said
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    for said in ("Stack Exchange Data Dump", "Posts", "Users", "Badges",
                 "meta.stackexchange.com/q/2677", "accepted answer"):
        assert said in cfg["source"], said
    assert "ASSUMED" in cfg["source_rows_note"]
    assert cfg["rows"] == 1572864 and cfg["rows"] % 262144 == 0
    assert cfg["model_types"] == list(cfg["selector"]) == [
        "OpLogisticRegression", "OpLinearSVC"]
    for key in ("raw_feature_filter", "folds", "fold_seed", "selector",
                "validation_metric", "racing", "guarantees", "work",
                "source_model_types", "sanity_checker"):
        assert cfg[key] == maps[key], key
    t = cfg["transmogrify"]
    assert (t["top_k"], t["min_support"], t["track_nulls"], t["num_hashes"],
            t["max_categorical_cardinality"]) == (
        T.TOP_K, T.MIN_SUPPORT, T.TRACK_NULLS, T.DEFAULT_NUM_OF_FEATURES,
        T.MAX_CATEGORICAL_CARDINALITY)
    dl = DateListVectorizer()
    assert (t["date_list_pivot"], t["reference_ms"]) == (
        dl.get("pivot"), dl.get("reference_ms"))
    assert cfg["precision"]["matrix_storage"] == \
        maps["precision"]["matrix_storage"]
    assert cfg["precision"]["control"] == maps["precision"]["control"]
    assert set(cfg["assumed"]) >= {"typing", "generator", "profiles",
                                   "label", "splitter"}
    P = importlib.import_module("benchmark.programs." + CONFIG)
    columns = (P.TEXTS + P.TAGS + P.DATES + P.IDS + P.URLS + P.LISTS)
    assert set(cfg["schema"]) == {"label", "left_out"} | set(columns)
    assert len(columns) == 10
    wl = run.by_name(MANIFEST["workloads"], CELL, "workload")
    assert (wl["config"], wl["traffic"], wl["chips"]) == (
        CONFIG, "mixed_sweep", 1)
    assert len(wl["why"]) <= 200 and len(entry["why"]) <= 200
    limits = run.load_json("benchmark", "limits", CELL + ".json")
    assert set(limits) - {"why"} == set(TINY["limits"])
    assert all(limits[k] >= 0.0 for k in TINY["limits"])
    with open(os.path.join(ROOT, "benchmark", "fixtures",
                           f"cpu_cells_{CELL}.json")) as fh:
        assert list(json.load(fh)["cells"]) == [CELL]


def test_generator_draws_what_the_configuration_states(cell, data):
    """Same seed, same rows; one entry a row, a history sliced by rows as
    the other arrays; an asker's fields repeated in each of their
    questions; the shares the configuration states."""
    again = cell.program.make_data(cell.rows, SEED, cell.config)
    for k in data:
        if k in HISTORIES:
            assert np.array_equal(data[k].values, again[k].values), k
            assert np.array_equal(data[k].offsets, again[k].offsets), k
        elif data[k].dtype == object:
            assert list(data[k]) == list(again[k]), k
        else:
            assert np.array_equal(data[k], again[k]), k
    other = cell.program.make_data(cell.rows, SEED + 1, cell.config)
    assert not np.array_equal(data["label"], other["label"])
    assert all(len(v) == cell.rows for v in data.values())
    half = {k: v[:100] for k, v in data.items()}
    assert all(len(v) == 100 for v in half.values())
    h = data["badge_dates"]
    assert half["badge_dates"].values.tolist() == \
        h.values[:h.offsets[100]].tolist()
    g = cell.config["generator"]
    assert abs(data["label"].mean() - g["label"]["positive_share"]) < 0.03
    dates = sum(data[k].lengths().mean() for k in HISTORIES)
    assert 30 < dates < 50
    assert all((data[k].lengths() == 0).mean() > 0.2 for k in HISTORIES)
    assert max(data[k].lengths().max() for k in HISTORIES) <= 10000
    # every history's dates lie between the asker's joining and the question
    for k in HISTORIES:
        rows = np.repeat(np.arange(cell.rows), data[k].lengths())
        v = data[k].values
        assert (v >= data["joined"][rows]).all()
        assert (v <= data["created"][rows]).all()
    site = [s is not None for s in data["website"]]
    assert 0.2 < np.mean(site) < 0.3
    assert all(1 <= len(t) <= 5 and len(set(t)) == len(t)
               for t in data["tags"])
    first = {}
    for o, w, l, j in zip(data["owner_id"], data["website"],
                          data["location"], data["joined"]):
        assert first.setdefault(o, (w, l, j)) == (w, l, j)


def test_a_program_without_the_staged_date_lists_is_refused_at_once(
        cell, data, monkeypatch):
    """The parent commit laid under this cell's files has to fail soon and
    cleanly (it would walk every row's list in Python, minutes a train):
    ``build`` leaves with an exit code before anything is trained."""
    from transmogrifai_tpu.ops.dates import DateListVectorizerModel
    monkeypatch.setattr(DateListVectorizerModel, "supports_staging", False)
    with pytest.raises(SystemExit, match="staged DateListVectorizerModel"):
        cell.program.build(data, cell.config)


# (g) ----------------------------------------------------------------------

@pytest.mark.parametrize("trace", [0, 1])
def test_selftest_runs_the_cell(trace):
    """What ``run.py --selftest`` does with the cell: set-up, a window of
    one train, the reference, the verdict, the result's metrics (no device
    metric off the chip)."""
    from transmogrifai_tpu.telemetry import REGISTRY
    # ``host_stages`` reads the process's counter: drop what the tests of
    # other files run in this worker counted before it
    with REGISTRY._lock:
        REGISTRY._counters.pop("transform.host_stages", None)
    res = run.run_cell(MANIFEST, CELL, 2 ** 31 + 7, 0, trace,
                       require_chip=False, rows=TINY["rows"],
                       limits=TINY["limits"])
    assert res["correct"] and res["failed"] == 0 and res["attempted"] == 1
    assert res["device"]["platform"] == "cpu"
    if not trace:
        assert sorted(res["metrics"]) == ["setup_s", "train_wall_s"]
        return
    got = set(res["metrics"])
    assert {"host_stages", "host_link_MB", "prologue_s", "selector_s",
            "window_compiles"} <= got
    assert res["metrics"]["host_stages"]["value"] == 0.0
    # read from a device trace, or from spans beside one, or from a span a
    # train under 100,000 rows does not open
    assert not got & {"device_idle_share", "sweep_mfu", "peak_hbm_GiB",
                      "prologue_idle_s", "list_profile_s", "map_profile_s",
                      "stage_wires_s", "rff_s"}


def test_selftest_has_six_cells():
    assert sorted(run.cpu_cells()) == [CELL, "maps_sweep", "mixed_sweep",
                                       "mixed_sweep_x4", "text_sweep",
                                       "typed_sweep"]
    assert {w["name"] for w in MANIFEST["workloads"]} == set(run.cpu_cells())

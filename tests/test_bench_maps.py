"""``yelp_review_maps`` / ``maps_sweep``: Yelp reviews joined to their
business and reviewer, the business's attributes and hours as
``PickListMap``s and its categories and the member's elite years as
``MultiPickList``s, through ``transmogrify`` and both default linear
families, at a size a CPU can hold (the rows of
``fixtures/cpu_cells_maps_sweep.json``).

(a) the program against the configuration's own plain reference, every number
under the fixture's CPU limits through ``run.verdict``, the control over one,
and a map key's Cramér's V group in both; (b) broken paths make ``correct``
false: a map's absent key read as OTHER, a set's unseen values dropped, a
key's vocabulary taken in the order first seen; (c) the spans, counters and
scopes of a train whose prologue pool walks the maps and sets; (d) the new
reader; (e) the configuration's file and the generator; (f) a program without
the staged forms refused at once; (g) the cell's part of ``run.py
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
CELL = "maps_sweep"
SEED = 2 ** 31 + 33
MANIFEST = run.load_json("BENCHMARK.json")
TINY = run.cpu_cells()[CELL]
STATED = plain.Precision.stated("cpu")
COUNTERS = ("transform.host_stages", "rff.python_rows", "map_profile.columns",
            "map_profile.keys", "transform.wires_ahead", "pivot.python_values")


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
        tracer = Tracer("maps")
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
    parts = cell.reference.host_parts(data, cell.config)
    widths = ([w for _, w in parts[0]] + [w for _, w in parts[1]]
              + [s[3] for s in parts[2]])
    assert p["stats"].shape == (5, 2 + 18 + 4 + 34 + 4 + 513 + sum(widths))
    # every attribute key the rows hold (the rarest are held by a business
    # in ten thousand), every day
    keys = {k for m in data["attributes"] if m for k in m}
    assert len(parts[1]) == len(keys) + 7 and len(keys) >= 35
    assert {r["family"] for r in p["cv"]} == {"OpLogisticRegression",
                                              "OpLinearSVC"}
    assert len(p["cv"]) == 12 and p["rff_dropped"] == []
    # a model, not the empty one, whose constant score reads about
    # (1 + 2/3) / 2 = 0.83
    share = float(data["label"].mean())
    assert p["winner"]["metric"] > (1 + share) / 2 + 0.03


def test_control_fails_the_cells_limits(cell, data, traced, references):
    p = traced["produced"]
    low = cell.reference.reference(
        data, cell.config, plain.Precision.control("cpu"),
        cell.reference.question(p), seed=SEED)
    ok, control = run.verdict(
        cell, [common.as_produced(low, p, cell.config)], references(p))
    assert not ok and "stats_gap" in over(control), control


def test_a_map_key_is_a_cramers_v_group_in_both(cell, data):
    """SanityChecker groups a map pivot's columns key by key; the
    reference's plain table of one key gives the program's V, and no group
    of this table passes 0.95."""
    from transmogrifai_tpu.dag import dag_stages
    R = cell.reference
    model = cell.program.build(data, cell.config).train()
    (sane,) = [s.summary for s in dag_stages(model.fitted_dag)
               if hasattr(getattr(s, "summary", None), "cramers_v_by_group")]
    v = sane.cramers_v_by_group
    assert {"attributes(NoiseLevel)", "hours(Monday)", "categories",
            "elite", "state"} <= set(v)
    parts = R.host_parts(data, cell.config)
    keys = [k for k in sorted({k for m in data["attributes"] if m for k in m})]
    ids, width = parts[1][keys.index("NoiseLevel")]
    table = np.zeros((2, width))
    np.add.at(table, (data["label"].astype(np.int64), ids), 1.0)
    assert v["attributes(NoiseLevel)"] == pytest.approx(R.cramers_v(table),
                                                        abs=1e-6)
    assert max(x for x in v.values() if np.isfinite(x)) < 0.95


# (b) ----------------------------------------------------------------------

def absent_key_read_as_other(mp):
    """A map's absent key and None value read as OTHER, not null."""
    from transmogrifai_tpu.ops import map_vectorizers as mv
    wire = mv.TextMapPivotVectorizerModel.column_wire

    def broken(self, i, col):
        part = wire(self, i, col)
        per_key = self.fitted["per_feature"][self.input_features[i].name]
        ids = part[f"ids{i}"].copy()
        for j, k in enumerate(per_key["keys"]):
            other = len(per_key["vocabs"][k])
            ids[ids[:, j] == other + 1, j] = other
        return {f"ids{i}": ids}
    mp.setattr(mv.TextMapPivotVectorizerModel, "column_wire", broken)


def unseen_set_values_dropped(mp):
    """A set's members past the vocabulary leave OTHER unset."""
    from transmogrifai_tpu.ops import collections as co
    wire = co.MultiPickListVectorizerModel.column_wire

    def broken(self, i, col):
        part = dict(wire(self, i, col))
        k = len(self.fitted["vocabs"][self.input_features[i].name])
        rows = part[f"rows{i}"].copy()
        rows[part[f"slots{i}"] == k] = len(col)
        part[f"rows{i}"] = rows
        return part
    mp.setattr(co.MultiPickListVectorizerModel, "column_wire", broken)


def vocabulary_in_first_seen_order(mp):
    """A key's top values taken in the order first seen, not by count."""
    from transmogrifai_tpu.ops import map_vectorizers as mv

    def first_seen(counts, top_k, min_support):
        return [v for v, c in counts.items() if c >= min_support][:top_k]
    mp.setattr(mv, "top_values_by_count", first_seen)


@pytest.mark.parametrize("fault", [absent_key_read_as_other,
                                   unseen_set_values_dropped,
                                   vocabulary_in_first_seen_order])
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
             if s.name == "prefetch.map_walk"}
    assert set(walks) == {"attributes", "hours", "categories", "elite"}
    assert {c: walks[c].attrs["kind"] for c in walks} == {
        "attributes": "map", "hours": "map", "categories": "set",
        "elite": "set"}
    assert walks["hours"].attrs["keys"] == 7
    assert all(w.attrs["rows"] == cell.rows for w in walks.values())
    (joins,) = [s for s in spans if s.name == "prefetch.maps"]
    assert joins.attrs["columns"] == 4 and joins.attrs["rows"] == cell.rows
    assert joins.attrs["keys"] == walks["attributes"].attrs["keys"] + 7 + 2
    assert by_id[joins.parent_id].name == "phase.prefetch"
    # each walk on a worker, under the phase open when it started
    assert all(by_id[w.parent_id].name == "phase.prefetch"
               for w in walks.values())
    assert {w.thread for w in walks.values()} - {joins.thread}
    for cls, columns in (("TextMapPivotVectorizerModel", 2),
                         ("MultiPickListVectorizerModel", 2)):
        jobs = [s for s in spans
                if s.name == "transform.stage_wires." + cls]
        assert len(jobs) == columns
        assert all(s.attrs["wire_bytes"] > 0 for s in jobs)
    assert traced["counted"]["map_profile.columns"] == 4
    assert traced["counted"]["map_profile.keys"] == joins.attrs["keys"]
    assert traced["counted"]["transform.host_stages"] == 0
    assert traced["counted"]["rff.python_rows"] == 0
    # the ID, place and pick-list pivots read their interning as arrays
    assert traced["counted"]["pivot.python_values"] == 0
    # the six pivots, the two dates, the coordinate, the two maps and the
    # two sets: their wires made by the pool's jobs
    assert traced["counted"]["transform.wires_ahead"] == 13
    assert "transform.host_stage" not in {s.name for s in spans}
    assert traced["profile"]["prefetch.maps"]["count"] == 1


def test_without_a_pool_the_fit_walks_and_nothing_is_joined(cell, data):
    """A train under 100,000 rows opens no pool: the expansions are walked
    where first needed (RawFeatureFilter) and ``prefetch.maps`` is not
    there; the counters read as with a pool."""
    from transmogrifai_tpu.telemetry import Tracer
    tracer = Tracer("inline")
    rec, counts = counted(lambda: run.one_train(cell, data, "cpu", tracer))
    jax.clear_caches()
    assert not rec["why_failed"]
    names = [s.name for s in tracer.spans]
    assert "prefetch.maps" not in names
    assert names.count("prefetch.map_walk") == 4
    assert counts["map_profile.columns"] == 4
    assert counts["transform.host_stages"] == 0
    assert counts["rff.python_rows"] == 0
    assert counts["pivot.python_values"] == 0


def test_map_and_set_scopes_are_in_the_fused_program(cell, data):
    from transmogrifai_tpu.compiled import _stage_scope
    from transmogrifai_tpu.dag import dag_stages
    from transmogrifai_tpu.ops.collections import MultiPickListVectorizerModel
    from transmogrifai_tpu.ops.map_vectorizers import \
        TextMapPivotVectorizerModel
    model = cell.program.build(data, cell.config).train()
    stages = {type(s): s for s in dag_stages(model.fitted_dag)}
    batch = cell.program.build(data, cell.config)._input_batch
    for cls in (TextMapPivotVectorizerModel, MultiPickListVectorizerModel):
        st = stages[cls]
        scope = _stage_scope(st)
        assert scope == f"transform.{cls.__name__}.OPVector"
        wire, body = st.transform_staged(batch)

        def scoped(w, body=body, scope=scope):
            with jax.named_scope(scope):
                return body(w).values
        assert scope in jax.jit(scoped).lower(wire).compile().as_text()


# (d) ----------------------------------------------------------------------

def test_reader_reports_nothing_where_there_is_nothing(monkeypatch):
    from transmogrifai_tpu import telemetry
    monkeypatch.setattr(telemetry, "REGISTRY", telemetry.MetricsRegistry())
    mod = reader("map_profile_s")
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
        {"prefetch.maps": {"count": 1, "total_s": 0.75, "self_s": 0.75,
                           "jit_s": 0.0}})
    ctx = {"trains": [{}], "trace": {"busy_s": 1.0, "window_s": 2.0}}
    assert reader("map_profile_s").read(ctx) == 0.75
    assert reader("map_profile_s").read(dict(ctx, trace=None)) is None
    reg.gauge("train.span_profile").set(traced["profile"])
    assert reader("map_profile_s").read(ctx) == \
        traced["profile"]["prefetch.maps"]["total_s"] >= 0.0


def test_reader_and_appended_cell_say_what_benchmark_json_says():
    entries = {m["name"]: m for m in MANIFEST["per_layer"]}
    mod, m = reader("map_profile_s"), entries["map_profile_s"]
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
    assert [c["name"] for c in MANIFEST["configs"]][-1] == "yelp_review_maps"


# (e) ----------------------------------------------------------------------

def test_configuration_keeps_every_default_and_states_its_cuts():
    from transmogrifai_tpu.ops.map_vectorizers import TextMapPivotVectorizer
    from transmogrifai_tpu.ops.transmogrify import TransmogrifierDefaults as T
    cfg = run.load_json("benchmark", "configs", "yelp_review_maps.json")
    typed = run.load_json("benchmark", "configs", "nyc_taxi_typed.json")
    entry = run.by_name(MANIFEST["configs"], "yelp_review_maps", "config")
    assert entry["file"] == "benchmark/configs/yelp_review_maps.json"
    assert entry["reduced"] == cfg["reduced"] == ["rows", "model_types",
                                                  "columns"]
    assert set(cfg["reduced_note"]) == set(cfg["reduced"])
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    for said in ("Yelp", "6,990,280", "150,346", "1,987,897", ">= 4"):
        assert said in cfg["source"], said
    assert cfg["source_rows"] == 6990280
    assert 1048576 <= cfg["rows"] <= 1835008 and cfg["rows"] % 262144 == 0
    assert cfg["model_types"] == list(cfg["selector"]) == [
        "OpLogisticRegression", "OpLinearSVC"]
    for key in ("raw_feature_filter", "folds", "fold_seed", "selector",
                "validation_metric", "racing", "guarantees", "work",
                "source_model_types", "sanity_checker"):
        assert cfg[key] == typed[key], key
    t = cfg["transmogrify"]
    assert (t["top_k"], t["min_support"], t["track_nulls"], t["num_hashes"],
            t["max_categorical_cardinality"]) == (
        T.TOP_K, T.MIN_SUPPORT, T.TRACK_NULLS, T.DEFAULT_NUM_OF_FEATURES,
        T.MAX_CATEGORICAL_CARDINALITY)
    assert TextMapPivotVectorizer().get("max_keys") == 100
    assert cfg["precision"]["matrix_storage"] == \
        typed["precision"]["matrix_storage"]
    assert cfg["precision"]["control"] == typed["precision"]["control"]
    assert set(cfg["assumed"]) >= {"typing", "generator", "profiles",
                                   "label", "splitter"}
    P = importlib.import_module("benchmark.programs.yelp_review_maps")
    columns = (P.IDS + P.TEXTS + tuple(c for c, _ in P.CATEGORICALS)
               + P.REALS + P.INTEGRALS + P.BINARIES + P.DATES + P.MAPS
               + P.SETS + P.GEOS)
    assert set(cfg["schema"]) == {"label", "left_out"} | set(columns)
    assert len(columns) == 34
    g = cfg["generator"]
    assert (g["businesses"], g["users"]) == (150346, 1987897)
    assert len(g["attributes"]) == 39 and len(g["categories"]["named"]) == 50
    assert abs(sum(m["share"] for m in g["metros"]) - 1.0) < 1e-9
    wl = run.by_name(MANIFEST["workloads"], CELL, "workload")
    assert (wl["config"], wl["traffic"], wl["chips"]) == (
        "yelp_review_maps", "mixed_sweep", 1)
    assert len(wl["why"]) <= 200 and len(entry["why"]) <= 200
    limits = run.load_json("benchmark", "limits", CELL + ".json")
    assert set(limits) - {"why"} == set(TINY["limits"])
    assert all(limits[k] >= 0.0 for k in TINY["limits"])
    with open(os.path.join(ROOT, "benchmark", "fixtures",
                           "cpu_cells_maps_sweep.json")) as fh:
        assert list(json.load(fh)["cells"]) == [CELL]


def test_generator_draws_what_the_configuration_states(cell, data):
    """Same seed, same rows; one entry a row; every row's dict and list its
    own object; a business's fields repeated in each of its reviews."""
    again = cell.program.make_data(cell.rows, SEED, cell.config)
    for k in data:
        if data[k].dtype == object:
            assert list(data[k]) == list(again[k]), k
        else:
            assert np.array_equal(data[k], again[k]), k
    other = cell.program.make_data(cell.rows, SEED + 1, cell.config)
    assert not np.array_equal(data["label"], other["label"])
    assert all(len(v) == cell.rows for v in data.values())
    assert 0.6 < data["label"].mean() < 0.72
    for name in ("attributes", "hours", "categories", "elite"):
        held = [v for v in data[name] if v is not None]
        assert len({id(v) for v in held}) == len(held), name
    first = {}
    for b, a, c, s in zip(data["business_id"], data["attributes"],
                          data["categories"], data["business_stars"]):
        assert first.setdefault(b, (a, c, s)) == (a, c, s)
    assert all(len(b) == 22 for b in data["business_id"][:100])
    attrs = [m for m in data["attributes"] if m is not None]
    assert 0.85 < len(attrs) / cell.rows < 0.95
    assert any(v.startswith("{'garage'") for m in attrs
               for v in m.values())
    assert set().union(*(m for m in data["hours"] if m)) <= set(
        cell.program.DAYS)
    assert 0.05 < np.mean([bool(e) for e in data["elite"]]) < 0.15
    assert all(len(c) == len(set(c)) for c in data["categories"] if c)
    assert set(np.unique(data["business_stars"])) <= set(
        np.arange(1.0, 5.5, 0.5))


def test_a_program_without_the_staged_forms_is_refused_at_once(
        cell, data, monkeypatch):
    """The parent commit laid under this cell's files has to fail soon and
    cleanly (it would walk every row's dict in Python, minutes a train):
    ``build`` leaves with an exit code before anything is trained."""
    from transmogrifai_tpu.ops.map_vectorizers import \
        TextMapPivotVectorizerModel
    monkeypatch.setattr(TextMapPivotVectorizerModel, "supports_staging",
                        False)
    with pytest.raises(SystemExit,
                       match="staged TextMapPivotVectorizerModel"):
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
                      "prologue_idle_s", "map_profile_s", "stage_wires_s",
                      "rff_s"}


def test_selftest_has_five_cells():
    assert sorted(run.cpu_cells()) == [CELL, "mixed_sweep", "mixed_sweep_x4",
                                       "text_sweep", "typed_sweep"]
    assert {w["name"] for w in MANIFEST["workloads"]} == set(run.cpu_cells())

"""``criteo_mixed_x4`` / ``mixed_sweep_x4``: the Criteo sweep with its rows
partitioned over four devices, at a size a CPU can hold (12,288 rows, the
suite's forced host devices capped to four).

(a) the program on a mesh of four against the configuration's own plain
reference, every number under the CPU limits of the cell's fixture; (b) the
same train on one device and on four gives the same answers within those
limits: the shards add up to the whole; (c) under a mesh the sweep keeps the
matrix in the dtype it is stored in, and ``mesh.devices``, ``selector.place``
and ``mesh.relayout_bytes`` read as expected; (d) the cell's readers report
nothing where there is nothing to read; (e) a shard left out of a reduction
makes ``correct`` false; (f) the reference with its blocks on several devices
answers as ``criteo_mixed``'s on one; (g) the configuration's file keeps every
shape of ``criteo_mixed`` and states its partition.
"""

import importlib
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import run
from benchmark.reference import common, plain

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "mixed_sweep_x4"
SEED = 2 ** 31 + 21
MANIFEST = run.load_json("BENCHMARK.json")
TINY = run.cpu_cells()[CELL]
SHARDS = 4


def reader(name):
    return importlib.import_module("benchmark.layer_metrics." + name)


def four_devices(mp):
    """The deployment at the suite's size: four of the forced host devices
    visible to the mesh policy, and rows enough at 12,288."""
    from transmogrifai_tpu.parallel import supervisor
    mp.setattr(supervisor, "_DEVICE_CAP", SHARDS)
    mp.setenv("TRANSMOGRIFAI_TPU_MESH_MIN_ROWS", "1024")


def traced_train(cell, data):
    from transmogrifai_tpu.telemetry import REGISTRY, Tracer
    tracer = Tracer("x4")
    before = REGISTRY.counters().get("mesh.relayout_bytes")
    rec = run.one_train(cell, data, "cpu", tracer)
    assert not rec["why_failed"], rec["why_failed"]
    rec["place"] = [s for s in tracer.spans if s.name == "selector.place"]
    rec["mesh_devices"] = REGISTRY.gauge("mesh.devices").value
    rec["relayout"] = (before, REGISTRY.counters().get("mesh.relayout_bytes"))
    rec["profile"] = REGISTRY.gauge("train.span_profile").value
    return rec


@pytest.fixture(scope="module")
def cell():
    return run.Cell(MANIFEST, CELL, TINY["rows"], TINY["limits"])


@pytest.fixture(scope="module")
def data(cell):
    return cell.program.make_data(cell.rows, SEED, cell.config)


@pytest.fixture(scope="module")
def on_four(cell, data):
    with pytest.MonkeyPatch.context() as mp:
        four_devices(mp)
        return traced_train(cell, data)


@pytest.fixture(scope="module")
def on_one(cell, data):
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TRANSMOGRIFAI_TPU_MESH", "0")
        return traced_train(cell, data)


@pytest.fixture(scope="module")
def ref(cell, data, on_four):
    ask = cell.reference.question(on_four["produced"])
    return cell.reference.reference(
        data, cell.config, plain.Precision.stated("cpu"), ask, seed=SEED)


def over(compared):
    return sorted(k for k, c in compared.items() if c["value"] > c["limit"])


# (a) ----------------------------------------------------------------------

def test_program_on_four_devices_agrees_with_its_reference(cell, on_four,
                                                           ref):
    ok, compared = run.verdict(cell, [on_four["produced"]], ref)
    assert ok, compared
    assert set(compared) == set(TINY["limits"])


def test_control_fails_the_cells_limits(cell, data, on_four, ref):
    p = on_four["produced"]
    low = cell.reference.reference(
        data, cell.config, plain.Precision.control("cpu"),
        cell.reference.question(p), seed=SEED)
    ok, control = run.verdict(
        cell, [common.as_produced(low, p, cell.config)], ref)
    assert not ok and over(control), control


# (b) ----------------------------------------------------------------------

def test_one_device_and_four_give_the_same_answers(cell, on_one, on_four):
    one, four = on_one["produced"], on_four["produced"]
    assert on_one["mesh_devices"] == 1 and on_four["mesh_devices"] == SHARDS
    assert np.array_equal(one["kept"], four["kept"])
    assert one["rff_dropped"] == four["rff_dropped"]
    assert one["winner"]["params"] == four["winner"]["params"]
    assert [(r["params"], r["raced_out"]) for r in one["cv"]] == [
        (r["params"], r["raced_out"]) for r in four["cv"]]
    # the four-device train in the reference's place: every gap between the
    # two trains is held to the limit the reference holds either to
    stand_in = dict(one, cv=[dict(r, per_fold=[r["metric"]])
                             for r in one["cv"]],
                    train_auroc=one["train_auroc"])
    gaps = common.compare(four, stand_in, cell.config)
    for k, v in gaps.items():
        assert v <= TINY["limits"][k], (k, v)


# (c) ----------------------------------------------------------------------

def test_place_span_gauge_and_counter(on_one, on_four):
    (place,) = on_four["place"]
    assert place.attrs["rows"] == TINY["rows"]
    assert place.attrs["pad_rows"] == 0
    assert place.attrs["devices"] == SHARDS
    assert place.attrs["dtype"] == "float32"      # a CPU backend's storage
    assert place.attrs["bytes_placed"] > TINY["rows"] * 8000 * 4
    # the fused transform's output is sharded as the sweep wants it
    assert place.attrs["relayout_bytes"] == 0
    before, after = on_four["relayout"]
    assert after is not None and after == (before or 0)
    assert on_four["profile"]["selector.place"]["count"] == 1
    (alone,) = on_one["place"]
    assert alone.attrs["devices"] == 1 and alone.attrs["pad_rows"] == 0


def test_mesh_keeps_the_stored_dtype(monkeypatch):
    """A bfloat16 matrix, as an accelerator stores it, stays bfloat16 on the
    mesh: no float32 copy is made, nothing is cast or re-laid."""
    from transmogrifai_tpu import columns
    four_devices(monkeypatch)
    monkeypatch.setattr(columns, "feature_matrix_dtype",
                        lambda n_elems: jnp.bfloat16)
    small = run.Cell(MANIFEST, CELL, 4096, TINY["limits"])
    small.config = dict(small.config, selector={"OpLogisticRegression": dict(
        small.config["selector"]["OpLogisticRegression"], max_iter=3)})
    rec = traced_train(small, small.program.make_data(4096, SEED,
                                                      small.config))
    (place,) = rec["place"]
    assert place.attrs["dtype"] == "bfloat16"
    assert place.attrs["devices"] == SHARDS
    assert place.attrs["relayout_bytes"] == 0
    assert rec["relayout"][1] == (rec["relayout"][0] or 0)
    kept = len(rec["produced"]["kept"])
    # matrix + label + [folds, rows] weights + the folds' masks: a float32
    # copy of the matrix would be twice the first term
    assert place.attrs["bytes_placed"] <= 4096 * kept * 2 + 4096 * 4 * 8


def test_a_cast_or_a_new_layout_is_counted(monkeypatch):
    """What the sweep has to move on the devices is counted: a matrix that
    arrives on one device is re-laid whole."""
    from transmogrifai_tpu.telemetry import REGISTRY
    from transmogrifai_tpu.models.linear import OpLogisticRegression
    from transmogrifai_tpu.selector import ModelCandidate, grid
    from transmogrifai_tpu.tuning import OpCrossValidation
    from transmogrifai_tpu.evaluators import Evaluators
    from transmogrifai_tpu import types as T
    from transmogrifai_tpu.columns import Column, ColumnBatch
    four_devices(monkeypatch)
    rng = np.random.default_rng(0)
    n, d = 2048, 8
    X = jax.device_put(jnp.asarray(rng.normal(size=(n, d)), jnp.bfloat16),
                       jax.devices()[0])
    y = (rng.random(n) < 0.5).astype(np.float32)
    batch = ColumnBatch({"y": Column(T.RealNN, y),
                         "x": Column(T.OPVector, X)}, n)
    before = REGISTRY.counters().get("mesh.relayout_bytes", 0)
    cv = OpCrossValidation(num_folds=2, seed=1,
                           evaluator=Evaluators.BinaryClassification.auPR())
    result = cv.validate(
        [ModelCandidate(OpLogisticRegression(),
                        grid(reg_param=[0.1], max_iter=[2]), "LR")],
        batch, "y", "x")
    assert result.placement.mesh is not None
    moved = REGISTRY.counters()["mesh.relayout_bytes"] - before
    assert moved == n * d * 2          # bfloat16 still: re-laid, not cast


# (d) ----------------------------------------------------------------------

@pytest.mark.parametrize("name", ["mesh_devices", "place_s", "relayout_MB",
                                  "sweep_mfu_x4", "chip_rows_per_s"])
def test_readers_report_nothing_where_there_is_nothing(name, monkeypatch):
    from transmogrifai_tpu import telemetry
    monkeypatch.setattr(telemetry, "REGISTRY", telemetry.MetricsRegistry())
    value = reader(name).read({"trains": [], "trace": None, "work": None,
                               "peaks": None, "train_wall_s": None,
                               "setup": {}, "memory_peak_bytes": None})
    assert value is None
    # a traced run of a program that has neither the span nor the counter
    value = reader(name).read({"trains": [{"link_bytes": 0}],
                               "trace": {"busy_s": 1.0, "window_s": 2.0},
                               "work": None, "peaks": None,
                               "train_wall_s": None, "setup": {},
                               "memory_peak_bytes": None})
    assert value is None


def test_readers_read_what_the_program_set(monkeypatch):
    from transmogrifai_tpu import telemetry
    reg = telemetry.MetricsRegistry()
    monkeypatch.setattr(telemetry, "REGISTRY", reg)
    reg.gauge("mesh.devices").set(4)
    reg.counter("mesh.relayout_bytes").inc(0)
    reg.gauge("train.span_profile").set(
        {"selector.place": {"count": 1, "total_s": 0.25, "self_s": 0.25,
                            "jit_s": 0.0}})
    ctx = {"trains": [{}, {}], "trace": {"busy_s": 1.0, "window_s": 2.0},
           "work": {"ops": 4 * 1.97e14, "bytes": 4 * 8.19e11 * 2.0},
           "peaks": run.load_json("benchmark", "peaks.json")["TPU v5 lite"],
           "train_wall_s": 8.0}
    assert reader("mesh_devices").read(ctx) == 4.0
    assert reader("place_s").read(ctx) == 0.25
    assert reader("relayout_MB").read(ctx) == 0.0      # 0 is a reading
    reg.counter("mesh.relayout_bytes").inc(6e6)
    assert reader("relayout_MB").read(ctx) == pytest.approx(2.0)
    # four chips' peaks: 1 s for the operations, 2 s for the bytes, of 8 s
    assert reader("sweep_mfu_x4").read(ctx) == pytest.approx(25.0)
    assert reader("sweep_mfu").read(ctx) == pytest.approx(100.0)
    assert reader("chip_rows_per_s").read(ctx) == pytest.approx(
        786432 / 8.0 / 4)
    with pytest.raises(RuntimeError):
        reader("sweep_mfu_x4").read(dict(ctx, train_wall_s=1.0))


def test_readers_say_what_benchmark_json_says():
    entries = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name in ("mesh_devices", "place_s", "relayout_MB", "sweep_mfu_x4",
                 "chip_rows_per_s"):
        mod, m = reader(name), entries[name]
        assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
            m["layer"], m["unit"], m["source"], m["moves"])
        assert m["workloads"] == [CELL]


# (e) ----------------------------------------------------------------------

def test_a_shard_left_out_of_a_reduction_is_not_correct(cell, data, ref,
                                                        monkeypatch):
    """SanityChecker's column statistics summed over three of the four
    shards: the train ends, nothing is logged, and ``correct`` is false."""
    from transmogrifai_tpu.preparators import sanity_checker
    four_devices(monkeypatch)
    whole = sanity_checker._col_stats_with_contingency

    def three_shards(Xs, ys, *a, **k):
        n = Xs.shape[0] // SHARDS * (SHARDS - 1)
        return whole(Xs[:n], ys[:n], *a, **k)
    monkeypatch.setattr(sanity_checker, "_col_stats_with_contingency",
                        three_shards)
    rec = run.one_train(cell, data, "cpu")
    assert not rec["why_failed"], rec["why_failed"]
    ok, compared = run.verdict(cell, [rec["produced"]], ref)
    assert not ok and "stats_gap" in over(compared), compared


# (f) ----------------------------------------------------------------------

def test_reference_on_several_devices_answers_as_on_one(cell, data, ref,
                                                        on_four,
                                                        monkeypatch):
    """Blocks of 8,192 rows put the table's two blocks on two devices; the
    partial sums pulled from each add up to ``criteo_mixed``'s answers, whose
    blocks live on one."""
    one = importlib.import_module("benchmark.reference.criteo_mixed")
    ask = cell.reference.question(on_four["produced"])
    stated = plain.Precision.stated("cpu")
    monkeypatch.setattr(plain, "BLOCK_ELEMS", 1 << 20)
    M = cell.reference.feature_matrix(data, cell.config, stated,
                                      cell.reference.devices_for(cell.config))
    assert len({next(iter(b.devices())) for b in M.blocks}) == 2
    split = cell.reference.reference(data, cell.config, stated, ask,
                                     seed=SEED)
    monkeypatch.undo()
    whole = one.reference(data, cell.config, stated, ask, seed=SEED)
    assert np.array_equal(split["kept"], whole["kept"])
    assert np.array_equal(split["kept"], ref["kept"])
    # the split reference in the program's place, against the whole one: far
    # inside the limits the program is held to
    p = on_four["produced"]
    for other in (whole, ref):
        gaps = common.compare(common.as_produced(split, p, cell.config),
                              other, cell.config)
        assert gaps["stats_gap"] <= 2e-5, gaps
        assert gaps["refit_coef_gap"] <= 1e-4, gaps
        assert gaps["train_auroc_gap"] <= 1e-6, gaps
        for k, v in gaps.items():
            assert v <= TINY["limits"][k], (k, v)


# (g) ----------------------------------------------------------------------

def test_configuration_keeps_every_shape_and_states_its_partition():
    x4 = run.load_json("benchmark", "configs", "criteo_mixed_x4.json")
    one = run.load_json("benchmark", "configs", "criteo_mixed.json")
    entry = run.by_name(MANIFEST["configs"], "criteo_mixed_x4", "config")
    assert entry["file"] == "benchmark/configs/criteo_mixed_x4.json"
    assert entry["reduced"] == x4["reduced"] == ["rows", "model_types"]
    assert entry["source"] == x4["source"] and len(x4["source"]) <= 200
    assert "train.txt" in x4["source"] and "45,840,617" in x4["source"]
    assert x4["source"] != one["source"]
    for key in ("source_rows", "model_types", "source_model_types", "schema",
                "click_share", "cardinalities", "generator", "transmogrify",
                "raw_feature_filter", "folds", "fold_seed",
                "validation_metric", "sanity_checker", "selector", "racing",
                "precision", "work"):
        assert x4[key] == one[key], key
    parts = x4["partitions"]
    assert x4["rows"] == 786432 == parts["row_shards"] * parts["rows_a_shard"]
    assert parts["rows_a_shard"] == one["rows"]
    assert set(x4["guarantees"]) >= {"answers", "partition_invariance",
                                     "precision", "failed"}
    wl = run.by_name(MANIFEST["workloads"], CELL, "workload")
    assert (wl["config"], wl["traffic"], wl["chips"]) == (
        "criteo_mixed_x4", "mixed_sweep", 4)
    assert len(wl["why"]) <= 200
    limits = run.load_json("benchmark", "limits", CELL + ".json")
    assert set(limits) - {"why"} == set(TINY["limits"])
    assert all(limits[k] >= 0.0 for k in TINY["limits"])
    with open(os.path.join(ROOT, "benchmark", "fixtures",
                           "cpu_cells_mixed_sweep_x4.json")) as fh:
        assert list(json.load(fh)["cells"]) == [CELL]


def test_mesh_policy_takes_the_deployment_unasked(monkeypatch):
    """786,432 rows on four devices: the 'data'-axis mesh with no
    environment variable; the same rows a chip on one device: none."""
    from transmogrifai_tpu.parallel import maybe_data_mesh, supervisor
    for var in ("TRANSMOGRIFAI_TPU_MESH", "TRANSMOGRIFAI_TPU_MESH_MIN_ROWS",
                "TRANSMOGRIFAI_TPU_MESH_MODEL"):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setattr(supervisor, "_DEVICE_CAP", SHARDS)
    mesh = maybe_data_mesh(786432)
    assert mesh is not None and dict(mesh.shape) == {"data": 4, "model": 1}
    assert maybe_data_mesh(196608) is None
    monkeypatch.setattr(supervisor, "_DEVICE_CAP", 1)
    assert maybe_data_mesh(786432) is None

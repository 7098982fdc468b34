"""Compiled score path: the fitted DAG's device-resident middle runs as ONE
jitted XLA program (transmogrifai_tpu/compiled.py), equivalent to the eager
apply_dag and robust to untraceable stages (automatic demotion)."""

import os

import numpy as np
import pytest

from transmogrifai_tpu import types as T
from transmogrifai_tpu.columns import Column, ColumnBatch
from transmogrifai_tpu.compiled import ScoreProgram
from transmogrifai_tpu.dag import apply_dag
from transmogrifai_tpu.features import FeatureBuilder
from transmogrifai_tpu.ops.transmogrify import transmogrify
from transmogrifai_tpu.selector import (BinaryClassificationModelSelector,
                                        ModelCandidate, grid)
from transmogrifai_tpu.models.linear import OpLogisticRegression
from transmogrifai_tpu.stages.base import LambdaTransformer
from transmogrifai_tpu.workflow import Workflow


def _make_model(n=400, d=5, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = (X[:, 0] - 0.5 * X[:, 1] > 0).astype(np.float32)
    records = [{"y": float(y[i]),
                **{f"x{j}": float(X[i, j]) for j in range(d)},
                "cat": ("a" if X[i, 2] > 0 else "b")}
               for i in range(n)]
    label = FeatureBuilder.RealNN("y").as_response()
    preds = [FeatureBuilder.Real(f"x{j}").as_predictor() for j in range(d)]
    preds.append(FeatureBuilder.PickList("cat").as_predictor())
    fv = transmogrify(preds)
    checked = label.sanity_check(fv, remove_bad_features=True)
    sel = BinaryClassificationModelSelector(models=[
        ModelCandidate(OpLogisticRegression(), grid(reg_param=[0.01]),
                       "OpLogisticRegression")])
    sel.set_input(label, checked)
    pred = sel.get_output()
    wf = Workflow().set_input_records(records).set_result_features(pred)
    return wf.train(), pred


@pytest.fixture(scope="module")
def model_and_pred():
    return _make_model()


def test_device_run_engages(model_and_pred):
    """The partition must place the vector-combine → sanity-slice → model
    chain (at minimum) inside ONE jitted device segment."""
    model, _ = model_and_pred
    prog = model.score_program()
    batch = model.generate_raw_data()
    segments = prog._partition(batch)
    dev_segs = [[s.operation_name for s in seg] for is_dev, seg in segments
                if is_dev]
    assert any({"VectorsCombiner", "SanityCheckerModel",
                "SelectedModel"} <= set(names) for names in dev_segs), dev_segs
    # the numeric vectorizer (device op over raw numeric columns) also
    # compiles, in its own earlier segment or the same one
    all_dev = {n for names in dev_segs for n in names}
    assert any("RealVectorizer" in n or "Vectorizer" in n for n in all_dev)


def test_compiled_matches_eager(model_and_pred):
    model, pred = model_and_pred
    batch = model.generate_raw_data()
    eager = apply_dag(batch, model.fitted_dag)
    compiled = model.score_program()(batch, keep_intermediate=True)
    p1 = np.asarray(eager[pred.name].values["prediction"])
    p2 = np.asarray(compiled[pred.name].values["prediction"])
    np.testing.assert_allclose(p1, p2, atol=1e-6)
    pr1 = np.asarray(eager[pred.name].values["probability"])
    pr2 = np.asarray(compiled[pred.name].values["probability"])
    np.testing.assert_allclose(pr1, pr2, atol=1e-6)


def test_score_varying_batch_sizes(model_and_pred):
    """jit retraces per shape; results must stay correct across sizes."""
    model, pred = model_and_pred
    full = model.generate_raw_data()
    for n in (full and [len(full), 7, 1]):
        sub = full.take_rows(np.arange(n))
        scored = model.score(batch=sub)
        assert len(scored[pred.name].values["prediction"]) == n


def test_untraceable_stage_demoted(model_and_pred):
    """A stage flagged device but actually host-bound (np.asarray on a tracer
    raises) must be demoted to the host segments, not break scoring."""
    model, pred = model_and_pred

    seen = []

    def hostile(col):
        arr = np.asarray(col.values)  # raises TracerArrayConversionError in jit
        seen.append(len(arr))
        return Column(T.RealNN, arr * 2.0)

    # consume the sanity-checked vector (produced inside the device run) so
    # the hostile stage lands in the traced segment
    checked_f = model.selected_model.input_features[1]
    lam = LambdaTransformer(hostile, T.RealNN, name="HostileOp")
    lam.set_input(checked_f)
    out_f = lam.get_output()

    prog = ScoreProgram(list(model.fitted_dag) + [[lam]],
                        [out_f.name] + [f.name for f in model.result_features])
    batch = model.generate_raw_data()
    scored = prog(batch, keep_intermediate=True)
    assert lam.uid in prog._demoted
    # demoted stage still executed on host and the model still scored
    assert out_f.name in scored
    eager = apply_dag(batch, model.fitted_dag)
    np.testing.assert_allclose(
        np.asarray(scored[pred.name].values["prediction"]),
        np.asarray(eager[pred.name].values["prediction"]), atol=1e-6)


def test_evaluate_error_messages(model_and_pred):
    model, _ = model_and_pred
    from transmogrifai_tpu.evaluators import Evaluators
    ev = Evaluators.BinaryClassification.auROC()
    # response column stripped from scoring data → actionable error
    batch = model.generate_raw_data()
    no_label = batch.drop(["y"])
    with pytest.raises(ValueError, match="response column 'y'"):
        model.evaluate(ev, batch=no_label)


# -- the process-wide table of compiled executables (ISSUE 26) ----------------

def _shared_counters():
    from transmogrifai_tpu.telemetry import REGISTRY
    c = REGISTRY.snapshot()["counters"]
    return {k: c.get("compiled.shared." + k, 0)
            for k in ("hit", "miss", "evict", "bypass")}


def _moved(before):
    return {k: v - before[k] for k, v in _shared_counters().items()}


@pytest.fixture()
def empty_table(monkeypatch):
    """The process-wide table as a fresh process has it."""
    from transmogrifai_tpu import compiled
    table = compiled._SharedExecutables(capacity=64)
    monkeypatch.setattr(compiled, "SHARED_EXECUTABLES", table)
    return table


def _affine(scale, offset):
    """A one-stage fused program ``x * scale + offset`` over a fresh stage
    (fresh uid): ``scale`` and ``offset`` are what a fit would leave."""
    x = FeatureBuilder.Real("x").as_predictor()
    lam = LambdaTransformer(
        lambda c: Column(T.Real, c.values * scale + offset, c.mask),
        T.Real, name="Affine")
    lam.set_input(x)
    out = lam.get_output()
    return ScoreProgram([[lam]], [out.name]), out.name


def _x_batch(n=16):
    return ColumnBatch({"x": Column(T.Real, np.arange(n, dtype=np.float32),
                                    np.ones(n, dtype=bool))}, n)


def test_second_workflow_dispatches_the_first_ones_executables(
        empty_table, monkeypatch):
    """Two trains of one process over the same data: equal fitted content,
    different uids.  The second program's first call traces and lowers, and
    compiles nothing."""
    from transmogrifai_tpu import compiled
    from transmogrifai_tpu.profiling import compile_stats
    (m1, p1), (m2, p2) = _make_model(), _make_model()
    assert p1.name != p2.name
    ScoreProgram(m1.fitted_dag, [p1.name])(m1.generate_raw_data(),
                                           keep_intermediate=True)
    before, compiles = _shared_counters(), compile_stats()["backend_compiles"]
    traces = compiled.trace_count()
    prog2 = ScoreProgram(m2.fitted_dag, [p2.name])
    out2 = prog2(m2.generate_raw_data(), keep_intermediate=True)
    segments = len(prog2._jitted)
    assert segments >= 1
    assert compile_stats()["backend_compiles"] == compiles
    assert _moved(before) == {"hit": segments, "miss": 0, "evict": 0,
                              "bypass": 0}
    assert compiled.trace_count() == traces + segments   # still traced
    # the metadata is the SECOND workflow's: names carry its uids
    produced = {f.name for st in prog2.stages for f in st.output_features}
    vectors = [n for n in produced if out2[n].meta is not None]
    assert vectors and all(out2[n].meta.name == n for n in vectors)
    assert not produced & {f.name for l in m1.fitted_dag for st in l
                           for f in st.output_features}
    # bit-equal to what the same program computes with nothing to share
    monkeypatch.setattr(compiled, "SHARED_EXECUTABLES",
                        compiled._SharedExecutables(capacity=64))
    before = _shared_counters()
    alone = ScoreProgram(m2.fitted_dag, [p2.name])(
        m2.generate_raw_data(), keep_intermediate=True)
    assert _moved(before)["hit"] == 0 and _moved(before)["miss"] == segments
    for n in produced:
        a, b = out2[n].values, alone[n].values
        for k in (a if isinstance(a, dict) else [None]):
            np.testing.assert_array_equal(
                np.asarray(a[k] if k else a), np.asarray(b[k] if k else b))


@pytest.mark.parametrize("other", ["scalar", "array"])
def test_a_different_fitted_value_is_a_different_program(empty_table, other):
    offset = np.linspace(0.0, 1.0, 16).astype(np.float32)
    scale2, offset2 = ((3.0, offset) if other == "scalar"
                       else (2.0, offset + np.float32(1e-3) * (offset > 0.5)))
    batch, x = _x_batch(), np.arange(16, dtype=np.float32)
    prog_a, out_a = _affine(2.0, offset)
    got_a = np.asarray(prog_a(batch)[out_a].values)
    before = _shared_counters()
    prog_b, out_b = _affine(scale2, offset2)
    got_b = np.asarray(prog_b(batch)[out_b].values)
    assert _moved(before) == {"hit": 0, "miss": 1, "evict": 0, "bypass": 0}
    np.testing.assert_array_equal(got_a, x * np.float32(2.0) + offset)
    np.testing.assert_array_equal(got_b, x * np.float32(scale2) + offset2)
    assert not np.array_equal(got_a, got_b)
    assert len(empty_table) == 2
    # and the same values again are the same program
    before = _shared_counters()
    prog_c, out_c = _affine(2.0, offset.copy())
    np.testing.assert_array_equal(np.asarray(prog_c(batch)[out_c].values),
                                  got_a)
    assert _moved(before) == {"hit": 1, "miss": 0, "evict": 0, "bypass": 0}


def test_table_is_bounded_and_evicts_the_least_recently_used():
    from transmogrifai_tpu.compiled import _SharedExecutables
    table, before = _SharedExecutables(capacity=2), _shared_counters()
    assert table.put("a", "exe-a") == "exe-a"
    assert table.put("b", "exe-b") == "exe-b"
    assert table.put("a", "late") == "exe-a"       # the first insert wins
    assert table.get("a") == "exe-a"               # ... and is now the newest
    table.put("c", "exe-c")
    assert len(table) == 2 and table.get("b") is None
    assert table.get("a") == "exe-a" and table.get("c") == "exe-c"
    assert _moved(before)["evict"] == 1


def test_threads_that_miss_together_share_one_entry(empty_table):
    """More threads than cores, one program identity: every thread gets the
    right answer and the table holds one executable."""
    import sys
    import threading
    offset = np.linspace(1.0, 2.0, 16).astype(np.float32)
    n = 2 * (os.cpu_count() or 4)
    progs = [_affine(1.5, offset) for _ in range(n)]
    gate, results, before = threading.Barrier(n), [None] * n, _shared_counters()

    def work(i):
        prog, out = progs[i]
        gate.wait(timeout=60)
        results[i] = np.asarray(prog(_x_batch())[out].values)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(n)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    want = np.arange(16, dtype=np.float32) * np.float32(1.5) + offset
    for r in results:
        np.testing.assert_array_equal(r, want)
    moved = _moved(before)
    assert len(empty_table) == 1
    assert moved["hit"] + moved["miss"] == n and moved["miss"] >= 1
    assert moved["bypass"] == 0 and moved["evict"] == 0


def test_table_entry_keeps_no_stage_alive(empty_table):
    import gc
    import weakref
    prog, out = _affine(0.5, np.ones(16, np.float32))
    prog(_x_batch())
    stage = weakref.ref(prog.stages[0])
    del prog
    gc.collect()
    assert len(empty_table) == 1 and stage() is None


def test_mesh_program_is_shared_by_its_devices(empty_table, monkeypatch):
    """Under a mesh the module carries the shardings and the identity the
    mesh's device ids: an equal program over the same devices hits."""
    import jax
    monkeypatch.setenv("TRANSMOGRIFAI_TPU_MESH", "1")
    offset = np.linspace(0.0, 1.0, 16).astype(np.float32)
    prog_a, out_a = _affine(2.0, offset)
    got_a = prog_a(_x_batch())[out_a].values
    assert len(got_a.sharding.device_set) == len(jax.devices()) > 1
    before = _shared_counters()
    prog_b, out_b = _affine(2.0, offset)
    got_b = prog_b(_x_batch())[out_b].values
    assert _moved(before) == {"hit": 1, "miss": 0, "evict": 0, "bypass": 0}
    np.testing.assert_array_equal(np.asarray(got_a), np.asarray(got_b))
    # the same program on one device is another executable
    monkeypatch.setenv("TRANSMOGRIFAI_TPU_MESH", "0")
    before = _shared_counters()
    prog_c, out_c = _affine(2.0, offset)
    np.testing.assert_array_equal(
        np.asarray(prog_c(_x_batch())[out_c].values), np.asarray(got_a))
    assert _moved(before)["miss"] == 1 and len(empty_table) == 2


_HOISTED_CHILD = r"""
import json, sys
sys.path.insert(0, sys.argv[1]); sys.path.insert(0, sys.argv[1] + "/tests")
import numpy as np
import jax.numpy as jnp
from test_compiled_score import _affine, _shared_counters, _x_batch
x = np.arange(16, dtype=np.float32)
got = []
for seed in (0, 1):
    offset = np.random.default_rng(seed).normal(size=16).astype(np.float32)
    prog, out = _affine(2.0, jnp.asarray(offset))
    got.append(bool(np.array_equal(np.asarray(prog(_x_batch())[out].values),
                                   x * np.float32(2.0) + offset)))
print(json.dumps({"own_values": got, **_shared_counters()}))
"""


def test_constants_hoisted_out_of_the_module_are_never_shared():
    """With ``jax_use_simplified_jaxpr_constants`` jax passes closed-over
    arrays as hidden call arguments: two programs with different fitted
    arrays then lower to ONE module, and the table must refuse both."""
    import json
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_USE_SIMPLIFIED_JAXPR_CONSTANTS="1",
               JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, "-c", _HOISTED_CHILD, repo],
                         env=env, capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr[-2000:]
    said = json.loads(res.stdout.strip().splitlines()[-1])
    assert said["own_values"] == [True, True]
    assert said["bypass"] == 2 and said["hit"] == 0 and said["miss"] == 0

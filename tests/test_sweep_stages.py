"""The sweep's stages on their own (``tuning.py``): the plan and the placement
without a sweep around them, the refit's arrays against the sweep's, and a
small sweep against values recorded from the commit before the stages
existed."""

import numpy as np
import pytest

import jax
from jax.sharding import PartitionSpec as P

from transmogrifai_tpu import types as T
from transmogrifai_tpu.checkpoint import SweepCheckpoint, use_sweep_checkpoint
from transmogrifai_tpu.columns import Column, ColumnBatch
from transmogrifai_tpu.evaluators import Evaluators
from transmogrifai_tpu.features import features_from_schema
from transmogrifai_tpu.models.linear import OpLinearSVC, OpLogisticRegression
from transmogrifai_tpu.selector import (BinaryClassificationModelSelector,
                                        ModelCandidate, grid)
from transmogrifai_tpu.sparse.matrix import SparseMatrix
from transmogrifai_tpu.telemetry import Tracer, use_tracer
from transmogrifai_tpu.tuning import (DataBalancer, OpCrossValidation,
                                      OpTrainValidationSplit, place,
                                      plan_sweep)

LR_GRID = grid(reg_param=[0.001, 0.01, 0.1, 0.2],
               elastic_net_param=[0.1, 0.5], max_iter=[20])   # races 8 -> 3
SVC_GRID = grid(reg_param=[0.01, 0.1], max_iter=[20])         # at the floor


def families():
    return [ModelCandidate(OpLogisticRegression(), LR_GRID, "LR"),
            ModelCandidate(OpLinearSVC(), SVC_GRID, "SVC")]


def small_table(n=300, d=6, seed=7):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = (X @ rng.normal(size=d) + rng.normal(size=n) > 0).astype(np.float32)
    return X, y


def small_batch(n=300, d=6, seed=7):
    X, y = small_table(n, d, seed)
    return ColumnBatch({"y": Column(T.RealNN, y),
                        "x": Column(T.OPVector, X)}, n)


def mesh_env(monkeypatch, on):
    monkeypatch.setenv("TRANSMOGRIFAI_TPU_MESH", "1" if on else "0")


# -- plan --------------------------------------------------------------------

@pytest.mark.parametrize("in_fold_dag", [None, [[object()]]],
                         ids=["shared", "in_fold_dag"])
@pytest.mark.parametrize("racing", [True, False], ids=["racing", "unraced"])
@pytest.mark.parametrize("kind", ["cv", "tvs"])
def test_plan(kind, racing, in_fold_dag, tmp_path):
    ev = Evaluators.BinaryClassification.auPR()
    v = (OpCrossValidation(num_folds=3, seed=5, evaluator=ev, racing=racing)
         if kind == "cv" else
         OpTrainValidationSplit(evaluator=ev, seed=5, racing=racing))
    y = small_table()[1].astype(np.float64)
    cp = SweepCheckpoint(str(tmp_path / "sweep"))
    with use_sweep_checkpoint(cp):
        plan = plan_sweep(v, families(), y, in_fold_dag, attempt=1,
                          oom_attempt=2)
    assert len(plan.splits) == (3 if kind == "cv" else 1)
    # only a CV over one shared matrix races, and only the grid over the floor
    raced = racing and kind == "cv" and not in_fold_dag
    assert plan.raced_flags == (raced, False)
    assert [plan.survivor_count(g) for g in (1, 2, 6, 8, 24)] == [
        2, 2, 2, 3, 8]
    assert (plan.attempt, plan.oom_attempt, plan.n_workers) == (1, 2, 2)
    assert plan.replayed == {}
    if in_fold_dag:
        # its metrics build up over fold groups: nothing to snapshot
        assert plan.checkpoint is None and plan.signatures == ()
        return
    assert plan.checkpoint is cp
    on = {"enabled": True, "eta": 3.0, "minSurvivors": 2}
    assert plan.signatures == (
        SweepCheckpoint.candidate_signature(
            "LR", 0, LR_GRID, racing=on if raced else {"enabled": False}),
        SweepCheckpoint.candidate_signature(
            "SVC", 1, SVC_GRID, racing={"enabled": False}))


def test_plan_replays_what_the_checkpoint_holds_and_fits_one_at_a_time(
        tmp_path, monkeypatch):
    from transmogrifai_tpu import tuning
    v = OpCrossValidation(num_folds=3, seed=5, racing=False,
                          evaluator=Evaluators.BinaryClassification.auPR())
    cands = families()
    sweep_dir = str(tmp_path / "sweep")
    with use_sweep_checkpoint(SweepCheckpoint(sweep_dir)):
        first = v.validate(cands, small_batch(), "y", "x")
    cands[0].estimator.hbm_heavy = True
    monkeypatch.setattr(tuning, "_SERIAL_FROM_ROWS", 300)
    with use_sweep_checkpoint(SweepCheckpoint(sweep_dir)):
        plan = plan_sweep(v, cands, small_table()[1].astype(np.float64))
    assert sorted(plan.replayed) == [0, 1] and plan.n_workers == 1
    assert [r["metricValues"] for r in plan.replayed[1]] == [
        r.metric_values for r in first.all_results if r.model_name == "SVC"]


# -- place -------------------------------------------------------------------

def spec(a):
    return a.sharding.spec if len(a.sharding.device_set) > 1 else None


@pytest.mark.parametrize("case", ["one_device", "mesh_divisible",
                                  "mesh_indivisible", "sparse", "balancer"])
def test_place(case, monkeypatch):
    n = 304 if case == "mesh_divisible" else 300
    mesh_env(monkeypatch, case != "one_device")
    X, y = small_table(n)
    splitter = y_all = None
    if case == "sparse":
        r, c = np.nonzero(np.random.default_rng(1).random((n, 40)) < 0.1)
        X = SparseMatrix.from_coo(r, c, np.ones(len(r), np.float32), n, 40)
    if case == "balancer":
        y = (np.arange(n) % 10 == 0).astype(np.float32)   # a tenth positive
        splitter, y_all = DataBalancer(sample_fraction=0.45), y.astype(float)
    cols = X.shape[1]
    splits = OpCrossValidation(num_folds=3, seed=2).splits(y.astype(float))
    tracer = Tracer("place")
    with use_tracer(tracer):
        p = place(X, y, splits, families(), splitter, y_all)
    pad = 0 if case in ("one_device", "mesh_divisible") else 4
    (sp,) = [s for s in tracer.spans if s.name == "selector.place"]
    assert (sp.attrs["rows"], sp.attrs["pad_rows"], sp.attrs["devices"]) == (
        n, pad, 1 if case == "one_device" else 8)
    assert sp.attrs["relayout_bytes"] == 0 and sp.attrs["bytes_placed"] > 0
    assert (p.N, p.N_fit, p.is_sparse) == (n, n + pad, case == "sparse")
    assert (p.mesh is None) == (case == "one_device")
    assert p.X.shape == (n + pad, cols) and p.X.dtype == np.float32
    assert p.y.shape == (n + pad,) and p.y.dtype == np.float32
    assert p.W.shape == (3, n + pad) and p.W.dtype == np.float32
    assert [m.shape for m in p.va_masks] == [(n + pad,)] * 3
    if case == "one_device":
        assert all(len(a.sharding.device_set) == 1
                   for a in (p.X, p.y, p.W, *p.va_masks))
    else:
        if case != "sparse":
            assert spec(p.X) == P("data", None)
        assert spec(p.y) == P("data") and spec(p.W) == P(None, "data")
        assert all(spec(m) == P("data") for m in p.va_masks)
    W, masks = np.asarray(p.W), np.asarray(jax.numpy.stack(p.va_masks))
    assert np.array_equal(np.asarray(p.y)[:n], y)
    # pad rows join no fold: they neither train nor validate
    assert not W[:, n:].any() and not masks[:, n:].any()
    for f, (tr, va) in enumerate(splits):
        assert np.array_equal(np.flatnonzero(masks[f]), np.sort(va))
        assert not W[f, va].any()
        if case != "balancer":
            assert np.array_equal(np.flatnonzero(W[f]), np.sort(tr))
    if case == "balancer":
        # the minority is sampled with replacement, the majority thinned
        assert W.max() > 1.0 and (W[0, splits[0][0]] == 0).any()
    # what the result carries to the refit holds the layout and no array
    d = p.descriptor()
    assert (d.N, d.N_fit, d.mesh) == (p.N, p.N_fit, p.mesh)
    assert d.X is None and d.y is None and d.W is None and not d.va_masks


# -- the refit's arrays against the sweep's -----------------------------------

def laid(a):
    return (tuple(a.shape), np.dtype(a.dtype),
            a.sharding if len(a.sharding.device_set) > 1 else None)


@pytest.mark.parametrize("on_mesh", [False, True], ids=["one_device", "mesh"])
@pytest.mark.parametrize("family", ["LR", "SVC"], ids=["raced", "unraced"])
def test_refit_arrays_are_laid_out_as_the_sweeps(family, on_mesh,
                                                 monkeypatch):
    """The last batched fit of a raced family ran its survivors on folds-1
    folds, an unraced one its whole grid on all of them; on the mesh 300 rows
    pad to 304.  The arrays the placement hands the refit match that call's
    in shape, dtype and sharding, so it lands on the same compiled program."""
    mesh_env(monkeypatch, on_mesh)
    cand = [c for c in families() if c.model_name == family]
    calls = []
    fit_grid = type(cand[0].estimator).fit_arrays_grid

    def spy(self, X, y, W, grids):
        calls.append((laid(X), laid(y), laid(W), len(grids)))
        return fit_grid(self, X, y, W, grids)

    monkeypatch.setattr(type(cand[0].estimator), "fit_arrays_grid", spy)
    batch = small_batch()
    cv = OpCrossValidation(num_folds=3, seed=11,
                           evaluator=Evaluators.BinaryClassification.auPR())
    result = cv.validate(cand, batch, "y", "x")
    meta = result.fit_meta[family]
    assert meta == ({"folds": 2, "lanes": 3} if family == "LR"
                    else {"folds": 3, "lanes": 2})
    assert (result.placement.N_fit, result.placement.mesh is not None) == (
        304 if on_mesh else 300, on_mesh)
    X, y, W = result.placement.refit_arrays(
        batch["x"].values, batch["y"].values, meta["folds"])
    want = calls[-1]
    assert (tuple(X.shape), np.dtype(X.dtype)) == want[0][:2]
    assert (tuple(y.shape), np.dtype(y.dtype)) == want[1][:2]
    assert laid(W)[:2] == want[2][:2] and meta["lanes"] == want[3]
    if on_mesh:
        assert (laid(X), laid(y), laid(W)) == want[:3]
        assert not np.asarray(W)[:, 300:].any()
    assert np.asarray(W)[:, :300].all()
    # other rows than the sweep's (a Balancer resampled them): no reuse
    assert result.placement.refit_arrays(
        batch["x"].values[:200], batch["y"].values[:200], 2) is None


def jit_compiles_under(tracer, name):
    """``fun_name`` of every jit.compile event below the spans ``name``."""
    ids = {s.span_id for s in tracer.spans if s.name == name}
    assert ids
    for s in tracer.spans:              # spans are listed parents first
        if s.parent_id in ids:
            ids.add(s.span_id)
    return [s.attrs["fun_name"] for s in tracer.spans
            if s.name == "jit.compile" and s.parent_id in ids]


@pytest.mark.parametrize("on_mesh", [False, True], ids=["one_device", "mesh"])
@pytest.mark.parametrize("racing", [True, False], ids=["raced", "unraced"])
def test_refit_after_a_sweep_compiles_no_fit_program(racing, on_mesh,
                                                     monkeypatch):
    mesh_env(monkeypatch, on_mesh)
    # rows no other test of the file fits, so the first sweep compiles
    n = 264 + 8 * racing + 16 * on_mesh + 3
    batch = small_batch(n, 5)
    label, (fx,) = features_from_schema({"y": T.RealNN, "x": T.OPVector},
                                        response="y")

    def traced_fit():
        sel = BinaryClassificationModelSelector(
            num_folds=3, seed=3,
            models=[ModelCandidate(OpLogisticRegression(), LR_GRID, "LR")])
        sel.validator.racing = racing
        sel.set_input(label, fx)
        tracer = Tracer("fit")
        with use_tracer(tracer):
            sel.fit(batch)
        return tracer

    first = traced_fit()
    swept = set(jit_compiles_under(first, "selector.candidate_fit"))
    assert swept
    # the first refit may build its weights (ones, pad, scatter); it compiles
    # nothing the sweep's fits compiled, and a second user's refit nothing
    assert not swept & set(jit_compiles_under(first, "selector.winner_refit"))
    assert jit_compiles_under(traced_fit(), "selector.winner_refit") == []


# -- identity with the commit before the stages -------------------------------

# all_results of the sweep below at bd890eb (PR 30's tree), in order:
# (family, params as (reg_param, elastic_net_param), raced_out, fold metrics)
RACED = [
    ("LR", (0.001, 0.1), True, [0.9525583386421204]),
    ("LR", (0.001, 0.5), True, [0.9519069790840149]),
    ("LR", (0.01, 0.1), True, [0.9540584087371826]),
    ("LR", (0.01, 0.5), False,
     [0.9540878534317017, 0.9693188071250916, 0.9148779511451721]),
    ("LR", (0.1, 0.1), False,
     [0.9559355974197388, 0.9669144153594971, 0.894141435623169]),
    ("LR", (0.1, 0.5), True, [0.9473097920417786]),
    ("LR", (0.2, 0.1), False,
     [0.9556190371513367, 0.9645129442214966, 0.8897664546966553]),
    ("LR", (0.2, 0.5), True, [0.9273250699043274]),
    ("SVC", (0.01, None), False,
     [0.9524954557418823, 0.9695048332214355, 0.9163276553153992]),
    ("SVC", (0.1, None), False,
     [0.9551773071289062, 0.968299388885498, 0.9155994653701782]),
]
UNRACED = [
    ("LR", (0.001, 0.1), False,
     [0.9525583386421204, 0.9690623879432678, 0.9153469204902649]),
    ("LR", (0.001, 0.5), False,
     [0.9519069790840149, 0.9699349999427795, 0.9153469204902649]),
    ("LR", (0.01, 0.1), False,
     [0.9540584087371826, 0.9692901372909546, 0.9141511917114258]),
    ("LR", (0.01, 0.5), False,
     [0.9540878534317017, 0.9693188071250916, 0.9148779511451721]),
    ("LR", (0.1, 0.1), False,
     [0.9559355974197388, 0.9669144153594971, 0.894141435623169]),
    ("LR", (0.1, 0.5), False,
     [0.9473097920417786, 0.9518564343452454, 0.8875579237937927]),
    ("LR", (0.2, 0.1), False,
     [0.9556190371513367, 0.9645129442214966, 0.8897664546966553]),
    ("LR", (0.2, 0.5), False,
     [0.9273250699043274, 0.9293991327285767, 0.8860583305358887]),
    ("SVC", (0.01, None), False,
     [0.9524954557418823, 0.9695048332214355, 0.9163276553153992]),
    ("SVC", (0.1, None), False,
     [0.9551773071289062, 0.968299388885498, 0.9155994653701782]),
]
WINNER = ("SVC", {"reg_param": 0.1, "max_iter": 20}, 0.9463587204615275)


@pytest.mark.parametrize("racing", [True, False], ids=["raced", "unraced"])
def test_small_sweep_equals_the_parents(racing, monkeypatch):
    mesh_env(monkeypatch, False)
    cv = OpCrossValidation(num_folds=3, seed=11, racing=racing,
                           evaluator=Evaluators.BinaryClassification.auPR())
    result = cv.validate(families(), small_batch(), "y", "x")
    got = [(r.model_name,
            (r.params["reg_param"], r.params.get("elastic_net_param")),
            r.raced_out, r.metric_values) for r in result.all_results]
    want = RACED if racing else UNRACED
    assert [g[:3] for g in got] == [w[:3] for w in want]
    for g, w in zip(got, want):
        assert g[3] == pytest.approx(w[3], rel=1e-6), g[:2]
    assert [r.candidate_index for r in result.all_results] == [0] * 8 + [1] * 2
    assert (result.best.model_name, result.best_params) == WINNER[:2]
    assert result.best_metric == pytest.approx(WINNER[2], rel=1e-6)

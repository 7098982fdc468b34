"""Tests for the sweep racing engine (successive halving, ISSUE 4) and the
compile-reuse layer: determinism vs the full-CV sweep, raced_out markers in
the summary, tiny-grid parity, checkpoint-signature invalidation on racing
config changes, degraded notes on unraceable paths, and the exactness of
zero-weight pad rows."""

import numpy as np
import pytest

from test_aux_subsystems import make_records
from transmogrifai_tpu import types as T
from transmogrifai_tpu.checkpoint import SweepCheckpoint
from transmogrifai_tpu.features import features_from_schema
from transmogrifai_tpu.models.linear import OpLogisticRegression
from transmogrifai_tpu.ops.transmogrify import transmogrify
from transmogrifai_tpu.selector import (BinaryClassificationModelSelector,
                                        ModelCandidate, grid)
from transmogrifai_tpu.workflow import Workflow

LR_GRID = grid(reg_param=[0.001, 0.01, 0.1, 0.2],
               elastic_net_param=[0.1, 0.5])      # 8 points -> races to 3


def _workflow(records, racing=None, lr_grid=LR_GRID, num_folds=3,
              use_tvs=False):
    schema = {"y": T.RealNN, "x1": T.Real, "x2": T.Real, "cat": T.PickList,
              "sparse": T.Real}
    y, predictors = features_from_schema(schema, response="y")
    fv = transmogrify(predictors)
    checked = y.sanity_check(fv, remove_bad_features=True)
    sel = BinaryClassificationModelSelector(
        num_folds=num_folds, use_train_validation_split=use_tvs,
        models=[ModelCandidate(OpLogisticRegression(), lr_grid,
                               "OpLogisticRegression")])
    if racing is not None:
        sel.validator.racing = racing
    sel.set_input(y, checked)
    recs = [{k: (1.0 if k == "y" and v else 0.0) if k == "y" else v
             for k, v in r.items()} for r in records]
    return (Workflow().set_input_records(recs)
            .set_result_features(sel.get_output()))


def _summary(model):
    return model.selected_model.summary


class TestRacingDeterminism:
    @pytest.fixture(scope="class")
    def raced_and_full(self):
        records = make_records(240)
        raced = _workflow(records, racing=True).train()
        full = _workflow(records, racing=False).train()
        return _summary(raced), _summary(full)

    def test_winner_family_matches_full_cv(self, raced_and_full):
        raced, full = raced_and_full
        assert raced.best_model_name == full.best_model_name

    def test_survivor_metrics_match_full_cv(self, raced_and_full):
        """Survivors run every fold exactly as the full sweep does, so their
        k-fold means must agree with the unraced sweep's for the same
        params."""
        raced, full = raced_and_full
        full_by_params = {tuple(sorted(r.params.items())):
                          list(r.metric_values.values())[0]
                          for r in full.validation_results}
        survivors = [r for r in raced.validation_results if not r.raced_out]
        assert survivors
        for r in survivors:
            want = full_by_params[tuple(sorted(r.params.items()))]
            got = list(r.metric_values.values())[0]
            assert got == pytest.approx(want, abs=1e-6)

    def test_pruned_points_marked_raced_out(self, raced_and_full):
        raced, _ = raced_and_full
        pruned = [r for r in raced.validation_results if r.raced_out]
        # 8 grid points, eta=3, min_survivors=2 -> 3 survive, 5 raced out
        assert len(pruned) == 5
        assert len(raced.validation_results) == 8
        # every pruned point still carries its fold-0 screen metric
        for r in pruned:
            assert np.isfinite(list(r.metric_values.values())[0])

    def test_raced_out_never_wins(self, raced_and_full):
        raced, _ = raced_and_full
        winners = [r for r in raced.validation_results if not r.raced_out]
        best = _best(raced, winners)
        assert not best.raced_out

    def test_summary_json_and_pretty_carry_markers(self):
        records = make_records(240)
        model = _workflow(records, racing=True).train()
        js = _summary(model).to_json()
        marked = [r for r in js["validationResults"] if r.get("racedOut")]
        assert len(marked) == 5
        assert js["validationParameters"]["racing"]["enabled"] is True
        assert "raced out @fold0" in model.summary_pretty()

    def test_racing_stats_recorded(self):
        from transmogrifai_tpu.profiling import (racing_stats,
                                                 reset_racing_stats)
        reset_racing_stats()
        records = make_records(240)
        _workflow(records, racing=True).train()
        stats = racing_stats()
        # 5 pruned points x 2 remaining folds
        assert stats["points_pruned"] == 5
        assert stats["cv_fits_saved"] == 10
        assert stats["families_raced"] == 1


def _best(summary, results):
    metric = summary.evaluation_metric
    vals = [(list(r.metric_values.values())[0], i)
            for i, r in enumerate(results)]
    return results[max(vals)[1]]


class TestParityGuard:
    def test_tiny_grid_runs_full_cv_bit_identical(self):
        """A grid at/below the survivor floor cannot shrink — the parity
        guard keeps it on the exact unraced path, so scores are identical
        float-for-float."""
        records = make_records(200)
        tiny = grid(reg_param=[0.01, 0.1])
        m_on = _workflow(records, racing=True, lr_grid=tiny).train()
        m_off = _workflow(records, racing=False, lr_grid=tiny).train()
        on = {tuple(sorted(r.params.items())): r
              for r in _summary(m_on).validation_results}
        off = {tuple(sorted(r.params.items())): r
               for r in _summary(m_off).validation_results}
        assert set(on) == set(off)
        for k in on:
            assert not on[k].raced_out
            assert (list(on[k].metric_values.values())
                    == list(off[k].metric_values.values()))

    def test_single_split_records_degraded_note(self):
        """OpTrainValidationSplit (1 split) can't race: the default-on flag
        must be reported as degraded, not silently ignored."""
        records = make_records(200)
        model = _workflow(records, racing=True, use_tvs=True).train()
        notes = [e for e in model.failure_log
                 if e.action == "degraded" and e.point == "selector.racing"]
        assert notes, "unraceable path must record an explicit degraded note"
        assert not any(r.raced_out
                       for r in _summary(model).validation_results)


class TestCheckpointSignature:
    def test_signature_changes_with_racing_config(self):
        g = grid(reg_param=[0.01, 0.1])
        base = SweepCheckpoint.candidate_signature(
            "m", 0, g, racing={"enabled": True, "eta": 3.0,
                               "minSurvivors": 2})
        assert base != SweepCheckpoint.candidate_signature(
            "m", 0, g, racing={"enabled": False})
        assert base != SweepCheckpoint.candidate_signature(
            "m", 0, g, racing={"enabled": True, "eta": 2.0,
                               "minSurvivors": 2})
        assert base == SweepCheckpoint.candidate_signature(
            "m", 0, g, racing={"minSurvivors": 2, "eta": 3.0,
                               "enabled": True})

    def test_resume_with_changed_racing_params_refits(self, tmp_path):
        """Raced score lists must never replay into a sweep with different
        racing config: run 1 races, run 2 disables racing and resumes — the
        signatures differ, so the candidate re-fits (no 'resumed' events)
        and every point carries a full-CV mean (no raced_out leftovers)."""
        records = make_records(200)
        sweep_dir = str(tmp_path / "sweep")
        m1 = _workflow(records, racing=True).train(resume_from=sweep_dir)
        assert any(r.raced_out for r in _summary(m1).validation_results)
        assert len(SweepCheckpoint(sweep_dir)) == 1

        def replayed(model):
            # candidate-level replay events (the train-level "resumed" fires
            # whenever ANY checkpoint exists, even if nothing replays)
            return [e for e in model.failure_log
                    if e.action == "resumed"
                    and e.stage == "OpLogisticRegression"]

        m2 = _workflow(records, racing=False).train(resume_from=sweep_dir)
        assert not replayed(m2)
        assert not any(r.raced_out for r in _summary(m2).validation_results)

        # unchanged config DOES replay
        m3 = _workflow(records, racing=False).train(resume_from=sweep_dir)
        assert replayed(m3)


class TestZeroWeightPadding:
    def test_zero_weight_padding_leaves_linear_fit_exact(self):
        """The mesh pads the row count to its device quantum with
        zero-weight rows; every reduction in the linear solvers is
        sample-weighted, so the coefficients must not move."""
        import jax.numpy as jnp
        rng = np.random.default_rng(3)
        N, D, pad = 257, 5, 63
        X = rng.normal(size=(N, D)).astype(np.float32)
        w = rng.normal(size=D).astype(np.float32)
        y = (X @ w > 0).astype(np.float32)
        est = OpLogisticRegression(reg_param=0.01)
        assert est.weighted_pad_exact
        Xp = np.pad(X, ((0, pad), (0, 0)))
        yp = np.pad(y, (0, pad))
        W = np.ones((1, N + pad), np.float32)
        W[:, N:] = 0.0
        plain = est.fit_arrays_grid(jnp.asarray(X), jnp.asarray(y),
                                    jnp.ones((1, N), jnp.float32),
                                    [{"reg_param": 0.01}])[0][0]
        padded = est.fit_arrays_grid(jnp.asarray(Xp), jnp.asarray(yp),
                                     jnp.asarray(W),
                                     [{"reg_param": 0.01}])[0][0]
        np.testing.assert_allclose(np.asarray(padded["coef"]),
                                   np.asarray(plain["coef"]),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(padded["intercept"]),
                                   np.asarray(plain["intercept"]),
                                   rtol=1e-5, atol=1e-6)

"""Tests for the auxiliary subsystems: RawFeatureFilter, ModelInsights,
RecordInsightsLOCO, local scoring, testkit, runner, profiling
(≙ RawFeatureFilterTest, ModelInsightsTest, RecordInsightsLOCOTest,
OpWorkflowModelLocalTest, OpWorkflowRunnerTest)."""

import json
import os

import numpy as np
import pytest

from transmogrifai_tpu import types as T
from transmogrifai_tpu.columns import Column, ColumnBatch
from transmogrifai_tpu.evaluators import Evaluators
from transmogrifai_tpu.features import FeatureBuilder, features_from_schema
from transmogrifai_tpu.filters import RawFeatureFilter
from transmogrifai_tpu.models.linear import OpLogisticRegression
from transmogrifai_tpu.ops.transmogrify import transmogrify
from transmogrifai_tpu.readers.base import DataReader
from transmogrifai_tpu.selector import (BinaryClassificationModelSelector,
                                        ModelCandidate, grid)
from transmogrifai_tpu.testkit import (RandomBinary, RandomIntegral,
                                       RandomReal, RandomText, random_records)
from transmogrifai_tpu.workflow import Workflow


def make_records(n=300, seed=0):
    return random_records(n, {
        "y": RandomBinary(0.4),
        "x1": RandomReal.normal(0, 1),
        "x2": RandomReal.uniform(0, 10).with_probability_of_empty(0.2),
        "cat": RandomText.picklists(["a", "b", "c"]),
        "sparse": RandomReal.normal().with_probability_of_empty(0.995),
    }, seed=seed)


def train_small_model(records):
    schema = {"y": T.RealNN, "x1": T.Real, "x2": T.Real, "cat": T.PickList,
              "sparse": T.Real}
    y, predictors = features_from_schema(schema, response="y")
    fv = transmogrify(predictors)
    checked = y.sanity_check(fv, remove_bad_features=True)
    sel = BinaryClassificationModelSelector(models=[
        ModelCandidate(OpLogisticRegression(), grid(reg_param=[0.01]), "OpLogisticRegression")])
    sel.set_input(y, checked)
    pred = sel.get_output()
    recs = [{k: (1.0 if k == "y" and v else 0.0) if k == "y" else v
             for k, v in r.items()} for r in records]
    wf = Workflow().set_input_records(recs).set_result_features(pred)
    return wf, pred


class TestRawFeatureFilter:
    def test_drops_sparse_feature(self):
        records = make_records()
        schema = {"y": T.RealNN, "x1": T.Real, "x2": T.Real,
                  "cat": T.PickList, "sparse": T.Real}
        y, predictors = features_from_schema(schema, response="y")
        raw = [y] + predictors
        recs = [{k: (1.0 if k == "y" and v else 0.0) if k == "y" else v
                 for k, v in r.items()} for r in records]
        batch = DataReader(records=recs).generate_batch(raw)
        rff = RawFeatureFilter(min_fill_rate=0.1)
        clean, dropped, results = rff.filter_batch(batch, raw)
        assert "sparse" in results.dropped
        assert "sparse" not in clean
        assert "x1" not in results.dropped
        assert any(d.name == "x1" for d in results.train_distributions)
        js = json.dumps(results.to_json())
        assert "fillRate" in js

    def test_js_divergence_detects_shift(self):
        from transmogrifai_tpu.filters import FeatureDistribution
        d1 = FeatureDistribution("f", count=100, nulls=0,
                                 distribution=np.array([50, 50, 0, 0.0]))
        d2 = FeatureDistribution("f", count=100, nulls=0,
                                 distribution=np.array([0, 0, 50, 50.0]))
        assert d1.js_divergence(d2) > 0.9
        assert d1.js_divergence(d1) < 1e-9

    def test_workflow_integration(self):
        records = make_records()
        wf, pred = train_small_model(records)
        wf.with_raw_feature_filter(min_fill_rate=0.1)
        model = wf.train()
        assert any(f.name == "sparse" for f in model.blacklisted)
        assert model.rff_results is not None


class TestInsights:
    @pytest.fixture(scope="class")
    def model(self):
        wf, pred = train_small_model(make_records())
        return wf.train()

    def test_summary_json(self, model):
        s = model.summary()
        assert s["label"]["labelName"] == "y"
        assert s["selectedModelInfo"]["bestModelName"] == "OpLogisticRegression"
        assert len(s["features"]) > 0
        names = {f["featureName"] for f in s["features"]}
        assert "x1" in names or "x2" in names

    def test_summary_pretty(self, model):
        text = model.summary_pretty()
        assert "Selected model" in text
        assert "OpLogisticRegression" in text
        assert "+" in text and "|" in text  # ascii tables

    def test_record_insights_loco(self, model):
        from transmogrifai_tpu.record_insights import RecordInsightsLOCO
        sel = model.selected_model
        checked_f = sel.input_features[1]
        scored = model.score(keep_intermediate_features=True)
        loco = RecordInsightsLOCO(model=sel, top_k=3)
        loco.set_input(checked_f)
        out = loco.transform(scored)
        assert len(out) == len(scored)
        row0 = out.values[0]
        assert isinstance(row0, dict) and 0 < len(row0) <= 3


class TestLocalScoring:
    def test_score_function_matches_batch(self):
        from transmogrifai_tpu.local import score_function
        records = make_records(200)
        wf, pred = train_small_model(records)
        model = wf.train()
        scored = model.score()
        batch_preds = np.asarray(scored[pred.name].values["prediction"])
        fn = score_function(model)
        recs = [{k: (1.0 if k == "y" and v else 0.0) if k == "y" else v
                 for k, v in r.items()} for r in records]
        for i in [0, 7, 42, 199]:
            out = fn(recs[i])
            assert pred.name in out
            assert out[pred.name]["prediction"] == batch_preds[i]

    def test_score_function_without_label(self):
        from transmogrifai_tpu.local import score_function
        records = make_records(50)
        wf, pred = train_small_model(records)
        model = wf.train()
        fn = score_function(model)
        rec = {k: v for k, v in records[0].items() if k != "y"}
        out = fn(rec)
        assert out[pred.name]["prediction"] in (0.0, 1.0)


class TestTestkit:
    def test_probability_of_empty(self):
        vals = RandomReal.normal().with_probability_of_empty(0.5).limit(1000)
        frac_none = sum(v is None for v in vals) / len(vals)
        assert 0.4 < frac_none < 0.6

    def test_generators_deterministic(self):
        a = RandomText.picklists(["x", "y"], seed=7).limit(20)
        b = RandomText.picklists(["x", "y"], seed=7).limit(20)
        assert a == b

    def test_random_records(self):
        recs = random_records(10, {"a": RandomReal.normal(),
                                   "b": RandomIntegral.integers(0, 5)})
        assert len(recs) == 10
        assert set(recs[0]) == {"a", "b"}


class TestRunner:
    def test_train_then_score_run_types(self, tmp_path):
        from transmogrifai_tpu.params import OpParams
        from transmogrifai_tpu.runner import OpWorkflowRunner, RunType
        records = make_records(200)
        wf, pred = train_small_model(records)
        runner = OpWorkflowRunner(wf, evaluator=Evaluators.BinaryClassification.auROC())
        params = OpParams(model_location=str(tmp_path / "model"),
                          write_location=str(tmp_path / "scores"),
                          metrics_location=str(tmp_path / "metrics"))
        result = runner.run(RunType.TRAIN, params)
        assert result.model_summary is not None
        assert os.path.exists(tmp_path / "model" / "op-model.json")
        assert os.path.exists(tmp_path / "model" / "model-summary.json")
        assert result.app_metrics.total_wall_s > 0

        # score with the saved model
        recs = [{k: (1.0 if k == "y" and v else 0.0) if k == "y" else v
                 for k, v in r.items()} for r in records]
        runner2 = OpWorkflowRunner(wf, score_reader=DataReader(records=recs),
                                   evaluator=Evaluators.BinaryClassification.auROC())
        result2 = runner2.run(RunType.SCORE, params)
        assert result2.metrics is not None and result2.metrics["AuROC"] > 0.5
        scores_file = tmp_path / "scores" / "scores.jsonl"
        assert scores_file.exists()
        first = json.loads(scores_file.read_text().splitlines()[0])
        assert pred.name in first

    def test_streaming_score(self, tmp_path):
        from transmogrifai_tpu.params import OpParams
        from transmogrifai_tpu.readers.streaming import StreamingReaders
        from transmogrifai_tpu.runner import OpWorkflowRunner, RunType
        records = make_records(100)
        wf, pred = train_small_model(records)
        model = wf.train()
        model.save(str(tmp_path / "model"))
        recs = [{k: v for k, v in r.items() if k != "y"} for r in records]
        batches = [recs[:50], recs[50:]]
        runner = OpWorkflowRunner(
            wf, score_reader=StreamingReaders.custom(batches=batches))
        params = OpParams(model_location=str(tmp_path / "model"),
                          write_location=str(tmp_path / "stream_scores"))
        result = runner.run(RunType.STREAMING_SCORE, params)
        assert result.metrics["batches"] == 2
        assert (tmp_path / "stream_scores" / "scores_0.jsonl").exists()
        assert (tmp_path / "stream_scores" / "scores_1.jsonl").exists()


class TestParallel:
    def test_sharded_col_stats(self, eight_device_mesh):
        from transmogrifai_tpu.parallel import sharded_col_stats
        rng = np.random.default_rng(0)
        X = rng.normal(size=(64, 5)).astype(np.float32)
        y = rng.normal(size=64).astype(np.float32)
        stats = np.asarray(sharded_col_stats(X, y, eight_device_mesh))
        np.testing.assert_allclose(stats[0], X.mean(axis=0), atol=1e-5)
        np.testing.assert_allclose(stats[1], X.var(axis=0), atol=1e-5)
        expected_corr = [np.corrcoef(X[:, j], y)[0, 1] for j in range(5)]
        np.testing.assert_allclose(stats[2], expected_corr, atol=1e-4)

    def test_grid_fit_sharded_matches_single(self, eight_device_mesh):
        from transmogrifai_tpu.parallel import fit_logreg_grid_sharded
        rng = np.random.default_rng(1)
        N, D, G = 256, 6, 8
        X = rng.normal(size=(N, D)).astype(np.float32)
        w = rng.normal(size=D)
        y = ((X @ w) > 0).astype(np.float32)
        l2s = np.full(G, 1e-3, np.float32)
        l1s = np.zeros(G, np.float32)
        coefs, bs, accs = fit_logreg_grid_sharded(X, y, l2s, l1s,
                                                  eight_device_mesh, n_iter=200)
        coefs = np.asarray(coefs)
        # all identical hyperparams → identical solutions across the grid
        np.testing.assert_allclose(coefs[0], coefs[-1], atol=1e-5)
        assert float(np.asarray(accs).min()) > 0.9

    def test_sharded_train_step(self, eight_device_mesh):
        from transmogrifai_tpu.parallel import sharded_train_step
        rng = np.random.default_rng(2)
        N, D, G = 128, 4, 8
        X = rng.normal(size=(N, D)).astype(np.float32)
        y = (rng.random(N) > 0.5).astype(np.float32)
        step = sharded_train_step(eight_device_mesh, n_iter=4)
        w, b, losses = step(X, y, np.logspace(-3, 0, G).astype(np.float32),
                            np.zeros(G, np.float32))
        assert np.asarray(w).shape == (D,)
        assert np.isfinite(np.asarray(losses)).all()


def test_runner_score_without_workflow(tmp_path):
    """Score-type runs need only a saved model; train without a workflow
    raises an actionable error (≙ OpWorkflowRunner run-type dispatch)."""
    from transmogrifai_tpu.params import OpParams
    from transmogrifai_tpu.runner import OpWorkflowRunner, RunType
    from transmogrifai_tpu.selector import ModelCandidate, grid

    rng = np.random.default_rng(0)
    records = [{"y": float(i % 2), "x": float(rng.normal()) + (i % 2)}
               for i in range(120)]
    label = FeatureBuilder.RealNN("y").as_response()
    x = FeatureBuilder.Real("x").as_predictor()
    sel = BinaryClassificationModelSelector(models=[
        ModelCandidate(OpLogisticRegression(), grid(reg_param=[0.01]), "LR")])
    sel.set_input(label, transmogrify([x]))
    model = (Workflow().set_input_records(records)
             .set_result_features(sel.get_output()).train())
    loc = str(tmp_path / "m")
    model.save(loc)

    runner = OpWorkflowRunner(score_reader=DataReader(records=records[:10]))
    res = runner.run(RunType.SCORE, OpParams(
        model_location=loc, write_location=str(tmp_path / "scores")))
    assert res.scores_location

    with pytest.raises(ValueError, match="needs a Workflow"):
        OpWorkflowRunner().run(RunType.TRAIN, OpParams(model_location=loc))


def test_runner_applies_stage_params(tmp_path):
    """OpParams.stageParams inject per-stage-class hyperparameters before
    training (≙ OpWorkflow.setStageParameters, OpWorkflow.scala:178-199)."""
    import numpy as np
    from transmogrifai_tpu.params import OpParams
    from transmogrifai_tpu.runner import OpWorkflowRunner, RunType
    from transmogrifai_tpu.selector import ModelCandidate, grid

    rng = np.random.default_rng(0)
    records = [{"y": float(i % 2), "x": float(rng.normal()) + (i % 2)}
               for i in range(120)]
    label = FeatureBuilder.RealNN("y").as_response()
    x = FeatureBuilder.Real("x").as_predictor()
    checked = label.sanity_check(transmogrify([x]),
                                 remove_bad_features=False)
    sel = BinaryClassificationModelSelector(models=[
        ModelCandidate(OpLogisticRegression(), grid(reg_param=[0.01]), "LR")])
    sel.set_input(label, checked)
    wf = Workflow().set_input_records(records) \
                   .set_result_features(sel.get_output())
    checker_stage = checked.origin_stage
    assert checker_stage.get("max_correlation") != 0.77
    runner = OpWorkflowRunner(wf)
    runner.run(RunType.TRAIN, OpParams(
        model_location=str(tmp_path / "m"),
        stage_params={"SanityChecker": {"max_correlation": 0.77}}))
    assert checker_stage.get("max_correlation") == 0.77

    # a typo'd stage-class name warns instead of silently training defaults
    import warnings as _w
    with _w.catch_warnings(record=True) as caught:
        _w.simplefilter("always")
        wf.apply_stage_params(OpParams(
            stage_params={"SanityCheker": {"max_correlation": 0.5}}))
    assert any("matched no stage" in str(w.message) for w in caught)


def test_rff_js_divergence_drops_shifted_feature():
    """A feature whose train vs scoring distributions diverge beyond
    max_js_divergence is dropped (≙ RawFeatureFilter's train-vs-score JS
    check, RawFeatureFilter.scala:218-445)."""
    rng = np.random.default_rng(5)
    n = 400
    train_records, score_records = [], []
    for i in range(n):
        train_records.append({"y": float(i % 2),
                              "stable": float(rng.normal()),
                              "shifty": float(rng.normal(0.0, 0.5))})
        score_records.append({"stable": float(rng.normal()),
                              "shifty": float(rng.normal(50.0, 0.5))})
    schema = {"y": T.RealNN, "stable": T.Real, "shifty": T.Real}
    y, predictors = features_from_schema(schema, response="y")
    raw = [y] + predictors
    batch = DataReader(records=train_records).generate_batch(raw)
    rff = RawFeatureFilter(max_js_divergence=0.5,
                           score_reader=DataReader(records=score_records))
    clean, dropped, results = rff.filter_batch(batch, raw)
    assert "shifty" in results.dropped
    assert "stable" not in results.dropped
    assert any("js" in " ".join(rs).lower()
               for rs in results.reasons.values() if rs)


def test_rff_drops_shifted_map_keys_individually():
    """A map feature with one shifted key drops just that KEY (cleaned out of
    the surviving column); the whole feature drops only when every key
    fails (≙ per-key FeatureDistributions + mapKeysDropped)."""
    rng = np.random.default_rng(6)
    n = 300
    train_records, score_records = [], []
    for i in range(n):
        train_records.append({"y": float(i % 2),
                              "m": {"ok": float(rng.normal()),
                                    "drift": float(rng.normal(0.0, 0.5))}})
        score_records.append({"m": {"ok": float(rng.normal()),
                                    "drift": float(rng.normal(40.0, 0.5))}})
    schema = {"y": T.RealNN, "m": T.RealMap}
    y, predictors = features_from_schema(schema, response="y")
    raw = [y] + predictors
    batch = DataReader(records=train_records).generate_batch(raw)
    rff = RawFeatureFilter(max_js_divergence=0.5,
                           score_reader=DataReader(records=score_records))
    clean, dropped, results = rff.filter_batch(batch, raw)
    assert results.dropped_map_keys.get("m") == ["drift"]
    assert "m" not in results.dropped          # one healthy key survives
    assert all("drift" not in (m or {}) for m in clean["m"].values)
    assert any("ok" in (m or {}) for m in clean["m"].values)


class TestInsightsDepth:
    """Reference-depth ModelInsights (≙ ModelInsights.scala:74-392): RFF
    distributions, per-group Cramér's V, descaled contributions, training
    echo — the golden check."""

    @pytest.fixture(scope="class")
    def deep_model(self):
        wf, pred = train_small_model(make_records())
        wf.with_raw_feature_filter(min_fill_rate=0.1)
        wf.set_parameters({"custom_tag": "insights-golden"})
        return wf.train()

    def test_distributions_surfaced(self, deep_model):
        s = deep_model.summary()
        by_name = {f["featureName"]: f for f in s["features"]}
        assert "x1" in by_name
        dists = by_name["x1"]["distributions"]
        assert dists and dists[0]["count"] > 0
        assert "distribution" in dists[0]
        # the RFF-dropped sparse feature still appears, with its distribution
        assert "sparse" in by_name
        assert by_name["sparse"]["distributions"]

    def test_cramers_v_joined_per_group(self, deep_model):
        s = deep_model.summary()
        by_name = {f["featureName"]: f for f in s["features"]}
        cat_cols = by_name["cat"]["derivedFeatures"]
        cram = [c["cramersV"] for c in cat_cols
                if c.get("indicatorValue") is not None]
        assert cram and all(v is not None and 0.0 <= v <= 1.0 for v in cram)
        # only indicator columns carry a group Cramér's V (value columns of
        # x1 don't; its null-indicator column does, like the reference's
        # categorical tests over every indicator group)
        for f in s["features"]:
            for c in f["derivedFeatures"]:
                if c.get("indicatorValue") is None:
                    assert c["cramersV"] is None, c["name"]

    def test_descaled_contributions(self, deep_model):
        s = deep_model.summary()
        kept = [c for f in s["features"] for c in f["derivedFeatures"]
                if not c["dropped"]]
        assert any(c["descaledContribution"] is not None for c in kept)
        for c in kept:
            if c["descaledContribution"] is not None:
                want = abs(c["contribution"]) * np.sqrt(max(c["variance"], 0.0))
                assert abs(c["descaledContribution"] - want) < 1e-9

    def test_training_echo(self, deep_model):
        s = deep_model.summary()
        assert s["trainingParams"].get("custom_tag") == "insights-golden"
        classes = {v["className"] for v in s["stageInfo"].values()}
        assert "SanityCheckerModel" in classes
        assert "SelectedModel" in classes

    def test_pretty_includes_new_columns(self, deep_model):
        text = deep_model.summary_pretty()
        assert "Cramér's V" in text
        assert "Fill Rate" in text

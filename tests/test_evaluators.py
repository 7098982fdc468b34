"""Evaluator golden checks against hand-computed / sklearn-style values
(≙ OpBinaryClassificationEvaluatorTest etc.)."""

import numpy as np
import pytest

from transmogrifai_tpu.evaluators import (Evaluators, aupr, auroc,
                                          binary_confusion)


def test_auroc_perfect_and_random():
    y = np.array([0, 0, 1, 1])
    assert auroc(y, np.array([0.1, 0.2, 0.8, 0.9])) == 1.0
    assert auroc(y, np.array([0.9, 0.8, 0.2, 0.1])) == 0.0
    # known sklearn value for this case
    got = auroc(np.array([0, 0, 1, 1]), np.array([0.1, 0.4, 0.35, 0.8]))
    assert abs(got - 0.75) < 1e-9


def test_auroc_ties():
    y = np.array([0, 1, 0, 1])
    s = np.array([0.5, 0.5, 0.5, 0.5])
    assert abs(auroc(y, s) - 0.5) < 1e-9


def test_aupr_known_value():
    y = np.array([0, 0, 1, 1])
    s = np.array([0.1, 0.4, 0.35, 0.8])
    got = aupr(y, s)
    assert 0.7 < got < 0.9  # sklearn average_precision ≈ 0.83


def test_binary_confusion():
    y = np.array([1, 1, 0, 0, 1])
    yhat = np.array([1, 0, 0, 1, 1])
    m = binary_confusion(y, yhat)
    assert (m["TP"], m["TN"], m["FP"], m["FN"]) == (2, 1, 1, 1)
    assert abs(m["Precision"] - 2 / 3) < 1e-9
    assert abs(m["Recall"] - 2 / 3) < 1e-9
    assert abs(m["Error"] - 2 / 5) < 1e-9


def test_binary_evaluator_all_metrics():
    rng = np.random.default_rng(0)
    y = (rng.random(200) > 0.5).astype(float)
    p1 = np.clip(y * 0.6 + rng.random(200) * 0.4, 0, 1)
    pred = {"prediction": (p1 > 0.5).astype(float),
            "probability": np.stack([1 - p1, p1], axis=1),
            "rawPrediction": np.stack([-p1, p1], axis=1)}
    m = Evaluators.BinaryClassification.auPR().evaluate_all(y, pred)
    for k in ("AuROC", "AuPR", "Precision", "Recall", "F1", "Error",
              "TP", "TN", "FP", "FN", "thresholds", "precisionByThreshold"):
        assert k in m.metrics
    assert m["AuROC"] > 0.8


def test_multiclass_evaluator():
    y = np.array([0, 1, 2, 0, 1, 2], dtype=float)
    pred = {"prediction": np.array([0, 1, 2, 0, 2, 2], dtype=float),
            "probability": None, "rawPrediction": None}
    m = Evaluators.MultiClassification.f1().evaluate_all(y, pred)
    assert abs(m["Error"] - 1 / 6) < 1e-9
    assert 0 < m["F1"] <= 1


def test_regression_evaluator():
    y = np.array([1.0, 2.0, 3.0])
    pred = {"prediction": np.array([1.1, 1.9, 3.2])}
    m = Evaluators.Regression.rmse().evaluate_all(y, pred)
    expect_mse = np.mean([0.01, 0.01, 0.04])
    assert abs(m["MeanSquaredError"] - expect_mse) < 1e-6
    assert abs(m["RootMeanSquaredError"] - np.sqrt(expect_mse)) < 1e-6
    assert m["R2"] > 0.9


def test_forecast_evaluator():
    y = np.array([10.0, 12.0, 14.0, 16.0])
    pred = {"prediction": y * 1.1}
    m = Evaluators.Forecast.smape().evaluate_all(y, pred)
    assert 0 < m["SMAPE"] < 0.2
    assert m["MASE"] > 0


def test_bin_score_evaluator_calibrated():
    rng = np.random.default_rng(1)
    p = rng.random(5000)
    y = (rng.random(5000) < p).astype(float)
    pred = {"prediction": (p > 0.5).astype(float),
            "probability": np.stack([1 - p, p], axis=1),
            "rawPrediction": None}
    m = Evaluators.BinaryClassification.brierScore().evaluate_all(y, pred)
    # calibrated scores: avg score ≈ conversion rate in populated bins
    counts = np.array(m["numberOfDataPoints"])
    avg = np.array(m["averageScore"])
    conv = np.array(m["averageConversionRate"])
    big = counts > 30
    assert np.abs(avg[big] - conv[big]).mean() < 0.15


def test_device_panel_matches_host_binary():
    """evaluate_all_device must reproduce the host evaluate_all panel."""
    import jax.numpy as jnp
    from transmogrifai_tpu.evaluators import OpBinaryClassificationEvaluator
    rng = np.random.default_rng(7)
    n = 2000
    y = (rng.random(n) > 0.6).astype(np.float64)
    s = np.clip(y * 0.6 + rng.normal(scale=0.3, size=n) + 0.2, 0, 1)
    pred = {"prediction": (s > 0.5).astype(np.float64),
            "probability": np.stack([1 - s, s], axis=1),
            "rawPrediction": None}
    ev = OpBinaryClassificationEvaluator()
    host = ev.evaluate_all(y, pred).to_json()
    dev = ev.evaluate_all_device(
        jnp.asarray(y, jnp.float32),
        {"prediction": jnp.asarray(pred["prediction"], jnp.float32),
         "probability": jnp.asarray(pred["probability"], jnp.float32),
         "scores": jnp.asarray(s, jnp.float32)},
        jnp.ones(n, jnp.float32)).to_json()
    for k in ("TP", "TN", "FP", "FN"):
        assert dev[k] == host[k], k
    for k in ("Precision", "Recall", "F1", "Error", "AuROC", "AuPR"):
        assert abs(dev[k] - host[k]) < 1e-4, (k, dev[k], host[k])
    np.testing.assert_allclose(dev["truePositivesByThreshold"],
                               host["truePositivesByThreshold"], atol=0.5)
    np.testing.assert_allclose(dev["precisionByThreshold"],
                               host["precisionByThreshold"], atol=1e-4)


def test_device_panel_matches_host_regression():
    import jax.numpy as jnp
    from transmogrifai_tpu.evaluators import OpRegressionEvaluator
    rng = np.random.default_rng(8)
    n = 1500
    y = rng.normal(size=n)
    yhat = y + rng.normal(scale=0.4, size=n)
    ev = OpRegressionEvaluator()
    host = ev.evaluate_all(y, {"prediction": yhat}).to_json()
    dev = ev.evaluate_all_device(
        jnp.asarray(y, jnp.float32),
        {"prediction": jnp.asarray(yhat, jnp.float32)},
        jnp.ones(n, jnp.float32)).to_json()
    for k in ("RootMeanSquaredError", "MeanSquaredError",
              "MeanAbsoluteError", "R2"):
        assert abs(dev[k] - host[k]) < 1e-4, (k, dev[k], host[k])
    assert sum(dev["SignedPercentageErrorHistogram"]["counts"]) == n


def test_device_threshold_panel_unsorted_thresholds():
    """Non-ascending custom thresholds must come back in caller order,
    matching the host panel."""
    import jax.numpy as jnp
    from transmogrifai_tpu.evaluators import OpBinaryClassificationEvaluator
    rng = np.random.default_rng(9)
    n = 500
    y = (rng.random(n) > 0.5).astype(np.float64)
    s = np.clip(y * 0.5 + rng.normal(scale=0.3, size=n) + 0.25, 0, 1)
    ev = OpBinaryClassificationEvaluator(thresholds=np.array([0.9, 0.5, 0.1]))
    pred = {"prediction": (s > 0.5).astype(np.float64),
            "probability": np.stack([1 - s, s], axis=1), "rawPrediction": None}
    host = ev.evaluate_all(y, pred).to_json()
    dev = ev.evaluate_all_device(
        jnp.asarray(y, jnp.float32),
        {"prediction": jnp.asarray(pred["prediction"], jnp.float32),
         "scores": jnp.asarray(s, jnp.float32)},
        jnp.ones(n, jnp.float32)).to_json()
    np.testing.assert_allclose(dev["truePositivesByThreshold"],
                               host["truePositivesByThreshold"], atol=0.5)
    np.testing.assert_allclose(dev["falsePositivesByThreshold"],
                               host["falsePositivesByThreshold"], atol=0.5)


def test_device_panel_matches_host_multiclass():
    import jax.numpy as jnp
    from transmogrifai_tpu.evaluators import OpMultiClassificationEvaluator
    rng = np.random.default_rng(11)
    n, C = 1200, 4
    y = rng.integers(0, C, size=n)
    logits = rng.normal(size=(n, C)) + 2.0 * np.eye(C)[y]
    prob = np.exp(logits) / np.exp(logits).sum(1, keepdims=True)
    yhat = prob.argmax(1)
    ev = OpMultiClassificationEvaluator()
    host = ev.evaluate_all(y, {"prediction": yhat, "probability": prob}).to_json()
    dev = ev.evaluate_all_device(
        jnp.asarray(y, jnp.float32),
        {"prediction": jnp.asarray(yhat, jnp.float32),
         "probability": jnp.asarray(prob, jnp.float32)},
        jnp.ones(n, jnp.float32)).to_json()
    for k in ("Precision", "Recall", "F1", "Error"):
        assert abs(dev[k] - host[k]) < 1e-6, k
    np.testing.assert_allclose(dev["confusionMatrix"], host["confusionMatrix"])
    h = host["ThresholdMetrics"]["byTopN"]
    d = dev["ThresholdMetrics"]["byTopN"]
    for nk in h:
        np.testing.assert_allclose(d[nk]["topNCountByBin"],
                                   h[nk]["topNCountByBin"], atol=0.5)
        np.testing.assert_allclose(d[nk]["topNCorrectByBin"],
                                   h[nk]["topNCorrectByBin"], atol=0.5)


def test_custom_evaluator_in_selector():
    """Evaluators.*.custom drives model selection with a user metric
    (≙ Evaluators.scala custom evaluators)."""
    from transmogrifai_tpu.evaluators import Evaluators
    from transmogrifai_tpu.features import FeatureBuilder
    from transmogrifai_tpu.models.linear import OpLogisticRegression
    from transmogrifai_tpu.ops.transmogrify import transmogrify
    from transmogrifai_tpu.selector import (BinaryClassificationModelSelector,
                                            ModelCandidate, grid)
    from transmogrifai_tpu.workflow import Workflow

    def neg_logloss(y, pred):
        p = np.clip(np.asarray(pred["probability"])[:, 1], 1e-9, 1 - 1e-9)
        yy = np.asarray(y)
        return float(np.mean(yy * np.log(p) + (1 - yy) * np.log(1 - p)))

    ev = Evaluators.BinaryClassification.custom("negLogLoss", neg_logloss)
    assert ev.is_larger_better and ev.default_metric == "negLogLoss"
    rng = np.random.default_rng(0)
    records = [{"y": float(i % 2), "x": float(rng.normal()) + (i % 2)}
               for i in range(160)]
    label = FeatureBuilder.RealNN("y").as_response()
    x = FeatureBuilder.Real("x").as_predictor()
    sel = BinaryClassificationModelSelector(
        models=[ModelCandidate(OpLogisticRegression(),
                               grid(reg_param=[0.01, 0.5]), "LR")],
        validation_metric=ev)
    sel.set_input(label, transmogrify([x]))
    model = (Workflow().set_input_records(records)
             .set_result_features(sel.get_output()).train())
    m = model.evaluate(ev)
    assert -1.0 < m["negLogLoss"] < 0.0


def test_masked_grid_metrics_match_per_candidate():
    """The batched (fold x grid) metric path must equal the per-candidate
    masked metrics exactly — including under vmap (a float-max sentinel bug
    made vmapped one-hot walks diverge in round 4; guard the metric vmaps
    the same way)."""
    import jax.numpy as jnp

    from transmogrifai_tpu.metrics_device import (masked_aupr,
                                                  masked_aupr_grid,
                                                  masked_auroc,
                                                  masked_auroc_grid)

    rng = np.random.default_rng(3)
    n, k = 4096, 5
    y = jnp.asarray((rng.random(n) < 0.4).astype(np.float32))
    S = jnp.asarray(rng.normal(size=(n, k)).astype(np.float32))
    # ties included: quantize one column hard
    S = S.at[:, 2].set(jnp.round(S[:, 2]))
    W = jnp.asarray((rng.random((k, n)) < 0.5).astype(np.float32))

    g_roc = np.asarray(masked_auroc_grid(y, S, W))
    g_pr = np.asarray(masked_aupr_grid(y, S, W))
    for j in range(k):
        assert np.allclose(g_roc[j], float(masked_auroc(y, S[:, j], W[j])),
                           atol=1e-6)
        assert np.allclose(g_pr[j], float(masked_aupr(y, S[:, j], W[j])),
                           atol=1e-6)


def test_fold_grid_metric_panel_matches_per_fold():
    """The one-program (fold × grid) panel must equal the per-fold grid
    calls it replaces (masks stay [F, N], scores [N, F, G])."""
    import jax.numpy as jnp

    from transmogrifai_tpu.metrics_device import (masked_aupr_fold_grid,
                                                  masked_aupr_grid,
                                                  masked_auroc_fold_grid,
                                                  masked_auroc_grid)

    rng = np.random.default_rng(9)
    n, F, G = 2048, 3, 4
    y = jnp.asarray((rng.random(n) < 0.45).astype(np.float32))
    S3 = jnp.asarray(rng.normal(size=(n, F, G)).astype(np.float32))
    S3 = S3.at[:, 1, 0].set(jnp.round(S3[:, 1, 0]))     # ties
    W = jnp.asarray((rng.random((F, n)) < 0.33).astype(np.float32))

    p_roc = np.asarray(masked_auroc_fold_grid(y, S3, W))
    p_pr = np.asarray(masked_aupr_fold_grid(y, S3, W))
    assert p_roc.shape == (F, G) and p_pr.shape == (F, G)
    for f in range(F):
        np.testing.assert_allclose(
            p_roc[f], np.asarray(masked_auroc_grid(y, S3[:, f, :], W[f])),
            atol=1e-6)
        np.testing.assert_allclose(
            p_pr[f], np.asarray(masked_aupr_grid(y, S3[:, f, :], W[f])),
            atol=1e-6)


# --------------------------------------------------------------------------
# the panel's tie groups by scans (PR 32): against the host float64
# evaluators, and against the searchsorted form's own numbers
# --------------------------------------------------------------------------

PANEL_TIES = ("none", "heavy", "all_equal")
PANEL_WEIGHTS = ("mask01", "fractional", "no_positive", "all_zero")
PANEL_ROWS, PANEL_FOLDS, PANEL_GRID = 4096, 2, 2


def panel_case(ties, weights, rows=PANEL_ROWS,
               lanes=PANEL_FOLDS * PANEL_GRID):
    """y [rows], S [rows, lanes], W [lanes, rows], float32.  A row that tops
    a lane carries weight in every mask (unless the mask is empty), so the
    artefact of ROADMAP D10a stays out: it has a test of its own."""
    rng = np.random.default_rng(
        [32, PANEL_TIES.index(ties), PANEL_WEIGHTS.index(weights)])
    y = (rng.random(rows) < 0.35).astype(np.float32)
    S = (rng.normal(size=(rows, lanes)) + 0.8 * y[:, None]).astype(np.float32)
    if ties == "heavy":
        S = (np.round(S * 10.0) / 10.0).astype(np.float32)
    elif ties == "all_equal":
        S = np.full((rows, lanes), 0.25, np.float32)
    if weights == "fractional":
        W = rng.integers(0, 5, size=(lanes, rows)) / 4.0
    elif weights == "all_zero":
        W = np.zeros((lanes, rows))
    else:
        W = rng.random((lanes, rows)) < 0.4
        if weights == "no_positive":
            W &= y < 0.5
    W = W.astype(np.float32)
    if weights in ("mask01", "fractional") and ties != "all_equal":
        W[:, (S == S.max(axis=0)).any(axis=1)] = 1.0
    return y, S, W


def host_weighted(metric, y, s, w):
    """The host float64 metric under weights that are quarters: a row of
    weight k/4 is k equal rows (they share a tie group, so the curve is the
    weighted one)."""
    reps = np.rint(4.0 * w).astype(int)
    return metric(np.repeat(y, reps), np.repeat(s.astype(np.float64), reps))


PANEL_CASES = [(t, w) for t in PANEL_TIES for w in PANEL_WEIGHTS]
PANEL_ATOL = 5e-6          # float32 sums of 4,096 terms against float64


@pytest.mark.parametrize("ties,weights", PANEL_CASES)
def test_masked_metrics_match_host(ties, weights):
    from transmogrifai_tpu.metrics_device import masked_aupr, masked_auroc
    y, S, W = panel_case(ties, weights)
    for lane in range(S.shape[1]):
        s, w = S[:, lane], W[lane]
        assert abs(float(masked_auroc(y, s, w))
                   - host_weighted(auroc, y, s, w)) < PANEL_ATOL
        assert abs(float(masked_aupr(y, s, w))
                   - host_weighted(aupr, y, s, w)) < PANEL_ATOL


@pytest.mark.parametrize("ties,weights", PANEL_CASES)
def test_masked_grid_forms_match_host(ties, weights):
    """``_grid`` under one shared mask and under a mask a candidate, and
    ``_fold_grid``: every lane is the host's number for its column and mask."""
    from transmogrifai_tpu import metrics_device as md
    y, S, W = panel_case(ties, weights)
    S3 = S.reshape(PANEL_ROWS, PANEL_FOLDS, PANEL_GRID)
    for host, grid, fold_grid in (
            (auroc, md.masked_auroc_grid, md.masked_auroc_fold_grid),
            (aupr, md.masked_aupr_grid, md.masked_aupr_fold_grid)):
        want = lambda lane, mask: host_weighted(host, y, S[:, lane], W[mask])
        lanes = range(S.shape[1])
        np.testing.assert_allclose(
            np.asarray(grid(y, S, W)), [want(k, k) for k in lanes],
            rtol=0, atol=PANEL_ATOL)
        np.testing.assert_allclose(
            np.asarray(grid(y, S, W[0])), [want(k, 0) for k in lanes],
            rtol=0, atol=PANEL_ATOL)
        np.testing.assert_allclose(
            np.asarray(fold_grid(y, S3, W[:PANEL_FOLDS])),
            [[want(f * PANEL_GRID + g, f) for g in range(PANEL_GRID)]
             for f in range(PANEL_FOLDS)], rtol=0, atol=PANEL_ATOL)


# float32 bit patterns read from the parent commit 554b65f (the searchsorted
# form) on these cases, XLA:CPU; an empty class reads 0.0 in every form
PANEL_GOLDEN = {
    "none": {
        "auroc_grid": [1060177013, 1060275503, 1059916757, 1060468668],
        "aupr_grid": [1057343758, 1058652915, 1057529751, 1057753773],
        "auroc_fold_grid": [1060177013, 1060657737, 1059977596, 1060654645],
        "aupr_fold_grid": [1057343758, 1058405195, 1058136637, 1058694018]},
    "heavy": {
        "auroc_grid": [1060621746, 1060553258, 1060468714, 1060544513],
        "aupr_grid": [1057799973, 1058248577, 1058519350, 1058427721],
        "auroc_fold_grid": [1060621746, 1060616575, 1060886121, 1060663967],
        "aupr_fold_grid": [1057799973, 1057809304, 1058521136, 1058513390]},
    "all_equal": {
        "auroc_grid": [1056964608] * 4,
        "aupr_grid": [1059890505, 1059837706, 1059794439, 1059936748],
        "auroc_fold_grid": [1056964608] * 4,
        "aupr_fold_grid": [1059890505, 1059890505, 1059837706, 1059837706]},
}
# a weight-0 row ranked first (ROADMAP D10a), lane 0 of the mask01 cases
PANEL_GOLDEN_D10A = {"none": (1060177013, 1057328937),
                     "heavy": (1060617433, 1057784912)}


def float32_bits(a):
    return np.asarray(a).view(np.uint32).ravel().tolist()


@pytest.mark.parametrize("weights", ["mask01", "no_positive", "all_zero"])
@pytest.mark.parametrize("ties", PANEL_TIES)
def test_masked_metrics_equal_the_searchsorted_form_to_the_bit(ties, weights):
    """Under a 0/1 mask every count is an integer below 2^24, exact in
    float32 in any order: the scans find the curve's points the binary
    search found, and the same float32 comes out."""
    from transmogrifai_tpu import metrics_device as md
    y, S, W = panel_case(ties, weights)
    S3 = S.reshape(PANEL_ROWS, PANEL_FOLDS, PANEL_GRID)
    golden = (PANEL_GOLDEN[ties] if weights == "mask01"
              else dict.fromkeys(PANEL_GOLDEN[ties], [0] * 4))
    got = {"auroc_grid": md.masked_auroc_grid(y, S, W),
           "aupr_grid": md.masked_aupr_grid(y, S, W),
           "auroc_fold_grid": md.masked_auroc_fold_grid(
               y, S3, W[:PANEL_FOLDS]),
           "aupr_fold_grid": md.masked_aupr_fold_grid(y, S3, W[:PANEL_FOLDS])}
    assert {k: float32_bits(v) for k, v in got.items()} == golden
    # one lane alone is lane 0 of the grid
    assert float32_bits(md.masked_auroc(y, S[:, 0], W[0])) == \
        golden["auroc_grid"][:1]
    assert float32_bits(md.masked_aupr(y, S[:, 0], W[0])) == \
        golden["aupr_grid"][:1]


@pytest.mark.parametrize("ties", ["none", "heavy"])
def test_masked_aupr_weight_zero_row_ranked_first_is_pinned(ties):
    """ROADMAP D10a, pinned at today's reading so that the PR that repairs
    it changes it knowingly: a row of weight 0 that outranks every
    validation row adds a point (recall 0, precision 0) after the prepended
    (0, 1), so the first trapezoid loses half the first group's recall.
    AuROC does not see the row."""
    from transmogrifai_tpu.metrics_device import masked_aupr, masked_auroc
    y, S, W = panel_case(ties, "mask01")
    s, w = S[:, 0].copy(), W[0].copy()
    i = int(np.argmin(s))
    s[i], w[i] = s.max() + 1.0, 0.0
    keep = w > 0
    top = s[keep] == s[keep].max()
    first_recall = (y[keep][top] > 0.5).sum() / (y[keep] > 0.5).sum()
    assert first_recall > 0
    got_roc, got_pr = masked_auroc(y, s, w), masked_aupr(y, s, w)
    assert abs(float(got_pr) - (aupr(y[keep], s[keep].astype(np.float64))
                                - 0.5 * first_recall)) < PANEL_ATOL
    assert abs(float(got_roc)
               - auroc(y[keep], s[keep].astype(np.float64))) < PANEL_ATOL
    assert (float32_bits(got_roc) + float32_bits(got_pr)
            == list(PANEL_GOLDEN_D10A[ties]))


def test_validator_batched_linear_metrics_match_fallback(monkeypatch):
    """OpValidator's batched linear-family metric path must select the same
    winner with the same mean metrics as the per-candidate fallback."""
    import pytest

    from transmogrifai_tpu.columns import Column, ColumnBatch
    from transmogrifai_tpu.models.linear import OpLogisticRegression
    from transmogrifai_tpu.tuning import ModelCandidate, OpCrossValidation
    from transmogrifai_tpu.types import OPVector, RealNN
    import transmogrifai_tpu.tuning as tu

    rng = np.random.default_rng(9)
    n, d = 6000, 6
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = ((X[:, 0] - 0.5 * X[:, 1]) + rng.normal(scale=0.8, size=n) > 0
         ).astype(np.float32)
    batch = ColumnBatch({"label": Column(RealNN, y),
                         "fv": Column(OPVector, X)}, n)
    cands = [ModelCandidate(OpLogisticRegression(),
                            [dict(reg_param=r, max_iter=25)
                             for r in (0.01, 0.1, 1.0)], "LR")]

    def run(disable_batched):
        if disable_batched:
            monkeypatch.setattr(
                tu.OpValidator, "_record_grid_metrics_batched",
                lambda self, *a, **k: False)
        v = OpCrossValidation(num_folds=3,
                              evaluator=Evaluators.BinaryClassification.auPR())
        res = v.validate(cands, batch, "label", "fv")
        monkeypatch.undo()
        return res

    a = run(False)
    b = run(True)
    assert a.best_params == b.best_params
    ma = {(r.model_name, tuple(sorted(r.params.items()))): r.mean_metric
          for r in a.all_results}
    mb = {(r.model_name, tuple(sorted(r.params.items()))): r.mean_metric
          for r in b.all_results}
    assert ma.keys() == mb.keys()
    for key in ma:
        assert ma[key] == pytest.approx(mb[key], abs=1e-6), key


def test_validator_batched_tree_metrics_match_fallback(monkeypatch):
    """The grouped tree-family metric path (concatenated tree stacks, leaf
    sums as rank-equivalent scores) must reproduce the per-candidate device
    metrics for RF and GBT."""
    import pytest

    from transmogrifai_tpu.columns import Column, ColumnBatch
    from transmogrifai_tpu.models.trees import (OpGBTClassifier,
                                                OpRandomForestClassifier)
    from transmogrifai_tpu.tuning import ModelCandidate, OpCrossValidation
    from transmogrifai_tpu.types import OPVector, RealNN
    import transmogrifai_tpu.tuning as tu

    rng = np.random.default_rng(17)
    n, d = 4000, 6
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = ((X[:, 0] + 0.5 * X[:, 1] * X[:, 2])
         + rng.normal(scale=0.7, size=n) > 0).astype(np.float32)
    batch = ColumnBatch({"label": Column(RealNN, y),
                         "fv": Column(OPVector, X)}, n)
    cands = [
        ModelCandidate(OpRandomForestClassifier(),
                       [dict(num_trees=8, max_depth=4),
                        dict(num_trees=8, max_depth=3)], "RF"),
        ModelCandidate(OpGBTClassifier(),
                       [dict(max_iter=5, max_depth=3)], "GBT"),
    ]

    def run(disable_batched):
        if disable_batched:
            monkeypatch.setattr(
                tu.OpValidator, "_record_grid_metrics_batched",
                lambda self, *a, **k: False)
        v = OpCrossValidation(num_folds=3,
                              evaluator=Evaluators.BinaryClassification.auPR())
        res = v.validate(cands, batch, "label", "fv")
        monkeypatch.undo()
        return res

    a = run(False)
    b = run(True)
    assert a.best_params == b.best_params
    assert a.best.model_name == b.best.model_name
    ma = {(r.model_name, tuple(sorted(r.params.items()))): r.mean_metric
          for r in a.all_results}
    mb = {(r.model_name, tuple(sorted(r.params.items()))): r.mean_metric
          for r in b.all_results}
    assert ma.keys() == mb.keys()
    for key in ma:
        assert ma[key] == pytest.approx(mb[key], abs=2e-4), (
            key, ma[key], mb[key])

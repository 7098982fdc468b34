"""Golden numeric checks against sklearn/scipy (SURVEY §4: "numeric golden
checks against sklearn-computed stats") — metrics, model fits, calibrators,
and sanity statistics must agree with the independent implementations."""

import numpy as np
import pytest

sklearn = pytest.importorskip("sklearn")

from sklearn.isotonic import IsotonicRegression  # noqa: E402
from sklearn.linear_model import LogisticRegression, Ridge  # noqa: E402
from sklearn.metrics import (average_precision_score,  # noqa: E402
                             roc_auc_score)


def _binary_data(n=3000, d=6, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    w = rng.normal(size=d)
    y = (X @ w + rng.normal(scale=0.5, size=n) > 0).astype(np.float32)
    return X, y


def test_auroc_aupr_match_sklearn():
    from transmogrifai_tpu.evaluators import aupr, auroc
    rng = np.random.default_rng(1)
    y = (rng.random(4000) > 0.6).astype(np.float64)
    s = np.clip(y * 0.5 + rng.normal(scale=0.35, size=4000) + 0.25, 0, 1)
    assert auroc(y, s) == pytest.approx(roc_auc_score(y, s), abs=1e-9)
    # AuPR is MLlib-style trapezoid over threshold-grouped points; sklearn AP
    # is a right-step sum — systematically different estimators, so only a
    # loose agreement is expected
    assert aupr(y, s) == pytest.approx(average_precision_score(y, s), abs=2e-2)


def test_device_auroc_matches_sklearn():
    import jax.numpy as jnp
    from transmogrifai_tpu.metrics_device import masked_auroc
    rng = np.random.default_rng(2)
    y = (rng.random(2500) > 0.5).astype(np.float64)
    s = rng.random(2500).round(2)  # heavy ties → exercises midranks
    got = float(masked_auroc(jnp.asarray(y, jnp.float32),
                             jnp.asarray(s, jnp.float32),
                             jnp.ones(2500, jnp.float32)))
    assert got == pytest.approx(roc_auc_score(y, s), abs=1e-5)


def test_logistic_fit_matches_sklearn():
    from transmogrifai_tpu.models.linear import OpLogisticRegression
    X, y = _binary_data()
    reg = 0.01
    est = OpLogisticRegression(reg_param=reg, elastic_net_param=0.0,
                               max_iter=400, standardization=False)
    fitted = est.fit_arrays(X, y)
    # sklearn C = 1 / (n * reg) for mean-normalized log-loss
    sk = LogisticRegression(C=1.0 / (len(y) * reg), max_iter=2000,
                            tol=1e-10).fit(X, y)
    np.testing.assert_allclose(np.asarray(fitted["coef"]).ravel(),
                               sk.coef_.ravel(), atol=2e-2)
    assert float(np.asarray(fitted["intercept"]).ravel()[0]) == pytest.approx(
        float(sk.intercept_[0]), abs=2e-2)


def test_ridge_fit_matches_sklearn():
    from transmogrifai_tpu.models.linear import OpLinearRegression
    rng = np.random.default_rng(3)
    X = rng.normal(size=(2000, 5)).astype(np.float32)
    w = rng.normal(size=5)
    yv = (X @ w + 0.1 * rng.normal(size=2000)).astype(np.float32)
    reg = 0.1
    est = OpLinearRegression(reg_param=reg, elastic_net_param=0.0,
                             standardization=False)
    fitted = est.fit_arrays(X, yv)
    sk = Ridge(alpha=reg * len(yv)).fit(X, yv)
    np.testing.assert_allclose(np.asarray(fitted["coef"]).ravel(),
                               sk.coef_.ravel(), atol=1e-3)


def test_isotonic_calibrator_matches_sklearn():
    from transmogrifai_tpu.ops.bucketizers import pav_fit
    rng = np.random.default_rng(4)
    x = np.sort(rng.random(500))
    y = np.clip(x + rng.normal(scale=0.1, size=500), 0, 1)
    ours_x, ours_y = pav_fit(x, y)
    sk = IsotonicRegression(out_of_bounds="clip").fit(x, y)
    grid = np.linspace(0, 1, 101)
    ours = np.interp(grid, np.asarray(ours_x), np.asarray(ours_y))
    np.testing.assert_allclose(ours, sk.predict(grid), atol=1e-6)


def test_pearson_spearman_match_scipy():
    # Spearman's rank transform runs INSIDE the fused stats program
    # (spearman=True static arg) — one executable, no host ranking
    # (≙ SanityChecker.scala:535-640 Spearman option)
    scipy_stats = pytest.importorskip("scipy.stats")
    import jax.numpy as jnp
    from transmogrifai_tpu.preparators.sanity_checker import _col_stats
    rng = np.random.default_rng(5)
    X = rng.normal(size=(800, 4)).astype(np.float32)
    X[:, 1] = X[:, 0] ** 3 + 0.2 * rng.normal(size=800)  # monotone nonlinear
    X[:, 3] = np.round(X[:, 3] * 2)  # heavy ties: tie-averaged ranks matter
    y = (X[:, 0] + 0.3 * rng.normal(size=800)).astype(np.float32)
    pearson = np.asarray(_col_stats(jnp.asarray(X), jnp.asarray(y))[4])
    spearman = np.asarray(
        _col_stats(jnp.asarray(X), jnp.asarray(y), spearman=True)[4])
    for j in range(4):
        assert pearson[j] == pytest.approx(
            scipy_stats.pearsonr(X[:, j], y)[0], abs=1e-4)
        assert spearman[j] == pytest.approx(
            scipy_stats.spearmanr(X[:, j], y)[0], abs=1e-4)


def test_spearman_fused_with_contingency_matches_scipy():
    # the grouped-categorical path previously fell back to a separate
    # host-side second pass under spearman; now both ride one program
    scipy_stats = pytest.importorskip("scipy.stats")
    import jax.numpy as jnp
    from transmogrifai_tpu.preparators.sanity_checker import (
        _col_stats_with_contingency)
    rng = np.random.default_rng(6)
    X = rng.normal(size=(500, 3)).astype(np.float32)
    ind = (rng.random((500, 2)) < 0.4).astype(np.float32)  # indicator cols
    Xall = np.concatenate([X, ind], axis=1)
    y = (rng.random(500) < 0.5).astype(np.float32)
    stacked, cont = _col_stats_with_contingency(
        jnp.asarray(Xall), jnp.asarray(y), jnp.asarray([3, 4], jnp.int32),
        jnp.asarray([0.0, 1.0]), spearman=True)
    corr = np.asarray(stacked)[4]
    for j in range(5):
        assert corr[j] == pytest.approx(
            scipy_stats.spearmanr(Xall[:, j], y)[0], abs=1e-4)
    # contingency stays a raw-count contraction: [class, col] sums
    expect = np.stack([Xall[y == c][:, [3, 4]].sum(axis=0) for c in (0, 1)])
    np.testing.assert_allclose(np.asarray(cont), expect, atol=1e-3)


def test_cramers_v_matches_scipy_chi2():
    scipy_stats = pytest.importorskip("scipy.stats")
    from transmogrifai_tpu.utils.stats import contingency_stats
    rng = np.random.default_rng(6)
    table = rng.integers(5, 60, size=(3, 4)).astype(np.float64)
    cs = contingency_stats(table)
    chi2 = scipy_stats.chi2_contingency(table, correction=False)[0]
    n = table.sum()
    k = min(table.shape) - 1
    expected_v = np.sqrt(chi2 / (n * k))
    assert cs.cramers_v == pytest.approx(expected_v, abs=1e-9)
    # the p-value comes from the stdlib-only incomplete-gamma implementation
    # (scipy's import stall was ~2.6 s inside the measured train window)
    expected_p = scipy_stats.chi2_contingency(table, correction=False)[1]
    assert cs.p_value == pytest.approx(expected_p, abs=1e-12)


def test_chi2_sf_matches_scipy_across_regimes():
    scipy_stats = pytest.importorskip("scipy.stats")
    from transmogrifai_tpu.utils.stats import chi2_sf
    for chi in (0.0, 1e-3, 0.5, 1.0, 3.0, 7.88, 40.0, 300.0, 2000.0):
        for dof in (1, 2, 5, 19, 100):
            assert chi2_sf(chi, dof) == pytest.approx(
                float(scipy_stats.chi2.sf(chi, dof)), abs=1e-12)


def test_tree_feature_importances_match_sklearn_direction():
    """Gain-based importances: on planted-signal data the
    top features by accumulated impurity gain must match sklearn's
    gain-based feature_importances_ — and the planted noise features must
    rank at the bottom in both."""
    from sklearn.ensemble import (GradientBoostingClassifier,
                                  RandomForestClassifier)

    from transmogrifai_tpu.models.trees import fit_forest, fit_gbt

    rng = np.random.default_rng(5)
    n, d = 6000, 8
    X = rng.normal(size=(n, d)).astype(np.float32)
    # planted signal: features 0 and 3 dominate, 1 is weak, rest are noise
    logits = 2.0 * X[:, 0] - 1.5 * X[:, 3] + 0.4 * X[:, 1]
    y = (logits + rng.normal(scale=0.5, size=n) > 0).astype(np.float32)

    fitted = fit_forest(X, y, task="classification", n_classes=2,
                        n_trees=20, max_depth=5, max_bins=32,
                        min_instances=5, min_gain=0.0, subsample=1.0,
                        feature_strategy="all", seed=3)
    ours = np.asarray(fitted["feature_gain"], dtype=np.float64)
    assert ours.shape == (d,)
    assert ours.sum() > 0
    skrf = RandomForestClassifier(n_estimators=20, max_depth=5,
                                  max_features=None, random_state=0).fit(X, y)
    # top-2 sets agree, and both rank the planted signals above every noise
    # feature
    assert set(np.argsort(ours)[-2:]) == {0, 3}
    assert set(np.argsort(skrf.feature_importances_)[-2:]) == {0, 3}
    noise = [2, 4, 5, 6, 7]
    assert ours[noise].max() < min(ours[0], ours[3])

    gfit = fit_gbt(X, y, task="classification", n_rounds=15, max_depth=3,
                   max_bins=32, min_instances=5, min_gain=0.0, eta=0.3,
                   lam=1.0, min_child_weight=0.0, seed=3)
    g = np.asarray(gfit["feature_gain"], dtype=np.float64)
    skgb = GradientBoostingClassifier(n_estimators=15, max_depth=3,
                                      random_state=0).fit(X, y)
    assert set(np.argsort(g)[-2:]) == {0, 3}
    assert set(np.argsort(skgb.feature_importances_)[-2:]) == {0, 3}
    assert g[noise].max() < min(g[0], g[3])


def test_family_cv_quality_within_tolerance_of_sklearn():
    """Per-family CV quality pin: the batched (fold x grid)
    RF/GBT fitters must land within tolerance of sklearn's CV AuPR on the
    same folds — a silently-degraded tree fitter fails here even when LR
    wins the selection."""
    from sklearn.ensemble import (GradientBoostingClassifier,
                                  RandomForestClassifier)

    from transmogrifai_tpu.evaluators import Evaluators
    from transmogrifai_tpu.models.trees import (OpGBTClassifier,
                                                OpRandomForestClassifier)

    rng = np.random.default_rng(11)
    n, d = 9000, 10
    X = rng.normal(size=(n, d)).astype(np.float32)
    logits = (X[:, 0] + 0.6 * X[:, 1] * X[:, 2] - 0.4 * X[:, 3] ** 2
              + 0.3 * X[:, 4])
    y = (logits + rng.normal(scale=1.0, size=n) > 0).astype(np.float32)

    folds = np.array_split(rng.permutation(n), 3)
    W = np.zeros((3, n), np.float32)
    for f in range(3):
        for j in range(3):
            if j != f:
                W[f, folds[j]] = 1.0
    ev = Evaluators.BinaryClassification.auPR()

    def our_cv(est, grid_point):
        fitted = est.fit_arrays_grid(X, y, W, [grid_point])
        vals = []
        for f in range(3):
            model = est.model_cls(fitted=fitted[f][0],
                                  **{**est._params, **grid_point})
            pred = model.predict_arrays(X[folds[f]])
            vals.append(ev.evaluate(y[folds[f]], pred))
        return float(np.mean(vals))

    def sk_cv(mk):
        vals = []
        for f in range(3):
            tr = np.concatenate([folds[j] for j in range(3) if j != f])
            m = mk().fit(X[tr], y[tr])
            p = m.predict_proba(X[folds[f]])[:, 1]
            vals.append(average_precision_score(y[folds[f]], p))
        return float(np.mean(vals))

    rf_ours = our_cv(OpRandomForestClassifier(),
                     dict(num_trees=20, max_depth=6,
                          min_instances_per_node=10))
    rf_sk = sk_cv(lambda: RandomForestClassifier(
        n_estimators=20, max_depth=6, min_samples_leaf=10, random_state=0))
    assert rf_ours > rf_sk - 0.05, (rf_ours, rf_sk)

    gbt_ours = our_cv(OpGBTClassifier(),
                      dict(max_iter=20, max_depth=3,
                           min_instances_per_node=10))
    gbt_sk = sk_cv(lambda: GradientBoostingClassifier(
        n_estimators=20, max_depth=3, min_samples_leaf=10, random_state=0))
    assert gbt_ours > gbt_sk - 0.05, (gbt_ours, gbt_sk)


def test_sparse_logistic_fit_matches_sklearn_on_hashed_text():
    """ISSUE 7 golden check: the sparse COO logistic fitter on a hashed
    small-vocab design matrix must match sklearn LogisticRegression fit on
    the SAME matrix densified, and agree with our own dense fitter.

    reg=0.3 keeps the hashed design well-conditioned so FISTA reaches the
    optimum within tolerance (weaker reg on near-collinear hashed columns
    converges too slowly for a coefficient-level golden comparison — the
    probability-level parity below covers that regime)."""
    from transmogrifai_tpu.models.linear import OpLogisticRegression
    from transmogrifai_tpu.sparse.transform import hash_tokens_to_sparse

    rng = np.random.default_rng(12)
    n, H = 1200, 256
    vocab_pos = [f"up{i}" for i in range(40)]
    vocab_neg = [f"dn{i}" for i in range(40)]
    y = rng.integers(0, 2, n).astype(np.float32)
    tokens = []
    for yi in y:
        base = vocab_pos if yi else vocab_neg
        other = vocab_neg if yi else vocab_pos
        toks = list(rng.choice(base, size=4))
        if rng.random() < 0.3:  # label noise so the problem isn't separable
            toks.append(str(rng.choice(other)))
        tokens.append(toks)
    sm = hash_tokens_to_sparse(tokens, H)
    dense = np.asarray(sm.to_dense())

    reg = 0.3
    est = OpLogisticRegression(reg_param=reg, elastic_net_param=0.0,
                               max_iter=2000, tol=1e-9, standardization=False)
    f_sparse = est.fit_arrays(sm, y)
    f_dense = est.fit_arrays(dense, y)
    np.testing.assert_allclose(np.asarray(f_sparse["coef"]).ravel(),
                               np.asarray(f_dense["coef"]).ravel(), atol=1e-5)
    sk = LogisticRegression(C=1.0 / (n * reg), max_iter=4000,
                            tol=1e-11).fit(dense, y)
    np.testing.assert_allclose(np.asarray(f_sparse["coef"]).ravel(),
                               sk.coef_.ravel(), atol=1e-4)
    assert float(np.asarray(f_sparse["intercept"]).ravel()[0]) == \
        pytest.approx(float(sk.intercept_[0]), abs=1e-4)


def test_sparse_pipeline_accuracy_matches_sklearn_hashing_vectorizer():
    """End-to-end hashing-trick parity: our FNV-1a sparse path and sklearn's
    HashingVectorizer+LogisticRegression use different hash functions, so
    bucket layouts differ — but on a small planted vocab both pipelines must
    reach the same training accuracy regime."""
    from sklearn.feature_extraction.text import HashingVectorizer
    from sklearn.pipeline import make_pipeline

    from transmogrifai_tpu.models.linear import OpLogisticRegression
    from transmogrifai_tpu.ops.text import tokenize_text
    from transmogrifai_tpu.sparse.transform import hash_tokens_to_sparse

    rng = np.random.default_rng(13)
    n, H = 900, 512
    vocab_pos = [f"good{i}" for i in range(50)]
    vocab_neg = [f"bad{i}" for i in range(50)]
    y = rng.integers(0, 2, n).astype(np.float32)
    docs = [" ".join(rng.choice(vocab_pos if yi else vocab_neg, size=5))
            for yi in y]

    sm = hash_tokens_to_sparse([tokenize_text(d) for d in docs], H)
    est = OpLogisticRegression(reg_param=0.01, elastic_net_param=0.0,
                               max_iter=200, standardization=False)
    fitted = est.fit_arrays(sm, y)
    margin = (np.asarray(sm @ np.asarray(fitted["coef"], np.float32).ravel())
              + float(np.asarray(fitted["intercept"]).ravel()[0]))
    ours_acc = float(((margin > 0) == (y > 0)).mean())

    sk = make_pipeline(
        HashingVectorizer(n_features=H, alternate_sign=False, norm=None),
        LogisticRegression(C=1.0 / (n * 0.01), max_iter=500))
    sk_acc = float((sk.fit(docs, y).predict(docs) == y).mean())
    assert ours_acc == pytest.approx(sk_acc, abs=0.05)
    assert ours_acc > 0.9

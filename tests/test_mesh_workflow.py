"""Full-workflow mesh parity IN CI: the production path
— RawFeatureFilter + transmogrify over mixed raw types + SanityChecker + CV
selector + compiled score() — trained with the mesh ON and OFF must agree on
dropped features, winning model, and probabilities.  This covers the
SanityChecker/RFF/compiled-score mesh paths in the repo's own suite, so the
evidence doesn't depend on the driver's dryrun artifact.

≙ the reference, where distributed execution is the default substrate for
every stage fit/transform (FitStagesUtil.scala:96) and the SanityChecker's
stat reductions are cluster jobs (SanityChecker.scala:575).
"""

import numpy as np
import pytest

import jax

from transmogrifai_tpu import types as T
from transmogrifai_tpu.columns import Column, ColumnBatch, column_from_values
from transmogrifai_tpu.features import features_from_schema
from transmogrifai_tpu.models.linear import OpLogisticRegression
from transmogrifai_tpu.models.trees import OpGBTClassifier
from transmogrifai_tpu.ops.transmogrify import transmogrify
from transmogrifai_tpu.selector import (BinaryClassificationModelSelector,
                                        ModelCandidate, grid)
from transmogrifai_tpu.workflow import Workflow

N = 512  # 64 rows/device on the 8-device test mesh


def _mixed_batch(seed=7):
    r = np.random.default_rng(seed)
    words = [f"w{i}" for i in range(50)]
    text = np.asarray(
        [None if r.random() < 0.2 else " ".join(r.choice(words, 4))
         for _ in range(N)], object)
    cat = np.asarray(
        [None if r.random() < 0.1 else f"c{r.integers(5)}"
         for _ in range(N)], object)
    rmap = np.empty(N, object)
    for i in range(N):
        rmap[i] = {k: float(r.normal()) for k in ("a", "b")
                   if r.random() < 0.8}
    reals = [None if r.random() < 0.2 else float(r.normal())
             for _ in range(N)]
    y = (r.random(N) < 0.5).astype(np.float32)
    cols = {"label": Column(T.RealNN, y),
            "text": column_from_values(T.Text, text),
            "cat": column_from_values(T.PickList, cat),
            "rmap": Column(T.RealMap, rmap),
            "r0": column_from_values(T.Real, reals)}
    schema = {"label": T.RealNN, "text": T.Text, "cat": T.PickList,
              "rmap": T.RealMap, "r0": T.Real}
    return ColumnBatch(cols, N), schema


def _train_and_score(mesh_flag, monkeypatch):
    monkeypatch.setenv("TRANSMOGRIFAI_TPU_MESH", mesh_flag)
    batch, schema = _mixed_batch()
    label, predictors = features_from_schema(schema, response="label")
    fv = transmogrify(predictors, num_hashes=32)
    checked = label.sanity_check(fv, remove_bad_features=True)
    sel = BinaryClassificationModelSelector(models=[
        ModelCandidate(OpLogisticRegression(),
                       grid(reg_param=[0.01], max_iter=[15]), "LR"),
        ModelCandidate(OpGBTClassifier(),
                       grid(max_iter=[2], max_depth=[2],
                            min_instances_per_node=[1]), "GBT")])
    sel.set_input(label, checked)
    pred = sel.get_output()
    model = (Workflow()
             .set_input_batch(batch)
             .set_result_features(pred)
             .with_raw_feature_filter(min_fill_rate=0.01)
             .train())
    scored = model.score()
    vals = scored[pred.name].values
    # probabilities, not argmax labels: boundary rows may legitimately flip
    # under sharded-reduction reordering
    p = np.asarray(vals.get("probability", vals["prediction"]))
    dropped = sorted(f.name for f in model.blacklisted)
    return p, dropped, model.selected_model.summary.best_model_name


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs the 8-device mesh")
def test_full_workflow_mesh_parity(monkeypatch):
    p_on, dropped_on, best_on = _train_and_score("1", monkeypatch)
    p_off, dropped_off, best_off = _train_and_score("0", monkeypatch)
    assert len(p_on) == N
    assert dropped_on == dropped_off
    assert best_on == best_off
    # sharded reductions reorder float sums; outcomes must still agree
    assert np.allclose(p_on, p_off, atol=1e-3), (
        float(np.abs(p_on - p_off).max()))

"""Phase-level timing of the bench workload: cold (compile) vs warm (execute)
wall for each candidate family's grid fit, plus the feature/sanity DAG.

Usage: python scripts/profile_phases.py [N]
"""

import sys
import time

import numpy as np

sys.path.insert(0, ".")


def t(fn, *a, **k):
    """Time fn to COMPLETION: dispatch is asynchronous, so pull a scalar
    device→host over every array leaf of the result."""
    import jax
    import jax.numpy as jnp

    t0 = time.time()
    out = fn(*a, **k)
    leaves = [l for l in jax.tree.leaves(out) if isinstance(l, jax.Array)]
    if leaves:
        float(jnp.stack([jnp.sum(jnp.asarray(l, jnp.float32).ravel()[:1])
                         for l in leaves]).sum())
    return time.time() - t0, out


def main():
    import jax

    N = int(float(sys.argv[1])) if len(sys.argv) > 1 else 1_000_000
    D = 28
    from bench import make_data
    X, y = make_data(N, D)

    print(f"platform={jax.devices()[0].platform} N={N} D={D}", flush=True)

    from transmogrifai_tpu.models.linear import OpLogisticRegression
    from transmogrifai_tpu.models.trees import (OpGBTClassifier,
                                                OpRandomForestClassifier)

    # 3-fold masks like the validator builds
    rng = np.random.default_rng(42)
    perm = rng.permutation(N)
    folds = np.array_split(perm, 3)
    W = np.zeros((3, N), np.float32)
    for f in range(3):
        for j in range(3):
            if j != f:
                W[f, folds[j]] = 1.0

    y32 = y.astype(np.float32)

    lr = OpLogisticRegression()
    lr_grid = [dict(reg_param=r, elastic_net_param=0.1, max_iter=50)
               for r in (0.001, 0.01, 0.1, 0.2)]
    dt, _ = t(lr.fit_arrays_grid, X, y32, W, lr_grid)
    print(f"LR grid cold: {dt:.1f}s", flush=True)
    dt, _ = t(lr.fit_arrays_grid, X, y32, W, lr_grid)
    print(f"LR grid warm: {dt:.1f}s", flush=True)

    rf = OpRandomForestClassifier()
    rf_grid = [dict(num_trees=20, max_depth=6, min_instances_per_node=10)]
    dt, _ = t(rf.fit_arrays_grid, X, y32, W, rf_grid)
    print(f"RF grid cold: {dt:.1f}s", flush=True)
    dt, _ = t(rf.fit_arrays_grid, X, y32, W, rf_grid)
    print(f"RF grid warm: {dt:.1f}s", flush=True)

    gbt = OpGBTClassifier()
    gbt_grid = [dict(max_iter=20, max_depth=3, min_instances_per_node=10)]
    dt, _ = t(gbt.fit_arrays_grid, X, y32, W, gbt_grid)
    print(f"GBT grid cold: {dt:.1f}s", flush=True)
    dt, _ = t(gbt.fit_arrays_grid, X, y32, W, gbt_grid)
    print(f"GBT grid warm: {dt:.1f}s", flush=True)


if __name__ == "__main__" and "--train" not in sys.argv:
    main()


def profile_train(N=1_000_000, D=28):
    """Run the REAL bench workload with per-phase forced-sync timing."""
    import jax
    import jax.numpy as jnp

    from bench import make_data
    from transmogrifai_tpu import dag as dag_mod
    from transmogrifai_tpu.columns import Column, ColumnBatch
    from transmogrifai_tpu.evaluators import Evaluators
    from transmogrifai_tpu.features import FeatureBuilder
    from transmogrifai_tpu.models.linear import OpLogisticRegression
    from transmogrifai_tpu.models.trees import (OpGBTClassifier,
                                                OpRandomForestClassifier)
    from transmogrifai_tpu.ops.transmogrify import transmogrify
    from transmogrifai_tpu.selector import (BinaryClassificationModelSelector,
                                            ModelCandidate, ModelSelector,
                                            grid)
    from transmogrifai_tpu.types import RealNN
    from transmogrifai_tpu.workflow import Workflow

    def sync(tag, t0):
        # the device stream is in-order: pulling one fresh scalar waits for
        # all previously queued work
        float(jnp.zeros(()).sum())
        print(f"  {tag}: {time.time()-t0:.2f}s", flush=True)

    X, y = make_data(N, D)
    label = FeatureBuilder.RealNN("label").as_response()
    feats = [FeatureBuilder.RealNN(f"f{i}").as_predictor() for i in range(D)]
    checked = label.sanity_check(transmogrify(feats), remove_bad_features=True)
    models = [
        ModelCandidate(OpLogisticRegression(),
                       grid(reg_param=[0.001, 0.01, 0.1, 0.2],
                            elastic_net_param=[0.1], max_iter=[50]), "LR"),
        ModelCandidate(OpRandomForestClassifier(),
                       grid(num_trees=[20], max_depth=[6],
                            min_instances_per_node=[10]), "RF"),
        ModelCandidate(OpGBTClassifier(),
                       grid(max_iter=[20], max_depth=[3],
                            min_instances_per_node=[10]), "GBT"),
    ]
    selector = BinaryClassificationModelSelector(models=models)
    selector.set_input(label, checked)
    pred = selector.get_output()
    cols = {"label": Column(RealNN, y)}
    for i in range(D):
        cols[f"f{i}"] = Column(RealNN, X[:, i])
    batch = ColumnBatch(cols, N)
    wf = Workflow().set_input_batch(batch).set_result_features(pred)

    orig_fit_layer = dag_mod.fit_layer

    def timed_fit_layer(b, layer):
        t0 = time.time()
        out = orig_fit_layer(b, layer)
        names = [type(s).__name__ for s in layer]
        sync(f"fit_layer {names}", t0)
        return out

    dag_mod.fit_layer = timed_fit_layer
    import transmogrifai_tpu.workflow as wf_mod
    wf_mod.fit_layer = timed_fit_layer

    orig_find = ModelSelector.find_best_estimator
    orig_refit = ModelSelector._refit_reusing_grid_executable
    orig_eval_all = ModelSelector._evaluate_all

    def timed_find(self, *a, **k):
        t0 = time.time()
        out = orig_find(self, *a, **k)
        sync("selector.find_best_estimator", t0)
        return out

    def timed_refit(self, *a, **k):
        t0 = time.time()
        out = orig_refit(self, *a, **k)
        sync("selector.refit", t0)
        return out

    def timed_eval_all(self, *a, **k):
        t0 = time.time()
        out = orig_eval_all(self, *a, **k)
        sync("selector.evaluate_all", t0)
        return out

    ModelSelector.find_best_estimator = timed_find
    ModelSelector._refit_reusing_grid_executable = timed_refit
    ModelSelector._evaluate_all = timed_eval_all

    t0 = time.time()
    model = wf.train()
    print(f"TOTAL train: {time.time()-t0:.2f}s", flush=True)
    t0 = time.time()
    m = model.evaluate(Evaluators.BinaryClassification.auROC(), batch=batch)
    print(f"evaluate: {time.time()-t0:.2f}s AuROC={m['AuROC']:.4f}", flush=True)


if __name__ == "__main__" and "--train" in sys.argv:
    _pos = [a for a in sys.argv[1:] if not a.startswith("-")]
    profile_train(N=int(float(_pos[0])) if _pos else 1_000_000)

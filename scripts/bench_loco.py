"""Measure RecordInsightsLOCO: jitted device program vs the legacy host loop
(full X copy per group + per-row python assembly, the round-2 implementation).

Usage: python scripts/bench_loco.py [rows] [cols] [groups]
Prints one JSON line naming the platform it ran on.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def legacy_host_loco(model, X, groups, top_k=20):
    """The round-2 implementation, verbatim semantics: one full matrix copy
    per group, full [N, G] argsort, per-row python dict assembly."""
    def score(Xa):
        pred = model.predict_arrays(Xa)
        prob = pred.get("probability")
        if prob is not None:
            p = np.asarray(prob)
            return p[:, -1] if p.ndim == 2 else p
        return np.asarray(pred["prediction"], dtype=np.float64)

    base = score(X)
    diffs = {}
    for parent, idxs in groups.items():
        Xm = X.copy()
        Xm[:, idxs] = 0.0
        diffs[parent] = base - score(Xm)
    names = list(diffs)
    D = np.stack([diffs[p] for p in names], axis=1)
    order = np.argsort(-np.abs(D), axis=1)
    out = np.empty(len(X), dtype=object)
    k = min(top_k, len(names))
    for i in range(len(X)):
        row = {}
        for j in order[i, :k]:
            row[names[j]] = float(D[i, j])
        out[i] = {p: json.dumps([[p, v]]) for p, v in row.items()}
    return out


def main():
    import jax

    n = int(sys.argv[1]) if len(sys.argv) > 1 else 100_000
    d = int(sys.argv[2]) if len(sys.argv) > 2 else 512
    g = int(sys.argv[3]) if len(sys.argv) > 3 else 128

    from transmogrifai_tpu.columns import Column, ColumnBatch
    from transmogrifai_tpu.features import Feature
    from transmogrifai_tpu.models.linear import OpLogisticRegression
    from transmogrifai_tpu.record_insights import RecordInsightsLOCO
    from transmogrifai_tpu.types import OPVector, RealNN
    from transmogrifai_tpu.vector_meta import VectorColumnMeta, VectorMeta

    rng = np.random.default_rng(0)
    X = rng.normal(size=(n, d)).astype(np.float32)
    beta = rng.normal(size=d).astype(np.float32)
    y = (X @ beta > 0).astype(np.float32)

    per = max(1, d // g)
    meta = VectorMeta("v", [
        VectorColumnMeta(f"raw{i // per}", "Real", index=i) for i in range(d)])
    label = Feature("label", RealNN, True, None, parents=())
    vec = Feature("v", OPVector, False, None, parents=())
    est = OpLogisticRegression(max_iter=20).set_input(label, vec)
    fit_batch = ColumnBatch({"label": Column(RealNN, y),
                             "v": Column(OPVector, X, meta=meta)}, n)
    model = est.fit(fit_batch)

    loco = RecordInsightsLOCO(model=model, top_k=20).set_input(vec)
    groups = loco._groups(meta, d)

    # device program (includes host->device transfer + compile on first call;
    # timed on the second call like a scoring service would run it)
    batch = ColumnBatch({"v": Column(OPVector, X, meta=meta)}, n)
    t0 = time.time()
    out_dev = loco.transform(batch)
    cold = time.time() - t0
    t0 = time.time()
    out_dev = loco.transform(batch)
    warm = time.time() - t0

    t0 = time.time()
    out_host = legacy_host_loco(model, X, groups, top_k=20)
    legacy = time.time() - t0

    r0d = {k: json.loads(v)[0][1] for k, v in out_dev.values[0].items()}
    r0h = {k: json.loads(v)[0][1] for k, v in out_host[0].items()}
    common = set(r0d) & set(r0h)
    max_delta = max(abs(r0d[k] - r0h[k]) for k in common) if common else None

    print(json.dumps({
        "metric": f"RecordInsightsLOCO wall ({n}x{d}, {len(groups)} groups, "
                  f"top-20, {jax.devices()[0].platform})",
        "value": round(warm, 2), "unit": "s",
        "aux": {"device_cold_s": round(cold, 2),
                "device_warm_s": round(warm, 2),
                "legacy_host_loop_s": round(legacy, 2),
                "speedup_vs_legacy": round(legacy / warm, 1),
                "row0_common_topk": len(common),
                "row0_max_abs_delta": max_delta},
    }))


if __name__ == "__main__":
    main()

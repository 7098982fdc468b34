"""CI smoke for the sparse feature subsystem (ISSUE 7): train + score a
5k-row x 50k-hashed-column text workflow in ONE process and assert the
peak RSS stays well under the dense ``[N, num_hashes]`` matrix that the
pre-sparse path would have materialized — the memory bound IS the feature.

Usage:
    python scripts/ci_sparse_smoke.py run OUT_DIR       # train+score+export
    python scripts/ci_sparse_smoke.py validate OUT_DIR  # parse + assert

``run`` writes one JSON line (``sparse-bench.json``) that CI uploads;
``validate`` asserts the planted-vocab accuracy, a non-trivial nnz/density,
and the peak-RSS bound.
"""

import json
import os
import resource
import sys
import time

import numpy as np

# runnable as `python scripts/ci_sparse_smoke.py` from the repo root
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

ROWS = int(os.environ.get("SPARSE_SMOKE_ROWS", "5000"))
HASHES = int(os.environ.get("SPARSE_SMOKE_HASHES", "50000"))
# the 5k x 50k dense equivalent is ~1 GB; the sparse run (including the
# ~250 MB Python+JAX process baseline) must stay under 60% of it
RSS_BOUND_FRACTION = 0.6


def make_sparse_text_columns(n: int, vocab_size: int = 30_000, seed: int = 3):
    """Label-correlated token rows over a large vocabulary (disjoint
    positive/negative halves) + one dense real column."""
    rng = np.random.default_rng(seed)
    half = vocab_size // 2
    vpos = np.asarray([f"pos{i}" for i in range(half)])
    vneg = np.asarray([f"neg{i}" for i in range(half)])
    y = rng.integers(0, 2, n)
    toks_pos = vpos[rng.integers(0, half, size=(n, 8))]
    toks_neg = vneg[rng.integers(0, half, size=(n, 8))]
    txt = np.where(y[:, None] == 1, toks_pos, toks_neg)
    records = [{"label": float(y[i]), "txt": " ".join(txt[i]),
                "x0": float(v)}
               for i, v in enumerate(rng.normal(size=n))]
    return records, y


def run_text_sparse(N: int, num_hashes: int):
    """Sparse hashed-text workload: train + score in ONE process with peak
    memory bounded by nnz, not rows x num_hashes."""
    from transmogrifai_tpu.features import FeatureBuilder
    from transmogrifai_tpu.models.linear import OpLogisticRegression
    from transmogrifai_tpu.ops.transmogrify import transmogrify
    from transmogrifai_tpu.selector import (BinaryClassificationModelSelector,
                                            ModelCandidate, grid)
    from transmogrifai_tpu.sparse.transform import (reset_sparse_stats,
                                                    sparse_stats)
    from transmogrifai_tpu.workflow import Workflow

    records, y = make_sparse_text_columns(N)
    label = FeatureBuilder.RealNN("label").as_response()
    txt = FeatureBuilder.Text("txt").as_predictor()
    x0 = FeatureBuilder.Real("x0").as_predictor()
    fv = transmogrify([txt, x0], num_hashes=num_hashes)
    selector = BinaryClassificationModelSelector(models=[
        ModelCandidate(OpLogisticRegression(),
                       grid(reg_param=[0.01, 0.1], max_iter=[50]),
                       "OpLogisticRegression")])
    selector.set_input(label, fv)
    pred = selector.get_output()

    reset_sparse_stats()
    wf = Workflow().set_input_records(records).set_result_features(pred)
    t0 = time.time()
    model = wf.train()
    train_wall = time.time() - t0
    stats = sparse_stats()

    # compiled scoring in the SAME process — the acceptance bar is one
    # process training AND scoring with nnz-bounded peak memory
    batch = model.generate_raw_data()
    prog = model.score_program()
    t0 = time.time()
    scored = prog(batch)
    pred_vals = np.asarray(scored[pred.name].values["prediction"])
    score_wall = time.time() - t0

    peak_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0
    dense_equiv_mb = N * num_hashes * 4 / 1e6
    return {
        "metric": f"OpWorkflow.train wall (sparse text {N} rows x "
                  f"{num_hashes} hashed cols, 3-fold CV LR grid, cpu)",
        "value": round(train_wall, 2),
        "unit": "s",
        "aux": {
            "rows": N, "num_hashes": num_hashes,
            "train_accuracy": round(float((pred_vals == y).mean()), 4),
            "best_model": model.selected_model.summary.best_model_name,
            "score_wall_s": round(score_wall, 2),
            "score_rows_per_s": round(N / max(score_wall, 1e-9)),
            "nnz_total": stats["nnz_total"],
            "density": round(stats["density"], 6),
            "peak_rss_mb": round(peak_mb, 1),
            "dense_equivalent_mb": round(dense_equiv_mb, 1),
            "rss_vs_dense_equivalent": round(peak_mb / dense_equiv_mb, 4),
        },
    }


def run(out_dir):
    os.makedirs(out_dir, exist_ok=True)
    record = run_text_sparse(ROWS, HASHES)
    path = os.path.join(out_dir, "sparse-bench.json")
    with open(path, "w") as fh:
        fh.write(json.dumps(record) + "\n")
    aux = record["aux"]
    print(f"wrote {path}: train {record['value']}s, "
          f"score {aux['score_wall_s']}s, acc {aux['train_accuracy']}, "
          f"nnz {aux['nnz_total']}, peak RSS {aux['peak_rss_mb']} MB "
          f"vs dense-equivalent {aux['dense_equivalent_mb']} MB")
    return 0


def validate(out_dir):
    with open(os.path.join(out_dir, "sparse-bench.json")) as fh:
        record = json.loads(fh.readline())
    aux = record["aux"]
    assert aux["rows"] == ROWS and aux["num_hashes"] == HASHES, aux
    # planted disjoint pos/neg vocab: the sparse LR must separate it
    assert aux["train_accuracy"] >= 0.99, aux
    assert aux["score_rows_per_s"] > 0, aux
    # the hash block really was sparse: nnz present, density far below 1
    assert aux["nnz_total"] > 0, aux
    assert 0 < aux["density"] < 0.01, aux
    # THE acceptance bound: peak memory scales with nnz, not rows x cols —
    # a dense [N, num_hashes] materialization anywhere in train or score
    # would alone exceed this fraction of the dense-equivalent bytes
    bound_mb = RSS_BOUND_FRACTION * aux["dense_equivalent_mb"]
    assert aux["peak_rss_mb"] < bound_mb, (
        f"peak RSS {aux['peak_rss_mb']} MB >= {bound_mb} MB "
        f"({RSS_BOUND_FRACTION} x dense equivalent "
        f"{aux['dense_equivalent_mb']} MB) — a dense [N, num_hashes] "
        "materialization has crept back into the sparse path")
    print(f"OK: peak RSS {aux['peak_rss_mb']} MB < {bound_mb:.0f} MB bound, "
          f"nnz={aux['nnz_total']}, density={aux['density']}, "
          f"acc={aux['train_accuracy']}")
    return 0


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "run":
        sys.exit(run(sys.argv[2]))
    if len(sys.argv) == 3 and sys.argv[1] == "validate":
        sys.exit(validate(sys.argv[2]))
    sys.exit(f"usage: {sys.argv[0]} run OUT_DIR | validate OUT_DIR")

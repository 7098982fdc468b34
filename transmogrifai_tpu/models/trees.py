"""Tree ensembles — the TPU-native re-design of the reference's Spark MLlib
tree wrappers (core/.../impl/classification/OpRandomForestClassifier.scala:58,
OpGBTClassifier.scala, OpDecisionTreeClassifier.scala, impl/regression/
OpRandomForestRegressor.scala, OpGBTRegressor.scala, OpXGBoostClassifier.scala:47).

Architecture (LightGBM-style, built for the MXU/HBM rather than translated
from Spark's per-partition `findBestSplits`):

* features are quantile-binned once into a compact int matrix ``B [N, D]``
  (int8 when bins fit, else int32) held in
  HBM — every tree/round reuses it;
* trees grow level-wise with **static shapes**: level ``l`` has ``2^l`` nodes,
  per-(node, feature, bin) statistics are built with ``jax.ops.segment_sum``
  scanned over feature chunks (bounded memory), split gains for all bins come
  from one cumulative sum;
* a whole random forest trains as a single XLA program — ``vmap`` over trees
  with Poisson-bootstrap row weights and random feature masks (the TPU
  equivalent of Spark's distributed per-tree jobs, SURVEY.md §2.6 P3);
* gradient boosting scans rounds, computing grad/hess on device and fitting
  each tree to them (XGBoost-style second-order gains).

Trees are stored as perfect-heap arrays (feature, threshold, is_leaf,
leaf_value), so batch prediction is ``max_depth`` gathers — no recursion.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..columns import device_matrix, to_device_f32
from .base import PredictionModel, PredictorEstimator

MAX_BINS_DEFAULT = 32


def mxu_dtype_for(platform: str):
    """Histogram-matmul dtype for a device platform: bf16 hits the MXU on TPU;
    the CPU backend lacks BF16xBF16=F32 dot support, so f32 there."""
    return jnp.float32 if platform == "cpu" else jnp.bfloat16


def _mxu_dtype():
    """Default histogram dtype from the process-global backend.
    Computations pinned to an explicit mesh should instead pass
    ``hist_dtype=mxu_dtype_for(<mesh platform>)``."""
    return mxu_dtype_for(jax.default_backend())


# --------------------------------------------------------------------------
# binning
# --------------------------------------------------------------------------

# (weakref(X), {max_bins: (splits, B)}) keyed by id(X): every tree family in
# a CV grid shares ONE binned matrix per (matrix, max_bins) instead of each
# building its own — at 11M rows a duplicate B is ~0.3 GB of HBM and a full
# binning pass, and cumulative residency is what exhausts HBM.  Entries drop
# when the feature matrix is collected.
_SHARED_BINS: Dict[int, Any] = {}

# id(X) → (weakref(X), n_real) for zero-weight-padded matrices: the sweep's
# mesh placement (tuning.Placement.lay_matrix) appends all-zero rows whose
# fold weight is 0 everywhere.  Every tree statistic is sample-weighted, so
# those rows already contribute nothing to fits — but the UNWEIGHTED
# quantile sketch in build_bin_splits would see them as a spike at 0 and
# shift every split point.  Registering the true row count keeps padded
# binning bit-identical to the unpadded fit.
_REAL_ROWS: Dict[int, Any] = {}


def register_real_rows(X, n_real: int) -> None:
    """Mark ``X`` as padded: only its first ``n_real`` rows are data."""
    import weakref
    key = id(X)
    try:
        ref = weakref.ref(X, lambda _r, _k=key: _REAL_ROWS.pop(_k, None))
    except TypeError:
        return
    _REAL_ROWS[key] = (ref, int(n_real))


def real_rows(X) -> int:
    """The number of true data rows in ``X`` (== len(X) unless padded)."""
    ent = _REAL_ROWS.get(id(X))
    if ent is not None and ent[0]() is X:
        return min(int(ent[1]), X.shape[0])
    return X.shape[0]


def shared_binned(X, max_bins: int):
    """(splits, B) for a device matrix, cached across model families."""
    import weakref

    key = id(X)
    ent = _SHARED_BINS.get(key)
    if ent is not None and ent[0]() is X and max_bins in ent[1]:
        return ent[1][max_bins]
    Xj = device_matrix(X)
    sp = build_bin_splits(X, max_bins)
    B = bin_data(Xj, jnp.asarray(sp))
    if ent is None or ent[0]() is not X:
        try:
            ref = weakref.ref(X, lambda _r, _k=key: _SHARED_BINS.pop(_k, None))
        except TypeError:
            return sp, B
        ent = (ref, {})
        _SHARED_BINS[key] = ent
    ent[1][max_bins] = (sp, B)
    return sp, B


def build_bin_splits(X: np.ndarray, max_bins: int = MAX_BINS_DEFAULT) -> np.ndarray:
    """Per-feature quantile split points → [D, max_bins-1] float32, padded
    with +inf (≙ Spark's findSplits quantile sketch).  Device-resident inputs
    are quantiled on device — only the tiny [D, B] result crosses the link."""
    n, d = X.shape
    qs = np.linspace(0, 1, max_bins + 1)[1:-1]
    # padded matrices: sketch quantiles over the true rows only (the
    # zero-weight padding tail would otherwise shift every split point)
    n_q = real_rows(X)
    Xq = X[:n_q] if n_q < n else X
    if isinstance(X, jax.Array):
        splits = np.asarray(jnp.quantile(
            Xq, jnp.asarray(qs, jnp.float32), axis=0)).T.astype(np.float32)
    else:
        splits = np.quantile(Xq, qs, axis=0).T.astype(np.float32)  # [D, max_bins-1]
    # dedupe per row; pad with +inf so empty bins are harmless
    out = np.full((d, max_bins - 1), np.inf, dtype=np.float32)
    for j in range(d):
        u = np.unique(splits[j])
        u = u[np.isfinite(u)]
        out[j, :len(u)] = u
    return out


@jax.jit
def bin_data(X: jnp.ndarray, splits: jnp.ndarray) -> jnp.ndarray:
    """bin b of x = number of split points < x  → int32 [N, D].

    Chunked over rows: the one-shot broadcast materializes an [N, D, bins]
    boolean — ~9.5 GB at 11M x 28 x 31, which hard-faults a 16 GB worker.
    Row chunks keep the transient under ~1 GB while producing the same
    device-resident [N, D] result.  Bin ids store as int8 when they fit
    (max_bins ≤ 127 always holds for the reference's MaxBin=32 default) —
    the binned matrix and its padded/chunked views are the largest resident
    tree buffers at 10M+ rows."""
    n, d = X.shape
    nb = splits.shape[1]
    dt = jnp.int8 if nb < 127 else jnp.int32
    limit = 1 << 28                      # transient bool elements per chunk
    rows = max(1, limit // max(d * nb, 1))
    if n <= rows:
        return jnp.sum(X[:, :, None] > splits[None, :, :],
                       axis=-1).astype(dt)
    # lax.map keeps the traced body constant-size regardless of N (a python
    # loop of slices would grow the HLO linearly with the chunk count)
    n_blocks = -(-n // rows)
    pad = n_blocks * rows - n
    Xp = jnp.pad(X, ((0, pad), (0, 0))).reshape(n_blocks, rows, d)
    out = jax.lax.map(
        lambda xb: jnp.sum(xb[:, :, None] > splits[None, :, :],
                           axis=-1).astype(dt), Xp)
    return out.reshape(n_blocks * rows, d)[:n]


# --------------------------------------------------------------------------
# single-tree fit (jittable, vmappable over trees)
# --------------------------------------------------------------------------

class TreeArrays(NamedTuple):
    feature: jnp.ndarray    # [T] int32 (split feature; -1 at pure leaves)
    threshold: jnp.ndarray  # [T] float32 (raw split threshold)
    is_leaf: jnp.ndarray    # [T] bool
    leaf: jnp.ndarray       # [T, V] float32 leaf values
    gain: jnp.ndarray       # [D] per-feature impurity-gain sum over splits
                            # (count-weighted, ≙ Spark featureImportances /
                            # ModelInsights.scala:74-392 contributions)


def _gain_variance(left, right, parent, lam):
    """Variance-impurity gain (Spark 'variance'); stats = [count, wy, wy2]."""
    def sse(s):
        cnt = jnp.maximum(s[..., 0], 1e-12)
        return s[..., 2] - s[..., 1] ** 2 / cnt
    return sse(parent) - sse(left) - sse(right)


def _gain_gini(left, right, parent, lam):
    """Gini-impurity gain; stats = [count, class_0 .. class_{C-1}]."""
    def wgini(s):
        cnt = jnp.maximum(s[..., 0], 1e-12)
        return cnt * (1.0 - jnp.sum((s[..., 1:] / cnt[..., None]) ** 2, axis=-1))
    return wgini(parent) - wgini(left) - wgini(right)


def _gain_xgb(left, right, parent, lam):
    """Second-order gain; stats = [count, G, H]."""
    def score(s):
        return s[..., 1] ** 2 / (s[..., 2] + lam)
    return 0.5 * (score(left) + score(right) - score(parent))


_GAINS = {"variance": _gain_variance, "gini": _gain_gini, "xgb": _gain_xgb}


def _leaf_variance(s):
    return (s[..., 1:2] / jnp.maximum(s[..., 0:1], 1e-12))


def _leaf_gini(s):
    return s[..., 1:] / jnp.maximum(s[..., 0:1], 1e-12)


def _leaf_xgb(s, lam=1.0):
    return -(s[..., 1:2] / (s[..., 2:3] + lam))


def fit_tree(B: jnp.ndarray, splits: jnp.ndarray, stats: jnp.ndarray,
             feature_mask: jnp.ndarray, *, impurity: str, max_depth: int,
             n_bins: int, min_instances: jnp.ndarray, min_gain: jnp.ndarray,
             lam: jnp.ndarray, chunk: "Optional[int]" = None,
             hist_dtype=None, node_feature_key=None,
             features_per_node: "Optional[int]" = None) -> TreeArrays:
    """Grow one tree level-wise on binned data (see ``_fit_tree_unrolled``).

    Dispatches to a compact ``fori_loop``-over-levels implementation when the
    whole tree fits the matmul-histogram path (``max_depth <= 7``): one traced
    level body instead of ``max_depth`` unrolled ones → ~6x smaller HLO, which
    is what dominates wall-clock here (XLA compile + executable (de)serial-
    isation far outweigh device execution for these programs)."""
    S = stats.shape[1]
    P_n = max(1, 2 ** (max_depth - 1))
    if max_depth <= 7 and P_n * S <= 256:
        return _fit_tree_compact(
            B, splits, stats, feature_mask, impurity=impurity,
            max_depth=max_depth, n_bins=n_bins, min_instances=min_instances,
            min_gain=min_gain, lam=lam, chunk=chunk, hist_dtype=hist_dtype,
            node_feature_key=node_feature_key,
            features_per_node=features_per_node)
    return _fit_tree_unrolled(
        B, splits, stats, feature_mask, impurity=impurity,
        max_depth=max_depth, n_bins=n_bins, min_instances=min_instances,
        min_gain=min_gain, lam=lam, chunk=chunk, hist_dtype=hist_dtype,
        node_feature_key=node_feature_key, features_per_node=features_per_node)


def _chunk_prologue(B, feature_mask, splits, n_bins, chunk):
    """Shared feature-chunking prologue of the tree fitters: pad D to a chunk
    multiple and expose [n_chunks, chunk, N] views (bounds the one-hot
    histogram working set to ~chunk * N * n_bins bf16 per lane)."""
    N, D = B.shape
    if chunk is None:
        chunk = max(1, min(32, (512 << 20) // max(N * n_bins * 2, 1)))
    n_chunks = math.ceil(D / chunk)
    D_pad = n_chunks * chunk
    pad = D_pad - D
    B_pad = jnp.pad(B, ((0, 0), (0, pad)))                   # [N, D_pad]
    fmask = jnp.pad(feature_mask, (0, pad))                  # [D_pad]
    B_chunks = B_pad.T.reshape(n_chunks, chunk, N)
    m_chunks = fmask.reshape(n_chunks, chunk)
    splits_pad = (jnp.pad(splits, ((0, pad), (0, 0)), constant_values=np.inf)
                  if pad else splits)
    base_idxs = jnp.arange(n_chunks, dtype=jnp.int32) * chunk
    return (chunk, n_chunks, D_pad, pad, B_pad, fmask, B_chunks, m_chunks,
            splits_pad, base_idxs)


def _fit_tree_compact(B: jnp.ndarray, splits: jnp.ndarray, stats: jnp.ndarray,
                      feature_mask: jnp.ndarray, *, impurity: str,
                      max_depth: int, n_bins: int, min_instances: jnp.ndarray,
                      min_gain: jnp.ndarray, lam: jnp.ndarray,
                      chunk: "Optional[int]" = None, hist_dtype=None,
                      node_feature_key=None,
                      features_per_node: "Optional[int]" = None) -> TreeArrays:
    """``fit_tree`` with ONE traced level body under ``lax.fori_loop``.

    Rows carry their node as a HEAP id; every level works on a fixed padded
    node window of ``P_n = 2^(max_depth-1)`` slots starting at the level
    offset.  Writes use ``dynamic_update_slice`` of static size ``P_n`` at the
    (traced) offset — a level may scribble into the next level's slots, but
    each heap slot's OWN level is always the last writer, so the final arrays
    are exact.  Rows whose node became a leaf simply keep a node id below the
    current level offset and drop out of the one-hot contractions.
    """
    N, D = B.shape
    S = stats.shape[1]
    gain_fn = _GAINS[impurity]
    leaf_fn = {"variance": _leaf_variance, "gini": _leaf_gini,
               "xgb": lambda s: _leaf_xgb(s, lam)}[impurity]
    V = {"variance": 1, "gini": S - 1, "xgb": 1}[impurity]
    T = 2 ** (max_depth + 1) - 1
    P_n = max(1, 2 ** (max_depth - 1))
    mxu = hist_dtype if hist_dtype is not None else _mxu_dtype()

    (chunk, n_chunks, D_pad, pad, B_pad, fmask, B_chunks, m_chunks,
     splits_pad, base_idxs) = _chunk_prologue(B, feature_mask, splits,
                                              n_bins, chunk)
    subset = (node_feature_key is not None and features_per_node is not None
              and features_per_node < D)

    def level_body(lvl, carry):
        feat_arr, thr_arr, leaf_flag, leaf_val, row_node, gain_acc = carry
        offset = (1 << lvl) - 1                              # traced
        nodes = offset + jnp.arange(P_n, dtype=jnp.int32)
        # routing one-hot in MXU dtype: [N, P_n] is GBs at 10M+ rows and
        # deep windows; 0/1 is exact in bf16 and both consumers accumulate f32
        oh = (row_node[:, None] == nodes[None, :]).astype(mxu)
        node_stats = jnp.einsum("np,ns->ps", oh, stats,
                                preferred_element_type=jnp.float32)
        lv = leaf_fn(node_stats).astype(jnp.float32)
        leaf_val2 = jax.lax.dynamic_update_slice(leaf_val, lv, (offset, 0))

        if subset:
            kl = jax.random.fold_in(node_feature_key, lvl)
            scores = jax.random.uniform(kl, (P_n, D_pad))
            scores = jnp.where(fmask[None, :] > 0, scores, jnp.inf)
            kth = jnp.sort(scores, axis=1)[:, features_per_node - 1][:, None]
            nm_chunks = (scores <= kth).T.reshape(n_chunks, chunk, P_n)
        else:
            nm_chunks = jnp.ones((n_chunks, chunk, P_n), bool)

        P = (oh[:, :, None] * stats[:, None, :]).reshape(
            N, P_n * S).astype(mxu)

        def scan_chunk(c, xs):
            best_gain, best_feat, best_bin = c
            bc, mc, nmc, base_idx = xs
            ohb = (bc[:, :, None] == jnp.arange(n_bins)[None, None, :]
                   ).astype(mxu)                             # [chunk, N, n_bins]
            hist = jnp.einsum("cnb,nk->ckb", ohb, P,
                              preferred_element_type=jnp.float32)
            hist = hist.reshape(chunk, P_n, S, n_bins).transpose(0, 1, 3, 2)
            left = jnp.cumsum(hist, axis=2)                  # [chunk, P_n, n_bins, S]
            right = node_stats[None, :, None, :] - left
            gains = gain_fn(left, right, node_stats[None, :, None, :], lam)
            ok = ((left[..., 0] >= min_instances) &
                  (right[..., 0] >= min_instances) &
                  mc[:, None, None] & nmc[:, :, None] &
                  (jnp.arange(n_bins)[None, None, :] < n_bins - 1))
            gains = jnp.where(ok, gains, -jnp.inf)           # [chunk, P_n, n_bins]
            cg = jnp.max(gains, axis=2)
            cb = jnp.argmax(gains, axis=2).astype(jnp.int32)
            fg = jnp.max(cg, axis=0)                         # [P_n]
            fi = jnp.argmax(cg, axis=0)
            fb = jnp.take_along_axis(cb, fi[None, :], axis=0)[0]
            better = fg > best_gain
            best_gain = jnp.where(better, fg, best_gain)
            best_feat = jnp.where(better, base_idx + fi.astype(jnp.int32),
                                  best_feat)
            best_bin = jnp.where(better, fb, best_bin)
            return (best_gain, best_feat, best_bin), None

        init = (jnp.full((P_n,), -jnp.inf, jnp.float32),
                jnp.zeros((P_n,), jnp.int32), jnp.zeros((P_n,), jnp.int32))
        (best_gain, best_feat, best_bin), _ = jax.lax.scan(
            scan_chunk, init, (B_chunks, m_chunks, nm_chunks, base_idxs))

        node_is_leaf = (best_gain <= min_gain) | (~jnp.isfinite(best_gain))
        thr = splits_pad[best_feat,
                         jnp.clip(best_bin, 0, splits.shape[1] - 1)]
        feat_arr2 = jax.lax.dynamic_update_slice(
            feat_arr, jnp.where(node_is_leaf, -1, best_feat), (offset,))
        thr_arr2 = jax.lax.dynamic_update_slice(thr_arr, thr, (offset,))
        leaf_flag2 = jax.lax.dynamic_update_slice(
            leaf_flag, node_is_leaf, (offset,))

        # route rows through their node's split (one-hot contractions; rows
        # not at this level match nothing and stay put)
        f_of_row = (oh @ best_feat.astype(jnp.float32)).astype(jnp.int32)
        bin_of_row = oh @ best_bin.astype(jnp.float32)
        dead_of_row = oh @ node_is_leaf.astype(jnp.float32)
        at_level = jnp.sum(oh.astype(jnp.float32), axis=1) > 0.5
        # per-feature gain accumulation for importances: only nodes that
        # actually split contribute (zero-row window slots and pruned nodes
        # carry -inf/min gains and are excluded by node_is_leaf)
        gain_acc2 = gain_acc.at[best_feat].add(
            jnp.where(node_is_leaf, 0.0, best_gain))
        # per-row bin of the split feature: a [N] gather beats the [N, D]
        # one-hot einsum it replaces (two full-matrix f32 transients)
        b_of_row = jnp.take_along_axis(
            B_pad, f_of_row[:, None], axis=1)[:, 0].astype(jnp.float32)
        go_right = (b_of_row > bin_of_row).astype(jnp.int32)
        child = 2 * row_node + 1 + go_right
        advance = at_level & (dead_of_row < 0.5)
        row_node2 = jnp.where(advance, child, row_node)
        return (feat_arr2, thr_arr2, leaf_flag2, leaf_val2, row_node2,
                gain_acc2)

    init = (jnp.full((T,), -1, jnp.int32),
            jnp.full((T,), jnp.inf, jnp.float32),
            jnp.zeros((T,), bool),
            jnp.zeros((T, V), jnp.float32),
            jnp.zeros((N,), jnp.int32),
            jnp.zeros((D_pad,), jnp.float32))
    (feat_arr, thr_arr, leaf_flag, leaf_val, row_node,
     gain_acc) = jax.lax.fori_loop(0, max_depth, level_body, init)

    # epilogue: the bottom level is all leaves (static offset/shape)
    n_last = 2 ** max_depth
    off = n_last - 1
    nodes = off + jnp.arange(n_last, dtype=jnp.int32)
    oh = (row_node[:, None] == nodes[None, :]).astype(mxu)
    node_stats = jnp.einsum("np,ns->ps", oh, stats,
                            preferred_element_type=jnp.float32)
    lv = leaf_fn(node_stats).astype(jnp.float32)
    leaf_val = leaf_val.at[off:].set(lv)
    leaf_flag = leaf_flag.at[off:].set(True)
    feat_arr = feat_arr.at[off:].set(-1)
    thr_arr = thr_arr.at[off:].set(jnp.inf)
    return TreeArrays(feat_arr, thr_arr, leaf_flag, leaf_val, gain_acc[:D])


def _fit_tree_unrolled(B: jnp.ndarray, splits: jnp.ndarray, stats: jnp.ndarray,
                       feature_mask: jnp.ndarray, *, impurity: str,
                       max_depth: int, n_bins: int, min_instances: jnp.ndarray,
                       min_gain: jnp.ndarray, lam: jnp.ndarray,
                       chunk: "Optional[int]" = None, hist_dtype=None,
                       node_feature_key=None,
                       features_per_node: "Optional[int]" = None) -> TreeArrays:
    """Grow one tree level-wise on binned data.

    B [N, D] int (int8/int32 bin ids); stats [N, S] pre-weighted per-row statistics (col 0 must be
    the row weight/count); feature_mask [D] 0/1.  Returns perfect-heap arrays
    with ``T = 2^(max_depth+1) - 1`` nodes.

    ``node_feature_key`` + ``features_per_node`` enable random-forest PER-NODE
    feature subsetting (Spark's featureSubsetStrategy / sklearn max_features
    semantics): every node at every level draws its own candidate-feature set.
    Restricting whole TREES to a feature subset instead cripples interaction
    learning — with D features and k per tree, almost no tree holds all the
    interacting features together.

    Histogram strategy (the TPU-critical choice): for shallow levels
    (``n_l * S <= 256``) the per-(node, feature, bin) stats come from one bf16
    matmul on the MXU — ``(onehot_node x stats)^T @ onehot_bins`` — instead of
    scatter-adds, which XLA lowers to sorts on TPU.  Deep levels (only
    ``max_depth > 7``-ish trees reach them) fall back to per-stat segment-sums.

    ``hist_dtype`` pins the histogram-matmul dtype; callers running on an
    explicit device mesh should pass ``mxu_dtype_for(platform)`` of the mesh's
    platform — the default consults the process-global default backend, which
    can differ from the mesh (e.g. a CPU mesh under a TPU default backend).
    """
    N, D = B.shape
    S = stats.shape[1]
    gain_fn = _GAINS[impurity]
    leaf_fn = {"variance": _leaf_variance, "gini": _leaf_gini,
               "xgb": lambda s: _leaf_xgb(s, lam)}[impurity]
    V = {"variance": 1, "gini": S - 1, "xgb": 1}[impurity]
    T = 2 ** (max_depth + 1) - 1

    (chunk, n_chunks, D_pad, pad, B_pad, fmask, B_chunks, m_chunks,
     splits_pad, base_idxs) = _chunk_prologue(B, feature_mask, splits,
                                              n_bins, chunk)

    feat_arr = jnp.full((T,), -1, jnp.int32)
    thr_arr = jnp.full((T,), jnp.inf, jnp.float32)
    leaf_flag = jnp.zeros((T,), bool)
    leaf_val = jnp.zeros((T, V), jnp.float32)

    row_node = jnp.zeros((N,), jnp.int32)
    parent_dead = jnp.zeros((1,), bool)  # nodes whose ancestor is a leaf
    gain_acc = jnp.zeros((D_pad,), jnp.float32)

    for level in range(max_depth + 1):
        n_l = 2 ** level
        offset = n_l - 1
        if n_l <= 128:
            # one-hot matmul instead of segment_sum: TPU lowers scatter-adds
            # to sorts and the gather/scatter forms compile pathologically
            oh_stats = (row_node[:, None] == jnp.arange(n_l)[None, :]
                        ).astype(jnp.float32)
            node_stats = jnp.einsum("nk,ns->ks", oh_stats, stats)
        else:
            node_stats = jax.ops.segment_sum(stats, row_node,
                                             num_segments=n_l)
        lv = leaf_fn(node_stats)
        leaf_val = jax.lax.dynamic_update_slice(leaf_val, lv.astype(jnp.float32),
                                                (offset, 0))
        if level == max_depth:
            leaf_flag = jax.lax.dynamic_update_slice(
                leaf_flag, jnp.ones((n_l,), bool), (offset,))
            break

        use_matmul = n_l * S <= 256
        mxu = hist_dtype if hist_dtype is not None else _mxu_dtype()
        # per-node candidate-feature masks [n_chunks, chunk, n_l]: each node
        # draws its own subset (uniform scores, k-th order-statistic cut)
        if (node_feature_key is not None and features_per_node is not None
                and features_per_node < D):
            kl = jax.random.fold_in(node_feature_key, level)
            scores = jax.random.uniform(kl, (n_l, D_pad))
            scores = jnp.where(fmask[None, :] > 0, scores, jnp.inf)
            kth = jnp.sort(scores, axis=1)[:, features_per_node - 1][:, None]
            node_mask = scores <= kth                        # [n_l, D_pad]
            nm_chunks = node_mask.T.reshape(n_chunks, chunk, n_l)
        else:
            nm_chunks = jnp.ones((n_chunks, chunk, n_l), bool)
        if use_matmul:
            # P [N, n_l*S]: each row's stats routed to its node's slot;
            # the histogram then is one MXU matmul against one-hot bins
            oh_node = row_node[:, None] == jnp.arange(n_l)[None, :]
            P = (oh_node[:, :, None] * stats[:, None, :]).reshape(
                N, n_l * S).astype(mxu)

        def chunk_hist(bc):
            """[chunk, N] bins → [chunk, n_l, n_bins, S] histogram."""
            if use_matmul:
                oh = (bc[:, :, None] == jnp.arange(n_bins)[None, None, :]
                      ).astype(mxu)                          # [chunk, N, n_bins]
                hist = jnp.einsum("cnb,nk->ckb", oh, P,
                                  preferred_element_type=jnp.float32)
                return hist.reshape(chunk, n_l, S, n_bins).transpose(0, 1, 3, 2)
            seg = row_node[None, :] * n_bins + bc            # [chunk, N]

            # one 1-D segment-sum per stat component: every large tensor here
            # is [chunk, N] (N minormost), never [.., S] — a small-S minormost
            # dim would be padded to the 128-lane TPU tile (42x HBM blowup)
            def hist_for_stat(srow):
                return jax.vmap(lambda ids: jax.ops.segment_sum(
                    srow, ids, num_segments=n_l * n_bins))(seg)  # [chunk, nlb]

            hist = jnp.stack([hist_for_stat(stats[:, s]) for s in range(S)],
                             axis=-1)                        # [chunk, nlb, S]
            return hist.reshape(chunk, n_l, n_bins, S)

        def scan_chunk(carry, xs):
            best_gain, best_feat, best_bin = carry
            bc, mc, nmc, base_idx = xs      # [chunk, N], [chunk], [chunk, n_l]
            hist = chunk_hist(bc)
            left = jnp.cumsum(hist, axis=2)                  # [chunk, n_l, n_bins, S]
            right = node_stats[None, :, None, :] - left
            gains = gain_fn(left, right, node_stats[None, :, None, :], lam)
            ok = ((left[..., 0] >= min_instances) &
                  (right[..., 0] >= min_instances) &
                  mc[:, None, None] & nmc[:, :, None] &
                  (jnp.arange(n_bins)[None, None, :] < n_bins - 1))
            gains = jnp.where(ok, gains, -jnp.inf)           # [chunk, n_l, n_bins]
            cg = jnp.max(gains, axis=2)                      # [chunk, n_l]
            cb = jnp.argmax(gains, axis=2).astype(jnp.int32)
            fg = jnp.max(cg, axis=0)                         # [n_l]
            fi = jnp.argmax(cg, axis=0)                      # [n_l] chunk-local feat
            fb = jnp.take_along_axis(cb, fi[None, :], axis=0)[0]
            better = fg > best_gain
            best_gain = jnp.where(better, fg, best_gain)
            best_feat = jnp.where(better, base_idx + fi.astype(jnp.int32), best_feat)
            best_bin = jnp.where(better, fb, best_bin)
            return (best_gain, best_feat, best_bin), None

        init = (jnp.full((n_l,), -jnp.inf, jnp.float32),
                jnp.zeros((n_l,), jnp.int32), jnp.zeros((n_l,), jnp.int32))
        (best_gain, best_feat, best_bin), _ = jax.lax.scan(
            scan_chunk, init, (B_chunks, m_chunks, nm_chunks, base_idxs))

        node_is_leaf = (best_gain <= min_gain) | (~jnp.isfinite(best_gain)) | parent_dead
        gain_acc = gain_acc.at[best_feat].add(
            jnp.where(node_is_leaf, 0.0, best_gain))
        thr = splits_pad[best_feat, jnp.clip(best_bin, 0, splits.shape[1] - 1)]
        feat_arr = jax.lax.dynamic_update_slice(
            feat_arr, jnp.where(node_is_leaf, -1, best_feat), (offset,))
        thr_arr = jax.lax.dynamic_update_slice(thr_arr, thr, (offset,))
        leaf_flag = jax.lax.dynamic_update_slice(leaf_flag, node_is_leaf, (offset,))

        # route rows: bin(feature of my node) > split bin → right child.
        # All lookups are fused one-hot contractions — no per-row gathers
        # (same TPU pathology as in predict_trees_raw); bins/feat ids are
        # small integers, exact in float32
        oh_rows = (row_node[:, None] == jnp.arange(n_l)[None, :]
                   ).astype(jnp.float32)
        f_of_row = (oh_rows @ best_feat.astype(jnp.float32)).astype(jnp.int32)
        bin_of_row = oh_rows @ best_bin.astype(jnp.float32)
        f_oh = (f_of_row[:, None] == jnp.arange(D_pad)[None, :]
                ).astype(jnp.float32)
        b_of_row = jnp.einsum("nd,nd->n", f_oh, B_pad.astype(jnp.float32))
        go_right = b_of_row > bin_of_row
        row_node = 2 * row_node + go_right.astype(jnp.int32)
        parent_dead = jnp.repeat(node_is_leaf, 2)

    return TreeArrays(feat_arr, thr_arr, leaf_flag, leaf_val, gain_acc[:D])


@functools.partial(jax.jit, static_argnames=("max_depth",))
def predict_trees_raw(X: jnp.ndarray, feature: jnp.ndarray, threshold: jnp.ndarray,
                      is_leaf: jnp.ndarray, leaf: jnp.ndarray,
                      max_depth: int) -> jnp.ndarray:
    """Batch prediction over an ensemble on raw features — row-chunked via
    ``lax.map`` above ~1M rows so the per-step working set stays bounded
    regardless of N (the fused one-hot walk is cheap per block; very large
    single dispatches have crashed the worker on marginal links).
    feature/threshold/is_leaf: [Tr, T]; leaf: [Tr, T, V].
    Returns [N, Tr, V] leaf values (caller aggregates).

    TPU note: per-(row, tree) dynamic gathers (``take_along_axis``) lower to
    scalar gather loops and compile/run pathologically on TPU, so every node
    lookup is expressed as a one-hot contraction instead — the comparison
    one-hots fuse into the reductions, nothing of size [N, Tr, T] is
    materialized, and the MXU/VPU do the work (measured: ~100x faster compile
    AND faster steady-state than the gather form at 1Mx28, 20 trees)."""
    return _row_blocked(
        lambda xb: _predict_trees_block(xb, feature, threshold, is_leaf,
                                        leaf, max_depth), X)


def _row_blocked(per_block_fn, X: jnp.ndarray):
    """Apply ``per_block_fn`` over row blocks of ``X`` via ``lax.map`` when N
    exceeds the block size — the shared scaffold of the ensemble predictors
    (one traced body regardless of N; very large single dispatches have
    crashed the worker on marginal links)."""
    N = X.shape[0]
    BLOCK = 1 << 20
    if N <= BLOCK:
        return per_block_fn(X)
    n_blocks = -(-N // BLOCK)
    pad = n_blocks * BLOCK - N
    Xp = jnp.pad(X, ((0, pad), (0, 0))).reshape(n_blocks, BLOCK, X.shape[1])
    out = jax.lax.map(per_block_fn, Xp)
    return out.reshape((n_blocks * BLOCK,) + out.shape[2:])[:N]


@functools.partial(jax.jit, static_argnames=("max_depth", "members"))
def predict_trees_sum_grouped(X: jnp.ndarray, feature: jnp.ndarray,
                              threshold: jnp.ndarray, is_leaf: jnp.ndarray,
                              leaf: jnp.ndarray, max_depth: int,
                              members: int) -> jnp.ndarray:
    """Leaf SUMS for ``members`` tree ensembles at once → [N, members, V].

    The tree arrays are the members' stacks concatenated along the tree
    axis (equal trees-per-member).  One program replaces one predict
    dispatch per CV candidate; sums are rank-equivalent to each member's
    probability/margin (gini leaves sum to 1 per tree; GBT margins are a
    positive affine map of the leaf sum), which is all AUC metrics need."""
    T_total = feature.shape[0]
    per = T_total // members

    def blk(xb):
        lv = _predict_trees_block(xb, feature, threshold, is_leaf, leaf,
                                  max_depth)                 # [B, T, V]
        return lv.reshape(lv.shape[0], members, per,
                          lv.shape[-1]).sum(axis=2)          # [B, K, V]

    return _row_blocked(blk, X)


@functools.partial(jax.jit, static_argnames=("max_depth", "op"))
def predict_trees_agg(X: jnp.ndarray, feature: jnp.ndarray,
                      threshold: jnp.ndarray, is_leaf: jnp.ndarray,
                      leaf: jnp.ndarray, max_depth: int,
                      op: str = "mean") -> jnp.ndarray:
    """``predict_trees_raw`` with the tree axis reduced INSIDE each row
    block → [N, V].  The ensemble-score consumers only ever need the
    aggregate; materializing the full [N, Tr, V] leaf tensor costs
    Tr-times the HBM (≈1.8 GB at 11M x 20 trees x 2 classes) and is what
    pushed the near-capacity worker over during CV metric evaluation."""
    def blk(xb):
        lv = _predict_trees_block(xb, feature, threshold, is_leaf, leaf,
                                  max_depth)                   # [B, Tr, V]
        return lv.mean(axis=1) if op == "mean" else lv.sum(axis=1)

    return _row_blocked(blk, X)


def _predict_trees_block(X, feature, threshold, is_leaf, leaf,
                         max_depth: int):
    T = feature.shape[1]
    D = X.shape[1]
    dt = X.dtype
    k_iota = jnp.arange(T, dtype=jnp.int32)
    d_iota = jnp.arange(D, dtype=jnp.int32)
    feature_f = feature.astype(dt)
    # unvisited nodes carry +inf thresholds; 0 * inf = NaN would poison the
    # one-hot contraction.  The sentinel must ALSO survive summation: under
    # vmap the batched contraction can accumulate several sentinel lanes, and
    # float-max + float-max overflows to inf → NaN downstream (this silently
    # degraded every batched-CV GBT margin update).  1e30 keeps the compare
    # semantics (any real threshold is far smaller) with ~1e8 of headroom.
    threshold_f = jnp.where(jnp.isfinite(threshold),
                            threshold.astype(dt),
                            jnp.asarray(1e30, dt))
    leaf_flag = is_leaf.astype(dt)
    node = jnp.zeros((X.shape[0], feature.shape[0]), jnp.int32)

    def node_select(table, node):              # table [Tr, T] → [N, Tr]
        oh = (node[:, :, None] == k_iota).astype(dt)
        return jnp.einsum("ntk,tk->nt", oh, table)

    for _ in range(max_depth):
        f = node_select(feature_f, node).astype(jnp.int32)     # [N, Tr]
        th = node_select(threshold_f, node)
        lf = node_select(leaf_flag, node)
        f_oh = (f[:, :, None] == d_iota).astype(dt)            # fused
        xf = jnp.einsum("ntd,nd->nt", f_oh, X)
        nxt = 2 * node + 1 + (xf > th).astype(jnp.int32)
        nxt = jnp.where(nxt < T, nxt, node)    # bottom level has no children
        node = jnp.where(lf > 0.5, node, nxt)
    oh = (node[:, :, None] == k_iota).astype(dt)
    return jnp.einsum("ntk,tkv->ntv", oh, leaf.astype(dt))     # [N, Tr, V]


# --------------------------------------------------------------------------
# forest / boosting drivers
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _forest_fitter(impurity: str, max_depth: int, n_bins: int, use_vmap: bool,
                   features_per_node: Optional[int] = None):
    """Jitted whole-forest fit, cached on the static tree shape so CV-grid
    candidates sharing a config reuse the compiled executable.  Feature
    subsetting is PER NODE (Spark featureSubsetStrategy semantics) via
    per-tree RNG keys."""

    def fn(B, splits, base_stats, boot, masks, keys, min_instances, min_gain,
           lam):
        def fit_one(args):
            bw, fm, k_ = args
            stats = base_stats * bw[:, None]
            return fit_tree(B, splits, stats, fm, impurity=impurity,
                            max_depth=max_depth, n_bins=n_bins,
                            min_instances=min_instances, min_gain=min_gain,
                            lam=lam, node_feature_key=k_,
                            features_per_node=features_per_node)

        # memory heuristic: deep trees → sequential lax.map, shallow → vmap
        if use_vmap:
            return jax.vmap(fit_one)((boot, masks, keys))
        return jax.lax.map(fit_one, (boot, masks, keys))

    return jax.jit(fn)


def _features_per_node(strategy: str, d: int) -> Optional[int]:
    """Per-node candidate count for a featureSubsetStrategy name; None = all."""
    if strategy == "all":
        return None
    k = {"sqrt": max(1, int(math.sqrt(d))),
         "onethird": max(1, d // 3)}.get(strategy)
    return None if k is None or k >= d else k


def fit_forest(X: np.ndarray, y: np.ndarray, *, task: str, n_classes: int,
               n_trees: int, max_depth: int, max_bins: int,
               min_instances: float, min_gain: float, subsample: float,
               feature_strategy: str, seed: int, bootstrap: bool = True,
               sample_weight: Optional[np.ndarray] = None) -> Dict[str, Any]:
    """Random forest: all trees in one vmapped XLA program (chunked via
    lax.map when deep trees would blow HBM)."""
    N, D = X.shape
    splits, B = shared_binned(X, max_bins)
    w0 = jnp.ones(N, jnp.float32) if sample_weight is None else jnp.asarray(sample_weight)
    yj = jnp.asarray(y, jnp.float32)
    key = jax.random.PRNGKey(seed)
    k_boot, k_feat = jax.random.split(key)
    boot = (jax.random.poisson(k_boot, subsample, (n_trees, N)).astype(jnp.float32)
            if bootstrap else jnp.ones((n_trees, N), jnp.float32))
    # features sample PER NODE inside fit_tree; the tree-level mask stays
    # all-true (per-TREE subsetting cannot learn interactions across subsets)
    masks = jnp.ones((n_trees, D)) > 0
    fpn = _features_per_node(feature_strategy, D) if n_trees > 1 else None
    tree_keys = jax.random.split(k_feat, n_trees)

    if task == "classification":
        impurity = "gini"
        yoh = jax.nn.one_hot(yj.astype(jnp.int32), n_classes, dtype=jnp.float32)
        base_stats = jnp.concatenate([jnp.ones((N, 1)), yoh], axis=1)
    else:
        impurity = "variance"
        base_stats = jnp.stack([jnp.ones(N), yj, yj * yj], axis=1)
    base_stats = base_stats * w0[:, None]

    # tree-vmap multiplies every per-row intermediate by n_trees; cap the
    # broadcast working set (~chunk * N * S * n_trees floats) at ~2 GiB
    S = base_stats.shape[1]
    est_bytes = 32 * N * max(S, 4) * 4 * n_trees
    use_vmap = max_depth <= 8 and n_trees <= 64 and est_bytes < 2 << 30
    fitter = _forest_fitter(impurity, max_depth, max_bins, use_vmap, fpn)
    fit_args = (B, jnp.asarray(splits), base_stats, boot, masks, tree_keys,
                jnp.float32(min_instances), jnp.float32(min_gain),
                jnp.float32(1.0))
    trees = fitter(*fit_args)
    return {"kind": "forest", "task": task, "n_classes": n_classes,
            "max_depth": max_depth,
            "feature": np.asarray(trees.feature),
            "threshold": np.asarray(trees.threshold),
            "is_leaf": np.asarray(trees.is_leaf),
            "leaf": np.asarray(trees.leaf),
            "feature_gain": np.asarray(trees.gain).sum(axis=0),
            "bin_splits": splits}


def gbt_round_body(B, splits, X, y, w0, margin, fmask, min_instances,
                   min_gain, lam, eta, *, task: str, max_depth: int,
                   n_bins: int, hist_dtype=None):
    """One second-order boosting round (grad/hess → tree fit → margin
    update) — the single source of the round math, shared by the local jitted
    fitter and the mesh-sharded variant in parallel/dist_fit.py."""
    if task == "classification":
        p = jax.nn.sigmoid(margin)
        g, h = p - y, jnp.maximum(p * (1 - p), 1e-6)
    else:
        g, h = margin - y, jnp.ones_like(margin)
    # weight ALL stat columns (incl. count) so zero-weight rows are fully
    # excluded from min_instances feasibility, matching the grid path
    stats = jnp.stack([jnp.ones_like(g), g, h], axis=1) * w0[:, None]
    tree = fit_tree(B, splits, stats, fmask, impurity="xgb",
                    max_depth=max_depth, n_bins=n_bins,
                    min_instances=min_instances, min_gain=min_gain, lam=lam,
                    hist_dtype=hist_dtype)
    pred = predict_trees_raw(X, tree.feature[None], tree.threshold[None],
                             tree.is_leaf[None], tree.leaf[None],
                             max_depth + 1)[:, 0, 0]
    return margin + eta * pred, tree


def fit_gbt(X: np.ndarray, y: np.ndarray, *, task: str, n_rounds: int,
            max_depth: int, max_bins: int, min_instances: float,
            min_gain: float, eta: float, lam: float, seed: int,
            min_child_weight: float = 0.0,
            sample_weight: Optional[np.ndarray] = None) -> Dict[str, Any]:
    """Gradient boosting (XGBoost-style second-order): Python loop over rounds
    around a jitted tree fit; grad/hess computed on device."""
    N, D = X.shape
    splits, B = shared_binned(X, max_bins)
    splits_j = jnp.asarray(splits)
    Xj = device_matrix(X)
    w0 = jnp.ones(N, jnp.float32) if sample_weight is None else jnp.asarray(sample_weight)
    yj = jnp.asarray(y, jnp.float32)
    fmask = jnp.ones((D,), jnp.float32) > 0
    base = jnp.float32(0.0) if task == "classification" else jnp.mean(yj)
    mi = max(float(min_instances), float(min_child_weight))
    # single-candidate run of the scanned grid fitter: all rounds in one
    # program, and the selector's final refit reuses the CV executable when
    # the fold shape matches
    chunk, batch_size = _tree_batch_budget(N, max_bins)
    fit_all = _gbt_grid_scan_fitter(task, max_depth, max_bins, chunk,
                                    batch_size, n_rounds)
    margins = jnp.full((1, N), base, jnp.float32)
    one = lambda v: jnp.asarray([v], jnp.float32)
    _, rounds = fit_all(B, splits_j, Xj, yj, margins, w0[None, :], fmask,
                        one(mi), one(min_gain), one(lam), one(eta))
    feature = np.asarray(rounds.feature[:, 0])
    threshold = np.asarray(rounds.threshold[:, 0])
    is_leaf = np.asarray(rounds.is_leaf[:, 0])
    leaf = np.asarray(rounds.leaf[:, 0])
    return {"kind": "gbt", "task": task, "n_classes": 2,
            "max_depth": max_depth, "eta": eta, "base": float(base),
            "feature": feature, "threshold": threshold,
            "is_leaf": is_leaf, "leaf": leaf,
            "feature_gain": np.asarray(rounds.gain[:, 0]).sum(axis=0),
            "bin_splits": splits}


# --------------------------------------------------------------------------
# batched (fold × grid) CV fitters — shared binned matrix, one dispatch per
# static config (≙ OpValidator.scala:320-349 thread-pool fan-out, SURVEY §2.6 P3)
# --------------------------------------------------------------------------

def _tree_batch_budget(N: int, n_bins: int) -> Tuple[int, int]:
    """(chunk, batch_size) so the one-hot working set of the trees running
    concurrently under ``lax.map(batch_size=...)`` fits the budget
    below (HBM minus data/program headroom).

    Measured on v5e at 1Mx28: wide feature chunks with a narrow tree batch
    (chunk=16, batch=4) run ~2.5x faster than narrow chunks with a wide batch
    (2, 8) — fewer scan iterations beat more vmap lanes, and XLA compile time
    is flat across the grid.  TRANSMOGRIFAI_TREE_BUDGET_GB overrides the
    histogram budget (smaller = safer on workers that hard-fault under
    sustained near-capacity HBM pressure at 10M+ rows)."""
    import os
    budget = int(float(os.environ.get(
        "TRANSMOGRIFAI_TREE_BUDGET_GB", 6)) * (1 << 30))
    per_col = max(2 * N, 1)       # bf16 bytes of one [N] column
    p_cols = 256                  # routing matrix P [N, P_n*S] upper bound
    # prefer 4 concurrent lanes at wide chunks; shrink chunk, then lanes
    for batch_size in (4, 2, 1):
        avail = budget // batch_size // per_col - p_cols
        chunk = min(16, avail // n_bins)
        if chunk >= 1:
            return int(chunk), batch_size
    return 1, 1


@functools.lru_cache(maxsize=None)
def _forest_grid_fitter(impurity: str, max_depth: int, n_bins: int,
                        bootstrap: bool, chunk: int, batch_size: int,
                        features_per_node: Optional[int] = None):
    """Jitted fit of ALL trees of a (fold × grid-point) forest group.

    Per-tree traced inputs: fold id (row-weight mask row), PRNG key (Poisson
    bootstrap drawn on device — no [Kt, N] boot matrix in HBM), min_instances,
    min_gain, subsample rate, feature mask.  ``lax.map(batch_size=...)`` bounds
    the histogram working set while still vmapping ``batch_size`` trees onto
    the MXU at once.  Feature subsetting is PER NODE (featureSubsetStrategy
    semantics) using a key derived from the tree's bootstrap key."""

    def fn(B, splits, base_stats, fold_w, fold_ids, keys, mis, mgs, subs,
           masks, lam):
        N = B.shape[0]

        def fit_one(args):
            fid, key, mi, mg, sub, fm = args
            k_boot, k_feat = jax.random.split(key)
            w = fold_w[fid]
            if bootstrap:
                bw = jax.random.poisson(k_boot, sub, (N,)).astype(jnp.float32) * w
            else:
                bw = w
            stats = base_stats * bw[:, None]
            return fit_tree(B, splits, stats, fm, impurity=impurity,
                            max_depth=max_depth, n_bins=n_bins,
                            min_instances=mi, min_gain=mg, lam=lam,
                            chunk=chunk, node_feature_key=k_feat,
                            features_per_node=features_per_node)

        return jax.lax.map(fit_one, (fold_ids, keys, mis, mgs, subs, masks),
                           batch_size=batch_size)

    return jax.jit(fn)


def _gbt_grid_round_body(B, splits, X, y, margins, weights, fmask, mis, mgs,
                         lams, etas, *, task, max_depth, n_bins, chunk,
                         batch_size):
    def one(args):
        margin, w, mi, mg, lam, eta = args
        if task == "classification":
            p = jax.nn.sigmoid(margin)
            g, h = p - y, jnp.maximum(p * (1 - p), 1e-6)
        else:
            g, h = margin - y, jnp.ones_like(margin)
        stats = jnp.stack([jnp.ones_like(g), g, h], axis=1) * w[:, None]
        tree = fit_tree(B, splits, stats, fmask, impurity="xgb",
                        max_depth=max_depth, n_bins=n_bins,
                        min_instances=mi, min_gain=mg, lam=lam, chunk=chunk)
        pred = predict_trees_raw(X, tree.feature[None], tree.threshold[None],
                                 tree.is_leaf[None], tree.leaf[None],
                                 max_depth + 1)[:, 0, 0]
        return margin + eta * pred, tree

    return jax.lax.map(one, (margins, weights, mis, mgs, lams, etas),
                       batch_size=batch_size)


@functools.lru_cache(maxsize=None)
def _gbt_grid_scan_fitter(task: str, max_depth: int, n_bins: int, chunk: int,
                          batch_size: int, n_rounds: int):
    """ALL boosting rounds of the whole (fold × grid-point) candidate block as
    ONE jitted program — ``lax.scan`` over rounds around the per-round
    ``lax.map`` over candidates.  One compile + one dispatch for the entire
    GBT family grid (the reference launches k·Σ|grid|·rounds Spark jobs).
    Returns (final margins [K, N], trees stacked [R, K, ...])."""

    def fn(B, splits, X, y, margins, weights, fmask, mis, mgs, lams, etas):
        def round_step(m, _):
            m2, trees = _gbt_grid_round_body(
                B, splits, X, y, m, weights, fmask, mis, mgs, lams, etas,
                task=task, max_depth=max_depth, n_bins=n_bins, chunk=chunk,
                batch_size=batch_size)
            return m2, trees

        return jax.lax.scan(round_step, margins, None, length=n_rounds)

    return jax.jit(fn)


# --------------------------------------------------------------------------
# prediction models + estimator stages
# --------------------------------------------------------------------------

def _predict_trees_np(X: np.ndarray, feature: np.ndarray, threshold: np.ndarray,
                      is_leaf: np.ndarray, leaf: np.ndarray,
                      max_depth: int) -> np.ndarray:
    """Numpy twin of ``predict_trees_raw`` — scoring is gather-bound host work;
    running it here avoids a fresh XLA compile per validation-slice shape in
    the CV loop.  Returns [N, Tr, V]."""
    N = X.shape[0]
    Tr = feature.shape[0]
    node = np.zeros((N, Tr), np.int32)
    ar = np.arange(Tr)[None, :]
    for _ in range(max_depth):
        f = feature[ar, node]
        th = threshold[ar, node]
        lf = is_leaf[ar, node]
        xf = np.take_along_axis(X, np.maximum(f, 0), axis=1)
        nxt = 2 * node + 1 + (xf > th).astype(np.int32)
        node = np.where(lf, node, nxt)
    return leaf[ar, node]


class TreeEnsembleModel(PredictionModel):
    def device_scores(self, Xd, full: bool = False) -> Dict[str, Any]:
        """Device-resident scoring: leaves are aggregated in HBM and only
        [N]/[N,C]-sized results exist afterwards — never transfer the
        [N, Tr, V] leaf tensor over the (slow) host link."""
        f = self.fitted
        args = (Xd, jnp.asarray(f["feature"]), jnp.asarray(f["threshold"]),
                jnp.asarray(f["is_leaf"]), jnp.asarray(f["leaf"]),
                int(f["max_depth"]) + 1)
        if f["kind"] == "forest":
            if f["task"] == "classification":
                prob = predict_trees_agg(*args, op="mean")     # [N, C]
                prob = prob / jnp.maximum(
                    jnp.sum(prob, axis=1, keepdims=True), 1e-12)
                out = {"prediction": jnp.argmax(prob, axis=1).astype(jnp.float32),
                       "probability": prob}
                if prob.shape[1] == 2:
                    out["scores"] = prob[:, 1]
                if full:
                    out["rawPrediction"] = jnp.log(jnp.maximum(prob, 1e-12))
                return out
            return {"prediction": predict_trees_agg(*args, op="mean")[:, 0]}
        margin = f["base"] + f["eta"] * predict_trees_agg(*args, op="sum")[:, 0]
        if f["task"] == "classification":
            p1 = jax.nn.sigmoid(margin)
            out = {"prediction": (p1 > 0.5).astype(jnp.float32),
                   "scores": p1, "margin": margin}
            if full:
                out["probability"] = jnp.stack([1.0 - p1, p1], axis=1)
                out["rawPrediction"] = jnp.stack([-margin, margin], axis=1)
            return out
        return {"prediction": margin}

    def predict_arrays(self, X: np.ndarray) -> Dict[str, np.ndarray]:
        f = self.fitted
        depth_iters = int(f["max_depth"]) + 1
        if isinstance(X, jax.Array) and _mxu_dtype() != jnp.float32:
            # X already lives on a real accelerator: score there and pull only
            # the per-row results
            out = self.device_scores(X)
            if f["kind"] == "forest" and f["task"] == "classification":
                prob = np.asarray(out["probability"])
                return {"prediction": np.asarray(out["prediction"]),
                        "probability": prob,
                        "rawPrediction": np.log(np.maximum(prob, 1e-12))}
            if f["kind"] == "gbt" and f["task"] == "classification":
                margin = np.asarray(out["margin"])
                p1 = np.asarray(out["scores"])
                return {"prediction": np.asarray(out["prediction"]),
                        "probability": np.stack([1 - p1, p1], axis=1),
                        "rawPrediction": np.stack([-margin, margin], axis=1)}
            return {"prediction": np.asarray(out["prediction"])}
        X32 = np.asarray(X, np.float32)
        leaves = _predict_trees_np(
            X32, np.asarray(f["feature"]), np.asarray(f["threshold"]),
            np.asarray(f["is_leaf"]), np.asarray(f["leaf"]), depth_iters)
        if f["kind"] == "forest":
            if f["task"] == "classification":
                prob = leaves.mean(axis=1)                     # [N, C]
                prob = prob / np.maximum(prob.sum(axis=1, keepdims=True), 1e-12)
                return {"prediction": np.argmax(prob, axis=1).astype(np.float32),
                        "probability": prob,
                        "rawPrediction": np.log(np.maximum(prob, 1e-12))}
            return {"prediction": leaves.mean(axis=1)[:, 0].astype(np.float32)}
        # gbt
        margin = f["base"] + f["eta"] * leaves[:, :, 0].sum(axis=1)
        if f["task"] == "classification":
            p1 = 1.0 / (1.0 + np.exp(-margin))
            prob = np.stack([1 - p1, p1], axis=1)
            return {"prediction": (p1 > 0.5).astype(np.float32),
                    "probability": prob,
                    "rawPrediction": np.stack([-margin, margin], axis=1)}
        return {"prediction": margin.astype(np.float32)}


class _ForestEstimatorBase(PredictorEstimator):
    model_cls = TreeEnsembleModel
    task = "classification"
    default_feature_strategy = "sqrt"
    hbm_heavy = True      # one-hot histogram working set ~6 GiB at large N
    # every tree statistic (node/histogram counts, leaf values, gains) is
    # sample-weighted and binning quantiles skip registered padding rows
    # (real_rows above), so zero-weight padded fits pick identical splits;
    # leaf values agree to float reduction order (the histogram chunk
    # budget is shape-dependent).  Bootstrap draws remain a valid
    # (weight-masked) sample at the padded shape.
    weighted_pad_exact = True
    supports_pretrace = True

    def __init__(self, num_trees: int = 20, max_depth: int = 5,
                 max_bins: int = MAX_BINS_DEFAULT, min_instances_per_node: int = 1,
                 min_info_gain: float = 0.0, subsampling_rate: float = 1.0,
                 feature_subset_strategy: str = "auto", seed: int = 42,
                 bootstrap: bool = True, **kw):
        super().__init__(num_trees=num_trees, max_depth=max_depth,
                         max_bins=max_bins,
                         min_instances_per_node=min_instances_per_node,
                         min_info_gain=min_info_gain,
                         subsampling_rate=subsampling_rate,
                         feature_subset_strategy=feature_subset_strategy,
                         seed=seed, bootstrap=bootstrap, **kw)

    def fit_arrays(self, X, y, sample_weight=None) -> Dict[str, Any]:
        strategy = self.get("feature_subset_strategy", "auto")
        if strategy == "auto":
            strategy = (self.default_feature_strategy
                        if self.get("num_trees", 20) > 1 else "all")
        from .linear import _n_classes
        n_classes = (_n_classes(y) if self.task == "classification" else 0)
        return fit_forest(
            X, y, task=self.task, n_classes=max(n_classes, 2),
            n_trees=int(self.get("num_trees", 20)),
            max_depth=int(self.get("max_depth", 5)),
            max_bins=int(self.get("max_bins", MAX_BINS_DEFAULT)),
            min_instances=float(self.get("min_instances_per_node", 1)),
            min_gain=float(self.get("min_info_gain", 0.0)),
            subsample=float(self.get("subsampling_rate", 1.0)),
            feature_strategy=strategy, seed=int(self.get("seed", 42)),
            bootstrap=bool(self.get("bootstrap", True)),
            sample_weight=sample_weight)


    def fit_arrays_grid(self, X, y, fold_weights, grids):
        """All (fold × grid-point × tree) fits of this candidate family share
        ONE binned matrix and dispatch once per static config — the reference
        re-bins and re-launches a Spark job per (fold, paramMap)
        (OpCrossValidation.scala:114-137).  Quantile split candidates are
        computed from the full matrix (label-free, standard CV practice)."""
        from collections import defaultdict
        K, G = fold_weights.shape[0], len(grids)
        out: list = [[None] * G for _ in range(K)]
        N, D = X.shape
        from .linear import _n_classes
        n_classes = (_n_classes(y) if self.task == "classification" else 0)
        n_classes = max(n_classes, 2)

        groups = defaultdict(list)
        for gi, p in enumerate(grids):
            m = {**self._params, **p}
            strategy = m.get("feature_subset_strategy", "auto")
            if strategy == "auto":
                strategy = (self.default_feature_strategy
                            if int(m.get("num_trees", 20)) > 1 else "all")
            groups[(int(m.get("num_trees", 20)), int(m.get("max_depth", 5)),
                    int(m.get("max_bins", MAX_BINS_DEFAULT)), strategy,
                    bool(m.get("bootstrap", True)),
                    int(m.get("seed", 42)))].append(gi)

        from ..aot import pretrace_mode
        pretrace = pretrace_mode()
        yj = jnp.asarray(y, jnp.float32)
        if self.task == "classification":
            impurity = "gini"
            if pretrace:
                # compile-only pass: an abstract aval for the big per-row
                # stats is enough to lower the fitter — skip materializing
                base_stats = jax.ShapeDtypeStruct((N, 1 + n_classes),
                                                  jnp.float32)
            else:
                yoh = jax.nn.one_hot(yj.astype(jnp.int32), n_classes,
                                     dtype=jnp.float32)
                base_stats = jnp.concatenate([jnp.ones((N, 1)), yoh], axis=1)
        else:
            impurity = "variance"
            base_stats = (jax.ShapeDtypeStruct((N, 3), jnp.float32)
                          if pretrace
                          else jnp.stack([jnp.ones(N), yj, yj * yj], axis=1))
        fold_w = to_device_f32(fold_weights, exact=True)
        splits_cache: dict = {}

        def mval(gi, name, default):
            return float({**self._params, **grids[gi]}.get(name, default))

        for (n_trees, max_depth, max_bins, strategy, bootstrap,
             seed), gidx in groups.items():
            if max_bins not in splits_cache:
                splits_cache[max_bins] = shared_binned(X, max_bins)
            splits, B = splits_cache[max_bins]
            Gg = len(gidx)
            Kt = K * Gg * n_trees
            # (split kept for draw-compatibility with fit_forest's seeding;
            # per-node feature keys derive from each tree's bootstrap key)
            k_boot, _ = jax.random.split(jax.random.PRNGKey(seed))
            # per-NODE feature subsetting happens inside fit_tree (keys drawn
            # from each tree's key); the tree-level mask stays all-true
            fpn = (_features_per_node(strategy, D) if n_trees > 1 else None)
            masks = jnp.ones((Kt, D)) > 0
            # one bootstrap key per TREE INDEX, shared across folds and grid
            # points — grid points differing only in traced params see
            # identical draws (candidates are ranked by hyper-parameters, not
            # bootstrap noise), mirroring fit_forest's fixed-seed draws
            keys_one = jax.random.split(k_boot, n_trees)
            keys = jax.random.wrap_key_data(
                jnp.tile(jax.random.key_data(keys_one), (K * Gg, 1)))
            fold_ids = jnp.asarray(
                np.repeat(np.arange(K, dtype=np.int32), Gg * n_trees))
            per_tree = lambda vals: jnp.asarray(
                np.tile(np.repeat(np.asarray(vals, np.float32), n_trees), K))
            mis = per_tree([mval(gi, "min_instances_per_node", 1) for gi in gidx])
            mgs = per_tree([mval(gi, "min_info_gain", 0.0) for gi in gidx])
            subs = per_tree([mval(gi, "subsampling_rate", 1.0) for gi in gidx])
            chunk, batch_size = _tree_batch_budget(N, max_bins)
            fitter = _forest_grid_fitter(impurity, max_depth, max_bins,
                                         bootstrap, chunk, batch_size, fpn)
            grid_args = (B, jnp.asarray(splits), base_stats, fold_w,
                         fold_ids, keys, mis, mgs, subs, masks,
                         jnp.float32(1.0))
            from ..aot_registry import grid_call, grid_compile
            f_statics = dict(impurity=impurity, maxDepth=max_depth,
                             maxBins=max_bins, bootstrap=bootstrap,
                             chunk=chunk, batchSize=batch_size, fpn=fpn)
            if pretrace:
                # registry hit → the executable deserializes now and the
                # sweep's real fit dispatches it (zero compiles); miss →
                # lower+compile into the persistent compile cache (and
                # _SHARED_BINS, above) and publish the fresh build
                grid_compile("trees.forest_grid_fit", fitter, grid_args,
                             sig_statics=f_statics)
                continue
            trees = grid_call("trees.forest_grid_fit", fitter, grid_args,
                              sig_statics=f_statics)
            # keep the tree arrays device-resident: candidates slice views of
            # the [Kt, ...] stacks; they only cross the host link if a model
            # is serialized or scored on host data
            feature = trees.feature
            threshold = trees.threshold
            is_leaf = trees.is_leaf
            leaf = trees.leaf
            for k in range(K):
                for j, gi in enumerate(gidx):
                    s = (k * Gg + j) * n_trees
                    out[k][gi] = {
                        "kind": "forest", "task": self.task,
                        "n_classes": n_classes, "max_depth": max_depth,
                        "feature": feature[s:s + n_trees],
                        "threshold": threshold[s:s + n_trees],
                        "is_leaf": is_leaf[s:s + n_trees],
                        "leaf": leaf[s:s + n_trees],
                        "feature_gain": trees.gain[s:s + n_trees].sum(axis=0),
                        "bin_splits": splits}
        return out


class OpRandomForestClassifier(_ForestEstimatorBase):
    """≙ OpRandomForestClassifier.scala:58."""
    task = "classification"
    default_feature_strategy = "sqrt"


class OpRandomForestRegressor(_ForestEstimatorBase):
    """≙ OpRandomForestRegressor."""
    task = "regression"
    default_feature_strategy = "onethird"


class OpDecisionTreeClassifier(_ForestEstimatorBase):
    """≙ OpDecisionTreeClassifier: a single deterministic tree — no
    bootstrap, all features (like Spark's DecisionTreeClassifier)."""
    task = "classification"

    def __init__(self, max_depth: int = 5, **kw):
        kw.setdefault("num_trees", 1)
        kw.setdefault("feature_subset_strategy", "all")
        kw.setdefault("subsampling_rate", 1.0)
        kw.setdefault("bootstrap", False)
        super().__init__(max_depth=max_depth, **kw)


class OpDecisionTreeRegressor(OpDecisionTreeClassifier):
    task = "regression"


class _GBTEstimatorBase(PredictorEstimator):
    model_cls = TreeEnsembleModel
    task = "classification"
    hbm_heavy = True
    # GBT fits are deterministic (no per-fit RNG) and fully sample-weighted:
    # zero-weight padded rows have zero grad/hess and padding-aware binning
    # (real_rows) keeps split points fixed — padded fits choose identical
    # trees, with leaf values equal to float reduction order
    weighted_pad_exact = True
    supports_pretrace = True

    def __init__(self, max_iter: int = 20, max_depth: int = 5,
                 max_bins: int = MAX_BINS_DEFAULT, min_instances_per_node: int = 1,
                 min_info_gain: float = 0.0, step_size: float = 0.1,
                 reg_lambda: float = 1.0, seed: int = 42, **kw):
        super().__init__(max_iter=max_iter, max_depth=max_depth, max_bins=max_bins,
                         min_instances_per_node=min_instances_per_node,
                         min_info_gain=min_info_gain, step_size=step_size,
                         reg_lambda=reg_lambda, seed=seed, **kw)

    def fit_arrays(self, X, y, sample_weight=None) -> Dict[str, Any]:
        return fit_gbt(
            X, y, task=self.task,
            n_rounds=int(self.get("max_iter", 20)),
            max_depth=int(self.get("max_depth", 5)),
            max_bins=int(self.get("max_bins", MAX_BINS_DEFAULT)),
            min_instances=float(self.get("min_instances_per_node", 1)),
            min_gain=float(self.get("min_info_gain", 0.0)),
            eta=float(self.get("step_size", 0.1)),
            lam=float(self.get("reg_lambda", 1.0)),
            min_child_weight=float(self.get("min_child_weight", 0.0)),
            seed=int(self.get("seed", 42)), sample_weight=sample_weight)


    def fit_arrays_grid(self, X, y, fold_weights, grids):
        """Batched GBT grid: one jitted dispatch per boosting round fits that
        round's tree for ALL (fold × grid-point) candidates at once over a
        shared binned matrix (margins/weights [K·G, N] in HBM)."""
        from collections import defaultdict
        K, G = fold_weights.shape[0], len(grids)
        out: list = [[None] * G for _ in range(K)]
        N, D = X.shape

        groups = defaultdict(list)
        for gi, p in enumerate(grids):
            m = {**self._params, **p}
            groups[(int(m.get("max_iter", 20)), int(m.get("max_depth", 5)),
                    int(m.get("max_bins", MAX_BINS_DEFAULT)))].append(gi)

        Xj = device_matrix(X)
        yj = jnp.asarray(y, jnp.float32)
        fold_w = to_device_f32(fold_weights, exact=True)
        fmask = jnp.ones((D,), jnp.float32) > 0
        splits_cache: dict = {}

        def mval(gi, name, default):
            return float({**self._params, **grids[gi]}.get(name, default))

        for (n_rounds, max_depth, max_bins), gidx in groups.items():
            if max_bins not in splits_cache:
                splits_cache[max_bins] = shared_binned(X, max_bins)
            splits, B = splits_cache[max_bins]
            Gg = len(gidx)
            Kc = K * Gg
            from ..aot import pretrace_mode
            pretrace = pretrace_mode()
            if pretrace:
                # compile-only pass: abstract avals for the [Kc, N] buffers
                W = jax.ShapeDtypeStruct((Kc, N), jnp.float32)
                margins = jax.ShapeDtypeStruct((Kc, N), jnp.float32)
            else:
                # candidate kc = k*Gg + j
                W = jnp.repeat(fold_w, Gg, axis=0)             # [Kc, N]
                if self.task == "classification":
                    base = jnp.zeros((Kc,), jnp.float32)
                else:
                    base = (fold_w @ yj) / jnp.maximum(
                        jnp.sum(fold_w, axis=1), 1e-12)        # [K]
                    base = jnp.repeat(base, Gg)
                margins = jnp.broadcast_to(
                    base[:, None], (Kc, N)).astype(jnp.float32)
            per_cand = lambda vals: np.tile(np.asarray(vals, np.float32), K)
            mis = per_cand([max(mval(gi, "min_instances_per_node", 1),
                                mval(gi, "min_child_weight", 0.0))
                            for gi in gidx])
            mgs = per_cand([mval(gi, "min_info_gain", 0.0) for gi in gidx])
            lams = per_cand([mval(gi, "reg_lambda", 1.0) for gi in gidx])
            etas = per_cand([mval(gi, "step_size", 0.1) for gi in gidx])
            chunk, batch_size = _tree_batch_budget(N, max_bins)
            fit_all = _gbt_grid_scan_fitter(self.task, max_depth, max_bins,
                                            chunk, batch_size, n_rounds)
            mis_d, mgs_d, lams_d, etas_d = (jnp.asarray(a) for a in
                                            (mis, mgs, lams, etas))
            gbt_args = (B, jnp.asarray(splits), Xj, yj, margins, W, fmask,
                        mis_d, mgs_d, lams_d, etas_d)
            from ..aot_registry import grid_call, grid_compile
            g_statics = dict(task=self.task, maxDepth=max_depth,
                             maxBins=max_bins, chunk=chunk,
                             batchSize=batch_size, rounds=n_rounds)
            if pretrace:
                grid_compile("trees.gbt_grid_fit", fit_all, gbt_args,
                             sig_statics=g_statics)
                continue
            margins, rounds = grid_call("trees.gbt_grid_fit", fit_all,
                                        gbt_args, sig_statics=g_statics)
            # device-resident [Kc, R, T] stacks; sliced per candidate below
            feature = jnp.swapaxes(rounds.feature, 0, 1)
            threshold = jnp.swapaxes(rounds.threshold, 0, 1)
            is_leaf = jnp.swapaxes(rounds.is_leaf, 0, 1)
            leaf = jnp.swapaxes(rounds.leaf, 0, 1)
            base_np = np.asarray(base)
            for k in range(K):
                for j, gi in enumerate(gidx):
                    kc = k * Gg + j
                    out[k][gi] = {
                        "kind": "gbt", "task": self.task, "n_classes": 2,
                        "max_depth": max_depth,
                        "eta": float(etas[kc]), "base": float(base_np[kc]),
                        "feature": feature[kc], "threshold": threshold[kc],
                        "is_leaf": is_leaf[kc], "leaf": leaf[kc],
                        "feature_gain": rounds.gain[:, kc].sum(axis=0),
                        "bin_splits": splits}
        return out


class OpGBTClassifier(_GBTEstimatorBase):
    """≙ OpGBTClassifier (binary only, like Spark's GBTClassifier)."""
    task = "classification"


class OpGBTRegressor(_GBTEstimatorBase):
    """≙ OpGBTRegressor."""
    task = "regression"


class OpXGBoostClassifier(_GBTEstimatorBase):
    """≙ OpXGBoostClassifier.scala:47 — same boosted-tree engine with XGBoost
    parameter names/defaults (eta, numRound, minChildWeight, lambda)."""
    task = "classification"

    def __init__(self, num_round: int = 100, eta: float = 0.3,
                 max_depth: int = 6, min_child_weight: float = 1.0,
                 reg_lambda: float = 1.0, seed: int = 42, **kw):
        super().__init__(max_iter=num_round, max_depth=max_depth,
                         step_size=eta, reg_lambda=reg_lambda, seed=seed,
                         min_child_weight=min_child_weight, **kw)


class OpXGBoostRegressor(OpXGBoostClassifier):
    task = "regression"

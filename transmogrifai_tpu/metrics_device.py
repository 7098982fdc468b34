"""Masked on-device metrics for the CV loop.

Why this exists: the host link is orders of magnitude slower than HBM, so
pulling per-candidate prediction vectors to the host to score them — the
obvious port of the reference's evaluator.evaluateAll(Dataset) — moves
[folds x grid x rows] floats to save a reduction the device does in place.
Instead every validation metric is a
jitted reduction over the FULL row set with a 0/1 validation mask, so fold
slicing never changes array shapes (one compile covers every fold) and only
the final scalar crosses the link.

Ties are handled exactly (midranks for AuROC, threshold grouping for AuPR)
without a data-dependent gather, which is the expensive operation on a TPU
(12.5 ns an element where a sort or a scan streams; ledger, PR 31): a row's
signed weight w·(2·[y > 0.5] − 1) rides through the ONE sort as its payload,
so nothing is fetched by ``order`` afterwards, and since the keys come out
sorted a tie group's ends are a comparison with the neighbour; every row then
reads the running sum at its group's end through a cumulative min or max
(``_at_group_end`` / ``_at_group_start``), because a running sum of
non-negative weights never falls.  Until PR 32 the group ends were
``searchsorted(s, s)``, a binary search of the sorted scores in themselves:
⌈log2(rows+1)⌉ steps, each a gather of every row of every lane.

≙ reference evaluators OpBinaryClassificationEvaluator.scala:67-185 /
OpRegressionEvaluator / OpMultiClassificationEvaluator semantics.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _sort_with_signed_weight(key, y, w):
    """Sort rows by ``key`` with the signed weight as the payload; returns the
    sorted keys and the sorted rows' positive and negative weights.  Two
    operands, as ``argsort`` moves (key + iota): a stable sort would add an
    iota of its own as a third (and half again of the compile), and the order
    inside a tie group decides no point of either curve — every running sum
    is read at a group's end; it can move the rounding of a float32 sum past
    2^24 by an ulp."""
    signed = w * jnp.where(y > 0.5, 1.0, -1.0)
    key, signed = jax.lax.sort((key, signed), num_keys=1, is_stable=False)
    return key, jnp.maximum(signed, 0.0), jnp.maximum(-signed, 0.0)


def _group_ends(key):
    """(first, last): whether a row of the sorted ``key`` opens / closes its
    tie group."""
    edge = key[1:] != key[:-1]
    true = jnp.ones(1, bool)
    return jnp.concatenate([true, edge]), jnp.concatenate([edge, true])


def _at_group_end(run, last):
    """Every row reads the non-decreasing ``run`` at its group's last row.
    (Counts under a 0/1 mask are exact; with fractional weights the scan's
    own rounding can dip ``run`` by an ulp, and a row then reads that.)"""
    return jax.lax.cummin(jnp.where(last, run, jnp.inf), axis=0, reverse=True)


def _at_group_start(run, first):
    """Every row reads the non-decreasing ``run`` at its group's first row."""
    return jax.lax.cummax(jnp.where(first, run, -jnp.inf), axis=0)


@jax.jit
@jax.named_scope("panel.auroc")
def masked_auroc(y: jnp.ndarray, scores: jnp.ndarray, w: jnp.ndarray):
    """Weighted Mann-Whitney AUC with exact tie handling.  ``w`` is a 0/1 (or
    weighted) row mask; rows with w=0 are ignored."""
    ss, wpos, wneg = _sort_with_signed_weight(scores, y, w)
    first, last = _group_ends(ss)
    neg_run = jnp.cumsum(wneg)
    neg_before = jnp.concatenate([jnp.zeros(1, wneg.dtype), neg_run[:-1]])
    below = _at_group_start(neg_before, first)   # neg weight strictly below
    same = _at_group_end(neg_run, last) - below  # neg weight in tie group
    num = jnp.sum(wpos * (below + 0.5 * same))
    n_pos = jnp.sum(wpos)
    n_neg = jnp.sum(wneg)
    return jnp.where(n_pos * n_neg > 0, num / jnp.maximum(n_pos * n_neg, 1e-12), 0.0)


@jax.jit
@jax.named_scope("panel.aupr")
def masked_aupr(y: jnp.ndarray, scores: jnp.ndarray, w: jnp.ndarray):
    """Weighted area under the PR curve, MLlib-style (threshold-grouped,
    trapezoid over recall with a prepended (0, 1) point)."""
    neg, wpos, wneg = _sort_with_signed_weight(-scores, y, w)
    tp_run = jnp.cumsum(wpos)
    fp_run = jnp.cumsum(wneg)
    # group rows by distinct threshold: every row reads its tie-group's LAST
    # cumsum (the value at the threshold boundary); duplicated points then
    # contribute zero width to the trapezoid
    _, last = _group_ends(neg)
    tp = _at_group_end(tp_run, last)
    fp = _at_group_end(fp_run, last)
    n_pos = jnp.maximum(tp_run[-1], 1e-12)
    precision = tp / jnp.maximum(tp + fp, 1e-12)
    recall = tp / n_pos
    recall = jnp.concatenate([jnp.zeros(1, recall.dtype), recall])
    precision = jnp.concatenate([jnp.ones(1, precision.dtype), precision])
    return jnp.where(tp_run[-1] > 0,
                     jnp.trapezoid(precision, recall), 0.0)


@jax.jit
def masked_auroc_grid(y: jnp.ndarray, S: jnp.ndarray, W: jnp.ndarray):
    """``masked_auroc`` for K candidate score columns at once: S [N, K] →
    [K] AUCs in ONE program (the CV grid's per-candidate metric dispatches
    collapse to a single one).  ``W`` is either one shared [N] mask (a
    fold's validation rows — no K-fold duplication of mask HBM) or
    per-candidate [K, N] masks."""
    if W.ndim == 1:
        return jax.vmap(lambda s: masked_auroc(y, s, W), in_axes=1)(S)
    return jax.vmap(lambda s, w: masked_auroc(y, s, w), in_axes=(1, 0))(S, W)


@jax.jit
def masked_aupr_grid(y: jnp.ndarray, S: jnp.ndarray, W: jnp.ndarray):
    """``masked_aupr`` over K score columns (see masked_auroc_grid)."""
    if W.ndim == 1:
        return jax.vmap(lambda s: masked_aupr(y, s, W), in_axes=1)(S)
    return jax.vmap(lambda s, w: masked_aupr(y, s, w), in_axes=(1, 0))(S, W)


def _rows_last(S: jnp.ndarray) -> jnp.ndarray:
    """[N, F, G] scores as [F, G, N].  Mapped over axes 1 and 2 where they
    lie, the sorts and scans are laid out by the compiler as [N, F, G] with
    (F, G) tiled to (8, 128): for two folds of three survivors 768 bytes a
    score (8.5 GB at 1.8 M rows, no program at 8.4 M; compiled for a v5e);
    with the rows last every shape costs what the others did, 16 to 24."""
    return jnp.moveaxis(S, 0, -1)


@jax.jit
def masked_auroc_fold_grid(y: jnp.ndarray, S: jnp.ndarray, W: jnp.ndarray):
    """The whole (fold × grid) AUC panel in ONE program: S [N, F, G] score
    columns, W [F, N] per-fold validation masks → [F, G].  Replaces one
    grid-metric dispatch (plus an eager S slice) per fold, without
    duplicating mask HBM across grid points — the masks stay [F, N]."""
    return jax.vmap(
        lambda s, w: jax.vmap(lambda c: masked_auroc(y, c, w))(s))(
            _rows_last(S), W)


@jax.jit
def masked_aupr_fold_grid(y: jnp.ndarray, S: jnp.ndarray, W: jnp.ndarray):
    """``masked_aupr`` over the (fold × grid) panel (see
    masked_auroc_fold_grid)."""
    return jax.vmap(
        lambda s, w: jax.vmap(lambda c: masked_aupr(y, c, w))(s))(
            _rows_last(S), W)


@jax.jit
def masked_binary_confusion(y: jnp.ndarray, yhat: jnp.ndarray, w: jnp.ndarray):
    """Returns [tp, fp, tn, fn] weighted counts as ONE stacked array (a single
    scalar-block transfer over the host link)."""
    yp = y > 0.5
    hp = yhat > 0.5
    return jnp.stack([jnp.sum(w * (yp & hp)), jnp.sum(w * (~yp & hp)),
                      jnp.sum(w * (~yp & ~hp)), jnp.sum(w * (yp & ~hp))])


@jax.jit
def masked_reg_errors(y: jnp.ndarray, yhat: jnp.ndarray, w: jnp.ndarray):
    """Returns [mse, mae] over masked rows as one stacked array."""
    wsum = jnp.maximum(jnp.sum(w), 1e-12)
    err = yhat - y
    return jnp.stack([jnp.sum(w * err * err) / wsum,
                      jnp.sum(w * jnp.abs(err)) / wsum])


@functools.partial(jax.jit, static_argnames=("n_classes",))
def masked_multiclass_confusion(y: jnp.ndarray, yhat: jnp.ndarray,
                                w: jnp.ndarray, *, n_classes: int):
    """Weighted [C, C] confusion matrix via one-hot matmul on the MXU."""
    yo = jax.nn.one_hot(y.astype(jnp.int32), n_classes, dtype=jnp.float32)
    ho = jax.nn.one_hot(yhat.astype(jnp.int32), n_classes, dtype=jnp.float32)
    return (yo * w[:, None]).T @ ho


def _masked_reg_metric(y, yhat, w, metric):
    errs = masked_reg_errors(y, yhat, w)
    if metric == "RootMeanSquaredError":
        return jnp.sqrt(errs[0])
    if metric == "MeanSquaredError":
        return errs[0]
    return errs[1]                                  # MeanAbsoluteError


@functools.partial(jax.jit, static_argnames=("metric",))
def masked_reg_metric_grid(y: jnp.ndarray, S: jnp.ndarray, W: jnp.ndarray,
                           *, metric: str):
    """Regression analog of ``masked_auroc_grid``: S [N, K] holds K
    candidates' PREDICTION columns (linear-regression margins ARE the
    predictions, so the panel is exact, not merely rank-equivalent) →
    [K] device scalars of the chosen error metric."""
    if W.ndim == 1:
        return jax.vmap(lambda s: _masked_reg_metric(y, s, W, metric),
                        in_axes=1)(S)
    return jax.vmap(lambda s, w: _masked_reg_metric(y, s, w, metric),
                    in_axes=(1, 0))(S, W)


@functools.partial(jax.jit, static_argnames=("metric",))
def masked_reg_metric_fold_grid(y: jnp.ndarray, S: jnp.ndarray,
                                W: jnp.ndarray, *, metric: str):
    """Whole (fold × grid) regression panel: S [N, F, G] predictions,
    W [F, N] fold masks → [F, G]."""
    return jax.vmap(
        lambda s, w: jax.vmap(
            lambda c: _masked_reg_metric(y, c, w, metric), in_axes=1)(s),
        in_axes=(1, 0))(S, W)


def _conf_metric(conf, metric):
    """Weighted Precision/Recall/F1/Error from a [C, C] device confusion
    matrix — the jnp twin of OpMultiClassificationEvaluator._conf_panel
    (identical zero-guard semantics, so the fused panel matches the host
    per-candidate path bit-for-bit up to f32 rounding)."""
    support = conf.sum(axis=1)
    tp = jnp.diagonal(conf)
    if metric == "Error":
        return 1.0 - tp.sum() / jnp.maximum(support.sum(), 1.0)
    pred_count = conf.sum(axis=0)
    prec_c = jnp.where(pred_count > 0, tp / jnp.maximum(pred_count, 1e-30),
                       0.0)
    rec_c = jnp.where(support > 0, tp / jnp.maximum(support, 1e-30), 0.0)
    wts = support / jnp.maximum(support.sum(), 1.0)
    if metric == "Precision":
        return wts @ prec_c
    if metric == "Recall":
        return wts @ rec_c
    f1_c = jnp.where(prec_c + rec_c > 0,
                     2.0 * prec_c * rec_c / jnp.maximum(prec_c + rec_c,
                                                        1e-30), 0.0)
    return wts @ f1_c


@functools.partial(jax.jit, static_argnames=("n_classes", "metric"))
def masked_multiclass_metric_grid(y: jnp.ndarray, P: jnp.ndarray,
                                  W: jnp.ndarray, *, n_classes: int,
                                  metric: str):
    """Multiclass analog of ``masked_auroc_grid``: P [N, K] holds K
    candidates' integer PREDICTION columns → [K] device scalars of the
    weighted confusion metric.  Classes absent from the data contribute
    zero support/zero weight, so a generous static ``n_classes`` is exact."""
    def one(p, w):
        conf = masked_multiclass_confusion(y, p, w, n_classes=n_classes)
        return _conf_metric(conf, metric)
    if W.ndim == 1:
        return jax.vmap(lambda p: one(p, W), in_axes=1)(P)
    return jax.vmap(one, in_axes=(1, 0))(P, W)


@functools.partial(jax.jit, static_argnames=("n_classes", "metric"))
def masked_multiclass_metric_fold_grid(y: jnp.ndarray, P: jnp.ndarray,
                                       W: jnp.ndarray, *, n_classes: int,
                                       metric: str):
    """Whole (fold × grid) multiclass panel: P [N, F, G] integer
    predictions, W [F, N] fold masks → [F, G]."""
    def one(p, w):
        conf = masked_multiclass_confusion(y, p, w, n_classes=n_classes)
        return _conf_metric(conf, metric)
    return jax.vmap(
        lambda p, w: jax.vmap(lambda c: one(c, w), in_axes=1)(p),
        in_axes=(1, 0))(P, W)


@jax.jit
def masked_threshold_confusion(y: jnp.ndarray, scores: jnp.ndarray,
                               w: jnp.ndarray, thresholds: jnp.ndarray):
    """Per-threshold [4, T] weighted (tp, fp, tn, fn) in one fused program:
    scores are bucketed into the threshold grid with searchsorted, then the
    per-threshold counts are suffix sums of a [T+1]-bin histogram — no [T, N]
    broadcast ever materializes (≙ the reference evaluator's
    thresholds panel, OpBinaryClassificationEvaluator.scala:67-185)."""
    wpos = w * (y > 0.5)
    wneg = w * (y <= 0.5)
    # bin i ⇔ thresholds[i-1] <= s < thresholds[i]; prediction at threshold t
    # is s >= t, so counts at t = sum of bins >= its index (suffix sum)
    bins = jnp.searchsorted(thresholds, scores, side="right")
    T = thresholds.shape[0]
    pos_hist = jax.ops.segment_sum(wpos, bins, num_segments=T + 1)
    neg_hist = jax.ops.segment_sum(wneg, bins, num_segments=T + 1)
    pos_suffix = jnp.cumsum(pos_hist[::-1])[::-1]
    neg_suffix = jnp.cumsum(neg_hist[::-1])[::-1]
    tp = pos_suffix[1:]
    fp = neg_suffix[1:]
    n_pos = jnp.sum(wpos)
    n_neg = jnp.sum(wneg)
    return jnp.stack([tp, fp, n_neg - fp, n_pos - tp])

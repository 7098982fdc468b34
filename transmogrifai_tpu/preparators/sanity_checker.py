"""SanityChecker — automatic feature validation / leakage detection
(reference: core/src/main/scala/com/salesforce/op/stages/impl/preparators/
SanityChecker.scala:535-640 fitFn, SanityCheckerModel:695,
SanityCheckerMetadata.scala; stats from utils/stats/OpStatistics.scala:71,188,234).

On TPU the whole fit is a handful of fused XLA reductions over the HBM-resident
feature matrix: moments + label correlations are one [D+1]-wide matmul pass,
Cramér's V contingency tables are one-hot outer-product matmuls per categorical
group, and the model is a gather of the kept column indices.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..columns import Column, ColumnBatch
from ..stages.base import Estimator, TransformerModel
from ..telemetry import REGISTRY, span
from ..types import OPVector, RealNN
from ..vector_meta import VectorMeta

DEFAULT_MAX_CORRELATION = 0.95
DEFAULT_MIN_CORRELATION = 0.0
DEFAULT_MIN_VARIANCE = 1e-5
DEFAULT_MAX_CRAMERS_V = 0.95
DEFAULT_MAX_RULE_CONFIDENCE = 1.0
DEFAULT_MIN_REQUIRED_RULE_SUPPORT = 1.0
DEFAULT_SAMPLE_UPPER_LIMIT = 1_000_000
DEFAULT_CORRELATION_TYPE = "pearson"


def _label_corr(Xf: jnp.ndarray, yf: jnp.ndarray) -> jnp.ndarray:
    """Per-column Pearson correlation with the label (over raw values —
    or over average ranks, which makes it Spearman)."""
    ym = jnp.mean(yf)
    yc = yf - ym
    ysd = jnp.sqrt(jnp.sum(yc * yc))
    Xc = Xf - jnp.mean(Xf, axis=0)
    cov = yc @ Xc
    xsd = jnp.sqrt(jnp.sum(Xc * Xc, axis=0))
    return cov / jnp.maximum(xsd * ysd, 1e-12)


@partial(jax.jit, static_argnames=("spearman",))
@jax.named_scope("sanity.col_stats")
def _col_stats(X: jnp.ndarray, y: jnp.ndarray, spearman: bool = False):
    """Single fused pass: per-column count/mean/var/min/max + corr with the
    label (≙ Statistics.colStats + computeCorrelationsWithLabel,
    OpStatistics.scala:71).  With ``spearman=True`` the rank transform
    (argsort + tie-averaged positions) happens INSIDE the same program
    (≙ SanityChecker.scala:535-640 Spearman option) — one executable, one
    dispatch, no second stats pass.

    Jitted so the centred intermediates fuse into the reductions instead of
    materializing eagerly (an eager pass holds 2-3 full [N, D] temporaries —
    GBs at transmogrified widths).  ``X`` may arrive in bf16 storage; all
    accumulation is forced to f32."""
    Xf = X.astype(jnp.float32)
    yf = y.astype(jnp.float32)
    mean = jnp.mean(Xf, axis=0)
    var = jnp.var(Xf, axis=0, ddof=1)
    mn = jnp.min(Xf, axis=0)
    mx = jnp.max(Xf, axis=0)
    if spearman:
        corr = _label_corr(_rank_transform(Xf), _rank_transform(yf))
    else:
        corr = _label_corr(Xf, yf)
    return mean, var, mn, mx, corr


@partial(jax.jit, static_argnames=("spearman",))
def _col_stats_with_contingency(X, y, union_idx, y_classes, spearman=False):
    """``_col_stats`` + the categorical contingency contraction in ONE
    program (one executable load, two result pulls) — the per-group
    Cramér's V tables come from a single [C, |union|] matmul over the union
    of indicator columns (≙ SanityChecker.scala:575 categoricalTests).
    The contingency always contracts RAW indicator values; only the label
    correlation switches to ranks under ``spearman``."""
    mean, var, mn, mx, corr = _col_stats(X, y, spearman=spearman)
    with jax.named_scope("sanity.contingency"):
        yoh = (y[:, None] == y_classes[None, :]).astype(jnp.float32)
        cont = yoh.T @ X[:, union_idx].astype(jnp.float32)
    return jnp.stack([mean, var, mn, mx, corr]), cont


@jax.jit
def _rank_transform(a: jnp.ndarray) -> jnp.ndarray:
    """Average-rank transform per column for Spearman correlation — one
    sort + searchsorted per column, fully on device (ties get the average of
    their positions, matching scipy's 'average' ranking)."""

    def col_ranks(c):
        order = jnp.argsort(c)
        ss = c[order]
        left = jnp.searchsorted(ss, ss, side="left").astype(jnp.float32)
        right = jnp.searchsorted(ss, ss, side="right").astype(jnp.float32)
        avg = 0.5 * (left + right - 1.0)
        return jnp.zeros_like(avg).at[order].set(avg)

    if a.ndim == 1:
        return col_ranks(a)
    return jax.vmap(col_ranks, in_axes=1, out_axes=1)(a)


def cramers_v(contingency: np.ndarray) -> float:
    """Cramér's V (≙ OpStatistics.chiSquaredTest, OpStatistics.scala:188) —
    re-exported from utils.stats, the single implementation."""
    from ..utils.stats import chi_squared_test
    return chi_squared_test(contingency)[2]


@dataclass
class SanityCheckerSummary:
    """≙ SanityCheckerSummary metadata."""

    correlation_type: str = DEFAULT_CORRELATION_TYPE
    names: List[str] = field(default_factory=list)
    correlations_with_label: List[float] = field(default_factory=list)
    variances: List[float] = field(default_factory=list)
    means: List[float] = field(default_factory=list)
    mins: List[float] = field(default_factory=list)
    maxs: List[float] = field(default_factory=list)
    cramers_v_by_group: Dict[str, float] = field(default_factory=dict)
    contingency_stats_by_group: Dict[str, Any] = field(default_factory=dict)
    dropped: List[str] = field(default_factory=list)
    drop_reasons: Dict[str, List[str]] = field(default_factory=dict)
    sample_size: int = 0

    def to_json(self) -> Dict[str, Any]:
        return {
            "correlationType": self.correlation_type,
            "names": self.names,
            "correlationsWithLabel": self.correlations_with_label,
            "variances": self.variances,
            "means": self.means,
            "mins": self.mins,
            "maxs": self.maxs,
            "categoricalStats": {
                "cramersV": self.cramers_v_by_group,
                "contingencyStats": self.contingency_stats_by_group},
            "dropped": self.dropped,
            "dropReasons": self.drop_reasons,
            "sampleSize": self.sample_size,
        }


class SanityCheckerModel(TransformerModel):
    """Keeps the surviving column slice (≙ SanityCheckerModel:695)."""

    in_kinds = (RealNN, OPVector)
    out_kind = OPVector
    allow_label_as_input = True

    def transform(self, batch: ColumnBatch) -> Column:
        vec = batch[self.input_features[1].name]
        idx = np.asarray(self.fitted["indices_to_keep"], dtype=np.int64)
        values = jnp.asarray(vec.values)[:, idx]
        meta = vec.meta.select(idx.tolist(), name=self.output_features[0].name) \
            if vec.meta is not None else None
        return Column(OPVector, values, meta=meta)


class SanityChecker(Estimator):
    """≙ SanityChecker estimator on (label, featureVector)."""

    in_kinds = (RealNN, OPVector)
    out_kind = OPVector
    allow_label_as_input = True

    def __init__(self, max_correlation: float = DEFAULT_MAX_CORRELATION,
                 min_correlation: float = DEFAULT_MIN_CORRELATION,
                 min_variance: float = DEFAULT_MIN_VARIANCE,
                 max_cramers_v: float = DEFAULT_MAX_CRAMERS_V,
                 max_rule_confidence: float = DEFAULT_MAX_RULE_CONFIDENCE,
                 min_required_rule_support: float = DEFAULT_MIN_REQUIRED_RULE_SUPPORT,
                 remove_bad_features: bool = True,
                 correlation_type: str = DEFAULT_CORRELATION_TYPE,
                 check_sample_fraction: float = 1.0,
                 sample_upper_limit: int = DEFAULT_SAMPLE_UPPER_LIMIT,
                 seed: int = 42, **kw):
        super().__init__(max_correlation=max_correlation,
                         min_correlation=min_correlation,
                         min_variance=min_variance,
                         max_cramers_v=max_cramers_v,
                         max_rule_confidence=max_rule_confidence,
                         min_required_rule_support=min_required_rule_support,
                         remove_bad_features=remove_bad_features,
                         correlation_type=correlation_type,
                         check_sample_fraction=check_sample_fraction,
                         sample_upper_limit=sample_upper_limit, seed=seed, **kw)

    def output_name(self) -> str:
        return f"{self.input_features[1].name}_sanityChecked_{self.uid[-6:]}"

    def fit(self, batch: ColumnBatch) -> SanityCheckerModel:
        with span("sanity.fit"):
            return self._fit(batch)

    def _fit(self, batch: ColumnBatch) -> SanityCheckerModel:
        with span("sanity.stage"):
            label_f, vec_f = self.input_features
            y = np.asarray(batch[label_f.name].values, dtype=np.float32)
            vec = batch[vec_f.name]
            vals = vec.values
            # keep the matrix in its native residency — on real TPU hardware the
            # host link is the bottleneck, so all stats run on device and only the
            # [D]-sized results transfer (≙ colStats on executors)
            Xd = (vals if isinstance(vals, jax.Array)
                  else jnp.asarray(np.asarray(vals, np.float32)))
            if Xd.dtype not in (jnp.float32, jnp.bfloat16):
                # bf16 feature-matrix storage passes through untouched — the
                # jitted stats force f32 accumulation internally
                Xd = Xd.astype(jnp.float32)
            n, d = Xd.shape
            meta = vec.meta or VectorMeta(vec_f.name, [])
            names = (meta.column_names() if meta.size == d
                     else [f"f_{i}" for i in range(d)])

            # sampling (≙ SanityChecker sample fraction:524)
            frac = float(self.get("check_sample_fraction", 1.0))
            limit = int(self.get("sample_upper_limit", DEFAULT_SAMPLE_UPPER_LIMIT))
            if frac < 1.0 or n > limit:
                m = min(int(n * frac) if frac < 1.0 else n, limit)
                rng = np.random.default_rng(int(self.get("seed", 42)))
                idx = rng.choice(n, size=m, replace=False)
                Xs, ys_host = Xd[idx], y[idx]
            else:
                Xs, ys_host = Xd, y
            from ..columns import to_device_f32
            # exact bf16-when-lossless wire, weakref-cached: the selector's grid
            # fits reuse the SAME label transfer
            ys = to_device_f32(ys_host, exact=True)
            # multi-device: row-shard the matrix over the mesh 'data' axis so the
            # stats reductions run as ONE GSPMD program with psum collectives
            # (≙ SanityChecker colStats on executors, SanityChecker.scala:575)
            from ..parallel.mesh import data_sharding, maybe_data_mesh
            mesh = maybe_data_mesh(int(Xs.shape[0]))
            if mesh is not None:
                Xs = jax.device_put(Xs, data_sharding(mesh, 2))
                ys = jax.device_put(ys, data_sharding(mesh, 1))

            # Cramér's V + association rules per categorical indicator group
            # (≙ categoricalTests): group = columns with an indicatorValue sharing
            # (parentFeatureName, grouping)
            groups: Dict[Tuple[str, Optional[str]], List[int]] = {}
            if meta.size == d:
                for c in meta.columns:
                    if c.indicator_value is not None:
                        groups.setdefault((c.parent_feature_name, c.grouping), []
                                          ).append(c.index)
            y_classes = np.unique(ys_host)
            cont_all = None
            pos_of = {}
            corr_type = self.get("correlation_type", DEFAULT_CORRELATION_TYPE)
            union: List[int] = []
            if len(y_classes) > 100:
                # contingency tables need a CATEGORICAL label: a continuous
                # (regression) response would one-hot into an [N, ~N] block;
                # Cramér's V is meaningless there, so skip the tests entirely
                groups = {}
            if groups:
                # ONE device contraction over the UNION of indicator columns
                # covers every group's contingency — per-group gathers would pay
                # a dispatch + stream sync each on high-latency links, and
                # contracting all D columns would pull width-proportional bytes
                # (≙ categoricalTests, batched)
                union = sorted({i for idxs in groups.values() for i in idxs})
                pos_of = {i: p for p, i in enumerate(union)}
        # the span in which the host waits for the device: the dispatch
        # returns at once and the pulls block until the program has run
        with span("sanity.stats"):
            spearman = corr_type == "spearman"
            if groups:
                # stats + contingency (+ rank transform under spearman) in ONE
                # compiled program, TWO pulls.  Guard: groups only exist for
                # categorical indicator columns, so the label one-hot [N, C]
                # stays small — never build it for a continuous (regression)
                # label with ~N distinct values
                stacked, cont = _col_stats_with_contingency(
                    Xs, ys, jnp.asarray(union, jnp.int32),
                    jnp.asarray(y_classes, jnp.float32), spearman=spearman)
                mean, var, mn, mx, corr_arr = np.asarray(stacked)
                cont_all = np.asarray(cont)
            else:
                mean, var, mn, mx, corr = _col_stats(Xs, ys, spearman=spearman)
                corr_arr = np.asarray(corr)
                mean, var, mn, mx = (np.asarray(a) for a in (mean, var, mn, mx))
        with span("sanity.contingency", groups=len(groups)):
            cramers: Dict[str, float] = {}
            group_fail: Dict[int, List[str]] = {}
            max_rule_conf = float(self.get("max_rule_confidence", 1.0))
            min_rule_supp = float(self.get("min_required_rule_support", 1.0))
            contingency_by_group: Dict[str, Dict] = {}
            groups_dropped = 0
            for (parent, grouping), idxs in groups.items():
                contingency = cont_all[:, [pos_of[i] for i in idxs]]  # [C, k]
                # full contingency panel: Cramér's V + chi2 + PMI/MI + rule
                # confidences (≙ OpStatistics.contingencyStats:300; reference
                # rows=choices so transpose)
                from ..utils.stats import contingency_stats
                cstats = contingency_stats(contingency.T)
                v = cstats.cramers_v
                gname = parent if grouping is None else f"{parent}({grouping})"
                cramers[gname] = v
                contingency_by_group[gname] = cstats.to_json()
                reasons = []
                if np.isfinite(v) and v > float(self.get("max_cramers_v", 1.0)):
                    reasons.append(f"CramersV {v:.4f} > max")
                # association rule confidence (leakage): P(label=c | col=1)
                conf = np.asarray(cstats.max_confidences)
                supp = np.asarray(cstats.supports) * contingency.sum() / max(
                    len(ys_host), 1)
                if max_rule_conf < 1.0 or min_rule_supp < 1.0:
                    bad = (conf >= max_rule_conf) & (supp >= min_rule_supp)
                    if bad.any():
                        reasons.append("rule confidence leakage")
                if reasons:
                    groups_dropped += 1
                    for i in idxs:
                        group_fail.setdefault(i, []).extend(reasons)
            # groups that fail whole, by Cramér's V or a rule's confidence
            REGISTRY.counter("sanity.groups_dropped").inc(groups_dropped)

        with span("sanity.rules"):
            # per-column drop rules
            max_corr = float(self.get("max_correlation", DEFAULT_MAX_CORRELATION))
            min_corr = float(self.get("min_correlation", DEFAULT_MIN_CORRELATION))
            min_var = float(self.get("min_variance", DEFAULT_MIN_VARIANCE))
            reasons_by_col: Dict[int, List[str]] = {i: list(r) for i, r in group_fail.items()}
            for i in range(d):
                c = abs(corr_arr[i])
                if np.isfinite(c):
                    if c > max_corr:
                        reasons_by_col.setdefault(i, []).append(
                            f"correlation {c:.4f} > maxCorrelation")
                    elif c < min_corr:
                        reasons_by_col.setdefault(i, []).append(
                            f"correlation {c:.4f} < minCorrelation")
                if var[i] < min_var:
                    reasons_by_col.setdefault(i, []).append(
                        f"variance {var[i]:.2e} < minVariance")

            remove = bool(self.get("remove_bad_features", True))
            drop_idx = sorted(reasons_by_col) if remove else []
            keep = [i for i in range(d) if i not in set(drop_idx)]
            if not keep:  # never drop everything
                keep = list(range(d))
                drop_idx = []

        with span("sanity.summary"):
            summary = SanityCheckerSummary(
                correlation_type=corr_type, names=names,
                correlations_with_label=[float(c) for c in corr_arr],
                variances=[float(v) for v in var], means=[float(m) for m in mean],
                mins=[float(v) for v in mn], maxs=[float(v) for v in mx],
                cramers_v_by_group=cramers,
                contingency_stats_by_group=contingency_by_group,
                dropped=[names[i] for i in drop_idx],
                drop_reasons={names[i]: r for i, r in reasons_by_col.items()},
                sample_size=len(ys_host))

            model = SanityCheckerModel(
                fitted={"indices_to_keep": np.asarray(keep, dtype=np.int64)},
                **self._params)
            model.metadata["summary"] = summary.to_json()
            if meta.size == d:  # full input lineage for ModelInsights
                model.metadata["input_vector_meta"] = meta.to_json()
            model.summary = summary
            return self._finalize_model(model)

"""What every configuration's reference does once it has its feature matrix:
SanityChecker's statistics and kept columns, the fold x grid panel of the
linear family, the refit the program says it chose, and the comparison with
what the program produced.  Imports nothing of the program."""

import itertools

import numpy as np

from . import plain

NO_NUMBER = 1e30


def l2_l1(reg, elastic_net):
    return reg * (1.0 - elastic_net), reg * elastic_net


def storage_of(config, precision):
    """dtype the feature matrix is stored in on the device."""
    if precision.name == "control":
        return precision.wire
    if precision.platform == "cpu":
        return "float32"
    return config["precision"]["matrix_storage"].split()[0]


def grid_keys(family_params):
    """The hyperparameters a family's grid varies: those the configuration
    gives as lists, in its order."""
    return [k for k, v in family_params.items() if isinstance(v, list)]


def grid_points(family_params):
    """Every point of a family's grid as a dict, the first key slowest (the
    order the program's panel lists them in)."""
    keys = grid_keys(family_params)
    return [dict(zip(keys, values)) for values in itertools.product(
        *(family_params[k] for k in keys))]


def winner_question(produced):
    """What the reference has to be asked to answer the same question as the
    program: the family and hyperparameters of the winner it refitted."""
    return {"refit": {"family": produced["winner"]["family"],
                      "params": produced["winner"]["params"]}}


def logistic_family(M, y, folds, p, precision, refit):
    """The elastic-net logistic family's answers: every grid point's AuPR on
    every fold's validation rows, and with ``refit`` (a grid point) that
    point's fit on all rows.  Returns (panel entries, refit answers)."""
    n = len(y)
    grid = grid_points(p)
    lanes = [(va, g) for va in folds for g in grid]
    weights = np.ones((len(lanes) + (refit is not None), n), np.float32)
    for lane, (va, _) in enumerate(lanes):
        weights[lane, va] = 0.0
    points = [g for _, g in lanes] + ([refit] if refit is not None else [])
    l2, l1 = zip(*(l2_l1(g["reg_param"], g.get("elastic_net_param", 0.0))
                   for g in points))
    coef, icpt = plain.logistic_fista(
        M, y, weights, np.asarray(l2), np.asarray(l1), p["max_iter"],
        p["tol"], low=precision.low_matmul)
    S = plain.margins(M, coef, icpt, low=precision.low_matmul)
    G = len(grid)
    cv = [{"params": g,
           "per_fold": [plain.aupr(y[va], S[va, f * G + i])
                        for f, va in enumerate(folds)]}
          for i, g in enumerate(grid)]
    fit = {}
    if refit is not None:
        fit = {"coef": coef[-1], "intercept": float(icpt[-1]),
               "train_auroc": plain.auroc(y, S[:, -1])}
    return cv, fit


def sweep(M, y, config, precision, ask, families):
    """The reference's answers for one data set.

    ``M`` is the feature matrix on the device as the configuration stores it
    (a ``plain.BlockedMatrix``).  ``families`` maps each family name of
    ``config['selector']`` to its fit (``logistic_family`` is one); ``ask``
    names the winner the program refitted.  Returns a dict with the keys of
    ``produced.extract``."""
    sc = config["sanity_checker"]
    n = len(y)
    idx = plain.sanity_sample(n, sc)
    if idx is None:
        stats = plain.column_stats(M, y)
    else:
        idx = np.sort(idx)
        stats = plain.column_stats(M.take_rows(idx), y[idx])
    keep = plain.sanity_keep(stats, sc)
    out = {"stats": stats, "kept": keep, "cv": []}

    M = M.take_columns(keep)
    folds = plain.cv_folds(n, config["folds"], config["fold_seed"])
    won = ask.get("refit") or {}
    for family, p in config["selector"].items():
        refit = None
        if won.get("family") == family:
            refit = {k: won["params"][k] for k in grid_keys(p)}
        cv, fit = families[family](M, y, folds, p, precision, refit)
        out["cv"] += [dict(r, family=family) for r in cv]
        out.update(fit)
    return out


def panel_key(r, config):
    """A panel entry's family and grid point: the hyperparameters the
    configuration's grid varies, whatever else the entry carries."""
    keys = grid_keys(config["selector"].get(r["family"], {}))
    return r["family"], tuple((k, r["params"].get(k)) for k in keys)


def as_produced(ref, like, config):
    """A reference answer in the shape of ``produced.extract``, so that the
    control (the reference at a lower precision) can stand in the program's
    place.  ``like`` is the program's answer to the same question: it lends
    the panel's layout (which points were raced out) and nothing else."""
    by_key = {panel_key(r, config): r for r in ref["cv"]}
    cv = []
    for r in like["cv"]:
        folds = by_key[panel_key(r, config)]["per_fold"]
        cv.append(dict(r, metric=folds[0] if r["raced_out"]
                       else float(np.mean(folds))))
    sign = 1.0 if like["larger_better"] else -1.0
    top = max((r for r in cv if not r["raced_out"]),
              key=lambda r: sign * r["metric"])
    return dict(like, stats=ref["stats"], kept=ref["kept"], cv=cv,
                rff_dropped=ref.get("rff_dropped", []),
                winner=dict(like["winner"], metric=top["metric"]),
                coef=ref.get("coef"), intercept=ref.get("intercept"),
                train_auroc=ref.get("train_auroc", float("nan")))


def _rel(a, b, floor):
    return np.abs(a - b) / np.maximum(np.abs(b), floor)


def compare(produced, ref, config):
    """Every number compared, by name.  Gaps are the program's distance from
    the reference; counts are exact."""
    out = {}
    ps, rs = np.asarray(produced["stats"], np.float64), ref["stats"]
    if ps.shape != rs.shape:
        out["stats_gap"] = float("inf")
        out["kept_mismatch"] = float(abs(ps.shape[1] - rs.shape[1])) or 1.0
    else:
        floor = config["sanity_checker"]["min_variance"]
        std = np.sqrt(np.maximum(rs[1], floor))
        scale = np.maximum(std, np.maximum(np.abs(rs[2]), np.abs(rs[3])))
        gaps = [np.abs(ps[0] - rs[0]) / std,
                _rel(ps[1], rs[1], floor),
                np.abs(ps[2] - rs[2]) / scale,
                np.abs(ps[3] - rs[3]) / scale,
                np.abs(ps[4] - rs[4])]
        out["stats_gap"] = float(np.nanmax(np.stack(gaps)))
        out["kept_mismatch"] = float(len(
            set(map(int, produced["kept"])) ^ set(map(int, ref["kept"]))))
    out["kept_mismatch"] += float(len(
        set(produced.get("rff_dropped", [])) ^ set(ref.get("rff_dropped", []))))

    ref_cv = {panel_key(r, config): r for r in ref["cv"]}
    final, raced = {}, {}
    n_missing = 0
    for r in produced["cv"]:
        rr = ref_cv.get(panel_key(r, config))
        if rr is None or not np.isfinite(r["metric"]):
            n_missing += 1
            continue
        if r["raced_out"] and len(rr["per_fold"]) > 1:
            raced.setdefault(r["family"], []).append(
                abs(r["metric"] - rr["per_fold"][0]))
        else:
            final.setdefault(r["family"], []).append(
                abs(r["metric"] - float(np.mean(rr["per_fold"]))))
    n_missing += len(ref_cv) - (len(produced["cv"]) - n_missing)
    # the candidates that ran every fold decide the winner: their widest gap.
    # Those raced out after fold 0 are the near-empty models, whose AuPR
    # jumps by 0.03 where one more column enters the support: their MEDIAN
    # gap (PERF.md section 6 has the look)
    for fam, gaps in final.items():
        out["cv_gap." + fam] = float(max(gaps))
    for fam, gaps in raced.items():
        out["cv_raced_gap." + fam] = float(np.median(gaps))
    out["panel_missing"] = float(max(n_missing, 0))

    best = produced["winner"]
    sign = 1.0 if produced["larger_better"] else -1.0
    finals = [r for r in produced["cv"] if not r["raced_out"]]
    top = max(finals, key=lambda r: sign * r["metric"])
    out["winner_inconsistent"] = float(
        abs(top["metric"] - best["metric"]) > 0.0)

    if "coef" in ref and produced.get("coef") is not None:
        pc = np.r_[np.asarray(produced["coef"], np.float64).ravel(),
                   produced["intercept"]]
        rc = np.r_[ref["coef"], ref["intercept"]]
        out["refit_coef_gap"] = (
            float(np.linalg.norm(pc - rc) / max(np.linalg.norm(rc), 1e-12))
            if pc.shape == rc.shape else float("inf"))
        out["train_auroc_gap"] = abs(produced["train_auroc"]
                                     - ref["train_auroc"])
    # no number (NaN, or shapes that do not match) reads as NO_NUMBER: over
    # every limit, and still a number the result's line can carry
    return {k: float(v) if np.isfinite(v) else NO_NUMBER
            for k, v in out.items()}

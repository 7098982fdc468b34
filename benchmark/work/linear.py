"""Required work of the elastic-net logistic family in one train: the CV
panel (with racing) and, where it wins, the refit.

An iteration of a proximal-gradient fit needs X v and X^T g over the rows it
trains on: 4 N D floating-point operations a lane, N = rows (folds - 1) /
folds in a fold and all rows in the refit.  The lanes of one stage share the
matrix, and X^T g can be accumulated in the pass that computes X v, so the
least traffic is ONE read of the stored rows an iteration and stage: the
training rows of the one fold in the first stage of a raced sweep, every row
where two folds or more are fitted side by side.  The step size costs 17 more
such iterations (16 power iterations and the quotient) a fold.  Iterations are
counted at ``max_iter``."""

import math

FAMILY = "OpLogisticRegression"     # the winner whose refit this file counts
POWER = 17


def survivors(grid_points):
    return grid_points if grid_points <= 2 else max(2, math.ceil(
        grid_points / 3))


def required(shape, won):
    p = shape["selector"].get(FAMILY)
    if not p:
        return 0.0, 0.0
    n, d, b = shape["rows"], shape["kept_columns"], shape["storage_bytes"]
    folds = shape["folds"]
    g = math.prod(len(v) for v in p.values() if isinstance(v, list))
    iters = p["max_iter"] + POWER
    train = n * (folds - 1) / folds if folds > 1 else n
    s = survivors(g)
    # (grid points, folds) fitted side by side in each stage of the sweep
    stages = [(g, folds)] if s == g or folds == 1 else [(g, 1),
                                                        (s, folds - 1)]
    lane_rows = pass_rows = 0.0
    for points, stage_folds in stages:
        lane_rows += stage_folds * (points * p["max_iter"] + POWER) * train
        pass_rows += iters * (train if stage_folds == 1 else n)
    if won:
        lane_rows += iters * n
        pass_rows += iters * n
    return 4.0 * d * lane_rows, pass_rows * d * b

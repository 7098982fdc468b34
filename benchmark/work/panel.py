"""Required work of the fold x grid metric panel in one train: every
candidate's validation scores are sorted (N log2 N comparisons over the rows
it validates on) and read and written once as float32."""

import math

FAMILY = None     # the winner whose refit this file counts


def required(shape, won):
    n, folds = shape["rows"], shape["folds"]
    sel = shape["selector"]
    candidates = sum(math.prod(len(v) for v in p.values()
                               if isinstance(v, list)) for p in sel.values())
    va = n / folds
    ops = candidates * folds * va * math.log2(max(va, 2))
    return ops, 8.0 * candidates * folds * va

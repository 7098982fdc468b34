"""Required work of the linear SVC family in one train: the CV panel (with
racing) and, where it wins, the refit, counted as ``work/linear.py`` counts
the logistic family's: the squared hinge has another gradient of the margins
and the same two products with the matrix, so one read of the stored rows an
iteration and stage, 4 N D operations a lane, the 17 iterations of the step
size a fold.

The token wire of a text configuration (4 bytes a 3 tokens, once a train) is
under 1 % of the bytes counted here and in ``work/stats.py`` and is not
counted."""

from . import linear

FAMILY = "OpLinearSVC"     # the winner whose refit this file counts


def required(shape, won):
    p = shape["selector"].get(FAMILY)
    if not p:
        return 0.0, 0.0
    return linear.required(dict(shape, selector={linear.FAMILY: p}), won)

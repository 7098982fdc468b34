"""Sweep control and grid fits: the ``selector`` phase of the train's
PhaseTimer, a train, mean over the window's trains.  A host wall: the phase
absorbs the device work queued before it."""

LAYER = "sweep control and grid fits"
UNIT = "s"
SOURCE = "program_span"
MOVES = "train_wall_s"


def read(ctx):
    per_train = [sum(p["wall_s"] for p in t["phases"]
                     if p["name"] == "selector")
                 for t in ctx["trains"] if t["phases"]]
    return sum(per_train) / len(per_train) if per_train else None

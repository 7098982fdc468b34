"""Host prologue and wide reductions: the ``fit:*`` and ``rff`` phases of the
train's PhaseTimer, a train, mean over the window's trains."""

LAYER = "host prologue and SanityChecker/RFF"
UNIT = "s"
SOURCE = "program_span"
MOVES = "train_wall_s"


def read(ctx):
    per_train = [sum(p["wall_s"] for p in t["phases"]
                     if p["name"].startswith("fit:") or p["name"] == "rff")
                 for t in ctx["trains"] if t["phases"]]
    return sum(per_train) / len(per_train) if per_train else None

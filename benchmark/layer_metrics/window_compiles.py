"""Compile reuse: backend compile events (a cache retrieval counts as one)
inside the window, a train.  What a second user's train in a warm process
still compiles or fetches."""

LAYER = "compile reuse"
UNIT = "count"
SOURCE = "program_counter"
MOVES = "train_wall_s"


def read(ctx):
    per_train = [t["compiles"] for t in ctx["trains"]
                 if t.get("compiles") is not None]
    return sum(per_train) / len(per_train) if per_train else None

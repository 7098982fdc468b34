"""Host to device link: ``profiling.host_link_bytes()`` moved by a train,
mean over the window's trains."""

LAYER = "host to device link"
UNIT = "MB"
SOURCE = "program_counter"
MOVES = "train_wall_s"


def read(ctx):
    per_train = [t["link_bytes"] for t in ctx["trains"]
                 if t.get("link_bytes") is not None]
    return sum(per_train) / len(per_train) / 1e6 if per_train else None

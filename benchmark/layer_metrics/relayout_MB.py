"""Host to device link: bytes the sweep cast, padded or re-laid on the
devices to suit the mesh, a train: the counter ``mesh.relayout_bytes`` over
the trains of the process (the window's and set-up's one).  0 where the
fused transform's output is stored and sharded as the sweep wants it; a
program without the counter reports nothing."""

LAYER = "host to device link"
UNIT = "MB"
SOURCE = "program_counter"
MOVES = "train_wall_s"

COUNTER = "mesh.relayout_bytes"


def read(ctx):
    from transmogrifai_tpu.telemetry import REGISTRY
    counters = REGISTRY.counters()
    if COUNTER not in counters or not ctx.get("trains"):
        return None
    return counters[COUNTER] / (len(ctx["trains"]) + 1) / 1e6

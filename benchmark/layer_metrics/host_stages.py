"""Host prologue and SanityChecker/RFF: stages a flush of the fused transform
ran OUTSIDE a compiled segment, a train: the counter ``transform.host_stages``
over the trains of the process (the window's and set-up's one).  0 where
every stage of the table's transform is a device op or has a staged form; a
program without the counter reports nothing."""

LAYER = "host prologue and SanityChecker/RFF"
UNIT = "count"
SOURCE = "program_counter"
MOVES = "train_wall_s"

COUNTER = "transform.host_stages"


def read(ctx):
    from transmogrifai_tpu.telemetry import REGISTRY
    counters = REGISTRY.counters()
    if COUNTER not in counters or not ctx.get("trains"):
        return None
    return counters[COUNTER] / (len(ctx["trains"]) + 1)

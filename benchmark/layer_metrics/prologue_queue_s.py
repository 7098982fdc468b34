"""Host prologue and SanityChecker/RFF: wall seconds a train's prologue pool
held a ready job back, the counter ``prologue.queue_s``
(``ops.text_profile.HostPool``: the union over jobs of the time a job was
ready and no worker of its width was free — every thread busy, or the walks'
share of them) over the trains of the process (the window's and set-up's
one).  Near 0: the pool's width holds nothing back, and the device waits
for one long job.  A program without the counter reports nothing."""

LAYER = "host prologue and SanityChecker/RFF"
UNIT = "s"
SOURCE = "program_counter"
MOVES = "train_wall_s"

COUNTER = "prologue.queue_s"


def read(ctx):
    from transmogrifai_tpu.telemetry import REGISTRY
    counters = REGISTRY.counters()
    if COUNTER not in counters or not ctx.get("trains"):
        return None
    return counters[COUNTER] / (len(ctx["trains"]) + 1)

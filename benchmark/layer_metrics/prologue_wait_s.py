"""Host prologue and SanityChecker/RFF: seconds a train's calling thread was
blocked on the jobs of its prologue pool, the counter ``prologue.wait_s``
(``ops.text_profile.HostPool``: the waits of ``profile_columns`` for the
walks and packs, the joins of RawFeatureFilter's distributions that did not
return at once) over the trains of the process (the window's and set-up's
one).  What the calling thread computes itself is not waiting; beside
``text_profile_s`` and ``rff_s``, which time those spans' walls, it says how
much of them was the pool's.  A program without the counter reports
nothing."""

LAYER = "host prologue and SanityChecker/RFF"
UNIT = "s"
SOURCE = "program_counter"
MOVES = "train_wall_s"

COUNTER = "prologue.wait_s"


def read(ctx):
    from transmogrifai_tpu.telemetry import REGISTRY
    counters = REGISTRY.counters()
    if COUNTER not in counters or not ctx.get("trains"):
        return None
    return counters[COUNTER] / (len(ctx["trains"]) + 1)

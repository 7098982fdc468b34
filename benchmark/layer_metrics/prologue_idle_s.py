"""Host prologue and SanityChecker/RFF: the seconds of the traced train in
which the device waits for this layer.  The sum of the trace reduction's idle
gaps (``trace.py`` gives each gap to the innermost program span that covers
half of it) whose span is one of the layer's: the ``read``, ``prefetch``,
``rff`` and ``fit:*`` phases and the ``prefetch.*``, ``rff.*``,
``transform.*`` and ``sanity.*`` spans inside them."""

LAYER = "host prologue and SanityChecker/RFF"
UNIT = "s"
SOURCE = "device_trace"
MOVES = "train_wall_s"

SPANS = ("phase.read", "phase.prefetch", "phase.rff", "phase.fit:",
         "prefetch.", "rff.", "transform.", "sanity.")


def read(ctx):
    tr = ctx.get("trace")
    if not tr:
        return None
    return sum(s for name, s in tr["idle_gaps"] if name.startswith(SPANS))

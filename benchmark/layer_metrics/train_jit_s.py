"""Compile reuse: seconds the traced train spent tracing, lowering, and
compiling or loading programs: the sum of the ``seconds`` of its
``jit.trace``, ``jit.lower`` and ``jit.compile`` events, which the program's
table of its spans (the gauge ``train.span_profile``) keeps as ``jit_s`` of
the span each event fired under.  What a second user's train in a warm
process still pays before its programs dispatch."""

LAYER = "compile reuse"
UNIT = "s"
SOURCE = "program_counter"
MOVES = "train_wall_s"


def read(ctx):
    if not ctx.get("trace"):
        return None
    from transmogrifai_tpu.telemetry import REGISTRY
    profile = REGISTRY.gauge("train.span_profile").value
    if not isinstance(profile, dict):
        return None
    return sum(row.get("jit_s", 0.0) for row in profile.values())

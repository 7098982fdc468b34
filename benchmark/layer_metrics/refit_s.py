"""Sweep control and grid fits: seconds of the traced train inside
``selector.winner_refit``, the winner's refit on every row after the sweep.
Read from the program's own table of its spans, the gauge
``train.span_profile`` that ``Workflow.train`` sets under a tracer."""

LAYER = "sweep control and grid fits"
UNIT = "s"
SOURCE = "program_span"
MOVES = "train_wall_s"

SPAN = "selector.winner_refit"


def read(ctx):
    if not ctx.get("trace"):
        return None
    from transmogrifai_tpu.telemetry import REGISTRY
    profile = REGISTRY.gauge("train.span_profile").value
    row = profile.get(SPAN) if isinstance(profile, dict) else None
    return row["total_s"] if row else None

"""Host prologue and SanityChecker/RFF: seconds of the traced train inside
``transform.stage_wires``, the host prologues of the staged stages of every
flush (``compiled._apply_run``): a pivot's ids from its column profile, a
date's (day, millisecond of the day) split, a coordinate's triples and null
bits.  The child spans ``transform.stage_wires.<stage class>`` say which
stage.  Read from the program's own table of its spans, the gauge
``train.span_profile`` that ``Workflow.train`` sets under a tracer."""

LAYER = "host prologue and SanityChecker/RFF"
UNIT = "s"
SOURCE = "program_span"
MOVES = "train_wall_s"

SPAN = "transform.stage_wires"


def read(ctx):
    if not ctx.get("trace"):
        return None
    from transmogrifai_tpu.telemetry import REGISTRY
    profile = REGISTRY.gauge("train.span_profile").value
    row = profile.get(SPAN) if isinstance(profile, dict) else None
    return row["total_s"] if row else None

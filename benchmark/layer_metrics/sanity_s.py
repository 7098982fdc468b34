"""Host prologue and SanityChecker/RFF: seconds of the traced train inside
``sanity.fit``, which is ``SanityChecker.fit`` (staging, the statistics
program and its pulls, the contingency loop, the per-column rules, the
summary).  Read from the program's own table of its spans, the gauge
``train.span_profile`` that ``Workflow.train`` sets under a tracer."""

LAYER = "host prologue and SanityChecker/RFF"
UNIT = "s"
SOURCE = "program_span"
MOVES = "train_wall_s"

SPAN = "sanity.fit"


def read(ctx):
    if not ctx.get("trace"):
        return None
    from transmogrifai_tpu.telemetry import REGISTRY
    profile = REGISTRY.gauge("train.span_profile").value
    row = profile.get(SPAN) if isinstance(profile, dict) else None
    return row["total_s"] if row else None

"""Host prologue and SanityChecker/RFF: seconds of the traced train inside
``text.pack_ids``, where ``TextProfile.device_ids`` takes a hashed column's
tokens modulo the hash width, packs three ids a word, pads to the size class
and hands the words to the link, on the thread that called it.  Read from
the program's own table of its spans, the gauge ``train.span_profile`` that
``Workflow.train`` sets under a tracer; a program without the span reports
nothing."""

LAYER = "host prologue and SanityChecker/RFF"
UNIT = "s"
SOURCE = "program_span"
MOVES = "train_wall_s"

SPAN = "text.pack_ids"


def read(ctx):
    if not ctx.get("trace"):
        return None
    from transmogrifai_tpu.telemetry import REGISTRY
    profile = REGISTRY.gauge("train.span_profile").value
    row = profile.get(SPAN) if isinstance(profile, dict) else None
    return row["total_s"] if row else None

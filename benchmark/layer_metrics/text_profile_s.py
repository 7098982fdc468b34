"""Host prologue and SanityChecker/RFF: seconds of the traced train inside
``prefetch.text_profiles``: the native walk of every text column (side by
side on the pool's threads) and, as each profile arrives, the packing of its
token ids on the calling thread (``text_pack_s`` is that part).  Read from
the program's own table of its spans, the gauge ``train.span_profile`` that
``Workflow.train`` sets under a tracer."""

LAYER = "host prologue and SanityChecker/RFF"
UNIT = "s"
SOURCE = "program_span"
MOVES = "train_wall_s"

SPAN = "prefetch.text_profiles"


def read(ctx):
    if not ctx.get("trace"):
        return None
    from transmogrifai_tpu.telemetry import REGISTRY
    profile = REGISTRY.gauge("train.span_profile").value
    row = profile.get(SPAN) if isinstance(profile, dict) else None
    return row["total_s"] if row else None

"""Host prologue and SanityChecker/RFF: seconds of the traced train inside
RawFeatureFilter: ``rff.distributions`` (every raw feature's ranges and
histograms) and ``rff.decide`` (the drop rules and the cleaned batch), both
host work over rows before the device has anything to do.  Read from the
program's own table of its spans, the gauge ``train.span_profile`` that
``Workflow.train`` sets under a tracer."""

LAYER = "host prologue and SanityChecker/RFF"
UNIT = "s"
SOURCE = "program_span"
MOVES = "train_wall_s"

SPANS = ("rff.distributions", "rff.decide")


def read(ctx):
    if not ctx.get("trace"):
        return None
    from transmogrifai_tpu.telemetry import REGISTRY
    profile = REGISTRY.gauge("train.span_profile").value
    rows = [profile[s] for s in SPANS
            if isinstance(profile, dict) and s in profile]
    return sum(r["total_s"] for r in rows) if rows else None

"""Host prologue and SanityChecker/RFF: seconds of the traced train inside
the estimators' fits of its fit phases, the spans ``transform.fit.<estimator
class>`` around each ``fit`` but the selector's and SanityChecker's (which
``selector_s`` and ``sanity_s`` read).  Read from the program's own table of
its spans, the gauge ``train.span_profile`` that ``Workflow.train`` sets
under a tracer; a program without such spans reports nothing."""

LAYER = "host prologue and SanityChecker/RFF"
UNIT = "s"
SOURCE = "program_span"
MOVES = "train_wall_s"

PREFIX = "transform.fit."


def read(ctx):
    if not ctx.get("trace"):
        return None
    from transmogrifai_tpu.telemetry import REGISTRY
    profile = REGISTRY.gauge("train.span_profile").value
    rows = [row for name, row in profile.items()
            if name.startswith(PREFIX)] if isinstance(profile, dict) else []
    return sum(r["total_s"] for r in rows) if rows else None

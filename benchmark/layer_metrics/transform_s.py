"""Host prologue and SanityChecker/RFF: seconds of the traced train inside
``transform.apply``, the fused transform of the fitted vectorizers
(``workflow._fit_plain``'s flush: the host prologues of the staged stages, the
wire, the first call of a fresh ``jax.jit(traced)`` or a dispatch).  Read from
the program's own table of its spans, the gauge ``train.span_profile`` that
``Workflow.train`` sets under a tracer."""

LAYER = "host prologue and SanityChecker/RFF"
UNIT = "s"
SOURCE = "program_span"
MOVES = "train_wall_s"

SPAN = "transform.apply"


def read(ctx):
    if not ctx.get("trace"):
        return None
    from transmogrifai_tpu.telemetry import REGISTRY
    profile = REGISTRY.gauge("train.span_profile").value
    row = profile.get(SPAN) if isinstance(profile, dict) else None
    return row["total_s"] if row else None

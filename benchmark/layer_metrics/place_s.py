"""Host to device link: seconds of the traced train inside
``selector.place``, where the sweep lays the matrix, the label, the fold
assignment and the masks over the mesh.  Read from the program's own table of
its spans, the gauge ``train.span_profile`` that ``Workflow.train`` sets
under a tracer."""

LAYER = "host to device link"
UNIT = "s"
SOURCE = "program_span"
MOVES = "train_wall_s"

SPAN = "selector.place"


def read(ctx):
    if not ctx.get("trace"):
        return None
    from transmogrifai_tpu.telemetry import REGISTRY
    profile = REGISTRY.gauge("train.span_profile").value
    row = profile.get(SPAN) if isinstance(profile, dict) else None
    return row["total_s"] if row else None

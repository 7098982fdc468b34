"""Host prologue and SanityChecker/RFF: seconds of the traced train inside
``prefetch.lists``, the calling thread's wall for the walks of the date-list
columns and of the URL and e-mail columns whose domains a pivot reads
(``ops.list_profile``): its joins of the pool's jobs, counted in
``prologue.wait_s``, plus whatever it walked itself.  The worker spans
``prefetch.list_walk`` say which column.  Read from the program's own table
of its spans, the gauge ``train.span_profile`` that ``Workflow.train`` sets
under a tracer; nothing where the program has no such span."""

LAYER = "host prologue and SanityChecker/RFF"
UNIT = "s"
SOURCE = "program_span"
MOVES = "train_wall_s"

SPAN = "prefetch.lists"


def read(ctx):
    if not ctx.get("trace"):
        return None
    from transmogrifai_tpu.telemetry import REGISTRY
    profile = REGISTRY.gauge("train.span_profile").value
    row = profile.get(SPAN) if isinstance(profile, dict) else None
    return row["total_s"] if row else None

"""Device: 1 - union of device-operation intervals over the traced window,
from the benchmark's own reduction of the profiler's trace."""

LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_wall_s"


def read(ctx):
    tr = ctx.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])

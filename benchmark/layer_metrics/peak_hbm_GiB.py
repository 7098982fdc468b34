"""Device: ``memory_stats()['peak_bytes_in_use']`` of the fullest device
after the window."""

LAYER = "device"
UNIT = "GiB"
SOURCE = "program_counter"
MOVES = "train_wall_s"


def read(ctx):
    peak = ctx.get("memory_peak_bytes")
    return peak / 2.0 ** 30 if peak else None

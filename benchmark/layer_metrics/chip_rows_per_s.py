"""Whole step: rows a chip takes through a whole train a second: the
configuration's rows over ``train_wall_s`` over its row shards
(``configs/criteo_mixed_x4.json``).  To be read beside 196,608 over
``mixed_sweep``'s ``train_wall_s``, the same rows a chip on one chip: the two
are equal where the partition costs nothing."""

from .sweep_mfu_x4 import deployment

LAYER = "whole step"
UNIT = "rows/s"
SOURCE = "host_clock"
MOVES = "train_wall_s"


def read(ctx):
    if not ctx.get("train_wall_s"):
        return None
    config = deployment()
    return (config["rows"] / ctx["train_wall_s"]
            / config["partitions"]["row_shards"])

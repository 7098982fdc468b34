"""Whole step: ``sweep_mfu`` of a deployment whose rows are partitioned over
several chips: the least time ALL its chips could take for ONE train's
required work, the larger of operations over their peak FLOP/s and bytes over
their peak bytes/s, as a share of ``train_wall_s``.  Operations and bytes
are those of ``work/`` at the configuration's rows, the peaks ``peaks.json``'s
of one chip times the configuration's row shards (``partitions.row_shards``
of ``configs/criteo_mixed_x4.json``)."""

import json
import os

LAYER = "whole step"
UNIT = "%"
SOURCE = "host_clock"
MOVES = "train_wall_s"

CONFIG = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs", "criteo_mixed_x4.json")


def deployment():
    """The configuration's file: its rows and how they are partitioned."""
    with open(CONFIG) as fh:
        return json.load(fh)


def read(ctx):
    work, peaks = ctx.get("work"), ctx.get("peaks")
    if not work or not peaks or not ctx.get("train_wall_s"):
        return None
    n = deployment()["partitions"]["row_shards"]
    least = max(work["ops"] / (n * peaks["flops_per_s"]),
                work["bytes"] / (n * peaks["bytes_per_s"]))
    share = 100.0 * least / ctx["train_wall_s"]
    if share > 100.0:
        raise RuntimeError(f"sweep_mfu_x4 reads {share} %: the work "
                           "functions count too much or the wall leaves "
                           "work out")
    return share

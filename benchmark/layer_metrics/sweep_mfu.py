"""Whole step: the least time the chip could take for ONE train's required
work, the larger of operations over peak FLOP/s and bytes over peak bytes/s,
as a share of ``train_wall_s``.  Operations and bytes come from the functions
of the cell's shapes in ``work/`` and the peaks from ``peaks.json``: the same
number whatever implements the solver.  Bytes bind it in ``mixed_sweep``:
PERF.md section 3."""

LAYER = "whole step"
UNIT = "%"
SOURCE = "host_clock"
MOVES = "train_wall_s"


def read(ctx):
    work, peaks = ctx.get("work"), ctx.get("peaks")
    if not work or not peaks or not ctx.get("train_wall_s"):
        return None
    least = max(work["ops"] / peaks["flops_per_s"],
                work["bytes"] / peaks["bytes_per_s"])
    share = 100.0 * least / ctx["train_wall_s"]
    if share > 100.0:
        raise RuntimeError(f"sweep_mfu reads {share} %: the work functions "
                           "count too much or the wall leaves work out")
    return share

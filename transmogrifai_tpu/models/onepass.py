"""One read of the stored matrix for both products of a FISTA iteration.

A proximal-gradient iteration needs ``z = Xs v`` for every lane, the loss's
derivative at ``z`` and ``Xs^T g``.  XLA makes those two products two passes
over the matrix: no fusion holds a product together with a second one that
contracts the other axis of the same operand.  This Pallas kernel holds one
row block of X in VMEM and does both with it, for every lane of a stage at
once (the lanes are the rows of the small operands, so they cost the MXU
nothing the matrix does not already):

    Xp    = X_blk about the pivot, rounded to bfloat16 once
    z^T   = (Vs · Xp^T − c) + b                [L, rows]   lanes × rows
    g^T   = w ⊙ loss'(z, y) / wsum             (or wn ⊙ z for the power form)
    acc  += bf16(g^T) · Xp                     [L, D]      float32
    gsum += Σ_rows g^T                         [L]

Only the summation order differs from the two XLA products: the operands are
rounded to bfloat16 where the chip's DEFAULT precision dot rounds them, and
every sum is float32.  The caller finishes ``Xs^T g = (acc − off·gsum)/scale``
as it did after the XLA product.

``engages`` is the rule that picks this path; ``models/solvers.py`` is its
only caller.
"""

from __future__ import annotations

import functools
import importlib
import threading
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

# Pallas is imported where a kernel is built (or by ``warm``): its import
# costs 1.3 s on the chip's host, which a process that never takes this
# path would pay for nothing
_WARMING = threading.Lock()


def warm() -> None:
    """On a TPU, start importing Pallas on a thread of its own, once a
    process, so that the first fit that builds the kernel does not wait
    for it (``Workflow.train`` calls this before its prologue)."""
    if not _WARMING.acquire(blocking=False):
        return
    if jax.default_backend() == "tpu":
        threading.Thread(
            target=importlib.import_module, name="onepass-import",
            args=("jax.experimental.pallas.tpu",), daemon=True).start()


# the pointwise derivative of each loss the kernel computes, as
# ``solvers.LOSSES`` computes it (it imports these)
POINTWISE = {
    "logistic": lambda z, y: jax.nn.sigmoid(z) - y,
    "squared_hinge": lambda z, y: -2.0 * jnp.maximum(0.0, 1.0 - y * z) * y,
}
POWER = "power"          # the power iteration's form: u = wn ⊙ z

# a block of about this many bytes as VMEM lays it out (the width rounded
# up to 16 sublanes); the pipeline holds two of them.  On a v5e a pass over
# mixed_sweep's matrix took 5.08 / 4.81 / 4.65 ms at 4 / 8 / 16 MiB
_BLOCK_BYTES = 16 * 2 ** 20
_MIN_ROWS, _MAX_ROWS = 128, 32768


def column_major(X) -> bool:
    """Whether ``X`` [N, D] is laid out column by column, as the chip lays
    out a matrix whose width is not a multiple of 128 (it pads less): then
    ``X.T`` is the same bytes, read here as a row-major [D, N] matrix."""
    layout = getattr(getattr(X, "format", None), "layout", None)
    return tuple(getattr(layout, "major_to_minor", ())) == (1, 0)


def engages(X, loss: str, n_classes: int) -> bool:
    """Whether a dense linear fit of ``X`` takes this kernel: a two-
    dimensional array on ONE TPU device, laid out column by column, a loss
    the kernel computes and one output column.  Everything else (the CPU, a
    mesh, a row-major matrix, softmax, squared, poisson, gamma, a sparse
    matrix) keeps the two XLA products."""
    if loss not in POINTWISE or n_classes != 1 or getattr(X, "ndim", 0) != 2:
        return False
    devices = getattr(getattr(X, "sharding", None), "device_set", ())
    return (len(devices) == 1 and all(d.platform == "tpu" for d in devices)
            and column_major(X))


def block_rows(d: int, itemsize: int) -> int:
    """Rows of X in one block of its [D, N] view: a power of two, so that it
    divides the sweep's padded row counts, from the block's bytes."""
    col_bytes = -(-d // 16) * 16 * itemsize
    rows = _MIN_ROWS
    while rows * 2 <= _MAX_ROWS and rows * 2 * col_bytes <= _BLOCK_BYTES:
        rows *= 2
    return rows


def _kernel(xt_ref, v_ref, c_ref, b_ref, piv_ref, y_ref, w_ref, ws_ref,
            acc_ref, gsum_ref, *, form: str, grid_points: int, folds: int,
            rows: int, n: int):
    from jax.experimental import pallas as pl
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        gsum_ref[...] = jnp.zeros_like(gsum_ref)

    def block(masked: bool):
        xt = xt_ref[...]                                        # [D, rows]
        if piv_ref is not None:
            xt = (xt.astype(jnp.float32) - piv_ref[...]).astype(jnp.bfloat16)
        else:
            xt = xt.astype(jnp.bfloat16)
        col = i * rows + jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
        if masked:
            # rows past n hold whatever the buffer held: a zero weight
            # would still carry a NaN into both products
            xt = jnp.where(col < n, xt, jnp.zeros_like(xt))
        z = jnp.dot(v_ref[...], xt, preferred_element_type=jnp.float32)
        z = (z - c_ref[...]) + b_ref[...]                       # [L, rows]
        fold = jax.lax.broadcasted_iota(jnp.int32, z.shape, 0) // grid_points
        w = jnp.zeros_like(z)
        for f in range(folds):
            w = jnp.where(fold == f, w_ref[pl.ds(f, 1), :], w)
        if form == POWER:
            g = (w / ws_ref[...]) * z
        else:
            g = w * POINTWISE[form](z, y_ref[...]) / ws_ref[...]
        if masked:
            g = jnp.where(col < n, g, 0.0)
        acc_ref[...] += jax.lax.dot_general(
            g.astype(jnp.bfloat16), xt, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        gsum_ref[...] += jnp.sum(g, axis=1, keepdims=True)

    if n % rows == 0:
        block(False)
    else:
        last = pl.num_programs(0) - 1

        @pl.when(i < last)
        def _():
            block(False)

        @pl.when(i == last)
        def _():
            block(True)


@jax.named_scope("linear.onepass")
def grad(X: jnp.ndarray, vs: jnp.ndarray, c: jnp.ndarray, b: jnp.ndarray,
         pivot: Optional[jnp.ndarray], target: jnp.ndarray,
         weights: jnp.ndarray, wsum: jnp.ndarray, *, form: str,
         grid_points: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(acc [L, D], gsum [L]) in one pass over the rows of ``X`` [N', D],
    read through its column-major layout (``column_major``; another layout
    costs XLA a transposed copy first).

    ``vs`` [L, D] is each lane's coefficient over the columns' scale
    (rounded to bfloat16 here), ``c`` [L] the mean about the pivot times it
    and ``b`` [L] the lane's intercept.  Lane ``l`` belongs to fold
    ``l // grid_points``: its row weights are ``weights[fold]`` ([F, N])
    and their sum ``wsum[fold]`` ([F]).  ``form`` is a key of ``POINTWISE``
    or ``POWER``.  Only the first ``N = len(target)`` rows of ``X`` are
    read into the result."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    n, (_, d) = target.shape[0], X.shape
    lanes, folds = vs.shape[0], weights.shape[0]
    rows = block_rows(d, X.dtype.itemsize)
    whole = lambda i: (0, 0)                                  # noqa: E731
    by_rows = lambda i: (0, i)                                # noqa: E731
    lane_col = lambda a: a.astype(jnp.float32).reshape(lanes, 1)  # noqa: E731
    args = [X.T, vs.astype(jnp.bfloat16), lane_col(c), lane_col(b)]
    specs = [pl.BlockSpec((d, rows), by_rows),
             pl.BlockSpec((lanes, d), whole),
             pl.BlockSpec((lanes, 1), whole), pl.BlockSpec((lanes, 1), whole)]
    if pivot is not None:
        args.append(pivot.astype(jnp.float32).reshape(d, 1))
        specs.append(pl.BlockSpec((d, 1), whole))
    args += [target.astype(jnp.float32).reshape(1, n),
             weights.astype(jnp.float32),
             lane_col(jnp.repeat(wsum, grid_points))]
    specs += [pl.BlockSpec((1, rows), by_rows),
              pl.BlockSpec((folds, rows), by_rows),
              pl.BlockSpec((lanes, 1), whole)]
    body = functools.partial(_kernel, form=form, grid_points=grid_points,
                             folds=folds, rows=rows, n=n)
    kernel = body if pivot is not None else (
        lambda xt, v, c_, b_, *rest: body(xt, v, c_, b_, None, *rest))
    block_bytes = rows * (-(-d // 16) * 16) * X.dtype.itemsize
    acc, gsum = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(n, rows),),
        in_specs=specs,
        out_specs=[pl.BlockSpec((lanes, d), whole),
                   pl.BlockSpec((lanes, 1), whole)],
        out_shape=[jax.ShapeDtypeStruct((lanes, d), jnp.float32),
                   jax.ShapeDtypeStruct((lanes, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=int(min(100 * 2 ** 20,
                                     8 * block_bytes + 16 * 2 ** 20))),
        name="linear_onepass",
    )(*args)
    return acc, gsum[:, 0]

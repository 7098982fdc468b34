"""The hashed-text count matrix, one block of rows at a time on the MXU.

The packed wire (``ops/text.py``) carries every row's token bucket ids in
turn, three 10-bit ids a word: slot ``3 w + l`` is lane ``l`` of word ``w``,
and row ``r`` holds the slots ``[start_r, end_r)`` that the cumulative sum of
the rows' lengths gives.  XLA builds the counts as a scatter-add of one a
slot, which it sorts first.  This kernel reads the words that hold a block's
slots, a tile of 1,024 words at a time, and counts with two one-hot matrices
instead, for each lane ``l`` of the tile:

    B^T[h, k] = (id of word k, lane l) == h          [H, 1024]   int8
    M^T[k, r] = start_r <= 3 w_k + l < end_r         [1024, R]   int8
    acc[h, r] += B^T · M^T                           [H, R]      int32

Both operands are one-hots and the MXU sums their products in int32, so a
tile's counts are exact; a count is an integer far below 2^24, so the float32
sum over tiles is exact too: the matrix equals the scatter's to the bit.
int8 operands run the MXU at twice bfloat16's rate (on one v5e, 0.093 s
against 0.150 for ``text_sweep``'s review text).  A slot outside the block's
rows (the next block's tokens in a shared tile, the padding row, a final
word's sentinel lanes) meets no row of M and counts nowhere, so no slot
carries a row id.

The kernel writes the counts transposed, ``[H, N]``: each block's columns
once, in the stored dtype.  ``ops/text.py`` holds the rule that picks this
path (``_kernel_engages``) and is its only caller.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# rows a block: the MXU's width
_ROWS = 128
# words a tile: one [8, 128] int32 tile, copied from HBM as a unit
_TILE = 1024


def _kernel(t0_ref, t1_ref, bounds_ref, words_hbm, out_ref, buf, sem, acc_ref,
            *, binary: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    b = pl.program_id(0)
    t0, t1 = t0_ref[b], t1_ref[b]
    bins, rows = acc_ref.shape
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def copy(t, slot):
        return pltpu.make_async_copy(words_hbm.at[t], buf.at[slot],
                                     sem.at[slot])

    @pl.when(t1 > t0)
    def _():
        copy(t0, 0).start()

    def tile(t, carry):
        slot = jax.lax.rem(t - t0, 2)

        @pl.when(t + 1 < t1)
        def _():
            copy(t + 1, 1 - slot).start()

        copy(t, slot).wait()
        w = buf[slot]                                          # [8, 128]
        bin_of = jax.lax.broadcasted_iota(jnp.int32, (bins, 128), 0)
        word = jax.lax.broadcasted_iota(jnp.int32, (_TILE, rows), 0)
        total = jnp.zeros((bins, rows), jnp.int32)
        for lane in range(3):
            ids = (w >> (10 * lane)) & 0x3FF
            bt = jnp.concatenate(
                [jnp.where(bin_of == ids[s:s + 1, :], 1, 0)
                 for s in range(8)], axis=1).astype(jnp.int8)
            # the block's rows as word offsets into this tile
            lo = bounds_ref[lane:lane + 1, :] - t * _TILE
            hi = bounds_ref[3 + lane:4 + lane, :] - t * _TILE
            mt = jnp.where((word >= lo) & (word < hi), 1, 0).astype(jnp.int8)
            total += jnp.dot(bt, mt, preferred_element_type=jnp.int32)
        acc_ref[...] += total.astype(jnp.float32)
        return carry

    jax.lax.fori_loop(t0, t1, tile, 0)
    acc = acc_ref[...]
    out_ref[...] = (jnp.where(acc > 0, 1.0, 0.0) if binary
                    else acc).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def counts(words: jnp.ndarray, lens_padded: jnp.ndarray, num_hashes: int,
           binary: bool, dtype) -> jnp.ndarray:
    """``[n, num_hashes]`` counts (or, ``binary``, their indicators) in
    ``dtype`` of the packed ``words`` [cap] whose rows hold ``lens_padded``
    [n + 1] tokens each, the last entry the padding's.  A jit of its own, so
    that a fused program traced anew each train finds the kernel's trace
    made."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    n, cap = lens_padded.shape[0] - 1, words.shape[0]
    if n == 0:
        return jnp.zeros((0, num_hashes), dtype)
    bins = -(-num_hashes // 32) * 32        # a whole int8 sublane tile
    blocks = -(-n // _ROWS)
    tiles = -(-cap // _TILE)
    if tiles * _TILE != cap:
        # slots past the wire lie past every row's end: never counted
        words = jnp.pad(words, (0, tiles * _TILE - cap))
    lens = lens_padded[:n]
    ends = jnp.cumsum(lens)
    fill = jnp.full((blocks * _ROWS - n,), ends[-1], ends.dtype)
    ends = jnp.concatenate([ends, fill])
    starts = jnp.concatenate([ends[:n] - lens, fill])
    # the words of each lane a row's slots lie in: 3 w + l in [start, end)
    # for w in [(start - l + 2) // 3, (end - l + 2) // 3)
    bounds = jnp.stack(
        [(starts - lane + 2) // 3 for lane in range(3)]
        + [(ends - lane + 2) // 3 for lane in range(3)]
        + [jnp.zeros_like(ends)] * 2)            # [8, rows]: one int32 tile
    bounds = bounds.reshape(8, blocks, _ROWS).transpose(1, 0, 2)
    lo, hi = starts[::_ROWS], ends[_ROWS - 1::_ROWS]
    t0 = lo // (3 * _TILE)
    t1 = jnp.where(hi > lo, (hi - 1) // (3 * _TILE) + 1, t0)
    out = pl.pallas_call(
        functools.partial(_kernel, binary=binary),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(blocks,),
            in_specs=[pl.BlockSpec((None, 8, _ROWS),
                                   lambda b, t0, t1: (b, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((bins, _ROWS), lambda b, t0, t1: (0, b)),
            scratch_shapes=[pltpu.VMEM((2, 8, 128), jnp.int32),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.VMEM((bins, _ROWS), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((bins, blocks * _ROWS), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="hash_counts",
    )(t0, t1, bounds, words.reshape(tiles, 8, 128))
    return out[:num_hashes, :n].T

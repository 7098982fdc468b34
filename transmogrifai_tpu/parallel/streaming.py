"""Chunked host→device streaming for mesh-sharded arrays.

The one-shot ``jax.device_put(X, data_sharding(mesh, 2))`` stages the whole
host matrix at once: at 11M × 1596 f32 that is a ~70GB transient on top of
the resident copy, which is exactly the cumulative-HBM/host-RSS pressure
that kills a single worker.  This module
assembles each device's row shard from bounded host slices instead:

  * at most two chunk-sized host staging buffers are alive at any moment
    (double buffering: chunk *i* transfers while chunk *i+1* is sliced), so
    peak staging is O(TRANSMOGRIFAI_DEVICE_CHUNK_BYTES), not O(dataset);
  * pad rows (the device-divisibility quantum) are
    synthesised on-device with ``jnp.zeros`` — zero host-link bytes;
  * the assembled shards are stitched into one logically-sharded array via
    ``jax.make_array_from_single_device_arrays``, indistinguishable to the
    compiled program from a one-shot ``device_put``.

Chunks are converted to f32 with the same elementwise ``astype`` the
one-shot path used, so the streamed array is bitwise-identical to
``jax.device_put(jnp.asarray(X, jnp.float32), sharding)`` on the real rows.
"""

from __future__ import annotations

import math
import os
import threading
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .mesh import data_sharding

_DEFAULT_CHUNK_BYTES = 256 * 1024 * 1024


def _put_chunk(buf, dev, seq: int):
    """One supervised chunk transfer.  A hung host→device link surfaces as
    a typed ``TransferStallError``
    within the TRANSMOGRIFAI_CHUNK_DEADLINE_S budget instead of blocking
    the stream forever; ``supervisor.chunk_stall`` is the chaos-injection
    point, keyed by a monotone per-process chunk sequence so a sticky
    fail_keys entry stalls one specific chunk and the sweep-recovery
    re-stream proceeds cleanly."""
    from ..resilience import (InjectedFault, WatchdogTimeout, maybe_inject,
                              run_with_deadline)
    from .supervisor import TransferStallError, chunk_deadline_s
    deadline = chunk_deadline_s()
    try:
        maybe_inject("supervisor.chunk_stall", key=seq)
        if deadline is None:
            return jax.device_put(buf, dev)
        return run_with_deadline(jax.device_put, deadline, buf, dev,
                                 description="mesh.stream_chunk")
    except (InjectedFault, WatchdogTimeout) as e:
        raise TransferStallError(
            f"host->device chunk {seq} to {dev} stalled: {e}") from e

_lock = threading.Lock()
_STATS = {
    "chunks": 0,
    "bytes_streamed": 0,
    "staging_bytes": 0,
    "peak_staging_bytes": 0,
    "pad_rows": 0,
    "arrays": 0,
}


def device_chunk_bytes() -> int:
    """Host-staging budget per transfer chunk
    (TRANSMOGRIFAI_DEVICE_CHUNK_BYTES, default 256MB)."""
    try:
        v = int(os.environ.get("TRANSMOGRIFAI_DEVICE_CHUNK_BYTES",
                               _DEFAULT_CHUNK_BYTES))
    except ValueError:
        return _DEFAULT_CHUNK_BYTES
    return max(1, v)


def streaming_stats() -> dict:
    with _lock:
        return dict(_STATS)


def reset_streaming_stats() -> None:
    with _lock:
        for k in _STATS:
            _STATS[k] = 0


def _stage(nbytes: int) -> None:
    with _lock:
        _STATS["staging_bytes"] += nbytes
        if _STATS["staging_bytes"] > _STATS["peak_staging_bytes"]:
            _STATS["peak_staging_bytes"] = _STATS["staging_bytes"]


def _unstage(nbytes: int) -> None:
    with _lock:
        _STATS["staging_bytes"] -= nbytes


def _row_slice(shape: Tuple[int, ...], row_axis: int,
               start: int, stop: int) -> Tuple[slice, ...]:
    idx = [slice(None)] * len(shape)
    idx[row_axis] = slice(start, stop)
    return tuple(idx)


def stream_to_device(arr,
                     mesh,
                     ndim: Optional[int] = None,
                     row_axis: int = 0,
                     chunk_bytes: Optional[int] = None,
                     pad_to: Optional[int] = None,
                     dtype=jnp.float32,
                     row_offset: int = 0,
                     global_rows: Optional[int] = None) -> jax.Array:
    """Build a data-sharded device array from ``arr`` through bounded host
    chunks, optionally padding ``row_axis`` up to ``pad_to`` with zero rows.

    Returns the same logical array as
    ``jax.device_put(jnp.asarray(arr_padded, dtype), data_sharding(...))``
    with peak host staging bounded by ~2×``chunk_bytes``.

    Multi-process (host group): ``arr`` may be just this rank's row shard —
    its reader slice — positioned in the global row space by ``row_offset``
    with ``global_rows`` the full logical row count (``mesh.process_row_range``
    computes the slice to materialize).  Each process ``device_put``s only
    its own addressable shards from its own slice; the shards assemble via
    ``make_array_from_single_device_arrays`` into the same global array,
    bitwise-equal to the single-process path on the real rows, with the
    staging bound unchanged.  A slice that does not cover this process's
    shard extent raises ``ValueError`` (typed, never silent misalignment).
    """
    from ..profiling import add_host_link_bytes
    from ..telemetry import REGISTRY, event, span

    # one device data plane (ISSUE 19): DeviceTable and SparseMatrix
    # payloads stream under the SAME chunk budget and staging bound —
    # dense tables chunk by rows, sparse tables by nnz ranges
    from .device_table import DeviceTable
    from ..sparse.matrix import SparseMatrix
    if isinstance(arr, SparseMatrix):
        arr = DeviceTable.from_sparse(arr, row_offset=row_offset,
                                      global_rows=global_rows)
    if isinstance(arr, DeviceTable):
        return arr.to_device(mesh, pad_to=pad_to, chunk_bytes=chunk_bytes)

    host = np.asarray(arr)
    if ndim is None:
        ndim = host.ndim
    n_local = host.shape[row_axis]
    row_offset = int(row_offset)
    n_rows = int(global_rows) if global_rows is not None \
        else row_offset + n_local
    if row_offset < 0 or row_offset + n_local > n_rows:
        raise ValueError(
            f"stream_to_device: local slice [{row_offset}, "
            f"{row_offset + n_local}) exceeds the global row space "
            f"[0, {n_rows})")
    total_rows = n_rows if pad_to is None else max(pad_to, n_rows)
    target_shape = list(host.shape)
    target_shape[row_axis] = total_rows
    target_shape = tuple(target_shape)

    sharding = data_sharding(mesh, ndim=ndim, row_axis=row_axis)
    np_dtype = np.dtype(dtype.dtype if hasattr(dtype, "dtype") else dtype)
    row_bytes = np_dtype.itemsize * max(
        1, int(np.prod([s for a, s in enumerate(target_shape)
                        if a != row_axis])))
    # the memory-governor degrade ladder halves the chunk budget per rung:
    # applies to explicit planner-chosen budgets too, so a post-OOM retry
    # streams smaller even when the caller pinned chunk_bytes
    from .memory import effective_chunk_bytes
    budget = effective_chunk_bytes(
        chunk_bytes if chunk_bytes is not None else device_chunk_bytes())
    chunk_rows = max(1, budget // row_bytes)

    REGISTRY.gauge("mesh.chunk_bytes").set(budget)
    h2d = REGISTRY.counter("host_to_device_bytes_total")

    # per-device shard extents under this sharding of the *padded* shape
    dev_map = sharding.addressable_devices_indices_map(target_shape)

    shards = []
    inflight = []  # (device_array, host_buffer, staged_bytes) double buffer
    with span("mesh.stream_to_device", rows=int(n_rows),
              local_rows=int(n_local), row_offset=int(row_offset),
              pad_rows=int(total_rows - n_rows),
              devices=len(dev_map), chunk_rows=int(chunk_rows)):
        for dev, idx in dev_map.items():
            rsl = idx[row_axis]
            start = 0 if rsl.start is None else rsl.start
            stop = total_rows if rsl.stop is None else rsl.stop
            real_stop = min(stop, n_rows)
            if start < real_stop and (start < row_offset
                                      or real_stop > row_offset + n_local):
                raise ValueError(
                    f"stream_to_device: this process's shard on {dev} "
                    f"needs global rows [{start}, {real_stop}) but the "
                    f"local slice only covers [{row_offset}, "
                    f"{row_offset + n_local}) — pass the slice from "
                    f"mesh.process_row_range")
            pieces = []
            pos = start
            while pos < real_stop:
                end = min(pos + chunk_rows, real_stop)
                view = host[_row_slice(host.shape, row_axis,
                                       pos - row_offset, end - row_offset)]
                buf = np.ascontiguousarray(view, dtype=np_dtype)
                nbytes = buf.nbytes
                _stage(nbytes)
                from .supervisor import next_chunk_key
                seq = next_chunk_key()
                with span("mesh.stream_chunk", device=str(dev),
                          rows=int(end - pos), bytes=int(nbytes),
                          seq=int(seq)):
                    try:
                        piece = _put_chunk(buf, dev, seq)
                    except BaseException:
                        _unstage(nbytes)
                        raise
                # double buffering: keep this chunk's host buffer alive while
                # its transfer is in flight, but before slicing a third chunk
                # retire the oldest one — at most two staging buffers exist.
                inflight.append((piece, buf, nbytes))
                if len(inflight) > 1:
                    old_piece, _old_buf, old_bytes = inflight.pop(0)
                    old_piece.block_until_ready()
                    _unstage(old_bytes)
                h2d.inc(nbytes)
                add_host_link_bytes(nbytes)
                with _lock:
                    _STATS["chunks"] += 1
                    _STATS["bytes_streamed"] += nbytes
                pieces.append(piece)
                pos = end
            if stop > real_stop:  # zero pad rows synthesised on-device
                pad_shape = list(target_shape)
                pad_shape[row_axis] = stop - max(real_stop, start)
                pieces.append(jax.device_put(
                    jnp.zeros(tuple(pad_shape), dtype=np_dtype), dev))
                with _lock:
                    _STATS["pad_rows"] += pad_shape[row_axis]
            if len(pieces) == 1:
                shard = pieces[0]
            else:
                shard = jnp.concatenate(pieces, axis=row_axis)
            shards.append(shard)
        while inflight:
            piece, _buf, nbytes = inflight.pop(0)
            piece.block_until_ready()
            _unstage(nbytes)
        out = jax.make_array_from_single_device_arrays(
            target_shape, sharding, shards)
    with _lock:
        _STATS["arrays"] += 1
    if total_rows != n_rows:
        event("mesh.stream_pad", rows=int(n_rows),
              pad_rows=int(total_rows - n_rows))
    return out

"""Pallas TPU kernel: sparse weight-delta scatter (low-latency update, §4.3).

GPU scatter uses atomics; the TPU has no scatter unit, so we ADAPT
(DESIGN.md §2): scatter-as-compare.  The flat parameter buffer is tiled
over the grid's outer axis and the delta over its inner axis, in chunks
of ``DELTA_CHUNK``; each (tile, chunk) step builds
`hit = indices - tile_start ∈ [0, tile)` and reduces a one-hot selection
over the chunk on the VPU into the tile's output block, which stays
resident across the chunk axis.  Indices are unique (the WeightStore
guarantees one row per flat index per version), so each position is
touched at most once.

Cost: O(tiles × n_delta) compares — bandwidth-optimal in HBM terms (buffer
read once, written once; delta read per-tile from VMEM) and far cheaper
than a full-buffer download, which is the paper's point.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Deltas per grid step.  The step's (block, DELTA_CHUNK) one-hot select is
# the kernel's VMEM need: at block=4096 a chunk of 512 takes ~9 MiB of
# the 16 MiB scoped VMEM of a TPU v5e, and 1024 (17.6 MiB) is refused by
# the compiler.  The same chunking runs in interpret mode.
DELTA_CHUNK = 512


def _kernel(buf_ref, idx_ref, val_ref, out_ref, *, block: int):
    start = pl.program_id(0) * block

    @pl.when(pl.program_id(1) == 0)
    def _load():
        out_ref[...] = buf_ref[...]

    idx = idx_ref[...].astype(jnp.int32)              # (1, DELTA_CHUNK)
    val = val_ref[...].astype(jnp.float32)            # (1, DELTA_CHUNK)
    pos = idx - start
    in_tile = (pos >= 0) & (pos < block)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (block, idx.shape[1]), 0)
    onehot = (lanes == pos) & in_tile                  # (block, DELTA_CHUNK)
    update = jnp.sum(jnp.where(onehot, val, 0.0), axis=1)          # (block,)
    touched = jnp.any(onehot, axis=1)                  # (block,)
    out_ref[...] = jnp.where(
        touched[None, :], update[None, :].astype(out_ref.dtype), out_ref[...]
    )


def _call(buf, indices, values, *, block, interpret, alias):
    (n,) = buf.shape
    assert n % block == 0, (n, block)
    # pad the delta to whole chunks with index n: past every tile, inert
    pad = (-indices.shape[0]) % DELTA_CHUNK
    idx = jnp.pad(indices.astype(jnp.int32), (0, pad), constant_values=n)
    val = jnp.pad(values, (0, pad))
    grid = (n // block, idx.shape[0] // DELTA_CHUNK)
    return pl.pallas_call(
        functools.partial(_kernel, block=block),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block), lambda i, j: (0, i)),
            pl.BlockSpec((1, DELTA_CHUNK), lambda i, j: (0, j)),
            pl.BlockSpec((1, DELTA_CHUNK), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, block), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n), buf.dtype),
        input_output_aliases={0: 0} if alias else {},
        interpret=interpret,
    )(buf.reshape(1, n), idx.reshape(1, -1), val.reshape(1, -1)).reshape(n)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def delta_apply(
    buf: jnp.ndarray,
    indices: jnp.ndarray,
    values: jnp.ndarray,
    *,
    block: int = 4096,
    interpret: bool = False,
) -> jnp.ndarray:
    """Set buf[indices] = values (indices unique; padding idx >= buf.size).

    buf is flat (N,) with N % block == 0 (``ops.delta_apply`` pads); indices
    int32/int64 (n,), values (n,) castable to buf.dtype.
    """
    return _call(buf, indices, values, block=block, interpret=interpret,
                 alias=False)


@functools.partial(jax.jit, static_argnames=("block", "interpret"),
                   donate_argnums=(0,))
def delta_apply_inplace(
    buf: jnp.ndarray,
    indices: jnp.ndarray,
    values: jnp.ndarray,
    *,
    block: int = 4096,
    interpret: bool = False,
) -> jnp.ndarray:
    """:func:`delta_apply` that consumes ``buf``: the parameter buffer is
    donated and the scatter lands in place (``input_output_aliases``), so
    a staged weight update writes O(delta) bytes instead of cloning the
    whole layer per applied part.  The caller's ``buf`` array is invalid
    afterwards; backends without donation fall back to a copy."""
    return _call(buf, indices, values, block=block, interpret=interpret,
                 alias=True)

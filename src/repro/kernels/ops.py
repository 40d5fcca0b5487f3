"""Public jit'd wrappers around the Pallas kernels.

Handles padding or exact tiling to block multiples, backend selection
(interpret=True on CPU so the kernel *body* is what runs in tests), and
the pure-jnp fallback of the masked dequant and the delta scatter for
shapes too small to tile.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import delta_apply as _delta
from repro.kernels import masked_dequant as _mask
from repro.kernels import quant_matmul as _qmm
from repro.kernels import ref

MAX_INTERVALS = _mask.MAX_INTERVALS


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def _pad_to(x: jnp.ndarray, mults: Tuple[int, ...], value=0) -> jnp.ndarray:
    pads = []
    for dim, m in zip(x.shape, mults):
        pads.append((0, (-dim) % m))
    if not any(p[1] for p in pads):
        return x
    return jnp.pad(x, pads, constant_values=value)


def _tiles(dim: int, cap: int):
    """Block sizes that tile ``dim`` exactly, largest first: its divisors
    that are multiples of 128 and at most ``cap``, or ``dim`` itself."""
    fits = [b for b in range(128, min(dim, cap) + 1, 128) if dim % b == 0]
    return sorted(fits, reverse=True) or [dim]


def _licensed_blocks(m: int, k: int, n: int, itemsize: int):
    """(block_m, block_k, block_n) for :func:`licensed_matmul`: the weight
    is tiled exactly (padding a stacked weight would copy it), tiles hold
    at most 1M codes, and the blocks' VMEM — double-buffered activation,
    codes and output, the f32 accumulator and the mask's int32
    temporaries — stays within ~3/4 of the kernel's limit.  Rows are one
    block where they fit (up to 2048), so every weight tile is read and
    masked once per call; else the largest row block that fits.  Raises
    ValueError when no blocks fit."""
    budget = _qmm.VMEM_LIMIT * 3 // 4
    for bm in ([m] if m <= 2048 else []) + [512, 256, 128, 8]:
        best = None
        for bn in _tiles(n, 4096):
            for bk in _tiles(k, 4096):
                tile = bk * bn
                vmem = (2 * bm * bk * itemsize + 2 * tile + bm * bn * 4
                        + 2 * bm * bn * itemsize + 4 * tile * 4)
                if tile <= 1 << 20 and vmem <= budget and (
                        best is None or (tile, bn) > (best[1] * best[2], best[2])):
                    best = (bm, bk, bn)
        if best is not None:
            return best
    raise ValueError(f"no Pallas blocks tile an ({m}x{k}) @ ({k}x{n}) "
                     f"int8 matmul within {budget} bytes of VMEM")


def licensed_matmul(x: jnp.ndarray, codes: jnp.ndarray, scale: jnp.ndarray,
                    dead=None, unit=0, *, interpret: bool = False):
    """``x (…,K) @ mask(codes) * scale`` through the Pallas kernel, output
    in ``x.dtype``; ``codes`` (K,N) or (U,K,N) read at ``unit``, ``scale``
    (N,) or (1,N), ``dead`` None or a tier's code bounds (n,N) each.

    Under ``vmap`` over the activation alone (the lane-vmapped chunked
    prefill) the lanes fold into rows, so each weight tile is still read
    and masked once per call rather than once per lane; the blocks are
    chosen for the rows the kernel sees (:func:`_licensed_blocks`)."""
    k, n = codes.shape[-2:]

    def mm(x, codes, scale, dead, unit):
        m = int(np.prod(x.shape[:-1]))
        bm, bk, bn = _licensed_blocks(m, k, n, x.dtype.itemsize)
        x2 = _pad_to(x.reshape(m, k), (bm, 1))
        out = _qmm.quant_matmul(x2, codes, scale.reshape(n), dead, unit,
                                block_m=bm, block_n=bn, block_k=bk,
                                out_dtype=x.dtype, interpret=interpret)
        return out[:m].reshape(*x.shape[:-1], n)

    folded = jax.custom_batching.custom_vmap(mm)

    @folded.def_vmap
    def _fold_lanes(axis_size, in_batched, x, codes, scale, dead, unit):
        if not any(jax.tree_util.tree_leaves(in_batched[1:])):
            return folded(x, codes, scale, dead, unit), in_batched[0]
        axes = tuple(jax.tree_util.tree_map(lambda b: 0 if b else None, ib)
                     for ib in in_batched)
        return jax.vmap(mm, in_axes=axes)(x, codes, scale, dead, unit), True

    return folded(x, codes, scale, dead, jnp.asarray(unit, jnp.int32))


def pack_intervals(intervals: Sequence[Tuple[float, float]]) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Pad a license tier's interval list to (MAX_INTERVALS,) lo/hi arrays."""
    ivs = list(intervals)[:MAX_INTERVALS]
    lo = np.zeros(MAX_INTERVALS, np.float32)
    hi = np.zeros(MAX_INTERVALS, np.float32)
    for i, (a, b) in enumerate(ivs):
        lo[i], hi[i] = a, b
    return jnp.asarray(lo), jnp.asarray(hi)


def masked_dequant(
    codes: jnp.ndarray,
    scale: jnp.ndarray,
    intervals: Sequence[Tuple[float, float]] = (),
    *,
    out_dtype=jnp.float32,
    block_r: int = 256,
    block_c: int = 256,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Licensed weights from int8 codes in one fused pass (paper §3.5)."""
    interpret = _on_cpu() if interpret is None else interpret
    lo, hi = pack_intervals(intervals)
    r, c = codes.shape
    if r * c < 256 * 256:
        return ref.masked_dequant(codes, jnp.broadcast_to(scale, codes.shape), lo, hi, out_dtype)
    cp = _pad_to(codes, (block_r, block_c))
    if scale.ndim != 2:
        scale = scale.reshape((1, -1)) if scale.size == c else scale.reshape((-1, 1))
    sp = scale
    if scale.shape == (1, c):
        sp = _pad_to(scale, (1, block_c))
    elif scale.shape == (r, 1):
        sp = _pad_to(scale, (block_r, 1))
    out = _mask.masked_dequant(
        cp, sp, lo, hi, block_r=block_r, block_c=block_c,
        out_dtype=out_dtype, interpret=interpret,
    )
    return out[:r, :c]


def delta_apply(
    buf: jnp.ndarray,
    indices: jnp.ndarray,
    values: jnp.ndarray,
    *,
    block: int = 4096,
    interpret: Optional[bool] = None,
    donate: bool = False,
) -> jnp.ndarray:
    """buf.at[indices].set(values) via the Pallas scatter kernel.

    ``donate=True`` hands ``buf`` to the kernel for in-place update
    (``delta_apply_inplace``): the caller's array is consumed, and the
    scatter writes O(delta) bytes instead of cloning the buffer — the
    contract staged weight sync relies on when applying many bounded
    parts against one staging copy."""
    interpret = _on_cpu() if interpret is None else interpret
    (n,) = buf.shape
    if n < block or indices.shape[0] == 0:
        return ref.delta_apply(buf, indices, values)
    # interpret mode executes the kernel body in Python per grid cell —
    # O(tiles × n_delta) work is fine compiled on TPU but pathological
    # interpreted; large updates take the (identical-semantics) ref path.
    # (The old 1<<22 threshold let a full-layer update burn ~7s *per
    # layer* interpreted — a whole-model pull through apply_packet spent
    # minutes here on CPU.)
    if interpret and (n // block) * indices.shape[0] > 1 << 18:
        return ref.delta_apply(buf, indices, values)
    pad = (-n) % block
    bufp = jnp.pad(buf, (0, pad)) if pad else buf
    # the padded copy is fresh, so aliasing it is always safe; unpadded,
    # in-place needs the caller's explicit donation
    kernel = (_delta.delta_apply_inplace if (donate or pad)
              else _delta.delta_apply)
    out = kernel(
        bufp, indices.astype(jnp.int32), values.astype(buf.dtype),
        block=block, interpret=interpret,
    )
    return out[:n]

"""Pallas TPU kernel: activation × int8-weight matmul with the license mask
applied to the codes and the per-channel scale applied to the output — the
licensed-serving hot path once the paper's quantization pipeline (§3.2) is
adopted.

TPU mapping (DESIGN.md §2): int8 codes stream from HBM into VMEM (half the
bytes of bf16, ~1/4 of f32) and are masked and converted in-register right
before the MXU dot, so no float copy of the weight ever reaches HBM.  A
tier's dead codes are, per output column, the ranges ``a[i] <= |c| < b[i]``
(``models/int8.code_bounds``): int32 compares, one pair per live
interval (:func:`dead_codes`, shared with the jax.numpy form).  The MXU takes the activation dtype on both sides (int8 is exact
in bf16) and accumulates into an f32 VMEM scratch across the sequential,
innermost K grid axis; the last K step scales the columns and writes the
output block.

Stacked weights: ``codes`` may be (U, K, N) with ``unit`` a scalar-prefetch
operand, so a layer scan reads unit ``unit``'s codes in place through the
BlockSpec index map — slicing the stack in XLA would copy it first.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

VMEM_LIMIT = 48 * 1024 * 1024


def dead_codes(codes: jnp.ndarray, a: jnp.ndarray,
               b: jnp.ndarray) -> jnp.ndarray:
    """True where ``a[i] <= |codes| < b[i]`` for some ``i``: int32 compares
    of the code magnitudes against ``a``, ``b`` (n, N) — a tier's dead
    codes per column (``models/int8.code_bounds``)."""
    mag = jnp.abs(codes.astype(jnp.int32))
    dead = (mag >= a[0:1]) & (mag < b[0:1])
    for i in range(1, a.shape[0]):
        dead = dead | ((mag >= a[i:i + 1]) & (mag < b[i:i + 1]))
    return dead


def _kernel(unit_ref, x_ref, codes_ref, scale_ref, *rest, n_k: int,
            n_dead: int):
    del unit_ref                                             # index maps only
    dead_refs, (out_ref, acc_ref) = rest[:2 * bool(n_dead)], rest[-2:]
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    c = codes_ref[...].astype(jnp.int32)                     # (bk, bn)
    if n_dead:
        a_ref, b_ref = dead_refs
        c = jnp.where(dead_codes(c, a_ref[...], b_ref[...]), 0, c)
    w = c.astype(jnp.float32).astype(x_ref.dtype)            # exact for int8
    acc_ref[...] += jnp.dot(x_ref[...], w, preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _out():
        out_ref[...] = (acc_ref[...] * scale_ref[...]).astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "out_dtype", "interpret"),
)
def quant_matmul(
    x: jnp.ndarray,
    codes: jnp.ndarray,
    scale: jnp.ndarray,
    dead: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
    unit=0,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jnp.ndarray:
    """x (M,K) @ (mask(codes) * scale (N,)) -> (M,N) in out_dtype.

    ``codes`` is (K,N), or (U,K,N) read at unit ``unit``; ``dead`` is
    None or the (n,N) int32 code bounds ``(a, b)``: codes with ``a[i] <=
    |c| < b[i]`` for some ``i`` count as 0.  Shapes must be multiples of
    the blocks (``ops.licensed_matmul`` picks blocks that tile the weight
    exactly).
    """
    m, k = x.shape
    if codes.ndim == 2:
        codes = codes[None]
    _, k2, n = codes.shape
    assert k == k2 and scale.shape == (n,)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, (
        f"unpadded shapes {(m, k, n)} vs blocks {(block_m, block_k, block_n)}"
    )
    grid = (m // block_m, n // block_n, k // block_k)
    in_specs = [
        pl.BlockSpec((block_m, block_k), lambda i, j, kk, u: (i, kk)),
        pl.BlockSpec((None, block_k, block_n), lambda i, j, kk, u: (u[0], kk, j)),
        pl.BlockSpec((1, block_n), lambda i, j, kk, u: (0, j)),
    ]
    operands = [x, codes, scale.reshape(1, n)]
    n_dead = 0
    if dead is not None:
        n_dead = dead[0].shape[0]
        in_specs += [pl.BlockSpec((n_dead, block_n), lambda i, j, kk, u: (0, j))] * 2
        operands += [d.reshape(n_dead, n).astype(jnp.int32) for d in dead]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk, u: (i, j)),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, n_k=grid[2], n_dead=n_dead),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="quant_matmul",
    )(jnp.reshape(jnp.asarray(unit, jnp.int32), (1,)), *operands)

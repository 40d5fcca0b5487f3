"""Pallas TPU kernel: paged-attention decode through a block table.

The paged cache pool (``serving/paging.py``) stores K/V as fixed-size
physical blocks shared by every request; a request's logical cache is the
concatenation of the blocks named by its **block table**.  The host-side
serving path materializes that view with a gather before the vmapped
decode — one extra HBM round-trip per step.  This kernel removes it: the
block table rides the grid as a **scalar-prefetch** operand, so each
(sequence, block) grid step DMAs exactly the physical K/V block the table
names straight into VMEM — decode reads each byte of cache exactly once,
with no contiguous copy of the sequence ever existing.

Layout: one query token per sequence (decode), GQA handled in-kernel by
reshaping the query to (kv_heads, group, head_dim) and unrolling the
(static, small) kv-head loop into 2-D MXU dots.  The output block
accumulates across the sequential innermost block-table axis; the
online-softmax running stats (m, l) live in (H, 1) VMEM scratch, since a
(1, H) block over a (B, H) output would break the TPU tiling rule (the
last two block dims divisible by (8, 128) or equal to the array's).
Positions at or beyond a sequence's ``context_lens`` (including anything
read through null/pad table entries) are masked inert.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(tables_ref, lens_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
            *, scale: float, block_size: int, kv_heads: int, groups: int,
            n_blocks: int):
    b = pl.program_id(0)
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    ctx = lens_ref[b]

    @pl.when(t * block_size < ctx)          # skip fully-dead blocks
    def _block():
        q = q_ref[0].astype(jnp.float32) * scale          # (H, hd)
        k = k_ref[0].astype(jnp.float32)                  # (bs, KH, hd)
        v = v_ref[0].astype(jnp.float32)
        h, hd = q.shape
        qg = q.reshape(kv_heads, groups, hd)

        k_pos = t * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_size), 1)
        valid = k_pos < ctx                               # (1, bs)

        # per-kv-head 2-D dots (KH is static and small -> unrolled)
        s = jnp.stack([
            jax.lax.dot_general(qg[kh], k[:, kh], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            for kh in range(kv_heads)
        ], 0).reshape(h, block_size)
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[...]                               # (H, 1)
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(jnp.minimum(m_prev - m_new, 0.0))
        alpha = jnp.where(m_prev == NEG_INF, 0.0, alpha)
        pg = p.reshape(kv_heads, groups, block_size)
        acc = jnp.stack([
            jax.lax.dot_general(pg[kh], v[:, kh], (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
            for kh in range(kv_heads)
        ], 0).reshape(h, hd)
        o_ref[0] = o_ref[0] * alpha + acc
        m_ref[...] = m_new
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)

    @pl.when(t == n_blocks - 1)
    def _normalize():
        o_ref[0] = o_ref[0] / jnp.maximum(l_ref[...], 1e-20)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(
    q: jnp.ndarray,           # (B, H, hd)   one decode query per sequence
    k_blocks: jnp.ndarray,    # (P, bs, KH, hd) physical key blocks
    v_blocks: jnp.ndarray,    # (P, bs, KH, hd) physical value blocks
    block_tables: jnp.ndarray,  # (B, T) int32; entry t covers positions
                                # [t*bs, (t+1)*bs); pad entries may point
                                # anywhere in [0, P) — they are masked
    context_lens: jnp.ndarray,  # (B,) int32 valid cache length (pos + 1)
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """Decode attention over a block-paged KV cache; returns (B, H, hd) f32.

    GQA via ``H == KH * groups``.  The block table and context lengths are
    scalar-prefetched so the BlockSpec index map can route each grid step's
    DMA through the table — the gather lives in the kernel, not in HBM.
    """
    b, h, hd = q.shape
    p_blocks, bs, kh, _ = k_blocks.shape
    assert v_blocks.shape == k_blocks.shape, (v_blocks.shape, k_blocks.shape)
    assert h % kh == 0, (h, kh)
    groups = h // kh
    n_t = block_tables.shape[1]
    assert block_tables.shape[0] == b and context_lens.shape == (b,)
    scale = 1.0 / np.sqrt(hd)

    kernel = functools.partial(
        _kernel, scale=scale, block_size=bs, kv_heads=kh, groups=groups,
        n_blocks=n_t)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_t),
        in_specs=[
            pl.BlockSpec((1, h, hd), lambda b, t, tab, ln: (b, 0, 0)),
            pl.BlockSpec((1, bs, kh, hd),
                         lambda b, t, tab, ln: (tab[b, t], 0, 0, 0)),
            pl.BlockSpec((1, bs, kh, hd),
                         lambda b, t, tab, ln: (tab[b, t], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, hd), lambda b, t, tab, ln: (b, 0, 0)),
        scratch_shapes=[pltpu.VMEM((h, 1), jnp.float32),   # running max
                        pltpu.VMEM((h, 1), jnp.float32)],  # running sum
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, hd), jnp.float32),
        interpret=interpret,
        name="paged_attention",
    )(jnp.asarray(block_tables, jnp.int32),
      jnp.asarray(context_lens, jnp.int32), q, k_blocks, v_blocks)


def _write_kernel(blocks_ref, offs_ref, nk_ref, nv_ref, kb_ref, vb_ref,
                  ok_ref, ov_ref):
    # the scalars are consumed by the index maps; the aliased pools are
    # written through the out refs, never read
    del blocks_ref, offs_ref, kb_ref, vb_ref
    ok_ref[0, 0] = nk_ref[0].astype(ok_ref.dtype)
    ov_ref[0, 0] = nv_ref[0].astype(ov_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_write(
    k_blocks: jnp.ndarray,    # (P, bs, KH, hd) physical key blocks
    v_blocks: jnp.ndarray,    # (P, bs, KH, hd) physical value blocks
    new_k: jnp.ndarray,       # (B, KH, hd)  this step's key, one per lane
    new_v: jnp.ndarray,       # (B, KH, hd)  this step's value
    block_ids: jnp.ndarray,   # (B,) int32 physical block receiving the token
    offsets: jnp.ndarray,     # (B,) int32 row inside that block
    *,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Block-indexed scatter of ONE K/V token per lane — the write half of
    kernel-resident paged decode.

    Each lane's token lands at ``(block_ids[b], offsets[b])``; the block
    ids and offsets ride as scalar-prefetch operands so the output
    BlockSpec routes every grid step's (1, 1, KH, hd) store straight to
    its physical row, and ``input_output_aliases`` makes the update
    in-place — the untouched 2 * (P - B) blocks are never copied.  Pad
    lanes target the pool's null block (duplicates allowed: the null
    block absorbs garbage by contract).  Oracle: ``ref.paged_decode_write``.
    """
    b, kh, hd = new_k.shape
    assert new_v.shape == new_k.shape, (new_v.shape, new_k.shape)
    assert block_ids.shape == offsets.shape == (b,)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, kh, hd), lambda i, blk, off: (i, 0, 0)),
            pl.BlockSpec((1, kh, hd), lambda i, blk, off: (i, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),   # aliased k pool (unread)
            pl.BlockSpec(memory_space=pl.ANY),   # aliased v pool (unread)
        ],
        out_specs=[
            pl.BlockSpec((1, 1, kh, hd),
                         lambda i, blk, off: (blk[i], off[i], 0, 0)),
            pl.BlockSpec((1, 1, kh, hd),
                         lambda i, blk, off: (blk[i], off[i], 0, 0)),
        ],
    )
    return pl.pallas_call(
        _write_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(k_blocks.shape, k_blocks.dtype),
            jax.ShapeDtypeStruct(v_blocks.shape, v_blocks.dtype),
        ],
        # alias the block pools through (operand indices count the scalar
        # prefetch args): only the B addressed rows are ever written
        input_output_aliases={4: 0, 5: 1},
        interpret=interpret,
        name="paged_decode_write",
    )(jnp.asarray(block_ids, jnp.int32), jnp.asarray(offsets, jnp.int32),
      new_k, new_v, k_blocks, v_blocks)

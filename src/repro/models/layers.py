"""Shared neural layers: norms, RoPE, attention variants, MLP variants.

Attention is query-chunked ("flash-style" via ``lax.scan`` over Q blocks
against resident K/V with explicit masks) so 32k-token prefill never
materializes an S×S score matrix.  GQA/MQA, MLA (DeepSeek compressed KV),
sliding windows and decode-with-cache all route through here.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.int8 import dense


# ---------------------------------------------------------------------- init
def dense_init(key, in_dim: int, out_dim: int, dtype, scale: Optional[float] = None):
    scale = scale if scale is not None else 1.0 / np.sqrt(in_dim)
    return (jax.random.normal(key, (in_dim, out_dim), jnp.float32) * scale).astype(dtype)


def hint_sharding(x: jnp.ndarray, *spec) -> jnp.ndarray:
    """with_sharding_constraint that degrades to identity off-mesh.

    Model code stays mesh-agnostic: under the production mesh the hint pins
    GSPMD's intermediate sharding (critical for MoE dispatch); in 1-device
    tests it is a no-op.  The sentinel "batch" resolves to ("pod","data")
    when a pod axis exists, else ("data",)."""
    for batch_axes in (("pod", "data"), ("data",)):
        resolved = tuple(batch_axes if s == "batch" else s for s in spec)
        try:
            return jax.lax.with_sharding_constraint(
                x, jax.sharding.PartitionSpec(*resolved)
            )
        except (RuntimeError, ValueError, TypeError, AssertionError, KeyError):
            continue
    return x


# --------------------------------------------------------------------- norms
def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6,
             bf16_apply: bool = False) -> jnp.ndarray:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    if bf16_apply:
        # stats in f32, application in the residual dtype: the backward of
        # the (B,S,D)-sized multiplies then carries bf16 cotangents, halving
        # the per-layer all-reduce bytes (f32 only flows through the rank-1
        # variance chain)
        r = jax.lax.rsqrt(var + eps).astype(x.dtype)
        return x * r * scale.astype(x.dtype)
    return (x32 * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x: jnp.ndarray, scale: jnp.ndarray, bias: jnp.ndarray,
               eps: float = 1e-5) -> jnp.ndarray:
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    out = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def apply_norm(x, p: Dict[str, jnp.ndarray], cfg=None) -> jnp.ndarray:
    if "bias" in p:
        return layer_norm(x, p["norm_scale"], p["bias"])
    return rms_norm(x, p["norm_scale"],
                    bf16_apply=bool(cfg is not None and cfg.norm_bf16_apply))


def init_norm(dim: int, dtype, layernorm: bool = False) -> Dict[str, jnp.ndarray]:
    p = {"norm_scale": jnp.ones((dim,), dtype)}
    if layernorm:
        p["bias"] = jnp.zeros((dim,), dtype)
    return p


# ---------------------------------------------------------------------- RoPE
def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                              # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(angles)[..., None, :]                        # (..., S, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., : hd // 2], x32[..., hd // 2 :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------- attention
def _masked_softmax(scores: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    # rows with no valid key (can happen in padded decode) -> zeros
    any_valid = jnp.any(mask, axis=-1, keepdims=True)
    return jnp.where(any_valid, probs, 0.0)


def attention_core(
    q: jnp.ndarray,              # (B, Sq, H, hd)
    k: jnp.ndarray,              # (B, Sk, KH, hd)
    v: jnp.ndarray,              # (B, Sk, KH, hd)
    *,
    q_offset,                    # scalar or (B,): absolute position of q[0]
    window: int = 0,             # 0 = full causal; >0 = sliding window
    kv_len: Optional[jnp.ndarray] = None,  # valid cache length (decode)
    q_chunk: int = 512,
    softmax_scale: Optional[float] = None,
    k_positions: Optional[jnp.ndarray] = None,  # (Sk,) absolute key positions
) -> jnp.ndarray:
    """Causal (optionally windowed) attention, chunked over queries.

    By default key slot ``i`` is assumed to hold absolute position ``i``
    (linear cache / fresh prefill).  ``k_positions`` overrides that for
    out-of-order key buffers (the ring suffix-prefill path): masking uses
    the supplied absolute position per slot, and slots with a negative
    position are treated as empty.
    """
    b, sq, h, hd = q.shape
    _, sk, kh, _ = k.shape
    groups = h // kh
    scale = softmax_scale if softmax_scale is not None else 1.0 / np.sqrt(hd)

    q = q * jnp.asarray(scale, q.dtype)
    qg = q.reshape(b, sq, kh, groups, hd)
    k_pos = jnp.arange(sk)
    kp = k_pos if k_positions is None else k_positions

    def block(q_blk, q_pos):
        # q_blk (B, c, KH, G, hd); q_pos (c,) absolute positions
        scores = jnp.einsum("bqkgh,bskh->bkgqs", q_blk.astype(jnp.float32),
                            k.astype(jnp.float32))
        qp = q_pos[:, None]                         # (c, 1)
        mask = kp[None, :] <= qp                    # causal
        if k_positions is not None:
            mask &= kp[None, :] >= 0                # empty ring slots
        if window:
            mask &= kp[None, :] > qp - window
        mask = mask[None, None, None]               # (1,1,1,c,S)
        if kv_len is not None:
            valid = k_pos[None, :] < jnp.reshape(kv_len, (-1, 1, 1))[:, None]
            mask = mask & valid.reshape(b, 1, 1, 1, sk)
        probs = _masked_softmax(scores, mask)
        out = jnp.einsum("bkgqs,bskh->bqkgh", probs.astype(v.dtype), v)
        return out

    vd = v.shape[-1]
    if sq <= q_chunk:
        pos = q_offset + jnp.arange(sq)
        out = block(qg, pos)
    else:
        assert sq % q_chunk == 0, (sq, q_chunk)
        n_blk = sq // q_chunk
        qs = qg.reshape(b, n_blk, q_chunk, kh, groups, hd).swapaxes(0, 1)

        def step(_, inp):
            q_blk, i = inp
            pos = q_offset + i * q_chunk + jnp.arange(q_chunk)
            return None, block(q_blk, pos)

        _, outs = jax.lax.scan(step, None, (qs, jnp.arange(n_blk)))
        out = outs.swapaxes(0, 1).reshape(b, sq, kh, groups, vd)
    return out.reshape(b, sq, h, vd)


def _attn_qkv(p: Dict[str, Any], x: jnp.ndarray, cfg,
              positions: jnp.ndarray):
    """Shared GQA q/k/v projection + bias + RoPE — the ONE front end of
    both the contiguous and the kernel-resident paged attention paths
    (``positions`` broadcastable to (B, S)), so they cannot drift."""
    b, s, _ = x.shape
    h, kh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = dense(x, p["wq"])
    k = dense(x, p["wk"])
    v = dense(x, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, h, hd)
    k = k.reshape(b, s, kh, hd)
    v = v.reshape(b, s, kh, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _mla_qkv(p: Dict[str, Any], x: jnp.ndarray, cfg,
             positions: jnp.ndarray):
    """Shared MLA projection front end (query, compressed KV, rotary
    key) of the contiguous and paged paths; see :func:`_attn_qkv`."""
    b, s, _ = x.shape
    h = cfg.num_heads
    nope, rope_d, r = cfg.qk_nope_dim, cfg.rope_head_dim, cfg.kv_lora_rank
    q = jnp.einsum("bsd,df->bsf", x, p["wq"]).reshape(b, s, h, nope + rope_d)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    dkv = jnp.einsum("bsd,df->bsf", x, p["w_dkv"])            # (B,S,r+rope_d)
    c_kv = rms_norm(dkv[..., :r], p["ckv_norm"])
    k_rope = dkv[..., r:][:, :, None, :]                       # (B,S,1,rope_d)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    k_rope = apply_rope(k_rope, positions, cfg.rope_theta)
    return q_nope, q_rope, c_kv, k_rope


# ---------------------------------------------- kernel-resident paged decode
def gather_paged(blocks: jnp.ndarray, tables: jnp.ndarray) -> jnp.ndarray:
    """(P, bs, *rest) physical blocks + (B, T) tables -> (B, T*bs, *rest).

    The only read of paged cache bytes during kernel-resident decode:
    tables are trimmed to the micro-batch's used width, so this is
    O(context) — not O(capacity) — and there is no write-back (the one
    new token went in through its block index)."""
    g = blocks[tables]
    s = g.shape
    return g.reshape(s[0], s[1] * s[2], *s[3:])


def paged_decode_attend(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        ctx: jnp.ndarray, *,
                        softmax_scale: Optional[float] = None) -> jnp.ndarray:
    """One-token-per-lane attention over a table-gathered cache.

    q (B, H, hd); k/v (B, S, KH, hd) in logical order with junk past each
    lane's ``ctx`` (B,) valid length (masked).  Mirrors
    :func:`attention_core`'s decode numerics — q scaled in its own dtype,
    f32 scores, :func:`_masked_softmax`, probs cast to ``v.dtype`` — so
    kernel-resident and gather/scatter decode agree to float tolerance.
    """
    b, h, hd = q.shape
    kh = k.shape[2]
    groups = h // kh
    scale = softmax_scale if softmax_scale is not None else 1.0 / np.sqrt(hd)
    qg = (q * jnp.asarray(scale, q.dtype)).reshape(b, kh, groups, hd)
    scores = jnp.einsum("bkgh,bskh->bkgs", qg.astype(jnp.float32),
                        k.astype(jnp.float32))
    mask = jnp.arange(k.shape[1])[None, :] < ctx[:, None]     # (B, S)
    probs = _masked_softmax(scores, mask[:, None, None, :])
    out = jnp.einsum("bkgs,bskh->bkgh", probs.astype(v.dtype), v)
    return out.reshape(b, h, v.shape[-1])


def attention_block_paged(
    p: Dict[str, Any], x: jnp.ndarray, cfg, *,
    cache: Dict[str, jnp.ndarray], tables: jnp.ndarray, pos: jnp.ndarray,
    use_kernel: bool = False, interpret: bool = False,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """GQA decode straight against the paged pool — no contiguous view.

    ``x`` is (B, 1, d) — one token per lane; ``cache`` holds this layer's
    *physical block* leaves ``k``/``v`` (1, P+1, bs, KH, hd) (plus int8
    scales) shared by every lane, and the per-lane ``len`` (B,).
    ``tables`` (B, T) names each lane's blocks in logical order (trimmed
    to the batch's used width, null-padded); ``pos`` (B,) is each lane's
    absolute position.  The new K/V token is written through
    ``(tables[b, pos // bs], pos % bs)`` — a block-indexed scatter, the
    write half of ``kernels/paged_attention.paged_decode_write`` — and
    attention reads the cache once through the table (``use_kernel=True``
    routes it through the Pallas scalar-prefetch kernel; the default is
    the pure-JAX gather fallback with identical semantics).
    """
    b, s, d = x.shape
    assert s == 1, s
    h, kh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q, k, v = _attn_qkv(p, x, cfg, pos[:, None])

    quant = "k_scale" in cache
    kc = cache["k"][0]                                        # (P+1, bs, ...)
    vc = cache["v"][0]
    bs_sz = kc.shape[1]
    blk = jnp.take_along_axis(tables, (pos // bs_sz)[:, None], axis=1)[:, 0]
    off = pos % bs_sz
    if quant:
        kq, ks = _kv_quantize(k)
        vq, vs = _kv_quantize(v)
        kc = kc.at[blk, off].set(kq[:, 0])
        vc = vc.at[blk, off].set(vq[:, 0])
        ksc = cache["k_scale"][0].at[blk, off].set(ks[:, 0])
        vsc = cache["v_scale"][0].at[blk, off].set(vs[:, 0])
        kk = _kv_dequantize(gather_paged(kc, tables),
                            gather_paged(ksc, tables), k.dtype)
        vv = _kv_dequantize(gather_paged(vc, tables),
                            gather_paged(vsc, tables), v.dtype)
        out = paged_decode_attend(q[:, 0], kk, vv, pos + 1)
    elif use_kernel:
        from repro.kernels.paged_attention import (paged_attention,
                                                   paged_decode_write)

        kc, vc = paged_decode_write(kc, vc, k[:, 0], v[:, 0], blk, off,
                                    interpret=interpret)
        out = paged_attention(q[:, 0], kc, vc, tables, pos + 1,
                              interpret=interpret).astype(x.dtype)
    else:
        kc = kc.at[blk, off].set(k[:, 0].astype(kc.dtype))
        vc = vc.at[blk, off].set(v[:, 0].astype(vc.dtype))
        out = paged_decode_attend(q[:, 0], gather_paged(kc, tables),
                                  gather_paged(vc, tables), pos + 1)
    # len + 1 never clamps here: the gateway admits pos < capacity only
    new_cache = {"k": kc[None], "v": vc[None], "len": cache["len"] + 1}
    if quant:
        new_cache["k_scale"] = ksc[None]
        new_cache["v_scale"] = vsc[None]
    y = dense(out.reshape(b, 1, h * hd), p["wo"])
    return y, new_cache


def mla_block_paged(
    p: Dict[str, Any], x: jnp.ndarray, cfg, *,
    cache: Dict[str, jnp.ndarray], tables: jnp.ndarray, pos: jnp.ndarray,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """MLA decode against paged compressed-KV blocks.

    Same contract as :func:`attention_block_paged`: write the token's
    ``c_kv``/rotary key through its block index, gather the lane's chain
    once, decompress, attend.  Decompression covers T*bs gathered
    positions instead of the full capacity — strictly fewer FLOPs than
    the contiguous decode it replaces."""
    b, s, d = x.shape
    assert s == 1, s
    h = cfg.num_heads
    nope, rope_d, vd, r = (cfg.qk_nope_dim, cfg.rope_head_dim,
                           cfg.v_head_dim, cfg.kv_lora_rank)
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(p, x, cfg, pos[:, None])

    ckv_blocks = cache["ckv"][0]                              # (P+1, bs, r)
    kr_blocks = cache["k_rope"][0]
    bs_sz = ckv_blocks.shape[1]
    blk = jnp.take_along_axis(tables, (pos // bs_sz)[:, None], axis=1)[:, 0]
    off = pos % bs_sz
    ckv_blocks = ckv_blocks.at[blk, off].set(
        c_kv[:, 0].astype(ckv_blocks.dtype))
    kr_blocks = kr_blocks.at[blk, off].set(
        k_rope[:, 0, 0].astype(kr_blocks.dtype))

    c_all = gather_paged(ckv_blocks, tables)                  # (B, S, r)
    kr_all = gather_paged(kr_blocks, tables)[:, :, None, :]   # (B, S, 1, rd)
    ukv = jnp.einsum("bsr,rf->bsf", c_all, p["w_ukv"]).reshape(
        b, c_all.shape[1], h, nope + vd)
    k_nope, v = ukv[..., :nope], ukv[..., nope:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(kr_all, (*k_nope.shape[:3], rope_d))],
        axis=-1)
    qfull = jnp.concatenate([q_nope, q_rope], axis=-1)
    out = paged_decode_attend(qfull[:, 0], k, v, pos + 1,
                              softmax_scale=1.0 / np.sqrt(nope + rope_d))
    new_cache = {"ckv": ckv_blocks[None], "k_rope": kr_blocks[None],
                 "len": cache["len"] + 1}
    y = jnp.einsum("bsf,fd->bsd", out.reshape(b, 1, h * vd), p["wo"])
    return y, new_cache


# ------------------------------------------------------------- GQA attention
def init_attention(key, cfg, dtype) -> Dict[str, Any]:
    d, h, kh, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 5)
    p = {
        "wq": dense_init(ks[0], d, h * hd, dtype),
        "wk": dense_init(ks[1], d, kh * hd, dtype),
        "wv": dense_init(ks[2], d, kh * hd, dtype),
        "wo": dense_init(ks[3], h * hd, d, dtype),
    }
    if cfg.attn_bias:
        p["bq"] = jnp.zeros((h * hd,), dtype)
        p["bk"] = jnp.zeros((kh * hd,), dtype)
        p["bv"] = jnp.zeros((kh * hd,), dtype)
    return p


def attention_block(
    p: Dict[str, Any], x: jnp.ndarray, cfg, *,
    cache: Optional[Dict[str, jnp.ndarray]] = None,
    pos=0, window: int = 0, attend_cache: bool = False,
    chunk_valid=None,
) -> Tuple[jnp.ndarray, Optional[Dict[str, jnp.ndarray]]]:
    """GQA/MQA attention.  ``cache`` holds k/v (B, cap, KH, hd) + ``len``.

    Modes: train/prefill (cache None or filled-from-empty), decode
    (Sq == 1 with a pre-filled ring/linear cache), and — with
    ``attend_cache=True`` — *suffix/chunked prefill*: Sq > 1 new tokens
    starting at absolute ``pos`` attend over the updated cache contents
    instead of only each other, so a prompt whose prefix ``[0, pos)`` is
    already resident (prefix cache, or an earlier chunk of the same
    prompt) runs prefill on the uncached tail alone.

    For a *linear* cache (``window == 0``, slot == absolute position)
    writes beyond the last slot clamp onto it (masked until a real decode
    write lands there) rather than wrapping over live prefix slots.  With
    ``window > 0`` the cache is a ring: the chunk's own writes may evict
    positions its earliest queries still need, so attention reads a
    pre-write snapshot of the ring concatenated with the fresh chunk K/V,
    with per-slot absolute positions reconstructed from ``pos`` (see
    ``k_positions`` in :func:`attention_core`); writes then land at
    ``mod(position, cap)`` as usual.

    ``chunk_valid`` (optional, scalar or (B,)) is the number of leading
    *real* rows in this chunk — trailing rows are right-padding.  It
    keeps the ``len`` counter exact and, on the ring path, masks the pad
    rows' writes so they cannot clobber live slots.  Pad rows on the
    linear path are safe unmasked: their clamped/high slots are causally
    invisible to every real query and are overwritten by the next chunk
    or the first decode write before anything can attend to them.
    """
    b, s, d = x.shape
    h, kh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    positions = pos + jnp.arange(s)
    q, k, v = _attn_qkv(p, x, cfg, jnp.broadcast_to(positions, (b, s)))

    if cache is None:
        out = attention_core(q, k, v, q_offset=pos, window=window,
                             q_chunk=cfg.q_chunk)
        new_cache = None
    else:
        quant = "k_scale" in cache
        cap = cache["k"].shape[1]
        ring = bool(window) and attend_cache
        if ring:
            assert s <= cap, (s, cap)  # one chunk may not lap the ring
            slot = jnp.mod(positions, cap)
            # snapshot BEFORE the writes: the chunk's earliest queries may
            # need positions its own writes are about to evict
            if quant:
                old_k = _kv_dequantize(cache["k"], cache["k_scale"], k.dtype)
                old_v = _kv_dequantize(cache["v"], cache["v_scale"], v.dtype)
            else:
                old_k, old_v = cache["k"], cache["v"]
            # absolute position resident in ring slot i before this chunk:
            # the largest p < pos with mod(p, cap) == i (negative = empty,
            # masked in attention_core)
            last = positions[0] - 1
            old_pos = last - jnp.mod(last - jnp.arange(cap), cap)
        elif attend_cache:
            # linear cache: clamp instead of wrap, so a lane whose suffix
            # is padded past the capacity piles the pad writes onto the
            # (masked) last slot rather than corrupting prefix slots
            slot = jnp.clip(positions, 0, cap - 1)
        else:
            slot = jnp.mod(positions, cap)                 # ring for windowed
        sel = None
        if ring and chunk_valid is not None:
            # mask pad rows' writes: an invalid row re-writes the old
            # content of its slot (identity), so junk never lands
            keep = (jnp.arange(s)[None, :]
                    < jnp.reshape(jnp.asarray(chunk_valid), (-1, 1)))
            sel = keep[..., None, None]                     # (B|1, s, 1, 1)
        if quant:
            kq, ks = _kv_quantize(k)
            vq, vs = _kv_quantize(v)
            if sel is not None:
                # mask at the code/scale level so pad rows round-trip the
                # resident int8 content exactly
                kq = jnp.where(sel, kq, cache["k"][:, slot])
                vq = jnp.where(sel, vq, cache["v"][:, slot])
                ks = jnp.where(sel, ks, cache["k_scale"][:, slot])
                vs = jnp.where(sel, vs, cache["v_scale"][:, slot])
            ck = cache["k"].at[:, slot].set(kq)
            cv = cache["v"].at[:, slot].set(vq)
            cks = cache["k_scale"].at[:, slot].set(ks)
            cvs = cache["v_scale"].at[:, slot].set(vs)
        else:
            k_w = k if sel is None else jnp.where(sel, k, old_k[:, slot])
            v_w = v if sel is None else jnp.where(sel, v, old_v[:, slot])
            # the offset-0 contiguous fast path only holds for a filled-
            # from-empty prefill; a chunk at pos > 0 must scatter by slot
            dus = s == cap and not attend_cache
            ck = jax.lax.dynamic_update_slice(  # contiguous when s==cap write
                cache["k"], k_w.astype(cache["k"].dtype), (0, 0, 0, 0)
            ) if dus else cache["k"].at[:, slot].set(
                k_w.astype(cache["k"].dtype))
            cv = jax.lax.dynamic_update_slice(
                cache["v"], v_w.astype(cache["v"].dtype), (0, 0, 0, 0)
            ) if dus else cache["v"].at[:, slot].set(
                v_w.astype(cache["v"].dtype))
        cv_n = s if chunk_valid is None else jnp.asarray(chunk_valid)
        new_len = jnp.minimum(cache["len"] + cv_n, cap)
        if ring:
            # attend over [ring snapshot | fresh chunk K/V] with explicit
            # absolute key positions; window masking bounds the lookback.
            # Quantized caches attend the fresh chunk in round-tripped
            # int8 form so every key is seen dequantized no matter which
            # chunk boundary it fell on.
            if quant:
                k_att = _kv_dequantize(*_kv_quantize(k), k.dtype)
                v_att = _kv_dequantize(*_kv_quantize(v), v.dtype)
            else:
                k_att, v_att = k, v
            out = attention_core(
                q, jnp.concatenate([old_k, k_att], axis=1),
                jnp.concatenate([old_v, v_att], axis=1),
                q_offset=pos, window=window, q_chunk=cfg.q_chunk,
                k_positions=jnp.concatenate([old_pos, positions]),
            )
        elif s == 1 or attend_cache:
            # decode: attend over the valid cache (mask handles ring order —
            # with RoPE already applied per absolute position, order in the
            # buffer is irrelevant to the score computation).  Suffix
            # prefill attends the same way, but causal masking alone bounds
            # it: every slot <= query position holds either the resident
            # prefix or a token written this step, and ``len`` may be
            # unseeded (the gateway overrides counters after the step).
            if quant:
                kk = _kv_dequantize(ck, cks, k.dtype)
                vv = _kv_dequantize(cv, cvs, v.dtype)
            else:
                kk, vv = ck, cv
            out = attention_core(
                q, kk, vv, q_offset=pos, window=0,
                kv_len=None if attend_cache else new_len,
                q_chunk=cfg.q_chunk,
            )
        else:
            out = attention_core(q, k, v, q_offset=pos, window=window,
                                 q_chunk=cfg.q_chunk)
        new_cache = {"k": ck, "v": cv, "len": new_len}
        if quant:
            new_cache["k_scale"] = cks
            new_cache["v_scale"] = cvs
    y = dense(out.reshape(b, s, h * hd), p["wo"])
    return y, new_cache


def init_attn_cache(cfg, batch: int, capacity: int, dtype) -> Dict[str, jnp.ndarray]:
    kh, hd = cfg.num_kv_heads, cfg.head_dim
    if cfg.kv_cache_int8:
        # int8 codes + per-(token, head) scales: 2x less cache traffic than
        # bf16 at <0.5% logit error (decode rows are cache-read-bound)
        return {
            "k": jnp.zeros((batch, capacity, kh, hd), jnp.int8),
            "v": jnp.zeros((batch, capacity, kh, hd), jnp.int8),
            "k_scale": jnp.zeros((batch, capacity, kh, 1), jnp.float32),
            "v_scale": jnp.zeros((batch, capacity, kh, 1), jnp.float32),
            "len": jnp.zeros((batch,), jnp.int32),
        }
    return {
        "k": jnp.zeros((batch, capacity, kh, hd), dtype),
        "v": jnp.zeros((batch, capacity, kh, hd), dtype),
        "len": jnp.zeros((batch,), jnp.int32),
    }


def _kv_quantize(x: jnp.ndarray):
    """(B,S,KH,hd) -> int8 codes + (B,S,KH,1) scales (symmetric absmax)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    codes = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return codes.astype(jnp.int8), scale


def _kv_dequantize(codes: jnp.ndarray, scale: jnp.ndarray, dtype):
    return (codes.astype(jnp.float32) * scale).astype(dtype)


# ------------------------------------------------------------- MLA attention
def init_mla(key, cfg, dtype) -> Dict[str, Any]:
    d, h = cfg.d_model, cfg.num_heads
    nope, rope_d, vd, r = cfg.qk_nope_dim, cfg.rope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], d, h * (nope + rope_d), dtype),
        "w_dkv": dense_init(ks[1], d, r + rope_d, dtype),
        "w_ukv": dense_init(ks[2], r, h * (nope + vd), dtype),
        "wo": dense_init(ks[3], h * vd, d, dtype),
        "ckv_norm": jnp.ones((r,), dtype),
    }


def mla_block(
    p: Dict[str, Any], x: jnp.ndarray, cfg, *,
    cache: Optional[Dict[str, jnp.ndarray]] = None, pos=0, window: int = 0,
    attend_cache: bool = False, chunk_valid=None,
) -> Tuple[jnp.ndarray, Optional[Dict[str, jnp.ndarray]]]:
    """Multi-head Latent Attention (DeepSeek-V2).  The cache stores the
    COMPRESSED c_kv (r) + shared rotary key (rope_d) — the paper's KV-cache
    reduction.  Baseline decompresses per step (the weight-absorbed decode
    variant is a §Perf hillclimb)."""
    b, s, d = x.shape
    h = cfg.num_heads
    nope, rope_d, vd, r = cfg.qk_nope_dim, cfg.rope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank

    positions = pos + jnp.arange(s)
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(
        p, x, cfg, jnp.broadcast_to(positions, (b, s)))

    if cache is not None:
        cap = cache["ckv"].shape[1]
        # suffix/chunked prefill (attend_cache): linear cache — clamp,
        # don't wrap (see attention_block); pad writes pile onto the
        # masked last slot.  MLA configs never use sliding windows, so
        # the ring snapshot path is not implemented here.
        assert not (attend_cache and window), \
            "windowed MLA chunked prefill is unsupported"
        slot = (jnp.clip(positions, 0, cap - 1) if attend_cache
                else jnp.mod(positions, cap))
        c_all = cache["ckv"].at[:, slot].set(c_kv.astype(cache["ckv"].dtype))
        kr_all = cache["k_rope"].at[:, slot].set(k_rope.squeeze(2).astype(cache["k_rope"].dtype))
        cv_n = s if chunk_valid is None else jnp.asarray(chunk_valid)
        new_len = jnp.minimum(cache["len"] + cv_n, cap)
        new_cache = {"ckv": c_all, "k_rope": kr_all, "len": new_len}
        kv_src, kr_src = c_all, kr_all[:, :, None, :]
        # attend_cache: causal masking alone bounds the scores (slot ==
        # absolute position and ``len`` may be unseeded), matching the
        # suffix-prefill contract in attention_block
        kv_len = None if attend_cache else new_len
    else:
        new_cache = None
        kv_src, kr_src, kv_len = c_kv, k_rope, None

    ukv = jnp.einsum("bsr,rf->bsf", kv_src, p["w_ukv"]).reshape(
        b, kv_src.shape[1], h, nope + vd
    )
    k_nope, v = ukv[..., :nope], ukv[..., nope:]
    k = jnp.concatenate([k_nope, jnp.broadcast_to(kr_src, (*k_nope.shape[:3], rope_d))], axis=-1)
    qfull = jnp.concatenate([q_nope, q_rope], axis=-1)

    out = attention_core(
        qfull, k, v, q_offset=pos, window=window,
        kv_len=kv_len if s == 1 else None, q_chunk=cfg.q_chunk,
        softmax_scale=1.0 / np.sqrt(nope + rope_d),
    )
    y = jnp.einsum("bsf,fd->bsd", out.reshape(b, s, h * vd), p["wo"])
    return y, new_cache


def init_mla_cache(cfg, batch: int, capacity: int, dtype) -> Dict[str, jnp.ndarray]:
    return {
        "ckv": jnp.zeros((batch, capacity, cfg.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, capacity, cfg.rope_head_dim), dtype),
        "len": jnp.zeros((batch,), jnp.int32),
    }


# ---------------------------------------------------------------------- MLPs
def init_mlp(key, cfg, dtype, d_ff: Optional[int] = None) -> Dict[str, Any]:
    d = cfg.d_model
    ff = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.mlp_type == "swiglu":
        return {
            "w_gate": dense_init(ks[0], d, ff, dtype),
            "w_up": dense_init(ks[1], d, ff, dtype),
            "w_down": dense_init(ks[2], ff, d, dtype),
        }
    # squared_relu (nemotron family): two matrices
    return {
        "w_up": dense_init(ks[0], d, ff, dtype),
        "w_down": dense_init(ks[1], ff, d, dtype),
    }


def mlp_block(p: Dict[str, Any], x: jnp.ndarray, cfg) -> jnp.ndarray:
    if "w_gate" in p:
        g = dense(x, p["w_gate"])
        u = dense(x, p["w_up"])
        h = jax.nn.silu(g) * u
    else:
        u = dense(x, p["w_up"])
        r = jax.nn.relu(u)
        h = r * r  # squared ReLU
    return dense(h, p["w_down"])

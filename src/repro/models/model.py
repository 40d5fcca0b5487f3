"""Unified decoder-only model over all supported block kinds.

Layers are grouped into *pattern units* (one unit = one cycle of
``cfg.layer_pattern``), stacked over units, and evaluated with
``lax.scan`` so an 88-layer model lowers to the HLO of one unit — compile
time and HLO size stay bounded.  KV/SSM/LRU caches ride the scan as
stacked xs/ys.  ``remat`` checkpoints each unit for training.

Entry points:
  init_params(key, cfg)                      -> param pytree
  forward(params, cfg, tokens/embeds, ...)   -> logits (+ aux, + cache)
  init_cache(cfg, batch, capacity)           -> cache pytree
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import int8 as Q
from repro.models import layers as L
from repro.models import moe as M
from repro.models import rglru as R
from repro.models import ssm as S
from repro.configs.base import ModelConfig


# ------------------------------------------------------------------- blocks
def _init_block(key, cfg: ModelConfig, kind: str) -> Dict[str, Any]:
    ks = jax.random.split(key, 3)
    dtype = cfg.dtype
    ln = cfg.norm_layernorm
    if kind == "attn":
        mixer = (L.init_mla(ks[0], cfg, dtype) if cfg.use_mla
                 else L.init_attention(ks[0], cfg, dtype))
        p = {"norm1": L.init_norm(cfg.d_model, dtype, ln), "mixer": mixer,
             "norm2": L.init_norm(cfg.d_model, dtype, ln)}
        if cfg.num_experts:
            p["ffn"] = M.init_moe(ks[1], cfg, dtype)
        else:
            p["ffn"] = L.init_mlp(ks[1], cfg, dtype)
        return p
    if kind == "ssm":
        return {"norm1": L.init_norm(cfg.d_model, dtype, ln),
                "mixer": S.init_ssm(ks[0], cfg, dtype)}
    if kind == "rec":
        return {"norm1": L.init_norm(cfg.d_model, dtype, ln),
                "mixer": R.init_rglru(ks[0], cfg, dtype),
                "norm2": L.init_norm(cfg.d_model, dtype, ln),
                "ffn": L.init_mlp(ks[1], cfg, dtype)}
    raise ValueError(f"unknown block kind {kind!r}")


def _apply_block(
    p: Dict[str, Any], kind: str, x: jnp.ndarray, cfg: ModelConfig, *,
    cache: Optional[Dict[str, Any]], pos, attend_cache: bool = False,
    chunk_valid=None,
    paged_tables: Optional[jnp.ndarray] = None, paged_kernel: str = "off",
) -> Tuple[jnp.ndarray, jnp.ndarray, Optional[Dict[str, Any]]]:
    """Pre-norm residual block.  Returns (x, aux_loss, new_cache).

    ``attend_cache`` (static) selects suffix-prefill attention — Sq > 1
    tokens starting at ``pos`` attend over resident cache contents; only
    attention blocks consume it (SSM/RG-LRU state is sequential, so the
    prefix-cache gate never routes those models here).

    ``paged_tables`` (B, T) selects *kernel-resident paged decode*:
    attention blocks receive physical block leaves plus per-lane block
    tables and absolute positions (``pos`` is a (B,) vector) instead of
    a contiguous cache; SSM/RG-LRU state is position-independent and
    batch-row-local, so those blocks run unchanged on their lane-stacked
    state."""
    aux = jnp.zeros((), jnp.float32)
    h = L.apply_norm(x, p["norm1"], cfg)
    if kind == "attn":
        if paged_tables is not None and cfg.use_mla:
            y, new_cache = L.mla_block_paged(p["mixer"], h, cfg, cache=cache,
                                             tables=paged_tables, pos=pos)
        elif paged_tables is not None:
            y, new_cache = L.attention_block_paged(
                p["mixer"], h, cfg, cache=cache, tables=paged_tables,
                pos=pos, use_kernel=paged_kernel != "off",
                interpret=paged_kernel == "interpret")
        elif cfg.use_mla:
            y, new_cache = L.mla_block(p["mixer"], h, cfg, cache=cache, pos=pos,
                                       window=cfg.window,
                                       attend_cache=attend_cache,
                                       chunk_valid=chunk_valid)
        else:
            y, new_cache = L.attention_block(p["mixer"], h, cfg, cache=cache,
                                             pos=pos, window=cfg.window,
                                             attend_cache=attend_cache,
                                             chunk_valid=chunk_valid)
        x = x + y.astype(x.dtype)
        h2 = L.apply_norm(x, p["norm2"], cfg)
        if cfg.num_experts:
            y2, aux = M.moe_block(p["ffn"], h2, cfg)
        else:
            y2 = L.mlp_block(p["ffn"], h2, cfg)
        return x + y2.astype(x.dtype), aux, new_cache
    if kind == "ssm":
        y, new_cache = S.ssm_block(p["mixer"], h, cfg, cache=cache)
        return x + y, aux, new_cache
    if kind == "rec":
        y, new_cache = R.rglru_block(p["mixer"], h, cfg, cache=cache)
        x = x + y
        h2 = L.apply_norm(x, p["norm2"], cfg)
        return x + L.mlp_block(p["ffn"], h2, cfg), aux, new_cache
    raise ValueError(kind)


def _matmul_keys(kind: str, cfg: ModelConfig) -> Tuple[str, ...]:
    """Sub-trees of a ``kind`` block whose every weight reaches its layer
    through ``L.dense`` (the GQA projections, the dense MLPs), so int8
    leaves there stay int8 into the matmul; MLA, MoE, SSM and RG-LRU
    weights are dequantized before the block runs."""
    keys = []
    if kind == "attn" and not cfg.use_mla:
        keys.append("mixer")
    if kind == "rec" or (kind == "attn" and not cfg.num_experts):
        keys.append("ffn")
    return tuple(keys)


def _serve_block(p, kind: str, cfg: ModelConfig, license_intervals,
                 codes=None, unit=None):
    return Q.serve_block(p, _matmul_keys(kind, cfg), license_intervals,
                         cfg.dtype, codes, unit)


def fused_weight_share(params: Dict[str, Any], cfg: ModelConfig) -> float:
    """Share of the int8 weight elements that the matmuls take as codes
    (``int8.dense``) rather than an eager dequant."""
    blocks = [(params["units"][f"b{j}"], _matmul_keys(kind, cfg))
              for j, kind in enumerate(cfg.layer_pattern)]
    blocks += [(params["tail"][f"t{j}"], _matmul_keys(kind, cfg))
               for j, kind in enumerate(cfg.tail_pattern)]
    return Q.fused_weight_share(blocks)


def _init_block_cache(cfg: ModelConfig, kind: str, batch: int, capacity: int):
    dtype = cfg.dtype
    if kind == "attn":
        cap = min(capacity, cfg.window) if cfg.window else capacity
        if cfg.use_mla:
            return L.init_mla_cache(cfg, batch, cap, dtype)
        return L.init_attn_cache(cfg, batch, cap, dtype)
    if kind == "ssm":
        return S.init_ssm_cache(cfg, batch, dtype)
    if kind == "rec":
        return R.init_rglru_cache(cfg, batch, dtype)
    raise ValueError(kind)


# ------------------------------------------------------------------- params
def init_params(key, cfg: ModelConfig) -> Dict[str, Any]:
    ks = jax.random.split(key, 8)
    dtype = cfg.dtype
    n_units = cfg.pattern_units
    pattern = cfg.layer_pattern

    def unit(k):
        kk = jax.random.split(k, len(pattern))
        return {f"b{j}": _init_block(kk[j], cfg, kind)
                for j, kind in enumerate(pattern)}

    unit_keys = jax.random.split(ks[0], n_units)
    units = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *[unit(k) for k in unit_keys]
    ) if n_units > 1 else jax.tree_util.tree_map(
        lambda x: x[None], unit(unit_keys[0])
    )

    params: Dict[str, Any] = {
        "embed": {"tok": (jax.random.normal(ks[1], (cfg.padded_vocab, cfg.d_model),
                                            jnp.float32) * 0.02).astype(dtype)},
        "units": units,
        "final_norm": L.init_norm(cfg.d_model, dtype, cfg.norm_layernorm),
        "lm_head": L.dense_init(ks[2], cfg.d_model, cfg.padded_vocab, dtype, scale=0.02),
    }
    tail = cfg.tail_pattern
    if tail:
        tk = jax.random.split(ks[3], len(tail))
        params["tail"] = {f"t{j}": _init_block(tk[j], cfg, kind)
                          for j, kind in enumerate(tail)}
    if cfg.frontend == "vision":
        # projector from the (stub) vision encoder's output to d_model
        params["vision_proj"] = L.dense_init(ks[4], cfg.d_model, cfg.d_model, dtype)
    return params


def init_cache(cfg: ModelConfig, batch: int, capacity: int) -> Dict[str, Any]:
    n_units = cfg.pattern_units
    pattern = cfg.layer_pattern

    def unit_cache():
        return {f"b{j}": _init_block_cache(cfg, kind, batch, capacity)
                for j, kind in enumerate(pattern)}

    stacked = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (n_units, *x.shape)), unit_cache()
    )
    cache: Dict[str, Any] = {"units": stacked}
    tail = cfg.tail_pattern
    if tail:
        cache["tail"] = {f"t{j}": _init_block_cache(cfg, kind, batch, capacity)
                         for j, kind in enumerate(tail)}
    return cache


# ------------------------------------------------------------------ forward
def forward(
    params: Dict[str, Any],
    cfg: ModelConfig,
    tokens: Optional[jnp.ndarray] = None,       # (B, S_text) int32
    *,
    patch_embeds: Optional[jnp.ndarray] = None,  # (B, P, D) vision stub output
    cache: Optional[Dict[str, Any]] = None,
    pos=0,
    license_intervals=None,   # (lo, hi) f32[MAX_INTERVALS] — fused-dequant licensing
    attend_cache: bool = False,  # static: suffix prefill attends cache contents
    chunk_valid=None,         # scalar or (B,): real rows in a right-padded chunk
    paged_tables: Optional[jnp.ndarray] = None,  # (B, T): kernel-resident decode
    paged_kernel: str = "off",   # static: "off" | "pallas" | "interpret"
) -> Tuple[jnp.ndarray, jnp.ndarray, Optional[Dict[str, Any]]]:
    """Returns (logits (B,S,V), aux_loss, new_cache or None).

    ``params`` may contain int8 {"codes","scale"} leaves (see
    models/int8.py); ``license_intervals`` masks them INSIDE the
    layer scan, so weight HBM reads stay int8 and every license tier
    shares one stored model.  The GQA and dense-MLP matmuls multiply the
    masked codes directly; other blocks' leaves are dequantized per unit
    (``_matmul_keys``).

    ``attend_cache=True`` is the *suffix prefill* mode behind the prefix
    cache: ``tokens`` are the uncached tail of a prompt whose positions
    ``[0, pos)`` are already resident in ``cache``, and attention reads
    the cache (prefix + this step's writes) instead of only the provided
    tokens.  Linear caches clamp pad writes; windowed (ring) caches take
    the snapshot-attend path — see ``attention_block``.  ``chunk_valid``
    gives the number of leading real rows per lane when a chunk is
    right-padded (keeps ``len`` counters exact and masks ring pad
    writes); only attention blocks consume it.

    ``paged_tables`` selects *kernel-resident paged decode* (one token
    per lane): ``cache`` is the hybrid pytree from
    ``PagedCachePool.decode_cache`` — attention leaves are the pool's
    physical block arrays shared by every lane, per-lane state is
    lane-gathered — ``pos`` is a (B,) vector of absolute positions, and
    attention reads/writes the pool *through the block table* instead of
    a contiguous per-lane view.  ``paged_kernel`` routes the read through
    the Pallas scalar-prefetch kernel ("pallas"; "interpret" for CPU
    testing) or the pure-JAX gather fallback ("off")."""
    if paged_tables is not None:
        assert cache is not None and not attend_cache
    parts = []
    if patch_embeds is not None:
        proj = params.get("vision_proj")
        pe = jnp.einsum("bpd,df->bpf", patch_embeds, proj) if proj is not None else patch_embeds
        parts.append(pe.astype(cfg.dtype))
    if tokens is not None:
        parts.append(params["embed"]["tok"][tokens])
    x = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    if cfg.pin_acts and x.shape[1] > 1:
        # pin the entry activation: the vocab-sharded embedding gather (and
        # the VLM patch/text concat) otherwise seed feature-sharded
        # residuals through the whole layer stack
        x = L.hint_sharding(x, "batch", None, None)

    pattern = cfg.layer_pattern
    aux_total = jnp.zeros((), jnp.float32)
    # the matmuls' int8 codes stay whole, out of the scanned xs: each unit
    # reads its own in place (a scanned slice would be copied first)
    split = [Q.split_codes(params["units"][f"b{j}"], _matmul_keys(kind, cfg))
             for j, kind in enumerate(pattern)]
    whole_codes = [c for c, _ in split]
    units = {f"b{j}": rest for j, (_, rest) in enumerate(split)}
    unit_ids = jnp.arange(cfg.pattern_units)

    def unit_step(carry, xs):
        x, aux = carry
        unit_params, unit_cache, unit = xs
        new_caches = {}
        for j, kind in enumerate(pattern):
            c = None if unit_cache is None else unit_cache[f"b{j}"]
            bp = _serve_block(unit_params[f"b{j}"], kind, cfg,
                              license_intervals, whole_codes[j], unit)
            x, a, nc = _apply_block(bp, kind, x, cfg,
                                    cache=c, pos=pos,
                                    attend_cache=attend_cache,
                                    chunk_valid=chunk_valid,
                                    paged_tables=paged_tables,
                                    paged_kernel=paged_kernel)
            aux = aux + a
            new_caches[f"b{j}"] = nc if nc is not None else ()
        if cache is None and x.shape[1] > 1:
            # Pin the residual stream (== the per-unit activation checkpoint
            # jax.checkpoint saves): batch over DP axes, optionally
            # seq-sharded over "model" (Megatron-SP).
            if cfg.seq_sharded_acts:
                x = L.hint_sharding(x, "batch", "model", None)
            elif cfg.pin_acts:
                x = L.hint_sharding(x, "batch", None, None)
        return (x, aux), new_caches

    step = unit_step
    if cfg.remat and cache is None:
        step = jax.checkpoint(unit_step, prevent_cse=False)

    if cache is not None:
        (x, aux_total), new_unit_caches = jax.lax.scan(
            step, (x, aux_total), (units, cache["units"], unit_ids)
        )
    else:
        (x, aux_total), _ = jax.lax.scan(
            lambda c, xs: (step(c, (xs[0], None, xs[1]))[0], ()),
            (x, aux_total), (units, unit_ids),
        )
        new_unit_caches = None

    new_cache = None
    if cache is not None:
        new_cache = {"units": new_unit_caches}

    tail = cfg.tail_pattern
    if tail:
        new_tail = {}
        for j, kind in enumerate(tail):
            c = None if cache is None else cache["tail"][f"t{j}"]
            tp = _serve_block(params["tail"][f"t{j}"], kind, cfg,
                              license_intervals)
            x, a, nc = _apply_block(tp, kind, x, cfg,
                                    cache=c, pos=pos,
                                    attend_cache=attend_cache,
                                    chunk_valid=chunk_valid,
                                    paged_tables=paged_tables,
                                    paged_kernel=paged_kernel)
            aux_total = aux_total + a
            new_tail[f"t{j}"] = nc if nc is not None else ()
        if new_cache is not None:
            new_cache["tail"] = new_tail

    x = L.apply_norm(x, params["final_norm"], cfg)
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"]).astype(jnp.float32)
    if cfg.padded_vocab != cfg.vocab_size:
        pad_ids = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
        logits = jnp.where(pad_ids[None, None, :], -1e9, logits)
    return logits, aux_total, new_cache


# --------------------------------------------------------------------- loss
def lm_loss(
    params: Dict[str, Any], cfg: ModelConfig, tokens: jnp.ndarray,
    labels: jnp.ndarray, *, patch_embeds: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Causal LM cross-entropy (+ MoE aux).  labels = next-token ids, with
    -100 entries masked out.  For VLM inputs the patch prefix positions are
    excluded from the loss by construction (labels cover text only)."""
    logits, aux, _ = forward(params, cfg, tokens, patch_embeds=patch_embeds)
    if patch_embeds is not None:
        logits = logits[:, patch_embeds.shape[1]:]
    mask = labels != -100
    safe = jnp.where(mask, labels, 0)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    denom = jnp.maximum(jnp.sum(mask), 1)
    loss = jnp.sum(jnp.where(mask, nll, 0.0)) / denom
    total = loss + cfg.moe_aux_weight * aux
    return total, {"lm_loss": loss, "aux_loss": aux}

"""Serving engine: batched prefill + decode with KV/SSM caches, and the
paper's *licensed serving* as a first-class feature — a request's license
tier selects the interval-masked weight view served to it (one stored
weight set, many accuracy tiers, §3.5).

``serve_step`` / ``prefill_step`` are the pure functions the multi-pod
dry-run lowers; ``ServingEngine`` is the host-side driver (edge-device or
serving-pod role from Fig. 2).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.licensing import FULL_TIER, LicenseTier, apply_license
from repro.models import model as model_lib
from repro.serving.scheduler import TierViewCache


def prefill_step(params, cfg: ModelConfig, tokens, cache,
                 patch_embeds=None, license_intervals=None):
    """Fill the cache from a token batch; returns (last-token logits, cache)."""
    logits, _, cache = model_lib.forward(
        params, cfg, tokens, patch_embeds=patch_embeds, cache=cache, pos=0,
        license_intervals=license_intervals,
    )
    return logits[:, -1], cache


def prefill_suffix_step(params, cfg: ModelConfig, tokens, cache, pos,
                        license_intervals=None):
    """Suffix prefill: extend a cache already holding positions ``[0, pos)``
    with ``tokens`` (B, W) — the uncached tail of a prompt whose prefix the
    prefix cache (serving/prefix.py) restored from retained blocks.

    Unlike :func:`prefill_step`, attention reads the resident cache (the
    shared prefix plus this step's own writes), and the *full* per-step
    logits come back — the caller picks the row of the last real token,
    which for right-padded suffixes is not the last row.  ``pos`` may be a
    per-lane traced scalar under ``vmap`` (variable prefill offsets)."""
    logits, _, cache = model_lib.forward(
        params, cfg, tokens, cache=cache, pos=pos,
        license_intervals=license_intervals, attend_cache=True,
    )
    return logits, cache


def stack_lane_caches(cfg: ModelConfig, b: int, capacity: int):
    """``b`` independent batch-1 caches stacked on a new leading lane
    axis — the layout :func:`prefill_chunk_step` (and the gateway's
    vmapped per-lane steps) operates on.  Unlike ``init_cache(cfg, b,
    capacity)``, every leaf gets the lane axis *first* regardless of
    where its batch axis sits, so ``vmap`` over axis 0 hands each lane
    exactly a batch-1 cache."""
    lane = model_lib.init_cache(cfg, 1, capacity)
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (b, *x.shape)), lane)


def prefill_chunk_step(params, cfg: ModelConfig, tokens, caches, pos,
                       chunk_valid=None, license_intervals=None):
    """Left-aligned chunked prefill: advance each lane's cursor by up to
    ``chunk_size`` tokens against its own cache.

    ``tokens`` (B, W) holds each lane's next chunk starting at that
    lane's absolute cursor ``pos`` (B,); lanes whose remaining prompt is
    shorter than W right-pad the row and report the real row count in
    ``chunk_valid`` (B,) — pad rows are causally invisible and their
    cache writes are clamped/masked (see ``attention_block``).
    ``caches`` is the lane-stacked layout from :func:`stack_lane_caches`;
    per-lane offsets mean no single batch cache layout fits, so the step
    vmaps a batch-1 suffix prefill over the lane axis.  Returns the full
    per-chunk logits (B, W, V) — the caller reads row ``chunk_valid - 1``
    of the final chunk — and the updated lane caches."""
    b = tokens.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))

    def _one(t, c, po, cv):
        logits, _, nc = model_lib.forward(
            params, cfg, t[None], cache=c, pos=po,
            license_intervals=license_intervals, attend_cache=True,
            chunk_valid=cv)
        return logits[0], nc

    if chunk_valid is None:
        return jax.vmap(lambda t, c, po: _one(t, c, po, None),
                        in_axes=(0, 0, 0))(tokens, caches, pos)
    cv = jnp.broadcast_to(jnp.asarray(chunk_valid, jnp.int32), (b,))
    return jax.vmap(_one, in_axes=(0, 0, 0, 0))(tokens, caches, pos, cv)


def serve_step(params, cfg: ModelConfig, tokens, cache, pos,
               license_intervals=None):
    """ONE decode step: tokens (B,1) + cache at fill-level ``pos``.

    With int8 ``params`` (models/int8.py) and ``license_intervals``, this
    is the licensed decode: the tier's mask applied to the int8 codes
    inside the layer scan."""
    logits, _, cache = model_lib.forward(params, cfg, tokens, cache=cache,
                                         pos=pos, license_intervals=license_intervals)
    return logits[:, -1], cache


def serve_step_paged(params, cfg: ModelConfig, tokens, cache, tables, pos,
                     license_intervals=None, *, kernel: str = "off"):
    """ONE kernel-resident decode step over the paged pool.

    ``tokens`` (B, 1), ``cache`` the hybrid pytree from
    ``PagedCachePool.decode_cache`` (attention leaves are physical block
    arrays, per-lane state lane-gathered), ``tables`` (B, T) block tables
    trimmed to the micro-batch's used width, ``pos`` (B,) per-lane
    absolute positions.  Attention reads each cache byte once through the
    table and writes the one new K/V token through its block index — no
    contiguous view of any sequence exists (see ``models/layers.py``
    ``attention_block_paged``).  ``kernel`` selects the Pallas
    paged-attention kernel ("pallas" / "interpret") or the pure-JAX
    gather fallback ("off")."""
    logits, _, cache = model_lib.forward(
        params, cfg, tokens, cache=cache, pos=pos,
        license_intervals=license_intervals, paged_tables=tables,
        paged_kernel=kernel)
    return logits[:, -1], cache


def right_align(prompts, width: int, rows: int) -> np.ndarray:
    """(rows, width) int32 token matrix; short prompts padded on the left
    with their own first token (position-consistent, never attends ahead).
    Shared by the engine's group batching and the gateway's prompt bucket."""
    toks = np.zeros((rows, width), np.int32)
    for i, p in enumerate(prompts):
        if len(p) == 0:
            raise ValueError(f"empty prompt at row {i}")
        toks[i, width - len(p):] = p
        toks[i, : width - len(p)] = p[0]
    return toks


def sample(logits: jnp.ndarray, key, *, temperature: float = 1.0,
           top_k: int = 0) -> jnp.ndarray:
    if temperature <= 0:
        return jnp.argmax(logits, -1).astype(jnp.int32)
    logits = logits / temperature
    if top_k:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    return jax.random.categorical(key, logits).astype(jnp.int32)


def sample_lane(logits: jnp.ndarray, key, temperature, top_k, *,
                with_rng: bool = True, with_topk: bool = True) -> jnp.ndarray:
    """One lane's sampling step with *traced* temperature/top-k.

    The gateway fuses this into its vmapped decode so each step ships one
    token id per lane device->host instead of a full logits row.  Both
    knobs are per-lane arrays under ``vmap``: greedy (argmax) where
    ``temperature <= 0``, else temperature-scaled categorical; ``top_k``
    is a traced int (0 = off) whose kth-largest threshold comes from a
    descending sort, so lanes with different k share one compilation.
    Matches :func:`sample` for any static ``top_k``.

    ``with_rng``/``with_topk`` are *static* batch-level facts ("no lane
    in this micro-batch samples / uses top-k") that let an all-greedy or
    no-top-k batch skip the categorical draw and the O(V log V) sort
    entirely — the traced per-lane knobs would otherwise keep both live
    in the hot loop for every step.
    """
    greedy = jnp.argmax(logits, -1).astype(jnp.int32)
    if not with_rng:
        return greedy
    v = logits.shape[-1]
    scaled = logits / jnp.maximum(temperature, 1e-6)
    if with_topk:
        kth = jnp.sort(scaled)[::-1][jnp.clip(top_k - 1, 0, v - 1)]
        scaled = jnp.where((top_k > 0) & (scaled < kth), -jnp.inf, scaled)
    drawn = jax.random.categorical(key, scaled[None])[0].astype(jnp.int32)
    return jnp.where(temperature <= 0, greedy, drawn)


@dataclass
class Request:
    prompt: np.ndarray                 # (S,) int32
    max_new_tokens: int = 16
    license: str = "full"
    temperature: float = 0.0
    out_tokens: List[int] = field(default_factory=list)


class ServingEngine:
    """Batched serving with per-tier licensed weight views.

    Weight views are materialized once per tier (masking is elementwise and
    cheap relative to serving) and cached — the paper's "unlimited licenses,
    one stored model".
    """

    def __init__(self, cfg: ModelConfig, params,
                 tiers: Optional[Dict[str, LicenseTier]] = None,
                 quantized: bool = False):
        """``quantized=True``: ONE int8 weight store serves all tiers with
        license masks applied to the int8 codes in the layer scan
        (beyond-paper mode; see serving/quantized.py).  Default is the
        paper's mask-at-load."""
        self.cfg = cfg
        self.quantized = quantized
        if quantized:
            from repro.serving.quantized import quantize_serving_params

            self.base_params = quantize_serving_params(params)
        else:
            self.base_params = params
        self.tiers = dict(tiers or {})
        self.tiers.setdefault("full", FULL_TIER)
        # (tier, version=None)-keyed licensed views, shared machinery with
        # the gateway (serving/gateway.py); the engine is versionless.
        self._views = TierViewCache(self._build_view, capacity=64)
        self._prefill = jax.jit(
            lambda p, t, c, li: prefill_step(p, cfg, t, c, license_intervals=li)
        )
        self._decode = jax.jit(
            lambda p, t, c, pos, li: serve_step(p, cfg, t, c, pos,
                                                license_intervals=li)
        )

    def _build_view(self, license_name: str, _version):
        """(params, intervals) licensed view — built once per tier."""
        tier = self.tiers.get(license_name)
        if tier is None:
            raise KeyError(f"unknown license tier {license_name!r}")
        if self.quantized:
            from repro.serving.quantized import tier_intervals

            return self.base_params, tier_intervals(tier)  # one store, every tier
        return apply_license(self.base_params, tier), None

    def params_for(self, license_name: str):
        return self._views.get(license_name)[0]

    def intervals_for(self, license_name: str):
        if not self.quantized:
            return None
        return self._views.get(license_name)[1]

    def gateway(self, **kw):
        """A :class:`~repro.serving.gateway.LicensedGateway` over this
        engine's weights and tiers (continuous batching front end).

        Quantization follows the engine; construct ``LicensedGateway``
        directly to choose a different weight-store mode."""
        if "quantized" in kw or "already_quantized" in kw:
            raise ValueError("gateway() mirrors the engine's quantization; "
                             "construct LicensedGateway directly to override")
        from repro.serving.gateway import LicensedGateway

        return LicensedGateway(self.cfg, self.base_params, tiers=self.tiers,
                               quantized=self.quantized,
                               already_quantized=self.quantized, **kw)

    def generate(self, requests: List[Request], *, seed: int = 0) -> List[Request]:
        """Serve a batch of same-tier requests (mixed tiers are grouped)."""
        by_tier: Dict[str, List[Request]] = {}
        for r in requests:
            by_tier.setdefault(r.license, []).append(r)
        for tier_name, group in by_tier.items():
            self._generate_group(group, tier_name, seed)
        return requests

    def _generate_group(self, group: List[Request], tier_name: str, seed: int):
        params = self.params_for(tier_name)
        li = self.intervals_for(tier_name)
        cfg = self.cfg
        b = len(group)
        max_prompt = max(len(r.prompt) for r in group)
        max_new = max(r.max_new_tokens for r in group)
        capacity = max_prompt + max_new

        toks = right_align([r.prompt for r in group], max_prompt, b)

        cache = model_lib.init_cache(cfg, b, capacity)
        logits, cache = self._prefill(params, jnp.asarray(toks), cache, li)
        key = jax.random.PRNGKey(seed)
        cur = sample(logits, key, temperature=group[0].temperature)
        for i, r in enumerate(group):
            r.out_tokens.append(int(cur[i]))
        for step in range(max_new - 1):
            key, sub = jax.random.split(key)
            logits, cache = self._decode(
                params, cur[:, None], cache, max_prompt + step, li
            )
            cur = sample(logits, sub, temperature=group[0].temperature)
            for i, r in enumerate(group):
                if len(r.out_tokens) < r.max_new_tokens:
                    r.out_tokens.append(int(cur[i]))

"""Quantized licensed serving (beyond-paper §Perf).

The paper's licensing masks weights in the DB and ships a *separate* weight
view per tier (mask-at-load).  Here ONE int8 weight store serves every
tier: block weights are kept as int8 leaves (codes, f32 scale per output
column; the format and how a block consumes it live in
``models/int8.py``), and a tier is only its live magnitude intervals
(:func:`tier_intervals`), applied to the codes inside the layer scan.
This module builds the store (:func:`quantize_serving_params`, and
:func:`requantize_layers` for a staged update), turns a tier into its
intervals, and materializes a tier's float view once where a deployment
asks for it (:func:`materialize_licensed_view`, the ``masked_dequant``
Pallas kernel).

Wins vs mask-at-load:
  * weight HBM reads are int8 — ~2x less than bf16, 4x less than f32;
  * a new tier costs ZERO extra weight memory (masks are a few floats);
  * the licensed view can't leak: full-precision weights never exist in
    the serving process.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.licensing import LicenseTier
from repro.models.int8 import is_qleaf

# leaves excluded from quantization (precision- or structure-critical)
_SKIP = ("norm", "bias", "router", "conv", "A_log", "dt_bias", "D_skip",
         "a_param", "tok", "lm_head", "scale")


def _eligible(name: str, leaf) -> bool:
    short = name.split("/")[-1]
    if any(k in short for k in _SKIP):
        return False
    if not hasattr(leaf, "ndim"):
        return False
    # unit-stacked weights are (U, in, out[, ...]); plain 2-D under units are
    # stacked biases — leave those alone
    if "units/" in name:
        return leaf.ndim >= 3
    return "tail/" in name and leaf.ndim >= 2


@jax.jit
def _quantize_leaf(w) -> Dict[str, jnp.ndarray]:
    """Per-output-channel symmetric int8 of one eligible weight: scale
    reduces over the second-to-last dim (the contraction dim of every
    block matmul).  Jitted so the f32 intermediates fuse away; op by op,
    a full-width stacked FFN leaf would hold several 3.2 GB f32 copies."""
    w = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w), axis=-2, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    codes = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
    return {"codes": codes, "scale": scale}


def quantize_serving_params(params: Any) -> Any:
    """Same-structure tree; eligible weights become {"codes","scale"} dicts."""
    from repro.core.pytree_io import _path_str

    def q(path, leaf):
        name = _path_str(path)
        if not _eligible(name, leaf):
            return leaf
        return _quantize_leaf(leaf)

    return jax.tree_util.tree_map_with_path(q, params)


def requantize_layers(qparams: Any, new_flat: Dict[str, Any],
                      touched: Sequence[str]) -> Any:
    """Incremental requantize: rebuild the int8 store with ONLY ``touched``
    layers re-derived from ``new_flat`` (flat name -> new float array, as
    produced by ``core.pytree_io.flatten_params``); every other leaf is
    reused by reference from ``qparams``.

    This is the staged-update path's bounded alternative to
    ``quantize_serving_params`` over the whole tree: a delta touching k
    layers costs O(k) quantizations, and the stager can thread a batch of
    layer names per scheduler step.  Leaf eligibility is decided by what
    the *existing* store quantized (same names, same shapes across
    versions), so the rebuilt tree always matches the full requantize
    bit-for-bit."""
    from repro.core.pytree_io import _path_str

    want = set(touched)

    def q(path, leaf):
        name = _path_str(path)
        if name not in want:
            return leaf
        new = new_flat[name]
        return _quantize_leaf(new) if is_qleaf(leaf) else new

    return jax.tree_util.tree_map_with_path(q, qparams, is_leaf=is_qleaf)


def materialize_licensed_view(qparams: Any, tier: Optional[LicenseTier],
                              dtype) -> Any:
    """Run the fused masked-dequant ONCE, returning a full-precision
    licensed view of the int8 store.

    This is the gateway's ``materialize_int8_views`` path: a long decode
    stream re-pays the in-scan mask every step, so for hot tiers it
    can be cheaper to burn the HBM for a materialized view amortized
    across the whole (tier, version) lifetime.  2-D weight slices go
    through ``kernels.ops.masked_dequant`` (the Pallas kernel on TPU,
    its interpret/ref form on CPU); stacked leaves are dequantized
    slice-by-slice along their leading unit/expert axes.
    """
    from repro.kernels import ops

    li = tier_intervals(tier)
    if li is None:
        ivs = []
    else:
        lo, hi = (np.asarray(a) for a in li)
        ivs = [(float(l), float(h)) for l, h in zip(lo, hi) if h > l]

    def dq(leaf):
        if not is_qleaf(leaf):
            return leaf
        codes, scale = leaf["codes"], leaf["scale"]
        if codes.ndim == 2:
            return ops.masked_dequant(codes, scale, ivs, out_dtype=dtype)
        lead = codes.shape[:-2]
        r, c = codes.shape[-2:]
        flat_c = codes.reshape((-1, r, c))
        flat_s = jnp.broadcast_to(scale, (*lead, 1, c)).reshape((-1, 1, c))
        slices = [ops.masked_dequant(flat_c[i], flat_s[i], ivs, out_dtype=dtype)
                  for i in range(flat_c.shape[0])]
        return jnp.stack(slices).reshape((*lead, r, c))

    return jax.tree_util.tree_map(dq, qparams, is_leaf=is_qleaf)


def live_intervals(intervals: Sequence[Tuple[float, float]]
                   ) -> List[Tuple[float, float]]:
    """A tier's intervals as f32 bounds, empty ones dropped, sorted and
    merged where they overlap or touch: the same set of magnitudes, in as
    few ``[lo, hi)`` tests as it takes."""
    out: List[Tuple[float, float]] = []
    for lo, hi in sorted((float(np.float32(a)), float(np.float32(b)))
                         for a, b in intervals):
        if not hi > lo:
            continue
        if out and lo <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], hi))
        else:
            out.append((lo, hi))
    return out


def tier_intervals(tier: Optional[LicenseTier]) -> Optional[Tuple[jnp.ndarray, jnp.ndarray]]:
    """A tier's live intervals as (lo, hi) f32 arrays of shape (n,), or
    None when nothing is masked.

    The compiled step specializes on ``n``: the full tier (None) runs with
    no mask, and tiers with the same count of live intervals share one
    program.  The in-scan mask applies one global interval set (per-layer
    patterns would need per-unit interval tensors — supported by stacking,
    omitted for brevity); '*' tiers are the common production case."""
    if tier is None or not tier.masks:
        return None
    ivs = list(tier.masks.get("*", ()))
    for pat, v in tier.masks.items():
        if pat != "*":
            ivs.extend(v)
    live = live_intervals(ivs)
    if not live:
        return None
    lo, hi = (np.asarray(col, np.float32) for col in zip(*live))
    return jnp.asarray(lo), jnp.asarray(hi)

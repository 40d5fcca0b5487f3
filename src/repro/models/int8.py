"""The int8 weight leaf and how a model block consumes it.

A quantized weight is a dict leaf ``{"codes": int8 (..., K, N), "scale":
f32 (..., 1, N)}``: per-output-column symmetric codes, ``w = codes *
scale`` (``serving/quantized.py`` builds the store).  A license tier is a
set of magnitude intervals ``[lo, hi)``; the weights it kills are those
with ``lo <= |codes * scale| < hi`` in f32.

Two ways a block takes such a leaf, chosen per sub-tree by the model
(``models/model.py`` ``_matmul_keys``):

  * as codes, through :func:`dense`: the GQA projections and the dense
    MLPs.  A tier's intervals become, per column, ranges of dead codes
    (:func:`code_bounds`, exact against the f32 test), the masked codes
    enter the dot in the activation dtype (exact for int8), the dot
    accumulates in f32 and the column scale multiplies its output.  The
    layer scan leaves these codes whole (:func:`split_codes`) and each
    unit reads its own in place: on a TPU the Pallas kernel
    ``kernels/quant_matmul`` takes the stack and the unit index, elsewhere
    jax.numpy slices, masks and converts the unit's codes for the dot.
    No float copy of the weight is kept;
  * eagerly, through :func:`dequant_leaf`: every other int8 leaf (MLA,
    MoE experts, SSM and RG-LRU projections) is dequantized and masked to
    a float weight before its block runs.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.kernels.quant_matmul import dead_codes


def is_qleaf(leaf) -> bool:
    return isinstance(leaf, dict) and "codes" in leaf and "scale" in leaf


def dequant_leaf(leaf, lo: Optional[jnp.ndarray], hi: Optional[jnp.ndarray],
                 dtype) -> jnp.ndarray:
    """One int8 leaf as a float weight in ``dtype``: ``c * s`` in f32,
    zeroed where ``lo[i] <= |c * s| < hi[i]`` for any of the tier's live
    intervals (no mask when ``lo`` is None), then cast.  The same weights
    as the ``masked_dequant`` Pallas kernel.  A float leaf passes
    through."""
    if not is_qleaf(leaf):
        return leaf
    w = leaf["codes"].astype(jnp.float32) * leaf["scale"]
    if lo is not None:
        mag = jnp.abs(w)
        dead = jnp.zeros(w.shape, bool)
        for i in range(lo.shape[0]):
            dead = dead | ((mag >= lo[i]) & (mag < hi[i]))
        w = jnp.where(dead, 0.0, w)
    return w.astype(dtype)


def dequant_tree(tree: Any, license_intervals, dtype) -> Any:
    lo, hi = (None, None) if license_intervals is None else license_intervals
    return jax.tree_util.tree_map(
        lambda l: dequant_leaf(l, lo, hi, dtype), tree, is_leaf=is_qleaf
    )


def code_bounds(scale: jnp.ndarray, lo: jnp.ndarray,
                hi: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per column, the codes a tier kills: ``(a, b)``, each (n,
    *scale.shape) int32 in 0..128 (:func:`kernels.quant_matmul.dead_codes`
    applies them), such that for every code ``c`` in
    -127..127 ``a[i] <= |c| < b[i]`` exactly when ``lo[i] <= |fl(c * s)| <
    hi[i]`` in f32.

    ``fl(k * s)`` is monotone in ``k`` for ``s > 0``, so the codes whose
    magnitude is below a bound ``t`` are ``0..n(t)-1`` with ``n(t)`` the
    count of ``k`` in 0..127 with ``fl(k * s) < t``.  ``ceil(t / s)`` is
    within one of that count (the quotient and the products each round
    by far less than a step of ``k``), and one check with the f32 product
    on each side makes it exact.  O(columns x intervals) work."""
    def count_below(t):
        t = t.reshape((-1,) + (1,) * scale.ndim)
        k = jnp.clip(jnp.ceil(t / scale), 0.0, 128.0)
        k = jnp.where((k > 0) & ((k - 1.0) * scale >= t), k - 1.0, k)
        k = jnp.where((k < 128) & (k * scale < t), k + 1.0, k)
        return k.astype(jnp.int32)

    return count_below(lo), count_below(hi)


def _with_code_bounds(leaf, license_intervals):
    if not is_qleaf(leaf) or license_intervals is None:
        return leaf
    return {**leaf, "dead": code_bounds(leaf["scale"], *license_intervals)}


def split_codes(block: Dict[str, Any], matmul_keys: Sequence[str]
                ) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """``(codes, rest)`` of a unit-stacked block: ``codes[key][name]`` holds
    the int8 codes of each leaf under the block's matmul keys, whole, and
    ``rest`` is the block without them.  The layer scan slices ``rest``
    only; :func:`dense` reads a unit's codes in place from the whole stack
    (``serve_block(..., codes, unit)``), where a scanned slice would be
    copied first."""
    codes: Dict[str, Any] = {}
    rest = dict(block)
    for key in matmul_keys:
        sub = block.get(key, {})
        codes[key] = {n: l["codes"] for n, l in sub.items() if is_qleaf(l)}
        rest[key] = {n: ({"scale": l["scale"]} if is_qleaf(l) else l)
                     for n, l in sub.items()}
    return codes, rest


def serve_block(block: Dict[str, Any], matmul_keys: Sequence[str],
                license_intervals, dtype, codes: Optional[Dict] = None,
                unit=None) -> Dict[str, Any]:
    """One block's weights as its layers take them.  Sub-trees named in
    ``matmul_keys`` are consumed only through :func:`dense`: their int8
    leaves stay int8, paired with the tier's dead code ranges (and, from
    :func:`split_codes`, with the whole stack of codes and the ``unit`` to
    read).  Every other int8 leaf is dequantized and masked eagerly
    (:func:`dequant_leaf`)."""
    out = {}
    for key, sub in block.items():
        if key not in matmul_keys:
            out[key] = dequant_tree(sub, license_intervals, dtype)
            continue
        whole = (codes or {}).get(key, {})
        out[key] = {n: _with_code_bounds(
                        {**l, "codes": whole[n], "unit": unit} if n in whole else l,
                        license_intervals)
                    for n, l in sub.items()}
    return out


def dense(x: jnp.ndarray, w) -> jnp.ndarray:
    """``x (..., K) @ w (K, N)`` for a float weight or an int8 leaf.

    An int8 leaf is multiplied as codes: the tier's dead codes (``"dead"``,
    from :func:`serve_block`) count as 0, the codes enter the dot in
    ``x.dtype`` (exact for int8), the dot accumulates in f32, and the
    per-column scale multiplies its output.  The same mathematics as
    dequantizing first, without a weight-sized copy; slightly more
    precise, as ``c * s`` is never rounded to bf16.  On a TPU the Pallas
    kernel ``kernels/quant_matmul`` does it (a step of the served model
    runs 1-5% faster than with XLA's fusion of the form below); elsewhere
    jax.numpy does."""
    if not is_qleaf(w):
        return jnp.einsum("...k,kn->...n", x, w)
    unit = w.get("unit")
    dead = w.get("dead")
    if dead is not None:
        dead = tuple(d.reshape(d.shape[0], -1) for d in dead)
    if jax.default_backend() == "tpu":
        return ops.licensed_matmul(x, w["codes"], w["scale"], dead,
                                   0 if unit is None else unit)
    codes = w["codes"] if unit is None else w["codes"][unit]
    if dead is not None:
        codes = jnp.where(dead_codes(codes, *dead), jnp.zeros_like(codes),
                          codes)
    y = jnp.einsum("...k,kn->...n", x, codes.astype(x.dtype),
                   preferred_element_type=jnp.float32)
    return (y * w["scale"].reshape(-1)).astype(x.dtype)


def fused_weight_share(blocks: Sequence[Tuple[Dict[str, Any], Sequence[str]]]
                       ) -> float:
    """Share of the int8 weight elements of ``blocks`` — pairs of a block's
    weights and its :func:`serve_block` matmul keys — that :func:`dense`
    consumes as codes rather than an eager dequant; 0.0 with none.  Read
    from the tree, and exact: every int8 leaf under a matmul key reaches
    :func:`dense` (an array op on the leaf dict would fail), and
    :func:`dense` has no fallback (on a TPU a weight the kernel cannot
    tile raises when the step is traced)."""
    fused = total = 0
    for block, matmul_keys in blocks:
        for k, sub in block.items():
            n = sum(int(np.prod(l["codes"].shape))
                    for l in jax.tree_util.tree_leaves(sub, is_leaf=is_qleaf)
                    if is_qleaf(l))
            total += n
            fused += n if k in matmul_keys else 0
    return fused / total if total else 0.0

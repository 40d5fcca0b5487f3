"""Dynamic and static model licensing (paper §3.5, Algorithm 1).

A *license tier* is a set of per-layer magnitude intervals; weights whose
|w| falls inside a masked interval are zeroed at serve time.  One stored
weight set thus serves unlimited accuracy tiers ("dynamic licensing").

* ``apply_license`` — pure-JAX mask transform (jit-able, shard-preserving).
* ``calibrate_license`` — Algorithm 1 verbatim: divide the weight range into
  k equal intervals, cumulatively cut intervals layer-by-layer until the
  evaluated accuracy reaches the target.
* ``make_static_tiers`` — precompute a ladder of tiers for the Accuracy
  table (static licensing = lookup; dynamic licensing = on-demand calibrate).

Adaptation (DESIGN.md §4): dynamics params (SSM A_log / dt_bias / RG-LRU
gates, norm scales) are excluded from masking — interval-pruning those can
destabilize the recurrence rather than merely degrade accuracy.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.compression import is_dynamics_param
from repro.core.pytree_io import _path_str, flatten_params, unflatten_like

Interval = Tuple[float, float]


@dataclass(frozen=True)
class LicenseTier:
    """A named accuracy tier: per-layer-pattern magnitude-interval masks.

    ``masks`` maps a substring pattern (matched against the canonical layer
    path) to intervals [lo, hi); weights with lo <= |w| < hi are zeroed.
    Pattern "*" applies to every maskable layer.
    """

    name: str
    masks: Dict[str, Tuple[Interval, ...]] = field(default_factory=dict)
    accuracy: Optional[float] = None

    def intervals_for(self, layer_name: str) -> List[Interval]:
        out: List[Interval] = []
        for pattern, ivs in self.masks.items():
            if pattern == "*" or pattern in layer_name:
                out.extend(ivs)
        return out

    def as_json(self) -> Dict[str, list]:
        return {k: [list(iv) for iv in v] for k, v in self.masks.items()}

    def fingerprint(self) -> str:
        """Stable short hash of (name, masks) — the audit stream's proof
        of *which* mask definition a tier name meant at event time, so a
        redefined tier is distinguishable from its earlier self."""
        import hashlib
        import json as _json

        payload = _json.dumps({"name": self.name, "masks": self.as_json()},
                              sort_keys=True)
        return hashlib.sha256(payload.encode()).hexdigest()[:12]

    @staticmethod
    def from_json(name: str, masks: Dict[str, Sequence[Sequence[float]]],
                  accuracy: Optional[float] = None) -> "LicenseTier":
        return LicenseTier(
            name=name,
            masks={k: tuple((float(a), float(b)) for a, b in v) for k, v in masks.items()},
            accuracy=accuracy,
        )


FULL_TIER = LicenseTier(name="full", masks={})


def interval_mask(w: jnp.ndarray, intervals: Sequence[Interval]) -> jnp.ndarray:
    """Boolean mask: True where the weight SURVIVES (|w| outside all intervals)."""
    if not intervals:
        return jnp.ones(w.shape, dtype=bool)
    mag = jnp.abs(w)
    dead = jnp.zeros(w.shape, dtype=bool)
    for lo, hi in intervals:
        dead = dead | ((mag >= lo) & (mag < hi))
    return ~dead


def mask_weight(w: jnp.ndarray, intervals: Sequence[Interval]) -> jnp.ndarray:
    return jnp.where(interval_mask(w, intervals), w, jnp.zeros_like(w))


@functools.partial(jax.jit, static_argnums=1)
def _mask_leaf(w: jnp.ndarray, intervals: Tuple[Interval, ...]) -> jnp.ndarray:
    """:func:`mask_weight` as one fused pass, so the only new buffer is
    the masked leaf.  Op by op, |w|, the bool masks and a zeros copy
    come to ~5x the leaf, more than a 16 GB chip has left beside a
    full-width bf16 model and its masked view."""
    return mask_weight(w, intervals)


def apply_license(
    params: Any,
    tier: LicenseTier,
    *,
    exclude: Callable[[str], bool] = is_dynamics_param,
) -> Any:
    """Return params with the tier's interval masks applied (pure function).

    Leaves stay where they are: unmasked leaves are passed through by
    reference and masked ones are computed on the leaf's own device.
    Shard-preserving: masking is elementwise, so output shardings match
    inputs under jit; this runs inside the licensed ``serve_step``.
    """
    if not tier.masks:
        return params

    def mask(path, arr):
        name = _path_str(path)
        ivs = tier.intervals_for(name)
        if not ivs or exclude(name) or np.ndim(arr) < 2:
            return arr
        return _mask_leaf(arr, tuple(ivs))

    return jax.tree_util.tree_map_with_path(mask, params)


def license_stats(params: Any, tier: LicenseTier,
                  exclude: Callable[[str], bool] = is_dynamics_param) -> Dict[str, float]:
    """Fraction of weights hidden by the tier (reported per benchmark run)."""
    flat = flatten_params(params)
    total = masked = 0
    for name, arr in flat.items():
        ivs = tier.intervals_for(name)
        total += arr.size
        if ivs and not exclude(name) and arr.ndim >= 2:
            surv = np.asarray(interval_mask(jnp.asarray(arr), ivs))
            masked += int(arr.size - surv.sum())
    return {"total": float(total), "masked": float(masked),
            "masked_frac": masked / max(total, 1)}


# ----------------------------------------------------------- Algorithm 1
@dataclass
class CalibrationStep:
    interval: Interval
    layer: str
    accuracy: float


def calibrate_license(
    params: Any,
    eval_fn: Callable[[Any], float],
    target_accuracy: float,
    *,
    k_intervals: int = 10,
    tier_name: str = "custom",
    tolerance: float = 0.02,
    layer_order: Optional[List[str]] = None,
    exclude: Callable[[str], bool] = is_dynamics_param,
    interval_mode: str = "quantile",
    refine_steps: int = 0,
) -> Tuple[LicenseTier, List[CalibrationStep]]:
    """Algorithm 1 — prune the model based on desired accuracy.

    divide weight range into k equal intervals; for each interval, for each
    layer, cut weights in that interval; stop when accuracy of the pruned
    model is close to the target.  Returns the tier holding the CUT
    intervals per layer (the paper returns the *uncut* list; storing the cut
    list is equivalent and is what the Accuracy-table mask needs).

    ``interval_mode``: the paper's "equal-sized intervals" is ambiguous —
    "quantile" (default) makes intervals equal in POPULATION, giving smooth
    accuracy control (weights concentrate near 0, so equal-WIDTH intervals
    cut most of the model in the first step); "width" is the literal
    equal-width reading.

    ``refine_steps`` (beyond paper): Algorithm 1 is interval-quantized, so
    the final cut can overshoot the target by a whole interval's worth of
    accuracy.  With refine_steps > 0 the last interval's upper edge is
    bisected that many times, landing the achieved accuracy as close to
    the target as the model's accuracy curve allows.
    """
    flat = flatten_params(params)
    maskable = [n for n, a in flat.items() if not exclude(n) and a.ndim >= 2]
    if layer_order is not None:
        maskable = [n for n in layer_order if n in maskable]

    mags = np.concatenate([np.abs(np.asarray(flat[n])).reshape(-1) for n in maskable])
    hi = float(mags.max())
    if interval_mode == "quantile":
        qs = np.linspace(0.0, 1.0, k_intervals + 1)
        edges = np.quantile(mags, qs)
        edges[0], edges[-1] = 0.0, hi * (1 + 1e-6)
        edges = np.maximum.accumulate(edges)
    else:
        edges = np.linspace(0.0, hi * (1 + 1e-6), k_intervals + 1)

    cut: Dict[str, List[Interval]] = {n: [] for n in maskable}
    trace: List[CalibrationStep] = []
    current = dict(flat)

    # Ascending magnitude: cut least-important (smallest) intervals first,
    # mirroring gradual magnitude pruning (§3.5).
    done = False
    last_layer = None
    for i in range(k_intervals):
        iv = (float(edges[i]), float(edges[i + 1]))
        for layer in maskable:
            cut[layer].append(iv)
            current[layer] = np.asarray(mask_weight(jnp.asarray(current[layer]), [iv]))
            acc = float(eval_fn(unflatten_like(params, current)))
            trace.append(CalibrationStep(interval=iv, layer=layer, accuracy=acc))
            if acc <= target_accuracy + tolerance:
                done = True
                last_layer = layer
                break
        if done:
            break

    if done and refine_steps and trace and last_layer is not None:
        # bisect the final interval's upper edge on its layer
        lo_edge, hi_edge = cut[last_layer][-1]
        base = dict(current)
        base[last_layer] = np.asarray(flat[last_layer])
        # replay all cuts on this layer except the final one
        for iv in cut[last_layer][:-1]:
            base[last_layer] = np.asarray(
                mask_weight(jnp.asarray(base[last_layer]), [iv]))
        best_hi, lo, hi = hi_edge, lo_edge, hi_edge
        for _ in range(refine_steps):
            mid = 0.5 * (lo + hi)
            trial = np.asarray(mask_weight(jnp.asarray(base[last_layer]),
                                           [(lo_edge, mid)]))
            cand = dict(base)
            cand[last_layer] = trial
            acc = float(eval_fn(unflatten_like(params, cand)))
            trace.append(CalibrationStep(interval=(lo_edge, mid),
                                         layer=last_layer, accuracy=acc))
            if acc <= target_accuracy:
                best_hi, hi = mid, mid   # overshoot: shrink the cut
            else:
                lo = mid                 # undershoot: widen toward hi_edge
                best_hi = hi
        cut[last_layer][-1] = (lo_edge, float(best_hi))

    tier = LicenseTier(
        name=tier_name,
        masks={n: tuple(v) for n, v in cut.items() if v},
        accuracy=None,
    )
    if trace:
        # re-evaluate the final tier exactly
        final = apply_license(params, tier, exclude=exclude)
        tier = LicenseTier(name=tier.name, masks=tier.masks,
                           accuracy=float(eval_fn(final)))
    return tier, trace


def make_static_tiers(
    params: Any,
    eval_fn: Callable[[Any], float],
    tier_targets: Dict[str, float],
    *,
    k_intervals: int = 10,
) -> Dict[str, LicenseTier]:
    """Precompute the Accuracy-table ladder (static licensing, §3.5)."""
    tiers: Dict[str, LicenseTier] = {}
    for name, target in sorted(tier_targets.items(), key=lambda kv: -kv[1]):
        tier, _ = calibrate_license(
            params, eval_fn, target, k_intervals=k_intervals, tier_name=name
        )
        tiers[name] = tier
    return tiers

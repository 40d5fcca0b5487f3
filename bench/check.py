"""The comparison that decides ``correct``.

After the window has closed and the served state is freed, a sample of
the finished requests, drawn from the seed, is run through the plain
reference: the request with the most served tokens, then for each tier
of the mix its finished requests in seeded order until that tier holds
its floor of served tokens.  Each tier's floor is the configuration's
``min_served_tokens_per_tier``, or every token the tier's requests of
the run ask for where that is less (the schedule is fixed, so this is
the same in every run); a tier that falls short fails the run.
The number compared is the widest gap, over every served token of the
sample, between the reference's best logit and the served token's logit
at the position that produced it: greedy decoding serves the best token,
so a sound run reads only rounding (bf16 compute against float32).
With the control on, the int4 control's picks stand in the program's
place and go through the same comparison.
"""
from __future__ import annotations

import importlib
from typing import Dict, List, Sequence, Tuple

import numpy as np


def floors(tracked: Sequence, tiers: Sequence[str], per_tier: int
           ) -> Dict[str, int]:
    """Served tokens each tier of the mix has to bring to the sample."""
    asked = {t: 0 for t in tiers}
    for tr in tracked:
        asked[tr.spec.tier] = asked.get(tr.spec.tier, 0) + \
            tr.spec.max_new_tokens
    return {t: min(per_tier, asked[t]) for t in tiers}


def sample(finished: Sequence, seed: int, need: Dict[str, int]) -> List:
    """Finished tracked requests to compare, drawn from ``seed``."""
    if not finished:
        return []
    rng = np.random.default_rng([int(seed) & (2**63 - 1), 0xC4EC])
    order = [finished[i] for i in rng.permutation(len(finished))]
    picked = [max(order, key=lambda t: len(t.req.out_tokens))]
    for tier, n in sorted(need.items()):
        for t in order:
            if sum(len(p.req.out_tokens) for p in picked
                   if p.spec.tier == tier) >= n:
                break
            if t.spec.tier == tier and all(t is not p for p in picked):
                picked.append(t)
    return picked


def served_by_tier(picked: Sequence) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for t in picked:
        out[t.spec.tier] = out.get(t.spec.tier, 0) + len(t.req.out_tokens)
    return out


def reference_for(cfg: Dict, layout, seed: int):
    mod = importlib.import_module(f"bench.references.{cfg['reference']}")
    return mod.Reference(cfg, layout, seed)


def judge(ref, picked: Sequence, tiers: Dict[str, Sequence],
          *, control: bool = False) -> Dict[str, float]:
    """Readings over the sample: ``logit_gap`` of the served tokens, or
    with ``control`` of the int4 control's picks (the served tokens'
    gap is then ``program_gap``)."""
    gap, cgap, n = -np.inf, -np.inf, 0
    for t in picked:
        masks = tiers.get(t.spec.tier) or {}
        if set(masks) - {"*"}:
            raise ValueError(f"tier {t.spec.tier!r}: only '*' masks (every "
                             f"served matrix) are compared")
        g, cg = ref.gaps(t.spec.prompt, t.req.out_tokens,
                         masks.get("*", ()), control=control)
        gap, cgap = max(gap, g), max(cgap, cg)
        n += len(t.req.out_tokens)
    out = {"logit_gap": float(gap), "served_tokens": n,
           "requests": len(picked)}
    if control:
        out["logit_gap"], out["program_gap"] = float(cgap), float(gap)
    return out


def verdict(logit_gap: float, served: Dict[str, int], limit: float,
            need: Dict[str, int]
            ) -> Tuple[bool, List[Tuple[str, float, float, str]]]:
    """(correct, [(name, value, limit, 'max'|'min')]): ``logit_gap`` at
    most its limit, and each tier's served tokens in the sample at least
    its floor."""
    rows = [("logit_gap", logit_gap, limit, "max")]
    rows += [(f"served_tokens.{t}", served.get(t, 0), n, "min")
             for t, n in sorted(need.items())]
    ok = all((v <= lim) if kind == "max" else (v >= lim)
             for _, v, lim, kind in rows)
    return bool(ok and np.isfinite(logit_gap)), rows

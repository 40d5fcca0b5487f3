"""The one traffic generator: reads a mix file (``traffic/<mix>.json``).

The schedule (arrival times, tiers, prompt and output lengths) is drawn
from the mix's own ``schedule_seed``, so every run seed gets the same
work in the same order; the run seed draws the token ids.  A cell's
window holds a few tens of requests, each of which outlives much of it,
so the order of the work moves the metrics far more than the system's
own noise does: with the order drawn per seed, two runs of one seed
agreed within ~3% while six seeds spread by 20-40% (the qwen cell on
one TPU v5e).  Within the schedule the gaps and lengths are quantiles of
their distributions, shuffled, so the schedule is the distribution's
shape and not one lucky draw.

The schedule starts ``lead_in_s`` before the measured window (about one
request lifetime), so the window opens on a server already holding the
stated load, not on an empty one filling up.

Mix keys:

* ``arrival``: ``{"process": "poisson", "rate_per_s": r}`` (open loop;
  gaps are the exponential distribution's quantiles at ``(i + .5)/n``);
* ``tiers``: tier name -> share of requests (largest-remainder counts);
* ``prompt_tokens`` / ``output_tokens``: ``{"dist": "lognormal",
  "median": m, "sigma": s, "min": a, "max": b}``, quantiles likewise;
* ``max_context``: prompt + output never exceeds it (outputs are cut);
* ``lead_in_s``: seconds of the same traffic served before the window;
* ``schedule_seed``: the seed of the schedule's order;
* ``sampling``: ``"greedy"`` (the only kind the comparison can judge).
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist
from typing import Dict, List, Optional

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


@dataclass
class Request:
    due_s: float               # offset from the window's start (< 0: lead-in)
    tier: str
    prompt: np.ndarray         # int32 token ids
    max_new_tokens: int


def load_mix(name: str) -> Dict:
    return json.loads((TRAFFIC_DIR / f"{name}.json").read_text())


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _lengths(spec: Dict, n: int) -> np.ndarray:
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    vals = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)


def _tier_list(shares: Dict[str, float], n: int) -> List[str]:
    names = sorted(shares)
    total = sum(shares.values())
    exact = [shares[t] / total * n for t in names]
    counts = [math.floor(x) for x in exact]
    by_rem = sorted(range(len(names)), key=lambda i: (counts[i] - exact[i], i))
    for i in by_rem[: n - sum(counts)]:
        counts[i] += 1
    return [t for t, c in zip(names, counts) for _ in range(c)]


def lead_in_s(mix: Dict) -> float:
    return float(mix.get("lead_in_s", 0.0))


def n_requests(mix: Dict, seconds: float,
               rate: Optional[float] = None) -> int:
    """Requests of the lead-in and a window of ``seconds``."""
    r = float(mix["arrival"]["rate_per_s"] if rate is None else rate)
    return max(1, int(round(r * (lead_in_s(mix) + seconds))))


def generate(mix: Dict, seed: int, seconds: float, vocab: int,
             rate: Optional[float] = None) -> List[Request]:
    """Requests due in ``[-lead_in_s, seconds)``, sorted by due time."""
    if mix["arrival"]["process"] != "poisson":
        raise ValueError(f"unknown arrival process {mix['arrival']!r}")
    if mix.get("sampling", "greedy") != "greedy":
        raise ValueError("only greedy traffic can be judged against the "
                         "reference")
    r = float(mix["arrival"]["rate_per_s"] if rate is None else rate)
    n = n_requests(mix, seconds, r)
    lead = lead_in_s(mix)
    order = np.random.default_rng([int(mix["schedule_seed"]), 0x5C4E])
    gaps = -np.log1p(-_quantiles(n)) / r
    gaps *= (lead + seconds) / gaps.sum()   # n arrivals fill the span
    due = np.concatenate([[0.0], np.cumsum(order.permutation(gaps))[:-1]])
    due -= lead
    prompts = order.permutation(_lengths(mix["prompt_tokens"], n))
    outs = order.permutation(_lengths(mix["output_tokens"], n))
    tiers = order.permutation(np.array(_tier_list(mix["tiers"], n)))
    cap = int(mix["max_context"])
    ids = np.random.default_rng([int(seed) & (2**63 - 1), 0x7EA1])
    reqs = []
    for i in range(n):
        p = int(min(prompts[i], cap - 1))
        o = int(max(1, min(outs[i], cap - p)))
        toks = ids.integers(0, vocab, size=p, dtype=np.int32)
        reqs.append(Request(float(due[i]), str(tiers[i]), toks, o))
    return reqs

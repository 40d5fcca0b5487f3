"""The traffic generator: deterministic per seed, the same work for every
seed, and inside its length and tier bounds."""
import json
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from bench import traffic

MIXES = sorted(p.stem for p in (Path(traffic.__file__).parent / "traffic")
               .glob("*.json"))


@pytest.mark.parametrize("mix_name", MIXES)
def test_deterministic_and_bounded(mix_name):
    mix = traffic.load_mix(mix_name)
    seed = 2**31 + 12345                      # larger than 32 signed bits
    a = traffic.generate(mix, seed, 40, 1000)
    b = traffic.generate(mix, seed, 40, 1000)
    assert len(a) == len(b) == traffic.n_requests(mix, 40)
    for x, y in zip(a, b):
        assert (x.due_s, x.tier, x.max_new_tokens) == \
            (y.due_s, y.tier, y.max_new_tokens)
        assert np.array_equal(x.prompt, y.prompt)
    p, o = mix["prompt_tokens"], mix["output_tokens"]
    lead = traffic.lead_in_s(mix)
    assert lead > 0
    assert all(-lead <= r.due_s < 40 for r in a)
    assert a[0].due_s == -lead
    assert [r.due_s for r in a] == sorted(r.due_s for r in a)
    for r in a:
        assert p["min"] <= len(r.prompt) <= p["max"]
        assert 1 <= r.max_new_tokens <= o["max"]
        assert len(r.prompt) + r.max_new_tokens <= mix["max_context"]
        assert r.prompt.min() >= 0 and r.prompt.max() < 1000
        assert r.tier in mix["tiers"]


@pytest.mark.parametrize("mix_name", MIXES)
def test_every_seed_gets_the_same_schedule(mix_name):
    mix = traffic.load_mix(mix_name)
    a = traffic.generate(mix, 1, 40, 1000)
    b = traffic.generate(mix, 2, 40, 1000)
    key = lambda rs: [(r.due_s, r.tier, len(r.prompt),  # noqa: E731
                       r.max_new_tokens) for r in rs]
    assert key(a) == key(b)
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    # another schedule seed orders the same multiset of work differently
    c = traffic.generate(dict(mix, schedule_seed=mix["schedule_seed"] + 1),
                         1, 40, 1000)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in c)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in c]
    # the tier shares hold to one request
    n = len(a)
    tiers = Counter(r.tier for r in a)
    for tier, share in mix["tiers"].items():
        assert abs(tiers[tier] - share * n) <= 1


def test_rate_override_scales_the_count():
    mix = traffic.load_mix(MIXES[0])
    n = round(3.0 * (traffic.lead_in_s(mix) + 10))
    assert len(traffic.generate(mix, 0, 10, 100, rate=3.0)) == n
    no_lead = dict(mix, lead_in_s=0)
    assert len(traffic.generate(no_lead, 0, 10, 100, rate=3.0)) == 30
    assert json.dumps(mix)                   # plain data

"""The comparison's sample and verdict: every tier of the mix brings its
floor of served tokens, or the run is not correct."""
import numpy as np

from bench import check
from bench.client import Tracked
from bench.traffic import Request


class _Req:
    def __init__(self, n):
        self.out_tokens = [0] * n
        self.max_new_tokens = n
        self.error = None


def _tr(tier, n):
    tr = Tracked(Request(0.0, tier, np.zeros(4, np.int32), n), 0.0, _Req(n))
    tr.tok_t = [0.0] * n
    return tr


def test_floor_is_what_the_tier_asks_for_where_that_is_less():
    tracked = [_tr("full", 40), _tr("pro", 300), _tr("free", 100),
               _tr("free", 200)]
    assert check.floors(tracked, ["free", "full", "pro"], 256) == \
        {"free": 256, "full": 40, "pro": 256}


def test_sample_holds_each_tier_floor_and_the_longest():
    tracked = [_tr("free", n) for n in (50, 60, 70, 80, 500)] + \
        [_tr("pro", n) for n in (100, 120, 140)] + [_tr("full", 30)]
    need = check.floors(tracked, ["free", "full", "pro"], 200)
    picked = check.sample(tracked, 2**33 + 5, need)
    assert picked[0].spec.max_new_tokens == 500
    served = check.served_by_tier(picked)
    assert all(served[t] >= n for t, n in need.items())
    assert check.sample(tracked, 2**33 + 5, need) == picked   # seeded
    ok, rows = check.verdict(0.1, served, 0.5, need)
    assert ok and [r[0] for r in rows] == [
        "logit_gap", "served_tokens.free", "served_tokens.full",
        "served_tokens.pro"]


def test_a_tier_short_of_its_floor_or_a_wide_gap_fails():
    need = {"free": 200, "full": 30}
    assert not check.verdict(0.1, {"free": 250, "full": 29}, 0.5, need)[0]
    assert not check.verdict(0.1, {"free": 250}, 0.5, need)[0]
    assert not check.verdict(0.6, {"free": 250, "full": 30}, 0.5, need)[0]
    assert not check.verdict(float("-inf"), {"free": 250, "full": 30}, 0.5,
                             need)[0]
    assert check.verdict(0.5, {"free": 250, "full": 30}, 0.5, need)[0]

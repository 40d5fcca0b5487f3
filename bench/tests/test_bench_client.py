"""Exact percentiles and the client's reductions from token timestamps."""
import math

import numpy as np
import pytest

from bench.client import Client, Tracked, nearest_rank
from bench.traffic import Request


def test_nearest_rank_is_an_observed_value():
    vals = list(range(1, 101))                 # 1..100
    assert nearest_rank(vals, 0.95) == 95
    assert nearest_rank(vals, 0.5) == 50
    assert nearest_rank([3.0, 1.0, 2.0], 0.95) == 3.0
    assert nearest_rank([7.0], 0.95) == 7.0
    assert math.isnan(nearest_rank([], 0.95))
    # 20 samples: p95 is the 19th smallest, not an interpolation
    assert nearest_rank([float(i) for i in range(20)], 0.95) == 18.0


class _Req:
    def __init__(self, n):
        self.out_tokens = [0] * n
        self.max_new_tokens = n
        self.error = None


def _tracked(due, toks, n=None):
    spec = Request(due, "full", np.zeros(4, np.int32), len(toks))
    tr = Tracked(spec, due, _Req(len(toks) if n is None else n))
    tr.tok_t = list(toks)
    return tr


def test_ttft_itl_and_tokens_from_timestamps():
    c = Client(gw=None, requests=[])
    c.t0, c.end, c.closed = 0.0, 10.0, 12.0
    c.tracked = [_tracked(1.0, [1.5, 1.75, 2.25]),
                 _tracked(2.0, [4.0, 9.0, 11.0]),     # last token after end
                 _tracked(9.0, [])]                    # never served
    assert c.ttft_ms() == pytest.approx([500.0, 2000.0, 3000.0])
    # gaps with both tokens inside the window: 250, 500, 5000
    assert sorted(c.itl_ms()) == pytest.approx([250.0, 500.0, 5000.0])
    assert c.tokens_in_window() == 5
    assert c.failed() == 1
    assert nearest_rank(c.ttft_ms(), 0.95) == pytest.approx(3000.0)


def test_lead_in_counts_only_inside_the_window():
    c = Client(gw=None, requests=[])
    c.t0, c.end, c.closed = 10.0, 20.0, 21.0
    c.tracked = [_tracked(2.0, [3.0, 9.0, 10.5, 11.0]),    # lead-in request
                 _tracked(12.0, [12.5, 13.0])]
    # TTFT: only the request due in the window
    assert c.ttft_ms() == pytest.approx([500.0])
    # gaps with both ends in the window, the lead-in request's included
    assert sorted(c.itl_ms()) == pytest.approx([500.0, 500.0])
    assert c.tokens_in_window() == 4
    assert c.finished_tokens() == {"full": 6}

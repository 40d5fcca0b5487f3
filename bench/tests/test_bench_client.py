"""Exact percentiles, the client's reductions from token timestamps, and
the per-step records of a traced window."""
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


def test_itl_mean_reader_takes_the_gaps_itl_p95_ranks():
    from types import SimpleNamespace

    from bench.harness import reader

    c = Client(gw=None, requests=[])
    c.t0, c.end, c.closed = 0.0, 10.0, 12.0
    c.tracked = [_tracked(1.0, [1.5, 1.75, 2.25]),
                 _tracked(2.0, [4.0, 9.0, 11.0])]     # last token after end
    run = SimpleNamespace(client=c)
    assert reader("sched.itl_mean_ms")(run) == pytest.approx(
        (250.0 + 500.0 + 5000.0) / 3)
    assert reader("itl_p95_ms")(run) == pytest.approx(5000.0)
    c.tracked = [_tracked(1.0, [1.5])]
    assert reader("sched.itl_mean_ms")(run) is None


class _Lane:
    def __init__(self, n):
        self.out_tokens, self.max_new_tokens = [], n
        self.error, self.pos, self.cursor = None, 5, 0


class _Gateway:
    """Emits one token to every lane on each step and returns ``act``."""

    sync_active = False

    def __init__(self, make_act):
        self.make_act = make_act
        self.lanes = []

    def submit(self, prompt, license, max_new_tokens):
        lane = _Lane(max_new_tokens)
        self.lanes.append(lane)
        return lane

    def step(self):
        live = [r for r in self.lanes
                if len(r.out_tokens) < r.max_new_tokens]
        for r in live:
            r.out_tokens.append(1)
        return self.make_act(live) if live else None


def _recorded(make_act):
    reqs = [Request(0.0, "full", np.zeros(4, np.int32), 3)]
    c = Client(_Gateway(make_act), reqs)
    c.record_steps = True
    c.run(c.clock(), 0.05, drain_s=1.0)
    assert c.steps and all(s.kind == "decode" for s in c.steps)
    return c.steps


def test_step_records_carry_the_actions_counters():
    from types import SimpleNamespace

    counters = {"rows_routed": 96.0, "experts_touched": 12}
    steps = _recorded(lambda live: SimpleNamespace(
        kind="decode", requests=live, counters=counters))
    assert [s.counters for s in steps] == [counters] * len(steps)
    assert steps[0].counters is not counters       # a copy, per step


def test_step_records_of_todays_program_hold_no_counters():
    from repro.serving.scheduler import ScheduledAction

    steps = _recorded(lambda live: ScheduledAction("decode", "full", None,
                                                   live))
    assert [s.counters for s in steps] == [{}] * len(steps)

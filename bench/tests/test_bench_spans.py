"""The readers of the gateway's program spans on a small synthetic trace."""
from types import SimpleNamespace

import pytest

from bench import spans as S
from bench import trace as T
from bench.harness import reader

E = T.Event

NEW = ("gw.host_ms", "pool.host_ms", "device.idle_fetch_share",
       "device.idle_gateway_share")


def _trace(program_spans=True):
    # window [0, 20]; device busy [2.1,4] u [7.65,8] u [9,9.4] u [13,19],
    # so idle [0,2.1] u [4,7.65] u [8,9] u [9.4,13] u [19,20] (11.35 s).
    # Step 1 decodes, step 2 prefills (its idle [8,9] runs from inside
    # pool:absorb into step:fetch), step 3 has no action, one submit,
    # and a step that the window's end cuts
    ops = [E("%fusion.1 = bf16[8] fusion(...)", 2.1, 4.0),
           E("%fusion.2 = bf16[8] fusion(...)", 7.65, 8.0),
           E("%copy.3 = bf16[8] copy(...)", 9.0, 9.4),
           E("%fusion.4 = bf16[8] fusion(...)", 13.0, 19.0)]
    host = [E("bench.window", 0, 20), E("bench.step", 0.9, 6.1),
            E("PjitFunction(_step)", 2.05, 2.15)]
    if program_spans:
        host += [
            E("gw:step", 1, 6), E("sched:next", 1, 1.1),
            E("sched:decode", 1.1, 5.9), E("pool:grow", 1.2, 1.5),
            E("view:get", 1.5, 1.6), E("gw:inputs", 1.6, 1.8),
            E("pool:gather", 1.8, 2.0), E("step:dispatch", 2.0, 2.2),
            E("pool:absorb", 2.2, 2.6), E("step:fetch", 2.6, 5.0),
            E("gw:emit", 5.0, 5.8),
            E("gw:step", 7, 10), E("sched:next", 7, 7.2),
            E("sched:prefill", 7.2, 9.9), E("pool:grow", 7.3, 7.5),
            E("pool:gather", 7.5, 7.6), E("step:dispatch", 7.6, 7.7),
            E("pool:absorb", 7.7, 8.2), E("step:fetch", 8.2, 9.5),
            E("gw:emit", 9.5, 9.9),
            E("gw:step", 11, 11.5), E("sched:next", 11, 11.4),
            E("gw:submit", 12, 12.5),
            E("gw:step", 19.5, 21), E("sched:decode", 19.6, 20.9),
            E("step:fetch", 20.2, 20.8)]
    return T.Trace(ops=ops, host=host, window=(0, 20))


def _run(tr):
    return SimpleNamespace(trace=tr)


def test_interval_helpers():
    assert S.intersect([(0, 2), (3, 5)], [(1, 4)]) == [(1, 2), (3, 4)]
    assert S.intersect([(0, 1)], [(1, 2)]) == []
    assert S.length([(0, 2), (3, 3.5)]) == pytest.approx(2.5)


def test_steps_with_action_nest_their_spans():
    steps = S.steps_with_action(_trace())
    # the no-action step and the step the window cuts are left out
    assert [(s.start, s.end) for s, _ in steps] == [(1, 6), (7, 10)]
    # with the host events under them (the dispatch's PjitFunction)
    assert {e.name for e in steps[0][1]} - {"PjitFunction(_step)"} == {
        "sched:next", "sched:decode", "pool:grow", "view:get", "gw:inputs",
        "pool:gather", "step:dispatch", "pool:absorb", "step:fetch",
        "gw:emit"}
    assert "sched:prefill" in {e.name for e in steps[1][1]}


def test_host_ms_readers():
    run = _run(_trace())
    # step 1: 5 s less 2.4 s of fetch; step 2: 3 s less 1.3 s
    assert reader("gw.host_ms")(run) == pytest.approx((2.6 + 1.7) / 2 * 1e3)
    # pool spans: 0.3 + 0.2 + 0.4 and 0.2 + 0.1 + 0.5
    assert reader("pool.host_ms")(run) == pytest.approx((0.9 + 0.8) / 2 * 1e3)


def test_idle_split_by_program_span():
    tr = _trace()
    run = _run(tr)
    fetch = reader("device.idle_fetch_share")(run)
    gateway = reader("device.idle_gateway_share")(run)
    # idle in step:fetch: [4,5] + [8.2,9] + [9.4,9.5]
    assert fetch == pytest.approx(100 * 1.9 / 20)
    # idle in gw:step/gw:submit: 1.1 + 2.0 + 0.65 + 1.0 + 0.6 + 0.5 +
    # 0.5 + 0.5 (the cut step, to the window's end), less the fetch's;
    # the absorb's [8, 8.2] is the gateway's, the fetch's [8.2, 9] not
    assert gateway == pytest.approx(100 * (6.85 - 1.9) / 20)
    assert fetch + gateway <= 100 * T.idle_share(tr)


@pytest.mark.parametrize("name", NEW)
def test_readers_without_trace_or_spans_give_none(name):
    assert reader(name)(_run(None)) is None
    # a program that names no phase (the trace of an older gateway)
    assert reader(name)(_run(_trace(program_spans=False))) is None


def _client(*reqs, t0=100.0, end=140.0):
    return SimpleNamespace(client=SimpleNamespace(
        t0=t0, end=end,
        tracked=[SimpleNamespace(req=r) for r in reqs]))


def test_queue_wait_over_requests_admitted_in_window():
    R = SimpleNamespace
    run = _client(R(submit_t=90.0, admit_t=99.0),     # before the window
                  R(submit_t=101.0, admit_t=101.5),
                  R(submit_t=110.0, admit_t=110.1),
                  R(submit_t=120.0, admit_t=None),     # still queued
                  R(submit_t=139.0, admit_t=141.0))    # after it
    assert reader("sched.queue_wait_ms")(run) == pytest.approx(300.0)
    # a gateway that stamps no admission gives no value
    assert reader("sched.queue_wait_ms")(_client(R(submit_t=101.0))) is None

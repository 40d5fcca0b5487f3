"""The trace reduction on a small synthetic trace."""
import pytest

from bench import trace as T

E = T.Event


def _trace():
    # window [0, 10]; device busy [1,3] u [2,4] u [6,7] (+ one op poking
    # out of the window at 9.5..12, and a while loop around the first
    # two); op events are named by their HLO text as the TPU trace names
    # them; host spans say what the host did
    ops = [E("%while.5 = (s32[], bf16[8,1,2048]) while(...)", 1, 4),
           E("%fusion.1 = bf16[8,2048] fusion(...), kind=kLoop", 1, 3),
           E("%paged_attention.7 = bf16[8,16,128] custom-call(...)", 2, 4),
           E("%paged_decode_write.9 = bf16[36,201,256] custom-call(...)",
             6, 7),
           E("%fusion.2 = f32[8] fusion(...)", 9.5, 12)]
    host = [E("bench.window", 0, 10), E("bench.step", 0, 4.5),
            E("PjitFunction(_step)", 0.2, 0.9), E("bench.wait", 5, 5.9),
            E("bench.step", 5.9, 10), E("PjitFunction(take)", 7.2, 9.4)]
    mods = [E("jit__step(12)", 1, 4), E("jit__step(12)", 6, 7),
            E("jit__one(3)", 9.5, 12)]
    return T.Trace(ops=ops, modules=mods, host=host, window=(0, 10))


def test_busy_union_and_idle_share():
    tr = _trace()
    assert T.union(T.clip(tr.ops, tr.window)) == [(1, 4), (6, 7), (9.5, 10)]
    assert T.busy_seconds(tr) == pytest.approx(4.5)
    assert T.idle_share(tr) == pytest.approx(0.55)
    assert T.gaps(tr) == [(0, 1), (4, 6), (7, 9.5)]


def test_kernel_time_by_name():
    tr = _trace()
    assert T.kernel_seconds(tr, "paged_attention") == (2, 1)
    assert T.kernel_seconds(tr, "paged_decode_write") == (1, 1)
    assert T.kernel_seconds(tr, "delta_apply") == (0, 0)
    cats = dict(T.top_ops(tr))
    assert cats["paged_attention"] == pytest.approx(2)
    assert cats["fusion"] == pytest.approx(2.5)      # 2 + 0.5 in window
    assert "while" not in cats                       # its body is counted
    assert T.module_stats(tr, "jit__step") == (4, 2)
    assert T.module_stats(tr, "jit__one") == (0.5, 1)  # clipped


def test_gaps_labelled_by_host_activity():
    tr = _trace()
    assert T.gap_label((0, 1), tr.host) == "bench.step/PjitFunction(_step)"
    assert T.gap_label((4, 6), tr.host) == "bench.wait"
    assert T.gap_label((7, 9.5), tr.host) == "bench.step/PjitFunction(take)"
    idle = dict(T.idle_by_host(tr))
    assert idle["bench.step/PjitFunction(take)"] == pytest.approx(2.5)
    assert sum(idle.values()) == pytest.approx(5.5)

"""The gateway's program spans in a profiler trace, and the interval
arithmetic over them.

``repro.serving.tracing`` names each phase of ``LicensedGateway.step()``
and ``submit()`` with a profiler annotation (its span table: ``gw:step``,
``step:fetch``, ``pool:*``, ...).  The gateway runs on the client's
thread, so ``trace.load`` keeps these spans in ``Trace.host`` beside the
benchmark's own.  A program without them gives no spans, and every
reader here then returns ``None``.
"""
from __future__ import annotations

from bisect import bisect_right
from typing import Iterable, List, Optional, Sequence, Tuple

from bench.trace import Event, Trace, clip, gaps, union

STEP = "gw:step"
SUBMIT = "gw:submit"
FETCH = "step:fetch"
ACTIONS = ("sched:prefill", "sched:decode")
POOL = "pool:"

Intervals = List[Tuple[float, float]]


def named(tr: Trace, *names: str) -> List[Event]:
    """Host spans called one of ``names``."""
    return [e for e in tr.host if e.name in names]


def length(iv: Iterable[Tuple[float, float]]) -> float:
    return sum(t - s for s, t in iv)


def intersect(a: Sequence[Tuple[float, float]],
              b: Sequence[Tuple[float, float]]) -> Intervals:
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, t = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if t > s:
            out.append((s, t))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def steps_with_action(tr: Trace) -> List[Tuple[Event, List[Event]]]:
    """Each ``gw:step`` span wholly inside the traced window that holds a
    scheduled action, with the program spans nested in it."""
    a, b = tr.window
    steps = sorted((e for e in named(tr, STEP) if a <= e.start and e.end <= b),
                   key=lambda e: e.start)
    starts = [e.start for e in steps]
    inner: List[List[Event]] = [[] for _ in steps]
    for e in tr.host:
        if e.name == STEP:
            continue
        i = bisect_right(starts, e.start) - 1
        if i >= 0 and e.end <= steps[i].end:
            inner[i].append(e)
    return [(s, ev) for s, ev in zip(steps, inner)
            if any(e.name in ACTIONS for e in ev)]


def mean_ms(values: List[float]) -> Optional[float]:
    return 1e3 * sum(values) / len(values) if values else None


def idle_share(tr: Optional[Trace], inside: Sequence[str],
               outside: Sequence[str] = ()) -> Optional[float]:
    """Device-idle time inside the spans called ``inside`` but outside
    those called ``outside``, as a percentage of the traced window;
    ``None`` without a trace, device ops or an ``inside`` span."""
    if tr is None or not tr.ops or tr.window_s <= 0:
        return None
    spans = named(tr, *inside)
    if not spans:
        return None
    idle = intersect(gaps(tr), union(clip(spans, tr.window)))
    out = intersect(idle, union(clip(named(tr, *outside), tr.window)))
    return 100.0 * (length(idle) - length(out)) / tr.window_s

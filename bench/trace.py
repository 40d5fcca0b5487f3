"""Reduction of a profiler trace to device busy time, op time and idle gaps.

``load`` reads the ``.xplane.pb`` the JAX profiler writes into plain
interval lists; every other function works on those lists, so the
reduction is tested on small synthetic traces.  Times are seconds on the
trace's own clock, on which the profiler places host and device events
together.

* device ops: events of the ``XLA Ops`` line of the chip's plane.  On
  the TPU an op's event name is its HLO text, ``%<op>.<n> = <shape>
  <opcode>(...)``; a Pallas kernel's op is named after the kernel
  (``%paged_attention.3 = ...``), which is what kernels are found by;
* modules: events of the ``XLA Modules`` line (one per program run);
* host spans: the benchmark's own ``TraceAnnotation`` spans (names
  starting ``bench.``) and the host events beneath them.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Sequence, Tuple


@dataclass
class Event:
    name: str
    start: float
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class Trace:
    ops: List[Event] = field(default_factory=list)
    modules: List[Event] = field(default_factory=list)
    host: List[Event] = field(default_factory=list)
    window: Tuple[float, float] = (0.0, 0.0)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str, device: str = "/device:TPU:0",
         window_span: str = "bench.window") -> Trace:
    """Device ops and modules of ``device``, host events of every host
    thread that carries a ``bench.`` span, and the traced window (the
    ``window_span`` annotation, else the extent of the device ops)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        if plane.name == device:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    tr.ops.extend(Event(e.name, e.start_ns * 1e-9,
                                        e.end_ns * 1e-9)
                                  for e in line.events)
                elif line.name == "XLA Modules":
                    tr.modules.extend(Event(e.name, e.start_ns * 1e-9,
                                            e.end_ns * 1e-9)
                                      for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [Event(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                       for e in line.events]
                if any(e.name.startswith("bench.") for e in evs):
                    tr.host.extend(evs)
    win = [e for e in tr.host if e.name == window_span]
    if win:
        tr.window = (win[0].start, win[0].end)
    elif tr.ops:
        tr.window = (min(e.start for e in tr.ops), max(e.end for e in tr.ops))
    return tr


# ----------------------------------------------------------------- busy/idle
def clip(events: Iterable[Event], window: Tuple[float, float]) -> List[Event]:
    a, b = window
    out = []
    for e in events:
        s, t = max(e.start, a), min(e.end, b)
        if t > s:
            out.append(Event(e.name, s, t))
    return out


def union(events: Iterable[Event]) -> List[Tuple[float, float]]:
    """Merged busy intervals."""
    spans = sorted((e.start, e.end) for e in events if e.end > e.start)
    out: List[List[float]] = []
    for s, t in spans:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return [(s, t) for s, t in out]


def busy_seconds(tr: Trace) -> float:
    return sum(t - s for s, t in union(clip(tr.ops, tr.window)))


def idle_share(tr: Trace) -> float:
    return 1.0 - busy_seconds(tr) / tr.window_s


def gaps(tr: Trace) -> List[Tuple[float, float]]:
    """Idle intervals of the device inside the window."""
    a, b = tr.window
    out, cur = [], a
    for s, t in union(clip(tr.ops, tr.window)):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, t)
    if b > cur:
        out.append((cur, b))
    return out


def _overlap(a: Tuple[float, float], e: Event) -> float:
    return max(0.0, min(a[1], e.end) - max(a[0], e.start))


def gap_label(gap: Tuple[float, float], host: Sequence[Event]) -> str:
    """What the host was doing in ``gap``: the ``bench.`` span covering
    most of it, then the innermost other host event covering most of it
    (e.g. the dispatch of an op), as ``outer/inner``."""
    best_outer, ov_outer = "no bench span", 0.0
    best_inner, ov_inner, dur_inner = None, 0.0, float("inf")
    for e in host:
        ov = _overlap(gap, e)
        if ov <= 0:
            continue
        if e.name.startswith("bench."):
            if e.name != "bench.window" and ov > ov_outer:
                best_outer, ov_outer = e.name, ov
        elif ov > ov_inner or (ov == ov_inner and e.dur < dur_inner):
            best_inner, ov_inner, dur_inner = e.name, ov, e.dur
    return best_outer if best_inner is None else f"{best_outer}/{best_inner}"


def idle_by_host(tr: Trace, top: int = 10) -> List[Tuple[str, float]]:
    """Idle seconds summed by what the host was doing, largest first.
    One sweep over gaps and host events, both in time order: an event
    joins when it starts before a gap ends and leaves once it ends
    before a gap starts (gaps are disjoint, so it overlaps no later one)."""
    evs = sorted((e for e in tr.host if e.name != "bench.window"),
                 key=lambda e: e.start)
    out: Dict[str, float] = {}
    active: List[Event] = []
    i = 0
    for g in gaps(tr):
        while i < len(evs) and evs[i].start < g[1]:
            active.append(evs[i])
            i += 1
        active = [e for e in active if e.end > g[0]]
        lab = gap_label(g, active)
        out[lab] = out.get(lab, 0.0) + (g[1] - g[0])
    return sorted(out.items(), key=lambda kv: -kv[1])[:top]


# ------------------------------------------------------------ by name
# ops whose interval holds the ops of their body: their time is their
# children's, so the top-ops list leaves them out
CONTAINERS = ("while", "conditional", "call")


def op_category(e: Event) -> str:
    """An op's name without the ``%``, the HLO text after it or its
    numeric suffix: ``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion``,
    ``%paged_attention.3 = ...`` -> ``paged_attention``."""
    short = e.name.split(" = ", 1)[0].strip().lstrip("%")
    return short.rstrip("0123456789").rstrip(".")


def time_by(events: Iterable[Event], key) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for e in events:
        k = key(e)
        out[k] = out.get(k, 0.0) + e.dur
    return out


def top_ops(tr: Trace, top: int = 10) -> List[Tuple[str, float]]:
    """Device seconds by op category, control-flow containers left out."""
    t = time_by(clip(tr.ops, tr.window), op_category)
    t = {k: v for k, v in t.items() if k not in CONTAINERS}
    return sorted(t.items(), key=lambda kv: -kv[1])[:top]


def kernel_seconds(tr: Trace, kernel: str) -> Tuple[float, int]:
    """(seconds, calls) of the ops of Pallas kernel ``kernel``."""
    evs = [e for e in clip(tr.ops, tr.window) if op_category(e) == kernel]
    return sum(e.dur for e in evs), len(evs)


def module_stats(tr: Trace, pattern: str) -> Tuple[float, int]:
    """(seconds, runs) of programs whose module name contains
    ``pattern``."""
    evs = [e for e in clip(tr.modules, tr.window) if pattern in e.name]
    return sum(e.dur for e in evs), len(evs)

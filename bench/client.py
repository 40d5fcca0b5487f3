"""Open-loop client around ``LicensedGateway`` and its end-to-end reductions.

One thread: submit every request whose due time has passed, run one
``gateway.step()``, and stamp each token that became visible with the
host clock after the step returned (the step ends in a device-to-host
copy of the sampled ids, so a token is delivered when the step returns).
A request is timed from when it was DUE, so a stall delays every request
behind it; ``lateness`` reports how late the client submitted.  Requests
due before the window (the traffic's lead-in) are served like any other,
so the window opens on a loaded server; the window's reductions count
what happened inside it.
"""
from __future__ import annotations

import contextlib
import math
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence

from bench.traffic import Request


@dataclass
class Tracked:
    spec: Request
    due: float                       # absolute host time
    req: object = None               # the gateway's request
    submit_t: float = math.nan
    tok_t: List[float] = field(default_factory=list)

    @property
    def first_t(self) -> float:
        return self.tok_t[0] if self.tok_t else math.inf

    @property
    def rejected(self) -> bool:
        return self.req is not None and getattr(self.req, "error", None) \
            is not None

    @property
    def finished(self) -> bool:
        return (self.req is not None and not self.rejected
                and len(self.req.out_tokens) >= self.req.max_new_tokens
                and len(self.tok_t) >= self.req.max_new_tokens)


def nearest_rank(values: Sequence[float], q: float) -> float:
    """Exact percentile: the smallest value with at least ``q`` of the
    sample at or below it (an observed value, no interpolation)."""
    if not values:
        return math.nan
    s = sorted(values)
    k = max(1, math.ceil(q * len(s)))
    return s[k - 1]


@dataclass
class StepRecord:
    kind: str                        # "prefill" | "decode"
    n_req: int                       # requests in the micro-batch
    t0: float
    t1: float
    # decode: per lane, the context attended (position + 1)
    ctx: List[int] = field(default_factory=list)
    # prefill: per lane, (first position, tokens) of the chunk
    chunk: List[tuple] = field(default_factory=list)
    new_tokens: int = 0


class Client:
    """Drives ``gw`` with ``requests`` (sorted by due offset)."""

    def __init__(self, gw, requests: Sequence[Request], *,
                 clock: Callable[[], float] = time.perf_counter,
                 annotate: bool = False,
                 events: Optional[Dict[float, Callable[[], None]]] = None):
        self.gw = gw
        self.specs = list(requests)
        self.clock = clock
        self.annotate = annotate
        self.tracked: List[Tracked] = []
        self.live: List[Tracked] = []
        self.steps: List[StepRecord] = []
        self.record_steps = False
        self.events = dict(events or {})
        self.t0 = math.nan
        self.end = math.nan
        self.closed = math.nan

    def _span(self, name: str):
        if not self.annotate:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def _submit_due(self, now: float) -> None:
        while self._next < len(self.specs) and \
                self.t0 + self.specs[self._next].due_s <= now:
            spec = self.specs[self._next]
            tr = Tracked(spec, self.t0 + spec.due_s)
            with self._span("bench.submit"):
                tr.req = self.gw.submit(spec.prompt, license=spec.tier,
                                        max_new_tokens=spec.max_new_tokens)
            tr.submit_t = self.clock()
            self.tracked.append(tr)
            if not tr.rejected:
                self.live.append(tr)
            self._next += 1

    def _fire_events(self, now: float) -> None:
        for at in sorted(self.events):
            if self.t0 + at <= now:
                with self._span("bench.event"):
                    self.events.pop(at)()

    def _one_step(self) -> bool:
        before = None
        if self.record_steps:
            # prefill cursors before the step: a chunk's tokens are the
            # cursor's advance (a new admission starts at 0)
            before = {id(tr.req): tr.req.cursor for tr in self.live}
            t_start = self.clock()
        with self._span("bench.step"):
            act = self.gw.step()
        t = self.clock()
        new = 0
        still = []
        for tr in self.live:
            n = len(tr.req.out_tokens)
            while len(tr.tok_t) < n:   # a restart re-emits; count once
                tr.tok_t.append(t)
                new += 1
            if not tr.finished:
                still.append(tr)
        self.live = still
        if before is not None and act is not None and act.requests:
            rec = StepRecord(act.kind, len(act.requests), t_start, t,
                             new_tokens=new)
            if act.kind == "decode":
                # each member decoded at its pre-step position pos - 1
                rec.ctx = [int(r.pos) for r in act.requests]
            else:
                rec.chunk = [(before.get(id(r), 0),
                              int(r.cursor) - before.get(id(r), 0))
                             for r in act.requests]
            self.steps.append(rec)
        return act is not None

    def run(self, t0: float, seconds: float, *, on_window_open=None,
            on_window_end=None, drain_s: float = 60.0) -> None:
        """Serve from now (requests due before ``t0`` are the lead-in)
        through the window ``[t0, t0 + seconds)``, then keep stepping
        (nothing new submitted) until every request has its first token,
        or ``drain_s`` has passed."""
        self.t0, self.end = t0, t0 + seconds
        self._next = 0
        opened = False
        while True:
            now = self.clock()
            if now >= self.end:
                break
            if not opened and now >= self.t0:
                opened = True
                if on_window_open is not None:
                    on_window_open()
            self._fire_events(now)
            self._submit_due(now)
            if not self._one_step():
                nxt = (self.t0 + self.specs[self._next].due_s
                       if self._next < len(self.specs) else self.end)
                wait = min(nxt, self.end) - self.clock()
                if wait > 0 and not self.gw.sync_active:
                    with self._span("bench.wait"):
                        time.sleep(min(wait, 0.002))
        self._submit_due(self.end)     # due in the window, not yet sent
        if on_window_end is not None:
            on_window_end()
        deadline = self.clock() + drain_s
        while self.clock() < deadline and any(
                not tr.tok_t and not tr.rejected for tr in self.tracked):
            if not self._one_step():
                break
        self.closed = self.clock()

    def finished_tokens(self) -> Dict[str, int]:
        """Served tokens of the finished requests, per tier."""
        out: Dict[str, int] = {}
        for tr in self.tracked:
            if tr.finished:
                out[tr.spec.tier] = out.get(tr.spec.tier, 0) + len(tr.tok_t)
        return out

    def finish(self, need: Dict[str, int], seconds: float) -> None:
        """After the window: keep stepping (nothing new submitted) until
        the finished requests of each tier hold ``need[tier]`` served
        tokens, or ``seconds`` have passed.  The comparison judges
        finished requests only; the window's metrics are taken before
        this runs."""
        deadline = self.clock() + seconds

        def short():
            have = self.finished_tokens()
            return any(have.get(t, 0) < n for t, n in need.items())

        while self.clock() < deadline and short():
            if not self._one_step():
                break

    # ------------------------------------------------------------ reductions
    def failed(self) -> int:
        return sum(1 for tr in self.tracked if tr.rejected or not tr.tok_t)

    def ttft_ms(self) -> List[float]:
        """Due to first token, per request due in the window; a request
        that failed or never produced a token counts as due to the end of
        the run (a lower bound of what it would have read)."""
        return [(min(tr.first_t, self.closed) - tr.due) * 1e3
                for tr in self.tracked if self.t0 <= tr.due < self.end]

    def itl_ms(self) -> List[float]:
        """Gaps between consecutive tokens of one request, both inside the
        window, pooled over every request (lead-in requests included)."""
        out = []
        for tr in self.tracked:
            ts = [t for t in tr.tok_t if self.t0 <= t <= self.end]
            out.extend((b - a) * 1e3 for a, b in zip(ts, ts[1:]))
        return out

    def tokens_in_window(self) -> int:
        return sum(1 for tr in self.tracked for t in tr.tok_t
                   if self.t0 <= t <= self.end)

    def lateness_s(self) -> float:
        return max((tr.submit_t - tr.due for tr in self.tracked),
                   default=0.0)

"""Decode lanes filled per decode step over the gateway's ``max_batch``,
over every decode step of the window (the scheduler's own micro-batches,
as ``step()`` returned them)."""


def read(run):
    c = run.client
    steps = [s for s in c.steps
             if s.kind == "decode" and c.t0 <= s.t0 and s.t1 <= c.end]
    if not steps:
        return None
    lanes = sum(s.n_req for s in steps)
    return lanes / (len(steps) * run.gateway_settings["max_batch"])

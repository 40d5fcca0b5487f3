"""Host time of one gateway step: the mean, over the ``gw:step`` spans of
the traced window that hold a scheduled action, of the span's length
less the time in its ``step:fetch`` (the wait for the device's result)."""
from bench import spans


def read(run):
    if run.trace is None:
        return None
    return spans.mean_ms([
        s.dur - sum(e.dur for e in inner if e.name == spans.FETCH)
        for s, inner in spans.steps_with_action(run.trace)])

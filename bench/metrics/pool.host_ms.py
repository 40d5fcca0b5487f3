"""Host time of the paged pool in one gateway step: the mean, over the
``gw:step`` spans of the traced window that hold a scheduled action, of
the time inside its ``pool:*`` spans (block growth and copy-on-write,
table padding and gather, absorb/scatter)."""
from bench import spans
from bench.trace import union


def read(run):
    if run.trace is None:
        return None
    return spans.mean_ms([
        spans.length(union(e for e in inner if e.name.startswith(spans.POOL)))
        for _, inner in spans.steps_with_action(run.trace)])

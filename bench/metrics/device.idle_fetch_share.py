"""Share of the traced window in which the chip was idle while the host
waited in ``step:fetch``, the one device-to-host copy of a step's
sampled tokens."""
from bench import spans


def read(run):
    return spans.idle_share(run.trace, [spans.FETCH])

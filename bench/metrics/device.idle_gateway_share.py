"""Share of the traced window in which the chip was idle while the host
ran the gateway's own code: inside ``gw:step`` or ``gw:submit`` spans
but outside ``step:fetch``."""
from bench import spans


def read(run):
    return spans.idle_share(run.trace, [spans.STEP, spans.SUBMIT],
                            outside=[spans.FETCH])

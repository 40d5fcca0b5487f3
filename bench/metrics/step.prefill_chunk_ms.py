"""Mean device time of one run of the chunked-prefill program (module
``jit__one`` in the trace: the lane-vmapped suffix prefill)."""
from bench import trace

MODULE = "jit__one"


def read(run):
    if run.trace is None:
        return None
    secs, n = trace.module_stats(run.trace, MODULE)
    return secs / n * 1e3 if n else None

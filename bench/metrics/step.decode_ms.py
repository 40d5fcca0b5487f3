"""Mean device time of one run of the kernel-resident decode program
(module ``jit__step`` in the trace)."""
from bench import trace

MODULE = "jit__step"


def read(run):
    if run.trace is None:
        return None
    secs, n = trace.module_stats(run.trace, MODULE)
    return secs / n * 1e3 if n else None

"""Share of its roofline that the Pallas ``paged_attention`` kernel
reaches over the traced decode steps: the least time the chip needs
for each call (one per layer per step; operations over the live
context, bytes of the K/V blocks that hold it, q and the output) over
the kernel's time in the trace."""
from bench import costs, trace

KERNEL = "paged_attention"


def read(run):
    if run.trace is None:
        return None
    secs, calls = trace.kernel_seconds(run.trace, KERNEL)
    steps = [s for s in run.trace_steps if s.kind == "decode"]
    if not calls or not steps:
        return None
    cfg, g = run.cfg, run.gateway_settings
    least = 0.0
    for s in steps:
        ops, nbytes = costs.paged_attention_cost(
            cfg, s.ctx, block_size=g["block_size"])
        least += cfg["num_hidden_layers"] * costs.roofline_seconds(
            ops, nbytes, run.peaks)
    return 100.0 * least / secs

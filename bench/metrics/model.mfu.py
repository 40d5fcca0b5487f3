"""Model FLOPs of every token the traced steps processed (prefill chunks
and decode lanes; 2 x weights multiplied through, attention over each
token's context, the head where logits are needed), over the traced
window, over the chip's bf16 peak.  The int8 store multiplies in bf16
after the dequant, so bf16 is the peak that bounds it."""
from bench import costs


def read(run):
    if run.trace is None or not run.trace_steps:
        return None
    cfg = run.cfg
    flops = 0
    for s in run.trace_steps:
        if s.kind == "decode":
            flops += sum(costs.token_flops(cfg, c, logits=True) for c in s.ctx)
        else:
            # a chunk whose end is its prompt's end yields the first token
            flops += sum(costs.chunk_flops(cfg, a, n, last=False)
                         for a, n in s.chunk)
            flops += s.new_tokens * 2 * cfg["hidden_size"] * cfg["vocab_size"]
    return 100.0 * flops / run.trace.window_s / run.peaks["bf16_flops_per_s"]

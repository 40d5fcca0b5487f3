"""Operations and bytes from shapes, and the chip's peaks.

Counted from the algorithm's shapes, never from the compiler's cost
model, so a change to the program cannot move the yardstick.  A
multiply-add is 2 operations.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Sequence, Tuple

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"
BF16 = 2


def peaks(device_kind: str) -> Dict[str, float]:
    """Peaks of one chip of ``device_kind``; an unknown chip is an error."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]


# ------------------------------------------------------------ the model
def matmul_params(cfg: Dict) -> int:
    """Weights one token multiplies through in one layer (attention
    projections and MLP), from the configuration's widths."""
    d = cfg["hidden_size"]
    hd = cfg["head_dim"]
    h, kh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    ff = cfg["intermediate_size"]
    n_mlp = 3 if cfg["hidden_act"] == "silu" else 2
    return d * hd * (2 * h + 2 * kh) + n_mlp * d * ff


def attention_flops(cfg: Dict, ctx: int) -> int:
    """Scores and weighted sum of ONE query over ``ctx`` keys, all layers."""
    return (4 * cfg["num_attention_heads"] * cfg["head_dim"] * ctx
            * cfg["num_hidden_layers"])


def token_flops(cfg: Dict, ctx: int, *, logits: bool) -> int:
    """Model FLOPs of one token at context ``ctx`` (itself included):
    2 x the weights it multiplies through in every layer, attention over
    its context, and the output head where its logits are needed."""
    f = 2 * matmul_params(cfg) * cfg["num_hidden_layers"]
    f += attention_flops(cfg, ctx)
    if logits:
        f += 2 * cfg["hidden_size"] * cfg["vocab_size"]
    return f


def chunk_flops(cfg: Dict, start: int, n: int, *, last: bool) -> int:
    """A prefill chunk of ``n`` prompt tokens at positions ``start..``;
    the head runs for the prompt's last token only (``last``)."""
    total = 2 * matmul_params(cfg) * cfg["num_hidden_layers"] * n
    # sum over positions p of attention over p + 1 keys
    keys = n * start + n * (n + 1) // 2
    total += 4 * cfg["num_attention_heads"] * cfg["head_dim"] * keys \
        * cfg["num_hidden_layers"]
    if last:
        total += 2 * cfg["hidden_size"] * cfg["vocab_size"]
    return total


# ------------------------------------------------------------ kernels
def paged_attention_cost(cfg: Dict, ctx: Sequence[int], *,
                         block_size: int) -> Tuple[int, int]:
    """(ops, bytes) of ONE ``paged_attention`` call (one layer) of a
    decode step over live lanes whose contexts are ``ctx``.  Ops count
    each lane's live context; bytes count the whole K and V blocks that
    hold it (the kernel reads a block at a time), plus q in (bf16) and
    the output (f32).  Padding lanes are not counted: they are no work
    the step needs."""
    h, kh, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    ops = sum(4 * h * hd * c for c in ctx)
    blocks = sum(-(-c // block_size) for c in ctx)
    kv = blocks * block_size * kh * hd * BF16 * 2
    qo = len(ctx) * h * hd * (BF16 + 4)
    return ops, kv + qo


def roofline_seconds(ops: float, nbytes: float, pk: Dict[str, float]
                     ) -> float:
    """Least time the chip could take: the larger of the two bounds."""
    return max(ops / pk["bf16_flops_per_s"], nbytes / pk["hbm_bytes_per_s"])

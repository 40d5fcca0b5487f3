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
def is_moe_layer(cfg: Dict, i: int) -> bool:
    """Layer ``i`` is an expert layer: the file has routed experts, ``i``
    is past the leading dense layers and on the expert layers' period."""
    return bool(cfg.get("n_routed_experts")) \
        and i >= (cfg.get("first_k_dense_replace") or 0) \
        and i % (cfg.get("moe_layer_freq") or 1) == 0


def router_width(cfg: Dict) -> int:
    """Experts the router scores: the published count where the file
    holds a share of them, else the experts it holds."""
    return (cfg.get("published") or {}).get("n_routed_experts",
                                            cfg["n_routed_experts"])


def _attention_dims(cfg: Dict) -> Tuple[int, int]:
    """(query/key dims, value dims) of one head."""
    if cfg.get("kv_lora_rank"):
        return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
                cfg["v_head_dim"])
    return cfg["head_dim"], cfg["head_dim"]


def attention_params(cfg: Dict) -> int:
    """Weights of one layer's attention projections.

    Latent attention (MLA, arXiv:2405.04434 §2.1) is counted in its
    published form: the query from ``h`` (through ``q_lora_rank`` where
    it is given), the joint down-projection to ``kv_lora_rank`` plus the
    shared rotary key, the up-projection of the latent to every head's
    key and value once per token, and the output projection.  Attention
    then reads ``qk_nope + qk_rope`` dims a key and ``v_head_dim`` a
    value (``attention_flops``).  A program that absorbs the
    up-projection into the query, or re-expands cached latents every
    step, is measured against this same count."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    if cfg.get("kv_lora_rank"):
        r, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
        qk, vd = _attention_dims(cfg)
        qr = cfg.get("q_lora_rank")
        q = d * qr + qr * h * qk if qr else d * h * qk
        return q + d * (r + rope) + r * h * (cfg["qk_nope_head_dim"] + vd) \
            + h * vd * d
    return d * cfg["head_dim"] * (2 * h + 2 * cfg["num_key_value_heads"])


def ffn_params(cfg: Dict, i: int) -> int:
    """Weights one token multiplies through in layer ``i``'s feed-forward:
    a dense MLP at ``intermediate_size``, or the router over every
    routed expert, ``num_experts_per_tok`` routed experts times the share
    of them held here, and the shared experts."""
    d = cfg["hidden_size"]
    n_mlp = 3 if cfg["hidden_act"] == "silu" else 2
    if not is_moe_layer(cfg, i):
        return n_mlp * d * cfg["intermediate_size"]
    expert = n_mlp * d * cfg["moe_intermediate_size"]
    e_all = router_width(cfg)
    routed = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] * expert \
        // e_all
    return d * e_all + routed + (cfg.get("n_shared_experts") or 0) * expert


def matmul_params(cfg: Dict, i: int) -> int:
    """Weights one token multiplies through in layer ``i`` (attention
    projections and feed-forward), from the configuration's widths."""
    return attention_params(cfg) + ffn_params(cfg, i)


def body_params(cfg: Dict) -> int:
    """``matmul_params`` summed over every layer."""
    return sum(matmul_params(cfg, i) for i in range(cfg["num_hidden_layers"]))


def attention_flops(cfg: Dict, ctx: int) -> int:
    """Scores and weighted sum of ONE query over ``ctx`` keys, all layers."""
    qk, vd = _attention_dims(cfg)
    return (2 * cfg["num_attention_heads"] * (qk + vd) * ctx
            * cfg["num_hidden_layers"])


def token_flops(cfg: Dict, ctx: int, *, logits: bool) -> int:
    """Model FLOPs of one token at context ``ctx`` (itself included):
    2 x the weights it multiplies through in every layer, attention over
    its context, and the output head where its logits are needed."""
    f = 2 * body_params(cfg)
    f += attention_flops(cfg, ctx)
    if logits:
        f += 2 * cfg["hidden_size"] * cfg["vocab_size"]
    return f


def chunk_flops(cfg: Dict, start: int, n: int, *, last: bool) -> int:
    """A prefill chunk of ``n`` prompt tokens at positions ``start..``;
    the head runs for the prompt's last token only (``last``)."""
    total = 2 * body_params(cfg) * n
    # sum over positions p of attention over p + 1 keys
    total += attention_flops(cfg, n * start + n * (n + 1) // 2)
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

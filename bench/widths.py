"""The served layout against the widths the configuration file states.

Run in set-up on the program's layout (shapes only, before any array is
made): the program maps the file, so this is what keeps it from serving
a smaller model than the file states, under a reference and FLOP counts
that read the file.  Checked: the hidden size and the vocabulary rows of
the embedding and head (the file's ``vocab_size``, padded as the program
pads it); the number of layers (one ``norm1`` per block); every
attention projection's widths, so the heads and their sizes, and the
QKV biases where the file has them; the norms' biases where it states a
LayerNorm; the dense MLPs' width and how many layers hold one, with a
gate where the activation is SiLU; and where the file has experts, the
experts held, their width and gate, the router's width, the shared
experts' width and how many layers hold each, and the experts a token
is routed to as the program maps the file.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

from bench import costs


class LayoutMismatch(ValueError):
    """The served layout holds another model than the file states."""


def _stacks(layout: Dict[str, Tuple[int, ...]], suffix: str,
            mat: Tuple[int, ...], bad: List[str]) -> int:
    """How many ``mat``-shaped matrices the leaves named ``*suffix`` hold
    (their leading axes multiplied out); a leaf of another shape is
    reported in ``bad``."""
    n = 0
    for name, shape in sorted(layout.items()):
        if not name.endswith(suffix):
            continue
        if tuple(shape[len(shape) - len(mat):]) != tuple(mat):
            bad.append(f"{name}: served {shape}, the file states "
                       f"(..., {', '.join(map(str, mat))})")
        n += math.prod(shape[:len(shape) - len(mat)])
    return n


def check(cfg: Dict, layout: Dict[str, Tuple[int, ...]], padded_vocab: int,
          experts_per_token: int) -> None:
    """Raise ``LayoutMismatch`` naming every width that differs;
    ``experts_per_token`` is the program's mapping of the file's top-k."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    n_layers = cfg["num_hidden_layers"]
    moe = [costs.is_moe_layer(cfg, i) for i in range(n_layers)]
    gated = cfg["hidden_act"] == "silu"
    bad: List[str] = []

    def expect(what, served, stated):
        if served != stated:
            bad.append(f"{what}: served {served}, the file states {stated}")

    expect("embed/tok", layout.get("embed/tok"), (padded_vocab, d))
    expect("lm_head", layout.get("lm_head"), (d, padded_vocab))
    expect("layers", _stacks(layout, "/norm1/norm_scale", (d,), bad),
           n_layers)
    # a LayerNorm's bias: two a layer and the final norm's
    ln = 2 * n_layers + 1 if cfg.get("layer_norm_eps") else 0
    expect("norms with a bias", _stacks(layout, "/bias", (d,), bad), ln)

    def mats(suffix, shape, layers):
        expect(f"layers holding {suffix}",
               _stacks(layout, suffix + "/codes", shape, bad), layers)

    if cfg.get("kv_lora_rank"):
        r, vd = cfg["kv_lora_rank"], cfg["v_head_dim"]
        nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
        if not cfg.get("q_lora_rank"):
            mats("/mixer/wq", (d, h * (nope + rope)), n_layers)
        mats("/mixer/w_dkv", (d, r + rope), n_layers)
        mats("/mixer/w_ukv", (r, h * (nope + vd)), n_layers)
        mats("/mixer/wo", (h * vd, d), n_layers)
    else:
        hd, kh = cfg["head_dim"], cfg["num_key_value_heads"]
        mats("/mixer/wq", (d, h * hd), n_layers)
        mats("/mixer/wk", (d, kh * hd), n_layers)
        mats("/mixer/wv", (d, kh * hd), n_layers)
        mats("/mixer/wo", (h * hd, d), n_layers)
        biased = n_layers if cfg.get("attention_bias") else 0
        for b, width in (("bq", h * hd), ("bk", kh * hd), ("bv", kh * hd)):
            expect(f"layers holding /mixer/{b}",
                   _stacks(layout, f"/mixer/{b}", (width,), bad), biased)

    dense = n_layers - sum(moe)
    ff = cfg.get("intermediate_size") or 0
    mats("/ffn/w_up", (d, ff), dense)
    mats("/ffn/w_gate", (d, ff), dense if gated else 0)
    mats("/ffn/w_down", (ff, d), dense)
    if any(moe):
        e, eff = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
        mats("/ffn/experts/w_up", (e, d, eff), sum(moe))
        mats("/ffn/experts/w_gate", (e, d, eff), sum(moe) if gated else 0)
        mats("/ffn/experts/w_down", (e, eff, d), sum(moe))
        expect("layers holding /ffn/router",
               _stacks(layout, "/ffn/router", (d, costs.router_width(cfg)),
                       bad), sum(moe))
        shared = (cfg.get("n_shared_experts") or 0) * eff
        held = sum(moe) if shared else 0
        mats("/ffn/shared/w_up", (d, shared), held)
        mats("/ffn/shared/w_gate", (d, shared), held if gated else 0)
        mats("/ffn/shared/w_down", (shared, d), held)
        expect("experts a token is routed to", experts_per_token,
               cfg["num_experts_per_tok"])
    if bad:
        raise LayoutMismatch(f"configuration {cfg.get('name', '?')!r}: "
                             + "; ".join(bad))

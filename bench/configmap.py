"""A configuration file as the program's ``ModelConfig``, or the named
keys the program cannot honour.

The harness asks the program first (``repro.configs.from_file``,
function ``model_config``); where the program has no mapping of its own,
this one stands in.  Where it has one, every field this module sets for
a file it maps has to come out the same from the program's mapping, so
a parent and a change that map a file with different code serve the
same model.  It maps what the program serves today:

* dense GQA decoders: SwiGLU or squared-ReLU MLP, RMSNorm or LayerNorm,
  optional QKV bias, full-head RoPE;
* fine-grained MoE (``n_routed_experts``, ``num_experts_per_tok``,
  ``moe_intermediate_size``, ``n_shared_experts``, ``norm_topk_prob``),
  softmax scores, greedy top-k, every layer an expert layer.  Published
  routing drops no token, so the capacity factor is set so that every
  expert can take every token of a row (``E / k``);
* latent attention (``kv_lora_rank``, ``qk_nope_head_dim``,
  ``qk_rope_head_dim``, ``v_head_dim``) without a query compression.

Every other key is refused by name, unless it is on ``NEUTRAL``.  A
value the program holds fixed (a norm's epsilon) is checked against the
program's own constant.  So a program that lacks a mechanism a file
asks for stops here, before any weight is made.
"""
from __future__ import annotations

import inspect
from typing import Any, Dict

from bench.harness import UnsupportedConfig

# keys that change nothing the program computes when served
NEUTRAL = frozenset({
    "name", "source", "architectures", "model_type", "torch_dtype",
    "initializer_range", "max_position_embeddings",
    # training-loss settings
    "seq_aux", "aux_loss_alpha",
    # the file's own notes and the harness's settings
    "reduced", "assumed", "departures", "deployment", "serving", "check",
    "reference", "published",
})


def _default(fn, arg: str):
    """The program's fixed value of ``arg`` (a keyword default of ``fn``),
    or None where ``fn`` has no such keyword."""
    p = inspect.signature(fn).parameters.get(arg)
    return None if p is None or p.default is p.empty else p.default


def model_config(cfg: Dict[str, Any]):
    """The ``ModelConfig`` of the architecture ``cfg`` states; raises
    ``UnsupportedConfig`` naming every key the program cannot honour."""
    from repro.configs.base import ModelConfig

    return ModelConfig(name=cfg.get("name", ""), **fields(cfg))


def fields(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The ``ModelConfig`` fields ``cfg`` sets, by name; raises
    ``UnsupportedConfig`` naming every key the program cannot honour."""
    from repro.models import layers

    bad: Dict[str, str] = {}
    seen = set(NEUTRAL)

    def get(key, default=None):
        seen.add(key)
        return cfg.get(key, default)

    def need(key):
        seen.add(key)
        if cfg.get(key) is None:
            bad[key] = "required, not given"
        return cfg.get(key)

    def only(key, allowed, default=None):
        v = get(key, default)
        if v not in allowed:
            bad[key] = f"{v!r}; the program serves {sorted(map(repr, allowed))}"
        return v

    d = need("hidden_size")
    heads = need("num_attention_heads")
    kw: Dict[str, Any] = dict(
        num_layers=need("num_hidden_layers"), d_model=d,
        vocab_size=need("vocab_size"), num_heads=heads,
        num_kv_heads=get("num_key_value_heads", heads),
        d_ff=get("intermediate_size", 0) or 0,
        rope_theta=float(need("rope_theta") or 0))

    act = only("hidden_act", ("silu", "relu2"))
    kw["mlp_type"] = {"silu": "swiglu", "relu2": "squared_relu"}.get(act, "")
    only("rope_scaling", (None,))
    only("use_sliding_window", (False,), False)
    if not get("use_sliding_window"):
        seen.update({"sliding_window", "max_window_layers"})
    # the seeded weights fill a tied head with the embedding's transpose
    get("tie_word_embeddings")

    def eps(key, norm):
        held = _default(norm, "eps")
        if held is None:
            bad[key] = f"{norm.__name__} states no eps to hold it to"
        elif get(key) != held:
            bad[key] = f"{get(key)}; the program's is {held}"

    ln_eps, rms_eps = get("layer_norm_eps"), get("rms_norm_eps")
    if ln_eps:
        kw["norm_layernorm"] = True
        eps("layer_norm_eps", layers.layer_norm)
        if rms_eps is not None:
            bad["rms_norm_eps"] = "given beside layer_norm_eps"
    else:
        eps("rms_norm_eps", layers.rms_norm)

    bias = bool(get("attention_bias", False))
    if get("kv_lora_rank"):                      # latent attention
        only("q_lora_rank", (None,))
        if bias:
            bad["attention_bias"] = "latent attention has no biases here"
        if get("head_dim") is not None:
            bad["head_dim"] = "latent attention sizes its own heads"
        kw.update(use_mla=True, kv_lora_rank=cfg["kv_lora_rank"],
                  qk_nope_dim=need("qk_nope_head_dim"),
                  rope_head_dim=need("qk_rope_head_dim"),
                  v_head_dim=need("v_head_dim"))
    else:
        kw.update(head_dim=get("head_dim") or (d or 0) // (heads or 1),
                  attn_bias=bias)

    experts = get("n_routed_experts")
    if experts:
        k = need("num_experts_per_tok")
        if act != "silu":
            bad["hidden_act"] = f"{act!r}; experts are SwiGLU here"
        only("scoring_func", ("softmax",), "softmax")
        only("topk_method", ("greedy",), "greedy")
        only("n_group", (None, 1))
        only("topk_group", (None, 1))
        only("routed_scaling_factor", (1, 1.0), 1)
        only("moe_layer_freq", (1,), 1)
        only("first_k_dense_replace", (0,), 0)
        if get("published", {}).get("n_routed_experts", experts) != experts:
            bad["published"] = ("an expert share: the program routes over "
                                "the experts it holds")
        kw.update(arch_type="moe", num_experts=experts, experts_per_token=k,
                  moe_d_ff=need("moe_intermediate_size"),
                  num_shared_experts=get("n_shared_experts", 0) or 0,
                  moe_renormalize=bool(get("norm_topk_prob", False)),
                  moe_capacity_factor=experts / (k or 1))
    else:
        kw["arch_type"] = "dense"
        need("intermediate_size")

    for key in sorted(set(cfg) - seen):
        bad[key] = "unknown to the program"
    if bad:
        keys = sorted(bad)
        raise UnsupportedConfig(
            f"configuration {cfg.get('name', '?')!r}: the program cannot "
            "honour " + "; ".join(f"{k} ({bad[k]})" for k in keys), keys)
    return kw

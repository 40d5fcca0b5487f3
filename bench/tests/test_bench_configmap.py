"""A configuration file as the program's ``ModelConfig``: the qwen file
maps as it always did, what the program cannot honour is refused by
name before any weight, cache or gateway is made, and a served layout
that differs from the file's widths stops the run.

The refusals are held under both mappings the harness can meet
(fixture ``mapping``): ``bench``, no program mapping, so
``bench/configmap.py`` maps; and ``program``, a stub program mapping
that serves what ``configmap`` refuses of DeepSeek-V2-Lite (a leading
dense layer, YaRN, an expert share) and refuses the rest with its own
``ValueError`` subclass."""
import copy
import json
import time
from pathlib import Path

import pytest

from bench import configmap, harness, weights, widths
from bench.tests.test_bench_rehearsal import MLA_MOE, TOYS

QWEN = json.loads((Path(harness.BENCH) / "configs"
                   / "qwen2.5-3b-int8.json").read_text())

# DeepSeek-V2-Lite's published config.json, as the model catalog reads it
# (https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json)
DEEPSEEK_V2_LITE = {
    "name": "deepseek-v2-lite",
    "source": "https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json",
    "attention_bias": False, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 10944,
    "kv_lora_rank": 512, "max_position_embeddings": 163840,
    "model_type": "deepseek_v2", "moe_intermediate_size": 1408,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 2, "norm_topk_prob": False,
    "num_attention_heads": 16, "num_experts_per_tok": 6,
    "num_hidden_layers": 27, "num_key_value_heads": 16, "q_lora_rank": None,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 1,
    "scoring_func": "softmax", "seq_aux": True, "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "greedy", "v_head_dim": 128,
    "vocab_size": 102400,
}


class StubRefusal(ValueError):
    """The stub program mapping's own refusal: a ``ValueError`` with
    ``keys``, as the program's contract states."""

    def __init__(self, keys):
        self.keys = list(keys)
        super().__init__("the stub cannot honour " + ", ".join(self.keys))


def stub_program_mapping(cfg):
    """A program mapping that honours ``first_k_dense_replace``, YaRN
    ``rope_scaling`` and an expert share under ``published`` (mapping the
    file as if it stated none of them), and refuses whatever else
    ``configmap`` refuses, with its own exception type."""
    rest = {k: v for k, v in cfg.items() if k != "published"}
    if "first_k_dense_replace" in rest:
        rest["first_k_dense_replace"] = 0
    if (rest.get("rope_scaling") or {}).get("type") == "yarn":
        rest["rope_scaling"] = None
    try:
        return configmap.model_config(rest)
    except configmap.UnsupportedConfig as e:
        raise StubRefusal(e.keys) from None


@pytest.fixture(params=["bench", "program"])
def mapping(request, monkeypatch):
    """Which mapping serves a file: none of the program's (``bench``), or
    the stub program mapping (``program``)."""
    stub = stub_program_mapping if request.param == "program" else None
    monkeypatch.setattr(harness, "program_mapping", lambda: stub)
    return request.param


def test_qwen_file_maps_to_the_config_it_always_served():
    mapped = harness.model_config(QWEN)
    from repro.configs.base import ModelConfig

    assert mapped == ModelConfig(
        name="qwen2.5-3b-int8", arch_type="dense", num_layers=36,
        d_model=2048, vocab_size=151936, num_heads=16, num_kv_heads=2,
        head_dim=128, d_ff=11008, mlp_type="swiglu", attn_bias=True,
        norm_layernorm=False, rope_theta=1000000.0, dtype_name="bfloat16",
        remat=False,
        source="https://huggingface.co/Qwen/Qwen2.5-3B/blob/main/config.json")


# keys no mapping honours: a mechanism unknown to the program, a sliding
# window that is on, an activation no block of the program has
UNHONOURED = [("use_sliding_window", True), ("hidden_act", "gelu"),
              ("an_unknown_key", 0)]


@pytest.mark.parametrize("key,value", UNHONOURED)
def test_published_deepseek_v2_lite_stops_set_up_before_any_array(
        mapping, key, value, monkeypatch):
    def never(*_a, **_k):
        raise AssertionError("set-up went past the mapping")

    for mod, name in ((weights, "build"), (harness, "served_layout"),
                      (harness, "use_compile_cache"),
                      (harness, "make_gateway")):
        monkeypatch.setattr(mod, name, never)
    cfg, mix = MLA_MOE
    spec = {"cell": {"name": "dsv2", "chips": 1, "traffic": "x"},
            "config": dict(DEEPSEEK_V2_LITE, serving=cfg["serving"],
                           check=cfg["check"], **{key: value})}
    t0 = time.perf_counter()
    with pytest.raises(harness.UnsupportedConfig) as err:
        harness.set_up("dsv2", 1, require_chip=False, spec=spec, mix=mix,
                       log=lambda s: None)
    assert time.perf_counter() - t0 < 10
    assert key in err.value.keys and key in str(err.value)
    # configmap refuses the published file's own two keys beside it; the
    # program's mapping serves them
    rest = {"bench": ["first_k_dense_replace", "rope_scaling"],
            "program": []}[mapping]
    assert sorted(err.value.keys) == sorted({key, *rest})


def test_configmap_names_the_published_deepseek_v2_lite_keys_it_lacks():
    with pytest.raises(configmap.UnsupportedConfig) as err:
        configmap.model_config(DEEPSEEK_V2_LITE)
    assert err.value.keys == ["first_k_dense_replace", "rope_scaling"]
    assert "first_k_dense_replace" in str(err.value)
    assert "rope_scaling" in str(err.value)


def test_deepseek_v2_lite_without_them_maps_at_published_widths():
    cfg = dict(DEEPSEEK_V2_LITE, first_k_dense_replace=0, rope_scaling=None)
    m = configmap.model_config(cfg)
    assert (m.arch_type, m.num_layers, m.d_model, m.vocab_size) == \
        ("moe", 27, 2048, 102400)
    assert (m.num_heads, m.num_kv_heads, m.use_mla, m.kv_lora_rank,
            m.qk_nope_dim, m.rope_head_dim, m.v_head_dim) == \
        (16, 16, True, 512, 128, 64, 128)
    assert (m.num_experts, m.experts_per_token, m.num_shared_experts,
            m.moe_d_ff, m.moe_renormalize) == (64, 6, 2, 1408, False)
    assert (m.mlp_type, m.rope_theta, m.attn_bias, m.norm_layernorm) == \
        ("swiglu", 10000.0, False, False)
    # drop-free: every expert can take every token of a row
    assert m.moe_capacity_factor * m.experts_per_token / m.num_experts \
        == pytest.approx(1.0)


@pytest.mark.parametrize("key,value", [
    ("first_k_dense_replace", 1), ("rope_scaling", {"type": "yarn"}),
    ("q_lora_rank", 1536), ("scoring_func", "sigmoid"),
    ("topk_method", "group_limited_greedy"), ("routed_scaling_factor", 2.5),
    ("n_group", 8), ("moe_layer_freq", 2), ("rms_norm_eps", 1e-5),
    ("published", {"n_routed_experts": 256}), ("attention_bias", True),
    ("some_new_mechanism", 1),
])
def test_each_key_the_program_cannot_honour_is_named(key, value):
    cfg = dict(DEEPSEEK_V2_LITE, first_k_dense_replace=0, rope_scaling=None)
    cfg[key] = value
    with pytest.raises(configmap.UnsupportedConfig) as err:
        configmap.model_config(cfg)
    assert err.value.keys == [key]


@pytest.mark.parametrize("key,value", UNHONOURED)
def test_dense_file_refuses_what_the_program_cannot_honour(mapping, key,
                                                           value):
    cfg = dict(QWEN, **{key: value})
    with pytest.raises(harness.UnsupportedConfig) as err:
        harness.model_config(cfg)
    assert key in err.value.keys and key in str(err.value)
    if mapping == "program":
        assert isinstance(err.value.__cause__, StubRefusal)


def test_a_program_refusal_without_keys_keeps_its_message(monkeypatch):
    def refuse(cfg):
        raise ValueError("no YaRN beyond factor 32")

    monkeypatch.setattr(harness, "program_mapping", lambda: refuse)
    with pytest.raises(harness.UnsupportedConfig) as err:
        harness.model_config(QWEN)
    assert err.value.keys == []
    assert "no YaRN beyond factor 32" in str(err.value)


def test_configmap_refuses_with_the_harness_type():
    assert configmap.UnsupportedConfig is harness.UnsupportedConfig


def test_toy_mla_moe_layout_holds_what_the_file_states():
    cfg, _ = MLA_MOE
    mcfg = harness.model_config(cfg)
    names = weights.layer_names(harness.served_layout(mcfg))
    widths.check(cfg, names, mcfg.padded_vocab, mcfg.experts_per_token)


@pytest.mark.parametrize("toy", ["swiglu_rms_tied", "relu2_layernorm_untied"])
def test_dense_toy_layouts_hold_what_the_files_state(toy):
    cfg, _ = TOYS[toy]
    mcfg = harness.model_config(cfg)
    names = weights.layer_names(harness.served_layout(mcfg))
    widths.check(cfg, names, mcfg.padded_vocab, mcfg.experts_per_token)


@pytest.mark.parametrize("toy,change,named", [
    ("mla_moe", {"num_experts": 4}, "/ffn/experts/w_up"),
    ("mla_moe", {"num_layers": 1}, "layers"),
    ("mla_moe", {"moe_d_ff": 16}, "/ffn/experts/w_up"),
    ("mla_moe", {"kv_lora_rank": 16}, "/mixer/w_dkv"),
    ("mla_moe", {"num_heads": 2}, "/mixer/wq"),
    ("mla_moe", {"vocab_size": 256}, "embed/tok"),
    ("mla_moe", {"num_shared_experts": 0}, "/ffn/shared/w_up"),
    ("mla_moe", {"num_shared_experts": 2}, "/ffn/shared/w_down"),
    ("mla_moe", {"experts_per_token": 1}, "experts a token is routed to"),
    ("swiglu_rms_tied", {"num_kv_heads": 1}, "/mixer/wk"),
    ("swiglu_rms_tied", {"d_ff": 64}, "/ffn/w_up"),
    ("swiglu_rms_tied", {"mlp_type": "squared_relu"}, "/ffn/w_gate"),
    ("swiglu_rms_tied", {"attn_bias": False}, "/mixer/bq"),
    ("relu2_layernorm_untied", {"d_model": 32}, "embed/tok"),
    ("relu2_layernorm_untied", {"mlp_type": "swiglu"}, "/ffn/w_gate"),
    ("relu2_layernorm_untied", {"norm_layernorm": False}, "norms with a bias"),
])
def test_a_smaller_served_model_stops_set_up(toy, change, named,
                                             monkeypatch):
    cfg, mix = MLA_MOE if toy == "mla_moe" else TOYS[toy]
    mapped = harness.model_config

    monkeypatch.setattr(harness, "model_config",
                        lambda c: mapped(c).replace(**change))
    monkeypatch.setattr(weights, "build", lambda *a, **k: 1 / 0)
    spec = {"cell": {"name": "small", "chips": 1, "traffic": "x"},
            "config": copy.deepcopy(cfg)}
    with pytest.raises(widths.LayoutMismatch, match=named):
        harness.set_up("small", 1, require_chip=False, spec=spec, mix=mix,
                       compile_cache=False, log=lambda s: None)


@pytest.fixture
def program_maps(monkeypatch):
    """Stand in a program mapping: configmap's, with ``change`` applied."""
    def install(change):
        monkeypatch.setattr(harness, "program_mapping", lambda: (
            lambda c: configmap.model_config(c).replace(**change)))
    return install


def test_program_mapping_that_agrees_is_used(program_maps):
    program_maps({"q_chunk": 256})          # a serving choice, not the file's
    assert harness.model_config(QWEN).q_chunk == 256


@pytest.mark.parametrize("toy,change", [
    ("qwen", {"rope_theta": 10000.0}), ("qwen", {"mlp_type": "squared_relu"}),
    ("qwen", {"attn_bias": False}), ("mla_moe", {"experts_per_token": 1}),
    ("mla_moe", {"moe_capacity_factor": 1.25}),
    ("mla_moe", {"moe_renormalize": True}),
])
def test_program_mapping_that_differs_is_refused(program_maps, toy, change):
    program_maps(change)
    cfg = QWEN if toy == "qwen" else MLA_MOE[0]
    with pytest.raises(harness.MappingMismatch) as err:
        harness.model_config(cfg)
    assert all(k in str(err.value) for k in change)


def test_program_mapping_of_a_file_configmap_refuses_is_taken(monkeypatch):
    # a program that serves the leading dense layer and YaRN (here: maps
    # the file as if it stated neither)
    monkeypatch.setattr(harness, "program_mapping", lambda: (
        lambda c: configmap.model_config(
            dict(c, first_k_dense_replace=0, rope_scaling=None))))
    with pytest.raises(configmap.UnsupportedConfig):
        configmap.fields(DEEPSEEK_V2_LITE)
    assert harness.model_config(DEEPSEEK_V2_LITE).num_experts == 64


# one chip's share of DeepSeek-V2-Lite under 4-way expert parallelism:
# 16 of the 64 routed experts, every layer, the whole vocabulary
DSV2_SHARE = dict(DEEPSEEK_V2_LITE, name="dsv2lite-share", n_routed_experts=16,
                  published={"n_routed_experts": 64},
                  reduced=["n_routed_experts"])


def _share_layout(router=64, lead=True):
    """The share's served layout as the program would hold it (shapes
    only): layer 0 dense and unstacked, 26 expert layers stacked."""
    import jax
    import jax.numpy as jnp

    d, h, r, vocab = 2048, 16, 512, 102400
    mla = {"norm1/norm_scale": (d,), "norm2/norm_scale": (d,),
           "mixer/ckv_norm": (r,), "mixer/wq/codes": (d, h * 192),
           "mixer/w_dkv/codes": (d, r + 64), "mixer/w_ukv/codes": (r, h * 256),
           "mixer/wo/codes": (h * 128, d)}
    dense = {"ffn/w_up/codes": (d, 10944), "ffn/w_gate/codes": (d, 10944),
             "ffn/w_down/codes": (10944, d)}
    moe = {"ffn/experts/w_up/codes": (16, d, 1408),
           "ffn/experts/w_gate/codes": (16, d, 1408),
           "ffn/experts/w_down/codes": (16, 1408, d),
           "ffn/router": (d, router), "ffn/shared/w_up/codes": (d, 2816),
           "ffn/shared/w_gate/codes": (d, 2816),
           "ffn/shared/w_down/codes": (2816, d)}
    shapes = {"embed/tok": (vocab, d), "lm_head": (d, vocab),
              "final_norm/norm_scale": (d,)}
    if lead:
        shapes.update({f"lead/{k}": v for k, v in {**mla, **dense}.items()})
    shapes.update({f"units/b0/{k}": (26, *v)
                   for k, v in {**mla, **moe}.items()})
    return {k: jax.ShapeDtypeStruct(v, jnp.int8 if k.endswith("/codes")
                                    else jnp.bfloat16)
            for k, v in shapes.items()}


class _Built(Exception):
    """Set-up reached ``weights.build``."""


@pytest.mark.parametrize("layout,named", [
    ({}, None), ({"router": 16}, "/ffn/router"), ({"lead": False}, "/ffn/w_up")],
    ids=["as-served", "router-16-wide", "lead-layer-missing"])
def test_program_mapping_takes_a_deepseek_v2_lite_share(layout, named,
                                                        monkeypatch):
    monkeypatch.setattr(harness, "program_mapping",
                        lambda: stub_program_mapping)
    monkeypatch.setattr(harness, "served_layout",
                        lambda mcfg: _share_layout(**layout))

    def build(*_a, **_k):
        raise _Built

    monkeypatch.setattr(weights, "build", build)
    cfg, mix = MLA_MOE
    spec = {"cell": {"name": "dsv2", "chips": 1, "traffic": "x"},
            "config": dict(DSV2_SHARE, serving=cfg["serving"],
                           check=cfg["check"])}
    err = _Built if named is None else widths.LayoutMismatch
    with pytest.raises(err, match=named):
        harness.set_up("dsv2", 1, require_chip=False, spec=spec, mix=mix,
                       compile_cache=False, log=lambda s: None)
    # configmap alone refuses the share by its three keys
    with pytest.raises(configmap.UnsupportedConfig) as refused:
        configmap.model_config(DSV2_SHARE)
    assert refused.value.keys == ["first_k_dense_replace", "published",
                                  "rope_scaling"]


@pytest.mark.parametrize("path", sorted(
    (Path(harness.BENCH) / "configs").glob("*.json")), ids=lambda p: p.stem)
def test_every_benchmark_file_maps_and_holds_its_widths(path):
    cfg = json.loads(path.read_text())
    mcfg = harness.model_config(cfg)
    widths.check(cfg, weights.layer_names(harness.served_layout(mcfg)),
                 mcfg.replace(vocab_size=cfg["vocab_size"]).padded_vocab,
                 mcfg.experts_per_token)


@pytest.mark.parametrize("norm", ["rms_norm", "layer_norm"])
def test_a_program_norm_without_an_eps_is_refused_by_name(monkeypatch, norm):
    from repro.models import layers

    monkeypatch.setattr(layers, norm, lambda x, scale, *a: x)
    cfg = QWEN if norm == "rms_norm" else TOYS["relu2_layernorm_untied"][0]
    key = "rms_norm_eps" if norm == "rms_norm" else "layer_norm_eps"
    with pytest.raises(configmap.UnsupportedConfig) as err:
        configmap.model_config(cfg)
    assert key in err.value.keys

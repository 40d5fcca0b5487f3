"""A whole run of the harness on the CPU at a toy size: traffic, client,
metric arithmetic and the comparison, with the chip check skipped.

Two toy architectures cover both block kinds the reference holds:
SwiGLU with RMSNorm, QKV bias, a tied head and three tiers (the qwen
cell's shape), and squared ReLU with LayerNorm and an untied head (a
Nemotron-4 block).  A third, latent attention with fine-grained experts
(a DeepSeek-V2 block), is given only as a config dict and a mix and goes
through set-up and serving: no file of the harness names it.

It also holds the comparison's two controls at a size a test run can
hold: a run with the int4 control in the program's place comes out not
correct while the program reads under the limit, and so does a run
whose served tokens are altered where they are produced.  No device
metric is printed (the platform is the CPU).
"""
import math
import time

import pytest

from bench import harness, traffic

E2E = ["itl_p95_ms", "output_tok_s", "setup_s"]
LIMIT = 0.05


def _cfg(**kw):
    cfg = {
        "name": "toy", "source": "test", "reference": "dense_decoder",
        "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 512,
        "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
        "tie_word_embeddings": True, "attention_bias": True,
        "serving": {
            "tiers": {"full": {}, "pro": {"*": [[0.0, 0.02]]},
                      "free": {"*": [[0.0, 0.06]]}},
            "gateway": {"max_batch": 4, "max_lanes": 8, "block_size": 16,
                        "num_blocks": 64, "max_prompt": 112,
                        "max_new_cap": 16, "chunk_size": 32}},
        "check": {"logit_gap_limit": LIMIT, "min_served_tokens_per_tier": 32},
    }
    cfg.update(kw)
    return cfg


def _mix(**kw):
    mix = {"arrival": {"process": "poisson", "rate_per_s": 8},
           "tiers": {"full": 0.2, "pro": 0.3, "free": 0.5},
           "prompt_tokens": {"dist": "lognormal", "median": 40, "sigma": 0.5,
                             "min": 8, "max": 112},
           "output_tokens": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                             "min": 2, "max": 16},
           "max_context": 128, "lead_in_s": 1.0, "schedule_seed": 0,
           "sampling": "greedy"}
    mix.update(kw)
    return mix


TOYS = {
    "swiglu_rms_tied": (_cfg(), _mix()),
    "relu2_layernorm_untied": (
        _cfg(hidden_act="relu2", intermediate_size=256,
             num_attention_heads=6, rms_norm_eps=None, layer_norm_eps=1e-5,
             tie_word_embeddings=False, attention_bias=False,
             serving={"tiers": {"full": {}},
                      "gateway": {"max_batch": 4, "max_lanes": 6,
                                  "block_size": 16, "num_blocks": 64,
                                  "max_prompt": 112, "max_new_cap": 16,
                                  "chunk_size": 16}}),
        _mix(arrival={"process": "poisson", "rate_per_s": 6},
             tiers={"full": 1.0},
             prompt_tokens={"dist": "lognormal", "median": 70,
                            "sigma": 0.2, "min": 48, "max": 112},
             output_tokens={"dist": "lognormal", "median": 8,
                            "sigma": 0.25, "min": 4, "max": 16})),
}


MLA_MOE = (
    {"name": "toy-mla-moe", "source": "test", "hidden_act": "silu",
     "hidden_size": 64, "intermediate_size": 160, "num_hidden_layers": 2,
     "num_attention_heads": 4, "num_key_value_heads": 4, "vocab_size": 512,
     "rope_theta": 10000.0, "rope_scaling": None, "rms_norm_eps": 1e-6,
     "tie_word_embeddings": False, "attention_bias": False,
     "kv_lora_rank": 32, "q_lora_rank": None, "qk_nope_head_dim": 16,
     "qk_rope_head_dim": 8, "v_head_dim": 16, "n_routed_experts": 8,
     "num_experts_per_tok": 2, "moe_intermediate_size": 32,
     "n_shared_experts": 1, "norm_topk_prob": False,
     "scoring_func": "softmax", "topk_method": "greedy",
     "first_k_dense_replace": 0, "moe_layer_freq": 1,
     "serving": {"tiers": {"full": {}, "pro": {"*": [[0.0, 0.02]]}},
                 "gateway": {"max_batch": 2, "max_lanes": 4,
                             "block_size": 32, "num_blocks": 16,
                             "max_prompt": 60, "max_new_cap": 8,
                             "chunk_size": 32}},
     "check": {"logit_gap_limit": LIMIT, "min_served_tokens_per_tier": 8}},
    _mix(arrival={"process": "poisson", "rate_per_s": 4},
         tiers={"full": 0.5, "pro": 0.5},
         prompt_tokens={"dist": "lognormal", "median": 30, "sigma": 0.5,
                        "min": 8, "max": 60},
         output_tokens={"dist": "lognormal", "median": 4, "sigma": 0.5,
                        "min": 2, "max": 8},
         max_context=64))


def _run(toy, seed, *, control=False, on_gateway=None):
    cfg, mix = TOYS[toy]
    spec = {"cell": {"name": toy, "chips": 1, "traffic": toy},
            "config": cfg,
            "end_to_end": [{"name": n, "unit": "x"} for n in E2E],
            "per_layer": [{"name": n, "unit": "x"} for n in
                          ("sched.batch_occupancy", "pool.preemptions")]}
    return harness.run_cell(toy, seed, 3.0, False,
                            t_start=time.perf_counter(), require_chip=False,
                            spec=spec, mix=mix, control=control,
                            on_gateway=on_gateway, compile_cache=False,
                            log=lambda s: None)


@pytest.fixture(scope="module", params=sorted(TOYS))
def sound(request):
    return request.param, _run(request.param, 3_000_000_001)


def test_rehearsal_runs_the_whole_path(sound):
    toy, out = sound
    _, mix = TOYS[toy]
    assert out["correct"] is True
    assert out["attempted"] == mix["arrival"]["rate_per_s"] * (
        mix["lead_in_s"] + 3)
    assert out["failed"] == 0
    # every tier of the mix brought its floor of served tokens
    served = [k for k in out["checks"] if k.startswith("served_tokens.")]
    assert sorted(served) == [f"served_tokens.{t}" for t in sorted(mix["tiers"])]
    assert set(out["metrics"]) == set(E2E)
    assert all(math.isfinite(m["value"]) and m["value"] > 0
               for m in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"
    assert out["info"]["window_compiles"] == 0
    assert out["info"]["lead_in_compiles"] == 0
    assert list(out)[-1] == "checks"


def test_int4_control_fails_the_limit_the_program_meets(sound):
    toy, out = sound
    program = out["checks"]["logit_gap"]["value"]
    assert program <= LIMIT
    control = _run(toy, 3_000_000_001, control=True)
    assert control["correct"] is False
    c = control["checks"]["logit_gap"]
    assert c["value"] > c["limit"] == LIMIT
    assert c["value"] >= 3 * program


def test_altered_tokens_come_out_not_correct():
    toy = "swiglu_rms_tied"
    vocab = TOYS[toy][0]["vocab_size"]

    def alter(gw):
        emit = gw._emit

        def altered(req, tok=None, logits_row=None):
            if tok is not None and len(req.out_tokens) % 3 == 1:
                tok = (tok + 1) % vocab
            emit(req, tok=tok, logits_row=logits_row)

        gw._emit = altered

    out = _run(toy, 3_000_000_001, on_gateway=alter)
    assert out["correct"] is False
    assert out["checks"]["logit_gap"]["value"] > LIMIT


def test_mla_moe_toy_is_served_from_a_config_dict_alone():
    """Weights from the seed for every leaf kind (expert stacks, router,
    latent projections and norm), the gateway's warm-up, and requests of
    two tiers through the program's MoE path.  There is no MoE reference
    yet, so this toy stops before the comparison."""
    cfg, mix = MLA_MOE
    seed = 3_000_000_001
    spec = {"cell": {"name": "mla_moe", "chips": 1, "traffic": "mla_moe"},
            "config": cfg}
    su = harness.set_up("mla_moe", seed, require_chip=False, spec=spec,
                        mix=mix, compile_cache=False, log=lambda s: None)
    assert su.mcfg.use_mla and su.mcfg.num_experts == 8
    assert su.info["warm"]["warm_requests"] > 0
    warm = su.counter.n
    gw = harness.make_gateway(su.mcfg, su.params, cfg)
    reqs = [(r, gw.submit(r.prompt, license=r.tier,
                          max_new_tokens=r.max_new_tokens))
            for r in traffic.generate(mix, seed, 1.0, cfg["vocab_size"])]
    gw.run()
    assert {r.tier for r, _ in reqs} == {"full", "pro"}
    for r, served in reqs:
        assert served.error is None
        assert len(served.out_tokens) == r.max_new_tokens
    assert su.counter.n == warm, su.counter.names[warm:]

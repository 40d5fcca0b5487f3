"""Operations and bytes from shapes, against hand counts at the
qwen2.5-3b widths and at DeepSeek-V2-Lite's (latent attention, a leading
dense layer, a share of the experts), and the peaks table."""
import json
from pathlib import Path

import pytest

from bench import costs

CFG = json.loads((Path(costs.__file__).parent / "configs"
                  / "qwen2.5-3b-int8.json").read_text())


def test_paged_attention_ops_and_bytes():
    # two live lanes of 1000 and 2000 keys, blocks of 256: 4 + 8 blocks
    # read; 16 heads x 128 dims, 2 KV heads
    ops, nbytes = costs.paged_attention_cost(CFG, [1000, 2000],
                                             block_size=256)
    assert ops == 4 * 16 * 128 * 3000                 # QK^T + PV, 2 flop/MAC
    kv = 12 * 256 * 2 * 128 * 2 * 2                   # blocks*rows*KH*hd*bf16*(K,V)
    qo = 2 * 16 * 128 * (2 + 4)                       # bf16 q in, f32 out
    assert nbytes == kv + qo == 3_170_304


def test_model_flops_per_token():
    per_layer = 2048 * 128 * (2 * 16 + 2 * 2) + 3 * 2048 * 11008
    assert per_layer == 77_070_336
    assert all(costs.matmul_params(CFG, i) == per_layer for i in range(36))
    body = 2 * per_layer * 36
    assert costs.token_flops(CFG, 1, logits=False) == body + 4 * 16 * 128 * 36
    head = 2 * 2048 * 151936
    assert costs.token_flops(CFG, 4096, logits=True) == \
        body + 4 * 16 * 128 * 4096 * 36 + head
    # a chunk of 512 tokens after 1024 cached ones: keys 1025..1536
    keys = sum(range(1025, 1537))
    assert costs.chunk_flops(CFG, 1024, 512, last=True) == \
        512 * body + 4 * 16 * 128 * 36 * keys + head


def test_peaks_table_is_keyed_by_device_kind():
    pk = costs.peaks("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12
    assert pk["hbm_bytes_per_s"] == 819e9
    assert "source" in pk
    with pytest.raises(KeyError):
        costs.peaks("cpu")
    assert costs.roofline_seconds(197e12, 1, pk) == pytest.approx(1.0)
    assert costs.roofline_seconds(1, 819e9, pk) == pytest.approx(1.0)


def test_deepseek_v2_lite_token_with_16_of_64_experts_held():
    from bench.tests.test_bench_configmap import DEEPSEEK_V2_LITE

    cfg = dict(DEEPSEEK_V2_LITE, n_routed_experts=16,
               published={"n_routed_experts": 64})
    d = 2048
    # MLA, published form: q from h (no q_lora), latent + rotary key down,
    # latent up to 16 heads' keys (128) and values (128), output
    mla = d * 16 * 192 + d * (512 + 64) + 512 * 16 * (128 + 128) \
        + 16 * 128 * d
    assert mla == 13_762_560
    dense = 3 * d * 10944                             # layer 0
    expert = 3 * d * 1408
    # router over all 64, 6 routed x 16/64 held here, 2 shared
    moe = d * 64 + 6 * 16 * expert // 64 + 2 * expert
    assert moe == 131_072 + 12_976_128 + 17_301_504
    assert costs.matmul_params(cfg, 0) == mla + dense
    assert costs.matmul_params(cfg, 1) == costs.matmul_params(cfg, 26) \
        == mla + moe
    body = 2 * (27 * mla + dense + 26 * moe)
    assert body == 2_458_910_720
    attn = 2 * 16 * (192 + 128) * 27                  # per key
    head = 2 * d * 102400
    assert costs.token_flops(cfg, 1000, logits=True) == \
        body + attn * 1000 + head
    keys = sum(range(1, 257))
    assert costs.chunk_flops(cfg, 0, 256, last=False) == \
        256 * body + attn * keys
    # the whole layer held: 6 routed experts a token
    whole = dict(DEEPSEEK_V2_LITE)
    assert costs.matmul_params(whole, 1) == \
        mla + d * 64 + 6 * expert + 2 * expert

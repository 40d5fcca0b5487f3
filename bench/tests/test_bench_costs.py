"""Operations and bytes from shapes, against hand counts at the
qwen2.5-3b widths, and the peaks table."""
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
    assert costs.matmul_params(CFG) == per_layer
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

"""Compile the serving path's Pallas kernels for a TPU v5e at qwen2.5-3b widths.

Nothing runs: every test lowers and compiles against a described, not
attached, ``v5e:2x2`` topology, so what the chip's compiler refuses
(tiling, VMEM) fails here at no chip time.  Interpret mode checks the
kernel bodies (tests/test_kernels.py, tests/test_paged_attention.py); it
cannot see these refusals.

Only one process at a time may load the TPU library, so the topology is
described inside a module fixture, never while a module is imported.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import delta_apply as da
from repro.kernels import masked_dequant as md
from repro.kernels import paged_attention as pa

CFG = get_config("qwen2.5-3b")
HEADS, KV_HEADS, HEAD_DIM = CFG.num_heads, CFG.num_kv_heads, CFG.head_dim
LANES = 4
BLOCKS = 128        # physical blocks in the pool
TABLE = 18          # blocks per lane: (256 prompt + 32 new) / 16


@pytest.fixture(scope="module")
def spec():
    """Shape specs placed on one chip of a described v5e:2x2."""
    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        try:
            from jax.experimental import topologies

            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        chip = SingleDeviceSharding(topo.devices[0])
        yield lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        mp.undo()


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()   # the Pallas kernel is in
    return compiled


@pytest.mark.parametrize("block_size", [16, 32])
def test_paged_attention_compiles(spec, block_size):
    kv = spec((BLOCKS, block_size, KV_HEADS, HEAD_DIM), jnp.bfloat16)
    _compile(pa.paged_attention, spec((LANES, HEADS, HEAD_DIM), jnp.bfloat16), kv, kv,
             spec((LANES, TABLE), jnp.int32), spec((LANES,), jnp.int32))


def test_paged_decode_write_compiles(spec):
    kv = spec((BLOCKS, 16, KV_HEADS, HEAD_DIM), jnp.bfloat16)
    tok = spec((LANES, KV_HEADS, HEAD_DIM), jnp.bfloat16)
    lane = spec((LANES,), jnp.int32)
    _compile(pa.paged_decode_write, kv, kv, tok, tok, lane, lane)


def test_masked_dequant_compiles(spec):
    cols = -(-CFG.d_ff // 256) * 256          # ops.masked_dequant pads to 256
    iv = spec((md.MAX_INTERVALS,), jnp.float32)
    _compile(lambda c, sc, lo, hi: md.masked_dequant(c, sc, lo, hi,
                                                     out_dtype=jnp.bfloat16),
             spec((CFG.d_model, cols), jnp.int8), spec((1, cols), jnp.float32),
             iv, iv)


# a rows-mode layer (WeightStore row_limit) receiving one bounded stager
# part: 256 KiB of (int64 index, f32 value) rows is 21845 of them
@pytest.mark.parametrize("kernel,n_delta", [
    (da.delta_apply, 4096),
    (da.delta_apply_inplace, 21845),
])
def test_delta_apply_compiles(spec, kernel, n_delta):
    _compile(kernel, spec((262_144,), jnp.bfloat16), spec((n_delta,), jnp.int32),
             spec((n_delta,), jnp.bfloat16))


# the licensed matmul at every qwen2.5-3b unit matrix, stacked over its 36
# units and read in place: decode rows (8) and a full 256-row chunk of
# 8 lanes (2048), with one masked interval and unmasked
@pytest.mark.parametrize("rows", [8, 2048])
@pytest.mark.parametrize("k,n", [
    (CFG.d_model, HEADS * HEAD_DIM),          # wq, wo
    (CFG.d_model, KV_HEADS * HEAD_DIM),       # wk, wv
    (CFG.d_model, CFG.d_ff),                  # w_gate, w_up
    (CFG.d_ff, CFG.d_model),                  # w_down
])
@pytest.mark.parametrize("masked", [True, False])
def test_licensed_matmul_compiles(spec, rows, k, n, masked):
    from repro.kernels import ops

    dead = (spec((1, n), jnp.int32),) * 2 if masked else None
    _compile(lambda x, c, s, d, u: ops.licensed_matmul(x, c, s, d, u),
             spec((rows, k), jnp.bfloat16),
             spec((CFG.num_layers, k, n), jnp.int8),
             spec((1, n), jnp.float32), dead, spec((), jnp.int32))

"""Per-kernel allclose vs the pure-jnp oracles, swept over shapes/dtypes.

All Pallas bodies execute via interpret=True on CPU (the kernel *body* is
what is validated; compiled TPU lowering is exercised by the dry-run).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # property tests skip; plain tests still run
    from _hypothesis_compat import given, settings, st

from repro.kernels import ops, ref

jax.config.update("jax_enable_x64", False)


def rng(seed=0):
    return np.random.default_rng(seed)


# --------------------------------------------------------------- quant_matmul
@pytest.mark.parametrize("m,k,n", [(128, 512, 128), (256, 512, 256), (128, 1024, 384), (8, 512, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quant_matmul_matches_ref(m, k, n, dtype):
    r = rng(m * 7 + n)
    x = jnp.asarray(r.standard_normal((m, k)), dtype=dtype)
    codes = jnp.asarray(r.integers(-127, 128, (k, n)), dtype=jnp.int8)
    scale = jnp.asarray(np.abs(r.standard_normal(n)) * 0.02 + 1e-4, dtype=jnp.float32)
    got = ops.quant_matmul(x, codes, scale, out_dtype=jnp.float32, interpret=True)
    want = ref.quant_matmul(x, codes, scale, jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-2, atol=2e-2)


def test_quant_matmul_unaligned_shapes_pad():
    r = rng(3)
    x = jnp.asarray(r.standard_normal((130, 700)), dtype=jnp.float32)
    codes = jnp.asarray(r.integers(-127, 128, (700, 200)), dtype=jnp.int8)
    scale = jnp.asarray(np.abs(r.standard_normal(200)) + 0.01, dtype=jnp.float32)
    got = ops.quant_matmul(x, codes, scale, interpret=True)
    want = ref.quant_matmul(x, codes, scale, jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-2, atol=1e-2)


def test_quant_matmul_batched_leading_dims():
    r = rng(5)
    x = jnp.asarray(r.standard_normal((4, 64, 512)), dtype=jnp.float32)
    codes = jnp.asarray(r.integers(-127, 128, (512, 128)), dtype=jnp.int8)
    scale = jnp.ones(128, jnp.float32) * 0.02
    got = ops.quant_matmul(x, codes, scale, interpret=True)
    assert got.shape == (4, 64, 128)
    want = ref.quant_matmul(x.reshape(-1, 512), codes, scale, jnp.float32).reshape(4, 64, 128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-3, atol=1e-3)


# ------------------------------------------------------------- masked_dequant
@pytest.mark.parametrize("r_,c", [(256, 256), (512, 768), (300, 200), (64, 64)])
@pytest.mark.parametrize("out_dtype", [jnp.float32, jnp.bfloat16])
def test_masked_dequant_matches_ref(r_, c, out_dtype):
    r = rng(r_ + c)
    codes = jnp.asarray(r.integers(-127, 128, (r_, c)), dtype=jnp.int8)
    scale = jnp.asarray(np.abs(r.standard_normal((1, c))) * 0.02 + 1e-3, dtype=jnp.float32)
    lo, hi = ops.pack_intervals([(0.5, 0.8), (1.2, 1.5)])
    got = ops.masked_dequant(codes, scale, [(0.5, 0.8), (1.2, 1.5)],
                             out_dtype=out_dtype, interpret=True)
    want = ref.masked_dequant(codes, jnp.broadcast_to(scale, codes.shape), lo, hi, out_dtype)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=1e-2, atol=1e-2
    )


def test_masked_dequant_no_intervals_is_plain_dequant():
    r = rng(11)
    codes = jnp.asarray(r.integers(-127, 128, (256, 256)), dtype=jnp.int8)
    scale = jnp.full((1, 256), 0.01, jnp.float32)
    got = ops.masked_dequant(codes, scale, [], interpret=True)
    want = codes.astype(jnp.float32) * scale
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6)


def test_masked_dequant_row_scale():
    r = rng(13)
    codes = jnp.asarray(r.integers(-127, 128, (512, 256)), dtype=jnp.int8)
    scale = jnp.asarray(np.abs(r.standard_normal((512, 1))) * 0.02 + 1e-3, jnp.float32)
    got = ops.masked_dequant(codes, scale, [(0.3, 0.6)], interpret=True)
    lo, hi = ops.pack_intervals([(0.3, 0.6)])
    want = ref.masked_dequant(codes, jnp.broadcast_to(scale, codes.shape), lo, hi, jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_masked_dequant_zeroes_exactly_the_interval():
    codes = jnp.asarray(np.arange(-127, 129).reshape(1, -1).repeat(256, 0), dtype=jnp.int8)
    scale = jnp.full((1, 256), 0.01, jnp.float32)
    out = np.asarray(ops.masked_dequant(codes, scale, [(0.5, 0.8)], interpret=True))
    mag = np.abs(np.asarray(codes, np.float32) * 0.01)
    assert (out[(mag >= 0.5) & (mag < 0.8)] == 0).all()
    live = (mag < 0.5) | (mag >= 0.8)
    np.testing.assert_allclose(out[live], (np.asarray(codes, np.float32) * 0.01)[live])


# ---------------------------------------------------------------- delta_apply
@pytest.mark.parametrize("n,k", [(8192, 100), (4096, 1), (16384, 997), (100, 10)])
def test_delta_apply_matches_ref(n, k):
    r = rng(n + k)
    buf = jnp.asarray(r.standard_normal(n), dtype=jnp.float32)
    idx = jnp.asarray(r.choice(n, size=k, replace=False), dtype=jnp.int32)
    vals = jnp.asarray(r.standard_normal(k), dtype=jnp.float32)
    got = ops.delta_apply(buf, idx, vals, interpret=True)
    want = ref.delta_apply(buf, idx, vals)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=0)


@pytest.mark.parametrize("n,k,dtype,donate", [
    (10000, 1500, jnp.float32, False),     # ragged buffer and delta chunks
    (12288, 4096, jnp.bfloat16, True),     # in-place, eight full chunks
])
def test_delta_apply_spans_delta_chunks(n, k, dtype, donate):
    """Deltas past one DELTA_CHUNK (the old per-call VMEM bound) run
    through the kernel's inner chunk axis, not the oracle."""
    from repro.kernels.delta_apply import DELTA_CHUNK

    assert k > DELTA_CHUNK
    r = rng(n ^ k)
    buf = jnp.asarray(r.standard_normal(n), dtype=dtype)
    idx = jnp.asarray(r.choice(n, size=k, replace=False), dtype=jnp.int32)
    vals = jnp.asarray(r.standard_normal(k), dtype=dtype)
    want = ref.delta_apply(buf, idx, vals)
    got = ops.delta_apply(buf, idx, vals, interpret=True, donate=donate)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_delta_apply_bf16_buffer():
    r = rng(77)
    buf = jnp.asarray(r.standard_normal(8192), dtype=jnp.bfloat16)
    idx = jnp.asarray(r.choice(8192, size=64, replace=False), dtype=jnp.int32)
    vals = jnp.asarray(r.standard_normal(64), dtype=jnp.bfloat16)
    got = ops.delta_apply(buf, idx, vals, interpret=True)
    want = ref.delta_apply(buf, idx, vals)
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))


@settings(max_examples=25, deadline=None)
@given(
    n=st.sampled_from([4096, 8192]),
    k=st.integers(min_value=1, max_value=256),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
def test_delta_apply_property(n, k, seed):
    """Property: after apply, buf[idx]==vals and everything else unchanged."""
    r = rng(seed)
    buf = jnp.asarray(r.standard_normal(n), dtype=jnp.float32)
    idx_np = r.choice(n, size=k, replace=False)
    vals = jnp.asarray(r.standard_normal(k), dtype=jnp.float32)
    out = np.asarray(ops.delta_apply(buf, jnp.asarray(idx_np, jnp.int32), vals, interpret=True))
    np.testing.assert_array_equal(out[idx_np], np.asarray(vals))
    mask = np.ones(n, bool)
    mask[idx_np] = False
    np.testing.assert_array_equal(out[mask], np.asarray(buf)[mask])


@settings(max_examples=15, deadline=None)
@given(
    m=st.sampled_from([8, 64, 128]),
    k=st.sampled_from([512, 1024]),
    n=st.sampled_from([128, 256]),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
def test_quant_matmul_property(m, k, n, seed):
    r = rng(seed)
    x = jnp.asarray(r.standard_normal((m, k)), dtype=jnp.float32)
    codes = jnp.asarray(r.integers(-127, 128, (k, n)), dtype=jnp.int8)
    scale = jnp.asarray(np.abs(r.standard_normal(n)) * 0.05 + 1e-4, jnp.float32)
    got = ops.quant_matmul(x, codes, scale, interpret=True)
    want = ref.quant_matmul(x, codes, scale, jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-3, atol=1e-3)

"""Quantized licensed serving (serving/quantized.py) + hlo_cost model."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_variant
from repro.core.licensing import LicenseTier, apply_license
from repro.models import forward, init_cache, init_params
from repro.kernels.quant_matmul import dead_codes
from repro.models.int8 import (
    code_bounds,
    dense,
    dequant_leaf,
    dequant_tree,
    is_qleaf,
    serve_block,
)
from repro.serving.quantized import quantize_serving_params, tier_intervals


@pytest.fixture(scope="module")
def setup():
    key = jax.random.PRNGKey(0)
    cfg = smoke_variant(get_config("qwen2.5-3b"))
    params = init_params(key, cfg)
    toks = jax.random.randint(key, (2, 16), 0, cfg.vocab_size)
    return cfg, params, toks


def test_quantize_roundtrip_close(setup):
    cfg, params, toks = setup
    qp = quantize_serving_params(params)
    # structure preserved; eligible leaves became q-dicts
    q_leaves = [l for l in jax.tree_util.tree_leaves(
        qp, is_leaf=is_qleaf) if is_qleaf(l)]
    assert len(q_leaves) > 0
    for l in q_leaves:
        assert l["codes"].dtype == jnp.int8
    back = dequant_tree(qp, None, cfg.dtype)
    w0 = params["units"]["b0"]["mixer"]["wq"]
    w1 = back["units"]["b0"]["mixer"]["wq"]
    # per-channel int8: error bounded by half a step
    step = np.abs(np.asarray(w0, np.float32)).max(axis=-2, keepdims=True) / 127
    assert (np.abs(np.asarray(w1, np.float32) - np.asarray(w0, np.float32))
            <= step + 1e-6).all()


def test_quantized_forward_close_to_full(setup):
    cfg, params, toks = setup
    ref, _, _ = forward(params, cfg, toks)
    qout, _, _ = forward(quantize_serving_params(params), cfg, toks)
    corr = float(jnp.corrcoef(qout.reshape(-1), ref.reshape(-1))[0, 1])
    assert corr > 0.999


def test_fused_license_matches_mask_at_load(setup):
    """Fused in-scan masked-dequant == paper's mask-at-load on the same
    scope (the fused path licenses quantized BLOCK weights; embed/lm_head
    stay full — scope the oracle identically)."""
    from repro.serving.quantized import _eligible
    from repro.core.pytree_io import flatten_params

    cfg, params, toks = setup
    tier = LicenseTier(name="free", masks={"*": ((0.0, 0.003),)})
    qp = quantize_serving_params(params)
    deq = dequant_tree(qp, None, cfg.dtype)
    flat = flatten_params(params)

    def exclude(name):  # mask exactly what the fused path masks
        return not (name in flat and _eligible(name, flat[name]))

    masked_at_load = apply_license(deq, tier, exclude=exclude)
    ref, _, _ = forward(masked_at_load, cfg, toks)
    fused, _, _ = forward(qp, cfg, toks, license_intervals=tier_intervals(tier))
    np.testing.assert_allclose(np.asarray(fused), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)


def test_quantized_decode_consistency(setup):
    cfg, params, toks = setup
    qp = quantize_serving_params(params)
    li = tier_intervals(LicenseTier(name="f", masks={"*": ((0.0, 0.002),)}))
    ref, _, _ = forward(qp, cfg, toks, license_intervals=li)
    cache = init_cache(cfg, 2, 16)
    pre, _, cache = forward(qp, cfg, toks[:, :15], cache=cache, pos=0,
                            license_intervals=li)
    dec, _, _ = forward(qp, cfg, toks[:, 15:16], cache=cache, pos=15,
                        license_intervals=li)
    np.testing.assert_allclose(np.asarray(dec[:, 0]), np.asarray(ref[:, 15]),
                               rtol=2e-3, atol=2e-3)


def test_no_full_precision_weights_in_tree(setup):
    """Security property (§3.5): unlicensed full-precision weights never
    exist in a quantized serving tree."""
    cfg, params, _ = setup
    qp = quantize_serving_params(params)

    def check(path, leaf):
        if is_qleaf(leaf):
            return
        if hasattr(leaf, "ndim") and leaf.ndim >= 3 and not isinstance(leaf, dict):
            name = "/".join(str(getattr(p, "key", p)) for p in path)
            # only norms/biases/conv/embeds may remain float
            assert any(k in name for k in
                       ("norm", "bias", "conv", "bq", "bk", "bv", "A_log",
                        "dt_bias", "D_skip", "a_param")), name

    jax.tree_util.tree_map_with_path(check, qp, is_leaf=is_qleaf)


# ------------------------------------------------------- hlo_cost model
def test_hlo_cost_scan_equals_unrolled():
    from repro.launch import hlo_cost

    def body(x, w):
        return jnp.dot(x, w), ()

    def scanned(x, ws):
        y, _ = jax.lax.scan(body, x, ws)
        return jnp.sum(y)

    def unrolled(x, ws):
        for i in range(8):
            x = jnp.dot(x, ws[i])
        return jnp.sum(x)

    x = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    ws = jax.ShapeDtypeStruct((8, 256, 256), jnp.float32)
    cs = hlo_cost.analyze(jax.jit(scanned).lower(x, ws).compile().as_text())
    cu = hlo_cost.analyze(jax.jit(unrolled).lower(x, ws).compile().as_text())
    expect = 2.0 * 8 * 256**3
    assert cs.flops == cu.flops == expect


def test_hlo_cost_counts_nested_scans():
    from repro.launch import hlo_cost

    def inner(x, w):
        return jnp.dot(x, w), ()

    def outer(x, ws):
        def step(c, _):
            y, _ = jax.lax.scan(inner, c, ws)
            return y, ()
        y, _ = jax.lax.scan(step, x, None, length=3)
        return jnp.sum(y)

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((4, 128, 128), jnp.float32)
    c = hlo_cost.analyze(jax.jit(outer).lower(x, ws).compile().as_text())
    assert c.flops == 2.0 * 3 * 4 * 128**3


# ------------------------------------------- code-domain mask (exactness)
def _f32_dead(codes, scale, intervals):
    """Today's eager test: f32 ``lo <= |c * s| < hi`` for any interval."""
    mag = np.abs(codes.astype(np.float32) * scale)
    dead = np.zeros(mag.shape, bool)
    for lo, hi in intervals:
        dead |= (mag >= np.float32(lo)) & (mag < np.float32(hi))
    return dead


def _code_dead(codes, scale, li):
    if li is None:
        return np.zeros(np.broadcast_shapes(codes.shape, scale.shape), bool)
    a, b = (d.reshape(d.shape[0], -1)
            for d in code_bounds(jnp.asarray(scale), *li))
    return np.asarray(dead_codes(jnp.asarray(codes), a, b))


_COLS = 512
_SCALE = (np.exp(np.random.default_rng(7).normal(-8.0, 1.0, (1, _COLS)))
          .astype(np.float32))
_ON = [float(np.float32(k) * _SCALE[0, j])        # bounds equal to fl(k * s)
       for k, j in ((1, 0), (3, 5), (17, 9), (64, 11), (127, 13))]


@pytest.mark.parametrize("intervals", [
    [(0.0, 4e-3)],                                         # pro-like
    [(0.0, 1e-2)],                                         # free-like
    [(_ON[0], _ON[2])],                                    # both bounds on products
    [(_ON[1], _ON[3]), (_ON[2], _ON[4])],                  # overlapping, on products
    [(1e-4, 3e-4), (3e-4, 9e-4)],                          # adjacent
    [(5e-3, 2e-2), (1e-4, 6e-3), (0.0, 0.0), (0.0, 0.0)],  # overlap + padding
    [(0.0, 0.0)] * 8,                                      # padding only
    [(3e-3, 1e-3), (2e-4, 2e-4)],                          # empty
    [(1e-3, 1e30)],                                        # past every code
], ids=["pro", "free", "on-products", "overlap", "adjacent", "padded",
        "padding-only", "empty", "unbounded"])
def test_code_range_mask_equals_f32_interval_mask(intervals):
    """The code-range mask kills exactly the weights the f32 interval test
    kills, for every code and every column, after the tier's intervals are
    merged and pruned."""
    codes = np.arange(-127, 128, dtype=np.int8)[:, None]
    li = tier_intervals(LicenseTier(name="t", masks={"*": tuple(intervals)}))
    want = _f32_dead(codes, _SCALE, intervals)
    np.testing.assert_array_equal(_code_dead(codes, _SCALE, li), want)
    if not want.any():
        assert li is None                 # nothing to mask: the unmasked program


def test_code_range_mask_full_tier():
    assert tier_intervals(None) is None
    assert tier_intervals(LicenseTier(name="full", masks={})) is None


def test_live_intervals_merge_and_prune():
    from repro.serving.quantized import live_intervals

    assert live_intervals([(0.5, 0.8), (0.0, 0.1), (0.1, 0.2), (0.7, 0.9),
                           (0.3, 0.3), (0.6, 0.4)]) == [
        (0.0, float(np.float32(0.2))),
        (0.5, float(np.float32(0.9)))]
    lo, hi = tier_intervals(LicenseTier(name="t", masks={
        "*": ((0.0, 0.004), (0.001, 0.002), (0.0, 0.0))}))
    assert lo.shape == hi.shape == (1,)   # one live interval: the pro/free program


def test_dense_matches_masked_dequant():
    """``dense`` on an int8 leaf == einsum against today's eager masked
    dequant (f32 activations, so only the order of the scale differs)."""
    r = np.random.default_rng(3)
    x = jnp.asarray(r.standard_normal((2, 5, 64)), jnp.float32)
    leaf = {"codes": jnp.asarray(r.integers(-127, 128, (64, 96)), jnp.int8),
            "scale": jnp.asarray(r.uniform(1e-3, 3e-3, (1, 96)), jnp.float32)}
    for li in (None, tier_intervals(LicenseTier(name="t", masks={
            "*": ((0.0, 0.05), (0.1, 0.12))}))):
        got = dense(x, serve_block({"ffn": {"w": leaf}}, ("ffn",), li,
                                   jnp.float32)["ffn"]["w"])
        lo, hi = (None, None) if li is None else li
        want = jnp.einsum("bsk,kn->bsn", x,
                          dequant_leaf(leaf, lo, hi, jnp.float32))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


# ------------------------------------ routed forward == eager dequant forward
_TIERS = {"full": None, "pro": ((0.0, 0.02),), "free": ((0.0, 0.05),)}


@pytest.fixture(scope="module")
def served():
    """bf16 qwen2.5 smoke model in its int8 store (the cell's serving
    dtype), with each tier's interval arrays."""
    import dataclasses

    cfg = dataclasses.replace(smoke_variant(get_config("qwen2.5-3b")),
                              dtype_name="bfloat16")
    qp = quantize_serving_params(init_params(jax.random.PRNGKey(1), cfg))
    lis = {t: (None if ivs is None else tier_intervals(
        LicenseTier(name=t, masks={"*": ivs}))) for t, ivs in _TIERS.items()}
    return cfg, qp, lis


def _eager(qp, li, cfg):
    """The pre-routing form: every int8 leaf dequantized and masked up
    front, so every matmul runs on a float weight."""
    return dequant_tree(qp, li, cfg.dtype)


def _close(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    scale = np.abs(want).max()
    # bf16: the routed form rounds once per matmul output, the eager one
    # also rounds c * s to bf16 first
    assert np.abs(got - want).max() <= 0.05 * scale, (
        np.abs(got - want).max(), scale)


def _paged_case(cfg, seed=0):
    from repro.models.model import init_cache as _ic

    b, bs, n_blocks, t = 2, 8, 6, 3
    r = np.random.default_rng(seed)
    kv = (cfg.pattern_units, 1, n_blocks + 1, bs, cfg.num_kv_heads, cfg.head_dim)
    cache = {"units": {"b0": {
        "k": jnp.asarray(r.standard_normal(kv), cfg.dtype),
        "v": jnp.asarray(r.standard_normal(kv), cfg.dtype),
        "len": jnp.zeros((cfg.pattern_units, b), jnp.int32)}}}
    assert jax.tree_util.tree_structure(cache["units"]["b0"]) == \
        jax.tree_util.tree_structure(_ic(cfg, 1, 4)["units"]["b0"])
    tables = jnp.asarray([[0, 2, 4], [1, 3, 5]], jnp.int32)
    pos = jnp.asarray([9, 20], jnp.int32)
    toks = jnp.asarray(r.integers(0, cfg.vocab_size, (b, 1)), jnp.int32)
    return toks, cache, tables, pos


@pytest.mark.parametrize("tier", list(_TIERS))
@pytest.mark.parametrize("mode", ["paged_decode", "contiguous_decode",
                                  "chunked_prefill", "suffix_prefill"])
def test_routed_forward_matches_eager_dequant(served, tier, mode):
    """Each tier, each serving step: the int8-operand matmuls give the
    logits of the eager dequant-then-matmul form within bf16 rounding."""
    from repro.serving.engine import (prefill_chunk_step, prefill_suffix_step,
                                      serve_step, serve_step_paged,
                                      stack_lane_caches)

    cfg, qp, lis = served
    li = lis[tier]
    eager = _eager(qp, li, cfg)
    r = np.random.default_rng(11)
    toks = jnp.asarray(r.integers(0, cfg.vocab_size, (2, 12)), jnp.int32)
    if mode == "paged_decode":
        t1, cache, tables, pos = _paged_case(cfg)
        run = lambda p, l: serve_step_paged(p, cfg, t1, cache, tables, pos,
                                            license_intervals=l,
                                            kernel="interpret")[0]
    elif mode == "contiguous_decode":
        def run(p, l):
            cache = init_cache(cfg, 2, 16)
            _, _, cache = forward(p, cfg, toks[:, :11], cache=cache, pos=0,
                                  license_intervals=l)
            return serve_step(p, cfg, toks[:, 11:], cache, 11,
                              license_intervals=l)[0]
    elif mode == "chunked_prefill":
        def run(p, l):
            caches = stack_lane_caches(cfg, 2, 16)
            return prefill_chunk_step(
                p, cfg, toks[:, :8], caches, jnp.asarray([0, 0], jnp.int32),
                chunk_valid=jnp.asarray([8, 5], jnp.int32),
                license_intervals=l)[0]
    else:
        def run(p, l):
            cache = init_cache(cfg, 2, 16)
            _, _, cache = forward(p, cfg, toks[:, :7], cache=cache, pos=0,
                                  license_intervals=l)
            return prefill_suffix_step(p, cfg, toks[:, 7:], cache, 7,
                                       license_intervals=l)[0]
    got, want = run(qp, li), run(eager, None)
    _close(got, want)
    if tier != "full":
        # the mask matters at these intervals: unmasked logits are far off
        unmasked = np.asarray(run(_eager(qp, None, cfg), None), np.float32)
        assert np.abs(unmasked - np.asarray(want, np.float32)).max() > \
            3 * np.abs(np.asarray(got, np.float32) - np.asarray(want, np.float32)).max()


@pytest.mark.parametrize("name,share", [
    ("qwen2.5-3b", "one"), ("deepseek-moe-16b", "part"), ("mamba2-130m", "none")])
def test_eager_blocks_unchanged_and_fused_share(name, share):
    """MoE experts and SSM projections keep the eager dequant, giving what
    they gave before; the fused share reads 1.0 for a dense model, below
    1.0 with MoE experts and 0.0 with no routed matmul."""
    from repro.models.model import fused_weight_share

    cfg = smoke_variant(get_config(name))
    qp = quantize_serving_params(init_params(jax.random.PRNGKey(2), cfg))
    frac = fused_weight_share(qp, cfg)
    assert {"one": frac == 1.0, "part": 0.0 < frac < 1.0,
            "none": frac == 0.0}[share], frac
    assert fused_weight_share(init_params(jax.random.PRNGKey(2), cfg), cfg) == 0.0
    li = tier_intervals(LicenseTier(name="t", masks={"*": ((0.0, 0.02),)}))
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 8), 0, cfg.vocab_size)
    got, _, _ = forward(qp, cfg, toks, license_intervals=li)
    want, _, _ = forward(_eager(qp, li, cfg), cfg, toks)
    if share == "none":
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    else:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)

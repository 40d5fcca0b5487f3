"""Licensing (paper §3.5, Algorithm 1) + compression (§3.2) behaviour."""
import jax.numpy as jnp
import numpy as np

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # property tests skip; plain tests still run
    from _hypothesis_compat import given, settings, st

from repro.core import compression as comp
from repro.core.licensing import (
    FULL_TIER,
    LicenseTier,
    apply_license,
    calibrate_license,
    license_stats,
    mask_weight,
)


def mlp_params(seed=0):
    r = np.random.default_rng(seed)
    return {
        "layer1": {"kernel": r.standard_normal((32, 64)).astype(np.float32)},
        "layer2": {"kernel": r.standard_normal((64, 32)).astype(np.float32)},
        "out": {"kernel": r.standard_normal((32, 10)).astype(np.float32),
                 "norm": np.ones((10,), np.float32)},
    }


# ------------------------------------------------------------------- masking
def test_interval_mask_zeroes_only_the_band():
    w = jnp.asarray(np.linspace(-2, 2, 101), dtype=jnp.float32).reshape(1, -1)
    out = np.asarray(mask_weight(w, [(0.5, 0.8)]))
    mag = np.abs(np.asarray(w))
    assert (out[(mag >= 0.5) & (mag < 0.8)] == 0).all()
    keep = (mag < 0.5) | (mag >= 0.8)
    np.testing.assert_array_equal(out[keep], np.asarray(w)[keep])


def test_apply_license_full_tier_is_identity():
    p = mlp_params()
    out = apply_license(p, FULL_TIER)
    np.testing.assert_array_equal(out["layer1"]["kernel"], p["layer1"]["kernel"])


def test_apply_license_pattern_scoping():
    p = mlp_params()
    tier = LicenseTier(name="free", masks={"layer1": ((0.5, 0.8),)})
    out = apply_license(p, tier)
    w1 = np.asarray(out["layer1"]["kernel"])
    mag = np.abs(p["layer1"]["kernel"])
    assert (w1[(mag >= 0.5) & (mag < 0.8)] == 0).all()
    # other layers untouched
    np.testing.assert_array_equal(np.asarray(out["layer2"]["kernel"]), p["layer2"]["kernel"])


def test_apply_license_excludes_dynamics_params():
    p = mlp_params()
    tier = LicenseTier(name="free", masks={"*": ((0.0, 10.0),)})
    out = apply_license(p, tier)
    # norm params survive a mask that would zero everything
    np.testing.assert_array_equal(np.asarray(out["out"]["norm"]), p["out"]["norm"])
    assert (np.asarray(out["layer1"]["kernel"]) == 0).all()


def test_apply_license_keeps_leaves_on_device():
    """Masked leaves are computed where the weights live and equal the
    op-by-op mask; unmasked leaves pass through by reference."""
    p = {k: {n: jnp.asarray(a) for n, a in v.items()}
         for k, v in mlp_params(3).items()}
    tier = LicenseTier(name="free", masks={"layer": ((0.2, 0.9),)})
    out = apply_license(p, tier)
    assert out["out"]["kernel"] is p["out"]["kernel"]
    assert out["out"]["norm"] is p["out"]["norm"]
    for name in ("layer1", "layer2"):
        got = out[name]["kernel"]
        assert isinstance(got, type(p[name]["kernel"]))
        np.testing.assert_array_equal(
            np.asarray(got),
            np.asarray(mask_weight(p[name]["kernel"], [(0.2, 0.9)])))


def test_license_stats_counts_masked():
    p = mlp_params()
    tier = LicenseTier(name="free", masks={"layer1": ((0.0, 100.0),)})
    s = license_stats(p, tier)
    assert s["masked"] == 32 * 64
    assert 0 < s["masked_frac"] < 1


# --------------------------------------------------------------- Algorithm 1
def test_calibrate_license_hits_target():
    """Algorithm 1: eval = survival fraction; target 0.5 must be reachable."""
    p = mlp_params(3)

    def eval_fn(params):
        total = live = 0
        for layer in ("layer1", "layer2", "out"):
            k = np.asarray(params[layer]["kernel"])
            total += k.size
            live += int(np.count_nonzero(k))
        return live / total

    tier, trace = calibrate_license(p, eval_fn, target_accuracy=0.5, k_intervals=10)
    assert tier.accuracy is not None and tier.accuracy <= 0.52
    assert len(trace) >= 1
    assert tier.masks  # some interval was cut
    # applying the tier reproduces the calibration endpoint
    masked = apply_license(p, tier)
    assert abs(eval_fn(masked) - tier.accuracy) < 1e-6


def test_calibrate_trace_monotone_nonincreasing():
    p = mlp_params(4)

    def eval_fn(params):
        return float(np.mean([np.count_nonzero(np.asarray(params[l]["kernel"])) /
                              np.asarray(params[l]["kernel"]).size
                              for l in ("layer1", "layer2", "out")]))

    _, trace = calibrate_license(p, eval_fn, target_accuracy=0.3, k_intervals=8)
    accs = [s.accuracy for s in trace]
    assert all(a >= b - 1e-9 for a, b in zip(accs, accs[1:]))


# -------------------------------------------------------------- compression
def test_magnitude_prune_sparsity():
    r = np.random.default_rng(0)
    w = jnp.asarray(r.standard_normal((64, 64)), dtype=jnp.float32)
    pruned = comp.magnitude_prune(w, 0.8)
    sparsity = 1 - np.count_nonzero(np.asarray(pruned)) / w.size
    assert abs(sparsity - 0.8) < 0.02
    # surviving weights unchanged
    nz = np.asarray(pruned) != 0
    np.testing.assert_array_equal(np.asarray(pruned)[nz], np.asarray(w)[nz])


def test_quantize_dequantize_error_bounded():
    r = np.random.default_rng(1)
    w = jnp.asarray(r.standard_normal((32, 128)), dtype=jnp.float32)
    q = comp.quantize_int8(w)
    back = comp.dequantize(q)
    # max error is half a quantization step per channel
    step = np.asarray(q.scale).reshape(-1, 1)
    err = np.abs(np.asarray(back) - np.asarray(w))
    assert (err <= step * 0.5 + 1e-7).all()


def test_weight_share_reduces_alphabet():
    r = np.random.default_rng(2)
    w = jnp.asarray(r.standard_normal((64, 64)), dtype=jnp.float32)
    s = comp.weight_share(w, k=16)
    back = comp.unshare(s)
    assert len(np.unique(np.asarray(back))) <= 16
    assert np.abs(np.asarray(back) - np.asarray(w)).mean() < 0.2


def test_compress_pipeline_stats_ordering():
    p = mlp_params(5)
    pruned, quant, stats = comp.compress_pipeline(p, sparsity=0.8)
    # Table 1 ordering: full > pruned > quantized
    assert stats.full_bytes > stats.pruned_bytes > stats.quantized_bytes
    assert 0.7 < stats.sparsity < 0.9


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), lo=st.floats(0.0, 1.0), width=st.floats(0.01, 1.0))
def test_mask_idempotent_property(seed, lo, width):
    """Masking twice == masking once (idempotence of interval pruning)."""
    r = np.random.default_rng(seed)
    w = jnp.asarray(r.standard_normal((16, 16)), dtype=jnp.float32)
    ivs = [(lo, lo + width)]
    once = mask_weight(w, ivs)
    twice = mask_weight(once, ivs)
    np.testing.assert_array_equal(np.asarray(once), np.asarray(twice))


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), sparsity=st.floats(0.1, 0.95))
def test_prune_then_store_roundtrip_property(seed, sparsity):
    """Pruned params survive a WeightStore round trip exactly."""
    from repro.core.weightstore import WeightStore

    r = np.random.default_rng(seed)
    p = {"k": r.standard_normal((16, 16)).astype(np.float32)}
    pruned = {"k": np.asarray(comp.magnitude_prune(jnp.asarray(p["k"]), sparsity))}
    s = WeightStore(":memory:")
    s.register_model("m", "t")
    v = s.commit("m", pruned)
    out = s.checkout("m", v)
    np.testing.assert_allclose(out["k"], pruned["k"], rtol=1e-6)
    s.close()


def test_calibrate_refinement_tightens_target():
    """Beyond paper: bisecting the final interval lands closer to target."""
    p = mlp_params(9)

    def eval_fn(params):
        total = live = 0
        for layer in ("layer1", "layer2", "out"):
            k = np.asarray(params[layer]["kernel"])
            total += k.size
            live += int(np.count_nonzero(k))
        return live / total

    target = 0.55
    coarse, _ = calibrate_license(p, eval_fn, target, k_intervals=6)
    fine, _ = calibrate_license(p, eval_fn, target, k_intervals=6, refine_steps=8)
    assert abs(fine.accuracy - target) <= abs(coarse.accuracy - target) + 1e-9
    assert abs(fine.accuracy - target) < 0.05

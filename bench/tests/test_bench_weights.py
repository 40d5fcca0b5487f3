"""Seeded weights: the toy stores of the dense cells are bit for bit what
they were before expert stacks were added, a latent-attention expert
layout builds, and every (layer, expert) matrix regenerates alone, as a
reference regenerates it, bit-identical to the store."""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, weights
from bench.tests.test_bench_rehearsal import MLA_MOE, TOYS

SEED = 3_000_000_001

# sha256 of every leaf (name, dtype, bytes) of each toy's store at SEED,
# taken from `weights.build` before it took expert stacks
STORES = {
    "relu2_layernorm_untied":
        "3e8297dffbd08f7228e04f4e2806c8685a8521e47cfe452d5ba5fb005eed072c",
    "swiglu_rms_tied":
        "93601edc7efebf5edf103c1b5c7a5330bbc96d3bdb73b5c3c906462437a2c19d",
}


def _store(cfg):
    layout = harness.served_layout(harness.model_config(cfg))
    return layout, weights.build(layout, SEED,
                                 tied=bool(cfg["tie_word_embeddings"]))


def _flat(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {weights._path(p): np.asarray(x) for p, x in flat}


@pytest.mark.parametrize("toy", sorted(STORES))
def test_dense_toy_store_is_unchanged(toy):
    _, params = _store(TOYS[toy][0])
    h = hashlib.sha256()
    for name, a in _flat(params).items():
        h.update(name.encode())
        h.update(str(a.dtype).encode())
        h.update(a.tobytes())
    assert h.hexdigest() == STORES[toy]


@pytest.fixture(scope="module")
def mla_moe():
    layout, params = _store(MLA_MOE[0])
    return weights.layer_names(layout), _flat(params)


def test_mla_moe_store_has_every_leaf_kind(mla_moe):
    shapes, store = mla_moe
    assert {n: a.shape for n, a in store.items()} == shapes
    assert store["units/b0/ffn/experts/w_gate/codes"].shape == (2, 8, 64, 32)
    assert store["units/b0/ffn/experts/w_gate/codes"].dtype == np.int8
    assert store["units/b0/ffn/router"].dtype == np.float32
    assert np.all(store["units/b0/mixer/ckv_norm"] == 1)
    # each expert of each layer is a matrix of its own
    c = store["units/b0/ffn/experts/w_up/codes"]
    assert not np.array_equal(c[0, 0], c[0, 1])
    assert not np.array_equal(c[0, 0], c[1, 0])
    r = store["units/b0/ffn/router"]
    assert abs(float(r.std()) - weights.ROUTER_STD) < 0.1 * weights.ROUTER_STD


def test_each_matrix_regenerates_alone(mla_moe):
    shapes, store = mla_moe
    key = weights.seed_key(SEED)
    for w in ("w_gate", "w_up", "w_down"):
        base = f"units/b0/ffn/experts/{w}"
        n_layers, n_experts = shapes[base + "/codes"][:2]
        for i in range(n_layers):
            for e in range(n_experts):
                c, s = weights.qmatrix(key, base, (i, e),
                                       shapes[base + "/codes"][-2:])
                assert np.array_equal(c, store[base + "/codes"][i, e])
                assert np.array_equal(s, store[base + "/scale"][i, e])
    for base in ("units/b0/mixer/w_ukv", "units/b0/ffn/shared/w_down"):
        for i in range(shapes[base + "/codes"][0]):
            # a layer alone, as the dense reference asks for it
            c, s = weights.qmatrix(key, base, i, shapes[base + "/codes"][-2:])
            assert np.array_equal(c, store[base + "/codes"][i])
            assert np.array_equal(s, store[base + "/scale"][i])
    name = "units/b0/ffn/router"
    for i in range(shapes[name][0]):
        r = weights.router(key, name, i, shapes[name][-2:], jnp.float32)
        assert np.array_equal(r, store[name][i])


def test_an_unknown_leaf_is_refused():
    shapes = {"units": {"b0": {"mixer": {"mystery": jax.ShapeDtypeStruct(
        (2, 8, 8), jnp.float32)}}}}
    with pytest.raises(ValueError, match="mystery"):
        weights.build(shapes, SEED, tied=False)

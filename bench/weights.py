"""Seeded weights, made on the device in the type they are served in.

Every value is an exact function of (seed, leaf name, layer): integer
codes come from threefry bits through integer arithmetic only, and each
float is one rounding of an integer times a constant.  So the harness
(which builds the whole served tree in one jitted call) and the plain
reference (which regenerates one layer at a time, long after the served
tree is gone) see bit-identical weights without sharing any array.

Leaf kinds, by name:

* ``.../codes`` + ``.../scale``: an int8 matrix of the serving store,
  per-output-channel scales, dequantized as ``codes * scale`` with the
  standard deviation ``dense_init`` gives the float matrix
  (``1/sqrt(fan_in)``);
* ``embed/tok``, ``lm_head``: bf16, std 0.02 (``lm_head`` is ``tok.T``
  when the configuration ties them);
* attention biases ``bq``/``bk``/``bv``: bf16, std 0.02;
* norm scales: ones; norm biases: zeros (``init_norm``).
"""
from __future__ import annotations

import zlib
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# std of (sum of 4 uniform bytes - 510) // 4: sqrt(4 * (256**2 - 1) / 12) / 4
CODE_STD = 36.95
EMBED_STD = 0.02
BIAS_STD = 0.02


def seed_key(seed: int) -> jax.Array:
    """A threefry key from a seed of any size up to 63 bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def leaf_key(key: jax.Array, name: str) -> jax.Array:
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def codes(key: jax.Array, shape: Tuple[int, ...]) -> jax.Array:
    """int32 in [-127, 127], near-Gaussian with std ``CODE_STD``."""
    bits = jax.random.bits(key, shape, jnp.uint32)
    s = ((bits & 255) + ((bits >> 8) & 255) + ((bits >> 16) & 255)
         + (bits >> 24))
    return jnp.clip((s.astype(jnp.int32) - 510) // 4, -127, 127)


def channel_scale(key: jax.Array, fan_in: int, n_out: int) -> jax.Array:
    """(1, n_out) f32 scales: the matrix std times a per-channel factor in
    [0.75, 1.25) (an exact multiple of 2**-10), one rounding."""
    m = (768 + (jax.random.bits(key, (1, n_out), jnp.uint32) & 511)
         ).astype(jnp.float32) * np.float32(1.0 / 1024)
    return m * np.float32(1.0 / np.sqrt(fan_in) / CODE_STD)


def float_leaf(key: jax.Array, shape, std: float, dtype) -> jax.Array:
    return (codes(key, shape).astype(jnp.float32)
            * np.float32(std / CODE_STD)).astype(dtype)


def qmatrix(key: jax.Array, name: str, layer, shape: Tuple[int, int]
            ) -> Tuple[jax.Array, jax.Array]:
    """One layer's (codes int8 (in, out), scale f32 (1, out)) of the
    stacked int8 leaf ``name`` (its path without ``/codes``)."""
    k = jax.random.fold_in(leaf_key(key, name), layer)
    c = codes(jax.random.fold_in(k, 0), shape).astype(jnp.int8)
    s = channel_scale(jax.random.fold_in(k, 1), shape[0], shape[1])
    return c, s


def dequant(c: jax.Array, s: jax.Array) -> jax.Array:
    """f32 value of a served int8 matrix, as the serving store defines it."""
    return c.astype(jnp.float32) * s


def bias(key: jax.Array, name: str, layer, n: int, dtype) -> jax.Array:
    k = jax.random.fold_in(leaf_key(key, name), layer)
    return float_leaf(k, (n,), BIAS_STD, dtype)


def embedding(key: jax.Array, shape: Tuple[int, int], dtype) -> jax.Array:
    return float_leaf(leaf_key(key, "embed/tok"), shape, EMBED_STD, dtype)


def head(key: jax.Array, shape: Tuple[int, int], tied: bool, dtype
         ) -> jax.Array:
    if tied:
        return embedding(key, (shape[1], shape[0]), dtype).T
    return float_leaf(leaf_key(key, "lm_head"), shape, EMBED_STD, dtype)


def _path(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def build(shapes: Any, seed: int, *, tied: bool) -> Any:
    """The whole served tree for the shape tree ``shapes`` (leaves with
    ``.shape``/``.dtype``), built on the default device in ONE jitted
    call.  Stacked unit leaves carry the layer axis first."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [_path(p) for p, _ in flat]
    specs = [(tuple(x.shape), jnp.dtype(x.dtype)) for _, x in flat]
    by_name = dict(zip(names, specs))

    def one(key, name, shape, dtype):
        short = name.split("/")
        if name == "embed/tok":
            return embedding(key, shape, dtype)
        if name == "lm_head":
            return head(key, shape, tied, dtype)
        if short[-1] == "norm_scale":
            return jnp.ones(shape, dtype)
        if short[-1] == "bias" and "norm" in short[-2]:
            return jnp.zeros(shape, dtype)
        stacked = short[0] == "units"
        n_layers = shape[0] if stacked else None
        if short[-1] in ("codes", "scale"):
            base = "/".join(short[:-1])
            cshape = by_name[base + "/codes"][0]
            mat = cshape[-2:]
            idx = 0 if short[-1] == "codes" else 1
            if stacked:
                return jax.vmap(lambda i: qmatrix(key, base, i, mat)[idx])(
                    jnp.arange(n_layers))
            return qmatrix(key, base, 0, mat)[idx]
        if short[-1] in ("bq", "bk", "bv"):
            if stacked:
                return jax.vmap(lambda i: bias(key, name, i, shape[-1],
                                               dtype))(jnp.arange(n_layers))
            return bias(key, name, 0, shape[-1], dtype)
        raise ValueError(f"no generator for weight leaf {name!r} {shape}")

    @jax.jit
    def make(key):
        return [one(key, n, s, d) for n, (s, d) in zip(names, specs)]

    leaves = make(seed_key(seed))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def layer_names(shapes: Any) -> Dict[str, Tuple[int, ...]]:
    """Flat name -> shape of a shape tree (for the reference's layout)."""
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
    return {_path(p): tuple(x.shape) for p, x in flat}

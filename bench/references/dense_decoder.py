"""Plain float32 reference of a dense pre-norm decoder (GQA attention with
RoPE, SwiGLU or squared-ReLU MLP, RMSNorm or LayerNorm), as the
configurations that name ``"reference": "dense_decoder"`` publish it.

Written from the published architecture, not from the program: it
imports nothing of ``repro``.  The weights it multiplies are regenerated
here, layer by layer, from the seed by ``bench/weights.py`` — the same
integer codes and scales the served int8 store holds — dequantized in
float32 and masked by the request's license tier (``lo <= |w| < hi`` is
zeroed).  Every matmul runs at ``highest`` precision.

``gaps`` runs one request's prompt plus its served tokens through the
whole model once and returns, over the positions that produced a served
token, the widest gap between the reference's best logit and the served
token's logit.  With ``control=True`` it also runs the same model with
every served matrix requantized to int4 (per output channel) and
returns the reference gap of the token the int4 model ranks first.
"""
from __future__ import annotations

import functools
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

MAX_INTERVALS = 4
Q_BLOCK = 512


def pack(intervals: Sequence[Sequence[float]]) -> Tuple[np.ndarray, ...]:
    lo = np.zeros(MAX_INTERVALS, np.float32)
    hi = np.zeros(MAX_INTERVALS, np.float32)
    for i, (a, b) in enumerate(list(intervals)[:MAX_INTERVALS]):
        lo[i], hi[i] = a, b
    return lo, hi


def _mask(w, lo, hi):
    mag = jnp.abs(w)
    dead = jnp.zeros(w.shape, bool)
    for i in range(MAX_INTERVALS):
        dead = dead | ((mag >= lo[i]) & (mag < hi[i]))
    return jnp.where(dead, 0.0, w)


def _int4(w):
    """Per-output-channel symmetric int4 of an (in, out) matrix."""
    amax = jnp.max(jnp.abs(w), axis=0, keepdims=True)
    s = jnp.where(amax > 0, amax / 7.0, 1.0)
    return jnp.clip(jnp.round(w / s), -7, 7) * s


def _norm(x, cfg):
    if cfg["norm"] == "layernorm":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + cfg["norm_eps"])
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + cfg["norm_eps"])


def _rope(x, pos, theta):
    """Rotate-half RoPE over the whole head: x (S, heads, hd)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    half = hd // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def _attention(q, k, v, n_kv):
    """Causal GQA: q (S, H, hd), k/v (S, KH, hd); head h reads kv head
    h // (H / KH).  Queries in blocks so scores stay (H, Q_BLOCK, S)."""
    s, h, hd = q.shape
    g = h // n_kv
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    kpos = jnp.arange(s)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.einsum("qhd,khd->hqk", qb, k) / np.sqrt(hd)
        sc = jnp.where(kpos[None, None, :] <= qpos[None, :, None], sc,
                       -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    out = jax.lax.map(block, jnp.arange(s // Q_BLOCK))
    return out.reshape(s, h, hd)


class Reference:
    """The reference for one configuration, seed and served layout."""

    def __init__(self, cfg: Dict, layout: Dict[str, Tuple[int, ...]],
                 seed: int):
        self.cfg = cfg
        self.layout = layout
        self.seed = int(seed)
        self.spec = {
            "d": cfg["hidden_size"], "h": cfg["num_attention_heads"],
            "kh": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
            "layers": cfg["num_hidden_layers"],
            "vocab": cfg["vocab_size"], "theta": float(cfg["rope_theta"]),
            "norm": "layernorm" if cfg.get("layer_norm_eps") else "rms",
            "norm_eps": float(cfg.get("layer_norm_eps")
                              or cfg["rms_norm_eps"]),
            "act": cfg["hidden_act"], "bias": bool(cfg["attention_bias"]),
            "tied": bool(cfg["tie_word_embeddings"]),
        }

    def _qnames(self):
        return sorted(n[:-len("/codes")] for n in self.layout
                      if n.startswith("units/") and n.endswith("/codes"))

    @functools.partial(jax.jit, static_argnums=(0, 7))
    def _run(self, key, tokens, targets, valid, lo, hi, control):
        c, L = self.spec, self.layout
        names = self._qnames()
        tok = W.embedding(key, L["embed/tok"], jnp.bfloat16)
        s = tokens.shape[0]
        pos = jnp.arange(s)
        x0 = tok[tokens].astype(jnp.float32)

        def weights(i):
            out = {}
            for n in names:
                cw, sw = W.qmatrix(key, n, i, L[n + "/codes"][-2:])
                w = W.dequant(cw, sw)
                out[n.split("/")[-1]] = (w, _int4(w) if control else None)
            for b in ("bq", "bk", "bv"):
                n = f"units/b0/mixer/{b}"
                if n in L:
                    out[b] = W.bias(key, n, i, L[n][-1],
                                    jnp.bfloat16).astype(jnp.float32)
            return out

        def block(x, w, which):
            def mat(name):
                m = w[name][which]
                return _mask(m, lo, hi)

            h = _norm(x, c)
            q = h @ mat("wq")
            k = h @ mat("wk")
            v = h @ mat("wv")
            if c["bias"]:
                q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
            q = _rope(q.reshape(s, c["h"], c["hd"]), pos, c["theta"])
            k = _rope(k.reshape(s, c["kh"], c["hd"]), pos, c["theta"])
            v = v.reshape(s, c["kh"], c["hd"])
            a = _attention(q, k, v, c["kh"]).reshape(s, -1)
            x = x + a @ mat("wo")
            h = _norm(x, c)
            if c["act"] == "silu":
                f = jax.nn.silu(h @ mat("w_gate")) * (h @ mat("w_up"))
            else:
                f = jnp.square(jax.nn.relu(h @ mat("w_up")))
            return x + f @ mat("w_down")

        def layer(i, xs):
            w = weights(i)
            x, xc = xs
            x = block(x, w, 0)
            if control:
                xc = block(xc, w, 1)
            return x, xc

        with jax.default_matmul_precision("highest"):
            x, xc = jax.lax.fori_loop(0, c["layers"], layer, (x0, x0))
            head = W.head(key, L["lm_head"], c["tied"], jnp.bfloat16)
            head = head[:, : c["vocab"]].astype(jnp.float32)
            x = _norm(x, c)
            xc = _norm(xc, c)

            def rows(i):
                sl = lambda a: jax.lax.dynamic_slice_in_dim(  # noqa: E731
                    a, i * Q_BLOCK, Q_BLOCK, 0)
                lg = sl(x) @ head
                best = jnp.max(lg, -1)
                got = jnp.take_along_axis(lg, sl(targets)[:, None], -1)[:, 0]
                gap = jnp.where(sl(valid), best - got, -jnp.inf)
                if control:
                    pick = jnp.argmax(sl(xc) @ head, -1)
                    cg = best - jnp.take_along_axis(lg, pick[:, None], -1)[:, 0]
                    cgap = jnp.where(sl(valid), cg, -jnp.inf)
                else:
                    cgap = jnp.full_like(gap, -jnp.inf)
                return jnp.max(gap), jnp.max(cgap)

            g, cg = jax.lax.map(rows, jnp.arange(s // Q_BLOCK))
        return jnp.max(g), jnp.max(cg)

    def gaps(self, prompt: Sequence[int], served: Sequence[int],
             intervals, *, control: bool = False) -> Tuple[float, float]:
        """(widest served-token gap, widest int4-control gap or -inf)."""
        seq = list(map(int, prompt)) + list(map(int, served))
        n_in = len(seq) - 1                     # last served token: target only
        s = -(-n_in // 1024) * 1024
        tokens = np.zeros(s, np.int32)
        tokens[:n_in] = seq[:-1]
        targets = np.zeros(s, np.int32)
        targets[:n_in] = seq[1:]
        valid = np.zeros(s, bool)
        valid[len(prompt) - 1: n_in] = True     # positions producing served
        lo, hi = pack(intervals)
        g, cg = self._run(W.seed_key(self.seed), jnp.asarray(tokens),
                          jnp.asarray(targets), jnp.asarray(valid),
                          jnp.asarray(lo), jnp.asarray(hi), bool(control))
        return float(g), float(cg)

    def __hash__(self):
        return id(self)

    def __eq__(self, other):
        return self is other

"""Plain float32 reference of a dense decoder (Granite-3.0 style), and the
benchmark's seeded weights for it.

The equations are Granite's (``GraniteForCausalLM``): token embedding
times ``embedding_multiplier``; per layer RMS norm, grouped-query attention
with rotate-half RoPE and scores scaled by ``attention_multiplier``, a
residual add scaled by ``residual_multiplier``, RMS norm, a SiLU-gated MLP
and a second scaled residual add; a final RMS norm and the tied embedding
as output head, divided by ``logits_scaling``.  The configuration file gives
the values as run.  Causal attention over the whole sequence, no cache, no
batching; one jitted call per layer, so that a 2048-token sequence fits at
full width.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from .common import (einsum, mm, padded, padded_positions, rms_norm,
                     vocab_rows)


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    d: int
    heads: int
    kv_heads: int
    ff: int
    vocab: int
    theta: float
    eps: float
    tied: bool
    emb_mult: float
    attn_mult: float
    res_mult: float
    logit_scale: float

    @property
    def head_dim(self) -> int:
        return self.d // self.heads

    @classmethod
    def of(cls, c: dict) -> "Dims":
        return cls(layers=c["num_hidden_layers"], d=c["hidden_size"],
                   heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"],
                   ff=c["intermediate_size"], vocab=vocab_rows(c),
                   theta=float(c["rope_theta"]), eps=float(c["rms_norm_eps"]),
                   tied=bool(c["tie_word_embeddings"]),
                   emb_mult=float(c["embedding_multiplier"]),
                   attn_mult=float(c["attention_multiplier"]),
                   res_mult=float(c["residual_multiplier"]),
                   logit_scale=float(c["logits_scaling"]))


@functools.partial(jax.jit, static_argnums=0)
def _make(dims: Dims, key):
    bf = jnp.bfloat16
    ks = list(jax.random.split(key, 12))

    def normal(k, shape, scale, center=0.0, dtype=bf):
        x = jax.random.normal(k, shape, jnp.float32) * scale + center
        return x.astype(dtype)

    L, d, hd = dims.layers, dims.d, dims.head_dim
    qd, kvd = dims.heads * hd, dims.kv_heads * hd
    p = {"embed": normal(ks[0], (dims.vocab, d), 0.02),
         "final_norm": normal(ks[1], (d,), 0.1, 1.0),
         "blocks": {
             "ln1": normal(ks[2], (L, d), 0.1, 1.0),
             "attn": {"wq": normal(ks[3], (L, d, qd), d ** -0.5),
                      "wk": normal(ks[4], (L, d, kvd), d ** -0.5),
                      "wv": normal(ks[5], (L, d, kvd), d ** -0.5),
                      "wo": normal(ks[6], (L, qd, d), qd ** -0.5)},
             "ln2": normal(ks[7], (L, d), 0.1, 1.0),
             "mlp": {"wg": normal(ks[8], (L, d, dims.ff), d ** -0.5),
                     "wu": normal(ks[9], (L, d, dims.ff), d ** -0.5),
                     "wd": normal(ks[10], (L, dims.ff, d), dims.ff ** -0.5)}}}
    if not dims.tied:
        p["lm_head"] = normal(ks[11], (d, dims.vocab), d ** -0.5)
    return p


def make_weights(config: dict, key):
    """Seeded bf16 weights on the default device, in one jitted call."""
    return _make(Dims.of(config), key)


def _rope(x, theta):
    """Rotate-half RoPE on (T, heads, hd) at positions 0..T-1."""
    t, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv       # (T, hd/2)
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _layer(h, blocks, i, dims: Dims, mmdt: str):
    p = jax.tree.map(lambda a: a[i].astype(jnp.float32), blocks)
    t = h.shape[0]
    hd, kv = dims.head_dim, dims.kv_heads
    g = dims.heads // kv
    x = rms_norm(h, p["ln1"], dims.eps)
    q = _rope(mm(x, p["attn"]["wq"], mmdt).reshape(t, dims.heads, hd),
              dims.theta)
    k = _rope(mm(x, p["attn"]["wk"], mmdt).reshape(t, kv, hd), dims.theta)
    v = mm(x, p["attn"]["wv"], mmdt).reshape(t, kv, hd)
    s = einsum("tkgh,skh->kgts", q.reshape(t, kv, g, hd), k, mmdt)
    s = s * dims.attn_mult
    causal = jnp.tril(jnp.ones((t, t), bool))
    w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = einsum("kgts,skh->tkgh", w, v, mmdt).reshape(t, dims.heads * hd)
    h = h + dims.res_mult * mm(o, p["attn"]["wo"], mmdt)
    x = rms_norm(h, p["ln2"], dims.eps)
    f = jax.nn.silu(mm(x, p["mlp"]["wg"], mmdt)) * mm(x, p["mlp"]["wu"], mmdt)
    return h + dims.res_mult * mm(f, p["mlp"]["wd"], mmdt)


@functools.partial(jax.jit, static_argnums=(2,))
def _embed(embed, tokens, dims: Dims):
    return embed[tokens].astype(jnp.float32) * dims.emb_mult


@functools.partial(jax.jit, static_argnums=(4, 5))
def _head(h, final_norm, out_w, pos, dims: Dims, mmdt: str):
    x = rms_norm(h[pos], final_norm.astype(jnp.float32), dims.eps)
    w = out_w.T if dims.tied else out_w
    return mm(x, w.astype(jnp.float32), mmdt) / dims.logit_scale


def logits(params, config: dict, tokens, positions,
           matmul_dtype: str = "float32") -> np.ndarray:
    """Float32 logits (len(positions), vocab) of the causal forward pass
    over ``tokens`` (1-D), at ``positions``."""
    dims = Dims.of(config)
    h = _embed(params["embed"], jnp.asarray(padded(tokens)), dims)
    for i in range(dims.layers):
        h = _layer(h, params["blocks"], i, dims, matmul_dtype)
    out_w = params["embed"] if dims.tied else params["lm_head"]
    pos = padded_positions(positions)
    return np.asarray(_head(h, params["final_norm"], out_w, jnp.asarray(pos),
                            dims, matmul_dtype))[:len(positions)]

"""Plain float32 reference of a Mamba-2 language model, and the benchmark's
seeded weights for it.

The equations are the Mamba-2 block's (arXiv:2405.21060, ``Mamba2`` with
``ngroups`` 1): RMS norm; one input projection to the gate z, the input x,
B, C and dt; a causal depthwise convolution of width ``d_conv`` with bias
over (x, B, C), then SiLU; ``dt = softplus(dt + dt_bias)``, ``A =
-exp(A_log)``; the selective state-space recurrence, run one position at
a time (no chunked scan)::

    state_t = exp(dt_t A) * state_{t-1} + dt_t * x_t B_t^T
    y_t     = state_t C_t + D * x_t

then a gated RMS norm of ``y * silu(z)``, the output projection and the
residual add.  A final RMS norm and the tied embedding give the logits.
One jitted call per layer.
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
    state: int
    head_dim: int
    expand: int
    conv: int
    vocab: int
    eps: float
    tied: bool

    @property
    def inner(self) -> int:
        return self.expand * self.d

    @property
    def heads(self) -> int:
        return self.inner // self.head_dim

    @classmethod
    def of(cls, c: dict) -> "Dims":
        return cls(layers=c["n_layer"], d=c["d_model"], state=c["d_state"],
                   head_dim=c["headdim"], expand=c["expand"],
                   conv=c["d_conv"], vocab=vocab_rows(c),
                   eps=float(c["norm_epsilon"]),
                   tied=bool(c["tie_embeddings"]))


@functools.partial(jax.jit, static_argnums=0)
def _make(dims: Dims, key):
    bf, f32 = jnp.bfloat16, jnp.float32
    ks = list(jax.random.split(key, 12))

    def normal(k, shape, scale, center=0.0, dtype=bf):
        return (jax.random.normal(k, shape, f32) * scale + center).astype(dtype)

    L, d, di, n, h = dims.layers, dims.d, dims.inner, dims.state, dims.heads
    ch = di + 2 * n
    # dt at init spans [1e-3, 1e-1] log-uniformly, as in Mamba-2
    dt = jnp.exp(jax.random.uniform(ks[9], (L, h), f32, np.log(1e-3),
                                    np.log(1e-1)))
    p = {"embed": normal(ks[0], (dims.vocab, d), 0.02),
         "final_norm": normal(ks[1], (d,), 0.1, 1.0),
         "blocks": {
             "pre_norm": normal(ks[2], (L, d), 0.1, 1.0),
             "in_proj": normal(ks[3], (L, d, 2 * di + 2 * n + h), d ** -0.5),
             "conv_w": normal(ks[4], (L, dims.conv, ch), dims.conv ** -0.5),
             "conv_b": normal(ks[5], (L, ch), 0.1),
             "A_log": jnp.log(jax.random.uniform(ks[6], (L, h), f32, 1.0,
                                                 16.0)),
             "D": normal(ks[7], (L, h), 0.1, 1.0, f32),
             "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),   # softplus^-1(dt)
             "norm_w": normal(ks[8], (L, di), 0.1, 1.0),
             "out_proj": normal(ks[10], (L, di, d), di ** -0.5)}}
    if not dims.tied:
        p["lm_head"] = normal(ks[11], (d, dims.vocab), d ** -0.5)
    return p


def make_weights(config: dict, key):
    """Seeded weights on the default device, in one jitted call: bf16
    matrices and norms, float32 A_log, D and dt_bias."""
    return _make(Dims.of(config), key)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _layer(h, blocks, i, dims: Dims, mmdt: str):
    p = jax.tree.map(lambda a: a[i].astype(jnp.float32), blocks)
    t = h.shape[0]
    di, n, nh, hp = dims.inner, dims.state, dims.heads, dims.head_dim
    x = rms_norm(h, p["pre_norm"], dims.eps)
    zx = mm(x, p["in_proj"], mmdt)
    z, xs, bm, cm, dt = jnp.split(zx, [di, 2 * di, 2 * di + n, 2 * di + 2 * n],
                                  axis=-1)
    u = jnp.concatenate([xs, bm, cm], axis=-1)                 # (T, di+2n)
    up = jnp.pad(u, ((dims.conv - 1, 0), (0, 0)))
    conv = sum(up[j:j + t] * p["conv_w"][j] for j in range(dims.conv))
    conv = jax.nn.silu(conv + p["conv_b"])
    xs, bm, cm = jnp.split(conv, [di, di + n], axis=-1)
    xh = xs.reshape(t, nh, hp)
    dt = jax.nn.softplus(dt + p["dt_bias"])                    # (T, H)
    a = -jnp.exp(p["A_log"])

    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp
        state = (state * jnp.exp(dt_t * a)[:, None, None]
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        return state, einsum("hpn,n->hp", state, c_t, mmdt)

    _, y = jax.lax.scan(step, jnp.zeros((nh, hp, n), jnp.float32),
                        (xh, dt, bm, cm))
    y = (y + p["D"][:, None] * xh).reshape(t, di)
    y = rms_norm(y * jax.nn.silu(z), p["norm_w"], dims.eps)
    return h + mm(y, p["out_proj"], mmdt)


@jax.jit
def _embed(embed, tokens):
    return embed[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _head(h, final_norm, out_w, pos, dims: Dims, mmdt: str):
    x = rms_norm(h[pos], final_norm.astype(jnp.float32), dims.eps)
    w = out_w.T if dims.tied else out_w
    return mm(x, w.astype(jnp.float32), mmdt)


def logits(params, config: dict, tokens, positions,
           matmul_dtype: str = "float32") -> np.ndarray:
    """Float32 logits (len(positions), vocab) of the forward pass over
    ``tokens`` (1-D), at ``positions``."""
    dims = Dims.of(config)
    h = _embed(params["embed"], jnp.asarray(padded(tokens)))
    for i in range(dims.layers):
        h = _layer(h, params["blocks"], i, dims, matmul_dtype)
    out_w = params["embed"] if dims.tied else params["lm_head"]
    pos = padded_positions(positions)
    return np.asarray(_head(h, params["final_norm"], out_w, jnp.asarray(pos),
                            dims, matmul_dtype))[:len(positions)]

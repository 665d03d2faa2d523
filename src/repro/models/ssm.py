"""Mamba2 blocks via SSD — state-space duality (arXiv:2405.21060).

Chunked algorithm: within a chunk the SSM is computed as a masked
attention-like quadratic form (MXU-friendly); across chunks a linear
recurrence carries the (heads, head_dim, state) tensor.  Decode is the O(1)
per-token recurrence.  B/C are shared across heads (multi-value attention).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .config import ModelConfig
from .layers import ninit, rms_norm


def init_mamba_block(key, cfg: ModelConfig):
    d, di, n, h = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    ks = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.param_dtype)
    conv_ch = di + 2 * n
    return {
        "pre_norm": jnp.ones((d,), dt),
        "in_proj": ninit(ks[0], (d, 2 * di + 2 * n + h), dt, fan_in=d),
        "conv_w": ninit(ks[1], (cfg.ssm_conv, conv_ch), dt, scale=0.5),
        "conv_b": jnp.zeros((conv_ch,), dt),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, h)).astype(jnp.float32),
        "D": jnp.ones((h,), jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(jnp.full((h,), 0.005))).astype(jnp.float32),
        "norm_w": jnp.ones((di,), dt),
        "out_proj": ninit(ks[2], (di, d), dt, fan_in=di),
    }


def _causal_conv(x, w, b):
    """Depthwise causal conv: x (B,S,C), w (K,C).  Returns (B,S,C)."""
    k = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = sum(xp[:, i:i + x.shape[1], :] * w[i][None, None, :] for i in range(k))
    return out + b[None, None, :]


def _segsum(x):
    """x: (..., q) -> cumulative segment sums L[..., i, j] = sum_{j<m<=i} x_m,
    lower-triangular (i >= j), -inf elsewhere."""
    q = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]          # (..., i, j)
    mask = jnp.tril(jnp.ones((q, q), dtype=bool), 0)
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunked(xh, dt, A, Bm, Cm, chunk, init_state=None):
    """Chunked SSD scan.

    xh  : (b, s, h, p)   input per head
    dt  : (b, s, h)      softplus'd timestep (>0)
    A   : (h,)           negative decay rate
    Bm  : (b, s, n)      input projection (shared across heads)
    Cm  : (b, s, n)      output projection
    Returns (y (b,s,h,p), final_state (b,h,p,n)).
    """
    b, s, h, p = xh.shape
    n = Bm.shape[-1]
    q = min(chunk, s)
    pad = (-s) % q
    if pad:
        # dt=0 on padding => decay 1, zero state update, so padding is inert
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    s_orig, s = s, s + pad
    nc = s // q
    f32 = jnp.float32

    xc = xh.reshape(b, nc, q, h, p)
    dtc = dt.reshape(b, nc, q, h).astype(f32)
    Bc = Bm.reshape(b, nc, q, n).astype(f32)
    Cc = Cm.reshape(b, nc, q, n).astype(f32)
    dA = dtc * A[None, None, None, :]                    # (b,nc,q,h) negative

    # intra-chunk "attention" term.  Contractions are staged explicitly so no
    # intermediate exceeds 5 dims (a fused 4-operand einsum materializes a
    # (b,nc,h,q,q,p) tensor on some backends).
    L = jnp.exp(_segsum(dA.transpose(0, 1, 3, 2)))       # (b,nc,h,q,q)
    scores = jnp.einsum("bcin,bcjn->bcij", Cc, Bc)       # (b,nc,q,q)
    M = scores[:, :, None] * L \
        * dtc.transpose(0, 1, 3, 2)[:, :, :, None, :]    # (b,nc,h,i,j)
    Ydiag = jnp.einsum("bchij,bcjhp->bcihp", M, xc.astype(f32))

    # chunk-final states and inter-chunk recurrence
    cum = jnp.cumsum(dA, axis=2)                         # (b,nc,q,h)
    decay_out = jnp.exp(cum[:, :, -1:, :] - cum)         # (b,nc,q,h)
    xw = xc.astype(f32) * (decay_out * dtc)[..., None]   # (b,nc,q,h,p)
    chunk_states = jnp.einsum("bcjn,bcjhp->bchpn", Bc, xw)
    chunk_decay = jnp.exp(cum[:, :, -1, :])              # (b,nc,h)

    def step(carry, inp):
        st = carry                                        # (b,h,p,n)
        cstate, cdecay = inp                              # (b,h,p,n), (b,h)
        new = st * cdecay[:, :, None, None] + cstate
        return new, st                                    # emit state *before*

    st0 = jnp.zeros((b, h, p, n), f32) if init_state is None \
        else init_state.astype(f32)
    xs = (jnp.moveaxis(chunk_states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0))
    final_state, prev_states = jax.lax.scan(step, st0, xs)
    prev_states = jnp.moveaxis(prev_states, 0, 1)         # (b,nc,h,p,n)

    decay_in = jnp.exp(cum)                               # (b,nc,q,h)
    Yoff = jnp.einsum("bcin,bchpn->bcihp", Cc, prev_states) \
        * decay_in[..., None]
    y = (Ydiag + Yoff).reshape(b, s, h, p)[:, :s_orig]
    return y.astype(xh.dtype), final_state


def ssd_reference(xh, dt, A, Bm, Cm, init_state=None):
    """O(s) sequential oracle (pure recurrence) for tests."""
    b, s, h, p = xh.shape
    n = Bm.shape[-1]
    f32 = jnp.float32
    st = jnp.zeros((b, h, p, n), f32) if init_state is None else init_state.astype(f32)
    ys = []
    for t in range(s):
        dA = jnp.exp(dt[:, t].astype(f32) * A)            # (b,h)
        dBx = jnp.einsum("bh,bn,bhp->bhpn", dt[:, t].astype(f32),
                         Bm[:, t].astype(f32), xh[:, t].astype(f32))
        st = st * dA[:, :, None, None] + dBx
        y = jnp.einsum("bn,bhpn->bhp", Cm[:, t].astype(f32), st)
        ys.append(y)
    return jnp.stack(ys, axis=1).astype(xh.dtype), st


def mamba_block(params, x, cfg: ModelConfig, cache=None, layer=None):
    """Full Mamba2 block.  cache: None (train/prefill from scratch) or dict
    (conv_buf (B, K-1, C), state (B,h,p,n), len) for decode.
    layer: ``cache`` is the stacked cache of every layer (a leading layer
    axis); the block reads plane ``layer`` of it and writes its new conv
    buffer and state back into that plane in place, leaving len for the
    caller to advance once for all layers.
    Returns (y, new_cache)."""
    b, s, d = x.shape
    di, n, h = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    p = cfg.ssm_head_dim
    zxbcdt = x @ params["in_proj"]
    z, xs, Bm, Cm, dt = jnp.split(
        zxbcdt, [di, 2 * di, 2 * di + n, 2 * di + 2 * n], axis=-1)
    conv_in = jnp.concatenate([xs, Bm, Cm], axis=-1)      # (B,S,di+2n)

    new_cache = None
    if cache is None:
        conv = jax.nn.silu(_causal_conv(conv_in, params["conv_w"], params["conv_b"]))
    else:
        c = cache if layer is None else jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0, False), cache)
        kw = cfg.ssm_conv
        buf = jnp.concatenate([c["conv_buf"], conv_in], axis=1)  # (B,K-1+s,C)
        conv = sum(buf[:, i:i + s, :] * params["conv_w"][i][None, None, :]
                   for i in range(kw)) + params["conv_b"][None, None, :]
        conv = jax.nn.silu(conv)
        new_conv_buf = buf[:, -(kw - 1):, :]

    xs2, B2, C2 = jnp.split(conv, [di, di + n], axis=-1)
    xh = xs2.reshape(b, s, h, p)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    A = -jnp.exp(params["A_log"])

    if cache is None:
        y, _ = ssd_chunked(xh, dt, A, B2, C2, min(cfg.ssm_chunk, s))
    elif s == 1:
        st = c["state"]
        dA = jnp.exp(dt[:, 0] * A)                        # (b,h)
        dBx = jnp.einsum("bh,bn,bhp->bhpn", dt[:, 0],
                         B2[:, 0].astype(jnp.float32),
                         xh[:, 0].astype(jnp.float32))
        st = st * dA[:, :, None, None] + dBx
        new_cache = _store(cache, new_conv_buf, st, 1, layer)
        y = jnp.einsum("bn,bhpn->bhp", C2[:, 0].astype(jnp.float32), st)
        y = y[:, None].astype(x.dtype)                    # (b,1,h,p)
    else:                                                  # prefill into cache
        y, st = ssd_chunked(xh, dt, A, B2, C2, min(cfg.ssm_chunk, s))
        new_cache = _store(cache, new_conv_buf, st, s, layer)

    y = y + params["D"][None, None, :, None].astype(y.dtype) * xh.astype(y.dtype)
    y = y.reshape(b, s, di).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), params["norm_w"], cfg.norm_eps)
    return y @ params["out_proj"], new_cache


def _store(cache, conv_buf, state, s, layer):
    """The cache after a step of ``s`` tokens: with ``layer``, the stacked
    cache with the new conv buffer and state written into plane ``layer``
    in place, and len left as it was."""
    if layer is None:
        return {"conv_buf": conv_buf, "state": state, "len": cache["len"] + s}
    return {**cache, **{k: jax.lax.dynamic_update_index_in_dim(
        cache[k], v.astype(cache[k].dtype), layer, 0)
        for k, v in (("conv_buf", conv_buf), ("state", state))}}


def init_mamba_cache(cfg: ModelConfig, batch, dtype=jnp.bfloat16):
    di, n = cfg.d_inner, cfg.ssm_state
    return {
        "conv_buf": jnp.zeros((batch, cfg.ssm_conv - 1, di + 2 * n), dtype),
        "state": jnp.zeros((batch, cfg.ssm_heads, cfg.ssm_head_dim, n),
                           jnp.float32),
        "len": jnp.zeros((batch,), jnp.int32),
    }

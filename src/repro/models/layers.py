"""Shared model building blocks (pure JAX, shape-static, scan-friendly)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .config import ModelConfig


# ---------------------------------------------------------------------------
# sharding helper: apply a constraint when a mesh context is active, no-op
# otherwise (smoke tests / single device).  Specs below name the superset of
# axes ("pod","data","model"); set_mesh_axes() filters them to the axes that
# actually exist on the active mesh (single-pod has no "pod").
# ---------------------------------------------------------------------------

_ACTIVE_AXES: tuple[str, ...] | None = None
_DROPPED_AXES: frozenset = frozenset()
_ACT_MODE: str = "train"
_ACTIVE_MESH = None


def set_mesh_axes(axes, drop_for_activations=(), mode: str = "train",
                  mesh=None):
    """Called by launch code when entering a mesh; None disables.

    drop_for_activations: axis names removed from *activation* sharding
    constraints only.  mode="serve2d" switches activation constraints to
    weight-stationary 2-D TP (feature dims alternate data/model so every
    matmul contracts against an aligned weight shard; only tiny activation
    all-reduces hit the wire — §Perf iteration on decode cells)."""
    # trace-time toggle: launch code calls this OUTSIDE jit; jitted fns only
    # read the globals while tracing.
    global _ACTIVE_AXES, _DROPPED_AXES, _ACT_MODE, _ACTIVE_MESH  # repro: ignore[jit-purity]
    _ACTIVE_AXES = tuple(axes) if axes is not None else None
    _DROPPED_AXES = frozenset(drop_for_activations)
    _ACT_MODE = mode
    _ACTIVE_MESH = mesh


def active_mesh():
    return _ACTIVE_MESH


def _filter_entry(entry):
    if entry is None:
        return None
    if isinstance(entry, str):
        return entry if (entry in _ACTIVE_AXES
                         and entry not in _DROPPED_AXES) else None
    sub = tuple(a for a in entry
                if a in _ACTIVE_AXES and a not in _DROPPED_AXES)
    return sub if len(sub) > 1 else (sub[0] if sub else None)


def shard(x, spec: P):
    if _ACTIVE_AXES is None:
        return x
    fspec = P(*(_filter_entry(e) for e in spec))
    return jax.lax.with_sharding_constraint(x, fspec)


BATCH = P(("pod", "data"))                     # batch axis of activations
BATCH_SEQ = P(("pod", "data"), None)


class _ModalSpec:
    """Activation spec that depends on the active mode (train vs serve2d)."""

    def __init__(self, train_spec, serve2d_spec):
        self.train_spec = train_spec
        self.serve2d_spec = serve2d_spec

    def resolve(self):
        return self.serve2d_spec if _ACT_MODE == "serve2d" else self.train_spec


# hidden residual stream: train shards batch; serve2d shards the feature dim
# over 'data' (weights (D/data, F/model) then contract locally)
HIDDEN = _ModalSpec(P(("pod", "data"), None, None), P(None, None, "data"))
FFN_ACT = _ModalSpec(P(("pod", "data"), None, "model"), P(None, None, "model"))
VOCAB_ACT = _ModalSpec(P(("pod", "data"), None, "model"), P(None, None, "model"))


def shard_modal(x, mspec):
    spec = mspec.resolve() if isinstance(mspec, _ModalSpec) else mspec
    return shard(x, spec)


def dtype_of(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


# ---------------------------------------------------------------------------
# decode kv bucket: trace-time bound on the attended cache prefix.
# repro.serve sets this around tracing one bucket-specialized decode step —
# attention then reads only rows [0, bucket) of the kv cache instead of all
# max_len rows.  Every active row's kv_len must stay < bucket (the engine
# rounds the max active length up to its block size).  None = full cache.
# ---------------------------------------------------------------------------

_DECODE_KV_BUCKET: int | None = None


def set_decode_kv_bucket(n: int | None):
    # trace-time toggle: the engine sets the bucket before retracing decode;
    # never called under a trace.
    global _DECODE_KV_BUCKET  # repro: ignore[jit-purity]
    _DECODE_KV_BUCKET = n


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def ninit(key, shape, dtype, scale=0.02, fan_in=None):
    scale = scale if fan_in is None else 1.0 / np.sqrt(fan_in)
    return (scale * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def rms_norm(x, w, eps):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * w.astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# rotary position embedding
# ---------------------------------------------------------------------------

def rope_tables(positions, dim, theta):
    """positions: (B, S) int32 -> cos/sin (B, S, dim/2) fp32."""
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = positions.astype(jnp.float32)[..., None] * inv   # (B, S, dim/2)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x: (B, S, H, hd); rotate-half convention."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    c = cos[:, :, None, :]
    s = sin[:, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# attention (GQA) with optional KV cache
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, d_model=None):
    d = d_model or cfg.d_model
    hd = cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    dt = dtype_of(cfg)
    return {
        "wq": ninit(ks[0], (d, cfg.n_heads * hd), dt, fan_in=d),
        "wk": ninit(ks[1], (d, cfg.n_kv_heads * hd), dt, fan_in=d),
        "wv": ninit(ks[2], (d, cfg.n_kv_heads * hd), dt, fan_in=d),
        "wo": ninit(ks[3], (cfg.n_heads * hd, d), dt, fan_in=cfg.n_heads * hd),
    }


BLOCKED_ATTN_THRESHOLD = 8192   # use online-softmax blocking at/above this


def _blocked_core(q, k, v, causal, q_block=512, kv_block=1024):
    """Flash-style attention as pure JAX scans (online softmax over kv
    blocks, outer scan over q blocks).  Never materializes more than a
    (B, KV, G, q_block, kv_block) score tile — required for the 32k-prefill
    cells where a full (S, S) score tensor would be terabytes.

    Assumes fresh (cacheless) self-attention with aligned q/kv (the prefill
    path).  Returns (out, lse) with lse (B, KV, G, S) for the custom VJP."""
    b, s, h, hd = q.shape
    kv = k.shape[2]
    hdv = v.shape[-1]                       # may differ from hd (MLA)
    g = h // kv
    nq = s // q_block
    nk = s // kv_block
    qg = q.reshape(b, nq, q_block, kv, g, hd)
    kb = k.reshape(b, nk, kv_block, kv, hd)
    vb = v.reshape(b, nk, kv_block, kv, hdv)
    scale = 1.0 / np.sqrt(hd)

    def q_step(_, qi):
        qblk, qidx = qi                                   # (B,qb,KV,G,hd)
        q_pos = qidx * q_block + jnp.arange(q_block)

        def kv_step(carry, ki):
            m, l, acc = carry
            kblk, vblk, kidx = ki
            s_pos = kidx * kv_block + jnp.arange(kv_block)
            sc = jnp.einsum("bqkgh,bskh->bkgqs", qblk, kblk) * scale
            sc = sc.astype(jnp.float32)
            if causal:
                mask = (s_pos[None, :] <= q_pos[:, None])[None, None, None]
                sc = jnp.where(mask, sc, -1e30)
            m_new = jnp.maximum(m, sc.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(sc - m_new[..., None])
            l_new = l * alpha + p.sum(axis=-1)
            acc_new = acc * alpha[..., None] \
                + jnp.einsum("bkgqs,bskh->bkgqh", p.astype(qblk.dtype), vblk
                             ).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, kv, g, q_block), -1e30, jnp.float32)
        l0 = jnp.zeros((b, kv, g, q_block), jnp.float32)
        a0 = jnp.zeros((b, kv, g, q_block, hdv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0), jnp.arange(nk)))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        lse = m + jnp.log(jnp.maximum(l, 1e-30))           # (B,KV,G,qb)
        return None, (out.astype(qblk.dtype), lse)

    _, (outs, lses) = jax.lax.scan(q_step, None,
                                   (jnp.moveaxis(qg, 1, 0), jnp.arange(nq)))
    # outs: (nq, B, KV, G, q_block, hdv) -> (B, S, H, hdv)
    o = jnp.moveaxis(outs, 0, 1).transpose(0, 2, 3, 1, 4, 5)
    o = o.reshape(b, kv, g, s, hdv).transpose(0, 3, 1, 2, 4).reshape(b, s, h, hdv)
    lse = jnp.moveaxis(lses, 0, 3).reshape(b, kv, g, s)
    return o, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _blocked_sdpa(q, k, v, causal):
    """Blocked attention with a flash-style custom VJP: the backward pass
    recomputes score tiles from saved (q, k, v, out, lse) instead of letting
    scan autodiff save every probability tile (which materializes the full
    (S, S) score tensor again — measured 26 GB/layer on dsv3 train)."""
    out, _ = _blocked_core(q, k, v, causal)
    return out


def _blocked_fwd_rule(q, k, v, causal):
    out, lse = _blocked_core(q, k, v, causal)
    return out, (q, k, v, out, lse)


def _blocked_bwd_rule(causal, res, g, q_block=512, kv_block=1024):
    q, k, v, out, lse = res
    b, s, h, hd = q.shape
    kv = k.shape[2]
    hdv = v.shape[-1]
    grp = h // kv
    nq, nk = s // q_block, s // kv_block
    scale = 1.0 / np.sqrt(hd)
    f32 = jnp.float32

    qg = jnp.moveaxis(q.reshape(b, nq, q_block, kv, grp, hd), 1, 0)
    og = jnp.moveaxis(out.reshape(b, nq, q_block, kv, grp, hdv), 1, 0)
    gg = jnp.moveaxis(g.reshape(b, nq, q_block, kv, grp, hdv), 1, 0)
    lseg = jnp.moveaxis(lse.reshape(b, kv, grp, nq, q_block), 3, 0)
    kb = k.reshape(b, nk, kv_block, kv, hd)
    vb = v.reshape(b, nk, kv_block, kv, hdv)

    def q_step(carry, xs):
        dk, dv = carry                       # (B, nk, kb, KV, hd/hdv) f32
        qblk, oblk, gblk, lseblk, qidx = xs
        q_pos = qidx * q_block + jnp.arange(q_block)
        delta = jnp.einsum("bqkgh,bqkgh->bkgq", oblk.astype(f32),
                           gblk.astype(f32))

        def kv_step(dq_acc, kj):
            kblk, vblk, kidx = kj
            s_pos = kidx * kv_block + jnp.arange(kv_block)
            sc = jnp.einsum("bqkgh,bskh->bkgqs", qblk, kblk).astype(f32) * scale
            if causal:
                mask = (s_pos[None, :] <= q_pos[:, None])[None, None, None]
                sc = jnp.where(mask, sc, -1e30)
            p = jnp.exp(sc - lseblk[..., None])            # (B,KV,G,qb,kb)
            dp = jnp.einsum("bqkgh,bskh->bkgqs", gblk, vblk).astype(f32)
            ds = p * (dp - delta[..., None]) * scale
            dqi = jnp.einsum("bkgqs,bskh->bqkgh",
                             ds.astype(qblk.dtype), kblk).astype(f32)
            dki = jnp.einsum("bkgqs,bqkgh->bskh",
                             ds.astype(qblk.dtype), qblk)
            dvi = jnp.einsum("bkgqs,bqkgh->bskh",
                             p.astype(gblk.dtype), gblk)
            return dq_acc + dqi, (dki, dvi)

        dq0 = jnp.zeros((b, q_block, kv, grp, hd), f32)
        dq, (dk_inc, dv_inc) = jax.lax.scan(
            kv_step, dq0,
            (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0), jnp.arange(nk)))
        return (dk + jnp.moveaxis(dk_inc, 0, 1),
                dv + jnp.moveaxis(dv_inc, 0, 1)), dq

    dk0 = jnp.zeros((b, nk, kv_block, kv, hd), f32)
    dv0 = jnp.zeros((b, nk, kv_block, kv, hdv), f32)
    (dk, dv), dqs = jax.lax.scan(q_step, (dk0, dv0),
                                 (qg, og, gg, lseg, jnp.arange(nq)))
    dq = jnp.moveaxis(dqs, 0, 1).reshape(b, s, h, hd).astype(q.dtype)
    dk = dk.reshape(b, s, kv, hd).astype(k.dtype)
    dv = dv.reshape(b, s, kv, hdv).astype(v.dtype)
    return dq, dk, dv


_blocked_sdpa.defvjp(_blocked_fwd_rule, _blocked_bwd_rule)


def _sdpa(q, k, v, causal, q_offset=None, kv_len=None, impl="xla",
          block_threshold=BLOCKED_ATTN_THRESHOLD):
    """q: (B,Sq,H,hd)  k/v: (B,Skv,KV,hd); grouped-query broadcast.

    q_offset: optional (B,) absolute position of q's first token.
    kv_len:   optional (B,) active cache lengths — only applied when Sq == 1
              (decode); multi-token prefill assumes a fresh cache, where the
              causal mask subsumes the length mask (avoids materializing a
              (B,Sq,Skv) tensor at 32k).
    """
    if impl == "flash" and causal and q.shape[1] > 1 and kv_len is None:
        from repro.kernels.attention.ops import flash_attention
        return flash_attention(q, k, v, causal=True)
    if (q.shape[1] >= block_threshold and q.shape[1] == k.shape[1]
            and kv_len is None and q.shape[1] % 1024 == 0):
        return _blocked_sdpa(q, k, v, causal)
    b, sq, h, hd = q.shape
    skv, kv = k.shape[1], k.shape[2]
    group = h // kv
    qg = q.reshape(b, sq, kv, group, hd)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", qg, k).astype(jnp.float32)
    scores = scores / np.sqrt(hd)
    s_pos = jnp.arange(skv)                               # (Skv,)
    if sq == 1:
        # decode: mask by cache length (q attends to all written slots)
        if kv_len is not None:
            mask = (s_pos[None, :] < kv_len[:, None])[:, None, None, None, :]
        else:
            mask = jnp.ones((1, 1, 1, 1, skv), dtype=bool)
    else:
        if causal:
            if q_offset is None:
                q_pos = jnp.arange(sq)[None, :]           # (1, Sq)
            else:
                q_pos = jnp.arange(sq)[None, :] + q_offset[:, None]
            mask = (s_pos[None, None, :] <= q_pos[..., None])  # (B|1,Sq,Skv)
            mask = mask[:, None, None]                    # (B|1,1,1,Sq,Skv)
        else:
            mask = jnp.ones((1, 1, 1, sq, skv), dtype=bool)
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs, v)
    return out.reshape(b, sq, h, v.shape[-1])   # v head dim may differ (MLA)


def attention(params, x, cfg: ModelConfig, positions, *, causal=True,
              cache=None, kv_x=None, layer=None):
    """Returns (out, new_cache).

    cache: None, or dict(k, v, len) with k/v (B, S_max, KV, hd) and len (B,).
    layer: with a cache, the index of this layer's plane in a stacked cache
           (k/v (L, B, S_max, KV, hd), len (L, B)): the new rows are written
           into that plane in place and the rows attended are read from it
           where they lie; len is returned as given, for the caller to
           advance once for all layers.
    kv_x:  cross-attention source (B, Skv, D) — keys/values from here.
    """
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    q = (x @ params["wq"]).reshape(b, s, cfg.n_heads, hd)
    src = x if kv_x is None else kv_x
    k = (src @ params["wk"]).reshape(b, src.shape[1], cfg.n_kv_heads, hd)
    v = (src @ params["wv"]).reshape(b, src.shape[1], cfg.n_kv_heads, hd)
    if cfg.attn_head_shard:
        hs = P(None, None, "model", None) if _ACT_MODE == "serve2d" \
            else P(("pod", "data"), None, "model", None)
        q = shard(q, hs)
        k = shard(k, hs)
        v = shard(v, hs)

    if kv_x is None:                                   # self-attention: rope
        cos, sin = rope_tables(positions, hd, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)

    new_cache = None
    kv_len = None
    q_offset = None
    if cache is not None:
        ln = cache["len"] if layer is None else cache["len"][layer]
        kc = _batched_update(cache["k"], k, ln, layer)
        vc = _batched_update(cache["v"], v, ln, layer)
        kv_len = ln + s
        new_cache = {"k": kc, "v": vc,
                     "len": kv_len if layer is None else cache["len"]}
        q_offset = ln
        n = _attended_rows(kc, layer, s)
        k = _cached_rows(kc, layer, n)
        v = _cached_rows(vc, layer, n)
    out = _sdpa(q, k, v, causal, q_offset, kv_len, impl=cfg.attn_impl,
                block_threshold=cfg.attn_block_threshold)
    out = out.reshape(b, s, cfg.n_heads * hd)
    return out @ params["wo"], new_cache


def _batched_update(cache, new, lens, layer=None):
    """Write `new` (B,s,...) into `cache` (B,S,...) at per-row offsets, or,
    with `layer`, into plane `layer` of a stacked cache (L,B,S,...).

    Decode (s == 1) writes each row at its own length — slots in the
    continuous-batching engine advance independently.  Multi-token writes
    keep the contiguous shared-offset slice (lens[0]): prefill always runs
    on a fresh cache (offset 0) or one request at a time (repro.serve
    admits per request), so the offsets agree by construction."""
    new = new.astype(cache.dtype)
    if layer is None:
        if new.shape[1] == 1:
            rows = jnp.arange(cache.shape[0])
            return cache.at[rows, lens].set(new[:, 0])
        return jax.lax.dynamic_update_slice_in_dim(cache, new, lens[0], axis=1)
    if new.shape[1] == 1:
        # rewrite the plane's rows up to the kv bucket, each slot's new row
        # put in at its own length.  On a TPU v5e this beats scattering the
        # rows: with (KV, hd) windows the compiler relayouts the whole
        # stacked cache around the layer loop, and with hd windows granite's
        # step (64 slots) took 16.3-18.1 ms against 11.7-17.6 (buckets
        # 128-384).  Rows at or past the bucket are never written: the
        # engine keeps every active row below it
        n = _attended_rows(cache, layer, 1)
        starts = (layer,) + (0,) * (cache.ndim - 1)
        plane = jax.lax.dynamic_slice(
            cache, starts, (1, cache.shape[1], n) + cache.shape[3:])
        at = jnp.arange(n)[None, :] == lens[:, None]
        at = at.reshape((1,) + at.shape + (1,) * (cache.ndim - 3))
        return jax.lax.dynamic_update_slice(
            cache, jnp.where(at, new[None], plane), starts)
    starts = (layer, 0, lens[0]) + (0,) * (cache.ndim - 3)
    return jax.lax.dynamic_update_slice(cache, new[None], starts)


def _attended_rows(cache, layer, s):
    """Rows a step attends: decode reads [0, kv bucket) when a bucket is
    set (repro.serve's length-aware fast path), anything else every row."""
    rows = cache.shape[1 if layer is None else 2]
    nb = _DECODE_KV_BUCKET
    return nb if (s == 1 and nb is not None and nb < rows) else rows


def _cached_rows(cache, layer, n):
    """Rows [0, n) of a cache (B,S,...) or of plane `layer` of a stacked
    one (L,B,S,...), sliced where they lie."""
    if layer is None:
        return jax.lax.slice_in_dim(cache, 0, n, axis=1)
    starts = (layer,) + (0,) * (cache.ndim - 1)
    sizes = (1, cache.shape[1], n) + cache.shape[3:]
    return jax.lax.dynamic_slice(cache, starts, sizes)[0]


def init_cache(cfg: ModelConfig, batch, max_len, n_kv=None, head_dim=None,
               dtype=jnp.bfloat16):
    kv = n_kv or cfg.n_kv_heads
    hd = head_dim or cfg.resolved_head_dim
    return {
        "k": jnp.zeros((batch, max_len, kv, hd), dtype),
        "v": jnp.zeros((batch, max_len, kv, hd), dtype),
        "len": jnp.zeros((batch,), jnp.int32),
    }


# ---------------------------------------------------------------------------
# MLA — multi-head latent attention (deepseek-v3)
# ---------------------------------------------------------------------------

def init_mla(key, cfg: ModelConfig):
    d = cfg.d_model
    ks = jax.random.split(key, 7)
    dt = dtype_of(cfg)
    qk = cfg.qk_nope_dim + cfg.qk_rope_dim
    return {
        "wdq": ninit(ks[0], (d, cfg.q_lora_rank), dt, fan_in=d),
        "q_norm": jnp.ones((cfg.q_lora_rank,), dt),
        "wuq": ninit(ks[1], (cfg.q_lora_rank, cfg.n_heads * qk), dt,
                     fan_in=cfg.q_lora_rank),
        "wdkv": ninit(ks[2], (d, cfg.kv_lora_rank + cfg.qk_rope_dim), dt, fan_in=d),
        "kv_norm": jnp.ones((cfg.kv_lora_rank,), dt),
        "wuk": ninit(ks[3], (cfg.kv_lora_rank, cfg.n_heads * cfg.qk_nope_dim),
                     dt, fan_in=cfg.kv_lora_rank),
        "wuv": ninit(ks[4], (cfg.kv_lora_rank, cfg.n_heads * cfg.v_head_dim),
                     dt, fan_in=cfg.kv_lora_rank),
        "wo": ninit(ks[5], (cfg.n_heads * cfg.v_head_dim, d), dt,
                    fan_in=cfg.n_heads * cfg.v_head_dim),
    }


def mla_attention(params, x, cfg: ModelConfig, positions, cache=None,
                  layer=None):
    """MLA: cache holds the *compressed* c_kv (B,S,kv_lora) + rope key
    (B,S,rope_dim) — the technique's serving memory win.  layer: as for
    :func:`attention`, a plane of a stacked cache updated in place."""
    b, s, _ = x.shape
    nh = cfg.n_heads
    qk_all = cfg.qk_nope_dim + cfg.qk_rope_dim
    q = rms_norm(x @ params["wdq"], params["q_norm"], cfg.norm_eps) @ params["wuq"]
    q = q.reshape(b, s, nh, qk_all)
    q_nope, q_rope = q[..., :cfg.qk_nope_dim], q[..., cfg.qk_nope_dim:]

    dkv = x @ params["wdkv"]                              # (B,S,kv_lora+rope)
    c_kv = rms_norm(dkv[..., :cfg.kv_lora_rank], params["kv_norm"], cfg.norm_eps)
    k_rope = dkv[..., cfg.kv_lora_rank:][:, :, None, :]   # single shared head

    cos, sin = rope_tables(positions, cfg.qk_rope_dim, cfg.rope_theta)
    q_rope = apply_rope(q_rope, cos, sin)
    k_rope = apply_rope(k_rope, cos, sin)

    kv_len = None
    q_offset = None
    new_cache = None
    if cache is not None:
        ln = cache["len"] if layer is None else cache["len"][layer]
        ckv = _batched_update(cache["ckv"], c_kv, ln, layer)
        krope = _batched_update(cache["krope"], k_rope[:, :, 0, :], ln, layer)
        kv_len = ln + s
        new_cache = {"ckv": ckv, "krope": krope,
                     "len": kv_len if layer is None else cache["len"]}
        q_offset = ln
        # slice *before* the k/v up-projections: the length-aware win is
        # larger for MLA, whose per-row decode cost is a matmul
        n = _attended_rows(ckv, layer, s)
        c_kv = _cached_rows(ckv, layer, n)
        k_rope = _cached_rows(krope, layer, n)[:, :, None, :]

    skv = c_kv.shape[1]
    k_nope = (c_kv @ params["wuk"]).reshape(b, skv, nh, cfg.qk_nope_dim)
    val = (c_kv @ params["wuv"]).reshape(b, skv, nh, cfg.v_head_dim)
    k = jnp.concatenate([k_nope,
                         jnp.broadcast_to(k_rope, (b, skv, nh, cfg.qk_rope_dim))],
                        axis=-1)
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    if cfg.attn_head_shard:
        hs = P(None, None, "model", None) if _ACT_MODE == "serve2d" \
            else P(("pod", "data"), None, "model", None)
        q_full = shard(q_full, hs)
        k = shard(k, hs)
        val = shard(val, hs)
    out = _sdpa(q_full, k, val, True, q_offset, kv_len,
                block_threshold=cfg.attn_block_threshold)
    out = out.reshape(b, s, nh * cfg.v_head_dim)
    return out @ params["wo"], new_cache


def init_mla_cache(cfg: ModelConfig, batch, max_len, dtype=jnp.bfloat16):
    return {
        "ckv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
        "krope": jnp.zeros((batch, max_len, cfg.qk_rope_dim), dtype),
        "len": jnp.zeros((batch,), jnp.int32),
    }


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_mlp(key, cfg: ModelConfig, d_ff=None, d_model=None):
    d = d_model or cfg.d_model
    f = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    dt = dtype_of(cfg)
    return {
        "wg": ninit(ks[0], (d, f), dt, fan_in=d),
        "wu": ninit(ks[1], (d, f), dt, fan_in=d),
        "wd": ninit(ks[2], (f, d), dt, fan_in=f),
    }


def mlp(params, x):
    h = jax.nn.silu(x @ params["wg"]) * (x @ params["wu"])
    h = shard_modal(h, FFN_ACT)
    return h @ params["wd"]


# ---------------------------------------------------------------------------
# MoE: top-k routing with sorted capacity-based dispatch (EP-shardable)
# ---------------------------------------------------------------------------

def init_moe(key, cfg: ModelConfig):
    ks = jax.random.split(key, 5)
    dt = dtype_of(cfg)
    e, d, f = cfg.n_experts, cfg.d_model, cfg.moe_d_ff
    p = {
        "router": ninit(ks[0], (d, e), jnp.float32, fan_in=d),
        "wg": ninit(ks[1], (e, d, f), dt, fan_in=d),
        "wu": ninit(ks[2], (e, d, f), dt, fan_in=d),
        "wd": ninit(ks[3], (e, f, d), dt, fan_in=f),
    }
    if cfg.n_shared_experts:
        p["shared"] = init_mlp(ks[4], cfg, d_ff=cfg.moe_d_ff * cfg.n_shared_experts)
    return p


def moe_ffn(params, x, cfg: ModelConfig):
    """Returns (y, aux_loss).  Sorted dispatch with per-expert capacity
    C = cf * T * k / E; over-capacity tokens are dropped (their residual
    stream passes through unchanged) — Switch-style, TPU-friendly.

    With cfg.moe_impl == "ep" and an active mesh, dispatch goes through the
    shard_map expert-parallel path (explicit all_to_all; see moe_ep.py)."""
    if cfg.moe_impl == "ep" and _ACTIVE_MESH is not None:
        from .moe_ep import ep_applicable, moe_ffn_ep
        if ep_applicable(cfg, x.shape, _ACTIVE_MESH):
            batch_axes = tuple(a for a in ("pod", "data")
                               if a in _ACTIVE_MESH.shape)
            y, aux = moe_ffn_ep(params, x, cfg, _ACTIVE_MESH, batch_axes)
            if "shared" in params:
                y = y + mlp(params["shared"], x)
            return y, aux
    b, s, d = x.shape
    t = b * s
    k = cfg.experts_per_tok
    e = cfg.n_experts
    xf = x.reshape(t, d)

    logits = (xf.astype(jnp.float32) @ params["router"])        # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, k)                        # (T, k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)

    # load-balancing auxiliary loss (Switch eq. 4)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(idx[:, 0], e, dtype=jnp.float32), axis=0)
    aux = e * jnp.sum(me * ce)

    cap = max(1, int(cfg.moe_capacity_factor * t * k / e))
    flat_e = idx.reshape(-1)                                    # (T*k,)
    sort_idx = jnp.argsort(flat_e)
    sorted_e = flat_e[sort_idx]
    seg_start = jnp.searchsorted(sorted_e, jnp.arange(e))
    pos = jnp.arange(t * k) - seg_start[sorted_e]
    keep = pos < cap
    dest = jnp.where(keep, sorted_e * cap + pos, e * cap)       # OOB => drop
    token_of = sort_idx // k

    buf = jnp.zeros((e * cap, d), x.dtype)
    buf = buf.at[dest].set(xf[token_of], mode="drop")
    buf = buf.reshape(e, cap, d)
    buf = shard(buf, P("model", None, None))                    # EP
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, params["wg"])) \
        * jnp.einsum("ecd,edf->ecf", buf, params["wu"])
    out = jnp.einsum("ecf,efd->ecd", h, params["wd"]).reshape(e * cap, d)

    gate_of = gates.reshape(-1)[sort_idx].astype(x.dtype)
    safe_dest = jnp.where(keep, dest, 0)
    contrib = out[safe_dest] * (gate_of * keep)[:, None]
    y = jnp.zeros((t, d), x.dtype).at[token_of].add(contrib)
    y = y.reshape(b, s, d)
    if "shared" in params:
        y = y + mlp(params["shared"], x)
    return y, aux


def moe_ffn_reference(params, x, cfg: ModelConfig):
    """O(E*T) dense oracle for tests: every expert computes every token."""
    b, s, d = x.shape
    xf = x.reshape(-1, d)
    logits = xf.astype(jnp.float32) @ params["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, cfg.experts_per_tok)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    h = jax.nn.silu(jnp.einsum("td,edf->etf", xf, params["wg"])) \
        * jnp.einsum("td,edf->etf", xf, params["wu"])
    oute = jnp.einsum("etf,efd->etd", h, params["wd"])          # (E,T,D)
    sel = jax.nn.one_hot(idx, cfg.n_experts, dtype=jnp.float32) # (T,k,E)
    w = jnp.einsum("tke,tk->et", sel, gates).astype(x.dtype)
    y = jnp.einsum("etd,et->td", oute, w).reshape(b, s, d)
    if "shared" in params:
        y = y + mlp(params["shared"], x)
    return y

"""Unified model zoo: dense / MoE / SSM / hybrid / VLM / enc-dec families.

All families share the same contract:

  init_params(cfg, key)                     -> params pytree
  loss_fn(cfg, params, batch)               -> (scalar loss, metrics)
  init_serve_cache(cfg, batch, max_len)     -> cache pytree
  prefill(cfg, params, batch, cache)        -> (last_logits, cache)
  decode_step(cfg, params, tokens, cache, batch) -> (logits, cache)

Blocks are stacked with a leading layer axis and driven by ``jax.lax.scan``
(one compiled block body regardless of depth — essential for the 126-layer
dry-runs), with per-layer remat for training.

batch dict keys by family:
  all      : tokens (B, S) int32
  vlm      : + vision (B, n_vis, d_model)   [stub frontend embeddings]
  encdec   : + frames (B, S_enc, d_model)   [stub conv frontend embeddings]
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .config import ModelConfig
from .layers import (HIDDEN, VOCAB_ACT, attention, init_attention, init_cache,
                     init_mla, init_mla_cache, init_mlp, init_moe, mla_attention,
                     mlp, moe_ffn, ninit, rms_norm, set_decode_kv_bucket, shard,
                     shard_modal)
from .ssm import init_mamba_block, init_mamba_cache, mamba_block

AUX_LOSS_WEIGHT = 0.01
MTP_LOSS_WEIGHT = 0.3


def _stack_init(fn, key, n):
    return jax.vmap(fn)(jax.random.split(key, n))


def _slice_tree(tree, i):
    return jax.tree.map(lambda a: a[i], tree)


# ---------------------------------------------------------------------------
# block init/apply
# ---------------------------------------------------------------------------

def init_dense_block(key, cfg: ModelConfig, d_ff=None, causal_cross=False):
    ks = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.param_dtype)
    d = cfg.d_model
    p = {
        "ln1": jnp.ones((d,), dt),
        "attn": (init_mla(ks[0], cfg) if cfg.use_mla
                 else init_attention(ks[0], cfg)),
        "ln2": jnp.ones((d,), dt),
        "mlp": init_mlp(ks[1], cfg, d_ff=d_ff),
    }
    return p


def apply_dense_block(p, h, cfg: ModelConfig, positions, cache=None,
                      causal=True, layer=None):
    """layer: ``cache`` is the stacked cache of every layer and this block
    updates plane ``layer`` of it in place (``layers.attention``)."""
    x = rms_norm(h, p["ln1"], cfg.norm_eps)
    if cfg.use_mla:
        a, nc = mla_attention(p["attn"], x, cfg, positions, cache=cache,
                              layer=layer)
    else:
        a, nc = attention(p["attn"], x, cfg, positions, causal=causal,
                          cache=cache, layer=layer)
    h = h + a
    h = h + mlp(p["mlp"], rms_norm(h, p["ln2"], cfg.norm_eps))
    return h, nc


def init_moe_block(key, cfg: ModelConfig):
    ks = jax.random.split(key, 3)
    dt = jnp.dtype(cfg.param_dtype)
    d = cfg.d_model
    return {
        "ln1": jnp.ones((d,), dt),
        "attn": (init_mla(ks[0], cfg) if cfg.use_mla
                 else init_attention(ks[0], cfg)),
        "ln2": jnp.ones((d,), dt),
        "moe": init_moe(ks[1], cfg),
    }


def apply_moe_block(p, h, cfg: ModelConfig, positions, cache=None):
    x = rms_norm(h, p["ln1"], cfg.norm_eps)
    if cfg.use_mla:
        a, nc = mla_attention(p["attn"], x, cfg, positions, cache=cache)
    else:
        a, nc = attention(p["attn"], x, cfg, positions, cache=cache)
    h = h + a
    f, aux = moe_ffn(p["moe"], rms_norm(h, p["ln2"], cfg.norm_eps), cfg)
    h = h + f
    return h, nc, aux


def _cross_attend(p_attn, x, cfg: ModelConfig, positions, kv_x=None,
                  kv_cache=None):
    """Cross-attention core: kv from kv_x (compute) or kv_cache ({k, v})."""
    if kv_cache is not None:
        from .layers import _sdpa
        b, s, _ = x.shape
        hd = cfg.resolved_head_dim
        q = (x @ p_attn["wq"]).reshape(b, s, cfg.n_heads, hd)
        out = _sdpa(q, kv_cache["k"], kv_cache["v"], causal=False)
        return out.reshape(b, s, cfg.n_heads * hd) @ p_attn["wo"]
    a, _ = attention(p_attn, x, cfg, positions, causal=False, kv_x=kv_x)
    return a


def init_cross_block(key, cfg: ModelConfig):
    ks = jax.random.split(key, 3)
    dt = jnp.dtype(cfg.param_dtype)
    d = cfg.d_model
    return {
        "lnq": jnp.ones((d,), dt),
        "xattn": init_attention(ks[0], cfg),
        "lnf": jnp.ones((d,), dt),
        "xmlp": init_mlp(ks[1], cfg),
    }


def apply_cross_block(p, h, cfg: ModelConfig, positions, kv_x=None,
                      kv_cache=None):
    """Cross-attention block (vlm): xattn + its own mlp."""
    x = rms_norm(h, p["lnq"], cfg.norm_eps)
    h = h + _cross_attend(p["xattn"], x, cfg, positions, kv_x, kv_cache)
    h = h + mlp(p["xmlp"], rms_norm(h, p["lnf"], cfg.norm_eps))
    return h


def init_decoder_block(key, cfg: ModelConfig):
    """Enc-dec decoder block: self-attn -> cross-attn -> mlp."""
    ks = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.param_dtype)
    d = cfg.d_model
    return {
        "ln1": jnp.ones((d,), dt),
        "attn": init_attention(ks[0], cfg),
        "lnq": jnp.ones((d,), dt),
        "xattn": init_attention(ks[1], cfg),
        "ln2": jnp.ones((d,), dt),
        "mlp": init_mlp(ks[2], cfg),
    }


def apply_decoder_block(p, h, cfg: ModelConfig, positions, enc_out=None,
                        cache=None, kv_cache=None):
    x = rms_norm(h, p["ln1"], cfg.norm_eps)
    a, nc = attention(p["attn"], x, cfg, positions, causal=True, cache=cache)
    h = h + a
    x = rms_norm(h, p["lnq"], cfg.norm_eps)
    h = h + _cross_attend(p["xattn"], x, cfg, positions, enc_out, kv_cache)
    h = h + mlp(p["mlp"], rms_norm(h, p["ln2"], cfg.norm_eps))
    return h, nc


def cross_kv(p, cfg: ModelConfig, kv_x):
    b, skv, _ = kv_x.shape
    hd = cfg.resolved_head_dim
    k = (kv_x @ p["xattn"]["wk"]).reshape(b, skv, cfg.n_kv_heads, hd)
    v = (kv_x @ p["xattn"]["wv"]).reshape(b, skv, cfg.n_kv_heads, hd)
    return {"k": k, "v": v}


# ---------------------------------------------------------------------------
# embeddings / head / loss
# ---------------------------------------------------------------------------

def init_embed(key, cfg: ModelConfig):
    ks = jax.random.split(key, 2)
    dt = jnp.dtype(cfg.param_dtype)
    p = {"embed": ninit(ks[0], (cfg.vocab, cfg.d_model), dt),
         "final_norm": jnp.ones((cfg.d_model,), dt)}
    if not cfg.tie_embeddings:
        p["lm_head"] = ninit(ks[1], (cfg.d_model, cfg.vocab), dt,
                             fan_in=cfg.d_model)
    return p


def embed_tokens(params, cfg, tokens):
    h = params["embed"][tokens]
    return shard_modal(h, HIDDEN)


def lm_logits(params, cfg, h):
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    w = params["lm_head"] if "lm_head" in params else params["embed"].T
    logits = (h @ w).astype(jnp.float32)
    return shard_modal(logits, VOCAB_ACT)


def token_ce(logits, targets):
    """Mean next-token cross-entropy; logits (B,S,V) fp32, targets (B,S)."""
    lp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(lp, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll)


# ---------------------------------------------------------------------------
# family: dense (minicpm, deepseek-7b, granite, llama3-405b)
# ---------------------------------------------------------------------------

def _dense_init(cfg, key):
    ks = jax.random.split(key, 2)
    p = init_embed(ks[0], cfg)
    p["blocks"] = _stack_init(lambda k: init_dense_block(k, cfg), ks[1],
                              cfg.n_layers)
    return p


def _n_blocks(blocks):
    return jax.tree_util.tree_leaves(blocks)[0].shape[0]


def _dense_apply(cfg, params, h, positions, cache=None, kind="train"):
    if cache is None:
        def body(carry, bp):
            h, _ = apply_dense_block(bp, carry, cfg, positions)
            return h, None

        f = jax.checkpoint(body) if (cfg.remat and kind == "train") else body
        h, _ = jax.lax.scan(f, h, params["blocks"])
        return h, None, 0.0

    # the stacked cache rides in the carry: each layer writes its new rows
    # into its own plane in place, so no step builds a second cache; len
    # advances once, after the loop
    def body(carry, xs):
        h, c = carry
        bp, i = xs
        return apply_dense_block(bp, h, cfg, positions, cache=c, layer=i), \
            None

    n_blk = _n_blocks(params["blocks"])
    unroll = n_blk if (cfg.serve_unroll and kind == "decode") else 1
    (h, cache), _ = jax.lax.scan(body, (h, cache),
                                 (params["blocks"], jnp.arange(n_blk)),
                                 unroll=unroll)
    return h, {**cache, "len": cache["len"] + h.shape[1]}, 0.0


# ---------------------------------------------------------------------------
# family: moe (llama4-maverick interleave=2; deepseek-v3 interleave=1 + MTP)
# ---------------------------------------------------------------------------

def _moe_init(cfg, key):
    ks = jax.random.split(key, 4)
    p = init_embed(ks[0], cfg)
    il = cfg.moe_interleave
    n_groups = cfg.n_layers // il
    def init_group(k):
        k1, k2 = jax.random.split(k)
        g = {"moe": init_moe_block(k1, cfg)}
        if il > 1:
            g["dense"] = _stack_init(lambda kk: init_dense_block(kk, cfg),
                                     k2, il - 1)
        return g
    p["groups"] = _stack_init(init_group, ks[1], n_groups)
    if cfg.mtp_depth:
        p["mtp_proj"] = ninit(ks[2], (2 * cfg.d_model, cfg.d_model),
                              jnp.dtype(cfg.param_dtype), fan_in=2 * cfg.d_model)
        p["mtp_block"] = init_dense_block(ks[3], cfg)
    return p


def _moe_apply(cfg, params, h, positions, cache=None, kind="train"):
    il = cfg.moe_interleave

    def body(carry, xs):
        h, aux = carry
        if cache is None:
            gp = xs
            dc = mc = None
        else:
            gp, (dc, mc) = xs
        new_dc = []
        if il > 1:
            for i in range(il - 1):
                bp = _slice_tree(gp["dense"], i)
                c = None if dc is None else _slice_tree(dc, i)
                h, nc = apply_dense_block(bp, h, cfg, positions, cache=c)
                new_dc.append(nc)
        h, nmc, a = apply_moe_block(gp["moe"], h, cfg, positions, cache=mc)
        ys = None
        if cache is not None:
            stacked_dc = jax.tree.map(lambda *a: jnp.stack(a), *new_dc) \
                if new_dc else dc
            ys = (stacked_dc, nmc)
        return (h, aux + a), ys

    if cfg.remat and kind == "train":
        if cfg.remat_policy == "save_moe":
            pol = jax.checkpoint_policies.save_only_these_names("moe_y")
            f = jax.checkpoint(body, policy=pol)
        else:
            f = jax.checkpoint(body)
    else:
        f = body
    xs = params["groups"] if cache is None else (params["groups"], cache)
    aux0 = jnp.zeros((), jnp.float32)
    (h, aux), new_cache = jax.lax.scan(f, (h, aux0), xs)
    return h, new_cache, aux / cfg.n_layers


# ---------------------------------------------------------------------------
# family: ssm (mamba2) and hybrid (zamba2)
# ---------------------------------------------------------------------------

def _ssm_init(cfg, key):
    ks = jax.random.split(key, 3)
    p = init_embed(ks[0], cfg)
    p["blocks"] = _stack_init(lambda k: init_mamba_block(k, cfg), ks[1],
                              cfg.n_layers)
    if cfg.family == "hybrid":
        p["shared_attn"] = init_dense_block(ks[2], cfg)
    return p


def _ssm_apply(cfg, params, h, positions, cache=None, kind="train",
               layer_offset=0, app_offset=0):
    """layer_offset/app_offset: pipeline-stage execution (models.staging)
    runs a slice of the stacked blocks — block indices start at
    ``layer_offset`` and the sliced shared-attention cache starts at
    absolute app index ``app_offset``.  Defaults reproduce the monolithic
    path exactly."""
    shared = params.get("shared_attn")
    # a stage slice with no shared-attention call site carries neither the
    # shared params nor the shared cache; treat it as a pure-ssm run
    every = cfg.hybrid_attn_every if shared is not None else 0

    def body(carry, xs):
        h, shared_kv, mc = carry
        bp, idx = xs

        if every:
            def with_attn(h, skv):
                app = idx // every - app_offset
                if skv is None:                       # training: no cache
                    h2, _ = apply_dense_block(shared, h, cfg, positions)
                    return h2, skv
                c = _slice_tree(skv, app)
                h2, nc = apply_dense_block(shared, h, cfg, positions, cache=c)
                nskv = jax.tree.map(
                    lambda full, new: jax.lax.dynamic_update_index_in_dim(
                        full, new.astype(full.dtype), app, 0), skv, nc)
                return h2, nskv
            def no_attn(h, skv):
                return h, skv
            is_app = (idx % every) == 0
            if shared_kv is None:
                h, _ = jax.lax.cond(is_app,
                                    lambda hh: with_attn(hh, None),
                                    lambda hh: (hh, None), h)
            else:
                h, shared_kv = jax.lax.cond(
                    is_app, with_attn, no_attn, h, shared_kv)

        # with a cache, this layer's conv buffer and state go back into the
        # stacked cache in place; len advances once, after the loop
        y, mc = mamba_block(bp, rms_norm(h, bp["pre_norm"], cfg.norm_eps),
                            cfg, cache=mc,
                            layer=None if mc is None else idx - layer_offset)
        return (h + y, shared_kv, mc), None

    n_blk = _n_blocks(params["blocks"])
    idxs = jnp.arange(layer_offset, layer_offset + n_blk)
    shared_kv0 = mc0 = None
    if cache is not None:
        shared_kv0 = cache.get("shared")
        mc0 = cache["mamba"]
        # the conv buffer keeps the dtype the block computes it in
        buf_dt = jnp.result_type(mc0["conv_buf"], params["blocks"]["in_proj"])
        mc0 = {**mc0, "conv_buf": mc0["conv_buf"].astype(buf_dt)}
    f = jax.checkpoint(body) if (cfg.remat and kind == "train") else body
    (h, shared_kv, mc), _ = jax.lax.scan(f, (h, shared_kv0, mc0),
                                         (params["blocks"], idxs))
    new_cache = None
    if cache is not None:
        new_cache = {"mamba": {**mc, "len": mc["len"] + h.shape[1]}}
        if shared_kv is not None:
            new_cache["shared"] = shared_kv
    return h, new_cache, 0.0


# ---------------------------------------------------------------------------
# family: vlm (llama-3.2-vision): groups of self blocks + one cross block
# ---------------------------------------------------------------------------

def _vlm_init(cfg, key):
    ks = jax.random.split(key, 2)
    p = init_embed(ks[0], cfg)
    k_self = cfg.cross_attn_every
    n_groups = cfg.n_layers // (k_self + 1)
    def init_group(k):
        k1, k2 = jax.random.split(k)
        return {"self": _stack_init(lambda kk: init_dense_block(kk, cfg),
                                    k1, k_self),
                "cross": init_cross_block(k2, cfg)}
    p["groups"] = _stack_init(init_group, ks[1], n_groups)
    return p


def _vlm_apply(cfg, params, h, positions, vision=None, cache=None,
               kind="train"):
    k_self = cfg.cross_attn_every

    def body(carry, xs):
        h = carry
        if cache is None:
            gp = xs
            sc = xc = None
        else:
            gp, (sc, xc) = xs
        new_sc = []
        for i in range(k_self):
            bp = _slice_tree(gp["self"], i)
            c = None if sc is None else _slice_tree(sc, i)
            h, nc = apply_dense_block(bp, h, cfg, positions, cache=c)
            new_sc.append(nc)
        if xc is not None:                      # serve: precomputed vision K/V
            h = apply_cross_block(gp["cross"], h, cfg, positions, kv_cache=xc)
        else:
            h = apply_cross_block(gp["cross"], h, cfg, positions, kv_x=vision)
        ys = None
        if cache is not None:
            ys = (jax.tree.map(lambda *a: jnp.stack(a), *new_sc), xc)
        return h, ys

    f = jax.checkpoint(body) if (cfg.remat and kind == "train") else body
    xs = params["groups"] if cache is None else (params["groups"], cache)
    h, new_cache = jax.lax.scan(f, h, xs)
    return h, new_cache, 0.0


# ---------------------------------------------------------------------------
# family: encdec (whisper)
# ---------------------------------------------------------------------------

def _encdec_init(cfg, key):
    ks = jax.random.split(key, 5)
    p = init_embed(ks[0], cfg)
    dt = jnp.dtype(cfg.param_dtype)
    d = cfg.d_model
    # conv frontend stub: one projection standing in for the mel conv stack
    p["frontend"] = ninit(ks[1], (d, d), dt, fan_in=d)
    p["enc_blocks"] = _stack_init(lambda k: init_dense_block(k, cfg), ks[2],
                                  cfg.n_enc_layers)
    p["enc_norm"] = jnp.ones((d,), dt)
    p["dec_blocks"] = _stack_init(lambda k: init_decoder_block(k, cfg), ks[3],
                                  cfg.n_layers)
    return p


def encode(cfg, params, frames):
    h = frames @ params["frontend"]
    h = shard_modal(h, HIDDEN)
    pos = jnp.broadcast_to(jnp.arange(frames.shape[1])[None],
                           frames.shape[:2])

    def body(h, bp):
        h, _ = apply_dense_block(bp, h, cfg, pos, causal=False)
        return h, None

    h, _ = jax.lax.scan(body, h, params["enc_blocks"])
    return rms_norm(h, params["enc_norm"], cfg.norm_eps)


def _encdec_apply(cfg, params, h, positions, enc_out=None, cache=None,
                  kind="train"):
    def body(carry, xs):
        h = carry
        if cache is None:
            bp = xs
            sc = xc = None
        else:
            bp, (sc, xc) = xs
        h, nsc = apply_decoder_block(bp, h, cfg, positions, enc_out=enc_out,
                                     cache=sc, kv_cache=xc)
        ys = (nsc, xc) if cache is not None else None
        return h, ys

    f = jax.checkpoint(body) if (cfg.remat and kind == "train") else body
    xs = params["dec_blocks"] if cache is None else (params["dec_blocks"], cache)
    h, new_cache = jax.lax.scan(f, h, xs)
    return h, new_cache, 0.0


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

_INITS = {"dense": _dense_init, "moe": _moe_init, "ssm": _ssm_init,
          "hybrid": _ssm_init, "vlm": _vlm_init, "encdec": _encdec_init}


def init_params(cfg: ModelConfig, key):
    return _INITS[cfg.family](cfg, key)


def _backbone(cfg, params, h, positions, batch, cache=None, kind="train"):
    if cfg.family in ("dense",):
        return _dense_apply(cfg, params, h, positions, cache, kind)
    if cfg.family == "moe":
        return _moe_apply(cfg, params, h, positions, cache, kind)
    if cfg.family in ("ssm", "hybrid"):
        return _ssm_apply(cfg, params, h, positions, cache, kind)
    if cfg.family == "vlm":
        return _vlm_apply(cfg, params, h, positions,
                          vision=batch.get("vision"), cache=cache, kind=kind)
    if cfg.family == "encdec":
        enc_out = batch.get("enc_out")
        if enc_out is None and cache is None:
            enc_out = encode(cfg, params, batch["frames"])
        return _encdec_apply(cfg, params, h, positions, enc_out=enc_out,
                             cache=cache, kind=kind)
    raise ValueError(cfg.family)


def forward(cfg: ModelConfig, params, batch, kind="train"):
    """Full-sequence causal forward -> (logits, aux)."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    h = embed_tokens(params, cfg, tokens)
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    h, _, aux = _backbone(cfg, params, h, positions, batch, None, kind)
    return lm_logits(params, cfg, h), (h, aux)


def loss_fn(cfg: ModelConfig, params, batch):
    logits, (h, aux) = forward(cfg, params, batch, kind="train")
    targets = batch["tokens"]
    loss = token_ce(logits[:, :-1], targets[:, 1:])
    metrics = {"ce": loss}
    if cfg.n_experts:
        loss = loss + AUX_LOSS_WEIGHT * aux
        metrics["aux"] = aux
    if cfg.mtp_depth:
        # multi-token prediction: predict t+2 from (h_t, embed(token_{t+1}))
        emb_next = embed_tokens(params, cfg, targets)
        cat = jnp.concatenate([h[:, :-1], emb_next[:, 1:]], axis=-1)
        h2 = cat @ params["mtp_proj"]
        pos = jnp.broadcast_to(jnp.arange(h2.shape[1])[None], h2.shape[:2])
        h2, _ = apply_dense_block(params["mtp_block"], h2, cfg, pos)
        mtp_logits = lm_logits(params, cfg, h2)
        mtp = token_ce(mtp_logits[:, :-1], targets[:, 2:])
        loss = loss + MTP_LOSS_WEIGHT * mtp
        metrics["mtp"] = mtp
    metrics["loss"] = loss
    return loss, metrics


# ---- serving ---------------------------------------------------------------

def init_serve_cache(cfg: ModelConfig, batch_size: int, max_len: int,
                     batch=None, params=None):
    """Allocate an empty decode cache (zeros); prefill fills it."""
    dt = jnp.bfloat16
    if cfg.family == "dense":
        one = lambda: init_cache(cfg, batch_size, max_len, dtype=dt)
        return jax.tree.map(lambda *a: jnp.stack(a),
                            *[one() for _ in range(cfg.n_layers)])
    if cfg.family == "moe":
        il = cfg.moe_interleave
        n_groups = cfg.n_layers // il
        mk = ((lambda: init_mla_cache(cfg, batch_size, max_len, dt))
              if cfg.use_mla else
              (lambda: init_cache(cfg, batch_size, max_len, dtype=dt)))
        def group_cache():
            dc = None
            if il > 1:
                dc = jax.tree.map(lambda *a: jnp.stack(a),
                                  *[mk() for _ in range(il - 1)])
            return (dc, mk())
        gs = [group_cache() for _ in range(n_groups)]
        return jax.tree.map(lambda *a: jnp.stack(a), *gs)
    if cfg.family in ("ssm", "hybrid"):
        mc = [init_mamba_cache(cfg, batch_size, dt)
              for _ in range(cfg.n_layers)]
        out = {"mamba": jax.tree.map(lambda *a: jnp.stack(a), *mc)}
        if cfg.hybrid_attn_every:
            n_apps = -(-cfg.n_layers // cfg.hybrid_attn_every)
            sc = [init_cache(cfg, batch_size, max_len, dtype=dt)
                  for _ in range(n_apps)]
            out["shared"] = jax.tree.map(lambda *a: jnp.stack(a), *sc)
        return out
    if cfg.family == "vlm":
        k_self = cfg.cross_attn_every
        n_groups = cfg.n_layers // (k_self + 1)
        hd = cfg.resolved_head_dim
        def group_cache():
            sc = jax.tree.map(lambda *a: jnp.stack(a),
                              *[init_cache(cfg, batch_size, max_len, dtype=dt)
                                for _ in range(k_self)])
            xc = {"k": jnp.zeros((batch_size, cfg.vision_tokens,
                                  cfg.n_kv_heads, hd), dt),
                  "v": jnp.zeros((batch_size, cfg.vision_tokens,
                                  cfg.n_kv_heads, hd), dt)}
            return (sc, xc)
        gs = [group_cache() for _ in range(n_groups)]
        return jax.tree.map(lambda *a: jnp.stack(a), *gs)
    if cfg.family == "encdec":
        hd = cfg.resolved_head_dim
        def layer_cache(enc_len):
            sc = init_cache(cfg, batch_size, max_len, dtype=dt)
            xc = {"k": jnp.zeros((batch_size, enc_len, cfg.n_kv_heads, hd), dt),
                  "v": jnp.zeros((batch_size, enc_len, cfg.n_kv_heads, hd), dt)}
            return (sc, xc)
        enc_len = batch["frames"].shape[1] if batch else max_len
        ls = [layer_cache(enc_len) for _ in range(cfg.n_layers)]
        return jax.tree.map(lambda *a: jnp.stack(a), *ls)
    raise ValueError(cfg.family)


def fill_vlm_cross(cfg, groups, cache, vision):
    """Fill the cross-attention K/V of ``cache`` from vision embeddings;
    ``groups``/``cache`` may be any contiguous slice of the stacked groups
    (pipeline stages pass their own slice)."""
    def per_group(gc):
        gp, (sc, xc) = gc
        new = cross_kv(gp["cross"], cfg, vision)
        return (sc, jax.tree.map(lambda a, b: b.astype(a.dtype), xc, new))
    return jax.lax.map(per_group, (groups, cache))


def fill_encdec_cross(cfg, dec_blocks, cache, enc_out):
    """Fill decoder cross-attention K/V from a precomputed encoder output;
    slice-friendly like :func:`fill_vlm_cross`."""
    def per_layer(bc):
        bp, (sc, xc) = bc
        new = cross_kv(bp, cfg, enc_out)
        return (sc, jax.tree.map(lambda a, b: b.astype(a.dtype), xc, new))
    return jax.lax.map(per_layer, (dec_blocks, cache))


def _fill_cross_caches(cfg, params, cache, batch):
    """Compute cross-attention K/V once per request (vlm / encdec)."""
    if cfg.family == "vlm":
        return fill_vlm_cross(cfg, params["groups"], cache, batch["vision"])
    if cfg.family == "encdec":
        enc_out = encode(cfg, params, batch["frames"])
        return fill_encdec_cross(cfg, params["dec_blocks"], cache, enc_out)
    return cache


def prefill(cfg: ModelConfig, params, batch, cache):
    """Run the prompt through the model, filling the cache.
    Returns (last-token logits, cache)."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    cache = _fill_cross_caches(cfg, params, cache, batch)
    h = embed_tokens(params, cfg, tokens)
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    h, cache, _ = _backbone(cfg, params, h, positions, batch, cache,
                            kind="prefill")
    return lm_logits(params, cfg, h[:, -1:]), cache


def decode_step(cfg: ModelConfig, params, tokens, cache, batch=None,
                kv_bucket: int | None = None):
    """One decode step: tokens (B, 1) -> (logits (B,1,V), cache).

    kv_bucket: static (trace-time) bound on the active cache length —
    attention reads only rows [0, kv_bucket) instead of all max_len rows
    (repro.serve's length-aware fast path).  Callers must guarantee
    max(len) + 1 <= kv_bucket; None attends over the full cache."""
    b = tokens.shape[0]
    h = embed_tokens(params, cfg, tokens)
    ln = _cache_len(cfg, cache)
    positions = jnp.broadcast_to(ln[:, None], (b, 1))
    set_decode_kv_bucket(kv_bucket)
    try:
        h, cache, _ = _backbone(cfg, params, h, positions, batch or {}, cache,
                                kind="decode")
    finally:
        set_decode_kv_bucket(None)
    return lm_logits(params, cfg, h), cache


def _cache_len(cfg, cache):
    """Current sequence length from the cache pytree (layer 0's counter)."""
    if cfg.family == "dense":
        return cache["len"][0]
    if cfg.family == "moe":
        return cache[1]["len"][0]
    if cfg.family in ("ssm", "hybrid"):
        return cache["mamba"]["len"][0]
    if cfg.family == "vlm":
        return cache[0]["len"][0, 0]
    if cfg.family == "encdec":
        return cache[0]["len"][0]
    raise ValueError(cfg.family)

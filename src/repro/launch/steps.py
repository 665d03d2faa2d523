"""Jittable train / prefill / decode steps + input_specs for every cell.

``input_specs(cfg, shape)`` returns ShapeDtypeStruct stand-ins for every
model input (weak-type-correct, shardable, no device allocation) — the
dry-run lowers against these.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import (decode_step, init_params, init_serve_cache,
                          loss_fn, prefill)
from repro.models.config import ModelConfig, ShapeConfig
from repro.optim import adamw_init, adamw_update, make_schedule


# ---------------------------------------------------------------------------
# steps
# ---------------------------------------------------------------------------

def make_train_step(cfg: ModelConfig, *, grad_compress_bits: int = 0):
    """Returns train_step(params, opt, batch) -> (params, opt, metrics).

    grad_compress_bits: 0 = off; 8 = int8-quantize gradients before the
    cross-pod reduction (beyond-paper distributed-optimization trick reusing
    the activation-compression math; see kernels/quantize/ref.py)."""
    sched = make_schedule(cfg.lr_schedule)

    def train_step(params, opt, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, batch), has_aux=True)(params)
        if grad_compress_bits:
            from repro.kernels.quantize.ref import fake_quantize
            grads = jax.tree.map(
                functools.partial(fake_quantize, bits=grad_compress_bits),
                grads)
        lr = sched(opt.step)
        params, opt, om = adamw_update(params, grads, opt, lr)
        metrics = dict(metrics)
        metrics.update(om)
        metrics["lr"] = lr
        return params, opt, metrics

    return train_step


def make_prefill_step(cfg: ModelConfig):
    def prefill_step(params, batch, cache):
        return prefill(cfg, params, batch, cache)
    return prefill_step


def make_decode_step(cfg: ModelConfig):
    def serve_step(params, tokens, cache, extras):
        return decode_step(cfg, params, tokens, cache, extras)
    return serve_step


# -- greedy serving steps (repro.serve's jit units) -------------------------
# Greedy argmax happens *inside* the step so the serving loop never has to
# pull logits to the host; logits are still returned for callers that want
# them (consistency tests) — unread outputs cost nothing under async
# dispatch.

def make_greedy_prefill_step(cfg: ModelConfig):
    """prefill + argmax: (params, batch, cache) -> (tokens, logits, cache)."""
    def step(params, batch, cache):
        logits, cache = prefill(cfg, params, batch, cache)
        return jnp.argmax(logits, -1).astype(jnp.int32), logits, cache
    return step


def make_greedy_decode_step(cfg: ModelConfig):
    """One greedy decode step with a static kv bucket:
    (params, tokens, cache, kv_bucket) -> (tokens, logits, cache).

    ``kv_bucket`` must be a static argument of the surrounding jit — each
    bucket traces its own length-aware attention (see
    models.layers.set_decode_kv_bucket).  Its device ops are named under
    ``decode/model`` and ``decode/sample``; jitted, the program is
    ``jit_step``."""
    def step(params, tokens, cache, kv_bucket=None):
        with jax.named_scope("decode/model"):
            logits, cache = decode_step(cfg, params, tokens, cache,
                                        kv_bucket=kv_bucket)
        with jax.named_scope("decode/sample"):
            toks = jnp.argmax(logits, -1).astype(jnp.int32)
        return toks, logits, cache
    return step


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStructs, no allocation)
# ---------------------------------------------------------------------------

def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def batch_specs(cfg: ModelConfig, b: int, s: int):
    out = {"tokens": _sds((b, s), jnp.int32)}
    if cfg.family == "vlm":
        out["vision"] = _sds((b, cfg.vision_tokens, cfg.d_model), jnp.bfloat16)
    if cfg.family == "encdec":
        out["frames"] = _sds((b, s, cfg.d_model), jnp.bfloat16)
    return out


def params_specs(cfg: ModelConfig):
    return jax.eval_shape(lambda k: init_params(cfg, k),
                          jax.random.PRNGKey(0))


def opt_specs(cfg: ModelConfig, params_shape):
    dt = jnp.dtype(cfg.opt_state_dtype)
    return jax.eval_shape(
        lambda p: adamw_init(p, state_dtype=dt), params_shape)


def cache_specs(cfg: ModelConfig, b: int, max_len: int):
    # init_serve_cache only inspects batch shapes, so ShapeDtypeStructs work
    return jax.eval_shape(
        lambda: init_serve_cache(cfg, b, max_len,
                                 batch=batch_specs(cfg, b, max_len)))


def input_specs(cfg: ModelConfig, shape: ShapeConfig):
    """All step inputs for a (arch x shape) cell, as ShapeDtypeStructs."""
    b, s = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        p = params_specs(cfg)
        return {
            "params": p,
            "opt": opt_specs(cfg, p),
            "batch": batch_specs(cfg, b, s),
        }
    if shape.kind == "prefill":
        return {
            "params": params_specs(cfg),
            "batch": batch_specs(cfg, b, s),
            "cache": cache_specs(cfg, b, s),
        }
    if shape.kind == "decode":
        return {
            "params": params_specs(cfg),
            "tokens": _sds((b, 1), jnp.int32),
            "cache": cache_specs(cfg, b, s),
            "extras": ({"vision": _sds((b, cfg.vision_tokens, cfg.d_model),
                                       jnp.bfloat16)}
                       if cfg.family == "vlm" else {}),
        }
    raise ValueError(shape.kind)

"""Operations and bytes the algorithm needs, from shapes alone.

Counted from the configuration file (the published keys), never from what
XLA compiled: a multiply-add is two operations, weights are bf16 (2 bytes),
the dense family's KV cache is bf16, and the SSM state is float32 as the
serving cache holds it.  Element-wise work (norms, activations, RoPE,
softmax) is left out; it is a few percent of the matmuls at these widths.
"""

from __future__ import annotations

import json
from pathlib import Path

from .reference.common import vocab_rows

BF16 = 2
F32 = 4


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; an unknown chip is an error."""
    table = json.loads((Path(__file__).parent / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(k for k in table if k != 'source')}")
    return table[device_kind]


# -- dense (Granite-style) ---------------------------------------------------

def _dense(c):
    d, h, kv = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"]
    hd = d // h
    layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * c["intermediate_size"]
    return d, h, kv, hd, layer


def dense_weight_params(c) -> int:
    d, _, _, _, layer = _dense(c)
    head = 0 if c["tie_word_embeddings"] else d * vocab_rows(c)
    return c["num_hidden_layers"] * layer + vocab_rows(c) * d + head + \
        (2 * c["num_hidden_layers"] + 1) * d


def dense_token_flops(c, context: int) -> float:
    """One token through every layer, attending over ``context`` rows, and
    the output head."""
    d, h, _, hd, layer = _dense(c)
    attn = 4 * context * h * hd                       # q.k and p.v
    return c["num_hidden_layers"] * (2 * layer + attn) + 2 * d * vocab_rows(c)


def dense_prefill_flops(c, prompt: int) -> float:
    """A prompt through every layer with causal attention (each position
    attends to itself and those before it), and the head at its last."""
    d, h, _, hd, layer = _dense(c)
    attn = 4 * h * hd * prompt * (prompt + 1) / 2
    return (c["num_hidden_layers"] * (2 * layer * prompt + attn)
            + 2 * d * vocab_rows(c))


def dense_decode_step(c, batch: int, bucket: int) -> tuple[float, float]:
    """(operations, bytes) of one batched decode step whose attention reads
    ``bucket`` cache rows per slot: every weight once, the K and V rows of
    every slot at the bucket, and the new rows written."""
    _, _, kv, hd, _ = _dense(c)
    flops = batch * dense_token_flops(c, bucket)
    rows = 2 * c["num_hidden_layers"] * kv * hd * BF16        # K and V
    return flops, dense_weight_params(c) * BF16 + batch * rows * (bucket + 1)


# -- ssm (Mamba-2) -------------------------------------------------------------

def _ssm(c):
    d, n = c["d_model"], c["d_state"]
    di = c["expand"] * d
    h = di // c["headdim"]
    proj = d * (2 * di + 2 * n + h) + di * d
    return d, n, di, h, proj


def ssm_weight_params(c) -> int:
    d, n, di, h, proj = _ssm(c)
    layer = proj + c["d_conv"] * (di + 2 * n) + (di + 2 * n) + 3 * h + di + d
    head = 0 if c["tie_embeddings"] else d * vocab_rows(c)
    return c["n_layer"] * layer + vocab_rows(c) * d + head + d


def ssm_token_flops(c) -> float:
    """One token through every layer: projections, the convolution, and
    the state update and read-out (2 operations each per state element)."""
    d, n, di, h, proj = _ssm(c)
    state = 2 * 2 * di * n                    # dt*x*B^T into, state.C out of
    conv = 2 * c["d_conv"] * (di + 2 * n)
    return c["n_layer"] * (2 * proj + state + conv) + 2 * d * vocab_rows(c)


def ssm_prefill_flops(c, prompt: int) -> float:
    d = c["d_model"]
    head = 2 * d * vocab_rows(c)
    return (ssm_token_flops(c) - head) * prompt + head


def ssm_decode_step(c, batch: int, bucket: int) -> tuple[float, float]:
    """(operations, bytes) of one batched decode step: every weight once,
    and each slot's float32 state and bf16 convolution buffer read and
    written.  The kv bucket plays no part."""
    d, n, di, h, _ = _ssm(c)
    per_slot = (di * n * F32 + (c["d_conv"] - 1) * (di + 2 * n) * BF16) * 2
    return (batch * ssm_token_flops(c),
            ssm_weight_params(c) * BF16 + batch * c["n_layer"] * per_slot)


# -- by family -------------------------------------------------------------------

def decode_step(family: str, c: dict, batch: int, bucket: int):
    return (dense_decode_step if family == "dense" else ssm_decode_step)(
        c, batch, bucket)


def request_flops(family: str, c: dict, prompt: int, gen: int) -> float:
    """Useful operations of one served request: its prompt's prefill (which
    yields the first token) and gen - 1 decode steps at the context each
    actually has."""
    if family == "dense":
        decode = sum(dense_token_flops(c, prompt + i + 1)
                     for i in range(gen - 1))
        return dense_prefill_flops(c, prompt) + decode
    return ssm_prefill_flops(c, prompt) + (gen - 1) * ssm_token_flops(c)

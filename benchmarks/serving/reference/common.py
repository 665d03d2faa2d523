"""What the plain references share: matmuls at a stated precision, RMS
norm, sequence padding, and the comparison with what was served.

The references compute in float32 with every matmul at
``Precision.HIGHEST`` (on a TPU a float32 matmul otherwise runs in bf16
passes).  ``matmul_dtype`` rounds both operands of every contraction to a
lower type first and accumulates in float32: with ``float8_e4m3fn`` that
is the control, the reference computed in the precision below the
configuration's bf16.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
PAD_TO = 256          # sequences are padded to a multiple of this: few shapes


def vocab_rows(c: dict) -> int:
    """Rows of the embedding and of the logits: the vocabulary, padded up
    to a multiple of ``pad_vocab_size_multiple`` where the configuration
    pads it."""
    m = c.get("pad_vocab_size_multiple", 1)
    return -(-c["vocab_size"] // m) * m


def rnd(x, matmul_dtype):
    """Round ``x`` to ``matmul_dtype`` (a dtype name) and back to float32."""
    if matmul_dtype == "float32":
        return x.astype(jnp.float32)
    return x.astype(jnp.dtype(matmul_dtype)).astype(jnp.float32)


def mm(a, b, matmul_dtype="float32"):
    return jnp.matmul(rnd(a, matmul_dtype), rnd(b, matmul_dtype),
                      precision=HIGHEST)


def einsum(spec, a, b, matmul_dtype="float32"):
    return jnp.einsum(spec, rnd(a, matmul_dtype), rnd(b, matmul_dtype),
                      precision=HIGHEST)


def rms_norm(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * w.astype(jnp.float32)


def padded(tokens: np.ndarray) -> np.ndarray:
    """(T,) tokens padded with zeros to a multiple of PAD_TO.  Both
    references are causal, so padding after the last position changes
    nothing before it."""
    t = len(tokens)
    out = np.zeros(-(-t // PAD_TO) * PAD_TO, np.int32)
    out[:t] = tokens
    return out


def padded_positions(positions) -> np.ndarray:
    """Positions padded (with the last one) to a multiple of PAD_TO."""
    pos = np.asarray(positions, np.int32).reshape(-1)
    out = np.full(-(-len(pos) // PAD_TO) * PAD_TO, pos[-1], np.int32)
    out[:len(pos)] = pos
    return out


def sequence(prompt: np.ndarray, served: np.ndarray):
    """The prompt followed by the served tokens, and the positions whose
    logits chose each served token: the last prompt position for the
    first (admission prefill), then one per decode step."""
    prompt = np.asarray(prompt, np.int32).reshape(-1)
    served = np.asarray(served, np.int32).reshape(-1)
    seq = np.concatenate([prompt, served[:-1]])
    pos = np.arange(len(prompt) - 1, len(seq))
    return seq, pos


def served_gap(ref_logits: np.ndarray, served: np.ndarray) -> float:
    """Widest gap by which a served token's reference logit lies below the
    reference's best logit at that position (0 where greedy agrees)."""
    served = np.asarray(served).reshape(-1)
    best = ref_logits.max(axis=-1)
    got = ref_logits[np.arange(len(served)), served]
    return float((best - got).max())


def control_gap(ref_logits: np.ndarray, ctl_logits: np.ndarray) -> float:
    """The control's reading: the gap, in the reference, of the token the
    lower precision puts first at each position."""
    return served_gap(ref_logits, ctl_logits.argmax(axis=-1))

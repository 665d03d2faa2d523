"""The plain float32 references against the program's prefill-then-decode
logits, at the smoke presets on the CPU, with the benchmark's weights."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.serving import bench as B
from benchmarks.serving.reference import common

# The program computes in bf16 (8 significant bits) and the reference in
# float32; over two layers at width 64 their logits differ by rounding.  The
# largest difference seen here was 0.0115 of the largest logit (dense) and
# 0.0164 (ssm); the limit, 2**-5 of it, is 8 bf16 ulps.
REL_TOL = 2.0 ** -5


def engine_and_weights(spec, seed):
    from repro.serve import ServeEngine
    cfg = B.program_config(spec.config)
    fam = B.importlib.import_module(f"benchmarks.serving.reference."
                                    f"{spec.config['family']}")
    params = fam.make_weights(spec.config["config"], jax.random.PRNGKey(seed))
    B.check_layout(cfg, params)
    return ServeEngine(cfg, params, max_len=96, kv_block=16), fam, params


@pytest.mark.parametrize("family", ["dense", "ssm"])
@pytest.mark.parametrize("prompt_len,gen_len", [(8, 12), (40, 20), (17, 5)])
def test_reference_matches_prefill_then_decode(spec_of, family, prompt_len,
                                               gen_len):
    spec = spec_of(family)
    eng, fam, params = engine_and_weights(spec, prompt_len)
    rng = np.random.default_rng(prompt_len)
    prompt = rng.integers(0, 256, (1, prompt_len), dtype=np.int32)
    toks, logits = eng.generate({"tokens": jnp.asarray(prompt)}, gen_len,
                                collect_logits=True)
    seq, pos = common.sequence(prompt, toks[0])
    ref = fam.logits(params, spec.config["config"], seq, pos)
    assert ref.shape == (gen_len, 256)
    err = np.abs(logits[0] - ref).max() / np.abs(ref).max()
    assert err <= REL_TOL, err
    # greedy agrees wherever the reference's top two are apart
    top2 = np.sort(ref, axis=-1)[:, -2:]
    clear = (top2[:, 1] - top2[:, 0]) > 2 * REL_TOL * np.abs(ref).max()
    assert (toks[0][clear] == ref.argmax(-1)[clear]).all()
    assert common.served_gap(ref, toks[0]) <= 2 * REL_TOL * np.abs(ref).max()


@pytest.mark.parametrize("family", ["dense", "ssm"])
def test_reference_sees_a_changed_weight(spec_of, family):
    spec = spec_of(family)
    _, fam, params = engine_and_weights(spec, 3)
    seq = np.arange(30, dtype=np.int32) % 256
    pos = np.arange(29, 30)
    a = fam.logits(params, spec.config["config"], seq, pos)
    blocks = dict(params["blocks"])
    key = "ln2" if family == "dense" else "conv_b"
    blocks[key] = blocks[key].at[1].multiply(1.5)
    b = fam.logits({**params, "blocks": blocks}, spec.config["config"], seq,
                   pos)
    assert np.abs(a - b).max() > 1e-3


def test_padding_changes_no_earlier_logit(spec_of):
    spec = spec_of("dense")
    _, fam, params = engine_and_weights(spec, 5)
    seq = (np.arange(40, dtype=np.int32) * 7) % 256
    pos = np.arange(10, 40)
    full = fam.logits(params, spec.config["config"], seq, pos)
    part = fam.logits(params, spec.config["config"], seq[:25], pos[:15])
    np.testing.assert_allclose(full[:15], part, rtol=1e-5, atol=1e-5)


def test_sequence_aligns_served_tokens_with_their_positions():
    seq, pos = common.sequence(np.array([[5, 6, 7]]), np.array([8, 9, 10]))
    assert list(seq) == [5, 6, 7, 8, 9]
    assert list(pos) == [2, 3, 4]            # 8 from 7's logits, 9 from 8's
    ref = np.zeros((3, 12))
    ref[0, 8], ref[1, 3], ref[2, 10] = 1.0, 2.0, 4.0
    assert common.served_gap(ref, [8, 9, 10]) == 2.0
    assert common.control_gap(ref, np.eye(12)[[8, 3, 0]]) == 4.0

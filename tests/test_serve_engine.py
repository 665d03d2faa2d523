"""Unit tests for the repro.serve fast path: bucket math, no-retrace
guarantees, length-aware attention correctness, scheduler bookkeeping, and
per-family prefill/decode/forward consistency."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import decode_step, forward, init_params, init_serve_cache, prefill
from repro.serve import Request, ServeEngine, SlotScheduler
from repro.serve.equivalence import make_batch

KEY = jax.random.PRNGKey(0)

# one representative arch per model family
FAMILY_ARCHES = ["granite-3-2b", "deepseek-v3-671b", "mamba2-1.3b",
                 "zamba2-7b", "llama-3.2-vision-90b", "whisper-large-v3"]


def _engine(arch, max_len=32, kv_block=16, cfg_overrides=None):
    cfg = get_config(arch, "smoke")
    if cfg_overrides:
        cfg = cfg.replace(**cfg_overrides)
    params = init_params(cfg, KEY)
    return ServeEngine(cfg, params, max_len=max_len, kv_block=kv_block)


def test_bucket_math():
    eng = _engine("granite-3-2b", max_len=96, kv_block=32)
    assert eng.bucket_for(1) == 32
    assert eng.bucket_for(32) == 32
    assert eng.bucket_for(33) == 64
    assert eng.bucket_for(64) == 64
    assert eng.bucket_for(90) == 96
    assert eng.bucket_for(200) == 96          # clamped to max_len


def test_request_must_fit():
    eng = _engine("granite-3-2b", max_len=16)
    batch = make_batch(eng.cfg, 1, 12, 0)
    with pytest.raises(ValueError):
        eng.generate(batch, 6)                # 12 + 6 - 1 > 16


def test_decode_compiles_once_per_bucket():
    """The tentpole guarantee: generating N tokens retraces per kv bucket,
    never per step."""
    eng = _engine("granite-3-2b", max_len=64, kv_block=32)
    batch = make_batch(eng.cfg, 2, 8, 0)
    eng.generate(batch, 20, engine="fast")    # lens 8..27 -> buckets {32}
    assert eng._decode._cache_size() == 1
    eng.generate(batch, 26, engine="fast")    # lens up to 33 -> +bucket 64
    assert eng._decode._cache_size() == 2
    eng.generate(batch, 26, engine="fast")    # replay: no new traces
    assert eng._decode._cache_size() == 2
    assert eng._prefill._cache_size() == 1


def test_kv_bucket_attention_matches_full():
    """decode_step with a covering kv_bucket reproduces the full-cache
    logits (the length-aware slice only drops masked rows)."""
    cfg = get_config("granite-3-2b", "smoke")
    params = init_params(cfg, KEY)
    batch = make_batch(cfg, 2, 10, 3)
    cache_a = init_serve_cache(cfg, 2, 64, batch=batch)
    _, cache_a = prefill(cfg, params, batch, cache_a)
    cache_b = jax.tree.map(lambda a: a, cache_a)
    tok = batch["tokens"][:, -1:]
    full, _ = decode_step(cfg, params, tok, cache_a)
    sliced, _ = decode_step(cfg, params, tok, cache_b, kv_bucket=16)
    np.testing.assert_allclose(np.asarray(full), np.asarray(sliced),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("arch", FAMILY_ARCHES)
def test_prefill_decode_consistency_per_family(arch):
    """Greedy decode from a prefill cache reproduces the logits of a
    full-sequence forward at every generated position (teacher-forcing the
    engine's own greedy tokens)."""
    overrides = {}
    cfg0 = get_config(arch, "smoke")
    if cfg0.n_experts:
        overrides["moe_capacity_factor"] = 64.0   # no-drop regime: decode
        # (T=B) and forward (T=B*S) contend expert capacity differently
    eng = _engine(arch, max_len=32, kv_block=16, cfg_overrides=overrides)
    cfg = eng.cfg
    b, prompt_len, gen_len = 2, 10, 6
    batch = make_batch(cfg, b, prompt_len, 7)
    toks, logits = eng.generate(batch, gen_len, engine="fast",
                                collect_logits=True)
    seq = np.concatenate([np.asarray(batch["tokens"]), toks[:, :-1]], axis=1)
    full_batch = dict(batch)
    full_batch["tokens"] = jnp.asarray(seq)
    full_logits, _ = forward(cfg, params=eng.params, batch=full_batch,
                             kind="eval")
    full_logits = np.asarray(full_logits)
    for t in range(gen_len):
        pos = prompt_len - 1 + t
        np.testing.assert_allclose(logits[:, t], full_logits[:, pos],
                                   rtol=2e-3, atol=2e-3,
                                   err_msg=f"{arch}: position {pos}")
    assert (toks == full_logits[:, prompt_len - 1:prompt_len - 1 + gen_len]
            .argmax(-1)).all()


def test_scheduler_single_slot_serializes():
    """slots=1 degenerates to sequential serving with identical tokens."""
    eng = _engine("granite-3-2b")
    reqs = [Request(rid=i,
                    tokens=np.asarray(make_batch(eng.cfg, 1, 8, 50 + i)
                                      ["tokens"]),
                    gen_len=g) for i, g in enumerate([4, 6, 3])]
    ref, _ = SlotScheduler(eng, slots=1).run(reqs, engine="reference")
    fast, stats = SlotScheduler(eng, slots=1).run(reqs, engine="fast")
    for a, b, r in zip(ref, fast, reqs):
        assert len(a) == r.gen_len
        np.testing.assert_array_equal(a, b)
    assert stats["decode_steps"] == sum(r.gen_len - 1 for r in reqs)
    assert stats["slot_utilization"] == 1.0


def test_scheduler_slot_reuse_and_order():
    """More requests than slots: slots are recycled in arrival order and
    every stream matches its isolated reference."""
    eng = _engine("mamba2-1.3b")
    lens = [(8, 5), (10, 2), (8, 7), (6, 4), (8, 1), (10, 6)]
    reqs = [Request(rid=i,
                    tokens=np.asarray(make_batch(eng.cfg, 1, p, 80 + i)
                                      ["tokens"]),
                    gen_len=g) for i, (p, g) in enumerate(lens)]
    sched = SlotScheduler(eng, slots=2)
    ref, _ = sched.run(reqs, engine="reference")
    fast, stats = sched.run(reqs, engine="fast")
    for a, b, r in zip(ref, fast, reqs):
        assert len(a) == r.gen_len
        np.testing.assert_array_equal(a, b)
    assert 0.0 < stats["slot_utilization"] <= 1.0


class _StepLogits:
    """The engine as the scheduler sees it, keeping each decode step's
    logits; everything else is the engine's."""

    def __init__(self, engine):
        self._engine = engine
        self.logits = []

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def _decode_quiet(self, toks, cache, bucket):
        toks, logits, cache = self._engine._decode_quiet(toks, cache, bucket)
        self.logits.append(logits)
        return toks, logits, cache


@pytest.mark.parametrize("arch", ["granite-3-2b", "mamba2-1.3b", "zamba2-7b"])
def test_staggered_slots_decode_in_place(arch):
    """Slots at different lengths share each decode step, which updates the
    stacked cache in place and attends over a kv bucket below max_len:
    every request's decode logits match a full forward over its own prompt
    and tokens (a path that keeps no cache)."""
    eng = _engine(arch, max_len=48, kv_block=16)
    log = _StepLogits(eng)
    sched = SlotScheduler(log, slots=3)
    slots = []                                # admission order = rid order
    admit = sched._admit

    def _admit(*args):
        slots.append(int(args[-1]))
        return admit(*args)

    sched._admit = _admit
    lens = [(5, 9), (12, 4), (7, 11), (20, 6), (9, 8), (14, 5)]
    reqs = [Request(rid=i,
                    tokens=np.asarray(make_batch(eng.cfg, 1, p, 120 + i)
                                      ["tokens"]),
                    gen_len=g) for i, (p, g) in enumerate(lens)]
    streams, stats = sched.run(reqs)
    assert max(p + g for p, g in lens) - 1 <= 32 < eng.max_len
    assert len(set(stats["requests"][r.rid][0] for r in reqs)) > 2
    for r, slot, toks in zip(reqs, slots, streams):
        first = stats["requests"][r.rid][0]
        p = r.tokens.shape[1]
        seq = np.concatenate([r.tokens[0], toks[:-1]])[None]
        full, _ = forward(eng.cfg, eng.params, {"tokens": jnp.asarray(seq)},
                          kind="eval")
        full = np.asarray(full)[0]
        for k in range(1, r.gen_len):
            got = np.asarray(log.logits[first + k - 1])[slot, 0]
            np.testing.assert_allclose(
                got, full[p - 1 + k], rtol=2e-3, atol=2e-3,
                err_msg=f"{arch}: request {r.rid}, token {k}")


def test_timing_helpers_run():
    eng = _engine("granite-3-2b", max_len=48)
    batch = make_batch(eng.cfg, 2, 8, 0)
    eng.warmup(batch, 10)
    assert eng.timed_decode(batch, 9) > 0.0
    assert eng.timed_prefill(batch, reps=2) > 0.0
    assert eng.timed_decode(batch, 9, engine="reference") > 0.0

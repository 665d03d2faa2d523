"""The check that decides ``correct``: a sound run passes it, the control
(the reference computed in float8) fails it, and so does a run whose timed
path is broken underneath the harness.

These drive everything ``run.py`` does after its look for a chip
(``run.measure``), on tiny cells on the CPU; each fault is planted in
``ServeEngine``'s decode step, which ``SlotScheduler.run`` calls for every
batched step of the window.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import pytest

from benchmarks.serving import bench as B
from benchmarks.serving import run

SECONDS = 1.5


def state_unchanged(step):
    """The step computes its tokens but hands back the cache it was given."""
    def broken(self, toks, cache, bucket):
        kept = jax.tree.map(jnp.copy, cache)
        new_toks, logits, _ = step(self, toks, cache, bucket)
        return new_toks, logits, kept
    return broken


def half_batch_left_out(step):
    """Half of the slots, those the scheduler fills first, is not advanced:
    their tokens come back as they went in."""
    def broken(self, toks, cache, bucket):
        new_toks, logits, cache = step(self, toks, cache, bucket)
        h = toks.shape[0] // 2
        return new_toks.at[:h].set(toks[:h]), logits, cache
    return broken


def token_altered(step):
    """Slot 0's token is changed where the step produces it."""
    def broken(self, toks, cache, bucket):
        new_toks, logits, cache = step(self, toks, cache, bucket)
        v = self.cfg.vocab
        return new_toks.at[0, 0].set((new_toks[0, 0] + 1) % v), logits, cache
    return broken


@pytest.fixture(scope="module", params=["dense", "ssm"])
def bench(request, spec_of):
    """One set-up tiny cell per family, shared by the windows below."""
    b = B.Bench(spec_of(request.param), 1000)
    b.setup(SECONDS)
    return b


def serve_and_judge(b, seed):
    b.seed = seed
    served = run.serve(b, SECONDS, False, jax.devices(), time.perf_counter())
    return run.judge(b, served)


def test_a_whole_sound_run_is_correct(spec_of):
    res = run.measure(spec_of("dense"), 1000, SECONDS, False, jax.devices(),
                      time.perf_counter())
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 5
    assert list(res)[-1] == "checks"
    gap = res["checks"]["logit_gap"]
    assert gap["value"] <= gap["limit"]
    assert set(res["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert set(res["metrics"]) == set()      # a tiny spec names no metric


def test_sound_windows_are_correct(bench):
    for seed in (1005, 1006):
        assert serve_and_judge(bench, seed)["correct"] is True


@pytest.mark.parametrize("fault", [state_unchanged, half_batch_left_out,
                                   token_altered])
def test_broken_decode_step_is_not_correct(bench, monkeypatch, fault):
    from repro.serve.engine import ServeEngine
    monkeypatch.setattr(ServeEngine, "_decode_quiet",
                        fault(ServeEngine._decode_quiet))
    res = serve_and_judge(bench, 1001)
    assert res["correct"] is False
    assert res["checks"]["logit_gap"]["value"] > \
        res["checks"]["logit_gap"]["limit"]


@pytest.mark.parametrize("seed", [1002, 1003, 1004])
def test_control_fails_where_the_program_passes(bench, seed):
    checks = bench.check(bench.window(SECONDS, seed=seed), control=True)
    limit = checks["logit_gap"]["limit"]
    assert checks["logit_gap"]["value"] <= limit
    assert checks["control_logit_gap"]["value"] > limit


def test_nothing_compiles_in_the_window(bench):
    out = bench.window(SECONDS, seed=1007)
    assert out["compiles"] == 0
    bound = B.wave_steps_bound([a.gen_len for a in out["arrivals"]],
                               bench.spec.cell["slots"])
    assert max(w["decode_steps"] for w in out["waves"]) <= bound

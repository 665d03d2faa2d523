"""Spans and step-clock events of ``SlotScheduler.run``: the span recorder
(``repro.serve.telemetry.SpanRecorder``), the per-request admission and
completion steps in ``stats``, the device-side scope names of the
admission and decode programs, and that none of it touches a token."""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import init_params, init_serve_cache
from repro.serve import Request, ServeEngine, SlotScheduler
from repro.serve import scheduler as scheduler_mod
from repro.serve.equivalence import make_batch
from repro.serve.telemetry import SpanRecorder

PHASES = ("serve.wave", "serve.bank", "serve.admit", "serve.decode",
          "serve.fetch", "serve.unpack")


def ticking_clock():
    """A fake clock that reads 0, 1, 2, ... on successive calls."""
    ticks = itertools.count()
    return lambda: float(next(ticks))


class StubEngine:
    """The engine as ``SlotScheduler`` sees it, with steps that return at
    once: the per-engine bank and admission hooks, and a decode step that
    adds one to every slot's token.  ``log`` notes each call in order."""
    is_pipeline = True
    cfg = None
    max_len = 512

    def __init__(self, log=None):
        self.log = [] if log is None else log

    def _check_fit(self, prompt_len, gen_len):
        pass

    def bucket_for(self, filled):
        return -(-filled // 16) * 16

    def slot_bank(self, slots, proto_batch):
        return jnp.zeros((slots, 1), jnp.int32)

    def admit_slot(self, tokens, extras, cache, slot_tokens, slot):
        self.log.append(("admit", slot))
        tok = jnp.full((1, 1), tokens.shape[1], jnp.int32)
        return tok, cache, slot_tokens.at[slot].set(tok[0])

    def _decode_quiet(self, toks, cache, bucket):
        self.log.append(("decode", bucket))
        return toks + 1, None, cache


def stub_requests(gen_lens, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rid=100 + i,
                    tokens=np.zeros((1, int(rng.integers(1, 40))), np.int32),
                    gen_len=g) for i, g in enumerate(gen_lens)]


def random_schedule(seed):
    rng = np.random.default_rng(seed)
    return [int(g) for g in rng.integers(1, 12, int(rng.integers(1, 14)))]


# -- the recorder -----------------------------------------------------------

def test_recorder_counts_and_times_on_its_clock():
    rec = SpanRecorder(ticking_clock())
    with rec.span("a"):
        with rec.span("b", k=1):
            pass
    with rec.span("b"):
        pass
    # a: 0 -> 3 (b reads 1 and 2 inside it); each b is one tick long
    assert rec.snapshot() == {"a": [1, 3.0], "b": [2, 2.0]}
    assert rec.seconds("a") == 3.0 and rec.seconds("none") == 0.0


def test_recorder_closes_a_span_that_raises():
    rec = SpanRecorder(ticking_clock())
    with pytest.raises(ValueError):
        with rec.span("a"):
            raise ValueError
    assert rec.snapshot() == {"a": [1, 1.0]}


def test_snapshot_is_a_copy():
    rec = SpanRecorder(ticking_clock())
    with rec.span("a"):
        pass
    snap = rec.snapshot()
    snap["a"][0] = 99
    assert rec.snapshot()["a"][0] == 1


# -- spans and events of SlotScheduler.run ----------------------------------

def test_known_schedule_gives_known_events():
    """One slot, answers of 3, 1 and 2 tokens: the second and third are
    admitted after the first's two steps, the second done at once."""
    reqs = stub_requests([3, 1, 2])
    _, stats = SlotScheduler(StubEngine(), 1).run(reqs)
    assert stats["decode_steps"] == 3
    assert stats["requests"] == {100: [0, 2], 101: [2, 2], 102: [2, 3]}


@pytest.mark.parametrize("slots", [1, 2, 4])
@pytest.mark.parametrize("seed", range(4))
def test_span_counts_follow_the_schedule(slots, seed):
    reqs = stub_requests(random_schedule(seed), seed)
    streams, stats = SlotScheduler(StubEngine(), slots,
                                   clock=ticking_clock()).run(reqs)
    spans = stats["spans"]
    assert set(spans) == set(PHASES)
    assert spans["serve.decode"][0] == stats["decode_steps"]
    assert spans["serve.admit"][0] == len(reqs)
    for name in ("serve.wave", "serve.bank", "serve.fetch", "serve.unpack"):
        assert spans[name][0] == 1
    # the clock is read only on entering and leaving spans, so the whole
    # wave lasts one tick plus two for each span inside it
    inner = sum(c for n, (c, _) in spans.items() if n != "serve.wave")
    assert spans["serve.wave"][1] == 1 + 2 * inner
    assert stats["wall_s"] == spans["serve.wave"][1]
    assert [len(s) for s in streams] == [r.gen_len for r in reqs]


@pytest.mark.parametrize("slots", [1, 2, 4])
@pytest.mark.parametrize("seed", range(4))
def test_events_bracket_each_request(slots, seed):
    reqs = stub_requests(random_schedule(seed), seed)
    _, stats = SlotScheduler(StubEngine(), slots).run(reqs)
    ev = stats["requests"]
    assert set(ev) == {r.rid for r in reqs}
    for r in reqs:
        admit, done = ev[r.rid]
        assert done - admit == r.gen_len - 1
        assert 0 <= admit <= done <= stats["decode_steps"]
    busy = sum(r.gen_len - 1 for r in reqs)
    assert stats["slot_utilization"] == pytest.approx(
        busy / max(1, stats["decode_steps"] * slots))


def test_an_empty_run_reports_no_spans_and_no_events():
    sched = SlotScheduler(StubEngine(), 2)
    assert sched.run([])[1] == {"wall_s": 0.0, "decode_steps": 0,
                                "slot_utilization": 0.0, "spans": {},
                                "requests": {}}


def test_no_device_read_before_the_fetch(monkeypatch):
    """Every device-to-host read of a run comes after its last decode
    dispatch, and the first is the one fetch of all the wave's tokens."""
    log = []

    class SpyNumpy:
        def __getattr__(self, name):
            return getattr(np, name)

        @staticmethod
        def asarray(x, *a, **kw):
            if isinstance(x, jax.Array):
                log.append(("read", x.shape))
            return np.asarray(x, *a, **kw)

    monkeypatch.setattr(scheduler_mod, "np", SpyNumpy())
    reqs = stub_requests([5, 2, 7, 1, 3])
    streams, stats = SlotScheduler(StubEngine(log), 2).run(reqs)
    first = next(i for i, e in enumerate(log) if e[0] == "read")
    decodes = [e for e in log if e[0] == "decode"]
    assert len(decodes) == stats["decode_steps"] > 0
    assert all(e[0] == "read" for e in log[first:])
    assert log[first][1] == (2, stats["decode_steps"])
    assert len(log) - first == 1 + len(reqs)   # the fetch, the first tokens
    # the stub's tokens: prompt length, then one more per step
    for r, s in zip(reqs, streams):
        p = r.tokens.shape[1]
        assert list(s) == list(range(p, p + r.gen_len))


# -- a real tiny engine -------------------------------------------------------

def tiny_engine(arch):
    cfg = get_config(arch, "smoke")
    return ServeEngine(cfg, init_params(cfg, jax.random.PRNGKey(0)),
                       max_len=32, kv_block=16)


def tiny_requests(cfg, lens):
    return [Request(rid=i, tokens=np.asarray(
                make_batch(cfg, 1, p, 60 + i)["tokens"]), gen_len=g)
            for i, (p, g) in enumerate(lens)]


@pytest.mark.parametrize("arch", ["granite-3-2b", "mamba2-1.3b"])
def test_streams_do_not_depend_on_the_recorder(arch):
    """The same requests give the same tokens under the default clock, a
    fake clock and the per-request reference; the events hold."""
    eng = tiny_engine(arch)
    reqs = tiny_requests(eng.cfg, [(8, 5), (10, 2), (6, 7), (8, 1), (9, 4)])
    real, stats = SlotScheduler(eng, 2).run(reqs)
    fake, fstats = SlotScheduler(eng, 2, clock=ticking_clock()).run(reqs)
    ref, rstats = SlotScheduler(eng, 2).run(reqs, engine="reference")
    for a, b, c in zip(real, fake, ref):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, c)
    assert stats["requests"] == fstats["requests"]
    assert stats["decode_steps"] == fstats["decode_steps"]
    for r in reqs:
        admit, done = stats["requests"][r.rid]
        assert done - admit == r.gen_len - 1
    assert stats["wall_s"] == stats["spans"]["serve.wave"][1] > 0.0
    assert set(rstats["spans"]) == {"serve.wave"}
    assert rstats["wall_s"] == rstats["spans"]["serve.wave"][1]
    assert "requests" not in rstats


@pytest.mark.parametrize("arch", ["granite-3-2b", "mamba2-1.3b"])
def test_device_programs_keep_their_names_and_carry_scopes(arch):
    """The admission and decode programs are still ``jit__admit`` and
    ``jit_step`` (the names the benchmark's trace reduction reads), and
    their lowered text names the phases inside them."""
    eng = tiny_engine(arch)
    sched = SlotScheduler(eng, 2)
    reqs = tiny_requests(eng.cfg, [(8, 3)])
    sched.run(reqs)                            # sets the bank's batch axes
    cache = init_serve_cache(eng.cfg, 2, eng.max_len,
                             batch={"tokens": jnp.zeros((2, 1), jnp.int32)})
    st = jnp.zeros((2, 1), jnp.int32)
    admit = sched._admit.lower(eng.params, jnp.asarray(reqs[0].tokens), {},
                               cache, st, np.int32(0))
    decode = eng._decode.lower(eng.params, st, cache, 16)
    for low, module, scopes in (
            (admit, "jit__admit",
             ("admit/prefill", "admit/scatter", "admit/first_token")),
            (decode, "jit_step", ("decode/model", "decode/sample"))):
        assert low.as_text().startswith(f"module @{module} ")
        text = low.as_text(debug_info=True)
        for scope in scopes:
            assert scope in text, (module, scope)

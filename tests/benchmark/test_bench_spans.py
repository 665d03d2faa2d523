"""The per-layer metrics that read what ``SlotScheduler.run`` records about
itself: the step-clock shares from its per-request events and the host
times from its spans.  Their arithmetic on synthetic waves, what they read
from a program that records nothing, and one tiny window on the CPU."""

from __future__ import annotations

import importlib
import types

import pytest

from benchmarks.serving import bench as B

SHARES = ("wave_hold_share", "slot_wait_share")
SPANS = ("wave_host_ms", "decode_dispatch_ms")


def metric(name):
    return importlib.import_module(f"benchmarks.serving.metrics.{name}").read


def view(waves):
    return types.SimpleNamespace(waves=list(waves))


def wave(steps, requests, spans=None):
    return {"decode_steps": steps, "slot_utilization": 0.5,
            "requests": requests, "spans": spans or {}}


def test_shares_weigh_each_request_by_its_wave():
    # wave 1: 10 steps; admitted at 0 and 4, done at 6 and 10
    # wave 2: 4 steps; admitted at 0, done at 3; one more done at once
    waves = [wave(10, {1: [0, 6], 2: [4, 10]}),
             wave(4, {3: [0, 3], 4: [0, 0]})]
    v = view(waves)
    total = 10 + 10 + 4 + 4
    assert metric("wave_hold_share")(v) == pytest.approx(
        (4 + 0 + 1 + 4) / total)
    assert metric("slot_wait_share")(v) == pytest.approx((0 + 4) / total)


def test_spans_average_per_wave_and_per_step():
    waves = [wave(3, {}, {"serve.bank": [1, 0.010], "serve.unpack": [1, 0.002],
                          "serve.decode": [3, 0.003]}),
             wave(5, {}, {"serve.bank": [1, 0.020], "serve.unpack": [1, 0.004],
                          "serve.decode": [5, 0.013]}),
             wave(0, {}, {"serve.bank": [1, 0.006]})]
    v = view(waves)
    assert metric("wave_host_ms")(v) == pytest.approx(42.0 / 3)
    assert metric("decode_dispatch_ms")(v) == pytest.approx(16.0 / 8)


@pytest.mark.parametrize("name", SHARES + SPANS)
def test_nothing_to_read_reads_none(name):
    # no waves, waves without a decode step, and waves from a program that
    # records no spans or events
    assert metric(name)(view([])) is None
    assert metric(name)(view([wave(0, {7: [0, 0]},
                                   {"serve.bank": [1, 0.01]})])) is None
    bare = [{"decode_steps": 9, "slot_utilization": 0.5}]
    assert metric(name)(view(bare)) is None


@pytest.fixture(scope="module", params=["dense", "ssm"])
def tiny_window(request, spec_of):
    b = B.Bench(spec_of(request.param), 1000)
    b.setup(1.5)
    out = b.window(1.5)
    return b.run_view(out, 0.0)


def test_a_tiny_window_reports_all_four(tiny_window):
    v = tiny_window
    got = {n: metric(n)(v) for n in SHARES + SPANS}
    assert all(x is not None for x in got.values()), got
    for n in SHARES:
        assert 0.0 <= got[n] <= 1.0
    assert got["wave_host_ms"] > 0.0 and got["decode_dispatch_ms"] > 0.0
    # a wave's steps are the wait for a slot, the request's own steps and
    # the hold after it: the three shares add up to one
    own = sum(r["gen_len"] - 1 for r in v.records) / sum(
        w["decode_steps"] for w in v.waves for _ in w["requests"])
    assert got["wave_hold_share"] + got["slot_wait_share"] + own == \
        pytest.approx(1.0)

"""The traffic generator: deterministic by seed, inside the mix's grid and
clip bounds, and the same work for every seed."""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

from benchmarks.serving.traffic import generator

MIXES = Path(generator.__file__).parent
SEEDS = (0, 7, 2 ** 31 + 12345, 2 ** 40 + 3, -5)


def mix(name):
    return json.loads((MIXES / f"{name}.json").read_text())


def summary(arrivals):
    return [(a.rid, a.due_s, a.gen_len, a.tokens.tobytes()) for a in arrivals]


@pytest.mark.parametrize("name", ["chat", "burst"])
@pytest.mark.parametrize("seed", SEEDS)
def test_same_seed_same_arrivals(name, seed):
    a = generator.generate(mix(name), 3.0, 20.0, seed, vocab=1000)
    b = generator.generate(mix(name), 3.0, 20.0, seed, vocab=1000)
    assert summary(a) == summary(b)


@pytest.mark.parametrize("name", ["chat", "burst"])
def test_seeds_differ_in_order_not_in_work(name):
    runs = [generator.generate(mix(name), 3.0, 20.0, s, vocab=1000)
            for s in SEEDS]
    assert len({tuple(a.due_s for a in r) for r in runs}) == len(SEEDS)
    for r in runs:
        assert sorted(a.prompt_len for a in r) == \
            sorted(a.prompt_len for a in runs[0])
        assert sorted(a.gen_len for a in r) == \
            sorted(a.gen_len for a in runs[0])
        due = [a.due_s for a in r]
        gaps = np.append(np.diff(due), 20.0 - due[-1])   # the last gap closes
        full = generator.gaps(mix(name)["arrivals"], len(r), 20.0)
        assert sorted(gaps) == pytest.approx(sorted(full))


@pytest.mark.parametrize("name", ["chat", "burst"])
@pytest.mark.parametrize("seed", SEEDS[:3])
def test_lengths_on_grid_and_inside_clip(name, seed):
    m = mix(name)
    arr = generator.generate(m, 4.0, 30.0, seed, vocab=50)
    grid = set(generator.grid(m["prompt"]))
    for a in arr:
        assert a.prompt_len in grid
        assert a.prompt_len % m["prompt"]["grid"] == 0
        assert m["prompt"]["min"] <= a.prompt_len <= m["prompt"]["max"]
        assert m["output"]["min"] <= a.gen_len <= m["output"]["max"]
        assert a.tokens.dtype == np.int32 and a.tokens.shape == (1, a.prompt_len)
        assert 0 <= a.tokens.min() and a.tokens.max() < 50


@pytest.mark.parametrize("rate,seconds", [(3.0, 20.0), (0.1, 2.0), (9.5, 40.0)])
def test_arrivals_inside_window_sorted_and_counted(rate, seconds):
    arr = generator.generate(mix("burst"), rate, seconds, 11, vocab=10)
    due = [a.due_s for a in arr]
    assert len(arr) == generator.n_requests(rate, seconds)
    assert due == sorted(due) and due[0] == 0.0 and due[-1] < seconds
    assert [a.rid for a in arr] == list(range(len(arr)))


def test_burst_gaps_are_burstier_than_poisson():
    cv = {}
    for name in ("chat", "burst"):
        g = generator.gaps(mix(name)["arrivals"], 400, 100.0)
        assert g.sum() == pytest.approx(100.0)
        cv[name] = g.std() / g.mean()
    assert 0.85 < cv["chat"] < 1.05          # exponential: CV 1
    assert 1.6 < cv["burst"] < 2.1           # gamma shape 0.25: CV 2


def test_stratified_lengths_follow_the_lognormal():
    spec = {"median": 512, "sigma": 0.8, "min": 1, "max": 10 ** 9, "grid": 1}
    x = generator.lengths(spec, 1001)
    assert x[500] == 512                    # the middle quantile is the median
    assert list(x) == sorted(x)


def test_grid_lists_every_reachable_length():
    assert generator.grid(mix("chat")["prompt"]) == list(range(128, 1537, 128))
    assert generator.grid(mix("burst")["prompt"]) == [64, 128, 192, 256]


@pytest.mark.parametrize("seed", SEEDS[:3])
def test_bursts_of_twenty_arrive_together(seed):
    # gamma shape 0.25 (CV 2): somewhere twenty requests fall due within
    # eight mean gaps, a burst at 2.5 times the mean rate
    arr = generator.generate(mix("burst"), 10.0, 50.0, seed, vocab=10)
    due = np.array([a.due_s for a in arr])
    mean_gap = 50.0 / len(arr)
    assert ((due[20:] - due[:-20]) / mean_gap).min() < 8.0

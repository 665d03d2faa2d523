"""The one traffic generator: a mix file's parameters and a seed -> arrivals.

A mix (``traffic/<mix>.json``) gives the arrival process and the length
distributions; the cell gives the rate.  ``n = round(rate * seconds)``
requests are due in ``[0, seconds)``.

Every seed gets the same set of sizes and gaps, in another order: the gaps
between arrivals are the ``n`` quantile midpoints of a gamma distribution
(shape 1 is a Poisson process; shape k < 1 has a coefficient of variation
of 1/sqrt(k), so bursts), scaled so that they sum to ``seconds``; prompt and
output lengths are the ``n`` quantile midpoints of a lognormal, clipped to
``[min, max]`` and rounded up to a multiple of ``grid``.  The seed shuffles
each list whole, as independent draws would fall, so runs of short gaps
make bursts of any size, and draws the prompt tokens: runs with different
seeds do the same work and differ in when it comes and which request meets
which.
"""

from __future__ import annotations

import dataclasses
import statistics

import numpy as np
from scipy import stats


@dataclasses.dataclass(frozen=True)
class Arrival:
    rid: int
    due_s: float
    tokens: np.ndarray          # (1, prompt_len) int32
    gen_len: int

    @property
    def prompt_len(self) -> int:
        return int(self.tokens.shape[1])


def _midpoints(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int) -> np.ndarray:
    """The n stratified lengths of a lognormal spec, sorted ascending."""
    nd = statistics.NormalDist()
    z = np.array([nd.inv_cdf(p) for p in _midpoints(n)])
    x = np.clip(spec["median"] * np.exp(spec["sigma"] * z),
                spec["min"], spec["max"])
    grid = spec.get("grid", 1)
    return np.clip(np.ceil(x / grid) * grid, spec["min"],
                   spec["max"]).astype(np.int64)


def gaps(spec: dict, n: int, seconds: float) -> np.ndarray:
    """The n stratified gaps of the arrival process, summing to seconds."""
    q = stats.gamma.ppf(_midpoints(n), spec["shape"])
    return q / q.sum() * seconds


def grid(spec: dict) -> list[int]:
    """Every length a spec can produce."""
    g = spec.get("grid", 1)
    lo = -(-spec["min"] // g) * g
    return list(range(lo, spec["max"] + 1, g))


def n_requests(rate_per_s: float, seconds: float) -> int:
    return max(1, int(round(rate_per_s * seconds)))


def rng_for(seed: int) -> np.random.Generator:
    """Any whole number, however large or negative, seeds the generator."""
    return np.random.default_rng(int(seed) % (1 << 64))


def generate(mix: dict, rate_per_s: float, seconds: float, seed: int,
             vocab: int) -> list[Arrival]:
    """The arrivals of one window, sorted by due time."""
    n = n_requests(rate_per_s, seconds)
    rng = rng_for(seed)
    g = rng.permutation(gaps(mix["arrivals"], n, seconds))
    due = np.concatenate([[0.0], np.cumsum(g)[:-1]])
    prompts = rng.permutation(lengths(mix["prompt"], n))
    outputs = rng.permutation(lengths(mix["output"], n))
    return [Arrival(rid=i, due_s=float(due[i]),
                    tokens=rng.integers(0, vocab, (1, int(prompts[i])),
                                        dtype=np.int32),
                    gen_len=int(outputs[i]))
            for i in range(n)]

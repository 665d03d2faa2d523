"""Tiny cells of the serving benchmark, for tests on the CPU.

The configurations are the smoke presets of the two families the
benchmark serves (``data/tiny-dense.json``, ``data/tiny-ssm.json``) under a
short bursty mix (``data/tiny.json``).  The logit-gap limit of a tiny cell
(``TINY_LIMIT``) was set from CPU readings at this size the way a cell's
limit is set on the chip: over seeds 1000-1011 in 1.5-second windows,
each run once with the requests shuffled whole, as the generator does,
and once laid out in blocks of ten, the program's widest gap was at most
0.00623 (dense) and 0.00418 (ssm), the float8 control's at least 0.0332
(dense) and 0.0168 (ssm).
"""

from __future__ import annotations

from pathlib import Path

import pytest

from benchmarks.serving import bench as B

DATA = Path(__file__).parent / "data"
TINY_LIMIT = 0.011


def tiny_spec(family: str, **cell) -> B.Spec:
    c = {"driver": "waves", "rate_per_s": 12.0, "slots": 4, "max_len": 64,
         "kv_block": 16,
         "check": {"logit_gap_limit": TINY_LIMIT,
                   "control_dtype": "float8_e4m3fn", "sample_tokens": 60,
                   "sample_requests": 4}}
    c.update(cell)
    return B.Spec(name=f"tiny-{family}", workload={"chips": 1}, cell=c,
                  config=B.load_json(DATA / f"tiny-{family}.json"),
                  mix=B.load_json(DATA / "tiny.json"), per_layer=[],
                  end_to_end=[])


@pytest.fixture(scope="session")
def spec_of():
    return tiny_spec

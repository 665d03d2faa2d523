"""The trace reduction against a small trace recorded on the chip
(``benchmarks/serving/record_trace.py``): a one-second window of the tiny
dense cell on one TPU v5e, committed with what the host counted in it."""

from __future__ import annotations

import json
from pathlib import Path

import pytest

from benchmarks.serving import profile_reduce

DATA = Path(profile_reduce.__file__).parent / "data"
TRACE = DATA / "tiny-trace.xplane.pb.gz"
COUNTS = json.loads((DATA / "tiny-trace.json").read_text())


@pytest.fixture(scope="module")
def events():
    return profile_reduce.read_xplane(str(TRACE))


@pytest.fixture(scope="module")
def red():
    return profile_reduce.reduce_file(str(TRACE))


def test_recorded_on_a_v5e():
    assert COUNTS["device_kind"] == "TPU v5 lite"


def test_one_chip_one_window(events):
    host, devices = events
    assert len(devices) == 1
    assert [n for n, _, _ in host].count("window") == 1
    assert devices[0]["ops"] and devices[0]["modules"]


def test_programs_counted_as_the_host_called_them(red):
    assert red.program("jit_step")[0] == COUNTS["decode_calls"]
    assert red.program("jit__admit")[0] == COUNTS["admissions"]


def test_busy_and_gaps_tile_the_window(red):
    assert 0 < red.busy_s < red.window_s
    idle = sum(s for _, s in red.gaps)
    assert red.busy_s + idle == pytest.approx(red.window_s, rel=1e-6)
    names = {n for n, _ in red.gaps}
    assert names <= {"window", "wait_for_arrival", "SlotScheduler.run"}


def test_ops_run_inside_programs(red):
    # one chip runs one program at a time and every op inside one: the busy
    # union of the ops is at most the programs' device time, and most of it
    progs = sum(s for _, s in red.programs.values())
    assert 0.8 * progs <= red.busy_s <= progs
    assert not any(k.startswith("?/") for k in red.ops)


def test_breakdown_is_short_and_sorted(red):
    b = red.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    secs = [s for _, s in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)

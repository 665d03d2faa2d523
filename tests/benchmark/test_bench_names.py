"""``BENCHMARK.json``: every name resolves to its files and uses only the
allowed characters, each per-layer metric's ``moves`` is reported by all of
its cells, and the file keeps to the benchmark's limits."""

from __future__ import annotations

import importlib
import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
HERE = ROOT / "benchmarks" / "serving"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
TEXT = re.compile(r"^[^\t\n]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
# widths, which no configuration may cut
WIDTHS = {"hidden_size", "intermediate_size", "d_model", "d_intermediate",
          "d_state", "headdim", "head_dim", "expand", "num_experts_per_tok"}
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys_and_sizes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    assert 1 <= len(BENCH["configs"]) <= 24
    assert 1 <= len(BENCH["workloads"]) <= 24
    assert 1 <= len(BENCH["end_to_end"]) <= 16
    assert 1 <= len(BENCH["per_layer"]) <= 128
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51


def test_paths_and_command():
    assert 1 <= len(BENCH["paths"]) <= 16
    for p in BENCH["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (ROOT / p).is_dir()
    cmd = BENCH["command"]
    assert 1 <= len(cmd) <= 32 and all(TEXT.match(w) for w in cmd)
    files = [w for w in cmd if "/" in w]
    assert files and all(any(f.startswith(p + "/") for p in BENCH["paths"])
                         for f in files)
    assert all((ROOT / f).is_file() for f in files)


def test_full_check_fits_its_time_at_24_cells():
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_files(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"]) and TEXT.match(entry["source"])
    assert TEXT.match(entry["why"])
    assert any(entry["file"].startswith(p + "/") for p in BENCH["paths"])
    conf = json.loads((ROOT / entry["file"]).read_text())
    assert conf["name"] == entry["name"] and conf["source"] == entry["source"]
    assert entry["reduced"] == conf["reduced"] and len(entry["reduced"]) <= 16
    for k in entry["reduced"]:
        assert NAME.match(k) and k in conf["config"] and k in conf["published"]
        assert k not in WIDTHS and not k.endswith(("_dim", "_rank"))
    importlib.import_module(f"benchmarks.serving.reference.{conf['family']}")
    assert [c["file"] for c in BENCH["configs"]].count(entry["file"]) == 1
    assert any(w["config"] == entry["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("wl", BENCH["workloads"], ids=lambda w: w["name"])
def test_cells_resolve(wl):
    assert set(wl) == {"name", "config", "traffic", "chips", "why"}
    for k in ("name", "config", "traffic"):
        assert NAME.match(wl[k])
    assert TEXT.match(wl["why"]) and wl["chips"] in (1, 4)
    cell = json.loads((HERE / "cells" / f"{wl['name']}.json").read_text())
    assert (HERE / "traffic" / f"{wl['traffic']}.json").is_file()
    assert (HERE / "drivers" / f"{cell['driver']}.py").is_file()
    assert wl["config"] in {c["name"] for c in BENCH["configs"]}
    assert cell["check"]["logit_gap_limit"] > 0


def test_names_unique_and_four_chip_share():
    for group in (BENCH["configs"], BENCH["workloads"], METRICS):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(CELLS) // 2)


@pytest.mark.parametrize("m", METRICS, ids=lambda m: m["name"])
def test_metric_entries_resolve(m):
    e2e = m in BENCH["end_to_end"]
    keys = {"name", "unit", "better", "source"} | (
        {"bound"} if e2e else {"layer", "moves"})
    assert set(m) - {"workloads"} == keys
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    assert callable(importlib.import_module(
        f"benchmarks.serving.metrics.{m['name']}").read)
    for w in m.get("workloads", []):
        assert w in CELLS
    if e2e:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    else:
        assert TEXT.match(m["layer"])
    if m["name"].endswith("_roofline") or "mfu" in m["name"]:
        assert m["unit"] == "%"


@pytest.mark.parametrize("m", BENCH["per_layer"], ids=lambda m: m["name"])
def test_moves_is_reported_by_all_its_cells(m):
    moved = [e for e in BENCH["end_to_end"] if e["name"] == m["moves"]]
    assert moved
    for cell in m.get("workloads", CELLS):
        assert cell in moved[0].get("workloads", CELLS)


def test_every_cell_reports_setup_another_metric_and_a_layer():
    assert any(e["name"] == "setup_s" and e["bound"] <= 0.25
               for e in BENCH["end_to_end"])
    for cell in CELLS:
        e2e = [e["name"] for e in BENCH["end_to_end"]
               if cell in e.get("workloads", CELLS)]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(cell in m.get("workloads", CELLS)
                   for m in BENCH["per_layer"])


def test_layers_are_named_alike():
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert layers == {"front end", "scheduler", "engine", "model step",
                      "device"}


def test_files_under_paths_are_named_from_name_characters():
    for p in BENCH["paths"]:
        for f in (ROOT / p).rglob("*"):
            if "__pycache__" in f.parts or not f.is_file():
                continue
            rel = f.relative_to(ROOT).as_posix()
            assert re.match(r"^[A-Za-z0-9_./-]+$", rel), rel

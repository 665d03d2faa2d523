"""``run.py`` refuses to run without a TPU: it exits with a code other than
0 and prints no result, both from the repository and from a directory that
holds only ``BENCHMARK.json`` and the benchmark's files."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def run_cli(cwd: Path):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    cmd = BENCH["command"][1:]
    return subprocess.run(
        [sys.executable, *cmd, "--workload", BENCH["workloads"][0]["name"],
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_exits_nonzero_without_a_tpu():
    res = run_cli(ROOT)
    assert res.returncode != 0
    assert res.stdout.strip() == ""
    assert "no TPU" in res.stderr


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    res = run_cli(tmp_path)
    assert res.returncode != 0
    assert res.stdout.strip() == ""


@pytest.mark.parametrize("argv", [[], ["--workload", "x"],
                                  ["--workload", "x", "--seed", "1",
                                   "--seconds", "1", "--trace", "2"]])
def test_refuses_incomplete_arguments(argv):
    from benchmarks.serving import run
    with pytest.raises(SystemExit):
        run.parse(argv)

#!/usr/bin/env python3
"""Record the small trace the reduction is checked against.

    python3 benchmarks/serving/record_trace.py [OUT_DIR]

On the chip: a one-second traced window of the tiny dense cell of the
tests (granite-3-2b's smoke preset, ``tests/benchmark/data``).  Writes
``tiny-trace.xplane.pb.gz`` to ``OUT_DIR`` (by default
``benchmarks/serving/data``) and, beside it,
``tiny-trace.json`` with what the host counted in that window: decode
calls, admissions and waves.  ``tests/benchmark/test_bench_trace.py``
reduces the committed trace and compares.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if __name__ == "__main__":
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))

OUT = Path(__file__).resolve().parent / "data"
DATA = ROOT / "tests" / "benchmark" / "data"


def main(argv) -> int:
    import jax

    from benchmarks.serving import bench as B
    from benchmarks.serving import run
    if run.devices_or_none(1) is None:
        return 2
    spec = B.Spec(name="tiny-dense", workload={"chips": 1},
                  cell={"driver": "waves", "rate_per_s": 12.0, "slots": 4,
                        "max_len": 64, "kv_block": 16},
                  config=B.load_json(DATA / "tiny-dense.json"),
                  mix=B.load_json(DATA / "tiny.json"), per_layer=[],
                  end_to_end=[])
    out_dir = Path(argv[0]) if argv else OUT
    b = B.Bench(spec, 5)
    b.setup(1.0)
    tmp = tempfile.mkdtemp(prefix="tiny-trace-")
    try:
        out = b.window(1.0, trace_dir=tmp)
        (f,) = glob.glob(f"{tmp}/plugins/profile/*/*.xplane.pb")
        out_dir.mkdir(parents=True, exist_ok=True)
        with open(f, "rb") as src, \
                gzip.open(out_dir / "tiny-trace.xplane.pb.gz", "wb") as dst:
            shutil.copyfileobj(src, dst)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    counts = {"decode_calls": len(out["buckets"]),
              "admissions": len(out["records"]),
              "waves": len(out["waves"]),
              "device_kind": jax.devices()[0].device_kind}
    (out_dir / "tiny-trace.json").write_text(json.dumps(counts, indent=1) + "\n")
    print(json.dumps(counts))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

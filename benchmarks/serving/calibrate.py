#!/usr/bin/env python3
"""Readings that set a cell's rate and its limit, in one process on the chip.

    python3 benchmarks/serving/calibrate.py --workload granite-3-2b.chat \
        --seconds 40 --rates 1.5,2,2.5,3          # the sweep for the knee
    python3 benchmarks/serving/calibrate.py --workload granite-3-2b.chat \
        --seconds 20 --seeds 11,12,13 --control 1 # limit readings
    python3 benchmarks/serving/calibrate.py --workload granite-3-2b.chat \
        --seconds 40 --rates 1.5,2,2.5,3 --then-seconds 20 --seeds 11,12 \
        --control 1                               # both: readings at 0.8 x knee

The cell is set up once; then one window per rate (at the first seed), or
one per seed at the cell's rate.  With both, the sweep comes first, the
knee is the highest rate whose queue did not grow (``queue_growth`` under
``MAX_GROWTH``), and the seeds' windows run at 0.8 times the knee.
Each window prints one JSON line: the end-to-end metrics, the queue's growth over the window and the largest
logit gap of the program, and with ``--control 1`` that of the reference
computed in the cell's control precision at the same positions.  The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
MAX_GROWTH = 0.05     # seconds of queue wait gained per second of window
if __name__ == "__main__":
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))


def queue_growth(records) -> float:
    """Least-squares slope of queue wait against due time: about 0 below
    the knee, about 1 - capacity/load above it."""
    xs = [r["due_s"] for r in records]
    ys = [r["start_s"] - r["due_s"] for r in records]
    n = len(xs)
    mx, my = sum(xs) / n, sum(ys) / n
    sxx = sum((x - mx) ** 2 for x in xs)
    return sum((x - mx) * (y - my) for x, y in zip(xs, ys)) / sxx if sxx else 0.0


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--rates", default="")
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--check", type=int, default=1,
                    help="0: skip the reference (sweeps that set no limit)")
    ap.add_argument("--then-seconds", type=float, default=0.0)
    args = ap.parse_args(argv)

    import jax

    from benchmarks.serving import bench as B
    from benchmarks.serving import run
    from benchmarks.serving.metrics import (output_tokens_per_s,
                                            request_latency_p50_s,
                                            request_latency_p90_s)
    from repro.launch.compile_cache import enable_compile_cache

    spec = B.load_spec(args.workload)
    devices = run.devices_or_none(spec.workload["chips"])
    if devices is None:
        return 2
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    seeds = [int(s) for s in args.seeds.split(",")]
    rates = [float(r) for r in args.rates.split(",") if r]
    b = B.Bench(spec, seeds[0])
    b.setup(args.seconds)
    setup_s = time.perf_counter() - t_start

    def window(seed, rate, seconds, control):
        spec.cell["rate_per_s"] = rate
        out = b.window(seconds, seed=seed)
        view = b.run_view(out, setup_s)
        t = time.perf_counter()
        checks = b.check(out, control=control) if args.check else {}
        line = {"workload": args.workload, "seed": seed, "rate_per_s": rate,
                "seconds": seconds,
                "requests": len(out["records"]), "waves": len(out["waves"]),
                "last_completion_s": view.window_end_s,
                "compiles": out["compiles"],
                "queue_growth": queue_growth(out["records"]),
                "output_tokens_per_s": output_tokens_per_s.read(view),
                "request_latency_p50_s": request_latency_p50_s.read(view),
                "request_latency_p90_s": request_latency_p90_s.read(view),
                "check_s": time.perf_counter() - t, "setup_s": setup_s,
                "memory_peak_bytes": B.memory_bytes(devices),
                **{k: v["value"] for k, v in checks.items()}}
        print(json.dumps(line), flush=True)
        return line

    if rates:
        sweep = [window(seeds[0], r, args.seconds, False) for r in rates]
        if not args.then_seconds:
            return 0
        held = [w["rate_per_s"] for w in sweep
                if w["queue_growth"] < MAX_GROWTH]
        knee = max(held) if held else min(rates)
        rate = round(0.8 * knee, 2)
        print(json.dumps({"knee_per_s": knee, "rate_per_s": rate}),
              flush=True)
        seeds, seconds = seeds[1:], args.then_seconds
    else:
        rate, seconds = spec.cell["rate_per_s"], args.seconds
    for seed in seeds:
        window(seed, rate, seconds, bool(args.control))
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Serving benchmark: one cell of ``BENCHMARK.json`` on the chip.

    python3 benchmarks/serving/run.py --workload granite-3-2b.chat \
        --seed 7 --seconds 40 --trace 0

Builds the cell's engine and scheduler from its files, makes the weights
from the seed on the device, warms every program the traffic can reach,
serves one open-loop window through ``SlotScheduler`` and checks a sample
of what was served against the plain float32 reference.  With ``--trace 1``
the window runs under the profiler and the per-layer metrics are reported
instead of the end-to-end ones.  The last line of standard output is one
JSON object; the numbers compared, each with its limit, are the last lines
of standard error and the last key of that object.  Without a TPU, or with
fewer chips than the cell asks for, it exits 2 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
CACHE_DIR = ROOT / ".jax_cache"      # one fixed path inside the checkout
if __name__ == "__main__":
    # before jax is imported, which reads the variable once
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def devices_or_none(chips: int):
    """The chips to run on, or None (with the reason on stderr) when JAX
    finds no TPU or fewer than the cell asks for."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"run.py: no TPU; JAX sees {devs[0].platform} devices")
        return None
    if len(devs) < chips:
        log(f"run.py: the cell asks for {chips} chips, JAX sees {len(devs)}")
        return None
    return devs[:chips]


def measure(spec, seed: int, seconds: float, trace: bool, devices,
            t_start: float) -> dict:
    """Set up the cell, serve one window, read the metrics, free the
    program's state and check what was served.  Returns the result object
    (``checks`` as its last key)."""
    from benchmarks.serving import bench as B
    b = B.Bench(spec, seed)
    b.setup(seconds)
    served = serve(b, seconds, trace, devices, t_start)
    b.free_program_state()
    return judge(b, served)


def serve(b, seconds: float, trace: bool, devices, t_start: float) -> dict:
    """One window on a set-up bench: its metrics, the device and, traced,
    the breakdown; ``out`` holds what the window served."""
    from benchmarks.serving import bench as B
    from benchmarks.serving import profile_reduce

    trace_dir = tempfile.mkdtemp(prefix="serving-trace-") if trace else None
    try:
        at_start = B.memory_bytes(devices, "bytes_in_use")
        out = b.window(seconds, trace_dir=trace_dir)
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices),
                  "memory_peak_bytes": B.memory_bytes(devices)}
        red = None
        if trace:
            red = profile_reduce.reduce_dir(trace_dir, len(devices))
            device.update(busy_s=red.busy_s, window_s=red.window_s)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    view = b.run_view(out, out["t0"] - t_start, trace=red,
                      device_kind=devices[0].device_kind)
    spec = b.spec
    metrics = {}
    for m in (spec.per_layer if trace else spec.end_to_end):
        mod = importlib.import_module(f"benchmarks.serving.metrics.{m['name']}")
        v = mod.read(view)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    log(f"memory: {at_start} bytes in use at the window's start, peak "
        f"{device['memory_peak_bytes']}")
    late = out["lateness_s"]
    log(f"window: {len(out['arrivals'])} requests due in {seconds} s, "
        f"{len(out['waves'])} waves, last completion "
        f"{view.window_end_s!r} s, {out['compiles']} compiles in the window")
    log(f"generator lateness: {len(late)} waits, largest "
        f"{max(late) if late else 0.0!r} s, mean "
        f"{sum(late) / len(late) if late else 0.0!r} s")
    served = {"out": out, "metrics": metrics, "device": device}
    if red is not None:
        served["breakdown"] = red.breakdown()
    return served


def judge(b, served: dict) -> dict:
    """Check what a window served against the reference; the result
    object."""
    from benchmarks.serving import bench as B
    out = served["out"]
    checks = b.check(out)
    result = {"correct": B.is_correct(checks),
              "attempted": len(out["arrivals"]),
              "failed": checks["requests_incomplete"]["value"],
              "metrics": served["metrics"], "device": served["device"]}
    if "breakdown" in served:
        result["breakdown"] = served["breakdown"]
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    args = parse(argv)
    from benchmarks.serving import bench as B
    spec = B.load_spec(args.workload)
    devices = devices_or_none(spec.workload["chips"])
    if devices is None:
        return 2
    import jax

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    result = measure(spec, args.seed, args.seconds, bool(args.trace),
                     devices, T_START)
    print(json.dumps(result))
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    return 0


if __name__ == "__main__":
    sys.exit(main())

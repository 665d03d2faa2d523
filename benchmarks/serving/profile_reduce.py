"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

* the traced window: the harness's ``window`` span on the host;
* busy time: the union of the intervals in which an operation ran on each
  chip (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), clipped to
  the window and averaged over the chips;
* per-program device time: the ``XLA Modules`` line, by program name with
  its numeric suffix dropped (``jit_step(12)`` -> ``jit_step``);
* per-op device time: the ``XLA Ops`` line, each op named by the program
  it ran in and its HLO name (``jit_step/fusion.12``);
* idle gaps: the window minus the busy union, each named by the innermost
  harness span on the host that holds the gap's midpoint.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import gzip
import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "window"
HOST_SPANS = ("wait_for_arrival", "SlotScheduler.run")
_SUFFIX = re.compile(r"\(-?\d+\)$")
_HLO_NAME = re.compile(r"^%?([^ =]+)")


def program_name(event_name: str) -> str:
    return _SUFFIX.sub("", event_name).strip()


def op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    m = _HLO_NAME.match(event_name)
    return m.group(1) if m else event_name


def union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps_of(busy, lo, hi):
    """The parts of [lo, hi) that no busy interval covers."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                       # mean over the chips
    programs: dict                      # name -> [count, device seconds]
    ops: dict                           # op name -> device seconds
    gaps: list                          # (span name, seconds), longest first

    def program(self, name: str):
        """(calls, device seconds) of a program, summed over chips."""
        n, s = self.programs.get(name, (0, 0.0))
        return n, s

    def breakdown(self) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.gaps[:10]]}


def reduce_events(host_spans, device_planes) -> Reduction:
    """``host_spans``: (name, start_ns, end_ns); ``device_planes``: per chip,
    a dict with ``ops`` and ``modules`` lists of (name, start_ns, end_ns)."""
    win = [(s, e) for n, s, e in host_spans if n == WINDOW_SPAN]
    if not win:
        raise ValueError("the trace holds no window span")
    lo, hi = win[0]
    busy_ns, programs, ops, all_gaps = [], {}, {}, []
    for plane in device_planes:
        busy = union(clip([(s, e) for _, s, e in plane["ops"]], lo, hi))
        busy_ns.append(sum(e - s for s, e in busy))
        mods = sorted((s, e, program_name(n)) for n, s, e in plane["modules"])
        starts = [m[0] for m in mods]
        for name, s, e in plane["ops"]:
            if lo <= s < hi:
                i = bisect.bisect_right(starts, s) - 1
                prog = mods[i][2] if i >= 0 and s < mods[i][1] else "?"
                key = f"{prog}/{op_name(name)}"
                ops[key] = ops.get(key, 0.0) + (e - s) * 1e-9
        for name, s, e in plane["modules"]:
            if lo <= s < hi:
                p = programs.setdefault(program_name(name), [0, 0.0])
                p[0] += 1
                p[1] += (e - s) * 1e-9
        all_gaps += gaps_of(busy, lo, hi)
    spans = [(n, s, e) for n, s, e in host_spans if n in HOST_SPANS]
    named = []
    for s, e in all_gaps:
        mid = (s + e) / 2
        holders = [(ee - ss, n) for n, ss, ee in spans if ss <= mid < ee]
        named.append((min(holders)[1] if holders else WINDOW_SPAN,
                      (e - s) * 1e-9))
    named.sort(key=lambda g: -g[1])
    n_chips = max(1, len(device_planes))
    return Reduction(window_s=(hi - lo) * 1e-9,
                     busy_s=sum(busy_ns) * 1e-9 / n_chips,
                     programs=programs, ops=ops, gaps=named)


def read_xplane(path: str):
    """Host spans and device events of one ``.xplane.pb`` file (or its
    gzip, ``.xplane.pb.gz``)."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    host, devices = [], {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            d = devices.setdefault(int(m.group(1)), {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    d[key] += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events
                         if e.name in HOST_SPANS or e.name == WINDOW_SPAN]
    return host, [devices[k] for k in sorted(devices)]


def reduce_file(path: str, n_chips: int | None = None) -> Reduction:
    host, devices = read_xplane(path)
    if n_chips is not None:
        devices = devices[:n_chips]
    return reduce_events(host, devices)


def reduce_dir(trace_dir: str, n_chips: int | None = None) -> Reduction:
    files = glob.glob(str(Path(trace_dir) / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    if len(files) != 1:
        raise ValueError(f"expected one trace under {trace_dir}, found "
                         f"{len(files)}")
    return reduce_file(files[0], n_chips)

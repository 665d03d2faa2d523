"""Model step: the decode step's share of the chip's roofline.  For each
decode call of the window the least time is the larger of its operations
over the peak bf16 rate and its bytes over the HBM bandwidth (``flops.py``,
at the call's kv bucket for every slot); the mean least time over the mean
device time of the decode program in the trace, in percent."""

from .. import flops
from .decode_step_ms import PROGRAM


def read(run):
    if run.trace is None or run.peaks is None or not run.buckets:
        return None
    n, secs = run.trace.program(PROGRAM)
    if not n:
        return None
    least = [max(f / run.peaks["bf16_flops_per_s"],
                 b / run.peaks["hbm_bytes_per_s"])
             for f, b in (flops.decode_step(run.family, run.model, run.slots,
                                            k) for k in run.buckets)]
    return 100.0 * (sum(least) / len(least)) / (secs / n)

"""``request_latency_p90_s`` read as a per-layer metric, for cells whose
seeds move it too far for a bound: the same 90th percentile of the return
of the ``SlotScheduler.run`` call that served a request minus its due
time."""

from .request_latency_p90_s import read  # noqa: F401

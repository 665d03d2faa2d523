"""90th percentile, over every request due in the window, of the return of
the ``SlotScheduler.run`` call that served it minus its due time."""

from ..summary import percentile


def read(run):
    return percentile([r["end_s"] - r["due_s"] for r in run.records], 90)

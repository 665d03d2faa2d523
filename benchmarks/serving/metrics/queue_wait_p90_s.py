"""Front end: 90th percentile over the window's requests of the start of
the wave that took the request minus its due time."""

from ..summary import percentile


def read(run):
    return percentile([r["start_s"] - r["due_s"] for r in run.records], 90)

"""Output tokens of the requests due in the window, over the time from the
window's start to the last completion (the drain included)."""


def read(run):
    return sum(r["gen_len"] for r in run.records) / run.window_end_s

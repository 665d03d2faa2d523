"""Device: one minus the union of the intervals in which an operation ran
on the chip, over the traced window."""


def read(run):
    if run.trace is None or not run.trace.window_s:
        return None
    return 1.0 - run.trace.busy_s / run.trace.window_s

"""Scheduler: device time per admission of the admission program
(``SlotScheduler._admit``: prefill, the scatter into the slot bank and the
first token), from the trace."""

PROGRAM = "jit__admit"


def read(run):
    if run.trace is None:
        return None
    n, secs = run.trace.program(PROGRAM)
    return secs / n * 1e3 if n else None

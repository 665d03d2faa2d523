"""Engine: device time per call of the batched decode step (``ServeEngine``'s
greedy decode program; the scheduler path runs no other program of that
name), from the trace."""

PROGRAM = "jit_step"


def read(run):
    if run.trace is None:
        return None
    n, secs = run.trace.program(PROGRAM)
    return secs / n * 1e3 if n else None

"""Engine: host time to dispatch one batched decode step, in ms: the seconds
of the ``serve.decode`` spans over their count, from the ``spans`` each
``SlotScheduler.run`` call returns.  Near zero when the host runs ahead of
the chip; near the step's device time when each step waits for the last.
None where the program reports no spans or no decode step."""


def read(run):
    n, secs = 0, 0.0
    for w in run.waves:
        if "spans" not in w:
            return None
        c, s = w["spans"].get("serve.decode", (0, 0.0))
        n += c
        secs += s
    return secs / n * 1e3 if n else None

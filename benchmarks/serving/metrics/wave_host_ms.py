"""Scheduler: host time at the start and end of a wave, in ms per
``SlotScheduler.run`` call: its ``serve.bank`` span (the slot bank built)
plus its ``serve.unpack`` span (the streams assembled after the fetch),
from the ``spans`` each call returns.  None where the program reports no
spans or no wave has decode steps."""


def read(run):
    if not run.waves or any("spans" not in w for w in run.waves):
        return None
    if not any(w["decode_steps"] for w in run.waves):
        return None
    secs = sum(w["spans"].get(name, (0, 0.0))[1] for w in run.waves
               for name in ("serve.bank", "serve.unpack"))
    return secs / len(run.waves) * 1e3

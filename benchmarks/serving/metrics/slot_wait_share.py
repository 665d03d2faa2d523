"""Scheduler: the share of in-wave time a request waits for a free slot,
on the step clock.  Over the window's requests, the sum of the decode
steps its wave had dispatched when it was admitted over the sum of its
wave's decode steps, from the ``requests`` events each
``SlotScheduler.run`` call returns.  None where the program reports no
events or no wave has decode steps."""


def read(run):
    waited = total = 0
    for w in run.waves:
        if "requests" not in w:
            return None
        for admit, _done in w["requests"].values():
            waited += admit
            total += w["decode_steps"]
    return waited / total if total else None

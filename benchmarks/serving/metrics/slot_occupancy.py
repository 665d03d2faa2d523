"""Scheduler: busy slot-steps over all slot-steps of the window's decode
steps, from the stats each ``SlotScheduler.run`` call returns."""


def read(run):
    steps = sum(w["decode_steps"] for w in run.waves)
    if not steps:
        return None
    busy = sum(w["slot_utilization"] * w["decode_steps"] for w in run.waves)
    return busy / steps

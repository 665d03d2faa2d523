"""Scheduler: the share of in-wave time a finished answer waits for its
wave to end, on the step clock.  Over the window's requests, the sum of
(its wave's decode steps - the step after which its last token came out)
over the sum of its wave's decode steps, from the ``requests`` events each
``SlotScheduler.run`` call returns.  With ``slot_wait_share`` and a
request's own ``gen_len - 1`` steps it makes up the whole wave.  None
where the program reports no events or no wave has decode steps."""


def read(run):
    held = total = 0
    for w in run.waves:
        if "requests" not in w:
            return None
        for _admit, done in w["requests"].values():
            held += w["decode_steps"] - done
            total += w["decode_steps"]
    return held / total if total else None

"""Waves: the front end for ``SlotScheduler.run``, which takes a finished
list of requests and returns when all of them are served.

From the window's start the driver hands ``run`` every request due so far.
When the call returns it starts the next wave at once with everything that
fell due meanwhile, and when nothing is due it sleeps until the next due
time.  After the last due request it drains.  A request's latency is the
return time of the call that served it minus its due time; its queue wait
is the start of that call minus its due time.
"""

from __future__ import annotations

from repro.serve import Request


def drive(sched, arrivals, *, clock, sleep, span) -> dict:
    """Serve ``arrivals`` (sorted by ``due_s``) in waves.

    ``clock`` returns seconds, ``sleep`` waits, ``span(name)`` is a context
    manager around each host phase.  Returns per-request records, per-wave
    stats and the generator's lateness, all in seconds from the window's
    start."""
    requests = [Request(rid=a.rid, tokens=a.tokens, gen_len=a.gen_len)
                for a in arrivals]
    records, waves, lateness = [], [], []
    n, i = len(arrivals), 0
    t0 = clock()
    while i < n:
        now = clock() - t0
        if arrivals[i].due_s > now:
            with span("wait_for_arrival"):
                sleep(arrivals[i].due_s - now)
            now = clock() - t0
            lateness.append(now - arrivals[i].due_s)
        j = i
        while j < n and arrivals[j].due_s <= now:
            j += 1
        with span("SlotScheduler.run"):
            start = clock() - t0
            streams, stats = sched.run(requests[i:j])
            end = clock() - t0
        waves.append({"start_s": start, "end_s": end, "n": j - i, **stats})
        for a, s in zip(arrivals[i:j], streams):
            records.append({"rid": a.rid, "due_s": a.due_s, "start_s": start,
                            "end_s": end, "prompt_len": a.prompt_len,
                            "gen_len": a.gen_len, "tokens": s})
        i = j
    return {"records": records, "waves": waves, "lateness_s": lateness}

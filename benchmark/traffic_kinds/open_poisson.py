"""Open loop at a fixed rate: arrivals are due on a schedule whatever the
system does. Inter-arrival gaps are the mid-quantiles of Exp(rate) in blocks
(benchlib/trafficgen.exp_gaps), permuted by the seed: a Poisson-like stream
whose every block of ``block`` arrivals spans exactly block/rate seconds, so
every seed offers the same load. Optional bursts: every ``burst_every_s``
seconds ``burst_size`` arrivals are due at once (their gaps are taken out of
the stream, so the mean rate stays ``rate``)."""

from __future__ import annotations

import random

from benchlib import trafficgen as tg


def plan(mix: dict, seed: int, seconds: float, slots: int) -> tg.Plan:
    rate = float(mix["rate"])
    ramp_s = float(mix.get("ramp_s", 0.0))
    tail_s = float(mix.get("tail_s", 12.0))     # arrivals go on past the
    span = ramp_s + seconds + tail_s            # window, unmeasured
    block = int(mix.get("block", 64))
    count = int(rate * span) + block
    sized = tg.sized_requests(mix, seed, count)
    gaps_q = tg.exp_gaps(rate, block)
    rng = random.Random(seed * 131 + 9)
    due, t = [], 0.0
    while len(due) < count:
        g = gaps_q[:]
        rng.shuffle(g)
        for x in g:
            t += x
            due.append(t)
    due = due[:count]
    burst = int(mix.get("burst_size", 0))
    if burst > 1:
        every = float(mix["burst_every_s"])
        k = 0
        while (k + 1) * every < span:
            at = (k + 1) * every
            # the next `burst` arrivals at or after `at` all come at `at`
            nxt = [i for i, d in enumerate(due) if d >= at][:burst]
            for i in nxt:
                due[i] = at
            k += 1
    reqs = [tg.PlannedRequest(i, tg.prompt_text(seed, i, p), o, due_s=d)
            for i, ((p, o), d) in enumerate(zip(sized, due))]
    return tg.Plan(kind=mix["kind"], loop="open", requests=reqs, rate=rate,
                   ramp_s=ramp_s, meta={"rate": rate})

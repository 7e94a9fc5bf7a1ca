"""Closed loop: ``clients`` callers, each sends its next request when its
last one finished. ``clients`` is a number in the mix, or "slots" for the
configuration's decode slots. The first request of each client gets an output
length spread evenly over (first_out_min, output max), so the clients are out
of step from the start and the ramp is short."""

from __future__ import annotations

import random

from benchlib import trafficgen as tg


def plan(mix: dict, seed: int, seconds: float, slots: int) -> tg.Plan:
    clients = slots if mix["clients"] == "slots" else int(mix["clients"])
    ramp_s = float(mix.get("ramp_s", 0.0))
    # enough requests for the fastest imaginable system: every client
    # finishing its shortest request back to back is far below this
    count = int(mix.get("max_requests", 4096))
    sized = tg.sized_requests(mix, seed, count)
    rng = random.Random(seed * 31 + 5)
    lo = int(mix.get("first_out_min", 16))
    hi = int(mix["output_len"]["max"])
    first = [int(lo + (hi - lo) * (c + 0.5) / clients) for c in range(clients)]
    rng.shuffle(first)
    reqs = []
    for i, (p, o) in enumerate(sized):
        if i < clients:
            o = first[i]
        reqs.append(tg.PlannedRequest(i, tg.prompt_text(seed, i, p), o))
    return tg.Plan(kind=mix["kind"], loop="closed", requests=reqs,
                   clients=clients, ramp_s=ramp_s,
                   meta={"clients": clients})

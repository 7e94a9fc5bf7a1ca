"""The one general traffic generator: a mix file of parameters -> a plan.

Every seed gets THE SAME multiset of sizes (and, in an open loop, of
inter-arrival gaps), in another order: lengths are the stratified quantiles
of the mix's distributions in blocks of ``block`` requests, and ``--seed``
only permutes within each block and draws the prompt text. So two seeds offer
the same work, and a block of arrivals always spans exactly block/rate
seconds. The plan is a pure function of (mix, seed, clients).

A mix names its ``kind``; ``benchmark/traffic_kinds/<kind>.py`` turns the
sized requests into a closed loop or a schedule. A new kind is a new file.
"""

from __future__ import annotations

import math
import random
import statistics
from dataclasses import dataclass, field
from typing import List, Optional

from benchlib import files

# printable ASCII: one byte-tokenizer token per character
ALPHABET = "".join(chr(c) for c in range(0x20, 0x7f))


@dataclass
class PlannedRequest:
    idx: int
    prompt: str
    max_tokens: int
    due_s: Optional[float] = None     # open loop: seconds from traffic start


@dataclass
class Plan:
    kind: str
    loop: str                         # "closed" | "open"
    requests: List[PlannedRequest]
    clients: int = 0                  # closed loop
    rate: float = 0.0                 # open loop, requests/s
    ramp_s: float = 0.0               # traffic runs this long before the window
    body_extra: dict = field(default_factory=dict)   # merged into each body
    grace_s: float = 10.0             # a measured request may finish this
                                      # long after the window; later = failed
    meta: dict = field(default_factory=dict)


def _quantiles(dist: dict, n: int) -> List[int]:
    """n stratified draws (mid-quantiles) of a length distribution."""
    lo, hi = int(dist["min"]), int(dist["max"])
    out = []
    for i in range(n):
        u = (i + 0.5) / n
        if dist["dist"] == "uniform":
            v = lo + (hi - lo) * u
        elif dist["dist"] == "lognormal":
            z = statistics.NormalDist().inv_cdf(u)
            v = math.exp(math.log(dist["median"]) + dist["sigma"] * z)
        elif dist["dist"] == "fixed":
            v = dist["value"]
        else:
            raise ValueError(f"unknown length distribution {dist['dist']!r}")
        out.append(int(min(hi, max(lo, round(v)))))
    return out


def sized_requests(mix: dict, seed: int, count: int) -> List[tuple]:
    """[(prompt_len, out_len)] x count: per block the same two multisets,
    paired and ordered by the seed."""
    block = int(mix.get("block", 64))
    p_q = _quantiles(mix["prompt_len"], block)
    o_q = _quantiles(mix["output_len"], block)
    rng = random.Random(int(seed) * 2654435761 % (2**61) + 17)
    out: List[tuple] = []
    while len(out) < count:
        p, o = p_q[:], o_q[:]
        rng.shuffle(p)
        rng.shuffle(o)
        out += list(zip(p, o))
    return out[:count]


def prompt_text(seed: int, idx: int, n: int) -> str:
    """n printable-ASCII characters, distinct per (seed, idx) from the first
    character on, so no two prompts share a page-long prefix."""
    rng = random.Random((int(seed) << 20) ^ (idx * 7919 + 1))
    return "".join(rng.choices(ALPHABET, k=n))


def exp_gaps(rate: float, block: int) -> List[float]:
    """Mid-quantiles of Exp(rate), rescaled so a block sums to block/rate."""
    raw = [-math.log(1.0 - (i + 0.5) / block) for i in range(block)]
    scale = block / rate / sum(raw)
    return [g * scale for g in raw]


def make_plan(mix: dict, seed: int, seconds: float, slots: int) -> Plan:
    kind = files.load_module("traffic_kinds", mix["kind"])
    if kind is None:
        raise SystemExit(f"no traffic kind benchmark/traffic_kinds/"
                         f"{mix['kind']}.py")
    plan = kind.plan(mix, int(seed), float(seconds), int(slots))
    plan.body_extra = dict(mix.get("request_extra", {}))
    plan.grace_s = float(mix.get("grace_s", 10.0))
    return plan

"""From a profiler trace (.xplane.pb) to plain event lists and the numbers
the device metrics are made of. Read with nothing but JAX
(``jax.profiler.ProfileData``); event names are taken as today's trace gives
them (see README.md, "What the trace looks like").

Device planes are named ``/device:TPU:<n>``. On each, the line ``XLA
Modules`` holds one event per execution of a jitted program (named
``jit_<function>(<fingerprint>)``), and ``XLA Ops`` one per HLO operation.
The host plane ``/host:CPU`` holds a line per thread.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

Event = Tuple[str, int, int]          # (name, start ns, duration ns)

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
# control-flow ops whose event spans the events of their bodies (the layer
# scan is one ``while``): left out wherever op times are summed
CONTAINERS = {"while", "conditional", "call"}


@dataclass
class DeviceTrace:
    ordinal: int
    modules: List[Event] = field(default_factory=list)
    ops: List[Event] = field(default_factory=list)


@dataclass
class Trace:
    devices: List[DeviceTrace] = field(default_factory=list)
    host: Dict[str, List[Event]] = field(default_factory=dict)  # per thread


def find_xplane(trace_dir: str) -> Optional[str]:
    hits = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                         "*", "*.xplane.pb")))
    return hits[-1] if hits else None


def load(path: str, host_lines: bool = True) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    tr = Trace()
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = DeviceTrace(int(m.group(1)))
            for line in plane.lines:
                if line.name == MODULE_LINE:
                    dev.modules = [(e.name, int(e.start_ns),
                                    int(e.duration_ns)) for e in line.events]
                elif line.name == OPS_LINE:
                    dev.ops = [(e.name, int(e.start_ns), int(e.duration_ns))
                               for e in line.events]
            dev.ops = [e for e in dev.ops if short_op(e[0]) not in CONTAINERS]
            tr.devices.append(dev)
        elif host_lines and plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                evs = [(e.name, int(e.start_ns), int(e.duration_ns))
                       for e in line.events]
                if evs:
                    tr.host.setdefault(line.name, []).extend(evs)
    tr.devices.sort(key=lambda d: d.ordinal)
    return tr


# -- arithmetic on event lists (pure; tested on the recorded trace) ----------


def union_ns(events: List[Event]) -> int:
    """Length of the union of the events' intervals."""
    total, cur_s, cur_e = 0, None, None
    for _, s, d in sorted(events, key=lambda e: e[1]):
        e = s + d
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(events: List[Event], t0: int, t1: int) -> List[Tuple[int, int]]:
    """Idle intervals (start, duration) of [t0, t1] not covered by events."""
    out, cur = [], t0
    for _, s, d in sorted(events, key=lambda e: e[1]):
        if s > cur:
            out.append((cur, min(s, t1) - cur))
        cur = max(cur, s + d)
        if cur >= t1:
            break
    if cur < t1:
        out.append((cur, t1 - cur))
    return [(s, d) for s, d in out if d > 0]


def span_ns(tr: Trace) -> Tuple[int, int]:
    """The traced span on the device clock: first op start to last op end
    over all device planes."""
    starts = [e[1] for d in tr.devices for e in (d.ops or d.modules)]
    ends = [e[1] + e[2] for d in tr.devices for e in (d.ops or d.modules)]
    return (min(starts), max(ends)) if starts else (0, 0)


def busy_seconds(tr: Trace) -> float:
    """Seconds in which an operation ran, averaged over the device planes."""
    if not tr.devices:
        return 0.0
    return sum(union_ns(d.ops or d.modules)
               for d in tr.devices) / len(tr.devices) / 1e9


def short_op(name: str) -> str:
    """Today's trace names an op by its whole HLO line, ``%fusion.12 = bf16[..]
    fusion(...)``: keep the instruction's name, without ``%`` and without the
    trailing instance number."""
    m = re.match(r"^%?([^\s=]+)", name)
    base = m.group(1) if m else name
    return re.sub(r"[.]\d+$", "", base)


def program_of(module_name: str) -> str:
    """``jit_decode_steps(1234...)`` -> ``decode_steps``."""
    m = re.match(r"^jit_([A-Za-z0-9_]+)", module_name)
    return m.group(1) if m else module_name


def module_time(tr: Trace, programs: set) -> Tuple[int, float]:
    """(executions, device seconds) of the named programs on device 0 (under
    SPMD every chip runs the same program at the same time)."""
    if not tr.devices:
        return 0, 0.0
    evs = [e for e in tr.devices[0].modules if program_of(e[0]) in programs]
    return len(evs), sum(e[2] for e in evs) / 1e9


def ops_inside(tr: Trace, programs: set, name_re: str) -> List[Event]:
    """Ops on device 0 matching ``name_re`` that ran inside an execution of
    one of ``programs`` (by time containment in the module line)."""
    if not tr.devices:
        return []
    dev = tr.devices[0]
    spans = sorted((e[1], e[1] + e[2]) for e in dev.modules
                   if program_of(e[0]) in programs)
    pat = re.compile(name_re)
    out, i = [], 0
    for ev in sorted(dev.ops, key=lambda e: e[1]):
        if not pat.search(ev[0]):
            continue
        while i < len(spans) and spans[i][1] < ev[1]:
            i += 1
        if i < len(spans) and spans[i][0] <= ev[1] < spans[i][1]:
            out.append(ev)
    return out


def top_ops(tr: Trace, n: int = 10) -> List[list]:
    """Device operations that took most time on device 0, by name with the
    trailing instance number dropped, prefixed with the program they ran in."""
    if not tr.devices:
        return []
    dev = tr.devices[0]
    mods = sorted((e[1], e[1] + e[2], program_of(e[0])) for e in dev.modules)
    acc: Dict[str, int] = {}
    i = 0
    for name, s, d in sorted(dev.ops, key=lambda e: e[1]):
        while i < len(mods) and mods[i][1] < s:
            i += 1
        prog = mods[i][2] if i < len(mods) and mods[i][0] <= s else "-"
        key = f"{prog}:{short_op(name)}"
        acc[key] = acc.get(key, 0) + d
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in top]


def idle_by_host_span(tr: Trace, thread_re: str, n: int = 10) -> List[list]:
    """The device's idle time on device 0, attributed to the host event of
    the matching thread(s) that overlaps each gap most ("-" if none): the
    longest totals, [[name, seconds], ...]."""
    if not tr.devices:
        return []
    dev = tr.devices[0]
    t0, t1 = span_ns(tr)
    busy = dev.ops or dev.modules
    pat = re.compile(thread_re)
    host = sorted((e for name, evs in tr.host.items() if pat.search(name)
                   for e in evs), key=lambda e: e[1])
    acc: Dict[str, int] = {}
    starts = [e[1] for e in host]
    import bisect

    for gs, gd in gaps(busy, t0, t1):
        if gd < 20_000:            # < 20 us: launch spacing, not a stall
            acc["<20us gaps"] = acc.get("<20us gaps", 0) + gd
            continue
        best, best_ov = "-", 0
        j = bisect.bisect_right(starts, gs + gd)
        k = j - 1
        # walk back over events that start before the gap ends
        while k >= 0 and j - k < 2000:
            name, s, d = host[k]
            ov = min(s + d, gs + gd) - max(s, gs)
            if ov > best_ov:
                best, best_ov = name, ov
            k -= 1
        acc[best] = acc.get(best, 0) + gd
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in top]


def summary(tr: Trace, n: int = 40) -> dict:
    """What a person looks at before writing a reader against a trace."""
    out = {"devices": len(tr.devices), "host_threads": sorted(tr.host)[:40]}
    if tr.devices:
        d = tr.devices[0]
        mods: Dict[str, list] = {}
        for name, _, dur in d.modules:
            m = mods.setdefault(name, [0, 0])
            m[0] += 1
            m[1] += dur
        out["modules"] = {k: [c, t / 1e9] for k, (c, t) in mods.items()}
        out["top_ops"] = top_ops(tr, n)
        t0, t1 = span_ns(tr)
        out["span_s"] = (t1 - t0) / 1e9
        out["busy_s"] = busy_seconds(tr)
    return out


if __name__ == "__main__":      # python3 benchmark/benchlib/trace_reduce.py <file>
    import json
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    print(json.dumps(summary(load(sys.argv[1]), 40), indent=1))

"""What the engine loop reports of itself, for the layer-metric readers.

Three sources, all written by the program (serving/programs.py) and read
here without a change to it:

- **dispatch records**: one ``engine.dispatch`` span per device dispatch
  through the server tracer's exporter (``LayerContext.spans``, the whole
  window), whose attributes say what the program was given: ``seq``,
  ``program``, ``active``, ``ctx_tokens``, ``chunk_n``, ``chunk_rows``, ...;
- **engine phases**: ``engine.*`` ``TraceAnnotation`` events on the engine
  thread's line of ``/host:CPU`` in the traced slice's ``.xplane.pb``, with
  ``seq`` and ``program`` as stats on ``engine.dispatch`` (trace_reduce.load
  keeps no stats, so the file is read once more here);
- **compile stages**: the process-wide counter
  ``tpu_serve_compile_stage_seconds_total{program,stage}``.

A program that has none of these (the parent of the PR that added them)
gives empty results, and the readers return None: nothing here raises for a
missing source. Also here: the operations and bytes one ragged-attention
call needs, and the device's idle time by engine phase
(``python3 benchmark/benchlib/engine_loop.py <trace dir or file>``).
"""

from __future__ import annotations

import os
import statistics
from typing import Dict, List, Optional, Tuple

DISPATCH = "engine.dispatch"
# the engine thread is not doing host work of its own in these two
WAITING = {"engine.fetch", "engine.idle"}

Phase = Tuple[str, int, int, dict]      # (name, start ns, duration ns, stats)

_cache: dict = {}


# -- the xplane's engine.* events ---------------------------------------------


def load_phases(path: str) -> List[Phase]:
    """``engine.*`` events of the engine thread (the host line that holds
    ``engine.dispatch``), by start; [] where the trace has none."""
    key = (path, os.path.getmtime(path))
    if key in _cache:
        return _cache[key]
    from jax.profiler import ProfileData

    best: List[Phase] = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            evs = [(e.name, int(e.start_ns), int(e.duration_ns),
                    dict(e.stats) if e.name == DISPATCH else {})
                   for e in line.events if e.name.startswith("engine.")]
            if sum(e[0] == DISPATCH for e in evs) > \
                    sum(e[0] == DISPATCH for e in best):
                best = evs
    best.sort(key=lambda e: e[1])
    _cache.clear()
    _cache[key] = best
    return best


def phases_of(ctx) -> List[Phase]:
    """The traced slice's engine phases, or [] (no trace, no annotations)."""
    if ctx.trace is None:
        return []
    from benchlib import session, trace_reduce

    path = trace_reduce.find_xplane(session.TRACE_DIR)
    return load_phases(path) if path else []


def flatten(phases: List[Phase]) -> List[Tuple[str, int, int]]:
    """Non-overlapping (name, start, end) segments, each named after the
    INNERMOST phase open there (annotations nest properly on one thread: a
    blocking settle inside an admission is ``engine.fetch`` time)."""
    out: List[Tuple[str, int, int]] = []
    stack: List[Tuple[str, int]] = []       # (name, end)
    cur = 0

    def close_until(t: int):
        nonlocal cur
        while stack and stack[-1][1] <= t:
            name, end = stack.pop()
            if end > cur:
                out.append((name, cur, end))
                cur = end

    for name, s, d, _ in phases:
        close_until(s)
        if stack and s > cur:
            out.append((stack[-1][0], cur, s))
        cur = max(cur, s)
        stack.append((name, s + d))
    close_until(1 << 62)
    return out


def host_seconds_per_dispatch(phases: List[Phase]) -> Optional[float]:
    """Engine-thread seconds inside ``engine.*`` phases other than the two
    it waits in, per ``engine.dispatch`` event."""
    n = sum(1 for p in phases if p[0] == DISPATCH)
    if not n:
        return None
    busy = sum(e - s for name, s, e in flatten(phases)
               if name not in WAITING)
    return busy / 1e9 / n


def idle_by_phase(trace, phases: List[Phase],
                  min_gap_ns: int = 20_000) -> Dict[str, float]:
    """Seconds of device 0's idle gaps (over ``min_gap_ns``) by the engine
    phase open during them; ``-`` = no phase open, ``<20us gaps`` apart."""
    from benchlib import trace_reduce as tr

    if trace is None or not trace.devices:
        return {}
    dev = trace.devices[0]
    t0, t1 = tr.span_ns(trace)
    segs = flatten(phases)
    acc: Dict[str, int] = {}
    i = 0
    for gs, gd in tr.gaps(dev.ops or dev.modules, t0, t1):
        if gd < min_gap_ns:
            acc["<20us gaps"] = acc.get("<20us gaps", 0) + gd
            continue
        ge, covered = gs + gd, 0
        while i < len(segs) and segs[i][2] <= gs:
            i += 1
        j = i
        while j < len(segs) and segs[j][1] < ge:
            ov = min(segs[j][2], ge) - max(segs[j][1], gs)
            if ov > 0:
                acc[segs[j][0]] = acc.get(segs[j][0], 0) + ov
                covered += ov
            j += 1
        if gd > covered:
            acc["-"] = acc.get("-", 0) + gd - covered
    return {k: v / 1e9 for k, v in sorted(acc.items(), key=lambda kv: -kv[1])}


# -- dispatch records and their join with the device's executions -------------


def dispatch_records(spans) -> Dict[int, tuple]:
    """{seq: (start ns, end ns, attributes)} of the ``engine.dispatch``
    spans in ``LayerContext.spans`` (unix ns on the host clock)."""
    return {int(s[4]["seq"]): (int(s[2]), int(s[3]), s[4])
            for s in spans if s[1] == DISPATCH and "seq" in s[4]}


def clock_offset_ns(records: Dict[int, tuple],
                    phases: List[Phase]) -> Optional[int]:
    """xplane time minus the span clock, from the dispatches both hold (the
    record's ``t_enqueue`` is read just before the annotation opens)."""
    d = [p[1] - records[int(p[3]["seq"])][0] for p in phases
         if p[0] == DISPATCH and "seq" in p[3]
         and int(p[3]["seq"]) in records]
    return int(statistics.median(d)) if d else None


def join_executions(trace, records: Dict[int, tuple], phases: List[Phase],
                    program: str, slack_ns: int = 1_000_000) -> List[tuple]:
    """[(module event, attributes or None)] for every execution of
    ``jit_<program>`` on device 0, in order. An execution belongs to the
    earliest record of that program, not yet taken, whose [enqueue, ready]
    window (moved onto the xplane's clock) holds it: one stream, in order.
    The records cover the whole window, so only an execution cut by the
    slice's edge can stay unjoined."""
    from benchlib import trace_reduce as tr

    off = clock_offset_ns(records, phases)
    if trace is None or not trace.devices or off is None:
        return []
    execs = sorted((e for e in trace.devices[0].modules
                    if tr.program_of(e[0]) == program), key=lambda e: e[1])
    recs = sorted((r for r in records.values()
                   if r[2].get("program") == program), key=lambda r: r[0])
    out, i = [], 0
    for ev in execs:
        s, e = ev[1], ev[1] + ev[2]
        while i < len(recs) and recs[i][1] + off + slack_ns < e:
            i += 1                      # fetched before this one ended
        if i < len(recs) and recs[i][0] + off - slack_ns <= s:
            out.append((ev, recs[i][2]))
            i += 1
        else:
            out.append((ev, None))
    return out


def ragged_attention_call(mc: dict, rec: dict, kv_itemsize: int = 2,
                          chips: int = 1) -> tuple:
    """(flops, bytes) ONE ragged-attention call (one layer) of a mixed
    dispatch needs, per chip, from its record: ``active`` decode rows of one
    query each over contexts summing ``ctx_tokens`` (plus the rows an
    unfetched predecessor wrote, ``carry_steps`` each, plus the row being
    written), and ``chunk_n`` prompt rows at ``chunk_off``, row i causal
    over ``chunk_off + i + 1`` keys. Bytes: every K/V row the call attends
    over once, q in and out (bf16); padding rows of the chunk need nothing.
    Flops: q.k and p.v, 2 per multiply-add."""
    hq, d = mc["num_heads"], mc["head_dim"]
    active, n, off = rec["active"], rec["chunk_n"], rec["chunk_off"]
    dec_keys = rec["ctx_tokens"] + active * (rec.get("carry_steps", 0) + 1)
    kv_rows = dec_keys + off + n
    pairs = dec_keys + n * off + n * (n + 1) // 2
    row = 2 * mc["num_kv_heads"] * d * kv_itemsize
    byts = (kv_rows * row + 2 * (active + n) * hq * d * 2) / chips
    flops = 4.0 * pairs * hq * d / chips
    return flops, byts


# -- compile stages -----------------------------------------------------------


def compile_stage_seconds(stages: set) -> Optional[float]:
    """Seconds the step programs (every ``program`` but ``other``) spent in
    ``stages`` so far in this process; None where the program has no such
    counter or it is still empty."""
    try:
        from aws_k8s_ansible_provisioner_tpu.serving import metrics

        totals = metrics.compile_stages.stage_totals()
    except (ImportError, AttributeError):
        return None
    mine = [v for (prog, stage), v in totals.items()
            if prog != "other" and stage in stages]
    return sum(mine) if mine else None


if __name__ == "__main__":
    import json
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchlib import trace_reduce

    arg = sys.argv[1]
    path = arg if os.path.isfile(arg) else trace_reduce.find_xplane(arg)
    ph = load_phases(path)
    tr_ = trace_reduce.load(path, host_lines=False)
    idle = idle_by_phase(tr_, ph)
    big = {k: v for k, v in idle.items() if k != "<20us gaps"}
    named = sum(v for k, v in big.items() if k != "-")
    print(json.dumps({
        "phases": {n: sum(1 for p in ph if p[0] == n)
                   for n in sorted({p[0] for p in ph})},
        "host_ms_per_dispatch": (host_seconds_per_dispatch(ph) or 0) * 1e3,
        "idle_s_by_phase": idle,
        "idle_over_20us_inside_a_phase_pct":
            100.0 * named / sum(big.values()) if big else None}, indent=1))

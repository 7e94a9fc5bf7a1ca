"""Roofline share of the ragged paged-attention kernel inside ``mixed_step``.

need = for every ``jit_mixed_step`` execution of the traced slice that joins
       a dispatch record (benchlib/engine_loop.join_executions): the kernel's
       calls inside it x max(flops / peak, bytes / peak HBM bandwidth) of ONE
       call, from the record's ``active``, ``ctx_tokens``, ``carry_steps``,
       ``chunk_n``, ``chunk_off`` (engine_loop.ragged_attention_call);
time = device time of those calls (``%ragged_attend_pallas_paged`` events
       inside the joined executions).
The binding bound at these cells' shapes is bandwidth: a call streams the
decode rows' whole context (~25 k K/V rows, ~0.1 GB on the 0.6B) for about
2 GFLOP of work, 13 flops per byte against a ridge of 240. Source:
device_trace (time) over program_span (the record)."""

from benchlib import engine_loop, opsbytes
from benchlib import trace_reduce as tr

KERNEL_RE = r"^%ragged_attend_pallas_paged"


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    joined = [(ev, rec) for ev, rec in engine_loop.join_executions(
        ctx.trace, engine_loop.dispatch_records(ctx.spans),
        engine_loop.phases_of(ctx), "mixed_step") if rec is not None]
    calls = sorted(tr.ops_inside(ctx.trace, {"mixed_step"}, KERNEL_RE),
                   key=lambda e: e[1])
    need = secs = 0.0
    i = 0
    for ev, rec in joined:
        s, e = ev[1], ev[1] + ev[2]
        while i < len(calls) and calls[i][1] < s:
            i += 1
        mine = []
        while i < len(calls) and calls[i][1] < e:
            mine.append(calls[i])
            i += 1
        flops, byts = engine_loop.ragged_attention_call(
            ctx.mc, rec, ctx.engine["kv_itemsize"], ctx.chips)
        least, _ = opsbytes.roofline_seconds(flops, byts, ctx.peaks)
        need += least * len(mine)
        secs += sum(c[2] for c in mine) / 1e9
    return 100.0 * need / secs if secs else None

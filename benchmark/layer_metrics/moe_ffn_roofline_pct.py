"""Roofline share of the expert FFN inside the decode program.

need = for every ``jit_decode_steps`` execution of the traced slice that
       joins a dispatch record (benchlib/engine_loop.join_executions):
       max(flops / peak, bytes / peak HBM bandwidth) of what that dispatch's
       expert FFNs need (benchlib/moe_opsbytes.decode_dispatch: the routed
       rows through three matmuls; the stacks of the experts HIT once, their
       scales, the rows in and out), from the record's ``moe_rows``,
       ``moe_experts_hit`` and ``horizon``;
time = device time of the operations that take an expert stack as an
       operand (moe_opsbytes.expert_ops_re) inside the joined executions.
At a decode batch the bound is bandwidth: 24 x 8 rows need 2.4 GFLOP a layer
against 0.4 GB of int8 stacks, 6 flops a byte against a ridge of 240.
Source: device_trace (time) over program_span (the record)."""

from benchlib import engine_loop, moe_opsbytes, opsbytes
from benchlib import trace_reduce as tr


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    joined = [(ev, rec) for ev, rec in engine_loop.join_executions(
        ctx.trace, engine_loop.dispatch_records(ctx.spans),
        engine_loop.phases_of(ctx), "decode_steps")
        if rec is not None and "moe_experts_hit" in rec]
    calls = sorted(tr.ops_inside(ctx.trace, {"decode_steps"},
                                 moe_opsbytes.expert_ops_re(ctx.mc)),
                   key=lambda e: e[1])
    need = secs = 0.0
    i = 0
    for ev, rec in joined:
        s, e = ev[1], ev[1] + ev[2]
        while i < len(calls) and calls[i][1] < s:
            i += 1
        mine = 0
        while i < len(calls) and calls[i][1] < e:
            mine += calls[i][2]
            i += 1
        if not mine:
            continue
        flops, byts = moe_opsbytes.decode_dispatch(
            ctx.mc, rec, ctx.engine["w_itemsize"])
        need += opsbytes.roofline_seconds(flops, byts, ctx.peaks)[0]
        secs += mine / 1e9
    return 100.0 * need / secs if secs else None

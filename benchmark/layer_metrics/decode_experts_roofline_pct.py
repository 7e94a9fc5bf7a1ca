"""Roofline share of the routed experts inside the decode program of a
list whose leading layers are dense (``moe_ffn_roofline_pct`` counts every
layer as routed and looks for a stack with ``num_layers`` in front).

need = for every ``jit_decode_steps`` execution of the traced slice that
       joins a dispatch record: max(flops / peak, bytes / peak HBM
       bandwidth) of what that dispatch's ROUTED layers need
       (benchlib/trinity_opsbytes.experts_need_and_time: the routed rows
       through three matmuls; the stacks of the experts HIT once a layer
       and substep, their scales, the rows in and out), from the record's
       ``moe_rows``, ``moe_experts_hit`` and ``horizon``;
time = device time of the operations that take an expert stack as an
       operand inside the joined executions.
At a decode batch the bound is bandwidth: 48 x 8 rows reach ~122 of 128
experts a layer, 0.77 GB of int8 stacks a layer against 4.8 GFLOP. None
for any other model, without a device plane, or for a program whose
records carry no routing counts. Source: device_trace (time) over
program_span (the record)."""

from benchlib import trinity_opsbytes as tob


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices \
            or not tob.has_both_kinds(ctx.mc):
        return None
    need, secs = tob.experts_need_and_time(ctx)
    return 100.0 * need / secs if secs else None

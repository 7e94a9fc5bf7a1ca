"""Roofline share of the routed experts inside the decode program at a WIDE
batch — ``decode_experts_roofline_pct`` under another name (PERF.md section
7), with the time taken by part name.

need = for every joined ``jit_decode_steps`` execution: max(flops / peak,
       bytes / peak HBM bandwidth) of what that dispatch's ROUTED layers
       need (``moe_opsbytes.decode_dispatch`` over the 22 routed layers: the
       record's ``moe_rows`` through three matmuls; the stacks of the
       ``moe_experts_hit`` experts once a layer and substep, their scales,
       the rows in and out). At 128 rows x top-4 every one of the 32
       experts is hit: 352 MB a layer = 0.43 ms against 11.3 GFLOP = 0.06
       ms — the ROUTED rows are bandwidth-bound; the every-expert form
       computes all 32 experts for all rows (8 x the flops, 0.46 ms), and
       what it loses shows here;
time = device time of the ``experts`` part's operations inside the joined
       executions.
None for a model without "c" layers or records without routing counts.
Source: device_trace (time) over program_span (the record)."""

from benchlib import lfm2_opsbytes as lob


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices or not lob.is_lfm2(ctx.mc):
        return None
    need, secs = lob.experts_need_and_time(ctx)
    return 100.0 * need / secs if secs else None

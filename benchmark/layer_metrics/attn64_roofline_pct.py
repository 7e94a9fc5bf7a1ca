"""Roofline share of the paged decode-attention kernel where the heads are 64
wide (two a 128-lane pool row).

need = for every joined ``jit_decode_steps`` execution: the record's
       ``attn_pages_live`` (pages the rows hold, per attention layer, summed
       over substeps) x the 6 attention layers x 131,072 B a page (K and V,
       8 KV heads x 64 tokens x 64 wide, bf16) + q in and o out, over the
       peak HBM bandwidth (benchlib/lfm2_opsbytes.attn_decode_dispatch; 16
       flops a byte, under the ridge);
time = device time of the ``%decode_attend_pallas_paged`` calls inside the
       joined executions.
What the walk adds (a block of 8 rows walks its longest row's pages) and the
zero lanes of the widened q are not need: they are what the share loses.
None for a model without "c" layers. Source: device_trace (time) over
program_span (the record)."""

from benchlib import lfm2_opsbytes as lob


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices or not lob.is_lfm2(ctx.mc):
        return None
    need, secs = lob.attn_need_and_time(ctx)
    return 100.0 * need / secs if secs else None

"""The attention branch's share of the decode program's device time in a block
with two mixers — the OTHER branch of the same layer, beside
``ssm_share_pct`` —, by part NAME: device time of the ``decode_steps``
operations of the traced slice in the part ``attn.core`` (the paged decode
kernel at a query group of 5, the row write, the length order's gathers) /
device time of those executions (benchlib/falcon_h1_opsbytes.part_share).
None for a model without "h" layers or where no operation carries the part.
Source: device_trace."""

from benchlib import falcon_h1_opsbytes as fob


def read(ctx):
    return fob.part_share(ctx, "decode_steps", "attn.core")

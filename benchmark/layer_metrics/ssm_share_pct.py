"""The state-space branch's share of the decode program's device time, by part
NAME: device time of the ``decode_steps`` operations of the traced slice in
the part ``recur`` (models/parts.py: the biased SiLU convolution and its tail
rows, the decode update over the per-slot state, the D skip, the gated group
norm — the projections in and out are ``attn.proj`` / ``attn.out`` and not in
it) / device time of those executions (benchlib/falcon_h1_opsbytes
.part_share). None for a model without "h" layers or where no operation
carries the part. Source: device_trace."""

from benchlib import falcon_h1_opsbytes as fob


def read(ctx):
    return fob.part_share(ctx, "decode_steps", "recur")

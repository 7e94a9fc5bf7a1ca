"""The state-space branch's share of the MIXED program's device time, by part
NAME: device time of the ``mixed_step`` operations of the traced slice in the
part ``recur`` (the decode rows' update and, for the chunk, the span form: the
convolution over the carried tail, the blocked scalar-decay recurrence, the
gated norm) / device time of those executions (benchlib/falcon_h1_opsbytes
.part_share). An admission is one such step: this is how much of a first
token's wait the span form is. None for a model without "h" layers or where
no operation carries the part. Source: device_trace."""

from benchlib import falcon_h1_opsbytes as fob


def read(ctx):
    return fob.part_share(ctx, "mixed_step", "recur")

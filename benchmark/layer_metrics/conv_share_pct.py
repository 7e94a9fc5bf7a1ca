"""The gated short convolutions' share of the decode program's device time,
by part NAME: device time of the ``decode_steps`` operations of the traced
slice in the part ``recur`` (models/parts.py: the gates ``B * X`` and ``C *
conv``, the taps, the tail rows read and written — the projections in and
out are ``attn.proj`` / ``attn.out`` and not in it) / device time of those
executions. Says how much of a step the mechanism that mixes tokens in 18
of 24 layers costs beyond its two matmuls. None for a model without "c"
layers or where no operation carries the part. Source: device_trace."""

from benchlib import lfm2_opsbytes as lob
from benchlib import op_parts
from benchlib import trace_reduce as tr


def read(ctx):
    if not lob.is_lfm2(ctx.mc):
        return None
    evs = op_parts.of_context(ctx)
    if not evs:
        return None
    n, secs = tr.module_time(ctx.trace, {"decode_steps"})
    mine = op_parts.seconds(evs, "decode_steps", ("recur",))
    if not n or not secs or not mine:
        return None
    return 100.0 * mine / secs

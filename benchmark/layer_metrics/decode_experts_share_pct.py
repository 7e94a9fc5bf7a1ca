"""The routed experts' share of the decode program's device time, by part
NAME: device time of the ``decode_steps`` operations of the traced slice in
the parts ``router`` and ``experts`` (models/parts.py: the scores, the bias,
the top-k, the assignments and one-hots; the grouped or every-expert
matmuls with their dequantisation, the combine) / device time of those
executions — ``decode_dense_share_pct``'s denominator, so the two and the
attention kernels' shares add up. The shared expert and a dense layer's FFN
are ``mlp`` and not in it. None where no operation carries either part.
Source: device_trace."""

from benchlib import op_parts
from benchlib import trace_reduce as tr


def read(ctx):
    evs = op_parts.of_context(ctx)
    if not evs:
        return None
    n, secs = tr.module_time(ctx.trace, {"decode_steps"})
    routed = op_parts.seconds(evs, "decode_steps", ("router", "experts"))
    if not n or not secs or not routed:
        return None
    return 100.0 * routed / secs

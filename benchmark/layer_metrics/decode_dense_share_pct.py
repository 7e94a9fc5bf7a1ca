"""The dense matmuls' share of the decode program's device time: device time
of the ``decode_steps`` operations of the traced slice in the parts
``attn.proj``, ``attn.out``, ``mlp`` and ``head`` (benchlib/op_parts.DENSE:
the projections, the dense or shared FFN and the vocabulary matmul, each
with the dequantisation and the elementwise tail fused behind it) / device
time of those executions — ``moe_ffn_share_pct``'s denominator, so the two
and the attention kernel's share add up. The size of the int8 weight
stream (ROADMAP A3) as one number a cell, the expert and hybrid cells
included. None where no operation carries a part. Source: device_trace."""

from benchlib import op_parts
from benchlib import trace_reduce as tr


def read(ctx):
    evs = op_parts.of_context(ctx)
    if not evs:
        return None
    n, secs = tr.module_time(ctx.trace, {"decode_steps"})
    dense = op_parts.seconds(evs, "decode_steps", op_parts.DENSE)
    if not n or not secs or not dense:
        return None
    return 100.0 * dense / secs

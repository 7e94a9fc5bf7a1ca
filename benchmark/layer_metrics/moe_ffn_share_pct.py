"""The expert FFN's share of the decode program's device time: device time
of the operations that take an expert stack as an operand
(benchlib/moe_opsbytes.expert_ops_re: the every-expert form's fused batched
matmuls, or the sorted form's ``ragged-dot`` calls) inside ``decode_steps``
executions of the traced slice / device time of those executions. Says whether the mechanism that makes this block different does
most of the work. None where the trace holds no such operation (a dense
model, a program without them). Source: device_trace."""

from benchlib import moe_opsbytes
from benchlib import trace_reduce as tr


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    n, secs = tr.module_time(ctx.trace, {"decode_steps"})
    evs = tr.ops_inside(ctx.trace, {"decode_steps"}, moe_opsbytes.expert_ops_re(ctx.mc))
    if not n or not secs or not evs:
        return None
    return 100.0 * sum(e[2] for e in evs) / 1e9 / secs

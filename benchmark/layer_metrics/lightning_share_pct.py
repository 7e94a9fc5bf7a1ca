"""The Lightning state update's share of the decode program's device time:
device time of the operations that take the state leaf as an operand
(benchlib/sala_opsbytes.state_ops_re) inside ``decode_steps`` executions of
the traced slice / device time of those executions. None for a model
without Lightning layers or a trace with no such operation.
Source: device_trace."""

from benchlib import sala_opsbytes
from benchlib import trace_reduce as tr


def read(ctx):
    ops_re = sala_opsbytes.state_ops_re(ctx.mc, ctx.engine["slots"])
    if ctx.trace is None or not ctx.trace.devices or ops_re is None:
        return None
    n, secs = tr.module_time(ctx.trace, {"decode_steps"})
    evs = tr.ops_inside(ctx.trace, {"decode_steps"}, ops_re)
    if not n or not secs or not evs:
        return None
    return 100.0 * sum(e[2] for e in evs) / 1e9 / secs

"""The held experts' share of the decode program's device time
(``moe_ffn_share_pct`` for a chip that holds some of the experts its router
scores): device time of the operations that take a HELD expert stack as an
operand (benchlib/kda_opsbytes.held_expert_ops_re: 40 experts here, the
period axis in front) inside ``decode_steps`` executions of the traced slice
/ device time of those executions. The router, the shared expert and the
elementwise ops between the matmuls are other fusions and not in it. None
for a model that holds all its experts or a trace with no such operation.
Source: device_trace."""

from benchlib import kda_opsbytes
from benchlib import trace_reduce as tr


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices \
            or not ctx.mc.get("n_routed_experts"):
        return None
    n, secs = tr.module_time(ctx.trace, {"decode_steps"})
    evs = tr.ops_inside(ctx.trace, {"decode_steps"},
                        kda_opsbytes.held_expert_ops_re(ctx.mc))
    if not n or not secs or not evs:
        return None
    return 100.0 * sum(e[2] for e in evs) / 1e9 / secs

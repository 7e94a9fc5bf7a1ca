"""The SELECTION's share of the decode program's device time: device time of
the operations that take the selector's cache leaf as an operand or work on
what is derived from it — the new key's add into its run, the gather of the
slots' runs, the pooled-key logits and softmax, the pooling into block
scores, the top-k, the page lists (benchlib/sala_opsbytes.select_ops_re:
found by their types in the HLO line) — inside ``decode_steps`` executions of
the traced slice / device time of those executions. What choosing 64 pages
costs beside reading them. None for a model that does not select or a trace
with no such operation. Source: device_trace."""

from benchlib import sala_opsbytes
from benchlib import trace_reduce as tr


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    ops_re = sala_opsbytes.select_ops_re(
        ctx.mc, ctx.cell.config, ctx.engine["slots"],
        ctx.engine["page_size"])
    if ops_re is None:
        return None
    n, secs = tr.module_time(ctx.trace, {"decode_steps"})
    evs = [e for e in tr.ops_inside(ctx.trace, {"decode_steps"}, ops_re)
           if not e[0].startswith("%decode_attend_pallas_paged_select")]
    if not n or not secs or not evs:
        return None
    return 100.0 * sum(e[2] for e in evs) / 1e9 / secs

"""Attention's share of the mixed program's device time, for a model whose
attention selects: device time of the ragged attention calls
(``%ragged_attend_pallas_paged_select``: every live page of the chunk's
context walked under the rows' masks) and of the selection's operations for
the decode rows and the chunk's rows (benchlib/sala_opsbytes.select_ops_re)
inside ``mixed_step`` executions of the traced slice / device time of those
executions. An admission is 4-8 such steps: this is how much of a first
token's wait the mechanism is. None for a model that does not select or a
trace with no such operation. Source: device_trace."""

from benchlib import sala_opsbytes
from benchlib import trace_reduce as tr


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    flags = ctx.cell.config["server_flags"]
    chunk = int(flags[flags.index("--prefill-chunk") + 1]) \
        if "--prefill-chunk" in flags else 2048
    ops_re = sala_opsbytes.select_ops_re(
        ctx.mc, ctx.cell.config, ctx.engine["slots"],
        ctx.engine["page_size"], rows=(chunk,))
    if ops_re is None:
        return None
    n, secs = tr.module_time(ctx.trace, {"mixed_step"})
    evs = tr.ops_inside(ctx.trace, {"mixed_step"},
                        sala_opsbytes.RAGGED_KERNEL_RE + "|" + ops_re)
    if not n or not secs or not evs:
        return None
    return 100.0 * sum(e[2] for e in evs) / 1e9 / secs

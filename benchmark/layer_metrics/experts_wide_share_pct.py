"""The routed experts' share of the decode program's device time at a WIDE
batch (128 rows x top-4 over 32 experts: 16 rows an expert), by part NAME:
device time of the ``decode_steps`` operations in the parts ``router`` and
``experts`` / device time of those executions — ``decode_experts_share_pct``
under another name, because an accepted metric's list of cells cannot be
widened by the PR that adds a cell (PERF.md section 7). None for a model
without "c" layers or where no operation carries either part.
Source: device_trace."""

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
    mine = op_parts.seconds(evs, "decode_steps", ("router", "experts"))
    if not n or not secs or not mine:
        return None
    return 100.0 * mine / secs

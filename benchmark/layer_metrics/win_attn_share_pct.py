"""The WINDOW layers' attention kernel's share of the decode program's device
time (a list with window layers beside full ones): device time of that
kind's kernel calls (benchlib/trinity_opsbytes.WINDOW_KERNEL_RE) inside
``decode_steps`` executions of the traced slice / device time of those
executions. None for any other model or a trace with no such call.
Source: device_trace."""

from benchlib import trace_reduce as tr
from benchlib import trinity_opsbytes as tob


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices \
            or not tob.has_both_kinds(ctx.mc):
        return None
    n, secs = tr.module_time(ctx.trace, {"decode_steps"})
    evs = tr.ops_inside(ctx.trace, {"decode_steps"}, tob.WINDOW_KERNEL_RE)
    if not n or not secs or not evs:
        return None
    return 100.0 * sum(e[2] for e in evs) / 1e9 / secs

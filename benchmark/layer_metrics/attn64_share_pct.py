"""The paged decode kernel's share of the decode program's device time where
the heads are 64 wide (two a 128-lane pool row): device time of the
``%decode_attend_pallas_paged`` calls (benchlib/lfm2_opsbytes.KERNEL_RE)
inside ``decode_steps`` executions of the traced slice / device time of
those executions. None for a model without "c" layers or a trace with no
such call. Source: device_trace."""

from benchlib import lfm2_opsbytes as lob
from benchlib import trace_reduce as tr


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices or not lob.is_lfm2(ctx.mc):
        return None
    n, secs = tr.module_time(ctx.trace, {"decode_steps"})
    evs = tr.ops_inside(ctx.trace, {"decode_steps"}, lob.KERNEL_RE)
    if not n or not secs or not evs:
        return None
    return 100.0 * sum(e[2] for e in evs) / 1e9 / secs

"""Share of the traced span in which no operation ran on the device:
1 - union of the device's op intervals / span, averaged over the chips.
Source: device_trace."""

from benchlib import trace_reduce as tr


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    t0, t1 = tr.span_ns(ctx.trace)
    if t1 <= t0:
        return None
    return 100.0 * (1.0 - tr.busy_seconds(ctx.trace) / ((t1 - t0) / 1e9))

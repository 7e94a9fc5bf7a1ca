"""Device time of one decode step: device seconds of the ``decode_steps``
program's executions in the traced slice / (executions x fused horizon).
Source: device_trace."""

from benchlib import trace_reduce as tr


def read(ctx):
    if ctx.trace is None:
        return None
    n, secs = tr.module_time(ctx.trace, {"decode_steps"})
    if not n:
        return None
    return secs * 1e3 / (n * ctx.engine["horizon"])

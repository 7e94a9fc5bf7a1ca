"""95th percentile of the ``queue_wait`` phase span of the requests that
finished during the window and its tail: submit -> first admission into a
slot, from the engine's own timestamps (serving/server.py emits the span;
host clock on host events). Source: program_span."""

from benchlib.stats import percentile


def read(ctx):
    waits = [s[4].get("phase.ms") for s in ctx.spans if s[1] == "queue_wait"]
    waits = [float(w) for w in waits if w is not None]
    return percentile(waits, 95)

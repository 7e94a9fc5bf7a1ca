"""Device milliseconds of every program that prefills (``prefill_step``,
``prefill_batch_step``, ``prefill_chunk_step`` and the ragged ``mixed_step``,
whose dispatch also advances the decode batch by one token) per thousand
prompt tokens admitted in the traced slice. Source: device_trace (time) over
program_counter (``prompt_tokens``)."""

from benchlib import trace_reduce as tr

PROGRAMS = {"prefill_step", "prefill_batch_step", "prefill_chunk_step",
            "mixed_step"}


def read(ctx):
    if ctx.trace is None:
        return None
    ktok = ctx.traced_counters.get("prompt_tokens", 0) / 1000.0
    n, secs = tr.module_time(ctx.trace, PROGRAMS)
    if not n or not ktok:
        return None
    return secs * 1e3 / ktok

"""Roofline share of the FULL layers' attention read inside the decode
program (a list with window layers beside full ones).

need = as ``win_attn_roofline_pct``, from the record's ``attn_pages_live``
       (every page a row holds, per full layer) x ``attn_layers_full``;
time = device time of the full layers' kernel calls
       (``%decode_attend_pallas_paged``, the name without a suffix) inside
       the joined ``decode_steps`` executions.
None for any other model (the two Qwen3 cells' reader of the same kernel is
``decode_attn_roofline_pct``, from 50-ms samples). Source: device_trace
(time) over program_span (the record)."""

from benchlib import trinity_opsbytes as tob


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices \
            or not tob.has_both_kinds(ctx.mc):
        return None
    need, secs = tob.need_and_time_of(ctx, "full")
    return 100.0 * need / secs if secs else None

"""Roofline share of the WINDOW layers' attention read inside the decode
program (a list with window layers beside full ones).

need = for every ``jit_decode_steps`` execution of the traced slice that
       joins a dispatch record (benchlib/engine_loop.join_executions): the
       bytes of the pages inside its rows' windows — the record's
       ``win_pages_live`` a window layer x ``attn_layers_window`` layers, K
       and V of a page (131,072 B a page-layer at 4 KV heads of 128, page
       64, bf16) — plus the rows' q in and o out, over the peak HBM
       bandwidth (benchlib/trinity_opsbytes.attn_decode_dispatch);
time = device time of the window layers' kernel calls
       (``%decode_attend_pallas_paged_window``) inside the joined executions.
None for any other model, or a program whose records carry no
``win_pages_live``. Source: device_trace (time) over program_span (the
record)."""

from benchlib import trinity_opsbytes as tob


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices \
            or not tob.has_both_kinds(ctx.mc):
        return None
    need, secs = tob.need_and_time_of(ctx, "window")
    return 100.0 * need / secs if secs else None

"""Roofline share of the selecting layers' attention READ inside the decode
program.

need = for every ``jit_decode_steps`` execution of the traced slice that
       joins a dispatch record (benchlib/engine_loop.join_executions): the
       bytes of the pages its rows SELECTED — ``sparse_pages_selected``
       (row, KV head) pages a selecting layer, K and V in bf16 — plus the
       rows' q in and o out, over the peak HBM bandwidth
       (benchlib/sala_opsbytes.sparse_decode_dispatch; the bound is
       bandwidth, 32 flops a byte);
time = device time of the selecting kernel's calls
       (``%decode_attend_pallas_paged_select``) inside the joined executions.
What the selection costs (scoring, top-k) is NOT in the time: it is
``sparse_select_share_pct``. None for a model that does not select or a
program whose records carry no ``sparse_pages_selected``. Source:
device_trace (time) over program_span (the record)."""

from benchlib import sala_opsbytes


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices \
            or "s" not in ctx.mc.get("layer_pattern", ""):
        return None
    page = ctx.engine["page_size"]
    need, secs = sala_opsbytes.need_and_time(
        ctx, sala_opsbytes.DECODE_KERNEL_RE, "sparse_pages_selected",
        lambda rec: sala_opsbytes.sparse_decode_dispatch(ctx.mc, rec, page))
    return 100.0 * need / secs if secs else None

"""Share of the live pages that the selecting layers' rows READ: sum of
``sparse_pages_selected`` / sum of ``sparse_pages_live`` over the window's
``engine.dispatch`` spans of ``decode_steps`` and ``mixed_step``
(serving/programs.py writes both per dispatch, per selecting layer, over the
(row, KV head) pairs and the record's substeps; the program counts them on
the device). 100 while every context is under the dense length; 64 pages of
a 16k context's 256 read 25. None where no record carries the fields.
Source: program_span."""

from benchlib import engine_loop


def read(ctx):
    recs = [r[2] for r in engine_loop.dispatch_records(ctx.spans).values()
            if "sparse_pages_live" in r[2]]
    live = sum(r["sparse_pages_live"] for r in recs)
    if not live:
        return None
    return 100.0 * sum(r["sparse_pages_selected"] for r in recs) / live

"""What releasing pages behind the window leaves held, in the window
layers' page inventory (a list with window layers beside full ones), OVER
THE MEASURED WINDOW: of the window's dispatch records, the one that left
the inventory fullest — its ``win_pages_held`` (pages in use) / its
``win_pages_unreleased`` (the pages the same slots would have held for
those layers at that moment with nothing released: their contexts, in
pages). 30 % where 48 slots of 4k-9k tokens each hold a window of 2,048 and
a page or two; 100 = nothing is released. Beside it on ``/metrics``, since
the server started: ``tpu_serve_kv_window_pages_in_use_peak`` /
``..._unreleased_at_peak``, ``..._slot_peak`` (the most ONE slot held:
window + the chunk in flight + a page at most) and ``..._released_total``.
None for any other model or a program whose records lack the fields.
Source: program_counter."""

from benchlib import engine_loop
from benchlib import trinity_opsbytes as tob


def read(ctx):
    if not tob.has_both_kinds(ctx.mc):
        return None
    recs = [r for _, _, r in engine_loop.dispatch_records(ctx.spans).values()
            if r.get("win_pages_unreleased")]
    if not recs:
        return None
    fullest = max(recs, key=lambda r: r["win_pages_held"])
    return 100.0 * fullest["win_pages_held"] / fullest["win_pages_unreleased"]

"""Share of the mixed program's compiled chunk rows that held a prompt token:
sum of ``chunk_n`` / sum of ``chunk_rows`` over the window's
``engine.dispatch`` spans of ``mixed_step`` (serving/programs.py writes one
per dispatch; read through the server tracer's exporter). The chunk is
compiled at the largest bucket's width, so an admission under a live batch
pays for ``chunk_rows`` rows whatever the prompt's length: this is that fault
as a number. Source: program_span."""

from benchlib import engine_loop


def read(ctx):
    recs = [r[2] for r in engine_loop.dispatch_records(ctx.spans).values()
            if r[2].get("program") == "mixed_step"]
    rows = sum(r.get("chunk_rows", 0) for r in recs)
    if not rows:
        return None
    return 100.0 * sum(r.get("chunk_n", 0) for r in recs) / rows

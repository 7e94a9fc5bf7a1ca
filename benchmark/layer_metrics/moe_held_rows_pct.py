"""Share of the chosen (token, expert) pairs that land on an expert THIS
chip holds: sum of ``moe_rows_held`` / sum of ``moe_rows`` over the window's
``engine.dispatch`` spans of ``decode_steps`` and ``mixed_step`` (both per
layer, the program's own counts riding the dispatch record). 12.5 % under
even routing at 40 of 320; what the absent chips of the deployment would
compute is the rest. None for a model that holds all its experts.
Source: program_span."""

from benchlib import engine_loop


def read(ctx):
    recs = [r[2] for r in engine_loop.dispatch_records(ctx.spans).values()
            if "moe_rows_held" in r[2]]
    rows = sum(r["moe_rows"] for r in recs)
    if not rows:
        return None
    return 100.0 * sum(r["moe_rows_held"] for r in recs) / rows

"""Share of the experts HELD here that a decode step's routed rows reach
(``moe_experts_hit_pct`` for a chip that holds some of the experts its
router scores): mean ``moe_experts_hit`` (held experts with at least one
live row, mean over layers and substeps; the program's own output, riding
the dispatch record) over the window's ``engine.dispatch`` spans of
``decode_steps`` that carry ``moe_rows_held``, weighted by their substeps,
/ ``num_experts`` (the experts held). The every-expert form streams all the
held stacks, so this is the part of that stream a step needed. None for a
model that holds all its experts. Source: program_span."""

from benchlib import engine_loop


def read(ctx):
    recs = [r[2] for r in engine_loop.dispatch_records(ctx.spans).values()
            if r[2].get("program") == "decode_steps"
            and "moe_rows_held" in r[2]]
    steps = sum(r.get("horizon", 1) for r in recs)
    if not steps:
        return None
    hit = sum(r["moe_experts_hit"] * r.get("horizon", 1) for r in recs)
    return 100.0 * hit / steps / ctx.mc["num_experts"]

"""Share of a layer's experts that a decode step's routed rows reach: mean
``moe_experts_hit`` (experts with at least one live row, mean over layers and
substeps; the program's own output, riding the dispatch record) over the
window's ``engine.dispatch`` spans of ``decode_steps``, weighted by their
substeps, / ``num_experts``. The bytes an MoE step needs follow this number,
not the expert count. None for a program or a model whose records carry no
such field. Source: program_span."""

from benchlib import engine_loop


def read(ctx):
    recs = [r[2] for r in engine_loop.dispatch_records(ctx.spans).values()
            if r[2].get("program") == "decode_steps"
            and "moe_experts_hit" in r[2]]
    steps = sum(r.get("horizon", 1) for r in recs)
    if not steps or not ctx.mc.get("num_experts"):
        return None
    hit = sum(r["moe_experts_hit"] * r.get("horizon", 1) for r in recs)
    return 100.0 * hit / steps / ctx.mc["num_experts"]

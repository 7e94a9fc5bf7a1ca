"""Roofline share of an expert SHARE's held experts inside the decode
program (``moe_ffn_roofline_pct`` for a chip that holds some of the experts
its router scores).

need = for every ``jit_decode_steps`` execution of the traced slice that
       joins a dispatch record: what the HELD experts' FFNs need
       (benchlib/kda_opsbytes.held_decode_dispatch: ``moe_rows_held`` (token,
       expert) rows that landed on a held expert through three matmuls, the
       stacks of the ``moe_experts_hit`` held experts hit once, their
       scales, the rows in and out);
time = device time of the operations that take a held stack as an operand
       (kda_opsbytes.held_expert_ops_re: 40 experts here, the period axis
       in front) inside the joined executions.
The every-expert form streams all the held stacks whatever the routing hit,
so at 1.6 rows an expert the share reads about the fraction of them hit.
None for a model that holds all its experts (no ``moe_rows_held`` in its
records). Source: device_trace (time) over program_span (the record)."""

from benchlib import kda_opsbytes


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices \
            or not ctx.mc.get("n_routed_experts"):
        return None
    need, secs = kda_opsbytes.need_and_time(
        ctx, kda_opsbytes.held_expert_ops_re(ctx.mc), "moe_rows_held",
        lambda rec: kda_opsbytes.held_decode_dispatch(
            ctx.mc, rec, ctx.engine["w_itemsize"]))
    return 100.0 * need / secs if secs else None

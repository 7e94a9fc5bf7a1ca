"""Roofline share of the Lightning layers' state update inside the decode
program (``kda_state_roofline_pct``'s twin for the second recurrent kind).

need = for every ``jit_decode_steps`` execution of the traced slice that
       joins a dispatch record: ``state_slots`` live slots x the Lightning
       layers x ``horizon`` substeps x (the float32 ``[H, d, d]`` state read
       ONCE and written ONCE + the token's q, k, v rows and its output row)
       over the peak HBM bandwidth (benchlib/sala_opsbytes
       .lightning_decode_dispatch; 0.5 flops a byte);
time = device time of the operations that take the state leaf as an operand
       (sala_opsbytes.state_ops_re) inside the joined executions.
None for a model without Lightning layers or a program whose records carry
no ``state_slots``. Source: device_trace (time) over program_span (the
record)."""

from benchlib import sala_opsbytes


def read(ctx):
    ops_re = sala_opsbytes.state_ops_re(ctx.mc, ctx.engine["slots"])
    if ctx.trace is None or not ctx.trace.devices or ops_re is None:
        return None
    need, secs = sala_opsbytes.need_and_time(
        ctx, ops_re, "state_slots",
        lambda rec: sala_opsbytes.lightning_decode_dispatch(ctx.mc, rec))
    return 100.0 * need / secs if secs else None

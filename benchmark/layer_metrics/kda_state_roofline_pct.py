"""Roofline share of the KDA layers' state update inside the decode program.

need = for every ``jit_decode_steps`` execution of the traced slice that
       joins a dispatch record (benchlib/engine_loop.join_executions):
       what that dispatch's KDA layers need (benchlib/kda_opsbytes
       .decode_dispatch: ``kda_slots`` live slots x the KDA layers x
       ``horizon`` substeps x (the float32 state read ONCE and written ONCE
       + the token's q/k/v/g rows and step size)) over the peak HBM
       bandwidth — the bound is bandwidth, 0.75 flops a byte;
time = device time of the operations that take the state leaf as an operand
       (kda_opsbytes.state_ops_re: found by its type in the HLO line)
       inside the joined executions.
The kernel ``kda_decode_update`` makes one pass; XLA's form (the fallback)
reads the state twice — a reduce fusion, an update fusion — and cannot pass
67 %. None for a model without KDA layers or a program whose
records carry no ``kda_slots``. Source: device_trace (time) over
program_span (the record)."""

from benchlib import kda_opsbytes


def read(ctx):
    ops_re = kda_opsbytes.state_ops_re(ctx.mc, ctx.engine["slots"])
    if ctx.trace is None or not ctx.trace.devices or ops_re is None:
        return None
    need, secs = kda_opsbytes.need_and_time(
        ctx, ops_re, "kda_slots",
        lambda rec: kda_opsbytes.decode_dispatch(ctx.mc, rec))
    return 100.0 * need / secs if secs else None

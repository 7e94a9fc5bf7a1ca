"""Roofline share of the state-space mixers' decode update inside the decode
program (``kda_state_roofline_pct``'s twin for a state that is not square).

need = for every ``jit_decode_steps`` execution of the traced slice that
       joins a dispatch record: ``ssm_slots`` live slots x the "h" layers x
       ``horizon`` substeps x (the float32 ``[H, d_state, d_head]`` state
       read ONCE and written ONCE + the row's C, B, decay, x, step size and
       output) over the peak HBM bandwidth (benchlib/falcon_h1_opsbytes
       .ssm_decode_dispatch; 0.5 flops a byte);
time = device time of the ``kda_decode_update`` kernel's calls, found by the
       wrapper's NAME (falcon_h1_opsbytes.KERNEL_RE), inside the joined
       executions.
None for a model without "h" layers or a program whose records carry no
``ssm_slots``. Source: device_trace (time) over program_span (the
record)."""

from benchlib import falcon_h1_opsbytes as fob
from benchlib import kda_opsbytes


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices \
            or not fob.is_falcon_h1(ctx.mc):
        return None
    need, secs = kda_opsbytes.need_and_time(
        ctx, fob.KERNEL_RE, "ssm_slots",
        lambda rec: fob.ssm_decode_dispatch(ctx.mc, rec))
    return 100.0 * need / secs if secs else None

"""Roofline share of the paged decode-attention kernel inside the decode
program. The kernel is bandwidth-bound (4 flops per K/V element read; ridge
of the v5e at 240 flops/byte), so the bound is bytes / peak HBM bandwidth.

time  = device time of the kernel's events that ran inside ``decode_steps``
        executions in the traced slice (one event per layer per step);
bytes = events x bytes one call needs (benchlib/opsbytes.decode_attention_
        call) at the mean batch and mean summed context length of the active
        slots, sampled from the engine's host mirrors every 50 ms during the
        traced slice. A slot's context grows by <= horizon tokens between
        samples (about 1% of a mean context of ~800).
Source: device_trace."""

from benchlib import opsbytes
from benchlib import trace_reduce as tr

# today's trace names a Pallas call after the jitted wrapper that makes it
# (ops/pallas_attention.py::decode_attend_pallas_paged):
# ``%decode_attend_pallas_paged.8 = ... custom-call(...)``
KERNEL_RE = r"^%decode_attend_pallas_paged"


def read(ctx):
    if ctx.trace is None:
        return None
    evs = tr.ops_inside(ctx.trace, {"decode_steps"}, KERNEL_RE)
    sm = [s for s in ctx.samples
          if ctx.trace_t0 <= s[0] <= ctx.trace_t1 and s[3] > 0]
    if not evs or not sm:
        return None
    secs = sum(e[2] for e in evs) / 1e9
    batch = sum(s[3] for s in sm) / len(sm)
    ctx_sum = sum(s[4] for s in sm) / len(sm)
    flops, byts = opsbytes.decode_attention_call(
        ctx.mc, ctx_sum, batch, ctx.engine["kv_itemsize"], ctx.chips)
    least, _ = opsbytes.roofline_seconds(flops, byts, ctx.peaks)
    return 100.0 * least * len(evs) / secs

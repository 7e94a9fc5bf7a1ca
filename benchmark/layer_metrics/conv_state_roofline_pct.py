"""Roofline share of the gated short convolutions inside the decode program.

need = for every ``jit_decode_steps`` execution of the traced slice that
       joins a dispatch record: ``state_slots`` live slots x the conv
       layers x ``horizon`` substeps x the float32 tail read and written
       (32,768 B at hidden 2,048 and 3 taps) + the taps once a layer and
       substep, over the peak HBM bandwidth — exactly what the ``recur``
       fusions of the compiled program move through HBM
       (benchlib/lfm2_opsbytes.conv_decode_dispatch lists the operands; B,
       C, X and the gated row stay in the compiler's fast memory or inside
       the neighbouring matmul fusions; 9 flops an element: bandwidth
       bounds it);
time = device time of the ``recur`` part's operations inside the joined
       executions (benchlib/op_parts).
A few hundred KB a layer and substep: what this share reads is how far
small elementwise fusions between two matmuls sit from the HBM roofline,
not a kernel. None for a model without "c" layers or a program whose
records carry no ``state_slots``. Source: device_trace (time) over
program_span (the record)."""

from benchlib import lfm2_opsbytes as lob


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices or not lob.is_lfm2(ctx.mc):
        return None
    need, secs = lob.conv_need_and_time(ctx)
    return 100.0 * need / secs if secs else None

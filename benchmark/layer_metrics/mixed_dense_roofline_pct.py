"""Roofline share of the dense matmuls inside ``mixed_step``.

need = for every ``jit_mixed_step`` execution of the traced slice that joins
       a dispatch record: max(flops / peak, bytes / peak HBM bandwidth) of
       the parts ``attn.proj``, ``attn.out``, ``mlp`` over the record's
       ``padded_tokens`` rows (every packed row runs the layers, padding
       included: this says what a ROW costs, ``mixed_chunk_fill_pct`` how
       many carry a token) and of ``head`` over its ``head_rows`` — 2 flops
       a row and matmul element (``tpu_serve_param_elements{part}``), the
       parts' bytes once (``tpu_serve_param_bytes{part}``). At 2,080 rows
       the bound is compute: 4,160 flops a weight byte.
time = device time of the operations of those parts inside the joined
       executions (benchlib/op_parts).
A part whose operations an execution does not show drops out of BOTH sides.
None where no operation carries a part, the program has no such gauge or
its records carry no ``padded_tokens``. Source: device_trace (time) over
program_counter (the gauge) and program_span (the record)."""

from benchlib import op_parts


def read(ctx):
    def need_of(rec, weights):
        flops = sum(2.0 * rec.get(
            "head_rows" if part == "head" else "padded_tokens", 0) * n
            for part, (_, n) in weights.items())
        return flops, sum(b for b, _ in weights.values())

    need, secs = op_parts.dense_need_and_time(ctx, "mixed_step", need_of)
    return 100.0 * need / secs if need and secs else None

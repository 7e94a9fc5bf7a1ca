"""Roofline share of the dense matmuls' weight stream inside the decode
program.

need = for every ``jit_decode_steps`` execution of the traced slice that
       joins a dispatch record (benchlib/engine_loop.join_executions):
       ``horizon`` substeps x the bytes one forward pass streams for the
       parts ``attn.proj``, ``attn.out``, ``mlp``, ``head`` — what the
       program's own gauge ``tpu_serve_param_bytes{part}`` says the served
       tree holds of them (kernels with their scales, per chip), plus the
       ``active`` rows on the far side of each kernel from the residual
       stream (elements / hidden_size wide: a projection's outputs, the
       FFN's inner rows, the logits), bf16 — over the peak HBM bandwidth.
       A decode batch is bandwidth-bound: 32 rows x 2 flops a weight byte
       against a ridge of 240.
time = device time of the operations of those parts inside the joined
       executions (benchlib/op_parts).
A part whose operations an execution does not show drops out of BOTH sides.
None where no operation carries a part or the program has no such gauge.
Source: device_trace (time) over program_counter (the gauge) and
program_span (the record)."""

from benchlib import op_parts


def read(ctx):
    hidden = ctx.mc["hidden_size"]

    def need_of(rec, weights):
        steps = max(1, int(rec.get("horizon", 1)))
        rows = rec.get("active", 0)
        byts = sum(b + rows * 2.0 * n / hidden for b, n in weights.values())
        return 0.0, steps * byts

    need, secs = op_parts.dense_need_and_time(ctx, "decode_steps", need_of)
    return 100.0 * need / secs if secs else None

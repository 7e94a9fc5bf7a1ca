"""Share of the decode program's device time that carries the name of a part
of the model: device time of the ``decode_steps`` operations of the traced
slice whose HLO instruction names a part of the program's closed set
(benchlib/op_parts: ``attn.proj``, ``mlp``, ``head``, ``experts``, ...) /
device time of all its operations (containers left out, as trace_reduce
leaves them). The names' own guard: a refactor that drops a
``jax.named_scope`` shows here. What stays unnamed by construction: the layer
scan's own slices of the stacked weights and the copies the compiler makes
without metadata. None where nothing carries a part (a program without
scopes, a trace without its HLO modules). Source: device_trace."""

from benchlib import op_parts


def read(ctx):
    evs = op_parts.of_context(ctx)
    if not evs:
        return None
    total = op_parts.seconds(evs, "decode_steps")
    if not total:
        return None
    unnamed = op_parts.seconds(evs, "decode_steps", {op_parts.NONE})
    return 100.0 * (total - unnamed) / total

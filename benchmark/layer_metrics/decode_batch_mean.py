"""Mean sequences per decode step over the window: decode tokens / decode
steps, summed over the engine's own per-dispatch counts of the plain decode
program (a fused dispatch counts horizon x active tokens and horizon steps).
Mixed dispatches are left out: their token count includes the chunk's rows.
Source: program_counter."""


def read(ctx):
    toks = sum(d[2] for d in ctx.dispatches if d[0] == "decode")
    steps = sum(d[3] for d in ctx.dispatches if d[0] == "decode")
    return toks / steps if steps else None

"""Peak of KV pages in use / pages in the pool over the window, from the
pool's own gauges sampled by the benchmark every 50 ms.
Source: program_counter."""


def read(ctx):
    vals = [s[1] / s[2] for s in ctx.samples if s[2]]
    return 100.0 * max(vals) if vals else None

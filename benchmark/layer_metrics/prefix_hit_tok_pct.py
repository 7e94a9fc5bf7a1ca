"""Prompt tokens served from cached prefix pages / prompt tokens admitted,
over the window (engine counters ``prefix_tokens_reused`` and
``prompt_tokens``). Source: program_counter."""


def read(ctx):
    p = ctx.counters.get("prompt_tokens", 0)
    if not p:
        return None
    return 100.0 * ctx.counters.get("prefix_tokens_reused", 0) / p

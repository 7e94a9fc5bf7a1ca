"""``memory_stats()["peak_bytes_in_use"]`` of the fullest chip, in GB (1e9).
Source: program_counter (the runtime's allocator)."""


def read(ctx):
    return ctx.memory_peak_bytes / 1e9 if ctx.memory_peak_bytes else None

"""Host milliseconds the engine thread spends per device dispatch: time
inside the ``engine.*`` annotations of the traced slice other than
``engine.fetch`` (blocked on a transfer) and ``engine.idle`` (nothing to do)
/ ``engine.dispatch`` events. What is left is reaping, admission, operand
uploads, the jitted call's own enqueue and the emit loop: the host's share
of a step, which bounds the rate once the device is cheap. Nested
annotations count once, as the innermost. Source: program_span (profiler
annotations the program writes; serving/programs.py ENGINE_PHASES)."""

from benchlib import engine_loop


def read(ctx):
    secs = engine_loop.host_seconds_per_dispatch(engine_loop.phases_of(ctx))
    return None if secs is None else secs * 1e3

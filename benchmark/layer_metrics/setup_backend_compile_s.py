"""Seconds of this process spent in XLA's backend compile of the step
programs or loading them from the persistent cache: stages ``backend`` +
``cache_load`` of ``tpu_serve_compile_stage_seconds_total`` summed over the
step programs, read when the run's line is made. The part of ``setup_s`` a
warm cache shrinks. Source: program_counter."""

from benchlib import engine_loop


def read(ctx):
    return engine_loop.compile_stage_seconds({"backend", "cache_load"})

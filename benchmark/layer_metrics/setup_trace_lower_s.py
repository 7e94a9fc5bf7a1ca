"""Seconds of this process spent tracing the step programs to jaxprs and
lowering them to MLIR (Pallas lowering included): stages ``trace`` +
``lower`` of ``tpu_serve_compile_stage_seconds_total`` summed over the step
programs (not ``other``), read when the run's line is made. The part of
``setup_s`` no compile cache removes. Source: program_counter."""

from benchlib import engine_loop


def read(ctx):
    return engine_loop.compile_stage_seconds({"trace", "lower"})

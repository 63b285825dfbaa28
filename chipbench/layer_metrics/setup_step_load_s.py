"""Compiling the step program or loading it from the cache: the
``jit.compile_s`` and ``jit.cache_load_s`` filed under
``span=train/step_chunk`` (each event's own time, so the two add).
Seconds; program span."""

from chipbench import setup_phases


def read(ctx):
    return setup_phases.read(ctx, "step_load")

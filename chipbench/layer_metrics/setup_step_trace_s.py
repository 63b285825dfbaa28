"""Host time tracing and lowering the step program: the ``jit.trace_s`` and
``jit.lower_s`` the program filed under ``span=train/step_chunk``.
Seconds; program span."""

from chipbench import setup_phases


def read(ctx):
    return setup_phases.read(ctx, "step_trace")

"""What no span names: the end of the first ``train/step_chunk`` less the
process's start, less the five named phases, ``train/build_step`` and
the first chunk's run (the mean of the later chunks). Seconds; program
span."""

from chipbench import setup_phases


def read(ctx):
    return setup_phases.read(ctx, "unaccounted")

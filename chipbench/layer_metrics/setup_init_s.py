"""The program's ``train/init`` span: the init jit traced, loaded or
compiled, and run. Seconds; program span."""

from chipbench import setup_phases


def read(ctx):
    return setup_phases.read(ctx, "init")

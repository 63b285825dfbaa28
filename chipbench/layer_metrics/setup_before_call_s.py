"""The call's entry stamp (the start of the program's ``train/enter`` span)
less the process's first clock reading: imports, the PJRT client, arming
the cache, the benchmark's rows. Seconds; program span."""

from chipbench import setup_phases


def read(ctx):
    return setup_phases.read(ctx, "before_call")

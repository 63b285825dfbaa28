"""The program's ``train/data_prep`` and ``train/shuffle`` spans: the host
batch, its placement on the chips and the resident shuffle. Seconds;
program span."""

from chipbench import setup_phases


def read(ctx):
    return setup_phases.read(ctx, "data_place")

"""SPK501 true positive — the PR 10 shipped segfault, minimally: the
elastic run read `coord.generation` after the finally-stop had freed
the native gang state (use-after-free through ctypes)."""

from sparktorch_tpu.native.gang import GangCoordinator


def run_gang(n):
    coord = GangCoordinator(world_size=n)
    try:
        coord.barrier()
    finally:
        coord.stop()
    return coord.generation

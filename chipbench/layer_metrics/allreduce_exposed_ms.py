"""Milliseconds per step of collective-operation time on a chip during
which no compute operation runs on it (the gradient psum that the
backward pass does not hide). Device trace; a one-chip trace holds no
collective and reads nothing."""

from chipbench import harness, trace


def read(ctx):
    if ctx["trace"] is None or "steps_per_call" not in ctx["inputs"]:
        return None
    if ctx["inputs"]["n_chips"] < 2:
        return None
    steps = harness.load_module(
        "layer_metrics", "step_device_ms",
        ctx["cell"].root).steps_in_window(ctx)
    exposed = trace.collective_exposed_seconds(ctx["trace"],
                                               ctx["summary"]["window"])
    return exposed * 1e3 / steps if steps else None

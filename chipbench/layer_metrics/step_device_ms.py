"""Device-busy milliseconds per training step: the busy union inside
the traced steady chunks over their steps. Device trace."""

from chipbench import trace


def steps_in_window(ctx):
    runs = trace.module_runs(ctx["trace"], ctx["summary"]["window"])
    return len(runs) * ctx["inputs"]["steps_per_call"]


def read(ctx):
    if ctx["trace"] is None or "steps_per_call" not in ctx["inputs"]:
        return None
    steps = steps_in_window(ctx)
    return ctx["summary"]["busy_s"] * 1e3 / steps if steps else None

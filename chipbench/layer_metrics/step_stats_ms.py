"""Device milliseconds per training step in operations whose ``op_name``
carries the scope ``step_stats``: the gradient's global and per-leaf
norms and the health vector's reductions after the update. A fusion
counts whole under the one scope its event carries. Device trace."""

from chipbench import trace_scopes


def read(ctx):
    return trace_scopes.step_ms(ctx, "step_stats")

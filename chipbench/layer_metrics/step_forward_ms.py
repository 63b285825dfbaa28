"""Device milliseconds per training step in operations whose ``op_name``
carries the scope of the forward pass and the loss (``forward_loss``,
``jvp(forward_loss)``): own time on ``XLA Ops`` inside the traced steady
chunks, averaged over the chips. A fusion counts whole under the one
scope its event carries. Device trace."""

from chipbench import trace_scopes


def read(ctx):
    return trace_scopes.step_ms(ctx, "forward")

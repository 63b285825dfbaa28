"""Device milliseconds per training step in everything the multi-token
prediction module adds: operations whose ``op_name`` carries the scope
``mtp`` anywhere (the module's projection, norms and layer with its
attention and experts, its pass through the shared head, its cross
entropy inside the step's ``loss``, its own loss for the counters), with
their gradients. The same operations also count under their layer's own
scopes in the other per-layer metrics. Device trace."""

from chipbench import mla_scopes


def read(ctx):
    return mla_scopes.scope_ms(ctx, "mtp")

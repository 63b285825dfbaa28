"""``step_unnamed_pct`` for a step with the expert exchange: the share
of ``step_device_ms`` in operations of the forward and backward phases
whose ``op_name`` carries no scope of the model, the exchange's own
(``moe_exchange``) counted as named, in percent: what no per-layer metric
of the cell can see. Device trace."""

from chipbench import ep_scopes


def read(ctx):
    return ep_scopes.unnamed_pct(ctx)

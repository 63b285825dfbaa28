"""``step_unnamed_pct`` for a step of Gated DeltaNet layers: the share of
``step_device_ms`` in operations of the forward and backward phases
whose ``op_name`` carries no scope of the model, the linear layers'
own (``gated_delta``, ``gdn_conv``, ``gdn_gates``, ``gdn_out_norm``)
counted as named, in percent: what no per-layer metric of the cell can
see. Device trace."""

from chipbench import gdn_scopes


def read(ctx):
    return gdn_scopes.unnamed_pct(ctx)

"""Share of ``step_device_ms`` in operations of the forward and backward
phases whose ``op_name`` carries no scope of the model (none that any of
the four reader files knows; ``ragged-dot`` calls count as named), in
percent: what no per-layer metric can see. Device trace."""

from chipbench import step_parts


def read(ctx):
    return step_parts.unnamed_pct(ctx)

"""``step_unnamed_pct`` for a step of gated short-convolution layers: the
share of ``step_device_ms`` in operations of the forward and backward
phases whose ``op_name`` carries no scope of the model, the convolution
layers' own (``sconv_gate``) counted as named, in percent: what no
per-layer metric of the cell can see. Device trace."""

from chipbench import sconv_scopes


def read(ctx):
    return sconv_scopes.unnamed_pct(ctx)

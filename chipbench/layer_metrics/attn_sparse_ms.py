"""Device milliseconds per training step under the scope
``sparse_attention``: the selected-key attention kernels (forward, the
recomputed forward of the rematerialised layer, dq, dkv) and the layout
changes around them. Device trace."""

from chipbench import lm_scopes


def read(ctx):
    return lm_scopes.scope_ms(ctx, "sparse_attention")

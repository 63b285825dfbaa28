"""Device milliseconds per training step under the scope
``block_diffusion_attention``: the structural-mask attention kernels
(forward, dq, dkv, one of each a layer) over the clean row and its
noised copy, and the layout changes around them. Device trace."""

from chipbench import dlm_scopes


def read(ctx):
    return dlm_scopes.scope_ms(ctx, "block_diffusion_attention")

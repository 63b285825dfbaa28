"""Device milliseconds per training step under the scope
``latent_attention``: the latent-attention kernels (forward, dq, dkv, one
of each a layer and one of each in the multi-token prediction module's
layer) and the output's layout changes around them. Device trace."""

from chipbench import mla_scopes


def read(ctx):
    return mla_scopes.scope_ms(ctx, "latent_attention")

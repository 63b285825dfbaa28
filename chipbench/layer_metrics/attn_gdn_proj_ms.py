"""Device milliseconds per training step under the scopes
``gdn_in_proj`` (a linear-attention layer's two input products, ``[2048,
12288]`` and ``[2048, 64]``) and ``gdn_out_proj`` (its ``W_o``): forward,
recomputation and both gradients. ``attn_projections_ms`` counts them
too, with the full layers'. Device trace."""

from chipbench import gdn_scopes


def read(ctx):
    return gdn_scopes.scope_ms(ctx, "gdn_in_proj", "gdn_out_proj")

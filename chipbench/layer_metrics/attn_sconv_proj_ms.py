"""Device milliseconds per training step under the scopes
``sconv_in_proj`` (a convolution layer's input product, ``[2048, 3 x
2048]``) and ``sconv_out_proj`` (its ``W_out``): forward, recomputation
and both gradients. ``attn_projections_ms`` counts them too, with the
attention layers'. Device trace."""

from chipbench import sconv_scopes


def read(ctx):
    return sconv_scopes.scope_ms(ctx, "sconv_in_proj", "sconv_out_proj")

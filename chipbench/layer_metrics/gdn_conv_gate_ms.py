"""Device milliseconds per training step under the scopes ``gdn_conv``
(the causal convolution of 4 taps over 8,192 channels, SiLU, the split),
``gdn_gates`` (``beta``, the log-decay, the L2 norms of q and k) and
``gdn_out_norm`` (the gated RMSNorm a head): the element-wise passes of
a linear-attention layer over ``[T, 8192]`` and ``[T, 4096]``; forward,
recomputation and gradients. Device trace."""

from chipbench import gdn_scopes


def read(ctx):
    return gdn_scopes.scope_ms(ctx, "gdn_conv", "gdn_gates", "gdn_out_norm")

"""Device milliseconds per training step under the scope ``sconv_gate``:
a gated short convolution's two gates and three taps, the kernels of
``ops/short_conv_gate.py`` (``sconv_fwd`` twice a convolution layer, for
the forward pass and its recomputation, ``sconv_bwd`` once). Device
trace."""

from chipbench import sconv_scopes


def read(ctx):
    return sconv_scopes.scope_ms(ctx, "sconv_gate")

"""Share of their roofline that the fused convolution pass's kernels
(``sconv_fwd``, ``sconv_bwd``) reach: for every call the larger of its
operations over the chip's bf16 peak and its bytes over the memory's
bandwidth, summed, over the device time of the kernels' events. The
pass is bound by memory: a call's bytes are 14 a channel a token forward
(the float32 product's three blocks read, the bfloat16 result written)
and 26 backward (``flops/``: ``short_conv_kernel_cost``); the halo's
rows count nothing. The calls are held to the program's counter
``sconv_tokens``. Device trace."""

from chipbench import sconv_scopes


def read(ctx):
    return sconv_scopes.kernel_roofline_pct(ctx)

"""Share of their roofline that the gated delta rule's kernels
(``gdn_fwd``, ``gdn_bwd``) reach: for every call the larger of its
operations over the chip's bf16 peak and its bytes over the memory's
bandwidth, summed, over the device time of the kernels' events.
Operations are the chunked (WY) form's matrix products at chunk 64
whatever chunk the kernel uses, backward twice forward (``flops/``:
``gated_delta_kernel_cost``); the products by which a kernel finds the
triangular inverse, and the states it recomputes, count nothing. Device
trace."""

from chipbench import gdn_scopes


def read(ctx):
    return gdn_scopes.kernel_roofline_pct(ctx, "gated_delta")

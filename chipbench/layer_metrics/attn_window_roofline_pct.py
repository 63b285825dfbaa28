"""Share of their roofline that the window layers' attention kernels
(``window_attn_fwd``, ``window_attn_bwd_dq``, ``window_attn_bwd_dkv``)
reach: for every call the larger of its operations over the chip's bf16
peak and its bytes over the memory's bandwidth, summed, over the device
time of the kernels' events. Operations are those of the KEPT query-key
pairs (``flops/``: ``window_attention_kernel_cost``: 512 keys a query);
the kernels also compute the masked pairs of the tiles they visit (half
of them at tiles of 256 x 512) and read lower for it. Device trace."""

from chipbench import hlm_scopes


def read(ctx):
    return hlm_scopes.kernel_roofline_pct(ctx, "window")

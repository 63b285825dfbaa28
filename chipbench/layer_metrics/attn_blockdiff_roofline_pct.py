"""Share of their roofline that the block-diffusion attention kernels
(``blockdiff_attn_fwd``, ``blockdiff_attn_bwd_dq``,
``blockdiff_attn_bwd_dkv``) reach: for every call the larger of its
operations over the chip's bf16 peak and its bytes over the memory's
bandwidth, summed, over the device time of the kernels' events.
Operations are those of the ALLOWED query-key pairs (``flops/``:
``blockdiff_attention_kernel_cost``); the kernels also compute the
masked pairs of the tiles they visit and read lower for it. Device
trace."""

from chipbench import dlm_scopes


def read(ctx):
    return dlm_scopes.kernel_roofline_pct(ctx)

"""Share of their roofline that the full-causal layers' attention
kernels (``causal_attn_fwd``, ``causal_attn_bwd_dq``,
``causal_attn_bwd_dkv``) reach: for every call the larger of its
operations over the chip's bf16 peak and its bytes over the memory's
bandwidth, summed, over the device time of the kernels' events.
Operations are those of the KEPT query-key pairs (``flops/``:
``causal_attention_kernel_cost``: every causal pair); the kernels also
compute the masked pairs of the diagonal tiles. Device trace."""

from chipbench import hlm_scopes


def read(ctx):
    return hlm_scopes.kernel_roofline_pct(ctx, "causal")

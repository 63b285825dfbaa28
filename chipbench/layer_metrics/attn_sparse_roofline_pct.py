"""Share of their roofline that the sparse-attention kernels
(``sparse_attn_fwd``, ``sparse_attn_bwd_dq``, ``sparse_attn_bwd_dkv``)
reach: for every call the larger of its operations over the chip's bf16
peak and its bytes over the memory's bandwidth, summed, over the device
time of the kernels' events. Operations are those of the SELECTED
query-key pairs (``flops/``: ``sparse_attention_kernel_cost``); a
kernel that also computes pairs its mask throws away reads lower for
it. Device trace."""

from chipbench import lm_scopes


def read(ctx):
    return lm_scopes.kernel_roofline_pct(ctx, "sparse_attention_kernel_cost")

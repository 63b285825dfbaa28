"""Share of their roofline that the latent-attention kernels
(``latent_attn_fwd``, ``latent_attn_bwd_dq``, ``latent_attn_bwd_dkv``)
reach: for every call the larger of its operations over the chip's bf16
peak and its bytes over the memory's bandwidth, summed, over the device
time of the kernels' events. Operations are those of the KEPT causal
pairs at the heads' true widths, 192 for a score and 128 for a value
(``flops/``: ``latent_attention_kernel_cost``); the kernels also compute
the masked pairs of the diagonal tiles and contract 256 lanes for the
192. Device trace."""

from chipbench import mla_scopes


def read(ctx):
    return mla_scopes.kernel_roofline_pct(ctx, "latent_attention")

"""Share of their roofline that the two kernels of ``ops/latent_rope.py``
(``latent_rope_fwd``, ``latent_rope_bwd``: the rotary step, the cast and
the turn heads first in front of the latent-attention kernels) reach: the
bytes a call has to move once, at the heads' true widths, over the
memory's bandwidth (they multiply no matrices), over the device time of
the kernels' events (``flops/``: ``latent_rope_kernel_cost``). Device
trace."""

from chipbench import mla_scopes


def read(ctx):
    return mla_scopes.kernel_roofline_pct(ctx, "latent_rope")

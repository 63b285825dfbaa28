"""Device milliseconds per training step under the scope
``causal_attention``: the full-causal layers' attention kernels (forward,
dq, dkv, one of each a full layer) and the layout changes around them.
Device trace."""

from chipbench import hlm_scopes


def read(ctx):
    return hlm_scopes.scope_ms(ctx, "causal_attention")

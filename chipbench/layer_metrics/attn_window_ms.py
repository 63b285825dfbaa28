"""Device milliseconds per training step under the scope
``window_attention``: the window layers' attention kernels (forward, dq,
dkv, one of each a window layer) and the layout changes around them.
Device trace."""

from chipbench import hlm_scopes


def read(ctx):
    return hlm_scopes.scope_ms(ctx, "window_attention")

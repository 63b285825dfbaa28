"""Device milliseconds per training step in the MLPs every token goes
through: scopes ``shared_expert`` (the expert beside the routed ones, in
each expert layer) and ``dense_mlp`` (the leading dense layer's). Device
trace."""

from chipbench import hlm_scopes


def read(ctx):
    return hlm_scopes.scope_ms(ctx, "shared_expert", "dense_mlp")

"""Device milliseconds per training step in the expert layer: scopes
``moe_route`` (router, top-k, the sort by expert, the gather of rows
and the gated sum back) and ``moe_experts`` (the grouped matrix
products over the experts held). Device trace."""

from chipbench import lm_scopes


def read(ctx):
    return lm_scopes.scope_ms(ctx, "moe_route", "moe_experts")

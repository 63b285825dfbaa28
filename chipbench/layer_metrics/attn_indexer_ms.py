"""Device milliseconds per training step in the lightning indexer and
the selection: operations whose ``op_name`` carries the scope
``indexer`` (projections, rotary step, index scores head by head) or
``select_topk`` (the exact top-k and the mask). Device trace."""

from chipbench import lm_scopes


def read(ctx):
    return lm_scopes.scope_ms(ctx, "indexer", "select_topk")

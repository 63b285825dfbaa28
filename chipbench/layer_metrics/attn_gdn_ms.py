"""Device milliseconds per training step under the scope
``gated_delta``: the chunked gated delta rule's kernels (``gdn_fwd`` and
``gdn_bwd``, one of each a linear-attention layer) with the log-decays'
sums inside a chunk and the scalars' turn around them. Device trace."""

from chipbench import gdn_scopes


def read(ctx):
    return gdn_scopes.scope_ms(ctx, "gated_delta")

"""Device milliseconds per training step under the scopes ``embed`` (the
embedding gather; its gradient is the scatter-add into the table) and
``block_norm`` (each layer's two RMSNorms and the final one), with their
gradients. Device trace."""

from chipbench import step_parts


def read(ctx):
    return step_parts.metric_ms(ctx, "embed_norms_ms")

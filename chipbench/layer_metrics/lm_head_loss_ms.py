"""Device milliseconds per training step in the head and the criterion:
scopes ``lm_head`` (the vocabulary projection and its padding) and
``loss`` (the step's call of the loss function, the fused cross entropy
here, and the weighted sums), with their gradients. Device trace."""

from chipbench import step_parts


def read(ctx):
    return step_parts.metric_ms(ctx, "lm_head_loss_ms")

"""Device milliseconds per training step in the attention's own matrix
products outside its kernels: scopes ``attn_qkv`` (the q, k and v
projections), ``attn_out`` (the output projection) and, where the model
gates its attention's output, ``attn_gate`` (the gate's projection, the
sigmoid and the product); forward, recomputation and both gradients.
Device trace."""

from chipbench import step_parts


def read(ctx):
    return step_parts.metric_ms(ctx, "attn_projections_ms")

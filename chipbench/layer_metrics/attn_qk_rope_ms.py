"""Device milliseconds per training step under the scope
``attn_qk_rope``: the per-head RMSNorm of q and k, the rotary tables
(angles, ``cos``/``sin``, the attention factor) and both rotations,
elementwise float32 passes over ``[rows, tokens, heads, head width]``,
with their gradients. Device trace."""

from chipbench import step_parts


def read(ctx):
    return step_parts.metric_ms(ctx, "attn_qk_rope_ms")

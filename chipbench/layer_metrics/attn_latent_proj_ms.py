"""Device milliseconds per training step under the scopes ``latent_q``,
``latent_kv`` (each latent's down-projection, norm and up-projection,
with the weights' lay-out) and ``latent_rope`` (the rotary step on 64 of
192 dims, the cast and the turn into the kernels' layout, one kernel each
way); forward, recomputation and both gradients. The output projection
is not here (``attn_out``, in ``attn_projections_ms``). Device trace."""

from chipbench import mla_scopes


def read(ctx):
    return mla_scopes.scope_ms(ctx, "latent_q", "latent_kv", "latent_rope")

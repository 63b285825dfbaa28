"""Fused softmax cross-entropy in Pallas — forward AND backward.

For LM training the naive path materializes (tokens, vocab) softmax
probabilities in HBM. The forward kernel streams vocab blocks through
VMEM, carrying a running (max, sum-exp, picked-logit) per token — the
loss comes out without the probability matrix ever existing. The
backward saves only the per-token logsumexp and recomputes
``(softmax - onehot) * g`` per vocab block in VMEM, writing straight
into the (tokens, vocab) logit gradient (which must exist anyway) —
so neither direction ever holds a separate probability matrix in HBM.

Forward grid = (token_blocks, vocab_blocks); innermost axis iterates
sequentially so VMEM scratch accumulates across vocab blocks. The
backward grid has no cross-block carry (lse is known), so blocks are
fully parallel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

_LANES = 128


def can_tile(t: int, v: int, block_t: int = 256, block_v: int = 512) -> bool:
    """Whether (tokens, vocab) logits land on the kernel's blocks:
    tokens a multiple of ``min(block_t, t)`` and vocab a multiple of
    ``min(block_v, v)``. The ``cross_entropy`` auto loss asks this
    before it picks the kernel."""
    return t % min(block_t, t) == 0 and v % min(block_v, v) == 0


def _use_kernel(t: int, v: int, block_t: int, block_v: int) -> bool:
    """One predicate for BOTH directions — forward and backward must
    always pick the same path. Off the TPU an untileable shape takes
    the dense path; on a TPU backend the caller asked for the kernel
    by name, so it is an error that names the shape and the rule."""
    if can_tile(t, v, block_t, block_v):
        return True
    if jax.default_backend() == "tpu":
        raise ValueError(
            f"fused_cross_entropy: logits ({t}, {v}) cannot be tiled: "
            f"tokens must be a multiple of {block_t} and vocab of "
            f"{block_v}. Pad the vocabulary or use the dense "
            "'cross_entropy_dense' loss."
        )
    return False


def _ce_kernel(logits_ref, labels_ref, loss_ref, m_ref, l_ref, p_ref,
               *, block_v: int, n_v: int):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[:] = jnp.zeros_like(l_ref)
        p_ref[:] = jnp.zeros_like(p_ref)

    s = logits_ref[:].astype(jnp.float32)  # (block_t, block_v)
    labels = labels_ref[:, :1]  # (block_t, 1) int32
    col = vi * block_v + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)

    m_prev = m_ref[:, :1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_ref[:, :1] + jnp.sum(jnp.exp(s - m_new), axis=-1,
                                           keepdims=True)
    hit = col == labels
    picked = jnp.sum(jnp.where(hit, s, 0.0), axis=-1, keepdims=True)
    p_ref[:] = p_ref[:] + jnp.broadcast_to(picked, p_ref.shape)
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(vi == n_v - 1)
    def _finalize():
        lse = m_ref[:, :1] + jnp.log(jnp.maximum(l_ref[:, :1], 1e-30))
        loss_ref[:] = jnp.broadcast_to(lse - p_ref[:, :1], loss_ref.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def fused_cross_entropy(
    logits: jax.Array,
    labels: jax.Array,
    block_t: int = 256,
    block_v: int = 512,
) -> jax.Array:
    """Per-token CE loss. logits (tokens, vocab), labels (tokens,)
    int. Returns (tokens,) float32."""
    return _ce_impl(logits, labels, block_t, block_v)


def _ce_impl(logits, labels, block_t, block_v):
    t, v = logits.shape
    block_t = min(block_t, t)
    block_v = min(block_v, v)
    if not _use_kernel(t, v, block_t, block_v):
        logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
        picked = jnp.take_along_axis(
            logits.astype(jnp.float32), labels[:, None].astype(jnp.int32), axis=-1
        )[:, 0]
        return logz - picked

    interpret = jax.default_backend() != "tpu"
    labels2 = jnp.broadcast_to(labels.astype(jnp.int32)[:, None], (t, _LANES))
    n_v = v // block_v
    kernel = functools.partial(_ce_kernel, block_v=block_v, n_v=n_v)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((t, _LANES), jnp.float32),
        grid=(t // block_t, n_v),
        in_specs=[
            pl.BlockSpec((block_t, block_v), lambda ti, vi: (ti, vi)),
            pl.BlockSpec((block_t, _LANES), lambda ti, vi: (ti, 0)),
        ],
        out_specs=pl.BlockSpec((block_t, _LANES), lambda ti, vi: (ti, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_t, _LANES), jnp.float32),
            pltpu.VMEM((block_t, _LANES), jnp.float32),
            pltpu.VMEM((block_t, _LANES), jnp.float32),
        ],
        interpret=interpret,
        name="fused_ce_fwd",
    )(logits, labels2)
    return out[:, 0]


def _ce_bwd_kernel(logits_ref, labels_ref, lse_ref, g_ref, out_ref,
                   *, block_v: int):
    vi = pl.program_id(1)
    s = logits_ref[:].astype(jnp.float32)  # (block_t, block_v)
    lse = lse_ref[:, :1]
    gg = g_ref[:, :1]
    labels = labels_ref[:, :1]
    col = vi * block_v + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    probs = jnp.exp(s - lse)  # softmax block, lives only in VMEM
    grad = (probs - (col == labels).astype(jnp.float32)) * gg
    out_ref[:] = grad.astype(out_ref.dtype)


def _ce_fwd(logits, labels, block_t, block_v):
    loss = _ce_impl(logits, labels, block_t, block_v)
    return loss, (logits, labels, loss)


def _ce_bwd(block_t, block_v, res, g):
    logits, labels, loss = res
    t, v = logits.shape
    bt = min(block_t, t)
    bv = min(block_v, v)
    labels_i = labels.astype(jnp.int32)
    # lse = loss + picked logit (by definition loss = lse - picked);
    # recovering it costs one (t,)-gather instead of a saved residual.
    picked = jnp.take_along_axis(
        logits.astype(jnp.float32), labels_i[:, None], axis=-1
    )[:, 0]
    lse = loss + picked

    if not _use_kernel(t, v, bt, bv):
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        onehot = jax.nn.one_hot(labels_i, v, dtype=jnp.float32)
        return ((probs - onehot) * g[:, None]).astype(logits.dtype), None

    interpret = jax.default_backend() != "tpu"
    labels2 = jnp.broadcast_to(labels_i[:, None], (t, _LANES))
    lse2 = jnp.broadcast_to(lse[:, None], (t, _LANES))
    g2 = jnp.broadcast_to(g.astype(jnp.float32)[:, None], (t, _LANES))
    kernel = functools.partial(_ce_bwd_kernel, block_v=bv)
    grad = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((t, v), logits.dtype),
        grid=(t // bt, v // bv),
        in_specs=[
            pl.BlockSpec((bt, bv), lambda ti, vi: (ti, vi)),
            pl.BlockSpec((bt, _LANES), lambda ti, vi: (ti, 0)),
            pl.BlockSpec((bt, _LANES), lambda ti, vi: (ti, 0)),
            pl.BlockSpec((bt, _LANES), lambda ti, vi: (ti, 0)),
        ],
        out_specs=pl.BlockSpec((bt, bv), lambda ti, vi: (ti, vi)),
        interpret=interpret,
        name="fused_ce_bwd",
    )(logits, labels2, lse2, g2)
    return grad, None


fused_cross_entropy.defvjp(_ce_fwd, _ce_bwd)


def fused_cross_entropy_loss(preds: jax.Array, targets: jax.Array) -> jax.Array:
    """Registry-compatible loss: handles (batch, vocab) or
    (batch, seq, vocab) logits, returns per-example loss (batch,)."""
    labels = targets.astype(jnp.int32)
    if preds.ndim == 2:
        return fused_cross_entropy(preds, labels)
    b = preds.shape[0]
    flat = preds.reshape(-1, preds.shape[-1])
    per_token = fused_cross_entropy(flat, labels.reshape(-1))
    return per_token.reshape(b, -1).mean(axis=-1)

"""Loss library.

The reference ships the loss as an arbitrary pickled ``torch.nn``
criterion inside the TorchObj envelope (``util.py:30-32``) and works
around integer-label dtype mismatches with a try/except retry that
re-runs the forward with ``.long()`` labels
(``distributed.py:153-158``, ``hogwild.py:108-113``).

Here losses are pure functions ``(preds, targets) -> per-example loss``
and the dtype question is settled *statically* at trace time: each loss
declares what target dtype it needs and promotes once, so there is no
runtime retry (which would be untraceable under ``jit`` anyway).

Per-example (unreduced) losses are returned so the training step can
apply example weights — the mechanism that replaces the reference's
phantom-rank / empty-partition protocol (``distributed.py:46-63``):
an empty shard contributes weight-zero examples instead of a separate
zero-gradient all_reduce participant.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Union

import jax
import jax.numpy as jnp

LossFn = Callable[[jax.Array, jax.Array], jax.Array]


class TokenWeighted(NamedTuple):
    """What a training forward hands :func:`weighted_cross_entropy_loss`
    in place of bare logits: ``logits [batch, seq, vocab]`` and a weight
    for each token's cross entropy, ``weights [batch, seq]``."""

    logits: jax.Array
    weights: jax.Array


class MultiTokenLogits(NamedTuple):
    """What the training forward of a model with a multi-token
    prediction module hands :func:`multi_token_cross_entropy_loss`:
    ``logits [batch, seq, vocab]`` of the next token, ``mtp_logits`` of
    the same shape, whose position ``i`` predicts the token after the
    next (its last position predicts nothing and counts nothing), and
    the ``weight`` of the second loss beside the first."""

    logits: jax.Array
    mtp_logits: jax.Array
    weight: float


def _flatten_per_example(x: jax.Array) -> jax.Array:
    """Mean over all non-batch dims -> shape (batch,)."""
    if x.ndim <= 1:
        return x
    return jnp.mean(x.reshape(x.shape[0], -1), axis=-1)


def _align(preds: jax.Array, targets: jax.Array):
    """Rank-align regression preds/targets so (batch,) vs (batch, 1)
    never broadcasts into a (batch, batch) matrix. The reference's
    analog failure is the dtype/shape RuntimeError it retries around
    (distributed.py:153-158); here alignment is static."""
    targets = targets.astype(preds.dtype)
    if targets.ndim < preds.ndim:
        targets = targets.reshape(targets.shape + (1,) * (preds.ndim - targets.ndim))
    elif preds.ndim < targets.ndim:
        preds = preds.reshape(preds.shape + (1,) * (targets.ndim - preds.ndim))
    return preds, targets


def mse_loss(preds: jax.Array, targets: jax.Array) -> jax.Array:
    preds, targets = _align(preds, targets)
    return _flatten_per_example((preds - targets) ** 2)


def l1_loss(preds: jax.Array, targets: jax.Array) -> jax.Array:
    preds, targets = _align(preds, targets)
    return _flatten_per_example(jnp.abs(preds - targets))


def huber_loss(preds: jax.Array, targets: jax.Array, delta: float = 1.0) -> jax.Array:
    preds, targets = _align(preds, targets)
    err = jnp.abs(preds - targets)
    quad = jnp.minimum(err, delta)
    lin = err - quad
    return _flatten_per_example(0.5 * quad**2 + delta * lin)


def cross_entropy_loss(preds: jax.Array, targets: jax.Array) -> jax.Array:
    """Softmax cross entropy over the last axis of ``preds``.

    Integer targets are class indices (the reference's ``.long()``
    retry path); float targets of matching shape are soft labels.
    """
    logz = jax.nn.logsumexp(preds, axis=-1, keepdims=True)
    logp = preds - logz
    if jnp.issubdtype(targets.dtype, jnp.floating) and targets.shape == preds.shape:
        return -jnp.sum(targets * logp, axis=-1).reshape(preds.shape[0], -1).mean(-1)
    labels = targets.astype(jnp.int32)
    if labels.ndim == preds.ndim:  # (batch, 1) style
        labels = labels.reshape(labels.shape[:-1])
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return -picked.reshape(preds.shape[0], -1).mean(-1)


def fused_cross_entropy_loss(preds: jax.Array, targets: jax.Array) -> jax.Array:
    """The Pallas streaming-CE kernel (ops/fused_ce.py): identical math
    to :func:`cross_entropy_loss` for integer labels, but the softmax
    never materializes in HBM in either direction. Lazy import keeps
    Pallas out of the import path for non-LM users."""
    from sparktorch_tpu.ops.fused_ce import fused_cross_entropy_loss as _fce

    return _fce(preds, targets)


def _fused_tiles(preds: jax.Array) -> bool:
    """Whether LM-shaped logits go to the fused kernel: see
    :func:`cross_entropy_auto`'s two reasons to stay dense."""
    from sparktorch_tpu.ops.fused_ce import can_tile
    from sparktorch_tpu.parallel.compat import ambient_gspmd_mesh

    b, s, v = preds.shape
    return can_tile(b * s, v) and ambient_gspmd_mesh() is None


def cross_entropy_auto(preds: jax.Array, targets: jax.Array) -> jax.Array:
    """``cross_entropy`` registry entry. LM-shaped integer-label logits
    (batch, seq, vocab) dispatch to the fused Pallas kernel — the
    workload it was built for (CausalLM training) — at trace time;
    everything else takes the dense path.

    Two trace-time reasons to stay dense, both read from what the
    trace can see:

    - the (tokens, vocab) shape does not land on the kernel's blocks
      (:func:`sparktorch_tpu.ops.fused_ce.can_tile` — e.g. BERT's
      30,522 vocabulary). "auto" may choose; a caller who names
      ``cross_entropy_fused`` gets an error on the TPU instead.
    - a GSPMD (non-Manual) ambient mesh: a Pallas call cannot be
      partitioned automatically. The TPU compiler refuses it ("Mosaic
      kernels cannot be automatically partitioned. Please wrap the
      call in a shard_map" — found compiling the ep=4 MoE step for a
      described v5e:2x2), and off the TPU the interpret-mode kernel
      lowers to a while loop the partitioner can only handle by
      all-gathering the logits into every shard. Inside shard_map
      bodies — the DP and pipeline trainers, where the fused kernel is
      the right choice — every mesh axis is Manual, so the mesh probe
      reads None and the kernel stays."""
    lm_shaped = preds.ndim == 3 and not (
        jnp.issubdtype(targets.dtype, jnp.floating) and targets.shape == preds.shape
    )
    if lm_shaped and _fused_tiles(preds):
        return fused_cross_entropy_loss(preds, targets)
    return cross_entropy_loss(preds, targets)


def weighted_cross_entropy_loss(preds: TokenWeighted,
                                targets: jax.Array) -> jax.Array:
    """``cross_entropy_weighted`` registry entry: the row's mean over
    its positions of ``weight x cross entropy``, integer targets. A
    masked-diffusion LM's loss is this with weight ``m_i / t`` (1 / the
    row's noise level on the positions it masked, 0 elsewhere), so it
    sums over masked positions only and is normalised by the row's
    length.

    How the weights get here: the trainers call ``loss_fn(preds,
    targets)`` and know no weight by token, and the weights are drawn
    inside the training forward. So the forward returns them WITH its
    logits, as a :class:`TokenWeighted`, and that pair is the ``preds``
    this loss takes: the step has no second branch. The token's cross
    entropy goes through the fused Pallas kernel where
    :func:`cross_entropy_auto` would pick it, else the dense path."""
    logits, weights = preds
    return (token_cross_entropy(logits, targets) * weights).mean(-1)


def token_cross_entropy(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Cross entropy of every token, ``[batch, seq]``, from ``logits
    [batch, seq, vocab]`` and integer ``targets [batch, seq]``: through
    the fused Pallas kernel where :func:`cross_entropy_auto` would pick
    it, else the dense path."""
    b, s, v = logits.shape
    labels = targets.astype(jnp.int32).reshape(b * s)
    flat = logits.reshape(b * s, v)
    if _fused_tiles(logits):
        from sparktorch_tpu.ops.fused_ce import fused_cross_entropy

        per_token = fused_cross_entropy(flat, labels)
    else:
        per_token = jax.nn.logsumexp(flat, axis=-1) - jnp.take_along_axis(
            flat, labels[:, None], axis=-1)[:, 0]
    return per_token.reshape(b, s)


def multi_token_cross_entropy_loss(preds: MultiTokenLogits,
                                   targets: jax.Array) -> jax.Array:
    """``cross_entropy_multi_token`` registry entry: the row's mean
    next-token cross entropy plus ``weight`` times the mean, over the
    ``seq - 1`` positions that have one, of the cross entropy of the
    multi-token prediction module's logits against the label one further
    on (``targets[i]`` is the token after position ``i``, so position
    ``i`` of the module is held to ``targets[i + 1]``; the last position
    has no such label). The two heads reach the loss as TokenWeighted's
    weights do, WITH the logits (:class:`MultiTokenLogits`), and each
    goes through :func:`token_cross_entropy`; the module's part runs
    under the scope ``mtp``, as the module itself does."""
    logits, mtp_logits, weight = preds
    seq = logits.shape[1]
    labels = targets.astype(jnp.int32).reshape(logits.shape[:2])
    per = token_cross_entropy(logits, labels).mean(-1)
    with jax.named_scope("mtp"):
        further = token_cross_entropy(mtp_logits, jnp.roll(labels, -1, 1))
        held = jnp.arange(seq) < seq - 1
        return per + weight * jnp.sum(further * held, -1) / (seq - 1)


def nll_loss(preds: jax.Array, targets: jax.Array) -> jax.Array:
    """Negative log-likelihood on already-log-probability inputs."""
    labels = targets.astype(jnp.int32)
    if labels.ndim == preds.ndim:
        labels = labels.reshape(labels.shape[:-1])
    picked = jnp.take_along_axis(preds, labels[..., None], axis=-1)[..., 0]
    return -picked.reshape(preds.shape[0], -1).mean(-1)


def bce_with_logits_loss(preds: jax.Array, targets: jax.Array) -> jax.Array:
    preds, targets = _align(preds, targets)
    # Numerically-stable sigmoid BCE.
    per = jnp.maximum(preds, 0) - preds * targets + jnp.log1p(jnp.exp(-jnp.abs(preds)))
    return _flatten_per_example(per)


LOSS_REGISTRY: dict[str, LossFn] = {
    "mse": mse_loss,
    "l1": l1_loss,
    "mae": l1_loss,
    "huber": huber_loss,
    "smooth_l1": huber_loss,
    "cross_entropy": cross_entropy_auto,
    "cross_entropy_dense": cross_entropy_loss,
    "cross_entropy_fused": fused_cross_entropy_loss,
    "cross_entropy_weighted": weighted_cross_entropy_loss,
    "cross_entropy_multi_token": multi_token_cross_entropy_loss,
    "nll": nll_loss,
    "bce_with_logits": bce_with_logits_loss,
    # torch.nn criterion-class spellings, so reference users can pass the
    # names they know (util.py:30-32 pickles e.g. nn.MSELoss()).
    "MSELoss": mse_loss,
    "L1Loss": l1_loss,
    "SmoothL1Loss": huber_loss,
    "CrossEntropyLoss": cross_entropy_auto,
    "NLLLoss": nll_loss,
    "BCEWithLogitsLoss": bce_with_logits_loss,
}


def resolve_loss(loss: Union[str, LossFn]) -> LossFn:
    if callable(loss):
        return loss
    try:
        return LOSS_REGISTRY[loss]
    except KeyError:
        raise ValueError(
            f"Unknown loss {loss!r}; known: {sorted(LOSS_REGISTRY)} or pass a callable"
        ) from None

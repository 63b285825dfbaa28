"""The mask of masked block diffusion, as a rule for the kernels of
``ops/rule_attention.py``.

Masked block diffusion trains on a row that holds a sequence twice: the
``L`` clean tokens ``x_0`` and then their noised copy ``x_t``, ``T = 2L``
tokens that share positions (``p(i) = i mod L``). With blocks of ``b``
tokens, ``blk(i) = (i mod L) // b`` and ``noisy(i) = i >= L``, query
``i`` attends key ``j`` iff (:class:`BlockDiffusionMask`)

- both are clean and ``blk(j) <= blk(i)`` (block-causal), or
- ``i`` is noisy, ``j`` clean and ``blk(j) < blk(i)`` (the clean context
  strictly before its block), or
- both are noisy and ``blk(j) == blk(i)`` (its own block, both ways).

A clean query attends no noisy key. The mask is not causal: a query sees
up to ``b - 1`` keys after itself. It is the third of the three rules
the kernels have (``rule_attention.Causal`` and ``CausalWindow`` are the
others): at ``L`` = 8,192 and ``b`` = 4, 576 of 2,048 tiles of 256 x 512
are visited and 89% of the pairs in them are kept.

:func:`block_diffusion_attention` is ``rule_attention`` under the name
``blockdiff``: its kernels are ``blockdiff_attn_fwd``,
``blockdiff_attn_bwd_dq`` and ``blockdiff_attn_bwd_dkv`` in a trace, and
its forward rule names :data:`SAVED_NAMES` for a caller's remat policy.
It takes ``[b, T, h, d]`` operands and turns them itself;
:func:`block_diffusion_attention_heads_first` is
``rule_attention_heads_first`` under the same name, operands in the
kernels' layout and the result flat with nothing transposed, which the
decoder calls behind ``ops/qk_norm_rope.py``.
"""

from __future__ import annotations

import dataclasses

import jax

from sparktorch_tpu.ops.rule_attention import (
    rule_attention, rule_attention_heads_first, saved_names)

_NAME = "blockdiff"
# what the forward rule names for a caller's remat policy
SAVED_NAMES = saved_names(_NAME)


@dataclasses.dataclass(frozen=True)
class BlockDiffusionMask:
    """The rule above for rows of ``seq_len`` clean and ``seq_len``
    noised tokens in blocks of ``block_length``. Called with a column of
    query indices ``[n, 1]`` and a row of key indices ``[1, m]`` (numpy
    on the host, traced int32 in a kernel) it gives the ``[n, m]`` mask."""

    seq_len: int
    block_length: int

    def __post_init__(self):
        if self.seq_len % self.block_length:
            raise ValueError(f"rows of {self.seq_len} tokens are not whole "
                             f"blocks of {self.block_length}")

    def __call__(self, i, j):
        n_i, n_j = i >= self.seq_len, j >= self.seq_len
        b_i = (i % self.seq_len) // self.block_length
        b_j = (j % self.seq_len) // self.block_length
        return ((~n_i & ~n_j & (b_j <= b_i)) | (n_i & ~n_j & (b_j < b_i))
                | (n_i & n_j & (b_j == b_i)))


def block_diffusion_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                              rule) -> jax.Array:
    """``softmax`` attention of each query over the keys ``rule`` keeps
    for it (``rule_attention``'s shapes); ``rule`` is static, a
    :class:`BlockDiffusionMask` for ``T`` tokens or a callable like it."""
    return rule_attention(q, k, v, rule, _NAME)


def block_diffusion_attention_heads_first(q5: jax.Array, k4: jax.Array,
                                          v4: jax.Array, rule) -> jax.Array:
    """:func:`block_diffusion_attention` on operands in the kernels'
    layout (``rule_attention_heads_first``'s shapes): ``q5, k4, v4 ->
    o [b, T, heads * d]``, nothing transposed on either side."""
    return rule_attention_heads_first(q5, k4, v4, rule, _NAME)

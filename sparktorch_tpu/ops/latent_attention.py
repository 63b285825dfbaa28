"""Causal attention whose keys are wider than its values, one query head
a key/value head, in Pallas for TPU — forward and backward. The
uncompressed (training) form of multi-head latent attention: every head
has its own keys and values, a query and a key are ``[nope ; rope]``
(DeepSeek-V3's 128 + 64 = 192 dims, the rotary part one key a token
shared by all heads), a value is 128 wide, and the scale is that of the
192.

How the 192 is laid out: as ONE 256-lane product. ``q5 [b, heads, 1, T,
d_qk]`` and ``k4 [b, heads, T, d_qk]`` hold ``[nope (128) ; rope (64) ;
zeros (64)]``, ``d_qk`` the 192 rounded up to whole 128-lane registers,
``v4 [b, heads, T, d_v]`` the values; the padding lanes are zero on both
sides of every product, so the scores are the 192-wide ones and the
cotangents' padding lanes come out zero. Why this and not a 128 product
plus a 64 product, or the rotary key read from one shared ``[b, T, 64]``
array: the v5e's matrix unit contracts 128 lanes a pass, so 192 costs
two passes in every form and the padded one wastes no pass; it reads and
writes ``q`` and ``k`` a third wider than they are (0.13 GB a layer a
row of 8,192 tokens, 0.2 ms of a layer's 30), writes the rotary key once
a head; and it is the form under which the three tile bodies of
``ops/sparse_attention.py`` (``fwd_tile``, ``dq_tile``, ``dkv_tile``)
and the three kernels of ``ops/rule_attention.py`` around them serve as
they are: they never ask that a query be as wide as a value. What is
here is the block specs and scratch of the two widths, the scale, the
tiles and the ``custom_vjp``. The shared-key form would save a fifth of
the K traffic and the 32 writes of the rotary key; it needs a tile body
of its own (PERF.md section 7).

The mask is the rule :class:`~sparktorch_tpu.ops.rule_attention.Causal`
evaluated on a tile's own indices, and the grid's last axis runs over
``visited_tiles``' static tables, as in ``ops/rule_attention.py``. One
query head a grid step (``G`` = 1), so a step's work is an eighth of a
grouped-query step's at the same tiles: the tiles here are 1,024 x
1,024 (:func:`_blocks`).

:func:`latent_attention_heads_first` takes and returns the kernels'
layout and carries the ``custom_vjp`` (the decoder calls it, with
``ops/latent_rope.py`` writing ``q5``, ``k4`` and ``v4``; the tests pad
and turn ``[b, T, h, d]`` operands themselves). Its output stays heads
first, ``o5 [b, heads, 1, T, d_v]``, which the module turns with an XLA
transpose (:func:`heads_last`), where the grouped-query kernels write
``o`` flat, ``[b, T, heads * d]``: with one head a grid step a flat
block is ``[1024, 128]`` in rows of 256 B at a stride of 8 KB, and on
the chip dq and dkv lost more reading such a cotangent than the turns
cost (PERF.md section 6, PR 41: the two readings). ``pallas_call`` names
``latent_attn_fwd``, ``latent_attn_bwd_dq``, ``latent_attn_bwd_dkv``;
the forward rule names its output and row statistics for a caller's
remat policy (:data:`SAVED_NAMES`). Off the
TPU the kernels run in interpret mode; a shape that does not tile is an
error everywhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from sparktorch_tpu.ops.rule_attention import (
    Causal, _bwd_dkv_kernel, _bwd_dq_kernel, _fwd_kernel, saved_names,
    visited_tiles)
from sparktorch_tpu.ops.sparse_attention import (
    _LANES, _interpret, fwd_scratch, spread)

_NAME = "latent"
SAVED_NAMES = saved_names(_NAME)
_RULE = Causal()


def _blocks(seq: int) -> tuple:
    """``(block_q, block_k)``: the largest powers of two dividing ``seq``
    (a multiple of 128) up to 1,024 x 1,024. One query head shares a grid
    step with no other, so a step's fixed cost weighs eight times what it
    does in a grouped-query kernel at 256 x 512: at 8,192 tokens and 32
    heads forward / dq + dkv took 9.65 / 23.97 ms at 256 x 512, 7.23 /
    21.57 at 512 x 512, 6.60 / 20.23 at 1,024 x 512 and 6.59 / 19.61 at
    1,024 x 1,024, which computes 37.7 M pairs a head for the 35.7 M of
    the smaller tiles (my chip run, PR 40)."""
    b = 1
    while b * 2 <= min(1_024, seq) and seq % (b * 2) == 0:
        b *= 2
    return b, b


def tiles_visited(t: int) -> tuple:
    """``(tiles the kernels visit, tiles of the whole square)`` at ``t``
    tokens."""
    block_q, block_k = _blocks(t)
    (qt, _), _ = visited_tiles(_RULE, t, block_q, block_k)
    return len(qt), (t // block_q) * (t // block_k)


def padded_width(d: int) -> int:
    """``d`` rounded up to whole registers of 128 lanes."""
    return -(-d // _LANES) * _LANES


def heads_last(o5):
    """``o5 [b, heads, 1, T, d_v]`` -> ``[b, T, heads, d_v]``."""
    b, heads, _, t, d = o5.shape
    return jnp.transpose(o5, (0, 3, 1, 2, 4)).reshape(b, t, heads, d)


def _specs(d_qk: int, d_v: int, block_q: int, block_k: int):
    """Block specs on the grid ``(b, head, visit)`` with the table
    prefetched: a Q tile of queries (or their cotangent), of outputs (or
    theirs: the one head's ``[block_q, d_v]``, as the tile bodies read a
    head of a flat tile), a K tile, a V tile, and a Q tile's row
    statistics."""
    q_tile = lambda d: pl.BlockSpec(
        (None, None, 1, block_q, d),
        lambda b, h, v, qt, kt: (b, h, 0, qt[v], 0))
    o_tile = pl.BlockSpec(
        (None, None, None, block_q, d_v),
        lambda b, h, v, qt, kt: (b, h, 0, qt[v], 0))
    k_tile = lambda d: pl.BlockSpec(
        (None, None, block_k, d), lambda b, h, v, qt, kt: (b, h, kt[v], 0))
    return q_tile(d_qk), o_tile, k_tile(d_qk), k_tile(d_v), q_tile(_LANES)


def _call(kernel, name, table, q5, scale, out_shape, in_specs, out_specs,
          scratch_shapes, operands):
    """One kernel of ``ops/rule_attention.py`` over ``table``'s visits
    for every row and head."""
    b, heads, _, t, _ = q5.shape
    block_q, block_k = _blocks(t)
    qt, kt = table
    return pl.pallas_call(
        functools.partial(kernel, rule=_RULE, scale=scale, block_q=block_q,
                          block_k=block_k, n_visits=len(qt), groups=1),
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b, heads, len(qt)),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=scratch_shapes),
        interpret=_interpret(),
        name=name,
    )(jnp.asarray(qt), jnp.asarray(kt), *operands)


def _fwd(q5, k4, v4, scale):
    b, heads, _, t, d_qk = q5.shape
    d_v = v4.shape[-1]
    block_q, block_k = _blocks(t)
    q_major, _ = visited_tiles(_RULE, t, block_q, block_k)
    q_spec, o_spec, k_spec, v_spec, _ = _specs(d_qk, d_v, block_q, block_k)
    lse_spec = pl.BlockSpec((None, None, 1, block_q),
                            lambda b, h, v, qt, kt: (b, h, 0, qt[v]))
    return _call(
        _fwd_kernel, f"{_NAME}_attn_fwd", q_major, q5, scale,
        [jax.ShapeDtypeStruct((b, heads, 1, t, d_v), q5.dtype),
         jax.ShapeDtypeStruct((b, heads, 1, t), jnp.float32)],
        [q_spec, k_spec, v_spec], [o_spec, lse_spec],
        fwd_scratch(1, d_v, block_q), (q5, k4, v4))


def _bwd(q5, k4, v4, o5, lse, do5, scale):
    _, _, _, t, d_qk = q5.shape
    d_v = v4.shape[-1]
    block_q, block_k = _blocks(t)
    q_major, k_major = visited_tiles(_RULE, t, block_q, block_k)
    lse, di = spread(lse), spread(jnp.sum(
        o5.astype(jnp.float32) * do5.astype(jnp.float32), axis=-1))
    q_spec, o_spec, k_spec, v_spec, row_spec = _specs(
        d_qk, d_v, block_q, block_k)
    in_specs = [q_spec, k_spec, v_spec, o_spec, row_spec, row_spec]
    operands = (q5, k4, v4, do5, lse, di)
    dq5 = _call(
        _bwd_dq_kernel, f"{_NAME}_attn_bwd_dq", q_major, q5, scale,
        jax.ShapeDtypeStruct(q5.shape, q5.dtype), in_specs, q_spec,
        [pltpu.VMEM((1, block_q, d_qk), jnp.float32)], operands)
    dk4, dv4 = _call(
        _bwd_dkv_kernel, f"{_NAME}_attn_bwd_dkv", k_major, q5, scale,
        [jax.ShapeDtypeStruct(k4.shape, k4.dtype),
         jax.ShapeDtypeStruct(v4.shape, v4.dtype)], in_specs,
        [k_spec, v_spec],
        [pltpu.VMEM((block_k, d_qk), jnp.float32),
         pltpu.VMEM((block_k, d_v), jnp.float32)], operands)
    return dq5, dk4, dv4


def _check(q5, k4, v4):
    b, heads, groups, t, d_qk = q5.shape
    d_v = v4.shape[-1]
    if (groups != 1 or k4.shape != (b, heads, t, d_qk)
            or v4.shape != (b, heads, t, d_v)):
        raise ValueError(f"latent_attn: q {q5.shape}, k {k4.shape}, v "
                         f"{v4.shape} are not one query head a key/value "
                         f"head of one key width")
    if d_qk % _LANES or d_v % _LANES or t % _LANES:
        raise ValueError(
            f"latent_attn: seq {t}, keys of {d_qk} and values of {d_v} "
            f"cannot be tiled: all must be multiples of {_LANES}")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def latent_attention_heads_first(q5: jax.Array, k4: jax.Array, v4: jax.Array,
                                 scale: float) -> jax.Array:
    """Causal softmax attention on operands in the kernels' layout: ``q5
    [b, heads, 1, T, d_qk]``, ``k4 [b, heads, T, d_qk]``, ``v4 [b, heads,
    T, d_v]`` -> ``o5 [b, heads, 1, T, d_v]``, scores times ``scale``
    (static: that of the keys' true width, which the padding hides). The
    cotangents come back as the backward kernels write them."""
    return _forward(q5, k4, v4, scale)[0]


def _forward(q5, k4, v4, scale):
    _check(q5, k4, v4)
    o5, lse = _fwd(q5, k4, v4, scale)
    o5 = checkpoint_name(o5, SAVED_NAMES[0])
    lse = checkpoint_name(lse, SAVED_NAMES[1])
    return o5, (q5, k4, v4, o5, lse)


def _bwd_rule(scale, res, do5):
    q5, k4, v4, o5, lse = res
    return _bwd(q5, k4, v4, o5, lse, do5.astype(q5.dtype), scale)


latent_attention_heads_first.defvjp(_forward, _bwd_rule)


"""Attention under a mask that is a static rule of the two indices, in
Pallas for TPU — forward and backward, grouped-query heads.

A rule is a hashable callable: given a column of query indices ``[n,
1]`` and a row of key indices ``[1, m]`` (numpy on the host, traced
int32 in a kernel) it gives the ``[n, m]`` mask, the same for every row,
head and layer. As an array such a mask would be ``T x T`` bytes a row
(67 MB at 8,192 tokens); here it is never an array. The kernels evaluate
the rule on a tile's own indices (a few integer operations on a column
and a row of indices, one comparison or two a pair), and they visit only
the tiles in which the rule keeps a pair: the rule is evaluated once on
the host, tile by tile, into two static tables of ``(Q tile, K tile)``
visits, in Q-major order for the forward and dq kernels and in K-major
order for dkv. The grid's last axis runs over a table, which reaches the
kernel and its block index maps by scalar prefetch; a tile the rule
leaves empty costs nothing, not even a skipped grid step.

Three rules exist. Two are here:

- :class:`Causal`: query ``i`` attends key ``j`` iff ``j <= i``. At
  8,192 tokens in tiles of 256 x 512 it visits 272 of 512 tiles, 35.65 M
  pairs for 33.56 M kept.
- :class:`CausalWindow`: ``j <= i`` and ``i - j < window`` (``window``
  keys with its own). At 8,192 tokens and a window of 512 it visits 2
  tiles a Q tile (62 of 512), 8.13 M pairs for 4.06 M kept: half of what
  a window kernel computes is masked, at these tiles.

The third, ``ops/block_diffusion_attention.py``'s ``BlockDiffusionMask``
(the clean row and its noised copy of masked block diffusion), is not
causal; it lives with the model that needs it and calls these kernels.

Shared with ``ops/sparse_attention.py``, which holds them: the layout
(``q`` as ``[b, kv_heads, G, T, d]``, one grid step serving the ``G``
query heads of a key/value head from one K and one V tile; ``o`` and its
cotangent flat, ``[b, T, kv_heads * G * d]``, a step's block the ``G *
d`` lanes of its key/value head), the tile
sizes, the streaming-softmax body with its scratch and both backward
bodies (``fwd_tile`` / ``fwd_init`` / ``fwd_finalize`` / ``fwd_scratch``,
``dq_tile``, ``dkv_tile``: the mask comes from a tile of an array there
and from the rule here), the ``_NEG`` convention (a masked score is a
large negative finite number and a masked probability exactly 0) and the
row statistics the backward kernels read. The forward body works on the
tile turned (keys down the sublanes, queries along the lanes, so a
head's running maximum and sum are rows; that file's docstring says
why), and its log-sum-exp leaves one number a row, ``[b, kv_heads, G,
T]`` float32. A rule is element-wise on its broadcast indices, so the
forward kernel calls it on a row of queries and a column of keys and
gets the turned mask with nothing transposed (:func:`_keep`); dq and
dkv keep the tile queries down.

Heads of 64 (``head_dim`` 64 in an operand whose last axis is 128) lie
TWO TO A REGISTER, in HBM as in the products they come from: nothing is
padded to 128 and ``o`` is flat with head ``i`` in lanes ``[64 i, 64 (i
+ 1))``. ``q5 [b, pairs, G, T, 128]`` is then the flat ``q`` by
registers (register ``r`` of a pair holds its query heads ``2 r`` and
``2 r + 1``) and ``k4`` / ``v4 [b, pairs, T, 128]`` hold a PAIR of
key/value heads, ``A`` in lanes 0-63 and ``B`` in lanes 64-127; ``G`` is
still the query heads of one key/value head, so head ``h`` of the pair's
``2 G`` reads ``A`` when ``h < G`` and ``B`` otherwise. A grid step
serves a pair. The shared bodies want a whole register a head, so this
file has the three bodies again for halves (``_fwd_tile_halves`` and the
rest): a step makes, once for its ``2 G`` heads, the four copies of the
K tile and of the V tile with ONE head's 64 dims at ONE half of the
lanes and zeros at the other (a select, and a lane roll by 64 where the
head moves), so that a head's scores are the product of its register of
``q`` with the copy that has its key/value head at its own lanes (the
other head's lanes meet zeros), ``p v`` lands at its own rows of the
running output (a sublane slice) and ``dq`` at its own lanes; ``dk`` and
``dv`` are summed with a key/value head's two lane positions apart and
folded once a K tile. Every matrix product is a whole register deep or
wide, so a 64-wide head costs the matrix unit what a 128-wide one does:
the price of keeping HBM dense with no lane shuffles a head (PERF.md
section 6, PR 46). The statistics are a row a HEAD, ``[b, pairs, 2 G,
T]``.

Two entries, as ``ops/sparse_attention.py`` has them and says more of:
:func:`rule_attention_heads_first` takes the kernels' layout (``q5``,
``k4``, ``v4``), returns the flat ``o`` (backward: takes the flat
``do``, the cotangents back as the kernels write them) and carries the
``custom_vjp``; the decoder calls it, with ``ops/qk_norm_rope.py``
writing ``q5``, ``k4`` and ``v4`` in that layout and the module's gate
and ``Wo`` reading ``o`` as it lies. :func:`rule_attention` takes ``[b,
T, h, d]`` operands and is a thin wrapper that turns them with XLA
transposes and reshapes the flat result.

A call carries a static ``name``: its kernels are the ``pallas_call``s
``<name>_attn_fwd``, ``<name>_attn_bwd_dq`` and ``<name>_attn_bwd_dkv``,
so that two kinds of layer in one step separate in a device trace, and
its forward rule names its output and row statistics for a caller's
remat policy (:func:`saved_names`: ``<name>_attn_out``,
``<name>_attn_lse``, as ``sparse_attention.SAVED_NAMES``), so that the
forward kernel runs once a layer a step and a policy can keep one kind's
and not another's. Off the TPU the kernels run in interpret mode. A
shape that does not tile (``T`` no multiple of 128; a ``head_dim`` that
is neither a multiple of 128 nor 64), or a rule that leaves a whole row
of tiles empty, is an error everywhere: there is no dense path.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from sparktorch_tpu.ops.flash_attention import _nt, _tn
from sparktorch_tpu.ops.sparse_attention import (
    _LANES, _NEG, _blocks, _head, _interpret, _scores, dkv_tile, dq_tile,
    fwd_finalize, fwd_init, fwd_scratch, fwd_tile, heads_first,
    row_statistics, spread)


def saved_names(name: str) -> tuple:
    """What the forward rule of a call named ``name`` names for a
    caller's remat policy: its output (flat, ``[b, T, heads * d]``) and
    the row statistics."""
    return (f"{name}_attn_out", f"{name}_attn_lse")


@dataclasses.dataclass(frozen=True)
class Causal:
    """Query ``i`` attends key ``j`` iff ``j <= i``."""

    def __call__(self, i, j):
        return j <= i


@dataclasses.dataclass(frozen=True)
class CausalWindow:
    """Query ``i`` attends the ``window`` keys that end with its own:
    ``j <= i`` and ``i - j < window``."""

    window: int

    def __post_init__(self):
        if self.window < 1:
            raise ValueError(f"a window of {self.window} keys holds no key")

    def __call__(self, i, j):
        return (j <= i) & (i - j < self.window)


@functools.lru_cache(maxsize=None)
def visited_tiles(rule, t: int, block_q: int, block_k: int):
    """The tiles of ``[t, t]`` in which ``rule`` keeps a pair, as int32
    arrays ``(q tile, k tile)`` of equal length: in Q-major order, and
    the same visits in K-major order."""
    n_q, n_k = t // block_q, t // block_k
    cols = np.arange(t, dtype=np.int32)[None, :]
    kept = np.stack([
        rule(qi * block_q + np.arange(block_q, dtype=np.int32)[:, None], cols)
        .reshape(block_q, n_k, block_k).any((0, 2)) for qi in range(n_q)])
    if not (kept.any(0).all() and kept.any(1).all()):
        raise ValueError(f"{rule} leaves a whole row of {block_q} x "
                         f"{block_k} tiles empty at {t} tokens")
    q_major = np.argwhere(kept).astype(np.int32)
    k_major = q_major[np.lexsort((q_major[:, 0], q_major[:, 1]))]
    return (q_major[:, 0], q_major[:, 1]), (k_major[:, 0], k_major[:, 1])


def tiles_visited(rule, t: int) -> tuple:
    """``(tiles the kernels visit, tiles of the whole square)`` at ``t``
    tokens."""
    block_q, block_k = _blocks(t)
    (qt, _), _ = visited_tiles(rule, t, block_q, block_k)
    return len(qt), (t // block_q) * (t // block_k)


def _visit(major_ref, v, n_visits):
    """Whether visit ``v`` is the first and the last of its run in the
    table's major column."""
    here = major_ref[v]
    first = (v == 0) | (major_ref[jnp.maximum(v - 1, 0)] != here)
    last = (v == n_visits - 1) | (
        major_ref[jnp.minimum(v + 1, n_visits - 1)] != here)
    return first, last


def _keep(rule, qi, ki, block_q, block_k, keys_down: bool = False):
    """The rule on a tile's own indices: queries down and keys across,
    ``[block_q, block_k]``, or (``keys_down``, the forward kernel's
    tile) keys down and queries across. A rule is element-wise on its
    broadcast indices, so the turned tile is the rule on a row of
    queries and a column of keys: nothing is transposed."""
    q_shape, k_shape = ((1, block_q), (block_k, 1)) if keys_down else (
        (block_q, 1), (1, block_k))
    queries = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, q_shape, int(keys_down))
    keys = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, k_shape, int(not keys_down))
    return rule(queries, keys)


# -- heads of 64, two to a register ------------------------------------------

_HALF = 64


def _low(shape):
    """Which lanes are a register's first head's."""
    return jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1) < _HALF


def _apart(x):
    """``x [block_k, 128]`` (key/value head ``A`` in lanes 0-63, ``B`` in
    64-127) as ``copies[kv head][half]``: that head's 64 dims at the
    lanes of ``half`` and zeros at the others. The roll is made on 32-bit
    lanes."""
    wide = x.astype(jnp.float32)
    low = _low(wide.shape)
    a, b = jnp.where(low, wide, 0.0), jnp.where(low, 0.0, wide)
    to = lambda y: y.astype(x.dtype)
    return ((to(a), to(pltpu.roll(a, _HALF, 1))),
            (to(pltpu.roll(b, _HALF, 1)), to(b)))


def _halves(groups):
    """``(register, half, head of the pair, its key/value head)`` for the
    ``2 groups`` heads a step serves."""
    return [(r, half, 2 * r + half, (2 * r + half) // groups)
            for r in range(groups) for half in range(2)]


def _fwd_tile_halves(q_ref, k, v, keep, acc_ref, m_ref, l_ref, scale,
                     groups):
    """``sparse_attention.fwd_tile`` for heads of 64: the running output
    ``[groups, 128, block_q]`` holds a register's two heads one above
    the other, the statistics are a row a head, ``[2 groups, 8,
    block_q]``."""
    ks, vs = _apart(k), _apart(v)
    for r, half, h, kv in _halves(groups):
        rows = pl.ds(half * _HALF, _HALF)
        s = jnp.where(keep, _nt(ks[kv][half], q_ref[r]) * scale, _NEG)
        m_prev, l_prev = m_ref[h][:1], l_ref[h][:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
        l_new = alpha * l_prev + jnp.sum(p, axis=0, keepdims=True)
        pv = _tn(vs[kv][half], p.astype(v.dtype))   # zeros off its rows
        acc_ref[r, rows, :] = (acc_ref[r, rows, :] * alpha
                               + pv[half * _HALF:(half + 1) * _HALF])
        m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])
        l_ref[h] = jnp.broadcast_to(l_new, l_ref.shape[1:])


def _fwd_finalize_halves(o_ref, lse_ref, acc_ref, m_ref, l_ref, groups):
    for r in range(groups):
        ls = [jnp.maximum(l_ref[2 * r + half][:1], 1e-20)
              for half in range(2)]
        out = jnp.concatenate([acc_ref[r, :_HALF] / ls[0],
                               acc_ref[r, _HALF:] / ls[1]], 0)
        o_ref[_head(r, _LANES)] = out.T.astype(o_ref.dtype)
        for half in range(2):
            lse_ref[pl.ds(2 * r + half, 1), :] = (
                m_ref[2 * r + half][:1] + jnp.log(ls[half]))


def _dq_tile_halves(q_ref, k, v, keep, do_ref, lse_ref, d_ref, dq_acc,
                    scale, groups):
    ks, vs = _apart(k), _apart(v)
    for r, half, h, kv in _halves(groups):
        s = _scores(q_ref[r], ks[kv][half], keep, scale)
        p = jnp.where(keep, jnp.exp(s - lse_ref[h][:, :1]), 0.0)
        dp = _nt(do_ref[_head(r, _LANES)], vs[kv][half])
        ds = p * (dp - d_ref[h][:, :1])
        # at the head's own lanes: the copy is zero at the others
        dq_acc[r] = dq_acc[r] + jax.lax.dot_general(
            ds.astype(k.dtype), ks[kv][half], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def _dkv_tile_halves(q_ref, k, v, keep, do_ref, lse_ref, d_ref, dk_acc,
                     dv_acc, scale, groups):
    """``dk_acc`` and ``dv_acc [2, block_k, 128]``: a key/value head's
    sums with what its query heads at a register's first half give in
    lanes 0-63 and its others' in lanes 64-127 (:func:`_folded` adds the
    two)."""
    ks, vs = _apart(k), _apart(v)
    low = _low((1, _LANES))
    for r, half, h, kv in _halves(groups):
        own = low if half == 0 else ~low
        q, do = q_ref[r], do_ref[_head(r, _LANES)]
        q_own = jnp.where(own, q, jnp.zeros_like(q))
        do_own = jnp.where(own, do, jnp.zeros_like(do))
        s = _scores(q, ks[kv][half], keep, scale)
        p = jnp.where(keep, jnp.exp(s - lse_ref[h][:, :1]), 0.0)
        dv_acc[kv] = dv_acc[kv] + _tn(p.astype(do.dtype), do_own)
        dp = _nt(do, vs[kv][half])
        ds = p * (dp - d_ref[h][:, :1])
        dk_acc[kv] = dk_acc[kv] + _tn(ds.astype(q.dtype), q_own)


def _folded(acc_ref):
    """A pair's ``[block_k, 128]`` from its two heads' sums kept apart:
    head ``A``'s two halves added into lanes 0-63, ``B``'s into
    64-127."""
    both = [acc_ref[kv] + pltpu.roll(acc_ref[kv], _HALF, 1)
            for kv in range(2)]
    return jnp.where(_low(both[0].shape), both[0], both[1])


def _row_statistics_halves(o, lse, do):
    """``sparse_attention.row_statistics`` for heads of 64: ``sum(o *
    do)`` over a head's lanes as a product with the heads' indicator
    ``[heads * 64, heads]`` (float32, exact to its sums), because a
    reshape of the lanes into heads of 64 is a copy of the whole array
    into registers half empty (compiled for the v5e, PR 46)."""
    b, pairs, heads, t = lse.shape
    n = pairs * heads
    owner = (jnp.arange(n * _HALF)[:, None] // _HALF
             == jnp.arange(n)[None, :]).astype(jnp.float32)
    di = jnp.einsum("btf,fh->bth",
                    o.astype(jnp.float32) * do.astype(jnp.float32), owner,
                    precision=jax.lax.Precision.HIGHEST)
    di = jnp.transpose(di.reshape(b, t, pairs, heads), (0, 2, 3, 1))
    return spread(lse), spread(di)


def _fwd_kernel(qt_ref, kt_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, rule, scale, block_q, block_k,
                n_visits, groups, halves=False):
    v = pl.program_id(2)
    first, last = _visit(qt_ref, v, n_visits)

    @pl.when(first)
    def _init():
        fwd_init(acc_ref, m_ref, l_ref)

    keep = _keep(rule, qt_ref[v], kt_ref[v], block_q, block_k,
                 keys_down=True)
    (_fwd_tile_halves if halves else fwd_tile)(
        q_ref, k_ref[...], v_ref[...], keep, acc_ref, m_ref, l_ref, scale,
        groups)

    @pl.when(last)
    def _finalize():
        (_fwd_finalize_halves if halves else fwd_finalize)(
            o_ref, lse_ref, acc_ref, m_ref, l_ref, groups)


def _bwd_dq_kernel(qt_ref, kt_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   d_ref, dq_ref, dq_acc, *, rule, scale, block_q, block_k,
                   n_visits, groups, halves=False):
    v = pl.program_id(2)
    first, last = _visit(qt_ref, v, n_visits)

    @pl.when(first)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    keep = _keep(rule, qt_ref[v], kt_ref[v], block_q, block_k)
    (_dq_tile_halves if halves else dq_tile)(
        q_ref, k_ref[...], v_ref[...], keep, do_ref, lse_ref, d_ref, dq_acc,
        scale, groups)

    @pl.when(last)
    def _finalize():
        dq_ref[...] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(qt_ref, kt_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    d_ref, dk_ref, dv_ref, dk_acc, dv_acc, *, rule, scale,
                    block_q, block_k, n_visits, groups, halves=False):
    v = pl.program_id(2)
    first, last = _visit(kt_ref, v, n_visits)

    @pl.when(first)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    keep = _keep(rule, qt_ref[v], kt_ref[v], block_q, block_k)
    (_dkv_tile_halves if halves else dkv_tile)(
        q_ref, k_ref[...], v_ref[...], keep, do_ref, lse_ref, d_ref, dk_acc,
        dv_acc, scale, groups)

    @pl.when(last)
    def _finalize():
        dk, dv = ((_folded(dk_acc), _folded(dv_acc)) if halves
                  else (dk_acc[...], dv_acc[...]))
        dk_ref[...] = (dk * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv.astype(dv_ref.dtype)


def _specs(groups, d, block_q, block_k, heads):
    """Block specs of the operands every kernel shares, on the grid
    ``(b, kv head, visit)`` with the table prefetched; ``heads``: the
    rows of statistics a step reads (``groups``, or twice that for
    heads of 64)."""
    q_spec = pl.BlockSpec(
        (None, None, groups, block_q, d),
        lambda b, h, v, qt, kt: (b, h, 0, qt[v], 0))
    # o and its cotangent, [b, T, kv_heads * groups * d]: a key/value
    # head's group is a block of lanes
    o_spec = pl.BlockSpec(
        (None, block_q, groups * d), lambda b, h, v, qt, kt: (b, qt[v], h))
    kv_spec = pl.BlockSpec(
        (None, None, block_k, d), lambda b, h, v, qt, kt: (b, h, kt[v], 0))
    row_spec = pl.BlockSpec(
        (None, None, heads, block_q, _LANES),
        lambda b, h, v, qt, kt: (b, h, 0, qt[v], 0))
    return q_spec, o_spec, kv_spec, row_spec


def _call(kernel, name, table, shape, head_dim, out_shape, in_specs,
          out_specs, scratch_shapes, operands, **static):
    """One kernel over ``table``'s visits for every row and key/value
    head (every pair of them, for heads of ``head_dim`` 64)."""
    b, hkv, groups, t, d = shape
    block_q, block_k = _blocks(t)
    qt, kt = table
    return pl.pallas_call(
        functools.partial(kernel, scale=head_dim ** -0.5, block_q=block_q,
                          block_k=block_k, n_visits=len(qt), groups=groups,
                          halves=head_dim < d, **static),
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b, hkv, len(qt)),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=scratch_shapes),
        interpret=_interpret(),
        name=name,
    )(jnp.asarray(qt), jnp.asarray(kt), *operands)


def _fwd(rule, name, head_dim, q5, k4, v4):
    b, hkv, groups, t, d = q5.shape
    heads = groups * (d // head_dim)   # of one grid step
    block_q, block_k = _blocks(t)
    q_major, _ = visited_tiles(rule, t, block_q, block_k)
    q_spec, o_spec, kv_spec, _ = _specs(groups, d, block_q, block_k, heads)
    # the log-sum-exp, one number a row with the sequence along the lanes
    lse_spec = pl.BlockSpec((None, None, heads, block_q),
                            lambda b, h, v, qt, kt: (b, h, 0, qt[v]))
    acc, m, l = fwd_scratch(groups, d, block_q)
    if heads > groups:   # a row of statistics a head
        m = l = pltpu.VMEM((heads, *m.shape[1:]), jnp.float32)
    return _call(
        _fwd_kernel, f"{name}_attn_fwd", q_major, q5.shape, head_dim,
        [jax.ShapeDtypeStruct((b, t, hkv * groups * d), q5.dtype),
         jax.ShapeDtypeStruct((b, hkv, heads, t), jnp.float32)],
        [q_spec, kv_spec, kv_spec], [o_spec, lse_spec],
        [acc, m, l], (q5, k4, v4), rule=rule)


def _bwd(rule, name, head_dim, q5, k4, v4, o, lse, do):
    b, hkv, groups, t, d = q5.shape
    heads = groups * (d // head_dim)
    block_q, block_k = _blocks(t)
    q_major, k_major = visited_tiles(rule, t, block_q, block_k)
    lse, di = (row_statistics if heads == groups
               else _row_statistics_halves)(o, lse, do)
    q_spec, o_spec, kv_spec, row_spec = _specs(groups, d, block_q, block_k,
                                               heads)
    in_specs = [q_spec, kv_spec, kv_spec, o_spec, row_spec, row_spec]
    operands = (q5, k4, v4, do, lse, di)
    dq5 = _call(
        _bwd_dq_kernel, f"{name}_attn_bwd_dq", q_major, q5.shape, head_dim,
        jax.ShapeDtypeStruct(q5.shape, q5.dtype), in_specs, q_spec,
        [pltpu.VMEM((groups, block_q, d), jnp.float32)], operands,
        rule=rule)
    # heads of 64: a pair's two heads' sums apart (``_folded``)
    sums = (block_k, d) if heads == groups else (2, block_k, d)
    dk4, dv4 = _call(
        _bwd_dkv_kernel, f"{name}_attn_bwd_dkv", k_major, q5.shape, head_dim,
        [jax.ShapeDtypeStruct(k4.shape, k4.dtype),
         jax.ShapeDtypeStruct(v4.shape, v4.dtype)], in_specs,
        [kv_spec, kv_spec],
        [pltpu.VMEM(sums, jnp.float32), pltpu.VMEM(sums, jnp.float32)],
        operands, rule=rule)
    return dq5, dk4, dv4


def _check(q5, k4, v4, name, head_dim):
    b, hkv, _, t, d = q5.shape
    if k4.shape != v4.shape or k4.shape != (b, hkv, t, d):
        raise ValueError(f"{name}_attn: q {q5.shape}, k {k4.shape}, v "
                         f"{v4.shape} do not go together")
    if d % _LANES or t % _LANES or head_dim not in (d, _HALF) or (
            head_dim == _HALF and d != _LANES):
        raise ValueError(
            f"{name}_attn: seq {t} x head_dim {head_dim} in operands "
            f"{d} wide cannot be tiled: seq must be a multiple of {_LANES} "
            f"and head_dim one too, or {_HALF} with two heads to a register "
            f"of {_LANES} lanes")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def rule_attention_heads_first(q5: jax.Array, k4: jax.Array, v4: jax.Array,
                               rule, name: str,
                               head_dim: int = 0) -> jax.Array:
    """:func:`rule_attention` on operands in the kernels' layout: ``q5
    [b, kv_heads, G, T, d]``, ``k4`` and ``v4 [b, kv_heads, T, d]`` ->
    ``o [b, T, kv_heads * G * d]``, head ``i`` in lanes ``[i * d, (i +
    1) * d)``; the cotangents of ``q5``, ``k4`` and ``v4`` come back as
    the backward kernels write them. Nothing is transposed on either
    side. ``head_dim`` (0: ``d``) 64 with ``d`` 128 says that two heads
    lie in each register: ``q5 [b, pairs, G, T, 128]``, ``k4`` and ``v4
    [b, pairs, T, 128]`` as the module docstring lays them,
    ``ops/qk_norm_rope.py`` writes them and :func:`heads_in_registers`
    turns ``[b, T, h, 64]`` operands."""
    return _forward(q5, k4, v4, rule, name, head_dim)[0]


def _forward(q5, k4, v4, rule, name, head_dim=0):
    head_dim = head_dim or q5.shape[-1]
    _check(q5, k4, v4, name, head_dim)
    o, lse = _fwd(rule, name, head_dim, q5, k4, v4)
    out_name, lse_name = saved_names(name)
    o = checkpoint_name(o, out_name)
    lse = checkpoint_name(lse, lse_name)
    return o, (q5, k4, v4, o, lse)


def _bwd_rule(rule, name, head_dim, res, do):
    q5, k4, v4, o, lse = res
    return _bwd(rule, name, head_dim or q5.shape[-1], q5, k4, v4, o, lse,
                do.astype(q5.dtype))


rule_attention_heads_first.defvjp(_forward, _bwd_rule)


def rule_attention(q: jax.Array, k: jax.Array, v: jax.Array, rule,
                   name: str) -> jax.Array:
    """``softmax`` attention of each query over the keys ``rule`` keeps
    for it. ``q`` is ``[b, T, heads, d]``, ``k`` and ``v`` are ``[b, T,
    kv_heads, d]`` (query head ``i`` reads key/value head ``i // (heads
    // kv_heads)``); ``rule`` and ``name`` are static: a rule for ``T``
    tokens, and what the call's kernels and saved arrays are called. A
    thin wrapper: it turns its operands heads first (heads of 64 two to
    a register) and calls :func:`rule_attention_heads_first`, whose flat
    result reshapes to ``q``'s shape with no element moved."""
    if q.shape[-1] == _HALF:
        return rule_attention_heads_first(
            *heads_in_registers(q, k, v, f"{name}_attn"), rule, name,
            _HALF).reshape(q.shape)
    return rule_attention_heads_first(
        *heads_first(q, k, v, f"{name}_attn"), rule, name).reshape(q.shape)


def heads_in_registers(q, k, v, name: str):
    """``q [b, T, h, 64]``, ``k`` and ``v [b, T, hkv, 64]`` as the
    kernels read heads of 64: ``q5 [b, hkv / 2, h / hkv, T, 128]``,
    ``k4`` and ``v4 [b, hkv / 2, T, 128]``, the flat arrays' lanes by
    registers, by transposing copies (the decoder's fused
    ``ops/qk_norm_rope.py`` writes this layout itself)."""
    b, t, h, d = q.shape
    hkv = k.shape[2]
    if d != _HALF or h % hkv or hkv % 2:
        raise ValueError(
            f"{name}: {h} query heads on {hkv} key/value heads of {d} do "
            f"not lie two to a register, a pair of key/value heads a step")
    pairs = hkv // 2
    return (jnp.transpose(q.reshape(b, t, pairs, h // hkv, _LANES),
                          (0, 2, 3, 1, 4)),
            jnp.swapaxes(k.reshape(b, t, pairs, _LANES), 1, 2),
            jnp.swapaxes(v.reshape(b, t, pairs, _LANES), 1, 2))

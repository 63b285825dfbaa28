"""Attention over a per-query selected key set, in Pallas for TPU —
forward and backward, grouped-query heads.

Learned sparse attention (an indexer scores the keys of each query and
the query attends only its ``topk`` best) hands the attention a set
``S_t`` per query, one for all heads. Here the set is a mask
``[batch, T, T]`` of int8 (nonzero: query ``t`` attends key ``s``),
which whoever selected the keys has made. The kernels are the flash
kernels of ``ops/flash_attention.py`` with two changes:

- grouped-query heads: the grid runs over key/value heads, and one grid
  step serves the ``G`` query heads of its group from one K tile, one V
  tile and one mask tile in VMEM (``q`` is laid out ``[b, kv_heads, G,
  T, d]``), so the mask is read once a group, not once a head;
- a masked score is set to a large negative FINITE number and a masked
  probability to exactly 0, so a tile in which some query selects
  nothing (or every query: ``S_t`` may skip whole tiles) leaves the
  running maximum and sum as they were. The mask is zero above the
  diagonal (a query attends no later key): the tiles strictly above it
  are neither computed nor fetched.

These kernels compute every pair of a tile they visit and throw the
masked ones away: at 8,192 tokens and 2,048 selected keys that is about
2.3x the selected pairs. Skipping tiles no query selects from, or
gathering the selected keys, is a later optimisation; the roofline
share the benchmark reports counts selected pairs only and says so.

The forward kernel works on the tile TURNED, as ``flash_attention``'s
streaming branch does: keys down the sublanes, queries along the lanes
(``[block_k, block_q]`` = ``[512, 256]`` a head of the group at 8,192
tokens; scores ``K_tile @ Q_g^T``, the PV product ``V_tile^T @ p``). A
head's running maximum and sum are then ROWS ``[1, block_q]``: they
reduce down the registers with no cross-lane reduction, live in VMEM as
``[G, 8, block_q]`` float32 (two registers a head at 256 queries) and
rescale the running output ``[G, d, block_q]`` spread down the
sublanes; as ``[block_q, 1]`` columns they cost as much as the tile's
own passes (PERF.md section 6, PR 33 and 36). The output is turned back
once a Q tile, each head to its own lanes of the output's tile (below);
the log-sum-exp leaves one number a row, ``[b, kv_heads, G, T]`` float32
with T along the lanes. The int8 mask lies queries down in HBM (whoever
selected the keys wrote it so, and dq and dkv read it so): the forward
kernel turns its tile once a grid step, after the cast, for the heads
that share it.
The two backward kernels keep the tile queries down; they read finished
statistics and reduce nothing.

The output is FLAT, ``o [b, T, kv_heads * G * d]`` with head ``i`` in
lanes ``[i * d, (i + 1) * d)``: the layout in which an output projection
``[heads * d, hidden]`` reads it as it lies, and the encoder's kernels'
(``ops/flash_attention.py``). A kernel's output block is ``[block_q, G *
d]`` at ``(b, Q tile, key/value head)``: the turn from heads first is
the block's index map and a lane slice a head in the tile
(:func:`_head`), and the backward kernels read the cotangent ``do``
through the same spec. Nothing outside a kernel reshapes the lane axis
of an ``o``-sized array into heads by ``[b, T, heads, d]``, which on the
TPU is a copy of the whole array: a sum or a product a head goes through
:func:`by_head`.

The backward kernels need two arrays only the forward kernel can make:
its output (flat, the inputs' dtype) and the row statistics (the
log-sum-exp above, as the kernel wrote it). The forward rule names both
(``checkpoint_name``, :data:`SAVED_NAMES`), so a caller who
rematerialises around the attention can list them in its policy
(``save_only_these_names(*SAVED_NAMES)``) and the backward pass reads
what the forward pass left, for one more activation of ``q``'s size a
call, and does not launch ``sparse_attn_fwd`` a second time. Outside a
``jax.checkpoint``, or under a policy that lists neither, the names are
identities. On their way into dq and dkv the statistics and ``sum(o *
do)`` are spread over 128 lanes by XLA (:func:`row_statistics`).

Two entries, one set of kernels. :func:`sparse_attention_heads_first`
takes the kernels' own layout (``q5 [b, kv_heads, G, T, d]``, ``k4`` and
``v4 [b, kv_heads, T, d]``) and returns the flat ``o``; backward it
takes the flat ``do`` and hands back ``dq5``, ``dk4``, ``dv4`` as the
kernels write them. It carries the ``custom_vjp``: nothing is transposed
on either side. The decoder calls it (``models/sparse_moe_lm.py``):
there the turn INTO the layout is made by ``ops/qk_norm_rope.py``'s
kernels, whose output blocks' index map it is, and ``Wo`` reads ``o`` as
it lies. :func:`sparse_attention` takes ``[b, T, h, d]`` operands: a
thin wrapper that turns them with XLA transposes (:func:`heads_first`)
and reshapes the flat result, for the tests and any caller whose
operands lie tokens first.

``pallas_call`` names: ``sparse_attn_fwd``, ``sparse_attn_bwd_dq``,
``sparse_attn_bwd_dkv``. Off the TPU they run in interpret mode. A
shape that does not tile is an error everywhere: there is no dense
path.
"""

from __future__ import annotations

import functools
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from sparktorch_tpu.ops.flash_attention import _nt, _tn

_LANES = 128
_SUBLANES = 8
_NEG = -1e30         # a masked score: finite, so NEG - NEG is 0, not NaN

# what the forward rule names for a caller's remat policy: the kernel's
# output (flat) and the row statistics
SAVED_NAMES = ("sparse_attn_out", "sparse_attn_lse")


def _last_k(qi, block_q: int, block_k: int):
    """Index of the last K tile at or below the diagonal of Q tile ``qi``."""
    return (qi * block_q + block_q - 1) // block_k


def _first_q(ki, block_q: int, block_k: int):
    """Index of the first Q tile at or below the diagonal of K tile ``ki``."""
    return (ki * block_k) // block_q


def _keep(mask_ref, keys_down: bool = False):
    """The mask tile as booleans, queries down as it lies in HBM, or
    (``keys_down``) turned once for the heads that share it."""
    mask = mask_ref[...].astype(jnp.int32)
    return (mask.T if keys_down else mask) != 0


def _scores(q, k, keep, scale):
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    return jnp.where(keep, s, _NEG)


def fwd_init(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG)
    l_ref[...] = jnp.zeros_like(l_ref)


def fwd_tile(q_ref, k, v, keep, acc_ref, m_ref, l_ref, scale, groups):
    """One K/V tile into the running maximum, sum and output of the
    ``groups`` query heads of a Q tile, on the tile TURNED: keys down,
    queries across, ``keep`` ``[block_k, block_q]`` from wherever the
    kernel has it. A head's maximum and sum are then rows ``[1,
    block_q]``: they reduce down the registers, not across the lanes,
    and rescale the running output ``[d, block_q]`` spread down the
    sublanes. This body and the two around it are shared with
    ``ops/rule_attention.py``."""
    for g in range(groups):
        s = jnp.where(keep, _nt(k, q_ref[g]) * scale, _NEG)
        m_prev, l_prev = m_ref[g][:1], l_ref[g][:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
        l_new = alpha * l_prev + jnp.sum(p, axis=0, keepdims=True)
        acc_ref[g] = acc_ref[g] * alpha + _tn(v, p.astype(v.dtype))
        m_ref[g] = jnp.broadcast_to(m_new, m_ref.shape[1:])
        l_ref[g] = jnp.broadcast_to(l_new, l_ref.shape[1:])


def _head(g, d):
    """Where head ``g`` lies in a ``[block_q, groups * d]`` tile of ``o``
    or of its cotangent: ``d`` whole registers of lanes."""
    return slice(None), pl.ds(g * d, d)


def fwd_finalize(o_ref, lse_ref, acc_ref, m_ref, l_ref, groups):
    """The running output turned back once a Q tile, each head to its
    own lanes of the ``[block_q, groups * d]`` tile, and the log-sum-exp
    one number a row, ``[groups, block_q]``."""
    d = acc_ref.shape[1]
    for g in range(groups):
        l = jnp.maximum(l_ref[g][:1], 1e-20)
        o_ref[_head(g, d)] = (acc_ref[g] / l).T.astype(o_ref.dtype)
        lse_ref[pl.ds(g, 1), :] = m_ref[g][:1] + jnp.log(l)


def dq_tile(q_ref, k, v, keep, do_ref, lse_ref, d_ref, dq_acc, scale,
            groups):
    for g in range(groups):
        s = _scores(q_ref[g], k, keep, scale)
        p = jnp.where(keep, jnp.exp(s - lse_ref[g][:, :1]), 0.0)
        dp = jax.lax.dot_general(
            do_ref[_head(g, v.shape[-1])], v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - d_ref[g][:, :1])
        dq_acc[g] = dq_acc[g] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def dkv_tile(q_ref, k, v, keep, do_ref, lse_ref, d_ref, dk_acc, dv_acc,
             scale, groups):
    for g in range(groups):
        q, do = q_ref[g], do_ref[_head(g, v.shape[-1])]
        s = _scores(q, k, keep, scale)
        p = jnp.where(keep, jnp.exp(s - lse_ref[g][:, :1]), 0.0)
        dv_acc[...] = dv_acc[...] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - d_ref[g][:, :1])
        dk_acc[...] = dk_acc[...] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def _fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref, acc_ref,
                m_ref, l_ref, *, scale, block_q, block_k, n_k, groups):
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        fwd_init(acc_ref, m_ref, l_ref)

    @pl.when(ki <= _last_k(qi, block_q, block_k))
    def _body():
        # the mask lies queries down in HBM: turned once a grid step
        # for the heads that share it
        keep = _keep(mask_ref, keys_down=True)
        fwd_tile(q_ref, k_ref[...], v_ref[...], keep, acc_ref, m_ref, l_ref,
                 scale, groups)

    @pl.when(ki == n_k - 1)
    def _finalize():
        fwd_finalize(o_ref, lse_ref, acc_ref, m_ref, l_ref, groups)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref, d_ref,
                   dq_ref, dq_acc, *, scale, block_q, block_k, n_k, groups):
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(ki <= _last_k(qi, block_q, block_k))
    def _body():
        k, v, keep = k_ref[...], v_ref[...], _keep(mask_ref)
        dq_tile(q_ref, k, v, keep, do_ref, lse_ref, d_ref, dq_acc, scale,
                groups)

    @pl.when(ki == n_k - 1)
    def _finalize():
        dq_ref[...] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref, d_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, scale, block_q,
                    block_k, n_q, groups):
    ki, qi = pl.program_id(2), pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(qi >= _first_q(ki, block_q, block_k))
    def _body():
        k, v, keep = k_ref[...], v_ref[...], _keep(mask_ref)
        dkv_tile(q_ref, k, v, keep, do_ref, lse_ref, d_ref, dk_acc, dv_acc,
                 scale, groups)

    @pl.when(qi == n_q - 1)
    def _finalize():
        dk_ref[...] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _blocks(seq: int) -> tuple:
    """``(block_q, block_k)``: the largest powers of two dividing
    ``seq`` (a multiple of 128) up to 256 x 512. Eight query heads
    share a step, so a Q tile of 256 is 2,048 rows of scores against
    each K tile; an int8 mask tile wants 32 rows or more."""
    def largest(cap):
        b = 1
        while b * 2 <= min(cap, seq) and seq % (b * 2) == 0:
            b *= 2
        return b
    return largest(256), largest(512)


def _check(q5, k4, v4, mask):
    b, hkv, _, t, d = q5.shape
    if k4.shape != v4.shape or k4.shape != (b, hkv, t, d):
        raise ValueError(f"sparse_attention: q {q5.shape}, k {k4.shape}, "
                         f"v {v4.shape} do not go together")
    if mask.shape != (b, t, t) or mask.dtype != jnp.int8:
        raise ValueError(f"sparse_attention: the mask is {mask.dtype}"
                         f"{mask.shape}, not int8{(b, t, t)}")
    if d % _LANES or t % _LANES:
        raise ValueError(
            f"sparse_attention: seq {t} x head_dim {d} cannot be tiled: "
            f"both must be multiples of {_LANES}")


def heads_first(q, k, v, name: str):
    """``q [b, T, h, d]``, ``k`` and ``v [b, T, hkv, d]`` as the kernels
    read them: ``q5 [b, hkv, h // hkv, T, d]``, ``k4`` and ``v4 [b, hkv,
    T, d]``, by transposing copies (the decoder's fused
    ``ops/qk_norm_rope.py`` writes this layout itself)."""
    b, t, h, d = q.shape
    hkv = k.shape[2]
    if h % hkv:
        raise ValueError(f"{name}: {h} query heads are not a multiple of "
                         f"{hkv} key/value heads")
    return (jnp.transpose(q.reshape(b, t, hkv, h // hkv, d), (0, 2, 3, 1, 4)),
            jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2))


def by_head(x, d: int):
    """The flat ``x [b, T, heads * d]`` with its heads as an axis: ``[b,
    T // 8, 8, heads, d]``, the tokens split by the 8 sublanes of a
    register. Elementwise work and sums over a head's ``d`` lanes go
    through this shape: the TPU compiler lays the flat array out in
    tiles of 8 tokens x 128 lanes, so this reshape moves nothing (a
    bitcast: the 8 stays under the heads), where ``[b, T, heads, d]``
    puts the heads on the sublanes and costs a copy of the whole array
    (PERF.md section 6, PR 41)."""
    b, t, f = x.shape
    return x.reshape(b, t // _SUBLANES, _SUBLANES, f // d, d)


def _specs(groups, d, block_q, block_k, q_major: bool):
    """Block specs of the operands every kernel shares. ``q_major``:
    the grid is ``(b, h, qi, ki)``, else ``(b, h, ki, qi)``. A tile
    above the diagonal, which is skipped, maps to the last tile fetched,
    so nothing is copied for it."""
    def order(f):
        return f if q_major else (lambda b, h, ki, qi: f(b, h, qi, ki))

    def kk(qi, ki):
        return jnp.minimum(
            ki, _last_k(qi, block_q, block_k)) if q_major else ki

    def qq(qi, ki):
        return qi if q_major else jnp.maximum(
            qi, _first_q(ki, block_q, block_k))

    q_spec = pl.BlockSpec((None, None, groups, block_q, d), order(
        lambda b, h, qi, ki: (b, h, 0, qq(qi, ki), 0)))
    # o and its cotangent, [b, T, kv_heads * groups * d]: a key/value
    # head's group is a block of lanes
    o_spec = pl.BlockSpec((None, block_q, groups * d), order(
        lambda b, h, qi, ki: (b, qq(qi, ki), h)))
    kv_spec = pl.BlockSpec((None, None, block_k, d), order(
        lambda b, h, qi, ki: (b, h, kk(qi, ki), 0)))
    mask_spec = pl.BlockSpec((None, block_q, block_k), order(
        lambda b, h, qi, ki: (b, qq(qi, ki), kk(qi, ki))))
    row_spec = pl.BlockSpec((None, None, groups, block_q, _LANES), order(
        lambda b, h, qi, ki: (b, h, 0, qq(qi, ki), 0)))
    return q_spec, o_spec, kv_spec, mask_spec, row_spec


def fwd_scratch(groups, d, block_q):
    """The forward kernel's VMEM: the running output ``[d, block_q]`` a
    head, and its running maximum and sum as rows, one register high."""
    return [pltpu.VMEM((groups, d, block_q), jnp.float32),
            pltpu.VMEM((groups, 8, block_q), jnp.float32),
            pltpu.VMEM((groups, 8, block_q), jnp.float32)]


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _fwd(q5, k4, v4, mask):
    b, hkv, groups, t, d = q5.shape
    block_q, block_k = _blocks(t)
    n_q, n_k = t // block_q, t // block_k
    q_spec, o_spec, kv_spec, mask_spec, _ = _specs(
        groups, d, block_q, block_k, q_major=True)
    # the log-sum-exp, one number a row with the sequence along the lanes
    lse_spec = pl.BlockSpec((None, None, groups, block_q),
                            lambda b, h, qi, ki: (b, h, 0, qi))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=d ** -0.5, block_q=block_q,
                          block_k=block_k, n_k=n_k, groups=groups),
        out_shape=[jax.ShapeDtypeStruct((b, t, hkv * groups * d), q5.dtype),
                   jax.ShapeDtypeStruct((b, hkv, groups, t), jnp.float32)],
        grid=(b, hkv, n_q, n_k),
        in_specs=[q_spec, kv_spec, kv_spec, mask_spec],
        out_specs=[o_spec, lse_spec],
        scratch_shapes=fwd_scratch(groups, d, block_q),
        interpret=_interpret(),
        name="sparse_attn_fwd",
    )(q5, k4, v4, mask)


def spread(rows):
    """One float a row, ``[b, kv_heads, G, T]``, spread over a tile's
    lanes: how the backward kernels read a row statistic."""
    return jnp.broadcast_to(rows[..., None], (*rows.shape, _LANES))


def row_statistics(o, lse, do):
    """What the backward kernels read a row: the log-sum-exp ``[b,
    kv_heads, G, T]`` and ``sum(o * do)`` over each head's lanes of the
    flat ``o`` and ``do`` (``[b, T, heads]``, turned to the log-sum-exp's
    layout: one float a row), each spread over a tile's lanes."""
    b, hkv, groups, t = lse.shape
    di = jnp.sum(by_head(o.astype(jnp.float32) * do.astype(jnp.float32),
                         o.shape[-1] // (hkv * groups)), axis=-1)
    di = jnp.transpose(di.reshape(b, t, hkv, groups), (0, 2, 3, 1))
    return spread(lse), spread(di)


def _bwd(q5, k4, v4, mask, o, lse, do):
    b, hkv, groups, t, d = q5.shape
    block_q, block_k = _blocks(t)
    n_q, n_k = t // block_q, t // block_k
    kw = dict(scale=d ** -0.5, block_q=block_q, block_k=block_k,
              groups=groups)
    lse, di = row_statistics(o, lse, do)

    q_spec, o_spec, kv_spec, mask_spec, row_spec = _specs(
        groups, d, block_q, block_k, q_major=True)
    dq5 = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, n_k=n_k, **kw),
        out_shape=jax.ShapeDtypeStruct(q5.shape, q5.dtype),
        grid=(b, hkv, n_q, n_k),
        in_specs=[q_spec, kv_spec, kv_spec, mask_spec, o_spec, row_spec,
                  row_spec],
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((groups, block_q, d), jnp.float32)],
        interpret=_interpret(),
        name="sparse_attn_bwd_dq",
    )(q5, k4, v4, mask, do, lse, di)

    q_spec, o_spec, kv_spec, mask_spec, row_spec = _specs(
        groups, d, block_q, block_k, q_major=False)
    dk4, dv4 = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, n_q=n_q, **kw),
        out_shape=[jax.ShapeDtypeStruct(k4.shape, k4.dtype),
                   jax.ShapeDtypeStruct(v4.shape, v4.dtype)],
        grid=(b, hkv, n_k, n_q),
        in_specs=[q_spec, kv_spec, kv_spec, mask_spec, o_spec, row_spec,
                  row_spec],
        out_specs=[kv_spec, kv_spec],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=_interpret(),
        name="sparse_attn_bwd_dkv",
    )(q5, k4, v4, mask, do, lse, di)
    return dq5, dk4, dv4


@jax.custom_vjp
def sparse_attention_heads_first(q5: jax.Array, k4: jax.Array,
                                 v4: jax.Array, mask: jax.Array) -> jax.Array:
    """:func:`sparse_attention` on operands in the kernels' layout:
    ``q5 [b, kv_heads, G, T, d]``, ``k4`` and ``v4 [b, kv_heads, T,
    d]`` -> ``o [b, T, kv_heads * G * d]``, head ``i`` in lanes ``[i *
    d, (i + 1) * d)``; the cotangents of ``q5``, ``k4`` and ``v4`` come
    back as the backward kernels write them. Nothing is transposed on
    either side."""
    return _forward(q5, k4, v4, mask)[0]


def _forward(q5, k4, v4, mask):
    _check(q5, k4, v4, mask)
    o, lse = _fwd(q5, k4, v4, mask)
    o = checkpoint_name(o, SAVED_NAMES[0])
    lse = checkpoint_name(lse, SAVED_NAMES[1])
    return o, (q5, k4, v4, mask, o, lse)


def _bwd_rule(res, do):
    q5, k4, v4, mask, o, lse = res
    return (*_bwd(q5, k4, v4, mask, o, lse, do.astype(q5.dtype)), None)


sparse_attention_heads_first.defvjp(_forward, _bwd_rule)


def sparse_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     mask: jax.Array) -> jax.Array:
    """``softmax`` attention of each query over the keys its mask row
    selects. ``q`` is ``[b, T, heads, d]``, ``k`` and ``v`` are ``[b,
    T, kv_heads, d]`` (query head ``i`` reads key/value head ``i //
    (heads // kv_heads)``), ``mask`` is int8 ``[b, T, T]``, shared by
    all heads and zero above the diagonal. A query that selects nothing
    gets zeros. No gradient reaches the mask. A thin wrapper: it turns
    its operands heads first and calls
    :func:`sparse_attention_heads_first`, whose flat result reshapes to
    ``q``'s shape with no element moved."""
    return sparse_attention_heads_first(
        *heads_first(q, k, v, "sparse_attention"), mask).reshape(q.shape)

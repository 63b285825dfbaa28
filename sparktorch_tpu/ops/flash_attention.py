"""Fused (flash) attention in Pallas for TPU — forward and backward.

The hot op of the transformer family. One kernel fuses QK^T, the
streaming softmax and the PV contraction, so the (seq x seq) logits
matrix never hits HBM — the classic flash-attention recipe laid out
on the TPU grid, reading and writing the projection's own layout:

- q, k, v (and the output's cotangent) stay ``(batch, seq, heads *
  head_dim)``, which is a reshape of what the qkv projection wrote; a
  block is ``block`` rows of one 128-lane group of that last axis: two
  heads of 64, four of 32, or one head of a multiple of 128. Nothing is
  transposed to ``(batch * heads, seq, head_dim)`` and no head is padded
  when the widths pack (a head width that divides 128 or is a multiple
  of it, and ``heads * head_dim`` a multiple of 128); other widths are
  zero-padded up to ones that do. The outputs are written the same way.
- the heads of a group share the 128 lanes, so a head's operand is the
  block with the other heads' lanes set to zero: a contraction over all
  128 lanes is then that head's own (a head of 64 costs the matrix unit
  the passes of one of 128 either way), and of a product that comes out
  128 lanes wide only the head's own lanes are kept. No lane is sliced.
- forward grid = (batch, lane groups, q_blocks, k_blocks); the innermost
  (k) axis iterates sequentially per TPU core. Where all keys are one
  tile (an encoder's rows) a grid step is a plain softmax. Where they
  are several, VMEM scratch (acc, m, l) persists across k blocks and
  accumulates the streaming softmax, on the tile transposed (keys down,
  queries across) so that a head's running maximum and sum are a row of
  a few vector registers. All matmuls hit the MXU with float32
  accumulation (bf16 inputs fine).
- Causal masking skips whole k-blocks above the diagonal (`@pl.when`),
  and applies the in-block triangle mask on the diagonal blocks.

The backward is the flash-attention-2 recipe, also in Pallas: the
forward additionally emits the per-row logsumexp, and two streaming
kernels recompute p = exp(s - lse) block-by-block in VMEM — dq
accumulates over k blocks, dk/dv accumulate over q blocks — so training
never materializes the (seq x seq) matrix either. The dk/dv kernel works
on the transposed tile too, so each of its four products is a plain or a
transposed-right-hand matmul.

The two row statistics (logsumexp, ``sum(o * do)``) cross HBM as
``(batch, lane groups, heads a group, seq)`` float32, the sequence along
the lanes: one number a row, not 128. ``sum(o * do)`` is made by the dq
kernel from its own blocks of o and do. A kernel whose tile has the
keys down spreads a row of them down the sublanes as it stands; one
whose tile has the queries down (the one-tile forward, dq) turns them
in VMEM.

On non-TPU backends (tests run on the CPU mesh) the kernels run in
Pallas interpret mode, and sequence lengths that don't tile into blocks
of a multiple of 128 fall back to the XLA dense path in both directions.
On a TPU backend an untileable shape is an error that names the shape
and the rule: a caller who asked for the kernel never gets a dense
program.

Each ``pallas_call`` carries a stable ``name`` (``flash_fwd``,
``flash_bwd_dq``, ``flash_bwd_dkv``) — that is how a compiled step's
text is checked for the kernels (``chip_smoke.py``,
``tests/test_chip_compile.py``).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from sparktorch_tpu.ops.attention import dense_attention

_LANES = 128  # TPU lane width: last-dim tiling unit


class _Packing(NamedTuple):
    """How ``heads`` of ``head_dim`` lie in lane groups: the counts
    after zero-padding (equal to the true ones when the widths pack),
    and the heads of one group."""
    heads: int
    head_dim: int
    per_group: int

    @property
    def group_lanes(self) -> int:
        return self.per_group * self.head_dim


def _packing(heads: int, head_dim: int) -> _Packing:
    if head_dim >= _LANES:
        return _Packing(heads, -(-head_dim // _LANES) * _LANES, 1)
    d = 1
    while d < head_dim:
        d *= 2
    per_group = _LANES // d
    return _Packing(-(-heads // per_group) * per_group, d, per_group)


def _pack(x, pk: _Packing):
    """``(b, seq, heads, head_dim)`` -> ``(b, seq, lanes)``: a reshape,
    after zeros where the widths do not pack."""
    b, s, h, d = x.shape
    if (h, d) != (pk.heads, pk.head_dim):
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pk.heads - h),
                        (0, pk.head_dim - d)))
    return x.reshape(b, s, pk.heads * pk.head_dim)


def _unpack(x, pk: _Packing, h: int, d: int):
    b, s, _ = x.shape
    return x.reshape(b, s, pk.heads, pk.head_dim)[:, :, :h, :d]


def _head_masks(pk: _Packing, down: bool = False):
    """One mask a head of the group over the group's lanes, ``(1,
    lanes)``, or (``down``: an array with the lanes turned down the
    sublanes) ``(lanes, 1)``; None where a group is one head."""
    if pk.per_group == 1:
        return None
    shape = (pk.group_lanes, 1) if down else (1, pk.group_lanes)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 0 if down else 1)
    return [(lane >= j * pk.head_dim) & (lane < (j + 1) * pk.head_dim)
            for j in range(pk.per_group)]


def _own(x, masks, j):
    """``x`` with the lanes of every head but ``j`` set to zero."""
    return x if masks is None else jnp.where(masks[j], x, jnp.zeros_like(x))


def _by_head(xs, masks):
    """Lane by lane, the entry of the head that owns the lane."""
    if masks is None:
        return xs[0]
    out = xs[-1]
    for j in range(len(xs) - 2, -1, -1):
        out = jnp.where(masks[j], xs[j], out)
    return out


def _nt(a, b):
    """``a @ b.T`` with float32 accumulation."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _nn(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _tn(a, b):
    """``a.T @ b`` with float32 accumulation."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _causal_keep(qi, ki, block_q, block_k, keys_down: bool):
    """The tile's triangle: query position >= key position. Queries go
    down the tile, or (``keys_down``) across it."""
    shape = (block_k, block_q) if keys_down else (block_q, block_k)
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, shape, 1 if keys_down else 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, shape, 0 if keys_down else 1)
    return q_pos >= k_pos


def _column(row, block_q):
    """A ``(1, block_q)`` row of statistics as a column spread over 128
    lanes, ``(block_q, 128)``: down the sublanes, then turned."""
    return jnp.broadcast_to(row, (_LANES, block_q)).T


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, *rest, scale: float,
                causal: bool, block_q: int, block_k: int, n_k: int,
                pk: _Packing, with_lse: bool):
    """``rest``: the logsumexp's block if ``with_lse``, then, where the
    keys come in more than one tile, the running output ``(lanes,
    block_q)`` and the running maximum and sum ``(heads a group, 8,
    block_q)``."""
    lse_ref = rest[0] if with_lse else None
    qi, ki = pl.program_id(2), pl.program_id(3)
    q, k, v = q_ref[...], k_ref[...], v_ref[...]

    if n_k == 1:
        # All keys in one tile (an encoder's rows): a plain softmax, the
        # queries down the tile, no running statistics. 0.39 ms a call
        # at BERT-base's heads and 32 rows of 512 against 0.48 for the
        # streaming form below (v5e, PR 33).
        masks = _head_masks(pk)
        keep = (_causal_keep(qi, ki, block_q, block_k, False)
                if causal else None)
        sums, pvs = [], []
        for j in range(pk.per_group):
            s = _nt(_own(q, masks, j), k) * scale  # (block_q, block_k)
            if causal:
                s = jnp.where(keep, s, -jnp.inf)
            m = jnp.max(s, axis=-1, keepdims=True)
            p = jnp.exp(s - m)
            l = jnp.sum(p, axis=-1, keepdims=True)
            # 128 lanes wide: the head's own lanes hold its p @ v
            pvs.append(_nn(p.astype(v.dtype), v))
            sums.append(l)
            if with_lse:
                lse_ref[pl.ds(j, 1), :] = jnp.broadcast_to(
                    m + jnp.log(l), (block_q, _LANES)).T[:1]
        o_ref[...] = (_by_head(pvs, masks) / _by_head(sums, masks)
                      ).astype(o_ref.dtype)
        return

    # Keys in several tiles: the streaming softmax on the tile
    # transposed (keys down, queries across), so the running maximum
    # and sum of a head are a ROW: their updates are a few vector
    # registers, where columns of them cost as much as the tile's own
    # passes (0.91 ms a call against 0.48, same shape as above), and the
    # logsumexp leaves as it lies in HBM.
    acc_ref, m_ref, l_ref = rest[-3:]
    masks = _head_masks(pk)
    down = _head_masks(pk, down=True)
    spread = lambda row: jnp.broadcast_to(row, (pk.group_lanes, block_q))

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Causal: whole k-block strictly above the diagonal contributes
    # nothing — skip it (the big win for long sequences).
    run = (qi * block_q + block_q - 1 >= ki * block_k) if causal else True

    @pl.when(run)
    def _body():
        keep = (_causal_keep(qi, ki, block_q, block_k, True)
                if causal else None)
        alphas, pvs = [], []
        for j in range(pk.per_group):
            s = _nt(_own(k, masks, j), q) * scale  # (block_k, block_q)
            if causal:
                s = jnp.where(keep, s, -jnp.inf)
            m_prev, l_prev = m_ref[j][:1], l_ref[j][:1]  # (1, block_q)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
            # The first tile holds key 0, which every query sees, so
            # m_new is finite from there on and exp(-inf - m_new) == 0
            # handles the first tile's m_prev and every masked score.
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=0, keepdims=True)
            pvs.append(_tn(v, p.astype(v.dtype)))  # (lanes, block_q)
            alphas.append(spread(alpha))
            m_ref[j] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[j] = jnp.broadcast_to(l_new, l_ref.shape[1:])
        acc_ref[...] = (acc_ref[...] * _by_head(alphas, down)
                        + _by_head(pvs, down))

    @pl.when(ki == n_k - 1)
    def _finalize():
        ls = [jnp.maximum(l_ref[j][:1], 1e-20) for j in range(pk.per_group)]
        o_ref[...] = (acc_ref[...] / _by_head([spread(l) for l in ls], down)
                      ).T.astype(o_ref.dtype)
        if with_lse:
            for j, l in enumerate(ls):
                lse_ref[pl.ds(j, 1), :] = m_ref[j][:1] + jnp.log(l)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dq_ref,
                   di_ref, dq_acc, lse_col, di_col, *, scale: float,
                   causal: bool, block_q: int, block_k: int, n_k: int,
                   pk: _Packing):
    """dq, and on the way ``sum(o * do)`` a row, which the dk/dv kernel
    reads: out of this kernel's own blocks of o and do, so no pass over
    them outside the kernels makes it."""
    qi, ki = pl.program_id(2), pl.program_id(3)
    masks = _head_masks(pk)

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        od = o_ref[...].astype(jnp.float32) * do_ref[...].astype(jnp.float32)
        for j in range(pk.per_group):
            lse_col[j] = _column(lse_ref[pl.ds(j, 1), :], block_q)
            di = jnp.sum(_own(od, masks, j), axis=-1, keepdims=True)
            di_col[j] = jnp.broadcast_to(di, di_col.shape[1:])
            di_ref[pl.ds(j, 1), :] = di_col[j].T[:1]

    run = (qi * block_q + block_q - 1 >= ki * block_k) if causal else True

    @pl.when(run)
    def _body():
        q, k, v, do = q_ref[...], k_ref[...], v_ref[...], do_ref[...]
        keep = (_causal_keep(qi, ki, block_q, block_k, False)
                if causal else None)
        dqs = []
        for j in range(pk.per_group):
            s = _nt(_own(q, masks, j), k) * scale
            if causal:
                s = jnp.where(keep, s, -jnp.inf)
            p = jnp.exp(s - lse_col[j][:, :1])  # exact softmax block, VMEM-only
            dp = _nt(_own(do, masks, j), v)
            ds = p * (dp - di_col[j][:, :1])
            dqs.append(_nn(ds.astype(k.dtype), k))
        dq_acc[...] = dq_acc[...] + _by_head(dqs, masks)

    @pl.when(ki == n_k - 1)
    def _finalize():
        dq_ref[...] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref,
                    dv_ref, dk_acc, dv_acc, *, scale: float, causal: bool,
                    block_q: int, block_k: int, n_q: int, pk: _Packing):
    ki, qi = pl.program_id(2), pl.program_id(3)
    masks = _head_masks(pk)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    run = (qi * block_q + block_q - 1 >= ki * block_k) if causal else True

    @pl.when(run)
    def _body():
        q, k, v, do = q_ref[...], k_ref[...], v_ref[...], do_ref[...]
        keep = (_causal_keep(qi, ki, block_q, block_k, True)
                if causal else None)
        dks, dvs = [], []
        for j in range(pk.per_group):
            # the tile transposed: keys down, queries across, so a row
            # of statistics spreads down the sublanes as it stands
            s = _nt(_own(k, masks, j), q) * scale  # (block_k, block_q)
            if causal:
                s = jnp.where(keep, s, -jnp.inf)
            p = jnp.exp(s - lse_ref[pl.ds(j, 1), :])
            dvs.append(_nn(p.astype(do.dtype), do))
            dp = _nt(_own(v, masks, j), do)
            ds = p * (dp - di_ref[pl.ds(j, 1), :])
            dks.append(_nn(ds.astype(q.dtype), q))
        dk_acc[...] = dk_acc[...] + _by_head(dks, masks)
        dv_acc[...] = dv_acc[...] + _by_head(dvs, masks)

    @pl.when(qi == n_q - 1)
    def _finalize():
        dk_ref[...] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _tileable(s_q: int, s_k: int, block_q: int, block_k: int) -> bool:
    """Kernel path only for sequences that split into blocks of whole
    lane widths: a block of queries is the lanes of its row statistics,
    a block of keys the lanes of the scores."""
    return (
        s_q % block_q == 0 and s_k % block_k == 0
        and block_q % _LANES == 0 and block_k % _LANES == 0
    )


def _auto_block(s: int, d_pad: int = _LANES) -> int:
    """Default block size: largest power of two dividing ``s`` up to a
    cap chosen from the shape. Swept on a v5e chip: at seq 8192,
    1024x1024 blocks run the fwd+bwd chain ~20% faster than 512x512
    (fewer grid steps); at seq <= 4096 the 512 cap wins for causal
    attention (smaller blocks skip more below-diagonal work and waste
    less of the diagonal block's masked triangle). The cap also
    shrinks with the lanes of a block (``d_pad``: a head wider than
    128) so the backward kernels' VMEM residency (s/p/dp blocks +
    double-buffered (block, d_pad) inputs) stays within the old
    512 x 128-lane budget."""
    cap = 1024 if s >= 8192 else 512
    cap = max(_LANES, cap * _LANES // max(_LANES, d_pad))
    b = 1
    while b * 2 <= min(cap, s) and s % (b * 2) == 0:
        b *= 2
    return b


def _blocks(s_q: int, s_k: int, pk: _Packing, block_q, block_k):
    block_q = (_auto_block(s_q, pk.group_lanes) if block_q is None
               else min(block_q, s_q))
    block_k = (_auto_block(s_k, pk.group_lanes) if block_k is None
               else min(block_k, s_k))
    return block_q, block_k


def can_tile(s_q: int, s_k: int, heads: int, head_dim: int) -> bool:
    """Whether the kernels take this shape as it stands: the default
    blocks tile both sequences and the heads pack into lane groups with
    no padding. What a caller who may choose (``attn_impl='auto'``)
    asks; a caller who names the kernel gets the padding."""
    pk = _packing(heads, head_dim)
    return ((pk.heads, pk.head_dim) == (heads, head_dim)
            and _tileable(s_q, s_k, *_blocks(s_q, s_k, pk, None, None)))


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _specs(pk: _Packing, block_q: int, block_k: int, q_major: bool):
    """Block specs of a q-like operand, a k-like operand and a row
    statistic. ``q_major``: the grid is ``(b, group, qi, ki)``, else
    ``(b, group, ki, qi)``."""
    def order(f):
        return f if q_major else (lambda b, g, ki, qi: f(b, g, qi, ki))

    lanes = pk.group_lanes
    q_spec = pl.BlockSpec((None, block_q, lanes),
                          order(lambda b, g, qi, ki: (b, qi, g)))
    k_spec = pl.BlockSpec((None, block_k, lanes),
                          order(lambda b, g, qi, ki: (b, ki, g)))
    row_spec = pl.BlockSpec((None, None, pk.per_group, block_q),
                            order(lambda b, g, qi, ki: (b, g, 0, qi)))
    return q_spec, k_spec, row_spec


# The kernels' calls are jitted, every argument that is no array static:
# the layers of a model then share ONE trace and ONE lowering of each
# kernel where each layer would make its own (BERT-base: 36 kernels
# traced and lowered a step program, seconds of every job's start).
_STATIC = ("pk", "scale", "causal", "block_q", "block_k", "interpret")


@functools.partial(jax.jit, static_argnames=(*_STATIC, "with_lse"))
def _flash_fwd(q3, k3, v3, *, pk: _Packing, scale: float, causal: bool,
               block_q: int, block_k: int, interpret: bool, with_lse: bool):
    """q3/k3/v3: (b, seq, lanes). Returns out3 or (out3, lse)."""
    b, s_q, lanes = q3.shape
    n_q, n_k = s_q // block_q, k3.shape[1] // block_k
    q_spec, k_spec, row_spec = _specs(pk, block_q, block_k, q_major=True)
    out_shape = [jax.ShapeDtypeStruct(q3.shape, q3.dtype)]
    out_specs = [q_spec]
    if with_lse:
        out_shape.append(jax.ShapeDtypeStruct(
            (b, lanes // pk.group_lanes, pk.per_group, s_q), jnp.float32))
        out_specs.append(row_spec)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, n_k=n_k, pk=pk,
                          with_lse=with_lse),
        out_shape=out_shape,
        grid=(b, lanes // pk.group_lanes, n_q, n_k),
        in_specs=[q_spec, k_spec, k_spec],
        out_specs=out_specs,
        scratch_shapes=[] if n_k == 1 else [
            pltpu.VMEM((pk.group_lanes, block_q), jnp.float32),
            pltpu.VMEM((pk.per_group, 8, block_q), jnp.float32),
            pltpu.VMEM((pk.per_group, 8, block_q), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(q3, k3, v3)
    return out if with_lse else out[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
) -> jax.Array:
    """Fused attention. Shapes (batch, seq, heads, head_dim) — same
    contract as :func:`dense_attention`. Heads whose width divides 128
    or is a multiple of it (and fill whole 128-lane groups) are read
    where they lie; other widths are zero-padded inside (free for the
    math: zero dims add nothing to QK^T, and padded output dims and
    heads are sliced away). ``block_q``/``block_k`` default to the
    largest power of two up to 1024 dividing the respective sequence
    length.
    """
    out, _ = _flash_impl(q, k, v, causal, block_q, block_k, with_lse=False)
    return out


def _flash_impl(q, k, v, causal, block_q, block_k, with_lse):
    b, s_q, h, d = q.shape
    s_k = k.shape[1]
    pk = _packing(h, d)
    block_q, block_k = _blocks(s_q, s_k, pk, block_q, block_k)
    if not _tileable(s_q, s_k, block_q, block_k):
        if not _interpret():
            raise ValueError(
                f"flash_attention: q seq {s_q} / k seq {s_k} cannot be "
                f"tiled (blocks {block_q} x {block_k}): each sequence "
                "length must be a multiple of its block, and each block "
                f"a multiple of {_LANES}. Pad the sequence or use "
                "attn_impl='dense'."
            )
        return dense_attention(q, k, v, causal=causal), None

    # Softmax scale from the TRUE head_dim; zero-padding the lane dim
    # does not change QK^T, so no rescaling trick is needed.
    out = _flash_fwd(
        _pack(q, pk), _pack(k, pk), _pack(v, pk), pk=pk, scale=d ** -0.5,
        causal=causal, block_q=block_q, block_k=block_k,
        interpret=_interpret(), with_lse=with_lse,
    )
    out3, lse = out if with_lse else (out, None)
    return _unpack(out3, pk, h, d), lse


@functools.partial(jax.jit, static_argnames=_STATIC)
def _flash_bwd(q3, k3, v3, o3, do3, lse, *, pk: _Packing, scale: float,
               causal: bool, block_q: int, block_k: int, interpret: bool):
    """dq3, dk3, dv3 of operands ``(b, seq, lanes)`` and the forward's
    logsumexp."""
    b, s_q, lanes = q3.shape
    n_q, n_k = s_q // block_q, k3.shape[1] // block_k
    groups = lanes // pk.group_lanes
    kw = dict(scale=scale, causal=causal, block_q=block_q, block_k=block_k,
              pk=pk)
    rows = (pk.per_group, block_q, _LANES)

    # D_i = dO_i . O_i, one float32 a row laid out as the logsumexp is,
    # comes out of the dq kernel.
    q_spec, k_spec, row_spec = _specs(pk, block_q, block_k, q_major=True)
    dq3, di = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, n_k=n_k, **kw),
        out_shape=[jax.ShapeDtypeStruct(q3.shape, q3.dtype),
                   jax.ShapeDtypeStruct(lse.shape, jnp.float32)],
        grid=(b, groups, n_q, n_k),
        in_specs=[q_spec, k_spec, k_spec, q_spec, q_spec, row_spec],
        out_specs=[q_spec, row_spec],
        scratch_shapes=[pltpu.VMEM((block_q, pk.group_lanes), jnp.float32),
                        pltpu.VMEM(rows, jnp.float32),
                        pltpu.VMEM(rows, jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(q3, k3, v3, o3, do3, lse)

    q_spec, k_spec, row_spec = _specs(pk, block_q, block_k, q_major=False)
    dk3, dv3 = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, n_q=n_q, **kw),
        out_shape=[jax.ShapeDtypeStruct(k3.shape, k3.dtype),
                   jax.ShapeDtypeStruct(v3.shape, v3.dtype)],
        grid=(b, groups, n_k, n_q),
        in_specs=[q_spec, k_spec, k_spec, q_spec, row_spec, row_spec],
        out_specs=[k_spec, k_spec],
        scratch_shapes=[pltpu.VMEM((block_k, pk.group_lanes), jnp.float32),
                        pltpu.VMEM((block_k, pk.group_lanes), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q3, k3, v3, do3, lse, di)
    return dq3, dk3, dv3


def _flash_fwd_rule(q, k, v, causal, block_q, block_k):
    out, lse = _flash_impl(q, k, v, causal, block_q, block_k, with_lse=True)
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(causal, block_q, block_k, res, g):
    q, k, v, out, lse = res
    if lse is None:  # dense fallback took the forward too
        _, vjp = jax.vjp(
            lambda q, k, v: dense_attention(q, k, v, causal=causal), q, k, v
        )
        return vjp(g)
    _, s_q, h, d = q.shape
    pk = _packing(h, d)
    block_q, block_k = _blocks(s_q, k.shape[1], pk, block_q, block_k)
    grads = _flash_bwd(
        *(_pack(x, pk) for x in (q, k, v, out, g)), lse, pk=pk,
        scale=d ** -0.5, causal=causal, block_q=block_q, block_k=block_k,
        interpret=_interpret())
    return tuple(_unpack(x3, pk, h, d) for x3 in grads)


flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)

"""Fused (flash) attention in Pallas for TPU — forward and backward.

The hot op of the transformer family. One kernel fuses QK^T, the
streaming softmax and the PV contraction, so the (seq x seq) logits
matrix never hits HBM — the classic flash-attention recipe laid out
on the TPU grid:

- forward grid = (batch*heads, q_blocks, k_blocks); the innermost (k)
  axis iterates sequentially per TPU core, so VMEM scratch (acc, m, l)
  persists across k blocks and accumulates the streaming softmax.
- Q/K/V blocks stream HBM -> VMEM via BlockSpecs; both matmuls hit
  the MXU with float32 accumulation (bf16 inputs fine).
- Causal masking skips whole k-blocks above the diagonal
  (`@pl.when`), and applies the in-block triangle mask on the
  diagonal blocks.

The backward is the flash-attention-2 recipe, also in Pallas: the
forward additionally emits the per-row logsumexp, and two streaming
kernels recompute p = exp(s - lse) block-by-block in VMEM —
dq accumulates over k blocks, dk/dv accumulate over q blocks — so
training never materializes the (seq x seq) matrix either. (The
round-1 version recomputed the backward through the dense path;
this closes that gap.)

On non-TPU backends (tests run on the CPU mesh) the kernels run in
Pallas interpret mode, and shapes that don't tile onto (8, 128) TPU
blocks fall back to the XLA dense path in both directions. On a TPU
backend an untileable shape is an error that names the shape and the
rule: a caller who asked for the kernel never gets a dense program.

Each ``pallas_call`` carries a stable ``name`` (``flash_fwd``,
``flash_bwd_dq``, ``flash_bwd_dkv``) — that is how a compiled step's
text is checked for the kernels (``chip_smoke.py``,
``tests/test_chip_compile.py``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from sparktorch_tpu.ops.attention import dense_attention

_LANES = 128  # TPU lane width: last-dim tiling unit


def _fwd_body(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
              *, scale: float, causal: bool, block_q: int, block_k: int,
              n_k: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[:] = jnp.zeros_like(l_ref)

    # Causal: whole k-block strictly above the diagonal contributes
    # nothing — skip it (the big win for long sequences).
    run = (qi * block_q + block_q - 1 >= ki * block_k) if causal else True

    @pl.when(run)
    def _body():
        q = q_ref[0]  # (block_q, d)
        k = k_ref[0]  # (block_k, d)
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (block_q, block_k)
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(q_pos >= k_pos, s, -jnp.inf)

        m_prev = m_ref[:, :1]  # (block_q, 1)
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # Processed blocks always contain >=1 unmasked entry per row
        # (above-diagonal blocks were skipped), so m_new is finite and
        # exp(-inf - m_new) == 0 handles the first block's m_prev.
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[:] = acc_ref[:] * alpha + pv
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == n_k - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, :1], 1e-20)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)
        if lse_ref is not None:
            lse = m_ref[:, :1] + jnp.log(l)
            lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, **kw):
    _fwd_body(q_ref, k_ref, v_ref, o_ref, None, acc_ref, m_ref, l_ref, **kw)


def _fwd_kernel_lse(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref,
                    l_ref, **kw):
    _fwd_body(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, **kw)


def _flash_fwd(q3, k3, v3, *, scale: float, causal: bool, block_q: int,
               block_k: int, interpret: bool, with_lse: bool):
    """q3/k3/v3: (bh, seq, d_padded). Returns out3 or (out3, lse3)."""
    bh, s_q, d = q3.shape
    s_k = k3.shape[1]
    n_q = s_q // block_q
    n_k = s_k // block_k

    kw = dict(scale=scale, causal=causal, block_q=block_q, block_k=block_k,
              n_k=n_k)
    grid = (bh, n_q, n_k)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, qi, ki: (b, ki, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, qi, ki: (b, ki, 0)),
    ]
    o_spec = pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0))
    lse_spec = pl.BlockSpec((1, block_q, _LANES), lambda b, qi, ki: (b, qi, 0))
    scratch = [
        pltpu.VMEM((block_q, d), jnp.float32),
        pltpu.VMEM((block_q, _LANES), jnp.float32),
        pltpu.VMEM((block_q, _LANES), jnp.float32),
    ]
    if with_lse:
        return pl.pallas_call(
            functools.partial(_fwd_kernel_lse, **kw),
            out_shape=[
                jax.ShapeDtypeStruct((bh, s_q, d), q3.dtype),
                jax.ShapeDtypeStruct((bh, s_q, _LANES), jnp.float32),
            ],
            grid=grid,
            in_specs=in_specs,
            out_specs=[o_spec, lse_spec],
            scratch_shapes=scratch,
            interpret=interpret,
            name="flash_fwd",
        )(q3, k3, v3)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, **kw),
        out_shape=jax.ShapeDtypeStruct((bh, s_q, d), q3.dtype),
        grid=grid,
        in_specs=in_specs,
        out_specs=o_spec,
        scratch_shapes=scratch,
        interpret=interpret,
        name="flash_fwd",
    )(q3, k3, v3)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, dq_ref,
                   dq_acc, *, scale: float, causal: bool, block_q: int,
                   block_k: int, n_k: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    run = (qi * block_q + block_q - 1 >= ki * block_k) if causal else True

    @pl.when(run)
    def _body():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(q_pos >= k_pos, s, -jnp.inf)
        p = jnp.exp(s - lse_ref[0][:, :1])  # exact softmax block, VMEM-only
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - d_ref[0][:, :1])
        dq_acc[:] = dq_acc[:] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ki == n_k - 1)
    def _finalize():
        dq_ref[0] = (dq_acc[:] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, dk_ref,
                    dv_ref, dk_acc, dv_acc, *, scale: float, causal: bool,
                    block_q: int, block_k: int, n_q: int):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run = (qi * block_q + block_q - 1 >= ki * block_k) if causal else True

    @pl.when(run)
    def _body():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (block_q, block_k)
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(q_pos >= k_pos, s, -jnp.inf)
        p = jnp.exp(s - lse_ref[0][:, :1])
        # dv += p^T @ do — contract the q axis, no explicit transpose.
        dv_acc[:] = dv_acc[:] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - d_ref[0][:, :1])
        dk_acc[:] = dk_acc[:] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(qi == n_q - 1)
    def _finalize():
        dk_ref[0] = (dk_acc[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _tileable(s_q: int, s_k: int, block_q: int, block_k: int) -> bool:
    """Kernel path only for shapes that land on TPU (sublane, lane)
    tiles: block_q rows of 8, block_k lanes of 128."""
    return (
        s_q % block_q == 0 and s_k % block_k == 0
        and block_q % 8 == 0 and block_k % _LANES == 0
    )


def _auto_block(s: int, d_pad: int = _LANES) -> int:
    """Default block size: largest power of two dividing ``s`` up to a
    cap chosen from the shape. Swept on a v5e chip: at seq 8192,
    1024x1024 blocks run the fwd+bwd chain ~20% faster than 512x512
    (fewer grid steps); at seq <= 4096 the 512 cap wins for causal
    attention (smaller blocks skip more below-diagonal work and waste
    less of the diagonal block's masked triangle). The cap also
    shrinks with the padded head_dim so the backward kernels' VMEM
    residency (s/p/dp blocks + double-buffered (block, d_pad) inputs)
    stays within the old 512 x 128-lane budget."""
    cap = 1024 if s >= 8192 else 512
    cap = max(_LANES, cap * _LANES // max(_LANES, d_pad))
    b = 1
    while b * 2 <= min(cap, s) and s % (b * 2) == 0:
        b *= 2
    return b


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
    contract as :func:`dense_attention`. ``head_dim`` is zero-padded
    to the 128-lane width inside (free for the math: zero dims add
    nothing to QK^T, and padded output dims are sliced away).
    ``block_q``/``block_k`` default to the largest power of two up to
    1024 dividing the respective sequence length.
    """
    out, _ = _flash_impl(q, k, v, causal, block_q, block_k, with_lse=False)
    return out


def _to3(x, b, h, d):
    x = jnp.swapaxes(x, 1, 2).reshape(b * h, x.shape[1], d)
    if d % _LANES:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, _LANES - d % _LANES)))
    return x


def _from3(x3, b, h, d):
    x = x3[:, :, :d].reshape(b, h, -1, d)
    return jnp.swapaxes(x, 1, 2)


def _flash_impl(q, k, v, causal, block_q, block_k, with_lse):
    b, s_q, h, d = q.shape
    s_k = k.shape[1]
    d_pad = d if d % _LANES == 0 else d + (_LANES - d % _LANES)
    block_q = _auto_block(s_q, d_pad) if block_q is None else min(block_q, s_q)
    block_k = _auto_block(s_k, d_pad) if block_k is None else min(block_k, s_k)
    interpret = jax.default_backend() != "tpu"
    if not _tileable(s_q, s_k, block_q, block_k):
        if not interpret:
            raise ValueError(
                f"flash_attention: q seq {s_q} / k seq {s_k} cannot be "
                f"tiled (blocks {block_q} x {block_k}): each sequence "
                "length must be a multiple of its block, block_q a "
                f"multiple of 8 and block_k of {_LANES}. Pad the "
                "sequence or use attn_impl='dense'."
            )
        return dense_attention(q, k, v, causal=causal), None

    # Softmax scale from the TRUE head_dim; zero-padding the lane dim
    # does not change QK^T, so no rescaling trick is needed.
    scale = d ** -0.5
    out3 = _flash_fwd(
        _to3(q, b, h, d), _to3(k, b, h, d), _to3(v, b, h, d),
        scale=scale, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret, with_lse=with_lse,
    )
    if with_lse:
        out3, lse3 = out3
        # Keep only one lane in the residual: the kernel wrote lse
        # broadcast across all 128 lanes, and holding that from forward
        # to backward would pin a 128x-redundant tensor in HBM.
        return _from3(out3, b, h, d), lse3[:, :, :1]
    return _from3(out3, b, h, d), None


def _flash_bwd_impl(q, k, v, out, lse3, g, causal, block_q, block_k):
    b, s_q, h, d = q.shape
    s_k = k.shape[1]
    d_pad = d if d % _LANES == 0 else d + (_LANES - d % _LANES)
    block_q = _auto_block(s_q, d_pad) if block_q is None else min(block_q, s_q)
    block_k = _auto_block(s_k, d_pad) if block_k is None else min(block_k, s_k)
    scale = d ** -0.5
    interpret = jax.default_backend() != "tpu"

    q3 = _to3(q, b, h, d)
    k3 = _to3(k, b, h, d)
    v3 = _to3(v, b, h, d)
    do3 = _to3(g, b, h, d)
    o3 = _to3(out, b, h, d)
    bh, _, d_pad = q3.shape
    n_q = s_q // block_q
    n_k = s_k // block_k

    # D_i = dO_i . O_i (padded dims are zero, so padding is harmless).
    di = jnp.sum(o3.astype(jnp.float32) * do3.astype(jnp.float32), axis=-1)
    di3 = jnp.broadcast_to(di[..., None], (bh, s_q, _LANES))
    lse3 = jnp.broadcast_to(lse3, (bh, s_q, _LANES))  # single-lane residual

    row_spec = pl.BlockSpec((1, block_q, _LANES), lambda bb, qi, ki: (bb, qi, 0))
    dq3 = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, n_k=n_k),
        out_shape=jax.ShapeDtypeStruct((bh, s_q, d_pad), q3.dtype),
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d_pad), lambda bb, qi, ki: (bb, qi, 0)),
            pl.BlockSpec((1, block_k, d_pad), lambda bb, qi, ki: (bb, ki, 0)),
            pl.BlockSpec((1, block_k, d_pad), lambda bb, qi, ki: (bb, ki, 0)),
            pl.BlockSpec((1, block_q, d_pad), lambda bb, qi, ki: (bb, qi, 0)),
            row_spec,
            row_spec,
        ],
        out_specs=pl.BlockSpec((1, block_q, d_pad), lambda bb, qi, ki: (bb, qi, 0)),
        scratch_shapes=[pltpu.VMEM((block_q, d_pad), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(q3, k3, v3, do3, lse3, di3)

    row_spec_kv = pl.BlockSpec((1, block_q, _LANES), lambda bb, ki, qi: (bb, qi, 0))
    dk3, dv3 = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, n_q=n_q),
        out_shape=[
            jax.ShapeDtypeStruct((bh, s_k, d_pad), k3.dtype),
            jax.ShapeDtypeStruct((bh, s_k, d_pad), v3.dtype),
        ],
        grid=(bh, n_k, n_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d_pad), lambda bb, ki, qi: (bb, qi, 0)),
            pl.BlockSpec((1, block_k, d_pad), lambda bb, ki, qi: (bb, ki, 0)),
            pl.BlockSpec((1, block_k, d_pad), lambda bb, ki, qi: (bb, ki, 0)),
            pl.BlockSpec((1, block_q, d_pad), lambda bb, ki, qi: (bb, qi, 0)),
            row_spec_kv,
            row_spec_kv,
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d_pad), lambda bb, ki, qi: (bb, ki, 0)),
            pl.BlockSpec((1, block_k, d_pad), lambda bb, ki, qi: (bb, ki, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d_pad), jnp.float32),
            pltpu.VMEM((block_k, d_pad), jnp.float32),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q3, k3, v3, do3, lse3, di3)

    return (
        _from3(dq3, b, h, d).astype(q.dtype),
        _from3(dk3, b, h, d).astype(k.dtype),
        _from3(dv3, b, h, d).astype(v.dtype),
    )


def _flash_fwd_rule(q, k, v, causal, block_q, block_k):
    out, lse3 = _flash_impl(q, k, v, causal, block_q, block_k, with_lse=True)
    return out, (q, k, v, out, lse3)


def _flash_bwd_rule(causal, block_q, block_k, res, g):
    q, k, v, out, lse3 = res
    if lse3 is None:  # dense fallback took the forward too
        _, vjp = jax.vjp(
            lambda q, k, v: dense_attention(q, k, v, causal=causal), q, k, v
        )
        return vjp(g)
    return _flash_bwd_impl(q, k, v, out, lse3, g, causal, block_q, block_k)


flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)

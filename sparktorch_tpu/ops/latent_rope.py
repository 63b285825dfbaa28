"""What latent attention does to its products before its kernels read
them, as ONE pass over HBM each way, in Pallas for TPU: the rotary step
on the rotary part of every query head and of the one shared key, the
cast, and the turn into the layout of ``ops/latent_attention.py``.

The caller lays the WEIGHTS out so that the float32 products leave
their einsums in whole registers (a pass over a layer's weights, not
over its activations): ``xq [b, T, heads * d_qk]`` with a head's ``d_qk``
lanes ``[nope ; rope ; zeros]``, ``xkv [b, T, heads * (nope + d_v)]``
with a head's ``[k nope ; v]``, and the shared rotary key ``xkr [b, T,
rope slot]`` as ``[rope ; zeros]``; ``nope``, ``d_v`` and the rope slot
(``d_qk - nope``) are multiples of 128. The rotary dims arrive
DE-INTERLEAVED (the caller permutes the weights' columns: even dims
first, then odd), so that the pair ``(2j, 2j + 1)`` an interleaved table
turns lies ``half`` lanes apart and the rotation is the one
``ops/qk_norm_rope.py`` makes, by halves: ``y = x * cos + swap(x) *
sin`` on the whole slot with that file's tables (``tables``: cosines
twice and 1, ``-sin``, ``+sin`` and 0) and lane rolls (``_swap``).
Queries and keys are permuted alike, so every score is what the
interleaved rotation gives.

- :func:`latent_rope` returns ``q5 [b, heads, 1, T, d_qk]``, ``k4 [b,
  heads, T, d_qk]`` (each head's own ``nope`` lanes, then the ONE turned
  rotary key, written for every head) and ``v4 [b, heads, T, d_v]`` in
  the compute dtype. A grid step is ``(row, token tile, head)``; the
  table's tile and the shared key's are fetched once a token tile.
- Backward, the same grid: it reads ``dq5`` / ``dk4`` / ``dv4`` as the
  attention kernels leave them and writes the products' cotangents
  float32 once; the shared key's is summed over the heads in VMEM (the
  head is the grid's last axis). The rotation has no parameter and no
  norm, so nothing but the tables is kept between the passes.

``pallas_call`` names: ``latent_rope_fwd``, ``latent_rope_bwd``. Off the
TPU they run in interpret mode. A shape that does not tile is an error
everywhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from sparktorch_tpu.ops.qk_norm_rope import _swap, _token_tile
from sparktorch_tpu.ops.sparse_attention import _LANES, _interpret


def _fwd_kernel(cos_ref, sin_ref, xq_ref, xkv_ref, xkr_ref, q_ref, k_ref,
                v_ref, *, half, nope):
    cos, sin = cos_ref[...], sin_ref[...]
    turned = lambda x: x * cos + _swap(x, half) * sin
    q_ref[0, :, :nope] = xq_ref[:, :nope].astype(q_ref.dtype)
    q_ref[0, :, nope:] = turned(xq_ref[:, nope:]).astype(q_ref.dtype)
    k_ref[:, :nope] = xkv_ref[:, :nope].astype(k_ref.dtype)
    k_ref[:, nope:] = turned(xkr_ref[...]).astype(k_ref.dtype)
    v_ref[...] = xkv_ref[:, nope:].astype(v_ref.dtype)


def _bwd_kernel(cos_ref, sin_ref, dq_ref, dk_ref, dv_ref, dxq_ref, dxkv_ref,
                dxkr_ref, *, half, nope):
    cos, sin = cos_ref[...], sin_ref[...]
    f32 = lambda a: a.astype(jnp.float32)
    # the rotation's transpose
    back = lambda dy: dy * cos - _swap(dy, half) * sin

    @pl.when(pl.program_id(2) == 0)
    def _init():
        dxkr_ref[...] = jnp.zeros_like(dxkr_ref)

    dxq_ref[:, :nope] = f32(dq_ref[0, :, :nope])
    dxq_ref[:, nope:] = back(f32(dq_ref[0, :, nope:]))
    dxkv_ref[:, :nope] = f32(dk_ref[:, :nope])
    dxkv_ref[:, nope:] = f32(dv_ref[...])
    dxkr_ref[...] += back(f32(dk_ref[:, nope:]))


def _specs(d_qk: int, d_v: int, nope: int, tile: int):
    """Block specs on the grid ``(row, token tile, head)``: the table's
    tile (and the shared key's, the same shape), a head's columns of
    ``xq`` and of ``xkv``, and the same tokens heads first."""
    slot = pl.BlockSpec((None, tile, d_qk - nope), lambda b, i, h: (b, i, 0))
    flat = lambda n: pl.BlockSpec((None, tile, n), lambda b, i, h: (b, i, h))
    q5 = pl.BlockSpec((None, None, 1, tile, d_qk),
                      lambda b, i, h: (b, h, 0, i, 0))
    kv4 = lambda n: pl.BlockSpec((None, None, tile, n),
                                 lambda b, i, h: (b, h, i, 0))
    return slot, flat(d_qk), flat(nope + d_v), q5, kv4(d_qk), kv4(d_v)


def _sizes(xq, xkv, cos, nope):
    b, t, slot = cos.shape
    d_qk = nope + slot
    heads = xq.shape[-1] // d_qk
    return b, t, d_qk, xkv.shape[-1] // heads - nope, heads


# Jitted with everything that is no array static, so that the layers of
# a model share one trace and one lowering of each kernel.
@functools.partial(jax.jit,
                   static_argnames=("half", "nope", "dtype", "interpret"))
def _fwd(xq, xkv, xkr, cos, sin, *, half, nope, dtype, interpret):
    b, t, d_qk, d_v, heads = _sizes(xq, xkv, cos, nope)
    tile = _token_tile(t, 2 * d_qk)
    slot, q_flat, kv_flat, q5, k4, v4 = _specs(d_qk, d_v, nope, tile)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, half=half, nope=nope),
        out_shape=[jax.ShapeDtypeStruct((b, heads, 1, t, d_qk), dtype),
                   jax.ShapeDtypeStruct((b, heads, t, d_qk), dtype),
                   jax.ShapeDtypeStruct((b, heads, t, d_v), dtype)],
        grid=(b, t // tile, heads),
        in_specs=[slot, slot, q_flat, kv_flat, slot],
        out_specs=[q5, k4, v4],
        interpret=interpret,
        name="latent_rope_fwd",
    )(cos, sin, xq, xkv, xkr)


@functools.partial(jax.jit, static_argnames=("half", "nope", "interpret"))
def _bwd(cos, sin, dq5, dk4, dv4, *, half, nope, interpret):
    b, heads, _, t, d_qk = dq5.shape
    d_v = dv4.shape[-1]
    tile = _token_tile(t, 2 * d_qk)
    slot, q_flat, kv_flat, q5, k4, v4 = _specs(d_qk, d_v, nope, tile)
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, half=half, nope=nope),
        out_shape=[f32(b, t, heads * d_qk), f32(b, t, heads * (nope + d_v)),
                   f32(b, t, d_qk - nope)],
        grid=(b, t // tile, heads),
        in_specs=[slot, slot, q5, k4, v4],
        out_specs=[q_flat, kv_flat, slot],
        # the shared key's cotangent stays in VMEM over the heads
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="latent_rope_bwd",
    )(cos, sin, dq5, dk4, dv4)


def _check(xq, xkv, xkr, cos, sin, half, nope):
    b, t, slot = cos.shape
    d_qk = nope + slot
    heads = xq.shape[-1] // d_qk
    if (sin.shape != cos.shape or xkr.shape != cos.shape
            or not 0 < 2 * half <= slot or xq.shape != (b, t, heads * d_qk)
            or xkv.shape[:2] != (b, t) or xkv.shape[2] % heads
            or xkv.shape[2] // heads <= nope):
        raise ValueError(
            f"latent_rope: products {xq.shape}, {xkv.shape}, the shared key "
            f"{xkr.shape}, tables {cos.shape} and {sin.shape}, {nope} dims "
            f"passed and {half} rotated pairs do not describe heads of "
            f"[nope ; rope slot] for {b} rows of {t} tokens")
    if (nope % _LANES or slot % _LANES or (xkv.shape[2] // heads) % _LANES
            or t % _LANES):
        raise ValueError(
            f"latent_rope: seq {t}, nope {nope}, rope slot {slot} and values "
            f"of {xkv.shape[2] // heads - nope} cannot be tiled: all must be "
            f"multiples of {_LANES}")


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def latent_rope(xq: jax.Array, xkv: jax.Array, xkr: jax.Array,
                cos: jax.Array, sin: jax.Array, half: int, nope: int, dtype):
    """``(q5 [b, heads, 1, T, d_qk], k4 [b, heads, T, d_qk], v4 [b,
    heads, T, d_v])`` in ``dtype`` from the float32 products ``xq [b, T,
    heads * d_qk]`` (a head's lanes ``[nope ; rope slot]``), ``xkv [b, T,
    heads * (nope + d_v)]`` and the shared rotary key ``xkr [b, T, rope
    slot]``: the first ``2 half`` lanes of every rope slot turned by
    halves by ``cos`` and ``sin [b, T, rope slot]``
    (``ops.qk_norm_rope.tables``), the rest cast. No gradient reaches
    the tables."""
    return _forward(xq, xkv, xkr, cos, sin, half, nope, dtype)[0]


def _forward(xq, xkv, xkr, cos, sin, half, nope, dtype):
    _check(xq, xkv, xkr, cos, sin, half, nope)
    out = _fwd(xq, xkv, xkr, cos, sin, half=half, nope=nope,
               dtype=jnp.dtype(dtype), interpret=_interpret())
    return tuple(out), (cos, sin)


def _bwd_rule(half, nope, dtype, res, cotangents):
    return (*_bwd(*res, *cotangents, half=half, nope=nope,
                  interpret=_interpret()), None, None)


latent_rope.defvjp(_forward, _bwd_rule)

"""What a grouped-query attention does to its three products before its
kernels read them, as ONE pass over HBM each way, in Pallas for TPU.

Between the projections and the attention kernels of
``ops/sparse_attention.py`` and ``ops/rule_attention.py`` a decoder
layer normalises each head of ``q`` and ``k`` (RMSNorm over the head's
``d`` dims, a gain a dim), turns the first ``2 half`` dims of each head
by a rotary table (by halves: dim ``i`` pairs with dim ``i + half``; the
other dims pass through), rounds to the compute dtype and lays the
result out heads first, as the kernels read it. Written as array
operations that is six float32 passes over ``[b, T, heads, d]`` and
three transposing copies forward, and their transposes backward. Here it
is one kernel each way:

- :func:`qk_norm_rope` takes the float32 products as the einsums leave
  them, viewed ``[b, T, heads * d]`` (a head is a block of ``d`` lanes,
  ``d`` a multiple of 128: the reshape is free), the two gains ``[d]``
  and the table as two float32 arrays ``[b, T, d]`` (:func:`tables`),
  and returns ``q5 [b, kv_heads, G, T, d]``, ``k4`` and ``v4 [b,
  kv_heads, T, d]`` in the compute dtype. The turn into heads-first is
  the output blocks' index map, not a copy.
- Heads NARROWER than a register (``d`` = 64, any ``d`` that divides
  128) lie ``128 / d`` to a register's lanes, in HBM as in the products:
  nothing is padded. The same kernels then work on registers: a
  "key/value head" of the grid is the ``128 / d`` key/value heads of one
  register, its "query heads" the ``G`` registers of their query heads
  (``q5 [b, kv_heads d / 128, G, T, 128]``, ``k4`` and ``v4 [b, kv_heads
  d / 128, T, 128]``: the flat arrays' lanes in order, so head ``i`` of
  ``q`` is lanes ``[i d, (i + 1) d)`` of register ``i d // 128`` and
  reads the key/value head in the same lanes' register as before), the
  norm's mean is taken over each head's own lanes (masked sums), the
  rotation's ``swap`` stays inside a head, the gains and the tables are
  laid ``128 / d`` times across (:func:`tables` does that), and
  ``ops/rule_attention.py`` reads the result so.
- A grid step is ``(row, token tile, key/value head)``: it reads the
  ``[tile, G * d]`` float32 block of the head's ``G`` query heads, the
  ``[tile, d]`` blocks of its ``k`` and ``v`` and the table's tile (the
  key/value head is the grid's last axis, so the table's tile is fetched
  once for all of them), and for each head: ``n = x * rsqrt(mean(x^2) +
  eps) * gain`` in float32, ``y = n * cos + swap(n) * sin``, one cast,
  one write. ``swap`` exchanges the two halves of the rotated dims by
  lane rolls; ``cos`` holds the cosines twice and 1 on the dims passed
  through, ``sin`` holds ``-sin`` then ``+sin`` and 0 there, each times
  the table's ``attention_factor``: the rotation by halves is then two
  products and a sum on whole heads, and M-RoPE is only a different
  table. ``v`` is cast and written.
- Backward, the same grid: it reads the cotangents ``dq5`` / ``dk4`` /
  ``dv4`` as the attention kernels leave them and the float32 products,
  recomputes ``n``, and writes the products' cotangents ``[b, T, heads
  * d]`` float32 once: ``dn = dy * cos - swap(dy) * sin`` (the
  rotation's transpose), ``dx = rstd * (dn * gain - n_hat * mean(dn *
  gain * n_hat))``. The gains' gradients leave as partial sums a (row,
  token tile), ``[b, tiles, 8, d]`` float32 accumulated over the
  key/value heads in VMEM, and are reduced outside. No float32 ``[b, T,
  heads, d]`` intermediate of the norm or the rotation reaches HBM in
  either direction.

The arithmetic is ``rms_norm`` and ``_rotate`` of
``models/sparse_moe_lm.py`` in float32, rounded to the compute dtype
where the model rounded: the tests hold the op to that plain spelling.

``pallas_call`` names: ``qk_norm_rope_fwd``, ``qk_norm_rope_bwd``. Off
the TPU they run in interpret mode. A shape that does not tile (``T`` no
multiple of 128; ``d`` neither a multiple of 128 nor a divisor of it
whose heads fill whole registers; query heads no multiple of the
key/value heads) is an error everywhere, the attention kernels' own:
there is no other path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from sparktorch_tpu.ops.sparse_attention import _LANES, _interpret

# A grid step's float32 block of query heads holds at most this much:
# backward, the block, its cotangent and the attention's (half as wide)
# lie in VMEM twice each for the pipeline, 10 MiB of the 16 a kernel may
# use on a v5e beside the tables and the k / v blocks. At a window
# layer's shape blocks of 2 MiB (512 tokens of 8 heads) took 1.58 ms
# forward where 1 MiB took 1.71 and 0.5 MiB 2.04; backward 2.53, 2.54,
# 2.79 (PERF.md section 6, PR 38).
_BLOCK_BYTES = 2 << 20
_SUBLANES = 8


def tables(angles: jax.Array, head_dim: int, factor: float = 1.0):
    """``(cos, sin)`` float32 ``[b, T, head_dim]`` from the ``[b, T,
    half]`` angles of a rotary table that turns the first ``2 half``
    dims of a head by halves: ``cos`` is the cosines twice and 1 on the
    dims passed through, ``sin`` is ``-sin``, ``+sin`` and 0, each times
    ``factor`` on the rotated dims alone, so that the rotation of a
    whole head is ``x * cos + swap(x) * sin``. A ``head_dim`` below 128
    (one that divides it) gives ``[b, T, 128]``: the head's table as
    many times across as heads lie in a register."""
    cos, sin = factor * jnp.cos(angles), factor * jnp.sin(angles)
    rest = (*angles.shape[:-1], head_dim - 2 * angles.shape[-1])
    # heads narrower than a register lie several to its lanes
    across = max(_LANES // head_dim, 1)
    return (jnp.concatenate(
        [cos, cos, jnp.ones(rest, cos.dtype)] * across, -1),
            jnp.concatenate(
        [-sin, sin, jnp.zeros(rest, sin.dtype)] * across, -1))


def _swap(x, half: int, head: int = 0):
    """``x [tile, d]``, heads of ``head`` lanes (0: one head, ``d``
    wide), with a head's dims ``i`` and ``i + half`` exchanged for ``i <
    half``, by lane rolls (what lands on the dims past ``2 half`` meets a
    zero of ``sin``)."""
    d = x.shape[-1]
    head = head or d
    down = pltpu.roll(x, half, 1)          # x[i - half] at i
    if 2 * half == d:
        return down
    up = pltpu.roll(x, d - half, 1)        # x[i + half] at i
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where((lane if head == d else lane % head) < half, up, down)


def _head_mean(y, head: int):
    """The mean of ``y [tile, d]`` over each head's ``head`` lanes, at
    every lane of the head: the last axis's mean where a head is the
    whole of it, else a masked sum a head of the register."""
    d = y.shape[-1]
    if head == d:
        return jnp.mean(y, -1, keepdims=True)
    lane = jax.lax.broadcasted_iota(jnp.int32, y.shape, 1)
    out = jnp.zeros_like(y)
    for j in range(d // head):
        own = (lane >= j * head) & (lane < (j + 1) * head)
        out = jnp.where(own, jnp.sum(jnp.where(own, y, 0.0), -1,
                                     keepdims=True) / head, out)
    return out


def _normed(x, eps, head):
    """``(x / rms(x), 1 / rms(x))`` over each head's lanes, as
    ``rms_norm``."""
    rstd = jax.lax.rsqrt(_head_mean(jnp.square(x), head) + eps)
    return x * rstd, rstd


def _fwd_kernel(cos_ref, sin_ref, gq_ref, gk_ref, xq_ref, xk_ref, xv_ref,
                q_ref, k_ref, v_ref, *, eps, half, groups, d, head):
    cos, sin = cos_ref[...], sin_ref[...]

    def turned(x, gain):
        n = _normed(x, eps, head)[0] * gain
        return n * cos + _swap(n, half, head) * sin

    gq = gq_ref[...]
    for g in range(groups):
        q_ref[g] = turned(xq_ref[:, g * d:(g + 1) * d], gq).astype(
            q_ref.dtype)
    k_ref[...] = turned(xk_ref[...], gk_ref[...]).astype(k_ref.dtype)
    v_ref[...] = xv_ref[...].astype(v_ref.dtype)


def _bwd_kernel(cos_ref, sin_ref, gq_ref, gk_ref, xq_ref, xk_ref, dq_ref,
                dk_ref, dv_ref, dxq_ref, dxk_ref, dxv_ref, dgq_ref, dgk_ref,
                *, eps, half, groups, d, head):
    cos, sin = cos_ref[...], sin_ref[...]

    @pl.when(pl.program_id(2) == 0)
    def _init():
        dgq_ref[...] = jnp.zeros_like(dgq_ref)
        dgk_ref[...] = jnp.zeros_like(dgk_ref)

    def back(x, dy, gain):
        """The product's cotangent, and the gain's summed over the
        tile's tokens eight at a time (vector adds, no reduction across
        sublanes)."""
        dy = dy.astype(jnp.float32)
        n_hat, rstd = _normed(x, eps, head)
        dn = dy * cos - _swap(dy, half, head) * sin
        dn_hat = dn * gain
        dx = rstd * (dn_hat - n_hat * _head_mean(dn_hat * n_hat, head))
        return dx, jnp.sum((dn * n_hat).reshape(-1, _SUBLANES, d), 0)

    gq = gq_ref[...]
    for g in range(groups):
        cols = slice(g * d, (g + 1) * d)
        dx, dg = back(xq_ref[:, cols], dq_ref[g], gq)
        dxq_ref[:, cols] = dx
        dgq_ref[...] += dg
    dx, dg = back(xk_ref[...], dk_ref[...], gk_ref[...])
    dxk_ref[...] = dx
    dgk_ref[...] += dg
    dxv_ref[...] = dv_ref[...].astype(jnp.float32)


def _token_tile(t: int, width: int, head: int = _LANES) -> int:
    """Tokens a grid step: the largest power of two that divides ``t``
    (a multiple of 128) whose float32 block of ``width`` lanes stays
    within ``_BLOCK_BYTES``, 128 at the least. Heads narrower than a
    register take half of that: their masked sums a head stand beside
    the blocks, and at 32 / 8 heads of 64 the backward kernel asked 18.2
    MiB of the 16 with whole blocks (compiled for the v5e, PR 46)."""
    budget = _BLOCK_BYTES if head >= _LANES else _BLOCK_BYTES // 2
    tile = _LANES
    while t % (tile * 2) == 0 and tile * 2 * width * 4 <= budget:
        tile *= 2
    return tile


def _specs(groups: int, d: int, tile: int):
    """Block specs on the grid ``(row, token tile, key/value head)``: the
    table's tile, a gain, a head's columns of a product ``[b, T, heads *
    d]`` (``groups`` heads of ``q``, one of ``k`` or ``v``), and the
    same tokens heads first."""
    table = pl.BlockSpec((None, tile, d), lambda b, i, h: (b, i, 0))
    gain = pl.BlockSpec((1, d), lambda b, i, h: (0, 0))
    flat = lambda n: pl.BlockSpec((None, tile, n * d),
                                  lambda b, i, h: (b, i, h))
    q5 = pl.BlockSpec((None, None, groups, tile, d),
                      lambda b, i, h: (b, h, 0, i, 0))
    kv4 = pl.BlockSpec((None, None, tile, d), lambda b, i, h: (b, h, i, 0))
    return table, gain, flat(groups), flat(1), q5, kv4


def _across(gain, lanes: int):
    """A head's gain as many times across as heads lie in a register."""
    return gain if gain.shape[0] == lanes else jnp.tile(
        gain, lanes // gain.shape[0])


def _shape(xq, xk, cos):
    b, t, d = cos.shape
    hkv = xk.shape[-1] // d
    return b, t, d, hkv, xq.shape[-1] // d // hkv


# Jitted with everything that is no array static, so that the layers of
# a model share one trace and one lowering of each kernel
# (``ops/flash_attention.py`` says what it costs otherwise).
@functools.partial(jax.jit,
                   static_argnames=("eps", "half", "dtype", "interpret"))
def _fwd(xq, xk, xv, gq, gk, cos, sin, *, eps, half, dtype, interpret):
    head = gq.shape[0]
    gq, gk = _across(gq, cos.shape[-1]), _across(gk, cos.shape[-1])
    b, t, d, hkv, groups = _shape(xq, xk, cos)
    tile = _token_tile(t, groups * d, head)
    table, gain, q_flat, kv_flat, q5, kv4 = _specs(groups, d, tile)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps, half=half, groups=groups,
                          d=d, head=head),
        out_shape=[jax.ShapeDtypeStruct((b, hkv, groups, t, d), dtype),
                   jax.ShapeDtypeStruct((b, hkv, t, d), dtype),
                   jax.ShapeDtypeStruct((b, hkv, t, d), dtype)],
        grid=(b, t // tile, hkv),
        in_specs=[table, table, gain, gain, q_flat, kv_flat, kv_flat],
        out_specs=[q5, kv4, kv4],
        interpret=interpret,
        name="qk_norm_rope_fwd",
    )(cos, sin, gq[None], gk[None], xq, xk, xv)


@functools.partial(jax.jit, static_argnames=("eps", "half", "interpret"))
def _bwd(xq, xk, gq, gk, cos, sin, dq5, dk4, dv4, *, eps, half, interpret):
    head = gq.shape[0]
    gq, gk = _across(gq, cos.shape[-1]), _across(gk, cos.shape[-1])
    b, t, d, hkv, groups = _shape(xq, xk, cos)
    tile = _token_tile(t, groups * d, head)
    table, gain, q_flat, kv_flat, q5, kv4 = _specs(groups, d, tile)
    # a (row, token tile)'s partial sums stay in VMEM over the key/value
    # heads, the grid's last axis
    partial = pl.BlockSpec((None, None, _SUBLANES, d),
                           lambda b, i, h: (b, i, 0, 0))
    partials = jax.ShapeDtypeStruct((b, t // tile, _SUBLANES, d),
                                    jnp.float32)
    dxq, dxk, dxv, dgq, dgk = pl.pallas_call(
        functools.partial(_bwd_kernel, eps=eps, half=half, groups=groups,
                          d=d, head=head),
        out_shape=[jax.ShapeDtypeStruct(xq.shape, jnp.float32),
                   jax.ShapeDtypeStruct(xk.shape, jnp.float32),
                   jax.ShapeDtypeStruct(xk.shape, jnp.float32),
                   partials, partials],
        grid=(b, t // tile, hkv),
        in_specs=[table, table, gain, gain, q_flat, kv_flat, q5, kv4, kv4],
        out_specs=[q_flat, kv_flat, kv_flat, partial, partial],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="qk_norm_rope_bwd",
    )(cos, sin, gq[None], gk[None], xq, xk, dq5, dk4, dv4)
    # a gain's gradient over the heads of a register, where several lie
    dgq, dgk = dgq.sum((0, 1, 2)), dgk.sum((0, 1, 2))
    if head < d:
        dgq, dgk = (dg.reshape(-1, head).sum(0) for dg in (dgq, dgk))
    return dxq, dxk, dxv, dgq, dgk


def _check(xq, xk, xv, q_gain, k_gain, cos, sin, half):
    b, t, lanes = cos.shape
    d = q_gain.shape[0]
    if (sin.shape != cos.shape or q_gain.shape != (d,)
            or k_gain.shape != (d,) or not 0 < 2 * half <= d
            or lanes != max(d, _LANES) or lanes % d):
        raise ValueError(
            f"qk_norm_rope: tables {cos.shape} and {sin.shape}, gains "
            f"{q_gain.shape} and {k_gain.shape} and {half} rotated pairs do "
            f"not describe one head")
    if (xk.shape != xv.shape or xq.shape[:2] != (b, t)
            or xk.shape[:2] != (b, t) or xq.shape[2] % d or xk.shape[2] % d):
        raise ValueError(
            f"qk_norm_rope: products {xq.shape}, {xk.shape}, {xv.shape} are "
            f"not heads of {d} for {b} rows of {t} tokens")
    if (xq.shape[2] // d) % (xk.shape[2] // d):
        raise ValueError(
            f"qk_norm_rope: {xq.shape[2] // d} query heads are not a "
            f"multiple of {xk.shape[2] // d} key/value heads")
    per = lanes // d   # heads a register
    if (d % _LANES and _LANES % d) or t % _LANES or (
            xk.shape[2] // d) % per or (xq.shape[2] // xk.shape[2]) % per:
        raise ValueError(
            f"qk_norm_rope: seq {t} x head_dim {d} cannot be tiled: seq "
            f"must be a multiple of {_LANES}, and head_dim one too or a "
            f"divisor of it with {per} key/value heads, and the query heads "
            f"of one key/value head, filling whole registers")


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def qk_norm_rope(xq: jax.Array, xk: jax.Array, xv: jax.Array,
                 q_gain: jax.Array, k_gain: jax.Array, cos: jax.Array,
                 sin: jax.Array, eps: float, half: int, dtype):
    """``(q5 [b, kv_heads, G, T, d], k4, v4 [b, kv_heads, T, d])`` in
    ``dtype`` from the float32 products ``xq [b, T, heads * d]``, ``xk``
    and ``xv [b, T, kv_heads * d]``: RMSNorm (``eps``, gains ``[d]``)
    over each head of ``q`` and ``k``, then the rotation of the first
    ``2 half`` dims by :func:`tables`' ``cos`` and ``sin`` ``[b, T,
    d]``; ``v`` only cast. No gradient reaches the tables. Heads
    narrower than a register come out by registers (the module
    docstring): ``q5 [b, kv_heads d / 128, G, T, 128]``."""
    return _forward(xq, xk, xv, q_gain, k_gain, cos, sin, eps, half,
                    dtype)[0]


def _forward(xq, xk, xv, q_gain, k_gain, cos, sin, eps, half, dtype):
    _check(xq, xk, xv, q_gain, k_gain, cos, sin, half)
    out = _fwd(xq, xk, xv, q_gain, k_gain, cos, sin, eps=eps, half=half,
               dtype=jnp.dtype(dtype), interpret=_interpret())
    return tuple(out), (xq, xk, q_gain, k_gain, cos, sin)


def _bwd_rule(eps, half, dtype, res, cotangents):
    xq, xk, q_gain, k_gain, cos, sin = res
    return (*_bwd(xq, xk, q_gain, k_gain, cos, sin, *cotangents, eps=eps,
                  half=half, interpret=_interpret()), None, None)


qk_norm_rope.defvjp(_forward, _bwd_rule)

"""A gated short convolution (LFM2's mixer) between its two products, as
ONE Pallas pass over HBM each way, for TPU.

The layer's input product ``bcu [b, T, 3 D]`` (float32, three equal
column blocks ``[B ; C ; u]``) is read AS IT LIES, a block's columns
through its blocks' index map, and

    s = B * u;  c[t] = sum_{i < 3} taps[i] s[t - 2 + i],  s[t < 0] = 0;
    y = C * c

leaves as ``y [b, T, D]`` in the compute dtype: no activation, no state
beyond the two tokens before. The convolution has :data:`TAPS` = 3 taps,
the op's constant: it is the one width the model has, and the halo of 8
rows and the rolls below are written for it.

- Forward, a grid step is ``(row, block of columns, token tile)``: the
  tile's float32 blocks of ``B``, ``C`` and ``u`` (the same array three
  times, the block's index moved by a third of the columns) and the 8
  rows of ``B`` and ``u`` before the tile (zeros where the tile is the
  row's first); the rows ``t - 1`` and ``t - 2`` are sublane rolls of
  the tile and those 8 rows together, 128 lanes at a time
  (``ops/gdn_conv_gate.py``'s halo and rolls, whose code this file
  imports). It reads 12 bytes a channel a token and writes 2.
- Backward reads ``B``, ``C``, ``u`` again (with the 8 rows of ``B`` and
  ``u`` before the tile and the 8 of ``C`` after it) and the cotangent
  ``dy`` (the tile and the 16 rows after it, a bfloat16 tile),
  recomputes ``s`` and ``c`` and writes the product's cotangent ``[b, T,
  3 D]`` float32 ONCE: ``dC = dy c``; ``dc = dy C``; ``ds[t] = sum_i
  taps[i] dc[t + 2 - i]``; ``dB = ds u``; ``du = ds B``. The three
  column blocks of one array cannot be three blocks of one grid step's
  output, so the grid's last axis runs over them: the step for ``B``
  computes all three, writes ``dB`` and leaves ``dC`` and ``du`` in
  VMEM, and the two steps after it (whose inputs are the blocks already
  there: nothing is fetched) copy those out. It reads 14 bytes a channel
  a token and writes 12. The taps' gradient ``dtaps[i] = sum_t dc[t] s[t
  - 2 + i]`` is summed over the token tiles in VMEM, eight tokens apart
  (vector adds), ``[b, 3, 8, D]``, and reduced outside.

All arithmetic is float32 in the model's order, one rounding at the
output; ``tests/test_short_conv_gate.py`` holds the op to the plain
``jax.numpy`` spelling, value and both gradients. ``pallas_call`` names:
``sconv_fwd``, ``sconv_bwd``. Off the TPU they run in interpret mode. A
shape that does not tile (``D`` no multiple of 128 lanes, ``T`` no
multiple of 16 tokens, other than 3 taps) is an error everywhere: there
is no other path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from sparktorch_tpu.ops.gdn_conv_gate import (_PACKED, _VMEM_BYTES,
                                              _convolved, _earlier,
                                              _for_each_head)
from sparktorch_tpu.ops.sparse_attention import (_LANES, _SUBLANES,
                                                 _interpret)

# the convolution's taps: s[t - 2], s[t - 1], s[t]
TAPS = 3
# A block is at most this many lanes wide: rows of 2 KiB in float32, so
# that a tile holds some hundreds of tokens beside its three float32
# blocks and the halo is a few percent of what a step reads. At the
# cell's step (4 rows of 4,096 tokens of 2,048 channels: tiles of 512
# tokens forward, 256 backward) a call takes 0.69 ms forward and 1.83
# backward; blocks of 256 to 2,048 lanes and tiles of 128 tokens or more
# read within 4% of that, tiles of 64 up to twice (TPU v5e, a loop of
# twenty calls, PR 46).
_BLOCK_LANES = 512


def _column_block(d: int) -> int:
    """Lanes a block: the largest power-of-two count of registers that
    divides the ``d`` channels, up to ``_BLOCK_LANES``."""
    lanes = _LANES
    while lanes < _BLOCK_LANES and d % (2 * lanes) == 0:
        lanes *= 2
    return lanes


def _token_tile(t: int, lanes: int, bytes_a_lane: int) -> int:
    """Tokens a grid step: 16 times the largest power of two that
    divides ``t / 16`` whose blocks, ``bytes_a_lane`` a token a lane in
    all (the pipeline's two copies counted), fit ``_VMEM_BYTES``."""
    tile = _PACKED
    while (t % (2 * tile) == 0
           and 2 * tile * lanes * bytes_a_lane <= _VMEM_BYTES):
        tile *= 2
    return tile


def _fwd_kernel(taps_ref, b_before_ref, u_before_ref, b_ref, c_ref, u_ref,
                y_ref):
    tile = u_ref.shape[0]
    first = pl.program_id(2) == 0

    def group(cols):
        before = jnp.where(first, 0.0,
                           b_before_ref[:, cols] * u_before_ref[:, cols])
        rows = jnp.concatenate([b_ref[:, cols] * u_ref[:, cols], before], 0)
        conv = _convolved(taps_ref[:, cols], _earlier(rows, TAPS))[:tile]
        y_ref[:, cols] = (c_ref[:, cols] * conv).astype(y_ref.dtype)

    _for_each_head(u_ref, group)


def _bwd_kernel(taps_ref, b_before_ref, u_before_ref, b_ref, c_ref, u_ref,
                c_after_ref, dy_ref, dy_after_ref, dx_ref, dtaps_ref,
                dc_keep, du_keep):
    tile = u_ref.shape[0]
    i, part = pl.program_id(2), pl.program_id(3)
    first, last = i == 0, i == pl.num_programs(2) - 1

    @pl.when(first & (part == 0))
    def _init():
        dtaps_ref[...] = jnp.zeros_like(dtaps_ref)

    def group(cols):
        taps = taps_ref[:, cols]
        b, u = b_ref[:, cols], u_ref[:, cols]
        before = jnp.where(first, 0.0,
                           b_before_ref[:, cols] * u_before_ref[:, cols])
        shifted = _earlier(jnp.concatenate([b * u, before], 0), TAPS)
        dy = dy_ref[:, cols].astype(jnp.float32)
        # the cotangent of c on the tile and the 8 rows after it (zeros
        # past the row's end): a roll up brings c[t + 1], c[t + 2]
        dc = jnp.concatenate(
            [dy * c_ref[:, cols],
             jnp.where(last, 0.0, dy_after_ref[:, cols].astype(
                 jnp.float32)[:_SUBLANES] * c_after_ref[:, cols])], 0)
        n = dc.shape[0]
        ds = taps[TAPS - 1:TAPS] * dc
        for j in range(TAPS - 2, -1, -1):
            ds = ds + taps[j:j + 1] * pltpu.roll(dc, n - (TAPS - 1 - j), 0)
        ds = ds[:tile]
        dx_ref[:, cols] = ds * u
        dc_keep[:, cols] = dy * _convolved(taps, shifted)[:tile]
        du_keep[:, cols] = ds * b
        for j in range(TAPS):
            dtaps_ref[j, :, cols] += jnp.sum(
                (dc * shifted[j])[:tile].reshape(-1, _SUBLANES, _LANES), 0)

    @pl.when(part == 0)
    def _all_three():
        _for_each_head(u_ref, group)

    @pl.when(part == 1)
    def _c():
        dx_ref[...] = dc_keep[...]

    @pl.when(part == 2)
    def _u():
        dx_ref[...] = du_keep[...]


def _specs(t: int, tile: int, lanes: int, blocks: int, index):
    """Block specs on a grid whose first three axes are ``(row, block of
    columns, token tile)`` (``index`` picks them out of a step's ids):
    the taps' columns; of the product, by part (0, 1, 2: ``B``, ``C``,
    ``u``), the tile, the 8 rows before it (the first tile's are its
    own: the kernel puts zeros there) and the 8 rows after it (the last
    tile's likewise); of an array ``[b, T, D]``, the tile and the 16
    rows after it."""
    by8, by16 = tile // _SUBLANES, tile // _PACKED

    def rows(n, block, part=0):
        def at(*ids):
            b, c, i = index(*ids)
            return b, block(i), part * blocks + c
        return pl.BlockSpec((None, n, lanes), at)

    before = lambda i: jnp.maximum(i * by8 - 1, 0)
    after = lambda i: jnp.minimum((i + 1) * by8, t // _SUBLANES - 1)
    return dict(
        taps=pl.BlockSpec((TAPS, lanes),
                          lambda *ids: (0, index(*ids)[1])),
        tile=lambda part: rows(tile, lambda i: i, part),
        before=lambda part: rows(_SUBLANES, before, part),
        after=lambda part: rows(_SUBLANES, after, part),
        own=rows(tile, lambda i: i),
        own_after=rows(_PACKED, lambda i: jnp.minimum(
            (i + 1) * by16, t // _PACKED - 1)))


# Jitted with everything that is no array static, so that the layers of
# a model share one trace and one lowering of each kernel
# (``ops/flash_attention.py`` says what it costs otherwise).
@functools.partial(jax.jit, static_argnames=("dtype", "interpret"))
def _fwd(bcu, taps, *, dtype, interpret):
    b, t, d = bcu.shape[0], bcu.shape[1], bcu.shape[2] // 3
    lanes = _column_block(d)
    tile = _token_tile(t, lanes, 2 * (12 + dtype.itemsize))
    s = _specs(t, tile, lanes, d // lanes, lambda b, c, i: (b, c, i))
    return pl.pallas_call(
        _fwd_kernel,
        out_shape=jax.ShapeDtypeStruct((b, t, d), dtype),
        grid=(b, d // lanes, t // tile),
        in_specs=[s["taps"], s["before"](0), s["before"](2), s["tile"](0),
                  s["tile"](1), s["tile"](2)],
        out_specs=s["own"],
        interpret=interpret, name="sconv_fwd",
    )(taps, bcu, bcu, bcu, bcu, bcu)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _bwd(bcu, taps, dy, *, interpret):
    b, t, d = bcu.shape[0], bcu.shape[1], bcu.shape[2] // 3
    lanes = _column_block(d)
    # three float32 blocks, the cotangent's and one of the result, twice
    # each for the pipeline, and the two blocks kept
    tile = _token_tile(t, lanes, 2 * (16 + dy.dtype.itemsize) + 8)
    blocks = d // lanes
    s = _specs(t, tile, lanes, blocks, lambda b, c, i, p: (b, c, i))
    dx, partial = pl.pallas_call(
        _bwd_kernel,
        out_shape=[jax.ShapeDtypeStruct(bcu.shape, jnp.float32),
                   jax.ShapeDtypeStruct((b, TAPS, _SUBLANES, d),
                                        jnp.float32)],
        grid=(b, blocks, t // tile, 3),
        in_specs=[s["taps"], s["before"](0), s["before"](2), s["tile"](0),
                  s["tile"](1), s["tile"](2), s["after"](1), s["own"],
                  s["own_after"]],
        out_specs=[pl.BlockSpec((None, tile, lanes),
                                lambda b, c, i, p: (b, i, p * blocks + c)),
                   pl.BlockSpec((None, TAPS, _SUBLANES, lanes),
                                lambda b, c, i, p: (b, 0, 0, c))],
        scratch_shapes=[pltpu.VMEM((tile, lanes), jnp.float32),
                        pltpu.VMEM((tile, lanes), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "arbitrary", "arbitrary")),
        interpret=interpret, name="sconv_bwd",
    )(taps, bcu, bcu, bcu, bcu, bcu, bcu, dy, dy)
    return dx, partial.sum((0, 2))


def _check(bcu, taps):
    d = bcu.shape[-1] // 3
    if (bcu.ndim != 3 or bcu.dtype != jnp.float32 or bcu.shape[-1] != 3 * d
            or d % _LANES or taps.shape != (TAPS, d)):
        raise ValueError(
            f"short_conv_gate: a product {bcu.shape} {bcu.dtype} and taps "
            f"{taps.shape} are not float32 [B ; C ; u] of three equal blocks "
            f"of whole registers of {_LANES} lanes under {TAPS} taps a "
            f"channel")
    if bcu.shape[1] % _PACKED:
        raise ValueError(f"short_conv_gate: {bcu.shape[1]} tokens cannot be "
                         f"tiled: a multiple of {_PACKED} is needed")


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def short_conv_gate(bcu: jax.Array, taps: jax.Array, dtype):
    """``y [b, T, D] = C * conv3(B * u)`` in ``dtype`` from the float32
    product ``bcu [b, T, 3 D]`` (columns ``[B ; C ; u]``) and the
    convolution's ``taps [3, D]``: causal, depthwise, ``c[t] = sum_i
    taps[i] (B u)[t - 2 + i]``, no activation."""
    return _forward(bcu, taps, dtype)[0]


def _forward(bcu, taps, dtype):
    _check(bcu, taps)
    y = _fwd(bcu, taps, dtype=jnp.dtype(dtype), interpret=_interpret())
    return y, (bcu, taps)


def _backward(dtype, res, dy):
    bcu, taps = res
    return _bwd(bcu, taps, dy.astype(dtype), interpret=_interpret())


short_conv_gate.defvjp(_forward, _backward)


def plain(bcu, taps, dtype):
    """The same function as array operations: what the tests and
    ``chip_smoke.py`` hold the kernels to."""
    b, c, u = jnp.split(bcu, 3, -1)
    s = jnp.pad(b * u, ((0, 0), (TAPS - 1, 0), (0, 0)))
    t = bcu.shape[1]
    conv = _convolved(taps, [s[:, i:i + t] for i in range(TAPS)])
    return (c * conv).astype(dtype)

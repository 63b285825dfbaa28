"""What a Gated DeltaNet layer does to its input product before the
rule's kernels read it, and to the rule's output before ``W_o`` reads
it, as Pallas passes that cross HBM once each way, for TPU.

The layer's one wide product ``qkvz [b, T, 2 keys + 2 values]`` (float32,
columns ``[q ; k ; v ; z]``, a head a block of 128 lanes) is read AS IT
LIES, a part's columns through its blocks' index map:

- :func:`gdn_conv`: ``u = [q ; k ; v]`` through the causal depthwise
  convolution over time (``taps [n, 2 keys + values]``, ``pre[t] = sum_i
  taps[i] u[t - (n - 1) + i]`` summed in that order, ``u[t < 0] = 0``),
  SiLU, and for ``q`` and ``k`` the L2 norm over a head's 128 lanes, ``s
  * (scale * rsqrt(sum(s^2) + 1e-6))`` with ``scale`` ``128 ** -0.5``
  for ``q`` and 1 for ``k``; one cast; ``q`` and ``k [b, T, keys]`` and
  ``v [b, T, values]`` leave in the layout ``ops/gated_delta_rule.py``
  reads. A grid step is ``(row, block of columns, token tile)``: the
  tile's float32 block, and the same array a second time as the 8 rows
  before the tile (zeros where the tile is the row's first); the rows
  ``t - 1 .. t - (n - 1)`` are sublane rolls of the two together, a
  head's 128 lanes at a time (a head's arithmetic stays in registers
  and what it spills is small beside the blocks). One
  ``pallas_call`` for each of ``q``, ``k`` and ``v``: each has its own
  result, and a call's blocks span its part's whole width where that
  fits.
- its backward reads the cotangents as ``gdn_bwd``'s wrapper leaves them
  and ``u`` again (the tile, the 8 rows before it and the 8 after; of a
  cotangent the 16 rows after, a bfloat16 tile), recomputes ``pre``, the
  SiLU and the norms on the tile and the rows after it, and writes ``du[t]
  = sum_i taps[i] dpre[t + (n - 1) - i]`` float32 once. The taps'
  gradient is summed over the token tiles in VMEM, eight tokens apart
  (vector adds), ``[b, n, 8, columns]``, and reduced outside.
- :func:`gdn_out_norm`: ``y = rms_norm(o, gain) * silu(z)`` a head of
  128 lanes (``o`` the rule's output, ``z`` the product's last columns),
  cast to ``o``'s dtype; backward ``do``, ``dz`` and the gain's gradient
  as partial sums.

The product's cotangent is written ONCE. ``du`` and ``dz`` are column
ranges of one ``[b, T, 2 keys + 2 values]`` float32 array, and two
operations that each handed back their own range would meet in an
addition of two such arrays. So :func:`gdn_conv` hands the product on as
its fourth result, ``gate``, and :func:`gdn_out_norm` takes THAT: its
backward kernel allocates the product's cotangent and writes ``z``'s
columns, leaving the others unwritten; the cotangent reaches
:func:`gdn_conv`'s backward pass as ``gate``'s, whose three calls write
``q``'s, ``k``'s and ``v``'s columns into the same buffer
(``input_output_aliases``, the buffer never enters VMEM) and return it
whole. ``gate`` therefore goes to :func:`gdn_out_norm` and nowhere else:
what :func:`gdn_out_norm` hands back for it is only ``z``'s columns of a
cotangent. (A ``gate`` nothing reads has a zero cotangent, and ``dz`` is
then zero, as it should be.)

All arithmetic is float32, in the model's order, rounded to the compute
dtype where the model rounded; the tests hold both functions to the
plain spelling they replaced. ``pallas_call`` names: ``gdn_conv_fwd``,
``gdn_conv_bwd``, ``gdn_out_norm_fwd``, ``gdn_out_norm_bwd``. Off the TPU
they run in interpret mode. A shape that does not tile (``T`` no multiple
of the rule's chunk, a part that is not whole heads of 128 lanes, more
taps than a halo of 8 rows holds) is an error everywhere: there is no
other path.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from sparktorch_tpu.ops.gated_delta_rule import CHUNK
from sparktorch_tpu.ops.sparse_attention import (_LANES, _SUBLANES,
                                                 _interpret)

# rows of a cotangent's halo: a bfloat16 tile
_PACKED = 16
_L2_EPS = 1e-6
# A grid step's blocks, each held twice by the pipeline, stay within
# this much of the 16 MiB a kernel may use on a v5e; what a head's
# arithmetic spills lies beside them.
_VMEM_BYTES = 8 << 20
# A block is at most this many lanes wide: rows of 8 KiB in float32 move
# at the memory's rate, and a narrower block leaves the tile more tokens,
# so the halo is a smaller share of what a step reads.
_BLOCK_LANES = 2_048
# A block's heads run in a loop over groups of this many, a group's
# unrolled. Unrolled whole (16 heads a block in the cell), the eight
# kernels of a layer trace and lower in 2.2 s of a step's set-up where
# groups of four take 0.85 (this sandbox's CPU, PR 44), for 2-9% fewer
# bundles of the compiler's static schedule a grid step; one head a
# trip is 20% more.
_HEADS_UNROLLED = 4


def _column_block(width: int, offset: int) -> int:
    """Lanes a block: the widest run of whole heads that divides both a
    part's ``width`` and its ``offset`` in the product (a block's index
    is its offset in blocks), halved down to ``_BLOCK_LANES``."""
    lanes = _LANES * math.gcd(width // _LANES, offset // _LANES)
    while lanes > _BLOCK_LANES and lanes % (2 * _LANES) == 0:
        lanes //= 2
    return lanes


def _token_tile(t: int, lanes: int, bytes_a_lane: int) -> int:
    """Tokens a grid step: the rule's chunk times the largest power of
    two that divides the row's chunks (as ``gated_delta_rule`` chooses
    its block of chunks) whose blocks, ``bytes_a_lane`` a token a lane
    in all, fit ``_VMEM_BYTES`` twice."""
    tile = CHUNK
    while (t % (2 * tile) == 0
           and 2 * (2 * tile) * lanes * bytes_a_lane <= _VMEM_BYTES):
        tile *= 2
    return tile


def _for_each_head(ref, body):
    """``body(cols)`` for the lanes of each head of a block: a loop over
    groups of ``_HEADS_UNROLLED`` heads, a group's heads unrolled."""
    heads = ref.shape[-1] // _LANES
    group = math.gcd(heads, _HEADS_UNROLLED)

    def step(g, carry):
        for j in range(group):
            body(pl.ds(pl.multiple_of((g * group + j) * _LANES, _LANES),
                       _LANES))
        return carry

    jax.lax.fori_loop(0, heads // group, step, 0)


def _earlier(rows, n_taps: int):
    """``[rows[t - (n_taps - 1) + i] for i]`` along axis 0, circularly:
    the caller lays what precedes row 0 at the array's end."""
    return [rows if i == n_taps - 1 else pltpu.roll(rows, n_taps - 1 - i, 0)
            for i in range(n_taps)]


def _convolved(taps, shifted):
    """``sum_i taps[i] shifted[i]`` in the model's order."""
    pre = taps[0:1] * shifted[0]
    for i in range(1, len(shifted)):
        pre = pre + taps[i:i + 1] * shifted[i]
    return pre


def _l2_rstd(s):
    return jax.lax.rsqrt(jnp.sum(jnp.square(s), -1, keepdims=True) + _L2_EPS)


def _rms_rstd(o, eps):
    return jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True) + eps)


def _silu_slope(x, gate):
    """``silu'(x)`` from ``gate = sigmoid(x)``."""
    return gate * (1.0 + x * (1.0 - gate))


def _conv_fwd_kernel(taps_ref, before_ref, u_ref, out_ref, *, scale):
    tile, n_taps = u_ref.shape[0], taps_ref.shape[0]
    first = pl.program_id(2) == 0

    def head(cols):
        before = jnp.where(first, 0.0, before_ref[:, cols])
        rows = jnp.concatenate([u_ref[:, cols], before], 0)
        s = jax.nn.silu(_convolved(taps_ref[:, cols],
                                   _earlier(rows, n_taps)))[:tile]
        if scale is not None:
            s = s * (scale * _l2_rstd(s))
        out_ref[:, cols] = s.astype(out_ref.dtype)

    _for_each_head(u_ref, head)


def _conv_bwd_kernel(taps_ref, before_ref, u_ref, after_ref, dy_ref,
                     dy_after_ref, _whole_ref, du_ref, dtaps_ref, *, scale):
    tile, n_taps = u_ref.shape[0], taps_ref.shape[0]
    i = pl.program_id(2)
    first, last = i == 0, i == pl.num_programs(2) - 1

    @pl.when(first)
    def _init():
        dtaps_ref[...] = jnp.zeros_like(dtaps_ref)

    nothing = jnp.zeros((_SUBLANES, _LANES), jnp.float32)

    def head(cols):
        taps = taps_ref[:, cols]
        # the tile, the 8 rows after it and the 8 before: a roll down
        # brings the rows before to the tile's top, a roll up the rows
        # after to its end
        rows = jnp.concatenate(
            [u_ref[:, cols], after_ref[:, cols],
             jnp.where(first, 0.0, before_ref[:, cols])], 0)
        dy = jnp.concatenate(
            [dy_ref[:, cols].astype(jnp.float32),
             jnp.where(last, 0.0, dy_after_ref[:, cols].astype(
                 jnp.float32)[:_SUBLANES]), nothing], 0)
        shifted = _earlier(rows, n_taps)
        pre = _convolved(taps, shifted)
        gate = jax.nn.sigmoid(pre)
        ds = dy
        if scale is not None:
            s = pre * gate
            rstd = _l2_rstd(s)
            ds = (scale * rstd) * (dy - s * (jnp.square(rstd) * jnp.sum(
                dy * s, -1, keepdims=True)))
        dpre = ds * _silu_slope(pre, gate)
        n = rows.shape[0]
        du = taps[n_taps - 1:n_taps] * dpre
        for j in range(n_taps - 2, -1, -1):
            du = du + taps[j:j + 1] * pltpu.roll(
                dpre, n - (n_taps - 1 - j), 0)
        du_ref[:, cols] = du[:tile].astype(du_ref.dtype)
        for j in range(n_taps):
            dtaps_ref[j, :, cols] += jnp.sum(
                (dpre * shifted[j])[:tile].reshape(-1, _SUBLANES, _LANES), 0)

    _for_each_head(u_ref, head)


def _out_norm_fwd_kernel(gain_ref, o_ref, z_ref, y_ref, *, eps):
    gain = gain_ref[...]

    def head(cols):
        o = o_ref[:, cols].astype(jnp.float32)
        y_ref[:, cols] = (o * _rms_rstd(o, eps) * gain * jax.nn.silu(
            z_ref[:, cols].astype(jnp.float32))).astype(y_ref.dtype)

    _for_each_head(o_ref, head)


def _out_norm_bwd_kernel(gain_ref, o_ref, z_ref, dy_ref, do_ref, dz_ref,
                         dgain_ref, *, eps):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        dgain_ref[...] = jnp.zeros_like(dgain_ref)

    gain = gain_ref[...]

    def head(cols):
        o = o_ref[:, cols].astype(jnp.float32)
        z = z_ref[:, cols].astype(jnp.float32)
        dy = dy_ref[:, cols].astype(jnp.float32)
        rstd, gate = _rms_rstd(o, eps), jax.nn.sigmoid(z)
        n = o * rstd
        da = dy * (z * gate)
        dz_ref[:, cols] = (dy * (n * gain) * _silu_slope(z, gate)).astype(
            dz_ref.dtype)
        dn = da * gain
        do_ref[:, cols] = (rstd * (dn - n * jnp.mean(
            dn * n, -1, keepdims=True))).astype(do_ref.dtype)
        dgain_ref[...] += jnp.sum(
            (da * n).reshape(-1, _SUBLANES, _LANES), 0)

    _for_each_head(o_ref, head)


_SUMMED_OVER_TILES = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _parts(keys: int, values: int):
    """``(offset, width, the L2 norm's scale or None)`` of ``q``, ``k``
    and ``v`` in the product's columns."""
    return ((0, keys, float(_LANES) ** -0.5), (keys, keys, 1.0),
            (2 * keys, values, None))


def _conv_specs(n_taps: int, t: int, tile: int, lanes: int, at: int):
    """Block specs on the grid ``(row, block of columns, token tile)``:
    the taps' columns; of the product, whose block ``at`` is the part's
    first, the 8 rows before a tile (the first tile's are its own: the
    kernel puts zeros there), the tile and the 8 rows after it (the last
    tile's likewise); of an array of the part's own width, the tile and
    the 16 rows after it."""
    def rows(n, block, of_product=True):
        first = at if of_product else 0
        return pl.BlockSpec((None, n, lanes),
                            lambda b, c, i: (b, block(i), first + c))

    by8, by16 = tile // _SUBLANES, tile // _PACKED
    return (pl.BlockSpec((n_taps, lanes), lambda b, c, i: (0, at + c)),
            rows(_SUBLANES, lambda i: jnp.maximum(i * by8 - 1, 0)),
            rows(tile, lambda i: i),
            rows(_SUBLANES,
                 lambda i: jnp.minimum((i + 1) * by8, t // _SUBLANES - 1)),
            rows(tile, lambda i: i, of_product=False),
            rows(_PACKED,
                 lambda i: jnp.minimum((i + 1) * by16, t // _PACKED - 1),
                 of_product=False))


# Jitted with everything that is no array static, so that the layers of
# a model share one trace and one lowering of each kernel
# (``ops/flash_attention.py`` says what it costs otherwise).
@functools.partial(jax.jit, static_argnames=("keys", "dtype", "interpret"))
def _conv_fwd(qkvz, taps, *, keys, dtype, interpret):
    b, t, _ = qkvz.shape
    n_taps, conved = taps.shape
    outs = []
    for offset, width, scale in _parts(keys, conved - 2 * keys):
        lanes = _column_block(width, offset)
        tile = _token_tile(t, lanes, 4 + dtype.itemsize)
        w, before, inside, _, own, _ = _conv_specs(
            n_taps, t, tile, lanes, offset // lanes)
        outs.append(pl.pallas_call(
            functools.partial(_conv_fwd_kernel, scale=scale),
            out_shape=jax.ShapeDtypeStruct((b, t, width), dtype),
            grid=(b, width // lanes, t // tile),
            in_specs=[w, before, inside], out_specs=own,
            interpret=interpret, name="gdn_conv_fwd",
        )(taps, qkvz, qkvz))
    return tuple(outs)


@functools.partial(jax.jit, static_argnames=("keys", "interpret"))
def _conv_bwd(qkvz, taps, dq, dk, dv, whole, *, keys, interpret):
    b, t, _ = qkvz.shape
    n_taps, conved = taps.shape
    dtaps = []
    for (offset, width, scale), dy in zip(_parts(keys, conved - 2 * keys),
                                          (dq, dk, dv)):
        lanes = _column_block(width, offset)
        tile = _token_tile(t, lanes, 8 + dy.dtype.itemsize)
        w, before, inside, after, own, own_after = _conv_specs(
            n_taps, t, tile, lanes, offset // lanes)
        # ``whole`` stays in HBM: each call writes its part's columns of it
        whole, partial = pl.pallas_call(
            functools.partial(_conv_bwd_kernel, scale=scale),
            out_shape=[jax.ShapeDtypeStruct(whole.shape, whole.dtype),
                       jax.ShapeDtypeStruct((b, n_taps, _SUBLANES, width),
                                            jnp.float32)],
            grid=(b, width // lanes, t // tile),
            in_specs=[w, before, inside, after, own, own_after,
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[inside,
                       pl.BlockSpec((None, n_taps, _SUBLANES, lanes),
                                    lambda b, c, i: (b, 0, 0, c))],
            input_output_aliases={6: 0},
            compiler_params=_SUMMED_OVER_TILES,
            interpret=interpret, name="gdn_conv_bwd",
        )(taps, qkvz, qkvz, qkvz, dy, dy, whole)
        dtaps.append(partial.sum((0, 2)))
    return whole, jnp.concatenate(dtaps, -1)


def _out_norm_specs(values: int, width: int, tile: int, lanes: int):
    """Block specs on the grid ``(row, block of columns, token tile)``:
    the gain, a block of an array ``[b, T, values]`` and the same
    columns of ``z`` in the product."""
    at = (width - values) // lanes
    return (pl.BlockSpec((1, _LANES), lambda b, c, i: (0, 0)),
            pl.BlockSpec((None, tile, lanes), lambda b, c, i: (b, i, c)),
            pl.BlockSpec((None, tile, lanes), lambda b, c, i: (b, i, at + c)))


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def _out_norm_fwd(o, qkvz, gain, *, eps, interpret):
    b, t, values = o.shape
    lanes = _column_block(values, qkvz.shape[-1] - values)
    tile = _token_tile(t, lanes, 4 + 2 * o.dtype.itemsize)
    gain_spec, flat, z = _out_norm_specs(values, qkvz.shape[-1], tile, lanes)
    return pl.pallas_call(
        functools.partial(_out_norm_fwd_kernel, eps=eps),
        out_shape=jax.ShapeDtypeStruct(o.shape, o.dtype),
        grid=(b, values // lanes, t // tile),
        in_specs=[gain_spec, flat, z], out_specs=flat,
        interpret=interpret, name="gdn_out_norm_fwd",
    )(gain[None], o, qkvz)


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def _out_norm_bwd(o, qkvz, gain, dy, *, eps, interpret):
    b, t, values = o.shape
    lanes = _column_block(values, qkvz.shape[-1] - values)
    tile = _token_tile(t, lanes, 8 + 3 * o.dtype.itemsize)
    gain_spec, flat, z = _out_norm_specs(values, qkvz.shape[-1], tile, lanes)
    do, whole, dgain = pl.pallas_call(
        functools.partial(_out_norm_bwd_kernel, eps=eps),
        out_shape=[jax.ShapeDtypeStruct(o.shape, o.dtype),
                   jax.ShapeDtypeStruct(qkvz.shape, qkvz.dtype),
                   jax.ShapeDtypeStruct(
                       (b, values // lanes, _SUBLANES, _LANES), jnp.float32)],
        grid=(b, values // lanes, t // tile),
        in_specs=[gain_spec, flat, z, flat],
        out_specs=[flat, z, pl.BlockSpec((None, None, _SUBLANES, _LANES),
                                         lambda b, c, i: (b, c, 0, 0))],
        compiler_params=_SUMMED_OVER_TILES,
        interpret=interpret, name="gdn_out_norm_bwd",
    )(gain[None], o, qkvz, dy)
    return do, whole, dgain.sum((0, 1, 2))


def _check_conv(qkvz, taps, keys):
    n_taps, conved = taps.shape
    values = qkvz.shape[-1] - conved
    if (qkvz.ndim != 3 or qkvz.dtype != jnp.float32 or keys < _LANES
            or keys % _LANES or values < _LANES or values % _LANES
            or conved != 2 * keys + values):
        raise ValueError(
            f"gdn_conv: a product {qkvz.shape} {qkvz.dtype} and taps "
            f"{taps.shape} are not float32 [q ; k ; v ; z] with q and k "
            f"{keys} wide, v and z alike and every head {_LANES} lanes")
    if not 1 <= n_taps <= _SUBLANES + 1:
        raise ValueError(
            f"gdn_conv: {n_taps} taps reach past the {_SUBLANES} rows before "
            f"a tile")
    if qkvz.shape[1] % CHUNK:
        raise ValueError(f"gdn_conv: {qkvz.shape[1]} tokens are not whole "
                         f"chunks of {CHUNK}")


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def gdn_conv(qkvz: jax.Array, taps: jax.Array, keys: int, dtype):
    """``(q, k [b, T, keys], v [b, T, values], gate)`` from the product
    ``qkvz [b, T, 2 keys + 2 values]`` and the convolution's ``taps [n,
    2 keys + values]``: the causal convolution and SiLU over ``[q ; k ;
    v]``, ``q`` and ``k`` L2-normed a head of 128 lanes (``q`` times
    ``128 ** -0.5``), in ``dtype``. ``gate`` is the product again, for
    :func:`gdn_out_norm` alone (the module docstring says why)."""
    return _conv_forward(qkvz, taps, keys, dtype)[0]


def _conv_forward(qkvz, taps, keys, dtype):
    _check_conv(qkvz, taps, keys)
    out = _conv_fwd(qkvz, taps, keys=keys, dtype=jnp.dtype(dtype),
                    interpret=_interpret())
    return (*out, qkvz), (qkvz, taps)


def _conv_backward(keys, dtype, res, cotangents):
    del dtype
    return _conv_bwd(*res, *cotangents, keys=keys, interpret=_interpret())


gdn_conv.defvjp(_conv_forward, _conv_backward)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def gdn_out_norm(o: jax.Array, gate: jax.Array, gain: jax.Array, eps: float):
    """``rms_norm(o, gain) * silu(z)`` a head of 128 lanes, in ``o``'s
    dtype, for the rule's output ``o [b, T, values]``, :func:`gdn_conv`'s
    ``gate`` (``z`` is its last ``values`` columns) and the ``gain
    [128]``. ``gate``'s cotangent holds ``z``'s columns alone."""
    return _out_norm_forward(o, gate, gain, eps)[0]


def _out_norm_forward(o, gate, gain, eps):
    if (o.ndim != 3 or gate.shape[:2] != o.shape[:2] or o.shape[2] % _LANES
            or gate.shape[2] % _LANES or gate.shape[2] <= o.shape[2]
            or gain.shape != (_LANES,) or o.shape[1] % CHUNK):
        raise ValueError(
            f"gdn_out_norm: o {o.shape}, a product {gate.shape} and a gain "
            f"{gain.shape} are not heads of {_LANES} lanes over whole "
            f"chunks of {CHUNK} tokens")
    y = _out_norm_fwd(o, gate, gain, eps=eps, interpret=_interpret())
    return y, (o, gate, gain)


def _out_norm_backward(eps, res, dy):
    return _out_norm_bwd(*res, dy.astype(res[0].dtype), eps=eps,
                         interpret=_interpret())


gdn_out_norm.defvjp(_out_norm_forward, _out_norm_backward)

"""The gated delta rule of a Gated DeltaNet linear-attention layer, in
chunks, in Pallas for TPU — forward and backward.

For one value head with keys and queries ``k_t, q_t [d_k]``, values
``v_t [d_v]``, a log-decay ``g_t <= 0`` and a write strength ``beta_t``
a token, and a state ``S [d_k, d_v]`` that starts at 0:

    S' = exp(g_t) S_{t-1};  u_t = beta_t (v_t - S'^T k_t)
    S_t = S' + k_t u_t^T;   o_t = S_t^T q_t

(the caller hands ``q`` and ``k`` already normalised and ``q`` scaled).
Token by token that is ``T`` dependent steps of rank-1 work. Here a row
is cut into chunks of ``C`` tokens (:data:`CHUNK`, 64) and a chunk is
matrix products (the WY form): with ``G_r`` the log-decays summed from the chunk's first
token to ``r`` (float32, made by the caller's ``cumsum``), ``Gam[r, s] =
exp(G_r - G_s)`` for ``s <= r`` and the chunk's entering state ``S``,

    A  = -diag(beta) (K K^T * Gam) strictly below the diagonal
    T  = (I - A)^-1                      (unit lower triangular, C x C)
    W  = T diag(beta exp(G)) K;  U = T diag(beta) V;  V' = U - W S
    O  = (Q * exp(G)) S + (Q K^T * Gam, diagonal kept) V'
    S <- exp(G_C) S + (K * exp(G_C - G))^T V'

Every exponent is a difference taken BEFORE the exponential and masked
before it (``s > r`` never reaches ``exp``), so each factor lies in (0,
1] whatever the decay.

The triangular inverse is exact block substitution, as products: the
8 x 8 diagonal blocks by ``(I + A)(I + A^2)(I + A^4)`` (``A^8 = 0``
there), then three merges ``T <- T + T A_off T`` (16, 32, 64), float32
at full precision. (The same series over the whole 64 x 64, ``A^32``
and all, cancels catastrophically when keys repeat, as a language's
do: its terms reach ``C(62, 31) beta^32``.)

Layout. ``q`` and ``k`` are ``[b, T, key heads * 128]``, ``v`` and ``o``
``[b, T, value heads * 128]``: a head is a block of 128 lanes, as the
projections and the convolution leave them and as ``W_o`` reads ``o``;
value head ``j`` reads key head ``j // (value heads / key heads)``
through its block's index map, so nothing is repeated or transposed.
The two scalars a token come as ``gb [b, value heads, T / C, 8, C]``
float32, tokens along the lanes: row 0 the cumulative log-decay ``G``,
row 1 ``beta`` (six rows of padding make the block a register high);
a kernel turns them to columns by a product with the identity, which at
full precision is exact. A grid step is ``(row, value head, block of
``_BLOCK_CHUNKS`` chunks)``: the last axis is sequential and the state
``[128, 128]`` float32 lives in VMEM scratch across it; nothing of ``[T,
T]`` and no per-token state reaches HBM.

Kept for the backward pass: the operands, and the state ENTERING each
block of 8 chunks (``[b, value heads, T / 512, 128, 128]`` float32, 67
MB a layer at 16,384 tokens and 32 heads; a state a CHUNK would be 537
MB). The forward rule names its output and those states
(:data:`SAVED_NAMES`) for a caller's remat policy, so ``gdn_fwd`` runs
once a layer a step. The backward kernel visits the blocks last to
first with the state's cotangent in VMEM scratch; in a block it first
runs the chunks forward from the kept state, leaving each chunk's ``T``,
``W``, ``V'`` and entering state in VMEM (1.2 MB), then walks them
backward. So the backward pass costs one more forward pass of the
state, never a second of ``O``. The cotangents of ``q`` and ``k`` leave
a value head each (``[b, T, value heads * 128]``); the wrapper sums the
value heads of a key head.

Matrix products take their operands in the inputs' dtype (bfloat16 in
the model) and accumulate in float32; the state, ``T``, the decays and
every sum are float32. With float32 inputs every product is at full
precision, which is what the tests hold to the recurrence tightly.

``pallas_call`` names: ``gdn_fwd``, ``gdn_bwd``. Off the TPU they run in
interpret mode. A shape that does not tile (``T`` no multiple of the
chunk, heads not 128 wide) is an error everywhere: there is no dense or
``lax.scan`` path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from sparktorch_tpu.ops.sparse_attention import _LANES, _interpret

# A head's width: keys, queries and values alike, a block of lanes.
HEAD_DIM = _LANES
# Tokens a chunk, as the published kernels take them. The model runs
# this one; the op's ``chunk`` argument is for the tests of the op.
CHUNK = 64
_ROWS = 8            # rows of a token-scalar block: G, beta and padding
_BLOCK_CHUNKS = 8    # chunks a grid step, at most
_HIGHEST = jax.lax.Precision.HIGHEST
_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))

# what the forward rule names for a caller's remat policy: the rule's
# output and the states entering each block of chunks
SAVED_NAMES = ("gdn_out", "gdn_states")


def _mm(a, b, dims, dt=jnp.float32):
    """A product with float32 accumulation, its operands in ``dt``; at
    full precision where ``dt`` is float32."""
    return jax.lax.dot_general(
        a.astype(dt), b.astype(dt), (dims, ((), ())),
        precision=_HIGHEST if dt == jnp.float32 else None,
        preferred_element_type=jnp.float32)


class _Tile:
    """The index masks of a ``C x C`` tile, made once a grid step."""

    def __init__(self, chunk: int):
        self.chunk = chunk
        self.r = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        self.s = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
        self.eye = (self.r == self.s).astype(jnp.float32)
        self.incl, self.strict = self.s <= self.r, self.s < self.r

    def same(self, n: int):
        """Whether two tokens lie in one aligned block of ``n``."""
        shift = n.bit_length() - 1
        return (self.r >> shift) == (self.s >> shift)

    def columns(self, rows):
        """``[8, C]`` rows as ``[C, 8]`` columns: a product with the
        identity, exact at full precision."""
        return _mm(self.eye, rows, _NT)

    def rows(self, columns):
        return _mm(columns, self.eye, _TN)


def _inverse(a, tile: _Tile):
    """``(I - a)^-1`` for ``a`` strictly lower triangular, by blocks."""
    a8 = jnp.where(tile.same(8), a, 0.0)
    a2 = _mm(a8, a8, _NN)
    x = tile.eye + a8
    x = x + _mm(x, a2, _NN)
    x = x + _mm(x, _mm(a2, a2, _NN), _NN)
    n = 8
    while n < tile.chunk:
        off = jnp.where(tile.same(2 * n) & ~tile.same(n), a, 0.0)
        x = x + _mm(_mm(x, off, _NN), x, _NN)
        n *= 2
    return x


class _Chunk:
    """One chunk's operands and what both passes make of them first:
    the decays' factors, ``K K^T * Gam`` and ``T``."""

    def __init__(self, q, k, v, gb, tile: _Tile):
        self.q, self.k, self.v, self.dt = q, k, v, q.dtype
        cols = tile.columns(gb)
        g_col, self.beta = cols[:, 0:1], cols[:, 1:2]
        g_row = gb[0:1, :]
        lane = jax.lax.broadcasted_iota(jnp.int32, g_row.shape, 1)
        g_last = jnp.sum(jnp.where(lane == tile.chunk - 1, g_row, 0.0),
                         axis=1, keepdims=True)
        self.gam = jnp.exp(jnp.where(tile.incl, g_col - g_row, -1e30))
        self.gam_strict = jnp.where(tile.strict, self.gam, 0.0)
        self.e_g = jnp.exp(g_col)               # exp(G), a column
        self.e_last = jnp.exp(g_last)           # exp(G_C), [1, 1]
        self.e_rest = jnp.exp(g_last - g_col)   # exp(G_C - G)
        self.m = _mm(k, k, _NT, self.dt) * self.gam_strict
        self.k_beta = self.beta * self.e_g * k  # diag(beta exp G) K
        self.v_beta = self.beta * v
        self.k_rest = self.e_rest * k           # K * exp(G_C - G)

    def inverse(self, tile):
        return _inverse(-self.beta * self.m, tile)

    def new_values(self, t, state):
        """``(W, V')`` from ``T`` and the entering state."""
        w = _mm(t, self.k_beta, _NN, self.dt)
        return w, _mm(t, self.v_beta, _NN, self.dt) - _mm(
            w, state, _NN, self.dt)

    def attention(self):
        """``Q K^T * Gam``, the diagonal kept."""
        return _mm(self.q, self.k, _NT, self.dt) * self.gam

    def next_state(self, v_new, state):
        return self.e_last * state + _mm(self.k_rest, v_new, _TN, self.dt)


def _fwd_kernel(q_ref, k_ref, v_ref, gb_ref, o_ref, kept_ref, state_ref, *,
                chunk, n_chunks):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    kept_ref[...] = state_ref[...]
    tile = _Tile(chunk)

    def one_chunk(c, carry):
        rows = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        ck = _Chunk(q_ref[rows, :], k_ref[rows, :], v_ref[rows, :],
                    gb_ref[c], tile)
        state = state_ref[...]
        _, v_new = ck.new_values(ck.inverse(tile), state)
        o = _mm(ck.e_g * ck.q, state, _NN, ck.dt) + _mm(
            ck.attention(), v_new, _NN, ck.dt)
        o_ref[rows, :] = o.astype(o_ref.dtype)
        state_ref[...] = ck.next_state(v_new, state)
        return carry

    jax.lax.fori_loop(0, n_chunks, one_chunk, 0)


def _bwd_kernel(q_ref, k_ref, v_ref, gb_ref, kept_ref, do_ref, dq_ref,
                dk_ref, dv_ref, dgb_ref, t_ref, w_ref, vn_ref, s_ref,
                dstate_ref, *, chunk, n_chunks):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        dstate_ref[...] = jnp.zeros_like(dstate_ref)

    tile = _Tile(chunk)
    rows_of = lambda c: pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
    operands = lambda c: _Chunk(q_ref[rows_of(c), :], k_ref[rows_of(c), :],
                                v_ref[rows_of(c), :], gb_ref[c], tile)

    def forward(c, state):
        ck = operands(c)
        t = ck.inverse(tile)
        w, v_new = ck.new_values(t, state)
        t_ref[c], w_ref[c], vn_ref[c], s_ref[c] = t, w, v_new, state
        return ck.next_state(v_new, state)

    jax.lax.fori_loop(0, n_chunks, forward, kept_ref[...])

    sub = jax.lax.broadcasted_iota(jnp.int32, (chunk, _ROWS), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (chunk, _ROWS), 1)
    top = jax.lax.broadcasted_iota(jnp.int32, (_ROWS, chunk), 0) == 0

    def backward(i, carry):
        c = n_chunks - 1 - i
        ck, dt = operands(c), q_ref.dtype
        t, w, v_new, state = t_ref[c], w_ref[c], vn_ref[c], s_ref[c]
        do, d_next = do_ref[rows_of(c), :], dstate_ref[...]
        p = ck.attention()
        # O = (Q exp G) S + P V';  S' = exp(G_C) S + (K exp(G_C - G))^T V'
        dv_new = _mm(p, do, _TN, dt) + _mm(ck.k_rest, d_next, _NN, dt)
        dp = _mm(do, v_new, _NT, dt)
        dp_gam = dp * ck.gam
        dq_s = _mm(do, state, _NT, dt)                      # dO S^T
        dk_rest = _mm(v_new, d_next, _NT, dt)               # V' dS'^T
        from_rest = jnp.sum(dk_rest * ck.k_rest, axis=1, keepdims=True)
        d_last = jnp.sum(from_rest, axis=0, keepdims=True) + ck.e_last * (
            jnp.sum(jnp.sum(state * d_next, axis=1, keepdims=True),
                    axis=0, keepdims=True))
        dstate_ref[...] = (_mm(ck.e_g * ck.q, do, _TN, dt)
                           + ck.e_last * d_next - _mm(w, dv_new, _TN, dt))
        # V' = U - W S;  U = T (beta V);  W = T (beta exp(G) K)
        dw = -_mm(dv_new, state, _NT, dt)
        d_t = _mm(dv_new, ck.v_beta, _NT, dt) + _mm(dw, ck.k_beta, _NT, dt)
        dv_beta = _mm(t, dv_new, _TN, dt)
        dk_beta = _mm(t, dw, _TN, dt)
        # T = (I - A)^-1;  A = -diag(beta) M;  M = K K^T * Gam (strict)
        da = jnp.where(tile.strict, _mm(_mm(t, d_t, _TN), t, _NT), 0.0)
        dm_gam = -ck.beta * da * ck.gam_strict
        # Gam[r, s] = exp(G_r - G_s): what reaches G_r less what reaches G_s
        e = dp * p - ck.beta * da * ck.m
        dq_ref[rows_of(c), :] = (_mm(dp_gam, ck.k, _NN, dt)
                                 + ck.e_g * dq_s).astype(dq_ref.dtype)
        dk_ref[rows_of(c), :] = (
            _mm(dp_gam, ck.q, _TN, dt) + ck.e_rest * dk_rest
            + _mm(dm_gam, ck.k, _NN, dt) + _mm(dm_gam, ck.k, _TN, dt)
            + ck.beta * ck.e_g * dk_beta).astype(dk_ref.dtype)
        dv_ref[rows_of(c), :] = (ck.beta * dv_beta).astype(dv_ref.dtype)
        d_g = (ck.e_g * jnp.sum(dq_s * ck.q, axis=1, keepdims=True)
               - from_rest
               + jnp.sum(dk_beta * ck.k_beta, axis=1, keepdims=True)
               + jnp.sum(e, axis=1, keepdims=True)
               + jnp.where(sub[:, 0:1] == chunk - 1, d_last, 0.0))
        d_beta = (-jnp.sum(da * ck.m, axis=1, keepdims=True)
                  + jnp.sum(dv_beta * ck.v, axis=1, keepdims=True)
                  + ck.e_g * jnp.sum(dk_beta * ck.k, axis=1, keepdims=True))
        columns = jnp.where(lane == 0, d_g, jnp.where(lane == 1, d_beta, 0.0))
        dgb_ref[c] = tile.rows(columns) - jnp.where(
            top, jnp.sum(e, axis=0, keepdims=True), 0.0)
        return carry

    jax.lax.fori_loop(0, n_chunks, backward, 0)


def _block_chunks(t: int, chunk: int) -> int:
    """Chunks a grid step: the largest power of two up to
    ``_BLOCK_CHUNKS`` that divides the row's chunks."""
    n = 1
    while n < _BLOCK_CHUNKS and (t // chunk) % (2 * n) == 0:
        n *= 2
    return n


def _shapes(q, v, chunk):
    b, t, f = v.shape
    heads = f // _LANES
    ratio = heads // (q.shape[-1] // _LANES)
    n_chunks = _block_chunks(t, chunk)
    return b, t, heads, ratio, n_chunks, t // (n_chunks * chunk)


def _specs(ratio, chunk, n_chunks, n_blocks, backward: bool):
    """Block specs on the grid ``(row, value head, block of chunks)``;
    the backward kernel visits the blocks last to first."""
    at = (lambda i: n_blocks - 1 - i) if backward else (lambda i: i)
    tokens = n_chunks * chunk
    key = pl.BlockSpec((None, tokens, _LANES),
                       lambda b, h, i: (b, at(i), h // ratio))
    value = pl.BlockSpec((None, tokens, _LANES),
                         lambda b, h, i: (b, at(i), h))
    scalars = pl.BlockSpec((None, None, n_chunks, _ROWS, chunk),
                           lambda b, h, i: (b, h, at(i), 0, 0))
    kept = pl.BlockSpec((None, None, None, _LANES, _LANES),
                        lambda b, h, i: (b, h, at(i), 0, 0))
    return key, value, scalars, kept


_SEQUENTIAL = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _fwd(q, k, v, gb, chunk):
    b, t, heads, ratio, n_chunks, n_blocks = _shapes(q, v, chunk)
    key, value, scalars, kept = _specs(ratio, chunk, n_chunks, n_blocks,
                                       backward=False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, n_chunks=n_chunks),
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((b, heads, n_blocks, _LANES, _LANES),
                                        jnp.float32)],
        grid=(b, heads, n_blocks),
        in_specs=[key, key, value, scalars], out_specs=[value, kept],
        scratch_shapes=[pltpu.VMEM((_LANES, _LANES), jnp.float32)],
        compiler_params=_SEQUENTIAL, interpret=_interpret(), name="gdn_fwd",
    )(q, k, v, gb)


def _bwd(q, k, v, gb, states, do, chunk):
    b, t, heads, ratio, n_chunks, n_blocks = _shapes(q, v, chunk)
    key, value, scalars, kept = _specs(ratio, chunk, n_chunks, n_blocks,
                                       backward=True)
    f32 = lambda *shape: pltpu.VMEM(shape, jnp.float32)
    dq, dk, dv, dgb = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, n_chunks=n_chunks),
        out_shape=[jax.ShapeDtypeStruct(v.shape, q.dtype),
                   jax.ShapeDtypeStruct(v.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(gb.shape, jnp.float32)],
        grid=(b, heads, n_blocks),
        in_specs=[key, key, value, scalars, kept, value],
        out_specs=[value, value, value, scalars],
        scratch_shapes=[f32(n_chunks, chunk, chunk),
                        f32(n_chunks, chunk, _LANES),
                        f32(n_chunks, chunk, _LANES),
                        f32(n_chunks, _LANES, _LANES),
                        f32(_LANES, _LANES)],
        compiler_params=_SEQUENTIAL, interpret=_interpret(), name="gdn_bwd",
    )(q, k, v, gb, states, do)
    # the value heads of a key head: adjacent blocks of 128 lanes
    by_key = lambda d: jnp.sum(d.astype(jnp.float32).reshape(
        b, t, heads // ratio, ratio, _LANES), 3).reshape(q.shape).astype(
            q.dtype)
    return by_key(dq), by_key(dk), dv, dgb


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _rule(q, k, v, gb, chunk):
    return _forward(q, k, v, gb, chunk)[0]


def _forward(q, k, v, gb, chunk):
    o, states = _fwd(q, k, v, gb, chunk)
    o = checkpoint_name(o, SAVED_NAMES[0])
    states = checkpoint_name(states, SAVED_NAMES[1])
    return o, (q, k, v, gb, states)


def _backward(chunk, res, do):
    q, k, v, gb, states = res
    return _bwd(q, k, v, gb, states, do.astype(v.dtype), chunk)


_rule.defvjp(_forward, _backward)


def chunks_run(b: int, t: int, heads: int, chunk: int = CHUNK) -> int:
    """The chunks ``gdn_fwd`` runs for ``b`` rows of ``t`` tokens."""
    return b * heads * (t // chunk)


def _check(q, k, v, g, beta, chunk):
    b, t, f = v.shape
    if (q.shape != k.shape or q.shape[:2] != (b, t) or f % _LANES
            or q.shape[2] % _LANES or not q.shape[2]
            or (f // _LANES) % (q.shape[2] // _LANES)):
        raise ValueError(
            f"gated_delta_rule: q {q.shape}, k {k.shape}, v {v.shape} are "
            f"not key heads and a multiple of them of value heads, each "
            f"{_LANES} wide")
    if g.shape != (b, t, f // _LANES) or beta.shape != g.shape:
        raise ValueError(
            f"gated_delta_rule: g {g.shape} and beta {beta.shape} are not "
            f"one number a token a value head, {(b, t, f // _LANES)}")
    if chunk < _ROWS or chunk & (chunk - 1) or chunk > _LANES:
        raise ValueError(f"gated_delta_rule: a chunk of {chunk} tokens is "
                         f"no power of two from {_ROWS} to {_LANES}")
    if t % chunk:
        raise ValueError(f"gated_delta_rule: {t} tokens are not whole "
                         f"chunks of {chunk}")


def gated_delta_rule(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                     beta: jax.Array, chunk: int = CHUNK) -> jax.Array:
    """``o [b, T, value heads * 128]`` of the gated delta rule (the
    module docstring's recurrence, state 0 at each row's start) from
    ``q`` and ``k [b, T, key heads * 128]`` (normalised, ``q`` scaled),
    ``v [b, T, value heads * 128]``, the log-decays ``g`` and the write
    strengths ``beta``, ``[b, T, value heads]`` float32; value head ``j``
    reads key head ``j // (value heads / key heads)``. ``chunk`` is
    static. Differentiable in all five."""
    _check(q, k, v, g, beta, chunk)
    b, t, heads = g.shape
    by_chunk = lambda x: jnp.swapaxes(x.astype(jnp.float32), 1, 2).reshape(
        b, heads, t // chunk, 1, chunk)
    gb = jnp.concatenate(
        [jnp.cumsum(by_chunk(g), -1), by_chunk(beta),
         jnp.zeros((b, heads, t // chunk, _ROWS - 2, chunk), jnp.float32)],
        3)
    return _rule(q, k, v, gb, chunk)

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
a kernel turns a block's to columns by ONE product with the identity,
which at full precision is exact. A grid step is ``(row, value head,
block of ``_BLOCK_CHUNKS`` chunks)``: the last axis is sequential and
the state ``[128, 128]`` float32 lives in VMEM scratch across it;
nothing of ``[T, T]`` and no per-token state reaches HBM.

A grid step has state-free phases and serial loops. Of a chunk's
products only ``W S``, ``Q exp(G) S``, ``P V'`` and the next state read
the state; ``K K^T * Gam``, the ten products of the inverse, ``W``, ``U``
and ``P = Q K^T * Gam`` depend on nothing a previous chunk made. Inside
the loop that carries the state they were one chunk's dependent chain
(the inverse is nine products deep) for four matrix units to wait on;
so a kernel first makes them for ALL of the block's chunks, batched over
the axis of chunks (:class:`_Chunks`), leaves what the loop reads in
VMEM (0.8 MiB), and the loop is three products a chunk forward. The
backward kernel has the same shape twice over: the state's cotangent
chain is two products a chunk (``dV' = P^T dO + K exp(G_C - G) dS'``,
then ``dS = (Q exp G)^T dO + exp(G_C) dS' - W^T dV'``), and the other
sixteen and ``da = T^T dT T^T`` are made for all chunks after it.

Kept for the backward pass: the operands, the state ENTERING each block
of 8 chunks (``[b, value heads, T / 512, 128, 128]`` float32, 67 MB a
layer at 16,384 tokens and 32 heads; a state a CHUNK would be 537 MB)
and each chunk's ``T`` (``[b, value heads, T / C, 32, 128]`` float32: a
chunk's upper 32 rows beside its lower 32, so HBM's (8, 128) tiles hold
no padding; 134 MB a layer, 0.33 ms to write and read back). The
inverse is 60 of a chunk's 74 matrix-unit passes forward, and the
backward kernel, which needs ``T`` for ``W``, ``U`` and ``da``, inverted
again until PR 43: 11.0 ms of its 32.3 a layer at the cell's shape (TPU
v5e, the kernels alone). The forward rule names its output, those states
and ``T`` (:data:`SAVED_NAMES`) for a caller's remat policy, so
``gdn_fwd`` runs once a layer a step; a policy that keeps none of them
runs it twice and makes ``T`` twice. The backward kernel visits the
blocks last to first with the state's cotangent in VMEM scratch; in a
block it makes ``W`` and ``U`` of every chunk from the ``T`` kept (no
``K K^T`` and no inverse), runs the chunks forward from the kept state
for each chunk's ``V'`` and entering state (2.6 MiB of VMEM with the
cotangents), walks the cotangent's chain backward, and finishes all
chunks at once. So the backward pass costs one more forward pass of the
state, never a second of ``O`` or of ``T``. With both, at 1 x 16,384 x
16 / 32 heads: ``gdn_fwd`` 17.6 -> 9.6 ms and ``gdn_bwd`` 32.3 -> 12.7
(the kernels alone, PR 43), to the bit the same results. The cotangents
of ``q`` and ``k`` leave a value head each (``[b, T, value heads *
128]``); the wrapper sums the value heads of a key head.

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
# output, the states entering each block of chunks and each chunk's T
SAVED_NAMES = ("gdn_out", "gdn_states", "gdn_t")


def _mm(a, b, dims, dt=jnp.float32):
    """A product with float32 accumulation, its operands in ``dt``; at
    full precision where ``dt`` is float32. ``dims`` name the contracted
    axes of one chunk's matrices; an axis of chunks before them is a
    batch."""
    lead = a.ndim - 2
    (of_a,), (of_b,) = dims
    batch = tuple(range(lead))
    return jax.lax.dot_general(
        a.astype(dt), b.astype(dt),
        (((of_a + lead,), (of_b + lead,)), (batch, batch)),
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
        """``[n, C]`` rows as ``[C, n]`` columns: a product with the
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


def _fold(chunk: int) -> int:
    """The pieces of 8 rows or more a chunk's ``T`` is kept in, side by
    side: ``[C / fold, C * fold]`` is 128 lanes wide at 32 and 64, which
    HBM's (8, 128) tiles hold without padding."""
    return min(_LANES // chunk, chunk // _ROWS)


def _folded(t):
    fold = _fold(t.shape[-1])
    rows = t.shape[-1] // fold
    return jnp.concatenate(
        [t[..., i * rows:(i + 1) * rows, :] for i in range(fold)], axis=-1)


def _unfolded(kept, chunk: int):
    return jnp.concatenate(
        [kept[..., i * chunk:(i + 1) * chunk] for i in range(_fold(chunk))],
        axis=-2)


class _Chunks:
    """A block's chunks at once, an axis of chunks before every array's
    own: the operands, the decays' factors and the products no state
    enters. The chunks are independent work, which is what fills the
    matrix units; one product turns the scalars of them all."""

    def __init__(self, q_ref, k_ref, v_ref, gb_ref, tile: _Tile):
        n, chunk = gb_ref.shape[0], tile.chunk
        by_chunk = lambda ref: ref[...].reshape(n, chunk, _LANES)
        self.q, self.k, self.v = by_chunk(q_ref), by_chunk(k_ref), by_chunk(
            v_ref)
        self.dt, self.tile = self.q.dtype, tile
        cols = tile.columns(gb_ref[...].reshape(n * _ROWS, chunk))
        cols = jnp.stack([cols[:, c * _ROWS:(c + 1) * _ROWS]
                          for c in range(n)])
        self.g_col, self.beta = cols[..., 0:1], cols[..., 1:2]
        self.g_row = gb_ref[:, 0:1, :]
        lane = jax.lax.broadcasted_iota(jnp.int32, self.g_row.shape, 2)
        g_last = jnp.sum(jnp.where(lane == chunk - 1, self.g_row, 0.0),
                         axis=-1, keepdims=True)
        self.e_g = jnp.exp(self.g_col)               # exp(G), a column
        self.e_last = jnp.exp(g_last)                # exp(G_C), [n, 1, 1]
        self.e_rest = jnp.exp(g_last - self.g_col)   # exp(G_C - G)
        self.k_beta = self.beta * self.e_g * self.k  # diag(beta exp G) K
        self.v_beta = self.beta * self.v
        self.k_rest = self.e_rest * self.k           # K * exp(G_C - G)
        self.gam = jnp.exp(jnp.where(tile.incl, self.g_col - self.g_row,
                                     -1e30))
        self.gam_strict = jnp.where(tile.strict, self.gam, 0.0)

    @functools.cached_property
    def m(self):
        """``K K^T * Gam`` strictly below the diagonal."""
        return _mm(self.k, self.k, _NT, self.dt) * self.gam_strict

    def inverse(self):
        return _inverse(-self.beta * self.m, self.tile)

    def attention(self):
        """``P = Q K^T * Gam``, the diagonal kept."""
        return _mm(self.q, self.k, _NT, self.dt) * self.gam

    def for_the_loops(self, t, w_ref, u_ref, kr_ref, last_ref):
        """Leaves in VMEM what both kernels' loops over the state read of
        each chunk: ``W`` and ``U`` from ``T``, ``K exp(G_C - G)`` and
        ``exp(G_C)``; what enters a product, in the operands' dtype."""
        w_ref[...] = _mm(t, self.k_beta, _NN, self.dt).astype(self.dt)
        u_ref[...] = _mm(t, self.v_beta, _NN, self.dt)
        kr_ref[...] = self.k_rest.astype(self.dt)
        last_ref[...] = jnp.broadcast_to(self.e_last, last_ref.shape)


def _fwd_kernel(q_ref, k_ref, v_ref, gb_ref, o_ref, kept_ref, t_ref, wq_ref,
                u_ref, kr_ref, last_ref, p_ref, state_ref, *, chunk, n_chunks):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    kept_ref[...] = state_ref[...]
    ck = _Chunks(q_ref, k_ref, v_ref, gb_ref, _Tile(chunk))
    t, dt = ck.inverse(), ck.dt
    t_ref[...] = _folded(t)
    # [W ; Q exp G], one operand of 2 C rows: W S and Q exp(G) S are one
    # product
    ck.for_the_loops(t, wq_ref.at[:, :chunk, :], u_ref, kr_ref, last_ref)
    wq_ref[:, chunk:, :] = (ck.e_g * ck.q).astype(dt)
    p_ref[...] = ck.attention().astype(dt)

    def one_chunk(c, carry):
        state = state_ref[...]
        ws_qs = _mm(wq_ref[c], state, _NN, dt)          # [W S ; Q exp(G) S]
        v_new = u_ref[c] - ws_qs[:chunk]
        o = ws_qs[chunk:] + _mm(p_ref[c], v_new, _NN, dt)
        o_ref[pl.ds(pl.multiple_of(c * chunk, chunk), chunk), :] = o.astype(
            o_ref.dtype)
        state_ref[...] = last_ref[c] * state + _mm(kr_ref[c], v_new, _TN, dt)
        return carry

    jax.lax.fori_loop(0, n_chunks, one_chunk, 0)


def _bwd_kernel(q_ref, k_ref, v_ref, gb_ref, kept_ref, t_ref, do_ref, dq_ref,
                dk_ref, dv_ref, dgb_ref, w_ref, vn_ref, kr_ref, last_ref,
                ptdo_ref, qtdo_ref, s_ref, dvn_ref, dn_ref, dstate_ref, *,
                chunk, n_chunks):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        dstate_ref[...] = jnp.zeros_like(dstate_ref)

    tile = _Tile(chunk)
    ck = _Chunks(q_ref, k_ref, v_ref, gb_ref, tile)
    t, dt = _unfolded(t_ref[...], chunk), ck.dt
    do = do_ref[...].reshape(n_chunks, chunk, _LANES)
    p = ck.attention()
    ck.for_the_loops(t, w_ref, vn_ref, kr_ref, last_ref)
    # the terms of the cotangent's chain that no cotangent enters
    ptdo_ref[...] = _mm(p, do, _TN, dt)
    qtdo_ref[...] = _mm(ck.e_g * ck.q, do, _TN, dt)

    # the chunks forward from the state kept: each chunk's V' (over its
    # U) and entering state
    def forward(c, state):
        v_new = vn_ref[c] - _mm(w_ref[c], state, _NN, dt)
        vn_ref[c], s_ref[c] = v_new, state
        return last_ref[c] * state + _mm(kr_ref[c], v_new, _TN, dt)

    jax.lax.fori_loop(0, n_chunks, forward, kept_ref[...])

    # and backward, the state's cotangent alone: with O = (Q exp G) S +
    # P V', V' = U - W S and S' = exp(G_C) S + (K exp(G_C - G))^T V', a
    # chunk's dV' and the cotangent leaving it
    def backward(i, d_next):
        c = n_chunks - 1 - i
        dv_new = ptdo_ref[c] + _mm(kr_ref[c], d_next, _NN, dt)
        dvn_ref[c], dn_ref[c] = dv_new, d_next
        return (qtdo_ref[c] + last_ref[c] * d_next
                - _mm(w_ref[c], dv_new, _TN, dt))

    dstate_ref[...] = jax.lax.fori_loop(0, n_chunks, backward,
                                        dstate_ref[...])

    # every chunk's cotangents from its dV' and dS', the chunks at once
    v_new, state, dv_new, d_next = (vn_ref[...], s_ref[...], dvn_ref[...],
                                    dn_ref[...])
    total = lambda x: jnp.sum(x, axis=-1, keepdims=True)
    dp = _mm(do, v_new, _NT, dt)
    dp_gam = dp * ck.gam
    dq_s = _mm(do, state, _NT, dt)                      # dO S^T
    dk_rest = _mm(v_new, d_next, _NT, dt)               # V' dS'^T
    from_rest = total(dk_rest * ck.k_rest)
    d_last = jnp.sum(from_rest, axis=-2, keepdims=True) + ck.e_last * (
        jnp.sum(total(state * d_next), axis=-2, keepdims=True))
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
    flat = lambda x, ref: x.astype(ref.dtype).reshape(ref.shape)
    dq_ref[...] = flat(_mm(dp_gam, ck.k, _NN, dt) + ck.e_g * dq_s, dq_ref)
    dk_ref[...] = flat(
        _mm(dp_gam, ck.q, _TN, dt) + ck.e_rest * dk_rest
        + _mm(dm_gam, ck.k, _NN, dt) + _mm(dm_gam, ck.k, _TN, dt)
        + ck.beta * ck.e_g * dk_beta, dk_ref)
    dv_ref[...] = flat(ck.beta * dv_beta, dv_ref)
    last = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0) == chunk - 1
    d_g = (ck.e_g * total(dq_s * ck.q) - from_rest
           + total(dk_beta * ck.k_beta) + total(e)
           + jnp.where(last, d_last, 0.0))
    d_beta = (-total(da * ck.m) + total(dv_beta * ck.v)
              + ck.e_g * total(dk_beta * ck.k))
    # a chunk's two columns and six of padding, every chunk's turned back
    # to rows by one product
    padding = jnp.zeros((chunk, _ROWS - 2), jnp.float32)
    columns = jnp.concatenate(
        [x for c in range(n_chunks) for x in (d_g[c], d_beta[c], padding)],
        axis=1)
    top = jax.lax.broadcasted_iota(jnp.int32, (_ROWS, chunk), 0) == 0
    dgb_ref[...] = tile.rows(columns).reshape(dgb_ref.shape) - jnp.where(
        top, jnp.sum(e, axis=-2, keepdims=True), 0.0)


def _block_chunks(t: int, chunk: int) -> int:
    """Chunks a grid step: the largest power of two up to
    ``_BLOCK_CHUNKS`` that divides the row's chunks."""
    n = 1
    while n < _BLOCK_CHUNKS and (t // chunk) % (2 * n) == 0:
        n *= 2
    return n


def _t_shape(chunk: int):
    """A chunk's ``T`` as it is kept (:func:`_fold`)."""
    return chunk // _fold(chunk), chunk * _fold(chunk)


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
    inverses = pl.BlockSpec((None, None, n_chunks, *_t_shape(chunk)),
                            lambda b, h, i: (b, h, at(i), 0, 0))
    return key, value, scalars, kept, inverses


def _vmem(dtype, *shape):
    return pltpu.VMEM(shape, dtype)


_SEQUENTIAL = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _fwd(q, k, v, gb, chunk):
    b, t, heads, ratio, n_chunks, n_blocks = _shapes(q, v, chunk)
    key, value, scalars, kept, inverses = _specs(
        ratio, chunk, n_chunks, n_blocks, backward=False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, n_chunks=n_chunks),
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((b, heads, n_blocks, _LANES, _LANES),
                                        jnp.float32),
                   jax.ShapeDtypeStruct(
                       (b, heads, t // chunk, *_t_shape(chunk)), jnp.float32)],
        grid=(b, heads, n_blocks),
        in_specs=[key, key, value, scalars],
        out_specs=[value, kept, inverses],
        scratch_shapes=[
            _vmem(q.dtype, n_chunks, 2 * chunk, _LANES),    # [W ; Q exp G]
            _vmem(jnp.float32, n_chunks, chunk, _LANES),    # U
            _vmem(q.dtype, n_chunks, chunk, _LANES),        # K exp(G_C - G)
            _vmem(jnp.float32, n_chunks, 1, _LANES),        # exp(G_C)
            _vmem(q.dtype, n_chunks, chunk, chunk),         # P
            _vmem(jnp.float32, _LANES, _LANES)],            # the state
        compiler_params=_SEQUENTIAL, interpret=_interpret(), name="gdn_fwd",
    )(q, k, v, gb)


def _bwd(q, k, v, gb, states, inverses, do, chunk):
    b, t, heads, ratio, n_chunks, n_blocks = _shapes(q, v, chunk)
    key, value, scalars, kept, kept_t = _specs(
        ratio, chunk, n_chunks, n_blocks, backward=True)
    f32 = functools.partial(_vmem, jnp.float32)
    dq, dk, dv, dgb = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, n_chunks=n_chunks),
        out_shape=[jax.ShapeDtypeStruct(v.shape, q.dtype),
                   jax.ShapeDtypeStruct(v.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(gb.shape, jnp.float32)],
        grid=(b, heads, n_blocks),
        in_specs=[key, key, value, scalars, kept, kept_t, value],
        out_specs=[value, value, value, scalars],
        scratch_shapes=[
            _vmem(q.dtype, n_chunks, chunk, _LANES),    # W
            f32(n_chunks, chunk, _LANES),               # U, then V'
            _vmem(q.dtype, n_chunks, chunk, _LANES),    # K exp(G_C - G)
            f32(n_chunks, 1, _LANES),                   # exp(G_C)
            f32(n_chunks, chunk, _LANES),               # P^T dO
            f32(n_chunks, _LANES, _LANES),              # (Q exp G)^T dO
            f32(n_chunks, _LANES, _LANES),              # entering states
            f32(n_chunks, chunk, _LANES),               # dV'
            f32(n_chunks, _LANES, _LANES),              # dS' leaving a chunk
            f32(_LANES, _LANES)],                       # the state's cotangent
        compiler_params=_SEQUENTIAL, interpret=_interpret(), name="gdn_bwd",
    )(q, k, v, gb, states, inverses, do)
    # the value heads of a key head: adjacent blocks of 128 lanes
    by_key = lambda d: jnp.sum(d.astype(jnp.float32).reshape(
        b, t, heads // ratio, ratio, _LANES), 3).reshape(q.shape).astype(
            q.dtype)
    return by_key(dq), by_key(dk), dv, dgb


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _rule(q, k, v, gb, chunk):
    return _forward(q, k, v, gb, chunk)[0]


def _forward(q, k, v, gb, chunk):
    o, states, inverses = (checkpoint_name(x, name) for x, name in zip(
        _fwd(q, k, v, gb, chunk), SAVED_NAMES))
    return o, (q, k, v, gb, states, inverses)


def _backward(chunk, res, do):
    *kept, do = *res, do.astype(res[2].dtype)
    return _bwd(*kept, do, chunk)


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

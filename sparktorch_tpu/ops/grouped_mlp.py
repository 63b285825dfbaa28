"""The held experts' products on a chunk of sorted pairs, as Pallas
grouped-matmul kernels over the row tiles that hold a live row, for TPU.

A chunk is ``chunk`` rows sorted by expert: the rows of group 0, then of
group 1, ... (``sizes [groups]`` rows each), then rows of no group (pairs
of experts held elsewhere). The kernels tile the rows by ``tile`` and
walk a table of VISITS made from ``sizes`` on the device
(:func:`visit_table`) that reaches them by scalar prefetch: a visit is
one (row tile, group) pair with a row in common, in row order, so a tile
that three experts share is visited three times, each time under the
mask of that group's rows, and a tile with no live row is never read.
The grid is static (``chunk / tile + groups - 1`` visits, the most a
chunk can take): steps past the last visit run nothing and their blocks'
index maps repeat the last visit's, so they move nothing either.

Forward, two kernels a chunk:

- ``moe_gmm_in``: ``xs [chunk, d] x (w_gate[e], w_up[e]) [d, f]``,
  float32 sums in VMEM, epilogue ``silu(a) * b``: writes ``hidden
  [chunk, f]`` in the compute dtype once;
- ``moe_gmm_down``: ``hidden x w_down[e] [f, d]``, epilogue ``* gate``:
  writes ``gate * ys [chunk, d]`` float32, which :func:`sum_back` adds
  to the tokens' sums.

Backward, four:

- ``moe_gmm_bwd_hidden``: recomputes ``a``, ``b``, takes ``d_hidden = dy
  x w_down[e]^T`` for the same rows and writes ``d_a``, ``d_b`` and
  ``hidden * gate`` ``[chunk, f]`` in the compute dtype and ``d_gate
  [chunk, 1] = sum(hidden * d_hidden)`` float32;
- ``moe_gmm_dx``: ``d_a x w_gate[e]^T + d_b x w_up[e]^T`` ``[chunk, d]``
  float32, for :func:`sum_back` too;
- ``moe_gmm_dw_in`` and ``moe_gmm_dw_down``: ``xs^T d_a``, ``xs^T d_b``
  and ``(hidden * gate)^T dy`` over each group's rows, ADDED in place
  (``input_output_aliases``) to float32 sums ``[groups, d, f]`` /
  ``[groups, f, d]`` that the caller carries; the block of a group with
  no row in the chunk is never fetched nor written.

The rows' way back to their tokens, :func:`sum_back` (``moe_sum_back``),
walks the same table: the tokens' float32 sums stay in HBM, and a live
visit fetches the sums of its rows of its group by one DMA a row into
VMEM, adds the rows and writes them back by one DMA a row, all of a
visit's in flight at once. They are independent because the layer's sort
leaves one expert's rows ascending in token and ``top_k`` gives a token
an expert once; two experts may hold the same token, so a visit's writes
land before the next visit reads. No row of a tile without a live row is
written, read or moved (XLA's scatter-add of the whole chunk, which this
replaced, sorted the chunk's indices, gathered all its rows into that
order, passed over every token's sum and added the rows one by one,
zeros and all: PERF.md section 6, PR 48).

The rows' way in, :func:`fetch_rows` (``moe_fetch_rows``), is its
mirror and needs the live rows' count alone: its grid is the chunk's row
tiles, the sources (``x``, and ``d_out`` beside it in the backward pass,
as :func:`fetch_source` lays them out in one pass of a ninth kernel,
``moe_fetch_source``: a token's row whole tiles of its own) stay in HBM,
and a tile that holds a live row is fetched WHOLE by one DMA a row and
source, all of a tile's in flight at once, and turned
into the ``[tile, d]`` block the products read. A tile past the live
rows is neither fetched nor written. XLA's gathers of the whole chunk,
``x[token]`` forward and ``x[token]`` and ``d_out[token]`` backward,
which this replaced, paid by the row for as many rows of experts held
elsewhere as rows any kernel reads (PERF.md section 6, PR 51).

What a caller may rely on: the fetched rows are ``x[token]`` bit for
bit on every row of a tile with a live row (its rows of no group too,
under a tile of them) and NOTHING on the other tiles, which the kernels
below never read; every row of ``d_gate`` is WRITTEN, zeros
where the row is of no group (tiles without a live row are visited for
that alone, after the live ones: a store and no read); ``gate * ys``,
``dx``'s rows, ``hidden``, ``d_a``, ``d_b`` and ``hidden * gate`` are
written in the visited tiles only (zeros in their rows of no group) and
read under the same table only, by these kernels or by
:func:`sum_back`. Operands enter every product in the compute dtype,
every sum and every epilogue is float32; ``tests/test_grouped_mlp.py``
holds each kernel and the sum back to the same arithmetic in plain
``jax.numpy`` on whole arrays, and the fetch to ``x[token]``'s bits.

Blocks. A kernel's grid is ``(visits, column blocks)``, the columns
inside: a row tile's operands stay in VMEM over its column blocks, an
expert's weights over its consecutive visits, and an output row tile is
one block over all columns (a step stores its columns' slice), so that
the visits of a shared tile are consecutive steps of one resident block.
The weight gradients' grid is ``(row blocks, column blocks, visits)``,
the visits inside, a group's product summed in VMEM and added to the
carried sum at the group's last visit. Widths come from the shapes alone
(:func:`row_tile`, ``_widest``). Off the TPU the kernels run in
interpret mode; a chunk that ``tile`` does not divide is an error
everywhere: there is no other path.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from sparktorch_tpu.ops.sparse_attention import _LANES, _interpret

# Rows a tile where an expert expects that many or more; an expert that
# expects fewer takes the power of two below its expectation, down to
# ``_MIN_ROW_TILE`` (the MXU's side: a narrower tile only pads).
_ROW_TILE = 512
_MIN_ROW_TILE = 128
# What a kernel's blocks (the pipeline's two copies of each) and the
# float32 values it holds between them may take of VMEM, by the count
# each kernel's wrapper makes from its shapes: a kernel takes the widest
# column blocks that fit, the whole width where it can, because an
# expert's matrix that is ONE block stays in VMEM over the expert's
# visits and is fetched in whole rows (at LFM2's widths, 2,048 / 1,792,
# ``moe_gmm_down`` takes 1.04 ms a chunk of 16,384 live rows whole and
# 1.55 in four column blocks, ``moe_gmm_dx`` 1.76 and 2.53: TPU v5e,
# PR 47). The limit is what the compiler is told (its own, 16 MiB, is
# under one expert's blocks; the chip has 128 MiB).
_VMEM_BUDGET = 56 << 20
_VMEM_LIMIT = 100 << 20

_TABLE = 6  # scalar-prefetch operands of every kernel: visit_table's


def row_tile(chunk: int, expected_rows: int) -> int:
    """Rows a tile for chunks of ``chunk`` rows of which an expert
    expects ``expected_rows``: the largest power of two that divides
    ``chunk``, is at most ``_ROW_TILE`` and, above ``_MIN_ROW_TILE``, at
    most ``expected_rows``."""
    tile = 1
    while (chunk % (2 * tile) == 0 and 2 * tile <= _ROW_TILE
           and 2 * tile <= max(expected_rows, _MIN_ROW_TILE)):
        tile *= 2
    return tile


def _blocks_of(width: int, block) -> int:
    """How many blocks of ``block`` columns ``width`` is: a width given
    by hand that does not divide it is an error, no ragged last block."""
    if width % block:
        raise ValueError(f"blocks of {block} do not divide a width of "
                         f"{width}")
    return width // block


def _widest(width: int, fits) -> int:
    """The widest block of ``width`` columns, a multiple of 128 lanes
    that divides it, whose kernel ``fits(columns)`` says fits VMEM; the
    narrowest such block where none does, and ``width`` itself where 128
    lanes do not divide it (the compiler then says what it takes)."""
    blocks = [c for c in range(width, 0, -_LANES)
              if width % c == 0 and c % _LANES == 0]
    return next((c for c in blocks if fits(c)), blocks[-1] if blocks
                else width)


def _running(a):
    """``cumsum`` of a short int32 vector by comparison, one fusion: a
    scan, like a gather of scalars, is passes of its own on the TPU
    (PERF.md section 6, PR 32)."""
    upto = jnp.arange(a.size)[:, None] >= jnp.arange(a.size)[None, :]
    return jnp.sum(jnp.where(upto, a[None, :], 0), -1, dtype=jnp.int32)


def _tiles_taken(sizes, tile: int):
    """``(ends, takes)``: the row each group ends at, and the row tiles
    it has a row in (none for an empty group)."""
    ends = _running(sizes)
    return ends, jnp.where(
        sizes > 0, (ends - 1) // tile - (ends - sizes) // tile + 1, 0)


def tiles_visited(sizes, tile: int):
    """The visits :func:`visit_table` gives the kernels for ``sizes``:
    each group's tiles, a shared tile once a group."""
    return jnp.sum(_tiles_taken(sizes, tile)[1])


def visit_table(sizes, chunk: int, tile: int):
    """The kernels' table for a chunk whose groups hold ``sizes`` rows
    (int32 ``[groups]``, in row order from row 0): six int32 arrays,
    ``group``, ``tile``, ``tile_out``, ``lo``, ``hi`` of ``[visits]``
    and ``counts [2]``.

    Visit ``v < counts[0]`` is row tile ``tile[v]`` under group
    ``group[v]``, whose rows are ``[lo[v], hi[v])`` of the chunk; the
    visits run by group, then by tile, so a tile's visits are
    consecutive. Visits ``counts[0] <= v < counts[1]`` are the tiles
    without a live row, ``tile_out[v]``, for the kernel that writes
    zeros there (``d_gate``'s). Past its range each array repeats its
    last entry in range (``tile`` and ``group`` the last live visit's,
    ``tile_out`` the last tile's), which is what keeps a block where it
    is."""
    if chunk % tile:
        raise ValueError(f"a row tile of {tile} does not divide the chunk "
                         f"of {chunk} rows")
    n_tiles, groups = chunk // tile, sizes.size
    ends, takes = _tiles_taken(sizes, tile)
    visit_ends = _running(takes)
    n_live, live_tiles = visit_ends[-1], -(-ends[-1] // tile)
    v = jnp.arange(n_tiles + groups - 1, dtype=jnp.int32)
    at = jnp.minimum(v, jnp.maximum(n_live - 1, 0))
    group = jnp.minimum(jnp.sum(at[:, None] >= visit_ends[None, :], -1,
                                dtype=jnp.int32), groups - 1)
    # the group's entry by a one-hot sum, no gather
    hot = group[:, None] == jnp.arange(groups)[None, :]
    of = lambda a: jnp.sum(jnp.where(hot, a[None, :], 0), -1, dtype=jnp.int32)
    row_tile_of = of((ends - sizes) // tile) + at - of(visit_ends - takes)
    live = v < n_live
    return (group, row_tile_of,
            jnp.where(live, row_tile_of, jnp.minimum(
                live_tiles + v - n_live, n_tiles - 1)).astype(jnp.int32),
            jnp.where(live, of(ends - sizes), 0), jnp.where(live, of(ends), 0),
            jnp.stack([n_live, n_live + n_tiles - live_tiles]).astype(
                jnp.int32))


# -- what every kernel does with the table ------------------------------------


class _Visit:
    """The grid step's visit, read from the prefetched table: whether it
    is a live visit, a tile of zeros or nothing, whether its tile (and
    its group) is met for the first or the last time, whether the whole
    tile is its group's, and the mask of its group's rows."""

    def __init__(self, table, rows: int, axis: int = 0):
        self.group, self.tile, self.tile_out, self.lo, self.hi, counts = table
        self.rows = rows
        v = self.v = pl.program_id(axis)
        last = pl.num_programs(axis) - 1
        before, after = jnp.maximum(v - 1, 0), jnp.minimum(v + 1, last)
        self.live = v < counts[0]
        self.zeros = (v >= counts[0]) & (v < counts[1])
        self.new_tile = (v == 0) | (self.tile_out[v] != self.tile_out[before])
        self.new_group = (v == 0) | (self.group[v] != self.group[before])
        self.group_ends = self.live & ((v + 1 >= counts[0])
                                       | (self.group[after] != self.group[v]))
        self.whole = ((self.lo[v] <= self.tile[v] * rows)
                      & (self.hi[v] >= (self.tile[v] + 1) * rows))

    def mask(self, width: int):
        row = self.tile[self.v] * self.rows + jax.lax.broadcasted_iota(
            jnp.int32, (self.rows, width), 0)
        return (row >= self.lo[self.v]) & (row < self.hi[self.v])


def _store_columns(ref, j, cols: int, value, mask):
    """``value`` (the step's ``cols`` columns of a row tile) into block
    ``j`` of the columns of ``ref``, a whole row tile, on the rows of
    ``mask``; the other rows keep what the tile's earlier visits (or
    ``_clear``) left there. The slice is static under a ``pl.when``: a
    lane offset the program computes is no store the compiler takes."""
    for jj in range(ref.shape[-1] // cols):
        @pl.when(j == jj)
        def _store(at=slice(jj * cols, (jj + 1) * cols)):
            ref[:, at] = jnp.where(mask, value, ref[:, at])


def _clear(visit: _Visit, j, *refs, zero_tiles=False):
    """A tile's blocks start as zeros, at its first visit's first step:
    a live tile's, and where ``zero_tiles`` (blocks that follow
    ``tile_out``) those of a tile without a live row, which stay so."""
    met = visit.live | visit.zeros if zero_tiles else visit.live

    @pl.when(visit.new_tile & (j == 0) & met)
    def _zero():
        for ref in refs:
            ref[...] = jnp.zeros_like(ref)


def _dot(a, b, contract=(1, 0)):
    return jax.lax.dot_general(a, b, (((contract[0],), (contract[1],)),
                                      ((), ())),
                               preferred_element_type=jnp.float32)


def _row_spec(rows: int, width: int, table_index: int):
    """A row tile over all ``width`` columns, at the tile the table's
    array ``table_index`` (1: read, 2: written with the zero tiles) names."""
    return pl.BlockSpec((rows, width),
                        lambda v, j, *table: (table[table_index][v], 0))


def _weight_spec(block, where):
    """A block of the visit's group's matrix: ``where(j)`` its index."""
    return pl.BlockSpec((None, *block),
                        lambda v, j, *table: (table[0][v], *where(j)))


def _call(kernel, name, grid, in_specs, out_specs, out_shape, scratch=(),
          aliases=None, prefetch=_TABLE):
    return pl.pallas_call(
        kernel, out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=prefetch, grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=list(scratch)),
        input_output_aliases=aliases or {},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(), name=name)


# -- the rows' way in ----------------------------------------------------------

# the tokens of a row tile reach a kernel as a block of SMEM, and XLA
# lays a long int32 vector out in tiles of 1,024: the block is that wide
_TOKEN_BLOCK = 1024
_SUBLANES = 8
_SOURCE_ROWS = 256  # tokens a block of ``moe_fetch_source`` (512 read alike)
# Row DMAs a trip of ``moe_fetch_rows``' loop starts: the loop's own
# scalar work is what a row costs (a DMA a trip issued in 32.5 ns, 8 a
# trip in 19: 2.13 -> 1.25 ms for 65,536 rows of 2,304; TPU v5e, PR 51),
# and Mosaic's ``fori_loop`` takes no partial ``unroll``
_FETCH_UNROLL = 8


def _token_block(token, tile: int, tile_of):
    """``token [chunk]`` padded to whole blocks of SMEM, and the spec of
    the block that holds the tokens of the row tile ``tile_of(*grid
    indices and prefetched scalars)``; a kernel finds them at
    :func:`_tokens_at`."""
    block = max(tile, _TOKEN_BLOCK)
    return jnp.pad(token, (0, -token.size % block)), pl.BlockSpec(
        (block,), lambda *a: (tile_of(*a) * tile // block,),
        memory_space=pltpu.SMEM)


def _tokens_at(token_ref, row_tile, rows: int):
    """Where row tile ``row_tile``'s tokens lie in its block of SMEM."""
    return row_tile % (token_ref.shape[0] // rows) * rows


def _source_kernel(x_ref, out_ref):
    rows, padded, lanes = out_ref.shape
    whole, rest = divmod(x_ref.shape[1], lanes)
    if whole:
        out_ref[:, :whole, :] = x_ref[:, :whole * lanes].reshape(
            rows, whole, lanes)
    if padded > whole:
        out_ref[:, whole:, :] = jnp.zeros((rows, padded - whole, lanes),
                                          out_ref.dtype)
    if rest:  # a width 128 lanes do not divide (the tests' 64): the rest
        out_ref[:, whole, :rest] = x_ref[:, whole * lanes:]


@jax.jit
def fetch_source(x):
    """``x [tokens, d]`` as :func:`fetch_rows` reads it: ``[tokens, s,
    128]`` with ``s`` the sublanes of 128 lanes a row takes, rounded up
    to whole tiles of 8 (16 at a width of 2,048; 24, 18 of them the
    row's, at 2,304; 8, the row half of the first, at the tests' 64;
    zeros past the row), so that a token's row is whole tiles of its own
    in either dtype, as :func:`token_sums` lays the sums out and for
    Mosaic's same reason: of bfloat16 ``[tokens, 18, 128]`` it refuses a
    row ("Slice shape along dimension 1 must be aligned to tiling (8),
    but is 18"), of ``[tokens, 1, d]`` too (two rows to a sublane), and
    of ``[tokens, d]`` in any dtype.

    One pass of a kernel, ``moe_fetch_source``, at any width: a block of
    rows in, turned and padded in VMEM, out (0.57 ms at 32,768 bfloat16
    rows of 2,304, TPU v5e), where XLA spelled ``pad`` and ``reshape``
    as two passes of their own (1.64 ms; PERF.md section 6, PR 51)."""
    tokens, d = x.shape
    rows = row_tile(tokens, _SOURCE_ROWS)
    padded = -(-d // (_LANES * _SUBLANES)) * _SUBLANES
    return _call(
        _source_kernel, "moe_fetch_source", (tokens // rows,),
        [pl.BlockSpec((rows, d), lambda i: (i, 0))],
        pl.BlockSpec((rows, padded, _LANES), lambda i: (i, 0, 0)),
        jax.ShapeDtypeStruct((tokens, padded, _LANES), x.dtype), prefetch=0)(x)


def rows_fetched(live, tile: int):
    """The rows :func:`fetch_rows` moves of a chunk whose first ``live``
    rows are of a group: its live tiles', whole."""
    return -(-live // tile) * tile


def _fetch_kernel(live_ref, token_ref, *refs):
    n = (len(refs) - 1) // 3
    sources, outs, held, sems = (refs[:n], refs[n:2 * n], refs[2 * n:3 * n],
                                 refs[-1])
    i, rows = pl.program_id(0), outs[0].shape[0]
    a_trip = math.gcd(rows, _FETCH_UNROLL)

    @pl.when(i * rows < live_ref[0])
    def _fetch():
        at = _tokens_at(token_ref, i, rows)

        def start(trip, _):
            for r in range(a_trip):
                r = trip * a_trip + r
                for s in range(n):
                    pltpu.make_async_copy(sources[s].at[token_ref[at + r]],
                                          held[s].at[r], sems.at[s]).start()
            return 0

        # the whole tile's rows in flight at once, ONE wait a source for
        # all its rows' bytes (a copy of the scratch's size that is
        # never started), then the turn from a row of its own tiles to a
        # row of the block's (strided loads)
        jax.lax.fori_loop(0, rows // a_trip, start, 0)
        for s in range(n):
            pltpu.make_async_copy(held[s], held[s], sems.at[s]).wait()
        for out, got in zip(outs, held):
            out[...] = got[...].reshape(rows, -1)[:, :out.shape[1]]


@functools.partial(jax.jit, static_argnames=("d", "tile"))
def fetch_rows(live, token, *sources, d, tile):
    """``tuple(x[token] for x in sources)`` on the row tiles that hold
    one of the chunk's first ``live`` rows: ``[chunk, d]`` each, in the
    sources' dtype. ``sources`` are :func:`fetch_source`'s of arrays
    ``[tokens, d]``, ``token [chunk]`` the rows' tokens, ``live`` (int32,
    on the device) the rows of a group, which lie first.

    The kernel ``moe_fetch_rows``, the mirror of ``moe_sum_back``, keeps
    the sources in HBM; its grid is the chunk's row tiles. A tile with a
    live row is fetched WHOLE (so at most ``tile - 1`` rows that are of
    no group), a DMA a row and source, all of a tile's in flight at
    once, and written as one block; a tile past the live rows is neither
    fetched nor written, and its step's blocks repeat the last live
    tile's, so it moves nothing. A copy: the fetched rows are bit for
    bit the sources'. Nothing may read a tile this did not write."""
    chunk = token.size
    live_tile = lambda i, live: jnp.minimum(
        i, jnp.maximum(-(-live[0] // tile) - 1, 0))
    token, token_spec = _token_block(token, tile, live_tile)
    rows_out = pl.BlockSpec((tile, d), lambda i, live: (live_tile(i, live), 0))
    n, dt = len(sources), sources[0].dtype
    return _call(
        _fetch_kernel, "moe_fetch_rows", (_blocks_of(chunk, tile),),
        [token_spec, *[pl.BlockSpec(memory_space=pl.ANY)] * n],
        [rows_out] * n, [jax.ShapeDtypeStruct((chunk, d), dt)] * n,
        scratch=[*[pltpu.VMEM((tile, *sources[0].shape[1:]), dt)] * n,
                 pltpu.SemaphoreType.DMA((n,))], prefetch=1)(
            jnp.reshape(live, (1,)).astype(jnp.int32), token, *sources)


# -- forward ------------------------------------------------------------------


def _in_kernel(*refs, cols):
    table, (x_ref, wg_ref, wu_ref, h_ref) = refs[:_TABLE], refs[_TABLE:]
    visit, j = _Visit(table, x_ref.shape[0]), pl.program_id(1)
    _clear(visit, j, h_ref)

    @pl.when(visit.live)
    def _product():
        x = x_ref[...]
        a, b = _dot(x, wg_ref[...]), _dot(x, wu_ref[...])
        _store_columns(h_ref, j, cols,
                       (a * jax.nn.sigmoid(a) * b).astype(h_ref.dtype),
                       visit.mask(cols))


@functools.partial(jax.jit, static_argnames=("tile", "cols"))
def gmm_in(table, xs, w_gate, w_up, *, tile, cols=None):
    """``silu(xs w_gate[e]) * (xs w_up[e])`` in ``xs``'s dtype, ``[chunk,
    f]``: written in the visited tiles."""
    (chunk, d), f = xs.shape, w_gate.shape[-1]
    item = xs.dtype.itemsize
    # the row tile in and out, two weight blocks, a and b and their
    # epilogue in float32
    cols = cols or _widest(f, lambda c: (
        2 * tile * (d + f) * item + 4 * d * c * item + 16 * tile * c
        <= _VMEM_BUDGET))
    w_spec = _weight_spec((d, cols), lambda j: (0, j))
    return _call(
        functools.partial(_in_kernel, cols=cols), "moe_gmm_in",
        (table[0].size, _blocks_of(f, cols)),
        [_row_spec(tile, d, 1), w_spec, w_spec], _row_spec(tile, f, 1),
        jax.ShapeDtypeStruct((chunk, f), xs.dtype))(*table, xs, w_gate, w_up)


def _out_kernel(*refs, cols, transposed, gated):
    """``sum_i lhs_i x rhs_i[e]`` (``rhs`` transposed or not), times the
    row's gate where there is one, float32, zeros in a visited tile's
    rows of no group: ``moe_gmm_down`` (one product, gated) and
    ``moe_gmm_dx`` (two transposed ones)."""
    table, refs = refs[:_TABLE], refs[_TABLE:]
    n = (len(refs) - 1 - gated) // 2
    lhs, rhs, out_ref = refs[:n], refs[n:2 * n], refs[-1]
    visit, j = _Visit(table, out_ref.shape[0]), pl.program_id(1)
    _clear(visit, j, out_ref)

    @pl.when(visit.live)
    def _product():
        y = sum(_dot(a[...], m[...], (1, 1) if transposed else (1, 0))
                for a, m in zip(lhs, rhs))
        if gated:
            y = y * refs[2 * n][...]
        _store_columns(out_ref, j, cols, y, visit.mask(cols))


def _out_fits(tile, k, width, cols, n, item) -> bool:
    """Whether ``_out_kernel`` fits at ``cols`` columns a block: ``n``
    row tiles of ``k`` in and as many weight blocks, the float32 row
    tile out, a product and its store in float32."""
    return (2 * n * (tile + cols) * k * item + 8 * tile * width
            + 8 * tile * cols <= _VMEM_BUDGET)


@functools.partial(jax.jit, static_argnames=("tile", "cols"))
def gmm_down(table, hidden, w_down, gate, *, tile, cols=None):
    """``gate * (hidden w_down[e])`` float32 ``[chunk, d]``, written in
    the visited tiles, for :func:`sum_back`. ``gate`` is ``[chunk, 1]``
    float32."""
    (chunk, f), d = hidden.shape, w_down.shape[-1]
    cols = cols or _widest(d, lambda c: _out_fits(tile, f, d, c, 1,
                                                   hidden.dtype.itemsize))
    return _call(
        functools.partial(_out_kernel, cols=cols, transposed=False,
                          gated=True), "moe_gmm_down",
        (table[0].size, _blocks_of(d, cols)),
        [_row_spec(tile, f, 1), _weight_spec((f, cols), lambda j: (0, j)),
         _row_spec(tile, 1, 1)], _row_spec(tile, d, 1),
        jax.ShapeDtypeStruct((chunk, d), jnp.float32))(
            *table, hidden, w_down, gate)


# -- the rows' sum back to their tokens ---------------------------------------


def rows_summed(table, tile: int):
    """The rows :func:`sum_back` adds under ``table``: each live visit's
    rows of its group in its tile."""
    _, at, _, lo, hi, counts = table
    rows = jnp.minimum(hi, (at + 1) * tile) - jnp.maximum(lo, at * tile)
    return jnp.sum(jnp.where(jnp.arange(at.size) < counts[0], rows, 0))


def token_sums(tokens: int, d: int):
    """Zeros for :func:`sum_back` to add to: the tokens' float32 sums as
    ``[tokens, d / 128, 128]``, a token's row whole register tiles of its
    own (``[tokens, 1, d]`` where 128 lanes do not divide ``d``). Mosaic
    slices an array in HBM along a dimension its tiling does not cover,
    and of ``[tokens, d]``, tiled ``(8, 128)``, one row is an eighth of
    16 tiles ("Slice shape along dimension 0 must be aligned to tiling
    (8)"). ``reshape(tokens, d)`` gives the sums as the model reads
    them."""
    lanes = _LANES if d % _LANES == 0 else d
    return jnp.zeros((tokens, d // lanes, lanes), jnp.float32)


def _sum_back_kernel(*refs):
    table, refs = refs[:_TABLE], refs[_TABLE:]
    rows_ref, token_ref, _, sums_ref, held, sems = refs
    n = rows_ref.shape[0]
    visit = _Visit(table, n)

    @pl.when(visit.live)
    def _add():
        v = visit.v
        start = visit.tile[v] * n
        first = jnp.maximum(visit.lo[v], start) - start
        last = jnp.minimum(visit.hi[v], start + n) - start
        at = _tokens_at(token_ref, visit.tile[v], n)

        def fetch(r):
            return pltpu.make_async_copy(sums_ref.at[token_ref[at + r]],
                                         held.at[r], sems.at[0])

        def put(r):
            return pltpu.make_async_copy(
                held.at[r], sums_ref.at[token_ref[at + r]], sems.at[1])

        def each_row(do):
            jax.lax.fori_loop(first, last, lambda r, _: do(r) or 0, 0)

        # the visit's tokens are distinct: its rows' sums are fetched
        # all at once, added to, and all written back before the next
        # visit, which may hold one of the tokens again, reads
        each_row(lambda r: fetch(r).start())
        each_row(lambda r: fetch(r).wait())
        held[...] += rows_ref[...].reshape(held.shape)
        each_row(lambda r: put(r).start())
        each_row(lambda r: put(r).wait())


@functools.partial(jax.jit, static_argnames=("tile",))
def sum_back(table, sums, rows, token, *, tile):
    """``sums`` (:func:`token_sums`' layout, float32) with ``rows[r]``
    added to token ``token[r]``'s sum for every row ``r`` of a group, in
    place, a live visit of ``table`` at a time in the table's order (by
    group, then by tile: a token's rows are added in ascending order of
    group). ``rows`` is ``[chunk, d]`` float32, ``token`` ``[chunk]``.

    The kernel ``moe_sum_back`` keeps the sums in HBM and moves a row by
    a DMA of its own: a visit fetches the current sums of its rows of its
    group into VMEM, adds the rows and writes them back. What makes a
    visit's rows independent, and what the caller owes: within ONE
    group's rows no token comes twice. Two groups may hold the same
    token; their visits do not overlap. Rows of no group, and tiles
    without a live row, are never read nor moved (``rows`` may hold
    anything there), and a token without a row of a group keeps its sum
    bit for bit."""
    d = rows.shape[1]
    token, token_spec = _token_block(token, tile,
                                     lambda v, *table: table[1][v])
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    return _call(
        _sum_back_kernel, "moe_sum_back", (table[0].size,),
        [pl.BlockSpec((tile, d), lambda v, *table: (table[1][v], 0)),
         token_spec, in_hbm], in_hbm,
        jax.ShapeDtypeStruct(sums.shape, sums.dtype),
        scratch=[pltpu.VMEM((tile, *sums.shape[1:]), jnp.float32),
                 pltpu.SemaphoreType.DMA((2,))],
        aliases={_TABLE + 2: 0})(*table, rows, token, sums)


# -- backward -----------------------------------------------------------------


def _bwd_hidden_kernel(*refs, cols):
    table, refs = refs[:_TABLE], refs[_TABLE:]
    (x_ref, dy_ref, gate_ref, wg_ref, wu_ref, wd_ref,
     da_ref, db_ref, hg_ref, dgate_ref) = refs
    visit, j = _Visit(table, x_ref.shape[0]), pl.program_id(1)
    _clear(visit, j, da_ref, db_ref, hg_ref)
    _clear(visit, j, dgate_ref, zero_tiles=True)

    @pl.when(visit.live)
    def _product():
        dt = da_ref.dtype
        x, gate = x_ref[...], gate_ref[...]
        a, b = _dot(x, wg_ref[...]), _dot(x, wu_ref[...])
        sig = jax.nn.sigmoid(a)
        hidden = a * sig * b
        # ys = hidden w_down, out += gate ys
        d_hidden = _dot(dy_ref[...], wd_ref[...], (1, 1))  # before the gate
        d_gate = jnp.sum(hidden.astype(dt).astype(jnp.float32) * d_hidden,
                         -1, keepdims=True)
        dgate_ref[...] += jnp.where(visit.mask(1), d_gate, 0.0)
        d_hidden = d_hidden * gate
        mask = visit.mask(cols)
        _store_columns(da_ref, j, cols, (
            d_hidden * b * sig * (1.0 + a * (1.0 - sig))).astype(dt), mask)
        _store_columns(db_ref, j, cols, (d_hidden * a * sig).astype(dt), mask)
        _store_columns(hg_ref, j, cols, (hidden * gate).astype(dt), mask)


@functools.partial(jax.jit, static_argnames=("tile", "cols"))
def gmm_bwd_hidden(table, xs, dy, gate, w_gate, w_up, w_down, *, tile,
                   cols=None):
    """``(d_a, d_b, hidden * gate, d_gate)`` of a chunk: the cotangents
    of ``a = xs w_gate[e]`` and ``b = xs w_up[e]`` and the gated hidden
    rows in ``xs``'s dtype, ``[chunk, f]`` each (written in the visited
    tiles), and ``d_gate [chunk, 1]`` float32, every row written."""
    (chunk, d), f = xs.shape, w_gate.shape[-1]
    item = xs.dtype.itemsize
    # two row tiles in, three out, three weight blocks, and eight
    # float32 values of a row tile's columns between them
    cols = cols or _widest(f, lambda c: (
        (4 * tile * d + 6 * tile * f + 6 * d * c) * item + 32 * tile * c
        <= _VMEM_BUDGET))
    rows_d, rows_f = _row_spec(tile, d, 1), _row_spec(tile, f, 1)
    w_spec = _weight_spec((d, cols), lambda j: (0, j))
    wide = jax.ShapeDtypeStruct((chunk, f), xs.dtype)
    return _call(
        functools.partial(_bwd_hidden_kernel, cols=cols),
        "moe_gmm_bwd_hidden", (table[0].size, _blocks_of(f, cols)),
        [rows_d, rows_d, _row_spec(tile, 1, 1), w_spec, w_spec,
         _weight_spec((cols, d), lambda j: (j, 0))],
        [rows_f, rows_f, rows_f, _row_spec(tile, 1, 2)],
        [wide, wide, wide, jax.ShapeDtypeStruct((chunk, 1), jnp.float32)])(
            *table, xs, dy, gate, w_gate, w_up, w_down)


@functools.partial(jax.jit, static_argnames=("tile", "cols"))
def gmm_dx(table, d_a, d_b, w_gate, w_up, *, tile, cols=None):
    """``d_a w_gate[e]^T + d_b w_up[e]^T`` float32 ``[chunk, d]``,
    written in the visited tiles, for :func:`sum_back`."""
    (chunk, f), d = d_a.shape, w_gate.shape[1]
    cols = cols or _widest(d, lambda c: _out_fits(tile, f, d, c, 2,
                                                   d_a.dtype.itemsize))
    rows_f = _row_spec(tile, f, 1)
    w_spec = _weight_spec((cols, f), lambda j: (j, 0))
    return _call(
        functools.partial(_out_kernel, cols=cols, transposed=True,
                          gated=False), "moe_gmm_dx",
        (table[0].size, _blocks_of(d, cols)), [rows_f, rows_f, w_spec, w_spec],
        _row_spec(tile, d, 1),
        jax.ShapeDtypeStruct((chunk, d), jnp.float32))(
            *table, d_a, d_b, w_gate, w_up)


def _dw_kernel(*refs, n):
    """``sums_i[e] += lhs[rows of e]^T rhs_i[rows of e]`` for ``n``
    right-hand sides of one left-hand side."""
    table, refs = refs[:_TABLE], refs[_TABLE:]
    lhs_ref, rhs, sums = refs[0], refs[1:1 + n], refs[1 + n:1 + 2 * n]
    outs, accs = refs[1 + 2 * n:1 + 3 * n], refs[1 + 3 * n:]
    visit = _Visit(table, lhs_ref.shape[0], axis=2)

    @pl.when(visit.new_group)
    def _start():
        for acc in accs:
            acc[...] = jnp.zeros_like(acc)

    def product(lhs):
        for acc, b_ref in zip(accs, rhs):
            acc[...] += _dot(lhs, b_ref[...], (0, 0))

    # most tiles are one group's from end to end: the mask is a pass
    # over the tile that only a group's first and last tile need
    @pl.when(visit.live & visit.whole)
    def _whole():
        product(lhs_ref[...])

    @pl.when(visit.live & jnp.logical_not(visit.whole))
    def _part():
        lhs = lhs_ref[...]
        product(jnp.where(visit.mask(lhs.shape[1]), lhs,
                          jnp.zeros_like(lhs)))

    @pl.when(visit.group_ends)
    def _add():
        for out, base, acc in zip(outs, sums, accs):
            out[...] = base[...] + acc[...]

    # a chunk without a live row still brings group 0's block out: as it was
    @pl.when((visit.v == 0) & jnp.logical_not(visit.live))
    def _keep():
        for out, base in zip(outs, sums):
            out[...] = base[...]


def _gmm_dw(name, table, lhs, rhs, sums, tile, block):
    chunk, k = lhs.shape
    n, width, item = len(rhs), rhs[0].shape[1], lhs.dtype.itemsize
    # the sums' blocks in and out and the product's in VMEM, float32;
    # the row tiles in, the left one turned. All the columns where they
    # fit (the left tile is turned once a column block), then the rows
    fits = lambda r, c: (20 * n * r * c + 3 * tile * r * item
                         + 2 * n * tile * c * item <= _VMEM_BUDGET)
    cols = block[1] if block else _widest(width, lambda c: fits(_LANES, c))
    rows = block[0] if block else _widest(k, lambda r: fits(r, cols))
    sum_spec = pl.BlockSpec(
        (None, rows, cols), lambda i, j, v, *table: (table[0][v], i, j))
    rhs_spec = pl.BlockSpec((tile, cols),
                            lambda i, j, v, *table: (table[1][v], j))
    return _call(
        functools.partial(_dw_kernel, n=n), name,
        (_blocks_of(k, rows), _blocks_of(width, cols), table[0].size),
        [pl.BlockSpec((tile, rows), lambda i, j, v, *table: (table[1][v], i)),
         *[rhs_spec] * n, *[sum_spec] * n], [sum_spec] * n,
        [jax.ShapeDtypeStruct(s.shape, s.dtype) for s in sums],
        scratch=[pltpu.VMEM((rows, cols), jnp.float32)] * n,
        aliases={_TABLE + 1 + n + i: i for i in range(n)})(
            *table, lhs, *rhs, *sums)


@functools.partial(jax.jit, static_argnames=("tile", "block"))
def gmm_dw_in(table, xs, d_a, d_b, dw_gate, dw_up, *, tile, block=None):
    """``(dw_gate + xs^T d_a, dw_up + xs^T d_b)`` over each group's
    rows, float32 ``[groups, d, f]``, in place: a group with no row in
    the chunk keeps its sum as it is."""
    return _gmm_dw("moe_gmm_dw_in", table, xs, (d_a, d_b), (dw_gate, dw_up),
                   tile, block)


@functools.partial(jax.jit, static_argnames=("tile", "block"))
def gmm_dw_down(table, hg, dy, dw_down, *, tile, block=None):
    """``dw_down + (hidden * gate)^T dy`` over each group's rows,
    float32 ``[groups, f, d]``, in place."""
    return _gmm_dw("moe_gmm_dw_down", table, hg, (dy,), (dw_down,), tile,
                   block)[0]

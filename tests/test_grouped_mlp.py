"""``ops/grouped_mlp.py``: each kernel, the rows' fetch and their sum
back against the plain ``jax.numpy`` spelling, on the CPU in interpret
mode at tiny shapes, and ``held_experts_sum`` (values and all five
gradients) against a plain float32 reference of the layer's sum, and bit
for bit against itself on rows gathered as ``x[token]``."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from sparktorch_tpu.models import sparse_moe_lm as M
from sparktorch_tpu.ops import grouped_mlp as G

CHUNK, TILE, D, F = 64, 16, 24, 32
N_TOKENS, EVERYONES = 80, 3
# rows of each of four experts in a chunk of 64, tiles of 16
SIZES = {
    "an_empty_expert": [20, 0, 30, 5],
    # rows 18-20, 21-24 and the first of 25-44 lie in tile 1 with the
    # last of expert 0's: four visits of one tile
    "a_tile_shared_by_four": [18, 3, 4, 20],
    "experts_under_a_tile": [3, 2, 0, 1],
    "no_live_row": [0, 0, 0, 0],
    "a_full_chunk": [10, 22, 0, 32],
    "one_expert_takes_all": [0, 64, 0, 0],
    "second_half_dead": [10, 12, 0, 10],
}


def tokens_of(sizes, rng, n_tokens=N_TOKENS, pairs=CHUNK):
    """The tokens of ``pairs`` sorted pairs as the layer sorts them:
    within an expert's rows ascending, none twice; across experts they
    meet, and token ``EVERYONES`` is under every expert with a row (two
    to four of them: in ``a_tile_shared_by_four`` and
    ``experts_under_a_tile`` its rows lie in ONE tile under several
    experts, in ``an_empty_expert`` in three visits of three tiles).
    The rows of no group point anywhere."""
    others = np.delete(np.arange(n_tokens), EVERYONES)
    held = [np.sort(np.append(rng.choice(others, s - 1, replace=False),
                              EVERYONES)) for s in sizes if s]
    return jnp.asarray(np.concatenate(
        [*held, rng.integers(0, n_tokens, pairs - sum(sizes))]), jnp.int32)


def operands(sizes, dt, seed=0):
    keys = jax.random.split(jax.random.key(seed), 7)
    n = len(sizes)
    normal = lambda k, *shape: jax.random.normal(k, shape, jnp.float32)
    return dict(
        sizes=jnp.asarray(sizes, jnp.int32),
        token=tokens_of(sizes, np.random.default_rng(seed)),
        sums=[normal(k, N_TOKENS, D) for k in jax.random.split(keys[6])],
        xs=normal(keys[0], CHUNK, D).astype(dt),
        dy=normal(keys[1], CHUNK, D).astype(dt),
        gate=jax.random.uniform(keys[2], (CHUNK, 1), jnp.float32, 0.1, 1.0),
        w_gate=(normal(keys[3], n, D, F) * D ** -0.5).astype(dt),
        w_up=(normal(keys[4], n, D, F) * D ** -0.5).astype(dt),
        w_down=(normal(keys[5], n, F, D) * F ** -0.5).astype(dt))


def group_of_row(sizes):
    """``(group [chunk], live [chunk, 1])`` of a chunk's rows."""
    ends = jnp.cumsum(sizes)
    row = jnp.arange(CHUNK)
    group = jnp.sum(row[:, None] >= ends[None, :], -1)
    return jnp.minimum(group, sizes.size - 1), (row < ends[-1])[:, None]


def plain(o):
    """Every result of the six kernels by ``jax.numpy`` on whole arrays:
    each row by its own expert's matrices (a gather of them), operands
    in their dtype, sums and epilogues float32, zeros in the rows of no
    group; and the carried sums with ``ys`` and ``dx`` scatter-added to
    their tokens, whole arrays too."""
    dt, f32 = o["xs"].dtype, jnp.float32
    group, live = group_of_row(o["sizes"])
    dot = lambda a, m, eq: jnp.einsum(eq, a, m[group],
                                      preferred_element_type=f32)
    held = lambda a: jnp.where(live, a, 0).astype(a.dtype)
    a, b = (dot(o["xs"], o["w_gate"], "rd,rdf->rf"),
            dot(o["xs"], o["w_up"], "rd,rdf->rf"))
    sig = jax.nn.sigmoid(a)
    hidden = a * sig * b
    ys = held(dot(hidden.astype(dt), o["w_down"], "rf,rfd->rd") * o["gate"])
    d_hidden = dot(o["dy"], o["w_down"], "rd,rfd->rf")
    d_gate = held(jnp.sum(hidden.astype(dt).astype(f32) * d_hidden, -1,
                          keepdims=True))
    d_hidden = d_hidden * o["gate"]
    d_a = held((d_hidden * b * sig * (1.0 + a * (1.0 - sig))).astype(dt))
    d_b = held((d_hidden * a * sig).astype(dt))
    hg = held((hidden * o["gate"]).astype(dt))
    dx = held(dot(d_a, o["w_gate"], "rf,rdf->rd")
              + dot(d_b, o["w_up"], "rf,rdf->rd"))
    hot = ((group[:, None] == jnp.arange(o["sizes"].size)[None, :])
           & live).astype(f32)
    by_group = lambda l, r: jnp.einsum(
        "rg,rk,rn->gkn", hot, l.astype(f32), r.astype(f32))
    return dict(hidden=held(hidden.astype(dt)), ys=ys, d_a=d_a, d_b=d_b,
                hg=hg, d_gate=d_gate, dx=dx,
                out_sum=o["sums"][0].at[o["token"]].add(ys),
                dx_sum=o["sums"][1].at[o["token"]].add(dx),
                dw_gate=by_group(o["xs"], d_a), dw_up=by_group(o["xs"], d_b),
                dw_down=by_group(hg, o["dy"]))


def sum_back(table, sums, rows, token, tile):
    """``G.sum_back`` on sums that come and go as ``[tokens, d]``."""
    return G.sum_back(table, sums.reshape(G.token_sums(*sums.shape).shape),
                      rows, token, tile=tile).reshape(sums.shape)


def kernels(o, sums=None, tile=TILE, **blocks):
    """The same through the kernels; the blocks of tiles they do not
    visit read as the kernels leave them (``visited`` says which rows
    are in a visited tile). ``sums`` are the carried weight gradients
    (zeros where not given)."""
    table = G.visit_table(o["sizes"], CHUNK, tile)
    cols, out_cols = blocks.get("cols"), blocks.get("out_cols")
    hidden = G.gmm_in(table, o["xs"], o["w_gate"], o["w_up"], tile=tile,
                      cols=cols)
    ys = G.gmm_down(table, hidden, o["w_down"], o["gate"], tile=tile,
                    cols=out_cols)
    d_a, d_b, hg, d_gate = G.gmm_bwd_hidden(
        table, o["xs"], o["dy"], o["gate"], o["w_gate"], o["w_up"],
        o["w_down"], tile=tile, cols=cols)
    dx = G.gmm_dx(table, d_a, d_b, o["w_gate"], o["w_up"], tile=tile,
                  cols=out_cols)
    zeros = lambda w: jnp.zeros(w.shape, jnp.float32)
    sums = sums or (zeros(o["w_gate"]), zeros(o["w_up"]), zeros(o["w_down"]))
    block = blocks.get("block")
    dw_gate, dw_up = G.gmm_dw_in(table, o["xs"], d_a, d_b, sums[0], sums[1],
                                 tile=tile, block=block)
    dw_down = G.gmm_dw_down(table, hg, o["dy"], sums[2], tile=tile,
                            block=block and block[::-1])
    visited = (jnp.arange(CHUNK) // tile
               < -(-jnp.sum(o["sizes"]) // tile))[:, None]
    return dict(hidden=hidden, ys=ys, d_a=d_a, d_b=d_b, hg=hg, d_gate=d_gate,
                dx=dx,
                out_sum=sum_back(table, o["sums"][0], ys, o["token"], tile),
                dx_sum=sum_back(table, o["sums"][1], dx, o["token"], tile),
                dw_gate=dw_gate, dw_up=dw_up, dw_down=dw_down), visited


# read under the table only (by the kernels, or by ``sum_back``), so
# written in the visited tiles only
INNER = ("hidden", "d_a", "d_b", "hg", "ys", "dx")


def close(got, want, dt, name):
    tol = 2e-5 if dt == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol, err_msg=name)


@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(SIZES))
def test_each_kernel_is_the_plain_spelling(case, dt):
    o = operands(SIZES[case], dt)
    want = plain(o)
    got, visited = kernels(o)
    for name in want:
        a = jnp.where(visited, got[name], 0) if name in INNER else got[name]
        assert np.all(np.isfinite(np.asarray(a, np.float32))), name
        close(a, want[name], dt, name)


@pytest.mark.parametrize("blocks", [
    dict(cols=16, out_cols=8), dict(block=(8, 16)), dict(tile=8),
    dict(tile=64)],
    ids=["column_blocks_2_and_3", "dw_blocks_3x2", "tiles_of_8", "one_tile"])
def test_the_blocks_do_not_change_a_result(blocks):
    """Two and three column blocks (as many steps a visit, each storing
    its slice of the row tile's block), the weight gradients in 3 x 2
    and 2 x 3 blocks of an expert's matrices, and other row tiles."""
    o = operands(SIZES["a_tile_shared_by_four"], jnp.float32, seed=1)
    want = plain(o)
    got, visited = kernels(o, **blocks)
    for name in want:
        a = jnp.where(visited, got[name], 0) if name in INNER else got[name]
        close(a, want[name], jnp.float32, name)


def test_an_absent_experts_sum_is_left_as_it_is_bit_for_bit():
    """The weight gradients add into the carried sums in place and touch
    only the experts with a row in the chunk: an absent expert's sum
    keeps its bits (a NaN planted there stays, and stays alone), a
    present one's grows by its product."""
    o = operands(SIZES["an_empty_expert"], jnp.float32, seed=2)
    key = jax.random.key(5)
    base = [jax.random.normal(k, w.shape, jnp.float32) for k, w in zip(
        jax.random.split(key, 3), (o["w_gate"], o["w_up"], o["w_down"]))]
    base = [b.at[1, 0, 0].set(jnp.nan) for b in base]
    want = plain(o)
    got, _ = kernels(o, sums=tuple(base))
    for name, b in zip(("dw_gate", "dw_up", "dw_down"), base):
        assert np.asarray(got[name][1]).tobytes() == np.asarray(b[1]).tobytes()
        present = np.asarray([0, 2, 3])
        close(got[name][present], (b + want[name])[present], jnp.float32,
              name)
    # and with no live row at all, nothing moves
    o = operands(SIZES["no_live_row"], jnp.float32)
    got, _ = kernels(o, sums=tuple(base))
    for name, b in zip(("dw_gate", "dw_up", "dw_down"), base):
        assert np.asarray(got[name]).tobytes() == np.asarray(b).tobytes()


@pytest.mark.parametrize("case", ["second_half_dead", "no_live_row",
                                  "experts_under_a_tile"])
def test_a_token_without_a_held_row_keeps_its_sum_bit_for_bit(case):
    """The sum back touches only the tokens of the rows that hold a held
    pair: a token that only rows of no group point at (in a visited
    tile's tail and in the tiles without a live row, which no one reads:
    NaNs planted there do not arrive) keeps the bits it came with, a NaN
    planted there stays and stays alone; the others grow by their
    rows."""
    o = operands(SIZES[case], jnp.float32, seed=2)
    n_live = int(o["sizes"].sum())
    token = np.asarray(o["token"])
    untouched = np.setdiff1d(np.arange(N_TOKENS), token[:n_live])
    assert np.intersect1d(untouched, token[n_live:]).size  # pointed at
    base = o["sums"][0].at[untouched, 0].set(jnp.nan)
    table = G.visit_table(o["sizes"], CHUNK, TILE)
    ys = jnp.where((jnp.arange(CHUNK) < n_live)[:, None], plain(o)["ys"],
                   jnp.nan)
    got = np.asarray(sum_back(table, base, ys, o["token"], TILE))
    assert got[untouched].tobytes() == np.asarray(base)[untouched].tobytes()
    touched = np.unique(token[:n_live])
    close(got[touched], plain(o)["out_sum"][touched], jnp.float32, case)
    assert int(G.rows_summed(table, TILE)) == n_live


@pytest.mark.parametrize("case", list(SIZES))
def test_the_table_visits_each_experts_tiles_once(case):
    """The table against a count on the host: every (tile, group) pair
    with a row in common once, by group then tile, then the tiles
    without a live row, then repeats."""
    sizes = np.asarray(SIZES[case])
    ends = np.cumsum(sizes)
    pairs = [(t, g) for g in range(sizes.size)
             for t in range(CHUNK // TILE)
             if max(ends[g] - sizes[g], t * TILE) < min(ends[g],
                                                        (t + 1) * TILE)]
    group, tile, tile_out, lo, hi, counts = map(
        np.asarray, G.visit_table(jnp.asarray(sizes, jnp.int32), CHUNK, TILE))
    n_live, n_all = counts
    assert n_live == len(pairs) == int(G.tiles_visited(
        jnp.asarray(sizes, jnp.int32), TILE))
    assert list(zip(tile[:n_live], group[:n_live])) == pairs
    # what the sums back add under it: every row of a group, once
    assert int(G.rows_summed(G.visit_table(
        jnp.asarray(sizes, jnp.int32), CHUNK, TILE), TILE)) == sizes.sum()
    assert np.array_equal(tile_out[:n_live], tile[:n_live])
    assert np.array_equal(lo[:n_live], (ends - sizes)[group[:n_live]])
    assert np.array_equal(hi[:n_live], ends[group[:n_live]])
    live_tiles = -(-ends[-1] // TILE)
    assert list(tile_out[n_live:n_all]) == list(range(live_tiles,
                                                      CHUNK // TILE))
    assert group.size == CHUNK // TILE + sizes.size - 1 >= n_all
    # past their range the arrays repeat: no block moves
    assert np.all(tile[n_live:] == (tile[n_live - 1] if n_live else 0))
    assert np.all(group[n_live:] == (group[n_live - 1] if n_live
                                     else group[0]))
    assert np.all(tile_out[n_all:] == CHUNK // TILE - 1)


def test_row_tiles_follow_the_expected_rows_an_expert():
    # the six cells' (chunk, expected rows an expert)
    assert G.row_tile(32_768, 2_048) == 512   # LFM2
    assert G.row_tile(32_768, 1_024) == 512   # Keye, SDAR
    assert G.row_tile(32_768, 512) == 512     # Laguna
    assert G.row_tile(10_240, 320) == 256     # Qwen3-Next
    assert G.row_tile(8_192, 256) == 256      # JoyAI
    assert G.row_tile(8_192, 20) == 128       # never under the MXU's side
    assert G.row_tile(96, 24) == 32           # what divides the chunk
    with pytest.raises(ValueError, match="does not divide"):
        G.visit_table(jnp.zeros((2,), jnp.int32), 96, 64)
    o = operands(SIZES["a_full_chunk"], jnp.float32)
    with pytest.raises(ValueError, match="do not divide a width of 32"):
        G.gmm_in(G.visit_table(o["sizes"], CHUNK, TILE), o["xs"],
                 o["w_gate"], o["w_up"], tile=TILE, cols=24)


# -- the rows' way in ---------------------------------------------------------


def bits(a):
    return np.asarray(a).tobytes()


@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("d", [16 * 128, 18 * 128, 24],
                         ids=["16x128", "18x128", "24"])
@pytest.mark.parametrize("live", [0, 64, 37, 32],
                         ids=["none_held", "all_held", "partial_last_tile",
                              "second_half_dead"])
def test_fetched_rows_are_the_gathered_rows_bit_for_bit(live, d, dt):
    """``fetch_rows`` of two sources in one call against ``x[token]``:
    EQUAL on every row of a tile with a live row (the last live tile
    whole, its rows of no group too), whatever the width's sublanes (16
    are whole bfloat16 tiles, 18 are not, 24 columns have no lane tile
    at all) and the dtype; the source's padding never arrives."""
    keys = jax.random.split(jax.random.key(live + d), 3)
    x, dy = (jax.random.normal(k, (N_TOKENS, d), jnp.float32).astype(dt)
             for k in keys[:2])
    token = jax.random.randint(keys[2], (CHUNK,), 0, N_TOKENS)
    sources = [G.fetch_source(a) for a in (x, dy)]
    assert sources[0].shape[0] == N_TOKENS and sources[0].dtype == dt
    # a row is whole tiles of its own at any width: itself, then zeros
    row = sources[0].reshape(N_TOKENS, -1)
    assert row.shape[1] % (8 * 128) == 0
    assert bits(row[:, :d]) == bits(x) and not np.any(row[:, d:])
    xs, dys = G.fetch_rows(jnp.int32(live), token, *sources, d=d, tile=TILE)
    assert xs.shape == dys.shape == (CHUNK, d) and xs.dtype == dt
    fetched = int(G.rows_fetched(live, TILE))
    assert live <= fetched < live + TILE and fetched % TILE == 0
    assert bits(xs[:fetched]) == bits(x[token][:fetched])
    assert bits(dys[:fetched]) == bits(dy[token][:fetched])
    # one source alone, as the forward pass calls it
    alone, = G.fetch_rows(jnp.int32(live), token, sources[1], d=d, tile=TILE)
    assert bits(alone[:fetched]) == bits(dys[:fetched])


# -- the layer's sum through them ---------------------------------------------


def layer_reference(x, token, gate, rows, w_gate, w_up, w_down):
    """``held_experts_sum`` in plain float32 on whole arrays: every held
    pair's row by its expert's matrices, gated, summed by token."""
    expert = jnp.sum(jnp.arange(token.size)[:, None]
                     >= jnp.cumsum(rows)[None, :], -1)
    held = (expert < rows.size)[:, None]
    expert = jnp.minimum(expert, rows.size - 1)
    hp = jax.lax.Precision.HIGHEST
    xs = x[token]
    a = jnp.einsum("rd,rdf->rf", xs, w_gate[expert], precision=hp)
    b = jnp.einsum("rd,rdf->rf", xs, w_up[expert], precision=hp)
    ys = jnp.einsum("rf,rfd->rd", jax.nn.silu(a) * b, w_down[expert],
                    precision=hp)
    return jnp.zeros(x.shape, jnp.float32).at[token].add(
        jnp.where(held, ys * gate[:, None], 0.0))


@pytest.mark.parametrize("rows,chunk", [
    ([40, 0, 25, 7], 64),      # two trips, the second's tail dead
    ([5, 9, 3, 2], 64),        # one trip, three tiles dead
    ([70, 60, 50, 12], 48),    # every chunk runs (all pairs held)
    ([0, 0, 0, 0], 64),        # no trip at all
], ids=["two_trips", "one_trip", "all_held", "nothing_held"])
def test_held_experts_sum_and_its_five_gradients(rows, chunk, monkeypatch):
    monkeypatch.setattr(G, "_MIN_ROW_TILE", 16)
    n_tokens, k, d, f = 96, 2, 24, 32
    keys = jax.random.split(jax.random.key(7), 7)
    # token EVERYONES is under every expert with a row: in two_trips
    # under expert 0 (the first trip) and expert 3 (the second)
    token = tokens_of(rows, np.random.default_rng(7), n_tokens, n_tokens * k)
    rows = jnp.asarray(rows, jnp.int32)
    x = jax.random.normal(keys[0], (n_tokens, d), jnp.float32)
    gate = jax.random.uniform(keys[2], (n_tokens * k,), jnp.float32, 0.1, 1.0)
    w = [jax.random.normal(kk, (4, *s), jnp.float32) * s[0] ** -0.5
         for kk, s in zip(keys[3:6], ((d, f), (d, f), (f, d)))]
    target = jax.random.normal(keys[6], (n_tokens, d), jnp.float32)
    loss = lambda fn: lambda x, gate, *w: jnp.sum(target * fn(x, gate, *w))
    got = jax.value_and_grad(loss(
        lambda x, gate, *w: M.held_experts_sum(x, token, gate, rows, *w,
                                               chunk)),
        argnums=(0, 1, 2, 3, 4))(x, gate, *w)
    want = jax.value_and_grad(loss(
        lambda x, gate, *w: layer_reference(x, token, gate, rows, *w)),
        argnums=(0, 1, 2, 3, 4))(x, gate, *w)
    names = ("loss", "x", "gate", "w_gate", "w_up", "w_down")
    for a, b, name in zip(jax.tree.leaves(got), jax.tree.leaves(want), names):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5, err_msg=name)

    # a gather is a copy: on rows gathered as ``x[token]``, and on
    # fetched rows whose tiles without a held pair (which the fetch does
    # not write and nothing may read) hold NaN, the sum and its five
    # gradients keep every bit
    fetch = G.fetch_rows
    d = x.shape[1]

    def gathered(live, token, *sources, d, tile):
        return tuple(a.reshape(a.shape[0], -1)[:, :d][token] for a in sources)

    def poisoned(live, token, *sources, d, tile):
        dead = (jnp.arange(token.size) >= G.rows_fetched(live, tile))[:, None]
        return tuple(jnp.where(dead, jnp.nan, rows) for rows in fetch(
            live, token, *sources, d=d, tile=tile))

    for spelling in (gathered, poisoned):
        monkeypatch.setattr(G, "fetch_rows", spelling)
        again = jax.value_and_grad(loss(
            lambda x, gate, *w: M.held_experts_sum(x, token, gate, rows, *w,
                                                   chunk)),
            argnums=(0, 1, 2, 3, 4))(x, gate, *w)
        for a, b, name in zip(jax.tree.leaves(got), jax.tree.leaves(again),
                              names):
            assert bits(a) == bits(b), (spelling.__name__, name)


def test_the_chip_smokes_phase_rehearsed_at_a_small_size(monkeypatch):
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import chip_smoke

    monkeypatch.setattr(G, "_MIN_ROW_TILE", 16)
    report = chip_smoke.phase_grouped_mlp(
        chip_smoke.Sizes(grouped_case=(64, 2, 8, 4, 32, 48)), 0, {})
    assert "held_rows=" in report and "chunk=128" in report
    assert "sum_back_fwd_ms=" in report and "sum_back_bwd_ms=" in report
    assert "fetch_bwd_ms=" in report and "gather_bwd_ms=" in report

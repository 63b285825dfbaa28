"""``ops/block_diffusion_attention.py``'s rule through the kernels of
``ops/rule_attention.py`` in interpret mode against dense
masked float32 attention: forward and the three gradients, one and
eight query heads a key/value head, rows of one, two and three tiles,
blocks of 4 tokens and of a whole tile; the static tile tables against
the dense rule; and what a caller's remat keeps of the forward pass."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from sparktorch_tpu.ops import rule_attention as mod
from sparktorch_tpu.ops.block_diffusion_attention import (
    SAVED_NAMES, BlockDiffusionMask, block_diffusion_attention)
from test_sparse_attention import pallas_calls

D = 128
KERNELS = ("blockdiff_attn_fwd", "blockdiff_attn_bwd_dq",
           "blockdiff_attn_bwd_dkv")


def dense_rule(seq_len: int, block: int) -> np.ndarray:
    """The issue's three clauses written out pair by pair."""
    t = 2 * seq_len
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    blk_i, blk_j = (i % seq_len) // block, (j % seq_len) // block
    clean_i, clean_j = i < seq_len, j < seq_len
    return ((clean_i & clean_j & (blk_j <= blk_i))
            | (~clean_i & clean_j & (blk_j < blk_i))
            | (~clean_i & ~clean_j & (blk_j == blk_i)))


def dense(q, k, v, mask):
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   precision="highest") * q.shape[-1] ** -0.5
    s = jnp.where(jnp.asarray(mask), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v,
                      precision="highest")


def make_qkv(t: int, groups: int, rows: int = 1, dtype=jnp.float32):
    keys = jax.random.split(jax.random.key(t + groups), 3)
    return tuple(jax.random.normal(kk, (rows, t, h, D), dtype)
                 for kk, h in zip(keys, (2 * groups, 2, 2)))


def _grads(fn, qkv):
    weight = jnp.cos(jnp.arange(D, dtype=jnp.float32))
    return jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v) * weight),
                    argnums=(0, 1, 2))(*qkv)


# rows of L tokens, the tiles 2L tokens are cut into: one, two, three
# tiles of 128 keys a half
TILES = {128: (256, 256), 256: (256, 512), 384: (256, 256)}
CASES = [(128, 4, 1), (128, 128, 8), (256, 4, 8), (256, 128, 1),
         (384, 4, 1), (384, 128, 8), (128, 1, 1)]


@pytest.mark.parametrize("seq_len,block,groups", CASES)
def test_forward_and_gradients_match_dense_masked_attention(
        seq_len, block, groups, monkeypatch):
    """Also with tiles of 128 x 128, under which a row of 384 tokens is
    three tiles a half and the tables skip most of the square."""
    rule, mask = BlockDiffusionMask(seq_len, block), dense_rule(seq_len,
                                                                block)
    qkv = make_qkv(2 * seq_len, groups)
    ref = lambda q, k, v: dense(q, k, v, mask)
    mine = lambda q, k, v: block_diffusion_attention(q, k, v, rule)
    want, want_grads = ref(*qkv), _grads(ref, qkv)
    assert mod._blocks(2 * seq_len) == TILES[seq_len]
    for tiles in (None, (128, 128)):
        if tiles:
            monkeypatch.setattr(mod, "_blocks", lambda t: tiles)
        np.testing.assert_allclose(mine(*qkv), want, atol=2e-6)
        for got, ref_g, name in zip(_grads(mine, qkv), want_grads, "qkv"):
            # float32 sums in another order: dv of eight heads' worth
            # of queries reads in the tens
            np.testing.assert_allclose(got, ref_g, atol=2e-5, rtol=2e-6,
                                       err_msg=f"d{name} {tiles}")


@pytest.mark.parametrize("seq_len,block,tiles", [
    (128, 4, (128, 128)), (128, 4, (256, 256)), (256, 4, (128, 128)),
    (256, 4, (256, 512)), (256, 4, (256, 256)), (256, 1, (256, 512)),
    (384, 128, (128, 128)), (384, 128, (256, 256)), (512, 4, (128, 128)),
    (512, 4, (256, 512)), (1024, 4, (256, 512)), (1024, 4, (128, 128))])
def test_the_tile_tables_are_the_non_empty_tiles_of_the_dense_rule(
        seq_len, block, tiles):
    t, (bq, bk) = 2 * seq_len, tiles
    rule, mask = BlockDiffusionMask(seq_len, block), dense_rule(seq_len,
                                                                block)
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    assert np.array_equal(rule(i, j), mask)
    kept = mask.reshape(t // bq, bq, t // bk, bk).any((1, 3))
    (qt, kt), (qt2, kt2) = mod.visited_tiles(rule, t, bq, bk)
    assert sorted(zip(qt, kt)) == sorted(map(tuple, np.argwhere(kept)))
    assert sorted(zip(qt2, kt2)) == sorted(zip(qt, kt))
    assert np.all(np.diff(qt) >= 0) and np.all(np.diff(kt2) >= 0)
    # every allowed pair lies at most block - 1 above the diagonal, and
    # a row has L^2 + L b of them
    assert (j - i)[mask].max() == block - 1
    assert mask.sum() == seq_len ** 2 + seq_len * block


def test_at_the_cells_rows_288_of_1024_tiles_of_512_hold_a_pair():
    rule = BlockDiffusionMask(8_192, 4)
    (qt, _), _ = mod.visited_tiles(rule, 16_384, 512, 512)
    assert len(qt) == 288
    assert mod.tiles_visited(rule, 16_384) == (576, 2_048)  # 256 x 512


def test_rows_with_one_allowed_key_return_that_keys_value():
    """Blocks of one token: clean query 0 and noised query 0 attend one
    key each, themselves."""
    seq_len = 128
    q, k, v = make_qkv(2 * seq_len, 1)
    out = block_diffusion_attention(q, k, v, BlockDiffusionMask(seq_len, 1))
    for i in (0, seq_len):
        np.testing.assert_allclose(out[0, i], v[0, i], atol=1e-6)
    assert dense_rule(seq_len, 1)[[0, seq_len]].sum(-1).tolist() == [1, 1]


def test_in_bfloat16_it_is_dense_attention_to_bfloat16s_precision():
    qkv = make_qkv(512, 8, rows=2, dtype=jnp.bfloat16)
    got = block_diffusion_attention(*qkv, BlockDiffusionMask(256, 4))
    assert got.dtype == jnp.bfloat16
    want = dense(*(x.astype(jnp.float32) for x in qkv), dense_rule(256, 4))
    np.testing.assert_allclose(got.astype(jnp.float32), want, atol=2e-2)


@pytest.mark.parametrize("bad", ["head_dim", "heads", "seq", "blocks",
                                 "empty_row"])
def test_a_shape_that_cannot_be_tiled_is_an_error(bad):
    q, k, v = make_qkv(256, 2)
    rule, match = BlockDiffusionMask(128, 4), "blockdiff_attn"
    if bad == "head_dim":  # (64 tiles since PR 46: two heads a register)
        q, k, v = (x[..., :96] for x in (q, k, v))
    elif bad == "heads":
        q = q[:, :, :3]
    elif bad == "seq":
        q, k, v = (x[:, :200] for x in (q, k, v))
    elif bad == "blocks":
        with pytest.raises(ValueError, match="whole blocks"):
            BlockDiffusionMask(130, 4)
        return
    else:  # a rule for shorter rows keeps nothing of the last tiles
        q, k, v = make_qkv(512, 2)
        rule, match = (lambda i, j: (i < 128) & (j < 128)), "empty"
    with pytest.raises(ValueError, match=match):
        block_diffusion_attention(q, k, v, rule)


def test_a_remat_that_lists_the_saved_names_runs_the_forward_kernel_once():
    rule, qkv = BlockDiffusionMask(128, 4), make_qkv(256, 2)
    policies = jax.checkpoint_policies
    counts = {}
    for name, policy in (("saved", policies.save_only_these_names(
            *SAVED_NAMES)), ("nothing", policies.nothing_saveable)):
        attend = jax.checkpoint(
            lambda q, k, v: block_diffusion_attention(q, k, v, rule),
            policy=policy)
        grad = jax.grad(lambda q, k, v: jnp.sum(attend(q, k, v)),
                        argnums=(0, 1, 2))
        jaxpr = jax.make_jaxpr(grad)(*qkv).jaxpr
        counts[name] = [pallas_calls(jaxpr, k) for k in KERNELS]
    assert counts == {"saved": [1, 1, 1], "nothing": [2, 1, 1]}

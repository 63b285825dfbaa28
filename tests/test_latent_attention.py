"""``ops/latent_attention.py`` (causal attention with keys of 192 over
values of 128, one query head a key/value head) and ``ops/latent_rope.py``
(the rotary step, the cast and the turn in front of it) through their
kernels in interpret mode: forward and the three gradients against dense
masked float32 attention on rows that are and are not a multiple of the
largest tiles, at 32 heads; the exact leak test of the causal rule;
what a call names and a remat may keep; and the layout op against the
plain interleaved rotation, scores and cotangents."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from sparktorch_tpu.ops import latent_attention as mod
from sparktorch_tpu.ops import latent_rope as rope_mod
from sparktorch_tpu.ops.latent_attention import (
    SAVED_NAMES, latent_attention_heads_first)
from sparktorch_tpu.ops.latent_rope import latent_rope
from sparktorch_tpu.ops.qk_norm_rope import tables
from test_sparse_attention import pallas_calls

D_QK, D_V = 192, 128


def make_qkv(t: int, heads: int, rows: int = 1, dtype=jnp.float32):
    keys = jax.random.split(jax.random.key(t + heads), 3)
    return tuple(jax.random.normal(kk, (rows, t, heads, d), dtype)
                 for kk, d in zip(keys, (D_QK, D_QK, D_V)))


def latent_attention(q, k, v):
    """The op on ``q`` and ``k [b, T, heads, d_qk]`` and ``v [b, T, heads,
    d_v]`` of any widths: pads the widths to whole registers, turns the
    operands heads first with XLA transposes, calls
    ``latent_attention_heads_first`` and turns the result back."""
    d_qk, d_v = q.shape[-1], v.shape[-1]

    def first(x, d):
        x = jnp.pad(x, ((0, 0),) * 3 + ((0, mod.padded_width(d) - d),))
        return jnp.swapaxes(x, 1, 2)

    o5 = latent_attention_heads_first(
        first(q, d_qk)[:, :, None], first(k, d_qk), first(v, d_v),
        d_qk ** -0.5)
    return jnp.swapaxes(o5[:, :, 0], 1, 2)[..., :d_v]


def dense(q, k, v):
    t = q.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   precision="highest") * q.shape[-1] ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v,
                      precision="highest")


def _grads(fn, qkv):
    weight = jnp.cos(jnp.arange(D_V, dtype=jnp.float32))
    return jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v) * weight),
                    argnums=(0, 1, 2))(*qkv)


def test_tiles_and_what_the_cells_row_visits():
    """8,192 tokens in tiles of 1,024 x 1,024: 36 of 64 tiles, 37.7 M
    pairs computed for 33.56 M kept."""
    assert mod._blocks(8_192) == (1_024, 1_024)
    assert mod._blocks(384) == (128, 128) and mod._blocks(640) == (128, 128)
    assert mod._blocks(1_536) == (512, 512)
    assert mod.tiles_visited(8_192) == (36, 64)
    assert 36 * 1_024 * 1_024 == 37_748_736
    assert mod.padded_width(192) == 256 and mod.padded_width(128) == 128


@pytest.mark.parametrize("t,heads,rows", [
    (384, 32, 1),    # three tiles of 128, the published 32 / 32 heads
    (640, 2, 2),     # five tiles of 128: no multiple of a larger tile
    (1_536, 2, 1),   # three tiles of 512
    (2_048, 1, 1)])  # two tiles of 1,024
def test_forward_and_gradients_match_dense_causal_attention(t, heads, rows):
    qkv = make_qkv(t, heads, rows)
    np.testing.assert_allclose(latent_attention(*qkv), dense(*qkv),
                               atol=2e-6)
    for got, want, name in zip(_grads(latent_attention, qkv),
                               _grads(dense, qkv), "qkv"):
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-6,
                                   err_msg=f"d{name}")


def test_the_scale_is_the_true_widths_and_the_padding_is_inert():
    """The heads-first entry on operands padded to 256 lanes: the scale is
    an argument (that of the 192, which the padding hides), and the
    cotangents' padding lanes are exactly zero."""
    q, k, v = make_qkv(384, 2)
    pad = lambda x: jnp.swapaxes(jnp.pad(x, ((0, 0),) * 3 + ((0, 64),)), 1, 2)
    q5, k4, v4 = pad(q)[:, :, None], pad(k), jnp.swapaxes(v, 1, 2)
    o5 = latent_attention_heads_first(q5, k4, v4, D_QK ** -0.5)
    np.testing.assert_array_equal(jnp.swapaxes(o5[:, :, 0], 1, 2),
                                  latent_attention(q, k, v))
    other = latent_attention_heads_first(q5, k4, v4, 256 ** -0.5)
    assert not np.allclose(o5, other, atol=1e-3)
    dq5, dk4, _ = jax.grad(
        lambda *a: jnp.sum(jnp.sin(latent_attention_heads_first(
            *a, D_QK ** -0.5))), argnums=(0, 1, 2))(q5, k4, v4)
    assert not np.any(np.asarray(dq5[..., D_QK:]))
    assert not np.any(np.asarray(dk4[..., D_QK:]))
    assert np.any(np.asarray(dq5[..., :D_QK]))


def test_a_later_key_changes_nothing_and_its_own_query_sees_it():
    """The exact leak test, at the cell's tiles (2,048 tokens: 1,024 x
    1,024):
    a change to key and value ``j`` leaves every query before ``j`` as it
    was bit for bit and moves query ``j``, the first that attends it.
    Forward and, through the query's cotangent, dk and dv."""
    t, j = 2_048, 1_027
    q, k, v = make_qkv(t, 2)
    bump = lambda x: x.at[:, j].add(1.0)
    base, moved = latent_attention(q, k, v), latent_attention(q, bump(k),
                                                              bump(v))
    assert np.array_equal(np.asarray(base[:, :j]), np.asarray(moved[:, :j]))
    assert not np.array_equal(np.asarray(base[:, j]), np.asarray(moved[:, j]))
    assert not np.array_equal(np.asarray(base[:, -1]),
                              np.asarray(moved[:, -1]))
    for i, reached in ((j - 1, False), (j, True)):
        dk, dv = jax.grad(
            lambda k, v: jnp.sum(latent_attention(q, k, v)[:, i]),
            argnums=(0, 1))(k, v)
        for g in (dk, dv):
            assert bool(jnp.any(g[:, j] != 0)) is reached
            assert not bool(jnp.any(g[:, i + 1:] != 0))


@pytest.mark.parametrize("kept,forward_kernels", [
    (SAVED_NAMES, 1), (("causal_attn_out", "causal_attn_lse"), 2)])
def test_a_call_names_its_kernels_and_what_a_remat_may_keep(
        kept, forward_kernels):
    """Each kernel runs once in the gradient of a caller whose remat
    policy lists the call's names; one that lists another kind's pays a
    second forward kernel."""
    assert SAVED_NAMES == ("latent_attn_out", "latent_attn_lse")
    qkv = make_qkv(384, 2)
    attend = jax.checkpoint(
        latent_attention,
        policy=jax.checkpoint_policies.save_only_these_names(*kept))
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(attend(q, k, v)), argnums=(0, 1, 2)))(
            *qkv).jaxpr
    assert [pallas_calls(jaxpr, f"latent_attn_{k}")
            for k in ("fwd", "bwd_dq", "bwd_dkv")] == [forward_kernels, 1, 1]


@pytest.mark.parametrize("shape,match", [
    (dict(t=320), "cannot be tiled"), (dict(d_qk=192), "cannot be tiled"),
    (dict(groups=2), "one query head")])
def test_a_shape_that_does_not_tile_is_an_error(shape, match):
    t, d_qk, groups = (shape.get(k, v) for k, v in
                       (("t", 384), ("d_qk", 256), ("groups", 1)))
    q5 = jnp.zeros((1, 2, groups, t, d_qk))
    with pytest.raises(ValueError, match=match):
        latent_attention_heads_first(q5, jnp.zeros((1, 2, t, d_qk)),
                                     jnp.zeros((1, 2, t, 128)), 1.0)


# -- the rotary step, the cast and the turn ----------------------------------

NOPE, ROPE, HALF, SLOT = 128, 64, 32, 128
DEINTERLEAVE = np.concatenate([np.arange(0, ROPE, 2), np.arange(1, ROPE, 2)])


def rotate_interleaved(x, angles):
    """Pair ``j`` is dims ``(2j, 2j + 1)``: the published rotation, on
    ``x [..., 64]`` by ``angles [..., 32]``."""
    x1, x2 = x[..., 0::2], x[..., 1::2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).reshape(
        x.shape)


def rope_case(t: int, heads: int, rows: int = 1):
    """Heads of ``[nope ; rope]`` as the model's weights would give them
    in the published order, the shared rotary key, values, and angles."""
    keys = jax.random.split(jax.random.key(t), 4)
    q = jax.random.normal(keys[0], (rows, t, heads, NOPE + ROPE))
    kv = jax.random.normal(keys[1], (rows, t, heads, NOPE + D_V))
    kr = jax.random.normal(keys[2], (rows, t, ROPE))
    angles = jnp.arange(t, dtype=jnp.float32)[None, :, None] * (
        3.2e7 ** (-jnp.arange(HALF) / HALF))
    return q, kv, kr, jnp.broadcast_to(angles, (rows, t, HALF))


def laid_out(q, kv, kr):
    """The products as the op reads them: rotary dims de-interleaved,
    slots padded to whole registers, heads flat."""
    rows, t, heads, _ = q.shape
    slot = lambda x: jnp.pad(x[..., DEINTERLEAVE],
                             ((0, 0),) * (x.ndim - 1) + ((0, SLOT - ROPE),))
    xq = jnp.concatenate([q[..., :NOPE], slot(q[..., NOPE:])], -1)
    return (xq.reshape(rows, t, heads * (NOPE + SLOT)),
            kv.reshape(rows, t, heads * (NOPE + D_V)), slot(kr))


def plain(q, kv, kr, angles):
    """``(q, k, v) [b, T, heads, d]`` by the published equations: the
    interleaved rotation on the last 64 of a query and on the shared key,
    which every head's key ends with."""
    heads = q.shape[2]
    turned = rotate_interleaved(q[..., NOPE:], angles[:, :, None])
    k_rope = rotate_interleaved(kr, angles)[:, :, None]
    return (jnp.concatenate([q[..., :NOPE], turned], -1),
            jnp.concatenate([kv[..., :NOPE], jnp.broadcast_to(
                k_rope, (*kv.shape[:3], ROPE))], -1), kv[..., NOPE:])


@pytest.mark.parametrize("t,heads,rows,dtype", [
    (256, 3, 2, jnp.float32), (2_048, 2, 1, jnp.float32),
    (384, 2, 1, jnp.bfloat16)])
def test_the_layout_op_gives_the_published_scores_and_values(
        t, heads, rows, dtype):
    """De-interleaved and by halves, the queries' and keys' rotary dims
    are a permutation of the published ones, the same for both: every
    score, and every value, is what the plain equations give."""
    q, kv, kr, angles = rope_case(t, heads, rows)
    q5, k4, v4 = latent_rope(*laid_out(q, kv, kr), *tables(angles, SLOT),
                             HALF, NOPE, dtype)
    assert q5.shape == (rows, heads, 1, t, 256) and q5.dtype == dtype
    assert k4.shape == (rows, heads, t, 256) and v4.shape == (
        rows, heads, t, D_V)
    assert not np.any(np.asarray(q5[..., D_QK:], np.float32))
    assert not np.any(np.asarray(k4[..., D_QK:], np.float32))
    pq, pk, pv = plain(q, kv, kr, angles)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    f32 = lambda a: np.asarray(a, np.float32)
    np.testing.assert_allclose(f32(jnp.swapaxes(v4, 1, 2)), pv, atol=tol)
    np.testing.assert_allclose(f32(q5[:, :, 0, :, :NOPE]),
                               jnp.swapaxes(pq[..., :NOPE], 1, 2), atol=tol)
    rows_of = slice(0, min(t, 256))  # a block of the square will do
    got = jnp.einsum("bhqd,bhkd->bhqk", f32(q5[:, :, 0, rows_of]),
                     f32(k4[:, :, rows_of]), precision="highest")
    want = jnp.einsum("bqhd,bkhd->bhqk", pq[:, rows_of], pk[:, rows_of],
                      precision="highest")
    np.testing.assert_allclose(got, want, atol=tol * 200, rtol=tol)


def test_the_layout_ops_cotangents_are_the_plain_spellings():
    """Through a loss of the scores and the values: the products'
    cotangents (the shared key's summed over the heads) against autodiff
    of the plain equations, brought into the op's layout."""
    t, heads = 256, 3
    q, kv, kr, angles = rope_case(t, heads)
    cos, sin = tables(angles, SLOT)

    def loss_of(q, k, v):  # [b, T, heads, d] each
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest")
        return jnp.sum(jnp.sin(s * 0.05)) + jnp.sum(jnp.cos(v) * 0.3)

    def mine(xq, xkv, xkr):
        q5, k4, v4 = latent_rope(xq, xkv, xkr, cos, sin, HALF, NOPE,
                                 jnp.float32)
        turn = lambda x: jnp.swapaxes(x, 1, 2)
        return loss_of(turn(q5[:, :, 0]), turn(k4), turn(v4))

    got = jax.grad(mine, argnums=(0, 1, 2))(*laid_out(q, kv, kr))
    want = laid_out(*jax.grad(
        lambda q, kv, kr: loss_of(*plain(q, kv, kr, angles)),
        argnums=(0, 1, 2))(q, kv, kr))
    for a, b, name in zip(got, want, ("xq", "xkv", "xkr")):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=1e-5, err_msg=name)
    jaxpr = jax.make_jaxpr(jax.grad(mine, argnums=(0, 1, 2)))(
        *laid_out(q, kv, kr)).jaxpr
    assert [pallas_calls(jaxpr, f"latent_rope_{k}")
            for k in ("fwd", "bwd")] == [1, 1]


def test_a_layout_that_does_not_tile_is_an_error():
    q, kv, kr, angles = rope_case(256, 2)
    xq, xkv, xkr = laid_out(q, kv, kr)
    cos, sin = tables(angles, SLOT)
    with pytest.raises(ValueError, match="do not describe"):
        latent_rope(xq, xkv, xkr[..., :64], cos, sin, HALF, NOPE,
                    jnp.float32)
    with pytest.raises(ValueError, match="cannot be tiled"):
        latent_rope(xq[:, :200], xkv[:, :200], xkr[:, :200], cos[:, :200],
                    sin[:, :200], HALF, NOPE, jnp.float32)
    assert rope_mod._token_tile(8_192, 512) == 1_024


def test_the_chip_smokes_phase_rehearsed_at_a_small_size():
    """``chip_smoke.py``'s ``latent_attention`` phase (both ops against
    the plain equations, bf16 results) on rows of 384 tokens."""
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import chip_smoke

    said = chip_smoke.phase_latent_attention(
        chip_smoke.Sizes(latent_case=(1, 384, 2)), 0, {})
    assert said.startswith("2x384x1 out_rel=")

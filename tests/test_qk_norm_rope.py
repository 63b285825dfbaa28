"""``ops/qk_norm_rope.py`` in interpret mode against the plain spelling
it replaces (``rms_norm`` + ``_rotate`` of ``models/sparse_moe_lm.py``,
a cast, ``heads_first``): values, the products' cotangents and both
gains' gradients at the three cells' rotary shapes (all 128 dims by one
section; M-RoPE's 16/24/24; 64 of 128 dims with an attention factor and
the rest passed through), at six and eight query heads a key/value head
and over several token tiles; which blocks of the cotangents a block of
the result may read; and the shapes it refuses."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from sparktorch_tpu.models import sparse_moe_lm as M
from sparktorch_tpu.ops import qk_norm_rope as mod
from sparktorch_tpu.ops.qk_norm_rope import qk_norm_rope
from sparktorch_tpu.ops.sparse_attention import heads_first
from test_sparse_attention import pallas_calls

B, T, HKV, D, EPS = 2, 384, 2, 128, 1e-6
ROTARY = {
    "one_section": M.Rotary(1e6, (64,)),
    "mrope_16_24_24": M.Rotary(1e7, (16, 24, 24)),
    "half_the_dims_with_a_factor": M.Rotary(
        5e5, (32,), (64.0, 4_096.0, 64.0, 1.0), 1.4158883083359672),
}


def plain(xq, xk, xv, q_gain, k_gain, cos, sin, eps, half, dtype):
    """The op's signature, spelled as the model spelled it: float32
    norm, rotation by halves on the first ``2 half`` dims, one cast,
    transposing copies. ``cos`` and ``sin`` are the op's tables; the
    rotation's own are their first and second ``half`` columns."""
    b, t, d = cos.shape
    c, s = cos[:, :, None, :half], sin[:, :, None, half:2 * half]
    heads = lambda x: x.reshape(b, t, -1, d)
    q = M._rotate(M.rms_norm(heads(xq), q_gain, eps), c, s)
    k = M._rotate(M.rms_norm(heads(xk), k_gain, eps), c, s)
    return heads_first(q.astype(dtype), k.astype(dtype),
                       heads(xv).astype(dtype), "plain")


def operands(groups, rotary, seed=0):
    """Products as a projection leaves them (rows of very different
    norms), gains off 1, and the table of ``rotary`` at position ids
    that differ by section."""
    keys = jax.random.split(jax.random.key(seed), 7)
    scale = jnp.exp(jax.random.normal(keys[0], (B, T, 1)))
    xq, xk, xv = (scale * jax.random.normal(k, (B, T, h * D))
                  for k, h in zip(keys[1:4], (HKV * groups, HKV, HKV)))
    q_gain, k_gain = (1.0 + 0.2 * jax.random.normal(k, (D,))
                      for k in keys[4:6])
    position_ids = jax.random.randint(keys[6], (3, B, T), 0, 8_192)
    return (xq, xk, xv, q_gain, k_gain,
            *M.rotary_table(position_ids, rotary, D))


def weighted_sum(fn, args, rotary, dtype, weights):
    out = fn(*args, EPS, sum(rotary.sections), dtype)
    return sum(jnp.sum(o.astype(jnp.float32) * w)
               for o, w in zip(out, weights))


@pytest.mark.parametrize("groups", [6, 8])
@pytest.mark.parametrize("kind", list(ROTARY))
def test_values_and_gradients_are_the_plain_spellings(kind, groups):
    """384 tokens are three token tiles of 128, and every (row, tile,
    key/value head) is a grid step of its own."""
    rotary = ROTARY[kind]
    half = sum(rotary.sections)
    args = operands(groups, rotary)
    assert mod._token_tile(T, groups * D) == 128
    got = qk_norm_rope(*args, EPS, half, jnp.float32)
    want = plain(*args, EPS, half, jnp.float32)
    assert [o.shape for o in got] == [
        (B, HKV, groups, T, D), (B, HKV, T, D), (B, HKV, T, D)]
    for a, b, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(a, b, atol=2e-6, rtol=2e-6, err_msg=name)
    if 2 * half < D:  # the dims passed through carry the norm alone
        np.testing.assert_allclose(
            got[1][..., 2 * half:],
            jnp.swapaxes(M.rms_norm(args[1].reshape(B, T, HKV, D), args[4],
                                    EPS), 1, 2)[..., 2 * half:], rtol=2e-6)
    weights = [jax.random.normal(k, o.shape) for k, o in zip(
        jax.random.split(jax.random.key(9), 3), got)]
    grads = [jax.grad(lambda *a: weighted_sum(fn, (*a, *args[5:]), rotary,
                                              jnp.float32, weights),
                      argnums=(0, 1, 2, 3, 4))(*args[:5])
             for fn in (qk_norm_rope, plain)]
    for a, b, name in zip(*grads, ("dxq", "dxk", "dxv", "dq_gain",
                                   "dk_gain")):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(a, b, atol=1e-5 * scale, rtol=1e-5,
                                   err_msg=name)


def test_in_bfloat16_it_rounds_where_the_plain_spelling_rounds():
    """One cast after the float32 arithmetic: what differs from the
    plain spelling is an element here and there on a rounding boundary,
    by one bfloat16 step."""
    rotary = ROTARY["half_the_dims_with_a_factor"]
    args = operands(8, rotary)
    got = qk_norm_rope(*args, EPS, 32, jnp.bfloat16)
    want = plain(*args, EPS, 32, jnp.bfloat16)
    for a, b in zip(got, want):
        assert a.dtype == jnp.bfloat16
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.mean(a != b) < 1e-3
        np.testing.assert_allclose(a, b, rtol=2 ** -7)


@pytest.mark.parametrize("row,head,tile", [(0, 0, 0), (1, 1, 2), (0, 1, 1)])
def test_a_block_of_the_cotangent_reads_its_own_blocks_alone(row, head,
                                                             tile):
    """The backward kernel's index maps: with every cotangent NaN but
    at one (row, key/value head, token tile), the products' cotangents
    of that row, those tokens and that head's columns are what they are
    without the poison, and finite."""
    groups, rotary = 6, ROTARY["mrope_16_24_24"]
    args = operands(groups, rotary, seed=3)
    keys = jax.random.split(jax.random.key(5), 3)
    shapes = [(B, HKV, groups, T, D), (B, HKV, T, D), (B, HKV, T, D)]
    clean = [jax.random.normal(k, s) for k, s in zip(keys, shapes)]
    tokens = slice(tile * 128, (tile + 1) * 128)
    poisoned = [jnp.full(s, jnp.nan).at[row, head, ..., tokens, :].set(
        c[row, head, ..., tokens, :]) for c, s in zip(clean, shapes)]
    vjp = jax.vjp(lambda *a: qk_norm_rope(*a, *args[3:], EPS, 64,
                                          jnp.float32), *args[:3])[1]
    for got, want, heads in zip(vjp(tuple(poisoned)), vjp(tuple(clean)),
                                (groups, 1, 1)):
        cols = slice(head * heads * D, (head + 1) * heads * D)
        assert np.all(np.isfinite(np.asarray(got[row, tokens, cols])))
        np.testing.assert_array_equal(got[row, tokens, cols],
                                      want[row, tokens, cols])
        # and the poison is there to be met
        assert np.isnan(np.asarray(got)).sum() == got.size - 128 * heads * D


@pytest.mark.parametrize("t,width,tile", [
    (8_192, 8 * 128, 512), (8_192, 6 * 128, 512), (16_384, 8 * 128, 512),
    (8_192, 128, 4_096), (384, 8 * 128, 128), (1_024, 32 * 128, 128)])
def test_a_grid_step_holds_a_key_value_heads_group_for_a_token_tile(
        t, width, tile):
    """The cells' rows take 512 tokens a step (a block of 1.5 or 2
    MiB), a short row its largest power of two, nothing less than 128."""
    assert mod._token_tile(t, width) == tile
    assert tile == 128 or tile * width * 4 <= mod._BLOCK_BYTES


def test_one_kernel_each_way_and_the_layers_share_its_trace():
    """``qk_norm_rope_fwd`` once forward, ``qk_norm_rope_bwd`` once
    backward, whatever number of heads; two layers of one shape are two
    calls of ONE jitted function."""
    args = operands(8, ROTARY["one_section"])
    two_layers = lambda *a: sum(
        jnp.sum(o) for _ in range(2)
        for o in qk_norm_rope(*a, *args[3:], EPS, 64, jnp.float32))
    jaxpr = jax.make_jaxpr(jax.grad(two_layers, argnums=(0, 1, 2)))(
        *args[:3]).jaxpr
    assert pallas_calls(jaxpr, "qk_norm_rope_fwd") == 2
    assert pallas_calls(jaxpr, "qk_norm_rope_bwd") == 2
    traced = [eqn.params["jaxpr"] for eqn in jaxpr.eqns
              if eqn.primitive.name == "jit"
              and eqn.params["name"] == "_fwd"]
    assert len(traced) == 2 and traced[0] is traced[1]


@pytest.mark.parametrize("bad", ["head_dim", "seq", "heads", "gain", "half",
                                 "rows"])
def test_a_shape_that_cannot_be_tiled_is_an_error(bad):
    xq, xk, xv, q_gain, k_gain, cos, sin = operands(
        6, ROTARY["one_section"])
    half = 64
    if bad == "head_dim":
        q_gain, k_gain, cos, sin = (x[..., :64]
                                    for x in (q_gain, k_gain, cos, sin))
        half = 32
    elif bad == "seq":
        xq, xk, xv, cos, sin = (x[:, :200] for x in (xq, xk, xv, cos, sin))
    elif bad == "heads":
        xq = xq[..., :5 * D]
    elif bad == "gain":
        k_gain = k_gain[:64]
    elif bad == "half":
        half = 96
    else:
        xk = xk[:1]
    with pytest.raises(ValueError, match="qk_norm_rope"):
        qk_norm_rope(xq, xk, xv, q_gain, k_gain, cos, sin, EPS, half,
                     jnp.float32)

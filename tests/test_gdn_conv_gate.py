"""``ops/gdn_conv_gate.py`` in interpret mode against the plain spelling
it replaced in ``models/sparse_moe_lm.py`` ``GatedDeltaNet`` (kept here:
``jnp.pad`` and four shifted multiply-adds, ``jax.nn.silu``, the L2 norm
and the gated ``rms_norm`` through ``by_head``): the three results and
the gated norm's, the product's cotangent through BOTH functions (one
buffer, two writers), the taps', the gain's and ``o``'s, over rows of
two and three token tiles and parts of one and three blocks of
columns; what a tile's edge reads on either side; where a row starts;
the rounding to bfloat16; the tiles it chooses; the shapes it refuses."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from sparktorch_tpu.models.sparse_moe_lm import rms_norm
from sparktorch_tpu.ops import gdn_conv_gate as mod
from sparktorch_tpu.ops.gdn_conv_gate import gdn_conv, gdn_out_norm
from sparktorch_tpu.ops.sparse_attention import by_head
from test_sparse_attention import pallas_calls
from test_sparse_moe_lm import rel

B, TAPS, D, EPS = 2, 4, 128, 1e-6
# (tokens, key lanes, value lanes): two tiles of 64 at the tiny model's
# heads (a block of v and of z is two heads); three tiles with value
# heads three to a key head, whose offset in the product leaves blocks of
# ONE head (three blocks of columns a call)
SHAPES = [(128, 128, 256), (192, 128, 384)]
RESULTS = ("q", "k", "v", "y")
OPERANDS = ("qkvz", "taps", "gain", "o")


def plain_conv(qkvz, w, keys, dt):
    """``GatedDeltaNet``'s scopes ``gdn_conv`` and the norms of
    ``gdn_gates`` as they were before the op."""
    taps, conved = w.shape
    b, t, _ = qkvz.shape
    u = qkvz[..., :conved]
    padded = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    u = jax.nn.silu(sum(w[i] * padded[:, i:i + t] for i in range(taps)))
    q, k, v = (u[..., :keys], u[..., keys:2 * keys],
               u[..., 2 * keys:].astype(dt))

    def unit(x, scale):
        x = by_head(x, D)
        return (x * (scale * jax.lax.rsqrt(jnp.sum(
            jnp.square(x), -1, keepdims=True) + 1e-6))).astype(
                dt).reshape(b, t, keys)

    return unit(q, D ** -0.5), unit(k, 1.0), v


def plain_out_norm(o, qkvz, gain, eps):
    """Its scope ``gdn_out_norm`` as it was."""
    z = qkvz[..., qkvz.shape[-1] - o.shape[-1]:]
    return (rms_norm(by_head(o, D), gain, eps)
            * jax.nn.silu(by_head(z, D))).astype(o.dtype).reshape(o.shape)


def fused_all(qkvz, w, gain, o, keys, dt):
    q, k, v, gate = gdn_conv(qkvz, w, keys, dt)
    return q, k, v, gdn_out_norm(o.astype(dt), gate, gain, EPS)


def plain_all(qkvz, w, gain, o, keys, dt):
    return (*plain_conv(qkvz, w, keys, dt),
            plain_out_norm(o.astype(dt), qkvz, gain, EPS))


def operands(t, keys, values, seed=0):
    """A product as a projection leaves it (tokens of very different
    norms), taps as the model draws them, a gain off 1 and an ``o``."""
    ks = jax.random.split(jax.random.key(seed), 5)
    scale = jnp.exp(jax.random.normal(ks[0], (B, t, 1)))
    return (scale * jax.random.normal(ks[1], (B, t, 2 * keys + 2 * values)),
            0.289 * jax.random.normal(ks[2], (TAPS, 2 * keys + values)),
            1.0 + 0.2 * jax.random.normal(ks[3], (D,)),
            jax.random.normal(ks[4], (B, t, values)))


@pytest.fixture(scope="module", params=SHAPES, ids=lambda s: "x".join(
    map(str, s)))
def both(request):
    """Results and the four gradients of a weighted sum of them, the op's
    and the plain spelling's, in float32."""
    t, keys, values = request.param
    args = operands(t, keys, values)
    weights = [jax.random.normal(k, (B, t, n)) for k, n in zip(
        jax.random.split(jax.random.key(7), 4), (keys, keys, values, values))]

    def run(fn):
        def weighted(*a):
            out = fn(*a, keys, jnp.float32)
            return sum(jnp.sum(x * w) for x, w in zip(out, weights)), out

        (_, out), grads = jax.value_and_grad(
            weighted, argnums=(0, 1, 2, 3), has_aux=True)(*args)
        return dict(zip(RESULTS, out)), dict(zip(OPERANDS, grads))

    return run(fused_all), run(plain_all)


@pytest.mark.parametrize("name", RESULTS)
def test_a_result_is_the_plain_spellings(both, name):
    (got, _), (want, _) = both
    assert got[name].shape == want[name].shape
    assert rel(got[name], want[name]) < 1e-6


@pytest.mark.parametrize("name", OPERANDS)
def test_a_gradient_is_the_plain_spellings(both, name):
    """``qkvz``'s is ONE array that ``gdn_out_norm``'s backward kernel
    allocates (``z``'s columns) and ``gdn_conv``'s three complete."""
    (_, got), (_, want) = both
    assert got[name].shape == want[name].shape
    assert rel(got[name], want[name]) < 2e-6
    np.testing.assert_allclose(got[name], want[name], rtol=1e-3,
                               atol=1e-5 * float(jnp.abs(want[name]).max()))


def test_a_gate_nothing_reads_leaves_zs_columns_zero():
    t, keys, values = SHAPES[0]
    qkvz, w, *_ = operands(t, keys, values)
    d = jax.grad(lambda x: sum(jnp.sum(jnp.square(o)) for o in gdn_conv(
        x, w, keys, jnp.float32)[:3]))(qkvz)
    assert not np.any(np.asarray(d[..., 2 * keys + values:]))
    assert np.all(np.any(np.asarray(d[..., :2 * keys + values]) != 0, -1))


def test_a_rows_first_three_tokens_see_zeros_and_not_the_row_before():
    t, keys, values = SHAPES[0]
    qkvz, w, *_ = operands(t, keys, values)
    v = gdn_conv(qkvz, w, keys, jnp.float32)[2]
    u = qkvz[..., 2 * keys:2 * keys + values]
    taps = w[:, 2 * keys:]
    for row in range(B):
        want = u[row, 0] * taps[3]
        for token in range(3):
            np.testing.assert_allclose(v[row, token], jax.nn.silu(want),
                                       rtol=1e-6, atol=1e-7)
            want = u[row, token + 1] * taps[3] + sum(
                u[row, token - j] * taps[2 - j] for j in range(token + 1))
    moved = gdn_conv(qkvz.at[0, t - 3:].add(1.0), w, keys, jnp.float32)
    for got, was in zip(moved[:3], gdn_conv(qkvz, w, keys, jnp.float32)):
        np.testing.assert_array_equal(got[1], was[1])


@pytest.mark.parametrize("t", [128, 192])
@pytest.mark.parametrize("token", [61, 63, 64])
def test_a_token_moves_itself_and_the_three_after_across_a_tiles_edge(
        t, token):
    """Tiles of 64: token 63 is a tile's last row, and what it moves in
    the next tile is read from that tile's halo."""
    _, keys, values = SHAPES[0]
    qkvz, w, *_ = operands(t, keys, values, seed=2)
    nudged = qkvz.at[1, token, :2 * keys + values].add(0.5)
    was = gdn_conv(qkvz, w, keys, jnp.float32)[:3]
    got = gdn_conv(nudged, w, keys, jnp.float32)[:3]
    want = plain_conv(nudged, w, keys, jnp.float32)
    for a, b, c in zip(got, was, want):
        changed = np.flatnonzero(np.any(np.asarray(a[1] != b[1]), -1))
        assert changed.tolist() == list(range(token, token + 4))
        np.testing.assert_array_equal(a[0], b[0])
        assert rel(a[1, token:token + 4], c[1, token:token + 4]) < 1e-6


@pytest.mark.parametrize("t", [128, 192])
@pytest.mark.parametrize("token", [64, 66, 127])
def test_a_cotangent_reaches_its_token_and_the_three_before_across_the_edge(
        t, token):
    """A cotangent at the first rows of a tile reaches the last rows of
    the tile before, whose grid step reads it as the rows after its own;
    a row's last token has nothing after it."""
    _, keys, values = SHAPES[0]
    qkvz, w, *_ = operands(t, keys, values, seed=3)
    ks = jax.random.split(jax.random.key(4), 3)
    cts = tuple(jnp.zeros((B, t, n)).at[0, token].set(
        jax.random.normal(k, (n,))) for k, n in zip(ks, (keys, keys, values)))
    got = jax.vjp(lambda x: gdn_conv(x, w, keys, jnp.float32)[:3],
                  qkvz)[1](cts)[0]
    want = jax.vjp(lambda x: plain_conv(x, w, keys, jnp.float32),
                   qkvz)[1](cts)[0]
    touched = np.flatnonzero(np.any(np.asarray(got[0]) != 0, -1))
    assert touched.tolist() == list(range(token - 3, token + 1))
    assert not np.any(np.asarray(got[1]))
    assert rel(got, want) < 2e-6


def test_in_bfloat16_it_rounds_where_the_plain_spelling_rounds():
    """One cast after the float32 arithmetic: what differs from the
    plain spelling is an element here and there on a rounding boundary,
    by one bfloat16 step; the cotangents come in bfloat16 and the
    product's leaves float32."""
    t, keys, values = SHAPES[1]
    args = operands(t, keys, values)
    got = fused_all(*args, keys, jnp.bfloat16)
    want = plain_all(*args, keys, jnp.bfloat16)
    for a, b in zip(got, want):
        assert a.dtype == jnp.bfloat16
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.mean(a != b) < 2e-3
        np.testing.assert_allclose(a, b, rtol=2 ** -7, atol=1e-6)
    total = lambda fn: lambda *a: sum(
        jnp.sum(jnp.square(x.astype(jnp.float32)))
        for x in fn(*a, keys, jnp.bfloat16))
    for a, b in zip(jax.grad(total(fused_all), argnums=(0, 1, 2, 3))(*args),
                    jax.grad(total(plain_all), argnums=(0, 1, 2, 3))(*args)):
        assert a.dtype == jnp.float32 and rel(a, b) < 2e-2


@pytest.mark.parametrize("width,offset,lanes", [
    (2_048, 0, 2_048), (2_048, 2_048, 2_048), (4_096, 4_096, 2_048),
    (4_096, 8_192, 2_048), (128, 128, 128), (256, 256, 256), (384, 256, 128)])
def test_a_block_is_whole_heads_that_divide_the_parts_offset(width, offset,
                                                             lanes):
    assert mod._column_block(width, offset) == lanes
    assert width % lanes == 0 and offset % lanes == 0


@pytest.mark.parametrize("t,lanes,bytes_a_lane,tile", [
    (16_384, 2_048, 6, 256), (16_384, 2_048, 10, 128),
    (16_384, 2_048, 8, 256), (16_384, 2_048, 14, 128), (128, 128, 8, 128),
    (192, 128, 8, 64), (64, 256, 8, 64), (8_192 + 64, 128, 8, 64)])
def test_a_token_tile_is_the_rules_chunk_times_a_power_of_two(
        t, lanes, bytes_a_lane, tile):
    """The cell's rows: 256 tokens a step forward and 128 backward, 8 MiB
    of blocks at the most; any row of whole chunks runs."""
    assert mod._token_tile(t, lanes, bytes_a_lane) == tile
    assert t % tile == 0
    assert tile == 64 or 2 * tile * lanes * bytes_a_lane <= mod._VMEM_BYTES


def test_three_calls_a_pass_of_the_product_and_the_layers_share_a_trace():
    """``q``, ``k`` and ``v`` each by a ``gdn_conv_fwd`` / ``gdn_conv_bwd``
    of its own, the gated norm by one each way; two layers of one shape
    are two calls of ONE jitted function."""
    t, keys, values = SHAPES[0]
    args = operands(t, keys, values)
    two_layers = lambda *a: sum(
        jnp.sum(o) for _ in range(2)
        for o in fused_all(*a, keys, jnp.float32))
    jaxpr = jax.make_jaxpr(jax.grad(two_layers, argnums=(0, 1, 2, 3)))(
        *args).jaxpr
    calls = {k: pallas_calls(jaxpr, k) for k in (
        "gdn_conv_fwd", "gdn_conv_bwd", "gdn_out_norm_fwd",
        "gdn_out_norm_bwd")}
    assert list(calls.values()) == [6, 6, 2, 2]
    for name in ("_conv_fwd", "_conv_bwd", "_out_norm_fwd", "_out_norm_bwd"):
        traced = [eqn.params["jaxpr"] for eqn in jaxpr.eqns
                  if eqn.primitive.name == "jit"
                  and eqn.params["name"] == name]
        assert len(traced) == 2 and traced[0] is traced[1], name


@pytest.mark.parametrize("bad", ["seq", "head", "values", "taps", "dtype",
                                 "gain", "o_width", "o_seq"])
def test_a_shape_that_cannot_be_tiled_is_an_error(bad):
    t, keys, values = SHAPES[0]
    qkvz, w, gain, o = operands(t, keys, values)
    conv = lambda: gdn_conv(qkvz, w, keys, jnp.float32)
    norm = lambda: gdn_out_norm(o, qkvz, gain, EPS)
    if bad == "seq":        # whole chunks of 64
        qkvz, fails = qkvz[:, :96], conv
    elif bad == "head":     # q and k of half a head
        keys, fails = 64, conv
    elif bad == "values":   # v and z of different widths
        qkvz, fails = qkvz[..., :-128], conv
    elif bad == "taps":     # more than a halo holds
        w, fails = jnp.zeros((10, w.shape[1])), conv
    elif bad == "dtype":
        qkvz, fails = qkvz.astype(jnp.bfloat16), conv
    elif bad == "gain":
        gain, fails = gain[:64], norm
    elif bad == "o_width":
        o, fails = o[..., :192], norm
    else:
        o, fails = o[:, :64], norm
    with pytest.raises(ValueError, match="gdn_conv|gdn_out_norm"):
        fails()


def test_the_chip_smokes_phase_rehearsed_at_a_small_size():
    """``chip_smoke.py``'s ``gdn_conv_gate`` phase (the op against the
    plain spelling, bfloat16 results, four gradients) on rows of 128
    tokens, one key head under two value heads."""
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import chip_smoke

    said = chip_smoke.phase_gdn_conv_gate(
        chip_smoke.Sizes(gdn_case=(1, 128, 1, 2)), 0, {})
    assert said.startswith("1/2x128x1 value_rel=")

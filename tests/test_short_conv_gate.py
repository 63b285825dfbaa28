"""``ops/short_conv_gate.py`` in interpret mode against its plain
``jax.numpy`` spelling (``plain`` there: a pad and three shifted
multiply-adds between two products): the value and both gradients over
rows of one, two and four token tiles and one and two blocks of columns;
what a tile's edge reads on either side; where a row starts; the rounding
to bfloat16; the tiles it chooses; the shapes it refuses. And the
decoder's grouped-query path at heads of 64, two to a register:
``ops/qk_norm_rope.py`` against ``rms_norm`` and ``_rotate``, the
``causal`` and ``window`` kernels of ``ops/rule_attention.py`` against
dense masked attention, and the shapes they still refuse."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from sparktorch_tpu.models.sparse_moe_lm import _rotate, rms_norm
from sparktorch_tpu.ops import qk_norm_rope as fused
from sparktorch_tpu.ops import rule_attention as rules
from sparktorch_tpu.ops import short_conv_gate as mod
from sparktorch_tpu.ops.short_conv_gate import plain, short_conv_gate
from test_sparse_attention import pallas_calls
from test_sparse_moe_lm import rel

B = 2
# (tokens, channels, bytes of VMEM the tiles may take): one tile and one
# block of columns; two tiles of 32; four tiles of 16 over two blocks
# (1,024 channels are two blocks of 512 lanes)
SHAPES = [(64, 128, None), (64, 256, 32 * 256 * 56), (64, 1024, 1)]


def operands(t, d, seed=0):
    keys = jax.random.split(jax.random.key(seed), 3)
    bcu = jax.random.normal(keys[0], (B, t, 3 * d)) * jnp.exp(
        jax.random.normal(keys[1], (B, t, 1)))
    return bcu, 0.333 * jax.random.normal(keys[2], (mod.TAPS, d))


@pytest.fixture(params=SHAPES, ids=lambda s: f"{s[0]}x{s[1]}")
def both(request, monkeypatch):
    """The op and its plain spelling on the same operands: the value and
    the gradients of a weighted sum."""
    t, d, vmem = request.param
    if vmem is not None:
        monkeypatch.setattr(mod, "_VMEM_BYTES", vmem)
        jax.clear_caches()   # the tile is chosen when the call is traced
    bcu, taps = operands(t, d)
    weight = jax.random.normal(jax.random.key(9), (B, t, d))

    def run(fn):
        loss = lambda x, w: jnp.sum(fn(x, w, jnp.float32) * weight)
        return fn(bcu, taps, jnp.float32), jax.grad(loss, (0, 1))(bcu, taps)

    got, want = run(short_conv_gate), run(plain)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda x, w: jnp.sum(short_conv_gate(x, w, jnp.float32)), (0, 1)))(
            bcu, taps).jaxpr
    jax.clear_caches()
    return got, want, jaxpr


def test_the_value_is_the_plain_spellings(both):
    (value, _), (want, _), _ = both
    assert rel(value, want) < 1e-6


@pytest.mark.parametrize("operand", [0, 1], ids=["product", "taps"])
def test_a_gradient_is_the_plain_spellings(both, operand):
    (_, grads), (_, want), _ = both
    assert float(jnp.linalg.norm(want[operand])) > 0
    assert rel(grads[operand], want[operand]) < 1e-6


def test_one_kernel_each_way_writes_the_products_cotangent_whole(both):
    """The backward pass is ONE ``pallas_call`` whose first result is
    the whole ``[b, T, 3 D]`` cotangent, the three column blocks by the
    grid's last axis, beside one forward call."""
    *_, jaxpr = both
    assert pallas_calls(jaxpr, "sconv_bwd") == 1
    assert pallas_calls(jaxpr, "sconv_fwd") == 1


def test_a_rows_first_two_tokens_see_zeros_and_not_the_row_before():
    bcu, taps = operands(32, 128)
    out = short_conv_gate(bcu, taps, jnp.float32)
    alone = short_conv_gate(bcu[1:], taps, jnp.float32)
    np.testing.assert_array_equal(np.asarray(out[1]), np.asarray(alone[0]))
    b, c, u = jnp.split(bcu, 3, -1)
    np.testing.assert_allclose(out[:, 0], c[:, 0] * taps[2] * (b * u)[:, 0],
                               rtol=1e-6)


@pytest.mark.parametrize("token", [14, 15, 16, 31])
def test_a_token_moves_itself_and_the_two_after_across_a_tiles_edge(
        token, monkeypatch):
    """The exact leak test: ``B`` and ``u`` of one token reach the
    outputs of that token and the two after it, bit for bit nothing
    before and nothing later, in tiles of 16 tokens."""
    monkeypatch.setattr(mod, "_VMEM_BYTES", 1)
    jax.clear_caches()
    bcu, taps = operands(48, 128, seed=3)
    out = short_conv_gate(bcu, taps, jnp.float32)
    bumped = bcu.at[:, token, :128].add(1.0).at[:, token, 256:].add(1.0)
    moved = short_conv_gate(bumped, taps, jnp.float32)
    jax.clear_caches()
    changed = np.flatnonzero(np.any(np.asarray(moved != out), (0, 2)))
    assert changed.tolist() == [token, token + 1, token + 2]


@pytest.mark.parametrize("token", [16, 17, 33])
def test_a_cotangent_reaches_its_token_and_the_two_before_across_the_edge(
        token, monkeypatch):
    monkeypatch.setattr(mod, "_VMEM_BYTES", 1)
    jax.clear_caches()
    bcu, taps = operands(48, 128, seed=4)
    d_bcu = jax.grad(lambda x: jnp.sum(
        short_conv_gate(x, taps, jnp.float32)[:, token]))(bcu)
    jax.clear_caches()
    b_and_u = jnp.concatenate([d_bcu[..., :128], d_bcu[..., 256:]], -1)
    assert np.flatnonzero(np.any(np.asarray(b_and_u != 0), (0, 2))).tolist() \
        == [token - 2, token - 1, token]
    # the out-gate's cotangent stays on the token
    assert np.flatnonzero(np.any(np.asarray(
        d_bcu[..., 128:256] != 0), (0, 2))).tolist() == [token]


def test_in_bfloat16_it_rounds_once_where_the_plain_spelling_rounds():
    bcu, taps = operands(64, 256, seed=5)
    low = short_conv_gate(bcu, taps, jnp.bfloat16)
    assert low.dtype == jnp.bfloat16
    exact = short_conv_gate(bcu, taps, jnp.float32)
    # one rounding of the float32 result: half a bfloat16 step at most
    assert float(jnp.max(jnp.abs(low.astype(jnp.float32) - exact)
                         / (jnp.abs(exact) + 1e-30))) <= 2.0 ** -8
    assert rel(low.astype(jnp.float32), plain(bcu, taps, jnp.bfloat16).astype(
        jnp.float32)) < 1e-3


@pytest.mark.parametrize("d,lanes", [(128, 128), (384, 128), (2_048, 512),
                                     (1_024, 512), (768, 256)])
def test_a_block_is_a_power_of_two_of_registers_that_divides_the_channels(
        d, lanes):
    assert mod._column_block(d) == lanes


@pytest.mark.parametrize("t,lanes,bytes_a_lane,tile", [
    (4_096, 512, 28, 512),   # the cell's forward: 14 bytes, held twice
    (4_096, 512, 44, 256),   # its backward: 18 twice and two blocks kept
    (48, 128, 28, 16), (96, 128, 28, 32), (4_096, 128, 28, 2_048)])
def test_a_token_tile_is_sixteen_times_a_power_of_two(t, lanes, bytes_a_lane,
                                                      tile):
    assert mod._token_tile(t, lanes, bytes_a_lane) == tile
    assert t % tile == 0


def test_the_layers_of_a_model_share_one_trace_of_each_kernel():
    bcu, taps = operands(32, 128)
    jax.clear_caches()
    for _ in range(3):
        jax.grad(lambda x, w: jnp.sum(short_conv_gate(
            x, w, jnp.float32) ** 2), (0, 1))(bcu, 2.0 * taps)
    assert mod._fwd._cache_size() == 1 and mod._bwd._cache_size() == 1


@pytest.mark.parametrize("bad", ["seq", "channels", "thirds", "taps",
                                 "dtype", "rank"])
def test_a_shape_that_cannot_be_tiled_is_an_error(bad):
    bcu, taps = operands(32, 128)
    if bad == "seq":
        bcu, match = bcu[:, :24], "24 tokens cannot be tiled"
    elif bad == "channels":
        bcu, taps, match = bcu[..., :3 * 64], taps[:, :64], "whole registers"
    elif bad == "thirds":
        bcu, match = bcu[..., :-1], "three equal blocks"
    elif bad == "taps":
        taps, match = jnp.concatenate([taps, taps[:1]]), "3 taps a channel"
    elif bad == "dtype":
        bcu, match = bcu.astype(jnp.bfloat16), "not float32"
    else:
        bcu, match = bcu[0], "not float32"
    with pytest.raises(ValueError, match=match):
        short_conv_gate(bcu, taps, jnp.float32)


def test_the_chip_smokes_phases_rehearsed_at_a_small_size(monkeypatch):
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import chip_smoke

    sizes = chip_smoke.Sizes(sconv_case=(2, 64, 256),
                             heads64_case=(1, 256, 8, 2))
    assert "value_rel=" in chip_smoke.phase_short_conv_gate(sizes, 0, {})
    # off the chip the compiled text holds no kernel: the phase says so
    with pytest.raises(AssertionError, match="'causal_attn_fwd': 0"):
        chip_smoke.phase_causal_heads_64(sizes, 0, {})


# -- heads of 64, two to a register ------------------------------------------

T64, EPS = 256, 1e-5


def dense_attention(q, k, v, rule):
    b, t, h, d = q.shape
    k, v = (jnp.repeat(x, h // k.shape[2], 2) for x in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") * d ** -0.5
    keep = rule(jnp.arange(t)[:, None], jnp.arange(t)[None, :])
    p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision="highest")


RULES = {"causal": rules.Causal(), "window": rules.CausalWindow(160)}


@pytest.fixture(scope="module", params=[(8, 2), (6, 2), (16, 4)],
                ids=lambda hs: f"{hs[0]}q{hs[1]}kv")
def attended(request):
    """4, 3 and 4 query heads a key/value head (an odd count leaves a
    register's two heads on different key/value heads), one and two
    pairs of key/value heads, under both causal rules."""
    heads, kv = request.param
    keys = jax.random.split(jax.random.key(heads), 4)
    q, k, v, w = (jax.random.normal(key, (1, T64, n, 64)) for key, n in zip(
        keys, (heads, kv, kv, heads)))
    out = {}
    for name, rule in RULES.items():
        run = lambda fn: (fn(q, k, v), jax.grad(lambda q, k, v: jnp.sum(
            fn(q, k, v) * w), (0, 1, 2))(q, k, v))
        out[name] = (run(lambda q, k, v: rules.rule_attention(
            q, k, v, rule, name)), run(lambda q, k, v: dense_attention(
                q, k, v, rule)))
    return out


@pytest.mark.parametrize("name", list(RULES))
def test_the_kernels_at_heads_of_64_are_the_dense_rule(attended, name):
    (out, _), (want, _) = attended[name]
    assert rel(out, want) < 2e-6


@pytest.mark.parametrize("operand", [0, 1, 2], ids=["q", "k", "v"])
@pytest.mark.parametrize("name", list(RULES))
def test_a_gradient_at_heads_of_64_is_the_dense_rules(attended, name,
                                                      operand):
    (_, grads), (_, want) = attended[name]
    assert rel(grads[operand], want[operand]) < 5e-6


def test_heads_of_64_lie_two_to_a_register_and_the_output_is_flat():
    """``heads_in_registers`` is the flat arrays' lanes by registers (a
    reshape and a turn, nothing padded), and the heads-first entry
    returns ``o [b, T, heads * 64]`` with head ``i`` in lanes ``[64 i,
    64 (i + 1))``; the statistics kept are a row a head."""
    keys = jax.random.split(jax.random.key(2), 3)
    q, k, v = (jax.random.normal(key, (1, 128, n, 64)) for key, n in zip(
        keys, (8, 2, 2)))
    q5, k4, v4 = rules.heads_in_registers(q, k, v, "t")
    assert q5.shape == (1, 1, 4, 128, 128) and k4.shape == (1, 1, 128, 128)
    np.testing.assert_array_equal(q5[0, 0, 1, :, 64:], q[0, :, 3])
    np.testing.assert_array_equal(k4[0, 0, :, 64:], k[0, :, 1])
    o, (_, _, _, _, lse) = rules._forward(q5, k4, v4, rules.Causal(), "causal",
                                          64)
    assert o.shape == (1, 128, 8 * 64) and lse.shape == (1, 1, 8, 128)
    np.testing.assert_allclose(
        o.reshape(1, 128, 8, 64), dense_attention(q, k, v, rules.Causal()),
        atol=2e-6)


def plain_qk(xq, xk, xv, gq, gk, angles, d):
    b, t, _ = xq.shape
    cos, sin = jnp.cos(angles)[:, :, None], jnp.sin(angles)[:, :, None]
    heads = lambda x: x.reshape(b, t, -1, d)
    return rules.heads_in_registers(
        _rotate(rms_norm(heads(xq), gq, EPS), cos, sin),
        _rotate(rms_norm(heads(xk), gk, EPS), cos, sin), heads(xv), "plain")


@pytest.mark.parametrize("half", [32, 16], ids=["whole_head", "half_head"])
@pytest.mark.parametrize("heads,kv", [(8, 2), (16, 4)])
def test_qk_norm_rope_at_heads_of_64_is_the_plain_spelling(heads, kv, half):
    """RMSNorm over each head's 64 lanes (two heads a register), the
    rotation inside a head, and the layout the kernels read: values and
    all five gradients."""
    b, d = 2, 64
    keys = jax.random.split(jax.random.key(heads + half), 9)
    xq, xk, xv = (jax.random.normal(key, (b, T64, n * d)) for key, n in zip(
        keys, (heads, kv, kv)))
    gq, gk = (1.0 + 0.2 * jax.random.normal(key, (d,)) for key in keys[3:5])
    angles = 6.0 * jax.random.uniform(keys[5], (b, T64, half))
    table = fused.tables(angles, d)
    assert table[0].shape == (b, T64, 128)
    ours = lambda *a: fused.qk_norm_rope(*a, *table, EPS, half, jnp.float32)
    theirs = lambda *a: plain_qk(*a, angles, d)
    args = (xq, xk, xv, gq, gk)
    shapes = [o.shape for o in ours(*args)]
    assert shapes == [(b, kv // 2, heads // kv, T64, 128)] + [
        (b, kv // 2, T64, 128)] * 2
    weights = [jax.random.normal(key, s) for key, s in zip(keys[6:], shapes)]
    loss = lambda fn: (lambda *a: sum(
        jnp.sum(o * w) for o, w in zip(fn(*a), weights)))
    for got, want in zip(ours(*args), theirs(*args)):
        assert rel(got, want) < 1e-6
    for got, want in zip(jax.grad(loss(ours), (0, 1, 2, 3, 4))(*args),
                         jax.grad(loss(theirs), (0, 1, 2, 3, 4))(*args)):
        assert rel(got, want) < 2e-6


@pytest.mark.parametrize("bad", ["head_32_attention", "head_96", "odd_kv",
                                 "seq", "operand_64_wide", "head_64_in_256"])
def test_an_untileable_shape_is_still_an_error(bad):
    keys = jax.random.split(jax.random.key(0), 3)
    q, k, v = (jax.random.normal(key, (1, 128, n, 64)) for key, n in zip(
        keys, (8, 2, 2)))
    rule = rules.Causal()
    if bad == "head_32_attention":
        with pytest.raises(ValueError, match="cannot be tiled"):
            rules.rule_attention(*(x[..., :32] for x in (q, k, v)), rule,
                                 "causal")
    elif bad == "head_96":
        gain = jnp.ones((96,))
        with pytest.raises(ValueError, match="do not describe one head"):
            fused.qk_norm_rope(
                jnp.ones((1, 128, 96 * 4)), jnp.ones((1, 128, 96 * 2)),
                jnp.ones((1, 128, 96 * 2)), gain, gain,
                jnp.ones((1, 128, 128)), jnp.ones((1, 128, 128)), EPS, 32,
                jnp.float32)
    elif bad == "odd_kv":
        with pytest.raises(ValueError, match="two to a register"):
            rules.rule_attention(q[:, :, :3], k[:, :, :1], v[:, :, :1], rule,
                                 "causal")
        gain, table = jnp.ones((64,)), jnp.ones((1, 128, 128))
        with pytest.raises(ValueError, match="filling whole registers"):
            fused.qk_norm_rope(
                jnp.ones((1, 128, 64 * 3)), jnp.ones((1, 128, 64)),
                jnp.ones((1, 128, 64)), gain, gain, table, table, EPS, 32,
                jnp.float32)
    elif bad == "seq":
        with pytest.raises(ValueError, match="cannot be tiled"):
            rules.rule_attention(*(x[:, :100] for x in (q, k, v)), rule,
                                 "causal")
    elif bad == "operand_64_wide":
        # heads first with a last axis of 64 (a head padded in HBM)
        q5 = jnp.ones((1, 2, 4, 128, 64))
        with pytest.raises(ValueError, match="cannot be tiled"):
            rules.rule_attention_heads_first(
                q5, q5[:, :, 0], q5[:, :, 0], rule, "causal", 64)
    else:
        q5 = jnp.ones((1, 1, 4, 128, 256))
        with pytest.raises(ValueError, match="cannot be tiled"):
            rules.rule_attention_heads_first(
                q5, q5[:, :, 0], q5[:, :, 0], rule, "causal", 64)

"""The output side of the five attention kernel families (``sparse``,
``blockdiff``, ``causal``, ``window``, ``latent``) in interpret mode:
``o`` leaves a grouped-query kernel flat, ``[b, T, heads * d]`` with
head ``i`` in lanes ``[i * d, (i + 1) * d)``, and its cotangent enters
so; what ``Wo`` and the gate do with it. Latent attention (one head a
grid step) keeps ``o5 [b, heads, 1, T, d_v]`` and its module's turn
(``latent.heads_last``): here it is read through that turn, so that the
five families answer to one description.

A key/value head's grid steps touch no other head's, and inside a step
the group's query heads share nothing but the K, V and mask tiles (dk
and dv sum over them). So the flat array is held to the kernels' own
results on PART of the heads: one key/value head alone gives its block
of ``G * d`` lanes and its ``dq5`` / ``dk4`` / ``dv4`` bit for bit (the
output blocks' index map, both ways), one query head alone gives its
``d`` lanes and its ``dq5`` bit for bit (the lane slices in a tile),
and the heads first layout ``[b, kv_heads, G, T, d]`` of before is a
``jnp.transpose`` of the flat array read by head. The same families
against dense masked attention are in the op's own test files."""

import flax.linen as nn
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import test_sparse_attention as sparse_case
from sparktorch_tpu.models import sparse_moe_lm as M
from sparktorch_tpu.ops import latent_attention as latent
from sparktorch_tpu.ops import sparse_attention as sparse
from sparktorch_tpu.ops.block_diffusion_attention import (
    BlockDiffusionMask, block_diffusion_attention_heads_first)
from sparktorch_tpu.ops.rule_attention import (
    Causal, CausalWindow, rule_attention_heads_first)

B, T, D = 2, 384, 128      # three tiles of 128 (latent: of 128 too)
HKV = 2                    # key/value heads of the grouped-query families
FAMILIES = ("sparse", "blockdiff", "causal", "window", "latent")
GROUPS = (1, 6, 8)


def entry_of(family):
    """The family's heads-first entry as ``fn(q5, k4, v4) -> o``, the
    pairs it keeps as a dense mask ``[rows or 1, T, T]``, and its
    scale."""
    i, j = np.arange(T)[:, None], np.arange(T)[None, :]
    if family == "sparse":
        mask = sparse_case.make_mask("random", T)
        return (lambda q5, k4, v4: sparse.sparse_attention_heads_first(
            q5, k4, v4, mask)), np.asarray(mask) != 0, D ** -0.5
    if family == "blockdiff":
        rule = BlockDiffusionMask(T // 2, 4)
        return (lambda q5, k4, v4: block_diffusion_attention_heads_first(
            q5, k4, v4, rule)), rule(i, j)[None], D ** -0.5
    if family == "latent":  # through its module's turn, read flat
        return (lambda q5, k4, v4: latent.heads_last(
            latent.latent_attention_heads_first(
                q5, k4, v4, 192 ** -0.5)).reshape(B, T, -1)), (
                    j <= i)[None], 192 ** -0.5
    rule = Causal() if family == "causal" else CausalWindow(160)
    return (lambda q5, k4, v4: rule_attention_heads_first(
        q5, k4, v4, rule, family)), rule(i, j)[None], D ** -0.5


def operands(family, groups, dtype=jnp.float32):
    """``(q5, k4, v4, do)`` in the kernels' layout and the flat one.
    Latent attention has one query head a key/value head, so there
    ``groups`` is its number of heads, keys 192 wide padded to 256."""
    keys = jax.random.split(jax.random.key(groups), 4)
    if family == "latent":
        hkv, g, d_qk = groups, 1, 256
    else:
        hkv, g, d_qk = HKV, groups, D
    q5 = jax.random.normal(keys[0], (B, hkv, g, T, d_qk), dtype)
    k4 = jax.random.normal(keys[1], (B, hkv, T, d_qk), dtype)
    if family == "latent":  # the padding lanes are zero on both sides
        q5, k4 = (x.at[..., 192:].set(0) for x in (q5, k4))
    v4 = jax.random.normal(keys[2], (B, hkv, T, D), dtype)
    do = jax.random.normal(keys[3], (B, T, hkv * g * D), dtype)
    return q5, k4, v4, do


@pytest.fixture(scope="module", params=[
    (f, g) for f in FAMILIES for g in GROUPS], ids=lambda p: f"{p[0]}-{p[1]}")
def whole(request):
    """A family at a group size: its entry, operands, and the flat ``o``
    with the three cotangents of the call on ALL heads."""
    family, groups = request.param
    fn, kept, scale = entry_of(family)
    q5, k4, v4, do = operands(family, groups)
    o, vjp = jax.vjp(fn, q5, k4, v4)
    return dict(fn=fn, kept=kept, scale=scale, operands=(q5, k4, v4), do=do,
                o=o, grads=vjp(do))


def test_a_key_value_head_alone_writes_its_block_of_lanes(whole):
    """``o`` is ``[b, T, kv_heads * G * d]`` in the operands' dtype, and
    key/value head ``h`` alone gives lanes ``[h * G * d, (h + 1) * G *
    d)`` of it and, from those lanes of ``do``, row ``h`` of ``dq5``,
    ``dk4`` and ``dv4``, all bit for bit."""
    q5, k4, v4 = whole["operands"]
    _, hkv, g, _, _ = q5.shape
    width = g * D
    assert whole["o"].shape == (B, T, hkv * width)
    assert whole["o"].dtype == q5.dtype
    for h in range(hkv):
        lanes = slice(h * width, (h + 1) * width)
        o, vjp = jax.vjp(whole["fn"], q5[:, h:h + 1], k4[:, h:h + 1],
                         v4[:, h:h + 1])
        np.testing.assert_array_equal(o, whole["o"][:, :, lanes])
        for got, want, name in zip(vjp(whole["do"][:, :, lanes]),
                                   whole["grads"], "qkv"):
            np.testing.assert_array_equal(got, want[:, h:h + 1],
                                          err_msg=f"d{name}, head {h}")


def test_a_query_head_alone_writes_its_own_lanes(whole):
    """Query head ``g`` of a group alone (one query head on its
    key/value head) gives its ``d`` lanes of ``o`` and its ``dq5`` bit
    for bit; ``dk4`` and ``dv4`` of the group are the sum over its
    heads, in float32 to 1e-5."""
    q5, k4, v4 = whole["operands"]
    _, _, groups, _, _ = q5.shape
    h, dk, dv = q5.shape[1] - 1, 0.0, 0.0
    for g in range(groups):
        lanes = slice((h * groups + g) * D, (h * groups + g + 1) * D)
        o, vjp = jax.vjp(whole["fn"], q5[:, h:h + 1, g:g + 1],
                         k4[:, h:h + 1], v4[:, h:h + 1])
        np.testing.assert_array_equal(o, whole["o"][:, :, lanes])
        dq, dk_g, dv_g = vjp(whole["do"][:, :, lanes])
        np.testing.assert_array_equal(
            dq, whole["grads"][0][:, h:h + 1, g:g + 1])
        dk, dv = dk + dk_g, dv + dv_g
    for got, want in zip((dk, dv), whole["grads"][1:]):
        np.testing.assert_allclose(got, want[:, h:h + 1], atol=1e-5,
                                   rtol=1e-5)


def test_the_heads_first_layout_is_a_transpose_of_the_flat_one(whole):
    """``[b, kv_heads, G, T, d]``, as the kernels wrote ``o`` before,
    is ``jnp.transpose`` of the flat array read by head: row ``(h, g)``
    is dense masked attention of query head ``g`` on key/value head
    ``h``, written out."""
    q5, k4, v4 = whole["operands"]
    _, hkv, g, _, _ = q5.shape
    o5 = jnp.transpose(whole["o"].reshape(B, T, hkv, g, D), (0, 2, 3, 1, 4))
    scores = jnp.einsum("bhgqd,bhkd->bhgqk", q5, k4,
                        precision="highest") * whole["scale"]
    scores = jnp.where(whole["kept"][:, None, None], scores, -jnp.inf)
    want = jnp.einsum("bhgqk,bhkd->bhgqd", jax.nn.softmax(scores, -1), v4,
                      precision="highest")
    np.testing.assert_allclose(o5, want, atol=2e-6)


# -- what reads the flat array: the gate and ``Wo`` ---------------------------

HEADS, HIDDEN, WINDOW = 6, 64, 160


class HeadsLastAttention(M.RuleAttention):
    """``RuleAttention`` in its plain spelling: ``o`` read by head as
    ``[b, T, heads, 128]``, ``o * gate[..., None]``, then ``bthk,hkd->
    btd`` with ``wo`` as the parameter lies. Same leaves, same names."""

    @nn.compact
    def __call__(self, h, table, temporal):
        cfg, dt = self.config, self.config.compute_dtype
        b, t, d = h.shape
        heads = self.kind.n_heads
        q5, k4, v4 = self._qkv(h, table)
        o = rule_attention_heads_first(
            q5, k4, v4, M.layer_rule(cfg, self.kind),
            M._RULE_NAMES[self.kind.attention]).reshape(b, t, heads, -1)
        if cfg.attn_gate:
            gate = jax.nn.sigmoid(self._proj(h, self._dense("wg", (d, heads))))
            o = (o * gate[..., None]).astype(dt)
        wo = self._dense("wo", (heads, cfg.head_dim, d))
        return jnp.einsum("bthk,hkd->btd", o, wo.astype(dt),
                          preferred_element_type=jnp.float32)


@pytest.mark.parametrize("gated", [True, False], ids=["gated", "ungated"])
@pytest.mark.parametrize("attention", ["window", "full"])
def test_the_gate_and_wo_on_the_flat_output_are_their_plain_spelling(
        attention, gated):
    """The module's output and every gradient leaf against the spelling
    with a head axis, float32, 1e-5; the gate's and ``Wo``'s gradients
    are something."""
    kind = M.LayerKind(attention, HEADS, M.Rotary(1e4, (64,)))
    cfg = M.laguna_lm(
        vocab_size=96, d_model=HIDDEN, n_layers=1, layers=[kind],
        n_kv_heads=1, window=WINDOW, n_routed_experts=16, experts_held=(2, 3),
        experts_per_token=4, expert_width=32, shared_expert_width=32,
        dense_width=128, attn_gate=gated, compute_dtype="float32").config
    h = jax.random.normal(jax.random.key(0), (B, T, HIDDEN))
    angles = jnp.broadcast_to(
        jnp.arange(T, dtype=jnp.float32)[None, :, None]
        * 1e4 ** (-jnp.arange(64) / 64), (B, T, 64))
    table = M.fused.tables(angles, 128)
    mine, plain = M.RuleAttention(cfg, kind), HeadsLastAttention(cfg, kind)
    params = mine.init(jax.random.key(1), h, table, None)["params"]
    assert ("wg" in params) is gated
    assert jax.tree.map(jnp.shape, params) == jax.tree.map(
        jnp.shape, plain.init(jax.random.key(1), h, table, None)["params"])
    # weights large enough that gate and product matter
    params = jax.tree.map(lambda a: a * 20.0 if a.ndim > 1 else a, params)

    def loss(module):
        return lambda p: jnp.sum(jnp.sin(
            module.apply({"params": p}, h, table, None)))

    rel = lambda a, b: float(jnp.linalg.norm(a - b)
                             / (jnp.linalg.norm(b) + 1e-30))
    got, want = (module.apply({"params": params}, h, table, None)
                 for module in (mine, plain))
    assert got.shape == (B, T, HIDDEN) and rel(got, want) < 1e-5
    g_mine, g_plain = (jax.grad(loss(m))(params) for m in (mine, plain))
    errs = jax.tree.map(rel, g_mine, g_plain)
    assert max(jax.tree.leaves(errs)) < 1e-5, errs
    for leaf in ("wo", "wq", "wv") + (("wg",) if gated else ()):
        assert float(jnp.linalg.norm(g_plain[leaf])) > 0, leaf


def test_by_head_splits_the_tokens_by_eight_and_moves_nothing():
    """``[b, T, heads * d]`` -> ``[b, T // 8, 8, heads, d]``: row-major
    order kept, so element ``(b, t, h * d + k)`` is ``(b, t // 8, t % 8,
    h, k)``; a sum over the last axis is the sum over a head's lanes."""
    x = jnp.arange(2 * 16 * 3 * 4, dtype=jnp.float32).reshape(2, 16, 12)
    x5 = sparse.by_head(x, 4)
    assert x5.shape == (2, 2, 8, 3, 4)
    np.testing.assert_array_equal(x5.reshape(2, 16, 3, 4),
                                  x.reshape(2, 16, 3, 4))
    np.testing.assert_array_equal(
        jnp.sum(x5, -1).reshape(2, 16, 3), x.reshape(2, 16, 3, 4).sum(-1))

"""``ops/rule_attention.py``'s two causal rules (every causal key, the
last ``window`` of them) through its kernels in interpret mode: each
rule against its dense mask pair by pair, its tile tables against the
closed form, forward and the three gradients against dense masked
float32 attention at six and eight query heads a key/value head, the
exact leak test of the window, and the names a call gives its kernels
and its saved arrays. And, over the four forward kernels that share one
tile body (``blockdiff``, ``causal``, ``window``, ``sparse``): the row
statistics as the kernel writes them and as the forward rule saves
them, and the turned tile's mask."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import test_sparse_attention as sparse_case
from sparktorch_tpu.ops import rule_attention as mod
from sparktorch_tpu.ops import sparse_attention as sparse
from sparktorch_tpu.ops.block_diffusion_attention import (
    BlockDiffusionMask, block_diffusion_attention,
    block_diffusion_attention_heads_first)
from sparktorch_tpu.ops.rule_attention import (
    Causal, CausalWindow, rule_attention, rule_attention_heads_first,
    saved_names)
from test_block_diffusion_attention import D, _grads, dense
from test_sparse_attention import pallas_calls


def dense_rule(rule_name: str, t: int, window: int) -> np.ndarray:
    """The issue's sentence written out pair by pair."""
    mask = np.zeros((t, t), bool)
    for i in range(t):
        first = 0 if rule_name == "causal" else max(0, i - window + 1)
        mask[i, first:i + 1] = True
    return mask


def rule_of(rule_name: str, window: int):
    return Causal() if rule_name == "causal" else CausalWindow(window)


def make_qkv(t: int, groups: int, kv: int = 1, rows: int = 1):
    keys = jax.random.split(jax.random.key(t + groups), 3)
    return tuple(jax.random.normal(kk, (rows, t, h, D), jnp.float32)
                 for kk, h in zip(keys, (kv * groups, kv, kv)))


@pytest.mark.parametrize("rule_name,t,window,tiles", [
    ("causal", 384, 0, (128, 128)), ("causal", 1024, 0, (256, 512)),
    ("causal", 8192, 0, (256, 512)),
    ("window", 384, 160, (128, 128)), ("window", 384, 128, (128, 128)),
    ("window", 384, 1, (128, 128)), ("window", 384, 384, (128, 128)),
    ("window", 1024, 512, (256, 512)), ("window", 1024, 513, (256, 512)),
    ("window", 2048, 512, (256, 256)), ("window", 8192, 512, (256, 512))])
def test_a_rule_is_its_dense_mask_and_its_tables_the_closed_form(
        rule_name, t, window, tiles):
    bq, bk = tiles
    rule = rule_of(rule_name, window)
    if t <= 2048:
        mask = dense_rule(rule_name, t, window)
        i, j = np.arange(t)[:, None], np.arange(t)[None, :]
        assert np.array_equal(rule(i, j), mask)
        kept = mask.reshape(t // bq, bq, t // bk, bk).any((1, 3))
        want = sorted(map(tuple, np.argwhere(kept)))
    # closed form: K tile ki holds a key of Q tile qi iff its first key
    # is at or before the tile's last query, and (window) its last key at
    # or after the first key the tile's first query attends
    closed = [(qi, ki) for qi in range(t // bq) for ki in range(t // bk)
              if ki * bk <= qi * bq + bq - 1 and (
                  rule_name == "causal"
                  or ki * bk + bk - 1 >= qi * bq - (window - 1))]
    (qt, kt), (qt2, kt2) = mod.visited_tiles(rule, t, bq, bk)
    assert sorted(zip(qt, kt)) == closed
    if t <= 2048:
        assert closed == want
    assert sorted(zip(qt2, kt2)) == closed
    assert np.all(np.diff(qt) >= 0) and np.all(np.diff(kt2) >= 0)


def test_at_the_cells_rows_the_window_visits_62_tiles_and_causal_272():
    """8,192 tokens in tiles of 256 x 512: two K tiles a Q tile under the
    window (one for the first two), 8.13 M pairs computed for 4.06 M
    kept; causal 35.65 M for 33.56 M."""
    assert mod._blocks(8_192) == (256, 512)
    assert mod.tiles_visited(CausalWindow(512), 8_192) == (62, 512)
    assert mod.tiles_visited(Causal(), 8_192) == (272, 512)
    assert 62 * 256 * 512 == 8_126_464 and 272 * 256 * 512 == 35_651_584
    t, w = 8_192, 512
    assert w * (w + 1) // 2 + (t - w) * w == 4_063_488
    assert t * (t + 1) // 2 == 33_558_528


@pytest.mark.parametrize("rule_name,window,groups", [
    ("causal", 0, 6), ("causal", 0, 8), ("window", 160, 8),
    ("window", 160, 6), ("window", 128, 1), ("window", 1, 1)])
def test_forward_and_gradients_match_dense_masked_attention(
        rule_name, window, groups):
    """Rows of 384 tokens are three tiles of 128: a window of 160 is
    longer than a tile and shorter than the row."""
    t = 384
    rule, mask = rule_of(rule_name, window), dense_rule(rule_name, t, window)
    qkv = make_qkv(t, groups)
    assert mod._blocks(t) == (128, 128)
    ref = lambda q, k, v: dense(q, k, v, mask)
    mine = lambda q, k, v: rule_attention(q, k, v, rule, rule_name)
    np.testing.assert_allclose(mine(*qkv), ref(*qkv), atol=2e-6)
    for got, want, name in zip(_grads(mine, qkv), _grads(ref, qkv), "qkv"):
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-6,
                                   err_msg=f"d{name}")


HEADS_FIRST = {
    # the [b, T, h, d] wrapper, and its heads-first entry
    "window": (lambda *a: rule_attention(*a, CausalWindow(160), "window"),
               lambda *a: rule_attention_heads_first(
                   *a, CausalWindow(160), "window")),
    "blockdiff": (
        lambda *a: block_diffusion_attention(*a, BlockDiffusionMask(192, 4)),
        lambda *a: block_diffusion_attention_heads_first(
            *a, BlockDiffusionMask(192, 4))),
}


@pytest.mark.parametrize("name", list(HEADS_FIRST))
def test_the_heads_first_entry_is_the_op_without_its_turns(name):
    """What the decoder calls: operands as the kernels read them give
    the flat ``o [b, T, heads * d]`` and, backward, the cotangents as
    the kernels write them, bit for bit what the ``[b, T, h, d]``
    wrapper turns in and reads by head."""
    wrapper, entry = HEADS_FIRST[name]
    qkv = make_qkv(384, 6, kv=2)
    q5, k4, v4 = sparse.heads_first(*qkv, "test")
    assert q5.shape == (1, 2, 6, 384, D) and k4.shape == (1, 2, 384, D)
    by_head = lambda *a: entry(*a).reshape(1, 384, 12, D)
    assert entry(q5, k4, v4).shape == (1, 384, 12 * D)
    np.testing.assert_array_equal(by_head(q5, k4, v4), wrapper(*qkv))
    got = _grads(by_head, (q5, k4, v4))
    want = sparse.heads_first(*_grads(wrapper, qkv), "test")
    for a, b, which in zip(got, want, "qkv"):
        np.testing.assert_array_equal(a, b, err_msg=f"d{which}")


def test_a_key_512_back_changes_nothing_and_a_key_511_back_does():
    """The exact leak test, at the published window and the cell's tiles
    (1,024 tokens: 256 x 512): a change to key and value ``j`` leaves the
    output of query ``j + 512`` as it was bit for bit, and moves that of
    query ``j + 511``, the last that attends it. Forward and, through the
    query's cotangent, dk and dv."""
    t, w, j = 1_024, 512, 300
    rule = CausalWindow(w)
    q, k, v = make_qkv(t, 2)
    bump = lambda x: x.at[:, j].add(1.0)
    base = rule_attention(q, k, v, rule, "window")
    moved = rule_attention(q, bump(k), bump(v), rule, "window")
    assert np.array_equal(np.asarray(base[:, j + w:]),
                          np.asarray(moved[:, j + w:]))
    assert np.array_equal(np.asarray(base[:, :j]), np.asarray(moved[:, :j]))
    assert not np.array_equal(np.asarray(base[:, j + w - 1]),
                              np.asarray(moved[:, j + w - 1]))
    assert not np.array_equal(np.asarray(base[:, j]), np.asarray(moved[:, j]))
    # the gradient of query i's output reaches keys i - 511 .. i alone
    for i, reached in ((j + w, False), (j + w - 1, True)):
        dk, dv = jax.grad(
            lambda k, v: jnp.sum(rule_attention(q, k, v, rule, "window")
                                 [:, i]), argnums=(0, 1))(k, v)
        for g in (dk, dv):
            assert bool(jnp.any(g[:, j] != 0)) is reached
            assert not bool(jnp.any(g[:, :i - w + 1] != 0))
            assert not bool(jnp.any(g[:, i + 1:] != 0))


def test_a_window_of_the_whole_row_is_causal_attention():
    qkv = make_qkv(384, 2)
    np.testing.assert_array_equal(
        rule_attention(*qkv, CausalWindow(384), "window"),
        rule_attention(*qkv, Causal(), "causal"))
    with pytest.raises(ValueError, match="holds no key"):
        CausalWindow(0)


@pytest.mark.parametrize("name", ["window", "causal"])
def test_a_call_names_its_kernels_and_what_a_remat_may_keep(name):
    """Two kinds of layer in one step separate in a trace, and a policy
    that lists one kind's saved arrays keeps that kind's alone."""
    rule, qkv = rule_of(name, 160), make_qkv(384, 2)
    kernels = [f"{name}_attn_fwd", f"{name}_attn_bwd_dq",
               f"{name}_attn_bwd_dkv"]
    assert saved_names(name) == (f"{name}_attn_out", f"{name}_attn_lse")
    other = "causal" if name == "window" else "window"
    policies = jax.checkpoint_policies
    counts = {}
    for kept in (name, other):
        attend = jax.checkpoint(
            lambda q, k, v: rule_attention(q, k, v, rule, name),
            policy=policies.save_only_these_names(*saved_names(kept)))
        grad = jax.grad(lambda q, k, v: jnp.sum(attend(q, k, v)),
                        argnums=(0, 1, 2))
        jaxpr = jax.make_jaxpr(grad)(*qkv).jaxpr
        counts[kept] = [pallas_calls(jaxpr, k) for k in kernels]
        assert pallas_calls(jaxpr, f"{other}_attn_fwd") == 0
    assert counts == {name: [1, 1, 1], other: [2, 1, 1]}


@dataclasses.dataclass(frozen=True)
class Without:
    """``rule`` with one query's row emptied: a query that keeps
    nothing, as a learned selection may leave one."""

    rule: object
    query: int

    def __call__(self, i, j):
        return self.rule(i, j) & (i != self.query)


T, EMPTY = 384, 200
FORWARD_KERNELS = {
    # kernel name: (the rule, query heads a key/value head)
    "blockdiff": (BlockDiffusionMask(T // 2, 4), 2),
    "causal": (Causal(), 6),
    "window": (CausalWindow(160), 8),
    "sparse": (None, 2),
}


def forward_call(name, dtype, empty_row: bool = False):
    """``(forward rule of the kernel called name on ``q, k, v`` turned
    heads first, q, k, v, its dense mask [rows, T, T])``: the rule
    kernels under their own names, the selected-key kernel on a random
    causal int8 mask; ``empty_row``: query ``EMPTY`` keeps no key."""
    rule, groups = FORWARD_KERNELS[name]
    if name == "sparse":
        mask = np.array(sparse_case.make_mask("random", T))
        if empty_row:
            mask[:, EMPTY] = 0
        mask = jnp.asarray(mask)
        return (lambda q, k, v: sparse._forward(
                    *sparse.heads_first(q, k, v, name), mask),
                *sparse_case.make_qkv(T, dtype), np.asarray(mask) != 0)
    if empty_row:
        rule = Without(rule, EMPTY)
    i, j = np.arange(T)[:, None], np.arange(T)[None, :]
    q, k, v = (x.astype(dtype) for x in make_qkv(T, groups, kv=2))
    return (lambda q, k, v: mod._forward(
                *sparse.heads_first(q, k, v, name), rule, name), q, k, v,
            np.broadcast_to(rule(i, j), (1, T, T)))


@pytest.mark.parametrize("name", list(FORWARD_KERNELS))
def test_the_forward_kernel_writes_one_statistic_a_row(name):
    """Its second result is ``[b, kv_heads, G, T]`` float32, T along the
    lanes, and that array itself is what the forward rule names for a
    remat: no ``[.., T, 128]`` float32 array of statistics spread over
    the lanes, and no slice of one, anywhere in a forward call (the
    operands are bfloat16, so any float32 array 128 wide would be
    one)."""
    forward, q, k, v, _ = forward_call(name, jnp.bfloat16)
    jaxpr = jax.make_jaxpr(forward)(q, k, v).jaxpr
    (kernel,) = [eqn for eqn in jaxpr.eqns if eqn.primitive.name ==
                 "pallas_call" and eqn.params["name"] == f"{name}_attn_fwd"]
    out, lse = (var.aval for var in kernel.outvars)
    b, hkv, groups = q.shape[0], k.shape[2], q.shape[2] // k.shape[2]
    assert (out.shape, out.dtype) == ((b, T, hkv * groups * D), jnp.bfloat16)
    assert (lse.shape, lse.dtype) == ((b, hkv, groups, T), jnp.float32)
    named = {eqn.params["name"]: eqn.invars[0] for eqn in jaxpr.eqns
             if eqn.primitive.name == "name"}
    assert saved_names("sparse") == sparse.SAVED_NAMES
    assert named[saved_names(name)[1]] is kernel.outvars[1]
    spread = [var.aval for eqn in jaxpr.eqns for var in eqn.outvars
              if var.aval.dtype == jnp.float32 and var.aval.shape[-1:] == (
                  128,)]
    assert not spread


@pytest.mark.parametrize("name", list(FORWARD_KERNELS))
def test_the_saved_statistics_are_the_dense_log_sum_exp(name):
    """A row, in float32, against the masked scores' log-sum-exp written
    out; a query that keeps nothing gets zeros and the statistics the
    kernel started from (the masked score and the floor of the sum)."""
    forward, q, k, v, mask = forward_call(name, jnp.float32, empty_row=True)
    out, res = forward(q, k, v)
    lse = res[-1]
    groups = q.shape[2] // k.shape[2]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, groups, axis=2),
                        precision="highest") * D ** -0.5
    kept = jnp.asarray(mask)[:, None]
    want = jnp.where(
        kept.any(-1),
        jax.nn.logsumexp(jnp.where(kept, scores, -jnp.inf), axis=-1),
        sparse._NEG + np.log(1e-20))
    assert not mask[:, EMPTY].any() and mask.any(-1).sum() == mask.shape[
        0] * (T - 1)
    np.testing.assert_allclose(lse, want.reshape(lse.shape), atol=1e-5,
                               rtol=1e-6)
    assert out.shape == (q.shape[0], T, q.shape[2] * D)
    assert np.all(np.asarray(out[:, EMPTY]) == 0)
    assert np.all(np.isfinite(np.asarray(out)))


@pytest.mark.parametrize("rule", [
    Causal(), CausalWindow(512), BlockDiffusionMask(512, 4)], ids=repr)
def test_a_rule_on_a_row_of_queries_is_its_transpose(rule):
    """What the forward kernel's turned tile rests on: a rule is
    element-wise on its broadcast indices, so a row of queries against a
    column of keys gives keys down, queries across, and the kernel's
    mask of a tile is the same pairs either way up."""
    t = 1_024
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    down = rule(i, j)
    assert down.shape == (t, t) and down.any() and not down.all()
    assert np.array_equal(rule(i.T, j.T), down.T)
    for qi, ki in ((0, 0), (3, 1), (7, 3), (5, 0)):
        tile = mod._keep(rule, qi, ki, 128, 256)
        turned = mod._keep(rule, qi, ki, 128, 256, keys_down=True)
        assert turned.shape == (256, 128)
        assert np.array_equal(turned, np.asarray(tile).T)
        assert np.array_equal(
            tile, down[qi * 128:(qi + 1) * 128, ki * 256:(ki + 1) * 256])

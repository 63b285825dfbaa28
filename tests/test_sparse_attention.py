"""``ops/sparse_attention.py`` in interpret mode against dense masked
attention: forward and both gradients, grouped-query heads, selected
sets that leave whole tiles empty; and what a caller's remat keeps of
the forward pass."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from sparktorch_tpu.ops.sparse_attention import (
    SAVED_NAMES, heads_first, sparse_attention,
    sparse_attention_heads_first)

B, HQ, HKV, D = 2, 4, 2, 128
# sequence lengths and the tiles the kernels cut them into
SEQS = {384: (128, 128), 768: (256, 256), 1024: (256, 512)}


def dense(q, k, v, mask):
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    s = jnp.where(mask[:, None] != 0, s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


def make_mask(kind: str, t: int):
    rng = np.random.default_rng(3)
    causal = np.tril(np.ones((t, t), bool))
    if kind == "causal":
        mask = np.broadcast_to(causal, (B, t, t)).copy()
    else:
        mask = (rng.random((B, t, t)) < 0.3) & causal
        mask[:, np.arange(t), np.arange(t)] = True
    if kind == "empty_tiles":
        # the queries past the first K tile of row 0 select nothing in
        # it: every tile of that block column is empty; and one query's
        # first selected key lies in its last tile
        block_k = SEQS[t][1]
        mask[0, block_k:, :block_k] = False
        mask[1, t - 56, :t - 128] = False
    return jnp.asarray(mask.astype(np.int8))


def make_qkv(t: int, dtype=jnp.float32):
    keys = jax.random.split(jax.random.key(0), 3)
    return tuple(jax.random.normal(kk, (B, t, h, D), dtype)
                 for kk, h in zip(keys, (HQ, HKV, HKV)))


@pytest.fixture(scope="module")
def qkv():
    return make_qkv(384)


def _grads(fn, qkv):
    weight = jnp.cos(jnp.arange(D, dtype=jnp.float32))
    return jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v) * weight),
                    argnums=(0, 1, 2))(*qkv)


@pytest.mark.parametrize("t", list(SEQS))
@pytest.mark.parametrize("kind", ["random", "empty_tiles", "causal"])
def test_forward_and_gradients_match_dense_masked_attention(kind, t):
    from sparktorch_tpu.ops import sparse_attention as mod

    assert mod._blocks(t) == SEQS[t]
    qkv, mask = make_qkv(t), make_mask(kind, t)
    mine = lambda q, k, v: sparse_attention(q, k, v, mask)
    ref = lambda q, k, v: dense(q, k, v, mask)
    np.testing.assert_allclose(mine(*qkv), ref(*qkv), atol=2e-6)
    for got, want, name in zip(_grads(mine, qkv), _grads(ref, qkv), "qkv"):
        np.testing.assert_allclose(got, want, atol=2e-5, err_msg=f"d{name}")


def test_a_query_that_selects_nothing_gets_zeros_and_no_nan(qkv):
    mask = np.array(make_mask("random", 384))
    mask[0, 7, :] = 0
    out = sparse_attention(*qkv, jnp.asarray(mask))
    assert np.all(np.asarray(out[0, 7]) == 0)
    assert np.all(np.isfinite(np.asarray(out)))
    grads = _grads(lambda q, k, v: sparse_attention(
        q, k, v, jnp.asarray(mask)), qkv)
    assert all(np.all(np.isfinite(np.asarray(g))) for g in grads)


def test_in_bfloat16_it_is_dense_attention_to_bfloat16s_precision():
    """The model hands the kernels bfloat16: scores and sums stay in
    float32, the probabilities and the result are rounded."""
    qkv, mask = make_qkv(384, jnp.bfloat16), make_mask("empty_tiles", 384)
    got = sparse_attention(*qkv, mask)
    assert got.dtype == jnp.bfloat16
    want = dense(*(x.astype(jnp.float32) for x in qkv), mask)
    np.testing.assert_allclose(got.astype(jnp.float32), want, atol=2e-2)


def test_the_heads_first_entry_is_the_op_without_its_turns(qkv):
    """What the decoder calls: operands as the kernels read them (``q5
    [b, kv_heads, G, T, d]``, ``k4``, ``v4``) give the flat ``o [b, T,
    heads * d]``, head ``i`` in lanes ``[i * d, (i + 1) * d)``, and,
    backward, the cotangents as the kernels write them, bit for bit what
    the ``[b, T, h, d]`` wrapper turns in and reads by head."""
    mask = make_mask("random", 384)
    q5, k4, v4 = heads_first(*qkv, "test")
    assert q5.shape == (B, HKV, HQ // HKV, 384, D)
    assert k4.shape == v4.shape == (B, HKV, 384, D)
    o = sparse_attention_heads_first(q5, k4, v4, mask)
    assert o.shape == (B, 384, HQ * D)
    np.testing.assert_array_equal(o.reshape(B, 384, HQ, D),
                                  sparse_attention(*qkv, mask))
    weight = jnp.tile(jnp.cos(jnp.arange(D, dtype=jnp.float32)), HQ)
    got = jax.grad(lambda *a: jnp.sum(
        sparse_attention_heads_first(*a, mask) * weight),
        argnums=(0, 1, 2))(q5, k4, v4)
    want = heads_first(*_grads(lambda *a: sparse_attention(*a, mask), qkv),
                       "test")
    for a, b, name in zip(got, want, "qkv"):
        np.testing.assert_array_equal(a, b, err_msg=f"d{name}")


@pytest.mark.parametrize("bad", ["mask_dtype", "head_dim", "heads", "seq"])
def test_a_shape_that_cannot_be_tiled_is_an_error(qkv, bad):
    q, k, v = qkv
    mask = make_mask("causal", 384)
    if bad == "mask_dtype":
        mask = mask.astype(jnp.int32)
    elif bad == "head_dim":
        q, k, v = (x[..., :64] for x in (q, k, v))
    elif bad == "heads":
        q = q[:, :, :3]
    else:
        q, k, v, mask = (x[:, :200] for x in (q, k, v, mask[:, :, :200]))
    with pytest.raises(ValueError, match="sparse_attention"):
        sparse_attention(q, k, v, mask)


def pallas_calls(jaxpr, name: str) -> int:
    """``pallas_call``s of that name in the jaxpr and in every jaxpr
    inside it."""
    return sum(
        (eqn.primitive.name == "pallas_call"
         and eqn.params["name"] == name)
        + sum(pallas_calls(sub, name)
              for sub in jax.core.jaxprs_in_params(eqn.params))
        for eqn in jaxpr.eqns)


POLICIES = {
    # policy of the caller's remat, forward kernels in the gradient
    "nothing_saveable": (jax.checkpoint_policies.nothing_saveable, 2),
    "exported_names": (
        jax.checkpoint_policies.save_only_these_names(*SAVED_NAMES), 1),
    "everything_saveable": (jax.checkpoint_policies.everything_saveable, 1),
}


@pytest.mark.parametrize("policy", list(POLICIES))
def test_under_a_remat_the_forward_kernel_runs_again_only_if_nothing_is_kept(
        qkv, policy):
    """A caller whose policy lists the module's names (or keeps
    everything) gets the backward pass on the arrays the forward pass
    left; one that keeps nothing pays a second forward kernel. The
    numbers are the bare call's under every policy."""
    mask = make_mask("empty_tiles", 384)
    bare = lambda q, k, v: sparse_attention(q, k, v, mask)
    keep, n_fwd = POLICIES[policy]
    remat = jax.checkpoint(bare, policy=keep)
    np.testing.assert_array_equal(remat(*qkv), bare(*qkv))
    for got, want, name in zip(_grads(remat, qkv), _grads(bare, qkv), "qkv"):
        np.testing.assert_array_equal(got, want, err_msg=f"d{name}")
    jaxpr = jax.make_jaxpr(lambda *a: _grads(remat, a))(*qkv).jaxpr
    assert pallas_calls(jaxpr, "sparse_attn_fwd") == n_fwd
    assert pallas_calls(jaxpr, "sparse_attn_bwd_dq") == 1
    assert pallas_calls(jaxpr, "sparse_attn_bwd_dkv") == 1
    # outside a remat the names keep nothing and cost nothing
    bare_jaxpr = jax.make_jaxpr(lambda *a: _grads(bare, a))(*qkv).jaxpr
    assert pallas_calls(bare_jaxpr, "sparse_attn_fwd") == 1


def test_the_row_statistics_are_kept_without_an_axis_of_one():
    """``[b, kv_heads, G, T]``: a trailing axis of one would be padded
    to 128 lanes on the TPU, 128 times the array."""
    qkv, mask = make_qkv(384, jnp.bfloat16), make_mask("causal", 384)
    jaxpr = jax.make_jaxpr(lambda q, k, v: jax.vjp(
        lambda *a: sparse_attention(*a, mask), q, k, v)[0])(*qkv).jaxpr
    named = {eqn.params["name"]: eqn.outvars[0].aval for eqn in jaxpr.eqns
             if eqn.primitive.name == "name"}
    assert set(named) == set(SAVED_NAMES)
    out, lse = (named[n] for n in SAVED_NAMES)
    assert (out.shape, out.dtype) == ((B, 384, HQ * D), jnp.bfloat16)
    assert (lse.shape, lse.dtype) == ((B, HKV, HQ // HKV, 384), jnp.float32)

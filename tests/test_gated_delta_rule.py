"""``ops/gated_delta_rule.py`` (the chunked WY form, Pallas, interpret
mode here) against the token-by-token recurrence it restates: outputs
and all five gradients, the state carried from chunk to chunk and from
block to block, nothing leaking backwards in time, the shapes it refuses,
what a remat policy keeps (each chunk's ``T`` among it) and what the
backward kernel no longer computes: the inverse.

Tolerances. With float32 operands every product in the kernels is at
full precision and the two derivations differ by float32's order of
sums: 1e-5 of the output's norm is ten times what is read here, and 1e-4
of a gradient's (at a decay near 0 the log-decays' gradient is itself
e^-5 of the others', and float32's noise 2e-5 of it). With
bfloat16 operands the chunk's ``T``, ``W``, ``V'`` and the state enter
their products rounded to 8 bits of mantissa where the recurrence (on
the same bfloat16 operands) keeps float32: 4e-3 of the norm is read on
the output, 2e-2 is the limit, and a lost state or a wrong decay is off
by O(1)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparktorch_tpu.ops import gated_delta_rule as G
from sparktorch_tpu.ops.gated_delta_rule import gated_delta_rule

D = 128
NAMES = ("q", "k", "v", "g", "beta")


def recurrence(q, k, v, g, beta, reset_at=()):
    """The rule token by token, float32; ``reset_at``: token indices
    before which the state is set to 0 (a planted fault)."""
    b, t, fk = q.shape
    hv, hk = v.shape[-1] // D, fk // D
    heads = lambda x, n: jnp.repeat(
        x.astype(jnp.float32).reshape(b, t, n, D), hv // n, 2)
    keep = jnp.ones((t,)).at[jnp.asarray(reset_at, jnp.int32)].set(0.0)

    def step(state, x):
        q, k, v, g, beta, keep = x
        state = (keep * jnp.exp(g))[..., None, None] * state
        u = beta[..., None] * (v - jnp.einsum(
            "bhkv,bhk->bhv", state, k, precision="highest"))
        state = state + k[..., :, None] * u[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q,
                                 precision="highest")

    _, o = jax.lax.scan(
        step, jnp.zeros((b, hv, D, D), jnp.float32),
        (*(jnp.moveaxis(a, 1, 0) for a in (
            heads(q, hk), heads(k, hk), heads(v, hv), g, beta)), keep))
    return jnp.moveaxis(o, 0, 1).reshape(b, t, hv * D)


def operands(seed, b, t, hk, hv, rate, dtype=jnp.float32):
    """``(q, k, v, g, beta, w)``: unit keys, queries scaled as the layer
    scales them, log-decays around ``-rate`` a token, and a cotangent."""
    ks = jax.random.split(jax.random.key(seed), 6)
    unit = lambda x: (x / jnp.linalg.norm(x, axis=-1, keepdims=True)
                      ).reshape(b, t, -1)
    q = (unit(jax.random.normal(ks[0], (b, t, hk, D))) * D ** -0.5)
    k = unit(jax.random.normal(ks[1], (b, t, hk, D)))
    v = jax.random.normal(ks[2], (b, t, hv * D))
    g = -rate * jax.random.uniform(ks[3], (b, t, hv), minval=0.5, maxval=1.5)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, hv)))
    w = jax.random.normal(ks[5], (b, t, hv * D))
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta, w)


@functools.lru_cache(maxsize=None)
def both(seed, b, t, hk, hv, rate, chunk, dtype="float32"):
    """``((out, grads) of the op, (out, grads) of the recurrence)``."""
    *args, w = operands(seed, b, t, hk, hv, rate, jnp.dtype(dtype))

    def run(fn):
        weighed = lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w)
        return jax.jit(lambda *a: (fn(*a), jax.grad(
            weighed, argnums=(0, 1, 2, 3, 4))(*a)))(*args)

    return (run(functools.partial(gated_delta_rule, chunk=chunk)),
            run(recurrence))


def rel(a, b):
    a, b = (np.asarray(x, np.float32) for x in (a, b))
    return float(np.linalg.norm(a - b) / (np.linalg.norm(b) + 1e-30))


# (tokens, chunk): two and four chunks in one block; sixteen chunks,
# two blocks of eight, so a state is kept and read back; small chunks
CASES = {"2_chunks": (128, 64), "4_chunks": (256, 64),
         "2_blocks": (1024, 64), "chunk_16": (64, 16)}
# a decay near 1 (the state lives hundreds of tokens) and near 0 (it is
# gone within a token: every exponent is far below 0)
RATES = {"decay_near_1": 1e-3, "decay_near_0": 5.0}


@pytest.mark.parametrize("rate", list(RATES))
@pytest.mark.parametrize("case", list(CASES))
def test_the_output_is_the_recurrences(case, rate):
    (out, _), (want, _) = both(0, 1, *CASES[case][:1], 1, 2, RATES[rate],
                               CASES[case][1])
    assert rel(out, want) < 1e-5


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("rate", list(RATES))
@pytest.mark.parametrize("case", list(CASES))
def test_each_gradient_is_the_recurrences(case, rate, name):
    (_, grads), (_, want) = both(0, 1, *CASES[case][:1], 1, 2, RATES[rate],
                                 CASES[case][1])
    i = NAMES.index(name)
    assert grads[i].shape == want[i].shape
    assert grads[i].dtype == want[i].dtype
    assert rel(grads[i], want[i]) < 1e-4


@pytest.mark.parametrize("part", ["out", *NAMES])
def test_rows_and_grouped_heads(part):
    """Two rows, two key heads under four value heads: value head ``j``
    reads key head ``j // 2``, and the key heads' cotangents are the sums
    over their value heads."""
    (out, grads), (want, g_want) = both(1, 2, 128, 2, 4, 0.05, 64)
    got, ref = ((out, want) if part == "out" else
                (grads[NAMES.index(part)], g_want[NAMES.index(part)]))
    assert rel(got, ref) < 1e-5


@pytest.mark.parametrize("part", ["out", *NAMES])
def test_bfloat16_operands(part):
    (out, grads), (want, g_want) = both(2, 1, 256, 1, 2, 0.02, 64,
                                        "bfloat16")
    got, ref = ((out, want) if part == "out" else
                (grads[NAMES.index(part)], g_want[NAMES.index(part)]))
    assert out.dtype == jnp.bfloat16
    assert rel(got, ref) < 2e-2


def test_keys_that_repeat_do_not_break_the_inverse():
    """A language's keys repeat. With one key all along a chunk the
    powers of ``A`` grow as binomials (``A^32`` of a whole 64 x 64 to
    1e8) while ``(I - A)^-1`` stays below 1: the blocks' substitution
    holds where the whole series would cancel."""
    q, k, v, g, beta, _ = operands(3, 1, 128, 1, 2, 1e-3)
    k = jnp.broadcast_to(k[:, :1], k.shape)
    beta = jnp.full_like(beta, 0.9)
    out = gated_delta_rule(q, k, v, g, beta)
    assert rel(out, recurrence(q, k, v, g, beta)) < 1e-5


@pytest.mark.parametrize("boundary", [64, 512])
def test_the_state_is_carried_across_chunks_and_blocks(boundary):
    """A state reset planted at a chunk's (64) or a block's (512) first
    token is far from the rule; the op is the rule."""
    t = 2 * boundary
    q, k, v, g, beta, _ = operands(4, 1, t, 1, 2, 2e-3)
    out = gated_delta_rule(q, k, v, g, beta)
    reset = recurrence(q, k, v, g, beta, reset_at=(boundary,))
    assert rel(out[:, boundary:], reset[:, boundary:]) > 0.3
    assert rel(out, recurrence(q, k, v, g, beta)) < 1e-5


@pytest.mark.parametrize("operand", range(5))
def test_nothing_leaks_backwards_in_time(operand):
    """A change at token 70 (inside the second chunk) moves no output
    before it, bit for bit, and moves some after it."""
    args = list(operands(5, 1, 256, 1, 2, 0.01)[:5])
    out = gated_delta_rule(*args)
    args[operand] = args[operand].at[:, 70].multiply(0.5)
    moved = gated_delta_rule(*args)
    np.testing.assert_array_equal(np.asarray(out[:, :70]),
                                  np.asarray(moved[:, :70]))
    assert rel(moved[:, 70:], out[:, 70:]) > 1e-3


def test_the_backward_pass_leaks_nothing_either():
    """A cotangent on tokens before 70 alone reaches no operand at or
    after token 70."""
    args = operands(6, 1, 256, 1, 2, 0.01)[:5]
    early = (jnp.arange(256) < 70)[None, :, None]
    grads = jax.grad(lambda *a: jnp.sum(jnp.where(
        early, gated_delta_rule(*a), 0.0)), argnums=(0, 1, 2, 3, 4))(*args)
    for grad in grads:
        assert not np.any(np.asarray(grad[:, 70:]))
        assert np.any(np.asarray(grad[:, :70]))


def test_shapes_that_do_not_tile_are_errors():
    q, k, v, g, beta, _ = operands(7, 1, 128, 1, 2, 0.01)
    with pytest.raises(ValueError, match="not whole chunks of 64"):
        gated_delta_rule(q[:, :96], k[:, :96], v[:, :96], g[:, :96],
                         beta[:, :96])
    with pytest.raises(ValueError, match="no power of two"):
        gated_delta_rule(q, k, v, g, beta, chunk=48)
    with pytest.raises(ValueError, match="128 wide"):
        gated_delta_rule(q[..., :64], k[..., :64], v, g, beta)
    with pytest.raises(ValueError, match="a multiple of them"):
        gated_delta_rule(jnp.tile(q, 3), jnp.tile(k, 3), v, g, beta)
    with pytest.raises(ValueError, match="one number a token a value head"):
        gated_delta_rule(q, k, v, g[..., :1], beta)


def _kernels(jaxpr, found=None):
    """``{name: [pallas_call equations]}`` of a jaxpr and all inside it."""
    found = {} if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.setdefault(eqn.params["name"], []).append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _kernels(sub, found)
    return found


def _kernel_calls(jaxpr):
    return {name: len(eqns) for name, eqns in _kernels(jaxpr).items()}


def _under_remat(kept, chunk=64):
    """The rule as a layer calls it: under a remat policy that keeps the
    names ``kept`` and nothing else."""
    policy = jax.checkpoint_policies.save_only_these_names(*kept)
    return jax.checkpoint(
        lambda *a: gated_delta_rule(*a, chunk=chunk) * 2.0, policy=policy)


def _gradient_jaxpr(kept, args, chunk=64):
    layer = _under_remat(kept, chunk)
    return jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(layer(*a)),
                                   argnums=(0, 1, 2, 3, 4)))(*args).jaxpr


@pytest.mark.parametrize("kept,fwd_calls", [(G.SAVED_NAMES, 1), ((), 2)])
def test_a_remat_policy_that_keeps_the_names_runs_the_forward_kernel_once(
        kept, fwd_calls):
    args = operands(8, 1, 128, 1, 2, 0.01)[:5]
    assert _kernel_calls(_gradient_jaxpr(kept, args)) == {
        "gdn_fwd": fwd_calls, "gdn_bwd": 1}


def _full_precision_tile_products(jaxpr, chunk):
    """How many ``dot_general`` s in a kernel's body (its loops' bodies
    too) multiply two ``chunk x chunk`` matrices, or a batch of them, at
    full precision: the inverse's ten, and the two of ``da = T^T dT
    T^T``. (The products with the identity that turn a token's scalars
    have an operand of 8 rows a chunk; the cases below run 4 chunks a
    block, so 32 rows, no tile.)"""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            highest = jax.lax.Precision.HIGHEST in jax.tree.leaves(
                eqn.params["precision"])
            n += highest and all(
                v.aval.shape[-2:] == (chunk, chunk) for v in eqn.invars)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _full_precision_tile_products(sub, chunk)
    return n


# the inverse's products: four for the 8 x 8 blocks and two a merge
@pytest.mark.parametrize("tokens,chunk,inverse",
                         [(256, 64, 10), (64, 16, 6)])
def test_the_backward_kernel_reads_t_and_inverts_nothing(tokens, chunk,
                                                         inverse):
    """With the names kept the gradient is one ``gdn_fwd`` and one
    ``gdn_bwd``; the forward kernel's body holds the inverse's
    full-precision tile products once for its block's chunks, the
    backward kernel's the two of ``da`` and none of the inverse, and ``T``
    as the forward kernel wrote it is among its operands."""
    args = operands(10, 1, tokens, 1, 2, 0.01)[:5]
    kernels = _kernels(_gradient_jaxpr(G.SAVED_NAMES, args, chunk))
    assert {k: len(v) for k, v in kernels.items()} == {"gdn_fwd": 1,
                                                       "gdn_bwd": 1}
    (fwd,), (bwd,) = kernels["gdn_fwd"], kernels["gdn_bwd"]
    assert _full_precision_tile_products(fwd.params["jaxpr"],
                                         chunk) == inverse
    assert _full_precision_tile_products(bwd.params["jaxpr"], chunk) == 2
    t_kept = fwd.outvars[2].aval
    assert t_kept.shape == (1, 2, tokens // chunk, *G._t_shape(chunk))
    assert [v.aval for v in bwd.invars].count(t_kept) == 1


@pytest.mark.parametrize("tokens,chunk", [(1024, 64), (64, 16)])
def test_the_t_kept_is_each_chunks_inverse(tokens, chunk):
    """``T`` is kept ``[b, value heads, T / chunk, ...]`` float32 (a
    chunk's rows folded side by side, 128 lanes wide at 64), unit lower
    triangular a chunk, and ``(I - A) T = I`` for the chunk's ``A =
    -diag(beta) (K K^T * Gam)`` strictly below the diagonal."""
    q, k, v, g, beta, _ = operands(11, 1, tokens, 1, 2, 0.05)
    n = tokens // chunk
    by_chunk = lambda x: jnp.moveaxis(x, 1, 2).reshape(1, 2, n, 1, chunk)
    gb = jnp.concatenate([jnp.cumsum(by_chunk(g), -1), by_chunk(beta),
                          jnp.zeros((1, 2, n, 6, chunk))], 3)
    _, (*_, kept) = G._forward(q, k, v, gb, chunk)
    assert kept.dtype == jnp.float32
    assert kept.shape == (1, 2, n, *G._t_shape(chunk))
    assert kept.shape[-1] == (128 if chunk == 64 else 32)
    t = np.asarray(G._unfolded(kept, chunk), np.float64)
    assert t.shape == (1, 2, n, chunk, chunk)
    np.testing.assert_array_equal(np.triu(t, 1), 0.0)
    np.testing.assert_array_equal(np.diagonal(t, axis1=-2, axis2=-1), 1.0)
    kc = np.asarray(k, np.float64).reshape(1, 1, n, chunk, D)
    g_sum, b = (np.asarray(gb[..., i, :], np.float64) for i in (0, 1))
    gam = np.exp(np.minimum(g_sum[..., :, None] - g_sum[..., None, :], 0.0))
    a = np.tril(-b[..., :, None] * (kc @ np.swapaxes(kc, -1, -2)) * gam, -1)
    assert np.abs((np.eye(chunk) - a) @ t - np.eye(chunk)).max() < 1e-5


@functools.lru_cache(maxsize=None)
def _remat_gradients(kept):
    """The gradients of the rule under :func:`_under_remat`, two blocks
    of eight chunks, weighed as :func:`both` weighs them."""
    *args, w = operands(12, 1, 1024, 1, 2, 0.01)
    layer = _under_remat(kept)
    return jax.jit(jax.grad(lambda *a: jnp.sum(layer(*a) * w),
                            argnums=(0, 1, 2, 3, 4)))(*args)


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("kept", [G.SAVED_NAMES, ()],
                         ids=["names_kept", "nothing_kept"])
def test_each_gradient_under_a_remat_policy_is_the_recurrences(kept, name):
    """With the names kept the backward kernel reads the forward pass's
    ``T`` and states; with nothing kept the forward kernel runs again
    and makes them again. Either way the gradients are the rule's."""
    want = both(12, 1, 1024, 1, 2, 0.01, 64)[1][1]
    i = NAMES.index(name)
    assert rel(_remat_gradients(kept)[i], 2.0 * want[i]) < 1e-4


def test_the_states_kept_are_one_a_block_of_eight_chunks():
    q, k, v, g, beta, _ = operands(9, 1, 1024, 1, 2, 0.01)
    # G = 0 (no decay) in row 0 and beta = 0.5 in row 1 of the scalars
    gb = jnp.zeros((1, 2, 16, 8, 64)).at[..., 1, :].set(0.5)
    _, (_, _, _, _, states, _) = G._forward(q, k, v, gb, 64)
    assert states.shape == (1, 2, 2, D, D)
    assert not np.any(np.asarray(states[:, :, 0]))   # a row starts from 0
    assert np.any(np.asarray(states[:, :, 1]))
    assert G.chunks_run(1, 1024, 2, 64) == 32


def test_the_chip_smokes_phase_rehearsed_at_a_small_size():
    """``chip_smoke.py``'s ``gated_delta`` phase (the op against the
    recurrence, bf16 operands, output and five gradients) on rows of 128
    tokens, one key head under two value heads."""
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import chip_smoke

    said = chip_smoke.phase_gated_delta(
        chip_smoke.Sizes(gdn_case=(1, 128, 1, 2)), 0, {})
    assert said.startswith("1/2x128x1 out_rel=")

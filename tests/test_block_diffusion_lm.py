"""``models/sparse_moe_lm.py`` as a block-diffusion LM (``sdar_moe_lm``:
the clean row and its noised copy in one sequence under the block mask,
the head and the weighted loss on the noised half) against its plain
reference (``chipbench/reference/sdar-30b-a3b-chat-ep8.py``) at tiny
widths on the CPU, seeded weights, float32, given the SAME noise: same
arithmetic in another order, so 1e-5 relative. bfloat16 in float32's
place reads 2e-3 (``test_bfloat16_for_float32_fails...``)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench import harness
from chipbench.jobs import fit_sync_dlm
from test_sparse_attention import pallas_calls
from test_sparse_moe_lm import chunks_of, rel
from sparktorch_tpu.models import sparse_moe_lm as M
from sparktorch_tpu.utils.losses import TokenWeighted, resolve_loss

REF = harness.load_module("reference", "sdar-30b-a3b-chat-ep8")
ROWS, L, VOCAB, MASK, EPS = 2, 128, 96, 95, 1e-3
STREAM = {M.NOISE_STREAM: jax.random.key(7)}
LOSS = resolve_loss("cross_entropy_weighted")


def sizes(held=(2, 3), layers=2, block=4, dtype="float32", **more):
    """The reference's configuration (the source's keys) and the
    program's module for the same tiny model."""
    cfg = dict(
        hidden_size=64, num_hidden_layers=layers, num_attention_heads=4,
        num_key_value_heads=2, head_dim=128, vocab_size=VOCAB,
        num_routed_experts=16, num_experts_per_tok=4,
        moe_intermediate_size=32, experts_held=list(held), rms_norm_eps=1e-6,
        rope_theta=1e6, block_length=block, mask_token_id=MASK,
        noise_eps=EPS, embedding_init_std=1.0)
    module = M.sdar_moe_lm(
        vocab_size=VOCAB, mask_token_id=MASK, d_model=64, n_layers=layers,
        n_heads=4, n_kv_heads=2, n_routed_experts=16, experts_held=held,
        experts_per_token=4, expert_width=32, block_length=block,
        compute_dtype=dtype, **more)
    return cfg, module


def rows(seed=1):
    return jax.random.randint(jax.random.key(seed), (ROWS, L), 0, MASK)


def noise_of(stream_key):
    """``(t [rows], m [rows, L])`` the model draws from a stream seeded
    with ``stream_key``: the job's restatement less the step's keys."""
    level, masked = M.diffusion_noise(fit_sync_dlm._first_draw(stream_key),
                                      ROWS, L, EPS)
    return level[:, 0], masked


@pytest.fixture(scope="module")
def both():
    """Program and reference on the same weights, rows and noise: the
    noised half's logits, the loss and every gradient leaf."""
    cfg, module = sizes()
    patch = pytest.MonkeyPatch()
    chunks_of(patch, 96)
    variables = REF.init(jax.random.key(0), cfg)
    ids, noise = rows(), noise_of(STREAM[M.NOISE_STREAM])

    def prog_loss(p):
        out = module.apply({"params": p}, ids.astype(jnp.float32),
                           rngs=STREAM)
        return jnp.sum(LOSS(out, ids)), out

    def ref_loss(p):
        return REF.loss_sum({"params": p}, ids, ids, jnp.ones(ROWS), cfg,
                            noise=noise)

    (p_loss, out), p_grads = jax.value_and_grad(
        prog_loss, has_aux=True)(variables["params"])
    r_loss, r_grads = jax.value_and_grad(ref_loss)(variables["params"])
    patch.undo()
    return dict(out=out, r_logits=REF.forward(variables, ids, cfg,
                                              noise=noise),
                p_loss=p_loss, r_loss=r_loss, p_grads=p_grads,
                r_grads=r_grads, noise=noise, cfg=cfg, variables=variables)


def test_the_forward_returns_the_noised_halfs_logits_and_the_weights(both):
    out, (level, masked) = both["out"], both["noise"]
    assert isinstance(out, TokenWeighted)
    assert out.logits.shape == (ROWS, L, VOCAB)  # 96 tiles: no padding
    np.testing.assert_allclose(out.weights, masked / level[:, None],
                               rtol=1e-6)
    assert 0 < int(masked.sum()) < ROWS * L
    assert rel(out.logits, both["r_logits"]) < 1e-5


def test_loss_matches_the_reference(both):
    assert abs(float(both["p_loss"] - both["r_loss"])) \
        < 1e-5 * abs(float(both["r_loss"]))


def test_every_gradient_leaf_matches_the_reference(both):
    errs = jax.tree.map(rel, both["p_grads"], both["r_grads"])
    assert max(jax.tree.leaves(errs)) < 1e-5, errs
    # a comparison of something, but for the last layer's experts: its
    # output matters at masked positions alone, [MASK] tokens share one
    # embedding and so choose alike, here none of the two experts held
    norms = jax.tree.map(lambda g: float(jnp.linalg.norm(g)),
                         both["r_grads"])
    last = norms.pop("layer_1")
    assert min(jax.tree.leaves(norms)) > 0 and min(
        jax.tree.leaves(last["attn"])) > 0


def test_bfloat16_for_float32_fails_the_tolerance(both):
    """The tolerance is tight enough to tell the precision below: the
    reference itself with bfloat16 operands is 200x outside it."""
    ids = rows()
    low = REF.forward(both["variables"], ids, both["cfg"], "bf16",
                      noise=both["noise"])
    assert rel(low, both["r_logits"]) > 1e-3


def test_the_loss_weighs_masked_tokens_by_one_over_t_over_the_rows_length():
    logits = jax.random.normal(jax.random.key(0), (ROWS, L, VOCAB))
    labels = rows()
    level = jnp.asarray([0.25, 0.5])[:, None]
    masked = jax.random.uniform(jax.random.key(1), (ROWS, L)) < level
    got = LOSS(TokenWeighted(logits, masked / level), labels)
    ce = -jnp.take_along_axis(jax.nn.log_softmax(logits), labels[..., None],
                              -1)[..., 0]
    want = jnp.sum(jnp.where(masked, ce, 0.0), -1) / level[:, 0] / L
    np.testing.assert_allclose(got, want, rtol=1e-5)
    # through the fused kernel where "cross_entropy" would pick it
    jaxpr = jax.make_jaxpr(LOSS)(TokenWeighted(logits, masked / level),
                                 labels).jaxpr
    assert pallas_calls(jaxpr, "fused_ce_fwd") == 1
    # 600 columns tile no block of 512: the dense path, the same loss
    wide = jax.random.normal(jax.random.key(2), (ROWS, L, 600))
    odd = TokenWeighted(wide, masked / level)
    assert pallas_calls(jax.make_jaxpr(LOSS)(odd, labels).jaxpr,
                        "fused_ce_fwd") == 0
    ce = -jnp.take_along_axis(jax.nn.log_softmax(wide), labels[..., None],
                              -1)[..., 0]
    np.testing.assert_allclose(
        LOSS(odd, labels),
        jnp.sum(jnp.where(masked, ce, 0.0), -1) / level[:, 0] / L, rtol=1e-5)


@pytest.fixture(scope="module")
def halves():
    """Both halves' logits by the reference, for rows that differ in
    chosen places."""
    cfg, _ = sizes()
    variables = REF.init(jax.random.key(0), cfg)
    ids = rows()
    level, masked = noise_of(jax.random.key(3))
    masked = masked.at[:, 40:44].set(jnp.asarray([True, False, True, True]))
    run = lambda ids, masked: REF.forward(
        variables, ids, cfg, noise=(level, masked), both_halves=True)
    return ids, masked, run


def test_a_clean_tokens_output_does_not_depend_on_any_noised_token(halves):
    ids, masked, run = halves
    base = run(ids, masked)[:, :L]
    other = run(ids, ~masked)[:, :L]  # every noised token changed
    assert np.array_equal(np.asarray(base), np.asarray(other))


def test_x0_inside_a_block_does_not_reach_that_blocks_noised_logits(halves):
    """Tokens 40-43 are one block, 41 unmasked in the noised copy: x_0
    at a MASKED position of the block changes nothing the block's
    noised queries see (the clean copy of their own block is hidden,
    the answer does not leak), and does change later blocks'."""
    ids, masked, run = halves
    base = run(ids, masked)
    changed = run(ids.at[:, 40].set((ids[:, 40] + 1) % MASK), masked)
    block = slice(L + 40, L + 44)
    assert np.array_equal(np.asarray(base[:, block]),
                          np.asarray(changed[:, block]))
    assert rel(changed[:, L + 44:], base[:, L + 44:]) > 1e-6
    # the planted fault leaks it
    cfg, _ = sizes()
    variables = REF.init(jax.random.key(0), cfg)
    leak = lambda ids: REF.forward(
        variables, ids, {**cfg, "fault": "own_block_seen"},
        noise=(noise_of(jax.random.key(3))[0], masked))
    assert rel(leak(ids.at[:, 40].set((ids[:, 40] + 1) % MASK))[:, 40:44],
               leak(ids)[:, 40:44]) > 1e-6


@pytest.mark.parametrize("fault", [
    "own_block_seen", "causal_mask", "positions_not_shared", "shifted_share",
    "no_renorm"])
def test_a_planted_fault_changes_the_references_logits(both, fault):
    ids = rows()
    got = REF.forward(both["variables"], ids, {**both["cfg"], "fault": fault},
                      noise=both["noise"])
    assert rel(got, both["r_logits"]) > 1e-4


@pytest.mark.parametrize("fault", ["no_loss_weight", "loss_on_all"])
def test_a_planted_fault_changes_the_references_loss(both, fault):
    ids = rows()
    got = REF.loss_sum(both["variables"], ids, ids, jnp.ones(ROWS),
                       {**both["cfg"], "fault": fault}, noise=both["noise"])
    assert abs(float(got - both["r_loss"])) > 0.1 * float(both["r_loss"])


def test_without_a_stream_the_forward_draws_from_a_fixed_key():
    """``init`` and a validation forward (it collects no counters) get
    no stream: the noise is then the same at every call, so a validation
    loss is comparable."""
    cfg, module = sizes()
    variables = REF.init(jax.random.key(0), cfg)
    a = module.apply(variables, rows())
    b = module.apply(variables, rows())
    assert np.array_equal(np.asarray(a.weights), np.asarray(b.weights))
    drawn = module.apply(variables, rows(), rngs=STREAM)
    assert not np.array_equal(np.asarray(a.weights),
                              np.asarray(drawn.weights))
    shapes = jax.eval_shape(lambda: module.init(jax.random.key(0), rows()))
    assert set(shapes["params"]["layer_0"]["attn"]) == {
        "wq", "wk", "wv", "wo", "q_norm", "k_norm"}  # no indexer


def test_each_attention_kernel_runs_once_a_layer_in_the_gradient():
    cfg, module = sizes()
    params = REF.init(jax.random.key(0), cfg)["params"]
    ids = rows()
    grad = jax.grad(lambda p: jnp.sum(LOSS(
        module.apply({"params": p}, ids, rngs=STREAM), ids)))
    jaxpr = jax.make_jaxpr(grad)(params).jaxpr
    assert [pallas_calls(jaxpr, k) for k in (
        "blockdiff_attn_fwd", "blockdiff_attn_bwd_dq",
        "blockdiff_attn_bwd_dkv", "sparse_attn_fwd")] == [2, 2, 2, 0]


def test_the_chunk_sets_the_trips_and_changes_no_result(monkeypatch):
    """The module's own rule (twice the pairs the layer expects to hold:
    512 of the 2,048 chosen at two experts of 16) against chunks of 64:
    the loop's trips follow the chunk, the logits and the gradient do
    not."""
    cfg, module = sizes(layers=1)
    params = REF.init(jax.random.key(0), cfg)["params"]
    ids = rows()

    def run(p):
        out, state = module.apply({"params": p}, ids, rngs=STREAM,
                                  mutable=["moe_metrics"])
        return jnp.sum(LOSS(out, ids)), (out.logits, state["moe_metrics"])

    (_, (want, sown_a)), g_a = jax.value_and_grad(run, has_aux=True)(params)
    chunks_of(monkeypatch, 64)
    (_, (got, sown_b)), g_b = jax.value_and_grad(run, has_aux=True)(params)
    moe = lambda sown: sown["layer_0"]["moe"]
    held = int(moe(sown_b)["expert_rows"][0].sum())
    assert 64 < held <= 512
    assert np.array_equal(moe(sown_a)["row_chunks"][0], [1, 4])
    assert np.array_equal(moe(sown_b)["row_chunks"][0],
                          [-(-held // 64), 2 * ROWS * L * 4 // 64])
    assert rel(got, want) < 1e-6
    assert max(jax.tree.leaves(jax.tree.map(rel, g_b, g_a))) < 1e-5


def test_a_training_forward_without_its_stream_is_an_error():
    """A forward that collects the step's counters is a training
    forward: with no stream it would train on one fixed mask (a wrapped
    ``apply`` hides ``train_rngs`` from the step), so it raises; ``init``
    and a forward that collects nothing draw from the fixed key."""
    cfg, module = sizes(layers=1)
    variables = REF.init(jax.random.key(0), cfg)
    with pytest.raises(ValueError, match="train_rngs"):
        module.apply(variables, rows(), mutable=["moe_metrics"])
    module.apply(variables, rows(), mutable=["moe_metrics"], rngs=STREAM)
    module.apply(variables, rows())
    jax.eval_shape(lambda: module.init(jax.random.key(0), rows()))


def test_a_bad_configuration_is_refused():
    with pytest.raises(ValueError, match="mask_token_id"):
        M.sdar_moe_lm(vocab_size=100)
    with pytest.raises(ValueError, match="attention"):
        M.sdar_moe_lm(attention="windowed")
    _, module = sizes(block=3)
    with pytest.raises(ValueError, match="whole blocks"):
        module.init(jax.random.key(0), rows())
    full = M.sdar_moe_lm().config
    assert (full.n_layers, full.vocab_size, full.rope_theta,
            full.block_length, full.mask_token_id) == (
                48, 151_936, 1e6, 4, 151_669)


# -- through the trainer -------------------------------------------------


def _train(n_devices, iters=4, **kwargs):
    from sparktorch_tpu.obs.telemetry import Telemetry
    from sparktorch_tpu.parallel.mesh import build_mesh
    from sparktorch_tpu.train.sync import train_distributed
    from sparktorch_tpu.utils.serde import ModelSpec

    _, module = sizes()
    spec = ModelSpec(module=module, loss="cross_entropy_weighted",
                     optimizer="adam", optimizer_params={"lr": 1e-3},
                     input_shape=(L,))
    ids = np.asarray(jax.random.randint(jax.random.key(3), (4, L), 0, MASK),
                     np.float32)
    tele, records = Telemetry(run_id="test"), []
    train_distributed(
        spec, ids, labels=ids, iters=iters, seed=0, mini_batch=1,
        mesh=build_mesh(devices=jax.devices()[:n_devices]),
        metrics_hook=records.append, telemetry=tele, **kwargs)
    return records, tele


@pytest.mark.parametrize("n_devices", [1, 2])
def test_counters_and_gauges_reach_the_records_and_the_bus(n_devices):
    """And the masked tokens of every step are the job's restated draw:
    the stream differs by step and by shard, as documented."""
    records, tele = _train(n_devices, steps_per_call=2)
    assert len(records) == 4
    drawn = [int(fit_sync_dlm.restated_noise(0, s, n_devices, 1, L, EPS)[1]
                 .sum()) for s in range(4)]
    assert [r["diffusion_masked_tokens"] for r in records] == drawn
    assert len(set(drawn)) == 4
    assert {r["diffusion_tokens"] for r in records} == {n_devices * L}
    assert all(r["moe_pairs_dropped"] == 0.0 and r["moe_rows"] > 0
               for r in records)
    assert tele.counter_value("train.diffusion.masked_tokens") == sum(drawn)
    assert tele.counter_value("train.diffusion.tokens") == 4 * n_devices * L
    assert tele.gauge_value("train.diffusion.block_length") == 4
    # 256 tokens are one tile, visited; two layers, two kv heads, a row
    # a shard
    assert tele.gauge_value("train.diffusion.attn_tiles_visited") \
        == tele.gauge_value("train.diffusion.attn_tiles_total") \
        == 2 * 2 * n_devices
    assert tele.gauge_value("train.moe.experts_held") == 2
    assert tele.gauge_value("train.sparse_attn.topk") is None
    assert np.isfinite([r["loss"] for r in records]).all()


def test_the_gspmd_trainer_refuses_the_model():
    from sparktorch_tpu.parallel.mesh import build_mesh
    from sparktorch_tpu.train.sharded import create_sharded_state
    from sparktorch_tpu.utils.serde import ModelSpec

    _, module = sizes()
    spec = ModelSpec(module=module, loss="cross_entropy_weighted",
                     optimizer="adam", optimizer_params={"lr": 1e-3},
                     input_shape=(L,))
    with pytest.raises(NotImplementedError, match="random stream"):
        create_sharded_state(spec, build_mesh(devices=jax.devices()[:2]),
                             jax.random.key(0), jnp.zeros((2, L), jnp.float32))

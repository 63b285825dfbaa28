"""``models/sparse_moe_lm.py`` as a decoder whose layers differ in kind
(``laguna_lm``: full-causal and window layers with their own head
counts and rotary tables, a gated attention output, a leading dense
layer, sigmoid-routed experts beside a shared one) against its plain
reference (``chipbench/reference/laguna-xs.2-ep16.py``) at tiny widths
on the CPU, seeded weights, float32: same arithmetic in another order,
so 1e-5 relative. bfloat16 in float32's place reads 1e-3 and more
(``test_bfloat16_for_float32_fails...``)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench import harness
from test_sparse_attention import pallas_calls
from test_sparse_moe_lm import chunks_of, rel
from sparktorch_tpu.models import sparse_moe_lm as M
from sparktorch_tpu.utils.losses import resolve_loss

REF = harness.load_module("reference", "laguna-xs.2-ep16")
# rows of 384 tokens are three tiles of 128; the window, 160, is longer
# than a tile and shorter than the row
ROWS, T, VOCAB, WINDOW = 2, 384, 96, 160
KINDS = ["full_attention", "sliding_attention", "sliding_attention",
         "sliding_attention", "full_attention"]
HEADS = [6, 8, 8, 8, 6]      # a key/value head: 6 and 8 query heads
FULL_ROPE = {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
             "original_max_position_embeddings": 4096, "beta_slow": 1,
             "beta_fast": 64, "attention_factor": 1.4158883083359672,
             "partial_rotary_factor": 0.5}
WINDOW_ROPE = {"rope_type": "default", "rope_theta": 10000,
               "partial_rotary_factor": 1}
LOSS = resolve_loss("cross_entropy")


def sizes(held=(2, 3), dtype="float32", **more):
    """The reference's configuration (the source's keys) and the
    program's module for the same tiny model: all three kinds of layer."""
    cfg = dict(
        hidden_size=64, intermediate_size=128, num_hidden_layers=5,
        num_attention_heads_per_layer=HEADS, layer_types=KINDS,
        mlp_layer_types=["dense"] + ["sparse"] * 4, num_key_value_heads=1,
        head_dim=128, vocab_size=VOCAB, sliding_window=WINDOW,
        rope_parameters={"full_attention": FULL_ROPE,
                         "sliding_attention": WINDOW_ROPE},
        gating=True, num_routed_experts=16, num_experts_per_tok=4,
        moe_intermediate_size=32, shared_expert_intermediate_size=32,
        moe_routed_scaling_factor=2.5, experts_held=list(held),
        rms_norm_eps=1e-6, embedding_init_std=1.0)
    full = M.Rotary(5e5, (32,), (64.0, 4096.0, 64.0, 1.0), 1.4158883083359672)
    window = M.Rotary(1e4, (64,))
    layers = [M.LayerKind("full", 6, full, "dense")] + [
        M.LayerKind("window", 8, window)] * 3 + [M.LayerKind("full", 6, full)]
    module = M.laguna_lm(
        vocab_size=VOCAB, d_model=64, n_layers=5, n_kv_heads=1,
        layers=layers, window=WINDOW, n_routed_experts=16, experts_held=held,
        experts_per_token=4, expert_width=32, shared_expert_width=32,
        dense_width=128, compute_dtype=dtype, **more)
    return cfg, module


def rows(seed=1):
    k1, k2 = jax.random.split(jax.random.key(seed))
    return (jax.random.randint(k1, (ROWS, T), 0, VOCAB),
            jax.random.randint(k2, (ROWS, T), 0, VOCAB))


@pytest.fixture(scope="module")
def both():
    """Program and reference on the same weights and rows: logits, the
    loss and every gradient leaf."""
    cfg, module = sizes()
    patch = pytest.MonkeyPatch()
    chunks_of(patch, 96)
    variables = REF.init(jax.random.key(0), cfg)
    ids, labels = rows()

    def prog_loss(p):
        logits = module.apply({"params": p}, ids.astype(jnp.float32))
        return jnp.sum(LOSS(logits, labels)), logits

    def ref_loss(p):
        return REF.loss_sum({"params": p}, ids, labels, jnp.ones(ROWS), cfg)

    (p_loss, p_logits), p_grads = jax.value_and_grad(
        prog_loss, has_aux=True)(variables["params"])
    r_loss, r_grads = jax.value_and_grad(ref_loss)(variables["params"])
    patch.undo()
    return dict(p_logits=p_logits, r_logits=REF.forward(variables, ids, cfg),
                p_loss=p_loss, r_loss=r_loss, p_grads=p_grads,
                r_grads=r_grads, cfg=cfg, variables=variables)


def test_the_trees_are_one_tree_with_a_layers_own_leaves(both):
    """A full layer has 6 query heads and a window layer 8; the first
    layer's MLP is dense, the others hold experts beside a shared one."""
    _, module = sizes()
    shapes = jax.eval_shape(
        lambda: module.init(jax.random.key(0), rows()[0]))["params"]
    ours = jax.tree.map(lambda a: a.shape, shapes)
    theirs = jax.tree.map(lambda a: a.shape, both["variables"]["params"])
    assert ours == theirs
    assert ours["layer_0"]["attn"]["wq"] == (64, 6, 128)
    assert ours["layer_1"]["attn"]["wq"] == (64, 8, 128)
    assert ours["layer_1"]["attn"]["wg"] == (64, 8)
    assert set(ours["layer_0"]) == {"attn_norm", "attn", "mlp_norm", "mlp"}
    assert set(ours["layer_4"]) == {"attn_norm", "attn", "moe_norm", "moe",
                                    "shared"}
    assert ours["layer_0"]["mlp"]["w_gate"] == (64, 128)
    assert ours["layer_4"]["moe"]["router"] == (64, 16)


def test_logits_match_the_reference(both):
    assert both["p_logits"].shape == (ROWS, T, VOCAB)
    assert rel(both["p_logits"], both["r_logits"]) < 1e-5


def test_loss_matches_the_reference(both):
    assert abs(float(both["p_loss"] - both["r_loss"])) \
        < 1e-5 * abs(float(both["r_loss"]))


def test_every_gradient_leaf_matches_the_reference(both):
    errs = jax.tree.map(rel, both["p_grads"], both["r_grads"])
    assert max(jax.tree.leaves(errs)) < 1e-5, errs
    norms = jax.tree.map(lambda g: float(jnp.linalg.norm(g)),
                         both["r_grads"])
    assert min(jax.tree.leaves(norms)) > 0  # a comparison of something


def test_bfloat16_for_float32_fails_the_tolerance(both):
    """The tolerance is tight enough to tell the precision below: the
    reference itself with bfloat16 operands is 100x outside it."""
    low = REF.forward(both["variables"], rows()[0], both["cfg"], "bf16")
    assert rel(low, both["r_logits"]) > 1e-3


@pytest.mark.parametrize("fault", [
    "window_ignored", "rope_swapped", "no_yarn", "no_attn_gate",
    "no_shared_expert", "no_routed_scale", "softmax_scores", "shifted_share",
    "no_renorm"])
def test_a_planted_fault_changes_the_references_logits(both, fault):
    """Softmax in sigmoid's place chooses the same experts (both rise
    with the logit) and, at router logits of 0.16 a standard deviation
    (0.9 at the published width), renormalises to nearly the same gates:
    5e-5 here, above float32's 1e-6; what tells it apart is the router's
    gradient, a quarter of softmax's."""
    got = REF.forward(both["variables"], rows()[0],
                      {**both["cfg"], "fault": fault})
    assert rel(got, both["r_logits"]) > (
        2e-5 if fault == "softmax_scores" else 1e-4)
    if fault == "softmax_scores":
        router = lambda cfg: jax.grad(lambda p: REF.loss_sum(
            {"params": p}, *rows(), jnp.ones(ROWS), cfg))(
                both["variables"]["params"])["layer_1"]["moe"]["router"]
        assert rel(router({**both["cfg"], "fault": fault}),
                   both["r_grads"]["layer_1"]["moe"]["router"]) > 0.3


def test_one_key_too_many_is_seen_by_the_pairs_alone(both):
    """A window of 161 for 160 moves the logits by less than the faults
    above: the job holds the rules to each other pair by pair."""
    i, j = np.arange(T)[:, None], np.arange(T)[None, :]
    sound = REF.allowed(i, j, "sliding_attention", WINDOW)
    wide = REF.allowed(i, j, "sliding_attention", WINDOW, "window_513")
    assert int(np.sum(sound != wide)) == T - WINDOW
    cfg, module = sizes()
    kinds = {k.attention: k for k in module.config.layers}
    assert np.array_equal(M.layer_rule(module.config, kinds["window"])(i, j),
                          sound)
    assert np.array_equal(
        M.layer_rule(module.config, kinds["full"])(i, j),
        REF.allowed(i, j, "full_attention", WINDOW))


def test_yarns_table_and_the_dims_it_leaves_alone():
    """The published full-attention table over 64 dims: pairs 0-5 turn at
    ``5e5^(-i / 32)``, pairs 16 and up at a 64th of it, those between
    blend; written out from the formula by hand (``c(64)`` = 5.66,
    ``c(1)`` = 15.80, so low 5, high 16)."""
    inv = np.asarray(M._yarn_inv_freq(32, 5e5, 64.0, 4096.0, 64.0, 1.0))
    plain = 5e5 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(inv[:6], plain[:6], rtol=1e-6)
    np.testing.assert_allclose(inv[16:], plain[16:] / 64.0, rtol=1e-6)
    assert inv[0] == 1.0 and abs(inv[5] - 0.128687) < 1e-6
    assert abs(inv[16] - 2.20971e-5) < 1e-10
    ramp = (np.arange(6, 16) - 5) / 11.0
    np.testing.assert_allclose(
        inv[6:16], plain[6:16] * (ramp / 64.0 + 1.0 - ramp), rtol=1e-6)
    ref_inv, factor = REF.inv_freq(FULL_ROPE, 128)
    assert np.array_equal(np.asarray(ref_inv), inv)
    assert factor == 1.4158883083359672 == pytest.approx(
        0.1 * np.log(64.0) + 1.0)
    # the first 64 dims of a head turn and carry the factor, the last 64
    # pass: position 0 scales the turned dims alone
    x = jax.random.normal(jax.random.key(0), (1, 4, 2, 128))
    angles = M.rotary_angles(jnp.broadcast_to(jnp.arange(4), (3, 1, 4)),
                             M.Rotary(5e5, (32,), (64.0, 4096.0, 64.0, 1.0),
                                      factor))
    assert angles.shape == (1, 4, 32)
    turned = M._rotate(x, factor * jnp.cos(angles)[:, :, None],
                       factor * jnp.sin(angles)[:, :, None])
    assert np.array_equal(np.asarray(turned[..., 64:]),
                          np.asarray(x[..., 64:]))
    np.testing.assert_allclose(turned[:, 0, :, :64], factor * x[:, 0, :, :64],
                               rtol=1e-6)
    assert not np.allclose(turned[:, 1, :, :64], factor * x[:, 1, :, :64])
    # the window layers' plain table turns all 128
    plain_angles = M.rotary_angles(
        jnp.broadcast_to(jnp.arange(4), (3, 1, 4)), M.Rotary(1e4, (64,)))
    np.testing.assert_allclose(
        plain_angles[0, 3], 3.0 * 1e4 ** (-np.arange(64) / 64.0), rtol=1e-6)


def test_each_attention_kernel_runs_once_a_layer_in_the_gradient():
    """Three window layers and two full ones: each kind's three kernels
    once a layer, the saved arrays of each kept by that layer's remat."""
    cfg, module = sizes()
    params = REF.init(jax.random.key(0), cfg)["params"]
    ids, labels = rows()
    grad = jax.grad(lambda p: jnp.sum(LOSS(
        module.apply({"params": p}, ids), labels)))
    jaxpr = jax.make_jaxpr(grad)(params).jaxpr
    calls = {k: pallas_calls(jaxpr, k) for k in (
        "window_attn_fwd", "window_attn_bwd_dq", "window_attn_bwd_dkv",
        "causal_attn_fwd", "causal_attn_bwd_dq", "causal_attn_bwd_dkv",
        "blockdiff_attn_fwd", "sparse_attn_fwd")}
    assert list(calls.values()) == [3, 3, 3, 2, 2, 2, 0, 0]


def test_a_layers_kind_is_the_layers_and_one_kind_is_the_old_model():
    """The published pattern, and a model of one kind described layer by
    layer is the model the four fields describe."""
    full = M.laguna_lm().config
    assert (full.n_layers, full.vocab_size, full.n_routed_experts,
            full.window, full.layers_of("window"), full.layers_of("full")) \
        == (40, 100_352, 256, 512, 30, 10)
    assert [(k.attention, k.n_heads, k.mlp) for k in full.layers[:5]] == [
        ("full", 48, "dense"), ("window", 64, "experts"),
        ("window", 64, "experts"), ("window", 64, "experts"),
        ("full", 48, "experts")]
    assert M.laguna_lm(n_layers=5).config.layers == full.layers[:5]
    keye = M.keye_vl2_lm(n_layers=3).config
    assert keye.layers == (M.LayerKind(
        "learned_sparse", 32, M.Rotary(1e7, (16, 24, 24))),) * 3
    from_file = M.laguna_lm(n_layers=1, layers=[{
        "attention": "window", "n_heads": 16, "mlp": "experts",
        "rotary": {"theta": 1e4, "sections": [64]}}]).config
    assert from_file.layers == (M.LayerKind("window", 16,
                                            M.Rotary(1e4, (64,))),)
    with pytest.raises(ValueError, match="layers are described"):
        M.laguna_lm(n_layers=4, layers=full.layers[:5])
    with pytest.raises(ValueError, match="every layer's attention or none"):
        M.sdar_moe_lm(n_layers=2, layers=(
            M.LayerKind("block_diffusion", 32, M.Rotary(1e6, (64,))),
            M.LayerKind("full", 32, M.Rotary(1e6, (64,)))))
    with pytest.raises(ValueError, match="scoring"):
        M.laguna_lm(scoring="tanh")
    with pytest.raises(ValueError, match="multiple of"):
        M.laguna_lm(n_kv_heads=7)


# -- through the trainers ------------------------------------------------


def _spec():
    from sparktorch_tpu.utils.serde import ModelSpec

    _, module = sizes()
    return ModelSpec(module=module, loss="cross_entropy", optimizer="adam",
                     optimizer_params={"lr": 1e-3}, input_shape=(T,))


def _train(n_devices, iters=2, **kwargs):
    from sparktorch_tpu.obs.telemetry import Telemetry
    from sparktorch_tpu.parallel.mesh import build_mesh
    from sparktorch_tpu.train.sync import train_distributed

    k1, k2 = jax.random.split(jax.random.key(3))
    ids = np.asarray(jax.random.randint(k1, (4, T), 0, VOCAB), np.float32)
    labels = np.asarray(jax.random.randint(k2, (4, T), 0, VOCAB), np.float32)
    tele, records = Telemetry(run_id="test"), []
    result = train_distributed(
        _spec(), ids, labels=labels, iters=iters, seed=0,
        mesh=build_mesh(devices=jax.devices()[:n_devices]),
        metrics_hook=records.append, telemetry=tele, **kwargs)
    return records, result, tele


@pytest.fixture(scope="module")
def one_and_two_shards():
    return _train(1, steps_per_call=1), _train(2, steps_per_call=2)


@pytest.mark.parametrize("field", ["loss", "grad_norm", "examples",
                                   "moe_rows"])
def test_dp2_on_the_cpu_mesh_equals_one_shard_on_the_same_rows(
        one_and_two_shards, field):
    (one, _, _), (two, _, _) = one_and_two_shards
    assert len(one) == len(two) == 2
    for a, b in zip(one, two):
        assert a[field] == pytest.approx(b[field], rel=2e-5)


def test_counters_and_gauges_reach_the_records_and_the_bus(
        one_and_two_shards):
    """Four of the five layers hold experts: the counters are theirs, and
    the dense layer's absence moves nothing (4 rows x 384 tokens x 4
    choices a layer, 2 of 16 experts held)."""
    for n_devices, (records, _, tele) in zip((1, 2), one_and_two_shards):
        for r in records:
            assert r["moe_pairs_dropped"] == 0.0
            assert 0 < r["moe_rows"] < 4 * 4 * T * 4
            assert r["moe_rows_mean"] == r["moe_rows"] / (4 * 2)
        assert tele.gauge_value("train.moe.experts_held") == 2
        assert tele.gauge_value("train.moe.experts_routed") == 16
        assert tele.gauge_value("train.moe.shared_width") == 32
        assert tele.gauge_value("train.attention.window") == WINDOW
        assert tele.gauge_value("train.attention.layers_window") == 3
        assert tele.gauge_value("train.attention.layers_full") == 2
        assert tele.gauge_value("train.sparse_attn.topk") is None
        assert tele.gauge_value("train.diffusion.block_length") is None
        # tiles of 128 x 128 over 384 tokens: causal visits 6 of 9, and
        # so does a window of 160 (a Q tile's first query reaches 159
        # keys back, into the tile before the last: 1 + 2 + 3); over the
        # layers of the kind, the step's 4 rows and one key/value head
        assert (tele.gauge_value("train.attention.full_tiles_visited"),
                tele.gauge_value("train.attention.full_tiles_total")) \
            == (2 * 4 * 6, 2 * 4 * 9)
        assert (tele.gauge_value("train.attention.window_tiles_visited"),
                tele.gauge_value("train.attention.window_tiles_total")) \
            == (3 * 4 * 6, 3 * 4 * 9)
        keys = records[0]["leaf_grad_norm_keys"]
        assert {"layer_0.mlp.w_down", "layer_4.shared.w_up",
                "layer_2.attn.wg"} <= set(keys)


def test_the_gspmd_and_pipeline_trainers_refuse_the_model():
    import optax

    from sparktorch_tpu.parallel.mesh import MeshConfig, build_mesh
    from sparktorch_tpu.train.sharded import (create_sharded_state,
                                              make_sharded_train_step)
    from sparktorch_tpu.train.sync import train_distributed

    spec = _spec()
    assert "Pallas kernel" in spec.module.sync_dp_only
    mesh = build_mesh(devices=jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="GSPMD.*Pallas kernel"):
        create_sharded_state(spec, mesh, jax.random.key(0),
                             jnp.zeros((2, T), jnp.float32))
    with pytest.raises(NotImplementedError, match="GSPMD"):
        make_sharded_train_step(spec.module.apply, LOSS, optax.adam(1e-3),
                                mesh, state_shardings=())
    pp_mesh = build_mesh(MeshConfig(dp=1, pp=2), devices=jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="pipeline"):
        train_distributed(spec, np.zeros((4, T), np.float32),
                          labels=np.zeros((4, T), np.float32), mesh=pp_mesh,
                          iters=1)

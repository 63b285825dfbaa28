"""``models/sparse_moe_lm.py`` as a decoder of latent-attention layers
with a selection bias on its router and a multi-token prediction module
(``joyai_flash_lm``) against its plain reference
(``chipbench/reference/joyai-llm-flash-ep16.py``) at tiny widths on the
CPU, seeded weights, float32: same arithmetic in another order, so 1e-5
relative on logits and loss; 3e-5 on a gradient leaf, because the latent
norms' gains see a sum over 32-64 lanes of products that the two sides
round in different orders (read 1.2e-5 at most). bfloat16 in float32's
place reads 1e-3 and more. And that the three older models are what they
were on the parent commit: their trees and their lowered steps."""

import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench import harness
from test_sparse_attention import pallas_calls
from test_sparse_moe_lm import chunks_of, rel
from sparktorch_tpu.models import sparse_moe_lm as M
from sparktorch_tpu.utils.losses import MultiTokenLogits, resolve_loss

REF = harness.load_module("reference", "joyai-llm-flash-ep16")
# rows of 384 tokens are three tiles of 128; the head's dims are the
# published ones (the kernels tile nothing narrower), the ranks are not
ROWS, T, VOCAB, HEADS = 2, 384, 96, 2
LOSS = resolve_loss("cross_entropy_multi_token")
FAULTS = ["no_mtp_loss", "mtp_unshifted", "mtp_own_head", "scale_128",
          "rope_on_whole_head", "rope_by_halves", "k_rope_normed",
          "no_latent_norm", "no_selection_bias", "bias_in_gates",
          "no_shared_expert", "no_routed_scale", "softmax_scores",
          "shifted_share", "no_renorm"]


def sizes(held=(2, 3), dtype="float32", layers=3, **more):
    """The reference's configuration (the source's keys) and the
    program's module for the same tiny model: a dense layer, expert
    layers, the module."""
    cfg = dict(
        hidden_size=64, intermediate_size=128, num_hidden_layers=layers,
        num_attention_heads=HEADS, q_lora_rank=48, kv_lora_rank=32,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        rope_theta=32_000_000, first_k_dense_replace=1, vocab_size=VOCAB,
        num_routed_experts=16, num_experts_per_tok=4,
        moe_intermediate_size=32, n_shared_experts=1,
        routed_scaling_factor=2.5, experts_held=list(held),
        rms_norm_eps=1e-6, num_nextn_predict_layers=1, mtp_loss_weight=0.3,
        embedding_init_std=1.0, selection_bias_std=0.03)
    rotary = M.Rotary(3.2e7, (32,))
    module = M.joyai_flash_lm(
        vocab_size=VOCAB, d_model=64, n_layers=layers,
        layers=[M.LayerKind("latent", HEADS, rotary,
                            "experts" if i else "dense")
                for i in range(layers)],
        q_lora_rank=48, kv_lora_rank=32, n_routed_experts=16,
        experts_held=held, experts_per_token=4, expert_width=32,
        shared_expert_width=32, dense_width=128, compute_dtype=dtype, **more)
    return cfg, module


def rows(seed=1):
    k1, k2 = jax.random.split(jax.random.key(seed))
    return (jax.random.randint(k1, (ROWS, T), 0, VOCAB),
            jax.random.randint(k2, (ROWS, T), 0, VOCAB))


@pytest.fixture(scope="module")
def both():
    """Program and reference on the same weights and rows: both heads'
    logits, the loss, the counters and every gradient leaf."""
    cfg, module = sizes()
    patch = pytest.MonkeyPatch()
    chunks_of(patch, 96)
    variables = REF.init(jax.random.key(0), cfg)
    ids, labels = rows()

    def prog_loss(p):
        out, sown = module.apply({"params": p}, ids.astype(jnp.float32),
                                 mutable=["moe_metrics"])
        return jnp.sum(LOSS(out, labels)), (out, sown["moe_metrics"])

    def ref_loss(p, fault=None):
        return REF.loss_sum({"params": p}, ids, labels, jnp.ones(ROWS),
                            {**cfg, "fault": fault})

    (p_loss, (out, sown)), p_grads = jax.value_and_grad(
        prog_loss, has_aux=True)(variables["params"])
    r_loss, r_grads = jax.value_and_grad(ref_loss)(variables["params"])
    patch.undo()
    return dict(out=out, sown=sown, r_logits=REF.forward(variables, ids, cfg),
                p_loss=p_loss, r_loss=r_loss, p_grads=p_grads,
                r_grads=r_grads, cfg=cfg, variables=variables,
                ref_loss=ref_loss)


def test_the_trees_are_one_tree(both):
    _, module = sizes()
    shapes = jax.eval_shape(
        lambda: module.init(jax.random.key(0), rows()[0]))["params"]
    ours = jax.tree.map(lambda a: a.shape, shapes)
    assert ours == jax.tree.map(lambda a: a.shape,
                                both["variables"]["params"])
    assert set(ours) == {"embed", "head", "final_norm", "layer_0", "layer_1",
                         "layer_2", "mtp"}  # ONE embedding, ONE head
    assert ours["layer_0"]["attn"] == {
        "w_dq": (64, 48), "q_norm": (48,), "w_uq": (48, HEADS, 192),
        "w_dkv": (64, 32 + 64), "kv_norm": (32,),
        "w_ukv": (32, HEADS, 256), "wo": (HEADS, 128, 64)}
    assert ours["layer_2"]["moe"]["selection_bias"] == (16,)
    assert set(ours["mtp"]) == {"embed_norm", "hidden_norm", "proj", "layer",
                                "final_norm"}
    assert ours["mtp"]["proj"] == (128, 64)
    assert set(ours["mtp"]["layer"]) == set(ours["layer_2"])


def test_logits_of_both_heads_match_the_reference(both):
    """The module's last position has no next embedding: the program
    computes it on a stand-in and gives it no weight; the reference runs
    ``T - 1`` positions."""
    out, (r_logits, r_mtp) = both["out"], both["r_logits"]
    assert isinstance(out, MultiTokenLogits) and out.weight == 0.3
    assert out.logits.shape == out.mtp_logits.shape == (ROWS, T, VOCAB)
    assert r_mtp.shape == (ROWS, T - 1, VOCAB)
    assert rel(out.logits, r_logits) < 1e-5
    assert rel(out.mtp_logits[:, :-1], r_mtp) < 1e-5


def test_loss_and_the_modules_own_counter_match_the_reference(both):
    assert abs(float(both["p_loss"] - both["r_loss"])) \
        < 1e-5 * abs(float(both["r_loss"]))
    total, count = REF.mtp_loss_seen(both["variables"], rows()[0],
                                     both["cfg"])
    assert count == ROWS * (T - 2)
    assert float(both["sown"]["mtp_tokens"][0]) == count
    assert float(both["sown"]["mtp_loss"][0]) == pytest.approx(
        float(total), rel=1e-5)


def test_every_gradient_leaf_matches_the_reference(both):
    """The selection biases' gradient is exactly 0 on both sides: the
    gradient does not reach them."""
    for side in ("p_grads", "r_grads"):
        g = both[side]
        for moe in (g["layer_1"]["moe"], g["layer_2"]["moe"],
                    g["mtp"]["layer"]["moe"]):
            assert not np.any(np.asarray(moe["selection_bias"]))
    errs = jax.tree.map(rel, both["p_grads"], both["r_grads"])
    assert max(jax.tree.leaves(errs)) < 3e-5, errs
    norms = jax.tree_util.tree_flatten_with_path(jax.tree.map(
        lambda g: float(jnp.linalg.norm(g)), both["r_grads"]))[0]
    assert all(n > 0 for path, n in norms
               if "selection_bias" not in jax.tree_util.keystr(path))


def test_the_shared_leaves_gradients_are_the_two_paths_sums(both):
    """The embedding and the head serve the main path and the module:
    each one's gradient is the sum of the gradient under the next-token
    loss alone and under the module's loss alone (by the reference, whose
    ``no_mtp_loss`` drops the second), and the module adds to both."""
    p = both["variables"]["params"]
    whole = both["p_grads"]
    main = jax.grad(both["ref_loss"])(p, "no_mtp_loss")
    for leaf in ("embed", "head"):
        module_part = whole[leaf] - main[leaf]
        assert rel(whole[leaf], main[leaf]) > 1e-2
        assert float(jnp.linalg.norm(module_part)) > 0
    # the module's loss alone: the whole less the main, leaf by leaf,
    # is what a copy of the head would have kept from the shared one
    own = jax.grad(both["ref_loss"])(p, "mtp_own_head")
    assert rel(own["head"], main["head"]) < 1e-5
    assert rel(own["embed"], whole["embed"]) < 1e-5


def test_bfloat16_for_float32_fails_the_tolerance(both):
    low = REF.forward(both["variables"], rows()[0], both["cfg"], "bf16")
    assert rel(low[0], both["r_logits"][0]) > 1e-3
    assert rel(low[1], both["r_logits"][1]) > 1e-3


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_changes_the_references_result(both, fault):
    """The faults of the module's loss and head change no logit: they
    change the loss, or the shared head's gradient. Held to the label one
    too early the loss moves little at random weights (every label costs
    about ln V: 3e-4 here, over float32's 1e-6) and the head's gradient
    much."""
    cfg = {**both["cfg"], "fault": fault}
    params = both["variables"]["params"]
    if fault in ("no_mtp_loss", "mtp_unshifted"):
        loss = both["ref_loss"](params, fault)
        assert abs(float(loss - both["r_loss"])) > (
            1e-4 if fault == "mtp_unshifted" else 1e-2) * float(
                both["r_loss"])
    if fault in ("mtp_unshifted", "mtp_own_head"):
        head = jax.grad(both["ref_loss"])(params, fault)["head"]
        assert rel(head, both["r_grads"]["head"]) > 1e-2
    if fault not in ("no_mtp_loss", "mtp_unshifted", "mtp_own_head"):
        got = REF.forward(both["variables"], rows()[0], cfg)
        moved = max(rel(a, b) for a, b in zip(got, both["r_logits"]))
        # renormalised gates hardly feel a common shift: softmax for
        # sigmoid, or a bias of 0.03 on scores near a half
        small = fault in ("softmax_scores", "bias_in_gates")
        assert moved > (2e-5 if small else 1e-4)


def test_the_selection_bias_chooses_and_does_not_gate():
    """A bias that lifts expert 5 over every score sends every token to
    it, whatever its score; its gate is still its score's share."""
    cfg, module = sizes(held=(5,), layers=2)
    params = REF.init(jax.random.key(0), cfg)["params"]["layer_1"]["moe"]
    g = jax.random.normal(jax.random.key(5), (ROWS, T, 64))
    layer = M.HeldExperts(module.config)
    lifted = {**params, "selection_bias": jnp.zeros(16).at[5].set(10.0)}
    _, state = layer.apply({"params": lifted}, g, mutable=["moe_metrics"])
    assert float(state["moe_metrics"]["expert_rows"][0][0]) == ROWS * T
    flat = {**params, "selection_bias": jnp.zeros(16)}
    _, state = layer.apply({"params": flat}, g, mutable=["moe_metrics"])
    assert float(state["moe_metrics"]["expert_rows"][0][0]) < ROWS * T
    ein = lambda eq, a, b: jnp.einsum(eq, a, b, precision="highest")
    for p in (lifted, flat, params):
        want = jnp.stack([REF._experts_row(p, row, REF._sizes(cfg), ein, None)
                          for row in g])
        assert rel(layer.apply({"params": p}, g), want) < 1e-5
    # tokens that do not exist reach no expert and no counter
    live = jnp.arange(T)[None, :] < jnp.asarray([[T], [10]])
    out, state = layer.apply({"params": lifted}, g, live,
                             mutable=["moe_metrics"])
    assert float(state["moe_metrics"]["expert_rows"][0][0]) == T + 10
    assert float(state["moe_metrics"]["routed"][0]) == T + 10
    assert not np.any(np.asarray(out[1, 10:]))


@pytest.mark.parametrize("n_shares", [16, 4])
def test_the_shares_of_the_expert_layer_sum_to_the_uncut_layer(n_shares):
    """Each share routes over all 16 experts under the same biases and
    computes its own; the shares' routed parts and ONE shared expert are
    the uncut layer."""
    cfg, module = sizes(held=tuple(range(16)))
    layer_p = REF.init(jax.random.key(4), cfg)["params"]["layer_2"]
    whole = layer_p["moe"]
    g = jax.random.normal(jax.random.key(5), (ROWS, T, 64), jnp.float32)
    ein = lambda eq, a, b: jnp.einsum(eq, a, b, precision="highest")
    want = jnp.stack([
        REF._experts_row(whole, row, REF._sizes(cfg), ein, None)
        + REF._swiglu(layer_p["shared"], row, ein) for row in g])
    config = module.config
    shared = M.SwiGLU(config, config.shared_expert_width,
                      "shared_expert").apply({"params": layer_p["shared"]}, g)
    per, total = 16 // n_shares, 0.0
    for share in range(n_shares):
        held = tuple(range(share * per, (share + 1) * per))
        params = {"router": whole["router"],
                  "selection_bias": whole["selection_bias"],
                  **{k: whole[k][jnp.asarray(held)]
                     for k in ("w_gate", "w_up", "w_down")}}
        out = M.HeldExperts(sizes(held=held)[1].config).apply(
            {"params": params}, g)
        total = total + out
        assert rel(out + shared, want) > 1e-2
    assert rel(total + shared, want) < 1e-5
    assert rel(total + n_shares * shared, want) > 1e-2


def test_each_kernel_runs_once_a_layer_in_the_gradient():
    """Three layers and the module's: four latent layers, each attention
    kernel and the layout op's backward once a layer; the layout op's
    forward a second time under the layer's remat; both heads through
    the loss's kernel where it tiles (it does not at 96 columns)."""
    cfg, module = sizes()
    params = REF.init(jax.random.key(0), cfg)["params"]
    ids, labels = rows()
    grad = jax.grad(lambda p: jnp.sum(LOSS(
        module.apply({"params": p}, ids), labels)))
    jaxpr = jax.make_jaxpr(grad)(params).jaxpr
    calls = {k: pallas_calls(jaxpr, k) for k in (
        "latent_attn_fwd", "latent_attn_bwd_dq", "latent_attn_bwd_dkv",
        "latent_rope_fwd", "latent_rope_bwd", "causal_attn_fwd",
        "qk_norm_rope_fwd")}
    assert list(calls.values()) == [4, 4, 4, 8, 4, 0, 0]


def test_the_published_model_and_what_a_configuration_may_not_say():
    full = M.joyai_flash_lm().config
    assert (full.n_layers, full.vocab_size, full.n_routed_experts,
            full.layers_of("latent"), full.mtp_depth, full.mtp_weight,
            full.selection_bias) == (40, 129_280, 256, 40, 1, 0.3, True)
    assert (full.q_lora_rank, full.kv_lora_rank, full.qk_nope_dim,
            full.qk_rope_dim, full.v_dim) == (1_536, 512, 128, 64, 128)
    assert [(k.attention, k.n_heads, k.mlp) for k in full.layers[:2]] == [
        ("latent", 32, "dense"), ("latent", 32, "experts")]
    with pytest.raises(ValueError, match="a latent layer needs"):
        M.joyai_flash_lm(q_lora_rank=0)
    with pytest.raises(ValueError, match="a latent layer needs"):
        M.joyai_flash_lm(qk_rope_dim=32)
    with pytest.raises(ValueError, match="one multi-token prediction"):
        M.joyai_flash_lm(mtp_depth=2)
    with pytest.raises(ValueError, match="which holds experts"):
        M.joyai_flash_lm(n_layers=1)
    with pytest.raises(ValueError, match="after a causal model"):
        M.sdar_moe_lm(n_layers=2, mtp_depth=1)


# -- the older models are the parent's ---------------------------------------

# sha256 (first 16) of each older model's parameter tree (paths, shapes,
# dtypes) and of the lowered text of the gradient of its loss with its
# counters, at the sizes below. The trees are the ones read on commit
# 6539c26 (PR 38) by the same lines: no PR since has moved a leaf. The
# steps were read anew at PR 41, which meant to change them (``o``
# leaves the attention kernels flat and ``Wo`` reads it so), and at PR
# 47, which meant to as well (the held experts' products are
# ``ops/grouped_mlp.py``'s kernels, in all five older models and in
# ``tests/test_gated_delta_lm.py``'s and ``tests/test_short_conv_lm.py``'s
# hashes too), and at PR 48, likewise in all five (the held
# experts' sums back are ``grouped_mlp.sum_back``, no scatter-add of a
# chunk), and at PR 51, likewise in all five (the held experts fetch a
# chunk's rows by ``grouped_mlp.fetch_rows``, no gather of a chunk; loss
# and every gradient leaf read bit-equal to the parent's on seeded rows
# in all five models before the hashes were read). A PR that means to
# change one of these models' steps reads them anew.
PARENT = {"keye": ("96e560cdd12a17e7", "6e0a8e2287eec3e6"),
          "sdar": ("b60f6b0d23c7320d", "d52f4bc8b427cb9c"),
          "laguna": ("0ce980a58c390130", "c79c1f5b0ab261dd")}


def older_model(name):
    moe = dict(vocab_size=VOCAB, d_model=64, n_kv_heads=1,
               experts_per_token=2, expert_width=32)
    if name == "keye":
        return M.keye_vl2_lm(
            n_layers=2, n_heads=2, idx_heads=2, idx_dim=32, idx_rope_dims=16,
            topk=128, n_routed_experts=8, experts_held=(1, 2),
            **moe), "cross_entropy"
    if name == "sdar":
        return M.sdar_moe_lm(
            mask_token_id=95, n_layers=2, n_heads=2, n_routed_experts=8,
            experts_held=(1, 2), **moe), "cross_entropy_weighted"
    full = M.Rotary(5e5, (32,), (64.0, 4096.0, 64.0, 1.0), 1.4158883083359672)
    layers = [M.LayerKind("full", 6, full, "dense"),
              M.LayerKind("window", 8, M.Rotary(1e4, (64,))),
              M.LayerKind("full", 6, full)]
    return M.laguna_lm(
        **{**moe, "experts_per_token": 4}, n_layers=3, layers=layers,
        window=160, n_routed_experts=16, experts_held=(2, 3),
        shared_expert_width=32, dense_width=128), "cross_entropy"


@pytest.mark.parametrize("name", list(PARENT))
def test_an_older_model_builds_the_parents_tree_and_lowered_step(name):
    """And carries no leaf of what it does not have: no selection bias,
    no module."""
    module, loss = older_model(name)
    loss_fn = resolve_loss(loss)
    ids = jnp.zeros((2, T), jnp.float32)
    shapes = jax.eval_shape(
        lambda: module.init(jax.random.key(0), ids))["params"]
    paths = [jax.tree_util.keystr(path) for path, _ in
             jax.tree_util.tree_flatten_with_path(shapes)[0]]
    assert not [p for p in paths if "selection_bias" in p or "mtp" in p]
    tree = str(jax.tree_util.tree_flatten_with_path(
        jax.tree.map(lambda a: (a.shape, str(a.dtype)), shapes))[0])

    def step(p, x, y):
        out, sown = module.apply(
            {"params": p}, x, mutable=["moe_metrics"],
            rngs={"diffusion": jax.random.key(1)})
        return loss_fn(out, y).sum(), sown

    text = jax.jit(jax.grad(step, has_aux=True)).lower(
        shapes, ids, ids).as_text()
    sha = lambda s: hashlib.sha256(s.encode()).hexdigest()[:16]
    assert (sha(tree), sha(text)) == PARENT[name]


# -- through the trainers ------------------------------------------------


def _spec():
    from sparktorch_tpu.utils.serde import ModelSpec

    _, module = sizes()
    return ModelSpec(module=module, loss="cross_entropy_multi_token",
                     optimizer="adam", optimizer_params={"lr": 1e-3},
                     input_shape=(T,))


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
                                   "moe_rows", "mtp_loss", "mtp_tokens"])
def test_dp2_on_the_cpu_mesh_equals_one_shard_on_the_same_rows(
        one_and_two_shards, field):
    (one, _, _), (two, _, _) = one_and_two_shards
    assert len(one) == len(two) == 2
    for a, b in zip(one, two):
        assert a[field] == pytest.approx(b[field], rel=2e-5)


def test_counters_and_gauges_reach_the_records_and_the_bus(
        one_and_two_shards):
    """Two of the three layers and the module's layer hold experts: three
    layers' counters (4 rows x 384 tokens x 4 choices a layer, the
    module's layer one token a row fewer; 2 of 16 experts held)."""
    for records, _, tele in one_and_two_shards:
        for r in records:
            assert r["moe_pairs_dropped"] == 0.0
            assert 0 < r["moe_rows"] < (3 * T - 1) * 4 * 4
            assert r["moe_rows_mean"] == r["moe_rows"] / (3 * 2)
            assert r["mtp_tokens"] == 4 * (T - 2)
            assert 0.5 * np.log(VOCAB) < r["mtp_loss"] < 2 * np.log(VOCAB)
        assert tele.gauge_value("train.moe.experts_held") == 2
        assert tele.gauge_value("train.moe.selection_bias") == 1
        assert tele.gauge_value("train.moe.shared_width") == 32
        assert tele.gauge_value("train.attention.layers_latent") == 3
        assert tele.gauge_value("train.attention.latent_q_rank") == 48
        assert tele.gauge_value("train.attention.latent_kv_rank") == 32
        assert tele.gauge_value("train.mtp.depth") == 1
        assert tele.gauge_value("train.mtp.weight") == pytest.approx(0.3)
        assert tele.gauge_value("train.mtp.loss") == records[-1]["mtp_loss"]
        assert tele.counter_value("train.mtp.tokens") == 2 * 4 * (T - 2)
        assert tele.gauge_value("train.attention.layers_full") is None
        # tiles of 128 x 128 over 384 tokens: 6 of 9, over the three
        # layers and the module's, the step's 4 rows and 2 heads
        assert (tele.gauge_value("train.attention.latent_tiles_visited"),
                tele.gauge_value("train.attention.latent_tiles_total")) \
            == (4 * 4 * HEADS * 6, 4 * 4 * HEADS * 9)
        keys = set(records[0]["leaf_grad_norm_keys"])
        assert {"mtp.proj", "mtp.layer.attn.w_uq", "layer_1.attn.w_dkv",
                "layer_2.moe.selection_bias", "embed", "head"} <= keys


def test_the_gspmd_and_pipeline_trainers_refuse_the_model():
    import optax

    from sparktorch_tpu.parallel.mesh import MeshConfig, build_mesh
    from sparktorch_tpu.train.sharded import (create_sharded_state,
                                              make_sharded_train_step)
    from sparktorch_tpu.train.sync import train_distributed

    spec = _spec()
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

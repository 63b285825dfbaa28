"""The expert exchange across an ``ep`` axis under the sync DP trainer (PR
49): ``HeldExperts`` whole across four members, the step placing and
reducing the carry leaf by leaf, Mellum2's model against its plain
reference. Tiny widths, float32, on 4 of the suite's 8 host devices."""

import importlib.util
import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from sparktorch_tpu.models import sparse_moe_lm as M
from sparktorch_tpu.models import tiny_transformer
from sparktorch_tpu.models.transformer import CausalLM
from sparktorch_tpu.parallel.mesh import MeshConfig, build_mesh
from sparktorch_tpu.parallel.sharding_rules import decoder_ep_axes
from sparktorch_tpu.train import sync
from sparktorch_tpu.train.step import TrainState, cut_specs, make_train_step
from sparktorch_tpu.utils.data import DataBatch
from sparktorch_tpu.utils.losses import resolve_loss
from sparktorch_tpu.utils.serde import ModelSpec

REPO = Path(__file__).resolve().parent.parent
T, VOCAB, EXPERTS, MEMBERS = 128, 96, 16, 4
TINY = dict(vocab_size=VOCAB, d_model=64, n_layers=2, n_kv_heads=1,
            n_routed_experts=EXPERTS, experts_held=tuple(range(EXPERTS)),
            experts_per_token=4, expert_width=32, compute_dtype="float32")


def mesh_of(**axes):
    return build_mesh(MeshConfig(**axes), jax.devices()[:MEMBERS])


def tiny_mellum2(**more):
    """Mellum2's two kinds of layer at tiny widths: a window layer and a
    full one, two query heads on one key/value head of 128."""
    full = M.Rotary(5e5, (64,), (16.0, 64.0, 32.0, 1.0), 1.2772588722239782)
    layers = [M.LayerKind("window", 2, M.Rotary(5e5, (64,))),
              M.LayerKind("full", 2, full)]
    return M.mellum2_lm(**{**TINY, "layers": layers, "window": 48, **more})


# -- (a) a layer under ep = 4 is the uncut layer ------------------------------


def _layer():
    cfg = tiny_mellum2().config
    return M.DecoderLayer(cfg, cfg.layers[0])


def _uneven_rows():
    """Rows ``[4, T, d]`` whose tokens load the members unevenly, and a
    router under which expert 5 gets no row."""
    layer = _layer()
    x = jax.random.normal(jax.random.key(0), (MEMBERS, T, 64))
    x = x.at[1].set(x[0] * 1.5).at[2, : T // 2].set(x[3, : T // 2])
    table = M.rotary_table(jnp.broadcast_to(jnp.arange(T), (3, MEMBERS, T)),
                           layer.kind.rotary, 128)
    params = layer.init(jax.random.key(1), x, table, None)["params"]
    # expert 5 scores 0 and, of four pairs of opposite columns, one of
    # each pair scores above it: four experts always come before it
    router = (params["moe"]["router"] * 8.0).at[:, :4].add(0.3)
    router = router.at[:, 5].set(0.0).at[:, 7:14:2].set(-router[:, 6:13:2])
    params["moe"]["router"] = router
    return layer, params, x, table


def test_a_layer_under_ep4_is_the_uncut_layer_forward_and_every_gradient():
    layer, params, x, table = _uneven_rows()
    weights = jnp.cos(jnp.arange(x.size, dtype=jnp.float32)).reshape(x.shape)

    def loss(p, x, w, table):
        out, sown = layer.apply({"params": p}, x, table, None,
                                mutable=["moe_metrics"])
        return jnp.sum(out * w), (out, sown["moe_metrics"])

    (_, (out, sown)), (grads, dx) = jax.value_and_grad(
        loss, argnums=(0, 1), has_aux=True)(params, x, weights, table)
    rows = np.asarray(sown["moe"]["expert_rows"][0])
    assert rows[5] == 0 and rows.max() > 1.3 * rows.mean()

    specs = cut_specs(params, decoder_ep_axes)
    assert specs["moe"]["w_up"] == P("ep") and specs["moe"]["router"] == P()

    def member(p, x, w, table):
        (_, (out, sown)), (g, dx) = jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True)(p, x, w, table)
        g = jax.tree_util.tree_map_with_path(
            lambda path, a: a if decoder_ep_axes(path)
            else jax.lax.psum(a, "ep"), g)
        return out, g, dx, jax.tree.map(lambda a: jax.lax.psum(a, "ep"), sown)

    rows_spec = P("ep")
    cut = jax.jit(jax.shard_map(
        member, mesh=mesh_of(dp=1, ep=MEMBERS),
        in_specs=(specs, rows_spec, rows_spec, rows_spec),
        out_specs=(rows_spec, specs, rows_spec, P()), check_vma=False))
    out4, grads4, dx4, sown4 = cut(params, x, weights, table)

    close = lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-5,
                                                    atol=2e-6)
    close(out4, out)
    close(dx4, dx)
    jax.tree.map(close, grads4, grads)
    moe, moe4 = sown["moe"], sown4["moe"]
    np.testing.assert_array_equal(moe4["expert_rows"][0], rows)
    assert float(moe4["routed"][0]) == float(moe["routed"][0]) == rows.sum()
    assert float(moe4["dropped"][0]) == 0.0
    # three of four members' rows reach each member, in and back
    assert float(moe4["exchange_rows"][0]) == MEMBERS * (MEMBERS - 1) * T
    assert float(moe4["exchange_bytes"][0]) == (
        MEMBERS * (MEMBERS - 1) * T * 64 * (4 + 4))


# -- (b) three steps of train_distributed at ep = 4 and at ep = 1 -------------


def _fit(mesh, module=None, iters=3, **kwargs):
    rng = np.random.default_rng(0)
    ids = rng.integers(0, VOCAB, (8, T + 1))
    spec = ModelSpec(module=module or tiny_mellum2(), loss="cross_entropy",
                     optimizer="adam", optimizer_params={"lr": 1e-3},
                     input_shape=(T,))
    records = []
    result = sync.train_distributed(
        spec, ids[:, :-1].astype(np.float32),
        labels=ids[:, 1:].astype(np.float32), mesh=mesh, iters=iters,
        mini_batch=1, seed=3, metrics_hook=records.append, **kwargs)
    return result, records


@pytest.fixture(scope="module")
def fits():
    """The same model, rows and seed on the same four devices: every
    device holding every expert (dp = 4), and the experts cut (ep = 4)."""
    return _fit(mesh_of(dp=MEMBERS)), _fit(mesh_of(dp=1, ep=MEMBERS))


def test_three_steps_at_ep4_are_the_steps_of_a_holder_of_every_expert(fits):
    (whole, whole_records), (cut, cut_records) = fits
    assert len(cut_records) == 3
    for a, b in zip(whole_records, cut_records):
        for key in ("loss", "grad_norm", "examples", "moe_rows",
                    "moe_rows_max", "moe_pairs_dropped"):
            np.testing.assert_allclose(b[key], a[key], rtol=1e-5, err_msg=key)
        np.testing.assert_allclose(b["leaf_grad_norms"], a["leaf_grad_norms"],
                                   rtol=2e-4, atol=1e-7)
    assert (cut_records[0]["leaf_grad_norm_keys"]
            == whole_records[0]["leaf_grad_norm_keys"])
    assert cut_records[0]["moe_exchange_rows"] == 2 * 4 * 3 * T
    # Adam's first steps are lr * sign(g): a leaf's difference is rounding
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, atol=2e-5),
                 cut.params, whole.params)
    assert cut.params["layer_0"]["moe"]["w_gate"].shape == (EXPERTS, 64, 32)
    assert not np.allclose(cut.params["layer_1"]["moe"]["w_down"][-1],
                           tiny_mellum2().init(
                               jax.random.key(3), jnp.zeros((1, T)))["params"][
                               "layer_1"]["moe"]["w_down"][-1])


def test_the_fused_builder_with_validation_places_the_carry_by_leaf():
    whole, a = _fit(mesh_of(dp=MEMBERS), iters=4, steps_per_call=2,
                    validation_pct=0.25, early_stop_patience=3)
    cut, b = _fit(mesh_of(dp=1, ep=MEMBERS), iters=4, steps_per_call=2,
                  validation_pct=0.25, early_stop_patience=3)
    assert len(a) == len(b) == 4
    for ra, rb in zip(a, b):
        np.testing.assert_allclose(rb["loss"], ra["loss"], rtol=1e-5)
        np.testing.assert_allclose(rb["val_loss"], ra["val_loss"], rtol=1e-5)


# -- (c) what the lowered step holds ------------------------------------------


def test_the_lowered_step_exchanges_over_ep_and_reduces_no_expert_leaf():
    import optax

    module, tx = tiny_mellum2(), optax.adam(1e-3)
    mesh = mesh_of(dp=1, ep=MEMBERS)

    def init():
        params = module.init(jax.random.key(0), jnp.zeros((1, T)))["params"]
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          model_state={}, opt_state=tx.init(params),
                          rng=jax.random.key(1))

    S = jax.ShapeDtypeStruct
    batch = DataBatch(S((8, T), jnp.float32), S((8, T), jnp.float32),
                      S((8,), jnp.float32))
    step = make_train_step(module.apply, resolve_loss("cross_entropy"), tx,
                           mesh, mini_batch=1)
    text = step.lower(jax.eval_shape(init), batch).as_text()
    # the body's view: a member's block of an expert leaf
    block = f"tensor<{EXPERTS // MEMBERS}x64x32xf32>"
    assert text.count(block) > 10
    # (replica groups, operand types) of every ``op``: the types follow
    # the attributes and the reduction's region, if any
    found = lambda op: re.findall(
        rf'"stablehlo\.{op}"\(%[\w#]+\) <\{{[^\n]*?replica_groups = '
        rf'dense<(\[\[.*?\]\])>[^\n]*?\}}>(?: \(\{{.*?\}}\))? '
        rf': \((.*?)\) ->', text, re.S)
    over_ep, alone = "[[0, 1, 2, 3]]", "[[0], [1], [2], [3]]"
    gathers, scatters = found("all_gather"), found("reduce_scatter")
    # forward: rows, gates and chosen ids in, sums back; backward: their
    # transposes; a layer, two layers
    assert len(gathers) >= 2 * 4 and len(scatters) >= 2 * 3, (
        len(gathers), len(scatters))
    assert {groups for groups, _ in gathers + scatters} == {over_ep}
    reduces = found("all_reduce")
    assert len(reduces) == text.count('"stablehlo.all_reduce"')
    assert {groups for groups, _ in reduces} == {over_ep, alone}
    # an expert leaf's gradient is summed over the batch axes alone, of
    # one member here; every other leaf's over ep
    assert {groups for groups, types in reduces if block in types} == {alone}
    assert {groups for groups, types in reduces
            if "tensor<64x2x128xf32>" in types} == {over_ep}


# -- what cannot take a cut carry says so -------------------------------------


def test_a_model_without_expert_leaves_refuses_an_ep_axis():
    with pytest.raises(ValueError, match="no leaf that lies on it"):
        _fit(mesh_of(dp=1, ep=MEMBERS), module=CausalLM(tiny_transformer(
            vocab_size=VOCAB, max_len=T)))


@pytest.mark.parametrize("entry", ["multihost", "streaming", "checkpoint"])
def test_what_cannot_take_a_cut_carry_refuses_it(entry, tmp_path):
    mesh = mesh_of(dp=1, ep=MEMBERS)
    x = np.zeros((8, T), np.float32)
    spec = ModelSpec(module=tiny_mellum2(), loss="cross_entropy",
                     optimizer="adam", input_shape=(T,))
    with pytest.raises(NotImplementedError, match="cut over an ep axis"):
        if entry == "multihost":
            sync.train_distributed_multihost(spec, x, x, mesh=mesh)
        elif entry == "streaming":
            sync.train_distributed_streaming(spec, x, x, mesh=mesh)
        else:
            sync.train_distributed(spec, x, labels=x, mesh=mesh, iters=1,
                                   checkpoint_dir=str(tmp_path))


# -- (d) Mellum2's model against its plain reference --------------------------

REF_T, REF_WINDOW = 384, 160   # three tiles of 128; a window over a tile


def _mellum2_pair():
    """The reference's configuration (the source's keys) and the
    program's module for the same tiny model."""
    from chipbench import harness

    published = json.loads(
        (REPO / "chipbench/configs/mellum2-12b-a2.5b-ep4.json").read_text())
    rope = published["rope_parameters"]
    cfg = dict(published, hidden_size=64, num_hidden_layers=4,
               num_attention_heads=4, num_key_value_heads=2, vocab_size=VOCAB,
               num_experts=EXPERTS, num_experts_per_tok=4,
               moe_intermediate_size=32, sliding_window=REF_WINDOW,
               rope_parameters={**rope, "full_attention": {
                   **rope["full_attention"],
                   "original_max_position_embeddings": 64}})
    full = M.Rotary(5e5, (64,), (16.0, 64.0, 32.0, 1.0), 1.2772588722239782)
    module = M.mellum2_lm(**{**TINY, "n_layers": 4, "n_kv_heads": 2,
                             "window": REF_WINDOW, "layers": [
        M.LayerKind("full", 4, full) if (i + 1) % 4 == 0
        else M.LayerKind("window", 4, M.Rotary(5e5, (64,)))
        for i in range(4)]})
    return harness.load_module("reference", "mellum2-12b-a2.5b-ep4"), cfg, \
        module


@pytest.fixture(scope="module")
def parity():
    reference, cfg, module = _mellum2_pair()
    variables = reference.init(jax.random.key(0), cfg)
    k1, k2 = jax.random.split(jax.random.key(1))
    ids = jax.random.randint(k1, (2, REF_T), 0, VOCAB)
    labels = jax.random.randint(k2, (2, REF_T), 0, VOCAB)
    loss = resolve_loss("cross_entropy")

    def prog_loss(p):
        logits = module.apply({"params": p}, ids.astype(jnp.float32))
        return jnp.sum(loss(logits, labels)), logits

    ref_loss = lambda p, **more: reference.loss_sum(
        {"params": p}, ids, labels, jnp.ones(2), {**cfg, **more})
    (p_loss, p_logits), p_grads = jax.value_and_grad(
        prog_loss, has_aux=True)(variables["params"])
    r_loss, r_grads = jax.value_and_grad(ref_loss)(variables["params"])
    return dict(reference=reference, cfg=cfg, module=module, ids=ids,
                variables=variables, ref_loss=ref_loss, p_loss=p_loss,
                p_logits=p_logits, p_grads=p_grads, r_loss=r_loss,
                r_grads=r_grads)


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def test_mellum2_is_its_reference_at_a_small_size(parity):
    """Window and full rules, the YaRN table on the whole head, softmax
    over all experts before a renormalised top-k: logits, loss and every
    gradient leaf, float32 in another order."""
    z = parity
    shapes = jax.eval_shape(lambda: z["module"].init(
        jax.random.key(0), z["ids"]))["params"]
    assert jax.tree.map(lambda a: a.shape, shapes) == jax.tree.map(
        lambda a: a.shape, z["variables"]["params"])
    assert _rel(z["p_logits"], z["reference"].forward(
        z["variables"], z["ids"], z["cfg"])) < 1e-5
    assert abs(float(z["p_loss"] - z["r_loss"])) < 1e-5 * float(z["r_loss"])
    errs = jax.tree.map(_rel, z["p_grads"], z["r_grads"])
    assert max(jax.tree.leaves(errs)) < 2e-5, errs


@pytest.mark.parametrize("fault", [
    "window_ignored", "window_1025", "rope_swapped", "no_yarn", "no_renorm",
    "own_rows_only", "experts_psummed"])
def test_a_planted_fault_moves_the_reference(parity, fault):
    z = parity
    loss, grads = jax.value_and_grad(
        lambda p: z["ref_loss"](p, fault=fault))(z["variables"]["params"])
    experts = lambda g: jnp.linalg.norm(g["layer_1"]["moe"]["w_up"])
    if fault == "experts_psummed":
        assert abs(float(loss - z["r_loss"])) < 1e-6 * float(z["r_loss"])
        np.testing.assert_allclose(experts(grads), 4 * experts(z["r_grads"]),
                                   rtol=1e-5)
        np.testing.assert_allclose(
            grads["layer_1"]["attn"]["wq"], z["r_grads"]["layer_1"]["attn"][
                "wq"], rtol=1e-4, atol=1e-9)
    else:
        moved = max(jax.tree.leaves(jax.tree.map(_rel, grads, z["r_grads"])))
        assert moved > 1e-3, moved


def test_mellum2_at_published_widths_is_595m_parameters_a_chip_of_four():
    module = M.mellum2_lm(vocab_size=24_576, n_layers=4)
    cfg = module.config
    assert (cfg.d_model, cfg.n_kv_heads, cfg.head_dim, cfg.window) == (
        2_304, 4, 128, 1_024)
    assert [k.attention for k in cfg.layers] == ["window"] * 3 + ["full"]
    assert {k.n_heads for k in cfg.layers} == {32}
    assert (cfg.n_routed_experts, cfg.experts_per_token, cfg.expert_width,
            cfg.scoring, cfg.shared_expert_width, cfg.dense_width) == (
        64, 8, 896, "softmax", 0, 0)
    shapes = jax.eval_shape(lambda: module.init(
        jax.random.key(0), jnp.zeros((1, 128))))["params"]
    count = lambda held: sum(
        int(np.prod(a.shape)) // (held if decoder_ep_axes(path) else 1)
        for path, a in jax.tree_util.tree_flatten_with_path(shapes)[0])
    assert count(1) == 1_784_239_360
    assert count(MEMBERS) == 595_154_176
    assert len(M.mellum2_lm().config.layers) == 28

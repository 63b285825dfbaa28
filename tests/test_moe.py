"""Mixture-of-experts layer + expert parallelism over the ep axis.

No reference counterpart (SURVEY §2.4: EP "absent") — this is the
framework making the fifth mesh axis real: expert weights shard over
``ep``, GSPMD derives the dispatch/combine all-to-alls from the einsum
operand shardings.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from sparktorch_tpu.models import CausalLM, tiny_transformer
from sparktorch_tpu.models.transformer import SequenceClassifier
from sparktorch_tpu.parallel.compat import set_mesh
from sparktorch_tpu.parallel.mesh import MeshConfig, build_mesh
from sparktorch_tpu.train.sharded import (
    create_sharded_state,
    make_sharded_train_step,
    shard_batch,
)
from sparktorch_tpu.utils.data import DataBatch
from sparktorch_tpu.utils.serde import ModelSpec


def _moe_cfg(**over):
    base = dict(vocab_size=128, d_model=32, n_heads=2, n_layers=2,
                d_ff=64, max_len=32, n_experts=4, moe_every=2)
    base.update(over)
    return tiny_transformer(**base)


def _lm_batch(cfg, b=8, seq=16, seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, cfg.vocab_size, (b, seq + 1)).astype(np.int32)
    return DataBatch(x=jnp.asarray(ids[:, :-1]), y=jnp.asarray(ids[:, 1:]),
                     w=jnp.ones((b,), jnp.float32))


def _run_steps(mesh_cfg, n_steps=8, seed=0, seq_sharded=False, **cfg_over):
    cfg = _moe_cfg(**cfg_over)
    mesh = build_mesh(mesh_cfg)
    spec = ModelSpec(module=CausalLM(cfg), loss="cross_entropy",
                     optimizer="adamw", optimizer_params={"lr": 1e-2})
    batch = _lm_batch(cfg, seed=seed)
    tx = spec.make_optimizer()
    state, shardings = create_sharded_state(
        spec, mesh, jax.random.key(0), sample_x=np.asarray(batch.x[:1]), tx=tx
    )
    step = make_sharded_train_step(
        spec.make_module().apply, spec.loss_fn(), tx, mesh, shardings,
        seq_sharded=seq_sharded,
    )
    batch = shard_batch(batch, mesh, seq_sharded=seq_sharded)
    losses = []
    for _ in range(n_steps):
        state, metrics = step(state, batch)
        losses.append(float(metrics.loss))
    return losses


def test_moe_trains_and_loss_decreases():
    losses = _run_steps(MeshConfig(), n_steps=12)
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0], losses


def test_moe_ep_parity():
    # The SAME training run on an ep=1 vs ep=2 mesh must agree: expert
    # parallelism is a layout choice, not a math choice. rtol 1e-5 is
    # deliberately tight — the explicit dispatch/combine all-to-alls
    # are a PERMUTATION of the global capacity blocks (numerics-proof
    # by construction), the group partition is mesh-anchored so both
    # worlds route identically, and layout-invariant init
    # (threefry_partitionable, see create_sharded_state) starts both
    # from the same parameters; the only residual is f32 reduction
    # ordering in the cross-device grad sums.
    l1 = _run_steps(MeshConfig(ep=1), n_steps=6)
    l2 = _run_steps(MeshConfig(ep=2), n_steps=6)
    np.testing.assert_allclose(l1, l2, rtol=1e-5)


def test_moe_aux_loss_joins_objective():
    # With a large aux weight the optimized loss must visibly exceed
    # the task loss; with weight 0 they coincide.
    def total_loss(weight):
        cfg = _moe_cfg(moe_aux_weight=weight)
        mesh = build_mesh(MeshConfig())
        spec = ModelSpec(module=CausalLM(cfg), loss="cross_entropy",
                         optimizer="sgd", optimizer_params={"lr": 0.0})
        batch = _lm_batch(cfg)
        tx = spec.make_optimizer()
        state, shardings = create_sharded_state(
            spec, mesh, jax.random.key(0),
            sample_x=np.asarray(batch.x[:1]), tx=tx,
        )
        step = make_sharded_train_step(
            spec.make_module().apply, spec.loss_fn(), tx, mesh, shardings
        )
        state, metrics = step(state, shard_batch(batch, mesh))
        return float(metrics.loss)

    base = total_loss(0.0)
    heavy = total_loss(10.0)
    # Switch aux loss is ~1 at balance, so weight 10 adds ~10.
    assert heavy > base + 1.0, (base, heavy)


def test_moe_classifier_forward():
    # MoE composes with the classifier head and plain init/apply.
    cfg = _moe_cfg()
    module = SequenceClassifier(cfg)
    ids = np.zeros((2, 16), np.int32)
    variables = module.init(jax.random.key(0), ids)
    # init runs with all collections mutable, so the sown aux loss
    # lands in 'losses' — the trainers are responsible for dropping it
    # from carried state (step._split_variables).
    assert "losses" in variables
    from sparktorch_tpu.train.step import _split_variables

    _, mstate = _split_variables(variables)
    assert "losses" not in mstate
    out = module.apply(variables, ids)
    assert out.shape == (2, cfg.n_classes)


def test_moe_top2_trains_and_ep_parity():
    """Top-2 routing (gate-weighted combine, choice-level capacity
    priority) converges AND stays exact under expert parallelism —
    the explicit a2a dispatch keeps ep=2 a pure layout choice even at
    k=2 (choice-priority capacity assignment is per-group, and every
    group routes on exactly one device)."""
    # float32 compute: at bf16 the two layouts are BITWISE equal for
    # five steps on this jax, then one reduction-order ulp flips a
    # bf16 rounding and adamw amplifies it past 1e-5 (3e-4 by step 8)
    # — chaos, not layout. f32 holds 2e-7 over ten steps.
    l1 = _run_steps(MeshConfig(ep=1), n_steps=8, moe_top_k=2,
                    dtype="float32")
    assert all(np.isfinite(l1))
    assert l1[-1] < l1[0], l1
    l2 = _run_steps(MeshConfig(ep=2), n_steps=8, moe_top_k=2,
                    dtype="float32")
    np.testing.assert_allclose(l1, l2, rtol=1e-5)


def test_moe_drop_fraction_in_metrics():
    """The token-drop fraction reaches the step metrics: with a
    starving capacity_factor most token-choices must drop; with a huge
    one, none may."""
    def drop_at(cf):
        cfg = _moe_cfg(capacity_factor=cf, moe_top_k=2)
        mesh = build_mesh(MeshConfig())
        spec = ModelSpec(module=CausalLM(cfg), loss="cross_entropy",
                         optimizer="sgd", optimizer_params={"lr": 1e-3})
        batch = _lm_batch(cfg)
        tx = spec.make_optimizer()
        state, shardings = create_sharded_state(
            spec, mesh, jax.random.key(0), sample_x=np.asarray(batch.x[:1]),
            tx=tx,
        )
        step = make_sharded_train_step(
            spec.make_module().apply, spec.loss_fn(), tx, mesh, shardings
        )
        _, metrics = step(state, shard_batch(batch, mesh))
        assert metrics.drop_fraction is not None
        return float(metrics.drop_fraction)

    assert drop_at(0.05) > 0.3
    assert drop_at(8.0) == 0.0


def test_moe_padding_rows_masked_from_routing():
    """Weight-0 padding rows (the empty-partition protocol) must not
    claim expert capacity or move the aux loss: a batch with 4 real +
    4 padding rows must produce the SAME loss as the 4 real rows alone
    (at lr=0, forward-only). Without masking, padding tokens would
    steal capacity slots and shift the weighted loss."""
    from sparktorch_tpu.train.sync import train_distributed

    cfg = _moe_cfg(capacity_factor=0.5)  # tight: stealing would show
    spec = ModelSpec(module=CausalLM(cfg), loss="cross_entropy",
                     optimizer="sgd", optimizer_params={"lr": 0.0})
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (8, 17)).astype(np.int32)
    x, y = ids[:, :-1], ids[:, 1:]

    from sparktorch_tpu.utils.data import DataBatch as DB
    padded = DB(
        x=jnp.asarray(x), y=jnp.asarray(y),
        w=jnp.asarray([1, 1, 1, 1, 0, 0, 0, 0], jnp.float32),
    )
    real4 = DB(x=jnp.asarray(np.tile(x[:4], (2, 1))),
               y=jnp.asarray(np.tile(y[:4], (2, 1))),
               w=jnp.asarray([1, 1, 1, 1, 0, 0, 0, 0], jnp.float32))

    r_pad = train_distributed(spec, padded, iters=1, seed=0)
    r_real = train_distributed(spec, real4, iters=1, seed=0)
    # Same 4 real rows -> same weighted loss, regardless of the junk
    # occupying the padding slots (they were masked out of routing).
    np.testing.assert_allclose(
        r_pad.metrics[0]["loss"], r_real.metrics[0]["loss"], rtol=1e-5
    )
    assert "moe_drop_fraction" in r_pad.metrics[0]


def _compiled_ep2_hlo(**cfg_over):
    cfg = _moe_cfg(**cfg_over)
    mesh = build_mesh(MeshConfig(ep=2))
    spec = ModelSpec(module=CausalLM(cfg), loss="cross_entropy",
                     optimizer="adamw", optimizer_params={"lr": 1e-2})
    batch = _lm_batch(cfg)
    tx = spec.make_optimizer()
    state, shardings = create_sharded_state(
        spec, mesh, jax.random.key(0), sample_x=np.asarray(batch.x[:1]),
        tx=tx,
    )
    step = make_sharded_train_step(
        spec.make_module().apply, spec.loss_fn(), tx, mesh, shardings
    )
    batch = shard_batch(batch, mesh)
    with set_mesh(mesh):
        return step.jitted.lower(state, batch).compile().as_text()


def test_moe_gspmd_ep_lowers_to_all_to_all():
    """The explicit shard_map dispatch (transformer.py MoEFFN /
    _ep_relayout) must land REAL dispatch/combine all-to-alls in the
    compiled ep=2 train step — the GShard scaling property, not token
    replication. Asserted on the compiled HLO of
    the actual train step."""
    hlo = _compiled_ep2_hlo(moe_group_size=16)
    assert "all-to-all" in hlo, "no all-to-all in the ep=2 MoE step HLO"


def test_moe_ep2_hlo_no_token_all_gather():
    """HLO-lowering regression pin: the compiled ep=2 MoE step must
    contain the dispatch/combine all-to-alls and NO all-gather — the
    signature of jax 0.4.x GSPMD's degraded lowering of the
    constraint-derived dispatch (all-gather + all-reduce = every token
    replicated ep-fold). A future jax bump that re-degrades the
    explicit shard_map lowering fails HERE, not as a silent comm/loss
    regression. (The dp4xep2 mesh has no fsdp axis, so NOTHING in this
    program should all-gather; the a2a count covers the MoE layer's
    dispatch + combine in both the forward and the backward.)"""
    from sparktorch_tpu.obs.xprof import hlo_collective_bytes

    hlo = _compiled_ep2_hlo(moe_group_size=16)
    stats = hlo_collective_bytes(hlo)
    assert stats["counts"].get("all_to_all", 0) >= 4, stats
    assert stats["counts"].get("all_gather", 0) == 0, (
        "token all-gather resurfaced in the ep=2 MoE step HLO — the "
        f"partitioner is replicating tokens again: {stats}"
    )
    assert stats["bytes"]["all_to_all"] > 0, stats


def test_moe_a2a_moves_fewer_bytes_than_replicate_and_grounds_tuner():
    """On one dp4 x ep2 mesh the compiled a2a step moves strictly
    fewer collective bytes than the token-replication layout (the
    ``auto`` fallback), and the tuner's ``ep_all_to_all`` term stays
    within 4x of what the compiled a2a program ships: the model prices
    the FORWARD dispatch + combine pair fleet-wide, the HLO is per
    device and holds the backward pair too."""
    from sparktorch_tpu.obs.xprof import hlo_collective_bytes
    from sparktorch_tpu.parallel.tune import (
        predict_comm_bytes,
        transformer_workload,
    )

    rep = hlo_collective_bytes(_compiled_ep2_hlo(
        moe_group_size=16, moe_ep_dispatch="replicate"))
    a2a = hlo_collective_bytes(_compiled_ep2_hlo(
        moe_group_size=16, moe_ep_dispatch="a2a"))
    assert 0 < a2a["total_bytes"] < rep["total_bytes"], (a2a, rep)

    n_dev = jax.device_count()
    shape = transformer_workload(_moe_cfg(moe_group_size=16),
                                 global_batch=8)
    predicted = predict_comm_bytes(MeshConfig(ep=2), shape, n_dev)
    shipped_fwd = a2a["bytes"]["all_to_all"] * n_dev / 2
    assert 0.25 <= predicted["ep_all_to_all"] / shipped_fwd <= 4.0, (
        predicted["ep_all_to_all"], shipped_fwd)


def test_moe_drop_accounting_exact_across_ep():
    """Capacity-overflow drop accounting must be EXACT under expert
    parallelism: at a starving capacity factor, the global (dropped,
    routed) counts an ep=2 run reports must equal the ep=1 run's
    bitwise (both integer-valued f32 sums over identical per-group
    routing — the mesh-anchored partition routes the same groups on
    both worlds), and routed == n_tokens * k exactly (all weights 1),
    so the reported fraction times n*k must be a whole number of
    dropped choices."""
    def drop_fraction_at(mesh_cfg):
        cfg = _moe_cfg(capacity_factor=0.25, moe_top_k=2)
        mesh = build_mesh(mesh_cfg)
        spec = ModelSpec(module=CausalLM(cfg), loss="cross_entropy",
                         optimizer="sgd", optimizer_params={"lr": 0.0})
        batch = _lm_batch(cfg)
        tx = spec.make_optimizer()
        state, shardings = create_sharded_state(
            spec, mesh, jax.random.key(0), sample_x=np.asarray(batch.x[:1]),
            tx=tx,
        )
        step = make_sharded_train_step(
            spec.make_module().apply, spec.loss_fn(), tx, mesh, shardings
        )
        _, metrics = step(state, shard_batch(batch, mesh))
        return float(metrics.drop_fraction)

    f1 = drop_fraction_at(MeshConfig(ep=1))
    f2 = drop_fraction_at(MeshConfig(ep=2))
    assert f1 == f2, (f1, f2)  # bitwise: same routing, exact counts
    n_choices = 8 * 16 * 2  # b * s * top_k, every token weight 1
    dropped = f1 * n_choices
    assert abs(dropped - round(dropped)) < 1e-6, (f1, dropped)
    assert 0.0 < f1 < 1.0, f1


def test_moe_seed_determinism_across_ep_worlds():
    """Same seed -> bitwise-identical loss trajectories, per ep world
    (rerunning ep=2 must reproduce itself exactly — the a2a dispatch
    introduces no nondeterminism), and across worlds the seed yields
    the same parity the rtol gates pin."""
    a = _run_steps(MeshConfig(ep=2), n_steps=4, seed=3)
    b = _run_steps(MeshConfig(ep=2), n_steps=4, seed=3)
    assert a == b, (a, b)
    c = _run_steps(MeshConfig(ep=1), n_steps=4, seed=3)
    d = _run_steps(MeshConfig(ep=1), n_steps=4, seed=3)
    assert c == d, (c, d)
    np.testing.assert_allclose(a, c, rtol=1e-5)


def test_moe_sp_ep_composition_parity():
    """MoE composes with SEQUENCE parallelism in the GSPMD trainer: a
    dp x sp x ep mesh (ring attention over sp, expert dispatch over
    ep) must reproduce the dp-only dense-attention numbers — routing
    is per-group and GSPMD computes over global arrays, so neither
    the sp sharding nor the ep all-to-alls may change the math."""
    l_ref = _run_steps(MeshConfig(), n_steps=5, moe_group_size=16)
    l_sp = _run_steps(MeshConfig(dp=2, sp=2, ep=2), n_steps=5,
                      seq_sharded=True, attn_impl="ring",
                      moe_group_size=16)
    np.testing.assert_allclose(l_sp, l_ref, rtol=3e-3)
    # And every non-batch axis at once: tp slices heads/FFN columns on
    # top of the sp ring and the ep dispatch.
    l_all = _run_steps(MeshConfig(dp=1, tp=2, sp=2, ep=2), n_steps=5,
                       seq_sharded=True, attn_impl="ring",
                       moe_group_size=16)
    np.testing.assert_allclose(l_all, l_ref, rtol=3e-3)


def test_moe_fsdp_ep_composition_parity():
    # fsdp shards the non-expert params (experts already shard over
    # ep) with XLA inserting the all-gathers; composed with ep it must
    # reproduce the dp-only numbers — the last untested pairing in the
    # GSPMD trainer's MoE composition matrix.
    l_ref = _run_steps(MeshConfig(), n_steps=5)
    l_f = _run_steps(MeshConfig(dp=2, fsdp=2, ep=2), n_steps=5)
    np.testing.assert_allclose(l_f, l_ref, rtol=3e-3)


def test_moe_tp_ep_composition_parity():
    # tp shards the experts' inner d_ff dim on top of ep sharding the
    # expert dim; composed layouts must reproduce the dp-only numbers
    # (layout is never allowed to change the math).
    l_ref = _run_steps(MeshConfig(), n_steps=5)
    l_comp = _run_steps(MeshConfig(dp=2, tp=2, ep=2), n_steps=5)
    np.testing.assert_allclose(l_ref, l_comp, rtol=2e-3)

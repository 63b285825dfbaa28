"""The sync step's gradient all-reduce (``train/step.py``): one ``psum``
of the finished gradient tree, which lowers to one all-reduce a
parameter array, and on TPUs over more than one shard a step compiled
ahead with the options that run those all-reduces under the backward
pass (``_CompiledWithOptions``).

Same work: after three steps the step's parameters, Adam state, loss
and gradient norm equal those of a reference step written here, for
every model kind and through every step builder. (Where the compiled
v5e program runs the all-reduces is read in ``test_chip_compile.py``.)
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from sparktorch_tpu.models import (
    Net,
    ResNet,
    SequenceClassifier,
    tiny_transformer,
)
from sparktorch_tpu.models.resnet import ResNetBlock
from sparktorch_tpu.obs.telemetry import Telemetry
from sparktorch_tpu.parallel.mesh import local_mesh
from sparktorch_tpu.train import step as step_mod
from sparktorch_tpu.train.step import (
    HealthVec,
    StepMetrics,
    TrainState,
    create_train_state,
    init_es_state,
    make_train_epoch,
    make_train_epoch_fused,
    make_train_step,
)
from sparktorch_tpu.train.sync import prepare_sharded_batch, train_distributed
from sparktorch_tpu.utils.data import DataBatch, sample_minibatch
from sparktorch_tpu.utils.serde import ModelSpec, serialize_model

STEPS = 3
# Adam's step is g / (|g| + eps) at first: at the default eps of 1e-8 a
# gradient that is zero but for rounding (the keys' bias) turns one ulp
# into a step of lr. At this eps the comparison below is of the
# all-reduce, not of that amplifier.
ADAM = {"lr": 1e-2, "eps": 1e-3}


def reference_dp_body(apply_fn, loss_fn, tx, axis_names, per_shard_mb,
                      state, batch):
    """The step's arithmetic, restated: value_and_grad of the whole
    model, one psum of the finished tree, the weighted mean, Adam."""
    rng, next_rng = jax.random.split(state.rng)
    sample_key = jax.random.fold_in(rng, step_mod._shard_index(axis_names))
    if per_shard_mb is not None and per_shard_mb < batch.x.shape[0]:
        mb = sample_minibatch(batch, sample_key, per_shard_mb)
    else:
        mb = batch

    def weighted_sums(params):
        preds, new_model_state, sown, sown_metrics = step_mod._forward(
            apply_fn, params, state.model_state, mb.x, train=True,
            example_w=mb.w)
        per = loss_fn(preds, mb.y)
        den = jnp.sum(mb.w)
        num = (jnp.sum(per * mb.w)
               + step_mod._sown_total(sown, per.dtype) * den)
        return num, (den, new_model_state,
                     step_mod._moe_drop_counts(sown_metrics))

    (num, (den, new_model_state, drop_counts)), grads_num = (
        jax.value_and_grad(weighted_sums, has_aux=True)(state.params))
    num_g = jax.lax.psum(num, axis_names)
    den_g = jax.lax.psum(den, axis_names)
    grads_g = jax.lax.psum(grads_num, axis_names)
    safe_den = jnp.maximum(den_g, 1.0)
    grads = jax.tree.map(lambda g: g / safe_den, grads_g)
    loss = num_g / safe_den
    drop_fraction = None
    if drop_counts is not None:
        drop_fraction = (jax.lax.psum(drop_counts[0], axis_names)
                         / jnp.maximum(
                             jax.lax.psum(drop_counts[1], axis_names), 1.0))
    if state.model_state:
        new_model_state = jax.tree.map(
            lambda a: jax.lax.pmean(a, axis_names)
            if jnp.issubdtype(a.dtype, jnp.floating) else a,
            new_model_state)
    updates, new_opt_state = tx.update(grads, state.opt_state, state.params)
    new_params = optax.apply_updates(state.params, updates)
    gnorm = optax.global_norm(grads)
    leaf_norms = jnp.stack(
        [jnp.sqrt(jnp.sum(jnp.square(g))).astype(jnp.float32)
         for g in jax.tree.leaves(grads)])
    health = HealthVec(
        finite=(jnp.isfinite(loss) & jnp.isfinite(gnorm)).astype(jnp.float32),
        update_ratio=optax.global_norm(updates)
        / jnp.maximum(optax.global_norm(new_params), 1e-12),
        leaf_norms=leaf_norms)
    new_state = TrainState(step=state.step + 1, params=new_params,
                           model_state=new_model_state,
                           opt_state=new_opt_state, rng=next_rng)
    return new_state, StepMetrics(loss=loss, examples=den_g, grad_norm=gnorm,
                                  drop_fraction=drop_fraction, health=health)


def _token_rows(n, seq=8, vocab=64, seed=0):
    rng = np.random.default_rng(seed)
    y = rng.integers(0, 2, (n,))
    ids = np.where(y == 1, vocab // 2, 0)[:, None] + rng.integers(
        0, vocab // 2, (n, seq))
    return ids.astype(np.float32), y.astype(np.float32)


def _job(kind, n_rows):
    """(spec, x, y) of one model kind at a size a CPU compiles fast."""
    rng = np.random.default_rng(1)
    if kind == "mlp":
        x = rng.normal(size=(n_rows, 10)).astype(np.float32)
        y = (x.sum(1) > 0).astype(np.float32)
        return ModelSpec(module=Net(), loss="mse", optimizer="adam",
                         optimizer_params=ADAM,
                         input_shape=(10,)), x, y
    if kind == "batch_stats":
        x = rng.normal(size=(n_rows, 8 * 8 * 3)).astype(np.float32)
        y = rng.integers(0, 2, (n_rows,)).astype(np.float32)
        module = ResNet(stage_sizes=[1], block_cls=ResNetBlock, num_classes=2,
                        width=8, compute_dtype=jnp.float32,
                        input_hw=(8, 8, 3))
        return ModelSpec(module=module, loss="cross_entropy",
                         optimizer="adam", optimizer_params=ADAM,
                         input_shape=(8 * 8 * 3,)), x, y
    cfg = dict(vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
               max_len=8, n_classes=2, dtype="float32")
    if kind == "moe":  # tight capacity, so token-choices drop
        cfg.update(n_experts=4, moe_every=2, capacity_factor=0.5)
    x, y = _token_rows(n_rows)
    return ModelSpec(module=SequenceClassifier(tiny_transformer(**cfg)),
                     loss="cross_entropy", optimizer="adam",
                     optimizer_params=ADAM, input_shape=(8,)), x, y


def _run(builder, spec, x, y, mesh):
    """State and per-step metrics after ``STEPS`` steps through one of
    the three builders."""
    tx = spec.make_optimizer()
    module, loss_fn = spec.make_module(), spec.loss_fn()
    state = create_train_state(spec, jax.random.key(0),
                               sample_x=jnp.asarray(x[:1]), tx=tx)
    batch = prepare_sharded_batch(
        DataBatch(x=x, y=y, w=np.ones((x.shape[0],), np.float32)), mesh)
    if builder == "step":
        fn = make_train_step(module.apply, loss_fn, tx, mesh, mini_batch=4)
        rows = []
        for _ in range(STEPS):
            state, m = fn(state, batch)
            rows.append(m)
        metrics = jax.tree.map(lambda *a: jnp.stack(a), *rows)
    elif builder == "epoch":
        fn = make_train_epoch(module.apply, loss_fn, tx, mesh, STEPS,
                              mini_batch=4)
        state, metrics = fn(state, batch)
    else:
        fn = make_train_epoch_fused(module.apply, loss_fn, tx, mesh, STEPS,
                                    mini_batch=4)
        (state, _), metrics = fn((state, init_es_state()), batch)
    return state, metrics


@pytest.mark.parametrize("dp", [4, 8])
@pytest.mark.parametrize("builder", ["step", "epoch", "fused"])
@pytest.mark.parametrize("kind", ["mlp", "transformer", "batch_stats", "moe"])
def test_step_equals_one_psum_reference(kind, builder, dp, monkeypatch):
    spec, x, y = _job(kind, n_rows=8 * dp)
    mesh = local_mesh(dp)
    state, metrics = _run(builder, spec, x, y, mesh)
    with monkeypatch.context() as m:
        m.setattr(step_mod, "_dp_body", reference_dp_body)
        ref_state, ref_metrics = _run(builder, spec, x, y, mesh)

    def same(a, b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    jax.tree.map(same, state.params, ref_state.params)
    jax.tree.map(same, state.opt_state, ref_state.opt_state)
    jax.tree.map(same, state.model_state, ref_state.model_state)
    same(metrics.loss, ref_metrics.loss)
    same(metrics.grad_norm, ref_metrics.grad_norm)
    same(metrics.examples, ref_metrics.examples)
    if kind == "moe":
        assert float(jnp.max(metrics.drop_fraction)) > 0.0
        same(metrics.drop_fraction, ref_metrics.drop_fraction)
    if kind == "batch_stats":
        assert state.model_state


def _lowered_step_text(mesh, spec, x, y):
    tx = spec.make_optimizer()
    state = create_train_state(spec, jax.random.key(0),
                               sample_x=jnp.asarray(x[:1]), tx=tx)
    batch = prepare_sharded_batch(
        DataBatch(x=x, y=y, w=np.ones((x.shape[0],), np.float32)), mesh)
    fn = make_train_step(spec.make_module().apply, spec.loss_fn(), tx, mesh)
    return fn.lower(state, batch).as_text(), state


def test_dp4_lowers_one_allreduce_per_gradient_array():
    """What reaches the compiler over four shards: one all-reduce a
    parameter array, each dependent on its own gradient alone, and the
    loss's numerator and denominator. That is what lets the compiler
    start a late layer's all-reduce while the early layers' backward
    computes (``_TPU_DP_OPTIONS``); where they stand in the lowered
    text says nothing, XLA schedules by data dependence."""
    spec, x, y = _job("transformer", n_rows=32)
    text, state = _lowered_step_text(local_mesh(4), spec, x, y)
    n_arrays = len(jax.tree.leaves(state.params))
    assert n_arrays > 1
    assert text.count("stablehlo.all_reduce") == n_arrays + 2


@pytest.mark.parametrize("dp", [1, 4])
def test_gauges_say_what_the_allreduce_sums(dp):
    spec, x, y = _job("mlp", n_rows=32)
    tele = Telemetry(run_id="t")
    train_distributed(
        serialize_model(Net(), "mse", "adam", dict(ADAM), input_shape=(10,)),
        x, labels=y, iters=2, mesh=local_mesh(dp), telemetry=tele)
    leaves = jax.tree.leaves(create_train_state(
        spec, jax.random.key(0), sample_x=jnp.asarray(x[:1])).params)
    buckets = tele.gauge_value("train.grad_allreduce.buckets")
    nbytes = tele.gauge_value("train.grad_allreduce.bytes")
    if dp == 1:
        assert (buckets, nbytes) == (0.0, 0.0)
    else:
        assert buckets == len(leaves) > 1
        assert nbytes == sum(4 * leaf.size for leaf in leaves)


# -- the step compiled ahead with compiler options ----------------------------

# An option the CPU compiler knows, and one no compiler does.
CPU_OPTION = {"xla_cpu_enable_fast_math": False}
NO_SUCH_OPTION = {"xla_no_such_option_of_any_compiler": True}


def test_options_apply_on_tpus_over_more_than_one_shard_only():
    class Tpu:
        platform = "tpu"

    def tpus(mesh):
        fake = np.empty(mesh.devices.shape, object)
        fake.fill(Tpu())
        return type("M", (), {"shape": mesh.shape, "devices": fake})()

    axes = step_mod.BATCH_AXES
    assert step_mod._dp_compiler_options(local_mesh(4), axes) is None
    assert step_mod._dp_compiler_options(tpus(local_mesh(1)), axes) is None
    assert (step_mod._dp_compiler_options(tpus(local_mesh(4)), axes)
            is step_mod._TPU_DP_OPTIONS)


@pytest.mark.parametrize("how", ["call", "compile", "lower_compile"])
def test_every_way_to_an_executable_carries_the_options(how):
    """``fn(x)``, ``fn.compile(x)`` and ``fn.lower(x).compile()`` all
    hand the options to the compiler: an option it does not know is
    refused on each way, one it knows compiles and runs."""
    x = jnp.arange(4.0)

    def via(options):
        fn = step_mod._CompiledWithOptions(jax.jit(lambda a: a * 2), options)
        if how == "call":
            return fn(x)
        if how == "compile":
            return fn.compile(x)(x)
        return fn.lower(x).compile()(x)

    np.testing.assert_array_equal(via(CPU_OPTION), 2 * np.arange(4.0))
    with pytest.raises(Exception, match="xla_no_such_option_of_any_compiler"):
        via(NO_SUCH_OPTION)


def test_one_executable_per_signature_sharding_and_weak_type_included():
    from jax.sharding import NamedSharding, PartitionSpec as P

    fn = step_mod._CompiledWithOptions(jax.jit(lambda a: a + 1), CPU_OPTION)
    mesh = local_mesh(4)
    x = jnp.arange(8.0)
    rows = jax.device_put(x, NamedSharding(mesh, P(step_mod.BATCH_AXES)))
    rep = jax.device_put(x, NamedSharding(mesh, P()))
    for arg in (rows, rows + 1, rep, rows, jax.ShapeDtypeStruct(
            rows.shape, rows.dtype, sharding=rows.sharding)):
        fn.compile(arg)
    assert fn._cache_size() == 2
    # a committed input of another sharding re-specialises, as under jit
    np.testing.assert_array_equal(fn(rep), np.arange(8.0) + 1)
    np.testing.assert_array_equal(fn(rows), np.arange(8.0) + 1)
    assert fn._cache_size() == 2
    fn.compile(jnp.asarray(1.0))
    fn.compile(jnp.asarray(1.0, jnp.float32))  # not weakly typed
    assert fn._cache_size() == 4


@pytest.mark.parametrize("builder", ["epoch", "fused"])
def test_a_fit_compiles_its_step_once(builder, monkeypatch):
    """Through ``train_distributed`` with the wrapper forced onto the
    CPU mesh: three chunks, one executable (two where the early-stop
    carry enters uncommitted and comes back placed, for which
    ``jax.jit`` too lowers again), the plain run's losses."""
    steps = []
    real = step_mod._jit_step

    def spy(mapped, mesh, axis_names):
        steps.append(real(mapped, mesh, axis_names))
        return steps[-1]

    def fit():
        del steps[:]
        res = train_distributed(
            serialize_model(Net(), "mse", "adam", dict(ADAM),
                            input_shape=(10,)),
            x, labels=y, iters=96, mesh=local_mesh(4), seed=0,
            steps_per_call=32, **extra)
        return [r["loss"] for r in res.metrics]

    _, x, y = _job("mlp", n_rows=32)
    extra = (dict(validation_pct=0.25, early_stop_patience=10**6)
             if builder == "fused" else {})
    monkeypatch.setattr(step_mod, "_jit_step", spy)
    losses = fit()
    monkeypatch.setattr(step_mod, "_dp_compiler_options",
                        lambda mesh, axes: CPU_OPTION)
    forced_losses = fit()
    assert all(isinstance(fn, step_mod._CompiledWithOptions) for fn in steps)
    assert ([fn._cache_size() for fn in steps if fn._cache_size()]
            == [2 if builder == "fused" else 1])
    np.testing.assert_array_equal(forced_losses, losses)

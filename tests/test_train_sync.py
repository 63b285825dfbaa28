"""Synchronous SPMD trainer tests.

Strengthened vs the reference (SURVEY §4): the reference only smoke-
checks that a prediction column appears. Here we assert loss actually
decreases, empty/ragged shards are harmless, and an 8-device run is
step-for-step consistent with expectations.
"""

import jax
import jax.numpy as jnp
import numpy as np

from sparktorch_tpu.models import ClassificationNet, MnistMLP, Net
from sparktorch_tpu.parallel.mesh import local_mesh
from sparktorch_tpu.train.step import create_train_state, make_train_step
from sparktorch_tpu.train.sync import prepare_sharded_batch, train_distributed
from sparktorch_tpu.utils.data import handle_features
from sparktorch_tpu.utils.serde import ModelSpec, serialize_model


def _blob_data(n=400, dim=10, seed=0):
    rng = np.random.default_rng(seed)
    x0 = rng.normal(0.0, 1.0, (n // 2, dim)).astype(np.float32)
    x1 = rng.normal(2.0, 1.0, (n // 2, dim)).astype(np.float32)
    x = np.concatenate([x0, x1])
    y = np.concatenate([np.zeros(n // 2), np.ones(n // 2)]).astype(np.float32)
    perm = rng.permutation(n)
    return x[perm], y[perm]


def test_loss_decreases_8dev():
    x, y = _blob_data()
    payload = serialize_model(
        Net(), "mse", "adam", {"lr": 1e-2}, input_shape=(10,)
    )
    result = train_distributed(payload, x, labels=y, iters=30, seed=0)
    losses = [m["loss"] for m in result.metrics]
    assert losses[-1] < losses[0] * 0.7, losses
    assert result.metrics[0]["examples"] == 400.0


def test_ragged_padding_does_not_skew_loss():
    # 401 rows over 8 shards -> padding rows with weight 0; the global
    # weighted mean must count exactly 401 examples (the analog of the
    # reference's empty-partition protocol, distributed.py:131-133).
    x, y = _blob_data(n=402)
    x, y = x[:401], y[:401]
    payload = serialize_model(Net(), "mse", "sgd", {"lr": 1e-3}, input_shape=(10,))
    result = train_distributed(payload, x, labels=y, iters=2)
    assert result.metrics[0]["examples"] == 401.0


def test_minibatch_mode():
    x, y = _blob_data()
    payload = serialize_model(Net(), "mse", "adam", {"lr": 1e-2}, input_shape=(10,))
    result = train_distributed(payload, x, labels=y, iters=20, mini_batch=16)
    losses = [m["loss"] for m in result.metrics]
    assert losses[-1] < losses[0]
    # mini_batch is PER SHARD (reference per-partition semantics,
    # distributed.py:146-149): 8 shards x 16 = 128 examples per step.
    assert result.metrics[0]["examples"] == 16.0 * 8


def test_validation_split_and_early_stop():
    x, y = _blob_data()
    payload = serialize_model(Net(), "mse", "adam", {"lr": 5e-2}, input_shape=(10,))
    result = train_distributed(
        payload, x, labels=y, iters=200, validation_pct=0.2,
        early_stop_patience=3,
    )
    assert all(m["val_loss"] is not None for m in result.metrics)
    # Early stop must have fired well before 200 iters on this problem.
    assert len(result.metrics) < 200


def test_early_stop_fused_matches_per_step():
    """An EXPLICIT steps_per_call > 1 with early
    stopping must stop within one step of the per-step path — the stop
    decision rides the fused scan (EsState), masking post-stop steps."""
    x, y = _blob_data()
    payload = serialize_model(Net(), "mse", "adam", {"lr": 5e-2}, input_shape=(10,))
    kw = dict(iters=200, validation_pct=0.2, early_stop_patience=3, seed=3)
    r_per_step = train_distributed(payload, x, labels=y, steps_per_call=1, **kw)
    r_fused = train_distributed(payload, x, labels=y, steps_per_call=8, **kw)
    n1, n8 = len(r_per_step.metrics), len(r_fused.metrics)
    assert n1 < 200 and n8 < 200, (n1, n8)
    assert abs(n1 - n8) <= 1, (n1, n8)
    # The fused path must also keep recording the per-step val forward.
    assert all(m["val_loss"] is not None for m in r_fused.metrics)
    # Identical rng stream + math => identical signals; losses agree.
    l1 = [m["loss"] for m in r_per_step.metrics[: min(n1, n8)]]
    l8 = [m["loss"] for m in r_fused.metrics[: min(n1, n8)]]
    np.testing.assert_allclose(l1, l8, rtol=1e-4)


def test_early_stop_fused_no_validation():
    """Early stop on the TRAIN loss inside a fused chunk (no val split):
    lr=0 makes the loss constant, so the stopper's patience must run
    out after exactly patience+1 steps on both paths."""
    x, y = _blob_data(n=64)
    payload = serialize_model(Net(), "mse", "sgd", {"lr": 0.0}, input_shape=(10,))
    kw = dict(iters=32, early_stop_patience=2, seed=0)
    r1 = train_distributed(payload, x, labels=y, steps_per_call=1, **kw)
    r8 = train_distributed(payload, x, labels=y, steps_per_call=8, **kw)
    assert len(r1.metrics) == len(r8.metrics) == 3, (
        len(r1.metrics), len(r8.metrics))


def test_classification_cross_entropy_long_labels():
    # Integer class labels through cross entropy — the reference needed
    # a runtime retry for this (distributed.py:153-158).
    x, y = _blob_data()
    payload = serialize_model(
        ClassificationNet(n_classes=2), "nll", "adam", {"lr": 1e-2},
        input_shape=(10,),
    )
    result = train_distributed(payload, x, labels=y.astype(np.int64), iters=30)
    losses = [m["loss"] for m in result.metrics]
    assert losses[-1] < losses[0]


def test_partition_shuffles():
    x, y = _blob_data()
    payload = serialize_model(Net(), "mse", "adam", {"lr": 1e-2}, input_shape=(10,))
    result = train_distributed(payload, x, labels=y, iters=5, partition_shuffles=3)
    assert len(result.metrics) == 15
    assert {m["round"] for m in result.metrics} == {0, 1, 2}


def test_single_vs_multi_device_parity():
    """Full-batch sync training on 1 device and on 8 devices must agree
    step-for-step (same global weighted-mean gradient) — the assertion
    SURVEY §4 says the reference never makes."""
    x, y = _blob_data(n=64)
    payload = serialize_model(Net(), "mse", "sgd", {"lr": 1e-2}, input_shape=(10,))
    r1 = train_distributed(payload, x, labels=y, iters=5,
                           mesh=local_mesh(1), seed=7)
    r8 = train_distributed(payload, x, labels=y, iters=5,
                           mesh=local_mesh(8), seed=7)
    l1 = [m["loss"] for m in r1.metrics]
    l8 = [m["loss"] for m in r8.metrics]
    np.testing.assert_allclose(l1, l8, rtol=2e-4)
    for a, b in zip(jax.tree.leaves(r1.params), jax.tree.leaves(r8.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-5)


def test_multihost_entry_single_process():
    """train_distributed_multihost in a 1-process world still builds
    the global batch via make_array_from_process_local_data and runs
    the pre-sharded path (the barrier deploy mode's data feeding)."""
    from sparktorch_tpu.train.sync import train_distributed_multihost

    x, y = _blob_data(n=102)
    x, y = x[:101], y[:101]  # ragged: padding to shard divisibility
    payload = serialize_model(Net(), "mse", "adam", {"lr": 1e-2}, input_shape=(10,))
    result = train_distributed_multihost(payload, x, local_y=y, iters=10)
    losses = [m["loss"] for m in result.metrics]
    assert losses[-1] < losses[0]
    assert result.metrics[0]["examples"] == 101.0


def test_minibatch_sorted_labels_converges():
    # Regression: block minibatch sampling must see shuffled resident
    # order even on round 0 — a label-sorted input (common from Spark
    # groupBy ingestion) would otherwise feed single-class blocks.
    import jax

    from sparktorch_tpu.models import MnistMLP
    from sparktorch_tpu.utils.serde import ModelSpec

    rng = np.random.default_rng(0)
    n = 512
    x = rng.normal(0, 1, (n, 784)).astype(np.float32)
    w = rng.normal(0, 0.1, (784, 10))
    y = (x @ w).argmax(1).astype(np.int32)
    order = np.argsort(y)  # fully label-sorted
    spec = ModelSpec(module=MnistMLP(), loss="cross_entropy",
                     optimizer="adam", optimizer_params={"lr": 1e-3},
                     input_shape=(784,))
    result = train_distributed(spec, x[order], labels=y[order],
                               iters=120, mini_batch=64)
    losses = [m["loss"] for m in result.metrics]
    assert losses[-1] < losses[0] * 0.25, (losses[0], losses[-1])


def test_streaming_trainer_matches_ceiling():
    # Larger-than-HBM path: stream host chunks through the device with
    # double buffering; loss must drop and every example must be seen
    # (chunk padding is weight-0).
    from sparktorch_tpu.models import MnistMLP
    from sparktorch_tpu.train.sync import train_distributed_streaming
    from sparktorch_tpu.utils.serde import ModelSpec

    rng = np.random.default_rng(0)
    n = 1000  # deliberately not a multiple of chunk or shards
    x = rng.normal(0, 1, (n, 784)).astype(np.float32)
    w = rng.normal(0, 0.1, (784, 10))
    y = (x @ w).argmax(1).astype(np.int32)
    spec = ModelSpec(module=MnistMLP(), loss="cross_entropy",
                     optimizer="adam", optimizer_params={"lr": 1e-3},
                     input_shape=(784,))
    result = train_distributed_streaming(
        spec, x, labels=y, chunk_rows=512, epochs=8, mini_batch=16,
    )
    losses = [m["loss"] for m in result.metrics]
    assert losses[-1] < losses[0] * 0.5, (losses[0], losses[-1])
    # One pass/epoch over every chunk: 2 chunks x 4 steps x 8 epochs
    # (mini_batch is per shard: 512/8 shards = 64 rows, 4 blocks of 16).
    assert len(losses) == 64
    # Each step sees at most its sampled block (16 rows x 8 shards);
    # pad rows are weight-0 and never counted.
    assert all(m["examples"] <= 16 * 8 + 1e-6 for m in result.metrics)
    assert sum(m["examples"] for m in result.metrics) > 0


def test_es_percentage_mode_parity_signed_best():
    """Percentage-mode min_delta uses SIGNED best (reference
    early_stopper.py:51-56: ``best * min_delta / 100``, no abs): for a
    negative best the better-threshold moves toward zero. The host
    stopper and the fused jax stopper must agree signal-for-signal."""
    from sparktorch_tpu.train.step import EsConfig, _es_update, init_es_state
    from sparktorch_tpu.utils.early_stopper import EarlyStopping

    # Crosses zero and hovers: exercises the signed-delta branch both
    # sides of zero in both modes.
    signals = [-10.0, -10.4, -10.4, -9.0, -9.3, -9.31, 2.0, 2.05, 2.2,
               2.1, 2.1, 2.1]
    for mode in ("min", "max"):
        cfg = EsConfig(mode=mode, min_delta=5.0, patience=2,
                       percentage=True)
        host = EarlyStopping(mode=mode, min_delta=5.0, patience=2,
                             percentage=True)
        es = init_es_state()
        host_stop = None
        fused_stop = None
        for i, s in enumerate(signals):
            hs = host.step(s)
            es = _es_update(cfg, es, jnp.float32(s))
            assert abs(float(es.best) - host.best) < 1e-6, (mode, i)
            if hs and host_stop is None:
                host_stop = i
            if bool(es.stopped) and fused_stop is None:
                fused_stop = i
        assert host_stop == fused_stop, (mode, host_stop, fused_stop)

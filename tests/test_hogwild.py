"""Async (hogwild) mode tests — the reference ships this mode with
ZERO test coverage (SURVEY §4: "hogwild mode is never tested"). Here
both the in-process and the HTTP wire paths are exercised for real.
"""

import numpy as np
import pytest

from sparktorch_tpu import SparkTorch, serialize_torch_obj
from sparktorch_tpu.models import ClassificationNet, Net
from sparktorch_tpu.serve.param_server import ParameterServer, ParamServerHttp
from sparktorch_tpu.train.hogwild import HttpTransport, train_async
from sparktorch_tpu.utils.serde import deserialize_model


def _blob_data(n=400, dim=10, seed=0):
    rng = np.random.default_rng(seed)
    x0 = rng.normal(0.0, 1.0, (n // 2, dim)).astype(np.float32)
    x1 = rng.normal(2.0, 1.0, (n // 2, dim)).astype(np.float32)
    x = np.concatenate([x0, x1])
    y = np.concatenate([np.zeros(n // 2), np.ones(n // 2)]).astype(np.float32)
    perm = rng.permutation(n)
    return x[perm], y[perm]


@pytest.fixture
def payload():
    return serialize_torch_obj(
        Net(), criterion="mse", optimizer="adam",
        optimizer_params={"lr": 5e-3}, input_shape=(10,),
    )


def test_param_server_versioned_pull(payload):
    server = ParameterServer(payload, window_len=2)
    try:
        snap = server.get_parameters(-1)
        assert snap is not None
        v0, params = snap
        # Up-to-date client gets None instead of a redundant transfer
        # (the reference re-ships the full state_dict every iteration,
        # hogwild.py:103).
        assert server.get_parameters(v0) is None
        # A pushed gradient bumps the version.
        import jax

        grads = jax.tree.map(lambda a: np.ones_like(np.asarray(a)), params)
        server.push_gradients(grads)
        server.drain()
        snap2 = server.get_parameters(v0)
        assert snap2 is not None and snap2[0] > v0
        assert server.applied_updates == 1
    finally:
        server.stop()


def test_param_server_error_tolerance(payload):
    # server.py:139-142: tolerate up to 10 bad updates, then fail.
    server = ParameterServer(payload, window_len=2)
    try:
        for _ in range(11):
            server.push_gradients({"not": "a valid grad pytree"})
        server.drain()
        with pytest.raises(RuntimeError):
            server.push_gradients({"still": "bad"})
    finally:
        server.stop()


def test_hogwild_local_loss_decreases(payload):
    x, y = _blob_data()
    result = train_async(payload, x, labels=y, iters=25, partitions=4,
                         mini_batch=32, seed=0)
    # Per-minibatch worker losses are noisy under async staleness, so
    # measure what matters: full-data loss at initial vs final params.
    import jax.numpy as jnp

    spec = deserialize_model(payload)
    module = spec.make_module()
    init_vars = spec.init_params(__import__("jax").random.key(0))

    def full_loss(variables):
        preds = module.apply(variables, jnp.asarray(x))
        return float(jnp.mean((preds[:, 0] - jnp.asarray(y)) ** 2))

    before = full_loss(init_vars)
    after = full_loss({"params": result.params})
    assert after < before * 0.8, (before, after)


def test_hogwild_sorted_input_no_minibatch_trains():
    """Regression (round-5 verify drive): a LABEL-SORTED input with
    full-batch workers used to split contiguously into single-class
    shards — async training then collapsed to whichever class pushed
    last (chance accuracy, race-dependent). train_async now shuffles
    round 0 too, like the reference's unconditional repartition before
    training (torch_distributed.py:288-289)."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    dim = 10
    x = np.concatenate([
        rng.normal(0.0, 1.0, (100, dim)),
        rng.normal(2.0, 1.0, (100, dim)),
    ]).astype(np.float32)             # sorted: class 0 rows, then class 1
    y = np.concatenate([np.zeros(100), np.ones(100)]).astype(np.float32)
    payload = serialize_torch_obj(
        ClassificationNet(n_classes=2), criterion="cross_entropy",
        optimizer="adam", optimizer_params={"lr": 5e-3}, input_shape=(dim,),
    )
    # NO mini_batch: the failing config. 25 iters: a collapsed run
    # stays at chance accuracy however long it trains, while a healthy
    # one needs the extra headroom on this jax/optax build (15 iters
    # lands at ~0.83 here, 25 at ~0.96).
    result = train_async(payload, x, labels=y, iters=25, partitions=2,
                         seed=0)
    spec = deserialize_model(payload)
    module = spec.make_module()
    preds = np.argmax(
        np.asarray(module.apply({"params": result.params}, jnp.asarray(x))),
        axis=1,
    )
    acc = float((preds == y).mean())
    assert acc > 0.9, acc


def test_hogwild_http_wire(payload):
    # Full HTTP path: pull / push / losses / liveness over a real
    # socket (the reference's Flask equivalent, server.py:89-147).
    x, y = _blob_data(n=128)
    result = train_async(payload, x, labels=y, iters=6, partitions=2,
                         transport="http", port=0, seed=0)
    assert len(result.metrics) == 12
    versions = [m["version"] for m in result.metrics]
    assert max(versions) > 0  # weights actually moved over the wire


def test_hogwild_early_stop_window(payload):
    server = ParameterServer(payload, window_len=2, early_stop_patience=1)
    try:
        # Feed a worsening loss sequence; window avg grows -> stop.
        stops = [server.post_loss(v) for v in [1.0, 1.0, 5.0, 5.0, 9.0, 9.0]]
        assert stops[-1] is True
        assert server.should_stop
    finally:
        server.stop()


def test_estimator_hogwild_mode(data):
    # Through the public Estimator surface (mode='hogwild'), which the
    # reference never covers in tests.
    payload = serialize_torch_obj(
        ClassificationNet(n_classes=2), criterion="nll", optimizer="adam",
        optimizer_params={"lr": 1e-2}, input_shape=(10,),
    )
    est = SparkTorch(
        inputCol="features", labelCol="label", predictionCol="predictions",
        torchObj=payload, iters=40, mode="hogwild", partitions=4, miniBatch=64,
    )
    model = est.fit(data)
    res = model.transform(data)
    rows = res.collect()
    acc = np.mean([float(r["predictions"]) == float(r["label"]) for r in rows])
    assert acc > 0.85, acc


def test_http_transport_liveness_and_stop(payload):
    server = ParameterServer(payload, window_len=1, early_stop_patience=1)
    http = ParamServerHttp(server, port=0).start()
    try:
        t = HttpTransport(http.url)
        assert t.alive()
        assert t.post_loss(1.0) is False
        assert t.post_loss(10.0) is True  # worse window -> stop
    finally:
        http.stop()
        server.stop()


def test_hogwild_http_bf16_compressed_push(payload):
    # The HTTP wire ships bf16 gradients by default (half the bytes of
    # the reference's full-precision push); training must still learn.
    x, y = _blob_data()
    result = train_async(payload, x, labels=y, iters=25, partitions=2,
                         mini_batch=32, transport="http", port=0, seed=0)
    import jax
    import jax.numpy as jnp

    spec = deserialize_model(payload)
    module = spec.make_module()
    init_vars = spec.init_params(jax.random.key(0))

    def full_loss(variables):
        preds = module.apply(variables, jnp.asarray(x))
        return float(jnp.mean((preds[:, 0] - jnp.asarray(y)) ** 2))

    assert full_loss({"params": result.params}) < full_loss(init_vars) * 0.8


def test_hogwild_push_every_accumulates(payload, monkeypatch):
    # push_every=k accumulates k minibatch grads on-device and pushes
    # their mean: k-fold fewer server applies, same examples seen.
    from sparktorch_tpu.train import hogwild as hw

    pushes = []
    real_push = hw.LocalTransport.push
    monkeypatch.setattr(
        hw.LocalTransport, "push",
        lambda self, grads: (pushes.append(1), real_push(self, grads))[1],
    )
    x, y = _blob_data()
    result = train_async(payload, x, labels=y, iters=24, partitions=2,
                         mini_batch=32, push_every=4, seed=0)
    # 2 workers x 24 iters / 4 = 12 pushes; worker records still 48.
    assert len(pushes) == 12
    assert len(result.metrics) == 48
    import jax
    import jax.numpy as jnp

    spec = deserialize_model(payload)
    module = spec.make_module()
    init_vars = spec.init_params(jax.random.key(0))

    def full_loss(variables):
        preds = module.apply(variables, jnp.asarray(x))
        return float(jnp.mean((preds[:, 0] - jnp.asarray(y)) ** 2))

    assert full_loss({"params": result.params}) < full_loss(init_vars) * 0.8


def test_hogwild_phase_budget_sums_to_whole(payload):
    """The per-phase budget: every worker's loop
    wall decomposes into pull / placement / dispatch / materialize /
    wire / poll / other, summing to the whole; the http transport also
    counts wire bytes; shuffle rounds don't double-count."""
    x, y = _blob_data()
    phases = ("pull_s", "pull_place_s", "dispatch_s",
              "push_materialize_s", "push_wire_s", "poll_s",
              "drain_s", "other_s")
    for transport, expect_bytes in (("local", False), ("http", True)):
        result = train_async(payload, x, labels=y, iters=8, partitions=2,
                             mini_batch=32, push_every=4, seed=0,
                             partition_shuffles=2, transport=transport)
        summary = result.summary
        assert summary is not None
        budget = summary["hogwild_budget"]
        # 2 workers x 2 shuffle rounds of per-round stats.
        assert len(summary["hogwild_phases"]) == 4
        acct = sum(budget[k] for k in phases)
        assert abs(acct - budget["loop_s"]) < 1e-6 * max(1.0, budget["loop_s"])
        assert budget["loop_s"] > 0
        # 2 workers x 2 rounds x (8/4) windows = 8 pushes total.
        assert budget["pushes"] == 8
        assert summary["server_applied"] == 8
        if expect_bytes:
            assert budget["push_bytes"] > 0
            assert budget["pull_bytes"] > 0

"""The pyspark deployment adapter, exercised against the localspark
runtime (sparktorch_tpu.spark.localsession) — the stand-in for the
reference's "real local Spark session" test tier
(tests/test_sparktorch.py:13-26: local[2] + 2 partitions).

Key property: mapPartitions tasks run in SEPARATE PROCESSES, so the
barrier-mode tests below really form a 2-process jax.distributed
world over the native gang coordinator's TCP rendezvous.
"""

import numpy as np
import pytest

from sparktorch_tpu.spark import localsession

assert localsession.install(), "real pyspark present? these tests target the shim"

from sparktorch_tpu.spark.torch_distributed import SparkTorch, SparkTorchModel  # noqa: E402
from sparktorch_tpu.models import Net, MnistMLP  # noqa: E402
from sparktorch_tpu.utils.serde import serialize_model  # noqa: E402

DenseVector = localsession.DenseVector


@pytest.fixture(scope="module")
def spark():
    s = localsession.SparkSession.builder.master("local[2]").getOrCreate()
    yield s
    s.stop()


@pytest.fixture(scope="module")
def data(spark):
    """The reference's fixture dataset: two 200-row Gaussian blobs as
    (label, DenseVector) rows, 2 partitions."""
    rng = np.random.default_rng(42)
    x0 = rng.normal(0.0, 1.0, (200, 10))
    x1 = rng.normal(2.0, 1.0, (200, 10))
    x = np.concatenate([x0, x1])
    y = np.concatenate([np.zeros(200), np.ones(200)])
    perm = rng.permutation(400)
    rows = [(float(y[i]), DenseVector(x[i])) for i in perm]
    return spark.createDataFrame(rows, ["label", "features"]).repartition(2)


def _estimator(**overrides):
    payload = serialize_model(
        Net(), "mse", "adam", {"lr": 1e-2}, input_shape=(10,)
    )
    kwargs = dict(
        inputCol="features", labelCol="label", predictionCol="predictions",
        torchObj=payload, iters=30, verbose=0,
    )
    kwargs.update(overrides)
    return SparkTorch(**kwargs)


def test_driver_mode_fit_transform(data):
    model = _estimator().fit(data)
    assert isinstance(model, SparkTorchModel)
    res = model.transform(data).collect()
    assert "predictions" in res[0].asDict()
    preds = np.asarray([r["predictions"] for r in res])
    labels = np.asarray([r["label"] for r in res])
    acc = np.mean((preds > 0.5) == (labels > 0.5))
    assert acc > 0.9, acc


def test_vector_out(data):
    payload = serialize_model(
        MnistMLP(hidden=(16,), n_classes=2), "cross_entropy", "adam",
        {"lr": 1e-2}, input_shape=(10,),
    )
    model = _estimator(torchObj=payload, useVectorOut=True).fit(data)
    res = model.transform(data).collect()
    vec = res[0]["predictions"]
    assert len(vec) == 2  # raw logits vector (reference predict_vec)


def test_classifier_argmax_predictions(data):
    payload = serialize_model(
        MnistMLP(hidden=(16,), n_classes=2), "cross_entropy", "adam",
        {"lr": 1e-2}, input_shape=(10,),
    )
    model = _estimator(torchObj=payload, iters=40).fit(data)
    res = model.transform(data).collect()
    preds = np.asarray([r["predictions"] for r in res])
    labels = np.asarray([r["label"] for r in res])
    assert set(np.unique(preds)) <= {0.0, 1.0}
    assert np.mean(preds == labels) > 0.9


def test_string_labels_actionable_error(spark):
    rows = [("a", DenseVector(np.zeros(4))), ("b", DenseVector(np.ones(4)))]
    df = spark.createDataFrame(rows, ["label", "features"])
    est = _estimator(iters=1)
    with pytest.raises(ValueError, match="StringIndexer"):
        est.fit(df)


def test_hogwild_driver_mode(data):
    model = _estimator(mode="hogwild", iters=30, miniBatch=64).fit(data)
    res = model.transform(data).collect()
    preds = np.asarray([r["predictions"] for r in res])
    labels = np.asarray([r["label"] for r in res])
    assert np.mean((preds > 0.5) == (labels > 0.5)) > 0.85


@pytest.mark.slow
def test_hogwild_executor_side_over_http(data):
    """The reference's hogwild topology for real: the driver hosts the
    parameter server, 2 executor PROCESSES run async worker loops over
    the HTTP wire (pull/grad/push, version-tagged pulls —
    hogwild.py:65-142). Asserts final full-data loss drops and that
    workers observed evolving parameter versions (version skew)."""
    est = _estimator(mode="hogwild", deployMode="barrier", partitions=2,
                     iters=25, miniBatch=64)
    model = est.fit(data)
    summaries = est._last_hogwild_summaries
    assert len(summaries) == 2  # one per executor process
    assert summaries[0]["worker"] != summaries[1]["worker"]
    # Version skew: each worker saw the server's parameters advance as
    # the OTHER worker pushed (strictly more versions than its own
    # pushes alone would produce is not guaranteed, but growth is).
    for s in summaries:
        versions = s["versions"]
        assert versions[-1] > versions[0] >= 0
        assert len(set(versions)) > 1
    # Both workers contributed distinct server versions (neither's
    # observation set swallows the other's) — robust to cold-start
    # skew, unlike asserting a literal time overlap.
    v0, v1 = set(summaries[0]["versions"]), set(summaries[1]["versions"])
    assert len(v0 | v1) > max(len(v0), len(v1))
    # Final full-data loss must beat the untrained model's.
    import jax
    import jax.numpy as jnp

    from sparktorch_tpu.utils.serde import deserialize_model

    payload = est.getOrDefault(est.torchObj)
    spec = deserialize_model(payload)
    x = np.stack([r["features"].toArray() for r in data.collect()]).astype(np.float32)
    y = np.asarray([r["label"] for r in data.collect()], np.float32)
    module = spec.make_module()
    loss_fn = spec.loss_fn()

    def full_loss(params, model_state):
        preds = module.apply({"params": params, **model_state}, jnp.asarray(x))
        return float(jnp.mean(loss_fn(preds, jnp.asarray(y))))

    bundle = model.getPytorchModel()
    init_vars = dict(spec.init_params(jax.random.key(0)))
    init_params = init_vars.pop("params")
    assert full_loss(bundle["params"], bundle["model_state"]) < 0.5 * full_loss(
        init_params, init_vars
    )


@pytest.mark.slow
def test_barrier_mode_two_process_world(data):
    """deployMode='barrier': 2 partitions -> 2 executor PROCESSES that
    rendezvous through the native gang coordinator, run
    jax.distributed.initialize, and train one global SPMD step stream
    over a real 2-process CPU mesh."""
    model = _estimator(deployMode="barrier", partitions=2, iters=25).fit(data)
    res = model.transform(data).collect()
    preds = np.asarray([r["predictions"] for r in res])
    labels = np.asarray([r["label"] for r in res])
    acc = np.mean((preds > 0.5) == (labels > 0.5))
    assert acc > 0.9, acc


def _gang_train_lm(spark, cfg, heartbeat_dir=None, **train_kwargs):
    """Shared scaffold for the 2-process barrier LM trainings: build a
    16-row token frame, gang-launch a 2-task barrier stage, bring up
    the 2-process jax.distributed world, train over a dp=8 x pp=2 mesh
    with ``train_distributed_multihost`` (pre-sharded global batch),
    and return rank 0's per-iteration metrics dicts.

    ``heartbeat_dir`` (optional): enable rank/host-attributed gang
    heartbeats (obs.heartbeat) in every executor process, publishing
    into the shared directory the driver can read back."""
    import numpy as _np

    from sparktorch_tpu.models import CausalLM
    from sparktorch_tpu.native.gang import GangCoordinator

    payload = serialize_model(CausalLM(cfg), "cross_entropy", "adam",
                              {"lr": 1e-2}, input_shape=(16,))
    rng = _np.random.default_rng(0)
    ids = rng.integers(0, 64, (16, 17))
    rows = [(float(i), DenseVector(ids[i].astype(float))) for i in range(16)]
    df = spark.createDataFrame(rows, ["idx", "tokens"]).repartition(2)

    coord = GangCoordinator(world_size=2, port=0)
    gang_port = coord.port

    def run_host(iterator):
        import os

        import numpy as np
        from pyspark import BarrierTaskContext

        ctx = BarrierTaskContext.get()
        rank = ctx.partitionId()
        toks = np.stack([
            np.asarray(r[0].toArray(), np.int64) for r in iterator
        ]).astype(np.int32)

        if heartbeat_dir:
            # Enable attributed heartbeats in THIS executor process:
            # GangWorker picks the directory up at construction.
            from sparktorch_tpu.obs import HEARTBEAT_DIR_ENV

            os.environ[HEARTBEAT_DIR_ENV] = heartbeat_dir

        from sparktorch_tpu.parallel.launch import bringup_multihost
        from sparktorch_tpu.train.sync import train_distributed_multihost

        _, worker = bringup_multihost(
            rank=rank, world_size=2, coordinator_host="127.0.0.1",
            gang_port=gang_port, start_coordinator=False,
        )
        try:
            from sparktorch_tpu.parallel.mesh import MeshConfig, build_mesh

            mesh = build_mesh(MeshConfig(pp=2))  # dp=8 x pp=2 over 16
            result = train_distributed_multihost(
                payload, toks[:, :-1], local_y=toks[:, 1:], mesh=mesh,
                **train_kwargs,
            )
            if rank == 0:
                yield result.metrics
        finally:
            if worker is not None:
                worker.close()

    try:
        rdd = df.select("tokens").rdd
        out = rdd.barrier().mapPartitions(run_host).collect()
    finally:
        coord.stop()
    (metrics,) = out
    return metrics


@pytest.mark.slow
def test_barrier_two_process_pp_pre_sharded(spark):
    """pre_sharded under pp>1 (the last Param-contract gap): a
    gang-launched 2-process world assembles the global batch with
    train_distributed_multihost and trains a pipeline-parallel LM —
    the pp route consuming the globally-sharded DataBatch directly
    (pre_sharded=True), dp=8 x pp=2 over the 16-device world."""
    import numpy as _np

    from sparktorch_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                            n_layers=4, d_ff=64, max_len=16,
                            dtype="float32")
    metrics = _gang_train_lm(spark, cfg, iters=4, n_micro=2)
    losses = [m["loss"] for m in metrics]
    assert len(losses) == 4
    assert all(_np.isfinite(losses))
    assert losses[-1] < losses[0], losses


@pytest.mark.slow
def test_barrier_two_process_interleaved_moe(spark):
    """The closed composition matrix survives the MULTI-PROCESS
    world: the same gang-launched 2-process barrier stage trains an
    MoE LM under the interleaved 1F1B schedule (virtual_stages=2) —
    the per-kind stack permutations, aux seeds, and drop metrics all
    riding the multihost route on the pre-sharded global batch."""
    import numpy as _np

    from sparktorch_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                            n_layers=8, d_ff=64, max_len=16,
                            dtype="float32", n_experts=4, moe_every=2,
                            moe_top_k=2, moe_group_size=16)
    metrics = _gang_train_lm(spark, cfg, iters=4, n_micro=2,
                             pipeline_schedule="1f1b", virtual_stages=2)
    losses = [m["loss"] for m in metrics]
    drops = [m.get("moe_drop_fraction") for m in metrics]
    assert len(losses) == 4
    assert all(_np.isfinite(losses))
    assert losses[-1] < losses[0], losses
    assert drops[0] is not None and _np.isfinite(drops[0])


def test_barrier_two_process_gang_heartbeats(spark, tmp_path):
    """Gang heartbeat smoke test (obs.heartbeat) under the 2-process
    barrier scaffold: two executor PROCESSES rendezvous through the
    native gang coordinator with SPARKTORCH_TPU_HEARTBEAT_DIR set,
    publish attributed liveness (rank, host, pid, training step,
    last-seen ts) through the real trainer path
    (register_gang_worker + notify_gang_step), and the driver-side
    ``gang_report`` derives per-rank step skew and reads the clean
    shutdown as alive=False — distinct from a silent death.

    Deliberately no jax.distributed training: this jaxlib's CPU
    backend can't run multiprocess computations (the slow barrier
    trainings above document that), and liveness/skew attribution
    must be testable without it anyway — that's its whole point."""
    from sparktorch_tpu.native.gang import GangCoordinator
    from sparktorch_tpu.obs import gang_report, read_heartbeats

    hb_dir = str(tmp_path / "gang_hb")
    rng = np.random.default_rng(0)
    rows = [(float(i), DenseVector(rng.normal(0, 1, 4))) for i in range(8)]
    df = spark.createDataFrame(rows, ["idx", "features"]).repartition(2)

    coord = GangCoordinator(world_size=2, port=0)
    gang_port = coord.port

    def run_host(iterator):
        import os

        from pyspark import BarrierTaskContext

        from sparktorch_tpu.native.gang import GangWorker
        from sparktorch_tpu.parallel.launch import (
            notify_gang_step,
            register_gang_worker,
        )

        rank = BarrierTaskContext.get().partitionId()
        os.environ["SPARKTORCH_TPU_HEARTBEAT_DIR"] = hb_dir
        worker = GangWorker("127.0.0.1", gang_port, rank,
                            f"127.0.0.1:{9000 + rank}")
        try:
            worker.barrier(0)  # full gang assembled
            register_gang_worker(worker)
            # The trainer cadence: one progress publish per dispatched
            # step. Rank 1 lags one step behind — measurable skew.
            last = 3 - rank
            for step in range(last + 1):
                notify_gang_step(step)
            yield {"rank": rank, "pid": os.getpid(), "last_step": last}
        finally:
            worker.close()  # final alive=False beat (clean shutdown)

    try:
        out = df.rdd.barrier().mapPartitions(run_host).collect()
    finally:
        coord.stop()

    assert len(out) == 2 and {o["rank"] for o in out} == {0, 1}

    beats = read_heartbeats(hb_dir)
    assert [b["rank"] for b in beats] == [0, 1]
    # Two real PROCESSES, each attributed with host + pid.
    assert beats[0]["pid"] != beats[1]["pid"]
    assert {b["pid"] for b in beats} == {o["pid"] for o in out}
    assert all(b["host"] for b in beats)
    # One beat per published step + the final shutdown beat.
    assert beats[0]["beats"] >= 5 and beats[1]["beats"] >= 4

    report = gang_report(hb_dir)
    assert report["n_ranks"] == 2
    # Per-rank training progress and the derived cross-rank step skew.
    assert report["ranks"][0]["step"] == 3
    assert report["ranks"][1]["step"] == 2
    assert report["step_min"] == 2 and report["step_max"] == 3
    assert report["step_skew"] == 1
    for rank in (0, 1):
        assert report["ranks"][rank]["last_seen_age_s"] >= 0.0
        # worker.close() emitted the final alive=False beat — a CLEAN
        # shutdown, not a silent death (which would age alive=True).
        assert report["ranks"][rank]["alive"] is False
    assert report["alive"] == []


@pytest.mark.slow
def test_barrier_mode_empty_partition(spark):
    """3 barrier tasks, 2 rows: one task has NO data and must still
    enter the collectives (weight-0 shape agreement — the reference's
    empty-partition protocol, distributed.py:131-133)."""
    rng = np.random.default_rng(0)
    rows = [(float(i % 2), DenseVector(rng.normal(i % 2, 0.1, 10)))
            for i in range(2)]
    df = spark.createDataFrame(rows, ["label", "features"]).repartition(3)
    model = _estimator(deployMode="barrier", partitions=3, iters=2).fit(df)
    res = model.transform(df).collect()
    assert len(res) == 2 and "predictions" in res[0].asDict()


@pytest.mark.slow
def test_hogwild_executor_push_every_windows(data):
    """PushEvery must reach the executor deployment.
    With pushEvery=4 over 16 iters x 2 workers, the server applies
    ~2*(16/4)=8 window pushes — NOT 32 per-iteration pushes — proving
    the wire carried fused window gradients. compress=False also rides
    the Param into HttpTransport."""
    est = _estimator(mode="hogwild", deployMode="barrier", partitions=2,
                     iters=16, miniBatch=32, pushEvery=4, compress=False)
    model = est.fit(data)
    assert isinstance(model, SparkTorchModel)
    applied = est._last_hogwild_applied
    assert applied == 2 * (16 // 4), applied
    # Per-iter loss records still cover every iteration (windows report
    # k losses each).
    summaries = est._last_hogwild_summaries
    assert all(len(s["losses"]) == 16 for s in summaries)


@pytest.mark.slow
def test_hogwild_executor_shuffles_and_validation(data):
    """partitionShuffles reruns worker rounds with fresh seeds and
    validationPct carves a per-partition holdout (both silently
    ignored before this test existed)."""
    est = _estimator(mode="hogwild", deployMode="barrier", partitions=2,
                     iters=8, miniBatch=32, partitionShuffles=2,
                     validationPct=0.25, earlyStopPatience=50)
    model = est.fit(data)
    summaries = est._last_hogwild_summaries
    assert len(summaries) == 4  # 2 workers x 2 shuffle rounds
    # Different rounds must not replay an identical minibatch stream:
    # with fresh per-round seeds the loss traces differ.
    r0 = [s["losses"] for s in summaries[:2]]
    r1 = [s["losses"] for s in summaries[2:]]
    assert r0[0] != r1[0] or r0[1] != r1[1]
    res = model.transform(data).collect()
    preds = np.asarray([r["predictions"] for r in res])
    labels = np.asarray([r["label"] for r in res])
    assert np.mean((preds > 0.5) == (labels > 0.5)) > 0.8


def test_pipeline_persistence_round_trip(data, tmp_path):
    """The reference's flagship persistence flow (README.md:174-183):
    fit a Pipeline, save the fitted PipelineModel, load, unwrap, and
    get IDENTICAL transforms — the fitted Python stage rides inside a
    StopWordsRemover carrier tagged with the reference's GUID."""
    from pyspark.ml import Pipeline, PipelineModel

    from sparktorch_tpu.spark.pipeline_util import (
        CARRIER_GUID,
        PysparkPipelineWrapper,
        is_carrier,
    )

    est = _estimator(iters=20)
    fitted = Pipeline(stages=[est]).fit(data)
    path = str(tmp_path / "pipe")
    fitted.write().overwrite().save(path)

    loaded_raw = PipelineModel.load(path)
    # On disk the stage is a carrier, GUID-tagged like the reference's.
    assert is_carrier(loaded_raw.stages[0])
    assert loaded_raw.stages[0].getStopWords()[-1] == CARRIER_GUID

    loaded = PysparkPipelineWrapper.unwrap(loaded_raw)
    assert isinstance(loaded.stages[0], SparkTorchModel)
    a = [r["predictions"] for r in fitted.transform(data).collect()]
    b = [r["predictions"] for r in loaded.transform(data).collect()]
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_unfitted_pipeline_persistence(data, tmp_path):
    """Persistence is mixed into the ESTIMATOR too (reference
    ``torch_distributed.py:130-138``): an unfitted Pipeline holding a
    SparkTorch stage saves, loads, unwraps back to a live estimator,
    and that estimator still fits."""
    from pyspark.ml import Pipeline

    from sparktorch_tpu.spark.pipeline_util import (
        PysparkPipelineWrapper,
        is_carrier,
    )

    est = _estimator(iters=15, miniBatch=64)
    pipe = Pipeline(stages=[est])
    path = str(tmp_path / "unfitted")
    pipe.write().overwrite().save(path)

    loaded_raw = Pipeline.load(path)
    assert is_carrier(loaded_raw.getStages()[0])
    loaded = PysparkPipelineWrapper.unwrap(loaded_raw)
    lest = loaded.getStages()[0]
    assert isinstance(lest, SparkTorch)
    # Param surface survives the round trip.
    assert lest.getOrDefault(lest.iters) == 15
    assert lest.getOrDefault(lest.miniBatch) == 64
    model = loaded.fit(data)
    res = model.transform(data).collect()
    preds = np.asarray([r["predictions"] for r in res])
    labels = np.asarray([r["label"] for r in res])
    assert np.mean((preds > 0.5) == (labels > 0.5)) > 0.85


def test_direct_stage_write_read_load(data, tmp_path):
    """Direct stage-level persistence (reference
    ``pipeline_util.py:88-101``): ``stage.write().save(path)`` and
    ``Cls.load(path)`` on both the estimator and the fitted model,
    without a surrounding Pipeline."""
    est = _estimator(iters=20)
    epath = str(tmp_path / "est")
    est.write().overwrite().save(epath)
    loaded_est = SparkTorch.load(epath)
    assert isinstance(loaded_est, SparkTorch)
    assert loaded_est.getOrDefault(loaded_est.iters) == 20

    model = loaded_est.fit(data)
    mpath = str(tmp_path / "model")
    model.write().overwrite().save(mpath)
    loaded_model = SparkTorchModel.load(mpath)
    assert isinstance(loaded_model, SparkTorchModel)
    a = [r["predictions"] for r in model.transform(data).collect()]
    b = [r["predictions"] for r in loaded_model.transform(data).collect()]
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # Unoverwritten re-save must refuse (JavaMLWriter contract).
    with pytest.raises(FileExistsError):
        est.write().save(epath)

    # The carrier format has no class discriminator: a wrong-kind load
    # must fail AT LOAD with a clear type error.
    with pytest.raises(TypeError, match="SparkTorchModel"):
        SparkTorchModel.load(epath)


def test_to_java_gateway_round_trip(data):
    """The Py4J-protocol leg executes for real: ``_to_java`` builds the
    carrier through ``SparkContext._active_spark_context._gateway``
    (string array + ``JavaParams._new_java_obj``, reference
    ``pipeline_util.py:112-130``) and ``_from_java`` re-hydrates from
    the gateway object. Under real pyspark the same calls cross into
    the JVM; the protocol surface is identical."""
    from sparktorch_tpu.spark.pipeline_util import (
        CARRIER_GUID,
        PythonStagePersistence,
    )

    est = _estimator(iters=7)
    jobj = est._to_java()
    words = jobj.getStopWords()
    assert words[-1] == CARRIER_GUID
    assert words[0].endswith(",")  # reference reader drops the last token
    back = PythonStagePersistence._from_java(jobj)
    assert isinstance(back, SparkTorch)
    assert back.getOrDefault(back.iters) == 7

    # A non-carrier stage must be rejected, not mis-decoded.
    plain = localsession.StopWordsRemover(inputCol="a", outputCol="b")
    plain.setStopWords(["the", "and"])
    with pytest.raises(ValueError, match="carrier"):
        PythonStagePersistence._from_java(plain)


def test_localsession_rdd_process_isolation(spark):
    """mapPartitions really runs in separate processes (PIDs differ
    from the driver) — the property the wire-level tests rely on."""
    import os

    df = spark.createDataFrame([(float(i), DenseVector([i]))
                                for i in range(4)], ["label", "features"])
    pids = df.repartition(2).rdd.mapPartitions(
        lambda it: [__import__("os").getpid()]
    ).collect()
    assert len(pids) == 2
    assert all(p != os.getpid() for p in pids)
    assert pids[0] != pids[1]

"""Batch-inference engine: mesh-parallel equality, streaming, and the
fitted-model path with setMesh."""

import jax
import numpy as np
import pytest

from sparktorch_tpu import BatchPredictor, SparkTorch, serialize_torch_obj
from sparktorch_tpu.models import MnistMLP, Net
from sparktorch_tpu.parallel.mesh import local_mesh


@pytest.fixture(scope="module")
def trained():
    module = Net()
    x = np.random.default_rng(0).normal(0, 1, (16, 10)).astype(np.float32)
    variables = module.init(jax.random.key(0), x)
    return module, variables


def test_mesh_inference_matches_single_device(trained):
    module, variables = trained
    x = np.random.default_rng(1).normal(0, 1, (1000, 10)).astype(np.float32)
    single = BatchPredictor(module, variables["params"], chunk=256)
    meshed = BatchPredictor(module, variables["params"],
                            mesh=local_mesh(), chunk=256)
    np.testing.assert_allclose(single.predict(x), meshed.predict(x),
                               rtol=1e-5, atol=1e-6)


def test_mesh_inference_ragged_tail(trained):
    module, variables = trained
    # 1000 % 256 = 232 tail; 232 % 8 = 0; also try a tail not
    # divisible by the shard count.
    x = np.random.default_rng(2).normal(0, 1, (1003, 10)).astype(np.float32)
    meshed = BatchPredictor(module, variables["params"],
                            mesh=local_mesh(), chunk=256)
    out = meshed.predict(x)
    assert out.shape[0] == 1003
    single = BatchPredictor(module, variables["params"], chunk=256)
    np.testing.assert_allclose(out, single.predict(x), rtol=1e-5, atol=1e-6)


def test_predict_stream(trained):
    module, variables = trained
    rng = np.random.default_rng(3)
    batches = [rng.normal(0, 1, (n, 10)).astype(np.float32)
               for n in (128, 64, 200)]
    p = BatchPredictor(module, variables["params"], mesh=local_mesh(), chunk=128)
    outs = list(p.predict_stream(batches))
    assert [o.shape[0] for o in outs] == [128, 64, 200]


def test_fitted_model_set_mesh(data):
    payload = serialize_torch_obj(
        Net(), criterion="mse", optimizer="adam",
        optimizer_params={"lr": 1e-2}, input_shape=(10,),
    )
    est = SparkTorch(inputCol="features", labelCol="label",
                     predictionCol="predictions", torchObj=payload, iters=5)
    model = est.fit(data)
    res_plain = model.transform(data)
    model.setMesh(local_mesh())
    res_mesh = model.transform(data)
    p1 = [float(r["predictions"]) for r in res_plain.collect()]
    p2 = [float(r["predictions"]) for r in res_mesh.collect()]
    np.testing.assert_allclose(p1, p2, rtol=1e-5)


def test_parquet_streaming_matches_direct(tmp_path, trained):
    """The columnar-ingest->device streaming path.
    Rows written as raw fixed-size binary Parquet must stream through
    the reader thread + double-buffered predictor and match the direct
    in-memory predict, with uint8 ingest decoded ON DEVICE via the
    fused preprocess."""
    import jax.numpy as jnp

    from sparktorch_tpu.inference import (
        stream_parquet_predict,
        write_rows_parquet,
    )

    module, variables = trained
    rng = np.random.default_rng(0)
    raw = rng.integers(0, 256, (777, 10), dtype=np.uint8)
    path = str(tmp_path / "rows.parquet")
    n = write_rows_parquet(
        path, (raw[i : i + 200] for i in range(0, 777, 200)),
        rows_per_group=128,
    )
    assert n == 777

    preprocess = lambda x: x.astype(jnp.float32) / 255.0
    pred = BatchPredictor(module, variables["params"], chunk=128,
                          preprocess=preprocess)
    outs = []
    stats = stream_parquet_predict(
        pred, path, row_shape=(10,), dtype=np.uint8,
        drain=outs.append,
    )
    assert stats["n_rows"] == 777
    assert stats["rows_per_sec"] > 0
    got = np.concatenate(outs)

    want = BatchPredictor(module, variables["params"], chunk=128).predict(
        raw.astype(np.float32) / 255.0
    )
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_predictor_postprocess_fused(trained):
    """Device-side postprocess (argmax readback shrink) must match
    host-side argmax over the raw outputs."""
    import jax.numpy as jnp

    from sparktorch_tpu.models import MnistMLP

    module = MnistMLP(hidden=(16,), n_classes=4)
    x = np.random.default_rng(0).normal(0, 1, (300, 10)).astype(np.float32)
    variables = module.init(jax.random.key(0), x[:1])
    raw = BatchPredictor(module, variables["params"], chunk=128).predict(x)
    cls = BatchPredictor(
        module, variables["params"], chunk=128,
        postprocess=lambda y: jnp.argmax(y, -1).astype(jnp.int32),
    ).predict(x)
    np.testing.assert_array_equal(cls, np.argmax(raw, -1))


def test_predictor_device_input_parity():
    # Device-resident input must skip host transfers and match the
    # numpy path bit-for-bit (incl. the ragged last chunk).
    import jax
    import jax.numpy as jnp

    from sparktorch_tpu.models import MnistMLP

    module = MnistMLP()
    variables = module.init(jax.random.key(0), np.zeros((1, 784), np.float32))
    pred = BatchPredictor(module, variables["params"], {}, chunk=64)
    x = np.random.default_rng(0).normal(0, 1, (200, 784)).astype(np.float32)
    np.testing.assert_allclose(
        pred.predict(x), np.asarray(pred.predict(jnp.asarray(x))), rtol=1e-6
    )


def test_parquet_stream_skip_and_limit_windows(tmp_path, trained):
    """skip_rows/max_rows window the stream exactly (the 1M-run resume
    path): any (skip, limit) cut — including cuts landing mid record
    batch — must yield the same rows as slicing the direct predict,
    and stitched windows must reassemble the full run with no row
    dropped or duplicated at batch boundaries."""
    import jax.numpy as jnp

    from sparktorch_tpu.inference import (
        stream_parquet_predict,
        write_rows_parquet,
    )

    module, variables = trained
    rng = np.random.default_rng(1)
    raw = rng.integers(0, 256, (500, 10), dtype=np.uint8)
    path = str(tmp_path / "rows.parquet")
    write_rows_parquet(path, [raw], rows_per_group=64)

    preprocess = lambda x: x.astype(jnp.float32) / 255.0
    pred = BatchPredictor(module, variables["params"], chunk=96,
                          preprocess=preprocess)
    want = BatchPredictor(module, variables["params"], chunk=96).predict(
        raw.astype(np.float32) / 255.0
    )

    def window(skip, limit):
        outs = []
        stats = stream_parquet_predict(
            pred, path, row_shape=(10,), dtype=np.uint8,
            batch_rows=64, drain=outs.append,
            skip_rows=skip, max_rows=limit,
        )
        got = (np.concatenate(outs) if outs
               else np.zeros((0,) + want.shape[1:], want.dtype))
        assert stats["n_rows"] == got.shape[0]
        return got

    # Mid-batch skip, mid-batch limit (64-row groups; 100 and 137 both
    # land inside a batch), whole-batch skip, zero-limit, over-read.
    for skip, limit in [(0, 137), (100, 137), (128, 64), (499, 10),
                        (0, None), (500, None), (77, 0)]:
        got = window(skip, limit)
        end = 500 if limit is None else min(500, skip + limit)
        np.testing.assert_allclose(got, want[skip:end], rtol=1e-5,
                                   atol=1e-6)

    # Resume stitching: consecutive windows reassemble the full set.
    parts = [window(0, 190), window(190, 190), window(380, None)]
    np.testing.assert_allclose(np.concatenate(parts), want, rtol=1e-5,
                               atol=1e-6)

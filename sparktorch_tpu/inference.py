"""Wrap already-trained models for batch inference.

Reference: ``sparktorch/inference.py`` —
``convert_to_serialized_torch`` (:8-15), ``create_spark_torch_model``
(:18-39), ``attach_pytorch_model_to_pipeline`` (:42-61).

Here a "trained model" is a Flax module + trained variables; the
wrapped :class:`SparkTorchModel` runs the compiled chunked forward
(no per-row UDF).
"""

from __future__ import annotations

from typing import Any, Iterable, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from sparktorch_tpu.ml.estimator import SparkTorchModel, _encode_bundle
from sparktorch_tpu.ml.pipeline import PipelineModel
from sparktorch_tpu.parallel.mesh import batch_sharding, replicated
from sparktorch_tpu.utils.serde import ModelSpec


def _jit_forward(module, mesh: Optional[Mesh], params, model_state,
                 preprocess=None, postprocess=None):
    """The predictor's compiled forward ``(params, model_state, x)``.
    Over a mesh the parameters are replicated and the rows split over
    its batch axes by the ``jit``'s own shardings: the partitioner
    splits the program, and no mesh is in sight of the trace (a module
    that picks a path by the mesh, :func:`sparktorch_tpu.models.
    transformer.pick_attention`, sees the process's device count)."""

    def fwd(params, model_state, x):
        if preprocess is not None:
            x = preprocess(x)
        out = module.apply({"params": params, **model_state}, x)
        if postprocess is not None:
            out = postprocess(out)
        return out

    if mesh is None:
        return jax.jit(fwd)
    whole = lambda tree: jax.tree.map(lambda _: replicated(mesh), tree)
    return jax.jit(fwd, in_shardings=(whole(params), whole(model_state),
                                      batch_sharding(mesh)))


class BatchPredictor:
    """Mesh-parallel batch inference engine.

    The reference's inference is a batch-1 Python UDF per DataFrame
    row (``torch_distributed.py:106-120``); its 1M-row ResNet-50
    config (BASELINE.md #5) runs that loop per partition. Here: fixed
    static chunks, ONE compiled forward, and — with a mesh — the chunk
    batch dim sharded over dp(+fsdp) so all chips run inference
    concurrently on their slice (params replicated; XLA inserts
    nothing but the initial broadcast).
    """

    def __init__(self, module, params, model_state=None,
                 mesh: Optional[Mesh] = None, chunk: int = 1024,
                 preprocess=None, postprocess=None, telemetry=None):
        """``preprocess``/``postprocess`` (optional jax fns) are fused
        INTO the compiled forward. preprocess lets the wire carry the
        raw column dtype (e.g. uint8 pixels straight out of Parquet)
        with the cast/normalize on device — 4x less host->device
        traffic than shipping float32. postprocess shrinks the
        READBACK the same way (e.g. ``lambda y: jnp.argmax(y, -1)`` —
        the reference's predict_float argmax, ``torch_distributed.py:
        112-120``, computed on device: 1 value/row over the wire
        instead of the logits row). Both matter most when hosts are
        remote from the chips."""
        from sparktorch_tpu.obs import get_telemetry

        self.module = module
        self.mesh = mesh
        # Serving metrics on the shared bus: rows/batches served,
        # request latency percentiles, and batch fill (real rows over
        # padded chunk rows — low fill means the compiled shape is
        # oversized for the traffic).
        self.telemetry = telemetry or get_telemetry()
        n_shards = 1
        if mesh is not None:
            from sparktorch_tpu.parallel.mesh import BATCH_AXES

            for ax in BATCH_AXES:
                n_shards *= mesh.shape[ax]
        c = max(chunk, n_shards)
        self.chunk = ((c + n_shards - 1) // n_shards) * n_shards
        self._n_shards = n_shards

        self._fwd = _jit_forward(module, mesh, params, model_state or {},
                                 preprocess, postprocess)
        if mesh is not None:
            self._params = jax.device_put(params, replicated(mesh))
            self._model_state = jax.device_put(model_state or {}, replicated(mesh))
            self._x_sharding = batch_sharding(mesh)
        else:
            # Pin params/state to ONE device ONCE. Leaving them as
            # host numpy re-ships the full model through every jitted
            # call. The device is EXPLICIT: a tree assembled off a
            # param-server fleet arrives committed to scattered shard
            # devices, and a bare device_put would keep that torn
            # placement and fail the jit.
            self._params = jax.device_put(params, self._device)
            self._model_state = jax.device_put(model_state or {},
                                               self._device)
            self._x_sharding = None

    @property
    def _device(self):
        # Never stored on the instance: jax Device handles don't
        # pickle, and a dill-dumped fitted model must round-trip.
        return jax.devices()[0]

    def update_params(self, params, model_state=None) -> None:
        """Swap the served weights in place (the LIVE-update path the
        online serving tier drives from its background weight puller).

        The new trees are device-put with the same placement the
        constructor used, then installed by attribute assignment
        (atomic per attribute under the GIL): a concurrent ``predict``
        chunk sees old or new params wholesale, never a torn tree.
        Params and model_state are two separate assignments, though —
        a caller that must flip them TOGETHER between batches (the
        continuous batcher's contract) should hold the coherent pair
        in its own versioned slot and execute from that snapshot,
        which is exactly what :class:`sparktorch_tpu.serve.infer.
        InferenceReplica` does; it calls through here only so this
        predictor's direct ``predict`` path serves the same weights.
        """
        if self.mesh is not None:
            self._params = jax.device_put(params, replicated(self.mesh))
            if model_state is not None:
                self._model_state = jax.device_put(
                    model_state, replicated(self.mesh))
        else:
            self._params = jax.device_put(params, self._device)
            if model_state is not None:
                self._model_state = jax.device_put(model_state,
                                                   self._device)

    def _chunks(self, x, n: int):
        """Yield (padded_part, real_rows) chunks of ONE compiled shape
        (the last small chunk pads only to shard divisibility)."""
        ns = self._n_shards
        for start in range(0, n, self.chunk):
            part = x[start : start + self.chunk]
            real = part.shape[0]
            if real < self.chunk:
                target = (
                    self.chunk if n > self.chunk
                    else ((real + ns - 1) // ns) * ns
                )
                if target != real:
                    if isinstance(part, np.ndarray):
                        pad = np.zeros((target - real, *part.shape[1:]),
                                       part.dtype)
                        part = np.concatenate([part, pad])
                    else:  # device-resident input pads on-device
                        pad = jnp.zeros((target - real, *part.shape[1:]),
                                        part.dtype)
                        part = jnp.concatenate([part, pad])
            self.telemetry.observe("inference.batch_fill",
                                   real / max(1, part.shape[0]))
            yield part, real

    def _put(self, part):
        # jax.device_put, NOT jnp.asarray: asarray routes a host numpy
        # array through a conversion path; device_put is the direct
        # transfer.
        if self._x_sharding is not None:
            return jax.device_put(part, self._x_sharding)
        if isinstance(part, np.ndarray):
            return jax.device_put(part)
        return jnp.asarray(part)

    def predict(self, x) -> np.ndarray:
        """Chunked forward over ``x`` (numpy or an already-device-
        resident jax array — the latter skips host transfers).

        The loop is double-buffered: chunk i+1's host→device copy is
        enqueued and chunk i+1's forward dispatched BEFORE chunk i's
        result is read back, so the (blocking) readback of one chunk
        overlaps the transfer+compute of the next (JAX dispatch is
        async). Device memory stays O(2 chunks) — outputs are drained
        as the loop advances, never accumulated on device (a 1M-row
        run would otherwise hold the full logits array in HBM).
        """
        n = x.shape[0]
        if n == 0:
            # Probe one padded shard-batch for the output shape.
            probe = np.zeros((self._n_shards, *x.shape[1:]), x.dtype)
            out = np.asarray(
                self._fwd(self._params, self._model_state, self._put(probe))
            )
            return out[:0]
        import time as _time

        t0 = _time.perf_counter()
        parts = self._chunks(x, n)
        host = []
        nxt = next(parts, None)
        dev = self._put(nxt[0]) if nxt else None
        prev = None  # (device_out, real) one chunk behind
        while nxt is not None:
            _, real = nxt
            out = self._fwd(self._params, self._model_state, dev)
            nxt = next(parts, None)
            if nxt is not None:
                dev = self._put(nxt[0])  # overlaps with the fwd above
            if prev is not None:
                host.append(np.asarray(prev[0])[: prev[1]])
            prev = (out, real)
        host.append(np.asarray(prev[0])[: prev[1]])
        out = np.concatenate(host) if len(host) > 1 else host[0]
        # The readback loop above drained the device, so this latency
        # covers transfer+compute honestly (not just dispatch).
        tele = self.telemetry
        tele.observe("inference.predict_s", _time.perf_counter() - t0,
                     labels={"path": "host"})
        tele.counter("inference.requests", labels={"path": "host"})
        tele.counter("inference.rows", float(n), labels={"path": "host"})
        return out

    def predict_device(self, x, in_flight: int = 3):
        """Chunked forward with no device->host readbacks: returns ONE
        device array of predictions (padding trimmed), leaving the
        download — and therefore the sync cadence — to the caller.

        Why this exists: every readback is a host<->device sync. The
        ordinary ``predict`` interleaves one readback per chunk; this
        path emits none, so a long streaming run can fence at its own
        cadence (e.g. one data-dependent scalar per reader batch)
        instead of once per chunk.
        ``in_flight`` paces via ``block_until_ready`` as best-effort
        backpressure; callers needing a HARD bound must fence with a
        readback themselves."""
        n = x.shape[0]
        if n == 0:
            # Shape probe WITHOUT the readback predict() does — one
            # readback is exactly what this method exists to avoid.
            probe = np.zeros((self._n_shards, *x.shape[1:]), x.dtype)
            out = self._fwd(self._params, self._model_state,
                            self._put(probe))
            return out[:0]
        import time as _time

        t0 = _time.perf_counter()
        outs = []
        pending = []
        for part, real in self._chunks(x, n):
            dev = self._put(part)
            out = self._fwd(self._params, self._model_state, dev)
            outs.append(out[:real] if real != out.shape[0] else out)
            pending.append(out)
            if len(pending) >= max(2, in_flight):
                # Transfer-free backpressure: bound live input buffers.
                pending.pop(0).block_until_ready()
        tele = self.telemetry
        # Dispatch latency only — this path deliberately never fences
        # (see docstring); the caller's eventual download is the sync.
        tele.observe("inference.predict_s", _time.perf_counter() - t0,
                     labels={"path": "device"})
        tele.counter("inference.requests", labels={"path": "device"})
        tele.counter("inference.rows", float(n), labels={"path": "device"})
        return outs[0] if len(outs) == 1 else jnp.concatenate(outs)

    def predict_stream(self, batches: Iterable[np.ndarray]) -> Iterator[np.ndarray]:
        """Partition-parallel streaming inference: feed numpy batches
        (e.g. parquet row groups), get predictions per batch — the
        shape of the reference's per-partition UDF path, compiled."""
        for batch in batches:
            yield self.predict(np.asarray(batch))


def write_rows_parquet(path: str, rows: Iterable[np.ndarray],
                       column: str = "features",
                       rows_per_group: int = 1024) -> int:
    """Write row batches (each a (n, ...) ndarray, any fixed dtype) to
    a Parquet file as raw fixed-size binary — the columnar on-disk
    format the streaming inference path ingests. Returns rows written.

    No compression: synthetic/pixel payloads barely compress, and a
    reader's rate should be the wire's, not the codec's.
    """
    import pyarrow as pa
    import pyarrow.parquet as pq

    writer = None
    total = 0
    try:
        for batch in rows:
            batch = np.ascontiguousarray(batch)
            n = batch.shape[0]
            nbytes = batch[0].nbytes if n else 0
            arr = pa.FixedSizeBinaryArray.from_buffers(
                pa.binary(nbytes), n,
                [None, pa.py_buffer(batch.tobytes())],
            )
            table = pa.table({column: arr})
            if writer is None:
                writer = pq.ParquetWriter(path, table.schema,
                                          compression="NONE")
            writer.write_table(table, row_group_size=rows_per_group)
            total += n
    finally:
        if writer is not None:
            writer.close()
    return total


def stream_parquet_predict(
    predictor: BatchPredictor,
    path: str,
    row_shape,
    dtype=np.uint8,
    column: str = "features",
    batch_rows: Optional[int] = None,
    drain=None,
    prefetch: int = 2,
    skip_rows: int = 0,
    max_rows: Optional[int] = None,
    device_outputs: bool = False,
) -> dict:
    """Columnar-ingest -> device streaming inference: the measured
    BASELINE config-5 path (the reference feeds DataFrame partitions
    to a batch-1 row UDF, ``torch_distributed.py:96-127``; here Parquet
    row groups stream through a reader thread into the predictor's
    double-buffered compiled forward).

    Pipeline: a READER thread iterates Parquet record batches, decodes
    the fixed-size-binary column into (n, *row_shape) arrays of the
    raw column dtype, and fills a bounded queue; the main thread feeds
    the predictor, whose double buffering overlaps each chunk's
    host->device transfer + forward with the previous chunk's
    readback. Disk/decode, wire, and compute all overlap — sustained
    rate ~= the slowest stage, not the sum.

    ``drain`` (optional callable) receives each prediction batch
    (e.g. to write results out); defaults to discarding after a shape
    check. Returns timing stats incl. per-stage busy times so overlap
    is visible: wall << read_busy + predict_busy when pipelined.

    ``skip_rows``/``max_rows`` window the stream (resume support for
    long runs): the reader drops the first ``skip_rows`` rows (sliced
    at record-batch granularity) and ends after ``max_rows`` rows.

    ``device_outputs=True`` routes through ``predict_device``: drain
    receives DEVICE arrays and no device->host readback happens inside
    the stream (see ``predict_device``). ``predict_busy`` then
    measures dispatch, not
    completion; the wall time stays honest (the caller's final
    download syncs everything).
    """
    import queue as _queue
    import threading
    import time as _time

    import pyarrow.parquet as pq

    q: "_queue.Queue" = _queue.Queue(maxsize=max(1, prefetch))
    stop = threading.Event()
    reader_err: list = []
    read_busy = [0.0]

    row_elems = int(np.prod(row_shape))
    itemsize = np.dtype(dtype).itemsize

    def _put(item) -> bool:
        while not stop.is_set():
            try:
                q.put(item, timeout=0.25)
                return True
            except _queue.Full:
                continue
        return False

    def reader():
        try:
            pf = pq.ParquetFile(path)
            it = iter(pf.iter_batches(
                batch_size=batch_rows or predictor.chunk, columns=[column]
            ))
            to_skip = max(0, int(skip_rows))
            budget = max_rows if max_rows is not None else float("inf")
            while budget > 0:
                # Time the iterator pull itself: the Parquet disk IO +
                # Arrow decode happen inside __next__, and they are the
                # bulk of read_busy — timing only the numpy reshape
                # (as before) made a 14 GB read look like 0.014 s and
                # voided the overlap_factor claim.
                t0 = _time.perf_counter()
                rb = next(it, None)
                if rb is None or stop.is_set():
                    read_busy[0] += _time.perf_counter() - t0
                    return
                col = rb.column(0)
                if to_skip >= len(col):
                    to_skip -= len(col)
                    read_busy[0] += _time.perf_counter() - t0
                    continue
                buf = col.buffers()[-1]
                arr = np.frombuffer(
                    buf, dtype=dtype, count=len(col) * row_elems,
                    offset=col.offset * row_elems * itemsize,
                ).reshape(len(col), *row_shape)
                if to_skip:
                    arr = arr[to_skip:]
                    to_skip = 0
                if arr.shape[0] > budget:
                    arr = arr[: int(budget)]
                budget -= arr.shape[0]
                read_busy[0] += _time.perf_counter() - t0
                if not _put(arr):
                    return
        except BaseException as e:  # pragma: no cover - surfaced below
            reader_err.append(e)
        finally:
            # Best-effort end-of-stream sentinel; bail as soon as the
            # consumer signalled stop (it no longer reads the queue).
            # The consumer does NOT rely on the sentinel arriving — it
            # also treats (reader dead + queue empty) as end-of-stream
            # — so a full queue here cannot wedge either side.
            while not stop.is_set():
                try:
                    q.put(None, timeout=0.25)
                    break
                except _queue.Full:
                    continue

    tele = predictor.telemetry
    t = threading.Thread(target=reader, daemon=True)
    t_start = _time.perf_counter()
    t.start()
    n_rows = 0
    n_batches = 0
    predict_busy = 0.0
    try:
        while True:
            try:
                item = q.get(timeout=1.0)
                # Depth AFTER the pop: 0 means the reader is the
                # bottleneck (compute starves); ~prefetch means the
                # predictor is (queue saturated).
                tele.observe("inference.queue_depth", q.qsize())
            except _queue.Empty:
                # Sentinel-free end detection: a dead reader with an
                # empty queue is end-of-stream (or a reader crash —
                # surfaced below) even if its sentinel was dropped.
                # The reader may have enqueued final items between the
                # timeout expiring and the liveness check — only an
                # Empty queue observed AFTER seeing it dead ends the
                # stream, so nothing enqueued before death is lost.
                if not t.is_alive():
                    try:
                        item = q.get_nowait()
                    except _queue.Empty:
                        break
                else:
                    continue
            if item is None:
                break
            t0 = _time.perf_counter()
            out = (predictor.predict_device(item) if device_outputs
                   else predictor.predict(item))
            predict_busy += _time.perf_counter() - t0
            assert out.shape[0] == item.shape[0]
            if drain is not None:
                drain(out)
            n_rows += item.shape[0]
            n_batches += 1
    finally:
        stop.set()
        t.join(timeout=30)
    if reader_err:
        raise reader_err[0]
    wall = _time.perf_counter() - t_start
    tele.counter("inference.stream_runs")
    tele.counter("inference.stream_rows", float(n_rows))
    return {
        "n_rows": n_rows,
        "n_batches": n_batches,
        "wall_s": round(wall, 3),
        "rows_per_sec": round(n_rows / max(wall, 1e-9), 2),
        "read_busy_s": round(read_busy[0], 3),
        "predict_busy_s": round(predict_busy, 3),
        # > 1.0 means the stages genuinely overlapped (pipelining won
        # wall time vs running them back to back).
        "overlap_factor": round(
            (read_busy[0] + predict_busy) / max(wall, 1e-9), 3
        ),
    }


def _bundle_spec(model: Any, variables: Optional[dict], loss: str = "mse"):
    if variables is None:
        raise ValueError(
            "pass trained variables (the dict returned by module.init/"
            "training) — Flax modules carry no weights"
        )
    variables = dict(variables)
    params = variables.pop("params", variables)
    spec = ModelSpec(module=model, loss=loss)
    return spec, params, variables


def convert_to_serialized(model: Any, variables: dict) -> str:
    """Serialize a trained (module, variables) pair to the model
    string format used by :class:`SparkTorchModel`.

    Parity: ``convert_to_serialized_torch`` (inference.py:8-15).
    """
    spec, params, model_state = _bundle_spec(model, variables)
    return _encode_bundle(spec, params, model_state)


def create_spark_torch_model(
    model: Any,
    variables: Optional[dict] = None,
    inputCol: str = "features",
    predictionCol: str = "predicted",
    useVectorOut: bool = False,
) -> SparkTorchModel:
    """Wrap a trained model as a transformer without running ``fit``.

    Parity: ``create_spark_torch_model`` (inference.py:18-39).
    """
    spec, params, model_state = _bundle_spec(model, variables)
    return SparkTorchModel(
        inputCol=inputCol,
        predictionCol=predictionCol,
        modStr=_encode_bundle(spec, params, model_state),
        useVectorOut=useVectorOut,
    )


def attach_model_to_pipeline(
    pipeline_model: PipelineModel,
    spark_model: SparkTorchModel,
) -> PipelineModel:
    """Append an inference stage to a fitted pipeline.

    Parity: ``attach_pytorch_model_to_pipeline`` (inference.py:42-61).
    """
    return PipelineModel(list(pipeline_model.stages) + [spark_model])


# Reference-compatible name.
attach_pytorch_model_to_pipeline = attach_model_to_pipeline

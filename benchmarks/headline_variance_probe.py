"""Variance probe for the headline MNIST-CNN bench.

Round-3 problem: the driver-captured headline spanned 289k-375k
examples/sec/chip across same-day runs (+-13%) despite a min-of-8-
chunks estimator, so a real regression is indistinguishable from
noise. This probe gathers the data to find the variance source:

- per-chunk times WITH a blocking materialize per chunk (the r03
  estimator) vs ONE materialize at the end of a long dispatch span
  (amortizes the per-sync round-trip out of the estimate);
- several steps_per_call settings (dispatch-RTT amortization);
- everything timestamped and repeated over minutes, so bursty
  interference shows up as time-correlated slow chunks.

Writes raw records to benchmarks/headline_probe.jsonl.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np


def main() -> None:
    import jax

    from sparktorch_tpu.utils.checkpoint import arm_compile_cache

    arm_compile_cache(min_compile_time_s=0.5)

    from sparktorch_tpu.models import MnistCNN
    from sparktorch_tpu.parallel.mesh import MeshConfig, build_mesh, replicated
    from sparktorch_tpu.train.step import create_train_state, make_train_epoch
    from sparktorch_tpu.train.sync import prepare_sharded_batch
    from sparktorch_tpu.utils.data import handle_features
    from sparktorch_tpu.utils.serde import ModelSpec

    out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "headline_probe.jsonl")
    rng = np.random.default_rng(0)
    batch = 1024
    x = rng.normal(0, 1, (batch, 784)).astype(np.float32)
    y = rng.integers(0, 10, (batch,)).astype(np.int32)
    spec = ModelSpec(module=MnistCNN(), loss="cross_entropy",
                     optimizer="adam", optimizer_params={"lr": 1e-3},
                     input_shape=(784,))

    devices = jax.devices()
    mesh = build_mesh(MeshConfig(), devices)
    b, _ = handle_features(x, y)
    b = prepare_sharded_batch(b, mesh)
    tx = spec.make_optimizer()
    with mesh:
        state = jax.jit(
            lambda: create_train_state(spec, jax.random.key(0),
                                       sample_x=b.x[:1], tx=tx),
            out_shardings=replicated(mesh),
        )()

    apply_fn = spec.make_module().apply
    loss_fn = spec.loss_fn()

    def mat(m):
        float(np.asarray(jax.device_get(m.loss))[-1])

    records = []

    def emit(rec):
        rec["ts"] = round(time.time(), 3)
        records.append(rec)
        with open(out_path, "a") as f:
            f.write(json.dumps(rec) + "\n")
        print(json.dumps(rec), flush=True)

    epochs = {}
    # The epoch donates its input state: thread ONE state through every
    # call (rates don't depend on param values).
    for spc in (30, 120):
        epochs[spc] = make_train_epoch(apply_fn, loss_fn, tx, mesh,
                                       steps_per_call=spc)
        for _ in range(3):
            state, m = epochs[spc](state, b)
        mat(m)

    # ~4 minutes of alternating trials.
    for trial in range(8):
        # A: r03 estimator — 8 chunks of 30, materialize per chunk.
        ep = epochs[30]
        chunk_times = []
        for _ in range(8):
            t0 = time.perf_counter()
            state, m = ep(state, b)
            mat(m)
            chunk_times.append(time.perf_counter() - t0)
        per_step = [t / 30 for t in chunk_times]
        emit({"mode": "per_chunk_mat", "spc": 30, "trial": trial,
              "chunk_ms": [round(t * 1e3, 2) for t in chunk_times],
              "rate_min": round(batch / min(per_step), 0),
              "rate_med": round(batch / float(np.median(per_step)), 0)})

        # B: one long span — 8 calls of 30 dispatched back-to-back,
        # single materialize at the end.
        t0 = time.perf_counter()
        for _ in range(8):
            state, m = ep(state, b)
        mat(m)
        dt = time.perf_counter() - t0
        emit({"mode": "span_mat", "spc": 30, "trial": trial,
              "span_ms": round(dt * 1e3, 2),
              "rate": round(batch / (dt / 240), 0)})

        # C: bigger fused call — 2 calls of 120, one materialize.
        ep2 = epochs[120]
        t0 = time.perf_counter()
        for _ in range(2):
            state, m = ep2(state, b)
        mat(m)
        dt = time.perf_counter() - t0
        emit({"mode": "span_mat", "spc": 120, "trial": trial,
              "span_ms": round(dt * 1e3, 2),
              "rate": round(batch / (dt / 240), 0)})

    # Summary over trials.
    for key in [("per_chunk_mat", 30), ("span_mat", 30), ("span_mat", 120)]:
        sel = [r for r in records
               if (r["mode"], r["spc"]) == key]
        rates = [r.get("rate", r.get("rate_min")) for r in sel]
        print(f"summary mode={key[0]} spc={key[1]} "
              f"min={min(rates):.0f} med={np.median(rates):.0f} "
              f"max={max(rates):.0f} "
              f"spread={(max(rates) - min(rates)) / np.median(rates) * 100:.1f}%",
              flush=True)


if __name__ == "__main__":
    main()

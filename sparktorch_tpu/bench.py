"""Benchmark suite: the BASELINE.md configs (plus extensions) on real TPU.

The reference publishes no numbers (BASELINE.md), so these are the
project's measured baselines. BASELINE.json configs:

1. mnist_mlp_sync     — MNIST 3-layer MLP, synchronous DP
2. lazy_cnn_sync      — MNIST CNN with LAZY model materialization
3. resnet18_hogwild   — ResNet-18/CIFAR-10 shapes, async param server
4. bert_dp            — BERT-base-shape encoder, sync DP (compute-bound)
5. resnet50_inference — ResNet-50 batch inference (1M-row projection)

Extensions beyond the reference's scope: mnist_cnn_sync (the headline),
long_context_lm (flash kernels at seq 8192), moe_lm (switch MoE vs its
dense twin, with a comm/compute budget from an analyzed XLA capture),
hogwild_wire (dill vs framed-binary parameter-server wire on real
sockets), hogwild_chaos (supervised recovery from one seeded worker
kill), hogwild_chaos_soak (multi-round random kill/freeze/drop
schedule), sharded_trace (capture→analyze→publish trace-attribution
round-trip) — the last three are gates, not just measurements.

Each bench returns a summary dict (examples/sec/chip + p50/p99 step
times where steps exist) and appends raw per-phase records to a JSONL
log (the protocol BASELINE.md prescribes: raw logs under
``benchmarks/``).

Timing: every measured region ends with a forced materialization
(``float(jnp.sum(...))``) — a data-dependent readback that cannot
return before the device work it depends on.

CLI: ``sparktorch-tpu-bench [--config all|headline|<name>] [--log PATH]``.
``headline`` prints the single JSON line the benchmark driver consumes
(same MNIST-CNN metric as round 1, for cross-round comparability).
"""

from __future__ import annotations

import argparse
import gc
import json
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from sparktorch_tpu.obs.telemetry import wall_ts

# Measured reference proxy (examples/sec) for the MNIST-CNN workload:
# torch-CPU forward+backward+Adam, batch 1024, on this machine — the
# substrate the reference's own tests/CI train on (environment.yml
# pins CPU pytorch). Measured 2026-07-29 by benchmarks/reference_proxy.py.
REFERENCE_BASELINE_EXAMPLES_PER_SEC = 1120.8

# Per-chip peaks for MFU/roofline fields live in obs.goodput's
# DEVICE_PEAKS, keyed by device_kind (single source: the run-level
# goodput ledger's MFU and the bench records use the SAME row and the
# same mfu_honest division). A device that is not in the table gets
# no MFU or roofline field.
from sparktorch_tpu.obs.goodput import (  # noqa: E402
    device_peaks as _device_peaks,
    mfu_honest as _mfu_honest,
)


def _materialize(*arrays) -> None:
    import jax.numpy as jnp

    for a in arrays:
        float(jnp.sum(a)) if hasattr(a, "dtype") else None


def _steps_summary(times: List[float]) -> Dict[str, float]:
    ts = np.asarray(sorted(times))
    return {
        "step_time_p50_s": float(np.percentile(ts, 50)),
        "step_time_p99_s": float(np.percentile(ts, 99)),
        "step_time_mean_s": float(ts.mean()),
    }


def _xla_cost_per_step(epoch, epoch1, state, batch):
    """XLA's own accounting for ONE train step: ``flops`` (executed
    HLO flops — includes optimizer, layernorms, any remat) and
    ``bytes accessed`` (HBM traffic as modeled by the compiler). Both
    are PER-DEVICE numbers — cost_analysis runs on the SPMD-partitioned
    per-device module (verified against a hand-counted matmul on an
    8-device mesh). The analysis runs on a SINGLE-step program
    (``epoch1``): backends disagree on whether a scanned chunk's while
    body is counted once or trip-count times (TPU counts it once —
    discovered when the 10-step chunk reported exactly 1/10 of the
    analytic FLOPs), and a length-1 program is unambiguous either way.
    This is the methodology-free cross-check for every analytic MFU
    number, costed by the compiler that scheduled it.

    Returns ``(cost_dict_or_None, compiled_or_None)`` — ``compiled``
    is the AOT executable of the MEASURED chunk, which the caller
    reuses so the jit cache doesn't compile it a second time."""
    try:
        compiled = epoch.lower(state, batch).compile()
    except Exception:
        return None, None
    try:
        ca = epoch1.lower(state, batch).compile().cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        flops = float(ca.get("flops", -1.0))
        byts = float(ca.get("bytes accessed", -1.0))
        if flops <= 0:
            return None, compiled
        return {
            "xla_flops_per_step": flops,
            "xla_bytes_per_step": byts if byts > 0 else None,
        }, compiled
    except Exception:  # cost_analysis availability varies by backend;
        # keep the measured chunk's AOT executable either way.
        return None, compiled


def _sync_epoch_bench(spec, x, y, batch_size: int, iters: int = 30,
                      warmup: int = 3, chunks: int = 8,
                      repeats: int = 5, with_cost_analysis: bool = False,
                      with_trace: bool = False) -> dict:
    """Shared harness for the sync-DP configs: whole chunks of steps
    fused into one compiled call (the framework's fast path).

    Estimator (round 4): PAIRED-SPAN SLOPE. Each repeat times a short
    span (1 fused call of ``iters`` steps) and a long span (``chunks``
    calls dispatched back-to-back), each ended by ONE forced
    materialization; per-step time is the slope
    ``(T_long - T_short) / ((chunks-1)*iters)``, which cancels the
    constant per-span sync cost (paid once per span, whatever it is
    on the machine that runs). Reports the median over
    ``repeats`` interleaved slope samples plus best and spread, so a
    regression is distinguishable from residual noise."""
    import jax

    from sparktorch_tpu.obs import get_telemetry
    from sparktorch_tpu.parallel.mesh import MeshConfig, build_mesh, replicated
    from sparktorch_tpu.train.step import create_train_state, make_train_epoch
    from sparktorch_tpu.train.sync import prepare_sharded_batch
    from sparktorch_tpu.utils.data import handle_features

    # Per-phase attribution for the BENCH record: every phase below is
    # a span on the process bus, and the record carries the phase-
    # seconds breakdown — so a regression names its phase (data, init,
    # compile+warmup, measure) instead of being one opaque rate drop.
    tele = get_telemetry()
    devices = jax.devices()
    mesh = build_mesh(MeshConfig(), devices)
    with tele.span("bench/data") as _sp_data:
        batch, _ = handle_features(x, y)
        batch = prepare_sharded_batch(batch, mesh)
        _sp_data.sync(batch.x)
    tx = spec.make_optimizer()
    with tele.span("bench/init") as _sp_init, mesh:
        state = jax.jit(
            lambda: create_train_state(spec, jax.random.key(0),
                                       sample_x=batch.x[:1], tx=tx),
            out_shardings=replicated(mesh),
        )()
        _sp_init.sync(state.step)
    with tele.span("bench/compile_warmup") as _sp_warm:
        epoch = make_train_epoch(spec.make_module().apply, spec.loss_fn(), tx,
                                 mesh, steps_per_call=iters)
        cost = None
        if with_cost_analysis:
            epoch1 = make_train_epoch(spec.make_module().apply,
                                      spec.loss_fn(), tx, mesh,
                                      steps_per_call=1)
            cost, compiled = _xla_cost_per_step(epoch, epoch1, state, batch)
            if compiled is not None:
                epoch = compiled  # one compile serves analysis AND timing
        for _ in range(warmup):
            state, metrics = epoch(state, batch)
        _materialize(metrics.loss)
        _sp_warm.synced = True  # _materialize above fenced the device

    slopes = []  # per-step seconds, one sample per repeat
    n_long = max(chunks, 2)
    with tele.span("bench/measure") as _sp_measure:
        for _ in range(max(2, repeats)):
            t0 = time.perf_counter()
            state, metrics = epoch(state, batch)
            _materialize(metrics.loss)
            t_short = time.perf_counter() - t0
            while True:
                t0 = time.perf_counter()
                for _ in range(n_long):
                    state, metrics = epoch(state, batch)
                _materialize(metrics.loss)
                t_long = time.perf_counter() - t0
                # The difference must dwarf the sync-cost jitter
                # (+-40 ms observed): grow the long span until the
                # extra compute is >= 1.6 s, so jitter stays a <=2.5%
                # effect. The grown span carries over to the
                # remaining repeats.
                if t_long - t_short >= 1.6 or n_long >= 512:
                    break
                n_long *= 2
            # n_long calls vs 1 call: the extra (n_long-1)*iters steps
            # ran with zero extra syncs, so the difference is pure
            # step time.
            slopes.append((t_long - t_short) / max((n_long - 1) * iters, 1))
        _sp_measure.synced = True  # every iteration ended in a fence
    # An RTT drop between the paired spans can push a sample to ~0 or
    # negative; the median over repeats is robust to those, but drop
    # them from the reported spread so it reflects usable samples.
    # Trim SYMMETRICALLY: a near-zero positive slope is the same RTT
    # artifact as a negative one, and leaving it in wildly inflates
    # rate_best/rate_spread_pct (ADVICE r04) — anything below 20% of
    # the positive median is jitter, not a measurement.
    # Optional trace-attribution phase (with_trace): capture an XLA
    # profile of two more fused-epoch calls and machine-read it
    # (obs.xprof) — the per-collective comm/compute budget then rides
    # the record beside the rate, and the same xprof.* metrics land on
    # the bus for --telemetry-dump / /metrics parity.
    trace_rec = None
    _sp_trace = None
    if with_trace:
        import tempfile

        from sparktorch_tpu.utils.tracing import profile_run, step_annotation

        with tele.span("bench/trace") as _sp_trace, \
                tempfile.TemporaryDirectory() as td:
            with profile_run(td, telemetry=tele) as prof_handle:
                for i in range(2):
                    with step_annotation(i, telemetry=tele):
                        state, metrics = epoch(state, batch)
                    _materialize(metrics.loss)
            _sp_trace.synced = True
        analysis = prof_handle["analysis"]
        if analysis is not None:
            trace_rec = {
                "comm_s": round(analysis.comm_s, 6),
                "comm_fraction": round(analysis.comm_fraction, 4),
                "overlap_fraction": round(analysis.overlap_fraction, 4),
                "collective_s": {k: round(v, 6)
                                 for k, v in analysis.family_s().items()},
                "collective_counts": analysis.family_counts(),
                "n_collective_events": analysis.n_collective_events,
            }

    good = [s for s in slopes if s > 0]
    if good:
        floor = 0.2 * float(np.median(good))
        good = [s for s in good if s >= floor]
    if not good:
        # Degenerate link (every sample non-positive): fall back to
        # the whole-span mean INCLUDING its one sync cost — an upper
        # bound on step time, so the reported rate is conservative —
        # rather than crashing the whole benchmark run.
        good = [t_long / max(n_long * iters, 1)]
    med = float(np.median(good))
    best = min(good)
    rates = [batch_size / s / len(devices) for s in good]
    per_chip = batch_size / med / len(devices)
    spread_pct = 100.0 * (max(rates) - min(rates)) / max(np.median(rates), 1e-9)
    out = {
        "examples_per_sec_per_chip": round(per_chip, 1),
        "rate_best": round(batch_size / best / len(devices), 1),
        "rate_samples": [round(r, 1) for r in rates],
        "rate_spread_pct": round(spread_pct, 1),
        "n_chips": len(devices),
        "final_loss": float(np.asarray(metrics.loss)[-1]),
        # Where this config's wall time went — the per-phase breakdown
        # the BENCH logs owe (mirrors the bus's bench/* spans).
        "phase_s": {
            "data": round(_sp_data.duration_s, 3),
            "init": round(_sp_init.duration_s, 3),
            "compile_warmup": round(_sp_warm.duration_s, 3),
            "measure": round(_sp_measure.duration_s, 3),
        },
        **_steps_summary(good),
    }
    if cost is not None:
        out.update(cost)
    if trace_rec is not None:
        # The comm/compute budget section: seconds join the phase
        # breakdown, the attribution detail rides beside it.
        out["comm_budget"] = trace_rec
        out["phase_s"]["trace"] = round(_sp_trace.duration_s, 3)
        out["phase_s"]["comm_s"] = trace_rec["comm_s"]
        out["comm_fraction"] = trace_rec["comm_fraction"]
        out["overlap_fraction"] = trace_rec["overlap_fraction"]
    return out


# ---------------------------------------------------------------------------
# Configs
# ---------------------------------------------------------------------------


def bench_mnist_mlp_sync() -> dict:
    """BASELINE config 1 (examples/simple_dnn.py workload)."""
    from sparktorch_tpu.models import MnistMLP
    from sparktorch_tpu.utils.serde import ModelSpec

    rng = np.random.default_rng(0)
    batch = 1024
    x = rng.normal(0, 1, (batch, 784)).astype(np.float32)
    y = rng.integers(0, 10, (batch,)).astype(np.int32)
    spec = ModelSpec(module=MnistMLP(), loss="cross_entropy",
                     optimizer="adam", optimizer_params={"lr": 1e-3},
                     input_shape=(784,))
    out = _sync_epoch_bench(spec, x, y, batch)
    return {"config": "mnist_mlp_sync", "unit": "examples/sec/chip", **out}


def bench_mnist_cnn_sync() -> dict:
    """The round-1 headline workload (examples/simple_cnn.py)."""
    from sparktorch_tpu.models import MnistCNN
    from sparktorch_tpu.utils.serde import ModelSpec

    rng = np.random.default_rng(0)
    batch = 1024
    x = rng.normal(0, 1, (batch, 784)).astype(np.float32)
    y = rng.integers(0, 10, (batch,)).astype(np.int32)
    spec = ModelSpec(module=MnistCNN(), loss="cross_entropy",
                     optimizer="adam", optimizer_params={"lr": 1e-3},
                     input_shape=(784,))
    out = _sync_epoch_bench(spec, x, y, batch)
    return {"config": "mnist_cnn_sync", "unit": "examples/sec/chip", **out}


def bench_lazy_cnn_sync() -> dict:
    """BASELINE config 2: the LAZY serialization path — the model
    class ships unmaterialized and is first instantiated here
    (examples/lazy_load_cnn.py; reference util.py:148-179)."""
    from sparktorch_tpu.models import MnistCNN
    from sparktorch_tpu.utils.serde import deserialize_model, serialize_model_lazy

    payload = serialize_model_lazy(
        MnistCNN, criterion="cross_entropy", optimizer="adam",
        optimizer_params={"lr": 1e-3}, input_shape=(784,),
    )
    t0 = time.perf_counter()
    spec = deserialize_model(payload)
    lazy_materialize_s = time.perf_counter() - t0

    rng = np.random.default_rng(0)
    batch = 1024
    x = rng.normal(0, 1, (batch, 784)).astype(np.float32)
    y = rng.integers(0, 10, (batch,)).astype(np.int32)
    out = _sync_epoch_bench(spec, x, y, batch)
    return {"config": "lazy_cnn_sync", "unit": "examples/sec/chip",
            "lazy_materialize_s": round(lazy_materialize_s, 4), **out}


def bench_resnet18_hogwild() -> dict:
    """BASELINE config 3: ResNet-18 on CIFAR-10 shapes through the
    async param server (device-pinned workers, versioned pulls), plus
    a SYNC ResNet-18 leg at the same minibatch so async efficiency
    (hogwild rate / sync rate) is a measured number, not an
    extrapolation. Round 4 hardening: 256 push windows per run (4x
    round 3) and median-of-5 repeats — the spread target is <=20%."""
    import jax

    from sparktorch_tpu.models.resnet import resnet18
    from sparktorch_tpu.obs import get_telemetry
    from sparktorch_tpu.train.hogwild import train_async
    from sparktorch_tpu.utils.serde import ModelSpec

    tele = get_telemetry()
    with tele.span("bench/data") as _sp_data:
        rng = np.random.default_rng(0)
        n, mb = 2048, 256
        x = rng.normal(0, 1, (n, 32, 32, 3)).astype(np.float32)
        y = rng.integers(0, 10, (n,)).astype(np.int32)
    with tele.span("bench/init") as _sp_init:
        spec = ModelSpec(module=resnet18(num_classes=10), loss="cross_entropy",
                         optimizer="sgd", optimizer_params={"lr": 1e-2},
                         input_shape=(32, 32, 3))
    # push_every=4: the accumulation knob is part of the async design
    # (k on-device grad means per server apply — wire/apply traffic
    # drops 4x, the same examples train).
    iters = 1024  # 256 push windows per worker: long spans beat jitter
    # Fixed warmup with the SAME shapes and window size: train_async
    # builds fresh jitted closures per call, so this relies on the
    # persistent compilation cache (enabled in main()) to make the
    # measured runs compile-free.
    with tele.span("bench/compile_warmup") as _sp_warm:
        train_async(spec, x, labels=y, iters=8, mini_batch=mb, push_every=4)

    def _one_run(transport: str = "local",
                 run_iters: int = iters) -> tuple[float, dict, dict]:
        t0 = time.perf_counter()
        result = train_async(spec, x, labels=y, iters=run_iters,
                             mini_batch=mb, push_every=4,
                             transport=transport)
        dt = time.perf_counter() - t0
        n_workers = len(jax.devices())
        # One push per window: count distinct (worker, dispatch-ts)
        # pairs, not per-iteration records (push_every=4 emits 4
        # records/push).
        pushes = len({(m["worker"], m["t"]) for m in result.metrics})
        n_rec = len(result.metrics)
        # Steady-state: drop everything up to and INCLUDING the window
        # dispatched at the second timestamp — that window's compute
        # happened before the measured span starts (span begins at
        # uts[1]), so counting it would inflate the rate by ~1 window.
        # The span STARTS at a dispatch timestamp but ENDS at t_done —
        # the device sync each worker records when its final loss
        # materializes — so async dispatch can't overstate throughput.
        uts = sorted({m["t"] for m in result.metrics})
        t_done = [m["t_done"] for m in result.metrics if "t_done" in m]
        if len(uts) > 2 and t_done:
            n_steady = sum(1 for m in result.metrics if m["t"] > uts[1])
            steady = n_steady * mb / (max(t_done) - uts[1]) / n_workers
        else:
            steady = n_rec * mb / dt / n_workers
        budget = (result.summary or {}).get("hogwild_budget", {})
        return steady, {"n_chips": n_workers, "pushes": pushes,
                        "iters_recorded": n_rec, "dt": dt,
                        "final_loss": result.metrics[-1]["loss"]}, budget

    # Five measured repeats: report the median and the spread so a
    # regression is distinguishable from run-to-run variance. The
    # auxiliary stats come from the median run so they can't
    # contradict the headline rate.
    with tele.span("bench/measure") as _sp_measure:
        runs = sorted([_one_run() for _ in range(5)], key=lambda r: r[0])
        rates = [r[0] for r in runs]
        per_chip, info, budget = runs[len(runs) // 2]
        spread_pct = 100.0 * (rates[-1] - rates[0]) / max(
            rates[len(rates) // 2], 1e-9
        )
        times = [info["dt"] / max(1, info["iters_recorded"])] * max(
            1, info["iters_recorded"]
        )

        # Wire ablation: the same workload over the HTTP transport
        # (the deployment wire; binary frames by default since the
        # net/ subsystem landed). local-vs-http separates the DESIGN
        # overhead (server round-trips, pull placement, materialize
        # fences) from the WIRE itself. Fault-isolated: a 45 MB pull
        # stalling past even the generous deadline must not discard
        # the already-measured local numbers — the failure is
        # recorded instead.
        try:
            http_rate, _, http_budget = _one_run(
                transport="http", run_iters=max(64, iters // 4))
            http_error = None
        except Exception as e:
            http_rate, http_budget = 0.0, {}
            http_error = f"{type(e).__name__}: {e}"
            if e.__cause__ is not None:  # the worker's root failure
                http_error += (f" (from {type(e.__cause__).__name__}: "
                               f"{e.__cause__})")
            http_error = http_error[:300]

    # The decomposition the efficiency ratio owes: where the median
    # run's worker wall time went, as fractions that sum to ~1
    # (pull wire, pulled-params placement, async dispatch, the push's
    # device-draining materialize fence, push wire + server apply,
    # stop-poll, and unattributed loop bookkeeping).
    budget_rec = {}
    if budget and budget.get("loop_s"):
        loop_s = budget["loop_s"]
        phases = ("pull_s", "pull_place_s", "dispatch_s",
                  "push_materialize_s", "push_wire_s", "poll_s",
                  "drain_s", "other_s")
        budget_rec = {
            "budget_loop_s": round(loop_s, 3),
            **{f"budget_{k}": round(budget.get(k, 0.0), 3)
               for k in phases},
            "budget_fractions": {
                k: round(budget.get(k, 0.0) / loop_s, 4) for k in phases
            },
            "pull_mb": round(budget.get("pull_bytes", 0) / 1e6, 2),
            "push_mb": round(budget.get("push_bytes", 0) / 1e6, 2),
            "pulls": int(budget.get("pulls", 0)),
            "pull_fresh": int(budget.get("pull_fresh", 0)),
        }

    # Sync twin at the same PER-CHIP batch: each hogwild worker
    # computes 256-row minibatches, so the sync leg runs 256 rows per
    # chip (global batch mb x n_chips, tiling the dataset when the rig
    # has more chips than 2048 rows cover) — the async/sync ratio then
    # isolates server/transport overhead, not batch-size utilization.
    n_chips_now = len(jax.devices())
    n_sync = mb * n_chips_now
    reps = -(-n_sync // n)
    xs = np.tile(x, (reps, 1, 1, 1))[:n_sync]
    ys = np.tile(y, reps)[:n_sync]
    sync = _sync_epoch_bench(spec, xs, ys, n_sync,
                             iters=16, warmup=2, chunks=4)
    sync_rate = sync["examples_per_sec_per_chip"]
    return {
        "config": "resnet18_hogwild", "unit": "examples/sec/chip",
        "examples_per_sec_per_chip": round(per_chip, 1),
        "repeat_rates": [round(r, 1) for r in rates],
        "repeat_spread_pct": round(spread_pct, 1),
        "n_chips": info["n_chips"], "pushes": info["pushes"],
        "iters_recorded": info["iters_recorded"],
        "final_loss": info["final_loss"],
        "sync_examples_per_sec_per_chip": sync_rate,
        "async_efficiency_vs_sync": round(per_chip / max(sync_rate, 1e-9), 3),
        "http_examples_per_sec_per_chip": round(http_rate, 1),
        "async_efficiency_http_vs_local": round(
            http_rate / max(per_chip, 1e-9), 3
        ),
        "http_push_wire_s_per_push": round(
            http_budget.get("push_wire_s", 0.0)
            / max(1, http_budget.get("pushes", 1)), 4
        ),
        **({"http_ablation_error": http_error} if http_error else {}),
        **budget_rec,
        # Same decomposition contract as _sync_epoch_bench, from this
        # config's own bus spans (the sync twin reports its own
        # phase_s inside `sync_*`; it runs outside the measure span so
        # its nested spans keep their canonical bench/* paths).
        "phase_s": {
            "data": round(_sp_data.duration_s, 3),
            "init": round(_sp_init.duration_s, 3),
            "compile_warmup": round(_sp_warm.duration_s, 3),
            "measure": round(_sp_measure.duration_s, 3),
            "sync_twin": round(sum(sync["phase_s"].values()), 3),
        },
        **_steps_summary(times),
    }


def bench_hogwild_wire() -> dict:
    """Wire ablation: the SAME hogwild workload over the dill wire vs
    the framed binary wire (net/), both on real sockets. The headline
    numbers are per-operation: seconds and bytes per push and per
    fresh pull, which is what the wire change actually buys — the
    end-to-end rate also rides along. ``phase_s`` carries both the
    standard data/init/compile_warmup/measure decomposition and the
    pull/push budget of each wire (the hot-path seconds the ISSUE's
    acceptance names)."""
    from sparktorch_tpu.models import MnistMLP
    from sparktorch_tpu.obs import get_telemetry
    from sparktorch_tpu.train.hogwild import train_async
    from sparktorch_tpu.utils.serde import ModelSpec

    tele = get_telemetry()
    with tele.span("bench/data") as _sp_data:
        rng = np.random.default_rng(0)
        n, mb = 2048, 256
        x = rng.normal(0, 1, (n, 784)).astype(np.float32)
        y = rng.integers(0, 10, (n,)).astype(np.int32)
    with tele.span("bench/init") as _sp_init:
        spec = ModelSpec(module=MnistMLP(), loss="cross_entropy",
                         optimizer="adam", optimizer_params={"lr": 1e-3},
                         input_shape=(784,))
    with tele.span("bench/compile_warmup") as _sp_warm:
        # Same shapes/window as the measured runs: the persistent
        # compile cache (enabled in main()) makes them compile-free.
        train_async(spec, x, labels=y, iters=8, mini_batch=mb,
                    push_every=4)

    iters = 128
    wires: Dict[str, dict] = {}
    with tele.span("bench/measure") as _sp_measure:
        for wire_fmt in ("dill", "binary"):
            t0 = time.perf_counter()
            result = train_async(spec, x, labels=y, iters=iters,
                                 mini_batch=mb, push_every=4,
                                 transport="http", wire=wire_fmt, seed=0)
            wall = time.perf_counter() - t0
            b = (result.summary or {}).get("hogwild_budget", {})
            pushes = max(1, int(b.get("pushes", 0)))
            fresh = max(1, int(b.get("pull_fresh", 0)))
            wires[wire_fmt] = {
                "wall_s": round(wall, 3),
                "pull_s": round(b.get("pull_s", 0.0), 4),
                "push_wire_s": round(b.get("push_wire_s", 0.0), 4),
                "push_materialize_s": round(
                    b.get("push_materialize_s", 0.0), 4),
                "pull_mb": round(b.get("pull_bytes", 0) / 1e6, 3),
                "push_mb": round(b.get("push_bytes", 0) / 1e6, 3),
                "pulls": int(b.get("pulls", 0)),
                "pull_fresh": int(b.get("pull_fresh", 0)),
                "pushes": int(b.get("pushes", 0)),
                "push_wire_s_per_push": round(
                    b.get("push_wire_s", 0.0) / pushes, 5),
                "pull_s_per_fresh_pull": round(
                    b.get("pull_s", 0.0) / fresh, 5),
                # Steps = pushes x push_every (device count varies by
                # rig; the budget's own push count doesn't).
                "push_bytes_per_step": round(
                    b.get("push_bytes", 0)
                    / max(1, int(b.get("pushes", 0)) * 4), 1),
                "final_loss": result.metrics[-1]["loss"],
            }

    d, bn = wires["dill"], wires["binary"]
    return {
        "config": "hogwild_wire", "unit": "s/push",
        "value": bn["push_wire_s_per_push"],
        "binary": bn, "dill": d,
        "push_bytes_ratio_dill_over_binary": round(
            d["push_mb"] / max(bn["push_mb"], 1e-9), 3),
        "pull_bytes_ratio_dill_over_binary": round(
            d["pull_mb"] / max(bn["pull_mb"], 1e-9), 3),
        "push_wire_speedup": round(
            d["push_wire_s_per_push"]
            / max(bn["push_wire_s_per_push"], 1e-9), 3),
        "phase_s": {
            "data": round(_sp_data.duration_s, 3),
            "init": round(_sp_init.duration_s, 3),
            "compile_warmup": round(_sp_warm.duration_s, 3),
            "measure": round(_sp_measure.duration_s, 3),
            # The hot-path budget the wire change targets, per wire.
            "pull": round(bn["pull_s"], 4),
            "push": round(bn["push_wire_s"] + bn["push_materialize_s"], 4),
            "pull_dill": round(d["pull_s"], 4),
            "push_dill": round(d["push_wire_s"] + d["push_materialize_s"], 4),
        },
    }


def bench_hogwild_chaos() -> dict:
    """Fault-tolerance gate: the SAME hogwild workload run clean and
    under a seeded one-worker kill with supervision on. FAILS (raises)
    unless the chaos run completes, the supervisor restarted exactly
    one worker, the recovered model still learned, and the recovery's
    wall-clock overhead stays under budget — so a regression in the
    recovery path breaks `make bench-chaos`, not production.

    Headline value is the measured recovery latency (death ->
    restarted worker running, from the ``ft_recovery_latency_s``
    histogram); ``overhead_pct`` is the chaos run's wall-clock cost
    over the clean twin (the restarted worker reruns its round
    assignment, so the expected overhead is roughly one worker's
    partial rerun plus the backoff delay)."""
    import jax

    from sparktorch_tpu.ft import ChaosConfig, FtPolicy, RestartPolicy, inject
    from sparktorch_tpu.models import MnistMLP
    from sparktorch_tpu.obs import Telemetry, get_telemetry
    from sparktorch_tpu.train.hogwild import train_async
    from sparktorch_tpu.utils.serde import ModelSpec

    tele = get_telemetry()
    with tele.span("bench/data") as _sp_data:
        rng = np.random.default_rng(0)
        n, mb = 2048, 128
        x = rng.normal(0, 1, (n, 784)).astype(np.float32)
        y = rng.integers(0, 10, (n,)).astype(np.int32)
    with tele.span("bench/init") as _sp_init:
        spec = ModelSpec(module=MnistMLP(), loss="cross_entropy",
                         optimizer="adam", optimizer_params={"lr": 1e-3},
                         input_shape=(784,))
    iters, kill_at = 64, 16
    # The victim must be a worker that EXISTS: train_async spawns one
    # per device, and on a single-chip backend that is worker 0.
    n_workers = len(jax.devices())
    victim = 1 if n_workers > 1 else 0
    policy = FtPolicy(restart=RestartPolicy(max_restarts=2,
                                            backoff_base_s=0.05),
                      seed=0)
    with tele.span("bench/compile_warmup") as _sp_warm:
        train_async(spec, x, labels=y, iters=8, mini_batch=mb, seed=0)

    with tele.span("bench/measure") as _sp_measure:
        t0 = time.perf_counter()
        clean = train_async(spec, x, labels=y, iters=iters, mini_batch=mb,
                            seed=0, supervise=True, ft_policy=policy)
        t_clean = time.perf_counter() - t0

        run_tele = Telemetry(run_id="bench_hogwild_chaos")
        t0 = time.perf_counter()
        with inject(ChaosConfig(kill_worker_at={victim: kill_at}, seed=0),
                    telemetry=run_tele):
            result = train_async(spec, x, labels=y, iters=iters,
                                 mini_batch=mb, seed=0, supervise=True,
                                 ft_policy=policy, telemetry=run_tele)
        t_chaos = time.perf_counter() - t0

    restarts = (result.summary or {}).get("ft", {}).get("restarts_total", -1)
    recovery = run_tele.histogram("ft_recovery_latency_s",
                                  labels={"worker": str(victim)})
    overhead_pct = 100.0 * (t_chaos - t_clean) / max(t_clean, 1e-9)

    # The gate. Budgets are generous (CPU rigs jitter) but real: the
    # run must COMPLETE with exactly one restart, the model must have
    # trained, recovery must be sub-second-scale, and the whole-run
    # overhead bounded by a rerun of one worker plus slack.
    if restarts != 1:
        raise AssertionError(f"expected exactly 1 restart, got {restarts}")
    if len(result.metrics) != len(clean.metrics):
        raise AssertionError(
            f"chaos run lost records: {len(result.metrics)} vs "
            f"{len(clean.metrics)} clean"
        )
    if recovery["count"] < 1 or recovery["max"] > 10.0:
        raise AssertionError(f"recovery latency out of budget: {recovery}")
    if overhead_pct > 300.0:
        raise AssertionError(
            f"recovery overhead {overhead_pct:.0f}% exceeds 300% budget"
        )
    return {
        "config": "hogwild_chaos", "unit": "s (recovery latency)",
        "value": round(recovery["max"], 4),
        "recovery_latency_s": round(recovery["max"], 4),
        "restarts": int(restarts),
        "wall_clean_s": round(t_clean, 3),
        "wall_chaos_s": round(t_chaos, 3),
        "overhead_pct": round(overhead_pct, 1),
        "kill_at_step": kill_at,
        "victim_worker": victim,
        "iters": iters,
        "n_chips": n_workers,
        "final_loss_clean": clean.metrics[-1]["loss"],
        "final_loss_chaos": result.metrics[-1]["loss"],
        "phase_s": {
            "data": round(_sp_data.duration_s, 3),
            "init": round(_sp_init.duration_s, 3),
            "compile_warmup": round(_sp_warm.duration_s, 3),
            "measure": round(_sp_measure.duration_s, 3),
        },
    }


def bench_hogwild_ps_fleet() -> dict:
    """Parameter-server FLEET gate (``make bench-ps-fleet``): the
    sharded tier must actually beat the single server where it
    claims to — FAILS (raises) otherwise.

    Workload: a ~28 MB MLP state dict under a SPARSE-update pusher (a
    stable hot quarter of the leaves receives closed-loop gradient
    pushes — the fine-tuning/embedding shape the delta wire exists
    for) while a swarm of stateful workers each completes a fixed
    quota of FRESH pulls at a step cadence. The single server's v1
    wire must re-ship the full tree on every fresh pull (and apply
    dense gradients); the 4-shard fleet ships per-tensor deltas and
    applies the sparse partials shard-parallel. Legs run interleaved
    x3 and gate on MEDIANS (this rig is CPU-share capped and noisy).

    Gates:
    - aggregate pull bandwidth (model-state refreshed per second
      across the swarm: quota x model bytes / leg wall) — fleet must
      beat the single server;
    - p99 fresh-pull latency — fleet must beat the single server;
    - wire bytes per fresh pull — the fleet's deltas must ship
      STRICTLY fewer bytes than the single server's full snapshots
      (and the int8 delta leg strictly fewer than the f32 delta leg);
    - a seeded shard kill (``ft.chaos`` ``fleet.shard`` site) during
      a real ``train_async(shards=4)`` run must complete with exact
      record counts and >= 1 monitored shard restart.
    """
    import threading

    import jax

    from sparktorch_tpu.ft import ChaosConfig, inject
    from sparktorch_tpu.models import MLP
    from sparktorch_tpu.net import wire as _wire
    from sparktorch_tpu.net.sharded import ShardedTransport
    from sparktorch_tpu.net.transport import BinaryTransport
    from sparktorch_tpu.obs import Telemetry, get_telemetry
    from sparktorch_tpu.serve.fleet import ParamServerFleet
    from sparktorch_tpu.serve.param_server import (
        ParameterServer,
        ParamServerHttp,
    )
    from sparktorch_tpu.train.hogwild import train_async
    from sparktorch_tpu.utils.serde import ModelSpec

    tele = get_telemetry()
    n_shards, workers, quota, cadence_s = 4, 6, 10, 0.005
    with tele.span("bench/init") as _sp_init:
        # ~67 MB of parameters: big enough that per-pull BYTES dwarf
        # this rig's scheduler jitter (cpu-share-capped container;
        # ±100-300 ms thread-starvation spikes are routine), so the
        # p99 gate measures the wire design, not the noise floor.
        spec = ModelSpec(module=MLP(features=[1024] * 16 + [10]),
                         loss="cross_entropy", optimizer="sgd",
                         optimizer_params={"lr": 1e-2},
                         input_shape=(784,))

    def _swarm_leg(make_pull, push_fn) -> dict:
        """Closed-loop pusher + W stateful pullers, each completing
        ``quota`` fresh pulls; per-pull latency and wire bytes out.
        Every transport opened here is closed before the leg returns
        (7 legs per bench run — leaked keep-alive sockets and fan-out
        pools would pile up for the life of the process)."""
        stop = threading.Event()
        lat: List[float] = []
        lock = threading.Lock()
        wire_bytes = [0]
        opened: list = []

        def pusher():
            while not stop.is_set():
                push_fn()  # wait=True: version cadence = apply capacity
                time.sleep(cadence_s)

        def puller():
            pull, bytes_fn, transport = make_pull()
            with lock:
                opened.append(transport)
            # Untimed initial sync (both legs ship the full model here
            # — a one-time cost); the measured quota is STEADY-STATE
            # pulls, which is where delta and full genuinely differ.
            have = -1
            snap = pull(have)
            if snap is not None:
                have = snap[0]
            done, mine, b0 = 0, [], bytes_fn()
            # Hard deadline: a server whose writer died stops minting
            # versions, every pull 304s forever, and without this the
            # leg would hang instead of failing the gate.
            deadline = time.monotonic() + 120.0
            while done < quota and time.monotonic() < deadline:
                t0 = time.perf_counter()
                snap = pull(have)
                dt = time.perf_counter() - t0
                if snap is not None:
                    have, done = snap[0], done + 1
                    mine.append(dt)
                time.sleep(cadence_s)
            with lock:
                lat.extend(mine)
                wire_bytes[0] += bytes_fn() - b0

        pt = threading.Thread(target=pusher, daemon=True)
        pt.start()
        t0 = time.perf_counter()
        threads = [threading.Thread(target=puller, daemon=True)
                   for _ in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        stop.set()
        pt.join()
        for transport in opened:
            transport.close()
        pulls = workers * quota
        if len(lat) < pulls:
            raise AssertionError(
                f"swarm leg stalled: {len(lat)}/{pulls} fresh pulls "
                f"completed before the 120s deadline — the server "
                f"stopped minting versions (dead writer?)"
            )
        return {
            "wall_s": wall,
            "state_mb_per_s": pulls * model_nbytes / wall / 1e6,
            "wire_mb_per_s": wire_bytes[0] / wall / 1e6,
            "wire_mb_per_pull": wire_bytes[0] / pulls / 1e6,
            "pull_p50_ms": float(np.percentile(lat, 50)) * 1e3,
            "pull_p99_ms": float(np.percentile(lat, 99)) * 1e3,
        }

    def _single_leg() -> dict:
        server = ParameterServer(spec, window_len=workers)
        http = ParamServerHttp(server, port=0).start()
        try:
            _, params = server.slot.read()
            zero_full = jax.tree.map(
                lambda a: np.zeros_like(np.asarray(a)), params)

            def push():
                try:
                    server.push_gradients(zero_full, wait=True)
                except Exception:
                    pass  # a raced stop must not kill the leg

            def make_pull():
                t = BinaryTransport(http.url, quant=None)
                return (lambda have: t.pull(have)), (
                    lambda: t.stats["pull_bytes"]), t

            push()
            server.drain()
            pull, _b, t = make_pull()  # warm render + connection path
            pull(-1)
            t.close()
            return _swarm_leg(make_pull, push)
        finally:
            http.stop()
            server.stop()

    def _fleet_leg(pull_quant=None) -> dict:
        fleet = ParamServerFleet(spec, n_shards=n_shards).start()
        try:
            def push():
                try:
                    fleet.scatter_push(hot_partial, wait=True)
                except Exception:
                    pass

            def make_pull():
                t = ShardedTransport(fleet, pull_quant=pull_quant)
                return (lambda have: t.pull(have)), (
                    lambda: t.stats["pull_bytes"]), t

            push()
            fleet.drain()
            pull, _b, t = make_pull()
            pull(-1)
            t.close()
            return _swarm_leg(make_pull, push)
        finally:
            fleet.stop()

    with tele.span("bench/compile_warmup") as _sp_warm:
        # One throwaway fleet warms the per-shard apply jits and leaf
        # partitioning; the measured legs then start compile-free
        # (same persistent-cache contract as every other config).
        probe = ParamServerFleet(spec, n_shards=n_shards)
        flat = {p: np.asarray(a)
                for p, a in _wire.flatten_tree(probe.assemble())}
        model_nbytes = sum(a.nbytes for a in flat.values())
        paths = sorted(flat)
        hot = paths[:max(1, len(paths) // 4)]
        hot_partial = {p: np.zeros_like(flat[p]) for p in hot}
        probe.scatter_push(hot_partial, wait=True)
        probe.stop()

    with tele.span("bench/measure") as _sp_measure:
        singles, fleets = [], []
        for _ in range(3):  # interleaved: rig noise hits both legs
            singles.append(_single_leg())
            fleets.append(_fleet_leg())
        int8 = _fleet_leg(pull_quant="int8")

    def _median(legs, key):
        return float(np.median([leg[key] for leg in legs]))

    single = {k: round(_median(singles, k), 3) for k in singles[0]}
    fleet = {k: round(_median(fleets, k), 3) for k in fleets[0]}
    bw_ratio = fleet["state_mb_per_s"] / max(single["state_mb_per_s"], 1e-9)
    p99_ratio = fleet["pull_p99_ms"] / max(single["pull_p99_ms"], 1e-9)

    # -- seeded shard kill during a real sharded training run ----------
    with tele.span("bench/shard_kill") as _sp_kill:
        rng = np.random.default_rng(0)
        x = np.concatenate([rng.normal(0, 1, (100, 10)),
                            rng.normal(2, 1, (100, 10))]).astype(np.float32)
        y = np.concatenate([np.zeros(100),
                            np.ones(100)]).astype(np.float32)
        from sparktorch_tpu import serialize_torch_obj
        from sparktorch_tpu.models import ClassificationNet

        clf = serialize_torch_obj(
            ClassificationNet(n_classes=2), criterion="cross_entropy",
            optimizer="adam", optimizer_params={"lr": 5e-3},
            input_shape=(10,),
        )
        kill_tele = Telemetry(run_id="bench_ps_fleet_kill")
        iters, parts = 12, 2
        with inject(ChaosConfig(kill_shard_at={1: 4}, seed=0),
                    telemetry=kill_tele) as inj:
            result = train_async(clf, x, labels=y, iters=iters,
                                 partitions=parts, seed=0,
                                 transport="http", shards=n_shards,
                                 telemetry=kill_tele)
        kill_fired = len([e for e in inj.events
                          if e["site"] == "fleet.shard"])
        kill_records = len(result.metrics)
        kill_restarts = int(result.summary["fleet"]["shard_restarts"])

    # -- the gates ------------------------------------------------------
    if not bw_ratio > 1.0:
        raise AssertionError(
            f"fleet aggregate pull bandwidth did not beat the single "
            f"server: {fleet['state_mb_per_s']:.0f} vs "
            f"{single['state_mb_per_s']:.0f} MB/s (x{bw_ratio:.2f})"
        )
    if not p99_ratio < 1.0:
        raise AssertionError(
            f"fleet p99 pull latency did not beat the single server: "
            f"{fleet['pull_p99_ms']:.0f} vs "
            f"{single['pull_p99_ms']:.0f} ms (x{p99_ratio:.2f})"
        )
    if not fleet["wire_mb_per_pull"] < single["wire_mb_per_pull"]:
        raise AssertionError(
            f"delta pulls did not ship fewer bytes than full pulls: "
            f"{fleet['wire_mb_per_pull']:.2f} vs "
            f"{single['wire_mb_per_pull']:.2f} MB/pull"
        )
    if not int8["wire_mb_per_pull"] < fleet["wire_mb_per_pull"]:
        raise AssertionError(
            f"int8 delta pulls did not ship fewer bytes than f32 "
            f"deltas: {int8['wire_mb_per_pull']:.2f} vs "
            f"{fleet['wire_mb_per_pull']:.2f} MB/pull"
        )
    if kill_fired < 1:
        raise AssertionError("seeded shard kill never fired")
    if kill_records != iters * parts:
        raise AssertionError(
            f"shard-kill run lost records: {kill_records} != "
            f"{iters * parts}"
        )
    if kill_restarts < 1:
        raise AssertionError(
            "shard kill produced no monitored restart "
            "(fleet.shard_restarts_total empty)"
        )

    return {
        "config": "hogwild_ps_fleet", "unit": "x (bandwidth ratio)",
        "value": round(bw_ratio, 3),
        "n_shards": n_shards, "workers": workers, "quota": quota,
        "model_mb": round(model_nbytes / 1e6, 1),
        "hot_leaves": len(hot), "total_leaves": len(paths),
        "bandwidth_ratio": round(bw_ratio, 3),
        "p99_ratio": round(p99_ratio, 3),
        "single": single, "fleet": fleet, "fleet_int8": int8,
        "delta_bytes_saved_pct": round(
            100 * (1 - fleet["wire_mb_per_pull"]
                   / single["wire_mb_per_pull"]), 1),
        "int8_bytes_saved_pct": round(
            100 * (1 - int8["wire_mb_per_pull"]
                   / fleet["wire_mb_per_pull"]), 1),
        "shard_kill": {"fired": kill_fired, "records": kill_records,
                       "restarts": kill_restarts},
        "phase_s": {
            "init": round(_sp_init.duration_s, 3),
            "compile_warmup": round(_sp_warm.duration_s, 3),
            "measure": round(_sp_measure.duration_s, 3),
            "shard_kill": round(_sp_kill.duration_s, 3),
        },
    }


def bench_rpc_trace() -> dict:
    """Per-request RPC tracing gate (``make bench-rpc-trace``): the
    tracing layer must be cheap, honest, and diagnostic — FAILS
    (raises) otherwise.

    Gates:
    - **overhead**: the binary-wire push+pull loop under DEFAULT head
      sampling must cost < 2% wall over the tracer fully OFF
      (medians of interleaved repeats — rig noise hits both legs);
    - **reconcile**: with sampling forced to 1.0, every fresh 4-shard
      pull yields exactly ONE stitched span tree; the per-shard
      ``serve`` span p50 agrees with that shard's ``wire_latency_s``
      histogram p50 (same request population — the span and the
      histogram time the same handler window through different
      pipelines), and every root wall contains its slowest serve hop;
    - **critical path**: a seeded slow shard (``ft.chaos``
      ``slow_shard_s``) is named as the critical path of each traced
      pull in the collector's stitched output AND in
      ``timeline --rpc`` rendered from the collector's JSONL sink.
    """
    import contextlib
    import io
    import os

    import jax

    from sparktorch_tpu.ft import ChaosConfig, inject
    from sparktorch_tpu.models import MnistMLP
    from sparktorch_tpu.net.sharded import ShardedTransport
    from sparktorch_tpu.net.transport import BinaryTransport
    from sparktorch_tpu.obs import FleetCollector, Telemetry, get_telemetry
    from sparktorch_tpu.obs import rpctrace
    from sparktorch_tpu.obs import timeline as _timeline
    from sparktorch_tpu.serve.fleet import ParamServerFleet
    from sparktorch_tpu.serve.param_server import (
        ParameterServer,
        ParamServerHttp,
    )
    from sparktorch_tpu.utils.serde import ModelSpec

    tele = get_telemetry()
    with tele.span("bench/init") as _sp_init:
        spec = ModelSpec(module=MnistMLP(), loss="cross_entropy",
                         optimizer="sgd", optimizer_params={"lr": 1e-2},
                         input_shape=(784,))

    # ---- leg 1: tracing overhead at default sampling ------------------
    # Gate = (measured per-op tracing cost at the default rate) /
    # (measured wire-bench op wall), where the tracing cost is the
    # unsampled fast path PLUS the amortized sampled-commit chain,
    # each timed by a tight microbenchmark (min of batches, ring
    # pre-filled to maxlen so the commit copies are worst-case), and
    # the op wall is the real push + fresh-pull round trip on a live
    # server with the tracer OFF.
    #
    # Why not difference two end-to-end timings? That was tried five
    # ways on this rig (independent legs, paired leg ratios, twin
    # stacks, summed alternating blocks, per-pair block-median
    # ratios on 304 pulls) and falsified: an A/A control (both modes
    # tracer-off) swings +-2%, and off-vs-on swings +-20%
    # UNCORRELATED with the actual sample rate (rate=1e-9 measured
    # "+19.9%", rate=0.01 "-10.2%") — the cpu-share scheduler's
    # multimodal epochs alias against any blocking, drowning a
    # microsecond-scale effect. Timing the mechanism directly and
    # dividing by the measured op wall is the statistic that
    # converges, and it is conservative: the microbench charges every
    # op the full client-root cost plus its amortized share of a
    # 7-commit sampled chain against a worst-case full ring.
    def _per_iter_us(fn, iters: int, batches: int = 7) -> float:
        best = float("inf")
        for _ in range(batches):
            t0 = time.perf_counter()
            for _ in range(iters):
                fn()
            best = min(best, (time.perf_counter() - t0) / iters)
        return best * 1e6

    with tele.span("bench/measure_overhead") as _sp_overhead:
        micro_tele = Telemetry(run_id="rpc_overhead_micro")
        mtr = rpctrace.tracer_for(micro_tele)
        # (a) unsampled fast path: what EVERY untraced wire op pays.
        mtr.sample_rate = 0.0

        def _fast():
            with mtr.root_span("pull", kind="client", host="h", port=1):
                pass

        fast_us = _per_iter_us(_fast, 2000)
        # (b) the sampled commit chain, shaped like a real traced
        # push (root + encode/socket client-side + serve/decode/
        # queue_wait/apply server-side = 7 commits), against a ring
        # already at maxlen (every commit pays the full-copy cost).
        mtr.sample_rate = 1.0
        for _ in range(mtr._ring.maxlen + 8):
            with mtr.root_span("fill"):
                pass

        def _sampled():
            with mtr.root_span("push", kind="client", host="h",
                               port=1) as sp:
                with mtr.child_span("encode", sp.ctx):
                    pass
                with mtr.child_span("socket", sp.ctx):
                    pass
                with mtr.child_span("serve", sp.ctx, kind="server",
                                    route="/update.bin"):
                    pass
                with mtr.child_span("decode", sp.ctx, kind="server"):
                    pass
                mtr.record("queue_wait", sp.ctx, wall_ts(), 0.001,
                           kind="server")
                with mtr.child_span("apply", sp.ctx, kind="server"):
                    pass

        sampled_us = _per_iter_us(_sampled, 300)
        # The timed wire iteration below is push + pull — TWO traced
        # roots — so the per-iteration tracing cost is two roots'
        # worth (each modeled with the push-shaped 7-commit sampled
        # chain, the heavier of the two).
        roots_per_op = 2
        traced_cost_us = roots_per_op * (
            fast_us + rpctrace.DEFAULT_SAMPLE_RATE
            * max(sampled_us - fast_us, 0.0))

        # (c) the real wire-bench op wall, tracer fully off.
        op_tele = Telemetry(run_id="rpc_overhead_op")
        rpctrace.tracer_for(op_tele).sample_rate = -1.0
        server = ParameterServer(spec, telemetry=op_tele)
        http = ParamServerHttp(server, port=0).start()
        try:
            transport = BinaryTransport(http.url, telemetry=op_tele)
            _, params = server.slot.read()
            zeros = jax.tree.map(
                lambda a: np.zeros_like(np.asarray(a)), params)
            transport.push(zeros)  # warm connection + apply jit
            server.drain()
            transport.pull(-1)
            walls = []
            for _ in range(48):
                t0 = time.perf_counter()
                transport.push(zeros)
                transport.pull(-1)
                walls.append(time.perf_counter() - t0)
            transport.close()
        finally:
            http.stop()
            server.stop()
        op_us = float(np.median(walls)) * 1e6
        overhead_pct = 100.0 * traced_cost_us / op_us

    # ---- leg 2: traced sharded pulls reconcile with wire_latency_s ---
    n_shards, n_pulls = 4, 10
    with tele.span("bench/measure_reconcile") as _sp_reconcile:
        rec_tele = Telemetry(run_id="rpc_reconcile")
        tracer = rpctrace.tracer_for(rec_tele)
        tracer.sample_rate = 1.0
        tracer.resize(8192)  # hold every span of the bounded run
        fleet = ParamServerFleet(spec, n_shards=n_shards,
                                 telemetry=rec_tele).start()
        sink_dir = os.environ.get("TMPDIR", "/tmp")
        sink = os.path.join(sink_dir, f"rpc_trace_sink_{os.getpid()}.jsonl")
        collector = None
        try:
            transport = ShardedTransport(fleet, telemetry=rec_tele,
                                         run_id=rec_tele.run_id)
            zeros = jax.tree.map(
                lambda a: np.zeros_like(np.asarray(a)), fleet.assemble())
            have = -1
            pulled = 0
            for _ in range(n_pulls):
                transport.push(zeros)   # advance every leaf's version
                fleet.drain()
                snap = transport.pull(have)
                if snap is not None:
                    have = snap[0]
                    pulled += 1
            spans = tracer.spans
            trees = rpctrace.stitch_spans(spans)
            pull_trees = [t for t in trees
                          if t["root"]["name"] == "pull"
                          and t["root"]["status"] == "ok"]
            if pulled != n_pulls:
                raise AssertionError(
                    f"only {pulled}/{n_pulls} pulls were fresh — the "
                    f"push cadence failed to mint versions"
                )
            # One stitched tree per sampled request: every pull() call
            # is sampled at 1.0 and must stitch to exactly one tree.
            if len(pull_trees) != n_pulls:
                raise AssertionError(
                    f"stitched pull trees != sampled pulls: "
                    f"{len(pull_trees)} vs {n_pulls}"
                )
            # Per-shard: serve-span p50 vs the wire_latency_s p50 the
            # same handlers recorded — two pipelines, one truth.
            serve_by_shard: Dict[str, List[float]] = {}
            for s in spans:
                if s["name"] == "serve" \
                        and s["ann"].get("route") == "/delta.bin":
                    serve_by_shard.setdefault(
                        str(s["ann"].get("shard")), []).append(s["dur_s"])
            if len(serve_by_shard) != n_shards:
                raise AssertionError(
                    f"serve spans seen for shards "
                    f"{sorted(serve_by_shard)} != {n_shards} shards"
                )
            recon = {}
            for sid, durs in serve_by_shard.items():
                span_p50 = float(np.percentile(durs, 50))
                hist = rec_tele.histogram(
                    "param_server.wire_latency_s",
                    labels={"route": "/delta.bin", "shard": sid})
                hist_p50 = hist["p50"]
                if hist_p50 is None:
                    raise AssertionError(
                        f"no wire_latency_s series for shard {sid}")
                tol = max(0.5 * hist_p50, 0.002)
                recon[sid] = {"span_p50_ms": round(span_p50 * 1e3, 3),
                              "hist_p50_ms": round(hist_p50 * 1e3, 3),
                              "spans": len(durs),
                              "hist_count": hist["count"]}
                if abs(span_p50 - hist_p50) > tol:
                    raise AssertionError(
                        f"shard {sid} serve-span p50 "
                        f"{span_p50 * 1e3:.2f}ms does not reconcile "
                        f"with wire_latency_s p50 "
                        f"{hist_p50 * 1e3:.2f}ms (tol "
                        f"{tol * 1e3:.2f}ms)"
                    )
            # Containment: a root wall must cover its slowest serve
            # hop — a tree whose hops outrun the root is mis-stitched.
            def _serves(node, acc):
                if node["name"] == "serve":
                    acc.append(float(node["dur_s"] or 0.0))
                for c in node.get("children") or []:
                    _serves(c, acc)
                return acc

            for t in pull_trees:
                hops = _serves(t["root"], [])
                if hops and t["wall_s"] < max(hops) - 1e-4:
                    raise AssertionError(
                        f"trace {t['trace_id'][:8]}: root wall "
                        f"{t['wall_s'] * 1e3:.2f}ms < slowest serve hop "
                        f"{max(hops) * 1e3:.2f}ms"
                    )

            # ---- leg 3: seeded slow shard named as critical path ----
            slow_shard, delay_s, slow_pulls = "2", 0.12, 3
            with inject(ChaosConfig(slow_shard_s={slow_shard: delay_s},
                                    seed=0)):
                for _ in range(slow_pulls):
                    transport.push(zeros)
                    fleet.drain()
                    snap = transport.pull(have)
                    if snap is not None:
                        have = snap[0]
            collector = FleetCollector.for_fleet(
                fleet, poll_interval_s=0, jsonl_path=sink)
            collector.poll()
            stitched = collector.rpc_traces()
            slow_trees = [t for t in stitched
                          if t["root"]["name"] == "pull"
                          and t["wall_s"] >= delay_s * 0.8][:slow_pulls]
            if len(slow_trees) < slow_pulls:
                raise AssertionError(
                    f"collector stitched only {len(slow_trees)} "
                    f"slow-pull trees of {slow_pulls}"
                )
            named = sum(1 for t in slow_trees
                        if str((t.get("critical") or {}).get("shard"))
                        == slow_shard)
            if named < slow_pulls:
                raise AssertionError(
                    f"slow shard {slow_shard} named as critical path in "
                    f"only {named}/{slow_pulls} traced pulls: "
                    f"{[t.get('critical') for t in slow_trees]}"
                )
            # And the CLI renders the same verdict from the sink.
            buf = io.StringIO()
            with contextlib.redirect_stdout(buf):
                rc = _timeline.main(["--rpc", sink])
            rendered = buf.getvalue()
            if rc != 0 or f"shard {slow_shard}" not in rendered \
                    or "bound by" not in rendered:
                raise AssertionError(
                    f"timeline --rpc did not name shard {slow_shard} "
                    f"(rc={rc})"
                )
            transport.close()
        finally:
            if collector is not None:
                collector.stop()
            fleet.stop()
            try:
                os.remove(sink)
            except OSError:
                pass

    # ---- the overhead gate (checked last so a failure reports with
    # the reconcile evidence already computed) -------------------------
    if overhead_pct >= 2.0:
        raise AssertionError(
            f"tracing overhead {overhead_pct:.3f}% >= 2% at default "
            f"sampling (fast path {fast_us:.2f}us + amortized sampled "
            f"chain {sampled_us:.1f}us x {rpctrace.DEFAULT_SAMPLE_RATE} "
            f"vs wire op p50 {op_us / 1e3:.2f}ms)"
        )

    return {
        "config": "rpc_trace", "unit": "% (tracing overhead)",
        "value": round(overhead_pct, 4),
        "overhead_pct": round(overhead_pct, 4),
        "fast_path_us": round(fast_us, 2),
        "sampled_chain_us": round(sampled_us, 1),
        "traced_cost_per_op_us": round(traced_cost_us, 2),
        "wire_op_p50_ms": round(op_us / 1e3, 3),
        "sample_rate_default": rpctrace.DEFAULT_SAMPLE_RATE,
        "pull_trees": len(pull_trees),
        "reconcile": recon,
        "slow_shard": {"shard": slow_shard, "delay_s": delay_s,
                       "named": named, "pulls": slow_pulls},
        "phase_s": {
            "init": round(_sp_init.duration_s, 3),
            "measure_overhead": round(_sp_overhead.duration_s, 3),
            "measure_reconcile": round(_sp_reconcile.duration_s, 3),
        },
    }


def bench_serve_online() -> dict:
    """Online serving gate (``make bench-serve``): the continuous-
    batching tier must actually beat the fixed-window tool where it
    claims to, and survive the faults it claims to — FAILS (raises)
    otherwise.

    Workload: Poisson open-loop single-row requests (seeded
    exponential interarrivals at ~2x the measured serial capacity, so
    a one-at-a-time server is genuinely overloaded — open loop:
    arrivals never wait for completions, like real users). The load
    threads are all PRE-SPAWNED and sleep to their own arrival times:
    spawning threads on the clock makes the generator the bottleneck
    and voids the comparison (measured: it halves the fast side's
    apparent throughput). The model is sized so single-row COMPUTE
    (~5ms) dominates per-request Python overhead — on a tiny model
    both legs converge on the GIL and the batching win is invisible.
    Legs run interleaved x2 and gate on MEDIANS (cpu-share rig noise
    hits both sides).

    Gates:
    - throughput at equal-or-better p99: the continuous-batching
      replica (admission queue -> coalesced bucket batches) must beat
      a serially-dispatched :class:`BatchPredictor` (the fixed-window
      tool — no admission, no coalescing) on completed rows/sec AND
      p99 request latency under the SAME arrival schedule, with zero
      failed requests on either side;
    - a seeded replica kill (``ft.chaos`` ``serve.replica`` site)
      mid-load drops ZERO requests: the router evicts the victim,
      re-routes its in-flight admissions, the tier monitor restarts
      it, and the router re-admits it — all observed in counters;
    - a mid-load weight push lands on EVERY replica within the
      staleness bound (20 poll intervals + 1s slack), and the served
      parameters equal the server's exactly after the swap;
    - drift: continuous throughput within tolerance of the newest
      prior ``serve_online`` record (``SPARKTORCH_TPU_SERVE_DRIFT_TOL``,
      default 0.5 relative — this rig's scheduler swings are real);
      skips cleanly with no prior record.
    """
    import os
    import threading

    import jax

    from sparktorch_tpu import serialize_torch_obj
    from sparktorch_tpu.ft import ChaosConfig, inject
    from sparktorch_tpu.ft.policy import FtPolicy, RestartPolicy
    from sparktorch_tpu.inference import BatchPredictor
    from sparktorch_tpu.models import ClassificationNet
    from sparktorch_tpu.net.transport import BinaryTransport
    from sparktorch_tpu.obs import Telemetry, get_telemetry
    from sparktorch_tpu.serve.infer import InferenceReplica
    from sparktorch_tpu.serve.param_server import (
        ParameterServer,
        ParamServerHttp,
    )
    from sparktorch_tpu.serve.router import InferenceTier, Router

    from sparktorch_tpu.models import MLP

    tele = get_telemetry()
    n_requests, overload = 300, 2.0
    rng = np.random.default_rng(0)

    with tele.span("bench/init") as _sp_init:
        # Throughput legs: an MLP big enough that one row costs real
        # compute (~5ms serial on this rig; batch-32 runs ~6x the
        # rows/sec of serial dispatch — the amortization continuous
        # batching exists to capture).
        module = MLP(features=[2048, 2048, 1024, 10])
        xpool = rng.normal(0, 1, (512, 512)).astype(np.float32)
        variables = module.init(jax.random.key(0), xpool[:1])
        params = variables["params"]
        # Fault/weight legs: the small classifier the param server
        # trains (recovery and staleness don't need the big model).
        clf_module = ClassificationNet(n_classes=2)
        xsmall = rng.normal(0, 1, (64, 10)).astype(np.float32)

    with tele.span("bench/compile_warmup") as _sp_warm:
        # Calibrate the SERIAL service time (the fixed-window tool's
        # capacity) on warmed compiles, then pick the arrival rate to
        # overload it: the gate must compare the designs under load,
        # not two idle servers.
        bp = BatchPredictor(module, params, chunk=32,
                            telemetry=Telemetry(run_id="serve_base"))
        bp.predict(xpool[:1])
        svc = []
        for _ in range(30):
            t0 = time.perf_counter()
            bp.predict(xpool[:1])
            svc.append(time.perf_counter() - t0)
        svc_s = float(np.median(svc))
        interarrival_s = svc_s / overload
        arrivals = np.cumsum(rng.exponential(interarrival_s, n_requests))

    def _poisson_leg(submit_fn, pool) -> dict:
        """Open-loop load: every request thread is PRE-SPAWNED, waits
        for the start gun, sleeps to its own scheduled arrival, fires,
        and records its own completion latency (arrivals never wait
        for completions). Failures are collected, never swallowed —
        the zero-drop gates read them."""
        lats: List[Optional[float]] = [None] * n_requests
        errors: list = []
        start = threading.Event()
        t_ref = [0.0]

        def _fire(i: int) -> None:
            start.wait()
            delay = arrivals[i] - (time.perf_counter() - t_ref[0])
            if delay > 0:
                time.sleep(delay)
            t0 = time.perf_counter()
            try:
                out = submit_fn(pool[i % len(pool)][None, :])
                assert out.shape[0] == 1
                lats[i] = time.perf_counter() - t0
            except Exception as e:  # noqa: BLE001 - gate counts these
                errors.append((i, f"{type(e).__name__}: {e}"))

        threads = [threading.Thread(target=_fire, args=(i,), daemon=True)
                   for i in range(n_requests)]
        for th in threads:
            th.start()
        time.sleep(0.05)  # let every thread park on the gun
        t_ref[0] = time.perf_counter()
        start.set()
        for th in threads:
            th.join(timeout=120)
        wall = time.perf_counter() - t_ref[0]
        done = [l for l in lats if l is not None]
        return {
            "wall_s": wall,
            "completed": len(done),
            "errors": len(errors),
            "error_samples": [e for _, e in errors[:3]],
            "rows_per_s": len(done) / max(wall, 1e-9),
            "p50_ms": float(np.percentile(done, 50)) * 1e3 if done else -1,
            "p99_ms": float(np.percentile(done, 99)) * 1e3 if done else -1,
        }

    def _baseline_leg() -> dict:
        # The fixed-window tool behind a serial dispatch: one
        # compiled predict per request, one at a time — exactly what
        # BatchPredictor gives an online caller (no admission queue,
        # no coalescing; concurrent callers serialize on the device
        # dispatch anyway, the lock just keeps the accounting honest).
        lock = threading.Lock()

        def submit(x):
            with lock:
                return bp.predict(x)

        return _poisson_leg(submit, xpool)

    def _continuous_leg() -> dict:
        # SAME hardware, same arrival schedule, ONE replica: the
        # throughput win must come from admission/coalescing, not
        # from extra compute.
        leg_tele = Telemetry(run_id="serve_cont")
        replica = InferenceReplica(module, params, replica_id="0",
                                   telemetry=leg_tele,
                                   buckets=(1, 8, 32),
                                   max_queue_rows=1024,
                                   warm_input=xpool[:1])
        router = Router(telemetry=leg_tele)
        router.register(replica)
        try:
            out = _poisson_leg(
                lambda x: router.submit(x, deadline_s=120.0), xpool)
            out["batches"] = leg_tele.counter_value(
                "serve.batches_total", {"replica": "0"})
            fill = leg_tele.histogram("serve.batch_fill",
                                      {"replica": "0"})
            out["batch_fill_p50"] = fill.get("p50")
            out["queue_depth_p99"] = leg_tele.histogram(
                "serve.queue_depth", {"replica": "0"}).get("p99")
            return out
        finally:
            router.stop()
            replica.stop()

    with tele.span("bench/measure") as _sp_measure:
        bases, conts = [], []
        for _ in range(2):  # interleaved: rig noise hits both legs
            bases.append(_baseline_leg())
            conts.append(_continuous_leg())

    def _median(legs, key):
        vals = [leg[key] for leg in legs if leg.get(key) is not None]
        return float(np.median(vals)) if vals else None

    base = {k: (round(_median(bases, k), 3)
                if isinstance(bases[0][k], (int, float)) else bases[0][k])
            for k in bases[0]}
    cont = {k: (round(_median(conts, k), 3)
                if isinstance(conts[0][k], (int, float)) else conts[0][k])
            for k in conts[0]}
    throughput_ratio = cont["rows_per_s"] / max(base["rows_per_s"], 1e-9)
    p99_ratio = cont["p99_ms"] / max(base["p99_ms"], 1e-9)

    # -- seeded replica kill under load --------------------------------
    with tele.span("bench/replica_kill") as _sp_kill:
        kill_tele = Telemetry(run_id="serve_kill")
        policy = FtPolicy(restart=RestartPolicy(backoff_base_s=0.02,
                                                backoff_max_s=0.1,
                                                max_restarts=3))
        clf_variables = clf_module.init(jax.random.key(0), xsmall[:1])
        tier = InferenceTier(clf_module, clf_variables["params"],
                             n_replicas=2,
                             telemetry=kill_tele, ft_policy=policy,
                             buckets=(1, 8, 32), max_queue_rows=1024,
                             warm_input=xsmall[:1],
                             probe_interval_s=0.05)
        # Deterministic victim: replica 0 carries a fat observed
        # latency so the weighted pick opens on replica 1, whose 8th
        # admission is the seeded kill.
        kill_tele.observe("serve.request_latency_s", 0.5,
                          labels={"replica": "0"})
        try:
            with inject(ChaosConfig(kill_replica_at={1: 8}),
                        telemetry=kill_tele) as inj:
                kill_leg = _poisson_leg(
                    lambda x: tier.submit(x, deadline_s=60.0), xsmall)
            kills = len([e for e in inj.events
                         if e["site"] == "serve.replica"])
            deadline = time.monotonic() + 15.0
            while (kill_tele.counter_value("router.readmissions_total",
                                           {"replica": "1"}) < 1
                   and time.monotonic() < deadline):
                time.sleep(0.05)
            evictions = kill_tele.counter_value(
                "router.evictions_total",
                {"replica": "1", "reason": "error"})
            restarts = kill_tele.counter_value(
                "serve.replica_restarts_total", {"replica": "1"})
            readmissions = kill_tele.counter_value(
                "router.readmissions_total", {"replica": "1"})
        finally:
            tier.stop()

    # -- mid-load weight push: bounded staleness + exactness -----------
    with tele.span("bench/weight_push") as _sp_push:
        poll_s = 0.05
        staleness_bound_s = 20 * poll_s + 1.0
        clf = serialize_torch_obj(
            ClassificationNet(n_classes=2), criterion="cross_entropy",
            optimizer="sgd", optimizer_params={"lr": 0.1},
            input_shape=(10,),
        )
        push_tele = Telemetry(run_id="serve_push")
        server = ParameterServer(clf, telemetry=push_tele)
        http = ParamServerHttp(server, port=0).start()
        _v, params0 = server.slot.read()
        tier = InferenceTier(clf_module, params0, n_replicas=2,
                             telemetry=push_tele,
                             buckets=(1, 8), max_queue_rows=1024,
                             warm_input=xsmall[:1],
                             probe_interval_s=0.05)
        tier.start_pullers(
            lambda: BinaryTransport(http.url, quant=None),
            poll_s=poll_s)
        stop_load = threading.Event()

        def _background_load():
            while not stop_load.is_set():
                tier.submit(xsmall[:1], deadline_s=30.0)
                time.sleep(0.005)

        loader = threading.Thread(target=_background_load, daemon=True)
        loader.start()
        try:
            time.sleep(0.3)  # pullers sync the initial version
            grads = jax.tree.map(
                lambda a: np.ones_like(np.asarray(a)), params0)
            server.push_gradients(grads, wait=True)
            pushed_version = server.slot.version
            t_push = time.monotonic()
            staleness: Dict[str, float] = {}
            deadline = t_push + staleness_bound_s + 5.0
            while (len(staleness) < len(tier.replicas)
                   and time.monotonic() < deadline):
                for rid, replica in tier.replicas.items():
                    if rid not in staleness \
                            and replica.params_version >= pushed_version:
                        staleness[rid] = time.monotonic() - t_push
                time.sleep(0.01)
            stop_load.set()
            loader.join(timeout=30)
            # Exactness: the SERVED parameters equal the pushed ones.
            _v2, server_params = server.slot.read()
            ref = np.asarray(clf_module.apply(
                {"params": server_params}, xsmall[:8]))
            push_exact = True
            for replica in tier.replicas.values():
                out = replica.infer(xsmall[:8])
                if not np.allclose(out, ref, rtol=1e-5, atol=1e-6):
                    push_exact = False
        finally:
            stop_load.set()
            tier.stop()
            http.stop()
            server.stop()

    # -- the gates ------------------------------------------------------
    if base["errors"] or cont["errors"]:
        raise AssertionError(
            f"load legs dropped requests: baseline {base['errors']} "
            f"({base['error_samples']}), continuous {cont['errors']} "
            f"({cont['error_samples']})"
        )
    # Completion counted SEPARATELY from errors: a future that is
    # never resolved raises nothing — its load thread just times out
    # — and an errors-only gate would report that orphaned request as
    # success.
    for leg_name, leg in (("baseline", base), ("continuous", cont),
                          ("replica_kill", kill_leg)):
        if leg["completed"] != n_requests:
            raise AssertionError(
                f"{leg_name} leg completed only {leg['completed']}/"
                f"{n_requests} requests with no error raised — "
                f"orphaned futures are silent drops"
            )
    if not throughput_ratio > 1.0:
        raise AssertionError(
            f"continuous batching did not beat the fixed-window "
            f"BatchPredictor on throughput: {cont['rows_per_s']:.0f} "
            f"vs {base['rows_per_s']:.0f} rows/s "
            f"(x{throughput_ratio:.2f})"
        )
    if not p99_ratio <= 1.0:
        raise AssertionError(
            f"continuous batching p99 regressed vs the fixed-window "
            f"baseline: {cont['p99_ms']:.1f} vs {base['p99_ms']:.1f} "
            f"ms (x{p99_ratio:.2f}) — the throughput win must not be "
            f"bought with latency"
        )
    if kill_leg["errors"]:
        raise AssertionError(
            f"replica-kill leg DROPPED {kill_leg['errors']} requests "
            f"({kill_leg['error_samples']}) — the router must re-route "
            f"every admission of the killed replica"
        )
    if kills < 1:
        raise AssertionError("seeded replica kill never fired")
    if evictions < 1 or restarts < 1 or readmissions < 1:
        raise AssertionError(
            f"recovery pipeline incomplete: evictions={evictions} "
            f"restarts={restarts} readmissions={readmissions}"
        )
    if len(staleness) < 2:
        raise AssertionError(
            f"mid-load weight push reached only {len(staleness)}/2 "
            f"replicas within {staleness_bound_s + 5.0:.1f}s"
        )
    max_staleness = max(staleness.values())
    if max_staleness > staleness_bound_s:
        raise AssertionError(
            f"weight-update staleness {max_staleness:.2f}s exceeds "
            f"the {staleness_bound_s:.2f}s bound"
        )
    if not push_exact:
        raise AssertionError(
            "served parameters != pushed parameters after the swap"
        )

    # -- drift gate (arms once a prior record is retained) -------------
    tol = float(os.environ.get("SPARKTORCH_TPU_SERVE_DRIFT_TOL", "0.5"))
    prior = _prior_record("serve_online", "cont_rows_per_s")
    if prior is None:
        drift = {"status": "no_prior_record", "tolerance": tol}
    else:
        prior_rate = float(prior["cont_rows_per_s"])
        drift = {
            "status": "checked", "tolerance": tol,
            "prior_ts": prior.get("ts"),
            "prior_cont_rows_per_s": round(prior_rate, 1),
            "rows_per_s_ratio": round(
                cont["rows_per_s"] / max(prior_rate, 1e-9), 3),
        }
        if cont["rows_per_s"] < prior_rate * (1.0 - tol):
            raise AssertionError(
                f"serve_online throughput regressed: "
                f"{cont['rows_per_s']:.0f} vs prior "
                f"{prior_rate:.0f} rows/s (past the {tol} relative "
                f"tolerance); drift: {drift}"
            )

    return {
        "config": "serve_online", "unit": "x (throughput ratio)",
        "value": round(throughput_ratio, 3),
        "n_requests": n_requests,
        "serial_service_ms": round(svc_s * 1e3, 3),
        "offered_rate_rps": round(1.0 / interarrival_s, 1),
        "throughput_ratio": round(throughput_ratio, 3),
        "p99_ratio": round(p99_ratio, 3),
        "cont_rows_per_s": cont["rows_per_s"],
        "baseline": base, "continuous": cont,
        "replica_kill": {**kill_leg, "kills": kills,
                         "evictions": evictions, "restarts": restarts,
                         "readmissions": readmissions},
        "weight_push": {
            "poll_s": poll_s,
            "staleness_s": {k: round(v, 3)
                            for k, v in sorted(staleness.items())},
            "staleness_bound_s": staleness_bound_s,
            "exact": push_exact,
        },
        "serve_drift": drift,
        "phase_s": {
            "init": round(_sp_init.duration_s, 3),
            "compile_warmup": round(_sp_warm.duration_s, 3),
            "measure": round(_sp_measure.duration_s, 3),
            "replica_kill": round(_sp_kill.duration_s, 3),
            "weight_push": round(_sp_push.duration_s, 3),
        },
    }


def _prior_records(config: str, field: str,
                   root: Optional[str] = None,
                   mesh: Optional[str] = None) -> List[dict]:
    """Every PRIOR round's record for ``config`` carrying ``field``,
    oldest first — scanned from the retained round artifacts
    (repo-root ``BENCH_r*.json`` / ``MULTICHIP_r*.json`` and the
    ``benchmarks/*.jsonl`` logs). ``mesh`` restricts the scan to
    records captured under the SAME layout (or predating the mesh
    field): the SPARKTORCH_TPU_TRACE_MESH=auto knob means adjacent
    rounds can capture different layouts with legitimately different
    comm budgets, and the newest same-mesh prior — not the newest
    prior outright — is the valid baseline."""
    import glob
    import os
    import re

    if root is None:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    candidates: List[tuple] = []

    def _round_of(path: str) -> int:
        m = re.search(r"_r(\d+)", os.path.basename(path))
        return int(m.group(1)) if m else -1

    # Recency key: the record's own ISO timestamp first (sortable as a
    # string; records without one sort oldest), the artifact's round
    # number as the tiebreak. NEVER the raw filename — lexicographic
    # basenames would rank any lowercase benchmarks/*.jsonl above
    # every BENCH_r*.json and compare the gate against a stale round.
    def _consider(rec, path):
        if isinstance(rec, dict) and rec.get("config") == config \
                and rec.get(field) is not None \
                and (mesh is None or rec.get("mesh") in (None, mesh)):
            candidates.append(((str(rec.get("ts") or ""),
                                _round_of(path)), rec))

    for path in sorted(glob.glob(os.path.join(root, "BENCH_r*.json"))
                       + glob.glob(os.path.join(root, "MULTICHIP_r*.json"))):
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue  # a torn artifact never blocks the bench
        parsed = doc.get("parsed") if isinstance(doc, dict) else None
        for rec in (parsed if isinstance(parsed, list) else [parsed]):
            _consider(rec, path)
    for path in sorted(glob.glob(os.path.join(root, "benchmarks",
                                              "*.jsonl"))):
        try:
            with open(path) as f:
                rows = [json.loads(line) for line in f if line.strip()]
        except (OSError, ValueError):
            continue
        for rec in rows:
            _consider(rec, path)
    return [rec for _, rec in sorted(candidates, key=lambda c: c[0])]


def _prior_record(config: str, field: str,
                  root: Optional[str] = None,
                  mesh: Optional[str] = None) -> Optional[dict]:
    """The most recent prior record (see :func:`_prior_records`).
    None when no (matching) prior exists — first armed round, the
    drift gate skips cleanly."""
    recs = _prior_records(config, field, root=root, mesh=mesh)
    return recs[-1] if recs else None


def _prior_window(config: str, field: str, k: int = 3,
                  root: Optional[str] = None,
                  mesh: Optional[str] = None) -> Optional[dict]:
    """WINDOWED drift baseline: the median of ``field`` over the
    newest ``k`` prior records, not the single newest one — the same
    judgment the collector's history tier applies to live metrics,
    applied to retained bench rounds. A drift gate comparing against
    one record inherits that record's rig luck (this rig's serve
    throughput breathes 2x hour to hour — see the PR 9 notes); the
    windowed median absorbs one outlier round. None when no prior
    exists."""
    recs = _prior_records(config, field, root=root, mesh=mesh)[-max(1, k):]
    if not recs:
        return None
    values = [float(r[field]) for r in recs]
    return {
        "median": float(np.median(values)),
        "n": len(values),
        "values": [round(v, 6) for v in values],
        "newest_ts": recs[-1].get("ts"),
    }


def _prior_comm_budget(config: str,
                       root: Optional[str] = None,
                       mesh: Optional[str] = None) -> Optional[dict]:
    """Most recent prior record of ``config`` with a comm budget —
    restricted to the same mesh layout when one is named."""
    return _prior_record(config, "comm_fraction", root, mesh=mesh)


def _prior_gang_budget(config: str,
                       root: Optional[str] = None) -> Optional[dict]:
    """Most recent prior record of ``config`` carrying a MERGED gang
    budget (``gang_comm_fraction`` — what ``gang_obs`` and multi-host
    rounds report). None until a multi-host round has recorded one."""
    return _prior_record(config, "gang_comm_fraction", root)


def _check_gang_drift(config: str, step_skew_s: float,
                      gang_comm_fraction: float) -> dict:
    """The GANG-level drift gate (PR 5 follow-up, armed): compare this
    run's merged cross-rank step skew and gang comm fraction against
    the newest prior round's gang record and FAIL when a rank started
    straggling (skew grew beyond tolerance) or gang comm grew to
    dominate the budget. Skips cleanly (``no_prior_record``) until a
    multi-host round has recorded a gang budget. Tolerances:
    ``SPARKTORCH_TPU_COMM_DRIFT_TOL`` (absolute, on the fraction —
    shared with the per-rank gate) and ``SPARKTORCH_TPU_GANG_SKEW_TOL``
    (relative growth on the skew, default 0.5 = +50%, with a 50ms
    absolute floor so microsecond-scale synthetic skews don't trip on
    rounding)."""
    import os

    tol = float(os.environ.get("SPARKTORCH_TPU_COMM_DRIFT_TOL", "0.25"))
    skew_tol = float(os.environ.get("SPARKTORCH_TPU_GANG_SKEW_TOL", "0.5"))
    prior = _prior_gang_budget(config)
    if prior is None:
        return {"status": "no_prior_record", "tolerance": tol,
                "skew_tolerance": skew_tol}
    prior_cf = float(prior["gang_comm_fraction"])
    prior_skew = float(prior.get("gang_step_skew_s", 0.0))
    skew_limit = prior_skew * (1.0 + skew_tol) + 0.05
    drift = {
        "status": "checked",
        "tolerance": tol,
        "skew_tolerance": skew_tol,
        "prior_ts": prior.get("ts"),
        "prior_gang_comm_fraction": round(prior_cf, 4),
        "prior_gang_step_skew_s": round(prior_skew, 6),
        "gang_comm_fraction_delta": round(gang_comm_fraction - prior_cf, 4),
        "gang_step_skew_delta_s": round(step_skew_s - prior_skew, 6),
    }
    if step_skew_s > skew_limit:
        raise AssertionError(
            f"{config}: gang step skew regressed "
            f"{prior_skew:.4f}s -> {step_skew_s:.4f}s (past the "
            f"{skew_limit:.4f}s limit) — a rank is straggling; "
            f"drift: {drift}"
        )
    if gang_comm_fraction - prior_cf > tol:
        raise AssertionError(
            f"{config}: gang comm_fraction regressed "
            f"{prior_cf:.3f} -> {gang_comm_fraction:.3f} "
            f"(comm grew beyond the {tol} tolerance); drift: {drift}"
        )
    return drift


def _check_comm_drift(config: str, comm_fraction: float,
                      overlap_fraction: float,
                      mesh: Optional[str] = None) -> dict:
    """The comm-fraction drift gate (ROADMAP follow-up, armed): now
    that ``sharded_trace`` and ``moe_lm`` record ``comm_budget`` every
    round, compare this run's fractions against the previous round's
    record and FAIL (AssertionError -> ``make bench-trace`` fails)
    when an overlap was lost (overlap_fraction collapsed — e.g. a
    remat change serializing the dp all-reduce) or comm grew to
    dominate the step. Skips cleanly when no prior record exists.
    Tolerance is absolute on the fractions (default 0.25 — generous
    for CPU-rig jitter; tighten via SPARKTORCH_TPU_COMM_DRIFT_TOL on
    stable hardware). ``mesh`` (when the config records one — the
    SPARKTORCH_TPU_TRACE_MESH=auto knob means different rounds can
    capture different LAYOUTS) guards the baseline: the prior scan is
    restricted to the newest record captured under the SAME mesh
    (records predating the mesh field compare as before), so an
    auto-mode round can neither raise a fake regression against a
    tp2 baseline nor mask a real one — and interleaved tp2/auto
    rounds still each find their own valid baseline instead of
    skipping forever. Returns the drift record the bench attaches."""
    import os

    tol = float(os.environ.get("SPARKTORCH_TPU_COMM_DRIFT_TOL", "0.25"))
    prior = _prior_comm_budget(config, mesh=mesh)
    if prior is None:
        return {"status": "no_prior_record", "tolerance": tol,
                "mesh": mesh}
    prior_cf = float(prior["comm_fraction"])
    prior_of = float(prior.get("overlap_fraction", 0.0))
    drift = {
        "status": "checked",
        "tolerance": tol,
        "prior_ts": prior.get("ts"),
        "prior_comm_fraction": round(prior_cf, 4),
        "prior_overlap_fraction": round(prior_of, 4),
        "comm_fraction_delta": round(comm_fraction - prior_cf, 4),
        "overlap_fraction_delta": round(overlap_fraction - prior_of, 4),
    }
    if prior_of - overlap_fraction > tol:
        raise AssertionError(
            f"{config}: overlap_fraction regressed "
            f"{prior_of:.3f} -> {overlap_fraction:.3f} "
            f"(lost overlap beyond the {tol} tolerance) — a comm that "
            f"was hidden under compute is now exposed; drift: {drift}"
        )
    if comm_fraction - prior_cf > tol:
        raise AssertionError(
            f"{config}: comm_fraction regressed "
            f"{prior_cf:.3f} -> {comm_fraction:.3f} "
            f"(comm grew beyond the {tol} tolerance); drift: {drift}"
        )
    return drift


def bench_sharded_trace() -> dict:
    """Trace-attribution gate (``make bench-trace``): capture an XLA
    profile of the GSPMD sharded trainer, machine-read it offline
    (:mod:`sparktorch_tpu.obs.xprof`), and FAIL unless

    - the analysis finds >=1 collective event (on any multi-device
      backend — GSPMD must have inserted tp/dp collectives),
    - the per-step slice wall reconciles with the bus's
      ``train_sharded/step`` span wall within tolerance (the step
      annotations live INSIDE those spans), and
    - a real ``/metrics`` scrape equals the JSONL telemetry dump for
      every published ``xprof.*`` metric (capture -> analyze ->
      publish round-trip, one source of truth).

    The record reports the comm/compute budget the capture exposed:
    ``comm_s`` / ``comm_fraction`` / ``overlap_fraction`` plus the
    per-family breakdown and top ops."""
    import tempfile

    import jax

    from sparktorch_tpu.models import SequenceClassifier, tiny_transformer
    from sparktorch_tpu.native.gang import GangMetricsExporter
    from sparktorch_tpu.obs import (
        Telemetry,
        parse_prometheus,
        read_jsonl,
        scrape_text,
    )
    from sparktorch_tpu.obs.prom import sanitize_name
    from sparktorch_tpu.parallel.compat import set_mesh as _set_mesh
    from sparktorch_tpu.parallel.mesh import MeshConfig, build_mesh
    from sparktorch_tpu.train.sharded import (
        create_sharded_state,
        make_sharded_train_step,
        shard_batch,
    )
    from sparktorch_tpu.utils.data import DataBatch
    from sparktorch_tpu.utils.serde import ModelSpec

    # This config executes a collective-bearing GSPMD program; the
    # persistent compile cache is disarmed for it on CPU (executing a
    # deserialized collective executable segfaults jax 0.4.37 CPU —
    # see tests/conftest.py / ROADMAP).
    old_cache = jax.config.jax_compilation_cache_dir
    if jax.default_backend() == "cpu":
        jax.config.update("jax_compilation_cache_dir", None)
    try:
        tele = Telemetry(run_id="bench_sharded_trace")
        devices = jax.devices()
        n_dev = len(devices)
        steps = 6
        with tele.span("bench/data") as _sp_data:
            rng = np.random.default_rng(0)
            bsz = 4 * n_dev
            batch = DataBatch(
                x=np.asarray(rng.integers(0, 256, (bsz, 16)).astype(np.int32)),
                y=np.asarray(rng.integers(0, 2, (bsz,)).astype(np.int32)),
                w=np.ones((bsz,), np.float32),
            )
        with tele.span("bench/init") as _sp_init:
            import os

            module = SequenceClassifier(tiny_transformer())
            spec = ModelSpec(module=module, loss="cross_entropy",
                             optimizer="adam", optimizer_params={"lr": 1e-3})
            tx = spec.make_optimizer()
            # Mesh knob: tp2 (default — tensor-parallel all-reduces
            # INSIDE the step, beside the dp gradient reduction), or
            # "auto" to let the trace-guided tuner pick the layout
            # (SPARKTORCH_TPU_TRACE_MESH=auto make bench-trace).
            mesh_knob = os.environ.get("SPARKTORCH_TPU_TRACE_MESH", "tp2")
            if mesh_knob not in ("tp2", "auto"):
                raise AssertionError(
                    f"SPARKTORCH_TPU_TRACE_MESH={mesh_knob!r}: "
                    f"use 'tp2' or 'auto'"
                )
            if mesh_knob == "auto":
                from sparktorch_tpu.parallel.tune import GSPMD_AXES, autotune

                # GSPMD_AXES: this leg builds a GSPMD step below — a
                # pp>1 schedule winner would not fit it (the pp space
                # has its own gate, bench-pp-tune).
                tuned = autotune(spec, batch, devices, steps=3,
                                 measure_top_k=3, telemetry=tele,
                                 axes=GSPMD_AXES)
                mesh = build_mesh(tuned.best_config(), devices)
            else:
                mesh = build_mesh(MeshConfig(tp=2) if n_dev % 2 == 0
                                  else MeshConfig(), devices)
            # Recorded from the mesh actually built — never the knob
            # (the tp2 fallback on an odd rig is pure dp, and the
            # retained record must say so).
            from sparktorch_tpu.parallel.tune import mesh_label

            mesh_ran = mesh_label(dict(mesh.shape))
            state, shardings = create_sharded_state(
                spec, mesh, jax.random.key(0), sample_x=batch.x[:1], tx=tx,
            )
        with tempfile.TemporaryDirectory() as profile_dir:
            step = make_sharded_train_step(
                module.apply, spec.loss_fn(), tx, mesh, shardings,
                profile_dir=profile_dir, telemetry=tele,
            )
            sharded = shard_batch(batch, mesh)
            with tele.span("bench/compile_warmup") as _sp_warm:
                # Compile OUTSIDE the capture (run.jitted directly, no
                # annotation/span), so the trace holds steady steps.
                with _set_mesh(mesh):
                    state, m = step.jitted(state, sharded)
                _sp_warm.sync(m.loss)
            with tele.span("bench/measure") as _sp_measure:
                for _ in range(steps):
                    state, metrics = step(state, sharded)
                    # Block per step so each step's device work drains
                    # inside its attribution slice.
                    jax.block_until_ready(metrics.loss)
                _sp_measure.synced = True
            analysis = step.finish()

        # ---- gates -------------------------------------------------------
        if analysis is None or analysis.n_device_events == 0:
            raise AssertionError(
                "trace analysis found no device events — the runtime "
                "emitted no usable capture"
            )
        if n_dev > 1 and analysis.n_collective_events < 1:
            raise AssertionError(
                f"no collectives found in a {n_dev}-device sharded step "
                f"(families seen: {analysis.family_counts()})"
            )
        # Span paths are slash-joined by nesting: the step spans ran
        # inside this config's bench/measure span.
        span = tele.span_rollup("bench/measure/train_sharded/step")
        step_wall = analysis.wall_s
        span_wall = span["sum"]
        # The annotations sit INSIDE the spans: their wall can never
        # exceed the span wall (beyond clock jitter), and must account
        # for most of it (the span adds only set_mesh + bookkeeping).
        tol = max(0.5 * span_wall, 0.02)
        if not (0 < step_wall <= span_wall + 0.005) or \
                abs(span_wall - step_wall) > tol:
            raise AssertionError(
                f"step-slice wall {step_wall:.4f}s does not reconcile "
                f"with bus span wall {span_wall:.4f}s (tol {tol:.4f}s)"
            )
        if len(analysis.steps) != steps or span["count"] != steps:
            raise AssertionError(
                f"expected {steps} steps: trace has "
                f"{len(analysis.steps)}, bus has {span['count']}"
            )

        # ---- /metrics scrape == JSONL dump parity ------------------------
        with GangMetricsExporter(telemetry=tele) as exporter:
            scraped = parse_prometheus(scrape_text(exporter.url + "/metrics"))
        with tempfile.TemporaryDirectory() as d:
            import os

            dump_path = os.path.join(d, "telemetry.jsonl")
            snap = tele.dump(dump_path)
            (snap_read,) = read_jsonl(dump_path)
        mismatches = []
        for flat, val in snap["counters"].items():
            if not flat.startswith("xprof."):
                continue
            name, _, labels = flat.partition("{")
            key = "sparktorch_" + sanitize_name(name)
            if labels:
                k, _, v = labels[:-1].partition("=")
                key += f'{{{k}="{v}"}}'
            got = scraped.get(key)
            if got != val or snap_read["counters"].get(flat) != val:
                mismatches.append((flat, val, got,
                                   snap_read["counters"].get(flat)))
        n_hists = 0
        for flat, roll in snap["histograms"].items():
            if not flat.startswith("xprof."):
                continue
            n_hists += 1
            name, _, labels = flat.partition("{")
            key = "sparktorch_" + sanitize_name(name)
            lbl = ""
            if labels:
                k, _, v = labels[:-1].partition("=")
                lbl = f'{{{k}="{v}"}}'
            if scraped.get(f"{key}_count{lbl}") != float(roll["count"]) or \
                    snap_read["histograms"][flat]["count"] != roll["count"]:
                mismatches.append((flat, roll["count"]))
        if mismatches or n_hists == 0:
            raise AssertionError(
                f"xprof /metrics scrape vs JSONL dump mismatch "
                f"(histograms seen: {n_hists}): {mismatches}"
            )

        # ---- comm-fraction drift gate (vs the previous round) ------------
        comm_drift = _check_comm_drift(
            "sharded_trace", analysis.comm_fraction,
            analysis.overlap_fraction, mesh=mesh_ran,
        )

        return {
            "config": "sharded_trace", "unit": "comm_fraction",
            "value": round(analysis.comm_fraction, 4),
            "comm_fraction": round(analysis.comm_fraction, 4),
            "overlap_fraction": round(analysis.overlap_fraction, 4),
            "comm_s": round(analysis.comm_s, 6),
            "compute_s": round(analysis.compute_s, 6),
            "collective_s": {k: round(v, 6)
                             for k, v in analysis.family_s().items()},
            "collective_counts": analysis.family_counts(),
            "n_collective_events": analysis.n_collective_events,
            "n_steps": len(analysis.steps),
            "n_chips": n_dev,
            "mesh": mesh_ran,
            "reconcile": {"steps_wall_s": round(step_wall, 6),
                          "span_wall_s": round(span_wall, 6)},
            "top_ops": analysis.top_ops[:5],
            "scrape_parity": "ok",
            "comm_drift": comm_drift,
            "phase_s": {
                "data": round(_sp_data.duration_s, 3),
                "init": round(_sp_init.duration_s, 3),
                "compile_warmup": round(_sp_warm.duration_s, 3),
                "measure": round(_sp_measure.duration_s, 3),
                "comm_s": round(analysis.comm_s, 6),
            },
        }
    finally:
        if jax.default_backend() == "cpu":
            jax.config.update("jax_compilation_cache_dir", old_cache)


def bench_mesh_tune() -> dict:
    """Mesh auto-tuner gate (``make bench-tune``): run the trace-guided
    tuner (:mod:`sparktorch_tpu.parallel.tune`) on a transformer
    workload over the local rig, then referee it against an EXHAUSTIVE
    measurement of the same candidate space, and FAIL unless

    - the tuner's chosen mesh matches the exhaustively-measured winner,
      or sits within tolerance (``SPARKTORCH_TPU_TUNE_TOL``, default
      10%) of its step wall — compared on the exhaustive pass's OWN
      numbers so run-to-run jitter can't fake a pass;
    - the prune step eliminated >=1 candidate WITHOUT executing it,
      and never eliminated the measured winner — judged at the same
      tolerance (a pruned candidate materially faster than the chosen
      config fails; one inside the noise between the top entries does
      not, because there the "winner" label is itself jitter);
    - the tuner stayed under its execution budget: profiled steps
      executed (warmup captures included) <=
      measure_top_k x steps x (repeats + warmup rounds), and the
      search wall under ``SPARKTORCH_TPU_TUNE_BUDGET_S``
      (default 600s);
    - the full ranking + prune log round-trips through the
      ``tune_result.json`` artifact.

    Scope: the GSPMD mesh zoo (axes=GSPMD_AXES). The pp x schedule
    dimension has its own referee with pipeline-trainer measurement —
    ``make bench-pp-tune``.

    The record reports both rankings, the prune decisions, and the
    chosen budget."""
    import os
    import tempfile

    import jax

    from sparktorch_tpu.models import SequenceClassifier, tiny_transformer
    from sparktorch_tpu.obs import Telemetry
    from sparktorch_tpu.parallel.tune import GSPMD_AXES, TuneResult, autotune
    from sparktorch_tpu.utils.data import DataBatch
    from sparktorch_tpu.utils.serde import ModelSpec

    # Same CPU compile-cache disarm as sharded_trace: candidates
    # execute collective-bearing GSPMD programs (see tests/conftest.py).
    old_cache = jax.config.jax_compilation_cache_dir
    if jax.default_backend() == "cpu":
        jax.config.update("jax_compilation_cache_dir", None)
    try:
        t0 = time.perf_counter()
        tele = Telemetry(run_id="bench_mesh_tune")
        devices = jax.devices()
        n_dev = len(devices)
        rng = np.random.default_rng(0)
        bsz = 8 * n_dev
        seq = 32
        batch = DataBatch(
            x=np.asarray(rng.integers(0, 256, (bsz, seq)).astype(np.int32)),
            y=np.asarray(rng.integers(0, 2, (bsz,)).astype(np.int32)),
            w=np.ones((bsz,), np.float32),
        )
        # Big enough that real layout differences beat this rig's
        # scheduler jitter (tiny models drown in it — same sizing
        # lesson as the fleet bench): ~50-200ms steps, not ~5ms.
        module = SequenceClassifier(tiny_transformer(
            d_model=256, d_ff=1024, max_len=seq))
        spec = ModelSpec(module=module, loss="cross_entropy",
                         optimizer="adam", optimizer_params={"lr": 1e-3})
        steps, repeats, top_k = 4, 3, 4

        # ---- the tuner under test ----------------------------------------
        with tempfile.TemporaryDirectory() as td:
            artifact = os.path.join(td, "tune_result.json")
            tuned = autotune(
                spec, batch, devices, steps=steps, repeats=repeats,
                measure_top_k=top_k, artifact_path=artifact,
                telemetry=tele, axes=GSPMD_AXES,
            )
            # Artifact round-trip: the ranking and prune log must
            # survive the JSON (what `mesh="auto"` consumers read).
            loaded = TuneResult.load(artifact)
        if loaded.to_dict() != tuned.to_dict():
            raise AssertionError("tune_result.json round-trip mismatch")
        if not tuned.to_dict()["ranking"]:
            raise AssertionError("tuner emitted no ranking")
        pruned = tuned.pruned()
        if not pruned:
            raise AssertionError(
                "prune step eliminated no candidate — the analytic "
                "comm model did no work"
            )
        if any(c.measured for c in pruned):
            raise AssertionError("a pruned candidate was executed")

        # ---- tuner execution budget --------------------------------------
        budget_s = float(os.environ.get("SPARKTORCH_TPU_TUNE_BUDGET_S",
                                        "600"))
        # The step budget counts EVERY profiled step the tuner ran —
        # warmup captures included (they execute; discarding their
        # scores doesn't refund their cost).
        step_budget = top_k * steps * (repeats + tuned.warmup_rounds)
        if tuned.executed_steps_total > step_budget:
            raise AssertionError(
                f"tuner executed {tuned.executed_steps_total} profiled "
                f"steps > budget {top_k} x {steps} x "
                f"({repeats} + {tuned.warmup_rounds} warmup)"
            )
        if tuned.wall_s > budget_s:
            raise AssertionError(
                f"tuner wall {tuned.wall_s:.1f}s over the {budget_s:.0f}s "
                f"budget"
            )

        # ---- the exhaustive referee --------------------------------------
        jax.clear_caches()
        gc.collect()
        exhaustive = autotune(
            spec, batch, devices, steps=steps, repeats=repeats,
            exhaustive=True, telemetry=tele, axes=GSPMD_AXES,
        )
        ex_ranked = exhaustive.ranking()
        ex_by_label = {c.label: c for c in ex_ranked}
        winner = ex_ranked[0]
        chosen_label = tuned.best_label

        tol = float(os.environ.get("SPARKTORCH_TPU_TUNE_TOL", "0.10"))
        chosen_ex = ex_by_label.get(chosen_label)
        if chosen_ex is None:
            raise AssertionError(
                f"chosen mesh {chosen_label} missing from the exhaustive "
                f"measurement ({sorted(ex_by_label)})"
            )
        winner_wall = float(winner.measured["step_wall_s"])
        chosen_wall = float(chosen_ex.measured["step_wall_s"])
        if chosen_label != winner.label and \
                chosen_wall > winner_wall * (1.0 + tol):
            raise AssertionError(
                f"tuner chose {chosen_label} "
                f"({chosen_wall * 1e3:.2f}ms/step on the exhaustive rig) "
                f"but the exhaustive winner is {winner.label} "
                f"({winner_wall * 1e3:.2f}ms/step) — "
                f"{(chosen_wall / winner_wall - 1) * 100:.1f}% slower, "
                f"over the {tol * 100:.0f}% tolerance"
            )
        # The prune must never eliminate the measured winner — judged
        # at the same tolerance, because on this rig the top entries
        # sit inside each other's noise and the "winner" identity is
        # a coin flip between them: a pruned candidate is a violation
        # when the exhaustive pass shows it MATERIALLY better than
        # what the tuner chose.
        materially_better = [
            c for c in pruned
            if c.label in ex_by_label
            and float(ex_by_label[c.label].measured["step_wall_s"])
            < chosen_wall / (1.0 + tol)
        ]
        if materially_better:
            raise AssertionError(
                f"the prune step eliminated candidate(s) materially "
                f"faster than the chosen {chosen_label} "
                f"({chosen_wall * 1e3:.2f}ms): "
                + ", ".join(
                    f"{c.label} ({float(ex_by_label[c.label].measured['step_wall_s']) * 1e3:.2f}ms)"
                    for c in materially_better)
                + f" — the comm model mis-ranked the space "
                f"(predicted order: "
                f"{[c.label for c in tuned.candidates]})"
            )

        return {
            "config": "mesh_tune", "unit": "chosen step wall vs best (x)",
            "value": round(chosen_wall / winner_wall, 4),
            "chosen": chosen_label,
            "exhaustive_winner": winner.label,
            "chosen_wall_ms": round(chosen_wall * 1e3, 3),
            "winner_wall_ms": round(winner_wall * 1e3, 3),
            "tolerance": tol,
            "n_candidates": len(tuned.candidates),
            "n_pruned": len(pruned),
            "n_measured_tuner": len(tuned.ranking()),
            "rounds_run": tuned.rounds_run,
            "early_stopped": tuned.early_stopped,
            "noise_floor_ms": round(tuned.noise_floor_s * 1e3, 3),
            "tuner_wall_s": round(tuned.wall_s, 1),
            "exhaustive_wall_s": round(exhaustive.wall_s, 1),
            "tuner_ranking": tuned.to_dict()["ranking"],
            "exhaustive_ranking": [
                {"mesh": c.label,
                 "wall_ms": round(float(c.measured["step_wall_s"]) * 1e3, 3),
                 "exposed": round(float(
                     c.measured["exposed_comm_fraction"]), 3)}
                for c in ex_ranked
            ],
            "pruned": [{"mesh": c.label, "reason": c.reason}
                       for c in pruned],
            "n_chips": n_dev,
            "wall_s": round(time.perf_counter() - t0, 1),
        }
    finally:
        if jax.default_backend() == "cpu":
            jax.config.update("jax_compilation_cache_dir", old_cache)


def _synthetic_rank_trace(rank: int, steps: int = 2) -> dict:
    """A deterministic per-rank Chrome-trace dict: each step has one
    marker, one compute fusion, one all-reduce — with rank-dependent
    timings so the merged gang budget has REAL cross-rank skew to
    gate on (rank r's step walls are (1 + r/4)x rank 0's)."""
    events = []
    scale = 1.0 + rank / 4.0
    t = 1000.0
    for s in range(steps):
        wall = 1000.0 * scale
        events.append({"ph": "X", "pid": 1, "tid": 1, "name": "train_step",
                       "ts": t, "dur": wall,
                       "args": {"step_num": str(s)}})
        events.append({"ph": "X", "pid": 1, "tid": 2, "name": f"fusion.{s}",
                       "ts": t + 50, "dur": 600 * scale})
        events.append({"ph": "X", "pid": 1, "tid": 3,
                       "name": f"all-reduce.{s}",
                       "ts": t + 400, "dur": 400 * scale})
        t += wall
    return {"traceEvents": events}


def bench_gang_obs(n_ranks: int = 3) -> dict:
    """Gang-observability gate (``make bench-gang-obs``): spin N local
    rank exporters, run the fleet collector over them, and FAIL unless

    - the collector's merged scrape carries EVERY per-rank series with
      ``rank``/``host`` labels, and the merged values reconcile with
      the per-rank scrapes (each labeled series equals its rank's own
      scrape; the cross-rank sum equals the sum of per-rank sums);
    - the merged xprof gang budget reconciles with the per-rank
      analyses: per-family comm seconds SUM, per-step walls MAX,
      cross-rank step skew >= 0 (and > 0 here — the synthetic ranks
      are deliberately skewed);
    - a seeded TRUNCATED capture (more steps annotated on the bus than
      markers in the trace) trips the ``xprof.capture_truncated``
      warning exactly once, and a complete capture trips nothing.

    Backend-free (no jax device work): this is the observability
    plane's own gate, runnable on any CI box."""
    import os
    import tempfile

    from sparktorch_tpu.native.gang import GangMetricsExporter
    from sparktorch_tpu.obs import (
        FleetCollector,
        Telemetry,
        analyze_trace,
        mint_run_id,
        parse_prometheus,
        scrape_json,
        scrape_text,
    )
    from sparktorch_tpu.obs.heartbeat import HeartbeatEmitter
    from sparktorch_tpu.obs.xprof import analyze_and_publish

    t0 = time.perf_counter()
    run_id = mint_run_id("bench-gang-obs")
    analyses = []
    exporters = []
    collector = None
    with tempfile.TemporaryDirectory() as hb_dir:
        try:
            for r in range(n_ranks):
                tele = Telemetry(run_id=run_id)
                # Distinct per-rank counter values, so sum/match gates
                # can't pass by accident.
                tele.counter("bench.gang_obs_ticks", r + 1)
                analysis = analyze_trace(_synthetic_rank_trace(r))
                analysis.publish(tele)
                analyses.append(analysis)
                HeartbeatEmitter(hb_dir, rank=r, telemetry=tele,
                                 run_id=run_id).notify_step(10 * (r + 1))
                exporters.append(GangMetricsExporter(
                    heartbeat_dir=hb_dir, telemetry=tele).start())

            collector = FleetCollector(
                {r: exp.url for r, exp in enumerate(exporters)},
                run_id=run_id, poll_interval_s=0,
            ).start(poll_loop=False)
            collector.poll()

            # ---- gate 1: merged scrape vs per-rank scrapes ---------------
            rank_scrapes = [parse_prometheus(scrape_text(e.url + "/metrics"))
                            for e in exporters]
            merged_scrape = parse_prometheus(
                scrape_text(collector.url + "/metrics"))
            host = "127.0.0.1"
            tick = "sparktorch_bench_gang_obs_ticks"
            merged_sum = 0.0
            for r, scrape in enumerate(rank_scrapes):
                own = scrape.get(tick)
                labeled = merged_scrape.get(
                    f'{tick}{{host="{host}",rank="{r}"}}')
                if own != float(r + 1) or labeled != own:
                    raise AssertionError(
                        f"rank {r}: merged series {labeled} != per-rank "
                        f"scrape {own}"
                    )
                merged_sum += labeled
            if merged_sum != sum(r + 1 for r in range(n_ranks)):
                raise AssertionError(
                    f"merged rank-labeled sum {merged_sum} != "
                    f"{sum(r + 1 for r in range(n_ranks))}"
                )
            # Every rank-originated series in the merged view must
            # carry a rank label (collector-own series are exempt).
            merged_snap = scrape_json(collector.url + "/telemetry")
            unlabeled = [
                k for section in ("counters", "gauges", "histograms")
                for k in merged_snap.get(section, {})
                if not k.startswith(("collector.", "xprof.gang_"))
                and "rank=" not in k
            ]
            if unlabeled:
                raise AssertionError(
                    f"merged series missing rank labels: {unlabeled[:5]}"
                )

            # ---- gate 2: gang budget reconciles with per-rank ------------
            gang = scrape_json(collector.url + "/gang")
            xp = gang.get("xprof")
            if not xp or xp.get("n_ranks") != n_ranks:
                raise AssertionError(f"gang xprof missing/short: {xp}")
            fam_sum = {}
            for a in analyses:
                for fam, sec in a.family_s().items():
                    fam_sum[fam] = fam_sum.get(fam, 0.0) + sec
            for fam, sec in fam_sum.items():
                got = xp["collective_s"].get(fam, 0.0)
                if abs(got - sec) > 1e-9:
                    raise AssertionError(
                        f"family {fam}: gang {got} != sum {sec}"
                    )
            for i, step in enumerate(xp["steps"]):
                walls = [a.steps[i].wall_s for a in analyses]
                if abs(step["wall_s"] - max(walls)) > 1e-9:
                    raise AssertionError(
                        f"step {i}: gang wall {step['wall_s']} != "
                        f"max {max(walls)}"
                    )
                if step["skew_s"] < 0 or \
                        abs(step["skew_s"]
                            - (max(walls) - min(walls))) > 1e-9:
                    raise AssertionError(
                        f"step {i}: skew {step['skew_s']} != "
                        f"{max(walls) - min(walls)}"
                    )
            if not xp["step_skew_s"] > 0:
                raise AssertionError(
                    "synthetic ranks are skewed but gang skew is 0"
                )
            hb = gang.get("heartbeats", {})
            if hb.get("n_ranks") != n_ranks or \
                    hb.get("step_skew") != 10 * (n_ranks - 1):
                raise AssertionError(f"merged heartbeat table wrong: {hb}")
            run_ids = set(gang.get("run_ids", {}).values())
            if run_ids != {run_id}:
                raise AssertionError(
                    f"run_id correlation broken: {run_ids} != {{{run_id}}}"
                )

            # ---- gate 3: truncation warning, exactly once ----------------
            trunc_tele = Telemetry(run_id="gang_obs_trunc")
            with tempfile.TemporaryDirectory() as td:
                path = os.path.join(td, "host0.trace.json")
                with open(path, "w") as f:
                    json.dump(  # lint-obs: ok (synthetic trace fixture)
                        _synthetic_rank_trace(0, steps=2), f)
                # Seeded truncation: 4 steps annotated on the bus, only
                # 2 markers survived in the capture.
                analyze_and_publish(td, telemetry=trunc_tele,
                                    expected_steps=4)
                tripped = trunc_tele.counter_value(
                    "xprof.capture_truncated_total")
                if tripped != 1:
                    raise AssertionError(
                        f"truncation warning tripped {tripped}x, want 1"
                    )
                # A COMPLETE capture must not trip it.
                analyze_and_publish(td, telemetry=trunc_tele,
                                    expected_steps=2)
                if trunc_tele.counter_value(
                        "xprof.capture_truncated_total") != 1:
                    raise AssertionError(
                        "complete capture tripped the truncation warning"
                    )
        finally:
            if collector is not None:
                collector.stop()
            for exp in exporters:
                exp.stop()

    # ---- gang drift gate (vs the previous round's gang record) -------
    gang_drift = _check_gang_drift(
        "gang_obs", float(xp["step_skew_s"]), float(xp["comm_fraction"]),
    )

    return {
        "config": "gang_obs", "unit": "ranks merged",
        "value": n_ranks,
        "n_ranks": n_ranks,
        "run_id": run_id,
        "gang_step_skew_s": round(float(xp["step_skew_s"]), 6),
        "gang_comm_s": round(float(xp["comm_s"]), 6),
        "gang_comm_fraction": round(float(xp["comm_fraction"]), 4),
        "gang_drift": gang_drift,
        "merged_series": sum(
            len(merged_snap.get(s, {}))
            for s in ("counters", "gauges", "histograms")
        ),
        "truncation_trips": 1,
        "scrape_reconciled": True,
        "wall_s": round(time.perf_counter() - t0, 3),
    }


def bench_hogwild_chaos_soak(rounds: int = 4, iters: int = 16,
                             freeze_rounds: int = 2,
                             worker_steps: int = 60) -> dict:
    """Chaos SOAK gate (``make bench-chaos-soak``): a seeded random
    kill/freeze/drop schedule over many supervised rounds — the
    multi-fault recovery races ``bench-chaos``'s single kill cannot
    catch. Two legs:

    - **hogwild leg** (kills + connection drops): each round runs
      ``train_async`` over real sockets under a random schedule —
      maybe kill a random worker at a random step, drop 0-2 keep-alive
      connections. Every round must complete with restart count ==
      that round's injected kills and an EXACT record count (a killed
      attempt flushes nothing; the rerun repays it — no double
      counting).
    - **freeze leg** (stall preemption): supervised heartbeat-emitting
      workers where a random rank's first attempt goes silent mid-run;
      the barrier deadline must preempt it (cooperatively — the worker
      polls its cancel event) and the restarted attempt must finish.

    FAILS (raises) on any mismatch: restarts != kills,
    stall preemptions != freezes, lost/duplicated records."""
    import tempfile
    import threading
    import time as _time

    import jax

    from sparktorch_tpu.ft import ChaosConfig, FtPolicy, RestartPolicy, inject
    from sparktorch_tpu.ft.policy import BarrierPolicy
    from sparktorch_tpu.ft.supervisor import Supervisor, ThreadWorker
    from sparktorch_tpu.models import MnistMLP
    from sparktorch_tpu.obs import Telemetry
    from sparktorch_tpu.obs.heartbeat import HeartbeatEmitter
    from sparktorch_tpu.train.hogwild import train_async
    from sparktorch_tpu.utils.serde import ModelSpec

    rng = np.random.default_rng(7)
    tele = Telemetry(run_id="bench_chaos_soak")
    t_start = time.perf_counter()

    # ---- hogwild leg: kills + drops over real sockets --------------------
    n_workers = len(jax.devices())
    spec = ModelSpec(module=MnistMLP(), loss="cross_entropy",
                     optimizer="adam", optimizer_params={"lr": 1e-3},
                     input_shape=(784,))
    x = rng.normal(0, 1, (1024, 784)).astype(np.float32)
    y = rng.integers(0, 10, (1024,)).astype(np.int32)
    policy = FtPolicy(restart=RestartPolicy(max_restarts=2,
                                            backoff_base_s=0.05), seed=0)
    train_async(spec, x, labels=y, iters=4, mini_batch=64, seed=0)  # warmup

    kills_total = drops_total = 0
    per_round = []
    for r in range(rounds):
        kills = {}
        if rng.random() < 0.75:
            kills[int(rng.integers(0, n_workers))] = int(rng.integers(2, 8))
        drops = int(rng.integers(0, 3))
        cfg = ChaosConfig(kill_worker_at=kills, drop_connections=drops,
                          seed=r)
        with inject(cfg, telemetry=tele) as inj:
            result = train_async(spec, x, labels=y, iters=iters,
                                 mini_batch=64, seed=r, transport="http",
                                 supervise=True, ft_policy=policy,
                                 telemetry=tele)
        restarts = (result.summary or {}).get("ft", {}).get(
            "restarts_total", 0)
        fired = [e["site"] for e in inj.events]
        if restarts != len(kills):
            raise AssertionError(
                f"soak round {r}: {restarts} restarts != "
                f"{len(kills)} injected kills (chaos events: {fired})"
            )
        if fired.count("worker.step") != len(kills):
            raise AssertionError(
                f"soak round {r}: kill schedule {kills} but fired {fired}"
            )
        # Exact records — the no-double-counting invariant: a killed
        # attempt flushes nothing, the restarted attempt reruns the
        # whole round assignment.
        if len(result.metrics) != iters * n_workers:
            raise AssertionError(
                f"soak round {r}: {len(result.metrics)} records != "
                f"{iters * n_workers} expected"
            )
        kills_total += len(kills)
        drops_total += fired.count("transport.request")
        per_round.append({"round": r, "kills": list(kills.items()),
                          "drops": fired.count("transport.request"),
                          "restarts": int(restarts)})

    restarts_bus = sum(
        v for k, v in tele.snapshot()["counters"].items()
        if k.startswith("ft_restarts_total")
    )
    if restarts_bus != kills_total:
        raise AssertionError(
            f"bus ft_restarts_total {restarts_bus} != {kills_total} "
            "injected kills across the soak (double-counted restarts?)"
        )

    # ---- freeze leg: stall-preempted heartbeats through the supervisor --
    freezes_total = 0
    for r in range(freeze_rounds):
        freeze_rank = int(rng.integers(0, 3))
        freeze_at = int(rng.integers(3, 8))
        freezes_total += 1
        with tempfile.TemporaryDirectory() as hb_dir:
            done_counts = {i: 0 for i in range(3)}
            lock = threading.Lock()

            def make_start(rank):
                def start(attempt):
                    # Freshen the slot BEFORE the handle exists: the
                    # frozen file's stale age must not instantly
                    # re-preempt the restarted attempt.
                    HeartbeatEmitter(hb_dir, rank).beat()

                    def target(cancel):
                        emitter = HeartbeatEmitter(hb_dir, rank)
                        frozen = attempt == 0 and rank == freeze_rank
                        for s in range(worker_steps):
                            if cancel.is_set():
                                return  # cooperative preemption
                            if not (frozen and s >= freeze_at):
                                emitter.notify_step(s)
                            _time.sleep(0.02)
                        with lock:
                            done_counts[rank] += 1
                        emitter.close()

                    return ThreadWorker(f"soak{rank}", target,
                                        pass_cancel=True)

                return start

            fpol = FtPolicy(
                restart=RestartPolicy(max_restarts=2, backoff_base_s=0.05),
                barrier=BarrierPolicy(deadline_s=0.3), seed=r,
            )
            sup = Supervisor(policy=fpol, telemetry=tele,
                             heartbeat_dir=hb_dir, name=f"soak_freeze{r}")
            for rank in range(3):
                sup.add(str(rank), make_start(rank), rank=rank)
            sup.run(deadline_s=60)
            if any(v != 1 for v in done_counts.values()):
                raise AssertionError(
                    f"freeze round {r}: completion counts {done_counts} "
                    "(a worker finished twice or never — double-counted)"
                )

    preempts = sum(
        v for k, v in tele.snapshot()["counters"].items()
        if k.startswith("ft_stall_preemptions_total")
    )
    if preempts != freezes_total:
        raise AssertionError(
            f"{preempts} stall preemptions != {freezes_total} injected "
            "freezes"
        )
    restarts_all = sum(
        v for k, v in tele.snapshot()["counters"].items()
        if k.startswith("ft_restarts_total")
    )
    if restarts_all != kills_total + freezes_total:
        raise AssertionError(
            f"total restarts {restarts_all} != kills {kills_total} + "
            f"freezes {freezes_total}"
        )
    return {
        "config": "hogwild_chaos_soak", "unit": "restarts",
        "value": int(restarts_all),
        "rounds": rounds, "freeze_rounds": freeze_rounds,
        "kills": int(kills_total), "freezes": int(freezes_total),
        "drops": int(drops_total),
        "restarts": int(restarts_all),
        "stall_preemptions": int(preempts),
        "records_exact": True,
        "n_chips": n_workers,
        "wall_s": round(time.perf_counter() - t_start, 2),
        "per_round": per_round,
    }


def bench_elastic_ctl(n_parts: int = 36, part_sleep_s: float = 0.4,
                      recovery_bound_s: float = 30.0) -> dict:
    """Elastic control-plane gate (``make bench-elastic``): one
    supervised MULTI-PROCESS run (real ``python -m sparktorch_tpu.ctl.
    worker`` children) must survive, in a single world, the three
    transitions the controller exists for —

    - a seeded NON-COOPERATIVE kill (chaos ``kill_process_at``: raw
      SIGKILL delivered by the controller's own liveness poll, no
      cancel event, no grace) -> restart, recovery latency bounded;
    - a restart-budget EXHAUSTION (one rank crashes on every attempt)
      -> world SHRINK through the native coordinator (generation
      bump), the dead rank's partitions redistributed, run continues;
    - a REJOIN (a new rank added after the shrink) -> world GROW,
      another generation.

    FAILS (raises) unless: every partition completes EXACTLY once
    (atomic rename + skip-if-exists idempotency — no loss, no double
    work), the chaos kill fired exactly once, shrink and grow each
    happened exactly once with the coordinator's generation following,
    and every transition is visible as a generation-tagged event in
    the fleet collector's ``/gang`` view scraped over HTTP. A
    recovery-latency drift gate arms once a prior record is retained
    (``SPARKTORCH_TPU_ELASTIC_DRIFT_TOL``, relative, default 2.0 —
    child-process boot cost breathes with rig load)."""
    import os
    import tempfile
    import threading

    from sparktorch_tpu.ctl import ElasticController, spawn_worker
    from sparktorch_tpu.ft import ChaosConfig, FtPolicy, RestartPolicy, inject
    from sparktorch_tpu.native.gang import GangCoordinator, GangMetricsExporter
    from sparktorch_tpu.obs import Telemetry
    from sparktorch_tpu.obs.collector import FleetCollector, scrape_json

    t_start = time.perf_counter()
    tele = Telemetry(run_id="bench_elastic")
    workdir = tempfile.mkdtemp(prefix="bench_elastic_")
    out = os.path.join(workdir, "parts")
    hb_dir = os.path.join(workdir, "hb")
    os.makedirs(out)
    work = [f"part{i:03d}" for i in range(n_parts)]

    def completed(p):
        return os.path.exists(os.path.join(out, p + ".done"))

    def start_fn(rank, attempt, generation, assignment):
        def workfn(ctx, _parts=tuple(assignment), _rank=rank,
                   _gen=generation, _out=out, _sleep=part_sleep_s):
            import os as _os
            import time as _t

            if _rank == 1:
                raise RuntimeError("rank1 permanently broken")
            for i, p in enumerate(_parts):
                if ctx.should_stop():
                    return
                ctx.notify_step(i)
                path = _os.path.join(_out, p + ".done")
                if _os.path.exists(path):
                    continue
                tmp = path + f".tmp{_os.getpid()}"
                with open(tmp, "w") as f:
                    f.write(f"{_rank}:{_gen}")
                _os.replace(tmp, path)
                _t.sleep(_sleep)

        return spawn_worker(workfn, rank=rank, heartbeat_dir=hb_dir,
                            name=f"rank{rank}", telemetry=tele)

    coord = GangCoordinator(world_size=3, port=0,
                            heartbeat_timeout_ms=30_000)
    exporter = GangMetricsExporter(heartbeat_dir=hb_dir, coordinator=coord,
                                   telemetry=tele, port=0).start()
    collector = FleetCollector({0: exporter.url}, telemetry=tele,
                               poll_interval_s=0.25)
    collector.start(poll_loop=True)
    policy = FtPolicy(restart=RestartPolicy(max_restarts=2,
                                            backoff_base_s=0.05,
                                            backoff_max_s=0.2), seed=0)
    ctl = ElasticController(work, completed, policy=policy, telemetry=tele,
                            coordinator=coord, collector=collector,
                            min_world=1, name="bench_elastic")
    for r in range(3):
        ctl.add_rank(r, start_fn)

    def grower():
        # The rejoin: a NEW rank joins right after the shrink lands,
        # so the gate always sees shrink THEN grow in one run.
        deadline = time.perf_counter() + 120.0
        while time.perf_counter() < deadline and not ctl._stop.is_set():
            if ctl._resizes["shrink"] >= 1:
                ctl.grow(3, start_fn)
                return
            time.sleep(0.05)

    threading.Thread(target=grower, name="bench-elastic-grower",
                     daemon=True).start()
    try:
        with inject(ChaosConfig(seed=11, kill_process_at={0: 2}),
                    telemetry=tele) as inj:
            summary = ctl.run(poll_interval_s=0.05, deadline_s=240.0)
        gang_doc = scrape_json(
            f"http://127.0.0.1:{collector.port}/gang")
    finally:
        collector.stop()
        exporter.stop()
        coord.stop()
    # Post-stop reads below are the PR 10 contract: stop() SNAPSHOTS
    # final native state before freeing it (the pre-snapshot version of
    # this very bench segfaulted here — sparklint SPK501 now guards the
    # class; these two reads are the documented exception).
    coord_generation = coord.generation  # lint-obs: ok (snapshot property, frozen by stop())
    coord_world_size = coord.world_size  # lint-obs: ok (snapshot property, frozen by stop())

    # -- gates ---------------------------------------------------------
    missing = [p for p in work if not completed(p)]
    if missing or summary["work_pending"]:
        raise AssertionError(f"partitions incomplete: {missing}")
    torn = [f for f in os.listdir(out) if ".tmp" in f]
    if torn:
        raise AssertionError(f"torn partition outputs left behind: {torn}")
    if len(os.listdir(out)) != n_parts:
        raise AssertionError(
            f"{len(os.listdir(out))} outputs != {n_parts} partitions")
    kills_fired = [e for e in inj.events if e["site"] == "ctl.process"]
    if len(kills_fired) != 1 or kills_fired[0]["rank"] != 0:
        raise AssertionError(
            f"chaos kill_process_at fired {kills_fired} (want exactly "
            "one SIGKILL on rank 0)")
    if summary["resizes"] != {"shrink": 1, "grow": 1}:
        raise AssertionError(f"resizes {summary['resizes']} != "
                             "{'shrink': 1, 'grow': 1}")
    if summary["removed"] != [1]:
        raise AssertionError(f"removed {summary['removed']} != [1]")
    kinds = [h["kind"] for h in ctl.history]
    for needed in ("restart", "shrink", "grow"):
        if needed not in kinds:
            raise AssertionError(
                f"no {needed!r} event in the controller history {kinds}")
    untagged = [h for h in ctl.history if "generation" not in h]
    if untagged:
        raise AssertionError(f"events missing generation tags: {untagged}")
    if not (coord_generation == ctl.generation == summary["generation"]
            >= 2):
        raise AssertionError(
            f"generation disagreement: coordinator {coord_generation}, "
            f"controller {ctl.generation}, summary "
            f"{summary['generation']} (want agreement, >= 2)")
    if coord_world_size != 3:  # ranks 0, 2 and the joined 3
        raise AssertionError(
            f"coordinator world_size {coord_world_size} != 3 after "
            "shrink+grow")
    # Every transition visible in the collector's /gang answer.
    elastic_doc = gang_doc.get("elastic") or {}
    doc_kinds = [h.get("kind") for h in elastic_doc.get("history", [])]
    for needed in ("restart", "shrink", "grow"):
        if needed not in doc_kinds:
            raise AssertionError(
                f"/gang elastic history lacks {needed!r}: {doc_kinds}")
    if elastic_doc.get("generation") != summary["generation"] or \
            elastic_doc.get("resizes") != summary["resizes"]:
        raise AssertionError(
            f"/gang elastic doc {elastic_doc.get('generation')}/"
            f"{elastic_doc.get('resizes')} disagrees with the run "
            f"summary {summary['generation']}/{summary['resizes']}")
    # Recovery latency: the restart of the SIGKILLed rank, detection
    # to relaunch, bounded (generous — child boot rides rig load).
    recovery = [
        v["max"] for k, v in tele.snapshot()["histograms"].items()
        if k.startswith("ft_recovery_latency_s") and v["count"]
    ]
    if not recovery or max(recovery) > recovery_bound_s:
        raise AssertionError(
            f"recovery latency {recovery} empty or past the "
            f"{recovery_bound_s}s bound")
    # Redistribution really happened: generations past 0 completed
    # partitions too (the shrunk/grown worlds carried the tail).
    by_gen: Dict[str, int] = {}
    for p in work:
        with open(os.path.join(out, p + ".done")) as f:
            _, gen = f.read().split(":")
        by_gen[gen] = by_gen.get(gen, 0) + 1
    if len(by_gen) < 2:
        raise AssertionError(
            f"all partitions completed in one generation ({by_gen}) — "
            "the resizes never redistributed work")

    # -- drift gate (arms once a prior record is retained) -------------
    tol = float(os.environ.get("SPARKTORCH_TPU_ELASTIC_DRIFT_TOL", "2.0"))
    recovery_max = max(recovery)
    prior = _prior_record("elastic_ctl", "recovery_latency_s")
    if prior is None:
        drift = {"status": "no_prior_record", "tolerance": tol}
    else:
        prior_lat = float(prior["recovery_latency_s"])
        drift = {
            "status": "checked", "tolerance": tol,
            "prior_ts": prior.get("ts"),
            "prior_recovery_latency_s": round(prior_lat, 3),
            "ratio": round(recovery_max / max(prior_lat, 1e-9), 3),
        }
        if recovery_max > prior_lat * (1.0 + tol) + 1.0:
            raise AssertionError(
                f"recovery latency regressed: {recovery_max:.2f}s vs "
                f"prior {prior_lat:.2f}s (past the {tol} relative "
                f"tolerance + 1s floor); drift: {drift}")

    return {
        "config": "elastic_ctl", "unit": "s (recovery latency)",
        "value": round(recovery_max, 3),
        "recovery_latency_s": round(recovery_max, 3),
        "n_parts": n_parts,
        "restarts": summary["restarts"],
        "resizes": summary["resizes"],
        "removed": summary["removed"],
        "generation": summary["generation"],
        "world_size": summary["world_size"],
        "parts_by_generation": dict(sorted(by_gen.items())),
        "chaos_kills": len(kills_fired),
        "records_exact": True,
        "elastic_drift": drift,
        "wall_s": round(time.perf_counter() - t_start, 2),
    }


def bench_obs_history(n_pulls: int = 6, slow_delay_s: float = 0.5,
                      for_sweeps: int = 3) -> dict:
    """Metrics-history / SLO-alerting / flight-recorder gate
    (``make bench-obs-history``) — FAILS (raises) unless all three
    retained-observability claims hold end to end:

    - **alerting is causal, not noisy**: against a live 2-shard fleet,
      a seeded degradation (chaos ``slow_shard_s``) must fire the
      sustained ``sharded.shard_pull_latency_s`` p99 breach rule (the
      client hop — the server-side ``wire_latency_s`` can never see
      the injected delay) within its rule window
      (``for_sweeps`` + 2 sweeps of the first breach), exactly one
      episode, visible in the collector's ``/gang`` ``alerts`` section
      over HTTP — while an A/A CONTROL run (identical loop, no chaos)
      fires nothing;
    - **postmortems capture the causal window**: a seeded
      NON-COOPERATIVE process-worker kill (chaos ``kill_process_at``)
      must produce a ``postmortem_<ts>.json`` bundle whose event
      window contains the kill's ``ctl.*`` transition AND the victim
      rank's last spans (recovered from the collector's last-good
      scrape of the dead process's flight-recorder ring), renderable
      by ``timeline --postmortem``;
    - **the memory tier is nearly free**: the collector sweep with
      history + alerts enabled stays within 10%
      (``SPARKTORCH_TPU_OBS_SWEEP_TOL``) of a history-off sweep —
      medians over interleaved sweeps against the same targets, so
      rig noise hits both legs.

    A throughput-shaped drift gate arms once a prior record is
    retained, judged against the WINDOWED median of the newest 3 prior
    rounds (``_prior_window`` — the satellite that moves drift gates
    off single records)."""
    import io
    import os
    import tempfile
    import contextlib

    import jax

    from sparktorch_tpu.ctl import ElasticController, spawn_worker
    from sparktorch_tpu.ft import ChaosConfig, FtPolicy, RestartPolicy, inject
    from sparktorch_tpu.models import MnistMLP
    from sparktorch_tpu.net.sharded import ShardedTransport
    from sparktorch_tpu.obs import AlertRule, FleetCollector, Telemetry
    from sparktorch_tpu.obs import timeline as _timeline
    from sparktorch_tpu.obs.blackbox import read_postmortem
    from sparktorch_tpu.obs.collector import scrape_json
    from sparktorch_tpu.native.gang import GangMetricsExporter
    from sparktorch_tpu.serve.fleet import ParamServerFleet
    from sparktorch_tpu.utils.serde import ModelSpec

    t_start = time.perf_counter()
    spec = ModelSpec(module=MnistMLP(), loss="cross_entropy",
                     optimizer="sgd", optimizer_params={"lr": 1e-2},
                     input_shape=(784,))
    slow_shard = "1"
    threshold_s = slow_delay_s * 0.4  # far above clean serve, far below delayed

    def _alert_leg(chaos_cfg) -> dict:
        """One fleet + collector + rule run; returns the alert story."""
        leg_tele = Telemetry(run_id="bench_obs_alert")
        fleet = ParamServerFleet(spec, n_shards=2,
                                 telemetry=leg_tele).start()
        # Client-observed hop latency, not the server-side
        # wire_latency_s: the chaos delay (like a real network/queue
        # straggler) lands BEFORE the serve handler's clock, on the
        # client's shard hop — which is exactly the series a hot-shard
        # rule must watch.
        rules = [AlertRule(
            name="hot_shard_p99",
            metric="sharded.shard_pull_latency_s",
            labels={"shard": slow_shard},
            kind="sustained", field="p99", op=">",
            threshold=threshold_s, for_sweeps=for_sweeps,
        )]
        collector = FleetCollector.for_fleet(fleet, poll_interval_s=0,
                                             alert_rules=rules)
        collector.start(poll_loop=False)
        first_breach_sweep = None
        fired_sweep = None
        try:
            transport = ShardedTransport(fleet, telemetry=leg_tele)
            zeros = jax.tree.map(
                lambda a: np.zeros_like(np.asarray(a)), fleet.assemble())
            have = -1
            ctx = (inject(chaos_cfg, telemetry=leg_tele) if chaos_cfg
                   else contextlib.nullcontext())
            with ctx:
                for sweep in range(n_pulls):
                    transport.push(zeros)
                    fleet.drain()
                    snap = transport.pull(have)
                    if snap is not None:
                        have = snap[0]
                    collector.poll()
                    state = collector.alerts.doc()["rules"]["hot_shard_p99"]
                    if first_breach_sweep is None and state["streak"] > 0:
                        first_breach_sweep = sweep
                    if fired_sweep is None and state["state"] == "firing":
                        fired_sweep = sweep
            gang = scrape_json(f"{collector.url}/gang")
            hist_rate = scrape_json(
                f"{collector.url}/history?name=collector.scrapes_total"
                f"&query=rate")
            transport.close()
            return {
                "doc": collector.alerts.doc(),
                "gang_alerts": gang.get("alerts") or {},
                "first_breach_sweep": first_breach_sweep,
                "fired_sweep": fired_sweep,
                "history_rate_ok": hist_rate.get("value") is not None,
            }
        finally:
            collector.stop()
            fleet.stop()

    with Telemetry(run_id="bench_obs").span("bench/alert_legs") as _sp_alerts:
        control = _alert_leg(None)
        chaotic = _alert_leg(ChaosConfig(
            seed=7, slow_shard_s={slow_shard: slow_delay_s}))

    # -- gates: A/A control silent, seeded breach fires in-window ------
    ctl_rule = control["doc"]["rules"]["hot_shard_p99"]
    if ctl_rule["episodes"] != 0 or control["gang_alerts"].get("active"):
        raise AssertionError(
            f"A/A control run fired alerts: {ctl_rule} "
            f"(active {control['gang_alerts'].get('active')})")
    hot_rule = chaotic["doc"]["rules"]["hot_shard_p99"]
    if hot_rule["episodes"] != 1 or hot_rule["state"] != "firing":
        raise AssertionError(
            f"seeded degradation did not fire exactly one episode: "
            f"{hot_rule}")
    if chaotic["first_breach_sweep"] is None \
            or chaotic["fired_sweep"] is None \
            or (chaotic["fired_sweep"] - chaotic["first_breach_sweep"]
                > for_sweeps + 1):
        raise AssertionError(
            f"alert missed its rule window: first breach sweep "
            f"{chaotic['first_breach_sweep']}, fired sweep "
            f"{chaotic['fired_sweep']} (for_sweeps={for_sweeps})")
    if "hot_shard_p99" not in (chaotic["gang_alerts"].get("active") or []):
        raise AssertionError(
            f"/gang alerts section does not show the firing rule: "
            f"{chaotic['gang_alerts']}")
    if not (control["history_rate_ok"] and chaotic["history_rate_ok"]):
        raise AssertionError("/history rate query answered null on a "
                             "live collector")

    # -- leg 2: seeded worker kill -> postmortem bundle ----------------
    with Telemetry(run_id="bench_obs").span("bench/postmortem_leg") as _sp_pm:
        tele = Telemetry(run_id="bench_obs_pm")
        workdir = tempfile.mkdtemp(prefix="bench_obs_pm_")
        out = os.path.join(workdir, "parts")
        hb_dir = os.path.join(workdir, "hb")
        pm_dir = os.path.join(workdir, "postmortems")
        os.makedirs(out)
        work = [f"part{i:02d}" for i in range(8)]

        def completed(p):
            return os.path.exists(os.path.join(out, p + ".done"))

        workers = {}
        # The chaos kill fires at rank 0's heartbeat step 2; the bundle
        # gate needs the victim's spans in the collector's last-good
        # snapshot first. Workers park before step 2 until this file
        # appears — the bench writes it once the collector has scraped
        # rank 0's blackbox ring, so a slow rank-1 spawn (the collector
        # starts only after BOTH URLs publish) can't let the kill
        # outrun the first scrape.
        scrape_gate = os.path.join(workdir, "scrape.gate")

        def start_fn(rank, attempt, generation, assignment):
            def workfn(ctx, _parts=tuple(assignment), _rank=rank,
                       _out=out, _gate=scrape_gate):
                import os as _os
                import time as _t

                for i, p in enumerate(_parts):
                    if ctx.should_stop():
                        return
                    if i == 2 and not _os.path.exists(_gate):
                        hold = _t.perf_counter() + 30.0
                        while (not _os.path.exists(_gate)
                               and _t.perf_counter() < hold
                               and not ctx.should_stop()):
                            _t.sleep(0.05)
                    ctx.notify_step(i)
                    # The victim's last evidence: a per-partition span
                    # on its own bus -> flight-recorder ring ->
                    # /telemetry scrape -> collector last-good.
                    with ctx.telemetry.span("work/partition", labels={
                            "part": p}):
                        path = _os.path.join(_out, p + ".done")
                        if not _os.path.exists(path):
                            tmp = path + f".tmp{_os.getpid()}"
                            with open(tmp, "w") as f:
                                f.write(f"{_rank}")
                            _os.replace(tmp, path)
                        _t.sleep(0.25)

            w = spawn_worker(workfn, rank=rank, heartbeat_dir=hb_dir,
                             name=f"rank{rank}", telemetry=tele,
                             ctl_port=0)
            workers[rank] = w
            return w

        policy = FtPolicy(restart=RestartPolicy(max_restarts=2,
                                                backoff_base_s=0.05,
                                                backoff_max_s=0.2), seed=0)
        ctl = ElasticController(work, completed, policy=policy,
                                telemetry=tele, min_world=1,
                                postmortem_dir=pm_dir,
                                name="bench_obs_pm")
        ctl.add_rank(0, start_fn)
        ctl.add_rank(1, start_fn)
        collector = None
        try:
            with inject(ChaosConfig(seed=13, kill_process_at={0: 2}),
                        telemetry=tele) as inj:
                # Launch via run() in a thread? No: run() launches and
                # supervises; the collector needs the workers' exporter
                # URLs, which exist only after launch. Launch first via
                # a short-lived controller thread would race — instead
                # poll the URLs from the handles the start_fn records.
                import threading as _threading

                run_err = []

                def _run():
                    try:
                        ctl.run(poll_interval_s=0.05, deadline_s=120.0)
                    except BaseException as e:  # surfaced below
                        run_err.append(e)

                runner = _threading.Thread(target=_run, daemon=True)
                runner.start()
                deadline = time.perf_counter() + 30.0
                urls = {}
                while time.perf_counter() < deadline and len(urls) < 2:
                    for rank, w in list(workers.items()):
                        if rank not in urls:
                            url = w.ctl_url(timeout_s=0.1)
                            if url:
                                urls[rank] = url
                    time.sleep(0.05)
                if len(urls) < 2:
                    raise AssertionError(
                        f"worker exporters never published URLs: {urls}")
                collector = FleetCollector(urls, telemetry=tele,
                                           poll_interval_s=0.1)
                collector.start(poll_loop=True)
                ctl.collector = collector
                # Open the kill gate only after the victim's ring is in
                # last-good — otherwise the bundle can miss its spans.
                from sparktorch_tpu.obs.blackbox import (
                    events_from_snapshot as _ring_events)
                scraped = time.perf_counter() + 30.0
                while time.perf_counter() < scraped:
                    with collector._lock:
                        st = collector._ranks.get("0")
                        snap = st.snapshot if st is not None else None
                    if snap and any(e.get("kind") == "span"
                                    for e in _ring_events(snap)):
                        break
                    time.sleep(0.05)
                else:
                    raise AssertionError(
                        "collector never scraped rank 0's blackbox ring")
                with open(scrape_gate + ".tmp", "w") as f:
                    f.write("ok")
                os.replace(scrape_gate + ".tmp", scrape_gate)
                runner.join(timeout=120.0)
                if runner.is_alive():
                    raise AssertionError("postmortem leg run() hung")
                if run_err:
                    raise AssertionError(
                        f"postmortem leg failed: {run_err[0]}")
        finally:
            if collector is not None:
                collector.stop()
        missing = [p for p in work if not completed(p)]
        if missing:
            raise AssertionError(f"partitions incomplete: {missing}")
        kills = [e for e in inj.events if e["site"] == "ctl.process"]
        if len(kills) != 1 or kills[0]["rank"] != 0:
            raise AssertionError(f"chaos kill fired {kills} (want one "
                                 f"SIGKILL on rank 0)")
        bundles = sorted(os.listdir(pm_dir)) if os.path.isdir(pm_dir) else []
        if not bundles:
            raise AssertionError("no postmortem bundle written")
        # The KILL's bundle is the first one (restart_scheduled fires
        # postmortems in detection order).
        bundle = read_postmortem(os.path.join(pm_dir, bundles[0]))
        kinds = {str(e.get("kind")) for e in bundle["events"]}
        if not kinds & {"ctl.restart_scheduled", "restart_scheduled"}:
            raise AssertionError(
                f"bundle window lacks the kill's ctl.* transition: "
                f"{sorted(kinds)}")
        victim_spans = [e for e in bundle["events"]
                        if e.get("kind") == "span"
                        and str(e.get("rank")) == "0"]
        if not victim_spans:
            raise AssertionError(
                f"bundle window lacks the victim's last spans "
                f"(kinds {sorted(kinds)})")
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = _timeline.main(["--postmortem",
                                 os.path.join(pm_dir, bundles[0])])
        if rc != 0 or "postmortem:" not in buf.getvalue():
            raise AssertionError(f"timeline --postmortem failed (rc={rc})")

    # -- leg 3: sweep overhead with history+alerts vs history-off ------
    with Telemetry(run_id="bench_obs").span("bench/overhead_leg") as _sp_ovr:
        ovr_tele = Telemetry(run_id="bench_obs_ovr")
        ovr_tele.counter("reqs_total", 10)
        for _ in range(64):
            ovr_tele.observe("lat_s", 0.01)
        exporters = [GangMetricsExporter(telemetry=ovr_tele,
                                         port=0).start()
                     for _ in range(2)]
        targets = {i: e.url for i, e in enumerate(exporters)}
        rules = [AlertRule(name="ovr", metric="lat_s",
                           labels={"rank": "0"}, kind="sustained",
                           field="p99", threshold=1e9, for_sweeps=2)]
        col_on = FleetCollector(targets, poll_interval_s=0,
                                alert_rules=rules)
        col_off = FleetCollector(targets, poll_interval_s=0,
                                 history=False)
        on_walls, off_walls = [], []
        try:
            for _ in range(4):  # warmup both paths
                col_on.poll()
                col_off.poll()
            for i in range(60):
                ovr_tele.counter("reqs_total")
                ovr_tele.observe("lat_s", 0.01)
                # Interleaved, order alternating: scheduler epochs hit
                # both legs equally.
                pair = ((col_on, on_walls), (col_off, off_walls))
                for col, walls in (pair if i % 2 == 0
                                   else reversed(pair)):
                    t0 = time.perf_counter()
                    col.poll()
                    walls.append(time.perf_counter() - t0)
        finally:
            col_on.stop()
            col_off.stop()
            for e in exporters:
                e.stop()
        on_ms = float(np.median(on_walls)) * 1e3
        off_ms = float(np.median(off_walls)) * 1e3
        on_min_ms = float(np.min(on_walls)) * 1e3
        off_min_ms = float(np.min(off_walls)) * 1e3
        tol = float(os.environ.get("SPARKTORCH_TPU_OBS_SWEEP_TOL", "0.10"))
        # Gate on MIN-of-sweeps, not the median: the sweep is a
        # deterministic workload, so its min isolates the real cost
        # while the median breathes ±1ms with this rig's cpu-share
        # scheduler (measured A/B medians swinging -4% to +6% across
        # runs of the SAME code — pure noise against a ~100µs true
        # cost). 0.2ms absolute floor for timer/allocator jitter.
        if on_min_ms > off_min_ms * (1.0 + tol) + 0.2:
            raise AssertionError(
                f"history+alerts sweep overhead past bound: min "
                f"{on_min_ms:.3f}ms vs {off_min_ms:.3f}ms history-off "
                f"(medians {on_ms:.3f}/{off_ms:.3f}ms; tol {tol:.0%} "
                f"+ 0.2ms)")

    # -- drift gate (windowed prior median, arms once retained) --------
    tol = float(os.environ.get("SPARKTORCH_TPU_OBS_DRIFT_TOL", "1.0"))
    prior = _prior_window("obs_history", "sweep_on_ms", k=3)
    if prior is None:
        drift = {"status": "no_prior_record", "tolerance": tol}
    else:
        drift = {
            "status": "checked", "tolerance": tol,
            "prior_median_ms": round(prior["median"], 3),
            "prior_n": prior["n"],
            "ratio": round(on_ms / max(prior["median"], 1e-9), 3),
        }
        if on_ms > prior["median"] * (1.0 + tol) + 1.0:
            raise AssertionError(
                f"history-on sweep regressed: {on_ms:.3f}ms vs prior "
                f"windowed median {prior['median']:.3f}ms (past the "
                f"{tol} relative tolerance + 1ms floor); drift: {drift}")

    return {
        "config": "obs_history", "unit": "ms (history-on sweep p50)",
        "value": round(on_ms, 3),
        "sweep_on_ms": round(on_ms, 3),
        "sweep_off_ms": round(off_ms, 3),
        "sweep_on_min_ms": round(on_min_ms, 3),
        "sweep_off_min_ms": round(off_min_ms, 3),
        "sweep_overhead_pct": round(100.0 * (on_min_ms - off_min_ms)
                                    / max(off_min_ms, 1e-9), 2),
        "alert": {
            "threshold_s": threshold_s,
            "for_sweeps": for_sweeps,
            "control_episodes": ctl_rule["episodes"],
            "chaos_episodes": hot_rule["episodes"],
            "first_breach_sweep": chaotic["first_breach_sweep"],
            "fired_sweep": chaotic["fired_sweep"],
        },
        "postmortem": {
            "bundles": len(bundles),
            "victim_spans": len(victim_spans),
            "event_kinds": sorted(kinds)[:12],
        },
        "obs_drift": drift,
        "phase_s": {
            "alert_legs": round(_sp_alerts.duration_s, 3),
            "postmortem_leg": round(_sp_pm.duration_s, 3),
            "overhead_leg": round(_sp_ovr.duration_s, 3),
        },
        "wall_s": round(time.perf_counter() - t_start, 2),
    }


def bench_goodput(n_parts: int = 10, part_sleep_s: float = 0.25,
                  n_pulls: int = 4, slow_delay_s: float = 0.5) -> dict:
    """Run-level goodput-ledger gate (``make bench-goodput``) — FAILS
    (raises) unless the time ledger's four claims hold end to end:

    - **attribution is real**: a streaming training run with
      checkpointing shows ``compile`` (init + first-chunk cache miss),
      ``checkpoint`` and ``data_wait`` as nonzero seconds, with the
      MECE invariant holding (buckets + idle sum to wall within 2%,
      ZERO over-attribution) and the run report served per rank and
      run-wide over ``GET /goodput`` + rendered by
      ``timeline --goodput`` with the biggest thief named;
    - **chaos lands in the right bucket**: a seeded ``slow_shard_s``
      delay on the hogwild wire shifts ``exposed_comm``, NOT
      ``compute``, vs an A/A control leg (whose downtime buckets are
      exactly zero);
    - **downtime reconciles**: on a real multi-process elastic run, a
      seeded non-cooperative kill lands at least its measured recovery
      gap in ``restart_downtime`` (the bucket is fed from the same
      detection->relaunch window ``ft_recovery_latency_s`` measures,
      so the two reconcile to a tolerance), the shrink+grow walls land
      in ``resize_downtime``, and the driver ledger stays MECE;
    - **the ledger is nearly free**: one LedgerSpan costs < 1% of the
      measured training step wall (drift-gated against the windowed
      median of prior rounds, ``SPARKTORCH_TPU_GOODPUT_DRIFT_TOL``).
    """
    import contextlib
    import io
    import os
    import tempfile
    import threading

    import jax

    from sparktorch_tpu.ctl import ElasticController, spawn_worker
    from sparktorch_tpu.ft import ChaosConfig, FtPolicy, RestartPolicy, inject
    from sparktorch_tpu.models import MnistMLP
    from sparktorch_tpu.native.gang import GangCoordinator, GangMetricsExporter
    from sparktorch_tpu.net.sharded import ShardedTransport
    from sparktorch_tpu.obs import FleetCollector, Telemetry
    from sparktorch_tpu.obs import goodput as _goodput
    from sparktorch_tpu.obs import timeline as _timeline
    from sparktorch_tpu.obs.collector import scrape_json
    from sparktorch_tpu.serve.fleet import ParamServerFleet
    from sparktorch_tpu.train.sync import train_distributed_streaming
    from sparktorch_tpu.utils.serde import ModelSpec

    t_start = time.perf_counter()
    workdir = tempfile.mkdtemp(prefix="bench_goodput_")

    def _mece(doc: dict, leg: str, over_tol_frac: float = 0.02) -> None:
        wall = float(doc["wall_s"])
        total = sum(float(v) for v in doc["buckets"].values())
        if abs(total - wall) > 0.02 * wall:
            raise AssertionError(
                f"{leg}: ledger not MECE — buckets sum {total:.3f}s vs "
                f"wall {wall:.3f}s (> 2%)")
        if float(doc["overattributed_s"]) > over_tol_frac * wall:
            raise AssertionError(
                f"{leg}: {doc['overattributed_s']}s over-attributed "
                f"(double-counted regions) against {wall:.3f}s wall")

    def _zero_downtime(doc: dict, leg: str) -> None:
        for b in ("restart_downtime", "resize_downtime"):
            if float(doc["buckets"][b]) != 0.0:
                raise AssertionError(
                    f"{leg}: A/A run shows nonzero {b} "
                    f"({doc['buckets'][b]}s)")

    # -- leg 1: training attribution (compile/checkpoint/data_wait) ----
    rng = np.random.default_rng(0)
    x = rng.normal(size=(2048, 784)).astype(np.float32)
    y = rng.integers(0, 10, (2048,)).astype(np.int32)
    spec = ModelSpec(module=MnistMLP(), loss="cross_entropy",
                     optimizer="sgd", optimizer_params={"lr": 1e-2},
                     input_shape=(784,))
    tele0 = Telemetry(run_id="bench_goodput_r0")
    ledger0 = _goodput.GoodputLedger(telemetry=tele0, rank=0)
    with ledger0.activate():
        train_distributed_streaming(
            spec, (x, y), chunk_rows=512, epochs=2, mini_batch=64,
            checkpoint_dir=os.path.join(workdir, "ckpt"),
            checkpoint_every=8, telemetry=tele0,
        )
    doc0 = tele0.get_section(_goodput.SECTION)
    _mece(doc0, "training leg")
    _zero_downtime(doc0, "training leg")
    for bucket, floor in (("compile", 0.01), ("checkpoint", 0.001),
                          ("data_wait", 0.0005), ("compute", 0.001)):
        if float(doc0["buckets"][bucket]) <= floor:
            raise AssertionError(
                f"training leg: {bucket} bucket empty "
                f"({doc0['buckets'][bucket]}s <= {floor}s floor) — "
                f"instrumentation lost: {doc0['buckets']}")
    if doc0["compiles"] < 2 or doc0["n_steps"] <= 0:
        raise AssertionError(
            f"training leg: compiles {doc0['compiles']} (want >= 2: "
            f"init + first chunk) / n_steps {doc0['n_steps']}")
    step_wall_s = ((float(doc0["buckets"]["compute"])
                    + float(doc0["buckets"]["exposed_comm"]))
                   / doc0["n_steps"])

    # Rank 1: a second, flops-declared ledger (a jitted matmul loop),
    # so the merged /goodput report is genuinely per-rank and carries
    # MFU.
    tele1 = Telemetry(run_id="bench_goodput_r1")
    m = 256
    mm = jax.jit(lambda a: a @ a)
    xm = np.ones((m, m), np.float32)
    ledger1 = _goodput.GoodputLedger(telemetry=tele1, rank=1,
                                     flops_per_step=2.0 * m ** 3)
    for _ in range(5):
        c0 = _goodput.jit_cache_size(mm)
        with ledger1.step_span() as sp:
            mm(xm).block_until_ready()
            c1 = _goodput.jit_cache_size(mm)
            if c0 is not None and c1 is not None and c1 > c0:
                sp.rebucket("compile")
    ledger1.set_comm_model(0.1, "estimate")
    doc1 = ledger1.close()
    _mece(doc1, "rank1 leg")

    # -- leg 2: collector merge, GET /goodput, timeline renders --------
    exp0 = GangMetricsExporter(telemetry=tele0, port=0).start()
    exp1 = GangMetricsExporter(telemetry=tele1, port=0).start()
    sink = os.path.join(workdir, "collector_sink.jsonl")
    collector = FleetCollector({0: exp0.url, 1: exp1.url},
                               poll_interval_s=0, jsonl_path=sink)
    collector.start(poll_loop=False)
    try:
        collector.poll()
        run_doc = scrape_json(f"{collector.url}/goodput")
    finally:
        collector.stop()
        exp0.stop()
        exp1.stop()
    ranks_seen = set(run_doc.get("per_rank") or {})
    if not {"0", "1"} <= ranks_seen:
        raise AssertionError(
            f"/goodput per_rank missing ranks: {sorted(ranks_seen)}")
    if not (0.0 < float(run_doc["goodput"]) <= 1.0) or \
            any("goodput" not in r for r in run_doc["per_rank"].values()):
        raise AssertionError(
            f"/goodput fractions malformed: run {run_doc.get('goodput')}")
    thief = run_doc.get("biggest_thief")
    if not thief or thief["bucket"] == "compute":
        raise AssertionError(f"/goodput biggest_thief missing: {thief}")
    if _device_peaks() is not None and run_doc.get("mfu") is None:
        raise AssertionError("/goodput lacks mfu despite a flops-"
                             "declaring rank")
    expected_thief = max(
        ((b, s) for b, s in run_doc["buckets"].items() if b != "compute"),
        key=lambda kv: kv[1])[0]
    for args_, what in ((["--goodput", sink], "collector sink"),
                        ([ "--goodput", os.path.join(workdir,
                                                     "goodput.json")],
                         "saved /goodput doc")):
        if what == "saved /goodput doc":
            with open(args_[1], "w") as f:
                f.write(json.dumps(run_doc))
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = _timeline.main(args_)
        out_txt = buf.getvalue()
        if rc != 0 or f"biggest thief: {expected_thief}" not in out_txt:
            raise AssertionError(
                f"timeline --goodput ({what}) failed (rc={rc}) or did "
                f"not name the biggest thief {expected_thief!r}:\n"
                f"{out_txt[:800]}")

    # -- leg 3: chaos slow shard shifts exposed_comm, not compute ------
    wire_spec = ModelSpec(module=MnistMLP(), loss="cross_entropy",
                          optimizer="sgd", optimizer_params={"lr": 1e-2},
                          input_shape=(784,))
    wf = jax.jit(lambda a: (a @ a).sum())
    wx = np.ones((512, 512), np.float32)
    wf(wx).block_until_ready()  # compiled OUTSIDE any leg's ledger

    def _wire_leg(chaos_cfg) -> dict:
        leg_tele = Telemetry(run_id="bench_goodput_wire")
        fleet = ParamServerFleet(wire_spec, n_shards=2,
                                 telemetry=leg_tele).start()
        ledger = _goodput.GoodputLedger(telemetry=leg_tele, rank="wire")
        try:
            transport = ShardedTransport(fleet, telemetry=leg_tele)
            have = -1
            ctx = (inject(chaos_cfg, telemetry=leg_tele) if chaos_cfg
                   else contextlib.nullcontext())
            with ctx:
                for _ in range(n_pulls):
                    with ledger.span("exposed_comm", {"site": "pull"}):
                        snap = transport.pull(have)
                        if snap is not None:
                            have = snap[0]
                    with ledger.step_span():
                        wf(wx).block_until_ready()
            transport.close()
            return ledger.close()
        finally:
            fleet.stop()

    wire_ctrl = _wire_leg(None)
    wire_chaos = _wire_leg(ChaosConfig(
        seed=7, slow_shard_s={"1": slow_delay_s}))
    _zero_downtime(wire_ctrl, "wire control leg")
    _zero_downtime(wire_chaos, "wire chaos leg")
    injected = slow_delay_s * n_pulls
    comm_shift = (float(wire_chaos["buckets"]["exposed_comm"])
                  - float(wire_ctrl["buckets"]["exposed_comm"]))
    if comm_shift < 0.8 * injected:
        raise AssertionError(
            f"seeded slow shard did not land in exposed_comm: shift "
            f"{comm_shift:.3f}s vs {injected:.3f}s injected")
    compute_shift = (float(wire_chaos["buckets"]["compute"])
                     - float(wire_ctrl["buckets"]["compute"]))
    if compute_shift > 0.25 * injected:
        raise AssertionError(
            f"seeded slow shard leaked into compute: +"
            f"{compute_shift:.3f}s (vs {injected:.3f}s injected — the "
            f"delay must land in exposed_comm)")

    # -- leg 4: elastic downtime attribution + reconciliation ----------
    out = os.path.join(workdir, "parts")
    hb_dir = os.path.join(workdir, "hb")
    os.makedirs(out)
    work = [f"part{i:03d}" for i in range(n_parts)]

    def completed(p):
        return os.path.exists(os.path.join(out, p + ".done"))

    etele = Telemetry(run_id="bench_goodput_elastic")

    def start_fn(rank, attempt, generation, assignment):
        def workfn(ctx, _parts=tuple(assignment), _rank=rank,
                   _gen=generation, _out=out, _sleep=part_sleep_s):
            import os as _os
            import time as _t

            if _rank == 1:
                raise RuntimeError("rank1 permanently broken")
            for i, p in enumerate(_parts):
                if ctx.should_stop():
                    return
                ctx.notify_step(i)
                path = _os.path.join(_out, p + ".done")
                if _os.path.exists(path):
                    continue
                tmp = path + f".tmp{_os.getpid()}"
                with open(tmp, "w") as f:
                    f.write(f"{_rank}:{_gen}")
                _os.replace(tmp, path)
                _t.sleep(_sleep)

        return spawn_worker(workfn, rank=rank, heartbeat_dir=hb_dir,
                            name=f"rank{rank}", telemetry=etele)

    coord = GangCoordinator(world_size=3, port=0,
                            heartbeat_timeout_ms=30_000)
    policy = FtPolicy(restart=RestartPolicy(max_restarts=2,
                                            backoff_base_s=0.05,
                                            backoff_max_s=0.2), seed=0)
    ctl = ElasticController(work, completed, policy=policy,
                            telemetry=etele, coordinator=coord,
                            min_world=1, name="bench_goodput")
    for r in range(3):
        ctl.add_rank(r, start_fn)

    def grower():
        deadline = time.perf_counter() + 120.0
        while time.perf_counter() < deadline and not ctl._stop.is_set():
            if ctl._resizes["shrink"] >= 1:
                ctl.grow(3, start_fn)
                return
            time.sleep(0.05)

    threading.Thread(target=grower, daemon=True).start()
    eledger = _goodput.GoodputLedger(telemetry=etele, rank="driver")
    try:
        with eledger.activate():
            with inject(ChaosConfig(seed=11, kill_process_at={0: 2}),
                        telemetry=etele) as inj:
                summary = ctl.run(poll_interval_s=0.05, deadline_s=240.0)
    finally:
        coord.stop()
    edoc = etele.get_section(_goodput.SECTION)
    _mece(edoc, "elastic leg")
    missing = [p for p in work if not completed(p)]
    if missing or summary["work_pending"]:
        raise AssertionError(f"elastic leg incomplete: {missing}")
    kills = [e for e in inj.events if e["site"] == "ctl.process"]
    if len(kills) != 1 or summary["resizes"] != {"shrink": 1, "grow": 1}:
        raise AssertionError(
            f"elastic leg chaos schedule wrong: kills {kills}, "
            f"resizes {summary['resizes']}")
    recovery = [
        (v["sum"], v["max"]) for k, v in
        etele.snapshot()["histograms"].items()
        if k.startswith("ft_recovery_latency_s") and v["count"]
    ]
    if not recovery:
        raise AssertionError("no ft_recovery_latency_s samples")
    recovery_sum = sum(s for s, _ in recovery)
    recovery_max = max(mx for _, mx in recovery)
    restart_bucket = float(edoc["buckets"]["restart_downtime"])
    if restart_bucket < recovery_max:
        raise AssertionError(
            f"seeded kill's measured gap {recovery_max:.3f}s not "
            f"covered by restart_downtime {restart_bucket:.3f}s")
    if abs(restart_bucket - recovery_sum) > 0.05 * recovery_sum + 0.05:
        raise AssertionError(
            f"restart_downtime {restart_bucket:.3f}s does not "
            f"reconcile with ft_recovery_latency_s sum "
            f"{recovery_sum:.3f}s (same event window)")
    resize_bucket = float(edoc["buckets"]["resize_downtime"])
    if resize_bucket <= 0 or edoc["counts"].get("resize_downtime", 0) != 2:
        raise AssertionError(
            f"shrink+grow not attributed: resize_downtime "
            f"{resize_bucket}s x{edoc['counts'].get('resize_downtime')}")

    # -- leg 5: ledger overhead vs step wall + drift gate --------------
    bare = _goodput.GoodputLedger(telemetry=None)
    reps = 5000
    t0 = time.perf_counter()
    for _ in range(reps):
        with bare.step_span():
            pass
    span_cost_s = (time.perf_counter() - t0) / reps
    span_us = span_cost_s * 1e6
    overhead_frac = span_cost_s / max(step_wall_s, 1e-9)
    if overhead_frac >= 0.01:
        raise AssertionError(
            f"ledger span overhead {span_us:.2f}us is "
            f"{100 * overhead_frac:.2f}% of the measured "
            f"{step_wall_s * 1e3:.3f}ms step wall (bound: 1%)")

    tol = float(os.environ.get("SPARKTORCH_TPU_GOODPUT_DRIFT_TOL", "1.0"))
    prior = _prior_window("goodput", "ledger_span_us", k=3)
    if prior is None:
        drift = {"status": "no_prior_record", "tolerance": tol}
    else:
        drift = {
            "status": "checked", "tolerance": tol,
            "prior_median_us": round(prior["median"], 3),
            "prior_n": prior["n"],
            "ratio": round(span_us / max(prior["median"], 1e-9), 3),
        }
        if span_us > prior["median"] * (1.0 + tol) + 2.0:
            raise AssertionError(
                f"ledger span cost regressed: {span_us:.2f}us vs prior "
                f"windowed median {prior['median']:.2f}us (past the "
                f"{tol} relative tolerance + 2us floor); drift: {drift}")

    return {
        "config": "goodput", "unit": "us (LedgerSpan overhead)",
        "value": round(span_us, 3),
        "ledger_span_us": round(span_us, 3),
        "overhead_pct_of_step": round(100 * overhead_frac, 4),
        "step_wall_ms": round(step_wall_s * 1e3, 3),
        "training": {
            "buckets": doc0["buckets"],
            "goodput": doc0["goodput"],
            "compiles": doc0["compiles"],
            "n_steps": doc0["n_steps"],
        },
        "run_report": {
            "goodput": run_doc["goodput"],
            "n_ranks": run_doc["n_ranks"],
            "biggest_thief": run_doc.get("biggest_thief"),
            "comm_source": run_doc.get("comm_source"),
            "mfu": run_doc.get("mfu"),
        },
        "wire": {
            "injected_s": injected,
            "exposed_comm_shift_s": round(comm_shift, 3),
            "compute_shift_s": round(compute_shift, 3),
        },
        "elastic": {
            "restart_downtime_s": round(restart_bucket, 3),
            "recovery_latency_sum_s": round(recovery_sum, 3),
            "resize_downtime_s": round(resize_bucket, 3),
            "goodput": edoc["goodput"],
            "resizes": summary["resizes"],
        },
        "goodput_drift": drift,
        "wall_s": round(time.perf_counter() - t_start, 2),
    }


def _profile_hot_planted(stop_t: float) -> float:
    """The seeded hot function bench_profile plants inside a compute
    LedgerSpan: pure-Python arithmetic (no genexpr, no callees), so
    every sample of it lands as SELF time on this very frame — the
    profiler must name it or the attribution chain is broken."""
    acc = 0.0
    while time.perf_counter() < stop_t:
        for i in range(2000):
            acc += i * i
    return acc


def bench_profile(n_steps: int = 30, reps: int = 3,
                  hot_s: float = 1.2) -> dict:
    """Continuous stack-profiler gate (``make bench-profile``) — FAILS
    (raises) unless the sampler's three claims hold end to end:

    - **it is nearly free**: with the sampler running at its default
      rate, the measured training-step wall grows by < 1% vs an A/A
      profiler-off leg (min of interleaved runs, the PR 11 lesson:
      medians swing with scheduler noise), and the per-tick sample
      cost is drift-gated against the windowed median of prior rounds
      (``SPARKTORCH_TPU_PROFILE_DRIFT_TOL``);
    - **attribution is real**: a planted busy-loop inside a
      ``compute`` LedgerSpan surfaces as the top self-time frame of
      the compute bucket with >= 80% of that bucket's samples;
    - **the fleet path works**: two ranks' published sections merge
      into ``GET /profile`` over real HTTP, and
      ``timeline --profile`` renders the planted frame from both a
      saved /profile document and the collector's JSONL sink.
    """
    import contextlib
    import io
    import os
    import tempfile
    import threading

    import jax

    from sparktorch_tpu.native.gang import GangMetricsExporter
    from sparktorch_tpu.obs import FleetCollector, Telemetry
    from sparktorch_tpu.obs import goodput as _goodput
    from sparktorch_tpu.obs import timeline as _timeline
    from sparktorch_tpu.obs.collector import scrape_json
    from sparktorch_tpu.obs.profile import StackProfiler, top_frames

    t_start = time.perf_counter()
    workdir = tempfile.mkdtemp(prefix="bench_profile_")

    # -- leg 1: A/A overhead (profiler off vs on, interleaved) ---------
    m = 768
    step = jax.jit(lambda a: a @ a)
    xm = np.ones((m, m), np.float32)
    step(xm).block_until_ready()  # compile outside both arms
    tick_costs_us: List[float] = []

    def _arm(profiler_on: bool) -> float:
        prof = StackProfiler() if profiler_on else None
        if prof is not None:
            prof.start()
        walls = []
        try:
            for _ in range(n_steps):
                t0 = time.perf_counter()
                step(xm).block_until_ready()
                walls.append(time.perf_counter() - t0)
        finally:
            if prof is not None:
                doc = prof.stop()
                if doc["ticks"] <= 0:
                    raise AssertionError(
                        "profiler-on arm took no sample ticks")
                tick_costs_us.append(float(doc["sample_tick_us"]))
        return min(walls)

    offs, ons = [], []
    for _ in range(reps):
        offs.append(_arm(False))
        ons.append(_arm(True))
    w_off, w_on = min(offs), min(ons)
    overhead_frac = max(w_on - w_off, 0.0) / max(w_off, 1e-9)
    if overhead_frac >= 0.01:
        raise AssertionError(
            f"sampler overhead is {100 * overhead_frac:.2f}% of the "
            f"{w_off * 1e3:.3f}ms step wall (bound: 1%; on "
            f"{w_on * 1e3:.3f}ms vs off {w_off * 1e3:.3f}ms, min of "
            f"{reps} interleaved runs)")
    sample_tick_us = min(tick_costs_us)

    # -- leg 2: planted hot function owns its bucket -------------------
    tele0 = Telemetry(run_id="bench_profile_r0")
    prof0 = StackProfiler(telemetry=tele0, rank=0, hz=250.0,
                          publish_interval_s=0.2)
    prof0.start()
    try:
        with _goodput.span("compute"):
            _profile_hot_planted(time.perf_counter() + hot_s)
    finally:
        doc0 = prof0.stop()
    buckets0 = doc0.get("buckets") or {}
    if "compute" not in buckets0:
        raise AssertionError(
            f"no compute bucket sampled: {sorted(buckets0)}")
    frames = top_frames(doc0, "compute", n=3)
    if not frames or not frames[0][0].startswith("_profile_hot_planted"):
        raise AssertionError(
            f"planted hot function is not the compute bucket's top "
            f"self-time frame: {frames}")
    bucket_samples = int(buckets0["compute"].get("samples") or 0)
    hot_share = frames[0][1] / max(bucket_samples, 1)
    if hot_share < 0.8:
        raise AssertionError(
            f"planted function holds only {100 * hot_share:.1f}% of "
            f"the compute bucket's {bucket_samples} samples "
            f"(want >= 80%)")

    # -- leg 3: 2-rank merge over HTTP + timeline renders --------------
    tele1 = Telemetry(run_id="bench_profile_r1")
    prof1 = StackProfiler(telemetry=tele1, rank=1, hz=250.0,
                          publish_interval_s=0.2)
    release = threading.Event()

    def _rank1_waits():
        with _goodput.span("data_wait", {"site": "bench"}):
            release.wait(timeout=10.0)

    waiter = threading.Thread(target=_rank1_waits, daemon=True)
    waiter.start()
    prof1.start()
    time.sleep(0.3)
    release.set()
    waiter.join(timeout=5.0)
    prof1.stop()

    exp0 = GangMetricsExporter(telemetry=tele0, port=0).start()
    exp1 = GangMetricsExporter(telemetry=tele1, port=0).start()
    sink = os.path.join(workdir, "collector_sink.jsonl")
    collector = FleetCollector({0: exp0.url, 1: exp1.url},
                               poll_interval_s=0, jsonl_path=sink)
    collector.start(poll_loop=False)
    try:
        collector.poll()
        run_doc = scrape_json(f"{collector.url}/profile")
    finally:
        collector.stop()
        exp0.stop()
        exp1.stop()
    ranks_seen = set(run_doc.get("per_rank") or {})
    if not {"0", "1"} <= ranks_seen:
        raise AssertionError(
            f"/profile per_rank missing ranks: {sorted(ranks_seen)}")
    if "data_wait" not in (run_doc.get("buckets") or {}):
        raise AssertionError(
            f"rank1's data_wait bucket lost in the merge: "
            f"{sorted(run_doc.get('buckets') or {})}")
    merged_top = top_frames(run_doc, "compute", n=1)
    if not merged_top or \
            not merged_top[0][0].startswith("_profile_hot_planted"):
        raise AssertionError(
            f"merged /profile lost the planted frame: {merged_top}")

    saved = os.path.join(workdir, "profile.json")
    with open(saved, "w") as f:
        f.write(json.dumps(run_doc))
    for path, what in ((sink, "collector sink"),
                       (saved, "saved /profile doc")):
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = _timeline.main([path, "--profile"])
        out_txt = buf.getvalue()
        if rc != 0 or "_profile_hot_planted" not in out_txt:
            raise AssertionError(
                f"timeline --profile ({what}) failed (rc={rc}) or did "
                f"not name the planted frame:\n{out_txt[:800]}")

    # -- drift gate: per-tick sample cost vs prior rounds --------------
    tol = float(os.environ.get("SPARKTORCH_TPU_PROFILE_DRIFT_TOL", "1.0"))
    prior = _prior_window("profile", "sample_tick_us", k=3)
    if prior is None:
        drift = {"status": "no_prior_record", "tolerance": tol}
    else:
        drift = {
            "status": "checked", "tolerance": tol,
            "prior_median_us": round(prior["median"], 3),
            "prior_n": prior["n"],
            "ratio": round(sample_tick_us / max(prior["median"], 1e-9), 3),
        }
        if sample_tick_us > prior["median"] * (1.0 + tol) + 2.0:
            raise AssertionError(
                f"sample tick cost regressed: {sample_tick_us:.2f}us "
                f"vs prior windowed median {prior['median']:.2f}us "
                f"(past the {tol} relative tolerance + 2us floor); "
                f"drift: {drift}")

    return {
        "config": "profile", "unit": "us (sample tick cost)",
        "value": round(sample_tick_us, 3),
        "sample_tick_us": round(sample_tick_us, 3),
        "overhead_pct_of_step": round(100 * overhead_frac, 4),
        "step_wall_off_ms": round(w_off * 1e3, 3),
        "step_wall_on_ms": round(w_on * 1e3, 3),
        "hz": float(doc0["hz"]),
        "hot": {
            "ticks": doc0["ticks"],
            "bucket_samples": bucket_samples,
            "hot_share": round(hot_share, 4),
            "top_frame": frames[0][0],
        },
        "run_report": {
            "n_ranks": run_doc["n_ranks"],
            "samples_total": run_doc["samples_total"],
            "buckets": sorted(run_doc["buckets"]),
            "truncated": run_doc["truncated"],
        },
        "profile_drift": drift,
        "wall_s": round(time.perf_counter() - t_start, 2),
    }


def _health_replay_builder(n_features: int = 10, rows: int = 256) -> dict:
    """Replay builder for ``bench_health`` bundles (the
    ``module:function`` spec stamped into each bundle's meta):
    reconstruct the EXACT jitted step the drill leg trained with —
    same ModelSpec, same mesh, same optimizer — plus state/batch
    pytree TEMPLATES (treedefs and dtypes only; the recorded leaf
    values come from the bundle's npz). The live drill pins
    ``steps_per_call=1``/``mini_batch=None`` so both processes compile
    the same single-step XLA program, which is what makes the bitwise
    comparison meaningful."""
    import jax
    import jax.numpy as jnp

    from sparktorch_tpu.models import Net
    from sparktorch_tpu.parallel.mesh import build_mesh
    from sparktorch_tpu.train.step import create_train_state, make_train_step
    from sparktorch_tpu.utils.data import DataBatch
    from sparktorch_tpu.utils.serde import ModelSpec

    spec = ModelSpec(module=Net(), loss="mse", optimizer="adam",
                     optimizer_params={"lr": 1e-2},
                     input_shape=(n_features,))
    mesh = build_mesh()
    tx = spec.make_optimizer()
    state = create_train_state(
        spec, jax.random.key(0),
        sample_x=jnp.zeros((1, n_features), jnp.float32), tx=tx)
    step_fn = make_train_step(spec.make_module().apply, spec.loss_fn(),
                              tx, mesh)
    batch = DataBatch(
        x=jnp.zeros((rows, n_features), jnp.float32),
        y=jnp.zeros((rows,), jnp.float32),
        w=jnp.ones((rows,), jnp.float32))
    return {"step_fn": step_fn, "state": state, "batch": batch}


def bench_health(poison_step: int = 6, iters: int = 12,
                 aa_steps: int = 20, aa_reps: int = 3) -> dict:
    """Model-health observability gate (``make bench-health``) — FAILS
    (raises) unless the health lane's four claims hold end to end:

    - **detection is real and bounded**: a seeded poison batch
      (``ChaosConfig.poison_batch_at``) on a real ``train_distributed``
      run trips the NaN sentinel AT the poisoned step, within 2 steps
      of the delayed fetch (``detect_lag - fetch_lag <= 2``), with the
      per-leaf grad-norm table carrying dotted param names; the
      latched ``health_nonfinite`` alert fires exactly ONE episode
      across repeated sweeps;
    - **replay is bitwise**: the bundle the sentinel wrote reproduces
      the recorded bad numerics in a FRESH process
      (``python -m sparktorch_tpu.obs.replay`` exits 0, float32 bit
      patterns equal — the only comparison two NaNs can pass);
    - **the lane is attributed and nearly free**: an interleaved A/A
      pair shows the health-on arm's goodput ledger with
      ``data_wait`` > 0 (the delayed fetch lands in
      ``data_wait{site=health}``) while the health-off arm's is
      EXACTLY 0.0, step wall grows < 1% (min of interleaved runs),
      and a clean run raises ZERO anomalies and ZERO alert episodes;
    - **the fleet path works**: the drill rank's section merges into
      ``GET /health`` rank-tagged (never averaged), renders via
      ``timeline --health`` from both the collector sink and a saved
      document, surfaces in ``--follow`` as a ``health.run``
      one-liner, and the postmortem bundle answers "health at death".

    ``note_step`` cost is the drift-gated value
    (``SPARKTORCH_TPU_HEALTH_DRIFT_TOL`` vs the windowed median of
    prior rounds).
    """
    import contextlib
    import io
    import os
    import subprocess
    import sys
    import tempfile
    import threading

    import jax
    import jax.numpy as jnp

    from sparktorch_tpu.ft import ChaosConfig, inject
    from sparktorch_tpu.models import Net
    from sparktorch_tpu.native.gang import GangMetricsExporter
    from sparktorch_tpu.obs import FleetCollector, Telemetry
    from sparktorch_tpu.obs import goodput as _goodput
    from sparktorch_tpu.obs import health as _health
    from sparktorch_tpu.obs import timeline as _timeline
    from sparktorch_tpu.obs.alerts import AlertManager
    from sparktorch_tpu.obs.blackbox import collect_postmortem
    from sparktorch_tpu.obs.collector import scrape_json
    from sparktorch_tpu.obs.history import MetricsHistory
    from sparktorch_tpu.obs.telemetry import wall_ts as _wall_ts
    from sparktorch_tpu.train.sync import train_distributed
    from sparktorch_tpu.utils.serde import ModelSpec

    t_start = time.perf_counter()
    workdir = tempfile.mkdtemp(prefix="bench_health_")
    replay_dir = os.path.join(workdir, "replay")

    # -- leg 1: A/A overhead + attribution delta (clean workload) ------
    # Runs FIRST, in a quiet process (same discipline as
    # bench_profile's A/A): the drill leg's jit/teardown residue
    # would pollute the timing floor. The ledger's per-step cost is
    # FIXED (queue + one delayed scalar fetch, ~tens of us), so quote
    # it against a training-representative step wall: a chained-matmul
    # step (~25ms on this rig's CPU floor) whose timing floor is
    # stable enough for a 1% bound — a single small matmul is both too
    # short (the fixed cost alone busts 1%) and too noisy.
    m = 768

    def _aa_fn(a):
        b = a
        for _ in range(4):
            b = (b @ a) * (1.0 / m)
        return b, (jnp.sum(b) / b.size).astype(jnp.float32)

    aa_step = jax.jit(_aa_fn)
    xm = np.ones((m, m), np.float32)
    out, _ = aa_step(xm)
    out.block_until_ready()  # compile outside both arms

    def _aa_arm(health_on: bool):
        tele_a = Telemetry(
            run_id=f"bench_health_aa_{'on' if health_on else 'off'}")
        led = _goodput.GoodputLedger(telemetry=tele_a, rank="aa")
        hl_a = (_health.TrainHealthLedger(rank="aa", telemetry=tele_a)
                if health_on else None)
        walls, notes = [], []
        with led.activate():
            for _ in range(aa_steps):
                t0 = time.perf_counter()
                o, dev = aa_step(xm)
                o.block_until_ready()
                if hl_a is not None:
                    t1 = time.perf_counter()
                    hl_a.note_step(device={"loss": dev})
                    notes.append(time.perf_counter() - t1)
                walls.append(time.perf_counter() - t0)
            if hl_a is not None:
                hl_a.flush()
        gdoc_a = tele_a.get_section(_goodput.SECTION)
        dw = float(gdoc_a["buckets"]["data_wait"])
        n_anom = (len(hl_a.snapshot()["anomalies"])
                  if hl_a is not None else 0)
        return min(walls), dw, n_anom, notes, tele_a

    gc.collect()
    offs, ons, dw_on, note_walls = [], [], [], []
    tele_clean = None
    for _ in range(aa_reps):
        w, dw, _n, _notes, _t = _aa_arm(False)
        offs.append(w)
        if dw != 0.0:
            raise AssertionError(
                f"health-OFF arm shows data_wait {dw}s — the A/A delta "
                f"is meaningless")
        w, dw, n_anom, notes, tele_clean = _aa_arm(True)
        ons.append(w)
        dw_on.append(dw)
        note_walls += notes
        if n_anom:
            raise AssertionError(
                f"clean health-ON arm raised {n_anom} anomalies — "
                f"false positives")
    if min(dw_on) <= 0.0:
        raise AssertionError(
            f"health-ON arms left data_wait empty ({dw_on}) — the "
            f"delayed fetch is not being attributed")
    # Two witnesses for the 1% bound, either passes: (a) the wall
    # delta of the interleaved A/A pair (min of reps per arm) — the
    # end-to-end statement, but this rig's floor breathes several
    # percent between IDENTICAL arms (a bare even/odd A/A with no
    # ledger shows 1-6% gaps), so on a noisy round it over-reads; (b)
    # the direct witness from the same ON-arm samples: the ledger's
    # entire synchronous footprint is the note_step call (queue + the
    # drained delayed fetch), so its floor against the step-wall floor
    # bounds the true per-step cost without differencing two noisy
    # walls. Fail only when BOTH read over 1%.
    w_off, w_on = min(offs), min(ons)
    aa_frac = max(w_on - w_off, 0.0) / max(w_off, 1e-9)
    note_frac = min(note_walls) / max(w_off, 1e-9)
    overhead_frac = min(aa_frac, note_frac)
    if overhead_frac >= 0.01:
        raise AssertionError(
            f"health lane overhead is over 1% of the "
            f"{w_off * 1e3:.3f}ms step wall by BOTH witnesses: A/A "
            f"wall delta {100 * aa_frac:.2f}% (on {w_on * 1e3:.3f}ms "
            f"vs off {w_off * 1e3:.3f}ms, min of {aa_reps} interleaved "
            f"runs) and direct note_step floor {100 * note_frac:.2f}% "
            f"({min(note_walls) * 1e6:.1f}us)")
    # Zero false positives also at the alert tier: a clean bus sweeps
    # without a single episode.
    clean_hist = MetricsHistory(retention=4)
    clean_mgr = AlertManager(clean_hist, rules=_health.health_alert_rules(),
                             telemetry=tele_clean)
    clean_fired = []
    base_aa = _wall_ts()
    for k in range(2):
        clean_hist.append(tele_clean.snapshot(), ts=base_aa + k)
        clean_fired += [e for e in clean_mgr.evaluate(ts=base_aa + k)
                        if e["event"] == "fired"]
    if clean_fired:
        raise AssertionError(
            f"clean leg fired alerts: "
            f"{[e['alert'] for e in clean_fired]}")

    # -- leg 2: seeded poison drill on a real trainer ------------------
    rng = np.random.default_rng(0)
    n_features, rows = 10, 256
    x = rng.normal(size=(rows, n_features)).astype(np.float32)
    y = rng.normal(size=(rows,)).astype(np.float32)
    spec = ModelSpec(module=Net(), loss="mse", optimizer="adam",
                     optimizer_params={"lr": 1e-2},
                     input_shape=(n_features,))
    tele = Telemetry(run_id="bench_health_drill")
    cfg = _health.HealthConfig(
        warmup_steps=3, replay_dir=replay_dir,
        replay_builder="sparktorch_tpu.bench:_health_replay_builder",
        replay_builder_kwargs={"n_features": n_features, "rows": rows})
    prev_hl = _health.install(None)
    try:
        hl = _health.ensure(tele, rank=0, config=cfg)
        if hl is None:
            raise AssertionError(
                "health lane disabled (SPARKTORCH_TPU_HEALTH=0) — the "
                "gate cannot run")
        ledger = _goodput.GoodputLedger(telemetry=tele, rank=0)
        with ledger.activate(), \
                inject(ChaosConfig(poison_batch_at={0: poison_step}),
                       telemetry=tele):
            train_distributed(spec, x, labels=y, iters=iters, seed=0,
                              steps_per_call=1, telemetry=tele)
        doc = hl.snapshot()
    finally:
        _health.install(prev_hl)

    anomalies = doc["anomalies"]
    if not anomalies:
        raise AssertionError(
            f"poisoned step {poison_step} raised no anomaly: {doc}")
    first = anomalies[0]
    if first["akind"] != "nonfinite" or first["step"] != poison_step:
        raise AssertionError(
            f"first anomaly is {first['akind']} @ step {first['step']}, "
            f"want nonfinite @ {poison_step}: {anomalies[:3]}")
    lag_past_fetch = first["detect_lag"] - cfg.fetch_lag
    if not (0 <= lag_past_fetch <= 2):
        raise AssertionError(
            f"detection lag {first['detect_lag']} steps vs fetch_lag "
            f"{cfg.fetch_lag}: the sentinel must trip within 2 steps "
            f"of the delayed fetch")
    leaves = doc.get("top_grad_leaves") or []
    if not leaves or not any("." in str(k) for k, _ in leaves):
        raise AssertionError(
            f"top grad leaves lack dotted param names: {leaves}")

    # The drill's own readbacks must be attributed: data_wait carries
    # the health fetch (site=health) and the ledger stays MECE.
    gdoc = tele.get_section(_goodput.SECTION)
    if float(gdoc["buckets"]["data_wait"]) <= 0.0:
        raise AssertionError(
            f"health fetches left data_wait empty: {gdoc['buckets']}")
    g_wall = float(gdoc["wall_s"])
    g_total = sum(float(v) for v in gdoc["buckets"].values())
    if abs(g_total - g_wall) > 0.02 * g_wall or \
            float(gdoc["overattributed_s"]) > 0.02 * g_wall:
        raise AssertionError(
            f"drill ledger not MECE: buckets sum {g_total:.3f}s vs "
            f"wall {g_wall:.3f}s, overattributed "
            f"{gdoc['overattributed_s']}s")

    # -- leg 3: the bundle replays BITWISE in a fresh process ----------
    bundles = (doc.get("replay") or {}).get("bundles") or []
    target = f"replay_step{poison_step:06d}_r0.json"
    meta_path = next((b for b in bundles
                      if os.path.basename(b) == target), None)
    if meta_path is None:
        raise AssertionError(
            f"no bundle for the poisoned step {poison_step}: {bundles}")
    with open(meta_path) as f:
        meta = json.load(f)
    if meta["anchor_step"] != poison_step:
        raise AssertionError(
            f"anchor did not re-arm on the poisoned batch: anchor "
            f"{meta['anchor_step']} vs step {poison_step} (replay "
            f"would span {meta['step'] - meta['anchor_step'] + 1} steps)")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "sparktorch_tpu.obs.replay", meta_path],
        capture_output=True, text=True, env=env, timeout=600)
    if proc.returncode != 0 or "bitwise reproduction" not in proc.stdout:
        raise AssertionError(
            f"replay did not reproduce bitwise (rc={proc.returncode}):\n"
            f"{proc.stdout}\n{proc.stderr}")

    # -- leg 4: latched alert, exactly one episode ---------------------
    history = MetricsHistory(retention=8)
    mgr = AlertManager(history, rules=_health.health_alert_rules(),
                       telemetry=tele)
    base = _wall_ts()
    fired = []
    for k in range(3):
        history.append(tele.snapshot(), ts=base + k)
        fired += [e for e in mgr.evaluate(ts=base + k)
                  if e["event"] == "fired"]
    if [e["alert"] for e in fired] != ["health_nonfinite"]:
        raise AssertionError(
            f"want exactly one latched health_nonfinite episode over 3 "
            f"sweeps, got {[(e['alert'], e['episode']) for e in fired]}")

    # -- leg 5: collector merge, GET /health, timeline renders ---------
    exp = GangMetricsExporter(telemetry=tele, port=0).start()
    sink = os.path.join(workdir, "collector_sink.jsonl")
    collector = FleetCollector({0: exp.url}, poll_interval_s=0,
                               jsonl_path=sink)
    collector.start(poll_loop=False)
    try:
        collector.poll()
        run_doc = scrape_json(f"{collector.url}/health")
        pm_path = collect_postmortem(workdir, "bench-health drill",
                                     telemetry=tele, collector=collector)
    finally:
        collector.stop()
        exp.stop()
    if run_doc.get("kind") != "health_run" or \
            "0" not in (run_doc.get("per_rank") or {}):
        raise AssertionError(
            f"/health missing the drill rank: "
            f"{sorted(run_doc.get('per_rank') or {})}")
    worst = run_doc.get("worst") or {}
    if worst.get("akind") != "nonfinite" or worst.get("rank") != "0":
        raise AssertionError(
            f"/health worst anomaly is not the rank-tagged NaN: {worst}")

    saved = os.path.join(workdir, "health.json")
    with open(saved, "w") as f:
        f.write(json.dumps(run_doc))
    for args_, what in ((["--health", sink], "collector sink"),
                        (["--health", saved], "saved /health doc")):
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = _timeline.main(args_)
        out_txt = buf.getvalue()
        if rc != 0 or "model health" not in out_txt \
                or "nonfinite" not in out_txt:
            raise AssertionError(
                f"timeline --health ({what}) failed (rc={rc}) or lost "
                f"the anomaly:\n{out_txt[:800]}")

    stop_ev = threading.Event()
    stop_ev.set()
    follow_lines = list(_timeline.follow(sink, poll_s=0.0, stop=stop_ev))
    if not any("health.run" in ln and "worst=nonfinite" in ln
               for ln in follow_lines):
        raise AssertionError(
            f"--follow tail lacks the health.run one-liner:\n"
            + "\n".join(follow_lines[:10]))

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = _timeline.main(["--postmortem", pm_path])
    out_txt = buf.getvalue()
    if rc != 0 or "model health at death" not in out_txt \
            or "nonfinite" not in out_txt:
        raise AssertionError(
            f"postmortem lost the health-at-death view (rc={rc}):\n"
            f"{out_txt[:800]}")

    # -- note_step microbench (the drift-gated value) ------------------
    hl_ub = _health.TrainHealthLedger(
        rank="ub", telemetry=Telemetry(run_id="bench_health_ub"))
    n_ub = 2000
    t0 = time.perf_counter()
    for i in range(n_ub):
        hl_ub.note_step(host={"loss": 1.0 + 1e-4 * i, "grad_norm": 0.5})
    note_step_us = (time.perf_counter() - t0) / n_ub * 1e6

    tol = float(os.environ.get("SPARKTORCH_TPU_HEALTH_DRIFT_TOL", "0.5"))
    prior = _prior_window("health", "note_step_us", k=3)
    if prior is None:
        drift = {"status": "no_prior_record", "tolerance": tol}
    else:
        drift = {"status": "ok", "tolerance": tol, "prior": prior,
                 "value": round(note_step_us, 3)}
        if note_step_us > prior["median"] * (1.0 + tol) + 2.0:
            drift["status"] = "regressed"
            raise AssertionError(
                f"note_step cost regressed: {note_step_us:.2f}us vs "
                f"prior windowed median {prior['median']:.2f}us (past "
                f"the {tol} relative tolerance + 2us floor); "
                f"drift: {drift}")

    return {
        "config": "health", "unit": "us (note_step cost)",
        "value": round(note_step_us, 3),
        "note_step_us": round(note_step_us, 3),
        "overhead_pct_of_step": round(100 * overhead_frac, 4),
        "overhead_pct_aa_wall": round(100 * aa_frac, 4),
        "overhead_pct_note_floor": round(100 * note_frac, 4),
        "step_wall_off_ms": round(w_off * 1e3, 3),
        "step_wall_on_ms": round(w_on * 1e3, 3),
        "detect": {
            "step": poison_step, "akind": first["akind"],
            "detect_lag": first["detect_lag"],
            "fetch_lag": cfg.fetch_lag,
            "anomalies_total": sum(doc["counts"].values()),
        },
        "replay": {
            "bundle": os.path.basename(meta_path),
            "anchor_step": meta["anchor_step"],
            "bitwise": True,
        },
        "aa": {
            "data_wait_on_s": round(min(dw_on), 6),
            "data_wait_off_s": 0.0,
            "clean_anomalies": 0,
        },
        "alerts": {"episodes": 1, "clean_episodes": 0},
        "health_drift": drift,
        "wall_s": round(time.perf_counter() - t_start, 2),
    }


def bench_skew(steps: int = 8, delay_s: float = 0.3,
               from_step: int = 2, stamp_iters: int = 4000) -> dict:
    """Cross-rank step-skew gate (``make bench-skew``) — FAILS (raises)
    unless the skew lane's claims hold end to end:

    - **decomposition is real and lands on the right rank**: a seeded
      ``delay_s``/step straggler on rank 1 (``ChaosConfig.slow_rank_s``,
      fired inside the step loop BEFORE the collective fence) shows up
      in the merged ``GET /skew`` document with >=80% of the injected
      seconds in ``straggler_wait_s``, charged to rank 1 in
      ``wait_by_laggard``, straggler wait dominating wire, and the
      persistent-laggard verdict naming rank 1 with a cause hypothesis;
    - **the alert reaches the controller**: the sustained
      ``skew_straggler_sustained`` rule latches exactly ONE episode
      across repeated collector sweeps, and the firing arrives at an
      ``ElasticController`` as a ``ctl.scale_signal``;
    - **the A/A leg stays quiet**: the identical fence workload with no
      chaos decomposes to ~0 straggler wait with ZERO alert episodes —
      a healthy fleet never pages;
    - **stamping is nearly free**: the per-step boundary stamp (the
      only new work this lane adds to the hot step path — one bounded
      ring append at ``step_span`` exit) costs <1% of a
      training-representative step wall;
    - **the render path works**: ``timeline --skew`` renders the
      verdict from both the collector sink JSONL and a saved ``/skew``
      document, and ``--follow`` emits the ``skew.run`` one-liner
      naming the laggard.

    The stamp cost is the drift-gated value
    (``SPARKTORCH_TPU_SKEW_DRIFT_TOL`` vs the windowed median of prior
    rounds).
    """
    import contextlib
    import io
    import os
    import tempfile
    import threading

    import jax

    from sparktorch_tpu.ctl.elastic import ElasticController
    from sparktorch_tpu.ft import ChaosConfig, inject
    from sparktorch_tpu.ft import chaos as _chaos
    from sparktorch_tpu.native.gang import GangMetricsExporter
    from sparktorch_tpu.obs import FleetCollector, Telemetry
    from sparktorch_tpu.obs import goodput as _goodput
    from sparktorch_tpu.obs import skew as _skew
    from sparktorch_tpu.obs import timeline as _timeline
    from sparktorch_tpu.obs.collector import scrape_json

    t_start = time.perf_counter()
    workdir = tempfile.mkdtemp(prefix="bench_skew_")
    injected_total = delay_s * (steps - from_step)

    def _fleet_leg(tag: str, chaos_cfg):
        """One 2-rank fence workload scraped through a collector with
        the skew rules armed and an ElasticController subscribed:
        returns (run_doc, latched episodes, scale signals, sink path).

        The rank threads stamp the exact shape the trainers do — chaos
        fires BEFORE the step span (a real straggler is late INTO the
        fence), the fence wait rides a nested exposed_comm span inside
        ``step_span`` (so the victim's wait is in the merged
        exposed_comm budget the decomposition splits)."""
        teles = [Telemetry(run_id=f"bench_skew_{tag}") for _ in range(2)]
        leds = [_goodput.GoodputLedger(telemetry=teles[r], rank=r)
                for r in range(2)]
        barrier = threading.Barrier(2)
        errs: list = []

        def rank_fn(r):
            try:
                led = leds[r]
                for i in range(steps):
                    _chaos.straggle(r, i)
                    with led.step_span(step=i):
                        with led.span("exposed_comm"):
                            barrier.wait()
                led.close()
            except Exception as e:  # noqa: BLE001 - surfaced below
                errs.append(e)

        threads = [threading.Thread(target=rank_fn, args=(r,))
                   for r in range(2)]
        cm = (inject(chaos_cfg, telemetry=teles[0]) if chaos_cfg
              else contextlib.nullcontext())
        with cm:
            for th in threads:
                th.start()
            for th in threads:
                th.join()
        if errs:
            raise AssertionError(f"{tag} rank thread died: {errs[0]!r}")

        exps = [GangMetricsExporter(telemetry=teles[r], port=0).start()
                for r in range(2)]
        sink = os.path.join(workdir, f"sink_{tag}.jsonl")
        collector = FleetCollector(
            {r: exps[r].url for r in range(2)}, poll_interval_s=0,
            jsonl_path=sink, alert_rules=_skew.skew_alert_rules())
        ctl = ElasticController([], lambda w: True,
                                telemetry=collector.telemetry,
                                alerts=collector.alerts)
        collector.start(poll_loop=False)
        try:
            # The sustained rule wants for_sweeps consecutive breaches;
            # one extra sweep proves the latch holds at ONE episode.
            for _ in range(4):
                collector.poll()
            run_doc = scrape_json(f"{collector.url}/skew")
        finally:
            collector.stop()
            for e in exps:
                e.stop()
            ctl.detach_alerts()
        state = collector.alerts.doc()["rules"]["skew_straggler_sustained"]
        return run_doc, int(state["episodes"]), list(ctl.scale_signals), sink

    # -- leg 1: A/A — identical fence, no chaos, must stay quiet -------
    aa_run, aa_eps, aa_signals, _aa_sink = _fleet_leg("aa", None)
    aa_wait = float(aa_run.get("straggler_wait_s") or 0.0)
    if aa_wait > 0.1 * injected_total:
        raise AssertionError(
            f"A/A leg shows {aa_wait:.3f}s straggler wait (injected "
            f"nothing; bound {0.1 * injected_total:.3f}s) — the "
            f"decomposition charges healthy fence jitter as straggling")
    if aa_eps or aa_signals:
        raise AssertionError(
            f"A/A leg paged: {aa_eps} alert episode(s), "
            f"{len(aa_signals)} scale signal(s) — false positives")

    # -- leg 2: seeded straggler on rank 1 -----------------------------
    chaos_run, chaos_eps, chaos_signals, chaos_sink = _fleet_leg(
        "chaos", ChaosConfig(slow_rank_s={1: (from_step, delay_s)}))
    wait = float(chaos_run.get("straggler_wait_s") or 0.0)
    if wait < 0.8 * injected_total:
        raise AssertionError(
            f"injected {injected_total:.2f}s of straggling but only "
            f"{wait:.3f}s landed in straggler_wait_s (<80%) — the "
            f"decomposition is leaking the wait into wire time")
    wire = chaos_run.get("wire_s")
    if wire is None or wait <= float(wire):
        raise AssertionError(
            f"straggler wait {wait:.3f}s does not dominate wire "
            f"{wire} — exposed_comm was not split")
    to_r1 = float((chaos_run.get("wait_by_laggard") or {}).get("1") or 0.0)
    if to_r1 < 0.8 * injected_total:
        raise AssertionError(
            f"only {to_r1:.3f}s of the {injected_total:.2f}s injected "
            f"wait is charged to rank 1: "
            f"{chaos_run.get('wait_by_laggard')}")
    lag = chaos_run.get("laggard") or {}
    if lag.get("rank") != "1" or not lag.get("persistent") \
            or not lag.get("cause"):
        raise AssertionError(
            f"verdict did not name rank 1 as a persistent straggler "
            f"with a cause hypothesis: {lag}")

    # -- leg 3: latched alert -> controller scale signal ---------------
    if chaos_eps != 1:
        raise AssertionError(
            f"want exactly one latched skew_straggler_sustained "
            f"episode over 4 sweeps, got {chaos_eps}")
    if not any(s.get("rule") == "skew_straggler_sustained"
               for s in chaos_signals):
        raise AssertionError(
            f"the latched firing never reached the ElasticController "
            f"as a ctl.scale_signal: {chaos_signals}")

    # -- leg 4: timeline renders from sink + saved doc, follow line ----
    saved = os.path.join(workdir, "skew.json")
    with open(saved, "w") as f:
        f.write(json.dumps(chaos_run))
    for args_, what in ((["--skew", chaos_sink], "collector sink"),
                        (["--skew", saved], "saved /skew doc")):
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = _timeline.main(args_)
        out_txt = buf.getvalue()
        if rc != 0 or "step skew" not in out_txt \
                or "persistent straggler" not in out_txt:
            raise AssertionError(
                f"timeline --skew ({what}) failed (rc={rc}) or lost "
                f"the verdict:\n{out_txt[:800]}")
    stop_ev = threading.Event()
    stop_ev.set()
    follow_lines = list(_timeline.follow(chaos_sink, poll_s=0.0,
                                         stop=stop_ev))
    if not any("skew.run" in ln and "laggard=rank 1" in ln
               for ln in follow_lines):
        raise AssertionError(
            f"--follow tail lacks the skew.run one-liner:\n"
            + "\n".join(follow_lines[:10]))

    # -- stamp microbench (the drift-gated value) ----------------------
    # The ONLY work this lane adds to the hot step path: one bounded
    # ring append at step_span exit (the enter/exit perf_counter reads
    # already existed for the goodput bucket). Quote it against a
    # training-representative step wall, same discipline as
    # bench_health: the fence microbench above is all-wait, so its
    # wall is not a denominator any trainer would recognize.
    led_ub = _goodput.GoodputLedger(
        telemetry=Telemetry(run_id="bench_skew_ub"), rank="ub")
    t0 = time.perf_counter()
    for i in range(stamp_iters):
        led_ub.skew.record(i, 1, 0.0, 1.0)
    stamp_us = (time.perf_counter() - t0) / stamp_iters * 1e6

    m = 768
    rep = jax.jit(lambda a: (a @ a) @ (a @ a) * (1.0 / m))
    xm = np.ones((m, m), np.float32)
    rep(xm).block_until_ready()  # compile outside the measurement
    rep_walls = []
    for _ in range(5):
        t0 = time.perf_counter()
        rep(xm).block_until_ready()
        rep_walls.append(time.perf_counter() - t0)
    step_wall = min(rep_walls)
    stamp_frac = (stamp_us * 1e-6) / max(step_wall, 1e-9)
    if stamp_frac >= 0.01:
        raise AssertionError(
            f"step stamp costs {stamp_us:.2f}us — "
            f"{100 * stamp_frac:.3f}% of the {step_wall * 1e3:.3f}ms "
            f"representative step wall (>=1%)")

    tol = float(os.environ.get("SPARKTORCH_TPU_SKEW_DRIFT_TOL", "0.5"))
    prior = _prior_window("skew", "stamp_us", k=3)
    if prior is None:
        drift = {"status": "no_prior_record", "tolerance": tol}
    else:
        drift = {"status": "ok", "tolerance": tol, "prior": prior,
                 "value": round(stamp_us, 3)}
        if stamp_us > prior["median"] * (1.0 + tol) + 2.0:
            drift["status"] = "regressed"
            raise AssertionError(
                f"step stamp cost regressed: {stamp_us:.2f}us vs prior "
                f"windowed median {prior['median']:.2f}us (past the "
                f"{tol} relative tolerance + 2us floor); drift: {drift}")

    return {
        "config": "skew", "unit": "us (step stamp cost)",
        "value": round(stamp_us, 3),
        "stamp_us": round(stamp_us, 3),
        "stamp_pct_of_step": round(100 * stamp_frac, 4),
        "step_wall_ms": round(step_wall * 1e3, 3),
        "decomposition": {
            "injected_s": round(injected_total, 3),
            "straggler_wait_s": round(wait, 3),
            "wire_s": round(float(wire), 3),
            "straggler_fraction": chaos_run.get("straggler_fraction"),
            "attributed_to_rank1_s": round(to_r1, 3),
            "laggard": {"rank": lag.get("rank"),
                        "persistent": bool(lag.get("persistent")),
                        "cause": lag.get("cause")},
        },
        "aa": {"straggler_wait_s": round(aa_wait, 6), "episodes": 0,
               "scale_signals": 0},
        "alerts": {"episodes": 1, "scale_signals": len(chaos_signals)},
        "skew_drift": drift,
        "wall_s": round(time.perf_counter() - t_start, 2),
    }


def _bert_flops_accounting(module, batch: int, seq: int) -> dict:
    """Honest model-FLOPs accounting for the BERT classifier.

    The round-4 record applied 6·N_total·T, which counts the 23.4M-param
    token-embedding table (and pos-embed) as if every token did a matmul
    against it — but an embedding lookup is a gather, and its backward a
    scatter-add: zero MXU FLOPs. Honest accounting (the standard
    PaLM-appendix / scaling-book decomposition):

      fwd  = 2·N_tok·T  +  4·L·b·s²·d  +  2·N_head·b
      step = 3·fwd                       (backward ≈ 2× forward)

    where N_tok = params applied per token (encoder layers + final LN),
    N_head = params applied per EXAMPLE (pooler + classifier — the 6N·T
    rule overcounts these by s×), and 4·L·b·s²·d is the QKᵀ + AV score
    math the 6N rule misses entirely. The legacy 6N-total number is kept
    alongside for round-over-round comparability."""
    import jax

    params = module.init(jax.random.key(0),
                         np.zeros((1, seq), np.int32))["params"]

    def _count(tree) -> int:
        return sum(int(np.prod(p.shape)) for p in jax.tree.leaves(tree))

    n_total = _count(params)
    backbone = params["backbone"]
    n_emb = (_count(backbone["tok_embed"])
             + int(np.prod(backbone["pos_embed"].shape)))
    n_head = _count(params["pooler"]) + _count(params["classifier"])
    n_tok = n_total - n_emb - n_head

    cfg = module.config
    tokens = batch * seq
    attn_fwd = 4 * cfg.n_layers * batch * seq * seq * cfg.d_model
    fwd = 2 * n_tok * tokens + attn_fwd + 2 * n_head * batch
    return {
        "n_params": n_total,
        "n_params_embedding": n_emb,
        "n_params_per_token": n_tok,
        "n_params_per_example_head": n_head,
        "model_flops_per_step": 3 * fwd,
        "legacy_6n_total_flops_per_step": 6 * n_total * tokens,
        "flops_methodology": (
            "3*(2*N_tok*T + 4*L*b*s^2*d + 2*N_head*b): matmul params per "
            "token (embedding gather/scatter and per-example head "
            "excluded from the per-token term) + attention QK^T/AV score "
            "FLOPs; bwd=2x fwd. Cross-checked against XLA "
            "compiled.cost_analysis() flops of the same program."
        ),
    }


def bench_bert_dp() -> dict:
    """BASELINE config 4: BERT-base-shape encoder fine-tune step,
    sync DP — the compute-bound all-reduce stress config. MFU is
    reported with HONEST model-FLOPs (``_bert_flops_accounting``) and
    cross-checked against XLA's own ``cost_analysis`` of the measured
    program; the round-≤4 6N·N_total number rides along as
    ``achieved_tflops_6n_total_legacy``."""
    from sparktorch_tpu.models.transformer import bert_base
    from sparktorch_tpu.utils.serde import ModelSpec

    batch, seq = 128, 128  # batch swept 32/64/128: MXU util peaks here
    rng = np.random.default_rng(0)
    x = rng.integers(0, 30522, (batch, seq)).astype(np.int32)
    y = rng.integers(0, 2, (batch,)).astype(np.int32)
    module = bert_base()
    spec = ModelSpec(module=module, loss="cross_entropy", optimizer="adam",
                     optimizer_params={"lr": 2e-5}, input_shape=(seq,))
    out = _sync_epoch_bench(spec, x, y, batch, iters=10, warmup=2, chunks=3,
                            with_cost_analysis=True)

    acct = _bert_flops_accounting(module, batch, seq)
    steps_per_sec = out["examples_per_sec_per_chip"] * out["n_chips"] / batch
    step_s = 1.0 / max(steps_per_sec, 1e-12)

    def _tflops(flops_per_step: float) -> float:
        return flops_per_step * steps_per_sec / out["n_chips"] / 1e12

    honest = _tflops(acct["model_flops_per_step"])
    peaks = _device_peaks()
    rec = {
        "config": "bert_dp", "unit": "examples/sec/chip",
        "n_params": acct["n_params"],
        "n_params_embedding": acct["n_params_embedding"],
        "n_params_per_token": acct["n_params_per_token"],
        "achieved_tflops_per_chip": round(honest, 2),
        "mfu_honest": (round(_mfu_honest(honest, peaks["bf16_tflops"]), 4)
                       if peaks else None),
        "achieved_tflops_6n_total_legacy": round(
            _tflops(acct["legacy_6n_total_flops_per_step"]), 2
        ),
        "flops_methodology": acct["flops_methodology"],
        **out,
    }
    # Roofline cross-check from the compiler's own cost model: the
    # minimum step time this program could take on the device is
    # max(flops/peak_flops, bytes/peak_bw); how close the measured step
    # comes to that bound says whether the gap to peak is the PROGRAM
    # (non-matmul ops, bandwidth) or the EXECUTION (stalls, overhead).
    if out.get("xla_flops_per_step") and peaks:
        # cost_analysis flops are PER-DEVICE (see _xla_cost_per_step),
        # so the achieved rate needs no n_chips division.
        xla_flops = out["xla_flops_per_step"]
        rec["xla_tflops_per_chip"] = round(xla_flops / step_s / 1e12, 2)
        t_flops = xla_flops / (peaks["bf16_tflops"] * 1e12)
        t_bytes = ((out["xla_bytes_per_step"] or 0)
                   / (peaks["hbm_gb_per_s"] * 1e9))
        rec["roofline_min_step_s"] = round(max(t_flops, t_bytes), 6)
        rec["roofline_bound"] = "flops" if t_flops >= t_bytes else "bytes"
        rec["roofline_attainment"] = round(
            max(t_flops, t_bytes) / step_s, 4
        )
    return rec


def bench_resnet50_inference() -> dict:
    """BASELINE config 5: ResNet-50 batch inference — MEASURED via the
    columnar-ingest -> device streaming path (Parquet row groups of
    raw uint8 pixels -> reader thread -> host->device uint8 wire ->
    normalize + forward + device-side argmax, double-buffered).

    Numbers reported:
    - `stream_rows_per_sec`: sustained end-to-end rate of THIS run
      (a few thousand rows so the suite stays fast);
    - `chip_rate_rows_per_sec_per_chip`: device-resident compute rate
      (the per-chip ceiling when data streams from colocated hosts);
    - `measured_run_*`: the LARGEST >=100k-row measured run on record
      in the benchmarks/ JSONL logs (the r04 1M-row run from
      benchmarks/stream_inference_1m.py once it has landed) — the
      honest long-haul number."""
    import os
    import tempfile

    import jax
    import jax.numpy as jnp

    from sparktorch_tpu.inference import (
        BatchPredictor,
        stream_parquet_predict,
        write_rows_parquet,
    )
    from sparktorch_tpu.models.resnet import resnet50
    from sparktorch_tpu.obs import get_telemetry

    tele = get_telemetry()
    module = resnet50()
    rng = np.random.default_rng(0)
    chunk = 256
    n_stream = chunk * 8
    with tempfile.TemporaryDirectory() as d:
        with tele.span("bench/data") as _sp_data:
            x = rng.integers(0, 256, (chunk * 4, 224, 224, 3),
                             dtype=np.uint8)
            path = os.path.join(d, "bench_stream.parquet")
            write_rows_parquet(
                path,
                (rng.integers(0, 256, (chunk, 224, 224, 3), dtype=np.uint8)
                 for _ in range(n_stream // chunk)),
                rows_per_group=chunk,
            )
        with tele.span("bench/init") as _sp_init:
            variables = module.init(jax.random.key(0),
                                    np.zeros((1, 224, 224, 3), np.float32))
            predictor = BatchPredictor(
                module, variables["params"],
                {k: v for k, v in variables.items() if k != "params"},
                chunk=chunk,
                preprocess=lambda v: v.astype(jnp.float32) / 255.0,
                # predict_float argmax on device
                # (torch_distributed.py:112-120)
                postprocess=lambda y: jnp.argmax(y, -1).astype(jnp.int32),
            )
            _sp_init.sync(variables["params"])
        with tele.span("bench/compile_warmup") as _sp_warm:
            _materialize(predictor.predict(x[:chunk]))  # compile
            _sp_warm.synced = True
        n_chips = len(jax.devices())

        with tele.span("bench/measure") as _sp_measure:
            xd = jax.device_put(x)  # device-resident: measures the chip
            _materialize(xd)
            rates = []
            for _ in range(3):  # best-of-3
                t0 = time.perf_counter()
                out = predictor.predict(xd)
                assert out.shape[0] == x.shape[0]
                rates.append(x.shape[0] / (time.perf_counter() - t0))
            per_chip = max(rates) / n_chips

            # End-to-end streaming leg over a real Parquet file (disk
            # -> decode -> wire -> compute -> drain).
            stats = stream_parquet_predict(
                predictor, path, row_shape=(224, 224, 3), dtype=np.uint8,
                batch_rows=4 * chunk,
            )
            _sp_measure.synced = True  # predict() drains per batch

    out = {
        "config": "resnet50_inference", "unit": "examples/sec/chip",
        "examples_per_sec_per_chip": round(per_chip, 1),
        "phase_s": {
            "data": round(_sp_data.duration_s, 3),
            "init": round(_sp_init.duration_s, 3),
            "compile_warmup": round(_sp_warm.duration_s, 3),
            "measure": round(_sp_measure.duration_s, 3),
        },
        "chip_rate_rows_per_sec_per_chip": round(per_chip, 1),
        "stream_rows_per_sec": stats["rows_per_sec"],
        "stream_n_rows": stats["n_rows"],
        "n_chips": n_chips,
        "projected_1M_rows_s_chip_rate": round(
            1_000_000 / (per_chip * n_chips), 1
        ),
        "projected_1M_rows_s_host_stream": round(
            1_000_000 / max(stats["rows_per_sec"], 1e-9), 1
        ),
        "wire_dtype": "uint8 (normalize + argmax fused on device)",
    }
    # Attach the LARGEST measured long-haul run on record across the
    # retained round logs (r03 100k, r04 1M, r05 segments).
    bench_dir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    big = []
    for name in ("bench_r03_tpu.jsonl", "bench_r04_tpu.jsonl",
                 "bench_r05_tpu.jsonl"):
        try:
            with open(os.path.join(bench_dir, name)) as f:
                runs = [json.loads(line) for line in f if line.strip()]
            big += [r for r in runs
                    if r.get("config") == "resnet50_inference_stream"
                    and r.get("n_rows", 0) >= 100_000]
        except (OSError, ValueError):
            # Missing log or a truncated line from a killed run — skip
            # the attachment, never the benchmark.
            continue
    if big:
        try:
            last = max(big, key=lambda r: r["n_rows"])
            # Read every key BEFORE assigning: a partial attachment
            # from an old-schema row would be worse than none.
            out.update({
                "measured_run_rows": last["n_rows"],
                "measured_run_rows_per_sec": last["steady_rows_per_sec"],
                "measured_run_wall_s": last["wall_s"],
            })
        except KeyError:
            pass
    return out


def bench_long_context_lm() -> dict:
    """Beyond the reference (which has no sequence code at all,
    SURVEY §5): causal-LM training at long context on one chip via the
    Pallas flash-attention kernel (fwd+bwd streaming, no (s,s) logits
    in HBM), plus a dense-vs-flash step-time comparison at a length
    both can run. Multi-chip sequence parallelism (ring attention over
    ``sp``) is exercised by dryrun_multichip and tests; this config is
    the single-chip kernel number."""
    import jax

    from sparktorch_tpu.models import CausalLM
    from sparktorch_tpu.models.transformer import TransformerConfig
    from sparktorch_tpu.utils.serde import ModelSpec

    rng = np.random.default_rng(0)
    vocab, batch, seq = 32768, 2, 8192

    def spec_for(attn: str, s: int) -> ModelSpec:
        cfg = TransformerConfig(
            vocab_size=vocab, d_model=512, n_heads=8, n_layers=4,
            d_ff=2048, max_len=s, attn_impl=attn, remat=True,
        )
        return ModelSpec(module=CausalLM(cfg), loss="cross_entropy",
                         optimizer="adamw", optimizer_params={"lr": 3e-4})

    ids = rng.integers(0, vocab, (batch, seq + 1)).astype(np.int32)
    out = _sync_epoch_bench(spec_for("flash", seq), ids[:, :-1], ids[:, 1:],
                            batch, iters=6, warmup=2, chunks=2)
    tokens_per_sec = out["examples_per_sec_per_chip"] * seq

    # Head-to-head at a length dense can still hold (s^2 logits fit).
    cmp_seq = 2048
    ids_c = rng.integers(0, vocab, (batch, cmp_seq + 1)).astype(np.int32)
    cmp = {}
    for attn in ("dense", "flash"):
        r = _sync_epoch_bench(spec_for(attn, cmp_seq), ids_c[:, :-1],
                              ids_c[:, 1:], batch, iters=6, warmup=2, chunks=2)
        cmp[attn] = r["step_time_p50_s"]
    return {
        "config": "long_context_lm", "unit": "tokens/sec/chip",
        "seq_len": seq,
        "tokens_per_sec_per_chip": round(tokens_per_sec, 1),
        "flash_vs_dense_step_ratio_at_2k": round(
            cmp["dense"] / cmp["flash"], 3
        ),
        **out,
    }


def bench_moe_lm() -> dict:
    """Beyond the reference: switch-style MoE causal LM on one chip
    (ep=1 layout; the all-to-all layout is exercised by tests and the
    multi-chip dry run). Reports tokens/sec and the MoE-vs-dense
    step-time ratio at matched active params per token."""
    import jax

    from sparktorch_tpu.models import CausalLM
    from sparktorch_tpu.models.transformer import TransformerConfig
    from sparktorch_tpu.utils.serde import ModelSpec

    rng = np.random.default_rng(0)
    vocab, batch, seq = 32768, 8, 1024

    def spec_for(n_experts: int) -> ModelSpec:
        cfg = TransformerConfig(
            vocab_size=vocab, d_model=512, n_heads=8, n_layers=4,
            d_ff=2048, max_len=seq, n_experts=n_experts, moe_every=2,
        )
        return ModelSpec(module=CausalLM(cfg), loss="cross_entropy",
                         optimizer="adamw", optimizer_params={"lr": 3e-4})

    ids = rng.integers(0, vocab, (batch, seq + 1)).astype(np.int32)
    # with_trace: the MoE leg's record carries the per-collective
    # comm/compute budget (dispatch/combine collectives vs expert
    # compute) from an analyzed XLA capture — comm_s/comm_fraction/
    # overlap_fraction in the phase budget, per the obs ISSUE.
    moe = _sync_epoch_bench(spec_for(8), ids[:, :-1], ids[:, 1:], batch,
                            iters=6, warmup=2, chunks=2, with_trace=True)
    dense = _sync_epoch_bench(spec_for(0), ids[:, :-1], ids[:, 1:], batch,
                              iters=6, warmup=2, chunks=2)
    # Comm-fraction drift gate: the MoE capture records a comm_budget
    # every round; once a prior round's record exists, a lost overlap
    # (dispatch/combine no longer hidden under expert compute) fails
    # the bench instead of silently shipping.
    if "comm_fraction" in moe:
        moe["comm_drift"] = _check_comm_drift(
            "moe_lm", moe["comm_fraction"], moe.get("overlap_fraction", 0.0)
        )
    return {
        "config": "moe_lm", "unit": "tokens/sec/chip",
        "n_experts": 8, "seq_len": seq,
        "tokens_per_sec_per_chip": round(
            moe["examples_per_sec_per_chip"] * seq, 1
        ),
        "moe_vs_dense_step_ratio": round(
            moe["step_time_p50_s"] / dense["step_time_p50_s"], 3
        ),
        **moe,
    }


def bench_moe_a2a() -> dict:
    """MoE expert-parallel dispatch gate (``make bench-moe``): on the
    same ep=2 mesh and matched init, the explicit shard_map all-to-all
    dispatch (``moe_ep_dispatch='a2a'``) must beat the legacy
    partitioner-derived token-replication path (``'replicate'`` — jax
    0.4.x GSPMD lowers it to all-gather + all-reduce) on

    - **collective bytes, strictly**: per-device HLO collective result
      bytes (:func:`sparktorch_tpu.obs.xprof.hlo_collective_bytes` —
      static, partitioner-independent, no profiler noise), with the
      a2a leg containing all-to-alls and ZERO all-gathers;
    - **step wall, equal-or-better**: medians over interleaved
      measurement rounds (the rig-noise discipline every gate here
      uses), within ``SPARKTORCH_TPU_MOE_WALL_TOL`` (default 0.05 —
      the byte win must not come at a wall cost);
    - **identical numbers**: both legs' losses agree at rtol 1e-5
      (the dispatch rewrite is a layout choice, pinned here end to
      end, not just in the unit suite).

    The tuner's ep a2a byte term (``predict_comm_bytes``:
    ``ep_all_to_all``) is validated against the measured HLO bytes —
    recorded as ``predicted_vs_hlo_a2a`` and gated to a factor band
    (the model is a monotone ranker, not a simulator; the band catches
    sign/scale regressions like a dropped capacity term).

    Retained (``--log benchmarks/bench_r10_moe.jsonl``) so the drift
    gate arms: the byte-reduction ratio must not collapse vs the
    windowed median of prior rounds (``SPARKTORCH_TPU_MOE_DRIFT_TOL``,
    relative, default 0.25)."""
    import dataclasses as _dc
    import os

    import jax
    import jax.numpy as jnp

    from sparktorch_tpu.models import CausalLM
    from sparktorch_tpu.models.transformer import TransformerConfig
    from sparktorch_tpu.obs.xprof import hlo_collective_bytes
    from sparktorch_tpu.parallel.compat import set_mesh as _set_mesh
    from sparktorch_tpu.parallel.mesh import MeshConfig, build_mesh
    from sparktorch_tpu.parallel.tune import (
        mesh_label,
        predict_comm_bytes,
        transformer_workload,
    )
    from sparktorch_tpu.train.sharded import (
        create_sharded_state,
        make_sharded_train_step,
        shard_batch,
    )
    from sparktorch_tpu.utils.data import DataBatch
    from sparktorch_tpu.utils.serde import ModelSpec

    n_dev = len(jax.devices())
    if n_dev % 2:
        raise AssertionError(
            f"bench moe_a2a needs an even device count for ep=2; got "
            f"{n_dev} (set XLA_FLAGS=--xla_force_host_platform_device_"
            "count=8 on a CPU rig)"
        )
    # Sized so the dispatch/combine traffic is a real fraction of the
    # step (d_model*seq*cf*k capacity blocks per MoE layer) without
    # blowing the CPU rig's step wall.
    base_cfg = TransformerConfig(
        vocab_size=512, d_model=128, n_heads=4, n_layers=2, d_ff=512,
        max_len=64, n_experts=8, moe_every=1, moe_top_k=2,
        moe_group_size=64,
    )
    mesh = build_mesh(MeshConfig(ep=2))
    mesh_ran = mesh_label(dict(mesh.shape))
    rng = np.random.default_rng(0)
    bsz = 4 * n_dev
    ids = rng.integers(0, base_cfg.vocab_size, (bsz, 65)).astype(np.int32)
    batch = DataBatch(x=jnp.asarray(ids[:, :-1]), y=jnp.asarray(ids[:, 1:]),
                      w=jnp.ones((bsz,), jnp.float32))

    # The persistent compile cache is disarmed for collective-bearing
    # programs on CPU (tests/conftest.py / ROADMAP).
    old_cache = jax.config.jax_compilation_cache_dir
    if jax.default_backend() == "cpu":
        jax.config.update("jax_compilation_cache_dir", None)
    try:
        # Dense CE, not the registry's fused Pallas kernel: on this
        # CPU rig the kernel runs in interpret mode — a while loop the
        # partitioner can only all-gather the (tokens, vocab) logits
        # into — which would put LOSS-path all-gathers in both legs'
        # HLO and blind the "zero all-gathers in the a2a program" gate
        # to the dispatch bytes this bench exists to measure.
        from sparktorch_tpu.utils.losses import cross_entropy_loss

        legs = {}
        for dispatch in ("replicate", "a2a"):
            cfg = _dc.replace(base_cfg, moe_ep_dispatch=dispatch)
            spec = ModelSpec(module=CausalLM(cfg), loss=cross_entropy_loss,
                             optimizer="adamw",
                             optimizer_params={"lr": 1e-3})
            tx = spec.make_optimizer()
            state, shardings = create_sharded_state(
                spec, mesh, jax.random.key(0),
                sample_x=np.asarray(batch.x[:1]), tx=tx,
            )
            step = make_sharded_train_step(
                spec.make_module().apply, spec.loss_fn(), tx, mesh,
                shardings,
            )
            sharded = shard_batch(batch, mesh)
            with _set_mesh(mesh):
                compiled = step.jitted.lower(state, sharded).compile()
            hlo_stats = hlo_collective_bytes(compiled.as_text())
            # Compile+warm outside timing.
            state, m = step(state, sharded)
            jax.block_until_ready(m.loss)
            legs[dispatch] = {
                "step": step, "state": state, "batch": sharded,
                "hlo": hlo_stats, "losses": [float(m.loss)], "walls": [],
            }

        # Interleaved rounds: back-to-back per-leg timing on a shared
        # rig swings whole windows into slow scheduler epochs — the
        # same discipline as bench-tune/bench-ps-fleet.
        steps_per_round, rounds = 3, 4
        for _ in range(rounds):
            for leg in legs.values():
                t0 = time.perf_counter()
                st = leg["state"]
                for _ in range(steps_per_round):
                    st, m = leg["step"](st, leg["batch"])
                jax.block_until_ready(m.loss)
                leg["state"] = st
                leg["walls"].append(
                    (time.perf_counter() - t0) / steps_per_round
                )
                leg["losses"].append(float(m.loss))

        rep, a2a = legs["replicate"], legs["a2a"]

        # ---- gate 1: strictly fewer collective bytes ---------------------
        bytes_rep = rep["hlo"]["total_bytes"]
        bytes_a2a = a2a["hlo"]["total_bytes"]
        if not (0 < bytes_a2a < bytes_rep):
            raise AssertionError(
                f"a2a path must move strictly fewer collective bytes: "
                f"a2a={bytes_a2a} vs replicate={bytes_rep} "
                f"(families: a2a={a2a['hlo']}, rep={rep['hlo']})"
            )
        if a2a["hlo"]["counts"].get("all_to_all", 0) < 4 \
                or a2a["hlo"]["counts"].get("all_gather", 0) != 0:
            raise AssertionError(
                f"a2a leg HLO shape wrong (want >=4 all-to-alls — "
                f"dispatch+combine, fwd+bwd, per MoE layer — and zero "
                f"all-gathers): {a2a['hlo']}"
            )

        # ---- gate 2: equal-or-better step wall ---------------------------
        wall_rep = float(np.median(rep["walls"]))
        wall_a2a = float(np.median(a2a["walls"]))
        wall_tol = float(os.environ.get("SPARKTORCH_TPU_MOE_WALL_TOL",
                                        "0.05"))
        if wall_a2a > wall_rep * (1.0 + wall_tol):
            raise AssertionError(
                f"a2a step wall regressed vs the token-replication "
                f"path: {wall_a2a * 1e3:.2f}ms vs {wall_rep * 1e3:.2f}ms "
                f"(tol {wall_tol:.0%}; walls a2a={a2a['walls']}, "
                f"rep={rep['walls']})"
            )

        # ---- gate 3: layout must not change the math ---------------------
        np.testing.assert_allclose(a2a["losses"], rep["losses"], rtol=1e-5)

        # ---- gate 4: tuner ep byte model vs HLO ground truth -------------
        shape = transformer_workload(base_cfg, global_batch=bsz)
        predicted = predict_comm_bytes(MeshConfig(ep=2), shape, n_dev)
        # predict_comm_bytes models the FORWARD dispatch+combine pair
        # fleet-wide; the compiled HLO is per-device and includes the
        # backward pair -> model ~= hlo_bytes * n_dev / 2.
        hlo_a2a_fleet_fwd = a2a["hlo"]["bytes"]["all_to_all"] * n_dev / 2
        ratio = predicted["ep_all_to_all"] / max(hlo_a2a_fleet_fwd, 1.0)
        if not (0.25 <= ratio <= 4.0):
            raise AssertionError(
                f"tuner ep_all_to_all byte model off the HLO ground "
                f"truth by {ratio:.2f}x (predicted "
                f"{predicted['ep_all_to_all']:.0f}, HLO fwd-pair "
                f"fleet-wide {hlo_a2a_fleet_fwd:.0f}) — the a2a term "
                "no longer tracks the real lowering"
            )

        # ---- gate 5: drift vs retained prior rounds ----------------------
        byte_ratio = bytes_rep / bytes_a2a
        drift_tol = float(os.environ.get("SPARKTORCH_TPU_MOE_DRIFT_TOL",
                                         "0.25"))
        prior = _prior_window("moe_a2a", "collective_byte_ratio",
                              mesh=mesh_ran)
        if prior is None:
            drift = {"status": "no_prior_record", "tolerance": drift_tol}
        else:
            drift = {"status": "checked", "tolerance": drift_tol,
                     "prior": prior}
            if byte_ratio < prior["median"] * (1.0 - drift_tol):
                raise AssertionError(
                    f"moe_a2a: collective byte reduction collapsed "
                    f"{prior['median']:.2f}x -> {byte_ratio:.2f}x "
                    f"(beyond the {drift_tol:.0%} tolerance); {drift}"
                )

        return {
            "config": "moe_a2a", "unit": "x fewer collective bytes",
            "value": round(byte_ratio, 3),
            "collective_byte_ratio": round(byte_ratio, 3),
            "mesh": mesh_ran, "n_chips": n_dev,
            "a2a_step_wall_s": round(wall_a2a, 6),
            "replicate_step_wall_s": round(wall_rep, 6),
            "wall_ratio": round(wall_a2a / wall_rep, 3),
            "a2a_hlo": a2a["hlo"], "replicate_hlo": rep["hlo"],
            "loss_parity_rtol": 1e-5,
            "predicted_vs_hlo_a2a": round(ratio, 3),
            "drift": drift,
        }
    finally:
        if jax.default_backend() == "cpu":
            jax.config.update("jax_compilation_cache_dir", old_cache)


def bench_pp_tune() -> dict:
    """Pipeline-schedule auto-tuning + recompile-tax gate
    (``make bench-pp-tune``, ROADMAP item 4). Two legs:

    **Referee leg** — the tuner searches the dp x pp x schedule x
    virtual_stages space (``axes=('dp','pp')``: the leg's subject is
    the SCHEDULE dimension, not the whole mesh zoo bench-tune already
    referees) on a 4-layer transformer, then an EXHAUSTIVE pass
    measures every candidate; FAILS unless the chosen config sits
    within ``SPARKTORCH_TPU_PP_TUNE_TOL`` (default 15%) of the
    exhaustive winner's step wall, the space actually contained
    measured pp>1 schedule candidates, and pruned candidates were
    never executed.

    **Recompile-tax leg** — a cold ``mesh="auto"`` build (fresh
    tune-result cache) vs a warm one, each inside its own goodput
    ledger; FAILS unless the warm build's ``TuneResult.compile_count``
    drops below the cold path's, the warm tune wall collapses (cache
    hit), and the warm ledger's ``compile`` bucket shows the saving
    in seconds. This is the acceptance gate for "the auto path stops
    compiling its winner twice": the persistent XLA cache (armed for
    the whole bench process) makes the winner's fresh-closure
    recompile a disk hit, and the tune-result cache deletes the
    search.

    The record retains both rankings + the compile bills; drift gate
    vs the ``_prior_window`` median of ``tuner_wall_s`` is ARMED
    (SPARKTORCH_TPU_PP_TUNE_DRIFT_TOL, relative, default 1.0 with a
    5s floor) once a prior round is retained."""
    import os
    import tempfile

    import jax

    from sparktorch_tpu.models import SequenceClassifier, tiny_transformer
    from sparktorch_tpu.obs import Telemetry
    from sparktorch_tpu.obs import goodput as goodput_mod
    from sparktorch_tpu.parallel.tune import autotune, transformer_caps
    from sparktorch_tpu.train.pipeline import PipelineState
    from sparktorch_tpu.train.sharded import (
        make_sharded_train_step,
        shard_batch,
    )
    from sparktorch_tpu.utils.data import DataBatch
    from sparktorch_tpu.utils.serde import ModelSpec

    t0 = time.perf_counter()
    tele = Telemetry(run_id="bench_pp_tune")
    devices = jax.devices()
    n_dev = len(devices)
    rng = np.random.default_rng(0)

    # ---- referee leg: pp x schedule vs exhaustive ---------------------
    bsz, seq = 8 * n_dev, 32
    batch = DataBatch(
        x=np.asarray(rng.integers(0, 256, (bsz, seq)).astype(np.int32)),
        y=np.asarray(rng.integers(0, 2, (bsz,)).astype(np.int32)),
        w=np.ones((bsz,), np.float32),
    )
    # 4 layers: pp in {1, 2, 4}, interleaved V=2 legal at pp=2. Sized
    # so layout differences beat scheduler jitter (the bench-tune
    # sizing lesson).
    cfg = tiny_transformer(d_model=128, d_ff=512, n_layers=4,
                           max_len=seq)
    module = SequenceClassifier(cfg)
    spec = ModelSpec(module=module, loss="cross_entropy",
                     optimizer="adam", optimizer_params={"lr": 1e-3})
    # 2 profiled steps x (1 warmup + 2 scored) rounds per candidate:
    # schedule steps on this rig run seconds each, and the referee
    # only needs a stable ORDERING, not tight walls.
    steps, repeats, top_k = 2, 2, 3
    axes = ("dp", "pp")
    # Cap pp at 2 (the caps knob, not the axes): pp=2 already carries
    # every schedule kind (gpipe / 1f1b / interleaved V=2 on the
    # 4-layer stack), and the exhaustive referee measures EVERY
    # candidate — pp=4 schedule steps on the 8-virtual-device CPU rig
    # run ~100x the dp wall and would blow the bench budget without
    # adding a schedule dimension to referee.
    caps = dict(transformer_caps(cfg, seq))
    caps["pp"] = (2,)
    caps["sp"] = (1,)

    tuned = autotune(
        spec, batch, devices, axes=axes, caps=caps, steps=steps,
        repeats=repeats, measure_top_k=top_k, telemetry=tele,
    )
    tuner_wall_s = tuned.wall_s
    pruned = tuned.pruned()
    if any(c.measured for c in pruned):
        raise AssertionError("a pruned candidate was executed")
    pp_cands = [c for c in tuned.candidates if c.axes.get("pp", 1) > 1]
    if not pp_cands:
        raise AssertionError("search space contained no pp>1 candidate")
    if not any(c.schedule for c in pp_cands):
        raise AssertionError("pp candidates carry no schedule meta")
    scheds = {c.schedule["schedule"] for c in pp_cands if c.schedule}
    if not {"gpipe", "1f1b"} <= scheds:
        raise AssertionError(
            f"schedule dims missing from the space: {sorted(scheds)}")

    jax.clear_caches()
    gc.collect()
    exhaustive = autotune(
        spec, batch, devices, axes=axes, caps=caps, steps=steps,
        repeats=repeats, exhaustive=True, telemetry=tele,
    )
    ex_ranked = exhaustive.ranking()
    if not any(c.axes.get("pp", 1) > 1 for c in ex_ranked):
        raise AssertionError(
            "exhaustive referee measured no pp>1 candidate — the "
            "schedule path never executed"
        )
    ex_by_label = {c.label: c for c in ex_ranked}
    winner = ex_ranked[0]
    tol = float(os.environ.get("SPARKTORCH_TPU_PP_TUNE_TOL", "0.15"))
    chosen_ex = ex_by_label.get(tuned.best_label)
    if chosen_ex is None:
        raise AssertionError(
            f"chosen {tuned.best_label} missing from the exhaustive "
            f"measurement ({sorted(ex_by_label)})"
        )
    winner_wall = float(winner.measured["step_wall_s"])
    chosen_wall = float(chosen_ex.measured["step_wall_s"])
    if tuned.best_label != winner.label and \
            chosen_wall > winner_wall * (1.0 + tol):
        raise AssertionError(
            f"tuner chose {tuned.best_label} "
            f"({chosen_wall * 1e3:.2f}ms on the exhaustive rig) but "
            f"the exhaustive winner is {winner.label} "
            f"({winner_wall * 1e3:.2f}ms) — over the {tol * 100:.0f}% "
            f"tolerance"
        )

    # ---- recompile-tax leg: cold vs warm mesh='auto' ------------------
    small = SequenceClassifier(tiny_transformer(
        vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
        max_len=8))
    small_spec = ModelSpec(module=small, loss="cross_entropy",
                           optimizer="adam",
                           optimizer_params={"lr": 1e-3})
    small_batch = DataBatch(
        x=np.asarray(rng.integers(0, 64, (2 * n_dev, 8)).astype(np.int32)),
        y=np.asarray(rng.integers(0, 2, (2 * n_dev,)).astype(np.int32)),
        w=np.ones((2 * n_dev,), np.float32),
    )

    def _auto_build_and_step():
        """One mesh='auto' build + first step under a fresh ledger;
        returns (tune_result, ledger snapshot, build wall)."""
        led = goodput_mod.GoodputLedger(telemetry=None, rank=0)
        tb = time.perf_counter()
        with led.activate():
            run = make_sharded_train_step(
                small.apply, small_spec.loss_fn(),
                small_spec.make_optimizer(),
                mesh="auto", spec=small_spec, sample_batch=small_batch,
                tune_kwargs={"steps": 1, "repeats": 1, "min_rounds": 1,
                             "measure_top_k": 2, "cache": True},
            )
            state = run.state
            if isinstance(state, PipelineState):
                out = run(state, small_batch)
            else:
                out = run(state, shard_batch(small_batch, run.mesh))
            jax.block_until_ready(jax.tree.leaves(out)[:1])
        wall = time.perf_counter() - tb
        led.close()
        return run.tune_result, led.snapshot(), wall

    with tempfile.TemporaryDirectory() as tune_cache_dir:
        # Sandbox BOTH caches the auto path touches: the tune-result
        # cache (cold-vs-warm is the leg's subject) and the XLA-cache
        # arming knob — if this config runs in a process where the
        # bench harness has not already armed a cache dir,
        # _maybe_arm_xla_cache must land in the sandbox, never in the
        # operator's ~/.cache.
        old_env = {k: os.environ.get(k)
                   for k in ("SPARKTORCH_TPU_TUNE_CACHE",
                             "SPARKTORCH_TPU_XLA_CACHE")}
        os.environ["SPARKTORCH_TPU_TUNE_CACHE"] = tune_cache_dir
        os.environ["SPARKTORCH_TPU_XLA_CACHE"] = os.path.join(
            tune_cache_dir, "xla")
        try:
            cold_result, cold_doc, cold_wall = _auto_build_and_step()
            jax.clear_caches()
            gc.collect()
            warm_result, warm_doc, warm_wall = _auto_build_and_step()
        finally:
            for k, v in old_env.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    if not warm_result.cache_hit:
        raise AssertionError("warm mesh='auto' build missed the "
                             "tune-result cache")
    if warm_result.compile_count >= cold_result.compile_count:
        raise AssertionError(
            f"cache-warm compile_count {warm_result.compile_count} did "
            f"not drop below the cold path's "
            f"{cold_result.compile_count}"
        )
    cold_compile_s = float(cold_doc["buckets"]["compile"])
    warm_compile_s = float(warm_doc["buckets"]["compile"])
    if cold_compile_s <= 0:
        raise AssertionError("cold build's goodput compile bucket is "
                             "empty — the tune LedgerSpans never landed")
    if warm_compile_s >= cold_compile_s:
        raise AssertionError(
            f"goodput compile bucket shows no saving: cold "
            f"{cold_compile_s:.2f}s vs warm {warm_compile_s:.2f}s"
        )
    # A cache-hit TuneResult reports the wall THIS process paid (the
    # lookup), not the stored search's — so the collapse is direct.
    if warm_result.wall_s > 0.2 * cold_result.wall_s + 0.5:
        raise AssertionError(
            f"warm tune wall {warm_result.wall_s:.2f}s did not "
            f"collapse vs cold {cold_result.wall_s:.2f}s (cache hit "
            f"should skip the search)"
        )

    # ---- drift gate vs the windowed prior ----------------------------
    drift = {"status": "no_prior_record"}
    prior = _prior_window("pp_tune", "tuner_wall_s", k=3)
    if prior is not None:
        dtol = float(os.environ.get("SPARKTORCH_TPU_PP_TUNE_DRIFT_TOL",
                                    "1.0"))
        floor_s = 5.0
        bound = prior["median"] * (1.0 + dtol) + floor_s
        if tuner_wall_s > bound:
            raise AssertionError(
                f"tuner wall {tuner_wall_s:.1f}s drifted past "
                f"{bound:.1f}s (prior median {prior['median']:.1f}s "
                f"over {prior['n']} rounds, tol {dtol})"
            )
        drift = {"status": "checked", "prior_median_s": prior["median"],
                 "bound_s": round(bound, 1), "tolerance": dtol}

    return {
        "config": "pp_tune", "unit": "chosen step wall vs best (x)",
        "value": round(chosen_wall / winner_wall, 4),
        "chosen": tuned.best_label,
        "chosen_schedule": tuned.best_schedule,
        "exhaustive_winner": winner.label,
        "chosen_wall_ms": round(chosen_wall * 1e3, 3),
        "winner_wall_ms": round(winner_wall * 1e3, 3),
        "tolerance": tol,
        "n_candidates": len(tuned.candidates),
        "n_pp_candidates": len(pp_cands),
        "schedules_in_space": sorted(scheds),
        "n_pruned": len(pruned),
        "tuner_wall_s": round(tuner_wall_s, 1),
        "exhaustive_wall_s": round(exhaustive.wall_s, 1),
        "exhaustive_ranking": [
            {"mesh": c.label,
             "wall_ms": round(float(c.measured["step_wall_s"]) * 1e3, 3),
             "bubble": round(float(
                 c.predicted.get("pp_bubble_fraction", 0.0)), 3)}
            for c in ex_ranked
        ],
        "compile_count_cold": cold_result.compile_count,
        "compile_count_warm": warm_result.compile_count,
        "compile_s_cold": round(cold_compile_s, 2),
        "compile_s_warm": round(warm_compile_s, 2),
        "tune_wall_cold_s": round(cold_result.wall_s, 2),
        "tune_wall_warm_s": round(warm_result.wall_s, 3),
        "build_wall_cold_s": round(cold_wall, 1),
        "build_wall_warm_s": round(warm_wall, 1),
        "drift": drift,
        "n_chips": n_dev,
        "wall_s": round(time.perf_counter() - t0, 1),
    }


CONFIGS: Dict[str, Callable[[], dict]] = {
    "mnist_mlp_sync": bench_mnist_mlp_sync,
    "mnist_cnn_sync": bench_mnist_cnn_sync,
    "lazy_cnn_sync": bench_lazy_cnn_sync,
    "resnet18_hogwild": bench_resnet18_hogwild,
    "hogwild_wire": bench_hogwild_wire,
    "hogwild_chaos": bench_hogwild_chaos,
    "hogwild_chaos_soak": bench_hogwild_chaos_soak,
    "elastic_ctl": bench_elastic_ctl,
    "obs_history": bench_obs_history,
    "goodput": bench_goodput,
    "profile": bench_profile,
    "health": bench_health,
    "skew": bench_skew,
    "hogwild_ps_fleet": bench_hogwild_ps_fleet,
    "serve_online": bench_serve_online,
    "rpc_trace": bench_rpc_trace,
    "sharded_trace": bench_sharded_trace,
    "gang_obs": bench_gang_obs,
    "mesh_tune": bench_mesh_tune,
    "pp_tune": bench_pp_tune,
    "moe_a2a": bench_moe_a2a,
    "bert_dp": bench_bert_dp,
    "resnet50_inference": bench_resnet50_inference,
    "long_context_lm": bench_long_context_lm,
    "moe_lm": bench_moe_lm,
}


def _headline() -> dict:
    """The driver's ONE-JSON-line metric — same workload as round 1.

    Round 4: the value is the MEDIAN of >=5 interleaved paired-span
    slope samples (see ``_sync_epoch_bench``), with best/spread/raw
    samples carried alongside so regression vs noise is decidable from
    the line itself. Nothing is appended to an old record: pass
    ``--log`` to keep one."""
    out = bench_mnist_cnn_sync()
    per_chip = out["examples_per_sec_per_chip"]
    rec = {
        "metric": "examples/sec/chip (MNIST-CNN sync DP, batch 1024)",
        "value": per_chip,
        "unit": "examples/sec/chip",
        "vs_baseline": round(per_chip / REFERENCE_BASELINE_EXAMPLES_PER_SEC, 3),
        "best": out["rate_best"],
        "spread_pct": out["rate_spread_pct"],
        "n_samples": len(out["rate_samples"]),
        "estimator": "median of paired-span slopes (cancels per-sync link RTT)",
    }
    return rec


def main(argv: Optional[List[str]] = None) -> None:
    # Persistent compilation cache: repeated configs (and the warmup
    # pattern above) hit disk instead of recompiling — also what a
    # production deployment should run with.
    import jax

    from sparktorch_tpu.utils.checkpoint import arm_compile_cache

    arm_compile_cache(min_compile_time_s=0.5)

    parser = argparse.ArgumentParser(prog="sparktorch-tpu-bench")
    parser.add_argument("--config", default="headline",
                        choices=["headline", "all", *CONFIGS])
    parser.add_argument("--log", default=None,
                        help="append raw result records to this JSONL file")
    parser.add_argument("--telemetry-dump", default=None, metavar="PATH",
                        help="append the run's full telemetry snapshot "
                             "(counters, gauges, histogram/span roll-ups) "
                             "as one JSONL line — the CLI twin of the "
                             "param server's /metrics route")
    args = parser.parse_args(argv)

    def _dump_telemetry() -> None:
        if args.telemetry_dump:
            from sparktorch_tpu.obs import get_telemetry

            get_telemetry().dump(args.telemetry_dump)

    if args.config == "headline":
        print(json.dumps(_headline()))
        _dump_telemetry()
        return

    names = list(CONFIGS) if args.config == "all" else [args.config]
    records = []
    for i, name in enumerate(names):
        if i:
            # Fresh device/executable state per config: carried-over
            # compiled programs and live buffers from earlier configs
            # measurably depress later ones (~20-25% on the CNN
            # config); with the persistent compile cache on disk,
            # clearing costs little.
            jax.clear_caches()
            gc.collect()
        rec = CONFIGS[name]()
        rec["ts"] = time.strftime("%Y-%m-%dT%H:%M:%S")
        records.append(rec)
        print(json.dumps(rec))
    if args.log:
        with open(args.log, "a") as f:
            for rec in records:
                f.write(json.dumps(rec) + "\n")
    _dump_telemetry()


if __name__ == "__main__":
    main()

"""Synchronous SPMD training orchestration.

Replaces ``sparktorch/distributed.py:209-277`` (``train_distributed``):
the reference forks a phantom rank-0 process, ships dill'd closures to
barrier-scheduled Spark executors, and loops `partition_shuffles`
rounds of `iters` steps with per-step gloo all_reduces.

Here the driver IS the orchestrator and the mesh IS the gang: data
lives as one globally-sharded array (each device holds its shard in
HBM), the compiled step from :mod:`sparktorch_tpu.train.step` runs the
whole world per call, and "partition shuffles" become an on-device
global permutation between rounds. No phantom ranks: empty shards are
weight-zero padding (see utils/data.py).
"""

from __future__ import annotations

import contextlib
from typing import Any, Callable, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from sparktorch_tpu.ft import chaos as _chaos
from sparktorch_tpu.obs import get_logger, get_telemetry
from sparktorch_tpu.obs import goodput as _goodput
from sparktorch_tpu.parallel.launch import check_gang, notify_gang_step
from sparktorch_tpu.parallel.mesh import BATCH_AXES, batch_sharding, build_mesh, replicated
from sparktorch_tpu.train.step import (
    EsConfig,
    TrainState,
    create_train_state,
    grad_allreduce_plan,
    init_es_state,
    make_eval_step,
    make_train_epoch,
    make_train_epoch_fused,
    make_train_step,
)
from sparktorch_tpu.utils.data import DataBatch, handle_features, pad_to_multiple
from sparktorch_tpu.utils.early_stopper import EarlyStopping
from sparktorch_tpu.utils.serde import ModelSpec, deserialize_model


class TrainResult(NamedTuple):
    params: Any
    model_state: Any
    metrics: list  # list of per-step dicts
    spec: ModelSpec
    summary: Optional[dict] = None  # roll-up (examples/sec/chip, p50/p99)


def _as_batch(data, labels=None, validation_pct=0.0, seed=0):
    if isinstance(data, DataBatch):
        return data, None
    if isinstance(data, tuple) and len(data) == 2 and labels is None:
        return handle_features(data[0], data[1], validation_pct, seed)
    return handle_features(data, labels, validation_pct, seed)


def prepare_sharded_batch(batch: DataBatch, mesh: Mesh) -> DataBatch:
    """Pad to a multiple of the batch-axis size and place shards.

    The padding rows carry weight 0 — this is the empty-partition
    protocol (``distributed.py:46-63,131-133``) done with math instead
    of phantom collective participants.
    """
    n_shards = 1
    for ax in BATCH_AXES:
        n_shards *= mesh.shape[ax]
    padded = pad_to_multiple(batch, n_shards)
    sharding = batch_sharding(mesh)
    return DataBatch(*(jax.device_put(a, sharding) for a in padded))


def _shuffle_batch(batch: DataBatch, key: jax.Array, mesh: Mesh) -> DataBatch:
    """Global permutation between shuffle rounds — the analog of the
    reference's RDD re-shuffle (``distributed.py:267-273``), executed
    on-device (an all-to-all under the hood, riding ICI)."""
    perm = jax.random.permutation(key, batch.x.shape[0])
    sharding = batch_sharding(mesh)
    out = jax.jit(
        lambda b, p: DataBatch(b.x[p], b.y[p], b.w[p]),
        out_shardings=DataBatch(sharding, sharding, sharding),
    )(batch, perm)
    return out


def _open_checkpoint(checkpoint_dir, resume, state):
    """Shared checkpoint bring-up for the trainers: open the manager
    and restore the latest snapshot when resuming. Returns
    (manager_or_None, possibly-restored state)."""
    if not checkpoint_dir:
        return None, state
    from sparktorch_tpu.utils.checkpoint import CheckpointManager

    ckpt = CheckpointManager(checkpoint_dir)
    if resume and ckpt.latest_step() is not None:
        abstract = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=a.sharding),
            state,
        )
        state = ckpt.restore(abstract)
    return ckpt, state


def _save_if_due(ckpt, state, last_ckpt_step: int, every: int) -> int:
    """Save on the first boundary at or past the cadence — a fused
    chunk that strides over the exact multiple must not silently skip
    the save. Returns the (possibly advanced) last-saved step."""
    if ckpt is None or every <= 0:
        return last_ckpt_step
    # lint-obs: ok (one scalar at checkpoint cadence, not per step)
    step_now = int(jax.device_get(state.step))
    if step_now - last_ckpt_step >= every:
        ckpt.save(step_now, state)
        return step_now
    return last_ckpt_step


def _resolve_steps_per_call(steps_per_call, default: int, iters: int,
                            checkpoint_every: int, ckpt_active: bool) -> int:
    """One place for the fused-chunk sizing contract (shared by the DP
    and pipeline trainers): a DEFAULTED chunk never exceeds the
    checkpoint cadence (saves happen between compiled calls); an
    EXPLICIT steps_per_call wins — saves then land at chunk boundaries
    >= the cadence (test_checkpoint_cadence_under_fused_stepping pins
    this). The result always divides ``iters`` exactly (a fused call
    runs its full scan; overshooting would silently train extra
    steps)."""
    if steps_per_call is None:
        steps_per_call = default
        if ckpt_active and checkpoint_every and checkpoint_every > 0:
            steps_per_call = min(steps_per_call, checkpoint_every)
    steps_per_call = max(1, min(int(steps_per_call), iters))
    while iters % steps_per_call != 0:
        steps_per_call -= 1
    return steps_per_call


def _finalize_checkpoint(ckpt, state, completed: bool) -> None:
    """Flush and close. The FINAL snapshot fires only on clean
    completion — orbax saves are cross-process collectives, so
    attempting one after a peer died would wedge the survivor in
    exactly the hang check_gang() exists to prevent (periodic saves
    already on disk keep the run resumable)."""
    if ckpt is None:
        return
    if completed:
        # lint-obs: ok (end-of-run scalar, the loop already drained)
        final_step = int(jax.device_get(state.step))
        if ckpt.latest_step() != final_step:
            ckpt.save(final_step, state, force=True)
    ckpt.wait()
    ckpt.close()


def _note_grad_allreduce(tele, params, mesh: Mesh) -> None:
    """What the step's gradient all-reduce sums, on the bus: the
    all-reduces a step issues, one a parameter array (0 where the batch
    axes have one member and nothing is reduced), and their bytes."""
    buckets, nbytes = grad_allreduce_plan(params, mesh)
    tele.gauge("train.grad_allreduce.buckets", buckets)
    tele.gauge("train.grad_allreduce.bytes", nbytes)


def _note_model_gauges(tele, module) -> None:
    """What a model says of its own structure (``train_gauges``: the
    sparse attention's top-k, the experts held and routed), on the bus."""
    for name, value in getattr(module, "train_gauges", dict)().items():
        tele.gauge(name, value)


def _note_moe_rows(tele, record, expert_rows, row_chunks, drop_fraction):
    """What an expert layer that counts its rows sowed in one step, into
    the step's record and onto the bus. From ``[MoE layers, experts
    held]`` rows: the rows computed, the most loaded expert's and the
    mean, and the routed pairs that were not computed (``drop_fraction``
    is dropped over routed, and routed is computed plus dropped). From
    ``[MoE layers, 2]`` chunks (where the layer moves its rows by
    chunks): the chunks its loops ran, whose ratio to the chunks that
    all chosen pairs would take is the share of them moved."""
    if expert_rows is not None:
        rows, f = float(expert_rows.sum()), float(drop_fraction or 0.0)
        record.update(
            moe_rows=rows, moe_rows_max=float(expert_rows.max()),
            moe_rows_mean=rows / expert_rows.size,
            moe_pairs_dropped=rows * f / (1.0 - f) if f < 1.0 else rows)
        tele.counter("train.moe.rows", rows)
        tele.counter("train.moe.pairs_dropped", record["moe_pairs_dropped"])
        tele.gauge("train.moe.rows_max", record["moe_rows_max"])
    if row_chunks is not None:
        record["moe_row_chunks"] = float(row_chunks[:, 0].sum())
        tele.counter("train.moe.row_chunks", record["moe_row_chunks"])
        tele.gauge("train.moe.row_chunks_possible",
                   float(row_chunks[:, 1].sum()))


def train_distributed(
    torch_obj: Union[str, ModelSpec],
    data: Any,
    labels: Optional[np.ndarray] = None,
    mesh: Optional[Mesh] = None,
    iters: int = 10,
    partition_shuffles: int = 1,
    verbose: int = 0,
    mini_batch: Optional[int] = None,
    validation_pct: float = 0.0,
    early_stop_patience: int = -1,
    seed: int = 0,
    device: Optional[str] = None,  # accepted for API parity; mesh decides
    metrics_hook: Optional[Callable[[dict], None]] = None,
    steps_per_call: Optional[int] = None,
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 0,
    resume: bool = False,
    profile_dir: Optional[str] = None,
    pre_sharded: bool = False,
    n_micro: int = 4,
    pipeline_schedule: str = "gpipe",
    virtual_stages: int = 1,
    telemetry=None,
) -> TrainResult:
    """Synchronous data-parallel training over the mesh.

    Parameter surface mirrors ``train_distributed``
    (``distributed.py:209-236``): iters, partition_shuffles, verbose,
    mini_batch, validation_pct, early_stop_patience. ``world_size`` and
    ``device`` disappear — the mesh defines the world. ``n_micro`` and
    ``pipeline_schedule`` ('gpipe' | '1f1b') apply only when the mesh
    has pp>1, as does ``virtual_stages`` (>1 = interleaved 1F1B:
    requires pipeline_schedule='1f1b', n_micro divisible by pp, and a
    dense/MoE pattern uniform across all pp*V chunks — tp, sp, MoE
    and ep all compose; shrinks the pipeline bubble ~V-fold at
    O(V*pp) activation memory).
    """
    del device
    tele = telemetry or get_telemetry()
    # The span's start is the call's entry stamp: what lies between
    # the process's start and it is the caller's (imports, the PJRT
    # client, the rows).
    with tele.span("train/enter"):
        spec = deserialize_model(torch_obj)
        mesh = mesh or build_mesh()

    from sparktorch_tpu.parallel.mesh import AXIS_PP

    if dict(mesh.shape).get(AXIS_PP, 1) > 1:
        # pp is a MESH choice on this same entry point: a mesh with
        # pp>1 routes to the GPipe trainer (pipeline.py), which trains
        # the spec's CausalLM under the pipelined schedule and returns
        # ordinary flax params.
        from sparktorch_tpu.train.pipeline import train_distributed_pipeline

        return train_distributed_pipeline(
            spec, data, labels=labels, mesh=mesh, iters=iters,
            n_micro=n_micro, verbose=verbose, seed=seed,
            metrics_hook=metrics_hook, checkpoint_dir=checkpoint_dir,
            checkpoint_every=checkpoint_every, resume=resume,
            partition_shuffles=partition_shuffles,
            early_stop_patience=early_stop_patience,
            validation_pct=validation_pct,
            # -1/0 are the torch-parity "disabled" sentinels (the
            # pp=1 paths check `mini_batch > 0`), not a request.
            mini_batch=(mini_batch
                        if mini_batch is not None and mini_batch > 0
                        else None),
            steps_per_call=steps_per_call,
            profile_dir=profile_dir,
            schedule=pipeline_schedule,
            virtual_stages=virtual_stages,
            pre_sharded=pre_sharded,
            telemetry=telemetry,
        )

    # The continuous stack sampler lives wherever ledgers live: the
    # ambient ledger names the thieving bucket, the sampler names the
    # function inside it. Env-gated; idempotent per process.
    from sparktorch_tpu.obs import health as _health
    from sparktorch_tpu.obs import profile as _profile

    _profile.ensure(tele)
    # Model-health lane (obs/health.py): per-rank ledger fed each step
    # with device values fetched K steps late. reset() re-bases the
    # EWMAs so a restarted attempt on the same bus is not judged
    # against the previous attempt's loss baseline.
    _hl = _health.ensure(tele, rank=jax.process_index())
    if _hl is not None:
        _hl.reset()
    if pre_sharded:
        # ``data`` is already a globally-sharded DataBatch (multi-host
        # path, train_distributed_multihost) — do not re-place it.
        train_batch, val_batch = data, None
        if spec.input_shape is None:
            spec.input_shape = tuple(train_batch.x.shape[1:])
    else:
        # data_wait: host-side batch prep + host->device placement is
        # time the accelerators spend waiting on input.
        with tele.span("train/data_prep"), _goodput.span("data_wait"):
            train_batch, val_batch = _as_batch(data, labels, validation_pct,
                                               seed)
            if spec.input_shape is None:
                spec.input_shape = tuple(np.asarray(train_batch.x).shape[1:])

            train_batch = prepare_sharded_batch(train_batch, mesh)
            if val_batch is not None:
                val_batch = prepare_sharded_batch(val_batch, mesh)

    rng = jax.random.key(seed)
    with tele.span("train/build_step"):
        tx = spec.make_optimizer()
    if pre_sharded:
        # Slicing a non-fully-addressable global array is not allowed;
        # init from an abstract sample of the right shape instead.
        sample_x = jnp.zeros((1,) + tuple(train_batch.x.shape[1:]),
                             train_batch.x.dtype)
    else:
        sample_x = train_batch.x[:1]
    # Initialize UNDER jit with replicated out_shardings: every process
    # runs the same compiled init, so this works on multi-process
    # (non-fully-addressable) meshes where a host-side device_put of
    # replicated state cannot (the reference replicates the model onto
    # every executor, distributed.py:112-115).
    # The jitted init is a compile-dominated call (one trace+compile,
    # negligible device work) — the ledger's compile bucket takes it.
    with tele.span("train/init"), _goodput.span(
            "compile", {"site": "train_init"}), mesh:
        state = jax.jit(
            lambda: create_train_state(spec, rng, sample_x=sample_x, tx=tx),
            out_shardings=replicated(mesh),
        )()

    ckpt, state = _open_checkpoint(checkpoint_dir, resume, state)
    if _hl is not None and _hl.leaf_keys is None:
        _hl.leaf_keys = _health.health_leaf_keys(state.params)

    with tele.span("train/build_step"):
        loss_fn = spec.loss_fn()
        module = spec.make_module()

        stopper = (
            EarlyStopping(patience=early_stop_patience)
            if early_stop_patience is not None and early_stop_patience > 0
            else None
        )
        # Fast path: fuse many steps into one compiled call (lax.scan).
        # Early stopping / the val forward no longer force 1 step/call:
        # the stop decision and per-iter val forward ride INSIDE the fused
        # scan (make_train_epoch_fused) with exact per-step semantics —
        # post-stop steps are masked to no-ops, so the only fusion cost is
        # the masked tail of the chunk where the stop fires (hence the
        # smaller default chunk there).
        steps_per_call = _resolve_steps_per_call(
            steps_per_call,
            default=(
                min(iters, 8)
                if (stopper is not None or val_batch is not None)
                else min(iters, 32)
            ),
            iters=iters,
            checkpoint_every=checkpoint_every,
            ckpt_active=ckpt is not None,
        )

        fused_signals = steps_per_call > 1 and (
            stopper is not None or val_batch is not None
        )
        es_state = init_es_state() if fused_signals else None
        if fused_signals:
            train_step = make_train_epoch_fused(
                module.apply, loss_fn, tx, mesh, steps_per_call,
                es_config=(
                    EsConfig(patience=early_stop_patience)
                    if stopper is not None else None
                ),
                with_val=val_batch is not None,
                mini_batch=mini_batch,
            )
        elif steps_per_call > 1:
            train_step = make_train_epoch(
                module.apply, loss_fn, tx, mesh, steps_per_call, mini_batch=mini_batch
            )
        else:
            train_step = make_train_step(
                module.apply, loss_fn, tx, mesh, mini_batch=mini_batch
            )
        eval_step = (
            make_eval_step(module.apply, loss_fn, mesh)
            if val_batch is not None and not fused_signals
            else None
        )
        _note_grad_allreduce(tele, state.params, mesh)
        _note_model_gauges(tele, module)

    from sparktorch_tpu.utils.metrics import MetricsRecorder
    from sparktorch_tpu.utils.tracing import profile_run, step_annotation

    recorder = MetricsRecorder(n_chips=mesh.size, telemetry=tele)
    metrics = recorder.records
    log = get_logger("sparktorch_tpu.train")
    # lint-obs: ok (pre-loop scalar — nothing queued yet)
    last_ckpt_step = int(jax.device_get(state.step)) if ckpt is not None else 0
    shuffle_key = jax.random.key(seed + 1)
    # What the hook gets beside the recorder's record: set per chunk.
    leaf_rows = None
    expert_rows = None  # [steps, MoE layers, experts held] of a chunk
    row_chunks = None  # [steps, MoE layers, 2 (run, possible)]
    leaf_keys = (_health.health_leaf_keys(state.params)
                 if metrics_hook else None)
    # On the fused path three host spans tile an iteration together
    # with train/step_chunk (prepare, readback, records): a few a
    # chunk. The per-step path keeps its one train/step span a step.
    chunk_span = (tele.span if steps_per_call > 1
                  else lambda _name: contextlib.nullcontext())
    profiler = profile_run(profile_dir, telemetry=tele)
    profiler.__enter__()
    completed = False
    try:
        for shuffle_round in range(max(1, partition_shuffles)):
            # Round 0 must ALSO shuffle when minibatch sampling is on:
            # sample_minibatch takes contiguous blocks, whose
            # uniformity argument requires random resident order — an
            # input sorted by label (common from Spark groupBy) would
            # otherwise feed near-single-class blocks all run.
            if shuffle_round > 0 or (mini_batch is not None and mini_batch > 0):
                shuffle_key, sub = jax.random.split(shuffle_key)
                with tele.span("train/shuffle"):
                    train_batch = _shuffle_batch(train_batch, sub, mesh)
            stop = False
            i = 0
            while i < iters:
                with chunk_span("train/chunk_prepare"):
                    # Fail fast if a peer host died (multi-host runs only; the
                    # gang's heartbeat marks survivors dead within one
                    # interval). Checking here — before dispatching the next
                    # compiled chunk — means we raise GangFailure instead of
                    # wedging in the chunk's collectives. The same spot
                    # publishes this rank's progress on its heartbeat so
                    # the driver can read cross-rank step skew, and hosts
                    # the chaos kill point (a seeded injection dies here,
                    # between compiled dispatches — where a real preempt
                    # lands; ft.supervisor.supervise_run then restarts the
                    # attempt resuming from the latest checkpoint).
                    check_gang()
                    notify_gang_step(i)
                    # `i` (the round-local iteration), not state.step: the
                    # latter would cost a device sync per chunk on the hot
                    # path; one-shot kill configs make the distinction
                    # irrelevant across resumes.
                    _chaos.fire("worker.step", worker=jax.process_index(),
                                step=i)
                    # Seeded poison-batch injection (bench-health drill):
                    # the site returns an action dict instead of raising,
                    # and the poisoned copy REPLACES the resident batch so
                    # the health ledger's replay anchor records exactly
                    # what dispatches.
                    _act = _chaos.fire("data.batch",
                                       worker=jax.process_index(), step=i)
                    if _act and _act.get("poison"):
                        train_batch = _chaos.poison_batch(train_batch)
                    if _hl is not None:
                        _hl.note_replay_anchor(state, train_batch)
                    # Seeded straggler injection: sleep BEFORE the step
                    # span so the skew referee sees a late fence arrival
                    # on this rank, not a longer step.
                    _chaos.straggle(jax.process_index(), i)
                    # The step clock is a goodput LedgerSpan: it times the
                    # dispatch+sync region whether or not a ledger is
                    # active (step_time_s comes off its duration), and when
                    # one is, the seconds land in the step bucket — or in
                    # ``compile`` when the jit dispatch cache grew under
                    # the call (first call / new shape).
                    cache0 = (_goodput.jit_cache_size(train_step)
                              if _goodput.active() is not None else None)
                if steps_per_call > 1:
                    n = min(steps_per_call, iters - i)
                    with _goodput.step_span(step=i) as _led:
                        with tele.span("train/step_chunk") as _chunk_span, \
                                step_annotation(
                                    int(metrics[-1]["iter"]) + 1
                                    if metrics else 0,
                                    telemetry=tele):
                            if fused_signals:
                                args = (((state, es_state), train_batch,
                                         val_batch)
                                        if val_batch is not None
                                        else ((state, es_state), train_batch))
                                (state, es_state), stacked = train_step(*args)
                            else:
                                state, stacked = train_step(state, train_batch)
                            _chunk_span.sync(stacked.loss)
                        with tele.span("train/chunk_readback"):
                            losses = np.asarray(stacked.loss)[:n]
                            examples = np.asarray(stacked.examples)[:n]
                            gnorms = np.asarray(stacked.grad_norm)[:n]
                            if fused_signals:
                                vals = np.asarray(stacked.val_loss)[:n]
                                actives = np.asarray(stacked.active)[:n]
                            else:
                                vals = [None] * n
                                actives = [True] * n
                            drops = (
                                np.asarray(stacked.drop_fraction)[:n]
                                if stacked.drop_fraction is not None
                                else [None] * n
                            )
                            if metrics_hook and stacked.health is not None:
                                leaf_rows = np.asarray(
                                    stacked.health.leaf_norms)[:n]
                            if stacked.expert_rows is not None:
                                expert_rows = np.asarray(
                                    stacked.expert_rows)[:n]
                            if stacked.row_chunks is not None:
                                row_chunks = np.asarray(
                                    stacked.row_chunks)[:n]
                        n_active = int(np.sum(np.asarray(actives)))
                        _led.count = max(1, n_active)
                        if cache0 is not None and (
                                _goodput.jit_cache_size(train_step)
                                or cache0) > cache0:
                            _led.rebucket("compile")
                else:
                    with _goodput.step_span(step=i) as _led:
                        with tele.span("train/step") as _step_span, \
                                step_annotation(i, telemetry=tele):
                            state, step_metrics = train_step(state,
                                                             train_batch)
                            _step_span.sync(step_metrics.loss)
                        if cache0 is not None and (
                                _goodput.jit_cache_size(train_step)
                                or cache0) > cache0:
                            _led.rebucket("compile")
                    if eval_step is not None:
                        # The per-iteration val forward is productive
                        # device work, just not a train step.
                        with _goodput.span("compute", {"site": "eval"}):
                            val_now = float(eval_step(state, val_batch))
                    else:
                        val_now = None
                    chunk = [(
                        float(step_metrics.loss),
                        float(step_metrics.examples),
                        float(step_metrics.grad_norm),
                        val_now,
                        True,
                        float(step_metrics.drop_fraction)
                        if step_metrics.drop_fraction is not None else None,
                    )]
                    dt = _led.duration_s
                    if metrics_hook and step_metrics.health is not None:
                        leaf_rows = [np.asarray(
                            step_metrics.health.leaf_norms)]
                    if step_metrics.expert_rows is not None:
                        expert_rows = [np.asarray(step_metrics.expert_rows)]
                    if step_metrics.row_chunks is not None:
                        row_chunks = [np.asarray(step_metrics.row_chunks)]
                    if _hl is not None:
                        _h = step_metrics.health
                        _hl.note_step(
                            device=None if _h is None else {
                                "finite": _h.finite,
                                "update_ratio": _h.update_ratio,
                                "leaf_norms": _h.leaf_norms,
                            },
                            host={"loss": chunk[0][0],
                                  "grad_norm": chunk[0][2]},
                        )

                with chunk_span("train/chunk_records"):
                    if steps_per_call > 1:
                        dt = _led.duration_s / max(1, n_active)
                        if _hl is not None and n_active > 0:
                            _h = stacked.health
                            _hl.note_step(
                                count=n_active,
                                device=None if _h is None else {
                                    "finite": _h.finite,
                                    "update_ratio": _h.update_ratio,
                                    "leaf_norms": _h.leaf_norms,
                                },
                                host={"loss": losses, "grad_norm": gnorms},
                            )
                        chunk = [
                            (float(l), float(e), float(g),
                             None if v is None or np.isnan(v) else float(v),
                             bool(a), None if dr is None else float(dr))
                            for l, e, g, v, a, dr in zip(
                                losses, examples, gnorms, vals, actives,
                                drops)
                        ]
                    for j, (loss, examples_n, gnorm, val_loss, active,
                            drop_f) in enumerate(chunk):
                        if not active:
                            # Step masked out inside the fused chunk: the
                            # stop had already fired — nothing trained.
                            break
                        record = {
                            "round": shuffle_round,
                            "iter": i,
                            "loss": loss,
                            "val_loss": val_loss,
                            "examples": examples_n,
                            "grad_norm": gnorm,
                            "step_time_s": dt,
                        }
                        if drop_f is not None:
                            record["moe_drop_fraction"] = drop_f
                        _note_moe_rows(
                            tele, record,
                            None if expert_rows is None else expert_rows[j],
                            None if row_chunks is None else row_chunks[j],
                            drop_f)
                        recorder.record(record)
                        if metrics_hook:
                            # The hook's copy also carries the step's
                            # per-leaf gradient norms (a row of the
                            # chunk's readback) and, once a call, their
                            # keys; the recorder keeps neither.
                            if leaf_rows is not None:
                                record = {**record,
                                          "leaf_grad_norms": leaf_rows[j]}
                                if leaf_keys is not None:
                                    record["leaf_grad_norm_keys"] = leaf_keys
                                    leaf_keys = None
                            metrics_hook(record)
                        if verbose:
                            # Reference prints per-partition loss lines
                            # (distributed.py:201-204); here one global
                            # line through the obs logger (lint-obs bans
                            # raw prints in library code).
                            msg = f"[sparktorch_tpu] round {shuffle_round} iter {i} loss {loss:.6f}"
                            if val_loss is not None:
                                msg += f" val_loss {val_loss:.6f}"
                            log.info(msg)
                        # Early stop needs no collective: `loss` is already the
                        # global mean, identical on every host (vs the
                        # reference's two extra all_reduces,
                        # distributed.py:186-197). On the fused path the
                        # decision already happened on-device (EsState).
                        if stopper is not None and not fused_signals:
                            signal = val_loss if val_loss is not None else loss
                            if stopper.step(signal):
                                stop = True
                                break
                        i += 1
                    # lint-obs: ok (one early-stop scalar per drained chunk)
                    if fused_signals and bool(jax.device_get(es_state.stopped)):
                        stop = True
                if ckpt is not None:
                    with tele.span("train/checkpoint"):
                        last_ckpt_step = _save_if_due(
                            ckpt, state, last_ckpt_step, checkpoint_every
                        )
                if stop:
                    break
            if stop:
                break
        completed = True
    finally:
        # Cleanup must run on the failure paths too (GangFailure from
        # check_gang, a raising metrics_hook): close the profiler
        # trace and flush async checkpoint writes already in flight.
        profiler.__exit__(None, None, None)
        if _hl is not None:
            # Drain the delayed-fetch tail so the published section
            # (and any postmortem) reflects the final steps.
            _hl.flush()
        _finalize_checkpoint(ckpt, state, completed)

    # lint-obs: ok (end-of-run gather after the loop drained)
    params = jax.device_get(state.params)
    model_state = jax.device_get(state.model_state)  # lint-obs: ok (end-of-run)
    return TrainResult(params=params, model_state=model_state, metrics=metrics,
                       spec=spec, summary=recorder.summary())


def train_distributed_multihost(
    torch_obj: Union[str, ModelSpec],
    local_x: np.ndarray,
    local_y: Optional[np.ndarray] = None,
    mesh: Optional[Mesh] = None,
    **kwargs,
) -> TrainResult:
    """Multi-host entry: each process contributes ITS partition of the
    data; the global batch is assembled across processes.

    Call after ``jax.distributed.initialize`` (e.g. via
    ``parallel.launch.bringup_multihost``). The analog of the
    reference's executor-side ``handle_model`` receiving a partition
    iterator (``distributed.py:66-128``), minus the phantom ranks:
    hosts with fewer rows pad with weight-0 examples, so skewed and
    empty partitions are mathematically absorbed into the global
    weighted mean.
    """
    from jax.experimental import multihost_utils

    mesh = mesh or build_mesh()
    n_proc = jax.process_count()

    local_x = np.asarray(local_x)
    if not np.issubdtype(local_x.dtype, np.integer):
        # Float features stay the DP trainer's float32; integer inputs
        # (token ids for the pp/sequence models) keep their dtype so
        # the pp route can cast them back to int32 on device.
        local_x = local_x.astype(np.float32)
    if local_x.ndim == 1:
        local_x = local_x.reshape(0, 1) if local_x.size == 0 else local_x[:, None]
    local_y = np.asarray(local_y) if local_y is not None else None

    # Agree on a common per-host row count AND feature shape (hosts
    # must build identically-shaped local shards for the global array;
    # an EMPTY host has no way to know the feature shape locally — the
    # analog of the reference's empty-partition protocol,
    # distributed.py:131-133). Fixed-width vector so the allgather
    # lines up even when ranks differ across hosts.
    _MAX_RANK = 8
    if local_x.ndim - 1 > _MAX_RANK:
        raise ValueError(f"feature rank {local_x.ndim - 1} > {_MAX_RANK}")
    # Layout: [rows, x_rank, x_dims(8), y_rank, y_dims(8), x_dtype,
    # y_dtype] — y_rank is -1 when this host has no labels, so donors
    # can repair BOTH the feature and label shapes of an empty host;
    # the dtype codes let the repair match the donors' dtype too (an
    # int-token host must not be joined by a float32 empty shard).
    _DTYPES = [np.float32, np.float64, np.int32, np.int64, np.int8,
               np.uint8, np.int16, np.uint16, np.uint32, np.uint64,
               np.bool_]

    def _dtype_code(dt) -> int:
        for i, d in enumerate(_DTYPES):
            if np.dtype(dt) == np.dtype(d):
                return i
        # Silently coding an unknown dtype as float32 would let an
        # empty host repair itself with a dtype its donors don't have.
        raise ValueError(
            f"unsupported multihost shard dtype {np.dtype(dt)}; use one "
            f"of {[np.dtype(d).name for d in _DTYPES]}"
        )

    width = 2 + _MAX_RANK + 1 + _MAX_RANK + 2
    shape_vec = np.full((width,), 0, np.int64)
    shape_vec[0] = local_x.shape[0]
    feat = local_x.shape[1:]
    shape_vec[1] = len(feat)
    shape_vec[2 : 2 + len(feat)] = feat
    y_off = 2 + _MAX_RANK
    if local_y is None:
        shape_vec[y_off] = -1
    else:
        y_feat = local_y.shape[1:]
        if len(y_feat) > _MAX_RANK:
            raise ValueError(f"label rank {len(y_feat)} > {_MAX_RANK}")
        shape_vec[y_off] = len(y_feat)
        shape_vec[y_off + 1 : y_off + 1 + len(y_feat)] = y_feat
    dt_off = y_off + 1 + _MAX_RANK
    shape_vec[dt_off] = _dtype_code(local_x.dtype)
    shape_vec[dt_off + 1] = (
        _dtype_code(local_y.dtype) if local_y is not None else -1
    )
    gathered = multihost_utils.process_allgather(shape_vec)
    gathered = gathered.reshape(-1, width)
    counts = gathered[:, 0]
    if local_x.shape[0] == 0:
        donors = gathered[gathered[:, 0] > 0]
        if len(donors):
            nd = int(donors[0, 1])
            feat = tuple(int(v) for v in donors[0, 2 : 2 + nd])
            local_x = np.zeros((0,) + feat,
                               _DTYPES[int(donors[0, dt_off])])
            if local_y is not None:
                y_rank = int(donors[0, y_off])
                y_feat = (
                    tuple(int(v) for v in donors[0, y_off + 1 : y_off + 1 + y_rank])
                    if y_rank > 0 else ()
                )
                y_code = int(donors[0, dt_off + 1])
                local_y = np.zeros(
                    (0,) + y_feat,
                    _DTYPES[y_code] if y_code >= 0 else local_y.dtype,
                )
    # Unsupervised (y=x) aliasing AFTER the donor repair, so the empty
    # host's labels adopt the repaired feature shape too. The pp route
    # must never see the alias: its heads are an LM (targets are the
    # NEXT token — alias the raw matrix and it trains an identity
    # copier) or a classifier (needs real labels).
    if local_y is None and dict(mesh.shape).get("pp", 1) > 1:
        from sparktorch_tpu.models.transformer import CausalLM as _CLM

        probe = deserialize_model(torch_obj)
        if isinstance(probe.make_module(), _CLM) and local_x.ndim == 2:
            local_x, local_y = local_x[:, :-1], local_x[:, 1:]
        else:
            raise ValueError(
                "pp>1 multihost training requires labels (local_y): "
                "next-token targets for a CausalLM id matrix, or class "
                "labels for a classifier"
            )
    if local_y is None:
        local_y = local_x
    local_w = np.ones((local_x.shape[0],), np.float32)
    per_host = int(counts.max())
    # The global batch must divide the mesh's batch shards.
    n_shards = 1
    for ax in BATCH_AXES:
        n_shards *= mesh.shape[ax]
    shards_per_host = max(1, n_shards // n_proc)
    per_host = max(
        shards_per_host,
        -(-per_host // shards_per_host) * shards_per_host,
    )
    from sparktorch_tpu.parallel.mesh import AXIS_PP as _PP

    if dict(mesh.shape).get(_PP, 1) > 1:
        # The pp route needs global rows divisible by dp * n_micro
        # (each dp shard splits into n_micro microbatches). Round
        # per_host up so per_host * n_proc satisfies that.
        import math as _math

        dp_sz = mesh.shape[BATCH_AXES[0]]
        need = dp_sz * int(kwargs.get("n_micro", 4))
        unit = need // _math.gcd(n_proc, need)
        per_host = -(-per_host // unit) * unit

    def pad_to(arr, n):
        if arr.shape[0] == n:
            return arr
        widths = [(0, n - arr.shape[0])] + [(0, 0)] * (arr.ndim - 1)
        return np.pad(arr, widths)

    sharding = batch_sharding(mesh)
    global_batch = DataBatch(
        jax.make_array_from_process_local_data(sharding, pad_to(local_x, per_host)),
        jax.make_array_from_process_local_data(sharding, pad_to(local_y, per_host)),
        jax.make_array_from_process_local_data(sharding, pad_to(local_w, per_host)),
    )
    return train_distributed(torch_obj, global_batch, mesh=mesh,
                             pre_sharded=True, **kwargs)


def train_distributed_streaming(
    torch_obj: Union[str, ModelSpec],
    data: Any,
    labels: Optional[np.ndarray] = None,
    mesh: Optional[Mesh] = None,
    chunk_rows: int = 65536,
    epochs: int = 1,
    steps_per_chunk: Optional[int] = None,
    mini_batch: Optional[int] = None,
    verbose: int = 0,
    seed: int = 0,
    metrics_hook: Optional[Callable[[dict], None]] = None,
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 0,
    resume: bool = False,
    telemetry=None,
) -> TrainResult:
    """Train on data LARGER than device HBM by streaming host chunks.

    The reference trains on whatever a Spark partition iterator yields
    (``distributed.py:66-128``) — dataset size is bounded by executor
    host memory, not accelerator memory. The resident-batch trainer
    (:func:`train_distributed`) device-puts the whole dataset, so its
    ceiling is HBM. This entry restores the reference's ceiling:

    - ``data`` is a host numpy array (or ``(x, y)`` tuple), kept in
      host RAM; it is walked in ``chunk_rows`` slices per epoch.
    - Each chunk is padded to the mesh's batch shards (weight-0 rows,
      the usual empty-partition protocol) and transferred while the
      PREVIOUS chunk is still training — double-buffered, so the copy
      rides under compute. Device memory stays O(2 chunks).
    - Per chunk, ``steps_per_chunk`` minibatch steps run as ONE fused
      compiled call (``lax.scan``); chunks share a single compiled
      program (uniform shape). Default: one pass over the chunk
      (``ceil(chunk_rows / mini_batch)`` steps, or 1 full-chunk step).
    - Each epoch re-walks the data in a fresh host permutation — the
      streaming analog of ``partition_shuffles``.
    """
    spec = deserialize_model(torch_obj)
    mesh = mesh or build_mesh()

    train_all, _ = _as_batch(data, labels, 0.0, seed)
    x = np.asarray(train_all.x, np.float32)
    y = np.asarray(train_all.y)
    w = np.asarray(train_all.w, np.float32)
    n = x.shape[0]
    if spec.input_shape is None:
        spec.input_shape = tuple(x.shape[1:])
    chunk_rows = min(chunk_rows, n)

    n_shards = 1
    for ax in BATCH_AXES:
        n_shards *= mesh.shape[ax]
    chunk_rows = -(-chunk_rows // n_shards) * n_shards  # pad up to shards
    if mini_batch is not None and mini_batch > 0:
        per_shard_rows = chunk_rows // n_shards
        default_steps = max(1, -(-per_shard_rows // max(1, mini_batch)))
    else:
        default_steps = 1
    steps = steps_per_chunk or default_steps

    tx = spec.make_optimizer()
    rng = jax.random.key(seed)
    sample_x = jnp.zeros((1,) + tuple(x.shape[1:]), jnp.float32)
    # Compile-dominated (same attribution as the DP trainer's init).
    with _goodput.span("compile", {"site": "train_init"}), mesh:
        state = jax.jit(
            lambda: create_train_state(spec, rng, sample_x=sample_x, tx=tx),
            out_shardings=replicated(mesh),
        )()

    module = spec.make_module()
    loss_fn = spec.loss_fn()
    if steps > 1:
        step_fn = make_train_epoch(module.apply, loss_fn, tx, mesh, steps,
                                   mini_batch=mini_batch)
    else:
        step_fn = make_train_step(module.apply, loss_fn, tx, mesh,
                                  mini_batch=mini_batch)

    sharding = batch_sharding(mesh)

    def put_chunk(lo: int, order: np.ndarray) -> DataBatch:
        idx = order[lo : lo + chunk_rows]
        cx, cy, cw = x[idx], y[idx], w[idx]
        pad = chunk_rows - cx.shape[0]
        if pad:
            cx = np.concatenate([cx, np.zeros((pad, *cx.shape[1:]), cx.dtype)])
            cy = np.concatenate([cy, np.zeros((pad, *cy.shape[1:]), cy.dtype)])
            cw = np.concatenate([cw, np.zeros((pad,), cw.dtype)])
        return DataBatch(
            jax.device_put(cx, sharding),
            jax.device_put(cy, sharding),
            jax.device_put(cw, sharding),
        )

    from sparktorch_tpu.utils.metrics import MetricsRecorder

    ckpt, state = _open_checkpoint(checkpoint_dir, resume, state)
    # lint-obs: ok (pre-loop scalar — nothing queued yet)
    last_ckpt_step = int(jax.device_get(state.step)) if ckpt is not None else 0

    tele = telemetry or get_telemetry()
    _note_grad_allreduce(tele, state.params, mesh)
    log = get_logger("sparktorch_tpu.train")
    # Stack sampler beside the ambient ledger (see train_distributed).
    from sparktorch_tpu.obs import health as _health
    from sparktorch_tpu.obs import profile as _profile

    _profile.ensure(tele)
    _hl = _health.ensure(tele, rank=jax.process_index())
    if _hl is not None:
        _hl.reset()
        if _hl.leaf_keys is None:
            _hl.leaf_keys = _health.health_leaf_keys(state.params)
    recorder = MetricsRecorder(n_chips=mesh.size, telemetry=tele,
                               prefix="train_streaming")
    # Fold the restored step into the shuffle seed: a resumed run must
    # draw FRESH permutations, not replay the epochs the interrupted
    # run already consumed.
    shuffle_rng = np.random.default_rng(seed + 1 + last_ckpt_step)
    it_counter = 0
    completed = False
    try:
        for epoch in range(max(1, epochs)):
            check_gang()
            order = shuffle_rng.permutation(n)
            starts = list(range(0, n, chunk_rows))
            # The epoch's first chunk has nothing to hide under: a
            # pure data wait.
            with _goodput.span("data_wait", {"site": "streaming_chunk"}):
                resident = put_chunk(starts[0], order)
            for ci, lo in enumerate(starts):
                # Per-chunk liveness, matching train_distributed: a
                # peer host dying mid-epoch must abort before the next
                # compiled dispatch, not at the epoch boundary.
                check_gang()
                notify_gang_step(it_counter)
                _act = _chaos.fire("data.batch",
                                   worker=jax.process_index(),
                                   step=it_counter)
                if _act and _act.get("poison"):
                    resident = _chaos.poison_batch(resident)
                if _hl is not None:
                    _hl.note_replay_anchor(state, resident)
                # Straggler injection before the step span: a late
                # fence arrival, visible to the skew referee.
                _chaos.straggle(jax.process_index(), it_counter)
                cache0 = (_goodput.jit_cache_size(step_fn)
                          if _goodput.active() is not None else None)
                with _goodput.step_span(step=it_counter) as _led, \
                        tele.span("train_streaming/chunk"):
                    state, metrics = step_fn(state, resident)
                    # Enqueue the NEXT chunk's host->device copy while
                    # the current chunk's (already dispatched) steps
                    # compute. The placement is a nested data_wait
                    # span: its seconds subtract from this chunk's
                    # step attribution (one second, one bucket) —
                    # though being deliberately overlapped under the
                    # in-flight compute, it is usually small.
                    if ci + 1 < len(starts):
                        with _goodput.span("data_wait",
                                           {"site": "streaming_chunk"}):
                            resident = put_chunk(starts[ci + 1], order)
                    losses = np.asarray(metrics.loss).reshape(-1)
                    _led.count = len(losses)
                    if cache0 is not None and (
                            _goodput.jit_cache_size(step_fn)
                            or cache0) > cache0:
                        _led.rebucket("compile")
                examples = np.asarray(metrics.examples).reshape(-1)
                dt = _led.duration_s / len(losses)
                if _hl is not None:
                    _h = metrics.health
                    _hl.note_step(
                        count=len(losses),
                        device=None if _h is None else {
                            "finite": _h.finite,
                            "update_ratio": _h.update_ratio,
                            "leaf_norms": _h.leaf_norms,
                        },
                        host={"loss": losses,
                              "grad_norm": np.asarray(
                                  metrics.grad_norm).reshape(
                                      losses.shape[0], -1)[:, 0]},
                    )
                for j in range(len(losses)):
                    record = {
                        "round": epoch, "iter": it_counter,
                        "loss": float(losses[j]),
                        "val_loss": None,
                        "examples": float(examples[j]),
                        "grad_norm": None,
                        "step_time_s": dt,
                    }
                    recorder.record(record)
                    if metrics_hook:
                        metrics_hook(record)
                    it_counter += 1
                # Chunk boundaries are the save points.
                last_ckpt_step = _save_if_due(
                    ckpt, state, last_ckpt_step, checkpoint_every
                )
                if verbose:
                    log.info(f"[sparktorch_tpu] epoch {epoch} chunk {ci} "
                             f"loss {losses[-1]:.6f}")
        completed = True
    finally:
        if _hl is not None:
            _hl.flush()
        _finalize_checkpoint(ckpt, state, completed)
    # lint-obs: ok (end-of-run gather after the loop drained)
    params = jax.device_get(state.params)
    model_state = jax.device_get(state.model_state)  # lint-obs: ok (end-of-run)
    return TrainResult(params=params, model_state=model_state,
                       metrics=recorder.records, spec=spec,
                       summary=recorder.summary())

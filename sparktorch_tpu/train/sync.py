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
import math
from typing import Any, Callable, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from sparktorch_tpu.ft import chaos as _chaos
from sparktorch_tpu.obs import get_logger, get_telemetry
from sparktorch_tpu.obs import goodput as _goodput
from sparktorch_tpu.parallel.launch import check_gang, notify_gang_step
from sparktorch_tpu.parallel.mesh import (
    AXIS_PP,
    BATCH_AXES,
    build_mesh,
    replicated,
)
from sparktorch_tpu.train.step import (
    EsConfig,
    TrainState,
    create_train_state,
    cut_specs,
    ep_rows,
    grad_allreduce_plan,
    init_es_state,
    make_eval_step,
    make_train_epoch,
    make_train_epoch_fused,
    make_train_step,
    refuse_ep_cut,
    shard_map_compat,
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


def _n_shards(mesh: Mesh) -> int:
    """How many shards the row axes cut the rows into: the batch axes
    and, where it has more than one member, ``ep``
    (``train/step.py`` ``ep_rows``)."""
    return math.prod(mesh.shape[ax] for ax in ep_rows(mesh)[0])


def _row_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for ``(rows, ...)`` arrays: rows split over the row axes."""
    return NamedSharding(mesh, PartitionSpec(ep_rows(mesh)[0]))


def prepare_sharded_batch(batch: DataBatch, mesh: Mesh) -> DataBatch:
    """Pad to a multiple of the row axes' size and place shards.

    The padding rows carry weight 0 — this is the empty-partition
    protocol (``distributed.py:46-63,131-133``) done with math instead
    of phantom collective participants.
    """
    padded = pad_to_multiple(batch, _n_shards(mesh))
    sharding = _row_sharding(mesh)
    return DataBatch(*(jax.device_put(a, sharding) for a in padded))


def _pad_rows(arr: np.ndarray, n: int) -> np.ndarray:
    """``arr`` with zero rows (weight 0) added up to ``n``."""
    if arr.shape[0] == n:
        return arr
    return np.pad(arr, [(0, n - arr.shape[0])] + [(0, 0)] * (arr.ndim - 1))


def _shuffle_batch(batch: DataBatch, key: jax.Array, mesh: Mesh) -> DataBatch:
    """Global permutation between shuffle rounds — the analog of the
    reference's RDD re-shuffle (``distributed.py:267-273``), executed
    on-device (an all-to-all under the hood, riding ICI)."""
    perm = jax.random.permutation(key, batch.x.shape[0])
    sharding = _row_sharding(mesh)
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
    all-reduces a step issues, one a parameter array (0 where the axes
    a leaf is summed over have one member and nothing is reduced), their
    bytes and, apart, a member's bytes of the leaves cut over ``ep``."""
    buckets, nbytes, cut_bytes = grad_allreduce_plan(params, mesh)
    tele.gauge("train.grad_allreduce.buckets", buckets)
    tele.gauge("train.grad_allreduce.bytes", nbytes)
    if cut_bytes:
        # a member's block of the leaves cut over ep, which no member
        # sums over ep
        tele.gauge("train.grad_allreduce.bytes_ep_cut", cut_bytes)


def _note_model_gauges(tele, module, row_shape, mesh: Mesh) -> None:
    """What a model says of its own structure and of the step built for
    rows of ``row_shape`` (``train_gauges``: the sparse attention's
    top-k, the experts held and routed, the layers whose attention is a
    fused kernel), on the bus. The model is asked where the step's
    trace asks it, in the body of a ``shard_map`` over ``mesh`` (an
    abstract evaluation: nothing runs), so a choice it makes from the
    mesh in sight is the one the step was built with."""
    gauges = getattr(module, "train_gauges", None)
    if gauges is None:
        return
    said = {}

    def body():
        said.update(gauges(row_shape))
        return jnp.zeros(())

    jax.eval_shape(shard_map_compat(body, mesh, in_specs=(),
                                    out_specs=jax.sharding.PartitionSpec()))
    for name, value in said.items():
        tele.gauge(name, value)


def _note_model_counters(tele, module, record, sown, drop_fraction) -> None:
    """What the model sowed in one step (``{name: that step's values
    by layer}``), into the step's record and onto the bus, as the model
    reads it (``train_counters``: the record's fields, the bus's
    counters and gauges). The trainer knows no counter by name."""
    explain = getattr(module, "train_counters", None)
    if not sown or explain is None:
        return
    fields, counters, gauges = explain(sown, drop_fraction)
    record.update(fields)
    for name, value in counters.items():
        tele.counter(name, value)
    for name, value in gauges.items():
        tele.gauge(name, value)


def _make_step(module, loss_fn, tx, mesh: Mesh, steps: int, mini_batch,
               in_scan: Optional[dict] = None):
    """The compiled step of ``steps`` steps a dispatch: one step, a scan
    of them, or (``in_scan``: ``es_config``, ``with_val``) a scan that
    decides the early stop and runs the val forward itself."""
    if in_scan is not None:
        return make_train_epoch_fused(module.apply, loss_fn, tx, mesh, steps,
                                      mini_batch=mini_batch, **in_scan)
    if steps > 1:
        return make_train_epoch(module.apply, loss_fn, tx, mesh, steps,
                                mini_batch=mini_batch)
    return make_train_step(module.apply, loss_fn, tx, mesh,
                           mini_batch=mini_batch)


def _result(spec, loop: "_ChunkLoop", obs: "_RunObservers") -> TrainResult:
    """What a fit hands back, the final parameters on the host."""
    # lint-obs: ok (end-of-run gather after the loop drained)
    params, model_state = jax.device_get(
        (loop.state.params, loop.state.model_state))
    return TrainResult(params=params, model_state=model_state,
                       metrics=obs.recorder.records, spec=spec,
                       summary=obs.recorder.summary())


def _jit_init(spec, mesh: Mesh, rng, sample_x, tx):
    """The compiled init: the state replicated over ``mesh`` by the
    ``jit``'s own ``out_shardings`` (no mesh is in sight of the trace:
    a module that picks a path by the mesh sees the process's device
    count, :func:`sparktorch_tpu.models.transformer.pick_attention`),
    or, over an ``ep`` axis of more than one member, placed leaf by leaf
    as the step takes it (``train/step.py`` ``ep_rows``): a cut leaf is
    born in blocks on its members and no device holds it whole."""
    make = lambda: create_train_state(spec, rng, sample_x=sample_x, tx=tx)
    shardings, cut = replicated(mesh), ep_rows(mesh)[1]
    if cut is not None:
        shardings = jax.tree.map(
            lambda spec: NamedSharding(mesh, spec),
            cut_specs(jax.eval_shape(make), cut))
    return jax.jit(make, out_shardings=shardings)


def _init_state(tele, spec, mesh: Mesh, rng, sample_x, tx) -> TrainState:
    """Initialize UNDER jit with replicated out_shardings: every process
    runs the same compiled init, so this works on multi-process
    (non-fully-addressable) meshes where a host-side device_put of
    replicated state cannot (the reference replicates the model onto
    every executor, distributed.py:112-115). The jitted init is a
    compile-dominated call (one trace+compile, negligible device work):
    the goodput ledger's compile bucket takes it."""
    with tele.span("train/init"), _goodput.span(
            "compile", {"site": "train_init"}), mesh:
        return _jit_init(spec, mesh, rng, sample_x, tx)()


class _RunObservers:
    """Everything that watches a fit without being part of it, wired
    once a call: the stack sampler and the model-health ledger
    (``obs.profile``, ``obs.health``), the goodput ledger's step clock
    (``obs.goodput``), the gang's liveness check, the seeded chaos
    sites (``ft.chaos``), the trace's step annotation and the
    recorder of the step records. The loop calls it at four points and
    sees none of them by name."""

    def __init__(self, tele, mesh: Mesh, prefix: str = "train"):
        from sparktorch_tpu.obs import health, profile
        from sparktorch_tpu.utils.metrics import MetricsRecorder

        self._tele = tele
        self._rank = jax.process_index()
        # The stack sampler lives wherever ledgers live: the ambient
        # ledger names the thieving bucket, the sampler the function
        # inside it. Env-gated; idempotent per process.
        profile.ensure(tele)
        # reset() re-bases the EWMAs, so a restarted attempt on the same
        # bus is not judged against the previous attempt's losses.
        self._health = health.ensure(tele, rank=self._rank)
        if self._health is not None:
            self._health.reset()
        self.recorder = MetricsRecorder(n_chips=mesh.size, telemetry=tele,
                                        prefix=prefix)

    def before_dispatch(self, i: int, state: TrainState,
                        batch: DataBatch) -> DataBatch:
        """Between two compiled dispatches, where a real preempt lands:
        GangFailure if a peer host died (its heartbeat marks it dead
        within one interval), not a wedge in the chunk's collectives;
        this rank's progress onto its heartbeat, for the driver's step
        skew; the seeded chaos sites. Returns the batch to dispatch on."""
        check_gang()
        notify_gang_step(i)
        # The kill point (ft.supervisor.supervise_run restarts the
        # attempt from the latest checkpoint). `i`, not state.step: that
        # would cost a device sync a chunk, and one-shot kill configs
        # make the distinction irrelevant across resumes.
        _chaos.fire("worker.step", worker=self._rank, step=i)
        # Poison-batch injection (chaos ``poison_batch_at``): the poisoned copy
        # REPLACES the batch, so the health ledger's replay anchor
        # records exactly what dispatches.
        act = _chaos.fire("data.batch", worker=self._rank, step=i)
        if act and act.get("poison"):
            batch = _chaos.poison_batch(batch)
        if self._health is not None:
            if self._health.leaf_keys is None:
                from sparktorch_tpu.obs.health import health_leaf_keys

                self._health.leaf_keys = health_leaf_keys(state.params)
            self._health.note_replay_anchor(state, batch)
        # Straggler injection: the sleep comes BEFORE the step span, so
        # the skew referee sees a late arrival, not a longer step.
        _chaos.straggle(self._rank, i)
        return batch

    @contextlib.contextmanager
    def step_span(self, i: int, step_fn):
        """The step clock, a goodput LedgerSpan: it times the
        dispatch+sync region whether or not a ledger is active
        (step_time_s comes off its duration), and when one is, the
        seconds land in the step bucket, or in ``compile`` when the jit
        dispatch cache grew under the call (first call / new shape).
        Set ``count`` on it to the steps the dispatch trained."""
        cache0 = (_goodput.jit_cache_size(step_fn)
                  if _goodput.active() is not None else None)
        with _goodput.step_span(step=i) as led:
            yield led
            if cache0 is not None and (
                    _goodput.jit_cache_size(step_fn) or cache0) > cache0:
                led.rebucket("compile")

    def annotation(self):
        """The trace's step boundary, numbered by the steps recorded."""
        from sparktorch_tpu.utils.tracing import step_annotation

        return step_annotation(len(self.recorder.records),
                               telemetry=self._tele)

    def after_chunk(self, count: int, health, loss, grad_norm) -> None:
        """The chunk's ``count`` trained steps to the health ledger:
        ``health`` stays on the device (it is fetched K notes late),
        ``loss`` and ``grad_norm`` are the rows just read back."""
        if self._health is None or count < 1:
            return
        self._health.note_step(
            count=count,
            device=None if health is None else {
                "finite": health.finite,
                "update_ratio": health.update_ratio,
                "leaf_norms": health.leaf_norms,
            },
            host={"loss": loss, "grad_norm": grad_norm},
        )

    def record(self, record: dict) -> None:
        self.recorder.record(record)

    def close(self) -> None:
        if self._health is not None:
            # Drain the delayed-fetch tail so the published section
            # (and any postmortem) reflects the final steps.
            self._health.flush()


class _BatchSource:
    """The rows a dispatch trains on: ``batch`` is what the next one
    takes (a poisoned copy is put back here); ``advance`` is called once
    it is dispatched, inside its step span (resident rows: nothing; the
    streaming fit enqueues the next chunk's copy)."""

    def __init__(self, batch: Optional[DataBatch] = None,
                 advance: Callable[[], None] = lambda: None):
        self.batch, self.advance = batch, advance


class _ChunkLoop:
    """The host's side of one dispatch of the compiled step, written
    once for the resident fit a step a dispatch (``steps`` 1), the
    resident fit by fused chunks and the streaming fit: observers,
    dispatch, readback, the steps' records, the checkpoint. Owns the
    carried ``state`` (the step donates it) and, where the stop is
    decided inside the scan, the early-stop carry ``es_state``.

    ``span`` names the dispatch (``train/step_chunk``, ``train/step``,
    ``train_streaming/chunk``). With ``tiled``, three more host spans
    tile an iteration together with it, a few a chunk; a step a
    dispatch keeps its one span a step."""

    def __init__(self, tele, obs: _RunObservers, module, state: TrainState,
                 step_fn, steps: int, span: str, tiled: bool = False,
                 metrics_hook=None, verbose: int = 0, ckpt=None,
                 checkpoint_every: int = 0, val_batch=None, eval_step=None,
                 es_state=None, stopper=None):
        self.state, self.es_state = state, es_state
        self._tele, self._obs, self._module = tele, obs, module
        self._step_fn, self._steps, self._span = step_fn, steps, span
        self._tile = (tele.span if tiled
                      else lambda _name: contextlib.nullcontext())
        self._checkpoint_span = span.split("/")[0] + "/checkpoint"
        self._hook, self._verbose = metrics_hook, verbose
        self._log = get_logger("sparktorch_tpu.train")
        self._val_batch, self._eval_step = val_batch, eval_step
        self._stopper = stopper
        self._ckpt, self._every = ckpt, checkpoint_every
        # lint-obs: ok (pre-loop scalar — nothing queued yet)
        self.last_ckpt_step = (int(jax.device_get(state.step))
                               if ckpt is not None else 0)
        # The hook's copy of a record also carries the step's per-leaf
        # gradient norms and, once a call, their keys.
        self._leaf_keys = None
        if metrics_hook:
            from sparktorch_tpu.obs.health import health_leaf_keys

            self._leaf_keys = health_leaf_keys(state.params)

    def _dispatch(self, batch: DataBatch):
        if self.es_state is None:
            self.state, stacked = self._step_fn(self.state, batch)
        else:
            batches = ((batch,) if self._val_batch is None
                       else (batch, self._val_batch))
            (self.state, self.es_state), stacked = self._step_fn(
                (self.state, self.es_state), *batches)
        return stacked

    def run(self, source: _BatchSource, i: int, round_: int):
        """One dispatch on ``source.batch`` at iteration ``i`` of round
        ``round_``. Returns ``(steps recorded, whether to stop)``."""
        tele, obs, steps = self._tele, self._obs, self._steps
        with self._tile("train/chunk_prepare"):
            source.batch = obs.before_dispatch(i, self.state, source.batch)
        with obs.step_span(i, self._step_fn) as led:
            with tele.span(self._span) as span, obs.annotation():
                stacked = self._dispatch(source.batch)
                source.advance()
                span.sync(stacked.loss)
            with self._tile("train/chunk_readback"):
                # A scan stacks its steps; one step is a chunk of one. The
                # health vector stays on the device, but for the hook's norms.
                rows = (np.asarray if steps > 1
                        else lambda a: np.asarray(a)[None])
                host = jax.tree.map(rows, stacked._replace(health=None))
                leaf_rows = (rows(stacked.health.leaf_norms)
                             if self._hook and stacked.health is not None
                             else None)
            n_active = (steps if host.active is None
                        else int(np.sum(host.active)))
            led.count = max(1, n_active)
        vals = [None] * steps
        if host.val_loss is not None:
            vals = [None if np.isnan(v) else float(v) for v in host.val_loss]
        elif self._eval_step is not None:
            # The per-iteration val forward is productive device work,
            # just not a train step.
            with _goodput.span("compute", {"site": "eval"}):
                vals = [float(self._eval_step(self.state, self._val_batch))]
        with self._tile("train/chunk_records"):
            obs.after_chunk(n_active, stacked.health, host.loss,
                            host.grad_norm)
            done, stop = self._record(
                host, vals, leaf_rows, i, round_, n_active,
                led.duration_s / max(1, n_active))
            if self.es_state is not None:
                # lint-obs: ok (one early-stop scalar per drained chunk)
                stop = bool(jax.device_get(self.es_state.stopped))
        if self._ckpt is not None:
            with tele.span(self._checkpoint_span):
                self.last_ckpt_step = _save_if_due(
                    self._ckpt, self.state, self.last_ckpt_step, self._every)
        return done, stop

    def _record(self, host, vals, leaf_rows, i, round_, n_active, dt):
        """The chunk's trained steps (the first ``n_active``: the stop had
        fired and the scan masked the rest out) to the recorder, the hook,
        the log and the host's stopper. Returns ``(recorded, stopped)``."""
        for j in range(n_active):
            loss, val_loss = float(host.loss[j]), vals[j]
            record = {
                "round": round_, "iter": i + j, "loss": loss,
                "val_loss": val_loss, "examples": float(host.examples[j]),
                "grad_norm": float(host.grad_norm[j]), "step_time_s": dt,
            }
            drop_f = (None if host.drop_fraction is None
                      else float(host.drop_fraction[j]))
            if drop_f is not None:
                record["moe_drop_fraction"] = drop_f
            _note_model_counters(
                self._tele, self._module, record,
                {name: v[j] for name, v in (host.sown or {}).items()}, drop_f)
            self._obs.record(record)
            if self._hook:
                # the recorder keeps neither the norms nor their keys
                if leaf_rows is not None:
                    record = {**record, "leaf_grad_norms": leaf_rows[j]}
                    if self._leaf_keys is not None:
                        record["leaf_grad_norm_keys"] = self._leaf_keys
                        self._leaf_keys = None
                self._hook(record)
            if self._verbose:
                # The reference prints a loss line a partition
                # (distributed.py:201-204); here one global line.
                msg = (f"[sparktorch_tpu] round {round_} iter {i + j} "
                       f"loss {loss:.6f}")
                if val_loss is not None:
                    msg += f" val_loss {val_loss:.6f}"
                self._log.info(msg)
            # Early stop needs no collective: `loss` is already the
            # global mean, identical on every host (vs the reference's
            # two extra all_reduces, distributed.py:186-197).
            if self._stopper is not None and self._stopper.step(
                    val_loss if val_loss is not None else loss):
                return j + 1, True
        return n_active, False


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

    if checkpoint_dir:
        refuse_ep_cut(mesh, "a checkpoint of the sync DP trainer")
    obs = _RunObservers(tele, mesh)
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
    state = _init_state(tele, spec, mesh, rng, sample_x, tx)
    ckpt, state = _open_checkpoint(checkpoint_dir, resume, state)

    with tele.span("train/build_step"):
        loss_fn = spec.loss_fn()
        module = spec.make_module()

        stopper = (EarlyStopping(patience=early_stop_patience)
                   if early_stop_patience is not None
                   and early_stop_patience > 0 else None)
        # Fast path: fuse many steps into one compiled call (lax.scan).
        # Early stopping / the val forward do not force 1 step/call: the
        # stop decision and per-iter val forward ride INSIDE the fused
        # scan (make_train_epoch_fused) with exact per-step semantics.
        # Post-stop steps are masked to no-ops, so the only fusion cost
        # is the masked tail of the chunk where the stop fires (hence
        # the smaller default chunk there).
        steps_per_call = _resolve_steps_per_call(
            steps_per_call,
            default=min(iters, 32 if stopper is None and val_batch is None
                        else 8),
            iters=iters, checkpoint_every=checkpoint_every,
            ckpt_active=ckpt is not None)
        # At one step a dispatch the host's stopper and a separate val
        # forward decide (ROADMAP D15); inside a scan, the scan's own.
        fused_signals = steps_per_call > 1 and (
            stopper is not None or val_batch is not None)
        train_step = _make_step(
            module, loss_fn, tx, mesh, steps_per_call, mini_batch,
            in_scan=dict(es_config=(EsConfig(patience=early_stop_patience)
                                    if stopper is not None else None),
                         with_val=val_batch is not None)
            if fused_signals else None)
        eval_step = (make_eval_step(module.apply, loss_fn, mesh)
                     if val_batch is not None and not fused_signals else None)
        _note_grad_allreduce(tele, state.params, mesh)
        _note_model_gauges(tele, module,
                           tuple(train_batch.x.shape[1:]), mesh)

    from sparktorch_tpu.utils.tracing import profile_run

    loop = _ChunkLoop(
        tele, obs, module, state, train_step, steps_per_call,
        span="train/step_chunk" if steps_per_call > 1 else "train/step",
        tiled=steps_per_call > 1, metrics_hook=metrics_hook, verbose=verbose,
        ckpt=ckpt, checkpoint_every=checkpoint_every, val_batch=val_batch,
        eval_step=eval_step,
        es_state=init_es_state() if fused_signals else None,
        stopper=None if fused_signals else stopper)
    source = _BatchSource(train_batch)
    del train_batch  # or a shuffle could not free the rows it read
    shuffle_key = jax.random.key(seed + 1)
    completed = False
    try:
        with profile_run(profile_dir, telemetry=tele):
            for shuffle_round in range(max(1, partition_shuffles)):
                # Round 0 must ALSO shuffle when minibatch sampling is
                # on: sample_minibatch takes contiguous blocks, whose
                # uniformity argument requires random resident order (an
                # input sorted by label, common from Spark groupBy,
                # would otherwise feed near-single-class blocks all run).
                if shuffle_round > 0 or (mini_batch is not None
                                         and mini_batch > 0):
                    shuffle_key, sub = jax.random.split(shuffle_key)
                    with tele.span("train/shuffle"):
                        source.batch = _shuffle_batch(source.batch, sub, mesh)
                i, stop = 0, False
                while i < iters and not stop:
                    done, stop = loop.run(source, i, shuffle_round)
                    i += done
                if stop:
                    break
        completed = True
    finally:
        # Cleanup must run on the failure paths too (GangFailure from
        # check_gang, a raising metrics_hook): the profiler's trace is
        # closed by now; flush the health ledger and the async
        # checkpoint writes already in flight.
        obs.close()
        _finalize_checkpoint(ckpt, loop.state, completed)
    return _result(spec, loop, obs)


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
    refuse_ep_cut(mesh, "train_distributed_multihost")
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
    if local_y is None and dict(mesh.shape).get(AXIS_PP, 1) > 1:
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
    n_shards = _n_shards(mesh)
    shards_per_host = max(1, n_shards // n_proc)
    per_host = max(
        shards_per_host,
        -(-per_host // shards_per_host) * shards_per_host,
    )
    if dict(mesh.shape).get(AXIS_PP, 1) > 1:
        # The pp route needs global rows divisible by dp * n_micro
        # (each dp shard splits into n_micro microbatches). Round
        # per_host up so per_host * n_proc satisfies that.
        dp_sz = mesh.shape[BATCH_AXES[0]]
        need = dp_sz * int(kwargs.get("n_micro", 4))
        unit = need // math.gcd(n_proc, need)
        per_host = -(-per_host // unit) * unit

    sharding = _row_sharding(mesh)
    global_batch = DataBatch(*(
        jax.make_array_from_process_local_data(sharding,
                                               _pad_rows(a, per_host))
        for a in (local_x, local_y, local_w)))
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
    refuse_ep_cut(mesh, "train_distributed_streaming")
    tele = telemetry or get_telemetry()
    obs = _RunObservers(tele, mesh, prefix="train_streaming")

    train_all, _ = _as_batch(data, labels, 0.0, seed)
    x = np.asarray(train_all.x, np.float32)
    y = np.asarray(train_all.y)
    w = np.asarray(train_all.w, np.float32)
    n = x.shape[0]
    if spec.input_shape is None:
        spec.input_shape = tuple(x.shape[1:])
    chunk_rows = min(chunk_rows, n)

    n_shards = _n_shards(mesh)
    chunk_rows = -(-chunk_rows // n_shards) * n_shards  # pad up to shards
    if mini_batch is not None and mini_batch > 0:
        per_shard_rows = chunk_rows // n_shards
        default_steps = max(1, -(-per_shard_rows // max(1, mini_batch)))
    else:
        default_steps = 1
    steps = steps_per_chunk or default_steps

    tx = spec.make_optimizer()
    state = _init_state(
        tele, spec, mesh, jax.random.key(seed),
        jnp.zeros((1,) + tuple(x.shape[1:]), jnp.float32), tx)
    ckpt, state = _open_checkpoint(checkpoint_dir, resume, state)

    module = spec.make_module()
    loss_fn = spec.loss_fn()
    step_fn = _make_step(module, loss_fn, tx, mesh, steps, mini_batch)
    _note_grad_allreduce(tele, state.params, mesh)
    _note_model_gauges(tele, module, tuple(x.shape[1:]), mesh)

    sharding = _row_sharding(mesh)

    def put_chunk(lo: int, order: np.ndarray) -> DataBatch:
        idx = order[lo : lo + chunk_rows]
        return DataBatch(*(
            jax.device_put(_pad_rows(a[idx], chunk_rows), sharding)
            for a in (x, y, w)))

    # Chunk boundaries are the save points.
    loop = _ChunkLoop(tele, obs, module, state, step_fn, steps,
                      span="train_streaming/chunk", metrics_hook=metrics_hook,
                      ckpt=ckpt, checkpoint_every=checkpoint_every)
    log = get_logger("sparktorch_tpu.train")
    # Fold the restored step into the shuffle seed: a resumed run must
    # draw FRESH permutations, not replay the epochs the interrupted
    # run already consumed.
    shuffle_rng = np.random.default_rng(seed + 1 + loop.last_ckpt_step)
    completed = False
    try:
        for epoch in range(max(1, epochs)):
            order = shuffle_rng.permutation(n)
            starts = iter(range(0, n, chunk_rows))

            def put_next():
                lo = next(starts, None)
                if lo is not None:
                    with _goodput.span("data_wait",
                                       {"site": "streaming_chunk"}):
                        source.batch = put_chunk(lo, order)

            # Double-buffered: the NEXT chunk's host->device copy is
            # enqueued while the current chunk's (already dispatched)
            # steps compute. The placement is a nested data_wait span:
            # its seconds subtract from that chunk's step attribution
            # (one second, one bucket), though being deliberately
            # overlapped under the in-flight compute, it is usually
            # small. The epoch's first chunk has nothing to hide under:
            # a pure data wait.
            source = _BatchSource(advance=put_next)
            put_next()
            for ci in range(-(-n // chunk_rows)):
                loop.run(source, len(obs.recorder.records), epoch)
                if verbose:
                    log.info(f"[sparktorch_tpu] epoch {epoch} chunk {ci} "
                             f"loss {obs.recorder.records[-1]['loss']:.6f}")
        completed = True
    finally:
        obs.close()
        _finalize_checkpoint(ckpt, loop.state, completed)
    return _result(spec, loop, obs)

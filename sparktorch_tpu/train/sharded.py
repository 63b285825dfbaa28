"""GSPMD sharded trainer: dp x fsdp x tp x sp in one jitted step.

The shard_map trainer (:mod:`sparktorch_tpu.train.step`) mirrors the
reference's replicated-model data parallelism. This module is the
scaling path the reference has no analog for (SURVEY §2.4: TP/SP
"absent"): parameters are laid out by sharding rules, the batch is
sharded over dp(+fsdp) and — for sequence models — the sequence axis
over sp; the loss is a global weighted mean, and XLA GSPMD inserts
every collective (tp all-reduces, fsdp all-gathers, dp grad
reduction) over ICI. Ring attention's shard_map island composes
inside this jit (transformer.py).

Run under ``jax.set_mesh(mesh)`` — :func:`make_sharded_train_step`
returns a step already wrapped to do so.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sparktorch_tpu.ft import chaos as _chaos
from sparktorch_tpu.parallel.compat import set_mesh as _set_mesh
from sparktorch_tpu.parallel.mesh import AXIS_SP, BATCH_AXES, replicated
from sparktorch_tpu.parallel.sharding_rules import shard_params, transformer_rules
from sparktorch_tpu.train.step import (
    HealthVec,
    StepMetrics,
    TrainState,
    _accepts_example_w,
    _moe_drop_counts,
    _split_variables,
    refuse_sync_dp_only,
)
from sparktorch_tpu.utils.data import DataBatch


def batch_specs(seq_sharded: bool) -> DataBatch:
    """PartitionSpecs for (x, y, w). Sequence models shard x/y's
    second dim over sp; targets of LMs are token-level, so y follows
    x's layout when it has a sequence dim."""
    if seq_sharded:
        return DataBatch(
            x=P(BATCH_AXES, AXIS_SP),
            y=P(BATCH_AXES, AXIS_SP),
            w=P(BATCH_AXES),
        )
    return DataBatch(x=P(BATCH_AXES), y=P(BATCH_AXES), w=P(BATCH_AXES))


def create_sharded_state(
    spec,
    mesh: Mesh,
    rng: jax.Array,
    sample_x: jax.Array,
    tx: Optional[optax.GradientTransformation] = None,
    rules: Optional[Callable] = None,
) -> Tuple[TrainState, Any]:
    """Initialize params DIRECTLY into their target shardings: init is
    jitted with out_shardings from the rules, so no host-side full
    materialization ever happens (the driver-OOM-avoidance property of
    the reference's lazy mode, README.md:115-132, done at the XLA
    level)."""
    tx = tx or spec.make_optimizer()
    module = spec.make_module()
    refuse_sync_dp_only(module, "the GSPMD trainer")
    rules = rules or transformer_rules(mesh)

    # The init trace runs the full forward (incl. any shard_map
    # island), so the sample batch must divide across the batch axes.
    import numpy as np

    n_batch_shards = 1
    for ax in BATCH_AXES:
        n_batch_shards *= mesh.shape[ax]
    sample_x = np.asarray(sample_x)
    if sample_x.shape[0] % n_batch_shards != 0:
        reps = -(-n_batch_shards // sample_x.shape[0])
        sample_x = np.tile(sample_x, (reps,) + (1,) * (sample_x.ndim - 1))[
            :n_batch_shards
        ]

    # Everything under set_mesh: tracing the module may hit the ring-
    # attention or MoE-dispatch shard_map islands, which resolve the
    # ambient mesh.
    #
    # Layout-invariant init is a PARITY requirement: the default
    # (non-partitionable) threefry lowering makes a jitted init's
    # draws depend on the out_shardings, so an ep-sharded expert
    # weight started at DIFFERENT values on an ep=2 mesh than on ep=1
    # — the dominant term of the historical ~0.7% ep-parity drift
    # (the MoE suite now pins ep=2 vs ep=1 at rtol 1e-5, which is
    # impossible without this). Scoped tightly to the init jit: the
    # train step itself draws no randoms, and the flag changes draw
    # VALUES, so leaking it process-wide would silently shift every
    # other trainer's seeds — hence set INSIDE the try whose finally
    # restores it.
    _old_threefry = jax.config.jax_threefry_partitionable
    try:
        jax.config.update("jax_threefry_partitionable", True)
        with _set_mesh(mesh):
            abstract = jax.eval_shape(lambda k: module.init(k, sample_x), rng)
            # _split_variables drops the write-only 'losses' collection
            # (sown aux objectives), which must never live in the carried
            # train state — see step().
            a_params, a_state = _split_variables(abstract)
            param_sh = shard_params(a_params, mesh, rules)
            state_sh = jax.tree.map(lambda _: replicated(mesh), a_state)

            def init_all(key):
                variables = module.init(key, sample_x)
                params, mstate = _split_variables(variables)
                opt_state = tx.init(params)
                return params, mstate, opt_state

            a_opt = jax.eval_shape(lambda k: init_all(k)[2], rng)
            opt_sh = _opt_state_shardings(a_opt, a_params, param_sh, mesh)

            params, mstate, opt_state = jax.jit(
                init_all, out_shardings=(param_sh, state_sh, opt_sh)
            )(rng)
    finally:
        jax.config.update("jax_threefry_partitionable", _old_threefry)
    # step/rng are placed on the mesh too: an off-mesh leaf gives the
    # first call a different abstract type than the step's own output,
    # and the second call would compile the whole step again.
    state = TrainState(
        step=jax.device_put(jnp.zeros((), jnp.int32), replicated(mesh)),
        params=params,
        model_state=mstate,
        opt_state=opt_state,
        rng=jax.device_put(rng, replicated(mesh)),
    )
    shardings = TrainState(
        step=replicated(mesh),
        params=param_sh,
        model_state=state_sh,
        opt_state=opt_sh,
        rng=replicated(mesh),
    )
    return state, shardings


def _opt_state_shardings(a_opt, a_params, param_sh, mesh: Mesh):
    """Optimizer-state leaves that mirror a param leaf (same shape)
    inherit its sharding; scalars/others replicate. Keeps Adam moments
    sharded like their params (fsdp/tp) — the memory win that matters."""
    shape_map = {}
    for leaf, sh in zip(jax.tree.leaves(a_params), jax.tree.leaves(param_sh)):
        shape_map.setdefault((tuple(leaf.shape), str(leaf.dtype)), sh)

    def pick(leaf):
        key = (tuple(getattr(leaf, "shape", ())), str(getattr(leaf, "dtype", "")))
        return shape_map.get(key, replicated(mesh))

    return jax.tree.map(pick, a_opt)


# Env knob for the auto path's persistent-compile-cache arming:
# unset/1 arms (utils.checkpoint.arm_compile_cache: the directory
# JAX_COMPILATION_CACHE_DIR names, else the one fixed path inside the
# checkout), 0/off disables, any other value is the cache directory.
XLA_CACHE_ENV = "SPARKTORCH_TPU_XLA_CACHE"


def _make_finish(loop_state):
    """The shared ``run.finish()`` for both auto paths (GSPMD and
    pipeline winners): end an in-flight XLA capture, return the
    published :class:`TraceAnalysis` (or None), and upgrade an active
    goodput ledger's comm model to 'measured' from the analysis."""
    from sparktorch_tpu.obs import goodput as _goodput

    def finish():
        profiler, loop_state["profiler"] = loop_state["profiler"], None
        if profiler is not None:
            profiler.__exit__(None, None, None)
        handle, loop_state["handle"] = loop_state["handle"], None
        analysis = handle["analysis"] if handle else None
        ledger = _goodput.active()
        if ledger is not None and analysis is not None:
            ledger.apply_analysis(analysis)
        return analysis

    return finish


def _maybe_arm_xla_cache() -> bool:
    """Arm the jax persistent compilation cache for ``mesh='auto'``
    builds (see :func:`sparktorch_tpu.utils.checkpoint.
    arm_persistent_cache` for the restore-safety rules)."""
    import os

    env = (os.environ.get(XLA_CACHE_ENV) or "").strip()
    if env in ("0", "off", "false"):
        return False
    from sparktorch_tpu.utils.checkpoint import (
        arm_compile_cache,
        arm_persistent_cache,
    )

    if env in ("", "1", "true", "on"):
        return arm_compile_cache()
    return arm_persistent_cache(env)


def _make_auto_pipeline_step(spec, tx, mesh, tune_result, rng,
                             sample_batch: DataBatch,
                             profile_dir: Optional[str] = None,
                             telemetry=None):
    """Build the ``mesh='auto'`` fast path for a PIPELINE winner: the
    tuner picked a pp>1 candidate (``tune_result.best_schedule`` names
    the schedule / virtual_stages / n_micro it measured), so the
    returned ``run`` dispatches through
    :func:`sparktorch_tpu.train.pipeline.make_pp_train_step` — the
    same schedule path the candidate was measured through — with the
    usual auto extras (``run.state`` is the initial
    :class:`~sparktorch_tpu.train.pipeline.PipelineState`,
    ``run.mesh``, ``run.tune_result``, ``run.finish``) plus
    ``run.pipeline_schedule`` (the schedule meta) and
    ``run.eval_loss``. Batches fed to ``run`` must keep rows
    divisible by dp x n_micro (the sample batch the tuner measured
    already is). MoE winners with ep>1 get the a2a grouping opt-in
    threaded through the built step (``pp_moe_group_size``), so the
    production step runs the same dispatch layout the measured
    candidate did."""
    import numpy as np

    from sparktorch_tpu.obs import get_telemetry
    from sparktorch_tpu.obs import goodput as _goodput
    from sparktorch_tpu.train.pipeline import (
        PipelineState,
        build_pp_schedule_step,
    )

    meta = dict(tune_result.best_schedule or {})
    if not meta:
        raise ValueError(
            "pp>1 tune winner carries no schedule meta — re-run the "
            "search (pre-schedule cache entries are fenced by the "
            "cache-key schema bump)"
        )
    rows = int(sample_batch.x.shape[0])
    seq = (int(sample_batch.x.shape[1])
           if np.asarray(sample_batch.x).ndim >= 2 else 1)
    # The ONE shared build recipe (validation, head pick, MoE a2a
    # group opt-in, restack + interleave + placement) — the same path
    # the tuner measured the winner through.
    auto_state, step, _cfg, _head = build_pp_schedule_step(
        spec, mesh, meta, rows, seq, tx=tx, rng=rng,
        sample_x=sample_batch.x[:1],
    )

    from sparktorch_tpu.utils.tracing import profile_run, step_annotation

    tele = telemetry or get_telemetry()
    loop_state = {"calls": 0, "profiler": None, "handle": None}
    est_comm_fraction = None
    ranking = tune_result.ranking()
    if ranking and ranking[0].measured:
        est_comm_fraction = float(
            ranking[0].measured.get("exposed_comm_fraction", 0.0))

    def run(state: PipelineState, batch: DataBatch):
        if profile_dir and loop_state["profiler"] is None:
            loop_state["profiler"] = profile_run(profile_dir,
                                                 telemetry=tele)
            loop_state["handle"] = loop_state["profiler"].__enter__()
        step_no = loop_state["calls"]
        loop_state["calls"] += 1
        ledger = _goodput.active()
        if ledger is None:
            with tele.span("train_sharded/step"), \
                    step_annotation(step_no, telemetry=tele):
                return step(state, batch)
        # Same ledger contract as the GSPMD run: synced step span,
        # re-aimed at ``compile`` when the schedule's jit dispatch
        # cache grew under the call (the winner's fresh-closure
        # recompile lands on the TuneResult's compile bill).
        if est_comm_fraction is not None:
            ledger.set_comm_model(est_comm_fraction, "estimate")
        # Straggler injection before the step span: the skew referee
        # must see a late fence arrival, not a longer step.
        _chaos.straggle(jax.process_index(), step_no)
        cache0 = step.jit_cache_size()
        with tele.span("train_sharded/step"), \
                step_annotation(step_no, telemetry=tele):
            with ledger.step_span(step=step_no) as led:
                out = step(state, batch)
                cache1 = step.jit_cache_size()
                if cache0 is not None and cache1 is not None \
                        and cache1 > cache0:
                    led.rebucket("compile")
                elif cache0 is None and cache1 is not None \
                        and cache1 > 0 and step_no == 0:
                    # First call: the probe reads None before the
                    # lazily-built jitted exists, so a grown cache
                    # after the call IS the compile signal.
                    led.rebucket("compile")
                jax.block_until_ready(out[1])
        if led.bucket == "compile":
            tele.counter("goodput.compiles_total",
                         labels={"site": "train_sharded"})
            tune_result.compile_count += 1
            tune_result.compile_s_total += float(led.duration_s)
        return out

    run.jitted = None              # pipeline jit is lazily built
    run.mesh = mesh
    run.finish = _make_finish(loop_state)
    run.state = auto_state
    run.shardings = None           # pipeline layout lives in the step
    run.tune_result = tune_result
    run.pipeline_schedule = meta
    run.pipeline_step = step
    run.eval_loss = step.eval_loss
    return run


def make_sharded_train_step(
    apply_fn: Callable,
    loss_fn: Callable,
    tx: optax.GradientTransformation,
    mesh,
    state_shardings: Optional[TrainState] = None,
    seq_sharded: bool = False,
    profile_dir: Optional[str] = None,
    telemetry=None,
    spec=None,
    sample_batch: Optional[DataBatch] = None,
    rng: Optional[jax.Array] = None,
    tune_kwargs: Optional[dict] = None,
) -> Callable[[TrainState, DataBatch], Tuple[TrainState, StepMetrics]]:
    """One GSPMD train step: global weighted-mean loss and grads; XLA
    derives every collective from the shardings.

    ``mesh`` is a concrete :class:`jax.sharding.Mesh` — or the string
    ``"auto"``: the trace-guided auto-tuner
    (:func:`sparktorch_tpu.parallel.tune.autotune`) searches the legal
    mesh space for ``spec`` on ``sample_batch`` (both required in auto
    mode; ``tune_kwargs`` forwards search knobs like ``measure_top_k``
    or ``artifact_path``) and the winner becomes the mesh. The auto
    path also initializes the train state INTO the winning layout, so
    the returned ``run`` exposes ``run.state`` (the initial
    :class:`TrainState`), ``run.shardings``, and ``run.tune_result``
    beside the usual ``run.mesh`` — callers start the loop from
    ``run.state`` instead of calling :func:`create_sharded_state`
    themselves (the mesh was not known until now). When the tuner's
    winner has pp>1 the returned ``run`` is a PIPELINE-scheduled step
    instead (same contract; ``run.state`` is a ``PipelineState``,
    ``run.pipeline_schedule`` names the winning schedule — see
    :func:`_make_auto_pipeline_step`). CONTRACT: that pipeline step
    derives its apply/loss from ``spec`` (head-typed cross entropy,
    like every train_distributed pp dispatch), NOT from the
    ``apply_fn``/``loss_fn`` arguments — the search only opens pp
    when ``spec.loss`` is in the cross-entropy family, so callers
    passing a loss_fn that does not match their spec's loss must pin
    ``tune_kwargs={'axes': GSPMD_AXES}`` to stay on the GSPMD path.
    Known cost: the
    winner's GSPMD program compiles once inside the tuner's
    measurement and once more for this fresh step closure (jit cannot
    dedupe across closures) — amortized over a training run; RE-runs
    of the same (workload, rig) skip the whole search via the
    tune-result cache (on by default here; ``tune_kwargs={'cache':
    False}`` or ``SPARKTORCH_TPU_TUNE_CACHE=0`` opts out, and the
    artifact records ``cache_hit``).

    Telemetry/tracing (same contract as the sync/pp trainers'
    ``profile_dir``): every call of the returned ``run`` carries a
    per-step trace annotation and a ``train_sharded/step`` span on the
    bus. With ``profile_dir`` set, the FIRST call starts an XLA
    profiler trace there; the caller owns the loop here (no trainer
    driver), so it ends the capture with ``run.finish()`` — also safe
    to call when no profile was requested. Stopping the capture
    auto-analyzes it (:mod:`sparktorch_tpu.obs.xprof`): per-step
    collective/compute attribution lands on the bus as ``xprof.*``
    metrics, and ``finish()`` returns the :class:`TraceAnalysis`
    (None when nothing was captured).
    """
    tune_result = None
    auto_state: Optional[TrainState] = None
    if isinstance(mesh, str):
        if mesh != "auto":
            raise ValueError(f"mesh must be a Mesh or 'auto', got {mesh!r}")
        if spec is None or sample_batch is None:
            raise ValueError(
                "mesh='auto' needs spec= and sample_batch= (the tuner "
                "measures candidate meshes on a representative batch)"
            )
        from sparktorch_tpu.parallel.mesh import build_mesh
        from sparktorch_tpu.parallel.tune import autotune

        # The tuner, the winning mesh, and the state layout must all
        # see the SAME device set — a tune_kwargs={'devices': ...}
        # subset would otherwise pick a config whose axis product no
        # longer matches jax.devices().
        tune_kwargs = dict(tune_kwargs or {})
        devices = tune_kwargs.pop("devices", None) or jax.devices()
        # Re-runs of the same workload on the same rig load the
        # cached winner instead of re-searching (and re-compiling
        # every candidate) — SPARKTORCH_TPU_TUNE_CACHE=0 opts out,
        # tune_kwargs={'cache': False} opts out per call.
        tune_kwargs.setdefault("cache", True)
        # Recompile tax (ROADMAP 4b): arm the PERSISTENT compile cache
        # for the auto path, so the winner's known second compile (the
        # tuner's measurement closure, then this fresh step closure —
        # jit cannot dedupe across closures) is a disk hit instead of
        # a full XLA compile, and the next process warm-starts the
        # whole search's compiles. SPARKTORCH_TPU_XLA_CACHE=0 opts
        # out; a path value relocates the cache dir. arm_persistent_
        # cache refuses after an orbax restore (the restore <->
        # cache-mediated-collective SIGABRT its disarm hook exists
        # for) and defers to an already-configured cache dir.
        _maybe_arm_xla_cache()
        tune_result = autotune(
            spec, sample_batch, devices, tx=tx, seq_sharded=seq_sharded,
            telemetry=telemetry, **tune_kwargs,
        )
        mesh = build_mesh(tune_result.best_config(), devices)
        if int(tune_result.best.get("pp", 1)) > 1:
            # The winner is a PIPELINE schedule: hand back a
            # pipeline-scheduled step (same run/finish/introspection
            # contract) instead of forcing the mesh through the
            # schedule-less GSPMD trainer.
            return _make_auto_pipeline_step(
                spec, tx, mesh, tune_result,
                rng if rng is not None else jax.random.key(0),
                sample_batch, profile_dir=profile_dir,
                telemetry=telemetry,
            )
        auto_state, state_shardings = create_sharded_state(
            spec, mesh,
            rng if rng is not None else jax.random.key(0),
            sample_x=sample_batch.x[:1], tx=tx,
        )
    if state_shardings is None:
        raise ValueError("state_shardings is required unless mesh='auto'")

    refuse_sync_dp_only(apply_fn, "the GSPMD trainer")
    pass_w = _accepts_example_w(apply_fn)

    def step(state: TrainState, batch: DataBatch):
        def weighted_mean_loss(params):
            variables = {"params": params, **state.model_state}
            # 'losses'/'moe_metrics' are write-only: requested mutable
            # every step so sow() records fresh values, but never
            # carried in the train state (sow APPENDS to carried-in
            # collections, which would grow the pytree every step).
            mutable = [*state.model_state.keys(), "losses", "moe_metrics"]
            kwargs = {"example_w": batch.w} if pass_w else {}
            preds, new_state = apply_fn(variables, batch.x, mutable=mutable,
                                        **kwargs)
            new_state = dict(new_state)
            sown = new_state.pop("losses", None)
            sown_metrics = new_state.pop("moe_metrics", None)
            if not state.model_state:
                new_state = state.model_state
            per = loss_fn(preds, batch.y)
            num = jnp.sum(per * batch.w)
            den = jnp.maximum(jnp.sum(batch.w), 1.0)
            loss = num / den
            # Sown auxiliary objectives (e.g. the MoE load-balance
            # loss, already weighted at the sow site) join the task
            # loss so their gradients flow.
            if sown is not None:
                for leaf in jax.tree.leaves(sown):
                    loss = loss + jnp.sum(leaf).astype(loss.dtype)
            return loss, (den, new_state, _moe_drop_counts(sown_metrics))

        (loss, (den, new_model_state, drops)), grads = jax.value_and_grad(
            weighted_mean_loss, has_aux=True
        )(state.params)
        updates, new_opt = tx.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        new_state = TrainState(
            step=state.step + 1,
            params=new_params,
            model_state=new_model_state,
            opt_state=new_opt,
            rng=state.rng,
        )
        # GSPMD computes over GLOBAL arrays, so the sown counters are
        # already global sums — no extra collective needed.
        gnorm = optax.global_norm(grads)
        grad_leaves = jax.tree.leaves(grads)
        leaf_norms = (
            jnp.stack([jnp.sqrt(jnp.sum(jnp.square(g))).astype(jnp.float32)
                       for g in grad_leaves])
            if grad_leaves else jnp.zeros((0,), jnp.float32)
        )
        metrics = StepMetrics(
            loss=loss, examples=den, grad_norm=gnorm,
            drop_fraction=(drops[0] / jnp.maximum(drops[1], 1.0)
                           if drops is not None else None),
            health=HealthVec(
                finite=(jnp.isfinite(loss)
                        & jnp.isfinite(gnorm)).astype(jnp.float32),
                update_ratio=optax.global_norm(updates)
                / jnp.maximum(optax.global_norm(new_params), 1e-12),
                leaf_norms=leaf_norms,
            ),
        )
        return new_state, metrics

    b_specs = batch_specs(seq_sharded)
    in_shardings = (
        state_shardings,
        DataBatch(*(NamedSharding(mesh, s) for s in b_specs)),
    )
    jitted = jax.jit(
        step,
        in_shardings=in_shardings,
        out_shardings=(state_shardings, None),
        donate_argnums=(0,),
    )

    from sparktorch_tpu.obs import get_telemetry
    from sparktorch_tpu.obs import goodput as _goodput
    from sparktorch_tpu.obs import health as _health
    from sparktorch_tpu.obs import profile as _stackprof
    from sparktorch_tpu.utils.tracing import profile_run, step_annotation

    tele = telemetry or get_telemetry()
    # Stack sampler beside the ambient ledger (see train/sync.py) —
    # the caller owns the loop here, so the step factory is where
    # "wherever ledgers live" lands for the GSPMD path.
    _stackprof.ensure(tele)
    _health.ensure(tele)

    def _feed_health(out) -> None:
        # Everything queues as DEVICE values (including loss/grad_norm
        # — this path never host-syncs them itself); the ledger's
        # K-late drain does the one attributed readback.
        hl = _health.active()
        if hl is None:
            return
        m = out[1]
        dev = {"loss": m.loss, "grad_norm": m.grad_norm}
        if m.health is not None:
            dev.update(finite=m.health.finite,
                       update_ratio=m.health.update_ratio,
                       leaf_norms=m.health.leaf_norms)
        hl.note_step(device=dev)
    loop_state = {"calls": 0, "profiler": None, "handle": None}
    # The comm model the goodput ledger starts under: the tuner's
    # measured exposed fraction for the winning mesh when the auto
    # path ran (a labeled ESTIMATE here — it was measured in the
    # search's capture, not this run's), upgraded to "measured" when
    # finish() analyzes a capture of THIS run.
    est_comm_fraction = None
    if tune_result is not None:
        ranking = tune_result.ranking()
        if ranking and ranking[0].measured:
            est_comm_fraction = float(
                ranking[0].measured.get("exposed_comm_fraction", 0.0))

    def run(state, batch):
        if profile_dir and loop_state["profiler"] is None:
            loop_state["profiler"] = profile_run(profile_dir, telemetry=tele)
            loop_state["handle"] = loop_state["profiler"].__enter__()
        step_no = loop_state["calls"]
        loop_state["calls"] += 1
        ledger = _goodput.active()
        if ledger is None:
            with _set_mesh(mesh), tele.span("train_sharded/step"), \
                    step_annotation(step_no, telemetry=tele):
                out = jitted(state, batch)
            _feed_health(out)
            return out
        # Ledger-armed path: the call is timed as a step span, synced
        # (async dispatch without a sync measures enqueue, not compute
        # — the ROUND4 honest-timing lesson), and re-bucketed to
        # ``compile`` when the jit dispatch cache GREW under it (the
        # first call, a new input shape, or the auto path's known
        # winner recompile).
        if est_comm_fraction is not None:
            ledger.set_comm_model(est_comm_fraction, "estimate")
        # Straggler injection before the step span (late fence
        # arrival, attributable by the skew referee).
        _chaos.straggle(jax.process_index(), step_no)
        cache0 = _goodput.jit_cache_size(jitted)
        with _set_mesh(mesh), tele.span("train_sharded/step"), \
                step_annotation(step_no, telemetry=tele):
            with ledger.step_span(step=step_no) as led:
                out = jitted(state, batch)
                cache1 = _goodput.jit_cache_size(jitted)
                if cache0 is not None and cache1 is not None \
                        and cache1 > cache0:
                    led.rebucket("compile")
                jax.block_until_ready(out[1].loss)
        if led.bucket == "compile":
            tele.counter("goodput.compiles_total",
                         labels={"site": "train_sharded"})
            if tune_result is not None:
                # The auto path's documented "compiles its winner
                # twice" cost, finally a number: the fresh step
                # closure's recompile lands on the SAME TuneResult the
                # artifact was stamped from.
                tune_result.compile_count += 1
                tune_result.compile_s_total += float(led.duration_s)
        _feed_health(out)
        return out

    # Introspection hooks (tests assert on the compiled HLO — e.g. that
    # the MoE layout constraints actually lower to all-to-alls).
    run.jitted = jitted
    run.mesh = mesh
    run.finish = _make_finish(loop_state)
    # Auto-tune extras (None unless mesh="auto"): the initial state in
    # the winning layout, its shardings, and the search record.
    run.state = auto_state
    run.shardings = state_shardings
    run.tune_result = tune_result
    return run


def shard_batch(batch: DataBatch, mesh: Mesh, seq_sharded: bool = False) -> DataBatch:
    specs = batch_specs(seq_sharded)
    return DataBatch(
        *(jax.device_put(a, NamedSharding(mesh, s)) for a, s in zip(batch, specs))
    )

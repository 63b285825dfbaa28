"""Asynchronous (hogwild) training against the parameter server.

Reference: ``sparktorch/hogwild.py`` — HTTP client helpers with one
retry (:31-62), a per-partition worker loop that pulls the full
state_dict, does forward/backward, pushes raw grads and polls early
stop (:65-142), and a driver ``train()`` that runs partition-shuffle
rounds and pulls final weights (:145-186).

TPU-native redesign:

- Workers are device-pinned: each worker owns a chip, holds its data
  shard in that chip's HBM, and runs one jitted gradient step per
  iteration. Pulls are version-tagged (no redundant transfers), and
  the push is the local weighted-mean gradient pytree.
- The reference's missing ``zero_grad`` (grads accumulate across
  iterations, ``hogwild.py:96-140`` — SURVEY flags it as a real
  behavioral quirk) is deliberately NOT reproduced: each push is the
  gradient of the current minibatch only.
- Transports: ``local`` (in-process, device-to-device) or ``http``.
  The HTTP wire defaults to the framed zero-copy binary protocol
  (:mod:`sparktorch_tpu.net`): persistent keep-alive connections,
  ``np.frombuffer`` decode, 304 not-modified pulls, quantized pushes
  with error feedback. ``wire='dill'`` falls back to the reference's
  wire shape (dill blobs, stdlib client with one retry + timeout like
  ``hogwild.py:34-38``) for parity runs and mixed-version gangs.
"""

from __future__ import annotations

import threading
import time
import urllib.error
import urllib.request
from typing import Any, List, Optional

import dill
import jax
import jax.numpy as jnp
import numpy as np

from sparktorch_tpu.ft import chaos as _chaos
from sparktorch_tpu.obs import goodput as _goodput
from sparktorch_tpu.obs import health as _health
from sparktorch_tpu.net.transport import BinaryTransport
from sparktorch_tpu.obs import get_logger, get_telemetry
from sparktorch_tpu.serve.param_server import ParameterServer, ParamServerHttp
from sparktorch_tpu.train.step import _sown_total
from sparktorch_tpu.train.sync import TrainResult, _as_batch
from sparktorch_tpu.utils.data import DataBatch
from sparktorch_tpu.utils.serde import deserialize_model
from sparktorch_tpu.utils.tracing import profile_run, step_annotation

_HTTP_TIMEOUT = 10.0  # hogwild.py:34-38 parity (10s timeout, 1 retry)
# Pulls carry the full model snapshot, and the server's first host
# materialization of a new version is a full device download — so
# the pull deadline is its own, generous one (the push/poll paths
# keep reference parity).
_HTTP_PULL_TIMEOUT = 180.0


# ---------------------------------------------------------------------------
# Transports
# ---------------------------------------------------------------------------


def _new_phase_stats() -> dict:
    """Per-transport phase accounting (seconds, bytes, counts) — the
    raw material for the hogwild budget the run summary carries: where a
    worker's wall time actually goes (pull wire, push materialize+wire,
    stop-poll), so ``async_efficiency`` decomposes instead of being one
    unexplained ratio."""
    return {
        "pull_s": 0.0, "pull_bytes": 0, "pulls": 0, "pull_fresh": 0,
        "push_wire_s": 0.0, "push_materialize_s": 0.0,
        "push_bytes": 0, "pushes": 0,
        "poll_s": 0.0,
    }


class LocalTransport:
    """Direct in-process access to the server object."""

    def __init__(self, server: ParameterServer):
        self.server = server
        self.stats = _new_phase_stats()

    def pull(self, have_version: int):
        t0 = time.perf_counter()  # lint-obs: ok (phase stats; the worker loop feeds the ledger from these)
        snap = self.server.get_parameters(have_version)
        st = self.stats
        st["pull_s"] += time.perf_counter() - t0  # lint-obs: ok (phase stats pair)
        st["pulls"] += 1
        st["pull_fresh"] += snap is not None
        return snap

    def push(self, grads) -> None:
        t0 = time.perf_counter()  # lint-obs: ok (phase stats pair)
        self.server.push_gradients(grads)
        self.stats["push_wire_s"] += time.perf_counter() - t0  # lint-obs: ok (phase stats pair)
        self.stats["pushes"] += 1

    def post_loss(self, loss: float) -> bool:
        t0 = time.perf_counter()  # lint-obs: ok (phase stats pair)
        out = self.server.post_loss(loss)
        self.stats["poll_s"] += time.perf_counter() - t0  # lint-obs: ok (phase stats pair)
        return out

    def alive(self) -> bool:
        return True


class HttpTransport:
    """The reference's wire (hogwild.py:31-62): dill over HTTP with
    one retry and a 10s timeout per call.

    Unlike the reference — which ships full-precision state both ways
    every iteration (its 2x-model-per-iter pathology) — pushes are
    bf16-compressed by default: gradients tolerate the 8-bit mantissa
    (it is the TPU's native matmul dtype) and the wire bytes halve.
    The server casts back up to the param dtype before the optimizer
    update, so moments stay full precision."""

    def __init__(self, url: str, compress: bool = True):
        self.url = url.rstrip("/")
        self.compress = compress
        self.stats = _new_phase_stats()

    def _request(self, req, timeout: float = _HTTP_TIMEOUT,
                 retry_on_timeout: bool = False):
        """One retry, reference parity. Timeouts retry only when the
        caller says the request is IDEMPOTENT (the pull GET): a timed-
        out POST may still complete server-side, and re-sending it
        would double-apply a gradient or double-count a loss."""
        retriable = (urllib.error.URLError, ConnectionError)
        if retry_on_timeout:
            retriable = retriable + (TimeoutError,)
        try:
            return urllib.request.urlopen(  # lint-obs: ok (dill data wire)
                req, timeout=timeout)
        except retriable:
            return urllib.request.urlopen(  # lint-obs: ok (dill data wire)
                req, timeout=timeout)  # retry once

    def pull(self, have_version: int):
        st = self.stats
        t0 = time.perf_counter()  # lint-obs: ok (phase stats pair)
        req = urllib.request.Request(
            self.url + "/parameters", headers={"X-Have-Version": str(have_version)}
        )
        with self._request(req, timeout=_HTTP_PULL_TIMEOUT,
                           retry_on_timeout=True) as resp:
            if resp.status == 204:
                st["pull_s"] += time.perf_counter() - t0  # lint-obs: ok (phase stats pair)
                st["pulls"] += 1
                return None
            body = resp.read()
        st["pull_s"] += time.perf_counter() - t0  # lint-obs: ok (phase stats pair)
        st["pulls"] += 1
        st["pull_fresh"] += 1
        st["pull_bytes"] += len(body)
        return dill.loads(body)

    def push(self, grads) -> None:
        st = self.stats
        # Materialize separately from the wire: np.asarray FENCES the
        # device (the gradient compute drains here), so this term is
        # the honest compute+download+serialize time and the urlopen
        # below is the pure wire+server-apply time.
        t0 = time.perf_counter()  # lint-obs: ok (phase stats pair)
        if self.compress:
            host_grads = jax.tree.map(
                lambda a: np.asarray(
                    a.astype(jnp.bfloat16)
                    if jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating)
                    else a
                ),
                grads,
            )
        else:
            host_grads = jax.tree.map(lambda a: np.asarray(a), grads)
        # Serialization counts as materialize, not wire — the same
        # bucketing as BinaryTransport (which encodes before ITS t1),
        # so the two transports' phase stats compare like with like.
        payload = dill.dumps(host_grads)
        t1 = time.perf_counter()  # lint-obs: ok (phase stats pair)
        st["push_materialize_s"] += t1 - t0
        req = urllib.request.Request(
            self.url + "/update", data=payload, method="POST"
        )
        with self._request(req) as resp:
            if resp.status != 200:
                raise RuntimeError(f"/update failed: {resp.status}")
        st["push_wire_s"] += time.perf_counter() - t1  # lint-obs: ok (phase stats pair)
        st["push_bytes"] += len(payload)
        st["pushes"] += 1

    def post_loss(self, loss: float) -> bool:
        t0 = time.perf_counter()  # lint-obs: ok (phase stats pair)
        req = urllib.request.Request(
            self.url + "/losses", data=dill.dumps(float(loss)), method="POST"
        )
        with self._request(req) as resp:
            out = bool(dill.loads(resp.read())["stop"])
        self.stats["poll_s"] += time.perf_counter() - t0  # lint-obs: ok (phase stats pair)
        return out

    def alive(self) -> bool:
        # GET / liveness probe (hogwild.py:60-62).
        req = urllib.request.Request(self.url + "/")
        with self._request(req) as resp:
            return resp.status == 200


# ---------------------------------------------------------------------------
# Worker
# ---------------------------------------------------------------------------


def make_grad_step(apply_fn, loss_fn, mini_batch: Optional[int] = None):
    """Jitted local gradient step: weighted-mean grads + loss of one
    minibatch — the worker half of ``hogwild.handle_model``'s hot loop
    (hogwild.py:96-130), with zero_grad semantics done right.

    With ``mini_batch`` set, the minibatch is sampled ON-DEVICE inside
    the compiled step (random-offset contiguous block — see
    ``utils.data.sample_minibatch`` for why gathers are wrong here):
    the whole iteration is ONE dispatch, vs host-side fancy-indexing
    which costs three device round-trips per iteration before the
    gradient even starts — the dominant cost on anything but a local
    chip."""

    @jax.jit
    def grad_step(params, model_state, shard: DataBatch, key):
        if mini_batch and 0 < mini_batch < shard.x.shape[0]:
            from sparktorch_tpu.utils.data import sample_minibatch

            batch = sample_minibatch(shard, key, mini_batch)
        else:
            batch = shard

        def weighted(params):
            from sparktorch_tpu.train.step import _accepts_example_w

            variables = {"params": params, **(model_state or {})}
            kwargs = (
                {"example_w": batch.w} if _accepts_example_w(apply_fn) else {}
            )
            # Request the write-only 'losses' collection so sown aux
            # objectives (MoE load-balance) train here too — the async
            # router must optimize the same objective as the sync one.
            preds, sown_state = apply_fn(variables, batch.x,
                                         mutable=["losses", "moe_metrics"],
                                         **kwargs)
            per = loss_fn(preds, batch.y)
            num = jnp.sum(per * batch.w)
            den = jnp.maximum(jnp.sum(batch.w), 1.0)
            sown = dict(sown_state).get("losses", None)
            return num / den + _sown_total(sown, per.dtype)

        loss, grads = jax.value_and_grad(weighted)(params)
        return grads, loss

    return grad_step


def make_grad_window(apply_fn, loss_fn, mini_batch: Optional[int], k: int):
    """``k`` minibatch gradient steps fused into ONE compiled call
    (``lax.scan``): returns the mean gradient over the window and the
    k per-step losses. This is the ``push_every`` hot path — a whole
    accumulation window costs a single dispatch, zero per-step Python.
    All k steps see the params the worker last pulled (the window is
    the staleness unit; that's the documented push_every tradeoff)."""

    grad_step = make_grad_step(apply_fn, loss_fn, mini_batch)

    @jax.jit
    def grad_window(params, model_state, shard: DataBatch, key):
        def body(acc, subkey):
            grads, loss = grad_step(params, model_state, shard, subkey)
            acc = jax.tree.map(jnp.add, acc, grads)
            return acc, loss

        zero = jax.tree.map(jnp.zeros_like, params)
        acc, losses = jax.lax.scan(body, zero, jax.random.split(key, k))
        return jax.tree.map(lambda g: g / k, acc), losses

    return grad_window


def make_grad_windows(apply_fn, loss_fn, mini_batch: Optional[int],
                      push_every: int, iters: int):
    """Build the ``(full_window, tail_window)`` pair ``_worker_loop``
    expects for ``push_every=k``: the full k-step window plus a
    remainder window when ``iters % k != 0`` (the full window is reused
    when the division is exact). One source of truth for the tail-
    window contract, shared by ``train_async`` and the Spark executor
    deployment. Returns None when ``push_every <= 1``."""
    if not push_every or push_every <= 1:
        return None
    rem = iters % push_every
    window = make_grad_window(apply_fn, loss_fn, mini_batch, push_every)
    return (
        window,
        make_grad_window(apply_fn, loss_fn, mini_batch, rem) if rem else window,
    )


def make_eval_loss(apply_fn, loss_fn):
    """Jitted full-shard weighted loss (no grads) — the validation
    probe for early stopping."""

    @jax.jit
    def eval_loss(params, model_state, batch: DataBatch):
        variables = {"params": params, **(model_state or {})}
        preds = apply_fn(variables, batch.x)
        per = loss_fn(preds, batch.y)
        return jnp.sum(per * batch.w) / jnp.maximum(jnp.sum(batch.w), 1.0)

    return eval_loss


def _worker_loop(
    worker_id: int,
    device: jax.Device,
    transport,
    grad_step,
    model_state,
    shard: DataBatch,
    val_shard: Optional[DataBatch],
    iters: int,
    verbose: int,
    early_stop: bool,
    seed: int,
    records: List[dict],
    errors: List[BaseException],
    push_every: int = 1,
    eval_loss=None,
    grad_windows=None,
    phase_out: Optional[List[dict]] = None,
    telemetry=None,
    cancel=None,
):
    """One worker's training loop.

    ``push_every<=1``: pull → one jitted grad step (minibatch sampled
    on-device) → push, per iteration. ``push_every=k`` with
    ``grad_windows=(window_k, window_rem)``: a whole k-step
    accumulation window runs as ONE compiled call and pushes its mean
    gradient — k-fold fewer pulls/pushes/dispatches; the window is the
    staleness unit. Losses stay on-device until the loop ends (or
    verbose/early-stop demands a value NOW): a ``float()`` per
    iteration serializes the pipeline on a host round-trip that costs
    more than the gradient step itself on remote-attached chips.

    ``cancel`` (a ``threading.Event``, wired by the supervised path)
    is polled BETWEEN windows: a supervisor ``kill()`` — straggler or
    stall preemption — stops the worker at the next window boundary
    with :class:`WorkerPreempted` instead of being silently ignored
    (threads cannot be preempted mid-dispatch; the window is the
    preemption unit, like it is the staleness unit). A preempted
    attempt flushes no records, so the restarted attempt's rerun
    keeps counts exact.
    """
    tele = telemetry or get_telemetry()
    log = get_logger("sparktorch_tpu.train.hogwild")
    labels = {"worker": worker_id}
    # Per-WORKER health ledger on the shared bus: each worker's loss
    # series and anomalies stay tagged with its own rank ("w<id>") in
    # the composite health section — a NaN on one worker must surface
    # as that worker's NaN, never fleet-averaged. Device losses are
    # queued un-synced; the K-late drain materializes windows whose
    # compute long finished, preserving the async dispatch pipeline.
    hl = (_health.TrainHealthLedger(rank=f"w{worker_id}", telemetry=tele)
          if _health.enabled() else None)
    try:
        if hasattr(transport, "stats"):
            # Fresh per-round stats: the transport object survives
            # shuffle rounds, the budget must not double-count.
            transport.stats = _new_phase_stats()
        shard = jax.device_put(shard, device)
        # Non-trainable collections (batch_stats) come off the server's
        # device; a worker pinned elsewhere must hold its own copy or
        # the jitted window sees two devices.
        model_state = jax.device_put(model_state, device)
        key = jax.device_put(jax.random.key(seed + worker_id), device)
        have_version = -1
        params = None
        pending: List[Any] = []
        window_k = push_every if push_every and push_every > 1 else 1
        it = 0
        t_place = 0.0   # host->device upload of pulled params
        t_dispatch = 0.0  # grad window dispatch (async; drain lands
        # in the push's materialize fence)
        t_loop0 = time.perf_counter()  # lint-obs: ok (loop-wall clock for the phase budget)
        while it < iters:
            if cancel is not None and cancel.is_set():
                from sparktorch_tpu.ft.supervisor import WorkerPreempted

                raise WorkerPreempted(
                    f"worker {worker_id} preempted at iter {it}"
                )
            # Chaos injection point: a seeded config can kill THIS
            # worker at step N (ChaosKill lands in `errors` like any
            # real failure; under supervision it triggers a restart).
            _chaos.fire("worker.step", worker=worker_id, step=it)
            _act = _chaos.fire("data.batch", worker=worker_id, step=it)
            if _act and _act.get("poison"):
                shard = _chaos.poison_batch(shard)
            # Straggler injection before the pull (this loop's wire
            # fence) and the step span: the skew referee sees a late
            # arrival on this worker.
            _chaos.straggle(worker_id, it)
            # Wire waits are EXPOSED comm by definition (nothing
            # overlaps them in this loop); the pulled params' host->
            # device upload is a data wait. Both ride LedgerSpans so
            # the goodput ledger and the phase budget read one clock.
            with _goodput.span("exposed_comm",
                               {"site": "hogwild_pull"}):
                snap = transport.pull(have_version)
            if snap is not None:
                have_version, params = snap
                with _goodput.span("data_wait",
                                   {"site": "hogwild_place"}) as _pl:
                    params = jax.device_put(params, device)
                t_place += _pl.duration_s

            key, sub = jax.random.split(key)
            k = min(window_k, iters - it)
            # The window dispatch is ASYNC by design (the device
            # compute drains at the push's materialize fence and the
            # end-of-loop drain): the step span here counts steps and
            # catches the dispatch wall; the real device seconds land
            # in compute via the materialize/drain attributions below.
            with _goodput.step_span(step=it) as _led:
                with step_annotation(it, telemetry=tele):
                    if window_k > 1 and grad_windows is not None:
                        fn = (grad_windows[0] if k == window_k
                              else grad_windows[1])
                        grads, losses = fn(params, model_state, shard, sub)
                    else:
                        k = 1
                        grads, losses = grad_step(params, model_state,
                                                  shard, sub)
                _led.count = k
            t_dispatch += _led.duration_s
            _pre = (dict(getattr(transport, "stats", None) or {})
                    if _goodput.active() is not None else None)
            transport.push(grads)
            _post = (getattr(transport, "stats", None)
                     if _pre is not None else None)
            if _post is not None:
                # Split the push by the transport's own phase stats:
                # the materialize half FENCES the device (that is the
                # window's gradient compute draining — productive),
                # the wire half is exposed comm.
                _goodput.add("compute",
                             _post["push_materialize_s"]
                             - (_pre or {}).get("push_materialize_s", 0.0))
                _goodput.add("exposed_comm",
                             _post["push_wire_s"]
                             - (_pre or {}).get("push_wire_s", 0.0))
            tele.counter("hogwild.iters", k, labels=labels)
            tele.counter("hogwild.pushes", labels=labels)
            tele.gauge("hogwild.pulled_version", have_version, labels=labels)
            pending.append((it, k, have_version, losses, time.perf_counter()))  # lint-obs: ok (throughput timestamp)
            if hl is not None:
                hl.note_step(step=it, count=k, device={"loss": losses})
            it += k
            if verbose:
                last = jnp.reshape(jnp.asarray(losses), (-1,))[-1]
                log.info(f"[sparktorch_tpu:hogwild] worker {worker_id} "
                         f"iter {it - 1} loss {float(last):.6f} v{have_version}")
            if early_stop:
                if eval_loss is not None and val_shard is not None:
                    signal = float(eval_loss(params, model_state, val_shard))
                else:
                    signal = float(
                        jnp.reshape(jnp.asarray(losses), (-1,))[-1]
                    )
                if transport.post_loss(signal):
                    break
        t_drain0 = time.perf_counter()  # lint-obs: ok (phase stats pair, ledger-fed below)
        done = []
        for start, k, version, losses, ts in pending:
            vals = np.asarray(losses).reshape(-1)
            for j in range(k):
                done.append(
                    {"worker": worker_id, "iter": start + j,
                     "loss": float(vals[j]), "version": version, "t": ts}
                )
        if done:
            # Wall time at which this worker's last loss actually
            # materialized (a device sync, unlike the per-window
            # dispatch timestamps) — the honest end of the window for
            # throughput math.
            done[-1]["t_done"] = time.perf_counter()  # lint-obs: ok (throughput timestamp)
        records.extend(done)
        # The drain is where the async windows' device compute lands.
        _goodput.add("compute", time.perf_counter() - t_drain0)  # lint-obs: ok (phase stats pair, feeds the ledger)
        if hl is not None:
            hl.flush()
        if phase_out is not None:
            st = dict(getattr(transport, "stats", {}) or {})
            st.update({
                "worker": worker_id,
                "pull_place_s": t_place,
                "dispatch_s": t_dispatch,
                # The post-loop loss materialization: where the async
                # window dispatches' device compute + link latency
                # actually drains (dominant with the local transport —
                # this IS the per-window-dispatch design cost).
                "drain_s": time.perf_counter() - t_drain0,  # lint-obs: ok (phase stats pair)
                "loop_s": time.perf_counter() - t_loop0,  # lint-obs: ok (phase stats pair)
                "iters": it,
            })
            phase_out.append(st)
            # Mirror the per-round phase budget onto the bus so the
            # same decomposition shows up in /metrics and JSONL dumps
            # alongside the counters bumped in the loop above.
            for phase in ("pull_s", "pull_place_s", "dispatch_s",
                          "push_materialize_s", "push_wire_s", "poll_s",
                          "drain_s", "loop_s"):
                if st.get(phase):
                    tele.observe(f"hogwild.{phase}", float(st[phase]),
                                 labels=labels)
    except BaseException as e:  # surfaced to the driver
        errors.append(e)


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------


def train_async(
    torch_obj,
    data: Any,
    labels: Optional[np.ndarray] = None,
    mesh=None,  # accepted for API symmetry; workers pin devices directly
    iters: int = 10,
    partition_shuffles: int = 1,
    verbose: int = 0,
    mini_batch: Optional[int] = None,
    validation_pct: float = 0.0,
    early_stop_patience: int = -1,
    acquire_lock: bool = True,
    port: int = 0,
    partitions: int = -1,
    seed: int = 0,
    transport: str = "local",
    push_every: int = 1,
    compress: bool = True,
    wire: str = "binary",
    quant: Optional[str] = None,
    shards: int = 1,
    pull_quant: Optional[str] = None,
    telemetry=None,
    profile_dir: Optional[str] = None,
    supervise: bool = False,
    ft_policy=None,
) -> TrainResult:
    """Asynchronous parameter-server training.

    The driver-side analog of ``hogwild.train`` (hogwild.py:145-186):
    start the server, run shuffle rounds of per-partition worker
    loops, pull final weights, stop the server (also on error,
    hogwild.py:184-186).

    ``push_every=k`` fuses k minibatch steps into one compiled window
    per push; pulls and the early-stop poll then happen once per
    window, so ``early_stop_patience`` counts k-iteration windows and
    staleness is bounded by one window.

    ``wire`` selects the HTTP wire format: ``'binary'`` (default —
    the framed zero-copy protocol with keep-alive connections and 304
    not-modified pulls) or ``'dill'`` (the reference's pickle wire,
    kept for parity and mixed-version gangs). ``quant='int8'``
    upgrades binary pushes from bf16 to int8 with error-feedback
    residuals; ``compress=False`` ships full-precision pushes on
    either wire.

    ``shards=N`` (with ``transport='http'``) replaces the single
    parameter server with an N-shard fleet
    (:class:`~sparktorch_tpu.serve.fleet.ParamServerFleet`): the
    tensor tree consistent-hashed across N shard servers, workers on
    :class:`~sparktorch_tpu.net.sharded.ShardedTransport` fanning
    per-tensor DELTA pulls and scattered pushes across them.
    ``pull_quant='int8'`` additionally serves int8 pulls with
    server-side error feedback. ``wire='dill'`` with ``shards=N``
    keeps legacy workers working through the fleet's gateway (the
    mixed-version-gang story).

    ``supervise=True`` (or any ``ft_policy``) runs the workers under
    the fault-tolerance supervisor (:mod:`sparktorch_tpu.ft`): a dead
    worker is restarted with exponential backoff + jitter under the
    policy's per-worker budget, and REJOINS by pulling the current
    server version on its first pull — gradients the dead attempt
    already pushed stay applied (hogwild semantics). The restart unit
    is the worker's round assignment (a killed attempt flushes no
    records, so the restarted attempt reruns the round's iterations).
    Recovery is observable as ``ft_restarts_total`` /
    ``ft_recovery_latency_s`` on the run's telemetry bus — the same
    bus ``/metrics`` scrapes.
    """
    tele = telemetry or get_telemetry()
    # Stack sampler beside the ambient ledger: the async trainer's N
    # worker lanes all sample into the same per-process tries, each
    # tagged by the bucket open on ITS thread.
    from sparktorch_tpu.obs import profile as _profile

    _profile.ensure(tele)
    if ft_policy is not None:
        supervise = True
    spec = deserialize_model(torch_obj)
    with tele.span("hogwild/data_prep"):
        train_batch, val_batch = _as_batch(data, labels, validation_pct, seed)
    if spec.input_shape is None:
        spec.input_shape = tuple(np.asarray(train_batch.x).shape[1:])

    devices = jax.devices()
    n_workers = partitions if partitions and partitions > 0 else len(devices)

    if shards and shards > 1 and transport != "http":
        raise ValueError("shards>1 requires transport='http' (the fleet "
                         "is an HTTP tier; local workers need no fleet)")
    # The server records into the SAME run-scoped bus as the workers,
    # so one /metrics scrape (or JSONL dump) tells the whole async
    # story: pulls/pushes/applies next to worker iters and phase times.
    def _restart_counter_total() -> float:
        return sum(
            v for k, v in tele.snapshot().get("counters", {}).items()
            if k.startswith("fleet.shard_restarts_total")
        )

    fleet = None
    restarts_baseline = 0.0
    if shards and shards > 1:
        from sparktorch_tpu.serve.fleet import ParamServerFleet

        # Counters on a shared bus are monotonic across runs; snapshot
        # the baseline so this run's summary reports ITS restarts, not
        # every prior run's on the same process-global bus.
        restarts_baseline = _restart_counter_total()
        server = fleet = ParamServerFleet(
            spec, n_shards=shards,
            window_len=n_workers,  # torch_distributed.py:315-322 parity
            early_stop_patience=early_stop_patience,
            seed=seed, telemetry=tele,
        )
    else:
        server = ParameterServer(
            spec,
            window_len=n_workers,  # torch_distributed.py:315-322 parity
            early_stop_patience=early_stop_patience,
            acquire_lock=acquire_lock,
            seed=seed,
            telemetry=tele,
        )
    http: Optional[ParamServerHttp] = None
    profiler = None
    worker_transports: List[Any] = []
    try:
        if transport == "http" and fleet is not None:
            fleet.start(port=port)
            grace_s = float(getattr(ft_policy, "rejoin_grace_s", 30.0)
                            or 30.0)
            if wire == "dill":
                # Legacy workers keep training through the fleet's
                # gateway — the mixed-version-gang contract.
                worker_transports = [
                    HttpTransport(fleet.gateway_url, compress=compress)
                    for _ in range(n_workers)
                ]
            elif wire == "binary":
                from sparktorch_tpu.net.sharded import ShardedTransport

                push_quant = quant if quant else ("bf16" if compress
                                                  else None)
                worker_transports = [
                    ShardedTransport(fleet, quant=push_quant,
                                     pull_quant=pull_quant,
                                     grace_s=grace_s,
                                     telemetry=tele, run_id=tele.run_id)
                    for _ in range(n_workers)
                ]
            else:
                raise ValueError(
                    f"unknown wire {wire!r}; use 'binary' or 'dill'"
                )
            assert worker_transports[0].alive()  # liveness gate
        elif transport == "http":
            http = ParamServerHttp(server, port=port).start()
            if wire == "dill":
                worker_transports = [
                    HttpTransport(http.url, compress=compress)
                    for _ in range(n_workers)
                ]
            elif wire == "binary":
                push_quant = quant if quant else ("bf16" if compress
                                                  else None)
                worker_transports = [
                    # run_id from the shared run bus: pushes and pulls
                    # carry the run's 16-bit tag in the frame header,
                    # so cross-run traffic (a worker aimed at another
                    # run's recycled port) is counted, never silent.
                    BinaryTransport(http.url, quant=push_quant,
                                    telemetry=tele, run_id=tele.run_id)
                    for _ in range(n_workers)
                ]
            else:
                raise ValueError(
                    f"unknown wire {wire!r}; use 'binary' or 'dill'"
                )
            assert worker_transports[0].alive()  # liveness gate
            # (torch_distributed.py:326 parity)
        else:
            worker_transports = [LocalTransport(server) for _ in range(n_workers)]

        module = spec.make_module()
        grad_step = make_grad_step(module.apply, spec.loss_fn(),
                                   mini_batch=mini_batch)
        grad_windows = make_grad_windows(module.apply, spec.loss_fn(),
                                         mini_batch, push_every, iters)
        eval_loss = (
            make_eval_loss(module.apply, spec.loss_fn())
            if val_batch is not None else None
        )
        model_state = server.model_state()

        records: List[dict] = []
        errors: List[BaseException] = []
        phase_stats: List[dict] = []
        ft_summaries: List[dict] = []
        # N concurrent worker threads each attribute into the ambient
        # goodput ledger (when the caller armed one): each thread is a
        # real execution LANE, so the ledger's MECE budget must be
        # lanes x clock wall — otherwise N threads' attributions read
        # as over-attribution with goodput > 1.
        _ambient = _goodput.active()
        if _ambient is not None:
            _ambient.lanes = max(_ambient.lanes, n_workers)
        x = np.asarray(train_batch.x)
        y = np.asarray(train_batch.y)
        w = np.asarray(train_batch.w)
        shuffle_rng = np.random.default_rng(seed + 1)

        # XLA trace capture around the worker rounds (the same
        # profile_dir contract as the sync/pp trainers); exited in the
        # outer finally so a worker failure still stops the trace.
        profiler = profile_run(profile_dir, telemetry=tele)
        profiler.__enter__()
        for round_idx in range(max(1, partition_shuffles)):
            # EVERY round shuffles, round 0 included: the reference's
            # _fit always repartition()s before training
            # (torch_distributed.py:288-289), redistributing rows
            # across partitions — without that, a label-sorted input
            # becomes single-class workers and async training can
            # collapse to whichever class pushed last (observed as
            # chance accuracy, race-dependent). Minibatch block
            # sampling needs the random resident order anyway.
            perm = shuffle_rng.permutation(x.shape[0])
            x, y, w = x[perm], y[perm], w[perm]  # hogwild.py:161-177
            xs = np.array_split(x, n_workers)
            ys = np.array_split(y, n_workers)
            ws = np.array_split(w, n_workers)
            t_round0 = time.perf_counter()  # lint-obs: ok (round-wall clock)
            worker_args = []
            for i in range(n_workers):
                shard = DataBatch(
                    jnp.asarray(xs[i]), jnp.asarray(ys[i]), jnp.asarray(ws[i])
                )
                worker_args.append((
                    i,
                    devices[i % len(devices)],
                    worker_transports[i],
                    grad_step,
                    model_state,
                    shard,
                    jax.device_put(val_batch, devices[i % len(devices)])
                    if val_batch is not None
                    else None,
                    iters,
                    verbose,
                    early_stop_patience is not None and early_stop_patience > 0,
                    seed + round_idx * n_workers,
                    records,
                ))
            if supervise:
                # The fault-tolerant path: each worker is a supervised
                # task. A dead worker (chaos kill, transport failure,
                # anything the loop surfaces) restarts under the
                # policy's backoff+budget and rejoins by pulling the
                # current server version — a killed attempt flushed no
                # records, so the restarted attempt reruns the round's
                # assignment and the record count stays exact.
                from sparktorch_tpu.ft.supervisor import (
                    Supervisor,
                    ThreadWorker,
                )

                sup = Supervisor(policy=ft_policy, telemetry=tele,
                                 name=f"hogwild_round{round_idx}")

                def make_start(args):
                    def target(cancel):
                        # A fresh error list per attempt: the loop
                        # traps its failure there; re-raising hands it
                        # to the supervisor's handle as THE failure.
                        # `cancel` is the handle's kill() event — the
                        # loop polls it between windows, so straggler
                        # and stall preemption genuinely stop a
                        # thread-based worker.
                        attempt_errors: List[BaseException] = []
                        _worker_loop(*args, attempt_errors, push_every,
                                     eval_loss, grad_windows,
                                     phase_stats, tele, cancel)
                        if attempt_errors:
                            raise attempt_errors[0]

                    return lambda attempt: ThreadWorker(
                        f"w{args[0]}", target, pass_cancel=True
                    )

                for args in worker_args:
                    sup.add(str(args[0]), make_start(args), rank=args[0])
                ft_summaries.append(sup.run())
            else:
                threads = []
                for args in worker_args:
                    t = threading.Thread(
                        target=_worker_loop,
                        args=(*args, errors, push_every, eval_loss,
                              grad_windows, phase_stats, tele),
                        daemon=True,
                    )
                    threads.append(t)
                    t.start()
                for t in threads:
                    t.join()
            tele.observe("hogwild.round_s", time.perf_counter() - t_round0)  # lint-obs: ok (round-wall pair)
            tele.counter("hogwild.rounds")
            if errors:
                raise RuntimeError("hogwild worker failed") from errors[0]
            if server.should_stop:
                break

        params, model_state = server.final_state()
        # The worker pool is joined; there is no dispatch pipeline
        # left to stall.
        # lint-obs: ok (end-of-run gather)
        params = jax.device_get(params)
        model_state = jax.device_get(model_state)  # lint-obs: ok (end-of-run)
        summary = None
        if phase_stats:
            # The budget that sums to the whole: per-phase seconds
            # across workers; other_s is loop bookkeeping (python,
            # record-keeping) not attributed to a phase.
            keys = ("pull_s", "pull_place_s", "dispatch_s",
                    "push_materialize_s", "push_wire_s", "poll_s",
                    "drain_s", "loop_s", "pull_bytes", "push_bytes",
                    "pulls", "pushes", "pull_fresh")
            tot = {k: float(sum(d.get(k, 0) for d in phase_stats))
                   for k in keys}
            tot["other_s"] = tot["loop_s"] - sum(
                tot[k] for k in ("pull_s", "pull_place_s", "dispatch_s",
                                 "push_materialize_s", "push_wire_s",
                                 "poll_s", "drain_s")
            )
            summary = {
                "hogwild_phases": phase_stats,
                "hogwild_budget": tot,
                "server_applied": server.applied_updates,
            }
        if fleet is not None:
            summary = dict(summary or {})
            summary["fleet"] = {
                "shards": len(fleet.urls()),
                "ring_version": fleet.ring_version,
                "shard_restarts": int(_restart_counter_total()
                                      - restarts_baseline),
            }
        if ft_summaries:
            summary = dict(summary or {})
            summary["ft"] = {
                "rounds": ft_summaries,
                "restarts_total": sum(
                    sum(s.get("restarts", {}).values())
                    for s in ft_summaries
                ),
            }
        return TrainResult(
            params=params, model_state=model_state, metrics=records,
            spec=spec, summary=summary,
        )
    finally:
        if profiler is not None:
            profiler.__exit__(None, None, None)
        # Stop server even on failure (hogwild.py:184-186 parity).
        # Transports first: a ShardedTransport owns connections (and
        # possibly a fan-out pool) that must not outlive the run.
        for transport in worker_transports:
            close = getattr(transport, "close", None)
            if close is not None:
                try:
                    close()
                except OSError:
                    pass
        if http is not None:
            http.stop()
        server.stop()


# ---------------------------------------------------------------------------
# Process entry point
# ---------------------------------------------------------------------------


def run_hogwild_worker(torch_obj, url: str, data,
                       labels=None, iters: int = 10,
                       mini_batch: Optional[int] = None,
                       push_every: int = 1, seed: int = 0,
                       worker_id: int = 0, wire: str = "binary",
                       quant: Optional[str] = None,
                       compress: bool = True,
                       records_path: Optional[str] = None,
                       ctx=None) -> dict:
    """ONE hogwild worker as a standalone process — the training third
    of the ``run_shard_server``-shaped entry family, runnable under
    ``python -m sparktorch_tpu.ctl.worker`` with
    ``kind='hogwild_worker'``: pull/push against ``url`` (a param
    server, or a fleet gateway for legacy-topology workers) with its
    own process, GIL, and device context.

    ``data`` is the worker's SHARD: arrays, an ``(x, y)`` tuple, or a
    path to an ``.npz`` with ``x``/``y`` (how a driver ships shards to
    spawned processes without dill-ing arrays through the payload).
    Records flush ATOMICALLY at completion to ``records_path``
    (tmp + rename): a killed attempt publishes nothing, so the
    supervisor-restarted rerun keeps counts exact — the same
    records-exactness contract the thread deployment pins. The ctl
    context's cancel event preempts between windows
    (:class:`~sparktorch_tpu.ft.supervisor.WorkerPreempted`), and its
    heartbeat carries the iteration for skew/stall policies.
    """
    if isinstance(data, str):
        loaded = np.load(data)
        x, y = loaded["x"], loaded["y"]
    elif isinstance(data, tuple) and labels is None:
        x, y = data
    else:
        x, y = data, labels
    spec = deserialize_model(torch_obj)
    if spec.input_shape is None:
        spec.input_shape = tuple(np.asarray(x).shape[1:])
    module = spec.make_module()
    variables = dict(spec.init_params(jax.random.key(seed)))
    variables.pop("params", None)
    model_state = variables or {}
    grad_step = make_grad_step(module.apply, spec.loss_fn(),
                               mini_batch=mini_batch)
    grad_windows = make_grad_windows(module.apply, spec.loss_fn(),
                                     mini_batch, push_every, iters)
    if wire == "binary":
        push_quant = quant if quant else ("bf16" if compress else None)
        transport = BinaryTransport(url, quant=push_quant)
    elif wire == "dill":
        transport = HttpTransport(url, compress=compress)
    else:
        raise ValueError(f"unknown wire {wire!r}; use 'binary' or 'dill'")
    device = jax.devices()[0]
    shard = DataBatch(jnp.asarray(x), jnp.asarray(y),
                      jnp.ones((np.asarray(x).shape[0],), jnp.float32))
    records: List[dict] = []
    errors: List[BaseException] = []
    tele = getattr(ctx, "telemetry", None) or get_telemetry()
    cancel = getattr(ctx, "cancel", None)
    hb = getattr(ctx, "heartbeat", None)
    if hb is not None:
        # Mirror loop progress onto the heartbeat: _worker_loop's
        # telemetry counters already track iters; the heartbeat step
        # is what the supervisor's skew/stall policies read. The real
        # cancel is captured under its own name BEFORE the rebind
        # below — is_set() reading the closure's `cancel` would find
        # the wrapper itself and recurse.
        inner_cancel = cancel

        class _HbCancel:
            """Duck-typed cancel: the loop polls is_set() once per
            window — piggyback the heartbeat step publish there."""

            def is_set(_self) -> bool:
                hb.notify_step(int(tele.snapshot().get("counters", {})
                                   .get(f"hogwild.iters{{worker={worker_id}}}",
                                        0)))
                return (inner_cancel.is_set()
                        if inner_cancel is not None else False)

        cancel = _HbCancel()
    try:
        _worker_loop(worker_id, device, transport, grad_step,
                     model_state, shard, None, iters, 0, False, seed,
                     records, errors, push_every, None, grad_windows,
                     None, tele, cancel)
    finally:
        close = getattr(transport, "close", None)
        if close is not None:
            try:
                close()
            except OSError:
                pass
    if errors:
        raise errors[0]
    if records_path:
        from sparktorch_tpu.obs.sinks import write_jsonl
        import os as _os
        import tempfile as _tempfile

        fd, tmp = _tempfile.mkstemp(
            prefix=".hogwild_records.", suffix=".jsonl",
            dir=_os.path.dirname(records_path) or ".")
        _os.close(fd)
        write_jsonl(tmp, records, append=False)
        _os.replace(tmp, records_path)
    return {"worker_id": worker_id, "iters": iters,
            "records": len(records),
            "final_loss": records[-1]["loss"] if records else None}

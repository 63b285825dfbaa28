"""Fault-tolerance subsystem: policies, chaos injection, supervised
recovery. The reference has NO recovery story at all (SURVEY §L3:
barrier training dies with the stage, hogwild merely tolerates server
errors) — here every recovery path is exercised for real, driven by
the seeded chaos harness so the tests are deterministic.
"""

import os
import threading
import time

import numpy as np
import pytest

from sparktorch_tpu import serialize_torch_obj
from sparktorch_tpu.ft import (
    ChaosConfig,
    ChaosInjector,
    ChaosKill,
    FtPolicy,
    RestartPolicy,
    StragglerPolicy,
    Supervisor,
    ThreadWorker,
    WorkerFailed,
    inject,
    supervise_run,
)
from sparktorch_tpu.models import ClassificationNet, Net
from sparktorch_tpu.obs import Telemetry


# ---------------------------------------------------------------------------
# Policies
# ---------------------------------------------------------------------------


def test_restart_policy_backoff_deterministic():
    pol = RestartPolicy(max_restarts=5, backoff_base_s=0.1,
                        backoff_max_s=1.0, jitter=0.2)
    a = [pol.delay_s(k, FtPolicy(seed=7).rng()) for k in range(6)]
    b = [pol.delay_s(k, FtPolicy(seed=7).rng()) for k in range(6)]
    assert a == b  # same seed -> same jitter -> same delays
    # Exponential growth up to the cap, jitter bounded at +-20%.
    for k, d in enumerate(a):
        base = min(1.0, 0.1 * 2 ** k)
        assert 0.8 * base <= d <= 1.2 * base
    # No jitter -> exact exponential.
    flat = RestartPolicy(backoff_base_s=0.1, backoff_max_s=1.0, jitter=0)
    rng = FtPolicy().rng()
    assert [flat.delay_s(k, rng) for k in range(5)] == [
        0.1, 0.2, 0.4, 0.8, 1.0
    ]


# ---------------------------------------------------------------------------
# Chaos injector
# ---------------------------------------------------------------------------


def test_chaos_kill_is_one_shot_and_recorded():
    inj = ChaosInjector(ChaosConfig(kill_worker_at={2: 5}))
    # Before the step: nothing.
    assert inj.fire("worker.step", worker=2, step=4) is None
    assert inj.fire("worker.step", worker=1, step=99) is None
    with pytest.raises(ChaosKill):
        inj.fire("worker.step", worker=2, step=5)
    # One-shot: the restarted worker's rerun must survive.
    assert inj.fire("worker.step", worker=2, step=5) is None
    assert inj.events == [{"site": "worker.step", "worker": 2, "step": 5}]


def test_chaos_heartbeat_freeze_stops_publishing(tmp_path):
    from sparktorch_tpu.obs import gang_report
    from sparktorch_tpu.obs.heartbeat import HeartbeatEmitter

    d = str(tmp_path / "hb")
    em = HeartbeatEmitter(d, rank=3)
    em.notify_step(1)
    first = gang_report(d)["ranks"][3]
    with inject(ChaosConfig(freeze_heartbeat_at={3: 2})):
        em.notify_step(2)  # at the freeze step: publish skipped
        rec = em.beat()
        assert rec.get("frozen") is True
    after = gang_report(d)["ranks"][3]
    # The table still shows the LAST published record, aging — the
    # alive-but-silent signature a stall deadline catches.
    assert after["step"] == first["step"] == 1
    assert after["beats"] == first["beats"]


# ---------------------------------------------------------------------------
# Supervisor
# ---------------------------------------------------------------------------


def _policy(max_restarts=3):
    return FtPolicy(restart=RestartPolicy(max_restarts=max_restarts,
                                          backoff_base_s=0.01,
                                          backoff_max_s=0.05))


def test_supervisor_restarts_until_success():
    tele = Telemetry(run_id="sup")
    attempts = []

    def start(attempt):
        def target():
            attempts.append(attempt)
            if attempt < 2:
                raise RuntimeError(f"boom {attempt}")
        return ThreadWorker("w", target)

    sup = Supervisor(policy=_policy(), telemetry=tele)
    sup.add("w", start)
    summary = sup.run()
    assert attempts == [0, 1, 2]
    assert summary["restarts"] == {"w": 2}
    assert summary["failed"] == []
    assert tele.counter_value("ft_restarts_total",
                              labels={"worker": "w"}) == 2
    lat = tele.histogram("ft_recovery_latency_s", labels={"worker": "w"})
    assert lat["count"] == 2 and lat["max"] > 0


def test_supervisor_budget_exhausted_raises():
    tele = Telemetry(run_id="sup2")

    def start(attempt):
        def target():
            raise RuntimeError("always")
        return ThreadWorker("w", target)

    sup = Supervisor(policy=_policy(max_restarts=2), telemetry=tele)
    sup.add("w", start)
    with pytest.raises(WorkerFailed):
        sup.run()
    assert tele.counter_value("ft_restarts_total",
                              labels={"worker": "w"}) == 2


def test_supervisor_straggler_warning_from_heartbeats(tmp_path):
    from sparktorch_tpu.obs.heartbeat import HeartbeatEmitter

    d = str(tmp_path / "hb")
    HeartbeatEmitter(d, rank=0).notify_step(100)
    HeartbeatEmitter(d, rank=1).notify_step(3)

    tele = Telemetry(run_id="strag")
    pol = FtPolicy(
        restart=RestartPolicy(max_restarts=0),
        straggler=StragglerPolicy(warn_skew_steps=50),
    )
    sup = Supervisor(policy=pol, telemetry=tele, heartbeat_dir=d)
    for rank in (0, 1):
        sup.add(str(rank),
                lambda attempt: ThreadWorker(str(attempt),
                                             lambda: time.sleep(0.3)),
                rank=rank)
    sup.run()
    # rank 1 lags by 97 steps >= warn threshold: warned exactly once
    # per episode, and the laggard is the one blamed.
    assert tele.counter_value("ft_straggler_warnings_total",
                              labels={"worker": "1"}) == 1
    assert tele.counter_value("ft_straggler_warnings_total",
                              labels={"worker": "0"}) == 0


def test_supervisor_straggler_warns_once_per_episode(tmp_path):
    """The warn latch re-arms when the laggard catches up: episode 1
    warns, the recovery clears the latch, episode 2 warns again —
    without re-arming, an operator watching the counter would think a
    recurring straggler resolved after its first episode."""
    from sparktorch_tpu.obs.heartbeat import HeartbeatEmitter

    d = str(tmp_path / "hb")
    fast = HeartbeatEmitter(d, rank=0)
    slow = HeartbeatEmitter(d, rank=1)
    fast.notify_step(100)
    slow.notify_step(3)

    tele = Telemetry(run_id="episodes")
    sup = Supervisor(policy=FtPolicy(
        restart=RestartPolicy(max_restarts=0),
        straggler=StragglerPolicy(warn_skew_steps=50),
    ), telemetry=tele, heartbeat_dir=d)
    for rank in (0, 1):
        sup.add(str(rank), lambda attempt: None, rank=rank)

    labels = {"worker": "1"}
    sup._apply_skew_policies()  # episode 1: skew 97 -> warn
    sup._apply_skew_policies()  # still lagging: latched, no re-warn
    assert tele.counter_value("ft_straggler_warnings_total",
                              labels=labels) == 1
    slow.notify_step(95)        # caught up: skew 5 ends the episode
    sup._apply_skew_policies()
    fast.notify_step(300)       # episode 2: skew 205
    sup._apply_skew_policies()
    assert tele.counter_value("ft_straggler_warnings_total",
                              labels=labels) == 2


def _broken_exporter(mode: str):
    """An HTTP server whose /heartbeats is broken in a named way:
    'http500' answers 500, 'torn' sends invalid JSON, 'junk_keys'
    sends well-formed JSON with non-numeric rank keys."""
    import http.server
    import threading

    class Handler(http.server.BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_GET(self):
            if mode == "http500":
                self.send_response(500)
                self.send_header("Content-Length", "0")
                self.end_headers()
                return
            body = (b'{"ranks": {' if mode == "torn"
                    else b'{"ranks": {"not-a-rank": {"alive": true}}}')
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    httpd = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    return httpd


@pytest.mark.parametrize("mode", ["http500", "torn", "junk_keys"])
def test_supervisor_exporter_scrape_failures_degrade(mode):
    """The exporter-scraping path under failure (ISSUE satellite): an
    exporter answering 500, serving torn JSON, or replying with a
    shape the reader doesn't expect must degrade to a warning +
    ft_scrape_errors_total — _report() returns None, the skew/stall
    policies skip the tick, and the supervision run COMPLETES."""
    httpd = _broken_exporter(mode)
    tele = Telemetry(run_id=f"scrape_{mode}")
    try:
        pol = FtPolicy(restart=RestartPolicy(max_restarts=0),
                       straggler=StragglerPolicy(warn_skew_steps=5))
        sup = Supervisor(
            policy=pol, telemetry=tele,
            exporter_url=f"http://127.0.0.1:{httpd.server_address[1]}",
        )
        assert sup._report() is None
        assert tele.counter_value("ft_scrape_errors_total",
                                  labels={"source": "exporter"}) == 1
        # The poll loop survives the broken exporter end to end.
        sup.add("w", lambda attempt: ThreadWorker(
            "w", lambda: time.sleep(0.15)), rank=0)
        summary = sup.run()
        assert summary["failed"] == []
        assert tele.counter_value("ft_scrape_errors_total",
                                  labels={"source": "exporter"}) >= 2
    finally:
        httpd.shutdown()
        httpd.server_close()


def test_supervisor_exporter_vanished_mid_poll_degrades():
    """An exporter that dies BETWEEN polls (connection refused) is the
    same degradation: None report, counter, run completes."""
    httpd = _broken_exporter("junk_keys")
    url = f"http://127.0.0.1:{httpd.server_address[1]}"
    httpd.shutdown()
    httpd.server_close()  # vanished: nothing listens anymore

    tele = Telemetry(run_id="scrape_vanish")
    sup = Supervisor(policy=_policy(), telemetry=tele, exporter_url=url)
    assert sup._report() is None
    assert tele.counter_value("ft_scrape_errors_total",
                              labels={"source": "exporter"}) == 1
    sup.add("w", lambda attempt: ThreadWorker("w", lambda: None), rank=0)
    assert sup.run()["failed"] == []


def test_supervisor_exporter_happy_path_still_reports():
    """The hardening must not break the working scrape: a real gang
    exporter over a heartbeat dir keeps feeding the skew policies."""
    import tempfile

    from sparktorch_tpu.native.gang import GangMetricsExporter
    from sparktorch_tpu.obs.heartbeat import HeartbeatEmitter

    with tempfile.TemporaryDirectory() as d:
        HeartbeatEmitter(d, rank=0).notify_step(50)
        HeartbeatEmitter(d, rank=1).notify_step(7)
        with GangMetricsExporter(heartbeat_dir=d) as exporter:
            sup = Supervisor(policy=_policy(),
                             telemetry=Telemetry(run_id="scrape_ok"),
                             exporter_url=exporter.url)
            report = sup._report()
    assert report is not None
    assert report["ranks"][0]["step"] == 50  # re-keyed to int
    assert report["step_skew"] == 43


# ---------------------------------------------------------------------------
# Checkpoint auto-discovery (latest_step)
# ---------------------------------------------------------------------------


def test_latest_step_skips_tmp_and_torn(tmp_path):
    from sparktorch_tpu.utils.checkpoint import latest_step

    d = tmp_path / "ckpt"
    assert latest_step(str(d)) is None  # missing dir, no error
    d.mkdir()
    for step, finalized in ((3, True), (10, True), (7, False)):
        sub = d / str(step)
        sub.mkdir()
        if finalized:
            (sub / "data").write_text("x")
        # step 7 stays EMPTY: an interrupted finalize.
    (d / "12.orbax-checkpoint-tmp-123").mkdir()  # in-progress save
    (d / "notes.txt").write_text("not a step")
    assert latest_step(str(d)) == 10
    # A tmp item INSIDE a step dir marks it non-finalized too.
    sub = d / "20"
    sub.mkdir()
    (sub / "state.orbax-checkpoint-tmp-9").mkdir()
    assert latest_step(str(d)) == 10


def test_latest_step_agrees_with_manager(tmp_path):
    from typing import NamedTuple

    import jax.numpy as jnp

    from sparktorch_tpu.utils.checkpoint import CheckpointManager, latest_step

    class S(NamedTuple):
        w: object

    d = str(tmp_path / "ckpt")
    with CheckpointManager(d, save_interval_steps=1) as mgr:
        mgr.save(2, S(w=jnp.ones((4,))), force=True)
        mgr.wait()
        mgr.save(5, S(w=jnp.zeros((4,))), force=True)
        mgr.wait()
        assert latest_step(d) == mgr.latest_step() == 5


# ---------------------------------------------------------------------------
# Transport recovery (satellite: reconnect deadline + counter)
# ---------------------------------------------------------------------------


def _server_and_transport(payload, tele, **kw):
    from sparktorch_tpu.net.transport import BinaryTransport
    from sparktorch_tpu.serve.param_server import (
        ParameterServer,
        ParamServerHttp,
    )

    server = ParameterServer(payload, window_len=2, telemetry=tele)
    http = ParamServerHttp(server, port=0).start()
    transport = BinaryTransport(http.url, telemetry=tele, **kw)
    return server, http, transport


@pytest.fixture
def payload():
    return serialize_torch_obj(
        Net(), criterion="mse", optimizer="adam",
        optimizer_params={"lr": 5e-3}, input_shape=(10,),
    )


def test_transport_dead_server_fails_fast_on_deadline(payload):
    from sparktorch_tpu.net.transport import BinaryTransport, TransportError

    tele = Telemetry(run_id="dead")
    # Nothing listens on this port; a huge retry budget would grind
    # for seconds — the wall-clock deadline must cut it short with a
    # clear error naming the deadline.
    t = BinaryTransport("http://127.0.0.1:9", retries=1000,
                        backoff_s=0.01, deadline_s=0.3, telemetry=tele)
    t0 = time.perf_counter()
    with pytest.raises(TransportError, match="deadline"):
        t.pull(-1)
    assert time.perf_counter() - t0 < 5.0
    assert tele.counter_value(
        "transport_reconnects_total",
        labels={"host": "127.0.0.1", "port": 9}) >= 1
    assert t.stats["reconnects"] >= 1


def test_param_server_restart_workers_reconnect(payload):
    """Kill the param server's HTTP front mid-conversation and bring
    it back on the same port: the transport must redial via backoff
    and the binary 304 version-resync must still be correct."""
    tele = Telemetry(run_id="restart")
    server, http, t = _server_and_transport(
        payload, tele, retries=8, backoff_s=0.05)
    try:
        snap = t.pull(-1)
        assert snap is not None
        v0, params = snap
        port = http.port
        http.stop()  # the keep-alive socket dies with the server

        from sparktorch_tpu.serve.param_server import ParamServerHttp

        http = ParamServerHttp(server, port=port).start()
        # Same version on the restarted server: a real 304, reached
        # over a RECONNECTED socket.
        assert t.pull(v0) is None
        assert t.stats["reconnects"] >= 1
        assert tele.counter_value(
            "transport_reconnects_total",
            labels={"host": "127.0.0.1", "port": port}) >= 1
        # And the wire still carries fresh versions after a push.
        import jax

        grads = jax.tree.map(lambda a: np.ones_like(np.asarray(a)), params)
        t.push(grads)
        server.drain()
        snap2 = t.pull(v0)
        assert snap2 is not None and snap2[0] > v0
    finally:
        http.stop()
        server.stop()


def test_chaos_forced_server_500_does_not_taint_server(payload):
    from sparktorch_tpu.net.transport import TransportError

    tele = Telemetry(run_id="c500")
    server, http, t = _server_and_transport(payload, tele)
    try:
        snap = t.pull(-1)
        import jax

        grads = jax.tree.map(lambda a: np.ones_like(np.asarray(a)), snap[1])
        with inject(ChaosConfig(server_error_pushes=1)):
            with pytest.raises(TransportError, match="500"):
                t.push(grads)
        t.push(grads)  # chaos budget spent: next push lands
        server.drain()
        assert server.applied_updates == 1
        # The forced 500 must not burn the tolerated-apply-error
        # budget (it never reached the apply queue).
        assert tele.counter_value("param_server.apply_errors") == 0
    finally:
        http.stop()
        server.stop()


def test_chaos_truncated_pull_frame_raises_wire_error(payload):
    from sparktorch_tpu.net.wire import WireError

    tele = Telemetry(run_id="trunc")
    server, http, t = _server_and_transport(payload, tele)
    try:
        with inject(ChaosConfig(truncate_pull_frames=1)):
            with pytest.raises(WireError):
                t.pull(-1)
        snap = t.pull(-1)  # budget spent: clean frame decodes
        assert snap is not None
    finally:
        http.stop()
        server.stop()


def test_chaos_connection_drop_exercises_reconnect(payload):
    tele = Telemetry(run_id="drop")
    server, http, t = _server_and_transport(
        payload, tele, retries=4, backoff_s=0.01)
    try:
        assert t.pull(-1) is not None
        with inject(ChaosConfig(drop_connections=1)):
            # The injected drop fails one attempt; reconnect+backoff
            # completes the request transparently.
            assert t.alive()
        assert t.stats["reconnects"] >= 1
    finally:
        http.stop()
        server.stop()


# ---------------------------------------------------------------------------
# End-to-end recovery (the acceptance scenarios)
# ---------------------------------------------------------------------------


def test_hogwild_chaos_kill_supervised_recovers_and_converges():
    """THE deterministic chaos test the ISSUE's acceptance names: a
    seeded kill takes out one hogwild worker mid-run; the supervisor
    restarts it; the restarted worker rejoins by pulling the current
    server version; the run completes with ``ft_restarts_total == 1``,
    the sorted-input model still converges (within tolerance of an
    uninterrupted run's ~0.96), and the recovery metrics appear in
    BOTH a real ``/metrics`` scrape and the JSONL dump."""
    import urllib.request

    import jax.numpy as jnp

    from sparktorch_tpu.native.gang import GangMetricsExporter
    from sparktorch_tpu.obs import read_jsonl
    from sparktorch_tpu.train.hogwild import train_async
    from sparktorch_tpu.utils.serde import deserialize_model

    rng = np.random.default_rng(0)
    dim = 10
    x = np.concatenate([
        rng.normal(0.0, 1.0, (100, dim)),
        rng.normal(2.0, 1.0, (100, dim)),
    ]).astype(np.float32)  # label-sorted: the hard input
    y = np.concatenate([np.zeros(100), np.ones(100)]).astype(np.float32)
    payload = serialize_torch_obj(
        ClassificationNet(n_classes=2), criterion="cross_entropy",
        optimizer="adam", optimizer_params={"lr": 5e-3}, input_shape=(dim,),
    )
    tele = Telemetry(run_id="chaos_hogwild")
    with inject(ChaosConfig(kill_worker_at={1: 5}, seed=0),
                telemetry=tele) as inj:
        result = train_async(payload, x, labels=y, iters=25, partitions=2,
                             seed=0, supervise=True, ft_policy=_policy(),
                             telemetry=tele)
    assert [e["site"] for e in inj.events] == ["worker.step"]

    ft = result.summary["ft"]
    assert ft["restarts_total"] == 1
    assert tele.counter_value("ft_restarts_total",
                              labels={"worker": "1"}) == 1
    lat = tele.histogram("ft_recovery_latency_s", labels={"worker": "1"})
    assert lat["count"] == 1 and 0 < lat["max"] < 30
    # Record count is exact: the killed attempt flushed nothing, the
    # restarted attempt reran the round assignment.
    assert len(result.metrics) == 50

    # Within tolerance of an uninterrupted run (which lands ~0.96 on
    # this config — see test_hogwild_sorted_input_no_minibatch_trains).
    spec = deserialize_model(payload)
    module = spec.make_module()
    preds = np.argmax(np.asarray(
        module.apply({"params": result.params}, jnp.asarray(x))), axis=1)
    acc = float((preds == y).mean())
    assert acc > 0.9, acc

    # The same bus, scraped over real HTTP and dumped as JSONL.
    with GangMetricsExporter(telemetry=tele) as exporter:
        with urllib.request.urlopen(exporter.url + "/metrics") as resp:
            text = resp.read().decode()
    assert "sparktorch_ft_restarts_total" in text
    assert "sparktorch_ft_recovery_latency_s" in text
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "telemetry.jsonl")
        tele.dump(path)
        (snap,) = read_jsonl(path)
    assert snap["counters"]["ft_restarts_total{worker=1}"] == 1
    assert snap["histograms"]["ft_recovery_latency_s{worker=1}"]["count"] == 1


def test_worker_loop_preemption_stops_slowed_worker():
    """Hogwild preemption made real (ROADMAP ft follow-up): a
    supervisor kill() on a thread-based worker sets the cancel event,
    and ``_worker_loop`` POLLS it between windows — so a deliberately
    slowed worker (a transport whose pulls crawl) stops within a
    window boundary instead of grinding through its whole iteration
    budget with the preempt silently ignored."""
    from sparktorch_tpu.ft import WorkerPreempted
    from sparktorch_tpu.train.hogwild import _worker_loop, make_grad_step
    from sparktorch_tpu.utils.data import DataBatch

    import jax

    class SlowTransport:
        """Each pull crawls: without preemption, 200 iters x 0.05s
        would take ~10s."""

        def __init__(self):
            self.stats = None
            self.pulls = 0

        def pull(self, have_version):
            self.pulls += 1
            time.sleep(0.05)
            if have_version < 0:
                params = {"w": np.zeros((4,), np.float32)}
                return 0, params
            return None

        def push(self, grads):
            pass

        def post_loss(self, loss):
            return False

    rng = np.random.default_rng(0)
    shard = DataBatch(
        x=np.asarray(rng.normal(size=(32, 4)).astype(np.float32)),
        y=np.asarray(rng.integers(0, 2, (32,)).astype(np.int32)),
        w=np.ones((32,), np.float32),
    )

    def apply_fn(variables, x, mutable=None):
        preds = x @ variables["params"]["w"].reshape(4, 1)
        return (preds, {}) if mutable is not None else preds

    def loss_fn(preds, y):
        return (preds[:, 0] - y) ** 2

    grad_step = make_grad_step(apply_fn, loss_fn)
    transport = SlowTransport()
    errors, records = [], []
    started = threading.Event()

    def target(cancel):
        started.set()
        _worker_loop(0, jax.devices()[0], transport, grad_step, {},
                     shard, None, 200, 0, False, 0, records, errors,
                     cancel=cancel)

    t0 = time.perf_counter()
    w = ThreadWorker("slow", target, pass_cancel=True)
    assert started.wait(5)
    while transport.pulls < 2 and time.perf_counter() - t0 < 5:
        time.sleep(0.01)
    w.kill()                       # the supervisor's preempt path
    w.join(timeout=5)
    assert not w.is_alive(), "preempt ignored: worker still running"
    assert time.perf_counter() - t0 < 8.0  # nowhere near the full loop
    assert errors and isinstance(errors[0], WorkerPreempted)
    # A preempted attempt flushes NO records (the restarted attempt
    # reruns the assignment, keeping counts exact).
    assert records == []


def test_supervisor_preempts_frozen_heartbeat_exactly_once(tmp_path):
    """A supervised rank whose first attempt goes silent mid-run (it
    keeps running, its heartbeat stops): the barrier deadline preempts
    it through its cancel event, the restarted attempt finishes, and
    the books are exact — one stall preemption, one restart, every
    rank completed once."""
    from sparktorch_tpu.ft.policy import BarrierPolicy
    from sparktorch_tpu.obs.heartbeat import HeartbeatEmitter

    hb_dir = str(tmp_path / "hb")
    tele = Telemetry(run_id="freeze")
    frozen_rank, freeze_at, steps = 1, 3, 40
    done = {r: 0 for r in range(3)}
    lock = threading.Lock()

    def make_start(rank):
        def start(attempt):
            # Freshen the slot before the handle exists: the frozen
            # file's age must not re-preempt the restarted attempt.
            HeartbeatEmitter(hb_dir, rank).beat()

            def target(cancel):
                emitter = HeartbeatEmitter(hb_dir, rank)
                frozen = attempt == 0 and rank == frozen_rank
                for s in range(steps):
                    if cancel.is_set():
                        return
                    if not (frozen and s >= freeze_at):
                        emitter.notify_step(s)
                    time.sleep(0.02)
                with lock:
                    done[rank] += 1
                emitter.close()

            return ThreadWorker(f"freeze{rank}", target, pass_cancel=True)

        return start

    policy = FtPolicy(restart=RestartPolicy(max_restarts=2,
                                            backoff_base_s=0.05),
                      barrier=BarrierPolicy(deadline_s=0.3), seed=0)
    sup = Supervisor(policy=policy, telemetry=tele, heartbeat_dir=hb_dir,
                     name="freeze")
    for rank in range(3):
        sup.add(str(rank), make_start(rank), rank=rank)
    sup.run(deadline_s=60)
    assert done == {0: 1, 1: 1, 2: 1}
    counters = tele.snapshot()["counters"]
    assert {k: v for k, v in counters.items()
            if k.startswith("ft_stall_preemptions_total")} == {
        f"ft_stall_preemptions_total{{worker={frozen_rank}}}": 1}
    assert {k: v for k, v in counters.items()
            if k.startswith("ft_restarts_total")} == {
        f"ft_restarts_total{{worker={frozen_rank}}}": 1}


def test_sync_chaos_kill_resumes_from_latest_checkpoint(tmp_path):
    """Sync recovery: a seeded kill interrupts a checkpointed DP run;
    ``supervise_run`` restarts the attempt, auto-discovers the latest
    finalized snapshot, and the resumed run continues FROM it (the
    restored step count proves it) instead of from scratch."""
    from sparktorch_tpu.train.sync import train_distributed
    from sparktorch_tpu.utils.checkpoint import latest_step

    rng = np.random.default_rng(0)
    x = rng.normal(0, 1, (64, 10)).astype(np.float32)
    y = (x.mean(1) > 0).astype(np.float32)
    payload = serialize_torch_obj(
        Net(), criterion="mse", optimizer="sgd",
        optimizer_params={"lr": 1e-2}, input_shape=(10,),
    )
    ckpt_dir = str(tmp_path / "ckpt")
    tele = Telemetry(run_id="chaos_sync")

    def attempt_fn(attempt, resume):
        return train_distributed(
            payload, x, labels=y, iters=6, steps_per_call=1,
            checkpoint_dir=ckpt_dir, checkpoint_every=2, resume=resume,
            seed=3,
        )

    with inject(ChaosConfig(kill_worker_at={0: 4}, seed=0), telemetry=tele):
        result = supervise_run(attempt_fn, policy=_policy(),
                               telemetry=tele, retry_on=(ChaosKill,),
                               checkpoint_dir=ckpt_dir, name="sync_gang")
    # Attempt 0 died at step 4 with snapshots at 2 and 4 on disk;
    # attempt 1 resumed from step 4 and trained 6 more.
    assert tele.counter_value("ft_restarts_total",
                              labels={"worker": "sync_gang"}) == 1
    assert latest_step(ckpt_dir) == 10
    assert len(result.metrics) == 6
    assert result.metrics[-1]["loss"] < result.metrics[0]["loss"]


def test_supervise_run_first_attempt_no_checkpoint_restarts_fresh(tmp_path):
    """A crash BEFORE any save must restart from scratch (resume=False
    — an empty directory is not an error), and only later attempts see
    resume=True once a finalized snapshot exists."""
    calls = []

    def fn(attempt, resume):
        calls.append((attempt, resume))
        if attempt == 0:
            raise RuntimeError("died before first save")
        return "ok"

    out = supervise_run(fn, policy=_policy(),
                        telemetry=Telemetry(run_id="fresh"),
                        checkpoint_dir=str(tmp_path / "empty"),
                        name="g")
    assert out == "ok"
    assert calls == [(0, False), (1, False)]

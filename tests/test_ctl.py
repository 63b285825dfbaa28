"""Elastic gang control plane (sparktorch_tpu/ctl): process workers,
the /ctl control route, live world resize, collector-driven
supervision, and the weight-0 padding protocol the resize leans on.

Named test_ctl.py (not test_elastic.py) so it lands before the tier-1
timeout cutoff — the suite dies mid test_pipeline_parallel and
anything alphabetically later never scores.
"""

import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparktorch_tpu.ctl import (
    EXIT_OK,
    CtlRefused,
    CtlRegistry,
    ElasticController,
    ctl_request,
    round_robin_assign,
    spawn_worker,
)
from sparktorch_tpu.ft import ChaosConfig, inject
from sparktorch_tpu.ft.policy import BarrierPolicy, FtPolicy, RestartPolicy
from sparktorch_tpu.ft.supervisor import (
    Supervisor,
    ThreadWorker,
    WorkerFailed,
)
from sparktorch_tpu.native.gang import GangCoordinator, GangMetricsExporter
from sparktorch_tpu.obs import Telemetry
from sparktorch_tpu.obs.collector import FleetCollector, ScrapeError, post_json


def _fast_policy(max_restarts=1, deadline_s=None):
    kw = {}
    if deadline_s is not None:
        kw["barrier"] = BarrierPolicy(deadline_s=deadline_s)
    return FtPolicy(
        restart=RestartPolicy(max_restarts=max_restarts,
                              backoff_base_s=0.02, backoff_max_s=0.05,
                              jitter=0.0),
        **kw,
    )


# ---------------------------------------------------------------------------
# /ctl route: registry, exporter mount, collector fan-out
# ---------------------------------------------------------------------------


def test_registry_token_and_dispatch():
    reg = CtlRegistry(token="sekrit")
    reg.register("echo", lambda v=None: {"v": v})
    assert reg.verbs() == ["echo"]
    assert reg.check_token("sekrit")
    assert not reg.check_token("wrong")
    assert not reg.check_token(None)
    assert reg.handle("echo", {"v": 7}) == {"v": 7}
    with pytest.raises(KeyError):
        reg.handle("nope", {})
    # No token configured = open (the loopback dev rig).
    assert CtlRegistry(token=None).check_token(None) or \
        os.environ.get("SPARKTORCH_TPU_CTL_TOKEN")


def test_exporter_ctl_route_and_refusals():
    reg = CtlRegistry(token="t0k")
    hits = []
    reg.register("drain", lambda: (hits.append(1), True)[1])
    exp = GangMetricsExporter(ctl=reg, port=0).start()
    url = f"http://127.0.0.1:{exp.port}"
    try:
        reply = ctl_request(url, "drain", token="t0k")
        assert reply["ok"] and reply["result"] is True and hits == [1]
        with pytest.raises(CtlRefused):  # bad token -> 403
            ctl_request(url, "drain", token="wrong")
        with pytest.raises(CtlRefused):  # unknown verb -> 400
            ctl_request(url, "nope", token="t0k")
        assert len(hits) == 1  # refusals never dispatched
    finally:
        exp.stop()
    # An exporter WITHOUT a registry keeps the original read-only
    # surface: POST /ctl is 404, not an open kill switch.
    exp2 = GangMetricsExporter(port=0).start()
    try:
        with pytest.raises(CtlRefused):
            ctl_request(f"http://127.0.0.1:{exp2.port}", "drain")
    finally:
        exp2.stop()


def test_collector_ctl_forward_and_local_dispatch():
    # Rank 0's exporter carries a ctl registry; the collector forwards
    # rank-addressed verbs there and dispatches rank-less verbs on its
    # own registry (the elastic controller's resize seam).
    rank_reg = CtlRegistry()
    rank_reg.register("ping", lambda: {"who": "rank0"})
    exp = GangMetricsExporter(ctl=rank_reg, port=0,
                              telemetry=Telemetry(run_id="r0")).start()
    own = CtlRegistry()
    own.register("world", lambda: {"size": 3})
    collector = FleetCollector({0: f"http://127.0.0.1:{exp.port}"},
                               poll_interval_s=0, ctl=own)
    collector.start(poll_loop=False)
    curl = f"http://127.0.0.1:{collector.port}/ctl"
    try:
        fwd = post_json(curl, {"verb": "ping", "rank": 0})
        assert fwd["ok"] and fwd["reply"]["result"] == {"who": "rank0"}
        loc = post_json(curl, {"verb": "world"})
        assert loc["ok"] and loc["result"] == {"size": 3}
        with pytest.raises(ScrapeError):  # unknown rank -> 404
            post_json(curl, {"verb": "ping", "rank": 9})
        with pytest.raises(ScrapeError):  # unknown local verb -> 400
            post_json(curl, {"verb": "nope"})
    finally:
        collector.stop()
        exp.stop()


# ---------------------------------------------------------------------------
# ProcessWorker: spawn, drain, escalation, HTTP kill
# ---------------------------------------------------------------------------


def _partition_work(out_dir, n=4, sleep=0.01):
    """A dill-shippable work loop with idempotent, atomically-renamed
    partition outputs — the records-exactness shape every restart test
    here leans on."""

    def work(ctx):
        for step in range(n):
            if ctx.should_stop():
                return
            ctx.notify_step(step)
            path = os.path.join(out_dir, f"p{step}.done")
            if os.path.exists(path):
                continue
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                f.write(f"{ctx.name}:{step}")
            os.replace(tmp, path)
            time.sleep(sleep)

    return work


def test_process_worker_completes_with_heartbeat(tmp_path):
    out, hb = str(tmp_path / "out"), str(tmp_path / "hb")
    os.makedirs(out)
    w = spawn_worker(_partition_work(out), rank=0, heartbeat_dir=hb,
                     name="pw0")
    try:
        w.join(90)
        assert w.process.returncode == EXIT_OK
        assert w.error is None
        assert sorted(os.listdir(out)) == [f"p{i}.done" for i in range(4)]
        rec = w.heartbeat_record()
        assert rec["rank"] == 0 and rec["step"] == 3
        assert rec["alive"] is False  # clean shutdown beat landed
    finally:
        w.cleanup()
    assert not os.path.exists(w.payload_path)


def test_process_worker_sigterm_drains_healthy_worker(tmp_path):
    out = str(tmp_path / "out")
    os.makedirs(out)
    w = spawn_worker(_partition_work(out, n=500, sleep=0.1), rank=1,
                     name="pw1", grace_s=30.0)
    try:
        deadline = time.time() + 60
        while not os.listdir(out) and time.time() < deadline:
            time.sleep(0.02)
        assert os.listdir(out), "worker never started producing"
        w.kill()
        w.join(60)
        # A healthy worker honors SIGTERM via the cancel event and
        # returns early: a DRAIN, not a crash, and never a SIGKILL.
        assert w.process.returncode == EXIT_OK
        assert w.preempted and not w.sigkilled
    finally:
        w.cleanup()


def test_process_worker_sigkill_escalation_for_wedged_worker(tmp_path):
    # A worker that never polls the cancel event models the wedge the
    # thread deployment can never exercise: SIGTERM is translated to a
    # cancel nobody reads, so only the grace escalation's SIGKILL
    # lands, and the error decodes the signal.
    def wedged(ctx):
        while True:
            time.sleep(0.05)

    tele = Telemetry(run_id="wedge")
    hb = str(tmp_path / "hb")
    w = spawn_worker(wedged, name="wedged", rank=0, heartbeat_dir=hb,
                     grace_s=1.0, telemetry=tele)
    try:
        # The entry beats once right after installing its SIGTERM
        # handler: wait for that record so the TERM we send is the
        # handled (ignored) one, not the default-action boot race.
        deadline = time.time() + 60
        while w.heartbeat_record() is None and time.time() < deadline \
                and w.process.poll() is None:
            time.sleep(0.05)
        assert w.heartbeat_record() is not None
        w.kill()
        w.join(90)
        assert w.process.returncode == -9
        assert w.sigkilled
        err = w.error
        assert isinstance(err, WorkerFailed) and "signal 9" in str(err)
        snap = tele.snapshot()["counters"]
        assert snap.get("ctl.sigkill_escalations_total{worker=wedged}") == 1
    finally:
        w.cleanup()


def test_process_worker_http_ctl_kill(tmp_path):
    out = str(tmp_path / "out")
    os.makedirs(out)
    w = spawn_worker(_partition_work(out, n=500, sleep=0.1), rank=2,
                     name="pw2", ctl_port=0)
    try:
        url = w.ctl_url(timeout_s=60)
        assert url, "worker never published its ctl url"
        pong = ctl_request(url, "ping")
        assert pong["result"]["rank"] == 2
        assert pong["result"]["pid"] == w.pid
        ctl_request(url, "kill")  # reply-then-die
        w.join(60)
        assert w.process.returncode == 86
        assert isinstance(w.error, WorkerFailed)
    finally:
        w.cleanup()


def test_chaos_kill_process_at_supervisor_restarts_exact_records(tmp_path):
    """Satellite: seeded NON-COOPERATIVE kill. The chaos site rides the
    supervising poll's is_alive(): when rank 0's heartbeat reports the
    configured step, a raw SIGKILL lands (no SIGTERM, no cancel event,
    no grace). The supervisor restarts it and the atomically-renamed
    partition outputs stay EXACT — each partition completed once."""
    out, hb = str(tmp_path / "out"), str(tmp_path / "hb")
    os.makedirs(out)
    tele = Telemetry(run_id="chaos-proc")
    n_parts = 6

    def start_fn(attempt):
        return spawn_worker(_partition_work(out, n=n_parts, sleep=0.25),
                            rank=0, heartbeat_dir=hb,
                            name=f"victim-a{attempt}", telemetry=tele)

    sup = Supervisor(policy=_fast_policy(max_restarts=2), telemetry=tele,
                     name="chaos-proc")
    sup.add("victim", start_fn, rank=0)
    with inject(ChaosConfig(seed=7, kill_process_at={0: 2}),
                telemetry=tele) as inj:
        summary = sup.run(poll_interval_s=0.05, deadline_s=120)
    assert summary["restarts"] == {"victim": 1}, summary
    fired = [e for e in inj.events if e["site"] == "ctl.process"]
    assert len(fired) == 1 and fired[0]["rank"] == 0
    # Records exact: every partition done exactly once, no .tmp torn
    # files, and the rerun's skip-if-exists kept early partitions from
    # the FIRST attempt (written before the kill at step 2).
    assert sorted(os.listdir(out)) == sorted(
        f"p{i}.done" for i in range(n_parts))
    attempts = {open(os.path.join(out, f"p{i}.done")).read().split(":")[0]
                for i in range(n_parts)}
    assert "victim-a0" in attempts and "victim-a1" in attempts
    counters = tele.snapshot()["counters"]
    assert counters.get("ft_restarts_total{worker=victim}") == 1


# ---------------------------------------------------------------------------
# Supervisor budget-exhaustion hook (the elastic shrink seam)
# ---------------------------------------------------------------------------


def test_supervisor_on_exhausted_absorbs_failure():
    crashes = {"n": 0}

    def start_fn(attempt):
        def run():
            crashes["n"] += 1
            raise RuntimeError("always dies")

        return ThreadWorker("dier", run)

    absorbed = []
    tele = Telemetry(run_id="absorb")
    sup = Supervisor(policy=_fast_policy(max_restarts=1), telemetry=tele,
                     on_exhausted=lambda name, rank, err:
                     (absorbed.append((name, rank)), True)[1])
    sup.add("dier", start_fn, rank=0)
    summary = sup.run(poll_interval_s=0.01, deadline_s=30)  # no raise
    assert absorbed == [("dier", 0)]
    assert crashes["n"] == 2  # first launch + one budgeted restart
    assert summary["failed"] == []
    counters = tele.snapshot()["counters"]
    assert counters.get("ft_budget_absorbed_total{worker=dier}") == 1
    # The default (no hook) still fails the run.
    sup2 = Supervisor(policy=_fast_policy(max_restarts=0))
    sup2.add("dier2", start_fn)
    with pytest.raises(WorkerFailed):
        sup2.run(poll_interval_s=0.01, deadline_s=30)


# ---------------------------------------------------------------------------
# ElasticController: shrink, grow, exact records, coordinator resize
# ---------------------------------------------------------------------------


def _elastic_rig(tmp_path, n_parts=12, crashy_ranks=(), sleep=0.04,
                 **ctl_kw):
    out = str(tmp_path / "elastic")
    os.makedirs(out, exist_ok=True)
    work = [f"part{i}" for i in range(n_parts)]
    crashy = {r: 10_000 for r in crashy_ranks}

    def completed(p):
        return os.path.exists(os.path.join(out, p + ".done"))

    def start_fn(rank, attempt, generation, assignment):
        def run():
            for p in assignment:
                if crashy.get(rank, 0) > 0:
                    crashy[rank] -= 1
                    raise RuntimeError(f"rank{rank} boom")
                if completed(p):
                    continue
                # A temp name per rank: the controller can hand one
                # part to two live ranks (ROADMAP D7), and two writers
                # of one temp name race on the rename.
                tmp = os.path.join(out, f"{p}.{rank}.tmp")
                with open(tmp, "w") as f:
                    f.write(f"{rank}:{generation}")
                os.replace(tmp, os.path.join(out, p + ".done"))
                time.sleep(sleep)

        return ThreadWorker(f"rank{rank}", run)

    tele = ctl_kw.pop("telemetry", None) or Telemetry(run_id="elastic")
    ctl = ElasticController(work, completed, policy=_fast_policy(),
                            telemetry=tele, **ctl_kw)
    return ctl, start_fn, completed, work, tele


def test_round_robin_assign_deterministic():
    a = round_robin_assign([2, 0, 1], ["a", "b", "c", "d", "e"])
    assert a == {0: ["a", "d"], 1: ["b", "e"], 2: ["c"]}
    # Same inputs, any order -> same layout (every generation computes
    # the identical assignment from the membership list alone).
    assert a == round_robin_assign([0, 1, 2], ["a", "b", "c", "d", "e"])


def test_elastic_shrink_and_grow_with_exact_records(tmp_path):
    ctl, start_fn, completed, work, tele = _elastic_rig(
        tmp_path, crashy_ranks=(1,), min_world=1)
    for r in range(3):
        ctl.add_rank(r, start_fn)

    def later_grow():
        time.sleep(0.15)
        ctl.grow(3, start_fn)

    threading.Thread(target=later_grow, daemon=True).start()
    summary = ctl.run(poll_interval_s=0.02, deadline_s=60)
    assert all(completed(p) for p in work)
    assert summary["work_pending"] == 0
    assert summary["resizes"]["shrink"] == 1, summary
    assert summary["resizes"]["grow"] == 1, summary
    assert summary["removed"] == [1]
    assert 3 in ctl.active_ranks() and 1 not in ctl.active_ranks()
    # Every membership change bumped the generation.
    assert summary["generation"] == 2
    kinds = [h["kind"] for h in ctl.history]
    assert "shrink" in kinds and "grow" in kinds and "finish" in kinds
    # Generation-tagged events: the shrink record carries the post-
    # resize generation and the world it left behind.
    shrink = next(h for h in ctl.history if h["kind"] == "shrink")
    assert shrink["generation"] >= 1 and shrink["rank"] == 1
    # The world document rides the bus as the 'elastic' section.
    sec = tele.get_section("elastic")
    assert sec["world_size"] == 3 and sec["generation"] == 2
    assert sec["members"]["1"]["state"] == "removed"
    assert sec["work"]["pending"] == 0
    counters = tele.snapshot()["counters"]
    assert counters.get("ctl.resizes_total{kind=shrink}") == 1
    assert counters.get("ctl.resizes_total{kind=grow}") == 1


def test_elastic_min_world_floor_fails_the_run(tmp_path):
    ctl, start_fn, _, _, _ = _elastic_rig(
        tmp_path, crashy_ranks=(0,), min_world=2)
    ctl.add_rank(0, start_fn)
    ctl.add_rank(1, start_fn)
    with pytest.raises(WorkerFailed, match="min_world"):
        ctl.run(poll_interval_s=0.02, deadline_s=60)


def test_elastic_coordinator_resize_bumps_real_generation(tmp_path):
    coord = GangCoordinator(world_size=3, port=0,
                            heartbeat_timeout_ms=5000)
    try:
        ctl, start_fn, completed, work, _ = _elastic_rig(
            tmp_path, crashy_ranks=(2,), min_world=1, coordinator=coord)
        for r in range(3):
            ctl.add_rank(r, start_fn)
        summary = ctl.run(poll_interval_s=0.02, deadline_s=60)
        assert all(completed(p) for p in work)
        # The shrink went THROUGH the native coordinator: its
        # generation is the controller's, and the world size followed.
        assert coord.generation == summary["generation"] >= 1
        assert coord.world_size == 2
    finally:
        coord.stop()


def test_elastic_shrink_then_grow_redistributes_through_coordinator(
        tmp_path):
    """Shrink THEN grow in one world, through the native coordinator:
    its generation and world size follow the controller's, every
    controller event is generation-tagged, every partition has exactly
    one output with no torn temp file, and partitions completed in
    more than one generation — the resizes really moved work."""
    coord = GangCoordinator(world_size=3, port=0,
                            heartbeat_timeout_ms=5000)
    try:
        ctl, start_fn, completed, work, _ = _elastic_rig(
            tmp_path, n_parts=24, crashy_ranks=(1,), min_world=1,
            coordinator=coord)
        for r in range(3):
            ctl.add_rank(r, start_fn)

        def grow_after_shrink():
            while not ctl._stop.is_set():
                if ctl._resizes["shrink"] >= 1:
                    ctl.grow(3, start_fn)
                    return
                time.sleep(0.01)

        threading.Thread(target=grow_after_shrink, daemon=True).start()
        summary = ctl.run(poll_interval_s=0.02, deadline_s=60)
        assert summary["resizes"] == {"shrink": 1, "grow": 1}
        assert summary["removed"] == [1]
        assert coord.generation == ctl.generation \
            == summary["generation"] == 2
        assert coord.world_size == 3  # ranks 0, 2 and the joined 3
    finally:
        coord.stop()
    kinds = [h["kind"] for h in ctl.history]
    assert kinds.index("shrink") < kinds.index("grow")
    assert all("generation" in h for h in ctl.history)
    out = str(tmp_path / "elastic")
    assert sorted(os.listdir(out)) == sorted(p + ".done" for p in work)
    generations = {open(os.path.join(out, p + ".done")).read().split(":")[1]
                   for p in work}
    assert len(generations) >= 2, generations


def test_native_resize_releases_waiters_and_reregisters():
    from sparktorch_tpu.native.gang import GangWorker

    coord = GangCoordinator(world_size=2, port=0,
                            heartbeat_timeout_ms=5000)
    workers = []
    try:
        w0 = GangWorker("127.0.0.1", coord.port, 0, "a:1")
        w1 = GangWorker("127.0.0.1", coord.port, 1, "b:1")
        workers += [w0, w1]
        ts = [threading.Thread(target=w.barrier, args=(0,))
              for w in (w0, w1)]
        [t.start() for t in ts]
        [t.join(30) for t in ts]
        assert coord.registered == 2
        gen0 = coord.generation
        assert coord.resize(1) == gen0 + 1
        assert coord.world_size == 1
        # A fresh rank registers into the new world and barriers alone
        # — the resized gang is immediately operational.
        w2 = GangWorker("127.0.0.1", coord.port, 0, "a:2")
        workers.append(w2)
        w2.barrier(1)
        assert w2.generation == gen0 + 1
        with pytest.raises(ValueError):
            coord.resize(0)
    finally:
        for w in workers:
            try:
                w.close()
            except Exception:
                pass
        coord.stop()


def test_native_resize_releases_parked_barrier_waiter():
    # A resize with a waiter PARKED mid-barrier (its peers never
    # arrived): the waiter must be released with an error — resize
    # clears barrier_count and the failure latch, so without the
    # generation check in the wait predicate it would re-park forever,
    # and a new generation reusing the same epoch number could hand it
    # a spurious GO.
    from sparktorch_tpu.native.gang import GangFailure, GangWorker

    coord = GangCoordinator(world_size=2, port=0,
                            heartbeat_timeout_ms=30_000)
    workers = []
    try:
        w0 = GangWorker("127.0.0.1", coord.port, 0, "a:1")
        workers.append(w0)
        result = {}

        def park():
            try:
                w0.barrier(0)  # 1 of 2 arrivals: parks server-side
                result["r"] = "GO"
            except GangFailure as e:
                result["r"] = e

        t = threading.Thread(target=park, daemon=True)
        t.start()
        deadline = time.time() + 10
        while coord.registered < 1 and time.time() < deadline:
            time.sleep(0.02)
        time.sleep(0.3)  # let the BAR line land and park
        gen = coord.resize(1)
        t.join(10)
        assert not t.is_alive(), \
            "parked barrier waiter never released by resize"
        assert isinstance(result["r"], GangFailure), result
        # The resized world is immediately operational, and the OLD
        # epoch number is safe to reuse in the new generation.
        w1 = GangWorker("127.0.0.1", coord.port, 0, "a:2")
        workers.append(w1)
        w1.barrier(0)
        assert w1.generation == gen
    finally:
        for w in workers:
            try:
                w.close()
            except Exception:
                pass
        coord.stop()


# ---------------------------------------------------------------------------
# Collector-driven supervision: exporter-vanished vs rank-died
# ---------------------------------------------------------------------------


class _StubHandle:
    def __init__(self, alive=True):
        self.alive = alive
        self.error = None
        self.killed = 0
        self.preempted = False

    name = "stub"

    def is_alive(self):
        return self.alive

    def join(self, timeout=None):
        pass

    def kill(self):
        self.killed += 1
        self.preempted = True
        self.alive = False


class _StubCollector:
    def __init__(self, view):
        self.view = view

    def gang_view(self):
        return self.view


def _gang_doc(scrape_ok, hb_age):
    return {
        "ranks": {"0": {"ok": scrape_ok}},
        "heartbeats": {"ranks": {"0": {"last_seen_age_s": hb_age}}},
    }


def test_gang_view_exporter_vanished_degrades_not_restarts(tmp_path):
    # Scrape failing + heartbeat fresh = the rank is WORKING, only its
    # observability died: one latched event, no kill, no restart.
    view = _gang_doc(scrape_ok=False, hb_age=0.1)
    ctl, start_fn, _, _, tele = _elastic_rig(
        tmp_path, collector=_StubCollector(view))
    ctl.policy = _fast_policy(deadline_s=1.0)
    ctl.add_rank(0, start_fn)
    m = ctl._members[0]
    m.handle = _StubHandle(alive=True)
    ctl._apply_gang_view()
    ctl._apply_gang_view()  # second pass must not re-fire the episode
    assert m.exporter_gone and m.handle.killed == 0 and not m.removed
    counters = tele.snapshot()["counters"]
    assert counters.get("ctl.exporter_vanished_total{rank=0}") == 1
    events = [h["kind"] for h in ctl.history]
    assert events.count("exporter_vanished") == 1
    # Scrape recovering closes the episode (re-armed for the next).
    ctl.collector = _StubCollector(_gang_doc(scrape_ok=True, hb_age=0.1))
    ctl._apply_gang_view()
    assert not m.exporter_gone
    assert "exporter_recovered" in [h["kind"] for h in ctl.history]


def test_gang_view_stalled_rank_with_handle_is_preempted(tmp_path):
    # Heartbeat age past the barrier deadline + a live local handle =
    # alive-but-wedged: preempt through the handle (its own grace ->
    # SIGKILL escalation applies); the restart rides the next poll.
    view = _gang_doc(scrape_ok=True, hb_age=9.0)
    ctl, start_fn, _, _, tele = _elastic_rig(
        tmp_path, collector=_StubCollector(view))
    ctl.policy = _fast_policy(deadline_s=1.0)
    ctl.add_rank(0, start_fn)
    m = ctl._members[0]
    m.handle = _StubHandle(alive=True)
    ctl._apply_gang_view()
    assert m.handle.killed == 1 and not m.removed
    counters = tele.snapshot()["counters"]
    assert counters.get("ft_stall_preemptions_total{worker=rank0}") == 1


def test_gang_view_silent_remote_rank_shrinks_world(tmp_path):
    # A remote member (ctl_url, no start_fn) silent past the deadline
    # cannot be relaunched here — the world must shrink around it.
    view = {
        "ranks": {"0": {"ok": True}, "1": {"ok": True}},
        "heartbeats": {"ranks": {
            "0": {"last_seen_age_s": 0.1},
            "1": {"last_seen_age_s": 9.0},
        }},
    }
    ctl, start_fn, _, _, _ = _elastic_rig(
        tmp_path, collector=_StubCollector(view), min_world=1)
    ctl.policy = _fast_policy(deadline_s=1.0)
    ctl.add_rank(0, start_fn)
    ctl.add_rank(1, ctl_url="http://127.0.0.1:1/nowhere")  # dead remote
    m0 = ctl._members[0]
    m0.handle = _StubHandle(alive=True)
    ctl._apply_gang_view()
    assert ctl._members[1].removed
    assert ctl.world_size() == 1
    assert ctl._resizes["shrink"] == 1


def test_collector_gang_route_carries_elastic_section(tmp_path):
    # The controller publishes its world document on the shared bus;
    # the collector's /gang answer folds it in, so one scrape answers
    # "who is alive" AND "what did the controller do about it".
    tele = Telemetry(run_id="gangelastic")
    exp = GangMetricsExporter(telemetry=Telemetry(run_id="r0"),
                              port=0).start()
    collector = FleetCollector({0: f"http://127.0.0.1:{exp.port}"},
                               telemetry=tele, poll_interval_s=0)
    collector.start(poll_loop=False)
    try:
        ctl, start_fn, completed, work, _ = _elastic_rig(
            tmp_path, crashy_ranks=(1,), min_world=1, telemetry=tele,
            n_parts=6)
        ctl.add_rank(0, start_fn)
        ctl.add_rank(1, start_fn)
        ctl.run(poll_interval_s=0.02, deadline_s=60)
        view = collector.gang_view()
        assert view["elastic"]["world_size"] == 1
        assert view["elastic"]["resizes"]["shrink"] == 1
        kinds = [h["kind"] for h in view["elastic"]["history"]]
        assert "shrink" in kinds
        # And over HTTP, exactly as an operator reads it.
        from sparktorch_tpu.obs.collector import scrape_json

        doc = scrape_json(f"http://127.0.0.1:{collector.port}/gang")
        assert doc["elastic"]["resizes"]["shrink"] == 1
    finally:
        collector.stop()
        exp.stop()


# ---------------------------------------------------------------------------
# Weight-0 padding protocol across world resizes (the math the
# shrink/grow redistribution leans on)
# ---------------------------------------------------------------------------


def _shard_global_batch(x, y, world_size):
    """Round-robin rows over `world_size` shards, each padded with
    weight-0 rows to the (static) max shard size — exactly the ragged-
    partition protocol the trainers use."""
    from sparktorch_tpu.utils.data import DataBatch, pad_batch

    idx = [np.arange(r, len(x), world_size) for r in range(world_size)]
    size = max(len(i) for i in idx)
    shards = []
    for i in idx:
        b = DataBatch(jnp.asarray(x[i]), jnp.asarray(y[i]),
                      jnp.ones((len(i),), jnp.float32))
        shards.append(pad_batch(b, size))
    return shards


def _global_loss_and_grad(w, shards):
    """Per-shard weighted SUMS folded into one global weighted mean —
    the cross-shard reduction every trainer here implements."""

    @jax.jit
    def sums(w, b):
        def num_fn(w):
            per = (b.x @ w - b.y) ** 2
            return jnp.sum(per * b.w)

        num, grad = jax.value_and_grad(num_fn)(w)
        return num, grad, jnp.sum(b.w)

    total_n, total_g, total_w = 0.0, jnp.zeros_like(w), 0.0
    for b in shards:
        n, g, ws = sums(w, b)
        total_n, total_g, total_w = total_n + n, total_g + g, total_w + ws
    return total_n / total_w, total_g / total_w, float(total_w)


def test_weight0_padding_exact_across_world_resize():
    """The resize primitive: a world of N-1 pads where a world of N
    didn't, and the weighted-mean loss/grad CANNOT tell the difference
    — shrink and grow never move the training math."""
    rng = np.random.default_rng(0)
    x = rng.normal(size=(23, 5)).astype(np.float32)  # ragged everywhere
    y = rng.normal(size=(23,)).astype(np.float32)
    w = jnp.asarray(rng.normal(size=(5,)).astype(np.float32))

    results = {}
    for world in (4, 3, 4):  # N -> N-1 -> N, the shrink/grow cycle
        shards = _shard_global_batch(x, y, world)
        loss, grad, weight = _global_loss_and_grad(w, shards)
        results.setdefault(world, []).append((loss, grad, weight))
        # Padding rows are weight 0: the global example count is the
        # REAL row count at every world size.
        assert weight == 23.0
    (l4, g4, _), = results[4][:1]
    (l3, g3, _), = results[3][:1]
    (l4b, g4b, _) = results[4][1]
    np.testing.assert_allclose(float(l4), float(l3), rtol=2e-6)
    np.testing.assert_allclose(np.asarray(g4), np.asarray(g3), rtol=2e-5)
    # Grow back: bitwise-identical to the first N-world pass (same
    # shards, same padding, same reduction order).
    assert float(l4) == float(l4b)
    np.testing.assert_array_equal(np.asarray(g4), np.asarray(g4b))


def test_pad_batch_weight0_rows_never_count():
    from sparktorch_tpu.utils.data import DataBatch, pad_batch

    b = DataBatch(jnp.ones((3, 2)), jnp.ones((3,)),
                  jnp.ones((3,), jnp.float32))
    p = pad_batch(b, 8)
    assert p.size == 8
    assert float(jnp.sum(p.w)) == 3.0
    np.testing.assert_array_equal(np.asarray(p.w[3:]), np.zeros(5))
    with pytest.raises(ValueError):
        pad_batch(p, 4)  # never pad DOWN

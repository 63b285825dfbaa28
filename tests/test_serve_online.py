"""Online inference tier: continuous-batching admission/coalescing,
bucket padding exactness, deadlines, 429 backpressure, router
eviction + re-admission, live weight updates over the wire's
304/delta path, and the traced router->replica->batch waterfall.

(Named test_serve_online so it lands before test_sharded.py — i.e.
before the tier-1 timeout cutoff position.)
"""

import threading
import time

import jax
import numpy as np
import pytest

from sparktorch_tpu import serialize_torch_obj
from sparktorch_tpu.ft import ChaosConfig, inject
from sparktorch_tpu.ft.policy import BarrierPolicy, FtPolicy, RestartPolicy
from sparktorch_tpu.models import ClassificationNet, Net
from sparktorch_tpu.net.transport import BinaryTransport
from sparktorch_tpu.obs import HeartbeatEmitter, Telemetry
from sparktorch_tpu.obs.rpctrace import stitch_spans, tracer_for
from sparktorch_tpu.serve.fleet import ParamServerFleet
from sparktorch_tpu.serve.infer import (
    DeadlineExceeded,
    InferenceReplica,
    Overloaded,
    WeightPuller,
)
from sparktorch_tpu.serve.param_server import ParameterServer, ParamServerHttp
from sparktorch_tpu.serve.router import InferenceTier, Router


@pytest.fixture(scope="module")
def trained():
    module = Net()
    x = np.random.default_rng(0).normal(0, 1, (16, 10)).astype(np.float32)
    variables = module.init(jax.random.key(0), x)
    return module, variables, x


def _replica(trained, tele, **kwargs):
    module, variables, x = trained
    kwargs.setdefault("buckets", (1, 8))
    kwargs.setdefault("warm_input", x[:1])
    return InferenceReplica(module, variables["params"], telemetry=tele,
                            **kwargs)


def _ref(trained, x):
    module, variables, _ = trained
    return np.asarray(module.apply(variables, x))


# ---------------------------------------------------------------------------
# Admission / coalescing / padding
# ---------------------------------------------------------------------------


def test_admission_coalesces_deterministically(trained):
    """Requests queued while no batch is in flight coalesce into ONE
    bucket-sized batch, FIFO, and each future gets exactly its own
    rows back."""
    _m, _v, x = trained
    tele = Telemetry(run_id="t_coalesce")
    rep = _replica(trained, tele, replica_id="0", auto_start=False)
    futs = [rep.submit(x[i:i + 1]) for i in range(5)]
    assert rep.queued_rows == 5
    rep.start()
    outs = [f.result(10.0) for f in futs]
    # One batch, smallest bucket that fits (8), fill 5/8.
    assert tele.counter_value("serve.batches_total",
                              {"replica": "0"}) == 1
    assert tele.gauge_value("serve.last_bucket", {"replica": "0"}) == 8
    fill = tele.histogram("serve.batch_fill", {"replica": "0"})
    assert fill["count"] == 1 and abs(fill["p50"] - 5 / 8) < 1e-9
    ref = _ref(trained, x[:5])
    for i, out in enumerate(outs):
        assert out.shape == (1, 1)
        np.testing.assert_allclose(out, ref[i:i + 1], rtol=1e-5, atol=1e-6)
    rep.stop()


def test_bucket_padding_never_leaks(trained):
    """Mixed-size requests padded to a bucket return exactly their own
    rows, bit-equal to the unpadded single-request forward — padded
    zero rows never appear in any output."""
    _m, _v, x = trained
    tele = Telemetry(run_id="t_pad")
    rep = _replica(trained, tele, replica_id="0", auto_start=False)
    sizes = [1, 3, 2]
    offs = np.cumsum([0] + sizes)
    futs = [rep.submit(x[offs[i]:offs[i] + n])
            for i, n in enumerate(sizes)]
    rep.start()
    ref = _ref(trained, x[:offs[-1]])
    for i, (fut, n) in enumerate(zip(futs, sizes)):
        out = fut.result(10.0)
        assert out.shape[0] == n
        np.testing.assert_allclose(out, ref[offs[i]:offs[i] + n],
                                   rtol=1e-5, atol=1e-6)
    # A full-bucket request (no padding at all) agrees too.
    out = rep.infer(x[:8])
    np.testing.assert_allclose(out, _ref(trained, x[:8]),
                               rtol=1e-5, atol=1e-6)
    rep.stop()


def test_mixed_shape_requests_never_coalesce(trained):
    """Requests with different row shapes/dtypes queued together form
    SEPARATE batches (a shape-blind concatenate would crash the loop
    thread and orphan every queued request): both complete, FIFO
    order preserved, and the loop survives to serve more traffic."""
    import flax.linen as nn

    class AnyShape(nn.Module):
        @nn.compact
        def __call__(self, x):
            scale = self.param("scale", nn.initializers.ones, ())
            return x.sum(axis=-1, keepdims=True) * (scale + 1.0)

    tele = Telemetry(run_id="t_mixed_shape")
    module = AnyShape()
    params = module.init(jax.random.key(0),
                         np.zeros((1, 10), np.float32))["params"]
    rep = InferenceReplica(module, params, telemetry=tele,
                           replica_id="0", buckets=(1, 8),
                           auto_start=False)
    rng = np.random.default_rng(3)
    a = rng.normal(0, 1, (2, 10)).astype(np.float32)
    b = rng.normal(0, 1, (2, 12)).astype(np.float32)
    fa, fb = rep.submit(a), rep.submit(b)
    rep.start()
    np.testing.assert_allclose(fa.result(10.0),
                               a.sum(-1, keepdims=True) * 2.0,
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(fb.result(10.0),
                               b.sum(-1, keepdims=True) * 2.0,
                               rtol=1e-5, atol=1e-6)
    # Two batches — never one — and the loop still serves.
    assert tele.counter_value("serve.batches_total",
                              {"replica": "0"}) == 2
    np.testing.assert_allclose(rep.infer(a[:1]),
                               a[:1].sum(-1, keepdims=True) * 2.0,
                               rtol=1e-5, atol=1e-6)
    rep.stop()


def test_oversized_request_rejected(trained):
    _m, _v, x = trained
    tele = Telemetry(run_id="t_oversize")
    rep = _replica(trained, tele, replica_id="0")
    with pytest.raises(ValueError, match="largest bucket"):
        rep.submit(np.concatenate([x, x]))  # 32 rows > bucket 8
    rep.stop()


def test_deadline_expiry(trained):
    """A request whose deadline lapses while queued fails with
    DeadlineExceeded (counted) and never occupies a batch slot; later
    requests are unaffected."""
    _m, _v, x = trained
    tele = Telemetry(run_id="t_deadline")
    rep = _replica(trained, tele, replica_id="0", auto_start=False)
    stale = rep.submit(x[:1], deadline_s=0.05)
    time.sleep(0.15)
    fresh = rep.submit(x[1:2], deadline_s=30.0)
    rep.start()
    with pytest.raises(DeadlineExceeded):
        stale.result(10.0)
    out = fresh.result(10.0)
    np.testing.assert_allclose(out, _ref(trained, x[1:2]),
                               rtol=1e-5, atol=1e-6)
    assert tele.counter_value("serve.deadline_expired_total",
                              {"replica": "0"}) == 1
    rep.stop()


def test_backpressure_429_accounting(trained):
    """Admission past max_queue_rows raises Overloaded and counts one
    rejection; the admitted requests still complete."""
    _m, _v, x = trained
    tele = Telemetry(run_id="t_429")
    rep = _replica(trained, tele, replica_id="0", auto_start=False,
                   max_queue_rows=4)
    futs = [rep.submit(x[i:i + 1]) for i in range(4)]
    with pytest.raises(Overloaded):
        rep.submit(x[4:5])
    assert tele.counter_value(
        "serve.rejected_total",
        {"replica": "0", "reason": "backpressure"}) == 1
    rep.start()
    for fut in futs:
        fut.result(10.0)
    rep.stop()


# ---------------------------------------------------------------------------
# Live weight updates
# ---------------------------------------------------------------------------


def _clf_payload(lr=0.1):
    return serialize_torch_obj(
        ClassificationNet(n_classes=2), criterion="cross_entropy",
        optimizer="sgd", optimizer_params={"lr": lr}, input_shape=(10,),
    )


def test_live_weight_swap_exactness_single_server():
    """The puller's version-tagged pulls land a pushed update on the
    replica, and the SERVED parameters equal the server's — exactly —
    after the swap."""
    tele = Telemetry(run_id="t_weights")
    server = ParameterServer(_clf_payload(), telemetry=tele)
    http = ParamServerHttp(server, port=0).start()
    module = ClassificationNet(n_classes=2)
    x = np.random.default_rng(1).normal(0, 1, (8, 10)).astype(np.float32)
    _v0, params0 = server.slot.read()
    rep = InferenceReplica(module, params0, replica_id="0",
                           telemetry=tele, buckets=(8,), warm_input=x)
    puller = WeightPuller(rep, BinaryTransport(http.url, quant=None),
                          poll_s=0.02, telemetry=tele).start()
    try:
        grads = jax.tree.map(lambda a: np.ones_like(np.asarray(a)),
                             params0)
        server.push_gradients(grads, wait=True)
        deadline = time.monotonic() + 10.0
        while rep.params_version < 1 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert rep.params_version >= 1, "pushed weights never landed"
        _v, server_params = server.slot.read()
        out = rep.infer(x)
        ref = np.asarray(module.apply({"params": server_params}, x))
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)
        assert tele.counter_value("serve.weight_updates_total",
                                  {"replica": "0"}) >= 1
    finally:
        puller.stop()
        rep.stop()
        http.stop()
        server.stop()


def test_tier_pullers_land_push_on_every_replica_under_load():
    """``InferenceTier.start_pullers``: a push made while the tier is
    serving reaches EVERY replica, no request is lost across the swap,
    and each replica then serves exactly the pushed parameters."""
    tele = Telemetry(run_id="t_tier_push")
    server = ParameterServer(_clf_payload(), telemetry=tele)
    http = ParamServerHttp(server, port=0).start()
    module = ClassificationNet(n_classes=2)
    x = np.random.default_rng(2).normal(0, 1, (8, 10)).astype(np.float32)
    _v0, params0 = server.slot.read()
    tier = InferenceTier(module, params0, n_replicas=2, telemetry=tele,
                         buckets=(1, 8), warm_input=x[:1],
                         probe_interval_s=0.05)
    tier.start_pullers(lambda: BinaryTransport(http.url, quant=None),
                       poll_s=0.02)
    stop_load = threading.Event()
    served, errors = [], []

    def load():
        while not stop_load.is_set():
            try:
                served.append(tier.submit(x[:1], deadline_s=30.0))
            except Exception as e:  # noqa: BLE001 - asserted empty below
                errors.append(e)
            time.sleep(0.005)

    loader = threading.Thread(target=load, daemon=True)
    loader.start()
    try:
        grads = jax.tree.map(lambda a: np.ones_like(np.asarray(a)),
                             params0)
        server.push_gradients(grads, wait=True)
        pushed = server.slot.version
        deadline = time.monotonic() + 15.0
        while (any(r.params_version < pushed
                   for r in tier.replicas.values())
               and time.monotonic() < deadline):
            time.sleep(0.01)
        stop_load.set()
        loader.join(timeout=30)
        assert {rid: r.params_version >= pushed
                for rid, r in tier.replicas.items()} == {"0": True,
                                                         "1": True}
        assert not errors and served
        assert all(out.shape[0] == 1 for out in served)
        _v, server_params = server.slot.read()
        ref = np.asarray(module.apply({"params": server_params}, x))
        for replica in tier.replicas.values():
            np.testing.assert_allclose(replica.infer(x), ref,
                                       rtol=1e-5, atol=1e-6)
    finally:
        stop_load.set()
        tier.stop()
        http.stop()
        server.stop()


def test_weight_puller_uses_gateway_deltas():
    """A replica pointed at the FLEET GATEWAY gets per-tensor delta
    pulls (the ROADMAP item-1 follow-up): after the initial sync, a
    sparse push ships only the changed leaves — strictly fewer bytes
    than the first full-state delta — and the served params track the
    fleet exactly."""
    tele = Telemetry(run_id="t_gw_pull")
    fleet = ParamServerFleet(_clf_payload(), n_shards=2,
                             telemetry=tele).start()
    module = ClassificationNet(n_classes=2)
    x = np.random.default_rng(2).normal(0, 1, (8, 10)).astype(np.float32)
    # Host copy: the assembled tree's leaves live on scattered shard
    # devices; the replica re-pins, but the module.apply reference
    # below must see one placement.
    params0 = jax.tree.map(lambda a: np.asarray(a), fleet.assemble())
    rep = InferenceReplica(module, params0, replica_id="0",
                           telemetry=tele, buckets=(8,), warm_input=x)
    transport = BinaryTransport(fleet.gateway_url, quant=None)
    puller = WeightPuller(rep, transport, poll_s=0.02,
                          telemetry=tele).start()
    try:
        deadline = time.monotonic() + 10.0
        while puller.version < 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert puller._use_delta is True  # the gateway served /delta.bin
        bytes_full_sync = transport.stats["pull_bytes"]
        assert bytes_full_sync > 0
        from sparktorch_tpu.net import wire

        flat = dict(wire.flatten_tree(params0))
        hot_path = sorted(flat)[0]
        fleet.scatter_push(
            {hot_path: np.ones_like(np.asarray(flat[hot_path]))},
            wait=True)
        v_before = rep.params_version
        deadline = time.monotonic() + 10.0
        while rep.params_version == v_before \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        assert rep.params_version != v_before
        delta_bytes = transport.stats["pull_bytes"] - bytes_full_sync
        assert 0 < delta_bytes < bytes_full_sync
        out = rep.infer(x)
        host_params = jax.tree.map(lambda a: np.asarray(a),
                                   fleet.assemble())
        ref = np.asarray(module.apply({"params": host_params}, x))
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)
    finally:
        puller.stop()
        rep.stop()
        fleet.stop()


# ---------------------------------------------------------------------------
# Router: load-aware routing, eviction, re-admission
# ---------------------------------------------------------------------------


def test_router_least_outstanding_weighted_by_latency(trained):
    """Routing picks (outstanding+1) x p50: with equal outstanding, a
    replica whose scraped latency is 10x worse loses the pick; with a
    big enough backlog, even the fast one is passed over."""
    tele = Telemetry(run_id="t_route")
    r0 = _replica(trained, tele, replica_id="0")
    r1 = _replica(trained, tele, replica_id="1")
    router = Router(telemetry=tele)
    router.register(r0)
    router.register(r1)
    tele.observe("serve.request_latency_s", 0.5, labels={"replica": "0"})
    tele.observe("serve.request_latency_s", 0.05, labels={"replica": "1"})
    assert router._choose(set()) == "1"
    # Pile outstanding onto 1 until 0 wins despite worse latency.
    with router._lock:
        router._replicas["1"].outstanding = 20
    assert router._choose(set()) == "0"
    r0.stop()
    r1.stop()
    router.stop()


def test_router_reads_collector_scraped_latency(trained):
    """With a collector attached, routing weights come from the
    MERGED scraped snapshot (rank/host labels and all), through the
    sanctioned snapshot_histogram reader."""
    tele = Telemetry(run_id="t_route_scrape")

    class _FakeCollector:
        def merged_snapshot(self):
            return {"histograms": {
                "serve.request_latency_s{host=h,rank=0,replica=0}":
                    {"count": 10, "p50": 0.4},
                "serve.request_latency_s{host=h,rank=0,replica=1}":
                    {"count": 10, "p50": 0.02},
            }}

    r0 = _replica(trained, tele, replica_id="0")
    r1 = _replica(trained, tele, replica_id="1")
    router = Router(telemetry=tele, collector=_FakeCollector())
    router.register(r0)
    router.register(r1)
    assert router._choose(set()) == "1"
    r0.stop()
    r1.stop()
    router.stop()


def test_router_evicts_and_readmits(trained):
    """A dead replica is evicted on the failed hop (the request is
    re-routed, not dropped); once it comes back, the health probe
    re-admits it and traffic reaches it again."""
    _m, _v, x = trained
    tele = Telemetry(run_id="t_evict")
    policy = FtPolicy(restart=RestartPolicy(backoff_base_s=0.01,
                                            backoff_max_s=0.05))
    r0 = _replica(trained, tele, replica_id="0")
    r1 = _replica(trained, tele, replica_id="1")
    router = Router(ft_policy=policy, telemetry=tele,
                    probe_interval_s=0.05)
    router.register(r0)
    router.register(r1)
    # Bias the pick toward replica 1 (it looks fastest), THEN kill it:
    # the failed hop — not a background probe — must evict it, and the
    # same request must land on replica 0 instead of dropping.
    tele.observe("serve.request_latency_s", 0.5, labels={"replica": "0"})
    tele.observe("serve.request_latency_s", 0.01, labels={"replica": "1"})
    assert router._choose(set()) == "1"
    r1.kill()
    outs = [router.submit(x[:1], deadline_s=10.0) for _ in range(6)]
    assert all(o.shape == (1, 1) for o in outs)
    assert tele.counter_value("router.evictions_total",
                              {"replica": "1", "reason": "error"}) >= 1
    # Recovery: restart the replica loop; the probe re-admits.
    r1.start()
    deadline = time.monotonic() + 5.0
    while router.stats["1"]["evicted"] and time.monotonic() < deadline:
        router.check_health()
        time.sleep(0.02)
    assert not router.stats["1"]["evicted"]
    assert tele.counter_value("router.readmissions_total",
                              {"replica": "1"}) >= 1
    # Re-admitted replica genuinely serves again: with replica 0 gone,
    # the next request MUST land on it.
    r0.kill()
    out = router.submit(x[:1], deadline_s=10.0)
    np.testing.assert_allclose(out, _ref(trained, x[:1]),
                               rtol=1e-5, atol=1e-6)
    assert tele.counter_value("router.routed_total",
                              {"replica": "1"}) >= 1
    r0.stop()
    r1.stop()
    router.stop()


def test_router_heartbeat_deadline_evicts_wedged_replica(tmp_path):
    """The ft barrier-deadline signal: a handle that still answers
    alive() but whose heartbeat AGED OUT (wedged loop, vanished
    exporter) is evicted — the supervisor's alive-but-silent detector
    reused at the serving tier."""
    tele = Telemetry(run_id="t_hb_evict")

    class _WedgedHandle:
        replica_id = "3"
        telemetry = tele

        def alive(self):
            return True

    hb_dir = str(tmp_path)
    HeartbeatEmitter(hb_dir, rank=3).beat()  # one beat, then silence
    policy = FtPolicy(barrier=BarrierPolicy(deadline_s=0.2))
    router = Router(ft_policy=policy, heartbeat_dir=hb_dir,
                    telemetry=tele)
    router.register(_WedgedHandle())
    router.check_health()
    assert not router.stats["3"]["evicted"]  # beat still fresh
    time.sleep(0.35)
    router.check_health()
    assert router.stats["3"]["evicted"]
    assert tele.counter_value("router.evictions_total",
                              {"replica": "3", "reason": "health"}) == 1
    router.stop()


def test_chaos_slow_replica_site(trained):
    """ChaosConfig.slow_replica_s delays that replica's admissions
    (the straggler fault the load-aware router sheds around)."""
    _m, _v, x = trained
    tele = Telemetry(run_id="t_slow")
    rep = _replica(trained, tele, replica_id="0")
    with inject(ChaosConfig(slow_replica_s={0: 0.15}),
                telemetry=tele) as inj:
        t0 = time.perf_counter()
        rep.infer(x[:1])
        elapsed = time.perf_counter() - t0
    assert elapsed >= 0.15
    assert any(e["site"] == "serve.replica" and e.get("delay_s")
               for e in inj.events)
    rep.stop()


def test_tier_chaos_kill_zero_drops(trained):
    """The headline recovery contract: a seeded replica kill mid-load
    drops ZERO requests (the router re-routes them), the monitor
    restarts the replica, and the router re-admits it."""
    _m, variables, x = trained
    module = trained[0]
    tele = Telemetry(run_id="t_tier_kill")
    policy = FtPolicy(restart=RestartPolicy(backoff_base_s=0.02,
                                            backoff_max_s=0.1,
                                            max_restarts=3))
    tier = InferenceTier(module, variables["params"], n_replicas=2,
                         telemetry=tele, ft_policy=policy,
                         warm_input=x[:1], buckets=(1, 8),
                         probe_interval_s=0.05)
    n = 30
    try:
        # Deterministic victim: replica 0 carries a fat observed
        # latency, so the weighted pick sends the opening requests to
        # replica 1 — whose 4th admission is the seeded kill.
        tele.observe("serve.request_latency_s", 0.5,
                     labels={"replica": "0"})
        with inject(ChaosConfig(kill_replica_at={1: 4}),
                    telemetry=tele) as inj:
            outs = []
            for _ in range(n):
                outs.append(tier.submit(x[:1], deadline_s=15.0))
                time.sleep(0.01)
        kills = [e for e in inj.events if e["site"] == "serve.replica"]
        assert len(kills) == 1
        assert len(outs) == n  # zero dropped
        ref = _ref(trained, x[:1])
        for out in outs:
            np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)
        assert tele.counter_value("router.evictions_total",
                                  {"replica": "1",
                                   "reason": "error"}) >= 1
        deadline = time.monotonic() + 10.0
        while (tele.counter_value("router.readmissions_total",
                                  {"replica": "1"}) < 1
               and time.monotonic() < deadline):
            time.sleep(0.05)
        assert tele.counter_value("serve.replica_restarts_total",
                                  {"replica": "1"}) >= 1
        assert tele.counter_value("router.readmissions_total",
                                  {"replica": "1"}) >= 1
    finally:
        tier.stop()


# ---------------------------------------------------------------------------
# Tracing: the router -> replica -> batch waterfall
# ---------------------------------------------------------------------------


def test_traced_request_waterfall_crosses_router_and_replica(trained):
    """A sampled request yields ONE stitched tree: root `infer`
    (router), child `replica` hop (annotated with the replica id),
    and queue_wait/execute under the hop — the waterfall that says
    where a slow request spent its time."""
    _m, _v, x = trained
    tele = Telemetry(run_id="t_trace")
    tracer = tracer_for(tele)
    tracer.sample_rate = 1.0
    rep = _replica(trained, tele, replica_id="0")
    router = Router(telemetry=tele)
    router.register(rep)
    router.submit(x[:2])
    # The batch loop commits its spans right before the future
    # resolves; one poll keeps this unracy.
    deadline = time.monotonic() + 5.0
    names = set()
    while time.monotonic() < deadline:
        names = {s["name"] for s in tracer.spans}
        if {"infer", "replica", "queue_wait", "execute"} <= names:
            break
        time.sleep(0.01)
    assert {"infer", "replica", "queue_wait", "execute"} <= names, names
    trees = stitch_spans(tracer.spans)
    tree = next(t for t in trees if t["root"]["name"] == "infer")
    hop = next(c for c in tree["root"]["children"]
               if c["name"] == "replica")
    assert hop["ann"]["replica"] == "0"
    kids = {c["name"] for c in hop["children"]}
    assert {"queue_wait", "execute"} <= kids
    execute = next(c for c in hop["children"] if c["name"] == "execute")
    assert execute["ann"]["replica"] == "0"
    assert execute["ann"]["bucket"] in (1, 8)
    rep.stop()
    router.stop()

"""Per-request distributed RPC tracing (obs/rpctrace.py): context
minting/propagation, the wire header extension, span trees, critical
paths, fault behavior, collector stitching, and the timeline CLI.

(Named test_obs_rpc.py, NOT test_rpctrace.py: the tier-1 suite dies at
its wall-clock budget mid test_pipeline_parallel — anything
alphabetically later never scores.)
"""

import json
import os
import threading
import time

import numpy as np
import pytest

from sparktorch_tpu.net import wire
from sparktorch_tpu.obs import Telemetry, rpctrace
from sparktorch_tpu.obs.rpctrace import RpcTracer, SpanContext


def _tracer(tele=None, rate=1.0, **kw):
    return RpcTracer(tele or Telemetry(run_id="t"), sample_rate=rate, **kw)


# ---------------------------------------------------------------------------
# Contexts and the wire
# ---------------------------------------------------------------------------


def test_context_header_roundtrip():
    tr = _tracer()
    with tr.root_span("pull") as sp:
        ctx = sp.ctx
    assert len(ctx.trace_id) == 32 and len(ctx.span_id) == 16
    back = SpanContext.from_header(ctx.to_header())
    assert back.trace_id == ctx.trace_id
    assert back.span_id == ctx.span_id
    assert back.sampled is True
    child = ctx.child()
    assert child.trace_id == ctx.trace_id
    assert child.span_id != ctx.span_id
    # Malformed headers degrade to None, never raise.
    for bad in (None, "", "garbage", "a-b-c", "zz" * 16 + "-" + "f" * 16
                + "-01", ctx.trace_id + "-" + ctx.span_id):
        assert SpanContext.from_header(bad) is None


def test_wire_trace_extension_roundtrip_and_v1_byte_stability():
    tree = {"w": np.arange(12, dtype=np.float32).reshape(3, 4),
            "b": {"c": np.int32(9)}}
    plain = wire.frame_bytes(wire.encode(tree, version=5, run_tag=321))
    assert wire.frame_trace(plain) is None

    tr = _tracer()
    with tr.root_span("push") as sp:
        ctx = sp.ctx
    traced = wire.frame_bytes(
        wire.encode(tree, version=5, run_tag=321, trace=ctx))
    # run-tag and trace context COEXIST in one frame — the two
    # correlation keys must never clobber each other.
    assert wire.frame_run_tag(traced) == 321
    got = wire.frame_trace(traced)
    assert (got.trace_id, got.span_id, got.sampled) == \
        (ctx.trace_id, ctx.span_id, True)
    v, out = wire.decode(traced)
    assert v == 5
    np.testing.assert_array_equal(out["w"], tree["w"])
    # Exactly the extension's bytes on top; untraced and unsampled
    # frames stay byte-identical to the pre-trace wire.
    assert len(traced) == len(plain) + wire.TRACE_EXT_SIZE
    unsampled = SpanContext.from_parts(ctx.trace_id, ctx.span_id, False)
    assert wire.frame_bytes(
        wire.encode(tree, version=5, run_tag=321, trace=unsampled)
    ) == plain
    # A traced DELTA frame round-trips too.
    leaves = dict(wire.flatten_tree(tree))
    dframe = wire.frame_bytes(wire.encode(
        list(leaves.items()), version=5,
        leaf_versions={p: 2 for p in leaves}, trace=ctx))
    dv, dleaves, dvers = wire.decode_delta(dframe)
    assert dv == 5 and set(dvers.values()) == {2}
    assert wire.frame_trace(dframe).trace_id == ctx.trace_id


def test_trace_extension_truncation_rejected():
    tr = _tracer()
    with tr.root_span("push") as sp:
        ctx = sp.ctx
    traced = wire.frame_bytes(
        wire.encode({"a": np.zeros(2, np.float32)}, trace=ctx))
    # Cut inside the extension: both the peek and the decode must
    # fail loudly.
    torn = traced[:wire.HEADER_SIZE + 3]
    with pytest.raises(wire.WireError):
        wire.frame_trace(torn)
    with pytest.raises(wire.WireError):
        wire.decode(traced[:-1])


# ---------------------------------------------------------------------------
# Sampling, the SLO escape hatch, no-op children
# ---------------------------------------------------------------------------


def test_head_sampling_decides_recording():
    on = _tracer(rate=1.0)
    with on.root_span("pull") as sp:
        assert sp.ctx.sampled
    assert len(on.spans) == 1

    off = _tracer(rate=0.0)
    with off.root_span("pull") as sp:
        assert sp.ctx is not None and not sp.ctx.sampled
        with off.child_span("hop", sp.ctx) as child:
            assert child.ctx is None  # disabled — children of an
            # unsampled root never record
    assert off.spans == []

    disabled = _tracer(rate=-1.0)
    with disabled.root_span("pull") as sp:
        assert sp.ctx is None
    assert disabled.spans == []


def test_slo_escape_hatch_forces_slow_roots():
    tr = _tracer(rate=0.0, slo_s=0.01)
    with tr.root_span("pull") as sp:
        time.sleep(0.02)
    assert len(tr.spans) == 1
    rec = tr.spans[0]
    assert rec["forced"] is True and rec["name"] == "pull"
    assert tr.telemetry.counter_value("rpctrace.slo_forced_total") == 1
    # A fast unsampled root stays invisible.
    with tr.root_span("pull"):
        pass
    assert len(tr.spans) == 1


def test_span_error_status_and_counters():
    tr = _tracer(rate=1.0)
    with pytest.raises(RuntimeError):
        with tr.root_span("push") as sp:
            with tr.child_span("socket", sp.ctx):
                raise RuntimeError("boom")
    spans = {s["name"]: s for s in tr.spans}
    assert spans["push"]["status"] == "error"
    assert "boom" in spans["push"]["error"]
    assert spans["socket"]["status"] == "error"
    assert tr.telemetry.counter_value(
        "rpctrace.span_errors_total", labels={"kind": "client"}) == 1


def test_ring_bounded_and_resize():
    tr = _tracer(rate=1.0, buffer_size=4)
    for _ in range(7):
        with tr.root_span("op"):
            pass
    assert len(tr.spans) == 4
    assert tr.dropped == 3
    sec = tr.telemetry.snapshot()["sections"]["rpc_spans"]
    assert sec["n"] == 4 and sec["dropped"] == 3
    tr.resize(16)
    with tr.root_span("op"):
        pass
    assert len(tr.spans) == 5


# ---------------------------------------------------------------------------
# Stitching + critical path
# ---------------------------------------------------------------------------


def _span(trace, sid, parent, name, ts, dur, shard=None, kind="client",
          status="ok"):
    return {"trace_id": trace, "span_id": sid, "parent_id": parent,
            "name": name, "kind": kind, "ts": ts, "dur_s": dur,
            "status": status, "error": None, "forced": False,
            "ann": ({"shard": shard} if shard is not None else {})}


def test_stitch_and_critical_path_names_straggler():
    # root [0, 0.2]; fast hop [0.01, 0.03]; slow hop [0.01, 0.19]
    # whose serve child covers [0.02, 0.18] -> serve on shard 7 bounds.
    spans = [
        _span("t1", "r", None, "pull", 100.0, 0.2),
        _span("t1", "a", "r", "shard_pull", 100.01, 0.02, shard="0"),
        _span("t1", "b", "r", "shard_pull", 100.01, 0.18, shard="7"),
        _span("t1", "c", "b", "serve", 100.02, 0.16, shard="7",
              kind="server"),
    ]
    trees = rpctrace.stitch_spans(spans)
    assert len(trees) == 1
    t = trees[0]
    assert t["n_spans"] == 4 and t["wall_s"] == pytest.approx(0.2)
    crit = t["critical"]
    assert crit["name"] == "serve" and crit["shard"] == "7"
    assert crit["fraction"] == pytest.approx(0.8, abs=0.05)
    names = [e["name"] for e in rpctrace.critical_path(t["root"])]
    assert names == ["pull", "shard_pull", "serve"]


def test_stitch_orphans_and_span_dedup():
    spans = [
        _span("t2", "r", None, "pull", 10.0, 0.1),
        _span("t2", "x", "missing", "apply", 10.05, 0.01, kind="server"),
        _span("t2", "r", None, "pull", 10.0, 0.1),  # scraped twice
    ]
    trees = rpctrace.stitch_spans(spans)
    assert len(trees) == 1
    assert trees[0]["n_spans"] == 2  # dedup by span_id
    assert [o["name"] for o in trees[0]["orphans"]] == ["apply"]
    # A trace with ONLY orphans still renders (promoted root).
    only = rpctrace.stitch_spans(
        [_span("t3", "y", "gone", "serve", 5.0, 0.02)])
    assert only[0]["root"]["name"] == "serve"
    assert only[0]["root"].get("orphan_root") is True


def test_chrome_trace_export(tmp_path):
    tr = _tracer(rate=1.0)
    with tr.root_span("pull") as sp:
        with tr.child_span("serve", sp.ctx, kind="server", shard="1"):
            pass
    path = str(tmp_path / "rpc.trace.json")
    rpctrace.write_chrome_trace(path, tr.spans)
    with open(path) as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    assert len(events) == 2
    assert {e["ph"] for e in events} == {"X"}
    serve = next(e for e in events if e["name"] == "serve")
    assert serve["args"]["shard"] == "1"
    assert serve["dur"] >= 0


# ---------------------------------------------------------------------------
# Live propagation: single server, faults, sharded fan-out
# ---------------------------------------------------------------------------


@pytest.fixture
def clf_payload():
    from sparktorch_tpu import serialize_torch_obj
    from sparktorch_tpu.models import ClassificationNet

    return serialize_torch_obj(
        ClassificationNet(n_classes=2), criterion="cross_entropy",
        optimizer="sgd", optimizer_params={"lr": 1e-2},
        input_shape=(10,),
    )


def _zeros_like_params(server_or_fleet):
    import jax

    tree = (server_or_fleet.assemble()
            if hasattr(server_or_fleet, "assemble")
            else server_or_fleet.slot.read()[1])
    return jax.tree.map(lambda a: np.zeros_like(np.asarray(a)), tree)


def test_single_server_full_vertical(clf_payload):
    """A traced push carries the context on the frame and comes back
    as ONE tree: push -> {encode, socket, serve -> {decode,
    queue_wait, apply}}; a traced pull as pull -> serve -> render."""
    from sparktorch_tpu.net.transport import BinaryTransport
    from sparktorch_tpu.serve.param_server import (
        ParameterServer,
        ParamServerHttp,
    )

    tele = Telemetry(run_id="rpc_single")
    tracer = rpctrace.tracer_for(tele)
    tracer.sample_rate = 1.0
    server = ParameterServer(clf_payload, telemetry=tele)
    http = ParamServerHttp(server, port=0).start()
    try:
        t = BinaryTransport(http.url, telemetry=tele)
        t.push(_zeros_like_params(server))
        server.drain()
        assert t.pull(-1) is not None
        time.sleep(0.1)  # handler threads close their serve spans
        trees = {tr["root"]["name"]: tr
                 for tr in rpctrace.stitch_spans(tracer.spans)}
        assert set(trees) == {"push", "pull"}

        def names(node, acc):
            acc.append(node["name"])
            for c in node["children"]:
                names(c, acc)
            return acc

        push_names = names(trees["push"]["root"], [])
        for expect in ("encode", "socket", "serve", "decode",
                       "queue_wait", "apply"):
            assert expect in push_names, push_names
        pull_names = names(trees["pull"]["root"], [])
        assert "serve" in pull_names and "render" in pull_names
        # Cross-pipeline sanity: the serve span and the request both
        # happened (span and histogram COUNTS reconcile per shard in
        # test_sharded_pulls_stitch_one_tree_each_...).
        assert trees["push"]["wall_s"] > 0
        t.close()
    finally:
        http.stop()
        server.stop()


def test_chaos_dropped_connection_mid_traced_push(clf_payload):
    """A connection dropped under a traced push: the root span closes
    with error status (no leak — the next request records normally)."""
    from sparktorch_tpu.ft import ChaosConfig, inject
    from sparktorch_tpu.net.transport import BinaryTransport, TransportError
    from sparktorch_tpu.serve.param_server import (
        ParameterServer,
        ParamServerHttp,
    )

    tele = Telemetry(run_id="rpc_drop")
    tracer = rpctrace.tracer_for(tele)
    tracer.sample_rate = 1.0
    server = ParameterServer(clf_payload, telemetry=tele)
    http = ParamServerHttp(server, port=0).start()
    try:
        t = BinaryTransport(http.url, telemetry=tele, retries=1)
        zeros = _zeros_like_params(server)
        with inject(ChaosConfig(drop_connections=1, seed=0)):
            with pytest.raises(TransportError):
                t.push(zeros)
        failed = [s for s in tracer.spans if s["name"] == "push"]
        assert len(failed) == 1
        assert failed[0]["status"] == "error"
        assert "TransportError" in failed[0]["error"]
        sockets = [s for s in tracer.spans if s["name"] == "socket"]
        assert sockets and sockets[-1]["status"] == "error"
        # No leaked open-span state: the next push records a fresh,
        # healthy tree under a NEW trace id.
        t.push(zeros)
        server.drain()
        ok = [s for s in tracer.spans
              if s["name"] == "push" and s["status"] == "ok"]
        assert len(ok) == 1
        assert ok[0]["trace_id"] != failed[0]["trace_id"]
        t.close()
    finally:
        http.stop()
        server.stop()


def test_sharded_degraded_hop_visible_in_tree(clf_payload):
    """A shard dead inside the grace window: its hop stays IN the
    request tree, closed with error status and marked degraded."""
    from sparktorch_tpu.net.sharded import ShardedTransport
    from sparktorch_tpu.serve.fleet import ParamServerFleet

    tele = Telemetry(run_id="rpc_degrade")
    tracer = rpctrace.tracer_for(tele)
    tracer.sample_rate = 1.0
    fleet = ParamServerFleet(clf_payload, n_shards=2, telemetry=tele,
                             restart_shards=False).start()
    try:
        t = ShardedTransport(fleet, telemetry=tele, grace_s=30.0)
        snap = t.pull(-1)
        assert snap is not None
        have = snap[0]
        victim = sorted(fleet.urls())[0]
        fleet.kill_shard(victim)  # no monitor: stays dark
        t.pull(have)  # all-304 + one dead shard -> degraded sweep
        time.sleep(0.05)
        trees = [tr for tr in rpctrace.stitch_spans(tracer.spans)
                 if tr["root"]["name"] == "pull"]
        degraded = trees[0]  # newest first
        hops = {(c["ann"].get("shard")): c
                for c in degraded["root"]["children"]}
        assert hops[victim]["status"] == "error"
        assert hops[victim]["ann"].get("degraded") is True
        other = next(s for s in hops if s != victim)
        assert hops[other]["status"] == "ok"
        assert t.stats["shard_failures"] >= 1
        t.close()
    finally:
        fleet.stop()


def test_slow_shard_named_critical_and_collector_stitch(clf_payload):
    """The headline path: a seeded slow shard bounds a traced sharded
    pull; the collector's stitched output and /gang name it."""
    from sparktorch_tpu.ft import ChaosConfig, inject
    from sparktorch_tpu.net.sharded import ShardedTransport
    from sparktorch_tpu.obs import FleetCollector
    from sparktorch_tpu.serve.fleet import ParamServerFleet

    tele = Telemetry(run_id="rpc_slow")
    tracer = rpctrace.tracer_for(tele)
    tracer.sample_rate = 1.0
    fleet = ParamServerFleet(clf_payload, n_shards=2, telemetry=tele).start()
    collector = None
    try:
        t = ShardedTransport(fleet, telemetry=tele)
        snap = t.pull(-1)
        have = snap[0]
        t.push(_zeros_like_params(fleet))
        fleet.drain()
        slow = sorted(fleet.urls())[1]
        with inject(ChaosConfig(slow_shard_s={slow: 0.08}, seed=0)):
            snap = t.pull(have)
        assert snap is not None
        time.sleep(0.05)
        collector = FleetCollector.for_fleet(fleet, poll_interval_s=0)
        collector.poll()
        traces = collector.rpc_traces()
        slow_pulls = [tr for tr in traces
                      if tr["root"]["name"] == "pull"
                      and tr["wall_s"] >= 0.06]
        assert slow_pulls, [(tr["root"]["name"], tr["wall_s"])
                            for tr in traces]
        crit = slow_pulls[0]["critical"]
        assert str(crit["shard"]) == slow, crit
        gang = collector.gang_view()
        assert gang["rpc"]["n_traces"] >= 2
        named = [x for x in gang["rpc"]["traces"]
                 if str((x.get("critical") or {}).get("shard")) == slow]
        assert named
        t.close()
    finally:
        if collector is not None:
            collector.stop()
        fleet.stop()


def test_sharded_pulls_stitch_one_tree_each_and_match_wire_histogram(
        clf_payload):
    """Every sampled sharded pull stitches to exactly ONE tree, every
    shard's serve hop is in it, and the serve spans are the same
    request population the per-shard ``wire_latency_s`` histogram
    counted — two pipelines, one set of requests."""
    from sparktorch_tpu.net.sharded import ShardedTransport
    from sparktorch_tpu.serve.fleet import ParamServerFleet

    n_shards, n_pulls = 2, 4
    tele = Telemetry(run_id="rpc_reconcile")
    tracer = rpctrace.tracer_for(tele)
    tracer.sample_rate = 1.0
    tracer.resize(4096)
    fleet = ParamServerFleet(clf_payload, n_shards=n_shards,
                             telemetry=tele).start()
    try:
        t = ShardedTransport(fleet, telemetry=tele, run_id=tele.run_id)
        zeros = _zeros_like_params(fleet)
        have = -1
        for _ in range(n_pulls):
            t.push(zeros)  # advances every leaf, so every shard serves
            fleet.drain()
            snap = t.pull(have)
            assert snap is not None
            have = snap[0]
        t.close()
        time.sleep(0.1)  # handler threads close their serve spans
        spans = tracer.spans
        pulls = [tr for tr in rpctrace.stitch_spans(spans)
                 if tr["root"]["name"] == "pull"]
        assert len(pulls) == n_pulls
        assert {tr["root"]["status"] for tr in pulls} == {"ok"}

        def shards_served(node, acc):
            if node["name"] == "serve":
                acc.add(str(node["ann"].get("shard")))
            for c in node["children"]:
                shards_served(c, acc)
            return acc

        shard_ids = {str(s) for s in fleet.urls()}
        assert len(shard_ids) == n_shards
        for tr in pulls:
            assert shards_served(tr["root"], set()) == shard_ids
        for sid in shard_ids:
            serves = [s for s in spans if s["name"] == "serve"
                      and s["ann"].get("route") == "/delta.bin"
                      and str(s["ann"].get("shard")) == sid]
            hist = tele.histogram("param_server.wire_latency_s",
                                  labels={"route": "/delta.bin",
                                          "shard": sid})
            assert len(serves) == hist["count"] == n_pulls
    finally:
        fleet.stop()


def test_unsampled_sharded_pull_records_nothing(clf_payload):
    """An UNSAMPLED sharded request must propagate the root's 'no'
    to every shard hop: zero spans recorded, and in particular no
    per-shard transport minting an independent root (which would
    fill the ring with shard-level 'requests' and roll its own
    sampling dice per hop)."""
    from sparktorch_tpu.net.sharded import ShardedTransport
    from sparktorch_tpu.serve.fleet import ParamServerFleet

    tele = Telemetry(run_id="rpc_unsampled")
    tracer = rpctrace.tracer_for(tele)
    tracer.sample_rate = 0.0  # enabled, never samples
    fleet = ParamServerFleet(clf_payload, n_shards=2,
                             telemetry=tele).start()
    try:
        t = ShardedTransport(fleet, telemetry=tele)
        assert t.pull(-1) is not None
        t.push(_zeros_like_params(fleet))
        fleet.drain()
        time.sleep(0.05)
        assert tracer.spans == [], [s["name"] for s in tracer.spans]
        t.close()
    finally:
        fleet.stop()


# ---------------------------------------------------------------------------
# Collector HA tail mode (fallback JSONL)
# ---------------------------------------------------------------------------


def test_collector_fallback_jsonl_serves_peer_sink(tmp_path):
    from sparktorch_tpu.obs import FleetCollector

    sink = str(tmp_path / "primary_sink.jsonl")
    from sparktorch_tpu.obs.sinks import write_jsonl

    write_jsonl(sink, [{
        "kind": "gang_snapshot", "run_id": "primary-run", "ts": 123.0,
        "ranks": {"0": {"ok": True, "run_id": "r0"}},
        # The real sink record carries the unioned heartbeat table
        # (FleetCollector.poll writes it alongside merged_snapshot).
        "heartbeats": {"n_ranks": 2, "step_skew": 3,
                       "ranks": {"0": {"alive": True, "step": 10}}},
        "sections": {
            "xprof_gang": {"steps": [], "n_ranks": 1},
            "rpc_traces": {"n_traces": 1, "traces": [
                {"trace_id": "abc", "root": {"name": "pull"},
                 "wall_s": 0.5,
                 "critical": {"name": "serve", "shard": "1"}}]},
        },
    }])
    # Secondary: every target dark, peer sink as fallback.
    secondary = FleetCollector({"0": "http://127.0.0.1:1"},
                               poll_interval_s=0,
                               scrape_timeout_s=0.2,
                               fallback_jsonl=sink)
    secondary.poll()  # scrape fails -> degraded
    gang = secondary.gang_view()
    assert gang["source"] == "fallback_jsonl"
    assert gang["run_id"] == "primary-run"
    assert gang["heartbeats"]["step_skew"] == 3
    assert gang["xprof"]["n_ranks"] == 1
    assert gang["rpc"]["traces"][0]["critical"]["shard"] == "1"
    assert gang["fallback_age_s"] is not None
    assert secondary.telemetry.counter_value(
        "collector.fallback_serves_total") >= 1
    secondary.stop()


def test_collector_fallback_ignored_once_live(tmp_path):
    """A collector that HAS scraped serves live data even when every
    target later fails — fallback is for the never-scraped secondary,
    not a stale override of degraded-but-known state."""
    import http.server

    from sparktorch_tpu.obs import FleetCollector
    from sparktorch_tpu.obs.sinks import write_jsonl

    class H(http.server.BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_GET(self):
            body = json.dumps({"run_id": "live", "counters": {},
                               "gauges": {}, "histograms": {},
                               "spans": {}, "info": {}}).encode()
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    httpd = http.server.ThreadingHTTPServer(("127.0.0.1", 0), H)
    thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    thread.start()
    sink = str(tmp_path / "peer.jsonl")
    write_jsonl(sink, [{"kind": "gang_snapshot", "run_id": "peer",
                        "ts": 1.0, "ranks": {}}])
    coll = FleetCollector(
        {"0": f"http://127.0.0.1:{httpd.server_address[1]}"},
        poll_interval_s=0, fallback_jsonl=sink)
    try:
        coll.poll()
        httpd.shutdown()
        httpd.server_close()
        coll.poll()  # now fails; last-good keeps serving
        gang = coll.gang_view()
        assert gang["source"] == "live"
        assert gang["ranks"]["0"]["scrapes"] == 1
    finally:
        coll.stop()


def test_collector_fallback_unreadable_file_degrades():
    from sparktorch_tpu.obs import FleetCollector

    coll = FleetCollector({"0": "http://127.0.0.1:1"},
                          poll_interval_s=0, scrape_timeout_s=0.2,
                          fallback_jsonl="/nonexistent/sink.jsonl")
    coll.poll()
    gang = coll.gang_view()  # no crash; empty live view
    assert gang["source"] == "live"
    coll.stop()


# ---------------------------------------------------------------------------
# timeline --rpc
# ---------------------------------------------------------------------------


def test_timeline_rpc_from_telemetry_dump(tmp_path, capsys):
    from sparktorch_tpu.obs import timeline

    tele = Telemetry(run_id="rpc_cli")
    tr = rpctrace.tracer_for(tele)
    tr.sample_rate = 1.0
    with tr.root_span("pull") as sp:
        with tr.child_span("shard_pull", sp.ctx, shard="3") as hop:
            time.sleep(0.02)
            with tr.child_span("serve", hop.ctx, kind="server",
                               shard="3"):
                time.sleep(0.01)
    dump = str(tmp_path / "run.jsonl")
    tele.dump(dump)
    rc = timeline.main(["--rpc", dump])
    out = capsys.readouterr().out
    assert rc == 0
    assert "bound by" in out and "shard 3" in out
    assert "pull" in out
    # Critical-path spans are starred in the waterfall (the path
    # entries carry span_ids precisely so renderers can do this).
    starred = [ln for ln in out.splitlines() if ln.startswith(" *")]
    assert starred, out
    assert any("serve" in ln for ln in starred), starred

    rc = timeline.main(["--rpc", dump, "--json"])
    out = capsys.readouterr().out
    doc = json.loads(out)
    assert doc[0]["trace_id"]

    # a dump with no spans
    empty = str(tmp_path / "empty.jsonl")
    Telemetry(run_id="none").dump(empty)
    assert timeline.main(["--rpc", empty]) == 1
    capsys.readouterr()
    # flag combinations are rejected
    assert timeline.main(["--rpc", "--gang", dump]) == 2
    capsys.readouterr()


def test_timeline_rpc_from_collector_sink(tmp_path, capsys):
    """A collector sink carries the already-stitched rpc_traces
    section — timeline must prefer it over re-stitching."""
    from sparktorch_tpu.obs import timeline
    from sparktorch_tpu.obs.sinks import write_jsonl

    spans = [
        _span("t9", "r", None, "pull", 50.0, 0.1),
        _span("t9", "s", "r", "serve", 50.01, 0.08, shard="2",
              kind="server"),
    ]
    stitched = rpctrace.stitch_spans(spans)
    sink = str(tmp_path / "collector.jsonl")
    write_jsonl(sink, [{"kind": "gang_snapshot", "ts": 1.0,
                        "sections": {"rpc_traces": {
                            "n_traces": 1, "traces": stitched}}}])
    rc = timeline.main(["--rpc", sink])
    out = capsys.readouterr().out
    assert rc == 0
    assert "shard 2" in out and "bound by: serve" in out

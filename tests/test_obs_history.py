"""Retained observability: the metrics-history tier (bounded rings,
rate / windowed-percentile / delta queries, JSONL spill+reconstruct),
declarative SLO alerting (threshold / sustained / burn-rate, latched
episodes, collector + controller wiring), and flight-recorder
postmortems — plus the satellites that ride this PR: the
percentile-outside-the-lock telemetry fix, the collector's
rpc_traces cap and stale-scrape accounting, and ``timeline --follow``.

The derived-query tests are GOLDEN: scripted (ts, value) sequences
with hand-computed expectations, no wall-clock dependence — history
timestamps come from the snapshots, never from append-time clocks.
"""

import json
import os
import threading
import time

import pytest

from sparktorch_tpu.obs import (
    AlertManager,
    AlertRule,
    FleetCollector,
    FlightRecorder,
    MetricsHistory,
    Telemetry,
    collect_postmortem,
    read_postmortem,
    wall_ts,
)
from sparktorch_tpu.obs.blackbox import events_from_snapshot


def _digest(p99, count=1, p50=None):
    return {"count": count, "sum": 0.0, "mean": 0.0, "min": 0.0,
            "max": p99, "p50": p50 if p50 is not None else p99,
            "p95": p99, "p99": p99}


def _sweep(ts, counters=None, gauges=None, hists=None):
    return {"ts": ts, "counters": counters or {}, "gauges": gauges or {},
            "histograms": hists or {}}


# ---------------------------------------------------------------------------
# MetricsHistory: golden derived queries
# ---------------------------------------------------------------------------


def test_history_rate_and_delta_golden():
    h = MetricsHistory(retention=16)
    # counter: 0, 4, 10, 10, 18 at ts 100..104 -> total increase 18
    for ts, v in [(100, 0), (101, 4), (102, 10), (103, 10), (104, 18)]:
        h.append(_sweep(float(ts), counters={"req_total{rank=0}": float(v)}))
    # whole retention: 18 increase over 4s
    assert h.rate("req_total") == pytest.approx(18 / 4)
    # windowed: points at ts >= 102 -> increase 8 over 2s
    assert h.rate("req_total", window_s=2.0) == pytest.approx(8 / 2)
    # delta since ts=101: latest point at-or-before 101 is (101, 4)
    assert h.delta_since("req_total", 101.0) == pytest.approx(14.0)
    # delta since before retention start: full increase
    assert h.delta_since("req_total", 0.0) == pytest.approx(18.0)
    # a single point has no rate
    h2 = MetricsHistory()
    h2.append(_sweep(1.0, counters={"c": 5.0}))
    assert h2.rate("c") is None


def test_history_rate_survives_counter_reset():
    h = MetricsHistory()
    # 10, 14, 2, 5: the drop to 2 is a restart — increase is
    # 4 (10->14) + 2 (post-reset value) + 3 (2->5) = 9 over 3s.
    for ts, v in [(0, 10), (1, 14), (2, 2), (3, 5)]:
        h.append(_sweep(float(ts), counters={"c": float(v)}))
    assert h.rate("c") == pytest.approx(9 / 3)
    assert h.delta_since("c", 0.0) == pytest.approx(9.0)


def test_history_windowed_percentile_of_percentiles_golden():
    h = MetricsHistory()
    # per-sweep p99 digests: 10, 20, 30, 40, 50ms at ts 0..4
    for i, p in enumerate([0.010, 0.020, 0.030, 0.040, 0.050]):
        h.append(_sweep(float(i), hists={"lat_s{shard=2}": _digest(p)}))
    # window 2s back from newest ts (4): sweeps at ts 2, 3, 4
    assert h.percentile_over("lat_s", 100, {"shard": "2"},
                             window_s=2.0) == pytest.approx(0.050)
    assert h.percentile_over("lat_s", 0, {"shard": "2"},
                             window_s=2.0) == pytest.approx(0.030)
    # median over the full retention
    assert h.percentile_over("lat_s", 50, {"shard": "2"}) == \
        pytest.approx(0.030)
    # unknown field -> None (no signal, not zero)
    assert h.percentile_over("lat_s", 99, {"shard": "2"},
                             field="p999") is None


def test_history_retention_bound_and_label_subset():
    h = MetricsHistory(retention=4)
    for i in range(10):
        h.append(_sweep(float(i), counters={"c{host=a,rank=3}": float(i)}))
    pts = h.series("c")
    assert len(pts) == 4 and pts[0][0] == 6.0  # oldest evicted
    # label SUBSET match: extra host label on the series is fine
    assert h.latest("c", {"rank": "3"}) == 9.0
    # a wrong label value does not match
    assert h.latest("c", {"rank": "4"}) is None
    # most-points-wins across several matching series
    h.append(_sweep(10.0, counters={"c{rank=4}": 100.0}))
    assert h.latest("c") == 9.0  # the 4-point series beats the 1-point


def test_history_spill_and_reconstruct(tmp_path):
    spill = str(tmp_path / "spill.jsonl")
    h = MetricsHistory(retention=8, spill_jsonl=spill)
    for i in range(5):
        h.append(_sweep(float(i), counters={"c": float(i * 2)},
                        hists={"lat_s": _digest(0.01 * (i + 1))}))
    rebuilt = MetricsHistory.from_jsonl(spill)
    assert rebuilt.rate("c") == h.rate("c") == pytest.approx(2.0)
    assert rebuilt.percentile_over("lat_s", 100) == pytest.approx(0.05)
    # collector-sink-shaped records (gang_snapshot) reconstruct too
    sink = str(tmp_path / "sink.jsonl")
    with open(sink, "w") as f:
        for i in range(4):
            f.write(json.dumps({"kind": "gang_snapshot", "ts": float(i),
                                "counters": {"x": float(i)}}) + "\n")
        f.write(json.dumps({"kind": "other", "ts": 9.0,
                            "counters": {"x": 99.0}}) + "\n")
    rebuilt2 = MetricsHistory.from_jsonl(sink)
    assert rebuilt2.rate("x") == pytest.approx(1.0)
    assert rebuilt2.latest("x") == 3.0  # non-sweep kinds skipped


def test_history_query_dispatch_and_errors():
    h = MetricsHistory()
    for i in range(3):
        h.append(_sweep(float(i), counters={"c": float(i)}))
    assert h.query("rate", "c")["value"] == pytest.approx(1.0)
    assert h.query("latest", "c")["value"] == 2.0
    assert h.query("delta", "c", since_ts=0.0)["value"] == 2.0
    assert h.query("series", "c")["points"] == [[0.0, 0.0], [1.0, 1.0],
                                                [2.0, 2.0]]
    with pytest.raises(ValueError):
        h.query("pctile", "c")  # q missing
    with pytest.raises(ValueError):
        h.query("delta", "c")  # since_ts missing
    with pytest.raises(ValueError):
        h.query("nope", "c")


# ---------------------------------------------------------------------------
# Alert rules: forms, latching, episodes
# ---------------------------------------------------------------------------


def test_alert_threshold_fires_and_resolves_with_episodes():
    h = MetricsHistory()
    tele = Telemetry(run_id="t")
    am = AlertManager(h, [AlertRule(name="g", metric="v",
                                    threshold=5.0)], telemetry=tele)
    seq = [3.0, 7.0, 8.0, 2.0, 9.0]
    transitions = []
    am.subscribe(lambda e: transitions.append((e["event"], e["episode"])))
    for i, v in enumerate(seq):
        h.append(_sweep(float(i), gauges={"v": v}))
        am.evaluate(ts=float(i))
    # fired at 7, latched through 8, resolved at 2, re-fired at 9:
    # two EPISODES, one callback per transition (never per sweep).
    assert transitions == [("fired", 1), ("resolved", 1), ("fired", 2)]
    assert tele.counter_value("alerts.fired_total",
                              labels={"rule": "g"}) == 2
    assert tele.counter_value("alerts.resolved_total",
                              labels={"rule": "g"}) == 1
    assert am.doc()["rules"]["g"]["episodes"] == 2
    assert am.active() == ["g"]


def test_alert_sustained_needs_consecutive_sweeps():
    h = MetricsHistory()
    am = AlertManager(h, [AlertRule(name="s", metric="v", kind="sustained",
                                    threshold=1.0, for_sweeps=3)],
                      telemetry=Telemetry(run_id="t"))
    # breach, breach, CLEAN, breach, breach, breach -> fires only at
    # the third consecutive breach.
    fired_at = []
    for i, v in enumerate([2.0, 2.0, 0.5, 2.0, 2.0, 2.0]):
        h.append(_sweep(float(i), gauges={"v": v}))
        for e in am.evaluate(ts=float(i)):
            fired_at.append((i, e["event"]))
    assert fired_at == [(5, "fired")]


def test_alert_burn_rate_golden_and_no_signal():
    h = MetricsHistory()
    tele = Telemetry(run_id="t")
    rule = AlertRule(name="burn", metric="bad", kind="burn_rate",
                     total_metric="total", slo=0.01, burn_factor=2.0,
                     window_s=10.0)
    am = AlertManager(h, [rule], telemetry=tele)
    # bad rate 1/s, total rate 40/s -> fraction 0.025, burn 2.5 > 2.
    for i in range(4):
        h.append(_sweep(float(i), counters={"bad": float(i),
                                            "total": float(i * 40)}))
    events = am.evaluate(ts=3.0)
    assert [e["event"] for e in events] == ["fired"]
    assert am.doc()["rules"]["burn"]["value"] == pytest.approx(2.5)
    # absent series: no signal, never a breach
    h2 = MetricsHistory()
    am2 = AlertManager(h2, [rule], telemetry=tele)
    assert am2.evaluate(ts=0.0) == []
    # bad ctor configs refused
    with pytest.raises(ValueError):
        AlertRule(name="x", metric="m", kind="burn_rate", slo=0.0,
                  total_metric="t")
    with pytest.raises(ValueError):
        AlertRule(name="x", metric="m", kind="nope")
    with pytest.raises(ValueError):
        AlertManager(h2, [rule, rule])  # duplicate names


def test_alert_subscriber_exception_degrades():
    h = MetricsHistory()
    tele = Telemetry(run_id="t")
    am = AlertManager(h, [AlertRule(name="g", metric="v",
                                    threshold=0.5)], telemetry=tele)

    def bad(_):
        raise RuntimeError("boom")

    seen = []
    am.subscribe(bad)
    am.subscribe(lambda e: seen.append(e["alert"]))
    h.append(_sweep(0.0, gauges={"v": 1.0}))
    am.evaluate(ts=0.0)
    assert seen == ["g"]  # later subscribers still ran
    assert tele.counter_value("alerts.subscriber_errors_total",
                              labels={"rule": "g"}) == 1


# ---------------------------------------------------------------------------
# Collector wiring: history append per sweep, /history, /gang, fallback
# ---------------------------------------------------------------------------


def _exporter(tele):
    from sparktorch_tpu.native.gang import GangMetricsExporter

    return GangMetricsExporter(telemetry=tele, port=0).start()


def test_collector_history_alerts_and_http_routes():
    from sparktorch_tpu.obs import scrape_json

    rank_tele = Telemetry(run_id="rank0")
    exp = _exporter(rank_tele)
    rules = [AlertRule(name="hot", metric="lat_s", field="p99",
                       kind="sustained", threshold=0.1, for_sweeps=2)]
    collector = FleetCollector({0: exp.url}, poll_interval_s=0,
                               alert_rules=rules)
    collector.start(poll_loop=False)
    try:
        for i in range(3):
            rank_tele.counter("req_total", 4)
            rank_tele.observe("lat_s", 0.3)
            collector.poll()
        # /gang carries the judgment layer
        gang = scrape_json(collector.url + "/gang")
        assert gang["alerts"]["active"] == ["hot"]
        assert gang["alerts"]["rules"]["hot"]["episodes"] == 1
        assert gang["history"]["sweeps"] == 3
        # /history describe + derived queries over HTTP
        desc = scrape_json(collector.url + "/history")
        assert desc["source"] == "live" and desc["sweeps"] == 3
        rate = scrape_json(collector.url +
                           "/history?name=req_total&query=rate"
                           "&labels=rank:0")
        assert rate["value"] is not None and rate["value"] > 0
        pct = scrape_json(collector.url +
                          "/history?name=lat_s&query=pctile&q=100"
                          "&field=p99&labels=rank:0")
        assert pct["value"] == pytest.approx(0.3)
        # unknown query -> 400
        from sparktorch_tpu.obs import ScrapeError

        with pytest.raises(ScrapeError):
            scrape_json(collector.url + "/history?name=x&query=bogus")
    finally:
        collector.stop()
        exp.stop()


def test_history_http_golden_against_hand_computed():
    """/history answers == hand-computed values on a SCRIPTED metric
    sequence: the history is fed explicit timestamps through the
    Python API, then queried through the HTTP route dispatch — no
    wall-clock dependence anywhere."""
    rank_tele = Telemetry(run_id="rank0")
    exp = _exporter(rank_tele)
    collector = FleetCollector({0: exp.url}, poll_interval_s=0)
    try:
        # scripted: counter 0,6,12 at ts 10,12,14 -> rate 3/s;
        # per-sweep p99 5,7,9ms -> windowed max 9ms.
        for ts, c, p in [(10.0, 0.0, 0.005), (12.0, 6.0, 0.007),
                         (14.0, 12.0, 0.009)]:
            collector.history.append(_sweep(
                ts, counters={"req_total": c},
                hists={"lat_s": _digest(p)}))
        code, doc = collector._handle_history(
            {"name": "req_total", "query": "rate"})
        assert code == 200 and doc["value"] == pytest.approx(3.0)
        code, doc = collector._handle_history(
            {"name": "req_total", "query": "delta", "since_ts": "12.0"})
        assert code == 200 and doc["value"] == pytest.approx(6.0)
        code, doc = collector._handle_history(
            {"name": "lat_s", "query": "pctile", "q": "100",
             "field": "p99", "window_s": "2.0"})
        assert code == 200 and doc["value"] == pytest.approx(0.009)
        code, doc = collector._handle_history(
            {"name": "lat_s", "query": "series", "field": "p99"})
        assert code == 200
        assert doc["points"] == [[10.0, 0.005], [12.0, 0.007],
                                 [14.0, 0.009]]
        code, doc = collector._handle_history({"name": "x",
                                               "query": "nope"})
        assert code == 400
    finally:
        collector.stop()
        exp.stop()


def test_slow_shard_fires_hot_shard_rule_once_and_control_stays_silent():
    """Against a live 2-shard fleet, a seeded slow shard breaches the
    sustained p99 rule on the CLIENT hop series
    (``sharded.shard_pull_latency_s``: the injected delay lands before
    the serve handler's clock, so the server-side series cannot see
    it): one episode, fired within the rule's window counted in
    sweeps, shown in ``/gang``. The identical loop with no chaos fires
    nothing."""
    import contextlib

    import jax
    import numpy as np

    from sparktorch_tpu import serialize_torch_obj
    from sparktorch_tpu.ft import ChaosConfig, inject
    from sparktorch_tpu.models import ClassificationNet
    from sparktorch_tpu.net.sharded import ShardedTransport
    from sparktorch_tpu.obs import scrape_json
    from sparktorch_tpu.serve.fleet import ParamServerFleet

    payload = serialize_torch_obj(
        ClassificationNet(n_classes=2), criterion="cross_entropy",
        optimizer="sgd", optimizer_params={"lr": 1e-2}, input_shape=(10,))
    n_pulls, for_sweeps, delay_s = 4, 2, 0.3

    def leg(chaos):
        tele = Telemetry(run_id="hot_shard")
        fleet = ParamServerFleet(payload, n_shards=2,
                                 telemetry=tele).start()
        slow = sorted(fleet.urls())[1]
        rules = [AlertRule(name="hot_shard_p99",
                           metric="sharded.shard_pull_latency_s",
                           labels={"shard": str(slow)}, kind="sustained",
                           field="p99", op=">", threshold=delay_s / 2,
                           for_sweeps=for_sweeps)]
        collector = FleetCollector.for_fleet(fleet, poll_interval_s=0,
                                             alert_rules=rules)
        collector.start(poll_loop=False)
        first_breach = fired = None
        try:
            t = ShardedTransport(fleet, telemetry=tele)
            zeros = jax.tree.map(lambda a: np.zeros_like(np.asarray(a)),
                                 fleet.assemble())
            have = -1
            ctx = (inject(ChaosConfig(seed=7,
                                      slow_shard_s={slow: delay_s}),
                          telemetry=tele)
                   if chaos else contextlib.nullcontext())
            with ctx:
                for sweep in range(n_pulls):
                    t.push(zeros)
                    fleet.drain()
                    snap = t.pull(have)
                    have = snap[0] if snap is not None else have
                    collector.poll()
                    state = collector.alerts.doc()["rules"]["hot_shard_p99"]
                    if first_breach is None and state["streak"] > 0:
                        first_breach = sweep
                    if fired is None and state["state"] == "firing":
                        fired = sweep
            t.close()
            gang = scrape_json(collector.url + "/gang")
            rate = scrape_json(collector.url + "/history"
                               "?name=collector.scrapes_total&query=rate")
            return (collector.alerts.doc()["rules"]["hot_shard_p99"],
                    gang["alerts"], first_breach, fired, rate)
        finally:
            collector.stop()
            fleet.stop()

    rule, gang_alerts, first_breach, fired, rate = leg(chaos=False)
    assert rule["episodes"] == 0 and not gang_alerts.get("active")
    assert first_breach is None and fired is None
    assert rate["value"] is not None

    rule, gang_alerts, first_breach, fired, rate = leg(chaos=True)
    assert rule["episodes"] == 1 and rule["state"] == "firing"
    assert first_breach is not None and fired is not None
    assert fired - first_breach <= for_sweeps + 1
    assert gang_alerts["active"] == ["hot_shard_p99"]
    assert rate["value"] is not None


def test_collector_fallback_serves_history_from_peer_sink(tmp_path):
    """HA tail mode for /history: a secondary that has NEVER scraped
    reconstructs windowed queries from the primary's JSONL sink —
    history, not just the newest snapshot."""
    sink = str(tmp_path / "primary.jsonl")
    with open(sink, "w") as f:
        for i in range(4):
            f.write(json.dumps({"kind": "gang_snapshot", "ts": float(i),
                                "counters": {"c": float(i * 5)},
                                "ranks": {}}) + "\n")
    secondary = FleetCollector({0: "http://127.0.0.1:1/"},
                               poll_interval_s=0, fallback_jsonl=sink)
    try:
        code, doc = secondary._handle_history({"name": "c",
                                               "query": "rate"})
        assert code == 200
        assert doc["source"] == "fallback_jsonl"
        assert doc["value"] == pytest.approx(5.0)
    finally:
        secondary.stop()


def test_collector_fallback_history_latest_and_since_ts(tmp_path):
    """The reconstructed fallback ring answers the point-lookup
    queries too, over the real HTTP route: ``query=latest`` returns
    the newest retained value (gauge and field-projected digest alike)
    and ``delta&since_ts=`` windows the counter increase from the
    sweep at-or-before the cut — every answer stamped
    ``source=fallback_jsonl``."""
    from sparktorch_tpu.obs import ScrapeError, scrape_json

    sink = str(tmp_path / "primary.jsonl")
    with open(sink, "w") as f:
        for i in range(5):
            f.write(json.dumps({
                "kind": "gang_snapshot", "ts": float(10 + i),
                "counters": {"req_total": float(i * 3)},
                "gauges": {"loss": 2.0 - 0.25 * i},
                "ranks": {}}) + "\n")
    secondary = FleetCollector({0: "http://127.0.0.1:1/"},
                               poll_interval_s=0, fallback_jsonl=sink)
    secondary.start(poll_loop=False)
    try:
        base = secondary.url + "/history"
        # describe: the ring itself is the reconstruction.
        desc = scrape_json(base)
        assert desc["source"] == "fallback_jsonl"
        assert desc["sweeps"] == 5
        # latest: newest retained gauge value (ts 14 -> 1.0).
        latest = scrape_json(base + "?name=loss&query=latest")
        assert latest["source"] == "fallback_jsonl"
        assert latest["value"] == pytest.approx(1.0)
        # delta since ts=12: counter 6 -> 12 across the newer sweeps.
        delta = scrape_json(base + "?name=req_total&query=delta"
                            "&since_ts=12")
        assert delta["source"] == "fallback_jsonl"
        assert delta["since_ts"] == 12.0
        assert delta["value"] == pytest.approx(6.0)
        # since_ts predating retention degrades to the full increase.
        delta_all = scrape_json(base + "?name=req_total&query=delta"
                                "&since_ts=0")
        assert delta_all["value"] == pytest.approx(12.0)
        # delta without its required since_ts is a 400 over the wire.
        with pytest.raises(ScrapeError):
            scrape_json(base + "?name=req_total&query=delta")
    finally:
        secondary.stop()


# ---------------------------------------------------------------------------
# Satellite: rpc_traces cap-32 retention + stale-scrape accounting
# ---------------------------------------------------------------------------


def _root_span(i, ts):
    return {"trace_id": f"{i:032x}", "span_id": f"{i:016x}",
            "parent_id": None, "name": "pull", "kind": "client",
            "ts": ts, "dur_s": 0.01, "status": "ok", "forced": False,
            "ann": {}}


def test_collector_rpc_traces_cap_keeps_newest_32():
    from sparktorch_tpu.obs import rpctrace

    # 40 roots at increasing ts; the cap keeps the NEWEST 32.
    spans = [_root_span(i, 1000.0 + i) for i in range(40)]
    trees = rpctrace.stitch_spans(spans, max_traces=32)
    assert len(trees) == 32
    kept = [t["root"]["ts"] for t in trees]
    assert kept == sorted(kept, reverse=True)  # newest first
    assert min(kept) == 1008.0  # the oldest 8 evicted
    # and through the collector's stitch: a rank snapshot carrying the
    # ring produces the same capped, newest-kept section.
    collector = FleetCollector({0: "http://127.0.0.1:1/"},
                               poll_interval_s=0, history=False)
    try:
        st = collector._ranks["0"]
        st.snapshot = {"sections": {rpctrace.SECTION: {"spans": spans}}}
        collector._stitch_rpc()
        traces = collector.rpc_traces()
        assert len(traces) == 32
        assert traces[0]["root"]["ts"] == 1039.0
        assert min(t["root"]["ts"] for t in traces) == 1008.0
    finally:
        collector.stop()


def test_collector_stale_straggler_scrape_dropped(tmp_path):
    """A scrape from an OLD sweep landing after a newer sweep already
    committed must be dropped (counted) — never allowed to roll the
    rank's snapshot backwards."""
    import http.server

    release = threading.Event()
    hold_next = {"armed": False}

    class SlowHandler(http.server.BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_GET(self):
            route = self.path.split("?", 1)[0]
            if route == "/telemetry":
                if hold_next["armed"]:
                    hold_next["armed"] = False
                    release.wait(10.0)  # the seeded straggler
                    body = json.dumps({"run_id": "old",
                                       "counters": {"v": 1.0}}).encode()
                else:
                    body = json.dumps({"run_id": "new",
                                       "counters": {"v": 2.0}}).encode()
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
            else:
                self.send_response(404)
                self.send_header("Content-Length", "0")
                self.end_headers()

    httpd = http.server.ThreadingHTTPServer(("127.0.0.1", 0), SlowHandler)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{httpd.server_address[1]}"
    collector = FleetCollector({0: url}, poll_interval_s=0,
                               history=False, poll_parallelism=1,
                               scrape_timeout_s=15.0)
    try:
        st = collector._ranks["0"]
        # Sweep 0: the straggler — run it on a thread, stuck on the
        # event (serial path, seq=0).
        hold_next["armed"] = True
        collector._poll_seq = 0
        straggler = threading.Thread(
            target=collector._scrape_rank, args=("0", st, 0), daemon=True)
        straggler.start()
        time.sleep(0.2)
        # Sweep 1 commits while the straggler hangs.
        collector._scrape_rank("0", st, 1)
        assert st.committed_seq == 1
        assert st.snapshot["run_id"] == "new"
        committed_at = st.last_ok_ts
        # Release the straggler: its seq-0 result must be DROPPED.
        release.set()
        straggler.join(10.0)
        assert st.snapshot["run_id"] == "new"  # not rolled back
        assert st.committed_seq == 1
        assert st.last_ok_ts == committed_at  # freshness not re-stamped
        assert collector.telemetry.counter_value(
            "collector.stale_scrapes_dropped_total",
            labels={"rank": "0"}) == 1
        # A normal NEWER sweep still commits.
        collector._scrape_rank("0", st, 2)
        assert st.committed_seq == 2
    finally:
        collector.stop()
        httpd.shutdown()
        httpd.server_close()


# ---------------------------------------------------------------------------
# Satellite: percentile math runs OUTSIDE the bus lock
# ---------------------------------------------------------------------------


def test_histogram_percentiles_computed_outside_bus_lock(monkeypatch):
    """Pin the router hot-path fix: while one thread is inside the
    percentile math of ``Telemetry.histogram()``, a writer bumping a
    counter (which takes the bus lock) must NOT block. Before the fix
    the percentile ran under the lock and the router's p50 reads
    serialized the bus against its own replicas."""
    from sparktorch_tpu.obs import telemetry as telemetry_mod

    tele = Telemetry(run_id="contention")
    for i in range(256):
        tele.observe("lat_s", float(i))

    inside = threading.Event()
    release = threading.Event()
    real_percentile = telemetry_mod.np.percentile

    def slow_percentile(*args, **kwargs):
        inside.set()
        release.wait(10.0)
        return real_percentile(*args, **kwargs)

    monkeypatch.setattr(telemetry_mod.np, "percentile", slow_percentile)
    reader = threading.Thread(target=lambda: tele.histogram("lat_s"),
                              daemon=True)
    reader.start()
    assert inside.wait(5.0)
    # The reader is parked inside the percentile. A writer must get
    # the lock immediately — the ring was snapshotted and released.
    t0 = time.perf_counter()
    tele.counter("writes_total")
    tele.observe("lat_s", 1.0)
    blocked_s = time.perf_counter() - t0
    release.set()
    reader.join(5.0)
    assert blocked_s < 1.0, (
        f"writer blocked {blocked_s:.3f}s behind a reader's percentile "
        f"math — the roll-up is back under the bus lock")
    # And snapshot() too (the collector-scrape read path).
    inside.clear()
    release.clear()
    snapper = threading.Thread(target=tele.snapshot, daemon=True)
    snapper.start()
    assert inside.wait(5.0)
    t0 = time.perf_counter()
    tele.counter("writes_total")
    blocked_s = time.perf_counter() - t0
    release.set()
    snapper.join(5.0)
    assert blocked_s < 1.0


# ---------------------------------------------------------------------------
# Flight recorder + postmortems
# ---------------------------------------------------------------------------


def test_flight_recorder_ring_filter_and_section():
    tele = Telemetry(run_id="fr")
    rec = FlightRecorder(tele, capacity=16,
                         publish_interval_s=0.0).attach()
    with tele.span("work/step"):
        pass
    tele.event("ctl.restart", rank=0)
    tele.event("metric_noise", v=1)  # filtered out
    for i in range(40):
        tele.event("ft_restart", worker=f"w{i}")  # overflows the ring
    events = rec.events()
    assert len(events) == 16  # bounded
    assert rec.dropped > 0
    kinds = {e["kind"] for e in events}
    assert "metric_noise" not in kinds
    # the section rides the snapshot (scrape == dump)
    rec.publish()
    snap_events = events_from_snapshot(tele.snapshot())
    assert [e["kind"] for e in snap_events] == [e["kind"] for e in events]
    rec.close()
    tele.event("ctl.after_close")
    assert all(e["kind"] != "ctl.after_close" for e in rec.events())


def test_attach_recorder_idempotent():
    from sparktorch_tpu.obs import attach_recorder

    tele = Telemetry(run_id="fr2")
    r1 = attach_recorder(tele)
    r2 = attach_recorder(tele)
    assert r1 is r2
    tele.event("ctl.x")
    assert sum(1 for e in r1.events() if e["kind"] == "ctl.x") == 1


def test_collect_postmortem_window_render_and_read(tmp_path):
    tele = Telemetry(run_id="pm")
    rec = FlightRecorder(tele, publish_interval_s=0.0).attach()
    now = wall_ts()
    tele.event("ctl.restart_scheduled", rank=2, reason="killed")
    with tele.span("work/partition"):
        pass
    rec.publish()
    extra = [{"kind": "shrink", "ts": now, "generation": 3, "rank": 2},
             {"kind": "ancient", "ts": now - 10_000.0}]  # outside window
    path = collect_postmortem(str(tmp_path), "rank 2 died",
                              telemetry=tele, extra_events=extra,
                              window_s=30.0, rank=2)
    doc = read_postmortem(path)
    kinds = [e["kind"] for e in doc["events"]]
    assert "ctl.restart_scheduled" in kinds
    assert "span" in kinds
    assert "shrink" in kinds
    assert "ancient" not in kinds  # the causal window is bounded
    assert doc["rank"] == 2 and doc["reason"] == "rank 2 died"
    # history deltas ride the bundle
    h = MetricsHistory()
    h.append(_sweep(now - 5.0, counters={"deaths_total": 0.0}))
    h.append(_sweep(now, counters={"deaths_total": 3.0}))
    path2 = collect_postmortem(str(tmp_path), "again", telemetry=tele,
                               history=h, window_s=30.0)
    assert read_postmortem(path2)["metric_deltas"]["deaths_total"] == 3.0
    # the renderer names the story
    from sparktorch_tpu.obs import timeline

    out = timeline.render_postmortem_report(doc)
    assert "rank 2 died" in out and "ctl.restart_scheduled" in out
    # and the CLI round-trips the same file
    import contextlib
    import io

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = timeline.main(["--postmortem", path])
    assert rc == 0 and "postmortem: rank 2 died" in buf.getvalue()
    with pytest.raises(ValueError):
        bad = str(tmp_path / "not_pm.json")
        with open(bad, "w") as f:
            json.dump({"kind": "other"}, f)
        read_postmortem(bad)


def test_postmortem_collects_dead_ranks_last_good_ring():
    """The load-bearing trick: a rank's final flight-recorder ring
    survives in the collector's last-good snapshot after the rank
    dies, and the bundle recovers it rank-tagged."""
    rank_tele = Telemetry(run_id="victim")
    rec = FlightRecorder(rank_tele, publish_interval_s=0.0).attach()
    with rank_tele.span("work/final"):
        pass
    rec.publish()
    exp = _exporter(rank_tele)
    collector = FleetCollector({7: exp.url}, poll_interval_s=0)
    try:
        collector.poll()
        exp.stop()  # the rank dies
        collector.poll()  # scrape fails; last good keeps serving
        import tempfile

        with tempfile.TemporaryDirectory() as d:
            path = collect_postmortem(d, "rank 7 vanished",
                                      collector=collector,
                                      history=collector.history)
            doc = read_postmortem(path)
        victim = [e for e in doc["events"]
                  if e.get("kind") == "span" and str(e.get("rank")) == "7"]
        assert victim, doc["events"]
        assert victim[-1]["name"] == "work/final"
    finally:
        collector.stop()


# ---------------------------------------------------------------------------
# Consumers: elastic controller scale signals, supervisor postmortems
# ---------------------------------------------------------------------------


def test_elastic_controller_consumes_alerts_as_scale_signals(tmp_path):
    from sparktorch_tpu.ctl import ElasticController

    tele = Telemetry(run_id="ctl")
    h = MetricsHistory()
    am = AlertManager(h, [AlertRule(name="hot_shard", metric="lat_s",
                                    labels={"shard": "2"}, field="p99",
                                    kind="sustained", threshold=0.1,
                                    for_sweeps=2)], telemetry=tele)
    acted = []
    ctl = ElasticController([1, 2], lambda p: True, telemetry=tele,
                            alerts=am, on_scale_signal=acted.append,
                            postmortem_dir=str(tmp_path))
    ctl.add_rank(0, lambda *a: None)
    for i in range(3):
        h.append(_sweep(float(i), hists={"lat_s{shard=2}": _digest(0.5)}))
        am.evaluate(ts=float(i))
    assert len(ctl.scale_signals) == 1
    sig = ctl.scale_signals[0]
    assert sig["rule"] == "hot_shard" and sig["labels"] == {"shard": "2"}
    assert acted and acted[0]["alert"] == "hot_shard"
    assert tele.counter_value("ctl.scale_signals_total",
                              labels={"rule": "hot_shard"}) == 1
    # generation-tagged ctl event in the controller history
    kinds = [e["kind"] for e in ctl.history]
    assert "scale_signal" in kinds
    assert all("generation" in e for e in ctl.history)
    # the alert-triggered snapshot landed as a bundle
    bundles = [f for f in os.listdir(tmp_path)
               if f.startswith("postmortem_")]
    assert len(bundles) == 1
    # resolution clears the signal
    h.append(_sweep(3.0, hists={"lat_s{shard=2}": _digest(0.01)}))
    am.evaluate(ts=3.0)
    assert "scale_signal_cleared" in [e["kind"] for e in ctl.history]


def test_elastic_controller_death_bundle_holds_restart_transition(
        tmp_path):
    """A member that dies once: the controller writes ONE postmortem
    bundle for the death, and the bundle's window holds the
    controller's own ``restart_scheduled`` transition (a dead rank's
    last spans reach a bundle through the collector's last-good ring,
    ``test_postmortem_collects_dead_ranks_last_good_ring``)."""
    from sparktorch_tpu.ctl import ElasticController
    from sparktorch_tpu.ft import FtPolicy, RestartPolicy
    from sparktorch_tpu.ft.supervisor import ThreadWorker

    tele = Telemetry(run_id="ctl_pm")
    done = []

    def start_fn(rank, attempt, generation, assignment):
        def run():
            if attempt == 0:
                raise RuntimeError("first attempt dies")
            done.extend(assignment)

        return ThreadWorker(f"rank{rank}-a{attempt}", run)

    policy = FtPolicy(restart=RestartPolicy(max_restarts=2,
                                            backoff_base_s=0.01,
                                            backoff_max_s=0.05), seed=0)
    ctl = ElasticController(["p0", "p1"], lambda p: p in done,
                            policy=policy, telemetry=tele,
                            postmortem_dir=str(tmp_path))
    ctl.add_rank(0, start_fn)
    summary = ctl.run(poll_interval_s=0.01, deadline_s=30)
    assert summary["restarts"] == {"0": 1} and sorted(done) == ["p0", "p1"]
    (bundle,) = [f for f in os.listdir(tmp_path)
                 if f.startswith("postmortem_")]
    doc = read_postmortem(str(tmp_path / bundle))
    assert "first attempt dies" in doc["reason"]
    kinds = {str(e.get("kind")) for e in doc["events"]}
    assert kinds & {"ctl.restart_scheduled", "restart_scheduled"}, kinds


def test_supervisor_writes_postmortem_on_death(tmp_path):
    from sparktorch_tpu.ft import FtPolicy, RestartPolicy
    from sparktorch_tpu.ft.supervisor import Supervisor, ThreadWorker

    tele = Telemetry(run_id="sup")
    policy = FtPolicy(restart=RestartPolicy(max_restarts=2,
                                            backoff_base_s=0.01,
                                            backoff_max_s=0.05), seed=0)
    sup = Supervisor(policy=policy, telemetry=tele,
                     postmortem_dir=str(tmp_path))
    attempts = []

    def start(attempt):
        attempts.append(attempt)

        def target():
            with tele.span("work/chunk"):
                pass
            if attempt == 0:
                raise RuntimeError("first attempt dies")

        return ThreadWorker(f"w-{attempt}", target)

    sup.add("w", start)
    sup.run(poll_interval_s=0.01)
    assert attempts == [0, 1]
    bundles = [f for f in os.listdir(tmp_path)
               if f.startswith("postmortem_")]
    assert len(bundles) == 1
    doc = read_postmortem(str(tmp_path / bundles[0]))
    assert "first attempt dies" in doc["reason"]
    # the supervisor's own ring caught the worker's spans
    assert any(e.get("kind") == "span" for e in doc["events"])
    assert tele.counter_value("ft_postmortems_total") == 1


# ---------------------------------------------------------------------------
# Satellite: timeline --follow
# ---------------------------------------------------------------------------


def test_follow_reader_incremental_torn_and_truncated(tmp_path):
    from sparktorch_tpu.obs.timeline import FollowReader

    path = str(tmp_path / "sink.jsonl")
    reader = FollowReader(path)
    assert reader.poll() == []  # file does not exist yet
    with open(path, "w") as f:
        f.write(json.dumps({"kind": "a", "ts": 1.0}) + "\n")
        f.write('{"kind": "torn", "ts"')  # no newline: still writing
    got = reader.poll()
    assert [r["kind"] for r in got] == ["a"]
    with open(path, "a") as f:
        f.write(': 2.0}\n')  # the torn line completes
        f.write(json.dumps({"kind": "b", "ts": 3.0}) + "\n")
    got = reader.poll()
    assert [r["kind"] for r in got] == ["torn", "b"]
    assert reader.poll() == []  # nothing new
    # truncation/rotation resets cleanly
    with open(path, "w") as f:
        f.write(json.dumps({"kind": "fresh", "ts": 4.0}) + "\n")
    got = reader.poll()
    assert [r["kind"] for r in got] == ["fresh"]


def test_follow_renders_alerts_and_ctl_events(tmp_path):
    from sparktorch_tpu.obs.timeline import follow, render_follow_line

    assert render_follow_line({"kind": "span", "ts": 1.0}) is None
    line = render_follow_line({"kind": "alert.fired", "ts": 2.0,
                               "alert": "hot", "value": 0.5,
                               "threshold": 0.1, "episode": 1})
    assert "alert.fired" in line and "hot" in line and "episode=1" in line
    line = render_follow_line({"kind": "ctl.shrink", "ts": 3.0,
                               "rank": 1, "generation": 2})
    assert "ctl.shrink" in line and "rank=1" in line and "gen=2" in line
    line = render_follow_line({"kind": "gang_snapshot", "ts": 4.0,
                               "ranks": {"0": {"ok": True},
                                         "1": {"ok": False}},
                               "heartbeats": {"step_skew": 3}})
    assert "1/2 ok" in line and "step skew 3" in line
    # the generator tails a GROWING file: records appended after the
    # first poll still arrive.
    path = str(tmp_path / "sink.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"kind": "alert.fired", "ts": 1.0,
                            "alert": "a1", "episode": 1}) + "\n")

    def append_later():
        time.sleep(0.3)
        with open(path, "a") as f:
            f.write(json.dumps({"kind": "ctl.grow", "ts": 2.0,
                                "rank": 5, "generation": 4}) + "\n")

    threading.Thread(target=append_later, daemon=True).start()
    lines = list(follow(path, poll_s=0.05, max_records=2))
    assert len(lines) == 2
    assert "a1" in lines[0] and "ctl.grow" in lines[1]


def test_collector_sink_carries_alert_records_for_follow(tmp_path):
    """End to end: collector sink records render under --follow —
    alert transitions land as their own records the tail shows."""
    from sparktorch_tpu.obs.timeline import follow

    sink = str(tmp_path / "sink.jsonl")
    rank_tele = Telemetry(run_id="rank0")
    exp = _exporter(rank_tele)
    collector = FleetCollector(
        {0: exp.url}, poll_interval_s=0, jsonl_path=sink,
        alert_rules=[AlertRule(name="hot", metric="lat_s", field="p99",
                               threshold=0.1)])
    try:
        rank_tele.observe("lat_s", 0.5)
        collector.poll()
    finally:
        collector.stop()
        exp.stop()
    stop = threading.Event()
    stop.set()  # drain what exists, then return
    lines = list(follow(sink, poll_s=0.01, stop=stop))
    assert any("alert.fired" in ln and "hot" in ln for ln in lines)
    assert any("gang_snapshot" in ln for ln in lines)


# ---------------------------------------------------------------------------
# wall_ts
# ---------------------------------------------------------------------------


def test_wall_ts_is_epoch_seconds():
    assert abs(wall_ts() - time.time()) < 5.0

"""Gang-level observability: cross-host trace merge (merge_analyses),
the fleet collector, run-ID correlation, the capture-truncation
detector, and the --gang timeline. All offline/backend-free — the
synthetic per-rank traces make the merge math exactly checkable.
"""

import gzip
import json
import os

import pytest

from sparktorch_tpu.obs import (
    FleetCollector,
    ScrapeError,
    Telemetry,
    analyze_trace,
    merge_analyses,
    mint_run_id,
    parse_prometheus,
    read_jsonl,
    run_tag,
    scrape_json,
    scrape_text,
)
from sparktorch_tpu.obs.xprof import analyze_and_publish

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures", "xprof")
SYNTHETIC = os.path.join(FIXTURES, "synthetic_overlap.trace.json.gz")


def _rank_trace(scale: float, steps: int = 2) -> dict:
    """One rank's capture: per step one marker (wall 1000*scale us),
    600*scale us of compute, 400*scale us of all-reduce of which
    200*scale us overlaps the compute."""
    events = []
    t = 1000.0
    for s in range(steps):
        wall = 1000.0 * scale
        events.append({"ph": "X", "pid": 1, "tid": 1, "name": "train_step",
                       "ts": t, "dur": wall, "args": {"step_num": str(s)}})
        events.append({"ph": "X", "pid": 1, "tid": 2, "name": f"fusion.{s}",
                       "ts": t, "dur": 600.0 * scale})
        events.append({"ph": "X", "pid": 1, "tid": 3,
                       "name": f"all-reduce.{s}",
                       "ts": t + 400.0 * scale, "dur": 400.0 * scale})
        t += wall
    return {"traceEvents": events}


# ---------------------------------------------------------------------------
# merge_analyses: the exact gang math
# ---------------------------------------------------------------------------


def test_merge_analyses_exact_math():
    us = 1e-6
    a0 = analyze_trace(_rank_trace(1.0))   # walls 1000us
    a1 = analyze_trace(_rank_trace(2.0))   # walls 2000us (the straggler)
    gang = merge_analyses([a0, a1], ranks=[0, 1], run_id="g-1")

    assert gang.n_ranks == 2 and len(gang.steps) == 2
    assert gang.run_id == "g-1"
    for i, s in enumerate(gang.steps):
        assert s.step == i
        # Walls MAX across ranks; seconds SUM.
        assert s.wall_s == pytest.approx(2000 * us)
        assert s.window_s == pytest.approx(2000 * us)
        assert s.comm_s == pytest.approx((400 + 800) * us)
        assert s.compute_s == pytest.approx((600 + 1200) * us)
        assert s.overlap_s == pytest.approx((200 + 400) * us)
        assert s.skew_s == pytest.approx(1000 * us)
        assert s.n_ranks == 2
        assert s.counts == {"all_reduce": 2}
        assert s.families == {"all_reduce": pytest.approx(1200 * us)}
        # Per-rank lanes survive for the timeline's lane rendering.
        assert s.ranks["0"]["wall_s"] == pytest.approx(1000 * us)
        assert s.ranks["1"]["wall_s"] == pytest.approx(2000 * us)
    # Aggregates: families sum, skew is the worst step's spread,
    # fractions recomputed over the union of every rank's windows.
    assert gang.family_s() == {"all_reduce": pytest.approx(2400 * us)}
    assert gang.family_counts() == {"all_reduce": 4}
    assert gang.step_skew_s == pytest.approx(1000 * us)
    assert gang.comm_fraction == pytest.approx(
        2400 / (2 * 2 * 2000))  # comm_s / (n_ranks * sum window)
    assert gang.overlap_fraction == pytest.approx(1200 / 2400)
    # Skew is >= 0 by construction, even for identical ranks.
    same = merge_analyses([a0, analyze_trace(_rank_trace(1.0))])
    assert same.step_skew_s == 0.0


def test_merge_analyses_accepts_dicts_and_uneven_steps():
    # The collector merges to_dict() forms scraped off /telemetry; a
    # truncated rank (fewer steps) contributes only where it has data.
    a0 = analyze_trace(_rank_trace(1.0, steps=3))
    a1 = analyze_trace(_rank_trace(1.5, steps=2))
    gang = merge_analyses([a0.to_dict(), a1], ranks=["0", "1"])
    assert [s.step for s in gang.steps] == [0, 1, 2]
    assert gang.steps[0].n_ranks == 2
    assert gang.steps[2].n_ranks == 1          # rank 1 missing step 2
    assert gang.steps[2].skew_s == 0.0         # one rank: no spread
    assert gang.steps[2].wall_s == pytest.approx(1000e-6)

    with pytest.raises(ValueError):
        merge_analyses([])
    with pytest.raises(ValueError):
        merge_analyses([a0], ranks=[0, 1])
    with pytest.raises(TypeError):
        merge_analyses(["not-an-analysis"])


def test_gang_publish_rides_bus_and_section():
    tele = Telemetry(run_id="gangpub")
    gang = merge_analyses([analyze_trace(_rank_trace(1.0)),
                           analyze_trace(_rank_trace(2.0))],
                          run_id="g-2")
    gang.publish(tele)
    assert tele.gauge_value("xprof.gang_ranks") == 2.0
    assert tele.counter_value("xprof.gang_steps_total") == 2.0
    assert tele.counter_value("xprof.gang_collectives_total",
                              labels={"op": "all_reduce"}) == 4.0
    assert tele.histogram("xprof.gang_step_skew_s")["count"] == 2
    assert tele.gauge_value("xprof.gang_step_skew_s_max") == \
        pytest.approx(1000e-6)
    # The full document rides the snapshot (scrape == dump).
    section = tele.snapshot()["sections"]["xprof_gang"]
    assert section["kind"] == "gang" and section["n_ranks"] == 2
    assert section["run_id"] == "g-2"


# ---------------------------------------------------------------------------
# Capture-truncation detector
# ---------------------------------------------------------------------------


def test_truncation_detector_trips_once_on_shortfall(tmp_path):
    path = tmp_path / "host0.trace.json.gz"
    with gzip.open(path, "wt") as f:
        json.dump(_rank_trace(1.0, steps=2), f)
    tele = Telemetry(run_id="trunc")
    # 5 steps annotated on the bus during the capture, 2 markers
    # survived -> exactly one warning event + counter bump.
    events = []
    tele.add_sink(events.append)
    analysis = analyze_and_publish(str(tmp_path), telemetry=tele,
                                   expected_steps=5)
    assert analysis is not None and analysis.n_markers == 2
    assert tele.counter_value("xprof.capture_truncated_total") == 1.0
    trunc = [e for e in events if e["kind"] == "xprof.capture_truncated"]
    assert len(trunc) == 1
    assert trunc[0]["expected_steps"] == 5
    assert trunc[0]["found_markers"] == 2
    # A complete capture (expected == found) must not trip it.
    analyze_and_publish(str(tmp_path), telemetry=tele, expected_steps=2)
    assert tele.counter_value("xprof.capture_truncated_total") == 1.0
    # No expectation -> no detector (the pre-armed behavior).
    analyze_and_publish(str(tmp_path), telemetry=tele)
    assert tele.counter_value("xprof.capture_truncated_total") == 1.0


def test_profile_run_arms_truncation_expectation(tmp_path, monkeypatch):
    """profile_run measures the annotated-steps delta across the
    capture and hands it to the analyzer as the expectation."""
    from sparktorch_tpu.obs import xprof as xprof_mod
    from sparktorch_tpu.utils.tracing import profile_run, step_annotation

    tele = Telemetry(run_id="arm")
    tele.counter("tracing.annotated_steps", 7)  # pre-capture noise
    seen = {}

    def fake_analyze(log_dir, telemetry=None, step_name="train_step",
                     expected_steps=None):
        seen["expected"] = expected_steps
        return None

    monkeypatch.setattr(xprof_mod, "analyze_and_publish", fake_analyze)
    with profile_run(str(tmp_path / "t"), telemetry=tele):
        for i in range(3):
            with step_annotation(i, telemetry=tele):
                pass
    assert seen["expected"] == 3  # the delta, not the absolute counter


# ---------------------------------------------------------------------------
# Run-ID minting, wire tag, heartbeat stamping
# ---------------------------------------------------------------------------


def test_mint_run_id_and_run_tag():
    a, b = mint_run_id(), mint_run_id()
    assert a != b
    assert " " not in a and "," not in a and "=" not in a
    assert run_tag(None) == 0 and run_tag("") == 0
    t = run_tag("gang-x")
    assert 1 <= t <= 0xFFFF
    assert run_tag("gang-x") == t  # deterministic


def test_wire_header_carries_run_tag():
    import numpy as np

    from sparktorch_tpu.net import wire

    tree = {"w": np.ones((3,), np.float32)}
    tag = run_tag("gang-y")
    body = wire.frame_bytes(wire.encode(tree, version=7, run_tag=tag))
    assert wire.frame_run_tag(body) == tag
    version, decoded = wire.decode(body)  # body decode is unaffected
    assert version == 7
    assert np.array_equal(decoded["w"], tree["w"])
    # Untagged (pre-run-id) frames read back 0.
    assert wire.frame_run_tag(
        wire.frame_bytes(wire.encode(tree))) == 0
    with pytest.raises(wire.WireError):
        wire.frame_run_tag(b"nope")


def test_heartbeat_records_carry_run_id(tmp_path):
    from sparktorch_tpu.obs import gang_report
    from sparktorch_tpu.obs.heartbeat import HeartbeatEmitter

    d = str(tmp_path / "hb")
    em = HeartbeatEmitter(d, rank=0, run_id="g-hb")
    em.notify_step(4)
    em2 = HeartbeatEmitter(d, rank=1)       # untagged rank
    em2.set_run_id("g-hb")                   # learns it post-register
    em2.notify_step(5)
    report = gang_report(d)
    assert report["ranks"][0]["run_id"] == "g-hb"
    assert report["ranks"][1]["run_id"] == "g-hb"


def test_gang_coordinator_announces_run_id_worker_adopts():
    from sparktorch_tpu.native.gang import GangCoordinator, GangWorker

    tele = Telemetry(run_id="local-scope")
    with GangCoordinator(world_size=1, heartbeat_timeout_ms=5000,
                         run_id="g-native") as coord:
        assert coord.run_id == "g-native"
        w = GangWorker("127.0.0.1", coord.port, 0, "a:1", telemetry=tele)
        try:
            # The OK reply announced the id; the worker stamped the
            # run-scoped bus with it (span/event correlation).
            assert w.run_id == "g-native"
            assert tele.run_id == "g-native"
        finally:
            w.close()


def test_gang_reg_refuses_mismatched_run_claim():
    import socket

    from sparktorch_tpu.native.gang import GangCoordinator

    def line(port, msg):
        with socket.create_connection(("127.0.0.1", port), timeout=5) as s:
            s.sendall(msg.encode())
            buf = b""
            while not buf.endswith(b"\n"):
                chunk = s.recv(256)
                if not chunk:
                    break
                buf += chunk
        return buf.decode().strip()

    with GangCoordinator(world_size=1, heartbeat_timeout_ms=5000,
                         run_id="g-claims") as coord:
        # Matching claim and no-claim both register; a mismatched
        # claim (a rank from another run's gang) is refused.
        assert line(coord.port, "REG 0 a:1 -1 g-claims\n") == \
            "OK 1 0 g-claims"
        assert line(coord.port, "REG 0 a:1 -1 -\n") == "OK 1 0 g-claims"
        assert line(coord.port, "REG 0 a:1 -1 other-run\n") == "ERR run"
    # Untagged coordinators keep the legacy reply (mixed-version gangs).
    with GangCoordinator(world_size=1, heartbeat_timeout_ms=5000) as coord:
        assert line(coord.port, "REG 0 a:1\n") == "OK 1 0"


# ---------------------------------------------------------------------------
# Fleet collector
# ---------------------------------------------------------------------------


def _rank_exporter(rank: int, run_id: str, hb_dir: str):
    from sparktorch_tpu.native.gang import GangMetricsExporter
    from sparktorch_tpu.obs.heartbeat import HeartbeatEmitter

    tele = Telemetry(run_id=run_id)
    tele.counter("gangtest.ticks", rank + 1)
    analyze_trace(_rank_trace(1.0 + rank)).publish(tele)
    HeartbeatEmitter(hb_dir, rank=rank, telemetry=tele,
                     run_id=run_id).notify_step(10 * (rank + 1))
    return GangMetricsExporter(heartbeat_dir=hb_dir, telemetry=tele).start()


def test_collector_merges_ranks_with_labels_and_gang_budget(tmp_path):
    run_id = mint_run_id("t")
    hb_dir = str(tmp_path / "hb")
    exps = [_rank_exporter(r, run_id, hb_dir) for r in range(2)]
    sink = str(tmp_path / "gang.jsonl")
    collector = FleetCollector({r: e.url for r, e in enumerate(exps)},
                               run_id=run_id, poll_interval_s=0,
                               jsonl_path=sink).start(poll_loop=False)
    try:
        merged = collector.poll()
        # Every rank series re-keyed with rank/host labels; existing
        # labels (the heartbeat gauges' own rank) preserved.
        assert merged["counters"][
            "gangtest.ticks{host=127.0.0.1,rank=0}"] == 1.0
        assert merged["counters"][
            "gangtest.ticks{host=127.0.0.1,rank=1}"] == 2.0
        assert merged["gauges"]["collector.ranks"] == 2.0
        assert merged["gauges"]["collector.ranks_ok"] == 2.0
        # hb gauges keep their own rank label (scraped via exporter 0
        # AND 1 — shared dir — but the label names the hb rank).
        hb_keys = [k for k in merged["gauges"] if "gang.hb_step{" in k]
        assert hb_keys and all("rank=" in k for k in hb_keys)

        # The merged xprof budget reconciles with the rank analyses.
        gang = collector.gang_view()
        assert gang["xprof"]["n_ranks"] == 2
        a0, a1 = (analyze_trace(_rank_trace(1.0 + r)) for r in range(2))
        assert gang["xprof"]["collective_s"]["all_reduce"] == pytest.approx(
            a0.family_s()["all_reduce"] + a1.family_s()["all_reduce"])
        assert gang["xprof"]["steps"][0]["wall_s"] == pytest.approx(
            max(a0.steps[0].wall_s, a1.steps[0].wall_s))
        assert gang["xprof"]["step_skew_s"] > 0
        # Merged heartbeat table: union with derived step skew.
        assert gang["heartbeats"]["n_ranks"] == 2
        assert gang["heartbeats"]["step_skew"] == 10
        assert set(gang["run_ids"].values()) == {run_id}

        # Publish-once: identical analyses must not duplicate gang
        # histogram samples on the next poll.
        collector.poll()
        assert collector.telemetry.counter_value(
            "xprof.gang_merges_total") == 1.0
        assert collector.telemetry.histogram(
            "xprof.gang_step_skew_s")["count"] == 2

        # HTTP surface: /gang, /metrics, /telemetry serve the merge.
        got = scrape_json(collector.url + "/gang")
        assert got["xprof"]["n_ranks"] == 2
        prom = parse_prometheus(scrape_text(collector.url + "/metrics"))
        assert prom[
            'sparktorch_gangtest_ticks{host="127.0.0.1",rank="1"}'] == 2.0
        assert prom["sparktorch_xprof_gang_ranks"] == 2.0

        # The JSONL sink feeds timeline --gang.
        records = read_jsonl(sink)
        assert records and records[-1]["kind"] == "gang_snapshot"
        assert records[-1]["sections"]["xprof_gang"]["n_ranks"] == 2
    finally:
        collector.stop()
        for e in exps:
            e.stop()


def test_collector_degrades_on_dead_and_torn_targets(tmp_path):
    import http.server
    import threading

    class TornHandler(http.server.BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_GET(self):
            body = b'{"counters": {'  # torn JSON
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    torn = http.server.ThreadingHTTPServer(("127.0.0.1", 0), TornHandler)
    threading.Thread(target=torn.serve_forever, daemon=True).start()
    good = _rank_exporter(0, "t-degrade", str(tmp_path / "hb"))
    collector = FleetCollector({
        0: good.url,
        1: "http://127.0.0.1:9",  # nothing listens: vanished exporter
        2: f"http://127.0.0.1:{torn.server_address[1]}",
    }, poll_interval_s=0)
    try:
        merged = collector.poll()  # must not raise
        assert merged["gauges"]["collector.ranks_ok"] == 1.0
        assert collector.telemetry.counter_value(
            "collector.scrape_errors_total", labels={"rank": "1"}) == 1.0
        assert collector.telemetry.counter_value(
            "collector.scrape_errors_total", labels={"rank": "2"}) == 1.0
        assert merged["ranks"]["1"]["ok"] is False
        assert merged["ranks"]["1"]["last_error"]
        # The good rank still fully merges.
        assert merged["counters"][
            "gangtest.ticks{host=127.0.0.1,rank=0}"] == 1.0
    finally:
        collector.stop()
        good.stop()
        torn.shutdown()
        torn.server_close()


def test_collector_keeps_last_good_heartbeats_on_hb_failure(tmp_path):
    """A transient /heartbeats failure must not make the target's
    ranks vanish from /gang: the last good table keeps serving (its
    ages grow — that is the visible signal), same degradation contract
    as the snapshot."""
    exp = _rank_exporter(0, "t-hb-keep", str(tmp_path / "hb"))
    collector = FleetCollector({0: exp.url}, poll_interval_s=0)
    try:
        collector.poll()
        assert collector.gang_view()["heartbeats"]["n_ranks"] == 1
        # Simulate the route breaking while /telemetry stays up.
        import sparktorch_tpu.obs.collector as collector_mod

        real = collector_mod.scrape_json

        def flaky(url, timeout=2.0):
            if url.endswith("/heartbeats"):
                raise ScrapeError("transient 500")
            return real(url, timeout=timeout)

        collector_mod_scrape, collector_mod.scrape_json = \
            collector_mod.scrape_json, flaky
        try:
            collector.poll()
        finally:
            collector_mod.scrape_json = collector_mod_scrape
        gang = collector.gang_view()
        assert gang["heartbeats"]["n_ranks"] == 1  # last good retained
        assert gang["ranks"]["0"]["ok"] is True    # /telemetry still fine
    finally:
        collector.stop()
        exp.stop()


def test_gang_coordinator_rejects_line_unsafe_run_id():
    from sparktorch_tpu.native.gang import GangCoordinator

    for bad in ("has space", "tab\tid", "", "x" * 121, "newl\nine"):
        with pytest.raises(ValueError, match="line-protocol-safe"):
            GangCoordinator(world_size=1, run_id=bad)
    # Minted ids always pass.
    with GangCoordinator(world_size=1, heartbeat_timeout_ms=5000,
                         run_id=mint_run_id()):
        pass


def test_scrape_helpers_error_classes(tmp_path):
    with pytest.raises(ScrapeError):
        scrape_text("http://127.0.0.1:9/metrics")
    with pytest.raises(ScrapeError):
        scrape_json("http://127.0.0.1:9/telemetry")
    assert isinstance(ScrapeError("x"), OSError)  # catchable as OSError


# ---------------------------------------------------------------------------
# timeline --gang
# ---------------------------------------------------------------------------


def test_timeline_gang_from_traces_and_jsonl(tmp_path, capsys):
    from sparktorch_tpu.obs.sinks import write_jsonl
    from sparktorch_tpu.obs.timeline import main, render_gang_report

    p0 = tmp_path / "host0.trace.json"
    p1 = tmp_path / "host1.trace.json"
    p0.write_text(json.dumps(_rank_trace(1.0)))
    p1.write_text(json.dumps(_rank_trace(2.0)))

    # N per-host traces merged on the spot: per-rank lanes + skew.
    assert main(["--gang", str(p0), str(p1)]) == 0
    out = capsys.readouterr().out
    assert "gang: 2 ranks" in out
    assert "rank 0" in out and "rank 1" in out
    assert "straggler" in out      # rank 1 is 2x slower
    assert "skew" in out

    # --json emits the raw merged dict.
    assert main(["--gang", "--json", str(p0), str(p1)]) == 0
    d = json.loads(capsys.readouterr().out)
    assert d["kind"] == "gang" and d["n_ranks"] == 2

    # A collector JSONL sink renders the already-merged budget.
    gang = merge_analyses([analyze_trace(_rank_trace(1.0)),
                           analyze_trace(_rank_trace(2.0))],
                          run_id="g-cli").to_dict()
    sink = str(tmp_path / "sink.jsonl")
    write_jsonl(sink, [{"kind": "gang_snapshot",
                        "sections": {"xprof_gang": gang}}])
    assert main(["--gang", sink]) == 0
    out = capsys.readouterr().out
    assert "g-cli" in out and "gang: 2 ranks" in out

    # Without --gang, several paths are an error, not a silent merge.
    assert main([str(p0), str(p1)]) == 2
    capsys.readouterr()
    # A JSONL without a merged budget exits cleanly.
    empty = str(tmp_path / "empty.jsonl")
    write_jsonl(empty, [{"kind": "other"}])
    assert main(["--gang", empty]) == 1

    # render_gang_report accepts the GangAnalysis object too.
    text = render_gang_report(merge_analyses(
        [analyze_trace(_rank_trace(1.0))], run_id="solo"))
    assert "gang: 1 ranks" in text


# ---------------------------------------------------------------------------
# Sections plumbing (the scrape surface the collector relies on)
# ---------------------------------------------------------------------------


def test_sections_ride_snapshot_dump_and_pickle(tmp_path):
    import dill

    tele = Telemetry(run_id="sect")
    analyze_trace(SYNTHETIC).publish(tele)
    snap = tele.snapshot()
    assert snap["sections"]["xprof"]["n_steps"] == 2
    # dump == scrape: the JSONL line carries the same section.
    path = str(tmp_path / "s.jsonl")
    tele.dump(path)
    (read,) = read_jsonl(path)
    assert read["sections"]["xprof"] == snap["sections"]["xprof"]
    # Pickle round-trip keeps sections (a fitted model's bus travels).
    clone = dill.loads(dill.dumps(tele))
    assert clone.snapshot()["sections"]["xprof"]["n_steps"] == 2
    # set_section(None) removes; reset clears.
    tele.set_section("xprof", None)
    assert "sections" not in tele.snapshot()


def test_gang_obs_bench_gate_passes(tmp_path):
    """Two rank exporters behind the collector, one rank's capture
    truncated: the merged scrape reconciles with each rank's own
    scrape series by series, the gang budget with the per-rank
    analyses (families SUM, step walls MAX, skew = max - min), and the
    truncation trips once, on the rank it happened to."""
    run_id = mint_run_id("gang-obs")
    n_ranks = 2
    exps = [_rank_exporter(r, run_id, str(tmp_path / "hb"))
            for r in range(n_ranks)]
    # Rank 1's capture lost markers: 4 steps annotated, 2 in the trace.
    trace_dir = tmp_path / "trace_r1"
    trace_dir.mkdir()
    with gzip.open(trace_dir / "host0.trace.json.gz", "wt") as f:
        json.dump(_rank_trace(2.0), f)
    analyze_and_publish(str(trace_dir), telemetry=exps[1].telemetry,
                        expected_steps=4)
    collector = FleetCollector({r: e.url for r, e in enumerate(exps)},
                               run_id=run_id, poll_interval_s=0,
                               ).start(poll_loop=False)
    try:
        collector.poll()
        merged = parse_prometheus(scrape_text(collector.url + "/metrics"))
        for r, exp in enumerate(exps):
            own = parse_prometheus(scrape_text(exp.url + "/metrics"))
            labels = f'{{host="127.0.0.1",rank="{r}"}}'
            assert own["sparktorch_gangtest_ticks"] == float(r + 1)
            assert merged["sparktorch_gangtest_ticks" + labels] == r + 1
        # Every rank-originated series carries a rank label.
        snap = scrape_json(collector.url + "/telemetry")
        assert not [k for section in ("counters", "gauges", "histograms")
                    for k in snap.get(section, {})
                    if not k.startswith(("collector.", "xprof.gang_"))
                    and "rank=" not in k]
        trunc = {k: v for k, v in snap["counters"].items()
                 if k.startswith("xprof.capture_truncated_total")}
        assert list(trunc.values()) == [1.0]
        assert "rank=1" in next(iter(trunc))

        xp = scrape_json(collector.url + "/gang")["xprof"]
        assert xp["n_ranks"] == n_ranks
        analyses = [analyze_trace(_rank_trace(1.0 + r))
                    for r in range(n_ranks)]
        for fam in analyses[0].family_s():
            assert xp["collective_s"][fam] == pytest.approx(
                sum(a.family_s()[fam] for a in analyses), abs=1e-9)
        for i, step in enumerate(xp["steps"]):
            walls = [a.steps[i].wall_s for a in analyses]
            assert step["wall_s"] == pytest.approx(max(walls), abs=1e-9)
            assert step["skew_s"] == pytest.approx(
                max(walls) - min(walls), abs=1e-9)
        assert xp["step_skew_s"] > 0
    finally:
        collector.stop()
        for e in exps:
            e.stop()

"""Continuous ledger-keyed stack profiler (obs/profile.py): the
cross-thread span registry, deterministic sampling/folding, trie
bounds, run-level merge + diff, the collector's /profile route,
timeline --profile rendering, the postmortem profile field, and the
alert -> burst reflex.

Named test_obs_profile so it sorts before the tier-1 timeout cutoff.
"""

import json
import threading
import time
from contextlib import redirect_stdout
from io import StringIO

import pytest

from sparktorch_tpu.obs import goodput as goodput_mod
from sparktorch_tpu.obs import profile as profile_mod
from sparktorch_tpu.obs.collector import FleetCollector
from sparktorch_tpu.obs.profile import (
    UNATTRIBUTED,
    StackProfiler,
    diff_docs,
    flatten_self,
    merge_sections,
    sections_from_snapshots,
    top_frames,
)
from sparktorch_tpu.obs.telemetry import Telemetry


# ---------------------------------------------------------------------------
# The ledger's cross-thread registry (the sampler's bucket source)
# ---------------------------------------------------------------------------


def _worker_in_span(bucket, entered, release):
    with goodput_mod.span(bucket):
        entered.set()
        release.wait(timeout=5.0)


def test_open_span_buckets_cross_thread_and_cleanup():
    entered, release = threading.Event(), threading.Event()
    t = threading.Thread(target=_worker_in_span,
                         args=("data_wait", entered, release), daemon=True)
    t.start()
    assert entered.wait(timeout=5.0)
    try:
        buckets = goodput_mod.open_span_buckets()
        assert buckets[t.ident] == "data_wait"
        # This thread has no open span -> absent, not "idle".
        assert threading.get_ident() not in buckets
    finally:
        release.set()
        t.join(timeout=5.0)
    # The outermost __exit__ drops the registry entry: a dead thread's
    # reused ident can never alias a stale stack.
    assert t.ident not in goodput_mod.open_span_buckets()


def test_step_pseudo_bucket_reads_as_compute():
    entered, release = threading.Event(), threading.Event()
    t = threading.Thread(target=_worker_in_span,
                         args=("step", entered, release), daemon=True)
    t.start()
    assert entered.wait(timeout=5.0)
    try:
        assert goodput_mod.open_span_buckets()[t.ident] == "compute"
    finally:
        release.set()
        t.join(timeout=5.0)


def test_nested_span_reports_innermost_bucket():
    entered, release = threading.Event(), threading.Event()

    def worker():
        with goodput_mod.span("compute"):
            with goodput_mod.span("exposed_comm"):
                entered.set()
                release.wait(timeout=5.0)

    t = threading.Thread(target=worker, daemon=True)
    t.start()
    assert entered.wait(timeout=5.0)
    try:
        assert goodput_mod.open_span_buckets()[t.ident] == "exposed_comm"
    finally:
        release.set()
        t.join(timeout=5.0)


# ---------------------------------------------------------------------------
# Deterministic sampling: the seeded-hot-function contract in miniature
# ---------------------------------------------------------------------------


def _hot_spin(release):
    while not release.is_set():
        sum(i * i for i in range(200))


def test_sample_once_names_hot_function_in_its_bucket():
    """The sampler's acceptance in unit form: a busy-loop inside a
    compute LedgerSpan must surface as the top self-time frame of the
    compute bucket, with the overwhelming share of its samples."""
    release = threading.Event()

    def worker():
        with goodput_mod.span("compute"):
            _hot_spin(release)

    t = threading.Thread(target=worker, daemon=True)
    # A second thread with NO open span: its samples must land in
    # unattributed (the sampler's own calling thread is skipped).
    idle = threading.Thread(target=release.wait, args=(10.0,),
                            daemon=True)
    t.start()
    idle.start()
    prof = StackProfiler()  # no thread: test drives sample_once()
    try:
        for _ in range(60):
            prof.sample_once()
            time.sleep(0.001)
    finally:
        release.set()
        t.join(timeout=5.0)
        idle.join(timeout=5.0)
    doc = prof.snapshot()
    assert doc["ticks"] == 60
    assert doc["samples_total"] >= 120  # both threads, every tick
    assert "compute" in doc["buckets"]
    frames = top_frames(doc, "compute", n=3)
    assert frames, "compute bucket collected no self samples"
    top_frame, top_self = frames[0]
    assert top_frame.startswith(("_hot_spin", "<genexpr>")), frames
    bucket_samples = doc["buckets"]["compute"]["samples"]
    hot = sum(s for f, s in flatten_self(
        doc["buckets"]["compute"]).items()
        if f.startswith(("_hot_spin", "<genexpr>")))
    assert hot >= 0.8 * bucket_samples, (hot, bucket_samples)
    # The idle, unspanned thread lands in unattributed; the sampling
    # thread itself is never in the doc (it skips its own ident).
    assert UNATTRIBUTED in doc["buckets"]
    assert doc["buckets"][UNATTRIBUTED]["samples"] >= 60


def test_sampler_thread_runs_and_publishes_throttled():
    tele = Telemetry(run_id="prof")
    prof = StackProfiler(telemetry=tele, rank=3, hz=200.0,
                         publish_interval_s=0.01)
    prof.start()
    try:
        deadline = time.time() + 5.0
        while time.time() < deadline:
            snap = tele.snapshot()
            section = (snap.get("sections") or {}).get(profile_mod.SECTION)
            if section and section.get("samples_total", 0) > 0:
                break
            time.sleep(0.01)
        else:
            pytest.fail("sampler never published a non-empty section")
    finally:
        final = prof.stop()
    assert final["rank"] == 3
    assert final["ticks"] > 0
    # stop() published the final doc and the overhead gauges.
    snap = tele.snapshot()
    section = (snap.get("sections") or {}).get(profile_mod.SECTION)
    assert section["samples_total"] == final["samples_total"]
    flat = snap["gauges"]
    assert any(k.startswith("profile.sample_tick_us") for k in flat)
    assert any(k.startswith("profile.samples_total") for k in flat)


# ---------------------------------------------------------------------------
# Trie bounds: coarsen, never drop
# ---------------------------------------------------------------------------


def test_trie_child_cap_folds_overflow_into_other():
    prof = StackProfiler(max_children=2)
    for i in range(10):
        prof._fold("compute", [f"f{i} (m.py:1)"])
    root = prof.snapshot()["buckets"]["compute"]
    assert root["samples"] == 10  # nothing dropped
    assert set(root["children"]) == {"f0 (m.py:1)", "f1 (m.py:1)",
                                     "(other)"}
    assert root["children"]["(other)"]["self"] == 8


def test_trie_node_budget_is_per_bucket():
    prof = StackProfiler(max_nodes=3)
    for i in range(6):
        prof._fold("compute", [f"f{i} (m.py:1)"])
    prof._fold("data_wait", ["g (m.py:2)"])
    buckets = prof.snapshot()["buckets"]
    # compute hit its budget and coarsened ...
    assert "(other)" in buckets["compute"]["children"]
    assert buckets["compute"]["samples"] == 6
    # ... without stealing data_wait's budget.
    assert set(buckets["data_wait"]["children"]) == {"g (m.py:2)"}


def test_depth_truncation_keeps_leaf_side():
    prof = StackProfiler(max_depth=3)
    keys = [f"d{i} (m.py:{i})" for i in range(8)]
    # Mirror sample_once()'s truncation (it operates on real frames).
    clipped = keys[-prof.max_depth:]
    prof._fold("compute", clipped)
    doc = prof.snapshot()
    flat = flatten_self(doc["buckets"]["compute"])
    # Self time lands on the true leaf; the dropped frames are the
    # root-side boilerplate.
    assert flat == {"d7 (m.py:7)": 1}
    assert "d0 (m.py:0)" not in json.dumps(doc["buckets"])


# ---------------------------------------------------------------------------
# Run-level merge + diff
# ---------------------------------------------------------------------------


def _doc(bucket, frame, n, rank=0):
    node = {"samples": n, "self": 0,
            "children": {frame: {"samples": n, "self": n, "children": {}}}}
    return {"rank": rank, "ticks": n, "samples_total": n, "truncated": 0,
            "bursts": 0, "wall_s": 1.0, "hz": 67.0,
            "buckets": {bucket: node}}


def test_merge_sections_sums_tries_nodewise():
    run = merge_sections({
        0: _doc("compute", "a (m.py:1)", 10, rank=0),
        1: _doc("compute", "a (m.py:1)", 6, rank=1),
    })
    assert run["kind"] == "profile_run"
    assert run["n_ranks"] == 2
    assert run["samples_total"] == 16
    node = run["buckets"]["compute"]["children"]["a (m.py:1)"]
    assert node["samples"] == 16 and node["self"] == 16
    assert set(run["per_rank"]) == {"0", "1"}
    # Non-profile garbage is skipped, not merged.
    assert merge_sections({0: {"nope": 1}})["n_ranks"] == 0


def test_sections_from_snapshots_skips_bare_ranks():
    snaps = {0: {"sections": {"profile": _doc("compute", "a (m.py:1)", 2)}},
             1: {"sections": {}},
             2: None}
    assert set(sections_from_snapshots(snaps)) == {0}


def test_diff_docs_compares_self_shares():
    cur = _doc("compute", "slow_path (m.py:9)", 80)
    cur["buckets"]["compute"]["children"]["fast (m.py:2)"] = {
        "samples": 20, "self": 20, "children": {}}
    cur["buckets"]["compute"]["samples"] = 100
    cur["samples_total"] = 100
    pri = _doc("compute", "slow_path (m.py:9)", 10)
    pri["buckets"]["compute"]["children"]["fast (m.py:2)"] = {
        "samples": 90, "self": 90, "children": {}}
    pri["buckets"]["compute"]["samples"] = 100
    pri["samples_total"] = 100
    diff = diff_docs(cur, pri)
    assert diff["kind"] == "profile_diff"
    frames = {f["frame"]: f for f in diff["buckets"]["compute"]["frames"]}
    grew = frames["slow_path (m.py:9)"]
    assert grew["delta"] == pytest.approx(0.7)
    assert grew["current_share"] == pytest.approx(0.8)
    shrank = frames["fast (m.py:2)"]
    assert shrank["delta"] == pytest.approx(-0.7)
    # Ranked by |delta|: both movers precede any noise.
    ranked = diff["buckets"]["compute"]["frames"]
    assert abs(ranked[0]["delta"]) >= abs(ranked[-1]["delta"])


# ---------------------------------------------------------------------------
# Collector: GET /profile (merged, last-good, 404 when empty)
# ---------------------------------------------------------------------------


def _exporter(tele):
    from sparktorch_tpu.native.gang import GangMetricsExporter

    return GangMetricsExporter(telemetry=tele, port=0).start()


def test_collector_profile_route_404_then_merged(tmp_path):
    from sparktorch_tpu.obs import ScrapeError, scrape_json

    sink = str(tmp_path / "sink.jsonl")
    teles = {r: Telemetry(run_id=f"rank{r}") for r in (0, 1)}
    exps = {r: _exporter(t) for r, t in teles.items()}
    collector = FleetCollector({r: e.url for r, e in exps.items()},
                               poll_interval_s=0, jsonl_path=sink)
    collector.start(poll_loop=False)
    try:
        collector.poll()
        # No rank has published a profile yet -> 404, like /goodput.
        with pytest.raises(ScrapeError):
            scrape_json(collector.url + "/profile")
        for r, tele in teles.items():
            tele.set_section(profile_mod.SECTION,
                             _doc("compute", "a (m.py:1)", 5 * (r + 1),
                                  rank=r))
        collector.poll()
        doc = scrape_json(collector.url + "/profile")
        assert doc["kind"] == "profile_run"
        assert doc["n_ranks"] == 2
        assert doc["samples_total"] == 15
        assert doc["run_id"] == collector.run_id
        node = doc["buckets"]["compute"]["children"]["a (m.py:1)"]
        assert node["self"] == 15
        # The sink carries a condensed profile.run line per sweep plus
        # the full tries on the gang snapshot (timeline's input).
        kinds = [json.loads(l)["kind"]
                 for l in open(sink) if l.strip()]
        assert "profile.run" in kinds
    finally:
        collector.stop()
        for e in exps.values():
            e.stop()
    # Last-good after death: the exporters are gone, but the merge
    # still serves the final published sections.
    assert collector.profile_view()["samples_total"] == 15


# ---------------------------------------------------------------------------
# timeline --profile / --diff
# ---------------------------------------------------------------------------


def _run_timeline(argv):
    from sparktorch_tpu.obs import timeline

    out = StringIO()
    with redirect_stdout(out):
        rc = timeline.main(argv)
    return rc, out.getvalue()


def test_timeline_profile_renders_saved_doc_and_sink(tmp_path):
    run = merge_sections({0: _doc("compute", "hot_fn (m.py:7)", 9)})
    saved = tmp_path / "profile.json"
    saved.write_text(json.dumps(run))
    rc, out = _run_timeline([str(saved), "--profile"])
    assert rc == 0
    assert "profile:" in out and "compute" in out and "hot_fn" in out
    # The collector-sink form: the newest gang_snapshot's profile_run
    # section wins.
    sink = tmp_path / "sink.jsonl"
    sink.write_text(json.dumps(
        {"kind": "gang_snapshot", "ts": 1.0,
         "sections": {"profile_run": run}}) + "\n")
    rc, out = _run_timeline([str(sink), "--profile"])
    assert rc == 0 and "hot_fn" in out
    # --json round-trips the doc itself.
    rc, out = _run_timeline([str(saved), "--profile", "--json"])
    assert rc == 0
    assert json.loads(out)["samples_total"] == 9


def test_timeline_profile_diff_and_arg_errors(tmp_path):
    cur = merge_sections({0: _doc("compute", "slow_path (m.py:9)", 8)})
    pri = merge_sections({0: _doc("compute", "fast (m.py:2)", 8)})
    cur_p, pri_p = tmp_path / "cur.json", tmp_path / "pri.json"
    cur_p.write_text(json.dumps(cur))
    pri_p.write_text(json.dumps(pri))
    rc, out = _run_timeline([str(cur_p), "--profile",
                             "--diff", str(pri_p)])
    assert rc == 0
    assert "profile diff" in out and "slow_path" in out
    # --diff without --profile is a usage error.
    rc, out = _run_timeline([str(cur_p), "--diff", str(pri_p)])
    assert rc == 2
    # A non-profile JSON document is refused, not mis-rendered.
    bogus = tmp_path / "bogus.json"
    bogus.write_text(json.dumps({"hello": 1}))
    rc, out = _run_timeline([str(bogus), "--profile"])
    assert rc == 1


# ---------------------------------------------------------------------------
# Postmortem: the victim's last-good profile rides in the bundle
# ---------------------------------------------------------------------------


def test_postmortem_bundle_carries_profile_section(tmp_path):
    from sparktorch_tpu.obs.blackbox import collect_postmortem

    tele = Telemetry(run_id="victim")
    tele.set_section(profile_mod.SECTION,
                     _doc("compute", "hot_fn (m.py:7)", 4))
    path = collect_postmortem(str(tmp_path), "test-death",
                              telemetry=tele)
    bundle = json.loads(open(path).read())
    assert bundle["profile"]["buckets"]["compute"]["samples"] == 4
    # And the report renderer names the frame under the death block.
    rc, out = _run_timeline([path, "--postmortem"])
    assert rc == 0
    assert "stack profile at death" in out and "hot_fn" in out


# ---------------------------------------------------------------------------
# Alert reflex: a latched firing opens a burst window
# ---------------------------------------------------------------------------


def test_alert_firing_triggers_burst_and_trace_event():
    from sparktorch_tpu.obs.alerts import AlertManager, AlertRule
    from sparktorch_tpu.obs.history import MetricsHistory

    tele = Telemetry(run_id="burst")
    records = []
    tele.add_sink(records.append)
    history = MetricsHistory()
    history.append({"ts": 1.0, "counters": {}, "gauges": {"loss": 9.0},
                    "histograms": {}})
    mgr = AlertManager(history, [AlertRule(name="loss-high",
                                           metric="loss",
                                           kind="threshold",
                                           threshold=1.0)],
                       telemetry=tele)
    prof = StackProfiler(telemetry=tele, hz=10.0)
    prof.attach_alerts(mgr, duration_s=30.0, hz=500.0)
    events = mgr.evaluate(ts=2.0)
    assert [e["event"] for e in events] == ["fired"]
    doc = prof.snapshot()
    assert doc["bursts"] == 1
    assert prof._burst_until > time.perf_counter()  # window still open
    assert prof._burst_hz == 500.0
    traces = [r for r in records if r["kind"] == "profile_trace"]
    assert len(traces) == 1
    assert traces[0]["alert"] == "loss-high"
    assert traces[0]["burst_hz"] == 500.0
    # resolved transitions do NOT re-burst.
    history.append({"ts": 3.0, "counters": {}, "gauges": {"loss": 0.0},
                    "histograms": {}})
    mgr.evaluate(ts=4.0)
    assert prof.snapshot()["bursts"] == 1
    # stop() detaches the subscriber (idempotent unsubscribe).
    prof.stop()
    assert mgr._subscribers == []


# ---------------------------------------------------------------------------
# Ambient install (the trainers' ensure() path)
# ---------------------------------------------------------------------------


def test_ensure_env_gate_and_rebind(monkeypatch):
    prev = profile_mod.install(None)
    try:
        monkeypatch.setenv(profile_mod.ENV_GATE, "0")
        assert profile_mod.ensure(Telemetry(run_id="x")) is None
        assert profile_mod.active() is None
        monkeypatch.setenv(profile_mod.ENV_GATE, "1")
        monkeypatch.setenv(profile_mod.ENV_HZ, "11.5")
        t1, t2 = Telemetry(run_id="a"), Telemetry(run_id="b")
        prof = profile_mod.ensure(t1, rank=0)
        try:
            assert prof is profile_mod.active()
            assert prof.hz == 11.5
            # Second trainer in the process: same sampler, rebound bus
            # (install-wins, like the ambient ledger).
            again = profile_mod.ensure(t2, rank=1)
            assert again is prof
            assert prof.telemetry is t2 and prof.rank == 1
        finally:
            prof.stop()
    finally:
        profile_mod.install(prev)

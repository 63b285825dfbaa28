"""Offline trace analysis (obs.xprof) + timeline rendering (obs.timeline).

The golden fixtures under tests/fixtures/xprof/ make these tests
profiler-free: a handcrafted Chrome trace with EXACT expected
attribution (synthetic_overlap) and a real CPU-backend capture of a
dp×tp-sharded step (cpu_allreduce) — regenerate with
tests/fixtures/xprof/make_fixtures.py. A live capture→analyze→publish
round-trip test runs last and skips gracefully if the runtime emits
no trace events.
"""

import gzip
import json
import os

import numpy as np
import pytest

from sparktorch_tpu.obs import Telemetry, read_jsonl
from sparktorch_tpu.obs.xprof import (
    TraceParseError,
    analyze_and_publish,
    analyze_trace,
    classify_op,
    find_trace_file,
)

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures", "xprof")
SYNTHETIC = os.path.join(FIXTURES, "synthetic_overlap.trace.json.gz")
CPU_GOLDEN = os.path.join(FIXTURES, "cpu_allreduce.trace.json.gz")
MOE_GOLDEN = os.path.join(FIXTURES, "cpu_moe_a2a.trace.json.gz")


# ---------------------------------------------------------------------------
# Classification
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name,family", [
    ("all-reduce", "all_reduce"),
    ("all-reduce.123", "all_reduce"),
    ("all-reduce-start.2", "all_reduce"),
    ("AllReduce", "all_reduce"),
    ("ncclAllReduceKernel", "all_reduce"),
    ("cross-replica-sum.1", "all_reduce"),
    ("all-gather.7", "all_gather"),
    ("reduce-scatter.3", "reduce_scatter"),
    ("all-to-all.9", "all_to_all"),
    ("AllToAll", "all_to_all"),
    ("collective-permute.1", "ppermute"),
    ("send.4", "send_recv"),
    ("recv-done.2", "send_recv"),
    ("collective-broadcast.1", "send_recv"),
    # Compute / non-collectives.
    ("dot", None),
    ("fusion.23", None),
    ("reduce-window", None),          # not reduce-scatter
    ("reduce.5", None),
    ("dynamic-update-slice", None),
    ("convolution.2", None),
])
def test_classify_op(name, family):
    assert classify_op(name) == family


# ---------------------------------------------------------------------------
# Golden: synthetic trace with exact expected math
# ---------------------------------------------------------------------------


def test_synthetic_golden_exact_attribution():
    a = analyze_trace(SYNTHETIC)
    assert [s.step for s in a.steps] == [0, 1]
    assert a.n_device_events == 8          # module lane + host noise excluded
    assert a.n_collective_events == 5
    assert a.n_unattributed == 1           # the pre-step fusion.0

    s0, s1 = a.steps
    us = 1e-6
    # Step 0: compute 600us, one 500us all-reduce, 200us of it hidden.
    assert s0.wall_s == pytest.approx(1000 * us)
    assert s0.window_s == pytest.approx(1000 * us)
    assert s0.compute_s == pytest.approx(600 * us)
    assert s0.comm_s == pytest.approx(500 * us)
    assert s0.overlap_s == pytest.approx(200 * us)
    assert s0.comm_fraction == pytest.approx(0.5)
    assert s0.overlap_fraction == pytest.approx(0.4)
    assert s0.families == {"all_reduce": pytest.approx(500 * us)}
    assert s0.counts == {"all_reduce": 1}
    # Step 1: ag 200us + a2a 100us + two CONCURRENT reduce-scatters
    # (union 100us, count 2); zero overlap with the 300us of compute.
    assert s1.wall_s == pytest.approx(800 * us)
    assert s1.compute_s == pytest.approx(300 * us)
    assert s1.comm_s == pytest.approx(400 * us)
    assert s1.overlap_s == 0.0
    assert s1.families == {
        "all_gather": pytest.approx(200 * us),
        "all_to_all": pytest.approx(100 * us),
        "reduce_scatter": pytest.approx(100 * us),
    }
    assert s1.counts["reduce_scatter"] == 2

    # Aggregates.
    assert a.comm_s == pytest.approx(900 * us)
    assert a.comm_fraction == pytest.approx(0.5)
    assert a.overlap_fraction == pytest.approx(200 / 900)
    assert a.family_counts() == {"all_reduce": 1, "all_gather": 1,
                                 "all_to_all": 1, "reduce_scatter": 2}
    # Top op by device-seconds is the 600us fusion.
    assert a.top_ops[0]["name"] == "fusion.1"
    assert a.top_ops[0]["family"] == "compute"


def test_cpu_golden_capture_structure():
    """The REAL capture: a dp(4)×tp(2) sharded matmul step on the CPU
    backend — 2 all-reduce HLOs × 8 device lanes × 3 annotated steps.
    Event counts are deterministic for the frozen file; timings are
    whatever the generating machine did, so those are asserted as
    invariants (positivity, fractions in range, wall == marker dur)."""
    a = analyze_trace(CPU_GOLDEN)
    assert [s.step for s in a.steps] == [0, 1, 2]
    assert a.n_device_events == 144
    assert a.n_collective_events == 48
    assert a.n_unattributed == 0
    assert a.family_counts() == {"all_reduce": 48}
    for s in a.steps:
        assert s.counts == {"all_reduce": 16}
        assert s.wall_s > 0 and s.window_s >= s.wall_s > 0
        assert s.comm_s > 0 and s.compute_s > 0
        assert 0 < s.comm_fraction <= 1
        assert 0 <= s.overlap_fraction <= 1
        # Union walls can never exceed the slice window.
        assert s.comm_s <= s.window_s and s.compute_s <= s.window_s
    assert a.top_ops[0]["family"] == "all_reduce"


def test_moe_a2a_golden_capture_classification():
    """Real capture of the GSPMD MoE trainer on dp4 x ep2 (frozen by
    make_fixtures.write_moe_capture): the explicit shard_map
    dispatch/combine all-to-alls must land in the analyzer's COMM lane
    as family ``all_to_all`` — not "other"/unclassified — at exactly 4
    a2a HLOs x 8 device lanes per step (dispatch + combine, forward +
    backward, one MoE layer), with ZERO all-gathers anywhere in the
    capture (the token-replication signature the dispatch rewrite
    killed; the HLO-level twin of this pin lives in
    tests/test_moe.py::test_moe_ep2_hlo_no_token_all_gather)."""
    a = analyze_trace(MOE_GOLDEN)
    assert [s.step for s in a.steps] == [0, 1, 2]
    counts = a.family_counts()
    assert counts.get("all_to_all") == 96  # 4 HLOs x 8 lanes x 3 steps
    assert "all_gather" not in counts, counts
    for s in a.steps:
        assert s.counts.get("all_to_all") == 32
        # In the comm lane for real: the family contributes measured
        # union wall, and the step's comm_s covers it.
        assert s.families["all_to_all"] > 0
        assert s.comm_s >= s.families["all_to_all"] > 0
        assert 0 < s.comm_fraction <= 1
    assert a.n_collective_events == sum(counts.values())
    # The dispatch a2a is prominent enough to surface in top_ops with
    # its family attributed (a classification regression would show it
    # as 'compute'/'other').
    assert any(o["family"] == "all_to_all" for o in a.top_ops)


def test_hlo_collective_bytes_parser():
    """The static HLO byte analyzer (tests/test_moe.py's ground
    truth) reads shapes and families off real HLO spellings — incl.
    -start/-done async pairs counted ONCE and tuple-shaped results."""
    from sparktorch_tpu.obs.xprof import hlo_collective_bytes

    hlo = """
  %all-to-all.1 = bf16[8,4,3,5]{3,2,1,0} all-to-all(bf16[8,4,3,5] %p0)
  %ag = f32[16,32]{1,0} all-gather(f32[4,32] %p1), dimensions={0}
  %ar = (f32[128]{0}, f32[64]{0}) all-reduce(%a, %b), to_apply=%sum
  %cp = u32[2]{0} collective-permute(u32[2] %p2)
  %done = bf16[8,4,3,5]{3,2,1,0} all-to-all-done(%all-to-all.1)
  %not_a_coll = f32[8]{0} add(f32[8] %x, f32[8] %y)
"""
    stats = hlo_collective_bytes(hlo)
    assert stats["counts"] == {"all_to_all": 1, "all_gather": 1,
                               "all_reduce": 1, "ppermute": 1}
    assert stats["bytes"]["all_to_all"] == 8 * 4 * 3 * 5 * 2
    assert stats["bytes"]["all_gather"] == 16 * 32 * 4
    assert stats["bytes"]["all_reduce"] == (128 + 64) * 4
    assert stats["bytes"]["ppermute"] == 2 * 4
    assert stats["total_bytes"] == sum(stats["bytes"].values())

    # Async -start tuple results alias the INPUT buffer beside the
    # real result (the TPU/GPU lowering) — one transfer, counted once.
    async_hlo = """
  %ar-start = (f32[128]{0}, f32[128]{0}) all-reduce-start(f32[128] %p)
  %ar-done = f32[128]{0} all-reduce-done(%ar-start)
"""
    a = hlo_collective_bytes(async_hlo)
    assert a["counts"] == {"all_reduce": 1}
    assert a["bytes"]["all_reduce"] == 128 * 4


def test_publish_scrape_equals_jsonl_dump(tmp_path):
    """The publish→scrape→dump round-trip the ISSUE gates: xprof
    histograms and counters read IDENTICALLY from a real /metrics
    scrape and a JSONL telemetry dump (one snapshot feeds both)."""
    import urllib.request

    from sparktorch_tpu.native.gang import GangMetricsExporter
    from sparktorch_tpu.obs import parse_prometheus

    tele = Telemetry(run_id="xprof_parity")
    analyze_trace(SYNTHETIC).publish(tele)

    assert tele.histogram("xprof.step_wall_s")["count"] == 2
    assert tele.histogram("xprof.collective_time_s",
                          labels={"op": "all_reduce"})["count"] == 1
    assert tele.counter_value("xprof.collectives_total",
                              labels={"op": "reduce_scatter"}) == 2
    assert tele.counter_value("xprof.steps_total") == 2

    with GangMetricsExporter(telemetry=tele) as exporter:
        with urllib.request.urlopen(exporter.url + "/metrics") as resp:
            scraped = parse_prometheus(resp.read().decode())
    path = str(tmp_path / "dump.jsonl")
    tele.dump(path)
    (snap,) = read_jsonl(path)

    assert snap["counters"]["xprof.collectives_total{op=all_reduce}"] == 1
    assert snap["counters"]["xprof.collectives_total{op=reduce_scatter}"] == 2
    assert scraped[
        'sparktorch_xprof_collectives_total{op="reduce_scatter"}'] == 2.0
    # Histogram roll-ups agree series by series.
    for fam in ("all_reduce", "all_gather", "all_to_all", "reduce_scatter"):
        roll = snap["histograms"][f"xprof.collective_time_s{{op={fam}}}"]
        key = f'sparktorch_xprof_collective_time_s_sum{{op="{fam}"}}'
        assert scraped[key] == pytest.approx(roll["sum"])
        assert scraped[
            f'sparktorch_xprof_collective_time_s_count{{op="{fam}"}}'
        ] == roll["count"]
    assert scraped["sparktorch_xprof_comm_fraction_run"] == pytest.approx(
        snap["gauges"]["xprof.comm_fraction_run"]) == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# Malformed / edge inputs
# ---------------------------------------------------------------------------


def test_malformed_traces_rejected(tmp_path):
    # Truncated gzip.
    p = tmp_path / "torn.trace.json.gz"
    p.write_bytes(gzip.compress(b'{"traceEvents": [')[:20])
    with pytest.raises(TraceParseError):
        analyze_trace(str(p))
    # Valid gzip, invalid JSON.
    p2 = tmp_path / "bad.trace.json.gz"
    with gzip.open(p2, "wt") as f:
        f.write('{"traceEvents": [')
    with pytest.raises(TraceParseError):
        analyze_trace(str(p2))
    # Valid JSON, wrong shape.
    for payload in ("[1, 2]", '{"no": "traceEvents"}',
                    '{"traceEvents": "nope"}'):
        p3 = tmp_path / "shape.trace.json"
        p3.write_text(payload)
        with pytest.raises(TraceParseError):
            analyze_trace(str(p3))
    with pytest.raises(TraceParseError):
        analyze_trace({"not_a_trace": True})
    # Missing file / empty dir.
    with pytest.raises(TraceParseError):
        analyze_trace(str(tmp_path / "nope"))
    empty = tmp_path / "emptydir"
    empty.mkdir()
    with pytest.raises(TraceParseError):
        analyze_trace(str(empty))


def test_analyze_and_publish_is_failure_safe(tmp_path):
    tele = Telemetry()
    assert analyze_and_publish(str(tmp_path), telemetry=tele) is None
    assert tele.counter_value("xprof.analyze_failures") == 1.0
    assert tele.counter_value("xprof.analyses_total") == 0.0


def test_analyze_and_publish_survives_publish_failure():
    """The never-fail-the-run contract covers PUBLISH too: a sink
    that raises mid-publish (disk full under a JSONL sink) must not
    escape into the profiled run."""
    tele = Telemetry()

    def broken_sink(record):
        raise OSError("disk full")

    tele.add_sink(broken_sink)
    assert analyze_and_publish(SYNTHETIC, telemetry=tele) is None
    assert tele.counter_value("xprof.analyze_failures") == 1.0


def test_overlapping_markers_collapse_to_aggregate_slice():
    """Concurrent step markers (hogwild: N worker threads annotating
    their own local steps) make start->next-start slicing meaningless;
    the analyzer must detect the overlap and attribute the capture as
    ONE aggregate slice — honest totals, no garbage per-step walls."""
    events = [
        # Two workers' markers overlapping in time, duplicate nums.
        {"ph": "X", "pid": 1, "tid": 1, "name": "train_step",
         "ts": 1000, "dur": 1000, "args": {"step_num": "0"}},
        {"ph": "X", "pid": 1, "tid": 2, "name": "train_step",
         "ts": 1500, "dur": 1000, "args": {"step_num": "0"}},
        {"ph": "X", "pid": 1, "tid": 3, "name": "dot",
         "ts": 1200, "dur": 400},
        {"ph": "X", "pid": 1, "tid": 4, "name": "all-reduce.1",
         "ts": 1400, "dur": 600},
    ]
    a = analyze_trace({"traceEvents": events})
    assert a.markers_overlap is True and a.n_markers == 2
    assert len(a.steps) == 1 and a.steps[0].step is None
    assert a.comm_s == pytest.approx(600e-6)
    assert a.steps[0].compute_s == pytest.approx(400e-6)
    # Sequential markers stay sliced per step.
    b = analyze_trace(SYNTHETIC)
    assert b.markers_overlap is False and b.n_markers == 2
    assert len(b.steps) == 2


def test_find_trace_file_prefers_newest(tmp_path):
    d = tmp_path / "plugins" / "profile" / "run1"
    d.mkdir(parents=True)
    old = d / "old.trace.json.gz"
    new = d / "new.trace.json.gz"
    for p in (old, new):
        with gzip.open(p, "wt") as f:
            json.dump({"traceEvents": []}, f)
    past = os.path.getmtime(new) - 100
    os.utime(old, (past, past))
    assert find_trace_file(str(tmp_path)) == str(new)
    assert find_trace_file(str(new)) == str(new)


def test_no_markers_whole_trace_pseudo_step():
    events = [
        {"ph": "X", "pid": 1, "tid": 2, "name": "dot", "ts": 100, "dur": 50},
        {"ph": "X", "pid": 1, "tid": 3, "name": "all-gather.1",
         "ts": 120, "dur": 40},
    ]
    a = analyze_trace({"traceEvents": events})
    assert len(a.steps) == 1 and a.steps[0].step is None
    assert a.steps[0].comm_s == pytest.approx(40e-6)
    assert a.steps[0].overlap_s == pytest.approx(30e-6)
    # Skips garbage events rather than dying on them.
    a2 = analyze_trace({"traceEvents": events + [
        {"ph": "X", "name": "dot"},                      # no ts
        {"ph": "X", "name": "dot", "ts": "x", "dur": 1},  # bad ts
        "not-an-event", None, 42,
    ]})
    assert a2.n_device_events == 2


# ---------------------------------------------------------------------------
# Timeline rendering + CLI
# ---------------------------------------------------------------------------


def test_render_report_golden():
    from sparktorch_tpu.obs.timeline import render_report

    text = render_report(analyze_trace(SYNTHETIC))
    assert "steps: 2" in text
    assert "all_reduce" in text and "reduce_scatter" in text
    assert "x2" in text                      # the concurrent rs pair
    assert "budget:" in text
    assert "fusion.1" in text                # top op
    assert "50.0% of windows" in text        # comm fraction


def test_render_snapshot_report_matches_bus():
    from sparktorch_tpu.obs.timeline import render_snapshot_report

    tele = Telemetry(run_id="snap_render")
    analyze_trace(SYNTHETIC).publish(tele)
    text = render_snapshot_report(tele.snapshot())
    assert "steps analyzed: 2" in text
    assert "all_reduce" in text
    assert "comm fraction: 50.0%" in text


def test_timeline_cli_trace_jsonl_and_errors(tmp_path, capsys):
    from sparktorch_tpu.obs.timeline import main

    # Trace mode.
    assert main([SYNTHETIC]) == 0
    assert "budget:" in capsys.readouterr().out
    # --json mode emits one parseable object.
    assert main([SYNTHETIC, "--json"]) == 0
    d = json.loads(capsys.readouterr().out)
    assert d["n_steps"] == 2 and d["collective_counts"]["reduce_scatter"] == 2
    # JSONL mode: render the budget from a telemetry dump.
    tele = Telemetry(run_id="cli")
    analyze_trace(SYNTHETIC).publish(tele)
    dump = str(tmp_path / "t.jsonl")
    tele.dump(dump)
    assert main([dump]) == 0
    assert "steps analyzed: 2" in capsys.readouterr().out
    # JSONL without xprof metrics -> error exit.
    Telemetry(run_id="empty").dump(str(tmp_path / "e.jsonl"))
    assert main([str(tmp_path / "e.jsonl")]) == 1
    capsys.readouterr()
    # Missing JSONL -> clean error exit, same contract as a bad trace.
    assert main([str(tmp_path / "missing.jsonl")]) == 1
    assert capsys.readouterr().out.startswith("error:")
    # Malformed trace -> error exit, no traceback.
    bad = tmp_path / "bad.trace.json"
    bad.write_text("{")
    assert main([str(bad)]) == 1


# ---------------------------------------------------------------------------
# Live capture -> analyze -> publish round-trip (no fixtures)
# ---------------------------------------------------------------------------


def test_live_capture_roundtrip(tmp_path):
    """profile_run's stop hook auto-analyzes the capture it just wrote
    and publishes xprof.* onto the bus. Runs a real dp×tp-sharded
    matmul (all-reduces on the 8-device world); skips gracefully if
    this runtime emits no usable trace events."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from sparktorch_tpu.utils.tracing import profile_run, step_annotation

    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("dp", "tp"))

    @jax.jit
    def step(xx, ww):
        y = xx @ ww
        return jnp.sum(y * y)

    x = jax.device_put(np.ones((16, 32), np.float32),
                       NamedSharding(mesh, P("dp", None)))
    w = jax.device_put(np.ones((32, 32), np.float32),
                       NamedSharding(mesh, P(None, "tp")))
    step(x, w).block_until_ready()  # compile outside the capture

    tele = Telemetry(run_id="live_roundtrip")
    with profile_run(str(tmp_path / "trace"), telemetry=tele) as handle:
        for i in range(2):
            with step_annotation(i, telemetry=tele):
                step(x, w).block_until_ready()

    analysis = handle["analysis"]
    if analysis is None or analysis.n_device_events == 0:
        pytest.skip("runtime emitted no trace events")
    assert len(analysis.steps) == 2
    assert analysis.n_collective_events >= 1
    assert "all_reduce" in analysis.family_counts()
    # Published onto the SAME bus the annotations used.
    assert tele.counter_value("xprof.analyses_total") == 1.0
    assert tele.counter_value("xprof.steps_total") == 2.0
    assert tele.histogram("xprof.comm_fraction")["count"] == 2
    assert tele.histogram(
        "xprof.collective_time_s", labels={"op": "all_reduce"})["count"] >= 1
    # Step walls reconcile with the annotation durations by
    # construction; fractions stay in range.
    for s in analysis.steps:
        assert 0 <= s.comm_fraction <= 1
        assert 0 <= s.overlap_fraction <= 1

"""Trace-guided mesh auto-tuner: deterministic candidate enumeration
and pruning on synthetic shapes, comm cost-model monotonicity, scoring
from the golden xprof fixture (no backend), artifact round-trip, the
decision loop against an injected measurer, and ``mesh="auto"``
end-to-end on the 8-device CPU rig.
"""

import json
import os

import numpy as np
import pytest

from sparktorch_tpu.parallel.mesh import MeshConfig
from sparktorch_tpu.parallel.tune import (
    ALPHA_ENV,
    GSPMD_AXES,
    Candidate,
    TuneResult,
    WorkloadShape,
    autotune,
    calibrate_alpha_bytes,
    candidate_label,
    enumerate_candidates,
    mesh_label,
    pp_bubble_fraction,
    pp_schedule_metas,
    pp_schedule_ticks,
    predict_comm_bytes,
    resolve_alpha_bytes,
    score_analysis,
    transformer_caps,
    transformer_workload,
)

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures", "xprof")
SYNTHETIC = os.path.join(FIXTURES, "synthetic_overlap.trace.json.gz")


# ---------------------------------------------------------------------------
# Enumeration (backend-free)
# ---------------------------------------------------------------------------


def test_enumerate_candidates_deterministic_and_legal():
    """8 devices, tp capped by 2 heads, sp by a 4-token sequence, no
    experts, pp by a 2-layer stack: the exact legal set, in the exact
    deterministic order (ascending (fsdp, tp, sp, ep, pp) tuples —
    pure dp first). pp=2 meshes appear (the schedule search is opened)
    but never combined with fsdp (no trainer runs pp x fsdp)."""
    caps = {"fsdp": (64,), "tp": (2, 128, 256), "sp": (4,), "ep": (1,),
            "pp": (2,)}
    got = [c.resolve(8) for c in enumerate_candidates(8, caps, 32)]
    labels = [mesh_label(s) for s in got]
    assert labels == [
        "dp8", "dp4xpp2", "dp4xsp2", "dp2xsp2xpp2",
        "dp2xsp4", "sp4xpp2",
        "dp4xtp2", "dp2xtp2xpp2", "dp2xtp2xsp2", "tp2xsp2xpp2",
        "tp2xsp4",
        "dp4xfsdp2", "dp2xfsdp2xsp2", "fsdp2xsp4",
        "dp2xfsdp2xtp2", "fsdp2xtp2xsp2",
        "dp2xfsdp4", "fsdp4xsp2", "fsdp4xtp2", "fsdp8",
    ]
    for sizes in got:
        # Every candidate fills the device world exactly.
        prod = 1
        for v in sizes.values():
            prod *= v
        assert prod == 8
        # And respects its caps: tp | 2, sp | 4, ep == 1, pp | 2.
        assert 2 % sizes["tp"] == 0
        assert 4 % sizes["sp"] == 0
        assert sizes["ep"] == 1
        assert 2 % sizes["pp"] == 0
        # No trainer runs pp x fsdp.
        assert not (sizes["pp"] > 1 and sizes["fsdp"] > 1)
        # Batch axes divide the global batch.
        assert 32 % (sizes["dp"] * sizes["fsdp"]) == 0
    # Same inputs -> same list (determinism is what goldens pin).
    again = [c.resolve(8) for c in enumerate_candidates(8, caps, 32)]
    assert again == got


def test_enumerate_candidates_batch_and_expert_caps():
    # A global batch of 4 forbids dp*fsdp == 8.
    caps = {"fsdp": (64,), "tp": (1,), "sp": (1,), "ep": (1,), "pp": (1,)}
    labels = [mesh_label(c.resolve(8))
              for c in enumerate_candidates(8, caps, 4)]
    assert labels == []  # dp*fsdp must be 8, but 4 % 8 != 0
    # 4 experts open ep in {1, 2, 4}; ep=8 stays illegal.
    caps = {"fsdp": (1,), "tp": (1,), "sp": (1,), "ep": (4,), "pp": (1,)}
    labels = [mesh_label(c.resolve(8))
              for c in enumerate_candidates(8, caps, 32)]
    assert labels == ["dp8", "dp4xep2", "dp2xep4"]


def test_transformer_caps_follow_model_dims():
    from sparktorch_tpu.models import tiny_transformer

    cfg = tiny_transformer(max_len=16)  # heads=4, d_ff=128, vocab=256
    caps = transformer_caps(cfg, seq_len=8)
    assert caps["tp"] == (4, 128, 256)
    assert caps["sp"] == (8,)
    assert caps["ep"] == (1,)          # dense model: ep locked to 1
    moe = tiny_transformer(n_experts=4)
    assert transformer_caps(moe)["ep"] == (4,)


# ---------------------------------------------------------------------------
# Cost model (backend-free)
# ---------------------------------------------------------------------------


def test_cost_model_monotone_in_replicated_bytes():
    """More replicated gradient bytes -> strictly higher predicted
    comm, for every config that reduces gradients (dp or fsdp > 1)."""
    small = WorkloadShape(param_bytes=1e6, tp_param_bytes=1e6,
                          global_batch=32, seq_len=16, d_model=64,
                          n_layers=2)
    big = WorkloadShape(param_bytes=2e6, tp_param_bytes=2e6,
                        global_batch=32, seq_len=16, d_model=64,
                        n_layers=2)
    for cfg in (MeshConfig(), MeshConfig(fsdp=2), MeshConfig(tp=2),
                MeshConfig(fsdp=2, tp=2)):
        lo = predict_comm_bytes(cfg, small, 8)
        hi = predict_comm_bytes(cfg, big, 8)
        assert hi["total_bytes"] > lo["total_bytes"], cfg
        assert hi["total_cost"] > lo["total_cost"], cfg


def test_cost_model_terms_and_alpha():
    shape = WorkloadShape(param_bytes=8e6, tp_param_bytes=8e6,
                          global_batch=64, seq_len=32, d_model=128,
                          n_layers=4)
    pure_dp = predict_comm_bytes(MeshConfig(), shape, 8)
    # Pure dp: one bucketed grad all-reduce, nothing else.
    assert pure_dp["collective_ops"] == 1
    assert pure_dp["tp_all_reduce"] == 0 and pure_dp["sp_ppermute"] == 0
    # Ring all-reduce of the full replica: 2 * (7/8) * bytes per dev.
    assert pure_dp["dp_all_reduce"] == pytest.approx(
        8 * 2 * (7 / 8) * 8e6)
    tp = predict_comm_bytes(MeshConfig(tp=2), shape, 8)
    # tp shards the grads (smaller dp term) but pays per-layer
    # activation all-reduces (2 per layer) in ops and bytes.
    assert tp["dp_all_reduce"] < pure_dp["dp_all_reduce"]
    assert tp["tp_all_reduce"] > 0
    assert tp["collective_ops"] == 1 + 2 * 4


def test_ep_a2a_byte_model_capacity_scaling():
    """The ep dispatch/combine term models the EXPLICIT shard_map
    lowering: (G, e, cap, d) capacity blocks — tokens expanded by
    capacity_factor x top_k — with a (ep-1)/ep wire fraction. Linear
    in both expansion knobs, zero at ep=1, monotone in ep; grounded
    against the compiled program's bytes in tests/test_moe.py."""
    import dataclasses

    base = WorkloadShape(param_bytes=1e6, tp_param_bytes=1e6,
                         global_batch=64, seq_len=32, d_model=128,
                         n_layers=4, n_moe_layers=2, dtype_bytes=2,
                         moe_capacity_factor=1.25, moe_top_k=2)
    ep2 = predict_comm_bytes(MeshConfig(ep=2), base, 8)
    assert ep2["ep_all_to_all"] > 0
    # 2 a2as per MoE layer in the op count.
    assert ep2["collective_ops"] == 1 + 2 * 2
    # Linear in capacity_factor and top_k.
    cf2 = dataclasses.replace(base, moe_capacity_factor=2.5)
    assert predict_comm_bytes(MeshConfig(ep=2), cf2, 8)[
        "ep_all_to_all"] == pytest.approx(2 * ep2["ep_all_to_all"])
    k1 = dataclasses.replace(base, moe_top_k=1)
    assert predict_comm_bytes(MeshConfig(ep=2), k1, 8)[
        "ep_all_to_all"] == pytest.approx(ep2["ep_all_to_all"] / 2)
    # No experts crossing the wire at ep=1; more ep -> more exposed.
    assert predict_comm_bytes(MeshConfig(), base, 8)["ep_all_to_all"] == 0.0
    ep4 = predict_comm_bytes(MeshConfig(ep=4), base, 8)
    assert ep4["ep_all_to_all"] > ep2["ep_all_to_all"]


def test_tune_cache_key_fences_pre_rewrite_ep_entries():
    """The cache key carries the MoE dispatch generation (schema 2 +
    shard_map_a2a marker) and the capacity knobs: a pre-rewrite entry
    — or one searched under different expert capacity — can never
    satisfy an ep search against the new lowering."""
    import dataclasses

    from sparktorch_tpu.models.transformer import tiny_transformer
    from sparktorch_tpu.parallel.tune import tune_cache_key

    cfg = tiny_transformer(n_experts=4, moe_top_k=2, capacity_factor=1.5)
    shape = transformer_workload(cfg, 64)
    # The workload shape carries the expansion knobs the a2a term uses.
    assert shape.moe_capacity_factor == 1.5
    assert shape.moe_top_k == 2
    caps = transformer_caps(cfg)
    devices = [object()]  # fingerprint only reads attrs defensively

    def key(s):
        return tune_cache_key(s, caps, ("dp", "ep"), devices,
                              seq_sharded=False, measure_top_k=4,
                              exposed_weight=0.25)

    k = key(shape)
    assert k != key(dataclasses.replace(shape, moe_capacity_factor=2.0))
    assert k != key(dataclasses.replace(shape, moe_top_k=1))
    # Same inputs -> same key (the cache still hits at all).
    assert k == key(dataclasses.replace(shape))
    # The alpha term orders equal-byte configs by launch count.
    a0 = predict_comm_bytes(MeshConfig(tp=2), shape, 8, alpha_bytes=0)
    a1 = predict_comm_bytes(MeshConfig(tp=2), shape, 8,
                            alpha_bytes=1 << 20)
    assert a1["total_cost"] == pytest.approx(
        a0["total_cost"] + (1 << 20) * a0["collective_ops"])


# ---------------------------------------------------------------------------
# Scoring from the golden fixture (no backend)
# ---------------------------------------------------------------------------


def test_score_from_golden_fixture_exact():
    """The synthetic_overlap fixture has exact known attribution
    (walls 1000us/800us, comm 500/400us, overlap 200/0us) — so the
    scoring hook's numbers are closed-form."""
    from sparktorch_tpu.obs.xprof import analyze_trace

    a = analyze_trace(SYNTHETIC)
    us = 1e-6
    stats = a.step_wall_stats()
    assert stats["n"] == 2
    assert stats["median_s"] == pytest.approx(900 * us)
    assert stats["min_s"] == pytest.approx(800 * us)
    assert stats["max_s"] == pytest.approx(1000 * us)
    # p75 - p25 of [800, 1000]us interpolates to 950 - 850.
    assert stats["spread_s"] == pytest.approx(100 * us)
    # Exposed comm: (500-200) + (400-0) = 700us over 1800us of window.
    assert a.exposed_comm_s == pytest.approx(700 * us)
    assert a.exposed_comm_fraction == pytest.approx(700 / 1800)
    score, measured = score_analysis(a, exposed_weight=0.25)
    assert score == pytest.approx(900 * us * (1 + 0.25 * 700 / 1800))
    assert measured["step_wall_s"] == pytest.approx(900 * us)
    assert measured["exposed_comm_fraction"] == pytest.approx(700 / 1800)
    assert measured["n_collective_events"] == 5
    # Zero weight: the score IS the median wall.
    score0, _ = score_analysis(a, exposed_weight=0.0)
    assert score0 == pytest.approx(900 * us)


# ---------------------------------------------------------------------------
# Decision loop with an injected measurer (no backend)
# ---------------------------------------------------------------------------


def _fake_spec_and_batch():
    """A ModelSpec whose module carries a TransformerConfig, plus a
    batch — none of it is ever executed (measure_fn is injected)."""
    from sparktorch_tpu.models import SequenceClassifier, tiny_transformer
    from sparktorch_tpu.utils.data import DataBatch
    from sparktorch_tpu.utils.serde import ModelSpec

    module = SequenceClassifier(tiny_transformer(max_len=16))
    spec = ModelSpec(module=module, loss="cross_entropy")
    batch = DataBatch(
        x=np.zeros((32, 16), np.int32),
        y=np.zeros((32,), np.int32),
        w=np.ones((32,), np.float32),
    )
    return spec, batch


def _fake_measure(walls):
    """measure_fn (prepare_candidate contract): scripted
    ``(wall, half_spread)`` per mesh label — each round's runner
    returns walls ``[w-s, w, w+s]`` so the pooled median is ``w`` and
    the spread scales with ``s``."""

    def prepare(spec, config, batch, devices, tx=None,
                seq_sharded=False, telemetry=None):
        label = mesh_label(config.resolve(len(devices)))
        wall, s = walls[label]

        def runner(steps):
            base = [wall - s, wall, wall + s]
            return {"walls": (base * steps)[:max(steps, 1)],
                    "comm_fraction": 0.3, "overlap_fraction": 0.5,
                    "exposed_comm_fraction": 0.1,
                    "n_collective_events": steps, "counts": {},
                    "loss": 0.0}

        runner.compile_s = 1.0
        return runner

    return prepare


def test_autotune_prunes_measures_and_ranks():
    spec, batch = _fake_spec_and_batch()
    devices = list(range(8))  # the fake measurer only len()s these
    # Half-spreads of 2ms keep the noise floor ABOVE the 1ms wall
    # gaps, so the round loop never early-stops.
    walls = {label: (0.010 + 0.001 * i, 0.002)
             for i, label in enumerate([
                 "dp8", "fsdp8", "fsdp4xtp2", "dp2xfsdp4", "dp4xfsdp2",
                 "dp4xtp2", "dp2xtp4", "fsdp2xtp4", "dp2xfsdp2xtp2"])}
    walls["fsdp8"] = (0.008, 0.002)  # scripted winner, rank 2 by cost
    result = autotune(spec, batch, devices, steps=3, repeats=3,
                      measure_top_k=4, noise_mult=2.0,
                      axes=GSPMD_AXES, measure_fn=_fake_measure(walls),
                      alpha_bytes=1 << 20)
    assert result.best_label == "fsdp8"
    assert not result.early_stopped and result.rounds_run == 3
    statuses = {c.label: c.status for c in result.candidates}
    assert sum(s == "measured" for s in statuses.values()) == 4
    assert sum(s == "pruned" for s in statuses.values()) == 5
    # Pruned candidates carry the model's reasoning, never a
    # measurement.
    for c in result.candidates:
        if c.status == "pruned":
            assert c.measured is None and "comm_model" in c.reason
    # The ranking is measured-only, best first.
    ranked = result.ranking()
    assert ranked[0].label == "fsdp8"
    assert [c.label for c in ranked] == sorted(
        (c.label for c in result.candidates if c.status == "measured"),
        key=lambda l: walls[l][0],
    )
    # All rounds ran for every measured candidate.
    assert result.measured_steps_total() == 4 * 3 * 3


def test_autotune_step_budget_counts_warmup_rounds():
    """The search never runs more profiled steps than
    ``measure_top_k x steps x (repeats + warmup_rounds)``, and the
    count it reports INCLUDES the discarded warmup rounds (they
    execute; discarding their scores refunds nothing) while the scored
    total leaves them out."""
    spec, batch = _fake_spec_and_batch()
    labels = ["dp8", "fsdp8", "fsdp4xtp2", "dp2xfsdp4", "dp4xfsdp2",
              "dp4xtp2", "dp2xtp4", "fsdp2xtp4", "dp2xfsdp2xtp2"]
    walls = {label: (0.010 + 0.001 * i, 0.002)
             for i, label in enumerate(labels)}
    steps, repeats, top_k, warmup = 3, 2, 4, 2
    ran = []
    prepare = _fake_measure(walls)

    def counting(*args, **kw):
        runner = prepare(*args, **kw)

        def counted(n):
            ran.append(n)
            return runner(n)

        counted.compile_s = runner.compile_s
        return counted

    result = autotune(spec, batch, list(range(8)), steps=steps,
                      repeats=repeats, measure_top_k=top_k,
                      warmup_rounds=warmup, noise_mult=2.0,
                      axes=GSPMD_AXES, measure_fn=counting,
                      alpha_bytes=1 << 20)
    assert result.warmup_rounds == warmup
    assert result.executed_steps_total == sum(ran) \
        == top_k * steps * (repeats + warmup)
    assert result.measured_steps_total() == top_k * steps * repeats
    assert all(c.measured is None for c in result.pruned())


def test_autotune_early_stops_on_noise_floor():
    spec, batch = _fake_spec_and_batch()
    devices = list(range(8))
    # dp8 at 10ms vs everyone at 30ms, tiny spread: after min_rounds
    # the 20ms lead dwarfs the noise floor -> the round loop stops.
    walls = {"dp8": (0.010, 0.0002)}
    for label in ("fsdp8", "fsdp4xtp2", "dp2xfsdp4", "dp4xfsdp2",
                  "dp4xtp2", "dp2xtp4", "fsdp2xtp4", "dp2xfsdp2xtp2"):
        walls[label] = (0.030, 0.0002)
    result = autotune(spec, batch, devices, steps=2, repeats=4,
                      min_rounds=2, measure_top_k=6, noise_mult=2.0,
                      axes=GSPMD_AXES, measure_fn=_fake_measure(walls),
                      alpha_bytes=1 << 20)
    assert result.early_stopped
    assert result.best_label == "dp8"
    assert result.rounds_run == 2       # stopped right after min_rounds
    assert sum(c.status == "measured" for c in result.candidates) == 6
    assert result.measured_steps_total() == 6 * 2 * 2
    # A noisy floor suppresses the early stop: same walls, but spreads
    # wider than the lead keep the tuner measuring all rounds.
    noisy = {k: (w, 0.05) for k, (w, _s) in walls.items()}
    result2 = autotune(spec, batch, devices, steps=2, repeats=4,
                       min_rounds=2, measure_top_k=6, noise_mult=2.0,
                       axes=GSPMD_AXES, measure_fn=_fake_measure(noisy),
                       alpha_bytes=1 << 20)
    assert not result2.early_stopped
    assert result2.rounds_run == 4


def test_autotune_survives_failed_candidates():
    spec, batch = _fake_spec_and_batch()
    devices = list(range(8))

    calls = []

    def prepare(spec, config, batch, devices, **kw):
        label = mesh_label(config.resolve(len(devices)))
        calls.append(label)
        if len(calls) == 1:
            raise RuntimeError("compile exploded")

        def runner(steps):
            return {"walls": [0.01] * steps, "comm_fraction": 0.1,
                    "overlap_fraction": 0.0,
                    "exposed_comm_fraction": 0.0,
                    "n_collective_events": 0, "counts": {}}

        return runner

    result = autotune(spec, batch, devices, steps=2, measure_top_k=2,
                      axes=GSPMD_AXES, measure_fn=prepare, alpha_bytes=1 << 20)
    failed = [c for c in result.candidates if c.status == "failed"]
    assert len(failed) == 1 and "compile exploded" in failed[0].reason
    assert result.best_label == calls[1]


def test_autotune_survives_mid_measure_failure():
    spec, batch = _fake_spec_and_batch()
    devices = list(range(8))

    def prepare(spec, config, batch, devices, **kw):
        label = mesh_label(config.resolve(len(devices)))
        state = {"rounds": 0}

        def runner(steps):
            state["rounds"] += 1
            if label == "dp8" and state["rounds"] == 2:
                raise RuntimeError("device wedged")
            return {"walls": [0.02 if label == "dp8" else 0.03] * steps,
                    "comm_fraction": 0.1, "overlap_fraction": 0.0,
                    "exposed_comm_fraction": 0.0,
                    "n_collective_events": 0, "counts": {}}

        return runner

    result = autotune(spec, batch, devices, steps=2, repeats=3,
                      measure_top_k=2, noise_mult=2.0,
                      axes=GSPMD_AXES, measure_fn=prepare, alpha_bytes=1 << 20)
    # dp8 died in round 2 -> failed, dropped from later rounds; the
    # survivor wins on its own pooled rounds.
    by_label = {c.label: c for c in result.candidates}
    assert by_label["dp8"].status == "failed"
    assert "device wedged" in by_label["dp8"].reason
    assert result.best_label != "dp8"


# ---------------------------------------------------------------------------
# Artifact + telemetry
# ---------------------------------------------------------------------------


def test_tune_result_artifact_roundtrip(tmp_path):
    spec, batch = _fake_spec_and_batch()
    devices = list(range(8))
    walls = {label: (0.010 + 0.001 * i, 0.001) for i, label in enumerate([
        "dp8", "fsdp8", "fsdp4xtp2", "dp2xfsdp4", "dp4xfsdp2",
        "dp4xtp2", "dp2xtp4", "fsdp2xtp4", "dp2xfsdp2xtp2"])}
    path = str(tmp_path / "tune_result.json")
    result = autotune(spec, batch, devices, steps=2, measure_top_k=3,
                      axes=GSPMD_AXES, measure_fn=_fake_measure(walls),
                      alpha_bytes=1 << 20, artifact_path=path)
    loaded = TuneResult.load(path)
    assert loaded.to_dict() == result.to_dict()
    assert loaded.best_config() == result.best_config()
    # The artifact names its kind and carries the full prune log.
    with open(path) as f:
        doc = json.load(f)
    assert doc["kind"] == "tune"
    assert doc["n_pruned"] == 6 and len(doc["candidates"]) == 9
    # The alpha the prune used travels with its provenance: an
    # explicit arg here, so the probe never ran.
    assert doc["alpha_bytes"] == float(1 << 20)
    assert doc["alpha_source"] == "arg"
    # A non-tune JSON is refused, loudly.
    other = tmp_path / "not_tune.json"
    other.write_text(json.dumps({"kind": "gang"}))
    with pytest.raises(ValueError):
        TuneResult.load(str(other))


def test_tune_result_compile_bill_stamped(tmp_path):
    """The search's compile bill is a visible number: one compile per
    prepared candidate (the scripted runner declares compile_s=1.0
    each), stamped into the TuneResult AND the artifact — and the
    mesh='auto' builder's winner recompile lands on the same counters
    (test_goodput pins the cache-miss detection; here the accounting
    contract)."""
    spec, batch = _fake_spec_and_batch()
    devices = list(range(8))
    walls = {label: (0.010 + 0.001 * i, 0.001) for i, label in enumerate([
        "dp8", "fsdp8", "fsdp4xtp2", "dp2xfsdp4", "dp4xfsdp2",
        "dp4xtp2", "dp2xtp4", "fsdp2xtp4", "dp2xfsdp2xtp2"])}
    path = str(tmp_path / "tune_result.json")
    result = autotune(spec, batch, devices, steps=2, measure_top_k=3,
                      axes=GSPMD_AXES, measure_fn=_fake_measure(walls),
                      alpha_bytes=1 << 20, artifact_path=path)
    assert result.compile_count == 3  # one per prepared candidate
    assert result.compile_s_total == pytest.approx(3.0)
    doc = TuneResult.load(path).to_dict()
    assert doc["compile_count"] == 3
    assert doc["compile_s_total"] == pytest.approx(3.0)
    # The winner's fresh-closure recompile is ADDED in place (what
    # make_sharded_train_step does on a detected cache miss).
    result.compile_count += 1
    result.compile_s_total += 2.5
    assert result.compile_count == 4
    # A failed prepare never counts as a compile.
    calls = []

    def prepare(spec_, config, batch_, devices_, **kw):
        from sparktorch_tpu.parallel.tune import mesh_label as _ml

        calls.append(_ml(config.resolve(len(devices_))))
        if len(calls) == 1:
            raise RuntimeError("compile exploded")

        def runner(steps):
            return {"walls": [0.01] * steps, "comm_fraction": 0.1,
                    "overlap_fraction": 0.0,
                    "exposed_comm_fraction": 0.0,
                    "n_collective_events": 0, "counts": {}}

        runner.compile_s = 0.5
        return runner

    result2 = autotune(spec, batch, devices, steps=2, measure_top_k=2,
                       axes=GSPMD_AXES, measure_fn=prepare, alpha_bytes=1 << 20)
    assert result2.compile_count == 1
    assert result2.compile_s_total == pytest.approx(0.5)


def test_tune_publish_puts_xprof_tune_on_the_bus(tmp_path):
    from sparktorch_tpu.obs import Telemetry

    spec, batch = _fake_spec_and_batch()
    devices = list(range(8))
    walls = {label: (0.010, 0.001) for label in [
        "dp8", "fsdp8", "fsdp4xtp2", "dp2xfsdp4", "dp4xfsdp2",
        "dp4xtp2", "dp2xtp4", "fsdp2xtp4", "dp2xfsdp2xtp2"]}
    tele = Telemetry(run_id="tune_pub")
    result = autotune(spec, batch, devices, steps=3, measure_top_k=2,
                      axes=GSPMD_AXES, measure_fn=_fake_measure(walls),
                      alpha_bytes=1 << 20, telemetry=tele)
    snap = tele.snapshot()
    assert snap["counters"]["xprof.tune_runs_total"] == 1
    assert snap["counters"][
        "xprof.tune_candidates_total{outcome=measured}"] == 2
    assert snap["counters"][
        "xprof.tune_candidates_total{outcome=pruned}"] == 7
    assert snap["gauges"]["xprof.tune_best_step_wall_s"] == \
        pytest.approx(0.010)
    section = tele.get_section("xprof_tune")
    assert section["best_label"] == result.best_label
    assert len(section["candidates"]) == 9
    # The timeline renders the section from a dump, and the artifact
    # from disk — same report.
    from sparktorch_tpu.obs.timeline import render_tune_report

    report = render_tune_report(section)
    assert result.best_label in report and "<- chosen" in report
    assert "pruned" in report


def test_timeline_tune_cli(tmp_path, capsys):
    from sparktorch_tpu.obs.timeline import main as timeline_main

    spec, batch = _fake_spec_and_batch()
    walls = {label: (0.010, 0.001) for label in [
        "dp8", "fsdp8", "fsdp4xtp2", "dp2xfsdp4", "dp4xfsdp2",
        "dp4xtp2", "dp2xtp4", "fsdp2xtp4", "dp2xfsdp2xtp2"]}
    path = str(tmp_path / "tune_result.json")
    autotune(spec, batch, list(range(8)), steps=2, measure_top_k=2,
             axes=GSPMD_AXES, measure_fn=_fake_measure(walls), alpha_bytes=1 << 20,
             artifact_path=path)
    assert timeline_main([path, "--tune"]) == 0
    out = capsys.readouterr().out
    assert "mesh auto-tune" in out and "chosen" in out
    # Not a tune artifact -> exit 1 with a clear error.
    bad = tmp_path / "trace.json"
    bad.write_text(json.dumps({"traceEvents": []}))
    assert timeline_main([str(bad), "--tune"]) == 1
    # --gang + --tune is a usage error.
    assert timeline_main([path, "--gang", "--tune"]) == 2


# ---------------------------------------------------------------------------
# mesh="auto" end-to-end (8-device CPU rig)
# ---------------------------------------------------------------------------


def test_mesh_auto_end_to_end(tmp_path):
    """The usable fast path: make_sharded_train_step(mesh='auto')
    searches the mesh space for real (1 measured candidate to keep the
    tier-1 budget sane), initializes state into the winning layout,
    and trains."""
    import jax

    from sparktorch_tpu.models import SequenceClassifier, tiny_transformer
    from sparktorch_tpu.train.sharded import make_sharded_train_step, shard_batch
    from sparktorch_tpu.utils.data import DataBatch
    from sparktorch_tpu.utils.serde import ModelSpec

    rng = np.random.default_rng(0)
    bsz, seq = 16, 8
    batch = DataBatch(
        x=rng.integers(0, 256, (bsz, seq)).astype(np.int32),
        y=rng.integers(0, 2, (bsz,)).astype(np.int32),
        w=np.ones((bsz,), np.float32),
    )
    module = SequenceClassifier(tiny_transformer(max_len=seq, n_layers=1))
    spec = ModelSpec(module=module, loss="cross_entropy",
                     optimizer="adam", optimizer_params={"lr": 1e-3})
    artifact = str(tmp_path / "tune_result.json")
    from sparktorch_tpu.obs import Telemetry
    from sparktorch_tpu.obs import goodput as _goodput

    tele = Telemetry(run_id="mesh_auto")
    with _goodput.GoodputLedger(telemetry=tele).activate():
        step = make_sharded_train_step(
            module.apply, spec.loss_fn(), spec.make_optimizer(),
            mesh="auto", spec=spec, sample_batch=batch,
            # Pinned alpha: THIS test asserts the predicted ranking
            # ("dp8 cheapest"), and a measured per-rig alpha must not
            # decide a deterministic assertion. The probe path has its
            # own tests below.
            tune_kwargs={"measure_top_k": 1, "steps": 2, "repeats": 2,
                         "artifact_path": artifact,
                         "alpha_bytes": 1 << 20},
        )
    # The search's compiles land in an armed ledger, site-labeled.
    assert tele.counter_value("goodput.compiles_total",
                              labels={"site": "tune"}) >= 1
    assert tele.get_section(_goodput.SECTION)["buckets"]["compile"] > 0
    # The auto path hands back the search and the initialized state.
    assert step.tune_result is not None and step.state is not None
    assert step.tune_result.best_label == "dp8"  # cheapest predicted
    assert os.path.exists(artifact)
    chosen = step.tune_result.best_config().resolve(
        len(jax.devices()))
    assert dict(step.mesh.shape) == chosen
    # And it trains: two steps, finite decreasing-ish loss.
    sharded = shard_batch(batch, step.mesh)
    state = step.state
    state, m0 = step(state, sharded)
    state, m1 = step(state, sharded)
    assert np.isfinite(float(m0.loss)) and np.isfinite(float(m1.loss))
    # Without spec/sample_batch, auto mode refuses loudly.
    with pytest.raises(ValueError, match="sample_batch"):
        make_sharded_train_step(module.apply, spec.loss_fn(),
                                spec.make_optimizer(), mesh="auto")
    with pytest.raises(ValueError, match="Mesh or 'auto'"):
        make_sharded_train_step(module.apply, spec.loss_fn(),
                                spec.make_optimizer(), mesh="bogus")


# ---------------------------------------------------------------------------
# Alpha micro-probe calibration (ROADMAP item-4 follow-up)
# ---------------------------------------------------------------------------


def test_calibrate_alpha_probe_measures_and_caches():
    import jax

    from sparktorch_tpu.parallel import tune as tune_mod

    tune_mod._ALPHA_PROBE_CACHE.clear()
    alpha = calibrate_alpha_bytes(jax.devices(), repeats=3)
    # Grounded, positive, and inside the sanity clamp.
    assert (1 << 14) <= alpha <= (1 << 24)
    # Cached per (backend, world): the second call is free and exact.
    assert calibrate_alpha_bytes(jax.devices(), repeats=3) == alpha
    assert len(tune_mod._ALPHA_PROBE_CACHE) == 1


def test_calibrate_alpha_refuses_single_device():
    import jax

    with pytest.raises(ValueError, match=">= 2 devices"):
        calibrate_alpha_bytes(jax.devices()[:1])


def test_resolve_alpha_priority_env_probe_default(monkeypatch):
    from sparktorch_tpu.parallel import tune as tune_mod

    # env wins over everything.
    monkeypatch.setenv(ALPHA_ENV, "424242")
    value, source = resolve_alpha_bytes()
    assert (value, source) == (424242.0, "env")
    # A garbled env falls through to the probe (cached from the test
    # above, or measured here).
    monkeypatch.setenv(ALPHA_ENV, "not-a-number")
    value, source = resolve_alpha_bytes()
    assert source == "probe" and value > 0
    # Probe failure degrades to the backend table, never raises.
    monkeypatch.delenv(ALPHA_ENV)
    monkeypatch.setattr(tune_mod, "calibrate_alpha_bytes",
                        lambda devices=None: (_ for _ in ()).throw(
                            RuntimeError("rig on fire")))
    value, source = resolve_alpha_bytes()
    assert source == "default" and value > 0


# ---------------------------------------------------------------------------
# Tune-result cache (ROADMAP item-4 follow-up)
# ---------------------------------------------------------------------------


def _cache_key_inputs():
    """Replicate autotune's key resolution for _fake_spec_and_batch:
    analytic transformer shape, default caps with sp locked (scalar
    labels), default axes/search knobs."""
    from sparktorch_tpu.parallel.tune import (
        DEFAULT_AXES,
        tune_cache_key,
        workload_for,
    )

    spec, batch = _fake_spec_and_batch()
    shape, cfg = workload_for(spec, batch)
    caps = dict(transformer_caps(cfg, shape.seq_len))
    caps["sp"] = (1,)
    devices = list(range(8))  # fingerprint only getattrs these
    key = tune_cache_key(shape, caps, DEFAULT_AXES, devices,
                         seq_sharded=False, measure_top_k=4,
                         exposed_weight=0.25)
    return spec, batch, devices, key


def test_tune_cache_key_fingerprints_workload_and_rig():
    from sparktorch_tpu.parallel.tune import (
        DEFAULT_AXES,
        tune_cache_key,
        workload_for,
    )

    spec, batch = _fake_spec_and_batch()
    shape, cfg = workload_for(spec, batch)
    caps = dict(transformer_caps(cfg, shape.seq_len))
    devices = list(range(8))
    key = tune_cache_key(shape, caps, DEFAULT_AXES, devices, False, 4, 0.25)
    # Deterministic for identical inputs.
    assert key == tune_cache_key(shape, caps, DEFAULT_AXES, devices,
                                 False, 4, 0.25)
    # A different global batch is a different workload...
    import dataclasses as _dc

    other = _dc.replace(shape, global_batch=shape.global_batch * 2)
    assert tune_cache_key(other, caps, DEFAULT_AXES, devices,
                          False, 4, 0.25) != key
    # ...and a different device count is a different rig.
    assert tune_cache_key(shape, caps, DEFAULT_AXES, devices[:4],
                          False, 4, 0.25) != key


def test_tune_cache_dir_env_semantics(monkeypatch, tmp_path):
    from sparktorch_tpu.parallel.tune import TUNE_CACHE_ENV, _tune_cache_dir

    monkeypatch.setenv(TUNE_CACHE_ENV, "0")
    assert _tune_cache_dir() is None
    monkeypatch.setenv(TUNE_CACHE_ENV, str(tmp_path))
    assert _tune_cache_dir() == str(tmp_path)
    monkeypatch.delenv(TUNE_CACHE_ENV)
    default = _tune_cache_dir()
    assert default is not None and "sparktorch_tpu" in default


def test_tune_cache_hit_skips_search_and_stamps_artifact(
        monkeypatch, tmp_path):
    """autotune(cache=True) finding a cached entry for the same
    (workload, rig, search space) returns it WITHOUT searching —
    nothing is measured — and both the returned result and the
    written artifact record cache_hit + the key."""
    from sparktorch_tpu.parallel.tune import (
        TUNE_CACHE_ENV,
        _cache_load,
        _cache_store,
    )

    monkeypatch.setenv(TUNE_CACHE_ENV, str(tmp_path))
    spec, batch, devices, key = _cache_key_inputs()
    seeded = TuneResult(
        n_devices=8, global_batch=32, best={"dp": 8}, candidates=[],
        noise_floor_s=0.0, early_stopped=False, steps_per_candidate=1,
        wall_s=1.0, exposed_weight=0.25,
    )
    _cache_store(key, seeded)
    assert _cache_load(key) is not None  # the key replication holds
    artifact = str(tmp_path / "tune_result.json")
    result = autotune(spec, batch, devices, cache=True,
                      artifact_path=artifact)
    assert result.cache_hit is True
    assert result.cache_key == key
    assert result.best_label == "dp8"
    # This process compiled nothing for the search: the warm path's
    # count starts below any cold search's.
    assert result.compile_count == 0 and result.compile_s_total == 0.0
    with open(artifact) as f:
        doc = json.load(f)
    assert doc["cache_hit"] is True and doc["cache_key"] == key
    # Round-trip keeps the stamp.
    assert TuneResult.load(artifact).cache_hit is True


def test_scripted_and_exhaustive_searches_never_touch_cache(
        monkeypatch, tmp_path):
    """A measure_fn (scripted test) or exhaustive (referee) run must
    neither read nor write the cache — a cache entry satisfying a
    referee would void what it referees."""
    from sparktorch_tpu.parallel.tune import TUNE_CACHE_ENV

    monkeypatch.setenv(TUNE_CACHE_ENV, str(tmp_path))
    spec, batch = _fake_spec_and_batch()
    devices = list(range(8))
    walls = {label: (0.010, 0.002) for label in [
        "dp8", "fsdp8", "fsdp4xtp2", "dp2xfsdp4", "dp4xfsdp2",
        "dp4xtp2", "dp2xtp4", "fsdp2xtp4", "dp2xfsdp2xtp2"]}
    result = autotune(spec, batch, devices, steps=1, repeats=1,
                      min_rounds=1, measure_top_k=2,
                      axes=GSPMD_AXES, measure_fn=_fake_measure(walls),
                      alpha_bytes=1 << 20, cache=True)
    assert result.cache_hit is False
    assert not [p for p in os.listdir(tmp_path)
                if p.startswith("tune_")]


# ---------------------------------------------------------------------------
# Pipeline schedules in the search space (ROADMAP item 4a)
# ---------------------------------------------------------------------------


def test_pp_bubble_and_ticks_closed_form():
    """The schedule terms are the textbook numbers: gpipe and 1f1b
    share the (S-1)/(M+S-1) bubble (1F1B reorders it for memory, not
    away); interleaved shrinks it to (S-1)/(V*M+S-1) and pays V x the
    ticks."""
    assert pp_bubble_fraction("gpipe", 1, 4) == 0.0
    assert pp_bubble_fraction("gpipe", 2, 4) == pytest.approx(1 / 5)
    assert pp_bubble_fraction("1f1b", 2, 4) == pytest.approx(1 / 5)
    assert pp_bubble_fraction("gpipe", 4, 8) == pytest.approx(3 / 11)
    assert pp_bubble_fraction("interleaved", 2, 4, 2) == pytest.approx(
        1 / 9)
    # More microbatches or more virtual stages -> smaller bubble.
    assert pp_bubble_fraction("gpipe", 2, 8) < pp_bubble_fraction(
        "gpipe", 2, 4)
    assert pp_bubble_fraction("interleaved", 2, 4, 4) < \
        pp_bubble_fraction("interleaved", 2, 4, 2)
    assert pp_schedule_ticks("gpipe", 2, 4) == 5
    assert pp_schedule_ticks("1f1b", 2, 4) == 6
    assert pp_schedule_ticks("interleaved", 2, 4, 2) == 10
    assert pp_schedule_ticks("gpipe", 1, 4) == 0


def test_cost_model_pp_schedule_terms():
    """The pp_send_recv term is schedule-aware: the bubble rides as a
    multiplicative penalty, interleaved chunks multiply the boundary
    bytes by V, and the alpha term charges one launch per tick per
    direction."""
    shape = WorkloadShape(param_bytes=8e6, tp_param_bytes=8e6,
                          global_batch=32, seq_len=16, d_model=64,
                          n_layers=4)
    cfg2 = MeshConfig(pp=2)
    flat = predict_comm_bytes(cfg2, shape, 8)
    assert flat["pp_bubble_fraction"] == 0.0  # no meta: flat terms
    g = predict_comm_bytes(cfg2, shape, 8, schedule_meta={
        "schedule": "gpipe", "virtual_stages": 1, "n_micro": 4})
    f = predict_comm_bytes(cfg2, shape, 8, schedule_meta={
        "schedule": "1f1b", "virtual_stages": 1, "n_micro": 4})
    i2 = predict_comm_bytes(cfg2, shape, 8, schedule_meta={
        "schedule": "interleaved", "virtual_stages": 2, "n_micro": 4})
    # gpipe's term = flat bytes grown by exactly the bubble factor.
    assert g["pp_bubble_fraction"] == pytest.approx(1 / 5)
    assert g["pp_send_recv"] == pytest.approx(
        flat["pp_send_recv"] * (1 + 1 / 5))
    # Same bytes/bubble for 1f1b; MORE launches (M+2S-2 vs M+S-1).
    assert f["pp_send_recv"] == pytest.approx(g["pp_send_recv"])
    assert f["collective_ops"] > g["collective_ops"]
    # Interleaved: V x boundary bytes, smaller bubble, most launches.
    assert i2["pp_bubble_fraction"] == pytest.approx(1 / 9)
    assert i2["pp_send_recv"] == pytest.approx(
        flat["pp_send_recv"] * 2 * (1 + 1 / 9))
    assert i2["collective_ops"] > f["collective_ops"]
    # The pp op counts are the tick counts, one launch per
    # direction (on top of the mesh's one dp grad-reduce launch).
    assert g["collective_ops"] == 1 + 2 * pp_schedule_ticks(
        "gpipe", 2, 4)
    assert i2["collective_ops"] == 1 + 2 * pp_schedule_ticks(
        "interleaved", 2, 4, 2)


def test_pp_schedule_metas_legality():
    from sparktorch_tpu.models import tiny_transformer

    cfg = tiny_transformer(n_layers=4, max_len=16)
    sizes = {"dp": 4, "fsdp": 1, "tp": 1, "sp": 1, "ep": 1, "pp": 2}
    metas = pp_schedule_metas(sizes, cfg, global_batch=32)
    # n_micro is a search dimension: EVERY legal M <= max(2S,4)=4
    # dividing per-shard rows 8 fans out per schedule ({1,2,4} for
    # gpipe/1f1b; {2,4} for interleaved, where M % pp == 0), plus
    # interleaved V=2 only (4 layers / 2 stages).
    assert {(m["schedule"], m["virtual_stages"], m["n_micro"])
            for m in metas} == {
        ("gpipe", 1, 1), ("gpipe", 1, 2), ("gpipe", 1, 4),
        ("1f1b", 1, 1), ("1f1b", 1, 2), ("1f1b", 1, 4),
        ("interleaved", 2, 2), ("interleaved", 2, 4)}
    for m in metas:
        assert (32 // sizes["dp"]) % m["n_micro"] == 0
        assert m["n_micro"] <= max(2 * sizes["pp"], 4)
        if m["schedule"] == "interleaved":
            assert cfg.n_layers % (2 * m["virtual_stages"]) == 0
            assert m["n_micro"] % sizes["pp"] == 0
    # 2 layers cannot interleave over pp=2 (n_layers % (S*V) != 0).
    cfg2 = tiny_transformer(n_layers=2, max_len=16)
    metas2 = pp_schedule_metas(sizes, cfg2, global_batch=32)
    assert {m["schedule"] for m in metas2} == {"gpipe", "1f1b"}
    # max_virtual < 2 disables interleaving entirely.
    metas_nov = pp_schedule_metas(sizes, cfg, 32, max_virtual=1)
    assert {m["schedule"] for m in metas_nov} == {"gpipe", "1f1b"}
    # Trainer-mirroring refusals: MoE x tp, sp without ring
    # attention, ep without experts, non-transformer specs.
    moe = tiny_transformer(n_layers=4, n_experts=4, moe_every=2)
    assert pp_schedule_metas({**sizes, "tp": 2, "dp": 2}, moe, 32) == []
    assert pp_schedule_metas({**sizes, "sp": 2, "dp": 2}, cfg, 32) == []
    assert pp_schedule_metas({**sizes, "ep": 2, "dp": 2}, cfg, 32) == []
    assert pp_schedule_metas(sizes, None, 32) == []
    # MoE with a uniform per-stage pattern IS legal (pattern
    # [dense, moe] x 2 over pp=2), and stays so for interleaved only
    # if every CHUNK repeats it (4 layers / (2*2) = 1-layer chunks
    # alternate dense/moe -> interleaved refused).
    metas_moe = pp_schedule_metas(sizes, moe, 32)
    assert {m["schedule"] for m in metas_moe} == {"gpipe", "1f1b"}


def test_autotune_expands_pp_schedules_and_keeps_pure_dp():
    """With the default axes the search space fans pp>1 meshes into
    per-schedule candidates (labels carry the schedule), pure dp is
    still always candidate material, and a scripted pp winner lands
    in best/best_schedule and round-trips through the artifact."""
    import tempfile

    from sparktorch_tpu.models import SequenceClassifier, tiny_transformer
    from sparktorch_tpu.utils.data import DataBatch
    from sparktorch_tpu.utils.serde import ModelSpec

    cfg = tiny_transformer(vocab_size=64, d_model=32, n_heads=2,
                           n_layers=2, d_ff=64, max_len=8)
    spec = ModelSpec(module=SequenceClassifier(cfg), loss="cross_entropy",
                     optimizer="adam", optimizer_params={"lr": 1e-3})
    batch = DataBatch(x=np.zeros((16, 8), np.int32),
                      y=np.zeros((16,), np.int32),
                      w=np.ones((16,), np.float32))
    devices = list(range(8))

    def scripted(spec_, config, batch_, devices_, tx=None,
                 seq_sharded=False, telemetry=None, schedule_meta=None):
        label = candidate_label(config.resolve(len(devices_)),
                                schedule_meta)
        wall = 0.005 if label == "dp4xpp2-gpipe_m4" else 0.030

        def runner(steps):
            return {"walls": [wall] * max(steps, 1),
                    "comm_fraction": 0.2, "overlap_fraction": 0.1,
                    "exposed_comm_fraction": 0.1,
                    "n_collective_events": steps, "counts": {},
                    "loss": 0.0}

        runner.compile_s = 0.1
        return runner

    with tempfile.TemporaryDirectory() as td:
        artifact = os.path.join(td, "tune_result.json")
        result = autotune(spec, batch, devices, steps=2, repeats=2,
                          min_rounds=1, measure_top_k=32,
                          measure_fn=scripted, alpha_bytes=1 << 20,
                          artifact_path=artifact)
        loaded = TuneResult.load(artifact)
    labels = [c.label for c in result.candidates]
    # Pure dp is present, and the pp meshes fan out per schedule AND
    # per legal n_micro (per-shard rows 4 -> M in {1, 2, 4}).
    assert "dp8" in labels
    assert "dp4xpp2-gpipe_m4" in labels
    assert "dp4xpp2-1f1b_m4" in labels
    assert "dp4xpp2-gpipe_m2" in labels
    assert "dp4xpp2-gpipe_m1" in labels
    # n_layers=2 cannot interleave over pp=2.
    assert not any("int" in l for l in labels)
    # Every pp candidate carries legal schedule meta (divisibility).
    for c in result.candidates:
        if c.axes.get("pp", 1) > 1:
            assert c.schedule is not None
            assert c.axes["fsdp"] == 1
            per_shard = 16 // c.axes["dp"]
            assert per_shard % c.schedule["n_micro"] == 0
        else:
            assert c.schedule is None
    # The scripted winner is the pp2 gpipe candidate, schedule
    # stamped on the result and preserved by the artifact round-trip.
    assert result.best_label == "dp4xpp2-gpipe_m4"
    assert result.best == {"dp": 4, "fsdp": 1, "tp": 1, "sp": 1,
                           "ep": 1, "pp": 2}
    assert result.best_schedule == {"schedule": "gpipe",
                                    "virtual_stages": 1, "n_micro": 4}
    assert loaded.best_schedule == result.best_schedule
    assert loaded.best_label == result.best_label
    for c, lc in zip(result.candidates, loaded.candidates):
        assert lc.schedule == c.schedule


def test_tune_cache_key_schema_fences_pre_pp_entries(monkeypatch,
                                                     tmp_path):
    """An entry cached by the pre-schedule tuner (schema 2, pp locked
    to 1) must never satisfy the opened search: replicate the OLD key
    doc for the same workload, store a result under it, and verify
    autotune's cache lookup misses (the schema bump changed the
    key)."""
    import hashlib

    from sparktorch_tpu.parallel.tune import (
        TUNE_CACHE_ENV,
        _cache_load,
        _cache_store,
        device_fingerprint,
        tune_cache_key,
        workload_for,
    )
    import dataclasses as _dc

    monkeypatch.setenv(TUNE_CACHE_ENV, str(tmp_path))
    spec, batch = _fake_spec_and_batch()
    shape, cfg = workload_for(spec, batch)
    caps = dict(transformer_caps(cfg, shape.seq_len))
    caps["sp"] = (1,)
    devices = list(range(8))
    from sparktorch_tpu.parallel.tune import DEFAULT_AXES

    # The OLD (schema 2) key for the same search inputs.
    old_doc = {
        "schema": 2,
        "moe_dispatch": "shard_map_a2a",
        "shape": _dc.asdict(shape),
        "caps": {k: sorted(int(x) for x in v) for k, v in caps.items()},
        "axes": list(DEFAULT_AXES),
        "device": device_fingerprint(devices),
        "seq_sharded": False,
        "measure_top_k": 4,
        "exposed_weight": 0.25,
        "max_candidates": 64,
        "measure": [4, 3, 2, 2.0],
        "tx": None,
        "alpha_override": None,
    }
    old_key = hashlib.sha256(
        json.dumps(old_doc, sort_keys=True).encode()).hexdigest()[:24]
    new_key = tune_cache_key(shape, caps, DEFAULT_AXES, devices,
                             seq_sharded=False, measure_top_k=4,
                             exposed_weight=0.25)
    assert new_key != old_key
    stale = TuneResult(
        n_devices=8, global_batch=32, best={"dp": 8}, candidates=[],
        noise_floor_s=0.0, early_stopped=False, steps_per_candidate=1,
        wall_s=1.0, exposed_weight=0.25,
    )
    _cache_store(old_key, stale)
    # The fenced entry exists on disk but the new key cannot load it.
    assert _cache_load(old_key) is not None
    assert _cache_load(new_key) is None


def test_mesh_auto_pp_winner_builds_pipeline_step_loss_parity(tmp_path):
    """mesh='auto' with a pp=2 winner returns a PIPELINE-scheduled
    step (the tentpole's acceptance): same schedule path as a
    directly-constructed train_pipeline step, pinned by loss equality
    over 3 steps from the same seed."""
    import jax

    from sparktorch_tpu.models import SequenceClassifier, tiny_transformer
    from sparktorch_tpu.parallel.mesh import MeshConfig, build_mesh
    from sparktorch_tpu.train.pipeline import (
        PipelineState,
        make_pp_train_step,
        pipeline_params_from_flax,
        place_pipeline_state,
    )
    from sparktorch_tpu.train.sharded import make_sharded_train_step
    from sparktorch_tpu.utils.data import DataBatch
    from sparktorch_tpu.utils.serde import ModelSpec

    cfg = tiny_transformer(vocab_size=64, d_model=32, n_heads=2,
                           n_layers=2, d_ff=64, max_len=8)
    spec = ModelSpec(module=SequenceClassifier(cfg), loss="cross_entropy",
                     optimizer="adam", optimizer_params={"lr": 1e-3})
    rng = np.random.default_rng(0)
    batch = DataBatch(x=rng.integers(0, 64, (16, 8)).astype(np.int32),
                      y=rng.integers(0, 2, (16,)).astype(np.int32),
                      w=np.ones((16,), np.float32))

    def scripted(spec_, config, batch_, devices_, tx=None,
                 seq_sharded=False, telemetry=None, schedule_meta=None):
        label = candidate_label(config.resolve(len(devices_)),
                                schedule_meta)
        wall = 0.005 if label == "dp4xpp2-gpipe_m4" else 0.030

        def runner(steps):
            return {"walls": [wall] * max(steps, 1),
                    "comm_fraction": 0.2, "overlap_fraction": 0.1,
                    "exposed_comm_fraction": 0.1,
                    "n_collective_events": steps, "counts": {},
                    "loss": 0.0}

        runner.compile_s = 0.1
        return runner

    run = make_sharded_train_step(
        spec.make_module().apply, spec.loss_fn(), spec.make_optimizer(),
        mesh="auto", spec=spec, sample_batch=batch,
        tune_kwargs={"measure_fn": scripted, "alpha_bytes": 1 << 20,
                     "measure_top_k": 32, "steps": 1, "repeats": 1,
                     "min_rounds": 1},
    )
    assert run.tune_result.best_label == "dp4xpp2-gpipe_m4"
    assert run.pipeline_schedule == {"schedule": "gpipe",
                                     "virtual_stages": 1, "n_micro": 4}
    assert isinstance(run.state, PipelineState)
    assert dict(run.mesh.shape)["pp"] == 2

    auto_losses = []
    state = run.state
    for _ in range(3):
        state, loss = run(state, batch)
        auto_losses.append(float(loss))

    # The direct construction: identical seed, layout, schedule.
    mesh = build_mesh(MeshConfig(dp=4, pp=2))
    tx = spec.make_optimizer()
    flax_params = dict(spec.init_params(
        jax.random.key(0), sample_x=np.asarray(batch.x[:1])))["params"]
    pparams = pipeline_params_from_flax(flax_params, cfg)
    dstate = place_pipeline_state(pparams, tx, mesh)
    dstep = make_pp_train_step(cfg, tx, mesh, n_micro=4,
                               head="classifier", schedule="gpipe")
    direct_losses = []
    for _ in range(3):
        dstate, dloss = dstep(dstate, batch)
        direct_losses.append(float(dloss))

    np.testing.assert_allclose(auto_losses, direct_losses,
                               rtol=1e-6, atol=0)
    # And the losses are real training signal, not NaN/frozen.
    assert np.isfinite(auto_losses).all()
    assert auto_losses[0] != auto_losses[-1]

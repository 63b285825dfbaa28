"""Model-health observability (obs.health + obs.replay): the delayed
asynchronous fetch, the NaN/spike/explosion/plateau detectors, latched
``health.*`` flags and alert rules, rank-tagged multi-rank merging
(a NaN on one hogwild worker must surface as THAT worker's NaN, never
dissolve into a fleet mean), bitwise replay bundles, and the
collector/timeline surfaces (``GET /health``, ``timeline --health``,
``--follow`` one-liners).
"""

import json
import os
import sys
import threading
import types
from collections import namedtuple

import numpy as np
import pytest

from sparktorch_tpu.obs import Telemetry
from sparktorch_tpu.obs import health as health_mod
from sparktorch_tpu.obs.health import (
    HealthConfig,
    TrainHealthLedger,
    float_bits,
    health_alert_rules,
    merge_sections,
    tree_checksum,
)


def _ledger(tele=None, **cfg):
    return TrainHealthLedger(
        rank=cfg.pop("rank", 0),
        config=HealthConfig(**cfg),
        telemetry=tele or Telemetry(run_id="health-test"),
    )


# ---------------------------------------------------------------------------
# Delayed fetch: the lag contract and its goodput attribution
# ---------------------------------------------------------------------------


def test_note_step_holds_queue_until_fetch_lag():
    hl = _ledger(fetch_lag=2)
    hl.note_step(host={"loss": 1.0})
    doc = hl.snapshot()
    # Nothing is ingested until fetch_lag newer notes exist.
    assert doc["steps_ingested"] == 0 and doc["pending_fetch"] == 1
    hl.note_step(host={"loss": 1.1})
    hl.note_step(host={"loss": 1.2})
    doc = hl.snapshot()
    assert doc["steps_ingested"] == 1 and doc["last_step"] == 0
    # flush drains the tail regardless of lag (the loop ended).
    hl.flush()
    doc = hl.snapshot()
    assert doc["steps_ingested"] == 3 and doc["last_step"] == 2
    assert doc["pending_fetch"] == 0
    assert doc["series"]["steps"] == [0, 1, 2]


def test_device_fetch_is_attributed_as_data_wait():
    import jax.numpy as jnp

    from sparktorch_tpu.obs import goodput as goodput_mod

    # Device-valued notes: the (delayed) sync lands in the goodput
    # ledger's data_wait bucket. Host-only notes never touch it.
    tele = Telemetry(run_id="health-dw")
    led = goodput_mod.GoodputLedger(telemetry=tele, rank=0)
    hl = _ledger(tele=tele, fetch_lag=1)
    with led.activate():
        for i in range(4):
            hl.note_step(device={"loss": jnp.float32(1.0 + i)})
        hl.flush()
    dw = float(tele.get_section(goodput_mod.SECTION)["buckets"]["data_wait"])
    assert dw > 0.0

    tele2 = Telemetry(run_id="health-dw-host")
    led2 = goodput_mod.GoodputLedger(telemetry=tele2, rank=0)
    hl2 = _ledger(tele=tele2, fetch_lag=1)
    with led2.activate():
        for i in range(4):
            hl2.note_step(host={"loss": 1.0 + i})
        hl2.flush()
    dw2 = float(tele2.get_section(goodput_mod.SECTION)["buckets"]["data_wait"])
    assert dw2 == 0.0


def test_fused_chunk_rows_index_per_step():
    # A fused chunk (count=n) carries stacked rows; each row lands on
    # its own step. Scalar values broadcast across the chunk.
    hl = _ledger(fetch_lag=0)
    hl.note_step(step=0, count=3,
                 host={"loss": np.array([1.0, 2.0, 3.0]),
                       "grad_norm": np.float64(0.5)})
    doc = hl.snapshot()
    assert doc["series"]["steps"] == [0, 1, 2]
    assert doc["series"]["loss"] == [1.0, 2.0, 3.0]
    assert doc["series"]["grad_norm"] == [0.5, 0.5, 0.5]
    # The chunk may be wider than the active count (steps_per_call
    # padding): rows past count-1 are simply never indexed.
    assert float(TrainHealthLedger._row(
        np.array([7.0, 8.0, 9.0, 0.0]), 2, 1)) == 8.0


# ---------------------------------------------------------------------------
# Detectors
# ---------------------------------------------------------------------------


def test_nonfinite_short_circuits_the_ewma_baseline():
    hl = _ledger(fetch_lag=0, warmup_steps=2)
    for i in range(4):
        hl.note_step(host={"loss": 1.0, "grad_norm": 0.5})
    hl.note_step(host={"loss": float("nan"), "grad_norm": 0.5})
    hl.note_step(host={"loss": 1.0, "grad_norm": 0.5})
    hl.flush()
    doc = hl.snapshot()
    assert doc["counts"] == {"nonfinite": 1}
    (anom,) = doc["anomalies"]
    assert anom["akind"] == "nonfinite" and anom["step"] == 4
    assert anom["rank"] == "0"
    # The poisoned row never fed the EWMA: the baseline stays finite.
    assert np.isfinite(doc["ewma"]["loss"])


def test_nonfinite_detect_lag_equals_fetch_lag_mid_run():
    hl = _ledger(fetch_lag=2, warmup_steps=2)
    for i in range(4):
        hl.note_step(host={"loss": 1.0})
    hl.note_step(host={"loss": float("inf")})  # step 4
    for i in range(4):
        hl.note_step(host={"loss": 1.0})
    doc = hl.snapshot()
    (anom,) = doc["anomalies"]
    # Detected when the fetch caught up, fetch_lag steps later.
    assert anom["step"] == 4 and anom["detect_lag"] == 2


def test_loss_spike_fires_after_warmup_and_reset_rebases():
    hl = _ledger(fetch_lag=0, warmup_steps=3, spike_factor=3.0,
                 spike_min_delta=0.25)
    # Within warmup a jump is NOT a spike (cold-start noise).
    hl.note_step(host={"loss": 1.0})
    hl.note_step(host={"loss": 5.0})
    for _ in range(4):
        hl.note_step(host={"loss": 1.0})
    assert "loss_spike" not in hl.snapshot()["counts"]
    hl.note_step(host={"loss": 50.0})
    doc = hl.snapshot()
    assert doc["counts"]["loss_spike"] == 1
    # reset() re-bases the baseline (checkpoint restore / elastic
    # resize): the first post-restart losses are not judged against
    # the stale EWMA — the classic restart false-spike.
    hl.reset()
    for _ in range(4):
        hl.note_step(host={"loss": 50.0})
    assert hl.snapshot()["counts"]["loss_spike"] == 1


def test_grad_explosion_detector():
    hl = _ledger(fetch_lag=0, warmup_steps=3, explode_factor=10.0)
    for _ in range(5):
        hl.note_step(host={"loss": 1.0, "grad_norm": 1.0})
    hl.note_step(host={"loss": 1.0, "grad_norm": 500.0})
    doc = hl.snapshot()
    assert doc["counts"]["grad_explosion"] == 1
    (anom,) = [a for a in doc["anomalies"]
               if a["akind"] == "grad_explosion"]
    assert anom["value"] == 500.0 and anom["threshold"] is not None


def test_plateau_fires_once_per_flat_window():
    hl = _ledger(fetch_lag=0, plateau_window=8, plateau_rel_delta=1e-5)
    for _ in range(20):
        hl.note_step(host={"loss": 0.75})
    doc = hl.snapshot()
    # Latched while flat: one anomaly, not one per step.
    assert doc["counts"] == {"plateau": 1}
    rules = {r.name: r for r in health_alert_rules()}
    assert rules["health_plateau"].severity == "warning"
    assert rules["health_nonfinite"].severity == "critical"


# ---------------------------------------------------------------------------
# Latched flags -> alert rules
# ---------------------------------------------------------------------------


def test_anomaly_flag_latches_then_expires_and_alert_fires_once():
    from sparktorch_tpu.obs.alerts import AlertManager
    from sparktorch_tpu.obs.history import MetricsHistory

    tele = Telemetry(run_id="health-alerts")
    hl = _ledger(tele=tele, fetch_lag=0, warmup_steps=2, flag_window=4)
    history = MetricsHistory(retention=16)
    mgr = AlertManager(history, rules=health_alert_rules(),
                       telemetry=tele)
    for _ in range(4):
        hl.note_step(host={"loss": 1.0})
    hl.note_step(host={"loss": float("nan")})
    hl.publish(force=True)
    events = []
    base = 1000.0
    for k in range(3):
        history.append(tele.snapshot(), ts=base + k)
        events += mgr.evaluate(ts=base + k)
    fired = [e for e in events if e["event"] == "fired"]
    # Latched: one episode across repeated sweeps, not one per sweep.
    assert [e["alert"] for e in fired] == ["health_nonfinite"]
    # flag_window clean steps later the flag drops and the alert
    # resolves.
    for _ in range(6):
        hl.note_step(host={"loss": 1.0})
    hl.publish(force=True)
    history.append(tele.snapshot(), ts=base + 10)
    resolved = [e for e in mgr.evaluate(ts=base + 10)
                if e["event"] == "resolved"]
    assert [e["alert"] for e in resolved] == ["health_nonfinite"]


# ---------------------------------------------------------------------------
# Multi-rank merge: rank-tagged, never averaged
# ---------------------------------------------------------------------------


def test_merge_keeps_anomalies_rank_tagged_never_averaged():
    clean = _ledger(rank="w0", fetch_lag=0, warmup_steps=2)
    sick = _ledger(rank="w1", fetch_lag=0, warmup_steps=2)
    for _ in range(5):
        clean.note_step(host={"loss": 0.5})
        sick.note_step(host={"loss": 0.5})
    sick.note_step(host={"loss": float("nan")})
    merged = merge_sections({"w0": clean.snapshot(),
                             "w1": sick.snapshot()})
    assert merged["kind"] == "health_run" and merged["n_ranks"] == 2
    assert merged["anomalies_total"] == 1
    assert all(a["rank"] == "w1" for a in merged["anomalies"])
    assert merged["worst"]["akind"] == "nonfinite"
    assert merged["worst"]["rank"] == "w1"
    # Never averaged: no fleet-mean loss exists anywhere in the run
    # doc; each rank's last loss survives separately (w0's stays
    # finite next to w1's NaN).
    assert "loss" not in merged and "mean" not in merged
    assert merged["last_by_rank"]["w0"]["loss"] == 0.5
    assert not np.isfinite(merged["last_by_rank"]["w1"]["loss"])
    assert not (merged["per_rank"]["w0"].get("counts") or {})


def test_merge_disambiguates_rank_collisions_across_processes():
    a = _ledger(rank=0, fetch_lag=0)
    b = _ledger(rank=0, fetch_lag=0)
    a.note_step(host={"loss": 1.0})
    b.note_step(host={"loss": 2.0})
    a.flush()
    b.flush()
    merged = merge_sections({"p0": a.snapshot(), "p1": b.snapshot()})
    # Same inner rank scraped from two processes: prefixed, not
    # silently merged.
    assert set(merged["per_rank"]) == {"0", "p1/0"}


def test_hogwild_poisoned_worker_surfaces_rank_tagged():
    """Satellite drill: NaN on exactly one hogwild worker. The merged
    run doc must carry it as THAT worker's anomaly; the clean worker
    stays clean (poison lands on the final iteration so the NaN can't
    travel through the param server into the other worker)."""
    from sparktorch_tpu import serialize_torch_obj
    from sparktorch_tpu.ft import ChaosConfig, inject
    from sparktorch_tpu.models import Net
    from sparktorch_tpu.train.hogwild import train_async

    payload = serialize_torch_obj(
        Net(), criterion="mse", optimizer="adam",
        optimizer_params={"lr": 5e-3}, input_shape=(10,))
    rng = np.random.default_rng(0)
    x = rng.normal(0, 1, (64, 10)).astype(np.float32)
    y = (x.sum(axis=1) > 0).astype(np.float32)
    tele = Telemetry(run_id="health-hogwild")
    iters = 6
    with inject(ChaosConfig(poison_batch_at={1: iters - 1}),
                telemetry=tele):
        # Full-batch iterations: the poisoned row always participates
        # in the loss (a sampled minibatch could miss it).
        train_async(payload, x, labels=y, iters=iters, partitions=2,
                    seed=0, telemetry=tele)
    sec = tele.get_section(health_mod.SECTION)
    assert sec and "ranks" in sec
    merged = merge_sections({"driver": sec})
    assert set(merged["per_rank"]) == {"w0", "w1"}
    assert merged["counts"].get("nonfinite", 0) >= 1
    assert {a["rank"] for a in merged["anomalies"]} == {"w1"}
    assert not (merged["per_rank"]["w0"].get("counts") or {})
    assert np.isfinite(merged["last_by_rank"]["w0"]["loss"])


# ---------------------------------------------------------------------------
# Replay bundles: bitwise round trip
# ---------------------------------------------------------------------------

_Metrics = namedtuple("_Metrics", ["loss", "grad_norm"])


def _toy_step(state, batch):
    loss = np.float32(float((state["w"] * batch).sum()))
    return state, _Metrics(loss=loss, grad_norm=None)


def _install_toy_builder():
    mod = types.ModuleType("_sparktorch_health_toy")

    def build():
        return {
            "step_fn": _toy_step,
            "state": {"w": np.zeros(4, np.float32)},
            "batch": np.zeros(4, np.float32),
        }

    mod.build = build
    sys.modules["_sparktorch_health_toy"] = mod
    return "_sparktorch_health_toy:build"


def test_replay_bundle_roundtrip_is_bitwise(tmp_path, capsys):
    from sparktorch_tpu.obs import replay as replay_mod

    builder = _install_toy_builder()
    hl = _ledger(fetch_lag=0, warmup_steps=2, replay_dir=str(tmp_path),
                 replay_builder=builder, replay_anchor_every=8)
    state = {"w": np.arange(4, dtype=np.float32)}
    batch = np.ones(4, np.float32)
    hl.note_replay_anchor(state, batch)
    for _ in range(4):
        hl.note_step(host={"loss": 1.0})
    # The spike step dispatches a NEW batch: identity change re-anchors
    # so the bundle replays exactly one step.
    batch2 = np.full(4, 3.0, np.float32)
    hl.note_replay_anchor(state, batch2)
    _, m = _toy_step(state, batch2)
    hl.note_step(host={"loss": float(m.loss)})
    hl.flush()

    doc = hl.snapshot()
    assert doc["counts"]["loss_spike"] == 1
    (meta_path,) = doc["replay"]["bundles"]
    with open(meta_path) as f:
        meta = json.load(f)
    assert meta["kind"] == "health_replay"
    assert meta["step"] == 4 and meta["anchor_step"] == 4
    assert meta["akind"] == "loss_spike"
    assert meta["bad"]["loss"]["bits"] == float_bits(m.loss)

    out = replay_mod.replay_bundle(meta_path)
    assert out["match"] is True and out["steps_run"] == 1
    assert out["compared"]["loss"]["recorded_bits"] == \
        out["compared"]["loss"]["replayed_bits"]

    # The CLI contract: exit 0 and the verdict line.
    rc = replay_mod.main([meta_path])
    cap = capsys.readouterr().out
    assert rc == 0 and "bitwise reproduction" in cap


def _install_sync_step_builder(n_features, rows):
    """The replay builder of the drill below: the jitted step
    ``train_distributed(steps_per_call=1)`` trains with (same spec,
    mesh and optimizer) plus state / batch TEMPLATES, importable as
    ``module:function`` the way a bundle's meta names it."""
    mod = types.ModuleType("_sparktorch_health_sync")

    def build():
        import jax
        import jax.numpy as jnp

        from sparktorch_tpu.models import Net
        from sparktorch_tpu.parallel.mesh import build_mesh
        from sparktorch_tpu.train.step import (
            create_train_state,
            make_train_step,
        )
        from sparktorch_tpu.utils.data import DataBatch
        from sparktorch_tpu.utils.serde import ModelSpec

        spec = ModelSpec(module=Net(), loss="mse", optimizer="adam",
                         optimizer_params={"lr": 1e-2},
                         input_shape=(n_features,))
        tx = spec.make_optimizer()
        state = create_train_state(
            spec, jax.random.key(0),
            sample_x=jnp.zeros((1, n_features), jnp.float32), tx=tx)
        step_fn = make_train_step(spec.make_module().apply,
                                  spec.loss_fn(), tx, build_mesh())
        batch = DataBatch(x=jnp.zeros((rows, n_features), jnp.float32),
                          y=jnp.zeros((rows,), jnp.float32),
                          w=jnp.ones((rows,), jnp.float32))
        return {"step_fn": step_fn, "state": state, "batch": batch}

    mod.build = build
    sys.modules["_sparktorch_health_sync"] = mod
    return "_sparktorch_health_sync:build"


def test_sync_trainer_poison_drill_names_the_step_and_replays_bitwise(
        tmp_path):
    """A seeded poison batch on a real ``train_distributed`` run: the
    first anomaly is ``nonfinite`` AT the poisoned step, seen within
    two steps of the delayed fetch, with dotted parameter names in the
    gradient table; the anchor re-armed on the poisoned batch, so the
    bundle replays ONE step, bit for bit."""
    from sparktorch_tpu.ft import ChaosConfig, inject
    from sparktorch_tpu.models import Net
    from sparktorch_tpu.obs import replay as replay_mod
    from sparktorch_tpu.train.sync import train_distributed
    from sparktorch_tpu.utils.serde import ModelSpec

    n_features, rows, poison_step, iters = 10, 64, 4, 8
    rng = np.random.default_rng(0)
    x = rng.normal(size=(rows, n_features)).astype(np.float32)
    y = rng.normal(size=(rows,)).astype(np.float32)
    spec = ModelSpec(module=Net(), loss="mse", optimizer="adam",
                     optimizer_params={"lr": 1e-2},
                     input_shape=(n_features,))
    tele = Telemetry(run_id="health-drill")
    cfg = HealthConfig(
        warmup_steps=3, replay_dir=str(tmp_path),
        replay_builder=_install_sync_step_builder(n_features, rows))
    prev = health_mod.install(None)
    try:
        hl = health_mod.ensure(tele, rank=0, config=cfg)
        with inject(ChaosConfig(poison_batch_at={0: poison_step}),
                    telemetry=tele):
            train_distributed(spec, x, labels=y, iters=iters, seed=0,
                              steps_per_call=1, telemetry=tele)
        doc = hl.snapshot()
    finally:
        health_mod.install(prev)

    first = doc["anomalies"][0]
    assert (first["akind"], first["step"]) == ("nonfinite", poison_step)
    assert 0 <= first["detect_lag"] - cfg.fetch_lag <= 2
    assert any("." in str(k) for k, _ in doc["top_grad_leaves"])

    (meta_path,) = [b for b in doc["replay"]["bundles"]
                    if os.path.basename(b)
                    == f"replay_step{poison_step:06d}_r0.json"]
    with open(meta_path) as f:
        meta = json.load(f)
    assert meta["anchor_step"] == poison_step
    out = replay_mod.replay_bundle(meta_path)
    assert out["match"] is True and out["steps_run"] == 1


def test_replay_checksum_guards_anchor_integrity(tmp_path):
    from sparktorch_tpu.obs import replay as replay_mod

    builder = _install_toy_builder()
    hl = _ledger(fetch_lag=0, warmup_steps=2, replay_dir=str(tmp_path),
                 replay_builder=builder)
    state = {"w": np.arange(4, dtype=np.float32)}
    hl.note_replay_anchor(state, np.ones(4, np.float32))
    hl.note_step(host={"loss": float("nan")})
    hl.flush()
    (meta_path,) = hl.snapshot()["replay"]["bundles"]
    bundle = replay_mod.load_bundle(meta_path)
    bundle["arrays"]["state_0"] = bundle["arrays"]["state_0"] + 1.0
    with pytest.raises(ValueError, match="checksum"):
        replay_mod.replay_bundle(bundle)


def test_tree_checksum_and_float_bits_are_content_addressed():
    t1 = {"a": np.arange(3, dtype=np.float32), "b": np.ones(2)}
    t2 = {"a": np.arange(3, dtype=np.float32), "b": np.ones(2)}
    t3 = {"a": np.arange(3, dtype=np.float32), "b": np.ones(2) * 2}
    assert tree_checksum(t1) == tree_checksum(t2)
    assert tree_checksum(t1) != tree_checksum(t3)
    # float_bits is the float32 bit pattern — the only equality two
    # NaNs can pass.
    assert float_bits(float("nan")) == float_bits(float("nan"))
    assert float_bits(1.0) != float_bits(np.nextafter(
        np.float32(1.0), np.float32(2.0)))


# ---------------------------------------------------------------------------
# Ambient install point + env gate
# ---------------------------------------------------------------------------


def test_ensure_reuses_bus_scoped_ledger_and_env_gate(monkeypatch):
    prev = health_mod.install(None)
    try:
        tele = Telemetry(run_id="health-ensure")
        a = health_mod.ensure(tele, rank=0)
        b = health_mod.ensure(tele)
        assert a is b  # same bus -> same ledger (a caller installs, the trainer reuses)
        other = health_mod.ensure(Telemetry(run_id="health-ensure-2"))
        assert other is not a  # new bus -> fresh EWMAs
        monkeypatch.setenv(health_mod.ENV_GATE, "0")
        assert health_mod.ensure(tele) is None
        assert not health_mod.enabled()
    finally:
        health_mod.install(prev)


# ---------------------------------------------------------------------------
# Collector + timeline surfaces
# ---------------------------------------------------------------------------


def test_collector_serves_health_and_timeline_renders(tmp_path):
    from sparktorch_tpu.native.gang import GangMetricsExporter
    from sparktorch_tpu.obs import FleetCollector
    from sparktorch_tpu.obs import timeline as timeline_mod
    from sparktorch_tpu.obs.collector import scrape_json

    tele = Telemetry(run_id="health-fleet")
    hl = _ledger(tele=tele, fetch_lag=0, warmup_steps=2)
    for _ in range(5):
        hl.note_step(host={"loss": 1.0, "grad_norm": 0.5})
    hl.note_step(host={"loss": float("nan")})
    hl.flush()

    exp = GangMetricsExporter(telemetry=tele, port=0).start()
    sink = str(tmp_path / "sink.jsonl")
    collector = FleetCollector({0: exp.url}, poll_interval_s=0,
                               jsonl_path=sink)
    collector.start(poll_loop=False)
    try:
        collector.poll()
        run_doc = scrape_json(f"{collector.url}/health")
    finally:
        collector.stop()
        exp.stop()

    assert run_doc["kind"] == "health_run"
    assert "0" in run_doc["per_rank"]
    assert run_doc["worst"]["akind"] == "nonfinite"

    report = timeline_mod.render_health_report(run_doc)
    assert "model health" in report and "nonfinite" in report

    with open(sink) as f:
        records = [json.loads(ln) for ln in f if ln.strip()]
    # The sink carries a condensed health.run record the --follow tail
    # renders as a one-liner...
    (condensed,) = [r for r in records if r.get("kind") == "health.run"]
    line = timeline_mod.render_follow_line(condensed)
    assert "health.run" in line and "worst=nonfinite" in line
    # ...and the full merged doc reconstructs from the gang snapshots.
    doc = timeline_mod._health_from_jsonl(records)
    assert doc and doc["worst"]["akind"] == "nonfinite"

    stop_ev = threading.Event()
    stop_ev.set()
    lines = list(timeline_mod.follow(sink, poll_s=0.0, stop=stop_ev))
    assert any("health.run" in ln for ln in lines)


def test_postmortem_bundle_carries_health_at_death(tmp_path):
    """A run that dies after a NaN: the postmortem bundle answers
    "was the model healthy" beside "why did it die", rank-tagged."""
    import contextlib
    import io

    from sparktorch_tpu.obs import timeline as timeline_mod
    from sparktorch_tpu.obs.blackbox import collect_postmortem

    tele = Telemetry(run_id="health-pm")
    hl = _ledger(tele=tele, fetch_lag=0, warmup_steps=2)
    for _ in range(3):
        hl.note_step(host={"loss": 1.0, "grad_norm": 0.5})
    hl.note_step(host={"loss": float("nan")})
    hl.flush()
    pm_path = collect_postmortem(str(tmp_path), "health test death",
                                 telemetry=tele)
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = timeline_mod.main(["--postmortem", pm_path])
    out = buf.getvalue()
    assert rc == 0
    assert "model health at death" in out and "nonfinite" in out
    assert "rank 0" in out

"""The bus's spans on the profiler's clock (PR 24): a span is name,
start, end and parent, a host event of any ``jax.profiler`` trace, and
the owner of the compile events that fall inside it; the sync trainer
tiles its loop with them and scopes its step program by phase."""

import pickle
import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparktorch_tpu.models import Net
from sparktorch_tpu.obs import telemetry as bus_module
from sparktorch_tpu.obs.telemetry import Telemetry
from sparktorch_tpu.parallel.mesh import build_mesh
from sparktorch_tpu.train.step import create_train_state, make_train_epoch
from sparktorch_tpu.train.sync import prepare_sharded_batch, train_distributed
from sparktorch_tpu.utils.data import handle_features
from sparktorch_tpu.utils.serde import ModelSpec, serialize_model

JIT_HISTOGRAMS = tuple(bus_module.JIT_EVENT_HISTOGRAMS.values())


def _rows(n=256, dim=10, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, dim)).astype(np.float32)
    return x, (x[:, 0] > 0).astype(np.float32)


def _payload():
    return serialize_model(Net(), "mse", "adam", {"lr": 1e-2},
                           input_shape=(10,))


def _host_events(trace_dir):
    """``{name: count}`` over the host plane of the newest capture."""
    from jax.profiler import ProfileData

    path = sorted(trace_dir.rglob("*.xplane.pb"))[-1]
    counts = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                counts[ev.name] = counts.get(ev.name, 0) + 1
    return counts


def _jit_sums(tele, span):
    return {n: tele.histogram(n, {"span": span}) for n in JIT_HISTOGRAMS}


def test_span_is_a_host_event_of_a_trace_without_the_python_tracer(tmp_path):
    tele = Telemetry()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with tele.span("outer"):
            with tele.span("inner", {"rank": 3}):
                jnp.ones((8,)).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    events = _host_events(tmp_path)
    assert events.get("outer") == 1 and events.get("outer/inner") == 1
    assert not any(name.startswith("$") for name in events)


def test_span_samples_are_starts_and_durations_oldest_first_and_bounded():
    tele = Telemetry(ring_size=4)
    for _ in range(6):
        with tele.span("tick"):
            pass
    with tele.span("tick", {"rank": 1}):
        pass
    samples = tele.span_samples("tick")
    assert len(samples) == 4
    starts = [t0 for t0, _d in samples]
    assert starts == sorted(starts) and len(set(starts)) == 4
    assert all(d >= 0.0 for _t0, d in samples)
    # a sample ends before the next one starts: same clock for both
    assert all(t0 + d <= nxt for (t0, d), (nxt, _) in zip(samples,
                                                          samples[1:]))
    assert tele.span_rollup("tick")["count"] == 6  # the aggregates are exact
    assert len(tele.span_samples("tick", {"rank": 1})) == 1
    assert tele.span_samples("never") == []
    tele.reset()
    assert tele.span_samples("tick") == []


def test_the_ring_the_benchmark_reaches_into_still_holds_floats():
    """``chipbench/harness.py`` ``span_samples`` reads
    ``tele._spans[(path, ())].ring`` under ``tele._lock``."""
    tele = Telemetry()
    with tele.span("train/step_chunk"):
        pass
    with tele._lock:
        ring = list(tele._spans[("train/step_chunk", ())].ring)
    assert len(ring) == 1 and type(ring[0]) is float
    assert ring == [d for _t0, d in tele.span_samples("train/step_chunk")]


def test_samples_survive_a_pickle_and_an_older_pickle_still_reads():
    tele = Telemetry()
    with tele.span("a"):
        pass
    clone = pickle.loads(pickle.dumps(tele))
    assert clone.span_samples("a") == tele.span_samples("a")
    state = tele.__getstate__()
    del state["_span_starts"]  # a bus pickled before starts were kept
    old = Telemetry.__new__(Telemetry)
    old.__setstate__(state)
    assert old.span_samples("a") == []
    with old.span("a"):
        pass
    assert len(old.span_samples("a")) == 1
    assert old.span_rollup("a")["count"] == 2


def test_a_synced_spans_wait_is_recorded_beside_its_sample():
    """``Span.sync`` times its own block: the wait is part of the span's
    duration, one number a sample in ``span_waits`` (one for one with
    ``span_samples``, whose pairs keep their shape), 0.0 for a span that
    never synced, bounded and reset like the starts."""
    tele = Telemetry(ring_size=3)
    program = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((256, 256))
    program(x).block_until_ready()
    with tele.span("work") as span:
        out = program(x)
        span.sync(out)
    assert span.synced and 0.0 < span.wait_s <= span.duration_s
    with tele.span("work") as twice:
        twice.sync(program(x))
        first = twice.wait_s
        twice.sync(program(x))
    assert first < twice.wait_s <= twice.duration_s  # the waits add up
    with tele.span("work") as never:
        time.sleep(0.001)
    assert never.wait_s == 0.0 and not never.synced
    with tele.span("other", {"rank": 1}) as host:
        host.sync(3.0, np.ones(2))  # host values: nothing to wait for
    assert 0.0 <= host.wait_s <= host.duration_s
    waits, samples = tele.span_waits("work"), tele.span_samples("work")
    assert waits == [span.wait_s, twice.wait_s, 0.0]
    assert all(len(pair) == 2 for pair in samples)
    assert all(w <= d for w, (_t0, d) in zip(waits, samples))
    assert tele.span_waits("other", {"rank": 1}) == [host.wait_s]
    assert tele.span_waits("never") == []
    with tele.span("work"):
        pass
    assert tele.span_waits("work") == [twice.wait_s, 0.0, 0.0]  # the ring's
    assert len(tele.span_samples("work")) == 3
    tele.reset()
    assert tele.span_waits("work") == [] and tele._span_waits == {}


def test_the_waits_travel_with_a_pickled_bus_and_an_older_pickle_has_none():
    tele = Telemetry()
    with tele.span("a") as a:
        a.sync(jnp.ones(3) + 1)
    with tele.span("a"):
        pass
    clone = pickle.loads(pickle.dumps(tele))
    assert clone.span_waits("a") == tele.span_waits("a") == [a.wait_s, 0.0]
    state = tele.__getstate__()
    del state["_span_waits"]  # a bus pickled before waits were kept
    old = Telemetry.__new__(Telemetry)
    old.__setstate__(state)
    assert old.span_waits("a") == [0.0, 0.0]
    with old.span("a") as later:
        later.sync(jnp.ones(3) * 2)
    assert old.span_waits("a") == [0.0, 0.0, later.wait_s]
    assert len(old.span_samples("a")) == 3


def test_the_wait_is_a_host_event_of_its_own_and_the_sink_gets_it(tmp_path):
    tele = Telemetry()
    seen = []
    tele.add_sink(seen.append)
    program = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((128, 128))
    program(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with tele.span("train"):
            with tele.span("step_chunk") as span:
                span.sync(program(x))
            with tele.span("chunk_records"):
                pass
    finally:
        jax.profiler.stop_trace()
    events = _host_events(tmp_path)
    assert events.get("train/step_chunk") == 1
    assert events.get("train/step_chunk/wait") == 1
    assert "train/chunk_records/wait" not in events
    # no character at which the benchmark's gap names are cut
    assert not set("#([:") & set("train/step_chunk/wait")
    # an annotation, not a span: the bus has no sample of that path
    assert "train/step_chunk/wait" not in tele.snapshot()["spans"]
    by_name = {e["name"]: e for e in seen if e["kind"] == "span"}
    assert by_name["train/step_chunk"]["wait_s"] == span.wait_s > 0.0
    assert by_name["train/chunk_records"]["wait_s"] == 0.0


def test_compiles_inside_a_synced_span_stay_filed_under_the_span():
    """The wait opens no span: a fused fit's first chunk traces, lowers
    and compiles inside ``train/step_chunk``, and every one of those
    events is still that path's; the ring the benchmark reaches into is
    the durations, as it was."""
    tele = Telemetry()
    x, y = _rows()
    train_distributed(_payload(), x, labels=y, iters=8, steps_per_call=4,
                      mini_batch=8, telemetry=tele)
    filed = _jit_sums(tele, "train/step_chunk")
    assert filed["jit.trace_s"]["sum"] > 0 and filed["jit.lower_s"]["sum"] > 0
    assert filed["jit.compile_s"]["count"] + filed[
        "jit.cache_load_s"]["count"] >= 1
    assert all(h["count"] == 0 for h in _jit_sums(
        tele, "train/step_chunk/wait").values())
    assert not any("wait" in k for k in tele.snapshot()["spans"])
    samples = tele.span_samples("train/step_chunk")
    waits = tele.span_waits("train/step_chunk")
    assert len(samples) == len(waits) == 2
    assert all(0.0 < w <= d for w, (_t0, d) in zip(waits, samples))
    # the first chunk's enqueue holds its trace and compile; later ones
    # enqueue in a fraction of what they wait
    assert samples[0][1] - waits[0] > sum(
        h["sum"] for h in filed.values()) * 0.5
    ring = list(tele._spans[("train/step_chunk", ())].ring)
    assert ring == [d for _t0, d in samples]
    assert all(isinstance(d, float) for d in ring)


def test_compile_events_go_to_the_innermost_open_span_and_nowhere_else():
    mine, other = Telemetry(), Telemetry()

    @jax.jit
    def inner(x):
        return x * 3.0 + 1.0

    def program(x):
        return inner(x).sum()

    with other.span("theirs"):
        pass  # closed: sees nothing of what follows
    jax.jit(program)(jnp.ones((5,))).block_until_ready()  # no span open
    assert all(h["count"] == 0 for b in (mine, other)
               for s in ("outer", "outer/build", "theirs")
               for h in _jit_sums(b, s).values())

    with mine.span("outer") as outer:
        with mine.span("build") as build:
            jax.jit(program)(jnp.ones((7,))).block_until_ready()
    got = _jit_sums(mine, "outer/build")
    # the outer jit and the jit it calls each have a trace event
    assert got["jit.trace_s"]["count"] >= 2
    assert got["jit.lower_s"]["count"] >= 1
    assert got["jit.compile_s"]["count"] >= 1
    # own times: they add up to no more than the span that held them
    assert sum(h["sum"] for h in got.values()) <= build.duration_s
    assert build.duration_s <= outer.duration_s
    assert all(h["count"] == 0 for h in _jit_sums(mine, "outer").values())
    assert all(h["count"] == 0 for h in _jit_sums(other, "theirs").values())


def test_nested_compile_events_count_their_own_time_once():
    """Tracing one large program fires thousands of trace events inside
    the outermost one (5,952 for a BERT-base step): the outer event's
    sample is its duration less all of theirs, however many."""
    tele = Telemetry()
    trace_event, lower_event = list(bus_module.JIT_EVENT_HISTOGRAMS)[:2]
    with tele.span("big"):
        t0 = time.perf_counter()
        for _ in range(3000):
            t1 = time.perf_counter()
            while time.perf_counter() - t1 < 2e-6:
                pass
            bus_module._on_jax_duration(trace_event,
                                        time.perf_counter() - t1)
        bus_module._on_jax_duration(lower_event, 1e-6)
        outer = time.perf_counter() - t0
        bus_module._on_jax_duration(trace_event, outer)
    got = _jit_sums(tele, "big")
    assert got["jit.trace_s"]["count"] == 3001
    assert got["jit.lower_s"]["count"] == 1
    assert sum(h["sum"] for h in got.values()) == pytest.approx(outer,
                                                              rel=1e-6)
    # the next, separate event is not taken for a child of the last
    with tele.span("after"):
        time.sleep(0.002)
        bus_module._on_jax_duration(trace_event, 0.001)
    assert _jit_sums(tele, "after")["jit.trace_s"]["sum"] \
        == pytest.approx(0.001)


def test_two_buses_and_two_threads_do_not_see_each_others_compiles():
    a, b = Telemetry(), Telemetry()
    in_b = threading.Event()
    done = threading.Event()

    def worker():
        with b.span("b_only"):
            in_b.set()
            done.wait(timeout=30)

    thread = threading.Thread(target=worker)
    thread.start()
    assert in_b.wait(timeout=30)
    try:
        with a.span("a_only"):
            jax.jit(lambda x: x - 2.0)(jnp.ones((3, 3))).block_until_ready()
    finally:
        done.set()
        thread.join(timeout=30)
    assert not thread.is_alive()
    assert _jit_sums(a, "a_only")["jit.compile_s"]["count"] >= 1
    # b's span was open the whole time, on another thread and bus
    assert all(h["count"] == 0 for h in _jit_sums(b, "b_only").values())
    assert all(h["count"] == 0 for h in _jit_sums(b, "a_only").values())


def test_sync_trainer_tiles_the_fused_loop_with_top_level_spans():
    tele = Telemetry()
    x, y = _rows()
    seen = []
    result = train_distributed(_payload(), x, labels=y, iters=12,
                               steps_per_call=4, mini_batch=8,
                               metrics_hook=seen.append, telemetry=tele)
    spans = tele.snapshot()["spans"]
    assert spans["train/enter"]["count"] == 1
    assert spans["train/build_step"]["count"] >= 1
    for path in ("train/chunk_prepare", "train/step_chunk",
                 "train/chunk_readback", "train/chunk_records"):
        assert spans[path]["count"] == 3, path  # 12 steps, 4 a chunk
    # top-level, unlabelled: the key the benchmark reads
    assert ("train/step_chunk", ()) in tele._spans
    assert not any("/train/" in k or "{" in k for k in spans)
    # one iteration is prepare, chunk, readback, records, in that order
    order = sorted(
        (t0, path) for path in ("train/chunk_prepare", "train/step_chunk",
                                "train/chunk_readback", "train/chunk_records")
        for t0, _d in tele.span_samples(path))
    assert [p.rsplit("/", 1)[1] for _t, p in order[:4]] == [
        "chunk_prepare", "step_chunk", "chunk_readback", "chunk_records"]
    # the entry stamp precedes everything else the call recorded
    entry = tele.span_samples("train/enter")[0][0]
    assert all(entry <= tele.span_samples(p.split("{")[0])[0][0]
               for p in spans)
    # the first chunk's trace, lowering and compile are filed under it
    filed = _jit_sums(tele, "train/step_chunk")
    assert filed["jit.trace_s"]["sum"] > 0 and filed["jit.lower_s"]["sum"] > 0
    assert sum(h["sum"] for h in filed.values()) \
        <= tele.span_samples("train/step_chunk")[0][1]
    assert len(result.metrics) == len(seen) == 12


def test_per_step_path_keeps_its_one_span_a_step():
    tele = Telemetry()
    x, y = _rows()
    train_distributed(_payload(), x, labels=y, iters=3, steps_per_call=1,
                      telemetry=tele)
    spans = tele.snapshot()["spans"]
    assert spans["train/step"]["count"] == 3
    assert not any(k.startswith("train/chunk_") for k in spans)


@pytest.mark.parametrize("steps_per_call", [4, 1])
def test_hook_gets_per_leaf_gradient_norms_and_the_recorder_does_not(
        steps_per_call):
    x, y = _rows()
    seen = []
    result = train_distributed(_payload(), x, labels=y, iters=8,
                               steps_per_call=steps_per_call,
                               metrics_hook=seen.append,
                               telemetry=Telemetry())
    keys = seen[0]["leaf_grad_norm_keys"]
    assert keys == ["Dense_0.bias", "Dense_0.kernel", "Dense_1.bias",
                    "Dense_1.kernel"]
    assert not any("leaf_grad_norm_keys" in r for r in seen[1:])
    for record, kept in zip(seen, result.metrics):
        row = record["leaf_grad_norms"]
        assert row.shape == (len(keys),) and row.dtype == np.float32
        # the leaves' norms compose to the global norm of the same step
        assert float(np.sqrt(np.sum(np.square(row.astype(np.float64))))) \
            == pytest.approx(record["grad_norm"], rel=1e-5)
        assert "leaf_grad_norms" not in kept
        assert {k: record[k] for k in kept} == kept


def test_step_program_carries_its_phases_in_op_name():
    spec = ModelSpec(module=Net(), loss="mse", optimizer="adam",
                     optimizer_params={"lr": 1e-2}, input_shape=(10,))
    mesh = build_mesh()
    x, y = _rows(64)
    batch = prepare_sharded_batch(handle_features(x, y, 0.0, 0)[0], mesh)
    tx = spec.make_optimizer()
    state = create_train_state(spec, jax.random.key(0),
                               sample_x=batch.x[:1], tx=tx)
    fn = make_train_epoch(spec.make_module().apply, spec.loss_fn(), tx,
                          mesh, 4, mini_batch=4)
    lowered = fn.lower(state, batch)
    assert lowered.as_text().startswith("module @jit_train_epoch ")
    names = set(re.findall(r'loc\("([^"]+)"', lowered.as_text(debug_info=True)))
    for scope in ("sample/", "jvp(forward_loss)/",
                  "transpose(jvp(forward_loss))/", "grad_allreduce/",
                  "optimizer/", "step_stats/"):
        assert any(n.startswith(scope) for n in names), scope
    # and the compiled program keeps them as op_name metadata
    op_names = re.findall(r'op_name="([^"]+)"', lowered.compile().as_text())
    for scope in ("/jvp(forward_loss)/", "/transpose(jvp(forward_loss))/",
                  "/grad_allreduce/", "/optimizer/"):
        assert any(scope in n for n in op_names), scope


def test_profile_dir_capture_holds_spans_and_steps_but_no_python_events(
        tmp_path):
    tele = Telemetry()
    x, y = _rows()
    train_distributed(_payload(), x, labels=y, iters=8, steps_per_call=4,
                      mini_batch=8, telemetry=tele,
                      profile_dir=str(tmp_path))
    events = _host_events(tmp_path)
    assert events.get("train/step_chunk") == 2
    assert events.get("train/chunk_readback") == 2
    assert events.get("train_step") == 2  # the step annotations
    assert not any(name.startswith("$") for name in events)
    # obs.xprof's analysis at stop time still finds its steps
    assert tele.counter_value("xprof.analyze_failures") == 0

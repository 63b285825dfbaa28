"""The ``setup_*`` readers on a hand-made bus whose phases are known,
and on a bus that keeps no starts (a program from before PR 24)."""

import json
import time

import pytest

from chipbench import harness, setup_phases
from sparktorch_tpu.obs.telemetry import Telemetry

BENCH = json.loads(harness.BENCHMARK_JSON.read_text())
SETUP = [m for m in BENCH["per_layer"] if m["name"].startswith("setup_")]


class HandMadeBus:
    """The two public readers of the program's bus that the phases
    use, over samples written by hand."""

    def __init__(self):
        self.spans, self.hists = {}, {}

    def put(self, path, t0, dur):
        self.spans.setdefault(path, []).append((harness.T_PROCESS + t0, dur))

    def observe(self, name, value, span):
        self.hists[(name, span)] = self.hists.get((name, span), 0.0) + value

    def span_samples(self, path, labels=None):
        return list(self.spans.get(path, []))

    def histogram(self, name, labels=None):
        return {"sum": self.hists.get((name, labels["span"]), 0.0)}


def hand_made():
    """On a clock whose zero is the process's start: 0..10 the caller;
    enter at 10; data_prep 10.0-11.0; build_step 11.0-11.1 and
    13.1-13.5; init 11.1-13.1; shuffle 13.5-14.0; the first chunk
    14.5-24.5 (trace 4, lower 1, compile 2 of which the cache's load
    1.5, then its run), three more of 2 s."""
    bus = HandMadeBus()
    bus.put("train/enter", 10.0, 0.001)
    bus.put("train/data_prep", 10.0, 1.0)
    bus.put("train/build_step", 11.0, 0.1)
    bus.put("train/init", 11.1, 2.0)
    bus.put("train/build_step", 13.1, 0.4)
    bus.put("train/shuffle", 13.5, 0.5)
    bus.put("train/step_chunk", 14.5, 10.0)
    for k in range(3):
        bus.put("train/step_chunk", 24.6 + 2.1 * k, 2.0)
    bus.observe("jit.trace_s", 3.0, "train/step_chunk")
    bus.observe("jit.trace_s", 1.0, "train/step_chunk")
    bus.observe("jit.lower_s", 1.0, "train/step_chunk")
    bus.observe("jit.compile_s", 0.5, "train/step_chunk")  # own: less its load
    bus.observe("jit.cache_load_s", 1.5, "train/step_chunk")
    bus.observe("jit.compile_s", 9.0, "train/init")        # not the step's
    return bus


def ctx_of(tele):
    return {"inputs": {"telemetry": tele}, "trace": None, "summary": None}


def test_phases_tile_process_start_to_the_first_chunks_end():
    got = setup_phases.phases(ctx_of(hand_made()))
    assert got == {
        "before_call": pytest.approx(10.0),
        "data_place": pytest.approx(1.5),
        "init": pytest.approx(2.0),
        "step_trace": pytest.approx(5.0),
        "step_load": pytest.approx(2.0),
        "build_step": pytest.approx(0.5),
        "first_chunk_run": pytest.approx(2.0),
        # 24.5 in all: the 0.5 s between shuffle and the chunk, and the
        # second of the first chunk that is neither trace, load nor run
        "unaccounted": pytest.approx(1.5),
    }
    assert sum(got.values()) == pytest.approx(24.5)


@pytest.mark.parametrize("metric", [m["name"] for m in SETUP])
def test_each_reader_reads_its_phase(metric):
    reader = harness.load_module("layer_metrics", metric)
    expected = {"setup_before_call_s": 10.0, "setup_data_place_s": 1.5,
                "setup_init_s": 2.0, "setup_step_trace_s": 5.0,
                "setup_step_load_s": 2.0, "setup_unaccounted_s": 1.5}
    assert reader.read(ctx_of(hand_made())) == pytest.approx(expected[metric])
    # nothing to read: no bus, a bus without starts, a call that never
    # ran a second chunk
    assert reader.read({"inputs": {}}) is None

    class OldBus:
        def histogram(self, *_a):
            return {"sum": 0.0}

    assert reader.read(ctx_of(OldBus())) is None
    assert reader.read(ctx_of(Telemetry())) is None


def test_the_six_are_declared_for_both_cells():
    assert len(SETUP) == 6
    for m in SETUP:
        assert m["moves"] == "setup_s" and m["unit"] == "s"
        assert m["source"] == "program_span" and m["better"] == "lower"
        assert m["workloads"] == ["bert_base_fit_sync", "bert_base_fit_dp4"]


def test_a_real_bus_gives_phases_that_add_up():
    """The program's own spans, from a span-by-span imitation of the
    call's order (no trainer: this suite's job tests run that)."""
    tele = Telemetry()
    with tele.span("train/enter"):
        pass
    for path in ("train/data_prep", "train/init", "train/build_step",
                 "train/shuffle", "train/step_chunk", "train/step_chunk"):
        with tele.span(path):
            time.sleep(0.002)
    got = setup_phases.phases(ctx_of(tele))
    end = sum(tele.span_samples("train/step_chunk")[0])
    assert sum(got.values()) == pytest.approx(end - harness.T_PROCESS)
    assert got["unaccounted"] >= 0.0 and got["before_call"] > 0.0

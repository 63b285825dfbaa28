"""The reduction from trace to numbers, on a recorded cut of a chip
trace and on a small table whose numbers are worked out by hand."""

import json
from pathlib import Path

import pytest

from chipbench import trace

DATA = Path(__file__).parent / "data"


def recorded():
    """2.5 ms around the end of a 32-step chunk of ``bert_base_fit_sync``
    (TPU v5 lite, my chip run, PR 23): the chunk's last operations, the
    idle gap while the host reads the chunk's metrics back, and the host
    events of that stretch. Times are ns from the cut's start."""
    cut = json.loads((DATA / "bert_chunk_boundary.json").read_text())
    table = {p: {l: [tuple(e) for e in evs] for l, evs in lines.items()}
             for p, lines in cut["table"].items()}
    return table, tuple(cut["window"])


def test_recorded_cut_busy_idle_and_names():
    table, window = recorded()
    assert trace.device_planes(table) == ["/device:TPU:0"]
    # by hand: the 21 operations of the cut, none overlapping, add up to
    # 254,607 ns (a raster of the window at 1 ns gives the same)
    assert trace.busy_seconds(table, window) == pytest.approx(254607e-9)
    ops = trace.op_times(table, window, top=2)
    assert ops[0] == ["multiply_reduce_fusion.1093",
                      pytest.approx(252948e-9)]
    assert ops[1][0] == "dynamic_update_slice.59"
    # the rest of the window is idle, and the innermost host event that
    # covers most of it is the program's own step annotation
    gaps = trace.idle_gaps(table, window)
    assert gaps == [["train_step", pytest.approx((2.5e6 - 254607) * 1e-9)]]
    assert trace.collective_exposed_seconds(table, window) == 0.0
    summary = trace.summarize(
        table, {"annotation": "np.asarray(jax.Array)"})
    assert summary["window_s"] == pytest.approx(613810e-9)
    assert summary["busy_s"] == 0.0


def by_hand():
    """Two chips, two executions of one program each, 100 ns apiece.

    chip 0, second execution (100..200):
      fusion.1 100-130, all-reduce-start.2 130-135 (its transfer runs
      130-195 on the async line), fusion.2 135-175 (hides 40 ns of the
      transfer), all-reduce-done.2 175-195, then idle to 200.
    chip 1 is the same, 10 ns later, and ends at 205.
    """
    def chip(shift):
        ops = [("%while.7 = (s32[]) while(...)", 0, 95),
               ("%fusion.1 = bf16[8] fusion(%all-reduce.9)", 0, 95)]
        ops += [(n, s + shift, d) for n, s, d in (
            ("%while.7 = (s32[]) while(...)", 100, 95),
            ("%fusion.1 = bf16[8] fusion(%all-reduce.9)", 100, 30),
            ("%all-reduce-start.2 = f32[4] all-reduce-start(...)", 130, 5),
            ("%fusion.2 = f32[4] fusion(...)", 135, 40),
            ("%all-reduce-done.2 = f32[4] all-reduce-done(...)", 175, 20))]
        return {
            trace.OPS_LINE: ops,
            trace.ASYNC_LINE: [
                ("%all-reduce-start.2 = f32[4] all-reduce-start(...)",
                 130 + shift, 65)],
            trace.MODULES_LINE: [("jit_step(1)", 0, 100),
                                 ("jit_step(1)", 100 + shift, 100),
                                 ("jit_tiny(2)", 96, 2)],
            "Steps": [("0", 0, 300)],
        }

    return {
        "/device:TPU:0": chip(0), "/device:TPU:1": chip(10),
        "/host:CPU": {"python": [("chipbench/window", 50, 300),
                                 ("readback", 190, 20),
                                 ("PjitFunction(step)", 96, 3)]},
    }


def test_by_hand_windows_busy_exposed_and_gaps():
    table = by_hand()
    # from the end of the first execution of the dominant program to the
    # end of its last, on the first chip
    window = trace.steady_window(table, {"module_skip_first": 1})
    assert window == (100, 200)
    assert trace.steady_window(table, {"annotation": "chipbench/window"}) \
        == (50, 350)
    # chip 0 is busy 100-195; chip 1 110-200: 95 and 90 ns
    assert trace.busy_seconds(table, window) == pytest.approx(92.5e-9)
    # exposed: the transfer (130-195) less fusion.2 (135-175) is 25 ns
    # on chip 0; on chip 1 (140-205, fusion.2 145-185) the window cuts
    # the tail at 200: 5 + 15 = 20 ns
    assert trace.collective_exposed_seconds(table, window) \
        == pytest.approx(22.5e-9)
    # a fusion that only NAMES an all-reduce among its operands is
    # compute, and the while that contains everything counts nothing
    names = [n for n, _t in trace.op_times(table, window)]
    assert names[:2] == ["fusion.2", "fusion.1"]
    assert "while.7" not in names
    # chip 0's one gap, 195-200, lies inside the host's readback
    assert trace.idle_gaps(table, window) == [
        ["readback", pytest.approx(5e-9)]]
    assert trace.module_runs(table, window) == [pytest.approx(100e-9)]


def test_intervals():
    assert trace.union([(5, 9), (0, 3), (2, 4), (9, 9)]) == [(0, 4), (5, 9)]
    assert trace.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert trace.clip([(0, 4), (5, 9)], (3, 6)) == [(3, 4), (5, 6)]
    segs = dict(trace.self_segments([("outer", 0, 10), ("a", 1, 3),
                                     ("b", 6, 2)]))
    assert segs == {"outer": [(0, 1), (4, 6), (8, 10)], "a": [(1, 4)],
                    "b": [(6, 8)]}


def test_a_trace_without_a_chip_is_an_error():
    with pytest.raises(ValueError):
        trace.busy_seconds({"/host:CPU": {}}, (0, 1))

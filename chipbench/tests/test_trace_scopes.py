"""The step's device time by scope: the map from instruction to
``op_name`` read out of a trace file's metadata plane (on a capture made
here, by the installed profiler), and the reduction by scope on a small
table worked out by hand and on a recorded cut of a chip trace."""

import json
from pathlib import Path

import pytest

from chipbench import harness, trace, trace_scopes

DATA = Path(__file__).parent / "data"
BENCH = json.loads(harness.BENCHMARK_JSON.read_text())


def test_scope_of_an_op_name():
    scope = trace_scopes.scope_of
    body = "jit(train_epoch)/shard_map/while/body/closed_call/"
    assert scope(body + "jvp(forward_loss)/Bert/layer_3/dot_general") \
        == "forward"
    assert scope(body + "transpose(jvp(forward_loss))/Bert/layer_3/"
                 "dot_general") == "backward"
    assert scope(body + "optimizer/mul") == "optimizer"
    assert scope(body + "grad_allreduce/psum") == "grad_allreduce"
    assert scope(body + "step_stats/reduce_sum") == "step_stats"
    assert scope(body + "sample/dynamic_slice") == "sample"
    # the outermost phase wins; a module of the model named like a phase
    # does not move its operations
    assert scope(body + "jvp(forward_loss)/optimizer/add") == "forward"
    assert scope("jit(train_epoch)/while/body/dynamic_update_slice") \
        == "unscoped"
    assert scope("") == scope(None) == "unscoped"


def test_op_names_are_read_from_the_trace_files_metadata_plane(tmp_path,
                                                               monkeypatch):
    """A capture of a scoped program made here: the installed profiler
    writes the plane, the wire reader finds the program and the scopes."""
    import jax
    import jax.numpy as jnp

    def phases(x):
        with jax.named_scope("forward_loss"):
            y = jnp.tanh(x @ x)
        with jax.named_scope("optimizer"):
            return y * 0.5 - x

    program = jax.jit(phases)
    x = jnp.ones((64, 64))
    program(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        program(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    data = trace.newest_xplane(tmp_path).read_bytes()
    protos = trace_scopes.hlo_protos(data)
    [name] = [n for n in protos if n.startswith("jit_phases(")]
    names = trace_scopes.instructions(protos[name])
    scopes = {trace_scopes.scope_of(op) for op, _inner in names.values()}
    assert {"forward", "optimizer"} <= scopes
    assert any(op.endswith("forward_loss/dot_general")
               for op, _inner in names.values())
    # by its module name, and by its function's name when the id differs
    assert trace_scopes.program_instructions(data, name) == names
    assert trace_scopes.program_instructions(data, "jit_phases(0)") == names
    assert trace_scopes.program_instructions(data, "jit_other(1)") == {}
    # what the fused computations hold comes with the fusion that calls them
    for fusion, inside in trace_scopes.mixed_fusions(names).items():
        assert inside == ["forward", "optimizer"], fusion

    # the whole reader, on that file under a checkout's trace directory
    # and a device table that runs two of the program's operations
    fwd = next(n for n, (op, _i) in names.items()
               if trace_scopes.scope_of(op) == "forward")
    opt = next(n for n, (op, _i) in names.items()
               if trace_scopes.scope_of(op) == "optimizer")
    table = {"/device:TPU:0": {
        trace.OPS_LINE: [(f"%{fwd} = f32[64,64] op(...)", 100, 60),
                         (f"%{opt} = f32[64,64] op(...)", 160, 30),
                         (f"%{fwd} = f32[64,64] op(...)", 200, 60),
                         (f"%{opt} = f32[64,64] op(...)", 260, 30)],
        trace.MODULES_LINE: [(name, 0, 95), (name, 100, 95),
                             (name, 200, 95)]}}
    monkeypatch.setattr(harness, "REPO", tmp_path)
    kept = tmp_path / ".chipbench_trace" / "a_cell"
    kept.mkdir(parents=True)
    (kept / "t.xplane.pb").write_bytes(data)

    class Cell:
        name = "a_cell"

    ctx = {"cell": Cell, "inputs": {"steps_per_call": 2}, "trace": table,
           "summary": {"window": trace.steady_window(
               table, {"module_skip_first": 1})}}
    # two executions of two steps each inside the window
    assert trace_scopes.step_ms(ctx, "forward") == pytest.approx(120e-6 / 4)
    assert trace_scopes.step_ms(ctx, "optimizer") == pytest.approx(60e-6 / 4)
    assert trace_scopes.step_ms(ctx, "backward") == 0.0
    # a program whose operations carry no scope reads nothing
    (kept / "t.xplane.pb").write_bytes(b"")
    ctx.pop("_scope_ms")
    assert trace_scopes.step_ms(ctx, "forward") is None


def recorded():
    """0.6 ms of one step of ``bert_base_fit_sync`` on chip 0 (TPU v5
    lite, my chip run, PR 24), around the end of layer 11's weight
    gradients: 65 operations with the ``op_name`` the trace file's
    metadata plane gave each, and one ``op_name`` per scope of what each
    fusion holds. Times are ns from the cut's start."""
    cut = json.loads((DATA / "bert_step_scopes.json").read_text())
    table = {p: {l: [tuple(e) for e in evs] for l, evs in lines.items()}
             for p, lines in cut["table"].items()}
    names = {n: (op, inner) for n, (op, inner) in cut["names"].items()}
    return table, tuple(cut["window"]), names


def test_recorded_cut_by_scope():
    table, window, names = recorded()
    got = trace_scopes.scope_seconds(table, window, names)
    # by hand: multiply_reduce_fusion.832 runs into the cut (299,592 ns
    # of it inside), add_multiply_fusion.52 165, fusion.2713 49,191 and
    # fusion.2714 out of it (219,256 inside): all carry the backward's
    # scope; the seven multiply_reduce_fusion.8xx/9xx that carry
    # ``step_stats`` add up to 13,511; copies and slices carry none
    assert got["backward"] == pytest.approx(568204e-9)
    assert got["step_stats"] == pytest.approx(13511e-9)
    assert got["unscoped"] == pytest.approx(14020e-9)
    assert got["forward"] == got["optimizer"] == got["sample"] == 0.0
    # the while that holds the step counts nothing: the 4,265 ns between
    # its operations are busy time (``step_device_ms``) under no scope
    assert trace.busy_seconds(table, window) == pytest.approx(600000e-9)
    assert sum(got.values()) == pytest.approx((600000 - 4265) * 1e-9)
    # what the chip showed: no operation is the optimizer's alone. The
    # weight-gradient fusions hold the division by the row count, the
    # Adam update and the norm's square-and-sum, under the backward's
    # scope or under ``step_stats``
    mixed = trace_scopes.mixed_fusions(names)
    assert mixed["multiply_reduce_fusion.832"] == [
        "backward", "grad_allreduce", "optimizer", "step_stats"]
    assert trace_scopes.scope_of(names["multiply_reduce_fusion.832"][0]) \
        == "backward"
    assert mixed["multiply_reduce_fusion.985"] == ["optimizer", "step_stats"]
    assert trace_scopes.scope_of(names["multiply_reduce_fusion.985"][0]) \
        == "step_stats"
    assert not any(trace_scopes.scope_of(op) == "optimizer"
                   for op, _inner in names.values())


def test_wire_reader_on_bytes_written_by_hand():
    # field 1 varint 150; field 2 bytes "hi"; field 3 fixed32 7;
    # field 4 packed varints 3, 270
    msg = bytes([0x08, 0x96, 0x01, 0x12, 0x02]) + b"hi" \
        + bytes([0x1D, 7, 0, 0, 0, 0x22, 0x03, 0x03, 0x8E, 0x02])
    got = list(trace_scopes.fields(memoryview(msg)))
    assert [(n, v if isinstance(v, int) else bytes(v)) for n, v in got] == [
        (1, 150), (2, b"hi"), (3, 7), (4, bytes([0x03, 0x8E, 0x02]))]
    assert list(trace_scopes._packed(got[3][1])) == [3, 270]
    with pytest.raises(ValueError):
        list(trace_scopes.fields(memoryview(bytes([0x0B]))))  # a group


def by_hand():
    """Two chips, one 100 ns step each inside the window 0..100 (chip 1
    runs 10 ns later and is cut at 100):

      while.1 spans the step; inside it fusion.1 0-30 (forward),
      fusion.2 30-70 (backward) with all-reduce.3 60-70 nested in no
      one (it starts when fusion.2 ends on chip 0: 70-80), fusion.4
      80-95 (optimizer), copy.5 95-98 (no op_name), idle 98-100.
    """
    def chip(shift):
        return {trace.OPS_LINE: [
            ("%while.1 = (s32[]) while(...)", shift, 98),
            ("%fusion.1 = bf16[8] fusion(...)", shift, 30),
            ("%fusion.2 = bf16[8] fusion(...)", 30 + shift, 40),
            ("%all-reduce.3 = f32[8] all-reduce(...)", 70 + shift, 10),
            ("%fusion.4 = f32[8] fusion(...)", 80 + shift, 15),
            ("%copy.5 = f32[8] copy(...)", 95 + shift, 3)],
            trace.MODULES_LINE: [("jit_train_epoch(7)", shift, 98)]}

    table = {"/device:TPU:0": chip(0), "/device:TPU:1": chip(10)}
    body = "jit(train_epoch)/while/body/"
    names = {
        "fusion.1": (body + "jvp(forward_loss)/dot_general", []),
        "fusion.2": (body + "transpose(jvp(forward_loss))/dot_general",
                     [body + "transpose(jvp(forward_loss))/mul",
                      body + "optimizer/add"]),
        "all-reduce.3": (body + "grad_allreduce/psum", []),
        "fusion.4": (body + "optimizer/sub", [body + "optimizer/sub"]),
        "while.1": ("jit(train_epoch)/while", []),
    }
    return table, (0, 100), names


def test_by_hand_scope_seconds_and_mixed_fusions():
    table, window, names = by_hand()
    got = trace_scopes.scope_seconds(table, window, names)
    # chip 1 is cut at 100: its optimizer runs 90-100 and its copy not
    # at all; the while counts nothing
    assert got == {
        "sample": 0.0,
        "forward": pytest.approx(30e-9),
        "backward": pytest.approx(40e-9),
        "grad_allreduce": pytest.approx(10e-9),
        "optimizer": pytest.approx((15 + 10) / 2 * 1e-9),
        "step_stats": 0.0,
        "unscoped": pytest.approx(3 / 2 * 1e-9),
    }
    # all of it is the busy time, which the same table gives otherwise
    assert sum(got.values()) == pytest.approx(
        trace.busy_seconds(table, window))
    assert trace_scopes.mixed_fusions(names) == {
        "fusion.2": ["backward", "optimizer"]}


def test_step_ms_reads_nothing_without_a_trace_or_scopes(tmp_path,
                                                         monkeypatch):
    class Cell:
        name = "no_such_cell"

    ctx = {"cell": Cell, "inputs": {"steps_per_call": 1}, "trace": None,
           "summary": None}
    assert trace_scopes.step_ms(ctx, "forward") is None
    table, window, _names = by_hand()
    ctx = {"cell": Cell, "inputs": {"steps_per_call": 1}, "trace": table,
           "summary": {"window": window}}
    # no trace file of that cell under the checkout
    assert trace_scopes.step_ms(ctx, "forward") is None
    assert trace_scopes.step_ms({**ctx, "inputs": {}}, "forward") is None
    for m in BENCH["per_layer"]:
        if m["name"] in ("step_forward_ms", "step_backward_ms",
                         "step_optimizer_ms"):
            reader = harness.load_module("layer_metrics", m["name"])
            assert reader.read(dict(ctx)) is None
            assert m["layer"] == "Step program" and m["unit"] == "ms"
            assert m["moves"] == "train_rate_sync"
            assert m["source"] == "device_trace"

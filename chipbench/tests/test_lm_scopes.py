"""The language-model readers: device time by the model's scopes, the
sparse-attention kernels' roofline share and the experts' imbalance, on
a table worked out by hand, on a program without the scopes (the parent
commit's), and on a recorded cut of a chip trace."""

import json
from pathlib import Path

import pytest

from chipbench import harness, lm_scopes, run, trace, trace_scopes

TINY = Path(__file__).parent / "tiny"
DATA = Path(__file__).parent / "data"
BODY = "jit(train_epoch)/shard_map/while/body/closed_call/"


def test_scope_of_an_op_name_under_transformations():
    scope = lm_scopes.scope_of
    fwd = BODY + "jvp(forward_loss)/jvp(SparseMoELM)/jvp(layer_1)/"
    assert scope(fwd + "jvp(attn)/jvp(indexer)/dot_general") == "indexer"
    assert scope(fwd + "attn/select_topk/while/body/reduce_sum") \
        == "select_topk"
    assert scope(BODY + "transpose(jvp(forward_loss))/checkpoint/"
                 "rematted_computation/layer_1/attn/sparse_attention/"
                 "sparse_attn_fwd/pallas_call") == "sparse_attention"
    assert scope(BODY + "transpose(jvp(forward_loss))/transpose(jvp("
                 "layer_0))/transpose(jvp(moe))/transpose(jvp(moe_experts))/"
                 "ragged_dot") == "moe_experts"
    assert scope(fwd + "moe/moe_route/sort") == "moe_route"
    assert scope(BODY + "jvp(forward_loss)/jvp(lm_head)/dot_general") \
        == "lm_head"
    assert scope(BODY + "optimizer/mul") is None
    assert scope("") is None and scope(None) is None


def _ctx(names, events, monkeypatch, tmp_path, cell_name="tiny_fit_sync_lm"):
    """A reader's context over one chip's ``XLA Ops`` events (name,
    start, duration in ns), two executions of a 2-step program."""
    cell = harness.resolve_cell(cell_name, TINY / "BENCHMARK_lm.json", TINY)
    table = {"/device:TPU:0": {
        trace.OPS_LINE: events,
        trace.MODULES_LINE: [("jit_train_epoch(1)", 0.0, 1000.0),
                             ("jit_train_epoch(1)", 1000.0, 1000.0)]}}
    (tmp_path / "t.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(trace, "newest_xplane",
                        lambda _dir: tmp_path / "t.xplane.pb")
    monkeypatch.setattr(trace_scopes, "program_instructions",
                        lambda _bytes, _program: names)
    return {"cell": cell, "trace": table,
            "summary": {"window": (0.0, 2000.0)},
            "inputs": {"steps_per_call": 2, "examples_per_step": 2,
                       "n_chips": 1},
            "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}


def _reader(name):
    return harness.load_module("layer_metrics", name).read


def test_readers_on_a_table_worked_out_by_hand(monkeypatch, tmp_path):
    names = {
        "fusion.1": (BODY + "jvp(forward_loss)/attn/jvp(indexer)/dot", []),
        "fusion.2": (BODY + "jvp(forward_loss)/attn/select_topk/sum", []),
        "jvp_sparse_attn_fwd_.3": (
            BODY + "jvp(forward_loss)/sparse_attention/jvp(sparse_attn_fwd)/"
            "pallas_call", []),
        "transpose.4": (BODY + "jvp(forward_loss)/sparse_attention/"
                        "transpose", []),
        "fusion.5": (BODY + "transpose(jvp(forward_loss))/moe/"
                     "transpose(jvp(moe_experts))/ragged_dot", []),
        "fusion.6": (BODY + "jvp(forward_loss)/moe/moe_route/sort", []),
        "fusion.7": (BODY + "optimizer/mul", []),
        "ragged-dot-none.8": ("ragged-dot-none", []),  # the compiler's name
    }
    # four steps in the window; times in ns
    events = [("%while.9 = while(...)", 0.0, 2000.0),
              ("%fusion.1 = f32[] fusion()", 0.0, 100.0),
              ("%fusion.2 = f32[] fusion()", 100.0, 300.0),
              ("%jvp_sparse_attn_fwd_.3 = custom-call()", 400.0, 400.0),
              ("%transpose.4 = transpose()", 800.0, 40.0),
              ("%fusion.5 = fusion()", 900.0, 60.0),
              ("%fusion.6 = fusion()", 1000.0, 20.0),
              ("%fusion.7 = fusion()", 1100.0, 500.0),
              ("%ragged-dot-none.8 = custom-call()", 1700.0, 120.0)]
    ctx = _ctx(names, events, monkeypatch, tmp_path)
    ms = lambda ns: ns / 1e6 / 4
    assert _reader("attn_indexer_ms")(ctx) == pytest.approx(ms(400.0))
    assert _reader("attn_sparse_ms")(ctx) == pytest.approx(ms(440.0))
    assert _reader("moe_experts_ms")(ctx) == pytest.approx(ms(200.0))
    # one call of the forward kernel in 400 ns against its least time
    cost = ctx["cell"].flops().sparse_attention_kernel_cost(
        ctx["cell"].config, rows=2, seq=128)["sparse_attn_fwd"]
    least = max(cost[0] / 1e12, cost[1] / 1e11)
    assert _reader("attn_sparse_roofline_pct")(ctx) == pytest.approx(
        100.0 * least / 400e-9)


def test_a_program_without_the_scopes_reads_nothing(monkeypatch, tmp_path):
    """The parent commit's step: its operations carry the step's phases
    and no kernel of the model's; every reader says None, none raises."""
    names = {"fusion.1": (BODY + "jvp(forward_loss)/Bert/layer_3/dot", [])}
    events = [("%fusion.1 = fusion()", 0.0, 100.0)]
    ctx = _ctx(names, events, monkeypatch, tmp_path)
    for name in ("attn_indexer_ms", "attn_sparse_ms", "moe_experts_ms",
                 "attn_sparse_roofline_pct", "moe_load_max_over_mean"):
        assert _reader(name)(ctx) is None
    ctx = {"cell": ctx["cell"], "trace": None, "summary": None, "inputs": {},
           "peaks": ctx["peaks"]}
    for name in ("attn_indexer_ms", "attn_sparse_roofline_pct",
                 "moe_load_max_over_mean"):
        assert _reader(name)(ctx) is None


def test_the_experts_imbalance_from_the_records_counters():
    read = _reader("moe_load_max_over_mean")
    ctx = {"inputs": {"moe_rows_max": [30.0, 20.0],
                      "moe_rows_mean": [10.0, 10.0]}}
    assert read(ctx) == pytest.approx(2.5)


def test_readers_on_a_recorded_cut_of_a_chip_trace(monkeypatch, tmp_path):
    """One traced chunk of ``keye_vl2_fit_sync_s8k`` on the v5e (the
    builder's chip run, PR 27), cut to the operations of its last step
    with the names the metadata plane gave them. The numbers are that
    cut's own, worked out once and kept."""
    cut = json.loads((DATA / "keye_step_scopes.json").read_text())
    names = {k: (v[0], v[1]) for k, v in cut["names"].items()}
    cell = harness.resolve_cell("keye_vl2_fit_sync_s8k")
    table = {p: {line: [tuple(e) for e in events]
                 for line, events in lines.items()}
             for p, lines in cut["table"].items()}
    (tmp_path / "t.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(trace, "newest_xplane",
                        lambda _dir: tmp_path / "t.xplane.pb")
    monkeypatch.setattr(trace_scopes, "program_instructions",
                        lambda _bytes, _program: names)
    ctx = {"cell": cell, "trace": table,
           "summary": {"window": tuple(cut["window"])},
           "inputs": {"steps_per_call": cut["steps_per_call"],
                      "examples_per_step": 2, "n_chips": 1},
           "peaks": harness.load_peaks("TPU v5 lite")}
    got = {m: _reader(m)(ctx) for m in cut["expected"]}
    assert got == pytest.approx(cut["expected"], rel=1e-6)
    assert 0 < got["attn_sparse_roofline_pct"] <= 100
    assert got["attn_sparse_ms"] > 0 and got["attn_indexer_ms"] > 0


def test_the_cells_metric_lists_name_readers_that_load():
    bench = json.loads(harness.BENCHMARK_JSON.read_text())
    mine = [m for m in bench["per_layer"]
            if m.get("workloads") == ["keye_vl2_fit_sync_s8k"]]
    assert {m["name"] for m in mine} == {
        "attn_indexer_ms", "attn_sparse_ms", "moe_experts_ms",
        "attn_sparse_roofline_pct", "moe_load_max_over_mean"}
    cell = harness.resolve_cell("keye_vl2_fit_sync_s8k")
    result = harness.JobResult(0, 0, {}, 0, 0, [], (0, 0, 0), layer_inputs={
        "moe_rows_max": [12.0], "moe_rows_mean": [8.0]})
    assert run.layer_metrics(cell, result, None, None, "TPU v5 lite") == {
        "moe_load_max_over_mean": {"value": 1.5, "unit": "x"}}

"""The mixed-attention readers: device time under the scopes a model of
several kinds of layer adds and each kind's kernels' roofline share, on a
table worked out by hand, on a recorded cut of a chip trace, and on a
program without the scopes or the kernels (the parent commit's, or
another model's), which reads nothing and raises nothing."""

import json
from pathlib import Path

import pytest

from chipbench import harness, hlm_scopes, run, trace, trace_scopes

TINY = Path(__file__).parent / "tiny"
DATA = Path(__file__).parent / "data"
BODY = "jit(train_epoch)/shard_map/while/body/closed_call/"
READERS = ("attn_window_ms", "attn_full_ms", "attn_window_roofline_pct",
           "attn_full_roofline_pct", "mlp_shared_dense_ms")


def test_scope_of_an_op_name_under_transformations():
    scope = hlm_scopes.scope_of
    fwd = BODY + "jvp(forward_loss)/jvp(SparseMoELM)/"
    assert scope(fwd + "jvp(layer_1)/jvp(attn)/jvp(window_attention)/"
                 "jvp(window_attn_fwd)/pallas_call") == "window_attention"
    assert scope(BODY + "transpose(jvp(forward_loss))/transpose(jvp("
                 "layer_4))/transpose(jvp(attn))/transpose(jvp("
                 "causal_attention))/transpose") == "causal_attention"
    assert scope(fwd + "checkpoint/rematted_computation/jvp(layer_2)/"
                 "shared/shared_expert/dot_general") == "shared_expert"
    assert scope(fwd + "jvp(layer_0)/mlp/dense_mlp/mul") == "dense_mlp"
    assert scope(fwd + "jvp(layer_1)/moe/moe_route/sort") is None
    assert scope(fwd + "jvp(layer_1)/attn/block_diffusion_attention/x") \
        is None
    assert scope("") is None and scope(None) is None


def _ctx(names, events, monkeypatch, tmp_path):
    """A reader's context over one chip's ``XLA Ops`` events (name,
    start, duration in ns), two executions of a 2-step program."""
    cell = harness.resolve_cell("tiny_fit_sync_hlm",
                                TINY / "BENCHMARK_hlm.json", TINY)
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
    window = BODY + "jvp(forward_loss)/attn/window_attention/"
    causal = BODY + "jvp(forward_loss)/attn/causal_attention/"
    names = {
        "window_attn_fwd.1": (window + "window_attn_fwd/pallas_call", []),
        "window_attn_fwd.2": (window + "window_attn_fwd/pallas_call", []),
        "transpose.3": (window + "transpose", []),
        "causal_attn_fwd.4": (causal + "causal_attn_fwd/pallas_call", []),
        "causal_attn_bwd_dkv.5": (
            BODY + "transpose(jvp(forward_loss))/attn/transpose(jvp("
            "causal_attention))/causal_attn_bwd_dkv/pallas_call", []),
        "fusion.6": (BODY + "jvp(forward_loss)/shared/shared_expert/dot",
                     []),
        "fusion.7": (BODY + "jvp(forward_loss)/mlp/dense_mlp/dot", []),
        "fusion.8": (BODY + "jvp(forward_loss)/moe/moe_route/sort", []),
        "fusion.9": (BODY + "optimizer/mul", []),
    }
    # four steps in the window; times in ns
    events = [("%while.10 = while(...)", 0.0, 2000.0),
              ("%window_attn_fwd.1 = custom-call()", 0.0, 100.0),
              ("%window_attn_fwd.2 = custom-call()", 100.0, 140.0),
              ("%transpose.3 = transpose()", 250.0, 40.0),
              ("%causal_attn_fwd.4 = custom-call()", 300.0, 400.0),
              ("%causal_attn_bwd_dkv.5 = custom-call()", 700.0, 800.0),
              ("%fusion.6 = fusion()", 1500.0, 60.0),
              ("%fusion.7 = fusion()", 1600.0, 30.0),
              ("%fusion.8 = fusion()", 1700.0, 50.0),
              ("%fusion.9 = fusion()", 1800.0, 100.0)]
    ctx = _ctx(names, events, monkeypatch, tmp_path)
    ms = lambda ns: ns / 1e6 / 4
    assert _reader("attn_window_ms")(ctx) == pytest.approx(ms(280.0))
    assert _reader("attn_full_ms")(ctx) == pytest.approx(ms(1200.0))
    assert _reader("mlp_shared_dense_ms")(ctx) == pytest.approx(ms(90.0))
    # the model's older scopes read through lm_scopes on the same trace
    assert _reader("moe_experts_ms")(ctx) == pytest.approx(ms(50.0))
    # each kind's calls against that kind's least times, apart
    cell = ctx["cell"]
    cost = cell.flops().window_attention_kernel_cost(cell.config, rows=2,
                                                     seq=384)
    least = 2 * max(cost["window_attn_fwd"][0] / 1e12,
                    cost["window_attn_fwd"][1] / 1e11)
    assert _reader("attn_window_roofline_pct")(ctx) == pytest.approx(
        100.0 * least / 240e-9)
    cost = cell.flops().causal_attention_kernel_cost(cell.config, rows=2,
                                                     seq=384)
    least = sum(max(cost[k][0] / 1e12, cost[k][1] / 1e11)
                for k in ("causal_attn_fwd", "causal_attn_bwd_dkv"))
    assert _reader("attn_full_roofline_pct")(ctx) == pytest.approx(
        100.0 * least / 1200e-9)


def test_readers_on_a_recorded_cut_of_a_chip_trace(monkeypatch, tmp_path):
    """One traced chunk of ``laguna_xs2_fit_sync_s8k`` on the v5e (the
    builder's chip run on the committed files, PR 35), cut to the
    operations of its last step with the names the metadata plane gave
    them. The numbers are that cut's own, worked out once and kept."""
    cut = json.loads((DATA / "laguna_step_scopes.json").read_text())
    names = {k: (v[0], v[1]) for k, v in cut["names"].items()}
    cell = harness.resolve_cell("laguna_xs2_fit_sync_s8k")
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
    got = {m: _reader(m)(ctx) for m in (*READERS, "moe_experts_ms")}
    assert got == pytest.approx(cut["expected"], rel=1e-6)
    assert 25 < got["attn_window_roofline_pct"] < 40
    assert 50 < got["attn_full_roofline_pct"] < 70
    # three window layers and two full ones, one kernel of each a layer
    kernels = hlm_scopes._reduce(ctx)["kernels"]
    assert {k: calls for k, (calls, _s) in kernels.items()} == {
        **dict.fromkeys(hlm_scopes.KERNELS["window"], 3.0),
        **dict.fromkeys(hlm_scopes.KERNELS["causal"], 2.0)}


def test_the_kernels_costs_count_kept_pairs_alone():
    cell = harness.resolve_cell("laguna_xs2_fit_sync_s8k")
    flops = cell.flops()
    assert flops.kept_pairs("full_attention", 8_192, 512) == 33_558_528
    assert flops.kept_pairs("sliding_attention", 8_192, 512) == 4_063_488
    assert flops.kept_pairs("sliding_attention", 256, 512) \
        == flops.kept_pairs("full_attention", 256, 512)
    window = flops.window_attention_kernel_cost(cell.config, rows=2,
                                                seq=8_192)
    causal = flops.causal_attention_kernel_cost(cell.config, rows=2,
                                                seq=8_192)
    assert set(window) == set(hlm_scopes.KERNELS["window"])
    assert set(causal) == set(hlm_scopes.KERNELS["causal"])
    # 64 heads on the window's pairs, 48 on every causal pair
    assert window["window_attn_fwd"][0] == 2 * 4_063_488 * 64 * 2 * 2 * 128
    assert causal["causal_attn_fwd"][0] == 2 * 33_558_528 * 48 * 2 * 2 * 128
    for cost, name in ((window, "window"), (causal, "causal")):
        assert [round(cost[k][0] / cost[f"{name}_attn_fwd"][0], 6)
                for k in hlm_scopes.KERNELS[name]] == [1.0, 1.5, 2.0]
    parts = flops.forward_flops_by_part(cell.config, rows=2, seq=8_192)
    assert parts["attention"] == 3 * window["window_attn_fwd"][0] \
        + 2 * causal["causal_attn_fwd"][0]
    assert flops.train_step_flops(cell.config, 2, 8_192) == 3 * sum(
        parts.values())
    assert 38.7e12 < flops.train_step_flops(cell.config, 2, 8_192) < 38.9e12


def test_a_program_without_the_scopes_reads_nothing(monkeypatch, tmp_path):
    names = {"fusion.1": (BODY + "jvp(forward_loss)/attn/sparse_attention/"
                          "sparse_attn_fwd/pallas_call", [])}
    events = [("%fusion.1 = fusion()", 0.0, 100.0)]
    ctx = _ctx(names, events, monkeypatch, tmp_path)
    for name in READERS:
        assert _reader(name)(ctx) is None
    ctx = {"cell": ctx["cell"], "trace": None, "summary": None, "inputs": {},
           "peaks": ctx["peaks"]}
    for name in READERS:
        assert _reader(name)(ctx) is None


def test_the_cells_metric_lists_name_readers_that_load():
    bench = json.loads(harness.BENCHMARK_JSON.read_text())
    cell_name = "laguna_xs2_fit_sync_s8k"
    mine = {m["name"] for m in bench["per_layer"]
            if cell_name in m.get("workloads", ())}
    assert mine == {
        "sync_loop_outside_chunk_pct", "step_device_ms", "step_mfu_pct",
        "step_forward_ms", "step_backward_ms", "step_optimizer_ms",
        "moe_experts_ms", "moe_load_max_over_mean", *READERS}
    alone = [m for m in bench["per_layer"]
             if m.get("workloads") == [cell_name]]
    assert {m["name"] for m in alone} == set(READERS)
    assert {m["layer"] for m in alone} == {"Mixed window and full attention",
                                           "Shared expert and dense MLP"}
    cell = harness.resolve_cell(cell_name)
    result = harness.JobResult(0, 0, {}, 0, 0, [], (0, 0, 0), layer_inputs={
        "moe_rows_max": [12.0], "moe_rows_mean": [8.0]})
    assert run.layer_metrics(cell, result, None, None, "TPU v5 lite") == {
        "moe_load_max_over_mean": {"value": 1.5, "unit": "x"}}

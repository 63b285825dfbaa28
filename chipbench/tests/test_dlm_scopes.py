"""The block-diffusion readers: device time under the model's new scope
and the three kernels' roofline share, on a table worked out by hand, on
a recorded cut of a chip trace, and on a program without the scope or
the kernels (the parent commit's, or another model's), which reads
nothing and raises nothing."""

import json
from pathlib import Path

import pytest

from chipbench import dlm_scopes, harness, run, trace, trace_scopes

TINY = Path(__file__).parent / "tiny"
DATA = Path(__file__).parent / "data"
BODY = "jit(train_epoch)/shard_map/while/body/closed_call/"


def test_scope_of_an_op_name_under_transformations():
    scope = dlm_scopes.scope_of
    fwd = BODY + "jvp(forward_loss)/jvp(SparseMoELM)/"
    assert scope(fwd + "diffusion_noise/threefry2x32") == "diffusion_noise"
    assert scope(fwd + "jvp(layer_1)/jvp(attn)/jvp("
                 "block_diffusion_attention)/jvp(blockdiff_attn_fwd)/"
                 "pallas_call") == "block_diffusion_attention"
    assert scope(BODY + "transpose(jvp(forward_loss))/transpose(jvp("
                 "layer_0))/transpose(jvp(attn))/transpose(jvp("
                 "block_diffusion_attention))/transpose") \
        == "block_diffusion_attention"
    assert scope(fwd + "jvp(layer_1)/moe/moe_route/sort") is None
    assert scope(fwd + "jvp(layer_1)/attn/sparse_attention/x") is None
    assert scope("") is None and scope(None) is None


def _ctx(names, events, monkeypatch, tmp_path):
    """A reader's context over one chip's ``XLA Ops`` events (name,
    start, duration in ns), two executions of a 2-step program."""
    cell = harness.resolve_cell("tiny_fit_sync_dlm",
                                TINY / "BENCHMARK_dlm.json", TINY)
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
            "inputs": {"steps_per_call": 2, "examples_per_step": 1,
                       "n_chips": 1},
            "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}


def _reader(name):
    return harness.load_module("layer_metrics", name).read


def test_readers_on_a_table_worked_out_by_hand(monkeypatch, tmp_path):
    attn = BODY + "jvp(forward_loss)/attn/block_diffusion_attention/"
    names = {
        "fusion.1": (BODY + "jvp(forward_loss)/diffusion_noise/select", []),
        "blockdiff_attn_fwd.2": (attn + "blockdiff_attn_fwd/pallas_call", []),
        "transpose.3": (attn + "transpose", []),
        "blockdiff_attn_bwd_dkv.4": (
            BODY + "transpose(jvp(forward_loss))/attn/transpose(jvp("
            "block_diffusion_attention))/blockdiff_attn_bwd_dkv/pallas_call",
            []),
        "fusion.5": (BODY + "jvp(forward_loss)/moe/moe_route/sort", []),
        "fusion.6": (BODY + "optimizer/mul", []),
    }
    # four steps in the window; times in ns
    events = [("%while.9 = while(...)", 0.0, 2000.0),
              ("%fusion.1 = fusion()", 0.0, 20.0),
              ("%blockdiff_attn_fwd.2 = custom-call()", 100.0, 400.0),
              ("%transpose.3 = transpose()", 500.0, 40.0),
              ("%blockdiff_attn_bwd_dkv.4 = custom-call()", 600.0, 800.0),
              ("%fusion.5 = fusion()", 1500.0, 60.0),
              ("%fusion.6 = fusion()", 1600.0, 300.0)]
    ctx = _ctx(names, events, monkeypatch, tmp_path)
    ms = lambda ns: ns / 1e6 / 4
    assert _reader("attn_blockdiff_ms")(ctx) == pytest.approx(ms(1240.0))
    assert dlm_scopes.scope_ms(ctx, "diffusion_noise") == pytest.approx(
        ms(20.0))
    # the model's older scopes read through lm_scopes on the same trace
    assert _reader("moe_experts_ms")(ctx) == pytest.approx(ms(60.0))
    # one call each of two kernels against their least times
    cell = ctx["cell"]
    cost = cell.flops().blockdiff_attention_kernel_cost(
        cell.config, rows=1, seq=128)
    least = sum(max(cost[k][0] / 1e12, cost[k][1] / 1e11)
                for k in ("blockdiff_attn_fwd", "blockdiff_attn_bwd_dkv"))
    assert _reader("attn_blockdiff_roofline_pct")(ctx) == pytest.approx(
        100.0 * least / 1200e-9)


def test_readers_on_a_recorded_cut_of_a_chip_trace(monkeypatch, tmp_path):
    """One traced chunk of ``sdar_30b_fit_sync_s8k`` on the v5e (the
    builder's chip run on the final tree, PR 32), cut to the operations
    of its last step with the names the metadata plane gave them. The
    numbers are that cut's own, worked out once and kept."""
    cut = json.loads((DATA / "sdar_step_scopes.json").read_text())
    names = {k: (v[0], v[1]) for k, v in cut["names"].items()}
    cell = harness.resolve_cell("sdar_30b_fit_sync_s8k")
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
                      "examples_per_step": 1, "n_chips": 1},
           "peaks": harness.load_peaks("TPU v5 lite")}
    got = {m: _reader(m)(ctx) for m in (
        "attn_blockdiff_ms", "attn_blockdiff_roofline_pct", "moe_experts_ms")}
    want = {m: cut["expected"][m] for m in got}
    assert got == pytest.approx(want, rel=1e-6)
    assert 50 < got["attn_blockdiff_roofline_pct"] < 60
    # four layers, one kernel of each kind a layer
    kernels = dlm_scopes._reduce(ctx)["kernels"]
    assert {k: calls for k, (calls, _s) in kernels.items()} == dict.fromkeys(
        dlm_scopes.KERNELS, 4.0)


def test_the_kernels_cost_counts_allowed_pairs_alone():
    cell = harness.resolve_cell("sdar_30b_fit_sync_s8k")
    flops = cell.flops()
    assert flops.allowed_pairs(8_192, 4) == 67_141_632
    cost = flops.blockdiff_attention_kernel_cost(cell.config, rows=1,
                                                 seq=8_192)
    assert cost["blockdiff_attn_fwd"][0] == 67_141_632 * 32 * 2 * 2 * 128
    assert [round(cost[k][0] / cost["blockdiff_attn_fwd"][0], 6)
            for k in dlm_scopes.KERNELS] == [1.0, 1.5, 2.0]
    parts = flops.forward_flops_by_part(cell.config, rows=1, seq=8_192)
    assert parts["attention"] == 4 * cost["blockdiff_attn_fwd"][0]
    assert 1.09e12 < parts["attention"] / 4 < 1.11e12  # a layer, forward
    assert flops.train_step_flops(cell.config, 1, 8_192) == 3 * sum(
        parts.values())


def test_a_program_without_the_scope_reads_nothing(monkeypatch, tmp_path):
    names = {"fusion.1": (BODY + "jvp(forward_loss)/attn/sparse_attention/"
                          "sparse_attn_fwd/pallas_call", [])}
    events = [("%fusion.1 = fusion()", 0.0, 100.0)]
    ctx = _ctx(names, events, monkeypatch, tmp_path)
    for name in ("attn_blockdiff_ms", "attn_blockdiff_roofline_pct"):
        assert _reader(name)(ctx) is None
    ctx = {"cell": ctx["cell"], "trace": None, "summary": None, "inputs": {},
           "peaks": ctx["peaks"]}
    for name in ("attn_blockdiff_ms", "attn_blockdiff_roofline_pct"):
        assert _reader(name)(ctx) is None


def test_the_cells_metric_lists_name_readers_that_load():
    bench = json.loads(harness.BENCHMARK_JSON.read_text())
    cell_name = "sdar_30b_fit_sync_s8k"
    mine = {m["name"] for m in bench["per_layer"]
            if cell_name in m.get("workloads", ())}
    assert mine == {
        "sync_loop_outside_chunk_pct", "step_device_ms", "step_mfu_pct",
        "step_forward_ms", "step_backward_ms", "step_optimizer_ms",
        "moe_experts_ms", "moe_load_max_over_mean", "attn_blockdiff_ms",
        "attn_blockdiff_roofline_pct"}
    alone = [m for m in bench["per_layer"]
             if m.get("workloads") == [cell_name]]
    assert {m["layer"] for m in alone} == {"Block-diffusion attention"}
    cell = harness.resolve_cell(cell_name)
    result = harness.JobResult(0, 0, {}, 0, 0, [], (0, 0, 0), layer_inputs={
        "moe_rows_max": [12.0], "moe_rows_mean": [8.0]})
    assert run.layer_metrics(cell, result, None, None, "TPU v5 lite") == {
        "moe_load_max_over_mean": {"value": 1.5, "unit": "x"}}

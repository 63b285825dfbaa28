"""The convolution cell's readers: device time under the scopes a gated
short-convolution layer adds (an operation counts under EVERY one of
them on its path: ``sconv_in_proj`` lies inside ``attn_qkv`` and
``sconv_out_proj`` around ``attn_out``, which the older readers go on
counting) and the fused pass's kernels' roofline share, on a table
worked out by hand and on a program without the scopes or the kernels
(the parent commit's, or another model's), which reads nothing and
raises nothing."""

import json
from pathlib import Path

import pytest

from chipbench import harness, run, sconv_scopes, step_parts, trace, \
    trace_scopes

TINY = Path(__file__).parent / "tiny"
BODY = "jit(train_epoch)/shard_map/while/body/closed_call/"
READERS = ("attn_sconv_ms", "attn_sconv_roofline_pct", "attn_sconv_proj_ms")
# the step's share under no scope, with this file's scopes known
UNNAMED = "step_unnamed_sconv_pct"
CELL = "lfm2_8b_fit_sync_s4k"


def test_scopes_of_an_op_name_under_transformations():
    scopes = sconv_scopes.scopes_of
    fwd = BODY + "jvp(forward_loss)/jvp(SparseMoELM)/"
    bwd = BODY + "transpose(jvp(forward_loss))/transpose(jvp(SparseMoELM))/"
    assert scopes(fwd + "jvp(layer_0)/jvp(attn)/jvp(sconv_gate)/"
                  "jvp(sconv_fwd)/pallas_call") == {"sconv_gate"}
    assert scopes(bwd + "transpose(jvp(layer_2))/transpose(jvp(attn))/"
                  "transpose(jvp(sconv_gate))/sconv_bwd/pallas_call") \
        == {"sconv_gate"}
    assert scopes(fwd + "jvp(layer_0)/jvp(attn)/attn_qkv/sconv_in_proj/dot") \
        == {"sconv_in_proj"}
    assert scopes(fwd + "layer_2/attn/sconv_out_proj/attn_out/dot") \
        == {"sconv_out_proj"}
    assert scopes(fwd + "layer_1/attn/attn_out/dot") == frozenset()
    assert scopes("") == frozenset() and scopes(None) == frozenset()
    # the older readers tile the same paths by their innermost scope
    assert step_parts.scope_of(
        fwd + "layer_0/attn/attn_qkv/sconv_in_proj/dot") == "attn_qkv"
    assert step_parts.scope_of(
        fwd + "layer_2/attn/sconv_out_proj/attn_out/dot") == "attn_out"
    assert step_parts.scope_of(fwd + "layer_0/attn/sconv_gate/x") is None


def _ctx(names, events, monkeypatch, tmp_path, **inputs):
    """A reader's context over one chip's ``XLA Ops`` events (name,
    start, duration in ns), two executions of a 2-step program."""
    cell = harness.resolve_cell("tiny_fit_sync_sconv",
                                TINY / "BENCHMARK_sconv.json", TINY)
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
            "summary": {"window": (0.0, 2000.0), "busy_s": 2e-6},
            "inputs": {"steps_per_call": 2, "examples_per_step": 2,
                       "n_chips": 1, **inputs},
            "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}


def _reader(name):
    return harness.load_module("layer_metrics", name, TINY).read


def _table():
    fwd = BODY + "jvp(forward_loss)/"
    bwd = BODY + "transpose(jvp(forward_loss))/"
    names = {
        "sconv_fwd.1": (fwd + "layer_0/attn/sconv_gate/sconv_fwd/pallas_call",
                        []),
        "sconv_bwd.2": (bwd + "layer_0/attn/transpose(jvp(sconv_gate))/"
                        "sconv_bwd/pallas_call", []),
        "fusion.3": (bwd + "layer_0/attn/transpose(jvp(sconv_gate))/"
                     "reduce_sum", []),
        "fusion.4": (fwd + "layer_0/attn/attn_qkv/sconv_in_proj/dot", []),
        "fusion.5": (fwd + "layer_2/attn/sconv_out_proj/attn_out/dot", []),
        "fusion.6": (fwd + "layer_1/attn/attn_out/dot", []),
        "causal_attn_fwd.10": (fwd + "layer_1/attn/causal_attention/"
                               "causal_attn_fwd/pallas_call", []),
        "fusion.11": (fwd + "layer_0/mlp/dense_mlp/dot", []),
        "fusion.12": (BODY + "optimizer/mul", []),
        "fusion.14": (fwd + "layer_0/add", []),
    }
    # four steps in the window; times in ns
    events = [("%while.13 = while(...)", 0.0, 2000.0),
              ("%sconv_fwd.1 = custom-call()", 0.0, 100.0),
              ("%sconv_bwd.2 = custom-call()", 100.0, 300.0),
              ("%fusion.3 = fusion()", 400.0, 40.0),
              ("%fusion.4 = fusion()", 500.0, 60.0),
              ("%fusion.5 = fusion()", 600.0, 20.0),
              ("%fusion.6 = fusion()", 700.0, 80.0),
              ("%causal_attn_fwd.10 = custom-call()", 1100.0, 70.0),
              ("%fusion.11 = fusion()", 1200.0, 25.0),
              ("%fusion.12 = fusion()", 1300.0, 100.0),
              ("%fusion.14 = fusion()", 1400.0, 15.0)]
    return names, events


def test_readers_on_a_table_worked_out_by_hand(monkeypatch, tmp_path):
    ctx = _ctx(*_table(), monkeypatch, tmp_path)
    ms = lambda ns: ns / 1e6 / 4
    assert _reader("attn_sconv_ms")(ctx) == pytest.approx(ms(440.0))
    assert _reader("attn_sconv_proj_ms")(ctx) == pytest.approx(ms(80.0))
    # the older scopes on the same trace, by their innermost: all three
    # products under attn_projections_ms, the attention layer's kernel
    # and the dense MLP under theirs
    assert _reader("attn_projections_ms")(ctx) == pytest.approx(ms(160.0))
    assert _reader("attn_full_ms")(ctx) == pytest.approx(ms(70.0))
    assert _reader("mlp_shared_dense_ms")(ctx) == pytest.approx(ms(25.0))
    # the tile: the pass's scope out of `unnamed`, the sum the step's
    parts = sconv_scopes.tile(ctx)
    assert parts["sconv_gate"] == pytest.approx(ms(440.0))
    assert parts[step_parts.UNNAMED] == pytest.approx(ms(15.0))
    assert sum(parts.values()) == pytest.approx(ms(810.0))
    assert sum(step_parts.tile(ctx).values()) == pytest.approx(ms(810.0))
    # of the 2,000 ns the chip was busy: the older readers' share counts
    # the pass's kernels, this file's does not
    assert _reader("step_unnamed_pct")(ctx) == pytest.approx(
        100.0 * 455.0 / 2000.0)
    assert _reader(UNNAMED)(ctx) == pytest.approx(100.0 * 15.0 / 2000.0)


def test_the_roofline_by_hand_and_its_calls_held_to_the_counter(
        monkeypatch, tmp_path):
    """One call of each kernel in four steps on 2 rows of 128 tokens of
    256 channels: forward 8 operations and 14 bytes a channel a token,
    backward 22 and 26, each call the larger of operations over 1e12 and
    bytes over 1e11 a second (the bytes, by far)."""
    ctx = _ctx(*_table(), monkeypatch, tmp_path)
    channels = 2 * 128 * 256
    cost = ctx["cell"].flops().short_conv_kernel_cost(
        ctx["cell"].config, rows=2, seq=128)
    assert cost == {"sconv_fwd": (8.0 * channels, 14.0 * channels),
                    "sconv_bwd": (22.0 * channels, 26.0 * channels)}
    least = (14 + 26) * channels / 1e11
    assert sconv_scopes.least_seconds(
        cost, {"sconv_fwd": 1, "sconv_bwd": 1},
        ctx["peaks"]) == pytest.approx(least)
    assert _reader("attn_sconv_roofline_pct")(ctx) == pytest.approx(
        100.0 * least / 400e-9)
    # the counter: a backward call for each rows x T the steps counted
    # (one call in four steps: a quarter of 2 x 128 tokens a step)
    held = _ctx(*_table(), monkeypatch, tmp_path,
                sconv_tokens=[2 * 128 / 4] * 4)
    assert _reader("attn_sconv_roofline_pct")(held) == pytest.approx(
        100.0 * least / 400e-9)
    off = _ctx(*_table(), monkeypatch, tmp_path, sconv_tokens=[2 * 128.0] * 4)
    assert _reader("attn_sconv_roofline_pct")(off) is None
    # the attention layer's kernels at the 64 dims a pair really has
    causal = ctx["cell"].flops().causal_attention_kernel_cost(
        ctx["cell"].config, rows=2, seq=128)
    assert causal["causal_attn_fwd"][0] == 2 * (128 * 129 // 2) * 4 * 2 * 2 * 64
    assert _reader("attn_full_roofline_pct")(ctx) == pytest.approx(
        100.0 * max(causal["causal_attn_fwd"][0] / 1e12,
                    causal["causal_attn_fwd"][1] / 1e11) / 70e-9)


def test_the_costs_count_the_cells_step_as_the_issue_did():
    cell = harness.resolve_cell(CELL)
    flops, cfg = cell.flops(), cell.config
    parts = flops.forward_flops_by_part(cfg, rows=4, seq=4_096)
    tokens = 16_384
    assert parts["conv_projections"] == 4 * tokens * 2 * 2_048 * 4 * 2_048
    assert parts["full_projections"] == tokens * 2 * 2_048 * 64 * (64 + 16)
    assert parts["attention"] == 4 * (4_096 * 4_097 // 2) * 32 * 4 * 64
    assert parts["dense_mlp"] == tokens * 3 * 2 * 2_048 * 7_168
    assert parts["experts"] == 4 * tokens * 4 / 4 * 3 * 2 * 2_048 * 1_792
    assert parts["head"] == tokens * 2 * 2_048 * 16_384
    total = sum(parts.values())
    assert 6.80e12 < total < 6.82e12
    share = lambda key: round(100 * parts[key] / total)
    assert [share(k) for k in ("conv_projections", "dense_mlp", "experts",
                               "head")] == [32, 21, 21, 16]
    assert flops.train_step_flops(cfg, 4, 4_096) == 3 * total
    cost = flops.short_conv_kernel_cost(cfg, rows=4, seq=4_096)
    channels = tokens * 2_048
    assert cost["sconv_fwd"] == (8.0 * channels, 14.0 * channels)
    assert cost["sconv_bwd"] == (22.0 * channels, 26.0 * channels)
    # bound by memory on a v5e: 0.57 ms a layer forward, 1.07 backward
    assert cost["sconv_fwd"][1] / 819e9 == pytest.approx(0.574e-3, rel=0.01)
    assert cost["sconv_fwd"][0] / 197e12 < 0.01 * cost["sconv_fwd"][1] / 819e9
    causal = flops.causal_attention_kernel_cost(cfg, rows=4, seq=4_096)
    assert causal["causal_attn_fwd"][0] == parts["attention"]
    assert causal["causal_attn_bwd_dkv"][0] == 2 * parts["attention"]


def test_a_program_without_the_scopes_reads_nothing(monkeypatch, tmp_path):
    names = {"fusion.1": (BODY + "jvp(forward_loss)/attn/latent_attention/"
                          "latent_attn_fwd/pallas_call", [])}
    events = [("%fusion.1 = fusion()", 0.0, 100.0)]
    ctx = _ctx(names, events, monkeypatch, tmp_path)
    for name in (*READERS, UNNAMED):
        assert _reader(name)(ctx) is None
    assert sconv_scopes.tile(ctx) == step_parts.tile(ctx)
    ctx = {"cell": ctx["cell"], "trace": None, "summary": None, "inputs": {},
           "peaks": ctx["peaks"]}
    for name in (*READERS, UNNAMED):
        assert _reader(name)(ctx) is None
    # another configuration's flops file has no cost function of these
    laguna = harness.resolve_cell("laguna_xs2_fit_sync_s8k")
    ctx = {**_ctx(names, events, monkeypatch, tmp_path), "cell": laguna}
    ctx["_sconv_scopes"] = {"ops": None, "steps": 4, "kernels": {
        "sconv_fwd": (1.0, 1e-3), "sconv_bwd": (1.0, 1e-3)}}
    assert sconv_scopes.kernel_roofline_pct(ctx) is None


def test_the_cells_metric_lists_name_readers_that_load():
    """By membership, wherever in its list an entry lies: the cell's own
    readers, and each older metric whose scope its step carries with the
    same meaning; not the share of the step under no scope the older
    readers know, which here would count the pass's kernels: the cell
    reports that share by a reader of its own."""
    bench = json.loads(harness.BENCHMARK_JSON.read_text())
    mine = {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", ())}
    assert {*READERS, UNNAMED} <= mine
    assert {"step_device_ms", "step_mfu_pct", "moe_experts_ms",
            "moe_load_max_over_mean", "mlp_shared_dense_ms",
            "attn_projections_ms", "attn_qk_rope_ms", "attn_full_ms",
            "attn_full_roofline_pct", "lm_head_loss_ms", "embed_norms_ms",
            "step_stats_ms", "step_unscoped_ms", "sync_chunk_enqueue_pct",
            "sync_loop_outside_chunk_pct", "step_forward_ms",
            "step_backward_ms", "step_optimizer_ms"} <= mine
    assert not {"step_unnamed_pct", "step_unnamed_gdn_pct", "attn_gdn_ms",
                "gdn_conv_gate_ms"} & mine
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["moves"] == "train_rate_sync"
        assert m["layer"] == "Gated short convolution"
        assert harness.load_module("layer_metrics", name).read
    assert {k: by_name[UNNAMED][k] for k in ("layer", "moves", "workloads")} \
        == {"layer": "Step program", "moves": "train_rate_sync",
            "workloads": [CELL]}
    assert [w["name"] for w in bench["workloads"] if w["chips"] == 4] == [
        "bert_base_fit_dp4"]
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (entry["chips"], entry["config"], entry["traffic"]) == (
        1, "lfm2-8b-a1b-ep4", "sft_s4k_mb4_conv")
    assert CELL in next(m for m in bench["end_to_end"]
                        if m["name"] == "train_rate_sync")["workloads"]
    cell = harness.resolve_cell(CELL)
    result = harness.JobResult(0, 0, {}, 0, 0, [], (0, 0, 0), layer_inputs={
        "moe_rows_max": [12.0], "moe_rows_mean": [8.0]})
    assert run.layer_metrics(cell, result, None, None, "TPU v5 lite") == {
        "moe_load_max_over_mean": {"value": 1.5, "unit": "x"}}

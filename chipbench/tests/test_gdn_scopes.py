"""The linear-attention readers: device time under the scopes a Gated
DeltaNet layer adds (an operation counts under EVERY one of them on its
path: ``gdn_in_proj`` lies inside ``attn_qkv`` and ``gdn_out_proj``
around ``attn_out``, which the older readers go on counting) and the
rule's kernels' roofline share, on a table worked out by hand and on a
program without the scopes or the kernels (the parent commit's, or
another model's), which reads nothing and raises nothing."""

import json
from pathlib import Path

import pytest

from chipbench import gdn_scopes, harness, run, step_parts, trace, trace_scopes

TINY = Path(__file__).parent / "tiny"
BODY = "jit(train_epoch)/shard_map/while/body/closed_call/"
READERS = ("attn_gdn_ms", "attn_gdn_roofline_pct", "gdn_conv_gate_ms",
           "attn_gdn_proj_ms")
# the step's share under no scope, with this file's scopes known
UNNAMED = "step_unnamed_gdn_pct"
CELL = "qwen3_next_fit_sync_s16k"


def test_scopes_of_an_op_name_under_transformations():
    scopes = gdn_scopes.scopes_of
    fwd = BODY + "jvp(forward_loss)/jvp(SparseMoELM)/"
    bwd = BODY + "transpose(jvp(forward_loss))/transpose(jvp(SparseMoELM))/"
    assert scopes(fwd + "jvp(layer_1)/jvp(attn)/jvp(gated_delta)/"
                  "jvp(gdn_fwd)/pallas_call") == {"gated_delta"}
    assert scopes(bwd + "transpose(jvp(layer_0))/transpose(jvp(attn))/"
                  "transpose(jvp(gated_delta))/gdn_bwd/pallas_call") \
        == {"gated_delta"}
    assert scopes(fwd + "jvp(layer_1)/jvp(attn)/attn_qkv/gdn_in_proj/dot") \
        == {"gdn_in_proj"}
    assert scopes(fwd + "layer_2/attn/gdn_out_proj/attn_out/dot") \
        == {"gdn_out_proj"}
    assert scopes(fwd + "layer_3/attn/attn_out/dot") == frozenset()
    assert scopes(fwd + "layer_0/attn/gdn_conv/mul") == {"gdn_conv"}
    assert scopes("") == frozenset() and scopes(None) == frozenset()
    # the older readers tile the same paths by their innermost scope
    assert step_parts.scope_of(
        fwd + "layer_1/attn/attn_qkv/gdn_in_proj/dot") == "attn_qkv"
    assert step_parts.scope_of(
        fwd + "layer_2/attn/gdn_out_proj/attn_out/dot") == "attn_out"
    assert step_parts.scope_of(fwd + "layer_0/attn/gated_delta/x") is None


def _ctx(names, events, monkeypatch, tmp_path):
    """A reader's context over one chip's ``XLA Ops`` events (name,
    start, duration in ns), two executions of a 2-step program."""
    cell = harness.resolve_cell("tiny_fit_sync_gdn",
                                TINY / "BENCHMARK_gdn.json", TINY)
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
            "inputs": {"steps_per_call": 2, "examples_per_step": 1,
                       "n_chips": 1},
            "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}


def _reader(name):
    return harness.load_module("layer_metrics", name).read


def test_readers_on_a_table_worked_out_by_hand(monkeypatch, tmp_path):
    fwd = BODY + "jvp(forward_loss)/"
    bwd = BODY + "transpose(jvp(forward_loss))/"
    names = {
        "gdn_fwd.1": (fwd + "layer_1/attn/gated_delta/gdn_fwd/pallas_call",
                      []),
        "gdn_bwd.2": (bwd + "layer_0/attn/transpose(jvp(gated_delta))/"
                      "gdn_bwd/pallas_call", []),
        "fusion.3": (fwd + "layer_1/attn/gated_delta/cumsum", []),
        "fusion.4": (fwd + "layer_1/attn/attn_qkv/gdn_in_proj/dot", []),
        "fusion.5": (fwd + "layer_2/attn/gdn_out_proj/attn_out/dot", []),
        "fusion.6": (fwd + "layer_3/attn/attn_out/dot", []),
        "fusion.7": (fwd + "layer_0/attn/gdn_conv/mul", []),
        "fusion.8": (fwd + "layer_0/attn/gdn_gates/rsqrt", []),
        "fusion.9": (bwd + "layer_0/attn/transpose(jvp(gdn_out_norm))/mul",
                     []),
        "causal_attn_fwd.10": (fwd + "layer_3/attn/causal_attention/"
                               "causal_attn_fwd/pallas_call", []),
        "fusion.11": (fwd + "layer_3/shared/shared_expert/dot", []),
        "fusion.12": (BODY + "optimizer/mul", []),
        "fusion.14": (fwd + "layer_0/add", []),
    }
    # four steps in the window; times in ns
    events = [("%while.13 = while(...)", 0.0, 2000.0),
              ("%gdn_fwd.1 = custom-call()", 0.0, 100.0),
              ("%gdn_bwd.2 = custom-call()", 100.0, 300.0),
              ("%fusion.3 = fusion()", 400.0, 40.0),
              ("%fusion.4 = fusion()", 500.0, 60.0),
              ("%fusion.5 = fusion()", 600.0, 20.0),
              ("%fusion.6 = fusion()", 700.0, 80.0),
              ("%fusion.7 = fusion()", 800.0, 30.0),
              ("%fusion.8 = fusion()", 900.0, 50.0),
              ("%fusion.9 = fusion()", 1000.0, 10.0),
              ("%causal_attn_fwd.10 = custom-call()", 1100.0, 70.0),
              ("%fusion.11 = fusion()", 1200.0, 25.0),
              ("%fusion.12 = fusion()", 1300.0, 100.0),
              ("%fusion.14 = fusion()", 1400.0, 15.0)]
    ctx = _ctx(names, events, monkeypatch, tmp_path)
    ms = lambda ns: ns / 1e6 / 4
    assert _reader("attn_gdn_ms")(ctx) == pytest.approx(ms(440.0))
    assert _reader("gdn_conv_gate_ms")(ctx) == pytest.approx(ms(90.0))
    assert _reader("attn_gdn_proj_ms")(ctx) == pytest.approx(ms(80.0))
    # the older scopes on the same trace, by their innermost: all three
    # products under attn_projections_ms, the full layer's kernel and
    # the shared expert under theirs
    assert _reader("attn_projections_ms")(ctx) == pytest.approx(ms(160.0))
    assert _reader("attn_full_ms")(ctx) == pytest.approx(ms(70.0))
    assert _reader("mlp_shared_dense_ms")(ctx) == pytest.approx(ms(25.0))
    # the tile: the four scopes out of `unnamed`, the sum the step's
    parts = gdn_scopes.tile(ctx)
    assert parts["gated_delta"] == pytest.approx(ms(440.0))
    assert parts["gdn_conv"] == pytest.approx(ms(30.0))
    assert parts[step_parts.UNNAMED] == pytest.approx(ms(15.0))
    assert sum(parts.values()) == pytest.approx(ms(900.0))
    assert sum(step_parts.tile(ctx).values()) == pytest.approx(ms(900.0))
    # of the 2,000 ns the chip was busy: the older readers' share counts
    # the rule's kernels and the element-wise passes, this file's does not
    assert _reader("step_unnamed_pct")(ctx) == pytest.approx(
        100.0 * 545.0 / 2000.0)
    assert _reader(UNNAMED)(ctx) == pytest.approx(100.0 * 15.0 / 2000.0)
    # one call of each kernel against its least time
    cell = ctx["cell"]
    cost = cell.flops().gated_delta_kernel_cost(cell.config, rows=1, seq=128)
    least = sum(max(cost[k][0] / 1e12, cost[k][1] / 1e11)
                for k in ("gdn_fwd", "gdn_bwd"))
    assert _reader("attn_gdn_roofline_pct")(ctx) == pytest.approx(
        100.0 * least / 400e-9)
    cost = cell.flops().causal_attention_kernel_cost(cell.config, rows=1,
                                                     seq=128)
    assert _reader("attn_full_roofline_pct")(ctx) == pytest.approx(
        100.0 * max(cost["causal_attn_fwd"][0] / 1e12,
                    cost["causal_attn_fwd"][1] / 1e11) / 70e-9)


def test_the_costs_count_the_rule_at_chunk_64_and_kept_pairs_at_256():
    cell = harness.resolve_cell(CELL)
    flops, cfg = cell.flops(), cell.config
    # a chunk a head: five products of 2 x 64 x 64 x 128, three of 2 x 64
    # x 128 x 128 and the substitution
    chunk = 5 * 2 * 64 * 64 * 128 + 3 * 2 * 64 * 128 * 128 + 2 * 64 ** 3 / 3
    assert flops.rule_chunk_flops(cfg) == chunk
    cost = flops.gated_delta_kernel_cost(cfg, rows=1, seq=16_384)
    assert set(cost) == set(gdn_scopes.KERNELS["gated_delta"])
    assert cost["gdn_fwd"][0] == 256 * 32 * chunk
    assert cost["gdn_bwd"][0] == 2 * cost["gdn_fwd"][0]
    # q and k a key head, v and o a value head, bf16; two floats a token a
    # value head; a state a value head a block of 512 tokens
    moved = 16_384 * ((2 * 2_048 + 4_096) * 2 + 32 * 2 * 4 + 4_096 * 2) \
        + 32 * 32 * 128 * 128 * 4
    assert cost["gdn_fwd"][1] == moved
    # neither bound is far from the other: 0.49 ms of products, 0.46 ms
    # of bytes a layer forward on a v5e
    assert 0.8 < (cost["gdn_fwd"][0] / 197e12) / (cost["gdn_fwd"][1]
                                                   / 819e9) < 1.25
    causal = flops.causal_attention_kernel_cost(cfg, rows=1, seq=16_384)
    pairs = 16_384 * 16_385 // 2
    assert causal["causal_attn_fwd"][0] == pairs * 16 * 2 * 2 * 256
    assert causal["causal_attn_bwd_dkv"][0] == pairs * 16 * 4 * 2 * 256
    parts = flops.forward_flops_by_part(cfg, rows=1, seq=16_384)
    assert parts["gated_delta_rule"] == 3 * cost["gdn_fwd"][0]
    assert parts["attention"] == causal["causal_attn_fwd"][0]
    assert parts["linear_projections"] == 3 * 16_384 * 2 * 2_048 * (
        12_288 + 64 + 4_096)
    assert parts["full_projections"] == 16_384 * 2 * 2_048 * 256 * (
        2 * 16 + 2 * 2 + 16)
    assert parts["experts"] == 4 * 16_384 * 10 / 32 * 3 * 2 * 2_048 * 512
    assert parts["head"] == 16_384 * 2 * 2_048 * 18_992
    # the linear layers' products and rule are over half of the mixers'
    mixers = sum(parts[k] for k in ("linear_projections", "gated_delta_rule",
                                    "full_projections", "attention"))
    assert 0.5 < (parts["linear_projections"]
                  + parts["gated_delta_rule"]) / mixers < 0.6
    assert flops.train_step_flops(cfg, 1, 16_384) == 3 * sum(parts.values())


def test_a_program_without_the_scopes_reads_nothing(monkeypatch, tmp_path):
    names = {"fusion.1": (BODY + "jvp(forward_loss)/attn/latent_attention/"
                          "latent_attn_fwd/pallas_call", [])}
    events = [("%fusion.1 = fusion()", 0.0, 100.0)]
    ctx = _ctx(names, events, monkeypatch, tmp_path)
    for name in (*READERS, UNNAMED):
        assert _reader(name)(ctx) is None
    assert gdn_scopes.tile(ctx) == step_parts.tile(ctx)
    ctx = {"cell": ctx["cell"], "trace": None, "summary": None, "inputs": {},
           "peaks": ctx["peaks"]}
    for name in (*READERS, UNNAMED):
        assert _reader(name)(ctx) is None
    # another configuration's flops file has no cost function of these
    laguna = harness.resolve_cell("laguna_xs2_fit_sync_s8k")
    ctx = {**_ctx(names, events, monkeypatch, tmp_path), "cell": laguna}
    ctx["_gdn_scopes"] = {"ops": None, "kernels": {"gdn_fwd": (1.0, 1e-3)}}
    assert gdn_scopes.kernel_roofline_pct(ctx, "gated_delta") is None


def test_the_cells_metric_lists_name_readers_that_load():
    """By membership: the cell's own readers, and each older metric whose
    scope its step carries with the same meaning; not the share of the
    step under no scope the older readers know, which here would count
    the rule's kernels: the cell reports that share by a reader of its
    own."""
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
    assert "step_unnamed_pct" not in mine
    for m in bench["per_layer"]:
        if m["name"] in READERS:
            assert m["workloads"] == [CELL] and m["moves"] == "train_rate_sync"
            assert m["layer"] == "Gated delta rule linear attention"
            assert harness.load_module("layer_metrics", m["name"]).read
    assert {k: v for k, v in bench["per_layer"][-1].items() if k in (
        "name", "layer", "moves", "workloads")} == {
            "name": UNNAMED, "layer": "Step program",
            "moves": "train_rate_sync", "workloads": [CELL]}
    assert len(bench["workloads"]) == 8
    assert [w["name"] for w in bench["workloads"] if w["chips"] == 4] == [
        "bert_base_fit_dp4"]
    entry = bench["workloads"][-1]
    assert (entry["name"], entry["chips"], entry["config"],
            entry["traffic"]) == (CELL, 1, "qwen3-next-80b-a3b-ep32",
                                  "sft_s16k_mb1_gdn")
    cell = harness.resolve_cell(CELL)
    result = harness.JobResult(0, 0, {}, 0, 0, [], (0, 0, 0), layer_inputs={
        "moe_rows_max": [12.0], "moe_rows_mean": [8.0]})
    assert run.layer_metrics(cell, result, None, None, "TPU v5 lite") == {
        "moe_load_max_over_mean": {"value": 1.5, "unit": "x"}}

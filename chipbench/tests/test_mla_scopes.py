"""The latent-attention readers: device time under the scopes latent
attention and a multi-token prediction module add (an operation counts
under EVERY one of them on its path: ``mtp`` lies outside its layer's
scopes) and the new kernels' roofline shares, on a table worked out by
hand and on a program without the scopes or the kernels (the parent
commit's, or another model's), which reads nothing and raises nothing."""

import json
from pathlib import Path

import pytest

from chipbench import harness, mla_scopes, run, step_parts, trace, trace_scopes

TINY = Path(__file__).parent / "tiny"
BODY = "jit(train_epoch)/shard_map/while/body/closed_call/"
READERS = ("attn_latent_ms", "attn_latent_roofline_pct",
           "latent_rope_roofline_pct", "attn_latent_proj_ms", "mtp_module_ms")
CELL = "joyai_flash_fit_sync_s8k"


def test_scopes_of_an_op_name_under_transformations():
    scopes = mla_scopes.scopes_of
    fwd = BODY + "jvp(forward_loss)/jvp(SparseMoELM)/"
    assert scopes(fwd + "jvp(layer_1)/jvp(attn)/jvp(latent_attention)/"
                  "jvp(latent_attn_fwd)/pallas_call") == {"latent_attention"}
    assert scopes(fwd + "jvp(layer_1)/jvp(attn)/attn_qkv/latent_q/dot") \
        == {"latent_q"}
    assert scopes(BODY + "transpose(jvp(forward_loss))/transpose(jvp(mtp))/"
                  "transpose(jvp(layer))/transpose(jvp(attn))/transpose(jvp("
                  "attn_qk_rope))/transpose(jvp(latent_rope))/pallas_call") \
        == {"mtp", "latent_rope"}
    assert scopes(BODY + "transpose(jvp(forward_loss))/loss/mtp/"
                  "fused_ce_bwd/pallas_call") == {"mtp"}
    assert scopes(fwd + "jvp(layer_1)/moe/moe_route/sort") == frozenset()
    assert scopes("") == frozenset() and scopes(None) == frozenset()
    # the older readers tile the same paths by their innermost scope
    assert step_parts.scope_of(fwd + "jvp(mtp)/jvp(layer)/jvp(attn)/"
                               "attn_qkv/latent_kv/dot") == "attn_qkv"
    assert step_parts.scope_of(fwd + "jvp(mtp)/lm_head/dot") == "lm_head"
    assert step_parts.scope_of(fwd + "jvp(layer_1)/jvp(attn)/jvp("
                               "latent_attention)/x") is None


def _ctx(names, events, monkeypatch, tmp_path):
    """A reader's context over one chip's ``XLA Ops`` events (name,
    start, duration in ns), two executions of a 2-step program."""
    cell = harness.resolve_cell("tiny_fit_sync_mtp",
                                TINY / "BENCHMARK_mtp.json", TINY)
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
                       "n_chips": 1},
            "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}


def _reader(name):
    return harness.load_module("layer_metrics", name).read


def test_readers_on_a_table_worked_out_by_hand(monkeypatch, tmp_path):
    fwd = BODY + "jvp(forward_loss)/"
    names = {
        "latent_attn_fwd.1": (fwd + "layer_1/attn/latent_attention/"
                              "latent_attn_fwd/pallas_call", []),
        "latent_attn_bwd_dkv.2": (
            BODY + "transpose(jvp(forward_loss))/mtp/layer/attn/transpose("
            "jvp(latent_attention))/latent_attn_bwd_dkv/pallas_call", []),
        "transpose.3": (fwd + "layer_1/attn/latent_attention/transpose", []),
        "fusion.4": (fwd + "layer_1/attn/attn_qkv/latent_q/dot", []),
        "fusion.5": (fwd + "mtp/layer/attn/attn_qkv/latent_kv/dot", []),
        "latent_rope_fwd.6": (fwd + "layer_1/attn/attn_qk_rope/latent_rope/"
                              "latent_rope_fwd/pallas_call", []),
        "fusion.7": (fwd + "layer_1/attn/attn_out/dot", []),
        "fusion.8": (fwd + "mtp/lm_head/dot", []),
        "fusion.9": (BODY + "transpose(jvp(forward_loss))/loss/mtp/mul", []),
        "fusion.10": (fwd + "layer_1/moe/moe_route/sort", []),
        "fusion.11": (BODY + "optimizer/mul", []),
    }
    # four steps in the window; times in ns
    events = [("%while.12 = while(...)", 0.0, 2000.0),
              ("%latent_attn_fwd.1 = custom-call()", 0.0, 100.0),
              ("%latent_attn_bwd_dkv.2 = custom-call()", 100.0, 300.0),
              ("%transpose.3 = transpose()", 400.0, 40.0),
              ("%fusion.4 = fusion()", 500.0, 60.0),
              ("%fusion.5 = fusion()", 600.0, 20.0),
              ("%latent_rope_fwd.6 = custom-call()", 700.0, 80.0),
              ("%fusion.7 = fusion()", 800.0, 30.0),
              ("%fusion.8 = fusion()", 900.0, 50.0),
              ("%fusion.9 = fusion()", 1000.0, 10.0),
              ("%fusion.10 = fusion()", 1100.0, 70.0),
              ("%fusion.11 = fusion()", 1200.0, 100.0)]
    ctx = _ctx(names, events, monkeypatch, tmp_path)
    ms = lambda ns: ns / 1e6 / 4
    assert _reader("attn_latent_ms")(ctx) == pytest.approx(ms(440.0))
    assert _reader("attn_latent_proj_ms")(ctx) == pytest.approx(ms(160.0))
    # the module's kernel, its latent product, its head and its loss
    assert _reader("mtp_module_ms")(ctx) == pytest.approx(ms(380.0))
    # the older scopes on the same trace, by their innermost: both latent
    # products and the output's under attn_projections_ms, the layout
    # op under attn_qk_rope_ms, the module's head and loss with the rest
    assert _reader("attn_projections_ms")(ctx) == pytest.approx(ms(110.0))
    assert _reader("attn_qk_rope_ms")(ctx) == pytest.approx(ms(80.0))
    assert _reader("lm_head_loss_ms")(ctx) == pytest.approx(ms(60.0))
    assert _reader("moe_experts_ms")(ctx) == pytest.approx(ms(70.0))
    # the tile: the attention's scope out of `unnamed`, the sum the step's
    parts = mla_scopes.tile(ctx)
    assert parts["latent_attention"] == pytest.approx(ms(440.0))
    assert parts[step_parts.UNNAMED] == pytest.approx(0.0, abs=1e-12)
    assert sum(parts.values()) == pytest.approx(ms(860.0))
    assert sum(step_parts.tile(ctx).values()) == pytest.approx(ms(860.0))
    # each family's calls against that family's least times
    cell = ctx["cell"]
    cost = cell.flops().latent_attention_kernel_cost(cell.config, rows=2,
                                                     seq=384)
    least = sum(max(cost[k][0] / 1e12, cost[k][1] / 1e11)
                for k in ("latent_attn_fwd", "latent_attn_bwd_dkv"))
    assert _reader("attn_latent_roofline_pct")(ctx) == pytest.approx(
        100.0 * least / 400e-9)
    cost = cell.flops().latent_rope_kernel_cost(cell.config, rows=2, seq=384)
    assert _reader("latent_rope_roofline_pct")(ctx) == pytest.approx(
        100.0 * max(cost["latent_rope_fwd"][0] / 1e12,
                    cost["latent_rope_fwd"][1] / 1e11) / 80e-9)


def test_the_kernels_costs_count_kept_pairs_at_the_true_widths():
    cell = harness.resolve_cell(CELL)
    flops, cfg = cell.flops(), cell.config
    assert flops.kept_pairs(8_192) == 33_558_528
    cost = flops.latent_attention_kernel_cost(cfg, rows=1, seq=8_192)
    assert set(cost) == set(mla_scopes.KERNELS["latent_attention"])
    pairs = 8_191 * 8_192 // 2 * 32  # every call counted as the module's
    assert cost["latent_attn_fwd"][0] == pairs * 2 * (192 + 128)
    assert cost["latent_attn_bwd_dq"][0] == pairs * 2 * (192 + 128 + 192)
    assert cost["latent_attn_bwd_dkv"][0] == pairs * 2 * (2 * 192 + 2 * 128)
    # q and k at 192, v and o at 128, bf16; a float a row a head
    assert cost["latent_attn_fwd"][1] == 8_192 * 32 * (
        2 * 192 * 2 + 2 * 128 * 2 + 4)
    rope = flops.latent_rope_kernel_cost(cfg, rows=1, seq=8_192)
    assert set(rope) == set(mla_scopes.KERNELS["latent_rope"])
    assert rope["latent_rope_fwd"][1] == 8_192 * (
        (32 * (192 + 256) + 64) * 4 + 64 * 4 + 32 * (192 + 192 + 128) * 2)
    # operations over bytes: the layout op is the memory's, 1e-4 of the
    # matrix unit's share of its time
    assert rope["latent_rope_fwd"][0] / 197e12 < 1e-3 * (
        rope["latent_rope_fwd"][1] / 819e9)
    parts = flops.forward_flops_by_part(cfg, rows=1, seq=8_192)
    assert parts["attention"] == 5 * 33_558_528 * 32 * 2 * 320
    assert parts["mtp_attention"] == cost["latent_attn_fwd"][0]
    assert parts["mtp_head"] == 8_191 * 2 * 2_048 * 16_160
    assert flops.train_step_flops(cfg, 1, 8_192) == 3 * sum(parts.values())
    assert 27.7e12 < flops.train_step_flops(cfg, 1, 8_192) < 27.9e12


def test_a_program_without_the_scopes_reads_nothing(monkeypatch, tmp_path):
    names = {"fusion.1": (BODY + "jvp(forward_loss)/attn/causal_attention/"
                          "causal_attn_fwd/pallas_call", [])}
    events = [("%fusion.1 = fusion()", 0.0, 100.0)]
    ctx = _ctx(names, events, monkeypatch, tmp_path)
    for name in READERS:
        assert _reader(name)(ctx) is None
    assert mla_scopes.tile(ctx) == step_parts.tile(ctx)
    ctx = {"cell": ctx["cell"], "trace": None, "summary": None, "inputs": {},
           "peaks": ctx["peaks"]}
    for name in READERS:
        assert _reader(name)(ctx) is None
    # another configuration's flops file has no cost function of these
    laguna = harness.resolve_cell("laguna_xs2_fit_sync_s8k")
    ctx = {**_ctx(names, events, monkeypatch, tmp_path), "cell": laguna}
    ctx["_mla_scopes"] = {"ops": None, "kernels": {
        "latent_attn_fwd": (1.0, 1e-3)}}
    assert mla_scopes.kernel_roofline_pct(ctx, "latent_attention") is None


def test_the_cells_metric_lists_name_readers_that_load():
    """By membership: the cell's own readers, and each older metric whose
    scope its step carries with the same meaning; not the share of the
    step under no scope the older readers know, which here would count
    the attention kernels."""
    bench = json.loads(harness.BENCHMARK_JSON.read_text())
    mine = {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", ())}
    assert set(READERS) <= mine
    assert {"step_device_ms", "step_mfu_pct", "moe_experts_ms",
            "moe_load_max_over_mean", "mlp_shared_dense_ms",
            "attn_projections_ms", "attn_qk_rope_ms", "lm_head_loss_ms",
            "embed_norms_ms", "step_stats_ms", "step_unscoped_ms",
            "sync_chunk_enqueue_pct"} <= mine
    assert "step_unnamed_pct" not in mine
    for m in bench["per_layer"]:
        if m["name"] in READERS:
            assert m["workloads"] == [CELL] and m["moves"] == "train_rate_sync"
            assert m["layer"] in ("Latent attention",
                                  "Multi-token prediction module")
            assert harness.load_module("layer_metrics", m["name"]).read
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (entry["chips"], entry["traffic"]) == (1, "sft_s8k_mb1_mtp")
    cell = harness.resolve_cell(CELL)
    result = harness.JobResult(0, 0, {}, 0, 0, [], (0, 0, 0), layer_inputs={
        "moe_rows_max": [12.0], "moe_rows_mean": [8.0]})
    assert run.layer_metrics(cell, result, None, None, "TPU v5 lite") == {
        "moe_load_max_over_mean": {"value": 1.5, "unit": "x"}}

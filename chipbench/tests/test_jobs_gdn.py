"""Job kind ``fit_sync_gdn`` end to end at a tiny size through its Python
API: ``correct`` on sound runs, the rule's chunk count read from the
window's records and held to the configuration file's (a program on
another chunk is not ``correct``), every planted fault outside a limit (readings on seed
5: ``state_not_carried`` moves the linear layers' gradients by 0.126
against a limit of 1.2e-3), the fp8 control outside one and the bf16
control inside all, and false where the program itself drops its
shared expert's gate or runs the full layers without their output gate."""

import dataclasses
from pathlib import Path

import numpy as np
import pytest

from chipbench import harness

TINY = Path(__file__).parent / "tiny"
CELL = "qwen3_next_fit_sync_s16k"


def tiny():
    return harness.resolve_cell("tiny_fit_sync_gdn",
                                TINY / "BENCHMARK_gdn.json", TINY)


def failed_checks(res):
    return {c.name for c in res.checks if not c.ok}


def outside(numbers, traffic):
    limits = traffic["limits"]
    return {k for k in limits if k in numbers and numbers[k] > limits[k]}


@pytest.mark.parametrize("seed", (2 ** 31 + 11, 3))
def test_job_runs_and_is_correct(seed):
    cell = tiny()
    res = cell.job.run(cell, seed, 0.3, None)
    assert [c.line() for c in res.checks if not c.ok] == []
    assert {c.name for c in res.checks} == set(cell.traffic["limits"]) | {
        "moe_pairs_dropped", "mask_pairs_off", "gdn_chunks_off",
        "steps_with_wrong_row_count", "nonfinite_losses"}
    assert res.attempted > 0 and res.failed == 0
    assert res.window_s >= 0.3 and res.setup_s > 0
    assert {cell.traffic["reports"][k] for k in res.end_to_end} | {
        "setup_s"} == {m["name"] for m in cell.end_to_end}
    li = res.layer_inputs
    assert len(li["moe_rows_max"]) == len(li["moe_rows_mean"]) == res.attempted
    # three linear layers x 1 row x 2 value heads x 2 chunks of 64
    assert any("chunks of the gated delta rule a step: 12 a chip" in n
               for n in res.notes)


def test_the_tiny_and_the_real_configuration_describe_the_same_model():
    """Every leaf of the program's tree belongs to a group of the traffic
    file, at both sizes; the real file keeps the published widths and
    states the cut and its count."""
    import jax
    import jax.numpy as jnp

    from chipbench.jobs import fit_sync_groups

    for cell in (tiny(), harness.resolve_cell(CELL)):
        module, cfg, t = cell.build_module(), cell.config, cell.traffic
        assert [k.attention for k in module.config.layers] == [
            "gated_delta", "gated_delta", "gated_delta", "full"]
        assert [k.mlp for k in module.config.layers] == ["experts"] * 4
        assert (module.config.attn_gate_width,
                module.config.shared_expert_gate) == ("element", True)
        assert module.config.experts_per_token \
            == cfg["num_experts_per_tok"] == 10
        assert module.config.n_routed_experts \
            == cfg["num_routed_experts"] == 512
        assert len(module.config.experts_held) == cfg["num_experts"] == 16
        assert t["loss"] == "cross_entropy" and t["frozen"] == [".moe.router"]
        assert cell.job.chunks_expected(cell) == 3 * t[
            "mini_batch"] * cfg["linear_num_value_heads"] * t["seq_len"] // 64
        shapes = jax.eval_shape(
            lambda: module.init(jax.random.key(0),
                                jnp.zeros((1, 128), jnp.int32)))["params"]
        ref = jax.eval_shape(
            lambda k: cell.reference.init(k, cfg), jax.random.key(0))["params"]
        assert jax.tree.map(lambda a: a.shape, shapes) \
            == jax.tree.map(lambda a: a.shape, ref)
        by_group = {}
        for path, _ in jax.tree_util.tree_flatten_with_path(shapes)[0]:
            key = fit_sync_groups.dotted(path)
            by_group.setdefault(fit_sync_groups.group_of(
                key, t["grad_groups"]), []).append(key)
        assert set(by_group) == set(t["grad_groups"])
        assert {"layer_0.attn.w_qkvz", "layer_1.attn.conv",
                "layer_2.attn.A_log", "layer_0.attn_norm"} <= set(
            by_group["attn_gdn"])
        assert set(by_group["attn_full"]) == {
            f"layer_3.{leaf}" for leaf in (
                "attn.wq", "attn.wq_gate", "attn.wk", "attn.wv", "attn.wo",
                "attn.q_norm", "attn.k_norm", "attn_norm")}
        assert "layer_3.shared.gate" in by_group["shared"]
        assert by_group["embedding"] == ["embed"]
    real = harness.resolve_cell(CELL)
    cfg, n = real.config, sum(a.size for a in jax.tree.leaves(shapes))
    assert n == 424_340_544 and "424,340,544" in cfg["deployment"]
    # every key of the catalog's row at its published value, but the cut
    assert {k: cfg[k] for k in (
        "hidden_size", "head_dim", "num_attention_heads",
        "num_key_value_heads", "linear_num_key_heads",
        "linear_num_value_heads", "linear_key_head_dim",
        "linear_value_head_dim", "linear_conv_kernel_dim",
        "moe_intermediate_size", "shared_expert_intermediate_size",
        "num_experts_per_tok", "intermediate_size",
        "full_attention_interval", "partial_rotary_factor", "rope_theta",
        "max_position_embeddings", "decoder_sparse_step")} == {
        "hidden_size": 2_048, "head_dim": 256, "num_attention_heads": 16,
        "num_key_value_heads": 2, "linear_num_key_heads": 16,
        "linear_num_value_heads": 32, "linear_key_head_dim": 128,
        "linear_value_head_dim": 128, "linear_conv_kernel_dim": 4,
        "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
        "num_experts_per_tok": 10, "intermediate_size": 5_120,
        "full_attention_interval": 4, "partial_rotary_factor": 0.25,
        "rope_theta": 10_000_000, "max_position_embeddings": 262_144,
        "decoder_sparse_step": 1}
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 512,
                                "vocab_size": 151_936}
    assert {"column_order", "norm_gains", "attention_bias", "decay_init",
            "conv_init_std", "router_frozen", "remat",
            "embedding_init_std"} <= set(cfg["assumed"])
    assert {"absent_experts", "decoding", "mtp"} <= set(cfg["left_out"])
    t = real.traffic
    assert {k: t[k] for k in (
        "job", "seq_len", "mini_batch", "resident_rows", "steps_per_call",
        "active_vocab", "map_share", "language_seed", "loss", "optimizer",
        "optimizer_params", "frozen", "program_seed", "check_steps",
        "reference_block_rows", "trace_chunks", "reports")} == {
        "job": "fit_sync_gdn", "seq_len": 16_384, "mini_batch": 1,
        "resident_rows": 1_024, "steps_per_call": 4, "active_vocab": 512,
        "map_share": 0.9, "language_seed": 0, "loss": "cross_entropy",
        "optimizer": "adam", "optimizer_params": {"lr": 1e-5},
        "frozen": [".moe.router"], "program_seed": 0, "check_steps": 3,
        "reference_block_rows": 1, "trace_chunks": 2,
        "reports": {"rate": "train_rate_sync"}}


@pytest.mark.parametrize("kwargs,caught_by", [
    ({"shared_expert_gate": False}, "grad_norm_rel_shared"),
    ({"attn_gate": False}, "grad_norm_rel_attn_full"),
])
def test_a_program_that_leaves_a_gate_out_is_not_correct(kwargs, caught_by):
    """The program itself (not the reference) built without one of its
    gates: it takes the benchmark's weights (the leaf is in the tree it
    is handed, and unread) and trains; the group's gradient says so."""
    cell = tiny()
    blind = dataclasses.replace(cell, config={**cell.config, "constructor_kwargs": {
        **cell.config["constructor_kwargs"], **kwargs}})
    res = blind.job.run(blind, 5, 0.3, None)
    assert caught_by in failed_checks(res)
    assert "gdn_chunks_off" not in failed_checks(res)


def test_a_program_on_another_chunk_counts_other_chunks(monkeypatch):
    """The count is held to the configuration file's own arithmetic at a
    chunk of 64, not to what the program says of itself: the rule run in
    chunks of 32 gives the same numbers and twice the chunks."""
    import functools

    from sparktorch_tpu.ops import gated_delta_rule as op

    monkeypatch.setattr(op, "gated_delta_rule", functools.partial(
        op.gated_delta_rule, chunk=32))
    monkeypatch.setattr(op, "chunks_run", functools.partial(
        op.chunks_run, chunk=32))
    cell = tiny()
    res = cell.job.run(cell, 5, 0.3, None)
    assert failed_checks(res) == {"gdn_chunks_off"}


@pytest.mark.parametrize("fault,caught_by", [
    ("lr_x1.5", "loss_rel_next"),
    ("state_not_carried", "grad_norm_rel_attn_gdn"),
    ("no_decay", "grad_norm_rel_attn_gdn"),
    ("no_beta", "grad_norm_rel_attn_gdn"),
    ("no_conv", "grad_norm_rel_attn_gdn"),
    ("conv_not_causal", "grad_norm_rel_attn_gdn"),
    ("no_qk_l2norm", "grad_norm_rel_attn_gdn"),
    ("no_out_gate_norm", "grad_norm_rel_attn_gdn"),
    ("no_attn_gate", "grad_norm_rel_attn_full"),
    ("rope_on_whole_head", "grad_norm_rel_attn_full"),
    ("no_shared_gate", "grad_norm_rel_shared"),
    ("softmax_top8", "grad_norm_rel_experts"),
    ("no_renorm", "grad_norm_rel_experts"),
    ("shifted_share", "grad_norm_rel_router"),
])
def test_a_planted_fault_in_the_reference_fails_a_limit(fault, caught_by):
    cell = tiny()
    assert set(cell.job.FAULTS) == {
        "lr_x1.5", "state_not_carried", "no_decay", "no_beta", "no_conv",
        "conv_not_causal", "no_qk_l2norm", "no_out_gate_norm",
        "no_attn_gate", "rope_on_whole_head", "no_shared_gate",
        "softmax_top8", "no_renorm", "shifted_share"}
    numbers = cell.job.control(cell, 5, kinds=(fault,))[fault]
    assert caught_by in outside(numbers, cell.traffic), numbers
    assert numbers["mask_pairs_off"] == 0 and numbers["gdn_chunks_off"] == 0


@pytest.mark.parametrize("seed", (5, 6))
def test_the_fp8_control_fails_a_limit_and_bf16_does_not(seed):
    cell = tiny()
    numbers = cell.job.control(cell, seed, kinds=("bf16", "fp8"))
    assert outside(numbers["fp8"], cell.traffic), numbers
    assert not outside(numbers["bf16"], cell.traffic), numbers


def test_rows_come_from_the_slice_and_labels_are_the_next_token():
    cell = harness.resolve_cell(CELL)
    small = {**cell.traffic, "resident_rows": 4, "seq_len": 512}
    ids, labels = cell.job.make_rows(np.random.default_rng(1), small,
                                     cell.config)
    assert ids.shape == labels.shape == (4, 512)
    assert np.array_equal(ids[:, 1:], labels[:, :-1])
    assert ids.max() < cell.config["vocab_size"] == 18_992
    assert len(np.unique(ids)) <= cell.traffic["active_vocab"] == 512
    assert cell.traffic["mini_batch"] * cell.traffic["seq_len"] == 16_384

"""Job kind ``fit_sync_sconv`` end to end at a tiny size through its
Python API: ``correct`` on sound runs, the fused pass's token count read
from the window's records and held to the configuration file's, every
planted fault outside a limit (readings on seed 5: ``conv_reach_4`` moves
the convolution layers' gradients by 0.117 against a limit of 1.5e-3,
``untied_head`` the tied leaf's by 0.159 against 8e-4), the fp8 control
outside one and the bf16 control inside all, and false where the program
itself is built with an untied head or without the expert bias."""

import dataclasses
from pathlib import Path

import numpy as np
import pytest

from chipbench import harness

TINY = Path(__file__).parent / "tiny"
CELL = "lfm2_8b_fit_sync_s4k"


def tiny():
    return harness.resolve_cell("tiny_fit_sync_sconv",
                                TINY / "BENCHMARK_sconv.json", TINY)


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
        "moe_pairs_dropped", "mask_pairs_off", "sconv_tokens_off",
        "steps_with_wrong_row_count", "nonfinite_losses"}
    assert res.attempted > 0 and res.failed == 0
    assert res.window_s >= 0.3 and res.setup_s > 0
    assert {cell.traffic["reports"][k] for k in res.end_to_end} | {
        "setup_s"} == {m["name"] for m in cell.end_to_end}
    li = res.layer_inputs
    assert len(li["moe_rows_max"]) == len(li["moe_rows_mean"]) == res.attempted
    # four convolution layers x 2 rows x 128 tokens, every step
    assert li["sconv_tokens"] == [4 * 2 * 128.0] * res.attempted
    assert any("fused convolution pass a step: 1024 a chip" in n
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
            "short_conv", "full", "short_conv", "short_conv", "short_conv"]
        assert [k.mlp for k in module.config.layers] == ["dense"] + [
            "experts"] * 4
        assert cfg["layer_types"] == ["conv", "full_attention"] + ["conv"] * 3
        assert (module.config.tie_word_embeddings, module.config.scoring,
                module.config.selection_bias, module.config.routed_norm_eps,
                module.config.head_dim) == (True, "sigmoid", True, 1e-6, 64)
        assert module.config.experts_per_token \
            == cfg["num_experts_per_tok"] == 4
        assert module.config.n_routed_experts \
            == cfg["num_routed_experts"] == 32
        assert len(module.config.experts_held) == cfg["num_experts"] == 8
        assert t["loss"] == "cross_entropy" and t["frozen"] == [
            ".moe.router", ".moe.selection_bias"]
        assert cell.job.tokens_expected(cell) == 4 * t["mini_batch"] * t[
            "seq_len"]
        shapes = jax.eval_shape(
            lambda: module.init(jax.random.key(0),
                                jnp.zeros((1, 128), jnp.int32)))["params"]
        ref = jax.eval_shape(
            lambda k: cell.reference.init(k, cfg), jax.random.key(0))["params"]
        assert jax.tree.map(lambda a: a.shape, shapes) \
            == jax.tree.map(lambda a: a.shape, ref)
        groups = {**t["zero_grad_groups"], **t["grad_groups"]}
        by_group = {}
        for path, _ in jax.tree_util.tree_flatten_with_path(shapes)[0]:
            key = fit_sync_groups.dotted(path)
            by_group.setdefault(fit_sync_groups.group_of(key, groups),
                                []).append(key)
        assert set(by_group) == set(groups)
        assert set(by_group["attn_sconv"]) == {
            f"layer_{i}.{leaf}" for i in (0, 2, 3, 4) for leaf in (
                "attn.w_in", "attn.conv", "attn.wo", "attn_norm")}
        assert set(by_group["attn_full"]) == {
            f"layer_1.{leaf}" for leaf in (
                "attn.wq", "attn.wk", "attn.wv", "attn.wo", "attn.q_norm",
                "attn.k_norm", "attn_norm")}
        assert set(by_group["dense"]) == {
            "layer_0.mlp.w_gate", "layer_0.mlp.w_up", "layer_0.mlp.w_down",
            "layer_0.mlp_norm"}
        # the tied leaf and the norm before it: ONE group
        assert by_group["embedding_head"] == ["embed", "final_norm"]
        assert len(by_group["selection_bias"]) == 4
    real = harness.resolve_cell(CELL)
    cfg, n = real.config, sum(a.size for a in jax.tree.leaves(shapes))
    assert n == 507_820_288 and "507,820,288" in cfg["deployment"]
    # every key of the catalog's row at its published value, but the cut
    assert {k: cfg[k] for k in (
        "hidden_size", "intermediate_size", "moe_intermediate_size",
        "num_attention_heads", "num_key_value_heads", "conv_L_cache",
        "conv_bias", "num_experts_per_tok", "norm_eps", "norm_topk_prob",
        "rope_theta", "routed_scaling_factor", "use_expert_bias",
        "max_position_embeddings", "model_type")} == {
        "hidden_size": 2_048, "intermediate_size": 7_168,
        "moe_intermediate_size": 1_792, "num_attention_heads": 32,
        "num_key_value_heads": 8, "conv_L_cache": 3, "conv_bias": False,
        "num_experts_per_tok": 4, "norm_eps": 1e-5, "norm_topk_prob": True,
        "rope_theta": 1_000_000, "routed_scaling_factor": 1,
        "use_expert_bias": True, "max_position_embeddings": 128_000,
        "model_type": "lfm2_moe"}
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types",
                              "num_dense_layers", "num_experts", "vocab_size"]
    assert {k: v for k, v in cfg["published"].items()
            if k != "layer_types"} == {
        "num_hidden_layers": 24, "num_dense_layers": 2, "num_experts": 32,
        "vocab_size": 65_536}
    assert [i for i, kind in enumerate(cfg["published"]["layer_types"])
            if kind == "full_attention"] == [2, 6, 10, 14, 18, 21]
    assert cfg["published"]["layer_types"][1:6] == cfg["layer_types"]
    assert {"tie_word_embeddings", "conv_init_std", "selection_bias",
            "embedding_init_std", "router_frozen", "remat", "full_attention",
            "router"} <= set(cfg["assumed"])
    assert "8,339,930,560" in cfg["assumed"]["tie_word_embeddings"]
    assert {"absent_experts", "router_balance", "decoding"} <= set(
        cfg["left_out"])
    t = real.traffic
    assert {k: t[k] for k in (
        "job", "seq_len", "mini_batch", "resident_rows", "steps_per_call",
        "active_vocab", "map_share", "language_seed", "loss", "optimizer",
        "optimizer_params", "program_seed", "check_steps",
        "reference_block_rows", "trace_chunks", "reports")} == {
        "job": "fit_sync_sconv", "seq_len": 4_096, "mini_batch": 4,
        "resident_rows": 4_096, "steps_per_call": 4, "active_vocab": 512,
        "map_share": 0.9, "language_seed": 0, "loss": "cross_entropy",
        "optimizer": "adam", "optimizer_params": {"lr": 1e-5},
        "program_seed": 0, "check_steps": 3, "reference_block_rows": 1,
        "trace_chunks": 2, "reports": {"rate": "train_rate_sync"}}


@pytest.mark.parametrize("kwargs,caught_by", [
    ({"tie_word_embeddings": False}, "a_tree_without_its_head"),
    ({"selection_bias": False}, "grad_norm_rel_experts"),
    ({"routed_norm_eps": 0.1}, "grad_norm_rel_experts"),
])
def test_a_program_built_otherwise_is_not_correct(kwargs, caught_by):
    """The program itself (not the reference) built without one of its
    mechanisms takes the benchmark's weights and trains, and a group's
    gradient says so; built untied it finds no ``head`` among them."""
    cell = tiny()
    blind = dataclasses.replace(cell, config={
        **cell.config, "constructor_kwargs": {
            **cell.config["constructor_kwargs"], **kwargs}})
    if caught_by == "a_tree_without_its_head":
        with pytest.raises(Exception, match="head"):
            blind.job.run(blind, 5, 0.3, None)
        return
    res = blind.job.run(blind, 5, 0.3, None)
    assert caught_by in failed_checks(res)
    assert "sconv_tokens_off" not in failed_checks(res)


def test_a_step_that_ran_the_pass_on_other_tokens_is_not_correct():
    """The count is held to the configuration and traffic files' own
    arithmetic, not to what the program says of itself: a window whose
    steps counted three layers' tokens for four reads off."""
    cell = tiny()
    step = dict(moe_rows=1.0, moe_rows_max=1.0, moe_rows_mean=1.0,
                moe_pairs_dropped=0.0, examples=2)
    ok = lambda tokens: {c.name: c.ok for c in cell.job._counters(
        [{**step, "sconv_tokens": tokens}], cell)[0]}["sconv_tokens_off"]
    assert ok(4 * 2 * 128.0) and not ok(3 * 2 * 128.0)


@pytest.mark.parametrize("fault,caught_by", [
    ("lr_x1.5", "loss_rel_next"),
    ("half_batch", "grad_norm_rel_first"),
    ("no_conv", "grad_norm_rel_attn_sconv"),
    ("conv_not_causal", "grad_norm_rel_attn_sconv"),
    ("conv_reach_4", "grad_norm_rel_attn_sconv"),
    ("conv_silu", "grad_norm_rel_attn_sconv"),
    ("no_in_gate", "grad_norm_rel_attn_sconv"),
    ("no_out_gate", "grad_norm_rel_attn_sconv"),
    ("scale_128", "grad_norm_rel_attn_full"),
    ("rope_on_half_head", "grad_norm_rel_attn_full"),
    ("no_qk_norm", "grad_norm_rel_attn_full"),
    ("untied_head", "grad_norm_rel_embedding_head"),
    ("no_selection_bias", "grad_norm_rel_experts"),
    ("bias_in_gates", "grad_norm_rel_router"),
    ("softmax_scores", "grad_norm_rel_router"),
    ("no_renorm", "grad_norm_rel_experts"),
    ("shifted_share", "grad_norm_rel_router"),
])
def test_a_planted_fault_in_the_reference_fails_a_limit(fault, caught_by):
    cell = tiny()
    assert set(cell.job.FAULTS) == {
        "lr_x1.5", "half_batch", "no_conv", "conv_not_causal",
        "conv_reach_4", "conv_silu", "no_in_gate", "no_out_gate",
        "scale_128", "rope_on_half_head", "no_qk_norm", "untied_head",
        "no_selection_bias", "bias_in_gates", "softmax_scores", "no_renorm",
        "shifted_share"}
    numbers = cell.job.control(cell, 5, kinds=(fault,))[fault]
    assert caught_by in outside(numbers, cell.traffic), numbers
    assert numbers["mask_pairs_off"] == 0 and numbers["sconv_tokens_off"] == 0


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
    assert ids.max() < cell.config["vocab_size"] == 16_384
    assert len(np.unique(ids)) <= cell.traffic["active_vocab"] == 512
    assert cell.traffic["mini_batch"] * cell.traffic["seq_len"] == 16_384

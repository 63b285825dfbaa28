"""Job kind ``fit_sync_mtp`` end to end at a tiny size through its Python
API: ``correct`` on sound runs, the module's own counters read from the
window's records, every planted fault that this size can see outside a limit
(three of the scores' faults move nothing at hidden 64, where every
query attends nearly alike: the chip's readings are PERF.md section
2's), the fp8 control outside one and the bf16 control inside all, and
false where the program's own bias is left out underneath."""

from pathlib import Path

import numpy as np
import pytest

from chipbench import harness

TINY = Path(__file__).parent / "tiny"
CELL = "joyai_flash_fit_sync_s8k"
# faults of the scores that no number of three steps at this size sees
# (attention 1.6e-4 - 3.6e-4 for a sound 5.1e-4)
UNSEEN_HERE = ("scale_128", "rope_on_whole_head", "rope_by_halves")


def tiny():
    return harness.resolve_cell("tiny_fit_sync_mtp",
                                TINY / "BENCHMARK_mtp.json", TINY)


def failed_checks(res):
    return {c.name for c in res.checks if not c.ok}


def outside(numbers, traffic):
    limits = {**traffic["limits"], **traffic["mtp_limits"]}
    return {k for k in limits if k in numbers and numbers[k] > limits[k]}


@pytest.mark.parametrize("seed", (2 ** 31 + 11, 3))
def test_job_runs_and_is_correct(seed):
    cell = tiny()
    res = cell.job.run(cell, seed, 0.3, None)
    assert [c.line() for c in res.checks if not c.ok] == []
    assert {c.name for c in res.checks} == set(cell.traffic["limits"]) | set(
        cell.traffic["mtp_limits"]) | {
        "moe_pairs_dropped", "mask_pairs_off", "steps_with_wrong_row_count",
        "nonfinite_losses"}
    assert res.attempted > 0 and res.failed == 0
    assert res.window_s >= 0.3 and res.setup_s > 0
    assert {cell.traffic["reports"][k] for k in res.end_to_end} | {
        "setup_s"} == {m["name"] for m in cell.end_to_end}
    li = res.layer_inputs
    assert len(li["moe_rows_max"]) == len(li["moe_rows_mean"]) == res.attempted
    assert any("the module's own loss in the window" in n for n in res.notes)


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
            "latent"] * cfg["num_hidden_layers"]
        assert [k.mlp for k in module.config.layers] == ["dense"] + [
            "experts"] * (cfg["num_hidden_layers"] - 1)
        assert module.config.mtp_depth == cfg["num_nextn_predict_layers"] == 1
        assert module.config.mtp_weight == cfg["mtp_loss_weight"] == 0.3
        assert module.config.selection_bias
        assert t["loss"] == "cross_entropy_multi_token"
        assert t["frozen"] == [".moe.router", ".moe.selection_bias"]
        shapes = jax.eval_shape(
            lambda: module.init(jax.random.key(0),
                                jnp.zeros((1, 256), jnp.int32)))["params"]
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
        assert set(by_group["mtp"]) == {"mtp.proj", "mtp.embed_norm",
                                        "mtp.hidden_norm", "mtp.final_norm"}
        assert "mtp.layer.attn.w_uq" in by_group["attn_latent"]
        assert "layer_0.attn.kv_norm" in by_group["attn_latent"]
        assert "mtp.layer.moe.selection_bias" in by_group["selection_bias"]
        assert "mtp.layer.shared.w_up" in by_group["shared_dense"]
        assert by_group["embedding"] == ["embed"]
    real = harness.resolve_cell(CELL)
    cfg, n = real.config, sum(a.size for a in jax.tree.leaves(shapes))
    assert n == 680_441_088 and "680,441,088" in cfg["deployment"]
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["num_routed_experts"]) == (
        2_048, 32, 1_536, 512, 128, 64, 128, 7_168, 768, 8, 256)
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 40,
                                "n_routed_experts": 256,
                                "vocab_size": 129_280}
    assert (real.traffic["mini_batch"], real.traffic["seq_len"],
            real.traffic["resident_rows"], real.traffic["steps_per_call"],
            real.traffic["check_steps"]) == (1, 8_192, 2_048, 4, 3)


def test_a_program_that_ignores_its_selection_bias_is_not_correct():
    """The program itself (not the reference) built without the bias: it
    takes the benchmark's weights (the leaf is in the tree it is handed,
    and unread), trains, and chooses other experts: the experts' and the
    router's gradients say so."""
    import dataclasses

    cell = tiny()
    kwargs = {**cell.config["constructor_kwargs"], "selection_bias": False}
    blind = dataclasses.replace(cell, config={
        **cell.config, "constructor_kwargs": kwargs})
    assert not blind.build_module().config.selection_bias
    res = blind.job.run(blind, 5, 0.3, None)
    assert {"grad_norm_rel_experts", "grad_norm_rel_router"} <= failed_checks(
        res)
    assert "mtp_tokens_off" not in failed_checks(res)


@pytest.mark.parametrize("fault,caught_by", [
    ("lr_x1.5", "loss_rel_next"),
    ("half_batch", "grad_norm_rel_first"),
    ("no_mtp_loss", "loss_rel_first"),
    ("no_mtp_loss", "grad_norm_rel_mtp"),
    ("mtp_unshifted", "grad_norm_rel_head"),
    ("mtp_unshifted", "grad_norm_rel_mtp"),
    ("mtp_own_head", "grad_norm_rel_head"),
    ("k_rope_normed", "grad_norm_rel_attn_latent"),
    ("no_latent_norm", "grad_norm_rel_attn_latent"),
    ("no_selection_bias", "grad_norm_rel_experts"),
    ("bias_in_gates", "grad_norm_rel_router"),
    ("no_shared_expert", "grad_norm_rel_shared_dense"),
    ("no_routed_scale", "grad_norm_rel_experts"),
    ("softmax_scores", "grad_norm_rel_router"),
    ("shifted_share", "grad_norm_rel_router"),
    ("no_renorm", "grad_norm_rel_router"),
])
def test_a_planted_fault_in_the_reference_fails_a_limit(fault, caught_by):
    cell = tiny()
    assert set(cell.job.FAULTS) == {
        "lr_x1.5", "half_batch", "no_mtp_loss", "mtp_unshifted",
        "mtp_own_head", "scale_128", "rope_on_whole_head", "rope_by_halves",
        "k_rope_normed", "no_latent_norm", "no_selection_bias",
        "bias_in_gates", "no_shared_expert", "no_routed_scale",
        "softmax_scores", "shifted_share", "no_renorm"}
    numbers = cell.job.control(cell, 5, kinds=(fault,))[fault]
    assert caught_by in outside(numbers, cell.traffic), numbers
    assert numbers["mask_pairs_off"] == 0
    assert numbers["grad_norm_selection_bias"] == 0.0


@pytest.mark.parametrize("fault", UNSEEN_HERE)
def test_a_fault_of_the_scores_moves_nothing_at_this_size(fault):
    """Hidden 64: the scores' spread is a tenth of the published
    width's and every query attends nearly alike, so a wrong scale or
    rotation moves the attention's gradients by less than a sound run's
    rounding. The reference's logits do move (``tests/
    test_latent_attention_lm.py``); what the chip's limits see of these
    three is PERF.md section 2."""
    cell = tiny()
    numbers = cell.job.control(cell, 5, kinds=(fault,))[fault]
    assert not outside(numbers, cell.traffic), numbers
    assert numbers["grad_norm_rel_attn_latent"] > 1e-4


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
    assert ids.max() < cell.config["vocab_size"] == 16_160
    assert len(np.unique(ids)) <= cell.traffic["active_vocab"] == 512
    assert cell.traffic["mini_batch"] * cell.traffic["seq_len"] == 8_192

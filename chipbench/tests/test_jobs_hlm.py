"""Job kind ``fit_sync_hlm`` end to end at a tiny size through its Python
API: ``correct`` on sound runs, every planted fault outside a limit, the
fp8 control outside one and the bf16 control inside all, each kind of
layer's rule held to the reference's pair by pair, and false where the
program's own window or gate is broken underneath."""

from pathlib import Path

import numpy as np
import pytest

from chipbench import harness

TINY = Path(__file__).parent / "tiny"


def tiny():
    return harness.resolve_cell("tiny_fit_sync_hlm",
                                TINY / "BENCHMARK_hlm.json", TINY)


def failed_checks(res):
    return {c.name for c in res.checks if not c.ok}


def outside(numbers, limits):
    """The limits a control's numbers break; its masks' pairs are held
    to 0, as the job's own check holds the program's."""
    return {k for k in limits if k in numbers and numbers[k] > limits[k]} | (
        {"mask_pairs_off"} if numbers["mask_pairs_off"] else set())


@pytest.mark.parametrize("seed", (2 ** 31 + 11, 3))
def test_job_runs_and_is_correct(seed):
    cell = tiny()
    res = cell.job.run(cell, seed, 0.3, None)
    assert [c.line() for c in res.checks if not c.ok] == []
    assert {c.name for c in res.checks} == set(cell.traffic["limits"]) | {
        "moe_pairs_dropped", "mask_pairs_off", "steps_with_wrong_row_count",
        "nonfinite_losses"}
    assert res.attempted > 0 and res.failed == 0
    assert res.window_s >= 0.3 and res.setup_s > 0
    assert {cell.traffic["reports"][k] for k in res.end_to_end} | {
        "setup_s"} == {m["name"] for m in cell.end_to_end}
    li = res.layer_inputs
    assert len(li["moe_rows_max"]) == len(li["moe_rows_mean"]) == res.attempted
    assert any("'full_attention': 0, 'sliding_attention': 0" in n
               for n in res.notes)


def test_the_tiny_and_the_real_configuration_describe_the_same_five_layers():
    """Every leaf of the program's tree belongs to a group of the traffic
    file, at both sizes, and the configuration file's lists are the
    module's layers."""
    import jax
    import jax.numpy as jnp

    from chipbench.jobs import fit_sync_groups

    for cell in (tiny(), harness.resolve_cell("laguna_xs2_fit_sync_s8k")):
        module = cell.build_module()
        kinds = [{"full": "full_attention", "window": "sliding_attention"}[
            k.attention] for k in module.config.layers]
        assert kinds == cell.config["layer_types"]
        assert [k.n_heads for k in module.config.layers] \
            == cell.config["num_attention_heads_per_layer"]
        assert [{"dense": "dense", "experts": "sparse"}[k.mlp]
                for k in module.config.layers] \
            == cell.config["mlp_layer_types"]
        assert module.config.window == cell.config["sliding_window"]
        shapes = jax.eval_shape(
            lambda: module.init(jax.random.key(0),
                                jnp.zeros((1, 256), jnp.int32)))["params"]
        ref = jax.eval_shape(
            lambda k: cell.reference.init(k, cell.config),
            jax.random.key(0))["params"]
        assert jax.tree.map(lambda a: a.shape, shapes) \
            == jax.tree.map(lambda a: a.shape, ref)
        groups = cell.traffic["grad_groups"]
        by_group = {}
        for path, _ in jax.tree_util.tree_flatten_with_path(shapes)[0]:
            key = fit_sync_groups.dotted(path)
            by_group.setdefault(fit_sync_groups.group_of(key, groups),
                                []).append(key)
        assert set(by_group) == set(groups)
        assert all(k.startswith(("layer_1.", "layer_2.", "layer_3."))
                   for k in by_group["attn_window"])
        assert {k.split(".")[0] for k in by_group["attn_full"]} \
            == {"layer_0", "layer_4"}
        assert "layer_0.mlp.w_up" in by_group["shared_dense"]
        assert "layer_3.shared.w_down" in by_group["shared_dense"]
        assert "layer_2.attn.wg" in by_group["attn_window"]


def test_a_program_whose_window_is_a_key_too_wide_is_not_correct(
        monkeypatch):
    """The program itself (not the reference) attending one key more: no
    norm moves at this size either, the pairs do."""
    from sparktorch_tpu.models import sparse_moe_lm as M
    from sparktorch_tpu.ops.rule_attention import CausalWindow

    monkeypatch.setattr(M, "CausalWindow", lambda w: CausalWindow(w + 1))
    cell = tiny()
    res = cell.job.run(cell, 5, 0.3, None)
    assert failed_checks(res) == {"mask_pairs_off"}
    off = {c.name: c.value for c in res.checks}["mask_pairs_off"]
    assert off == cell.traffic["seq_len"] - cell.config["sliding_window"]


def test_a_program_without_its_attention_gate_is_not_correct(monkeypatch):
    """The gate's weights are in the tree the benchmark hands over, so a
    program that skips the gate still trains: its attention's gradients
    say so."""
    import jax

    real = jax.nn.sigmoid
    from sparktorch_tpu.models import sparse_moe_lm as M

    class Ungated(M.RuleAttention):
        def _proj(self, x, w):
            out = super()._proj(x, w)
            # the gate's logits are the one projection of rank 3
            return out + 1e4 if out.ndim == 3 else out

    monkeypatch.setitem(M._ATTENTION, "window",
                        (Ungated, M._ATTENTION["window"][1]))
    assert float(real(1e4)) == 1.0
    cell = tiny()
    res = cell.job.run(cell, 6, 0.3, None)
    assert "grad_norm_rel_attn_window" in failed_checks(res)
    assert "grad_norm_rel_attn_full" not in failed_checks(res)


@pytest.mark.parametrize("fault,caught_by", [
    ("lr_x1.5", "loss_rel_next"),
    ("half_batch", "grad_norm_rel_first"),
    ("window_ignored", "grad_norm_rel_attn_window"),
    ("window_ignored", "mask_pairs_off"),
    ("window_513", "mask_pairs_off"),
    ("rope_swapped", "grad_norm_rel_attn_window"),
    ("no_yarn", "grad_norm_rel_attn_full"),
    ("no_attn_gate", "grad_norm_rel_attn_full"),
    ("no_shared_expert", "grad_norm_rel_shared_dense"),
    ("no_routed_scale", "grad_norm_rel_experts"),
    ("softmax_scores", "grad_norm_rel_router"),
    ("shifted_share", "grad_norm_rel_router"),
    ("no_renorm", "grad_norm_rel_router"),
])
def test_a_planted_fault_in_the_reference_fails_a_limit(fault, caught_by):
    cell = tiny()
    assert set(cell.job.FAULTS) == {
        "lr_x1.5", "half_batch", "window_ignored", "window_513",
        "rope_swapped", "no_yarn", "no_attn_gate", "no_shared_expert",
        "no_routed_scale", "softmax_scores", "shifted_share", "no_renorm"}
    numbers = cell.job.control(cell, 5, kinds=(fault,))[fault]
    assert caught_by in outside(numbers, cell.traffic["limits"]), numbers
    t, w = cell.traffic["seq_len"], cell.config["sliding_window"]
    assert numbers["mask_pairs_off"] == {
        "window_ignored": (t - w) * (t - w + 1) // 2,
        "window_513": t - w}.get(fault, 0)
    if fault == "window_513":
        assert outside(numbers, cell.traffic["limits"]) == {"mask_pairs_off"}


@pytest.mark.parametrize("seed", (5, 6))
def test_the_fp8_control_fails_a_limit_and_bf16_does_not(seed):
    cell = tiny()
    numbers = cell.job.control(cell, seed, kinds=("bf16", "fp8"))
    limits = cell.traffic["limits"]
    assert outside(numbers["fp8"], limits), numbers
    assert not outside(numbers["bf16"], limits), numbers


def test_rows_come_from_the_slice_and_labels_are_the_next_token():
    cell = harness.resolve_cell("laguna_xs2_fit_sync_s8k")
    small = {**cell.traffic, "resident_rows": 4, "seq_len": 512}
    ids, labels = cell.job.make_rows(np.random.default_rng(1), small,
                                     cell.config)
    assert ids.shape == labels.shape == (4, 512)
    assert np.array_equal(ids[:, 1:], labels[:, :-1])
    assert ids.max() < cell.config["vocab_size"] == 12_544
    assert len(np.unique(ids)) <= cell.traffic["active_vocab"] == 512
    assert cell.traffic["mini_batch"] * cell.traffic["seq_len"] == 16_384

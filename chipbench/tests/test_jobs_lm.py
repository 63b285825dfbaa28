"""Job kind ``fit_sync_lm`` end to end at a tiny size through its
Python API: ``correct`` on sound runs, false when the timed path is
broken underneath, every planted fault outside a limit, the fp8 control
outside one and the bf16 control inside all."""

from pathlib import Path

import numpy as np
import pytest

from chipbench import harness

TINY = Path(__file__).parent / "tiny"


def tiny():
    return harness.resolve_cell("tiny_fit_sync_lm", TINY / "BENCHMARK_lm.json",
                                TINY)


def run(seed=2 ** 31 + 11, seconds=0.3):
    cell = tiny()
    return cell, cell.job.run(cell, seed, seconds, None)


def failed_checks(res):
    return {c.name for c in res.checks if not c.ok}


def outside(numbers, limits):
    return {k for k in limits if k in numbers and numbers[k] > limits[k]}


@pytest.mark.parametrize("seed", (2 ** 31 + 11, 3, 4))
def test_job_runs_and_is_correct(seed):
    cell, res = run(seed)
    assert [c.line() for c in res.checks if not c.ok] == []
    assert {c.name for c in res.checks} == set(cell.traffic["limits"]) | {
        "moe_pairs_dropped", "steps_with_wrong_row_count",
        "nonfinite_losses"}
    assert res.attempted > 0 and res.failed == 0
    assert res.window_s >= 0.3 and res.setup_s > 0
    assert {cell.traffic["reports"][k] for k in res.end_to_end} | {
        "setup_s"} == {m["name"] for m in cell.end_to_end}
    li = res.layer_inputs
    assert len(li["moe_rows_max"]) == len(li["moe_rows_mean"]) == res.attempted
    assert all(a >= b > 0 for a, b in zip(li["moe_rows_max"],
                                          li["moe_rows_mean"]))


def test_rows_are_the_languages_and_labels_the_next_token():
    cell = tiny()
    a_x, a_y = cell.job.make_rows(np.random.default_rng(1), cell.traffic,
                                  cell.config)
    b_x, _ = cell.job.make_rows(np.random.default_rng(2), cell.traffic,
                                cell.config)
    assert a_x.shape == a_y.shape == (cell.traffic["resident_rows"],
                                      cell.traffic["seq_len"])
    assert np.array_equal(a_x[:, 1:], a_y[:, :-1])
    assert not np.array_equal(a_x, b_x)
    # one language for every seed: the same small support
    assert set(np.unique(a_x)) | set(np.unique(b_x)) <= set(
        np.unique(np.concatenate([a_x, a_y], 1)))
    assert len(np.unique(a_x)) <= cell.traffic["active_vocab"]
    assert a_x.max() < cell.config["vocab_size"]


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    from sparktorch_tpu.train import sync

    real = sync.make_train_epoch

    def frozen(*args, **kwargs):
        step = real(*args, **kwargs)

        def same_state(state, batch):
            import jax
            import jax.numpy as jnp

            _, metrics = step(jax.tree.map(jnp.copy, state), batch)
            return state, metrics

        return same_state

    monkeypatch.setattr(sync, "make_train_epoch", frozen)
    _, res = run()
    assert "loss_fall" in failed_checks(res)


def test_an_expert_layer_told_the_wrong_share_is_not_correct(monkeypatch):
    """The program itself holding experts 4-5 where the configuration
    says 2-3: only the gradient norms by group can tell."""
    cell = tiny()
    kwargs = {**cell.config["constructor_kwargs"], "experts_held": [4, 5]}
    monkeypatch.setitem(cell.config, "constructor_kwargs", kwargs)
    res = cell.job.run(cell, 3, 0.3, None)
    assert {"grad_norm_rel_experts"} <= failed_checks(res) <= {
        "grad_norm_rel_experts", "grad_norm_rel_router"}


@pytest.mark.parametrize("fault,caught_by", [
    ("lr_x1.5", "loss_rel_next"),
    ("half_batch", "grad_norm_rel_first"),
    ("no_selection", "grad_norm_rel_attention"),
    ("shifted_share", "grad_norm_rel_experts"),
    ("no_renorm", "grad_norm_rel_router"),
])
@pytest.mark.parametrize("seed", (5, 6))
def test_a_planted_fault_in_the_reference_fails_a_limit(seed, fault,
                                                        caught_by):
    cell = tiny()
    numbers = cell.job.control(cell, seed, kinds=(fault,))[fault]
    assert caught_by in outside(numbers, cell.traffic["limits"]), numbers


@pytest.mark.parametrize("seed", (5, 6))
def test_the_fp8_control_fails_a_limit_and_bf16_does_not(seed):
    cell = tiny()
    numbers = cell.job.control(cell, seed, kinds=("bf16", "fp8"))
    limits = cell.traffic["limits"]
    assert outside(numbers["fp8"], limits), numbers
    assert not outside(numbers["bf16"], limits), numbers


def test_leaves_fall_into_the_traffic_files_groups():
    from chipbench.jobs import fit_sync_groups as g

    t = tiny().traffic
    groups = {**t["zero_grad_groups"], **t["grad_groups"]}
    of = lambda key: g.group_of(key, groups)
    assert of("layer_3.attn.idx_k_norm.scale") == "indexer"
    assert of("layer_0.attn.wq") == of("layer_0.attn_norm") == "attention"
    assert of("layer_1.moe.router") == of("layer_1.moe_norm") == "router"
    assert of("layer_2.moe.w_down") == "experts"
    assert of("embed") == "embedding"
    assert of("head") == of("final_norm") == "head"
    with pytest.raises(KeyError):
        of("layer_0.something_new")
    assert g.norms_by({"embed": 3.0, "head": 4.0, "final_norm": 3.0}, of) \
        == {"embedding": 3.0, "head": 5.0}


def test_the_frozen_router_gets_a_gradient_and_no_update():
    """The traffic file freezes the router: its gradient is computed
    and compared, the optimizer the job hands over leaves it where it
    was and moves the rest."""
    import jax
    import jax.numpy as jnp

    from chipbench.jobs import fit_sync_groups as g

    cell = tiny()
    assert cell.traffic["frozen"] == [".moe.router"]
    tx = g.frozen_optimizer("adam", cell.traffic["frozen"])(learning_rate=0.1)
    params = {"layer_0": {"moe": {"router": jnp.ones((2, 2)),
                                  "w_up": jnp.ones((2, 2))}}}
    updates, _ = tx.update(params, tx.init(params), params)
    moe = updates["layer_0"]["moe"]
    assert np.all(np.asarray(moe["router"]) == 0)
    assert np.all(np.asarray(moe["w_up"]) < 0)
    _, res = run(3)
    numbers = {c.name: c.value for c in res.checks}
    assert 0 < numbers["grad_norm_rel_router"] < 1

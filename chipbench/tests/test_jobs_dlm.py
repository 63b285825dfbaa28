"""Job kind ``fit_sync_dlm`` end to end at a tiny size through its
Python API: ``correct`` on sound runs, every planted fault outside a
limit, the fp8 control outside one and the bf16 control inside all, the
noise restated step by step, and false where the program's own mask or
stream is broken underneath."""

from pathlib import Path

import numpy as np
import pytest

from chipbench import harness

TINY = Path(__file__).parent / "tiny"


def tiny():
    return harness.resolve_cell("tiny_fit_sync_dlm",
                                TINY / "BENCHMARK_dlm.json", TINY)


def failed_checks(res):
    return {c.name for c in res.checks if not c.ok}


def outside(numbers, limits):
    """The limits a control's numbers break; its mask's pairs are held
    to 0, as the job's own check holds the program's."""
    return {k for k in limits if k in numbers and numbers[k] > limits[k]} | (
        {"mask_pairs_off"} if numbers["mask_pairs_off"] else set())


@pytest.mark.parametrize("seed", (2 ** 31 + 11, 3, 4))
def test_job_runs_and_is_correct(seed):
    cell = tiny()
    res = cell.job.run(cell, seed, 0.3, None)
    assert [c.line() for c in res.checks if not c.ok] == []
    assert {c.name for c in res.checks} == set(cell.traffic["limits"]) | {
        "moe_pairs_dropped", "masked_tokens_off_restated", "mask_pairs_off",
        "steps_with_wrong_row_count", "nonfinite_losses"}
    assert res.attempted > 0 and res.failed == 0
    assert res.window_s >= 0.3 and res.setup_s > 0
    assert {cell.traffic["reports"][k] for k in res.end_to_end} | {
        "setup_s"} == {m["name"] for m in cell.end_to_end}
    li = res.layer_inputs
    assert len(li["moe_rows_max"]) == len(li["moe_rows_mean"]) == res.attempted


def test_rows_are_their_own_labels_and_never_hold_the_mask_id():
    cell = tiny()
    x, y = cell.job.make_rows(np.random.default_rng(1), cell.traffic,
                              cell.config)
    assert x.shape == (cell.traffic["resident_rows"], cell.traffic["seq_len"])
    assert np.array_equal(x, y)
    assert x.max() < cell.config["mask_token_id"] \
        == cell.config["vocab_size"] - 1
    assert len(np.unique(x)) <= cell.traffic["active_vocab"]
    # the real cell's language too: 512 ids of the slice, never 18,991
    real = harness.resolve_cell("sdar_30b_fit_sync_s8k")
    small = {**real.traffic, "resident_rows": 4, "seq_len": 512}
    ids, _ = real.job.make_rows(np.random.default_rng(1), small, real.config)
    assert ids.max() < real.config["mask_token_id"] == 18_991


def test_the_restated_noise_differs_by_step_and_by_shard():
    from chipbench.jobs import fit_sync_dlm as job

    draws = {}
    for step in range(3):
        level, masked = job.restated_noise(0, step, 2, 1, 128, 1e-3)
        assert level.shape == (2,) and masked.shape == (2, 128)
        assert np.all((1e-3 <= level) & (level < 1))
        for shard in range(2):
            draws[step, shard] = (float(level[shard]),
                                  masked[shard].tobytes())
    assert len(set(draws.values())) == 6
    # one shard's draw does not depend on how many shards there are
    alone = job.restated_noise(0, 2, 1, 1, 128, 1e-3)
    assert float(alone[0][0]) == draws[2, 0][0]
    # a row's share of masked tokens follows its level
    level, masked = job.restated_noise(0, 0, 1, 64, 4096, 1e-3)
    np.testing.assert_allclose(masked.mean(-1), level, atol=0.03)


def test_a_model_that_draws_from_another_key_is_not_correct(monkeypatch):
    """The program's noise from a key the job cannot restate: the masked
    counts and step 1's loss both say so."""
    import jax

    from sparktorch_tpu.models import sparse_moe_lm as M

    real = M.diffusion_noise
    monkeypatch.setattr(M, "diffusion_noise", lambda key, *a: real(
        jax.random.fold_in(key, 1), *a))
    cell = tiny()
    res = cell.job.run(cell, 3, 0.3, None)
    assert {"masked_tokens_off_restated", "loss_rel_first"} <= failed_checks(
        res)


def test_a_program_whose_mask_leaks_the_block_is_not_correct(monkeypatch):
    """The program itself (not the reference) letting a noised query see
    the clean copy of its own block."""
    from sparktorch_tpu.models import sparse_moe_lm as M
    from sparktorch_tpu.ops.block_diffusion_attention import \
        BlockDiffusionMask

    class Leaky(BlockDiffusionMask):
        def __call__(self, i, j):
            both_clean_or_own = super().__call__(i, j)
            b_i = (i % self.seq_len) // self.block_length
            b_j = (j % self.seq_len) // self.block_length
            return both_clean_or_own | (
                (i >= self.seq_len) & (j < self.seq_len) & (b_j == b_i))

    monkeypatch.setattr(M, "BlockDiffusionMask", Leaky)
    cell = tiny()
    res = cell.job.run(cell, 5, 0.3, None)
    assert {"grad_norm_rel_attention", "mask_pairs_off"} <= failed_checks(res)
    off = {c.name: c.value for c in res.checks}["mask_pairs_off"]
    assert off == cell.traffic["seq_len"] * cell.config["block_length"]


@pytest.mark.parametrize("fault,caught_by", [
    ("lr_x1.5", "loss_rel_next"),
    ("own_block_seen", "grad_norm_rel_attention"),
    ("own_block_seen", "mask_pairs_off"),
    ("causal_mask", "grad_norm_rel_attention"),
    ("causal_mask", "mask_pairs_off"),
    ("positions_not_shared", "loss_rel_next"),
    ("no_loss_weight", "loss_rel_first"),
    ("loss_on_all", "loss_rel_first"),
    ("shifted_share", "grad_norm_rel_experts"),
    ("no_renorm", "grad_norm_rel_router"),
])
@pytest.mark.parametrize("seed", (5, 6))
def test_a_planted_fault_in_the_reference_fails_a_limit(seed, fault,
                                                        caught_by):
    cell = tiny()
    assert set(cell.job.FAULTS) == {
        "lr_x1.5", "own_block_seen", "causal_mask", "positions_not_shared",
        "no_loss_weight", "loss_on_all", "shifted_share", "no_renorm"}
    numbers = cell.job.control(cell, seed, kinds=(fault,))[fault]
    assert caught_by in outside(numbers, cell.traffic["limits"]), numbers
    assert bool(numbers["mask_pairs_off"]) == (
        fault in ("own_block_seen", "causal_mask"))


@pytest.mark.parametrize("seed", (5, 6))
def test_the_fp8_control_fails_a_limit_and_bf16_does_not(seed):
    cell = tiny()
    numbers = cell.job.control(cell, seed, kinds=("bf16", "fp8"))
    limits = cell.traffic["limits"]
    assert outside(numbers["fp8"], limits), numbers
    assert not outside(numbers["bf16"], limits), numbers

"""Job kind ``fit_sync_groups`` at a tiny size through its Python API:
``fit_sync``'s cells under the traffic files that name this job, so the
same rows, weights and window, with step 1's gradient compared leaf by
leaf."""

import json
from pathlib import Path

import pytest

from chipbench import harness
from chipbench.jobs import fit_sync_groups as G

TINY = Path(__file__).parent / "tiny"


@pytest.fixture(scope="module")
def bench_json(tmp_path_factory):
    """``tiny/BENCHMARK.json`` with each cell's traffic swapped for its
    ``*_groups`` twin."""
    with open(TINY / "BENCHMARK.json") as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        w["traffic"] = w["traffic"].replace("_sync", "_groups")
    path = tmp_path_factory.mktemp("groups") / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    return path


def outside(numbers, limits):
    return {k for k in limits if k in numbers and numbers[k] > limits[k]}


@pytest.mark.parametrize("seed", (2 ** 31 + 11, 3, 4))
def test_job_runs_and_is_correct(bench_json, seed):
    cell = harness.resolve_cell("tiny_fit_sync", bench_json, TINY)
    assert cell.traffic["job"] == "fit_sync_groups"
    res = cell.job.run(cell, seed, 0.3, None)
    assert [c.line() for c in res.checks if not c.ok] == []
    assert {c.name for c in res.checks} == set(cell.traffic["limits"]) | {
        "steps_with_wrong_row_count", "nonfinite_losses"}
    assert "grad_norm_shape_rel" in cell.traffic["limits"]
    assert res.attempted > 0 and res.failed == 0 and res.window_s >= 0.3


def test_a_program_on_other_weights_than_the_references_is_not_correct(
        bench_json, monkeypatch):
    """Weights drawn from another key: the first step's loss hardly
    moves (a fresh classifier reads ln 2 whatever its weights), the
    gradient's leaves do."""
    import jax

    cell = harness.resolve_cell("tiny_fit_sync", bench_json, TINY)
    real = cell.reference.init
    calls = []

    def init(key, cfg):
        calls.append(key)
        # the program asks first, the reference after the window
        return real(jax.random.fold_in(key, 1) if len(calls) == 1 else key,
                    cfg)

    monkeypatch.setattr(cell.reference, "init", init)
    res = cell.job.run(cell, 3, 0.3, None)
    assert "grad_norm_shape_rel" in {c.name for c in res.checks if not c.ok}


@pytest.mark.parametrize("seed", (5, 6))
def test_the_fp8_control_fails_the_shape_limit_and_bf16_none(bench_json,
                                                             seed):
    cell = harness.resolve_cell("small_fit_sync", bench_json, TINY)
    numbers = cell.job.control(cell, seed, kinds=("bf16", "fp8"))
    limits = cell.traffic["limits"]
    assert "grad_norm_shape_rel" in outside(numbers["fp8"], limits), numbers
    assert not outside(numbers["bf16"], limits), numbers


@pytest.mark.parametrize("fault", ("lr_x1.5", "half_batch"))
def test_a_planted_fault_in_the_reference_fails_a_limit(bench_json, fault):
    cell = harness.resolve_cell("small_fit_sync", bench_json, TINY)
    numbers = cell.job.control(cell, 5, kinds=(fault,))[fault]
    assert outside(numbers, cell.traffic["limits"]), numbers


def test_the_shape_is_blind_to_a_common_factor_and_sees_the_rest():
    ref = {"layer_0.w": 3.0, "layer_0.b": 0.004, "layer_1.w": 4.0}
    scaled = {k: 1.01 * v for k, v in ref.items()}
    assert G.shape_rel(scaled, ref) < 1e-12
    # a small leaf far off weighs by its norm, a large one in full
    assert G.shape_rel({**ref, "layer_0.b": 0.008}, ref) < 1e-3
    assert G.shape_rel({**ref, "layer_1.w": 4.04}, ref) \
        == pytest.approx(0.04 * 3 / 25, rel=1e-2)


def test_kinds_and_groups_of_leaves():
    assert G.kind_of("backbone.layer_11.mlp_in.bias") == "backbone.mlp_in.bias"
    assert G.kind_of("layer_0.attn.wq") == "attn.wq"
    assert G.kind_of("pooler.kernel") == "pooler.kernel"
    groups = {"zero": [".attn.idx_"], "attention": [".attn."]}
    assert G.group_of("layer_3.attn.idx_wq", groups) == "zero"
    assert G.group_of("layer_3.attn.wq", groups) == "attention"
    with pytest.raises(KeyError):
        G.group_of("embed", groups)
    numbers, furthest = G.compare_leaves(
        {"layer_0.attn.idx_wq": 0.0, "layer_0.attn.wq": 2.2,
         "layer_1.attn.wq": 2.0},
        {"layer_0.attn.idx_wq": 0.0, "layer_0.attn.wq": 2.0,
         "layer_1.attn.wq": 2.0},
        {"grad_groups": {"attention": [".attn."]},
         "zero_grad_groups": {"zero": [".attn.idx_"]}})
    assert numbers["grad_norm_zero"] == 0.0
    assert numbers["grad_norm_rel_attention"] == pytest.approx(
        (8.84 ** 0.5 - 8 ** 0.5) / 8 ** 0.5)
    assert furthest[0][1] == "attn.wq"

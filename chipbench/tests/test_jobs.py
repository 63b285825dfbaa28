"""The job kind end to end at a tiny size through its Python API;
``correct`` comes out false when the timed path is broken underneath;
and the lower-precision control fails a limit."""

from pathlib import Path

import pytest

from chipbench import harness

TINY = Path(__file__).parent / "tiny"


def tiny(name):
    return harness.resolve_cell(name, TINY / "BENCHMARK.json", TINY)


def run(name, seed=2 ** 31 + 11, seconds=0.3):
    cell = tiny(name)
    return cell, cell.job.run(cell, seed, seconds, None)


def failed_checks(res):
    return {c.name for c in res.checks if not c.ok}


def break_the_step(monkeypatch, wrap):
    """``wrap(step, args)`` stands in for the compiled chunk program
    that ``train_distributed`` drives."""
    from sparktorch_tpu.train import sync

    real = sync.make_train_epoch
    monkeypatch.setattr(
        sync, "make_train_epoch",
        lambda *args, **kwargs: wrap(real, args, kwargs))


@pytest.mark.parametrize("seed", (2 ** 31 + 11, 3, 4))
def test_job_runs_and_is_correct(seed):
    cell, res = run("tiny_fit_sync", seed)
    assert [c.line() for c in res.checks if not c.ok] == []
    assert res.attempted > 0 and res.failed == 0
    assert res.window_s >= 0.3 and res.setup_s > 0
    reported = {cell.traffic["reports"][k] for k in res.end_to_end}
    assert reported | {"setup_s"} == {m["name"] for m in cell.end_to_end}
    assert all(v > 0 for v in res.end_to_end.values())


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    def wrap(real, args, kwargs):
        step = real(*args, **kwargs)

        def same_state(state, batch):
            import jax
            import jax.numpy as jnp

            _, metrics = step(jax.tree.map(jnp.copy, state), batch)
            return state, metrics

        return same_state

    break_the_step(monkeypatch, wrap)
    _, res = run("tiny_fit_sync")
    assert "loss_fall" in failed_checks(res)


def test_a_wrong_learning_rate_is_not_correct(monkeypatch):
    """The update is there but half again as long: only the losses of
    the steps after the first can tell."""
    import optax

    def wrap(real, args, kwargs):
        module_apply, loss_fn, _tx, *rest = args
        lr = tiny("tiny_fit_sync").traffic["optimizer_params"]["lr"]
        return real(module_apply, loss_fn, optax.adam(1.5 * lr), *rest,
                    **kwargs)

    break_the_step(monkeypatch, wrap)
    _, res = run("tiny_fit_sync", seed=3)
    assert failed_checks(res) == {"loss_rel_next"}


@pytest.mark.parametrize("fault", ("lr_x1.5", "half_batch"))
@pytest.mark.parametrize("seed", (5, 6))
def test_a_planted_fault_in_the_reference_fails_a_limit(seed, fault):
    cell = tiny("small_fit_sync")
    numbers = cell.job.control(cell, seed, kinds=(fault,))[fault]
    limits = cell.traffic["limits"]
    assert any(numbers[k] > limits[k] for k in numbers), numbers


@pytest.mark.parametrize("seed", (5, 6))
def test_the_fp8_control_fails_a_limit_and_bf16_does_not(seed):
    """On the chip, at the cells' own sizes, the control was read on a
    dozen seeds (PERF.md section 2). A test run cannot hold BERT-base,
    and a smaller model rounds less, so this size has limits of its
    own, set the same way from its own readings (``small_sync.json``
    gives them)."""
    cell = tiny("small_fit_sync")
    numbers = cell.job.control(cell, seed, kinds=("bf16", "fp8"))
    limits = cell.traffic["limits"]
    assert any(numbers["fp8"][k] > limits[k] for k in numbers["fp8"]), numbers
    assert all(numbers["bf16"][k] <= limits[k] for k in numbers["bf16"]), \
        numbers

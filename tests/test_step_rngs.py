"""The sync step's random streams (``train/step.py`` ``_forward_rngs``):
a module that declares ``train_rngs`` draws from a key that differs by
step and by shard and that the run's seed restates; a module that
declares none is called as it always was."""

import flax.linen as nn
import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp

from sparktorch_tpu.parallel.mesh import build_mesh
from sparktorch_tpu.train.step import (TrainState, _forward_rngs,
                                       make_train_epoch, make_train_step)
from sparktorch_tpu.utils.data import DataBatch


class Plain(nn.Module):
    @nn.compact
    def __call__(self, x):
        return x[:, :1] * self.param("w", nn.initializers.ones, ())


class DeclaresNone(Plain):
    train_rngs = ()


class Draws(nn.Module):
    """Predicts one uniform draw from its stream, whatever the row."""

    train_rngs = ("noise",)

    @nn.compact
    def __call__(self, x):
        u = jax.random.uniform(self.make_rng("noise"), ())
        return x[:, :1] * 0.0 + u + 0.0 * self.param(
            "w", nn.initializers.ones, ())


class DrawsTwo(nn.Module):
    train_rngs = ("noise", "dropout")

    @nn.compact
    def __call__(self, x):
        a = jax.random.uniform(self.make_rng("noise"), ())
        b = jax.random.uniform(self.make_rng("dropout"), ())
        return x[:, :1] * 0.0 + a - b + 0.0 * self.param(
            "w", nn.initializers.ones, ())


def _state(module, seed=5):
    tx = optax.sgd(0.1)
    params = module.init(jax.random.key(0), jnp.zeros((1, 3)))["params"]
    return tx, TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          model_state={}, opt_state=tx.init(params),
                          rng=jax.random.key(seed))


def _batch(n=8):
    return DataBatch(jnp.ones((n, 3)), jnp.zeros((n,)), jnp.ones((n,)))


def _first_draw(stream: str, key):
    class Draw(nn.Module):
        @nn.compact
        def __call__(self):
            return self.make_rng(stream)

    return Draw().apply({}, rngs={stream: key})


def _restated(seed, step, shard, fold=1, stream="noise"):
    """The uniform a top-level module draws from its ``fold``-th declared
    stream at global step ``step`` on shard ``shard``, from the seed."""
    rng = jax.random.key(seed)
    for _ in range(step):
        rng = jax.random.split(rng)[1]
    sample_key = jax.random.fold_in(jax.random.split(rng)[0], shard)
    return float(jax.random.uniform(
        _first_draw(stream, jax.random.fold_in(sample_key, fold)), ()))


loss_fn = lambda preds, y: preds[:, 0]


@pytest.mark.parametrize("dp", [1, 2])
def test_a_declaring_module_draws_a_new_key_each_step_and_shard(dp):
    mesh = build_mesh(devices=jax.devices()[:dp])
    module = Draws()
    tx, state = _state(module)
    step = make_train_step(module.apply, loss_fn, tx, mesh, mini_batch=2)
    losses = []
    for _ in range(3):
        state, metrics = step(state, _batch())
        losses.append(float(metrics.loss))
    want = [np.mean([_restated(5, s, r) for r in range(dp)])
            for s in range(3)]
    np.testing.assert_allclose(losses, want, rtol=1e-6)
    draws = {_restated(5, s, r) for s in range(3) for r in range(2)}
    assert len(draws) == 6  # every step and shard its own


def test_the_fused_chunk_draws_what_single_steps_draw():
    mesh = build_mesh(devices=jax.devices()[:2])
    module = Draws()
    tx, state = _state(module)
    epoch = make_train_epoch(module.apply, loss_fn, tx, mesh, 3, mini_batch=2)
    _, metrics = epoch(state, _batch())
    want = [np.mean([_restated(5, s, r) for r in range(2)]) for s in range(3)]
    np.testing.assert_allclose(metrics.loss, want, rtol=1e-6)


def test_each_declared_stream_gets_its_own_fold():
    key = jax.random.key(3)
    rngs = _forward_rngs(DrawsTwo().apply, key)
    assert list(rngs) == ["noise", "dropout"]
    for i, name in enumerate(rngs):
        assert jnp.array_equal(
            jax.random.key_data(rngs[name]),
            jax.random.key_data(jax.random.fold_in(key, i + 1)))
    mesh = build_mesh(devices=jax.devices()[:1])
    module = DrawsTwo()
    tx, state = _state(module)
    _, metrics = make_train_step(module.apply, loss_fn, tx, mesh)(
        state, _batch())
    assert float(metrics.loss) == pytest.approx(
        _restated(5, 0, 0, 1) - _restated(5, 0, 0, 2, "dropout"), rel=1e-5)


def test_a_module_that_declares_nothing_is_called_as_before():
    """No ``rngs`` reach it and its lowered step holds no key for it:
    the text is the text of the same module declaring an empty tuple,
    and a declared stream is what changes it."""
    assert _forward_rngs(Plain().apply, jax.random.key(0)) == {}
    assert _forward_rngs(lambda *a, **k: None, jax.random.key(0)) == {}
    mesh = build_mesh(devices=jax.devices()[:2])

    def text(module):
        tx, state = _state(module)
        return make_train_epoch(module.apply, loss_fn, tx, mesh, 2,
                                mini_batch=2).lower(state, _batch()).as_text()

    plain = text(Plain())
    assert plain == text(DeclaresNone())
    assert plain != text(Draws())
    with pytest.raises(Exception, match="noise"):
        Draws().apply({"params": {"w": jnp.ones(())}}, jnp.ones((2, 3)))

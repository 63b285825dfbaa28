"""The reference's side of a training comparison: the loss and the
gradient of one global minibatch, summed over blocks of rows so that it
fits beside nothing else, and the optimizer the configurations use,
written out."""

from __future__ import annotations

import jax
import jax.numpy as jnp


class Grader:
    """``(loss, grads)`` of the weighted-mean loss over the rows of one
    global minibatch; ``grads`` has the tree of ``variables["params"]``.
    One compiled block program for all its calls."""

    def __init__(self, reference, cfg: dict, block_rows: int,
                 precision: str = "f32"):
        self.block_rows = block_rows

        @jax.jit
        def block(params, rest, xb, yb, wb):
            return jax.value_and_grad(
                lambda p: reference.loss_sum({**rest, "params": p}, xb, yb,
                                             wb, cfg, precision))(params)

        self._block = block

    def __call__(self, variables: dict, x, y, w):
        rest = {k: v for k, v in variables.items() if k != "params"}
        num, den, grads = 0.0, 0.0, None
        for lo in range(0, x.shape[0], self.block_rows):
            xb, yb, wb = (jnp.asarray(a[lo:lo + self.block_rows])
                          for a in (x, y, w))
            n, g = self._block(variables["params"], rest, xb, yb, wb)
            num, den = num + n, den + jnp.sum(wb)
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
        den = jnp.maximum(den, 1.0)
        return num / den, jax.tree.map(lambda g: g / den, grads)


@jax.jit
def _global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(g))
                        for g in jax.tree.leaves(tree)))


def global_norm(tree) -> float:
    return float(_global_norm(tree))


class Adam:
    """Kingma & Ba 2014 with bias correction, optax's defaults."""

    def __init__(self, lr, b1=0.9, b2=0.999, eps=1e-8):
        self.m = self.v = None
        self.t = 0

        @jax.jit
        def update(params, grads, m, v, t):
            m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, grads)
            v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, grads)
            params = jax.tree.map(
                lambda p, m, v: p - lr * (m / (1 - b1 ** t))
                / (jnp.sqrt(v / (1 - b2 ** t)) + eps), params, m, v)
            return params, m, v

        self._update = update

    def step(self, params, grads):
        if self.m is None:
            self.m = jax.tree.map(jnp.zeros_like, params)
            self.v = jax.tree.map(jnp.zeros_like, params)
        self.t += 1
        params, self.m, self.v = self._update(
            params, grads, self.m, self.v, jnp.float32(self.t))
        return params


OPTIMIZERS = {"adam": Adam}

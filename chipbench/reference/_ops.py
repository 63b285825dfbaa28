"""Arithmetic the plain references share: matrix products at a stated
precision, and the lower precisions the controls compute in.

``precision`` is one of

- ``"f32"``   float32 operands, ``Precision.HIGHEST`` (six bf16 passes
  on the TPU): the reference itself;
- ``"bf16"``  operands rounded to bfloat16, float32 accumulation: what
  the configurations state the program computes in;
- ``"fp8"``   operands scaled per tensor to the e4m3 range and rounded
  to float8, float32 accumulation: the nearest precision below bf16,
  the step a later PR would be tempted to take. The control. (Per-tensor
  int8, the other 8-bit format, reads closer to float32 than fp8 does
  at these shapes, so fp8 is the harder control to tell apart.)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
_E4M3_MAX = 448.0


def round_to(x, precision: str):
    """``x`` as float32 after a round trip through ``precision``."""
    x = x.astype(jnp.float32)
    if precision == "f32":
        return x
    if precision == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "fp8":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _E4M3_MAX
        # The cast must not be fused away: a straight-through estimator
        # keeps the gradient of the rounding at 1.
        q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
        return x + jax.lax.stop_gradient(q - x)
    raise ValueError(f"unknown precision {precision!r}")


def einsum(eq: str, a, b, precision: str):
    return jnp.einsum(eq, round_to(a, precision), round_to(b, precision),
                      precision=HIGHEST,
                      preferred_element_type=jnp.float32)


class Draws:
    """Seeded weights from ONE normal draw, cut into tensors.

    A draw per tensor (some 200 random generators for BERT-base) made
    the init program's compile 20 s, one draw 11.7 s (my chip run,
    PR 23). Ask for every tensor first (``normal``), then ``cut`` the
    one draw."""

    def __init__(self):
        self._sizes = []

    def normal(self, shape) -> int:
        n = 1
        for d in shape:
            n *= d
        self._sizes.append((tuple(shape), n))
        return len(self._sizes) - 1

    def cut(self, key) -> list:
        total = sum(n for _s, n in self._sizes)
        flat = jax.random.normal(key, (total,), jnp.float32)
        out, off = [], 0
        for shape, n in self._sizes:
            out.append(flat[off:off + n].reshape(shape))
            off += n
        return out


def cross_entropy(logits, labels):
    """Per-example softmax cross entropy, integer labels."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, labels.astype(jnp.int32)[:, None], axis=-1)[:, 0]
    return logz - picked

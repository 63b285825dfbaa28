"""The Pallas kernels, compiled by the TPU v5e compiler for a chip that
is described, not attached (about two seconds each).

Every other test runs these kernels in interpret mode; this file is
what catches a slice the tiling refuses, a kernel that wants more
fast memory than it may use, or a kernel that silently left the
program. A compile that passes is not a chip run: results and times
come from ``chip_smoke.py`` on the chip.

Rules this file keeps (``on-chip-measurement`` guide, section 2):
the topology is described inside a module-scoped fixture that skips
when it cannot be; nothing is built from it at import time, in a
``skipif`` or a ``parametrize``; the fixture is not ``autouse``; the
compile runs in the test's own process; the persistent compile cache
is off around it (a described-device entry can be written but never
read back). The programme reads ``jax.default_backend()``, which is
``cpu`` here, so each test steers that call — not a new option.
"""

import os
import sys

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chip_smoke import kernel_call_counts  # noqa: E402

from sparktorch_tpu.ops.flash_attention import flash_attention  # noqa: E402
from sparktorch_tpu.ops.fused_ce import fused_cross_entropy  # noqa: E402


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_tpu(monkeypatch):
    """Steer the kernels' backend probe to the TPU branch and keep the
    persistent compile cache out of the way."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _flash_text(shape, dtype, sharding):
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def loss(q, k, v):
        return flash_attention(q, k, v, True).astype(jnp.float32).sum()

    return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile().as_text()


@pytest.mark.parametrize("shape,dtype", [
    ((2, 8192, 8, 64), jnp.bfloat16),    # chip_smoke's seq-8192 CausalLM
    ((128, 128, 12, 64), jnp.bfloat16),  # BERT-base heads at batch 128
])
def test_flash_fwd_bwd_compiles_for_v5e(one_chip, as_tpu, shape, dtype):
    counts = kernel_call_counts(_flash_text(shape, dtype, one_chip))
    assert counts == {"flash_fwd": 1, "flash_bwd_dq": 1, "flash_bwd_dkv": 1,
                      "fused_ce_fwd": 0, "fused_ce_bwd": 0}, counts


def test_fused_ce_fwd_bwd_compiles_for_v5e(one_chip, as_tpu):
    logits = jax.ShapeDtypeStruct((16384, 32768), jnp.float32,
                                  sharding=one_chip)
    labels = jax.ShapeDtypeStruct((16384,), jnp.int32, sharding=one_chip)
    text = jax.jit(jax.grad(
        lambda l, y: fused_cross_entropy(l, y).mean())).lower(
            logits, labels).compile().as_text()
    counts = kernel_call_counts(text)
    assert counts == {"flash_fwd": 0, "flash_bwd_dq": 0, "flash_bwd_dkv": 0,
                      "fused_ce_fwd": 1, "fused_ce_bwd": 1}, counts
    assert text.count('custom_call_target="tpu_custom_call"') == 2


def test_untileable_shape_raises_on_tpu_backend(one_chip, as_tpu):
    """A caller who asked for the kernel by name gets an error naming
    the shape and the rule on a TPU backend — never a dense program
    (BERT's 30,522 vocabulary; a 100-token sequence)."""
    logits = jax.ShapeDtypeStruct((16384, 30522), jnp.float32,
                                  sharding=one_chip)
    labels = jax.ShapeDtypeStruct((16384,), jnp.int32, sharding=one_chip)
    with pytest.raises(ValueError, match=r"\(16384, 30522\).*multiple of"):
        jax.jit(lambda l, y: fused_cross_entropy(l, y).mean()).lower(
            logits, labels)
    q = jax.ShapeDtypeStruct((2, 100, 8, 64), jnp.bfloat16,
                             sharding=one_chip)
    with pytest.raises(ValueError, match=r"seq 100.*multiple"):
        jax.jit(lambda q, k, v: flash_attention(q, k, v, True)).lower(q, q, q)


def test_untileable_shape_stays_dense_off_the_tpu():
    """The give-way to dense stays as behaviour on the CPU, and the
    ``cross_entropy`` auto loss may choose dense on any backend."""
    from sparktorch_tpu.utils.losses import cross_entropy_auto

    logits = jnp.zeros((8, 30522), jnp.float32)
    labels = jnp.zeros((8,), jnp.int32)
    assert fused_cross_entropy(logits, labels).shape == (8,)
    text = jax.jit(lambda l, y: cross_entropy_auto(l, y).sum()).lower(
        jnp.zeros((2, 4, 30522), jnp.float32),
        jnp.zeros((2, 4), jnp.int32)).as_text()
    assert "while" not in text  # no interpret-mode kernel loop: dense

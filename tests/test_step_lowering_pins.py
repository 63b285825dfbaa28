"""The sync DP trainer's LOWERED step on a mesh without an ``ep`` axis is
the one it was before the step learned to place and reduce by leaf (PR
49): the hashes below were read on the parent commit 5fbf653 (PR 48),
before ``train/step.py`` was touched, of
``make_train_epoch(...).lower(...).as_text()`` at a tiny size for one
decoder model and for the BERT encoder, on the default mesh of 1 and of
4 devices. The other pins of the suite (``tests/test_short_conv_lm.py``,
``tests/test_gated_delta_lm.py``, ``tests/test_latent_attention_lm.py``)
are of the MODULES' trees and gradients; none holds the trainer's step.
An edit that means to change what the step lowers to reads them anew.

PR 51 meant to: the held experts fetch a chunk's rows by
``ops/grouped_mlp.py``'s ``fetch_rows`` where they gathered them as
``x[token]``, so the two ``("decoder", n)`` hashes were read anew on its
tree (340d75e54bab6d64 and 43c512d9493582a8 before); the two
``("encoder", n)`` hashes stand as PR 48's tree gave them, and the
decoder's text holds no gather of a chunk's rows any more."""

import functools
import hashlib
import re

import optax
import pytest

import jax
import jax.numpy as jnp

from sparktorch_tpu.models import sparse_moe_lm as M
from sparktorch_tpu.models.transformer import SequenceClassifier, \
    TransformerConfig
from sparktorch_tpu.parallel.mesh import build_mesh
from sparktorch_tpu.train.step import TrainState, make_train_epoch
from sparktorch_tpu.utils.data import DataBatch
from sparktorch_tpu.utils.losses import resolve_loss

T = 128


def _decoder():
    rotary = M.Rotary(1e4, (64,))
    module = M.laguna_lm(
        vocab_size=96, d_model=64, n_layers=1, n_kv_heads=2,
        n_routed_experts=16, experts_held=(2, 3), experts_per_token=4,
        expert_width=32, compute_dtype="float32",
        layers=[M.LayerKind("window", 4, rotary)], window=96,
        shared_expert_width=32, dense_width=64)
    return module, "cross_entropy", (T,), jnp.float32


def _encoder():
    module = SequenceClassifier(TransformerConfig(
        vocab_size=96, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_len=T, n_classes=2, dtype="float32"))
    return module, "cross_entropy", (), jnp.float32


MODELS = {"decoder": _decoder, "encoder": _encoder}

# {(model, devices of the default mesh): sha256[:16] of the lowered text}
PARENT = {
    ("decoder", 1): "ab6046e213f262f5",
    ("decoder", 4): "46baffd364826251",
    ("encoder", 1): "e3f47ed5c194cb76",
    ("encoder", 4): "fb46153460b6f5f0",
}


@functools.cache
def lowered_text(model: str, n_devices: int) -> str:
    module, loss, label_shape, label_dtype = MODELS[model]()
    tx = optax.adam(1e-3)
    mesh = build_mesh(devices=jax.devices()[:n_devices])

    def init():
        params = module.init(jax.random.key(0), jnp.zeros((1, T)))["params"]
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          model_state={}, opt_state=tx.init(params),
                          rng=jax.random.key(1))

    S = jax.ShapeDtypeStruct
    batch = DataBatch(S((8, T), jnp.float32),
                      S((8, *label_shape), label_dtype),
                      S((8,), jnp.float32))
    step = make_train_epoch(module.apply, resolve_loss(loss), tx, mesh, 2,
                            mini_batch=1)
    return step.lower(jax.eval_shape(init), batch).as_text()


@pytest.mark.parametrize("model,n_devices", sorted(PARENT))
def test_the_lowered_step_without_an_ep_axis_is_the_parents(model,
                                                            n_devices):
    text = lowered_text(model, n_devices)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == PARENT[(model, n_devices)]


@pytest.mark.parametrize("n_devices", [1, 4])
def test_the_decoders_step_gathers_no_chunk_of_rows(n_devices):
    """The expert layer's loops fetch their chunks' rows by the kernel:
    no gather that gives a ``[chunk, d]`` array is left in the lowered
    step (PR 48's held three, ``x[token]`` in both passes and ``d_out[
    token]``); the gathers that are left give scalars a pair (the
    routing's) or the embedding's rows."""
    cfg = _decoder()[0].config
    chunk, _ = M._row_chunks(T * cfg.experts_per_token,
                             len(cfg.experts_held), cfg.n_routed_experts)
    gathered = re.findall(r"stablehlo\.gather.*-> tensor<([0-9x]+)x\w+>",
                          lowered_text("decoder", n_devices))
    assert gathered and f"{chunk}x{cfg.d_model}" not in gathered

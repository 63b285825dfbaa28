"""The encoder's attention: the fused kernels in the projection's own
layout against ``dense_attention`` (interpret mode on the CPU), the
rule that picks a layer's path from what it can see, and the gauge that
says what was picked."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from sparktorch_tpu.models.transformer import (
    KERNEL_MIN_SEQ,
    SequenceClassifier,
    TransformerConfig,
    bert_base,
    pick_attention,
)
from sparktorch_tpu.ops.attention import dense_attention
from sparktorch_tpu.ops.flash_attention import (
    _packing,
    can_tile,
    flash_attention,
)
from sparktorch_tpu.parallel.compat import set_mesh
from sparktorch_tpu.parallel.mesh import MeshConfig, build_mesh


def _qkvw(shape, seed, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 4)
    return tuple(jax.random.normal(k, shape, jnp.float32).astype(dtype)
                 for k in ks)


# (batch, seq, heads, head_dim), blocks: BERT-base's heads at 512 and
# 256 (six lane groups of two heads, one tile a group), a head of 128
# (one head a group), chip_smoke's (2, 8192, 8, 64) class scaled down
# (four lane groups, a 4 x 4 grid of tiles under the diagonal rule)
_SHAPES = {
    "bert_s512": ((1, 512, 12, 64), None),
    "bert_s256": ((2, 256, 12, 64), None),
    "head_128": ((1, 256, 2, 128), 128),
    "smoke_class": ((2, 512, 8, 64), 128),
}


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("name", list(_SHAPES))
def test_kernels_equal_dense_output_and_gradients(name, causal):
    shape, block = _SHAPES[name]
    q, k, v, w = _qkvw(shape, seed=len(name))
    assert can_tile(shape[1], shape[1], shape[2], shape[3])

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * w)

    flash = lambda q, k, v: flash_attention(q, k, v, causal, block, block)
    dense = lambda q, k, v: dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(dense(q, k, v)),
                               atol=2e-5, rtol=2e-5)
    got = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(dense), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


def test_keys_of_another_length_than_the_queries():
    """Cross attention: 256 queries on 128 keys, the row statistics
    follow the queries."""
    q, _, _, w = _qkvw((1, 256, 4, 64), seed=1)
    _, k, v, _ = _qkvw((1, 128, 4, 64), seed=2)
    got = jax.grad(lambda *a: jnp.sum(flash_attention(*a) * w),
                   argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(dense_attention(*a) * w),
                    argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("heads,head_dim,packed", [
    (12, 64, (12, 64, 2)),     # BERT-base: as it stands, two heads a group
    (8, 128, (8, 128, 1)),
    (4, 32, (4, 32, 4)),
    (3, 64, (4, 64, 2)),       # a head of zeros fills the last group
    (2, 48, (2, 64, 2)),       # widths that do not divide 128 are padded
    (2, 160, (2, 256, 1)),
])
def test_heads_pack_into_lane_groups(heads, head_dim, packed):
    pk = _packing(heads, head_dim)
    assert tuple(pk) == packed
    assert pk.group_lanes % 128 == 0
    assert (pk.heads * pk.head_dim) % pk.group_lanes == 0
    as_it_stands = (pk.heads, pk.head_dim) == (heads, head_dim)
    assert can_tile(256, 256, heads, head_dim) is as_it_stands


def test_padded_heads_still_equal_dense():
    """Three heads of 48: one head and 16 lanes a head of zeros inside,
    sliced away outside, in the output and in all three gradients."""
    q, k, v, w = _qkvw((1, 128, 3, 48), seed=5)
    got = jax.grad(lambda *a: jnp.sum(flash_attention(*a, True) * w),
                   argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(dense_attention(*a, causal=True) * w),
                    argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


def test_no_array_of_all_pairs_and_no_transposed_copy_in_the_jaxpr():
    """What the wrapper hands the kernels: reshapes of q, k, v and the
    cotangent, row statistics one number a row. No ``[b, h, T, T]``, no
    ``[b * h, T, d]``, nothing 128 lanes wide a row."""
    shape = (2, 256, 12, 64)
    q, k, v, w = _qkvw(shape, seed=3, dtype=jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(flash_attention(*a).astype(jnp.float32)),
        argnums=(0, 1, 2)))(q, k, v)
    shapes = {tuple(v.aval.shape) for eqn in jaxpr.eqns
              for v in eqn.outvars if hasattr(v.aval, "shape")}
    assert (2, 12, 256, 256) not in shapes
    assert not any(s[:1] == (24,) for s in shapes), shapes
    assert (2, 6, 2, 256) in shapes                  # lse, sum(o * do)
    assert not any(len(s) >= 3 and s[-1] == 128 and s[-2] == 256
                   for s in shapes), shapes
    prims = {eqn.primitive.name for eqn in jaxpr.eqns}
    assert "pad" not in prims


# -- the rule ---------------------------------------------------------------

def _picked(cfg, seq, context):
    """``pick_attention`` as a trace sees it in ``context``."""
    if context.startswith("no_mesh"):
        return pick_attention(cfg, seq)
    if context == "gspmd_mesh":
        with set_mesh(build_mesh()):
            return pick_attention(cfg, seq)
    mesh = build_mesh(MeshConfig(dp=4, sp=2) if context == "sp_2"
                      else MeshConfig())
    seen = []

    def body(x):
        seen.append(pick_attention(cfg, seq))
        return x

    jax.eval_shape(jax.shard_map(body, mesh=mesh, in_specs=P(),
                                 out_specs=P(), check_vma=False),
                   jnp.zeros((8,)))
    return seen[0]


@pytest.mark.parametrize("context", ["no_mesh_one_device",
                                     "no_mesh_four_devices", "gspmd_mesh",
                                     "sp_2", "manual_axes"])
@pytest.mark.parametrize("seq", [128, 256, 512, 500])
@pytest.mark.parametrize("backend", ["tpu", "cpu"])
def test_the_rule_picks_the_path_from_what_it_sees(monkeypatch, backend, seq,
                                                   context):
    """The kernels only where the trace is one device's own program: a
    ``shard_map`` body, or no mesh in a process of one device. With no
    mesh and four devices a ``jit``'s own shardings (the predictor over
    a mesh, the trainers' init) may hand the program to the partitioner,
    which no trace can see: dense."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if context.startswith("no_mesh"):
        monkeypatch.setattr(jax, "device_count",
                            lambda: 1 if "one_device" in context else 4)
    kernel = (backend == "tpu" and seq in (256, 512)
              and context in ("no_mesh_one_device", "manual_axes"))
    assert _picked(TransformerConfig(), seq, context) == (
        "flash" if kernel else "dense")


def test_the_threshold_and_the_named_paths(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    assert 128 < KERNEL_MIN_SEQ[64] <= 512
    assert TransformerConfig().attn_impl == "auto"
    assert pick_attention(TransformerConfig(), 512) == "flash"
    for named in ("dense", "flash", "ring"):
        for seq in (128, 512):
            cfg = TransformerConfig(attn_impl=named)
            assert pick_attention(cfg, seq) == named


@pytest.mark.parametrize("heads,seq,picked", [
    (6, 512, "flash"),   # a head of 128: read at 256 (a tie) and at 512
    (6, 256, "dense"),
    (24, 512, "dense"),  # four heads of 32 a lane group: never read
    (3, 512, "dense"),   # a head of 256
    (16, 512, "dense"),  # heads of 48 would be padded
])
def test_auto_keeps_to_the_head_widths_it_was_read_at(monkeypatch, heads,
                                                      seq, picked):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    assert pick_attention(TransformerConfig(n_heads=heads), seq) == picked
    named = TransformerConfig(n_heads=heads, attn_impl="flash")
    assert pick_attention(named, seq) == "flash"


@pytest.mark.parametrize("seq,mesh_cfg,layers", [
    (512, MeshConfig(), 12),
    (128, MeshConfig(), 0),
    (512, MeshConfig(dp=4, sp=2), 0),
])
def test_the_gauge_says_what_the_step_picked(monkeypatch, seq, mesh_cfg,
                                             layers):
    """The trainers ask the model inside a ``shard_map`` over their
    mesh, where the step's trace asks: with ``sp`` 2 in the mesh the
    step's layers go dense, and so says the gauge."""
    from sparktorch_tpu.obs import Telemetry
    from sparktorch_tpu.train.sync import _note_model_gauges

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    tele = Telemetry(run_id=f"gauge-{seq}-{layers}")
    _note_model_gauges(tele, bert_base(), (seq,), build_mesh(mesh_cfg))
    assert tele.gauge_value("train.attention.kernel_layers") == layers
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert bert_base().train_gauges((seq,)) == {
        "train.attention.kernel_layers": 0}


def test_the_module_under_flash_equals_dense_within_bf16():
    """What a predictor applies at long rows on a TPU: the forward
    kernel alone (no row statistics), through the module."""
    kw = dict(vocab_size=64, d_model=128, n_heads=2, n_layers=2, d_ff=256,
              max_len=256)
    ids = jax.random.randint(jax.random.key(0), (2, 256), 0, 64)
    dense = SequenceClassifier(TransformerConfig(attn_impl="dense", **kw))
    flash = SequenceClassifier(TransformerConfig(attn_impl="flash", **kw))
    params = dense.init(jax.random.key(1), ids)
    # the kernel path writes its projections flat, one 2-D matmul each
    # (HeadsDense): an init under it draws the same tree
    assert jax.tree.all(jax.tree.map(
        lambda a, b: a.shape == b.shape and bool(jnp.all(a == b)),
        params, flash.init(jax.random.key(1), ids)))
    want, got = dense.apply(params, ids), flash.apply(params, ids)
    assert "while" in jax.jit(flash.apply).lower(params, ids).as_text()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("in_shape,features", [((32,), (3, 4, 8)),
                                               ((4, 8), (32,))])
def test_heads_dense_is_dense_general_and_the_same_flat(in_shape, features):
    """The projections' module draws ``nn.DenseGeneral``'s tree bit for
    bit and gives its product; ``flat`` is that product on rows with the
    axes merged."""
    import flax.linen as nn

    from sparktorch_tpu.models.transformer import HeadsDense

    x = jax.random.normal(jax.random.key(0), (2, 16, *in_shape))
    ref = nn.DenseGeneral(features, axis=tuple(range(-len(in_shape), 0)))
    params = ref.init(jax.random.key(1), x)
    ours = HeadsDense(in_shape, features)
    assert jax.tree.all(jax.tree.map(
        lambda a, b: a.shape == b.shape and bool(jnp.all(a == b)),
        params, ours.init(jax.random.key(1), x)))
    params = jax.tree.map(lambda a: a + 0.5, params)  # a bias that shows
    want = ref.apply(params, x)
    np.testing.assert_array_equal(np.asarray(ours.apply(params, x)),
                                  np.asarray(want))
    flat = HeadsDense(in_shape, features, flat=True)
    rows = x.reshape(2, 16, -1)
    assert jax.tree.all(jax.tree.map(
        lambda a, b: bool(jnp.all(a == b)),
        ref.init(jax.random.key(1), x), flat.init(jax.random.key(1), rows)))
    np.testing.assert_allclose(np.asarray(flat.apply(params, rows)),
                               np.asarray(want).reshape(2, 16, -1),
                               atol=1e-5, rtol=1e-5)

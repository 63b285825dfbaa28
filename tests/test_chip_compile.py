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
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu
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


def _kernel_lines(text, kernel):
    """The compiled text's custom calls of the Pallas kernel ``kernel``."""
    call = re.compile(
        rf'custom_call_target="tpu_custom_call".*op_name="[^"]*\b{kernel}\b')
    return [line for line in text.splitlines() if call.search(line)]


def _pallas_calls(text, kernel):
    return len(_kernel_lines(text, kernel))


def _declared_vmem(jaxpr, found=None):
    """``{kernel name: [bytes, ...]}``: what each ``pallas_call`` inside
    ``jaxpr`` declares of VMEM: its blocks twice (the pipeline holds two
    of each) and its scratch, every array padded to its dtype's (8 x 4 /
    itemsize, 128) tile; an operand left in HBM (``pl.ANY``) is no block,
    nor is a block of SMEM or a semaphore VMEM's.
    What the compiler adds for values it spills is
    not in it: the compile, which refuses a kernel over the chip's scoped
    limit (16 MiB on the v5e), holds the sum."""
    found = {} if found is None else found

    def padded(aval):
        *lead, rows, lanes = aval.shape
        item = np.dtype(aval.dtype).itemsize
        tile = 32 // item
        return (int(np.prod(lead, dtype=np.int64)) * -(-rows // tile) * tile
                * -(-lanes // 128) * 128 * item)

    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            mapping, body = eqn.params["grid_mapping"], eqn.params["jaxpr"]
            scratch = body.invars[len(body.invars)
                                  - mapping.num_scratch_operands:]
            in_vmem = lambda aval: getattr(aval, "memory_space", None) in (
                None, pltpu.VMEM)
            found.setdefault(eqn.params["name"], []).append(
                2 * sum(padded(b.block_aval) for b in mapping.block_mappings
                        if in_vmem(b.block_aval))
                + sum(padded(v.aval) for v in scratch if in_vmem(v.aval)))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _declared_vmem(sub, found)
    return found


def _forward_statistics(text, kernel):
    """The float32 results of the compiled forward kernels ``kernel``,
    as their dims: the row statistics, one number a row (rank 4, the
    sequence along the lanes), not spread over 128 lanes (rank 5)."""
    return [tuple(map(int, dims.split(",")))
            for line in _kernel_lines(text, kernel)
            for dims in re.findall(r"f32\[([\d,]+)\]",
                                   line.split(" custom-call(")[0])]


def _o_sized_copies(text, tokens):
    """The compiled text's ``copy`` and ``transpose`` instructions whose
    result is ``o`` by head, ``[b, tokens, heads, 128]`` or heads first
    ``[b, kv_heads, G, tokens, 128]``, in any dtype and layout: what a
    module that does not read ``o`` as the kernels write it costs."""
    by_head = rf"\w+\[\d+,{tokens},\d+,128\]|\w+\[\d+,\d+,\d+,{tokens},128\]"
    return [line.strip()[:160] for line in text.splitlines()
            if re.search(rf"= ({by_head})\S* (copy|transpose)\(", line)]


def _wide_float32_passes(text, tokens):
    """The compiled text's top-level instructions with a float32 result
    ``[b, tokens, >= 2048]`` under a linear layer's element-wise scopes
    that are a ``pad``, ``copy``, ``concatenate`` or ``slice``, are a
    fusion named after one or read one: what XLA moves through HBM
    around the kernels of ``ops/gdn_conv_gate.py`` that the kernels
    could have read or written in place."""
    entry = text[text.index("\nENTRY "):]
    found = []
    for line in entry.splitlines():
        m = re.search(r"= f32\[\d+,(\d+),(\d+)\]\S* ([\w-]+)\(", line)
        if (m and int(m.group(1)) == tokens and int(m.group(2)) >= 2048
                and re.search(r"gdn_conv|gdn_gates|gdn_out_norm", line)
                and re.search(r"\b(pad|copy|concatenate|slice)\b",
                              line.split("metadata=")[0])):
            found.append(line.strip()[:160])
    return found


def test_sparse_attention_fwd_bwd_compiles_for_v5e(one_chip, as_tpu):
    """The selected-key kernels at Keye-VL-2.0's heads (32 query heads
    on 4 key/value heads of 128), one row of 4,096 tokens."""
    from sparktorch_tpu.ops.sparse_attention import sparse_attention

    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    q, kv = S((1, 4096, 32, 128), jnp.bfloat16), S((1, 4096, 4, 128),
                                                   jnp.bfloat16)
    mask = S((1, 4096, 4096), jnp.int8)
    text = jax.jit(jax.grad(
        lambda q, k, v, m: sparse_attention(q, k, v, m).astype(
            jnp.float32).sum(), argnums=(0, 1, 2))).lower(
                q, kv, kv, mask).compile().as_text()
    for kernel in ("sparse_attn_fwd", "sparse_attn_bwd_dq",
                   "sparse_attn_bwd_dkv"):
        assert _pallas_calls(text, kernel) == 1, kernel
    assert _forward_statistics(text, "sparse_attn_fwd") == [(1, 4, 8, 4096)]


def _assert_sums_back_are_the_kernels(text, module):
    """No XLA scatter is left under the expert layers' scope (the
    scatter-add of a whole chunk, which the compiler gave a sort of the
    chunk's indices and a gather of all its rows into that order), and
    no gather (of a whole chunk's rows of ``x`` or of ``d_out``, three a
    layer until PR 51): each of their loops, one a layer and pass, calls
    ``moe_sum_back`` and ``moe_fetch_rows`` once, on sources that
    ``moe_fetch_source`` lays out, one forward and two backward."""
    cfg = module.config
    layers = sum(k.mlp == "experts" for k in cfg.layers) + cfg.mtp_depth
    under = [line for lines in _computations(text).values() for line in lines
             if "moe_experts" in line]
    assert not [line for line in under
                if " scatter(" in line or " sort(" in line
                or " gather(" in line]
    assert _pallas_calls(text, "moe_sum_back") == 2 * layers
    assert _pallas_calls(text, "moe_fetch_rows") == 2 * layers
    assert _pallas_calls(text, "moe_fetch_source") == 3 * layers


@pytest.mark.parametrize("tokens,chunk,d", [
    (16_384, 32_768, 2_048),    # LFM2's layer: 16 sublanes a row
    (32_768, 131_072, 2_304),   # a member of Mellum2's: 18, 24 in the source
], ids=["2048", "2304"])
def test_the_rows_fetch_compiles_for_v5e_at_both_widths(one_chip, as_tpu,
                                                        tokens, chunk, d):
    """``moe_fetch_source`` and ``moe_fetch_rows`` (one source, as the
    forward pass calls it, and two) at the two widths the cells have:
    Mosaic takes a bfloat16 row as a DMA's end only as whole tiles of 8
    sublanes (it refused ``[tokens, 18, 128]`` and ``[tokens, 1, d]``),
    and the turn of the fetched rows into the products' block is a
    ``reshape`` of a value in the kernel. Interpret mode checks none of
    it."""
    from sparktorch_tpu.ops import grouped_mlp as G

    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    text = G.fetch_source.lower(
        S((tokens, d), jnp.bfloat16)).compile().as_text()
    assert _pallas_calls(text, "moe_fetch_source") == 1
    source = jax.eval_shape(G.fetch_source, S((tokens, d), jnp.bfloat16))
    assert source.shape == (tokens, -(-d // 1024) * 8, 128)
    source = S(source.shape, source.dtype)
    for n in (1, 2):
        text = G.fetch_rows.lower(
            S((), jnp.int32), S((chunk,), jnp.int32), *[source] * n, d=d,
            tile=512).compile().as_text()
        assert _pallas_calls(text, "moe_fetch_rows") == 1


def test_sparse_moe_layer_gradient_compiles_for_v5e(one_chip, as_tpu):
    """One layer of the sparse-attention MoE LM at the published widths
    and the benchmark cell's rows (2 x 8,192 tokens, above the top-k of
    2,048, so the selection is in the program; 16 of 128 experts held;
    the padded head into the fused cross entropy): the whole gradient
    through the TPU compiler, which has refused scatters in it that the
    CPU's took. The layer is rematerialised under the model's own
    policy, which keeps the attention kernel's output and row
    statistics: the compiled program holds one ``sparse_attn_fwd`` a
    layer and none for the remat's second forward pass, so a compiler
    that put the call back would show here. The grouped kernels of the
    expert layer sit in the bodies of two loops over chunks of rows
    (forward and backward: the remat's second forward needs no result of
    the loop either and is gone), and the gradient's temporaries are
    under half of what they were with all 131,072 chosen pairs' rows
    held at once (6,009,584,128 bytes at this shape, compiled so on PR
    27's tree; 2,555,206,144 with the loop)."""
    from sparktorch_tpu.models.sparse_moe_lm import keye_vl2_lm
    from sparktorch_tpu.utils.losses import resolve_loss

    module = keye_vl2_lm(n_layers=1, vocab_size=4000,
                         experts_held=tuple(range(16)))
    ids = jnp.zeros((2, 8192), jnp.float32)
    shapes = jax.eval_shape(
        lambda: module.init(jax.random.key(0), ids))["params"]
    S = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    loss_fn = resolve_loss("cross_entropy")
    compiled = jax.jit(jax.grad(lambda p, x, y: loss_fn(
        module.apply({"params": p}, x), y).sum())).lower(
            jax.tree.map(S, shapes), S(ids), S(ids)).compile()
    text = compiled.as_text()
    assert _pallas_calls(text, "sparse_attn_fwd") == 1  # kept, not recomputed
    assert _pallas_calls(text, "sparse_attn_bwd_dq") == 1
    assert _pallas_calls(text, "sparse_attn_bwd_dkv") == 1
    # the fused q/k pass: forward, the remat's forward, one backward
    assert _pallas_calls(text, "qk_norm_rope_fwd") == 2
    assert _pallas_calls(text, "qk_norm_rope_bwd") == 1
    assert _pallas_calls(text, "fused_ce_fwd") == 1  # 4,000 padded to 4,096
    assert not _o_sized_copies(text, 8192)  # o leaves flat and is read so

    comps = _computations(text)
    bodies = {m.group(1) for lines in comps.values() for line in lines
              if " while(" in line
              for m in [re.search(r"body=(%[\w.\-]+)", line)]}
    # the grouped kernels of ops/grouped_mlp.py: gate|up with SwiGLU and
    # down with the gate forward; the hidden rows recomputed with their
    # cotangent, dx and the two weight gradients backward; the rows'
    # fetch and their sum back to their tokens in both
    kernels = ("moe_gmm_in", "moe_gmm_down", "moe_gmm_bwd_hidden",
               "moe_gmm_dx", "moe_gmm_dw_in", "moe_gmm_dw_down",
               "moe_fetch_rows", "moe_sum_back")
    calls = {body: [_pallas_calls("\n".join(comps[body]), kernel)
                    for kernel in kernels] for body in bodies}
    assert sorted(c for c in calls.values() if any(c)) == [
        [0, 0, 1, 1, 1, 1, 1, 1], [1, 1, 0, 0, 0, 0, 1, 1]]
    for kernel in kernels[:-2]:  # none outside the loops
        assert _pallas_calls(text, kernel) == 1, kernel
    assert "ragged-dot" not in text
    _assert_sums_back_are_the_kernels(text, module)
    assert compiled.memory_analysis().temp_size_in_bytes < 6_009_584_128 // 2


def test_block_diffusion_layer_gradient_compiles_for_v5e(one_chip, as_tpu):
    """One layer of the block-diffusion MoE LM at the published widths
    and the benchmark cell's rows (1 row of 8,192 tokens, 16,384 through
    the layer as the clean row and its noised copy; 16 of 128 experts
    held; the head and the weighted loss on the noised half, through the
    fused cross entropy): the mask is a rule the kernels evaluate on
    their tiles' indices, which Mosaic has to lower (integer division
    and remainder on a column and a row of indices), and their grids run
    over tables that reach them by scalar prefetch. One kernel of each
    kind a layer: the remat keeps the forward kernel's output and row
    statistics."""
    from sparktorch_tpu.models.sparse_moe_lm import NOISE_STREAM, sdar_moe_lm
    from sparktorch_tpu.utils.losses import resolve_loss

    module = sdar_moe_lm(n_layers=1, vocab_size=4000, mask_token_id=3999,
                         experts_held=tuple(range(16)))
    ids = jnp.zeros((1, 8192), jnp.float32)
    shapes = jax.eval_shape(
        lambda: module.init(jax.random.key(0), ids))["params"]
    S = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    loss_fn = resolve_loss("cross_entropy_weighted")
    text = jax.jit(jax.grad(lambda p, x: loss_fn(
        module.apply({"params": p}, x,
                     rngs={NOISE_STREAM: jax.random.key(1)}), x).sum())).lower(
            jax.tree.map(S, shapes), S(ids)).compile().as_text()
    assert _pallas_calls(text, "blockdiff_attn_fwd") == 1
    assert _forward_statistics(text, "blockdiff_attn_fwd") == [
        (1, 4, 8, 16384)]
    assert _pallas_calls(text, "blockdiff_attn_bwd_dq") == 1
    assert _pallas_calls(text, "blockdiff_attn_bwd_dkv") == 1
    assert _pallas_calls(text, "qk_norm_rope_fwd") == 2  # at 16,384 tokens
    assert _pallas_calls(text, "qk_norm_rope_bwd") == 1
    assert _pallas_calls(text, "sparse_attn_fwd") == 0
    assert _pallas_calls(text, "fused_ce_fwd") == 1  # on 8,192 rows
    assert not _o_sized_copies(text, 16384)
    _assert_sums_back_are_the_kernels(text, module)


def test_mixed_attention_cells_gradient_compiles_for_v5e(one_chip, as_tpu):
    """The whole model of the mixed-attention cell at its configuration
    file's sizes and the cell's rows (2 rows of 8,192 tokens; a full
    layer with a dense MLP, three window layers of 64 heads and a full
    layer of 48 with experts, 16 of 256 held; 12,544 rows of vocabulary):
    the two causal rules lower in Mosaic at 6 and 8 query heads a
    key/value head, each kind's kernels run once a layer (the remat keeps
    each kind's output and row statistics under its own names), and the
    gradient's scratch leaves room beside 7.84 GB of state."""
    import json

    from sparktorch_tpu.models.sparse_moe_lm import laguna_lm
    from sparktorch_tpu.utils.losses import resolve_loss

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "configs",
                           "laguna-xs.2-ep16.json")) as f:
        module = laguna_lm(**json.load(f)["constructor_kwargs"])
    ids = jnp.zeros((2, 8192), jnp.float32)
    shapes = jax.eval_shape(
        lambda: module.init(jax.random.key(0), ids))["params"]
    assert sum(a.size for a in jax.tree.leaves(shapes)) == 490_298_624
    S = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    loss_fn = resolve_loss("cross_entropy")
    compiled = jax.jit(jax.grad(lambda p, x, y: loss_fn(
        module.apply({"params": p}, x), y).sum())).lower(
            jax.tree.map(S, shapes), S(ids), S(ids)).compile()
    text = compiled.as_text()
    for kernel in ("fwd", "bwd_dq", "bwd_dkv"):
        assert _pallas_calls(text, f"window_attn_{kernel}") == 3
        assert _pallas_calls(text, f"causal_attn_{kernel}") == 2
    # 64 and 48 query heads on 8 key/value heads
    assert _forward_statistics(text, "window_attn_fwd") == 3 * [
        (2, 8, 8, 8192)]
    assert _forward_statistics(text, "causal_attn_fwd") == 2 * [
        (2, 8, 6, 8192)]
    # the fused q/k pass at 6 and 8 heads a group, half and all of the
    # dims rotated: twice forward (the remat's) and once backward a layer
    assert _pallas_calls(text, "qk_norm_rope_fwd") == 10
    assert _pallas_calls(text, "qk_norm_rope_bwd") == 5
    # and the products reach it as they leave: no transposing copy of a
    # float32 product (tokens minor from an einsum over [d, heads, 128])
    assert not re.search(r"f32\[2,8192,(6144|8192)\]\S* copy\(", text)
    # and o leaves the kernels as the gate and Wo read it: nothing copies
    # or transposes it by head, and outside a fusion's own computation
    # (so: to HBM) no instruction under the gate's scope writes a
    # float32 array of o's size
    assert not _o_sized_copies(text, 8192)
    o_sized = {2 * 8192 * heads * 128 for heads in (48, 64)}
    gate_f32 = [
        line.strip()[:160] for name, lines in _computations(text).items()
        if "fused_computation" not in name for line in lines
        if "attn_gate" in line for dims in re.findall(
            r"f32\[([\d,]+)\]", line.split(" = ")[-1].split("(")[0])
        if int(np.prod([int(d) for d in dims.split(",")])) in o_sized]
    assert not gate_f32, gate_f32
    assert _pallas_calls(text, "blockdiff_attn_fwd") == 0
    assert _pallas_calls(text, "fused_ce_fwd") == 1
    _assert_sums_back_are_the_kernels(text, module)
    # weights, gradients and Adam's moments are 7.84 GB of the 15.75
    assert compiled.memory_analysis().temp_size_in_bytes < 5_500_000_000


def test_latent_attention_cells_gradient_compiles_for_v5e(one_chip, as_tpu):
    """The whole model of the latent-attention cell at its configuration
    file's sizes and the cell's row (1 row of 8,192 tokens; a dense layer,
    four expert layers and the multi-token prediction module's, 32 heads
    of 192 / 128, 16 of 256 experts held, 16,160 rows of vocabulary)
    under its two-headed loss: keys of 256 lanes over values of 128 at one
    query head a grid step and tiles of 1,024 lower in Mosaic, each
    attention kernel runs once a layer (six layers: the remat keeps the
    output and row statistics), the layout op twice forward and once
    backward, both heads and the module's own loss go through the loss's
    kernel, and the gradient's scratch leaves room beside 8.17 GB of
    weights and Adam's moments."""
    import json

    from sparktorch_tpu.models.sparse_moe_lm import joyai_flash_lm
    from sparktorch_tpu.utils.losses import resolve_loss

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "configs",
                           "joyai-llm-flash-ep16.json")) as f:
        module = joyai_flash_lm(**json.load(f)["constructor_kwargs"])
    ids = jnp.zeros((1, 8192), jnp.float32)
    shapes = jax.eval_shape(
        lambda: module.init(jax.random.key(0), ids))["params"]
    assert sum(a.size for a in jax.tree.leaves(shapes)) == 680_441_088
    S = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    loss_fn = resolve_loss("cross_entropy_multi_token")

    def loss(p, x, y):
        out, sown = module.apply({"params": p}, x, mutable=["moe_metrics"])
        return loss_fn(out, y).sum(), sown

    compiled = jax.jit(jax.grad(loss, has_aux=True)).lower(
        jax.tree.map(S, shapes), S(ids), S(ids)).compile()
    text = compiled.as_text()
    for kernel in ("fwd", "bwd_dq", "bwd_dkv"):
        assert _pallas_calls(text, f"latent_attn_{kernel}") == 6
    assert _forward_statistics(text, "latent_attn_fwd") == 6 * [
        (1, 32, 1, 8192)]
    assert _pallas_calls(text, "latent_rope_fwd") == 12
    assert _pallas_calls(text, "latent_rope_bwd") == 6
    # two heads forward and backward, the module's own loss forward only
    assert _pallas_calls(text, "fused_ce_fwd") == 3
    assert _pallas_calls(text, "fused_ce_bwd") == 2
    assert _pallas_calls(text, "causal_attn_fwd") == 0
    assert _pallas_calls(text, "qk_norm_rope_fwd") == 0
    # no transposing copy of a float32 product on its way to the layout op
    assert not re.search(r"f32\[1,8192,8192\]\S* copy\(", text)
    _assert_sums_back_are_the_kernels(text, module)
    assert compiled.memory_analysis().temp_size_in_bytes < 7_000_000_000


def test_gated_delta_rule_fwd_bwd_compiles_for_v5e(one_chip, as_tpu):
    """``gdn_fwd`` and ``gdn_bwd`` at the linear-attention cell's shapes
    (1 row of 16,384 tokens, 16 key and 32 value heads of 128, chunks of
    64 in blocks of 8): the 64 x 64 float32 products of the triangular
    inverse batched over a block's chunks, the one identity product that
    turns the block's scalars, ``T`` folded 128 lanes wide on its way out
    and back, the state in scratch across the sequential axis and what
    the state's loops read (0.8 MiB forward; 2.6 MiB backward with the
    chunks' states and cotangents) lower in Mosaic and fit its VMEM."""
    from sparktorch_tpu.ops.gated_delta_rule import gated_delta_rule

    t = 16_384
    S = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                  sharding=one_chip)

    def loss(q, k, v, g, beta):
        return gated_delta_rule(q, k, v, g, beta).astype(jnp.float32).sum()

    traced = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).trace(
        S((1, t, 2048), jnp.bfloat16), S((1, t, 2048), jnp.bfloat16),
        S((1, t, 4096), jnp.bfloat16), S((1, t, 32), jnp.float32),
        S((1, t, 32), jnp.float32))
    text = traced.lower().compile().as_text()
    assert _pallas_calls(text, "gdn_fwd") == 1
    assert _pallas_calls(text, "gdn_bwd") == 1
    # the states kept: one a value head a block of 512 tokens; a chunk's
    # T: 32 rows of 128 lanes, 134 MB a layer
    assert re.search(r"f32\[1,32,32,128,128\]", text)
    assert re.search(r"f32\[1,32,256,32,128\]", text)
    # 2.28 and 4.84 MiB read at PR 43, of a scoped limit of 16: the
    # compiler has as much again for what the batched phases spill
    vmem = _declared_vmem(traced.jaxpr.jaxpr)
    assert sorted(vmem) == ["gdn_bwd", "gdn_fwd"]
    assert max(vmem["gdn_fwd"]) < 3 * 2 ** 20
    assert max(vmem["gdn_bwd"]) < 5.5 * 2 ** 20


def test_causal_kernels_and_qk_norm_rope_at_256_compile_for_v5e(one_chip,
                                                                as_tpu):
    """The full-attention layer of the linear-attention cell: heads of
    256, 8 query heads a key/value head, 2 key/value heads, 64 of the 256
    dims rotated, 1 row of 16,384 tokens. The shared tile bodies of
    ``ops/sparse_attention.py`` and the fused q/k pass had been tiled,
    compiled and timed at 128 alone: at 256 a Q tile's blocks are twice as
    wide and still fit the kernels' fast memory at 256 x 512."""
    from sparktorch_tpu.ops import qk_norm_rope as fused
    from sparktorch_tpu.ops.rule_attention import (
        Causal, rule_attention_heads_first)

    b, t, d, heads, kv = 1, 16_384, 256, 16, 2
    S = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                            sharding=one_chip)

    def loss(xq, xk, xv, cos, sin, gq, gk):
        q5, k4, v4 = fused.qk_norm_rope(xq, xk, xv, gq, gk, cos, sin, 1e-6,
                                        32, jnp.bfloat16)
        return rule_attention_heads_first(
            q5, k4, v4, Causal(), "causal").astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 5, 6))).lower(
        S(b, t, heads * d), S(b, t, kv * d), S(b, t, kv * d), S(b, t, d),
        S(b, t, d), S(d), S(d)).compile().as_text()
    for kernel in ("causal_attn_fwd", "causal_attn_bwd_dq",
                   "causal_attn_bwd_dkv", "qk_norm_rope_fwd",
                   "qk_norm_rope_bwd"):
        assert _pallas_calls(text, kernel) == 1
    assert _forward_statistics(text, "causal_attn_fwd") == [(1, 2, 8, t)]


@pytest.mark.slow  # 100 s; the two tests above keep its kernels in tier 1
def test_linear_attention_cells_gradient_compiles_for_v5e(one_chip, as_tpu):
    """The whole model of the linear-attention cell at its configuration
    file's sizes and the cell's row (1 row of 16,384 tokens; three Gated
    DeltaNet layers and a full layer at 256, 16 of 512 experts held, 10 a
    token, 18,992 rows of vocabulary): the rule's kernels run once a
    linear layer (the remat keeps its output, block states and each
    chunk's ``T``) and declare the VMEM they declare alone, the causal
    kernels once, and the gradient's scratch leaves room beside 6.79 GB
    of state. The passes around the rule are ``ops/gdn_conv_gate.py``'s
    kernels, forward twice (the remat keeps none of their results) and
    backward once a layer, three calls a pass of the convolution (``q``,
    ``k``, ``v``); the product's cotangent is one buffer that the four
    backward calls of a layer alias, and XLA is left no pass of its own
    over a float32 ``[16384, >= 2048]`` array under their scopes."""
    import json

    from sparktorch_tpu.models.sparse_moe_lm import qwen3_next_lm
    from sparktorch_tpu.utils.losses import resolve_loss

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "configs",
                           "qwen3-next-80b-a3b-ep32.json")) as f:
        module = qwen3_next_lm(**json.load(f)["constructor_kwargs"])
    ids = jnp.zeros((1, 16_384), jnp.float32)
    shapes = jax.eval_shape(
        lambda: module.init(jax.random.key(0), ids))["params"]
    assert sum(a.size for a in jax.tree.leaves(shapes)) == 424_340_544
    S = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    loss_fn = resolve_loss("cross_entropy")

    def loss(p, x, y):
        out, sown = module.apply({"params": p}, x, mutable=["moe_metrics"])
        return loss_fn(out, y).sum(), sown

    traced = jax.jit(jax.grad(loss, has_aux=True)).trace(
        jax.tree.map(S, shapes), S(ids), S(ids))
    compiled = traced.lower().compile()
    text = compiled.as_text()
    assert _pallas_calls(text, "gdn_fwd") == 3
    assert _pallas_calls(text, "gdn_bwd") == 3
    vmem = _declared_vmem(traced.jaxpr.jaxpr)
    assert len(vmem["gdn_fwd"]) == len(vmem["gdn_bwd"]) == 3
    assert max(vmem["gdn_fwd"] + vmem["gdn_bwd"]) < 5.5 * 2 ** 20
    layers, passes = 3, 3
    for kernel, calls in (("gdn_conv_fwd", 2 * passes * layers),
                          ("gdn_conv_bwd", passes * layers),
                          ("gdn_out_norm_fwd", 2 * layers),
                          ("gdn_out_norm_bwd", layers)):
        assert _pallas_calls(text, kernel) == calls, kernel
        # blocks of 8 MiB at the most, held twice, and the halos
        assert len(vmem[kernel]) == calls
        assert max(vmem[kernel]) < 9 * 2 ** 20, kernel
    assert _wide_float32_passes(text, 16_384) == []
    # the product's cotangent: written by ``gdn_out_norm_bwd`` and handed
    # through the three ``gdn_conv_bwd`` calls in place
    whole = r"f32\[1,16384,12288\]"
    assert not re.search(rf"= {whole}\S* (copy|add|concatenate)\(", text)
    assert len(re.findall(rf"= \({whole}\S*, [^=]*custom-call\(", text)) \
        == passes * layers
    for kernel in ("fwd", "bwd_dq", "bwd_dkv"):
        assert _pallas_calls(text, f"causal_attn_{kernel}") == 1
    assert _forward_statistics(text, "causal_attn_fwd") == [(1, 2, 8, 16_384)]
    assert _pallas_calls(text, "qk_norm_rope_fwd") == 2
    assert _pallas_calls(text, "qk_norm_rope_bwd") == 1
    assert _pallas_calls(text, "fused_ce_fwd") == 1
    _assert_sums_back_are_the_kernels(text, module)
    # 4.86 GB read at PR 42, 5.26 at PR 43, 5.74 at PR 44 (the product's
    # whole cotangent is live across ``gdn_bwd``)
    assert compiled.memory_analysis().temp_size_in_bytes < 6_000_000_000


def test_short_conv_pass_and_heads_of_64_compile_for_v5e(one_chip, as_tpu):
    """The two mixers of the convolution cell at its step (4 rows of
    4,096 tokens): ``sconv_fwd`` / ``sconv_bwd`` on the float32 product
    ``[4, 4096, 3 x 2048]`` (blocks of 512 lanes, the halo's sublane
    rolls, the backward grid's last axis over the product's three column
    blocks) and, for 32 query on 8 key/value heads of 64, two heads to a
    register, ``qk_norm_rope`` and the ``causal`` kernels (masked sums a
    head, lane rolls by 64, the statistics a row a head): they lower in
    Mosaic, fit its VMEM, and nothing 64 wide reaches HBM."""
    from sparktorch_tpu.ops import qk_norm_rope as fused
    from sparktorch_tpu.ops import short_conv_gate as sconv
    from sparktorch_tpu.ops.rule_attention import (
        Causal, rule_attention_heads_first)

    b, t, d, heads, kv, hd = 4, 4_096, 2_048, 32, 8, 64
    S = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                            sharding=one_chip)

    def conv_loss(bcu, taps):
        y = sconv.short_conv_gate(bcu, taps, jnp.bfloat16)
        return jnp.sum(jnp.square(y.astype(jnp.float32)))

    traced = jax.jit(jax.grad(conv_loss, argnums=(0, 1))).trace(
        S(b, t, 3 * d), S(sconv.TAPS, d))
    text = traced.lower().compile().as_text()
    assert _pallas_calls(text, "sconv_fwd") == 1
    assert _pallas_calls(text, "sconv_bwd") == 1
    # the product's cotangent leaves the kernel whole: no pass of XLA's
    # puts three blocks together
    assert not re.search(rf"= f32\[{b},{t},{3 * d}\]\S* "
                         rf"(copy|add|concatenate|pad)\(", text)
    vmem = _declared_vmem(traced.jaxpr.jaxpr)
    assert max(vmem["sconv_fwd"] + vmem["sconv_bwd"]) < 10 * 2 ** 20

    def attn_loss(xq, xk, xv, cos, sin, gq, gk):
        q5, k4, v4 = fused.qk_norm_rope(xq, xk, xv, gq, gk, cos, sin, 1e-5,
                                        32, jnp.bfloat16)
        return rule_attention_heads_first(
            q5, k4, v4, Causal(), "causal", hd).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(attn_loss, argnums=(0, 1, 2, 5, 6))).lower(
        S(b, t, heads * hd), S(b, t, kv * hd), S(b, t, kv * hd),
        S(b, t, 128), S(b, t, 128), S(hd), S(hd)).compile().as_text()
    for kernel in ("causal_attn_fwd", "causal_attn_bwd_dq",
                   "causal_attn_bwd_dkv", "qk_norm_rope_fwd",
                   "qk_norm_rope_bwd"):
        assert _pallas_calls(text, kernel) == 1
    # a row of statistics a head: 4 pairs of key/value heads, 8 heads each
    assert _forward_statistics(text, "causal_attn_fwd") == [(b, 4, 8, t)]
    # q, k and v by registers: no result in HBM by heads of 64 (rank 4 or more)
    assert not re.search(r"= \w+\[4,\d+,\d+,[\d,]*64\]",
                         text[text.index("\nENTRY "):])


@pytest.mark.slow  # 75 s; the test above keeps its kernels in tier 1
def test_convolution_cells_gradient_compiles_for_v5e(one_chip, as_tpu):
    """The whole model of the convolution cell at its configuration
    file's sizes and the cell's step (4 rows of 4,096 tokens; a dense
    convolution layer, an attention layer at 32 / 8 heads of 64 and
    three convolution layers with 8 of 32 experts held, the head tied to
    16,384 rows of embedding): ``sconv_fwd`` twice a convolution layer
    (the remat keeps nothing of it) and ``sconv_bwd`` once, the causal
    kernels once, no dense attention (no ``[.., 4096, 4096]`` array),
    and the gradient's scratch leaves room beside 8.13 GB of state."""
    import json

    from sparktorch_tpu.models.sparse_moe_lm import lfm2_moe_lm
    from sparktorch_tpu.utils.losses import resolve_loss

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "configs",
                           "lfm2-8b-a1b-ep4.json")) as f:
        module = lfm2_moe_lm(**json.load(f)["constructor_kwargs"])
    ids = jnp.zeros((4, 4_096), jnp.float32)
    shapes = jax.eval_shape(
        lambda: module.init(jax.random.key(0), ids))["params"]
    assert sum(a.size for a in jax.tree.leaves(shapes)) == 507_820_288
    S = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    loss_fn = resolve_loss("cross_entropy")

    def loss(p, x, y):
        out, sown = module.apply({"params": p}, x, mutable=["moe_metrics"])
        return loss_fn(out, y).sum(), sown

    compiled = jax.jit(jax.grad(loss, has_aux=True)).lower(
        jax.tree.map(S, shapes), S(ids), S(ids)).compile()
    text = compiled.as_text()
    layers = 4
    assert _pallas_calls(text, "sconv_fwd") == 2 * layers
    assert _pallas_calls(text, "sconv_bwd") == layers
    for kernel in ("fwd", "bwd_dq", "bwd_dkv"):
        assert _pallas_calls(text, f"causal_attn_{kernel}") == 1
    assert _forward_statistics(text, "causal_attn_fwd") == [(4, 4, 8, 4_096)]
    assert _pallas_calls(text, "qk_norm_rope_fwd") == 2
    assert _pallas_calls(text, "qk_norm_rope_bwd") == 1
    assert _pallas_calls(text, "fused_ce_fwd") == 1
    assert not re.search(r"\[[\d,]*4096,4096\]", text)   # no dense scores
    # no activation by heads of 64 (a weight's gradient may lie so)
    assert not re.search(r"= \w+\[4,\d+,\d+,[\d,]*64\]",
                         text[text.index("\nENTRY "):])
    _assert_sums_back_are_the_kernels(text, module)
    print(compiled.memory_analysis())
    assert compiled.memory_analysis().temp_size_in_bytes < 6_000_000_000


@pytest.mark.parametrize("rows,seq,calls", [(32, 512, 1), (128, 128, 0)])
def test_encoder_layer_gradient_picks_its_attention_for_v5e(
        one_chip, as_tpu, monkeypatch, rows, seq, calls):
    """One layer of BERT-base's classifier under the default
    ``attn_impl='auto'``, its whole gradient through the TPU compiler at
    the two benchmark shapes of 16,384 tokens: at 32 rows of 512 the
    layer's attention is one ``flash_fwd``, one ``flash_bwd_dq`` and one
    ``flash_bwd_dkv`` and no array of all (query, key) pairs is left in
    the program; at 128 rows of 128 it is XLA's dense fusions and no
    custom call."""
    from sparktorch_tpu.models.transformer import bert_base
    from sparktorch_tpu.utils.losses import resolve_loss

    monkeypatch.setattr(jax, "device_count", lambda: 1)  # a one-chip host
    module = bert_base(n_layers=1, max_len=seq)
    ids = jnp.zeros((rows, seq), jnp.float32)
    labels = jnp.zeros((rows,), jnp.int32)
    shapes = jax.eval_shape(
        lambda: module.init(jax.random.key(0), ids))["params"]
    S = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    loss_fn = resolve_loss("cross_entropy")
    text = jax.jit(jax.grad(lambda p, x, y: loss_fn(
        module.apply({"params": p}, x), y).sum())).lower(
            jax.tree.map(S, shapes), S(ids), S(labels)).compile().as_text()
    counts = kernel_call_counts(text)
    assert counts == {"flash_fwd": calls, "flash_bwd_dq": calls,
                      "flash_bwd_dkv": calls, "fused_ce_fwd": 0,
                      "fused_ce_bwd": 0}, counts
    assert module.train_gauges((seq,)) == {
        "train.attention.kernel_layers": calls}
    all_pairs = f"[{rows},12,{seq},{seq}]"
    assert (all_pairs in text) is (calls == 0)


def _encoder_over_2x2(topo, **overrides):
    """One BERT-base layer at rows of 512, its parameters' shapes, and a
    dp=4 mesh of the described chips."""
    from sparktorch_tpu.models.transformer import bert_base
    from sparktorch_tpu.parallel.mesh import MeshConfig, build_mesh

    module = bert_base(n_layers=1, max_len=512, **overrides)
    shapes = jax.eval_shape(lambda: module.init(
        jax.random.key(0), jnp.zeros((1, 512), jnp.float32)))["params"]
    return module, shapes, build_mesh(MeshConfig(dp=4), topo.devices)


def test_predictor_over_a_mesh_stays_dense_for_v5e_2x2(topo, as_tpu,
                                                       monkeypatch):
    """``BatchPredictor(mesh=...)``'s forward at rows of 512 on a 2x2
    host: the ``jit``'s own shardings hand the program to the
    partitioner with no mesh in sight of the trace, so the rule keeps a
    process of four devices dense and the program compiles with no
    custom call. Named, the kernel is what the TPU compiler refuses
    there: what the default must never ask for."""
    from sparktorch_tpu.inference import _jit_forward

    monkeypatch.setattr(jax, "device_count", lambda: len(topo.devices))
    x = jax.ShapeDtypeStruct((16, 512), jnp.float32)
    module, shapes, mesh = _encoder_over_2x2(topo)
    text = _jit_forward(module, mesh, shapes, {}).lower(
        shapes, {}, x).compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' not in text
    assert "all-gather" not in text and "all-reduce" not in text
    named, _, _ = _encoder_over_2x2(topo, attn_impl="flash")
    with pytest.raises(Exception, match="cannot be automatically partitioned"):
        _jit_forward(named, mesh, shapes, {}).lower(shapes, {}, x).compile()


def test_train_init_over_dp4_compiles_for_v5e_2x2(topo, as_tpu, monkeypatch):
    """The sync trainer's init for BERT-base at rows of 512 over dp=4
    (a ``jit`` with ``out_shardings`` under the legacy ``with mesh:``,
    no mesh in sight of the trace either): no kernel is traced into it,
    and the step's own gauge, asked inside the step's ``shard_map``,
    reads the kernels."""
    from sparktorch_tpu.obs import Telemetry
    from sparktorch_tpu.train.sync import _jit_init, _note_model_gauges
    from sparktorch_tpu.utils.serde import ModelSpec

    monkeypatch.setattr(jax, "device_count", lambda: len(topo.devices))
    module, _, mesh = _encoder_over_2x2(topo)
    spec = ModelSpec(module=module, loss="cross_entropy", optimizer="adam",
                     optimizer_params={"lr": 2e-5}, input_shape=(512,))
    with mesh:
        lowered = _jit_init(spec, mesh, jax.random.key(0),
                            jnp.zeros((1, 512), jnp.float32),
                            spec.make_optimizer()).lower()
    assert "tpu_custom_call" not in lowered.as_text()
    lowered.compile()
    tele = Telemetry(run_id="init-dp4")
    _note_model_gauges(tele, module, (512,), mesh)
    assert tele.gauge_value("train.attention.kernel_layers") == 1


def test_dp4_step_at_long_rows_holds_the_kernels_for_v5e_2x2(topo, as_tpu):
    """The sync trainer's dp=4 step at 8 rows of 512 a chip: inside the
    step's ``shard_map`` every axis is Manual, each chip's program holds
    its own three kernels a layer, and the TPU compiler takes it."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from sparktorch_tpu.train import step as step_mod
    from sparktorch_tpu.utils.data import DataBatch
    from sparktorch_tpu.utils.serde import ModelSpec

    module, _, mesh = _encoder_over_2x2(topo)
    spec = ModelSpec(module=module, loss="cross_entropy", optimizer="adam",
                     optimizer_params={"lr": 2e-5}, input_shape=(512,))
    tx = spec.make_optimizer()
    rep = NamedSharding(mesh, P())
    rows = NamedSharding(mesh, P(("dp", "fsdp")))
    state = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rep),
        jax.eval_shape(lambda: step_mod.create_train_state(
            spec, jax.random.key(0),
            sample_x=jnp.zeros((1, 512), jnp.float32), tx=tx)))
    batch = DataBatch(
        x=jax.ShapeDtypeStruct((32, 512), jnp.float32, sharding=rows),
        y=jax.ShapeDtypeStruct((32,), jnp.float32, sharding=rows),
        w=jax.ShapeDtypeStruct((32,), jnp.float32, sharding=rows))
    step = step_mod.make_train_step(module.apply, spec.loss_fn(), tx, mesh)
    counts = kernel_call_counts(step.lower(state, batch).compile().as_text())
    assert counts == {"flash_fwd": 1, "flash_bwd_dq": 1, "flash_bwd_dkv": 1,
                      "fused_ce_fwd": 0, "fused_ce_bwd": 0}, counts


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


# -- the sync step's gradient all-reduce on a 2x2 host ------------------------


def _computations(text):
    """``{name: [instruction lines]}`` of a compiled module's text."""
    out, cur = {}, None
    for line in text.split("\n"):
        if line.endswith("{") and not line.startswith(" "):
            cur = out.setdefault(line.split(" (")[0].split()[-1], [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line)
    return out


@pytest.mark.parametrize("builder", ["step", "epoch", "fused"])
def test_dp4_step_runs_its_allreduces_under_the_backward_pass(
        topo, as_tpu, builder):
    """The compiled dp=4 step of a small transformer, from each of the
    three builders (the loops' body is read): the all-reduce of
    every gradient of 1 MiB or more is an asynchronous collective
    fusion, the first of them starts before the backward pass's last
    matrix product, and one synchronous all-reduce is left (the small
    gradients and the loss's sums, merged). Without the compiler
    options of ``train/step.py`` the same lowered program compiles to
    no asynchronous all-reduce at all. Over one chip the step is a
    plain ``jax.jit``."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from sparktorch_tpu.models import SequenceClassifier, tiny_transformer
    from sparktorch_tpu.train import step as step_mod
    from sparktorch_tpu.utils.data import DataBatch
    from sparktorch_tpu.utils.serde import ModelSpec

    cfg = tiny_transformer(vocab_size=8192, d_model=512, n_heads=8,
                           n_layers=2, d_ff=2048, max_len=64, n_classes=2)
    spec = ModelSpec(module=SequenceClassifier(cfg), loss="cross_entropy",
                     optimizer="adam", optimizer_params={"lr": 1e-3},
                     input_shape=(64,))
    tx = spec.make_optimizer()
    apply_fn, loss_fn = spec.make_module().apply, spec.loss_fn()
    one = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("dp", "fsdp"))
    assert not isinstance(step_mod.make_train_step(apply_fn, loss_fn, tx, one),
                          step_mod._CompiledWithOptions)

    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("dp", "fsdp"))
    rep = NamedSharding(mesh, P())
    rows = NamedSharding(mesh, P(("dp", "fsdp")))
    shapes = jax.eval_shape(lambda: step_mod.create_train_state(
        spec, jax.random.key(0), sample_x=jnp.zeros((1, 64), jnp.float32),
        tx=tx))
    state = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rep), shapes)
    batch = DataBatch(
        x=jax.ShapeDtypeStruct((32, 64), jnp.float32, sharding=rows),
        y=jax.ShapeDtypeStruct((32,), jnp.float32, sharding=rows),
        w=jax.ShapeDtypeStruct((32,), jnp.float32, sharding=rows))
    step = step_mod.make_train_step(apply_fn, loss_fn, tx, mesh)
    def is_start(line):
        return line.lstrip().startswith("%async-collective-start")

    plain = step._jitted.lower(state, batch).compile().as_text()
    assert not any(is_start(line) for line in plain.split("\n"))
    comps = _computations(step.lower(state, batch).compile().as_text())
    body = next(lines for lines in comps.values()
                if any(is_start(line) for line in lines))
    has_matmul = {name for name, lines in comps.items()
                  if any(" convolution(" in line for line in lines)}
    starts = [i for i, line in enumerate(body) if is_start(line)]
    def is_matmul(line):
        called = re.search(r"calls=(%[\w.\-]+)", line)
        return " convolution(" in line or (
            called is not None and called.group(1) in has_matmul)

    backward_matmuls = [i for i, line in enumerate(body)
                        if "transpose(jvp(forward_loss))" in line
                        and is_matmul(line)]
    n_large = sum(1 for leaf in jax.tree.leaves(shapes.params)
                  if leaf.size * leaf.dtype.itemsize >= 2**20)
    assert len(starts) == n_large > 1
    assert sum(1 for line in body if " all-reduce(" in line) == 1
    assert backward_matmuls and starts[0] < backward_matmuls[-1]


def test_ep4_step_of_mellum2_compiles_for_v5e_2x2(topo, as_tpu):
    """The sync trainer's step over dp=1 x ep=4 for one window layer of
    Mellum2 at its published widths (rows of 2,048 tokens): the carry
    placed leaf by leaf, the expert exchange's all-gather and
    reduce-scatter and the members' own kernels in the compiled text; the
    options are the dp options less the one the TPU compiler refuses the
    cell's step with (``train/step.py`` ``_KLOOP_OPTION``)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from sparktorch_tpu.models.sparse_moe_lm import mellum2_lm
    from sparktorch_tpu.parallel.mesh import MeshConfig, build_mesh
    from sparktorch_tpu.train import step as step_mod
    from sparktorch_tpu.utils.data import DataBatch
    from sparktorch_tpu.utils.serde import ModelSpec

    seq = 2_048
    mesh = build_mesh(MeshConfig(dp=1, ep=4), topo.devices)
    rows_axes, cut = step_mod.ep_rows(mesh)
    options = step_mod._dp_compiler_options(mesh, rows_axes)
    assert set(step_mod._TPU_DP_OPTIONS) - set(options) == {
        step_mod._KLOOP_OPTION}
    assert step_mod._dp_compiler_options(
        build_mesh(MeshConfig(dp=4), topo.devices),
        ("dp", "fsdp")) == step_mod._TPU_DP_OPTIONS

    spec = ModelSpec(module=mellum2_lm(n_layers=1, vocab_size=24_576),
                     loss="cross_entropy", optimizer="adam",
                     optimizer_params={"lr": 1e-5}, input_shape=(seq,))
    tx = spec.make_optimizer()
    shapes = jax.eval_shape(lambda: step_mod.create_train_state(
        spec, jax.random.key(0), sample_x=jnp.zeros((1, seq), jnp.float32),
        tx=tx))
    state = jax.tree.map(
        lambda s, p: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                          sharding=NamedSharding(mesh, p)),
        shapes, step_mod.cut_specs(shapes, cut))
    assert state.params["layer_0"]["moe"]["w_up"].sharding.spec == P("ep")
    assert state.opt_state[0].mu["layer_0"]["moe"]["w_up"].sharding.spec \
        == P("ep")
    assert state.params["layer_0"]["moe"]["router"].sharding.spec == P()
    rows = NamedSharding(mesh, P(rows_axes))
    batch = DataBatch(*(jax.ShapeDtypeStruct(s, jnp.float32, sharding=rows)
                        for s in ((8, seq), (8, seq), (8,))))
    step = step_mod.make_train_step(spec.make_module().apply, spec.loss_fn(),
                                    tx, mesh, mini_batch=1)
    assert isinstance(step, step_mod._CompiledWithOptions)
    text = step.lower(state, batch).compile().as_text()
    assert text.count("tpu_custom_call") >= 10
    # The layer's rows' sums forward and their cotangent backward go
    # back by the OPCODE ``reduce-scatter`` under the exchange's scope,
    # as ``moe_exchange_ms``'s reader knows them, their tokens minor: a
    # tokens-major operand the compiler rewrites as an all-reduce and a
    # slice in a fusion ``all-reduce-scatter`` with no ``op_name`` (PR
    # 51; the gates' ``[tokens, 8]`` take that form at 8,192 tokens) ...
    scatters = [line.split(" reduce-scatter(")[0] for line in text.splitlines()
                if " reduce-scatter(" in line and "moe_exchange/" in line]
    scatters = [s for s in scatters if ",2304]" in s]
    assert len(scatters) == 2 and all("{0,1:" in s for s in scatters)
    assert not re.search(r"%all-reduce-scatter\S* \(\S+ \w+\[\d+,2304\]", text)
    # ... and the gathered rows reach ``moe_fetch_source`` row-major as
    # the all-gather left them: no transposing copy of them between
    gathers = [line.split(" all-gather(")[0] for line in text.splitlines()
               if " all-gather(" in line and "moe_exchange/" in line
               and ",2304]" in line.split(" all-gather(")[0]]
    assert gathers and all("{1,0:" in g or "{2,1,0:" in g for g in gathers)
    assert not re.search(r"= \w+\[%d,2304\]\S* copy\(" % (4 * seq), text)

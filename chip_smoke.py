"""Chip smoke: the system's main path, once, on the TPU.

One process, run from the root of the checkout, through the entry
points a user calls; data is made from ``--seed`` and nothing is
fetched. It fails before any phase unless JAX's first device is a TPU
— it never sets a platform itself.

With no arguments (one chip), eight phases:

- ``trainer_sync``   the README flow at BERT-base width:
  ``serialize_torch_obj(bert_base())`` -> ``SparkTorch(mode=
  "synchronous").fit(df)`` (-> ``train_distributed``), seq 128,
  mini-batch 128; loss finite and lower at the end than at the start.
- ``predictor``      ``model.transform(df)`` (-> ``BatchPredictor``):
  served parameters live on a TPU device and the predictions equal a
  plain ``module.apply`` on the same parameters.
- ``kernels``        ``CausalLM(attn_impl="flash")`` + the
  ``cross_entropy`` loss at seq 8192 through ``make_train_epoch`` as
  ``train_distributed`` drives it; the COMPILED step's text must hold
  each of the five Pallas kernels as a ``tpu_custom_call``; at seq
  2048 flash-vs-dense and fused-vs-dense losses and gradients agree.
- ``qk_norm_rope``   the decoder's fused q/k norm, rotary step, cast
  and turn heads first (``ops/qk_norm_rope.py``) against its plain
  spelling (``rms_norm`` + ``_rotate`` + cast + ``heads_first``) at one
  window layer's shape (2 rows of 8,192 tokens, 64 query heads on 8
  key/value heads of 128, all dims rotated) and at a full layer's
  rotary (48 heads, 64 of the 128 dims rotated, the attention factor)
  on shorter rows: values and the five gradients. Mosaic's lane rolls
  and the blocks' index maps are checked here, where interpret mode
  cannot.
- ``latent_attention`` latent attention's two ops at JoyAI-LLM-Flash's
  heads (32 of 192 / 128): ``ops/latent_rope.py`` (the rotary step on 64
  of 192 dims with the weights' columns de-interleaved, the cast, the
  turn heads first, the shared rotary key written a head) against the
  plain interleaved rotation, scores, values and the three products'
  gradients; and ``ops/latent_attention.py``'s kernels on what it wrote
  against dense causal attention at the true widths, output and the
  three gradients. The 256-lane padding, the lane rolls and the index
  maps are checked here, where interpret mode cannot.
- ``gated_delta``    ``ops/gated_delta_rule.py`` (the chunked gated
  delta rule, chunks of 64) at Qwen3-Next's width (16 key and 32 value
  heads of 128) on 2,048 tokens against the token-by-token recurrence
  in float32: the output and all five gradients (``q``, ``k``, ``v``,
  the log-decays, ``beta``). The triangular inverse's products, the
  identity products that turn a token's scalars and the state carried
  across the grid's sequential axis are checked here, where interpret
  mode cannot.
- ``gdn_conv_gate`` ``ops/gdn_conv_gate.py`` (what a Gated DeltaNet
  layer does around the rule: the causal convolution, SiLU, the q/k L2
  norms and the cast out of the float32 product, and the gated norm a
  head) at Qwen3-Next's width on 2,048 tokens against the plain
  spelling it replaced: the four results and the gradients of the
  product, the taps, the gain and ``o``. Mosaic's sublane rolls across
  a tile's halo, the index maps that read a part's columns out of the
  product and the one cotangent buffer the backward kernels alias are
  checked here, where interpret mode cannot.
- ``short_conv_gate`` ``ops/short_conv_gate.py`` (LFM2's gated short
  convolution between its two products: the two gates and the three
  taps out of the float32 product, one pass each way) at the cell's
  step, 4 rows of 4,096 tokens of 2,048 channels, against its plain
  spelling: the value and the gradients of the product and the taps,
  forward and gradient timed apart. The halo's sublane rolls, the three
  column blocks read through the index map and the backward grid's last
  axis that writes the product's cotangent block by block are checked
  here, where interpret mode cannot.
- ``causal_heads_64`` heads of 64, two to a register, at the same
  cell's attention layer (32 query on 8 key/value heads, 4 rows of
  4,096): ``ops/qk_norm_rope.py`` against its plain spelling, and the
  ``causal`` kernels of ``ops/rule_attention.py`` on its results
  against dense float32 attention on the same bfloat16 operands, the
  output and three gradients; the compiled text holds the three
  kernels. The masked sums a head, the lane rolls by 64 and the sublane
  slices of the running output are checked here, where interpret mode
  cannot.
- ``grouped_mlp``    ``ops/grouped_mlp.py`` through the expert layer's
  sum (``models.sparse_moe_lm.held_experts_sum``) at LFM2's layer: 16,384
  tokens, 4 of 32 experts each, 8 held (chunks of 32,768 sorted pairs,
  tiles of 512 rows, widths 2,048 / 1,792), against the same sum spelled
  with ``jax.lax.ragged_dot`` on whole arrays, rows past the held pairs
  zeroed: the output and all five gradients, forward and gradient timed
  apart. The output tile that stays in VMEM over a shared tile's visits
  and a visit's column blocks, the blocks no grid step writes, the
  carried sums the weight-gradient kernels add to in place and the
  transposed products are checked here, where interpret mode cannot.
  Then what a trip does around its products, each alone on the first
  chunk in a loop of twenty calls: ``fetch_rows`` of the chunk's rows
  (one source, as the forward pass calls it, and ``x`` with ``d_out``,
  as the backward pass does) against XLA's gathers of the whole chunk on
  the same operands, the fetched rows EQUAL to ``x[token]`` on every
  tile with a held pair and timed with half the chunk live (PR 51);
  ``fetch_source`` of ``x``; and the two sums back (PR 48).
- ``grouped_mlp_ep_member`` the same check at what one member of
  Mellum2's four-chip expert-parallel group computes in a layer: 32,768
  gathered rows of 2,304 (18 sublanes a row, 24 in the fetch's source),
  8 of 64 experts each, 16 held, chunks of 131,072 (PR 49).
- ``trainer_hogwild`` ``SparkTorch(mode="hogwild")`` (->
  ``train_async``), ResNet-18 at CIFAR shapes, two local workers: the
  server's version advances, loss finite.

With ``--chips 4`` only the multi-chip path and what it is compared
with: BERT-base ``train_distributed`` on dp=4 against the same run on
one chip, the same at rows of 512 tokens (the default attention is the
fused kernels there) against ``attn_impl="dense"`` and through the
predictor over the mesh, one ``make_sharded_train_step`` step on
dp=2 x fsdp=2, one MoE step at ep=4 whose compiled text contains
``all-to-all``, and (``expert_exchange``) one expert exchange of the
decoder's ``HeldExperts`` at Mellum2's widths over ep=4, 16 of 64
experts a chip, against a token's sum of its eight experts on one chip
that holds all 64. ``--only a,b`` runs the named phases alone.

Every phase is a function that raises on failure; a failure stops the
run (later phases print as "not run"), the exit code is non-zero and
no result line is printed. The last line of a passing run is the one
JSON object the driver reads.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import re
import sys
import time
import traceback

import numpy as np

# The five Pallas kernels, by the ``name=`` of their ``pallas_call``.
KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
           "fused_ce_fwd", "fused_ce_bwd")

# Stated tolerances. The models compute in bf16 (eps 7.8e-3), so two
# programs that order their sums differently agree to a few eps; a
# wrong mask, scale or block index is off by O(1).
TOL_PREDICT_ABS = 2e-2       # predictor logits vs plain apply (logits O(1))
TOL_PARITY_LOSS_REL = 1e-3   # kernel-vs-dense loss at seq 2048
TOL_PARITY_GRAD_REL = 2e-2   # ||g_kernel - g_dense|| / ||g_dense||
TOL_DP_LOSS_REL = 5e-3       # dp=4 vs one chip, per-step loss
# the fused q/k pass against its plain spelling, ||a - b|| / ||b||: both
# compute in float32 and round once, so values differ by a bf16 step on
# a rounding boundary here and there, gradients by float32's order of sums
TOL_FUSED_VALUE_REL = 2e-3
TOL_FUSED_GRAD_REL = 1e-4
# latent attention's kernels (bf16 operands, float32 sums) against dense
# float32 attention on the same bf16 operands
TOL_LATENT_OUT_REL = 1e-2
TOL_LATENT_GRAD_REL = 2e-2
# the grouped kernels against ``ragged_dot`` on the same bf16 operands:
# both sum in float32, in another order, and round hidden rows once
TOL_GROUPED_REL = 5e-3
# the expert layer over four chips against one chip that holds every
# expert: the same kernels on the same bf16 rows, a token's eight float32
# sums added chip by chip and not expert by expert
TOL_EXCHANGE_REL = 1e-3
# the chunked gated delta rule (bf16 operands, float32 sums and state)
# against the token-by-token recurrence in float32 on the same bf16
# operands: the chunk's T, W and V' enter their products rounded to bf16
TOL_GDN_OUT_REL = 1e-2
TOL_GDN_GRAD_REL = 2e-2


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Shapes of one run. The defaults are the real ones; a rehearsal
    on the CPU passes small ones (it imports this module — there is no
    option of the script that shrinks a phase)."""

    # trainer_sync / predictor / dp4: bert_base() as published
    bert_overrides: tuple = ()
    bert_vocab: int = 30522
    bert_seq: int = 128
    bert_rows: int = 512
    bert_mini_batch: int = 128
    bert_iters: int = 64
    predict_rows: int = 300
    # kernels: a long-context language model's widths
    lm_vocab: int = 32768
    lm_d_model: int = 512
    lm_heads: int = 8
    lm_layers: int = 4
    lm_d_ff: int = 2048
    lm_seq: int = 8192
    lm_batch: int = 2
    lm_steps: int = 4
    parity_seq: int = 2048
    # qk_norm_rope: (rows, tokens, query heads, rotated pairs, attention
    # factor) of Laguna-XS.2's window layer at the cell's rows, and of
    # its full layer on shorter rows; 8 key/value heads of 128
    fused_cases: tuple = ((2, 8192, 64, 64, 1.0),
                          (1, 2048, 48, 32, 1.4158883083359672))
    fused_kv_heads: int = 8
    fused_head_dim: int = 128
    # latent_attention: (rows, tokens, heads) of nope 128 + rope 64 over
    # values of 128
    latent_case: tuple = (1, 2048, 32)
    # gated_delta and gdn_conv_gate: (rows, tokens, key heads, value
    # heads) of 128
    gdn_case: tuple = (1, 2048, 16, 32)
    # short_conv_gate and causal_heads_64: (rows, tokens, channels) and
    # (rows, tokens, query heads, key/value heads) of 64 of the
    # convolution / attention cell's step
    sconv_case: tuple = (4, 4096, 2048)
    # tokens, experts a token, routed experts, held, d, f
    grouped_case: tuple = (16_384, 4, 32, 8, 2_048, 1_792)
    # grouped_case's fields for one member of Mellum2's four after the
    # exchange's way in
    exchange_member_case: tuple = (32_768, 8, 64, 16, 2_304, 896)
    # rows a chip, d, routed experts, experts a token, f of Mellum2's
    # expert layer over four chips
    exchange_case: tuple = (8_192, 2_304, 64, 8, 896)
    heads64_case: tuple = (4, 4096, 32, 8)
    # trainer_hogwild: ResNet-18 at CIFAR-10 shapes
    hog_rows: int = 1024
    hog_mini_batch: int = 256
    hog_iters: int = 16
    hog_push_every: int = 4
    hog_workers: int = 2
    # four chips
    dp_rows: int = 128
    dp_iters: int = 4
    long_seq: int = 512
    long_rows: int = 32
    moe_experts: int = 8
    moe_seq: int = 1024
    moe_batch: int = 8


def kernel_call_counts(hlo_text: str) -> dict:
    """``{kernel name: count}`` of the ``tpu_custom_call``s in a
    compiled program's text, by the ``pallas_call`` name that jax puts
    in the call's ``op_name`` metadata."""
    counts = {k: 0 for k in KERNELS}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = re.search(r'op_name="([^"]*)"', line)
        for k in KERNELS:
            if m and re.search(rf"\b{k}\b", m.group(1)):
                counts[k] += 1
    return counts


def _bert_rows(rng, n, seq, vocab):
    """Token-id rows with a learnable label: class 1 draws its tokens
    from the upper half of the vocabulary, class 0 from the lower."""
    y = rng.integers(0, 2, (n,))
    lo = np.where(y == 1, vocab // 2, 0)[:, None]
    ids = lo + rng.integers(0, vocab // 2, (n, seq))
    return ids.astype(np.float32), y.astype(np.float32)


def _bert_obj(sz: Sizes, lr: float = 1e-4):
    from sparktorch_tpu import serialize_torch_obj
    from sparktorch_tpu.models.transformer import bert_base

    return serialize_torch_obj(
        bert_base(**dict(sz.bert_overrides)), criterion="cross_entropy",
        optimizer="adam", optimizer_params={"lr": lr},
        input_shape=(sz.bert_seq,),
    )


def _peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _all_on_platform(tree, platform: str) -> bool:
    import jax

    leaves = jax.tree.leaves(tree)
    return bool(leaves) and all(
        isinstance(a, jax.Array)
        and all(d.platform == platform for d in a.devices())
        for a in leaves
    )


# ---------------------------------------------------------------------------
# One chip
# ---------------------------------------------------------------------------


def phase_trainer_sync(sz: Sizes, seed: int, ctx: dict) -> str:
    from sparktorch_tpu import SparkTorch

    rng = np.random.default_rng(seed)
    x, y = _bert_rows(rng, sz.bert_rows, sz.bert_seq, sz.bert_vocab)
    df = {"features": list(x), "label": y}
    est = SparkTorch(
        inputCol="features", labelCol="label", predictionCol="predictions",
        torchObj=_bert_obj(sz), iters=sz.bert_iters,
        miniBatch=sz.bert_mini_batch, mode="synchronous",
        useVectorOut=True,
    )
    t0 = time.perf_counter()
    model = est.fit(df)
    wall = time.perf_counter() - t0
    recs = est._last_metrics
    losses = [r["loss"] for r in recs]
    if len(losses) != sz.bert_iters:
        raise AssertionError(f"{len(losses)} records, wanted {sz.bert_iters}")
    if not np.all(np.isfinite(losses)):
        raise AssertionError(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses[0]} -> {losses[-1]}")
    # The first fused chunk holds the compile; later chunks are steady.
    first = recs[0]["step_time_s"]
    steady = sorted(r["step_time_s"] for r in recs
                    if r["step_time_s"] != first)
    ctx["model"], ctx["bert_x"] = model, x
    return (f"fit_s={wall:.2f} iters={len(losses)} "
            f"first_chunk_step_s={first:.4f} "
            f"steady_step_s={steady[len(steady) // 2] if steady else None} "
            f"loss_first={losses[0]:.4f} loss_last={losses[-1]:.4f}")


def phase_predictor(sz: Sizes, seed: int, ctx: dict) -> str:
    import jax

    model = ctx["model"]
    x = ctx["bert_x"][: sz.predict_rows]
    t0 = time.perf_counter()
    out = model.transform({"features": list(x)})
    wall = time.perf_counter() - t0
    preds = np.stack([np.asarray(v) for v in out["predictions"]])
    served = model._predictor()._params
    if not _all_on_platform(served, jax.devices()[0].platform):
        raise AssertionError("served parameters are not on the device")
    bundle = model.getModel()
    ref = np.asarray(jax.jit(bundle.module.apply)(
        {"params": bundle.params, **(bundle.model_state or {})}, x))
    if preds.shape != ref.shape or not np.all(np.isfinite(preds)):
        raise AssertionError(f"predictions {preds.shape} vs {ref.shape}")
    err = float(np.max(np.abs(preds - ref)))
    if err > TOL_PREDICT_ABS:
        raise AssertionError(f"predictor differs from module.apply by {err}")
    agree = float(np.mean(preds.argmax(-1) == ref.argmax(-1)))
    return (f"transform_s={wall:.2f} rows={len(preds)} "
            f"max_abs_err={err:.2e} (tol {TOL_PREDICT_ABS}) "
            f"argmax_agree={agree:.4f} params_on={jax.devices()[0].platform}")


def _lm_spec(sz: Sizes, attn: str, seq: int, loss: str = "cross_entropy"):
    from sparktorch_tpu.models import CausalLM
    from sparktorch_tpu.models.transformer import TransformerConfig
    from sparktorch_tpu.utils.serde import ModelSpec

    cfg = TransformerConfig(
        vocab_size=sz.lm_vocab, d_model=sz.lm_d_model, n_heads=sz.lm_heads,
        n_layers=sz.lm_layers, d_ff=sz.lm_d_ff, max_len=seq,
        attn_impl=attn, remat=True,
    )
    return ModelSpec(module=CausalLM(cfg), loss=loss, optimizer="adamw",
                     optimizer_params={"lr": 3e-4}, input_shape=(seq,))


def _kernel_parity(sz: Sizes, seed: int) -> str:
    """Loss and gradients of the same parameters at ``parity_seq``:
    flash attention vs dense attention (dense loss on both), and the
    fused loss vs the dense loss (dense attention on both)."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(seed + 1)
    ids = rng.integers(0, sz.lm_vocab,
                       (sz.lm_batch, sz.parity_seq + 1)).astype(np.int32)
    x, y = jnp.asarray(ids[:, :-1]), jnp.asarray(ids[:, 1:])
    base = _lm_spec(sz, "dense", sz.parity_seq, "cross_entropy_dense")
    params = jax.jit(base.make_module().init)(jax.random.key(seed), x)["params"]

    def loss_and_grad(attn, loss):
        spec = _lm_spec(sz, attn, sz.parity_seq, loss)
        module, loss_fn = spec.make_module(), spec.loss_fn()
        fn = jax.jit(jax.value_and_grad(
            lambda p: jnp.mean(loss_fn(module.apply({"params": p}, x), y))))
        val, grads = fn(params)
        return float(val), grads

    def rel(ga, gb):
        num = jnp.sqrt(sum(jnp.sum(jnp.square(a.astype(jnp.float32)
                                              - b.astype(jnp.float32)))
                           for a, b in zip(jax.tree.leaves(ga),
                                           jax.tree.leaves(gb))))
        den = jnp.sqrt(sum(jnp.sum(jnp.square(b.astype(jnp.float32)))
                           for b in jax.tree.leaves(gb)))
        return float(num / den)

    l_ref, g_ref = loss_and_grad("dense", "cross_entropy_dense")
    out = []
    for tag, attn, loss in (("flash_vs_dense", "flash", "cross_entropy_dense"),
                            ("fused_vs_dense", "dense", "cross_entropy_fused")):
        l, g = loss_and_grad(attn, loss)
        dl, dg = abs(l - l_ref) / abs(l_ref), rel(g, g_ref)
        if not (np.isfinite(l) and dl <= TOL_PARITY_LOSS_REL
                and dg <= TOL_PARITY_GRAD_REL):
            raise AssertionError(
                f"{tag} at seq {sz.parity_seq}: loss {l} vs {l_ref} "
                f"(rel {dl}, tol {TOL_PARITY_LOSS_REL}), grad rel {dg} "
                f"(tol {TOL_PARITY_GRAD_REL})")
        out.append(f"{tag}: loss_rel={dl:.2e} grad_rel={dg:.2e}")
    return (f"parity@seq{sz.parity_seq} loss={l_ref:.4f} " + " ".join(out)
            + f" (tol loss {TOL_PARITY_LOSS_REL} grad {TOL_PARITY_GRAD_REL})")


def phase_kernels(sz: Sizes, seed: int, ctx: dict) -> str:
    import jax

    from sparktorch_tpu.parallel.mesh import build_mesh, replicated
    from sparktorch_tpu.train.step import create_train_state, make_train_epoch
    from sparktorch_tpu.train.sync import prepare_sharded_batch
    from sparktorch_tpu.utils.data import handle_features

    rng = np.random.default_rng(seed)
    ids = rng.integers(0, sz.lm_vocab,
                       (sz.lm_batch, sz.lm_seq + 1)).astype(np.int32)
    spec = _lm_spec(sz, "flash", sz.lm_seq)
    mesh = build_mesh()
    batch, _ = handle_features(ids[:, :-1], ids[:, 1:])
    batch = prepare_sharded_batch(batch, mesh)
    tx = spec.make_optimizer()
    with mesh:
        state = jax.jit(
            lambda: create_train_state(spec, jax.random.key(seed),
                                       sample_x=batch.x[:1], tx=tx),
            out_shardings=replicated(mesh),
        )()
    step = make_train_epoch(spec.make_module().apply, spec.loss_fn(), tx,
                            mesh, sz.lm_steps)
    t0 = time.perf_counter()
    compiled = step.lower(state, batch).compile()
    compile_s = time.perf_counter() - t0
    # From the compiled text, not from the config that was asked for.
    counts = kernel_call_counts(compiled.as_text())
    missing = [k for k, n in counts.items() if n == 0]
    if missing:
        raise AssertionError(
            f"compiled step holds no tpu_custom_call for {missing}: {counts}")
    t0 = time.perf_counter()
    state, metrics = compiled(state, batch)
    losses = np.asarray(metrics.loss)
    run_s = time.perf_counter() - t0
    if losses.shape != (sz.lm_steps,) or not np.all(np.isfinite(losses)):
        raise AssertionError(f"kernel step losses: {losses}")
    del state, compiled
    parity = _kernel_parity(sz, seed)
    return (f"compile_s={compile_s:.2f} steps={sz.lm_steps} run_s={run_s:.3f} "
            f"seq={sz.lm_seq} losses={[round(float(v), 4) for v in losses]} "
            f"tpu_custom_calls={json.dumps(counts)} | {parity}")


def phase_qk_norm_rope(sz: Sizes, seed: int, ctx: dict) -> str:
    import jax
    import jax.numpy as jnp

    from sparktorch_tpu.models.sparse_moe_lm import _rotate, rms_norm
    from sparktorch_tpu.ops.qk_norm_rope import qk_norm_rope, tables
    from sparktorch_tpu.ops.sparse_attention import heads_first

    hkv, d, eps, dt = sz.fused_kv_heads, sz.fused_head_dim, 1e-6, jnp.bfloat16

    def plain(xq, xk, xv, gq, gk, angles, factor):
        b, t = angles.shape[:2]
        cos = factor * jnp.cos(angles)[:, :, None]
        sin = factor * jnp.sin(angles)[:, :, None]
        heads = lambda x: x.reshape(b, t, -1, d)
        q = _rotate(rms_norm(heads(xq), gq, eps), cos, sin)
        k = _rotate(rms_norm(heads(xk), gk, eps), cos, sin)
        return heads_first(q.astype(dt), k.astype(dt), heads(xv).astype(dt),
                           "plain")

    def rel(a, b):
        a, b = (np.asarray(x, np.float32) for x in (a, b))
        return float(np.linalg.norm(a - b) / (np.linalg.norm(b) + 1e-30))

    report = []
    for b, t, heads, half, factor in sz.fused_cases:
        keys = jax.random.split(jax.random.key(seed), 10)
        xq, xk, xv = (jax.random.normal(k, (b, t, h * d)) * jnp.exp(
            jax.random.normal(keys[3], (b, t, 1)))
            for k, h in zip(keys[:3], (heads, hkv, hkv)))
        gq, gk = (1.0 + 0.2 * jax.random.normal(k, (d,)) for k in keys[4:6])
        angles = jax.random.uniform(keys[6], (b, t, half)) * 100.0
        weights = [jax.random.normal(k, s).astype(dt) for k, s in zip(
            keys[7:], ((b, hkv, heads // hkv, t, d), (b, hkv, t, d),
                       (b, hkv, t, d)))]

        def fused(xq, xk, xv, gq, gk):
            return qk_norm_rope(xq, xk, xv, gq, gk,
                                *tables(angles, d, factor), eps, half, dt)

        def both(fn):
            def loss(*a):
                return sum(jnp.sum(o.astype(jnp.float32) * w) for o, w in
                           zip(fn(*a), weights))
            return jax.jit(lambda *a: (fn(*a), jax.grad(
                loss, argnums=(0, 1, 2, 3, 4))(*a)))

        operands = (xq, xk, xv, gq, gk)
        run = both(fused)
        got = jax.block_until_ready(run(*operands))
        t0 = time.perf_counter()
        jax.block_until_ready(run(*operands))
        fused_s = time.perf_counter() - t0
        want = jax.block_until_ready(both(
            lambda *a: plain(*a, angles, factor))(*operands))
        value_rel = max(map(rel, got[0], want[0]))
        grad_rel = max(map(rel, got[1], want[1]))
        report.append(f"{heads}x{t}x{b} half={half} value_rel="
                      f"{value_rel:.2e} grad_rel={grad_rel:.2e} "
                      f"fwd_and_grad_s={fused_s:.4f}")
        if not (value_rel <= TOL_FUSED_VALUE_REL          # NaN fails too
                and grad_rel <= TOL_FUSED_GRAD_REL):
            raise AssertionError(
                f"fused q/k pass vs plain spelling: {report[-1]} (limits "
                f"{TOL_FUSED_VALUE_REL}, {TOL_FUSED_GRAD_REL})")
    return " | ".join(report)


def phase_latent_attention(sz: Sizes, seed: int, ctx: dict) -> str:
    import jax
    import jax.numpy as jnp

    from sparktorch_tpu.ops.latent_attention import (
        latent_attention_heads_first)
    from sparktorch_tpu.ops.latent_rope import latent_rope
    from sparktorch_tpu.ops.qk_norm_rope import tables

    b, t, heads = sz.latent_case
    nope, rope, dv, slot, dt = 128, 64, 128, 128, jnp.bfloat16
    half, scale = rope // 2, (nope + rope) ** -0.5
    order = np.concatenate([np.arange(0, rope, 2), np.arange(1, rope, 2)])
    keys = jax.random.split(jax.random.key(seed), 5)
    q = jax.random.normal(keys[0], (b, t, heads, nope + rope))
    kv = jax.random.normal(keys[1], (b, t, heads, nope + dv))
    kr = jax.random.normal(keys[2], (b, t, rope))
    angles = jnp.broadcast_to(
        jnp.arange(t, dtype=jnp.float32)[None, :, None]
        * 3.2e7 ** (-jnp.arange(half) / half), (b, t, half))
    w_out = jax.random.normal(keys[3], (b, t, heads, dv))

    def turned(x, ang):  # pair j is dims (2j, 2j + 1)
        x1, x2 = x[..., 0::2], x[..., 1::2]
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                         -1).reshape(x.shape)

    def plain(q, kv, kr):
        """Dense causal attention by the published equations, float32
        on operands rounded to bf16 where the ops round them."""
        r = lambda x: x.astype(dt).astype(jnp.float32)
        qq = r(jnp.concatenate(
            [q[..., :nope], turned(q[..., nope:], angles[:, :, None])], -1))
        k_rope = jnp.broadcast_to(turned(kr, angles)[:, :, None],
                                  (b, t, heads, rope))
        kk = r(jnp.concatenate([kv[..., :nope], k_rope], -1))
        s = jnp.einsum("bqhd,bkhd->bhqk", qq, kk,
                       precision="highest") * scale
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1),
                          r(kv[..., nope:]), precision="highest")

    def mine(q, kv, kr):
        slot_of = lambda x: jnp.pad(
            x[..., order], ((0, 0),) * (x.ndim - 1) + ((0, slot - rope),))
        xq = jnp.concatenate([q[..., :nope], slot_of(q[..., nope:])],
                             -1).reshape(b, t, -1)
        q5, k4, v4 = latent_rope(xq, kv.reshape(b, t, -1), slot_of(kr),
                                 *tables(angles, slot), half, nope, dt)
        o5 = latent_attention_heads_first(q5, k4, v4, scale)
        return jnp.swapaxes(o5[:, :, 0], 1, 2).astype(jnp.float32)

    def both(fn):
        return jax.jit(lambda *a: (fn(*a), jax.grad(
            lambda *a: jnp.sum(fn(*a) * w_out), argnums=(0, 1, 2))(*a)))

    def rel(a, b):
        a, b = (np.asarray(x, np.float32) for x in (a, b))
        return float(np.linalg.norm(a - b) / (np.linalg.norm(b) + 1e-30))

    run = both(mine)
    got = jax.block_until_ready(run(q, kv, kr))
    t0 = time.perf_counter()
    jax.block_until_ready(run(q, kv, kr))
    mine_s = time.perf_counter() - t0
    want = jax.block_until_ready(both(plain)(q, kv, kr))
    out_rel = rel(got[0], want[0])
    grad_rel = max(map(rel, got[1], want[1]))
    report = (f"{heads}x{t}x{b} out_rel={out_rel:.2e} grad_rel="
              f"{grad_rel:.2e} fwd_and_grad_s={mine_s:.4f}")
    if not (out_rel <= TOL_LATENT_OUT_REL            # NaN fails too
            and grad_rel <= TOL_LATENT_GRAD_REL):
        raise AssertionError(
            f"latent attention vs the plain equations: {report} (limits "
            f"{TOL_LATENT_OUT_REL}, {TOL_LATENT_GRAD_REL})")
    return report


def phase_gated_delta(sz: Sizes, seed: int, ctx: dict) -> str:
    """``ops/gated_delta_rule.py`` (chunks of 64, the WY form) against
    the token-by-token recurrence at Qwen3-Next's width: the output and
    all five gradients, decays from a few tokens' half-life to some
    hundreds'."""
    import jax
    import jax.numpy as jnp

    from sparktorch_tpu.ops.gated_delta_rule import gated_delta_rule

    b, t, hk, hv = sz.gdn_case
    d, dt = 128, jnp.bfloat16
    keys = jax.random.split(jax.random.key(seed), 6)
    unit = lambda x: (x / jnp.linalg.norm(x, axis=-1, keepdims=True))
    q = (unit(jax.random.normal(keys[0], (b, t, hk, d))) * d ** -0.5).astype(dt)
    k = unit(jax.random.normal(keys[1], (b, t, hk, d))).astype(dt)
    v = jax.random.normal(keys[2], (b, t, hv, d)).astype(dt)
    g = -jnp.exp(jax.random.uniform(keys[3], (b, t, hv), minval=np.log(1e-3),
                                    maxval=np.log(0.5)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (b, t, hv)))
    w_out = jax.random.normal(keys[5], (b, t, hv * d))

    def recurrence(q, k, v, g, beta):
        f32 = lambda x, heads: jnp.repeat(
            x.astype(jnp.float32), hv // heads, 2)

        def step(state, x):
            q, k, v, g, beta = x
            state = jnp.exp(g)[..., None, None] * state
            u = beta[..., None] * (v - jnp.einsum(
                "bhkv,bhk->bhv", state, k, precision="highest"))
            state = state + k[..., :, None] * u[..., None, :]
            return state, jnp.einsum("bhkv,bhk->bhv", state, q,
                                     precision="highest")

        _, o = jax.lax.scan(
            step, jnp.zeros((b, hv, d, d), jnp.float32),
            tuple(jnp.moveaxis(a, 1, 0) for a in (
                f32(q, hk), f32(k, hk), f32(v, hv), g, beta)))
        return jnp.moveaxis(o, 0, 1).reshape(b, t, hv * d)

    def mine(q, k, v, g, beta):
        flat = lambda x: x.reshape(b, t, -1)
        return gated_delta_rule(flat(q), flat(k), flat(v), g,
                                beta).astype(jnp.float32)

    def both(fn):
        return jax.jit(lambda *a: (fn(*a), jax.grad(
            lambda *a: jnp.sum(fn(*a) * w_out),
            argnums=(0, 1, 2, 3, 4))(*a)))

    def rel(a, b):
        a, b = (np.asarray(x, np.float32) for x in (a, b))
        return float(np.linalg.norm(a - b) / (np.linalg.norm(b) + 1e-30))

    run = both(mine)
    got = jax.block_until_ready(run(q, k, v, g, beta))
    t0 = time.perf_counter()
    jax.block_until_ready(run(q, k, v, g, beta))
    mine_s = time.perf_counter() - t0
    want = jax.block_until_ready(both(recurrence)(q, k, v, g, beta))
    out_rel = rel(got[0], want[0])
    grads = {n: rel(a, b) for n, a, b in zip(
        ("q", "k", "v", "g", "beta"), got[1], want[1])}
    report = (f"{hk}/{hv}x{t}x{b} out_rel={out_rel:.2e} grad_rel="
              f"{ {n: float(f'{r:.2e}') for n, r in grads.items()} } "
              f"fwd_and_grad_s={mine_s:.4f}")
    if not (out_rel <= TOL_GDN_OUT_REL                  # NaN fails too
            and max(grads.values()) <= TOL_GDN_GRAD_REL):
        raise AssertionError(
            f"the chunked gated delta rule vs the recurrence: {report} "
            f"(limits {TOL_GDN_OUT_REL}, {TOL_GDN_GRAD_REL})")
    return report


def phase_gdn_conv_gate(sz: Sizes, seed: int, ctx: dict) -> str:
    """``ops/gdn_conv_gate.py`` against the array operations it replaced
    in ``GatedDeltaNet``, at Qwen3-Next's width, bfloat16 results out of
    a float32 product: ``q``, ``k``, ``v``, the gated norm's ``y`` and
    the four gradients."""
    import jax
    import jax.numpy as jnp

    from sparktorch_tpu.models.sparse_moe_lm import rms_norm
    from sparktorch_tpu.ops.gdn_conv_gate import gdn_conv, gdn_out_norm
    from sparktorch_tpu.ops.sparse_attention import by_head

    b, t, hk, hv = sz.gdn_case
    d, taps, eps, dt = 128, 4, 1e-6, jnp.bfloat16
    n_k, n_v = hk * d, hv * d
    keys = jax.random.split(jax.random.key(seed), 9)
    qkvz = jax.random.normal(keys[0], (b, t, 2 * n_k + 2 * n_v)) * jnp.exp(
        jax.random.normal(keys[1], (b, t, 1)))
    w = 0.289 * jax.random.normal(keys[2], (taps, 2 * n_k + n_v))
    gain = 1.0 + 0.2 * jax.random.normal(keys[3], (d,))
    o = jax.random.normal(keys[4], (b, t, n_v)).astype(dt)
    weights = [jax.random.normal(k, (b, t, n)).astype(dt) for k, n in zip(
        keys[5:], (n_k, n_k, n_v, n_v))]

    def fused(qkvz, w, gain, o):
        q, k, v, gate = gdn_conv(qkvz, w, n_k, dt)
        return q, k, v, gdn_out_norm(o, gate, gain, eps)

    def plain(qkvz, w, gain, o):
        u, z = qkvz[..., :2 * n_k + n_v], qkvz[..., 2 * n_k + n_v:]
        padded = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
        u = jax.nn.silu(sum(w[i] * padded[:, i:i + t] for i in range(taps)))

        def unit(x, scale):
            x = by_head(x, d)
            return (x * (scale * jax.lax.rsqrt(jnp.sum(
                jnp.square(x), -1, keepdims=True) + 1e-6))).astype(
                    dt).reshape(b, t, n_k)

        return (unit(u[..., :n_k], d ** -0.5), unit(u[..., n_k:2 * n_k], 1.0),
                u[..., 2 * n_k:].astype(dt),
                (rms_norm(by_head(o, d), gain, eps) * jax.nn.silu(
                    by_head(z, d))).astype(dt).reshape(o.shape))

    def both(fn):
        def loss(*a):
            return sum(jnp.sum(x.astype(jnp.float32) * w_)
                       for x, w_ in zip(fn(*a), weights))
        return jax.jit(lambda *a: (fn(*a), jax.grad(
            loss, argnums=(0, 1, 2, 3))(*a)))

    def rel(a, b):
        a, b = (np.asarray(x, np.float32) for x in (a, b))
        return float(np.linalg.norm(a - b) / (np.linalg.norm(b) + 1e-30))

    operands = (qkvz, w, gain, o)
    run = both(fused)
    got = jax.block_until_ready(run(*operands))
    t0 = time.perf_counter()
    jax.block_until_ready(run(*operands))
    fused_s = time.perf_counter() - t0
    want = jax.block_until_ready(both(plain)(*operands))
    value_rel = max(map(rel, got[0], want[0]))
    grads = {n: rel(a, b) for n, a, b in zip(
        ("qkvz", "taps", "gain", "o"), got[1], want[1])}
    report = (f"{hk}/{hv}x{t}x{b} value_rel={value_rel:.2e} grad_rel="
              f"{ {n: float(f'{r:.2e}') for n, r in grads.items()} } "
              f"fwd_and_grad_s={fused_s:.4f}")
    # o's cotangent leaves in bfloat16, rounded once on either side
    if not (value_rel <= TOL_FUSED_VALUE_REL            # NaN fails too
            and max(grads["qkvz"], grads["taps"],
                    grads["gain"]) <= TOL_FUSED_GRAD_REL
            and grads["o"] <= TOL_FUSED_VALUE_REL):
        raise AssertionError(
            f"the linear layer's fused passes vs the plain spelling: "
            f"{report} (limits {TOL_FUSED_VALUE_REL}, {TOL_FUSED_GRAD_REL})")
    return report


def _rel(a, b):
    a, b = (np.asarray(x, np.float32) for x in (a, b))
    return float(np.linalg.norm(a - b) / (np.linalg.norm(b) + 1e-30))


def _timed(fn, *operands):
    """``(the result, seconds of a second call)``."""
    import jax

    out = jax.block_until_ready(fn(*operands))
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*operands))
    return out, time.perf_counter() - t0


def phase_short_conv_gate(sz: Sizes, seed: int, ctx: dict) -> str:
    """``ops/short_conv_gate.py`` against its plain spelling at the
    convolution cell's step, a bfloat16 result out of a float32 product:
    the value and both gradients, forward and gradient timed apart."""
    import jax
    import jax.numpy as jnp

    from sparktorch_tpu.ops import short_conv_gate as op

    b, t, d = sz.sconv_case
    dt = jnp.bfloat16
    keys = jax.random.split(jax.random.key(seed), 4)
    bcu = jax.random.normal(keys[0], (b, t, 3 * d)) * jnp.exp(
        jax.random.normal(keys[1], (b, t, 1)))
    taps = 0.333 * jax.random.normal(keys[2], (op.TAPS, d))
    weight = jax.random.normal(keys[3], (b, t, d)).astype(dt)

    def grad_of(fn):
        return jax.jit(jax.grad(lambda x, w: jnp.sum(
            fn(x, w, dt).astype(jnp.float32) * weight), argnums=(0, 1)))

    value, fwd_s = _timed(jax.jit(lambda x, w: op.short_conv_gate(x, w, dt)),
                          bcu, taps)
    grads, grad_s = _timed(grad_of(op.short_conv_gate), bcu, taps)
    want = jax.jit(lambda x, w: op.plain(x, w, dt))(bcu, taps)
    want_grads = grad_of(op.plain)(bcu, taps)
    value_rel = _rel(value, want)
    grad_rel = {n: _rel(a, w) for n, a, w in zip(
        ("product", "taps"), grads, want_grads)}
    report = (f"{d}x{t}x{b} value_rel={value_rel:.2e} grad_rel="
              f"{ {n: float(f'{r:.2e}') for n, r in grad_rel.items()} } "
              f"fwd_s={fwd_s:.5f} bwd_s={grad_s:.5f}")
    if not (value_rel <= TOL_FUSED_VALUE_REL            # NaN fails too
            and max(grad_rel.values()) <= TOL_FUSED_GRAD_REL):
        raise AssertionError(
            f"the fused convolution pass vs its plain spelling: {report} "
            f"(limits {TOL_FUSED_VALUE_REL}, {TOL_FUSED_GRAD_REL})")
    return report


def _ragged_experts_sum(x, token, gate, rows, w_gate, w_up, w_down):
    """``held_experts_sum`` on whole arrays by ``jax.lax.ragged_dot``
    (what the layer ran before its kernels): operands in ``x``'s dtype,
    sums float32, the rows past the held pairs, which the TPU's
    ``ragged_dot`` leaves undefined, zeroed on both sides of each
    product."""
    import jax
    import jax.numpy as jnp

    dt = x.dtype
    live = (jnp.arange(token.size) < jnp.sum(rows))[:, None]
    held = lambda a: jnp.where(live, a, 0.0).astype(a.dtype)
    rdot = lambda a, m: held(jax.lax.ragged_dot(
        a, m.astype(dt), rows, preferred_element_type=jnp.float32))
    xs = held(x[token])
    hidden = (jax.nn.silu(rdot(xs, w_gate)) * rdot(xs, w_up)).astype(dt)
    return jnp.zeros(x.shape, jnp.float32).at[token].add(
        rdot(hidden, w_down) * gate[:, None])


def _loop_ms(call, carried=None) -> float:
    """Milliseconds a call of ``call`` in a loop of twenty (ONE call
    reads its dispatch): ``call(carried)`` gives the next ``carried``."""
    import jax

    carried = jax.block_until_ready(call(carried))
    t0 = time.perf_counter()
    for _ in range(20):
        carried = call(carried)
    jax.block_until_ready(carried)
    return (time.perf_counter() - t0) / 20 * 1e3


def _trip_parts_ms(x, token, gate, rows, weights, d_out, chunk) -> dict:
    """Milliseconds of what a trip of the layer's loops does around its
    products, alone, on the first chunk as the layer's two passes give
    it: ``ops/grouped_mlp.py``'s ``fetch_rows`` of ``x``'s rows (the
    forward pass's call) and of ``x``'s and ``d_out``'s (the backward
    pass's) against XLA's gathers of the whole chunk, ``x[token]`` and
    the pair, on the same operands, the fetched rows EQUAL to the
    gathered ones bit for bit on every tile with a held pair, the fetch
    timed with half the chunk live; the turn of ``x`` into what the
    fetch reads (``fetch_source``); and ``sum_back`` of the chunk's
    ``gate * ys`` and of ``dx``'s rows into one carried sum."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from sparktorch_tpu.models import sparse_moe_lm as M
    from sparktorch_tpu.ops import grouped_mlp as G

    tile, d = M._row_tile(chunk, rows.size), x.shape[1]
    dy_all = d_out.astype(x.dtype)
    sources = (G.fetch_source(x), G.fetch_source(dy_all))
    ck = M._Chunk(0, *M._padded_pairs(token, gate, chunk), rows, chunk, tile,
                  d, *sources)
    # timed HALF live, as a cell's chunk is by construction (twice the
    # expected share), whatever share of this draw's pairs is held
    fetch = functools.partial(G.fetch_rows, jnp.int32(chunk // 2), ck.token,
                              d=d, tile=tile)
    gather = functools.partial(jax.jit(
        lambda token, *arrays: tuple(a[token] for a in arrays)), ck.token)
    fetched = int(G.rows_fetched(ck.live, tile))
    for got, want in zip(ck.fetched, gather(x, dy_all)):
        if not np.array_equal(np.asarray(got[:fetched].astype("float32")),
                              np.asarray(want[:fetched].astype("float32"))):
            raise AssertionError(
                f"fetch_rows is not x[token] on the {fetched} rows of the "
                f"tiles with a held pair (chunk {chunk}, tile {tile})")
    xs, dy = ck.fetched
    w_gate, w_up, w_down = (w.astype(x.dtype) for w in weights)
    ys = G.gmm_down(ck.table, G.gmm_in(ck.table, xs, w_gate, w_up,
                                       tile=tile), w_down, ck.gate, tile=tile)
    d_a, d_b, _, _ = G.gmm_bwd_hidden(
        ck.table, xs, dy, ck.gate, w_gate, w_up, w_down, tile=tile)
    dx = G.gmm_dx(ck.table, d_a, d_b, w_gate, w_up, tile=tile)
    back = jax.jit(functools.partial(G.sum_back, tile=tile),
                   donate_argnums=1)
    sums_of = lambda moved: lambda sums: back(
        ck.table, G.token_sums(*x.shape) if sums is None else sums, moved,
        ck.token)
    return dict(
        fetch_fwd_ms=_loop_ms(lambda _: fetch(sources[0])),
        fetch_bwd_ms=_loop_ms(lambda _: fetch(*sources)),
        gather_fwd_ms=_loop_ms(lambda _: gather(x)),
        gather_bwd_ms=_loop_ms(lambda _: gather(x, dy_all)),
        fetch_source_ms=_loop_ms(lambda _: G.fetch_source(x)),
        sum_back_fwd_ms=_loop_ms(sums_of(ys)),
        sum_back_bwd_ms=_loop_ms(sums_of(dx)))


def phase_grouped_mlp(sz: Sizes, seed: int, ctx: dict) -> str:
    """The expert layer's sum through ``ops/grouped_mlp.py``'s kernels
    against its ``ragged_dot`` spelling at LFM2's layer: the output and
    the five gradients, forward and gradient timed apart, and the sums
    back of a chunk's rows to their tokens alone."""
    return _grouped_mlp(sz.grouped_case, seed)


def phase_grouped_mlp_ep_member(sz: Sizes, seed: int, ctx: dict) -> str:
    """The same at what ONE member of Mellum2's four computes in a layer
    after the exchange's way in: the four members' 32,768 rows of 2,304,
    8 of 64 experts each, the member's 16 held (65,536 held pairs in
    expectation, two a token; chunks of 131,072): the kernels' first
    hidden size that is not 2,048."""
    return _grouped_mlp(sz.exchange_member_case, seed)


def _grouped_mlp(case: tuple, seed: int) -> str:
    import jax
    import jax.numpy as jnp

    from sparktorch_tpu.models import sparse_moe_lm as M

    n, k, routed, n_held, d, f = case
    dt = jnp.bfloat16
    keys = jax.random.split(jax.random.key(seed), 8)
    x = jax.random.normal(keys[0], (n, d)).astype(dt)
    # each token's k experts, uneven, the held ones the first n_held
    expert = jnp.argsort(jax.random.gumbel(keys[1], (n, routed))
                         + jnp.linspace(0.0, 1.0, routed), -1)[:, :k]
    local = jnp.where(expert < n_held, expert, n_held).reshape(n * k)
    order = jnp.argsort(local, stable=True)
    rows = jnp.sum(local[:, None] == jnp.arange(n_held)[None, :], 0,
                   dtype=jnp.int32)
    token = order // k
    gate = jax.random.uniform(keys[2], (n * k,), jnp.float32, 0.05, 0.5)
    w_gate, w_up = (jax.random.normal(kk, (n_held, d, f)) * d ** -0.5
                    for kk in keys[3:5])
    w_down = jax.random.normal(keys[5], (n_held, f, d)) * f ** -0.5
    weight = jax.random.normal(keys[6], (n, d))
    chunk, _ = M._row_chunks(n * k, n_held, routed)
    ours = lambda x, gate, *w: M.held_experts_sum(x, token, gate, rows, *w,
                                                  chunk)
    plain = lambda x, gate, *w: _ragged_experts_sum(x, token, gate, rows, *w)
    grad_of = lambda fn: jax.jit(jax.grad(
        lambda *a: jnp.sum(fn(*a) * weight), argnums=(0, 1, 2, 3, 4)))
    operands = (x, gate, w_gate, w_up, w_down)
    value, fwd_s = _timed(jax.jit(ours), *operands)
    grads, grad_s = _timed(grad_of(ours), *operands)
    rels = {"out": _rel(value, jax.jit(plain)(*operands))}
    rels.update({name: _rel(a, b) for name, a, b in zip(
        ("x", "gate", "w_gate", "w_up", "w_down"), grads,
        grad_of(plain)(*operands))})
    parts = _trip_parts_ms(x, token, gate, rows, (w_gate, w_up, w_down),
                           weight, chunk)
    report = (f"tokens={n} held_rows={int(rows.sum())} chunk={chunk} "
              f"rel={ {n: float(f'{r:.2e}') for n, r in rels.items()} } "
              f"fwd_s={fwd_s:.5f} grad_s={grad_s:.5f} "
              + " ".join(f"{name}={ms:.3f}" for name, ms in parts.items()))
    if not max(rels.values()) <= TOL_GROUPED_REL:       # NaN fails too
        raise AssertionError(
            f"the grouped kernels vs the ragged_dot spelling: {report} "
            f"(limit {TOL_GROUPED_REL})")
    return report


def phase_causal_heads_64(sz: Sizes, seed: int, ctx: dict) -> str:
    """Heads of 64, two to a register, at the attention layer's shape of
    the convolution cell: ``qk_norm_rope`` against its plain spelling,
    and the ``causal`` kernels on its bfloat16 results against dense
    float32 attention on the same operands, output and three gradients;
    the compiled text holds the five kernels."""
    import jax
    import jax.numpy as jnp

    from sparktorch_tpu.models.sparse_moe_lm import _rotate, rms_norm
    from sparktorch_tpu.ops.qk_norm_rope import qk_norm_rope, tables
    from sparktorch_tpu.ops.rule_attention import (
        Causal, heads_in_registers, rule_attention_heads_first)

    b, t, heads, hkv = sz.heads64_case
    d, eps, dt = 64, 1e-5, jnp.bfloat16
    keys = jax.random.split(jax.random.key(seed), 8)
    xq, xk, xv = (jax.random.normal(k, (b, t, h * d)) * jnp.exp(
        0.5 * jax.random.normal(keys[3], (b, t, 1)))
        for k, h in zip(keys[:3], (heads, hkv, hkv)))
    gq, gk = (1.0 + 0.2 * jax.random.normal(k, (d,)) for k in keys[4:6])
    angles = jnp.arange(t, dtype=jnp.float32)[None, :, None] * (
        1e6 ** (-jnp.arange(d // 2) / (d // 2)))
    angles = jnp.broadcast_to(angles, (b, t, d // 2))
    w_out = jax.random.normal(keys[6], (b, t, heads * d)).astype(dt)
    table = tables(angles, d)

    def plain_qkv(xq, xk, xv, gq, gk):
        cos, sin = jnp.cos(angles)[:, :, None], jnp.sin(angles)[:, :, None]
        by_head = lambda x: x.reshape(b, t, -1, d)
        return heads_in_registers(
            _rotate(rms_norm(by_head(xq), gq, eps), cos, sin).astype(dt),
            _rotate(rms_norm(by_head(xk), gk, eps), cos, sin).astype(dt),
            by_head(xv).astype(dt), "plain")

    fused_qkv = lambda *a: qk_norm_rope(*a, *table, eps, d // 2, dt)
    operands = (xq, xk, xv, gq, gk)
    got, qk_s = _timed(jax.jit(fused_qkv), *operands)
    qkv_rel = max(map(_rel, got, jax.jit(plain_qkv)(*operands)))
    q5, k4, v4 = got

    def kernels(q5, k4, v4):
        return rule_attention_heads_first(q5, k4, v4, Causal(), "causal", d)

    def dense(q5, k4, v4):
        # registers -> heads: [b, pairs, G, T, 128] -> [b, heads, T, 64]
        f32 = lambda x: x.astype(jnp.float32)
        q = jnp.transpose(f32(q5), (0, 3, 1, 2, 4)).reshape(b, t, heads, d)
        k, v = (jnp.swapaxes(f32(x), 1, 2).reshape(b, t, hkv, d)
                for x in (k4, v4))
        k, v = (jnp.repeat(x, heads // hkv, 2) for x in (k, v))
        keep = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]

        def one_head(q, k, v):   # [b, T, 64] each
            s = jnp.einsum("bqd,bkd->bqk", q, k,
                           precision="highest") * d ** -0.5
            p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), -1)
            return jnp.einsum("bqk,bkd->bqd", p, v, precision="highest")

        out = jax.lax.map(lambda qkv: jax.checkpoint(one_head)(*qkv), tuple(
            jnp.moveaxis(x, 2, 0) for x in (q, k, v)))
        return jnp.moveaxis(out, 0, 2).reshape(b, t, heads * d)

    def with_grads(fn):
        return jax.jit(lambda *a: (fn(*a), jax.grad(lambda *a: jnp.sum(
            fn(*a).astype(jnp.float32) * w_out), argnums=(0, 1, 2))(*a)))

    run = with_grads(kernels)
    (o, grads), attn_s = _timed(run, q5, k4, v4)
    counts = {k: len(re.findall(
        rf'custom_call_target="tpu_custom_call".*\b{k}\b',
        run.lower(q5, k4, v4).compile().as_text()))
        for k in ("causal_attn_fwd", "causal_attn_bwd_dq",
                  "causal_attn_bwd_dkv")}
    want_o, want_grads = with_grads(dense)(q5, k4, v4)
    out_rel = _rel(o, want_o)
    grad_rel = max(map(_rel, grads, want_grads))
    report = (f"{heads}/{hkv}x64x{t}x{b} qkv_rel={qkv_rel:.2e} out_rel="
              f"{out_rel:.2e} grad_rel={grad_rel:.2e} qk_norm_rope_s="
              f"{qk_s:.5f} attn_fwd_and_grad_s={attn_s:.5f} {counts}")
    if not (qkv_rel <= TOL_FUSED_VALUE_REL              # NaN fails too
            and out_rel <= TOL_LATENT_OUT_REL
            and grad_rel <= TOL_LATENT_GRAD_REL
            and all(n >= 1 for n in counts.values())):
        raise AssertionError(
            f"heads of 64 through qk_norm_rope and the causal kernels: "
            f"{report} (limits {TOL_FUSED_VALUE_REL}, {TOL_LATENT_OUT_REL}, "
            f"{TOL_LATENT_GRAD_REL})")
    return report


def phase_trainer_hogwild(sz: Sizes, seed: int, ctx: dict) -> str:
    from sparktorch_tpu import SparkTorch, serialize_torch_obj
    from sparktorch_tpu.models.resnet import resnet18

    rng = np.random.default_rng(seed)
    x = rng.normal(0, 1, (sz.hog_rows, 32, 32, 3)).astype(np.float32)
    y = rng.integers(0, 10, (sz.hog_rows,)).astype(np.float32)
    obj = serialize_torch_obj(
        resnet18(num_classes=10), criterion="cross_entropy",
        optimizer="sgd", optimizer_params={"lr": 1e-2},
        input_shape=(32, 32, 3),
    )
    est = SparkTorch(
        inputCol="features", labelCol="label", torchObj=obj,
        iters=sz.hog_iters, miniBatch=sz.hog_mini_batch, mode="hogwild",
        partitions=sz.hog_workers, pushEvery=sz.hog_push_every,
    )
    t0 = time.perf_counter()
    est.fit({"features": list(x), "label": y})
    wall = time.perf_counter() - t0
    recs = est._last_metrics
    losses = [r["loss"] for r in recs]
    versions = sorted({r["version"] for r in recs})
    workers = sorted({r["worker"] for r in recs})
    if not losses or not np.all(np.isfinite(losses)):
        raise AssertionError(f"hogwild losses: {losses}")
    if len(workers) != sz.hog_workers:
        raise AssertionError(f"workers seen: {workers}")
    if not versions[-1] > versions[0]:
        raise AssertionError(f"server version never advanced: {versions}")
    return (f"fit_s={wall:.2f} workers={len(workers)} records={len(recs)} "
            f"pulled_versions={versions[0]}..{versions[-1]} "
            f"loss_first={losses[0]:.4f} loss_last={losses[-1]:.4f}")


# ---------------------------------------------------------------------------
# Four chips
# ---------------------------------------------------------------------------


def _spread(tree) -> set:
    import jax

    return {d for leaf in jax.tree.leaves(tree) for d in leaf.devices()}


def phase_dp4_vs_one_chip(sz: Sizes, seed: int, ctx: dict) -> str:
    """``train_distributed`` BERT-base, same global batch and seed, on
    a dp=4 mesh and on one chip: per-step losses agree; on dp=4 the
    parameters and the batch are really on four devices."""
    import jax

    from sparktorch_tpu.parallel.mesh import MeshConfig, build_mesh
    from sparktorch_tpu.train.sync import train_distributed

    rng = np.random.default_rng(seed)
    x, y = _bert_rows(rng, sz.dp_rows, sz.bert_seq, sz.bert_vocab)
    devices = jax.devices()
    seen = {}

    def witness(tag, n_dev):
        # The trainer keeps its state to itself; what is live on the
        # devices while a step's record is handed out is the witness.
        def hook(_record):
            if tag in seen:
                return
            big = [a for a in jax.live_arrays()
                   if a.ndim == 2 and a.shape[0] == sz.bert_vocab]
            rows = [a for a in jax.live_arrays()
                    if a.shape == x.shape and len(a.devices()) == n_dev]
            seen[tag] = (
                sorted(len(a.devices()) for a in big),
                sorted({a.addressable_shards[0].data.shape[0] for a in rows}),
            )
        return hook

    out = {}
    for tag, mesh in (
        ("dp4", build_mesh(MeshConfig(dp=4), devices[:4])),
        ("one", build_mesh(MeshConfig(dp=1), devices[:1])),
    ):
        t0 = time.perf_counter()
        res = train_distributed(
            _bert_obj(sz), x, labels=y, mesh=mesh, iters=sz.dp_iters,
            seed=seed, metrics_hook=witness(tag, mesh.size),
        )
        out[tag] = ([r["loss"] for r in res.metrics],
                    time.perf_counter() - t0)
    l4, l1 = np.asarray(out["dp4"][0]), np.asarray(out["one"][0])
    if l4.shape != (sz.dp_iters,) or not np.all(np.isfinite(l4)):
        raise AssertionError(f"dp=4 losses: {l4}")
    rel = float(np.max(np.abs(l4 - l1) / np.abs(l1)))
    if rel > TOL_DP_LOSS_REL:
        raise AssertionError(f"dp=4 {l4} vs one chip {l1}: rel {rel}")
    embed_devs, shard_rows = seen["dp4"]
    if not embed_devs or set(embed_devs) != {4}:
        raise AssertionError(f"dp=4 parameters on {embed_devs} devices")
    if shard_rows != [sz.dp_rows // 4]:
        raise AssertionError(f"dp=4 batch shards hold {shard_rows} rows")
    return (f"dp4_s={out['dp4'][1]:.2f} one_chip_s={out['one'][1]:.2f} "
            f"losses_dp4={[round(float(v), 5) for v in l4]} "
            f"losses_one={[round(float(v), 5) for v in l1]} "
            f"max_rel={rel:.2e} (tol {TOL_DP_LOSS_REL}) "
            f"vocab_leaves_on={embed_devs} devices, "
            f"batch_rows_per_device={shard_rows}")


def phase_dp4_long_rows(sz: Sizes, seed: int, ctx: dict) -> str:
    """BERT-base on rows of ``long_seq`` tokens over dp=4, where the
    default attention is the fused kernels, each chip's own inside the
    step's ``shard_map``: the bus says they were picked, the per-step
    losses agree with ``attn_impl="dense"`` on the same mesh, and the
    predictor over the same mesh (a program the partitioner splits, so
    dense by the rule) compiles and equals a plain ``module.apply``."""
    import jax

    from sparktorch_tpu import serialize_torch_obj
    from sparktorch_tpu.inference import BatchPredictor
    from sparktorch_tpu.models.transformer import bert_base
    from sparktorch_tpu.obs import Telemetry
    from sparktorch_tpu.parallel.mesh import MeshConfig, build_mesh
    from sparktorch_tpu.train.sync import train_distributed

    rng = np.random.default_rng(seed)
    x, y = _bert_rows(rng, sz.long_rows, sz.long_seq, sz.bert_vocab)
    mesh = build_mesh(MeshConfig(dp=4), jax.devices()[:4])
    losses, picked, wall = {}, {}, {}
    for attn in ("dense", "auto"):
        module = bert_base(attn_impl=attn, **dict(sz.bert_overrides))
        tele = Telemetry(run_id=f"smoke-long-{attn}")
        t0 = time.perf_counter()
        res = train_distributed(
            serialize_torch_obj(
                module, criterion="cross_entropy", optimizer="adam",
                optimizer_params={"lr": 1e-4}, input_shape=(sz.long_seq,)),
            x, labels=y, mesh=mesh, iters=sz.dp_iters, seed=seed,
            telemetry=tele)
        wall[attn] = time.perf_counter() - t0
        losses[attn] = np.asarray([r["loss"] for r in res.metrics])
        picked[attn] = tele.gauge_value("train.attention.kernel_layers")
    la, ld = losses["auto"], losses["dense"]
    if la.shape != (sz.dp_iters,) or not np.all(np.isfinite(la)):
        raise AssertionError(f"losses under 'auto': {la}")
    if (picked["auto"], picked["dense"]) != (module.config.n_layers, 0):
        raise AssertionError(
            f"train.attention.kernel_layers reads {picked['auto']} under "
            f"'auto' and {picked['dense']} under 'dense', wanted "
            f"{module.config.n_layers} and 0")
    rel = float(np.max(np.abs(la - ld) / np.abs(ld)))
    if rel > TOL_DP_LOSS_REL:
        raise AssertionError(f"kernels {la} vs dense {ld}: rel {rel}")
    # the model 'auto' trained, served over the same mesh
    preds = BatchPredictor(module, res.params, res.model_state, mesh=mesh,
                           chunk=sz.long_rows).predict(x)
    ref = np.asarray(jax.jit(module.apply)(
        {"params": res.params, **(res.model_state or {})}, x))
    err = float(np.max(np.abs(preds - ref)))
    if preds.shape != ref.shape or not err <= TOL_PREDICT_ABS:
        raise AssertionError(f"predictor over dp=4 differs from "
                             f"module.apply by {err}")
    return (f"seq={sz.long_seq} rows={sz.long_rows} "
            f"auto_s={wall['auto']:.2f} dense_s={wall['dense']:.2f} "
            f"kernel_layers={picked['auto']:.0f}/{picked['dense']:.0f} "
            f"losses_auto={[round(float(v), 5) for v in la]} "
            f"losses_dense={[round(float(v), 5) for v in ld]} "
            f"max_rel={rel:.2e} (tol {TOL_DP_LOSS_REL}) "
            f"predictor_max_abs_err={err:.2e} (tol {TOL_PREDICT_ABS})")


def _sharded_step(spec, mesh_cfg, x, y, seed, want_text=False):
    """One ``make_sharded_train_step`` step; returns (loss, state,
    compiled text or None, seconds)."""
    import jax

    from sparktorch_tpu.parallel.mesh import build_mesh
    from sparktorch_tpu.train.sharded import (
        create_sharded_state,
        make_sharded_train_step,
        shard_batch,
    )
    from sparktorch_tpu.utils.data import DataBatch

    mesh = build_mesh(mesh_cfg, jax.devices()[:4])
    tx = spec.make_optimizer()
    state, shardings = create_sharded_state(
        spec, mesh, jax.random.key(seed), sample_x=x[:1], tx=tx)
    step = make_sharded_train_step(
        spec.make_module().apply, spec.loss_fn(), tx, mesh, shardings)
    batch = shard_batch(
        DataBatch(x=x, y=y, w=np.ones((x.shape[0],), np.float32)), mesh)
    t0 = time.perf_counter()
    text = None
    if want_text:
        with jax.set_mesh(mesh):
            text = step.jitted.lower(state, batch).compile().as_text()
    state, metrics = step(state, batch)
    loss = float(metrics.loss)
    return loss, state, text, time.perf_counter() - t0


def phase_sharded_dp2_fsdp2(sz: Sizes, seed: int, ctx: dict) -> str:
    import jax

    from sparktorch_tpu.models.transformer import bert_base
    from sparktorch_tpu.parallel.mesh import MeshConfig
    from sparktorch_tpu.utils.serde import ModelSpec

    rng = np.random.default_rng(seed)
    x, y = _bert_rows(rng, sz.dp_rows, sz.bert_seq, sz.bert_vocab)
    spec = ModelSpec(module=bert_base(**dict(sz.bert_overrides)),
                     loss="cross_entropy", optimizer="adam",
                     optimizer_params={"lr": 1e-4})
    loss, state, _text, wall = _sharded_step(
        spec, MeshConfig(dp=2, fsdp=2), x.astype(np.int32),
        y.astype(np.int32), seed)
    if not np.isfinite(loss):
        raise AssertionError(f"dp2 x fsdp2 loss {loss}")
    n_dev = len(_spread(state.params))
    split = sum(
        1 for a in jax.tree.leaves(state.params)
        if a.addressable_shards[0].data.shape != a.shape)
    if n_dev != 4 or split == 0:
        raise AssertionError(
            f"dp2 x fsdp2: params on {n_dev} devices, {split} leaves split")
    return (f"step_s={wall:.2f} loss={loss:.4f} param_devices={n_dev} "
            f"leaves_split_over_fsdp={split}/{len(jax.tree.leaves(state.params))}")


def phase_moe_ep4(sz: Sizes, seed: int, ctx: dict) -> str:
    from sparktorch_tpu.models import CausalLM
    from sparktorch_tpu.models.transformer import TransformerConfig
    from sparktorch_tpu.parallel.mesh import MeshConfig
    from sparktorch_tpu.utils.serde import ModelSpec

    rng = np.random.default_rng(seed)
    ids = rng.integers(0, sz.lm_vocab,
                       (sz.moe_batch, sz.moe_seq + 1)).astype(np.int32)
    cfg = TransformerConfig(
        vocab_size=sz.lm_vocab, d_model=sz.lm_d_model, n_heads=sz.lm_heads,
        n_layers=sz.lm_layers, d_ff=sz.lm_d_ff, max_len=sz.moe_seq,
        n_experts=sz.moe_experts, moe_every=2,
    )
    spec = ModelSpec(module=CausalLM(cfg), loss="cross_entropy",
                     optimizer="adamw", optimizer_params={"lr": 3e-4})
    loss, state, text, wall = _sharded_step(
        spec, MeshConfig(ep=4), ids[:, :-1], ids[:, 1:], seed,
        want_text=True)
    n_a2a = len(re.findall(r"\ball-to-all(?:-start)?\(", text))
    if n_a2a == 0:
        raise AssertionError("no all-to-all in the ep=4 MoE step's "
                             "compiled text")
    if not np.isfinite(loss):
        raise AssertionError(f"ep=4 MoE loss {loss}")
    n_dev = len(_spread(state.params))
    if n_dev != 4:
        raise AssertionError(f"ep=4: params on {n_dev} devices")
    return (f"step_s={wall:.2f} loss={loss:.4f} all_to_all_ops={n_a2a} "
            f"param_devices={n_dev}")


def phase_expert_exchange(sz: Sizes, seed: int, ctx: dict) -> str:
    """One expert exchange at Mellum2's widths over the four chips (8,192
    rows of 2,304 a chip, 8 of 64 experts of 896, 16 held a chip: rows,
    gates and chosen experts all-gathered over ``ep``, float32 sums
    reduce-scattered back) against every token's sum of its eight
    experts computed on ONE chip that holds all 64: the same layer with
    no axis in sight, a member's rows at a time."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from sparktorch_tpu.models import sparse_moe_lm as M
    from sparktorch_tpu.parallel.mesh import MeshConfig, build_mesh

    n, d, routed, k, f = sz.exchange_case
    cfg = M.mellum2_lm(n_layers=4).config
    assert (cfg.d_model, cfg.n_routed_experts, cfg.experts_per_token,
            cfg.expert_width) == (d, routed, k, f)
    layer = M.HeldExperts(cfg)
    mesh = build_mesh(MeshConfig(dp=1, ep=4), jax.devices()[:4])
    keys = jax.random.split(jax.random.key(seed), 3)
    g = jax.random.normal(keys[0], (4, n, d))
    shapes = jax.eval_shape(lambda: layer.init(keys[1], g[:1]))["params"]
    specs = {name: P() if name == "router" else P("ep") for name in shapes}
    place = lambda tree, spec: jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec)
    # the router spread so that tokens choose unevenly
    params = jax.jit(lambda: {**(p := layer.init(keys[1], g[:1])["params"]),
                              "router": p["router"] * 8.0},
                     out_shardings=place(shapes, specs))()
    apply = lambda p, g: layer.apply({"params": p}, g,
                                     mutable=["moe_metrics"])
    cut = jax.jit(jax.shard_map(
        lambda p, g: (lambda out, sown: (out, jax.tree.map(
            lambda a: jax.lax.psum(a, "ep"), sown["moe_metrics"])))(
                *apply(p, g)),
        mesh=mesh, in_specs=(specs, P("ep")), out_specs=(P("ep"), P()),
        check_vma=False))
    g4 = jax.device_put(g, NamedSharding(mesh, P("ep")))
    (out4, sown), wall = _timed(cut, params, g4)
    one = jax.devices()[0]
    whole = jax.device_put(params, one)
    on_one = jax.jit(lambda p, g: apply(p, g)[0])
    rels = [_rel(out4[m], on_one(whole, jax.device_put(g[m:m + 1], one))[0])
            for m in range(4)]
    rows = np.asarray(sown["expert_rows"][0])
    report = (f"rows_a_chip={n} rel_by_member="
              f"{[float(f'{r:.2e}') for r in rels]} fwd_s={wall:.5f} "
              f"expert_rows min={rows.min()} max={rows.max()} "
              f"sum={rows.sum()} dropped={float(sown['dropped'][0])} "
              f"exchange_rows={float(sown['exchange_rows'][0]):.0f} "
              f"exchange_bytes={float(sown['exchange_bytes'][0]):.0f}")
    if (not max(rels) <= TOL_EXCHANGE_REL or rows.sum() != 4 * n * k
            or float(sown["dropped"][0]) != 0.0):
        raise AssertionError(f"the exchange over four chips vs one chip "
                             f"holding every expert: {report} (limit "
                             f"{TOL_EXCHANGE_REL})")
    return report


ONE_CHIP = (("trainer_sync", phase_trainer_sync),
            ("predictor", phase_predictor),
            ("kernels", phase_kernels),
            ("qk_norm_rope", phase_qk_norm_rope),
            ("latent_attention", phase_latent_attention),
            ("gated_delta", phase_gated_delta),
            ("gdn_conv_gate", phase_gdn_conv_gate),
            ("short_conv_gate", phase_short_conv_gate),
            ("causal_heads_64", phase_causal_heads_64),
            ("grouped_mlp", phase_grouped_mlp),
            ("grouped_mlp_ep_member", phase_grouped_mlp_ep_member),
            ("trainer_hogwild", phase_trainer_hogwild))
FOUR_CHIPS = (("dp4_vs_one_chip", phase_dp4_vs_one_chip),
              ("dp4_long_rows", phase_dp4_long_rows),
              ("sharded_dp2_fsdp2", phase_sharded_dp2_fsdp2),
              ("moe_ep4", phase_moe_ep4),
              ("expert_exchange", phase_expert_exchange))


def run_phases(phases, sz: Sizes, seed: int) -> bool:
    """Run in order; the first failure stops the run. Every phase
    prints its own line — also the ones that did not run."""
    ctx: dict = {}
    failed = None
    for name, fn in phases:
        if failed is not None:
            print(f"phase {name}: not run ({failed} failed)", flush=True)
            continue
        t0 = time.perf_counter()
        try:
            line = fn(sz, seed, ctx)
        except Exception:
            traceback.print_exc(file=sys.stderr)
            print(f"phase {name}: FAILED after "
                  f"{time.perf_counter() - t0:.1f}s (reason on stderr)",
                  flush=True)
            failed = name
            continue
        print(f"phase {name}: ok {time.perf_counter() - t0:.1f}s "
              f"peak_bytes={_peak_bytes()} | {line}", flush=True)
    return failed is None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the four-chip path and what it is "
                    "compared with")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--only", default=None,
                    help="comma-separated names of the phases to run, of "
                    "those --chips selects")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no accelerator: jax.devices()[0] is "
              f"{dev.platform!r} ({dev.device_kind}); this script runs "
              "on a TPU only", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2

    from sparktorch_tpu.utils.checkpoint import arm_compile_cache

    arm_compile_cache()
    print(f"chip_smoke: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} jax={jax.__version__} seed={args.seed} "
          f"compile_cache={jax.config.jax_compilation_cache_dir}",
          flush=True)
    t0 = time.perf_counter()
    phases = ONE_CHIP if args.chips == 1 else FOUR_CHIPS
    if args.only:
        phases = tuple(p for p in phases if p[0] in args.only.split(","))
    ok = run_phases(phases, Sizes(), args.seed)
    print(f"chip_smoke: total_s={time.perf_counter() - t0:.1f}", flush=True)
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

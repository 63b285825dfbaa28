"""Pipeline parallelism over the ``pp`` mesh axis (GPipe and 1F1B
schedules), composable with tensor parallelism over ``tp`` and —
for MoE stacks — expert parallelism over ``ep``.

No reference counterpart (SURVEY §2.4: PP "absent"). TPU-first
design: the transformer stack is split into ``pp`` stages — the
stacked per-layer params are sharded over ``pp`` on their leading
(layer) dim — and a ``shard_map`` step runs the schedule:
microbatches enter at stage 0, activations hop stage→stage on an ICI
ring via ``lax.ppermute``, the last stage accumulates the weighted
loss.

Two schedules, identical math (exactness-tested against each other):

- ``gpipe`` (default): the whole schedule (M + S - 1 ticks) is one
  ``lax.scan`` and autodiff THROUGH it (ppermute transposes to the
  reverse permute) yields exact gradients; activation memory scales
  with M (the scan saves per-tick carries).
- ``1f1b``: a combined-tick 1F1B schedule (M + 2S - 2 ticks) with a
  MANUAL backward — each backward tick re-runs its stage forward
  under ``jax.vjp``, so only the stage inputs of in-flight
  microbatches persist, in a ring of 2S - 1 slots: activation memory
  scales with S, not M (measured via XLA memory_analysis in the
  tests). FLOPs match remat-GPipe. MoE stacks (and ep sharding)
  compose — the aux loss and drop counts ride the manual backward.

Zero per-tick Python, static shapes; the GPipe bubble is the textbook
(S-1)/(M+S-1) fraction — raise ``n_micro`` to shrink it.

Within a stage the encoder layer is computed in explicit einsum form
(same math and param tree as ``models.transformer.EncoderLayer``) so
that:

- **tp composes**: attention heads and FFN columns are sliced over the
  ``tp`` axis, with the classic Megatron f/g pair implemented as
  custom-vjp ops (:func:`_tp_enter`: identity forward / psum backward
  at the entry of each parallel region; :func:`_tp_reduce`: psum
  forward / identity backward at its exit). With those two ops every
  parameter gradient is complete and tp-identical without any
  tp-axis gradient reduction.
- **remat works**: each layer's forward is wrapped in
  ``jax.checkpoint`` when ``cfg.remat`` — activations recompute in the
  backward pass, the standard memory/FLOPs trade for deep stacks.
- **flash attention works**: ``attn_impl='flash'`` calls the Pallas
  streaming kernel on the local heads (a kernel is a primitive, not a
  nested shard_map, so it composes with the pp schedule).
- **sp composes**: with ``attn_impl='ring'`` the sequence dim shards
  over ``sp`` and ring attention runs as a plain ``ppermute`` K/V
  rotation INSIDE the schedule's shard_map (no nested island). The
  per-example loss mean and the classifier pooling cross sp through
  :func:`_sp_reduce` (psum forward / identity backward), so every
  param grad stays an honest per-shard share that one psum over sp
  completes.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sparktorch_tpu.models.transformer import EncoderLayer, TransformerConfig
from sparktorch_tpu.obs import goodput as _goodput
from sparktorch_tpu.parallel.compat import axis_size as _axis_size
from sparktorch_tpu.ops.attention import dense_attention
from sparktorch_tpu.parallel.mesh import (
    AXIS_DP,
    AXIS_EP,
    AXIS_PP,
    AXIS_SP,
    AXIS_TP,
)
from sparktorch_tpu.train.step import refuse_sync_dp_only, shard_map_compat
from sparktorch_tpu.utils.data import DataBatch


class PipelineState(NamedTuple):
    step: jax.Array
    params: Any
    opt_state: Any


class PpStepOut(NamedTuple):
    """Per-step arrays from a fused multi-schedule call
    (``steps_per_call > 1``), each shaped ``(k,)``."""

    loss: jax.Array
    drop_fraction: Optional[jax.Array]
    grad_norm: jax.Array
    examples: jax.Array


# ---------------------------------------------------------------------------
# Megatron-style f/g for tensor parallelism (exact grads, no tp-axis
# gradient reductions needed anywhere).
# ---------------------------------------------------------------------------


@jax.custom_vjp
def _tp_enter(x):
    """Entry of a tp-parallel region: identity forward, psum backward.
    Makes cotangents on the replicated stream complete (summed over
    every head/column slice) and tp-identical."""
    return x


def _tp_enter_fwd(x):
    return x, None


def _tp_enter_bwd(_, ct):
    return (jax.lax.psum(ct, AXIS_TP),)


_tp_enter.defvjp(_tp_enter_fwd, _tp_enter_bwd)


@jax.custom_vjp
def _tp_reduce(x):
    """Exit of a tp-parallel region: psum forward, identity backward
    (each slice receives the full output cotangent)."""
    return jax.lax.psum(x, AXIS_TP)


def _tp_reduce_fwd(x):
    return jax.lax.psum(x, AXIS_TP), None


def _tp_reduce_bwd(_, ct):
    return (ct,)


_tp_reduce.defvjp(_tp_reduce_fwd, _tp_reduce_bwd)


@jax.custom_vjp
def _ep_enter(x):
    """Entry of the expert-parallel path: identity forward, psum-over-
    ep backward. Each ep member's expert-path input-cotangent covers
    only ITS experts' share; summing them here makes the cotangent
    leaving the MoE FFN complete and ep-identical, so every upstream
    gradient (attn, ln, dense layers, embeddings) keeps the ordinary
    replicated-over-ep reductions."""
    return x


def _ep_enter_fwd(x):
    return x, None


def _ep_enter_bwd(_, ct):
    return (jax.lax.psum(ct, AXIS_EP),)


_ep_enter.defvjp(_ep_enter_fwd, _ep_enter_bwd)


@jax.custom_vjp
def _ep_reduce(x):
    """Exit of the expert-parallel path: psum forward (combine the
    per-member partial expert outputs), identity backward (each member
    receives the full output cotangent ONCE — a raw psum would
    transpose to another psum and double-count it; same trap the tp
    f/g pair guards)."""
    return jax.lax.psum(x, AXIS_EP)


def _ep_reduce_fwd(x):
    return jax.lax.psum(x, AXIS_EP), None


def _ep_reduce_bwd(_, ct):
    return (ct,)


_ep_reduce.defvjp(_ep_reduce_fwd, _ep_reduce_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _a2a_ep(x, split_axis: int, concat_axis: int):
    """Tiled all_to_all over ``ep`` with an explicit reverse-exchange
    backward. The op is linear, so its true VJP is the inverse
    exchange (swap split/concat axes); spelling it as a custom_vjp
    keeps the pp schedules' autodiff (GPipe's grad-through-scan and
    1F1B's per-tick ``jax.vjp``) off jax's all_to_all transpose path,
    which miscompiles for split != concat (verified on jax 0.9)."""
    return jax.lax.all_to_all(x, AXIS_EP, split_axis, concat_axis,
                              tiled=True)


def _a2a_ep_fwd(x, split_axis, concat_axis):
    return _a2a_ep(x, split_axis, concat_axis), None


def _a2a_ep_bwd(split_axis, concat_axis, _, ct):
    return (jax.lax.all_to_all(ct, AXIS_EP, concat_axis, split_axis,
                               tiled=True),)


_a2a_ep.defvjp(_a2a_ep_fwd, _a2a_ep_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _ep_scatter(x, g_loc: int):
    """This member's block of ``g_loc`` leading-dim entries of an
    ep-REPLICATED array (block m for ep member m). Backward:
    all_gather of the per-member cotangent blocks — the assembled
    cotangent is complete and identical on every member, so gradients
    upstream of the scatter stay ep-replicated (each block counted
    exactly once; a transpose-of-slice alone would leave per-member
    partial cotangents)."""
    i = jax.lax.axis_index(AXIS_EP)
    return jax.lax.dynamic_slice_in_dim(x, i * g_loc, g_loc, 0)


def _ep_scatter_fwd(x, g_loc):
    return _ep_scatter(x, g_loc), None


def _ep_scatter_bwd(g_loc, _, ct):
    return (jax.lax.all_gather(ct, AXIS_EP, axis=0, tiled=True),)


_ep_scatter.defvjp(_ep_scatter_fwd, _ep_scatter_bwd)


@jax.custom_vjp
def _ep_gather(x):
    """Inverse of :func:`_ep_scatter`: all_gather the members' blocks
    into the full ep-replicated array. Backward: each member keeps its
    OWN block of the incoming cotangent — not a reduce_scatter: the
    downstream computation is ep-replicated, so every member already
    holds the full cotangent and summing over members would scale it
    by ep (the same trap the psum/psum pair guards)."""
    return jax.lax.all_gather(x, AXIS_EP, axis=0, tiled=True)


def _ep_gather_fwd(x):
    return _ep_gather(x), None


def _ep_gather_bwd(_, ct):
    n_ep = _axis_size(AXIS_EP)
    g_loc = ct.shape[0] // n_ep
    i = jax.lax.axis_index(AXIS_EP)
    return (jax.lax.dynamic_slice_in_dim(ct, i * g_loc, g_loc, 0),)


_ep_gather.defvjp(_ep_gather_fwd, _ep_gather_bwd)


@jax.custom_vjp
def _sp_reduce(x):
    """Exit of a sequence-parallel region: psum over ``sp`` forward
    (combine the per-member partial sums over their sequence shards),
    identity backward — each member receives the full output cotangent
    exactly once, so its upstream (per-token) gradients are its true
    per-shard share and the trainer's psum over sp completes them. The
    sp twin of the Megatron ``_tp_reduce`` g-op."""
    return jax.lax.psum(x, AXIS_SP)


def _sp_reduce_fwd(x):
    return jax.lax.psum(x, AXIS_SP), None


def _sp_reduce_bwd(_, ct):
    return (ct,)


_sp_reduce.defvjp(_sp_reduce_fwd, _sp_reduce_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _scale_grad(x, factor: float):
    """Identity forward, cotangent scaled by ``factor`` backward. Used
    on parameters whose forward inputs are REPLICATED across a mesh
    axis the trainer later psums their gradient over (the classifier
    head under sp: pooling makes its input sp-replicated, so each sp
    member computes the FULL head gradient and the sp psum would
    overcount by sp — scaling by 1/sp makes the psum exact)."""
    return x


def _scale_grad_fwd(x, factor):
    return x, None


def _scale_grad_bwd(factor, _, ct):
    return (jax.tree.map(lambda c: c * factor, ct),)


_scale_grad.defvjp(_scale_grad_fwd, _scale_grad_bwd)


# ---------------------------------------------------------------------------
# Stage math (EncoderLayer's exact param tree, explicit einsum form)
# ---------------------------------------------------------------------------


def _ln(p, x, dt):
    xf = x.astype(jnp.float32)
    mean = xf.mean(-1, keepdims=True)
    var = ((xf - mean) ** 2).mean(-1, keepdims=True)
    xf = (xf - mean) / jnp.sqrt(var + 1e-6)
    return (xf * p["scale"] + p["bias"]).astype(dt)


def _attn_half(cfg: TransformerConfig, lp, h):
    """ln_attn -> attention -> proj residual: the first half of
    :func:`_layer_forward`, shared with the MoE layer path (whose FFN
    half is an expert dispatch instead of the dense MLP)."""
    dt = cfg.compute_dtype
    a = _tp_enter(_ln(lp["ln_attn"], h, dt))
    qkv_k = lp["attn"]["qkv"]["kernel"].astype(dt)     # (d, 3, h_loc, hd)
    qkv_b = lp["attn"]["qkv"]["bias"].astype(dt)       # (3, h_loc, hd)
    qkv = jnp.einsum("bsd,dthf->bsthf", a, qkv_k) + qkv_b
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]  # (b, s, h_loc, hd)
    if cfg.attn_impl == "flash":
        from sparktorch_tpu.ops.flash_attention import flash_attention

        out = flash_attention(q, k, v, cfg.causal)
    elif cfg.attn_impl == "ring":
        # Ring attention expressed IN the pp shard_map: the
        # schedule's shard_map binds every mesh axis, so
        # the K/V rotation is a plain ppermute over ``sp`` here — no
        # nested shard_map island. Composes with tp (per-head) and
        # both schedules (ppermute transposes exactly under GPipe
        # autodiff; the 1F1B per-tick vjp re-runs it).
        from sparktorch_tpu.ops.attention import ring_attention

        out = ring_attention(q, k, v, axis_name=AXIS_SP, causal=cfg.causal)
    else:
        # 'dense', and 'auto': the stages' attention is picked by name
        # here, not by models.transformer.pick_attention
        out = dense_attention(q, k, v, causal=cfg.causal)
    proj_k = lp["attn"]["proj"]["kernel"].astype(dt)   # (h_loc, hd, d)
    proj_b = lp["attn"]["proj"]["bias"].astype(dt)     # (d,) replicated
    return h + _tp_reduce(jnp.einsum("bshf,hfd->bsd", out, proj_k)) + proj_b


def _layer_forward(cfg: TransformerConfig, lp, h):
    """One encoder layer on this device's head/column slice.

    ``lp`` is the layer's param tree with ``qkv``/``proj``/``mlp``
    kernels already SLICED over tp (shard_map did that); ln params and
    output-side biases arrive replicated. Replicated output-side
    biases are added AFTER :func:`_tp_reduce` (once, undivided): the
    cotangent there is the full output cotangent on every slice, so
    their gradients come out complete and tp-identical with no
    reduction — adding a 1/tp-scaled bias inside the reduce instead
    would silently shrink those gradients by tp (caught by the SGD
    grad-parity test).
    """
    dt = cfg.compute_dtype
    x = _attn_half(cfg, lp, h)
    m = _tp_enter(_ln(lp["ln_mlp"], x, dt))
    w1 = lp["mlp_in"]["kernel"].astype(dt)             # (d, ff_loc)
    b1 = lp["mlp_in"]["bias"].astype(dt)               # (ff_loc,)
    mid = nn.gelu(m @ w1 + b1)
    w2 = lp["mlp_out"]["kernel"].astype(dt)            # (ff_loc, d)
    b2 = lp["mlp_out"]["bias"].astype(dt)              # (d,) replicated
    return x + _tp_reduce(mid @ w2) + b2


def _moe_pattern(cfg: TransformerConfig):
    """Per-layer use_moe flags — delegates to the ONE schedule
    definition on the config (shared with the flax Transformer)."""
    return cfg.moe_pattern()


def _moe_groups(cfg: TransformerConfig, n: int) -> Tuple[int, int]:
    """(group size, group count) — the ONE group-partition definition
    (models.transformer.moe_group_partition), un-anchored: inside the
    pp shard_map the partition must depend only on (cfg, n) so ep
    stays a pure layout choice at pinned step-0 exactness (the GSPMD
    trainer's mesh-anchored variant would change the partition with
    the mesh shape; its parity suite re-baselines both worlds
    instead). The a2a layout therefore stays opt-in-by-group-size
    here: pick moe_group_size so the group count divides ep."""
    from sparktorch_tpu.models.transformer import moe_group_partition

    return moe_group_partition(cfg, n)


def pp_moe_group_size(cfg: TransformerConfig, n_tokens: int,
                      n_ep: int) -> Optional[int]:
    """The a2a grouping OPT-IN for MoE inside a pp schedule: the
    largest group size ``g <= cfg.moe_group_size`` that partitions
    ``n_tokens`` (one microbatch's tokens per dp/sp shard) into a
    group count divisible by ``n_ep`` — exactly the group-size choice
    the gpipe-ep dryrun config makes by hand, so the 'auto' dispatch
    (:func:`_moe_ffn_ep_dispatch`) takes the all-to-all layout instead
    of silently falling back to token replication. Returns None when
    no such size exists (the replicated fallback is then the only
    layout, and the caller should leave the config untouched). The
    pp group partition is deliberately un-anchored (see
    :func:`_moe_groups`), which is why the opt-in must come from the
    group SIZE rather than a mesh-derived partition."""
    if n_ep <= 1 or n_tokens <= 0:
        return None
    cap = max(1, int(cfg.moe_group_size))
    for g in range(min(cap, n_tokens), 0, -1):
        if n_tokens % g == 0 and (n_tokens // g) % n_ep == 0:
            return g
    return None


def pp_moe_opt_in_cfg(cfg: TransformerConfig, rows: int, seq: int,
                      dp: int, sp: int, ep: int,
                      n_micro: int) -> TransformerConfig:
    """Apply :func:`pp_moe_group_size` to a config about to build a
    pp step: returns ``cfg`` with ``moe_group_size`` replaced by the
    a2a opt-in when one exists for this (batch, mesh, n_micro)
    partition, or unchanged otherwise. The ONE definition both the
    tuner's measured candidate and the ``mesh='auto'`` winner build
    go through — the two must agree or the measured layout is not
    the one production pays for."""
    if cfg.n_experts <= 0 or ep <= 1:
        return cfg
    tokens = (rows // max(1, dp) // max(1, n_micro)) * (seq // max(1, sp))
    gs = pp_moe_group_size(cfg, tokens, ep)
    if gs is not None and gs != cfg.moe_group_size:
        return dataclasses.replace(cfg, moe_group_size=gs)
    return cfg


def build_pp_schedule_step(spec, mesh: Mesh,
                           schedule_meta, rows: int, seq: int,
                           tx: Optional[
                               optax.GradientTransformation] = None,
                           rng: Optional[jax.Array] = None,
                           sample_x=None):
    """Build a pipeline-scheduled step from a ``ModelSpec`` + a tuner
    schedule meta (``{"schedule": gpipe|1f1b|interleaved,
    "virtual_stages": V, "n_micro": M}``) — THE one build path shared
    by the tuner's measured candidate
    (:func:`sparktorch_tpu.parallel.tune.prepare_pipeline_candidate`)
    and the ``mesh='auto'`` winner
    (:func:`sparktorch_tpu.train.sharded._make_auto_pipeline_step`),
    so the measured layout and the production step cannot diverge.

    Validates the meta (schedule name, rows % (dp x n_micro)), picks
    the head from the module type, threads the MoE a2a group-size
    opt-in (:func:`pp_moe_opt_in_cfg`), restacks the spec's flax
    params into the pipeline layout (interleave-permuted for
    ``virtual_stages > 1``), places the state over ``mesh``, and
    returns ``(state, step, cfg_used, head)`` — no dispatch happens
    here, so callers own their compile accounting."""
    from sparktorch_tpu.models.transformer import CausalLM

    meta = dict(schedule_meta or {})
    if not meta:
        raise ValueError("pp>1 build requires a schedule meta")
    sched = str(meta.get("schedule"))
    if sched not in ("gpipe", "1f1b", "interleaved"):
        raise ValueError(f"unknown pipeline schedule {sched!r}")
    v_stages = int(meta.get("virtual_stages", 1))
    n_micro = int(meta["n_micro"])
    # "interleaved" is the search-space name; this trainer spells it
    # schedule='1f1b' + virtual_stages=V.
    pp_schedule = "1f1b" if sched in ("1f1b", "interleaved") else "gpipe"

    tx = tx or spec.make_optimizer()
    module = spec.make_module()
    cfg = getattr(module, "config", None)
    if cfg is None or not hasattr(cfg, "d_model"):
        raise ValueError(
            "pipeline schedules need a transformer ModelSpec "
            f"(got {type(module).__name__})"
        )
    head = "lm" if isinstance(module, CausalLM) else "classifier"
    sizes = dict(mesh.shape)
    dp = sizes[AXIS_DP]
    if rows % (dp * n_micro) != 0:
        raise ValueError(
            f"batch rows {rows} not divisible by dp({dp}) x "
            f"n_micro({n_micro})"
        )
    cfg = pp_moe_opt_in_cfg(cfg, rows, seq, dp,
                            sizes.get(AXIS_SP, 1),
                            sizes.get(AXIS_EP, 1), n_micro)
    if rng is None:
        rng = jax.random.key(0)
    if sample_x is None:
        sample_x = np.zeros((1, seq), np.int32)
    flax_params = dict(spec.init_params(
        rng, sample_x=np.asarray(sample_x)))["params"]
    pparams = pipeline_params_from_flax(flax_params, cfg)
    if v_stages > 1:
        pparams = apply_interleave_permutation(
            pparams, cfg, sizes[AXIS_PP], v_stages)
    state = place_pipeline_state(pparams, tx, mesh)
    step = make_pp_train_step(
        cfg, tx, mesh, n_micro=n_micro, head=head,
        schedule=pp_schedule, virtual_stages=v_stages,
    )
    return state, step, cfg, head


def _moe_route(cfg: TransformerConfig, mp, tokens, mask, cap: int):
    """Router + GShard capacity assignment for a block of routing
    groups — the exact routing math of
    :class:`models.transformer.MoEFFN`, factored so the replicated and
    all-to-all ep layouts share one definition (routing is per-group,
    so it is layout-independent). ``tokens``: (G, g, d). Returns
    ``(probs, oh, gates, disp, keep)`` with ``disp`` the
    (G, g, k, e, cap) choice-level dispatch plan."""
    e = cfg.n_experts
    k = max(1, min(cfg.moe_top_k, e))
    n_groups, g, _ = tokens.shape
    # Router in f32 (small matmul; numerics matter more than MXU).
    logits = (
        tokens.astype(jnp.float32) @ mp["router"]["kernel"]
        + mp["router"]["bias"]
    )                                            # (G, g, e)
    probs = jax.nn.softmax(logits, axis=-1)
    topk_p, topk_idx = jax.lax.top_k(probs, k)   # (G, g, k)
    if k == 1:
        gates = topk_p
    else:
        gates = topk_p / jnp.maximum(
            jnp.sum(topk_p, axis=-1, keepdims=True), 1e-9
        )
    oh = jax.nn.one_hot(topk_idx, e, dtype=jnp.int32)  # (G, g, k, e)
    if mask is not None:
        oh = oh * mask[:, :, None, None]
        gates = gates * mask[:, :, None]
    # Choice-major capacity priority (GShard): ALL first choices rank
    # before any second choice.
    oh_t = oh.transpose(0, 2, 1, 3).reshape(n_groups, k * g, e)
    pos = jnp.cumsum(oh_t, axis=1) * oh_t
    keep = (pos > 0) & (pos <= cap)
    slot = jnp.clip(pos - 1, 0, cap - 1)
    disp_flat = keep[..., None] & jax.nn.one_hot(slot, cap, dtype=bool)
    disp = disp_flat.reshape(n_groups, k, g, e, cap).transpose(0, 2, 1, 3, 4)
    return probs, oh, gates, disp, keep


def _moe_aux_counts(cfg: TransformerConfig, probs, oh, keep, mask):
    """Load-balance + observability sums over THIS block of groups:
    ``(term, dropped, routed)`` where ``term`` = sum over the block's
    groups of sum_e frac_e*mean_prob_e (the caller normalizes by the
    GLOBAL group count and applies moe_aux_weight * e)."""
    oh0 = oh[:, :, 0, :].astype(jnp.float32)
    if mask is not None:
        mf = mask.astype(jnp.float32)
        valid = jnp.maximum(jnp.sum(mf, axis=1), 1.0)
        frac = jnp.sum(oh0, axis=1) / valid[:, None]
        mean_prob = jnp.sum(probs * mf[:, :, None], axis=1) / valid[:, None]
    else:
        frac = jnp.mean(oh0, axis=1)
        mean_prob = jnp.mean(probs, axis=1)
    term = jnp.sum(frac * mean_prob)
    routed = jnp.sum(oh).astype(jnp.float32)
    kept = jnp.sum(keep.astype(jnp.float32))
    return term, routed - kept, routed


def _moe_ffn_ep(cfg: TransformerConfig, mp, h, token_w, n_ep: int):
    """Replicated-token expert-parallel MoE FFN inside the pp
    shard_map: tokens replicate across ep members (the batch shards
    over dp only), the router is replicated so every member computes
    identical routing, and each member applies only its local slice of
    experts — one psum over ``ep`` combines the partial outputs.
    Correct at any ep, but per-member routing work and activation
    bytes do NOT shrink with ep — :func:`_moe_ffn_ep_a2a` is the
    scaling layout; this one remains for group counts that don't
    divide by ep (and as the parity reference). Returns
    (out, aux_loss, dropped, routed) — the observables MoEFFN sows.

    ``mp`` is the LOCAL moe param subtree: expert leaves arrive
    pre-sliced to ``e_loc = n_experts/ep`` by shard_map; router params
    replicated."""
    import math

    dt = cfg.compute_dtype
    b, s, d = h.shape
    e = cfg.n_experts
    e_loc = e // n_ep
    k = max(1, min(cfg.moe_top_k, e))
    n = b * s
    g, n_groups = _moe_groups(cfg, n)
    tokens = h.reshape(n_groups, g, d)
    if n_ep > 1:
        # Identity forward / psum-over-ep backward: the ONLY consumer
        # of `tokens` is the expert path (router + dispatch), whose
        # per-member input-cotangents are partial (one expert slice
        # each) — _ep_enter completes them so upstream grads stay
        # ep-replicated.
        tokens = _ep_enter(tokens)
    cap = max(1, math.ceil(cfg.capacity_factor * g * k / e))
    mask = (token_w.reshape(n_groups, g) > 0) if token_w is not None else None

    probs, oh, gates, disp, keep = _moe_route(cfg, mp, tokens, mask, cap)
    dispatch = jnp.any(disp, axis=2).astype(dt)  # (G, g, e, cap)
    combine = jnp.einsum("gnk,gnkec->gnec", gates.astype(dt),
                         disp.astype(dt))        # (G, g, e, cap)
    # Local experts slice of the (replicated) dispatch/combine plans.
    if n_ep > 1:
        off = jax.lax.axis_index(AXIS_EP) * e_loc
        dispatch = jax.lax.dynamic_slice_in_dim(dispatch, off, e_loc, axis=2)
        combine = jax.lax.dynamic_slice_in_dim(combine, off, e_loc, axis=2)

    expert_in = jnp.einsum("gnec,gnd->gecd", dispatch, tokens.astype(dt))
    hmid = jnp.einsum("gecd,edf->gecf", expert_in, mp["moe_w_in"].astype(dt))
    hmid = nn.gelu(hmid + mp["moe_b_in"][None, :, None].astype(dt))
    expert_out = jnp.einsum("gecf,efd->gecd", hmid,
                            mp["moe_w_out"].astype(dt))
    expert_out = expert_out + mp["moe_b_out"][None, :, None].astype(dt)
    out = jnp.einsum("gnec,gecd->gnd", combine, expert_out)
    if n_ep > 1:
        # Each member combined only its experts' outputs; the sum over
        # ep members is the full gate-weighted combine (custom-vjp:
        # identity backward, so the output cotangent isn't re-summed).
        out = _ep_reduce(out)

    # Aux + drop counts from the (replicated) routing — already global
    # per (pp, dp) shard, no ep reduction.
    term, dropped, routed = _moe_aux_counts(cfg, probs, oh, keep, mask)
    aux = cfg.moe_aux_weight * e * term / n_groups
    if n_ep > 1:
        # The aux VALUE is replicated across ep (computed from the
        # replicated routing), but its router gradient is computed in
        # full on every member — while the task path contributes only
        # a per-member share. Scale the aux GRADIENT by 1/ep (value
        # unchanged) so the (dp, ep) psum of router grads is exact.
        aux = aux / n_ep + jax.lax.stop_gradient(aux * (1.0 - 1.0 / n_ep))
    return out.reshape(b, s, d), aux, dropped, routed


def _moe_ffn_ep_a2a(cfg: TransformerConfig, mp, h, token_w, n_ep: int):
    """GShard-style expert-parallel MoE FFN inside the pp shard_map:
    token blocks travel to their experts' owners over an explicit
    ``all_to_all`` (and back), so — unlike the replicated layout —
    per-member routing/dispatch work and activation bytes scale 1/ep.

    Layout (the explicit-collective twin of the sharding-constraint
    layout in ``models.transformer.MoEFFN``):

    1. each ep member takes its 1/ep block of the routing GROUPS
       (:func:`_ep_scatter`; groups route independently, so routing
       decisions are bit-identical to ep=1),
    2. routes only those groups and builds its (G_loc, e, cap)
       dispatch plan + (G_loc, e, cap, d) expert inputs,
    3. ``all_to_all``: expert blocks swap for group blocks — each
       member now holds (G, e_loc, cap, d), every group's capacity
       slots for ITS experts,
    4. local expert FFN, reverse ``all_to_all``, gate-weighted combine
       of its own groups,
    5. :func:`_ep_gather` restores the ep-replicated (b, s, d) layout
       the surrounding (attention/residual) stage math expects.

    Requires ``n_groups % ep == 0`` (the dispatcher falls back to the
    replicated layout otherwise). Same return contract as
    :func:`_moe_ffn_ep`; exactness against it is pinned by
    ``test_pp_ep_a2a_parity``."""
    import math

    dt = cfg.compute_dtype
    b, s, d = h.shape
    e = cfg.n_experts
    k = max(1, min(cfg.moe_top_k, e))
    n = b * s
    g, n_groups = _moe_groups(cfg, n)
    g_loc = n_groups // n_ep
    cap = max(1, math.ceil(cfg.capacity_factor * g * k / e))

    tokens = _ep_scatter(h.reshape(n_groups, g, d), g_loc)  # (G_loc, g, d)
    if token_w is not None:
        i = jax.lax.axis_index(AXIS_EP)
        mask = jax.lax.dynamic_slice_in_dim(
            token_w.reshape(n_groups, g) > 0, i * g_loc, g_loc, 0
        )
    else:
        mask = None

    probs, oh, gates, disp, keep = _moe_route(cfg, mp, tokens, mask, cap)
    dispatch = jnp.any(disp, axis=2).astype(dt)      # (G_loc, g, e, cap)
    combine = jnp.einsum("gnk,gnkec->gnec", gates.astype(dt),
                         disp.astype(dt))            # (G_loc, g, e, cap)

    expert_in = jnp.einsum("gnec,gnd->gecd", dispatch,
                           tokens.astype(dt))        # (G_loc, e, cap, d)
    expert_in = _a2a_ep(expert_in, 1, 0)             # (G, e_loc, cap, d)
    hmid = jnp.einsum("gecd,edf->gecf", expert_in, mp["moe_w_in"].astype(dt))
    hmid = nn.gelu(hmid + mp["moe_b_in"][None, :, None].astype(dt))
    expert_out = jnp.einsum("gecf,efd->gecd", hmid,
                            mp["moe_w_out"].astype(dt))
    expert_out = expert_out + mp["moe_b_out"][None, :, None].astype(dt)
    back = _a2a_ep(expert_out, 0, 1)                 # (G_loc, e, cap, d)
    out_loc = jnp.einsum("gnec,gecd->gnd", combine, back)  # (G_loc, g, d)
    out = _ep_gather(out_loc).reshape(b, s, d)

    # Per-member partial sums over its OWN groups; the aux value uses
    # _ep_reduce (psum forward, identity backward) so each member's
    # router gradient stays its true per-group share — the (dp, ep)
    # psum in the trainer's grad reduction completes it. Drop counts
    # are metrics (never differentiated): a plain psum globalizes them.
    term, dropped, routed = _moe_aux_counts(cfg, probs, oh, keep, mask)
    aux = cfg.moe_aux_weight * e * _ep_reduce(term) / n_groups
    dropped = jax.lax.psum(dropped, AXIS_EP)
    routed = jax.lax.psum(routed, AXIS_EP)
    return out, aux, dropped, routed


def _moe_ffn_ep_dispatch(cfg: TransformerConfig, mp, h, token_w, n_ep: int):
    """Pick the ep layout per ``cfg.moe_ep_dispatch`` ('a2a' /
    'replicate' / 'auto'; trace-time decision — shapes are static)."""
    mode = cfg.moe_ep_dispatch
    if mode not in ("auto", "a2a", "replicate"):
        raise ValueError(f"unknown moe_ep_dispatch {mode!r}")
    _, n_groups = _moe_groups(cfg, h.shape[0] * h.shape[1])
    divisible = n_groups % n_ep == 0
    if mode == "a2a" and not divisible:
        raise ValueError(
            f"moe_ep_dispatch='a2a' needs the routing group count "
            f"({n_groups}) divisible by ep={n_ep}; lower moe_group_size "
            "or use 'auto'"
        )
    if n_ep > 1 and divisible and mode in ("auto", "a2a"):
        return _moe_ffn_ep_a2a(cfg, mp, h, token_w, n_ep)
    return _moe_ffn_ep(cfg, mp, h, token_w, n_ep)


def interleave_stack_permutation(n_layers: int, S: int, V: int) -> np.ndarray:
    """Global layer order for the INTERLEAVED pipeline layout: virtual
    stage j = v*S + d (v-th chunk on device d) covers global layers
    [j*lps, (j+1)*lps), and the pp sharding splits the stacked layer
    dim into S contiguous device blocks — so device d's block must
    hold its V chunks in chunk order. Apply to the stacked tree before
    :func:`place_pipeline_state` (``a[perm]``); invert with
    ``np.argsort(perm)`` after training. V=1 is the identity."""
    if n_layers % (S * V) != 0:
        raise ValueError(
            f"n_layers={n_layers} not divisible by pp*virtual_stages="
            f"{S * V}"
        )
    lps = n_layers // (S * V)
    order = []
    for d in range(S):
        for v in range(V):
            j = v * S + d
            order.extend(range(j * lps, (j + 1) * lps))
    return np.asarray(order)


def apply_interleave_permutation(pparams, cfg: TransformerConfig,
                                 S: int, V: int, inverse: bool = False):
    """Permute the stacked layer trees into (``inverse=False``) or
    back out of (``inverse=True``) the interleaved layout. The dense
    and MoE stacks permute INDEPENDENTLY: with a per-chunk-uniform
    pattern (enforced by ``make_pp_train_step``) each chunk holds a
    fixed count of each kind, so each stack's chunk rows are
    contiguous and reorder with that stack's own interleave
    permutation."""
    pattern = _moe_pattern(cfg)
    out = dict(pparams)
    for key, count in (("layers", pattern.count(False)),
                       ("layers_moe", pattern.count(True))):
        if key in out and count:
            p = interleave_stack_permutation(count, S, V)
            if inverse:
                p = np.argsort(p)
            out[key] = jax.tree.map(lambda a, p=p: a[p], out[key])
    return out


def _interleaved_schedule(S: int, V: int, M: int):
    """Host-side static schedule for interleaved 1F1B on a global
    combined-tick clock. Microbatches advance in groups of S per chunk
    (the Megatron ordering), giving closed-form tick times:

      fwd  of stage j=v*S+d, microbatch m=g*S+r:
          t = g*V*S + v*S + r + d
      bwd (mirrored), offset D = V*S - 1:
          t = D + g*V*S + (V-1-v)*S + r + (S-1-d)

    Every consecutive virtual stage runs EXACTLY one tick later, so
    the single +1-ring ppermute per tick delivers each activation the
    tick it is consumed — no receive buffering. Total ticks
    T = V*M + V*S + S - 2 (V=1 recovers the plain 1F1B's M + 2S - 2);
    per tick each device does ONE chunk fwd + ONE chunk bwd (1/V of a
    full stage), so the warmup/drain bubble shrinks ~V-fold relative
    to plain 1F1B at equal per-tick width.

    Returns ``(T, fwd_v, fwd_m, bwd_v, bwd_m)`` with (T, S) int32
    tables, -1 marking an idle sub-tick."""
    if M % S != 0:
        raise ValueError(
            f"interleaved 1F1B needs n_micro ({M}) divisible by pp ({S})"
        )
    D = V * S - 1
    T = V * M + V * S + S - 2
    fwd_v = -np.ones((T, S), np.int32)
    fwd_m = -np.ones((T, S), np.int32)
    bwd_v = -np.ones((T, S), np.int32)
    bwd_m = -np.ones((T, S), np.int32)
    for d in range(S):
        for g in range(M // S):
            for v in range(V):
                for r in range(S):
                    m = g * S + r
                    tf = g * V * S + v * S + r + d
                    tb = D + g * V * S + (V - 1 - v) * S + r + (S - 1 - d)
                    assert fwd_v[tf, d] < 0 and bwd_v[tb, d] < 0, "collision"
                    fwd_v[tf, d] = v
                    fwd_m[tf, d] = m
                    bwd_v[tb, d] = v
                    bwd_m[tb, d] = m
    return T, fwd_v, fwd_m, bwd_v, bwd_m


def _interleaved_ring_slots(S: int, V: int, M: int, tables=None) -> int:
    """Smallest ring size RV such that slot ``m % RV`` is collision-
    free among in-flight microbatches of any one chunk (checked
    exactly against the schedule's [t_fwd, t_bwd] lifetimes).
    ``tables``: pass the already-computed ``_interleaved_schedule``
    result to avoid rebuilding it."""
    T, fwd_v, fwd_m, bwd_v, bwd_m = (
        tables if tables is not None else _interleaved_schedule(S, V, M)
    )
    # Lifetimes grouped by (device, chunk) — only same-chunk
    # microbatches can collide on a slot.
    groups: dict = {}
    for d in range(S):
        for t in range(T):
            if fwd_v[t, d] >= 0:
                groups.setdefault((d, int(fwd_v[t, d])), {})[
                    int(fwd_m[t, d])
                ] = [t, None]
            if bwd_v[t, d] >= 0:
                groups[(d, int(bwd_v[t, d]))][int(bwd_m[t, d])][1] = t
    for RV in range(1, 3 * S + 2):
        ok = True
        for life in groups.values():
            for m, (t0, t1) in life.items():
                # Only later microbatches sharing the slot can overlap.
                m2 = m + RV
                while ok and m2 in life:
                    u0, u1 = life[m2]
                    if not (t1 < u0 or u1 < t0):
                        ok = False
                    m2 += RV
                if not ok:
                    break
            if not ok:
                break
        if ok:
            return RV
    return M  # fallback: one slot per microbatch


def _stacked_layer_init(cfg, key, use_moe: bool, n: int):
    if cfg.attn_impl == "ring":
        # The attention impl never changes the param tree; the flax
        # ring branch would open its own shard_map island (needs an
        # ambient mesh) just to trace init — init as dense instead.
        cfg = dataclasses.replace(cfg, attn_impl="dense")
    layer = EncoderLayer(cfg, use_moe=use_moe)
    sample_h = jnp.zeros((1, cfg.max_len, cfg.d_model), cfg.compute_dtype)
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: layer.init(k, sample_h)["params"])(keys)


def _init_backbone(cfg: TransformerConfig, k_embed, k_pos, k_dense, k_moe):
    """Shared pipeline backbone init: embeddings, final norm, and the
    dense / MoE layer stacks (separate stacks — their trees differ;
    each pp-sharded on its leading layer dim)."""
    pattern = _moe_pattern(cfg)
    n_dense = pattern.count(False)
    n_moe = pattern.count(True)
    d = cfg.d_model
    params = {
        "tok_embed": jax.random.normal(k_embed, (cfg.vocab_size, d)) * 0.02,
        "pos_embed": jax.random.normal(k_pos, (cfg.max_len, d)) * 0.02,
        "ln_scale": jnp.ones((d,)),
        "ln_bias": jnp.zeros((d,)),
    }
    if n_dense:
        params["layers"] = _stacked_layer_init(cfg, k_dense, False, n_dense)
    if n_moe:
        params["layers_moe"] = _stacked_layer_init(cfg, k_moe, True, n_moe)
    return params


def init_pipeline_lm(cfg: TransformerConfig, key: jax.Array):
    """Host-side init of a causal LM laid out for pipelining: the
    encoder layers' params are STACKED on a leading (layer) dim — the
    dim the pp sharding splits — plus replicated embedding / final
    norm / LM head tensors."""
    cfg = dataclasses.replace(cfg, causal=True)
    k_embed, k_pos, k_head, k_dense, k_moe = jax.random.split(key, 5)
    d = cfg.d_model
    params = _init_backbone(cfg, k_embed, k_pos, k_dense, k_moe)
    params["head_w"] = jax.random.normal(k_head, (d, cfg.vocab_size)) * (
        1.0 / np.sqrt(d)
    )
    params["head_b"] = jnp.zeros((cfg.vocab_size,))
    return params


def init_pipeline_classifier(cfg: TransformerConfig, key: jax.Array):
    """Pipeline layout of the BERT-style ``SequenceClassifier``: same
    stacked layers + embedding, with a pooler (tanh) + classifier head
    instead of the LM head."""
    k_embed, k_pos, k_pool, k_cls, k_dense, k_moe = jax.random.split(key, 6)
    d = cfg.d_model
    params = _init_backbone(cfg, k_embed, k_pos, k_dense, k_moe)
    params["pool_w"] = jax.random.normal(k_pool, (d, d)) * (1.0 / np.sqrt(d))
    params["pool_b"] = jnp.zeros((d,))
    params["cls_w"] = jax.random.normal(k_cls, (d, cfg.n_classes)) * (
        1.0 / np.sqrt(d)
    )
    params["cls_b"] = jnp.zeros((cfg.n_classes,))
    return params


# Per-leaf tp sharding of the stacked layer tree, keyed by the dim the
# head/column slice lives on (after the leading layer-stack dim).
_TP_LAYER_DIMS = {
    ("attn", "qkv", "kernel"): 3,   # (L, d, 3, h, hd) -> heads
    ("attn", "qkv", "bias"): 2,     # (L, 3, h, hd)
    ("attn", "proj", "kernel"): 1,  # (L, h, hd, d)
    ("mlp_in", "kernel"): 2,        # (L, d, ff)
    ("mlp_in", "bias"): 1,          # (L, ff)
    ("mlp_out", "kernel"): 1,       # (L, ff, d)
}


def _layer_leaf_spec(path_names: Tuple[str, ...], ndim: int) -> P:
    """Spec for one stacked-layer leaf: pp on the stack dim, tp on the
    leaf's head/column dim when it has one."""
    for key, dim in _TP_LAYER_DIMS.items():
        if path_names[-len(key):] == key:
            parts = [AXIS_PP] + [None] * (ndim - 1)
            parts[dim] = AXIS_TP
            return P(*parts)
    return P(AXIS_PP)


_MOE_EXPERT_LEAVES = ("moe_w_in", "moe_b_in", "moe_w_out", "moe_b_out")


def _path_names(path) -> Tuple[str, ...]:
    """Decode a tree_util key path into plain name strings — the one
    place for the idiom, so the grad-reduction and norm-weighting
    rules keyed off these names stay consistent with the sharding
    specs."""
    return tuple(
        str(getattr(p, "key", getattr(p, "name", p))) for p in path
    )


def _moe_leaf_spec(path_names: Tuple[str, ...]) -> P:
    """Spec for one stacked MoE-layer leaf: pp on the stack dim, and —
    for the expert weight tensors, whose dim 1 is the experts dim —
    ep, so experts shard ACROSS chips within a pipeline stage. The
    router/ln/attn params replicate over ep (every ep member routes
    identically)."""
    if path_names[-1] in _MOE_EXPERT_LEAVES:
        return P(AXIS_PP, AXIS_EP)
    return P(AXIS_PP)


def _param_specs(params) -> Any:
    """Per-leaf PartitionSpecs: layer stacks split over pp on their
    leading (layer) dim and over tp on head/column dims; MoE layer
    stacks split over pp (stack dim) and ep (experts dim of the expert
    weights — tp is rejected with MoE); everything else replicated."""
    from jax.tree_util import tree_map_with_path

    def layers_spec(path, leaf):
        return _layer_leaf_spec(_path_names(path), np.ndim(leaf))

    def moe_spec(path, leaf):
        return _moe_leaf_spec(_path_names(path))

    return {
        k: (
            tree_map_with_path(layers_spec, v)
            if k == "layers"
            else tree_map_with_path(moe_spec, v)
            if k == "layers_moe"
            else jax.tree.map(lambda _: P(), v)
        )
        for k, v in params.items()
    }


def place_pipeline_state(params, tx, mesh: Mesh) -> PipelineState:
    """device_put params into their pipeline layout and init the
    optimizer on the placed arrays. EVERY leaf (incl. optimizer
    scalars and the step counter) gets an explicit mesh-wide
    sharding: eager optax init would otherwise leave scalar leaves on
    one device, and a checkpoint restored against those shardings
    could not feed the pp shard_map step."""
    specs = _param_specs(params)
    sh = jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P),
    )
    params = jax.tree.map(jax.device_put, params, sh)
    opt_state = tx.init(params)
    opt_sh = jax.tree.map(
        lambda s: NamedSharding(mesh, s), _opt_specs(tx, opt_state, specs),
        is_leaf=lambda x: isinstance(x, P),
    )
    opt_state = jax.tree.map(jax.device_put, opt_state, opt_sh)
    return PipelineState(
        step=jax.device_put(jnp.zeros((), jnp.int32),
                            NamedSharding(mesh, P())),
        params=params,
        opt_state=opt_state,
    )


def make_pp_train_step(
    cfg: TransformerConfig,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    n_micro: int,
    head: str = "lm",
    mini_batch: Optional[int] = None,
    steps_per_call: int = 1,
    schedule: str = "gpipe",
    virtual_stages: int = 1,
) -> Callable[[PipelineState, DataBatch], Tuple[PipelineState, jax.Array]]:
    """Build the jitted pipelined train step over ``mesh`` (dp x pp x
    tp x sp x ep; other axes must be 1 for this trainer). sp > 1
    shards the sequence dim and requires ``attn_impl='ring'`` (the
    ring rides the same shard_map as the schedule). MoE stacks
    compose with sp when ``moe_group_size`` divides the per-shard
    sequence length (routing groups then tile inside sequence shards,
    keeping sp a pure layout choice), and with ep on the same mesh.

    ``head``: ``'lm'`` (next-token CE over the vocab, causal) or
    ``'classifier'`` (BERT-style pooler + class CE — the config-4
    workload, pipelined).

    ``mini_batch`` (per dp shard, like the DP trainer's): each step
    samples a contiguous random block of that many rows ON DEVICE
    (``utils.data.sample_minibatch``) and feeds it to the microbatch
    split — so it must divide into ``n_micro`` microbatches.
    ``steps_per_call=k`` scans k WHOLE schedules inside the one jitted
    call (fresh minibatch sample per step); with ``k == 1`` the step
    returns a scalar loss as before, otherwise ``(state, PpStepOut)``
    with per-step arrays."""
    if head not in ("lm", "classifier"):
        raise ValueError(f"unknown head {head!r}")
    if schedule not in ("gpipe", "1f1b"):
        raise ValueError(f"unknown pipeline schedule {schedule!r}")
    K = max(1, int(steps_per_call))
    if mini_batch is not None and mini_batch > 0:
        if mini_batch % n_micro != 0:
            raise ValueError(
                f"mini_batch={mini_batch} not divisible by "
                f"n_micro={n_micro}"
            )
    for ax in mesh.shape:
        if (ax not in (AXIS_DP, AXIS_PP, AXIS_TP, AXIS_EP, AXIS_SP)
                and mesh.shape[ax] != 1):
            raise ValueError(
                f"pipeline trainer supports dp x pp x tp x sp x ep only; "
                f"{ax}>1"
            )
    S = mesh.shape[AXIS_PP]
    T = mesh.shape[AXIS_TP]
    E = dict(mesh.shape).get(AXIS_EP, 1)
    SP = dict(mesh.shape).get(AXIS_SP, 1)
    if SP > 1 and cfg.attn_impl != "ring":
        raise ValueError(
            "mesh sp>1 shards the sequence: attention must be global "
            "over sp, so attn_impl must be 'ring' (dense/flash only see "
            "the local block)"
        )
    V = max(1, int(virtual_stages))
    if V > 1:
        # Interleaved 1F1B: V chunks per device, chunk-granular ticks
        # (the layer stack must be pre-permuted with
        # interleave_stack_permutation so device d's pp shard holds
        # stages {d, S+d, ...}).
        if schedule != "1f1b":
            raise ValueError(
                "virtual_stages>1 is the interleaved 1F1B schedule; "
                "set schedule='1f1b'"
            )
        if cfg.n_layers % (S * V) != 0:
            raise ValueError(
                f"n_layers={cfg.n_layers} not divisible by pp*virtual_"
                f"stages={S * V}"
            )
        if n_micro % S != 0:
            raise ValueError(
                f"interleaved 1F1B needs n_micro ({n_micro}) divisible "
                f"by pp ({S})"
            )
    if cfg.n_layers % max(1, S) != 0:
        raise ValueError(f"n_layers={cfg.n_layers} not divisible by pp={S}")
    if cfg.n_heads % max(1, T) != 0:
        raise ValueError(f"n_heads={cfg.n_heads} not divisible by tp={T}")
    if cfg.d_ff % max(1, T) != 0:
        raise ValueError(f"d_ff={cfg.d_ff} not divisible by tp={T}")
    # MoE composes when every stage sees the SAME dense/MoE layer
    # pattern (the two layer kinds live in separate pp-sharded
    # stacks); experts replicate within a stage — expert PARALLELISM
    # stays the GSPMD trainer's ep axis.
    pattern = _moe_pattern(cfg)
    has_moe = any(pattern)
    if V > 1 and has_moe:
        # Interleaved chunks are the schedule's unit: every one of the
        # S*V virtual stages must hold the same dense/MoE sequence so
        # (a) the per-kind stacks slice uniformly per chunk and (b)
        # the interleave permutation applies per stack.
        lps_c = cfg.n_layers // (S * V)
        chunk_patterns = [pattern[j * lps_c:(j + 1) * lps_c]
                          for j in range(S * V)]
        if any(cp != chunk_patterns[0] for cp in chunk_patterns):
            raise ValueError(
                f"interleaved 1F1B with MoE needs the dense/MoE "
                f"pattern {pattern} uniform across all pp*virtual_"
                f"stages={S * V} chunks; choose moe_every/n_layers "
                "accordingly"
            )
    if E > 1 and not has_moe:
        raise ValueError(
            "mesh ep>1 needs MoE layers (n_experts>0) — there are no "
            "experts to shard"
        )
    if has_moe:
        if T > 1:
            raise ValueError(
                "pp x tp with MoE layers is not supported; use tp=1 "
                "(experts shard over the ep axis instead)"
            )
        # sp>1 composes with MoE when moe_group_size tiles the
        # per-shard sequence (checked at trace time in moe_apply —
        # reached from every walk — where the shard's seq length is
        # known): routing groups then
        # sit INSIDE sequence-shard rows, so the sp>1 group partition
        # is exactly the sp=1 partition and sp stays a pure layout
        # choice. Each member's local aux is its per-shard share of
        # the global (sum over sp / SP) load-balance objective.
        if E > 1 and cfg.n_experts % E != 0:
            raise ValueError(
                f"n_experts={cfg.n_experts} not divisible by ep={E}"
            )
        lps = cfg.n_layers // max(1, S)
        stage_patterns = [pattern[s * lps:(s + 1) * lps] for s in range(S)]
        if any(sp != stage_patterns[0] for sp in stage_patterns):
            raise ValueError(
                f"MoE layer pattern {pattern} is not uniform across "
                f"pp={S} stages; choose moe_every/n_layers so every "
                "stage holds the same dense/MoE sequence"
            )
        stage_pattern = stage_patterns[0]
    if head == "lm":
        cfg = dataclasses.replace(cfg, causal=True)
    dt = cfg.compute_dtype

    layer_fwd = lambda lp, h: _layer_forward(cfg, lp, h)
    if cfg.remat:
        layer_fwd = jax.checkpoint(layer_fwd)

    def stage_fn(local_layers, h):
        def body(h, lp):
            return layer_fwd(lp, h), None

        h, _ = jax.lax.scan(body, h, local_layers)
        return h

    if has_moe:
        def moe_apply(lp, h, token_w):
            # Split the layer: the attention half is the SAME manual
            # math as the dense layers (so its ring branch works
            # under sp — a flax-module attention here would silently
            # fall back to block-local dense inside the Manual-axes
            # shard_map), and the expert FFN runs the layout picked by
            # moe_ep_dispatch (no collectives at ep=1; experts
            # pre-sliced over the ep axis by shard_map otherwise).
            if SP > 1 and h.shape[1] % max(1, cfg.moe_group_size):
                # Trace-time contract: groups must tile the per-shard
                # sequence rows so every group lives inside ONE sp
                # shard and both sp=1 and sp>1 pick g=moe_group_size —
                # the condition under which sp is a pure layout choice
                # for routing/capacity/aux (any other g silently
                # changes the group partition vs sp=1).
                raise ValueError(
                    f"pp x sp with MoE needs moe_group_size "
                    f"({cfg.moe_group_size}) dividing the per-shard "
                    f"sequence length ({h.shape[1]}); set "
                    "moe_group_size to a divisor of seq/sp"
                )
            x_mid = _attn_half(cfg, lp, h)
            h_ln = _ln(lp["ln_mlp"], x_mid, dt)
            moe_out, aux, dropped, routed = _moe_ffn_ep_dispatch(
                cfg, lp["moe"], h_ln, token_w, E
            )
            return x_mid + moe_out, aux, dropped, routed

        if cfg.remat:
            moe_apply = jax.checkpoint(moe_apply)

        def walk_moe(pattern_, layers, layers_moe, h, token_w):
            """Unrolled dense/MoE layer walk over ``pattern_``,
            indexing each kind's stacked rows in order — the ONE
            stage-body definition shared by the per-stage walk
            (stage_fn_moe) and the interleaved per-chunk walk
            (chunk_forward)."""
            aux = jnp.zeros((), jnp.float32)
            dropped = jnp.zeros((), jnp.float32)
            routed = jnp.zeros((), jnp.float32)
            jd = jm = 0
            for is_moe in pattern_:
                if is_moe:
                    lp = jax.tree.map(lambda a: a[jm], layers_moe)
                    h, a, dr, rt = moe_apply(lp, h, token_w)
                    aux = aux + a
                    dropped = dropped + dr
                    routed = routed + rt
                    jm += 1
                else:
                    lp = jax.tree.map(lambda a: a[jd], layers)
                    h = layer_fwd(lp, h)
                    jd += 1
            return h, aux, dropped, routed

        def stage_fn_moe(params, h, token_w):
            return walk_moe(stage_pattern, params.get("layers"),
                            params.get("layers_moe"), h, token_w)

    def embed(params, ids):
        s = ids.shape[1]
        if SP > 1:
            # ids hold this member's SEQUENCE shard: its positional
            # rows start at sp_index * s_local.
            off = jax.lax.axis_index(AXIS_SP) * s
            pe = jax.lax.dynamic_slice_in_dim(params["pos_embed"], off, s, 0)
        else:
            pe = params["pos_embed"][:s]
        h = params["tok_embed"][ids] + pe[None]
        return h.astype(dt)

    def head_loss(params, h, y, w):
        hf = _ln({"scale": params["ln_scale"], "bias": params["ln_bias"]},
                 h, jnp.float32)
        if head == "classifier":
            # Pooler in the model's compute dtype, classifier logits in
            # f32 — matching the flax SequenceClassifier exactly
            # (transformer.py: pooler Dense dtype=compute_dtype,
            # classifier Dense dtype=float32), so pp-trained params see
            # the same numerics the module applies at transform time.
            if SP > 1:
                # Mean-pool over the GLOBAL sequence: psum the local
                # sums (identity backward — each member's per-token
                # grads are its true share). The pooled stream is then
                # sp-REPLICATED, so the head params would see their
                # full gradient on every member: pre-scale their
                # cotangents by 1/sp so the trainer's sp psum is exact.
                pooled_in = _sp_reduce(hf.astype(dt).sum(1)) / (
                    h.shape[1] * SP
                )
                pool_w = _scale_grad(params["pool_w"], 1.0 / SP)
                pool_b = _scale_grad(params["pool_b"], 1.0 / SP)
                cls_w = _scale_grad(params["cls_w"], 1.0 / SP)
                cls_b = _scale_grad(params["cls_b"], 1.0 / SP)
            else:
                pooled_in = hf.astype(dt).mean(1)
                pool_w, pool_b = params["pool_w"], params["pool_b"]
                cls_w, cls_b = params["cls_w"], params["cls_b"]
            pooled = jnp.tanh(
                pooled_in @ pool_w.astype(dt) + pool_b.astype(dt)
            )
            logits = pooled.astype(jnp.float32) @ cls_w + cls_b
            per_ex = optax.softmax_cross_entropy_with_integer_labels(logits, y)
        else:
            logits = hf @ params["head_w"] + params["head_b"]
            per_tok = optax.softmax_cross_entropy_with_integer_labels(logits, y)
            if SP > 1:
                # Per-example mean over the GLOBAL sequence. Everything
                # upstream stays per-token (the head matmul runs on
                # local tokens), so all param grads remain honest
                # per-shard shares that the sp psum completes.
                per_ex = _sp_reduce(per_tok.sum(-1)) / (
                    per_tok.shape[-1] * SP
                )
            else:
                per_ex = per_tok.mean(-1)
        return jnp.sum(per_ex * w), jnp.sum(w)

    ring = [(i, (i + 1) % S) for i in range(S)]

    def schedule_loss(params, x, y, w):
        """The full GPipe schedule's global weighted-mean loss (plus
        the MoE aux term and drop fraction) — differentiated by
        local_step, called forward-only by the eval step."""
        stage = jax.lax.axis_index(AXIS_PP)
        b_local, s = x.shape
        if b_local % n_micro != 0:
            raise ValueError(
                f"local batch {b_local} not divisible by n_micro={n_micro}"
            )
        mb = b_local // n_micro
        micro_x = x.reshape(n_micro, mb, s)
        # lm targets are token-level (b, s); classifier labels (b,).
        micro_y = y.reshape((n_micro, mb) + y.shape[1:])
        micro_w = w.reshape(n_micro, mb)

        def pipeline_loss(params):
            def tick(carry, t):
                h_prev, num, den, aux, dropped, routed = carry
                inj = jnp.clip(t, 0, n_micro - 1)
                # Only stage 0 embeds and only the last stage (inside
                # its valid drain window) runs the vocab-sized head —
                # lax.cond skips the dead branch at runtime instead of
                # computing it everywhere and masking to zero (the
                # head matmul + its backward dominate for real vocabs).
                h_in = jax.lax.cond(
                    stage == 0,
                    lambda: embed(params, micro_x[inj]),
                    lambda: h_prev,
                )
                if has_moe:
                    # The microbatch THIS stage processes at tick t was
                    # injected at t - stage; bubble ticks (no valid
                    # microbatch) get all-zero token weights so their
                    # garbage activations never touch routing, capacity
                    # or the aux loss.
                    m_in = t - stage
                    mi_in = jnp.clip(m_in, 0, n_micro - 1)
                    valid_in = ((m_in >= 0) & (m_in < n_micro)).astype(
                        micro_w.dtype
                    )
                    tw = jnp.broadcast_to(
                        (micro_w[mi_in] * valid_in)[:, None], (mb, s)
                    )
                    h_out, aux_t, dr_t, rt_t = stage_fn_moe(params, h_in, tw)
                    aux = aux + aux_t
                    dropped = dropped + dr_t
                    routed = routed + rt_t
                else:
                    h_out = stage_fn(params["layers"], h_in)
                m = t - (S - 1)
                mi = jnp.clip(m, 0, n_micro - 1)
                use = (m >= 0) & (m < n_micro) & (stage == S - 1)
                n_, d_ = jax.lax.cond(
                    use,
                    lambda: head_loss(params, h_out, micro_y[mi], micro_w[mi]),
                    lambda: (jnp.zeros(()), jnp.zeros(())),
                )
                num = num + n_
                den = den + d_
                h_next = jax.lax.ppermute(h_out, AXIS_PP, ring)
                return (h_next, num, den, aux, dropped, routed), None

            init_h = jnp.zeros((mb, s, cfg.d_model), dt)
            zero = jnp.zeros(())
            (_, num, den, aux, dropped, routed), _ = jax.lax.scan(
                tick,
                (init_h, zero, zero, zero, zero, zero),
                jnp.arange(n_micro + S - 1),
            )
            num_g = jax.lax.psum(num, (AXIS_PP, AXIS_DP))
            den_g = jax.lax.psum(den, (AXIS_PP, AXIS_DP))
            task = num_g / jnp.maximum(den_g, 1.0)
            loss = task
            examples = den_g
            if has_moe:
                # Sum over stages/layers (psum pp — stages hold
                # disjoint MoE layers), mean over microbatches and dp
                # shards: the pipelined analog of the GSPMD trainer's
                # batch-mean sown aux. With sp>1 each member's local
                # aux covers its DISJOINT sequence-shard groups:
                # _sp_reduce (psum fwd / identity bwd) globalizes the
                # value while each member's backward keeps its honest
                # per-shard share (completed by the trainer's sp grad
                # psum), and /SP converts the sp-sum of local group
                # means into the global group mean.
                sp_axes = (AXIS_SP,) if SP > 1 else ()
                aux_g = jax.lax.psum(
                    _sp_reduce(aux) if SP > 1 else aux,
                    (AXIS_PP, AXIS_DP),
                )
                dp_n = _axis_size(AXIS_DP)
                loss = loss + aux_g / (n_micro * dp_n * SP)
                dropped_g = jax.lax.psum(
                    dropped, (AXIS_PP, AXIS_DP) + sp_axes
                )
                routed_g = jax.lax.psum(
                    routed, (AXIS_PP, AXIS_DP) + sp_axes
                )
                drop_fraction = dropped_g / jnp.maximum(routed_g, 1.0)
            else:
                drop_fraction = jnp.zeros(())
            # aux triple: (drop_fraction, task-only loss, examples) —
            # the eval path reports the task loss (the DP eval
            # excludes sown aux objectives from the validation signal
            # too); examples is the global weighted row count actually
            # trained on this step (== mini_batch rows when sampling).
            return loss, (drop_fraction, task, examples)

        return pipeline_loss(params)

    def one_f_one_b_grads(params, x, y, w):
        """1F1B schedule with a MANUAL backward: loss + gradients of
        the same math as ``schedule_loss`` (exactness-tested), with
        activation memory O(pp) instead of the O(n_micro) that
        autodiff-through-the-GPipe-scan stores.

        Combined-tick form: T = M + 2(S-1) ticks; at tick t stage s
        forwards microbatch ``t - s`` and backwards microbatch
        ``t - 2(S-1) + s`` (the last stage backwards a microbatch the
        same tick it forwards it). Each backward re-runs the stage
        forward under ``jax.vjp`` — residuals live only within the
        tick — so only the stage INPUTS of in-flight microbatches are
        stored, in a ring of ``2S-1`` slots. FLOPs match remat-GPipe
        (1 forward + recompute-backward per microbatch per stage);
        ticks are (M+2S-2) vs GPipe's (M+S-1) fused fwd+bwd ticks.

        Gradients accumulate for the SUM of weighted losses (num) and
        are scaled by the global weight den afterwards (den is
        params-independent), exactly reproducing num_g/max(den_g, 1).

        MoE stacks compose: each valid tick processes a REAL
        microbatch (bubbles are cond-skipped, so no zero-token-weight
        masking is needed, unlike the GPipe scan), the sown aux loss
        and drop counts accumulate in the forward sub-ticks, and the
        backward seeds the aux output with ``den_safe/(n_micro*dp)``
        so ONE pullback covers both the task path (later divided by
        den) and the aux path (whose GPipe weight is 1/(n_micro*dp))
        — den is params-independent and computable up front.
        """
        stage = jax.lax.axis_index(AXIS_PP)
        b_local, s_len = x.shape
        if b_local % n_micro != 0:
            raise ValueError(
                f"local batch {b_local} not divisible by n_micro={n_micro}"
            )
        mb = b_local // n_micro
        micro_x = x.reshape(n_micro, mb, s_len)
        micro_y = y.reshape((n_micro, mb) + y.shape[1:])
        micro_w = w.reshape(n_micro, mb)
        M = n_micro
        R = 2 * S - 1  # ring capacity >= max in-flight microbatches
        fwd_ring = [(i, (i + 1) % S) for i in range(S)]
        bwd_ring = [(i, (i - 1) % S) for i in range(S)]

        # den is the global weight sum — schedule-independent (w is
        # replicated across pp), so the aux seed below can use it.
        den_g = jax.lax.psum(jnp.sum(w), AXIS_DP)
        den_safe = jnp.maximum(den_g, 1.0)
        dp_n = _axis_size(AXIS_DP)
        # With sp>1 each member's local aux is a per-shard share of
        # the global aux = (sum over sp of local) / SP, so its
        # gradient weight carries an extra 1/SP.
        aux_seed = den_safe / (n_micro * dp_n * SP)

        def stage_out(p, h_in, tw):
            """(h_out, aux, dropped, routed) — zeros for dense."""
            if has_moe:
                return stage_fn_moe(p, h_in, tw)
            z = jnp.zeros(())
            return stage_fn(p["layers"], h_in), z, z, z

        def tick_outs(p, h_in, tw, mi):
            """Stage forward + (last-stage-only) head num, as ONE
            differentiable function — the sp>1 tick path, where the
            stage body contains ring-attention ppermutes that must
            execute UNCONDITIONALLY: a collective inside a lax.cond
            whose predicate varies over pp deadlocks/miscomputes (the
            sp members of a skipping stage never enter the exchange).
            Masking moves to the VJP seeds instead of branch choice.
            Returns the MoE drop metrics too — the forward sub-tick
            accumulates them (validity-masked); the backward vjp runs
            over the first three outputs only."""
            h_out, aux, dr_, rt_ = stage_out(p, h_in, tw)
            num = jax.lax.cond(
                stage == S - 1,
                lambda: head_loss(p, h_out, micro_y[mi], micro_w[mi])[0],
                lambda: jnp.zeros(()),
            )
            return h_out, num, aux, dr_, rt_

        def last_outs(p, h_in, yy, ww, tw):
            """(num, aux) of the last stage — the two differentiated
            outputs; den/drop-counts are params-independent."""
            h_out, aux, _, _ = stage_out(p, h_in, tw)
            num, _ = head_loss(p, h_out, yy, ww)
            return num, aux

        def mid_outs(p, h_in, tw):
            h_out, aux, _, _ = stage_out(p, h_in, tw)
            return h_out, aux

        def tw_of(ww):
            return jnp.broadcast_to(ww[:, None], (mb, s_len))

        zero_grads = jax.tree.map(jnp.zeros_like, params)

        def tick(carry, t):
            ring, fwd_ch, bwd_ch, grads, num, aux, dr, rt = carry

            # ---- forward sub-tick: microbatch t - stage ----
            m_f = t - stage
            fwd_valid = (m_f >= 0) & (m_f < M)
            mi_f = jnp.clip(m_f, 0, M - 1)

            def do_fwd():
                h_in = jax.lax.cond(
                    stage == 0,
                    lambda: embed(params, micro_x[mi_f]),
                    lambda: fwd_ch,
                )
                h_out, a_, dr_, rt_ = stage_out(params, h_in,
                                                tw_of(micro_w[mi_f]))
                n_ = jax.lax.cond(
                    stage == S - 1,
                    lambda: head_loss(params, h_out,
                                      micro_y[mi_f], micro_w[mi_f])[0],
                    lambda: jnp.zeros(()),
                )
                return h_in, h_out, n_, a_, dr_, rt_

            def skip_fwd():
                z = jnp.zeros((mb, s_len, cfg.d_model), dt)
                zs = jnp.zeros(())
                return z, z, zs, zs, zs, zs

            h_in, h_out, n_, a_, dr_, rt_ = jax.lax.cond(
                fwd_valid, do_fwd, skip_fwd
            )
            num = num + n_
            aux = aux + a_
            dr = dr + dr_
            rt = rt + rt_
            ring = jnp.where(
                fwd_valid,
                jax.lax.dynamic_update_slice(
                    ring, h_in[None], (mi_f % R, 0, 0, 0)
                ),
                ring,
            )

            # ---- backward sub-tick: microbatch t - 2(S-1) + stage ----
            m_b = t - 2 * (S - 1) + stage
            bwd_valid = (m_b >= 0) & (m_b < M)
            mi_b = jnp.clip(m_b, 0, M - 1)

            def do_bwd():
                h_saved = jax.lax.dynamic_index_in_dim(
                    ring, mi_b % R, axis=0, keepdims=False
                )
                tw_b = tw_of(micro_w[mi_b])

                def bwd_last():
                    _, pull = jax.vjp(
                        lambda p, h: last_outs(p, h, micro_y[mi_b],
                                               micro_w[mi_b], tw_b),
                        params, h_saved,
                    )
                    # Seeds: d(num)=1; aux pre-scaled by den_safe so
                    # the final /den_safe nets the GPipe aux weight.
                    return pull((jnp.ones(()), aux_seed))

                def bwd_mid():
                    _, pull = jax.vjp(
                        lambda p, h: mid_outs(p, h, tw_b),
                        params, h_saved,
                    )
                    return pull((bwd_ch, aux_seed))

                ct_params, ct_h = jax.lax.cond(
                    stage == S - 1, bwd_last, bwd_mid
                )
                # Stage 0 folds its input cotangent into the embedding
                # tables (its "previous stage").
                def embed_grads():
                    _, pull = jax.vjp(
                        lambda p: embed(p, micro_x[mi_b]), params
                    )
                    return pull(ct_h)[0]

                ct_params = jax.lax.cond(
                    stage == 0,
                    lambda: jax.tree.map(jnp.add, ct_params,
                                         embed_grads()),
                    lambda: ct_params,
                )
                return ct_params, ct_h

            def skip_bwd():
                return zero_grads, jnp.zeros((mb, s_len, cfg.d_model), dt)

            ct_params, ct_h = jax.lax.cond(bwd_valid, do_bwd, skip_bwd)
            grads = jax.tree.map(jnp.add, grads, ct_params)

            fwd_next = jax.lax.ppermute(h_out, AXIS_PP, fwd_ring)
            bwd_next = jax.lax.ppermute(ct_h, AXIS_PP, bwd_ring)
            return (ring, fwd_next, bwd_next, grads, num, aux, dr, rt), None

        def tick_masked(carry, t):
            """The sp>1 tick: identical math to ``tick``, but the stage
            body and ONE unified vjp run UNCONDITIONALLY every tick,
            with validity masking the accumulators and the vjp seeds
            instead of choosing a lax.cond branch. Required because the
            stage body contains ring-attention ppermutes over sp and a
            collective inside a cond whose predicate varies over pp
            deadlocks/miscomputes (the sp members of a skipping stage
            never enter the exchange — reproduced on the CPU backend).
            Costs bubble-tick compute, exactly like the GPipe scan."""
            ring, fwd_ch, bwd_ch, grads, num, aux, dr, rt = carry

            # ---- forward sub-tick: microbatch t - stage ----
            m_f = t - stage
            fwd_valid = (m_f >= 0) & (m_f < M)
            mi_f = jnp.clip(m_f, 0, M - 1)
            fv = fwd_valid.astype(jnp.float32)

            # embed has no collectives, so the stage-0 cond is safe
            # (unlike the stage body below, which must run everywhere).
            h_in = jax.lax.cond(
                stage == 0,
                lambda: embed(params, micro_x[mi_f]),
                lambda: fwd_ch,
            )
            h_out, n_, a_, dr_, rt_ = tick_outs(
                params, h_in, tw_of(micro_w[mi_f]), mi_f
            )
            num = num + fv * n_
            aux = aux + fv * a_
            # Bubble ticks route a REAL microbatch's token weights
            # over garbage activations (the body must run for its
            # collectives): validity-mask the drop metrics here, where
            # the GPipe scan masks via zeroed token weights instead.
            dr = dr + fv * dr_
            rt = rt + fv * rt_
            ring = jnp.where(
                fwd_valid,
                jax.lax.dynamic_update_slice(
                    ring, h_in[None], (mi_f % R, 0, 0, 0)
                ),
                ring,
            )

            # ---- backward sub-tick: microbatch t - 2(S-1) + stage ----
            m_b = t - 2 * (S - 1) + stage
            bwd_valid = (m_b >= 0) & (m_b < M)
            mi_b = jnp.clip(m_b, 0, M - 1)
            h_saved = jax.lax.dynamic_index_in_dim(
                ring, mi_b % R, axis=0, keepdims=False
            )
            tw_b = tw_of(micro_w[mi_b])
            _, pull = jax.vjp(
                lambda p, h: tick_outs(p, h, tw_b, mi_b)[:3],
                params, h_saved,
            )
            # Seeds do the masking (pullbacks are linear, so zero seeds
            # yield zero cotangents): the last stage's h_out cotangent
            # comes only through its own head term; mid stages seed
            # h_out with the ct arriving on the backward ring. The num
            # seed is harmless on mid stages (their num branch is the
            # zero function).
            bv = bwd_valid.astype(jnp.float32)
            seed_h = (
                jnp.where(bwd_valid & (stage != S - 1), 1.0, 0.0)
                .astype(dt) * bwd_ch
            )
            ct_params, ct_h = pull((seed_h, bv, bv * aux_seed))

            def embed_grads():
                _, epull = jax.vjp(
                    lambda p: embed(p, micro_x[mi_b]), params
                )
                return epull(ct_h)[0]

            # embed's vjp has no collectives, so this cond is safe.
            ct_params = jax.lax.cond(
                stage == 0,
                lambda: jax.tree.map(jnp.add, ct_params, embed_grads()),
                lambda: ct_params,
            )
            grads = jax.tree.map(jnp.add, grads, ct_params)

            fwd_next = jax.lax.ppermute(h_out, AXIS_PP, fwd_ring)
            bwd_next = jax.lax.ppermute(ct_h, AXIS_PP, bwd_ring)
            return (ring, fwd_next, bwd_next, grads, num, aux, dr, rt), None

        init = (
            jnp.zeros((R, mb, s_len, cfg.d_model), dt),
            jnp.zeros((mb, s_len, cfg.d_model), dt),
            jnp.zeros((mb, s_len, cfg.d_model), dt),
            zero_grads,
            jnp.zeros(()), jnp.zeros(()), jnp.zeros(()), jnp.zeros(()),
        )
        (_, _, _, grads, num, aux, dr, rt), _ = jax.lax.scan(
            tick_masked if SP > 1 else tick, init,
            jnp.arange(M + 2 * (S - 1))
        )
        num_g = jax.lax.psum(num, (AXIS_PP, AXIS_DP))
        loss = num_g / den_safe
        if has_moe:
            # Same accounting as the GPipe schedule_loss: stages hold
            # disjoint MoE layers (psum over pp), mean over
            # microbatches and dp shards; sp members hold disjoint
            # sequence-shard groups (sum over sp / SP).
            sp_axes = (AXIS_SP,) if SP > 1 else ()
            aux_g = jax.lax.psum(aux, (AXIS_PP, AXIS_DP) + sp_axes)
            loss = loss + aux_g / (n_micro * dp_n * SP)
            dr_g = jax.lax.psum(dr, (AXIS_PP, AXIS_DP) + sp_axes)
            rt_g = jax.lax.psum(rt, (AXIS_PP, AXIS_DP) + sp_axes)
            drop_fraction = dr_g / jnp.maximum(rt_g, 1.0)
        else:
            drop_fraction = jnp.zeros(())
        grads = jax.tree.map(lambda g: g / den_safe, grads)
        return loss, den_g, grads, drop_fraction

    if V > 1:
        T_ticks, _fv, _fm, _bv, _bm = _interleaved_schedule(S, V, n_micro)
        RV = _interleaved_ring_slots(
            S, V, n_micro, tables=(T_ticks, _fv, _fm, _bv, _bm)
        )
        fv_tab, fm_tab = jnp.asarray(_fv), jnp.asarray(_fm)
        bv_tab, bm_tab = jnp.asarray(_bv), jnp.asarray(_bm)
        lps_i = cfg.n_layers // (S * V)
        if has_moe:
            chunk_pattern = pattern[:lps_i]
            nd_c = chunk_pattern.count(False)
            nm_c = chunk_pattern.count(True)

        def chunk_params(p, v):
            """Device-local chunk v's layer rows. The dynamic slice
            transposes to a dynamic-update into zeros, so each
            backward lands its gradient on the right chunk rows. With
            MoE, each kind's stack slices by its own per-chunk count
            (the per-chunk-uniform pattern makes chunk rows
            contiguous in both stacks)."""
            if not has_moe:
                return jax.tree.map(
                    lambda a: jax.lax.dynamic_slice_in_dim(
                        a, v * lps_i, lps_i, 0
                    ),
                    p["layers"],
                )
            cp = {}
            if nd_c:
                cp["layers"] = jax.tree.map(
                    lambda a: jax.lax.dynamic_slice_in_dim(
                        a, v * nd_c, nd_c, 0
                    ),
                    p["layers"],
                )
            if nm_c:
                cp["layers_moe"] = jax.tree.map(
                    lambda a: jax.lax.dynamic_slice_in_dim(
                        a, v * nm_c, nm_c, 0
                    ),
                    p["layers_moe"],
                )
            return cp

        def chunk_forward(p, v, h, tw):
            """One chunk's stage walk — the interleaved twin of
            stage_fn/stage_fn_moe, shared by the train ticks and the
            forward-only eval. Returns (h, aux, dropped, routed);
            dense chunks return zero observables."""
            cp = chunk_params(p, v)
            if not has_moe:
                z = jnp.zeros((), jnp.float32)
                return stage_fn(cp, h), z, z, z
            return walk_moe(chunk_pattern, cp.get("layers"),
                            cp.get("layers_moe"), h, tw)

    def interleaved_grads(params, x, y, w):
        """Interleaved (virtual-stage) 1F1B: each device owns V chunks
        of lps = L/(S*V) layers (virtual stage j = v*S + d), and each
        combined tick runs ONE chunk forward + ONE chunk backward per
        the static ``_interleaved_schedule`` tables — 1/V of a plain
        1F1B tick's width, so the warmup/drain bubble shrinks ~V-fold:
        T = V*M + V*S + S - 2 chunk-ticks of (1 fwd + 1 recompute-bwd)
        chunk vs plain 1F1B's (M + 2S - 2) ticks of V-chunk width.
        Stage inputs persist in a (V, RV) ring (RV from the schedule's
        exact in-flight lifetimes): activation memory stays O(V*S),
        independent of M. Same gradient math as the other schedules
        (exactness-tested); the layer stack must be in the
        ``interleave_stack_permutation`` order."""
        stage = jax.lax.axis_index(AXIS_PP)
        b_local, s_len = x.shape
        if b_local % n_micro != 0:
            raise ValueError(
                f"local batch {b_local} not divisible by n_micro={n_micro}"
            )
        mb = b_local // n_micro
        micro_x = x.reshape(n_micro, mb, s_len)
        micro_y = y.reshape((n_micro, mb) + y.shape[1:])
        micro_w = w.reshape(n_micro, mb)
        M = n_micro
        fwd_ring = [(i, (i + 1) % S) for i in range(S)]
        bwd_ring = [(i, (i - 1) % S) for i in range(S)]
        dp_n = _axis_size(AXIS_DP)
        if has_moe:
            # den BEFORE the scan, like plain 1F1B: the aux seeds
            # consume it, which both weights the aux gradient
            # correctly (net 1/(n_micro*dp*SP) after the final
            # /den_safe) and — as a side effect — serializes the dp
            # psum against the scan's collectives (see the dense-path
            # barrier note below).
            den_pre = jax.lax.psum(jnp.sum(w), AXIS_DP)
            den_pre_safe = jnp.maximum(den_pre, 1.0)
            aux_seed = den_pre_safe / (n_micro * dp_n * SP)
        else:
            aux_seed = jnp.zeros(())

        def tw_of(mi):
            return (jnp.broadcast_to(micro_w[mi][:, None], (mb, s_len))
                    if has_moe else None)

        def chunk_outs(p, h_in, v, mi):
            """One chunk's forward + (final-virtual-stage-only) head
            num + MoE observables — the differentiable unit of the
            interleaved tick (the per-tick vjp runs over the first
            THREE outputs; drop counts are metrics only)."""
            h_out, aux, dr_, rt_ = chunk_forward(p, v, h_in, tw_of(mi))
            num = jax.lax.cond(
                (v == V - 1) & (stage == S - 1),
                lambda: head_loss(p, h_out, micro_y[mi], micro_w[mi])[0],
                lambda: jnp.zeros(()),
            )
            return h_out, num, aux, dr_, rt_

        zero_grads = jax.tree.map(jnp.zeros_like, params)

        def tick(carry, t):
            ring, fwd_ch, bwd_ch, grads, num, aux, dr, rt = carry

            vf = fv_tab[t, stage]
            mf = fm_tab[t, stage]
            fwd_valid = vf >= 0
            vf_c = jnp.clip(vf, 0, V - 1)
            mf_c = jnp.clip(mf, 0, M - 1)

            def do_fwd():
                h_in = jax.lax.cond(
                    (vf_c == 0) & (stage == 0),
                    lambda: embed(params, micro_x[mf_c]),
                    lambda: fwd_ch,
                )
                h_out, n_, a_, dr_, rt_ = chunk_outs(params, h_in,
                                                     vf_c, mf_c)
                return h_in, h_out, n_, a_, dr_, rt_

            def skip_fwd():
                z = jnp.zeros((mb, s_len, cfg.d_model), dt)
                zs = jnp.zeros(())
                return z, z, zs, zs, zs, zs

            h_in, h_out, n_, a_, dr_, rt_ = jax.lax.cond(
                fwd_valid, do_fwd, skip_fwd
            )
            num = num + n_
            aux = aux + a_
            dr = dr + dr_
            rt = rt + rt_
            ring = jnp.where(
                fwd_valid,
                jax.lax.dynamic_update_slice(
                    ring, h_in[None, None], (vf_c, mf_c % RV, 0, 0, 0)
                ),
                ring,
            )

            vb = bv_tab[t, stage]
            mb_i = bm_tab[t, stage]
            bwd_valid = vb >= 0
            vb_c = jnp.clip(vb, 0, V - 1)
            mb_c = jnp.clip(mb_i, 0, M - 1)

            def do_bwd():
                h_saved = jax.lax.dynamic_slice(
                    ring, (vb_c, mb_c % RV, 0, 0, 0),
                    (1, 1, mb, s_len, cfg.d_model),
                )[0, 0]
                is_last = (vb_c == V - 1) & (stage == S - 1)
                _, pull = jax.vjp(
                    lambda p, h: chunk_outs(p, h, vb_c, mb_c)[:3],
                    params, h_saved,
                )
                # Last virtual stage: h_out ct comes only through its
                # own head term; elsewhere seed with the backward-ring
                # ct (the num seed is harmless off the last stage —
                # that branch is the zero function there). The aux
                # seed covers the MoE load-balance path (zero for
                # dense chunks).
                seed_h = jnp.where(is_last, 0.0, 1.0).astype(dt) * bwd_ch
                ct_params, ct_h = pull((seed_h, jnp.ones(()), aux_seed))

                def embed_grads():
                    _, epull = jax.vjp(
                        lambda p: embed(p, micro_x[mb_c]), params
                    )
                    return epull(ct_h)[0]

                ct_params = jax.lax.cond(
                    (vb_c == 0) & (stage == 0),
                    lambda: jax.tree.map(jnp.add, ct_params,
                                         embed_grads()),
                    lambda: ct_params,
                )
                return ct_params, ct_h

            def skip_bwd():
                return zero_grads, jnp.zeros((mb, s_len, cfg.d_model), dt)

            ct_params, ct_h = jax.lax.cond(bwd_valid, do_bwd, skip_bwd)
            grads = jax.tree.map(jnp.add, grads, ct_params)

            fwd_next = jax.lax.ppermute(h_out, AXIS_PP, fwd_ring)
            bwd_next = jax.lax.ppermute(ct_h, AXIS_PP, bwd_ring)
            return (ring, fwd_next, bwd_next, grads, num, aux, dr, rt), None

        def tick_masked(carry, t):
            """The sp>1 interleaved tick: same discipline as the plain
            1F1B ``tick_masked`` — the chunk body (whose ring
            attention ppermutes over sp must execute on EVERY tick;
            a collective under a pp-varying lax.cond deadlocks or
            miscomputes) and one unified per-tick vjp run
            unconditionally, with validity masking the accumulators
            and the vjp seeds. chunk_outs' inner head cond is safe:
            its predicate (vf==V-1 & stage==S-1) is uniform across sp
            members, and invalid ticks clip vf to 0 != V-1 (V>=2), so
            the head never fires on garbage."""
            ring, fwd_ch, bwd_ch, grads, num, aux, dr, rt = carry

            vf = fv_tab[t, stage]
            mf = fm_tab[t, stage]
            fwd_valid = vf >= 0
            vf_c = jnp.clip(vf, 0, V - 1)
            mf_c = jnp.clip(mf, 0, M - 1)
            fv = fwd_valid.astype(jnp.float32)

            # embed has no collectives: the cond is safe (and on
            # invalid ticks h_in is garbage that nothing consumes —
            # the ring only stores it under fwd_valid).
            h_in = jax.lax.cond(
                (vf_c == 0) & (stage == 0),
                lambda: embed(params, micro_x[mf_c]),
                lambda: fwd_ch,
            )
            h_out, n_, a_, dr_, rt_ = chunk_outs(params, h_in, vf_c, mf_c)
            num = num + fv * n_
            # Bubble ticks route real token weights over garbage
            # activations (the body must run for its collectives):
            # validity-mask the MoE observables here.
            aux = aux + fv * a_
            dr = dr + fv * dr_
            rt = rt + fv * rt_
            ring = jnp.where(
                fwd_valid,
                jax.lax.dynamic_update_slice(
                    ring, h_in[None, None], (vf_c, mf_c % RV, 0, 0, 0)
                ),
                ring,
            )

            vb = bv_tab[t, stage]
            mb_i = bm_tab[t, stage]
            bwd_valid = vb >= 0
            vb_c = jnp.clip(vb, 0, V - 1)
            mb_c = jnp.clip(mb_i, 0, M - 1)
            h_saved = jax.lax.dynamic_slice(
                ring, (vb_c, mb_c % RV, 0, 0, 0),
                (1, 1, mb, s_len, cfg.d_model),
            )[0, 0]
            is_last = (vb_c == V - 1) & (stage == S - 1)
            _, pull = jax.vjp(
                lambda p, h: chunk_outs(p, h, vb_c, mb_c)[:3],
                params, h_saved,
            )
            bv = bwd_valid.astype(jnp.float32)
            seed_h = (
                jnp.where(bwd_valid & ~is_last, 1.0, 0.0).astype(dt)
                * bwd_ch
            )
            ct_params, ct_h = pull((seed_h, bv, bv * aux_seed))

            def embed_grads():
                _, epull = jax.vjp(
                    lambda p: embed(p, micro_x[mb_c]), params
                )
                return epull(ct_h)[0]

            ct_params = jax.lax.cond(
                (vb_c == 0) & (stage == 0),
                lambda: jax.tree.map(jnp.add, ct_params, embed_grads()),
                lambda: ct_params,
            )
            grads = jax.tree.map(jnp.add, grads, ct_params)

            fwd_next = jax.lax.ppermute(h_out, AXIS_PP, fwd_ring)
            bwd_next = jax.lax.ppermute(ct_h, AXIS_PP, bwd_ring)
            return (ring, fwd_next, bwd_next, grads, num, aux, dr, rt), None

        zs = jnp.zeros(())
        init = (
            jnp.zeros((V, RV, mb, s_len, cfg.d_model), dt),
            jnp.zeros((mb, s_len, cfg.d_model), dt),
            jnp.zeros((mb, s_len, cfg.d_model), dt),
            zero_grads,
            zs, zs, zs, zs,
        )
        (_, _, _, grads, num, aux, dr, rt), _ = jax.lax.scan(
            tick_masked if SP > 1 else tick, init, jnp.arange(T_ticks)
        )
        num_g = jax.lax.psum(num, (AXIS_PP, AXIS_DP))
        if has_moe:
            # den was computed BEFORE the scan (the aux seeds consume
            # it, which also serializes its psum against the scan).
            den_g, den_safe = den_pre, den_pre_safe
        else:
            # den is schedule-independent, but its dp psum must NOT
            # float freely against the scan's collectives: the CPU
            # backend's thunk executor runs independent collectives in
            # arbitrary per-device order, and a cross-device inversion
            # (one device parked in this all-reduce while its dp
            # partner waits inside a scan ppermute rendezvous)
            # deadlocks on a starved thread pool — observed on the
            # 8-virtual-device test rig, second step. Plain 1F1B is
            # naturally immune (its aux_seed makes the scan consume
            # den); here an optimization_barrier ties den's input to
            # num_g, pinning the psum strictly after the scan on every
            # device at zero math cost (a 0*num_g term could be
            # algebraically simplified away).
            w_dep = jax.lax.optimization_barrier((jnp.sum(w), num_g))[0]
            den_g = jax.lax.psum(w_dep, AXIS_DP)
            den_safe = jnp.maximum(den_g, 1.0)
        loss = num_g / den_safe
        if has_moe:
            # Same accounting as the other schedules: stages hold
            # disjoint MoE layers (psum over pp — each layer runs in
            # exactly one device's chunk), mean over microbatches and
            # dp shards; sp members hold disjoint sequence-shard
            # groups (sum over sp / SP).
            sp_axes = (AXIS_SP,) if SP > 1 else ()
            aux_g = jax.lax.psum(aux, (AXIS_PP, AXIS_DP) + sp_axes)
            loss = loss + aux_g / (n_micro * dp_n * SP)
            dr_g = jax.lax.psum(dr, (AXIS_PP, AXIS_DP) + sp_axes)
            rt_g = jax.lax.psum(rt, (AXIS_PP, AXIS_DP) + sp_axes)
            drop_fraction = dr_g / jnp.maximum(rt_g, 1.0)
        else:
            drop_fraction = jnp.zeros(())
        grads = jax.tree.map(lambda g: g / den_safe, grads)
        return loss, den_g, grads, drop_fraction

    def interleaved_eval_loss(params, x, y, w):
        """Forward-only interleaved schedule: the validation loss on
        the SAME (interleave-permuted) layer layout the train step
        runs — only the forward half of the schedule tables fires
        (the last forward entry lands at tick V*M + S - 2, so the
        scan runs V*M + S - 1 ticks). Same mask/cond discipline as
        ``interleaved_grads``."""
        stage = jax.lax.axis_index(AXIS_PP)
        b_local, s_len = x.shape
        if b_local % n_micro != 0:
            raise ValueError(
                f"local batch {b_local} not divisible by n_micro={n_micro}"
            )
        mb = b_local // n_micro
        micro_x = x.reshape(n_micro, mb, s_len)
        micro_y = y.reshape((n_micro, mb) + y.shape[1:])
        micro_w = w.reshape(n_micro, mb)
        M = n_micro
        fwd_ring = [(i, (i + 1) % S) for i in range(S)]

        def tw_of(mi):
            return (jnp.broadcast_to(micro_w[mi][:, None], (mb, s_len))
                    if has_moe else None)

        def tick(carry, t):
            fwd_ch, num, den = carry
            vf = fv_tab[t, stage]
            mf = fm_tab[t, stage]
            fwd_valid = vf >= 0
            vf_c = jnp.clip(vf, 0, V - 1)
            mf_c = jnp.clip(mf, 0, M - 1)

            def do_fwd():
                h_in = jax.lax.cond(
                    (vf_c == 0) & (stage == 0),
                    lambda: embed(params, micro_x[mf_c]),
                    lambda: fwd_ch,
                )
                h_out, _, _, _ = chunk_forward(params, vf_c, h_in,
                                               tw_of(mf_c))
                n_, d_ = jax.lax.cond(
                    (vf_c == V - 1) & (stage == S - 1),
                    lambda: head_loss(params, h_out, micro_y[mf_c],
                                      micro_w[mf_c]),
                    lambda: (jnp.zeros(()), jnp.zeros(())),
                )
                return h_out, n_, d_

            def skip_fwd():
                z = jnp.zeros((mb, s_len, cfg.d_model), dt)
                return z, jnp.zeros(()), jnp.zeros(())

            if SP > 1:
                # Masked-tick discipline (see tick_masked in
                # interleaved_grads): the chunk body's ring-attention
                # collectives must run every tick — do_fwd runs
                # UNCONDITIONALLY (its inner embed/head conds are
                # sp-uniform and never fire on clipped garbage) and
                # validity masks the accumulators instead.
                h_out, n_, d_ = do_fwd()
                fvv = fwd_valid.astype(jnp.float32)
                n_, d_ = fvv * n_, fvv * d_
            else:
                h_out, n_, d_ = jax.lax.cond(fwd_valid, do_fwd, skip_fwd)
            num = num + n_
            den = den + d_
            fwd_next = jax.lax.ppermute(h_out, AXIS_PP, fwd_ring)
            return (fwd_next, num, den), None

        init = (
            jnp.zeros((mb, s_len, cfg.d_model), dt),
            jnp.zeros(()), jnp.zeros(()),
        )
        # Every forward entry lands by tick V*M + S - 2 (the combined
        # schedule's later ticks are backward-only).
        (_, num, den), _ = jax.lax.scan(
            tick, init, jnp.arange(V * M + S - 1)
        )
        num_g = jax.lax.psum(num, (AXIS_PP, AXIS_DP))
        den_g = jax.lax.psum(den, (AXIS_PP, AXIS_DP))
        return num_g / jnp.maximum(den_g, 1.0)

    def local_step(params, opt_state, x, y, w, key):
        dp_idx = jax.lax.axis_index(AXIS_DP)

        def one(carry, sub):
            params, opt_state = carry
            if (mini_batch is not None and mini_batch > 0
                    and mini_batch > x.shape[0]):
                # Fail loudly (trace-time): silently training on the
                # full resident batch would be the quiet failure mode
                # the knob contract forbids. == resident size is the
                # documented identity case.
                raise ValueError(
                    f"mini_batch={mini_batch} exceeds the {x.shape[0]} "
                    "resident rows per dp shard"
                )
            if mini_batch is not None and 0 < mini_batch < x.shape[0]:
                from sparktorch_tpu.utils.data import sample_minibatch

                # Fold in the dp index: each dp shard samples its own
                # block, but pp/tp members of the same dp row MUST
                # sample the same rows (they cooperate on one batch).
                b = sample_minibatch(
                    DataBatch(x=x, y=y, w=w),
                    jax.random.fold_in(sub, dp_idx), mini_batch,
                )
            else:
                b = DataBatch(x=x, y=y, w=w)
            if schedule == "1f1b" and V > 1:
                loss, examples, grads, drop_fraction = interleaved_grads(
                    params, b.x, b.y, b.w
                )
            elif schedule == "1f1b":
                loss, examples, grads, drop_fraction = one_f_one_b_grads(
                    params, b.x, b.y, b.w
                )
            else:
                (loss, (drop_fraction, _, examples)), grads = (
                    jax.value_and_grad(
                        lambda p: schedule_loss(p, b.x, b.y, b.w),
                        has_aux=True,
                    )(params)
                )
                # psum under shard_map autodiff transposes to psum, so
                # the cotangent of the (pp, dp)-psummed loss arrives
                # SUMMED over those S*dp members: without this
                # normalization the effective gradient (and therefore
                # the SGD learning rate) grew linearly with mesh size.
                # Found by the 1f1b exactness test, whose manual
                # backward computes the honest mesh-size-invariant
                # gradient; dp=1/pp=1 agreement pins the right scale.
                grads = jax.tree.map(
                    lambda g: g / (S * mesh.shape[AXIS_DP]), grads
                )
            # Replicated-param grads must be summed over every axis
            # the param is replicated across: layer stacks live on one
            # pp shard each (sum over dp only); embed/head/norm are
            # used on all stages (masked elsewhere -> zero grads) and
            # replicated over both axes. No tp reductions anywhere:
            # the f/g pair in _layer_forward already makes every grad
            # complete and tp-identical. With ep>1, _ep_enter keeps
            # every grad ep-replicated EXCEPT the router's, whose
            # per-member share must additionally sum over ep (expert
            # leaves are ep-SHARDED and need no ep reduction).
            # With sp>1 each member trained on its SEQUENCE shard, so
            # every param grad is a per-shard share: sp joins dp in
            # the data axes every reduction sums over — MoE leaves
            # included (their routing groups partition over sp too).
            data_axes = (AXIS_DP,) + ((AXIS_SP,) if SP > 1 else ())

            def _reduce_moe(path, g):
                names = _path_names(path)
                if E > 1 and "router" in names:
                    return jax.lax.psum(g, data_axes + (AXIS_EP,))
                return jax.lax.psum(g, data_axes)

            from jax.tree_util import tree_map_with_path

            grads = {
                k: (
                    jax.tree.map(lambda g: jax.lax.psum(g, data_axes), v)
                    if k == "layers"
                    else tree_map_with_path(_reduce_moe, v)
                    if k == "layers_moe"
                    else jax.tree.map(
                        lambda g: jax.lax.psum(g, (AXIS_PP,) + data_axes), v
                    )
                )
                for k, v in grads.items()
            }
            updates, new_opt = tx.update(grads, opt_state, params)
            new_params = optax.apply_updates(params, updates)
            # Post-reduction grads are complete on every shard for the
            # params that shard owns: expert leaves are distinct per
            # (pp, ep) shard; other layer-stack squares distinct per
            # pp stage (dp/tp/ep-identical); embed/head/norm identical
            # everywhere. One FULL-mesh psum (the same collective
            # family the loss uses) with static 1/extent weights
            # counts each square exactly once in the global norm.
            S_pp = mesh.shape[AXIS_PP]
            S_dp = mesh.shape[AXIS_DP]
            E_ax = E if E > 1 else 1
            T_ax = T if T > 1 else 1
            SP_ax = SP if SP > 1 else 1
            norm_axes = (
                (AXIS_PP, AXIS_DP)
                + ((AXIS_EP,) if E > 1 else ())
                + ((AXIS_TP,) if T > 1 else ())
                + ((AXIS_SP,) if SP > 1 else ())
            )

            def _sq_moe(path, g):
                names = _path_names(path)
                # Expert leaves are distinct per (pp, ep) shard; the
                # rest of the MoE layer is ep-replicated; everything
                # is sp-replicated post-reduction. (tp is rejected
                # with MoE, so no tp term here.)
                w_ = (1.0 / (S_dp * SP_ax)
                      if names[-1] in _MOE_EXPERT_LEAVES
                      else 1.0 / (S_dp * E_ax * SP_ax))
                return jnp.sum(jnp.square(g)) * w_

            def _sq_layers(path, g):
                names = _path_names(path)
                # qkv/proj/mlp leaves are tp-SHARDED (distinct per
                # (pp, tp) shard); ln and output-side biases are
                # tp-replicated. Dense stacks are ep-replicated, and
                # every param is sp-replicated (post-reduction grads
                # identical across sp).
                is_tp_sharded = any(
                    names[-len(key):] == key for key in _TP_LAYER_DIMS
                )
                w_ = (1.0 / (S_dp * E_ax * SP_ax) if is_tp_sharded
                      else 1.0 / (S_dp * E_ax * T_ax * SP_ax))
                return jnp.sum(jnp.square(g)) * w_

            sq = {
                k: (
                    sum(jax.tree.leaves(tree_map_with_path(_sq_moe, v)))
                    if k == "layers_moe"
                    else sum(jax.tree.leaves(
                        tree_map_with_path(_sq_layers, v)))
                    if k == "layers"
                    else sum(jnp.sum(jnp.square(g))
                             for g in jax.tree.leaves(v))
                    * (1.0 / (S_dp * S_pp * E_ax * T_ax * SP_ax))
                )
                for k, v in grads.items()
            }
            grad_norm = jnp.sqrt(jax.lax.psum(sum(sq.values()), norm_axes))
            return (new_params, new_opt), (
                loss, drop_fraction, grad_norm, examples
            )

        (params, opt_state), outs = jax.lax.scan(
            one, (params, opt_state), jax.random.split(key, K)
        )
        loss, drop_fraction, grad_norm, examples = outs
        return params, opt_state, loss, drop_fraction, grad_norm, examples

    cache = {}
    # Data layout: rows over dp; with sp>1 the SEQUENCE dim of x (and
    # of token-level lm targets) shards over sp — classifier labels
    # are per-row and stay dp-only. Weights are per-row everywhere.
    x_spec = P(AXIS_DP, AXIS_SP) if SP > 1 else P(AXIS_DP)
    y_spec = x_spec if head == "lm" else P(AXIS_DP)

    def _build_eval(specs):
        """Forward-only schedule for validation: same pipeline, no
        grads, reporting the TASK loss (the [1][1] aux slot — sown MoE
        aux objectives are excluded from the validation signal, like
        the DP eval)."""
        if V > 1:
            # The GPipe eval walks each device's local stack in stage
            # order, which would be SCRAMBLED under the interleaved
            # layout — eval with the forward half of the interleaved
            # schedule instead (same chunk walk as training).
            eval_mapped = shard_map_compat(
                interleaved_eval_loss,
                mesh,
                in_specs=(specs, x_spec, y_spec, P(AXIS_DP)),
                out_specs=P(),
            )
            return jax.jit(eval_mapped)
        eval_mapped = shard_map_compat(
            lambda p, x, y, w: schedule_loss(p, x, y, w)[1][1],
            mesh,
            in_specs=(specs, x_spec, y_spec, P(AXIS_DP)),
            out_specs=P(),
        )
        return jax.jit(eval_mapped)

    def _ensure_built(state: PipelineState):
        if "jitted" not in cache:
            specs = _param_specs(state.params)
            opt_specs = _opt_specs(tx, state.opt_state, specs)
            mapped = shard_map_compat(
                local_step,
                mesh,
                in_specs=(specs, opt_specs,
                          x_spec, y_spec, P(AXIS_DP), P()),
                out_specs=(specs, opt_specs, P(), P(), P(), P()),
            )
            cache["jitted"] = jax.jit(mapped, donate_argnums=(0, 1))
            cache["eval"] = _build_eval(specs)

    def memory_analysis(state: PipelineState, batch: DataBatch, key=None):
        """XLA's memory analysis of the compiled train step (temp
        allocation bytes etc.) — how the 1f1b-vs-gpipe activation-
        memory claim is MEASURED rather than asserted. Call before
        stepping (lowering uses the live buffers; no donation)."""
        _ensure_built(state)
        k = key if key is not None else jax.random.key(0)
        return cache["jitted"].lower(
            state.params, state.opt_state, batch.x, batch.y, batch.w, k
        ).compile().memory_analysis()

    def step(state: PipelineState, batch: DataBatch, key=None):
        _ensure_built(state)
        if key is None:
            if mini_batch is None and K == 1:
                # The key is never consumed on this configuration —
                # any constant avoids the device sync a
                # device_get(state.step) fold would cost per call.
                key = cache.setdefault("zero_key", jax.random.key(0))
            else:
                # Deterministic per-call key for minibatch sampling:
                # a host-side step counter seeded from the device step,
                # so fresh blocks are drawn each call without a
                # per-call device sync. The counter is resynced (one
                # device_get) whenever the caller passes a state this
                # step fn did NOT produce — a restored checkpoint or a
                # fresh PipelineState — detected by identity on the
                # step array, so resumed runs key off the true
                # state.step instead of a stale cache (ADVICE r04).
                if ("host_step" not in cache
                        or state.step is not cache.get("last_step_arr")):
                    # One scalar, only on resume/cache invalidation —
                    # steady state uses the host mirror.
                    # lint-obs: ok (resume-only scalar)
                    cache["host_step"] = int(jax.device_get(state.step))
                key = jax.random.fold_in(
                    jax.random.key(0), cache["host_step"]
                )
                cache["host_step"] += K
        new_params, new_opt, loss, drop, grad_norm, examples = cache[
            "jitted"
        ](state.params, state.opt_state, batch.x, batch.y, batch.w, key)
        if jax.default_backend() == "cpu":
            # The in-process CPU collectives runtime keys its
            # rendezvous on a run id that COLLIDES across overlapping
            # launches of the same executable; donation orders buffer
            # reuse but not execution tails, so back-to-back steps can
            # overlap and flakily mix rendezvous (observed as a 9th
            # participant at an 8-thread collective permute, or a
            # cross-collective deadlock). The virtual-device test rig
            # serializes executions instead; real TPU stays async.
            # lint-obs: ok (deliberate CPU-only rendezvous serialization)
            jax.block_until_ready((new_params, new_opt, loss))
        new_state = PipelineState(step=state.step + K, params=new_params,
                                  opt_state=new_opt)
        cache["last_step_arr"] = new_state.step
        if K == 1:
            # Introspection hooks (concrete post-jit values), same
            # single-step contract as before for existing callers.
            step.last_drop_fraction = float(drop[0]) if has_moe else None
            step.last_grad_norm = float(grad_norm[0])
            step.last_examples = float(examples[0])
            return new_state, loss[0]
        return new_state, PpStepOut(
            loss=loss, drop_fraction=drop if has_moe else None,
            grad_norm=grad_norm, examples=examples,
        )

    def eval_loss(state: PipelineState, batch: DataBatch):
        if "eval" not in cache:
            cache["eval"] = _build_eval(_param_specs(state.params))
        return cache["eval"](state.params, batch.x, batch.y, batch.w)

    step.eval_loss = eval_loss
    step.memory_analysis = memory_analysis
    # Goodput compile detection: the trainer probes the lazily-built
    # jitted's dispatch-cache size around each call (None until the
    # first _ensure_built, which reads as "no signal").
    step.jit_cache_size = (
        lambda: _goodput.jit_cache_size(cache.get("jitted")))
    return step


def _opt_specs(tx, opt_state, param_specs):
    """Optimizer leaves that mirror the param TREE (Adam moments etc.)
    inherit the matching param's spec exactly — structural matching
    via ``optax.tree_map_params``, not shape heuristics (two params
    can share a shape); every non-param leaf replicates."""
    return optax.tree_map_params(
        tx,
        lambda _, spec: spec,
        opt_state,
        param_specs,
        transform_non_params=lambda _: P(),
        is_leaf=lambda x: isinstance(x, P),
    )


# ---------------------------------------------------------------------------
# ModelSpec / estimator integration: pp as a mesh-config choice
# ---------------------------------------------------------------------------


def pipeline_params_from_flax(params, cfg: TransformerConfig):
    """Convert a ``CausalLM`` (untied) or ``SequenceClassifier`` flax
    param tree into the pipeline's stacked layout (dense and MoE
    layers into their separate stacks). Inverse of
    :func:`flax_params_from_pipeline`."""
    bb = params["backbone"]
    pattern = _moe_pattern(cfg)
    out = {
        "tok_embed": bb["tok_embed"]["embedding"],
        "pos_embed": bb["pos_embed"],
        "ln_scale": bb["ln_final"]["scale"],
        "ln_bias": bb["ln_final"]["bias"],
    }
    dense = [bb[f"layer_{i}"] for i in range(cfg.n_layers) if not pattern[i]]
    moe = [bb[f"layer_{i}"] for i in range(cfg.n_layers) if pattern[i]]
    if dense:
        out["layers"] = jax.tree.map(lambda *xs: jnp.stack(xs), *dense)
    if moe:
        out["layers_moe"] = jax.tree.map(lambda *xs: jnp.stack(xs), *moe)
    if "lm_head" in params:
        out["head_w"] = params["lm_head"]["kernel"]
        out["head_b"] = params["lm_head"]["bias"]
    else:
        out["pool_w"] = params["pooler"]["kernel"]
        out["pool_b"] = params["pooler"]["bias"]
        out["cls_w"] = params["classifier"]["kernel"]
        out["cls_b"] = params["classifier"]["bias"]
    return out


def flax_params_from_pipeline(pparams, cfg: TransformerConfig):
    """Back to the ``CausalLM`` / ``SequenceClassifier`` flax tree (so
    the fitted bundle transforms through the ordinary module apply)."""
    pattern = _moe_pattern(cfg)
    bb = {}
    jd = jm = 0
    for i in range(cfg.n_layers):
        if pattern[i]:
            k = jm
            bb[f"layer_{i}"] = jax.tree.map(
                lambda a, k=k: a[k], pparams["layers_moe"]
            )
            jm += 1
        else:
            k = jd
            bb[f"layer_{i}"] = jax.tree.map(
                lambda a, k=k: a[k], pparams["layers"]
            )
            jd += 1
    bb["tok_embed"] = {"embedding": pparams["tok_embed"]}
    bb["pos_embed"] = pparams["pos_embed"]
    bb["ln_final"] = {"scale": pparams["ln_scale"],
                      "bias": pparams["ln_bias"]}
    if "head_w" in pparams:
        return {
            "backbone": bb,
            "lm_head": {"kernel": pparams["head_w"],
                        "bias": pparams["head_b"]},
        }
    return {
        "backbone": bb,
        "pooler": {"kernel": pparams["pool_w"], "bias": pparams["pool_b"]},
        "classifier": {"kernel": pparams["cls_w"], "bias": pparams["cls_b"]},
    }


def train_distributed_pipeline(
    spec,
    data,
    labels=None,
    mesh: Optional[Mesh] = None,
    iters: int = 10,
    n_micro: int = 4,
    verbose: int = 0,
    seed: int = 0,
    metrics_hook=None,
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 0,
    resume: bool = False,
    partition_shuffles: int = 1,
    early_stop_patience: int = -1,
    validation_pct: float = 0.0,
    mini_batch: Optional[int] = None,
    steps_per_call: Optional[int] = None,
    profile_dir: Optional[str] = None,
    schedule: str = "gpipe",
    virtual_stages: int = 1,
    pre_sharded: bool = False,
    telemetry=None,
):
    """Pipelined training entry for a ``ModelSpec`` holding a
    ``CausalLM`` — the dispatch target ``train_distributed`` uses when
    the mesh has pp > 1, so pp is a MESH choice on the ordinary
    Estimator/ModelSpec surface, not a separate API.

    The spec's flax params are initialized normally, restacked into
    the pipeline layout, trained under the GPipe schedule, and
    unstacked back — the returned ``TrainResult`` bundles ordinary
    ``CausalLM`` params that transform through the module apply.
    """
    from sparktorch_tpu.models.transformer import CausalLM, SequenceClassifier
    from sparktorch_tpu.obs import get_logger, get_telemetry
    from sparktorch_tpu.parallel.launch import check_gang, notify_gang_step
    from sparktorch_tpu.train.sync import TrainResult
    from sparktorch_tpu.utils.metrics import MetricsRecorder

    tele = telemetry or get_telemetry()
    log = get_logger("sparktorch_tpu.train")
    # Stack sampler beside the ambient ledger (see train/sync.py).
    from sparktorch_tpu.ft import chaos as _chaos
    from sparktorch_tpu.obs import health as _health
    from sparktorch_tpu.obs import profile as _profile

    _profile.ensure(tele)
    _hl = _health.ensure(tele, rank=jax.process_index())
    if _hl is not None:
        _hl.reset()

    module = spec.make_module()
    refuse_sync_dp_only(module, "the pipeline trainer (mesh pp>1)")
    if isinstance(module, CausalLM):
        head = "lm"
    elif isinstance(module, SequenceClassifier):
        head = "classifier"
    else:
        raise ValueError(
            "pipeline-parallel training (mesh pp>1) supports CausalLM "
            f"and SequenceClassifier specs; got {type(module).__name__}. "
            "Use a mesh with pp=1 for other model families."
        )
    cfg = module.config
    if cfg.tie_embeddings:
        raise ValueError("pp training does not support tie_embeddings yet")
    if spec.loss not in ("cross_entropy", "cross_entropy_fused", "nll"):
        raise ValueError(
            f"pp training uses cross entropy; got {spec.loss!r}"
        )

    if pre_sharded:
        # ``data`` is a globally-sharded DataBatch (multi-host path:
        # per-process shards assembled by train_distributed_multihost
        # via make_array_from_process_local_data). No host-side
        # conversion is possible — or needed: validate shapes, cast on
        # device (sharding-preserving), and train on it directly.
        if not isinstance(data, DataBatch):
            raise ValueError(
                "pre_sharded pp training expects a DataBatch of global "
                f"arrays; got {type(data).__name__}"
            )
        if validation_pct and validation_pct > 0:
            raise ValueError(
                "validation_pct is not supported with pre_sharded pp "
                "data — split before assembling the global batch"
            )
        dp = mesh.shape[AXIS_DP]
        rows = int(data.x.shape[0])
        if rows % dp != 0 or (rows // dp) % n_micro != 0:
            raise ValueError(
                f"pre_sharded rows ({rows}) must divide dp ({dp}) x "
                f"n_micro ({n_micro}); pad with weight-0 rows before "
                "sharding (train_distributed_multihost does this)"
            )
        sp_ = dict(mesh.shape).get(AXIS_SP, 1)
        if sp_ > 1 and int(data.x.shape[1]) % sp_ != 0:
            raise ValueError(
                f"sequence length {data.x.shape[1]} not divisible by "
                f"sp={sp_}"
            )
        cast = jax.jit(lambda a: a.astype(jnp.int32))
        batch = DataBatch(x=cast(data.x), y=cast(data.y), w=data.w)
        val_batch = None
        n_rows_padded = rows
        sample_x = np.zeros((1, int(batch.x.shape[1])), np.int32)
    elif isinstance(data, DataBatch):
        x = np.asarray(data.x)
        y = np.asarray(data.y)
        w = np.asarray(data.w, dtype=np.float32)
    elif (isinstance(data, tuple) and len(data) == 2 and labels is None):
        # The (x, y) tuple form _as_batch accepts on the pp=1 path.
        x = np.asarray(data[0])
        y = np.asarray(data[1])
        w = np.ones((x.shape[0],), np.float32)
    else:
        x = np.asarray(data)
        y = np.asarray(labels) if labels is not None else None
        if y is None:
            if head == "classifier":
                raise ValueError("classifier pp training requires labels")
            x, y = x[:, :-1], x[:, 1:]  # next-token LM on one id matrix
        w = np.ones((x.shape[0],), np.float32)
    if not pre_sharded:
        x = x.astype(np.int32)
        y = y.astype(np.int32)

        sp = dict(mesh.shape).get(AXIS_SP, 1)
        if sp > 1 and x.shape[1] % sp != 0:
            raise ValueError(
                f"sequence length {x.shape[1]} not divisible by sp={sp}"
            )

        from sparktorch_tpu.utils.data import pad_to_multiple

        dp = mesh.shape[AXIS_DP]
        need = dp * n_micro

        def _pad_batch(bx, by, bw):
            return pad_to_multiple(
                DataBatch(x=jnp.asarray(bx), y=jnp.asarray(by),
                          w=jnp.asarray(bw)),
                need,
            )

        val_batch = None
        if validation_pct and validation_pct > 0:
            # Split BEFORE padding (the reference's per-worker holdout,
            # util.py:81-95): a shuffled cut of real rows, keeping any
            # caller-supplied sample weights.
            perm0 = np.random.default_rng(seed).permutation(x.shape[0])
            n_val = max(1, int(x.shape[0] * validation_pct))
            val_idx, train_idx = perm0[:n_val], perm0[n_val:]
            if train_idx.size == 0:
                raise ValueError("validation_pct leaves no training rows")
            val_batch = _pad_batch(x[val_idx], y[val_idx], w[val_idx])
            x, y, w = x[train_idx], y[train_idx], w[train_idx]
        batch = _pad_batch(x, y, w)
        n_rows_padded = int(batch.x.shape[0])
        sample_x = x[:1]

    if mini_batch is not None and mini_batch > 0:
        per_shard = n_rows_padded // dp
        if mini_batch > per_shard:
            raise ValueError(
                f"mini_batch={mini_batch} exceeds the {per_shard} "
                f"resident rows per dp shard"
            )
    else:
        mini_batch = None

    # Chunking mirrors the DP trainer (the shared contract lives in
    # sync._resolve_steps_per_call): fuse many schedules per compiled
    # call unless early stopping / validation need a signal at every
    # step (the pp path checks those at call boundaries, so their
    # cadence IS the chunk size).
    from sparktorch_tpu.train.sync import _resolve_steps_per_call

    steps_per_call = _resolve_steps_per_call(
        steps_per_call,
        default=(
            1
            if (early_stop_patience and early_stop_patience > 0)
            or validation_pct > 0
            else min(iters, 16)
        ),
        iters=iters,
        checkpoint_every=checkpoint_every,
        ckpt_active=bool(checkpoint_dir),
    )
    if (steps_per_call > 1
            and ((early_stop_patience and early_stop_patience > 0)
                 or validation_pct > 0)):
        # The default resolution already picks 1 when these signals
        # are active, so reaching here means an EXPLICIT override:
        # make the cadence change loud rather than silent (ADVICE
        # r04 — patience would otherwise silently multiply by the
        # chunk size).
        import warnings

        warnings.warn(
            f"steps_per_call={steps_per_call} with early stopping / "
            "validation on the pp path: the stop signal and val loss "
            "are evaluated at COMPILED-CALL boundaries, so "
            "early_stop_patience counts calls (each "
            f"{steps_per_call} steps), not steps",
            stacklevel=2,
        )

    tx = spec.make_optimizer()
    # Build the step FIRST: its config validation (stage divisibility,
    # MoE pattern uniformity, tp x MoE) produces actionable errors;
    # placement would otherwise fail earlier with a raw sharding error.
    step = make_pp_train_step(cfg, tx, mesh, n_micro=n_micro, head=head,
                              mini_batch=mini_batch,
                              steps_per_call=steps_per_call,
                              schedule=schedule,
                              virtual_stages=virtual_stages)
    rng = jax.random.key(seed)
    flax_params = dict(spec.init_params(rng, sample_x=sample_x))["params"]
    pparams = pipeline_params_from_flax(flax_params, cfg)
    interleaved = bool(virtual_stages and virtual_stages > 1)
    if interleaved:
        # Interleaved layout: re-order the stacked layers (each kind's
        # stack with its own permutation) so device d's contiguous pp
        # shard holds its V chunks (undone below so the returned
        # params are in ordinary flax order).
        pparams = apply_interleave_permutation(
            pparams, cfg, mesh.shape[AXIS_PP], virtual_stages
        )
    state = place_pipeline_state(pparams, tx, mesh)

    from sparktorch_tpu.train.sync import (
        _finalize_checkpoint,
        _open_checkpoint,
        _save_if_due,
    )

    # Checkpointed stacks are stored in the SCHEDULE'S layer order
    # (interleave-permuted under virtual_stages>1) — a layout marker
    # makes a mismatched resume fail loudly instead of silently
    # training a scrambled model.
    if checkpoint_dir:
        import json
        import os

        layout = {
            "pp": int(mesh.shape[AXIS_PP]),
            "virtual_stages": int(virtual_stages or 1),
        }
        layout_path = os.path.join(checkpoint_dir, "pipeline_layout.json")
        if resume and os.path.exists(layout_path):
            with open(layout_path) as f:
                saved = json.load(f)
            if saved != layout:
                raise ValueError(
                    f"checkpoint layer layout {saved} does not match the "
                    f"requested {layout}: the stacked layers are stored "
                    "in the schedule's permuted order — resume with the "
                    "same pp and virtual_stages"
                )
        elif jax.process_index() == 0:
            # One writer, atomic rename: concurrent gang processes
            # sharing a checkpoint dir must never see a torn marker.
            os.makedirs(checkpoint_dir, exist_ok=True)
            tmp = layout_path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(layout, f)  # lint-obs: ok (checkpoint layout)
            os.replace(tmp, layout_path)

    # PipelineState checkpoints like TrainState (step-indexed orbax
    # snapshots restored INTO the pp/tp-sharded layout).
    ckpt, state = _open_checkpoint(checkpoint_dir, resume, state)

    from sparktorch_tpu.utils.early_stopper import EarlyStopping

    stopper = (
        EarlyStopping(patience=early_stop_patience)
        if early_stop_patience is not None and early_stop_patience > 0
        else None
    )
    recorder = MetricsRecorder(n_chips=mesh.size, telemetry=tele,
                               prefix="train_pp")
    # lint-obs: ok (two scalars before the loop starts — nothing queued)
    last_ckpt = int(jax.device_get(state.step)) if ckpt is not None else 0
    start = int(jax.device_get(state.step))  # lint-obs: ok (pre-loop scalar)
    # Seed folded with the restored step: a resumed run must draw
    # FRESH permutations, not replay the interrupted run's (same
    # invariant as the streaming trainer's resume seeding).
    shuffle_rng = np.random.default_rng(seed + 1 + start)
    # On-device permutation: one small index upload per round instead
    # of re-uploading the full x/y/w arrays from the host.
    permute = jax.jit(
        lambda b, p: DataBatch(x=b.x[p], y=b.y[p], w=b.w[p])
    )
    from sparktorch_tpu.utils.tracing import profile_run, step_annotation

    sample_key = jax.random.key(seed + 2 + start)
    completed = False
    stop = False
    profiler = profile_run(profile_dir, telemetry=tele)
    profiler.__enter__()
    try:
        for shuffle_round in range(max(1, partition_shuffles)):
            # Round 0 must ALSO shuffle when minibatch sampling is on:
            # sample_minibatch takes contiguous blocks, whose
            # uniformity argument requires random resident order (the
            # same invariant as the DP trainer).
            if shuffle_round > 0 or mini_batch is not None:
                # The reference's partition reshuffle between rounds
                # (distributed.py:267-273): microbatch membership
                # changes; weight-0 padding rows stay masked wherever
                # they land.
                batch = permute(
                    batch,
                    jnp.asarray(shuffle_rng.permutation(n_rows_padded)),
                )
            i = 0
            while i < iters:
                # Same pre-dispatch liveness check + progress publish
                # as the DP trainer: a dead peer aborts before the next
                # compiled schedule (instead of wedging in its
                # collectives), and this rank's step lands on its gang
                # heartbeat so the driver can read cross-rank skew.
                check_gang()
                notify_gang_step(i)
                _act = _chaos.fire("data.batch",
                                   worker=jax.process_index(), step=i)
                if _act and _act.get("poison"):
                    batch = _chaos.poison_batch(batch)
                # Straggler injection before the step span: a late
                # fence arrival the skew referee can attribute.
                _chaos.straggle(jax.process_index(), i)
                sample_key, sub = jax.random.split(sample_key)
                # Goodput step clock: dispatch + loss materialization
                # timed by a LedgerSpan (step_time_s comes off its
                # duration; the seconds land in the ledger's step
                # bucket when one is armed, re-aimed at ``compile``
                # when the jitted's dispatch cache grew under it).
                cache0 = (step.jit_cache_size()
                          if _goodput.active() is not None else None)
                with _goodput.step_span(step=i) as _led:
                    with tele.span("train_pp/step_call"), \
                            step_annotation(i, telemetry=tele):
                        state, out = step(state, batch, key=sub)
                    if steps_per_call == 1:
                        losses = [float(out)]
                        gnorms = [step.last_grad_norm]
                        exs = [step.last_examples]
                        drops = [step.last_drop_fraction]
                    else:
                        losses = [float(v) for v in np.asarray(out.loss)]
                        gnorms = [float(v) for v in np.asarray(out.grad_norm)]
                        exs = [float(v) for v in np.asarray(out.examples)]
                        drops = (
                            [float(v) for v in np.asarray(out.drop_fraction)]
                            if out.drop_fraction is not None
                            else [None] * steps_per_call
                        )
                    _led.count = len(losses)
                    if cache0 is not None and (
                            step.jit_cache_size() or cache0) > cache0:
                        _led.rebucket("compile")
                # Time the once-per-call eval separately: smearing it
                # into the per-step dt would inflate step_time_s by
                # eval_wall/steps_per_call (ADVICE r04). Productive
                # device work, so the ledger files it under compute.
                with _goodput.span("compute", {"site": "pp_eval"}) \
                        as _eval_led:
                    val_loss = (
                        float(step.eval_loss(state, val_batch))
                        if val_batch is not None else None
                    )
                eval_s = _eval_led.duration_s
                dt = _led.duration_s / len(losses)
                if _hl is not None:
                    # Loss/grad-norm are already host floats here (the
                    # step call materializes them); the ledger still
                    # applies its detectors on the K-late cadence.
                    _hl.note_step(count=len(losses),
                                  host={"loss": np.asarray(losses),
                                        "grad_norm": np.asarray(
                                            [g if g is not None else np.nan
                                             for g in gnorms])})
                for j, (l, g, e, dr) in enumerate(
                    zip(losses, gnorms, exs, drops)
                ):
                    record = {
                        "round": shuffle_round, "iter": i + j,
                        "loss": l,
                        # val runs once per call, on the post-call
                        # params: attach it to the chunk's last step.
                        "val_loss": (val_loss if j == len(losses) - 1
                                     else None),
                        "examples": e,
                        "grad_norm": g,
                        "step_time_s": dt,
                    }
                    if val_loss is not None and j == len(losses) - 1:
                        record["eval_time_s"] = eval_s
                    if dr is not None:
                        record["moe_drop_fraction"] = dr
                    recorder.record(record)
                    if metrics_hook:
                        metrics_hook(record)
                    if verbose:
                        msg = (f"[sparktorch_tpu:pp] round {shuffle_round} "
                               f"iter {i + j} loss {l:.6f}")
                        if record["val_loss"] is not None:
                            msg += f" val_loss {record['val_loss']:.6f}"
                        log.info(msg)
                i += len(losses)
                if ckpt is not None:
                    with tele.span("train_pp/checkpoint"):
                        last_ckpt = _save_if_due(ckpt, state, last_ckpt,
                                                 checkpoint_every)
                # The global loss is replicated on every host, so the
                # per-host stopper reaches the identical decision (no
                # extra collective — same argument as the DP trainer).
                # With steps_per_call > 1 the signal cadence is the
                # call boundary (patience counts calls, not steps).
                if stopper is not None and stopper.step(
                    val_loss if val_loss is not None else losses[-1]
                ):
                    stop = True
                    break
            if stop:
                break
        completed = True
    finally:
        if _hl is not None:
            _hl.flush()
        profiler.__exit__(None, None, None)
        _finalize_checkpoint(ckpt, state, completed)

    if jax.process_count() > 1:
        # The pp/tp-sharded stacks span non-addressable devices in a
        # multi-process world: gather to replicated (one all-gather)
        # so every host returns the full params — the DP multihost
        # path's contract.
        from sparktorch_tpu.parallel.mesh import replicated as _replicated

        gather = jax.jit(
            lambda p: p,
            out_shardings=jax.tree.map(lambda _: _replicated(mesh),
                                       state.params),
        )
        # lint-obs: ok (end-of-run gather after the loop drained)
        trained = jax.device_get(gather(state.params))
    else:
        trained = jax.device_get(state.params)  # lint-obs: ok (end-of-run)
    if interleaved:
        trained = apply_interleave_permutation(
            trained, cfg, mesh.shape[AXIS_PP], virtual_stages,
            inverse=True,
        )
    out_params = flax_params_from_pipeline(trained, cfg)
    return TrainResult(params=out_params, model_state={},
                       metrics=recorder.records, spec=spec,
                       summary=recorder.summary())

"""Transformer encoder / LM family (BERT-class workloads).

Covers the BASELINE stress configs the reference can only feed through
its generic DP loop (BERT-base SST-2 fine-tune, BASELINE.md config 4;
the reference itself contains no transformer or attention code —
SURVEY §5 "Long-context": *entirely absent*). Long context is
first-class here:

- ``attn_impl='auto'`` (the default): the code picks, layer by layer at
  trace time, from what it can see (:func:`pick_attention`): the fused
  Pallas kernels of :mod:`sparktorch_tpu.ops.flash_attention` on a TPU
  at rows of :data:`KERNEL_MIN_SEQ` tokens or more (by head width, as
  read on the chip) whose shape the kernels take as it stands, where
  the trace is one device's own program (a ``shard_map`` body, or no
  mesh in a process of one device) and the sequence is whole; else
  dense.
- ``attn_impl='dense'``: softmax attention as XLA fuses it; the
  ``[rows, heads, T, T]`` scores and probabilities cross HBM.
- ``attn_impl='flash'``: the fused kernels, asked for by name (an
  untileable sequence is an error on a TPU).
- ``attn_impl='ring'``: sequence-parallel ring attention
  (:mod:`sparktorch_tpu.ops.attention`) — the sequence axis is
  sharded over the mesh's ``sp`` axis and K/V blocks rotate over ICI,
  so max sequence length scales linearly with the number of chips.
  In the pipeline trainer the rotation rides the schedule's own
  shard_map; under the GSPMD trainer the partitioner computes the
  global dense attention over the sp sharding (the island form is
  opt-in via ``SPARKTORCH_TPU_GSPMD_RING_ISLAND=1`` — it shifts
  blockwise-softmax rounding at bf16, see ``MultiHeadAttention``).

Tensor parallelism: head and FFN dims are sharded over ``tp`` by the
sharding rules in :mod:`sparktorch_tpu.parallel.sharding_rules`; XLA
GSPMD inserts the tp collectives. Heads must divide the tp size.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from sparktorch_tpu.ops.attention import dense_attention, ring_attention
from sparktorch_tpu.parallel.compat import ambient_gspmd_mesh
from sparktorch_tpu.parallel.mesh import AXIS_EP, BATCH_AXES



@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 30522
    d_model: int = 768
    n_heads: int = 12
    n_layers: int = 12
    d_ff: int = 3072
    max_len: int = 512
    n_classes: int = 2
    dtype: str = "bfloat16"
    attn_impl: str = "auto"  # 'auto' | 'dense' | 'flash' | 'ring'
    causal: bool = False
    remat: bool = False
    # Mixture-of-experts (0 = dense FFN everywhere). Expert weights
    # carry a leading experts dim that the sharding rules lay out over
    # the ``ep`` mesh axis; the dispatch/combine are explicit shard_map
    # all-to-alls (MoEFFN / _ep_relayout), never partitioner-derived.
    n_experts: int = 0
    moe_every: int = 2          # every k-th layer uses the MoE FFN
    capacity_factor: float = 1.25
    moe_aux_weight: float = 1e-2  # switch-style load-balance loss
    # Routing fan-out per token. 1 = switch-style (gate = raw top prob);
    # k>=2 = GShard-style: top-k experts with gates renormalized over
    # the chosen k, first choices claim capacity before second choices.
    moe_top_k: int = 1
    # Routing group size: tokens route within fixed-size groups, so
    # the dispatch/combine one-hots are O(n * group * cf) elements —
    # linear in total tokens — instead of O(n^2) with global routing.
    moe_group_size: int = 4096
    # How tokens reach their experts across the ``ep`` mesh axis —
    # governs BOTH manual-ep paths (the pipeline trainer's shard_map
    # MoE in train/pipeline.py, and the GSPMD trainer's MoEFFN, whose
    # dispatch/combine are explicit shard_map all_to_all islands):
    # 'a2a'       — GShard-style: each ep member routes only its own
    #               slice of the routing groups and token blocks travel
    #               to their experts' owners over an all_to_all (and
    #               back) — per-member routing/dispatch work and
    #               activation bytes scale 1/ep. Raises at trace time
    #               if the group count cannot shard evenly.
    # 'replicate' — no explicit dispatch collectives. In the pipeline
    #               trainer: every ep member routes the full batch and
    #               computes its expert slice, one psum combines (the
    #               round-4 layout; correct but does not shrink with
    #               ep). In the GSPMD trainer: the layout is left to
    #               sharding constraints and the partitioner — which on
    #               jax 0.4.x lowers to all-gather + all-reduce (full
    #               token replication); kept as 'auto''s fallback and
    #               the tests' reference (test_pipeline_parallel.py).
    # 'auto'      — 'a2a' when the routing groups shard evenly, else
    #               'replicate'. Under the GSPMD trainer the group
    #               partition is mesh-anchored (see moe_group_partition)
    #               so 'auto' reaches the a2a path whenever the token
    #               count divides the device count.
    moe_ep_dispatch: str = "auto"
    # CausalLM: share the input embedding matrix with the LM head
    # (logits = h @ E^T) — halves the vocab-sized params.
    tie_embeddings: bool = False

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    def moe_pattern(self):
        """Per-layer use_moe flags — THE layer schedule, shared by the
        flax ``Transformer`` stack and the pipeline trainer's stacked
        layout (they must agree or restacked params would silently
        swap kinds)."""
        return [
            self.n_experts > 0 and (i + 1) % max(1, self.moe_every) == 0
            for i in range(self.n_layers)
        ]

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)


class HeadsDense(nn.Module):
    """A dense layer from the trailing axes ``in_shape`` onto the axes
    ``features``, with ``nn.DenseGeneral``'s parameters (``kernel``
    ``in_shape + features`` drawn flat, ``bias`` ``features``) and, as
    the default, its product: the tree, the initial values and the
    program are the ones it gives.

    ``flat``: the same product written as one 2-D matmul, from rows
    ``[..., prod(in_shape)]`` onto ``[..., prod(features)]``, for the
    fused attention kernels, which take and give rows ``[b, T, heads *
    head_dim]``. A product ``[b, T, 3, heads, 64]`` XLA lays out with
    the sequence in the lanes (64 would fill half of them), and each
    operand of a kernel then costs a transposing copy: 8 copies of 25 MB
    and a slicing pass a layer at BERT-base on 32 rows of 512 (compiled
    for a v5e, PR 33; a 2-D product reshaped to five axes before the
    bias is added is folded back into that one). Flat, q, k and v leave
    the projection's fusion as the kernels read them, and the three
    gradients enter its transpose as the kernels wrote them."""

    in_shape: Tuple[int, ...]
    features: Tuple[int, ...]
    dtype: Optional[jnp.dtype] = None
    flat: bool = False

    @nn.compact
    def __call__(self, x):
        n_in, n_out = math.prod(self.in_shape), math.prod(self.features)

        def drawn_flat(rng, shape):
            return nn.initializers.lecun_normal()(rng, (n_in, n_out)
                                                  ).reshape(shape)

        kernel = self.param("kernel", drawn_flat,
                            self.in_shape + self.features)
        bias = self.param("bias", nn.initializers.zeros, self.features)
        x, kernel, bias = nn.dtypes.promote_dtype(x, kernel, bias,
                                                  dtype=self.dtype)
        if self.flat:
            return x @ kernel.reshape(n_in, n_out) + bias.reshape(n_out)
        n, lead = len(self.in_shape), x.ndim - len(self.in_shape)
        out = jax.lax.dot_general(
            x, kernel,
            ((tuple(range(lead, x.ndim)), tuple(range(n))), ((), ())))
        return out + bias.reshape((1,) * lead + self.features)


class MultiHeadAttention(nn.Module):
    config: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        b, s, _ = x.shape
        dt = cfg.compute_dtype
        heads = (cfg.n_heads, cfg.head_dim)
        kernels = pick_attention(cfg, s) == "flash"
        qkv = HeadsDense((cfg.d_model,), (3, *heads), dt, flat=kernels,
                         name="qkv")(x)
        out_proj = HeadsDense(heads, (cfg.d_model,), dt, flat=kernels,
                              name="proj")
        if kernels:
            from sparktorch_tpu.ops.flash_attention import flash_attention

            q, k, v = (t.reshape(b, s, *heads)
                       for t in jnp.split(qkv, 3, axis=-1))
            out = flash_attention(q, k, v, cfg.causal)
            return out_proj(out.reshape(b, s, cfg.d_model))
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]  # (b,s,h,hd)

        if cfg.attn_impl == "ring" and _ring_island_enabled() \
                and _sp_mesh_available(q.shape):
            from sparktorch_tpu.train.step import shard_map_compat

            spec = P(BATCH_AXES, "sp", "tp", None)
            attn = shard_map_compat(
                lambda q, k, v: ring_attention(
                    q, k, v, axis_name="sp", causal=cfg.causal
                ),
                mesh=ambient_gspmd_mesh(),
                in_specs=(spec, spec, spec),
                out_specs=spec,
            )
            out = attn(q, k, v)
        else:
            # dense — the ring default under the GSPMD trainer and the
            # fallback everywhere else (plain init/apply, inference
            # transforms, manual-axis trainers): ring IS dense
            # attention computed blockwise, so a ring-trained model
            # applies anywhere. Under a GSPMD mesh with sp>1 the
            # partitioner computes THIS global dense attention over the
            # sequence sharding itself — the correctness the sp/ep
            # parity matrix pins; the explicit ring island
            # (SPARKTORCH_TPU_GSPMD_RING_ISLAND=1) changes blockwise-
            # softmax rounding at bf16 and is opt-in on this jax line.
            # (The pipeline trainer's ring — where the rotation is
            # load-bearing — is unaffected: it rides the pp shard_map,
            # not this island.)
            out = dense_attention(q, k, v, causal=cfg.causal)
        return out_proj(out)


# Head width -> the shortest rows that go to the fused kernels under
# ``attn_impl='auto'``; a width that is no key stays dense. Placed by
# measurement on a v5e chip, whole steps at BERT-base's widths and
# 16,384 tokens, dense / kernels (PERF.md section 6, PR 33): heads of 64
# 68.9 / 76.9 ms at 128, 83.5 / 76.3 at 256, 111.8 / 75.9 at 512; heads
# of 128 70.4 / 70.2 at 256, 85.0 / 69.3 at 512.
KERNEL_MIN_SEQ = {64: 256, 128: 512}


def pick_attention(cfg: TransformerConfig, seq: int) -> str:
    """Which attention a layer of ``cfg`` runs on rows of ``seq``
    tokens: ``cfg.attn_impl`` as named, and for ``'auto'`` ``'flash'``
    or ``'dense'`` from what the trace can see. The kernels when

    - the backend is a TPU (off it they run in interpret mode: a test's
      tool, not a path);
    - the shape is theirs as it stands
      (:func:`sparktorch_tpu.ops.flash_attention.can_tile`: the
      sequence splits into blocks of whole lane widths and the heads
      fill 128-lane groups with no padding);
    - the trace is one device's own program, which no partitioner will
      split: a Mosaic kernel cannot be partitioned automatically (the
      rule ``cross_entropy_auto`` follows), and the TPU compiler
      refuses a program that asks. Inside a ``shard_map`` body every
      mesh axis is Manual and the kernel sees its shard's rows. A
      GSPMD (non-Manual) ambient mesh says the opposite. No mesh in
      sight says nothing: a ``jit`` may carry shardings of its own (the
      predictor over a mesh, the trainers' init), which a trace cannot
      see, so without a mesh only a process that drives one device in
      all gets the kernel;
    - the sequence is not sharded over ``sp``: a shard's rows hold part
      of the keys;
    - the rows are as long as :data:`KERNEL_MIN_SEQ` asks at this head
      width, which was read on the chip at the widths it holds: under
      that the scores are small, XLA's fusions hold them well, and the
      kernel would run one small tile a grid step.
    """
    if cfg.attn_impl != "auto":
        return cfg.attn_impl
    from sparktorch_tpu.ops.flash_attention import can_tile

    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        own_program = jax.device_count() == 1
    else:
        own_program = (set(mesh.manual_axes) == set(mesh.axis_names)
                       and dict(mesh.shape).get("sp", 1) == 1)
    if (jax.default_backend() == "tpu" and own_program
            and seq >= KERNEL_MIN_SEQ.get(cfg.head_dim, math.inf)
            and can_tile(seq, seq, cfg.n_heads, cfg.head_dim)):
        return "flash"
    return "dense"


def _ring_island_enabled() -> bool:
    """Opt-in knob for the GSPMD ring-attention island. Off by
    default: GSPMD computes the global dense attention over the sp
    sharding itself, and the island's blockwise softmax would shift
    bf16 rounding vs the dense-reference parity matrix."""
    import os

    return os.environ.get(
        "SPARKTORCH_TPU_GSPMD_RING_ISLAND", "0"
    ) not in ("", "0", "false", "off")


def _sp_mesh_available(qkv_shape=None) -> bool:
    """Whether a GSPMD (non-Manual) ambient mesh with sp > 1 is in
    scope — the only context where the ring-attention shard_map island
    can (and should) open. Everywhere else — plain init/apply with no
    mesh, or inside a shard_map trainer where axes are Manual — ring
    falls back to dense (same math, single block). With ``qkv_shape``
    given, the island's (b, s, h, hd) in_spec must also divide
    (batch over dp+fsdp, sequence over sp, heads over tp)."""
    mesh = ambient_gspmd_mesh()
    if mesh is None or dict(mesh.shape).get("sp", 1) <= 1:
        return False
    if qkv_shape is not None:
        sizes = dict(mesh.shape)
        b, s, h = qkv_shape[0], qkv_shape[1], qkv_shape[2]
        n_batch = 1
        for ax in BATCH_AXES:
            n_batch *= sizes.get(ax, 1)
        if b % n_batch or s % sizes["sp"] or h % sizes.get("tp", 1):
            return False
    return True


def _gspmd_constraint(x, spec: P):
    """``with_sharding_constraint`` iff an ambient (set_mesh) mesh is
    in scope in GSPMD (non-Manual) mode — i.e. the GSPMD sharded
    trainer. Inside a shard_map trainer (DP or pipeline) the axes are
    Manual and the constraint would be meaningless-to-wrong, and under
    plain apply (inference, tests) there is no mesh at all; both cases
    fall through to identity (:func:`ambient_gspmd_mesh` returns
    None)."""
    mesh = ambient_gspmd_mesh()
    if mesh is None:
        return x
    sizes = dict(mesh.shape)
    for part in spec:
        if part is None:
            continue
        for a in (part if isinstance(part, tuple) else (part,)):
            if a not in sizes:
                return x
    # Each constrained dim must divide its axes' total extent —
    # constraining a 1-group tensor across 8 devices just forces
    # an involuntary full reshard (SPMD partitioner warning).
    for dim, part in zip(x.shape, spec):
        if part is None:
            continue
        total = 1
        for a in (part if isinstance(part, tuple) else (part,)):
            total *= sizes[a]
        if total > 1 and dim % total != 0:
            return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def moe_group_partition(cfg, n: int,
                        n_shards: Optional[int] = None) -> Tuple[int, int]:
    """``(group size, group count)`` for routing ``n`` tokens — THE one
    definition of the MoE group partition, shared by the flax
    :class:`MoEFFN` and the pipeline trainer's manual MoE paths.

    Base rule: the largest ``g <= cfg.moe_group_size`` dividing ``n``
    (trace-time ints, the loop is free). With ``n_shards`` (the GSPMD
    trainer passes its mesh's TOTAL device count), ``g`` must also
    keep ``n/g`` divisible by ``n_shards`` — at least one routing
    group per device, the GShard layout — so the groups dim shards
    evenly over dp x fsdp x ep and the dispatch all-to-all can engage.
    Anchoring on the whole device count (not dp*fsdp*ep) keeps the
    partition IDENTICAL across every mesh shape of the same rig, which
    is what makes ep (and tp/sp/fsdp) a pure layout choice in the
    parity tests. Falls back to the base rule when ``n`` has no such
    divisor (then the a2a path cannot engage either)."""
    cap = max(1, cfg.moe_group_size)
    if n_shards and n_shards > 1 and n % n_shards == 0:
        per_shard = n // n_shards
        g = min(per_shard, cap)
        while per_shard % g:
            g -= 1
        return g, n // g
    g = min(n, cap)
    while n % g:
        g -= 1
    return g, n // g


# ---------------------------------------------------------------------------
# Explicit MoE dispatch/combine all-to-alls (the shard_map island)
# ---------------------------------------------------------------------------


def _moe_relayout_island(x, to_experts: bool):
    """One tiled ``all_to_all`` over ``ep`` relaying a (G, e, cap, d)
    capacity-block tensor between the two MoE layouts (specs in
    :mod:`sparktorch_tpu.parallel.sharding_rules`):

    - GROUPS layout (``to_experts=True`` input): groups dim sharded
      over dp x fsdp x ep — each member holds its own groups' blocks
      for EVERY expert;
    - EXPERTS layout (output): experts dim sharded over ep — each
      member holds every group's blocks for ITS experts.

    Within an ep subgroup the exchange swaps expert slices for group
    blocks, which is exactly the relayout of the UNCHANGED global
    array: the island is a global identity, so it is numerics-proof by
    construction — and partitioner-proof, because the all-to-all is
    spelled out instead of derived (jax 0.4.x GSPMD derives all-gather
    + all-reduce, replicating every token ep-fold). ``to_experts=False``
    is the combine-side inverse."""
    from sparktorch_tpu.parallel.sharding_rules import (
        MOE_EXPERTS_BLOCKS_SPEC,
        MOE_GROUPS_BLOCKS_SPEC,
    )
    from sparktorch_tpu.train.step import shard_map_compat

    if to_experts:
        body = lambda t: jax.lax.all_to_all(t, AXIS_EP, 1, 0, tiled=True)
        in_s, out_s = MOE_GROUPS_BLOCKS_SPEC, MOE_EXPERTS_BLOCKS_SPEC
    else:
        body = lambda t: jax.lax.all_to_all(t, AXIS_EP, 0, 1, tiled=True)
        in_s, out_s = MOE_EXPERTS_BLOCKS_SPEC, MOE_GROUPS_BLOCKS_SPEC
    return shard_map_compat(
        body, mesh=ambient_gspmd_mesh(), in_specs=(in_s,), out_specs=out_s,
    )(x)


def _top_k_routing(probs, k: int):
    """``jax.lax.top_k`` equivalent for the router (first index wins
    ties, like top_k), as ``k`` argmax+mask rounds. top_k's sort-based
    partitioner lowering ALL-GATHERS the sharded probs tensor (the one
    token-scale gather the HLO regression pin would flag); argmax
    reduces only the (local) experts dim, so routing stays device-
    local under the groups sharding."""
    vals, idxs = [], []
    p = probs
    for _ in range(k):
        i = jnp.argmax(p, axis=-1)
        vals.append(jnp.max(p, axis=-1))
        idxs.append(i)
        # Finite mask sentinel: probs are softmax outputs in [0, 1],
        # so -1 loses every later argmax. -inf would poison the next
        # round's max/argmax gradients with (-inf * 0) NaNs in eager
        # mode (jitted runs were rescued only by XLA's simplifier).
        p = jnp.where(jax.nn.one_hot(i, p.shape[-1], dtype=bool),
                      -1.0, p)
    return jnp.stack(vals, axis=-1), jnp.stack(idxs, axis=-1)


@partial(jax.custom_vjp, nondiff_argnums=(5,))
def _expert_ffn(x, w_in, b_in, w_out, b_out, dt):
    """The dense per-expert FFN on (G, e, cap, d) capacity blocks —
    custom VJP so the WEIGHT gradients are layout-invariant.

    Autodiff would contract the weight grads over (groups x cap) in
    one low-precision dot whose per-device extent depends on the mesh
    (ep absorbs dp, so ep=2 holds 2x the groups per device that ep=1
    does) — reassociating the bf16 reduction and drifting expert grads
    ~1e-4 between worlds, which adamw amplifies well past the rtol
    1e-5 ep-parity gate within a few steps. The custom backward
    contracts each GROUP's partial separately (identical work on every
    world — cap never shards) and accumulates across groups in f32, so
    the only cross-world difference left is f32 psum ordering
    (~1e-7/step). Forward math is exactly the inline version it
    replaces."""
    return _expert_ffn_fwd(x, w_in, b_in, w_out, b_out, dt)[0]


def _expert_ffn_fwd(x, w_in, b_in, w_out, b_out, dt):
    from sparktorch_tpu.parallel.sharding_rules import (
        MOE_EXPERTS_BLOCKS_SPEC,
    )

    z = jnp.einsum("gecd,edf->gecf", x, w_in.astype(dt)) \
        + b_in[None, :, None].astype(dt)
    h = nn.gelu(z)
    h = _gspmd_constraint(h, MOE_EXPERTS_BLOCKS_SPEC)
    y = jnp.einsum("gecf,efd->gecd", h, w_out.astype(dt)) \
        + b_out[None, :, None].astype(dt)
    # Residuals hold z but NOT h: the post-gelu hidden is one
    # elementwise gelu away, and saving both would double the
    # dominant (G, e, cap, d_ff) activation footprint per MoE layer.
    return y, (x, z, w_in, b_in, w_out, b_out)


def _expert_ffn_bwd(dt, res, ct):
    x, z, w_in, b_in, w_out, b_out = res
    f32 = jnp.float32
    h = nn.gelu(z)  # recomputed from the saved pre-activation
    # Per-group partials contract over cap ONLY (world-consistent);
    # the f32 sum over the groups dim is the one cross-device
    # reduction (GSPMD psums it over the axes the groups shard over).
    d_w_out = jnp.sum(
        jnp.einsum("gecf,gecd->gefd", h, ct, preferred_element_type=f32),
        axis=0,
    )
    d_b_out = jnp.sum(jnp.sum(ct.astype(f32), axis=2), axis=0)
    d_h = jnp.einsum("gecd,efd->gecf", ct, w_out.astype(dt))
    _, gelu_vjp = jax.vjp(nn.gelu, z)
    d_z = gelu_vjp(d_h)[0]
    d_b_in = jnp.sum(jnp.sum(d_z.astype(f32), axis=2), axis=0)
    d_w_in = jnp.sum(
        jnp.einsum("gecd,gecf->gedf", x, d_z, preferred_element_type=f32),
        axis=0,
    )
    d_x = jnp.einsum("gecf,edf->gecd", d_z, w_in.astype(dt))
    return (d_x, d_w_in.astype(w_in.dtype), d_b_in.astype(b_in.dtype),
            d_w_out.astype(w_out.dtype), d_b_out.astype(b_out.dtype))


_expert_ffn.defvjp(_expert_ffn_fwd, _expert_ffn_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _ep_relayout(x, to_experts: bool):
    """Custom-vjp wrapper of :func:`_moe_relayout_island`: the op is a
    permutation of the global array, so its true VJP is the inverse
    exchange. Spelling it out keeps autodiff off jax's all_to_all
    transpose path (miscompiles for split != concat on some versions —
    same guard as the pipeline trainer's ``_a2a_ep``) and off
    shard_map's replication-rewrite rules."""
    return _moe_relayout_island(x, to_experts)


def _ep_relayout_fwd(x, to_experts):
    return _ep_relayout(x, to_experts), None


def _ep_relayout_bwd(to_experts, _, ct):
    return (_moe_relayout_island(ct, not to_experts),)


_ep_relayout.defvjp(_ep_relayout_fwd, _ep_relayout_bwd)


class MoEFFN(nn.Module):
    """Top-k mixture-of-experts FFN (switch-style at k=1, GShard-style
    gate-weighted combine at k>=2).

    No reference counterpart (SURVEY §2.4: EP "absent"). TPU-first
    design: routing, dispatch, expert matmuls and combine are einsums
    over a (experts, capacity, d_model) layout — no per-expert Python,
    no dynamic shapes. Expert weights have a leading experts dim that
    the sharding rules place on the ``ep`` mesh axis.

    Under the GSPMD sharded trainer (an ambient ``set_mesh`` mesh with
    ep > 1) the dispatch and combine are EXPLICIT shard_map
    all-to-alls (:func:`_ep_relayout`): the group partition is
    mesh-anchored (one-plus routing groups per device,
    :func:`moe_group_partition`), each ep member routes only its own
    slice of the groups, a dispatch all_to_all ships its capacity
    blocks to the owning expert shards, the experts run dense against
    their local weights, and a combine all_to_all ships the outputs
    back for the gate-weighted sum — no token replication, version-
    independent, partitioner-proof. (Deriving the same movement from
    einsum operand shardings — ``moe_ep_dispatch='replicate'`` — is
    lowered by jax 0.4.x GSPMD to all-gather + all-reduce, O(world)
    comm bytes and ~0.7% loss drift; kept as ``'auto'``'s fallback and
    a test reference.) The switch load-balance loss is sown (pre-weighted
    by ``moe_aux_weight``) into the ``losses`` collection; every
    trainer adds sown losses to the objective.

    Tokens route within fixed-size groups (``moe_group_size``), so the
    dispatch/combine one-hots stay linear in total tokens.

    ``token_w`` (per-token weights, (b, s)) masks weight-0 rows — the
    empty-partition padding protocol — OUT of routing: masked tokens
    claim no capacity, contribute nothing to the aux loss, and get
    zero expert output (their residual path carries them). Trainers
    pass the batch's example weights down automatically (step._forward).

    Observability: the fraction of routed token-choices dropped at
    capacity is sown into the ``moe_metrics`` collection as raw
    (dropped, routed) counts; trainers psum them and expose
    ``moe_drop_fraction`` in the step metrics.
    """

    config: TransformerConfig

    @nn.compact
    def __call__(self, x, token_w=None):
        import math

        from sparktorch_tpu.parallel.sharding_rules import (
            MOE_EXPERTS_BLOCKS_SPEC as _experts_spec,
            MOE_GROUPS_BLOCKS_SPEC as _blocks_spec,
            MOE_GROUPS_TOKENS_SPEC as _groups_spec,
        )

        cfg = self.config
        dt = cfg.compute_dtype
        b, s, d = x.shape
        e = cfg.n_experts
        k = max(1, min(cfg.moe_top_k, e))
        n = b * s
        # The ambient GSPMD mesh (the sharded trainer) anchors the
        # group partition and decides whether the explicit-a2a path
        # engages; everywhere else (plain apply, shard_map trainers)
        # mesh is None and the base partition applies.
        mesh = ambient_gspmd_mesh()
        sizes = dict(mesh.shape) if mesh is not None else {}
        n_dev = 1
        for v in sizes.values():
            n_dev *= v
        g, n_groups = moe_group_partition(
            cfg, n, n_dev if mesh is not None else None
        )
        n_ep = sizes.get(AXIS_EP, 1)
        n_shards = n_ep
        for ax in BATCH_AXES:
            n_shards *= sizes.get(ax, 1)
        mode = cfg.moe_ep_dispatch
        if mode not in ("auto", "a2a", "replicate"):
            raise ValueError(f"unknown moe_ep_dispatch {mode!r}")
        # Explicit dispatch/combine all-to-alls (trace-time decision —
        # shapes are static): each ep member routes 1/ep of the groups
        # and only its experts' capacity blocks ever cross the wire.
        use_a2a = (
            mesh is not None and n_ep > 1 and mode in ("auto", "a2a")
            and e % n_ep == 0 and n_groups % n_shards == 0
        )
        if mode == "a2a" and mesh is not None and n_ep > 1 and not use_a2a:
            raise ValueError(
                f"moe_ep_dispatch='a2a' needs n_experts ({e}) divisible "
                f"by ep={n_ep} and the routing group count ({n_groups}) "
                f"divisible by dp*fsdp*ep={n_shards}; lower "
                "moe_group_size or use 'auto'"
            )
        tokens = x.reshape(n_groups, g, d)
        # GSPMD layout (active only under the sharded trainer's mesh):
        # routing groups shard over EVERY data axis including ep — each
        # ep member routes only its share of the groups, device-locally.
        tokens = _gspmd_constraint(tokens, _groups_spec)
        # Static per-group capacity: ceil(cf * g * k / e) — scales with
        # the routing fan-out so k=2 doesn't halve effective capacity.
        cap = max(1, math.ceil(cfg.capacity_factor * g * k / e))
        if token_w is not None:
            mask = (token_w.reshape(n_groups, g) > 0)      # (G, g) bool
        else:
            mask = None

        # Router in f32 (small matmul; numerics matter more than MXU).
        logits = nn.Dense(e, dtype=jnp.float32, name="router")(
            tokens.astype(jnp.float32)
        )                                            # (G, g, e)
        probs = jax.nn.softmax(logits, axis=-1)
        topk_p, topk_idx = _top_k_routing(probs, k)  # (G, g, k)
        if k == 1:
            gates = topk_p                           # switch: raw prob
        else:
            gates = topk_p / jnp.maximum(
                jnp.sum(topk_p, axis=-1, keepdims=True), 1e-9
            )

        oh = jax.nn.one_hot(topk_idx, e, dtype=jnp.int32)  # (G, g, k, e)
        if mask is not None:
            oh = oh * mask[:, :, None, None]
            gates = gates * mask[:, :, None]
        # Capacity assignment with choice-level priority: ALL first
        # choices rank before any second choice (GShard). Flatten
        # (k, g) choice-major, cumsum arrival order, unflatten.
        oh_t = oh.transpose(0, 2, 1, 3).reshape(n_groups, k * g, e)
        pos = jnp.cumsum(oh_t, axis=1) * oh_t        # 1-based rank
        keep = (pos > 0) & (pos <= cap)
        slot = jnp.clip(pos - 1, 0, cap - 1)
        disp_flat = keep[..., None] & jax.nn.one_hot(slot, cap, dtype=bool)
        disp = disp_flat.reshape(n_groups, k, g, e, cap).transpose(
            0, 2, 1, 3, 4
        )                                            # (G, g, k, e, cap)

        # A token's k choices hit k DISTINCT experts, so summing over
        # the choice dim yields a 0/1 dispatch tensor.
        dispatch = jnp.any(disp, axis=2).astype(dt)  # (G, g, e, cap)
        expert_in = jnp.einsum("gnec,gnd->gecd", dispatch,
                               tokens.astype(dt))    # (G, e, cap, d)
        if use_a2a:
            # Dispatch all-to-all: the member's locally-built capacity
            # blocks travel to their experts' owners (groups layout ->
            # experts layout; a global identity, see _ep_relayout).
            expert_in = _gspmd_constraint(expert_in, _blocks_spec)
            expert_in = _ep_relayout(expert_in, True)
        expert_in = _gspmd_constraint(expert_in, _experts_spec)
        w_in = self.param("moe_w_in", nn.initializers.lecun_normal(),
                          (e, d, cfg.d_ff))
        b_in = self.param("moe_b_in", nn.initializers.zeros, (e, cfg.d_ff))
        w_out = self.param("moe_w_out", nn.initializers.lecun_normal(),
                           (e, cfg.d_ff, d))
        b_out = self.param("moe_b_out", nn.initializers.zeros, (e, d))
        expert_out = _expert_ffn(expert_in, w_in, b_in, w_out, b_out, dt)
        expert_out = _gspmd_constraint(expert_out, _experts_spec)
        if use_a2a:
            # Combine all-to-all: weighted-output blocks ship back to
            # their groups' owners; the gate-weighted sum below then
            # runs device-local on the member's own groups.
            expert_out = _ep_relayout(expert_out, False)
            expert_out = _gspmd_constraint(expert_out, _blocks_spec)

        # Gate-weighted combine over the kept (token, choice) slots.
        combine = jnp.einsum("gnk,gnkec->gnec", gates.astype(dt),
                             disp.astype(dt))        # (G, g, e, cap)
        out = jnp.einsum("gnec,gecd->gnd", combine, expert_out)
        out = _gspmd_constraint(out, _groups_spec)   # <- groups layout

        # Switch load-balance loss over VALID tokens only: e * sum_e
        # frac_e * prob_e, where frac uses the primary (first) choice.
        oh0 = oh[:, :, 0, :].astype(jnp.float32)     # (G, g, e)
        if mask is not None:
            mf = mask.astype(jnp.float32)
            valid = jnp.maximum(jnp.sum(mf, axis=1), 1.0)         # (G,)
            frac = jnp.sum(oh0, axis=1) / valid[:, None]
            mean_prob = (
                jnp.sum(probs * mf[:, :, None], axis=1) / valid[:, None]
            )
        else:
            frac = jnp.mean(oh0, axis=1)                          # (G, e)
            mean_prob = jnp.mean(probs, axis=1)                   # (G, e)
        aux = cfg.moe_aux_weight * e * jnp.mean(
            jnp.sum(frac * mean_prob, axis=-1)
        )
        self.sow("losses", "moe_aux", aux)

        # Raw drop counts (masked tokens never counted as routed).
        routed = jnp.sum(oh).astype(jnp.float32)
        kept = jnp.sum(keep.astype(jnp.float32))
        self.sow("moe_metrics", "dropped", routed - kept)
        self.sow("moe_metrics", "routed", routed)
        return out.reshape(b, s, d)


class EncoderLayer(nn.Module):
    config: TransformerConfig
    use_moe: bool = False

    @nn.compact
    def __call__(self, x, token_w=None):
        cfg = self.config
        dt = cfg.compute_dtype
        h = nn.LayerNorm(dtype=dt, name="ln_attn")(x)
        x = x + MultiHeadAttention(cfg, name="attn")(h)
        h = nn.LayerNorm(dtype=dt, name="ln_mlp")(x)
        if self.use_moe:
            h = MoEFFN(cfg, name="moe")(h, token_w)
        else:
            h = nn.Dense(cfg.d_ff, dtype=dt, name="mlp_in")(h)
            h = nn.gelu(h)
            h = nn.Dense(cfg.d_model, dtype=dt, name="mlp_out")(h)
        return x + h


class Transformer(nn.Module):
    """Token-id encoder backbone. Accepts int ids or float columns
    (the estimator's feature matrix is float32; ids are cast)."""

    config: TransformerConfig

    # Optional externally-owned embedding module (weight tying: the
    # CausalLM owns it and reuses it as the LM head).
    embed: Optional[nn.Module] = None

    @nn.compact
    def __call__(self, ids, example_w=None):
        cfg = self.config
        if jnp.issubdtype(ids.dtype, jnp.floating):
            ids = ids.astype(jnp.int32)
        b, s = ids.shape
        embed = self.embed if self.embed is not None else nn.Embed(
            cfg.vocab_size, cfg.d_model, dtype=cfg.compute_dtype,
            name="tok_embed",
        )
        tok = embed(ids)
        pos = self.param(
            "pos_embed",
            nn.initializers.normal(0.02),
            (cfg.max_len, cfg.d_model),
        )
        x = tok + pos[None, :s].astype(cfg.compute_dtype)
        # Per-token weights for MoE routing: padding EXAMPLES (w=0,
        # the empty-partition protocol) broadcast over their tokens.
        token_w = (
            jnp.broadcast_to(example_w[:, None], (b, s))
            if example_w is not None and cfg.n_experts > 0 else None
        )
        layer = EncoderLayer
        if cfg.remat:
            layer = nn.remat(EncoderLayer)
        for i, use_moe in enumerate(cfg.moe_pattern()):
            x = layer(cfg, use_moe=use_moe, name=f"layer_{i}")(x, token_w)
        return nn.LayerNorm(dtype=cfg.compute_dtype, name="ln_final")(x)


def _attention_gauges(cfg: TransformerConfig, row_shape) -> dict:
    """What the trainers put on the bus when they build a step for rows
    of ``row_shape`` (``(seq,)``): the layers whose attention is the
    fused kernels, by :func:`pick_attention` where it is called (the
    trainers call from inside their step's ``shard_map``)."""
    kernel = pick_attention(cfg, row_shape[0]) == "flash"
    return {"train.attention.kernel_layers": cfg.n_layers if kernel else 0}


class SequenceClassifier(nn.Module):
    """BERT-style classifier (SST-2 workload, BASELINE config 4)."""

    config: TransformerConfig

    def train_gauges(self, row_shape) -> dict:
        return _attention_gauges(self.config, row_shape)

    @nn.compact
    def __call__(self, ids, example_w=None):
        x = Transformer(self.config, name="backbone")(ids, example_w)
        # Mean-pool (padding-id masking is the caller's concern; the
        # estimator's weighted loss handles padded *examples*).
        pooled = jnp.mean(x, axis=1)
        pooled = jnp.tanh(
            nn.Dense(self.config.d_model, dtype=self.config.compute_dtype,
                     name="pooler")(pooled)
        )
        return nn.Dense(self.config.n_classes, dtype=jnp.float32,
                        name="classifier")(pooled)


class CausalLM(nn.Module):
    """Decoder-style LM head over the same backbone (long-context
    training workload for ring attention)."""

    config: TransformerConfig

    def train_gauges(self, row_shape) -> dict:
        return _attention_gauges(self.config, row_shape)

    def setup(self):
        cfg = dataclasses.replace(self.config, causal=True)
        if cfg.tie_embeddings:
            # One vocab-sized matrix: the embedding doubles as the LM
            # head (logits = h @ E^T via nn.Embed.attend).
            self.tok_embed = nn.Embed(
                cfg.vocab_size, cfg.d_model, dtype=cfg.compute_dtype,
                name="tok_embed",
            )
            self.backbone = Transformer(cfg, embed=self.tok_embed)
        else:
            self.backbone = Transformer(cfg)
            self.lm_head = nn.Dense(cfg.vocab_size, dtype=jnp.float32)

    def __call__(self, ids, example_w=None):
        x = self.backbone(ids, example_w)
        if self.config.tie_embeddings:
            # f32 logits like the untied Dense head (attend would run
            # the vocab matmul in the embed's compute dtype; logit
            # precision matters for the CE loss and its gradients).
            emb = self.tok_embed.embedding
            return x.astype(jnp.float32) @ emb.astype(jnp.float32).T
        return self.lm_head(x)


def bert_base(n_classes: int = 2, **overrides) -> SequenceClassifier:
    cfg = TransformerConfig(n_classes=n_classes, **overrides)
    return SequenceClassifier(cfg)


def tiny_transformer(**overrides) -> TransformerConfig:
    """Small config for tests/dryruns."""
    defaults = dict(vocab_size=256, d_model=64, n_heads=4, n_layers=2,
                    d_ff=128, max_len=128)
    defaults.update(overrides)
    return TransformerConfig(**defaults)

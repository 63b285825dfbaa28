"""Parameter sharding rules: param path -> PartitionSpec.

The reference replicates the full model on every executor
(``distributed.py:112-115``) — its only layout. Here layouts are
first-class: rules map parameter tree paths to mesh axes, XLA GSPMD
inserts the collectives. Megatron-style conventions for transformers:

- qkv / mlp-in kernels: column-parallel over ``tp`` (output dim)
- attention-out / mlp-out kernels: row-parallel over ``tp`` (input
  dim; GSPMD adds the all-reduce after the matmul)
- embeddings: vocab dim over ``tp``
- everything else: optionally ``fsdp``-sharded on the largest
  divisible dim, else replicated
"""

from __future__ import annotations

import re
from typing import Callable, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sparktorch_tpu.parallel.mesh import (
    AXIS_EP,
    AXIS_FSDP,
    AXIS_TP,
    BATCH_AXES,
    fsdp_param_sharding,
)


# ---------------------------------------------------------------------------
# MoE dispatch/combine layouts (the shard_mapped all-to-all region)
# ---------------------------------------------------------------------------
#
# The MoE hot path has exactly two layouts, and the dispatch/combine
# all-to-alls are the relayout between them (models.transformer
# ``_ep_relayout`` — an explicit shard_map island, NOT a partitioner-
# derived reshard; jax 0.4.37's GSPMD lowers the constraint-derived
# version to all-gather + all-reduce, full token replication):
#
# - GROUPS layout: routing groups shard over every batch axis AND ep —
#   each ep member routes only its share of the groups. Routing,
#   dispatch-plan construction and the gate-weighted combine all run
#   here, fully device-local.
# - EXPERTS layout: the experts dim shards over ep (groups stay over
#   the batch axes only) — the dense expert FFN runs here, against the
#   ep-sharded expert weights laid out by the param rules below.

# (G, g, d) routed tokens / (G, g, e, cap) dispatch plans: groups over
# dp+fsdp+ep, everything else local.
MOE_GROUPS_TOKENS_SPEC = P(BATCH_AXES + (AXIS_EP,), None, None)
# (G, e, cap, d) capacity blocks, groups layout (pre-dispatch /
# post-combine side of the all-to-alls).
MOE_GROUPS_BLOCKS_SPEC = P(BATCH_AXES + (AXIS_EP,), None, None, None)
# (G, e, cap, d) capacity blocks, experts layout (the expert-FFN side).
MOE_EXPERTS_BLOCKS_SPEC = P(BATCH_AXES, AXIS_EP, None, None)


# (path regex, spec builder taking leaf ndim) — first match wins.
_TRANSFORMER_RULES = [
    # qkv DenseGeneral kernel (d_model, 3, heads, head_dim): heads on tp.
    (re.compile(r".*attn/qkv/kernel$"), lambda nd: P(*([None] * (nd - 2) + [AXIS_TP, None]))),
    (re.compile(r".*attn/qkv/bias$"), lambda nd: P(*([None] * (nd - 2) + [AXIS_TP, None])) if nd >= 2 else P()),
    # attention out DenseGeneral kernel (heads, head_dim, d_model): row-parallel.
    (re.compile(r".*attn/proj/kernel$"), lambda nd: P(*([AXIS_TP] + [None] * (nd - 1)))),
    # MLP column then row parallel.
    (re.compile(r".*mlp_in/kernel$"), lambda nd: P(*([None] * (nd - 1) + [AXIS_TP]))),
    (re.compile(r".*mlp_in/bias$"), lambda nd: P(AXIS_TP) if nd == 1 else P()),
    (re.compile(r".*mlp_out/kernel$"), lambda nd: P(*([AXIS_TP] + [None] * (nd - 1)))),
    # Embeddings: vocab over tp, model dim over fsdp.
    (re.compile(r".*tok_embed/embedding$"), lambda nd: P(AXIS_TP, AXIS_FSDP)),
    (re.compile(r".*lm_head/kernel$"), lambda nd: P(None, AXIS_TP)),
    # Mixture-of-experts: experts dim over ep; the FFN's inner dim
    # additionally over tp (column then row parallel, like the dense
    # MLP). The router is tiny and stays replicated (no rule).
    (re.compile(r".*moe_w_in$"), lambda nd: P(AXIS_EP, None, AXIS_TP)),
    (re.compile(r".*moe_b_in$"), lambda nd: P(AXIS_EP, AXIS_TP)),
    (re.compile(r".*moe_w_out$"), lambda nd: P(AXIS_EP, AXIS_TP, None)),
    (re.compile(r".*moe_b_out$"), lambda nd: P(AXIS_EP, None)),
]


# The decoder (``models/sparse_moe_lm.py`` ``SparseMoELM``) under the sync
# DP trainer: an expert layer's three weight leaves ``[experts, ...]`` lie
# on ``ep`` along their first axis, a member holding a contiguous block
# (``HeldExperts``); every other leaf is on every member whole. The same
# rule reads a gradient's path and an optimizer state's, whose moments
# keep the parameters' keys at the end of their own.
_DECODER_EP_LEAF = re.compile(r"(^|.*/)moe/w_(gate|up|down)$")


def decoder_ep_axes(path) -> tuple:
    """The mesh axes the decoder's leaf at ``path`` (of the parameters,
    their gradient or an optimizer state of theirs) is cut over, along
    its first axis: ``("ep",)`` for an expert layer's weights, else
    ``()``."""
    return (AXIS_EP,) if _DECODER_EP_LEAF.match(_path_str(path)) else ()


def _path_str(path) -> str:
    parts = []
    for key in path:
        name = getattr(key, "key", None) or getattr(key, "name", None) or str(key)
        parts.append(str(name))
    return "/".join(parts)


def transformer_rules(mesh: Mesh) -> Callable:
    """Rules callable: (path, leaf) -> NamedSharding."""

    def rule(path, leaf) -> NamedSharding:
        path_s = _path_str(path)
        nd = getattr(leaf, "ndim", 0)
        shape = getattr(leaf, "shape", ())
        for pattern, builder in _TRANSFORMER_RULES:
            if pattern.match(path_s):
                spec = builder(nd)
                if _spec_fits(spec, shape, mesh):
                    return NamedSharding(mesh, spec)
                break
        return fsdp_param_sharding(mesh, leaf)

    return rule


def _spec_fits(spec: P, shape, mesh: Mesh) -> bool:
    for dim, axis in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if axis is None:
            continue
        axes = axis if isinstance(axis, tuple) else (axis,)
        total = 1
        for a in axes:
            total *= mesh.shape[a]
        if total > 1 and dim % total != 0:
            return False
    return True


def shard_params(params, mesh: Mesh, rules: Optional[Callable] = None):
    """Pytree of NamedShardings for a (possibly abstract) param tree."""
    rules = rules or transformer_rules(mesh)
    return jax.tree_util.tree_map_with_path(rules, params)

"""Operations one training step of the ``keye-vl-2.0-30b-a3b-ep8``
configuration needs, from shapes alone, and the operations and bytes of
one call of each sparse-attention kernel.

Counted: matrix products only, on the pairs and rows the algorithm
needs. Attention and index scores count the causal-and-selected pairs
(a query ``t`` has ``min(t + 1, topk)`` selected keys and ``t + 1``
causal ones), not the pairs a masked-dense kernel visits; the experts
count the expected rows (tokens x experts a token x held / routed).
Backward is twice forward, except for the indexer, which has none (its
input is ``stop_gradient``). Recomputation is not counted. Embedding
lookups, norms, rotary steps and the top-k are not matrix products and
count nothing.
"""


def _pairs(seq: int, topk: int) -> tuple:
    """``(causal, selected)`` query-key pairs of one row."""
    causal = seq * (seq + 1) // 2
    short = min(seq, topk)
    return causal, short * (short + 1) // 2 + (seq - short) * topk


def forward_flops_by_part(cfg: dict, rows: int, seq: int) -> dict:
    """Forward operations of one step on one chip, by part."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    sa = cfg["sa_config"]
    ih, idim = sa["indexer_num_heads"], sa["indexer_head_dim"]
    layers, tokens = cfg["num_hidden_layers"], rows * seq
    causal, selected = _pairs(seq, sa["topk"])
    held_share = len(cfg["experts_held"]) / cfg["num_local_experts"]
    return {
        "projections": layers * tokens * 2 * d * hd * (2 * heads + 2 * kv),
        "indexer": layers * (tokens * 2 * d * (ih * idim + idim + ih)
                             + rows * causal * 2 * ih * idim),
        "attention": layers * rows * selected * heads * 4 * hd,
        "router": layers * tokens * 2 * d * cfg["num_local_experts"],
        "experts": layers * tokens * cfg["num_experts_per_tok"] * held_share
        * 3 * 2 * d * cfg["moe_intermediate_size"],
        "head": tokens * 2 * d * cfg["vocab_size"],
    }


def train_step_flops(cfg: dict, rows: int, seq: int) -> float:
    parts = forward_flops_by_part(cfg, rows, seq)
    return float(3 * sum(parts.values()) - 2 * parts["indexer"])


# One call of each kernel of ``ops/sparse_attention.py`` on ``rows``
# rows: operations on the selected pairs, per pair and query head the
# matrix products the kernel's algorithm makes (forward: scores and PV;
# dq: scores, dP, dQ; dkv: scores, dV, dP, dK), and the bytes it has to
# move once: its operands and results, the mask's lower triangle, the
# row statistics at one float a row.
_PRODUCTS = {"sparse_attn_fwd": 2, "sparse_attn_bwd_dq": 3,
             "sparse_attn_bwd_dkv": 4}


def sparse_attention_kernel_cost(cfg: dict, rows: int, seq: int) -> dict:
    """``{kernel name: (operations, bytes)}`` of one call."""
    hd, heads = cfg["head_dim"], cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    causal, selected = _pairs(seq, cfg["sa_config"]["topk"])
    q_bytes = rows * seq * heads * hd * 2      # bf16; also o, do, dq
    kv_bytes = rows * seq * kv * hd * 2        # each of k, v, dk, dv
    stats = rows * seq * heads * 4
    mask = rows * causal
    moved = {"sparse_attn_fwd": 2 * q_bytes + 2 * kv_bytes + mask + stats,
             "sparse_attn_bwd_dq": 3 * q_bytes + 2 * kv_bytes + mask
             + 2 * stats,
             "sparse_attn_bwd_dkv": 2 * q_bytes + 4 * kv_bytes + mask
             + 2 * stats}
    return {name: (float(rows * selected * heads * n * 2 * hd),
                   float(moved[name]))
            for name, n in _PRODUCTS.items()}

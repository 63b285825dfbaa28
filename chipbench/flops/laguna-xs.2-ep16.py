"""Operations one training step of the ``laguna-xs.2-ep16`` configuration
needs, from shapes alone, and the operations and bytes of one call of
each attention kernel of its two kinds of layer.

Counted: matrix products only, on the pairs and rows the algorithm
needs. Attention counts the KEPT query-key pairs of a layer's rule, not
the pairs of the tiles a kernel visits: a full layer ``T (T + 1) / 2`` a
row, a window layer ``w (w + 1) / 2 + (T - w) w`` (``w`` keys with the
query's own, fewer for the first ``w`` queries), each times that
layer's own query heads. The routed experts count the expected rows
(tokens x experts a token x held / routed); the shared expert and the
dense layer's MLP run on every token. Backward is twice forward.
Recomputation is not counted. Embedding lookups, norms, rotary steps,
the gates' sigmoids and the top-k are not matrix products and count
nothing.
"""


def kept_pairs(layer_type: str, seq: int, window: int) -> int:
    """Kept query-key pairs of one row of ``seq`` tokens in one layer."""
    if layer_type == "full_attention":
        return seq * (seq + 1) // 2
    w = min(window, seq)
    return w * (w + 1) // 2 + (seq - w) * w


def forward_flops_by_part(cfg: dict, rows: int, seq: int) -> dict:
    """Forward operations of one step on one chip, by part."""
    d, hd, kv = cfg["hidden_size"], cfg["head_dim"], cfg["num_key_value_heads"]
    tokens = rows * seq
    held_share = len(cfg["experts_held"]) / cfg["num_routed_experts"]
    sparse = sum(m == "sparse" for m in cfg["mlp_layer_types"])
    dense = len(cfg["mlp_layer_types"]) - sparse
    heads = cfg["num_attention_heads_per_layer"]
    gate = 2 * d if cfg["gating"] else 0
    return {
        "projections": sum(tokens * (2 * d * hd * (2 * h + 2 * kv) + gate * h)
                           for h in heads),
        "attention": sum(
            rows * kept_pairs(kind, seq, cfg["sliding_window"]) * h * 4 * hd
            for kind, h in zip(cfg["layer_types"], heads)),
        "router": sparse * tokens * 2 * d * cfg["num_routed_experts"],
        "experts": sparse * tokens * cfg["num_experts_per_tok"] * held_share
        * 3 * 2 * d * cfg["moe_intermediate_size"],
        "shared_expert": sparse * tokens * 3 * 2 * d
        * cfg["shared_expert_intermediate_size"],
        "dense_mlp": dense * tokens * 3 * 2 * d * cfg["intermediate_size"],
        "head": tokens * 2 * d * cfg["vocab_size"],
    }


def train_step_flops(cfg: dict, rows: int, seq: int) -> float:
    return float(3 * sum(forward_flops_by_part(cfg, rows, seq).values()))


# One call of each kernel of ``ops/rule_attention.py`` under a kind's
# name, on ``rows`` rows: operations on the KEPT pairs, per pair and
# query head the matrix products the kernel's algorithm makes (forward:
# scores and PV; dq: scores, dP, dQ; dkv: scores, dV, dP, dK), and the
# bytes it has to move once: its operands and results and the row
# statistics at one float a row. There is no mask to read.
_PRODUCTS = {"fwd": 2, "bwd_dq": 3, "bwd_dkv": 4}


def _kernel_cost(cfg: dict, rows: int, seq: int, layer_type: str,
                 name: str) -> dict:
    """``{kernel name: (operations, bytes)}`` of one call in a layer of
    ``layer_type``, whose layers all have one number of query heads."""
    heads = {h for kind, h in zip(cfg["layer_types"],
                                  cfg["num_attention_heads_per_layer"])
             if kind == layer_type}
    (heads,) = heads
    hd, kv, tokens = cfg["head_dim"], cfg["num_key_value_heads"], rows * seq
    pairs = rows * kept_pairs(layer_type, seq, cfg["sliding_window"])
    q_bytes = tokens * heads * hd * 2      # bf16; also o, do, dq
    kv_bytes = tokens * kv * hd * 2        # each of k, v, dk, dv
    stats = tokens * heads * 4
    moved = {"fwd": 2 * q_bytes + 2 * kv_bytes + stats,
             "bwd_dq": 3 * q_bytes + 2 * kv_bytes + 2 * stats,
             "bwd_dkv": 2 * q_bytes + 4 * kv_bytes + 2 * stats}
    return {f"{name}_attn_{k}": (float(pairs * heads * n * 2 * hd),
                                 float(moved[k]))
            for k, n in _PRODUCTS.items()}


def window_attention_kernel_cost(cfg: dict, rows: int, seq: int) -> dict:
    return _kernel_cost(cfg, rows, seq, "sliding_attention", "window")


def causal_attention_kernel_cost(cfg: dict, rows: int, seq: int) -> dict:
    return _kernel_cost(cfg, rows, seq, "full_attention", "causal")

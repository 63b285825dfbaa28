"""Operations one training step of the ``sdar-30b-a3b-chat-ep8``
configuration needs, from shapes alone, and the operations and bytes of
one call of each block-diffusion attention kernel.

``seq`` is the row's own length ``L``; a step runs ``2 L`` tokens a row
through every layer (the clean row and its noised copy) and the head on
``L``. Counted: matrix products only, on the pairs and rows the
algorithm needs. Attention counts the ALLOWED query-key pairs, ``L^2 +
L b`` a row (clean block-causal ``L (L + b) / 2``, noised to the clean
context before its block ``L (L - b) / 2``, noised to its own block ``L
b``), not the pairs of the tiles a kernel visits; the experts count the
expected rows (tokens x experts a token x held / routed). Backward is
twice forward. Recomputation is not counted. Embedding lookups, norms,
rotary steps, the noise and the top-k are not matrix products and count
nothing.
"""


def allowed_pairs(seq: int, block: int) -> int:
    """Allowed query-key pairs of one row of ``seq`` tokens."""
    return seq * seq + seq * block


def forward_flops_by_part(cfg: dict, rows: int, seq: int) -> dict:
    """Forward operations of one step on one chip, by part."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    layers, tokens = cfg["num_hidden_layers"], rows * 2 * seq
    held_share = len(cfg["experts_held"]) / cfg["num_routed_experts"]
    return {
        "projections": layers * tokens * 2 * d * hd * (2 * heads + 2 * kv),
        "attention": layers * rows * allowed_pairs(seq, cfg["block_length"])
        * heads * 4 * hd,
        "router": layers * tokens * 2 * d * cfg["num_routed_experts"],
        "experts": layers * tokens * cfg["num_experts_per_tok"] * held_share
        * 3 * 2 * d * cfg["moe_intermediate_size"],
        "head": rows * seq * 2 * d * cfg["vocab_size"],
    }


def train_step_flops(cfg: dict, rows: int, seq: int) -> float:
    return float(3 * sum(forward_flops_by_part(cfg, rows, seq).values()))


# One call of each kernel of ``ops/block_diffusion_attention.py`` on
# ``rows`` rows: operations on the allowed pairs, per pair and query
# head the matrix products the kernel's algorithm makes (forward: scores
# and PV; dq: scores, dP, dQ; dkv: scores, dV, dP, dK), and the bytes it
# has to move once: its operands and results over the ``2 L`` tokens and
# the row statistics at one float a row. There is no mask to read.
_PRODUCTS = {"blockdiff_attn_fwd": 2, "blockdiff_attn_bwd_dq": 3,
             "blockdiff_attn_bwd_dkv": 4}


def blockdiff_attention_kernel_cost(cfg: dict, rows: int, seq: int) -> dict:
    """``{kernel name: (operations, bytes)}`` of one call."""
    hd, heads = cfg["head_dim"], cfg["num_attention_heads"]
    kv, tokens = cfg["num_key_value_heads"], rows * 2 * seq
    pairs = rows * allowed_pairs(seq, cfg["block_length"])
    q_bytes = tokens * heads * hd * 2      # bf16; also o, do, dq
    kv_bytes = tokens * kv * hd * 2        # each of k, v, dk, dv
    stats = tokens * heads * 4
    moved = {"blockdiff_attn_fwd": 2 * q_bytes + 2 * kv_bytes + stats,
             "blockdiff_attn_bwd_dq": 3 * q_bytes + 2 * kv_bytes + 2 * stats,
             "blockdiff_attn_bwd_dkv": 2 * q_bytes + 4 * kv_bytes
             + 2 * stats}
    return {name: (float(pairs * heads * n * 2 * hd), float(moved[name]))
            for name, n in _PRODUCTS.items()}

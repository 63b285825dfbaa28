"""Operations one training step of the ``joyai-llm-flash-ep16``
configuration needs, from shapes alone, and the operations and bytes of
one call of each kernel its latent attention adds.

Counted: matrix products only, on the pairs and rows the algorithm needs.
Attention counts the KEPT causal pairs, ``T (T + 1) / 2`` a row a head,
at the heads' TRUE widths: 192 for a score, 128 for a value (the kernels
contract 256 lanes for the 192 and visit the masked pairs of the
diagonal tiles; neither counts). The multi-token prediction module is a
layer more on ``T - 1`` positions, its projection and a second pass
through the head. The routed experts count the expected rows (tokens x
experts a token x held / routed); the shared expert and the dense
layer's MLP run on every token. Backward is twice forward.
Recomputation is not counted. Embedding lookups, norms, the rotary step,
the sigmoids and the top-k are not matrix products and count nothing.
"""


def kept_pairs(seq: int) -> int:
    """Kept query-key pairs of one row of ``seq`` tokens in one layer."""
    return seq * (seq + 1) // 2


def _layer_flops(cfg: dict, tokens: int, pairs: int, dense: bool) -> dict:
    """Forward operations of one layer on ``tokens`` tokens holding
    ``pairs`` kept pairs a head, by part."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dv, rq, rkv = cfg["v_head_dim"], cfg["q_lora_rank"], cfg["kv_lora_rank"]
    out = {
        "projections": tokens * 2 * (
            d * rq + rq * heads * qk + d * (rkv + cfg["qk_rope_head_dim"])
            + rkv * heads * (cfg["qk_nope_head_dim"] + dv) + heads * dv * d),
        "attention": pairs * heads * 2 * (qk + dv),
    }
    if dense:
        out["dense_mlp"] = tokens * 3 * 2 * d * cfg["intermediate_size"]
        return out
    width = cfg["moe_intermediate_size"]
    held_share = len(cfg["experts_held"]) / cfg["num_routed_experts"]
    out["router"] = tokens * 2 * d * cfg["num_routed_experts"]
    out["experts"] = (tokens * cfg["num_experts_per_tok"] * held_share
                      * 3 * 2 * d * width)
    out["shared_expert"] = (tokens * 3 * 2 * d * width
                            * cfg["n_shared_experts"])
    return out


def forward_flops_by_part(cfg: dict, rows: int, seq: int) -> dict:
    """Forward operations of one step on one chip, by part; the module's
    under ``mtp_*``."""
    d, vocab = cfg["hidden_size"], cfg["vocab_size"]
    out: dict = {}

    def add(parts: dict, prefix: str = ""):
        for k, v in parts.items():
            out[prefix + k] = out.get(prefix + k, 0) + v

    for i in range(cfg["num_hidden_layers"]):
        add(_layer_flops(cfg, rows * seq, rows * kept_pairs(seq),
                         i < cfg["first_k_dense_replace"]))
    add({"head": rows * seq * 2 * d * vocab})
    for _ in range(cfg["num_nextn_predict_layers"]):
        tokens = rows * (seq - 1)
        add(_layer_flops(cfg, tokens, rows * kept_pairs(seq - 1), False),
            "mtp_")
        add({"proj": tokens * 2 * 2 * d * d, "head": tokens * 2 * d * vocab},
            "mtp_")
    return out


def train_step_flops(cfg: dict, rows: int, seq: int) -> float:
    return float(3 * sum(forward_flops_by_part(cfg, rows, seq).values()))


# One call of each kernel of ``ops/latent_attention.py`` on ``rows``
# rows: operations on the KEPT pairs, per pair and head the matrix
# products the kernel's algorithm makes, each at its own depth (forward:
# scores 192 and PV 128; dq: scores, dP 128, dQ 192; dkv: scores, dV 128,
# dP 128, dK 192), and the bytes it has to move once at the true widths:
# its operands and results and the row statistics at one float a row.
# The module's call keeps ``seq`` pairs a head fewer (its last position
# is a stand-in): every call is counted as the module's, 0.02% low.
def latent_attention_kernel_cost(cfg: dict, rows: int, seq: int) -> dict:
    heads = cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dv, tokens = cfg["v_head_dim"], rows * seq
    pairs = rows * kept_pairs(seq - 1) * heads
    wide, narrow = tokens * heads * qk * 2, tokens * heads * dv * 2  # bf16
    stats = tokens * heads * 4
    return {
        # q, k, v -> o
        "latent_attn_fwd": (float(pairs * 2 * (qk + dv)),
                            float(2 * wide + 2 * narrow + stats)),
        # q, k, v, do -> dq
        "latent_attn_bwd_dq": (float(pairs * 2 * (qk + dv + qk)),
                               float(3 * wide + 2 * narrow + 2 * stats)),
        # q, k, v, do -> dk, dv
        "latent_attn_bwd_dkv": (float(pairs * 2 * (qk + dv + dv + qk)),
                                float(3 * wide + 3 * narrow + 2 * stats)),
    }


# One call of each kernel of ``ops/latent_rope.py``: no matrix product
# (the rotation's two products and a sum on the rotary dims of every
# query head and of the one shared key), and the bytes at the true
# widths: the float32 products (a head's 192 of ``q``, its 128 + 128 of
# ``k`` and ``v``, the shared key's 64), the two tables' 32 pairs a
# token, and the bf16 results (``k``'s rotary part written a head).
def latent_rope_kernel_cost(cfg: dict, rows: int, seq: int) -> dict:
    heads, rope = cfg["num_attention_heads"], cfg["qk_rope_head_dim"]
    nope, dv, tokens = cfg["qk_nope_head_dim"], cfg["v_head_dim"], rows * seq
    products = tokens * (heads * (nope + rope + nope + dv) + rope) * 4
    tables = tokens * rope * 4
    laid_out = tokens * heads * (2 * (nope + rope) + dv) * 2
    turned = float(tokens * (heads + 1) * rope * 3)
    return {"latent_rope_fwd": (turned, float(products + tables + laid_out)),
            "latent_rope_bwd": (float(tokens * (2 * heads) * rope * 3),
                                float(products + tables + laid_out))}

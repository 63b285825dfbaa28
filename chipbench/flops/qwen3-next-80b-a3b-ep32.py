"""Operations one training step of the ``qwen3-next-80b-a3b-ep32``
configuration needs, from shapes alone, and the operations and bytes of
one call of each kernel of its two mixers.

Counted: matrix products only, on the pairs, rows and chunks the
algorithm needs. A linear-attention layer counts its two projections and
the gated delta rule's chunked (WY) form AT CHUNK 64, whatever chunk a
kernel uses, so that a later kernel is read against the same work: a
chunk a value head, with ``C`` = 64 and heads of 128, ``K K^T``, ``Q
K^T``, ``W = T (beta exp(G) K)``, ``U = T (beta V)`` and ``P V'`` (five
products of ``2 C^2 128``), ``W S``, ``Q S`` and the state's ``K^T V'``
(three of ``2 C 128^2``) and the unit triangular inverse as a
substitution (``2 C^3 / 3``); the products a kernel adds to find that
inverse by blocks, or to turn a token's scalars, are its own and count
nothing. A full layer counts its projections (queries AND their gate)
and the KEPT causal pairs, ``T (T + 1) / 2`` a row a head at 256. The
routed experts count the expected rows (tokens x experts a token x held
/ routed); the shared expert and its gate run on every token. Backward
is twice forward. Recomputation is not counted. The convolution's four
multiply-adds a channel, embedding lookups, norms, the rotary step, the
sigmoids, the decays and the top-k are not matrix products and count
nothing.
"""

RULE_CHUNK = 64   # the chunk the rule's work is counted at


def is_full(layer: int, cfg: dict) -> bool:
    return (layer + 1) % cfg["full_attention_interval"] == 0


def kept_pairs(seq: int) -> int:
    """Kept query-key pairs of one row of ``seq`` tokens in a full layer."""
    return seq * (seq + 1) // 2


def rule_chunk_flops(cfg: dict) -> float:
    """Forward matrix-product operations of one chunk of one value head."""
    c, d_k, d_v = RULE_CHUNK, cfg["linear_key_head_dim"], \
        cfg["linear_value_head_dim"]
    return (2 * c * c * (3 * d_k + 2 * d_v) + 3 * 2 * c * d_k * d_v
            + 2 * c ** 3 / 3)


def _linear_widths(cfg: dict) -> tuple:
    keys = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    values = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    return keys, values


def forward_flops_by_part(cfg: dict, rows: int, seq: int) -> dict:
    """Forward operations of one step on one chip, by part."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    keys, values = _linear_widths(cfg)
    n_v, tokens = cfg["linear_num_value_heads"], rows * seq
    n_full = sum(is_full(i, cfg) for i in range(cfg["num_hidden_layers"]))
    n_linear = cfg["num_hidden_layers"] - n_full
    layers = cfg["num_hidden_layers"]
    held_share = len(cfg["experts_held"]) / cfg["num_routed_experts"]
    width = cfg["moe_intermediate_size"]
    return {
        "linear_projections": n_linear * tokens * 2 * d * (
            2 * keys + 2 * values + 2 * n_v + values),
        "gated_delta_rule": n_linear * rows * (seq // RULE_CHUNK) * n_v
        * rule_chunk_flops(cfg),
        "full_projections": n_full * tokens * 2 * d * hd * (
            2 * heads + 2 * kv + heads),
        "attention": n_full * rows * kept_pairs(seq) * heads * 4 * hd,
        "router": layers * tokens * 2 * d * cfg["num_routed_experts"],
        "experts": layers * tokens * cfg["num_experts_per_tok"] * held_share
        * 3 * 2 * d * width,
        "shared_expert": layers * tokens * (
            3 * 2 * d * cfg["shared_expert_intermediate_size"] + 2 * d),
        "head": tokens * 2 * d * cfg["vocab_size"],
    }


def train_step_flops(cfg: dict, rows: int, seq: int) -> float:
    return float(3 * sum(forward_flops_by_part(cfg, rows, seq).values()))


# One call of each kernel of ``ops/gated_delta_rule.py`` on ``rows`` rows
# (one linear layer): the WY form's products at chunk 64 (backward: twice
# forward; the states and ``T`` it recomputes count nothing), and the
# bytes it has to move once at the true widths: ``q`` and ``k`` once a
# KEY head and ``v`` and ``o`` a value head in bf16, the two scalars a
# token a value head in float32, and the states kept, one ``[128, 128]``
# float32 a value head a block of ``STATE_EVERY`` tokens, written by the
# forward kernel and read by the backward; backward also the cotangents
# of all five operands and ``do``.
STATE_EVERY = 512


def gated_delta_kernel_cost(cfg: dict, rows: int, seq: int) -> dict:
    keys, values = _linear_widths(cfg)
    n_v, tokens = cfg["linear_num_value_heads"], rows * seq
    flops = rows * (seq // RULE_CHUNK) * n_v * rule_chunk_flops(cfg)
    qkv = tokens * (2 * keys + values) * 2
    scalars = tokens * n_v * 2 * 4
    states = (rows * n_v * -(-seq // STATE_EVERY)
              * cfg["linear_key_head_dim"] * cfg["linear_value_head_dim"] * 4)
    out = tokens * values * 2
    return {"gdn_fwd": (float(flops), float(qkv + scalars + out + states)),
            "gdn_bwd": (float(2 * flops),
                        float(2 * (qkv + scalars) + out + states))}


# One call of each kernel of ``ops/rule_attention.py`` under the name
# ``causal`` on ``rows`` rows, as ``flops/laguna-xs.2-ep16.py`` counts
# them at 128: operations on the KEPT pairs, per pair and query head the
# matrix products the kernel's algorithm makes (forward: scores and PV;
# dq: scores, dP, dQ; dkv: scores, dV, dP, dK) at 256, and the bytes it
# has to move once: operands, results and the row statistics at one
# float a row.
_PRODUCTS = {"fwd": 2, "bwd_dq": 3, "bwd_dkv": 4}


def causal_attention_kernel_cost(cfg: dict, rows: int, seq: int) -> dict:
    heads, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    tokens, pairs = rows * seq, rows * kept_pairs(seq)
    q_bytes = tokens * heads * hd * 2      # bf16; also o, do, dq
    kv_bytes = tokens * kv * hd * 2        # each of k, v, dk, dv
    stats = tokens * heads * 4
    moved = {"fwd": 2 * q_bytes + 2 * kv_bytes + stats,
             "bwd_dq": 3 * q_bytes + 2 * kv_bytes + 2 * stats,
             "bwd_dkv": 2 * q_bytes + 4 * kv_bytes + 2 * stats}
    return {f"causal_attn_{k}": (float(pairs * heads * n * 2 * hd),
                                 float(moved[k]))
            for k, n in _PRODUCTS.items()}

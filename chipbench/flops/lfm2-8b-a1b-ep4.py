"""Operations one training step of the ``lfm2-8b-a1b-ep4`` configuration
needs, from shapes alone, and the operations and bytes of one call of
each kernel of its two mixers.

Counted: matrix products only, on the pairs and rows the algorithm
needs. A convolution layer counts its two projections (``[2048, 3 x
2048]`` and ``[2048, 2048]``); an attention layer its four projections
and the KEPT causal pairs, ``T (T + 1) / 2`` a row a head at the 64 dims
a pair really has (a kernel that spends a whole register's depth on a
64-wide head spends it; it is not counted). The dense layer counts its
three products on every token; the routed experts count the expected
rows (tokens x experts a token x held / routed); the head is the tied
embedding's product, counted once a token like any head. Backward is
twice forward. Recomputation is not counted. The convolution's taps and
gates (7 multiply-adds a channel a token), embedding lookups, norms, the
rotary step, the sigmoids and the top-k are not matrix products and
count nothing in a step's operations; the fused pass has a cost of its
own below, for its roofline.
"""


def kept_pairs(seq: int) -> int:
    """Kept query-key pairs of one row of ``seq`` tokens in an attention
    layer."""
    return seq * (seq + 1) // 2


def _head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def forward_flops_by_part(cfg: dict, rows: int, seq: int) -> dict:
    """Forward operations of one step on one chip, by part."""
    d, hd = cfg["hidden_size"], _head_dim(cfg)
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    tokens = rows * seq
    n_conv = sum(kind == "conv" for kind in cfg["layer_types"])
    n_full = len(cfg["layer_types"]) - n_conv
    n_dense = cfg["num_dense_layers"]
    n_moe = len(cfg["layer_types"]) - n_dense
    held_share = len(cfg["experts_held"]) / cfg["num_routed_experts"]
    return {
        "conv_projections": n_conv * tokens * 2 * d * (3 * d + d),
        "full_projections": n_full * tokens * 2 * d * hd * (
            2 * heads + 2 * kv),
        "attention": n_full * rows * kept_pairs(seq) * heads * 4 * hd,
        "dense_mlp": n_dense * tokens * 3 * 2 * d * cfg["intermediate_size"],
        "router": n_moe * tokens * 2 * d * cfg["num_routed_experts"],
        "experts": n_moe * tokens * cfg["num_experts_per_tok"] * held_share
        * 3 * 2 * d * cfg["moe_intermediate_size"],
        "head": tokens * 2 * d * cfg["vocab_size"],
    }


def train_step_flops(cfg: dict, rows: int, seq: int) -> float:
    return float(3 * sum(forward_flops_by_part(cfg, rows, seq).values()))


# One call of each kernel of ``ops/short_conv_gate.py`` on ``rows`` rows
# (one convolution layer), at float32 products and a bfloat16 result: the
# bytes it has to move once a channel a token (forward 12 read, ``B``,
# ``C`` and ``u``, and 2 written; backward 12 and the cotangent's 2 read
# and the product's cotangent, 12, written) and its operations, from
# shapes alone: forward the in-gate's product, three multiply-adds and
# the out-gate's product (8 a channel a token); backward those again and
# the three gradients' (``dC``, ``dc``, three multiply-adds for ``ds``,
# ``dB``, ``du`` and three for the taps': 22). The halo's rows are the
# kernel's own and count nothing.
def short_conv_kernel_cost(cfg: dict, rows: int, seq: int) -> dict:
    channels = rows * seq * cfg["hidden_size"]
    return {"sconv_fwd": (float(8 * channels), float(14 * channels)),
            "sconv_bwd": (float(22 * channels), float(26 * channels))}


# One call of each kernel of ``ops/rule_attention.py`` under the name
# ``causal`` on ``rows`` rows, as ``flops/laguna-xs.2-ep16.py`` counts
# them at 128: operations on the KEPT pairs, per pair and query head the
# matrix products the kernel's algorithm makes (forward: scores and PV;
# dq: scores, dP, dQ; dkv: scores, dV, dP, dK) at the 64 dims a pair
# really has, and the bytes it has to move once at the true width:
# operands, results and the row statistics at one float a row.
_PRODUCTS = {"fwd": 2, "bwd_dq": 3, "bwd_dkv": 4}


def causal_attention_kernel_cost(cfg: dict, rows: int, seq: int) -> dict:
    heads, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        _head_dim(cfg)
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

"""Operations one training step of the ``mellum2-12b-a2.5b-ep4``
configuration needs ON ONE CHIP of its four, from shapes alone, and the
operations and bytes of one call of each attention kernel of its two
kinds of layer.

Counted: matrix products only, on the pairs and rows the algorithm
needs. Attention counts the KEPT query-key pairs of a layer's rule, not
the pairs of the tiles a kernel visits: a full layer ``T (T + 1) / 2`` a
row, a window layer ``w (w + 1) / 2 + (T - w) w``. The routed experts
count the pairs a chip holds AVERAGED over the four, which is no
expectation: the four chips hold every expert between them, so the held
pairs of a step (the records' ``moe_rows``, summed over the chips) are
all the chosen pairs, tokens x experts a token, and a chip's average of
them is its own rows' pairs, whatever the router does; how unevenly the
chips share them is ``moe_load_max_over_mean``'s to say. The exchange
moves rows and multiplies nothing. Backward is twice forward.
Recomputation is not counted. Embedding lookups, norms, rotary steps and
the top-k are not matrix products and count nothing.
"""


def kept_pairs(layer_type: str, seq: int, window: int) -> int:
    """Kept query-key pairs of one row of ``seq`` tokens in one layer."""
    if layer_type == "full_attention":
        return seq * (seq + 1) // 2
    w = min(window, seq)
    return w * (w + 1) // 2 + (seq - w) * w


def forward_flops_by_part(cfg: dict, rows: int, seq: int) -> dict:
    """Forward operations of one step on one chip (``rows`` rows of its
    own), by part."""
    d, hd, kv = cfg["hidden_size"], cfg["head_dim"], cfg["num_key_value_heads"]
    heads, layers = cfg["num_attention_heads"], cfg["num_hidden_layers"]
    tokens = rows * seq
    return {
        "projections": layers * tokens * 2 * d * hd * (2 * heads + 2 * kv),
        "attention": sum(
            rows * kept_pairs(kind, seq, cfg["sliding_window"]) * heads
            * 4 * hd for kind in cfg["layer_types"]),
        "router": layers * tokens * 2 * d * cfg["num_experts"],
        "experts": layers * tokens * cfg["num_experts_per_tok"]
        * 3 * 2 * d * cfg["moe_intermediate_size"],
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
    ``layer_type``."""
    heads, hd = cfg["num_attention_heads"], cfg["head_dim"]
    kv, tokens = cfg["num_key_value_heads"], rows * seq
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

"""Operations one training step of the BERT classifier needs, from
shapes alone (copied in substance from ``sparktorch_tpu/bench.py``
``_bert_flops_accounting``, PR 23): matrix-multiply weights applied per
token, the attention score and value products, and the per-example
head. Embedding lookups are gathers and count nothing; backward is
twice forward; recomputation is not counted."""


def train_step_flops(cfg: dict, rows: int, seq: int) -> float:
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    n_layers = cfg["num_hidden_layers"]
    per_token = n_layers * (3 * d * d + d * d + 2 * d * ff)
    per_example = d * d + d * cfg["num_labels"]
    tokens = rows * seq
    attention = 4 * n_layers * rows * seq * seq * d
    forward = 2 * per_token * tokens + attention + 2 * per_example * rows
    return 3.0 * forward

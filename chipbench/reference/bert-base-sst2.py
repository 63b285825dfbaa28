"""Plain reference of the ``bert-base-sst2`` configuration: the encoder
classifier in straightforward ``jax.numpy``, float32, no kernels.

It follows Devlin et al. 2018 at the published sizes, with the
departures the program's block makes and the configuration file lists
under ``assumed``: pre-LN residual order, tanh-approximated GELU,
LayerNorm eps 1e-6, no token-type embedding, no embedding LayerNorm,
mean pooling into the tanh pooler. It imports nothing of the program.

The tree of weights has the names the program's module gives its own,
so handing the weights over needs no renaming.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference import _ops

_LN_EPS = 1e-6


def _sizes(cfg: dict):
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    return (d, cfg["num_hidden_layers"], heads, d // heads,
            cfg["intermediate_size"], cfg["vocab_size"],
            cfg["max_position_embeddings"], cfg["num_labels"])


def init(key, cfg: dict) -> dict:
    """``{"params": tree}`` from one key: N(0, 0.02) matrices and
    embeddings (BERT's ``initializer_range``), zero biases, unit
    LayerNorm scales."""
    d, n_layers, heads, hd, ff, vocab, n_pos, n_cls = _sizes(cfg)
    draws = _ops.Draws()

    def ln():
        return {"scale": jnp.ones((d,), jnp.float32),
                "bias": jnp.zeros((d,), jnp.float32)}

    def dense(shape_in, shape_out):
        return {"kernel": draws.normal((*shape_in, *shape_out)),
                "bias": jnp.zeros(shape_out, jnp.float32)}

    backbone = {"tok_embed": {"embedding": draws.normal((vocab, d))},
                "pos_embed": draws.normal((n_pos, d)),
                "ln_final": ln()}
    for i in range(n_layers):
        backbone[f"layer_{i}"] = {
            "ln_attn": ln(),
            "attn": {"qkv": dense((d,), (3, heads, hd)),
                     "proj": dense((heads, hd), (d,))},
            "ln_mlp": ln(),
            "mlp_in": dense((d,), (ff,)),
            "mlp_out": dense((ff,), (d,)),
        }
    tree = {"backbone": backbone, "pooler": dense((d,), (d,)),
            "classifier": dense((d,), (n_cls,))}
    drawn = draws.cut(key)
    return {"params": jax.tree.map(
        lambda leaf: 0.02 * drawn[leaf] if isinstance(leaf, int) else leaf,
        tree)}


def _layer_norm(x, p):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + _LN_EPS) * p["scale"] + p["bias"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def forward(variables: dict, ids, cfg: dict, precision: str = "f32"):
    """Logits ``(rows, num_labels)`` of integer ``ids`` ``(rows, seq)``."""
    p = variables["params"]
    bb = p["backbone"]
    ein = lambda eq, a, b: _ops.einsum(eq, a, b, precision)
    ids = ids.astype(jnp.int32)
    seq = ids.shape[1]
    x = bb["tok_embed"]["embedding"][ids] + bb["pos_embed"][None, :seq]
    x = _ops.round_to(x, precision)
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    for i in range(cfg["num_hidden_layers"]):
        lp = bb[f"layer_{i}"]
        h = _layer_norm(x, lp["ln_attn"])
        qkv = ein("bsd,dthk->bsthk", h, lp["attn"]["qkv"]["kernel"]) \
            + lp["attn"]["qkv"]["bias"]
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        scores = ein("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
        weights = jax.nn.softmax(scores, axis=-1)
        out = ein("bhqk,bkhd->bqhd", weights, v)
        x = x + ein("bqhd,hdm->bqm", out, lp["attn"]["proj"]["kernel"]) \
            + lp["attn"]["proj"]["bias"]
        h = _layer_norm(x, lp["ln_mlp"])
        h = _gelu_tanh(ein("bsd,df->bsf", h, lp["mlp_in"]["kernel"])
                       + lp["mlp_in"]["bias"])
        x = x + ein("bsf,fd->bsd", h, lp["mlp_out"]["kernel"]) \
            + lp["mlp_out"]["bias"]
    x = _layer_norm(x, bb["ln_final"])
    pooled = jnp.mean(x, axis=1)
    pooled = jnp.tanh(ein("bd,de->be", pooled, p["pooler"]["kernel"])
                      + p["pooler"]["bias"])
    return ein("bd,dc->bc", pooled, p["classifier"]["kernel"]) \
        + p["classifier"]["bias"]


def loss_sum(variables: dict, x, y, w, cfg: dict, precision: str = "f32"):
    """Weighted sum of the per-example losses of one block of rows."""
    per = _ops.cross_entropy(forward(variables, x, cfg, precision), y)
    return jnp.sum(per * w)

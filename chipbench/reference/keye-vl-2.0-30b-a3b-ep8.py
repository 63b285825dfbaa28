"""Plain reference of the ``keye-vl-2.0-30b-a3b-ep8`` configuration:
the language model of Keye-VL-2.0-30B-A3B (Qwen3-MoE's block, grouped
query heads, M-RoPE, with DeepSeek-V3.2's lightning indexer selecting
the keys each query attends) in straightforward ``jax.numpy``, float32,
no kernels, one chip's share of the experts and of the vocabulary.

One layer, for the tokens ``x`` of a row (``T`` tokens), in the
published order; what the source does not say is listed in the
configuration file under ``assumed``:

- ``h = RMSNorm(x)``; ``q = h Wq`` (32 heads of 128), ``k = h Wk``,
  ``v = h Wv`` (4 heads), no biases; RMSNorm over the 128 of each q and
  k head; M-RoPE: three position ids a token, the 64 frequency pairs of
  theta 1e7 cut 16 / 24 / 24 among them, rotation by halves.
- Indexer on ``stop_gradient(h)``: ``qI = h WIq`` (16 heads of 64),
  ``kI = LayerNorm(h WIk)`` (one head of 64), ``w = h WIw`` (16),
  rotary by the temporal id on the first 32 dims; ``I[t, s] = sum_j
  16^-0.5 64^-0.5 w[t, j] relu(qI[t, j] . kI[s])``; ``S_t`` is the 2,048
  keys of largest ``I[t, :]`` among ``s <= t`` (all while ``t <
  2,048``), ties to the lower index.
- head ``i`` attends with kv head ``i // 8`` over ``S_t`` only, dense
  scores with a mask, in blocks of queries so that it fits; ``x = x +
  concat(o) Wo``.
- ``g = RMSNorm(x)``; ``p = softmax(g Wr)`` over all 128 experts; the 8
  largest, renormalised to sum 1; expert ``e`` is ``Wd_e (silu(Wg_e g)
  * Wu_e g)``. Every held expert (``experts_held``) runs on every token
  and is weighted by its gate, 0 where the token did not choose it;
  what experts held elsewhere would add is left out.

Then RMSNorm and an untied head over the configuration's slice of the
vocabulary. It imports nothing of the program; the tree of weights has
the names the program's module gives its own.

``cfg["fault"]`` plants a fault for the job's ``control``:
``no_selection`` (every causal key attended), ``shifted_share`` (the
layer told it holds the next block of experts), ``no_renorm`` (gates
not renormalised).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference import _ops

_Q_BLOCK = 512   # queries a block of dense scores: [heads, 512, T]


def _sizes(cfg: dict) -> dict:
    sa = cfg["sa_config"]
    return dict(
        d=cfg["hidden_size"], layers=cfg["num_hidden_layers"],
        heads=cfg["num_attention_heads"], kv=cfg["num_key_value_heads"],
        hd=cfg["head_dim"], vocab=cfg["vocab_size"],
        routed=cfg["num_local_experts"], per_tok=cfg["num_experts_per_tok"],
        width=cfg["moe_intermediate_size"], held=list(cfg["experts_held"]),
        eps=cfg["rms_norm_eps"], theta=float(cfg["rope_theta"]),
        sections=list(cfg["rope_scaling"]["mrope_section"]),
        ih=sa["indexer_num_heads"], idim=sa["indexer_head_dim"],
        irope=cfg["indexer_rope_dims"], topk=sa["topk"],
        embed_std=cfg["embedding_init_std"])


def init(key, cfg: dict) -> dict:
    """``{"params": tree}`` from one key: N(0, 0.02) matrices, N(0,
    ``embedding_init_std``) embedding rows, unit norm gains, zero
    LayerNorm bias."""
    z = _sizes(cfg)
    d, hd, n_held = z["d"], z["hd"], len(z["held"])
    draws = _ops.Draws()
    ones = lambda n: jnp.ones((n,), jnp.float32)
    tree = {"embed": draws.normal((z["vocab"], d)), "final_norm": ones(d),
            "head": draws.normal((d, z["vocab"]))}
    for i in range(z["layers"]):
        tree[f"layer_{i}"] = {
            "attn_norm": ones(d),
            "attn": {
                "wq": draws.normal((d, z["heads"], hd)),
                "wk": draws.normal((d, z["kv"], hd)),
                "wv": draws.normal((d, z["kv"], hd)),
                "wo": draws.normal((z["heads"], hd, d)),
                "q_norm": ones(hd), "k_norm": ones(hd),
                "idx_wq": draws.normal((d, z["ih"], z["idim"])),
                "idx_wk": draws.normal((d, z["idim"])),
                "idx_ww": draws.normal((d, z["ih"])),
                "idx_k_norm": {"scale": ones(z["idim"]),
                               "bias": jnp.zeros((z["idim"],), jnp.float32)},
            },
            "moe_norm": ones(d),
            "moe": {"router": draws.normal((d, z["routed"])),
                    "w_gate": draws.normal((n_held, d, z["width"])),
                    "w_up": draws.normal((n_held, d, z["width"])),
                    "w_down": draws.normal((n_held, z["width"], d))},
        }
    drawn = draws.cut(key)
    params = jax.tree.map(
        lambda leaf: 0.02 * drawn[leaf] if isinstance(leaf, int) else leaf,
        tree)
    params["embed"] = params["embed"] * (z["embed_std"] / 0.02)
    return {"params": params}


def _rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * gain


def _layer_norm(x, p, eps=1e-6):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _rotate(x, angles):
    """Rotation by halves of the last axis of ``x [T, heads, n]`` by
    ``angles [T, n / 2]``."""
    half = x.shape[-1] // 2
    cos, sin = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _inv_freq(n_pairs, theta):
    return theta ** (-jnp.arange(n_pairs, dtype=jnp.float32) / n_pairs)


def selected(scores, first_query: int, topk: int):
    """Bool ``[queries, T]``: the ``topk`` keys of largest score among
    ``s <= t`` for the queries ``t = first_query + row``; all of them
    while ``t < topk``; ties to the lower index."""
    n, t_all = scores.shape
    t = first_query + jnp.arange(n)[:, None]
    causal = jnp.arange(t_all)[None, :] <= t
    if t_all <= topk:
        return causal
    s = jnp.where(causal, jnp.where(scores == 0, 0.0, scores), -jnp.inf)
    kth = jax.lax.top_k(s, topk)[0][:, -1:]
    above, tied = s > kth, s == kth
    need = topk - jnp.sum(above, -1, keepdims=True)
    taken = above | (tied & (jnp.cumsum(tied, -1) <= need))
    return jnp.where(t < topk, causal, taken)


def _attention_row(lp, h, pos, z, ein, fault, with_selected):
    """Attention output (before ``Wo``) of one row: ``h [T, d]``,
    ``pos [3, T]``; and the selected sets ``[T, T]`` where asked for."""
    t_all, hd = h.shape[0], z["hd"]
    group = z["heads"] // z["kv"]
    q = _rms_norm(ein("td,dhk->thk", h, lp["wq"]), lp["q_norm"], z["eps"])
    k = _rms_norm(ein("td,dhk->thk", h, lp["wk"]), lp["k_norm"], z["eps"])
    v = ein("td,dhk->thk", h, lp["wv"])
    section = jnp.repeat(jnp.arange(3), jnp.asarray(z["sections"]),
                         total_repeat_length=hd // 2)
    angles = pos.astype(jnp.float32)[section].T * _inv_freq(hd // 2,
                                                            z["theta"])
    q, k = _rotate(q, angles), _rotate(k, angles)
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)

    hi = jax.lax.stop_gradient(h)
    q_i = ein("td,dhk->thk", hi, lp["idx_wq"])
    k_i = _layer_norm(ein("td,dk->tk", hi, lp["idx_wk"]), lp["idx_k_norm"])
    w_i = ein("td,dh->th", hi, lp["idx_ww"]) * (
        z["ih"] ** -0.5 * z["idim"] ** -0.5)
    r = z["irope"]
    ang_i = pos[0].astype(jnp.float32)[:, None] * _inv_freq(r // 2, z["theta"])
    q_i = jnp.concatenate([_rotate(q_i[..., :r], ang_i), q_i[..., r:]], -1)
    k_i = jnp.concatenate(
        [_rotate(k_i[:, None, :r], ang_i)[:, 0], k_i[:, r:]], -1)

    block = min(_Q_BLOCK, t_all)

    @jax.checkpoint
    def one_block(first):
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, first, block, 0)
        index = jnp.einsum(
            "qh,hqs->qs", sl(w_i),
            jax.nn.relu(ein("qhk,sk->hqs", sl(q_i), k_i)),
            precision=_ops.HIGHEST)
        keep = selected(index, first, z["topk"])
        if fault == "no_selection":
            keep = jnp.arange(t_all)[None, :] <= first + jnp.arange(block)[:, None]
        s = ein("qhk,shk->hqs", sl(q), k) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), -1)
        return ein("hqs,shk->qhk", p, v), keep if with_selected else None

    out, keep = jax.lax.map(one_block, jnp.arange(0, t_all, block))
    return (out.reshape(t_all, z["heads"], hd),
            keep.reshape(t_all, t_all) if with_selected else None)


def _experts_row(lp, g, z, ein, fault):
    """This chip's part of the expert layer's result for ``g [T, d]``."""
    held = z["held"]
    if fault == "shifted_share":
        held = [(e + len(held)) % z["routed"] for e in held]
    p = jax.nn.softmax(ein("td,de->te", g, lp["router"]), -1)
    top_p, top_e = jax.lax.top_k(p, z["per_tok"])
    if fault != "no_renorm":
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    gates = jnp.sum(jax.nn.one_hot(top_e, z["routed"]) * top_p[..., None], 1)

    @jax.checkpoint  # the backward pass recomputes an expert's hidden
    def gated(g, gate, w_gate, w_up, w_down):
        hidden = jax.nn.silu(ein("td,df->tf", g, w_gate)) \
            * ein("td,df->tf", g, w_up)
        return gate[:, None] * ein("tf,fd->td", hidden, w_down)

    def one_expert(acc, ew):
        return acc + gated(g, *ew), None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(g),
        (gates[:, jnp.asarray(held)].T, lp["w_gate"], lp["w_up"],
         lp["w_down"]))
    return out


def forward(variables: dict, ids, cfg: dict, precision: str = "f32",
            position_ids=None, with_selected: bool = False):
    """Logits ``[rows, T, vocab]`` of integer ``ids [rows, T]``;
    ``position_ids`` is ``[3, rows, T]`` and defaults to the token's
    index. ``with_selected`` also returns the selected sets ``[layers,
    rows, T, T]``."""
    p, z = variables["params"], _sizes(cfg)
    fault = cfg.get("fault")
    ein = lambda eq, a, b: _ops.einsum(eq, a, b, precision)
    ids = ids.astype(jnp.int32)
    rows, t_all = ids.shape
    if position_ids is None:
        position_ids = jnp.broadcast_to(jnp.arange(t_all), (3, rows, t_all))

    @jax.checkpoint  # a layer's inside is recomputed, so that it fits
    def layer(lp, x, pos):
        h = _rms_norm(x, lp["attn_norm"], z["eps"])
        o, keep = _attention_row(lp["attn"], h, pos, z, ein, fault,
                                 with_selected)
        x = x + ein("thk,hkd->td", o, lp["attn"]["wo"])
        g = _rms_norm(x, lp["moe_norm"], z["eps"])
        return x + _experts_row(lp["moe"], g, z, ein, fault), keep

    def one_row(ids_row, pos):
        x, sets = p["embed"][ids_row], []
        for i in range(z["layers"]):
            x, keep = layer(p[f"layer_{i}"], x, pos)
            sets.append(keep)
        x = _rms_norm(x, p["final_norm"], z["eps"])
        return (ein("td,dv->tv", x, p["head"]),
                jnp.stack(sets) if with_selected else None)

    logits, sets = jax.lax.map(
        lambda a: one_row(*a), (ids, jnp.moveaxis(position_ids, 1, 0)))
    if with_selected:
        return logits, jnp.moveaxis(sets, 0, 1)
    return logits


def loss_sum(variables: dict, x, y, w, cfg: dict, precision: str = "f32"):
    """Weighted sum over the rows of each row's mean next-token cross
    entropy; ``y [rows, T]`` holds the labels."""
    logits = forward(variables, x, cfg, precision)
    rows, t_all, vocab = logits.shape
    per_token = _ops.cross_entropy(logits.reshape(rows * t_all, vocab),
                                   y.reshape(rows * t_all))
    return jnp.sum(jnp.mean(per_token.reshape(rows, t_all), -1) * w)

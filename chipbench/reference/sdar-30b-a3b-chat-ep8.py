"""Plain reference of the ``sdar-30b-a3b-chat-ep8`` configuration:
SDAR-30B-A3B-Chat (Qwen3-MoE's block, grouped query heads, plain rotary)
trained by masked block diffusion, in straightforward ``jax.numpy``,
float32, no kernels, one chip's share of the experts and of the
vocabulary.

A row is ``x_0``, ``L`` token ids. The noise is DATA here (``noise = (t
[rows], m [rows, L])``: the level of each row and which of its tokens
are masked), so the reference sees the masks the program drew: ``x_t[i]
= MASK if m[i] else x_0[i]``, and the model's input is ``z = [x_0 ;
x_t]``, ``2L`` tokens at positions ``p(i) = i mod L``. With blocks of
``b`` tokens, ``blk(i) = (i mod L) // b`` and ``noisy(i) = i >= L``,
query ``i`` attends key ``j`` iff

- both clean and ``blk(j) <= blk(i)``, or
- ``i`` noisy, ``j`` clean and ``blk(j) < blk(i)``, or
- both noisy and ``blk(j) == blk(i)``.

One layer, for the ``2L`` tokens ``x``; what the source does not say is
listed in the configuration file under ``assumed``:

- ``h = RMSNorm(x)``; ``q = h Wq`` (32 heads of 128), ``k = h Wk``, ``v =
  h Wv`` (4 heads), no biases; RMSNorm over the 128 of each q and k
  head; rotary by halves on all 128 dims at ``p(i)``, theta 1e6.
- head ``i`` attends with kv head ``i // 8`` over the allowed keys,
  dense scores under the mask as a boolean rule, in blocks of queries
  so that it fits; ``x = x + concat(o) Wo``.
- ``g = RMSNorm(x)``; ``p = softmax(g Wr)`` over all 128 experts; the 8
  largest, renormalised to sum 1; expert ``e`` is ``Wd_e (silu(Wg_e g)
  * Wu_e g)``. Every held expert (``experts_held``) runs on every token
  and is weighted by its gate, 0 where the token did not choose it;
  what experts held elsewhere would add is left out.

Then RMSNorm and an untied head over the configuration's slice of the
vocabulary, on the noised half only: the logit at noised position ``L +
i`` predicts ``x_0[i]`` (no shift). The loss of a row is ``(1 / L) sum_{i
< L, m_i = 1} (1 / t) CE(logits[L + i], x_0[i])``. It imports nothing of
the program; the tree of weights has the names the program's module
gives its own.

``cfg["fault"]`` plants a fault for the job's ``control``:
``own_block_seen`` (a noised query sees the clean copy of its own
block: the answer leaks), ``causal_mask`` (plain causal over the ``2L``
tokens), ``positions_not_shared`` (``p(i) = i``), ``no_loss_weight``
(``1 / t`` left out), ``loss_on_all`` (unmasked positions counted too),
``shifted_share`` (the layer told it holds the next block of experts),
``no_renorm`` (gates not renormalised).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference import _ops

_Q_BLOCK = 128   # queries a block of dense scores: [heads, 128, 2L]


def _sizes(cfg: dict) -> dict:
    return dict(
        d=cfg["hidden_size"], layers=cfg["num_hidden_layers"],
        heads=cfg["num_attention_heads"], kv=cfg["num_key_value_heads"],
        hd=cfg["head_dim"], vocab=cfg["vocab_size"],
        routed=cfg["num_routed_experts"], per_tok=cfg["num_experts_per_tok"],
        width=cfg["moe_intermediate_size"], held=list(cfg["experts_held"]),
        eps=cfg["rms_norm_eps"], theta=float(cfg["rope_theta"]),
        block=cfg["block_length"], mask_id=cfg["mask_token_id"],
        embed_std=cfg["embedding_init_std"])


def init(key, cfg: dict) -> dict:
    """``{"params": tree}`` from one key: N(0, 0.02) matrices, N(0,
    ``embedding_init_std``) embedding rows, unit norm gains."""
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


def _rotate(x, angles):
    """Rotation by halves of the last axis of ``x [T, heads, n]`` by
    ``angles [T, n / 2]``."""
    half = x.shape[-1] // 2
    cos, sin = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def allowed(i, j, seq_len: int, block: int, fault=None):
    """Whether query ``i`` attends key ``j`` (broadcast), of the ``2 x
    seq_len`` tokens of a clean row and its noised copy."""
    if fault == "causal_mask":
        return j <= i
    blk_i, blk_j = (i % seq_len) // block, (j % seq_len) // block
    clean_i, clean_j = i < seq_len, j < seq_len
    before = blk_j <= blk_i if fault == "own_block_seen" else blk_j < blk_i
    return ((clean_i & clean_j & (blk_j <= blk_i))
            | (~clean_i & clean_j & before)
            | (~clean_i & ~clean_j & (blk_j == blk_i)))


def _attention_row(lp, h, pos, z, ein, fault):
    """Attention output (before ``Wo``) of one doubled row: ``h [2L,
    d]``, ``pos [2L]``."""
    t_all, hd, kv = h.shape[0], z["hd"], z["kv"]
    q = _rms_norm(ein("td,dhk->thk", h, lp["wq"]), lp["q_norm"], z["eps"])
    k = _rms_norm(ein("td,dhk->thk", h, lp["wk"]), lp["k_norm"], z["eps"])
    v = ein("td,dhk->thk", h, lp["wv"])
    angles = pos.astype(jnp.float32)[:, None] * z["theta"] ** (
        -jnp.arange(hd // 2, dtype=jnp.float32) / (hd // 2))
    q, k = _rotate(q, angles), _rotate(k, angles)
    # query head i with key/value head i // group: [T, kv, group, hd]
    q = q.reshape(t_all, kv, z["heads"] // kv, hd)
    block = min(_Q_BLOCK, t_all)

    @jax.checkpoint
    def one_block(first):
        keep = allowed(first + jnp.arange(block)[:, None],
                       jnp.arange(t_all)[None, :], t_all // 2, z["block"],
                       fault)
        s = ein("qhgk,shk->hgqs",
                jax.lax.dynamic_slice_in_dim(q, first, block, 0),
                k) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), -1)
        return ein("hgqs,shk->qhgk", p, v)

    out = jax.lax.map(one_block, jnp.arange(0, t_all, block))
    return out.reshape(t_all, z["heads"], hd)


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
            noise=None, both_halves: bool = False):
    """Logits ``[rows, L, vocab]`` at the noised positions of integer
    ``ids [rows, L]`` under ``noise = (t [rows], m [rows, L])`` (nothing
    masked when None). ``both_halves`` gives ``[rows, 2L, vocab]``, the
    clean half's logits first (they enter no loss; for tests)."""
    p, z = variables["params"], _sizes(cfg)
    fault = cfg.get("fault")
    ein = lambda eq, a, b: _ops.einsum(eq, a, b, precision)
    ids = ids.astype(jnp.int32)
    rows, seq = ids.shape
    masked = (jnp.zeros(ids.shape, bool) if noise is None
              else noise[1].astype(bool))
    pos = jnp.arange(2 * seq)
    if fault != "positions_not_shared":
        pos = pos % seq

    @jax.checkpoint  # a layer's inside is recomputed, so that it fits
    def layer(lp, x):
        h = _rms_norm(x, lp["attn_norm"], z["eps"])
        o = _attention_row(lp["attn"], h, pos, z, ein, fault)
        x = x + ein("thk,hkd->td", o, lp["attn"]["wo"])
        g = _rms_norm(x, lp["moe_norm"], z["eps"])
        return x + _experts_row(lp["moe"], g, z, ein, fault)

    def one_row(row):
        ids_row, m_row = row
        doubled = jnp.concatenate(
            [ids_row, jnp.where(m_row, z["mask_id"], ids_row)])
        x = p["embed"][doubled]
        for i in range(z["layers"]):
            x = layer(p[f"layer_{i}"], x)
        if not both_halves:
            x = x[seq:]
        x = _rms_norm(x, p["final_norm"], z["eps"])
        return ein("td,dv->tv", x, p["head"])

    return jax.lax.map(one_row, (ids, masked))


def loss_sum(variables: dict, x, y, w, cfg: dict, precision: str = "f32",
             noise=None):
    """Weighted sum over the rows of each row's loss; ``y [rows, L]``
    holds the labels (the row itself), ``noise`` the levels and masks."""
    level, masked = noise
    logits = forward(variables, x, cfg, precision, noise)
    rows, seq, vocab = logits.shape
    per_token = _ops.cross_entropy(logits.reshape(rows * seq, vocab),
                                   y.reshape(rows * seq)).reshape(rows, seq)
    fault = cfg.get("fault")
    weight = masked.astype(jnp.float32)
    if fault == "loss_on_all":
        weight = jnp.ones_like(weight)
    if fault != "no_loss_weight":
        weight = weight / level.reshape(rows, 1)
    return jnp.sum(jnp.mean(per_token * weight, -1) * w)

"""Plain reference of the ``mellum2-12b-a2.5b-ep4`` configuration:
Mellum2-12B-A2.5B (window and full-causal layers 3 : 1 over grouped query
heads, every layer 64 softmax-routed experts, 8 a token, no shared
expert, no dense layer) in straightforward ``jax.numpy``, float32, no
kernels, ALL 64 experts of every layer: the configuration's four chips
hold each layer whole between them, so nothing of a layer is left out
here and no share is taken. It knows no chip, no mesh and no exchange;
where its caller lays the experts' leaves over four devices, the
compiler's partitioner follows them (the experts are taken
``expert_groups`` at a time, one of each group's block, so that each
device computes on the block it holds).

Layer ``l`` of a row's ``T`` tokens ``x``, of kind ``layer_types[l]``
(``full_attention`` or ``sliding_attention``), 32 query heads on 4
key/value heads of 128; what the source does not say is listed in the
configuration file under ``assumed``:

- ``h = RMSNorm(x)``; ``q = h Wq``, ``k = h Wk``, ``v = h Wv``, no
  biases; RMSNorm over the 128 of each q and k head.
- Rotary by halves on all 128 dims of q and k, by the kind's
  ``rope_parameters``: sliding ``inv_freq_i = 5e5^(-2i / 128)``; full
  YaRN over ``D`` = 128 dims: ``e_i = 5e5^(-2i / D)``, ``c(b) = D ln(8192
  / (2 pi b)) / (2 ln 5e5)``, ``low = max(floor(c(beta_fast)), 0)``,
  ``high = min(ceil(c(beta_slow)), D - 1)``, ``ramp_i = clip((i - low) /
  (high - low), 0, 1)``, ``inv_freq_i = (e_i / factor) ramp_i + e_i (1 -
  ramp_i)``; ``cos`` and ``sin`` times ``attention_factor``.
- Query ``i`` attends key ``j`` iff ``j <= i`` (full), and ``i - j <
  sliding_window`` too (sliding: 1,024 keys with its own); softmax of
  ``q_i . k_j / sqrt(128)``, head ``i`` with kv head ``i // 8``, dense
  scores under the mask, a block of queries at a time so that it fits.
- ``x = x + concat(o) Wo``; ``g = RMSNorm(x)``.
- ``p = softmax(g Wr)`` over all 64 experts; the 8 largest (ties to the
  lower index); gates ``p_e / sum_chosen p`` (``norm_topk_prob``); ``x =
  x + sum_e gate_e Wd_e (silu(Wg_e g) * Wu_e g)``: every expert runs on
  every token and is weighted by its gate, 0 where the token did not
  choose it.

Then RMSNorm and an untied head over the configuration's slice of the
vocabulary; the loss is a row's mean next-token cross entropy. It
imports nothing of the program; the tree of weights has the names the
program's module gives its own.

``cfg["fault"]`` plants a fault for the job's ``control``:
``window_ignored`` (sliding layers attend every causal key),
``window_1025`` (one key too many), ``rope_swapped`` (sliding layers
turned by the full layers' table), ``no_yarn`` (full layers: plain rotary
of theta 5e5, no attention factor), ``no_renorm`` (gates not
renormalised), and the two an expert-parallel step could hide:
``own_rows_only`` (the exchange skipped: a row's tokens reach only the
experts of the member that holds the row, ``members`` says which) and
``experts_psummed`` (the experts' gradient summed over the members once
more: theirs alone comes out ``expert_groups`` times too large).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import _ops

_Q_BLOCK = 128   # queries a block of dense scores: [heads, 128, T]
_EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def _sizes(cfg: dict) -> dict:
    return dict(
        d=cfg["hidden_size"], layers=cfg["num_hidden_layers"],
        heads=cfg["num_attention_heads"], kinds=list(cfg["layer_types"]),
        kv=cfg["num_key_value_heads"], hd=cfg["head_dim"],
        vocab=cfg["vocab_size"], experts=cfg["num_experts"],
        per_tok=cfg["num_experts_per_tok"],
        width=cfg["moe_intermediate_size"], eps=cfg["rms_norm_eps"],
        window=cfg["sliding_window"], rope=cfg["rope_parameters"],
        groups=cfg["expert_groups"], embed_std=cfg["embedding_init_std"])


def init(key, cfg: dict) -> dict:
    """``{"params": tree}`` from one key: N(0, 0.02) matrices, N(0,
    ``embedding_init_std``) embedding rows, unit norm gains. Everything
    but the experts' leaves is cut from ONE draw of ``key``; each
    expert leaf ``[64, ...]`` is a draw of its own, of ``fold_in(key,
    3 l + i)`` for leaf ``i`` of layer ``l``, so that a caller that lays
    it over devices never has to hold it whole."""
    z = _sizes(cfg)
    d, hd, f = z["d"], z["hd"], z["width"]
    draws = _ops.Draws()
    ones = lambda n: jnp.ones((n,), jnp.float32)
    shapes = {"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}
    tree = {"embed": draws.normal((z["vocab"], d)), "final_norm": ones(d),
            "head": draws.normal((d, z["vocab"]))}
    for l in range(z["layers"]):
        tree[f"layer_{l}"] = {
            "attn_norm": ones(d), "moe_norm": ones(d),
            "attn": {"wq": draws.normal((d, z["heads"], hd)),
                     "wk": draws.normal((d, z["kv"], hd)),
                     "wv": draws.normal((d, z["kv"], hd)),
                     "wo": draws.normal((z["heads"], hd, d)),
                     "q_norm": ones(hd), "k_norm": ones(hd)},
            "moe": {"router": draws.normal((d, z["experts"])),
                    **{name: 0.02 * jax.random.normal(
                        jax.random.fold_in(key, 3 * l + i),
                        (z["experts"], *shapes[name]), jnp.float32)
                       for i, name in enumerate(_EXPERT_LEAVES)}}}
    drawn = draws.cut(key)
    params = jax.tree.map(
        lambda leaf: 0.02 * drawn[leaf] if isinstance(leaf, int) else leaf,
        tree)
    params["embed"] = params["embed"] * (z["embed_std"] / 0.02)
    return {"params": params}


def _rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * gain


def inv_freq(rope: dict, head_dim: int, fault=None):
    """``(float32 [head_dim / 2] frequencies, factor on cos and sin)`` of
    one kind's ``rope_parameters``."""
    theta = float(rope["rope_theta"])
    if rope["rope_type"] == "default" or fault == "no_yarn":
        return theta ** (-jnp.arange(head_dim // 2, dtype=jnp.float32)
                         / (head_dim // 2)), 1.0
    # rope_type yarn, in float64 on the host
    i = np.arange(head_dim // 2, dtype=np.float64)
    e = theta ** (-2.0 * i / head_dim)
    orig = float(rope["original_max_position_embeddings"])
    c = lambda b: (head_dim * np.log(orig / (2 * np.pi * b))
                   / (2 * np.log(theta)))
    low = max(np.floor(c(float(rope["beta_fast"]))), 0.0)
    high = min(np.ceil(c(float(rope["beta_slow"]))), head_dim - 1.0)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    blended = e / float(rope["factor"]) * ramp + e * (1.0 - ramp)
    return jnp.asarray(blended, jnp.float32), float(rope["attention_factor"])


def _rotate(x, angles, factor):
    """Rotation by halves of ``x [T, heads, n]`` by ``angles [T, n / 2]``."""
    half = angles.shape[-1]
    cos = factor * jnp.cos(angles)[:, None]
    sin = factor * jnp.sin(angles)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def allowed(i, j, layer_type: str, window: int, fault=None):
    """Whether query ``i`` attends key ``j`` (broadcast) in a layer of
    ``layer_type``."""
    if layer_type == "full_attention" or fault == "window_ignored":
        return j <= i
    if fault == "window_1025":
        window = window + 1
    return (j <= i) & (i - j < window)


def _attention_row(lp, h, kind: str, z, ein, fault):
    """Attention output (before ``Wo``) of one row: ``h [T, d]``."""
    t_all, hd, kv, heads = h.shape[0], z["hd"], z["kv"], z["heads"]
    q = _rms_norm(ein("td,dhk->thk", h, lp["wq"]), lp["q_norm"], z["eps"])
    k = _rms_norm(ein("td,dhk->thk", h, lp["wk"]), lp["k_norm"], z["eps"])
    v = ein("td,dhk->thk", h, lp["wv"])
    table = "full_attention" if fault == "rope_swapped" else kind
    freq, factor = inv_freq(z["rope"][table], hd, fault)
    angles = jnp.arange(t_all, dtype=jnp.float32)[:, None] * freq
    q, k = _rotate(q, angles, factor), _rotate(k, angles, factor)
    # query head i with key/value head i // group: [T, kv, group, hd]
    q = q.reshape(t_all, kv, heads // kv, hd)
    block = min(_Q_BLOCK, t_all)

    @jax.checkpoint
    def one_block(first):
        keep = allowed(first + jnp.arange(block)[:, None],
                       jnp.arange(t_all)[None, :], kind, z["window"], fault)
        s = ein("qhgk,shk->hgqs",
                jax.lax.dynamic_slice_in_dim(q, first, block, 0),
                k) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), -1)
        return ein("hgqs,shk->qhgk", p, v)

    out = jax.lax.map(one_block, jnp.arange(0, t_all, block))
    return out.reshape(t_all, heads, hd)


def _experts_row(lp, g, member, z, ein, fault):
    """All the experts' result for ``g [T, d]``. ``member`` (a traced
    int) is read by ``own_rows_only`` alone: the block of experts the
    row's tokens reach when nothing is exchanged."""
    n, groups = z["experts"], z["groups"]
    p = jax.nn.softmax(ein("td,de->te", g, lp["router"]), -1)
    top_p, top_e = jax.lax.top_k(p, z["per_tok"])
    if fault != "no_renorm":
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    gates = jnp.sum(jax.nn.one_hot(top_e, n) * top_p[..., None], 1)
    if fault == "own_rows_only":
        gates = gates * (jnp.arange(n) // (n // groups) == member)
    weights = [lp[name] for name in _EXPERT_LEAVES]
    if fault == "experts_psummed":
        # the value as it is, the gradient ``groups`` times over
        weights = [w * groups - jax.lax.stop_gradient(w * (groups - 1))
                   for w in weights]

    @jax.checkpoint  # the backward pass recomputes the experts' hidden
    def gated(g, gate, w_gate, w_up, w_down):
        """``groups`` experts at once, one of each block of ``n /
        groups``: ``gate [T, groups]``, weights ``[groups, ...]``."""
        hidden = jax.nn.silu(ein("td,cdf->ctf", g, w_gate)) \
            * ein("td,cdf->ctf", g, w_up)
        return jnp.sum(gate.T[..., None] * ein("ctf,cfd->ctd", hidden,
                                               w_down), 0)

    def some_experts(acc, ew):
        return acc + gated(g, *ew), None

    # expert e = c * (n / groups) + i is the i-th of block c
    by_block = lambda a: jnp.moveaxis(
        a.reshape(groups, n // groups, *a.shape[1:]), 1, 0)
    out, _ = jax.lax.scan(
        some_experts, jnp.zeros_like(g),
        (jnp.moveaxis(gates.reshape(-1, groups, n // groups), 2, 0),
         *map(by_block, weights)))
    return out


def forward(variables: dict, ids, cfg: dict, precision: str = "f32",
            members=None):
    """Logits ``[rows, T, vocab]`` of integer ``ids [rows, T]``;
    ``members [rows]`` (the member of the deployment that holds each
    row) is read by the fault ``own_rows_only`` alone."""
    p, z = variables["params"], _sizes(cfg)
    fault = cfg.get("fault")
    ein = lambda eq, a, b: _ops.einsum(eq, a, b, precision)
    ids = ids.astype(jnp.int32)
    if members is None:
        members = jnp.zeros((ids.shape[0],), jnp.int32)

    def layer(kind):
        @jax.checkpoint  # a layer's inside is recomputed, so that it fits
        def run(lp, x, member):
            h = _rms_norm(x, lp["attn_norm"], z["eps"])
            o = _attention_row(lp["attn"], h, kind, z, ein, fault)
            x = x + ein("thk,hkd->td", o, lp["attn"]["wo"])
            g = _rms_norm(x, lp["moe_norm"], z["eps"])
            return x + _experts_row(lp["moe"], g, member, z, ein, fault)

        return run

    def one_row(row):
        ids_row, member = row
        x = p["embed"][ids_row]
        for i, kind in enumerate(z["kinds"]):
            x = layer(kind)(p[f"layer_{i}"], x, member)
        x = _rms_norm(x, p["final_norm"], z["eps"])
        return ein("td,dv->tv", x, p["head"])

    return jax.lax.map(one_row, (ids, jnp.asarray(members, jnp.int32)))


def loss_sum(variables: dict, x, y, w, cfg: dict, precision: str = "f32",
             members=None):
    """Weighted sum over the rows of each row's mean next-token cross
    entropy; ``y [rows, T]`` holds the labels."""
    logits = forward(variables, x, cfg, precision, members)
    rows, t_all, vocab = logits.shape
    per_token = _ops.cross_entropy(logits.reshape(rows * t_all, vocab),
                                   y.reshape(rows * t_all))
    return jnp.sum(jnp.mean(per_token.reshape(rows, t_all), -1) * w)

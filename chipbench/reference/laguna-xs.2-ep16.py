"""Plain reference of the ``laguna-xs.2-ep16`` configuration: Laguna-XS.2
(window and full-causal layers mixed, grouped query heads whose number
differs by kind of layer, a gated attention output, a leading dense
layer, then sigmoid-routed experts beside a shared one) in
straightforward ``jax.numpy``, float32, no kernels, one chip's share of
the experts and of the vocabulary.

Layer ``l`` of a row's ``T`` tokens ``x``, of kind ``layer_types[l]``
(``full_attention`` or ``sliding_attention``) with ``H(l) =
num_attention_heads_per_layer[l]`` query heads, 8 key/value heads of
128; what the source does not say is listed in the configuration file
under ``assumed``:

- ``h = RMSNorm(x)``; ``q = h Wq`` (``H(l)`` heads), ``k = h Wk``, ``v =
  h Wv``, no biases; RMSNorm over the 128 of each q and k head.
- Rotary by halves on the first ``r`` dims of q and k, the rest passed
  through, by the kind's ``rope_parameters``: sliding ``r`` = 128,
  ``inv_freq_i = 1e4^(-2i / 128)``; full ``r`` = 64
  (``partial_rotary_factor`` 0.5) and YaRN over ``D`` = 64 dims: ``e_i =
  5e5^(-2i / D)``, ``c(b) = D ln(4096 / (2 pi b)) / (2 ln 5e5)``, ``low =
  max(floor(c(beta_fast)), 0)``, ``high = min(ceil(c(beta_slow)), D -
  1)``, ``ramp_i = clip((i - low) / (high - low), 0, 1)``, ``inv_freq_i =
  (e_i / factor) ramp_i + e_i (1 - ramp_i)``; ``cos`` and ``sin`` times
  ``attention_factor``.
- Query ``i`` attends key ``j`` iff ``j <= i`` (full), and ``i - j <
  sliding_window`` too (sliding: 512 keys with its own); softmax of ``q_i
  . k_j / sqrt(128)``, head ``i`` with kv head ``i // (H(l) / 8)``, dense
  scores under the mask, a block of queries at a time so that it fits.
- ``gating``: ``o_{t,i} <- sigmoid(h_t Wg)_i o_{t,i}``, one gate a head
  a token; ``x = x + concat(o) Wo``; ``g = RMSNorm(x)``.
- ``mlp_layer_types[l] == "dense"``: ``x = x + Wd (silu(Wg g) * Wu g)``,
  width ``intermediate_size``.
- ``"sparse"``: ``s = sigmoid(g Wr)`` over all 256 experts; the 8
  largest (ties to the lower index); gates ``2.5 s_e / sum_chosen s``;
  every held expert (``experts_held``) runs on every token and is
  weighted by its gate, 0 where the token did not choose it; what
  experts held elsewhere would add is left out; the shared expert (the
  same SwiGLU, width ``shared_expert_intermediate_size``) is added for
  every token, ungated.

Then RMSNorm and an untied head over the configuration's slice of the
vocabulary; the loss is a row's mean next-token cross entropy. It
imports nothing of the program; the tree of weights has the names the
program's module gives its own.

``cfg["fault"]`` plants a fault for the job's ``control``:
``window_ignored`` (sliding layers attend every causal key),
``window_513`` (one key too many), ``rope_swapped`` (sliding layers
turned by the full layers' table), ``no_yarn`` (full layers: plain
rotary of theta 5e5 on their 64 dims, no attention factor),
``no_attn_gate``, ``no_shared_expert``, ``no_routed_scale`` (2.5 left
out), ``softmax_scores`` (softmax over the 256 in sigmoid's place),
``shifted_share`` (the layer told it holds the next block of experts),
``no_renorm`` (gates not renormalised).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import _ops

_Q_BLOCK = 128   # queries a block of dense scores: [heads, 128, T]


def _sizes(cfg: dict) -> dict:
    return dict(
        d=cfg["hidden_size"], layers=cfg["num_hidden_layers"],
        heads=list(cfg["num_attention_heads_per_layer"]),
        kinds=list(cfg["layer_types"]), mlps=list(cfg["mlp_layer_types"]),
        kv=cfg["num_key_value_heads"], hd=cfg["head_dim"],
        vocab=cfg["vocab_size"], dense=cfg["intermediate_size"],
        routed=cfg["num_routed_experts"], per_tok=cfg["num_experts_per_tok"],
        width=cfg["moe_intermediate_size"],
        shared=cfg["shared_expert_intermediate_size"],
        scale=float(cfg["moe_routed_scaling_factor"]),
        held=list(cfg["experts_held"]), eps=cfg["rms_norm_eps"],
        window=cfg["sliding_window"], rope=cfg["rope_parameters"],
        gating=bool(cfg["gating"]), embed_std=cfg["embedding_init_std"])


def init(key, cfg: dict) -> dict:
    """``{"params": tree}`` from one key: N(0, 0.02) matrices, N(0,
    ``embedding_init_std``) embedding rows, unit norm gains."""
    z = _sizes(cfg)
    d, hd, n_held = z["d"], z["hd"], len(z["held"])
    draws = _ops.Draws()
    ones = lambda n: jnp.ones((n,), jnp.float32)
    swiglu = lambda width, lead=(): {
        "w_gate": draws.normal((*lead, d, width)),
        "w_up": draws.normal((*lead, d, width)),
        "w_down": draws.normal((*lead, width, d))}
    tree = {"embed": draws.normal((z["vocab"], d)), "final_norm": ones(d),
            "head": draws.normal((d, z["vocab"]))}
    for i in range(z["layers"]):
        heads = z["heads"][i]
        attn = {"wq": draws.normal((d, heads, hd)),
                "wk": draws.normal((d, z["kv"], hd)),
                "wv": draws.normal((d, z["kv"], hd)),
                "wo": draws.normal((heads, hd, d)),
                "q_norm": ones(hd), "k_norm": ones(hd)}
        if z["gating"]:
            attn["wg"] = draws.normal((d, heads))
        layer = {"attn_norm": ones(d), "attn": attn}
        if z["mlps"][i] == "dense":
            layer.update(mlp_norm=ones(d), mlp=swiglu(z["dense"]))
        else:
            layer.update(
                moe_norm=ones(d),
                moe={"router": draws.normal((d, z["routed"])),
                     **swiglu(z["width"], (n_held,))},
                shared=swiglu(z["shared"]))
        tree[f"layer_{i}"] = layer
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
    """``(float32 [r / 2] frequencies, factor on cos and sin)`` of one
    kind's ``rope_parameters``, ``r`` the dims it turns."""
    dims = int(head_dim * rope.get("partial_rotary_factor", 1))
    theta = float(rope["rope_theta"])
    if rope["rope_type"] == "default" or fault == "no_yarn":
        return theta ** (-jnp.arange(dims // 2, dtype=jnp.float32)
                         / (dims // 2)), 1.0
    # rope_type yarn, in float64 on the host
    i = np.arange(dims // 2, dtype=np.float64)
    e = theta ** (-2.0 * i / dims)
    orig = float(rope["original_max_position_embeddings"])
    c = lambda b: dims * np.log(orig / (2 * np.pi * b)) / (2 * np.log(theta))
    low = max(np.floor(c(float(rope["beta_fast"]))), 0.0)
    high = min(np.ceil(c(float(rope["beta_slow"]))), dims - 1.0)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    blended = e / float(rope["factor"]) * ramp + e * (1.0 - ramp)
    return jnp.asarray(blended, jnp.float32), float(rope["attention_factor"])


def _rotate(x, angles, factor):
    """Rotation by halves of the first ``2 * angles.shape[-1]`` dims of
    ``x [T, heads, n]`` by ``angles [T, r / 2]``; the rest passes."""
    half = angles.shape[-1]
    cos = factor * jnp.cos(angles)[:, None]
    sin = factor * jnp.sin(angles)[:, None]
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1)


def allowed(i, j, layer_type: str, window: int, fault=None):
    """Whether query ``i`` attends key ``j`` (broadcast) in a layer of
    ``layer_type``."""
    if layer_type == "full_attention" or fault == "window_ignored":
        return j <= i
    if fault == "window_513":
        window = window + 1
    return (j <= i) & (i - j < window)


def _attention_row(lp, h, kind: str, heads: int, z, ein, fault):
    """Attention output (gated, before ``Wo``) of one row: ``h [T, d]``."""
    t_all, hd, kv = h.shape[0], z["hd"], z["kv"]
    q = _rms_norm(ein("td,dhk->thk", h, lp["wq"]), lp["q_norm"], z["eps"])
    k = _rms_norm(ein("td,dhk->thk", h, lp["wk"]), lp["k_norm"], z["eps"])
    v = ein("td,dhk->thk", h, lp["wv"])
    table = ("full_attention" if fault == "rope_swapped" else kind)
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
    out = out.reshape(t_all, heads, hd)
    if z["gating"] and fault != "no_attn_gate":
        out = out * jax.nn.sigmoid(ein("td,dh->th", h, lp["wg"]))[..., None]
    return out


def _swiglu(lp, g, ein):
    hidden = jax.nn.silu(ein("td,df->tf", g, lp["w_gate"])) \
        * ein("td,df->tf", g, lp["w_up"])
    return ein("tf,fd->td", hidden, lp["w_down"])


def _experts_row(lp, g, z, ein, fault):
    """This chip's part of the routed experts' result for ``g [T, d]``."""
    held = z["held"]
    if fault == "shifted_share":
        held = [(e + len(held)) % z["routed"] for e in held]
    logits = ein("td,de->te", g, lp["router"])
    s = (jax.nn.softmax(logits, -1) if fault == "softmax_scores"
         else jax.nn.sigmoid(logits))
    top_s, top_e = jax.lax.top_k(s, z["per_tok"])
    if fault != "no_renorm":
        top_s = top_s / jnp.sum(top_s, -1, keepdims=True)
    if fault != "no_routed_scale":
        top_s = z["scale"] * top_s
    gates = jnp.sum(jax.nn.one_hot(top_e, z["routed"]) * top_s[..., None], 1)

    @jax.checkpoint  # the backward pass recomputes an expert's hidden
    def gated(g, gate, w_gate, w_up, w_down):
        return gate[:, None] * _swiglu(
            {"w_gate": w_gate, "w_up": w_up, "w_down": w_down}, g, ein)

    def one_expert(acc, ew):
        return acc + gated(g, *ew), None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(g),
        (gates[:, jnp.asarray(held)].T, lp["w_gate"], lp["w_up"],
         lp["w_down"]))
    return out


def forward(variables: dict, ids, cfg: dict, precision: str = "f32"):
    """Logits ``[rows, T, vocab]`` of integer ``ids [rows, T]``."""
    p, z = variables["params"], _sizes(cfg)
    fault = cfg.get("fault")
    ein = lambda eq, a, b: _ops.einsum(eq, a, b, precision)
    ids = ids.astype(jnp.int32)

    def layer(i):
        kind, heads, mlp = z["kinds"][i], z["heads"][i], z["mlps"][i]

        @jax.checkpoint  # a layer's inside is recomputed, so that it fits
        def run(lp, x):
            h = _rms_norm(x, lp["attn_norm"], z["eps"])
            o = _attention_row(lp["attn"], h, kind, heads, z, ein, fault)
            x = x + ein("thk,hkd->td", o, lp["attn"]["wo"])
            if mlp == "dense":
                g = _rms_norm(x, lp["mlp_norm"], z["eps"])
                return x + _swiglu(lp["mlp"], g, ein)
            g = _rms_norm(x, lp["moe_norm"], z["eps"])
            x = x + _experts_row(lp["moe"], g, z, ein, fault)
            if fault != "no_shared_expert":
                x = x + _swiglu(lp["shared"], g, ein)
            return x

        return run

    def one_row(ids_row):
        x = p["embed"][ids_row]
        for i in range(z["layers"]):
            x = layer(i)(p[f"layer_{i}"], x)
        x = _rms_norm(x, p["final_norm"], z["eps"])
        return ein("td,dv->tv", x, p["head"])

    return jax.lax.map(one_row, ids)


def loss_sum(variables: dict, x, y, w, cfg: dict, precision: str = "f32"):
    """Weighted sum over the rows of each row's mean next-token cross
    entropy; ``y [rows, T]`` holds the labels."""
    logits = forward(variables, x, cfg, precision)
    rows, t_all, vocab = logits.shape
    per_token = _ops.cross_entropy(logits.reshape(rows * t_all, vocab),
                                   y.reshape(rows * t_all))
    return jnp.sum(jnp.mean(per_token.reshape(rows, t_all), -1) * w)

"""Plain reference of the ``lfm2-8b-a1b-ep4`` configuration: LFM2-8B-A1B
(gated short convolutions three to one with grouped-query attention at
64-wide heads, a tied head, 4 of 32 sigmoid-routed experts under an
expert bias) in straightforward ``jax.numpy``, float32, no kernels, one
chip's share of the experts and of the vocabulary.

Every layer ``l`` of kind ``layer_types[l]``, for the tokens ``x [T,
d]`` of a row, ``eps`` = ``norm_eps``, no bias anywhere: ``h =
RMSNorm(x)``; ``x = x + Mixer(h)``; ``g = RMSNorm(x)``; ``x = x +
MLP(g)``. What the source's config names without a formula is listed in
the configuration file under ``assumed``.

``conv`` mixer: ``[B ; C ; u] = h W_in`` (three equal column blocks in
that order); ``s = B * u``; a causal depthwise convolution over time of
``conv_L_cache`` taps a channel, ``c[t] = sum_i w[i] s[t - (taps - 1) +
i]``, ``s[t < 0] = 0``, as ``taps`` shifted multiply-adds; NO
activation; ``y = C * c``; ``Mixer = y W_out``.

``full_attention`` mixer: ``q = h W_q`` (``num_attention_heads`` heads
of ``hidden / heads`` = 64), ``k = h W_k``, ``v = h W_v``
(``num_key_value_heads``); RMSNorm (gain ``[64]``) over each head of
``q`` and ``k``; rotary by halves on all 64 dims, ``inv_freq_i =
theta^(-2i / 64)``, by the token's index; causal softmax of ``q_i . k_j
/ sqrt(64)``, query head ``i`` reading key/value head ``i // (heads /
kv)``, dense scores under the mask a block of queries at a time;
``Mixer = o W_o``; no gate.

MLP: layers ``l < num_dense_layers`` SwiGLU at ``intermediate_size``;
the others ``s = sigmoid(g W_r)`` over ALL ``num_routed_experts``; the
``num_experts_per_tok`` of largest ``s + b`` (``b`` the expert bias, a
leaf no gradient reaches; ties to the lower index); gates ``s_e /
(sum_chosen s + 1e-6)`` from ``s`` WITHOUT ``b``, times
``routed_scaling_factor``; every held expert (``experts_held``) runs on
every token and is weighted by its gate, 0 where the token did not
choose it; what experts held elsewhere would add is left out. No shared
expert.

Then RMSNorm and the head ``logits = n E^T`` with ``E`` the embedding
(``tie_word_embeddings``: ONE leaf, ``embed``) over the configuration's
slice of the vocabulary; the loss is a row's mean next-token cross
entropy. It imports nothing of the program; the tree of weights has the
names the program's module gives its own.

``cfg["fault"]`` plants a fault for the job's ``control``: ``no_conv``
(taps 0, 0, 1: ``c = s``), ``conv_not_causal`` (the taps reach forward:
``s[t + 2 - i]``), ``conv_reach_4`` (a fourth tap, ``w[0]`` again on
``s[t - 3]``), ``conv_silu`` (SiLU after the taps, as the sibling's
convolution has it), ``no_in_gate`` (``s = u``), ``no_out_gate`` (``y =
c``), ``scale_128`` (``1 / sqrt(128)``), ``rope_on_half_head`` (the
first 32 dims turned, 16 pairs), ``no_qk_norm``, ``untied_head`` (the
head a constant copy of the embedding: no gradient reaches the
embedding through it), ``no_selection_bias`` (chosen by ``s`` alone),
``bias_in_gates`` (gates from ``s + b``), ``softmax_scores``,
``no_renorm``, ``shifted_share`` (the layer told it holds the next block
of experts).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference import _ops

_Q_BLOCK = 128   # queries a block of dense scores: [heads, 128, T]
_GATE_EPS = 1e-6   # in the chosen scores' sum


def _sizes(cfg: dict) -> dict:
    heads = cfg["num_attention_heads"]
    return dict(
        d=cfg["hidden_size"], types=list(cfg["layer_types"]),
        n_dense=cfg["num_dense_layers"], dense=cfg["intermediate_size"],
        heads=heads, kv=cfg["num_key_value_heads"],
        hd=cfg["hidden_size"] // heads, theta=float(cfg["rope_theta"]),
        taps=cfg["conv_L_cache"], vocab=cfg["vocab_size"],
        routed=cfg["num_routed_experts"], per_tok=cfg["num_experts_per_tok"],
        width=cfg["moe_intermediate_size"],
        scale=float(cfg["routed_scaling_factor"]),
        held=list(cfg["experts_held"]), eps=cfg["norm_eps"],
        embed_std=cfg["embedding_init_std"], conv_std=cfg["conv_init_std"],
        bias_std=cfg["selection_bias_std"])


def init(key, cfg: dict) -> dict:
    """``{"params": tree}`` from one key: N(0, 0.02) matrices, N(0,
    ``conv_init_std``) taps, N(0, ``embedding_init_std``) embedding rows
    (the head is their transpose: no leaf of its own), N(0,
    ``selection_bias_std``) expert biases, unit norm gains."""
    z = _sizes(cfg)
    d, hd, n_held = z["d"], z["hd"], len(z["held"])
    draws = _ops.Draws()
    ones = lambda n: jnp.ones((n,), jnp.float32)
    swiglu = lambda width, lead=(): {
        "w_gate": draws.normal((*lead, d, width)),
        "w_up": draws.normal((*lead, d, width)),
        "w_down": draws.normal((*lead, width, d))}
    tree = {"embed": draws.normal((z["vocab"], d)), "final_norm": ones(d)}
    for i, kind in enumerate(z["types"]):
        if kind == "conv":
            attn = {"w_in": draws.normal((d, 3 * d)),
                    "conv": draws.normal((z["taps"], d)),
                    "wo": draws.normal((d, d))}
        else:
            attn = {"wq": draws.normal((d, z["heads"], hd)),
                    "wk": draws.normal((d, z["kv"], hd)),
                    "wv": draws.normal((d, z["kv"], hd)),
                    "wo": draws.normal((z["heads"], hd, d)),
                    "q_norm": ones(hd), "k_norm": ones(hd)}
        layer = {"attn_norm": ones(d), "attn": attn}
        if i < z["n_dense"]:
            layer.update(mlp_norm=ones(d), mlp=swiglu(z["dense"]))
        else:
            layer.update(moe_norm=ones(d), moe={
                "router": draws.normal((d, z["routed"])),
                "selection_bias": draws.normal((z["routed"],)),
                **swiglu(z["width"], (n_held,))})
        tree[f"layer_{i}"] = layer
    drawn = draws.cut(key)
    params = jax.tree.map(
        lambda leaf: 0.02 * drawn[leaf] if isinstance(leaf, int) else leaf,
        tree)
    params["embed"] = params["embed"] * (z["embed_std"] / 0.02)
    for i, kind in enumerate(z["types"]):
        layer = params[f"layer_{i}"]
        if kind == "conv":
            layer["attn"]["conv"] = layer["attn"]["conv"] * (
                z["conv_std"] / 0.02)
        if "moe" in layer:
            layer["moe"]["selection_bias"] = layer["moe"][
                "selection_bias"] * (z["bias_std"] / 0.02)
    return {"params": params}


def _rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * gain


def _rotate(x, angles):
    """Rotation by halves of the first ``2 * angles.shape[-1]`` dims of
    ``x [T, heads, n]`` by ``angles [T, r / 2]``; the rest passes."""
    half = angles.shape[-1]
    cos, sin = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1)


def allowed(i, j, *_unused):
    """Whether query ``i`` attends key ``j`` (broadcast) in a
    ``full_attention`` layer: every causal key."""
    return j <= i


def convolved(s, w, fault=None):
    """The causal depthwise convolution of ``s [T, c]`` by the taps ``w
    [taps, c]``: tap ``i`` reads ``s[t - (taps - 1) + i]``, zeros before
    the row."""
    t_all, taps = s.shape[0], w.shape[0]
    if fault == "no_conv":
        return s
    if fault == "conv_not_causal":   # the taps reach forward in time
        padded = jnp.pad(s, ((0, taps - 1), (0, 0)))
        return sum(w[i] * padded[taps - 1 - i:taps - 1 - i + t_all]
                   for i in range(taps))
    reach = taps + (fault == "conv_reach_4")
    padded = jnp.pad(s, ((reach - 1, 0), (0, 0)))
    out = sum(w[i] * padded[reach - taps + i:reach - taps + i + t_all]
              for i in range(taps))
    if fault == "conv_reach_4":      # s[t - 3] under the first tap again
        out = out + w[0] * padded[:t_all]
    return jax.nn.silu(out) if fault == "conv_silu" else out


def _conv_row(lp, h, z, ein, fault):
    """The gated short convolution's output (before ``W_out``) of one
    row ``h [T, d]``: ``[T, d]``."""
    b, c, u = jnp.split(ein("td,df->tf", h, lp["w_in"]), 3, -1)
    s = u if fault == "no_in_gate" else b * u
    conv = convolved(s, lp["conv"], fault)
    return conv if fault == "no_out_gate" else c * conv


def _attention_row(lp, h, z, ein, fault):
    """The attention mixer's output (before ``W_o``) of one row ``h [T,
    d]``: ``[T, heads, 64]``."""
    t_all, hd, kv, heads = h.shape[0], z["hd"], z["kv"], z["heads"]
    q = ein("td,dhk->thk", h, lp["wq"])
    k = ein("td,dhk->thk", h, lp["wk"])
    v = ein("td,dhk->thk", h, lp["wv"])
    if fault != "no_qk_norm":
        q = _rms_norm(q, lp["q_norm"], z["eps"])
        k = _rms_norm(k, lp["k_norm"], z["eps"])
    dims = hd // 2 if fault == "rope_on_half_head" else hd
    freq = z["theta"] ** (-jnp.arange(dims // 2, dtype=jnp.float32)
                          / (dims // 2))
    angles = jnp.arange(t_all, dtype=jnp.float32)[:, None] * freq
    q, k = _rotate(q, angles), _rotate(k, angles)
    q = q.reshape(t_all, kv, heads // kv, hd)
    scale = (128 if fault == "scale_128" else hd) ** -0.5
    block = min(_Q_BLOCK, t_all)

    @jax.checkpoint
    def one_block(first):
        keep = allowed(first + jnp.arange(block)[:, None],
                       jnp.arange(t_all)[None, :])
        s = ein("qhgk,shk->hgqs",
                jax.lax.dynamic_slice_in_dim(q, first, block, 0), k) * scale
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), -1)
        return ein("hgqs,shk->qhgk", p, v)

    out = jax.lax.map(one_block, jnp.arange(0, t_all, block))
    return out.reshape(t_all, heads, hd)


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
    bias = jax.lax.stop_gradient(lp["selection_bias"])
    chosen_by = jax.lax.stop_gradient(s)
    if fault != "no_selection_bias":
        chosen_by = chosen_by + bias
    top_e = jax.lax.top_k(chosen_by, z["per_tok"])[1]
    top_s = jnp.take_along_axis(s + bias if fault == "bias_in_gates" else s,
                                top_e, -1)
    if fault != "no_renorm":
        top_s = top_s / (jnp.sum(top_s, -1, keepdims=True) + _GATE_EPS)
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


def layer_row(lp, x, kind: str, z, ein, fault=None):
    """One layer of ``kind`` (``conv`` / ``full_attention``) on one row
    ``x [T, d]``; its MLP is dense where the layer holds ``mlp``, else
    this chip's part of the routed experts."""
    h = _rms_norm(x, lp["attn_norm"], z["eps"])
    if kind == "conv":
        x = x + ein("tf,fd->td", _conv_row(lp["attn"], h, z, ein, fault),
                    lp["attn"]["wo"])
    else:
        x = x + ein("thk,hkd->td", _attention_row(lp["attn"], h, z, ein,
                                                  fault), lp["attn"]["wo"])
    if "mlp" in lp:
        g = _rms_norm(x, lp["mlp_norm"], z["eps"])
        return x + _swiglu(lp["mlp"], g, ein)
    g = _rms_norm(x, lp["moe_norm"], z["eps"])
    return x + _experts_row(lp["moe"], g, z, ein, fault)


def forward(variables: dict, ids, cfg: dict, precision: str = "f32"):
    """Logits ``[rows, T, vocab]`` of integer ``ids [rows, T]``."""
    p, z = variables["params"], _sizes(cfg)
    fault = cfg.get("fault")
    ein = lambda eq, a, b: _ops.einsum(eq, a, b, precision)
    ids = ids.astype(jnp.int32)
    head = p["embed"]   # tied: the one leaf a second time
    if fault == "untied_head":
        head = jax.lax.stop_gradient(head)

    def one_row(ids_row):
        x = p["embed"][ids_row]
        for i, kind in enumerate(z["types"]):
            # a layer's inside is recomputed, so that it fits
            x = jax.checkpoint(lambda lp, x, kind=kind: layer_row(
                lp, x, kind, z, ein, fault))(p[f"layer_{i}"], x)
        x = _rms_norm(x, p["final_norm"], z["eps"])
        return ein("td,vd->tv", x, head)

    return jax.lax.map(one_row, ids)


def loss_sum(variables: dict, x, y, w, cfg: dict, precision: str = "f32"):
    """Weighted sum over the rows of each row's mean next-token cross
    entropy; ``y [rows, T]`` holds the labels."""
    logits = forward(variables, x, cfg, precision)
    rows, t_all, vocab = logits.shape
    per_token = _ops.cross_entropy(logits.reshape(rows * t_all, vocab),
                                   y.reshape(rows * t_all))
    return jnp.sum(jnp.mean(per_token.reshape(rows, t_all), -1) * w)

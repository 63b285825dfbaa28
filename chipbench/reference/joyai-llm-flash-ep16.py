"""Plain reference of the ``joyai-llm-flash-ep16`` configuration:
JoyAI-LLM-Flash (DeepSeek-V3's layer: multi-head latent attention, a
leading dense layer, then sigmoid-routed experts chosen under a selection
bias beside a shared one, and one multi-token prediction module that
shares the embedding and the head) in straightforward ``jax.numpy``,
float32, no kernels, one chip's share of the experts and of the
vocabulary.

Layer ``l`` of a row's ``T`` tokens ``x``, ``H`` heads; what the source
does not say is listed in the configuration file under ``assumed``:

1. ``h = RMSNorm(x)``.
2. ``c_q = RMSNorm(h W_dq)`` (rank ``q_lora_rank``); ``q = c_q W_uq``;
   each head ``q_i = [q_i^nope (qk_nope_head_dim) ; q_i^rope
   (qk_rope_head_dim)]``.
3. ``[c_kv (kv_lora_rank) ; k^rope] = h W_dkv``; ``c_kv <- RMSNorm(c_kv)``;
   ``[k_i^nope ; v_i] = c_kv W_ukv`` for each head. ``k^rope`` is ONE key
   a token, shared by all heads, not normed.
4. Rotary on ``q_i^rope`` and ``k^rope`` only: pairs ``theta^(-2j /
   qk_rope_head_dim)`` by the token's index, ``rope_interleave``: pair
   ``j`` is dims ``(2j, 2j + 1)``.
5. ``k_i = [k_i^nope ; k^rope]``; softmax over ``j <= i`` of ``q_i . k_j /
   sqrt(192)``; ``o_i = sum_j p_ij v_j``; ``x = x + concat_i(o_i) W_o``.
   Dense scores under the mask, a block of queries at a time so that it
   fits.
6. ``g = RMSNorm(x)``. Layer 0 (``first_k_dense_replace``): ``x = x +
   SwiGLU(g)`` at ``intermediate_size``. Others: ``s = sigmoid(g W_r)``
   over all 256 experts; the 8 of largest ``s + b`` (``b`` the selection
   bias, ties to the lower index); gates ``2.5 s_e / (sum_chosen s +
   1e-20)`` from ``s`` without ``b``; every held expert runs on every
   token and is weighted by its gate, 0 where the token did not choose
   it; the shared expert is added for every token, ungated.
7. ``logits = RMSNorm(x) W_head``.
8. Multi-token prediction, depth 1: with ``x^L`` the stream after the
   last layer and ``E`` the embedding, for ``i < T - 1``: ``u_i =
   [RMSNorm_e(E[t_{i+1}]) ; RMSNorm_h(x^L_i)] W_eh``, one layer of the
   kind of 1-6 with its own weights over positions ``0 .. T - 2``,
   ``logits^mtp_i = RMSNorm_s(.) W_head`` with the SAME head.
9. A row's loss: ``mean_i CE(logits_i, y_i) + lambda / (T - 1) sum_{i <
   T - 1} CE(logits^mtp_i, y_{i+1})``.

It imports nothing of the program; the tree of weights has the names the
program's module gives its own.

``cfg["fault"]`` plants a fault for the job's ``control``:
``no_mtp_loss`` (lambda 0), ``mtp_unshifted`` (the module held to ``y_i``),
``mtp_own_head`` (its head a copy: the shared head gets no gradient from
it), ``scale_128`` (``1 / sqrt(128)``), ``rope_on_whole_head`` (all 192
dims of q and k turned, 96 pairs), ``rope_by_halves`` (pair ``j`` is dims
``(j, j + 32)``), ``k_rope_normed``, ``no_latent_norm``,
``no_selection_bias`` (chosen by ``s`` alone), ``bias_in_gates`` (gates
from ``s + b``), ``no_shared_expert``, ``no_routed_scale``,
``softmax_scores``, ``shifted_share`` (the layer told it holds the next
block of experts), ``no_renorm``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference import _ops

_Q_BLOCK = 128   # queries a block of dense scores: [heads, 128, T]


def _sizes(cfg: dict) -> dict:
    return dict(
        d=cfg["hidden_size"], layers=cfg["num_hidden_layers"],
        heads=cfg["num_attention_heads"], rq=cfg["q_lora_rank"],
        rkv=cfg["kv_lora_rank"], nope=cfg["qk_nope_head_dim"],
        rope=cfg["qk_rope_head_dim"], dv=cfg["v_head_dim"],
        theta=float(cfg["rope_theta"]), vocab=cfg["vocab_size"],
        dense=cfg["intermediate_size"],
        first_dense=cfg["first_k_dense_replace"],
        routed=cfg["num_routed_experts"], per_tok=cfg["num_experts_per_tok"],
        width=cfg["moe_intermediate_size"],
        shared=cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
        scale=float(cfg["routed_scaling_factor"]),
        held=list(cfg["experts_held"]), eps=cfg["rms_norm_eps"],
        mtp=cfg["num_nextn_predict_layers"],
        mtp_weight=float(cfg["mtp_loss_weight"]),
        embed_std=cfg["embedding_init_std"],
        bias_std=cfg["selection_bias_std"])


def init(key, cfg: dict) -> dict:
    """``{"params": tree}`` from one key: N(0, 0.02) matrices, N(0,
    ``embedding_init_std``) embedding rows, N(0, ``selection_bias_std``)
    selection biases, unit norm gains."""
    z = _sizes(cfg)
    d, n_held = z["d"], len(z["held"])
    draws = _ops.Draws()
    ones = lambda n: jnp.ones((n,), jnp.float32)
    swiglu = lambda width, lead=(): {
        "w_gate": draws.normal((*lead, d, width)),
        "w_up": draws.normal((*lead, d, width)),
        "w_down": draws.normal((*lead, width, d))}

    def layer(dense: bool) -> dict:
        attn = {"w_dq": draws.normal((d, z["rq"])), "q_norm": ones(z["rq"]),
                "w_uq": draws.normal((z["rq"], z["heads"],
                                      z["nope"] + z["rope"])),
                "w_dkv": draws.normal((d, z["rkv"] + z["rope"])),
                "kv_norm": ones(z["rkv"]),
                "w_ukv": draws.normal((z["rkv"], z["heads"],
                                       z["nope"] + z["dv"])),
                "wo": draws.normal((z["heads"], z["dv"], d))}
        out = {"attn_norm": ones(d), "attn": attn}
        if dense:
            out.update(mlp_norm=ones(d), mlp=swiglu(z["dense"]))
        else:
            out.update(
                moe_norm=ones(d),
                moe={"router": draws.normal((d, z["routed"])),
                     "selection_bias": draws.normal((z["routed"],)),
                     **swiglu(z["width"], (n_held,))},
                shared=swiglu(z["shared"]))
        return out

    tree = {"embed": draws.normal((z["vocab"], d)), "final_norm": ones(d),
            "head": draws.normal((d, z["vocab"]))}
    for i in range(z["layers"]):
        tree[f"layer_{i}"] = layer(i < z["first_dense"])
    if z["mtp"]:
        tree["mtp"] = {"embed_norm": ones(d), "hidden_norm": ones(d),
                       "proj": draws.normal((2 * d, d)),
                       "layer": layer(False), "final_norm": ones(d)}
    drawn = draws.cut(key)
    stds = {"embed": z["embed_std"], "selection_bias": z["bias_std"]}
    params = jax.tree_util.tree_map_with_path(
        lambda path, leaf: stds.get(path[-1].key, 0.02) * drawn[leaf]
        if isinstance(leaf, int) else leaf, tree)
    return {"params": params}


def _rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * gain


def _rotate(x, theta: float, by_halves: bool = False):
    """Rotary on the whole last axis of ``x [T, ..., n]`` by the token's
    index: ``n / 2`` pairs ``theta^(-2j / n)``, pair ``j`` dims ``(2j, 2j +
    1)``, or (``by_halves``) dims ``(j, j + n / 2)``."""
    t_all, n = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(n // 2, dtype=jnp.float32) / (n // 2))
    angles = jnp.arange(t_all, dtype=jnp.float32)[:, None] * inv
    angles = angles.reshape(t_all, *[1] * (x.ndim - 2), n // 2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if by_halves:
        x1, x2 = x[..., :n // 2], x[..., n // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def allowed(i, j):
    """Whether query ``i`` attends key ``j`` (broadcast)."""
    return j <= i


def _attention_row(lp, h, z, ein, fault):
    """Attention output (before ``W_o``) of one row: ``h [T, d]`` -> ``[T,
    heads, v_head_dim]``."""
    t_all, heads, nope, rope = h.shape[0], z["heads"], z["nope"], z["rope"]
    norm = (lambda x, gain: x) if fault == "no_latent_norm" else (
        lambda x, gain: _rms_norm(x, gain, z["eps"]))
    c_q = norm(ein("td,dr->tr", h, lp["w_dq"]), lp["q_norm"])
    q = ein("tr,rhk->thk", c_q, lp["w_uq"])
    down = ein("td,dr->tr", h, lp["w_dkv"])
    c_kv, k_rope = norm(down[:, :z["rkv"]], lp["kv_norm"]), down[:, z["rkv"]:]
    if fault == "k_rope_normed":
        k_rope = _rms_norm(k_rope, 1.0, z["eps"])
    up = ein("tr,rhk->thk", c_kv, lp["w_ukv"])
    k_nope, v = up[..., :nope], up[..., nope:]
    shared = jnp.broadcast_to(k_rope[:, None], (t_all, heads, rope))
    if fault == "rope_on_whole_head":
        q = _rotate(q, z["theta"])
        k = _rotate(jnp.concatenate([k_nope, shared], -1), z["theta"])
    else:
        halves = fault == "rope_by_halves"
        q = jnp.concatenate(
            [q[..., :nope], _rotate(q[..., nope:], z["theta"], halves)], -1)
        k = jnp.concatenate(
            [k_nope, _rotate(shared, z["theta"], halves)], -1)
    scale = (nope if fault == "scale_128" else nope + rope) ** -0.5
    # any T: the queries padded to whole blocks, the keys as they are
    block = min(_Q_BLOCK, t_all)
    n_blocks = -(-t_all // block)
    q = jnp.pad(q, ((0, n_blocks * block - t_all), (0, 0), (0, 0)))

    @jax.checkpoint
    def one_block(first):
        keep = allowed(first + jnp.arange(block)[:, None],
                       jnp.arange(t_all)[None, :])
        s = ein("qhk,shk->hqs",
                jax.lax.dynamic_slice_in_dim(q, first, block, 0), k) * scale
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), -1)
        return ein("hqs,shk->qhk", p, v)

    out = jax.lax.map(one_block, jnp.arange(n_blocks) * block)
    return out.reshape(n_blocks * block, heads, z["dv"])[:t_all]


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
    biased = jax.lax.stop_gradient(s) + bias
    top_e = jax.lax.top_k(
        jax.lax.stop_gradient(s) if fault == "no_selection_bias" else biased,
        z["per_tok"])[1]
    top_s = jnp.take_along_axis(s + bias if fault == "bias_in_gates" else s,
                                top_e, -1)
    if fault != "no_renorm":
        top_s = top_s / (jnp.sum(top_s, -1, keepdims=True) + 1e-20)
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


def _layer(z, ein, fault, dense: bool):
    @jax.checkpoint  # a layer's inside is recomputed, so that it fits
    def run(lp, x):
        h = _rms_norm(x, lp["attn_norm"], z["eps"])
        o = _attention_row(lp["attn"], h, z, ein, fault)
        x = x + ein("thk,hkd->td", o, lp["attn"]["wo"])
        if dense:
            g = _rms_norm(x, lp["mlp_norm"], z["eps"])
            return x + _swiglu(lp["mlp"], g, ein)
        g = _rms_norm(x, lp["moe_norm"], z["eps"])
        x = x + _experts_row(lp["moe"], g, z, ein, fault)
        if fault != "no_shared_expert":
            x = x + _swiglu(lp["shared"], g, ein)
        return x

    return run


def _hidden(variables: dict, ids, cfg: dict, precision: str):
    """What the head reads: ``(RMSNorm(x^L) [rows, T, d], the module's
    normed output [rows, T - 1, d] or None)`` of integer ``ids [rows,
    T]``."""
    p, z = variables["params"], _sizes(cfg)
    fault = cfg.get("fault")
    ein = lambda eq, a, b: _ops.einsum(eq, a, b, precision)

    def one_row(ids_row):
        emb = p["embed"][ids_row]
        x = emb
        for i in range(z["layers"]):
            x = _layer(z, ein, fault, i < z["first_dense"])(
                p[f"layer_{i}"], x)
        main = _rms_norm(x, p["final_norm"], z["eps"])
        if not z["mtp"]:
            return main, None
        m = p["mtp"]
        u = ein("te,ed->td", jnp.concatenate(
            [_rms_norm(emb[1:], m["embed_norm"], z["eps"]),
             _rms_norm(x[:-1], m["hidden_norm"], z["eps"])], -1), m["proj"])
        u = _layer(z, ein, fault, False)(m["layer"], u)
        return main, _rms_norm(u, m["final_norm"], z["eps"])

    return jax.lax.map(one_row, ids.astype(jnp.int32))


def _heads(variables: dict, cfg: dict):
    """``(the head, the module's)``: the same matrix; under
    ``mtp_own_head`` the module's is a copy the gradient does not join."""
    head = variables["params"]["head"]
    return head, (jax.lax.stop_gradient(head)
                  if cfg.get("fault") == "mtp_own_head" else head)


def forward(variables: dict, ids, cfg: dict, precision: str = "f32"):
    """``(logits [rows, T, vocab], mtp_logits [rows, T - 1, vocab])`` of
    integer ``ids [rows, T]``; the second is None without a module."""
    main, mtp = _hidden(variables, ids, cfg, precision)
    head, mtp_head = _heads(variables, cfg)
    logits = _ops.einsum("rtd,dv->rtv", main, head, precision)
    if mtp is None:
        return logits, None
    return logits, _ops.einsum("rtd,dv->rtv", mtp, mtp_head, precision)


def _token_ce(hidden, head, labels, precision: str):
    """Cross entropy of every token ``[rows, T]`` from what the head
    reads; the logits are recomputed in the backward pass, so that two
    heads' fit beside the weights."""
    @jax.checkpoint
    def ce(hidden, head):
        logits = _ops.einsum("rtd,dv->rtv", hidden, head, precision)
        rows, t_all, vocab = logits.shape
        return _ops.cross_entropy(
            logits.reshape(rows * t_all, vocab),
            labels.reshape(rows * t_all)).reshape(rows, t_all)

    return ce(hidden, head)


def loss_sum(variables: dict, x, y, w, cfg: dict, precision: str = "f32"):
    """Weighted sum over the rows of each row's loss (equation 9); ``y
    [rows, T]`` holds the labels, ``y[i]`` the token after ``x[i]``."""
    main, mtp = _hidden(variables, x, cfg, precision)
    head, mtp_head = _heads(variables, cfg)
    fault = cfg.get("fault")
    y = y.astype(jnp.int32)
    per_row = jnp.mean(_token_ce(main, head, y, precision), -1)
    if mtp is not None and fault != "no_mtp_loss":
        held_to = y[:, :-1] if fault == "mtp_unshifted" else y[:, 1:]
        per_row = per_row + float(cfg["mtp_loss_weight"]) * jnp.mean(
            _token_ce(mtp, mtp_head, held_to, precision), -1)
    return jnp.sum(per_row * w)


def mtp_loss_seen(variables: dict, x, cfg: dict, precision: str = "f32"):
    """``(sum, count)`` of the module's cross entropy over the positions
    whose label the row itself holds: position ``i < T - 2`` against
    ``x[i + 2]`` (``x[i + 1]`` under ``mtp_unshifted``, as a program with
    that fault would count). What the program's counter ``mtp_loss``
    reports."""
    _, mtp = _hidden(variables, x, cfg, precision)
    x = x.astype(jnp.int32)
    held_to = x[:, 1:-1] if cfg.get("fault") == "mtp_unshifted" else x[:, 2:]
    per_token = _token_ce(mtp[:, :-1], _heads(variables, cfg)[1], held_to,
                          precision)
    return jnp.sum(per_token), per_token.size

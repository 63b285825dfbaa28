"""Plain reference of the ``qwen3-next-80b-a3b-ep32`` configuration:
Qwen3-Next-80B-A3B (Gated DeltaNet linear-attention layers three to one
with gated full attention at 256-wide heads, 512 softmax-routed experts
ten a token beside a gated shared expert) in straightforward
``jax.numpy``, float32, no kernels, one chip's share of the experts and
of the vocabulary.

Every layer ``l``, for the tokens ``x [T, d]`` of a row: ``h =
RMSNorm(x)``; ``x = x + Mixer(h)``; ``g = RMSNorm(x)``; ``x = x +
Experts(g) + sigmoid(g w_s) S(g)``. What the source's config names
without a formula is listed in the configuration file under ``assumed``.

Linear-attention mixer, layers with ``(l + 1) % full_attention_interval
!= 0``; ``n_k`` key heads, ``n_v`` value heads of 128, value head ``j``
reading key head ``j // (n_v / n_k)``:

- ``[q ; k ; v ; z] = h W_qkvz``, ``[b ; a] = h W_ba``, no biases, the
  columns in that order, heads in order.
- ``u = [q ; k ; v]`` through a causal depthwise convolution over time
  of ``linear_conv_kernel_dim`` taps, no bias, then SiLU: ``u~[t, c] =
  silu(sum_i w[i, c] u[t - 3 + i, c])``, ``u[t < 0] = 0``: four shifted
  multiply-adds. ``z``, ``a``, ``b`` do not pass it.
- ``beta = sigmoid(b)``; the log-decay ``g = -exp(A_log) softplus(a +
  dt_bias)``; ``q^ = q / sqrt(sum q^2 + 1e-6) / sqrt(128)``, ``k^ = k /
  sqrt(sum k^2 + 1e-6)`` over a key head's dims.
- The gated delta rule TOKEN BY TOKEN (a ``lax.scan`` over ``T``; the
  program computes it in chunks, an independent derivation), a state ``S
  [128, 128]`` a value head from 0: ``S' = exp(g_t) S``; ``u_t = beta_t
  (v_t - S'^T k^_t)``; ``S = S' + k^_t u_t^T``; ``o_t = S^T q^_t``. The
  scan runs in segments of at most 128 tokens with ``jax.checkpoint`` on
  a segment, so that a backward pass keeps the states entering the
  segments and one segment's own (some hundreds of MB at 16,384 tokens),
  not one a token (34 GB). The state's arithmetic is float32 at every
  ``precision``; the lower precisions round ``q^``, ``k^`` and ``v`` on
  their way in, as they round every matrix product's operands.
- ``y_j = RMSNorm(o_j; gain [128]) * silu(z_j)`` a head; ``Mixer =
  concat_j(y_j) W_o``.

Full-attention mixer, layers with ``(l + 1) % 4 == 0``: ``q = h W_q``
and the gate ``h W_q^gate`` (the two halves of the published query
projection, 16 heads of 256 each), ``k = h W_k``, ``v = h W_v`` (2 heads
of 256); RMSNorm over the 256 of each q and k head; rotary by halves on
the first ``256 partial_rotary_factor`` = 64 dims, ``inv_freq_i =
theta^(-2i / 64)``, by the token's index; causal softmax of ``q_i . k_j /
sqrt(256)``, query head ``i`` with key/value head ``i // 8``, dense
scores under the mask a block of 128 queries at a time so that nothing
``[16, T, T]`` exists; ``o <- o * sigmoid(gate)`` element by element;
``Mixer = o W_o``.

Experts: ``p = softmax(g W_r)`` over all 512; the 10 largest (ties to
the lower index); gates renormalised to sum 1; every held expert
(``experts_held``) runs on every token and is weighted by its gate, 0
where the token did not choose it; what experts held elsewhere would add
is left out. ``S`` is one more SwiGLU of width
``shared_expert_intermediate_size``, times ``sigmoid(g w_s)``.

Then RMSNorm and an untied head over the configuration's slice of the
vocabulary; the loss is a row's mean next-token cross entropy. It
imports nothing of the program; the tree of weights has the names the
program's module gives its own.

``cfg["fault"]`` plants a fault for the job's ``control``:
``state_not_carried`` (the state set to 0 at every 64th token),
``no_decay`` (``exp(g) = 1``), ``no_beta`` (``beta = 1``), ``no_conv``
(``u~ = silu(u)``), ``conv_not_causal`` (the taps reach forward: ``u[t +
3 - i]``), ``no_qk_l2norm``, ``no_out_gate_norm`` (``silu(z)`` left
out), ``no_attn_gate``, ``rope_on_whole_head`` (all 256 dims turned,
128 pairs), ``no_shared_gate``, ``softmax_top8`` (8 experts a token for
10), ``no_renorm``, ``shifted_share`` (the layer told it holds the next
block of experts).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.reference import _ops

_Q_BLOCK = 128   # queries a block of dense scores: [heads, 128, T]
_SEGMENT = 128   # tokens a checkpointed segment of the rule's scan
_RESET_EVERY = 64  # the fault state_not_carried: a chunk of the program's


def _sizes(cfg: dict) -> dict:
    return dict(
        d=cfg["hidden_size"], layers=cfg["num_hidden_layers"],
        every=cfg["full_attention_interval"],
        heads=cfg["num_attention_heads"], kv=cfg["num_key_value_heads"],
        hd=cfg["head_dim"], rotated=int(cfg["head_dim"]
                                        * cfg["partial_rotary_factor"]),
        theta=float(cfg["rope_theta"]),
        n_k=cfg["linear_num_key_heads"], n_v=cfg["linear_num_value_heads"],
        d_k=cfg["linear_key_head_dim"], d_v=cfg["linear_value_head_dim"],
        taps=cfg["linear_conv_kernel_dim"], vocab=cfg["vocab_size"],
        routed=cfg["num_routed_experts"], per_tok=cfg["num_experts_per_tok"],
        width=cfg["moe_intermediate_size"],
        shared=cfg["shared_expert_intermediate_size"],
        held=list(cfg["experts_held"]), eps=cfg["rms_norm_eps"],
        embed_std=cfg["embedding_init_std"], conv_std=cfg["conv_init_std"],
        decay=cfg["decay_init"])


def is_full(layer: int, every: int) -> bool:
    return (layer + 1) % every == 0


def decay_init(n_v: int, decay: dict):
    """``(A_log [n_v], dt_bias [n_v])``: value head ``j``'s rate
    ``exp(A_log_j)`` on a geometric ladder from ``rate_min`` to
    ``rate_max`` and ``softplus(dt_bias) = 1``, so that a token's
    log-decay is minus the rate times ``softplus(a + dt_bias)``, a
    factor around 1."""
    lo, hi = math.log(decay["rate_min"]), math.log(decay["rate_max"])
    ladder = lo + (hi - lo) * jnp.arange(n_v, dtype=jnp.float32) / max(
        n_v - 1, 1)
    return ladder, jnp.full((n_v,), math.log(math.e - 1.0), jnp.float32)


def init(key, cfg: dict) -> dict:
    """``{"params": tree}`` from one key: N(0, 0.02) matrices, N(0,
    ``conv_init_std``) taps, N(0, ``embedding_init_std``) embedding
    rows, unit norm gains, the decays' ladder."""
    z = _sizes(cfg)
    d, n_held = z["d"], len(z["held"])
    keys, values = z["n_k"] * z["d_k"], z["n_v"] * z["d_v"]
    draws = _ops.Draws()
    ones = lambda n: jnp.ones((n,), jnp.float32)
    swiglu = lambda width, lead=(): {
        "w_gate": draws.normal((*lead, d, width)),
        "w_up": draws.normal((*lead, d, width)),
        "w_down": draws.normal((*lead, width, d))}
    tree = {"embed": draws.normal((z["vocab"], d)), "final_norm": ones(d),
            "head": draws.normal((d, z["vocab"]))}
    a_log, dt_bias = decay_init(z["n_v"], z["decay"])
    for i in range(z["layers"]):
        if is_full(i, z["every"]):
            attn = {"wq": draws.normal((d, z["heads"], z["hd"])),
                    "wq_gate": draws.normal((d, z["heads"], z["hd"])),
                    "wk": draws.normal((d, z["kv"], z["hd"])),
                    "wv": draws.normal((d, z["kv"], z["hd"])),
                    "wo": draws.normal((z["heads"], z["hd"], d)),
                    "q_norm": ones(z["hd"]), "k_norm": ones(z["hd"])}
        else:
            attn = {"w_qkvz": draws.normal((d, 2 * keys + 2 * values)),
                    "w_ba": draws.normal((d, 2 * z["n_v"])),
                    "conv": draws.normal((z["taps"], 2 * keys + values)),
                    "A_log": a_log, "dt_bias": dt_bias,
                    "out_norm": ones(z["d_v"]),
                    "wo": draws.normal((z["n_v"], z["d_v"], d))}
        tree[f"layer_{i}"] = {
            "attn_norm": ones(d), "attn": attn, "moe_norm": ones(d),
            "moe": {"router": draws.normal((d, z["routed"])),
                    **swiglu(z["width"], (n_held,))},
            "shared": {**swiglu(z["shared"]), "gate": draws.normal((d, 1))}}
    drawn = draws.cut(key)
    params = jax.tree.map(
        lambda leaf: 0.02 * drawn[leaf] if isinstance(leaf, int) else leaf,
        tree)
    params["embed"] = params["embed"] * (z["embed_std"] / 0.02)
    for i in range(z["layers"]):
        if not is_full(i, z["every"]):
            attn = params[f"layer_{i}"]["attn"]
            attn["conv"] = attn["conv"] * (z["conv_std"] / 0.02)
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
    """Whether query ``i`` attends key ``j`` (broadcast) in a full
    layer: every causal key."""
    return j <= i


def _full_attention_row(lp, h, z, ein, fault):
    """The full-attention mixer's output (gated, before ``W_o``) of one
    row ``h [T, d]``: ``[T, heads, 256]``."""
    t_all, hd, kv, heads = h.shape[0], z["hd"], z["kv"], z["heads"]
    q = _rms_norm(ein("td,dhk->thk", h, lp["wq"]), lp["q_norm"], z["eps"])
    k = _rms_norm(ein("td,dhk->thk", h, lp["wk"]), lp["k_norm"], z["eps"])
    v = ein("td,dhk->thk", h, lp["wv"])
    dims = hd if fault == "rope_on_whole_head" else z["rotated"]
    freq = z["theta"] ** (-jnp.arange(dims // 2, dtype=jnp.float32)
                          / (dims // 2))
    angles = jnp.arange(t_all, dtype=jnp.float32)[:, None] * freq
    q, k = _rotate(q, angles), _rotate(k, angles)
    q = q.reshape(t_all, kv, heads // kv, hd)
    block = min(_Q_BLOCK, t_all)

    @jax.checkpoint
    def one_block(first):
        keep = allowed(first + jnp.arange(block)[:, None],
                       jnp.arange(t_all)[None, :])
        s = ein("qhgk,shk->hgqs",
                jax.lax.dynamic_slice_in_dim(q, first, block, 0),
                k) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), -1)
        return ein("hgqs,shk->qhgk", p, v)

    out = jax.lax.map(one_block, jnp.arange(0, t_all, block))
    out = out.reshape(t_all, heads, hd)
    if fault != "no_attn_gate":
        out = out * jax.nn.sigmoid(ein("td,dhk->thk", h, lp["wq_gate"]))
    return out


def _convolved(u, w, fault):
    """``silu`` of the causal depthwise convolution of ``u [T, c]`` by
    the taps ``w [taps, c]``: tap ``i`` reads ``u[t - (taps - 1) + i]``.
    (Callers wrap it, the rule and the gated norm in ``jax.checkpoint``:
    a backward pass then holds one of them open at a time, not the
    layer's every float32 intermediate of ``[T, 8192]`` at once.)"""
    if fault == "no_conv":
        return jax.nn.silu(u)
    t_all, taps = u.shape[0], w.shape[0]
    if fault == "conv_not_causal":   # the taps reach forward in time
        padded = jnp.pad(u, ((0, taps - 1), (0, 0)))
        return jax.nn.silu(sum(w[i] * padded[taps - 1 - i:
                                             taps - 1 - i + t_all]
                               for i in range(taps)))
    padded = jnp.pad(u, ((taps - 1, 0), (0, 0)))
    return jax.nn.silu(sum(w[i] * padded[i:i + t_all] for i in range(taps)))


def delta_rule(q, k, v, log_decay, beta, reset_every=None):
    """The gated delta rule token by token: ``q``, ``k``, ``v [T, heads,
    128]``, ``log_decay`` and ``beta [T, heads]`` -> ``o [T, heads,
    128]``, float32, the state from 0. ``reset_every``: the state set to
    0 before every token whose index it divides (a planted fault)."""
    t_all, heads, d_k = k.shape
    carried = jnp.ones((t_all,), jnp.float32)
    if reset_every:
        carried = (jnp.arange(t_all) % reset_every != 0).astype(jnp.float32)

    def step(state, x):
        q_t, k_t, v_t, g_t, beta_t, keep = x
        state = (keep * jnp.exp(g_t))[:, None, None] * state
        u = beta_t[:, None] * (v_t - jnp.einsum(
            "hkv,hk->hv", state, k_t, precision=_ops.HIGHEST))
        state = state + k_t[:, :, None] * u[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t,
                                 precision=_ops.HIGHEST)

    segment = next(n for n in range(min(_SEGMENT, t_all), 0, -1)
                   if t_all % n == 0)
    one_segment = jax.checkpoint(lambda state, xs: jax.lax.scan(
        step, state, xs))
    xs = tuple(a.reshape(t_all // segment, segment, *a.shape[1:])
               for a in (q, k, v, log_decay, beta, carried))
    _, o = jax.lax.scan(one_segment,
                        jnp.zeros((heads, d_k, v.shape[-1]), jnp.float32),
                        xs)
    return o.reshape(t_all, heads, v.shape[-1])


def _linear_attention_row(lp, h, z, ein, precision, fault):
    """The Gated DeltaNet mixer's output (normed and gated, before
    ``W_o``) of one row ``h [T, d]``: ``[T, n_v, 128]``."""
    t_all, n_k, n_v, d_k, d_v = h.shape[0], z["n_k"], z["n_v"], z["d_k"], \
        z["d_v"]
    keys, values = n_k * d_k, n_v * d_v
    qkvz = ein("td,df->tf", h, lp["w_qkvz"])
    ba = ein("td,df->tf", h, lp["w_ba"])
    u = jax.checkpoint(lambda u, w: _convolved(u, w, fault))(
        qkvz[:, :2 * keys + values], lp["conv"])
    zz = qkvz[:, 2 * keys + values:].reshape(t_all, n_v, d_v)
    q = u[:, :keys].reshape(t_all, n_k, d_k)
    k = u[:, keys:2 * keys].reshape(t_all, n_k, d_k)
    v = u[:, 2 * keys:].reshape(t_all, n_v, d_v)
    beta = jax.nn.sigmoid(ba[:, :n_v])
    log_decay = -jnp.exp(lp["A_log"]) * jax.nn.softplus(
        ba[:, n_v:] + lp["dt_bias"])
    if fault == "no_beta":     # (still a function of b: the checkpointed
        beta = 1.0 + 0.0 * beta   # rule differentiates every operand)
    if fault == "no_decay":
        log_decay = 0.0 * log_decay
    if fault != "no_qk_l2norm":
        unit = lambda x: x * jax.lax.rsqrt(
            jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-6)
        q, k = unit(q), unit(k)
    q = q * d_k ** -0.5
    @jax.checkpoint
    def rule(q, k, v, log_decay, beta):
        by_value = lambda x: jnp.repeat(_ops.round_to(x, precision),
                                        n_v // n_k, 1)
        return delta_rule(
            by_value(q), by_value(k), _ops.round_to(v, precision), log_decay,
            beta, _RESET_EVERY if fault == "state_not_carried" else None)

    @jax.checkpoint
    def gated_norm(o, zz, gain):
        y = _rms_norm(o, gain, z["eps"])
        return y if fault == "no_out_gate_norm" else y * jax.nn.silu(zz)

    return gated_norm(rule(q, k, v, log_decay, beta), zz, lp["out_norm"])


def _swiglu(lp, g, ein):
    hidden = jax.nn.silu(ein("td,df->tf", g, lp["w_gate"])) \
        * ein("td,df->tf", g, lp["w_up"])
    return ein("tf,fd->td", hidden, lp["w_down"])


def _experts_row(lp, g, z, ein, fault):
    """This chip's part of the routed experts' result for ``g [T, d]``."""
    held = z["held"]
    if fault == "shifted_share":
        held = [(e + len(held)) % z["routed"] for e in held]
    @jax.checkpoint  # [T, 10, 512] is not kept
    def gates_of(p):
        top_p, top_e = jax.lax.top_k(
            p, 8 if fault == "softmax_top8" else z["per_tok"])
        if fault != "no_renorm":
            top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
        return jnp.sum(jax.nn.one_hot(top_e, z["routed"])
                       * top_p[..., None], 1)

    gates = gates_of(jax.nn.softmax(ein("td,de->te", g, lp["router"]), -1))

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


def layer_row(lp, x, full: bool, z, ein, precision: str = "f32", fault=None):
    """One layer on one row ``x [T, d]``: the mixer of its kind, this
    chip's part of the routed experts and the gated shared expert."""
    h = _rms_norm(x, lp["attn_norm"], z["eps"])
    if full:
        o = _full_attention_row(lp["attn"], h, z, ein, fault)
    else:
        o = _linear_attention_row(lp["attn"], h, z, ein, precision, fault)
    x = x + ein("thk,hkd->td", o, lp["attn"]["wo"])
    g = _rms_norm(x, lp["moe_norm"], z["eps"])
    shared = _swiglu(lp["shared"], g, ein)
    if fault != "no_shared_gate":
        shared = shared * jax.nn.sigmoid(
            ein("td,do->to", g, lp["shared"]["gate"]))
    return x + _experts_row(lp["moe"], g, z, ein, fault) + shared


def forward(variables: dict, ids, cfg: dict, precision: str = "f32"):
    """Logits ``[rows, T, vocab]`` of integer ``ids [rows, T]``."""
    p, z = variables["params"], _sizes(cfg)
    fault = cfg.get("fault")
    ein = lambda eq, a, b: _ops.einsum(eq, a, b, precision)
    ids = ids.astype(jnp.int32)

    def one_row(ids_row):
        x = p["embed"][ids_row]
        for i in range(z["layers"]):
            # a layer's inside is recomputed, so that it fits
            x = jax.checkpoint(lambda lp, x, i=i: layer_row(
                lp, x, is_full(i, z["every"]), z, ein, precision, fault))(
                    p[f"layer_{i}"], x)
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

"""Decoder LM with learned sparse attention over grouped-query heads
and a dropless mixture of experts that is told which experts it holds
(the language model of Keye-VL-2.0-30B-A3B: Qwen3-MoE's block with
DeepSeek-V3.2's lightning indexer in front of the attention).

One layer, for the tokens ``x`` of a row, in the published order:

- ``h = RMSNorm(x)``; ``q, k, v = h Wq, h Wk, h Wv`` (no biases, ``k``
  and ``v`` with fewer heads than ``q``); RMSNorm over each head of
  ``q`` and ``k``; M-RoPE on ``q`` and ``k``: three position ids a
  token, the frequency pairs cut among them in contiguous sections.
- Indexer, on ``stop_gradient(h)``: ``qI = h WIq`` (``idx_heads`` of
  ``idx_dim``), ``kI = LayerNorm(h WIk)`` (one key head), ``w = h
  WIw``, rotary by the temporal id on the first ``idx_rope_dims``;
  ``I[t, s] = sum_j idx_heads^-0.5 idx_dim^-0.5 w[t, j] relu(qI[t, j]
  . kI[s])``. ``S_t`` is the ``topk`` keys of largest ``I[t, :]``
  among ``s <= t``, all of them while ``t < topk``, ties to the lower
  index. The scores are accumulated head by head and the selection is
  exact (a bisection on the scores' bits, then on the index among
  ties), so nothing holds ``[heads, T, T]``. The set goes to the
  attention as an int8 mask and is kept for the backward pass, not
  selected again.
- ``x = x + concat_i(softmax_{s in S_t}(q_i . k_{i // G} / sqrt d)
  v_{i // G}) Wo`` by ``ops/sparse_attention.py``.
- ``g = RMSNorm(x)``; ``p = softmax(g Wr)`` over ALL routed experts;
  the ``experts_per_token`` largest, gates renormalised to sum 1;
  expert ``e`` is ``Wd_e (silu(Wg_e g) * Wu_e g)``. The layer holds the
  experts ``experts_held`` and adds, for each token, the gated outputs
  of its chosen experts among them; what the others would add is left
  out (they live on other chips, whose exchange is not here). Every
  chosen (token, held expert) pair is computed: pairs are sorted by
  expert (those of experts held elsewhere last), their tokens' rows
  gathered, multiplied (``jax.lax.ragged_dot``), gated and summed back
  a chunk of ``_ROW_CHUNK`` sorted pairs at a time, by a loop that runs
  as many chunks as hold a pair of a held expert
  (:func:`held_experts_sum`, with a backward pass of its own: the same
  loop again). So the rows moved are the rows held, rounded up to a
  chunk, at any load: a holder of every expert runs every chunk. There
  is no capacity and nothing is dropped.

After the last layer RMSNorm and an untied head over ``vocab_size``
rows (a slice of the published vocabulary, when the configuration says
so). The logits come out at a width the fused cross-entropy kernel
tiles (18,992 does not): the columns past ``vocab_size`` are no
parameters, they read -1e30 and so never enter a softmax, and the loss
over the padded width is the loss over ``vocab_size``. Every layer is
rematerialised in the backward pass, but for three arrays the forward
pass keeps: its selected sets, and the attention kernel's output and
row statistics (``ops.sparse_attention.SAVED_NAMES``: one activation of
``[rows, T, heads, head_dim]`` in the compute dtype and 4 bytes a row a
head, for each layer), so the selection and the attention's forward
kernel run once a layer a step.

Counters sown into ``moe_metrics`` each forward pass: ``expert_rows``
(rows computed by each held expert), ``row_chunks`` (the chunks the
loop ran, and the chunks that all chosen pairs would take), ``routed``
(chosen pairs whose expert is held) and ``dropped`` (those of them that
the grouped products, chunk by chunk, did not multiply by their own
expert's weights: 0).
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from sparktorch_tpu.ops.sparse_attention import SAVED_NAMES, sparse_attention

_MASK_NAME = "sparse_attn_mask"
# Queries a block of index scores. The source's q_chunk_size is 512;
# the selected sets do not depend on the block (tests shrink it).
_IDX_Q_CHUNK = 1_024
# Sorted pairs a trip of the expert layer's loop (fewer where a layer
# sees fewer pairs; tests shrink it). A trip costs what 7,000 rows cost
# (its scatter-adds pass over all tokens' sums, its weight gradients
# are added to all experts'), so the chunk is near the rows a layer
# holds: PERF.md section 6, PR 28.
_ROW_CHUNK = 16_384
# The vocabulary tile of ``ops/fused_ce.py``, which the head pads to.
_CE_BLOCK_V = 512


@dataclasses.dataclass(frozen=True)
class SparseMoEConfig:
    vocab_size: int = 151_936
    d_model: int = 2_048
    n_layers: int = 48
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e7
    mrope_section: Tuple[int, ...] = (16, 24, 24)
    rms_eps: float = 1e-6
    idx_heads: int = 16
    idx_dim: int = 64
    idx_rope_dims: int = 32
    topk: int = 2_048
    n_routed_experts: int = 128
    experts_held: Tuple[int, ...] = tuple(range(128))
    experts_per_token: int = 8
    expert_width: int = 768
    compute_dtype: jnp.dtype = jnp.bfloat16

    def __post_init__(self):
        if sum(self.mrope_section) * 2 != self.head_dim:
            raise ValueError(
                f"mrope_section {self.mrope_section} does not cut the "
                f"{self.head_dim // 2} frequency pairs of a head")
        held = tuple(self.experts_held)
        if (len(set(held)) != len(held) or not held
                or not all(0 <= e < self.n_routed_experts for e in held)):
            raise ValueError(f"experts_held {held} is not a set of ids below "
                             f"{self.n_routed_experts}")
        if self.experts_per_token > self.n_routed_experts:
            raise ValueError("more experts a token than routed experts")


def _normal(stddev=0.02):
    return nn.initializers.normal(stddev)


def rms_norm(x, gain, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True) + eps)
    return y * gain


def _rotate(x, cos, sin):
    """Rotary by halves (the pair of frequency ``i`` is dims ``i`` and
    ``i + n/2``) on the last axis; ``cos``/``sin`` broadcast over heads."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _inv_freq(n_pairs: int, theta: float):
    return theta ** (-jnp.arange(n_pairs, dtype=jnp.float32) / n_pairs)


def mrope_angles(position_ids, cfg: SparseMoEConfig):
    """``[b, T, head_dim / 2]`` angles: frequency pair ``i`` turns with
    the position id of its section (temporal, height, width)."""
    inv = _inv_freq(cfg.head_dim // 2, cfg.rope_theta)
    section = np.repeat(np.arange(len(cfg.mrope_section)), cfg.mrope_section)
    pos = position_ids.astype(jnp.float32)[section]
    return jnp.moveaxis(pos, 0, -1) * inv  # [3->pairs, b, T] -> [b, T, pairs]


# -- selection ---------------------------------------------------------------


def _sortable(x):
    """float32 -> uint32 whose order is the floats' (-0.0 below 0.0)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def _bisect(holds, n_bits: int, shape):
    """For each element of ``shape``, the largest ``n_bits``-bit ``m``
    at which a monotone predicate still holds (it holds at 0, up to some
    point, and fails above), two bits a pass: ``holds(cands)`` takes
    ``[*shape, 3]`` candidates and says for each whether it holds."""
    n_bits += n_bits % 2

    def step(i, prefix):
        shift = (n_bits - 2 * (i + 1)).astype(jnp.uint32)
        cands = prefix[..., None] | (
            jnp.arange(1, 4, dtype=jnp.uint32) << shift)
        held = jnp.sum(holds(cands).astype(jnp.uint32), -1)
        return prefix | (held << shift)

    return jax.lax.fori_loop(0, n_bits // 2, step,
                             jnp.zeros(shape, jnp.uint32))


def select_topk(scores, topk: int, first_query: int = 0):
    """int8 ``[b, queries, keys]``: 1 where key ``s`` is among the
    ``topk`` largest ``scores[b, q, :]`` with ``s <= t`` for the query
    ``t = first_query + q`` (every ``s <= t`` while ``t < topk``), ties
    to the lower index. Exact."""
    b, n_q, n_k = scores.shape
    q_idx = first_query + jnp.arange(n_q, dtype=jnp.uint32)[:, None]
    s_idx = jnp.arange(n_k, dtype=jnp.uint32)[None, :]
    causal = s_idx <= q_idx
    if first_query + n_q <= topk:
        return jnp.broadcast_to(causal, scores.shape).astype(jnp.int8)
    # canonical zero, so that -0.0 ties with 0.0 as it does for floats
    key = jnp.where(causal, _sortable(scores + 0.0), jnp.uint32(0))

    # the topk-th largest key of each row: the largest threshold that
    # at least topk keys reach
    thr = _bisect(
        lambda c: jnp.sum(key[..., None] >= c[:, :, None, :], 2) >= topk,
        32, (b, n_q))[..., None]
    above, tied = key > thr, key == thr
    need = topk - jnp.sum(above, -1, keepdims=True)
    # among the tied, the `need` of lowest index: the largest m with
    # fewer than `need` tied keys below index m is the last one taken
    last = _bisect(
        lambda c: jnp.sum(tied[..., None] & (s_idx[..., None] < c[:, :, None, :]),
                          2) < need,
        max(1, (n_k - 1).bit_length()), (b, n_q))[..., None]
    chosen = above | (tied & (s_idx <= last))
    return jnp.where(q_idx < topk, causal, chosen).astype(jnp.int8)


def index_scores(q_idx, k_idx, w):
    """``I[b, t, s]`` in float32 from ``q_idx [b, queries, heads,
    dim]``, ``k_idx [b, keys, dim]`` and ``w [b, queries, heads]`` (the
    two scale factors already in ``w``), one head at a time."""
    b, t = q_idx.shape[:2]
    n_k = k_idx.shape[1]

    def one_head(acc, qw):
        q_j, w_j = qw
        s = jnp.einsum("btd,bsd->bts", q_j, k_idx,
                       preferred_element_type=jnp.float32)
        return acc + w_j[..., None] * jax.nn.relu(s), None

    acc, _ = jax.lax.scan(
        one_head, jnp.zeros((b, t, n_k), jnp.float32),
        (jnp.moveaxis(q_idx, 2, 0), jnp.moveaxis(w, 2, 0)))
    return acc


def selected_keys(q_idx, k_idx, w, topk: int, chunk: int):
    """The int8 mask ``[b, T, T]`` of the keys each query attends, a
    block of ``chunk`` queries at a time against the keys up to the
    block's last query only: scores above the diagonal are never
    computed, and blocks that end at or below ``topk`` (every causal key
    attended) compute none."""
    t_all, blocks = q_idx.shape[1], []
    for first in range(0, t_all, chunk):
        end = min(first + chunk, t_all)
        if end <= topk:
            scores = jnp.zeros((q_idx.shape[0], end - first, end))
        else:
            with jax.named_scope("indexer"):
                scores = index_scores(q_idx[:, first:end], k_idx[:, :end],
                                      w[:, first:end])
        with jax.named_scope("select_topk"):
            blocks.append(jnp.pad(select_topk(scores, topk, first),
                                  ((0, 0), (0, 0), (0, t_all - end))))
    return jnp.concatenate(blocks, axis=1)


# -- modules -----------------------------------------------------------------


class SparseAttention(nn.Module):
    config: SparseMoEConfig

    @nn.compact
    def __call__(self, h, angles, temporal):
        cfg, dt = self.config, self.config.compute_dtype
        b, t, d = h.shape
        hd = cfg.head_dim
        dense = lambda name, shape: self.param(name, _normal(), shape)
        proj = lambda x, w: jnp.einsum(
            "btd,d...->bt...", x.astype(dt), w.astype(dt),
            preferred_element_type=jnp.float32)

        q = proj(h, dense("wq", (d, cfg.n_heads, hd)))
        k = proj(h, dense("wk", (d, cfg.n_kv_heads, hd)))
        v = proj(h, dense("wv", (d, cfg.n_kv_heads, hd)))
        ones = nn.initializers.ones
        q = rms_norm(q, self.param("q_norm", ones, (hd,)), cfg.rms_eps)
        k = rms_norm(k, self.param("k_norm", ones, (hd,)), cfg.rms_eps)
        cos, sin = jnp.cos(angles)[:, :, None], jnp.sin(angles)[:, :, None]
        q, k = _rotate(q, cos, sin), _rotate(k, cos, sin)

        with jax.named_scope("indexer"):
            hi = jax.lax.stop_gradient(h)
            q_i = proj(hi, dense("idx_wq", (d, cfg.idx_heads, cfg.idx_dim)))
            k_i = nn.LayerNorm(epsilon=1e-6, dtype=jnp.float32,
                               name="idx_k_norm")(
                proj(hi, dense("idx_wk", (d, cfg.idx_dim))))
            w_i = proj(hi, dense("idx_ww", (d, cfg.idx_heads))) * (
                cfg.idx_heads ** -0.5 * cfg.idx_dim ** -0.5)
            r = cfg.idx_rope_dims
            ang = temporal.astype(jnp.float32)[..., None] * _inv_freq(
                r // 2, cfg.rope_theta)
            c_i, s_i = jnp.cos(ang), jnp.sin(ang)
            q_i = jnp.concatenate(
                [_rotate(q_i[..., :r], c_i[:, :, None], s_i[:, :, None]),
                 q_i[..., r:]], -1)
            k_i = jnp.concatenate(
                [_rotate(k_i[..., :r], c_i, s_i), k_i[..., r:]], -1)
        mask = checkpoint_name(jax.lax.stop_gradient(selected_keys(
            q_i.astype(dt), k_i.astype(dt), w_i, cfg.topk, _IDX_Q_CHUNK)),
            _MASK_NAME)
        self.sow("intermediates", "selected", mask)  # for whoever asks
        with jax.named_scope("sparse_attention"):
            o = sparse_attention(q.astype(dt), k.astype(dt), v.astype(dt),
                                 mask)
        return jnp.einsum("bthk,hkd->btd", o,
                          dense("wo", (cfg.n_heads, hd, d)).astype(dt),
                          preferred_element_type=jnp.float32)


class HeldExperts(nn.Module):
    """The experts of ``experts_held`` out of a router over all of
    ``n_routed_experts``: this chip's part of the layer's result."""

    config: SparseMoEConfig

    @nn.compact
    def __call__(self, g):
        cfg, dt = self.config, self.config.compute_dtype
        b, t, d = g.shape
        n, k = b * t, cfg.experts_per_token
        held = tuple(cfg.experts_held)
        n_held, f = len(held), cfg.expert_width
        x = g.reshape(n, d).astype(dt)

        with jax.named_scope("moe_route"):
            logits = jnp.einsum(
                "nd,de->ne", x,
                self.param("router", _normal(),
                           (d, cfg.n_routed_experts)).astype(dt),
                preferred_element_type=jnp.float32)
            probs = jax.nn.softmax(logits, -1)
            top_e = jax.lax.top_k(jax.lax.stop_gradient(probs), k)[1]
            # the chosen probabilities by a one-hot product, whose
            # transpose is dense (top_k's own is a batched scatter)
            top_p = jnp.einsum("nke,ne->nk", jax.nn.one_hot(
                top_e, cfg.n_routed_experts, dtype=probs.dtype), probs)
            gates = top_p / jnp.sum(top_p, -1, keepdims=True)
            # local id of each chosen expert, n_held for one held elsewhere
            local = np.full((cfg.n_routed_experts,), n_held, np.int32)
            local[list(held)] = np.arange(n_held)
            pair_local = jnp.asarray(local)[top_e].reshape(n * k)
            # held pairs first, by expert; pairs of experts held elsewhere
            # last
            order = jnp.argsort(pair_local, stable=True)
            rows = jnp.sum(
                pair_local[:, None] == jnp.arange(n_held)[None, :], 0,
                dtype=jnp.int32)
            token = order // k
            gate = gates.reshape(n * k)[order]

        w = lambda name, shape: self.param(name, _normal(), (n_held, *shape))
        out = held_experts_sum(x, token, gate, rows, w("w_gate", (d, f)),
                               w("w_up", (d, f)), w("w_down", (f, d)))

        chunk, n_chunks = _row_chunks(n * k)
        n_pairs = jnp.sum(pair_local < n_held).astype(jnp.float32)
        # counted chunk by chunk against the group sizes the loop gives
        # its products (chunks it does not run hold no held pair)
        covered = jax.vmap(pairs_covered)(
            jnp.pad(pair_local[order], (0, n_chunks * chunk - n * k),
                    constant_values=n_held).reshape(n_chunks, chunk),
            jax.vmap(lambda c: chunk_rows(rows, c * chunk, chunk))(
                jnp.arange(n_chunks)))
        self.sow("moe_metrics", "expert_rows", rows)
        self.sow("moe_metrics", "row_chunks", jnp.stack(
            [_trips(rows, chunk), jnp.int32(n_chunks)]))
        self.sow("moe_metrics", "routed", n_pairs)
        self.sow("moe_metrics", "dropped", n_pairs - jnp.sum(covered))
        return out.reshape(b, t, d)


# -- the held experts' rows, a chunk of the sorted pairs at a time ------------

# [m, a] x [m, b] over groups of the rows -> [groups, a, b]: the weight
# gradient of a grouped product
_BY_GROUP = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(([0], [0]), ([], [])),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


def _row_chunks(n_pairs: int):
    """``(pairs a chunk, chunks that n_pairs take)``."""
    chunk = min(_ROW_CHUNK, n_pairs)
    return chunk, -(-n_pairs // chunk)


def _trips(rows, chunk: int):
    """The chunks that hold a pair of a held expert: the loops' bound."""
    return -(-jnp.sum(rows) // chunk)


def chunk_rows(rows, start, chunk: int):
    """The group sizes of the sorted pairs ``[start, start + chunk)``:
    the overlap of each expert's range of pairs with the chunk."""
    ends = jnp.cumsum(rows)
    return jnp.maximum(jnp.minimum(ends, start + chunk)
                       - jnp.maximum(ends - rows, start), 0)


class _Chunk:
    """Chunk ``c`` of the sorted pairs: its tokens, gates, group sizes
    and gathered rows of ``x``, and the grouped product on them.

    Rows past the held pairs belong to no group (their experts are held
    elsewhere). ``ragged_dot`` leaves such rows of its result undefined
    (the TPU's does not write them: found on the chip, PR 27), so both
    sides of every product are zero there: nothing undefined reaches a
    sum in either pass."""

    def __init__(self, c, x, token, gate, rows, chunk):
        self.start = c * chunk
        self.token = jax.lax.dynamic_slice(token, (self.start,), (chunk,))
        self.gate = jax.lax.dynamic_slice(gate, (self.start,), (chunk,))[:, None]
        self.sizes = chunk_rows(rows, self.start, chunk)
        self.live = (self.start + jnp.arange(chunk) < jnp.sum(rows))[:, None]
        self.xs = self.held(x[self.token])

    def held(self, a):
        return jnp.where(self.live, a, 0.0).astype(a.dtype)

    def rdot(self, a, m):
        """``a`` (zero past the held pairs) by each row's expert's ``m``."""
        return self.held(jax.lax.ragged_dot(
            a, m, self.sizes, preferred_element_type=jnp.float32))

    def by_group(self, a, b):
        """``a^T b`` over each expert's rows, float32. A group with no
        row in this chunk reads 0 whatever the product wrote there."""
        prod = jax.lax.ragged_dot_general(
            a, b, self.sizes, _BY_GROUP, preferred_element_type=jnp.float32)
        return jnp.where((self.sizes > 0)[:, None, None], prod, 0.0)


def _padded_pairs(token, gate):
    """The sorted pairs padded to whole chunks, and the chunk."""
    chunk, n_chunks = _row_chunks(token.size)
    pad = (0, n_chunks * chunk - token.size)
    return jnp.pad(token, pad), jnp.pad(gate, pad), chunk


@jax.custom_vjp
def held_experts_sum(x, token, gate, rows, w_gate, w_up, w_down):
    """``out[t] = sum over the pairs p of token t on held experts of
    gate[p] * w_down_e (silu(w_gate_e x[t]) * w_up_e x[t])``, float32
    ``[tokens, d]``. ``token`` and ``gate`` are the chosen pairs' in
    sorted order (held pairs first, by expert), ``rows`` the pairs of
    each held expert. A loop over chunks of the sorted pairs, as many
    as hold a held pair: products in ``x``'s dtype, sums in float32."""
    dt = x.dtype
    with jax.named_scope("moe_experts"):
        token, gate, chunk = _padded_pairs(token, gate)
        w_in = jnp.concatenate([w_gate, w_up], -1).astype(dt)
        w_out = w_down.astype(dt)

        def one_chunk(c, out):
            ck = _Chunk(c, x, token, gate, rows, chunk)
            a, b = jnp.split(ck.rdot(ck.xs, w_in), 2, -1)
            ys = ck.rdot((jax.nn.silu(a) * b).astype(dt), w_out)
            return out.at[ck.token].add(ys * ck.gate)

        return jax.lax.fori_loop(0, _trips(rows, chunk), one_chunk,
                                 jnp.zeros(x.shape, jnp.float32))


def _held_experts_fwd(*args):
    return held_experts_sum(*args), args


def _held_experts_bwd(args, d_out):
    """The same loop again, a chunk's hidden rows recomputed: what is
    kept between the passes is the function's arguments, and what the
    loop carries is the gradients' sums in float32."""
    x, token, gate, rows, w_gate, w_up, w_down = args
    dt, n_pairs = x.dtype, token.size
    # a custom_vjp's backward rule carries no scope of the forward's
    with jax.named_scope("moe_experts"):
        token, gate, chunk = _padded_pairs(token, gate)
        w_in = jnp.concatenate([w_gate, w_up], -1).astype(dt)
        w_in_t, w_out_t = (jnp.swapaxes(w_in, 1, 2),
                           jnp.swapaxes(w_down.astype(dt), 1, 2))

        def one_chunk(c, sums):
            dx, d_gate, dw_in, dw_out = sums
            ck = _Chunk(c, x, token, gate, rows, chunk)
            a, b = jnp.split(ck.rdot(ck.xs, w_in), 2, -1)
            sig = jax.nn.sigmoid(a)
            hidden = a * sig * b
            dy = ck.held(d_out[ck.token]).astype(dt)
            # ys = hidden w_down, out += gate ys
            d_hidden = ck.rdot(dy, w_out_t)  # before the gate
            d_gate = jax.lax.dynamic_update_slice(
                d_gate, jnp.sum(hidden.astype(dt) * d_hidden, -1),
                (ck.start,))
            d_hidden = d_hidden * ck.gate
            d_ab = jnp.concatenate(
                [d_hidden * b * sig * (1.0 + a * (1.0 - sig)),
                 d_hidden * a * sig], -1).astype(dt)
            return (dx.at[ck.token].add(ck.rdot(d_ab, w_in_t)), d_gate,
                    dw_in + ck.by_group(ck.xs, d_ab),
                    dw_out + ck.by_group((hidden * ck.gate).astype(dt), dy))

        f32 = lambda a: jnp.zeros(a.shape, jnp.float32)
        dx, d_gate, dw_in, dw_out = jax.lax.fori_loop(
            0, _trips(rows, chunk), one_chunk,
            (f32(x), f32(gate), f32(w_in), f32(w_down)))
        dw_gate, dw_up = jnp.split(dw_in, 2, -1)
    return (dx.astype(dt), None, d_gate[:n_pairs].astype(gate.dtype), None,
            dw_gate.astype(w_gate.dtype), dw_up.astype(w_up.dtype),
            dw_out.astype(w_down.dtype))


held_experts_sum.defvjp(_held_experts_fwd, _held_experts_bwd)


def pairs_covered(sorted_expert, group_sizes):
    """How many of the pairs, in the order the grouped product gets
    them, it multiplies by their own expert's weights: pair ``i`` lies
    in the group ``g`` with ``sum(group_sizes[:g]) <= i <
    sum(group_sizes[:g + 1])`` (past the last group in none), and is
    covered when ``g`` is the local id of its expert. Counted from the
    sorted ids against the group sizes, which the layer derives apart."""
    ends = jnp.cumsum(group_sizes)
    group = jnp.searchsorted(ends, jnp.arange(sorted_expert.size),
                             side="right")
    return jnp.sum((group == sorted_expert) & (group < group_sizes.size),
                   dtype=jnp.float32)


class DecoderLayer(nn.Module):
    config: SparseMoEConfig

    @nn.compact
    def __call__(self, x, angles, temporal):
        cfg = self.config
        ones = nn.initializers.ones
        d = x.shape[-1]
        h = rms_norm(x, self.param("attn_norm", ones, (d,)), cfg.rms_eps)
        x = x + SparseAttention(cfg, name="attn")(h, angles, temporal)
        g = rms_norm(x, self.param("moe_norm", ones, (d,)), cfg.rms_eps)
        return x + HeldExperts(cfg, name="moe")(g)


class SparseMoELM(nn.Module):
    """Token ids ``[b, T]`` (ints, or the estimator's float columns) ->
    float32 logits ``[b, T, vocab_size rounded up to the fused cross
    entropy's tile]`` (-1e30 past ``vocab_size``). ``position_ids`` is ``[3, b,
    T]`` (temporal, height, width) and defaults to the token's index in
    all three, which is plain rotary."""

    config: SparseMoEConfig

    # read by the trainers that were not taught this model (D1)
    sync_dp_only = ("its attention is a Pallas kernel that GSPMD cannot "
                    "partition, and its expert layer computes one chip's "
                    "share of the experts with no exchange, so no mesh axis "
                    "may divide the model")

    def train_gauges(self) -> dict:
        """What the trainers put on the bus when they build a step."""
        cfg = self.config
        return {"train.sparse_attn.topk": cfg.topk,
                "train.moe.experts_held": len(cfg.experts_held),
                "train.moe.experts_routed": cfg.n_routed_experts}

    def train_counters(self, sown: dict, drop_fraction) -> tuple:
        """What the counters sown in one step mean, to the trainer that
        read them back (``{name: [MoE layers, ...]}``, summed over the
        shards; ``drop_fraction`` is the step's dropped over routed):
        ``(the record's fields, the bus's counters, the bus's gauges)``.
        From ``expert_rows``, ``[layers, experts held]``: the rows
        computed, the most loaded expert's and the mean, and the routed
        pairs that were not computed (routed is computed plus dropped).
        From ``row_chunks``, ``[layers, 2]``: the chunks the layers'
        loops ran, whose ratio to the chunks that all chosen pairs would
        take is the share of them moved."""
        by_expert, chunks = sown["expert_rows"], sown["row_chunks"]
        rows, f = float(by_expert.sum()), float(drop_fraction or 0.0)
        fields = dict(
            moe_rows=rows, moe_rows_max=float(by_expert.max()),
            moe_rows_mean=rows / by_expert.size,
            moe_pairs_dropped=rows * f / (1.0 - f) if f < 1.0 else rows,
            moe_row_chunks=float(chunks[:, 0].sum()))
        return (fields,
                {"train.moe.rows": rows,
                 "train.moe.pairs_dropped": fields["moe_pairs_dropped"],
                 "train.moe.row_chunks": fields["moe_row_chunks"]},
                {"train.moe.rows_max": fields["moe_rows_max"],
                 "train.moe.row_chunks_possible": float(chunks[:, 1].sum())})

    @nn.compact
    def __call__(self, ids, example_w=None, position_ids=None):
        cfg, dt = self.config, self.config.compute_dtype
        del example_w  # every row is routed; a padding row's loss weighs 0
        if jnp.issubdtype(ids.dtype, jnp.floating):
            ids = ids.astype(jnp.int32)
        b, t = ids.shape
        if position_ids is None:
            position_ids = jnp.broadcast_to(jnp.arange(t), (3, b, t))
        angles, temporal = mrope_angles(position_ids, cfg), position_ids[0]
        x = self.param("embed", _normal(),
                       (cfg.vocab_size, cfg.d_model))[ids]
        layer = nn.remat(
            DecoderLayer,
            policy=jax.checkpoint_policies.save_only_these_names(
                _MASK_NAME, *SAVED_NAMES))
        for i in range(cfg.n_layers):
            x = layer(cfg, name=f"layer_{i}")(x, angles, temporal)
        x = rms_norm(x, self.param("final_norm", nn.initializers.ones,
                                   (cfg.d_model,)), cfg.rms_eps)
        with jax.named_scope("lm_head"):
            head = self.param("head", _normal(),
                              (cfg.d_model, cfg.vocab_size)).astype(dt)
            pad = -cfg.vocab_size % min(_CE_BLOCK_V, cfg.vocab_size)
            logits = jnp.einsum("btd,dv->btv", x.astype(dt),
                                jnp.pad(head, ((0, 0), (0, pad))),
                                preferred_element_type=jnp.float32)
            return logits + jnp.where(
                jnp.arange(cfg.vocab_size + pad) < cfg.vocab_size, 0.0, -1e30)


def keye_vl2_lm(**overrides) -> SparseMoELM:
    """Keye-VL-2.0-30B-A3B's language model at its published sizes;
    ``overrides`` are :class:`SparseMoEConfig` fields (the benchmark's
    configuration file gives its cut: layers, the experts held, the
    vocabulary's slice)."""
    for key in ("mrope_section", "experts_held"):
        if key in overrides:
            overrides[key] = tuple(overrides[key])
    if isinstance(overrides.get("compute_dtype"), str):
        overrides["compute_dtype"] = jnp.dtype(overrides["compute_dtype"])
    return SparseMoELM(SparseMoEConfig(**overrides))

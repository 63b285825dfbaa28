"""Decoder LM over grouped-query heads with a dropless mixture of
experts that is told which experts it holds (Qwen3-MoE's block). What a
layer is belongs to the LAYER (:class:`LayerKind`: its attention, its
query heads, its rotary table, experts or a dense MLP after it), and a
model is its list of layers (:class:`SparseMoEConfig` ``layers``). Seven
models are built on it:

- every layer ``learned_sparse``: DeepSeek-V3.2's lightning indexer in
  front of a causal attention over the keys it selects (the language
  model of Keye-VL-2.0-30B-A3B, :func:`keye_vl2_lm`);
- every layer ``block_diffusion``: every key a static block mask allows,
  for a model trained by masked block diffusion (SDAR-30B-A3B-Chat,
  :func:`sdar_moe_lm`; "Masked block diffusion" below);
- ``full`` and ``window`` layers mixed 1:3 with different head counts and
  rotary tables, a gated attention output, a leading dense layer and a
  shared expert beside sigmoid-routed ones (Laguna-XS.2,
  :func:`laguna_lm`; "Layers of several kinds" below);
- every layer ``latent``: queries, keys and values from two low-rank
  latents, keys wider than values, a selection bias on the router and a
  multi-token prediction module after the last layer (JoyAI-LLM-Flash,
  :func:`joyai_flash_lm`; "Latent attention" and "Multi-token
  prediction" below);
- ``gated_delta`` (Gated DeltaNet linear attention: no keys and values,
  a recurrent state a head) and ``full`` layers mixed 3:1, the full
  layers at 256-wide heads under an element-wise output gate, ten of
  512 softmax-routed experts beside a GATED shared expert
  (Qwen3-Next-80B-A3B, :func:`qwen3_next_lm`; "Gated delta rule" below);
- ``short_conv`` (a gated short convolution: no softmax, no keys and
  values, no state beyond two tokens) and ``full`` layers mixed 3:1, the
  full layers at 64-wide heads, two leading dense layers, 4 of 32
  sigmoid-routed experts chosen under an expert bias, and the head TIED
  to the embedding (LFM2-8B-A1B, :func:`lfm2_moe_lm`; "Gated short
  convolution" below);
- ``window`` (1,024 keys) and ``full`` layers mixed 3:1 at hidden 2,304,
  the full layers' table YaRN over the whole head, 8 of 64
  softmax-routed experts and nothing beside them (Mellum2-12B-A2.5B,
  :func:`mellum2_lm`): every equation is an earlier model's; what it
  adds is that its 64 experts are ONE host's, held whole across an
  ``ep`` axis ("The expert exchange" below).

Shared by all, written once: the projections, q/k norm and rotary step
around the attention kernel of the grouped-query kinds
(``_GroupedQueryProjections``; who lays out what is under "Layouts
around the attention kernels" below), the expert layer (``HeldExperts``, ``held_experts_sum``), ``rms_norm``, a layer's
remat with its attention kernel's output and row statistics kept, the
head's padding to the fused cross entropy's tile, the counters and
gauges. What differs is the attention module (``_ATTENTION``, by the
layer's kind: the grouped-query kinds share ``_GroupedQueryProjections``,
``latent``, ``gated_delta`` and ``short_conv`` are modules beside it
that share no projection with them) and, under block diffusion, what the model does before its
first layer and hands back after its last. A model whose layers are all
alike says its one kind by four fields (``attention``, ``n_heads``,
``rope_theta``, ``mrope_section``) and builds what it built before
layers had kinds: the same parameter tree, the same lowered step.

One layer, for the tokens ``x`` of a row, in the published order:

- ``h = RMSNorm(x)``; ``q, k, v = h Wq, h Wk, h Wv`` (no biases, ``k``
  and ``v`` with fewer heads than ``q``); RMSNorm over each head of
  ``q`` and ``k``; M-RoPE on ``q`` and ``k``: three position ids a
  token, the frequency pairs cut among them in contiguous sections.
  (The first two models' layer; the third's is under "Layers of several
  kinds".)
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
  gathered, multiplied, gated and summed back a chunk of the sorted
  pairs at a time (:func:`_row_chunks`: twice the pairs the layer
  expects to hold), by a loop that runs as many chunks as hold a pair of
  a held expert (:func:`held_experts_sum`, with a backward pass of its
  own: the same loop again). The products are ``ops/grouped_mlp.py``'s
  Pallas kernels over the chunk's row tiles that hold a held pair, each
  tile by the expert (or, one after the other, the experts) whose rows
  lie in it: SwiGLU, the gate and the row masks are their epilogues,
  and no float32 array of ``[chunk, expert_width]`` lies between two of
  them. A tile's rows of an expert go back to their tokens' float32
  sums by a DMA a row (``moe_sum_back``; no scatter-add). So the rows
  gathered are the rows held, rounded up to a chunk, the rows multiplied
  are the rows held, rounded up to a tile an expert, and the rows summed
  back are the rows held, at any load: a holder of every expert runs
  every chunk. There is no capacity and nothing is dropped.

The expert exchange. Inside the sync DP step's ``shard_map`` over a mesh
whose ``ep`` axis has ``m`` > 1 members (:func:`ep_members`), the layer
is WHOLE across them: member ``i`` holds the block ``experts_held[i n /
m : (i + 1) n / m]`` (its ``w_gate``, ``w_up``, ``w_down`` are that
block of the leaves, which lie ``P("ep")`` on their first axis:
``parallel/sharding_rules.py`` ``decoder_ep_axes``; outside the step, at
``init``, they are ``[n, ...]`` whole) and trains on its own rows. It
routes its own rows, then (:func:`exchange_in`) all-gathers over ``ep``
the rows ``[b t, d]`` in the compute dtype, the gates and the chosen
experts of every member, sorts and runs :func:`held_experts_sum` on ALL
``m b t`` rows for its own block (the kernels as they are), and
(:func:`exchange_out`) reduce-scatters the float32 partial sums, each
member keeping its own rows' sums over every member's experts: the sum
of all ``experts_per_token`` for every token, exact, static shapes,
nothing dropped, no stand-in for a member. A token has an expert on
most members when ``experts_per_token`` is near ``m`` or above (8 of 64
over 4: 3.66 of 4 in expectation), which is when gathering every row
costs little over sending each row where it is needed (3 copies a row
for 2.75); for a group much wider than ``experts_per_token`` an
all-to-all deduplicated by chip is the better exchange and is not here.
Both collectives lie under the scope ``moe_exchange``, outside
``moe_route``; their transposes are each other, so the backward pass is
the same two, the rows' cotangent coming back summed in the compute
dtype, and each stands in one layout in either pass (the gathered rows
row-major, as the experts' kernels take them; a reduce-scatter's operand
with its tokens minor, as the TPU compiler keeps it a reduce-scatter). The step (``train/step.py`` ``ep_rows``) sums an expert leaf's
gradient over the batch axes alone, since every member's rows already
reached it, and everything else over ``ep`` too; the counters below are
summed over ``ep`` by the step, ``expert_rows`` laid out so that the sum
holds all ``n`` experts once.

After the last layer RMSNorm and a head over ``vocab_size`` rows (a
slice of the published vocabulary, when the configuration says so):
untied, a leaf ``head`` of its own, or (``tie_word_embeddings``) the
embedding transposed, in which case ``embed`` is ONE leaf used twice, by
the gather and by the head's product, its gradient the sum of the
gather's scatter-add and the product's, and the tree has no leaf
``head``. The logits come out at a width the fused cross-entropy kernel
tiles (18,992 does not): the columns past ``vocab_size`` are no
parameters, they read -1e30 and so never enter a softmax, and the loss
over the padded width is the loss over ``vocab_size``. Every layer is
rematerialised in the backward pass, but for three arrays the forward
pass keeps: its selected sets, and the attention kernel's output and
row statistics (``ops.sparse_attention.SAVED_NAMES``: one activation of
``[rows, T, heads * head_dim]`` in the compute dtype and 4 bytes a row a
head, for each layer; each kind of attention names its own, and a
layer's remat lists its kind's), so the selection and the attention's
forward kernel run once a layer a step.

Layouts around the attention kernels. The three products leave their
einsums float32 ``[b, T, heads * head_dim]`` (``_heads``: the weights
enter flat, a head is a block of lanes). ``ops/qk_norm_rope.py`` reads
them so and writes ``q5 [b, kv_heads, G, T, head_dim]``, ``k4`` and
``v4 [b, kv_heads, T, head_dim]`` in the compute dtype: the q/k norm,
the rotary step of the layer's table (:func:`rotary_table`, built once a
table a forward pass), the cast and the turn heads first are ONE kernel
forward and one backward, under the scope ``attn_qk_rope``. Every
grouped-query attention module hands those to its op's heads-first
entry (``sparse_attention_heads_first``,
``rule_attention_heads_first``,
``block_diffusion_attention_heads_first``), which returns ``o`` FLAT,
``[b, T, heads * head_dim]`` with a head a block of lanes (the kernels'
output blocks' index map makes the turn), and, backward, takes its
cotangent so and hands back the three cotangents as its kernels write
them, which the fused op's backward kernel reads so. ``Wo`` enters its product flat too
(``[heads * head_dim, d]``, a free reshape of the parameter), and the
output gate multiplies ``o`` through ``ops.sparse_attention.by_head``
(the tokens split by 8, so that the heads become an axis and nothing
moves): none of these modules transposes anything or lays an array of
``o``'s size out ``[b, T, heads, head_dim]``. (Latent attention, one
head a grid step, keeps ``o5`` heads first and its module's turn:
``ops/latent_attention.py`` says why.) The ``[b, T, h, d]`` entries of
the three ops (``sparse_attention``, ``rule_attention``,
``block_diffusion_attention``) are thin wrappers for other callers and
the tests; ``rms_norm`` and ``_rotate`` serve the block norms and the
indexer, and are the plain spelling the tests hold the fused op to.

Masked block diffusion. The forward pass of the second model is its
TRAINING forward: a row ``x_0`` of ``L`` ids is noised (one level a row,
``t = noise_eps + (1 - noise_eps) u``, ``u ~ U[0, 1)``; each token
masked independently with probability ``t``: ``x_t[i] = mask_token_id if
m_i else x_0[i]``; :func:`diffusion_noise`), and the layers run on ``[x_0
; x_t]``, ``2L`` tokens whose halves share position ids, under
``ops/block_diffusion_attention.py``'s mask in blocks of
``block_length``. The head runs on the noised half only (the clean
half's last output enters nothing), the logit at noised position ``L +
i`` predicts ``x_0[i]`` (no shift), and the model returns
``utils.losses.TokenWeighted(logits, m / t)``: the loss
``cross_entropy_weighted`` with the row as labels is then ``(1 / L)
sum_{m_i} (1 / t) CE_i``. The noise comes from the flax stream
:data:`NOISE_STREAM`, which the sync DP step hands a module that
declares it (``train_rngs``; ``train/step.py`` ``_forward_rngs``); with
no stream, ``init`` and a forward that collects no counters (a
validation forward) draw from a fixed key, the same at every call, and a
forward that collects the step's counters raises. Assumed, as the benchmark's configuration file
lists with reasons: the block length 4, the schedule and ``noise_eps``
1e-3 (the LLaDA / SDAR fine-tune convention), q/k norm (Qwen3-MoE's
convention), no shift, the loss normalised by the row's length. All
``[MASK]`` tokens share one embedding row, so a layer's router sends
them alike. Generation by denoising is not here.

Layers of several kinds. Layer ``l`` of kind ``a(l)`` in {``full``,
``window``} with ``H(l)`` query heads (Laguna-XS.2: 48 / 64), 8
key/value heads, ``d`` = 128; "assumed" marks what the source's config
names without a shape or a formula (the benchmark's configuration file
lists each with its reason):

- ``h = RMSNorm(x)``; ``q = h Wq`` (``H(l)`` heads), ``k = h Wk``, ``v =
  h Wv`` (8 heads), no biases. Assumed: RMSNorm (gain 1 at init) over
  each head of ``q`` and ``k`` before the rotary step, as above.
- Rotary by halves on the first ``r(a)`` dims of ``q`` and ``k``, the
  rest passed through (:class:`Rotary`). ``window``: ``r`` = 128,
  ``inv_freq_i = 1e4^(-2i / 128)``, ``i < 64``. ``full``: ``r`` = 64
  (``partial_rotary_factor`` 0.5) and YaRN over ``D`` = 64 dims, ``i <
  32``: ``e_i = 5e5^(-2i / D)``, ``n_i = e_i / 64``, ``c(b) = D ln(4096 /
  (2 pi b)) / (2 ln 5e5)``, ``low = max(floor(c(64)), 0)``, ``high =
  min(ceil(c(1)), D - 1)``, ``ramp_i = clip((i - low) / (high - low), 0,
  1)``, ``inv_freq_i = n_i ramp_i + e_i (1 - ramp_i)``
  (:func:`_yarn_inv_freq`); ``cos`` and ``sin`` are multiplied by
  ``attention_factor`` 1.4158883 (= 0.1 ln 64 + 1), so the rotated dims
  of ``q`` and ``k`` carry it and the passed dims do not. Assumed: this
  is Hugging Face's ``rope_type: yarn`` computation.
- Keys query ``i`` attends: ``full``: ``j <= i``. ``window``: ``j <= i``
  and ``i - j < 512`` (512 keys with its own). Softmax over them of ``q_i
  . k_j / sqrt(128)``, times ``v``, by ``ops/rule_attention.py`` under
  the kind's name (``causal`` / ``window``: :func:`layer_rule`).
- Output gate (``attn_gate``; the source's ``gating: true``). Assumed:
  one gate a head a token, ``o_{t,i} <- sigmoid(h_t Wg)_i o_{t,i}`` with
  ``Wg`` ``[2048, H(l)]`` (N(0, 0.02), no bias), on the normed input
  ``h``, before ``Wo``.
- ``x = x + o Wo``; ``g = RMSNorm(x)``.
- A ``dense`` layer (layer 0): ``x = x + Wd (silu(Wg g) * Wu g)``, width
  8,192 (:class:`SwiGLU`, scope ``dense_mlp``).
- An ``experts`` layer: ``s = sigmoid(g Wr)`` over ALL 256 experts
  (``scoring``); the 8 largest; gates ``2.5 s_e / sum_chosen s``
  (``routed_scale``); ``x = x + sum_{chosen e held here} gate_e E_e(g) +
  S(g)``, ``E_e`` and ``S`` SwiGLU of width 512, ``S`` the shared expert
  (scope ``shared_expert``), added to every token ungated, on every chip
  alike: it is no share of anything and goes through no router. Assumed:
  sigmoid scores renormalised over the chosen 8 (the convention the
  scaling factor comes with); no group limit; the selection bias that
  convention carries is a buffer, zero here, and is left out; ties to the
  lower index.

Latent attention (DeepSeek-V3's multi-head latent attention in its
uncompressed, training form; :class:`LatentAttention`). For the tokens
``x [T, d]`` of a row and ``H`` heads (JoyAI-LLM-Flash: 32), with
``q_lora_rank`` 1,536, ``kv_lora_rank`` 512, ``qk_nope_dim`` 128,
``qk_rope_dim`` 64, ``v_dim`` 128:

- ``h = RMSNorm(x)``; ``c_q = RMSNorm(h W_dq)`` (``W_dq [d, 1536]``, a
  gain a latent dim); ``q = c_q W_uq`` (``[1536, H x 192]``); each head
  ``q_i = [q_i^nope (128) ; q_i^rope (64)]`` (scope ``latent_q`` inside
  ``attn_qkv``).
- ``[c_kv (512) ; k^rope (64)] = h W_dkv``; ``c_kv <- RMSNorm(c_kv)``;
  ``[k_i^nope (128) ; v_i (128)] = c_kv W_ukv`` for each head (``[512, H x
  256]``); ``k^rope`` is ONE 64-wide key a token, shared by all heads,
  and is not normed (scope ``latent_kv``).
- Rotary on ``q_i^rope`` and ``k^rope`` only, ``theta`` 3.2e7, 32 pairs
  ``theta^(-2j / 64)``, by the token's index, INTERLEAVED: pair ``j`` is
  dims ``(2j, 2j + 1)``. The module permutes the rotary COLUMNS of
  ``W_uq`` and ``W_dkv`` (even dims, then odd: a pass over the weights)
  and rotates by halves, queries and keys alike, so every score is the
  interleaved rotation's; the same pass pads a head's query to 256
  lanes, so the products leave their einsums in whole registers and
  ``ops/latent_rope.py`` turns, casts and lays them out heads first in
  one kernel each way (scope ``latent_rope`` inside ``attn_qk_rope``).
- ``k_i = [k_i^nope ; k^rope]``; ``s_ij = q_i . k_j / sqrt(192)`` over
  causal keys ``j <= i``; softmax; ``o_i = sum_j p_ij v_j`` (128 wide),
  by ``ops/latent_attention.py`` (scope ``latent_attention``; its
  docstring says how the 192 is laid out and why); ``x = x +
  concat_i(o_i) W_o`` (``[H x 128, d]``, scope ``attn_out``). No biases,
  no per-head q/k norm, no output gate.
- The expert layer after it is the third model's, with a selection bias
  (``selection_bias``; DeepSeek-V3's ``noaux_tc``): ``s = sigmoid(g
  W_r)`` over ALL 256 experts; the 8 experts of largest ``s + b`` (``b
  [256]`` a leaf of the router that takes no gradient; no group limit;
  ties to the lower index); gates ``2.5 s_e / sum_chosen s`` from ``s``
  WITHOUT ``b``. Who sets ``b`` is not here (the rule that moves it
  against the experts' loads is left out): a configuration without one
  builds no leaf for it.

Gated delta rule (Gated DeltaNet, the linear-attention layer of
Qwen3-Next; :class:`GatedDeltaNet`). Layer ``l`` is linear attention
when ``(l + 1) % 4 != 0`` and full attention otherwise. For the tokens
``x [T, d]`` of a row, ``n_k`` = 16 key heads and ``n_v`` = 32 value
heads of 128, value head ``j`` reading key head ``j // 2``:

- ``h = RMSNorm(x)``; ``[q ; k ; v ; z] = h W_qkvz`` (``[d, 2 n_k 128 +
  2 n_v 128]``: ``q`` and ``k`` 2,048 wide, ``v`` and ``z`` 4,096),
  ``[b ; a] = h W_ba`` (``[d, 2 n_v]``), no biases, the columns in that
  order and heads in order (scope ``gdn_in_proj`` inside ``attn_qkv``).
- ``u = [q ; k ; v]`` (8,192 channels) through a causal depthwise
  convolution over time of 4 taps, no bias, then SiLU: ``u~[t, c] =
  silu(sum_{i < 4} w[i, c] u[t - 3 + i, c])``, ``u[t < 0] = 0``. ``z``,
  ``a`` and ``b`` do not pass it. Then ``q^ = q / sqrt(sum q^2 + 1e-6) /
  sqrt(128)`` and ``k^ = k / sqrt(sum k^2 + 1e-6)`` over a key head's
  128 dims, and the cast. All of it is ``ops/gdn_conv_gate.py``'s
  ``gdn_conv`` (scope ``gdn_conv``): it reads the float32 product as it
  lies, a part's columns through its blocks' index map and the three
  tokens before a tile as a halo, and writes ``q^``, ``k^`` and ``v`` in
  the compute dtype, one pass over HBM each way (kernels
  ``gdn_conv_fwd`` / ``gdn_conv_bwd``, a call for each of the three).
- A value head ``j`` and a token ``t``: ``beta = sigmoid(b)``, the
  log-decay ``g = -exp(A_log_j) softplus(a + dt_bias_j)`` (float32),
  ``alpha = exp(g)`` in (0, 1): array operations on ``[T, 32]`` (scope
  ``gdn_gates``).
- The gated delta rule, a state ``S [128, 128]`` a value head from ``S_0
  = 0``: ``S' = alpha_t S_{t-1}``; ``u_t = beta_t (v_t - S'^T k^_t)``;
  ``S_t = S' + k^_t u_t^T``; ``o_t = S_t^T q^_t`` (so ``S_t = alpha_t (I
  - beta_t k^ k^^T) S_{t-1} + beta_t k^ v^T``), computed in chunks of
  64 tokens by ``ops/gated_delta_rule.py`` (its ``CHUNK``; its
  docstring has the chunk's form, the layout and the kernels' phases;
  scope ``gated_delta``). A row that is not whole chunks is an error.
  Kept for the backward pass under the layer's remat (the op's
  ``SAVED_NAMES``): ``o``, the state entering each block of 8 chunks
  (67 MB a layer at the cell's row) and, since PR 43, each chunk's
  triangular inverse ``T`` in float32 (134 MB a layer): inverting is 60
  of a chunk's 74 matrix-unit passes, and ``gdn_bwd`` did it again for
  11.0 of its 32.3 ms a layer (TPU v5e, the kernels alone, PR 43).
- ``y_{t,j} = RMSNorm(o_{t,j}; gain [128]) * silu(z_{t,j})``, the norm
  over a head's 128 dims: ``ops/gdn_conv_gate.py``'s ``gdn_out_norm``
  (scope ``gdn_out_norm``; kernels ``gdn_out_norm_fwd`` / ``_bwd``),
  which reads ``z`` out of the product's last columns through what
  ``gdn_conv`` handed on as ``gate``, so that the product's cotangent is
  ONE buffer: the norm's backward kernel writes ``z``'s columns of it
  and the convolution's the rest, in place. ``x = x + concat_j(y_j)
  W_o`` (``[n_v 128, d]``, scope ``attn_out`` inside ``gdn_out_proj``).
- ``A_log`` and ``dt_bias`` start on a ladder (``_DECAY_RATES``): head
  ``j``'s rate ``exp(A_log_j)`` runs geometrically from 2e-3 to 0.25 and
  ``softplus(dt_bias) = 1``, half-lives from a few tokens to some
  hundreds; the convolution's taps N(0, 0.289).
- The full layers are the third model's ``full`` kind at ``head_dim``
  256 (16 query heads on 2 key/value heads, rotary by halves on 64 of
  the 256 dims, theta 1e7, plain) with ``attn_gate_width`` ``"element"``:
  the query projection is twice as wide, ``[q ; gate] = h W_q``, held as
  two leaves of one shape (``wq`` and ``wq_gate``), and ``o <- o *
  sigmoid(gate)`` element by element on the flat ``o`` before ``W_o``
  (scope ``attn_gate``).
- The expert layer after every mixer is the first model's (softmax over
  ALL 512, the 10 largest, gates renormalised), and beside it the shared
  expert under a gate (``shared_expert_gate``): ``x = x + sum_{chosen e
  held here} gate_e E_e(g) + sigmoid(g w_s) S(g)``, ``w_s [d, 1]``
  (``gate``, a leaf of the shared expert), one sigmoid a token, on every
  chip alike (scope ``shared_expert``).

Left out: decoding (the recurrent state and the convolution's last
three tokens as a cache), state resets at document boundaries in a
packed row, and the model's multi-token prediction module.

Gated short convolution (LFM2's mixer; :class:`ShortConv`). Layer ``l``
of LFM2-8B-A1B is ``layer_types[l]``, ``conv`` (18 of 24) or
``full_attention`` (layers 2, 6, 10, 14, 18, 21); RMSNorm with a plain
gain and ``eps`` 1e-5, no bias anywhere. For the tokens ``x [T, d]`` of a
row:

- ``conv``: ``h = RMSNorm(x)``; ``[B ; C ; u] = h W_in`` (``W_in [d, 3
  d]``, three equal column blocks in that order; scope ``sconv_in_proj``
  inside ``attn_qkv``); ``s = B * u``; a causal depthwise convolution
  over time of 3 taps a channel, ``c[t] = sum_{i < 3} w[i] s[t - 2 +
  i]``, ``s[t < 0] = 0``; NO activation; ``y = C * c``. The two gates
  and the taps are ``ops/short_conv_gate.py``'s ``short_conv_gate``
  (scope ``sconv_gate``; kernels ``sconv_fwd`` / ``sconv_bwd``): it
  reads the float32 product as it lies, the three blocks through its
  blocks' index map and the two tokens before a tile as a halo, and
  writes ``y`` in the compute dtype, one pass over HBM each way; the
  taps' count is that op's constant (``TAPS``), not a field. ``x = x +
  y W_out`` (``[d, d]``, scope ``attn_out`` inside ``sconv_out_proj``).
  The taps start N(0, 0.333) (``_SCONV_STD``). The layer's remat keeps
  nothing of it: the product and the pass are recomputed. A
  ``short_conv`` layer has no heads (its ``LayerKind.n_heads`` holds 0)
  and no rotary table.
- ``full_attention``: the third model's ``full`` kind at ``head_dim`` 64
  (32 query heads on 8 key/value heads, q/k RMSNorm a head, rotary by
  halves on all 64 dims, theta 1e6, plain, no gate). Heads of 64 lie
  TWO TO A REGISTER of 128 lanes from the products through
  ``ops/qk_norm_rope.py`` and the ``causal`` kernels of
  ``ops/rule_attention.py`` to the flat ``o`` (those files' docstrings
  say how): nothing is padded to 128 in HBM.
- Layers ``l < num_dense_layers`` (2): the dense SwiGLU at 7,168. The
  others: ``s = sigmoid(g W_r)`` over ALL 32 experts; the 4 of largest
  ``s + b`` (the fourth model's ``selection_bias``: the source's expert
  bias); gates ``s_e / (sum_chosen s + 1e-6)`` (``routed_norm_eps``)
  from ``s`` WITHOUT ``b``, times ``routed_scale`` 1; no shared expert.
- After the last layer RMSNorm and the head ``logits = n E^T``, ``E``
  the embedding (``tie_word_embeddings``).

Left out: decoding (the convolution's last two tokens as a cache beside
keys and values) and state resets at document boundaries in a packed
row.

Multi-token prediction (DeepSeek-V3 section 2.2, depth 1;
:class:`MultiTokenPredictor`, ``mtp_depth`` 1). With ``x^L`` the stream
after the last layer (before the final norm), ``E`` the model's own
embedding and the job's ``ids[i] = t_i``: for ``i < T - 1``, ``u_i =
[RMSNorm_e(E[t_{i+1}]) ; RMSNorm_h(x^L_i)] W_eh`` (``W_eh [2d, d]``, the
embedding's half first); ONE layer of the last layer's kind with its own
weights (its own router, bias, held experts and shared expert) over
positions ``0 .. T - 2``; ``logits^mtp_i = RMSNorm_s(.) W_head`` with the
SAME head and the same embedding as the main path: each is ONE leaf of
the tree, used twice, and its gradient is the sum of the two paths'.
``logits^mtp_i`` predicts ``t_{i+2}``, the label ``y[i + 1]``. The
kernels tile whole rows, so the module runs ``T`` positions: position
``T - 1`` takes a stand-in embedding (the row's first), no earlier
position attends it, its tokens are sent to no expert and counted by no
counter (``HeldExperts``' ``live``), and the loss gives it no weight. The
model returns ``utils.losses.MultiTokenLogits(logits, mtp_logits,
mtp_weight)`` and the loss ``cross_entropy_multi_token`` is the row's
``mean_i CE(logits_i, y_i) + mtp_weight / (T - 1) sum_{i < T - 1}
CE(logits^mtp_i, y_{i+1})``, both heads through the fused cross entropy
at the padded width. Everything the module adds runs under the scope
``mtp``, outside its layer's own scopes. Decoding with the module as a
drafter is not here.

Counters sown into ``moe_metrics`` each forward pass: ``expert_rows``
(rows computed by each held expert), ``row_chunks`` (the chunks the
loop ran, and the chunks that all chosen pairs would take), ``row_tiles``
(the row tiles the grouped kernels visited in those chunks, a tile once
an expert with a row in it, and the row tiles those chunks hold),
``rows_summed`` (the rows the loop's sums back add to their tokens, by
the table they walk, and the rows of those chunks), ``rows_fetched``
(the rows the loop's fetches move, the tiles with a held pair whole, and
the rows of those chunks: what a gather of whole chunks moved),
``routed``
(chosen pairs whose expert is held) and ``dropped`` (those of them that
the grouped products, chunk by chunk, did not multiply by their own
expert's weights: 0), by each layer that holds experts (a dense layer
sows none); over an ``ep`` axis also ``exchange_rows`` (the rows a
member gets from the others) and ``exchange_bytes`` (theirs in, in the
compute dtype, and their float32 sums back; a forward pass's); under
block diffusion also ``masked_tokens`` and ``tokens``
of the step and, by layer, ``attn_tiles`` (the tiles the attention's
forward kernel visits, of the whole square's); by each ``full``,
``window`` and ``latent`` layer the same count as ``attn_tiles_full`` /
``attn_tiles_window`` / ``attn_tiles_latent``; by each ``short_conv``
layer ``sconv_tokens`` (rows x ``T``: the tokens through the fused
pass); by each ``gated_delta``
layer ``gdn_chunks`` (the chunks the rule's forward kernel runs: rows x
value heads x ``T / 64``) and ``gdn_fused_tokens`` (rows x ``T``: the
tokens through the fused passes around the rule); by the multi-token
prediction module ``mtp_loss`` (the sum of its cross entropy over the
positions whose label the row itself holds, ``ids[i + 2]`` for ``i < T -
2``: a forward pass of the loss's kernel on its logits, no gradient) and
``mtp_tokens`` (those positions, counted from the mask the sum took);
its layer sows the expert counters with the other layers'.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental.layout import Layout, with_layout_constraint

from sparktorch_tpu.ops import gated_delta_rule as delta
from sparktorch_tpu.ops import gdn_conv_gate as conv_gate
from sparktorch_tpu.ops import grouped_mlp as grouped
from sparktorch_tpu.ops import qk_norm_rope as fused
from sparktorch_tpu.ops import short_conv_gate as sconv
from sparktorch_tpu.ops import latent_attention as latent
from sparktorch_tpu.ops.block_diffusion_attention import (
    SAVED_NAMES as BLOCKDIFF_SAVED_NAMES, BlockDiffusionMask,
    block_diffusion_attention_heads_first)
from sparktorch_tpu.ops.rule_attention import (
    Causal, CausalWindow, rule_attention_heads_first, saved_names,
    tiles_visited)
from sparktorch_tpu.ops.sparse_attention import (
    SAVED_NAMES, by_head, sparse_attention_heads_first)
from sparktorch_tpu.ops.latent_rope import latent_rope
from sparktorch_tpu.utils.losses import (MultiTokenLogits, TokenWeighted,
                                         token_cross_entropy)

_MASK_NAME = "sparse_attn_mask"
# Queries a block of index scores. The source's q_chunk_size is 512;
# the selected sets do not depend on the block (tests shrink it).
_IDX_Q_CHUNK = 1_024
# A trip of the expert layer's loop takes the sorted pairs this many
# times the layer's expected share of them (:func:`_row_chunks`). A
# trip's products cost the rows held (``ops/grouped_mlp.py``'s kernels
# skip a chunk's row tiles without a held pair and leave an expert
# without a row alone: 3.3 ms forward and 9.7 backward for 16,384 held
# rows of 32,768 at LFM2's widths, 4.8 and 14.3 for all 32,768; TPU v5e,
# the kernels alone, PERF.md section 6, PR 47), and so do the rows' sums
# back to their tokens since PR 48 (``moe_sum_back``: two DMAs a held
# row, 1.16 ms for 16,384 held rows of 32,768 where XLA's scatter-add of
# the chunk took 3.25, 0.32 for 4,096 of 8,192; PERF.md section 6, PR
# 48), and the rows' way in since PR 51 (``moe_fetch_rows``: a DMA a row
# of the tiles with a held pair, where XLA's gathers moved the whole
# chunk; PERF.md section 6, PR 51). What a trip still pays by the CHUNK,
# or before its first row, is XLA's: the weights' casts and the kernels'
# table. So a layer's usual load should still take ONE trip: a
# chunk of exactly the share ran one trip or two by the step's rows (13
# ms apart then; PERF.md section 6, PR 28 and PR 32).
_CHUNK_OVER_SHARE = 2
# The vocabulary tile of ``ops/fused_ce.py``, which the head pads to.
_CE_BLOCK_V = 512


@dataclasses.dataclass(frozen=True)
class Rotary:
    """One rotary table. The ``n = sum(sections)`` frequency pairs
    ``theta^(-i / n)`` turn the first ``2 n`` dims of a head by halves
    (the rest pass through), pair ``i`` with the position id of its
    section (one section: plain rotary by the token's index). ``yarn``
    is ``(factor, original_max_positions, beta_fast, beta_slow)``: the
    frequencies blended as :func:`_yarn_inv_freq` says; ``cos`` and
    ``sin`` are multiplied by ``attention_factor``."""

    theta: float
    sections: Tuple[int, ...]
    yarn: Optional[Tuple[float, float, float, float]] = None
    attention_factor: float = 1.0


@dataclasses.dataclass(frozen=True)
class LayerKind:
    """What one layer is: its attention (a key of ``_ATTENTION``), its
    query heads (a ``gated_delta`` layer's value heads; a ``short_conv``
    layer has no heads and the field holds 0), its rotary table (None
    for a layer that takes no rotary step: ``gated_delta``,
    ``short_conv``), and ``"experts"`` or ``"dense"`` after the
    attention."""

    attention: str
    n_heads: int
    rotary: Optional[Rotary]
    mlp: str = "experts"


@dataclasses.dataclass(frozen=True)
class SparseMoEConfig:
    vocab_size: int = 151_936
    d_model: int = 2_048
    n_layers: int = 48
    n_kv_heads: int = 4
    head_dim: int = 128
    rms_eps: float = 1e-6
    # A model whose layers are all alike says its one kind by these four;
    # ``layers``, where given, says each layer's and these are not read.
    # "learned_sparse" (the indexer's selected keys, causal),
    # "block_diffusion" (the module docstring's second model), "full"
    # (every causal key) or "window" (the last ``window`` causal keys)
    attention: str = "learned_sparse"
    n_heads: int = 32
    rope_theta: float = 1e7
    mrope_section: Tuple[int, ...] = (16, 24, 24)
    layers: Tuple[LayerKind, ...] = ()
    idx_heads: int = 16
    idx_dim: int = 64
    idx_rope_dims: int = 32
    topk: int = 2_048
    block_length: int = 4
    mask_token_id: int = 151_669
    noise_eps: float = 1e-3
    window: int = 512
    # ("short_conv", a gated short convolution, and "gated_delta" are a
    # model's ``layers``' to say: they take no rotary table)
    # "full" and "window" layers: one sigmoid gate a head a token on the
    # attention's output, from the layer's normed input
    attn_gate: bool = False
    # the gate's width: "head" (one a head a token, from a projection of
    # its own, ``wg``) or "element" (one a dim of a head: the query
    # projection's second half, ``wq_gate``)
    attn_gate_width: str = "head"
    # "gated_delta" layers: key heads (a layer's ``n_heads`` is its value
    # heads, each reading key head ``j // (value / key)``; every head is
    # ``ops/gated_delta_rule.py``'s ``HEAD_DIM`` wide and the rule runs in
    # its ``CHUNK``) and the causal convolution's taps
    linear_key_heads: int = 0
    linear_conv_width: int = 4
    # "latent" layers: the ranks of the two latents, a head's dims that
    # pass the rotary step and that take it (a query and a key are both,
    # the rotary part of the key one for all heads), and a value's dims
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_dim: int = 0
    n_routed_experts: int = 128
    experts_held: Tuple[int, ...] = tuple(range(128))
    experts_per_token: int = 8
    expert_width: int = 768
    # the chosen experts' gates: "softmax" over all routed experts or
    # "sigmoid" of each, renormalised over the chosen, times routed_scale
    scoring: str = "softmax"
    routed_scale: float = 1.0
    # added to the chosen scores' sum before it divides them
    routed_norm_eps: float = 0.0
    # a bias an expert, added to the scores where the experts are chosen
    # and nowhere else: a leaf the gradient does not reach
    selection_bias: bool = False
    # an expert every token goes through, beside the routed ones (0: none)
    shared_expert_width: int = 0
    # one sigmoid a token on the shared expert's output, from ``g``
    shared_expert_gate: bool = False
    dense_width: int = 0   # of a "dense" layer's MLP
    # multi-token prediction modules after the last layer (0 or 1), and
    # the weight of their loss beside the next token's
    mtp_depth: int = 0
    mtp_weight: float = 0.0
    # the head is the embedding, transposed: one leaf, ``embed``, used
    # twice, and no leaf ``head``
    tie_word_embeddings: bool = False
    compute_dtype: jnp.dtype = jnp.bfloat16

    def __post_init__(self):
        if not self.layers:
            object.__setattr__(self, "layers", (LayerKind(
                self.attention, self.n_heads,
                None if self.attention in _NO_ROTARY else
                Rotary(self.rope_theta, tuple(self.mrope_section))),)
                * self.n_layers)
        if len(self.layers) != self.n_layers:
            raise ValueError(f"{len(self.layers)} layers are described, "
                             f"n_layers is {self.n_layers}")
        for kind in self.layers:
            if kind.attention not in _ATTENTION:
                raise ValueError(f"attention {kind.attention!r} is none of "
                                 f"{sorted(_ATTENTION)}")
            if kind.mlp not in ("experts", "dense"):
                raise ValueError(f"mlp {kind.mlp!r} is neither experts nor "
                                 f"dense")
            if kind.attention in _NO_ROTARY:
                if kind.rotary is not None:
                    raise ValueError(f"a {kind.attention} layer takes no "
                                     f"rotary step: its rotary is None")
                if kind.attention == "short_conv":
                    continue
                if (self.linear_key_heads < 1
                        or kind.n_heads % self.linear_key_heads
                        or self.linear_conv_width < 1):
                    raise ValueError(
                        f"a gated_delta layer needs linear_key_heads that "
                        f"divide its {kind.n_heads} value heads and a "
                        f"convolution of one tap or more; got "
                        f"{self.linear_key_heads}, {self.linear_conv_width}")
                continue
            if kind.rotary is None:
                raise ValueError(f"a {kind.attention} layer needs a rotary "
                                 f"table")
            pairs = sum(kind.rotary.sections)
            if kind.attention == "latent":
                # a head a key/value head; the widths are the five fields'
                if 2 * pairs != self.qk_rope_dim or not (
                        self.q_lora_rank and self.kv_lora_rank
                        and self.qk_nope_dim and self.v_dim):
                    raise ValueError(
                        f"a latent layer needs q_lora_rank, kv_lora_rank, "
                        f"qk_nope_dim, v_dim and rotary sections "
                        f"{kind.rotary.sections} that cut the "
                        f"{self.qk_rope_dim // 2} pairs of qk_rope_dim")
            elif not 0 < 2 * pairs <= self.head_dim:
                raise ValueError(
                    f"rotary sections {kind.rotary.sections} do not cut the "
                    f"{self.head_dim // 2} frequency pairs of a head, or a "
                    f"leading part of them")
            elif kind.n_heads % self.n_kv_heads:
                raise ValueError(f"{kind.n_heads} query heads are not a "
                                 f"multiple of {self.n_kv_heads} key/value "
                                 f"heads")
        if 0 < self.layers_of("block_diffusion") < self.n_layers:
            raise ValueError("block diffusion doubles the row for every "
                             "layer: it is every layer's attention or none's")
        if not self.dense_width and any(k.mlp == "dense"
                                        for k in self.layers):
            raise ValueError("a dense layer's MLP needs dense_width")
        if self.diffusion and not 0 <= self.mask_token_id < self.vocab_size:
            raise ValueError(f"mask_token_id {self.mask_token_id} is no row "
                             f"of a vocabulary of {self.vocab_size}")
        if self.attn_gate_width not in ("head", "element"):
            raise ValueError(f"attn_gate_width {self.attn_gate_width!r} is "
                             f"neither head nor element")
        if self.scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"scoring {self.scoring!r} is neither softmax "
                             f"nor sigmoid")
        held = tuple(self.experts_held)
        if (len(set(held)) != len(held) or not held
                or not all(0 <= e < self.n_routed_experts for e in held)):
            raise ValueError(f"experts_held {held} is not a set of ids below "
                             f"{self.n_routed_experts}")
        if self.experts_per_token > self.n_routed_experts:
            raise ValueError("more experts a token than routed experts")
        if self.mtp_depth not in (0, 1):
            raise ValueError(f"mtp_depth {self.mtp_depth}: one multi-token "
                             f"prediction module is built, or none")
        if self.mtp_depth and (self.diffusion
                               or self.layers[-1].mlp != "experts"):
            raise ValueError("the multi-token prediction module is a layer "
                             "of the last layer's kind, which holds experts, "
                             "after a causal model")

    def layers_of(self, attention: str) -> int:
        return sum(k.attention == attention for k in self.layers)

    @property
    def diffusion(self) -> bool:
        """Whether the model trains by masked block diffusion."""
        return self.layers_of("block_diffusion") == self.n_layers


# the kinds of layer that take no rotary step and name no table
_NO_ROTARY = ("gated_delta", "short_conv")


def _normal(stddev=0.02):
    return nn.initializers.normal(stddev)


def rms_norm(x, gain, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True) + eps)
    return y * gain


def _rotate(x, cos, sin):
    """Rotary by halves (the pair of frequency ``i`` is dims ``i`` and
    ``i + n/2``) on the first ``n = 2 * cos.shape[-1]`` dims of the last
    axis, the rest passed through; ``cos``/``sin`` broadcast over heads."""
    half = cos.shape[-1]
    x1, x2 = x[..., :half], x[..., half:2 * half]
    parts = [x1 * cos - x2 * sin, x2 * cos + x1 * sin]
    if 2 * half < x.shape[-1]:
        parts.append(x[..., 2 * half:])
    return jnp.concatenate(parts, -1)


def _inv_freq(n_pairs: int, theta: float):
    return theta ** (-jnp.arange(n_pairs, dtype=jnp.float32) / n_pairs)


def _yarn_inv_freq(n_pairs: int, theta: float, factor: float,
                   original_max: float, beta_fast: float, beta_slow: float):
    """YaRN's frequencies over ``D = 2 n_pairs`` dims (Hugging Face's
    ``rope_type: yarn``, ``truncate`` as default): ``e_i = theta^(-2i /
    D)`` extrapolates, ``e_i / factor`` interpolates, and pair ``i`` takes
    ``ramp_i`` of the second, where ``c(b) = D ln(original_max / (2 pi b))
    / (2 ln theta)`` is the pair that turns ``b`` times over the original
    context, ``low = max(floor(c(beta_fast)), 0)``, ``high =
    min(ceil(c(beta_slow)), D - 1)`` and ``ramp_i = clip((i - low) / (high
    - low), 0, 1)``. Static, float64 on the host, rounded once."""
    dims = 2 * n_pairs
    pair = np.arange(n_pairs, dtype=np.float64)
    extrapolated = theta ** (-2.0 * pair / dims)
    turns = lambda b: (dims * np.log(original_max / (2 * np.pi * b))
                       / (2 * np.log(theta)))
    low = max(np.floor(turns(beta_fast)), 0.0)
    high = min(np.ceil(turns(beta_slow)), dims - 1.0)
    ramp = np.clip((pair - low) / max(high - low, 1e-3), 0.0, 1.0)
    return jnp.asarray(extrapolated / factor * ramp
                       + extrapolated * (1.0 - ramp), jnp.float32)


def rotary_angles(position_ids, rotary: Rotary):
    """``[b, T, pairs]`` angles of ``rotary``'s table: frequency pair
    ``i`` turns with the position id of its section (M-RoPE's temporal,
    height, width; one section: the first id alone)."""
    n_pairs = sum(rotary.sections)
    inv = (_inv_freq(n_pairs, rotary.theta) if rotary.yarn is None
           else _yarn_inv_freq(n_pairs, rotary.theta, *rotary.yarn))
    section = np.repeat(np.arange(len(rotary.sections)), rotary.sections)
    pos = position_ids.astype(jnp.float32)[section]
    return jnp.moveaxis(pos, 0, -1) * inv  # [3->pairs, b, T] -> [b, T, pairs]


def rotary_table(position_ids, rotary: Rotary, head_dim: int):
    """``(cos, sin)`` float32 ``[b, T, head_dim]`` of ``rotary`` as
    ``ops/qk_norm_rope.py`` reads a table (``tables`` there): whole
    heads, signed, the ``attention_factor`` on the rotated dims alone;
    built once a table a forward pass and shared by its layers."""
    return fused.tables(rotary_angles(position_ids, rotary), head_dim,
                        rotary.attention_factor)


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


class _GroupedQueryProjections(nn.Module):
    """What every attention does around its kernels: ``q`` (the layer's
    own number of heads), ``k``, ``v`` without biases; RMSNorm over each
    head of ``q`` and ``k``, the rotary step of the layer's table, the
    cast and the turn into the kernels' layout as one fused op
    (``ops/qk_norm_rope.py``); and the output projection."""

    config: SparseMoEConfig
    kind: LayerKind

    def _dense(self, name, shape):
        return self.param(name, _normal(), shape)

    def _proj(self, x, w):
        dt = self.config.compute_dtype
        return jnp.einsum("btd,d...->bt...", x.astype(dt), w.astype(dt),
                          preferred_element_type=jnp.float32)

    def _heads(self, x, name, heads):
        """``x W`` for the ``heads`` heads of the parameter ``name`` ``[d,
        heads, head_dim]``, float32 ``[b, T, heads * head_dim]``: a head
        is a block of lanes. The weights enter the product FLAT (a free
        reshape), so it leaves tokens down and lanes across, the layout
        the fused op reads; from ``btd,dhk->bthk`` the compiler writes it
        tokens minor and a transposing copy of the whole float32 product
        follows (PERF.md section 6, PR 38)."""
        dt, d = self.config.compute_dtype, x.shape[-1]
        w = self._dense(name, (d, heads, self.config.head_dim))
        return jnp.einsum("btd,df->btf", x.astype(dt),
                          w.reshape(d, -1).astype(dt),
                          preferred_element_type=jnp.float32)

    def _qkv(self, h, table):
        """``q5 [b, kv_heads, G, T, head_dim]``, ``k4`` and ``v4 [b,
        kv_heads, T, head_dim]`` in the compute dtype, as the attention
        kernels' heads-first entries read them; ``table`` is the layer's
        ``(cos, sin)`` of :func:`rotary_table`."""
        cfg, hd = self.config, self.config.head_dim
        with jax.named_scope("attn_qkv"):  # the three products, float32
            q = self._heads(h, "wq", self.kind.n_heads)
            k = self._heads(h, "wk", cfg.n_kv_heads)
            v = self._heads(h, "wv", cfg.n_kv_heads)
        ones = nn.initializers.ones
        # norm, rotation, cast and the turn heads first: one kernel
        with jax.named_scope("attn_qk_rope"):
            return fused.qk_norm_rope(
                q, k, v, self.param("q_norm", ones, (hd,)),
                self.param("k_norm", ones, (hd,)), *table, cfg.rms_eps,
                sum(self.kind.rotary.sections), cfg.compute_dtype)

    def _out(self, o, d):
        """``o Wo`` float32 for the flat ``o [b, T, heads * head_dim]``
        the attention kernels write: the parameter ``wo [heads,
        head_dim, d]`` enters the product flat too (a free reshape), as
        ``_heads`` hands its weights and for its reason."""
        cfg, dt = self.config, self.config.compute_dtype
        wo = self._dense("wo", (self.kind.n_heads, cfg.head_dim, d))
        with jax.named_scope("attn_out"):
            return jnp.einsum("btf,fd->btd", o, wo.reshape(-1, d).astype(dt),
                              preferred_element_type=jnp.float32)


class SparseAttention(_GroupedQueryProjections):

    @nn.compact
    def __call__(self, h, table, temporal):
        cfg, dt = self.config, self.config.compute_dtype
        d = h.shape[-1]
        dense, proj = self._dense, self._proj
        q5, k4, v4 = self._qkv(h, table)

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
                r // 2, self.kind.rotary.theta)
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
            o = sparse_attention_heads_first(q5, k4, v4, mask)
        return self._out(o, d)


class BlockDiffusionAttention(_GroupedQueryProjections):
    """Every key the block-diffusion mask allows, for the ``2L`` tokens
    of a clean row and its noised copy: no indexer, nothing selected."""

    @nn.compact
    def __call__(self, h, table, temporal):
        cfg = self.config
        b, t, d = h.shape
        del temporal
        q5, k4, v4 = self._qkv(h, table)
        rule = BlockDiffusionMask(t // 2, cfg.block_length)
        visited, total = tiles_visited(rule, t)
        self.sow("moe_metrics", "attn_tiles", b * cfg.n_kv_heads
                 * jnp.asarray([visited, total], jnp.float32))
        with jax.named_scope("block_diffusion_attention"):
            o = block_diffusion_attention_heads_first(q5, k4, v4, rule)
        return self._out(o, d)


# a kind of layer whose attention is a static causal rule -> what its
# kernels, its saved arrays and its scope are called in a trace
_RULE_NAMES = {"full": "causal", "window": "window"}


def layer_rule(cfg: SparseMoEConfig, kind: LayerKind):
    """The static rule of a ``full`` or ``window`` layer's attention."""
    return Causal() if kind.attention == "full" else CausalWindow(cfg.window)


class RuleAttention(_GroupedQueryProjections):
    """Every key a static causal rule keeps (``full``: all of them,
    ``window``: the last ``window``), by the kernels of
    ``ops/rule_attention.py`` under the kind's name, and where the
    configuration says so a gate on the output, from the layer's normed
    input, before ``Wo``: one sigmoid a head a token from a projection
    of its own (``attn_gate_width`` ``"head"``), or one a dim of a head
    from the second half of the query projection (``"element"``:
    ``wq_gate``, of ``wq``'s shape)."""

    @nn.compact
    def __call__(self, h, table, temporal):
        cfg, dt = self.config, self.config.compute_dtype
        b, t, d = h.shape
        del temporal
        name = _RULE_NAMES[self.kind.attention]
        q5, k4, v4 = self._qkv(h, table)
        rule = layer_rule(cfg, self.kind)
        visited, total = tiles_visited(rule, t)
        self.sow("moe_metrics", f"attn_tiles_{self.kind.attention}",
                 b * cfg.n_kv_heads
                 * jnp.asarray([visited, total], jnp.float32))
        with jax.named_scope(f"{name}_attention"):
            o = rule_attention_heads_first(q5, k4, v4, rule, name,
                                           cfg.head_dim)
        if cfg.attn_gate and cfg.attn_gate_width == "element":
            with jax.named_scope("attn_gate"):
                # flat as o lies: a gate a lane
                o = (o * jax.nn.sigmoid(self._heads(
                    h, "wq_gate", self.kind.n_heads))).astype(dt)
        elif cfg.attn_gate:
            with jax.named_scope("attn_gate"):
                gate = jax.nn.sigmoid(self._proj(
                    h, self._dense("wg", (d, self.kind.n_heads))))
                # a head is head_dim lanes of a token's row: no axis moves
                o = (by_head(o, cfg.head_dim)
                     * by_head(gate, 1)).astype(dt).reshape(o.shape)
        return self._out(o, d)


class _FlatProducts(nn.Module):
    """What the mixers beside ``_GroupedQueryProjections`` share: their
    weights' draw and a product with a weight already laid out."""

    config: SparseMoEConfig
    kind: LayerKind

    def _dense(self, name, shape):
        return self.param(name, _normal(), shape)

    def _product(self, x, w):
        """``x w`` float32 for a weight already laid out, lanes across."""
        dt = self.config.compute_dtype
        return jnp.einsum("btd,df->btf", x.astype(dt), w.astype(dt),
                          preferred_element_type=jnp.float32)


class LatentAttention(_FlatProducts):
    """Multi-head latent attention in its uncompressed (training) form
    ("Latent attention" in the module docstring): queries and keys from
    two normed latents, a query and a key ``[nope ; rope]`` with the
    rotary part of the key one for all heads, values narrower than keys,
    one key/value head a query head, every causal key
    (``ops/latent_attention.py``). No per-head norm, no gate, no bias."""

    def _slot(self, w):
        """The rotary columns of a weight (its last axis) as the fused op
        reads their product: DE-INTERLEAVED (even dims, then odd, so the
        pair ``(2j, 2j + 1)`` lies half a slot apart and the rotation is
        by halves; queries and keys alike, so no score moves) and padded
        with zero columns to whole registers. A pass over the weight,
        not over the tokens."""
        rope = self.config.qk_rope_dim
        order = np.concatenate([np.arange(0, rope, 2), np.arange(1, rope, 2)])
        return jnp.pad(w[..., order], ((0, 0),) * (w.ndim - 1) + (
            (0, latent.padded_width(rope) - rope),))

    @nn.compact
    def __call__(self, h, table, temporal):
        cfg, dt = self.config, self.config.compute_dtype
        b, t, d = h.shape
        del temporal
        heads, nope, d_v = self.kind.n_heads, cfg.qk_nope_dim, cfg.v_dim
        rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
        ones = nn.initializers.ones
        # the products before the kernels, each latent's under its name
        with jax.named_scope("attn_qkv"):
            with jax.named_scope("latent_q"):  # down, norm, up
                c_q = rms_norm(self._product(h, self._dense("w_dq", (d, rq))),
                               self.param("q_norm", ones, (rq,)), cfg.rms_eps)
                w_uq = self._dense("w_uq", (rq, heads, nope + cfg.qk_rope_dim))
                xq = self._product(c_q, jnp.concatenate(
                    [w_uq[..., :nope], self._slot(w_uq[..., nope:])],
                    -1).reshape(rq, -1))
            with jax.named_scope("latent_kv"):
                w_dkv = self._dense("w_dkv", (d, rkv + cfg.qk_rope_dim))
                down = self._product(h, jnp.concatenate(
                    [w_dkv[:, :rkv], self._slot(w_dkv[:, rkv:])], -1))
                c_kv = rms_norm(down[..., :rkv],
                                self.param("kv_norm", ones, (rkv,)),
                                cfg.rms_eps)
                xkv = self._product(c_kv, self._dense(
                    "w_ukv", (rkv, heads, nope + d_v)).reshape(rkv, -1))
        # rotation, cast and the turn heads first: one kernel
        with jax.named_scope("attn_qk_rope"), jax.named_scope("latent_rope"):
            q5, k4, v4 = latent_rope(
                xq, xkv, down[..., rkv:], *table,
                sum(self.kind.rotary.sections), nope, dt)
        visited, total = latent.tiles_visited(t)
        self.sow("moe_metrics", "attn_tiles_latent",
                 b * heads * jnp.asarray([visited, total], jnp.float32))
        with jax.named_scope("latent_attention"):
            # one head a grid step: the kernels keep o heads first and
            # the turn is XLA's (ops/latent_attention.py says why)
            o = latent.heads_last(latent.latent_attention_heads_first(
                q5, k4, v4, (nope + cfg.qk_rope_dim) ** -0.5))
        with jax.named_scope("attn_out"):
            return jnp.einsum(
                "bthk,hkd->btd", o,
                self._dense("wo", (heads, d_v, d)).astype(dt),
                preferred_element_type=jnp.float32)


# A convolution's taps at init: the spread of U(-1/2, 1/2), what an
# unset depthwise convolution of 4 taps gets where the model was written.
_CONV_STD = 0.289
# The decay rates exp(A_log) of a layer's value heads at init: a
# geometric ladder, so that with softplus(dt_bias) = 1 a token's
# log-decay lies between about -0.5 and -1e-3 and the heads' half-lives
# run from a few tokens to some hundreds, as a trained model's do. (The
# released initialisation draws the rate from U(0, 16): most heads then
# forget within a token and the state carries nothing.)
_DECAY_RATES = (2e-3, 0.25)


def _decay_ladder(key, shape, dtype=jnp.float32):
    del key
    lo, hi = np.log(_DECAY_RATES)
    return jnp.asarray(lo + (hi - lo) * np.arange(shape[0])
                       / max(shape[0] - 1, 1), dtype)


class GatedDeltaNet(_FlatProducts):
    """A Gated DeltaNet linear-attention layer ("Gated delta rule" in the
    module docstring): one product for ``q``, ``k``, ``v`` and the output
    gate ``z`` and one for the two scalars a value head, a causal
    depthwise convolution with SiLU over ``[q ; k ; v]`` and L2-normed
    ``q`` and ``k`` (one fused pass, ``ops/gdn_conv_gate.py``), ``beta``
    and the log-decay, the chunked gated delta rule
    (``ops/gated_delta_rule.py``), a gated RMSNorm a head (the same
    file's) and the output projection. No rotary step, no keys and values: what it
    carries along a row is a state ``[128, 128]`` a value head, inside
    the rule's kernels."""

    @nn.compact
    def __call__(self, h, table, temporal):
        cfg, dt = self.config, self.config.compute_dtype
        b, t, d = h.shape
        del table, temporal
        n_k, n_v = cfg.linear_key_heads, self.kind.n_heads
        d_k = d_v = delta.HEAD_DIM
        keys, values = n_k * d_k, n_v * d_v
        taps = cfg.linear_conv_width
        with jax.named_scope("attn_qkv"), jax.named_scope("gdn_in_proj"):
            # columns [q ; k ; v ; z] and [b ; a], heads in order
            qkvz = self._product(h, self._dense(
                "w_qkvz", (d, 2 * keys + 2 * values)))
            ba = self._product(h, self._dense("w_ba", (d, 2 * n_v)))
        with jax.named_scope("gdn_conv"):
            # u~[t] = silu(sum_i w[i] u[t - (taps - 1) + i]), u[t < 0] = 0
            # over [q ; k ; v] as they lie in the product, q and k
            # L2-normed a head, the cast: one pass (ops/gdn_conv_gate.py);
            # ``gate`` is the product again, for the gated norm's z
            q, k, v, gate = conv_gate.gdn_conv(
                qkvz, self.param("conv", _normal(_CONV_STD),
                                 (taps, 2 * keys + values)), keys, dt)
        with jax.named_scope("gdn_gates"):
            beta = jax.nn.sigmoid(ba[..., :n_v])
            log_decay = -jnp.exp(self.param(
                "A_log", _decay_ladder, (n_v,))) * jax.nn.softplus(
                    ba[..., n_v:] + self.param(
                        "dt_bias", nn.initializers.constant(
                            np.log(np.e - 1.0)), (n_v,)))
        self.sow("moe_metrics", "gdn_chunks", jnp.float32(
            delta.chunks_run(b, t, n_v)))
        self.sow("moe_metrics", "gdn_fused_tokens", jnp.float32(b * t))
        with jax.named_scope("gated_delta"):
            o = delta.gated_delta_rule(q, k, v, log_decay, beta)
        with jax.named_scope("gdn_out_norm"):
            y = conv_gate.gdn_out_norm(
                o, gate, self.param("out_norm", nn.initializers.ones, (d_v,)),
                cfg.rms_eps)
        # ``attn_out`` as every mixer's; ``gdn_out_proj`` tells a linear
        # layer's from a full layer's in a trace
        with jax.named_scope("gdn_out_proj"), jax.named_scope("attn_out"):
            return jnp.einsum(
                "btf,fd->btd", y,
                self._dense("wo", (n_v, d_v, d)).reshape(-1, d).astype(dt),
                preferred_element_type=jnp.float32)


# A short convolution's taps at init: the spread of U(-1/sqrt 3, 1/sqrt
# 3), what an unset depthwise convolution of 3 taps gets where the model
# was written.
_SCONV_STD = 0.333


class ShortConv(_FlatProducts):
    """A gated short convolution ("Gated short convolution" in the
    module docstring): one product for the two gates and the input, ``[B
    ; C ; u] = h W_in``; ``y = C * conv3(B * u)``, a causal depthwise
    convolution of 3 taps between two element-wise gates, no activation
    (one fused pass, ``ops/short_conv_gate.py``); the output projection.
    No softmax, no rotary step, no heads; what it carries along a row is
    the two tokens before, inside the pass."""

    @nn.compact
    def __call__(self, h, table, temporal):
        dt = self.config.compute_dtype
        b, t, d = h.shape
        del table, temporal
        with jax.named_scope("attn_qkv"), jax.named_scope("sconv_in_proj"):
            # columns [B ; C ; u], three equal blocks
            bcu = self._product(h, self._dense("w_in", (d, 3 * d)))
        self.sow("moe_metrics", "sconv_tokens", jnp.float32(b * t))
        with jax.named_scope("sconv_gate"):
            y = sconv.short_conv_gate(
                bcu, self.param("conv", _normal(_SCONV_STD),
                                (sconv.TAPS, d)), dt)
        # ``attn_out`` as every mixer's; ``sconv_out_proj`` tells a
        # convolution layer's from a full layer's in a trace
        with jax.named_scope("sconv_out_proj"), jax.named_scope("attn_out"):
            return self._product(y, self._dense("wo", (d, d)))


# attention by kind of layer: the module, and what its forward pass
# names for the layer's remat policy to keep
_ATTENTION = {
    "learned_sparse": (SparseAttention, (_MASK_NAME, *SAVED_NAMES)),
    "block_diffusion": (BlockDiffusionAttention, BLOCKDIFF_SAVED_NAMES),
    **{kind: (RuleAttention, saved_names(name))
       for kind, name in _RULE_NAMES.items()},
    "latent": (LatentAttention, latent.SAVED_NAMES),
    # the rule's output, the states entering each block of chunks and
    # each chunk's inverse
    "gated_delta": (GatedDeltaNet, delta.SAVED_NAMES),
    # nothing: the product and the pass are recomputed
    "short_conv": (ShortConv, ()),
}
# the kinds whose forward kernel's tiles a layer counts under its name
_TILES_BY_KIND = (*_RULE_NAMES, "latent")


def _table_key(cfg: SparseMoEConfig, kind: LayerKind):
    """What names a layer's rotary table among the model's: the table
    and its lanes (a whole head, or a latent layer's rotary slot); None
    for a layer that takes no rotary step, for which none is built."""
    if kind.rotary is None:
        return None
    if kind.attention == "latent":
        return kind.rotary, latent.padded_width(cfg.qk_rope_dim)
    return kind.rotary, cfg.head_dim


_EP = "ep"   # the mesh axis an expert layer's weights may be cut over


def ep_members() -> int:
    """The members of the ``ep`` axis the trace is one of: the axis's
    size inside a ``shard_map`` body that cuts it (the sync DP step over
    a mesh with ``ep`` > 1), else 1 (``init``, a plain ``apply``, a mesh
    whose ``ep`` has one member)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or _EP not in mesh.manual_axes:
        return 1
    return mesh.shape[_EP]


def _laid(a, *major_to_minor):
    return with_layout_constraint(a, Layout(major_to_minor=major_to_minor))


# The exchange's two collectives of ``[tokens, d]`` arrays, each the
# other's transpose, spelled out so that each stands in ONE layout in
# either pass. A token's row is contiguous on both sides of the
# all-gather: what it hands on is what a Pallas kernel takes
# (``grouped.fetch_source``), no transposing copy of the gathered rows
# between them. The reduce-scatter's operand has its tokens MINOR: over
# a minor dimension the TPU compiler keeps a reduce-scatter, over the
# major one it rewrites it as an all-reduce and a slice (a fusion
# ``all-reduce-scatter`` of twice the bytes on the wire: 6-9 ms a step
# in Mellum2's cell, PERF.md section 6, PR 51).


def _gather(a, axis):
    return _laid(jax.lax.all_gather(_laid(a, 0, 1), axis, axis=0, tiled=True),
                 0, 1)


def _scatter(a, axis):
    return jax.lax.psum_scatter(_laid(a, 1, 0), axis, scatter_dimension=0,
                                tiled=True)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _gather_rows(a, axis):
    return _gather(a, axis)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _scatter_sums(a, axis):
    return _scatter(a, axis)


_gather_rows.defvjp(lambda a, axis: (_gather(a, axis), None),
                    lambda axis, _, ct: (_scatter(ct, axis),))
_scatter_sums.defvjp(lambda a, axis: (_scatter(a, axis), None),
                     lambda axis, _, ct: (_gather(ct, axis),))


def exchange_in(x, gates, pair_held, n_block: int, axis: str = _EP):
    """The expert exchange's way in, on one member of ``axis``: the rows
    ``x [n, d]``, gates ``[n, k]`` and chosen experts ``pair_held [n, k]``
    (an id among ALL the experts the layer holds across ``ep``, or their
    count for a pair no member computes) of every member, members in
    order, and ``pair_held`` turned into an id in THIS member's block of
    ``n_block`` experts (``n_block`` for a pair of another member's). An
    all-gather each: static shapes, no capacity, nothing dropped; its
    transpose hands each member the sum of the members' cotangents of
    its own rows (a reduce-scatter)."""
    with jax.named_scope("moe_exchange"):
        x = _gather_rows(x, axis)
        gates, pair_held = (jax.lax.all_gather(a, axis, axis=0, tiled=True)
                            for a in (gates, pair_held))
    mine = pair_held - jax.lax.axis_index(axis) * n_block
    return x, gates, jnp.where((mine >= 0) & (mine < n_block), mine, n_block)


def exchange_out(out, rows, axis: str = _EP):
    """The exchange's way back: of the float32 partial sums ``out [ep x
    n, d]`` over this member's experts for every member's rows, each
    member keeps its own ``n`` rows' sums over ALL members' experts (a
    reduce-scatter, float32 on the wire; its transpose is an all-gather).
    ``rows`` (the pairs of each of this member's experts) comes back in
    its block of a vector over all the layer's experts, zeros elsewhere,
    so that summed over ``ep`` it counts every expert once."""
    members, block = jax.lax.axis_size(axis), rows.size
    with jax.named_scope("moe_exchange"):
        out = _scatter_sums(out, axis)
    return out, jax.lax.dynamic_update_slice(
        jnp.zeros((members * block,), rows.dtype), rows,
        (jax.lax.axis_index(axis) * block,))


class HeldExperts(nn.Module):
    """The experts of ``experts_held`` out of a router over all of
    ``n_routed_experts``: this chip's part of the layer's result, or,
    under an ``ep`` axis of more than one member (:func:`ep_members`),
    the WHOLE result for this member's rows: the member holds the block
    ``experts_held[m * len / ep : (m + 1) * len / ep]`` (its weights are
    that block of the leaves, ``parallel/sharding_rules.py``
    ``decoder_ep_axes``), computes it for the rows of every member
    (:func:`exchange_in`) and gets back its own rows' sums over every
    member's block (:func:`exchange_out`). A
    token's scores are the configuration's (``scoring``: the softmax
    over all routed experts, or each expert's own sigmoid); its gates are
    the chosen scores renormalised to sum 1, times ``routed_scale``.
    Where the configuration carries a ``selection_bias``, the experts are
    chosen by ``score + bias`` and gated by the score alone: the bias is
    a leaf no gradient reaches (whoever balances the experts sets it; no
    rule for that is here). ``live [b, t]``, where given, marks the
    tokens that exist: the others (a row's padding) are sent to no held
    expert and count in no counter."""

    config: SparseMoEConfig

    @nn.compact
    def __call__(self, g, live=None):
        cfg, dt = self.config, self.config.compute_dtype
        b, t, d = g.shape
        n, k = b * t, cfg.experts_per_token
        held = tuple(cfg.experts_held)
        n_held, f = len(held), cfg.expert_width
        ep = ep_members()
        if n_held % ep:
            raise ValueError(f"{n_held} held experts are not whole blocks "
                             f"for the {ep} members of the ep axis")
        x = g.reshape(n, d).astype(dt)

        with jax.named_scope("moe_route"):
            logits = jnp.einsum(
                "nd,de->ne", x,
                self.param("router", _normal(),
                           (d, cfg.n_routed_experts)).astype(dt),
                preferred_element_type=jnp.float32)
            probs = (jax.nn.softmax(logits, -1) if cfg.scoring == "softmax"
                     else jax.nn.sigmoid(logits))
            chosen_by = jax.lax.stop_gradient(probs)
            if cfg.selection_bias:
                with jax.named_scope("selection_bias"):
                    chosen_by = chosen_by + jax.lax.stop_gradient(self.param(
                        "selection_bias", nn.initializers.zeros,
                        (cfg.n_routed_experts,)))
            top_e = jax.lax.top_k(chosen_by, k)[1]
            # the chosen probabilities by a one-hot product, whose
            # transpose is dense (top_k's own is a batched scatter)
            top_p = jnp.einsum("nke,ne->nk", jax.nn.one_hot(
                top_e, cfg.n_routed_experts, dtype=probs.dtype), probs)
            chosen_sum = jnp.sum(top_p, -1, keepdims=True)
            if cfg.routed_norm_eps:
                chosen_sum = chosen_sum + cfg.routed_norm_eps
            gates = top_p / chosen_sum
            if cfg.routed_scale != 1.0:
                gates = cfg.routed_scale * gates
            # local id of each chosen expert, n_held for one held elsewhere
            local = np.full((cfg.n_routed_experts,), n_held, np.int32)
            local[list(held)] = np.arange(n_held)
            pair_local = jnp.asarray(local)[top_e]
            if live is not None:
                pair_local = jnp.where(live.reshape(n, 1), pair_local, n_held)
        if ep > 1:
            # every member's rows, gates and chosen experts (under its own
            # scope, ``moe_exchange``, outside ``moe_route``); a pair's id
            # becomes its id in this member's block, or none
            n_held //= ep
            x, gates, pair_local = exchange_in(x, gates, pair_local, n_held)
            n *= ep
        with jax.named_scope("moe_route"):
            pair_local = pair_local.reshape(n * k)
            # held pairs first, by expert; pairs of experts held elsewhere
            # last
            order = jnp.argsort(pair_local, stable=True)
            rows = jnp.sum(
                pair_local[:, None] == jnp.arange(n_held)[None, :], 0,
                dtype=jnp.int32)
            token = order // k
            gate = gates.reshape(n * k)[order]

        chunk, n_chunks = _row_chunks(n * k, n_held, cfg.n_routed_experts)
        w = lambda name, shape: self.param(name, _normal(), (n_held, *shape))
        out = held_experts_sum(x, token, gate, rows, w("w_gate", (d, f)),
                               w("w_up", (d, f)), w("w_down", (f, d)), chunk)

        n_pairs = jnp.sum(pair_local < n_held).astype(jnp.float32)
        # counted chunk by chunk against the group sizes the loop gives
        # its products (chunks it does not run hold no held pair)
        sizes = jax.vmap(lambda c: chunk_rows(rows, c * chunk, chunk))(
            jnp.arange(n_chunks))
        covered = jax.vmap(pairs_covered)(
            jnp.pad(pair_local[order], (0, n_chunks * chunk - n * k),
                    constant_values=n_held).reshape(n_chunks, chunk), sizes)
        tile, trips = _row_tile(chunk, n_held), _trips(rows, chunk)
        if ep > 1:
            out, rows = exchange_out(out, rows)
            n //= ep
            # the rows that crossed to another member and the bytes of
            # them, in as ``x``'s dtype and back as float32 sums, forward
            moved = jnp.float32((ep - 1) * n)
            self.sow("moe_metrics", "exchange_rows", moved)
            self.sow("moe_metrics", "exchange_bytes",
                     moved * d * (jnp.dtype(dt).itemsize + 4))
        self.sow("moe_metrics", "expert_rows", rows)
        self.sow("moe_metrics", "row_chunks", jnp.stack(
            [trips, jnp.int32(n_chunks)]))
        # a chunk the loop does not run holds no held pair: no visit
        self.sow("moe_metrics", "row_tiles", jnp.stack(
            [jnp.sum(jax.vmap(lambda s: grouped.tiles_visited(s, tile))(
                sizes)), trips * (chunk // tile)]))
        # from the table the sums back walk, not from ``rows``
        self.sow("moe_metrics", "rows_summed", jnp.stack(
            [jnp.sum(jax.vmap(lambda s: grouped.rows_summed(
                grouped.visit_table(s, chunk, tile), tile))(sizes)),
             trips * chunk]))
        # the live tiles the trips' fetches move whole, of the same rows
        self.sow("moe_metrics", "rows_fetched", jnp.stack(
            [jnp.sum(grouped.rows_fetched(jnp.sum(sizes, -1), tile)),
             trips * chunk]))
        self.sow("moe_metrics", "routed", n_pairs)
        self.sow("moe_metrics", "dropped", n_pairs - jnp.sum(covered))
        return out.reshape(b, t, d)


# -- the held experts' rows, a chunk of the sorted pairs at a time ------------


def _row_chunks(n_pairs: int, n_held: int, n_routed: int):
    """``(pairs a chunk, chunks that n_pairs take)`` for a layer that
    holds ``n_held`` of ``n_routed`` experts: a chunk is
    ``_CHUNK_OVER_SHARE`` times the pairs it expects to hold, its share
    of the ``n_pairs`` chosen (16,384 of 131,072 at an eighth: 32,768),
    so the load takes one trip until it doubles, whatever the step's
    rows; the whole of ``n_pairs`` where that is less."""
    share = -(-n_pairs * n_held // n_routed)
    chunk = min(_CHUNK_OVER_SHARE * share, n_pairs)
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


def _row_tile(chunk: int, n_held: int) -> int:
    """Rows a tile of the grouped kernels for a layer's chunks: by the
    rows one of its ``n_held`` experts expects in a chunk, which holds
    ``_CHUNK_OVER_SHARE`` times the layer's expected share."""
    return grouped.row_tile(chunk, chunk // (_CHUNK_OVER_SHARE * n_held))


class _Chunk:
    """Chunk ``c`` of the sorted pairs: its tokens, gates (a column), the
    count of its held rows (``live``: they lie first), the grouped
    kernels' table of the row tiles they lie in and the rows of
    ``sources`` (``grouped.fetch_source``'s of arrays ``[tokens, d]``)
    its tokens name, ``fetched``, on those tiles
    (``ops/grouped_mlp.py``).

    Rows past the held pairs belong to no group (their experts are held
    elsewhere): ``grouped.fetch_rows`` fetches the tiles that hold a
    held pair, whole, and no kernel reads another; the sums back to the
    tokens (``grouped.sum_back``: the gated outputs, ``dx``'s rows) add
    the rows of a group only, and ``d_gate``, which is copied whole, the
    kernel writes as zeros there. Nothing is left unwritten and read: a
    grouped product that left such rows undefined put 25x gradients on
    the chip (XLA's own, PR 27).

    ``token`` is what ``sum_back`` rests on: the sort is stable over
    pairs in token order, so one expert's rows ascend in token, and
    ``top_k`` gives a token an expert at most once, so none repeats."""

    def __init__(self, c, token, gate, rows, chunk, tile, d, *sources):
        self.start = c * chunk
        self.token = jax.lax.dynamic_slice(token, (self.start,), (chunk,))
        self.gate = jax.lax.dynamic_slice(gate, (self.start,), (chunk,))[:, None]
        sizes = chunk_rows(rows, self.start, chunk)
        self.live = jnp.sum(sizes)
        self.table = grouped.visit_table(sizes, chunk, tile)
        self.fetched = grouped.fetch_rows(self.live, self.token, *sources,
                                          d=d, tile=tile)


def _padded_pairs(token, gate, chunk: int):
    """The sorted pairs padded to whole chunks."""
    pad = (0, -token.size % chunk)
    return jnp.pad(token, pad), jnp.pad(gate, pad)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def held_experts_sum(x, token, gate, rows, w_gate, w_up, w_down, chunk):
    """``out[t] = sum over the pairs p of token t on held experts of
    gate[p] * w_down_e (silu(w_gate_e x[t]) * w_up_e x[t])``, float32
    ``[tokens, d]``. ``token`` and ``gate`` are the chosen pairs' in
    sorted order (held pairs first, by expert), ``rows`` the pairs of
    each held expert. A loop over chunks of ``chunk`` sorted pairs (a
    Python int: :func:`_row_chunks`), as many as hold a held pair: a
    trip fetches its chunk's rows on the row tiles that hold a held pair
    (``fetch_rows``), runs ``ops/grouped_mlp.py``'s two forward kernels
    on those tiles and adds their rows of a group to the tokens' sums
    (``sum_back``: within one expert's rows ``token`` has to ascend, no
    token twice); products in ``x``'s dtype, sums in float32, a token's
    pairs in ascending order of expert."""
    dt, tile = x.dtype, _row_tile(chunk, rows.size)
    with jax.named_scope("moe_experts"):
        token, gate = _padded_pairs(token, gate.astype(jnp.float32), chunk)
        w_gate, w_up, w_down = (w.astype(dt) for w in (w_gate, w_up, w_down))
        x_rows = grouped.fetch_source(x)

        def one_chunk(c, out):
            ck = _Chunk(c, token, gate, rows, chunk, tile, x.shape[1], x_rows)
            xs, = ck.fetched
            hidden = grouped.gmm_in(ck.table, xs, w_gate, w_up, tile=tile)
            return grouped.sum_back(ck.table, out, grouped.gmm_down(
                ck.table, hidden, w_down, ck.gate, tile=tile), ck.token,
                tile=tile)

        return jax.lax.fori_loop(0, _trips(rows, chunk), one_chunk,
                                 grouped.token_sums(*x.shape)).reshape(x.shape)


def _held_experts_fwd(*args):
    return held_experts_sum(*args), args[:7]


def _held_experts_bwd(chunk, args, d_out):
    """The same loop again, a chunk's hidden rows recomputed in the
    kernel that takes their cotangent: what is kept between the passes
    is the function's arguments, and what the loop carries is the
    gradients' sums in float32, the weights' added to in place by the
    kernels, an expert without a row in the chunk left as it is."""
    x, token, gate, rows, w_gate, w_up, w_down = args
    dt, n_pairs, tile = x.dtype, token.size, _row_tile(chunk, rows.size)
    # a custom_vjp's backward rule carries no scope of the forward's
    with jax.named_scope("moe_experts"):
        token, gate_f = _padded_pairs(token, gate.astype(jnp.float32), chunk)
        w_g, w_u, w_d = (w.astype(dt) for w in (w_gate, w_up, w_down))
        sources = (grouped.fetch_source(x),
                   grouped.fetch_source(d_out.astype(dt)))

        def one_chunk(c, sums):
            dx, d_gate, dw_gate, dw_up, dw_down = sums
            ck = _Chunk(c, token, gate_f, rows, chunk, tile, x.shape[1],
                        *sources)
            xs, dy = ck.fetched
            d_a, d_b, gated, d_gate_c = grouped.gmm_bwd_hidden(
                ck.table, xs, dy, ck.gate, w_g, w_u, w_d, tile=tile)
            dw_gate, dw_up = grouped.gmm_dw_in(
                ck.table, xs, d_a, d_b, dw_gate, dw_up, tile=tile)
            return (grouped.sum_back(ck.table, dx, grouped.gmm_dx(
                        ck.table, d_a, d_b, w_g, w_u, tile=tile), ck.token,
                        tile=tile),
                    jax.lax.dynamic_update_slice(d_gate, d_gate_c[:, 0],
                                                 (ck.start,)),
                    dw_gate, dw_up,
                    grouped.gmm_dw_down(ck.table, gated, dy, dw_down,
                                        tile=tile))

        f32 = lambda a: jnp.zeros(a.shape, jnp.float32)
        dx, d_gate, dw_gate, dw_up, dw_down = jax.lax.fori_loop(
            0, _trips(rows, chunk), one_chunk,
            (grouped.token_sums(*x.shape), f32(gate_f), f32(w_gate),
             f32(w_up), f32(w_down)))
    return (dx.reshape(x.shape).astype(dt), None, d_gate[:n_pairs].astype(gate.dtype), None,
            dw_gate.astype(w_gate.dtype), dw_up.astype(w_up.dtype),
            dw_down.astype(w_down.dtype))


held_experts_sum.defvjp(_held_experts_fwd, _held_experts_bwd)


def pairs_covered(sorted_expert, group_sizes):
    """How many of the pairs, in the order the grouped product gets
    them, it multiplies by their own expert's weights: pair ``i`` lies
    in the group ``g`` with ``sum(group_sizes[:g]) <= i <
    sum(group_sizes[:g + 1])`` (past the last group in none), and is
    covered when ``g`` is the local id of its expert. Counted from the
    sorted ids against the group sizes, which the layer derives apart.
    ``g`` is the number of groups that end at or before ``i``, counted
    by comparison: ``jnp.searchsorted`` found the same ``g`` in five
    passes of scalar gathers over all chosen pairs, 15 to 28 ms a step
    of the 8k cells on the chip (PERF.md section 6, PR 32)."""
    ends = jnp.cumsum(group_sizes)
    group = jnp.sum(jnp.arange(sorted_expert.size)[:, None] >= ends[None, :],
                    -1, dtype=jnp.int32)
    return jnp.sum((group == sorted_expert) & (group < group_sizes.size),
                   dtype=jnp.float32)


class SwiGLU(nn.Module):
    """``Wd (silu(Wg g) * Wu g)`` for every token, under a scope of its
    own: a dense layer's MLP, or the expert every token goes through;
    ``gated``: times ``sigmoid(g w)``, one gate a token (``gate [d,
    1]``)."""

    config: SparseMoEConfig
    width: int
    traced_as: str   # the scope's name
    gated: bool = False

    @nn.compact
    def __call__(self, g):
        dt, d = self.config.compute_dtype, g.shape[-1]
        w = lambda name, shape: self.param(name, _normal(), shape).astype(dt)
        with jax.named_scope(self.traced_as):
            x = g.astype(dt)
            a, b = (jnp.einsum("btd,df->btf", x, w(name, (d, self.width)),
                               preferred_element_type=jnp.float32)
                    for name in ("w_gate", "w_up"))
            out = jnp.einsum("btf,fd->btd", (jax.nn.silu(a) * b).astype(dt),
                             w("w_down", (self.width, d)),
                             preferred_element_type=jnp.float32)
            if self.gated:
                out = out * jax.nn.sigmoid(jnp.einsum(
                    "btd,do->bto", x, w("gate", (d, 1)),
                    preferred_element_type=jnp.float32))
            return out


class DecoderLayer(nn.Module):
    config: SparseMoEConfig
    kind: LayerKind

    @nn.compact
    def __call__(self, x, table, temporal, live=None):
        """``live [b, t]``, where given: the tokens that exist, for the
        expert layer (:class:`HeldExperts`)."""
        cfg, kind = self.config, self.kind
        ones = nn.initializers.ones
        d = x.shape[-1]
        norm = jax.named_scope("block_norm")(rms_norm)
        h = norm(x, self.param("attn_norm", ones, (d,)), cfg.rms_eps)
        attention, _ = _ATTENTION[kind.attention]
        x = x + attention(cfg, kind, name="attn")(h, table, temporal)
        if kind.mlp == "dense":
            g = norm(x, self.param("mlp_norm", ones, (d,)), cfg.rms_eps)
            return x + SwiGLU(cfg, cfg.dense_width, "dense_mlp",
                              name="mlp")(g)
        g = norm(x, self.param("moe_norm", ones, (d,)), cfg.rms_eps)
        x = x + HeldExperts(cfg, name="moe")(g, live)
        if cfg.shared_expert_width:
            # on every chip alike: no share of it
            x = x + SwiGLU(cfg, cfg.shared_expert_width, "shared_expert",
                           cfg.shared_expert_gate, name="shared")(g)
        return x


def _remat_layer(kept: tuple):
    """:class:`DecoderLayer` rematerialised but for the arrays its
    attention names (``kept``)."""
    return nn.remat(
        DecoderLayer,
        policy=jax.checkpoint_policies.save_only_these_names(*kept))


class MultiTokenPredictor(nn.Module):
    """One multi-token prediction module ("Multi-token prediction" in the
    module docstring): from the stream after the last layer ``x [b, T,
    d]`` and the row's embeddings ``emb``, position ``i``'s hidden state
    for the token after the next: ``[RMSNorm(emb[i + 1]) ; RMSNorm(x[i])]
    W_eh``, one layer of the last layer's kind with its own weights, and
    a norm; the caller's head makes the logits. Position ``T - 1`` has no
    next embedding: it is computed on a stand-in (the row's first), no
    earlier position attends it, its tokens reach no expert (``live``)
    and the loss gives it no weight."""

    config: SparseMoEConfig

    @nn.compact
    def __call__(self, x, emb, table, temporal):
        cfg, dt = self.config, self.config.compute_dtype
        b, t, d = x.shape
        ones = nn.initializers.ones
        norm = jax.named_scope("block_norm")(rms_norm)
        gain = lambda name: self.param(name, ones, (d,))
        halves = jnp.concatenate(
            [norm(jnp.roll(emb, -1, 1), gain("embed_norm"), cfg.rms_eps),
             norm(x, gain("hidden_norm"), cfg.rms_eps)], -1)
        with jax.named_scope("mtp_proj"):
            u = jnp.einsum(
                "bte,ed->btd", halves.astype(dt),
                self.param("proj", _normal(), (2 * d, d)).astype(dt),
                preferred_element_type=jnp.float32)
        kind = cfg.layers[-1]
        live = jnp.broadcast_to(jnp.arange(t) < t - 1, (b, t))
        u = _remat_layer(_ATTENTION[kind.attention][1])(
            cfg, kind, name="layer")(u, table, temporal, live)
        return norm(u, gain("final_norm"), cfg.rms_eps)


class SparseMoELM(nn.Module):
    """Token ids ``[b, T]`` (ints, or the estimator's float columns) ->
    float32 logits ``[b, T, vocab_size rounded up to the fused cross
    entropy's tile]`` (-1e30 past ``vocab_size``). ``position_ids`` is ``[3, b,
    T]`` (temporal, height, width) and defaults to the token's index in
    all three, which is plain rotary. Under block diffusion the logits
    come with their tokens' weights (``TokenWeighted``), with a
    multi-token prediction module with the module's logits and its
    loss's weight (``MultiTokenLogits``): what the configuration's loss
    takes as ``preds``."""

    config: SparseMoEConfig

    # read by the trainers that were not taught this model (D1)
    sync_dp_only = ("its attention is a Pallas kernel that GSPMD cannot "
                    "partition, and its expert layer's exchange over ep is "
                    "written for the sync DP step's shard_map alone, so no "
                    "other mesh axis may divide the model; and where its "
                    "training forward draws (block diffusion's noise) only "
                    "the sync DP step hands a forward pass a random stream")

    @property
    def train_rngs(self) -> tuple:
        """The flax streams the training forward draws from, for the
        step that hands them over (``train/step.py``): block diffusion's
        noise, and nothing for the deterministic model."""
        return (NOISE_STREAM,) if self.config.diffusion else ()

    def train_gauges(self, row_shape) -> dict:
        """What the trainers put on the bus when they build a step (for
        rows of ``row_shape``, which changes nothing here): the experts
        held (by a member of the ``ep`` axis the step is built over) and
        routed, and what each kind of attention among the layers says
        of itself."""
        cfg, ep = self.config, ep_members()
        gauges = {"train.moe.experts_held": len(cfg.experts_held) // ep,
                  "train.moe.experts_routed": cfg.n_routed_experts}
        if ep > 1:
            gauges["train.moe.ep_members"] = ep
        if cfg.shared_expert_width:
            gauges["train.moe.shared_width"] = cfg.shared_expert_width
        if cfg.layers_of("learned_sparse"):
            gauges["train.sparse_attn.topk"] = cfg.topk
        if cfg.diffusion:
            gauges["train.diffusion.block_length"] = cfg.block_length
        if cfg.layers_of("window"):
            gauges["train.attention.window"] = cfg.window
        gauges.update({f"train.attention.layers_{kind}": cfg.layers_of(kind)
                       for kind in _TILES_BY_KIND if cfg.layers_of(kind)})
        for kind in _NO_ROTARY:
            if cfg.layers_of(kind):
                gauges[f"train.attention.layers_{kind}"] = cfg.layers_of(
                    kind)
        if cfg.shared_expert_gate:
            gauges["train.moe.shared_gate"] = 1
        if cfg.layers_of("latent"):
            gauges["train.attention.latent_q_rank"] = cfg.q_lora_rank
            gauges["train.attention.latent_kv_rank"] = cfg.kv_lora_rank
        if cfg.selection_bias:
            gauges["train.moe.selection_bias"] = 1
        if cfg.mtp_depth:
            gauges["train.mtp.depth"] = cfg.mtp_depth
            gauges["train.mtp.weight"] = cfg.mtp_weight
        return gauges

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
        take is the share of them moved. From ``row_tiles``, ``[layers,
        2]``: the row tiles the grouped kernels visited (a tile once an
        expert with a row in it) and the row tiles of the chunks the
        loops ran: visited over ``moe_rows`` / the tile's rows is what
        the tiles' padding costs, visited over the chunks' tiles what
        the skipped tiles save. From ``rows_summed``, ``[layers, 2]``:
        the rows the sums back added to their tokens, counted from the
        visits they walk (``moe_rows`` on every step, or a held pair's
        row was lost between the products and its token), and the rows
        of the chunks the loops ran, which a scatter-add of whole chunks
        moved. From ``rows_fetched``, ``[layers, 2]``: the rows the
        trips fetched for their products (``fetch_rows``: the tiles with
        a held pair, whole, so ``moe_rows`` and under a tile more a
        trip) of the same chunks' rows, which a gather of whole chunks
        moved. Under block diffusion also the
        step's ``masked_tokens`` of its ``tokens`` (the rows' own, not
        the doubled sequence's), and from ``attn_tiles``, ``[layers,
        2]``: the tiles the attention's forward kernel visited of the
        tiles of the whole square, over rows and key/value heads; from
        ``attn_tiles_<kind>``, ``[layers of that kind, 2]``, the same by
        kind of layer. A layer sows what it has: a dense layer no expert
        counter, so no array here has a row a layer of the model."""
        by_expert, chunks = sown["expert_rows"], sown["row_chunks"]
        tiles, summed = sown["row_tiles"], sown["rows_summed"]
        fetched = sown["rows_fetched"]
        rows, f = float(by_expert.sum()), float(drop_fraction or 0.0)
        fields = dict(
            moe_rows=rows, moe_rows_max=float(by_expert.max()),
            moe_rows_mean=rows / by_expert.size,
            moe_pairs_dropped=rows * f / (1.0 - f) if f < 1.0 else rows,
            moe_row_chunks=float(chunks[:, 0].sum()),
            moe_row_tiles=float(tiles[:, 0].sum()),
            moe_rows_summed=float(summed[:, 0].sum()),
            moe_rows_fetched=float(fetched[:, 0].sum()))
        counters = {"train.moe.rows": rows,
                    "train.moe.pairs_dropped": fields["moe_pairs_dropped"],
                    "train.moe.row_chunks": fields["moe_row_chunks"]}
        gauges = {"train.moe.rows_max": fields["moe_rows_max"],
                  "train.moe.row_chunks_possible": float(chunks[:, 1].sum()),
                  "train.moe.row_tiles_visited": fields["moe_row_tiles"],
                  "train.moe.row_tiles": float(tiles[:, 1].sum()),
                  "train.moe.rows_summed": fields["moe_rows_summed"],
                  "train.moe.rows_fetched": fields["moe_rows_fetched"],
                  "train.moe.rows_moved": float(summed[:, 1].sum())}
        if "exchange_rows" in sown:
            # over an ep axis: the rows that crossed to another member,
            # summed over members and layers, and their bytes in and back
            # (a forward pass's; the backward pass moves as many)
            fields["moe_exchange_rows"] = float(sown["exchange_rows"].sum())
            counters.update({
                "train.moe.exchange_rows": fields["moe_exchange_rows"],
                "train.moe.exchange_bytes":
                    float(sown["exchange_bytes"].sum())})
        if "masked_tokens" in sown:
            fields.update(
                diffusion_masked_tokens=float(sown["masked_tokens"].sum()),
                diffusion_tokens=float(sown["tokens"].sum()))
            counters.update({
                "train.diffusion.masked_tokens":
                    fields["diffusion_masked_tokens"],
                "train.diffusion.tokens": fields["diffusion_tokens"]})
            tiles = sown["attn_tiles"].sum(0)
            gauges.update({
                "train.diffusion.attn_tiles_visited": float(tiles[0]),
                "train.diffusion.attn_tiles_total": float(tiles[1])})
        if "mtp_loss" in sown:
            # the module's own cross entropy, over the positions whose
            # label the row itself holds (all but its last two)
            tokens = float(sown["mtp_tokens"].sum())
            fields.update(mtp_loss=float(sown["mtp_loss"].sum()) / tokens,
                          mtp_tokens=tokens)
            counters["train.mtp.tokens"] = tokens
            gauges["train.mtp.loss"] = fields["mtp_loss"]
        if "gdn_chunks" in sown:
            # the chunks the rule's forward kernel ran, over the linear
            # layers: rows x value heads x tokens / chunk each
            fields["gdn_chunks"] = float(sown["gdn_chunks"].sum())
            counters["train.attention.gdn_chunks"] = fields["gdn_chunks"]
            # rows x tokens through the fused passes around the rule
            # (ops/gdn_conv_gate.py), over the linear layers
            fields["gdn_fused_tokens"] = float(
                sown["gdn_fused_tokens"].sum())
            counters["train.attention.gdn_fused_tokens"] = fields[
                "gdn_fused_tokens"]
        if "sconv_tokens" in sown:
            # rows x tokens through the fused pass, over the convolution
            # layers
            fields["sconv_tokens"] = float(sown["sconv_tokens"].sum())
            counters["train.attention.sconv_tokens"] = fields[
                "sconv_tokens"]
        for kind in _TILES_BY_KIND:
            if f"attn_tiles_{kind}" in sown:
                tiles = sown[f"attn_tiles_{kind}"].sum(0)
                gauges.update({
                    f"train.attention.{kind}_tiles_visited": float(tiles[0]),
                    f"train.attention.{kind}_tiles_total": float(tiles[1])})
        return fields, counters, gauges

    def _noise_key(self):
        """The step's key from :data:`NOISE_STREAM`. Without the stream,
        a fixed key for ``init`` and for a forward that collects no
        counters (a validation loss is then comparable from call to
        call); a forward that collects the step's counters is a
        training forward, and training on one fixed mask is an error."""
        if self.has_rng(NOISE_STREAM):
            return self.make_rng(NOISE_STREAM)
        if (self.is_mutable_collection("moe_metrics")
                and not self.is_initializing()):
            raise ValueError(
                f"a training forward under block diffusion draws its noise "
                f"from the stream {NOISE_STREAM!r} and got none: the sync DP "
                f"step finds the stream's name in the bound module's "
                f"`train_rngs` (train/step.py _forward_rngs), which a "
                f"wrapped `apply` hides")
        return jax.random.key(0)

    @nn.compact
    def __call__(self, ids, example_w=None, position_ids=None):
        cfg, dt = self.config, self.config.compute_dtype
        del example_w  # every row is routed; a padding row's loss weighs 0
        if jnp.issubdtype(ids.dtype, jnp.floating):
            ids = ids.astype(jnp.int32)
        b, t = ids.shape
        if position_ids is None:
            position_ids = jnp.broadcast_to(jnp.arange(t), (3, b, t))
        diffusion = cfg.diffusion
        if diffusion:
            with jax.named_scope("diffusion_noise"):
                level, masked = diffusion_noise(
                    self._noise_key(), b, t, cfg.noise_eps)
                ids = jnp.concatenate(
                    [ids, jnp.where(masked, cfg.mask_token_id, ids)], 1)
                position_ids = jnp.concatenate([position_ids] * 2, -1)
            self.sow("moe_metrics", "masked_tokens",
                     jnp.sum(masked, dtype=jnp.float32))
            self.sow("moe_metrics", "tokens", jnp.float32(b * t))
        # one (cos, sin) a rotary table among the layers, and one
        # rematerialised layer class a set of names an attention keeps
        with jax.named_scope("attn_qk_rope"):
            tables = {key: key and rotary_table(position_ids, *key)
                      for key in dict.fromkeys(
                          _table_key(cfg, k) for k in cfg.layers)}
        temporal = position_ids[0]
        with jax.named_scope("embed"):  # its gradient: the scatter-add
            embed = self.param("embed", _normal(),
                               (cfg.vocab_size, cfg.d_model))
            emb = embed[ids]
        x = emb
        remat = {kept: _remat_layer(kept) for kept in dict.fromkeys(
            _ATTENTION[k.attention][1] for k in cfg.layers)}
        for i, kind in enumerate(cfg.layers):
            layer = remat[_ATTENTION[kind.attention][1]]
            x = layer(cfg, kind, name=f"layer_{i}")(
                x, tables[_table_key(cfg, kind)], temporal)
        if diffusion:
            x = x[:, t:]  # the clean half's last output enters nothing
        with jax.named_scope("block_norm"):
            normed = rms_norm(x, self.param("final_norm",
                                            nn.initializers.ones,
                                            (cfg.d_model,)), cfg.rms_eps)
        with jax.named_scope("lm_head"):
            # tied: the embedding's leaf a second time (its gradient the
            # sum of the gather's scatter-add and this product's)
            head = (embed.T if cfg.tie_word_embeddings else self.param(
                "head", _normal(), (cfg.d_model, cfg.vocab_size))).astype(dt)
            pad = -cfg.vocab_size % min(_CE_BLOCK_V, cfg.vocab_size)

            def to_logits(h):
                logits = jnp.einsum("btd,dv->btv", h.astype(dt),
                                    jnp.pad(head, ((0, 0), (0, pad))),
                                    preferred_element_type=jnp.float32)
                return logits + jnp.where(
                    jnp.arange(cfg.vocab_size + pad) < cfg.vocab_size, 0.0,
                    -1e30)

            logits = to_logits(normed)
        if diffusion:
            return TokenWeighted(logits, masked / level)
        if not cfg.mtp_depth:
            return logits
        # the module, its pass through the SAME head and embedding (their
        # gradients add), and its own loss for the counters: all of it
        # under ``mtp``, outside its layer's own scopes
        with jax.named_scope("mtp"):
            last = cfg.layers[-1]
            hidden = MultiTokenPredictor(cfg, name="mtp")(
                x, emb, tables[_table_key(cfg, last)], temporal)
            with jax.named_scope("lm_head"):
                mtp_logits = to_logits(hidden)
            if self.is_mutable_collection("moe_metrics"):
                # position i is held to the token two on, ids[i + 2]: the
                # row holds that label for all but its last two positions
                seen = jnp.broadcast_to(jnp.arange(t) < t - 2, (b, t))
                per_token = token_cross_entropy(
                    jax.lax.stop_gradient(mtp_logits), jnp.roll(ids, -2, 1))
                self.sow("moe_metrics", "mtp_loss",
                         jnp.sum(per_token * seen))
                self.sow("moe_metrics", "mtp_tokens",
                         jnp.sum(seen, dtype=jnp.float32))
        return MultiTokenLogits(logits, mtp_logits, cfg.mtp_weight)


# the flax stream block diffusion's noise is drawn from
NOISE_STREAM = "diffusion"


def diffusion_noise(key, rows: int, seq_len: int, eps: float):
    """``(t [rows, 1], m [rows, seq_len])``: one noise level a row, ``t
    = eps + (1 - eps) u`` with ``u ~ U[0, 1)`` from the first half of
    ``split(key)``, and each token masked independently with probability
    ``t`` (a uniform from the second half below ``t``)."""
    k_level, k_mask = jax.random.split(key)
    level = eps + (1.0 - eps) * jax.random.uniform(k_level, (rows, 1))
    return level, jax.random.uniform(k_mask, (rows, seq_len)) < level


def _layer_kind(kind) -> LayerKind:
    """A :class:`LayerKind`, or its fields as a configuration file's
    object (``rotary`` an object of :class:`Rotary`'s fields)."""
    if isinstance(kind, LayerKind):
        return kind
    if kind.get("rotary") is None:
        return LayerKind(**{**kind, "rotary": None})
    rotary = dict(kind["rotary"])
    for key in ("sections", "yarn"):
        if rotary.get(key) is not None:
            rotary[key] = tuple(rotary[key])
    return LayerKind(**{**kind, "rotary": Rotary(**rotary)})


def _coerced(overrides: dict) -> dict:
    """A configuration file's lists, objects and dtype names as the
    fields' types."""
    for key in ("mrope_section", "experts_held"):
        if key in overrides:
            overrides[key] = tuple(overrides[key])
    if isinstance(overrides.get("compute_dtype"), str):
        overrides["compute_dtype"] = jnp.dtype(overrides["compute_dtype"])
    if "layers" in overrides:
        overrides["layers"] = tuple(map(_layer_kind, overrides["layers"]))
    return overrides


def keye_vl2_lm(**overrides) -> SparseMoELM:
    """Keye-VL-2.0-30B-A3B's language model at its published sizes;
    ``overrides`` are :class:`SparseMoEConfig` fields (the benchmark's
    configuration file gives its cut: layers, the experts held, the
    vocabulary's slice)."""
    return SparseMoELM(SparseMoEConfig(**_coerced(overrides)))


def sdar_moe_lm(**overrides) -> SparseMoELM:
    """SDAR-30B-A3B-Chat at its published sizes (the same 30B-A3B block:
    48 layers, 32 / 4 heads of 128, 128 experts of 768, 8 a token), plain
    rotary of theta 1e6, trained by masked block diffusion in blocks of
    4; ``overrides`` as for :func:`keye_vl2_lm`."""
    return SparseMoELM(SparseMoEConfig(**{
        "attention": "block_diffusion", "rope_theta": 1e6,
        "mrope_section": (64,), **_coerced(overrides)}))


def laguna_lm(**overrides) -> SparseMoELM:
    """Laguna-XS.2 at its published sizes: 40 layers of hidden 2,048 over
    8 key/value heads of 128; every fourth layer from the first attends
    every causal key with 48 query heads (rotary on 64 of the 128 dims,
    theta 5e5, YaRN by 64 over 4,096 positions), the others a window of
    512 with 64 (plain rotary, theta 1e4, all dims); a gate on every
    attention's output; the first layer's MLP dense (8,192), the others'
    256 experts of 512, 8 a token by sigmoid scores renormalised and
    scaled by 2.5, beside one shared expert of 512; vocabulary 100,352.
    ``overrides`` as for :func:`keye_vl2_lm`; ``layers`` (each a
    :class:`LayerKind` or its fields as a dict), where given, replaces
    the published pattern, which is otherwise cut to ``n_layers``."""
    overrides = _coerced(overrides)
    full = Rotary(5e5, (32,), (64.0, 4_096.0, 64.0, 1.0), 1.4158883083359672)
    window = Rotary(1e4, (64,))
    n_layers = overrides.get("n_layers", 40)
    return SparseMoELM(SparseMoEConfig(**{
        "vocab_size": 100_352, "n_layers": n_layers, "n_kv_heads": 8,
        "layers": tuple(
            LayerKind("window", 64, window) if i % 4 else
            LayerKind("full", 48, full, "experts" if i else "dense")
            for i in range(n_layers)),
        "window": 512, "attn_gate": True, "n_routed_experts": 256,
        "experts_held": tuple(range(256)), "expert_width": 512,
        "scoring": "sigmoid", "routed_scale": 2.5,
        "shared_expert_width": 512, "dense_width": 8_192, **overrides}))


def joyai_flash_lm(**overrides) -> SparseMoELM:
    """JoyAI-LLM-Flash at its published sizes: 40 layers of hidden 2,048,
    every one latent attention (32 heads, queries and keys of 128 + 64
    over values of 128, from latents of rank 1,536 and 512; interleaved
    rotary of theta 3.2e7 on the 64); the first layer's MLP dense
    (7,168), the others' 256 experts of 768, 8 a token by sigmoid scores
    plus a selection bias, renormalised and scaled by 2.5, beside one
    shared expert of 768; one multi-token prediction module weighed 0.3;
    vocabulary 129,280. ``overrides`` as for :func:`keye_vl2_lm`."""
    overrides = _coerced(overrides)
    n_layers = overrides.get("n_layers", 40)
    rotary = Rotary(3.2e7, (32,))
    return SparseMoELM(SparseMoEConfig(**{
        "vocab_size": 129_280, "n_layers": n_layers, "n_kv_heads": 32,
        "layers": tuple(
            LayerKind("latent", 32, rotary, "experts" if i else "dense")
            for i in range(n_layers)),
        "q_lora_rank": 1_536, "kv_lora_rank": 512, "qk_nope_dim": 128,
        "qk_rope_dim": 64, "v_dim": 128, "n_routed_experts": 256,
        "experts_held": tuple(range(256)), "expert_width": 768,
        "scoring": "sigmoid", "routed_scale": 2.5, "selection_bias": True,
        "shared_expert_width": 768, "dense_width": 7_168, "mtp_depth": 1,
        "mtp_weight": 0.3, **overrides}))


def qwen3_next_lm(**overrides) -> SparseMoELM:
    """Qwen3-Next-80B-A3B at its published sizes: 48 layers of hidden
    2,048; three of every four Gated DeltaNet linear attention (16 key
    and 32 value heads of 128, a causal convolution of 4 taps, the gated
    delta rule in chunks of 64), every fourth (layers 3, 7, ...) full
    causal attention with 16 query over 2 key/value heads of 256, rotary
    on 64 of the 256 dims (theta 1e7) and an element-wise output gate
    from the query projection's second half; every layer 512 experts of
    512, 10 a token by softmax scores renormalised, beside one shared
    expert of 512 under a sigmoid gate a token; vocabulary 151,936.
    ``overrides`` as for :func:`keye_vl2_lm`; ``layers``, where given,
    replaces the published pattern, which is otherwise cut to
    ``n_layers``."""
    overrides = _coerced(overrides)
    n_layers = overrides.get("n_layers", 48)
    full = Rotary(1e7, (32,))
    return SparseMoELM(SparseMoEConfig(**{
        "n_layers": n_layers, "n_kv_heads": 2, "head_dim": 256,
        "layers": tuple(
            LayerKind("full", 16, full) if (i + 1) % 4 == 0 else
            LayerKind("gated_delta", 32, None) for i in range(n_layers)),
        "attn_gate": True, "attn_gate_width": "element",
        "linear_key_heads": 16, "linear_conv_width": 4,
        "n_routed_experts": 512, "experts_held": tuple(range(512)),
        "experts_per_token": 10, "expert_width": 512,
        "shared_expert_width": 512, "shared_expert_gate": True,
        **overrides}))


# LFM2-8B-A1B's published ``layer_types``
_LFM2_LAYER_TYPES = tuple(
    "full_attention" if i in (2, 6, 10, 14, 18, 21) else "conv"
    for i in range(24))


def lfm2_moe_lm(**overrides) -> SparseMoELM:
    """LFM2-8B-A1B at its published sizes: 24 layers of hidden 2,048, 18
    gated short convolutions (3 taps) and 6 full causal attentions (32
    query over 8 key/value heads of 64, q/k norm a head, rotary by
    halves on the whole head, theta 1e6) as ``layer_types`` lists them;
    the first ``num_dense_layers`` 2 layers' MLP dense (7,168), the
    others' 32 experts of 1,792, 4 a token by sigmoid scores plus an
    expert bias, renormalised (1e-6 in the sum), scale 1, no shared
    expert; the head tied to the embedding; vocabulary 65,536, norm eps
    1e-5. ``overrides`` as for :func:`keye_vl2_lm`, and the source's own
    two keys for the pattern: ``layer_types`` (``"conv"`` /
    ``"full_attention"`` a layer) and ``num_dense_layers``, from which
    ``layers`` and ``n_layers`` are built (a cut gives its own list)."""
    overrides = _coerced(overrides)
    layer_types = tuple(overrides.pop("layer_types", _LFM2_LAYER_TYPES))
    n_dense = overrides.pop("num_dense_layers", 2)
    kinds = {"conv": ("short_conv", 0, None),
             "full_attention": ("full", 32, Rotary(1e6, (32,)))}
    if set(layer_types) - set(kinds):
        raise ValueError(f"layer_types {layer_types} name a kind that is "
                         f"none of {sorted(kinds)}")
    return SparseMoELM(SparseMoEConfig(**{
        "vocab_size": 65_536, "n_layers": len(layer_types), "n_kv_heads": 8,
        "head_dim": 64, "rms_eps": 1e-5,
        "layers": tuple(
            LayerKind(*kinds[kind], "dense" if i < n_dense else "experts")
            for i, kind in enumerate(layer_types)),
        "n_routed_experts": 32, "experts_held": tuple(range(32)),
        "experts_per_token": 4, "expert_width": 1_792, "scoring": "sigmoid",
        "selection_bias": True, "routed_norm_eps": 1e-6,
        "dense_width": 7_168, "tie_word_embeddings": True, **overrides}))


def mellum2_lm(**overrides) -> SparseMoELM:
    """Mellum2-12B-A2.5B at its published sizes: 28 layers of hidden
    2,304 over 32 query and 4 key/value heads of 128, three of every four
    attending a window of 1,024 (plain rotary, theta 5e5) and every
    fourth (layers 3, 7, ...) every causal key (YaRN by 16 over 8,192
    positions on the same theta, ``beta_fast`` 32, ``beta_slow`` 1,
    ``attention_factor`` 1.2772588722239782), rotary on the whole head;
    every layer 64 experts of 896, 8 a token by softmax scores
    renormalised, no shared expert, no dense layer; an untied head over
    98,304. Its 64 experts are ONE four-chip host's: under the sync DP
    trainer on a mesh with ``ep`` = 4 each chip holds 16 of every layer
    (:class:`HeldExperts`). ``overrides`` as for :func:`keye_vl2_lm`;
    ``layers``, where given, replaces the published pattern, which is
    otherwise cut to ``n_layers``."""
    overrides = _coerced(overrides)
    n_layers = overrides.get("n_layers", 28)
    full = Rotary(5e5, (64,), (16.0, 8_192.0, 32.0, 1.0), 1.2772588722239782)
    window = Rotary(5e5, (64,))
    return SparseMoELM(SparseMoEConfig(**{
        "vocab_size": 98_304, "d_model": 2_304, "n_layers": n_layers,
        "layers": tuple(
            LayerKind("full", 32, full) if (i + 1) % 4 == 0 else
            LayerKind("window", 32, window) for i in range(n_layers)),
        "window": 1_024, "n_routed_experts": 64,
        "experts_held": tuple(range(64)), "expert_width": 896, **overrides}))

"""``models/sparse_moe_lm.py`` (learned sparse attention over GQA, a
dropless MoE that is told which experts it holds) against its plain
reference (``chipbench/reference/keye-vl-2.0-30b-a3b-ep8.py``) at tiny
widths on the CPU, seeded weights, float32: same arithmetic in another
order, so 1e-5 relative."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench import harness
from test_sparse_attention import pallas_calls
from sparktorch_tpu.models import sparse_moe_lm as M
from sparktorch_tpu.utils.losses import resolve_loss

REF = harness.load_module("reference", "keye-vl-2.0-30b-a3b-ep8")
ROWS, T, VOCAB = 2, 128, 96


def sizes(topk=32, held=(2, 3), layers=2):
    """The reference's configuration (the source's keys) and the
    program's module for the same tiny model."""
    cfg = dict(
        hidden_size=64, num_hidden_layers=layers, num_attention_heads=4,
        num_key_value_heads=2, head_dim=128, vocab_size=VOCAB,
        num_local_experts=16, num_experts_per_tok=4,
        moe_intermediate_size=32, experts_held=list(held), rms_norm_eps=1e-6,
        rope_theta=1e7, rope_scaling={"mrope_section": [16, 24, 24]},
        sa_config={"indexer_num_heads": 2, "indexer_head_dim": 16,
                   "topk": topk}, indexer_rope_dims=8,
        embedding_init_std=1.0)
    module = M.keye_vl2_lm(
        vocab_size=VOCAB, d_model=64, n_layers=layers, n_heads=4,
        n_kv_heads=2, idx_heads=2, idx_dim=16, idx_rope_dims=8, topk=topk,
        n_routed_experts=16, experts_held=held, experts_per_token=4,
        expert_width=32, compute_dtype="float32")
    return cfg, module


def rows(seed=1):
    k1, k2 = jax.random.split(jax.random.key(seed))
    return (jax.random.randint(k1, (ROWS, T), 0, VOCAB),
            jax.random.randint(k2, (ROWS, T), 0, VOCAB))


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


# the module's own rule, kept from before any test patches it
ROW_CHUNKS_RULE = M._row_chunks


def chunks_of(patch, chunk):
    """The expert layers' loops over chunks of ``chunk`` sorted pairs,
    whatever share is held: the module's own rule (twice the expected
    share of the pairs) gives these sizes one trip or two."""
    patch.setattr(M, "_row_chunks", lambda n_pairs, *_: (
        min(chunk, n_pairs), -(-n_pairs // min(chunk, n_pairs))))


# T = 128 above topk = 32 (selection at work) and below topk = 256
# (every causal key attended)
CASES = {"above_topk": 32, "below_topk": 256}


@pytest.fixture(scope="module", params=list(CASES))
def both(request):
    """Program and reference on the same weights and rows: logits, the
    selected sets, the loss and every gradient leaf."""
    cfg, module = sizes(topk=CASES[request.param])
    # index scores in three blocks of queries, the last one short; the
    # expert layers' 1,024 sorted pairs in chunks of 96
    patch = pytest.MonkeyPatch()
    patch.setattr(M, "_IDX_Q_CHUNK", 48)
    chunks_of(patch, 96)
    request.addfinalizer(patch.undo)
    variables = REF.init(jax.random.key(0), cfg)
    ids, labels = rows()
    loss_fn = resolve_loss("cross_entropy")

    def prog_loss(p):
        logits, state = module.apply(
            {"params": p}, ids.astype(jnp.float32), mutable=["intermediates"])
        return jnp.sum(loss_fn(logits, labels)), (logits, state)

    def ref_loss(p):
        return REF.loss_sum({"params": p}, ids, labels, jnp.ones(ROWS), cfg)

    (p_loss, (p_logits, state)), p_grads = jax.value_and_grad(
        prog_loss, has_aux=True)(variables["params"])
    r_loss, r_grads = jax.value_and_grad(ref_loss)(variables["params"])
    r_logits, r_sets = REF.forward(variables, ids, cfg, with_selected=True)
    p_sets = jnp.stack([
        state["intermediates"][f"layer_{i}"]["attn"]["selected"][0]
        for i in range(cfg["num_hidden_layers"])])
    return dict(p_logits=p_logits, r_logits=r_logits, p_loss=p_loss,
                r_loss=r_loss, p_grads=p_grads, r_grads=r_grads,
                p_sets=p_sets, r_sets=r_sets, case=request.param)


def test_logits_match_the_reference(both):
    assert rel(both["p_logits"], both["r_logits"]) < 1e-5


def test_a_vocabulary_the_fused_loss_cannot_tile_is_padded_inside():
    """600 columns come out as 1,024: the columns past the vocabulary
    are no parameters and never enter a softmax, so the loss over the
    padded width is the loss over the vocabulary."""
    module = M.keye_vl2_lm(
        vocab_size=600, d_model=64, n_layers=1, n_heads=4, n_kv_heads=2,
        idx_heads=2, idx_dim=16, idx_rope_dims=8, topk=32,
        n_routed_experts=16, experts_held=(2, 3), experts_per_token=4,
        expert_width=32, compute_dtype="float32")
    ids, labels = rows()
    variables = module.init(jax.random.key(0), ids)
    assert variables["params"]["head"].shape == (64, 600)
    logits = module.apply(variables, ids)
    assert logits.shape == (ROWS, T, 1024)
    assert np.all(np.asarray(logits[..., 600:]) <= -1e29)
    loss_fn = resolve_loss("cross_entropy")
    np.testing.assert_allclose(loss_fn(logits, labels),
                               loss_fn(logits[..., :600], labels), rtol=1e-6)


def test_loss_matches_the_reference(both):
    assert abs(float(both["p_loss"] - both["r_loss"])) \
        < 1e-5 * abs(float(both["r_loss"]))


def test_every_gradient_leaf_matches_the_reference(both):
    errs = jax.tree.map(rel, both["p_grads"], both["r_grads"])
    worst = max(jax.tree.leaves(errs))
    assert worst < 1e-5, errs


def test_selected_sets_equal_the_references_exactly(both):
    p_sets, r_sets = np.asarray(both["p_sets"]), np.asarray(both["r_sets"])
    assert p_sets.shape == r_sets.shape == (2, ROWS, T, T)
    assert np.array_equal(p_sets != 0, r_sets)
    per_query = (p_sets != 0).sum(-1)
    topk = CASES[both["case"]]
    assert np.array_equal(per_query[0, 0], np.minimum(np.arange(T) + 1, topk))


def test_indexer_leaves_read_gradient_exactly_zero(both):
    for grads in (both["p_grads"], both["r_grads"]):
        for i in range(2):
            attn = grads[f"layer_{i}"]["attn"]
            for name in ("idx_wq", "idx_wk", "idx_ww", "idx_k_norm"):
                for leaf in jax.tree.leaves(attn[name]):
                    assert np.all(np.asarray(leaf) == 0.0), name
            assert float(jnp.linalg.norm(attn["wq"])) > 0


@pytest.mark.parametrize("levels", [2, 5, 1000])
@pytest.mark.parametrize("topk", [8, 100])
def test_select_topk_is_exact_under_ties(topk, levels):
    """Scores quantised to a few levels (and exact zeros of both signs)
    tie at the threshold; the lower index wins, as in the reference."""
    rng = np.random.default_rng(levels * 1000 + topk)
    scores = rng.integers(-levels, levels + 1, (2, T, T)).astype(np.float32)
    scores = np.where(scores == 0, rng.choice([0.0, -0.0], scores.shape),
                      scores * np.float32(0.37))
    got = np.asarray(M.select_topk(jnp.asarray(scores), topk)) != 0
    want = np.stack([np.asarray(REF.selected(jnp.asarray(s), 0, topk))
                     for s in scores])
    assert np.array_equal(got, want)
    assert np.array_equal(got.sum(-1)[0], np.minimum(np.arange(T) + 1, topk))
    # a block of queries against the keys up to its last query
    block = np.asarray(M.select_topk(jnp.asarray(scores[:, 40:72, :72]),
                                     topk, 40)) != 0
    assert np.array_equal(block, want[:, 40:72, :72])
    assert not want[:, 40:72, 72:].any()


def test_unequal_mrope_ids_match_the_reference():
    cfg, module = sizes()
    variables = REF.init(jax.random.key(0), cfg)
    ids, _ = rows()
    keys = jax.random.split(jax.random.key(9), 2)
    pos = jnp.stack([jnp.broadcast_to(jnp.arange(T), (ROWS, T))]
                    + [jax.random.randint(k, (ROWS, T), 0, 40) for k in keys])
    got = module.apply(variables, ids, position_ids=pos)[..., :VOCAB]
    want = REF.forward(variables, ids, cfg, position_ids=pos)
    assert rel(got, want) < 1e-5
    plain = REF.forward(variables, ids, cfg)
    assert rel(plain, want) > 1e-3  # the ids matter


def _expert_layer(held):
    _, module = sizes(held=held, layers=1)
    return M.HeldExperts(module.config)


def _seeded_layer(held=(2, 3)):
    """The expert layer holding ``held``, seeded weights for it and a
    seeded input."""
    cfg, _ = sizes(held=held, layers=1)
    params = REF.init(jax.random.key(4), cfg)["params"]["layer_0"]["moe"]
    g = jax.random.normal(jax.random.key(5), (ROWS, T, 64), jnp.float32)
    return _expert_layer(held), params, g


def _uncut_layer(model):
    """The reference, its configuration holding all 16 experts, and the
    program's expert layer by the experts held, for each model built on
    the block."""
    if model == "keye":
        cfg, _ = sizes(held=tuple(range(16)), layers=1)
        return REF, cfg, _expert_layer
    if model == "sdar":
        import test_block_diffusion_lm as D
    else:
        import test_mixed_attention_lm as D

    cfg, _ = D.sizes(held=tuple(range(16)))
    return D.REF, cfg, lambda held: M.HeldExperts(D.sizes(held=held)[1].config)


@pytest.mark.parametrize("model,n_shares", [
    ("keye", 8), ("keye", 4), ("sdar", 8), ("sdar", 4), ("laguna", 16),
    ("laguna", 4)])
def test_the_shares_of_the_expert_layer_sum_to_the_uncut_layer(model,
                                                               n_shares):
    """Each share routes over all 16 experts and computes its own; the
    shares' outputs summed are the reference's layer with every expert.
    Where the layer has a shared expert (the third model: sigmoid scores,
    gates scaled by 2.5), every chip computes it alike and it counts
    ONCE: the shares' routed parts and one shared expert are the uncut
    layer, and a shared expert a share is not."""
    ref, cfg, layer_holding = _uncut_layer(model)
    # the last layer holds experts in every model
    last = f"layer_{cfg['num_hidden_layers'] - 1}"
    whole_layer = ref.init(jax.random.key(4), cfg)["params"][last]
    whole = whole_layer["moe"]
    g = jax.random.normal(jax.random.key(5), (ROWS, T, 64), jnp.float32)
    ein = lambda eq, a, b: jnp.einsum(eq, a, b, precision="highest")
    want = jnp.stack([ref._experts_row(whole, row, ref._sizes(cfg), ein, None)
                      for row in g])
    shared = 0.0
    if "shared" in whole_layer:
        config = layer_holding((0,)).config
        assert (config.scoring, config.routed_scale) == ("sigmoid", 2.5)
        shared = M.SwiGLU(config, config.shared_expert_width,
                          "shared_expert").apply(
            {"params": whole_layer["shared"]}, g)
        want = want + jnp.stack([ref._swiglu(whole_layer["shared"], row, ein)
                                 for row in g])
        assert rel(shared, want) > 1e-2
    per = 16 // n_shares
    total = 0.0
    for share in range(n_shares):
        held = tuple(range(share * per, (share + 1) * per))
        params = {"router": whole["router"],
                  **{k: whole[k][jnp.asarray(held)]
                     for k in ("w_gate", "w_up", "w_down")}}
        out = layer_holding(held).apply({"params": params}, g)
        total = total + out
        assert rel(out, want) > 1e-2  # one share alone is not the layer
    assert rel(total + shared, want) < 1e-5
    if "shared" in whole_layer:
        assert rel(total + n_shares * shared, want) > 1e-2


@pytest.mark.parametrize("forced", [(2,), (2, 3)])
def test_no_pair_is_dropped_when_the_router_forces_held_experts(forced):
    """Every token chooses the forced held experts (and experts held
    elsewhere): all tokens' rows land on them, 4 (one expert) and 8
    times (two) the rows an even router sends here, the second past
    what the smaller row buffer holds; none is dropped, and the output
    still is the reference's."""
    cfg, _ = sizes(held=(2, 3), layers=1)
    params = REF.init(jax.random.key(4), cfg)["params"]["layer_0"]["moe"]
    g = jnp.abs(jax.random.normal(jax.random.key(5), (ROWS, T, 64))) + 0.1
    router = jnp.zeros((64, 16)).at[:, 3].set(-1.0)
    router = router.at[:, jnp.asarray(forced)].set(1.0)
    params = {**params, "router": router}
    layer = _expert_layer((2, 3))
    out, state = layer.apply({"params": params}, g, mutable=["moe_metrics"])
    sown = state["moe_metrics"]
    assert np.array_equal(np.asarray(sown["expert_rows"][0]),
                          [ROWS * T, ROWS * T * (3 in forced)])
    assert float(sown["routed"][0]) == ROWS * T * len(forced)
    assert float(sown["dropped"][0]) == 0.0
    ein = lambda eq, a, b: jnp.einsum(eq, a, b, precision="highest")
    want = jnp.stack([REF._experts_row(params, row, REF._sizes(cfg), ein, None)
                      for row in g])
    assert rel(out, want) < 1e-5


# -- the loop over chunks of the sorted pairs, under planted loads --------


@pytest.mark.parametrize("n_pairs, n_held, n_routed, want", [
    (131_072, 16, 128, (32_768, 4)),   # the 8k cells: 16,384 tokens x 8
    (131_072, 128, 128, (131_072, 1)),  # a holder of every expert
    (1_024, 3, 16, (384, 3)),          # 192 expected, the last chunk short
    (10, 1, 128, (2, 5)),              # an expected share under one pair
])
def test_a_chunk_is_twice_the_pairs_the_layer_expects_to_hold(
        n_pairs, n_held, n_routed, want):
    assert ROW_CHUNKS_RULE(n_pairs, n_held, n_routed) == want


HELD4, ELSEWHERE, LOAD_CHUNK = (2, 3, 5, 7), (8, 9, 10, 11), 64
# case -> [(tokens, the four experts each of them chooses)]; the other
# tokens of the 256 choose four experts held elsewhere. 1,024 pairs in
# chunks of 64.
LOADS = {
    "no_held_pair": [],
    "under_one_chunk": [(40, (2, 8, 9, 10))],
    "a_multiple_of_the_chunk": [(64, (2, 3, 8, 9))],
    "one_row_over_a_multiple": [(64, (2, 3, 8, 9)), (1, (5, 8, 9, 10))],
    "an_expert_without_rows_between": [(50, (2, 5, 8, 9))],
    "every_pair_held": [(ROWS * T, HELD4)],
}


def _planted(load):
    """Input, norm gain and expert layer weights under which each token
    chooses the experts the load plants for it: the token's class is a
    large entry of its input, and the router's row of that entry lifts
    the class's four experts far above the rest."""
    cfg, _ = sizes(held=HELD4, layers=1)
    params = REF.init(jax.random.key(4), cfg)["params"]["layer_0"]["moe"]
    classes = [ELSEWHERE] + [experts for _, experts in load]
    of_token = np.repeat(np.arange(len(classes)),
                         [ROWS * T - sum(n for n, _ in load)]
                         + [n for n, _ in load])
    of_token = np.random.default_rng(0).permutation(of_token)
    x = 0.3 * jax.random.normal(jax.random.key(5), (ROWS * T, 64))
    x = x.at[jnp.arange(ROWS * T), of_token].set(6.0)
    router = np.array(params["router"])
    for c, experts in enumerate(classes):
        router[c, list(experts)] = 4.0
    gain = 1.0 + 0.1 * jax.random.normal(jax.random.key(6), (64,))
    want_rows = [sum(n for n, experts in load if e in experts)
                 for e in HELD4]
    return (cfg, x.reshape(ROWS, T, 64), gain,
            {**params, "router": jnp.asarray(router)}, want_rows)


@pytest.mark.parametrize("case", list(LOADS))
def test_the_chunked_layer_is_the_reference_at_every_load(case, monkeypatch):
    """Value and every gradient (the input's, the gain's of the norm in
    front, the router's, the three expert weights') of the layer whose
    loop runs 0, 1, 2, 3 and all 16 chunks, against the plain reference.
    With no held pair the layer adds exactly 0 and every gradient is
    exactly 0."""
    chunks_of(monkeypatch, LOAD_CHUNK)
    cfg, x, gain, params, want_rows = _planted(LOADS[case])
    layer = _expert_layer(HELD4)
    ein = lambda eq, a, b: jnp.einsum(eq, a, b, precision="highest")

    def prog(x, gain, p):
        out, state = layer.apply({"params": p}, M.rms_norm(x, gain, 1e-6),
                                 mutable=["moe_metrics"])
        return jnp.sum(jnp.sin(out) + out), (out, state["moe_metrics"])

    def ref(x, gain, p):
        out = jnp.stack([REF._experts_row(
            p, REF._rms_norm(row, gain, 1e-6), REF._sizes(cfg), ein, None)
            for row in x])
        return jnp.sum(jnp.sin(out) + out), out

    (_, (got, sown)), got_grads = jax.value_and_grad(
        prog, argnums=(0, 1, 2), has_aux=True)(x, gain, params)
    (_, want), want_grads = jax.value_and_grad(
        ref, argnums=(0, 1, 2), has_aux=True)(x, gain, params)

    assert np.array_equal(np.asarray(sown["expert_rows"][0]), want_rows)
    assert np.array_equal(
        np.asarray(sown["row_chunks"][0]),
        [-(-sum(want_rows) // LOAD_CHUNK), ROWS * T * 4 // LOAD_CHUNK])
    assert float(sown["routed"][0]) == sum(want_rows)
    assert float(sown["dropped"][0]) == 0.0
    if not sum(want_rows):
        for leaf in jax.tree.leaves((got, got_grads, want, want_grads)):
            assert np.all(np.asarray(leaf) == 0.0)
        return
    assert rel(got, want) < 1e-5
    errs = jax.tree.map(rel, got_grads, want_grads)
    assert max(jax.tree.leaves(errs)) < 1e-5, errs
    for leaf in jax.tree.leaves(want_grads):
        assert float(jnp.linalg.norm(leaf)) > 0  # a comparison of something


ATTN_KERNELS = ("sparse_attn_fwd", "sparse_attn_bwd_dq", "sparse_attn_bwd_dkv")


@pytest.fixture(scope="module")
def gradient_by_policy():
    """``{policy: ({kernel: calls in the gradient's jaxpr}, the
    gradient)}`` of the tiny two-layer model under the policy its layers
    are rematerialised with, and with ``nothing_saveable`` in its place;
    and under ``"names"`` the names the model's policy lists."""
    cfg, module = sizes()
    params = REF.init(jax.random.key(0), cfg)["params"]
    ids, labels = rows()
    loss_fn = resolve_loss("cross_entropy")
    policies, listed = jax.checkpoint_policies, set()
    by_names = policies.save_only_these_names

    def the_models_own(*names):
        listed.update(names)
        return by_names(*names)

    stand_ins = {"model": the_models_own,
                 "nothing_saveable": lambda *names: policies.nothing_saveable}
    patch, out = pytest.MonkeyPatch(), {"names": listed}
    for policy, stand_in in stand_ins.items():
        patch.setattr(policies, "save_only_these_names", stand_in)
        # a function of its own each time: a trace is cached by function
        grad = jax.grad(lambda p: jnp.sum(loss_fn(
            module.apply({"params": p}, ids), labels)))
        jaxpr = jax.make_jaxpr(grad)(params).jaxpr
        out[policy] = ({k: pallas_calls(jaxpr, k) for k in ATTN_KERNELS},
                       grad(params))
    patch.undo()
    return out


def test_each_attention_kernel_runs_once_a_layer_in_the_gradient(
        gradient_by_policy):
    """The remat's second forward pass launches no ``sparse_attn_fwd``:
    the backward kernels read the output and row statistics the first
    one left."""
    calls, grads = gradient_by_policy["model"]
    n = sum(name.startswith("layer_") for name in grads)
    assert n == 2 and calls == dict.fromkeys(ATTN_KERNELS, n)
    # the count sees a second forward where a policy keeps neither array
    assert gradient_by_policy["nothing_saveable"][0] == {
        **calls, "sparse_attn_fwd": 2 * n}


def test_keeping_the_attentions_results_changes_no_gradient_leaf(
        gradient_by_policy):
    assert gradient_by_policy["names"] == {M._MASK_NAME, *M.SAVED_NAMES}
    grads, recomputed = (gradient_by_policy[k][1]
                         for k in ("model", "nothing_saveable"))
    same = jax.tree.map(lambda a, b: bool(jnp.array_equal(a, b)),
                        grads, recomputed)
    assert all(jax.tree.leaves(same)), same
    # what the attention's backward kernels hand on is something
    assert all(float(jnp.linalg.norm(grads["layer_0"]["attn"][w])) > 0
               for w in ("wq", "wk", "wv"))


# -- the fused q/k pass (``ops/qk_norm_rope.py``) in the three models ---------


def _tiny_loss(model):
    """``(loss of the parameters, the parameters)`` of the tiny model of
    that name: this file's, ``test_block_diffusion_lm``'s,
    ``test_mixed_attention_lm``'s (gated) or
    ``test_latent_attention_lm``'s, on its own rows. A function of its
    own each call: a trace is cached by function."""
    if model == "keye":
        (cfg, module), (ids, labels) = sizes(), rows()
        loss_fn, ref, more = resolve_loss("cross_entropy"), REF, {}
    elif model == "sdar":
        import test_block_diffusion_lm as case
        (cfg, module), ids = case.sizes(), case.rows()
        loss_fn, ref, labels, more = case.LOSS, case.REF, ids, {
            "rngs": case.STREAM}
    else:
        if model == "laguna":
            import test_mixed_attention_lm as case
        else:
            import test_latent_attention_lm as case
        (cfg, module), (ids, labels) = case.sizes(), case.rows()
        loss_fn, ref, more = case.LOSS, case.REF, {}
    params = ref.init(jax.random.key(0), cfg)["params"]
    return (lambda p: jnp.sum(loss_fn(
        module.apply({"params": p}, ids, **more), labels))), params


def _transposes(jaxpr):
    """``(permutation, operand's shape)`` of every ``transpose`` of the
    jaxpr and of every jaxpr inside it."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "transpose":
            found.append((tuple(eqn.params["permutation"]),
                          eqn.invars[0].aval.shape))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _transposes(sub)
    return found


@pytest.fixture(scope="module", params=["keye", "sdar", "laguna"])
def fused_and_plain(request):
    """The tiny model's loss and gradient with the fused op and with the
    op's plain spelling (``test_qk_norm_rope.plain``: ``rms_norm`` +
    ``_rotate`` + cast + ``heads_first``) in its place, and the jaxpr of
    the loss and gradient as the model builds them."""
    from test_qk_norm_rope import plain

    loss, params = _tiny_loss(request.param)
    # one trace gives the jaxpr and, compiled, the numbers
    traced = jax.jit(jax.value_and_grad(loss)).trace(params)
    fused = traced.lower().compile()(params)
    patch = pytest.MonkeyPatch()
    patch.setattr(M.fused, "qk_norm_rope", plain)
    spelled = jax.jit(jax.value_and_grad(_tiny_loss(request.param)[0]))(
        params)
    patch.undo()
    layers = sum(name.startswith("layer_") for name in params)
    return dict(fused=fused, plain=spelled, jaxpr=traced.jaxpr.jaxpr,
                layers=layers)


def test_the_fused_q_k_pass_is_its_plain_spelling_in_the_model(
        fused_and_plain):
    (loss, grads), (p_loss, p_grads) = (fused_and_plain[k]
                                        for k in ("fused", "plain"))
    assert abs(float(loss - p_loss)) < 1e-5 * abs(float(p_loss))
    errs = jax.tree.map(rel, grads, p_grads)
    assert max(jax.tree.leaves(errs)) < 1e-5, errs
    assert all(float(jnp.linalg.norm(layer["attn"][w])) > 0
               for name, layer in grads.items() if name.startswith("layer_")
               for w in ("q_norm", "k_norm", "wq", "wk", "wv"))


def test_the_fused_kernels_run_twice_forward_and_once_backward_a_layer(
        fused_and_plain):
    """Forward, the remat's forward again (its results are the
    attention's operands, which no policy keeps), and one backward."""
    jaxpr, n = fused_and_plain["jaxpr"], fused_and_plain["layers"]
    assert pallas_calls(jaxpr, "qk_norm_rope_fwd") == 2 * n
    assert pallas_calls(jaxpr, "qk_norm_rope_bwd") == n


def _shapes(jaxpr):
    """The shape of every array an equation of the jaxpr, or of a jaxpr
    inside it, produces."""
    found = []
    for eqn in jaxpr.eqns:
        found += [v.aval.shape for v in eqn.outvars
                  if hasattr(v.aval, "shape")]
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _shapes(sub)
    return found


# the turns between [b, T, heads, 128] and heads first, either way, and
# the swap of a key or value array's token and head axes
_TURNS = {(0, 2, 3, 1, 4), (0, 3, 1, 2, 4), (0, 2, 1, 3)}


@pytest.mark.parametrize("model", ["keye", "sdar", "laguna", "joyai"])
def test_nothing_is_turned_on_either_side_of_an_attention_kernel(model):
    """q, k and v reach the kernels as the fused op writes them, their
    cotangents leave as the kernels write them, and ``o`` leaves flat,
    ``[b, T, heads * 128]``, for the gate and ``Wo`` to read as it lies
    (its cotangent comes back so): the traced loss and gradient of each
    tiny grouped-query model (a gated one among them) transposes no
    array of 128 lanes between tokens first and heads first, and holds
    no array ``[b, T, heads, 128]`` at all, which on the TPU is a copy
    of the whole array away from the flat one (the gate's product and
    the row statistics' sum go through ``by_head``). The latent one
    keeps its output's turn and nothing else."""
    loss, params = _tiny_loss(model)
    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss))(params).jaxpr
    turns = [perm for perm, shape in _transposes(jaxpr) if shape[-1] == 128]
    if model == "joyai":
        # one head a grid step: latent attention keeps o5 heads first,
        # and its module's turn (forward, the remat's, the cotangent's)
        # a layer is all that is left (ops/latent_attention.py)
        layers = sum(name.startswith("layer_") for name in params) + 1
        assert sorted(set(turns) & _TURNS) == [(0, 2, 3, 1, 4),
                                               (0, 3, 1, 2, 4)]
        assert turns.count((0, 3, 1, 2, 4)) == 2 * layers
        assert turns.count((0, 2, 3, 1, 4)) == layers
        return
    assert not set(turns) & _TURNS, turns
    rows = {shape[:2] for shape in _shapes(jaxpr)
            if len(shape) == 3 and shape[-1] % 128 == 0 and shape[-1] > 128}
    assert rows  # the flat arrays are there: [b, T, heads * 128]
    by_heads = [shape for shape in _shapes(jaxpr)
                if len(shape) == 4 and shape[-1] == 128
                and shape[:2] in rows]
    assert not by_heads, by_heads
    # the probe sees both where they are: the parent's spelling
    b, t = next(iter(rows))
    turned = jax.make_jaxpr(lambda o5: jnp.transpose(
        o5, (0, 3, 1, 2, 4)).reshape(b, t, 2, 128))(
            jnp.zeros((b, 1, 2, t, 128))).jaxpr
    assert {perm for perm, _ in _transposes(turned)} & _TURNS
    assert (b, t, 2, 128) in _shapes(turned)


def _float_arrays_by_pair(jaxpr, n_pairs):
    """Every float array with a row for each chosen pair (rank 2 or
    more, ``n_pairs`` or more rows) that an equation of the jaxpr, or of
    a jaxpr inside it, produces."""
    found = []
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            a = v.aval
            if (jnp.issubdtype(a.dtype, jnp.floating) and a.ndim >= 2
                    and a.shape[0] >= n_pairs):
                found.append((eqn.primitive.name, a.shape))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _float_arrays_by_pair(sub, n_pairs)
    return found


def test_no_pass_of_the_layer_holds_a_row_for_every_chosen_pair(monkeypatch):
    """Neither the layer nor its gradient makes a float array of
    ``[tokens x k, ...]``: the rows exist a chunk at a time."""
    chunks_of(monkeypatch, LOAD_CHUNK)
    cfg, x, gain, params, _ = _planted(LOADS["under_one_chunk"])
    layer = _expert_layer(HELD4)
    loss = lambda x, p: jnp.sum(jnp.sin(layer.apply({"params": p}, x)))
    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(x, params)
    assert _float_arrays_by_pair(jaxpr.jaxpr, ROWS * T * 4) == []
    # the probe sees such an array where there is one
    gathered = jax.make_jaxpr(lambda x: x.reshape(-1, 64)[
        jnp.arange(ROWS * T * 4) // 4] * 2.0)(x)
    assert _float_arrays_by_pair(gathered.jaxpr, ROWS * T * 4)


def test_a_pair_outside_its_experts_group_counts_as_dropped():
    """``dropped`` is counted from the sorted pairs against the group
    sizes the grouped product is given: sizes that are right cover every
    held pair, a group one short loses the pairs that slide into the
    next expert's group, and rows past the last group are not covered."""
    sorted_expert = jnp.asarray([0, 0, 0, 1, 1, 2, 2, 2])  # 2: held elsewhere
    assert float(M.pairs_covered(sorted_expert, jnp.asarray([3, 2]))) == 5.0
    assert float(M.pairs_covered(sorted_expert, jnp.asarray([2, 3]))) == 4.0
    assert float(M.pairs_covered(sorted_expert, jnp.asarray([3, 1]))) == 4.0
    assert float(M.pairs_covered(sorted_expert, jnp.asarray([3, 5]))) == 5.0
    # chunk by chunk, as the layer counts it: each chunk's pairs against
    # the overlap of the experts' ranges with the chunk
    rows, halves = jnp.asarray([3, 2]), sorted_expert.reshape(2, 4)
    by_chunk = lambda sizes_of: sum(
        float(M.pairs_covered(halves[c], sizes_of(c))) for c in range(2))
    assert np.array_equal(M.chunk_rows(rows, 4, 4), [0, 1])
    assert by_chunk(lambda c: M.chunk_rows(rows, 4 * c, 4)) == 5.0
    # sizes that forget where the chunk starts put the second chunk's
    # pairs in the first expert's group
    assert by_chunk(lambda c: jnp.minimum(rows, 4)) == 4.0


def test_chunk_sizes_derived_wrongly_show_as_dropped_pairs(monkeypatch):
    """The layer's ``dropped`` is counted against the group sizes its
    loop gives the products, chunk by chunk."""
    chunks_of(monkeypatch, 64)
    layer, params, g = _seeded_layer()
    sown = lambda: layer.apply({"params": params}, g,
                               mutable=["moe_metrics"])[1]["moe_metrics"]
    right = sown()
    assert float(right["routed"][0]) > 64 and float(right["dropped"][0]) == 0
    monkeypatch.setattr(M, "chunk_rows",
                        lambda rows, start, chunk: jnp.minimum(rows, chunk))
    assert float(sown()["dropped"][0]) > 0


@pytest.mark.parametrize("fault", ["no_selection", "shifted_share",
                                   "no_renorm"])
def test_a_planted_fault_changes_the_references_logits(fault):
    cfg, _ = sizes()
    variables = REF.init(jax.random.key(0), cfg)
    ids, _ = rows()
    sound = REF.forward(variables, ids, cfg)
    assert rel(REF.forward(variables, ids, {**cfg, "fault": fault}),
               sound) > 1e-4


def test_a_bad_configuration_is_refused():
    with pytest.raises(ValueError, match="experts_held"):
        M.keye_vl2_lm(experts_held=(0, 0))
    # 72 frequency pairs where a head of 128 has 64; (16, 16, 16) turns
    # the first 96 dims and passes the rest: partial rotary
    with pytest.raises(ValueError, match="rotary sections"):
        M.keye_vl2_lm(mrope_section=(16, 24, 32))
    assert M.keye_vl2_lm(mrope_section=(16, 16, 16)).config.layers[0] \
        .rotary.sections == (16, 16, 16)


# -- through the trainers ------------------------------------------------


def _train(n_devices, iters=2, layers=2, **kwargs):
    """``train_distributed`` on the first ``n_devices`` CPU devices over
    the same four rows; the records the hook got, the result, the bus."""
    from sparktorch_tpu.obs.telemetry import Telemetry
    from sparktorch_tpu.parallel.mesh import build_mesh
    from sparktorch_tpu.train.sync import train_distributed
    from sparktorch_tpu.utils.serde import ModelSpec

    _, module = sizes(layers=layers)
    spec = ModelSpec(module=module, loss="cross_entropy", optimizer="adam",
                     optimizer_params={"lr": 1e-3}, input_shape=(T,))
    k1, k2 = jax.random.split(jax.random.key(3))
    ids = np.asarray(jax.random.randint(k1, (4, T), 0, VOCAB), np.float32)
    labels = np.asarray(jax.random.randint(k2, (4, T), 0, VOCAB), np.float32)
    tele, records = Telemetry(run_id="test"), []
    result = train_distributed(
        spec, ids, labels=labels, iters=iters, seed=0,
        mesh=build_mesh(devices=jax.devices()[:n_devices]),
        metrics_hook=records.append, telemetry=tele, **kwargs)
    return records, result, tele


@pytest.fixture(scope="module")
def one_and_two_shards():
    return _train(1, steps_per_call=1), _train(2, steps_per_call=1)


@pytest.mark.parametrize("field", ["loss", "grad_norm", "examples",
                                   "moe_rows"])
def test_dp2_on_the_cpu_mesh_equals_one_shard_on_the_same_rows(
        one_and_two_shards, field):
    (one, _, _), (two, _, _) = one_and_two_shards
    assert len(one) == len(two) == 2
    for a, b in zip(one, two):
        assert a[field] == pytest.approx(b[field], rel=2e-5)


def test_dp2_ends_on_the_parameters_of_one_shard(one_and_two_shards):
    (_, one, _), (_, two, _) = one_and_two_shards
    # Adam's first steps move every weight by lr whatever the gradient's
    # size, so a leaf whose gradient is noise about zero may differ by a
    # step; the loss of the second step (above) is the tight comparison
    errs = jax.tree.map(rel, one.params, two.params)
    assert max(jax.tree.leaves(errs)) < 2e-2, errs


def test_counters_and_gauges_reach_the_records_and_the_bus(monkeypatch):
    chunks_of(monkeypatch, 96)
    records, _, tele = _train(1, iters=4, steps_per_call=2)
    assert len(records) == 4
    for r in records:
        assert r["moe_pairs_dropped"] == 0.0 and r["moe_drop_fraction"] == 0.0
        # 4 rows x 128 tokens x 4 choices, 2 of 16 experts held, 2 layers
        assert 0 < r["moe_rows"] < 2 * 4 * T * 4
        assert r["moe_rows_max"] >= r["moe_rows_mean"] > 0
        # each layer's rows rounded up to a chunk of 96
        assert 0 <= r["moe_row_chunks"] - r["moe_rows"] / 96 < 2
    keys = records[0]["leaf_grad_norm_keys"]
    norms = dict(zip(keys, records[0]["leaf_grad_norms"]))
    assert all(v == 0.0 for k, v in norms.items() if ".idx_" in k)
    assert norms["layer_0.attn.wq"] > 0
    assert tele.gauge_value("train.sparse_attn.topk") == 32
    assert tele.gauge_value("train.moe.experts_held") == 2
    assert tele.gauge_value("train.moe.experts_routed") == 16
    assert tele.counter_value("train.moe.pairs_dropped") == 0.0
    assert tele.counter_value("train.moe.rows") == pytest.approx(
        sum(r["moe_rows"] for r in records))
    assert tele.counter_value("train.moe.row_chunks") == sum(
        r["moe_row_chunks"] for r in records)
    assert tele.gauge_value("train.moe.row_chunks_possible") \
        == 2 * -(-4 * T * 4 // 96)


@pytest.mark.parametrize("n_devices", [1, 2])
def test_row_chunks_in_the_records_are_the_chunks_the_rows_take(
        monkeypatch, n_devices):
    """One expert layer, so a step's ``moe_rows`` are that layer's: the
    chunks its loop ran are those rows rounded up to a chunk (on two
    shards each rounds up its own), of the chunks that all chosen pairs
    would take."""
    chunks_of(monkeypatch, 96)
    records, _, tele = _train(n_devices, iters=3, layers=1, steps_per_call=1)
    assert len(records) == 3
    for r in records:
        whole = -(-r["moe_rows"] // 96)
        assert whole <= r["moe_row_chunks"] <= whole + n_devices - 1
    assert tele.counter_value("train.moe.row_chunks") == sum(
        r["moe_row_chunks"] for r in records)
    assert tele.gauge_value("train.moe.row_chunks_possible") \
        == n_devices * -(-(4 // n_devices) * T * 4 // 96)
    share = (tele.counter_value("train.moe.row_chunks")
             / (3 * tele.gauge_value("train.moe.row_chunks_possible")))
    assert 0 < share < 0.5  # 2 of 16 experts held


def test_row_tiles_in_the_records_are_the_tiles_the_kernels_visit(
        monkeypatch):
    """``row_tiles`` against a count on the host from ``expert_rows``:
    every (row tile, expert) pair with a row in common, chunk by chunk
    of 64 pairs in tiles of 16, of the tiles the chunks that ran hold;
    and through the trainer the record's ``moe_row_tiles`` and the two
    gauges on the bus; ``rows_summed``, counted from the visits the sums
    back walk, is ``moe_rows`` on every step."""
    from sparktorch_tpu.ops import grouped_mlp as G

    chunk, tile = 64, 16
    chunks_of(monkeypatch, chunk)
    monkeypatch.setattr(G, "_MIN_ROW_TILE", tile)
    layer, params, g = _seeded_layer()
    sown = layer.apply({"params": params}, g,
                       mutable=["moe_metrics"])[1]["moe_metrics"]
    rows = np.asarray(sown["expert_rows"][0])
    ends = np.cumsum(rows)
    trips = -(-ends[-1] // chunk)
    visits = sum(
        (min(hi, (c + 1) * chunk) - 1) // tile - max(lo, c * chunk) // tile + 1
        for c in range(trips) for lo, hi in zip(ends - rows, ends)
        if max(lo, c * chunk) < min(hi, (c + 1) * chunk))
    assert np.array_equal(sown["row_tiles"][0],
                          [visits, trips * (chunk // tile)])
    assert -(-ends[-1] // tile) <= visits < trips * (chunk // tile)
    # the sums back add every held pair's row, of the chunks' rows
    assert np.array_equal(sown["rows_summed"][0], [ends[-1], trips * chunk])

    records, _, tele = _train(1, iters=3, layers=1, steps_per_call=1)
    for r in records:
        whole = -(-r["moe_rows"] // tile)
        # a tile more for each expert after the first, each trip
        assert whole <= r["moe_row_tiles"] <= whole + r["moe_row_chunks"]
        assert r["moe_rows_summed"] == r["moe_rows"] > 0
    assert tele.gauge_value("train.moe.rows_summed") \
        == records[-1]["moe_rows_summed"]
    assert tele.gauge_value("train.moe.rows_moved") \
        == records[-1]["moe_row_chunks"] * chunk
    assert tele.gauge_value("train.moe.row_tiles_visited") \
        == records[-1]["moe_row_tiles"]
    assert tele.gauge_value("train.moe.row_tiles") \
        == records[-1]["moe_row_chunks"] * (chunk // tile)


def test_rows_fetched_in_the_records_are_the_live_tiles_rows(monkeypatch):
    """``rows_fetched`` against a count on the host from ``expert_rows``:
    chunk by chunk of 64 pairs in tiles of 16, the tiles that hold a
    held pair, whole, of the rows of the chunks that ran; and through
    the trainer the record's ``moe_rows_fetched`` (at least
    ``moe_rows``, under a tile more a trip) and the gauge on the bus
    beside ``train.moe.rows_moved``."""
    from sparktorch_tpu.ops import grouped_mlp as G

    chunk, tile = 64, 16
    chunks_of(monkeypatch, chunk)
    monkeypatch.setattr(G, "_MIN_ROW_TILE", tile)
    layer, params, g = _seeded_layer()
    sown = layer.apply({"params": params}, g,
                       mutable=["moe_metrics"])[1]["moe_metrics"]
    held = int(np.asarray(sown["expert_rows"][0]).sum())
    trips = -(-held // chunk)
    assert held % chunk % tile  # the last live tile is partial
    fetched = (trips - 1) * chunk + -(-(held - (trips - 1) * chunk) // tile
                                      ) * tile
    assert np.array_equal(sown["rows_fetched"][0], [fetched, trips * chunk])
    assert held < fetched < held + tile

    records, _, tele = _train(1, iters=3, layers=1, steps_per_call=1)
    for r in records:
        assert 0 < r["moe_rows"] <= r["moe_rows_fetched"] \
            < r["moe_rows"] + tile * r["moe_row_chunks"]
        assert r["moe_rows_fetched"] % tile == 0
    assert tele.gauge_value("train.moe.rows_fetched") \
        == records[-1]["moe_rows_fetched"]
    assert tele.gauge_value("train.moe.rows_fetched") \
        <= tele.gauge_value("train.moe.rows_moved")


def test_the_gspmd_and_pipeline_trainers_refuse_the_model():
    import optax

    from sparktorch_tpu.parallel.mesh import MeshConfig, build_mesh
    from sparktorch_tpu.train.sharded import (create_sharded_state,
                                              make_sharded_train_step)
    from sparktorch_tpu.train.sync import train_distributed
    from sparktorch_tpu.utils.serde import ModelSpec

    _, module = sizes()
    spec = ModelSpec(module=module, loss="cross_entropy", optimizer="adam",
                     optimizer_params={"lr": 1e-3}, input_shape=(T,))
    mesh = build_mesh(devices=jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="GSPMD.*Pallas kernel"):
        create_sharded_state(spec, mesh, jax.random.key(0),
                             jnp.zeros((2, T), jnp.float32))
    with pytest.raises(NotImplementedError, match="GSPMD"):
        make_sharded_train_step(module.apply, resolve_loss("cross_entropy"),
                                optax.adam(1e-3), mesh, state_shardings=())
    pp_mesh = build_mesh(MeshConfig(dp=1, pp=2), devices=jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="pipeline"):
        train_distributed(spec, np.zeros((4, T), np.float32),
                          labels=np.zeros((4, T), np.float32), mesh=pp_mesh,
                          iters=1)


def _poisoned_grouped_kernels(monkeypatch, met):
    """``ops/grouped_mlp.py``'s kernels and its sum back with NaN in
    every row tile they do not visit (of each array they read) and every
    block they do not write (of each array they return): what the chip
    leaves there is whatever the memory held. ``met`` collects the arrays
    poisoned."""
    from sparktorch_tpu.ops import grouped_mlp as G

    def unvisited(table, a, tile):
        # the table's tiles repeat the last live visit's past it
        live_tiles = jnp.where(table[5][0] > 0, table[1][-1] + 1, 0)
        past = (jnp.arange(a.shape[0]) // tile >= live_tiles)[:, None]
        met.append(a.shape)
        return jnp.where(past, jnp.nan, a).astype(a.dtype)

    def poisoned(kernel, rows_in, rows_out):
        def call(table, *args, tile, **kw):
            args = [unvisited(table, a, tile) if i in rows_in else a
                    for i, a in enumerate(args)]
            out = kernel(table, *args, tile=tile, **kw)
            several = isinstance(out, (tuple, list))
            outs = [unvisited(table, a, tile) if i in rows_out else a
                    for i, a in enumerate(out if several else [out])]
            return outs if several else outs[0]
        return call

    for name, rows_in, rows_out in (
            ("gmm_in", (0,), (0,)), ("gmm_down", (0,), (0,)),
            ("gmm_bwd_hidden", (0, 1), (0, 1, 2)), ("gmm_dx", (0, 1), (0,)),
            ("gmm_dw_in", (0, 1, 2), ()), ("gmm_dw_down", (0, 1), ()),
            ("sum_back", (1,), ())):
        monkeypatch.setattr(G, name, poisoned(getattr(G, name), rows_in,
                                              rows_out))


def test_rows_and_tiles_the_grouped_kernels_skip_never_reach_a_sum(
        monkeypatch):
    """On the TPU a kernel's output block that no grid step writes holds
    what the memory held (``ragged_dot`` left the rows past its groups
    so, which the CPU's zero-fill hid: the chip's gradients read 25x the
    reference's, PR 27), and the grouped kernels visit only the row
    tiles with a held pair. Poison (NaN) every tile they do not visit,
    of everything they read and of every result they need not write, on
    the tail of the last chunk (150 or so held rows in chunks of 64,
    tiles of 16): the layer's output and every gradient must be what
    they are without the poison, and finite."""
    from sparktorch_tpu.ops import grouped_mlp as G

    chunks_of(monkeypatch, 64)
    monkeypatch.setattr(G, "_MIN_ROW_TILE", 16)
    layer, params, g = _seeded_layer()
    loss = lambda p, g: jnp.sum(jnp.sin(layer.apply({"params": p}, g)))
    want = jax.value_and_grad(loss, argnums=(0, 1))(params, g)
    met = []
    _poisoned_grouped_kernels(monkeypatch, met)
    got = jax.value_and_grad(loss, argnums=(0, 1))(params, g)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.all(np.isfinite(np.asarray(a)))
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
    # and the poison is there to be met: the arrays the six kernels of
    # one gradient read by row tile and the six they write so, and the
    # rows the two sums back read
    assert len(met) == (1 + 1) + (1 + 1) + (2 + 3) + (2 + 1) + 3 + 2 + 2
    sown = layer.apply({"params": params}, g,
                       mutable=["moe_metrics"])[1]["moe_metrics"]
    visited, held = np.asarray(sown["row_tiles"][0])
    assert 0 < visited < held  # tiles of the last chunk are skipped

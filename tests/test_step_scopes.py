"""The names a decoder's training step carries into a trace (PR 37):
``jax.named_scope``s on the attention's projections, its q/k norm and
rotary passes, the output gate, the embedding, the norms and the loss,
forward and backward, for each of the four kinds of attention; and the
scopes that were there before name exactly the operations they named.
Lowering only, tiny configurations, on the CPU (about 25 s in all)."""

import contextlib
import re

import optax
import pytest

import jax
import jax.numpy as jnp

from chipbench import dlm_scopes, hlm_scopes, lm_scopes, step_parts, \
    trace_scopes
from sparktorch_tpu.models import sparse_moe_lm as M
from sparktorch_tpu.parallel.mesh import build_mesh
from sparktorch_tpu.train.step import TrainState, make_train_step
from sparktorch_tpu.utils.data import DataBatch
from sparktorch_tpu.utils.losses import resolve_loss

T, VOCAB = 128, 96
TINY = dict(vocab_size=VOCAB, d_model=64, n_layers=1, n_kv_heads=2,
            n_routed_experts=16, experts_held=(2, 3), experts_per_token=4,
            expert_width=32, compute_dtype="float32")
NEW = ("attn_qkv", "attn_qk_rope", "attn_out", "attn_gate", "embed",
       "block_norm", "loss")
OLD = (*lm_scopes.SCOPES, *dlm_scopes.SCOPES, *hlm_scopes.SCOPES)


def _rule_model(kind):
    rotary = (M.Rotary(1e4, (64,)) if kind == "window" else
              M.Rotary(5e5, (32,), (64.0, 4096.0, 64.0, 1.0), 1.4158883))
    return M.laguna_lm(**TINY, layers=[M.LayerKind(kind, 4, rotary)],
                       window=96, shared_expert_width=32, dense_width=64)


# kind of attention -> (module, loss, the new scopes its step carries)
KINDS = {
    "learned_sparse": (lambda: M.keye_vl2_lm(
        **TINY, n_heads=4, idx_heads=2, idx_dim=16, idx_rope_dims=8,
        topk=32), "cross_entropy", set(NEW) - {"attn_gate"}),
    "block_diffusion": (lambda: M.sdar_moe_lm(
        **TINY, n_heads=4, mask_token_id=VOCAB - 1, block_length=4),
        "cross_entropy_weighted", set(NEW) - {"attn_gate"}),
    "full": (lambda: _rule_model("full"), "cross_entropy", set(NEW)),
    "window": (lambda: _rule_model("window"), "cross_entropy", set(NEW)),
}


class _NoScope(contextlib.nullcontext, contextlib.ContextDecorator):
    """``jax.named_scope`` is used as a decorator too."""


def _lowered(kind, without_new=False):
    """The lowered text of one training step of the kind's tiny model,
    with locations; ``without_new``: the new names' scopes are no-ops,
    which is the step as it was named before them."""
    build, loss, _ = KINDS[kind]
    module, tx = build(), optax.adam(1e-3)

    def init():
        params = module.init(jax.random.key(0), jnp.zeros((1, T)))["params"]
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          model_state={}, opt_state=tx.init(params),
                          rng=jax.random.key(1))

    S = jax.ShapeDtypeStruct
    batch = DataBatch(S((4, T), jnp.float32), S((4, T), jnp.float32),
                      S((4,), jnp.float32))
    patch = pytest.MonkeyPatch()
    if without_new:
        named = jax.named_scope
        patch.setattr(jax, "named_scope", lambda name: (
            _NoScope() if name in NEW else named(name)))
    try:
        step = make_train_step(module.apply, resolve_loss(loss), tx,
                               build_mesh(devices=jax.devices()[:1]),
                               mini_batch=2)
        return step.lower(jax.eval_shape(init), batch).as_text(
            debug_info=True)
    finally:
        patch.undo()


@pytest.fixture(scope="module")
def lowered():
    texts = {}

    def get(kind, without_new=False):
        if (kind, without_new) not in texts:
            texts[kind, without_new] = _lowered(kind, without_new)
        return texts[kind, without_new]

    return get


_DEF = re.compile(r"^(#loc\d+) = loc\((.*)\)$")
_NAMED = re.compile(r'^"([^"]*)"\(#loc\d+\)$')
_CALLSITE = re.compile(r"^callsite\((#loc\d+) at ")
_OP = re.compile(r"^(.*) loc\((#loc\d+)\)$")


def _operations(text):
    """``[(the operation's line without its location, its op_name)]`` of
    a lowered text with locations, in order."""
    defs = dict(m.groups() for m in map(_DEF.match, text.splitlines()) if m)

    def name(ref):
        body = defs.get(ref, "")
        if (m := _NAMED.match(body)):
            return m.group(1)
        if (m := _CALLSITE.match(body)):
            return name(m.group(1))
        return ""

    return [(m.group(1), name(m.group(2)))
            for m in map(_OP.match, text.splitlines()) if m]


@pytest.mark.parametrize("kind", list(KINDS))
def test_a_step_carries_the_new_scopes_forward_and_backward(lowered, kind):
    found = {(trace_scopes.scope_of(op_name),
              step_parts.scope_of(op_name, step_parts.SCOPES))
             for _line, op_name in _operations(lowered(kind))}
    want = KINDS[kind][2]
    for scope in want:
        assert ("forward", scope) in found, scope
        assert ("backward", scope) in found, scope
    # the gate is the gated kinds' alone; no other phase holds the names
    assert {s for _phase, s in found if s in NEW} == want
    assert {p for p, s in found if s in NEW} == {"forward", "backward"}


@pytest.mark.parametrize("kind", list(KINDS))
def test_no_older_scope_names_another_operation_than_before(lowered, kind):
    """Line for line the same program (the text without locations is
    the same text), and each line under the same one of the scopes that
    existed before, or under none of them, with and without the new
    names; without them no line carries one."""
    with_new = _operations(lowered(kind))
    without = _operations(lowered(kind, without_new=True))
    assert [line for line, _n in with_new] == [line for line, _n in without]
    assert not any(step_parts.scope_of(n, NEW) for _line, n in without)
    older = lambda n: (trace_scopes.scope_of(n), step_parts.scope_of(n, OLD))
    assert [older(n) for _line, n in with_new] \
        == [older(n) for _line, n in without]
    assert any(step_parts.scope_of(n, OLD) for _line, n in with_new)
    # the new names are siblings of the older ones, nested in none
    assert not any(step_parts.scope_of(n, NEW) and step_parts.scope_of(n, OLD)
                   for _line, n in with_new)

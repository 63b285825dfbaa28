"""A traced step as a sum of named parts: one table ``metric ->
scopes`` and one reduction of the trace a run.

The program names its work twice over. ``train/step.py`` names the
step's phases (``trace_scopes.py`` reads them: ``forward``,
``backward``, ``optimizer``, ...), and the model names its own parts
with ``jax.named_scope``s that reach the trace as path components of
each instruction's ``op_name``. This file reads the second kind for the
scopes of ``TABLE``: the decoder's attention projections
(``attn_qkv``, ``attn_out``, ``attn_gate``), its q/k norm and rotary
passes (``attn_qk_rope``), the embedding, the norms, the head and the
loss (``loss`` is the step's own, around the criterion, in every
model's step).

One reduction (``_reduce``) serves every metric here: each operation's
own time inside the window (``lm_scopes._own_times``), per chip and per
step, keyed by ``(phase, model scope)``. The phase is
``trace_scopes.scope_of``; the model scope is the INNERMOST component of
the ``op_name``, transformation wrappers taken off as ``lm_scopes``
takes them off, that is a scope of ``TABLE`` or of one of the three
older reader files (``lm_scopes.SCOPES``, ``dlm_scopes.SCOPES``,
``hlm_scopes.SCOPES``: read there, only named here), or none. The
compiler's ``ragged-dot`` calls carry neither phase nor scope and count
under ``moe_experts``, as ``lm_scopes`` counts them; a fusion left
without a name whose operations all carry one scope of ``TABLE`` counts
under that scope and under no phase. So the keys tile
the step, and ``tile`` gives the sum by part: the model's scopes, what
the forward and backward phases hold under no model scope (the numerator
of ``step_unnamed_pct``), the other phases, and what carries no phase at
all (``unscoped_ms``).

A further metric of a scope is a row of ``TABLE`` and a three-line file
under ``layer_metrics/``; the scopes of the three older files fold in
the same way (ROADMAP D17), their kernels' roofline shares apart.

A fusion has ONE ``op_name`` and counts whole under it; ``mixed_ms``
gives, by the scope a fusion counts under, the time of the fusions whose
called computations also hold operations of another scope: the error
bar of every number here. For the builder, not a metric.

A program without the scopes (one from before they existed, another
model's) gives ``None`` and raises nothing.
"""

from __future__ import annotations

from chipbench import (dlm_scopes, harness, hlm_scopes, lm_scopes, trace,
                       trace_scopes)

# metric -> the scopes whose time it sums; a scope the program does not
# carry adds nothing (``attn_gate``: the gated model alone)
TABLE = {
    "attn_projections_ms": ("attn_qkv", "attn_out", "attn_gate"),
    "attn_qk_rope_ms": ("attn_qk_rope",),
    "lm_head_loss_ms": ("lm_head", "loss"),
    "embed_norms_ms": ("embed", "block_norm"),
}
SCOPES = tuple(dict.fromkeys(s for row in TABLE.values() for s in row))
_OWN = frozenset(SCOPES)
# every scope of the model that some reader file knows
NAMED = frozenset((*SCOPES, *lm_scopes.SCOPES, *dlm_scopes.SCOPES,
                   *hlm_scopes.SCOPES))
UNNAMED = "unnamed"
_MODEL_PHASES = ("forward", "backward")


def scope_of(op_name, scopes=NAMED):
    """The innermost of ``scopes`` on an ``op_name`` path, or None."""
    found = None
    for part in (op_name or "").split("/"):
        while (m := lm_scopes._WRAPPED.match(part)):
            part = m.group(1)
        if part in scopes:
            found = part
    return found


def _instruction_scopes(names: dict) -> dict:
    """``{instruction: (phase, model scope or None)}``. A fusion the
    compiler left with no ``op_name`` of its own (the rotation of q and
    k: ``subtract_convert_fusion``, 9.4 ms a Laguna step) keeps no
    phase and takes the scope of ``SCOPES`` that ALL the named
    operations of its computations carry, if there is one."""
    out = {}
    for name, (op_name, inner) in names.items():
        scope = scope_of(op_name)
        if scope is None and name.startswith("ragged-dot"):
            scope = "moe_experts"
        elif not op_name:
            held = {scope_of(i) for i in inner if i}
            if len(held) == 1 and held <= _OWN:
                scope, = held
        out[name] = (trace_scopes.scope_of(op_name), scope)
    return out


def _part(phase, scope) -> str:
    """The part of ``tile`` a key of ``_reduce`` counts under."""
    return scope or (UNNAMED if phase in _MODEL_PHASES else phase)


def _reduce(ctx):
    """``{"by": {(phase, scope or None): ms a step a chip}, "steps":
    the steps in the window, "carried": the model scopes on the
    program's instructions, "mixed": see ``mixed_ms``}``, or None where
    the program's instructions carry neither phase nor scope; once per
    run."""
    if "_step_parts" in ctx:
        return ctx["_step_parts"]
    ctx["_step_parts"] = None
    if ctx.get("trace") is None or "steps_per_call" not in ctx["inputs"]:
        return None
    table, window = ctx["trace"], ctx["summary"]["window"]
    steps = len(trace.module_runs(table, window)) \
        * ctx["inputs"]["steps_per_call"]
    if not steps:
        return None
    try:
        xplane = trace.newest_xplane(
            harness.REPO / ".chipbench_trace" / ctx["cell"].name).read_bytes()
    except FileNotFoundError:
        return None
    names = trace_scopes.program_instructions(
        xplane, trace.dominant_module(table, trace.device_planes(table)[0]))
    keys = _instruction_scopes(names)
    if all(key == (trace_scopes.UNSCOPED, None) for key in keys.values()):
        return None
    times, chips = lm_scopes._own_times(table, window)
    per = 1.0 / (chips * 1e6 * steps)
    own = {}
    for name, t in times:
        own[name] = own.get(name, 0.0) + t * per
    by, mixed = {}, {}
    for name, ms in own.items():
        key = keys.get(name, (trace_scopes.UNSCOPED, None))
        by[key] = by.get(key, 0.0) + ms
        others = _held(names.get(name, ("", ()))[1]) - {_part(*key)}
        if others:
            row = mixed.setdefault(_part(*key), {"ms": 0.0, "with": {}})
            row["ms"] += ms
            for other in others:
                row["with"][other] = row["with"].get(other, 0.0) + ms
    ctx["_step_parts"] = {
        "by": by, "steps": steps, "mixed": mixed,
        "carried": {scope for _phase, scope in keys.values() if scope}}
    return ctx["_step_parts"]


def _model(ctx):
    """``_reduce`` of a program that carries a model's scopes, or None
    (BERT's step: phases alone)."""
    found = _reduce(ctx)
    return found if found and found["carried"] else None


def _held(inner_op_names) -> set:
    """The model scopes of the operations a fusion's computations hold;
    ``UNNAMED`` for one of the model's phases under no scope."""
    out = set()
    for op_name in inner_op_names:
        scope = scope_of(op_name)
        if scope or trace_scopes.scope_of(op_name) in _MODEL_PHASES:
            out.add(scope or UNNAMED)
    return out


def metric_ms(ctx, metric: str):
    """Milliseconds a step under the scopes of ``TABLE[metric]``
    together; None where the program carries none of them."""
    found = _model(ctx)
    if found is None or not found["carried"] & set(TABLE[metric]):
        return None
    return sum(ms for (_phase, scope), ms in found["by"].items()
               if scope in TABLE[metric])


def unnamed_pct(ctx):
    """Share of the step's device time in operations of the phases
    ``forward`` and ``backward`` whose ``op_name`` carries no scope of
    the model, in percent; None where the program carries none at all."""
    found = _model(ctx)
    if found is None or not ctx["summary"].get("busy_s"):
        return None
    unnamed = sum(found["by"].get((phase, None), 0.0)
                  for phase in _MODEL_PHASES)
    return 100.0 * unnamed * found["steps"] / (ctx["summary"]["busy_s"] * 1e3)


def unscoped_ms(ctx):
    """Milliseconds a step in operations under no phase of the step
    (copy waits, the resident rows' copies) and no scope of the model:
    ``trace_scopes``' ``unscoped`` less what a model scope names all the
    same (the compiler's ``ragged-dot`` calls, which ``moe_experts_ms``
    counts, and nameless fusions of one scope of ``TABLE``, which its
    metric counts), so disjoint from every other part of ``tile``. A
    program with no model scope (BERT's) reads ``trace_scopes``' number."""
    found = _reduce(ctx)
    return None if found is None else found["by"].get(
        (trace_scopes.UNSCOPED, None), 0.0)


def tile(ctx):
    """``{part: ms a step}`` whose values add up to the own time of
    every operation of the step: each model scope (whatever its phase),
    ``unnamed`` (the model's phases under no scope), each other phase
    under no scope, and ``unscoped``. None as ``_model``."""
    found = _model(ctx)
    if found is None:
        return None
    out = {}
    for key, ms in found["by"].items():
        out[_part(*key)] = out.get(_part(*key), 0.0) + ms
    return out


def mixed_ms(ctx):
    """``{part of ``tile`` a fusion counts under: {"ms": its fusions'
    time that also hold another scope's operations, "with": {that
    scope: ms}}}``, ms a step; None as ``_model``."""
    found = _model(ctx)
    return None if found is None else found["mixed"]

"""Device time of the expert exchange of a step whose expert layers are
cut over an ``ep`` axis: the collectives that take every member's rows to
every member's experts and the experts' sums back
(``models/sparse_moe_lm.py`` ``exchange_in`` / ``exchange_out``, scope
``moe_exchange``), and how much of it no other operation of the chip
covers.

A collective is known by its HLO OPCODE, not by its name: the
instruction names the compiler hands out say what an operation is only
by convention, and an asynchronous collective is a ``-start`` / ``-done``
pair or a fusion around one (``allreduce_exposed_ms`` matches names and
has read 0.025 ms of the dp=4 cell's all-reduce since PR 25). The opcodes
are read from the trace file's own copy of the compiled program (the
metadata plane's ``Hlo Proto``, as ``trace_scopes.py`` reads the
``op_name``s, with its wire-format reader); an instruction is the
exchange's when it, or an instruction of a computation it calls, is an
``all-gather`` or a ``reduce-scatter`` (in any asynchronous form) whose
``op_name`` carries the scope. Its device time is the union, a chip, of
its own segments on ``XLA Ops`` and of its spans on ``Async XLA Ops``;
exposed is that union less the own segments of every OTHER operation of
the chip.

A program without the scope (a step with no ``ep`` axis, the parent
commit's) gives ``None``: the readers then report nothing.
"""

from __future__ import annotations

from chipbench import harness, lm_scopes, step_parts, trace, trace_scopes
from chipbench.trace_scopes import _packed, _text, fields

SCOPE = "moe_exchange"
OPCODES = ("all-gather", "reduce-scatter")


def carries_scope(op_name) -> bool:
    """Whether ``SCOPE`` lies on an ``op_name`` path, under whatever
    transformations it was traced."""
    for part in (op_name or "").split("/"):
        while (m := lm_scopes._WRAPPED.match(part)):
            part = m.group(1)
        if part == SCOPE:
            return True
    return False


def opcodes(hlo_proto) -> dict:
    """``{instruction name: [(opcode, op_name) of the instruction, then of
    each instruction of the computations it calls]}`` of one serialized
    ``xla.HloProto``."""
    by_id, own, called = {}, {}, {}
    for f, module in fields(hlo_proto):
        if f != 1:  # HloProto.hlo_module
            continue
        for g, comp in fields(module):
            if g != 3:  # HloModuleProto.computations
                continue
            comp_id, inner = None, []
            for h, v in fields(comp):
                if h == 5:  # HloComputationProto.id
                    comp_id = v
                elif h == 2:  # HloComputationProto.instructions
                    name, opcode, op_name, calls = None, "", "", []
                    for k, w in fields(v):
                        if k == 1:  # HloInstructionProto.name
                            name = _text(w)
                        elif k == 2:  # .opcode
                            opcode = _text(w)
                        elif k == 7:  # .metadata -> OpMetadata.op_name
                            op_name = next((_text(x) for j, x in fields(w)
                                            if j == 2), "")
                        elif k == 38:  # .called_computation_ids
                            calls += ([w] if isinstance(w, int)
                                      else list(_packed(w)))
                    inner.append((opcode, op_name))
                    own[name], called[name] = (opcode, op_name), calls
            by_id[comp_id] = inner
    return {name: [pair, *(p for c in called[name] for p in by_id.get(c, []))]
            for name, pair in own.items()}


def program_opcodes(xplane_bytes, program: str) -> dict:
    """``opcodes`` of the program of that name on ``XLA Modules``, found
    as ``trace_scopes.program_instructions`` finds it."""
    protos = trace_scopes.hlo_protos(xplane_bytes)
    if program in protos:
        return opcodes(protos[program])
    stem = program.split("(", 1)[0]
    same = [p for n, p in protos.items() if n.split("(", 1)[0] == stem]
    return opcodes(max(same, key=len)) if same else {}


def is_exchange(pairs) -> bool:
    """Whether an instruction (``opcodes``' value) is, or wraps, an
    all-gather or a reduce-scatter under the scope."""
    return any(opcode.startswith(OPCODES) and carries_scope(op_name)
               for opcode, op_name in pairs)


def _reduce(ctx):
    """``{"ms": the exchange's device ms a step a chip, "exposed_ms":
    the part no other operation covers, "scope_ms": the own time of
    every operation under the scope}`` or None; once per run."""
    if "_ep_scopes" in ctx:
        return ctx["_ep_scopes"]
    ctx["_ep_scopes"] = None
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
    by_name = program_opcodes(
        xplane, trace.dominant_module(table, trace.device_planes(table)[0]))
    ours = {name for name, pairs in by_name.items() if is_exchange(pairs)}
    if not ours:
        return None
    scoped = {name for name, pairs in by_name.items()
              if carries_scope(pairs[0][1])}
    planes = trace.device_planes(table)
    total = exposed = under = 0.0
    for p in planes:
        mine, others = [], []
        for event, segs in trace.self_segments(
                table[p].get(trace.OPS_LINE, [])):
            name = trace.op_name(event)
            if trace._CONTAINER.match(name):
                continue
            (mine if name in ours else others).extend(segs)
            if name in scoped:
                under += trace.measure(trace.clip(segs, window))
        mine += [(s, s + d) for n, s, d in table[p].get(trace.ASYNC_LINE, [])
                 if trace.op_name(n) in ours]
        mine = trace.clip(trace.union(mine), window)
        total += trace.measure(mine)
        exposed += trace.measure(trace.subtract(mine, trace.union(others)))
    per = 1.0 / (len(planes) * 1e6 * steps)
    ctx["_ep_scopes"] = {"ms": total * per, "exposed_ms": exposed * per,
                         "scope_ms": under * per}
    return ctx["_ep_scopes"]


def exchange_ms(ctx):
    found = _reduce(ctx)
    return None if found is None else found["ms"]


def exposed_ms(ctx):
    found = _reduce(ctx)
    return None if found is None else found["exposed_ms"]


def unnamed_pct(ctx):
    """``step_parts.unnamed_pct`` of a program with the exchange's scope:
    the share of the step's device time in operations of the forward and
    backward phases under no scope that any reader file knows, this one
    among them, in percent; or None."""
    older, found = step_parts.unnamed_pct(ctx), _reduce(ctx)
    if older is None or found is None:
        return None
    before = step_parts.tile(ctx).get(step_parts.UNNAMED, 0.0)
    return older * (before - found["scope_ms"]) / before if before else older

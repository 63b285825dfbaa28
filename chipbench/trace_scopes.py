"""The step program's device time by the program's own phases.

``train/step.py`` puts ``jax.named_scope``s on the phases of a step
(``sample``, ``forward_loss``, ``grad_allreduce``, ``optimizer``,
``step_stats``); they reach the compiled program as the ``op_name``
metadata of each instruction. On the v5e the trace's ``XLA Ops`` events
carry no such stat (theirs are ``device_offset_ps``,
``device_duration_ps`` and ``Time Scale Multiplier``) and their names
are the instruction's HLO text without metadata (my chip run, PR 24).
But the trace file itself holds every executed program: the plane
``/host:metadata`` has one event metadata per program, named like the
program's events on ``XLA Modules`` (``jit_train_epoch(<id>)``), whose
stat ``Hlo Proto`` is the serialized ``xla.HloProto`` of the compiled
module. So the map from instruction name to ``op_name`` is read from
there, for nothing on the program's side.

``jax.profiler.ProfileData`` does not expose event metadata, so the few
fields needed are read from the protobuf wire format directly (field
numbers from ``tsl/profiler/protobuf/xplane.proto`` and
``xla/service/hlo.proto``); nothing else of the file is decoded, and the
lines of the device planes are skipped as one field each.

An operation counts under the scope its own ``op_name`` carries. A
fusion has one ``op_name`` (XLA gives it one of its instructions'), so
a fusion that mixes two phases counts whole under that one;
``mixed_fusions`` lists them.
"""

from __future__ import annotations

from chipbench import trace

METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"

# scope of an ``op_name``: the first path component that is one of the
# program's phases. The backward pass of ``forward_loss`` is what JAX
# names ``transpose(jvp(forward_loss))``.
SCOPES = ("sample", "forward", "backward", "grad_allreduce", "optimizer",
          "step_stats")
UNSCOPED = "unscoped"
_COMPONENT = {
    "sample": "sample",
    "forward_loss": "forward",
    "jvp(forward_loss)": "forward",
    "transpose(jvp(forward_loss))": "backward",
    "grad_allreduce": "grad_allreduce",
    "optimizer": "optimizer",
    "step_stats": "step_stats",
}


def scope_of(op_name) -> str:
    for part in (op_name or "").split("/"):
        if part in _COMPONENT:
            return _COMPONENT[part]
    return UNSCOPED


# -- protobuf wire format ----------------------------------------------------


def _varint(buf, i):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def fields(buf):
    """``(field number, value)`` of one message: an int for a varint or
    a fixed-width field, a memoryview for a length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = int.from_bytes(buf[i:i + size], "little"), i + size
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield number, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_values(plane, number):
    """The values of one ``map<int64, Message>`` field of a plane."""
    for f, entry in fields(plane):
        if f == number:
            for g, value in fields(entry):
                if g == 2:
                    yield value


def hlo_protos(xplane_bytes) -> dict:
    """``{program name: serialized xla.HloProto}`` from the metadata
    plane of an ``.xplane.pb``."""
    out = {}
    for f, plane in fields(memoryview(xplane_bytes)):
        if f != 1:  # XSpace.planes
            continue
        if not any(g == 2 and _text(v) == METADATA_PLANE
                   for g, v in fields(plane)):  # XPlane.name
            continue
        stat_id = None
        for meta in _map_values(plane, 5):  # XPlane.stat_metadata
            got = dict(fields(meta))  # XStatMetadata: id 1, name 2
            if _text(got.get(2, b"")) == HLO_PROTO_STAT:
                stat_id = got.get(1)
        for meta in _map_values(plane, 4):  # XPlane.event_metadata
            name = proto = None
            for g, v in fields(meta):
                if g == 2:  # XEventMetadata.name
                    name = _text(v)
                elif g == 5:  # XEventMetadata.stats
                    stat = dict(fields(v))  # XStat: metadata_id 1, bytes 6
                    if stat.get(1) == stat_id and 6 in stat:
                        proto = stat[6]
            if name is not None and proto is not None:
                out[name] = proto
    return out


def instructions(hlo_proto) -> dict:
    """``{instruction name: (op_name, [op_name of each instruction of
    the computations it calls])}`` of one serialized ``xla.HloProto``."""
    by_id, called = {}, {}
    out = {}
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
                    name, op_name, calls = None, "", []
                    for k, w in fields(v):
                        if k == 1:  # HloInstructionProto.name
                            name = _text(w)
                        elif k == 7:  # .metadata -> OpMetadata.op_name
                            op_name = next((_text(x) for j, x in fields(w)
                                            if j == 2), "")
                        elif k == 38:  # .called_computation_ids
                            calls += ([w] if isinstance(w, int) else
                                      [c for c in _packed(w)])
                    inner.append(op_name)
                    out[name] = op_name
                    called[name] = calls
            by_id[comp_id] = inner
    return {name: (op_name, [n for c in called[name]
                             for n in by_id.get(c, [])])
            for name, op_name in out.items()}


def _packed(view):
    i = 0
    while i < len(view):
        value, i = _varint(view, i)
        yield value


def program_instructions(xplane_bytes, program: str) -> dict:
    """``instructions`` of the program of that name on ``XLA Modules``
    (``jit_train_epoch(<id>)``); if the metadata plane spells the id
    otherwise, of the largest program of that function name."""
    protos = hlo_protos(xplane_bytes)
    if program in protos:
        return instructions(protos[program])
    stem = program.split("(", 1)[0]
    same = [p for n, p in protos.items() if n.split("(", 1)[0] == stem]
    return instructions(max(same, key=len)) if same else {}


# -- reductions -------------------------------------------------------------


def scope_seconds(table: dict, window, names: dict) -> dict:
    """``{scope: seconds}``: own time (``trace.self_segments``) of the
    operations on ``XLA Ops`` inside the window, by the scope of each
    operation's ``op_name``, averaged over the chips. ``names`` is what
    ``instructions`` gives. Operations that only contain others count
    nothing."""
    planes = trace.device_planes(table)
    totals = dict.fromkeys((*SCOPES, UNSCOPED), 0.0)
    for p in planes:
        for event, segs in trace.self_segments(
                table[p].get(trace.OPS_LINE, [])):
            name = trace.op_name(event)
            if trace._CONTAINER.match(name):
                continue
            t = trace.measure(trace.clip(segs, window))
            if t:
                totals[scope_of(names.get(name, ("",))[0])] += t
    return {k: v / len(planes) / 1e9 for k, v in totals.items()}


def mixed_fusions(names: dict) -> dict:
    """``{fusion name: sorted scopes}`` of the instructions whose called
    computations hold operations of more than one phase."""
    out = {}
    for name, (_op_name, inner) in names.items():
        scopes = {scope_of(n) for n in inner} - {UNSCOPED}
        if len(scopes) > 1:
            out[name] = sorted(scopes)
    return out


def step_ms(ctx, scope: str):
    """Milliseconds per step under one scope in a traced ``fit_sync``
    run, or ``None`` where there is nothing to read: no trace, no
    metadata plane, or a program whose operations carry none of the
    scopes (one from before PR 24). The reduction runs once per run and
    is kept on ``ctx``."""
    from chipbench import harness

    if ctx.get("trace") is None or "steps_per_call" not in ctx["inputs"]:
        return None
    if "_scope_ms" not in ctx:
        ctx["_scope_ms"] = None
        table, window = ctx["trace"], ctx["summary"]["window"]
        trace_dir = harness.REPO / ".chipbench_trace" / ctx["cell"].name
        try:
            xplane = trace.newest_xplane(trace_dir).read_bytes()
        except FileNotFoundError:
            return None
        program = trace.dominant_module(table, trace.device_planes(table)[0])
        names = program_instructions(xplane, program)
        steps = len(trace.module_runs(table, window)) \
            * ctx["inputs"]["steps_per_call"]
        if steps and any(scope_of(v[0]) != UNSCOPED for v in names.values()):
            ctx["_scope_ms"] = {
                k: v * 1e3 / steps
                for k, v in scope_seconds(table, window, names).items()}
    found = ctx["_scope_ms"]
    return None if found is None else found[scope]

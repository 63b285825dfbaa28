"""Device time of a step of gated short-convolution layers under the
scopes those add, and the fused pass's kernels' share of their roofline.

``models/sparse_moe_lm.py`` names a convolution layer's work:
``attn_qkv/sconv_in_proj`` (the input product ``[2048, 3 x 2048]``,
INSIDE ``attn_qkv`` so that ``step_parts``' ``attn_projections_ms`` keeps
covering it), ``sconv_gate`` (the two gates and the three taps: the
kernels of ``ops/short_conv_gate.py``) and ``sconv_out_proj/attn_out``
(``W_out``: ``attn_out`` as every mixer's, the outer scope tells a
convolution layer's from an attention layer's). ``sconv_gate`` lies
under no scope an older reader knows, so ``step_parts.tile`` counts it
as ``unnamed``: :func:`tile` gives it its own part. This file counts an
operation under every one of its scopes that its ``op_name`` carries,
anywhere on the path, as ``gdn_scopes.py`` does, with ``lm_scopes``' own
reduction of the trace.

A program without these scopes or kernels (another model's, or one from
before they existed) gives ``None``: the readers then report nothing.
"""

from __future__ import annotations

from chipbench import harness, lm_scopes, step_parts, trace, trace_scopes

SCOPES = ("sconv_gate", "sconv_in_proj", "sconv_out_proj")
# the fused pass's kernels; a call's cost is the ``flops/`` function
# ``short_conv_kernel_cost``
KERNELS = ("sconv_fwd", "sconv_bwd")


def scopes_of(op_name) -> frozenset:
    """Every one of ``SCOPES`` on an ``op_name`` path."""
    found = set()
    for part in (op_name or "").split("/"):
        while (m := lm_scopes._WRAPPED.match(part)):
            part = m.group(1)
        if part in SCOPES:
            found.add(part)
    return frozenset(found)


def _reduce(ctx):
    """``{"ops": [(the operation's scopes, ms a step)] or None,
    "kernels": {kernel: (calls a chip, seconds a chip)}, "steps": the
    steps traced}``, once per run."""
    if "_sconv_scopes" in ctx:
        return ctx["_sconv_scopes"]
    ctx["_sconv_scopes"] = found = {"ops": None, "kernels": {}, "steps": 0}
    if ctx.get("trace") is None or "steps_per_call" not in ctx["inputs"]:
        return found
    table, window = ctx["trace"], ctx["summary"]["window"]
    found["steps"] = steps = len(trace.module_runs(table, window)) \
        * ctx["inputs"]["steps_per_call"]
    if not steps:
        return found
    times, chips = lm_scopes._own_times(table, window)
    for kernel in KERNELS:
        mine = [t for name, t in times if kernel in name]
        if mine:
            found["kernels"][kernel] = (len(mine) / chips,
                                        sum(mine) / chips / 1e9)
    try:
        xplane = trace.newest_xplane(
            harness.REPO / ".chipbench_trace" / ctx["cell"].name).read_bytes()
    except FileNotFoundError:
        return found
    names = trace_scopes.program_instructions(
        xplane, trace.dominant_module(table, trace.device_planes(table)[0]))
    scopes = {name: scopes_of(v[0]) for name, v in names.items()}
    if any(scopes.values()):
        found["ops"] = [(scopes[name], t / chips / 1e6 / steps)
                        for name, t in times if scopes.get(name)]
    return found


def scope_ms(ctx, *scopes):
    """Milliseconds a step in operations that carry any of ``scopes``,
    each counted once; or None."""
    ops = _reduce(ctx)["ops"]
    if ops is None:
        return None
    return sum(ms for held, ms in ops if held & set(scopes))


def least_seconds(cost: dict, calls: dict, peaks: dict) -> float:
    """The least possible time of ``calls[kernel]`` calls of each kernel:
    each call the larger of its operations over the bf16 peak and its
    bytes over the memory's bandwidth (``cost[kernel]``: ``(operations,
    bytes)``)."""
    return sum(n * max(cost[k][0] / peaks["bf16_flops_per_s"],
                       cost[k][1] / peaks["hbm_bytes_per_s"])
               for k, n in calls.items())


def kernel_roofline_pct(ctx):
    """The least possible time of the fused pass's kernels (from the
    configuration's ``flops/`` function ``short_conv_kernel_cost``, a
    call on the step's rows) over their device time, in percent. The
    calls are held to the program's counter: where the job hands on the
    tokens a step's passes took (``sconv_tokens``), the backward kernel
    has to have run once for each ``rows x T`` of them, or nothing is
    reported. None without the kernels."""
    found = _reduce(ctx)
    kernels = found["kernels"]
    cost_of = getattr(ctx["cell"].flops(), "short_conv_kernel_cost", None)
    if set(kernels) != set(KERNELS) or cost_of is None:
        return None
    cell, li = ctx["cell"], ctx["inputs"]
    rows = li["examples_per_step"] // li["n_chips"]
    seq = cell.traffic["seq_len"]
    counted = li.get("sconv_tokens")
    if counted and (kernels["sconv_bwd"][0] / found["steps"] * rows * seq
                    != sum(counted) / len(counted)):
        return None
    least = least_seconds(cost_of(cell.config, rows=rows, seq=seq),
                          {k: calls for k, (calls, _s) in kernels.items()},
                          ctx["peaks"])
    return 100.0 * least / sum(s for _c, s in kernels.values())


def tile(ctx):
    """``step_parts.tile`` with ``sconv_gate``, which no older reader
    knows, as a part of its own, taken out of ``unnamed``; the two
    scopes around products count under ``attn_qkv`` and ``attn_out``
    there already. None as ``step_parts.tile``."""
    parts = step_parts.tile(ctx)
    if parts is None or _reduce(ctx)["ops"] is None:
        return parts
    own = scope_ms(ctx, "sconv_gate")
    return {**parts, "sconv_gate": own, step_parts.UNNAMED: parts.get(
        step_parts.UNNAMED, 0.0) - own}


def unnamed_pct(ctx):
    """``step_parts.unnamed_pct`` of a program with these scopes: the
    share of the step's device time in operations of the forward and
    backward phases under no scope that any reader file knows, this one
    among them, in percent; or None."""
    older, parts = step_parts.unnamed_pct(ctx), tile(ctx)
    if older is None or _reduce(ctx)["ops"] is None:
        return None
    before = step_parts.tile(ctx).get(step_parts.UNNAMED, 0.0)
    return older * parts[step_parts.UNNAMED] / before if before else older

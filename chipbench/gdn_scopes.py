"""Device time of a step of Gated DeltaNet linear-attention layers under
the scopes those add, and the rule's kernels' share of their roofline.

``models/sparse_moe_lm.py`` names a linear layer's work: ``attn_qkv/
gdn_in_proj`` (the two input products, INSIDE ``attn_qkv`` so that
``step_parts``' ``attn_projections_ms`` keeps covering them),
``gdn_conv`` (the causal convolution, SiLU and the split), ``gdn_gates``
(``beta``, the log-decay and the two L2 norms), ``gated_delta`` (the
kernels of ``ops/gated_delta_rule.py``, with the scalars' ``cumsum`` and
turn around them), ``gdn_out_norm`` (the gated norm a head) and
``gdn_out_proj/attn_out`` (``W_o``: ``attn_out`` as every mixer's, the
outer scope tells a linear layer's from a full layer's). None but the
two around products lies under a scope an older reader knows, so
``step_parts.tile`` counts them as ``unnamed``: :func:`tile` gives each
its own part. This file counts an operation under every one of its
scopes that its ``op_name`` carries, anywhere on the path, as
``mla_scopes.py`` does.

A program without these scopes or kernels (another model's, or one from
before they existed) gives ``None``: the readers then report nothing.
"""

from __future__ import annotations

from chipbench import harness, lm_scopes, step_parts, trace, trace_scopes

SCOPES = ("gated_delta", "gdn_conv", "gdn_gates", "gdn_out_norm",
          "gdn_in_proj", "gdn_out_proj")
# a family of kernels, and the ``flops/`` function of a call of each
KERNELS = {"gated_delta": ("gdn_fwd", "gdn_bwd")}


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
    "kernels": {kernel: (calls a chip, seconds a chip)}}``, once per
    run."""
    if "_gdn_scopes" in ctx:
        return ctx["_gdn_scopes"]
    ctx["_gdn_scopes"] = found = {"ops": None, "kernels": {}}
    if ctx.get("trace") is None or "steps_per_call" not in ctx["inputs"]:
        return found
    table, window = ctx["trace"], ctx["summary"]["window"]
    steps = len(trace.module_runs(table, window)) \
        * ctx["inputs"]["steps_per_call"]
    if not steps:
        return found
    times, chips = lm_scopes._own_times(table, window)
    for kernel in (k for family in KERNELS.values() for k in family):
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


def kernel_roofline_pct(ctx, family: str):
    """The least possible time of the kernels of ``family`` (each call
    the larger of its operations over the bf16 peak and its bytes over
    the memory's bandwidth, from the configuration's ``flops/`` function
    ``<family>_kernel_cost``) over their device time, in percent; or
    None."""
    kernels = {k: v for k, v in _reduce(ctx)["kernels"].items()
               if k in KERNELS[family]}
    cost_of = getattr(ctx["cell"].flops(), f"{family}_kernel_cost", None)
    if not kernels or cost_of is None:
        return None
    cell, li, peaks = ctx["cell"], ctx["inputs"], ctx["peaks"]
    cost = cost_of(cell.config,
                   rows=li["examples_per_step"] // li["n_chips"],
                   seq=cell.traffic["seq_len"])
    least = sum(calls * max(cost[k][0] / peaks["bf16_flops_per_s"],
                            cost[k][1] / peaks["hbm_bytes_per_s"])
                for k, (calls, _s) in kernels.items())
    return 100.0 * least / sum(s for _c, s in kernels.values())


def tile(ctx):
    """``step_parts.tile`` with the four scopes no older reader knows as
    parts of their own, taken out of ``unnamed``; the two around products
    count under ``attn_qkv`` and ``attn_out`` there already. None as
    ``step_parts.tile``."""
    parts = step_parts.tile(ctx)
    if parts is None or _reduce(ctx)["ops"] is None:
        return parts
    own = {s: scope_ms(ctx, s) for s in SCOPES[:4]}
    return {**parts, **own, step_parts.UNNAMED: parts.get(
        step_parts.UNNAMED, 0.0) - sum(own.values())}


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

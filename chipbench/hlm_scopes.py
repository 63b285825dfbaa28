"""Device time of a step whose layers differ in kind under the scopes
those layers add, and each kind's attention kernels' share of their
roofline.

``models/sparse_moe_lm.py`` puts ``jax.named_scope``s on what a
mixed-attention model adds to a layer: ``window_attention`` and
``causal_attention`` (the kernels of ``ops/rule_attention.py`` under the
kind's name and the layout changes around them), ``shared_expert`` and
``dense_mlp`` (plain SwiGLU for every token). ``lm_scopes.py`` reads the
scopes the model had before (``moe_route``, ``moe_experts``,
``lm_head``, which this model's step carries too); this file reads the
new ones the same way, with ``lm_scopes``' own reduction of the trace
and its unwrapping of a scope's name.

A program without these scopes or kernels (another model's, or one from
before they existed) gives ``None``: the readers then report nothing.
"""

from __future__ import annotations

from chipbench import harness, lm_scopes, trace, trace_scopes

SCOPES = ("window_attention", "causal_attention", "shared_expert",
          "dense_mlp")
# a kind of attention's kernels, and the ``flops/`` function of a call
KERNELS = {name: tuple(f"{name}_attn_{k}" for k in ("fwd", "bwd_dq",
                                                    "bwd_dkv"))
           for name in ("window", "causal")}


def scope_of(op_name):
    """The innermost of ``SCOPES`` on an ``op_name`` path, or None."""
    found = None
    for part in (op_name or "").split("/"):
        while (m := lm_scopes._WRAPPED.match(part)):
            part = m.group(1)
        if part in SCOPES:
            found = part
    return found


def _reduce(ctx):
    """``{"scope_ms": {scope: ms a step} or None, "kernels": {kernel:
    (calls a chip, seconds a chip)}}``, once per run."""
    if "_hlm_scopes" in ctx:
        return ctx["_hlm_scopes"]
    ctx["_hlm_scopes"] = found = {"scope_ms": None, "kernels": {}}
    if ctx.get("trace") is None or "steps_per_call" not in ctx["inputs"]:
        return found
    table, window = ctx["trace"], ctx["summary"]["window"]
    steps = len(trace.module_runs(table, window)) \
        * ctx["inputs"]["steps_per_call"]
    if not steps:
        return found
    times, chips = lm_scopes._own_times(table, window)
    for kernel in (k for kind in KERNELS.values() for k in kind):
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
    scopes = {name: scope_of(v[0]) for name, v in names.items()}
    if any(scopes.values()):
        totals = dict.fromkeys(SCOPES, 0.0)
        for name, t in times:
            if scopes.get(name):
                totals[scopes[name]] += t
        found["scope_ms"] = {k: v / chips / 1e6 / steps
                             for k, v in totals.items()}
    return found


def scope_ms(ctx, *scopes):
    """Milliseconds a step under the given scopes together, or None."""
    by_scope = _reduce(ctx)["scope_ms"]
    return None if by_scope is None else sum(by_scope[s] for s in scopes)


def kernel_roofline_pct(ctx, name: str):
    """The least possible time of the three kernels called ``name``
    (each call the larger of its operations over the bf16 peak and its
    bytes over the memory's bandwidth, from the configuration's
    ``flops/`` function ``<name>_attention_kernel_cost``) over their
    device time, in percent; or None."""
    kernels = {k: v for k, v in _reduce(ctx)["kernels"].items()
               if k in KERNELS[name]}
    if not kernels:
        return None
    cell, li, peaks = ctx["cell"], ctx["inputs"], ctx["peaks"]
    cost = getattr(cell.flops(), f"{name}_attention_kernel_cost")(
        cell.config, rows=li["examples_per_step"] // li["n_chips"],
        seq=cell.traffic["seq_len"])
    least = sum(calls * max(cost[k][0] / peaks["bf16_flops_per_s"],
                            cost[k][1] / peaks["hbm_bytes_per_s"])
                for k, (calls, _s) in kernels.items())
    return 100.0 * least / sum(s for _c, s in kernels.values())

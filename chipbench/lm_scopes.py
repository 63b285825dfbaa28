"""Device time of a language-model step by the model's own scopes, and
the sparse-attention kernels' share of their roofline.

``models/sparse_moe_lm.py`` puts ``jax.named_scope``s on the parts of a
layer (``indexer``, ``select_topk``, ``sparse_attention``,
``moe_route``, ``moe_experts``) and on ``lm_head``; like the step's
phases (``trace_scopes.py``) they reach the trace only as the
``op_name`` of each instruction of the compiled program, read from the
trace file's metadata plane by ``trace_scopes.program_instructions``.
JAX wraps a scope's name by the transformation it was traced under
(``jvp(indexer)``, ``transpose(jvp(moe_experts))``, a rematerialised
pass under ``checkpoint``/``rematted_computation``), so forward,
recomputation and backward all count under the bare name.

The grouped matrix products of the expert layer are the compiler's own
``ragged-dot`` custom calls, whose ``op_name`` is the compiler's
(``ragged-dot-none``) and carries no scope of the program's (read in
the first traced run: 119 ms a step under no scope), so an instruction
of that name under no scope counts under ``moe_experts``.

A program without these scopes (one from before they existed) gives
``None`` everywhere: the readers then report nothing.
"""

from __future__ import annotations

import re

from chipbench import harness, trace, trace_scopes

SCOPES = ("indexer", "select_topk", "sparse_attention", "moe_route",
          "moe_experts", "lm_head")
_WRAPPED = re.compile(r"^[\w.]+\((.*)\)$")
KERNELS = ("sparse_attn_fwd", "sparse_attn_bwd_dq", "sparse_attn_bwd_dkv")


def scope_of(op_name):
    """The innermost of ``SCOPES`` on an ``op_name`` path, or None."""
    found = None
    for part in (op_name or "").split("/"):
        while (m := _WRAPPED.match(part)):
            part = m.group(1)
        if part in SCOPES:
            found = part
    return found


def _own_times(table, window):
    """``(instruction name, own ns inside the window)`` of every
    operation on ``XLA Ops``, all chips; and the chips."""
    planes = trace.device_planes(table)
    out = []
    for p in planes:
        for event, segs in trace.self_segments(
                table[p].get(trace.OPS_LINE, [])):
            name = trace.op_name(event)
            if not trace._CONTAINER.match(name):
                t = trace.measure(trace.clip(segs, window))
                if t:
                    out.append((name, t))
    return out, len(planes)


def _reduce(ctx):
    """``{"scope_ms": {scope: ms a step} or None, "kernels": {kernel:
    (calls a chip, seconds a chip)}}``, once per run."""
    if "_lm_scopes" in ctx:
        return ctx["_lm_scopes"]
    ctx["_lm_scopes"] = found = {"scope_ms": None, "kernels": {}}
    if ctx.get("trace") is None or "steps_per_call" not in ctx["inputs"]:
        return found
    table, window = ctx["trace"], ctx["summary"]["window"]
    steps = len(trace.module_runs(table, window)) \
        * ctx["inputs"]["steps_per_call"]
    if not steps:
        return found
    times, chips = _own_times(table, window)
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
    scopes = {name: scope_of(v[0]) for name, v in names.items()}
    if any(scopes.values()):
        scopes.update({name: "moe_experts" for name in names
                       if name.startswith("ragged-dot")
                       and not scopes[name]})
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


def kernel_roofline_pct(ctx, cost_fn: str):
    """The kernels' least possible time (each call the larger of its
    operations over the bf16 peak and its bytes over the memory's
    bandwidth, from the configuration's ``flops/`` function
    ``cost_fn``) over their device time, in percent; or None."""
    kernels = _reduce(ctx)["kernels"]
    if not kernels:
        return None
    cell, li, peaks = ctx["cell"], ctx["inputs"], ctx["peaks"]
    cost = getattr(cell.flops(), cost_fn)(
        cell.config, rows=li["examples_per_step"] // li["n_chips"],
        seq=cell.traffic["seq_len"])
    least = sum(calls * max(cost[k][0] / peaks["bf16_flops_per_s"],
                            cost[k][1] / peaks["hbm_bytes_per_s"])
                for k, (calls, _s) in kernels.items())
    return 100.0 * least / sum(s for _c, s in kernels.values())

"""Device time of a step of latent-attention layers with a multi-token
prediction module under the scopes those add, and the new kernels' share
of their roofline.

``models/sparse_moe_lm.py`` nests the new scopes INSIDE the ones
``step_parts.py`` already tiles a step by, so that its table keeps
adding up: ``attn_qkv/latent_q`` and ``attn_qkv/latent_kv`` (each
latent's down-projection, norm and up-projection), ``attn_qk_rope/
latent_rope`` (``ops/latent_rope.py``: the rotary step, the cast and the
turn heads first), and ``latent_attention`` (the kernels of
``ops/latent_attention.py`` and the output's turn; under no older
scope, so ``step_parts.tile`` counts it as ``unnamed``: :func:`tile`
gives it its own part). ``mtp`` lies OUTSIDE its layer's scopes: around
the whole module in the model, and around the module's cross entropy in
the loss (there inside the step's ``loss``). So this file counts an
operation under every one of its scopes that its ``op_name`` carries,
anywhere on the path, where the older readers take the innermost.

A program without these scopes or kernels (another model's, or one from
before they existed) gives ``None``: the readers then report nothing.
"""

from __future__ import annotations

from chipbench import harness, lm_scopes, step_parts, trace, trace_scopes

SCOPES = ("latent_attention", "latent_q", "latent_kv", "latent_rope", "mtp")
# a family of kernels, and the ``flops/`` function of a call of each
KERNELS = {
    "latent_attention": tuple(f"latent_attn_{k}"
                              for k in ("fwd", "bwd_dq", "bwd_dkv")),
    "latent_rope": ("latent_rope_fwd", "latent_rope_bwd"),
}


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
    if "_mla_scopes" in ctx:
        return ctx["_mla_scopes"]
    ctx["_mla_scopes"] = found = {"ops": None, "kernels": {}}
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
    """``step_parts.tile`` with the attention kernels' scope as a part
    of its own, taken out of ``unnamed`` (no older reader knows the
    scope, and it lies under none of theirs); ``mtp`` is no part: the
    module's operations count under their layer's scopes. For the
    builder, not a metric. None as ``step_parts.tile``."""
    parts = step_parts.tile(ctx)
    latent = scope_ms(ctx, "latent_attention")
    if parts is None or latent is None:
        return parts
    return {**parts, "latent_attention": latent,
            step_parts.UNNAMED: parts.get(step_parts.UNNAMED, 0.0) - latent}

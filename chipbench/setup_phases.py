"""``setup_s`` by phase, from the program's own bus: the starts and
durations of its spans (``Telemetry.span_samples``) and the compile
events it files under the span they fell in (``jit.*_s{span=...}``).

Every ``layer_metrics/setup_*.py`` reads one entry of ``phases``. A
program whose bus keeps no starts (one from before PR 24) has nothing
to read here, and the readers return ``None``.
"""

from __future__ import annotations

from chipbench import harness


def _total(tele, path: str) -> float:
    return sum(d for _t0, d in tele.span_samples(path))


def _jit(tele, names, span: str) -> float:
    return sum(tele.histogram(n, {"span": span})["sum"] for n in names)


def phases(ctx):
    """Seconds of set-up by phase, or ``None``. They tile the time from
    the process's start (``harness.T_PROCESS``) to the end of the first
    ``train/step_chunk``: what ``setup_s`` measures in a ``fit_sync``
    run, but for the hook's first stamp a few records later."""
    tele = ctx["inputs"].get("telemetry")
    if tele is None or not hasattr(tele, "span_samples"):
        return None
    enter = tele.span_samples("train/enter")
    chunks = tele.span_samples("train/step_chunk")
    if not enter or len(chunks) < 2:
        return None
    first_t0, first_dur = chunks[0]
    later = [d for _t0, d in chunks[1:]]
    out = {
        "before_call": enter[0][0] - harness.T_PROCESS,
        "data_place": _total(tele, "train/data_prep")
        + _total(tele, "train/shuffle"),
        "init": _total(tele, "train/init"),
        "step_trace": _jit(tele, ("jit.trace_s", "jit.lower_s"),
                           "train/step_chunk"),
        "step_load": _jit(tele, ("jit.compile_s", "jit.cache_load_s"),
                          "train/step_chunk"),
        "build_step": _total(tele, "train/build_step"),
        "first_chunk_run": sum(later) / len(later),
    }
    out["unaccounted"] = (first_t0 + first_dur - harness.T_PROCESS
                          - sum(out.values()))
    return out


def read(ctx, phase: str):
    found = phases(ctx)
    return None if found is None else found[phase]

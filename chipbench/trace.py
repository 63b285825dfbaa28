"""The reduction from the profiler's trace to numbers: device busy
union and idle share, time per operation, collective time not hidden
behind compute, and idle gaps named by what the host was doing.

It reads ``jax.profiler.ProfileData`` (the ``.xplane.pb`` the profiler
writes) into a plain table first, ``{plane: {line: [(name, start_ns,
dur_ns), ...]}}``, and every reduction works on that table, so the
recorded trace under ``tests/data`` checks the same code the runs use.

On a TPU each chip is a plane ``/device:TPU:<n>``. Its line
``XLA Ops`` holds one event per executed HLO operation (a ``while`` or
a ``call`` spans its body, whose operations nest inside it on the same
line; an event's name is the operation's HLO text, ``%fusion.12 =
...``), ``Async XLA Ops`` the spans of asynchronous copies and
collectives, and ``XLA Modules`` one event per executed program. Host threads
are lines of the plane ``/host:CPU``; ``TraceAnnotation``s and the
runtime's own ``TraceMe``s are their events. All on one clock.
"""

from __future__ import annotations

import re
from pathlib import Path

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
_DEVICE = re.compile(r"^/device:TPU:\d+$")
_COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast)", re.I)
# operations that only contain others; their own time is their body's
_CONTAINER = re.compile(r"^(while|conditional|call)([.\d]|$)", re.I)


def op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.lstrip("%").split(" ", 1)[0]


def newest_xplane(trace_dir) -> Path:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def table_from_profile(profile) -> dict:
    out = {}
    for plane in profile.planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (ev.name, float(ev.start_ns), float(ev.duration_ns))
                for ev in line.events)
    return out


def load(trace_dir) -> dict:
    from jax.profiler import ProfileData

    return table_from_profile(
        ProfileData.from_file(str(newest_xplane(trace_dir))))


def device_planes(table: dict) -> list:
    return sorted(p for p in table if _DEVICE.match(p))


# -- intervals --------------------------------------------------------------


def union(intervals) -> list:
    """Merged, sorted ``[(start, end)]``."""
    out = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def measure(intervals) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals, window) -> list:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a, b) -> list:
    """The part of merged ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def _spans(events):
    return [(s, s + d) for _n, s, d in events]


def self_segments(events) -> list:
    """``[(name, [(start, end)])]``: every event's own time, its
    interval less the events nested inside it on the same line."""
    out, stack = [], []  # stack of [name, end, cursor, segments]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, end, cur, segs = stack.pop()
            if end > cur:
                segs.append((cur, end))
            out.append((name, segs))
            if stack:
                stack[-1][2] = max(stack[-1][2], end)

    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        close(s)
        if stack and s > stack[-1][2]:
            stack[-1][3].append((stack[-1][2], s))
        if stack:
            stack[-1][2] = max(stack[-1][2], s)
        stack.append([name, s + d, s, []])
    close(float("inf"))
    return out


# -- windows ----------------------------------------------------------------


def dominant_module(table: dict, plane: str) -> str:
    totals = {}
    for name, _s, d in table[plane].get(MODULES_LINE, []):
        totals[name] = totals.get(name, 0.0) + d
    if not totals:
        raise ValueError(f"no {MODULES_LINE!r} events on {plane}")
    return max(totals, key=totals.get)


def steady_window(table: dict, hint: dict):
    """``(start_ns, end_ns)`` of the steady stretch the job names:
    ``{"annotation": name}``, the host annotation of that name, or
    ``{"module_skip_first": n}``, from the end of the n-th execution of
    the program that took most device time to the end of its last."""
    if "annotation" in hint:
        for line in table.get("/host:CPU", {}).values():
            for name, s, d in line:
                if name == hint["annotation"]:
                    return (s, s + d)
        raise ValueError(f"no host annotation {hint['annotation']!r}")
    plane = device_planes(table)[0]
    mod = dominant_module(table, plane)
    runs = sorted((s, s + d) for n, s, d in table[plane][MODULES_LINE]
                  if n == mod)
    skip = int(hint.get("module_skip_first", 0))
    if len(runs) <= skip:
        raise ValueError(f"{len(runs)} executions of {mod}, need > {skip}")
    return (runs[skip - 1][1] if skip else runs[0][0], runs[-1][1])


# -- reductions -------------------------------------------------------------


def _op_events(table: dict, plane: str):
    lines = table[plane]
    return lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []


def busy_union(table: dict, plane: str, window) -> list:
    return clip(union(_spans(_op_events(table, plane))), window)


def busy_seconds(table: dict, window) -> float:
    """Seconds in which an operation ran on the device inside the
    window, averaged over the chips."""
    planes = device_planes(table)
    if not planes:
        raise ValueError("the trace holds no /device:TPU plane")
    return sum(measure(busy_union(table, p, window))
               for p in planes) / len(planes) / 1e9


def module_runs(table: dict, window, name: str | None = None) -> list:
    """Durations (s) of the executions, inside the window, of one
    program (default: the one that took most device time) on the first
    chip."""
    plane = device_planes(table)[0]
    name = name or dominant_module(table, plane)
    lo, hi = window
    return [d / 1e9 for n, s, d in table[plane].get(MODULES_LINE, [])
            if n == name and s >= lo and s + d <= hi + 1]


def op_times(table: dict, window, top: int = 10) -> list:
    """``[[name, seconds]]`` of the operations that took most of their
    own time inside the window, averaged over the chips."""
    planes = device_planes(table)
    totals = {}
    for p in planes:
        for name, segs in self_segments(_op_events(table, p)):
            name = op_name(name)
            if _CONTAINER.match(name):
                continue
            t = measure(clip(segs, window))
            if t:
                totals[name] = totals.get(name, 0.0) + t
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    return [[n, t / len(planes) / 1e9] for n, t in ranked]


def collective_exposed_seconds(table: dict, window) -> float:
    """Collective-operation time on a chip during which no compute
    operation runs on it, inside the window, averaged over the chips."""
    planes = device_planes(table)
    total = 0.0
    for p in planes:
        coll, comp = [], []
        for name, segs in self_segments(table[p].get(OPS_LINE, [])):
            name = op_name(name)
            if not _CONTAINER.match(name):
                (coll if _COLLECTIVE.match(name) else comp).extend(segs)
        coll += [(s, s + d) for n, s, d in table[p].get(ASYNC_LINE, [])
                 if _COLLECTIVE.match(op_name(n))]
        total += measure(clip(subtract(union(coll), union(comp)), window))
    return total / len(planes) / 1e9


def _short(name: str) -> str:
    return re.split(r"[(\[:#]| \$", name, maxsplit=1)[0].strip()[:64] or name[:64]


def idle_gaps(table: dict, window, top: int = 10, longest: int = 200) -> list:
    """``[[what the host was doing, seconds]]``: the first chip's idle
    gaps inside the window, each of the ``longest`` gaps named by the
    innermost host event that covers at least half of it (else the one
    that covers the largest part) and the rest summed as ``short
    gaps``; summed by name."""
    import numpy as np

    plane = device_planes(table)[0]
    gaps = subtract([tuple(window)], busy_union(table, plane, window))
    gaps.sort(key=lambda g: g[0] - g[1])
    host = [(n, s, s + d) for line in table.get("/host:CPU", {}).values()
            for n, s, d in line if d > 0]
    names = [h[0] for h in host]
    starts = np.array([h[1] for h in host], dtype=np.float64)
    ends = np.array([h[2] for h in host], dtype=np.float64)
    totals = {}
    for gs, ge in gaps[:longest]:
        best = "unattributed"
        if len(host):
            ov = np.minimum(ends, ge) - np.maximum(starts, gs)
            if ov.max() > 0:
                # the innermost event that covers most of the gap, else
                # the one that covers the largest part of it
                most = np.flatnonzero(ov >= 0.5 * (ge - gs))
                pick = (most[np.argmin((ends - starts)[most])]
                        if len(most) else int(np.argmax(ov)))
                best = _short(names[pick])
        totals[best] = totals.get(best, 0.0) + (ge - gs)
    rest = sum(ge - gs for gs, ge in gaps[longest:])
    if rest:
        totals["short gaps"] = rest
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    return [[n, t / 1e9] for n, t in ranked]


def summarize(table: dict, hint: dict) -> dict:
    """Everything a traced run reports from the device."""
    window = steady_window(table, hint)
    return {
        "window": window,
        "window_s": (window[1] - window[0]) / 1e9,
        "busy_s": busy_seconds(table, window),
        "device_ops": op_times(table, window),
        "idle_gaps": idle_gaps(table, window),
    }

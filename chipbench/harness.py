"""What every job shares: finding a cell's files by name, the seeded
spec that hands the benchmark's weights to the program, the compile
counter, the device's memory reading and the result line.

Nothing here names a cell, a configuration, a job kind or a metric:
those are files under ``configs/``, ``traffic/``, ``jobs/``,
``layer_metrics/``, ``reference/`` and ``flops/``, found by the names
``BENCHMARK.json`` gives.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
import sys
import time
from pathlib import Path
from typing import Any, Callable, Optional

BENCH_DIR = Path(__file__).resolve().parent
REPO = BENCH_DIR.parent
BENCHMARK_JSON = REPO / "BENCHMARK.json"

# The process's first clock reading the benchmark can take; ``setup_s``
# runs from here to the start of the measured window.
T_PROCESS = time.perf_counter()


# ---------------------------------------------------------------------------
# Files by name
# ---------------------------------------------------------------------------


def _find(kind: str, filename: str, root: Path) -> Path:
    """``<root>/<kind>/<filename>``, else the benchmark's own: a root
    other than the benchmark's (the tests' tiny one) adds files and
    need not repeat the rest."""
    for base in (Path(root), BENCH_DIR):
        if (base / kind / filename).is_file():
            return base / kind / filename
    raise FileNotFoundError(
        f"no {kind}/{filename} under {root} or {BENCH_DIR}")


def load_json(kind: str, name: str, root: Path = BENCH_DIR) -> dict:
    with open(_find(kind, f"{name}.json", root)) as f:
        return json.load(f)


def load_module(kind: str, name: str, root: Path = BENCH_DIR):
    """``<root>/<kind>/<name>.py`` as a module. Loaded by path because
    a configuration's name may hold ``-``."""
    path = _find(kind, f"{name}.py", root)
    mod_name = f"chipbench_{kind}_{name}".replace("-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def resolve_dotted(dotted: str) -> Callable:
    """``package.module:attr`` or ``package.module.attr`` -> object."""
    mod_name, _, attr = dotted.replace(":", ".").rpartition(".")
    return getattr(importlib.import_module(mod_name), attr)


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with every file it names loaded."""

    name: str
    chips: int
    config_name: str
    config: dict          # configs/<config>.json
    traffic: dict         # traffic/<traffic>.json
    job: Any              # jobs/<traffic["job"]>.py
    reference: Any        # reference/<config>.py
    end_to_end: list      # metric entries this cell reports
    per_layer: list       # metric entries whose readers run here
    root: Path = BENCH_DIR

    def build_module(self):
        ctor = resolve_dotted(self.config["constructor"])
        return ctor(**self.config.get("constructor_kwargs", {}))

    def flops(self):
        return load_module(
            "flops", self.config.get("flops", self.config_name), self.root)


def _applies(metric: dict, cell_name: str, reported: Optional[set]) -> bool:
    if "workloads" in metric:
        return cell_name in metric["workloads"]
    return reported is None or metric.get("moves") in reported


def resolve_cell(name: str, bench_json: Path = BENCHMARK_JSON,
                 root: Path = BENCH_DIR) -> Cell:
    with open(bench_json) as f:
        bench = json.load(f)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in {bench_json}; have "
                       f"{[w['name'] for w in bench['workloads']]}")
    config = load_json("configs", entry["config"], root)
    traffic = load_json("traffic", entry["traffic"], root)
    e2e = [m for m in bench["end_to_end"] if _applies(m, name, None)]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if _applies(m, name, reported)]
    return Cell(
        name=name, chips=int(entry["chips"]), config_name=entry["config"],
        config=config, traffic=traffic,
        job=load_module("jobs", traffic["job"], root),
        reference=load_module(
            "reference", config.get("reference", entry["config"]), root),
        end_to_end=e2e, per_layer=layer, root=Path(root),
    )


def load_peaks(device_kind: str, root: Path = BENCH_DIR) -> dict:
    with open(_find(".", "peaks.json", root)) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json "
                       f"({sorted(k for k in table if not k.startswith('_'))})")
    return table[device_kind]


# ---------------------------------------------------------------------------
# The program gets the benchmark's weights
# ---------------------------------------------------------------------------


def seeded_spec(cell: Cell, **spec_kwargs):
    """A ``ModelSpec`` whose ``init_params`` returns the weights the
    cell's reference file makes from the key it is given. The trainers
    call ``init_params(jax.random.key(seed))`` themselves, with the
    seed the job hands them; the check later makes the same weights
    from the same key without asking the program for anything."""
    import jax

    from sparktorch_tpu.utils.serde import ModelSpec

    reference, sizes = cell.reference, cell.config

    @dataclasses.dataclass
    class SeededSpec(ModelSpec):
        def init_params(self, rng, sample_x=None):
            return jax.jit(lambda k: reference.init(k, sizes))(rng)

    return SeededSpec(module=cell.build_module(), **spec_kwargs)


# ---------------------------------------------------------------------------
# Compiles, memory, spans
# ---------------------------------------------------------------------------

_COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                   "/jax/compilation_cache/cache_retrieval_time_sec")


class CompileCounter:
    """Stamps of every backend compile and every load of a compiled
    program from the persistent cache, so a job can count those that
    fell inside its window (there must be none)."""

    def __init__(self):
        import jax.monitoring

        self.stamps: list = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration_s: float, **_kw) -> None:
        if event in _COMPILE_EVENTS:
            self.stamps.append((time.perf_counter(), event, duration_s))

    def within(self, t0: float, t1: float) -> int:
        return sum(1 for t, _e, _d in self.stamps if t0 <= t <= t1)

    def require_none_within(self, t0: float, t1: float) -> None:
        n = self.within(t0, t1)
        if n:
            raise WindowCompile(
                f"{n} compile(s) or cache load(s) inside the window")

    def before(self, t: float) -> str:
        """What set-up spent compiling and loading, for the log."""
        comp = [d for s, e, d in self.stamps
                if s < t and e == _COMPILE_EVENTS[0]]
        load = [d for s, e, d in self.stamps
                if s < t and e == _COMPILE_EVENTS[1]]
        return (f"set-up compiled {len(comp)} program(s) in "
                f"{sum(comp):.2f}s (longest {max(comp, default=0):.2f}s) and "
                f"loaded {len(load)} from the cache in {sum(load):.2f}s")


class WindowCompile(RuntimeError):
    """Something compiled inside the measured window."""


def memory_peak_bytes() -> tuple:
    """``(peak, buffers, scratch)`` in bytes on the fullest chip.

    ``buffers`` is the runtime's ``peak_bytes_in_use``. On this libtpu
    it counts live arrays only: BERT-base's training step reads 1.49 GB
    there, less than its own saved activations (my chip run, PR 23). So
    ``scratch``, the largest ``temp_size_in_bytes`` among the programs
    loaded now, is added: the peak is a steady set of buffers plus the
    scratch of the program that runs on them. Call it while the
    program's executables are alive and before the reference runs."""
    import jax

    buffers = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        buffers = max(buffers, int(stats.get("peak_bytes_in_use", 0)))
    scratch = 0
    for exe in jax.local_devices()[0].client.live_executables():
        try:
            scratch = max(scratch, int(
                exe.get_compiled_memory_stats().temp_size_in_bytes))
        except Exception:  # an executable without stats adds nothing
            continue
    return buffers + scratch, buffers, scratch


def start_trace(trace_dir: str) -> None:
    """The profiler without its Python tracer and with the host tracer
    at the level of annotations. At the defaults the Python tracer wrote
    7.9 M events for one ``train_distributed`` call and the call's first
    chunk took 23.6 s under it (my chip run, PR 23). The runtime's own
    per-tile ``Transpose`` events of the host-to-device path are still
    written at this level, so a traced stretch that feeds the chip from
    the host is slowed by the tracer: see PERF.md, Open questions."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def span_samples(tele, path: str) -> list:
    """Every recorded duration of one program span, oldest first."""
    with tele._lock:
        hist = tele._spans.get((path, ()))
        return list(hist.ring) if hist is not None else []


# ---------------------------------------------------------------------------
# Results
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Check:
    """One compared number beside its limit."""

    name: str
    value: float
    limit: float
    kind: str = "max"   # "max": value <= limit; "min": value >= limit

    @property
    def ok(self) -> bool:
        v = self.value
        if v != v:  # NaN
            return False
        return v <= self.limit if self.kind == "max" else v >= self.limit

    def line(self) -> str:
        op = "<=" if self.kind == "max" else ">="
        return (f"check {self.name}: {self.value!r} {op} {self.limit!r} "
                f"{'ok' if self.ok else 'FAILED'}")


@dataclasses.dataclass
class JobResult:
    setup_s: float
    window_s: float
    end_to_end: dict            # name -> value, host clock
    attempted: int
    failed: int
    checks: list                # [Check]
    memory: tuple               # memory_peak_bytes()
    # what the layer-metric readers get: spans, counters, the trace
    layer_inputs: dict = dataclasses.field(default_factory=dict)
    notes: list = dataclasses.field(default_factory=list)


def arm_compile_cache() -> str:
    """Where ``JAX_COMPILATION_CACHE_DIR`` says, else a fixed directory
    inside the checkout. Every compile is kept, however short."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(REPO / ".jax_compile_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cc.reset_cache()
    return str(jax.config.jax_compilation_cache_dir)

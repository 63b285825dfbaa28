"""Trace-guided auto-tuning of mesh/parallelism configs.

The repo can *measure* exactly where step time goes (per-collective
comm/compute/overlap budgets from :mod:`sparktorch_tpu.obs.xprof`) and
can *run* every dp/fsdp/tp/sp/ep mesh combination — but picking the
mesh for a workload was still a human. This module closes the loop,
Alpa/AutoSharding-style but grounded in MEASURED traces rather than a
static cost model alone:

1. **Enumerate** every legal :class:`MeshConfig` for the device count:
   axis products must divide the device world, and each axis is capped
   by the model dims the sharding rules lay out over it (``tp`` must
   divide heads/FFN/vocab, ``sp`` the sequence, ``ep`` the expert
   count, the batch axes the global batch).
2. **Prune** the space with a cheap analytic comm-volume model — bytes
   moved per step per candidate from param/activation shapes, no
   execution. The model is a PRUNER, not a predictor: it only has to
   rank badly-communicating layouts below plausible ones.
3. **Measure** the survivors: compile every survivor once (outside
   any capture — a capture containing the multi-second XLA compile
   overflows the profiler buffer), then run INTERLEAVED rounds of a
   few profiled steps per candidate — medians over interleaved
   repeats, because on a cpu-share rig whole measurement windows land
   in slow scheduler epochs and back-to-back candidate timings swing.
   Each round's capture is analyzed offline
   (:class:`~sparktorch_tpu.obs.xprof.TraceAnalysis`); candidates are
   scored by the median step wall across all rounds with an
   exposed-comm tiebreak, and the round loop early-stops once the
   best candidate's lead exceeds the measurement noise floor (the
   cross-candidate max of p75-p25 step-wall spreads).
4. **Emit** the search as an artifact (``tune_result.json``: full
   ranking, per-candidate budgets, prune decisions, chosen mesh) and
   as an ``xprof_tune`` telemetry section + ``xprof.tune_*`` metrics,
   so the collector and ``obs.timeline --tune`` can render it.

The winner is a usable fast path, not a report:
``make_sharded_train_step(mesh="auto", spec=..., sample_batch=...)``
runs this search and trains on the chosen mesh
(:mod:`sparktorch_tpu.train.sharded`). tests/test_autotune.py holds
the search's decisions on scripted measurements; no chip has run it.

CLI::

    python -m sparktorch_tpu.parallel.tune --model tiny --batch 32 \
        --out tune_result.json

Everything through step (2) is backend-free (no device execution), so
enumeration, pruning, and scoring are tier-1-testable on synthetic
shapes; only :func:`measure_candidate` touches the accelerator.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import time
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np

from sparktorch_tpu.obs.log import get_logger
from sparktorch_tpu.parallel.mesh import ALL_AXES, AXIS_DP, MeshConfig

_LOG = get_logger("sparktorch_tpu.parallel.tune")

# The full default search space, ``pp`` included: pp>1 candidates are
# measured through the PIPELINE trainer's schedule path
# (train/pipeline.py — gpipe / 1f1b / interleaved-1f1b), everything
# else through the GSPMD trainer. Callers that only ever build GSPMD
# steps can pass ``axes=GSPMD_AXES`` to keep the pp-less space.
DEFAULT_AXES: Tuple[str, ...] = ("dp", "fsdp", "tp", "sp", "ep", "pp")

# The pp-less space the tuner searched before pipeline schedules were
# opened (PR 7-13 behavior; scripted decision tests pin against it).
GSPMD_AXES: Tuple[str, ...] = ("dp", "fsdp", "tp", "sp", "ep")

# Schedule search dims for pp>1 candidates. "interleaved" is the
# interleaved 1F1B schedule (virtual_stages>1 chunks per device);
# it reaches make_pp_train_step as schedule='1f1b' + virtual_stages=V.
PP_SCHEDULES = ("gpipe", "1f1b", "interleaved")

ARTIFACT_KIND = "tune"


def pp_bubble_fraction(schedule: str, n_stages: int, n_micro: int,
                       virtual_stages: int = 1) -> float:
    """Pipeline bubble (idle fraction of the schedule) — the textbook
    (S-1)/(M+S-1) for gpipe AND 1f1b (1F1B reorders the bubble for
    memory, not away: same ticks, same idle — Narayanan et al.), and
    the V-scaled interleaved variant (S-1)/(V*M+S-1): V chunks per
    device shrink the warmup/drain ramps V-fold at the price of V x
    the stage-boundary traffic (the trade the cost model ranks)."""
    if schedule not in PP_SCHEDULES:
        raise ValueError(f"unknown pipeline schedule {schedule!r} "
                         f"(of {PP_SCHEDULES})")
    S = int(n_stages)
    M = max(1, int(n_micro))
    V = max(1, int(virtual_stages))
    if S <= 1:
        return 0.0
    if schedule == "interleaved":
        return (S - 1) / (V * M + S - 1)
    return (S - 1) / (M + S - 1)


def pp_schedule_ticks(schedule: str, n_stages: int, n_micro: int,
                      virtual_stages: int = 1) -> int:
    """Schedule ticks per step — the pp launch count the alpha term
    charges (each tick moves one activation block over the stage
    ring, fwd or combined fwd+bwd): M+S-1 for gpipe's scanned
    forward (backward rides the transposed scan), M+2S-2 combined
    ticks for 1F1B, and the chunk-granular V*M+2S-2 for interleaved
    (V x the hops — the bytes that buy the smaller bubble)."""
    if schedule not in PP_SCHEDULES:
        raise ValueError(f"unknown pipeline schedule {schedule!r} "
                         f"(of {PP_SCHEDULES})")
    S = int(n_stages)
    M = max(1, int(n_micro))
    V = max(1, int(virtual_stages))
    if S <= 1:
        return 0
    if schedule == "gpipe":
        return M + S - 1
    if schedule == "1f1b":
        return M + 2 * S - 2
    return V * M + 2 * S - 2


# ---------------------------------------------------------------------------
# Search space: legal MeshConfig candidates
# ---------------------------------------------------------------------------


def transformer_caps(cfg, seq_len: Optional[int] = None) -> Dict[str, Tuple[int, ...]]:
    """Per-axis divisibility caps for a :class:`TransformerConfig`,
    mirroring what :mod:`sparktorch_tpu.parallel.sharding_rules`
    actually lays out over each axis: an axis size is legal iff it
    divides EVERY listed dim (``_spec_fits`` would otherwise silently
    fall back to replication and the axis would waste devices).

    - ``tp``: qkv heads, the FFN inner dim, and the vocab (embedding
      rows ride ``P(tp, fsdp)``);
    - ``fsdp``: the model dim (the embedding's fsdp-sharded column);
    - ``sp``: the sequence length;
    - ``ep``: the expert count (dense model -> ep stays 1). The ep
      axis is a first-class search dimension: dispatch/combine are
      explicit shard_map all-to-alls with a mesh-anchored group
      partition (models.transformer.MoEFFN), so measured ep candidates
      reflect the real scaling layout, not the degraded partitioner-
      derived lowering the pre-rewrite tuner had to distrust (the old
      "defer ep re-validation" caveat is closed — stale entries are
      fenced off by the cache-key schema bump);
    - ``pp``: the layer count.
    """
    return {
        "fsdp": (cfg.d_model,),
        "tp": (cfg.n_heads, cfg.d_ff, cfg.vocab_size),
        "sp": (int(seq_len or cfg.max_len),),
        "ep": (cfg.n_experts,) if cfg.n_experts > 0 else (1,),
        "pp": (cfg.n_layers,),
    }


def _legal(axis_size: int, dims: Sequence[int]) -> bool:
    return all(d > 0 and d % axis_size == 0 for d in dims) if dims \
        else True


def _divisors(n: int) -> List[int]:
    return [d for d in range(1, n + 1) if n % d == 0]


def enumerate_candidates(
    n_devices: int,
    caps: Mapping[str, Sequence[int]],
    global_batch: int,
    axes: Sequence[str] = DEFAULT_AXES,
    max_candidates: Optional[int] = None,
) -> List[MeshConfig]:
    """Every legal :class:`MeshConfig` for ``n_devices``: the non-dp
    axis product divides the device count (dp absorbs the rest), each
    axis size divides its cap dims, and the batch axes (dp*fsdp)
    divide the global batch. Deterministic order: ascending by the
    (fsdp, tp, sp, ep, pp) size tuple, so the pure-dp config is always
    candidate 0 and goldens can assert exact lists."""
    if n_devices < 1:
        raise ValueError(f"n_devices must be >= 1, got {n_devices}")
    axes = tuple(axes)
    for ax in axes:
        if ax not in ALL_AXES:
            raise ValueError(f"unknown mesh axis {ax!r} (of {ALL_AXES})")
    fixed_axes = [a for a in ALL_AXES if a != AXIS_DP]
    choices: Dict[str, List[int]] = {}
    for ax in fixed_axes:
        if ax not in axes:
            choices[ax] = [1]
            continue
        choices[ax] = [d for d in _divisors(n_devices)
                       if _legal(d, tuple(caps.get(ax, ())))]

    out: List[MeshConfig] = []
    import itertools

    for combo in itertools.product(*(choices[a] for a in fixed_axes)):
        fixed = math.prod(combo)
        if n_devices % fixed != 0:
            continue
        dp = n_devices // fixed
        if AXIS_DP not in axes and dp != 1:
            continue
        sizes = dict(zip(fixed_axes, combo))
        if sizes["pp"] > 1 and sizes["fsdp"] > 1:
            # No trainer runs pp x fsdp: the pipeline trainer shards
            # params over pp (dp x pp x tp x sp x ep only), the GSPMD
            # trainer has no schedule. Not a legal layout anywhere.
            continue
        if global_batch % (dp * sizes["fsdp"]) != 0:
            continue
        if not _legal(dp, tuple(caps.get(AXIS_DP, ()))):
            continue
        out.append(MeshConfig(dp=dp, **sizes))
    out.sort(key=lambda c: (c.fsdp, c.tp, c.sp, c.ep, c.pp))
    if max_candidates is not None and len(out) > max_candidates:
        # Truncation here is in ENUMERATION order, blind to cost —
        # callers that can rank first (autotune does) should cap
        # after the cost model instead.
        _LOG.warning(
            f"[sparktorch_tpu:tune] enumeration truncated "
            f"{len(out)} -> {max_candidates} candidates "
            f"(enumeration order, not cost order)"
        )
        out = out[:max_candidates]
    return out


# ---------------------------------------------------------------------------
# Analytic comm-volume model (the pruner — no execution)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class WorkloadShape:
    """Byte-level skeleton of one training step, enough to rank mesh
    candidates by communication volume without running anything.

    ``param_bytes`` is the FULL (unsharded) parameter footprint;
    ``tp_param_bytes`` the subset the sharding rules lay out over
    ``tp`` (the big matmul weights — for a transformer, nearly all of
    it). Activations are modeled as ``tokens x d_model`` blocks."""

    param_bytes: float
    tp_param_bytes: float = 0.0
    global_batch: int = 1
    seq_len: int = 1
    d_model: int = 1
    n_layers: int = 1
    n_moe_layers: int = 0
    dtype_bytes: int = 4
    # MoE capacity expansion: the dispatch/combine all-to-alls move
    # (tokens x capacity_factor x top_k) capacity slots, not raw
    # tokens — the a2a byte term scales by both (compared with the
    # compiled shard_map lowering's bytes in tests/test_moe.py).
    moe_capacity_factor: float = 1.0
    moe_top_k: int = 1


def transformer_workload(cfg, global_batch: int,
                         seq_len: Optional[int] = None) -> WorkloadShape:
    """Analytic parameter/activation shape for a transformer config
    (counts the matmul weights; biases/layernorms are noise at this
    resolution)."""
    d, ff, v = cfg.d_model, cfg.d_ff, cfg.vocab_size
    moe = sum(cfg.moe_pattern()) if cfg.n_experts > 0 else 0
    dense = cfg.n_layers - moe
    per_dense = 4 * d * d + 2 * d * ff
    per_moe = 4 * d * d + cfg.n_experts * 2 * d * ff
    matmul_params = v * d + dense * per_dense + moe * per_moe
    dtype = 4  # params/grads travel f32 on the wire-level collectives
    return WorkloadShape(
        param_bytes=float(matmul_params) * dtype,
        tp_param_bytes=float(matmul_params) * dtype,
        global_batch=int(global_batch),
        seq_len=int(seq_len or cfg.max_len),
        d_model=d,
        n_layers=cfg.n_layers,
        n_moe_layers=moe,
        dtype_bytes=dtype,
        moe_capacity_factor=float(getattr(cfg, "capacity_factor", 1.0))
        if moe else 1.0,
        moe_top_k=int(max(1, min(getattr(cfg, "moe_top_k", 1),
                                 cfg.n_experts)))
        if moe else 1,
    )


# Per-collective launch/rendezvous latency expressed in EQUIVALENT
# BYTES (the LogP alpha/beta ratio: latency x bandwidth). Small-tensor
# workloads are latency-bound — a pure byte count would rank a config
# with 4 tiny activation all-reduces per layer "cheaper" than one
# bucketed gradient all-reduce and prune the actual winner. The CPU
# rig's in-process rendezvous is orders slower than ICI, hence the
# much larger equivalent.
DEFAULT_ALPHA_BYTES = {"cpu": 1 << 20, "gpu": 1 << 18, "tpu": 1 << 17}

# Explicit override wins over both the probe and the table (the knob
# the ROADMAP's alpha-calibration follow-up promised to keep).
ALPHA_ENV = "SPARKTORCH_TPU_TUNE_ALPHA_BYTES"

# Tune-result cache knob: "0" disables, a path overrides the default
# cache directory (~/.cache/sparktorch_tpu/tune). The cache is keyed
# by a (workload dims, global batch, device fingerprint, search
# space) hash, so a ``mesh="auto"`` RE-RUN of the same workload on
# the same rig loads the cached winner instead of re-searching (and
# re-compiling every candidate).
TUNE_CACHE_ENV = "SPARKTORCH_TPU_TUNE_CACHE"

# One probe per (backend, device-count) per process: the measurement
# costs two tiny compiles (~1-2s on the CPU rig), and every
# mesh="auto" call in a session shares the same rig.
_ALPHA_PROBE_CACHE: Dict[Tuple[str, int], float] = {}


def alpha_bytes_for_backend(backend: Optional[str] = None) -> float:
    """The table's alpha for ``backend`` (default: the running one).
    A backend the table does not know is an error — it never inherits
    another backend's guess; set ``SPARKTORCH_TPU_TUNE_ALPHA_BYTES``
    or calibrate."""
    if backend is None:
        import jax

        backend = jax.default_backend()
    if backend not in DEFAULT_ALPHA_BYTES:
        raise ValueError(
            f"no default collective alpha for backend {backend!r} "
            f"(known: {sorted(DEFAULT_ALPHA_BYTES)}); set {ALPHA_ENV} "
            "or use calibrate_alpha_bytes()"
        )
    return float(DEFAULT_ALPHA_BYTES[backend])


def calibrate_alpha_bytes(devices: Optional[Sequence[Any]] = None,
                          big_nbytes: int = 4 << 20,
                          repeats: int = 7) -> float:
    """Ground the per-launch alpha in a MEASUREMENT instead of the
    order-of-magnitude table: time one TINY all-reduce (its wall is
    ~pure launch/rendezvous latency) and one BIG one (bandwidth-
    dominated), derive the rig's collective bandwidth from their
    difference, and convert the tiny latency to equivalent bytes —
    the LogP alpha x beta product the cost model's ``total_cost``
    wants. MIN of ``repeats`` timed runs after a compile+warmup pass:
    first-dispatch walls on this rig are 3-10x inflated, and the
    cpu-share scheduler lands whole runs in slow epochs — the fastest
    observed run is the only stable estimate of what the collective
    costs when the rig isn't fighting itself (medians here swung 6x
    between processes).

    Clamped to [16KB, 16MB]: a probe gone sideways (scheduler spike,
    1-device world) must perturb the ranking, not capsize it. Raises
    on no/one device — callers fall back to the table."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from sparktorch_tpu.train.step import shard_map_compat

    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if n < 2:
        raise ValueError("alpha probe needs >= 2 devices")
    key = (str(devices[0].platform), n)
    cached = _ALPHA_PROBE_CACHE.get(key)
    if cached is not None:
        return cached

    mesh = Mesh(np.array(devices), ("probe",))

    def _timed_psum(per_dev_elems: int) -> float:
        fn = jax.jit(shard_map_compat(
            lambda x: jax.lax.psum(x, "probe"), mesh=mesh,
            in_specs=P("probe"), out_specs=P(),
        ))
        x = jnp.zeros((n, per_dev_elems), jnp.float32)
        fn(x).block_until_ready()  # compile + warmup outside the clock
        walls = []
        for _ in range(repeats):
            t0 = time.perf_counter()  # lint-obs: ok (alpha micro-probe min-of-runs timing, not run attribution)
            fn(x).block_until_ready()
            walls.append(time.perf_counter() - t0)  # lint-obs: ok (alpha micro-probe)
        return float(np.min(walls))

    t_tiny = _timed_psum(1)
    big_per_dev = max(1, int(big_nbytes) // 4)
    t_big = _timed_psum(big_per_dev)
    # Model-consistent byte count for the big probe: the same ring
    # all-reduce accounting predict_comm_bytes uses (2(n-1)/n x shard
    # bytes per device, summed over devices) — alpha must come out in
    # the units the prune key adds it to.
    model_bytes = n * (2.0 * (n - 1) / n) * big_per_dev * 4.0
    bandwidth = model_bytes / max(t_big - t_tiny, 1e-6)
    alpha = t_tiny * bandwidth
    alpha = float(min(max(alpha, 1 << 14), 1 << 24))
    _ALPHA_PROBE_CACHE[key] = alpha
    _LOG.info(
        f"[sparktorch_tpu:tune] alpha probe: tiny all-reduce "
        f"{t_tiny * 1e3:.3f}ms, {big_nbytes >> 20}MB all-reduce "
        f"{t_big * 1e3:.3f}ms -> alpha {alpha / 1e6:.2f}MB-eq "
        f"(table default {alpha_bytes_for_backend() / 1e6:.2f}MB-eq)"
    )
    return alpha


def resolve_alpha_bytes(devices: Optional[Sequence[Any]] = None
                        ) -> Tuple[float, str]:
    """The alpha the search should use, with its provenance:
    ``(value, 'env' | 'probe' | 'default')``. Priority: the env
    override, then the per-rig micro-probe, then the backend table
    (probe failure degrades to the table with a warning — calibration
    must never kill a search)."""
    env = os.environ.get(ALPHA_ENV)
    if env:
        try:
            return float(env), "env"
        except ValueError:
            _LOG.warning(
                f"[sparktorch_tpu:tune] bad {ALPHA_ENV}={env!r}; ignoring"
            )
    try:
        return calibrate_alpha_bytes(devices), "probe"
    except Exception as e:
        _LOG.warning(
            f"[sparktorch_tpu:tune] alpha probe failed "
            f"({type(e).__name__}: {e}); using the backend table"
        )
        return alpha_bytes_for_backend(), "default"


def predict_comm_bytes(config: MeshConfig, shape: WorkloadShape,
                       n_devices: int,
                       alpha_bytes: float = 0.0,
                       schedule_meta: Optional[Mapping[str, Any]] = None,
                       ) -> Dict[str, float]:
    """Communication cost of ONE step of ``shape`` under ``config`` —
    ring/bidirectional collective byte models summed over devices,
    plus an alpha term (``alpha_bytes`` equivalent bytes per logical
    collective) for launch/rendezvous latency. Returns per-mechanism
    byte totals, the ``collective_ops`` count, ``total_bytes`` (beta
    term only), and ``total_cost`` (the prune key: bytes + alpha).

    ``schedule_meta`` (pp>1 candidates: ``{"schedule", "virtual_
    stages", "n_micro"}``) makes the ``pp_send_recv`` term schedule-
    aware: interleaved chunks multiply the stage-boundary bytes by V,
    and the term grows the schedule's BUBBLE factor
    (:func:`pp_bubble_fraction` — (S-1)/(M+S-1) for gpipe/1f1b, the
    V-scaled interleaved variant) as a multiplicative penalty, so a
    schedule that idles (S-1)/(M+S-1) of its devices ranks behind one
    that doesn't even at equal wire bytes; the alpha term charges one
    launch per schedule tick (:func:`pp_schedule_ticks`). Without the
    meta a pp>1 config keeps the flat pre-schedule terms.

    Deliberately coarse (no link topology, no overlap): its one job
    is a monotone ranking — more replicated gradient bytes, more
    exposed activation traffic, or more collective launches MUST
    predict more comm — so the pruner never has to execute the
    obviously-worst layouts. The measured phase owns the final
    ranking."""
    sizes = config.resolve(n_devices)
    dp, fsdp, tp = sizes["dp"], sizes["fsdp"], sizes["tp"]
    sp, ep, pp = sizes["sp"], sizes["ep"], sizes["pp"]
    pp_meta = schedule_meta if pp > 1 and schedule_meta else None
    pp_sched = str(pp_meta["schedule"]) if pp_meta else "gpipe"
    pp_v = int(pp_meta.get("virtual_stages", 1)) if pp_meta else 1
    pp_m = int(pp_meta.get("n_micro", 1)) if pp_meta else 1
    pp_bubble = (pp_bubble_fraction(pp_sched, pp, pp_m, pp_v)
                 if pp_meta else 0.0)

    # Per-device parameter/gradient residency after layout: with
    # tp>1 the rule-matched weights shard over tp; EVERYTHING not
    # tp-sharded (including those same weights when tp==1) falls back
    # to fsdp sharding.
    tp_bytes = shape.tp_param_bytes if tp > 1 else 0.0
    rest_bytes = max(shape.param_bytes - tp_bytes, 0.0)
    grad_dev = tp_bytes / tp + rest_bytes / fsdp

    # Activation block per device: the tokens this device computes.
    tokens_dev = (shape.global_batch / (dp * fsdp)) * (shape.seq_len / sp)
    act_dev = tokens_dev * shape.d_model * shape.dtype_bytes

    per_dev = {
        # dp gradient ring all-reduce of the per-device grad shard.
        "dp_all_reduce": (2.0 * (dp - 1) / dp) * grad_dev if dp > 1 else 0.0,
        # fsdp: param all-gather (fwd) + grad reduce-scatter (bwd).
        "fsdp_gather_scatter": (2.0 * (fsdp - 1) / fsdp) * rest_bytes
        if fsdp > 1 else 0.0,
        # tp: two activation all-reduces per layer (attn-out, mlp-out).
        "tp_all_reduce": shape.n_layers * 2 * (2.0 * (tp - 1) / tp) * act_dev
        if tp > 1 else 0.0,
        # sp: ring-attention k/v block rotation, (sp-1) hops per layer.
        "sp_ppermute": shape.n_layers * (sp - 1) * 2.0 * act_dev
        if sp > 1 else 0.0,
        # ep: dispatch + combine all-to-alls per MoE layer. The
        # explicit shard_map lowering (models.transformer._ep_relayout)
        # exchanges (G, e, cap, d) CAPACITY blocks — tokens expanded by
        # capacity_factor x top_k — with each member keeping its own
        # 1/ep slice resident, hence the (ep-1)/ep wire fraction.
        # Held within 4x of the compiled program's a2a bytes by
        # tests/test_moe.py (..._fewer_bytes_than_replicate_and_grounds_tuner).
        "ep_all_to_all": (
            shape.n_moe_layers * 2 * ((ep - 1) / ep) * act_dev
            * shape.moe_capacity_factor * shape.moe_top_k
        )
        if ep > 1 else 0.0,
        # pp: stage-boundary activation sends, fwd + bwd. Interleaved
        # chunks hop V x as often (each device's V chunks each hand
        # off), and the schedule's bubble rides as a multiplicative
        # penalty — idle devices are a cost the byte terms alone
        # cannot see (the measured phase sees it as step wall).
        "pp_send_recv": (2.0 * ((pp - 1) / pp) * act_dev * pp_v
                         * (1.0 + pp_bubble))
        if pp > 1 else 0.0,
    }
    out = {k: n_devices * v for k, v in per_dev.items()}
    out["total_bytes"] = sum(out.values())
    # Logical collective launches per step (the alpha term's count):
    # the bucketed dp grad reduction is ONE launch; tp pays two per
    # layer; sp pays one ppermute per ring hop per layer; a pipeline
    # schedule pays one ppermute per tick per direction.
    ops = (
        (1 if dp > 1 else 0)
        + (2 if fsdp > 1 else 0)
        + (shape.n_layers * 2 if tp > 1 else 0)
        + (shape.n_layers * (sp - 1) if sp > 1 else 0)
        + (shape.n_moe_layers * 2 if ep > 1 else 0)
        + ((2 * pp_schedule_ticks(pp_sched, pp, pp_m, pp_v)
            if pp_meta else 2 * (pp - 1)) if pp > 1 else 0)
    )
    out["collective_ops"] = float(ops)
    out["total_cost"] = out["total_bytes"] + float(alpha_bytes) * ops
    # Bookkeeping (NOT a byte term — added after the totals): what
    # bubble the pp term charged, for artifacts and goldens.
    out["pp_bubble_fraction"] = pp_bubble
    return out


# ---------------------------------------------------------------------------
# Candidates and results
# ---------------------------------------------------------------------------


# Candidate fates. Note there is no "skipped": the early stop ends
# the ROUND loop (every surviving candidate keeps its rounds so far),
# it never leaves a candidate half-decided.
STATUS_MEASURED = "measured"
STATUS_PRUNED = "pruned"
STATUS_FAILED = "failed"


def mesh_label(sizes: Mapping[str, int]) -> str:
    """Compact prom-label-safe spelling: ``dp4xtp2`` (axes of size 1
    omitted; the trivial mesh is ``dp1``)."""
    parts = [f"{a}{sizes[a]}" for a in ALL_AXES if sizes.get(a, 1) > 1]
    return "x".join(parts) if parts else "dp1"


def schedule_suffix(meta: Mapping[str, Any]) -> str:
    """Label suffix for a pipeline-scheduled candidate:
    ``gpipe_m4`` / ``1f1b_m4`` / ``int2_m8`` (interleaved, V chunks,
    M microbatches). Prom-label-safe like :func:`mesh_label`."""
    sched = str(meta["schedule"])
    v = int(meta.get("virtual_stages", 1))
    m = int(meta.get("n_micro", 1))
    name = f"int{v}" if sched == "interleaved" else sched
    return f"{name}_m{m}"


def candidate_label(axes: Mapping[str, int],
                    schedule: Optional[Mapping[str, Any]] = None) -> str:
    base = mesh_label(axes)
    return f"{base}-{schedule_suffix(schedule)}" if schedule else base


@dataclasses.dataclass
class Candidate:
    """One point of the search space and everything decided about it.
    pp>1 candidates carry a ``schedule`` dict (``{"schedule":
    gpipe|1f1b|interleaved, "virtual_stages": V, "n_micro": M}``) —
    the same mesh under two schedules is two candidates."""

    axes: Dict[str, int]
    predicted: Dict[str, float]
    status: str = "pending"
    reason: Optional[str] = None
    measured: Optional[Dict[str, Any]] = None
    score: Optional[float] = None
    schedule: Optional[Dict[str, Any]] = None

    @property
    def predicted_bytes(self) -> float:
        return float(self.predicted.get("total_bytes", 0.0))

    @property
    def predicted_cost(self) -> float:
        """The prune key: beta (bytes) + alpha (launch) terms."""
        return float(self.predicted.get("total_cost",
                                        self.predicted_bytes))

    @property
    def label(self) -> str:
        return candidate_label(self.axes, self.schedule)

    def mesh_config(self) -> MeshConfig:
        sizes = {a: int(self.axes.get(a, 1)) for a in ALL_AXES}
        return MeshConfig(**sizes)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "axes": dict(self.axes),
            "label": self.label,
            "predicted": {k: round(float(v), 2)
                          for k, v in self.predicted.items()},
            "status": self.status,
            "reason": self.reason,
            "measured": dict(self.measured) if self.measured else None,
            "score": self.score,
            "schedule": dict(self.schedule) if self.schedule else None,
        }

    @classmethod
    def from_dict(cls, d: Mapping[str, Any]) -> "Candidate":
        return cls(
            axes={k: int(v) for k, v in (d.get("axes") or {}).items()},
            predicted=dict(d.get("predicted") or {}),
            status=str(d.get("status", "pending")),
            reason=d.get("reason"),
            measured=dict(d["measured"]) if d.get("measured") else None,
            score=d.get("score"),
            schedule=dict(d["schedule"]) if d.get("schedule") else None,
        )


@dataclasses.dataclass
class TuneResult:
    """The whole search: every candidate with its fate, the winner,
    and the bookkeeping a gate needs to audit the decision."""

    n_devices: int
    global_batch: int
    best: Dict[str, int]
    candidates: List[Candidate]
    noise_floor_s: float
    early_stopped: bool
    steps_per_candidate: int     # profiled steps per candidate PER ROUND
    wall_s: float
    exposed_weight: float
    rounds_run: int = 0          # scored interleaved rounds executed
    warmup_rounds: int = 0       # discarded warmup rounds per candidate
    executed_steps_total: int = 0  # ALL profiled steps run, incl. warmup
    candidates_dropped: int = 0  # past the max_candidates cap (logged)
    caps: Dict[str, List[int]] = dataclasses.field(default_factory=dict)
    run_id: Optional[str] = None
    alpha_bytes: float = 0.0     # the per-launch alpha the prune used
    alpha_source: str = "default"  # arg | env | probe | default
    cache_hit: bool = False      # loaded from the tune-result cache
    cache_key: Optional[str] = None  # (workload, rig) fingerprint hash
    # The winner's pipeline schedule when best has pp>1 (None for
    # GSPMD winners): {"schedule", "virtual_stages", "n_micro"} — what
    # make_sharded_train_step(mesh="auto") builds the pp step from.
    best_schedule: Optional[Dict[str, Any]] = None
    # The search's total compile bill — every candidate the tuner
    # compiled (count + summed walls). The mesh='auto' step builder
    # ADDS its own fresh-closure recompile of the winner here the
    # moment the goodput cache-miss probe sees it, so "the auto path
    # compiles its winner twice" is a visible number on the live
    # result, not a README caveat. (The artifact/cache entry carries
    # the search-time bill; a cache HIT run's only compile is the
    # winner's own.)
    compile_count: int = 0
    compile_s_total: float = 0.0

    def best_config(self) -> MeshConfig:
        sizes = {a: int(self.best.get(a, 1)) for a in ALL_AXES}
        return MeshConfig(**sizes)

    @property
    def best_label(self) -> str:
        return candidate_label(self.best, self.best_schedule)

    def ranking(self) -> List[Candidate]:
        """Measured candidates, best (lowest score) first."""
        measured = [c for c in self.candidates
                    if c.status == STATUS_MEASURED and c.score is not None]
        return sorted(measured, key=lambda c: c.score)

    def pruned(self) -> List[Candidate]:
        return [c for c in self.candidates if c.status == STATUS_PRUNED]

    def measured_steps_total(self) -> int:
        return sum(
            int((c.measured or {}).get("n_steps", 0))
            for c in self.candidates if c.status == STATUS_MEASURED
        )

    def to_dict(self) -> Dict[str, Any]:
        return {
            "kind": ARTIFACT_KIND,
            "run_id": self.run_id,
            "n_devices": self.n_devices,
            "global_batch": self.global_batch,
            "best": dict(self.best),
            "best_schedule": (dict(self.best_schedule)
                              if self.best_schedule else None),
            "best_label": self.best_label,
            "noise_floor_s": self.noise_floor_s,
            "early_stopped": self.early_stopped,
            "steps_per_candidate": self.steps_per_candidate,
            "rounds_run": self.rounds_run,
            "warmup_rounds": self.warmup_rounds,
            "measured_steps_total": self.measured_steps_total(),
            "executed_steps_total": self.executed_steps_total,
            "candidates_dropped": self.candidates_dropped,
            "wall_s": self.wall_s,
            "exposed_weight": self.exposed_weight,
            "alpha_bytes": self.alpha_bytes,
            "alpha_source": self.alpha_source,
            "cache_hit": self.cache_hit,
            "cache_key": self.cache_key,
            "compile_count": self.compile_count,
            "compile_s_total": round(self.compile_s_total, 6),
            "caps": {k: list(v) for k, v in self.caps.items()},
            "n_candidates": len(self.candidates),
            "n_measured": sum(c.status == STATUS_MEASURED
                              for c in self.candidates),
            "n_pruned": sum(c.status == STATUS_PRUNED
                            for c in self.candidates),
            "ranking": [c.label for c in self.ranking()],
            "candidates": [c.to_dict() for c in self.candidates],
        }

    @classmethod
    def from_dict(cls, d: Mapping[str, Any]) -> "TuneResult":
        if d.get("kind") != ARTIFACT_KIND:
            raise ValueError(
                f"not a tune artifact (kind={d.get('kind')!r})"
            )
        return cls(
            n_devices=int(d["n_devices"]),
            global_batch=int(d["global_batch"]),
            best={k: int(v) for k, v in d["best"].items()},
            candidates=[Candidate.from_dict(c)
                        for c in d.get("candidates", [])],
            noise_floor_s=float(d.get("noise_floor_s", 0.0)),
            early_stopped=bool(d.get("early_stopped", False)),
            steps_per_candidate=int(d.get("steps_per_candidate", 0)),
            rounds_run=int(d.get("rounds_run", 0)),
            warmup_rounds=int(d.get("warmup_rounds", 0)),
            executed_steps_total=int(d.get("executed_steps_total", 0)),
            candidates_dropped=int(d.get("candidates_dropped", 0)),
            wall_s=float(d.get("wall_s", 0.0)),
            exposed_weight=float(d.get("exposed_weight", 0.0)),
            caps={k: [int(x) for x in v]
                  for k, v in (d.get("caps") or {}).items()},
            run_id=d.get("run_id"),
            alpha_bytes=float(d.get("alpha_bytes", 0.0)),
            alpha_source=str(d.get("alpha_source", "default")),
            cache_hit=bool(d.get("cache_hit", False)),
            cache_key=d.get("cache_key"),
            best_schedule=(dict(d["best_schedule"])
                           if d.get("best_schedule") else None),
            compile_count=int(d.get("compile_count", 0)),
            compile_s_total=float(d.get("compile_s_total", 0.0)),
        )

    def save(self, path: str) -> str:
        """Write the ``tune_result.json`` artifact atomically (tmp +
        rename: a killed tuner must not leave a torn artifact that a
        later ``mesh="auto"`` run half-parses)."""
        import os

        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(self.to_dict(), f, indent=2)  # lint-obs: ok (tune artifact persistence, not telemetry)
        os.replace(tmp, path)
        return path

    @classmethod
    def load(cls, path: str) -> "TuneResult":
        with open(path) as f:
            return cls.from_dict(json.load(f))

    # -- bus publication ---------------------------------------------------

    def publish(self, telemetry=None) -> None:
        """Put the search on the telemetry bus under ``xprof.tune_*``
        names (the same contract as
        :meth:`~sparktorch_tpu.obs.xprof.TraceAnalysis.publish`):
        per-candidate wall samples, outcome counters, winner gauges,
        one condensed ``xprof_tune`` event, and the full document as
        the ``xprof_tune`` snapshot section — so a ``/telemetry``
        scrape, a collector merge, and ``obs.timeline --tune`` all
        render the same search."""
        from sparktorch_tpu.obs.telemetry import get_telemetry

        tele = telemetry or get_telemetry()
        for c in self.candidates:
            tele.counter("xprof.tune_candidates_total",
                         labels={"outcome": c.status})
            if c.status == STATUS_MEASURED and c.measured:
                tele.observe("xprof.tune_candidate_step_wall_s",
                             float(c.measured.get("step_wall_s", 0.0)),
                             labels={"mesh": c.label})
        tele.counter("xprof.tune_runs_total")
        best = self.ranking()
        if best:
            tele.gauge("xprof.tune_best_step_wall_s",
                       float(best[0].measured.get("step_wall_s", 0.0)))
            tele.gauge("xprof.tune_best_exposed_fraction",
                       float(best[0].measured.get(
                           "exposed_comm_fraction", 0.0)))
        tele.gauge("xprof.tune_noise_floor_s", self.noise_floor_s)
        tele.gauge("xprof.tune_wall_s", self.wall_s)
        tele.event(
            "xprof_tune",
            best=self.best_label,
            n_candidates=len(self.candidates),
            n_measured=sum(c.status == STATUS_MEASURED
                           for c in self.candidates),
            n_pruned=sum(c.status == STATUS_PRUNED
                         for c in self.candidates),
            early_stopped=self.early_stopped,
            noise_floor_s=self.noise_floor_s,
            wall_s=self.wall_s,
            ranking=[c.label for c in self.ranking()][:8],
        )
        tele.set_section("xprof_tune", self.to_dict())


# ---------------------------------------------------------------------------
# Scoring (the xprof hook)
# ---------------------------------------------------------------------------


def score_wall(median_wall_s: float, exposed_fraction: float,
               exposed_weight: float) -> float:
    """THE scoring formula — LOWER is better. The decision variable
    is the median step wall (robust to one GC pause on a noisy rig);
    the exposed-comm fraction rides as a multiplicative penalty
    (``wall * (1 + w * exposed)``) so that two configs inside each
    other's noise tie-break toward the one whose collectives hide
    under compute — that one keeps its rank when compute grows.
    Shared by :func:`score_analysis` (single capture — what the
    golden-fixture test pins) and the interleaved-round aggregation
    (:func:`_aggregate_rounds` — the production decision path), so
    the pinned formula IS the deciding one."""
    return median_wall_s * (1.0 + exposed_weight * exposed_fraction)


def score_analysis(analysis, exposed_weight: float = 0.25
                   ) -> Tuple[float, Dict[str, Any]]:
    """Score one candidate's :class:`TraceAnalysis` via
    :func:`score_wall`. Returns ``(score, measured_record)``."""
    stats = analysis.step_wall_stats()
    exposed = analysis.exposed_comm_fraction
    score = score_wall(stats["median_s"], exposed, exposed_weight)
    measured = {
        "step_wall_s": stats["median_s"],
        "step_wall_mean_s": stats["mean_s"],
        "spread_s": stats["spread_s"],
        "n_steps": stats["n"],
        "comm_fraction": analysis.comm_fraction,
        "overlap_fraction": analysis.overlap_fraction,
        "exposed_comm_fraction": exposed,
        "comm_s": analysis.comm_s,
        "compute_s": analysis.compute_s,
        "n_collective_events": analysis.n_collective_events,
        "collective_counts": analysis.family_counts(),
    }
    return score, measured


# ---------------------------------------------------------------------------
# Measurement (the only part that touches the accelerator)
# ---------------------------------------------------------------------------


def prepare_candidate(spec, config: MeshConfig, batch, devices,
                      tx=None, seq_sharded: bool = False,
                      telemetry=None) -> Callable[[int], Dict[str, Any]]:
    """Compile ``spec`` under ``config`` and return a ROUND RUNNER:
    ``runner(steps)`` captures one fresh XLA profile around ``steps``
    train steps (state carried across rounds), analyzes it offline,
    and returns the round record (``walls`` per step, comm/overlap/
    exposed fractions, collective counts). Compilation happens here,
    OUTSIDE any capture — a capture containing the multi-second XLA
    compile floods the profiler buffer and the step markers vanish
    (see obs/xprof WATCH note). Raises on compile failure (the caller
    records the candidate as failed and moves on). The runner carries
    ``runner.compile_s``."""
    import tempfile

    import jax

    from sparktorch_tpu.obs.xprof import analyze_trace
    from sparktorch_tpu.parallel.compat import set_mesh as _set_mesh
    from sparktorch_tpu.parallel.mesh import build_mesh
    from sparktorch_tpu.train.sharded import (
        create_sharded_state,
        make_sharded_train_step,
        shard_batch,
    )
    from sparktorch_tpu.utils.tracing import profile_run

    from sparktorch_tpu.obs import goodput as _goodput

    tx = tx or spec.make_optimizer()
    module = spec.make_module()
    mesh = build_mesh(config, devices)
    # The whole build-and-first-dispatch is one compile LedgerSpan:
    # tune-time compile seconds land in an armed run ledger's
    # ``compile`` bucket (and the span's duration is the compile bill
    # the TuneResult stamps) instead of vanishing into idle.
    with _goodput.span("compile", {"site": "tune"}) as _comp:
        state, shardings = create_sharded_state(
            spec, mesh, jax.random.key(0), sample_x=batch.x[:1], tx=tx,
        )
        # No profile_dir here: the runner owns its per-round captures.
        step = make_sharded_train_step(
            module.apply, spec.loss_fn(), tx, mesh, shardings,
            seq_sharded=seq_sharded, telemetry=telemetry,
        )
        sharded = shard_batch(batch, mesh, seq_sharded=seq_sharded)
        with _set_mesh(mesh):
            state, m = step.jitted(state, sharded)  # compile, uncaptured
        jax.block_until_ready(m.loss)
    compile_s = _comp.duration_s
    ledger = _goodput.active()
    if ledger is not None and ledger.telemetry is not None:
        # The site-labeled counter note_compile used to emit; the
        # LedgerSpan carries the seconds, this carries the count.
        ledger.telemetry.counter("goodput.compiles_total",
                                 labels={"site": "tune"})
    carried = {"state": state}

    def runner(steps: int) -> Dict[str, Any]:
        with tempfile.TemporaryDirectory() as profile_dir:
            # analyze=False: 1 capture per (candidate, round) — the
            # per-round budgets aggregate into ONE published tune
            # record; auto-publishing every capture would spam the
            # xprof.* series with per-round samples.
            with profile_run(profile_dir, telemetry=telemetry,
                             analyze=False):
                st = carried["state"]
                for _ in range(steps):
                    st, metrics = step(st, sharded)
                    # Drain per step so each step's device work lands
                    # inside its own attribution slice.
                    jax.block_until_ready(metrics.loss)
                carried["state"] = st
            analysis = analyze_trace(profile_dir)
        if not analysis.steps:
            raise RuntimeError("profiler emitted no usable capture")
        return {
            "walls": [s.wall_s for s in analysis.steps],
            "comm_fraction": analysis.comm_fraction,
            "overlap_fraction": analysis.overlap_fraction,
            "exposed_comm_fraction": analysis.exposed_comm_fraction,
            "n_collective_events": analysis.n_collective_events,
            "counts": analysis.family_counts(),
            "loss": float(metrics.loss),
        }

    runner.compile_s = compile_s
    return runner


def prepare_pipeline_candidate(spec, config: MeshConfig, batch, devices,
                               tx=None, seq_sharded: bool = False,
                               telemetry=None,
                               schedule_meta: Optional[Mapping[str, Any]]
                               = None) -> Callable[[int], Dict[str, Any]]:
    """The pp>1 analog of :func:`prepare_candidate`: build the
    candidate through the PIPELINE trainer's schedule path
    (:func:`sparktorch_tpu.train.pipeline.make_pp_train_step`) —
    gpipe / 1f1b / interleaved per ``schedule_meta`` — and return the
    same round-runner contract. The measured walls therefore include
    the schedule's real bubble and stage-boundary traffic, which is
    the whole point of opening pp to the search.

    MoE candidates with ep>1 thread the a2a grouping OPT-IN through
    the built step (``pp_moe_group_size`` — the same group-size choice
    the gpipe-ep dryrun config makes), so the measured step runs the
    all-to-all dispatch layout the mesh pays for, not the replicated
    fallback."""
    import tempfile

    import jax
    import jax.numpy as jnp

    from sparktorch_tpu.obs import goodput as _goodput
    from sparktorch_tpu.obs.xprof import analyze_trace
    from sparktorch_tpu.parallel.mesh import build_mesh
    from sparktorch_tpu.train.pipeline import build_pp_schedule_step
    from sparktorch_tpu.utils.data import DataBatch
    from sparktorch_tpu.utils.tracing import profile_run

    if not schedule_meta:
        raise ValueError("pp>1 candidate without a schedule meta")
    rows = int(batch.x.shape[0])
    seq = int(batch.x.shape[1]) if batch.x.ndim >= 2 else 1
    mesh = build_mesh(config, devices)
    b = DataBatch(
        x=jnp.asarray(np.asarray(batch.x), jnp.int32),
        y=jnp.asarray(np.asarray(batch.y), jnp.int32),
        w=jnp.asarray(np.asarray(batch.w), jnp.float32),
    )
    # Same compile LedgerSpan contract as the GSPMD prepare: the
    # schedule build + first dispatch is the candidate's compile bill.
    # The build itself is the ONE shared recipe
    # (pipeline.build_pp_schedule_step) the mesh='auto' winner also
    # goes through — measured layout == production layout by
    # construction.
    with _goodput.span("compile", {"site": "tune"}) as _comp:
        state, step, _cfg, _head = build_pp_schedule_step(
            spec, mesh, schedule_meta, rows, seq, tx=tx,
            sample_x=batch.x[:1],
        )
        state, loss = step(state, b)  # compile, uncaptured
        jax.block_until_ready(loss)
    compile_s = _comp.duration_s
    ledger = _goodput.active()
    if ledger is not None and ledger.telemetry is not None:
        # The site-labeled counter note_compile used to emit; the
        # LedgerSpan carries the seconds, this carries the count.
        ledger.telemetry.counter("goodput.compiles_total",
                                 labels={"site": "tune"})
    carried = {"state": state}

    def runner(steps: int) -> Dict[str, Any]:
        with tempfile.TemporaryDirectory() as profile_dir:
            with profile_run(profile_dir, telemetry=telemetry,
                             analyze=False):
                st = carried["state"]
                for _ in range(steps):
                    st, loss_ = step(st, b)
                    jax.block_until_ready(loss_)
                carried["state"] = st
            analysis = analyze_trace(profile_dir)
        if not analysis.steps:
            raise RuntimeError("profiler emitted no usable capture")
        return {
            "walls": [s.wall_s for s in analysis.steps],
            "comm_fraction": analysis.comm_fraction,
            "overlap_fraction": analysis.overlap_fraction,
            "exposed_comm_fraction": analysis.exposed_comm_fraction,
            "n_collective_events": analysis.n_collective_events,
            "counts": analysis.family_counts(),
            "loss": float(loss_),
        }

    runner.compile_s = compile_s
    return runner


def _aggregate_rounds(rounds: List[Dict[str, Any]], compile_s: float,
                      exposed_weight: float
                      ) -> Tuple[float, Dict[str, Any]]:
    """Fold a candidate's round records into ``(score, measured)`` —
    the same formula as :func:`score_analysis`, over the pooled
    walls."""
    from sparktorch_tpu.obs.xprof import wall_stats

    walls = [w for r in rounds for w in r["walls"]]
    stats = wall_stats(walls)
    exposed = sum(r["exposed_comm_fraction"] for r in rounds) / len(rounds)
    score = score_wall(stats["median_s"], exposed, exposed_weight)
    counts: Dict[str, int] = {}
    for r in rounds:
        for fam, n in (r.get("counts") or {}).items():
            counts[fam] = counts.get(fam, 0) + int(n)
    measured = {
        "step_wall_s": stats["median_s"],
        "spread_s": stats["spread_s"],
        "n_steps": stats["n"],
        "rounds": len(rounds),
        "comm_fraction": sum(r["comm_fraction"]
                             for r in rounds) / len(rounds),
        "overlap_fraction": sum(r["overlap_fraction"]
                                for r in rounds) / len(rounds),
        "exposed_comm_fraction": exposed,
        "n_collective_events": sum(r["n_collective_events"]
                                   for r in rounds),
        "collective_counts": counts,
        "compile_s": compile_s,
    }
    return score, measured


# ---------------------------------------------------------------------------
# The driver
# ---------------------------------------------------------------------------


def workload_for(spec, batch, seq_len: Optional[int] = None
                 ) -> Tuple[WorkloadShape, Optional[Any]]:
    """(WorkloadShape, transformer config or None) for a ModelSpec +
    representative batch. Transformer modules get the analytic shape;
    anything else gets its parameter bytes from an abstract init trace
    (``jax.eval_shape`` — no device execution) with no tp share."""
    module = spec.make_module()
    cfg = getattr(module, "config", None)
    global_batch = int(batch.x.shape[0])
    if cfg is not None and hasattr(cfg, "d_model"):
        seq = seq_len or (batch.x.shape[1] if batch.x.ndim >= 2
                          else cfg.max_len)
        return transformer_workload(cfg, global_batch, seq), cfg
    import jax
    import numpy as np

    abstract = jax.eval_shape(
        lambda k: module.init(k, np.asarray(batch.x[:1])),
        jax.random.key(0),
    )
    param_bytes = float(sum(
        math.prod(leaf.shape) * leaf.dtype.itemsize
        for leaf in jax.tree.leaves(abstract)
    ))
    return WorkloadShape(param_bytes=param_bytes,
                         global_batch=global_batch), None


def pp_schedule_metas(sizes: Mapping[str, int], cfg,
                      global_batch: int,
                      max_virtual: int = 4) -> List[Dict[str, Any]]:
    """Legal schedule candidates for one pp>1 mesh: ``gpipe`` and
    ``1f1b`` (V=1), plus every ``interleaved`` V in [2, max_virtual]
    with ``n_layers % (pp*V) == 0`` — each fanned out over EVERY legal
    ``n_micro`` (M <= max(2*pp, 4) dividing the per-dp-shard rows;
    interleaved additionally needs M % pp == 0). The schedule-aware
    bubble term (S-1)/(M+S-1) and the per-tick alpha charge pull in
    opposite directions — more microbatches shrink the bubble but pay
    more launches — so M is a real search dimension the cost model
    ranks, not a heuristic pick; the cap keeps the fan-out bounded
    (microbatches beyond ~2S shave little bubble but still multiply
    ticks). Empty when the pipeline trainer cannot run this mesh at
    all (non-transformer spec, MoE x tp, sp>1 without ring attention,
    no legal microbatch split, non-uniform dense/MoE stage pattern) —
    those meshes simply don't enter the candidate list, mirroring
    ``make_pp_train_step``'s own validation."""
    S = int(sizes.get("pp", 1))
    if S <= 1 or cfg is None or not hasattr(cfg, "n_layers"):
        return []
    dp = int(sizes.get("dp", 1)) * int(sizes.get("fsdp", 1))
    tp = int(sizes.get("tp", 1))
    sp = int(sizes.get("sp", 1))
    ep = int(sizes.get("ep", 1))
    n_layers = int(cfg.n_layers)
    if n_layers % S != 0 or dp < 1 or global_batch % dp != 0:
        return []
    per_shard = global_batch // dp
    pattern = (tuple(cfg.moe_pattern())
               if getattr(cfg, "n_experts", 0) > 0 else ())
    has_moe = any(pattern)
    if has_moe and tp > 1:
        return []                 # experts shard over ep, not tp
    if ep > 1 and not has_moe:
        return []                 # nothing to shard over ep
    if sp > 1 and getattr(cfg, "attn_impl", "dense") != "ring":
        return []                 # sp needs global attention via ring

    def _uniform(n_chunks: int) -> bool:
        """Every chunk must hold the same dense/MoE sequence (the
        trainer's stage/chunk-pattern validation)."""
        if not has_moe:
            return True
        if n_layers % n_chunks:
            return False
        c = n_layers // n_chunks
        chunks = [pattern[i * c:(i + 1) * c] for i in range(n_chunks)]
        return all(ch == chunks[0] for ch in chunks)

    def _legal_ms(multiple: int) -> List[int]:
        cap = max(2 * S, 4)
        return [m for m in range(multiple, min(per_shard, cap) + 1,
                                 multiple)
                if per_shard % m == 0]

    metas: List[Dict[str, Any]] = []
    if _uniform(S):
        for m in _legal_ms(1):
            metas.append({"schedule": "gpipe", "virtual_stages": 1,
                          "n_micro": m})
            metas.append({"schedule": "1f1b", "virtual_stages": 1,
                          "n_micro": m})
    ms_int = _legal_ms(S)         # interleaved ticks need M % pp == 0
    if ms_int:
        # range is empty when max_virtual < 2: a caller disabling
        # interleaving gets exactly gpipe + 1f1b.
        for v in range(2, int(max_virtual) + 1):
            if n_layers % (S * v) != 0 or not _uniform(S * v):
                continue
            for m in ms_int:
                metas.append({"schedule": "interleaved",
                              "virtual_stages": v, "n_micro": m})
    return metas


# ---------------------------------------------------------------------------
# Tune-result cache (ROADMAP item-4 follow-up)
# ---------------------------------------------------------------------------


def device_fingerprint(devices: Sequence[Any]) -> Dict[str, Any]:
    """What makes this rig THIS rig for mesh selection: backend,
    device kinds, and count. Deliberately excludes the calibrated
    alpha (a measurement input that jitters run to run — two runs on
    the same hardware must share a cache entry)."""
    kinds = sorted({str(getattr(d, "device_kind", "?")) for d in devices})
    platforms = sorted({str(getattr(d, "platform", "?")) for d in devices})
    return {"n_devices": len(devices), "platforms": platforms,
            "kinds": kinds}


def _tx_cache_tag(tx) -> Optional[str]:
    """Coarse deterministic optimizer fingerprint for the tune-result
    cache: the STRUCTURE of its init state on a probe param (adam's
    moment leaves vs sgd's empty state — the state tree is what fsdp
    shards and the measured step applies). Hyperparameters like the
    learning rate don't change which mesh wins and deliberately don't
    key; optax transforms carry no stable repr, so structure is the
    only deterministic handle."""
    if tx is None:
        return None
    try:
        import jax as _jax

        state = tx.init({"w": np.zeros((1,), np.float32)})
        leaves, treedef = _jax.tree_util.tree_flatten(state)
        dtypes = [str(getattr(leaf, "dtype", type(leaf).__name__))
                  for leaf in leaves]
        return f"{treedef}:{dtypes}"
    except Exception:  # noqa: BLE001 - an exotic tx degrades, not dies
        return type(tx).__name__


def tune_cache_key(shape: WorkloadShape, caps: Mapping[str, Sequence[int]],
                   axes: Sequence[str], devices: Sequence[Any],
                   seq_sharded: bool, measure_top_k: int,
                   exposed_weight: float, *, max_candidates: int = 64,
                   steps: int = 4, repeats: int = 3,
                   min_rounds: int = 2, noise_mult: float = 2.0,
                   tx_tag: Optional[str] = None,
                   alpha_override: Optional[str] = None) -> str:
    """Deterministic hash of everything that decides WHICH mesh wins:
    the workload's dims (model shape + global batch), the rig
    fingerprint, and the search space/scoring/measurement knobs
    (``max_candidates`` can TRUNCATE the candidate list — an entry
    searched under a tighter cap must not satisfy a wider re-run;
    the round/step knobs decide measurement fidelity; ``tx_tag``
    distinguishes optimizers by state structure; ``alpha_override``
    keys an EXPLICIT alpha — kwarg or env — which deterministically
    changes the prune ranking, while the probe-measured alpha stays
    excluded because it jitters). Two calls with the same key would
    re-run the identical search — which is exactly what the cache
    skips."""
    import hashlib

    doc = {
        # Bump when the cost model, scoring, or enumeration changes
        # behavior: an on-disk entry searched by obsolete logic must
        # not satisfy the new version's key. Schema 2: the MoE
        # dispatch rewrite (explicit shard_map all-to-alls, mesh-
        # anchored group partition, capacity-aware ep byte term) —
        # entries measured under the degraded partitioner-derived
        # lowering must not satisfy an ep search against the new one.
        # Schema 3: pipeline schedules opened to the search (pp>1
        # candidates x {gpipe, 1f1b, interleaved} x virtual_stages,
        # schedule-aware bubble/tick terms in the cost model, winners
        # may carry a best_schedule) — a pre-rewrite entry searched
        # with pp locked to 1 must not satisfy the opened space.
        # Schema 4: n_micro opened to the search (every legal M <=
        # max(2*pp, 4) fans out per schedule x V instead of the
        # deterministic largest-M pick) — an entry whose candidates
        # were enumerated under the single-M heuristic must not
        # satisfy the widened space.
        "schema": 4,
        "moe_dispatch": "shard_map_a2a",
        "pp_schedules": list(PP_SCHEDULES),
        "shape": dataclasses.asdict(shape),
        "caps": {k: sorted(int(x) for x in v) for k, v in caps.items()},
        "axes": list(axes),
        "device": device_fingerprint(devices),
        "seq_sharded": bool(seq_sharded),
        "measure_top_k": int(measure_top_k),
        "exposed_weight": float(exposed_weight),
        "max_candidates": int(max_candidates),
        "measure": [int(steps), int(repeats), int(min_rounds),
                    float(noise_mult)],
        "tx": tx_tag,
        "alpha_override": alpha_override,
    }
    blob = json.dumps(doc, sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()[:24]


def _tune_cache_dir() -> Optional[str]:
    """The cache directory, or None when disabled
    (``SPARKTORCH_TPU_TUNE_CACHE=0``). A non-flag env value is a
    directory override."""
    env = os.environ.get(TUNE_CACHE_ENV)
    if env is not None:
        env = env.strip()
        if env in ("0", "false", "off"):
            return None
        if env not in ("", "1", "true", "on"):
            return env
    return os.path.join(os.path.expanduser("~"), ".cache",
                        "sparktorch_tpu", "tune")


def _cache_load(key: str) -> Optional[TuneResult]:
    cache_dir = _tune_cache_dir()
    if cache_dir is None:
        return None
    path = os.path.join(cache_dir, f"tune_{key}.json")
    try:
        result = TuneResult.load(path)
    except (OSError, ValueError, KeyError):
        return None  # absent or torn: a cache never fails a search
    return result


def _cache_store(key: str, result: TuneResult) -> None:
    cache_dir = _tune_cache_dir()
    if cache_dir is None:
        return
    try:
        os.makedirs(cache_dir, exist_ok=True)
        result.save(os.path.join(cache_dir, f"tune_{key}.json"))
    except OSError:
        pass  # read-only home: the search result still returns


def autotune(
    spec,
    batch,
    devices: Optional[Sequence[Any]] = None,
    *,
    tx=None,
    caps: Optional[Mapping[str, Sequence[int]]] = None,
    axes: Sequence[str] = DEFAULT_AXES,
    steps: int = 4,
    repeats: int = 3,
    warmup_rounds: int = 1,
    min_rounds: int = 2,
    measure_top_k: int = 4,
    exposed_weight: float = 0.25,
    noise_mult: float = 2.0,
    exhaustive: bool = False,
    seq_sharded: Optional[bool] = None,
    alpha_bytes: Optional[float] = None,
    max_candidates: int = 64,
    artifact_path: Optional[str] = None,
    telemetry=None,
    measure_fn: Optional[Callable] = None,
    cache: bool = False,
) -> TuneResult:
    """Search mesh configs for ``spec`` on ``batch``; return the
    :class:`TuneResult` whose ``best_config()`` is the chosen mesh.

    The ``measure_top_k`` survivors of the comm-volume prune are
    compiled once each, then measured in INTERLEAVED rounds of
    ``steps`` profiled steps per candidate (up to ``repeats`` scored
    rounds, after ``warmup_rounds`` discarded ones — the FIRST
    capture per candidate is systematically inflated by profiler
    init, XLA autotuning, and allocator warmup and must not vote) —
    back-to-back per-candidate timing on a cpu-share rig lands
    whole windows in slow scheduler epochs and swings 10x; the
    interleave samples every candidate across the same epochs, and
    the pooled median cancels them. The round loop early-stops after
    ``min_rounds`` once the leader's margin over the runner-up
    exceeds ``noise_mult x`` the noise floor (cross-candidate max of
    p75-p25 wall spreads). ``exhaustive=True`` disables pruning and
    the early stop — every legal candidate is measured for all
    rounds (the CLI's ``--exhaustive``: a referee for the pruned
    search). ``measure_fn``
    (same signature as :func:`prepare_candidate`) lets tests pin the
    decision logic without a backend. ``cache=True`` keys the result
    by a (workload dims, rig fingerprint, search space) hash and
    loads a prior run's winner instead of re-searching (artifact
    records ``cache_hit``; ``SPARKTORCH_TPU_TUNE_CACHE=0`` opts out,
    a path value relocates the cache directory)."""
    t_start = time.perf_counter()  # lint-obs: ok (artifact wall_s stat; compile regions carry their own LedgerSpans)
    if devices is None:
        import jax

        devices = jax.devices()
    n_devices = len(devices)
    global_batch = int(batch.x.shape[0])

    shape, cfg = workload_for(spec, batch)
    if seq_sharded is None:
        # Sequence sharding needs token-level targets (y carries a
        # sequence dim); a classifier's scalar labels cannot split
        # over sp.
        seq_sharded = getattr(batch.y, "ndim", 1) >= 2
    if caps is None:
        caps = transformer_caps(cfg, shape.seq_len) if cfg is not None \
            else {"tp": (1,), "sp": (1,), "ep": (1,), "pp": (1,)}
    caps = dict(caps)
    if not seq_sharded:
        caps["sp"] = (1,)

    # Tune-result cache: a re-run of the same (workload dims, rig
    # fingerprint, search space) loads the cached winner instead of
    # re-searching — checked BEFORE the alpha probe, which is itself
    # seconds of compile. Only real searches participate: a scripted
    # measure_fn (tests) or exhaustive referee run must never be
    # satisfied — or poisoned — by a cache entry, and
    # SPARKTORCH_TPU_TUNE_CACHE=0 kills it globally.
    cache_key: Optional[str] = None
    use_cache = (cache and measure_fn is None and not exhaustive
                 and _tune_cache_dir() is not None)
    if use_cache:
        cache_key = tune_cache_key(shape, caps, axes, devices,
                                   seq_sharded, measure_top_k,
                                   exposed_weight,
                                   max_candidates=max_candidates,
                                   steps=steps, repeats=repeats,
                                   min_rounds=min_rounds,
                                   noise_mult=noise_mult,
                                   tx_tag=_tx_cache_tag(tx),
                                   alpha_override=(
                                       str(alpha_bytes)
                                       if alpha_bytes is not None
                                       else os.environ.get(ALPHA_ENV)))
        cached = _cache_load(cache_key)
        if cached is not None:
            cached.cache_hit = True
            cached.cache_key = cache_key
            # The compile bill is per-RUN, not per-search: a cache hit
            # compiled nothing here, and the live result (and the
            # artifact a hit run writes) must report what THIS process
            # paid — zero so far; the mesh='auto' builder adds the
            # winner's own compile when it happens. The cache ENTRY on
            # disk keeps the original search-time bill.
            cached.compile_count = 0
            cached.compile_s_total = 0.0
            # Same per-RUN semantics for the wall: the entry stores
            # the original search's wall, but THIS process only paid
            # the lookup.
            cached.wall_s = time.perf_counter() - t_start  # lint-obs: ok (artifact stat)
            cached.publish(telemetry)
            if artifact_path:
                cached.save(artifact_path)
            _LOG.info(
                f"[sparktorch_tpu:tune] cache HIT {cache_key}: "
                f"{cached.best_label} (search skipped; "
                f"{TUNE_CACHE_ENV}=0 to disable)"
            )
            return cached

    # Enumerate the FULL legal space — the cost model is what decides
    # what gets dropped, never enumeration order.
    configs = enumerate_candidates(n_devices, caps, global_batch,
                                   axes=axes)
    if not configs:
        raise ValueError(
            f"no legal mesh for {n_devices} devices / batch "
            f"{global_batch} under caps {caps}"
        )
    alpha_source = "arg"
    if alpha_bytes is None:
        # Per-rig calibration: env override > one-time micro-probe
        # (a tiny all-reduce timed at search start) > backend table.
        alpha_bytes, alpha_source = resolve_alpha_bytes(devices)
    # pp=1 meshes are one candidate each (the GSPMD trainer); a pp>1
    # mesh fans out into one candidate PER legal schedule (gpipe /
    # 1f1b / interleaved-V), each with its own schedule-aware
    # prediction — and drops out entirely when the pipeline trainer
    # cannot run it (pp_schedule_metas mirrors its validation; the
    # spec-level gates — cross-entropy family, untied embeddings —
    # mirror train_distributed_pipeline's).
    pp_trainable = (
        cfg is not None
        and str(getattr(spec, "loss", "cross_entropy")) in (
            "cross_entropy", "cross_entropy_fused", "nll")
        and not bool(getattr(cfg, "tie_embeddings", False))
    )
    candidates = []
    for c in configs:
        sizes = c.resolve(n_devices)
        if sizes.get("pp", 1) > 1:
            if not pp_trainable:
                continue
            for meta in pp_schedule_metas(sizes, cfg, global_batch):
                candidates.append(Candidate(
                    axes=sizes,
                    predicted=predict_comm_bytes(
                        c, shape, n_devices, alpha_bytes=alpha_bytes,
                        schedule_meta=meta),
                    schedule=meta,
                ))
            continue
        candidates.append(Candidate(
            axes=sizes,
            predicted=predict_comm_bytes(c, shape, n_devices,
                                         alpha_bytes=alpha_bytes),
        ))
    # Predicted order, cheapest comm first; ties keep enumeration
    # order (the sort is stable), so the whole pass is deterministic.
    candidates.sort(key=lambda c: c.predicted_cost)
    candidates_dropped = 0
    if len(candidates) > max_candidates:
        # Combinatorial-explosion guard, applied AFTER the cost
        # ranking so what falls off is the model's worst tail — and
        # loudly, not silently (the dropped count rides the artifact).
        candidates_dropped = len(candidates) - max_candidates
        _LOG.warning(
            f"[sparktorch_tpu:tune] {candidates_dropped} worst-"
            f"predicted candidates dropped past the "
            f"max_candidates={max_candidates} cap"
        )
        candidates = candidates[:max_candidates]

    to_measure = candidates if exhaustive else candidates[:measure_top_k]
    measure_ids = {id(c) for c in to_measure}
    for rank, c in enumerate(candidates):
        if id(c) in measure_ids:
            continue
        c.status = STATUS_PRUNED
        c.reason = (
            f"comm_model: rank {rank} of {len(candidates)} "
            f"({c.predicted_cost / 1e6:.2f}MB-eq/step predicted vs "
            f"{candidates[0].predicted_cost / 1e6:.2f}MB-eq best)"
        )

    # Phase A: compile every survivor (outside any capture). A layout
    # the partitioner rejects becomes a failed candidate, never a
    # failed search. Each successful prepare is one XLA compile —
    # counted + summed into the result's compile bill. The real
    # prepare paths time their build inside a ``compile`` LedgerSpan,
    # so tune-time compile seconds land in an armed goodput ledger by
    # themselves; only an injected measure_fn (scripted tests) still
    # goes through note_compile, or its declared bill would vanish.
    runners: List[Tuple[Candidate, Callable]] = []
    compile_count = 0
    compile_s_total = 0.0
    import inspect as _inspect

    def _accepts_schedule(fn) -> bool:
        try:
            params = _inspect.signature(fn).parameters
        except (TypeError, ValueError):
            return False
        return "schedule_meta" in params or any(
            p.kind is _inspect.Parameter.VAR_KEYWORD
            for p in params.values())

    for cand in to_measure:
        if measure_fn is not None:
            prepare = measure_fn
        elif cand.schedule is not None:
            prepare = prepare_pipeline_candidate
        else:
            prepare = prepare_candidate
        kwargs: Dict[str, Any] = {}
        if cand.schedule is not None and _accepts_schedule(prepare):
            kwargs["schedule_meta"] = cand.schedule
        try:
            runner = prepare(
                spec, cand.mesh_config(), batch, devices, tx=tx,
                seq_sharded=seq_sharded, telemetry=telemetry, **kwargs,
            )
        except Exception as e:  # one bad layout must not kill the search
            cand.status = STATUS_FAILED
            cand.reason = f"{type(e).__name__}: {e}"
            _LOG.warning(f"[sparktorch_tpu:tune] candidate {cand.label} "
                         f"failed to prepare: {cand.reason}")
            continue
        compile_count += 1
        cand_compile_s = float(getattr(runner, "compile_s", 0.0))
        compile_s_total += cand_compile_s
        if measure_fn is not None:
            from sparktorch_tpu.obs import goodput as _goodput

            _goodput.note_compile(cand_compile_s, site="tune")
        runners.append((cand, runner))

    # Phase B: interleaved measurement rounds. Every live candidate
    # runs `steps` captured steps per round; scores re-aggregate over
    # the pooled walls after each round.
    rounds: Dict[int, List[Dict[str, Any]]] = {id(c): [] for c, _ in runners}
    noise_floor = 0.0
    early_stopped = False
    rounds_run = 0
    executed_steps = 0  # EVERY profiled step run, warmup included
    for raw_rnd in range(warmup_rounds + repeats):
        warming = raw_rnd < warmup_rounds
        rnd = raw_rnd - warmup_rounds
        live = [(c, r) for c, r in runners if c.status != STATUS_FAILED]
        if not live:
            break
        for cand, runner in live:
            try:
                executed_steps += steps
                record = runner(steps)
                if warming:
                    continue  # warmup capture: executed, never scored
                rounds[id(cand)].append(record)
            except Exception as e:
                cand.status = STATUS_FAILED
                cand.reason = f"{type(e).__name__}: {e}"
                cand.score = None
                cand.measured = None
                _LOG.warning(f"[sparktorch_tpu:tune] candidate "
                             f"{cand.label} failed mid-measure: "
                             f"{cand.reason}")
                continue
            score, record = _aggregate_rounds(
                rounds[id(cand)], getattr(runner, "compile_s", 0.0),
                exposed_weight,
            )
            cand.status = STATUS_MEASURED
            cand.score = float(score)
            cand.measured = record
        if warming:
            continue
        rounds_run = rnd + 1
        measured = [c for c, _ in runners if c.status == STATUS_MEASURED]
        if not measured:
            continue
        noise_floor = max((float(c.measured.get("spread_s", 0.0))
                           for c in measured), default=0.0)
        ranked = sorted(measured, key=lambda c: c.score)
        _LOG.info(
            f"[sparktorch_tpu:tune] round {rnd + 1}/{repeats}: "
            + ", ".join(
                f"{c.label} {c.measured['step_wall_s'] * 1e3:.2f}ms"
                for c in ranked)
            + f" (noise floor {noise_floor * 1e3:.2f}ms)"
        )
        if exhaustive or rnd + 1 >= repeats or rnd + 1 < min_rounds \
                or len(ranked) < 2:
            continue
        margin = noise_mult * noise_floor
        if ranked[1].score - ranked[0].score > margin:
            early_stopped = True
            _LOG.info(
                f"[sparktorch_tpu:tune] early stop after round "
                f"{rnd + 1}: {ranked[0].label} leads "
                f"{ranked[1].label} by "
                f"{(ranked[1].score - ranked[0].score) * 1e3:.2f}ms "
                f"> noise margin {margin * 1e3:.2f}ms"
            )
            break
    measured = [c for c, _ in runners if c.status == STATUS_MEASURED]
    if not measured:
        raise RuntimeError(
            "auto-tune measured no candidate successfully: "
            + "; ".join(f"{c.label}: {c.reason}" for c in to_measure)
        )

    best = min(measured, key=lambda c: c.score)
    result = TuneResult(
        n_devices=n_devices,
        global_batch=global_batch,
        best=dict(best.axes),
        best_schedule=(dict(best.schedule) if best.schedule else None),
        candidates=candidates,
        noise_floor_s=noise_floor,
        early_stopped=early_stopped,
        steps_per_candidate=steps,
        rounds_run=rounds_run,
        warmup_rounds=warmup_rounds,
        executed_steps_total=executed_steps,
        candidates_dropped=candidates_dropped,
        wall_s=time.perf_counter() - t_start,  # lint-obs: ok (artifact stat)
        exposed_weight=exposed_weight,
        caps={k: list(v) for k, v in caps.items()},
        run_id=getattr(telemetry, "run_id", None),
        alpha_bytes=float(alpha_bytes),
        alpha_source=alpha_source,
        cache_key=cache_key,
        compile_count=compile_count,
        compile_s_total=compile_s_total,
    )
    result.publish(telemetry)
    if artifact_path:
        result.save(artifact_path)
    if use_cache and cache_key is not None:
        _cache_store(cache_key, result)
    _LOG.info(
        f"[sparktorch_tpu:tune] chose {result.best_label} from "
        f"{len(candidates)} candidates "
        f"({len(result.pruned())} pruned without execution, "
        f"{len(measured)} measured, early_stop={early_stopped}) "
        f"in {result.wall_s:.1f}s"
    )
    return result


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def _cli_spec(model: str, seq: int):
    from sparktorch_tpu.models import (
        MnistMLP,
        SequenceClassifier,
        bert_base,
        tiny_transformer,
    )
    from sparktorch_tpu.utils.serde import ModelSpec

    if model == "tiny":
        module = SequenceClassifier(tiny_transformer(max_len=seq))
    elif model == "bert":
        module = bert_base(max_len=seq)
    elif model == "mlp":
        module = MnistMLP()
    else:
        raise SystemExit(f"unknown --model {model!r} (tiny|bert|mlp)")
    return ModelSpec(module=module, loss="cross_entropy",
                     optimizer="adam", optimizer_params={"lr": 1e-3})


def main(argv: Optional[List[str]] = None) -> int:
    import argparse

    import numpy as np

    parser = argparse.ArgumentParser(
        prog="python -m sparktorch_tpu.parallel.tune",
        description="Trace-guided mesh auto-tuner: enumerate legal "
                    "mesh configs, prune by analytic comm volume, "
                    "measure survivors under the XLA profiler, emit "
                    "the winner + full ranking as tune_result.json.",
    )
    parser.add_argument("--model", default="tiny",
                        help="tiny | bert | mlp (synthetic workload)")
    parser.add_argument("--batch", type=int, default=32,
                        help="global batch size")
    parser.add_argument("--seq", type=int, default=16,
                        help="sequence length (transformer models)")
    parser.add_argument("--steps", type=int, default=4,
                        help="profiled steps per candidate per round")
    parser.add_argument("--repeats", type=int, default=3,
                        help="interleaved measurement rounds")
    parser.add_argument("--top-k", type=int, default=4,
                        help="candidates measured after the prune")
    parser.add_argument("--exhaustive", action="store_true",
                        help="measure every legal candidate (no prune, "
                             "no early stop)")
    parser.add_argument("--out", default="tune_result.json",
                        help="artifact path")
    args = parser.parse_args(argv)

    spec = _cli_spec(args.model, args.seq)
    from sparktorch_tpu.utils.data import DataBatch

    rng = np.random.default_rng(0)
    if args.model == "mlp":
        x = rng.normal(size=(args.batch, 784)).astype(np.float32)
        y = rng.integers(0, 10, (args.batch,)).astype(np.int32)
    else:
        x = rng.integers(0, 256, (args.batch, args.seq)).astype(np.int32)
        y = rng.integers(0, 2, (args.batch,)).astype(np.int32)
    batch = DataBatch(x=x, y=y, w=np.ones((args.batch,), np.float32))

    result = autotune(
        spec, batch, steps=args.steps, repeats=args.repeats,
        measure_top_k=args.top_k, exhaustive=args.exhaustive,
        artifact_path=args.out,
    )
    doc = result.to_dict()
    print(json.dumps({
        "best": doc["best_label"],
        "mesh": doc["best"],
        "n_candidates": doc["n_candidates"],
        "n_pruned": doc["n_pruned"],
        "n_measured": doc["n_measured"],
        "early_stopped": doc["early_stopped"],
        "noise_floor_s": round(doc["noise_floor_s"], 6),
        "wall_s": round(doc["wall_s"], 2),
        "artifact": args.out,
        "ranking": doc["ranking"],
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

"""Job kind ``fit_sync_groups``: ``fit_sync`` with a ``correct`` that
also holds step 1's gradient to the reference's part by part.

The rows, the window, the seeds, the feed and the comparison of losses
and of the global gradient norm are ``fit_sync``'s, imported as they
are. The global norm alone cannot tell a lower precision from a sound
run. Most of a gradient's error, sound or not, is one common factor
on every leaf (the error of the loss's own derivative), and that
factor has a long tail over the seeds: on ``bert_base_fit_sync_s512``
sound runs read up to 3.5e-3 and the fp8 control down to 1.6e-4 (my
chip runs, PR 27; PR 26's builder). What tells them apart is what the
factor leaves, the error's spread between the leaves, which a sound run
keeps near a quarter of its factor and fp8 does not. So the job
compares, from the per-leaf norms the records carry:

- ``grad_norm_shape_rel``: the leaves' norms as one vector ``P``
  against the reference's ``R``, less the best common factor: ``min_c
  |P - c R| / |R|``. Leaves weigh by their norm, so a bias whose
  gradient nearly cancels on some seed cannot carry it;
- where the traffic file names ``grad_groups`` (``{group: [fragments of
  a dotted path]}``, the first group that matches takes the leaf),
  ``grad_norm_rel_<group>``; and for ``zero_grad_groups``, whose
  gradient is exactly 0 on both sides, ``grad_norm_<group>``: the larger
  of the two norms.

Only numbers with a limit in the traffic file are checks. ``frozen``
in the traffic file lists fragments of the paths of leaves the job does
not train: the program gets the optimizer through ``ModelSpec``'s
``optimizer`` as a constructor (the documented way to pass one's own)
that gives those leaves no update, the reference zeroes their gradient
before its Adam step. Their gradient is still computed and compared.
"""

from __future__ import annotations

import gc
import re
import time

import numpy as np

from chipbench import harness
from chipbench.jobs.fit_sync import (FAULTS, _WindowClosed, compare, feed,
                                     make_rows)
from chipbench.reference import _train


def dotted(path) -> str:
    return ".".join(str(getattr(p, "key", p)) for p in path)


def leaf_norms(tree) -> dict:
    """``{leaf's dotted path: norm}`` of a tree of nested dicts."""
    import jax

    return {dotted(path): float(np.sqrt(np.sum(np.square(np.asarray(leaf)))))
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def kind_of(key: str) -> str:
    """A leaf's dotted path less its ``layer_<i>.``: the twelve
    ``mlp_in.bias`` of a model are one kind."""
    return re.sub(r"(^|\.)layer_\d+\.", r"\1", key)


def shape_rel(prog: dict, ref: dict) -> float:
    """``min_c |P - c R| / |R|`` over the vectors of leaf norms."""
    rr = sum(r * r for r in ref.values())
    c = sum(prog[k] * r for k, r in ref.items()) / rr
    return (sum((prog[k] - c * r) ** 2 for k, r in ref.items()) / rr) ** 0.5


def _holds(key: str, fragments) -> bool:
    return any(f in f".{key}" for f in fragments)


def group_of(key: str, groups: dict) -> str:
    for group, fragments in groups.items():
        if _holds(key, fragments):
            return group
    raise KeyError(f"leaf {key!r} belongs to no group of {groups}")


def norms_by(norms: dict, name_of) -> dict:
    """``{name_of(leaf): norm over its leaves}`` from the norms by leaf."""
    squares: dict = {}
    for key, norm in norms.items():
        squares[name_of(key)] = squares.get(name_of(key), 0.0) + norm ** 2
    return {g: s ** 0.5 for g, s in squares.items()}


def compare_leaves(prog: dict, ref: dict, traffic: dict) -> tuple:
    """``(numbers, furthest)``: step 1's gradient norm by part against
    the reference's, from the norms by leaf, and the five kinds furthest
    off as ``(error, kind)``, for the log."""
    p_kind, r_kind = norms_by(prog, kind_of), norms_by(ref, kind_of)
    by_error = sorted(((abs(p_kind[k] - r) / r, k)
                       for k, r in r_kind.items() if r > 0), reverse=True)
    out = {"grad_norm_shape_rel": shape_rel(prog, ref)}
    rel = traffic.get("grad_groups", {})
    zero = traffic.get("zero_grad_groups", {})
    if rel or zero:
        groups = {**zero, **rel}  # a zero group takes its leaves first
        name_of = lambda key: group_of(key, groups)
        p_group, r_group = norms_by(prog, name_of), norms_by(ref, name_of)
        for g in rel:
            out[f"grad_norm_rel_{g}"] = (abs(p_group[g] - r_group[g])
                                         / r_group[g])
        for g in zero:
            out[f"grad_norm_{g}"] = max(p_group[g], r_group[g])
    return out, by_error[:5]


def frozen_optimizer(name: str, fragments):
    """A constructor for ``ModelSpec.optimizer``: the registry's
    optimizer ``name``, and no update for the leaves whose dotted path
    holds one of ``fragments``."""
    import jax
    import optax

    from sparktorch_tpu.utils.serde import OPTIMIZER_REGISTRY

    def labels(params):
        return jax.tree_util.tree_map_with_path(
            lambda path, _: "frozen" if _holds(dotted(path), fragments)
            else "trained", params)

    return lambda **kw: optax.multi_transform(
        {"trained": OPTIMIZER_REGISTRY[name](**kw),
         "frozen": optax.set_to_zero()}, labels)


def reference_steps(cell, x, y, n_shards: int, precision: str = "f32",
                    lr_scale: float = 1.0, rows_kept: float = 1.0,
                    fault=None, grader=_train.Grader, in_place=False):
    """Losses and global gradient norms of the first ``check_steps``
    steps and step 1's gradient norm by leaf, by the reference.
    ``grader`` is ``_train.Grader`` or one with its interface;
    ``in_place`` donates the weights and Adam's moments to the update
    (for weights of which seven copies do not fit)."""
    import jax

    t = cell.traffic
    cfg = {**cell.config, "fault": fault} if fault else cell.config
    seed, mb = t["program_seed"], t["mini_batch"]
    frozen = t.get("frozen", ())
    variables = jax.jit(lambda k: cell.reference.init(k, cell.config))(
        jax.random.key(seed))
    opt = _train.OPTIMIZERS[t["optimizer"]](
        t["optimizer_params"]["lr"] * lr_scale)
    if in_place:
        opt._update = jax.jit(opt._update, donate_argnums=(0, 2, 3))
    grade = grader(cell.reference, cfg, t["reference_block_rows"], precision)
    losses, gnorms, leaves = [], [], None
    for idx in feed(seed, x.shape[0], n_shards, mb, t["check_steps"]):
        real = (idx >= 0) & (np.arange(idx.size) % mb < rows_kept * mb)
        xb = np.where(real[:, None], x[np.maximum(idx, 0)], 0)
        yb = np.where(real.reshape(-1, *[1] * (y.ndim - 1)),
                      y[np.maximum(idx, 0)], 0)
        loss, grads = grade(variables, xb, yb, real.astype(np.float32))
        losses.append(float(loss))
        gnorms.append(_train.global_norm(grads))
        if leaves is None:
            leaves = leaf_norms(grads)
        if frozen:
            grads = jax.tree_util.tree_map_with_path(
                lambda path, g: g * 0 if _holds(dotted(path), frozen) else g,
                grads)
        variables = {**variables,
                     "params": opt.step(variables["params"], grads)}
        del grads
    del variables, opt, grade
    return losses, gnorms, leaves


def control(cell, seed: int, kinds=None, faults=FAULTS, rows=make_rows,
            **reference) -> dict:
    """The reference in the program's place against the float32
    reference: in the configuration's precision (``bf16``, has to
    pass), one below it (``fp8``), and sound but for a planted fault."""
    kinds = kinds or ("bf16", "fp8", *faults)
    x, y = rows(np.random.default_rng(seed), cell.traffic, cell.config)
    n_shards = cell.chips  # the feed depends on the shards alone
    ref_losses, ref_gnorms, ref_leaves = reference_steps(
        cell, x, y, n_shards, **reference)
    out = {}
    for kind in kinds:
        losses, gnorms, leaves = reference_steps(
            cell, x, y, n_shards, **reference,
            **(faults[kind] if kind in faults else {"precision": kind}))
        out[kind] = {**compare(losses, gnorms, ref_losses, ref_gnorms),
                     **compare_leaves(leaves, ref_leaves, cell.traffic)[0]}
    return out


def run(cell, seed: int, seconds: float, trace_dir=None, rows=make_rows,
        extra=None, **reference) -> harness.JobResult:
    """``fit_sync.run`` with the comparison by part. ``extra(window,
    cell)`` gives a job built on this one its own ``(checks, notes,
    layer_inputs)`` from the window's records."""
    import jax

    from sparktorch_tpu.obs.telemetry import Telemetry
    from sparktorch_tpu.train.sync import train_distributed

    t, cfg = cell.traffic, cell.config
    compiles = harness.CompileCounter()
    x, y = rows(np.random.default_rng(seed), t, cfg)
    optimizer = t["optimizer"]
    if t.get("frozen"):
        optimizer = frozen_optimizer(optimizer, t["frozen"])
    spec = harness.seeded_spec(
        cell, loss=t["loss"], optimizer=optimizer,
        optimizer_params=dict(t["optimizer_params"]),
        input_shape=(t["seq_len"],))
    tele = Telemetry(run_id="chipbench")
    n_chips = len(jax.devices())
    spc = t["steps_per_call"]
    budget_s = seconds if trace_dir is None else 0.0
    min_chunks = 1 if trace_dir is None else t["trace_chunks"]

    records, chunk_ends, memory = [], [], []

    def hook(record):
        if record["iter"] % spc == 0:
            chunk_ends.append(time.perf_counter())
        records.append(record)
        if (record["iter"] % spc == spc - 1
                and len(chunk_ends) - 1 >= min_chunks
                and chunk_ends[-1] - chunk_ends[0] >= budget_s):
            memory.append(harness.memory_peak_bytes())
            raise _WindowClosed

    iters = spc * (2 + int(max(seconds, 1.0) / t["min_chunk_s"]))
    if trace_dir:
        harness.start_trace(trace_dir)
    try:
        with jax.profiler.TraceAnnotation("chipbench/train_distributed"):
            train_distributed(
                spec, x, labels=y, mini_batch=t["mini_batch"], iters=iters,
                steps_per_call=spc, seed=t["program_seed"],
                metrics_hook=hook, telemetry=tele)
        raise RuntimeError(f"{iters} iterations ended before the window "
                           f"closed; lower min_chunk_s in the traffic file")
    except _WindowClosed:
        pass
    finally:
        if trace_dir:
            jax.profiler.stop_trace()
    gc.collect()
    if reference.get("in_place"):
        # the reference needs the chip's memory: the step program's
        # scratch goes with its executable
        jax.clear_caches()
    n_chunks = len(chunk_ends) - 1
    t_open, t_close = chunk_ends[0], chunk_ends[-1]
    window = records[spc:spc * (n_chunks + 1)]
    compiles.require_none_within(t_open, t_close)
    wall = t_close - t_open
    losses = [r["loss"] for r in records[:spc * (n_chunks + 1)]]
    failed = sum(1 for r in window if not np.isfinite(r["loss"]))

    t_check = time.perf_counter()
    ref_losses, ref_gnorms, ref_leaves = reference_steps(
        cell, x, y, n_chips, **reference)
    numbers = compare(losses, [r["grad_norm"] for r in records],
                      ref_losses, ref_gnorms)
    numbers["loss_fall"] = (float(np.mean(losses[-spc:]))
                            / float(np.mean(losses[:spc])))
    prog_leaves = dict(zip(records[0]["leaf_grad_norm_keys"],
                           map(float, records[0]["leaf_grad_norms"])))
    by_part, furthest = compare_leaves(prog_leaves, ref_leaves, t)
    numbers.update(by_part)
    checks = [harness.Check(k, numbers[k], limit)
              for k, limit in t["limits"].items()]
    mb_global = t["mini_batch"] * n_chips
    pad = -x.shape[0] % n_chips
    short = sum(1 for r in window
                if not mb_global - pad <= r["examples"] <= mb_global)
    checks.append(harness.Check("steps_with_wrong_row_count", short, 0))
    checks.append(harness.Check("nonfinite_losses", failed, 0))
    notes = [
        compiles.before(t_open),
        f"chunks in window {n_chunks} steps {len(window)} wall {wall:.4f}s",
        f"program loss {losses[:len(ref_losses)]} reference {ref_losses}",
        f"program grad_norm "
        f"{[r['grad_norm'] for r in records[:len(ref_gnorms)]]} "
        f"reference {ref_gnorms}",
        f"compared but held to no limit: "
        f"{ {k: v for k, v in numbers.items() if k not in t['limits']} }",
        f"gradient norm by kind of leaf, the five furthest from the "
        f"reference's: {[(k, round(e, 5)) for e, k in furthest]}",
        f"the reference took {time.perf_counter() - t_check:.2f}s",
    ]
    layer_inputs = {
        "telemetry": tele, "steps": len(window), "chunks": n_chunks,
        "steps_per_call": spc, "window_wall_s": wall,
        "chunk_span_s": harness.span_samples(tele, "train/step_chunk"),
        "examples_per_step": mb_global, "n_chips": n_chips,
        "trace_window": {"module_skip_first": 1},
    }
    if extra is not None:
        more_checks, more_notes, more_inputs = extra(window, cell)
        checks += more_checks
        notes += more_notes
        layer_inputs.update(more_inputs)
    return harness.JobResult(
        setup_s=t_open - harness.T_PROCESS, window_s=wall,
        end_to_end={"rate": sum(r["examples"] for r in window) / wall
                    / n_chips},
        attempted=len(window), failed=failed, checks=checks,
        memory=memory[0], notes=notes, layer_inputs=layer_inputs)

"""Job kind ``fit_sync_ep``: ``fit_sync_hlm`` (a language model of window
and full-causal layers through ONE ``train_distributed`` call, held to
its plain reference on the call's own first steps) on a mesh that is not
the default one: the traffic's ``mesh`` (``{"dp": 1, "ep": 4}``), over
whose ``ep`` axis the model's expert layers are WHOLE: each chip holds a
block of every layer's experts and the expert exchange takes every
token's row to every chip that holds one of its experts.

The rows, the window, the feed (a row shard a chip, whatever the axis is
called), the comparison of losses and of gradient norms by group, the
frozen leaves, the expert rows' check and ``mask_pairs_off`` are
``fit_sync``'s, ``fit_sync_groups``', ``fit_sync_lm``'s and
``fit_sync_hlm``'s, imported as they are. What this file adds:

- a spec of its own. ``harness.seeded_spec`` makes the reference's whole
  tree in a ``jit`` of its own; here that tree is 7.14 GB and no chip may
  hold it. This spec's ``init_params`` is the reference's ``init`` traced
  INTO the trainer's init program, whose ``out_shardings`` lay the
  experts' leaves over ``ep`` (``train/sync.py`` ``_jit_init``), and the
  reference draws each of those leaves on its own, so each is born in
  blocks on its chips. The same numbers: the check makes the same weights
  from the same key.
- a reference that fits. Its tree in float32 with a gradient's running
  sum, a block's gradient and Adam's moments is five copies. The job lets
  the program's state and executables go first, then lays EVERY large
  leaf of the reference over the chips (the experts' by their first axis,
  the projections by their heads, the embedding and the head by the
  vocabulary: ``_PLACED``) and lets the compiler's partitioner follow
  them through the reference's plain code; a block is one row and the
  running sum is donated to each block's program.
- the faults only an expert-parallel step can hide: ``own_rows_only`` and
  ``experts_psummed`` (the reference's docstring says what each is).
- counters of the exchange, from the window's records:
  ``exchange_rows_off``, every step moves ``layers x members x (members -
  1) x rows x T`` rows.
"""

from __future__ import annotations

import dataclasses
import functools
import gc
import time

import numpy as np

from chipbench import harness
from chipbench.jobs import fit_sync, fit_sync_groups, fit_sync_hlm, \
    fit_sync_lm
from chipbench.reference import _train

FAULTS = {
    "lr_x1.5": fit_sync.FAULTS["lr_x1.5"],
    **{name: {"fault": name} for name in (
        "own_rows_only",     # the exchange skipped
        "experts_psummed",   # the experts' gradient summed over ep again
        "window_ignored", "window_1025", "rope_swapped", "no_yarn",
        "no_renorm")},
}

make_rows = fit_sync_lm.make_rows

# where the reference's large leaves lie over the chips: the axis of each
# (by the last two keys of its path) that is cut over the mesh's one axis
_PLACED = {("moe", "w_gate"): 0, ("moe", "w_up"): 0, ("moe", "w_down"): 0,
           ("attn", "wq"): 1, ("attn", "wk"): 1, ("attn", "wv"): 1,
           ("attn", "wo"): 0, ("embed",): 0, ("head",): 1}


def _devices(cell) -> list:
    """As many of the process's devices as the traffic's mesh has members."""
    import jax

    return jax.devices()[:int(np.prod(list(cell.traffic["mesh"].values())))]


def _program_mesh(cell):
    """The traffic's mesh (``{"dp": 1, "ep": 4}``), for the program."""
    from sparktorch_tpu.parallel.mesh import MeshConfig, build_mesh

    return build_mesh(MeshConfig(**cell.traffic["mesh"]), _devices(cell))


def _reference_mesh(cell):
    """One axis over the same devices, for the reference's leaves."""
    from jax.sharding import Mesh

    return Mesh(np.array(_devices(cell)), ("chips",))


def _placement(tree, mesh):
    """A ``NamedSharding`` a leaf of the reference's tree (or of a tree
    of its shape: a gradient, a moment)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    def place(path, leaf):
        keys = tuple(str(getattr(p, "key", p)) for p in path)
        axis = _PLACED.get(keys[-2:], _PLACED.get(keys[-1:]))
        if axis is None or leaf.shape[axis] % mesh.size:
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, P(*[None] * axis, "chips"))

    return jax.tree_util.tree_map_with_path(place, tree)


def seeded_spec(cell, **spec_kwargs):
    """``harness.seeded_spec`` without a ``jit`` of its own around the
    reference's ``init``: the trainer's init program traces it and
    places its leaves."""
    from sparktorch_tpu.utils.serde import ModelSpec

    reference, sizes = cell.reference, cell.config

    @dataclasses.dataclass
    class SeededSpec(ModelSpec):
        def init_params(self, rng, sample_x=None):
            return reference.init(rng, sizes)

    return SeededSpec(module=cell.build_module(), **spec_kwargs)


class _Grader:
    """``(loss, grads)`` of one global minibatch over weights laid over
    the chips: a block of ``block_rows`` rows at a time into a running
    sum that is donated to each block's program, every result placed as
    the weights are. ``members`` says which member of the deployment
    holds each row (the fault ``own_rows_only`` reads it)."""

    def __init__(self, reference, cfg: dict, block_rows: int,
                 precision: str, shardings):
        import jax
        import jax.numpy as jnp

        self.block_rows = block_rows

        @functools.partial(jax.jit, donate_argnums=(6,),
                           out_shardings=(None, shardings))
        def block(params, rest, xb, yb, wb, members, total):
            num, g = jax.value_and_grad(
                lambda p: reference.loss_sum({**rest, "params": p}, xb, yb,
                                             wb, cfg, precision, members))(
                params)
            return num, jax.tree.map(jnp.add, total, g)

        self._block = block
        self._zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p),
                              out_shardings=shardings)
        self._scale = jax.jit(lambda g, den: jax.tree.map(
            lambda a: a / den, g), donate_argnums=(0,),
            out_shardings=shardings)

    def __call__(self, variables: dict, x, y, w, members):
        import jax.numpy as jnp

        rest = {k: v for k, v in variables.items() if k != "params"}
        num, den = 0.0, 0.0
        total = self._zeros(variables["params"])
        for lo in range(0, x.shape[0], self.block_rows):
            xb, yb, wb, mb = (jnp.asarray(a[lo:lo + self.block_rows])
                              for a in (x, y, w, members))
            n, total = self._block(variables["params"], rest, xb, yb, wb, mb,
                                   total)
            num, den = num + n, den + jnp.sum(wb)
        den = jnp.maximum(den, 1.0)
        return num / den, self._scale(total, den)


def _leaf_norms(tree) -> dict:
    """``fit_sync_groups.leaf_norms`` computed where the leaves lie."""
    import jax
    import jax.numpy as jnp

    norms = jax.jit(lambda t: jax.tree.map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a))), t))(tree)
    return {fit_sync_groups.dotted(path): float(norm) for path, norm
            in jax.tree_util.tree_flatten_with_path(norms)[0]}


def reference_steps(cell, x, y, n_shards: int, precision: str = "f32",
                    lr_scale: float = 1.0, fault=None):
    """Losses and global gradient norms of the first ``check_steps``
    steps and step 1's gradient norm by leaf, by the reference, its
    weights, gradients and Adam's moments laid over the chips."""
    import jax

    t = cell.traffic
    cfg = {**cell.config, "fault": fault} if fault else cell.config
    seed, mb = t["program_seed"], t["mini_batch"]
    frozen = t.get("frozen", ())
    mesh = _reference_mesh(cell)
    init = lambda k: cell.reference.init(k, cell.config)
    shardings = _placement(jax.eval_shape(init, jax.random.key(seed)), mesh)
    variables = jax.jit(init, out_shardings=shardings)(jax.random.key(seed))
    opt = _train.OPTIMIZERS[t["optimizer"]](
        t["optimizer_params"]["lr"] * lr_scale)
    opt._update = jax.jit(opt._update, donate_argnums=(0, 2, 3))
    grade = _Grader(cell.reference, cfg, t["reference_block_rows"],
                    precision, shardings["params"])
    losses, gnorms, leaves = [], [], None
    for idx in fit_sync.feed(seed, x.shape[0], n_shards, mb,
                             t["check_steps"]):
        real = idx >= 0
        xb = np.where(real[:, None], x[np.maximum(idx, 0)], 0)
        yb = np.where(real[:, None], y[np.maximum(idx, 0)], 0)
        loss, grads = grade(variables, xb, yb, real.astype(np.float32),
                            (np.arange(idx.size) // mb).astype(np.int32))
        losses.append(float(loss))
        gnorms.append(_train.global_norm(grads))
        if leaves is None:
            leaves = _leaf_norms(grads)
        if frozen:
            grads = jax.tree_util.tree_map_with_path(
                lambda path, g: g * 0 if fit_sync_groups._holds(
                    fit_sync_groups.dotted(path), frozen) else g, grads)
        variables = {**variables,
                     "params": opt.step(variables["params"], grads)}
        del grads
    del variables, opt, grade
    return losses, gnorms, leaves


def control(cell, seed: int, kinds=None) -> dict:
    """The reference in the program's place against the float32
    reference: in the configuration's precision (``bf16``, has to pass),
    one below it (``fp8``), and sound but for a planted fault; and for
    each kind the pairs on which its masks differ from the sound
    reference's (limit 0)."""
    kinds = kinds or ("bf16", "fp8", *FAULTS)
    x, y = make_rows(np.random.default_rng(seed), cell.traffic, cell.config)
    ref_losses, ref_gnorms, ref_leaves = reference_steps(
        cell, x, y, cell.chips)
    out = {}
    for kind in kinds:
        losses, gnorms, leaves = reference_steps(
            cell, x, y, cell.chips,
            **(FAULTS[kind] if kind in FAULTS else {"precision": kind}))
        fault = FAULTS.get(kind, {}).get("fault")
        out[kind] = {
            **fit_sync.compare(losses, gnorms, ref_losses, ref_gnorms),
            **fit_sync_groups.compare_leaves(leaves, ref_leaves,
                                             cell.traffic)[0],
            "mask_pairs_off": sum(fit_sync_hlm.mask_pairs_off(
                fit_sync_hlm._reference_mask(cell, kind_, fault),
                fit_sync_hlm._reference_mask(cell, kind_),
                cell.traffic["seq_len"])
                for kind_ in fit_sync_hlm._layer_types(cell))}
    return out


def _counters(window: list, cell):
    """``fit_sync_hlm``'s checks (the expert layers' counters, each kind
    of layer's rule against the reference's) and the exchange's own
    counter in the window."""
    checks, notes, inputs = fit_sync_hlm._counters(window, cell)
    t, members = cell.traffic, cell.traffic["mesh"]["ep"]
    moved = (cell.config["num_hidden_layers"] * members * (members - 1)
             * t["mini_batch"] * t["seq_len"])
    checks.append(harness.Check("exchange_rows_off", sum(
        abs(r.get("moe_exchange_rows", 0.0) - moved) for r in window), 0))
    notes.append(f"rows the exchange moves a step: {moved} (a layer "
                 f"{moved // cell.config['num_hidden_layers']})")
    return checks, notes, inputs


def run(cell, seed: int, seconds: float, trace_dir=None) -> harness.JobResult:
    """``fit_sync_groups.run`` on the traffic's mesh, with this file's
    spec and reference."""
    import jax

    from sparktorch_tpu.obs.telemetry import Telemetry
    from sparktorch_tpu.train.sync import train_distributed

    t, cfg = cell.traffic, cell.config
    compiles = harness.CompileCounter()
    x, y = make_rows(np.random.default_rng(seed), t, cfg)
    spec = seeded_spec(
        cell, loss=t["loss"],
        optimizer=fit_sync_groups.frozen_optimizer(t["optimizer"],
                                                   t["frozen"]),
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
            raise fit_sync._WindowClosed

    iters = spc * (2 + int(max(seconds, 1.0) / t["min_chunk_s"]))
    if trace_dir:
        harness.start_trace(trace_dir)
    try:
        with jax.profiler.TraceAnnotation("chipbench/train_distributed"):
            train_distributed(
                spec, x, labels=y, mesh=_program_mesh(cell),
                mini_batch=t["mini_batch"], iters=iters, steps_per_call=spc,
                seed=t["program_seed"], metrics_hook=hook, telemetry=tele)
        raise RuntimeError(f"{iters} iterations ended before the window "
                           f"closed; lower min_chunk_s in the traffic file")
    except fit_sync._WindowClosed:
        pass
    finally:
        if trace_dir:
            jax.profiler.stop_trace()
    # the reference needs the chips' memory: the program's state goes
    # with the call's frames, its scratch with its executables
    gc.collect()
    jax.clear_caches()
    n_chunks = len(chunk_ends) - 1
    t_open, t_close = chunk_ends[0], chunk_ends[-1]
    window = records[spc:spc * (n_chunks + 1)]
    compiles.require_none_within(t_open, t_close)
    wall = t_close - t_open
    losses = [r["loss"] for r in records[:spc * (n_chunks + 1)]]
    failed = sum(1 for r in window if not np.isfinite(r["loss"]))

    t_check = time.perf_counter()
    ref_losses, ref_gnorms, ref_leaves = reference_steps(cell, x, y, n_chips)
    numbers = fit_sync.compare(losses, [r["grad_norm"] for r in records],
                               ref_losses, ref_gnorms)
    numbers["loss_fall"] = (float(np.mean(losses[-spc:]))
                            / float(np.mean(losses[:spc])))
    prog_leaves = dict(zip(records[0]["leaf_grad_norm_keys"],
                           map(float, records[0]["leaf_grad_norms"])))
    by_part, furthest = fit_sync_groups.compare_leaves(prog_leaves,
                                                       ref_leaves, t)
    numbers.update(by_part)
    checks = [harness.Check(k, numbers[k], limit)
              for k, limit in t["limits"].items()]
    mb_global = t["mini_batch"] * n_chips
    short = sum(1 for r in window if r["examples"] != mb_global)
    checks.append(harness.Check("steps_with_wrong_row_count", short, 0))
    checks.append(harness.Check("nonfinite_losses", failed, 0))
    more_checks, more_notes, more_inputs = _counters(window, cell)
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
        *more_notes,
    ]
    return harness.JobResult(
        setup_s=t_open - harness.T_PROCESS, window_s=wall,
        end_to_end={"rate": sum(r["examples"] for r in window) / wall
                    / n_chips},
        attempted=len(window), failed=failed, checks=checks + more_checks,
        memory=memory[0], notes=notes,
        layer_inputs={
            "telemetry": tele, "steps": len(window), "chunks": n_chunks,
            "steps_per_call": spc, "window_wall_s": wall,
            "chunk_span_s": harness.span_samples(tele, "train/step_chunk"),
            "examples_per_step": mb_global, "n_chips": n_chips,
            "trace_window": {"module_skip_first": 1}, **more_inputs})

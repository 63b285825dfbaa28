"""Job kind ``fit_sync``: ONE ``train_distributed`` call on the default
mesh, timed between the ends of its fused chunks.

Set-up is everything up to the end of the call's first chunk (process
start, data, weights, trace, compile or cache load, the chunk itself).
The window opens at that chunk's end and closes at the end of the first
chunk that ends ``seconds`` or more later; the job's ``metrics_hook``
then raises, which is the entry point's own way out (its ``finally``
closes the run). The rate is the examples of the chunks inside the
window over the window's wall, per chip. Every chunk ends in a
readback inside the program (``_chunk_span.sync``), and the hook runs
after it.

``--seed`` makes the rows and their labels. The program gets the seed
of the traffic file (``program_seed``), the same in every run: its
init jit takes no argument and closes over ``key(seed)``, so whatever
depends on that seed is a constant of the init program, and a seed per
run compiled that program anew in every run (11.7 s of set-up, my chip
run, PR 23). So the weights, the resident order and the block offsets
are the same in every run and the rows differ.

``correct`` holds the call to the plain reference on the call's own
first steps: the reference makes the same weights from the same key,
draws the rows the program drew (``feed`` below restates the program's
resident order and block offsets), and follows the first
``check_steps`` steps with its own optimizer.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from chipbench import harness
from chipbench.reference import _train

# The limit of each number compared is the cell's own, in its traffic
# file under ``limits``: set from chip readings of sound runs and of the
# fp8 control at that cell's size (PERF.md section 2 gives them).


class _WindowClosed(Exception):
    pass


def make_rows(rng: np.random.Generator, traffic: dict, cfg: dict):
    """Token-id rows with a learnable label: class 1 draws its tokens
    from the upper half of the vocabulary, class 0 from the lower, so a
    step that trains lowers the loss. Rows all differ."""
    n, seq, vocab = traffic["resident_rows"], traffic["seq_len"], cfg["vocab_size"]
    y = rng.integers(0, 2, (n,))
    lo = np.where(y == 1, vocab // 2, 0)[:, None]
    ids = lo + rng.integers(0, vocab // 2, (n, seq))
    return ids.astype(np.float32), y.astype(np.float32)


def feed(seed: int, n_rows: int, n_shards: int, mini_batch: int, steps: int):
    """Row indices (into the rows as given, -1 for a padding row) of the
    first ``steps`` global minibatches, as ``train/sync.py`` and
    ``utils/data.py`` define the feed at PR 23: rows padded to a
    multiple of the shards, permuted once by the first split of
    ``key(seed + 1)``, cut into contiguous shards; at each step every
    shard takes ``mini_batch`` rows at ``randint(fold_in(split(rng)[0],
    shard))``, and ``rng`` becomes ``split(rng)[1]``."""
    import jax

    padded = max(n_shards, -(-n_rows // n_shards) * n_shards)
    perm = np.asarray(jax.random.permutation(
        jax.random.split(jax.random.key(seed + 1))[1], padded))
    per_shard = padded // n_shards
    rng = jax.random.key(seed)
    out = []
    for _ in range(steps):
        step_key, rng = jax.random.split(rng)
        rows = []
        for s in range(n_shards):
            if mini_batch < per_shard:
                off = int(jax.random.randint(
                    jax.random.fold_in(step_key, s), (), 0,
                    per_shard - mini_batch + 1))
                idx = perm[s * per_shard + off:
                           s * per_shard + off + mini_batch]
            else:
                idx = perm[s * per_shard:(s + 1) * per_shard]
            rows.append(np.where(idx < n_rows, idx, -1))
        out.append(np.concatenate(rows))
    return out


# Faults a sound-looking program could hide, planted in the reference
# put in the program's place (``control``): the numbers that the lower
# precision hardly moves are held against these.
FAULTS = {
    "lr_x1.5": {"lr_scale": 1.5},       # a wrong update
    "half_batch": {"rows_kept": 0.5},   # every shard trains on half its rows
}


def reference_steps(cell, x, y, n_shards: int, precision: str = "f32",
                    lr_scale: float = 1.0, rows_kept: float = 1.0):
    """Loss and global gradient norm of the first ``check_steps`` steps,
    by the reference, from the rows and the traffic file alone."""
    import jax

    t, cfg = cell.traffic, cell.config
    seed, mb = t["program_seed"], t["mini_batch"]
    variables = jax.jit(lambda k: cell.reference.init(k, cfg))(
        jax.random.key(seed))
    opt = _train.OPTIMIZERS[t["optimizer"]](
        t["optimizer_params"]["lr"] * lr_scale)
    grader = _train.Grader(cell.reference, cfg, t["reference_block_rows"],
                           precision)
    losses, gnorms = [], []
    for idx in feed(seed, x.shape[0], n_shards, mb, t["check_steps"]):
        real = (idx >= 0) & (np.arange(idx.size) % mb < rows_kept * mb)
        xb = np.where(real[:, None], x[np.maximum(idx, 0)], 0)
        yb = np.where(real, y[np.maximum(idx, 0)], 0)
        loss, grads = grader(variables, xb, yb, real.astype(np.float32))
        losses.append(float(loss))
        gnorms.append(_train.global_norm(grads))
        variables = {**variables,
                     "params": opt.step(variables["params"], grads)}
    del variables, opt
    return losses, gnorms


def compare(prog_losses, prog_gnorms, ref_losses, ref_gnorms) -> dict:
    """The first step runs on the seeded weights, so its loss and the
    norm of its gradient (as the optimizer gets it) differ from the
    reference's by the precision alone. The next steps' losses also
    carry what the optimizer did with it."""
    rel = [abs(p - r) / abs(r) for p, r in zip(prog_losses, ref_losses)]
    return {
        "loss_rel_first": rel[0],
        "grad_norm_rel_first": abs(prog_gnorms[0] - ref_gnorms[0])
        / ref_gnorms[0],
        "loss_rel_next": max(rel[1:]),
    }


def control(cell, seed: int, kinds=("bf16", "fp8", *FAULTS)) -> dict:
    """The reference in the program's place, against the float32
    reference: one precision below the configuration's (``fp8``), in
    the configuration's own (``bf16``, which has to pass), and sound
    but for one planted fault."""
    x, y = make_rows(np.random.default_rng(seed), cell.traffic, cell.config)
    n_shards = cell.chips  # the feed depends on the shards alone
    ref = reference_steps(cell, x, y, n_shards)
    return {kind: compare(*reference_steps(
        cell, x, y, n_shards,
        **(FAULTS[kind] if kind in FAULTS else {"precision": kind})), *ref)
        for kind in kinds}


def run(cell, seed: int, seconds: float, trace_dir=None) -> harness.JobResult:
    import jax

    from sparktorch_tpu.obs.telemetry import Telemetry
    from sparktorch_tpu.train.sync import train_distributed

    t, cfg = cell.traffic, cell.config
    compiles = harness.CompileCounter()
    x, y = make_rows(np.random.default_rng(seed), t, cfg)
    spec = harness.seeded_spec(
        cell, loss=t["loss"], optimizer=t["optimizer"],
        optimizer_params=dict(t["optimizer_params"]),
        input_shape=(t["seq_len"],))
    tele = Telemetry(run_id="chipbench")
    n_chips = len(jax.devices())
    spc = t["steps_per_call"]
    # traced runs keep to a few chunks: traces are large
    budget_s = seconds if trace_dir is None else 0.0
    min_chunks = 1 if trace_dir is None else t["trace_chunks"]

    records, chunk_ends, memory = [], [], []

    def hook(record):
        # the chunk's first record comes right after its readback: the
        # stamp; its last record is where the call may be left whole
        if record["iter"] % spc == 0:
            chunk_ends.append(time.perf_counter())
        records.append(record)
        if (record["iter"] % spc == spc - 1
                and len(chunk_ends) - 1 >= min_chunks
                and chunk_ends[-1] - chunk_ends[0] >= budget_s):
            # while the program's state and executables are alive
            memory.append(harness.memory_peak_bytes())
            raise _WindowClosed

    # an upper bound the hook always closes first
    iters = spc * (2 + int(max(seconds, 1.0) / t["min_chunk_s"]))
    # The benchmark's own trace, not ``profile_dir=``: the program's
    # starts the profiler at its defaults (see ``harness.start_trace``)
    # and reduces the trace itself when it stops.
    if trace_dir:
        harness.start_trace(trace_dir)
    try:
        with jax.profiler.TraceAnnotation("chipbench/train_distributed"):
            train_distributed(
                spec, x, labels=y, mini_batch=t["mini_batch"], iters=iters,
                steps_per_call=spc, seed=t["program_seed"],
                metrics_hook=hook,
                telemetry=tele)
        raise RuntimeError(f"{iters} iterations ended before the window "
                           f"closed; lower min_chunk_s in the traffic file")
    except _WindowClosed:
        pass
    finally:
        if trace_dir:
            jax.profiler.stop_trace()
    gc.collect()
    n_chunks = len(chunk_ends) - 1
    t_open, t_close = chunk_ends[0], chunk_ends[-1]
    window = records[spc:spc * (n_chunks + 1)]
    compiles.require_none_within(t_open, t_close)
    examples = sum(r["examples"] for r in window)
    wall = t_close - t_open
    losses = [r["loss"] for r in records[:spc * (n_chunks + 1)]]
    failed = sum(1 for r in window if not np.isfinite(r["loss"]))

    # the reference, after the program's state is gone
    t_check = time.perf_counter()
    ref_losses, ref_gnorms = reference_steps(cell, x, y, n_chips)
    numbers = compare(losses, [r["grad_norm"] for r in records],
                      ref_losses, ref_gnorms)
    numbers["loss_fall"] = (float(np.mean(losses[-spc:]))
                            / float(np.mean(losses[:spc])))
    checks = [harness.Check(k, numbers[k], t["limits"][k]) for k in numbers]
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
        f"the reference took {time.perf_counter() - t_check:.2f}s",
    ]
    return harness.JobResult(
        setup_s=t_open - harness.T_PROCESS, window_s=wall,
        end_to_end={"rate": examples / wall / n_chips},
        attempted=len(window), failed=failed, checks=checks,
        memory=memory[0], notes=notes,
        layer_inputs={
            "telemetry": tele, "steps": len(window), "chunks": n_chunks,
            "steps_per_call": spc, "window_wall_s": wall,
            "chunk_span_s": harness.span_samples(tele, "train/step_chunk"),
            "examples_per_step": mb_global, "n_chips": n_chips,
            "trace_window": {"module_skip_first": 1},
        })

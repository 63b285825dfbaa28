"""Job kind ``fit_sync_dlm``: ``fit_sync_groups`` for a language model
trained by masked diffusion: rows of token ids from ``fit_sync_lm``'s
seeded language, labels the row itself, the loss the row's mean over its
positions of ``m_i / t`` times the cross entropy at the noised position.

The program draws its noise inside the step, from the step's random
stream. The reference takes noise as data, so this job restates the
draw from what a run fixes: the traffic's ``program_seed`` makes
``state.rng = key(seed)``; at each step ``_dp_body`` splits it, takes
``sample_key = fold_in(first half, shard)`` and hands the model's one
declared stream ``fold_in(sample_key, 1)`` (``train/step.py``
``_forward_rngs``); the model's top-level module draws ``make_rng`` from
it once (flax folds the module's path and the draw's count into the
key: :func:`_first_draw` asks flax for the same) and makes ``t = eps +
(1 - eps) U[0, 1)`` a row from the first half of that key's split and
``m = U[0, 1) < t`` a token from the second. :func:`restated_noise`
writes these lines out with ``jax.random`` alone; it calls nothing of
the model.

What this file adds to ``fit_sync_lm``: the labels, the restated noise
handed to the reference step by step, the faults ``control`` plants for
the mechanisms of a block-diffusion LM, the check that each step masked
as many tokens as the restated draw says, and ``mask_pairs_off``: the
program's mask is a static rule, so it is held to the reference's pair by
pair over the whole ``2L x 2L`` square of the cell (on the host, after
the window). At step 1 no norm can tell a mask that is off by a few keys
among the thousands a query attends (``own_block_seen`` reads inside
every limit at 8,192 tokens); the pairs can.

``half_batch`` is not among this job's faults: it drops the second half
of every shard's minibatch, and the cell's step is one row a shard.
"""

from __future__ import annotations

import functools

import numpy as np

from chipbench import harness
from chipbench.jobs import fit_sync, fit_sync_groups, fit_sync_lm

FAULTS = {
    "lr_x1.5": fit_sync.FAULTS["lr_x1.5"],
    "own_block_seen": {"fault": "own_block_seen"},    # the answer leaks
    "causal_mask": {"fault": "causal_mask"},          # causal over 2L
    "positions_not_shared": {"fault": "positions_not_shared"},  # p(i) = i
    "no_loss_weight": {"fault": "no_loss_weight"},    # 1 / t left out
    "loss_on_all": {"fault": "loss_on_all"},          # unmasked counted
    "shifted_share": fit_sync_lm.FAULTS["shifted_share"],
    "no_renorm": fit_sync_lm.FAULTS["no_renorm"],
}

_STREAM = "diffusion"   # the model's one declared stream, its first


def make_rows(rng: np.random.Generator, traffic: dict, cfg: dict):
    """``(ids, ids)``: ``fit_sync_lm``'s rows, each its own label. The
    language never draws the id that stands for ``[MASK]``."""
    ids, _next = fit_sync_lm.make_rows(
        rng, traffic, {**cfg, "vocab_size": cfg["mask_token_id"]})
    return ids, ids


def _first_draw(key):
    """What a top-level flax module's first ``make_rng`` from a stream
    seeded with ``key`` returns."""
    import flax.linen as nn

    class Draw(nn.Module):
        @nn.compact
        def __call__(self):
            return self.make_rng(_STREAM)

    return Draw().apply({}, rngs={_STREAM: key})


def restated_noise(seed: int, step: int, n_shards: int, mini_batch: int,
                   seq_len: int, eps: float):
    """``(t [rows], m [rows, seq_len])`` of global step ``step`` (from
    0), rows in the feed's order: shard by shard."""
    import jax

    rng = jax.random.key(seed)
    for _ in range(step):
        rng = jax.random.split(rng)[1]
    step_key = jax.random.split(rng)[0]
    levels, masks = [], []
    for shard in range(n_shards):
        key = _first_draw(jax.random.fold_in(
            jax.random.fold_in(step_key, shard), 1))
        k_level, k_mask = jax.random.split(key)
        t = eps + (1.0 - eps) * jax.random.uniform(k_level, (mini_batch, 1))
        levels.append(np.asarray(t[:, 0]))
        masks.append(np.asarray(
            jax.random.uniform(k_mask, (mini_batch, seq_len)) < t))
    return np.concatenate(levels), np.concatenate(masks)


def mask_pairs_off(rule, allowed, seq_len: int) -> int:
    """On how many pairs ``(query, key)`` of the ``2 seq_len`` tokens the
    two masks disagree, a block of queries at a time; each takes a
    column of query indices and a row of key indices."""
    t_all, off = 2 * seq_len, 0
    cols = np.arange(t_all, dtype=np.int32)[None, :]
    for lo in range(0, t_all, 1_024):
        rows = np.arange(lo, min(lo + 1_024, t_all), dtype=np.int32)[:, None]
        off += int(np.sum(np.asarray(rule(rows, cols))
                          != np.asarray(allowed(rows, cols))))
    return off


def _reference_mask(cell, fault=None):
    seq_len, block = cell.traffic["seq_len"], cell.config["block_length"]
    return lambda i, j: cell.reference.allowed(i, j, seq_len, block, fault)


class _NoiseInLabels:
    """The reference's ``loss_sum`` for ``fit_sync_lm``'s grader, which
    knows rows, labels and weights only: the labels come as ``[rows, 3,
    L]``, the row's ids, its mask and its noise level."""

    def __init__(self, reference):
        self._reference = reference

    def loss_sum(self, variables, x, packed, w, cfg, precision):
        return self._reference.loss_sum(
            variables, x, packed[:, 0], w, cfg, precision,
            noise=(packed[:, 2, 0], packed[:, 1] > 0))


class _Grader(fit_sync_lm._Grader):
    """``fit_sync_lm``'s grader, handing the reference each step's
    restated noise beside its rows: it is called once a step, in order,
    with that step's global minibatch."""

    def __init__(self, reference, cfg: dict, block_rows: int, precision: str,
                 traffic: dict):
        super().__init__(_NoiseInLabels(reference), cfg, block_rows,
                         precision)
        self._traffic, self._eps, self._step = traffic, cfg["noise_eps"], 0

    def __call__(self, variables: dict, x, y, w):
        t = self._traffic
        level, masked = restated_noise(
            t["program_seed"], self._step, x.shape[0] // t["mini_batch"],
            t["mini_batch"], x.shape[1], self._eps)
        self._step += 1
        packed = np.stack([y, masked, np.broadcast_to(level[:, None],
                                                      y.shape)], 1)
        return super().__call__(variables, x, packed.astype(np.float32), w)


def _counters(window: list, cell):
    """``fit_sync_lm``'s check of the expert layer's counters, the
    masked tokens of each step of the window against the restated draw,
    and the program's mask rule against the reference's."""
    import jax

    from sparktorch_tpu.models import sparse_moe_lm

    checks, notes, inputs = fit_sync_lm._expert_rows(window, cell)
    rule = sparse_moe_lm.BlockDiffusionMask(
        cell.traffic["seq_len"], cell.build_module().config.block_length)
    checks.append(harness.Check("mask_pairs_off", mask_pairs_off(
        rule, _reference_mask(cell), cell.traffic["seq_len"]), 0))
    t, n_shards = cell.traffic, len(jax.devices())
    off, drawn = 0.0, []
    for r in window:
        _, masked = restated_noise(
            t["program_seed"], r["iter"], n_shards, t["mini_batch"],
            t["seq_len"], cell.config["noise_eps"])
        drawn.append(int(masked.sum()))
        off += abs(r["diffusion_masked_tokens"] - drawn[-1])
    tokens = {r["diffusion_tokens"] for r in window}
    checks.append(harness.Check("masked_tokens_off_restated", off, 0))
    notes.append(f"masked tokens a step, as restated: {drawn} of "
                 f"{sorted(tokens)} tokens")
    return checks, notes, inputs


def _reference(cell) -> dict:
    return {"grader": functools.partial(_Grader, traffic=cell.traffic),
            "in_place": True}


def control(cell, seed: int, kinds=None) -> dict:
    """``fit_sync_groups.control``, and for each kind the pairs on which
    its mask differs from the sound reference's (limit 0)."""
    out = fit_sync_groups.control(cell, seed, kinds, faults=FAULTS,
                                  rows=make_rows, **_reference(cell))
    for kind, numbers in out.items():
        numbers["mask_pairs_off"] = mask_pairs_off(
            _reference_mask(cell, FAULTS.get(kind, {}).get("fault")),
            _reference_mask(cell), cell.traffic["seq_len"])
    return out


def run(cell, seed: int, seconds: float, trace_dir=None):
    return fit_sync_groups.run(cell, seed, seconds, trace_dir,
                               rows=make_rows, extra=_counters,
                               **_reference(cell))

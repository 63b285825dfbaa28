"""Job kind ``fit_sync_mtp``: ``fit_sync_lm`` for a language model whose
training forward has TWO heads' losses: latent attention, a selection
bias on the router, and a multi-token prediction module that shares the
embedding and the head. Rows of token ids from ``fit_sync_lm``'s seeded
language, labels the next token, the reference's grader and the expert
rows' check ``fit_sync_lm``'s; the loss (the traffic file's) the row's
mean next-token cross entropy plus the module's, weighed.

What this file adds:

- the module's own counters, from the window's records (the timed call's
  own): ``mtp_tokens_off``, every step counts ``rows x (T - 2)``
  positions (the model counts the mask its sum took), and
  ``mtp_loss_fall``, the module's cross entropy at the window's last
  step over the window's first. Step 1's module loss is held through
  the total loss and the gradient groups of the step's first record;
  on its own it is not compared: ``fit_sync_groups.run`` hands a job
  built on it the window's records alone, and the window opens after
  the first dispatch (PERF.md section 7).
- a grader that fits 680 M parameters beside their Adam state.
- ``mask_pairs_off`` for the causal rule, as ``fit_sync_hlm`` has it.
- the faults ``control`` plants for these mechanisms.
"""

from __future__ import annotations

import functools

from chipbench import harness
from chipbench.jobs import fit_sync, fit_sync_groups, fit_sync_hlm, fit_sync_lm

FAULTS = {
    **fit_sync.FAULTS,
    **{name: {"fault": name} for name in (
        "no_mtp_loss",         # lambda 0
        "mtp_unshifted",       # the module held to t_{i+1}
        "mtp_own_head",        # the shared head gets no gradient from it
        "scale_128",           # 1 / sqrt(128) for 1 / sqrt(192)
        "rope_on_whole_head",  # all 192 dims turned
        "rope_by_halves",      # pair j is dims (j, j + 32): no interleave
        "k_rope_normed", "no_latent_norm",
        "no_selection_bias",   # the experts chosen by the scores alone
        "bias_in_gates")},     # the gates from s + b
    **{name: fit_sync_hlm.FAULTS[name] for name in (
        "no_shared_expert", "no_routed_scale", "softmax_scores",
        "shifted_share", "no_renorm")},
}

make_rows = fit_sync_lm.make_rows
mask_pairs_off = fit_sync_hlm.mask_pairs_off


class _Grader(fit_sync_lm._Grader):
    """``fit_sync_lm``'s grader with a one-block step that keeps no
    running sum, which does not fit here."""

    def __init__(self, reference, cfg: dict, block_rows: int,
                 precision: str):
        import jax

        super().__init__(reference, cfg, block_rows, precision)
        self._last = None  # the gradient the last call handed out
        self._one = jax.jit(lambda params, rest, xb, yb, wb: (
            jax.value_and_grad(lambda p: reference.loss_sum(
                {**rest, "params": p}, xb, yb, wb, cfg, precision))(params)))

    def __call__(self, variables: dict, x, y, w):
        if x.shape[0] > self.block_rows:
            return super().__call__(variables, x, y, w)
        import jax
        import jax.numpy as jnp

        # one block a step: its gradient IS the sum, and no running sum
        # lies beside it (680 M parameters: weights, Adam's moments, a
        # running sum and a block's gradient are 13.6 GB before any
        # activation). The LAST step's gradient has to be gone before this
        # one's results are allocated, at dispatch: the caller has dropped
        # it, but the chip still held it at the third step's call (11.3 GB
        # in use with weights and moments, 3.4 of this program's scratch
        # reserved, 2.7 more asked for: my chip runs, PR 40), so it is
        # deleted here, after the update that read it has ended
        jax.block_until_ready(variables["params"])
        for leaf in jax.tree.leaves(self._last):
            if not leaf.is_deleted():
                leaf.delete()
        rest = {k: v for k, v in variables.items() if k != "params"}
        x, y, w = (jnp.asarray(a) for a in (x, y, w))
        num, grads = self._one(variables["params"], rest, x, y, w)
        den = jnp.maximum(jnp.sum(w), 1.0)
        self._last = self._scale(grads, den)
        return num / den, self._last


_REFERENCE = {"grader": _Grader, "in_place": True}


def _counters(window: list, cell):
    """``fit_sync_lm``'s check of the expert layers' counters; the causal
    rule the program's kernels are built on against the reference's, pair
    by pair; and the module's own counters in the window: its token
    counts and its loss's fall."""
    from sparktorch_tpu.ops import latent_attention

    checks, notes, inputs = fit_sync_lm._expert_rows(window, cell)
    t = cell.traffic
    checks.append(harness.Check("mask_pairs_off", mask_pairs_off(
        latent_attention._RULE, cell.reference.allowed, t["seq_len"]), 0))
    numbers = {
        "mtp_tokens_off": sum(
            abs(r["mtp_tokens"] - r["examples"] * (t["seq_len"] - 2))
            for r in window),
        "mtp_loss_fall": window[-1]["mtp_loss"] / window[0]["mtp_loss"],
    }
    checks += [harness.Check(k, numbers[k], limit)
               for k, limit in t["mtp_limits"].items()]
    notes.append(f"the module's own loss in the window: "
                 f"{[round(r['mtp_loss'], 5) for r in window]}")
    return checks, notes, inputs


def control(cell, seed: int, kinds=None) -> dict:
    """``fit_sync_groups.control``; no fault here moves the mask's rule."""
    out = fit_sync_groups.control(cell, seed, kinds, faults=FAULTS,
                                  rows=make_rows, **_REFERENCE)
    for numbers in out.values():
        numbers["mask_pairs_off"] = 0
    return out


run = functools.partial(fit_sync_groups.run, rows=make_rows, extra=_counters,
                        **_REFERENCE)

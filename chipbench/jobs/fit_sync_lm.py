"""Job kind ``fit_sync_lm``: ``fit_sync_groups`` (``fit_sync``'s window
with step 1's gradient compared part by part) for a language model:
rows of token ids, labels the next token of each position, the loss the
row's mean next-token cross entropy.

What this file adds: the rows, a reference that fits beside weights
that fill a third of the chip, the faults ``control`` plants for the
mechanisms of a sparse-attention MoE LM, and, where the records carry
an expert layer's counters, their check and the inputs of their metric.

Rows a model can learn fast: the ids of a row come from a small subset
of the vocabulary's slice (``active_vocab`` ids), and on most positions
(``map_share``) the next token is a fixed map of the current one, else
a new draw from the subset. The loss falls first by the support, then
by the map. The subset and the map are the traffic's (``language_seed``,
the same in every run); ``--seed`` draws the rows from that language.
So every run sends the router tokens of one distribution, and the rows
its experts see (which the step's time follows) differ from run to run
by sampling alone.
"""

from __future__ import annotations

import functools

import numpy as np

from chipbench import harness
from chipbench.jobs import fit_sync, fit_sync_groups

# Faults planted in the reference put in the program's place: the first
# two are ``fit_sync``'s; the others are the mechanisms this job's
# configurations add, each computed wrongly in one plausible way
# (``cfg["fault"]``, read by the reference).
FAULTS = {
    **fit_sync.FAULTS,
    "no_selection": {"fault": "no_selection"},    # every causal key attended
    "shifted_share": {"fault": "shifted_share"},  # told the next experts
    "no_renorm": {"fault": "no_renorm"},          # gates not renormalised
}


def make_rows(rng: np.random.Generator, traffic: dict, cfg: dict):
    """``(ids, labels)``, both ``[rows, seq]`` float32: ``labels[t]`` is
    the token after ``ids[t]``."""
    n, seq = traffic["resident_rows"], traffic["seq_len"]
    language = np.random.default_rng(traffic["language_seed"])
    active = language.choice(cfg["vocab_size"], traffic["active_vocab"],
                             replace=False)
    step = language.permutation(traffic["active_vocab"])
    tok = np.empty((n, seq + 1), np.int64)
    tok[:, 0] = rng.integers(0, active.size, n)
    fresh = rng.integers(0, active.size, (n, seq))
    mapped = rng.random((n, seq)) < traffic["map_share"]
    for t in range(seq):
        tok[:, t + 1] = np.where(mapped[:, t], step[tok[:, t]], fresh[:, t])
    ids = active[tok]
    return ids[:, :-1].astype(np.float32), ids[:, 1:].astype(np.float32)


class _Grader:
    """``_train.Grader``'s result for weights that fill a third of the
    chip: the gradient is summed over the blocks of rows in place (the
    running sum is donated to each block's program), so two copies of it
    are alive at a time, not three."""

    def __init__(self, reference, cfg: dict, block_rows: int,
                 precision: str):
        import jax
        import jax.numpy as jnp

        self.block_rows = block_rows

        @functools.partial(jax.jit, donate_argnums=(5,))
        def block(params, rest, xb, yb, wb, total):
            num, g = jax.value_and_grad(
                lambda p: reference.loss_sum({**rest, "params": p}, xb, yb,
                                             wb, cfg, precision))(params)
            return num, jax.tree.map(jnp.add, total, g)

        self._block = block
        self._zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))
        self._scale = jax.jit(lambda g, den: jax.tree.map(
            lambda a: a / den, g), donate_argnums=(0,))

    def __call__(self, variables: dict, x, y, w):
        import jax.numpy as jnp

        rest = {k: v for k, v in variables.items() if k != "params"}
        num, den = 0.0, 0.0
        total = self._zeros(variables["params"])
        for lo in range(0, x.shape[0], self.block_rows):
            xb, yb, wb = (jnp.asarray(a[lo:lo + self.block_rows])
                          for a in (x, y, w))
            n, total = self._block(variables["params"], rest, xb, yb, wb,
                                   total)
            num, den = num + n, den + jnp.sum(wb)
        den = jnp.maximum(den, 1.0)
        return num / den, self._scale(total, den)


def _expert_rows(window: list, cell):
    """Check, note and metric inputs from the expert layer's counters,
    where the window's records carry them."""
    moe = [r for r in window if "moe_rows_max" in r]
    if not moe:
        return [], [], {}
    rows = [r["moe_rows"] for r in moe]
    routed = [r["moe_rows"] + r["moe_pairs_dropped"] for r in moe]
    return (
        [harness.Check("moe_pairs_dropped",
                       sum(r["moe_pairs_dropped"] for r in moe), 0)],
        [f"expert rows a step: computed {np.mean(rows):.0f} (first step of "
         f"the window {rows[0]:.0f}, last {rows[-1]:.0f}, range "
         f"{min(rows):.0f}-{max(rows):.0f}) of {np.mean(routed):.0f} pairs "
         f"routed to held experts, most loaded expert "
         f"{np.mean([r['moe_rows_max'] for r in moe]):.0f}"],
        {"moe_rows_max": [r["moe_rows_max"] for r in moe],
         "moe_rows_mean": [r["moe_rows_mean"] for r in moe]})


_REFERENCE = {"grader": _Grader, "in_place": True}
control = functools.partial(fit_sync_groups.control, faults=FAULTS,
                            rows=make_rows, **_REFERENCE)
run = functools.partial(fit_sync_groups.run, rows=make_rows,
                        extra=_expert_rows, **_REFERENCE)

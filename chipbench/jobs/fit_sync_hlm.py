"""Job kind ``fit_sync_hlm``: ``fit_sync_lm`` for a language model whose
layers differ in kind (window and full-causal attention with their own
head counts and rotary tables, a gated attention output, a dense layer
before the expert layers, a shared expert beside sigmoid-routed ones):
rows of token ids from ``fit_sync_lm``'s seeded language, labels the
next token, the loss the row's mean next-token cross entropy, the
reference and its grader ``fit_sync_lm``'s.

What this file adds: the faults ``control`` plants for these mechanisms,
and ``mask_pairs_off``: each kind of layer's mask is a static rule, so
the program's rule is held to the reference's pair by pair over the
cell's whole ``T x T`` square (on the host, after the window). At step 1,
random weights, one key more among the 512 a query attends moves no
norm (``window_513`` reads inside every limit); the pairs can tell.
What that check is not: it compares rule objects and takes nothing from
the timed path, so it cannot see a kernel or a tile table that misapplies
a correct rule; ``tests/test_rule_attention.py`` holds those on the CPU.
"""

from __future__ import annotations

import functools

import numpy as np

from chipbench import harness
from chipbench.jobs import fit_sync, fit_sync_groups, fit_sync_lm

FAULTS = {
    **fit_sync.FAULTS,
    "window_ignored": {"fault": "window_ignored"},  # window layers causal
    "window_513": {"fault": "window_513"},          # one key too many
    "rope_swapped": {"fault": "rope_swapped"},      # the other kind's table
    "no_yarn": {"fault": "no_yarn"},                # plain rotary, factor 1
    "no_attn_gate": {"fault": "no_attn_gate"},
    "no_shared_expert": {"fault": "no_shared_expert"},
    "no_routed_scale": {"fault": "no_routed_scale"},  # 2.5 left out
    "softmax_scores": {"fault": "softmax_scores"},    # for sigmoid
    "shifted_share": fit_sync_lm.FAULTS["shifted_share"],
    "no_renorm": fit_sync_lm.FAULTS["no_renorm"],
}

make_rows = fit_sync_lm.make_rows

# the program's kinds of layer by the configuration's
_PROGRAM_KIND = {"full_attention": "full", "sliding_attention": "window"}


def mask_pairs_off(rule, allowed, seq_len: int) -> int:
    """On how many pairs ``(query, key)`` of ``seq_len`` tokens the two
    masks disagree, a block of queries at a time; each takes a column of
    query indices and a row of key indices."""
    off = 0
    cols = np.arange(seq_len, dtype=np.int32)[None, :]
    for lo in range(0, seq_len, 1_024):
        rows = np.arange(lo, min(lo + 1_024, seq_len), dtype=np.int32)[:, None]
        off += int(np.sum(np.asarray(rule(rows, cols))
                          != np.asarray(allowed(rows, cols))))
    return off


def _reference_mask(cell, layer_type: str, fault=None):
    window = cell.config["sliding_window"]
    return lambda i, j: cell.reference.allowed(i, j, layer_type, window,
                                               fault)


def _layer_types(cell) -> list:
    return list(dict.fromkeys(cell.config["layer_types"]))


def _counters(window: list, cell):
    """``fit_sync_lm``'s check of the expert layer's counters, and each
    kind of layer's rule as the program's module builds it against the
    reference's, summed over the kinds."""
    from sparktorch_tpu.models import sparse_moe_lm

    checks, notes, inputs = fit_sync_lm._expert_rows(window, cell)
    config = cell.build_module().config
    kinds = {k.attention: k for k in config.layers}
    off = {t: mask_pairs_off(
        sparse_moe_lm.layer_rule(config, kinds[_PROGRAM_KIND[t]]),
        _reference_mask(cell, t), cell.traffic["seq_len"])
        for t in _layer_types(cell)}
    checks.append(harness.Check("mask_pairs_off", sum(off.values()), 0))
    notes.append(f"pairs on which the program's rule differs from the "
                 f"reference's, by kind of layer: {off}")
    return checks, notes, inputs


def control(cell, seed: int, kinds=None) -> dict:
    """``fit_sync_groups.control``, and for each kind the pairs on which
    its masks differ from the sound reference's (limit 0)."""
    out = fit_sync_groups.control(cell, seed, kinds, faults=FAULTS,
                                  rows=make_rows, **fit_sync_lm._REFERENCE)
    for kind, numbers in out.items():
        fault = FAULTS.get(kind, {}).get("fault")
        numbers["mask_pairs_off"] = sum(mask_pairs_off(
            _reference_mask(cell, t, fault), _reference_mask(cell, t),
            cell.traffic["seq_len"]) for t in _layer_types(cell))
    return out


run = functools.partial(fit_sync_groups.run, rows=make_rows, extra=_counters,
                        **fit_sync_lm._REFERENCE)

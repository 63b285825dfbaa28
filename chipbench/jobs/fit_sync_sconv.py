"""Job kind ``fit_sync_sconv``: ``fit_sync_lm`` for a language model
whose layers are gated short convolutions three to one with grouped-query
attention at 64-wide heads, under a tied head, with sigmoid-routed
experts chosen under an expert bias: rows of token ids from
``fit_sync_lm``'s seeded language, labels the next token, the loss the
row's mean next-token cross entropy, the expert rows' check and the
grader ``fit_sync_lm``'s (four rows a step, a block of one row into a
running sum).

What this file adds: the faults ``control`` plants for these mechanisms;
``mask_pairs_off`` for the attention layers' causal rule, as
``fit_sync_hlm`` has it; and ``sconv_tokens_off``: every step of the
window counts the tokens its fused convolution passes took, against
``conv layers x rows x T`` worked out from the configuration and traffic
files alone (the program counts them from the shapes it hands the
kernel, so a program with other layers convolutions, or one that runs
the pass on part of a step's rows, reads off). What those two are not:
they take nothing from the kernels' results, so they cannot see a kernel
that misapplies a correct rule or a pass that drops its halo; the
convolution layers' gradients see the second (PERF.md section 2), and
``tests/test_short_conv_gate.py`` and ``tests/test_rule_attention.py``
hold the kernels on the CPU.
"""

from __future__ import annotations

import functools

from chipbench import harness
from chipbench.jobs import (fit_sync, fit_sync_groups, fit_sync_hlm,
                            fit_sync_lm)

FAULTS = {
    **fit_sync.FAULTS,
    **{name: {"fault": name} for name in (
        "no_conv",             # taps 0, 0, 1
        "conv_not_causal",
        "conv_reach_4",        # a fourth tap on s[t - 3]
        "conv_silu",           # the sibling's SiLU after the taps
        "no_in_gate", "no_out_gate",
        "scale_128",           # 1 / sqrt(128) for 1 / sqrt(64)
        "rope_on_half_head",   # 32 of the 64 dims turned
        "no_qk_norm",
        "untied_head",         # no gradient reaches the embedding by it
        "no_selection_bias",   # the experts chosen by the scores alone
        "bias_in_gates")},     # the gates from s + b
    **{name: fit_sync_hlm.FAULTS[name] for name in (
        "softmax_scores", "no_renorm", "shifted_share")},
}

make_rows = fit_sync_lm.make_rows
mask_pairs_off = fit_sync_hlm.mask_pairs_off
_REFERENCE = fit_sync_lm._REFERENCE


def tokens_expected(cell) -> int:
    """The tokens a step's fused convolution passes take on a chip, by
    the configuration and traffic files: rows x ``T`` a ``conv`` layer."""
    t = cell.traffic
    return (sum(kind == "conv" for kind in cell.config["layer_types"])
            * t["mini_batch"] * t["seq_len"])


def _counters(window: list, cell):
    """``fit_sync_lm``'s check of the expert layers' counters; the causal
    rule of the program's attention layers against the reference's, pair
    by pair; and the tokens the fused pass took, step by step."""
    from sparktorch_tpu.models import sparse_moe_lm

    checks, notes, inputs = fit_sync_lm._expert_rows(window, cell)
    module = cell.build_module()
    full = next(k for k in module.config.layers if k.attention == "full")
    checks.append(harness.Check("mask_pairs_off", mask_pairs_off(
        sparse_moe_lm.layer_rule(module.config, full),
        cell.reference.allowed, cell.traffic["seq_len"]), 0))
    want = tokens_expected(cell)
    checks.append(harness.Check("sconv_tokens_off", sum(
        abs(r["sconv_tokens"] - want * r["examples"]
            / cell.traffic["mini_batch"]) for r in window), 0))
    notes.append(f"tokens through the fused convolution pass a step: "
                 f"{want} a chip")
    # a chip's, for the roofline's reader to hold the kernels' calls to
    inputs["sconv_tokens"] = [
        r["sconv_tokens"] * cell.traffic["mini_batch"] / r["examples"]
        for r in window]
    return checks, notes, inputs


def control(cell, seed: int, kinds=None) -> dict:
    """``fit_sync_groups.control``; no fault here moves the attention
    layers' rule or the count of tokens."""
    out = fit_sync_groups.control(cell, seed, kinds, faults=FAULTS,
                                  rows=make_rows, **_REFERENCE)
    for numbers in out.values():
        numbers["mask_pairs_off"] = 0
        numbers["sconv_tokens_off"] = 0
    return out


run = functools.partial(fit_sync_groups.run, rows=make_rows, extra=_counters,
                        **_REFERENCE)

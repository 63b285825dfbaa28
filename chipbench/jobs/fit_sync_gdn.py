"""Job kind ``fit_sync_gdn``: ``fit_sync_lm`` for a language model whose
layers are Gated DeltaNet linear attention three to one with gated full
attention (a chunked gated delta rule behind a causal convolution, a
gated norm a head; 256-wide heads with an element-wise output gate; a
gated shared expert beside softmax-routed ones): rows of token ids from
``fit_sync_lm``'s seeded language, labels the next token, the loss the
row's mean next-token cross entropy, the expert rows' check
``fit_sync_lm``'s, the one-block grader ``fit_sync_mtp``'s (one row of
16,384 tokens a step: its gradient IS the step's, and no running sum
lies beside it).

What this file adds: the faults ``control`` plants for these mechanisms;
``mask_pairs_off`` for the full layers' causal rule, as ``fit_sync_hlm``
has it; and ``gdn_chunks_off``: every step of the window counts the
chunks the rule's forward kernel ran, against ``linear layers x rows x
value heads x T / 64`` worked out from the configuration and traffic
files alone (the program counts them from the shapes and the chunk it
hands the kernel, so a program built with another chunk, or with other
layers linear, reads off). What those two are not: they take nothing
from the kernels' results, so they cannot see a kernel that misapplies a
correct rule or drops the state between chunks; ``state_not_carried`` is
seen by the linear layers' gradients (PERF.md section 2), and
``tests/test_gated_delta_rule.py`` holds the kernels on the CPU.
"""

from __future__ import annotations

import functools

from chipbench import harness
from chipbench.jobs import (fit_sync, fit_sync_groups, fit_sync_hlm,
                            fit_sync_lm, fit_sync_mtp)

FAULTS = {
    "lr_x1.5": fit_sync.FAULTS["lr_x1.5"],
    **{name: {"fault": name} for name in (
        "state_not_carried",   # the state set to 0 at every 64th token
        "no_decay",            # exp(g) = 1
        "no_beta",             # beta = 1
        "no_conv", "conv_not_causal",
        "no_qk_l2norm",
        "no_out_gate_norm",    # silu(z) left out
        "no_attn_gate",
        "rope_on_whole_head",  # all 256 dims turned
        "no_shared_gate",
        "softmax_top8")},      # 8 experts a token for 10
    **{name: fit_sync_lm.FAULTS[name] for name in (
        "no_renorm", "shifted_share")},
}

make_rows = fit_sync_lm.make_rows
mask_pairs_off = fit_sync_hlm.mask_pairs_off
_REFERENCE = fit_sync_mtp._REFERENCE


# The chunk the cell's roofline and its count of chunks are held to: the
# published kernels'. Nothing of the program is asked for it.
_CHUNK = 64


def chunks_expected(cell) -> int:
    """The chunks the rule's forward kernel has to run a step a chip, by
    the configuration and traffic files: every layer but each
    ``full_attention_interval``-th is linear, and runs a chunk of 64
    tokens a value head a row."""
    cfg, t = cell.config, cell.traffic
    linear = sum((layer + 1) % cfg["full_attention_interval"] != 0
                 for layer in range(cfg["num_hidden_layers"]))
    return (linear * t["mini_batch"] * cfg["linear_num_value_heads"]
            * (t["seq_len"] // _CHUNK))


def _counters(window: list, cell):
    """``fit_sync_lm``'s check of the expert layers' counters; the causal
    rule of the program's full layers against the reference's, pair by
    pair; and the chunks the rule's kernel ran, step by step."""
    from sparktorch_tpu.models import sparse_moe_lm

    checks, notes, inputs = fit_sync_lm._expert_rows(window, cell)
    module = cell.build_module()
    full = next(k for k in module.config.layers if k.attention == "full")
    checks.append(harness.Check("mask_pairs_off", mask_pairs_off(
        sparse_moe_lm.layer_rule(module.config, full),
        cell.reference.allowed, cell.traffic["seq_len"]), 0))
    want = chunks_expected(cell)
    checks.append(harness.Check("gdn_chunks_off", sum(
        abs(r["gdn_chunks"] - want * r["examples"]
            / cell.traffic["mini_batch"]) for r in window), 0))
    notes.append(f"chunks of the gated delta rule a step: {want} a chip")
    return checks, notes, inputs


def control(cell, seed: int, kinds=None) -> dict:
    """``fit_sync_groups.control``; no fault here moves the full layers'
    rule or the count of chunks."""
    out = fit_sync_groups.control(cell, seed, kinds, faults=FAULTS,
                                  rows=make_rows, **_REFERENCE)
    for numbers in out.values():
        numbers["mask_pairs_off"] = 0
        numbers["gdn_chunks_off"] = 0
    return out


run = functools.partial(fit_sync_groups.run, rows=make_rows, extra=_counters,
                        **_REFERENCE)

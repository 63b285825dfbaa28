"""Model FLOP/s utilisation of the step program: the operations the
forward and backward passes of one step need per chip (the
configuration's own function under ``flops/``, from shapes; recomputed
operations not counted) over the step's device-busy time and the chip's
published bf16 peak. Device trace."""

from chipbench import harness


def read(ctx):
    cell, li = ctx["cell"], ctx["inputs"]
    if ctx["trace"] is None or "steps_per_call" not in li:
        return None
    step_ms = harness.load_module("layer_metrics", "step_device_ms",
                                  cell.root).read(ctx)
    if not step_ms:
        return None
    flops = cell.flops().train_step_flops(
        cell.config, rows=li["examples_per_step"] // li["n_chips"],
        seq=cell.traffic["seq_len"])
    return 100.0 * flops / (step_ms / 1e3) / ctx["peaks"]["bf16_flops_per_s"]

"""Job kind ``fit_sync_ep`` at a tiny size on four host devices: the
whole job (``train_distributed`` on the mesh dp=1 x ep=4, the expert
layers whole across it, held to the reference laid over the four
devices), ``correct`` on a seed above 2**31, and the planted faults, each
outside a limit of the tiny traffic. The suite's process may hold one
device, so the job runs in a process of its own."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]

_SCRIPT = """
import json, sys
from pathlib import Path
sys.path.insert(0, {repo!r})
from chipbench import harness
tiny = Path({repo!r}) / "chipbench" / "tests" / "tiny"
cell = harness.resolve_cell("tiny_fit_sync_ep", tiny / "BENCHMARK_ep.json",
                            tiny)
if sys.argv[1] == "run":
    res = cell.job.run(cell, 2**31 + 5, 0.2, None)
    print("RESULT", json.dumps({{
        "checks": {{c.name: [c.value, c.limit, c.ok] for c in res.checks}},
        "rate": res.end_to_end["rate"], "steps": res.layer_inputs["steps"],
        "n_chips": res.layer_inputs["n_chips"]}}))
else:
    print("RESULT", json.dumps({{
        "limits": cell.traffic["limits"],
        "control": cell.job.control(cell, 7, kinds=tuple(
            sys.argv[2].split(",")))}}))
"""


def _job(*args):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS":
           "--xla_force_host_platform_device_count=4 "
           "--xla_cpu_enable_concurrency_optimized_scheduler=false"}
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT.format(repo=str(REPO)), *args],
        env=env, capture_output=True, text=True)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.split("RESULT ", 1)[1])


def test_the_job_is_correct_on_four_devices():
    got = _job("run")
    assert got["n_chips"] == 4 and got["steps"] >= 2 and got["rate"] > 0
    failed = {k: v for k, v in got["checks"].items() if not v[2]}
    assert not failed, failed
    for name in ("moe_pairs_dropped", "mask_pairs_off", "exchange_rows_off",
                 "steps_with_wrong_row_count"):
        assert got["checks"][name][0] == 0


def test_every_planted_fault_reads_outside_a_limit():
    kinds = ("bf16,own_rows_only,experts_psummed,window_ignored,window_1025,"
             "no_yarn,no_renorm,rope_swapped,lr_x1.5")
    got = _job("control", kinds)
    limits = {**got["limits"], "mask_pairs_off": 0}
    outside = {kind: [k for k, v in numbers.items()
                      if k in limits and not v <= limits[k]]
               for kind, numbers in got["control"].items()}
    # float32 limits here: bf16 is a fault of this tiny traffic too
    assert all(outside.values()), outside
    assert "grad_norm_rel_experts" in outside["experts_psummed"]
    assert got["control"]["experts_psummed"]["grad_norm_rel_experts"] \
        == pytest.approx(3.0, rel=1e-3)
    assert "grad_norm_rel_experts" in outside["own_rows_only"]
    assert "mask_pairs_off" in outside["window_1025"]

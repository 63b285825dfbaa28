"""The command refuses to run off the TPU: another exit code than 0 and
no result line."""

import os
import subprocess
import sys

from chipbench import harness


def test_cli_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="7")
    cell = harness.resolve_cell(
        __import__("json").loads(
            harness.BENCHMARK_JSON.read_text())["workloads"][0]["name"])
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", cell.name,
         "--seed", str(2 ** 31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=harness.REPO, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())
    assert "no accelerator" in proc.stderr

"""``python -m chipbench.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``: one run of one cell, on the TPU it is started on.

The last line of standard output is the one JSON object the driver
reads. With ``--trace 0`` its metrics are the cell's end-to-end metrics,
taken by the host's clock with the profiler off; with ``--trace 1`` a
short profiled stretch gives the cell's per-layer metrics, the device's
busy seconds and the breakdown. There is no CPU path: without a TPU, or
with another number of chips than the cell names, the run exits with
code 2 and prints no result.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys

from chipbench import harness


def tpu_devices(chips=None):
    """JAX's devices, or None (with the reason on stderr) unless they
    are TPUs and, where ``chips`` is given, exactly that many."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chipbench: no accelerator: jax.devices()[0] is "
              f"{devices[0].platform!r}; the benchmark runs on a TPU only",
              file=sys.stderr)
        return None
    if chips is not None and len(devices) != chips:
        print(f"chipbench: the cell needs {chips} chip(s), JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return None
    return devices


def layer_metrics(cell, result, table, summary, device_kind: str) -> dict:
    """Every per-layer metric of the cell whose reader finds something
    to read."""
    ctx = {"cell": cell, "inputs": result.layer_inputs, "trace": table,
           "summary": summary,
           "peaks": harness.load_peaks(device_kind, cell.root)}
    out = {}
    for m in cell.per_layer:
        value = harness.load_module("layer_metrics", m["name"],
                                    cell.root).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.resolve_cell(args.workload)
    devices = tpu_devices(cell.chips)
    if devices is None:
        return 2
    import jax

    cache = harness.arm_compile_cache()
    kind = devices[0].device_kind
    harness.load_peaks(kind)  # an unknown device is an error, up front
    print(f"chipbench: cell={cell.name} seed={args.seed} "
          f"seconds={args.seconds} trace={args.trace} platform=tpu "
          f"kind={kind} count={len(devices)} jax={jax.__version__} "
          f"compile_cache={cache}", flush=True)

    trace_dir = None
    if args.trace:
        trace_dir = harness.REPO / ".chipbench_trace" / cell.name
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
    result = cell.job.run(cell, args.seed, args.seconds,
                          str(trace_dir) if trace_dir else None)

    for note in result.notes:
        print(f"note: {note}")
    for check in result.checks:
        print(check.line())
    peak, buffers, scratch = result.memory
    print(f"memory_peak_bytes={peak} (buffers {buffers} + program scratch "
          f"{scratch}) setup_s={result.setup_s:.3f} "
          f"window_s={result.window_s:.3f}", flush=True)

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": peak}
    line = {"correct": all(c.ok for c in result.checks),
            "attempted": result.attempted, "failed": result.failed}
    if args.trace:
        from chipbench import trace

        table = trace.load(trace_dir)
        summary = trace.summarize(table, result.layer_inputs["trace_window"])
        line["metrics"] = layer_metrics(cell, result, table, summary, kind)
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        line["breakdown"] = {"device_ops": summary["device_ops"],
                             "idle_gaps": summary["idle_gaps"]}
        shutil.rmtree(trace_dir.parent, ignore_errors=True)
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        values = {cell.traffic["reports"][k]: v
                  for k, v in result.end_to_end.items()}
        values["setup_s"] = result.setup_s
        line["metrics"] = {n: {"value": float(values[n]), "unit": units[n]}
                           for n in units}
    line["device"] = device
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

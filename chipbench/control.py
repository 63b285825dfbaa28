"""``python -m chipbench.control --workload <cell> --seeds 1,2,3``: the
readings a cell's limits of ``correct`` are set from, on the chip, at
the cell's own size, many seeds to a process.

For each seed the cell's job puts the reference in the program's place
in the precision the configuration states (``bf16``, which has to
pass), in the nearest below it (``fp8``, which has to fail a limit) and
sound but for a planted fault, and prints the numbers ``correct``
compares, against the float32 reference's (``--kinds`` picks some).
With ``--program-seconds`` it also drives the cell's own job for that
long on each seed and prints the sound program's numbers. The
benchmark's runs never call this.
"""

from __future__ import annotations

import argparse
import json
import sys

from chipbench import harness
from chipbench.run import tpu_devices


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--kinds", default=None)
    ap.add_argument("--program-seconds", type=float, default=None)
    args = ap.parse_args(argv)
    cell = harness.resolve_cell(args.workload)
    # any TPU will do for the control: it is the reference alone, and
    # the rows it draws follow the cell's shards, not the chips present
    if tpu_devices(cell.chips if args.program_seconds else None) is None:
        return 2
    harness.arm_compile_cache()
    kinds = {"kinds": tuple(args.kinds.split(","))} if args.kinds else {}
    for seed in (int(s) for s in args.seeds.split(",")):
        line = {"cell": cell.name, "seed": seed,
                "limits": cell.traffic["limits"]}
        if args.kinds != "":
            line["control"] = cell.job.control(cell, seed, **kinds)
        if args.program_seconds:
            res = cell.job.run(cell, seed, args.program_seconds, None)
            line["program"] = {c.name: c.value for c in res.checks}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Executor-process entry for the localspark runtime.

Spawned by ``localsession.RDD._run_executors`` — one process per
partition, the analog of Spark's forked Python workers. The bootstrap
order is load-bearing: the CPU platform must be pinned *before* any
code (including dill unpickling, which imports the framework and
therefore jax) can initialize a backend — a chip belongs to one
process at a time, and the driver process is the one that holds it.
These executors are the localspark test double's; nothing that
needs the chip (``chip_smoke.py``) passes through them.
"""

import sys


def main(payload_path: str, result_path: str) -> None:
    import jax

    jax.config.update("jax_platforms", "cpu")

    from sparktorch_tpu.spark import localsession

    localsession.install()

    import dill
    import json

    with open(payload_path, "rb") as f:
        header = json.loads(f.readline())
        for p in header["sys_path"]:
            if p not in sys.path:
                sys.path.append(p)
        payload = dill.load(f)

    if payload["barrier"]:
        localsession.BarrierTaskContext._current = localsession.BarrierTaskContext(
            payload["partition_id"], payload["world"]
        )

    out = payload["fn"](iter(payload["rows"]))
    with open(result_path, "wb") as f:
        dill.dump(list(out), f)


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2])

"""sparktorch_tpu.obs.replay — bitwise poison-batch replay.

``python -m sparktorch_tpu.obs.replay bundle.json`` re-runs the
single training step a health replay bundle recorded (see
:class:`sparktorch_tpu.obs.health.TrainHealthLedger`) and verifies it
reproduces the recorded bad numerics **bitwise** — the debugging
story the profiler can't give: *which batch* broke the run, not
*which function*.

A bundle is a ``.json`` meta file plus a sibling ``.npz`` holding the
pre-step state anchor and the offending batch, leaf by leaf. The
bundle names a *builder* — ``"module:function"``, importable in the
replaying process, returning ``{"step_fn", "state", "batch"}`` — that
reconstructs the exact jitted step function and pytree templates
there; the replay then:

1. rebuilds ``(state, batch)`` from the npz leaves over the builder's
   tree structure,
2. checks the state against the bundle's param checksum (a replay
   against drifted params must fail loudly, not "reproduce" garbage),
3. runs ``step - anchor_step + 1`` steps (the anchor re-arms on every
   batch-identity change, so the batch is constant over that range),
4. compares the recorded metric values against the replayed ones by
   their float32 **bit patterns** — the only comparison under which
   two NaNs can agree.

Exit code 0 iff every recorded metric reproduced bitwise.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
from typing import Any, Dict, Mapping, Optional

import numpy as np

from sparktorch_tpu.obs.health import float_bits, tree_checksum
from sparktorch_tpu.obs.log import get_logger

_LOG = get_logger("sparktorch_tpu.obs.replay")


def load_bundle(meta_path: str) -> Dict[str, Any]:
    """Read a replay bundle: the meta dict plus its npz arrays."""
    with open(meta_path) as f:
        meta = json.load(f)
    if meta.get("kind") != "health_replay":
        raise ValueError(f"{meta_path}: not a health replay bundle "
                         f"(kind={meta.get('kind')!r})")
    npz_path = os.path.join(os.path.dirname(os.path.abspath(meta_path)),
                            meta["npz"])
    with np.load(npz_path) as z:
        arrays = {k: z[k] for k in z.files}
    return {"meta": meta, "arrays": arrays, "path": meta_path}


def resolve_builder(spec: str):
    """Import ``"module:function"`` and return the callable."""
    mod_name, sep, fn_name = spec.partition(":")
    if not sep or not fn_name:
        raise ValueError(f"builder must be 'module:function', got {spec!r}")
    mod = importlib.import_module(mod_name)
    fn = getattr(mod, fn_name, None)
    if fn is None:
        raise ValueError(f"builder {spec!r}: {mod_name} has no {fn_name}")
    return fn


def _rebuild(template: Any, arrays: Mapping[str, np.ndarray],
             prefix: str, n: int) -> Any:
    import jax

    t_leaves, treedef = jax.tree_util.tree_flatten(template)
    if treedef.num_leaves != n:
        raise ValueError(
            f"bundle has {n} {prefix} leaves but the builder's template "
            f"has {treedef.num_leaves} — wrong builder for this bundle")
    leaves = []
    for i, tmpl in enumerate(t_leaves):
        a = arrays[f"{prefix}_{i}"]
        dt = getattr(tmpl, "dtype", None)
        if dt is not None and jax.dtypes.issubdtype(dt,
                                                    jax.dtypes.prng_key):
            # Typed PRNG keys were stored as raw key data; re-wrap
            # over the template's impl so the rebuilt state traces
            # identically to the live run.
            a = jax.random.wrap_key_data(
                jax.numpy.asarray(a), impl=jax.random.key_impl(tmpl))
        leaves.append(a)
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _metric_values(metrics: Any) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for name in ("loss", "grad_norm"):
        v = getattr(metrics, name, None)
        if v is not None:
            out[name] = float(np.asarray(v).reshape(-1)[0])
    health = getattr(metrics, "health", None)
    if health is not None:
        for name in ("finite", "update_ratio"):
            v = getattr(health, name, None)
            if v is not None:
                out[name] = float(np.asarray(v).reshape(-1)[0])
    return out


def replay_bundle(bundle: Any, builder: Optional[str] = None
                  ) -> Dict[str, Any]:
    """Replay a bundle (path or the dict from :func:`load_bundle`).

    Returns ``{"match": bool, "steps_run": n, "compared": {name:
    {"recorded_bits", "replayed_bits", "recorded", "replayed",
    "match"}}}``."""
    if isinstance(bundle, str):
        bundle = load_bundle(bundle)
    meta, arrays = bundle["meta"], bundle["arrays"]
    builder_spec = builder or meta.get("builder")
    if not builder_spec:
        raise ValueError(
            "bundle names no builder — pass --builder module:function")
    build = resolve_builder(builder_spec)
    built = build(**(meta.get("builder_kwargs") or {}))
    step_fn = built["step_fn"]
    state = _rebuild(built["state"], arrays, "state",
                     int(meta["n_state_leaves"]))
    batch = _rebuild(built["batch"], arrays, "batch",
                     int(meta["n_batch_leaves"]))
    checksum = tree_checksum(state)
    if checksum != meta["param_checksum"]:
        raise ValueError(
            f"param checksum mismatch: bundle {meta['param_checksum']} vs "
            f"rebuilt state {checksum} — the anchor did not survive the "
            f"round trip")
    n_steps = int(meta["step"]) - int(meta["anchor_step"]) + 1
    if n_steps < 1:
        raise ValueError(f"bad step range: anchor {meta['anchor_step']} "
                         f"> step {meta['step']}")
    metrics = None
    for _ in range(n_steps):
        state, metrics = step_fn(state, batch)
    replayed = _metric_values(metrics)
    compared: Dict[str, Any] = {}
    ok = True
    for name, rec in (meta.get("bad") or {}).items():
        if name not in replayed:
            compared[name] = {"match": False, "replayed": None,
                              "recorded": rec.get("value"),
                              "recorded_bits": rec["bits"],
                              "replayed_bits": None}
            ok = False
            continue
        rbits = float_bits(replayed[name])
        match = rbits == int(rec["bits"])
        compared[name] = {
            "recorded": rec.get("value"), "replayed": replayed[name],
            "recorded_bits": int(rec["bits"]), "replayed_bits": rbits,
            "match": match,
        }
        ok = ok and match
    return {"match": ok, "steps_run": n_steps, "compared": compared,
            "step": int(meta["step"]), "akind": meta.get("akind"),
            "rank": meta.get("rank")}


def main(argv: Optional[list] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m sparktorch_tpu.obs.replay",
        description="Re-run the step a health replay bundle recorded and "
                    "verify the bad numerics reproduce bitwise.")
    ap.add_argument("bundle", help="path to the bundle .json")
    ap.add_argument("--builder", default=None,
                    help="module:function overriding the bundle's builder")
    args = ap.parse_args(argv)
    bundle = load_bundle(args.bundle)
    meta = bundle["meta"]
    print(f"replay bundle: step {meta['step']} (anchor "
          f"{meta['anchor_step']}) rank {meta['rank']} "
          f"akind={meta.get('akind')}")
    result = replay_bundle(bundle, builder=args.builder)
    for name, cmp_ in sorted(result["compared"].items()):
        mark = "ok " if cmp_["match"] else "FAIL"
        print(f"  [{mark}] {name}: recorded bits "
              f"0x{cmp_['recorded_bits']:08x} ({cmp_['recorded']}) vs "
              f"replayed "
              + (f"0x{cmp_['replayed_bits']:08x} ({cmp_['replayed']})"
                 if cmp_["replayed_bits"] is not None else "<absent>"))
    verdict = "bitwise reproduction" if result["match"] else "MISMATCH"
    print(f"replay: {verdict} over {result['steps_run']} step(s)")
    return 0 if result["match"] else 1


if __name__ == "__main__":
    raise SystemExit(main())

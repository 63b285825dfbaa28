"""Mesh construction, sharding rules, multi-host launch, and the
trace-guided mesh auto-tuner.

Submodules import jax at module level (mesh/sharding_rules) or lazily
(tune's measurement path); this package init re-exports only the
names the trainers reach for, without forcing the heavy
imports on ``import sparktorch_tpu.parallel`` alone.
"""

__all__ = [
    "MeshConfig",
    "build_mesh",
    "local_mesh",
    "autotune",
    "TuneResult",
    "enumerate_candidates",
]


def __getattr__(name):
    if name in ("MeshConfig", "build_mesh", "local_mesh"):
        from sparktorch_tpu.parallel import mesh

        return getattr(mesh, name)
    if name in ("autotune", "TuneResult", "enumerate_candidates"):
        from sparktorch_tpu.parallel import tune

        return getattr(tune, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

"""The ambient-mesh probe, on the installed JAX's public API.

One installation is supported (see ``pyproject.toml``); ``axis_size``
and ``set_mesh`` are plain aliases kept so call sites read the same.
"""

from __future__ import annotations

import jax

axis_size = jax.lax.axis_size
set_mesh = jax.set_mesh


def ambient_gspmd_mesh():
    """The ambient mesh (``jax.set_mesh``) when we are in GSPMD
    context, else None.

    "GSPMD context" means a mesh is installed and NONE of its axes is
    Manual — inside a ``shard_map`` body sharding constraints are
    meaningless-to-wrong and collective islands must not nest. The
    mesh returned is the :class:`~jax.sharding.AbstractMesh` (the only
    handle available under ``jit`` tracing): it carries ``shape`` and
    ``axis_names``, and ``jax.shard_map`` / ``with_sharding_constraint``
    resolve specs against it. None means exactly "no mesh set" or "an
    axis is Manual"; an API that moved raises."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.manual_axes:
        return None
    return mesh

"""``shard_map`` / mesh-context entry points (single copy for the whole
package), spelled for the installed jax (0.9): ``jax.shard_map`` with
``check_vma`` and ``jax.set_mesh``.
"""

from __future__ import annotations

import contextlib

import jax


def mesh_context(mesh):
    """Context manager installing ``mesh`` as the ambient mesh for
    tracing (axis names resolvable by ``with_sharding_constraint``/
    collectives); a no-op for ``mesh=None``. Used by the sharded TFG108
    probe, which must re-trace a program exactly as the executor traced
    it, without touching device data."""
    if mesh is None:
        return contextlib.nullcontext()
    return jax.set_mesh(mesh)


def shard_map(f, mesh, in_specs, out_specs, check: bool = False):
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check
    )

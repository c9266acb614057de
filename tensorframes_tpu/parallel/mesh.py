"""Device mesh construction and axis conventions.

The reference's distributed substrate is Apache Spark: partitions +
broadcast + driver-coordinated reduce (SURVEY.md §5-comm). The TPU-native
substrate is a ``jax.sharding.Mesh`` over the chips of a slice, with data
laid out by ``NamedSharding`` and cross-chip traffic compiled to ICI
collectives by XLA's SPMD partitioner.

Axis naming conventions used across the framework:

* ``dp``  — data/batch parallelism (≙ Spark partitions; frames shard their
  row dimension here)
* ``tp``  — tensor parallelism (model weights; used by models/)
* ``sp``  — sequence/context parallelism (long-context attention)
* ``pp`` / ``ep`` — pipeline / expert parallelism (model-level)
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..config import get_config

BATCH_AXIS = "dp"


def _scrubbed(text: str) -> str:
    import re

    return re.sub(r"0x[0-9a-fA-F]+", "0x", text)


def mesh_descriptor(mesh: Mesh) -> Dict[str, object]:
    """JSON-able identity of a mesh for compile-cache keys: axis names +
    sizes and the global device assignment (ids are GLOBAL and agree on
    every process of a fleet, so the descriptor is process-index-
    independent by construction)."""
    return {
        "axes": [[str(n), int(s)] for n, s in
                 zip(mesh.axis_names, mesh.devices.shape)],
        "devices": [int(d.id) for d in mesh.devices.flat],
    }


def spec_descriptor(spec) -> list:
    """PartitionSpec → JSON-able form: one entry per dim, each None, an
    axis name, or a list of axis names."""
    out = []
    for part in tuple(spec):
        if part is None:
            out.append(None)
        elif isinstance(part, (tuple, list)):
            out.append([str(p) for p in part])
        else:
            out.append(str(part))
    return out


def sharding_descriptor(sharding) -> Optional[Dict[str, object]]:
    """Stable JSON-able identity of an input sharding for dispatch keys
    and persistent-cache fingerprints — None for the trivial placement
    (single default device, or no sharding at all), so host-fed and
    plain single-device dispatches keep their unsharded identity.

    An AOT executable is specialized to its input shardings (calling it
    with differently-laid-out arguments raises), so everything that
    changes the layout must be in the key: mesh axis names + shape +
    device assignment and the per-dim partition spec for
    ``NamedSharding``; the concrete device for an off-default
    ``SingleDeviceSharding``; a scrubbed repr for exotic sharding types.
    """
    if sharding is None:
        return None
    SDS = getattr(jax.sharding, "SingleDeviceSharding", ())
    if isinstance(sharding, SDS):
        try:
            (dev,) = sharding.device_set
        except (ValueError, TypeError):  # pragma: no cover - defensive
            return {"type": "single", "repr": _scrubbed(repr(sharding))}
        # the default placement — where a fresh host transfer lands on
        # THIS process — keys identically to host feeds. That device is
        # the process-LOCAL default (jax.devices()[0] only equals it on
        # rank 0): comparing against the global device 0 would give every
        # other rank a device-bearing token for plain host feeds, so no
        # rank would ever share a store entry or match a warmed key.
        if dev == default_device():
            return None
        return {"type": "single", "device": int(dev.id)}
    if isinstance(sharding, NamedSharding):
        desc = {
            "type": "named",
            "mesh": mesh_descriptor(sharding.mesh),
            "spec": spec_descriptor(sharding.spec),
        }
        mk = getattr(sharding, "memory_kind", None)
        if mk is not None:
            desc["memory_kind"] = str(mk)
        return desc
    return {
        "type": type(sharding).__name__,
        "repr": _scrubbed(repr(sharding)),
        "devices": sorted(int(d.id) for d in sharding.device_set),
    }


def default_device():
    """Where an uncommitted host transfer lands on THIS process: the
    configured ``jax_default_device``, else the first process-local
    device. Descriptor/token caches key on it so a mid-process
    ``jax.config.update('jax_default_device', ...)`` is honored."""
    dd = getattr(jax.config, "jax_default_device", None)
    return dd if dd is not None else jax.local_devices()[0]


def device_count() -> int:
    return len(jax.devices())


def make_mesh(
    axes: Optional[Dict[str, int]] = None,
    devices: Optional[Sequence] = None,
) -> Mesh:
    """Build a mesh. Default: a 1-D data-parallel mesh over every device.

    ``axes`` maps axis name → size; one entry may be -1 meaning "all
    remaining devices". Example: ``make_mesh({"dp": -1})`` or
    ``make_mesh({"dp": 2, "tp": 4})``.
    """
    devices = list(devices) if devices is not None else jax.devices()
    n = len(devices)
    if not axes:
        axes = {BATCH_AXIS: n}
    names = list(axes.keys())
    sizes = list(axes.values())
    if sizes.count(-1) > 1:
        raise ValueError("At most one mesh axis may be -1")
    known = math.prod(s for s in sizes if s != -1)
    if -1 in sizes:
        if n % known != 0:
            raise ValueError(
                f"Cannot infer -1 axis: {n} devices not divisible by {known}"
            )
        sizes[sizes.index(-1)] = n // known
    if math.prod(sizes) != n:
        raise ValueError(
            f"Mesh axes {dict(zip(names, sizes))} need "
            f"{math.prod(sizes)} devices but {n} are available"
        )
    # Auto axis types: XLA's SPMD partitioner solves intermediate shardings
    # (explicit sharding-in-types would demand out_sharding annotations on
    # ambiguous ops like embedding gathers).
    return jax.make_mesh(
        tuple(sizes), tuple(names),
        (jax.sharding.AxisType.Auto,) * len(names), devices=devices,
    )


def batch_sharding(mesh: Mesh, rank: int, axis: Optional[str] = None) -> NamedSharding:
    """NamedSharding that splits the leading (row) dim over the batch axis
    and replicates the rest — the frame layout (≙ Spark row partitioning)."""
    axis = axis or get_config().batch_axis
    return NamedSharding(mesh, P(axis, *([None] * (rank - 1))))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())

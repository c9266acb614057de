"""Pallas ragged row gather — device-side staging for ragged
``map_rows`` (the ~12M rows/s straggler vs 1B+ for fixed-shape add3).

The ragged fallback groups rows by cell shape, then per group
``np.stack``-s the cells on the HOST and ships the padded batch to the
device — for B shape groups that is B host stack passes and B
transfers, and the host stack dominated every measured round. With
this kernel the cells move ONCE, as a flat concatenation: the kernel's
grid walks the rows of one shape group, each row's slice streaming
from the flat buffer in HBM straight into its row of the padded VMEM
batch via a scalar-prefetched start offset (async DMA — no gathered
copy ever materializes on the host). The group's vmapped program then
runs on the device-resident batch.

Pure data movement: the gather is **bit-identical to the host
``np.stack`` staging by construction** (asserted in tests), so the
ragged ``map_rows`` results cannot change — only where the bytes flow.
Selected by ``plan/rules.decide_ragged_gather`` (counted
``pallas_ragged_gather``); the single-1-D-ragged-column fast path is
the eligible shape, mirroring the vectorized grouping fast path it
accelerates.
"""

from __future__ import annotations

from functools import lru_cache
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import build_timer, note_dispatch


@lru_cache(maxsize=64)
def _gather_fn_for(length: int, dtype_name: str, interpret: bool):
    """Jitted gather for one cell length: ``fn(flat [T], starts [g])
    -> [g, length]`` (re-traced per distinct g by jit, executable
    cached)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(starts_ref, flat_ref, o_ref, sem):
        r = pl.program_id(0)
        cp = pltpu.make_async_copy(
            flat_ref.at[pl.ds(starts_ref[r], length)],
            o_ref.at[0],
            sem,
        )
        cp.start()
        cp.wait()

    @jax.jit
    def run(flat, starts):
        g = starts.shape[0]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(g,),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),  # flat stays HBM
            ],
            out_specs=pl.BlockSpec(
                (1, length), lambda r, starts: (r, r - r)
            ),
            scratch_shapes=[pltpu.SemaphoreType.DMA],
        )
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(
                (g, length), flat.dtype
            ),
            interpret=interpret,
        )(starts.astype(jnp.int32), flat)

    return run


def ragged_gather_rows(
    flat: jnp.ndarray,       # [T] the flat cell concatenation (device)
    starts,                  # [g] int32 start offsets into ``flat``
    length: int,             # the group's (uniform) cell length
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Gather ``g`` rows of ``length`` cells from ``flat`` into a dense
    device batch ``[g, length]``. ``starts`` may be numpy or device;
    rows may overlap (padding rows reuse offset 0)."""
    from . import interpret_mode

    if interpret is None:
        interpret = interpret_mode()
    if length < 1:
        raise ValueError(
            f"ragged_gather_rows needs length >= 1, got {length} "
            "(zero-length cells stay on the host stack path)"
        )
    with build_timer():
        fn = _gather_fn_for(
            int(length), str(flat.dtype), bool(interpret)
        )
    note_dispatch("ragged_gather", bool(interpret))
    return fn(flat, jnp.asarray(np.asarray(starts, dtype=np.int32)))


def gather_reference(flat, starts, length: int) -> np.ndarray:
    """Host emulation of the gather (the ``np.stack`` staging the
    kernel replaces) — the bit-identity oracle."""
    flat = np.asarray(flat)
    return np.stack([
        flat[int(s):int(s) + length] for s in np.asarray(starts)
    ]) if len(np.asarray(starts)) else np.empty(
        (0, length), flat.dtype
    )

"""Grouped matmul of the served expert layer: rows sorted by expert
against each held expert's matrix, as one Pallas kernel.

``lax.ragged_dot`` is the plain form (``models/moe.routed_experts``
uses it where no kernel is selectable). On the v5e XLA's lowering of it
ran a decode step's ``[1024, 2304] x [64, 2304, 896]`` at 2.7 ms a call
where reading the 264 MB of matrices takes 0.32 ms; the kernel here,
``jax.experimental.pallas.ops.tpu.megablox.gmm`` with tiles sized for
the expert widths, took 1.26 ms on the same operands (PERF.md section 5).
It walks the row tiles in order, each ``[128, tk] x [tk, tn]`` tile pair
folded on the MXU into a float32 accumulator, and a row's result depends
on its own row and its expert's matrix alone (the k tiles fold in a
fixed order), so a batched call equals a solo call row for row.

Rows past the groups' total (the pairs of experts this holder lacks,
and the padding up to a whole row tile) are not computed: their output
is whatever the buffer held, and the caller masks them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["grouped_matmul", "tiling"]

#: rows a grid step folds (the kernel's m tile; ``m`` is padded to it)
ROW_TILE = 128
#: widest k and n tile: a bfloat16 ``[tk, tn]`` tile of an expert's
#: matrix is then at most 1.6 MB, double-buffered well inside VMEM
MAX_TILE = 896


def _tile(dim: int) -> int:
    """The widest multiple of 128 that divides ``dim`` and is at most
    ``MAX_TILE``; ``dim`` itself where it is small or has none."""
    if dim <= MAX_TILE:
        return dim
    for t in range(MAX_TILE, 0, -128):
        if dim % t == 0:
            return t
    return dim


def tiling(k: int, n: int):
    """``(tm, tk, tn)`` for ``[m, k] x [groups, k, n]``."""
    return ROW_TILE, _tile(int(k)), _tile(int(n))


def grouped_matmul(rows: jnp.ndarray, w: jnp.ndarray, sizes: jnp.ndarray,
                   interpret: bool = False) -> jnp.ndarray:
    """``rows`` [m, k] sorted by group, ``w`` [groups, k, n], ``sizes``
    [groups] int32 (``sum(sizes) <= m``) → float32 [m, n]: row ``i`` of
    group ``g`` times ``w[g]``. Rows past ``sum(sizes)`` are left
    uninitialised."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    m, k = rows.shape
    pad = -m % ROW_TILE
    if pad:
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
    # the package runs under x64; the kernel's scalar-prefetched group
    # metadata must stay int32
    with jax.enable_x64(False):
        out = gmm(rows, w, sizes.astype(jnp.int32),
                  preferred_element_type=jnp.float32,
                  tiling=tiling(k, w.shape[2]), interpret=bool(interpret))
    return out[:m] if pad else out

"""Segment reduction kernels for keyed ``aggregate``.

The aggregate fast path (verbs.py) lowers algebraic fetches to segment
reductions over key-sorted rows. On TPU, XLA implements
``jax.ops.segment_sum`` as a scatter-add — a serialized, VPU-bound op.
This module adds a **custom pallas kernel** that reformulates the sorted
segment-sum as a one-hot contraction: for each row tile, build the
``[tile, segments]`` membership one-hot and contract it against the value
tile on the **MXU** (a dense matmul), accumulating into the output block
across the grid. Dense MXU work replaces the scatter — the standard TPU
trick for small-to-moderate segment counts.

``segment_sum`` dispatches: pallas on TPU for f32/bf16 2-D values with a
bounded segment count, XLA's segment_sum otherwise. The pallas path is
also exercised on CPU in interpreter mode by the tests.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax import lax

from ..utils import is_tpu_backend

# rows per grid step (sublane-aligned); lanes carry the feature dim
_TILE_ROWS = 256
# above this many segments the one-hot matmul wastes more FLOPs than the
# scatter costs; fall back to XLA
_MAX_PALLAS_SEGMENTS = 4096


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _seg_kernel(seg_ref, val_ref, out_ref):
    """One grid step: out[s, d] += Σ_{rows r in tile with seg(r)=s} val[r, d].

    seg_ref: [tile, 1] int32 (padded rows carry num_segments → no match);
    val_ref: [tile, d]; out_ref: [segments_padded, d] (same block every
    step — accumulates across the sequential TPU grid).
    """
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    seg = seg_ref[:, 0]  # [tile]
    tile = seg.shape[0]
    s_pad = out_ref.shape[0]
    # [tile, segments] membership one-hot; 2-D iota (TPU requires ≥2D)
    seg_iota = lax.broadcasted_iota(jnp.int32, (tile, s_pad), 1)
    onehot = (seg[:, None] == seg_iota).astype(jnp.float32)
    vals = val_ref[:].astype(jnp.float32)
    # [segments, tile] @ [tile, d] on the MXU. precision=HIGHEST: the TPU
    # MXU's default single-pass f32 matmul truncates inputs to bf16 —
    # measured on v5e (round 3 smoke), that costs ~2e-1 relative error on
    # cancelling sums vs the exact scatter. The one-hot operand is exact
    # either way; HIGHEST makes the value operand f32-faithful.
    out_ref[:] += lax.dot_general(
        onehot,
        vals,
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=lax.Precision.HIGHEST,
    )


def segment_sum_pallas(
    values: jnp.ndarray,
    seg_ids: jnp.ndarray,
    num_segments: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """Sorted-or-not segment sum via the one-hot MXU kernel.

    values [n, d] (f32/bf16), seg_ids [n] int32 in [0, num_segments).
    Returns [num_segments, d] float32.
    """
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, d = values.shape
    n_pad = _round_up(max(n, 1), _TILE_ROWS)
    d_pad = _round_up(max(d, 1), 128)
    s_pad = _round_up(num_segments, 8)

    vals = jnp.zeros((n_pad, d_pad), values.dtype).at[:n, :d].set(values)
    # padded rows point at segment id == num_segments → match nothing
    segs = jnp.full((n_pad, 1), num_segments, jnp.int32).at[:n, 0].set(
        seg_ids.astype(jnp.int32)
    )

    grid = (n_pad // _TILE_ROWS,)
    # index maps derive EVERY component from the grid index: this package
    # enables jax x64 at import, under which a literal ``0`` traces as an
    # i64 constant next to the i32 grid index — Mosaic then fails to
    # legalize the index map's mixed-type func.return
    # ("(i32, i64) -> ()", observed on v5e). ``i - i`` is an i32 zero.
    out = pl.pallas_call(
        _seg_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (_TILE_ROWS, 1), lambda i: (i, i - i), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (_TILE_ROWS, d_pad), lambda i: (i, i - i), memory_space=pltpu.VMEM
            ),
        ],
        out_specs=pl.BlockSpec(
            (s_pad, d_pad), lambda i: (i - i, i - i), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((s_pad, d_pad), jnp.float32),
        interpret=interpret,
    )(segs, vals)
    return out[:num_segments, :d]


# Manual process-wide switch for every pallas family (this kernel and
# tensorframes_tpu/kernels). Nothing throws it automatically: a kernel
# Mosaic refuses raises at its call site. It is a compile-cache
# fingerprint axis (kernels.fingerprint_token), so executables built on
# either side of a flip never mix.
_pallas_disabled = False


def disable_pallas(reason: str = "") -> None:
    """Take every pallas kernel out of selection for the rest of the
    process (the in-process form of ``TFTPU_PALLAS=0``)."""
    global _pallas_disabled
    if not _pallas_disabled:
        import logging

        logging.getLogger(__name__).warning(
            "pallas kernels disabled for this process (XLA/host "
            "lowerings only)%s", f": {reason}" if reason else ""
        )
        # fused plan epilogues traced with pallas enabled are stale the
        # moment the switch flips — drop them so the next force
        # re-traces onto the XLA scatter
        from ..plan.lower import clear_fused_cache

        clear_fused_cache()
    _pallas_disabled = True


def pallas_enabled() -> bool:
    return not _pallas_disabled


def _pallas_eligible(values: jnp.ndarray, num_segments: int) -> bool:
    return (
        not _pallas_disabled
        and values.ndim == 2
        and values.dtype in (jnp.float32, jnp.bfloat16)
        and 0 < num_segments <= _MAX_PALLAS_SEGMENTS
        and is_tpu_backend()
    )


def host_segment_eligible(ops_key, val_cols) -> bool:
    """True when the keyed reduction should run as HOST ``np.bincount``
    instead of the jitted segment program: CPU backend only (XLA:CPU
    lowers ``segment_sum`` to a serialized scatter — measured ~45ms per
    1M-row f32 column vs ~4ms for bincount's weighted histogram), and
    only for 1-D float sum/mean (int sums must not ride bincount's
    float64 weights — >2^53 would silently lose bits; min/max have no
    bincount form). Works on numpy AND jax-array values so the fused
    plan epilogue and the eager path take the SAME branch — that
    sameness is what keeps fused and unfused outputs bit-identical."""
    if is_tpu_backend():
        return False
    for x, op in ops_key:
        v = val_cols[x]
        if op not in ("reduce_sum", "reduce_mean"):
            return False
        if getattr(v, "ndim", None) != 1:
            return False
        if not jnp.issubdtype(v.dtype, jnp.floating):
            return False
    return True


def segment_reduce_host(ops_key, num_segments, val_cols, seg_ids):
    """CPU segment sums/means via ``np.bincount``: one fused weighted-
    histogram pass per column, accumulating in float64 (a strictly
    tighter error bound than the f32 sequential scatter) and cast back
    to the value dtype — the fetch-dtype contract the jitted path
    keeps. Both the plan's fused epilogue and the ``TFTPU_FUSION=0``
    path dispatch through THIS function on CPU, so the bit-identical
    contract holds by construction."""
    import numpy as np

    seg_ids = np.asarray(seg_ids)
    if seg_ids.size == 0:
        # zero-row feed (ISSUE 12 bugfix sweep): ``np.asarray([])`` is
        # float64 and ``np.bincount`` rejects float ids with a
        # TypeError. Every segment is empty, so the answer is closed-
        # form: zeros for sums, 0/0 → NaN for means — exactly the bits
        # the jitted segment program produces for empty segments.
        out = {}
        for x, op in ops_key:
            v = np.asarray(val_cols[x])
            s = np.zeros(num_segments, np.float64)
            if op == "reduce_mean":
                with np.errstate(invalid="ignore", divide="ignore"):
                    s = s / np.zeros(num_segments, np.float64)
            out[x] = s.astype(v.dtype)
        return out
    seg_ids = seg_ids.astype(np.intp, copy=False)
    out = {}
    counts = None
    for x, op in ops_key:
        v = np.asarray(val_cols[x])  # syncs a device value in one copy
        s = np.bincount(seg_ids, weights=v, minlength=num_segments)
        if op == "reduce_mean":
            if counts is None:
                counts = np.bincount(seg_ids, minlength=num_segments)
            # segment-count bucketing pads num_segments past the real
            # group count; the padded slots divide 0/0 and are sliced
            # away by the caller — suppress numpy's warning so a
            # warnings-as-errors consumer sees no fused-only noise
            with np.errstate(invalid="ignore", divide="ignore"):
                s = s / counts
        out[x] = s.astype(v.dtype)
    return out


def segment_sum(
    values: jnp.ndarray,
    seg_ids: jnp.ndarray,
    num_segments: int,
) -> jnp.ndarray:
    """Segment sum with automatic kernel dispatch: the pallas one-hot MXU
    kernel on TPU (1-D/2-D f32/bf16 values, bounded segment count), XLA's
    scatter-based ``jax.ops.segment_sum`` otherwise. Result dtype matches
    ``values``."""
    v2 = values[:, None] if values.ndim == 1 else values
    if _pallas_eligible(v2, num_segments):
        out = segment_sum_pallas(v2, seg_ids, num_segments)
        if values.ndim == 1:
            out = out[:, 0]
        return out.astype(values.dtype)
    return jax.ops.segment_sum(values, seg_ids, num_segments=num_segments)

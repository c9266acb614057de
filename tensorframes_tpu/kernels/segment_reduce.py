"""Fused multi-op pallas segment reduce — the third keyed-reduction
strategy beside the jitted scatter and the host ``np.bincount``
(``ops/segment.py``), selected per segment by
``plan/rules.decide_segment_reduce``.

One pallas dispatch computes EVERY (column, op) fetch of a keyed
``aggregate``. Rows ride the **lanes**: the id column and every value
column enter as ``[rows / 128, 128]`` (a 1-D column costs its own
bytes, not a 128x lane pad), the grid walks row tiles sequentially,
and each (column, op) keeps a ``[segments, 128]`` accumulator resident
in VMEM — lane ``l`` of segment ``s`` holds the partial over rows
``r ≡ l (mod 128)`` whose id is ``s``. One grid step, per 128 rows:
compare the id row against a segment iota down the sublanes
(membership), then select-and-accumulate each value row:

* ``sum``/``mean``: ``acc += where(member, value, 0)`` — float32
  accumulate for floats, **int32** for ints/bools (exact associative
  arithmetic, bit-identical to the scatter by construction);
* ``min``/``max``: ``acc = min(acc, where(member, value, identity))``
  (order-free, so also exactly the scatter's bits).

Only 32-bit compare/select/add/min on the VPU — no MXU contraction
(Mosaic has no int32 matmul, and the earlier ``[tile, segments, d]``
masked broadcast needed lane↔sublane relayouts it refused; PR 21) and
no dynamic indexing. Narrower dtypes widen to the accumulator dtype
OUTSIDE the kernel and narrow back after. A 2-D column ``[n, d]``
(``d <= MAX_INNER``) is ``d`` row streams.

The lane reduction, mean division and final casts happen outside the
kernel with the jitted path's formula (``(s / c).astype(v.dtype)``;
the count table is i32-exact). Bit-identity is gated two ways: against
:func:`segment_reduce_reference` — the same tiled computation in plain
jnp, exact by construction for every op/dtype — and against the XLA
scatter for the order-free classes (min/max, integer sums).

Sorted-or-not segment ids; padded rows carry id ``-1`` and match no
segment. Runs on the pallas CPU interpreter when the backend is CPU
(:func:`tensorframes_tpu.kernels.interpret_mode`).
"""

from __future__ import annotations

from functools import lru_cache
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import build_timer, note_dispatch

_LANES = 128
#: sublane rows (of 128 lanes each) per grid step
_TILE_R = 8
#: rows consumed per grid step; row counts pad up to a multiple of it
_ROW_QUANTUM = _TILE_R * _LANES
#: past this, the resident accumulators outgrow VMEM
MAX_SEGMENTS = 4096
#: widest 2-D column served (each inner column is its own row stream)
MAX_INNER = 8
#: accumulators the kernel may keep resident (Pallas double-buffers the
#: output blocks; a v5e core has 128 MiB of VMEM)
_VMEM_BUDGET = 64 << 20

_FLOAT_OK = ("float32", "bfloat16")
_INT_OK = ("int32", "int16", "int8", "uint8", "bool")
_OPS = ("reduce_sum", "reduce_mean", "reduce_min", "reduce_max")


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _dtype_name(v) -> str:
    return str(v.dtype)


def _np_to_jnp_dtype(name: str):
    return jnp.bfloat16 if name == "bfloat16" else np.dtype(name)


def _col_meta(ops_key, val_cols) -> Tuple[Tuple[str, str, int, int, str], ...]:
    """Per-column (name, dtype, inner dim, ndim, op) — the build-cache
    key axis that varies with the feed."""
    meta = []
    for x, op in ops_key:
        v = val_cols[x]
        ndim = int(getattr(v, "ndim", 1))
        d = 1 if ndim == 1 else int(v.shape[1])
        meta.append((x, _dtype_name(v), d, ndim, op))
    return tuple(meta)


def _acc_bytes(meta, num_segments: int) -> int:
    """VMEM the resident accumulators take (double-buffered)."""
    s_pad = _round_up(max(num_segments, 1), 8)
    n_acc = sum(d for _, _, d, _, _ in meta)
    if any(op == "reduce_mean" for *_, op in meta):
        n_acc += 1
    return 2 * n_acc * s_pad * _LANES * 4


def eligible(ops_key, val_cols, num_segments: int) -> bool:
    """True when the fused pallas kernel can serve this keyed
    reduction exactly: bounded segment count, 1-D or narrow 2-D
    values, float32/bfloat16 (f32 accumulate) or ≤32-bit int/bool (i32
    accumulate — wider ints could overflow the exact accumulator), and
    accumulators that fit the VMEM budget."""
    if not 0 < num_segments <= MAX_SEGMENTS:
        return False
    for x, op in ops_key:
        if op not in _OPS:
            return False
        v = val_cols[x]
        ndim = getattr(v, "ndim", None)
        if ndim not in (1, 2):
            return False
        if ndim == 2 and not 0 < int(v.shape[1]) <= MAX_INNER:
            return False
        if _dtype_name(v) not in _FLOAT_OK + _INT_OK:
            return False
    return _acc_bytes(
        _col_meta(ops_key, val_cols), num_segments
    ) <= _VMEM_BUDGET


def _acc_dtype(dtype_name: str):
    """Accumulator dtype of a column: f32 for floats, i32 otherwise."""
    return jnp.float32 if dtype_name in _FLOAT_OK else jnp.int32


def _minmax_identity(dtype_name: str, op: str):
    """The reduction identity of the column's OWN dtype, held in the
    accumulator dtype — an empty segment then narrows back to exactly
    the scatter's answer (``iinfo(int8).max``, not int32's). A numpy
    scalar, so the kernel body inlines it as a literal (a jax array
    would be a captured constant, which pallas_call refuses)."""
    if dtype_name in _FLOAT_OK:
        return np.float32(np.inf if op == "reduce_min" else -np.inf)
    if dtype_name == "bool":
        return np.int32(op == "reduce_min")
    info = np.iinfo(np.dtype(dtype_name))
    return np.int32(info.max if op == "reduce_min" else info.min)


def _member(seg_row: jnp.ndarray, s_pad: int) -> jnp.ndarray:
    """[s_pad, 128] membership of one id row ([1, 128] int32): lane
    ``l`` of segment ``s`` is true when row ``l`` belongs to ``s``."""
    seg_iota = lax.broadcasted_iota(jnp.int32, (s_pad, _LANES), 0)
    return seg_iota == seg_row


def _fold_row(op: str, dtype_name: str, acc: jnp.ndarray,
              member: jnp.ndarray, row: jnp.ndarray) -> jnp.ndarray:
    """Fold one value row ([1, 128], accumulator dtype) into a
    [s_pad, 128] accumulator — THE shared math of the kernel body and
    the plain-jnp reference emulation (bit-identity between them is by
    construction: same ops, same order, same dtypes)."""
    if op in ("reduce_sum", "reduce_mean"):
        return acc + jnp.where(member, row, row.dtype.type(0))
    comb = jnp.minimum if op == "reduce_min" else jnp.maximum
    return comb(acc, jnp.where(member, row, _minmax_identity(dtype_name, op)))


def _init_acc(op: str, dtype_name: str, shape) -> jnp.ndarray:
    if op in ("reduce_min", "reduce_max"):
        return jnp.full(shape, _minmax_identity(dtype_name, op))
    return jnp.zeros(shape, _acc_dtype(dtype_name))


def _pad_inputs(meta, val_cols, seg_ids):
    """Lay the feed out rows-on-lanes: ids ``[R, 128]`` int32 (padding
    rows → ``-1``), each column ``[d, R, 128]`` in its accumulator
    dtype, ``R`` a multiple of the row tile."""
    seg_ids = jnp.asarray(np.asarray(seg_ids)).astype(jnp.int32)
    n = int(seg_ids.shape[0])
    n_pad = _round_up(max(n, 1), _ROW_QUANTUM)
    segs = jnp.pad(seg_ids, (0, n_pad - n), constant_values=-1)
    segs = segs.reshape(n_pad // _LANES, _LANES)
    padded = {}
    for x, dtype_name, d, ndim, _ in meta:
        v = jnp.asarray(val_cols[x]).astype(_acc_dtype(dtype_name))
        v2 = v[:, None] if ndim == 1 else v
        v2 = jnp.pad(v2, ((0, n_pad - n), (0, 0)))
        padded[x] = v2.T.reshape(d, n_pad // _LANES, _LANES)
    return segs, padded


def _finalize(meta, num_segments, partials, counts):
    """Reduce the lanes, slice away padding and apply the jitted
    path's mean/cast formula: ``s.astype(v.dtype)`` for sums,
    ``(s / c).astype(v.dtype)`` for means. Returns 2-D [K, d] columns
    (callers restore 1-D)."""
    out = {}
    for x, dtype_name, d, _, op in meta:
        dt = _np_to_jnp_dtype(dtype_name)
        p = partials[x][:, :num_segments]           # [d, K, 128]
        if op == "reduce_min":
            out[x] = p.min(axis=-1).T.astype(dt)
        elif op == "reduce_max":
            out[x] = p.max(axis=-1).T.astype(dt)
        elif op == "reduce_sum":
            out[x] = p.sum(axis=-1).T.astype(dt)
        else:  # reduce_mean
            s = p.sum(axis=-1).T.astype(dt)
            c = counts[:num_segments].sum(axis=-1)[:, None].astype(dt)
            out[x] = (s / c).astype(dt)
    return out


def _unpad(meta, res) -> Dict[str, np.ndarray]:
    out = {}
    for x, _, _, ndim, _ in meta:
        v = np.asarray(res[x])
        out[x] = v[:, 0] if ndim == 1 else v
    return out


@lru_cache(maxsize=32)
def _pallas_fn_for(meta, num_segments: int, interpret: bool):
    """Build (once per op-set/shape family) the jitted wrapper whose
    body is ONE pallas_call computing every partial + the shared count
    table. ``meta`` is the :func:`_col_meta` tuple."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s_pad = _round_up(num_segments, 8)
    need_counts = any(op == "reduce_mean" for *_, op in meta)
    n_cols = len(meta)

    def kernel(seg_ref, *refs):
        val_refs = refs[:n_cols]
        out_refs = refs[n_cols:2 * n_cols]
        cnt_ref = refs[2 * n_cols] if need_counts else None

        @pl.when(pl.program_id(0) == 0)
        def _init():
            for (_, dtype_name, _, _, op), o_ref in zip(meta, out_refs):
                o_ref[...] = _init_acc(op, dtype_name, o_ref.shape)
            if cnt_ref is not None:
                cnt_ref[...] = jnp.zeros(cnt_ref.shape, jnp.int32)

        for t in range(_TILE_R):
            member = _member(seg_ref[t:t + 1, :], s_pad)
            for (_, dtype_name, d, _, op), v_ref, o_ref in zip(
                meta, val_refs, out_refs
            ):
                for c in range(d):
                    o_ref[c] = _fold_row(
                        op, dtype_name, o_ref[c], member,
                        v_ref[c, t:t + 1, :],
                    )
            if cnt_ref is not None:
                cnt_ref[...] += member.astype(jnp.int32)

    @jax.jit
    def run(segs, vals):
        grid = (segs.shape[0] // _TILE_R,)
        # every index-map component derives from the grid index: this
        # package enables x64 at import, under which a literal 0
        # traces i64 beside the i32 grid index and Mosaic fails to
        # legalize the mixed-type func.return (the ops/segment.py
        # lesson); ``i - i`` is an i32 zero
        in_specs = [pl.BlockSpec((_TILE_R, _LANES), lambda i: (i, i - i),
                                 memory_space=pltpu.VMEM)]
        out_shapes = []
        out_specs = []
        ins = [segs]
        for x, dtype_name, d, ndim, op in meta:
            in_specs.append(pl.BlockSpec(
                (d, _TILE_R, _LANES), lambda i: (i - i, i, i - i),
                memory_space=pltpu.VMEM,
            ))
            ins.append(vals[x])
            out_shapes.append(jax.ShapeDtypeStruct(
                (d, s_pad, _LANES), _acc_dtype(dtype_name)
            ))
            out_specs.append(pl.BlockSpec(
                (d, s_pad, _LANES), lambda i: (i - i, i - i, i - i),
                memory_space=pltpu.VMEM,
            ))
        if need_counts:
            out_shapes.append(
                jax.ShapeDtypeStruct((s_pad, _LANES), jnp.int32)
            )
            out_specs.append(pl.BlockSpec(
                (s_pad, _LANES), lambda i: (i - i, i - i),
                memory_space=pltpu.VMEM,
            ))
        outs = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shapes,
            # the accumulators stay resident across the whole grid;
            # state the scoped-VMEM need instead of inheriting the
            # 16 MiB default that a 4096-segment table would exceed
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=_acc_bytes(meta, num_segments)
                + (16 << 20),
            ),
            interpret=interpret,
            name="segment_reduce",
        )(*ins)
        partials = {meta[k][0]: outs[k] for k in range(n_cols)}
        counts = outs[n_cols] if need_counts else None
        return _finalize(meta, num_segments, partials, counts)

    return run


def segment_reduce_pallas(
    ops_key, num_segments: int, val_cols, seg_ids,
    interpret: Optional[bool] = None,
) -> Dict[str, np.ndarray]:
    """Run the fused kernel: ``ops_key`` is the ((name, op), ...) tuple
    of ``_segment_reduce_best``, ``val_cols`` maps names to 1-D/2-D
    numpy or jax arrays, ``seg_ids`` the int row→segment map. Returns
    numpy columns sliced to ``num_segments``, dtypes matching the
    jitted path's contract. Caller gates :func:`eligible` first."""
    from . import interpret_mode

    if interpret is None:
        interpret = interpret_mode()
    meta = _col_meta(ops_key, val_cols)
    with build_timer():
        fn = _pallas_fn_for(meta, num_segments, bool(interpret))
    segs, padded = _pad_inputs(meta, val_cols, seg_ids)
    note_dispatch("segment_reduce", bool(interpret))
    return _unpad(meta, fn(segs, padded))


def segment_reduce_reference(
    ops_key, num_segments: int, val_cols, seg_ids,
) -> Dict[str, np.ndarray]:
    """Plain-jnp emulation of the kernel's exact tiled computation —
    the bit-identity oracle (same per-row fold via :func:`_fold_row`,
    same sequential row order, same finalize formula; no pallas
    anywhere). Tests assert ``segment_reduce_pallas ==
    segment_reduce_reference`` bitwise."""
    meta = _col_meta(ops_key, val_cols)
    s_pad = _round_up(num_segments, 8)
    segs, padded = _pad_inputs(meta, val_cols, seg_ids)
    need_counts = any(op == "reduce_mean" for *_, op in meta)
    partials = {
        x: [_init_acc(op, dtype_name, (s_pad, _LANES)) for _ in range(d)]
        for x, dtype_name, d, _, op in meta
    }
    counts = jnp.zeros((s_pad, _LANES), jnp.int32)
    for r in range(int(segs.shape[0])):
        member = _member(segs[r:r + 1, :], s_pad)
        for x, dtype_name, d, _, op in meta:
            for c in range(d):
                partials[x][c] = _fold_row(
                    op, dtype_name, partials[x][c], member,
                    padded[x][c, r:r + 1, :],
                )
        if need_counts:
            counts = counts + member.astype(jnp.int32)
    return _unpad(meta, _finalize(
        meta, num_segments,
        {x: jnp.stack(p) for x, p in partials.items()},
        counts if need_counts else None,
    ))

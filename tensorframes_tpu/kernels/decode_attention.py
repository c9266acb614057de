"""Fused paged int8-KV decode attention (the kernel half of the
serving decode engine, ROADMAP #6 / ISSUE 12).

The XLA lowering of ``models/generation.paged_decode_step_fn`` runs
decode attention as a chain: gather every slot's pages into a
materialized ``[S, pages, page, heads*hd]`` HBM copy, dequantize, and
attend. Decode is HBM-bandwidth-bound, so that copy IS the cost. This
kernel fuses the chain: the grid walks ``(slot, page-table entry)``,
each page streams HBM→VMEM **as int8** through a scalar-prefetched
page-table index map (the vLLM paged-attention shape), scales ride
along, and each page folds into a per-slot **online softmax** (running
max, denominator and context accumulator in VMEM scratch). Nothing
gathered ever touches HBM and nothing wider than one page is ever held
dequantized.

One physical layout (PR 26). The kernel's operands ARE the resident
pool columns of ``models/generation.init_paged_kv``: k/v
``[P, L, page, heads*hd]`` int8 and scales ``[P, L, page, SCALE_LANES]``
float32, read through ``(1, 1, page, ·)`` blocks. Their row-major
default layout is the one the step's KV scatter writes and the one
Mosaic reads, so no program converts a pool column: the earlier
``[P, L, heads, page, hd]`` shape cost a whole-pool layout copy per
column per layer (the ``(page, hd)`` and ``(page, 1)`` minor dims pad
to the 128-lane tile, and XLA keeps such an array in another layout
than Mosaic demands).

Mosaic shape discipline: every array in the body is 2-D
``[rows, lanes]`` with the page position on sublanes. Heads lie side
by side on the lanes of a k/v row, and per-head quantities (scores,
weights, running max and denominator) are *compact* ``[rows,
SCALE_LANES]`` arrays with head ``h`` in lane ``h`` — the layout the
pool's scale rows have, so scores and scales multiply with no
relayout. :func:`_head_sums` folds a ``[rows, heads*hd]`` array to
compact form (masked lane reductions over 128-lane blocks) and
:func:`_head_spread` spreads a compact array back over each head's
``hd`` lanes; both use only aligned 128-lane slices, lane iotas and
selects (no lane-offset slice, no reshape, no dynamic-offset store).
``q`` enters as ``[S, 1, heads*hd]`` float32 and the context leaves the
same way; the activation-dtype casts happen outside the kernel.

Equality gates: the kernel is bit-identical on the CPU pallas
interpreter to :func:`paged_attention_emulation` — the same per-page
update (:func:`_page_update`) folded in the same order in plain jnp —
and agrees with the whole-horizon XLA chain
(:func:`paged_attention_reference`, the production non-kernel
lowering) to float tolerance: an online softmax reassociates the
denominator, so the two are not bitwise equal. The engine-level gates
(batched==solo, preemption replay) hold whichever lowering the cost
model picks because the choice is made once per engine, not per step.

Null-page handling is inherited unchanged: padding slots carry
all-null tables (every gathered page is page 0) and real slots mask to
``position <= pos``, so the null page's garbage never reaches an
unmasked score — the same invariant the XLA chain relies on.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_NEG = -1e30

# Lanes of a pool scale row (and of every compact per-head array): one
# TPU vector register's width, so a page's ``[page, SCALE_LANES]`` scale
# block is whole (8, 128) float32 tiles. Lane ``h < heads`` holds head
# ``h``; the lanes beyond are padding that no output reads.
SCALE_LANES = 128


def _head_sums(x, nh: int, hd: int):
    """``[rows, nh*hd]`` → compact ``[rows, SCALE_LANES]``: lane ``h``
    holds the sum over head ``h``'s ``hd`` lanes (lanes >= nh hold 0).
    One masked lane reduction per head and 128-lane block it touches."""
    width = x.shape[-1]
    lane = lax.broadcasted_iota(jnp.int32, (1, SCALE_LANES), 1)
    out = jnp.zeros((x.shape[0], SCALE_LANES), jnp.float32)
    for h in range(nh):
        lo, hi = h * hd, (h + 1) * hd
        total = None
        for b in range(lo // SCALE_LANES, (hi - 1) // SCALE_LANES + 1):
            start = b * SCALE_LANES
            blk = x[:, start:min(start + SCALE_LANES, width)]
            w = blk.shape[1]
            if start < lo or start + w > hi:
                at = start + lax.broadcasted_iota(jnp.int32, (1, w), 1)
                blk = jnp.where((at >= lo) & (at < hi), blk,
                                np.float32(0))
            part = jnp.sum(blk, axis=-1, keepdims=True)
            total = part if total is None else total + part
        out = jnp.where(lane == h, total, out)
    return out


def _head_spread(c, nh: int, hd: int):
    """Compact ``[rows, SCALE_LANES]`` → ``[rows, nh*hd]``: head ``h``'s
    value (lane ``h``) over its ``hd`` lanes — the inverse placement of
    :func:`_head_sums`. Exact: a lane is picked, never summed with
    another value."""
    width = nh * hd
    lane = lax.broadcasted_iota(jnp.int32, (1, SCALE_LANES), 1)
    cols = [
        jnp.sum(jnp.where(lane == h, c, np.float32(0)), axis=-1,
                keepdims=True)
        for h in range(nh)
    ]
    blocks = []
    for start in range(0, width, SCALE_LANES):
        w = min(SCALE_LANES, width - start)
        at = start + lax.broadcasted_iota(jnp.int32, (1, w), 1)
        first = start // hd
        blk = jnp.broadcast_to(cols[first], (c.shape[0], w))
        for h in range(first + 1, (start + w - 1) // hd + 1):
            blk = jnp.where(at >= h * hd, cols[h], blk)
        blocks.append(blk)
    return blocks[0] if len(blocks) == 1 else jnp.concatenate(
        blocks, axis=-1
    )


def _page_update(q, k8, v8, ks, vs, valid, m, l, acc, sm_scale: float,
                 nh: int, hd: int):
    """Fold one KV page into a slot's online softmax — THE shared math
    of the kernel body and the plain-jnp emulation (same ops, same
    order, same dtypes, so the two are bit-identical on CPU).

    ``q`` [1, nh*hd] f32; ``k8``/``v8`` [page, nh*hd] int8; ``ks``/
    ``vs`` [page, SCALE_LANES] f32; ``valid`` [page, 1] bool; running
    ``m``/``l`` compact [1, SCALE_LANES] and ``acc`` [1, nh*hd], all
    f32."""
    f32 = jnp.float32
    s = _head_sums(q * k8.astype(f32), nh, hd) * sm_scale
    s = jnp.where(valid, s * ks, np.float32(_NEG))
    m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=0, keepdims=True)
    pv = _head_spread(p * vs, nh, hd) * v8.astype(f32)
    acc_new = acc * _head_spread(corr, nh, hd) + jnp.sum(
        pv, axis=0, keepdims=True
    )
    return m_new, l_new, acc_new


def _check_pool(q, k_pages, k_scale):
    S, nh, hd = q.shape
    if k_pages.ndim != 4 or k_pages.shape[-1] != nh * hd:
        raise ValueError(
            f"k/v pages must be [pages, layers, page_size, heads*head_dim"
            f"={nh * hd}], got {tuple(k_pages.shape)}"
        )
    if k_scale.shape != k_pages.shape[:3] + (SCALE_LANES,) \
            or nh > SCALE_LANES:
        raise ValueError(
            f"scales must be {k_pages.shape[:3] + (SCALE_LANES,)} with "
            f"heads={nh} <= {SCALE_LANES}, got {tuple(k_scale.shape)}"
        )


def paged_decode_attention(
    q: jnp.ndarray,          # [S, nh, hd] activation dtype
    k_pages: jnp.ndarray,    # [P, L, page, nh*hd] int8
    v_pages: jnp.ndarray,    # [P, L, page, nh*hd] int8
    k_scale: jnp.ndarray,    # [P, L, page, SCALE_LANES] f32
    v_scale: jnp.ndarray,    # [P, L, page, SCALE_LANES] f32
    layer: int,              # static layer index
    tables: jnp.ndarray,     # [S, maxp] int32 page tables
    pos: jnp.ndarray,        # [S] int32 current positions
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """One layer's paged decode attention for every slot: returns the
    ``[S, nh, hd]`` context in ``q.dtype``. Traceable (callers embed it
    in the jitted decode step); ``interpret`` defaults to the backend's
    :func:`tensorframes_tpu.kernels.interpret_mode`."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from . import interpret_mode

    if interpret is None:
        interpret = interpret_mode()
    _check_pool(q, k_pages, k_scale)
    S, nh, hd = q.shape
    width = nh * hd
    page = int(k_pages.shape[2])
    maxp = int(tables.shape[1])
    li = int(layer)
    f32 = jnp.float32
    sm_scale = 1.0 / float(np.sqrt(hd))

    def kernel(tbl_ref, pos_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
               o_ref, m_ref, l_ref, acc_ref):
        s = pl.program_id(0)
        j = pl.program_id(1)

        @pl.when(j == 0)
        def _init():
            m_ref[...] = jnp.full(m_ref.shape, _NEG, f32)
            l_ref[...] = jnp.zeros(l_ref.shape, f32)
            acc_ref[...] = jnp.zeros(acc_ref.shape, f32)

        kpos = j * page + lax.broadcasted_iota(jnp.int32, (page, 1), 0)
        m_new, l_new, acc_new = _page_update(
            q_ref[0], k_ref[0, 0], v_ref[0, 0], ks_ref[0, 0],
            vs_ref[0, 0], kpos <= pos_ref[s],
            m_ref[...], l_ref[...], acc_ref[...], sm_scale, nh, hd,
        )
        m_ref[...] = m_new
        l_ref[...] = l_new
        acc_ref[...] = acc_new

        @pl.when(j == maxp - 1)
        def _finish():
            o_ref[0] = acc_new / _head_spread(l_new, nh, hd)

    # Every index-map component derives from a grid index (``j - j``
    # zeros): this package enables x64 at import, under which literal
    # ints trace i64 beside the i32 grid index and Mosaic fails to
    # legalize the mixed-type func.return (the ops/segment.py lesson).
    def page_map(s, j, tbl, p):
        return (tbl[s, j], (j - j) + li, j - j, j - j)

    def slot_map(s, j, tbl, p):
        return (s, j - j, j - j)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, maxp),
        in_specs=[
            pl.BlockSpec((1, 1, width), slot_map),
            pl.BlockSpec((1, 1, page, width), page_map),
            pl.BlockSpec((1, 1, page, width), page_map),
            pl.BlockSpec((1, 1, page, SCALE_LANES), page_map),
            pl.BlockSpec((1, 1, page, SCALE_LANES), page_map),
        ],
        out_specs=pl.BlockSpec((1, 1, width), slot_map),
        scratch_shapes=[
            pltpu.VMEM((1, SCALE_LANES), f32),
            pltpu.VMEM((1, SCALE_LANES), f32),
            pltpu.VMEM((1, width), f32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, 1, width), f32),
        interpret=bool(interpret),
        name="paged_decode_attention",
    )(
        tables.astype(jnp.int32), pos.astype(jnp.int32),
        q.astype(f32).reshape(S, 1, width), k_pages, v_pages, k_scale,
        v_scale,
    )
    return out.reshape(S, nh, hd).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("layer",))
def paged_attention_emulation(
    q, k_pages, v_pages, k_scale, v_scale, layer, tables, pos
):
    """Plain-jnp emulation of the kernel's exact computation — the
    bit-identity oracle: the same :func:`_page_update` folded over the
    page table in the same order, no pallas anywhere. Jitted and
    written as loops (slots mapped, pages a ``fori_loop``), because the
    interpreter runs the grid as a loop around ONE compiled body:
    unrolled or op-by-op, XLA:CPU fuses a page's update with its
    neighbours and rounds ``acc * corr + sum`` differently."""
    _check_pool(q, k_pages, k_scale)
    S, nh, hd = q.shape
    width = nh * hd
    page = int(k_pages.shape[2])
    maxp = int(tables.shape[1])
    li = int(layer)
    f32 = jnp.float32
    sm_scale = 1.0 / float(np.sqrt(hd))
    qf = q.astype(f32).reshape(S, 1, width)

    def slot(s):
        def fold(j, carry):
            pg = tables[s, j]
            kpos = j * page + lax.broadcasted_iota(
                jnp.int32, (page, 1), 0
            )
            return _page_update(
                qf[s], k_pages[pg, li], v_pages[pg, li],
                k_scale[pg, li], v_scale[pg, li], kpos <= pos[s],
                *carry, sm_scale, nh, hd,
            )

        _, l, acc = lax.fori_loop(0, maxp, fold, (
            jnp.full((1, SCALE_LANES), _NEG, f32),
            jnp.zeros((1, SCALE_LANES), f32),
            jnp.zeros((1, width), f32),
        ))
        return acc / _head_spread(l, nh, hd)

    out = lax.map(slot, jnp.arange(S))
    return out.reshape(S, nh, hd).astype(q.dtype)


def paged_attention_reference(
    q, k_pages, v_pages, k_scale, v_scale, layer, tables, pos
):
    """The XLA gather→dequant→attend chain — the production non-kernel
    lowering (``paged_decode_step_fn``'s other branch calls it) AND the
    float oracle the kernel is checked against to tolerance."""
    _check_pool(q, k_pages, k_scale)
    S, nh, hd = q.shape
    page = int(k_pages.shape[2])
    maxp = int(tables.shape[1])
    C = maxp * page
    dtype = q.dtype
    li = int(layer)
    neg = jnp.asarray(_NEG, jnp.float32)
    valid = jnp.arange(C)[None, :] <= pos[:, None]
    # each slot's pages as one context: [S, maxp, page, ·] → [S, nh, C, ·]
    pk = k_pages[tables, li].reshape(S, C, nh, hd).transpose(0, 2, 1, 3)
    pv = v_pages[tables, li].reshape(S, C, nh, hd).transpose(0, 2, 1, 3)
    pks = k_scale[tables, li][..., :nh].reshape(S, C, nh)
    pvs = v_scale[tables, li][..., :nh].reshape(S, C, nh)
    pks = pks.transpose(0, 2, 1)
    pvs = pvs.transpose(0, 2, 1)
    scores = jnp.einsum(
        "nhd,nhcd->nhc", q, pk.astype(dtype),
        preferred_element_type=jnp.float32,
    ) / float(np.sqrt(hd))
    scores = scores * pks
    scores = jnp.where(valid[:, None, :], scores, neg)
    w = jax.nn.softmax(scores, axis=-1)
    w = (w * pvs).astype(dtype)
    return jnp.einsum("nhc,nhcd->nhd", w, pv.astype(dtype))

"""Fused paged int8-KV decode attention (the kernel half of the
serving decode engine, ROADMAP #6 / ISSUE 12).

The XLA lowering of ``models/generation.paged_decode_step_fn`` runs
decode attention as a chain: gather every slot's pages into a
materialized ``[S, pages, heads, page, hd]`` HBM copy, dequantize, and
attend. Decode is HBM-bandwidth-bound, so that copy IS the cost. This
kernel fuses the chain: the grid walks ``(slot, page-table entry)``,
each page streams HBM→VMEM **as int8** through a scalar-prefetched
page-table index map (the vLLM paged-attention shape), scales ride
along, and each page folds into a per-slot **online softmax** (running
max, denominator and context accumulator in VMEM scratch). Nothing
gathered ever touches HBM and nothing wider than one page is ever held
dequantized.

Mosaic shape discipline (what the first v5e run forced, PR 21): every
array in the body is 3-D ``[heads, rows, lanes]`` with the page
position on sublanes — the layout the pool's ``[.., page, 1]`` scales
already have, so scores, weights and scales multiply with no relayout;
``q·k`` is a VPU broadcast-multiply + lane reduction (an M=1 batched
einsum has no MXU lowering), and there are no dynamic-offset stores.
``q`` enters as ``[S, heads, 1, hd]`` float32 and the context leaves
the same way; the activation-dtype casts happen outside the kernel.

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


def _page_update(q, k8, v8, ks, vs, valid, m, l, acc, sm_scale: float):
    """Fold one KV page into a slot's online softmax — THE shared math
    of the kernel body and the plain-jnp emulation (same ops, same
    order, same dtypes, so the two are bit-identical on CPU).

    ``q`` [nh, 1, hd] f32; ``k8``/``v8`` [nh, page, hd] int8; ``ks``/
    ``vs`` [nh, page, 1] f32; ``valid`` [nh, page, 1] bool; running
    ``m``/``l`` [nh, 1, 1] and ``acc`` [nh, 1, hd], all f32."""
    f32 = jnp.float32
    s = jnp.sum(q * k8.astype(f32), axis=-1, keepdims=True) * sm_scale
    s = jnp.where(valid, s * ks, np.float32(_NEG))
    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=1, keepdims=True)
    pv = (p * vs) * v8.astype(f32)
    acc_new = acc * corr + jnp.sum(pv, axis=1, keepdims=True)
    return m_new, l_new, acc_new


def paged_decode_attention(
    q: jnp.ndarray,          # [S, nh, hd] activation dtype
    k_pages: jnp.ndarray,    # [P, L, nh, page, hd] int8
    v_pages: jnp.ndarray,    # [P, L, nh, page, hd] int8
    k_scale: jnp.ndarray,    # [P, L, nh, page, 1] f32
    v_scale: jnp.ndarray,    # [P, L, nh, page, 1] f32
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
    S, nh, hd = q.shape
    page = int(k_pages.shape[3])
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

        kpos = j * page + lax.broadcasted_iota(
            jnp.int32, (nh, page, 1), 1
        )
        m_new, l_new, acc_new = _page_update(
            q_ref[0], k_ref[0, 0], v_ref[0, 0], ks_ref[0, 0],
            vs_ref[0, 0], kpos <= pos_ref[s],
            m_ref[...], l_ref[...], acc_ref[...], sm_scale,
        )
        m_ref[...] = m_new
        l_ref[...] = l_new
        acc_ref[...] = acc_new

        @pl.when(j == maxp - 1)
        def _finish():
            o_ref[0] = acc_new / l_new

    # Every index-map component derives from a grid index (``j - j``
    # zeros): this package enables x64 at import, under which literal
    # ints trace i64 beside the i32 grid index and Mosaic fails to
    # legalize the mixed-type func.return (the ops/segment.py lesson).
    def page_map(s, j, tbl, p):
        return (tbl[s, j], (j - j) + li, j - j, j - j, j - j)

    def slot_map(s, j, tbl, p):
        return (s, j - j, j - j, j - j)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, maxp),
        in_specs=[
            pl.BlockSpec((1, nh, 1, hd), slot_map),
            pl.BlockSpec((1, 1, nh, page, hd), page_map),
            pl.BlockSpec((1, 1, nh, page, hd), page_map),
            pl.BlockSpec((1, 1, nh, page, 1), page_map),
            pl.BlockSpec((1, 1, nh, page, 1), page_map),
        ],
        out_specs=pl.BlockSpec((1, nh, 1, hd), slot_map),
        scratch_shapes=[
            pltpu.VMEM((nh, 1, 1), f32),
            pltpu.VMEM((nh, 1, 1), f32),
            pltpu.VMEM((nh, 1, hd), f32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, nh, 1, hd), f32),
        interpret=bool(interpret),
        name="paged_decode_attention",
    )(
        tables.astype(jnp.int32), pos.astype(jnp.int32),
        q.astype(f32)[:, :, None, :], k_pages, v_pages, k_scale, v_scale,
    )
    return out[:, :, 0, :].astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("layer",))
def paged_attention_emulation(
    q, k_pages, v_pages, k_scale, v_scale, layer, tables, pos
):
    """Plain-jnp emulation of the kernel's exact computation — the
    bit-identity oracle: the same :func:`_page_update` folded over the
    page table in the same order, no pallas anywhere. Jitted, because
    the interpreter compiles the kernel body as one XLA computation:
    op-by-op eager dispatch rounds ``acc * corr + sum`` differently
    from the fused form."""
    S, nh, hd = q.shape
    page = int(k_pages.shape[3])
    maxp = int(tables.shape[1])
    li = int(layer)
    f32 = jnp.float32
    sm_scale = 1.0 / float(np.sqrt(hd))
    qf = q.astype(f32)[:, :, None, :]
    outs = []
    for s in range(S):
        m = jnp.full((nh, 1, 1), _NEG, f32)
        l = jnp.zeros((nh, 1, 1), f32)
        acc = jnp.zeros((nh, 1, hd), f32)
        for j in range(maxp):
            pg = tables[s, j]
            kpos = j * page + lax.broadcasted_iota(
                jnp.int32, (nh, page, 1), 1
            )
            m, l, acc = _page_update(
                qf[s], k_pages[pg, li], v_pages[pg, li],
                k_scale[pg, li], v_scale[pg, li], kpos <= pos[s],
                m, l, acc, sm_scale,
            )
        outs.append(acc / l)
    return jnp.stack(outs)[:, :, 0, :].astype(q.dtype)


def paged_attention_reference(
    q, k_pages, v_pages, k_scale, v_scale, layer, tables, pos
):
    """The XLA gather→dequant→attend chain — the production non-kernel
    lowering (``paged_decode_step_fn``'s other branch calls it) AND the
    float oracle the kernel is checked against to tolerance."""
    S, nh, hd = q.shape
    page = int(k_pages.shape[3])
    maxp = int(tables.shape[1])
    C = maxp * page
    dtype = q.dtype
    li = int(layer)
    neg = jnp.asarray(_NEG, jnp.float32)
    valid = jnp.arange(C)[None, :] <= pos[:, None]
    pk = k_pages[tables, li]
    pv = v_pages[tables, li]
    pks = k_scale[tables, li][..., 0]
    pvs = v_scale[tables, li][..., 0]
    pk = pk.transpose(0, 2, 1, 3, 4).reshape(S, nh, C, hd)
    pv = pv.transpose(0, 2, 1, 3, 4).reshape(S, nh, C, hd)
    pks = pks.transpose(0, 2, 1, 3).reshape(S, nh, C)
    pvs = pvs.transpose(0, 2, 1, 3).reshape(S, nh, C)
    scores = jnp.einsum(
        "nhd,nhcd->nhc", q, pk.astype(dtype),
        preferred_element_type=jnp.float32,
    ) / float(np.sqrt(hd))
    scores = scores * pks
    scores = jnp.where(valid[:, None, :], scores, neg)
    w = jax.nn.softmax(scores, axis=-1)
    w = (w * pvs).astype(dtype)
    return jnp.einsum("nhc,nhcd->nhd", w, pv.astype(dtype))

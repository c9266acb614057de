"""Fused paged int8-KV decode attention (the kernel half of the
serving decode engine, ROADMAP #6 / ISSUE 12).

The XLA lowering of ``models/generation.paged_decode_step_fn`` runs
decode attention as a chain: gather every slot's pages into a
materialized ``[S, pages, page, heads*hd]`` HBM copy, dequantize, and
attend. Decode is HBM-bandwidth-bound, so that copy IS the cost. This
kernel fuses the chain and does work only where a slot has context.

The walk (PR 29). The grid runs over slots alone. The pool columns
stay in HBM (``pl.ANY``); for each slot an in-kernel loop with the
slot's own trip count folds *chunks* of ``G`` table entries (about 128
positions, :func:`chunk_pages`) into a per-slot **online softmax**:
``pos // (G*page) + 1`` chunks, one for a padding slot. A chunk's pages
are copied HBM→VMEM **as int8** by one async copy per page and column,
page ids from the scalar-prefetched table, into one half of a double
buffer while the other half is folded; under a slot's last fold the
next slot's first chunk streams in. Only pages the context reaches are
copied: the rows of a chunk beyond the slot's position keep whatever
the buffer held and are *selected* out of both products (never
multiplied by a zero weight), so no page beyond a context is ever read
and a table entry beyond it may point anywhere. The engine counts the
table entries the chunks cover against the whole table's
(``tftpu_decode_attn_pages_walked_total`` / ``..._grid_total``): the
share of a whole-table walk that the contexts make necessary.

The fold is two matmuls with heads on sublanes and the chunk's
positions on lanes. Scores: ``q`` is laid out as ``[head_rows,
heads*hd]`` with head ``h``'s values on row ``h`` (zeros elsewhere) and
contracted with the chunk's int8 key rows — every head's scores in one
MXU pass, as ``[head_rows, rows]``. Context: the weights times the
value rows give ``[head_rows, heads*hd]``, of which row ``h`` is
meaningful on head ``h``'s lanes; the diagonal blocks are picked once
per slot. The matmuls run in bfloat16 with float32 accumulation and
round nothing: int8 rows are exact in bfloat16, and a float32 operand
(``q``, the softmax weights times the value scales) goes in as three
bfloat16 pieces that sum back to it (:func:`_split3`), so every product
is exact and only the order of the float32 sums differs from the plain
formulation. The pool's scale rows ``[rows, SCALE_LANES]`` (head ``h``
in lane ``h``) are transposed to that layout in-kernel.

One physical layout (PR 26). The kernel's operands ARE the resident
pool columns of ``models/generation.init_paged_kv``: k/v
``[P, L, page, heads*hd]`` int8 and scales ``[P, L, page, SCALE_LANES]``
float32. Their row-major default layout is the one the step's KV
scatter writes and the one Mosaic copies ``[page, ·]`` slabs from, so no
program converts a pool column.

Mosaic under this package's x64: a literal index or divisor traces
i64, which Mosaic cannot legalize, so indices are ``np.int32`` scalars,
zeros derive from a grid index (``s - s``), and integer division is
``lax.div`` on int32 (``//`` and ``%`` trace through an i64 helper).

Equality gates: the kernel is bit-identical on the CPU pallas
interpreter to :func:`paged_attention_emulation` — the same chunk
update (:func:`_chunk_update`) folded over the same chunks in the same
order in plain jnp — and agrees with the whole-horizon XLA chain
(:func:`paged_attention_reference`, the production non-kernel
lowering) to float tolerance: an online softmax reassociates the
denominator, so the two are not bitwise equal. ``G`` depends on the
page size and the table's width only, never on the slot count, so a
solo step and a batched step fold a slot's chunks identically: the
engine-level gates (batched==solo, preemption replay) hold, and they
hold on either lowering because every step of a process traces the same
one (``ops.attention.paged_decode_attention`` asks
``kernels.selectable`` where the step is traced).

Null-page handling is inherited: padding slots carry all-null tables
and position 0, so they fold the null page's first row (the row the
step's own scatter just wrote) and nothing else.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_NEG = -1e30

# Lanes of a pool scale row: one TPU vector register's width, so a
# page's ``[page, SCALE_LANES]`` scale block is whole (8, 128) float32
# tiles. Lane ``h < heads`` holds head ``h``; the lanes beyond are
# padding that no output reads.
SCALE_LANES = 128


def chunk_pages(page: int, maxp: int) -> int:
    """Table entries folded per online-softmax update: about 128
    positions (the lane width of the scores), never more than the table
    holds. A function of ``page`` and ``maxp`` alone — never of the
    slot count — so the solo step and every batched bucket fold the
    same chunks in the same order (the engine's batched==solo
    bit-identity)."""
    return min(max(1, 128 // int(page)), int(maxp))


def pages_walked(pos, page: int, maxp: int, window: Optional[int] = None):
    """Table entries covered by the chunks the kernel folds for slots at
    positions ``pos`` (a numpy array): a slot's context is ``pos + 1``
    positions, so it folds ``pos // (G*page) + 1`` chunks of ``G``
    entries (the last chunk of a table that ``G`` does not divide is
    short); a padding slot (``pos`` 0) folds one. The one-page-a-step
    grid this walk replaced ran ``maxp`` entries for every slot. With a
    ``window`` the walk starts at the chunk that holds ``pos - (window
    - 1)``: the chunks before it are not folded."""
    g = chunk_pages(page, maxp)
    if window is None:
        return np.minimum((pos // (g * page) + 1) * g, maxp)
    first = np.maximum(pos - (int(window) - 1), 0) // (g * page)
    return (pos // (g * page) + 1 - first) * g


def _head_rows(nh: int) -> int:
    """Heads padded to whole bfloat16 tiles of 16 sublanes."""
    return -(-nh // 16) * 16


def _own_lanes(nh: int, hd: int, group: int = 1):
    """``[head_rows, (nh // group)*hd]`` bool: row ``h`` owns the lanes
    of the KV head it reads, ``h // group`` (the rows beyond ``nh`` own
    none). ``group`` is 1 where every query head has a KV head of its
    own."""
    shape = (_head_rows(nh), (nh // group) * hd)
    head = lax.broadcasted_iota(jnp.int32, shape, 0)
    lane = lax.broadcasted_iota(jnp.int32, shape, 1)
    if group > 1:
        live = head < nh
        head = lax.div(head, np.int32(group))
        return live & (lane >= head * hd) & (lane < head * hd + hd)
    return (lane >= head * hd) & (lane < head * hd + hd)


def _split3(x):
    """float32 ``[n, ·]`` → bfloat16 ``[3n, ·]``: three pieces that sum
    back to ``x`` exactly (8 + 8 + 8 significand bits), stacked on the
    rows — what lets a one-pass bfloat16 matmul carry a float32 operand
    unrounded. :func:`_sum3` adds the three row blocks of the product."""
    bf16, f32 = jnp.bfloat16, jnp.float32
    a = x.astype(bf16)
    r = x - a.astype(f32)
    b = r.astype(bf16)
    c = (r - b.astype(f32)).astype(bf16)
    return jnp.concatenate([a, b, c], axis=0)


def _sum3(x):
    n = x.shape[0] // 3
    return (x[:n] + x[n:2 * n]) + x[2 * n:]


def _q_rows(q, nh: int, hd: int):
    """``q`` [1, nh*hd] f32 → ``[3*head_rows, nh*hd]`` bf16: row ``h``
    holds head ``h``'s values on its own lanes and 0 elsewhere, so one
    matmul against a chunk's key rows gives every head's scores."""
    return _split3(jnp.where(_own_lanes(nh, hd), q, np.float32(0)))


def _q_rows_grouped(q, nq: int, nkv: int, hd: int):
    """``q`` [nq, hd] f32 → ``[3*head_rows, nkv*hd]`` bf16 for grouped
    heads: row ``j`` holds query head ``j``'s values on the lanes of KV
    head ``j // group`` and 0 elsewhere, so one matmul against a chunk's
    key rows folds each KV head with its ``group`` query heads."""
    hr = _head_rows(nq)
    tiled = jnp.concatenate([q] * nkv, axis=1)
    if hr > nq:
        tiled = jnp.concatenate(
            [tiled, jnp.zeros((hr - nq, nkv * hd), jnp.float32)], axis=0
        )
    return _split3(jnp.where(
        _own_lanes(nq, hd, nq // nkv), tiled, np.float32(0)
    ))


def _fold_init(nh: int, hd: int, group: int = 1):
    """A slot's running max, denominator ``[head_rows, 1]`` and context
    accumulator ``[head_rows, (nh // group)*hd]`` before its first
    chunk."""
    f32 = jnp.float32
    hr = _head_rows(nh)
    return (jnp.full((hr, 1), _NEG, f32), jnp.zeros((hr, 1), f32),
            jnp.zeros((hr, (nh // group) * hd), f32))


def _chunk_update(qx, k, v, ks, vs, valid, m, l, acc, sm_scale: float,
                  nh: int):
    """Fold one chunk of KV rows into a slot's online softmax — THE
    shared math of the kernel body and the plain-jnp emulation (same
    ops, same order, same dtypes, so the two are bit-identical on CPU).

    ``qx`` from :func:`_q_rows`; ``k``/``v`` [rows, nh*hd] bf16 (the
    int8 rows, converted: exact); ``ks``/``vs`` [rows, SCALE_LANES]
    f32; ``valid`` [1, rows] bool; running ``m``/``l`` [head_rows, 1]
    and ``acc`` [head_rows, nh*hd] f32 (row ``h`` meaningful on head
    ``h``'s lanes). A row that is not ``valid`` may hold anything (a
    page the walk never copied: stale VMEM, NaN scales): its score and
    its weight are selected away, not multiplied by 0."""
    f32 = jnp.float32
    hr = _head_rows(nh)
    s = _sum3(lax.dot_general(
        qx, k, (((1,), (1,)), ((), ())), preferred_element_type=f32
    )) * sm_scale                                     # [head_rows, rows]
    s = jnp.where(valid, s * ks.T[:hr], np.float32(_NEG))
    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=1, keepdims=True)
    w = jnp.where(valid, p * vs.T[:hr], np.float32(0))
    o = _sum3(jnp.dot(_split3(w), v, preferred_element_type=f32))
    return m_new, l_new, acc * corr + o


def _fold_finish(l, acc, nh: int, hd: int):
    """``[1, nh*hd]`` context: each head's own lanes of its row."""
    return jnp.sum(
        jnp.where(_own_lanes(nh, hd), acc / l, np.float32(0)),
        axis=0, keepdims=True,
    )


def _fold_finish_grouped(l, acc, nq: int, nkv: int, hd: int):
    """``[nq, hd]`` context for grouped heads: row ``j`` of the
    accumulator is meaningful on the lanes of KV head ``j // group``."""
    o = acc / l
    kv_of_row = lax.div(
        lax.broadcasted_iota(jnp.int32, (o.shape[0], hd), 0),
        np.int32(nq // nkv),
    )
    out = jnp.zeros((o.shape[0], hd), jnp.float32)
    for g in range(nkv):
        out = jnp.where(kv_of_row == g, o[:, g * hd:(g + 1) * hd], out)
    return out[:nq]


def _check_pool(q, k_pages, k_scale):
    S, nh, hd = q.shape
    width = k_pages.shape[-1] if k_pages.ndim == 4 else 0
    if k_pages.ndim != 4 or width % hd or (
            width != nh * hd and nh % max(width // hd, 1)):
        raise ValueError(
            f"k/v pages must be [pages, layers, page_size, kv_heads*"
            f"head_dim] with the {nh} query heads a multiple of the KV "
            f"heads (head_dim {hd}), got {tuple(k_pages.shape)}"
        )
    if k_scale.shape != k_pages.shape[:3] + (SCALE_LANES,) \
            or nh > SCALE_LANES:
        raise ValueError(
            f"scales must be {k_pages.shape[:3] + (SCALE_LANES,)} with "
            f"heads={nh} <= {SCALE_LANES}, got {tuple(k_scale.shape)}"
        )


def paged_decode_attention(
    q: jnp.ndarray,          # [S, nh, hd] activation dtype
    k_pages: jnp.ndarray,    # [P, L, page, kv_heads*hd] int8
    v_pages: jnp.ndarray,    # [P, L, page, kv_heads*hd] int8
    k_scale: jnp.ndarray,    # [P, L, page, SCALE_LANES] f32
    v_scale: jnp.ndarray,    # [P, L, page, SCALE_LANES] f32
    layer: int,              # layer index
    tables: jnp.ndarray,     # [S, maxp] int32 page tables
    pos: jnp.ndarray,        # [S] int32 current positions
    interpret: Optional[bool] = None,
    window: Optional[int] = None,
    ring: bool = False,
) -> jnp.ndarray:
    """One layer's paged decode attention for every slot: returns the
    ``[S, nh, hd]`` context in ``q.dtype``. Traceable (callers embed it
    in the jitted decode step); ``interpret`` defaults to the backend's
    :func:`tensorframes_tpu.kernels.interpret_mode`.

    Grouped heads: the pool rows may hold fewer KV heads than ``q`` has
    query heads (a whole multiple); query head ``j`` reads KV head ``j
    // group``, and the pool's scale lane ``j`` holds that KV head's
    scale (the KV write lays a scale out once per query head). With a
    ``window`` a slot attends positions ``pos - (window - 1) .. pos``
    and the walk starts at the chunk that holds the first of them. With
    ``ring`` the table is a ring: logical page ``j`` of a slot's context
    stands at entry ``j % maxp``."""
    from . import interpret_mode

    if interpret is None:
        interpret = interpret_mode()
    _check_pool(q, k_pages, k_scale)
    return _paged_walk(
        np.asarray([layer], np.int32), tables.astype(jnp.int32),
        pos.astype(jnp.int32), q, k_pages, v_pages, k_scale, v_scale,
        interpret=bool(interpret),
        window=None if window is None else int(window), ring=bool(ring),
    )


@functools.partial(jax.jit, static_argnames=("interpret", "window", "ring"))
def _paged_walk(layer, tables, pos, q, k_pages, v_pages, k_scale,
                v_scale, *, interpret: bool, window: Optional[int] = None,
                ring: bool = False):
    """The kernel call. Jitted with the layer an operand so that a
    step's twelve layers trace and lower ONE kernel: traced per layer,
    the kernel was most of what ``server.start()`` spends on a warm
    start (the AOT store's key needs each program's jaxpr)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, nh, hd = q.shape
    width = int(k_pages.shape[-1])
    nkv = width // hd
    grouped = nkv != nh
    page = int(k_pages.shape[2])
    maxp = int(tables.shape[1])
    f32, bf16, i32 = jnp.float32, jnp.bfloat16, np.int32
    sm_scale = 1.0 / float(np.sqrt(hd))
    G = chunk_pages(page, maxp)
    rows = G * page

    def kernel(layer_ref, tbl_ref, pos_ref, q_ref, k_hbm, v_hbm, ks_hbm,
               vs_hbm, o_ref, k_buf, v_buf, ks_buf, vs_buf, sem, half_ref):
        s = pl.program_id(0)
        zero = s - s
        li = layer_ref[0]
        pos_s = pos_ref[s]
        columns = ((k_hbm, k_buf), (v_hbm, v_buf), (ks_hbm, ks_buf),
                   (vs_hbm, vs_buf))

        def first_pos(slot):
            """The first position slot ``slot`` attends (windowed)."""
            return jnp.maximum(pos_ref[slot] - i32(window - 1), zero)

        def first_chunk(slot):
            if window is None:
                return zero
            return lax.div(first_pos(slot), i32(rows))

        def chunk_copies(slot, c, half, start):
            """Start, or await, the copies of slot ``slot``'s chunk
            ``c`` into buffer ``half``: one copy per column and page the
            slot's context reaches, none beyond it — so a chunk past
            the slot's last is no copy at all."""
            last = lax.div(pos_ref[slot], i32(page))
            if window is not None:
                first = lax.div(first_pos(slot), i32(page))
            for g in range(G):
                j = c * G + g
                wanted = j <= last
                if window is not None:
                    wanted = wanted & (j >= first)

                @pl.when(wanted)
                def _():
                    # awaiting needs the copy's size, not its source
                    if not start:
                        pg = zero
                    elif ring:
                        pg = tbl_ref[slot, lax.rem(j, i32(maxp))]
                    else:
                        pg = tbl_ref[slot, j]
                    for i, (hbm, buf) in enumerate(columns):
                        cp = pltpu.make_async_copy(
                            hbm.at[pg, li], buf.at[half, i32(g)],
                            sem.at[half, i32(i)],
                        )
                        cp.start() if start else cp.wait()

        @pl.when(s == 0)
        def _first():
            half_ref[0] = zero
            chunk_copies(s, first_chunk(s), zero, True)

        half0 = half_ref[0]
        n = lax.div(pos_s, i32(rows)) + 1
        c0 = first_chunk(s)
        if window is not None:
            n = n - c0
            lo = first_pos(s)
        half_ref[0] = (half0 + n) & 1
        if grouped:
            qx = _q_rows_grouped(q_ref[0], nh, nkv, hd)
        else:
            qx = _q_rows(q_ref[0], nh, hd)

        def fold(r, carry):
            half = (half0 + r) & 1
            c = r if window is None else r + c0
            # look ahead into the other half, which chunk c-1 has left:
            # this slot's next chunk or, under its last (where that is
            # no copy at all), the next slot's first
            chunk_copies(s, c + 1, 1 - half, True)

            @pl.when((r == n - 1) & (s + 1 < S))
            def _next_slot():
                nxt = jnp.minimum(s + 1, S - 1)
                chunk_copies(nxt, first_chunk(nxt), 1 - half, True)

            chunk_copies(s, c, half, False)

            def chunk(buf, dtype):
                return jnp.concatenate(
                    [buf[half, i32(g)].astype(dtype) for g in range(G)],
                    axis=0,
                )

            kpos = c * rows + lax.broadcasted_iota(
                jnp.int32, (1, rows), 1
            )
            folded = (chunk(k_buf, bf16), chunk(v_buf, bf16),
                      chunk(ks_buf, f32), chunk(vs_buf, f32))
            valid = kpos <= pos_s
            if window is not None:
                valid = valid & (kpos >= lo)
            return _chunk_update(
                qx, *folded, valid, *carry, sm_scale, nh,
            )

        _, l, acc = lax.fori_loop(
            zero, n, fold, _fold_init(nh, hd, nh // nkv)
        )
        if grouped:
            o_ref[0] = _fold_finish_grouped(l, acc, nh, nkv, hd)
        else:
            o_ref[0] = _fold_finish(l, acc, nh, hd)

    def slot_map(s, lay, tbl, p):
        return (s, s - s, s - s)

    # a slot's query and context: one row of all heads' lanes, or with
    # grouped heads the heads on sublanes (the fold's own layout)
    qo_block = (1, nh, hd) if grouped else (1, 1, width)
    pool = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S,),
        in_specs=[pl.BlockSpec(qo_block, slot_map), pool, pool,
                  pool, pool],
        out_specs=pl.BlockSpec(qo_block, slot_map),
        scratch_shapes=[
            # the double buffer: [half, page of the chunk, page, ·],
            # each copy's target a whole [page, ·] slab
            pltpu.VMEM((2, G, page, width), jnp.int8),
            pltpu.VMEM((2, G, page, width), jnp.int8),
            pltpu.VMEM((2, G, page, SCALE_LANES), f32),
            pltpu.VMEM((2, G, page, SCALE_LANES), f32),
            pltpu.SemaphoreType.DMA((2, 4)),
            # which half holds this slot's first chunk (carried from
            # the slot before, which started its copies)
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S,) + qo_block[1:], f32),
        interpret=bool(interpret),
        name="paged_decode_attention",
    )(
        layer, tables, pos, q.astype(f32).reshape((S,) + qo_block[1:]),
        k_pages, v_pages, k_scale, v_scale,
    )
    return out.reshape(S, nh, hd).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("layer", "window", "ring"))
def paged_attention_emulation(
    q, k_pages, v_pages, k_scale, v_scale, layer, tables, pos,
    window: Optional[int] = None, ring: bool = False,
):
    """Plain-jnp emulation of the kernel's exact computation — the
    bit-identity oracle: the same :func:`_chunk_update` folded over the
    same chunks of the page table in the same order, no pallas
    anywhere. Jitted and written as loops (slots mapped, chunks a
    ``fori_loop`` with the slot's trip count), as the interpreter runs
    the grid: a loop around ONE compiled body."""
    _check_pool(q, k_pages, k_scale)
    S, nh, hd = q.shape
    width = int(k_pages.shape[-1])
    nkv = width // hd
    grouped = nkv != nh
    page = int(k_pages.shape[2])
    maxp = int(tables.shape[1])
    li = int(layer)
    f32, bf16 = jnp.float32, jnp.bfloat16
    sm_scale = 1.0 / float(np.sqrt(hd))
    qf = q.astype(f32).reshape((S, nh, hd) if grouped else (S, 1, width))
    G = chunk_pages(page, maxp)
    rows = G * page

    def slot(s):
        if grouped:
            qx = _q_rows_grouped(qf[s], nh, nkv, hd)
        else:
            qx = _q_rows(qf[s], nh, hd)
        lo = 0 if window is None else jnp.maximum(pos[s] - (window - 1), 0)

        def fold(c, carry):
            # entries the kernel never copies (past the slot's last
            # page, before its window's first) are selected away
            # whatever stands there, so a real page does as well as any
            j = jnp.clip(c * G + jnp.arange(G), lo // page, pos[s] // page)
            pgs = tables[s, j % maxp if ring else j]
            kpos = c * rows + lax.broadcasted_iota(
                jnp.int32, (1, rows), 1
            )
            valid = kpos <= pos[s]
            if window is not None:
                valid = valid & (kpos >= lo)

            def chunk(col, dtype):
                return col[pgs, li].reshape(rows, -1).astype(dtype)

            return _chunk_update(
                qx, chunk(k_pages, bf16), chunk(v_pages, bf16),
                chunk(k_scale, f32), chunk(v_scale, f32),
                valid, *carry, sm_scale, nh,
            )

        _, l, acc = lax.fori_loop(
            lo // rows, pos[s] // rows + 1, fold,
            _fold_init(nh, hd, nh // nkv),
        )
        if grouped:
            return _fold_finish_grouped(l, acc, nh, nkv, hd)
        return _fold_finish(l, acc, nh, hd)

    out = lax.map(slot, jnp.arange(S))
    return out.reshape(S, nh, hd).astype(q.dtype)


def paged_attention_reference(
    q, k_pages, v_pages, k_scale, v_scale, layer, tables, pos,
    window: Optional[int] = None, ring: bool = False,
):
    """The XLA gather→dequant→attend chain — the production non-kernel
    lowering (``paged_decode_step_fn``'s other branch calls it) AND the
    float oracle the kernel is checked against to tolerance. Grouped
    heads, a window and a ring table as the kernel takes them."""
    _check_pool(q, k_pages, k_scale)
    S, nh, hd = q.shape
    page = int(k_pages.shape[2])
    maxp = int(tables.shape[1])
    C = maxp * page
    dtype = q.dtype
    li = int(layer)
    neg = jnp.asarray(_NEG, jnp.float32)
    nkv = int(k_pages.shape[-1]) // hd
    if nkv != nh or window is not None or ring:
        g = nh // nkv
        entry = jnp.arange(maxp)[None, :]
        if ring:
            # entry e holds the newest logical page congruent to it
            last = (pos // page)[:, None]
            entry = last - (last - entry) % maxp
        kpos = (entry[:, :, None] * page
                + jnp.arange(page)[None, None, :]).reshape(-1, C)
        valid = (kpos >= 0) & (kpos <= pos[:, None])
        if window is not None:
            valid = valid & (kpos >= pos[:, None] - (window - 1))
        pk = k_pages[tables, li].reshape(S, C, nkv, hd)
        pv = v_pages[tables, li].reshape(S, C, nkv, hd)
        pks = k_scale[tables, li][..., :nh].reshape(S, C, nh)
        pvs = v_scale[tables, li][..., :nh].reshape(S, C, nh)
        scores = jnp.einsum(
            "nkgd,nckd->nkgc", q.reshape(S, nkv, g, hd), pk.astype(dtype),
            preferred_element_type=jnp.float32,
        ).reshape(S, nh, C) / float(np.sqrt(hd))
        scores = scores * pks.transpose(0, 2, 1)
        scores = jnp.where(valid[:, None, :], scores, neg)
        w = jax.nn.softmax(scores, axis=-1)
        w = (w * pvs.transpose(0, 2, 1)).astype(dtype)
        return jnp.einsum(
            "nkgc,nckd->nkgd", w.reshape(S, nkv, g, C), pv.astype(dtype)
        ).reshape(S, nh, hd)
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

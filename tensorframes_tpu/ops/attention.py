"""Long-context attention kernels: blockwise, pallas-flash, and ring.

The reference has **no** sequence/long-context support at all (SURVEY.md
§5: max tensor order 2 per cell; scaling is by rows only). For the TPU
framework long-context is first-class: these kernels power the
transformer model family and are public ops in their own right.

Three implementations, one contract (``[batch, heads, seq, head_dim]``):

* :func:`blockwise_attention` — pure-jax online-softmax scan over key/value
  chunks (memory O(seq·block) instead of O(seq²)); runs on any backend and
  is the reference implementation for the other two.
* :func:`flash_attention` — the TPU pallas flash kernel (VMEM-tiled MXU
  kernel) on a TPU for shapes it serves, blockwise otherwise; chosen
  statically, never by catching a failure.
* :func:`ring_attention` — sequence parallelism over a mesh axis: q/k/v
  are sharded on the sequence dim; each device scans the full sequence by
  rotating its k/v shard around the ring with ``lax.ppermute`` (ICI
  neighbor exchange) while accumulating the online softmax. Communication
  overlaps compute, memory per device is O(seq/sp), and the math is
  exactly dense attention.

Serving decode adds a fourth: :func:`paged_decode_attention` — the
fused paged int8-KV kernel (``kernels/decode_attention.py``) where the
backend runs it (``kernels.selectable``), the XLA gather chain elsewhere.
Likewise :func:`blockwise_attention`'s block-bounded fold of packed
prompts (the packed prefill's) runs ``kernels/packed_attention.py``
where the backend runs it, :func:`_band_attention` elsewhere.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..utils import is_tpu_backend

NEG_INF = -1e30


def _online_block(
    q: jnp.ndarray,  # [b, h, sq, d] (pre-scaled)
    k: jnp.ndarray,  # [b, h, sk, d]
    v: jnp.ndarray,  # [b, h, sk, d]
    o: jnp.ndarray,  # [b, h, sq, d] f32 accumulator
    m: jnp.ndarray,  # [b, h, sq] f32 running max
    l: jnp.ndarray,  # [b, h, sq] f32 running denominator
    mask: Optional[jnp.ndarray],  # [sq, sk] bool or None
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One online-softmax accumulation step (flash-attention recurrence)."""
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    )
    if mask is not None:
        s = jnp.where(mask[None, None], s, NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    # rows with nothing attended yet keep m at NEG_INF; exp underflows to 0
    p = jnp.exp(s - m_new[..., None])
    if mask is not None:
        p = jnp.where(mask[None, None], p, 0.0)
    corr = jnp.exp(m - m_new)
    l_new = l * corr + p.sum(axis=-1)
    o_new = o * corr[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p, v.astype(jnp.float32)
    )
    return o_new, m_new, l_new


def blockwise_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = False,
    block_size: int = 512,
    window: Optional[int] = None,
    k_scale: Optional[jnp.ndarray] = None,
    v_scale: Optional[jnp.ndarray] = None,
    first_block: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Memory-efficient attention: lax.scan over k/v chunks with an online
    softmax. Exact (not an approximation); peak memory O(sq · block_size)
    per head instead of O(sq · sk).

    A causal call may also give a ``window`` (position ``i`` attends
    ``i - (window - 1) .. i``: only the blocks of that band are
    computed), fewer KV heads than query heads (a whole multiple: query
    head ``j`` reads KV head ``j // group``), per-position scales
    ``[batch, kv_heads, seq]`` of quantized ``k`` / ``v`` (the key's
    scale multiplies the scores, the value's the softmax weights, so no
    dequantized copy is made) and ``first_block`` ``[seq // block_size]``
    int32, each query block's lowest key block (several sequences packed
    along ``seq``, each starting at a multiple of ``block_size``: a
    block attends its own sequence only): :func:`_band_attention`.

    Block bounds over quantized KV with its scales and no window run the
    packed-attention kernel (``kernels/packed_attention.py``) where
    ``kernels.selectable("packed_attn")`` holds and the heads fit it,
    asked where the call is traced; :func:`_band_attention` elsewhere."""
    if window is not None or k_scale is not None or v_scale is not None \
            or first_block is not None or q.shape[1] != k.shape[1]:
        if not causal or q.shape[2] != k.shape[2]:
            raise ValueError(
                "a window, grouped heads, KV scales or block bounds need "
                "causal=True and queries and keys over the same positions"
            )
        if first_block is not None and window is None \
                and k_scale is not None and v_scale is not None \
                and q.shape == k.shape:
            from .. import kernels as _kernels
            from ..kernels import packed_attention as _kpa

            if _kernels.selectable("packed_attn") \
                    and _kpa.fits(q.shape[1], q.shape[3]):
                return _kpa.packed_attention(q, k, v, k_scale, v_scale,
                                             first_block, block_size)
        return _band_attention(q, k, v, block_size, window, k_scale, v_scale,
                               first_block)
    b, h, sq, d = q.shape
    sk = k.shape[2]
    block_size = min(block_size, sk)
    num_blocks = -(-sk // block_size)
    pad = num_blocks * block_size - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    scale = float(1.0 / np.sqrt(d))  # python float: weak-typed, no f64 promotion under x64
    qs = (q * scale).astype(q.dtype)

    kb = k.reshape(b, h, num_blocks, block_size, d).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(b, h, num_blocks, block_size, d).transpose(2, 0, 1, 3, 4)

    q_pos = jnp.arange(sq)
    k_pos_base = jnp.arange(block_size)

    def step(carry, inp):
        o, m, l = carry
        blk_idx, k_blk, v_blk = inp
        if causal or pad:
            k_pos = blk_idx * block_size + k_pos_base
            mask = k_pos[None, :] < sk  # mask padding
            if causal:
                mask = mask & (q_pos[:, None] >= k_pos[None, :])
        else:
            mask = None
        o, m, l = _online_block(qs, k_blk, v_blk, o, m, l, mask)
        return (o, m, l), None

    o0 = jnp.zeros((b, h, sq, d), jnp.float32)
    m0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    (o, m, l), _ = jax.lax.scan(
        step, (o0, m0, l0), (jnp.arange(num_blocks), kb, vb)
    )
    return (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


def _band_attention(q, k, v, block_size, window, k_scale, v_scale,
                    first_block=None):
    """Causal self-attention a query block at a time, each against the
    KV blocks its band reaches: block ``i`` folds blocks ``first(i) ..
    i``, ``first`` 0 without a window, so a windowed layer's cost grows
    with ``seq · window`` and no ``seq × seq`` array exists. Online
    softmax in float32; scores and weights of one block pair at a time
    (``[batch, kv_heads, group, block, block]``).

    Given ``first_block`` (packed sequences, each starting on a block
    edge), ``first(i)`` is ``first_block[i]``, the first block of
    ``i``'s own sequence: a sequence's rows see the same keys in the same
    order wherever it sits and whatever lies beside it."""
    b, hq, s, d = q.shape
    hk = k.shape[1]
    if hq % hk:
        raise ValueError(f"{hq} query heads over {hk} KV heads")
    g = hq // hk
    blk = min(int(block_size), s)
    nb = -(-s // blk)
    pad = nb * blk - s
    if first_block is not None and (pad or window is not None):
        raise ValueError(
            "block bounds need a whole number of blocks and no window"
        )
    f32 = jnp.float32
    ones = jnp.ones((b, hk, s), f32)
    ks = ones if k_scale is None else k_scale.astype(f32)
    vs = ones if v_scale is None else v_scale.astype(f32)
    if pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        ks = jnp.pad(ks, ((0, 0), (0, 0), (0, pad)))
        vs = jnp.pad(vs, ((0, 0), (0, 0), (0, pad)))
    scale = float(1.0 / np.sqrt(d))
    qg = q.reshape(b, hk, g, nb, blk, d)
    kb = k.astype(q.dtype).reshape(b, hk, nb, blk, d)
    vb = v.astype(q.dtype).reshape(b, hk, nb, blk, d)
    ksb = ks.reshape(b, hk, nb, blk)
    vsb = vs.reshape(b, hk, nb, blk)
    at = jnp.arange(blk)

    def q_block(i):
        qi = lax.dynamic_index_in_dim(qg, i, axis=3, keepdims=False)
        qpos = i * blk + at

        def fold(j, carry):
            o, m, l = carry
            kj = lax.dynamic_index_in_dim(kb, j, axis=2, keepdims=False)
            vj = lax.dynamic_index_in_dim(vb, j, axis=2, keepdims=False)
            ksj = lax.dynamic_index_in_dim(ksb, j, axis=2, keepdims=False)
            vsj = lax.dynamic_index_in_dim(vsb, j, axis=2, keepdims=False)
            kpos = j * blk + at
            sc = jnp.einsum("bkgqd,bksd->bkgqs", qi, kj,
                            preferred_element_type=f32) * scale
            sc = sc * ksj[:, :, None, None, :]
            mask = kpos[None, :] <= qpos[:, None]
            if window is not None:
                mask = mask & (kpos[None, :] > qpos[:, None] - window)
            sc = jnp.where(mask, sc, NEG_INF)
            m_new = jnp.maximum(m, sc.max(axis=-1))
            p = jnp.where(mask, jnp.exp(sc - m_new[..., None]), 0.0)
            corr = jnp.exp(m - m_new)
            w = (p * vsj[:, :, None, None, :]).astype(q.dtype)
            o = o * corr[..., None] + jnp.einsum(
                "bkgqs,bksd->bkgqd", w, vj, preferred_element_type=f32)
            return o, m_new, l * corr + p.sum(axis=-1)

        if first_block is not None:
            first = first_block[i].astype(i.dtype)
        else:
            first = 0 if window is None else jnp.maximum(
                i * blk - (window - 1), 0) // blk
        o, _, l = lax.fori_loop(first, i + 1, fold, (
            jnp.zeros((b, hk, g, blk, d), f32),
            jnp.full((b, hk, g, blk), NEG_INF, f32),
            jnp.zeros((b, hk, g, blk), f32)))
        return (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)

    out = lax.map(q_block, jnp.arange(nb))      # [nb, b, hk, g, blk, d]
    out = out.transpose(1, 2, 3, 0, 4, 5).reshape(b, hq, nb * blk, d)
    return out[:, :, :s]


#: q/kv block edge of the upstream pallas flash kernel's default
#: ``BlockSizes``: sequence lengths must be whole multiples of it
_FLASH_BLOCK = 128


def flash_eligible(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray) -> bool:
    """Static predicate: can the upstream TPU pallas flash kernel serve
    these operands? Its own shape checks, evaluated BEFORE the call:
    ``[batch, heads, seq, head_dim]`` operands of one float32/bfloat16
    dtype, both sequence lengths whole multiples of the 128 block, and
    a head_dim that is at most 128 or a multiple of it."""
    if q.ndim != 4 or not (q.dtype == k.dtype == v.dtype):
        return False
    if q.dtype not in (jnp.float32, jnp.bfloat16):
        return False
    d = q.shape[-1]
    return (
        q.shape[2] % _FLASH_BLOCK == 0
        and k.shape[2] % _FLASH_BLOCK == 0
        and (d <= _FLASH_BLOCK or d % _FLASH_BLOCK == 0)
    )


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = False,
    block_size: int = 512,
) -> jnp.ndarray:
    """The TPU pallas flash kernel on a TPU for operands it serves
    (:func:`flash_eligible`), blockwise attention otherwise (CPU, or a
    shape outside the kernel's block rules). The choice is made from
    the backend and the static shapes alone — a kernel failure raises,
    it never falls back."""
    if not (is_tpu_backend() and flash_eligible(q, k, v)):
        return blockwise_attention(
            q, k, v, causal=causal, block_size=block_size
        )
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        flash_attention as pallas_flash,
    )

    d = q.shape[-1]
    # trace the kernel with x64 OFF: this package enables x64 globally,
    # under which integer literals in the upstream kernel's index maps
    # trace as i64 beside i32 grid indices — the same Mosaic
    # func.return legalization failure the segment kernel hit (see
    # ops/segment.py)
    with jax.enable_x64(False):
        return pallas_flash(
            q, k, v, causal=causal, sm_scale=float(1.0 / np.sqrt(d))
        )


# ---------------------------------------------------------------------------
# Ring attention (sequence parallelism)
# ---------------------------------------------------------------------------

def _ring_attention_local(
    q: jnp.ndarray,  # [b, h, s_loc, d] — local sequence shard
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str,
    causal: bool,
) -> jnp.ndarray:
    """shard_map body: rotate k/v shards around the ring while accumulating
    the online softmax for the local queries."""
    n = int(jax.lax.axis_size(axis_name))
    my = jax.lax.axis_index(axis_name)
    b, h, s_loc, d = q.shape
    scale = float(1.0 / np.sqrt(d))  # weak-typed: no f64 promotion under x64
    qs = (q * scale).astype(q.dtype)
    q_pos = my * s_loc + jnp.arange(s_loc)

    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, t):
        o, m, l, k_cur, v_cur = carry
        # the shard we currently hold originated on device (my - t) mod n
        src = (my - t) % n
        if causal:
            k_pos = src * s_loc + jnp.arange(s_loc)
            mask = q_pos[:, None] >= k_pos[None, :]
        else:
            mask = None
        o, m, l = _online_block(qs, k_cur, v_cur, o, m, l, mask)
        # rotate k/v to the next device; overlaps with next iteration's
        # compute under XLA's async collective scheduling
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (o, m, l, k_nxt, v_nxt), None

    o0 = jnp.zeros((b, h, s_loc, d), jnp.float32)
    m0 = jnp.full((b, h, s_loc), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s_loc), jnp.float32)
    (o, m, l, _, _), _ = jax.lax.scan(
        step, (o0, m0, l0, k, v), jnp.arange(n)
    )
    return (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


def _maybe_axis(mesh: Mesh, name: Optional[str], dim_size: int) -> Optional[str]:
    """Use mesh axis ``name`` for a dim only when it exists and divides the
    dim evenly; otherwise keep the dim replicated (shard_map would reject
    an uneven split)."""
    if not name or name not in mesh.shape:
        return None
    return name if dim_size % mesh.shape[name] == 0 else None


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    axis: str = "sp",
    causal: bool = False,
    batch_axis: Optional[str] = "dp",
    head_axis: Optional[str] = "tp",
) -> jnp.ndarray:
    """Sequence-parallel exact attention over ``mesh[axis]``.

    Inputs are global arrays [b, heads, seq, head_dim] with ``seq``
    (logically) sharded over ``axis``; ``seq`` must divide evenly by the
    axis size. Batch / heads may additionally be sharded over
    ``batch_axis`` / ``head_axis`` (heads stay tp-sharded end-to-end in
    the Megatron layout instead of being all-gathered at the shard_map
    boundary).
    """
    from ..parallel._shard_map import shard_map

    seq = q.shape[2]
    sp = mesh.shape[axis]
    if seq % sp != 0:
        raise ValueError(
            f"ring_attention: seq {seq} not divisible by mesh axis "
            f"{axis!r} of size {sp}"
        )
    db = _maybe_axis(mesh, batch_axis, q.shape[0])
    ha = _maybe_axis(mesh, head_axis, q.shape[1])
    spec = P(db, ha, axis, None)
    fn = shard_map(
        functools.partial(
            _ring_attention_local, axis_name=axis, causal=causal
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check=False,
    )
    return fn(q, k, v)


def ulysses_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    axis: str = "sp",
    causal: bool = False,
    batch_axis: Optional[str] = "dp",
) -> jnp.ndarray:
    """All-to-all sequence parallelism (DeepSpeed-Ulysses style) — the
    complement of :func:`ring_attention`.

    Inputs are [b, heads, seq, head_dim] with ``seq`` sharded over
    ``axis``. Two ``all_to_all`` collectives re-shard: heads scatter
    across the sp group while sequence gathers (each device then holds the
    FULL sequence for heads/sp heads), standard blockwise attention runs
    locally with no per-step communication, and the reverse exchange
    restores sequence sharding. Versus the ring: 2 bulk a2a transfers
    instead of sp ppermute rounds — better when ICI latency dominates and
    heads divide evenly; the ring wins when heads < sp or memory for the
    full sequence per head is tight.
    """
    from ..parallel._shard_map import shard_map

    seq, heads = q.shape[2], q.shape[1]
    sp = mesh.shape[axis]
    if seq % sp != 0:
        raise ValueError(
            f"ulysses_attention: seq {seq} not divisible by mesh axis "
            f"{axis!r} of size {sp}"
        )
    if heads % sp != 0:
        raise ValueError(
            f"ulysses_attention: heads {heads} not divisible by mesh axis "
            f"{axis!r} of size {sp} (use ring_attention for heads < sp)"
        )
    db = _maybe_axis(mesh, batch_axis, q.shape[0])

    def local(qs, ks, vs):
        # one fused exchange for q/k/v (stacked on a lead axis): heads
        # scatter (split dim 2), sequence gathers (concat dim 3)
        # [3, b, h, s/sp, d] → [3, b, h/sp, s, d]
        qkv = jnp.stack([qs, ks, vs])
        qkv = lax.all_to_all(qkv, axis, split_axis=2, concat_axis=3, tiled=True)
        ctx = blockwise_attention(qkv[0], qkv[1], qkv[2], causal=causal)
        # reverse: sequence scatters, heads gather → [b, h, s/sp, d]
        return lax.all_to_all(ctx, axis, split_axis=2, concat_axis=1, tiled=True)

    spec = P(db, None, axis, None)
    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check=False,
    )
    return fn(q, k, v)


def paged_decode_attention(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    k_scale: jnp.ndarray,
    v_scale: jnp.ndarray,
    layer: int,
    tables: jnp.ndarray,
    pos: jnp.ndarray,
    window: Optional[int] = None,
    ring: bool = False,
) -> jnp.ndarray:
    """One layer's paged int8-KV decode attention for every slot:
    ``q`` [slots, heads, head_dim] against the
    ``models/generation.init_paged_kv`` pool columns (k/v ``[pages,
    layers, page, heads*head_dim]`` int8, scales ``[pages, layers, page,
    128]`` float32), read where they lie through ``tables`` and masked
    to ``j <= pos``. Traceable; the decode step embeds it. The pool may
    hold fewer KV heads than ``q`` has query heads (grouped heads), a
    ``window`` bounds the positions a slot attends, and ``ring`` says the
    table is a ring (``kernels/decode_attention.paged_decode_attention``
    has the three).

    This is where the lowering is chosen, from the backend alone:
    where ``kernels.selectable("decode_attn")`` holds (a TPU, or the
    CPU interpreter under the test hook) the fused kernel
    (``kernels/decode_attention.paged_decode_attention`` — pages stream
    HBM→VMEM through the page table a chunk a fold and dequantize
    in-register), elsewhere the XLA gather→dequant→attend chain
    (``paged_attention_reference``, also the kernel's float oracle).
    The two agree to float tolerance, not bitwise (asserted in tests)."""
    from .. import kernels as _kernels
    from ..kernels import decode_attention as _kda

    attend = (
        _kda.paged_decode_attention if _kernels.selectable("decode_attn")
        else _kda.paged_attention_reference
    )
    return attend(q, k_pages, v_pages, k_scale, v_scale, layer, tables, pos,
                  window=window, ring=ring)


def dense_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = False,
    padding_mask: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Plain O(s²) attention — the correctness oracle for the kernels.

    ``padding_mask``: bool [batch, seq_k]; False positions are masked out.
    """
    d = q.shape[-1]
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q / float(np.sqrt(d)), k,
        preferred_element_type=jnp.float32,
    )
    if causal:
        sq, sk = s.shape[-2:]
        mask = jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :]
        s = jnp.where(mask[None, None], s, NEG_INF)
    if padding_mask is not None:
        s = jnp.where(padding_mask[:, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w, v.astype(jnp.float32)).astype(q.dtype)

"""Causal attention of the packed prefill over int8 KV, as one Pallas
call a layer.

The packed prefill (``models/generation.paged_packed_prefill_fn``)
lays several prompts along one row axis, each starting on a 64-row
block edge, and gives every query block ``i`` the first key block of
its own prompt, ``first_block[i]``. Its XLA lowering
(``ops/attention._band_attention``) maps over the query blocks and,
inside each, runs a ``fori_loop`` of one 64x64 block pair an iteration:
on the v5e each iteration is a while-loop trip and a few small fusions
whose cost is not the arithmetic's.

Here the grid runs over the query blocks and the key blocks
``first_block[i] .. i`` fold in ascending order inside the kernel, all
heads of a grid step together. The operands stay resident in VMEM for
the whole call: q, k and v as rows of all heads' lanes (``[T,
heads*head_dim]``, the layout the model's projections give), the
scales as one row a key block (``[T // block, heads*block]``, head
``h``'s in lanes ``h*block ..``, padded to whole 8-row tiles).

Heads share a 128-lane tile in groups (two of 64 lanes for GPT-2). A
group's scores are ONE matmul, lane-dense: the query rows ``[block,
lanes]`` against the key block stacked once per head of the group, each
copy zero outside its head's lanes, give ``[block, group*block]`` with
head ``r``'s scores on lanes ``r*block ..``. The context is the
weights times the value block stacked the same way: ``[block, lanes]``,
head ``r``'s context on its own lanes, the layout the output projection
reads. The zero lanes add exact zeros, so each score and each context
element is the head's own sum.

The math is ``_band_attention``'s, op for op: operands in the
activation dtype (int8 codes exact), float32 accumulation, the scaled
scores times the key scale, the float32 running max and denominator,
the weights times the value scale cast to the activation dtype before
the value product, key blocks in ascending order from the block's own
prompt's first; the mask passes every key but the diagonal block's
upper triangle. A query block's result depends on its own rows and
those key blocks alone, so a prompt gives the same bits wherever it
sits in the pack and whatever lies beside it; a padding block
(``first_block[i] == i``) folds itself alone, as before.

Mosaic under this package's x64: indices and divisors are ``np.int32``
and integer division is ``lax.div`` (``//`` traces through an i64
helper Mosaic cannot legalize).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

__all__ = ["packed_attention", "fits"]

_NEG = -1e30
#: lanes of one vector register: the heads of a group share them
LANES = 128


def _group(nh: int, hd: int) -> int:
    """Heads whose lanes share one 128-lane tile."""
    return min(nh, LANES // hd)


def fits(nh: int, hd: int) -> bool:
    """Does the kernel take ``nh`` heads of ``hd`` lanes? A head dim
    that divides 128 and a head count the groups divide."""
    return 0 < hd <= LANES and LANES % hd == 0 and nh % _group(nh, hd) == 0


def packed_attention(q, kq, vq, k_scale, v_scale, first_block,
                     block: int, interpret: Optional[bool] = None):
    """``q`` [1, nh, T, hd] in the activation dtype; ``kq`` / ``vq``
    int8 [1, nh, T, hd] with float32 scales [1, nh, T]; ``first_block``
    [T // block] int32, each query block's first key block. Returns the
    causal context [1, nh, T, hd] in ``q.dtype``. ``interpret``
    defaults to the backend's
    :func:`tensorframes_tpu.kernels.interpret_mode`."""
    from . import interpret_mode

    b, nh, T, hd = q.shape
    if b != 1 or T % block or not fits(nh, hd):
        raise ValueError(
            f"packed attention takes one row of whole {block}-row blocks "
            f"and heads that share 128 lanes, got {tuple(q.shape)}")
    if interpret is None:
        interpret = interpret_mode()
    nb = T // block

    def rows(x):                                # [1, nh, T, hd] -> [T, nh*hd]
        return x[0].transpose(1, 0, 2).reshape(T, nh * hd)

    def scale_rows(s):                  # [1, nh, T] -> [nb + pad, nh*block]
        s = s[0].astype(jnp.float32).reshape(nh, nb, block).transpose(
            1, 0, 2).reshape(nb, nh * block)
        return jnp.pad(s, ((0, -nb % 8), (0, 0)))

    out = _packed_call(
        first_block.astype(jnp.int32), rows(q), rows(kq), rows(vq),
        scale_rows(k_scale), scale_rows(v_scale),
        nh=nh, block=int(block), interpret=bool(interpret))
    return out.reshape(T, nh, hd).transpose(1, 0, 2)[None]


@functools.partial(jax.jit, static_argnames=("nh", "block", "interpret"))
def _packed_call(first_block, q, k, v, ks, vs, *, nh: int, block: int,
                 interpret: bool):
    """The kernel call over the row layouts of :func:`packed_attention`.
    Jitted so that a program's layers trace and lower one kernel."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, width = q.shape
    hd = width // nh
    gh = _group(nh, hd)
    lanes = gh * hd                 # a group's lanes of q, k, v, output
    keys = gh * block               # a group's lanes of scores, scales
    dt = q.dtype
    f32, i32 = jnp.float32, np.int32
    sm_scale = 1.0 / float(np.sqrt(hd))

    def head_of(shape, dim, per):
        """Which head of the group each element's ``dim`` index holds."""
        return lax.div(lax.broadcasted_iota(jnp.int32, shape, dim),
                       i32(per))

    def stacked(x):
        """``[block, lanes]`` → ``[keys, lanes]``: one copy a head of the
        group, zero outside that head's lanes."""
        lane_head = head_of(x.shape, 1, hd)
        return jnp.concatenate(
            [jnp.where(lane_head == r, x, jnp.zeros_like(x))
             for r in range(gh)], axis=0)

    def spread(per_head, shape, per):
        """Per-head ``[block, 1]`` values over each head's lanes."""
        head = head_of(shape, 1, per)
        out = jnp.broadcast_to(per_head[0], shape)
        for r in range(1, gh):
            out = jnp.where(head == r, per_head[r], out)
        return out

    def scale_row(ref, j):
        """Every head's scales of key block ``j``, ``[1, heads*block]``:
        the aligned 8-row tile that holds the row, the row picked out by
        a sum over the tile's rows (Mosaic loads no single row at a
        dynamic index)."""
        base = pl.multiple_of(lax.div(j, i32(8)) * i32(8), 8)
        tile = ref[pl.ds(base, 8), :]
        row = lax.broadcasted_iota(jnp.int32, tile.shape, 0)
        return jnp.sum(jnp.where(row == j - base, tile, np.float32(0)),
                       axis=0, keepdims=True)

    def kernel(first_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref):
        i = pl.program_id(0)
        key_head = head_of((block, keys), 1, block)
        # causal within a block: row r of the query block sees key r' <= r
        below = lax.rem(lax.broadcasted_iota(jnp.int32, (block, keys), 1),
                        i32(block)) <= lax.broadcasted_iota(
                            jnp.int32, (block, keys), 0)

        def fold(j, carry):
            start = pl.multiple_of(j * i32(block), block)
            ks_j, vs_j = scale_row(ks_ref, j), scale_row(vs_ref, j)
            valid = below | (j < i)             # the diagonal block's mask
            out = []
            for g, (o, m, l) in enumerate(carry):
                qg = q_ref[:, g * lanes:(g + 1) * lanes]
                kb = k_ref[pl.ds(start, block),
                           g * lanes:(g + 1) * lanes].astype(dt)
                vb = v_ref[pl.ds(start, block),
                           g * lanes:(g + 1) * lanes].astype(dt)
                s = lax.dot_general(
                    qg, stacked(kb), (((1,), (1,)), ((), ())),
                    preferred_element_type=f32) * sm_scale
                s = s * ks_j[:, g * keys:(g + 1) * keys]   # [block, keys]
                s = jnp.where(valid, s, np.float32(_NEG))
                m_new = [jnp.maximum(m[r], jnp.max(
                    jnp.where(key_head == r, s, np.float32(_NEG)),
                    axis=1, keepdims=True)) for r in range(gh)]
                p = jnp.where(valid, jnp.exp(s - spread(m_new, s.shape, block)),
                              np.float32(0))
                corr = [jnp.exp(m[r] - m_new[r]) for r in range(gh)]
                l_new = [l[r] * corr[r] + jnp.sum(
                    jnp.where(key_head == r, p, np.float32(0)),
                    axis=1, keepdims=True) for r in range(gh)]
                w = (p * vs_j[:, g * keys:(g + 1) * keys]).astype(dt)
                o = o * spread(corr, o.shape, hd) + jnp.dot(
                    w, stacked(vb), preferred_element_type=f32)
                out.append((o, m_new, l_new))
            return tuple(out)

        init = tuple(
            (jnp.zeros((block, lanes), f32),
             [jnp.full((block, 1), _NEG, f32)] * gh,
             [jnp.zeros((block, 1), f32)] * gh)
            for _ in range(width // lanes))
        carry = lax.fori_loop(first_ref[i], i + 1, fold, init)
        for g, (o, _m, l) in enumerate(carry):
            den = spread([jnp.maximum(x, np.float32(1e-30)) for x in l],
                         o.shape, hd)
            o_ref[:, g * lanes:(g + 1) * lanes] = (o / den).astype(
                o_ref.dtype)

    def whole(i, fb):
        return (i - i, i - i)

    def query_block(i, fb):
        return (i, i - i)

    resident = [pl.BlockSpec((T, width), whole)] * 2 \
        + [pl.BlockSpec(ks.shape, whole)] * 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(T // block,),
        in_specs=[pl.BlockSpec((block, width), query_block)] + resident,
        out_specs=pl.BlockSpec((block, width), query_block),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, width), dt),
        interpret=interpret,
        name="packed_attention",
    )(first_block, q, k, v, ks, vs)

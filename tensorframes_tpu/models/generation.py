"""Autoregressive decoding for the transformer family (causal LM).

The reference is inference-only over frozen graphs; its model ceiling is
one Session.run per block. A causal decoder is the workload that shows
why the TPU formulation matters: generation is a ``lax.scan`` over
single-token steps against a **static-shape KV cache**, so the whole
decode loop is ONE compiled XLA program — no per-token dispatch, no
dynamic shapes, cache updates as ``dynamic_update_slice`` in HBM.

Reuses the transformer parameter tree (transformer.init_params) with
``causal=True``; logits tie to the token embedding (no separate LM head).
``generate_program`` plugs batched generation into ``map_blocks`` like
any other program: a frame of prompt rows in, a column of continuations
out.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .transformer import TransformerConfig, _layer_norm, _mlp


def gpt_tiny(**kw) -> TransformerConfig:
    """A small causal config for tests/demos."""
    kw.setdefault("vocab_size", 97)
    kw.setdefault("hidden", 32)
    kw.setdefault("num_heads", 4)
    kw.setdefault("num_layers", 2)
    kw.setdefault("max_seq_len", 48)
    kw.setdefault("dtype", jnp.float32)
    kw.setdefault("causal", True)
    return TransformerConfig(**kw)


def gpt_small(**kw) -> TransformerConfig:
    """GPT-2-small-shaped causal config (bench workload)."""
    kw.setdefault("vocab_size", 32_000)
    kw.setdefault("hidden", 768)
    kw.setdefault("num_heads", 12)
    kw.setdefault("num_layers", 12)
    kw.setdefault("max_seq_len", 1024)
    kw.setdefault("causal", True)
    return TransformerConfig(**kw)


# ---------------------------------------------------------------------------
# KV cache
# ---------------------------------------------------------------------------

def init_kv_cache(
    cfg: TransformerConfig,
    batch: int,
    length: Optional[int] = None,
    quant: bool = False,
) -> Dict:
    """Static-shape cache: k/v per layer, [b, heads, length, head_dim].

    ``length`` defaults to ``cfg.max_seq_len`` but callers that know the
    exact decode horizon (prompt + new tokens — ``generate`` does) should
    pass it: cache HBM and per-step attention FLOPs scale with it.

    ``quant=True`` stores k/v as int8 with one f32 scale per cache slot
    (per layer/batch/head/position — absmax over head_dim): decode is
    HBM-bandwidth-bound and the cache is the per-step traffic that GROWS
    with sequence length, so int8 halves it vs a bf16 cache (4× vs f32)
    at a ~1.6% scale overhead (4 bytes per head_dim=64 slot). Reads
    dequantize inside the attention contractions — the scale commutes
    out of the score contraction and folds into the softmax weights for
    the context one (see ``_forward_cached``); no dequantized copy is
    materialized (VERDICT r3 #4)."""
    S = length or cfg.max_seq_len
    shape = (cfg.num_layers, batch, cfg.num_heads, S, cfg.head_dim)
    if quant:
        sshape = shape[:-1] + (1,)
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.ones(sshape, jnp.float32),
            "v_scale": jnp.ones(sshape, jnp.float32),
        }
    return {
        "k": jnp.zeros(shape, cfg.dtype),
        "v": jnp.zeros(shape, cfg.dtype),
    }


def kv_cache_nbytes(cache: Dict) -> int:
    """Total cache HBM footprint in bytes — the number int8 KV
    quantization exists to shrink."""
    return sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in cache.values())


def _quantize_slots(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric int8 per-slot quantization over the trailing head_dim:
    [b, nh, t, hd] → (int8 values, f32 scales [b, nh, t, 1]). One-line
    wrapper over the shared ``ops/quantize.quantize`` scheme (keeping
    every axis but head_dim as channel axes) so the zero-guard/rounding
    conventions cannot diverge from the weight path."""
    from ..ops.quantize import quantize

    qt = quantize(x.astype(jnp.float32), channel_axis=(0, 1, 2))
    return qt.q, qt.scale


def _forward_cached(
    cfg: TransformerConfig,
    params: Dict,
    tokens: jnp.ndarray,   # [b, t] chunk (prompt prefill or one decode step)
    cache: Dict,
    offset,                # scalar: positions [offset, offset+t) being written
) -> Tuple[jnp.ndarray, Dict]:
    """Run a chunk through the decoder, reading/writing the KV cache.

    Returns (hidden states [b, t, h], updated cache). Attention is dense
    over the cache's static horizon S = cache["k"].shape[3] (the decode
    horizon ``generate`` sizes it to, ≤ cfg.max_seq_len) with a validity
    mask (j <= offset + local position) — the standard static-shape
    decode formulation.
    """
    b, t = tokens.shape
    h, nh, hd = cfg.hidden, cfg.num_heads, cfg.head_dim
    S = cache["k"].shape[3]  # cache horizon (≤ cfg.max_seq_len)
    x = params["embed"]["tok"][tokens].astype(cfg.dtype)
    pos = offset + jnp.arange(t)
    x = x + params["embed"]["pos"][pos].astype(cfg.dtype)

    # mask [t, S]: chunk position i may attend cache slot j iff j <= offset+i
    valid = jnp.arange(S)[None, :] <= (offset + jnp.arange(t))[:, None]
    neg = jnp.asarray(-1e30, jnp.float32)

    from ..ops.quantize import matmul as _mm

    quant = "k_scale" in cache  # int8 cache (init_kv_cache(quant=True))
    new_cache = dict(cache)
    for li, p in enumerate(params["layers"]):
        y = _layer_norm(x, **p["ln1"])
        qkv = _mm(y, p["attn"]["qkv"]).reshape(b, t, 3, nh, hd)
        q = qkv[:, :, 0].transpose(0, 2, 1, 3)           # [b, nh, t, hd]
        k = qkv[:, :, 1].transpose(0, 2, 1, 3)
        v = qkv[:, :, 2].transpose(0, 2, 1, 3)
        if quant:
            k, k_s = _quantize_slots(k)
            v, v_s = _quantize_slots(v)
            for key, chunk in (("k_scale", k_s), ("v_scale", v_s)):
                new_cache[key] = lax.dynamic_update_slice(
                    new_cache[key], chunk[None], (li, 0, 0, offset, 0)
                )
        # ONE 5-D dynamic_update_slice per tensor (li is a static index
        # here, the loop is python): the previous slice-out → update →
        # .at[li].set chain rematerialized the whole [L,b,nh,S,hd]
        # cache per layer when XLA failed to prove in-place — the CPU
        # cost model charged ~24x the analytic step traffic for
        # gpt_small (dev/int8_breakeven.py); a single DUS on the full
        # array aliases reliably
        new_cache["k"] = lax.dynamic_update_slice(
            new_cache["k"], k[None], (li, 0, 0, offset, 0)
        )
        new_cache["v"] = lax.dynamic_update_slice(
            new_cache["v"], v[None], (li, 0, 0, offset, 0)
        )
        ck = new_cache["k"][li]  # li is static: a plain slice, no scatter
        cv = new_cache["v"][li]
        if quant:
            # int8 k/v stream from HBM and convert on-chip; each scale
            # is per cache SLOT (constant along the contracted head_dim
            # for scores, so it commutes out; for the context
            # contraction over s it folds into the softmax weights)
            ck_s = new_cache["k_scale"][li][..., 0]       # [b, nh, S]
            cv_s = new_cache["v_scale"][li][..., 0]
            ck = ck.astype(cfg.dtype)
            cv = cv.astype(cfg.dtype)
        # attend q against the whole (static) cache, masked to valid slots
        scores = jnp.einsum(
            "bntd,bnsd->bnts", q, ck, preferred_element_type=jnp.float32
        ) / float(np.sqrt(hd))
        if quant:
            scores = scores * ck_s[:, :, None, :]
        scores = jnp.where(valid[None, None], scores, neg)
        w = jax.nn.softmax(scores, axis=-1)
        if quant:
            w = (w * cv_s[:, :, None, :]).astype(cfg.dtype)
        else:
            w = w.astype(cfg.dtype)
        ctx = jnp.einsum("bnts,bnsd->bntd", w, cv)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(b, t, h)
        x = x + _mm(ctx, p["attn"]["out"])
        x = x + _mlp(p["mlp"], _layer_norm(x, **p["ln2"]))
    return _layer_norm(x, **params["final_ln"]), new_cache


def _logits(cfg: TransformerConfig, params: Dict, hs: jnp.ndarray) -> jnp.ndarray:
    """Weight-tied LM head: hidden [.., h] → logits [.., vocab] (f32)."""
    emb = params["embed"]["tok"].astype(jnp.float32)
    return hs.astype(jnp.float32) @ emb.T


# ---------------------------------------------------------------------------
# Generation
# ---------------------------------------------------------------------------

def generate(
    cfg: TransformerConfig,
    params: Dict,
    prompts: jnp.ndarray,   # [b, prompt_len] int tokens
    max_new_tokens: int,
    temperature: float = 0.0,
    seed: int = 0,
    kv_quant: bool = False,
) -> jnp.ndarray:
    """Generate ``max_new_tokens`` continuations. Greedy when
    ``temperature == 0``, else categorical sampling.

    Prefill runs the prompt as one chunk; the decode loop is a
    ``lax.scan`` of single-token cached steps — one XLA program end to
    end. Returns [b, max_new_tokens] int32. ``kv_quant=True`` keeps the
    KV cache int8 in HBM (see :func:`init_kv_cache`).
    """
    prompts = jnp.asarray(prompts)
    b, plen = prompts.shape
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    if plen + max_new_tokens > cfg.max_seq_len:
        raise ValueError(
            f"prompt_len({plen}) + max_new_tokens({max_new_tokens}) exceeds "
            f"max_seq_len({cfg.max_seq_len})"
        )
    # size the cache to the actual decode horizon: HBM and per-step
    # attention FLOPs scale with it, and both lengths are static here
    cache = init_kv_cache(cfg, b, length=plen + max_new_tokens, quant=kv_quant)
    hs, cache = _forward_cached(cfg, params, prompts, cache, 0)
    first = _pick(cfg, params, hs[:, -1], temperature, jax.random.PRNGKey(seed))

    def step(carry, rng):
        tok, pos, cache = carry
        hs, cache = _forward_cached(cfg, params, tok[:, None], cache, pos)
        nxt = _pick(cfg, params, hs[:, -1], temperature, rng)
        return (nxt, pos + 1, cache), nxt

    rngs = jax.random.split(jax.random.PRNGKey(seed + 1), max_new_tokens - 1)
    (_, _, _), rest = lax.scan(step, (first, plen, cache), rngs)
    return jnp.concatenate([first[:, None], rest.T], axis=1).astype(jnp.int32)


def _pick(cfg, params, h_last, temperature, rng):
    logits = _logits(cfg, params, h_last)
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(rng, logits / temperature, axis=-1).astype(
        jnp.int32
    )


def generate_naive(
    cfg: TransformerConfig,
    params: Dict,
    prompts: jnp.ndarray,
    max_new_tokens: int,
) -> jnp.ndarray:
    """Cache-free greedy reference: re-run the full forward per token.

    O(n²) per token — exists as the correctness oracle for the cached
    path (tests assert identical outputs), mirroring the reference's
    slow-but-obviously-correct execution stance (DebugRowOps.scala:277-280).
    """
    from . import transformer as tr

    toks = jnp.asarray(prompts)
    for _ in range(max_new_tokens):
        hs = tr.forward(cfg, params, toks)
        nxt = jnp.argmax(_logits(cfg, params, hs[:, -1]), axis=-1)
        toks = jnp.concatenate([toks, nxt[:, None].astype(toks.dtype)], axis=1)
    return toks[:, prompts.shape[1]:].astype(jnp.int32)


# ---------------------------------------------------------------------------
# Paged KV: the pool layout + step functions the serving decode engine
# (serving/decode.py) runs through aot_jit. Same int8-KV scheme as
# init_kv_cache(quant=True) — int8 k/v with one f32 scale per cache slot
# — but laid out page-major so a pool of fixed-size pages can be shared
# by many sequences through per-sequence page tables (vLLM-style paged
# attention, ISSUE 11).
#
# One physical layout (PR 26): a column's row-major default layout is
# the layout it is resident in, the layout every KV write scatters into
# and the layout the attention kernel reads, and every program that
# returns the pool takes it DONATED (serving/decode.py), so a write
# touches the rows it writes and nothing else. No program here may
# reshape, transpose or slice a whole column: that is a pool-sized copy
# per layer (what the earlier [pages, layers, heads, page, head_dim]
# shape cost; tests/test_decode_compile.py holds the compiled step to
# zero such copies).
# ---------------------------------------------------------------------------

def init_paged_kv(
    cfg: TransformerConfig, num_pages: int, page_size: int
) -> Dict[str, jnp.ndarray]:
    """The paged int8 KV pool as columnar state, page-major: ``k``/``v``
    int8 ``[num_pages, layers, page_size, heads*head_dim]`` (a position's
    heads side by side in one row) and ``k_scale``/``v_scale`` float32
    ``[num_pages, layers, page_size, SCALE_LANES]`` (head ``h``'s
    per-position scale in lane ``h``; the lanes past ``heads`` are
    padding, there so that a page's scale rows are whole (8, 128)
    tiles: 8 KB per page and layer beside 12 KB of int8 at GPT-2-small
    widths). Each array is one frame column with pages as rows
    (``serving.kvpool.PagedKVPool.as_frame``). Page 0 is the reserved
    NULL page: padding slots and masked prefill positions write there,
    and attention masks guarantee it is never read unmasked, so its
    garbage contents cannot reach any output."""
    from ..kernels.decode_attention import SCALE_LANES

    if num_pages < 2:
        raise ValueError(
            f"num_pages must be >= 2 (page 0 is the reserved null "
            f"page), got {num_pages}"
        )
    if page_size < 1:
        raise ValueError(f"page_size must be >= 1, got {page_size}")
    if cfg.num_heads > SCALE_LANES:
        raise ValueError(
            f"the paged pool holds one scale lane per head: num_heads="
            f"{cfg.num_heads} exceeds {SCALE_LANES}"
        )
    shape = (num_pages, cfg.num_layers, page_size,
             cfg.num_heads * cfg.head_dim)
    sshape = shape[:-1] + (SCALE_LANES,)
    return {
        "k": jnp.zeros(shape, jnp.int8),
        "v": jnp.zeros(shape, jnp.int8),
        "k_scale": jnp.ones(sshape, jnp.float32),
        "v_scale": jnp.ones(sshape, jnp.float32),
    }


def _pool_rows(xq: jnp.ndarray, xs: jnp.ndarray):
    """Arrange quantized positions as pool rows: int8 ``[N, heads,
    head_dim]`` → ``[N, heads*head_dim]`` and scales ``[N, heads]`` →
    ``[N, SCALE_LANES]`` (padding lanes 1.0, read by no output)."""
    from ..kernels.decode_attention import SCALE_LANES

    n, nh, hd = xq.shape
    return xq.reshape(n, nh * hd), jnp.pad(
        xs, ((0, 0), (0, SCALE_LANES - nh)), constant_values=1.0
    )


def _write_kv(pool, li: int, pg, off, k_rows, v_rows) -> None:
    """Scatter one layer's new positions into ``pool`` (a dict, updated
    in place) at (page ``pg``, offset ``off``) pairs: ONE scatter per
    column, of whole rows of the resident layout — in place when the
    pool was donated. ``k_rows``/``v_rows`` are :func:`_pool_rows`
    pairs."""
    for name, (rows, srows) in (("k", k_rows), ("v", v_rows)):
        pool[name] = pool[name].at[pg, li, off].set(rows)
        pool[name + "_scale"] = pool[name + "_scale"].at[
            pg, li, off].set(srows)


def _write_kv_pages(pool, li: int, pg, page_size: int, k_rows,
                    v_rows) -> None:
    """:func:`_write_kv` a whole page at a time: the rows of
    ``k_rows``/``v_rows`` in runs of ``page_size``, run ``i`` to page
    ``pg[i]`` — one scatter update a page where :func:`_write_kv` makes
    one a row."""
    for name, (rows, srows) in (("k", k_rows), ("v", v_rows)):
        for col, x in ((name, rows), (name + "_scale", srows)):
            pool[col] = pool[col].at[pg, li].set(
                x.reshape(-1, page_size, x.shape[-1]))


def paged_kv_nbytes(pool: Dict[str, jnp.ndarray]) -> int:
    """Pool HBM footprint in bytes (the budget eviction exists to honor)."""
    return sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a in pool.values())


def paged_prefill_fn(cfg: TransformerConfig, page_size: int,
                     max_pages: int):
    """Build the prefill step for one sequence: ``fn(params, pool,
    tokens[T], length, table[max_pages]) -> (pool, first_token)``.

    ``tokens`` is the prompt padded to a ladder bucket T; ``length`` is
    the true prompt length (a traced int32 scalar — one executable per
    T bucket serves every prompt length in it). Writes positions
    ``[0, length)`` into the sequence's pages through ``table`` (padding
    positions route to the null page), attends causally within the
    chunk over the QUANTIZED k/v — exactly the values decode steps will
    read back from the pool — and returns the first generated token
    (greedy argmax at position ``length - 1``).
    """

    def prefill(params, pool, tokens, length, table):
        from ..ops.quantize import matmul as _mm

        (T,) = tokens.shape
        h, nh, hd = cfg.hidden, cfg.num_heads, cfg.head_dim
        tpos = jnp.arange(T)
        with jax.named_scope("embed"):
            x = params["embed"]["tok"][tokens].astype(cfg.dtype)
            x = x + params["embed"]["pos"][tpos].astype(cfg.dtype)
        valid = tpos < length                       # real prompt slots
        # per-position pool coordinates; masked positions → null page 0
        pg = jnp.where(valid, table[jnp.minimum(tpos // page_size,
                                                max_pages - 1)], 0)
        off = tpos % page_size
        causal = tpos[None, :] <= tpos[:, None]     # [T, T]
        neg = jnp.asarray(-1e30, jnp.float32)
        pool = dict(pool)
        for li, p in enumerate(params["layers"]):
            with jax.named_scope(f"layer_{li}/attn"):
                y = _layer_norm(x, **p["ln1"])
                qkv = _mm(y, p["attn"]["qkv"]).reshape(T, 3, nh, hd)
                q = qkv[:, 0].transpose(1, 0, 2)        # [nh, T, hd]
                k = qkv[:, 1].transpose(1, 0, 2)
                v = qkv[:, 2].transpose(1, 0, 2)
            with jax.named_scope(f"layer_{li}/kv_write"):
                kq, ks = _quantize_slots(k[None])       # [1, nh, T, hd]
                vq, vs = _quantize_slots(v[None])
                kq, ks, vq, vs = kq[0], ks[0], vq[0], vs[0]
                _write_kv(
                    pool, li, pg, off,
                    _pool_rows(kq.transpose(1, 0, 2), ks[..., 0].T),
                    _pool_rows(vq.transpose(1, 0, 2), vs[..., 0].T),
                )
            with jax.named_scope(f"layer_{li}/attn"):
                # attend within the chunk over the quantized k/v — the
                # same dequantize-commutes formulation as
                # _forward_cached, so prefill sees exactly what the pool
                # now holds
                kd = kq.astype(cfg.dtype)
                scores = jnp.einsum(
                    "ntd,nsd->nts", q, kd,
                    preferred_element_type=jnp.float32,
                ) / float(np.sqrt(hd))
                scores = scores * ks[..., 0][:, None, :]
                scores = jnp.where(causal[None], scores, neg)
                w = jax.nn.softmax(scores, axis=-1)
                w = (w * vs[..., 0][:, None, :]).astype(cfg.dtype)
                ctx = jnp.einsum("nts,nsd->ntd", w, vq.astype(cfg.dtype))
                ctx = ctx.transpose(1, 0, 2).reshape(T, h)
                x = x + _mm(ctx, p["attn"]["out"])
            with jax.named_scope(f"layer_{li}/mlp"):
                x = x + _mlp(p["mlp"], _layer_norm(x, **p["ln2"]))
        with jax.named_scope("head"):
            hs = _layer_norm(x, **params["final_ln"])
            last = jnp.take(hs, length - 1, axis=0)
            first = jnp.argmax(
                _logits(cfg, params, last), axis=-1
            ).astype(jnp.int32)
        return pool, first

    return prefill


#: rows of the packed prefill's attention block: every packed prompt
#: starts on a multiple of it
PACK_BLOCK = 64


def paged_packed_prefill_fn(cfg: TransformerConfig, page_size: int,
                            max_pages: int, block: int = PACK_BLOCK):
    """Build the prefill of several prompts at once: ``fn(params, pool,
    tokens[T], seg_start[B], seg_len[B], tables[B, max_pages]) -> (pool,
    first_tokens[B])``.

    Segment ``b`` is one prompt at rows ``[seg_start[b], seg_start[b] +
    seg_len[b])`` of ``tokens``; every ``seg_start`` is a multiple of
    ``block`` and ``T`` is too. A segment's positions count from its own
    start: they index the position embedding, place its KV writes
    through ``tables[b]`` (the same int8 quantization and scatter as
    :func:`paged_prefill_fn`) and order its causal attention, which
    folds the segment's own blocks only
    (``ops.attention.blockwise_attention`` with ``first_block``). Rows
    outside every segment and empty segments (``seg_len`` 0) write the
    null page. ``first_tokens[b]`` is the greedy argmax at the segment's
    last row; the head runs on those ``B`` rows alone. A prompt gives
    the same bits wherever it sits and whatever is packed beside it.
    """

    def packed_prefill(params, pool, tokens, seg_start, seg_len, tables):
        from ..ops.attention import blockwise_attention
        from ..ops.quantize import matmul as _mm

        (T,) = tokens.shape
        if T % block or block % page_size:
            raise ValueError(f"{T} packed rows are no whole number of "
                             f"{block}-row blocks of {page_size}-row pages")
        h, nh, hd = cfg.hidden, cfg.num_heads, cfg.head_dim
        rows = jnp.arange(T)
        seg_end = seg_start + seg_len
        inside = ((rows[:, None] >= seg_start[None, :])
                  & (rows[:, None] < seg_end[None, :]))     # [T, B]
        valid = inside.any(axis=1)
        seg = jnp.argmax(inside, axis=1)
        rel = jnp.where(valid, rows - seg_start[seg], 0)    # segment position
        with jax.named_scope("embed"):
            x = params["embed"]["tok"][tokens].astype(cfg.dtype)
            x = x + params["embed"]["pos"][rel].astype(cfg.dtype)
        # KV goes to the pool a page at a time: a segment starts on a
        # block edge and a block is whole pages, so each run of page_size
        # rows is one page of one segment (past the prompt its rows hold
        # padding until decode steps write them); a run of no segment
        # writes the null page
        run = rows[::page_size]
        pg = jnp.where(valid[run], tables[
            seg[run], jnp.minimum(rel[run] // page_size, max_pages - 1)], 0)
        # a block folds from its segment's first block; a block of no
        # segment folds itself alone
        heads = rows[::block]
        first_block = jnp.where(valid[heads], seg_start[seg[heads]] // block,
                                heads // block)
        pool = dict(pool)
        for li, p in enumerate(params["layers"]):
            with jax.named_scope(f"layer_{li}/attn"):
                y = _layer_norm(x, **p["ln1"])
                qkv = _mm(y, p["attn"]["qkv"]).reshape(T, 3, nh, hd)
                q = qkv[:, 0].transpose(1, 0, 2)        # [nh, T, hd]
                k = qkv[:, 1].transpose(1, 0, 2)
                v = qkv[:, 2].transpose(1, 0, 2)
            with jax.named_scope(f"layer_{li}/kv_write"):
                kq, ks = _quantize_slots(k[None])       # [1, nh, T, hd]
                vq, vs = _quantize_slots(v[None])
                # the int8 rows and scales made once, read by the page
                # write and the attention alike: fused into each, the
                # quantization kept every layer's float32 k and v live
                # until the writes (compiled for a v5e at the 4,096-row
                # bucket: 0.50 GB of temporaries over the XLA fold and
                # 0.70 over the kernel; made once, 0.15 and 0.14)
                kq, ks, vq, vs = lax.optimization_barrier((kq, ks, vq, vs))
                _write_kv_pages(
                    pool, li, pg, page_size,
                    _pool_rows(kq[0].transpose(1, 0, 2), ks[0, ..., 0].T),
                    _pool_rows(vq[0].transpose(1, 0, 2), vs[0, ..., 0].T),
                )
            with jax.named_scope(f"layer_{li}/attn"):
                ctx = blockwise_attention(
                    q[None], kq, vq, causal=True, block_size=block,
                    k_scale=ks[..., 0], v_scale=vs[..., 0],
                    first_block=first_block,
                )[0].transpose(1, 0, 2).reshape(T, h)
                x = x + _mm(ctx, p["attn"]["out"])
            with jax.named_scope(f"layer_{li}/mlp"):
                x = x + _mlp(p["mlp"], _layer_norm(x, **p["ln2"]))
        with jax.named_scope("head"):
            last = x[jnp.clip(seg_end - 1, 0, T - 1)]   # [B, h]
            hs = _layer_norm(last, **params["final_ln"])
            first = jnp.argmax(
                _logits(cfg, params, hs), axis=-1
            ).astype(jnp.int32)
        return pool, first

    return packed_prefill


def paged_suffix_prefill_fn(cfg: TransformerConfig, page_size: int,
                            max_pages: int):
    """Build the prefix-cache suffix prefill: ``fn(params, pool,
    tokens[T], start, length, table[max_pages]) -> (pool, first)``.

    The prefix-cache join path (serving/decode.py): positions
    ``[0, start)`` are already resident in the sequence's pages (shared
    pages matched by content hash), so only the suffix ``tokens[:length]``
    is processed — written at positions ``[start, start + length)``
    through ``table`` and attended against the WHOLE sequence via the
    paged gather (`paged_attention_reference`, the same dequantize-
    commutes chain decode steps read through, so a cache-hit join emits
    exactly the tokens full prefill + decode would). ``start`` and
    ``length`` are traced int32 scalars — one executable per suffix
    bucket T serves every (start, length) in it; ``start=0`` degrades
    to a full prefill through the gather chain.
    """

    def suffix_prefill(params, pool, tokens, start, length, table):
        from ..kernels.decode_attention import paged_attention_reference
        from ..ops.quantize import matmul as _mm

        (T,) = tokens.shape
        h, nh, hd = cfg.hidden, cfg.num_heads, cfg.head_dim
        tpos = jnp.arange(T)
        valid = tpos < length
        seqpos = start + tpos                   # absolute KV positions
        emb_pos = jnp.minimum(
            seqpos, params["embed"]["pos"].shape[0] - 1
        )
        with jax.named_scope("embed"):
            x = params["embed"]["tok"][tokens].astype(cfg.dtype)
            x = x + params["embed"]["pos"][emb_pos].astype(cfg.dtype)
        pg = jnp.where(
            valid,
            table[jnp.minimum(seqpos // page_size, max_pages - 1)], 0,
        )
        off = seqpos % page_size
        # per-row gather coordinates: each suffix position attends the
        # sequence's own pages masked to j <= its absolute position;
        # padding rows carry null tables and position 0
        tables_r = jnp.where(valid[:, None], table[None, :], 0)
        pos_r = jnp.where(valid, seqpos, 0)
        pool = dict(pool)
        for li, p in enumerate(params["layers"]):
            with jax.named_scope(f"layer_{li}/attn"):
                y = _layer_norm(x, **p["ln1"])
                qkv = _mm(y, p["attn"]["qkv"]).reshape(T, 3, nh, hd)
                q = qkv[:, 0]                       # [T, nh, hd]
                k = qkv[:, 1]
                v = qkv[:, 2]
            with jax.named_scope(f"layer_{li}/kv_write"):
                kq, ks = _quantize_slots(k[:, :, None, :])
                vq, vs = _quantize_slots(v[:, :, None, :])
                kq, ks = kq[:, :, 0], ks[:, :, 0]
                vq, vs = vq[:, :, 0], vs[:, :, 0]
                # write first, then gather-attend — row i sees positions
                # 0..start+i including its own token, the decode-step
                # order
                _write_kv(
                    pool, li, pg, off,
                    _pool_rows(kq, ks[..., 0]), _pool_rows(vq, vs[..., 0]),
                )
            with jax.named_scope(f"layer_{li}/attn"):
                ctx = paged_attention_reference(
                    q, pool["k"], pool["v"],
                    pool["k_scale"], pool["v_scale"],
                    li, tables_r, pos_r,
                ).reshape(T, h)
                x = x + _mm(ctx, p["attn"]["out"])
            with jax.named_scope(f"layer_{li}/mlp"):
                x = x + _mlp(p["mlp"], _layer_norm(x, **p["ln2"]))
        with jax.named_scope("head"):
            hs = _layer_norm(x, **params["final_ln"])
            last = jnp.take(hs, length - 1, axis=0)
            first = jnp.argmax(
                _logits(cfg, params, last), axis=-1
            ).astype(jnp.int32)
        return pool, first

    return suffix_prefill


def paged_page_ops_fns(max_pages: int):
    """Build the page-granular pool maintenance steps the KV memory
    hierarchy dispatches (serving/decode.py, ISSUE 19) — all shapes
    fixed, so each is ONE warmable executable:

    * ``extract(pool, idx[max_pages]) -> {col: [max_pages, ...]}`` —
      gather a sequence's pages out of the pool (host-swap-out reads
      this, then trims to the real page count; padding entries gather
      the null page and are discarded).
    * ``restore(pool, idx[max_pages], k, v, k_scale, v_scale) -> pool``
      — scatter swapped-in page payloads back (padding entries target
      the null page, whose contents are garbage by contract).
    * ``copy_page(pool, src, dst) -> pool`` — duplicate one page
      (copy-on-extend: a ragged-tail prefix-cache hit copies the shared
      page before writing into it).

    ``restore`` and ``copy_page`` return the pool: the engine jits them
    with it donated, so they touch the pages they name and no others.
    """

    def extract(pool, idx):
        return {name: col[idx] for name, col in pool.items()}

    def restore(pool, idx, k, v, k_scale, v_scale):
        pool = dict(pool)
        pool["k"] = pool["k"].at[idx].set(k)
        pool["v"] = pool["v"].at[idx].set(v)
        pool["k_scale"] = pool["k_scale"].at[idx].set(k_scale)
        pool["v_scale"] = pool["v_scale"].at[idx].set(v_scale)
        return pool

    def copy_page(pool, src, dst):
        pool = dict(pool)
        for name in ("k", "v", "k_scale", "v_scale"):
            pool[name] = pool[name].at[dst].set(pool[name][src])
        return pool

    return extract, restore, copy_page


def paged_decode_step_fn(cfg: TransformerConfig, page_size: int,
                         max_pages: int):
    """Build the batched decode step: ``fn(params, pool, tokens[S],
    pos[S], tables[S, max_pages]) -> (pool, next_tokens[S])``.

    One token per running slot: writes each slot's new k/v into its
    current page (padding slots carry all-null tables and write into
    the null page), gathers each slot's pages back as a contiguous
    ``[S, heads, max_pages*page_size, head_dim]`` context (the paged KV
    gather), and attends masked to ``j <= pos``. Every slot's row is
    computed independently (the map_rows/vmap convention), which is
    what makes a batched step bit-identical per slot to a solo step —
    the serving bench hard-gates it. The engine jits the step with
    ``pool`` donated: the writes are scatters of whole rows into the
    resident columns, in place.

    The attention itself is
    :func:`tensorframes_tpu.ops.attention.paged_decode_attention`: the
    fused paged int8-KV pallas kernel where the backend can run it
    (``kernels.selectable("decode_attn")``, asked at trace time), the
    gather→dequant→attend chain elsewhere. Batched and solo steps of
    one process trace the same one, so the bit-identity gates hold on
    either.
    """

    def step(params, pool, tokens, pos, tables):
        from ..ops.attention import paged_decode_attention as _paged_attn
        from ..ops.quantize import matmul as _mm

        (S,) = tokens.shape
        h, nh, hd = cfg.hidden, cfg.num_heads, cfg.head_dim
        with jax.named_scope("embed"):
            x = params["embed"]["tok"][tokens].astype(cfg.dtype)
            x = x + params["embed"]["pos"][pos].astype(cfg.dtype)
        wpg = jnp.take_along_axis(
            tables, jnp.minimum(pos // page_size, max_pages - 1)[:, None],
            axis=1,
        )[:, 0]                                     # [S] write page
        woff = pos % page_size
        pool = dict(pool)
        for li, p in enumerate(params["layers"]):
            # scopes are trace-time names on the ops (layer_<i>/attn,
            # /kv_write, /mlp): what a profile calls them, no run cost
            with jax.named_scope(f"layer_{li}/attn"):
                y = _layer_norm(x, **p["ln1"])
                qkv = _mm(y, p["attn"]["qkv"]).reshape(S, 3, nh, hd)
                q = qkv[:, 0]                           # [S, nh, hd]
                k = qkv[:, 1]
                v = qkv[:, 2]
            with jax.named_scope(f"layer_{li}/kv_write"):
                kq, ks = _quantize_slots(k[:, :, None, :])  # [S, nh, 1, hd]
                vq, vs = _quantize_slots(v[:, :, None, :])
                kq, ks = kq[:, :, 0], ks[:, :, 0]       # [S, nh, hd/1]
                vq, vs = vq[:, :, 0], vs[:, :, 0]
                _write_kv(
                    pool, li, wpg, woff,
                    _pool_rows(kq, ks[..., 0]), _pool_rows(vq, vs[..., 0]),
                )
            # paged attention, after the write above so slot j attends
            # its own current token; ops.attention picks the kernel or
            # the XLA chain from kernels.selectable at trace time
            with jax.named_scope(f"layer_{li}/attn"):
                ctx = _paged_attn(
                    q, pool["k"], pool["v"],
                    pool["k_scale"], pool["v_scale"],
                    li, tables, pos,
                ).reshape(S, h)
            with jax.named_scope(f"layer_{li}/attn"):
                x = x + _mm(ctx, p["attn"]["out"])
            with jax.named_scope(f"layer_{li}/mlp"):
                x = x + _mlp(p["mlp"], _layer_norm(x, **p["ln2"]))
        with jax.named_scope("head"):
            hs = _layer_norm(x, **params["final_ln"])
            nxt = jnp.argmax(
                _logits(cfg, params, hs), axis=-1
            ).astype(jnp.int32)
        return pool, nxt

    return step


def served_model(cfg: TransformerConfig, page_size: int, horizon: int):
    """The transformer family as the decode engine takes it
    (``models/served.py``): one page kind holding every layer, a page
    per ``page_size`` positions of context, and every optional program
    (the packed prefill's prompts start on 64-row blocks)."""
    from .served import PageKind, ServedModel

    max_pages = -(-int(horizon) // int(page_size))
    # a packed prompt starts on a block edge that is a page edge too
    block = math.lcm(PACK_BLOCK, int(page_size))
    return ServedModel(
        vocab_size=int(cfg.vocab_size),
        max_seq_len=int(cfg.max_seq_len),
        kinds=(PageKind(
            "kv", max_pages,
            lambda num_pages: init_paged_kv(cfg, num_pages, page_size)),),
        prefill=paged_prefill_fn(cfg, page_size, max_pages),
        step=paged_decode_step_fn(cfg, page_size, max_pages),
        suffix_prefill=paged_suffix_prefill_fn(cfg, page_size, max_pages),
        page_ops=paged_page_ops_fns(max_pages),
        packed_prefill=paged_packed_prefill_fn(cfg, page_size, max_pages,
                                               block),
        pack_block=block,
    )


def generate_program(
    cfg: TransformerConfig,
    params: Dict,
    max_new_tokens: int,
    temperature: float = 0.0,
    seed: int = 0,
    kv_quant: bool = False,
):
    """map_blocks program: prompt block [n, plen] → {"generated": [n, new]}.

    When sampling (``temperature > 0``), a content-derived salt folds
    into the seed so different blocks of a multi-block frame draw
    different noise (a pure program cannot see its block index — identical
    blocks still sample identically, which is at least deterministic)."""

    def program(prompts):
        salt = (
            prompts.astype(jnp.uint32).sum() if temperature > 0.0 else 0
        )
        return {
            "generated": generate(
                cfg, params, prompts, max_new_tokens, temperature,
                seed + salt, kv_quant=kv_quant,
            )
        }

    return program

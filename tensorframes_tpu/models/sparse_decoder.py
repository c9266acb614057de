"""A served decoder block with sparse experts, grouped-query rotary
attention and window layers: what ``TransformerConfig`` cannot express.

Per layer ``l`` of kind ``layer_types[l]`` (``"full"`` or ``"sliding"``):
RMSNorm; ``q`` / ``k`` / ``v`` projections without bias (``num_heads``
query heads and ``num_kv_heads`` KV heads of an explicit ``head_dim``);
rotary positions over the whole head in the halves convention, with one
parameter set per kind (:class:`RopeSpec`: plain, or YaRN-scaled with
its attention factor on cos and sin); attention of query head ``j`` over
KV head ``j // group``, causal on a full layer and over the last
``sliding_window`` positions on a sliding one; RMSNorm; a router over
all experts (softmax in float32, top-k, renormalised) and the gated-SiLU
experts, all of them on this chip (``models/moe.routed_experts``: no
token dropped, grouped matmuls; it can compute a range of the experts,
but nothing here exchanges the shares across chips yet). After the last layer an RMSNorm and an untied
head. Weights and activations are ``dtype`` (bfloat16); the RMSNorm
statistics, the rotary tables, the router's softmax and top-k and the
attention's running maximum and sum are float32.

It is served through the same seam as the transformer family
(``models/served.py``) with TWO page kinds: ``full`` pages hold the full
layers' keys and values and a sequence holds one per ``page_size``
positions of context; ``window`` pages hold the sliding layers' and are
a ring of ``ceil(window / page_size) + 1`` table entries, position ``p``
writing entry ``(p // page_size) % entries``, so a sequence never holds
more of them however long it grows. Both are int8 with one float32 scale
a KV head and position, laid out as ``models/generation.init_paged_kv``
lays them (the scale of query head ``j``'s KV head in lane ``j``), and
read by the same attention entry point
(``ops.attention.paged_decode_attention``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .generation import _quantize_slots, _write_kv
from .moe import expert_counts, route_topk, routed_experts
from .served import PageKind, ServedModel

__all__ = ["RopeSpec", "SparseDecoderConfig", "rope_inv_freq", "tiny"]


@dataclasses.dataclass(frozen=True)
class RopeSpec:
    """One rotary parameter set. ``factor`` set means YaRN: the
    frequencies between the ``beta_fast`` and ``beta_slow`` rotations
    over ``original_max`` positions are blended towards ``1 / factor``
    of themselves, and cos and sin are multiplied by
    ``attention_factor``."""
    theta: float
    factor: Optional[float] = None
    original_max: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


def rope_inv_freq(spec: RopeSpec, head_dim: int) -> np.ndarray:
    """The ``head_dim // 2`` rotary frequencies of ``spec``, float64."""
    half = head_dim // 2
    plain = spec.theta ** (-2.0 * np.arange(half) / head_dim)
    if spec.factor is None:
        return plain

    def dim_of(rotations: float) -> float:
        return head_dim * math.log(
            spec.original_max / (2 * math.pi * rotations)
        ) / (2 * math.log(spec.theta))

    lo = max(math.floor(dim_of(spec.beta_fast)), 0)
    hi = min(math.ceil(dim_of(spec.beta_slow)), head_dim - 1)
    ramp = np.clip((np.arange(half) - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return (1.0 - ramp) * plain + ramp * plain / spec.factor


@dataclasses.dataclass(frozen=True)
class SparseDecoderConfig:
    vocab_size: int
    hidden: int
    layer_types: Tuple[str, ...]          # "sliding" | "full", per layer
    num_heads: int
    num_kv_heads: int
    head_dim: int
    sliding_window: int
    rope_full: RopeSpec
    rope_sliding: RopeSpec
    num_experts: int
    experts_per_token: int
    expert_hidden: int
    norm_topk_prob: bool = True
    rms_eps: float = 1e-6
    max_seq_len: int = 8192
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        bad = set(self.layer_types) - {"sliding", "full"}
        if bad or not self.layer_types:
            raise ValueError(f"layer_types holds {sorted(bad)}; each is "
                             "'sliding' or 'full', and there is one")
        if self.num_heads % self.num_kv_heads or self.head_dim % 2:
            raise ValueError(
                f"{self.num_heads} query heads over {self.num_kv_heads} KV "
                f"heads of {self.head_dim}: the first a multiple of the "
                "second, the head size even")

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    def kind_layers(self, kind: str) -> Tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.layer_types) if t == kind)

    def window_entries(self, page_size: int) -> int:
        """Table entries of the window kind's ring: the pages a window
        can straddle."""
        return -(-self.sliding_window // int(page_size)) + 1

    def served_model(self, page_size: int, horizon: int) -> ServedModel:
        return served_model(self, page_size, horizon)


def tiny(**kw) -> SparseDecoderConfig:
    """A small configuration for tests: two periods of (sliding,
    sliding, sliding, full), YaRN over 16 positions on the full layers."""
    kw.setdefault("vocab_size", 97)
    kw.setdefault("hidden", 64)
    kw.setdefault("layer_types",
                  ("sliding", "sliding", "sliding", "full"))
    kw.setdefault("num_heads", 4)
    kw.setdefault("num_kv_heads", 2)
    kw.setdefault("head_dim", 16)
    kw.setdefault("sliding_window", 8)
    kw.setdefault("rope_full", RopeSpec(
        10000.0, factor=4.0, original_max=16,
        attention_factor=0.1 * math.log(4.0) + 1.0))
    kw.setdefault("rope_sliding", RopeSpec(10000.0))
    kw.setdefault("num_experts", 8)
    kw.setdefault("experts_per_token", 2)
    kw.setdefault("expert_hidden", 32)
    kw.setdefault("max_seq_len", 128)
    kw.setdefault("dtype", jnp.float32)
    return SparseDecoderConfig(**kw)


def layer_shapes(cfg: SparseDecoderConfig) -> Dict[str, Tuple[int, ...]]:
    """One layer's parameter tree, as shapes (every layer has the same)."""
    d, hd = cfg.hidden, cfg.head_dim
    held = cfg.num_experts
    return {
        "norm1": (d,), "norm2": (d,),
        "wq": (d, cfg.num_heads * hd), "wk": (d, cfg.num_kv_heads * hd),
        "wv": (d, cfg.num_kv_heads * hd), "wo": (cfg.num_heads * hd, d),
        "router": (d, cfg.num_experts),
        "w_gate": (held, d, cfg.expert_hidden),
        "w_up": (held, d, cfg.expert_hidden),
        "w_down": (held, cfg.expert_hidden, d),
    }


# -- the block's pieces --------------------------------------------------

def _rms_norm(x, gain, eps: float):
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * gain).astype(x.dtype)


def _rope_tables(spec: RopeSpec, head_dim: int, positions):
    """cos and sin ``[n, head_dim]`` float32 at ``positions`` [n]."""
    inv = jnp.asarray(rope_inv_freq(spec, head_dim), jnp.float32)
    angle = positions.astype(jnp.float32)[:, None] * inv[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)
    scale = float(spec.attention_factor)
    return jnp.cos(angle) * scale, jnp.sin(angle) * scale


def _rotate(x, cos, sin):
    """Rotary on ``x`` [n, heads, head_dim], halves convention."""
    xf = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-xf[..., half:], xf[..., :half]], axis=-1)
    return (xf * cos[:, None, :] + turned * sin[:, None, :]).astype(x.dtype)


def _qkv(cfg: SparseDecoderConfig, p, x, cos, sin):
    """``x`` [n, hidden] → rotated ``q`` [n, heads, hd], ``k``, and ``v``
    [n, kv_heads, hd]."""
    n = x.shape[0]
    h = _rms_norm(x, p["norm1"], cfg.rms_eps)
    q = (h @ p["wq"]).reshape(n, cfg.num_heads, cfg.head_dim)
    k = (h @ p["wk"]).reshape(n, cfg.num_kv_heads, cfg.head_dim)
    v = (h @ p["wv"]).reshape(n, cfg.num_kv_heads, cfg.head_dim)
    return _rotate(q, cos, sin), _rotate(k, cos, sin), v


def _quantized_rows(cfg: SparseDecoderConfig, x):
    """``x`` [n, kv_heads, hd] → int8 values [n, kv_heads, hd], scales
    [n, kv_heads], and the pool rows: ``[n, kv_heads*hd]`` int8 and
    ``[n, SCALE_LANES]`` float32 with the scale of query head ``j``'s KV
    head in lane ``j`` (padding lanes 1.0), so that the attention reads a
    scale per query head where it lies."""
    from ..kernels.decode_attention import SCALE_LANES

    xq, xs = _quantize_slots(x[:, :, None, :])
    xq, xs = xq[:, :, 0], xs[:, :, 0, 0]
    n = x.shape[0]
    lanes = jnp.repeat(xs, cfg.num_heads // cfg.num_kv_heads, axis=1)
    lanes = jnp.pad(lanes, ((0, 0), (0, SCALE_LANES - cfg.num_heads)),
                    constant_values=1.0)
    return xq, xs, (xq.reshape(n, -1), lanes)


def _experts(cfg: SparseDecoderConfig, p, li: int, x):
    """The expert layer's residual update, and each row's experts."""
    with jax.named_scope(f"layer_{li}/moe_route"):
        h = _rms_norm(x, p["norm2"], cfg.rms_eps)
        experts, weights = route_topk(
            h, p["router"], cfg.experts_per_token, cfg.norm_topk_prob)
    with jax.named_scope(f"layer_{li}/moe_experts"):
        y = routed_experts(h, experts, weights, p["w_gate"], p["w_up"],
                           p["w_down"])
    return x + y.astype(x.dtype), experts


def _head(cfg: SparseDecoderConfig, params, x):
    """Greedy next token of each row of ``x`` [n, hidden]."""
    with jax.named_scope("head"):
        h = _rms_norm(x, params["final_norm"], cfg.rms_eps)
        logits = jnp.matmul(h, params["head"],
                            preferred_element_type=jnp.float32)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def _check_sizes(cfg: SparseDecoderConfig):
    from ..kernels.decode_attention import SCALE_LANES

    if cfg.num_heads > SCALE_LANES:
        raise ValueError(
            f"the paged pool holds one scale lane per query head: "
            f"num_heads={cfg.num_heads} exceeds {SCALE_LANES}")


def _kind_of(cfg: SparseDecoderConfig):
    """Per layer: (pool kind, index among that kind's layers)."""
    seen = {"full": 0, "sliding": 0}
    out = []
    for t in cfg.layer_types:
        out.append(("full" if t == "full" else "window", seen[t]))
        seen[t] += 1
    return out


def init_paged_kv(cfg: SparseDecoderConfig, kind: str, num_pages: int,
                  page_size: int) -> Dict[str, jnp.ndarray]:
    """One page kind's pool columns (``"full"`` or ``"window"``), laid
    out as ``models/generation.init_paged_kv``: ``k``/``v`` int8
    ``[pages, kind's layers, page, kv_heads*head_dim]``, scales float32
    ``[pages, kind's layers, page, SCALE_LANES]``."""
    from ..kernels.decode_attention import SCALE_LANES

    _check_sizes(cfg)
    if num_pages < 2:
        raise ValueError("num_pages must be >= 2 (page 0 is the null page)")
    layers = len(cfg.kind_layers("full" if kind == "full" else "sliding"))
    shape = (num_pages, layers, page_size, cfg.num_kv_heads * cfg.head_dim)
    sshape = shape[:-1] + (SCALE_LANES,)
    return {
        "k": jnp.zeros(shape, jnp.int8), "v": jnp.zeros(shape, jnp.int8),
        "k_scale": jnp.ones(sshape, jnp.float32),
        "v_scale": jnp.ones(sshape, jnp.float32),
    }


def _run_layers(cfg: SparseDecoderConfig, params, x, pool, layer):
    """``x`` through every layer: ``layer(li, weights, kind, ki, x, pool)
    -> (x, per-layer output or None)`` writes ``pool[kind]`` (dicts of
    columns, replaced in place) at the kind's layer ``ki``. A python
    loop: a ``lax.scan`` over the pattern's periods would halve the
    compile, but XLA then copies every expert matrix out of the stacked
    weights each iteration (a ``dynamic-slice`` feeding the grouped
    matmul's custom call: all 6.3 GB of them, every step). Returns
    ``(x, pool, outputs stacked by layer or None)``."""
    pool = {name: dict(cols) for name, cols in pool.items()}
    outs = []
    for li, (p, (kind, ki)) in enumerate(zip(params["layers"],
                                             _kind_of(cfg))):
        x, out = layer(li, p, kind, ki, x, pool)
        outs.append(out)
    return x, pool, None if outs[0] is None else jnp.stack(outs)


def paged_prefill_fn(cfg: SparseDecoderConfig, page_size: int,
                     max_pages: int):
    """``fn(params, pool, tokens[T], length, table_full[max_pages],
    table_window[entries]) -> (pool, first_token)``: the prompt padded to
    a ladder bucket, its keys and values written through both tables
    (padding positions, and on the ring the positions a later page of
    the prompt overwrites, go to the null page), attention within the
    chunk over the QUANTIZED keys and values a block pair at a time
    (``ops.attention.blockwise_attention``: causal, and on a sliding
    layer the window's band only)."""
    from ..ops.attention import blockwise_attention

    ring = cfg.window_entries(page_size)

    def prefill(params, pool, tokens, length, table_full, table_window):
        (T,) = tokens.shape
        tpos = jnp.arange(T)
        page_of = tpos // page_size
        valid = tpos < length
        pages = {
            "full": jnp.where(
                valid, table_full[jnp.minimum(page_of, max_pages - 1)], 0),
            # the ring keeps the prompt's last `ring` pages
            "window": jnp.where(
                valid & (page_of > (length - 1) // page_size - ring),
                table_window[page_of % ring], 0),
        }
        off = tpos % page_size
        with jax.named_scope("embed"):
            x = params["embed"][tokens].astype(cfg.dtype)
        ropes = {"full": _rope_tables(cfg.rope_full, cfg.head_dim, tpos),
                 "window": _rope_tables(cfg.rope_sliding, cfg.head_dim,
                                        tpos)}

        def layer(li, p, kind, ki, x, pool):
            with jax.named_scope(f"layer_{li}/attn"):
                q, k, v = _qkv(cfg, p, x, *ropes[kind])
            with jax.named_scope(f"layer_{li}/kv_write"):
                kq, ks, k_rows = _quantized_rows(cfg, k)
                vq, vs, v_rows = _quantized_rows(cfg, v)
                _write_kv(pool[kind], ki, pages[kind], off, k_rows, v_rows)
            with jax.named_scope(f"layer_{li}/attn"):
                ctx = blockwise_attention(
                    q.transpose(1, 0, 2)[None], kq.transpose(1, 0, 2)[None],
                    vq.transpose(1, 0, 2)[None], causal=True,
                    window=cfg.sliding_window if kind == "window" else None,
                    k_scale=ks.T[None], v_scale=vs.T[None],
                )[0].transpose(1, 0, 2).reshape(T, -1)
                x = x + ctx @ p["wo"]
            return _experts(cfg, p, li, x)[0], None

        x, pool, _ = _run_layers(cfg, params, x, pool, layer)
        first = _head(cfg, params, jnp.take(x, length - 1, axis=0)[None])[0]
        return pool, first

    return prefill


def paged_decode_step_fn(cfg: SparseDecoderConfig, page_size: int,
                         max_pages: int):
    """``fn(params, pool, tokens[S], pos[S], tables_full[S, max_pages],
    tables_window[S, entries]) -> (pool, next_tokens[S], stats)``: one
    token a running slot, every row computed on its own (a batched step
    equals a solo step bit for bit per slot). ``stats["expert_counts"]``
    is ``[layers, experts]`` int32: the tokens each expert got from the
    live rows (a padding row's tables are null)."""
    from ..ops.attention import paged_decode_attention as _paged_attn

    ring = cfg.window_entries(page_size)

    def step(params, pool, tokens, pos, tables_full, tables_window):
        (S,) = tokens.shape
        page_of = pos // page_size
        tables = {"full": tables_full, "window": tables_window}
        wpg = {
            "full": jnp.take_along_axis(
                tables_full, jnp.minimum(page_of, max_pages - 1)[:, None],
                axis=1)[:, 0],
            "window": jnp.take_along_axis(
                tables_window, (page_of % ring)[:, None], axis=1)[:, 0],
        }
        woff = pos % page_size
        live = tables_full[:, 0] > 0
        with jax.named_scope("embed"):
            x = params["embed"][tokens].astype(cfg.dtype)
        ropes = {"full": _rope_tables(cfg.rope_full, cfg.head_dim, pos),
                 "window": _rope_tables(cfg.rope_sliding, cfg.head_dim, pos)}

        def layer(li, p, kind, ki, x, pool):
            with jax.named_scope(f"layer_{li}/attn"):
                q, k, v = _qkv(cfg, p, x, *ropes[kind])
            with jax.named_scope(f"layer_{li}/kv_write"):
                _, _, k_rows = _quantized_rows(cfg, k)
                _, _, v_rows = _quantized_rows(cfg, v)
                _write_kv(pool[kind], ki, wpg[kind], woff, k_rows, v_rows)
            # after the write above, so a slot attends its own token
            with jax.named_scope(f"layer_{li}/attn"):
                cols = pool[kind]
                ctx = _paged_attn(
                    q, cols["k"], cols["v"], cols["k_scale"],
                    cols["v_scale"], ki, tables[kind], pos,
                    window=cfg.sliding_window if kind == "window" else None,
                    ring=kind == "window",
                ).reshape(S, -1)
                x = x + ctx @ p["wo"]
            x, experts = _experts(cfg, p, li, x)
            with jax.named_scope(f"layer_{li}/moe_route"):
                return x, expert_counts(experts, live, cfg.num_experts)

        x, pool, counts = _run_layers(cfg, params, x, pool, layer)
        nxt = _head(cfg, params, x)
        return pool, nxt, {"expert_counts": counts}

    return step


def served_model(cfg: SparseDecoderConfig, page_size: int,
                 horizon: int) -> ServedModel:
    """The block as the decode engine takes it (``models/served.py``):
    two page kinds, prefill and step, and neither the prefix cache's
    suffix prefill nor the page operations of the host swap (both would
    have to handle the ring)."""
    _check_sizes(cfg)
    max_pages = -(-int(horizon) // int(page_size))
    return ServedModel(
        vocab_size=int(cfg.vocab_size),
        max_seq_len=int(cfg.max_seq_len),
        kinds=(
            PageKind("full", max_pages,
                     lambda n: init_paged_kv(cfg, "full", n, page_size)),
            PageKind("window", cfg.window_entries(page_size),
                     lambda n: init_paged_kv(cfg, "window", n, page_size),
                     ring=True, window=cfg.sliding_window),
        ),
        prefill=paged_prefill_fn(cfg, page_size, max_pages),
        step=paged_decode_step_fn(cfg, page_size, max_pages),
        kernels=("decode_attn", "expert_matmul"),
    )

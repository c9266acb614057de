"""Operations and bytes of the benchmark's work, from the traffic's
shapes alone: rows and image size for an Inception block; active slots
and their context lengths for a decode step. Nothing here looks at how
the program tiles, fuses or pads the work, so a share of a peak reads the
same numerator whatever implements it. ``selfcheck.py`` cross-checks
these closed forms against the plain references' traced shapes.

A multiply-accumulate counts as 2 operations.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Tuple


def _valid(n: int, k: int, s: int) -> int:
    return (n - k) // s + 1


def inception_v3_layers(config: Dict) -> List[Tuple[str, int, int, int, int, int]]:
    """``(name, kh, kw, cin, cout, out_side)`` for every convolution of
    Inception-v3 at ``config["image_size"]`` (published widths scaled by
    ``channel_scale``, 1.0 in every cell)."""
    scale = float(config.get("channel_scale", 1.0))

    def ch(n: int) -> int:
        return max(8, int(round(n * scale / 8.0)) * 8)

    rows: List[Tuple[str, int, int, int, int, int]] = []
    s = _valid(int(config["image_size"]), 3, 2)            # 149
    rows.append(("stem.c1", 3, 3, 3, ch(32), s))
    s = _valid(s, 3, 1)                                    # 147
    rows.append(("stem.c2", 3, 3, ch(32), ch(32), s))
    rows.append(("stem.c3", 3, 3, ch(32), ch(64), s))
    s = _valid(s, 3, 2)                                    # 73
    rows.append(("stem.c4", 1, 1, ch(64), ch(80), s))
    s = _valid(s, 3, 1)                                    # 71
    rows.append(("stem.c5", 3, 3, ch(80), ch(192), s))
    s = _valid(s, 3, 2)                                    # 35
    cin = ch(192)
    for i, pool in enumerate((32, 64, 64)):
        m = f"mixed_a{i}"
        rows += [(f"{m}.b1", 1, 1, cin, ch(64), s),
                 (f"{m}.b5_1", 1, 1, cin, ch(48), s),
                 (f"{m}.b5_2", 5, 5, ch(48), ch(64), s),
                 (f"{m}.b3_1", 1, 1, cin, ch(64), s),
                 (f"{m}.b3_2", 3, 3, ch(64), ch(96), s),
                 (f"{m}.b3_3", 3, 3, ch(96), ch(96), s),
                 (f"{m}.bp", 1, 1, cin, ch(pool), s)]
        cin = 2 * ch(64) + ch(96) + ch(pool)
    s2 = _valid(s, 3, 2)                                   # 17
    rows += [("mixed_b.b3", 3, 3, cin, ch(384), s2),
             ("mixed_b.bd_1", 1, 1, cin, ch(64), s),
             ("mixed_b.bd_2", 3, 3, ch(64), ch(96), s),
             ("mixed_b.bd_3", 3, 3, ch(96), ch(96), s2)]
    cin, s = ch(384) + ch(96) + cin, s2
    for i, c7 in enumerate((128, 160, 160, 192)):
        m, c7 = f"mixed_c{i}", ch(c7)
        rows += [(f"{m}.b1", 1, 1, cin, ch(192), s),
                 (f"{m}.b7_1", 1, 1, cin, c7, s),
                 (f"{m}.b7_2", 1, 7, c7, c7, s),
                 (f"{m}.b7_3", 7, 1, c7, ch(192), s),
                 (f"{m}.bd_1", 1, 1, cin, c7, s),
                 (f"{m}.bd_2", 7, 1, c7, c7, s),
                 (f"{m}.bd_3", 1, 7, c7, c7, s),
                 (f"{m}.bd_4", 7, 1, c7, c7, s),
                 (f"{m}.bd_5", 1, 7, c7, ch(192), s),
                 (f"{m}.bp", 1, 1, cin, ch(192), s)]
        cin = 4 * ch(192)
    s2 = _valid(s, 3, 2)                                   # 8
    rows += [("mixed_d.b3_1", 1, 1, cin, ch(192), s),
             ("mixed_d.b3_2", 3, 3, ch(192), ch(320), s2),
             ("mixed_d.b7_1", 1, 1, cin, ch(192), s),
             ("mixed_d.b7_2", 1, 7, ch(192), ch(192), s),
             ("mixed_d.b7_3", 7, 1, ch(192), ch(192), s),
             ("mixed_d.b7_4", 3, 3, ch(192), ch(192), s2)]
    cin, s = ch(320) + ch(192) + cin, s2
    for i in range(2):
        m = f"mixed_e{i}"
        rows += [(f"{m}.b1", 1, 1, cin, ch(320), s),
                 (f"{m}.b3_1", 1, 1, cin, ch(384), s),
                 (f"{m}.b3_2a", 1, 3, ch(384), ch(384), s),
                 (f"{m}.b3_2b", 3, 1, ch(384), ch(384), s),
                 (f"{m}.bd_1", 1, 1, cin, ch(448), s),
                 (f"{m}.bd_2", 3, 3, ch(448), ch(384), s),
                 (f"{m}.bd_3a", 1, 3, ch(384), ch(384), s),
                 (f"{m}.bd_3b", 3, 1, ch(384), ch(384), s),
                 (f"{m}.bp", 1, 1, cin, ch(192), s)]
        cin = ch(320) + 4 * ch(384) + ch(192)
    rows.append(("fc", 1, 1, cin, int(config["num_classes"]), 1))
    return rows


def inception_v3_flops(config: Dict, rows: int) -> float:
    """Convolution and classifier operations for ``rows`` images; the
    affine, ReLU, pooling and concatenation (well under 1 %) are left
    out, so a share of the peak computed from this is never flattered."""
    per_image = sum(2 * kh * kw * cin * cout * side * side
                    for _, kh, kw, cin, cout, side
                    in inception_v3_layers(config))
    return float(per_image) * rows


def gpt2_matmul_params(config: Dict) -> int:
    """Parameters of the per-token matmuls of the decoder blocks: fused
    qkv, attention output, MLP in and out (no embeddings, no biases)."""
    h, m = int(config["n_embd"]), int(config["n_inner"])
    return int(config["n_layer"]) * (3 * h * h + h * h + 2 * h * m)


def gpt2_decode_step_flops(config: Dict, contexts: Iterable[int]) -> float:
    """One decode step over the given slots: per slot 2 operations per
    matmul parameter, the tied LM head (2 * n_embd * vocab), and per
    layer QK^T and PV over that slot's context (4 * context * n_embd)."""
    h = int(config["n_embd"])
    per_token = 2 * gpt2_matmul_params(config) \
        + 2 * h * int(config["vocab_size"])
    attn = 4 * h * int(config["n_layer"])
    return float(sum(per_token + attn * int(c) for c in contexts))


def gpt2_decode_attn_bytes(config: Dict, contexts: Iterable[int],
                           kv_bytes: int = 1, scale_bytes: int = 4) -> float:
    """Bytes the decode-attention calls of ONE step (all layers) have to
    read: every context position's key and value (``kv_bytes`` per
    element: int8) and their per-head scales. Queries and outputs
    (a few KB per slot) are left out."""
    h, nh = int(config["n_embd"]), int(config["n_head"])
    per_pos = 2 * h * kv_bytes + 2 * nh * scale_bytes
    return float(int(config["n_layer"]) * per_pos
                 * sum(int(c) for c in contexts))

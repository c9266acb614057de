"""Operations and bytes of a decode step of the sparse-expert decoder
(``configs/mellum2-12b-a2.5b.json``'s keys), from the traffic's shapes
alone: the live slots and their context lengths. Nothing here looks at
how the program tiles, fuses or pads the work. ``tests/
test_decoder_counts.py`` holds the closed forms to the plain
reference's jaxpr.

A multiply-accumulate counts as 2 operations.
"""

from __future__ import annotations

from typing import Dict, Iterable, List


def _kinds(config: Dict) -> List[str]:
    n = int(config["num_hidden_layers"])
    return [t.split("_")[0] for t in config["layer_types"][:n]]


def attention_params(config: Dict) -> int:
    """Parameters of one layer's q, k, v and output projections."""
    d, hd = int(config["hidden_size"]), int(config["head_dim"])
    nq, nkv = (int(config["num_attention_heads"]),
               int(config["num_key_value_heads"]))
    return 2 * d * nq * hd + 2 * d * nkv * hd


def expert_params(config: Dict) -> int:
    """Parameters of one expert's three matrices."""
    return 3 * int(config["hidden_size"]) * int(
        config["moe_intermediate_size"])


def router_params(config: Dict) -> int:
    return int(config["hidden_size"]) * int(config["num_experts"])


def attended(config: Dict, kind: str, context: float) -> float:
    """Positions a slot at ``context`` attends on a layer of ``kind``."""
    if kind == "sliding":
        return min(float(context), float(config["sliding_window"]))
    return float(context)


def decode_step_flops(config: Dict, contexts: Iterable[float]) -> float:
    """One decode step over the given slots: per slot and layer 2
    operations per parameter of the projections, the router and the
    ``num_experts_per_tok`` experts it is routed to, the untied head
    once, and per layer QK^T and PV over what the slot attends there
    (4 * heads * head_dim a position)."""
    kinds = _kinds(config)
    per_layer = 2 * (attention_params(config) + router_params(config)
                     + int(config["num_experts_per_tok"])
                     * expert_params(config))
    head = 2 * int(config["hidden_size"]) * int(config["vocab_size"])
    qk_pv = 4 * int(config["num_attention_heads"]) * int(config["head_dim"])
    return float(sum(
        len(kinds) * per_layer + head
        + qk_pv * sum(attended(config, k, c) for k in kinds)
        for c in contexts))


def expert_flops(config: Dict, slots: int) -> float:
    """The expert matmuls of one step: every (slot, expert) pair through
    the expert's three matrices, in every layer."""
    return float(len(_kinds(config)) * int(slots)
                 * int(config["num_experts_per_tok"])
                 * 2 * expert_params(config))


def expert_bytes(config: Dict, slots: int, weight_bytes: int = 2) -> float:
    """Bytes the expert matmuls of one step have to read: the three
    matrices of every expert some slot is routed to, once a layer (the
    pairs' activations, a few MB, are left out). Under even routing an
    expert is missed with probability ``(1 - k/E) ** slots``: at 128
    slots of 8-of-64 that is 4e-8, so every expert's matrices are read."""
    e, k = int(config["num_experts"]), int(config["num_experts_per_tok"])
    touched = e * (1.0 - (1.0 - k / e) ** int(slots))
    return float(len(_kinds(config)) * touched * expert_params(config)
                 * weight_bytes)


def decode_attn_bytes(config: Dict, contexts: Iterable[float],
                      kv_bytes: int = 1, scale_bytes: int = 4) -> float:
    """Bytes the attention of ONE step (all layers) has to read: per
    layer and attended position the KV heads' keys and values
    (``kv_bytes`` an element: int8) and one scale a KV head for each;
    a sliding layer attends its window only."""
    nkv, hd = int(config["num_key_value_heads"]), int(config["head_dim"])
    per_pos = 2 * nkv * hd * kv_bytes + 2 * nkv * scale_bytes
    kinds = _kinds(config)
    return float(per_pos * sum(
        attended(config, k, c) for c in contexts for k in kinds))

"""Plain Mellum2 (JetBrains, ``Mellum2-12B-A2.5B-Instruct`` ``config.json``)
decoder forward in float32 ``jax.numpy``: the yardstick the
``mellum2-12b-a2.5b`` cells are held to. Imports nothing of
``tensorframes_tpu``.

The layer, as read from the published ``config`` (each departure is
under ``assumed`` in ``configs/mellum2-12b-a2.5b.json``). For layer ``l``
of kind ``layer_types[l]``, hidden ``x`` [T, hidden]:

1. ``h = RMSNorm(x)``: ``x / sqrt(mean(x^2) + eps) * g1``.
2. ``q = h Wq`` (heads x head_dim), ``k = h Wk``, ``v = h Wv`` (KV heads x
   head_dim), no bias, no per-head norm.
3. Rotary on ``q`` and ``k``, whole head, halves convention, base
   ``rope_theta``. Sliding layers: plain frequencies. Full layers: YaRN
   (``factor``, ``original_max_position_embeddings``, ``beta_fast``,
   ``beta_slow``): the frequencies between the two rotations' dimensions
   blended towards ``1 / factor`` of themselves by a linear ramp; cos
   and sin times ``attention_factor``.
4. Attention, scale ``1 / sqrt(head_dim)``, query head ``j`` on KV head
   ``j // group``; position ``i`` attends ``j <= i`` on a full layer and
   ``max(0, i - (window - 1)) <= j <= i`` on a sliding one. ``x += concat
   (heads) Wo``.
5. ``h = RMSNorm(x)`` with ``g2``. ``p = softmax(h Wr)`` over all experts;
   the ``num_experts_per_tok`` largest, renormalised to sum 1
   (``norm_topk_prob``); expert ``e``: ``(silu(h Wg_e) * (h Wu_e)) Wd_e``.
   ``x += sum_e w_e E_e(h)``. No shared expert, no dense layer, no
   router bias, no token dropped.
6. After the last layer RMSNorm and an untied head.

No cache, no kernel, no batching: one causal pass over a whole sequence,
every product at ``precision=HIGHEST``, the experts one after another
over all tokens with the unrouted ones weighted 0. Weights are made from
the seed A LAYER AT A TIME (:func:`make_layer_weights`; the program's are
the same draws cast to bfloat16), and :func:`logits_at` runs layer by
layer over all its sequences, so that no more than one layer's float32
weights is on the device at once.

``quant="int4"`` is the control of "How correct is decided": the layers'
matmul weights (per output channel) and the keys and values (per KV head
and position) rounded to 4-bit integers (``quant="int8kv"`` rounds only
the keys and values, to the 8 bits the program's cache holds: what the
unit tests compare a float32 program with). ``fault=`` puts a planted fault
in the reference's place: ``"no_window"`` (sliding layers attend their
whole context), ``"no_yarn"`` (full layers rotate as sliding ones do),
``"top7"`` (one expert fewer a token), ``"no_renorm"`` (the top-k weights
left as the softmax gave them). Neither is run by a benchmark run.
"""

from __future__ import annotations

import math
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_HI = lax.Precision.HIGHEST

FAULTS = ("no_window", "no_yarn", "top7", "no_renorm")


def layer_kinds(config: Dict) -> List[str]:
    """``"full"`` or ``"sliding"`` for each of the layers kept."""
    n = int(config["num_hidden_layers"])
    return [t.split("_")[0] for t in config["layer_types"][:n]]


def inv_freq(config: Dict, kind: str, yarn: bool = True) -> np.ndarray:
    """The rotary frequencies of a layer kind, float64 [head_dim / 2]."""
    hd = int(config["head_dim"])
    rope = config["rope_parameters"][f"{kind}_attention"]
    base = float(rope["rope_theta"])
    plain = base ** (-2.0 * np.arange(hd // 2) / hd)
    if rope["rope_type"] != "yarn" or not yarn:
        return plain
    original = float(rope["original_max_position_embeddings"])

    def dimension(rotations: float) -> float:
        return hd * math.log(original / (2 * math.pi * rotations)) / (
            2 * math.log(base))

    lo = max(math.floor(dimension(float(rope["beta_fast"]))), 0)
    hi = min(math.ceil(dimension(float(rope["beta_slow"]))), hd - 1)
    ramp = np.clip((np.arange(hd // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
    return (1 - ramp) * plain + ramp * plain / float(rope["factor"])


def _rope(config: Dict, kind: str, t: int, yarn: bool):
    rope = config["rope_parameters"][f"{kind}_attention"]
    scaled = rope["rope_type"] == "yarn" and yarn
    angle = np.arange(t)[:, None] * inv_freq(config, kind, yarn)[None, :]
    angle = np.concatenate([angle, angle], axis=-1)
    factor = float(rope["attention_factor"]) if scaled else 1.0
    return (jnp.asarray(np.cos(angle) * factor, jnp.float32),
            jnp.asarray(np.sin(angle) * factor, jnp.float32))


def _rotate(x, cos, sin):
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[:, None, :] + turned * sin[:, None, :]


def _rms(x, gain, eps: float):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _fq(x, axes, bits: int):
    top = float(2 ** (bits - 1) - 1)
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    scale = jnp.where(amax > 0, amax / top, 1.0)
    return jnp.round(x / scale) * scale


def layer_shapes(config: Dict) -> Dict[str, Tuple[int, ...]]:
    d, hd = int(config["hidden_size"]), int(config["head_dim"])
    nq, nkv = (int(config["num_attention_heads"]),
               int(config["num_key_value_heads"]))
    e, f = int(config["num_experts"]), int(config["moe_intermediate_size"])
    return {
        "norm1": (d,), "norm2": (d,),
        "wq": (d, nq * hd), "wk": (d, nkv * hd), "wv": (d, nkv * hd),
        "wo": (nq * hd, d), "router": (d, e),
        "w_gate": (e, d, f), "w_up": (e, d, f), "w_down": (e, f, d),
    }


def _draw(key, shapes: Dict[str, Tuple[int, ...]]) -> Dict:
    """Seeded leaves, each its own draw of standard normals (so that
    making a layer never holds more than its largest leaf twice): norm
    gains near 1, the embedding at unit variance, every matrix at
    1 / sqrt(fan-in)."""
    out = {}
    for i, (name, shape) in enumerate(shapes.items()):
        leaf = jax.random.normal(jax.random.fold_in(key, i), shape,
                                 jnp.float32)
        if "norm" in name:
            out[name] = 1.0 + leaf * 0.02
        elif name == "embed":
            out[name] = leaf
        else:
            out[name] = leaf * float(shape[-2] ** -0.5)
    return out


def make_layer_weights(config: Dict, seed, layer) -> Dict:
    """Layer ``layer``'s float32 weights from ``seed`` (call under
    ``jax.jit``; ``seed`` and ``layer`` may be traced)."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), layer + 1)
    return _draw(key, layer_shapes(config))


def make_outer_weights(config: Dict, seed) -> Dict:
    """The embedding, the final norm and the untied head."""
    d, v = int(config["hidden_size"]), int(config["vocab_size"])
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 0)
    return _draw(key, {"embed": (v, d), "final_norm": (d,), "head": (d, v)})


def layer_forward(config: Dict, w: Dict, x, kind: str,
                  quant: Optional[str] = None, fault: Optional[str] = None):
    """One layer over one sequence: ``x`` [T, hidden] float32 → the same."""
    t = x.shape[0]
    hd = int(config["head_dim"])
    nq, nkv = (int(config["num_attention_heads"]),
               int(config["num_key_value_heads"]))
    group = nq // nkv
    eps = float(config["rms_norm_eps"])
    w_of = (lambda a: _fq(a, (-2,), 4)) if quant == "int4" else (lambda a: a)
    cos, sin = _rope(config, kind, t, yarn=fault != "no_yarn")

    h = _rms(x, w["norm1"], eps)
    q = jnp.matmul(h, w_of(w["wq"]), precision=_HI).reshape(t, nq, hd)
    k = jnp.matmul(h, w_of(w["wk"]), precision=_HI).reshape(t, nkv, hd)
    v = jnp.matmul(h, w_of(w["wv"]), precision=_HI).reshape(t, nkv, hd)
    q, k = _rotate(q, cos, sin), _rotate(k, cos, sin)
    if quant in ("int4", "int8kv"):
        bits = 4 if quant == "int4" else 8
        k, v = _fq(k, (2,), bits), _fq(v, (2,), bits)
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    mask = j <= i
    if kind == "sliding" and fault != "no_window":
        mask = mask & (j > i - int(config["sliding_window"]))
    heads = []
    for g in range(nkv):          # a KV head and its query heads at a time
        qg = q[:, g * group:(g + 1) * group]
        s = jnp.einsum("tnd,sd->nts", qg, k[:, g], precision=_HI)
        s = jnp.where(mask[None], s / np.float32(np.sqrt(hd)), -jnp.inf)
        heads.append(jnp.einsum("nts,sd->tnd", jax.nn.softmax(s, axis=-1),
                                v[:, g], precision=_HI))
    ctx = jnp.concatenate(heads, axis=1).reshape(t, nq * hd)
    x = x + jnp.matmul(ctx, w_of(w["wo"]), precision=_HI)

    h = _rms(x, w["norm2"], eps)
    probs = jax.nn.softmax(jnp.matmul(h, w["router"], precision=_HI), axis=-1)
    top = int(config["num_experts_per_tok"]) - (fault == "top7")
    picked, experts = lax.top_k(probs, top)
    if config["norm_topk_prob"] and fault != "no_renorm":
        picked = picked / picked.sum(axis=-1, keepdims=True)
    gates = jnp.zeros_like(probs).at[jnp.arange(t)[:, None], experts].set(
        picked)

    def expert(y, e):
        wg, wu, wd, gate = e
        a = jax.nn.silu(jnp.matmul(h, w_of(wg), precision=_HI)) \
            * jnp.matmul(h, w_of(wu), precision=_HI)
        return y + gate[:, None] * jnp.matmul(a, w_of(wd), precision=_HI), None

    y, _ = lax.scan(expert, jnp.zeros_like(x),
                    (w["w_gate"], w["w_up"], w["w_down"], gates.T))
    return x + y


def logits_at(config: Dict, seed: int,
              rows: Sequence[Tuple[np.ndarray, np.ndarray]],
              quant: Optional[str] = None, fault: Optional[str] = None
              ) -> Iterator[jnp.ndarray]:
    """For each ``(tokens [T], positions [k])`` of ``rows`` the logits
    ``[k, vocab]`` at those positions of that sequence, one sequence at a
    time. Layer by layer over all the sequences: one layer's weights are
    made, every sequence passes through it, and they are dropped. Rows
    of one length share a compiled layer."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    outer = jax.jit(lambda s: make_outer_weights(config, s))(np.int64(seed))
    xs = [outer["embed"][jnp.asarray(tokens)] for tokens, _ in rows]
    make = jax.jit(lambda s, l: make_layer_weights(config, s, l))
    forward = {kind: jax.jit(
        lambda w, x, kind=kind: layer_forward(config, w, x, kind, quant,
                                              fault))
        for kind in ("full", "sliding")}
    for l, kind in enumerate(layer_kinds(config)):
        w = make(np.int64(seed), np.int32(l))
        xs = [forward[kind](w, x) for x in xs]
        del w
    eps = float(config["rms_norm_eps"])

    @jax.jit
    def head(gain, weight, x, positions):
        return jnp.matmul(_rms(x[positions], gain, eps), weight,
                          precision=_HI)

    for x, (_, positions) in zip(xs, rows):
        yield head(outer["final_norm"], outer["head"], x,
                   jnp.asarray(positions))

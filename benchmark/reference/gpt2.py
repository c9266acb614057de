"""Plain GPT-2 (Radford et al. 2019; ``openai-community/gpt2``) decoder
forward in float32 ``jax.numpy`` — the yardstick the ``gpt2-small`` cells
are held to. Imports nothing of ``tensorframes_tpu``.

Pre-LN decoder blocks, learned positions, tanh-GELU, logits tied to the
token embedding. It follows the served variant where that departs from
the published model (each listed under ``assumed`` in
``configs/gpt2-small.json``): no bias on the attention projections,
layer-norm epsilon 1e-6. No cache, no batching tricks: one causal pass
over the whole sequence, every product at ``precision=HIGHEST``.

``quant="int4"`` is the control of "How correct is decided": layer
matmul weights (per output channel) and the keys and values (per head
and position) rounded to 4-bit integers — one step below the int8 the
configuration states. Never run by a benchmark run.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_HI = lax.Precision.HIGHEST
LN_EPS = 1e-6


def _fq(x, axes, bits: int):
    top = float(2 ** (bits - 1) - 1)
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    scale = jnp.where(amax > 0, amax / top, 1.0)
    return jnp.round(x / scale) * scale


def _ln(x, p):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        np.sqrt(2.0 / np.pi).astype(np.float32)
        * (x + np.float32(0.044715) * x ** 3)))


def hidden_states(config: Dict, params: Dict, tokens,
                  quant: Optional[str] = None):
    """tokens [b, t] int → final hidden states [b, t, n_embd] float32."""
    nh = int(config["n_head"])
    b, t = tokens.shape
    w_of = (lambda w: _fq(w, (0,), 4)) if quant == "int4" else (lambda w: w)
    x = params["embed"]["tok"][tokens] + params["embed"]["pos"][:t]
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    for p in params["layers"]:
        y = _ln(x, p["ln1"])
        qkv = jnp.matmul(y, w_of(p["attn"]["qkv"]), precision=_HI)
        hd = qkv.shape[-1] // (3 * nh)
        qkv = qkv.reshape(b, t, 3, nh, hd)
        q, k, v = (qkv[:, :, i].transpose(0, 2, 1, 3) for i in range(3))
        if quant == "int4":
            k, v = _fq(k, (3,), 4), _fq(v, (3,), 4)
        s = jnp.einsum("bntd,bnsd->bnts", q, k, precision=_HI)
        s = jnp.where(causal[None, None], s / np.float32(np.sqrt(hd)),
                      -jnp.inf)
        ctx = jnp.einsum("bnts,bnsd->bntd", jax.nn.softmax(s, axis=-1), v,
                         precision=_HI)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(b, t, nh * hd)
        x = x + jnp.matmul(ctx, w_of(p["attn"]["out"]), precision=_HI)
        y = _ln(x, p["ln2"])
        y = jnp.matmul(y, w_of(p["mlp"]["in"]), precision=_HI)
        y = _gelu_tanh(y + p["mlp"]["in_bias"])
        x = x + jnp.matmul(y, w_of(p["mlp"]["out"]), precision=_HI) \
            + p["mlp"]["out_bias"]
    return _ln(x, params["final_ln"])


def logits_at(config: Dict, params: Dict, tokens, positions,
              quant: Optional[str] = None):
    """Logits [b, k, vocab] at ``positions`` [b, k] of each row."""
    hs = hidden_states(config, params, tokens, quant)
    picked = jnp.take_along_axis(hs, positions[:, :, None], axis=1)
    return jnp.matmul(picked, params["embed"]["tok"].T, precision=_HI)


def make_weights(config: Dict, seed: int) -> Dict:
    """The float32 master weights from ``seed`` (call under ``jax.jit``:
    one program, made on the device), in the tree the program under test
    takes: GPT-2's 0.02-normal embeddings, 1/sqrt(fan-in) projections,
    layer norms near identity, small non-zero MLP biases. One draw of
    standard normals is cut into the leaves, so the program that makes
    them is one random operation and compiles in seconds."""
    h = int(config["n_embd"])
    m = int(config["n_inner"])
    n_layer = int(config["n_layer"])
    layer_shapes = [(h,), (h,), (h,), (h,), (h, 3 * h), (h, h), (h, m),
                    (m,), (m, h), (h,)]
    shapes = [(int(config["vocab_size"]), h),
              (int(config["n_positions"]), h), (h,), (h,)]
    shapes += layer_shapes * n_layer
    sizes = [int(np.prod(s)) for s in shapes]
    flat = jax.random.normal(jax.random.PRNGKey(seed), (sum(sizes),),
                             jnp.float32)
    ends = np.cumsum(sizes)
    leaves = iter(flat[e - n:e].reshape(s)
                  for s, n, e in zip(shapes, sizes, ends))

    def take(scale):
        return next(leaves) * float(scale)

    def ln():
        return {"scale": 1.0 + take(0.02), "bias": take(0.02)}

    tree = {"embed": {"tok": take(0.02), "pos": take(0.02)},
            "final_ln": ln(), "layers": []}
    for _ in range(n_layer):
        tree["layers"].append({
            "ln1": ln(), "ln2": ln(),
            "attn": {"qkv": take(h ** -0.5), "out": take(h ** -0.5)},
            "mlp": {"in": take(h ** -0.5), "in_bias": take(0.02),
                    "out": take(m ** -0.5), "out_bias": take(0.02)},
        })
    return tree

"""Plain Inception-v3 (Szegedy et al. 2015, arXiv:1512.00567) forward in
float32 ``jax.numpy`` — the yardstick the ``inception-v3`` cells are held
to. Imports nothing of ``tensorframes_tpu``.

Frozen-graph inference: batch norm is folded into a per-channel affine
(``scale``, ``bias``) after each convolution, followed by ReLU. Layout is
NHWC. Every product runs at ``precision=HIGHEST`` (on a TPU the default
float32 matmul rounds its operands to bfloat16).

``conv_table`` lists every convolution's shape as this file computes
it; the weights generator reads it, and ``selfcheck.py`` holds
``counts.py``'s closed form to it. The parameter tree is keyed the way the
program under test expects its weights (``stem.c1`` … ``mixed_e1.bp``,
``fc``) because the benchmark hands the same seeded weights to both.

``quant="int8"`` is the control of "How correct is decided": the same
forward with weights (per output channel) and conv inputs (per tensor)
rounded to 8-bit integers — the precision a later PR would be tempted
by. It is never run by a benchmark run, only by the limit tool and tests.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_DN = ("NHWC", "HWIO", "NHWC")
_HI = lax.Precision.HIGHEST

# (name, kh, kw, cout, stride, padding) per conv, per module; cin follows
# from the graph. Published widths (channel_scale 1.0).
_A = lambda pool: [  # noqa: E731 - table constructor
    ("b1", 1, 1, 64), ("b5_1", 1, 1, 48), ("b5_2", 5, 5, 64),
    ("b3_1", 1, 1, 64), ("b3_2", 3, 3, 96), ("b3_3", 3, 3, 96),
    ("bp", 1, 1, pool)]
_C = lambda c7: [  # noqa: E731
    ("b1", 1, 1, 192), ("b7_1", 1, 1, c7), ("b7_2", 1, 7, c7),
    ("b7_3", 7, 1, 192), ("bd_1", 1, 1, c7), ("bd_2", 7, 1, c7),
    ("bd_3", 1, 7, c7), ("bd_4", 7, 1, c7), ("bd_5", 1, 7, 192),
    ("bp", 1, 1, 192)]
_E = [("b1", 1, 1, 320), ("b3_1", 1, 1, 384), ("b3_2a", 1, 3, 384),
      ("b3_2b", 3, 1, 384), ("bd_1", 1, 1, 448), ("bd_2", 3, 3, 384),
      ("bd_3a", 1, 3, 384), ("bd_3b", 3, 1, 384), ("bp", 1, 1, 192)]


def _fq(x, axes, bits: int):
    """Symmetric fake quantisation: round to ``bits``-bit integers with
    one absmax scale over ``axes``, back in float32."""
    top = float(2 ** (bits - 1) - 1)
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    scale = jnp.where(amax > 0, amax / top, 1.0)
    return jnp.round(x / scale) * scale


def _conv(p, x, stride=1, padding="SAME", quant=None):
    w = p["w"].astype(jnp.float32)
    if quant == "int8":
        w = _fq(w, (0, 1, 2), 8)
        x = _fq(x, (0, 1, 2, 3), 8)
    y = lax.conv_general_dilated(x, w, (stride, stride), padding,
                                 dimension_numbers=_DN, precision=_HI)
    y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return jnp.maximum(y, 0.0)


def _maxpool(x):
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1),
                             (1, 2, 2, 1), "VALID")


def _avgpool3(x):
    """3x3 stride-1 SAME average, dividing by the cells inside the image."""
    win, one = (1, 3, 3, 1), (1, 1, 1, 1)
    s = lax.reduce_window(x, 0.0, lax.add, win, one, "SAME")
    n = lax.reduce_window(jnp.ones((1,) + x.shape[1:3] + (1,), x.dtype),
                          0.0, lax.add, win, one, "SAME")
    return s / n


def forward(params: Dict, images, quant: Optional[str] = None):
    """images [n, S, S, 3] → logits [n, classes], float32."""
    c = lambda p, x, **kw: _conv(p, x, quant=quant, **kw)  # noqa: E731
    x = images.astype(jnp.float32)
    s = params["stem"]
    x = c(s["c1"], x, stride=2, padding="VALID")
    x = c(s["c2"], x, padding="VALID")
    x = c(s["c3"], x)
    x = _maxpool(x)
    x = c(s["c4"], x)
    x = c(s["c5"], x, padding="VALID")
    x = _maxpool(x)
    for i in range(3):
        p = params[f"mixed_a{i}"]
        x = jnp.concatenate([
            c(p["b1"], x),
            c(p["b5_2"], c(p["b5_1"], x)),
            c(p["b3_3"], c(p["b3_2"], c(p["b3_1"], x))),
            c(p["bp"], _avgpool3(x))], axis=-1)
    p = params["mixed_b"]
    x = jnp.concatenate([
        c(p["b3"], x, stride=2, padding="VALID"),
        c(p["bd_3"], c(p["bd_2"], c(p["bd_1"], x)), stride=2,
          padding="VALID"),
        _maxpool(x)], axis=-1)
    for i in range(4):
        p = params[f"mixed_c{i}"]
        bd = x
        for k in ("bd_1", "bd_2", "bd_3", "bd_4", "bd_5"):
            bd = c(p[k], bd)
        x = jnp.concatenate([
            c(p["b1"], x),
            c(p["b7_3"], c(p["b7_2"], c(p["b7_1"], x))),
            bd,
            c(p["bp"], _avgpool3(x))], axis=-1)
    p = params["mixed_d"]
    b7 = x
    for k in ("b7_1", "b7_2", "b7_3"):
        b7 = c(p[k], b7)
    x = jnp.concatenate([
        c(p["b3_2"], c(p["b3_1"], x), stride=2, padding="VALID"),
        c(p["b7_4"], b7, stride=2, padding="VALID"),
        _maxpool(x)], axis=-1)
    for i in range(2):
        p = params[f"mixed_e{i}"]
        b3 = c(p["b3_1"], x)
        bd = c(p["bd_2"], c(p["bd_1"], x))
        x = jnp.concatenate([
            c(p["b1"], x),
            c(p["b3_2a"], b3), c(p["b3_2b"], b3),
            c(p["bd_3a"], bd), c(p["bd_3b"], bd),
            c(p["bp"], _avgpool3(x))], axis=-1)
    x = jnp.mean(x, axis=(1, 2))
    fc = params["fc"]
    w = fc["w"].astype(jnp.float32)
    if quant == "int8":
        w, x = _fq(w, (0,), 8), _fq(x, (0, 1), 8)
    return jnp.matmul(x, w, precision=_HI) + fc["b"].astype(jnp.float32)


def _widths(config: Dict):
    """``channel_scale`` (rehearsal only) shrinks widths to multiples of 8."""
    scale = float(config.get("channel_scale", 1.0))
    return lambda n: max(8, int(round(n * scale / 8.0)) * 8)


def conv_table(config: Dict) -> List[Tuple[str, int, int, int, int]]:
    """Every convolution as ``(path, kh, kw, cin, cout)`` in forward
    order — the shapes the weights are generated at."""
    ch = _widths(config)
    out: List[Tuple[str, int, int, int, int]] = []

    def add(mod, name, kh, kw, cin, cout):
        out.append((f"{mod}.{name}", kh, kw, cin, ch(cout)))
        return ch(cout)

    cur = 3
    for name, k, cout in (("c1", 3, 32), ("c2", 3, 32), ("c3", 3, 64),
                          ("c4", 1, 80), ("c5", 3, 192)):
        cur = add("stem", name, k, k, cur, cout)

    def module(mod, rows, cur, chains):
        """``chains`` maps a branch's first conv to the convs after it."""
        spec = {r[0]: r for r in rows}
        for head, rest in chains:
            c_in = cur
            for name in (head,) + rest:
                _, kh, kw, cout = spec[name]
                c_in = add(mod, name, kh, kw, c_in, cout)

    for i, pool in enumerate((32, 64, 64)):
        module(f"mixed_a{i}", _A(pool), cur,
               [("b1", ()), ("b5_1", ("b5_2",)),
                ("b3_1", ("b3_2", "b3_3")), ("bp", ())])
        cur = ch(64) + ch(64) + ch(96) + ch(pool)
    module("mixed_b", [("b3", 3, 3, 384), ("bd_1", 1, 1, 64),
                       ("bd_2", 3, 3, 96), ("bd_3", 3, 3, 96)], cur,
           [("b3", ()), ("bd_1", ("bd_2", "bd_3"))])
    cur = ch(384) + ch(96) + cur
    for i, c7 in enumerate((128, 160, 160, 192)):
        module(f"mixed_c{i}", _C(c7), cur,
               [("b1", ()), ("b7_1", ("b7_2", "b7_3")),
                ("bd_1", ("bd_2", "bd_3", "bd_4", "bd_5")), ("bp", ())])
        cur = 4 * ch(192)
    module("mixed_d", [("b3_1", 1, 1, 192), ("b3_2", 3, 3, 320),
                       ("b7_1", 1, 1, 192), ("b7_2", 1, 7, 192),
                       ("b7_3", 7, 1, 192), ("b7_4", 3, 3, 192)], cur,
           [("b3_1", ("b3_2",)), ("b7_1", ("b7_2", "b7_3", "b7_4"))])
    cur = ch(320) + ch(192) + cur
    for i in range(2):
        module(f"mixed_e{i}", _E, cur,
               [("b1", ()), ("b3_1", ("b3_2a",)), ("bd_1", ("bd_2", "bd_3a")),
                ("bp", ())])
        # the two split branches read the same input as their sibling
        out.append((f"mixed_e{i}.b3_2b", 3, 1, ch(384), ch(384)))
        out.append((f"mixed_e{i}.bd_3b", 3, 1, ch(384), ch(384)))
        cur = ch(320) + 4 * ch(384) + ch(192)
    return out


def fc_in(config: Dict) -> int:
    ch = _widths(config)
    return ch(320) + 4 * ch(384) + ch(192)


def make_weights(config: Dict, seed: int, dtype=jnp.bfloat16) -> Dict:
    """The whole parameter tree from ``seed`` (call under ``jax.jit``:
    one program, made on the device). He-normal convolutions, folded-BN
    affine near identity, stored in ``dtype`` — the type they are served
    in, so the program and the reference read identical values. One
    draw of standard normals is cut into the leaves: a program of one
    random operation compiles in seconds where 290 took a minute."""
    table = conv_table(config)
    classes = int(config["num_classes"])
    shapes = []
    for _, kh, kw, cin, cout in table:
        shapes += [(kh, kw, cin, cout), (cout,), (cout,)]
    shapes += [(fc_in(config), classes), (classes,)]
    sizes = [int(np.prod(s)) for s in shapes]
    flat = jax.random.normal(jax.random.PRNGKey(seed), (sum(sizes),),
                             jnp.float32)
    ends = np.cumsum(sizes)
    leaves = iter(flat[e - n:e].reshape(s)
                  for s, n, e in zip(shapes, sizes, ends))
    tree: Dict = {}
    for path, kh, kw, cin, _ in table:
        mod, name = path.split(".")
        w, scale, bias = next(leaves), next(leaves), next(leaves)
        tree.setdefault(mod, {})[name] = {
            "w": (w * float(np.sqrt(2.0 / (kh * kw * cin)))).astype(dtype),
            "scale": (1.0 + 0.05 * scale).astype(dtype),
            "bias": (0.05 * bias).astype(dtype),
        }
    tree["fc"] = {"w": (0.01 * next(leaves)).astype(dtype),
                  "b": (0.01 * next(leaves)).astype(dtype)}
    return tree

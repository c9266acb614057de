"""Inception-v3 image classification (batch-inference workload).

BASELINE config 4: "Inception-v3 frozen GraphDef batch inference over
image-bytes DataFrame" — the reference's VGG sketch
(tensorframes_snippets/read_image.py) generalized to the BASELINE's named
model. Re-designed TPU-first rather than ported:

* NHWC layout end-to-end (the TPU-native conv layout; XLA tiles the
  channel dim onto the MXU lanes).
* bfloat16 activations/weights with float32 accumulation
  (``preferred_element_type``) — the standard TPU inference recipe.
* batch-norm folded into conv scale/bias at init (this is *frozen-graph*
  inference ≙ variables-to-constants freezing, core.py:42-56, so BN is a
  constant affine).
* scoring plugs into ``map_blocks`` as a plain function program over an
  image column, like every other workload.

Architecture follows the Inception-v3 paper (Szegedy et al. 2015): stem,
3×block-A (35×35), grid-reduction-B, 4×block-C (17×17, factorized 7×1/1×7),
grid-reduction-D, 2×block-E (8×8), global average pool, dense classifier.
A ``channel_scale`` knob shrinks widths for tests; ``tiny()`` runs on
75×75 inputs in seconds on CPU.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_DN = ("NHWC", "HWIO", "NHWC")


@dataclasses.dataclass(frozen=True)
class InceptionConfig:
    num_classes: int = 1000
    image_size: int = 299
    channel_scale: float = 1.0
    compute_dtype: str = "bfloat16"  # activations/weights; accum is f32

    def ch(self, c: int) -> int:
        """Scaled channel count, rounded up to a multiple of 8 (keeps the
        last dim MXU/VPU lane-aligned even for tiny test configs)."""
        return max(8, int(round(c * self.channel_scale / 8.0)) * 8)


def inception_v3(**kw) -> InceptionConfig:
    return InceptionConfig(**kw)


def tiny(**kw) -> InceptionConfig:
    kw.setdefault("num_classes", 10)
    kw.setdefault("image_size", 75)
    kw.setdefault("channel_scale", 0.125)
    kw.setdefault("compute_dtype", "float32")
    return InceptionConfig(**kw)


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------

def _conv_init(key, kh: int, kw: int, cin: int, cout: int, dtype) -> Dict:
    """He-normal conv weight + the folded-BN affine (scale, bias)."""
    fan_in = kh * kw * cin
    w = jax.random.normal(key, (kh, kw, cin, cout), jnp.float32)
    w = (w * np.sqrt(2.0 / fan_in)).astype(dtype)
    # frozen BN folds to an affine; identity-initialized here (random
    # weights — the bench measures compute, not accuracy)
    return {"w": w, "scale": jnp.ones((cout,), dtype), "bias": jnp.zeros((cout,), dtype)}


class _KeyGen:
    def __init__(self, seed: int):
        self._key = jax.random.PRNGKey(seed)

    def __call__(self):
        self._key, sub = jax.random.split(self._key)
        return sub


def init_params(cfg: InceptionConfig, seed: int = 0) -> Dict:
    """Build the full parameter tree. Layer names mirror the paper's
    mixed-block structure so shardings/checkpoints address them stably."""
    kg = _KeyGen(seed)
    dt = jnp.dtype(cfg.compute_dtype)
    c = cfg.ch

    def conv(kh, kw, cin, cout):
        return _conv_init(kg(), kh, kw, cin, cout, dt)

    p: Dict = {}
    # -- stem ---------------------------------------------------------------
    p["stem"] = {
        "c1": conv(3, 3, 3, c(32)),        # /2
        "c2": conv(3, 3, c(32), c(32)),
        "c3": conv(3, 3, c(32), c(64)),    # SAME
        # maxpool /2
        "c4": conv(1, 1, c(64), c(80)),
        "c5": conv(3, 3, c(80), c(192)),
        # maxpool /2
    }
    cur = c(192)

    # -- 3 × block A (pool_proj 32, 64, 64) ---------------------------------
    for i, pool_ch in enumerate([32, 64, 64]):
        p[f"mixed_a{i}"] = {
            "b1": conv(1, 1, cur, c(64)),
            "b5_1": conv(1, 1, cur, c(48)),
            "b5_2": conv(5, 5, c(48), c(64)),
            "b3_1": conv(1, 1, cur, c(64)),
            "b3_2": conv(3, 3, c(64), c(96)),
            "b3_3": conv(3, 3, c(96), c(96)),
            "bp": conv(1, 1, cur, c(pool_ch)),
        }
        cur = c(64) + c(64) + c(96) + c(pool_ch)

    # -- grid reduction B ---------------------------------------------------
    p["mixed_b"] = {
        "b3": conv(3, 3, cur, c(384)),          # /2 VALID
        "bd_1": conv(1, 1, cur, c(64)),
        "bd_2": conv(3, 3, c(64), c(96)),
        "bd_3": conv(3, 3, c(96), c(96)),       # /2 VALID
        # maxpool /2
    }
    cur = c(384) + c(96) + cur

    # -- 4 × block C (7×1/1×7 factorized; c7 = 128,160,160,192) -------------
    for i, c7 in enumerate([128, 160, 160, 192]):
        p[f"mixed_c{i}"] = {
            "b1": conv(1, 1, cur, c(192)),
            "b7_1": conv(1, 1, cur, c(c7)),
            "b7_2": conv(1, 7, c(c7), c(c7)),
            "b7_3": conv(7, 1, c(c7), c(192)),
            "bd_1": conv(1, 1, cur, c(c7)),
            "bd_2": conv(7, 1, c(c7), c(c7)),
            "bd_3": conv(1, 7, c(c7), c(c7)),
            "bd_4": conv(7, 1, c(c7), c(c7)),
            "bd_5": conv(1, 7, c(c7), c(192)),
            "bp": conv(1, 1, cur, c(192)),
        }
        cur = 4 * c(192)

    # -- grid reduction D ---------------------------------------------------
    p["mixed_d"] = {
        "b3_1": conv(1, 1, cur, c(192)),
        "b3_2": conv(3, 3, c(192), c(320)),     # /2 VALID
        "b7_1": conv(1, 1, cur, c(192)),
        "b7_2": conv(1, 7, c(192), c(192)),
        "b7_3": conv(7, 1, c(192), c(192)),
        "b7_4": conv(3, 3, c(192), c(192)),     # /2 VALID
        # maxpool /2
    }
    cur = c(320) + c(192) + cur

    # -- 2 × block E --------------------------------------------------------
    for i in range(2):
        p[f"mixed_e{i}"] = {
            "b1": conv(1, 1, cur, c(320)),
            "b3_1": conv(1, 1, cur, c(384)),
            "b3_2a": conv(1, 3, c(384), c(384)),
            "b3_2b": conv(3, 1, c(384), c(384)),
            "bd_1": conv(1, 1, cur, c(448)),
            "bd_2": conv(3, 3, c(448), c(384)),
            "bd_3a": conv(1, 3, c(384), c(384)),
            "bd_3b": conv(3, 1, c(384), c(384)),
            "bp": conv(1, 1, cur, c(192)),
        }
        cur = c(320) + 2 * c(384) + 2 * c(384) + c(192)

    # -- classifier ---------------------------------------------------------
    wk = kg()
    p["fc"] = {
        "w": (jax.random.normal(wk, (cur, cfg.num_classes), jnp.float32) * 0.01).astype(dt),
        "b": jnp.zeros((cfg.num_classes,), dt),
    }
    return p


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _conv2d(p, x, stride: int = 1, padding="SAME"):
    """conv + folded-BN affine + relu; f32 accumulation on the MXU."""
    from ..ops.quantize import asarray as _qw

    y = lax.conv_general_dilated(
        x,
        _qw(p["w"], x.dtype),
        (stride, stride),
        padding,
        dimension_numbers=_DN,
        preferred_element_type=jnp.float32,
    )
    y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return jax.nn.relu(y).astype(x.dtype)


def _maxpool(x, window: int = 3, stride: int = 2, padding="VALID"):
    return lax.reduce_window(
        x,
        -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min,
        lax.max,
        (1, window, window, 1),
        (1, stride, stride, 1),
        padding,
    )


def _avgpool3(x):
    """3x3 SAME average pool with a trace-time numpy divisor — feeding
    ``reduce_window(ones)`` to XLA instead makes the compiler
    constant-fold a full-size reduce-window per shape (the 8-12s
    slow_operation_alarm stalls in the inception stem; ops/windows.py)."""
    from ..ops.windows import same_pool_counts

    s = lax.reduce_window(
        x.astype(jnp.float32), 0.0, lax.add, (1, 3, 3, 1), (1, 1, 1, 1), "SAME"
    )
    n = same_pool_counts(int(x.shape[1]), int(x.shape[2]), 3, 3)
    return (s / n).astype(x.dtype)


def _block_a(p, x):
    b1 = _conv2d(p["b1"], x)
    b5 = _conv2d(p["b5_2"], _conv2d(p["b5_1"], x))
    bd = _conv2d(p["b3_3"], _conv2d(p["b3_2"], _conv2d(p["b3_1"], x)))
    bp = _conv2d(p["bp"], _avgpool3(x))
    return jnp.concatenate([b1, b5, bd, bp], axis=-1)


def _block_b(p, x):
    b3 = _conv2d(p["b3"], x, stride=2, padding="VALID")
    bd = _conv2d(
        p["bd_3"],
        _conv2d(p["bd_2"], _conv2d(p["bd_1"], x)),
        stride=2,
        padding="VALID",
    )
    bp = _maxpool(x)
    return jnp.concatenate([b3, bd, bp], axis=-1)


def _block_c(p, x):
    b1 = _conv2d(p["b1"], x)
    b7 = _conv2d(p["b7_3"], _conv2d(p["b7_2"], _conv2d(p["b7_1"], x)))
    bd = x
    for k in ("bd_1", "bd_2", "bd_3", "bd_4", "bd_5"):
        bd = _conv2d(p[k], bd)
    bp = _conv2d(p["bp"], _avgpool3(x))
    return jnp.concatenate([b1, b7, bd, bp], axis=-1)


def _block_d(p, x):
    b3 = _conv2d(p["b3_2"], _conv2d(p["b3_1"], x), stride=2, padding="VALID")
    b7 = x
    for k in ("b7_1", "b7_2", "b7_3"):
        b7 = _conv2d(p[k], b7)
    b7 = _conv2d(p["b7_4"], b7, stride=2, padding="VALID")
    bp = _maxpool(x)
    return jnp.concatenate([b3, b7, bp], axis=-1)


def _block_e(p, x):
    b1 = _conv2d(p["b1"], x)
    b3 = _conv2d(p["b3_1"], x)
    b3 = jnp.concatenate(
        [_conv2d(p["b3_2a"], b3), _conv2d(p["b3_2b"], b3)], axis=-1
    )
    bd = _conv2d(p["bd_2"], _conv2d(p["bd_1"], x))
    bd = jnp.concatenate(
        [_conv2d(p["bd_3a"], bd), _conv2d(p["bd_3b"], bd)], axis=-1
    )
    bp = _conv2d(p["bp"], _avgpool3(x))
    return jnp.concatenate([b1, b3, bd, bp], axis=-1)


def forward(cfg: InceptionConfig, params: Dict, images: jnp.ndarray) -> jnp.ndarray:
    """images [n, H, W, 3] float → logits [n, num_classes] (float32)."""
    # scopes are trace-time names on the ops, after the paper's modules
    # (stem, mixed_5b … mixed_7c, logits): what a profile calls them,
    # no run cost
    with jax.named_scope("stem"):
        x = images.astype(jnp.dtype(cfg.compute_dtype))
        s = params["stem"]
        x = _conv2d(s["c1"], x, stride=2, padding="VALID")
        x = _conv2d(s["c2"], x, padding="VALID")
        x = _conv2d(s["c3"], x)
        x = _maxpool(x)
        x = _conv2d(s["c4"], x)
        x = _conv2d(s["c5"], x, padding="VALID")
        x = _maxpool(x)
    for i, scope in enumerate(("mixed_5b", "mixed_5c", "mixed_5d")):
        with jax.named_scope(scope):
            x = _block_a(params[f"mixed_a{i}"], x)
    with jax.named_scope("mixed_6a"):
        x = _block_b(params["mixed_b"], x)
    for i, scope in enumerate(
            ("mixed_6b", "mixed_6c", "mixed_6d", "mixed_6e")):
        with jax.named_scope(scope):
            x = _block_c(params[f"mixed_c{i}"], x)
    with jax.named_scope("mixed_7a"):
        x = _block_d(params["mixed_d"], x)
    for i, scope in enumerate(("mixed_7b", "mixed_7c")):
        with jax.named_scope(scope):
            x = _block_e(params[f"mixed_e{i}"], x)
    with jax.named_scope("logits"):
        # global average pool
        x = jnp.mean(x.astype(jnp.float32), axis=(1, 2))
        from ..ops.quantize import asarray as _qw

        fc = params["fc"]
        return x @ _qw(fc["w"], jnp.float32) + fc["b"].astype(jnp.float32)


# ---------------------------------------------------------------------------
# map_blocks program + synthetic data
# ---------------------------------------------------------------------------

def scoring_program(cfg: InceptionConfig, params: Dict):
    """A map_blocks program: image block [n, H, W, 3] → {"scores", "label"}.

    Params are closure-captured constants (≙ frozen-graph inference,
    core.py:42-56); the whole network compiles into one XLA program per
    block shape.
    """

    def program(images):
        logits = forward(cfg, params, images)
        return {
            "scores": jax.nn.softmax(logits, axis=-1).astype(jnp.float32),
            "label": jnp.argmax(logits, axis=-1).astype(jnp.int32),
        }

    return program


def synthetic_images(
    cfg: InceptionConfig, n: int, seed: int = 0
) -> np.ndarray:
    rng = np.random.default_rng(seed)
    side = cfg.image_size
    return rng.standard_normal((n, side, side, 3), dtype=np.float32)


def param_count(params) -> int:
    from ..ops.quantize import QuantizedTensor

    total = 0
    for v in jax.tree_util.tree_leaves(
        params, is_leaf=lambda x: isinstance(x, QuantizedTensor)
    ):
        shape = v.q.shape if isinstance(v, QuantizedTensor) else v.shape
        total += int(np.prod(shape))
    return total


def quantize_params(params: Dict) -> Dict:
    """Weight-only int8 for conv/dense weights; the folded-BN scale/bias
    and fc bias stay full precision (rank < 2)."""
    from ..ops.quantize import quantize_tree

    return quantize_tree(params)

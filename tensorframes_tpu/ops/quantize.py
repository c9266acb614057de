"""Weight-only int8 quantization for inference.

Model scoring through the verbs is frozen-graph inference (params are
closure-captured constants ≙ variables-to-constants freezing,
core.py:42-56). On TPU those frozen weights live in HBM, and HBM
bandwidth — not MXU FLOPs — bounds small-batch serving. Symmetric
per-channel int8 storage cuts weight traffic 4× vs f32 (2× vs bf16);
XLA fuses the dequantize-convert into the consuming matmul/conv, so the
compute still runs in bf16/f32 on the MXU with full-precision scales.

``QuantizedTensor`` is a pytree, so quantized parameter trees flow
through ``jax.jit``, shardings, and checkpoints like any other params.
``quantize_tree`` converts a whole parameter tree (floating arrays with
rank >= min_rank); ``asarray`` is the read-side accessor models use so
one forward pass serves both plain and quantized trees.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import is_tpu_backend


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QuantizedTensor:
    """Symmetric per-channel int8 weight: ``q * scale ≈ w``.

    ``scale`` broadcasts against ``q`` (kept with singleton dims), so
    dequantization is one fused multiply."""

    q: jnp.ndarray        # int8
    scale: jnp.ndarray    # f32, broadcastable to q's shape

    @property
    def shape(self):
        return self.q.shape

    @property
    def ndim(self):
        return self.q.ndim

    @property
    def nbytes(self) -> int:
        return int(np.prod(self.q.shape)) + 4 * int(np.prod(self.scale.shape))

    def dequantize(self, dtype=jnp.float32) -> jnp.ndarray:
        return (self.q.astype(jnp.float32) * self.scale).astype(dtype)

    def tree_flatten(self):
        return (self.q, self.scale), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def quantize(w, channel_axis=-1) -> QuantizedTensor:
    """Symmetric per-channel int8: scales are per-slice max/127 along
    every axis EXCEPT ``channel_axis`` (the output-feature axis, whose
    per-channel dynamic range is what matters for matmul accuracy).
    ``channel_axis`` may be a tuple for weights whose channels span
    several axes (depthwise filters ``[H,W,C,M]`` keep ``(2, 3)``)."""
    w = jnp.asarray(w)
    if not jnp.issubdtype(w.dtype, jnp.floating):
        raise TypeError(f"quantize expects a floating array, got {w.dtype}")
    axes = (
        (channel_axis,) if isinstance(channel_axis, int) else tuple(channel_axis)
    )
    keep = {a % w.ndim for a in axes}
    reduce_axes = tuple(i for i in range(w.ndim) if i not in keep)
    w32 = w.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(w32), axis=reduce_axes, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(w32 / scale), -127, 127).astype(jnp.int8)
    return QuantizedTensor(q, scale)


def asarray(w, dtype=jnp.float32) -> jnp.ndarray:
    """Read-side accessor: dequantize if quantized, else cast. Models use
    this so one forward serves plain and quantized parameter trees."""
    if isinstance(w, QuantizedTensor):
        return w.dequantize(dtype)
    return jnp.asarray(w).astype(dtype)


def matmul(x: jnp.ndarray, w) -> jnp.ndarray:
    """``x @ w`` with STRUCTURAL dequantization fusion for quantized
    weights (VERDICT r3 #4: ``asarray`` relied on XLA *choosing* to fuse
    the dequantize into the dot; on a compute-bound config it instead
    materialized a full-precision weight copy, making int8 pure
    overhead).

    For a per-OUTPUT-channel quantized 2D weight the scale commutes out
    of the contraction::

        x @ (q * s)  ==  (x @ q.astype(x.dtype)) * s

    so the int8 weights stream from HBM and convert on-chip inside the
    dot fusion; the scale applies to the (much smaller) result. The
    product runs in f32 before casting back, preserving the scales'
    precision. Falls back to plain dequantize-then-matmul for scale
    layouts that span contracted axes."""
    if not isinstance(w, QuantizedTensor):
        return x @ jnp.asarray(w).astype(x.dtype)
    # scale commutes iff it is constant along every contracted axis of w
    # (all axes but the last): quantize(channel_axis=-1) keeps them as
    # singleton dims
    if w.q.ndim != 2 or w.scale.shape[:-1] != (1,) * (w.q.ndim - 1):
        return x @ w.dequantize(x.dtype)
    if _pallas_int8_eligible(x, w):
        # the probe in _pallas_int8_eligible already validated the
        # kernel family eagerly — no try/except here, because under an
        # outer jax.jit (how models call this) tracing cannot catch a
        # downstream Mosaic failure anyway
        return matmul_pallas_int8(x, w)
    out = x @ w.q.astype(x.dtype)
    scale = w.scale.reshape(-1)
    return (out.astype(jnp.float32) * scale).astype(x.dtype)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def matmul_pallas_int8(
    x: jnp.ndarray,
    w: QuantizedTensor,
    tile_n: int = 256,
    tile_k: int = 256,
    tile_m: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """``x @ w`` with the int8 weight dequantized INSIDE a pallas
    kernel: each weight tile streams HBM→VMEM as int8 (the whole point
    — 4× less weight traffic than f32, 2× less than bf16) and converts
    on-chip right before the MXU dot; the per-output-channel scale
    multiplies the accumulator on the last k step.

    Exists because :func:`matmul`'s structural fusion still leaves the
    convert placement to XLA, and the r3 chip run measured int8 ≈ f32
    there — consistent with a materialized wide copy. This kernel makes
    the int8 byte saving unconditional. Fully tiled over (m, n, k) with
    k innermost (sequential accumulation into the output block), so
    VMEM holds only one tile per operand regardless of activation size.
    Gated behind ``config.pallas_int8_matmul`` (off by default until a
    chip cell adjudicates it — ``chip_smoke.py`` compiles it once and
    reports the outcome); shapes: x [*, k], w.q [k, n], per-output-channel
    scales. Same index-map x64 discipline as ops/segment.py (``i - i``
    is an i32 zero under jax x64)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    assert w.q.ndim == 2 and w.scale.shape[:-1] == (1,)
    lead = x.shape[:-1]
    k = x.shape[-1]
    n = w.q.shape[1]
    m = int(np.prod(lead)) if lead else 1
    x2 = x.reshape(m, k)

    t_m = tile_m if m > tile_m else _round_up(max(m, 1), 8)
    m_pad = _round_up(max(m, 1), t_m)
    k_pad = _round_up(k, tile_k)
    n_pad = _round_up(n, tile_n)
    xp = jnp.zeros((m_pad, k_pad), x.dtype).at[:m, :k].set(x2)
    qp = jnp.zeros((k_pad, n_pad), jnp.int8).at[:k, :n].set(w.q)
    sp = (
        jnp.ones((8, n_pad), jnp.float32)
        .at[:, :n]
        .set(jnp.broadcast_to(w.scale.reshape(1, n), (8, n)))
    )
    k_steps = k_pad // tile_k

    def kernel(x_ref, q_ref, s_ref, o_ref):
        ki = pl.program_id(2)

        @pl.when(ki == 0)
        def _init():
            o_ref[:] = jnp.zeros_like(o_ref)

        q_wide = q_ref[:].astype(x_ref.dtype)  # int8→wide IN VMEM
        o_ref[:] += jnp.dot(
            x_ref[:], q_wide, preferred_element_type=jnp.float32
        )

        @pl.when(ki == k_steps - 1)
        def _scale():
            o_ref[:] = o_ref[:] * s_ref[0, :][None, :]

    grid = (m_pad // t_m, n_pad // tile_n, k_steps)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (t_m, tile_k), lambda i, j, kk: (i, kk),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (tile_k, tile_n), lambda i, j, kk: (kk, j),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (8, tile_n), lambda i, j, kk: (i - i, j),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=pl.BlockSpec(
            (t_m, tile_n), lambda i, j, kk: (i, j),
            memory_space=pltpu.VMEM,
        ),
        out_shape=jax.ShapeDtypeStruct((m_pad, n_pad), jnp.float32),
        interpret=interpret,
    )(xp, qp, sp)
    return out[:m, :n].reshape(*lead, n).astype(x.dtype)


# Probe-once gate: under jax.jit (how models call matmul) a Mosaic
# compile failure surfaces at the OUTER jit's compile, where matmul's
# try/except can no longer catch it. So eligibility runs a tiny
# CONCRETE kernel once per process; if the kernel family doesn't
# compile on this toolchain, the flag disables before any traced use.
# The (m,n,k) tiling bounds every block to tile-sized VMEM, so probe
# success is shape-representative. Resettable via reset_pallas_int8().
_pallas_int8_state = {"probed": False, "ok": False}  # lint: guarded (benign race: a duplicate concurrent probe reaches the same verdict)


def reset_pallas_int8() -> None:
    """Forget the probe result (e.g. after switching backends)."""
    _pallas_int8_state["probed"] = False
    _pallas_int8_state["ok"] = False


def _pallas_int8_probe_ok() -> bool:
    if not _pallas_int8_state["probed"]:
        _pallas_int8_state["probed"] = True
        try:
            xs = jnp.ones((8, 128), jnp.bfloat16)
            ws = quantize(jnp.ones((128, 128), jnp.float32))
            jax.block_until_ready(matmul_pallas_int8(xs, ws))
            _pallas_int8_state["ok"] = True
        except Exception as e:
            import logging

            logging.getLogger(__name__).warning(
                "pallas int8 matmul probe failed — using the XLA "
                "structural fusion: %s", e,
            )
            _pallas_int8_state["ok"] = False
    return _pallas_int8_state["ok"]


#: The activation dtypes the once-per-process probe validates (ADVICE
#: r5): the probe compiles a bf16 kernel, and f32 shares its Mosaic
#: lowering family. Anything else (f64 under x64, f16, integers) was
#: never probed and could fail Mosaic INSIDE the outer jit — exactly
#: the failure the probe-once gate exists to prevent — so it takes the
#: XLA structural-fusion path instead.
_PROBED_DTYPES = (jnp.bfloat16, jnp.float32)


def _pallas_dtype_ok(dtype) -> bool:
    """True when ``dtype`` belongs to the probe-validated family."""
    return any(dtype == jnp.dtype(d) for d in _PROBED_DTYPES)


def _pallas_int8_eligible(x, w) -> bool:
    from ..config import get_config

    return (
        get_config().pallas_int8_matmul
        and isinstance(w, QuantizedTensor)
        and w.q.ndim == 2
        and w.scale.shape[:-1] == (1,)
        and _pallas_dtype_ok(jnp.asarray(x).dtype)
        and is_tpu_backend()
        and _pallas_int8_probe_ok()
    )


def quantize_tree(
    params: Any,
    min_rank: int = 2,
    predicate: Optional[Callable[[tuple, jnp.ndarray], bool]] = None,
    channel_axis: int = -1,
) -> Any:
    """Quantize every floating leaf with rank >= ``min_rank`` (weights;
    biases/norms stay full precision). ``predicate(path, leaf)`` can veto
    individual leaves (e.g. keep embeddings full precision)."""

    def maybe_q(path, leaf):
        if isinstance(leaf, QuantizedTensor):
            return leaf  # idempotent on already-quantized trees
        arr = jnp.asarray(leaf)
        if not jnp.issubdtype(arr.dtype, jnp.floating) or arr.ndim < min_rank:
            return leaf
        if predicate is not None and not predicate(path, arr):
            return leaf
        return quantize(arr, channel_axis)

    # is_leaf stops tree_map from descending INTO QuantizedTensor (a
    # registered pytree) and re-quantizing its scale array
    return jax.tree_util.tree_map_with_path(
        maybe_q, params, is_leaf=lambda x: isinstance(x, QuantizedTensor)
    )


def tree_nbytes(params: Any) -> int:
    """Total parameter bytes (QuantizedTensor-aware) — the HBM footprint
    the quantization exists to shrink."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(
        params, is_leaf=lambda x: isinstance(x, QuantizedTensor)
    ):
        if isinstance(leaf, QuantizedTensor):
            total += leaf.nbytes
        else:
            arr = np.asarray(leaf) if not hasattr(leaf, "dtype") else leaf
            total += int(np.prod(arr.shape)) * arr.dtype.itemsize
    return total

"""Pallas straggler kernels, selected per segment by the plan cost model.

The bench trajectory names three hot paths the default XLA lowerings
leave on the table (ROADMAP #6): ragged ``map_rows`` (~12M rows/s vs
1B+ for fixed-shape add3), decode attention (~17k tokens/s at 512 seq —
the steady-state inner loop of the serving decode engine), and the
segment reduce PR 7 routed to a host ``np.bincount`` because XLA:CPU
serializes scatter. This package holds the purpose-built kernels:

* :mod:`.segment_reduce` — one fused pallas dispatch computing every
  (column, op) of a keyed reduction: sum/mean via the one-hot MXU
  contraction, min/max via masked VPU reductions, sorted-or-not ids.
* :mod:`.decode_attention` — paged int8-KV decode attention: the
  pages a slot's context holds stream HBM→VMEM through the
  scalar-prefetched page table, a chunk of them a fold, dequantize
  in-register, and the attention math runs in the same kernel — the
  gather→dequant→attend chain of
  ``models/generation.paged_decode_step_fn`` becomes ONE kernel with no
  materialized ``[S, pages, page, heads*hd]`` copy, reading the
  resident pool columns in the layout the KV write leaves them in.
* :mod:`.ragged_gather` — ragged row staging on device: cells move as
  one flat buffer + offsets, and the kernel scatters each shape
  group's rows into its padded batch in VMEM, replacing the per-group
  host ``np.stack`` + transfer of the ragged ``map_rows`` path.

**Selection is a counted cost-model decision** (``plan/rules.py``:
``decide_segment_reduce`` / ``decide_decode_attention`` /
``decide_ragged_gather`` → ``pallas_*`` decision values), never an
unconditional dispatch. Which kernels a backend may select is ONE
table, :func:`selectable` — the ``decide_*`` functions and
``chip_smoke.py`` both read it: on a TPU the kernels Mosaic compiles
(:data:`TPU_SELECTABLE`), everywhere under ``TFTPU_PALLAS_FORCE=1``
(tests use it — the CPU pallas interpreter runs the kernels there, so
tier-1 stays green under ``JAX_PLATFORMS=cpu``). ``TFTPU_PALLAS=0``
removes them from every decision, and so does the manual switch
:func:`tensorframes_tpu.ops.segment.disable_pallas` (it invalidates
the fused-program cache, and the compile-cache fingerprint carries
:func:`fingerprint_token`, so no stale executable survives a flip).
Nothing trips that switch automatically: a kernel Mosaic refuses
raises at the call site.

Every kernel is **bit-identity-gated**: against its plain-jnp
same-tiling reference emulation always (exact by construction — the
gate that catches indexing/masking/dequant bugs), and against the
XLA/host reference wherever exactness is structural (min/max, integer
sums, and the decode-attention chain, which the pallas interpreter
reproduces bit-for-bit on CPU).
"""

from __future__ import annotations

import time
from typing import Dict

from ..observability.metrics import counter as _counter
from ..observability.metrics import histogram as _histogram
from ..utils import is_tpu_backend

__all__ = [
    "KERNELS",
    "TPU_SELECTABLE",
    "enabled",
    "selectable",
    "force_active",
    "interpret_mode",
    "fingerprint_token",
    "note_dispatch",
    "build_timer",
]

#: The registered kernel names — one counted dispatch series each, and
#: the vocabulary of the ``pallas_*`` cost-model decision values.
KERNELS = ("segment_reduce", "decode_attn", "ragged_gather")

#: The kernels Mosaic compiles on a TPU (v5e, jax 0.9.0 / libtpu
#: 0.0.34 — the chip run recorded in CHANGES.md PR 21). A kernel not
#: listed here is never selected on a TPU; ``chip_smoke.py`` requires a
#: non-zero dispatch count for every kernel that is. ``ragged_gather``
#: is out: its ``(1, length)`` output block over ``[g, length]`` breaks
#: the TPU lowering's (8, 128) block rule, and its 1-D HBM slice at an
#: element offset fails ``tpu.memref_slice`` verification.
TPU_SELECTABLE = ("segment_reduce", "decode_attn")

# Pre-registered at import (the `# kernels |` bench summary and the
# exposition must always carry the family — a process that never
# dispatched a kernel reads 0, the series does not vanish).
DISPATCHES = {
    k: _counter(
        "tftpu_kernels_dispatch_total",
        "Pallas straggler-kernel dispatches, by kernel",
        labels={"kernel": k},
    )
    for k in KERNELS
}
INTERPRET_FALLBACKS = {
    k: _counter(
        "tftpu_kernels_interpret_fallback_total",
        "Kernel dispatches that ran on the CPU pallas interpreter "
        "instead of a compiled Mosaic kernel, by kernel",
        labels={"kernel": k},
    )
    for k in KERNELS
}
BUILD_SECONDS = _histogram(
    "tftpu_kernels_build_seconds",
    "Wall-clock of building (tracing + first-dispatch compiling) one "
    "straggler-kernel call",
)


def enabled() -> bool:
    """True when the straggler kernels may be selected at all: the
    ``TFTPU_PALLAS`` config switch is on AND the process-wide manual
    switch is not thrown (``ops.segment.disable_pallas`` — one switch
    covers every pallas family, and throwing it clears the
    fused-program cache so no stale trace replays)."""
    from ..config import get_config
    from ..ops import segment as _segment

    return bool(get_config().pallas_kernels) and _segment.pallas_enabled()


def force_active() -> bool:
    """``TFTPU_PALLAS_FORCE`` — select kernels even off-TPU (the pallas
    interpreter runs them). The bit-identity test hook."""
    from ..config import get_config

    return bool(get_config().pallas_force)


def selectable(kernel: str) -> bool:
    """May the cost model select ``kernel`` on this backend right now?
    The single table behind every ``decide_*`` function and the chip
    smoke's dispatch check."""
    if kernel not in KERNELS:
        raise KeyError(f"unknown kernel {kernel!r}; known: {KERNELS}")
    if not enabled():
        return False
    if force_active():
        return True
    return is_tpu_backend() and kernel in TPU_SELECTABLE


def interpret_mode() -> bool:
    """True when kernels must run on the pallas CPU interpreter (the
    tier-1 configuration), False on a TPU (Mosaic compiles them). Any
    other backend raises: interpreting there would report a kernel as
    running where none is."""
    import jax

    if is_tpu_backend():
        return False
    backend = jax.default_backend()
    if backend != "cpu":
        raise RuntimeError(
            f"pallas kernels run on a TPU (Mosaic) or on CPU (the "
            f"interpreter); backend {backend!r} is neither"
        )
    return True


def fingerprint_token() -> Dict[str, object]:
    """The kernel-selection state that must key every compiled
    executable (folded into the compile-cache fingerprint's env slot):
    a ``disable_pallas()`` flip, a ``TFTPU_PALLAS``/``_FORCE`` change,
    or moving between interpreter and Mosaic must all miss cleanly —
    a store hit across any of them would replay a stale lowering."""
    return {
        "enabled": enabled(),
        "force": force_active(),
        "interpret": interpret_mode(),
    }


def note_dispatch(kernel: str, interpret: bool) -> None:
    """Count one kernel dispatch (and its interpreter fallback)."""
    DISPATCHES[kernel].inc()
    if interpret:
        INTERPRET_FALLBACKS[kernel].inc()


class build_timer:
    """``with build_timer(): ...`` — records kernel build wall-clock."""

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        BUILD_SECONDS.observe(time.perf_counter() - self._t0)
        return False

"""Pallas kernels for the hot paths XLA's default lowerings leave slow.

* :mod:`.segment_reduce` — one fused pallas dispatch computing every
  (column, op) of a keyed reduction: sum/mean via the one-hot MXU
  contraction, min/max via masked VPU reductions, sorted-or-not ids.
* :mod:`.decode_attention` — paged int8-KV decode attention: the
  pages a slot's context holds stream HBM→VMEM through the
  scalar-prefetched page table, a chunk of them a fold, dequantize
  in-register, and the attention math runs in the same kernel — no
  materialized ``[S, pages, page, heads*hd]`` copy, reading the
  resident pool columns in the layout the KV write leaves them in.
* :mod:`.expert_matmul` — the served expert layer's grouped matmul:
  rows sorted by expert against each held expert's matrix, a row tile
  a grid step (``megablox.gmm`` with tiles for the expert widths), in
  ``lax.ragged_dot``'s place.
* :mod:`.packed_attention` — the packed prefill's causal attention over
  int8 KV: one call a layer, a grid step a 64-row query block folding
  its own prompt's key blocks, in place of ``_band_attention``'s XLA
  loop of one block pair an iteration.

**One rule decides whether a kernel runs: what the process can observe
about its backend, and the call's own operands.** :func:`selectable`
is the whole of the first half — the kernels are enabled
(``TFTPU_PALLAS``, and the manual switch
:func:`tensorframes_tpu.ops.segment.disable_pallas`), and either the
backend is a TPU (Mosaic compiles every registered kernel; one it
refuses raises at the call site, nothing falls back) or the test hook
``TFTPU_PALLAS_FORCE=1`` puts them on the CPU pallas interpreter. The
second half is the kernel's own ``eligible`` / shape check. The call
sites ask here — ``ops.attention.paged_decode_attention``,
``ops.attention.blockwise_attention`` and ``models/moe.routed_experts``
at trace time,
``plan/rules.decide_segment_reduce`` per reduction — and
``chip_smoke.py`` checks the dispatch counters against the same table.
Nothing is timed to choose a kernel and nothing about the choice is
persisted; the compile-cache fingerprint carries
:func:`fingerprint_token`, so no executable survives a change of the
answer.

Every kernel is gated against its plain-jnp same-tiling emulation
bitwise (what catches indexing, masking and dequant bugs) and against
the XLA/host reference: exactly where that is structural (min/max,
integer sums), to float tolerance for the decode attention's online
softmax. The expert matmul is a library kernel (``megablox.gmm``) with
this package's tiles: it is gated against ``lax.ragged_dot`` to float32
rounding, and row for row against itself (batched equals solo). The
packed attention is gated against ``ops/attention._band_attention``, its
XLA lowering, to float rounding, and bitwise against itself (a prompt
alone equals the prompt among neighbours).
"""

from __future__ import annotations

import time
from typing import Dict

from ..observability.metrics import counter as _counter
from ..observability.metrics import histogram as _histogram
from ..utils import is_tpu_backend

__all__ = [
    "KERNELS",
    "enabled",
    "selectable",
    "force_active",
    "interpret_mode",
    "fingerprint_token",
    "note_dispatch",
    "build_timer",
]

#: The registered kernel names — one counted dispatch series each.
#: Every one compiles under Mosaic (v5e, jax 0.9.0 / libtpu 0.0.34);
#: ``chip_smoke.py`` requires a non-zero dispatch count for each.
KERNELS = ("segment_reduce", "decode_attn", "expert_matmul", "packed_attn")

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
    """May ``kernel`` run on this backend right now? The one table the
    call sites and the chip smoke's dispatch check read: enabled, and
    either a TPU or the interpreter forced by the test hook."""
    if kernel not in KERNELS:
        raise KeyError(f"unknown kernel {kernel!r}; known: {KERNELS}")
    return enabled() and (force_active() or is_tpu_backend())


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

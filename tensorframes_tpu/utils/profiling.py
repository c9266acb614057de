"""Tracing / profiling.

The reference has nothing beyond log4j levels (SURVEY.md §5: manual timing
only in ``ignore``-d perf suites); the survey's build note makes the
TPU-native equivalent first-class: per-verb wall-clock metrics plus
``jax.profiler`` device traces.

* ``span(name, rows=...)`` — context manager accumulating wall-clock,
  call count and row throughput per named operation; user code can add
  its own.
* ``record(name, seconds, rows, t0=...)`` — the same for code that times
  itself: the verbs call it once per invocation, with the instant they
  started, so the timeline span sits where the work was.
* ``metrics()`` / ``report()`` / ``reset_metrics()`` — inspect the
  accumulated stats (``report()`` is the profiling sibling of
  ``explain``): rates over the host's wall clock. Shares of a chip's
  peak come from device time in a profiler trace (``benchmark/``), not
  from here.
* ``trace(logdir)`` — context manager around ``jax.profiler.trace``:
  captures a TensorBoard-viewable device trace (XLA ops, HBM transfers)
  when the runtime supports it; a no-op (with a log line) otherwise.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
import warnings
from typing import Dict, Iterator, Optional

from .logging import get_logger

logger = get_logger(__name__)


_events_mod = None
_latency_mod = None


def _trace_events():
    """The structured event tracer (observability/events.py), imported
    lazily (then cached) to keep utils free of package-level import
    edges. Spans land on the Chrome-trace timeline whenever tracing is
    enabled — the aggregate table here and the timeline there come from
    the same instrumentation points."""
    global _events_mod
    if _events_mod is None:
        from ..observability import events

        _events_mod = events
    return _events_mod


def _latency(name: str, seconds: float) -> None:
    """Feed verb-named spans into the latency-quantile histograms
    (observability/latency.py) — same lazy-import shape as the tracer
    hook; non-verb names are ignored there with one dict lookup."""
    global _latency_mod
    if _latency_mod is None:
        from ..observability import latency

        _latency_mod = latency
    _latency_mod.observe_verb(name, seconds)


@dataclasses.dataclass
class SpanStats:
    calls: int = 0
    seconds: float = 0.0
    rows: int = 0
    flops: float = 0.0  # model FLOPs executed under this span (if known)
    bytes: float = 0.0  # XLA-cost-model bytes accessed (if known)

    @property
    def rows_per_sec(self) -> float:
        return self.rows / self.seconds if self.seconds > 0 else 0.0

    @property
    def flops_per_sec(self) -> float:
        return self.flops / self.seconds if self.seconds > 0 else 0.0

    @property
    def bytes_per_sec(self) -> float:
        return self.bytes / self.seconds if self.seconds > 0 else 0.0


_lock = threading.Lock()
_stats: Dict[str, SpanStats] = {}


@contextlib.contextmanager
def span(name: str, rows: int = 0) -> Iterator[None]:
    """Accumulate wall-clock (and optional row count) under ``name``.
    When structured tracing is enabled (``observability.events``), the
    span also lands on the Chrome-trace timeline as a complete event."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        dt = time.perf_counter() - t0
        with _lock:
            s = _stats.setdefault(name, SpanStats())
            s.calls += 1
            s.seconds += dt
            s.rows += rows
        _latency(name, dt)
        ev = _trace_events()
        if ev.TRACER.enabled:
            ev.TRACER.emit_complete(
                name, t0, dt, args={"rows": rows} if rows else None,
                cat="profiling",
            )


def record(
    name: str,
    seconds: float,
    rows: int = 0,
    flops: float = 0.0,
    bytes_accessed: Optional[float] = None,
    t0: Optional[float] = None,
    **kwargs: float,
) -> None:
    """Directly accumulate one measurement (for code that times itself).
    ``flops``/``bytes_accessed`` let callers attach XLA cost-model
    counts (e.g. from ``Program.flops_per_row``/``bytes_per_row``) so
    :func:`report` can print achieved FLOP/s and HBM GB/s. ``t0`` is
    the ``perf_counter`` instant the timed stretch began: with it the
    timeline span sits exactly where the work was, so the spans emitted
    inside the stretch nest in it.

    ``bytes=`` is the deprecated spelling of ``bytes_accessed`` (it
    shadowed the builtin); accepted for one release with a
    DeprecationWarning."""
    if "bytes" in kwargs:
        warnings.warn(
            "profiling.record(bytes=...) is deprecated; use "
            "bytes_accessed= (the old name shadowed the builtin)",
            DeprecationWarning,
            stacklevel=2,
        )
        if bytes_accessed is not None:
            raise TypeError(
                "record() got both bytes_accessed= and deprecated bytes="
            )
        bytes_accessed = kwargs.pop("bytes")
    if kwargs:
        raise TypeError(
            f"record() got unexpected keyword arguments {sorted(kwargs)}"
        )
    if bytes_accessed is None:
        bytes_accessed = 0.0
    with _lock:
        s = _stats.setdefault(name, SpanStats())
        s.calls += 1
        s.seconds += seconds
        s.rows += rows
        s.flops += flops
        s.bytes += bytes_accessed
    _latency(name, seconds)
    ev = _trace_events()
    if ev.TRACER.enabled:
        # without t0: callers record immediately after timing, so "it
        # just ended" reconstructs the start closely enough for a
        # timeline (a few microseconds late: children may stick out)
        ev.TRACER.emit_complete(
            name, time.perf_counter() - seconds if t0 is None else t0,
            seconds,
            args={"rows": rows} if rows else None, cat="profiling",
        )


def metrics() -> Dict[str, SpanStats]:
    """Snapshot of accumulated span stats."""
    with _lock:
        return {k: dataclasses.replace(v) for k, v in _stats.items()}


def reset_metrics() -> None:
    with _lock:
        _stats.clear()


def report() -> str:
    """Human-readable per-span table (the profiling ``explain``). Spans
    carrying FLOP or byte counts get achieved GFLOP/s and GB/s over the
    host's wall clock. A share of the chip's peak is not computed here:
    that takes device time and the device's own peak, which the
    benchmark reads from a profiler trace (``benchmark/readers``)."""
    snap = metrics()
    if not snap:
        return "no spans recorded"
    any_flops = any(s.flops for s in snap.values())
    any_bytes = any(s.bytes for s in snap.values())
    name_w = max(len(k) for k in snap) + 2
    hdr = f"{'span':<{name_w}}{'calls':>7}{'seconds':>12}{'rows':>12}{'rows/s':>14}"
    if any_flops:
        hdr += f"{'GFLOP/s':>12}"
    if any_bytes:
        hdr += f"{'GB/s':>10}"

    lines = [hdr]
    for name in sorted(snap):
        s = snap[name]
        rps = f"{s.rows_per_sec:,.0f}" if s.rows else "-"
        rows = f"{s.rows:,}" if s.rows else "-"
        line = f"{name:<{name_w}}{s.calls:>7}{s.seconds:>12.4f}{rows:>12}{rps:>14}"
        if any_flops:
            line += (
                f"{s.flops_per_sec / 1e9:>12,.1f}" if s.flops else f"{'-':>12}"
            )
        if any_bytes:
            line += (
                f"{s.bytes_per_sec / 1e9:>10,.1f}" if s.bytes else f"{'-':>10}"
            )
        lines.append(line)
    return "\n".join(lines)


@contextlib.contextmanager
def trace(logdir: str) -> Iterator[None]:
    """Capture a jax.profiler device trace into ``logdir`` (TensorBoard
    format). Degrades to a no-op where the backend can't trace."""
    import jax

    started = False
    try:
        jax.profiler.start_trace(logdir)
        started = True
    except Exception as e:  # pragma: no cover — backend-dependent
        logger.warning("jax.profiler trace unavailable: %s", e)
    try:
        yield
    finally:
        if started:
            try:
                jax.profiler.stop_trace()
            except Exception as e:  # pragma: no cover
                logger.warning("jax.profiler stop_trace failed: %s", e)


@contextlib.contextmanager
def annotate(name: str) -> Iterator[None]:
    """Name a region in the device trace (shows up in TensorBoard); also
    accumulates a wall-clock span. Exceptions from the annotated body
    propagate untouched — only TraceAnnotation setup failures are
    swallowed."""
    import jax

    ann = None
    try:
        ann = jax.profiler.TraceAnnotation(name)
        ann.__enter__()
    except Exception:  # pragma: no cover — backend-dependent
        ann = None
    with span(name):
        try:
            yield
        finally:
            if ann is not None:
                try:
                    ann.__exit__(None, None, None)
                except Exception:  # pragma: no cover
                    pass

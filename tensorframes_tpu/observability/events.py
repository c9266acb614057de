"""Structured event tracing → Chrome ``trace_event`` JSON.

The aggregate side of observability lives in ``utils/profiling.py``
(per-span totals) and ``observability/metrics.py`` (counters/gauges/
histograms). This module is the **timeline** side: complete spans,
async request events, instant events, monotonic microsecond timestamps
and real thread ids, exported in the Chrome ``trace_event`` JSON format
that Perfetto (https://ui.perfetto.dev) and ``chrome://tracing`` open
directly. It layers ON TOP of ``utils/profiling.py`` — when tracing is
enabled, every ``profiling.span`` (the five verbs, checkpoint IO, …)
also lands on the timeline; disabling tracing costs one attribute check
per span.

The rule every instrumented site keeps: **on any one thread, complete
("X") spans nest properly or do not overlap**; whatever the host does
between two device programs on the hot paths lies under a leaf span;
anything that overlaps freely on a thread — a request's life, of which
dozens are open at once — is an async event (:meth:`Tracer.emit_async`,
a "b"/"e" pair with its own id), never an "X" span.

Usage::

    from tensorframes_tpu.observability import events

    events.enable()
    with events.span("ingest", rows=100_000):
        ...
    events.instant("watermark", step=7)
    events.save("trace.json")           # open in Perfetto

The buffer is bounded (``max_events``): past the cap new events are
dropped and counted (``TRACER.dropped``) — a week-long run must not eat
the host's RAM. Spans are recorded as complete ("X"-phase) events at
span END, so nesting is reconstructed by time containment per thread;
a span that never exits (crash mid-body) leaves no partial event. An
async pair is recorded whole at its end too: a full ring drops both
halves (counted), never one.

While a ``jax.profiler`` capture runs, a span opened through
:meth:`Tracer.mirrored` (and whatever a site opens with :func:`native`)
is also a profiler ``TraceMe`` of the same name: the capture's
``/host:CPU`` plane then carries it on the clock of the device planes,
so one file lines up the host's spans with the chip's programs.
"""

from __future__ import annotations

import contextlib
import functools
import itertools
import json
import os
import threading
import time
from typing import Any, Dict, Iterator, List, Optional

from ..utils import get_logger
from . import context as _context
from .metrics import counter as _counter

logger = get_logger(__name__)

__all__ = [
    "Tracer",
    "TRACER",
    "enable",
    "disable",
    "active",
    "clear",
    "span",
    "native",
    "close_native",
    "instant",
    "to_chrome_trace",
    "save",
    "save_shard",
]

#: Monotonic epoch for this process: every timestamp is microseconds
#: since this instant (Chrome traces need only a consistent monotonic
#: base; perf_counter is the highest-resolution clock available).
_EPOCH = time.perf_counter()
#: Wall-clock captured at the same instant as ``_EPOCH``: the anchor
#: that lets the cross-process merge aggregator place each process's
#: monotonic timeline on one shared real-time axis.
_EPOCH_UNIX_US = int(time.time() * 1e6)

# Events dropped at the full ring, as a registry counter (pre-registered
# so the family is always in the exposition): the in-object ``dropped``
# count is invisible to a metrics scrape, and a silently-truncated trace
# reads as "nothing else happened" — exactly the failure ISSUE 6's first
# satellite names.
_EVENTS_DROPPED = _counter(
    "tftpu_trace_events_dropped_total",
    "Trace events discarded because the tracer ring was full",
)


def _us(t_perf: float) -> float:
    return (t_perf - _EPOCH) * 1e6


def _clean_args(args: Dict[str, Any]) -> Dict[str, Any]:
    """Coerce event args to strict-JSON-safe values at emit time: numpy
    scalars via .item(), non-finite floats to null (strict JSON has no
    NaN/Inf token), anything else to str. A week of collected events
    must never make the end-of-run export raise."""
    import math

    out: Dict[str, Any] = {}
    for k, v in args.items():
        if not isinstance(v, (str, int, float, bool)) and v is not None:
            item = getattr(v, "item", None)
            if callable(item):
                try:
                    v = item()
                except Exception:
                    v = str(v)
            if not isinstance(v, (str, int, float, bool)) and v is not None:
                v = str(v)
        if isinstance(v, float) and not math.isfinite(v):
            v = None
        out[k] = v
    return out


class Tracer:
    """Bounded in-memory trace_event collector (thread-safe)."""

    def __init__(self, max_events: int = 200_000):
        self.max_events = max_events
        self._lock = threading.Lock()
        self._events: List[Dict[str, Any]] = []
        self._named_threads: set = set()
        self._async_ids = itertools.count(1)
        self.dropped = 0
        self.enabled = False

    # -- lifecycle ----------------------------------------------------------

    def enable(self) -> None:
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self._named_threads.clear()
            self.dropped = 0

    # -- recording ----------------------------------------------------------

    def _append(self, ev: Dict[str, Any], tid: int,
                end: Optional[Dict[str, Any]] = None) -> None:
        """Append ``ev`` (and ``end``, the closing half of an async
        pair: both land or neither does)."""
        n = 1 if end is None else 2
        with self._lock:
            # the cap is hard: a full buffer drops the event (counted),
            # and thread_name metadata is only added when there is room
            # for it AND the event it annotates — no unbounded growth
            # from thread churn in a long run
            if len(self._events) + n > self.max_events:
                self.dropped += n
                _EVENTS_DROPPED.inc(n)
                return
            if (
                tid not in self._named_threads
                and len(self._events) + n + 1 <= self.max_events
            ):
                self._named_threads.add(tid)
                self._events.append({
                    "ph": "M",
                    "name": "thread_name",
                    "pid": ev["pid"],
                    "tid": tid,
                    "args": {"name": threading.current_thread().name},
                })
            self._events.append(ev)
            if end is not None:
                self._events.append(end)

    def emit_complete(
        self,
        name: str,
        t0_perf: float,
        dur_s: float,
        args: Optional[Dict[str, Any]] = None,
        cat: str = "tftpu",
    ) -> None:
        """Record a complete ("X") event from a perf_counter start + a
        duration — the hook ``profiling.span`` and the instrumented hot
        paths use, since they already hold both numbers."""
        if not self.enabled:
            return
        tid = threading.get_ident()
        ev: Dict[str, Any] = {
            "ph": "X",
            "name": name,
            "cat": cat,
            "ts": _us(t0_perf),
            "dur": dur_s * 1e6,
            "pid": os.getpid(),
            "tid": tid,
        }
        if args:
            ev["args"] = _clean_args(args)
        self._append(ev, tid)

    def emit_async(
        self,
        name: str,
        id: Optional[str],
        t0_perf: float,
        dur_s: float,
        args: Optional[Dict[str, Any]] = None,
        cat: str = "tftpu",
    ) -> None:
        """Record a Chrome async pair ("b" then "e", one ``id``) from a
        perf_counter start + a duration, on the clock of
        :meth:`emit_complete`. For what overlaps freely on one thread —
        request lifetimes — where "X" spans would break the nesting
        rule. The args ride the "b" event. ``id`` joins the pair (and,
        where it is a request id, the router's and the replica's events
        of one request); ``None`` draws a process-local one."""
        if not self.enabled:
            return
        tid = threading.get_ident()
        pid = os.getpid()
        if id is None:
            id = f"{pid:x}.{next(self._async_ids)}"
        begin: Dict[str, Any] = {
            "ph": "b", "name": name, "cat": cat, "id": id,
            "ts": _us(t0_perf), "pid": pid, "tid": tid,
        }
        if args:
            begin["args"] = _clean_args(args)
        self._append(begin, tid, end={
            "ph": "e", "name": name, "cat": cat, "id": id,
            "ts": _us(t0_perf + dur_s), "pid": pid, "tid": tid,
        })

    def mirrored(self, name: str, cat: str = "tftpu", **args: Any):
        """Trace the body as one complete event, and, while a
        ``jax.profiler`` capture runs, as a profiler ``TraceMe`` of the
        same name on the capture's own clock (:func:`native`). Entering
        gives the span object, whose ``args`` may still be filled in
        the body; disabled, it gives ``None`` and reads no clock."""
        if not self.enabled:
            return _NOT_MIRRORED
        return _Mirrored(self, name, cat, args)

    @contextlib.contextmanager
    def span(self, name: str, cat: str = "tftpu", **args: Any) -> Iterator[None]:
        """Trace the body as one complete event (no-op when disabled)."""
        if not self.enabled:
            yield
            return
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.emit_complete(
                name, t0, time.perf_counter() - t0,
                args=args or None, cat=cat,
            )

    def instant(self, name: str, cat: str = "tftpu", **args: Any) -> None:
        """A zero-duration marker ("i" phase, thread scope)."""
        if not self.enabled:
            return
        tid = threading.get_ident()
        ev: Dict[str, Any] = {
            "ph": "i",
            "s": "t",
            "name": name,
            "cat": cat,
            "ts": _us(time.perf_counter()),
            "pid": os.getpid(),
            "tid": tid,
        }
        if args:
            ev["args"] = _clean_args(args)
        self._append(ev, tid)

    # -- export -------------------------------------------------------------

    def to_chrome_trace(self) -> Dict[str, Any]:
        """The JSON-object trace format: ``{"traceEvents": [...]}`` plus
        metadata — accepted by Perfetto and chrome://tracing. The
        ``otherData`` stamp (run_id, process_index, wall-clock epoch)
        is the shard-correlation contract ``observability merge`` reads:
        without it a multi-process run's traces are unjoinable."""
        with self._lock:
            events = list(self._events)
            dropped = self.dropped
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {
                "producer": "tensorframes_tpu.observability.events",
                "dropped_events": dropped,
                "run_id": _context.run_id(),
                "process_index": _context.process_index(),
                "pid": os.getpid(),
                "trace_epoch_unix_us": _EPOCH_UNIX_US,
            },
        }

    def save(self, path: str) -> str:
        """Write the trace JSON to ``path`` and return it."""
        trace = self.to_chrome_trace()
        with open(path, "w") as f:
            # default=str is the last line of defense: args are cleaned
            # at emit, but an exotic leaf must degrade to a string, not
            # lose the whole collected trace at the final write
            json.dump(trace, f, default=str)
        logger.info(
            "trace: wrote %d events to %s (open in https://ui.perfetto.dev)",
            len(trace["traceEvents"]), path,
        )
        return path

    def save_shard(self, directory: str) -> str:
        """Write this process's trace as a per-process SHARD —
        ``<dir>/trace_<run_id>_p<process_index>.json`` — the file layout
        ``observability merge`` globs to rebuild a whole-run timeline.
        Every process of a run calls this against one shared directory
        (rank in the name keeps writers collision-free)."""
        os.makedirs(directory, exist_ok=True)
        path = os.path.join(
            directory,
            f"trace_{_context.run_id()}_p{_context.process_index()}.json",
        )
        return self.save(path)


@functools.lru_cache(maxsize=None)
def _traceme_class():
    try:
        from jax._src.lib import _profiler
    except Exception:  # pragma: no cover - a jaxlib without the profiler
        return None
    return getattr(_profiler, "TraceMe", None)


def native(name: str):
    """An entered profiler ``TraceMe`` named ``name`` while a
    ``jax.profiler`` capture runs, else ``None``. The capture records it
    on its ``/host:CPU`` plane, on the clock of the device planes beside
    it, when the caller passes it to :func:`close_native` on the same
    thread. Callers make one only where the tracer is enabled."""
    cls = _traceme_class()
    if cls is None or not cls.is_enabled():
        return None
    tm = cls(name)
    tm.__enter__()
    return tm


def close_native(tm) -> None:
    """End what :func:`native` opened (``None`` is a no-op)."""
    if tm is not None:
        tm.__exit__(None, None, None)


class _Mirrored:
    """The span :meth:`Tracer.mirrored` gives while tracing."""

    __slots__ = ("_tracer", "name", "cat", "args", "_t0", "_native")

    def __init__(self, tracer: Tracer, name: str, cat: str,
                 args: Dict[str, Any]):
        self._tracer, self.name, self.cat, self.args = tracer, name, cat, args

    def __enter__(self) -> "_Mirrored":
        self._native = native(self.name)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter()
        close_native(self._native)
        self._tracer.emit_complete(self.name, self._t0, t1 - self._t0,
                                   args=self.args or None, cat=self.cat)


_NOT_MIRRORED = contextlib.nullcontext()


#: Process-wide default tracer; the module-level helpers below and every
#: instrumented layer use this instance.
TRACER = Tracer()


def _abandon_buffer_after_fork() -> None:
    # forked worker: the parent's pre-fork events belong in the PARENT's
    # shard — replayed into every child shard they would appear once per
    # rank in the merged timeline. Enabled state is inherited (a tracing
    # parent wants tracing children); the monotonic/wall epoch pair stays
    # valid across fork, so child timestamps still anchor correctly.
    # No lock: the child is single-threaded at this instant.
    TRACER._events = []
    TRACER._named_threads = set()
    TRACER.dropped = 0


if hasattr(os, "register_at_fork"):  # pragma: no branch - posix
    os.register_at_fork(after_in_child=_abandon_buffer_after_fork)


def enable() -> None:
    """Start collecting events on the default tracer."""
    TRACER.enable()


def disable() -> None:
    TRACER.disable()


def active() -> bool:
    """True when the default tracer is collecting."""
    return TRACER.enabled


def clear() -> None:
    TRACER.clear()


def span(name: str, cat: str = "tftpu", **args: Any):
    """Context manager tracing the body on the default tracer."""
    return TRACER.span(name, cat=cat, **args)


def instant(name: str, cat: str = "tftpu", **args: Any) -> None:
    TRACER.instant(name, cat=cat, **args)


def to_chrome_trace() -> Dict[str, Any]:
    return TRACER.to_chrome_trace()


def save(path: str) -> str:
    return TRACER.save(path)


def save_shard(directory: str) -> str:
    """Write the default tracer's per-process shard into ``directory``."""
    return TRACER.save_shard(directory)

"""From a profiler trace (``*.xplane.pb``) to the numbers the benchmark
reports: device busy time as the union of the intervals in which an
operation ran, device time per compiled program, time per operation, and
the idle gaps with what the host was doing in them.

Read with ``jax.profiler.ProfileData`` alone. On a TPU v5e the planes
are ``/device:TPU:<n>`` with the lines ``XLA Modules`` (one event per
execution of a compiled program, named ``jit_<fn>(<fingerprint>)``) and
``XLA Ops`` (one event per operation, named by its HLO text,
``%fusion.12 = ...``); ``/host:CPU`` holds the host threads, where a
``jax.profiler.TraceAnnotation`` appears under its own name. All planes
share one clock, in nanoseconds. ``selfcheck.py`` holds this file to a
small trace recorded on the chip (``testdata/v5e_small.xplane.pb``).
"""

from __future__ import annotations

import bisect
import dataclasses
import re
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]  # start_ns, end_ns

_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_OP_NAME = re.compile(r"^%?([^\s=(]+)")
_MODULE = re.compile(r"^(.*?)(\(\d+\))?$")


@dataclasses.dataclass
class DevicePlane:
    ordinal: int
    modules: List[Tuple[str, float, float]]  # program, start_ns, end_ns
    ops: List[Tuple[str, float, float]]      # op, start_ns, end_ns


@dataclasses.dataclass
class Trace:
    devices: List[DevicePlane]
    annotations: Dict[str, List[Interval]]   # host TraceAnnotation spans


def op_name(text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` → ``fusion.12``."""
    m = _OP_NAME.match(text)
    return m.group(1) if m else text


def program_name(text: str) -> str:
    """``jit_step(8561072157832635237)`` → ``jit_step``."""
    return _MODULE.match(text).group(1)


def load(path: str, annotation_prefix: str = "bench.") -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: List[DevicePlane] = []
    annotations: Dict[str, List[Interval]] = {}
    for plane in data.planes:
        m = _DEVICE.match(plane.name)
        if m:
            dev = DevicePlane(int(m.group(1)), [], [])
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dev.modules = [
                        (program_name(e.name), e.start_ns,
                         e.start_ns + e.duration_ns) for e in line.events]
                elif line.name == "XLA Ops":
                    dev.ops = [
                        (op_name(e.name), e.start_ns,
                         e.start_ns + e.duration_ns) for e in line.events]
            devices.append(dev)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(annotation_prefix):
                        annotations.setdefault(e.name, []).append(
                            (e.start_ns, e.start_ns + e.duration_ns))
    devices.sort(key=lambda d: d.ordinal)
    return Trace(devices, annotations)


def union(intervals: Sequence[Interval],
          window: Optional[Interval] = None) -> List[Interval]:
    """Merged, sorted intervals, clipped to ``window``."""
    if window is not None:
        lo, hi = window
        intervals = [(max(a, lo), min(b, hi)) for a, b in intervals
                     if b > lo and a < hi]
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def gaps(busy: Sequence[Interval], window: Interval) -> List[Interval]:
    """The idle stretches of ``window`` between merged busy intervals."""
    out, at = [], window[0]
    for a, b in busy:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if window[1] > at:
        out.append((at, window[1]))
    return out


def label_gaps(idle: Sequence[Interval],
               host_spans: Sequence[Tuple[str, float, float]]) -> List[str]:
    """For every gap of ``idle`` the host span that covers most of it;
    of spans that cover it equally (nested ones) the shortest, which is
    the innermost, and of equally long ones the first of ``host_spans``;
    ``"(no host span)"`` where none reaches into it. One sweep: gaps and
    spans both walked in order of their starts, the spans that still
    reach into the gap in hand kept in a list. Each span enters and
    leaves that list once, so the cost is the sorting plus the pairs of
    gap and span that do overlap (``tests/test_trace_reduce.py`` holds
    the sweep to one scan of every span for every gap)."""
    spans = sorted(
        ((a, b, i, name) for i, (name, a, b) in enumerate(host_spans)
         if b > a), key=lambda s: s[0])
    out = ["(no host span)"] * len(idle)
    live: List[Tuple[float, float, int, str]] = []
    at = 0
    for k in sorted(range(len(idle)), key=lambda k: idle[k][0]):
        lo, hi = idle[k]
        live = [s for s in live if s[1] > lo]
        while at < len(spans) and spans[at][0] < hi:
            if spans[at][1] > lo:
                live.append(spans[at])
            at += 1
        best_key = (0.0, 0.0, 0)
        for a, b, i, name in live:
            cover = min(b, hi) - max(a, lo)
            if cover <= 0:
                continue
            key = (cover, -(b - a), -i)
            if key > best_key:
                out[k], best_key = name, key
    return out


def reduce(trace: Trace, window: Interval,
           host_spans: Sequence[Tuple[str, float, float]] = (),
           top: int = 10) -> Dict[str, object]:
    """Busy and idle over ``window`` (mean over the device planes), time
    per program and per operation (operation time averaged over the
    devices, as the busy time is), and the idle gaps of the first device
    labelled by ``host_spans`` (name, start_ns, end_ns on the trace's
    clock): the ``top // 2`` longest one by one, then sums per label."""
    if not trace.devices:
        raise ValueError("the trace holds no /device:TPU plane")
    n = len(trace.devices)
    busy_ns = 0.0
    op_ns: Dict[str, float] = {}
    programs: Dict[str, List[float]] = {}
    inside: Dict[str, Dict[str, float]] = {}
    for dev in trace.devices:
        merged = union([(a, b) for _, a, b in dev.ops], window)
        busy_ns += sum(b - a for a, b in merged)
        for name, a, b in dev.ops:
            if b > window[0] and a < window[1]:
                op_ns[name] = op_ns.get(name, 0.0) + (b - a) / n
        whole = sorted((a, b, name) for name, a, b in dev.modules
                       if a >= window[0] and b <= window[1])
        starts = [a for a, _, _ in whole]
        for a, b, name in whole:
            programs.setdefault(name, []).append((b - a) * 1e-9)
        for name, a, b in dev.ops:
            at = bisect.bisect_right(starts, a) - 1
            if at >= 0 and a < whole[at][1]:
                per = inside.setdefault(whole[at][2], {})
                per[name] = per.get(name, 0.0) + (b - a) * 1e-9
    first = trace.devices[0]
    idle = gaps(union([(a, b) for _, a, b in first.ops], window), window)
    labelled = [(name, (g[1] - g[0]) * 1e-9)
                for name, g in zip(label_gaps(idle, host_spans), idle)]
    longest = sorted(labelled, key=lambda x: -x[1])[:top // 2]
    sums: Dict[str, float] = {}
    for name, s in labelled:
        sums["sum_" + name] = sums.get("sum_" + name, 0.0) + s
    by_sum = sorted(sums.items(), key=lambda x: -x[1])[:top - len(longest)]
    return {
        "busy_s": busy_ns * 1e-9 / n,
        "window_s": (window[1] - window[0]) * 1e-9,
        "programs": programs,
        "op_seconds_in": inside,
        "breakdown": {
            "device_ops": [[k, v * 1e-9] for k, v in sorted(
                op_ns.items(), key=lambda x: -x[1])[:top]],
            "idle_gaps": [[k, v] for k, v in longest]
            + [[k, v] for k, v in by_sum],
        },
    }

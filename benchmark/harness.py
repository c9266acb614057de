"""What every driver shares: the manifest and the per-cell files it
names, the device gate and stamp, the table of peaks, registry and span
snapshots over the window, the profiler capture with its clock
alignment, the per-layer readers' dispatch, and the result line.

Data-driven: a cell is ``BENCHMARK.json``'s entry plus
``workloads/<cell>.json``; a configuration is its entry plus the file it
names; a per-layer metric is its entry plus ``metrics/<metric>.json``,
which names a module of ``readers/``. Nothing in this file knows a
cell, a configuration or a metric by name.
"""

from __future__ import annotations

import dataclasses
import glob
import importlib
import json
import os
import shutil
import sys
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
#: traces of a ``--trace 1`` run, deleted once reduced (git-ignored)
WORK_DIR = os.path.join(ROOT, ".bench_work")
#: the compile cache's fixed place inside the checkout (git-ignored);
#: ``JAX_COMPILATION_CACHE_DIR`` wins where the machine sets it
CACHE_DIR = os.path.join(ROOT, ".tftpu_cache")


def load_json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    traffic: Dict[str, Any]      # workloads/<cell>.json
    config: Dict[str, Any]       # the configuration's file
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    rehearsal: bool = False

    def limit(self, name: str) -> float:
        return float(self.traffic["limits"][name])


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, rehearsal: bool = False) -> Cell:
    manifest = load_json(ROOT, "BENCHMARK.json")
    entries = {w["name"]: w for w in manifest["workloads"]}
    if name not in entries:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(entries)}")
    entry = entries[name]
    config_entry = next(c for c in manifest["configs"]
                        if c["name"] == entry["config"])
    traffic = load_json(HERE, "workloads", name + ".json")
    config = load_json(ROOT, config_entry["file"])
    if rehearsal:
        # tiny sizes for the CPU wiring check, stated beside the real ones
        traffic = {**traffic, **traffic.get("rehearsal", {})}
        config = {**config, **config.get("rehearsal", {})}
    e2e = [m for m in manifest["end_to_end"] if _applies(m, name)]
    # a per-layer metric with no list of cells is due wherever the
    # end-to-end metric it moves is reported
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in manifest["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in e2e_names)]
    return Cell(name, int(entry["chips"]), traffic, config, e2e, layer,
                rehearsal)


def driver_of(cell: Cell):
    """The window loop that runs the cell: the module of ``drivers/`` its
    traffic file names, else the one its configuration names."""
    name = cell.traffic.get("driver") or cell.config["driver"]
    return importlib.import_module(f"benchmark.drivers.{name}")


def prepare_env(cell: Cell) -> None:
    """Before JAX is imported: place the compile cache, and give a
    rehearsal the virtual devices a four-chip cell needs."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.environ["TFTPU_COMPILE_CACHE"] = CACHE_DIR
    if cell.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{cell.chips}").strip()


def gate_devices(cell: Cell):
    """The devices the cell runs on, or exit non-zero with no result."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not cell.rehearsal:
        print(f"benchmark: JAX found platform={platform!r}, not a TPU; "
              "no result (--rehearsal runs the tiny CPU wiring check)",
              file=sys.stderr)
        raise SystemExit(3)
    if len(devices) < cell.chips:
        print(f"benchmark: cell {cell.name} needs {cell.chips} chip(s), "
              f"JAX found {len(devices)}; no result", file=sys.stderr)
        raise SystemExit(3)
    if not cell.rehearsal:
        peaks(devices[0].device_kind)  # an unknown device is an error
    # every small program too: the second run of a cell compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return devices[:cell.chips]


def peaks(device_kind: str) -> Dict[str, float]:
    table = load_json(HERE, "peaks.json")["devices"]
    if device_kind not in table:
        raise SystemExit(f"benchmark: no peaks for device_kind="
                         f"{device_kind!r} in benchmark/peaks.json")
    return table[device_kind]


def memory_peak_bytes(devices) -> int:
    best = 0
    for d in devices:
        stats = d.memory_stats() or {}
        best = max(best, int(stats.get("peak_bytes_in_use", 0)))
    return best


class SetupClock:
    """Where set-up goes: ``mark(name)`` closes a phase; ``line()`` is
    printed on standard error before the checks, so a run says what its
    ``setup_s`` was spent on."""

    def __init__(self, t_start: float):
        self._last = t_start
        self.phases: List[Tuple[str, float]] = []

    def mark(self, name: str, at: Optional[float] = None) -> None:
        now = time.perf_counter() if at is None else at
        self.phases.append((name, now - self._last))
        self._last = now

    def line(self) -> str:
        return "setup phases (s): " + ", ".join(
            f"{name} {seconds:.2f}" for name, seconds in self.phases)


# -- registry and spans --------------------------------------------------

def registry_snapshot() -> List[Dict[str, Any]]:
    from tensorframes_tpu.observability.metrics import REGISTRY

    return REGISTRY.snapshot()


def metric_total(snapshot: Sequence[Dict[str, Any]], name: str,
                 field: str = "value", **labels: str) -> float:
    total = 0.0
    for d in snapshot:
        if d["name"] == name and all(
                dict(d.get("labels") or {}).get(k) == v
                for k, v in labels.items()):
            total += float(d.get(field, 0.0) or 0.0)
    return total


def histogram_buckets(snapshot: Sequence[Dict[str, Any]], name: str
                      ) -> Dict[float, float]:
    """Cumulative counts per upper bound, summed over label sets."""
    out: Dict[float, float] = {}
    for d in snapshot:
        if d["name"] == name and "buckets" in d:
            for le, c in d["buckets"].items():
                bound = float("inf") if le in ("+Inf", "inf") else float(le)
                out[bound] = out.get(bound, 0.0) + float(c)
    return out


def plan_decisions(snapshot: Sequence[Dict[str, Any]]) -> Dict[str, int]:
    return {dict(d["labels"]).get("decision", "?"): int(d["value"])
            for d in snapshot
            if d["name"] == "tftpu_plan_cost_decisions_total"
            and d.get("value")}


class HostSpans:
    """The program's own ``TRACER`` spans over the window, on the
    ``time.perf_counter`` clock (seconds)."""

    def __init__(self):
        from tensorframes_tpu.observability import events

        self._events = events
        self.spans: List[Dict[str, Any]] = []

    def start(self) -> None:
        self._events.TRACER.clear()
        self._events.TRACER.enable()

    def stop(self) -> None:
        tracer = self._events.TRACER
        tracer.disable()
        # a marker at a known perf_counter instant maps the tracer's
        # own epoch back onto that clock
        tracer.enable()
        mark = time.perf_counter()
        tracer.emit_complete("bench.epoch_mark", mark, 0.0)
        tracer.disable()
        events = tracer.to_chrome_trace()["traceEvents"]
        at = next(e["ts"] for e in events
                  if e.get("name") == "bench.epoch_mark")
        offset = mark - at * 1e-6
        self.spans = [
            {"name": e["name"], "start": e["ts"] * 1e-6 + offset,
             "dur": e["dur"] * 1e-6, "args": e.get("args", {})}
            for e in events
            if e.get("ph") == "X" and e["name"] != "bench.epoch_mark"]
        tracer.clear()

    def named(self, name: str, window: Optional[Tuple[float, float]] = None
              ) -> List[Dict[str, Any]]:
        return [s for s in self.spans if s["name"] == name and (
            window is None
            or (s["start"] >= window[0]
                and s["start"] + s["dur"] <= window[1]))]


class DeviceTrace:
    """A profiler capture of a stretch of the window: the profiler
    starts, the traced stretch opens (at once, or after the stall of
    starting has washed out) and closes, the profiler stops; ``reduce``
    reads the xplane once the run is over and deletes it. The annotation
    ``bench.trace_window`` marks the stretch and ties the trace's clock
    to ``time.perf_counter``. Starting takes 50 ms on the chip. Stopping
    takes seconds on an idle host and over a minute beside a loop that
    keeps the interpreter busy, so a driver whose own thread offers load
    stops the profiler once the load has ended."""

    def __init__(self, tag: str):
        self.dir = os.path.join(WORK_DIR, "trace", tag)
        self._annot = None
        #: perf_counter at the call that starts the profiler, at its
        #: return, and at the traced stretch's open and close
        self.t_start = self.t_ready = self.t0 = self.t1 = 0.0
        self.state = "idle"  # -> profiling -> window -> closed -> done

    def start_profiler(self) -> None:
        import jax

        self.t_start = time.perf_counter()
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        # the device planes and the host's own annotations are all that
        # ``reduce`` reads: no Python call tracing, and of the host's
        # events only those a program marks itself (level 1)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.t_ready = time.perf_counter()
        self.state = "profiling"

    def open_window(self) -> None:
        import jax

        self._annot = jax.profiler.TraceAnnotation("bench.trace_window")
        self.t0 = time.perf_counter()
        self._annot.__enter__()
        self.state = "window"

    def close_window(self) -> None:
        self._annot.__exit__(None, None, None)
        self.t1 = time.perf_counter()
        self.state = "closed"

    def stop_profiler(self) -> None:
        import jax

        jax.profiler.stop_trace()
        self.state = "done"

    def reduce(self, host_spans: Sequence[Dict[str, Any]]
               ) -> Optional[Dict[str, Any]]:
        from . import trace_reduce

        paths = glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb"))
        try:
            if not paths:
                return None
            trace = trace_reduce.load(paths[0])
            marks = trace.annotations.get("bench.trace_window")
            if not trace.devices or not marks:
                return None  # a CPU rehearsal: no device plane to read
            window = marks[0]
            to_ns = lambda t: window[0] + (t - self.t0) * 1e9  # noqa: E731
            # only the spans that reach into the traced stretch: the
            # window's other spans can label no gap of it
            spans = [(s["name"], to_ns(s["start"]),
                      to_ns(s["start"] + s["dur"])) for s in host_spans
                     if s["start"] < self.t1
                     and s["start"] + s["dur"] > self.t0]
            return trace_reduce.reduce(trace, window, spans)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


# -- per-layer readers ---------------------------------------------------

@dataclasses.dataclass
class Readings:
    """What a traced run hands the readers."""
    cell: Cell
    window: Tuple[float, float]              # perf_counter seconds
    before: List[Dict[str, Any]]             # registry at window open
    after: List[Dict[str, Any]]              # registry at window close
    spans: HostSpans
    trace: Optional[Dict[str, Any]]          # trace_reduce.reduce(...)
    device_kind: str
    memory_peak_bytes: int
    client: Dict[str, Any]                   # the driver's own records


def read_per_layer(readings: Readings) -> Dict[str, Dict[str, Any]]:
    """Each due metric through its reader; a reader that finds nothing
    to read returns None and the metric is left out of the line."""
    out: Dict[str, Dict[str, Any]] = {}
    for metric in readings.cell.per_layer:
        spec = load_json(HERE, "metrics", metric["name"] + ".json")
        reader = importlib.import_module(
            f"benchmark.readers.{spec['reader']}")
        value = reader.read(readings, spec.get("params", {}))
        if value is not None:
            out[metric["name"]] = {"value": float(value),
                                   "unit": metric["unit"]}
    return out


# -- the result ----------------------------------------------------------

def quantile(values: Sequence[float], q: float) -> float:
    """Linear interpolation between order statistics (numpy's default)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("quantile of nothing")
    at = q * (len(xs) - 1)
    lo = int(at)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (at - lo)


def emit(cell: Cell, devices, *, correct: bool, attempted: int, failed: int,
         metrics: Dict[str, Dict[str, Any]], memory_peak: int,
         checks: Dict[str, Dict[str, float]], decisions: Dict[str, int],
         trace: Optional[Dict[str, Any]] = None,
         clock: Optional[SetupClock] = None) -> None:
    """The plan decisions on a line of their own, the numbers compared
    as the last lines of standard error, the result as the last line of
    standard output."""
    d0 = devices[0]
    device: Dict[str, Any] = {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices), "memory_peak_bytes": int(memory_peak)}
    line: Dict[str, Any] = {
        "correct": bool(correct), "attempted": int(attempted),
        "failed": int(failed), "metrics": metrics, "device": device}
    if trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        line["breakdown"] = trace["breakdown"]
    if cell.rehearsal:
        # a CPU run gives counts, never a time, a rate or a share: the
        # others are named, so the wiring shows, and their values dropped
        line["metrics"] = {k: v for k, v in metrics.items()
                           if v["unit"] == "count"}
        line["read_not_printed"] = sorted(set(metrics) - set(line["metrics"]))
    line["checks"] = checks
    tag = "REHEARSAL " if cell.rehearsal else ""
    print(f"{tag}plan decisions: {json.dumps(decisions, sort_keys=True)}",
          flush=True)
    if clock is not None:
        print(tag + clock.line(), file=sys.stderr)
    for name, c in checks.items():
        print(f"{tag}check {name}: value={c['value']!r} "
              f"limit={c['limit']!r} "
              f"{'ok' if c['value'] <= c['limit'] else 'FAILED'}",
              file=sys.stderr)
    sys.stderr.flush()
    print(tag + json.dumps(line), flush=True)

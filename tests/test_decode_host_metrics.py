"""The serving cell's per-layer metrics of the engine thread's host
work, read by hand: each ``benchmark/metrics/<name>.json`` through the
reader it names, on spans and registry snapshots made here, gives the
number worked out beside it. What the engine writes for them (the
leaves of a join and of a step, the loop's CPU counter) is held by
``tests/test_span_discipline.py``."""

import importlib
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CELL = "gpt2-small.closed-loop-384"
WINDOW = (100.0, 110.0)


def _spans(name, durations, at=101.0):
    """One span of ``name`` a second apart inside the window, and one
    that straddles its close (never counted)."""
    out = [{"name": name, "start": at + i, "dur": d, "args": {}}
           for i, d in enumerate(durations)]
    out.append({"name": name, "start": WINDOW[1] - 1e-4, "dur": 1.0,
                "args": {}})
    return out


def _snapshot(cpu_s, tokens):
    return [
        {"name": "tftpu_decode_loop_cpu_seconds_total", "kind": "counter",
         "labels": {}, "value": cpu_s},
        {"name": "tftpu_decode_tokens_total", "kind": "counter",
         "labels": {}, "value": tokens},
        {"name": "tftpu_decode_steps_total", "kind": "counter",
         "labels": {"phase": "decode"}, "value": 7.0},
    ]


def _readings(spans=(), before=(), after=()):
    from benchmark import harness

    host = harness.HostSpans()
    host.spans = list(spans)
    cell = harness.Cell(CELL, 1, {}, {}, [], [])
    return harness.Readings(
        cell=cell, window=WINDOW, before=list(before), after=list(after),
        spans=host, trace=None, device_kind="cpu", memory_peak_bytes=0,
        client={})


def _read(metric, readings):
    with open(os.path.join(ROOT, "benchmark", "metrics",
                           metric + ".json")) as f:
        spec = json.load(f)
    reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
    return reader.read(readings, spec.get("params", {}))


SPAN_METRICS = {
    # metric: (span, durations in s, their median in ms)
    "join_build_ms_p50": ("decode.join.build",
                          [0.0004, 0.0007, 0.0005], 0.5),
    "join_seat_ms_p50": ("decode.join.seat",
                         [0.0003, 0.0002, 0.0006, 0.0004], 0.35),
    "prefill_enqueue_ms_p50": ("decode.prefill.enqueue",
                               [0.0021, 0.0016, 0.0018], 1.8),
    "decode_count_ms_p50": ("decode.step.count",
                            [0.00012, 0.00009, 0.00011, 0.0001, 0.0003],
                            0.11),
}


@pytest.mark.parametrize("metric", sorted(SPAN_METRICS))
def test_span_metric_is_the_median_of_its_leaf(metric):
    name, durations, want = SPAN_METRICS[metric]
    spans = _spans(name, durations) + _spans("decode.join", [0.009, 0.02])
    assert _read(metric, _readings(spans)) == pytest.approx(want)
    # a program without the leaf (the parent of the leaves) reads nothing
    assert _read(metric, _readings(_spans("decode.join", [0.01]))) is None


def test_loop_cpu_is_microseconds_a_token():
    # 0.42 s of the engine thread's CPU over the window's 8,400 tokens
    before, after = _snapshot(3.0, 1_000.0), _snapshot(3.42, 9_400.0)
    got = _read("decode_loop_cpu_us_per_token", _readings(
        before=before, after=after))
    assert got == pytest.approx(0.42 / 8_400 * 1e6)   # 50 us a token
    # no token in the window: nothing to read
    assert _read("decode_loop_cpu_us_per_token", _readings(
        before=before, after=_snapshot(3.1, 1_000.0))) is None


def test_the_five_metrics_are_the_serving_cells_alone():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entries = {m["name"]: m for m in manifest["per_layer"]}
    for metric in list(SPAN_METRICS) + ["decode_loop_cpu_us_per_token"]:
        entry = entries[metric]
        assert entry["workloads"] == [CELL]
        assert (entry["layer"], entry["moves"]) == ("serving",
                                                   "out_tokens_per_s")
        with open(os.path.join(ROOT, "benchmark", "metrics",
                               metric + ".json")) as f:
            spec = json.load(f)
        assert {k: spec[k] for k in entry} == entry


def test_a_traced_rehearsal_of_the_cell_reads_all_five():
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELL,
         "--seed", "4000000040", "--seconds", "2", "--trace", "1",
         "--rehearsal"],
        cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    last = out.stdout.strip().splitlines()[-1]
    line = json.loads(last[len("REHEARSAL "):])
    assert line["correct"] is True
    assert set(SPAN_METRICS) | {"decode_loop_cpu_us_per_token"} <= set(
        line["read_not_printed"]), line["read_not_printed"]

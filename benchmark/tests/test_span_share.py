"""The ``span_share`` reader on spans made by hand, and the new
per-layer metrics' wiring: a traced rehearsal of each cell has to name
every one of them (a CPU run prints no time or share, so they appear
under ``read_not_printed``)."""

import json
import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

NEW = {
    "gpt2-small.closed-loop-384": {
        "decode_host_share", "decode_admit_ms_p50", "decode_prepare_ms_p50",
        "decode_commit_ms_p50", "decode_enqueue_ms_p50",
        "prefill_dispatch_ms_p50", "client_resubmit_lag_ms_p95",
        "decode_pages_walked_share", "kv_pool_used_share",
        "slowest_tenth_rate_share", "collector_share"},
    "inception-v3.device-frame": {
        "frame_host_share", "executor_prepare_ms_p50",
        "reduce_gather_ms_p50", "reduce_fetch_ms_p50"},
    "inception-v3.device-frame-4chip": {
        "frame_host_share", "executor_prepare_ms_p50",
        "reduce_gather_ms_p50", "reduce_fetch_ms_p50"},
}


def readings(spans, window=(10.0, 20.0)):
    from benchmark import harness

    host = harness.HostSpans()
    host.spans = [{"name": s[0], "start": s[1], "dur": s[2],
                   "args": s[3] if len(s) > 3 else {}} for s in spans]
    return types.SimpleNamespace(window=window, spans=host)


def test_span_share_counts_whole_spans_less_their_minus():
    from benchmark.readers import span_share

    spans = [
        ("a", 10.5, 1.0),    # inside
        ("a", 9.5, 1.0),     # straddles the open: not counted
        ("a", 19.5, 1.0),    # straddles the close: not counted
        ("b", 12.0, 2.0),    # inside
        ("m", 12.5, 0.5),    # inside b: subtracted
        ("m", 9.8, 0.4),     # straddles: not subtracted
        ("m", 10.1, 0.2),    # inside the window, under the straddling
                             # "a" alone: its parent is not counted
        ("other", 15.0, 3.0),
    ]
    got = span_share.read(readings(spans), {"spans": ["a", "b"],
                                            "minus": ["m"]})
    assert got == pytest.approx(100.0 * (1.0 + 2.0 - 0.5) / 10.0)
    plain = span_share.read(readings(spans), {"spans": ["a", "b"]})
    assert plain == pytest.approx(30.0)


def test_span_share_where_counts_only_spans_with_the_arguments():
    from benchmark.readers import span_share

    spans = [
        ("run", 11.0, 1.0, {"synced": False}),  # the enqueue alone
        ("run", 13.0, 2.0, {"synced": True}),   # holds the device wait
        ("run", 16.0, 0.5),                     # an older program's: no arg
        ("prep", 12.0, 0.5),
    ]
    params = {"spans": ["run", "prep"], "where": {"run": {"synced": False}}}
    assert span_share.read(readings(spans), params) == pytest.approx(15.0)
    # every dispatch synced: the name is there, its share is nothing
    assert span_share.read(readings(spans[1:]), params) == pytest.approx(5.0)


def test_span_share_reads_nothing_from_a_program_without_the_spans():
    from benchmark.readers import span_share

    spans = [("decode.join", 11.0, 0.1), ("decode.step", 12.0, 0.3)]
    assert span_share.read(
        readings(spans),
        {"spans": ["decode.admit", "decode.join"], "minus": ["x"]}) is None
    assert span_share.read(readings([]), {"spans": ["a"]}) is None


@pytest.mark.parametrize("cell", sorted(NEW))
def test_traced_rehearsal_names_the_new_metrics(cell):
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", cell,
         "--seed", "3", "--seconds", "2", "--trace", "1", "--rehearsal"],
        cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    last = out.stdout.strip().splitlines()[-1]
    assert last.startswith("REHEARSAL ")
    line = json.loads(last[len("REHEARSAL "):])
    assert line["correct"] is True
    assert NEW[cell] <= set(line["read_not_printed"]), line["read_not_printed"]

"""``trace_reduce.label_gaps`` (one sweep over sorted gaps and spans)
against ``scan_label`` below (one scan of every span for one gap, the
form ``reduce`` used before), the oracle: the same label gap for gap on
seeded random nested spans, in a time that does not grow with gaps times
spans, and the recorded trace's ``breakdown`` as the scan gives it."""

import os
import random
import time

import pytest

from benchmark import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(os.path.dirname(HERE), "testdata",
                        "v5e_small.xplane.pb")


def scan_label(gap, host_spans):
    """The host span that covers most of ``gap``; of spans that cover it
    equally (nested ones), the shortest, which is the innermost."""
    best, best_key = "(no host span)", (0.0, 0.0)
    for name, a, b in host_spans:
        cover = min(b, gap[1]) - max(a, gap[0])
        if cover <= 0:
            continue
        key = (cover, -(b - a))
        if key > best_key:
            best, best_key = name, key
    return best


def nested_spans(rng, n, threads=3, extent=1e9):
    """``n`` spans on a few threads, each thread's under stack
    discipline: a span holds up to three children side by side, down to
    four levels, and some spans share a name, a start or a length."""
    out = []

    def fill(lo, hi, depth, thread):
        if len(out) >= n or hi - lo < 4:
            return
        cuts = sorted(rng.uniform(lo, hi) for _ in range(2 * rng.randint(1, 3)))
        for a, b in zip(cuts[::2], cuts[1::2]):
            if rng.random() < 0.1:
                a, b = float(int(a)), float(int(a)) + float(int(b - a))
            out.append((f"t{thread}.d{depth}.{rng.randint(0, 4)}", a, b))
            if depth < 3 and rng.random() < 0.8:
                fill(a, b, depth + 1, thread)
            if rng.random() < 0.05:  # the same interval under another name
                out.append((f"t{thread}.twin", a, b))

    while len(out) < n:
        thread = rng.randrange(threads)
        at = rng.uniform(0, extent)
        fill(at, at + rng.uniform(10, extent / 50), 0, thread)
    rng.shuffle(out)
    return out[:n]


def random_gaps(rng, n, extent=1e9, disjoint=True):
    if disjoint:
        cuts = sorted(rng.uniform(-0.01 * extent, 1.01 * extent)
                      for _ in range(2 * n))
        return list(zip(cuts[::2], cuts[1::2]))
    return [(a, a + rng.uniform(1, extent / 20))
            for a in (rng.uniform(0, extent) for _ in range(n))]


@pytest.mark.parametrize("seed", [1, 2, 3, 4])
@pytest.mark.parametrize("disjoint", [True, False])
def test_sweep_labels_every_gap_as_the_scan_does(seed, disjoint):
    rng = random.Random(seed)
    spans = nested_spans(rng, 1500)
    idle = random_gaps(rng, 700, disjoint=disjoint)
    if not disjoint:
        rng.shuffle(idle)
    want = [scan_label(g, spans) for g in idle]
    assert tr.label_gaps(idle, spans) == want
    assert len(set(want)) > 10 and "(no host span)" in want


def test_sweep_handles_nothing_to_label():
    assert tr.label_gaps([], [("a", 0.0, 1.0)]) == []
    assert tr.label_gaps([(0.0, 1.0)], []) == ["(no host span)"]
    assert tr.label_gaps([(0.0, 1.0)], [("point", 0.5, 0.5)]) \
        == ["(no host span)"]


def test_40000_spans_and_10000_gaps_label_in_seconds():
    rng = random.Random(7)
    spans = nested_spans(rng, 40_000)
    # one span over everything, as a verb's span lies over a whole pass
    spans.append(("whole", -1.0, 2e9))
    idle = random_gaps(rng, 10_000)
    t0 = time.perf_counter()
    got = tr.label_gaps(idle, spans)
    took = time.perf_counter() - t0
    assert took < 5.0, f"{took:.1f} s"
    for k in rng.sample(range(len(idle)), 50):
        assert got[k] == scan_label(idle[k], spans)


def test_reduce_of_the_recorded_trace_is_what_the_scan_gives():
    trace = tr.load(RECORDED, annotation_prefix="host.")
    passes = trace.annotations["host.pass"]
    window = (passes[0][0], passes[-1][1])
    spans = [("host.pass", a, b) for a, b in passes]
    spans.append(("inner", passes[1][0] + 1e5, passes[1][1] - 1e5))
    # spans outside the window cover no gap
    spans += [("before", window[0] - 5e6, window[0]),
              ("after", window[1], window[1] + 5e6)]
    out = tr.reduce(trace, window, spans)
    ops = trace.devices[0].ops
    idle = tr.gaps(tr.union([(a, b) for _, a, b in ops], window), window)
    scanned = [(scan_label(g, spans), (g[1] - g[0]) * 1e-9) for g in idle]
    longest = sorted(scanned, key=lambda x: -x[1])[:5]
    sums = {}
    for name, s in scanned:
        sums["sum_" + name] = sums.get("sum_" + name, 0.0) + s
    by_sum = sorted(sums.items(), key=lambda x: -x[1])[:10 - len(longest)]
    assert out["breakdown"]["idle_gaps"] == \
        [[k, v] for k, v in longest] + [[k, v] for k, v in by_sum]
    assert {k for k, _ in out["breakdown"]["idle_gaps"]} >= \
        {"host.pass", "sum_host.pass"}
    assert [k for k, _ in out["breakdown"]["device_ops"]] == [
        "convolution_tanh_fusion", "convolution_reduce_fusion",
        "copy-start", "copy-done"]

"""What the serving driver records beside the requests: the pool's size
as the engine has it, the host's doings tenth by tenth, and the series
its per-layer metrics read."""

import gc
import time
import types

import pytest


def test_pool_pages_is_the_file_s_or_every_slot_s_horizon():
    from benchmark.drivers import serving

    sizes = {"max_slots": 384, "page_size": 16, "max_prompt_len": 896,
             "max_new_tokens": 128}
    # the null page holds no sequence
    assert serving.pool_pages({**sizes, "num_pages": 8193}) == 8192
    assert serving.pool_pages({**sizes, "num_pages": None}) == 384 * 64
    assert serving.pool_pages({"max_slots": 4, "page_size": 4,
                               "max_prompt_len": 24,
                               "max_new_tokens": 7}) == 4 * 8


def test_host_sampler_times_the_collector_between_samples():
    from benchmark.drivers import serving

    host = serving.HostSampler()
    host.start()
    try:
        host.sample(time.perf_counter())
        gc.collect()
        host.sample(time.perf_counter())
        sum(i * i for i in range(200000))
        host.sample(time.perf_counter())
    finally:
        host.stop()
    assert host._on_gc not in gc.callbacks
    full, quiet = host.deltas("gc_full_n")
    assert full >= 1 and quiet == 0
    assert host.deltas("gc_s")[0] > 0
    assert host.deltas("cpu_s")[1] > 0
    assert host.deltas("no such reading") == []
    # no engine here: its counter stands still, its thread is not found
    assert host.engine_rates() == [0.0, 0.0]
    assert host.engine_rates(until=host.rows[1]["t"]) == [0.0]
    assert host.deltas("loop_cpu_s") == []


@pytest.mark.parametrize("series, q, want", [
    ([1.02, 0.71, 1.0, 0.99], 0.0, 71.0),       # the slowest tenth
    ([0.80, 0.90, 0.85], 0.5, 85.0),            # the pool's median
])
def test_client_quantile_reads_the_driver_s_series(series, q, want):
    from benchmark.readers import client_quantile

    readings = types.SimpleNamespace(client={"s": series})
    got = client_quantile.read(readings, {"series": "s", "q": q,
                                          "scale": 100.0})
    assert got == pytest.approx(want)
    assert client_quantile.read(readings, {"series": "none", "q": q}) is None

"""Test fixture: force a virtual 8-device CPU platform BEFORE jax loads.

≙ the reference's shared local-mode fixture (TensorFramesTestSparkContext:
local[1] Spark with 4 shuffle partitions) — here "distributed" is tested by
device count, not hosts: 8 virtual CPU devices stand in for a TPU slice.
"""

import os

# Tests run on the CPU, pinned before any backend initializes, on eight
# virtual devices. A machine-wide JAX_COMPILATION_CACHE_DIR is dropped:
# the compile-count tests need a cache-off process (a CI leg that wants
# a store says so with TFTPU_COMPILE_CACHE; tests configure their own).
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

assert jax.devices()[0].platform == "cpu" and len(jax.devices()) >= 8, (
    f"conftest expected >=8 virtual CPU devices, got {jax.devices()}"
)

import pytest  # noqa: E402


def pytest_sessionstart(session):
    """TFTPU_OBS_EXPORT=<dir>: arm the structured tracer for the whole
    suite so the session-end export (below) carries a real timeline —
    CI uploads the pair as its observability artifact."""
    if os.environ.get("TFTPU_OBS_EXPORT"):
        from tensorframes_tpu.observability import events

        events.enable()


def pytest_sessionfinish(session, exitstatus):
    """Write the suite's metrics snapshot (JSONL) + Chrome trace into
    $TFTPU_OBS_EXPORT. Best-effort: telemetry export must never turn a
    green suite red."""
    out = os.environ.get("TFTPU_OBS_EXPORT")
    if not out:
        return
    try:
        from tensorframes_tpu.observability import REGISTRY, events

        os.makedirs(out, exist_ok=True)
        REGISTRY.write_jsonl(os.path.join(out, "tier1_metrics.jsonl"))
        events.save(os.path.join(out, "tier1_trace.json"))
    except Exception as e:  # pragma: no cover - diagnostic path
        print(f"TFTPU_OBS_EXPORT failed: {e}")
    try:
        # static-analysis findings the suite produced, next to the
        # metrics artifact (ISSUE 3: lint posture rides along with CI).
        # Own try: an analysis-import failure must not take the
        # metrics/trace exports above down with it.
        from tensorframes_tpu.analysis import save_jsonl as _save_diag

        _save_diag(os.path.join(out, "tier1_diagnostics.jsonl"))
    except Exception as e:  # pragma: no cover - diagnostic path
        print(f"TFTPU_OBS_EXPORT diagnostics export failed: {e}")


@pytest.fixture(autouse=True)
def _fresh_graph():
    """Graph-state hygiene: every test runs in a fresh naming context
    (≙ GraphScoping.testGraph, dsl/GraphScoping.scala:8-15)."""
    from tensorframes_tpu.dsl import with_graph

    with with_graph():
        yield


@pytest.fixture(autouse=True)
def _strategy_walls_isolated():
    """Latency-feedback hygiene: the strategy-wall EWMA table
    (plan/stats) is process-global BY DESIGN — in production every
    pipeline's observed walls inform every decision. Across a test
    suite that design makes decision-kind assertions order-dependent
    (one test's recorded walls can flip a later test's decide_*), so
    each test starts from an empty in-memory table. Memory only: the
    sidecar file is untouched, and tests that exercise persistence
    re-arm loading themselves via plan_stats.clear_memory()."""
    from tensorframes_tpu.plan import stats as _plan_stats

    _plan_stats.reset_strategy_walls(unlink_sidecar=False)
    yield

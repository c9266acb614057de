"""Plan profiler (ISSUE 17): EXPLAIN ANALYZE and latency-driven plan
decisions.

Four surfaces under test:

* **EXPLAIN ANALYZE** — ``explain(analyze=True)`` on a forced frame
  renders the plan tree followed by the recorded per-stage profile
  (wall, strategy), and the per-stage walls reconcile with the measured
  force wall;
* **latency-driven flips** — an inverted observed-wall table flips
  ``decide_fuse`` to the per-stage replay on the next execution,
  counted as ``reoptimized``, with bit-identical results; the pure
  ``pick_by_observed_wall`` core honors min-samples and the hysteresis
  margin; ``decide_epilogue`` flips from an injected table, and only
  where the ops are exact (kernel choices take no walls at all:
  ``tests/test_kernels.py``);
* **sidecar hygiene** — a corrupt ``strategy_walls.json`` quarantines
  (counted + unlinked, decisions fall back to static) and stale entries
  are pruned, mirroring the selectivity-record contract;
* **observability surface** — ``report --profile`` renders the sidecar
  offline, and the new series are PRE-registered (TFL003)."""

import glob
import json
import os
import time

import numpy as np
import pytest

import tensorframes_tpu as tfs
from tensorframes_tpu.observability import cli, profile
from tensorframes_tpu.observability.metrics import REGISTRY
from tensorframes_tpu.plan import rules
from tensorframes_tpu.plan import stats as plan_stats


@pytest.fixture(autouse=True)
def _fusion_on():
    """Pin fusion on (the flip target is the fused segment); leave
    plan_reopt AMBIENT so the CI REOPT=0 leg still collects this file
    (the engaged-machinery tests skip themselves)."""
    cfg = tfs.configure()
    before = (cfg.plan_fusion, cfg.plan_reopt)
    tfs.configure(plan_fusion=True)
    yield
    tfs.configure(plan_fusion=before[0], plan_reopt=before[1])


_reopt_only = pytest.mark.skipif(
    not tfs.configure().plan_reopt,
    reason="adaptive optimizer disabled (TFTPU_REOPT=0)",
)


def _count(kind):
    for d in REGISTRY.snapshot():
        if (
            d["name"] == "tftpu_plan_cost_decisions_total"
            and d["labels"].get("decision") == kind
        ):
            return float(d.get("value", 0.0))
    return 0.0


def _sidecar_count(event):
    for d in REGISTRY.snapshot():
        if (
            d["name"] == "tftpu_plan_reopt_sidecar_total"
            and d["labels"].get("event") == event
        ):
            return float(d.get("value", 0.0))
    return 0.0


def _rows_equal(a, b):
    assert len(a) == len(b)
    for ra, rb in zip(a, b):
        assert ra.keys() == rb.keys()
        for k in ra:
            va, vb = np.asarray(ra[k]), np.asarray(rb[k])
            assert va.dtype == vb.dtype, (k, va.dtype, vb.dtype)
            np.testing.assert_array_equal(va, vb)


def _fused_chain(n=256, blocks=4):
    """A 2-stage composable map chain — decide_fuse's 'fuse' territory."""
    df = tfs.frame_from_arrays(
        {"x": np.arange(float(n), dtype=np.float32)}, num_blocks=blocks
    )
    f = tfs.map_blocks(lambda x: {"u": x * 2.0}, df)
    return tfs.map_blocks(lambda u: {"y": u + 1.0}, f)


# ---------------------------------------------------------------------------
# EXPLAIN ANALYZE: structure + wall reconciliation
# ---------------------------------------------------------------------------

@_reopt_only
def test_explain_analyze_renders_profile_and_walls_reconcile(tmp_path):
    was = tfs.configure().compilation_cache_dir
    tfs.configure(compilation_cache_dir=str(tmp_path))
    try:
        plan_stats.clear_memory()
        g = _fused_chain()
        t0 = time.perf_counter()
        g.collect()
        measured = time.perf_counter() - t0

        fp = getattr(g, "_plan_fp", None)
        assert fp, "force must stash the plan fingerprint on the frame"
        text = g.explain(analyze=True)
        assert f"profile: fp={fp}" in text
        assert "execs=" in text and "wall=" in text

        rec = plan_stats.lookup(fp)
        assert rec is not None and rec["execs"] >= 1
        prof = rec.get("profile")
        assert isinstance(prof, list) and prof, (
            "EXPLAIN ANALYZE needs a recorded per-stage breakdown"
        )
        for entry in prof:
            assert entry["stage"], entry
            assert float(entry["wall_s"]) >= 0.0
            # every recorded stage renders as an indented profile line
            assert f"{entry['stage']}  wall=" in text
        # reconciliation: stages run sequentially inside the force, so
        # their walls sum to no more than the measured force wall (the
        # recorded total is the exact force wall on a first execution —
        # generous slack keeps slow-CI timer jitter out of the gate)
        stage_sum = sum(float(e["wall_s"]) for e in prof)
        assert 0.0 < stage_sum <= float(rec["wall_s"]) * 1.05 + 0.01
        assert float(rec["wall_s"]) <= measured * 1.5 + 0.05
        # the chosen strategy is part of the profile (the whole point:
        # seeing WHICH lowering the walls were observed under)
        assert any(e.get("strategy") for e in prof)
    finally:
        tfs.configure(compilation_cache_dir=was)
        plan_stats.clear_memory()


@_reopt_only
def test_explain_analyze_before_any_execution_points_at_force(tmp_path):
    was = tfs.configure().compilation_cache_dir
    tfs.configure(compilation_cache_dir=str(tmp_path))
    try:
        plan_stats.clear_memory()
        g = _fused_chain()
        text = g.explain(analyze=True)
        assert "no recorded execution" in text
        assert "->" in text  # the plan tree still renders
    finally:
        tfs.configure(compilation_cache_dir=was)
        plan_stats.clear_memory()


def test_explain_analyze_reopt_off_says_so():
    was = tfs.configure().plan_reopt
    tfs.configure(plan_reopt=False)
    try:
        g = _fused_chain()
        text = g.explain(analyze=True)
        assert "adaptive stats are off" in text
        # once forced with stats off there is nothing recorded to show:
        # the frame drops its plan chain and no fingerprint was stashed
        g.collect()
        text = g.explain(analyze=True)
        assert "no plan chain and no recorded execution" in text
    finally:
        tfs.configure(plan_reopt=was)


# ---------------------------------------------------------------------------
# latency-driven decisions: end-to-end flip + the pure core
# ---------------------------------------------------------------------------

@_reopt_only
def test_inverted_walls_flip_fuse_to_per_stage_bit_identically():
    """The tentpole gate: after the observed-wall table says the
    per-stage replay is faster, the next execution takes it — counted
    as a flip — and moves not a single bit."""
    plan_stats.clear_memory()
    plan_stats.reset_strategy_walls()

    def build():
        return _fused_chain().collect()

    try:
        baseline = build()
        walls = plan_stats.strategy_walls("fuse")
        assert walls.get("fuse", {}).get("n", 0) >= 1, (
            "the fused dispatch must feed its wall back into the table"
        )
        # invert: the fused path 'measures' slow, the replay fast
        for _ in range(max(2, plan_stats.STRATEGY_WALL_MIN_SAMPLES) * 2):
            plan_stats.observe_strategy_wall("fuse", "fuse", 10.0)
            plan_stats.observe_strategy_wall(
                "fuse", "split_single_stage", 1e-4
            )
        s0 = _count("split_single_stage")
        r0 = _count("reoptimized")
        flipped = build()
        assert _count("split_single_stage") > s0, (
            "inverted walls must flip decide_fuse to the replay"
        )
        assert _count("reoptimized") > r0, (
            "a latency flip must count as a re-optimization"
        )
        _rows_equal(baseline, flipped)
    finally:
        plan_stats.reset_strategy_walls()
        plan_stats.clear_memory()


def test_pick_by_observed_wall_min_samples_and_margin():
    pick = rules.pick_by_observed_wall
    # no table / thin evidence → no flip
    assert pick("fuse", ("split_single_stage",), None) is None
    assert pick("fuse", ("split_single_stage",), {}) is None
    thin = {
        "fuse": {"ewma_s": 1.0, "n": 1},
        "split_single_stage": {"ewma_s": 0.01, "n": 9},
    }
    assert pick("fuse", ("split_single_stage",), thin) is None
    thin_alt = {
        "fuse": {"ewma_s": 1.0, "n": 9},
        "split_single_stage": {"ewma_s": 0.01, "n": 1},
    }
    assert pick("fuse", ("split_single_stage",), thin_alt) is None
    # hysteresis: 10% faster is inside the margin, not a flip
    close = {
        "fuse": {"ewma_s": 1.0, "n": 4},
        "split_single_stage": {
            "ewma_s": rules.LATENCY_FLIP_MARGIN + 0.01, "n": 4
        },
    }
    assert pick("fuse", ("split_single_stage",), close) is None
    # decisively faster → flip, with auditable evidence
    clear = {
        "fuse": {"ewma_s": 1.0, "n": 4},
        "split_single_stage": {"ewma_s": 0.5, "n": 4},
    }
    got = pick("fuse", ("split_single_stage",), clear)
    assert got is not None
    kind, evidence = got
    assert kind == "split_single_stage"
    assert evidence["latency_flip"] is True
    assert evidence["observed_wall_s"] == {
        "fuse": 1.0, "split_single_stage": 0.5
    }
    assert evidence["wall_samples"] == {
        "fuse": 4, "split_single_stage": 4
    }


def test_decide_epilogue_flips_only_when_exact():
    walls = {
        "epilogue_per_block": {"ewma_s": 1.0, "n": 4},
        "epilogue_concat": {"ewma_s": 0.1, "n": 4},
    }
    # all-exact ops: the flip is pure latency, allowed
    d = rules.decide_epilogue(
        [("reduce_sum", np.int32)], num_groups=4, value_bytes=1024,
        observed_walls=walls,
    )
    assert d.kind == "epilogue_concat"
    assert d.details["latency_flip"] is True
    # no walls → the static per-block choice
    d = rules.decide_epilogue(
        [("reduce_sum", np.int32)], num_groups=4, value_bytes=1024,
    )
    assert d.kind == "epilogue_per_block"
    # float sums: concat is the CORRECTNESS choice, never a wall flip
    d = rules.decide_epilogue(
        [("reduce_sum", np.float32)], num_groups=4, value_bytes=1024,
        observed_walls=walls,
    )
    assert d.kind == "epilogue_concat"
    assert "latency_flip" not in d.details


# ---------------------------------------------------------------------------
# strategy-wall sidecar hygiene: corrupt → quarantine, stale → pruned
# ---------------------------------------------------------------------------

@_reopt_only
def test_strategy_wall_sidecar_corruption_quarantines(tmp_path):
    was = tfs.configure().compilation_cache_dir
    tfs.configure(compilation_cache_dir=str(tmp_path))
    try:
        plan_stats.clear_memory()
        plan_stats.observe_strategy_wall("fuse", "fuse", 0.5)
        path = tmp_path / "planstats" / "strategy_walls.json"
        assert path.exists(), "observations must persist to the sidecar"

        plan_stats.clear_memory()
        path.write_text("{definitely not json")
        q0 = _sidecar_count("quarantine")
        assert plan_stats.strategy_walls("fuse") == {}
        assert _sidecar_count("quarantine") == q0 + 1
        assert not path.exists(), "a corrupt table is unlinked, not kept"

        # stale format: same contract
        plan_stats.clear_memory()
        plan_stats.observe_strategy_wall("fuse", "fuse", 0.5)
        rec = json.loads(path.read_text())
        rec["v"] = plan_stats.FORMAT_VERSION + 999
        path.write_text(json.dumps(rec))
        plan_stats.clear_memory()
        q1 = _sidecar_count("quarantine")
        assert plan_stats.strategy_walls("fuse") == {}
        assert _sidecar_count("quarantine") == q1 + 1
    finally:
        plan_stats.reset_strategy_walls()
        tfs.configure(compilation_cache_dir=was)
        plan_stats.clear_memory()


@_reopt_only
def test_strategy_wall_stale_entries_are_pruned(tmp_path):
    was = tfs.configure().compilation_cache_dir
    tfs.configure(compilation_cache_dir=str(tmp_path))
    try:
        plan_stats.clear_memory()
        side = tmp_path / "planstats"
        side.mkdir()
        obs = plan_stats.STRATEGY_STALE_OBS + 10
        (side / "strategy_walls.json").write_text(json.dumps({
            "v": plan_stats.SW_FORMAT_VERSION, "kind": "strategy_walls",
            "tables": {"fuse": {"obs": obs, "strategies": {
                # unrefreshed for > STRATEGY_STALE_OBS observations
                "fuse": {"ewma_s": 1.0, "n": 5, "last_obs": 1},
                "split_single_stage": {
                    "ewma_s": 0.5, "n": 5, "last_obs": obs - 1
                },
            }}},
            "workloads": {},
        }))
        q0 = _sidecar_count("quarantine")
        walls = plan_stats.strategy_walls("fuse")
        assert set(walls) == {"split_single_stage"}, (
            "a months-stale entry is not evidence — it must be dropped"
        )
        assert _sidecar_count("quarantine") == q0 + 1
    finally:
        plan_stats.reset_strategy_walls()
        tfs.configure(compilation_cache_dir=was)
        plan_stats.clear_memory()


# ---------------------------------------------------------------------------
# offline report + pre-registered series
# ---------------------------------------------------------------------------

@_reopt_only
def test_report_profile_renders_sidecar_offline(tmp_path, capsys):
    was = tfs.configure().compilation_cache_dir
    tfs.configure(compilation_cache_dir=str(tmp_path))
    try:
        plan_stats.clear_memory()
        _fused_chain().collect()
        side = str(tmp_path / "planstats")
        assert glob.glob(os.path.join(side, "*.json"))

        text = profile.render_report(side)
        assert "plan-profile sidecar" in text
        assert "1 fingerprint(s)" in text
        assert "slowest recorded plan stage" in text
        assert "wall=" in text and "fp=" in text

        rc = cli.main(["report", "--profile", side])
        assert rc == 0
        assert "plan-profile sidecar" in capsys.readouterr().out

        # a corrupt file is skipped and COUNTED, never quarantined: the
        # report is a read-only visitor over someone else's artifact
        junk = os.path.join(side, "deadbeef" * 4 + ".json")
        with open(junk, "w") as f:
            f.write("{nope")
        text = profile.render_report(side)
        assert "1 unreadable file(s) skipped" in text
        assert os.path.exists(junk)
    finally:
        plan_stats.reset_strategy_walls()
        tfs.configure(compilation_cache_dir=was)
        plan_stats.clear_memory()


def test_profiler_series_are_preregistered():
    """TFL003: the profiler's series exist (zero-valued) before any
    traffic — dashboards never see a label set pop into existence."""
    snap = REGISTRY.snapshot()
    stages = {
        d["labels"].get("stage")
        for d in snap if d["name"] == "tftpu_plan_stage_wall_seconds"
    }
    assert {"fused", "per_stage", "join", "aggregate",
            "pushdown"} <= stages
    pairs = {
        (d["labels"].get("decision"), d["labels"].get("strategy"))
        for d in snap if d["name"] == "tftpu_plan_strategy_wall_seconds"
    }
    assert ("fuse", "fuse") in pairs
    assert ("fuse", "split_single_stage") in pairs
    assert ("epilogue", "epilogue_concat") in pairs
    # the plan's own decisions only: no kernel choice is timed
    assert {d for d, _ in pairs} == {"fuse", "epilogue"}
    assert any(
        d["name"] == "tftpu_serving_request_trace_total" for d in snap
    )

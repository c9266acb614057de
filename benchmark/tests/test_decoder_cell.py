"""The sparse-expert decoder's cell at its rehearsal size (CPU, float32):
the unbroken run is ``correct`` and names its counter metrics; the
roofline reader gives the three trace metrics from a hand-made trace;
planted faults each read ``correct`` false; ``counts_decoder.py`` agrees
with the plain reference's jaxpr.

The comparison has the cell's own two limits at sizes of its own: the
mean gap by which a served token's logit lies below the reference's
best (``served_logit_gap_mean``: what a fault that moves most tokens a
little raises) and the share of the served tokens that are not the
reference's first choice (``served_not_first_share``: what a fault that
moves few tokens far raises). The widest gap has no limit: at these
widths as at the published ones a single route that flips under a
rounding moves one token further than a fault moves any (PERF.md
section 2). Each planted fault fails one of the two, the int4 control
both.
"""

import argparse
import json
import math
import time
import types

import numpy as np
import pytest

CELL = "mellum2-12b-a2.5b.closed-loop-128"
LIMITS = ("served_logit_gap_mean", "served_not_first_share")
NEW = ("moe_step_mfu", "moe_expert_roofline", "mixed_attn_roofline",
       "moe_expert_load_max_over_mean", "window_pages_walked_share")


def load():
    from benchmark import harness

    return harness.load_cell(CELL, rehearsal=True)


def drive(capsys, seed=7, seconds=2.0, trace=0):
    import jax

    from benchmark import harness

    cell = load()
    driver = harness.driver_of(cell)
    args = argparse.Namespace(seed=seed, seconds=seconds, trace=trace)
    driver.run(cell, args, time.perf_counter(), jax.devices()[:1])
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert last.startswith("REHEARSAL ")
    return json.loads(last[len("REHEARSAL "):])


def test_the_cell_names_its_five_metrics_and_its_files_agree():
    cell = load()
    assert {m["name"] for m in cell.per_layer} == set(NEW)
    assert {m["name"] for m in cell.end_to_end} == {
        "out_tokens_per_s", "norm_latency_p95_ms", "setup_s"}
    assert cell.config["num_hidden_layers"] == 4   # the rehearsal block
    full = __import__("benchmark.harness", fromlist=["x"]).load_cell(CELL)
    assert full.config["num_hidden_layers"] == 8
    assert full.config["reduced"] == ["num_hidden_layers"]
    assert len(full.config["layer_types"]) == 28   # the group kept whole


def test_traced_rehearsal_is_correct_and_names_the_counter_metrics(capsys):
    line = drive(capsys, trace=1)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    # no device plane on the CPU: the three trace metrics have nothing
    # to read there (the next test reads them from a hand-made trace)
    assert set(line["read_not_printed"]) == {
        "moe_expert_load_max_over_mean", "window_pages_walked_share"}
    assert set(line["checks"]) == set(LIMITS) | {"failed_requests"}
    # the rehearsal is held to the cell's own two statistics
    assert set(__import__("benchmark.harness", fromlist=["x"]).load_cell(
        CELL).traffic["limits"]) == set(LIMITS)


def test_roofline_reader_reads_the_three_trace_metrics():
    from benchmark import counts_decoder as counts
    from benchmark import harness
    from benchmark.readers import decoder_roofline

    cell = harness.load_cell(CELL)
    cfg = cell.config
    contexts = [[300.0, 1500.0, 4000.0] * 40, [1024.0] * 128]
    trace = {
        "programs": {"jit_step": [0.020, 0.024], "jit_prefill": [0.05]},
        "op_seconds_in": {
            # the expert matmuls under either name: the kernel's on the
            # chip, ``lax.ragged_dot``'s where no kernel is selectable
            "jit_step": {"gmm.7": 0.012, "ragged-dot-none": 0.014,
                         "paged_decode_attention.3": 0.004, "fusion.9": 0.01},
            "jit_prefill": {"gmm.1": 0.04}},
    }
    readings = types.SimpleNamespace(
        cell=cell, trace=trace, device_kind="TPU v5 lite",
        client={"traced_step_contexts": contexts})
    got = {}
    for name in NEW[:3]:
        spec = harness.load_json(harness.HERE, "metrics", name + ".json")
        assert spec["reader"] == "decoder_roofline"
        got[name] = decoder_roofline.read(readings, spec["params"])
        assert 0 < got[name] <= 105, (name, got[name])
    flops = np.mean([counts.decode_step_flops(cfg, c) for c in contexts])
    assert got["moe_step_mfu"] == pytest.approx(
        100 * flops / 197e12 / 0.022)
    nbytes = np.mean([counts.expert_bytes(cfg, len(c)) for c in contexts])
    assert nbytes == pytest.approx(8 * 64 * 3 * 2304 * 896 * 2, rel=1e-3)
    assert got["moe_expert_roofline"] == pytest.approx(
        100 * (nbytes / 819e9) / 0.013)
    attn = np.mean([counts.decode_attn_bytes(cfg, c) for c in contexts])
    assert got["mixed_attn_roofline"] == pytest.approx(
        100 * (attn / 819e9) / 0.002)
    # a program that lacks the step, or the operations: nothing to read
    for name in NEW[:3]:
        spec = harness.load_json(harness.HERE, "metrics", name + ".json")
        bare = types.SimpleNamespace(
            cell=cell, device_kind="TPU v5 lite",
            trace={"programs": {"jit_other": [0.1]}, "op_seconds_in": {}},
            client={"traced_step_contexts": contexts})
        assert decoder_roofline.read(bare, spec["params"]) is None
        bare.trace = None
        assert decoder_roofline.read(bare, spec["params"]) is None


def test_counts_agree_with_the_reference_s_jaxpr():
    """The closed forms against the operations of the plain reference's
    own matmuls (counted from its jaxpr: no arithmetic runs). The
    reference passes every token through all experts, weighting the
    unrouted ones 0, and attends a whole sequence: so it is held to the
    closed form at ``num_experts_per_tok`` = ``num_experts`` and at
    contexts inside the window, and the routed count is the same form at
    the published 8."""
    import jax
    import jax.numpy as jnp

    from benchmark import counts_decoder as counts
    from benchmark import harness
    from benchmark.reference import mellum2 as ref

    def flops_of(jaxpr) -> float:
        total = 0.0
        for eqn in jaxpr.eqns:
            times = float(eqn.params.get("length", 1)) \
                if eqn.primitive.name == "scan" else 1.0
            for sub in eqn.params.values():
                inner = getattr(sub, "jaxpr", None)
                if inner is not None and hasattr(inner, "eqns"):
                    total += times * flops_of(inner)
            if eqn.primitive.name == "dot_general":
                (lc, _), _ = eqn.params["dimension_numbers"]
                k = math.prod(eqn.invars[0].aval.shape[i] for i in lc)
                total += 2.0 * math.prod(eqn.outvars[0].aval.shape) * k
        return total

    full = harness.load_cell(CELL).config
    for cfg, t in ((load().config, 8), ({**full, "num_hidden_layers": 4}, 64)):
        dense = {**cfg, "num_experts_per_tok": cfg["num_experts"]}
        traced = 0.0
        for kind in ref.layer_kinds(cfg):
            w = jax.eval_shape(lambda: ref.make_layer_weights(cfg, 0, 0))
            x = jax.ShapeDtypeStruct((t, cfg["hidden_size"]), jnp.float32)
            traced += flops_of(jax.make_jaxpr(
                lambda w, x, kind=kind: ref.layer_forward(cfg, w, x, kind)
            )(w, x).jaxpr)
        traced += 2.0 * t * cfg["hidden_size"] * cfg["vocab_size"]  # head
        # the dense reference attends all t positions from each of t
        closed = counts.decode_step_flops(dense, [t] * t)
        assert traced == pytest.approx(closed, rel=1e-12), (traced, closed)
    # the published sizes, by hand
    assert counts.attention_params(full) == 2 * 2304 * 4096 + 2 * 2304 * 512
    assert counts.expert_params(full) == 3 * 2304 * 896 == 6_193_152
    per_slot = counts.decode_step_flops(full, [0.0])
    assert per_slot == 8 * 2 * (21_233_664 + 147_456 + 8 * 6_193_152) \
        + 2 * 2304 * 98304
    # a context of 4,096: the two full layers attend it all, the six
    # sliding ones their 1,024
    assert counts.decode_step_flops(full, [4096.0]) - per_slot == \
        4 * 4096 * (2 * 4096 + 6 * 1024)
    assert counts.decode_attn_bytes(full, [4096.0]) == \
        (2 * 512 + 2 * 4 * 4) * (2 * 4096 + 6 * 1024)
    assert counts.expert_flops(full, 128) == 8 * 128 * 8 * 2 * 6_193_152


def readings(seed, fault=None, control=False, seconds=1.5):
    import jax

    from benchmark import harness

    cell = load()
    return harness.driver_of(cell).limit_readings(
        cell, jax.devices()[:1], seed, control, seconds, fault=fault)


def limit(key):
    return load().limit(key)


def over(row, prefix="served"):
    """The limits of the cell that ``row`` reads above."""
    return {name for name in LIMITS
            if row[name.replace("served", prefix)] > limit(name)}


@pytest.mark.parametrize("fault,fails", [
    ("no_window", set(LIMITS)), ("no_yarn", {"served_not_first_share"}),
    ("top7", set(LIMITS)), ("no_renorm", set(LIMITS))])
def test_a_fault_in_the_reference_s_place_is_not_correct(fault, fails):
    """The window ignored on sliding layers, YaRN's scaling dropped,
    top-7 in place of top-8 (here: one expert fewer of the two),
    the renormalisation left out."""
    row = readings(31, fault=fault)
    assert row["served_tokens"] > 50
    assert over(row) == fails


def test_int4_in_the_reference_s_place_is_not_correct():
    row = readings(32, control=True)
    assert over(row) == set()
    assert over(row, "control") == set(LIMITS)
    for name in LIMITS:
        assert row[name.replace("served", "control")] > 3 * limit(name)


def test_one_expert_s_result_left_out_is_not_correct(capsys, monkeypatch):
    """The program drops what expert 3 gives to every token routed to
    it: a quarter of the tokens lose one of their two experts."""
    import jax.numpy as jnp

    from tensorframes_tpu.models import sparse_decoder as sd

    real = sd.routed_experts

    def broken(h, experts, weights, *w, **kw):
        return real(h, experts, jnp.where(experts == 3, 0.0, weights),
                    *w, **kw)

    monkeypatch.setattr(sd, "routed_experts", broken)
    line = drive(capsys, seed=33)
    assert line["correct"] is False
    for name in LIMITS:
        check = line["checks"][name]
        assert check["value"] > check["limit"], name

"""The control of each configuration has to come out as not correct:
the plain reference put in the program's place, computed in the nearest
precision below the configuration's (int8 for bfloat16 Inception-v3,
int4 for int8 GPT-2-small). The chip readings at the cells' own sizes
are in PERF.md; here the same comparison runs at the rehearsal sizes,
with a limit read at that size the same way (the cell's own limit
belongs to the published widths)."""


import pytest


def cell_limit(name, key):
    """The limit the cell's file states for its rehearsal size."""
    from benchmark import harness

    return harness.load_cell(name, rehearsal=True).limit(key)


def readings(name, seed, seconds=1.5):
    import jax

    from benchmark import harness

    cell = harness.load_cell(name, rehearsal=True)
    devices = jax.devices()[:cell.chips]
    if len(devices) < cell.chips:
        pytest.skip(f"{name} needs {cell.chips} devices")
    driver = harness.driver_of(cell)
    return driver.limit_readings(cell, devices, seed, True, seconds)


@pytest.mark.parametrize("name", ["inception-v3.device-frame",
                                  "inception-v3.device-frame-4chip"])
def test_int8_inception_is_not_correct(name):
    # rehearsal size, float32 program: the program reads ~1e-7, the int8
    # control ~2e-2 (seeds 5, 6, 11); the rehearsal limit 1e-3 sits between
    rows = [readings(name, seed) for seed in (11, 12, 13)]
    limit = cell_limit(name, "class_sum_gap")
    for row in rows:
        assert row["class_sum_gap"] <= limit < row["control_class_sum_gap"]
        assert row["control_class_sum_gap"] >= 3 * row["class_sum_gap"]
        assert row["pass_drift"] == 0.0


def test_int4_gpt2_is_not_correct():
    # rehearsal size (2 layers, width 32, float32 activations): the
    # served tokens are the reference's own (gap 0 or rounding), the
    # int4 control's first choice lies 0.04-0.09 below the best;
    # the program (bfloat16 activations, int8 weights and KV) reads up to 0.006
    name = "gpt2-small.closed-loop-384"
    rows = [readings(name, seed) for seed in (21, 22, 23)]
    limit = cell_limit(name, "served_logit_gap")
    for row in rows:
        assert row["served_tokens"] > 0
        assert row["served_logit_gap"] <= limit < row["control_logit_gap"]

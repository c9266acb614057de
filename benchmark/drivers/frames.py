"""Window loop for frame cells: a device-resident frame of image blocks,
each pass ``map_blocks(model)`` over it then ``reduce_blocks`` (per-class
sum of logits) to the host.

The traffic file gives ``rows`` and ``block_rows``; the configuration
gives the model's sizes and names its plain reference. Weights and
images are made on the device from the seed, each in one jitted call.
"""

from __future__ import annotations

import importlib
import time
from typing import Any, Dict, List

import numpy as np

from .. import harness


def make_frame(cell: harness.Cell, devices, seed: int):
    """The device frame: ``rows / block_rows`` blocks, each one global
    array with its rows split over the mesh ``to_device()`` would build
    (one chip: a mesh of one)."""
    import jax
    import jax.numpy as jnp

    from tensorframes_tpu import dtypes as dt
    from tensorframes_tpu.frame import TensorFrame
    from tensorframes_tpu.parallel.mesh import batch_sharding, make_mesh
    from tensorframes_tpu.schema import ColumnInfo, Schema
    from tensorframes_tpu.shape import Shape

    rows, block_rows = int(cell.traffic["rows"]), int(cell.traffic["block_rows"])
    side = int(cell.config["image_size"])
    mesh = make_mesh(devices=devices)
    sharding = batch_sharding(mesh, 4)
    shape = (block_rows, side, side, 3)
    draw = jax.jit(
        lambda key: jax.random.normal(key, shape, jnp.float32),
        out_shardings=sharding)
    base = jax.random.PRNGKey(seed)
    blocks = [{"images": draw(jax.random.fold_in(base, i))}
              for i in range(rows // block_rows)]
    schema = Schema([ColumnInfo(
        "images", dt.float32, Shape((-1, side, side, 3)))])
    frame = TensorFrame(blocks, schema)
    frame._mesh, frame._axis = mesh, mesh.axis_names[0]
    return frame


def build(cell: harness.Cell, devices, seed: int, clock=None):
    """Weights, frame and the pass function the window drives."""
    import jax
    import jax.numpy as jnp

    import tensorframes_tpu as tfs
    from tensorframes_tpu.models import inception as inc

    ref = importlib.import_module(
        f"benchmark.reference.{cell.config['reference']}")
    dtype = jnp.dtype(cell.config["compute_dtype"])
    # the seed is an argument, not a constant of the program: every
    # seed then finds the same executable in the compile cache
    mark = clock.mark if clock else (lambda name: None)
    mark("imports")
    params = jax.block_until_ready(jax.jit(
        lambda s: ref.make_weights(cell.config, s, dtype))(np.int64(seed)))
    mark("weights")
    model_cfg = inc.InceptionConfig(
        num_classes=int(cell.config["num_classes"]),
        image_size=int(cell.config["image_size"]),
        channel_scale=float(cell.config.get("channel_scale", 1.0)),
        compute_dtype=str(cell.config["compute_dtype"]))
    frame = make_frame(cell, devices, seed)
    jax.block_until_ready(frame.blocks())
    mark("frame")

    def program(images):
        return {"logits": inc.forward(model_cfg, params, images)}

    # Program objects made once: the executor's and the plan's caches
    # key on their identity, so every pass reuses one executable
    score = tfs.compile_program(program, frame)
    total = tfs.compile_program(
        lambda logits_input: {"logits": logits_input.sum(axis=0)},
        tfs.map_blocks(score, frame), reduce_mode="blocks")
    mark("programs")

    def one_pass() -> np.ndarray:
        out = tfs.reduce_blocks(total, tfs.map_blocks(score, frame))
        return np.asarray(out["logits"] if isinstance(out, dict) else out)

    return params, frame, one_pass, ref


def reference_sums(cell: harness.Cell, ref, params, frame,
                   quant=None) -> np.ndarray:
    """Per-class sums of the reference's logits over the whole frame.
    Each chip runs the reference over its own rows of a block,
    ``reference_rows`` at a time so that the float32 activations fit;
    nothing crosses chips until the host adds the partial sums, in
    float64."""
    import jax
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    step = int(cell.traffic["reference_rows"])
    mesh, axis = frame.mesh, frame._axis

    def local(p, x, lo):
        rows = lax.dynamic_slice_in_dim(x, lo, step, axis=0)
        return ref.forward(p, rows, quant=quant).sum(axis=0)[None]

    fwd = jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P(), P(axis), P()), out_specs=P(axis)))
    params = jax.device_put(params, NamedSharding(mesh, P()))
    total = np.zeros(int(cell.config["num_classes"]), np.float64)
    parts = []
    for block in frame.blocks():
        images = block["images"]
        per_chip = images.shape[0] // mesh.size
        for lo in range(0, per_chip, step):
            parts.append(fwd(params, images, np.int32(lo)))
    for part in parts:
        total += np.asarray(part, np.float64).sum(axis=0)
    return total


def compare(cell: harness.Cell, answers: List[np.ndarray],
            want: np.ndarray) -> Dict[str, Dict[str, float]]:
    """The numbers that decide ``correct``. ``class_sum_gap``: the widest
    per-class gap between the last pass's sums and the reference's, over
    the reference's largest sum. ``pass_drift``: every other pass of the
    window against the last, exactly (the frame does not change)."""
    last = answers[-1].astype(np.float64)
    scale = float(np.abs(want).max())
    gap = float(np.abs(last - want).max() / scale) if (
        last.shape == want.shape and np.isfinite(last).all()) else float("inf")
    drift = max((float(np.abs(a.astype(np.float64) - last).max())
                 if a.shape == last.shape else float("inf"))
                for a in answers)
    return {
        "class_sum_gap": {"value": gap, "limit": cell.limit("class_sum_gap")},
        "pass_drift": {"value": drift, "limit": cell.limit("pass_drift")},
    }


def limit_readings(cell: harness.Cell, devices, seed: int, control: bool,
                   seconds: float) -> Dict[str, Any]:
    """One seed's readings for ``tools/limits.py``: the program's
    numbers from two passes, and the control's ``class_sum_gap``."""
    params, frame, one_pass, ref = build(cell, devices, seed)
    answers = [one_pass(), one_pass()]
    want = reference_sums(cell, ref, params, frame)
    row = {k: v["value"] for k, v in compare(cell, answers, want).items()}
    if control:
        low = reference_sums(cell, ref, params, frame,
                             quant=cell.config["control"])
        row["control_class_sum_gap"] = compare(
            cell, [low.astype(np.float32)], want)["class_sum_gap"]["value"]
    row["ref_scale"] = float(np.abs(want).max())
    return row


def run(cell: harness.Cell, args, t_start: float, devices) -> None:
    seconds = float(args.seconds)
    clock = harness.SetupClock(t_start)
    params, frame, one_pass, ref = build(cell, devices, int(args.seed), clock)
    rows = int(cell.traffic["rows"])
    for i in range(int(cell.traffic.get("warm_passes", 2))):
        one_pass()  # compiles (or loads) every program of the pass
        clock.mark(f"warm_pass_{i + 1}")
    spans = harness.HostSpans()
    capture = harness.DeviceTrace(cell.name) if args.trace else None
    if args.trace:
        spans.start()
    before = harness.registry_snapshot()
    answers: List[np.ndarray] = []
    pass_spans: List[Dict[str, Any]] = []
    trace_at = 0.2 * seconds
    trace_passes = int(cell.traffic.get("trace_passes", 4))
    t_open = time.perf_counter()
    setup_s = t_open - t_start
    while True:
        t0 = time.perf_counter()
        if capture and capture.state == "idle" and t0 - t_open >= trace_at:
            capture.start_profiler()
            capture.open_window()
            traced = 0
            t0 = time.perf_counter()
        answers.append(one_pass())
        t1 = time.perf_counter()
        pass_spans.append({"name": "bench.pass", "start": t0,
                           "dur": t1 - t0, "args": {}})
        if capture and capture.state == "window":
            traced += 1
            if traced >= trace_passes:
                capture.close_window()
                capture.stop_profiler()
        in_trace = capture is not None and capture.state == "window"
        if t1 - t_open >= seconds and not in_trace:
            break
    t_close = time.perf_counter()
    after = harness.registry_snapshot()
    if args.trace:
        spans.stop()
        spans.spans.extend(pass_spans)
    memory_peak = harness.memory_peak_bytes(devices)
    elapsed = t_close - t_open
    passes = len(answers)

    clock.mark("window")
    want = reference_sums(cell, ref, params, frame)
    clock.mark("reference")
    checks = compare(cell, answers, want)
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    trace = capture.reduce(spans.spans) if capture else None
    if args.trace:
        readings = harness.Readings(
            cell, (t_open, t_close), before, after, spans, trace,
            devices[0].device_kind, memory_peak,
            {"rows_per_pass": rows, "passes": passes,
             "block_rows": int(cell.traffic["block_rows"])})
        metrics = harness.read_per_layer(readings)
    else:
        values = {"rows_per_s": passes * rows / elapsed, "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    harness.emit(cell, devices, correct=correct, attempted=passes, failed=0,
                 metrics=metrics, memory_peak=memory_peak, checks=checks,
                 decisions=harness.plan_decisions(after), trace=trace,
                 clock=clock)

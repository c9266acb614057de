"""Checks of the yardstick itself, on the CPU, in seconds:

    python -m benchmark.selfcheck

1. ``trace_reduce`` against ``testdata/v5e_small.xplane.pb``, a trace
   recorded on a TPU v5e (five executions of a jitted two-matmul step
   under ``host.pass`` annotations): busy/idle union, per-program time,
   per-operation time, gap labelling.
2. ``counts.py`` against the plain references' own shapes: the
   convolution and matmul operations are counted from the jaxpr of each
   reference's forward pass (no arithmetic runs) and must equal the
   closed forms.
3. ``BENCHMARK.json`` against the files it names.

Exits non-zero on the first mismatch.
"""

from __future__ import annotations

import importlib
import math
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def check(cond: bool, what: str) -> None:
    print(("ok   " if cond else "FAIL ") + what)
    if not cond:
        raise SystemExit(1)


def close(a: float, b: float, rel: float = 1e-9) -> bool:
    return abs(a - b) <= rel * max(abs(a), abs(b), 1e-300)


def check_trace_reduce() -> None:
    from benchmark import trace_reduce as tr

    trace = tr.load(os.path.join(HERE, "testdata", "v5e_small.xplane.pb"),
                    annotation_prefix="host.")
    check(len(trace.devices) == 1 and trace.devices[0].ordinal == 0,
          "one /device:TPU plane")
    dev = trace.devices[0]
    check(len(dev.modules) == 5
          and {m[0] for m in dev.modules} == {"jit_small_step"},
          "5 executions of jit_small_step on the XLA Modules line")
    check(len(dev.ops) == 20 and {o[0] for o in dev.ops} == {
        "copy-start", "copy-done", "convolution_tanh_fusion",
        "convolution_reduce_fusion"}, "20 operations, 4 names")
    passes = trace.annotations["host.pass"]
    check(len(passes) == 5, "5 host.pass annotations")
    window = (passes[0][0], passes[-1][1])
    spans = [("host.pass", a, b) for a, b in passes]
    spans.append(("inner", passes[1][0] + 1e5, passes[1][1] - 1e5))
    out = tr.reduce(trace, window, spans)
    # by hand: the first execution starts before the first annotation
    # (device and host clocks agree to ~1 ms), so 4 lie wholly inside
    want_busy = sum(min(b, window[1]) - max(a, window[0])
                    for _, a, b in dev.ops
                    if b > window[0] and a < window[1]) * 1e-9
    check(close(out["busy_s"], want_busy, 1e-6),
          f"busy {out['busy_s']:.9f} s = sum of back-to-back ops "
          f"{want_busy:.9f} s")
    check(close(out["window_s"], (window[1] - window[0]) * 1e-9),
          "window is the annotations' extent")
    check(len(out["programs"]["jit_small_step"]) == 4
          and all(close(d, 181e-6, 5e-3) for d in
                  out["programs"]["jit_small_step"]),
          "4 whole executions, 181 us each")
    inner = out["op_seconds_in"]["jit_small_step"]
    check(close(sum(inner.values()), sum(out["programs"]["jit_small_step"]),
                1e-3), "ops inside the program add up to its device time")
    check(out["breakdown"]["device_ops"][0][0] == "convolution_tanh_fusion",
          "longest operation first")
    check(tr.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
          and tr.gaps([(0, 3), (5, 6)], (0, 8)) == [(3, 5), (6, 8)],
          "union and gaps")
    check(tr.label_gaps([(passes[1][0] + 2e5, passes[1][0] + 3e5)], spans)
          == ["inner"], "a gap is labelled by the innermost covering span")
    check(tr.label_gaps([(0.0, 1.0)], spans) == ["(no host span)"],
          "a gap no span covers says so")
    check(tr.op_name("%fusion.12 = bf16[8]{0} fusion(%x)") == "fusion.12"
          and tr.program_name("jit_step(123)") == "jit_step", "names")


def _jaxpr_flops(jaxpr) -> float:
    total = 0.0
    for eqn in jaxpr.eqns:
        for sub in eqn.params.values():
            inner = getattr(sub, "jaxpr", None)
            if inner is not None and hasattr(inner, "eqns"):
                total += _jaxpr_flops(inner)
        out = eqn.outvars[0].aval.shape if eqn.outvars else ()
        if eqn.primitive.name == "conv_general_dilated":
            kh, kw, cin, _ = eqn.invars[1].aval.shape  # HWIO
            total += 2.0 * math.prod(out) * kh * kw * cin
        elif eqn.primitive.name == "dot_general":
            (lc, _), _ = eqn.params["dimension_numbers"]
            k = math.prod(eqn.invars[0].aval.shape[i] for i in lc)
            total += 2.0 * math.prod(out) * k
    return total


def check_counts() -> None:
    import jax
    import jax.numpy as jnp

    from benchmark import counts
    from benchmark.harness import load_json
    from benchmark.reference import gpt2, inception_v3

    cfg = load_json(HERE, "configs", "inception-v3.json")
    shapes = jax.eval_shape(
        lambda: inception_v3.make_weights(cfg, 0, jnp.bfloat16))
    rows = 2
    images = jax.ShapeDtypeStruct(
        (rows, cfg["image_size"], cfg["image_size"], 3), jnp.float32)
    traced = _jaxpr_flops(jax.make_jaxpr(inception_v3.forward)(
        shapes, images).jaxpr)
    closed = counts.inception_v3_flops(cfg, rows)
    check(close(traced, closed), f"Inception-v3: reference's convolutions "
          f"and classifier {traced / rows / 1e9:.4f} GFLOP/image = "
          f"counts.py {closed / rows / 1e9:.4f}")
    check(11.3e9 < closed / rows < 11.6e9, "about 11.4 GFLOP per image")
    table = {n: (kh, kw, ci, co) for n, kh, kw, ci, co, _ in
             counts.inception_v3_layers(cfg)}
    ref_table = {n: (kh, kw, ci, co) for n, kh, kw, ci, co in
                 inception_v3.conv_table(cfg)}
    check({k: v for k, v in table.items() if k != "fc"} == ref_table,
          f"the {len(ref_table)} convolutions' shapes agree one by one")

    g = load_json(HERE, "configs", "gpt2-small.json")
    t = 64
    shapes = jax.eval_shape(lambda: gpt2.make_weights(g, 0))
    tokens = jax.ShapeDtypeStruct((1, t), jnp.int32)
    traced = _jaxpr_flops(jax.make_jaxpr(
        lambda p, x, pos: gpt2.logits_at(g, p, x, pos))(
            shapes, tokens, tokens).jaxpr)
    # the dense reference attends all t positions from each of t tokens
    closed = counts.gpt2_decode_step_flops(g, [t] * t)
    check(close(traced, closed), f"GPT-2: reference's matmuls over {t} "
          f"tokens {traced / 1e9:.4f} GFLOP = counts.py at {t} slots of "
          f"context {t} {closed / 1e9:.4f}")
    check(counts.gpt2_matmul_params(g) == 84_934_656,
          "84,934,656 matmul parameters in the 12 blocks")
    per_pos = counts.gpt2_decode_attn_bytes(g, [1])
    check(per_pos == 12 * (2 * 768 + 2 * 12 * 4) == 19_584,
          "19,584 KV bytes per context position (int8 + f32 scales)")


def check_manifest() -> None:
    from benchmark.harness import load_json

    root = os.path.dirname(HERE)
    manifest = load_json(root, "BENCHMARK.json")
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    cells = {w["name"]: w for w in manifest["workloads"]}
    for c in manifest["configs"]:
        body = load_json(root, c["file"])
        check(body["source"] == c["source"] and body["reduced"] == c["reduced"],
              f"config {c['name']}: file agrees with its entry")
        importlib.import_module(f"benchmark.drivers.{body['driver']}")
        importlib.import_module(f"benchmark.reference.{body['reference']}")
    for name, w in cells.items():
        body = load_json(HERE, "workloads", name + ".json")
        check(body["config"] == w["config"] and body["chips"] == w["chips"]
              and name == f"{w['config']}.{w['traffic']}",
              f"cell {name}: file agrees with its entry")
    for m in manifest["per_layer"]:
        body = load_json(HERE, "metrics", m["name"] + ".json")
        reader = importlib.import_module(f"benchmark.readers.{body['reader']}")
        same = all(body[k] == m[k] for k in m)
        reported = all(
            m["moves"] in e2e and ("workloads" not in e2e[m["moves"]]
                                   or w in e2e[m["moves"]]["workloads"])
            for w in m["workloads"])
        check(same and reported and callable(reader.read),
              f"metric {m['name']}: file, reader {body['reader']}, moves "
              f"{m['moves']}")


def main() -> int:
    sys.path.insert(0, os.path.dirname(HERE))
    check_trace_reduce()
    check_counts()
    check_manifest()
    print("selfcheck: all ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())

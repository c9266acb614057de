"""Window loop for the served sparse-expert decoder cells: the closed
loop of ``drivers/serving.py`` (its ``Window``, ``Request``,
``HostSampler``, ``sample_served`` and ``step_contexts``, the pro-rata
token count, the window rule and ``failed_requests``, all reused as they
are) against a ``Server`` whose ``register_decode`` endpoint is built
from the configuration file.

Its own: the model and its bfloat16 weights from the configuration's
published keys, a layer at a time (the float32 draws of one layer, 1.7
GB, never stand beside another's); ``served_logit_gap`` over the
layer-streamed reference (one layer's float32 weights on the chip at a
time, the sampled requests passed through it one after another); and
the lines on standard error that give each page kind's share in use.
"""

from __future__ import annotations

import gc
import importlib
import sys
from typing import Any, Dict, List, Optional

import numpy as np

from .. import harness
from .serving import Request, Window, sample_served, step_contexts


def dtype_of(cell: harness.Cell):
    """bfloat16, as the configuration states. A rehearsal runs float32:
    at its widths (top-2 of 8 experts over 64 channels) one bfloat16
    rounding flips a route and moves a logit further than a planted
    fault does, so its check could tell nothing apart."""
    import jax.numpy as jnp

    return jnp.float32 if cell.rehearsal else jnp.bfloat16


def model_config(cell: harness.Cell):
    from tensorframes_tpu.models import sparse_decoder as sd

    c = cell.config

    def rope(kind: str):
        r = c["rope_parameters"][f"{kind}_attention"]
        if r["rope_type"] != "yarn":
            return sd.RopeSpec(float(r["rope_theta"]))
        return sd.RopeSpec(
            float(r["rope_theta"]), factor=float(r["factor"]),
            original_max=int(r["original_max_position_embeddings"]),
            beta_fast=float(r["beta_fast"]), beta_slow=float(r["beta_slow"]),
            attention_factor=float(r["attention_factor"]))

    layers = int(c["num_hidden_layers"])
    t = cell.traffic
    return sd.SparseDecoderConfig(
        vocab_size=int(c["vocab_size"]), hidden=int(c["hidden_size"]),
        layer_types=tuple(x.split("_")[0] for x in c["layer_types"][:layers]),
        num_heads=int(c["num_attention_heads"]),
        num_kv_heads=int(c["num_key_value_heads"]),
        head_dim=int(c["head_dim"]),
        sliding_window=int(c["sliding_window"]),
        rope_full=rope("full"), rope_sliding=rope("sliding"),
        num_experts=int(c["num_experts"]),
        experts_per_token=int(c["num_experts_per_tok"]),
        expert_hidden=int(c["moe_intermediate_size"]),
        norm_topk_prob=bool(c["norm_topk_prob"]),
        rms_eps=float(c["rms_norm_eps"]),
        max_seq_len=int(t["max_prompt_len"]) + int(t["max_new_tokens"]),
        dtype=dtype_of(cell))


def make_params(cell: harness.Cell, ref, seed: int):
    """The program's weights: the reference's float32 draws cast to
    bfloat16 (the norm gains stay float32), made on the device one layer
    at a time."""
    import jax

    dtype = dtype_of(cell)

    def cast(tree):
        return {k: v if "norm" in k else v.astype(dtype)
                for k, v in tree.items()}

    outer = jax.jit(lambda s: cast(ref.make_outer_weights(cell.config, s)))
    layer = jax.jit(
        lambda s, l: cast(ref.make_layer_weights(cell.config, s, l)))
    params = dict(outer(np.int64(seed)))
    params["layers"] = [
        jax.block_until_ready(layer(np.int64(seed), np.int32(l)))
        for l in range(int(cell.config["num_hidden_layers"]))]
    return params


def start_server(cell: harness.Cell, seed: int, clock=None):
    import tensorframes_tpu as tfs

    ref = importlib.import_module(
        f"benchmark.reference.{cell.config['reference']}")
    mark = clock.mark if clock else (lambda name: None)
    # first, so that a program without this block fails at once
    config = model_config(cell)
    mark("imports")
    params = make_params(cell, ref, seed)
    mark("weights")
    t = cell.traffic
    server = tfs.Server()
    server.register_decode("gen", config, params, tfs.DecodeConfig(
        max_slots=int(t["max_slots"]), page_size=int(t["page_size"]),
        max_prompt_len=int(t["max_prompt_len"]),
        max_new_tokens=int(t["max_new_tokens"]), num_pages=t.get("num_pages")))
    server.start()
    mark("server_start")
    return server, ref


def kind_pages(cell: harness.Cell) -> Dict[str, int]:
    """The pages of each kind that sequences can hold: the full kind as
    the traffic file sizes it (else every slot's horizon), the window
    kind every slot's whole ring."""
    t = cell.traffic
    page, slots = int(t["page_size"]), int(t["max_slots"])
    horizon = int(t["max_prompt_len"]) + int(t["max_new_tokens"])
    full = int(t["num_pages"]) - 1 if t.get("num_pages") \
        else slots * -(-horizon // page)
    ring = -(-int(cell.config["sliding_window"]) // page) + 1
    return {"full": full, "window": slots * ring}


def served_logit_gap(cell: harness.Cell, ref, seed: int,
                     sample: List[Request], control: bool = False,
                     fault: Optional[str] = None) -> Dict[str, float]:
    """The gap by which a served token's logit lies below the
    reference's best, over every answer position of the sampled
    requests: its mean (``served_logit_gap_mean``), its widest
    (``served_logit_gap``), and the share of the compared tokens that are
    not the reference's first choice (``served_not_first_share``). The
    reference runs once over each prompt with its served tokens
    (``fault`` puts a planted fault in the reference's place). With
    ``control`` also the same three for the token that the
    lower-precision reference puts first at each of those positions
    (``control_*``)."""
    vocab = int(cell.config["vocab_size"])
    out = {"served_logit_gap": 0.0, "served_tokens": 0, "served_top1": 0}
    lengths = sorted(int(x) for x in cell.traffic["reference_lengths"])
    rows, served = [], []
    for r in sample:
        plen = len(r.prompt)
        if r.tokens.shape != (r.new,) or r.tokens.min() < 0 \
                or r.tokens.max() >= vocab:
            out["served_logit_gap"] = float("inf")
            continue
        # one of a few lengths whatever the sample drew (a causal pass:
        # the padding behind a sequence changes nothing before it)
        width = next(x for x in lengths if x >= plen + r.new - 1)
        tokens = np.zeros(width, np.int32)
        tokens[:plen] = r.prompt
        tokens[plen:plen + r.new - 1] = r.tokens[:-1]
        rows.append((tokens, plen - 1 + np.arange(r.new, dtype=np.int32)))
        served.append(r.tokens)
    firsts = [None] * len(rows)
    if control:
        firsts = [np.asarray(low).argmax(axis=-1) for low in ref.logits_at(
            cell.config, seed, rows, quant=cell.config["control"])]
    gaps, control_gaps, first_choice = [], [], 0
    at = ref.logits_at(cell.config, seed, rows, fault=fault)
    for logits, got, first in zip(at, served, firsts):
        logits = np.asarray(logits)
        top, each = logits.max(axis=-1), np.arange(len(got))
        choice = logits.argmax(axis=-1)
        gaps.append(top - logits[each, got])
        out["served_top1"] += int((choice == got).sum())
        if control:
            control_gaps.append(top - logits[each, first])
            first_choice += int((choice == first).sum())
    for name, parts, same in (("served", gaps, out["served_top1"]),
                              ("control", control_gaps, first_choice)):
        if not parts:
            continue
        all_gaps = np.concatenate(parts)
        out["served_tokens"] = int(all_gaps.size)
        # the widest gap is one token's: a route that flips under a
        # rounding moves a single token far. What a fault moves is most
        # tokens a little: the mean gap, and how many of the compared
        # tokens are not the reference's first choice
        out[f"{name}_logit_gap"] = max(
            float(out.get(f"{name}_logit_gap", 0.0)),
            float(all_gaps.max(initial=0.0)))
        out[f"{name}_logit_gap_mean"] = float(all_gaps.mean())
        out[f"{name}_not_first_share"] = 1.0 - same / all_gaps.size
    return out


def measure(cell: harness.Cell, devices, seed: int, seconds: float,
            trace: bool, clock=None):
    """Set-up, ramp and window; the server is stopped and its state
    freed on return."""
    server, ref = start_server(cell, seed, clock)
    window = Window(cell, server, seed, seconds, trace)
    try:
        window.drive()
        if clock:
            clock.mark("ramp", at=window.t_open)
            clock.mark("window_and_drain")
        memory_peak = harness.memory_peak_bytes(devices)
    finally:
        server.stop(drain=False, timeout=30)
    if clock:
        clock.mark("server_stop")
    if window.capture is not None and window.capture.state == "closed":
        window.capture.stop_profiler()
        if clock:
            clock.mark("profiler_stop")
    # the reference needs the chip's memory: nothing may keep the
    # engine's weights and pool alive (the window holds its server)
    window.server = None
    del server
    gc.collect()
    return window, ref, memory_peak


def limit_readings(cell: harness.Cell, devices, seed: int, control: bool,
                   seconds: float, fault: Optional[str] = None
                   ) -> Dict[str, Any]:
    """One seed's readings for ``tools/limits.py``."""
    window, ref, _ = measure(cell, devices, seed, seconds, trace=False)
    finished = window.in_window()
    row = served_logit_gap(cell, ref, seed,
                           sample_served(cell, finished, seed), control,
                           fault)
    row["finished"] = len(finished)
    return row


def run(cell: harness.Cell, args, t_start: float, devices) -> None:
    seed = int(args.seed)
    clock = harness.SetupClock(t_start)
    window, ref, memory_peak = measure(cell, devices, seed,
                                       float(args.seconds), bool(args.trace),
                                       clock)
    setup_s = window.t_open - t_start
    elapsed = window.t_close - window.t_open
    finished = window.in_window()
    good = [r for r in finished if r.error is None]
    failed = len(finished) - len(good)

    sample = sample_served(cell, finished, seed)
    got = served_logit_gap(cell, ref, seed, sample) if sample else {}
    clock.mark("reference")
    # every limit the traffic file states is a check of that name
    checks = {name: {"value": float(got.get(name, float("inf"))),
                     "limit": cell.limit(name)}
              for name in cell.traffic["limits"]}
    checks["failed_requests"] = {"value": float(failed), "limit": 0.0}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    cap = window.capture
    trace = cap.reduce(window.spans.spans) if cap else None
    if args.trace:
        client = {"traced_step_contexts":
                  step_contexts(window, cap.t0, cap.t1)}
        readings = harness.Readings(
            cell, (window.t_open, window.t_close), window.before,
            window.after, window.spans, trace, devices[0].device_kind,
            memory_peak, client)
        metrics = harness.read_per_layer(readings)
    else:
        norm = [1e3 * (r.t_done - r.t_submit) / r.new for r in good]
        values = {
            "out_tokens_per_s": window.tokens_in_window() / elapsed,
            "norm_latency_p95_ms": (harness.quantile(norm, 0.95)
                                    if norm else float("inf")),
            "setup_s": setup_s,
        }
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    tag = "REHEARSAL " if cell.rehearsal else ""
    pages = kind_pages(cell)
    used = [1.0 - free / pages["full"] for free in window.pool_free]
    print(tag + f"full pages in use: median {100 * float(np.median(used)):.1f}"
          f" %, least {100 * min(used):.1f} %, most {100 * max(used):.1f} % "
          f"of {pages['full']} ({len(used)} readings)", file=sys.stderr)

    def grew(name: str, **labels) -> float:
        return (harness.metric_total(window.after, name, **labels)
                - harness.metric_total(window.before, name, **labels))

    ring_free = [harness.metric_total(s, "tftpu_decode_free_window_pages")
                 for s in (window.before, window.after)]
    print(tag + "window pages in use at the window's open and close: "
          + " and ".join(f"{100 * (1 - f / pages['window']):.1f} %"
                         for f in ring_free)
          + f" of {pages['window']}; preemptions "
          f"{grew('tftpu_decode_preemptions_total'):.0f}; compiles in the "
          f"window {grew('tftpu_executor_jit_cache_misses_total'):.0f}; "
          f"requests finished {len(finished)}; served tokens compared "
          f"{got.get('served_tokens', 0)}, of them the reference's first "
          f"choice {got.get('served_top1', 0)}; gap mean "
          f"{got.get('served_logit_gap_mean', float('nan')):.6f}, widest "
          f"{got.get('served_logit_gap', float('nan')):.4f}",
          file=sys.stderr)
    for line in window.tenths_lines():
        print(tag + line, file=sys.stderr)
    harness.emit(cell, devices, correct=correct, attempted=len(finished),
                 failed=failed, metrics=metrics, memory_peak=memory_peak,
                 checks=checks, decisions=harness.plan_decisions(window.after),
                 trace=trace, clock=clock)

"""Window loop for served-model cells: closed-loop clients against
``Server.submit`` on a ``Server`` with one ``register_decode`` endpoint.

The traffic file gives the clients, the engine's sizes and the length
mix (``traffic.py`` turns it into requests); the configuration gives the
model's sizes and names its plain reference. One thread offers all the
load: it polls the clients' futures every millisecond and sends a
client's next request the moment its last one completed.

The window opens at the completion that gives every client its first
answer (that ramp is set-up the traffic needs) and closes ``--seconds``
later. The loop goes on past the close, under the same load, until
every request that was in flight at the close has its answer: a request
counts its tokens in the share of its life that lies inside the window.
"""

from __future__ import annotations

import importlib
import time
from typing import Any, Dict, List, Optional

import numpy as np

from .. import harness, traffic


class Request:
    __slots__ = ("client", "prompt", "new", "t_submit", "t_done", "future",
                 "tokens", "error")

    def __init__(self, client: int, feeds: Dict[str, Any]):
        self.client = client
        self.prompt = feeds["prompt"]
        self.new = int(feeds["max_new_tokens"])
        self.t_submit = 0.0
        self.t_done: Optional[float] = None
        self.future = None
        self.tokens: Optional[np.ndarray] = None
        self.error: Optional[BaseException] = None


def model_config(cell: harness.Cell):
    from tensorframes_tpu.models import generation as gen

    c = cell.config
    return gen.gpt_small(
        vocab_size=int(c["vocab_size"]), hidden=int(c["n_embd"]),
        num_heads=int(c["n_head"]), num_layers=int(c["n_layer"]),
        mlp_ratio=int(c["n_inner"]) // int(c["n_embd"]),
        max_seq_len=int(c["n_positions"]))


def start_server(cell: harness.Cell, seed: int, clock=None):
    """The server under test with its seeded weights: float32 masters
    made on the device in one jitted call, quantised by the program's
    own int8 path in another, and the engine's warm-up ladder run."""
    import jax

    import tensorframes_tpu as tfs
    from tensorframes_tpu.models import transformer as tr

    ref = importlib.import_module(
        f"benchmark.reference.{cell.config['reference']}")
    mark = clock.mark if clock else (lambda name: None)
    mark("imports")
    master = jax.jit(lambda s: ref.make_weights(cell.config, s))(
        np.int64(seed))
    params = jax.block_until_ready(jax.jit(tr.quantize_params)(master))
    del master
    mark("weights")
    t = cell.traffic
    server = tfs.Server()
    server.register_decode("gen", model_config(cell), params, tfs.DecodeConfig(
        max_slots=int(t["max_slots"]), page_size=int(t["page_size"]),
        max_prompt_len=int(t["max_prompt_len"]),
        max_new_tokens=int(t["max_new_tokens"]), num_pages=None))
    server.start()
    mark("server_start")
    return server, ref


class Window:
    """The closed loop and what it recorded."""

    def __init__(self, cell: harness.Cell, server, seed: int,
                 seconds: float, trace: bool):
        self.cell, self.server = cell, server
        self.seconds, self.trace = seconds, trace
        self.stream = traffic.requests(
            cell.traffic, int(cell.config["vocab_size"]), seed)
        self.done: List[Request] = []
        self.spans = harness.HostSpans()
        self.capture = harness.DeviceTrace(cell.name) if trace else None
        self.before: List[Dict[str, Any]] = []
        self.after: List[Dict[str, Any]] = []
        self.t_open = self.t_close = 0.0

    def _send(self, client: int) -> Request:
        req = Request(client, next(self.stream))
        req.t_submit = time.perf_counter()
        req.future = self.server.submit(
            "gen", {"prompt": req.prompt, "max_new_tokens": req.new})
        return req

    def drive(self) -> None:
        t = self.cell.traffic
        n = int(t["clients"])
        stagger = float(t["stagger_s"])
        settle, traced = float(t["trace_settle_s"]), float(t["trace_seconds"])
        flying: List[Optional[Request]] = [None] * n
        answered = [False] * n
        t_begin = time.perf_counter()
        opened = closed = False
        owed: List[Request] = []  # in flight when the window closed
        cap = self.capture
        while True:
            now = time.perf_counter()
            if opened and not closed and now - self.t_open >= self.seconds \
                    and (cap is None or cap.state in ("idle", "done")):
                closed = True
                self.t_close = now
                self.after = harness.registry_snapshot()
                if self.trace:
                    self.spans.stop()
                owed = [r for r in flying if r is not None]
            for i in range(n):
                req = flying[i]
                if req is None:
                    if now - t_begin >= i * stagger:
                        flying[i] = self._send(i)
                    continue
                if not req.future.done():
                    continue
                req.t_done = time.perf_counter()
                req.error = req.future.exception(0)
                if req.error is None:
                    req.tokens = np.asarray(
                        req.future.result(0)["tokens"]).reshape(-1)
                self.done.append(req)
                answered[i] = True
                if not opened and all(answered):
                    # the completion that gives the last client its
                    # first answer opens the window
                    opened = True
                    self.t_open = req.t_done
                    self.before = harness.registry_snapshot()
                    if self.trace:
                        self.spans.start()
                # the loop stays closed past the window's end, so that
                # the requests it owes finish under the same load
                flying[i] = self._send(i)
            if closed and all(r.t_done is not None for r in owed):
                break
            if cap is not None and opened and not closed:
                since = now - self.t_open
                if cap.state == "idle" and since >= 0.2 * self.seconds:
                    cap.start_profiler()
                    t_prof = time.perf_counter()
                elif cap.state == "profiling" \
                        and time.perf_counter() - t_prof >= settle:
                    cap.open_window()
                elif cap.state == "window" \
                        and time.perf_counter() - cap.t0 >= traced:
                    cap.close_window()
            time.sleep(0.001)
        self.unfinished = [r for r in flying if r is not None
                           and r.t_done is None]

    def in_window(self) -> List[Request]:
        """The requests that completed inside the window."""
        return [r for r in self.done
                if self.t_open < r.t_done <= self.t_close]

    def tokens_in_window(self) -> float:
        """Output tokens of the window: every answered request gives its
        tokens in the share of its life (submit to result) that lies
        inside the window. A request wholly inside gives all of them,
        one that straddles an edge its share, so the count does not jump
        with which request happened to end beside an edge."""
        total = 0.0
        for r in self.done:
            if r.error is not None:
                continue
            inside = min(r.t_done, self.t_close) - max(r.t_submit, self.t_open)
            if inside > 0:
                total += r.new * inside / (r.t_done - r.t_submit)
        return total


def step_contexts(window: Window, t0: float, t1: float) -> List[List[float]]:
    """For each ``decode.step`` span inside [t0, t1]: the context lengths
    of the requests in flight at its midpoint, from the client's records
    (prompt length plus the share of the answer that the elapsed share of
    the request's life had produced)."""
    good = [r for r in window.done if r.error is None]
    per_token = np.median([(r.t_done - r.t_submit) / r.new for r in good])
    known = [(r.t_submit, r.t_done, len(r.prompt), r.new) for r in good]
    known += [(r.t_submit, r.t_submit + r.new * per_token, len(r.prompt),
               r.new) for r in window.unfinished]
    out = []
    for span in window.spans.named("decode.step", (t0, t1)):
        mid = span["start"] + span["dur"] / 2
        out.append([plen + new * (mid - a) / (b - a)
                    for a, b, plen, new in known if a <= mid < b])
    return [c for c in out if c]


def sample_served(cell: harness.Cell, finished: List[Request], seed: int
                  ) -> List[Request]:
    """A seeded sample of the window's finished requests, the longest
    (prompt plus answer) among them."""
    ok = [r for r in finished if r.error is None]
    if not ok:
        return []
    k = min(int(cell.traffic["sample_requests"]), len(ok))
    longest = max(range(len(ok)), key=lambda i: len(ok[i].prompt) + ok[i].new)
    rng = np.random.default_rng(int(seed) + 1)
    picks = {longest}
    for i in rng.permutation(len(ok)):
        if len(picks) >= k:
            break
        picks.add(int(i))
    return [ok[i] for i in sorted(picks)]


def served_logit_gap(cell: harness.Cell, ref, seed: int,
                     sample: List[Request], control: bool = False
                     ) -> Dict[str, float]:
    """The widest gap by which a served token's logit lies below the
    reference's best, over every answer position of the sampled
    requests: the reference runs once over each prompt with its served
    tokens. With ``control`` also the same gap for the token that the
    lower-precision reference puts first at each of those positions."""
    import jax
    import jax.numpy as jnp

    cfg = cell.config
    width = int(cfg["n_positions"])
    # one shape whatever the sample drew: the programs below compile once
    most = int(cell.traffic["max_new_tokens"])
    vocab = int(cfg["vocab_size"])
    params = jax.jit(lambda s: ref.make_weights(cfg, s))(np.int64(seed))

    @jax.jit
    def gaps(params, tokens, positions, served):
        logits = ref.logits_at(cfg, params, tokens, positions)
        best = logits.max(axis=-1)
        got = jnp.take_along_axis(logits, served[:, :, None], axis=-1)[..., 0]
        return best - got, logits.argmax(axis=-1)

    @jax.jit
    def control_gaps(params, tokens, positions):
        logits = ref.logits_at(cfg, params, tokens, positions)
        low = ref.logits_at(cfg, params, tokens, positions,
                            quant=cfg["control"]).argmax(axis=-1)
        got = jnp.take_along_axis(logits, low[:, :, None], axis=-1)[..., 0]
        return logits.max(axis=-1) - got

    out = {"served_logit_gap": 0.0, "served_tokens": 0, "served_top1": 0}
    if control:
        out["control_logit_gap"] = 0.0
    chunk = 8
    for lo in range(0, len(sample), chunk):
        part = sample[lo:lo + chunk]
        tokens = np.zeros((chunk, width), np.int32)
        positions = np.zeros((chunk, most), np.int32)
        served = np.zeros((chunk, most), np.int32)
        valid = np.zeros((chunk, most), bool)
        for k, r in enumerate(part):
            plen = len(r.prompt)
            if r.tokens.shape != (r.new,) or r.tokens.min() < 0 \
                    or r.tokens.max() >= vocab:
                out["served_logit_gap"] = float("inf")
                continue
            tokens[k, :plen] = r.prompt
            tokens[k, plen:plen + r.new - 1] = r.tokens[:-1]
            positions[k, :r.new] = plen - 1 + np.arange(r.new)
            served[k, :r.new] = r.tokens
            valid[k, :r.new] = True
        gap, top = gaps(params, tokens, positions, served)
        gap, top = np.asarray(gap), np.asarray(top)
        out["served_logit_gap"] = max(out["served_logit_gap"],
                                      float(gap[valid].max(initial=0.0)))
        out["served_tokens"] += int(valid.sum())
        out["served_top1"] += int((top == served)[valid].sum())
        if control:
            low = np.asarray(control_gaps(params, tokens, positions))
            out["control_logit_gap"] = max(out["control_logit_gap"],
                                           float(low[valid].max(initial=0.0)))
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
    del server
    return window, ref, memory_peak


def limit_readings(cell: harness.Cell, devices, seed: int, control: bool,
                   seconds: float) -> Dict[str, Any]:
    """One seed's readings for ``tools/limits.py``."""
    window, ref, _ = measure(cell, devices, seed, seconds, trace=False)
    finished = window.in_window()
    row = served_logit_gap(cell, ref, seed,
                           sample_served(cell, finished, seed), control)
    row["finished"] = len(finished)
    return row


def run(cell: harness.Cell, args, t_start: float, devices) -> None:
    seed = int(args.seed)
    clock = harness.SetupClock(t_start)
    window, ref, memory_peak = measure(cell, devices, seed,
                                       float(args.seconds), bool(args.trace),
                                       clock)
    clock.mark("server_stop")
    setup_s = window.t_open - t_start
    elapsed = window.t_close - window.t_open
    finished = window.in_window()
    good = [r for r in finished if r.error is None]
    failed = len(finished) - len(good)

    sample = sample_served(cell, finished, seed)
    got = served_logit_gap(cell, ref, seed, sample) if sample else {
        "served_logit_gap": float("inf")}
    clock.mark("reference")
    checks = {
        "served_logit_gap": {"value": got["served_logit_gap"],
                             "limit": cell.limit("served_logit_gap")},
        "failed_requests": {"value": float(failed), "limit": 0.0},
    }
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    cap = window.capture
    trace = cap.reduce(window.spans.spans) if cap else None
    if args.trace:
        client = {
            "latency_s": [r.t_done - r.t_submit for r in good],
            "traced_step_contexts": step_contexts(window, cap.t0, cap.t1),
        }
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
    harness.emit(cell, devices, correct=correct, attempted=len(finished),
                 failed=failed, metrics=metrics, memory_peak=memory_peak,
                 checks=checks, decisions=harness.plan_decisions(window.after),
                 trace=trace, clock=clock)

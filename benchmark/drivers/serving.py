"""Window loop for served-model cells: closed-loop clients against
``Server.submit`` on a ``Server`` with one ``register_decode`` endpoint.

The traffic file gives the clients, the engine's sizes and the length
mix (``traffic.py`` turns it into requests); the configuration gives the
model's sizes and names its plain reference. One thread offers all the
load: it polls the clients' futures every millisecond and sends a
client's next request the moment it sees the last one answered. How
late that was (the next submit less the instant the engine set the
result) is recorded with each request. Clients that each block on their
own future were tried in its place (PERF.md, PR 33): the lag was no
shorter, since a woken client waits for the interpreter lock as the
poll does, and the runs spread four times as widely.

The same thread reads, every tenth of a second of the window, how many
of the pool's pages are free (the cell's size is what its traffic holds,
not what the engine reserves), and at every tenth of the window what the
engine and the host had done by then (``HostSampler``).

The window opens at the completion that gives every client its first
answer (that ramp is set-up the traffic needs) and closes ``--seconds``
later. The loop goes on past the close, under the same load, until
every request that was in flight at the close has its answer: a request
counts its tokens in the share of its life that lies inside the window.
"""

from __future__ import annotations

import gc
import importlib
import sys
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from .. import harness, traffic


class Request:
    __slots__ = ("client", "prompt", "new", "t_submit", "t_done", "future",
                 "tokens", "error", "lag")

    def __init__(self, client: int, feeds: Dict[str, Any]):
        self.client = client
        self.prompt = feeds["prompt"]
        self.new = int(feeds["max_new_tokens"])
        self.t_submit = 0.0
        self.t_done: Optional[float] = None
        self.future = None
        self.tokens: Optional[np.ndarray] = None
        self.error: Optional[BaseException] = None
        #: t_submit less the instant the engine answered this client's
        #: last request; None for a client's first
        self.lag: Optional[float] = None


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
        max_new_tokens=int(t["max_new_tokens"]), num_pages=t.get("num_pages")))
    server.start()
    mark("server_start")
    return server, ref


def pool_pages(traffic: Dict[str, Any]) -> int:
    """The pages of the KV pool that sequences can hold: the traffic
    file's ``num_pages``, or as the engine sizes it where that is null
    (every slot's full horizon), less the reserved null page."""
    if traffic.get("num_pages"):
        return int(traffic["num_pages"]) - 1
    horizon = int(traffic["max_prompt_len"]) + int(traffic["max_new_tokens"])
    return int(traffic["max_slots"]) * -(-horizon // int(traffic["page_size"]))


class HostSampler:
    """What the host did beside the clients, read at the window's open,
    at each tenth's edge and at its close: the tokens the engine had
    made (its own counter: a request's pro-rata share smears a slow
    stretch over its whole life), the seconds the interpreter's
    collector held the process (``gc.callbacks``), the process's CPU
    seconds and those of the engine's loop thread. A slow tenth then
    says whether the loop waited or ran and got less done. The
    machine's own counters (``/proc/stat``, involuntary context
    switches) read nought on the chip's host and are not taken."""

    def __init__(self):
        from tensorframes_tpu.observability.metrics import REGISTRY

        self.rows: List[Dict[str, float]] = []
        self._tokens = REGISTRY.counter("tftpu_decode_tokens_total")
        self._gc_s = self._gc_t = 0.0
        self._gc_full_n = 0
        self._loop_clock = None

    def _on_gc(self, phase: str, info: Dict[str, int]) -> None:
        if phase == "start":
            self._gc_t = time.perf_counter()
        else:
            self._gc_s += time.perf_counter() - self._gc_t
            self._gc_full_n += info["generation"] == 2

    def start(self) -> None:
        gc.callbacks.append(self._on_gc)
        for thread in threading.enumerate():
            if thread.name.startswith("tfs-decode-"):
                try:
                    self._loop_clock = time.pthread_getcpuclockid(
                        thread.ident)
                except (AttributeError, OSError):
                    pass

    def stop(self) -> None:
        gc.callbacks.remove(self._on_gc)

    def sample(self, now: float) -> None:
        row = {"t": now, "tokens": self._tokens.value, "gc_s": self._gc_s,
               "gc_full_n": float(self._gc_full_n),
               "cpu_s": time.process_time()}
        if self._loop_clock is not None:
            try:
                row["loop_cpu_s"] = time.clock_gettime(self._loop_clock)
            except OSError:
                pass
        self.rows.append(row)

    def deltas(self, key: str) -> List[float]:
        """``key``'s growth between one sample and the next."""
        got = [r[key] for r in self.rows if key in r]
        return [b - a for a, b in zip(got[:-1], got[1:])]

    def engine_rates(self, until: Optional[float] = None) -> List[float]:
        """The engine's tokens per second between one sample and the
        next, for the stretches that ended by ``until``."""
        return [(b["tokens"] - a["tokens"]) / (b["t"] - a["t"])
                for a, b in zip(self.rows[:-1], self.rows[1:])
                if until is None or b["t"] <= until]


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
        self.host = HostSampler()
        #: the pool's free pages, read every tenth of a second of the window
        self.pool_free: List[float] = []

    def _send(self, client: int, last: Optional[Request] = None) -> Request:
        req = Request(client, next(self.stream))
        req.t_submit = time.perf_counter()
        if last is not None:
            req.lag = req.t_submit - last.future.t_done
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
        from tensorframes_tpu.observability.metrics import REGISTRY

        free_pages = REGISTRY.gauge("tftpu_decode_free_pages")
        next_edge = next_gauge = 0.0
        while True:
            now = time.perf_counter()
            if opened and not closed and now - self.t_open >= self.seconds \
                    and (cap is None or cap.state in ("idle", "closed")):
                closed = True
                self.t_close = now
                self.host.sample(now)
                self.host.stop()
                self.after = harness.registry_snapshot()
                if self.trace:
                    self.spans.stop()
                owed = [r for r in flying if r is not None]
            if opened and not closed:
                if now >= next_gauge:
                    self.pool_free.append(free_pages.value)
                    next_gauge += 0.1
                if now >= next_edge and len(self.host.rows) < 10:
                    self.host.sample(now)
                    next_edge += self.seconds / 10
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
                    self.t_open = next_edge = next_gauge = req.t_done
                    self.host.start()
                    self.before = harness.registry_snapshot()
                    if self.trace:
                        self.spans.start()
                # the loop stays closed past the window's end, so that
                # the requests it owes finish under the same load
                flying[i] = self._send(i, req)
            if closed and all(r.t_done is not None for r in owed):
                break
            if cap is not None and opened and not closed:
                # the traced stretch ends a second before the window
                # does; the profiler is stopped when the load has ended
                # (measure): beside this loop stopping takes a minute
                if cap.state == "idle" and now - self.t_open >= \
                        self.seconds - settle - traced - 1.0:
                    cap.start_profiler()
                elif cap.state == "profiling" and now - cap.t_ready >= settle:
                    cap.open_window()
                elif cap.state == "window" and now - cap.t0 >= traced:
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
        inside. A request wholly inside gives all of them, one that
        straddles an edge its share, so the count does not jump with
        which request happened to end beside an edge."""
        total = 0.0
        for r in self.done:
            if r.error is not None:
                continue
            inside = min(r.t_done, self.t_close) - max(r.t_submit, self.t_open)
            if inside > 0:
                total += r.new * inside / (r.t_done - r.t_submit)
        return total

    def tenths_lines(self) -> List[str]:
        """The window tenth by tenth, for standard error: the engine's
        rate, what the host did beside the loop, and in a traced run the
        loop's own spans, so that a slow tenth shows what grew in it."""
        rows = {"engine tokens/s": [f"{x:.0f}"
                                    for x in self.host.engine_rates()]}
        for key, name, scale, digits in (
                ("gc_s", "collector ms", 1e3, 1),
                ("gc_full_n", "full collections", 1, 0),
                ("cpu_s", "process cpu s", 1, 2),
                ("loop_cpu_s", "engine loop cpu s", 1, 2)):
            got = self.host.deltas(key)
            if got:
                rows[name] = [f"{scale * x:.{digits}f}" for x in got]
        edges = [r["t"] for r in self.host.rows]
        for span in ("decode.step", "decode.step.enqueue", "decode.join",
                     "decode.prepare", "decode.commit"):
            per = [self.spans.named(span, edge)
                   for edge in zip(edges[:-1], edges[1:])]
            if any(per):
                rows[f"{span} ms p50 (spans)"] = [
                    f"{1e3 * float(np.median([x['dur'] for x in got])):.2f}"
                    f" ({len(got)})" if got else "-" for got in per]
        return [f"window tenths, {name}: " + " ".join(cells)
                for name, cells in rows.items()]


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
    if clock:
        clock.mark("server_stop")
    if window.capture is not None and window.capture.state == "closed":
        window.capture.stop_profiler()
        if clock:
            clock.mark("profiler_stop")
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
    pages = pool_pages(cell.traffic)
    pool_used = [1.0 - free / pages for free in window.pool_free]
    if args.trace:
        # the tenths before the profiler was started: starting it can
        # hold the whole process for a second or two
        tenths = window.host.engine_rates(until=cap.t_start or None)
        client = {
            "latency_s": [r.t_done - r.t_submit for r in good],
            "resubmit_lag_s": [r.lag for r in good if r.lag is not None],
            "traced_step_contexts": step_contexts(window, cap.t0, cap.t1),
            "tenth_rate_over_median": [x / float(np.median(tenths))
                                       for x in tenths],
            "pool_used_share": pool_used,
            "collector_share": [sum(window.host.deltas("gc_s")) / elapsed],
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
    tag = "REHEARSAL " if cell.rehearsal else ""
    print(tag + f"pool in use: median {100 * float(np.median(pool_used)):.1f}"
          f" %, least {100 * min(pool_used):.1f} %, most "
          f"{100 * max(pool_used):.1f} % of {pages} pages "
          f"({len(pool_used)} readings)", file=sys.stderr)
    for line in window.tenths_lines():
        print(tag + line, file=sys.stderr)
    harness.emit(cell, devices, correct=correct, attempted=len(finished),
                 failed=failed, metrics=metrics, memory_peak=memory_peak,
                 checks=checks, decisions=harness.plan_decisions(window.after),
                 trace=trace, clock=clock)

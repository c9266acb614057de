"""Iterative decode engine: token-level continuous batching (ISSUE 11).

The flush batcher (batcher.py) coalesces ROW-independent requests into
one dispatch each — right for stateless scoring, wrong for
autoregressive decode, where a request is a *sequence* of dependent
steps against growing KV state. This module is the vLLM-style engine
the ROADMAP's "heavy traffic" target needs: a persistent decode loop
where per-request sequence slots **join and leave the running batch
every step**, over a block-paged KV pool
(:class:`~tensorframes_tpu.serving.kvpool.PagedKVPool`) shared by all
sequences.

Scheduling shape, per loop iteration:

1. **join** — poll the admission queue (a pull-mode
   :class:`~tensorframes_tpu.serving.batcher.ContinuousBatcher`: its
   expirer thread covers requests waiting for a free slot, so a full
   pool can never hold one past its deadline) while slots and prompt
   pages are free; each join runs one **prefill** step (the prompt
   chunk, padded to a ladder bucket) producing the first token. Where
   the model offers a packed prefill (``models/served.py``) and no
   prefix cache is armed, the cold joins of one poll share one call:
   their prompts packed on block edges, up to ``PACK_SEGMENTS`` a call
   and the packed ladder's top bucket, one dispatch and one fetch of
   their first tokens.
2. **decode** — one batched single-token step over every running slot,
   padded to the slot-count bucket ladder. A slot that needs a new KV
   page and finds the pool empty triggers **preemption**: the
   youngest running sequence is evicted (pages freed, counted) and
   requeued at the queue head with its generated tokens intact; on
   rejoin it replays prefill + teacher-forced decode through the SAME
   executables, so its continuation is bit-identical to never having
   been preempted (asserted, not assumed). The oldest sequence is
   never preempted and the pool floor guarantees its horizon fits —
   forward progress is structural, not probabilistic.
3. **leave** — finished sequences resolve their futures, free their
   pages, and their slots are immediately joinable.

Zero-steady-state-compile contract: both phases dispatch through
``aot_jit`` at shapes drawn from ONE ladder —
``compilecache.decode_warmup_grid`` (slot-count buckets for decode,
prompt-length buckets for prefill, both delegating to
``serving_row_buckets``, or the packed call's total-token ladder where
the engine packs) — and ``start()`` warms every point of that
grid, so a warmed engine sustains any join/leave mix without touching
XLA. Decode is greedy (argmax inside the step executable): determinism
is what makes preemption-replay and the batched-vs-solo bit-identity
gate (bench.py hard-gates it) meaningful.

KV memory hierarchy (ISSUE 19), both tiers off by default:

* ``prefix_cache=True`` arms the content-addressed prefix cache: a
  fresh prompt's page-granular prefix is hash-matched against pages
  other sequences already prefilled and published read-only
  (refcounted; ``PagedKVPool.check()``'s partition extends to them); a
  hit skips those prefill chunks — the suffix runs through a dedicated
  gather-attending prefill executable, or copy-on-extend duplicates a
  shared ragged-tail page and teacher-forces only the final prompt
  token — so TTFT drops to the unshared remainder while outputs stay
  bit-identical to the cold path (same quantized-KV math end to end).
* ``kv_swap=True`` arms per-sequence host-swap: a preempted sequence's
  own pages (slot scales and generated prefix included) travel to a
  CRC-stamped BlockStore segment, and rejoin RESTORES them instead of
  recompute-replaying. Replay data is kept alongside every swap
  snapshot: segment corruption falls back to the replay path, counted
  (``tftpu_kvswap_fallback_total``), so no store problem can lose a
  request.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
import time
from typing import Deque, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import kernels as _kernels
from ..observability import events as _events
from ..observability import flight as _flight
from ..utils import get_logger
from ..validation import ValidationError
from . import metrics as m
from .batcher import (
    ContinuousBatcher,
    DeadlineExceededError,
    RejectedError,
    ResultFuture,
    ServingError,
    _Request,
)
from .kvpool import PagedKVPool, PoolExhaustedError

logger = get_logger(__name__)

__all__ = ["DecodeConfig", "DecodeEngine", "prefix_cache_events"]

#: prompts one packed prefill call holds at most (a poll of the cell's
#: traffic joins about seven)
PACK_SEGMENTS = 16

# Prefix-cache ineligibility evidence for lint_plan's TFG113 rule: one
# entry per (endpoint, reason) the first time it arises, bounded. The
# analyzer reads this through prefix_cache_events() — serving state
# never imports analysis, only the other way around.
_PREFIX_INELIGIBLE: Deque[Dict[str, object]] = collections.deque(
    maxlen=128
)
_PREFIX_INELIGIBLE_SEEN: set = set()


def prefix_cache_events() -> List[Dict[str, object]]:
    """Recent prefix-cache ineligibility evidence (deduplicated per
    endpoint and reason) — the TFG113 rule's input."""
    return list(_PREFIX_INELIGIBLE)


@dataclasses.dataclass
class DecodeConfig:
    """Sizing knobs for one decode endpoint.

    ``max_slots`` — running-batch width (slot counts pad through the
    bucket ladder, so the top bucket is what compiles).
    ``page_size`` — KV positions per pool page.
    ``num_pages`` — total pool pages incl. the reserved null page;
    ``None`` auto-sizes to hold every slot's full horizon (no
    preemption under any admissible load). Size it smaller to trade
    preemptions for HBM.
    ``max_prompt_len`` / ``max_new_tokens`` — per-request bounds; their
    sum is the decode horizon (must fit the model's ``max_seq_len``).
    ``max_queue_requests`` — admission bound; past it submits shed with
    ``RejectedError(reason="queue_full")``.
    ``default_deadline_s`` — total-elapsed deadline applied when a
    request carries none (``RetryPolicy.deadline_s`` semantics; expiry
    covers queue AND slot wait — once running, a sequence completes).
    ``warmup`` — precompile the slot × phase bucket grid at start.
    ``kv_swap`` — host-swap a preempted sequence's pages to a
    BlockStore segment and restore them on rejoin (counted fallback to
    recompute-replay on corruption). ``swap_dir`` roots the swap store
    (default: a private temp dir, deleted at stop).
    ``prefix_cache`` — share read-only prompt-prefix pages across
    requests by content hash (refcounted, copy-on-extend at the ragged
    tail, evicted only at refcount 0).
    """

    max_slots: int = 8
    page_size: int = 16
    num_pages: Optional[int] = None
    max_prompt_len: int = 32
    max_new_tokens: int = 16
    max_queue_requests: int = 1024
    default_deadline_s: Optional[float] = None
    warmup: bool = True
    kv_swap: bool = False
    prefix_cache: bool = False
    swap_dir: Optional[str] = None


class _Seq:
    """One running sequence slot (engine-thread private)."""

    __slots__ = ("req", "seq", "prompt", "want", "pos", "joined",
                 "waited", "generated", "replay")

    def __init__(self, req: _Request, seq: int, prompt: np.ndarray,
                 want: int, joined: int, waited: float):
        self.req = req
        self.seq = seq
        self.prompt = prompt
        self.want = want
        self.pos = int(prompt.shape[0])  # next KV position to write
        self.joined = joined             # monotonic join counter
        self.waited = waited             # submit → this join, seconds
        self.generated: List[int] = []
        self.replay: Optional[Deque[int]] = None


class DecodeEngine:
    """The persistent decode loop over one model + one paged KV pool.

    Usually constructed through
    :meth:`~tensorframes_tpu.serving.Server.register_decode`, which
    routes ``Server.submit(name, {"prompt": ...})`` here and exposes it
    over the HTTP sidecar. Standalone use::

        eng = DecodeEngine("gen", gpt_tiny_cfg, params, DecodeConfig())
        eng.start()
        fut = eng.submit({"prompt": np.arange(7, dtype=np.int32)})
        fut.result(60.0)["tokens"]     # [1, max_new_tokens] int32
        eng.stop(drain=True)
    """

    def __init__(self, name: str, model_cfg, params,
                 config: Optional[DecodeConfig] = None):
        from ..compilecache import decode_warmup_grid
        from ..kernels.decode_attention import pages_walked
        from ..models.served import served_model_of
        from ..ops.executor import aot_jit

        self._pages_walked = pages_walked
        self.name = name
        self.cfg = model_cfg
        self.params = params
        self.config = cfg = config or DecodeConfig()
        if cfg.max_slots < 1:
            raise ValueError("max_slots must be >= 1")
        if cfg.max_prompt_len < 1 or cfg.max_new_tokens < 1:
            raise ValueError(
                "max_prompt_len and max_new_tokens must be >= 1"
            )
        horizon = cfg.max_prompt_len + cfg.max_new_tokens
        # the seam (models/served.py): the model's page kinds and its
        # programs; the engine asks nothing else of a model
        self.model = model = served_model_of(
            model_cfg, cfg.page_size, horizon
        )
        if horizon > model.max_seq_len:
            raise ValueError(
                f"decode horizon {horizon} (max_prompt_len + "
                f"max_new_tokens) exceeds the model's max_seq_len="
                f"{model.max_seq_len}"
            )
        for flag, have, what in (
                (cfg.prefix_cache, model.suffix_prefill and model.page_ops,
                 "prefix_cache=True needs the model's suffix-prefill and "
                 "page-operation programs"),
                (cfg.kv_swap, model.page_ops,
                 "kv_swap=True needs the model's page-operation programs")):
            if flag and not have:
                raise ValueError(
                    f"decode endpoint {name!r}: {what}, and "
                    f"{type(model_cfg).__name__} gives none (its page "
                    f"kinds: {[k.name for k in model.kinds]}); serve it "
                    "without that tier"
                )
        self._kinds = model.kinds
        max_pages = self._kinds[0].entries
        num_pages = cfg.num_pages
        if num_pages is None:
            # auto-size: every slot can hold a full horizon — the
            # no-preemption configuration
            num_pages = 1 + cfg.max_slots * max_pages
        # a kind past the first (a ring) never fills: every slot's whole
        # ring fits
        self._pool = PagedKVPool(
            model, num_pages, cfg.page_size,
            extra_pages={k.name: 1 + cfg.max_slots * k.entries
                         for k in self._kinds[1:]},
        )
        # a poll's cold joins share one packed prefill where the model
        # offers the program and no prefix cache routes joins one by one
        self._pack = model.packed_prefill is not None \
            and not cfg.prefix_cache
        grid = decode_warmup_grid(
            cfg.max_slots, cfg.max_prompt_len,
            pack_block=model.pack_block if self._pack else None,
        )
        self._slot_buckets = grid["decode"]
        # the packed call's total-token ladder where the engine packs
        self._prefill_buckets = grid["prefill"]
        self._pack_segments = min(PACK_SEGMENTS, cfg.max_slots)
        # Every program that returns the pool takes it DONATED
        # (argument 1 of the model steps, 0 of the page ops): the KV
        # write happens in the resident buffers, and the columns handed
        # in are deleted by the call. Each call site rebinds
        # ``self._pool.columns`` to what came back before anything else
        # can read them; only the engine thread (or start()'s warm-up,
        # before that thread exists) ever holds the columns.
        self._prefill = aot_jit(
            model.prefill,
            label=f"decode.prefill[{name}]", donate_argnums=(1,),
        )
        self._packed_prefill = aot_jit(
            model.packed_prefill,
            label=f"decode.packed_prefill[{name}]", donate_argnums=(1,),
        ) if self._pack else None
        self._step = aot_jit(
            model.step,
            label=f"decode.step[{name}]", donate_argnums=(1,),
        )
        # which attention the step traces is the backend's fact
        # (ops.attention asks the same table at trace time); kept for
        # the dispatch counter and the pages-walked accounting
        self._attn_is_kernel = _kernels.selectable("decode_attn")
        self._step_kernels = tuple(
            k for k in model.kernels if _kernels.selectable(k)
        )
        self._kernels_interpreted = (
            bool(self._step_kernels) and _kernels.interpret_mode()
        )
        # widest slot bucket whose step executable's memory plan the
        # tftpu_decode_step_*_bytes gauges show (0: none yet)
        self._step_memory_bucket = 0
        # KV memory hierarchy executables (ISSUE 19) — all fixed-shape,
        # warmed alongside the grid, so neither tier costs a
        # steady-state compile
        self._prefix_cache = bool(cfg.prefix_cache)
        self._kv_swap = bool(cfg.kv_swap)
        self._suffix_prefill = None
        self._extract = self._restore = self._copy_page = None
        if self._prefix_cache:
            self._suffix_prefill = aot_jit(
                model.suffix_prefill,
                label=f"decode.suffix_prefill[{name}]",
                donate_argnums=(1,),
            )
        if self._prefix_cache or self._kv_swap:
            ex_fn, rs_fn, cp_fn = model.page_ops
            if self._kv_swap:
                self._extract = aot_jit(
                    ex_fn, label=f"decode.kvswap.extract[{name}]"
                )
                self._restore = aot_jit(
                    rs_fn, label=f"decode.kvswap.restore[{name}]",
                    donate_argnums=(0,),
                )
            if self._prefix_cache:
                self._copy_page = aot_jit(
                    cp_fn, label=f"decode.prefix.copy[{name}]",
                    donate_argnums=(0,),
                )
        self._swap_store = None
        self._swap: Dict[_Request, Dict[str, object]] = {}
        # PR 18 follow-up: swap segments survive the engine. stop()
        # PARKS pending keyed sequences' segments (trace_id → swap
        # snapshot) instead of dropping them; spill() folds them into
        # the whole-pool snapshot; restore() on a fresh engine re-homes
        # them here, and a redriven request with the same trace id
        # resumes from its pages (no prefill recompute).
        self._swap_parked: Dict[str, Dict[str, object]] = {}
        self._swap_restored: Dict[str, Dict[str, object]] = {}
        # first-page fingerprints of fresh prompts on an UNARMED
        # engine: a repeat is hard evidence prefill work was shareable
        # (the TFG113 store_unarmed signal) — bounded, never grows past
        # the cap
        self._seen_first_pages: set = set()
        self._swap_outs = 0
        self._swap_resumes = 0
        self._swap_fallbacks = 0
        self._prefix_hits = 0
        self._prefix_misses = 0
        # admission: pull mode — no worker thread; the engine loop
        # drains it, its expirer covers the slot-wait queue
        self._admission = ContinuousBatcher(
            name, None,
            max_batch_rows=1,
            max_latency_s=0.0,
            max_queue_rows=cfg.max_queue_requests,
        )
        self._slots: List[Optional[_Seq]] = [None] * cfg.max_slots
        # polled out of the queue, not yet in a slot: a join that
        # raises must still answer them (_fail_all reads this)
        self._in_hand: Sequence[_Request] = ()
        self._resume: Dict[_Request, List[int]] = {}
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        self._running = False
        self._starting = False
        self._stopping = False
        self._drain = True
        self._next_seq = 0
        self._join_counter = 0
        # engine-thread trace state: the last phase boundary
        # (perf_counter seconds; None while not tracing or idle), the
        # thread's CPU time there, and the profiler TraceMe of the phase
        # that runs from it (None unless a jax.profiler capture runs)
        self._t_mark: Optional[float] = None
        self._cpu_mark = 0.0
        self._native = None
        # the thread's CPU time at the top of the last loop iteration
        # (tftpu_decode_loop_cpu_seconds_total counts its growth)
        self._cpu_loop = 0.0

    def _phase(self, name: str, then: Optional[str] = None,
               **args) -> None:
        """Close the engine thread's current phase as the span ``name``:
        it runs from the last boundary to now, and now is the next
        phase's start, so the loop's phases are contiguous by
        construction (one clock read per boundary, and one of the
        thread's CPU time, for ``cpu_ms``). The phase's profiler
        ``TraceMe`` closes here too, and ``then``'s opens where the next
        phase is known at the boundary; a phase whose name is known
        only later opens its own (:meth:`_open_phase`). The first
        boundary after tracing came on only sets the mark. Call sites
        sit behind ``if _events.TRACER.enabled``."""
        now = time.perf_counter()
        cpu = time.thread_time()
        _events.close_native(self._native)
        # the next phase's TraceMe opens before this span is written, so
        # that it starts as near the boundary as the clock read
        self._native = _events.native(then) if then is not None else None
        if self._t_mark is not None:
            args["endpoint"] = self.name
            args["cpu_ms"] = round((cpu - self._cpu_mark) * 1e3, 4)
            _events.TRACER.emit_complete(
                name, self._t_mark, now - self._t_mark, args=args,
                cat="serving",
            )
        self._t_mark, self._cpu_mark = now, cpu

    def _open_phase(self, name: str) -> None:
        """Open the profiler ``TraceMe`` of the phase that began at the
        last boundary, where the loop learns which phase it is only
        after that boundary (a join, or the prepare after the joins)."""
        if self._native is None and self._t_mark is not None \
                and _events.TRACER.enabled:
            self._native = _events.native(name)

    def _run_step(self, *args):
        """Dispatch one batched decode step and count its kernel
        dispatch. Returns what the model's step returns, ``(pool,
        next_tokens)`` or ``(pool, next_tokens, stats)``
        (``models/served.py``); the pool columns
        passed in (``args[1]``) are donated — deleted by the call — so
        the caller rebinds ``self._pool.columns`` to the returned ones.
        A failure raises — the step is never rebuilt on another
        lowering."""
        # the host's enqueue of the step program (key walk, argument
        # transfer, launch): a leaf inside decode.step, whose rest is
        # the fetch of its tokens and their counting
        with _events.TRACER.mirrored("decode.step.enqueue", cat="serving",
                                     endpoint=self.name):
            out = self._step(*args)
        if args[2].shape[0] > self._step_memory_bucket:
            self._note_step_memory(args)
        for kernel in self._step_kernels:
            _kernels.note_dispatch(kernel, self._kernels_interpreted)
        return out

    def _note_step_memory(self, args) -> None:
        """Publish the memory plan of the step executable that served
        ``args`` (the widest slot bucket so far) as the
        ``tftpu_decode_step_alias_bytes`` / ``_temp_bytes`` gauges: the
        evidence that the pool is written in place (alias = the pool's
        bytes, temp well under one pool). Shapes only — the donated
        columns in ``args`` are never touched. Left unset where the
        executable offers no analysis."""
        self._step_memory_bucket = int(args[2].shape[0])
        try:
            stats = self._step.executable(*args).memory_analysis()
            alias = int(stats.alias_size_in_bytes)
            temp = int(stats.temp_size_in_bytes)
        except Exception:  # no executable or no analysis: leave unset
            return
        m.DECODE_STEP_ALIAS_BYTES.set(alias)
        m.DECODE_STEP_TEMP_BYTES.set(temp)

    # -- introspection ------------------------------------------------------

    @property
    def pool(self) -> PagedKVPool:
        return self._pool

    @property
    def running(self) -> bool:
        return self._running

    def counters(self) -> Dict[str, object]:
        """Admission counters (shared batcher snapshot) + engine state."""
        snap = self._admission.counters()
        with self._lock:
            snap["running_slots"] = sum(
                1 for s in self._slots if s is not None
            )
        snap["free_pages"] = self._pool.num_free
        snap["allocatable_pages"] = self._pool.num_allocatable
        snap["shared_pages"] = self._pool.num_shared
        snap["swap_outs"] = self._swap_outs
        snap["swap_resumes"] = self._swap_resumes
        snap["swap_fallbacks"] = self._swap_fallbacks
        snap["prefix_hits"] = self._prefix_hits
        snap["prefix_misses"] = self._prefix_misses
        return snap

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> "DecodeEngine":
        with self._lock:
            if self._running or self._starting:
                return self
            if self._thread is not None and self._thread.is_alive():
                # a previous stop(timeout=...) expired with the loop
                # still draining: starting a SECOND loop over the same
                # slots/pool would corrupt both — refuse until it exits
                raise ServingError(
                    f"decode engine {self.name!r} is still draining "
                    "from a timed-out stop(); retry once it finishes"
                )
            self._starting = True
        t0 = time.perf_counter()
        try:
            # _running commits only AFTER warmup + admission + the loop
            # thread all succeed: a failed warm must leave the engine
            # cleanly restartable, not a zombie that reports running
            # while every submit sheds as 'closed'
            self._pool.reopen()  # no-op unless restarting after stop()
            if self._kv_swap and self._swap_store is None:
                from ..blockstore import BlockStore

                # budget 0: swap segments go straight to disk anyway
                # (put_spilled), and the swap store must never hold
                # pages resident on behalf of the pool it is relieving
                self._swap_store = BlockStore(
                    root=self.config.swap_dir, budget_bytes=0,
                )
            if self.config.warmup:
                self._warm()
            self._admission.start()
            thread = threading.Thread(
                target=self._loop, daemon=True,
                name=f"tfs-decode-{self.name}",
            )
            with self._lock:
                self._thread = thread
                self._stopping = False
                self._running = True
            thread.start()
        finally:
            with self._lock:
                self._starting = False
        _flight.record(
            "serving.decode.start", endpoint=self.name,
            slots=self.config.max_slots,
            pages=self._pool.num_pages,
            page_size=self.config.page_size,
            warmup_s=round(time.perf_counter() - t0, 6),
        )
        return self

    def _warm(self) -> None:
        """Execute every point of the slot × phase bucket grid once
        against null tables (writes land in the null page, whose
        contents are garbage by contract). Every program that returns
        the pool takes it donated, so each call's result is threaded
        into the next and ``self._pool.columns`` is rebound after every
        call — a compile that fails mid-ladder leaves the pool holding
        live arrays. Unlike ``warm_program`` this executes, not just
        compiles: the grid is tiny, and the run also faults in the
        gather/scatter kernels."""
        t0 = time.perf_counter()
        pool = self._pool
        null = pool.null_table()
        maxp = pool.max_pages_per_seq
        segs = self._pack_segments
        for tb in self._prefill_buckets:
            if self._pack:
                # every segment empty: all rows write the null page
                pool.columns, _ = self._packed_prefill(
                    self.params, pool.columns, np.zeros(tb, np.int32),
                    np.zeros(segs, np.int32), np.zeros(segs, np.int32),
                    np.zeros((segs, maxp), np.int32),
                )
                continue
            pool.columns, _ = self._prefill(
                self.params, pool.columns, np.zeros(tb, np.int32),
                np.int32(1), *pool.null_tables(),
            )
        for sb in self._slot_buckets:
            pool.columns = self._run_step(
                self.params, pool.columns, np.zeros(sb, np.int32),
                np.zeros(sb, np.int32),
                *(np.zeros((sb, k.entries), np.int32) for k in self._kinds),
            )[0]
        if self._suffix_prefill is not None:
            for tb in self._prefill_buckets:
                pool.columns, _ = self._suffix_prefill(
                    self.params, pool.columns, np.zeros(tb, np.int32),
                    np.int32(0), np.int32(1), null,
                )
        if self._copy_page is not None:
            # null page onto itself — garbage by contract either way
            pool.columns = self._copy_page(
                pool.columns, np.int32(0), np.int32(0)
            )
        if self._extract is not None:
            idx = np.zeros(maxp, np.int32)
            ex = self._extract(pool.columns, idx)
            pool.columns = self._restore(
                pool.columns, idx,
                np.asarray(ex["k"]), np.asarray(ex["v"]),
                np.asarray(ex["k_scale"]), np.asarray(ex["v_scale"]),
            )
        logger.info(
            "decode warmup[%s]: prefill buckets %s + decode buckets %s "
            "in %.2fs", self.name, self._prefill_buckets,
            self._slot_buckets, time.perf_counter() - t0,
        )

    def stop(self, drain: bool = True,
             timeout: Optional[float] = None) -> None:
        """Close admission; ``drain=True`` completes every admitted AND
        queued sequence first, ``drain=False`` fails queued and running
        requests with :class:`ServingError`. Bounded by ``timeout``."""
        with self._lock:
            if not self._running and self._thread is None:
                # never started (or already stopped): still withdraw
                # the pool from the process-wide free-pages gauge — a
                # registered-but-never-started engine's pages must not
                # inflate other engines' headroom signal forever
                self._pool.close()
                return
            self._stopping = True
            self._drain = drain
            thread = self._thread
        self._admission.close(drain=drain)
        if thread is not None:
            thread.join(timeout)
            if thread.is_alive():
                logger.warning(
                    "decode engine %r still draining after stop "
                    "timeout", self.name,
                )
        self._admission.stop(drain=drain, timeout=timeout)
        with self._lock:
            self._running = False
            # keep the ref while the loop is still draining past the
            # timeout — start() checks it to refuse a second loop
            if self._thread is thread and not (
                thread is not None and thread.is_alive()
            ):
                self._thread = None
        self._pool.close()  # withdraw from the free-pages gauge
        store = self._swap_store
        if store is not None:
            # segments of still-unanswered requests WITH a cross-restart
            # identity are parked for spill(), not dropped: the fleet
            # redrives such a request (same trace id) into the restarted
            # engine, and a parked segment turns that redrive into a
            # swap-in resume instead of a full prefill recompute.
            # Unkeyed or answered sequences drop as before.
            for r in list(self._swap):
                if r.trace_id:
                    self._swap_parked[str(r.trace_id)] = self._swap.pop(r)
                else:
                    self._drop_swap(r)
            # restored-but-never-redriven segments already live in this
            # store: park them too, so chained restarts keep them
            self._swap_parked.update(self._swap_restored)
            self._swap_restored.clear()
            if not self._swap_parked:
                self._swap_store = None
                store.close()  # deletes the root if the engine made it
            # else: the store stays open — spill() reads the parked
            # segments out of it (and then closes it), or a subsequent
            # start() reuses it
        # TFG113 evidence is scoped to RUNNING endpoints: a stopped
        # engine's config can no longer be fixed, so its findings are
        # withdrawn (lint_plan reads the live evidence each call)
        kept = [e for e in _PREFIX_INELIGIBLE
                if e.get("endpoint") != self.name]
        _PREFIX_INELIGIBLE.clear()
        _PREFIX_INELIGIBLE.extend(kept)
        for key in [k for k in _PREFIX_INELIGIBLE_SEEN
                    if k[0] == self.name]:
            _PREFIX_INELIGIBLE_SEEN.discard(key)
        _flight.record(
            "serving.decode.stop", endpoint=self.name, drain=drain,
        )

    def spill(self, store) -> Dict[str, object]:
        """Whole-engine KV snapshot (call after ``stop()``): the pool's
        whole-pool spill PLUS every parked per-sequence swap segment,
        folded into one snapshot dict — the PR 18 follow-up that stops
        swap segments dying with the engine. Hand the snapshot to a
        fresh engine's :meth:`restore` and redrive the pending requests
        (same trace ids): each resumes from its swapped pages through
        the normal swap-in path, bit-identically, with no prefill
        recompute. The engine's own swap store is emptied and closed
        (the segments now live in ``store``)."""
        with self._lock:
            if self._running or self._starting:
                raise ServingError(
                    f"decode engine {self.name!r}: spill() requires a "
                    "stopped engine (stop() first — a live loop would "
                    "race the snapshot)"
                )
            parked = dict(self._swap_parked)
        snap = self._pool.spill(
            store, swaps=parked, swap_store=self._swap_store,
        )
        swap_store = self._swap_store
        if swap_store is not None:
            for entry in parked.values():
                try:
                    swap_store.drop(entry["ref"])
                except Exception:  # pragma: no cover - already dropped
                    pass
            self._swap_parked.clear()
            self._swap_store = None
            swap_store.close()
        _flight.record(
            "serving.decode.spill", endpoint=self.name,
            swapped=len(snap.get("swapped", {})),
        )
        return snap

    def restore(self, store, snapshot: Dict[str, object]) -> int:
        """Adopt a :meth:`spill` snapshot's host-swapped sequences into
        this engine: segments are re-homed into the engine's swap store
        and parked by trace id; when the fleet redrives a pending
        request (same trace id), it resumes from its pages through the
        warmed swap-in executables instead of recomputing its prefill.
        Pool page state is NOT restored — a fresh engine owns a fresh
        pool, and swapped sequences hold no pages by construction.
        Returns the number of sequences adopted (corrupt segments are
        skipped with the store's counted quarantine; those requests
        degrade to a plain fresh decode on redrive)."""
        if not self._kv_swap:
            return 0
        if self._swap_store is None:
            from ..blockstore import BlockStore

            self._swap_store = BlockStore(
                root=self.config.swap_dir, budget_bytes=0,
            )
        manifest = self._pool.adopt_swapped(
            store, snapshot, self._swap_store
        )
        with self._lock:
            self._swap_restored.update(manifest)
        _flight.record(
            "serving.decode.restore", endpoint=self.name,
            adopted=len(manifest),
            offered=len(snapshot.get("swapped", {})),
        )
        return len(manifest)

    def _adopt_restored(self, req: "_Request") -> Optional[Dict]:
        """Move a restored swap snapshot onto a redriven request (same
        trace id), keeping the recompute-replay data beside it — the
        counted fallback if the segment comes back corrupt, exactly as
        :meth:`_preempt` does for a live preemption."""
        if not self._swap_restored or not req.trace_id:
            return None
        snap = self._swap_restored.pop(str(req.trace_id), None)
        if snap is None:
            return None
        self._swap[req] = snap
        self._resume[req] = (
            list(snap["generated"]) + list(snap["replay"] or ())
        )
        return snap

    # -- request path -------------------------------------------------------

    def validate_feeds(self, feeds) -> Dict[str, object]:
        """Normalize one decode request: ``{"prompt": 1-D int tokens
        (or [1, plen]), "max_new_tokens": optional int}``. Length bounds
        reject as ``too_large`` (the pool could never hold the
        horizon), malformed feeds as :class:`ValidationError`."""
        if not isinstance(feeds, dict) or "prompt" not in feeds:
            raise ValidationError(
                f"decode endpoint {self.name!r}: feeds must be a dict "
                "with a 'prompt' key (int token ids)"
            )
        extra = set(feeds) - {"prompt", "max_new_tokens"}
        if extra:
            raise ValidationError(
                f"decode endpoint {self.name!r}: unexpected feed(s) "
                f"{sorted(extra)}; accepted: prompt, max_new_tokens"
            )
        try:
            prompt = np.asarray(feeds["prompt"], dtype=np.int32)
        except (TypeError, ValueError) as e:
            raise ValidationError(
                f"decode endpoint {self.name!r}: prompt does not "
                f"convert to int32 tokens: {e}"
            ) from None
        if prompt.ndim == 2 and prompt.shape[0] == 1:
            prompt = prompt[0]
        if prompt.ndim != 1 or prompt.shape[0] < 1:
            raise ValidationError(
                f"decode endpoint {self.name!r}: prompt must be a "
                f"non-empty 1-D token vector (or [1, plen]), got shape "
                f"{prompt.shape}"
            )
        vocab = int(self.model.vocab_size)
        if prompt.min() < 0 or prompt.max() >= vocab:
            raise ValidationError(
                f"decode endpoint {self.name!r}: prompt tokens must be "
                f"in [0, {vocab})"
            )
        new = feeds.get("max_new_tokens", self.config.max_new_tokens)
        try:
            new = int(new)
        except (TypeError, ValueError):
            raise ValidationError(
                f"decode endpoint {self.name!r}: max_new_tokens must "
                f"be an int, got {feeds['max_new_tokens']!r}"
            ) from None
        if new < 1 or new > self.config.max_new_tokens:
            raise ValidationError(
                f"decode endpoint {self.name!r}: max_new_tokens={new} "
                f"outside [1, {self.config.max_new_tokens}]"
            )
        plen = int(prompt.shape[0])
        if plen > self.config.max_prompt_len:
            m.rejected("too_large").inc()
            raise RejectedError(
                f"decode endpoint {self.name!r}: prompt of {plen} "
                f"tokens exceeds max_prompt_len="
                f"{self.config.max_prompt_len} — split or raise the "
                "engine's DecodeConfig",
                reason="too_large",
            )
        return {"prompt": prompt, "new": new}

    def submit(self, feeds,
               deadline_s: Optional[float] = None) -> ResultFuture:
        """Admit one decode request; the future resolves to
        ``{"tokens": int32 [1, max_new_tokens]}`` when its LAST token is
        generated (streaming-final semantics). Raises
        :class:`RejectedError` on shed/closed/oversize, the deadline
        covers queue + slot wait."""
        norm = self.validate_feeds(feeds)
        if deadline_s is None:
            deadline_s = self.config.default_deadline_s
        if deadline_s is not None and deadline_s <= 0:
            raise ValueError(
                f"deadline_s must be > 0 (got {deadline_s}) — the same "
                "contract as RetryPolicy.deadline_s"
            )
        return self._admission.offer(norm, 1, deadline_s)

    def call(self, feeds, deadline_s: Optional[float] = None,
             timeout: Optional[float] = None) -> Dict[str, np.ndarray]:
        return self.submit(feeds, deadline_s).result(timeout)

    # -- the engine loop ----------------------------------------------------

    def _loop(self) -> None:
        try:
            self._loop_body()
        except BaseException as e:  # pragma: no cover - crash guard
            logger.exception("decode engine %r loop died", self.name)
            _flight.record(
                "serving.decode.error", endpoint=self.name,
                error=type(e).__name__, message=str(e),
            )
            self._fail_all(ServingError(
                f"decode engine {self.name!r} failed: "
                f"{type(e).__name__}: {e}"
            ))

    def _loop_body(self) -> None:
        self._cpu_loop = time.thread_time()
        while True:
            with self._lock:
                stopping, drain = self._stopping, self._drain
            if stopping and not drain:
                self._fail_all(ServingError(
                    f"decode engine {self.name!r} stopped without "
                    "drain; running sequences abandoned"
                ))
                return
            # the thread's CPU time: one read a turn, traced or not
            cpu = time.thread_time()
            m.DECODE_LOOP_CPU.inc(cpu - self._cpu_loop)
            self._cpu_loop = cpu
            if not _events.TRACER.enabled:
                self._t_mark = None
                if self._native is not None:  # tracing went off mid-turn
                    _events.close_native(self._native)
                    self._native = None
            elif self._t_mark is None:
                self._t_mark, self._cpu_mark = time.perf_counter(), cpu
                self._native = _events.native("decode.admit")
            self._purge_resume()
            free = [i for i, s in enumerate(self._slots) if s is None]
            polled = self._admission.poll(
                len(free), can_take=self._admit_budget()
            ) if free else ()
            if _events.TRACER.enabled:
                self._phase("decode.admit", polled=len(polled))
            self._in_hand = polled
            if self._pack:
                self._join_packed(polled)
            else:
                for req in polled:
                    self._join(req)
            self._in_hand = ()
            if any(s is not None for s in self._slots):
                self._decode_step()
                continue
            # idle: the nap below belongs to no phase
            self._t_mark = None
            _events.close_native(self._native)
            self._native = None
            if stopping and self._admission.queued_rows == 0:
                return
            if self._admission.queued_rows > 0:
                # queued but unadmittable (pool pages held elsewhere):
                # a bounded nap, not a hot spin — wait_for_work returns
                # immediately on a non-empty queue, and the expirer
                # thread (not this loop) owns deadline expiry
                time.sleep(0.005)
            else:
                self._admission.wait_for_work(0.02)

    def _admit_budget(self):
        """A fresh admission predicate for ONE poll: each accepted
        request claims its prompt pages from the snapshot budget, so a
        multi-request poll can never overcommit the pool (the joins run
        after the poll returns). The budget is ``num_allocatable`` —
        free pages plus reclaimable refcount-0 shared pages — and a
        host-swapped request claims its SNAPSHOT's page count (it may
        hold pages past its prompt), not its prompt estimate. The pool
        is read at the first head request, under the admission lock: a
        request offered after pages left the pool never sees the pool
        as it was before."""
        budget: Dict[str, int] = {}
        first = self._kinds[0].name

        def can_take(req: _Request) -> bool:
            if not budget:
                budget.update({k.name: self._pool.allocatable(k.name)
                               for k in self._kinds})
            snap = self._swap.get(req)
            if snap is None:
                # a redriven request adopting a restored swap segment
                # claims its SNAPSHOT pages too (engine-restart resume)
                snap = self._adopt_restored(req)
            # a sequence's demand is reckoned per page kind
            need = self._pool.demand(int(req.feeds["prompt"].shape[0]))
            if snap is not None:
                need[first] = int(snap["pages"])
            if any(n > budget[kind] for kind, n in need.items()):
                return False
            for kind, n in need.items():
                budget[kind] -= n
            return True

        return can_take

    def _purge_resume(self) -> None:
        # a preempted request can expire (or be abandoned) while
        # requeued — its future resolves in the expirer; drop its
        # replay state (and swap segment) so neither can grow
        # unboundedly
        if self._resume:
            dead = [r for r in self._resume if r.future.done()]
            for r in dead:
                del self._resume[r]
        if self._swap:
            for r in [r for r in self._swap if r.future.done()]:
                self._drop_swap(r)

    def _drop_swap(self, req: _Request) -> None:
        snap = self._swap.pop(req, None)
        if snap is not None and self._swap_store is not None:
            try:
                self._swap_store.drop(snap["ref"])
            except Exception:  # pragma: no cover - already dropped
                pass

    def _prefill_bucket(self, plen: int) -> int:
        for b in self._prefill_buckets:
            if b >= plen:
                return b
        raise AssertionError(  # pragma: no cover - validated at submit
            f"prompt of {plen} tokens above the warmed prefill ladder "
            f"{self._prefill_buckets}"
        )

    def _note_prefix_ineligible(self, reason: str, plen: int) -> None:
        key = (self.name, reason)
        if key in _PREFIX_INELIGIBLE_SEEN:
            return
        _PREFIX_INELIGIBLE_SEEN.add(key)
        _PREFIX_INELIGIBLE.append({
            "endpoint": self.name, "reason": reason,
            "prompt_len": int(plen),
            "page_size": int(self.config.page_size),
        })

    def _note_repeat(self, prompt: np.ndarray, plen: int) -> None:
        # evidence only on an OBSERVED repeat: a fresh prompt whose first
        # page was already prefilled by an earlier fresh request is work
        # the prefix cache would have shared — an engine that never sees
        # overlap has nothing to gain and records nothing
        if plen > self.config.page_size:
            fp = prompt[:self.config.page_size].tobytes()
            if fp in self._seen_first_pages:
                self._note_prefix_ineligible("store_unarmed", plen)
            elif len(self._seen_first_pages) < 512:
                self._seen_first_pages.add(fp)

    def _plan_prefill(self, seq: int, prompt: np.ndarray, plen: int,
                      resumed: bool) -> Dict[str, object]:
        """The host's build of a fresh sequence's prefill through the
        cheapest eligible path: shared-prefix suffix prefill,
        copy-on-extend, or cold full prefill. Allocates the sequence's
        pages and returns what :meth:`_run_prefill` dispatches:
        ``path`` (``cold`` / ``suffix`` / ``cow``), ``bucket``, the
        program's host ``operands``, and the prefix hit (``hit_pages``,
        ``covered``, ``cow``)."""
        hit_pages: List[int] = []
        covered = 0
        cow = None
        if not self._prefix_cache:
            if not resumed:
                self._note_repeat(prompt, plen)
        elif resumed:
            # a replay-resumed join must reproduce its recorded tokens
            # against the page state that existed at first admission;
            # routing it through cache pages published since would
            # change accounting mid-replay — ineligible by design
            self._note_prefix_ineligible("sampling_state_mismatch", plen)
        else:
            hit_pages, covered, cow, _r = self._pool.prefix_match(prompt)
            if not hit_pages and cow is None:
                if plen <= self.config.page_size:
                    # below one full page nothing can ever be published
                    # or matched at page granularity
                    self._note_prefix_ineligible("page_misalignment", plen)
                m.PREFIX_MISSES.inc()
                self._prefix_misses += 1
        if hit_pages:
            self._pool.prefix_acquire(seq, hit_pages)
        if hit_pages or cow is not None:
            m.PREFIX_HITS.inc()
            self._prefix_hits += 1
        plan: Dict[str, object] = {
            "hit_pages": hit_pages, "covered": covered, "cow": cow}
        if cow is not None:
            # the whole remaining tail is resident in a published page:
            # copy it (never write a shared page), then teacher-force
            # only the final prompt token through the solo decode step
            # — it rewrites KV the copy already holds (deterministic,
            # identical) and yields the first-token logits
            dst = self._pool.copy_on_extend(seq, cow)
            sb = self._slot_buckets[0]
            tokens = np.zeros(sb, np.int32)
            pos = np.zeros(sb, np.int32)
            tables = np.zeros((sb, self._pool.max_pages_per_seq), np.int32)
            tokens[0] = int(prompt[plen - 1])
            pos[0] = plen - 1
            tables[0] = self._pool.table(seq)
            plan.update(path="cow", bucket=sb, dst=dst,
                        operands=(tokens, pos, tables))
        elif hit_pages:
            # matched pages cover [0, covered); prefill only the suffix
            # through the gather-attending executable (its rows see the
            # shared pages through the sequence's table)
            self._pool.alloc(
                seq, self._pool.pages_needed(plen) - len(hit_pages)
            )
            tlen = plen - covered
            tb = self._prefill_bucket(tlen)
            padded = np.zeros(tb, np.int32)
            padded[:tlen] = prompt[covered:]
            plan.update(path="suffix", bucket=tb, operands=(
                padded, np.int32(covered), np.int32(tlen),
                self._pool.table(seq)))
        else:
            for kind, n in self._pool.demand(plen).items():
                self._pool.alloc(seq, n, kind)
            tb = self._prefill_bucket(plen)
            padded = np.zeros(tb, np.int32)
            padded[:plen] = prompt
            plan.update(path="cold", bucket=tb, operands=(
                padded, np.int32(plen), *self._pool.tables(seq)))
        return plan

    def _run_prefill(self, seq: int, plan: Dict[str, object]) -> int:
        """Dispatch the planned prefill and fetch its first token."""
        path, operands = plan["path"], plan["operands"]
        tracer = _events.TRACER
        # the program's dispatch through the first token's arrival on
        # the host: the device's share of decode.join
        with tracer.mirrored("decode.prefill", cat="serving",
                             endpoint=self.name, seq=seq, path=path,
                             bucket=plan["bucket"]):
            with tracer.mirrored("decode.prefill.enqueue", cat="serving",
                                 endpoint=self.name):
                if path == "cow":
                    self._pool.columns = self._copy_page(
                        self._pool.columns, np.int32(plan["cow"]),
                        np.int32(plan["dst"]),
                    )
                else:
                    program = (self._suffix_prefill if path == "suffix"
                               else self._prefill)
                    cols, fd = program(
                        self.params, self._pool.columns, *operands)
            if path == "cow":
                # the solo step (its own decode.step.enqueue)
                cols, fd = self._run_step(
                    self.params, self._pool.columns, *operands)[:2]
            self._pool.columns = cols
            with tracer.mirrored("decode.prefill.fetch", cat="serving",
                                 endpoint=self.name):
                first = int(np.asarray(fd)[0]) if path == "cow" \
                    else int(fd)
        m.DECODE_STEPS["prefill"].inc()
        m.DECODE_PREFILL_SEGMENTS.inc()
        return first

    def _publish(self, seq: int, prompt: np.ndarray, plen: int,
                 resumed: bool, plan: Dict[str, object]) -> None:
        """After a one-sequence prefill: share its prompt pages and
        record a prefix hit."""
        if self._prefix_cache and not resumed:
            # publish this prompt's freshly written FULL pages so later
            # requests can share them (no-op on total overlap; stops at
            # chain-key collisions with another lineage)
            self._pool.publish_prefix(seq, prompt)
        hit_pages, cow = plan["hit_pages"], plan["cow"]
        if hit_pages or cow is not None:
            _flight.record(
                "serving.decode.prefix_hit", endpoint=self.name,
                seq=seq, prompt_len=plen,
                shared_pages=len(hit_pages), covered_tokens=plan["covered"],
                copy_on_extend=cow is not None,
            )

    def _joinable(self, req: _Request, now: float) -> bool:
        """False where the request is answered or seated already: its
        deadline passed between the poll and here, or its swapped pages
        came back (a swap-in is a join of its own)."""
        if req.deadline is not None and req.deadline <= now:
            # lost the race with the expirer between poll and here
            m.DEADLINE_EXPIRED.inc()
            req.future._fail(DeadlineExceededError(
                f"request to {self.name!r} expired after "
                f"{now - req.t_submit:.4f}s waiting for a decode slot"
            ))
            self._resume.pop(req, None)
            self._drop_swap(req)
            return False
        if self._swap_store is not None and req in self._swap:
            if self._swap_in(req, self._swap.pop(req), now):
                return False
            # counted fallback: the replay data kept alongside the
            # snapshot resumes it through the recompute path
        return True

    def _join(self, req: _Request) -> None:
        # the deadline and the wait read the clock, traced or not; the
        # traced span starts at _t_mark, where the phase before it ended
        now = time.perf_counter()
        if not self._joinable(req, now):
            return
        self._open_phase("decode.join")
        tracer = _events.TRACER
        prompt = req.feeds["prompt"]
        plen = int(prompt.shape[0])
        seq = self._next_seq
        self._next_seq += 1
        replay = self._resume.pop(req, None)
        with tracer.mirrored("decode.join.build", cat="serving",
                             endpoint=self.name):
            plan = self._plan_prefill(seq, prompt, plen,
                                      resumed=bool(replay))
        first = self._run_prefill(seq, plan)
        with tracer.mirrored("decode.join.seat", cat="serving",
                             endpoint=self.name):
            self._publish(seq, prompt, plen, bool(replay), plan)
            self._seat(req, seq, first, replay, now,
                       len(plan["hit_pages"]))
        if tracer.enabled:
            args = {"seq": seq, "prompt_len": plen,
                    "resumed": bool(replay), "path": plan["path"],
                    "waited_s": round(now - req.t_submit, 6)}
            if req.trace_id:
                args["request_id"] = req.trace_id
            self._phase("decode.join", **args)

    def _join_packed(self, polled: Sequence[_Request]) -> None:
        """Join one poll's requests with their prompts packed into as few
        prefill calls as fit: a call takes the next requests in arrival
        order, each one's pages allocated and its rows placed on the
        next block edge, until it holds ``PACK_SEGMENTS`` prompts or the
        next would pass the ladder's top bucket; then one dispatch and
        one fetch a call, and its requests seated."""
        now = time.perf_counter()
        # a swapped-out request's pages coming back is a join of its own
        joins = [req for req in polled if self._joinable(req, now)]
        block = self.model.pack_block
        top = self._prefill_buckets[-1]
        at = 0
        while at < len(joins):
            self._open_phase("decode.join")
            with _events.TRACER.mirrored("decode.join.build",
                                         cat="serving", endpoint=self.name):
                call: List[tuple] = []
                rows = 0
                while at < len(joins) and len(call) < self._pack_segments:
                    req = joins[at]
                    prompt = req.feeds["prompt"]
                    plen = int(prompt.shape[0])
                    span = -(-plen // block) * block
                    if call and rows + span > top:
                        break
                    replay = self._resume.pop(req, None)
                    if not replay:
                        self._note_repeat(prompt, plen)
                    seq = self._next_seq
                    self._next_seq += 1
                    for kind, n in self._pool.demand(plen).items():
                        self._pool.alloc(seq, n, kind)
                    call.append((req, seq, prompt, replay, rows))
                    rows += span
                    at += 1
                operands = self._pack_operands(call)
            self._prefill_packed(call, operands, now)

    def _pack_operands(self, call: List[tuple]) -> Tuple[np.ndarray, ...]:
        """The packed program's host operands for ``call``'s ``(request,
        seq, prompt, replay, first row)`` entries: the tokens in the
        call's bucket, each prompt's start and length, its page table."""
        segs = self._pack_segments
        _req, _seq, prompt, _replay, at = call[-1]
        tb = next(b for b in self._prefill_buckets if b >= at + len(prompt))
        tokens = np.zeros(tb, np.int32)
        start = np.zeros(segs, np.int32)
        length = np.zeros(segs, np.int32)
        tables = np.zeros((segs, self._pool.max_pages_per_seq), np.int32)
        for b, (_req, seq, prompt, _replay, at) in enumerate(call):
            tokens[at:at + len(prompt)] = prompt
            start[b], length[b] = at, len(prompt)
            tables[b] = self._pool.table(seq)
        return tokens, start, length, tables

    def _prefill_packed(self, call: List[tuple],
                        operands: Tuple[np.ndarray, ...], now: float) -> None:
        """One packed prefill call over ``call``'s entries, then each
        request seated."""
        tokens, _start, length, _tables = operands
        tb = len(tokens)
        tracer = _events.TRACER
        # the program's dispatch through the first tokens' arrival on the
        # host: the device's share of decode.join
        with tracer.mirrored("decode.prefill", cat="serving",
                             endpoint=self.name, path="packed", bucket=tb,
                             segments=len(call)):
            with tracer.mirrored("decode.prefill.enqueue", cat="serving",
                                 endpoint=self.name):
                cols, fd = self._packed_prefill(
                    self.params, self._pool.columns, *operands)
            self._pool.columns = cols
            with tracer.mirrored("decode.prefill.fetch", cat="serving",
                                 endpoint=self.name):
                firsts = np.asarray(fd)
        m.DECODE_STEPS["prefill"].inc()
        m.DECODE_PREFILL_SEGMENTS.inc(len(call))
        with tracer.mirrored("decode.join.seat", cat="serving",
                             endpoint=self.name):
            for b, (req, seq, _prompt, replay, _at) in enumerate(call):
                self._seat(req, seq, int(firsts[b]), replay, now, 0)
        if tracer.enabled:
            used = int(length.sum())
            args = {"joins": len(call), "tokens": used, "bucket": tb,
                    "padded": tb - used, "path": "packed",
                    "waited_s": round(max(now - e[0].t_submit
                                          for e in call), 6)}
            rids = [e[0].trace_id for e in call if e[0].trace_id]
            if rids:
                args["request_ids"] = rids
            self._phase("decode.join", **args)

    def _seat(self, req: _Request, seq: int, first: int,
              replay: Optional[List[int]], now: float,
              prefix_pages: int) -> None:
        """Put a prefilled sequence into a free slot with its first token
        (a replay checks it against the recorded one instead of counting
        it), and finish it at once if it wanted one token."""
        prompt = req.feeds["prompt"]
        self._join_counter += 1
        s = _Seq(req, seq, prompt, int(req.feeds["new"]),
                 self._join_counter, now - req.t_submit)
        tok = first
        if replay:
            s.replay = collections.deque(replay)
            expect = s.replay.popleft()
            if tok != expect:
                self._bit_identity_violation(s, tok, expect)
                return
            if not s.replay:
                s.replay = None
        else:
            t_first = time.perf_counter()
            m.DECODE_TTFT.observe(t_first - req.t_submit)
            req.future.t_first_token = t_first
            m.DECODE_TOKENS.inc()
        s.generated.append(tok)
        idx = self._slots.index(None)
        self._slots[idx] = s
        # delta, not set(): several engines share the process-wide
        # occupancy gauge (the free-pages twin lives in PagedKVPool)
        m.DECODE_SLOTS.inc()
        _flight.record(
            "serving.decode.join", endpoint=self.name, seq=seq,
            prompt_len=int(prompt.shape[0]), new_tokens=s.want,
            resumed=bool(replay), prefix_pages=prefix_pages,
            waited_s=round(s.waited, 6),
        )
        if len(s.generated) >= s.want:
            self._finish(s)

    def _swap_in(self, req: _Request, snap: Dict[str, object],
                 now: float) -> bool:
        """Restore a host-swapped sequence's pages bit-identically and
        put it straight back into a slot — no prefill, no replay, no
        recompute. Returns False on ANY store or pool problem (counted
        as ``tftpu_kvswap_fallback_total``; the caller's replay path
        still resumes the request — a swap problem never loses one)."""
        self._open_phase("decode.join")
        seq = self._next_seq
        self._next_seq += 1
        try:
            pages, block = self._pool.swap_in_seq(
                self._swap_store, snap, seq
            )
        except Exception as e:
            # a corrupt segment was already quarantined + counted by
            # the store; drop the ref if it survived, count the
            # fallback, and let the replay join take over
            try:
                self._swap_store.drop(snap["ref"])
            except Exception:
                pass
            m.KVSWAP_FALLBACKS.inc()
            self._swap_fallbacks += 1
            logger.warning(
                "decode engine %r: swap-in failed (%s: %s); falling "
                "back to recompute-replay", self.name,
                type(e).__name__, e,
            )
            _flight.record(
                "serving.decode.swap_fallback", endpoint=self.name,
                error=type(e).__name__, message=str(e)[:200],
            )
            return False
        maxp = self._pool.max_pages_per_seq
        npg = len(pages)
        idx = np.zeros(maxp, np.int32)
        idx[:npg] = pages
        payload = []
        for name in ("k", "v", "k_scale", "v_scale"):
            arr = np.asarray(block[name])
            fullp = np.zeros((maxp,) + arr.shape[1:], arr.dtype)
            fullp[:npg] = arr
            payload.append(fullp)
        # padding rows scatter zeros into the null page — garbage by
        # contract; one fixed-shape dispatch, warmed at start
        self._pool.columns = self._restore(
            self._pool.columns, idx, *payload
        )
        self._join_counter += 1
        s = _Seq(req, seq, req.feeds["prompt"],
                 int(req.feeds["new"]), self._join_counter,
                 now - req.t_submit)
        s.pos = int(snap["pos"])
        s.generated = list(snap["generated"])
        s.replay = (collections.deque(snap["replay"])
                    if snap["replay"] else None)
        self._resume.pop(req, None)
        self._slots[self._slots.index(None)] = s
        m.DECODE_SLOTS.inc()
        m.KVSWAP_RESUMES.inc()
        self._swap_resumes += 1
        _flight.record(
            "serving.decode.swap_in", endpoint=self.name, seq=seq,
            pages=npg, tokens_done=len(s.generated),
            waited_s=round(s.waited, 6),
        )
        if len(s.generated) >= s.want:
            self._finish(s)
        if _events.TRACER.enabled:
            args = {"seq": seq, "path": "swap",
                    "waited_s": round(s.waited, 6)}
            if req.trace_id:
                args["request_id"] = req.trace_id
            self._phase("decode.join", **args)
        return True

    def _active(self) -> List[_Seq]:
        return [s for s in self._slots if s is not None]

    def _decode_step(self) -> None:
        # page faults first, oldest slot first: a slot whose next write
        # position crosses into an unallocated page must get one, by
        # preemption if the pool is dry. The victim is always the
        # YOUNGEST running sequence (possibly the faulting slot itself)
        # — the oldest is never evicted, and the pool floor (one full
        # horizon) guarantees it can always finish: forward progress is
        # structural, preemption cannot livelock.
        self._open_phase("decode.prepare")
        allocated = preempted = 0
        for s in sorted(self._active(), key=lambda x: x.joined):
            for kind in self._kinds:
                if preempted and s not in self._slots:
                    break  # preempted by an earlier fault in this pass
                # the pages of this kind a context of pos + 1 holds (a
                # ring kind: never more than its entries)
                if kind.pages_for(s.pos + 1, self._pool.page_size) \
                        <= self._pool.held(s.seq, kind.name):
                    continue
                preempted_self = False
                while self._pool.allocatable(kind.name) < 1:
                    victim = max(self._active(), key=lambda x: x.joined)
                    self._preempt(victim)
                    preempted += 1
                    if victim is s:
                        preempted_self = True
                        break
                if preempted_self:
                    break
                try:
                    self._pool.alloc(s.seq, 1, kind.name)
                    allocated += 1
                except PoolExhaustedError:  # pragma: no cover - guarded
                    self._preempt(s)
                    preempted += 1
        active = self._active()
        tracer = _events.TRACER
        if not active:
            if tracer.enabled:
                self._phase("decode.prepare", "decode.admit", slots=0,
                            pages_allocated=allocated,
                            preempted=preempted)
            return
        n = len(active)
        sb = next(b for b in self._slot_buckets if b >= n)
        maxp = self._pool.max_pages_per_seq
        tokens = np.zeros(sb, np.int32)
        pos = np.zeros(sb, np.int32)
        tables = [np.zeros((sb, k.entries), np.int32) for k in self._kinds]
        for row, s in enumerate(active):
            tokens[row] = s.generated[-1]
            pos[row] = s.pos
            for table, kind in zip(tables, self._kinds):
                table[row] = self._pool.table(s.seq, kind.name)
        if tracer.enabled:
            # page faults, preemption and the host-side build above
            self._phase("decode.prepare", "decode.step", slots=n,
                        pages_allocated=allocated, preempted=preempted)
        out = self._run_step(
            self.params, self._pool.columns, tokens, pos, *tables
        )
        self._pool.columns = out[0]
        # the wait for the device until the step's tokens are on the host
        with tracer.mirrored("decode.step.fetch", cat="serving",
                             endpoint=self.name):
            nxt = np.asarray(out[1])
        with tracer.mirrored("decode.step.count", cat="serving",
                             endpoint=self.name) as leaf:
            m.DECODE_STEPS["decode"].inc()
            span = self._count_walk(pos, sb)
            if len(out) > 2:
                span.update(self._count_experts(out[2]))
            if leaf is not None:
                leaf.args.update(span)
        if tracer.enabled:
            args = {"slots": n, "bucket": sb, **span}
            rids = [s.req.trace_id for s in active if s.req.trace_id]
            if rids:
                args["request_ids"] = rids[:16]
            self._phase("decode.step", "decode.commit", **args)
        finished = fresh = 0
        for row, s in enumerate(active):
            s.pos += 1
            tok = int(nxt[row])
            if s.replay:
                expect = s.replay.popleft()
                if tok != expect:
                    self._bit_identity_violation(s, tok, expect)
                    continue
                if not s.replay:
                    s.replay = None
                tok = expect
            else:
                fresh += 1
            s.generated.append(tok)
            if len(s.generated) >= s.want:
                self._finish(s)
                finished += 1
        # one increment a step (the replayed tokens are not progress)
        m.DECODE_TOKENS.inc(fresh)
        if tracer.enabled:
            # token bookkeeping, replay checks and the finishes above
            self._phase("decode.commit", "decode.admit", finished=finished)

    def _count_walk(self, pos: np.ndarray, sb: int) -> Dict[str, int]:
        """Count the page-table entries one step's attention walked, and
        return the ``decode.step`` span's share of them. The kernel
        folds the chunks each row's context (or window) reaches; the XLA
        chain gathers and attends the whole table. A model with one page
        kind counts on the unlabelled series, as it always has; one with
        several counts each kind under ``kind=``, and beside it the
        entries the contexts reach, window or not."""
        page = self._pool.page_size
        span: Dict[str, int] = {}
        reach = int((pos // page + 1).sum())
        for kind in self._kinds:
            walked = grid = sb * kind.entries
            if self._attn_is_kernel:
                walked = int(self._pages_walked(
                    pos, page, kind.entries, kind.window
                ).sum())
            if len(self._kinds) == 1:
                m.DECODE_ATTN_PAGES_WALKED.inc(walked)
                m.DECODE_ATTN_PAGES_GRID.inc(grid)
                return {"pages_walked": walked, "pages_grid": grid}
            m.DECODE_ATTN_PAGES_WALKED_BY_KIND[kind.name].inc(walked)
            m.DECODE_ATTN_PAGES_GRID_BY_KIND[kind.name].inc(grid)
            m.DECODE_ATTN_PAGES_CONTEXT[kind.name].inc(reach)
            span[f"{kind.name}_pages_walked"] = walked
        return span

    @staticmethod
    def _count_experts(stats) -> Dict[str, int]:
        """Count what a step's expert layers routed (``stats`` as the
        model's step returns them, ``models/served.py``)."""
        counts = np.asarray(stats["expert_counts"])
        load_max = int(counts.max(axis=1).sum())
        m.MOE_TOKENS_ROUTED.inc(int(counts.sum()))
        m.MOE_EXPERT_LOAD_MAX.inc(load_max)
        return {"experts_max_load": load_max}

    def _slot_of(self, s: _Seq) -> int:
        return self._slots.index(s)

    def _swap_out(self, s: _Seq) -> Optional[Dict[str, object]]:
        """Extract the sequence's pages (one fixed-shape gather, warmed)
        and publish them to the swap store's CRC-stamped disk segment.
        Returns the snapshot, or None if the store write failed — the
        caller falls back to plain eviction + recompute-replay, so a
        swap problem can never lose a request."""
        pages = self._pool.seq_pages(s.seq)
        maxp = self._pool.max_pages_per_seq
        idx = np.zeros(maxp, np.int32)
        idx[:len(pages)] = pages
        ex = self._extract(self._pool.columns, idx)
        block = {
            name: np.ascontiguousarray(np.asarray(col)[:len(pages)])
            for name, col in ex.items()
        }
        try:
            snap = self._pool.swap_out_seq(
                self._swap_store, s.seq, block
            )
        except Exception as e:
            logger.warning(
                "decode engine %r: swap-out failed (%s: %s); evicting "
                "with recompute-replay resume", self.name,
                type(e).__name__, e,
            )
            return None
        snap["pos"] = s.pos
        snap["generated"] = list(s.generated)
        snap["replay"] = list(s.replay or ())
        m.KVSWAP_OUTS.inc()
        m.KVSWAP_BYTES.inc(int(snap["ref"].nbytes))
        self._swap_outs += 1
        _flight.record(
            "serving.decode.swap_out", endpoint=self.name, seq=s.seq,
            pages=int(snap["pages"]), bytes=int(snap["ref"].nbytes),
            tokens_done=len(s.generated),
        )
        return snap

    def _preempt(self, s: _Seq) -> None:
        self._slots[self._slot_of(s)] = None
        m.DECODE_SLOTS.dec()
        snap = (self._swap_out(s)
                if self._swap_store is not None else None)
        freed = (int(snap["freed"]) if snap is not None
                 else self._pool.free_seq(s.seq))
        m.DECODE_PREEMPTIONS.inc()
        m.DECODE_EVICTIONS.inc(freed)
        _flight.record(
            "serving.decode.preempt", endpoint=self.name, seq=s.seq,
            tokens_done=len(s.generated), pages_evicted=freed,
            swapped=snap is not None,
        )
        # requeue at the HEAD with the generated prefix intact: on
        # rejoin, a swap snapshot restores the pages outright, and the
        # recompute-replay data is ALWAYS kept beside it — prefill +
        # teacher-forced replay through the same executables is the
        # counted fallback if the segment comes back corrupt. A
        # sequence preempted MID-replay keeps its unreplayed suffix
        # too — dropping it would re-count those tokens as fresh and
        # silently skip their bit-identity check
        self._resume[s.req] = list(s.generated) + list(s.replay or ())
        if snap is not None:
            self._swap[s.req] = snap
        if not self._admission.requeue_front(s.req):
            self._resume.pop(s.req, None)
            self._drop_swap(s.req)

    def _finish(self, s: _Seq) -> None:
        req = s.req
        # the finish work itself: a leaf inside decode.commit (or
        # decode.join.seat, for a request that wanted one token)
        with _events.TRACER.mirrored(
                "decode.finish", cat="serving", endpoint=self.name,
                seq=s.seq, tokens=min(len(s.generated), s.want)) as leaf:
            if leaf is not None and req.trace_id:
                leaf.args["request_id"] = req.trace_id
            self._slots[self._slot_of(s)] = None
            m.DECODE_SLOTS.dec()
            self._pool.free_seq(s.seq)
            out = np.asarray(s.generated[:s.want], np.int32)[None, :]
            done = time.perf_counter()
            m.REQUEST_LATENCY.observe(done - req.t_submit)
            req.future._set({"tokens": out})
            _flight.record(
                "serving.decode.finish", endpoint=self.name, seq=s.seq,
                tokens=int(out.shape[1]),
                seconds=round(done - req.t_submit, 6),
            )
        if leaf is not None:
            # the request's whole life overlaps every other request's:
            # an async pair, off the span timeline
            t_first = req.future.t_first_token
            _events.TRACER.emit_async(
                "decode.request", req.trace_id, req.t_submit,
                done - req.t_submit,
                args=dict(
                    leaf.args, prompt_len=int(s.prompt.shape[0]),
                    waited_s=round(s.waited, 6),
                    ttft_s=(None if t_first is None
                            else round(t_first - req.t_submit, 6)),
                ),
                cat="serving",
            )

    def _bit_identity_violation(self, s: _Seq, got: int,
                                expect: int) -> None:
        """A resumed sequence diverged from its recorded prefix — a
        determinism bug, never load. Fail THIS request loudly (the
        engine keeps serving); silently continuing would hand the
        client a sequence that contradicts the preemption contract.
        Callable both mid-join (slot not yet assigned) and mid-step."""
        if s in self._slots:
            self._slots[self._slot_of(s)] = None
            m.DECODE_SLOTS.dec()
        self._pool.free_seq(s.seq)
        m.DISPATCH_ERRORS.inc()
        _flight.record(
            "serving.decode.replay_divergence", endpoint=self.name,
            seq=s.seq, got=got, expected=expect,
            at_token=len(s.generated),
        )
        s.req.future._fail(ServingError(
            f"decode engine {self.name!r}: resumed sequence diverged "
            f"from its pre-preemption prefix (got token {got}, "
            f"recorded {expect} at index {len(s.generated)}) — "
            "determinism bug, please report"
        ))

    def _fail_all(self, exc: BaseException) -> None:
        for i, s in enumerate(self._slots):
            if s is not None:
                self._slots[i] = None
                m.DECODE_SLOTS.dec()
                self._pool.free_seq(s.seq)
                s.req.future._fail(exc)
        for req in self._in_hand:
            if not req.future.done():
                req.future._fail(exc)
        self._in_hand = ()
        self._admission.close(drain=False)

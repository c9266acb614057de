"""Block execution engine: marshalling + compiled-program caching.

This layer replaces the reference's per-partition worker kernels
(``DebugRowOpsImpl``, impl/DebugRowOps.scala:704-980) and its Row⇄Tensor
marshalling stack (``TFDataOps``/``DataOps``/``datatypes``). Where the
reference opens a fresh TF ``Graph``+``Session`` per partition
(TensorFlowOps.scala:76-95) and hand-rolls buffer fill loops
(DataOps.scala:63-81), here each program is ``jax.jit``-compiled **once per
distinct block shape** and cached by XLA; marshalling is a zero-copy
``numpy → jax.Array`` device transfer.

Block row counts produced by the frame partitioner take at most two
distinct values (n//k and n//k+1), so map_blocks' jit cache stays tiny
without padding. map_rows additionally buckets its vmapped lead dim to
powers of two (:func:`bucket_rows`) so externally-built frames with
arbitrary block sizes — and ragged blocks grouped by cell shape — keep
the compile count O(log n); ``cache_sizes`` gives the honest recompile
accounting SURVEY.md §7 hard-part 1 calls for.

Dispatch is ONE pipeline (ISSUE 10): every feed — host blocks,
multi-device sharded columns, multi-process SPMD frames, callback
programs — keys by (entry kind, feed shapes/dtypes, input placements)
and builds a per-key executable by explicit ``lower().compile()``,
consulting the persistent store (:mod:`tensorframes_tpu.compilecache`)
first. That is the Julia-to-TPU thesis (arXiv 1810.09868) applied at
the executor: whole programs compiled ahead-of-time for the actual
target topology, never per-process lazy jit. The old jax.jit path
survives only as :meth:`CompiledProgram._fallback_call` — an
explicitly-counted last resort for programs whose AOT build raises —
and :func:`aot_jit` offers the same pipeline for arbitrary pytree
functions (the model train steps the MULTICHIP dryruns compile).
"""

from __future__ import annotations

import functools
import threading
import time
import weakref
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import dtypes as dt
from ..config import get_config
from ..observability import events as _events
from ..observability import flight as _flight
from ..observability import latency as _latency
from ..observability.metrics import counter as _counter
from ..observability.metrics import histogram as _histogram
from ..program import Program
from ..resilience import fleet as _fleet
from ..resilience.faults import delay_point, fault_point, register_site
from ..utils import get_logger, is_tpu_backend

logger = get_logger(__name__)

register_site(
    "executor.dispatch",
    "CompiledProgram._run dispatch body, inside the deadline-watchdog "
    "scope — an injected Delay simulates a hung collective (the "
    "dispatch stalls instead of failing) so the watchdog is drillable",
)

# Registered at import so the exposition always carries the executor
# family (a cold cache reads hits=0, it does not vanish). "Hit" means
# this CompiledProgram has already dispatched this exact feed-shape key;
# A miss's cost is split honestly (ISSUE 5 satellite, completed by the
# ISSUE 10 unification): trace + XLA compile lands in compile-seconds
# (skipped entirely when the persistent store serves the executable —
# compare against tftpu_compilecache_load_seconds), the first execution
# in first-run-seconds — on EVERY dispatch path, sharded and
# multi-process included. The old "legacy fallback lumps compile+run"
# caveat is gone with the legacy path: the last-resort jit fallback is
# separately counted and observes neither histogram. This is the honest
# recompile accounting SURVEY §7 hard-part 1 asks for.
_JIT_HITS = _counter(
    "tftpu_executor_jit_cache_hits_total",
    "Dispatches whose feed-shape/placement key was already compiled",
)
_JIT_MISSES = _counter(
    "tftpu_executor_jit_cache_misses_total",
    "Dispatches that required a fresh executable (compiled or loaded "
    "from the persistent store)",
)
_COMPILE_SECONDS = _histogram(
    "tftpu_executor_compile_seconds",
    "Trace + XLA-compile wall-clock per feed-shape key (persistent-"
    "store hits skip it; run time is never included)",
)
_FIRST_RUN_SECONDS = _histogram(
    "tftpu_executor_first_run_seconds",
    "Wall-clock of the first execution per feed-shape key, compile "
    "excluded",
)
_FALLBACK_DISPATCHES = _counter(
    "tftpu_executor_fallback_dispatch_total",
    "Dispatches that could not build an AOT executable and fell back "
    "to lazy jax.jit (last resort; the failure reason is logged once "
    "per key)",
)
_PADDING_WASTE = _counter(
    "tftpu_executor_padding_waste_rows_total",
    "Rows added by bucket padding of the vmapped lead dim",
)
_GATHER_BYTES = _counter(
    "tftpu_executor_gather_bytes_total",
    "Bytes of feed columns gathered for program dispatch — the plan "
    "layer's select pushdown shows up as this counter NOT growing for "
    "pruned columns",
)
# Both grow when a hoisted entry is BUILT (once per program, feed shape
# and placement) and never on a dispatch: growth in steady state means
# weights are being uploaded again.
_CONST_PLACEMENTS = _counter(
    "tftpu_executor_const_placements_total",
    "Times a hoisted program's constants (weights) were put on a "
    "device set: once per hoisted entry, at its construction",
)
_CONST_PLACED_BYTES = _counter(
    "tftpu_executor_const_placed_bytes_total",
    "Bytes of hoisted constants those placements wrote to device "
    "memory (a replicated placement counts every device's copy)",
)


def donation_supported() -> bool:
    """True when the active backend implements input-buffer donation.
    XLA:CPU ignores donation with a per-call warning, so the donate
    paths gate on this instead of spamming host-only runs."""
    return is_tpu_backend()


def bucket_rows(n: int) -> int:
    """Round a row count up to the next power-of-two bucket:
    ``min_bucket * 2**k`` for the smallest k that fits, bounded by
    ``max_bucket_doublings`` (config). Beyond the largest bucket the
    exact count is returned — an honest exact-shape compile instead of
    unbounded padding.

    This is the static-shape answer to the reference's per-shape
    recompiles (DataOps.scala:103-144 dynamic-shape handling; SURVEY §7
    hard-part 1): padding the *vmapped lead dim* keeps the jit cache
    O(log n) over arbitrary block sizes. Only row-independent (map_rows)
    semantics may use it — padded rows are sliced off after execution.
    """
    cfg = get_config()
    b = max(1, int(cfg.min_bucket))
    if n <= b:
        return b
    for _ in range(max(0, int(cfg.max_bucket_doublings))):
        b *= 2
        if b >= n:
            return b
    return n


def bucket_table() -> List[int]:
    """The lead-dim bucket ladder :func:`bucket_rows` rounds into under
    the current config: ``[min_bucket, min_bucket*2, …]``, one entry per
    allowed doubling. The static analyzer's recompile-storm rule
    (TFG101) cross-checks program shapes against this table — an
    Unknown dim the ladder cannot bound compiles per distinct extent."""
    cfg = get_config()
    b = max(1, int(cfg.min_bucket))
    out = [b]
    for _ in range(max(0, int(cfg.max_bucket_doublings))):
        b *= 2
        out.append(b)
    return out


def pad_lead_dim(
    feeds: Dict[str, np.ndarray], n: int, target: int
) -> Dict[str, np.ndarray]:
    """Pad every feed's leading dim from ``n`` to ``target`` rows by
    replicating the last row (replication keeps padded rows numerically
    tame — no 0-divides or log(0) from zero fill; results are sliced back
    to ``n`` rows by the caller)."""
    if target == n:
        return feeds
    _PADDING_WASTE.inc(target - n)
    out = {}
    for k, v in feeds.items():
        v = np.asarray(v)
        pad = np.broadcast_to(v[-1:], (target - n,) + v.shape[1:])
        out[k] = np.concatenate([v, pad])
    return out


def _sharding_token(sh) -> Optional[str]:
    """Canonical JSON of a sharding's descriptor, memoized per
    (sharding, current default device) — jax shardings are hashable and
    reused across dispatches, and rebuilding the descriptor walks
    mesh.devices per feed per call, per-step overhead the replaced raw
    jax.jit dispatch never paid. The default device is part of the memo
    key because the descriptor normalizes the default placement to the
    trivial token: a mid-process ``jax_default_device`` change must not
    serve stale Nones. None for the trivial placement."""
    from ..parallel.mesh import default_device

    return _sharding_token_cached(sh, default_device())


@functools.lru_cache(maxsize=256)
def _sharding_token_cached(sh, _default_dev) -> Optional[str]:
    import json as _json

    from ..parallel.mesh import sharding_descriptor

    desc = sharding_descriptor(sh)
    return None if desc is None else _json.dumps(desc, sort_keys=True)


def _feed_sharding(v):
    """The feed's sharding when it is a NON-TRIVIAL placement (sharded
    over a mesh, or committed to a non-default device), else None —
    host arrays and default-device feeds keep a placement-free identity
    so warmed shapes match them regardless of how the data arrives."""
    try:
        sh = getattr(v, "sharding", None)
        if sh is None:
            return None
        return sh if _sharding_token(sh) is not None else None
    except Exception:  # pragma: no cover - defensive: never block dispatch
        return None


def _placement_token(v) -> Optional[str]:
    """Hashable dispatch-key component for a feed's placement (the
    canonical JSON of its sharding descriptor; None for the trivial
    placement). An AOT executable is layout-specialized — calling it
    with differently-sharded arguments raises — so the placement is
    part of the dispatch identity exactly like shape and dtype."""
    try:
        sh = getattr(v, "sharding", None)
        return None if sh is None else _sharding_token(sh)
    except Exception:  # pragma: no cover - defensive: never block dispatch
        return None


class _KeyedBuildCache:
    """Double-checked per-key build memoization shared by the two AOT
    builders (CompiledProgram executables and _AotJit entries): an
    outer lock guards the maps, builds serialize on a PER-KEY lock so
    distinct keys compile concurrently, and a key whose build raised is
    memoized as failed — callers fall back to lazy jit. ONE copy of the
    protocol, so a lock-ordering or accounting fix cannot silently skip
    one builder."""

    def __init__(self):
        self.built: Dict[Tuple, object] = {}
        self.failed: set = set()
        self._lock = threading.Lock()
        self._key_locks: Dict[Tuple, threading.Lock] = {}

    def peek(self, key):
        """Lock-free read for the dispatch fast path (dict.get is
        GIL-atomic); None when unbuilt or failed."""
        return self.built.get(key)

    def get_or_build(self, key: Tuple, build: Callable,
                     describe: str) -> Tuple[object, str]:
        """Return ``(value, how)`` — ``('cached')`` when already built,
        the builder's own ``(value, how)`` on a fresh build, or
        ``(None, 'failed')`` when this (or an earlier) build of ``key``
        raised."""
        with self._lock:
            if key in self.built:
                return self.built[key], "cached"
            if key in self.failed:
                return None, "failed"
            key_lock = self._key_locks.setdefault(key, threading.Lock())
        with key_lock:
            with self._lock:  # lost the race: another thread built it
                if key in self.built:
                    return self.built[key], "cached"
                if key in self.failed:
                    return None, "failed"
            try:
                value, how = build()
            except Exception as e:
                logger.warning(
                    "AOT build failed for %s (%s: %s); this key "
                    "dispatches through the counted lazy-jit fallback",
                    describe, type(e).__name__, e,
                )
                with self._lock:
                    self.failed.add(key)
                return None, "failed"
            with self._lock:
                self.built[key] = value
            return value, how


def _store_meta(kind: str, form: str, donate: bool, inputs,
                shardings: Dict, multiprocess: bool,
                rank: Optional[int], label: Optional[str] = None) -> Dict:
    """The ONE store-entry meta schema, shared by both AOT builders
    (CompiledProgram and _AotJit) so an accounting or schema change
    cannot silently diverge between the two dispatch entries."""
    meta = {
        "kind": kind,
        "form": form,
        "donate": donate,
        "backend": jax.default_backend(),
        "device_kind": getattr(jax.devices()[0], "device_kind", "unknown"),
        "jax": jax.__version__,
        "inputs": inputs,
    }
    if label is not None:
        meta["label"] = label
    if shardings:
        from ..parallel.mesh import sharding_descriptor

        meta["shardings"] = {
            k: sharding_descriptor(sh)
            for k, sh in sorted(shardings.items())
        }
    if multiprocess:
        meta["n_processes"] = jax.process_count()
        meta["published_by_rank"] = rank
    return meta


def _consts_placement(shardings: Sequence):
    """Where a hoisted program's constants live, given its feeds'
    NON-TRIVIAL shardings: fully replicated over the feeds' own device
    set — the feeds' mesh with an empty spec, or the one device a feed
    is committed to. None (uncommitted on the default device, as host
    and default-device feeds have always had them) when no feed carries
    a placement: committing there would make a later call with feeds
    elsewhere an error. None too when the feeds name different device
    sets or a multi-device sharding without a mesh — jax refuses such a
    call at ``lower()`` as it always has; nothing is guessed."""
    if not shardings:
        return None
    first = shardings[0]
    devices = first.device_set
    if any(sh.device_set != devices for sh in shardings[1:]):
        return None
    if len(devices) == 1:
        (dev,) = devices
        return jax.sharding.SingleDeviceSharding(dev)
    if not isinstance(first, jax.sharding.NamedSharding):
        return None
    return jax.sharding.NamedSharding(
        first.mesh, jax.sharding.PartitionSpec()
    )


def _hoisted_for(fn, feeds: Dict[str, jnp.ndarray], name: str = "run"):
    """Build a :class:`HoistedProgram` (program.py — weights as runtime
    arguments, put on their devices once, here) at these feeds' shapes
    — and placements: sharded feeds trace (and later lower) with their
    shardings attached and the constants replicated over the same
    device set (:func:`_consts_placement`), so the hoisted executable
    is specialized to the layout the dispatch will call it with and a
    call moves no weights. ``name`` becomes the XLA module's
    (``jit_<name>``)."""
    from ..program import HoistedProgram

    abstract = {}
    shardings = []
    for k, v in feeds.items():
        sh = _feed_sharding(v)
        if sh is not None:
            shardings.append(sh)
            abstract[k] = jax.ShapeDtypeStruct(
                np.shape(v), v.dtype, sharding=sh
            )
        else:
            abstract[k] = jax.ShapeDtypeStruct(np.shape(v), v.dtype)
    placement = _consts_placement(shardings)
    entry = HoistedProgram(fn, abstract, name=name, placement=placement)
    nbytes = entry.const_bytes()
    if nbytes:
        _CONST_PLACEMENTS.inc()
        _CONST_PLACED_BYTES.inc(
            nbytes * (1 if placement is None else len(placement.device_set))
        )
    return entry


class CompiledProgram:
    """A Program plus its jitted entrypoints (block and per-row)."""

    def __init__(self, program: Program, hoist_consts: Optional[bool] = None):
        self.program = program
        self.hoist = (
            get_config().hoist_constants if hoist_consts is None else hoist_consts
        )
        self.jit_block = jax.jit(program.fn)
        # vmapped form: maps the program over the leading axis of every
        # input — the TPU-native replacement for the reference's row loop
        # (performMapRows, DebugRowOps.scala:826-864).
        self.jit_vmap = jax.jit(jax.vmap(program.fn))
        # input-donating variants, built lazily: the caller passes
        # donate=True only for freshly-transferred host feeds, letting
        # XLA reuse input HBM for outputs (peak-footprint halving on
        # big blocks)
        self._jit_block_donate = None
        self._jit_vmap_donate = None
        self._hoisted: Dict[Tuple, object] = {}
        # feed-shape keys already dispatched at least once, per entry
        # kind — the basis of the exported jit-cache hit/miss counters
        # (mirrors what XLA's own cache will decide, without reaching
        # into jax internals on the hot path)
        self._dispatched: set = set()
        # per-feed-shape AOT executables (the primary dispatch path):
        # built by explicit lower().compile() — or deserialized from
        # the persistent store (compilecache) — so compile time and
        # run time are separately measurable, and a warm store can
        # skip XLA entirely. Keys include the donate variant; a failed
        # key permanently uses the legacy jit path instead.
        self._aot = _KeyedBuildCache()

    @staticmethod
    def _feeds_key(kind: str, feeds) -> Tuple:
        return (kind,) + tuple(
            sorted(
                (k, tuple(int(d) for d in np.shape(v)), str(v.dtype),
                 _placement_token(v))
                for k, v in feeds.items()
            )
        )

    def _note_dispatch(self, key: Tuple, donate: bool) -> bool:
        """Count a cache hit or miss for this dispatch; True on miss.
        ``donate`` is part of the dispatch identity — the donating
        variants compile through separate jitted callables, so a first
        donate=True call at a known shape is still a fresh compile."""
        if donate:
            key = key + ("donate",)
        if key in self._dispatched:
            _JIT_HITS.inc()
            return False
        self._dispatched.add(key)
        _JIT_MISSES.inc()
        return True

    def module_name(self, kind: str) -> str:
        """What the executable is called in a device trace, less jax's
        ``jit_`` prefix: ``tftpu_<role>_<block|rows>``, the role being
        the program's (``map``, ``fused_map``, ``map_reduce``,
        ``reduce``, …; see ``Program.role``)."""
        return (f"tftpu_{self.program.role}_"
                f"{'block' if kind == 'block' else 'rows'}")

    def _entry(self, key: Tuple, fn, feeds):
        entry = self._hoisted.get(key)
        if entry is None:
            try:
                entry = _hoisted_for(fn, feeds, self.module_name(key[0]))
            except Exception as e:
                # exotic programs (host callbacks, non-array consts) keep
                # the plain closure-capture path
                logger.debug("constant hoisting unavailable: %s", e)
                entry = False
            self._hoisted[key] = entry
        return entry

    def _kind_fn(self, kind: str) -> Callable:
        return self.program.fn if kind == "block" else jax.vmap(
            self.program.fn
        )

    def _fingerprint(self, kind: str, abstract: Dict, donate: bool,
                     entry) -> Optional[str]:
        """Persistent-store key for this (program, feed-shape, variant,
        placement). None when the program cannot be fingerprinted (no
        store use)."""
        from ..compilecache.fingerprint import fingerprint_from_closed

        avals = sorted(
            (k, tuple(int(d) for d in v.shape), str(v.dtype))
            for k, v in abstract.items()
        )
        shardings = {
            k: sh for k, v in abstract.items()
            if (sh := _feed_sharding(v)) is not None
        }
        outs = list(
            self.program.fetch_order
            or [o.name for o in self.program.outputs]
        )
        try:
            if entry:
                closed = entry.closed
                hoisted = True
            else:
                closed = jax.make_jaxpr(self._kind_fn(kind))(abstract)
                hoisted = False
            # the name is baked into the stored executable
            extra = {"module": self.module_name(kind)}
            consts = _sharding_token(entry.placement) if entry else None
            if consts is not None:
                # so is the constants' layout: an entry compiled from
                # uncommitted constants left that layout to the
                # compiler (sharding propagation to parameters) and
                # must not be served for buffers replicated up front.
                # On the default device alone there is one layout, and
                # the key stays what it was
                extra["consts"] = consts
            return fingerprint_from_closed(
                closed, avals, outs, kind=kind, donate=donate,
                hoisted=hoisted, shardings=shardings, extra=extra,
            )
        except Exception as e:
            from ..compilecache.store import note_unfingerprintable

            logger.debug("program not fingerprintable: %s", e)
            note_unfingerprintable()
            return None

    def _build_aot(self, kind: str, akey: Tuple, feeds: Dict,
                   donate: bool) -> Optional[Tuple[Callable, str]]:
        """Build the per-shape executable for ``akey``: trace (hoisted
        when possible), consult the persistent store, else AOT
        lower+compile (timed into compile-seconds) and publish to the
        store. Returns (callable, 'disk'|'compiled'), or None when this
        key must use the legacy jit path. ``feeds`` may be concrete
        arrays or ShapeDtypeStructs (warmup compiles without data)."""
        call, how = self._aot.get_or_build(
            akey,
            lambda: self._build_aot_impl(kind, akey, feeds, donate),
            describe=str(akey[0]),
        )
        return None if call is None else (call, how)

    def _build_aot_impl(self, kind, akey, feeds, donate):
        from ..compilecache import store as cc_store

        base = akey[:-1] if akey and akey[-1] == "donate" else akey
        abstract = {}
        shardings = {}
        for k, v in feeds.items():
            sh = _feed_sharding(v)
            if sh is not None:
                shardings[k] = sh
                abstract[k] = jax.ShapeDtypeStruct(
                    np.shape(v), v.dtype, sharding=sh
                )
            else:
                abstract[k] = jax.ShapeDtypeStruct(np.shape(v), v.dtype)
        multiprocess = jax.process_count() > 1
        t0 = time.perf_counter()
        # multi-process fleets keep the plain (closure-capture) form:
        # hoisted consts live on THIS rank's devices (uncommitted on
        # its default device, or replicated over the feeds' device
        # set), so a hoisted executable bakes a per-rank device
        # assignment into its input layout and could never be shared
        # across the fleet's store — baked consts compile identically
        # on every rank. On one process the entry's constants are in
        # place before lower(), so the compiled input layout and the
        # buffers _wrap_executable closes over agree.
        entry = (
            self._entry(base, self._kind_fn(kind), feeds)
            if self.hoist and not multiprocess else None
        )
        trace_s = time.perf_counter() - t0

        store = None
        fp = None
        rank = jax.process_index() if multiprocess else None
        from ..plan.ir import program_has_callback

        if not program_has_callback(self.program):
            # callback programs bind process-local host functions — an
            # executable serialized from one process cannot call back
            # into another's registry, so they never touch the store
            # (in-process AOT still applies, through this same pipeline,
            # so the hit/compile/first-run accounting stays uniform)
            store = cc_store.active_store()
        if store is not None:
            fp = self._fingerprint(kind, abstract, donate, entry)
        meta_inputs = sorted(
            (k, list(v.shape), str(v.dtype)) for k, v in abstract.items()
        )
        if fp is not None:
            loaded = store.get(fp, rank=rank)
            if loaded is not None:
                return self._wrap_executable(entry, loaded), "disk"
            store.record_miss(
                kind,
                [(n, tuple(s), d) for (n, s, d) in meta_inputs],
                donate,
                sharded=bool(shardings),
            )

        t1 = time.perf_counter()
        if entry:
            jitted = (
                jax.jit(entry._run, donate_argnums=(1,))
                if donate else entry.jitted
            )
            compiled = jitted.lower(
                entry.consts, entry._flat_abstract
            ).compile()
        else:
            fn = self._kind_fn(kind)

            def plain(feeds):
                return fn(feeds)

            plain.__name__ = plain.__qualname__ = self.module_name(kind)
            jitted = (
                jax.jit(plain, donate_argnums=(0,))
                if donate else jax.jit(plain)
            )
            compiled = jitted.lower(abstract).compile()
        _COMPILE_SECONDS.observe(trace_s + (time.perf_counter() - t1))
        if fp is not None:
            meta = _store_meta(
                kind, "hoisted" if entry else "plain", donate,
                meta_inputs, shardings, multiprocess, rank,
            )
            store.put(fp, compiled, meta=meta, rank=rank)
        return self._wrap_executable(entry, compiled), "compiled"

    @staticmethod
    def _wrap_executable(entry, executable) -> Callable:
        """Close the executable over its call convention: hoisted form
        takes (consts, flat_inputs), plain form the feeds dict."""
        if entry:
            in_tree = entry.in_tree
            consts = entry.consts

            def call(feeds):
                flat, tree = jax.tree_util.tree_flatten(feeds)
                if tree != in_tree:
                    raise ValueError(
                        "input structure changed since tracing"
                    )
                return executable(consts, flat)

            return call
        return lambda feeds: executable(feeds)

    def warm(self, kind: str, abstract: Dict[str, object],
             donate: bool = False) -> str:
        """Precompile (or disk-load) the executable for one feed-shape
        key WITHOUT executing it — ``abstract`` maps input names to
        ShapeDtypeStructs (attach a ``sharding`` to warm a sharded
        placement's key). The key is marked dispatched, so the first
        real dispatch at this shape counts as a jit-cache hit (no
        compile happens there). Multi-process fleets warm like anything
        else — every dispatch rides the unified AOT path, so the old
        refusal (warming keys the legacy jit path would bypass) has
        nothing left to refuse. Returns 'cached' | 'disk' | 'compiled'
        | 'failed'."""
        donate = donate and donation_supported()
        key = self._feeds_key(kind, abstract)
        akey = key + ("donate",) if donate else key
        built = self._build_aot(kind, akey, abstract, donate)
        if built is None:
            return "failed"
        self._dispatched.add(akey)
        return built[1]

    def _run(self, kind: str, feeds, to_numpy: bool, donate: bool):
        tracing = _events.TRACER.enabled
        t_in = time.perf_counter() if tracing else 0.0
        # flight-record identity of this dispatch BEFORE anything can
        # fail (fault injection fires at the fault_point below): a crash
        # postmortem must carry the dispatch that was in flight
        def _shape_of(v):
            s = getattr(v, "shape", None)
            if s is not None:
                return list(s)
            try:
                return [len(v)]  # ragged list feed: lead dim only
            except TypeError:
                return []

        summary = {
            "entry": kind,
            "outputs": ",".join(self.program.fetch_order[:6]),
            "shapes": {
                k: _shape_of(v) for k, v in list(feeds.items())[:6]
            },
        }
        try:
            fault_point(
                f"executor.run_{'block' if kind == 'block' else 'rows'}"
            )
            donate = donate and donation_supported()
            feeds = {k: jnp.asarray(v) for k, v in feeds.items()}
            key = self._feeds_key(kind, feeds)
            # NOTE: the hoisted entry is keyed WITHOUT donate (one
            # HoistedProgram serves both; donation is a call-time
            # argument), while the hit/miss identity includes it
            # (donate variants are separate executables)
            akey = key + ("donate",) if donate else key
            fresh = self._note_dispatch(key, donate)
            call = self._aot.peek(akey)
            placed = None  # the hoisted entry THIS dispatch built
            if call is None:
                had_entry = key in self._hoisted
                built = self._build_aot(kind, akey, feeds, donate)
                if built is not None:
                    call = built[0]
                if tracing and not had_entry:
                    placed = self._hoisted.get(key)
            deadline = _fleet.dispatch_deadline_s()
            if deadline and call is None and fresh:
                # last-resort jit fallback, first dispatch at this
                # shape: the XLA compile happens lazily INSIDE the call
                # (the unified AOT path compiles outside the watchdog,
                # above — so a store-hit or freshly-AOT-compiled first
                # dispatch stays bounded). A 20-40s TPU compile is not
                # a hung collective — and under supervise() a
                # deterministic compile > deadline would burn the whole
                # restart budget without any rank ever being hung.
                # Genuine cache-miss lazy compiles are therefore the
                # ONLY exempt dispatches (counted, so an exemption in
                # steady state is visible); everything else stays
                # bounded.
                _fleet.note_deadline_exemption(
                    f"executor.run_{'block' if kind == 'block' else 'rows'}"
                )
                deadline = 0.0

            def _invoke():
                delay_point("executor.dispatch")
                r = (
                    call(feeds) if call is not None
                    else self._fallback_call(kind, key, feeds, donate)
                )
                if deadline:
                    # deadline mode synchronizes: a collective wedged on
                    # a dead peer must hang INSIDE the watchdog scope,
                    # not at a later np.asarray outside it
                    r = jax.block_until_ready(r)
                return r

            t0 = time.perf_counter()
            if deadline:
                out = _fleet.run_with_deadline(
                    _invoke,
                    describe=(
                        f"executor.run_"
                        f"{'block' if kind == 'block' else 'rows'}"
                        f"[{','.join(self.program.fetch_order[:4])}]"
                    ),
                    deadline=deadline,
                )
            else:
                out = _invoke()
            dt = time.perf_counter() - t0
        except BaseException as e:
            _flight.record(
                "dispatch.error", error=type(e).__name__,
                message=str(e), **summary,
            )
            raise
        _latency.dispatch_histogram(kind).observe(dt)
        _flight.record(
            "dispatch", seconds=round(dt, 6), compiled=fresh, **summary
        )
        if fresh:
            if call is not None:
                _FIRST_RUN_SECONDS.observe(dt)
            # the jit fallback's lazy compile+run is deliberately NOT
            # observed into compile-seconds: that histogram times pure
            # trace+XLA-compile on every path now, and the fallback has
            # its own counter (lumping would resurrect the pre-unification
            # accounting caveat)
        if tracing:
            which = "block" if kind == "block" else "rows"
            # entry to dispatch: the flight summary, the feeds'
            # asarray, the keys and the executable's lookup or build
            prep = {"kind": which, "compiled": fresh}
            if placed and (nbytes := placed.const_bytes()):
                # this dispatch put the program's constants on devices
                prep["const_bytes"] = nbytes
                prep["placement"] = (
                    _sharding_token(placed.placement) or "default"
                )
            _events.TRACER.emit_complete(
                "executor.prepare", t_in, t0 - t_in,
                args=prep, cat="executor",
            )
            # synced: deadline mode blocked on the result inside the
            # span; otherwise it times the dispatch alone
            _events.TRACER.emit_complete(
                f"executor.run_{which}", t0, dt,
                args={"compiled": fresh, "synced": bool(deadline)},
                cat="executor",
            )
        if not to_numpy:
            return out  # stay in HBM: sharded frames chain without transfers
        host = {k: np.asarray(v) for k, v in out.items()}
        if tracing:
            # from the end of the run span: where an unsynced dispatch
            # is waited for
            t1 = t0 + dt
            _events.TRACER.emit_complete(
                "executor.fetch", t1, time.perf_counter() - t1,
                args={"bytes": sum(v.nbytes for v in host.values())},
                cat="executor",
            )
        return host

    def _fallback_call(self, kind: str, key: Tuple, feeds, donate: bool):
        """Last-resort lazy jax.jit dispatch, reachable ONLY when the
        unified AOT build raised (``_aot.failed``) — every normal feed
        class (host, sharded, multi-process, callback) rides the AOT
        pipeline. Explicitly counted so a fleet quietly living on this
        path is visible in the exposition; the build failure itself is
        logged by :meth:`_build_aot`."""
        _FALLBACK_DISPATCHES.inc()
        entry = (
            self._entry(key, self._kind_fn(kind), feeds)
            if self.hoist else None
        )
        if entry:
            return entry(feeds, donate=donate)
        if kind == "block":
            if donate:
                if self._jit_block_donate is None:
                    self._jit_block_donate = jax.jit(
                        self.program.fn, donate_argnums=(0,)
                    )
                return self._jit_block_donate(feeds)
            return self.jit_block(feeds)
        if donate:
            if self._jit_vmap_donate is None:
                self._jit_vmap_donate = jax.jit(
                    jax.vmap(self.program.fn), donate_argnums=(0,)
                )
            return self._jit_vmap_donate(feeds)
        return self.jit_vmap(feeds)

    def run_block(
        self,
        feeds: Dict[str, np.ndarray],
        to_numpy: bool = True,
        donate: bool = False,
    ) -> Dict[str, np.ndarray]:
        return self._run("block", feeds, to_numpy, donate)

    def run_rows(
        self,
        feeds: Dict[str, np.ndarray],
        to_numpy: bool = True,
        donate: bool = False,
    ) -> Dict[str, np.ndarray]:
        return self._run("vmap", feeds, to_numpy, donate)

    def run_rows_bucketed(
        self,
        feeds: Dict[str, np.ndarray],
        to_numpy: bool = True,
        donate: bool = False,
    ) -> Dict[str, np.ndarray]:
        """The serving layer's batched dispatch entry (ISSUE 9): pad
        the shared lead dim up the power-of-two ladder
        (:func:`bucket_rows` — the same policy ``compilecache.warmup``
        precompiles), run the vmapped program, slice back to the true
        row count. Unlike ``map_rows``' adaptive bucketing this ALWAYS
        buckets, so a server warmed over the ladder dispatches any
        admissible row count with zero steady-state compiles — and a
        row's result is bit-identical however it was coalesced (vmap is
        row-independent; padding replicates the last row and is sliced
        off here)."""
        sizes = {k: int(np.shape(v)[0]) for k, v in feeds.items()}
        ns = set(sizes.values())
        if len(ns) != 1:
            raise ValueError(
                f"run_rows_bucketed: feeds disagree on the lead dim: "
                f"{sizes}"
            )
        n = ns.pop()
        if n == 0:
            raise ValueError("run_rows_bucketed: zero-row dispatch")
        feeds = pad_lead_dim(feeds, n, bucket_rows(n))
        outs = self._run("vmap", feeds, to_numpy=False, donate=donate)
        outs = {k: v[:n] for k, v in outs.items()}
        if not to_numpy:
            return outs
        return {k: np.asarray(v) for k, v in outs.items()}

    def cache_sizes(self) -> Dict[str, int]:
        """Honest recompile accounting (SURVEY §7 hard-part 1): how many
        distinct shapes each entrypoint holds an executable for (AOT
        entries — compiled or store-loaded — plus legacy jit/hoisted
        compiles; donate variants of one shape count once, as before).
        Ragged map_rows grows the vmap cache by one per distinct
        (cell shape, lead-dim bucket) group."""
        def size(fn) -> int:
            try:
                return int(fn._cache_size())
            except Exception:  # pragma: no cover - jax internals moved
                return -1

        aot_bases = {
            (k[:-1] if k and k[-1] == "donate" else k)
            for k in self._aot.built
        }

        def count(kind: str) -> int:
            aot = sum(1 for b in aot_bases if b[0] == kind)
            hoisted = sum(
                1 for k, v in self._hoisted.items()
                if v and k[0] == kind and k not in aot_bases
            )
            return aot + hoisted

        return {
            "block": size(self.jit_block) + count("block"),
            "vmap": size(self.jit_vmap) + count("vmap"),
        }


# ---------------------------------------------------------------------------
# aot_jit — the unified pipeline for arbitrary pytree functions
# ---------------------------------------------------------------------------

def _shardings_tree_token(tree) -> object:
    """JSON-able identity of a declared in/out_shardings pytree (None
    passes through; sharding leaves become their descriptors). Folded
    into the fingerprint's ``extra`` slot: two aot_jit entries tracing
    to the same jaxpr but declaring different output layouts compile
    different collective schedules and must key apart."""
    if tree is None:
        return None
    Sharding = jax.sharding.Sharding
    leaves, treedef = jax.tree_util.tree_flatten(
        tree, is_leaf=lambda x: isinstance(x, Sharding)
    )
    from ..parallel.mesh import sharding_descriptor

    return {
        "tree": str(treedef),
        "leaves": [
            sharding_descriptor(leaf) if isinstance(leaf, Sharding)
            else (None if leaf is None else str(leaf))
            for leaf in leaves
        ],
    }


#: key components an ``_AotJit`` keeps by identity before it drops the
#: dead ones (a donated buffer leaves one behind each dispatch)
_LEAF_TOKENS_MAX = 4096


class _AotJit:
    """``jax.jit``-shaped callable whose dispatch rides the executor's
    unified AOT pipeline: per-argument-shape/placement keys, explicit
    ``lower().compile()`` timed into ``tftpu_executor_compile_seconds``,
    the persistent store consulted first (topology-fingerprinted, so a
    fleet restart loads instead of recompiling), and the lazy-jit
    fallback explicitly counted. See :func:`aot_jit`."""

    def __init__(self, fn, in_shardings=None, out_shardings=None,
                 label: Optional[str] = None,
                 donate_argnums: Tuple[int, ...] = ()):
        kw = {}
        if in_shardings is not None:
            kw["in_shardings"] = in_shardings
        if out_shardings is not None:
            kw["out_shardings"] = out_shardings
        donate_argnums = tuple(sorted(int(i) for i in donate_argnums))
        if donate_argnums:
            kw["donate_argnums"] = donate_argnums
        self._fn = fn
        self._donate = donate_argnums
        self._jitted = jax.jit(fn, **kw)
        self._label = label or getattr(fn, "__qualname__", None) \
            or type(fn).__name__
        self._decl = {
            "in_shardings": _shardings_tree_token(in_shardings),
            "out_shardings": _shardings_tree_token(out_shardings),
        }
        if donate_argnums:
            # a donating executable aliases its outputs onto these
            # arguments and deletes them: the in-process key and the
            # store's fingerprint must never serve it to (or from) an
            # undonated entry of the same function
            self._decl["donate_argnums"] = list(donate_argnums)
        self._builds = _KeyedBuildCache()
        self._dispatched: set = set()
        # id(leaf) -> (weakref to the leaf, default device, its key
        # component): see _leaf_token
        self._leaf_tokens: Dict[int, Tuple] = {}

    def _leaf_token(self, v, default_dev) -> Tuple:
        """One leaf's component of the dispatch key. A device array is
        immutable (shape, dtype, weak type and placement are fixed for
        its life), and a served model hands the same hundred weight
        arrays to every step: their components are kept by identity
        (a weak reference, so nothing is held alive and a recycled
        ``id`` never matches) instead of being rebuilt from
        ``str(dtype)`` and the sharding's descriptor on each dispatch."""
        kept = isinstance(v, jax.Array) \
            and not isinstance(v, jax.core.Tracer)
        if kept:
            hit = self._leaf_tokens.get(id(v))
            if hit is not None and hit[0]() is v and hit[1] is default_dev:
                return hit[2]
        token = (tuple(int(d) for d in v.shape), str(v.dtype),
                 bool(getattr(v, "weak_type", False)), _placement_token(v))
        if kept:
            if len(self._leaf_tokens) >= _LEAF_TOKENS_MAX:
                # donated buffers come and go each dispatch: drop the dead
                live = {k: h for k, h in self._leaf_tokens.items()
                        if h[0]() is not None}
                self._leaf_tokens = live \
                    if len(live) < _LEAF_TOKENS_MAX // 2 else {}
            self._leaf_tokens[id(v)] = (weakref.ref(v), default_dev, token)
        return token

    def _key(self, leaves, treedef) -> Optional[Tuple]:
        if any(
            not hasattr(v, "dtype") or not hasattr(v, "shape")
            for v in leaves
        ):
            # a Python-scalar leaf traces weakly-typed under jit; an AOT
            # executable is strongly typed — this entry stays lazy-jit
            return None
        # weak_type is part of the identity: a weak leaf promotes
        # differently (int8 + weak int stays int8), so a weak and a
        # strong feed of the same dtype must not share an executable.
        # The treedef enters as the OBJECT (hashable, eq-comparable) —
        # stringifying a transformer's param tree repr per step is
        # dispatch overhead the jax.jit C++ fast path never paid.
        from ..parallel.mesh import default_device

        dev = default_device()
        return (treedef, self._donate) + tuple(
            self._leaf_token(v, dev) for v in leaves
        )

    def _build(self, key: Tuple, args) -> Optional[Callable]:
        call, _ = self._builds.get_or_build(
            key,
            lambda: (self._build_impl(args), "built"),
            describe=f"aot_jit({self._label})",
        )
        return call

    def _build_impl(self, args) -> Callable:
        from ..compilecache import store as cc_store
        from ..compilecache.fingerprint import fingerprint_from_closed

        def abstract_of(v):
            # weak_type must survive into the trace: dropping it would
            # promote int8 + weak-int to the weak leaf's dtype, a result
            # the jax.jit this wraps never produces
            weak = bool(getattr(v, "weak_type", False))
            sh = _feed_sharding(v)
            if sh is not None:
                return jax.ShapeDtypeStruct(np.shape(v), v.dtype,
                                            sharding=sh, weak_type=weak)
            return jax.ShapeDtypeStruct(np.shape(v), v.dtype,
                                        weak_type=weak)

        abstract = jax.tree_util.tree_map(abstract_of, args)
        multiprocess = jax.process_count() > 1
        rank = jax.process_index() if multiprocess else None

        t0 = time.perf_counter()
        closed = jax.make_jaxpr(self._fn)(*abstract)
        trace_s = time.perf_counter() - t0

        from ..analysis.rules import _iter_eqns

        has_callback = any(
            "callback" in eqn.primitive.name
            for eqn in _iter_eqns(closed.jaxpr)
        )
        leaves = jax.tree_util.tree_leaves(abstract)
        avals = [
            (f"a{i}", tuple(int(d) for d in v.shape), str(v.dtype))
            for i, v in enumerate(leaves)
        ]
        shardings = {
            f"a{i}": sh for i, v in enumerate(leaves)
            if (sh := getattr(v, "sharding", None)) is not None
        }
        store = None if has_callback else cc_store.active_store()
        fp = None
        if store is not None:
            # weak_type must reach the PERSISTENT key too: the jaxpr
            # text renders weak and strong avals identically, so without
            # this a strong-compiled store entry would be served to a
            # weak-typed feed of the same shape/dtype (the in-process
            # key already splits them)
            extra = dict(self._decl)
            weak = [
                bool(getattr(v, "weak_type", False)) for v in leaves
            ]
            if any(weak):
                extra["weak"] = weak
            try:
                fp = fingerprint_from_closed(
                    closed, avals, [self._label], kind="fn",
                    shardings=shardings, extra=extra,
                )
            except Exception as e:
                logger.debug("aot_jit(%s) not fingerprintable: %s",
                             self._label, e)
                cc_store.note_unfingerprintable()
        if fp is not None:
            loaded = store.get(fp, rank=rank)
            if loaded is not None:
                return loaded
            store.record_miss(
                "fn", [(n, tuple(s), d) for (n, s, d) in avals],
                False, sharded=bool(shardings),
            )
        t1 = time.perf_counter()
        compiled = self._jitted.lower(*abstract).compile()
        _COMPILE_SECONDS.observe(trace_s + (time.perf_counter() - t1))
        if fp is not None:
            meta = _store_meta(
                "fn", "plain", bool(self._donate),
                sorted((n, list(s), d) for (n, s, d) in avals),
                shardings, multiprocess, rank, label=self._label,
            )
            store.put(fp, compiled, meta=meta, rank=rank)
        return compiled

    def executable(self, *args):
        """The executable (``jax.stages.Compiled``, compiled here or
        loaded from the store) that serves ``args``' signature, built
        now if it was not yet; None for a lazy-jit-only signature. For
        introspection (``memory_analysis()``, ``as_text()``): dispatch
        goes through ``__call__``, which counts it."""
        leaves, treedef = jax.tree_util.tree_flatten(args)
        key = self._key(leaves, treedef)
        return None if key is None else self._build(key, args)

    def __call__(self, *args):
        leaves, treedef = jax.tree_util.tree_flatten(args)
        key = self._key(leaves, treedef)
        call = None
        if key is not None:
            fresh = key not in self._dispatched
            if fresh:
                self._dispatched.add(key)
                _JIT_MISSES.inc()
            else:
                _JIT_HITS.inc()
            call = self._build(key, args)
        else:
            # keyless (lazy-jit-only) entries still scope the deadline
            # exemption to the FIRST dispatch of each signature jax's
            # own trace cache would compile for — weak-typed Python
            # scalars key by type, not value. Without this, `fresh`
            # would hold on every call and permanently blind the fleet
            # watchdog to steady-state hangs of this entry.
            lazy_key = ("lazy", treedef) + tuple(
                (tuple(int(d) for d in v.shape), str(v.dtype),
                 _placement_token(v))
                if hasattr(v, "shape") and hasattr(v, "dtype")
                else (type(v).__name__,)
                for v in leaves
            )
            fresh = lazy_key not in self._dispatched
            if fresh:
                self._dispatched.add(lazy_key)
        deadline = _fleet.dispatch_deadline_s()
        if deadline and call is None and fresh:
            # same scoping as CompiledProgram._run: only a genuine
            # cache-miss lazy compile (the counted fallback) is exempt
            # from the dispatch deadline — AOT/store-served first
            # dispatches compiled above, outside the watchdog scope
            _fleet.note_deadline_exemption(f"aot_jit[{self._label}]")
            deadline = 0.0
        if call is None:
            _FALLBACK_DISPATCHES.inc()

        def _invoke():
            r = call(*args) if call is not None else self._jitted(*args)
            if deadline:
                r = jax.block_until_ready(r)
            return r

        t0 = time.perf_counter()
        if deadline:
            out = _fleet.run_with_deadline(
                _invoke, describe=f"aot_jit[{self._label}]",
                deadline=deadline,
            )
        else:
            out = _invoke()
        if fresh and call is not None:
            _FIRST_RUN_SECONDS.observe(time.perf_counter() - t0)
        return out


def aot_jit(fn, *, in_shardings=None, out_shardings=None,
            label: Optional[str] = None,
            donate_argnums: Tuple[int, ...] = ()) -> Callable:
    """Drop-in replacement for ``jax.jit(fn, in_shardings=...,
    out_shardings=...)`` that dispatches through the executor's unified
    AOT pipeline (ISSUE 10): explicit ``lower().compile()`` per
    argument-shape/placement key with the compile timed into
    ``tftpu_executor_compile_seconds``, the persistent store
    (``TFTPU_COMPILE_CACHE``) consulted before XLA — keyed by the
    topology-fingerprinted content hash, so sharded and multi-process
    programs restart warm — and lazy jit surviving only as the counted
    last-resort fallback. The model train-step factories (transformer
    dp/tp/sp, MoE ep, pipeline pp) build their steps through this, which
    is what lets the MULTICHIP dryruns hit the store on a second run.

    Positional array arguments only (pytrees fine); a call with a
    Python-scalar leaf stays on the lazy-jit path for that key (an AOT
    executable is strongly typed; jit traces scalars weakly).

    ``donate_argnums`` is ``jax.jit``'s: the named positional arguments'
    buffers become the outputs' where shapes allow, and the arrays the
    caller passed are DELETED by the call — use what came back. It is
    part of the in-process key and of the store's fingerprint, so a
    donating executable is never confused with the plain one."""
    return _AotJit(fn, in_shardings=in_shardings,
                   out_shardings=out_shardings, label=label,
                   donate_argnums=donate_argnums)


def gather_feeds(
    block: Dict[str, object],
    input_names: Sequence[str],
    program: Program,
) -> Dict[str, np.ndarray]:
    """Materialize the program's input columns from a block as dense arrays.

    Ragged (list-stored) columns raise here with the analyze hint — the
    reference's equivalent failure happens in ``TFDataOps.convert``'s
    lead-dim check (TFDataOps.scala:28-59).
    """
    demote = dt.demotion_active()
    feeds = {}
    for name in input_names:
        v = block[name]
        if isinstance(v, list):
            spec = program.input(name)
            try:
                v = np.asarray(v, dtype=spec.dtype.np_dtype)
            except (ValueError, TypeError):
                raise ValueError(
                    f"Column {name!r} holds ragged cells and cannot form a "
                    "dense block. Use map_rows for ragged data, or run "
                    "analyze()/append_shape() if the cells are uniform."
                ) from None
        elif demote:
            # x64 demotion boundary: cast 64-bit columns down to the
            # program's 32-bit input spec (works for numpy and sharded
            # jax arrays alike — on device it is a cheap elementwise op)
            spec = program.input(name)
            if getattr(v, "dtype", None) != spec.dtype.np_dtype:
                v = v.astype(spec.dtype.np_dtype)
        feeds[name] = v
        nbytes = getattr(v, "nbytes", 0)
        if nbytes:
            _GATHER_BYTES.inc(int(nbytes))
    return feeds


def block_is_ragged(block: Dict[str, object], input_names: Sequence[str]) -> bool:
    for name in input_names:
        v = block[name]
        if isinstance(v, list):
            shapes = set()
            for c in v:
                shapes.add(np.shape(c))
                if len(shapes) > 1:
                    return True
    return False


# ---------------------------------------------------------------------------
# reduce_rows folds (sequential pairwise, ≙ performReducePairwise,
# DebugRowOps.scala:939-979 — but as a single lax.scan under one jit per
# block shape instead of one Session.run per row pair)
# ---------------------------------------------------------------------------

def pair_fold_body(program: Program, out_names: Sequence[str]) -> Callable:
    """The (unjitted) pairwise fold over the leading axis of per-output
    arrays: dict x -> [n, ...cell] (n >= 1) → dict x -> cell. Shared by
    the host fold (below) and the sharded reduce_rows program
    (verbs._sharded_reduce_rows_fn), so fold semantics cannot diverge."""

    def fold(cols: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
        init = {x: cols[x][0] for x in out_names}
        rest = {x: cols[x][1:] for x in out_names}

        def step(carry, xs):
            feeds = {}
            for x in out_names:
                feeds[f"{x}_1"] = carry[x]
                feeds[f"{x}_2"] = xs[x]
            out = program.fn(feeds)
            return {x: out[x] for x in out_names}, None

        carry, _ = jax.lax.scan(step, init, rest)
        return carry

    return fold


def make_pair_fold(program: Program, out_names: Sequence[str]) -> Callable:
    """Jitted form of :func:`pair_fold_body`."""
    return jax.jit(pair_fold_body(program, out_names))

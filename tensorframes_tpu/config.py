"""Runtime configuration for tensorframes_tpu.

The reference has no runtime config system (SURVEY.md §5-config); its only
knobs are per-call ``ShapeDescription`` hints. The TPU build adds a small,
explicit config object because compilation behavior (padding buckets, x64,
default mesh axis names) genuinely needs global knobs on XLA.

All values can be overridden via environment variables (``TFTPU_*``) or
programmatically via :func:`configure`.
"""

from __future__ import annotations

import dataclasses
import os


def _env_bool(name: str, default: bool) -> bool:
    v = os.environ.get(name)
    if v is None:
        return default
    return v.lower() in ("1", "true", "yes", "on")


def _env_int(name: str, default: int) -> int:
    v = os.environ.get(name)
    return default if v is None else int(v)


def _env_float(name: str, default: float) -> float:
    v = os.environ.get(name)
    return default if v in (None, "") else float(v)


#: The fixed, git-ignored compile-cache directory of a checkout — what
#: the entry points that run on the chip (``chip_smoke.py``,
#: ``bench.py``, ``serving.replica_main``) use when the environment
#: names none. The path is part of jax's cache key, so it must not move
#: between runs: never a temporary directory.
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".tftpu_cache",
)


def resolve_compile_cache_dir(entry_point: bool = False) -> str:
    """THE compile-cache resolver: ``JAX_COMPILATION_CACHE_DIR`` if
    set (jax reads it itself — the package then only hangs its own
    layers under it and never calls ``jax.config.update`` for the
    cache); else ``TFTPU_COMPILE_CACHE``; else, for an entry point,
    :data:`CHECKOUT_CACHE_DIR`; else ``""`` (plain library import stays
    cache-off, so tier-1's compile-count tests keep their meaning)."""
    for var in ("JAX_COMPILATION_CACHE_DIR", "TFTPU_COMPILE_CACHE"):
        if os.environ.get(var):
            return os.environ[var]
    return CHECKOUT_CACHE_DIR if entry_point else ""


def use_compile_cache(entry_point: bool = False) -> str:
    """Point the package's cache layers (``<dir>/aot``, ``planstats``,
    ``results``) at the resolved directory and, unless the environment
    already placed jax's own cache (``JAX_COMPILATION_CACHE_DIR``),
    point jax's persistent cache there too. Called at package import
    and by the chip entry points (``entry_point=True``) before they
    compile anything. Returns the directory (``""`` = cache off)."""
    path = resolve_compile_cache_dir(entry_point)
    _config.compilation_cache_dir = path
    if path and not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path


@dataclasses.dataclass
class Config:
    # Enable float64/int64 end-to-end (the reference's Double/Long columns).
    enable_x64: bool = _env_bool("TFTPU_ENABLE_X64", True)
    # map_rows lead-dim bucketing: pad the vmapped row count up to
    # min_bucket * 2**k (k <= max_bucket_doublings) so jit caches stay
    # O(log n) across varying block sizes; padded rows are sliced off
    # (XLA wants static shapes; SURVEY.md §7 hard-part 1). Only row-
    # independent semantics pad — map_blocks programs see the true block.
    min_bucket: int = _env_int("TFTPU_MIN_BUCKET", 8)
    max_bucket_doublings: int = _env_int("TFTPU_MAX_BUCKET_DOUBLINGS", 30)
    # Default number of blocks when partitioning un-blocked input.
    default_num_blocks: int = _env_int("TFTPU_DEFAULT_NUM_BLOCKS", 4)
    # Mesh axis names used by sharded execution.
    batch_axis: str = os.environ.get("TFTPU_BATCH_AXIS", "dp")
    # aggregate(): rows buffered before compaction in the streaming keyed
    # aggregator (≙ TensorFlowUDAF bufferSize=10, DebugRowOps.scala:580).
    aggregate_buffer_size: int = _env_int("TFTPU_AGG_BUFFER", 10)
    # Per-verb timing metrics collection (upgrade over the reference's
    # log4j-only observability, SURVEY.md §5-tracing).
    collect_metrics: bool = _env_bool("TFTPU_METRICS", True)
    # map_blocks keeps this many extra blocks in flight so transfer and
    # compute overlap (0 = fully synchronous per block).
    map_pipeline_depth: int = _env_int("TFTPU_MAP_PIPELINE_DEPTH", 2)
    # map_blocks host-frame path: stage up to this many blocks' feeds in
    # HBM from a background thread (io.prefetch_to_device) so the
    # host→device transfer of block k+1 overlaps block k's compute —
    # the answer to the reference's admitted convert bottleneck
    # (TFDataOps.scala:32-33) on transfer-taxed links (0 = off).
    map_prefetch_depth: int = _env_int("TFTPU_MAP_PREFETCH_DEPTH", 2)
    # Donate freshly-transferred input buffers to the XLA executable so
    # output HBM reuses input HBM (halves peak footprint for big
    # blocks). Only applies where provably safe: host-sourced feeds on
    # backends that implement donation (not XLA:CPU); device-resident
    # frame columns are never donated.
    donate_inputs: bool = _env_bool("TFTPU_DONATE_INPUTS", True)
    # Persistent executable cache directory: first TPU compiles of
    # the big model programs take 20-40s; with a cache dir set, later
    # processes deserialize the executable instead of recompiling
    # (empty = disabled). Two layers share the knob: jax's builtin
    # HLO-keyed cache writes the root, and the AOT executable store
    # (tensorframes_tpu/compilecache — consulted BEFORE lowering, so a
    # hit skips HLO generation and XLA entirely) lives under <dir>/aot.
    # Resolved by resolve_compile_cache_dir(): JAX_COMPILATION_CACHE_DIR,
    # else TFTPU_COMPILE_CACHE, else (chip entry points only) the
    # checkout's fixed directory.
    compilation_cache_dir: str = dataclasses.field(
        default_factory=resolve_compile_cache_dir
    )
    # Byte bound of the AOT executable store (<cache dir>/aot): least-
    # recently-used entries are evicted past it. 0 disables eviction.
    compile_cache_max_bytes: int = _env_int(
        "TFTPU_COMPILE_CACHE_MAX_MB", 2048
    ) * (1 << 20)
    # Lift closure-captured program constants (frozen model weights) out
    # of the HLO and pass them as runtime arguments. Without this, XLA
    # constant-folds through embedded weights — un-doing int8 weight
    # quantization (measured round 3: folded back to f32, zero byte
    # saving) and bloating every per-shape compile with literal copies
    # of the weights.
    hoist_constants: bool = _env_bool("TFTPU_HOIST_CONSTS", True)
    # Multi-process relational verbs (sort_values / join): frames whose
    # replicated side would exceed this byte budget PER PROCESS switch
    # from the replicating plan (allgather sort / broadcast join) to the
    # hash/range-partitioned exchange (ops/exchange.py), which holds
    # only O(global/P) rows per process (VERDICT r4 #2/#7; ≙ Catalyst's
    # hash-partitioned exchange, DebugRowOps.scala:583).
    relational_broadcast_bytes: int = _env_int(
        "TFTPU_RELATIONAL_BROADCAST_MB", 64
    ) * (1 << 20)
    # Kill-switch for the exchange path (debugging): with it off, an
    # over-budget replicated plan raises an actionable error instead of
    # silently OOMing every process at once.
    relational_exchange: bool = _env_bool("TFTPU_RELATIONAL_EXCHANGE", True)
    # Route quantized 2-D matmuls through the pallas int8 kernel
    # (in-kernel dequant: weights stream HBM→VMEM as int8
    # unconditionally, ops/quantize.matmul_pallas_int8). OFF until a
    # chip cell shows it beating the XLA structural fusion
    # (chip_smoke.py compiles it once and reports the outcome).
    pallas_int8_matmul: bool = _env_bool("TFTPU_PALLAS_INT8_MM", False)
    # Master switch for the straggler pallas kernels (tensorframes_tpu/
    # kernels: paged int8-KV decode attention, fused segment reduce,
    # ragged gather). TFTPU_PALLAS=0 removes them from every cost-model
    # decision — the CI smoke proves the XLA/host lowerings alone keep
    # every suite green. Distinct from the in-process manual switch
    # (ops/segment.disable_pallas); nothing throws either automatically.
    pallas_kernels: bool = _env_bool("TFTPU_PALLAS", True)
    # Force-select the straggler kernels even on CPU backends (the
    # pallas interpreter runs them — slow, but the full wiring from
    # cost model to kernel executes). Tests and the in-bench
    # bit-identity gates use this; never enable it for throughput.
    pallas_force: bool = _env_bool("TFTPU_PALLAS_FORCE", False)
    # Lazy verb-chain fusion (tensorframes_tpu/plan): chained lazy maps
    # record a logical plan instead of nesting compute thunks, and each
    # maximal fusable run lowers to ONE composed XLA program dispatched
    # once per block — per-stage jit dispatch, device<->host transfers
    # and intermediate materialization disappear. TFTPU_FUSION=0 is the
    # escape hatch back to per-stage execution (bit-identical results;
    # the fused path exists purely for speed).
    plan_fusion: bool = _env_bool("TFTPU_FUSION", True)
    # Adaptive query optimizer (tensorframes_tpu/plan: aggregate
    # pushdown below joins, multi-join reordering, and feedback
    # re-optimization from the per-plan stats sidecar under the
    # compile-cache directory). TFTPU_REOPT=0 is the escape hatch back to
    # the PR 7 static cost model: no plan rewrite, no reordering, no
    # stats recording or consultation — bit-identical results either
    # way (the optimizer exists purely for speed; every rewrite is
    # gated on reassoc_safe-style exactness).
    plan_reopt: bool = _env_bool("TFTPU_REOPT", True)
    # Verified UDF lifting (tensorframes_tpu/analysis/lifting +
    # plan/lift): numpy UDFs captured as host callbacks are statically
    # inspected, synthesized into a pure plan-IR Program, and verified
    # bit-exactly on a bounded boundary-value corpus before
    # substitution — a verified lift clears the TFG107 fusion barrier
    # so map→UDF→aggregate chains compile to one dispatch. Anything
    # that does not verify stays a counted callback barrier with the
    # decline reason in TFG112. TFTPU_LIFT=0 replays the callback path
    # for every UDF — the bit-identity oracle (results are identical
    # either way by construction; the lift exists purely for speed).
    udf_lifting: bool = _env_bool("TFTPU_LIFT", True)
    # Out-of-core data plane (tensorframes_tpu/blockstore): resident-
    # bytes budget of a BlockStore — blocks past it spill to disk
    # least-recently-used, and the streaming partitioner's peak RSS is
    # bounded by (pipeline depth x chunk bytes + this budget) instead
    # of the frame size. Also the TFG111 threshold: a forced
    # to_host/to_numpy materialization estimated past this budget is
    # flagged by lint_plan with the streaming alternative named.
    block_budget_bytes: int = _env_int("TFTPU_BLOCK_BUDGET_MB", 512) * (1 << 20)
    # Default spill directory for block stores (empty = a private temp
    # dir per store, deleted with it). Point at fast local SSD in
    # production; the shuffle's per-rank spill files use the shared
    # rendezvous dir (TFTPU_SHUFFLE_DIR / TFTPU_FLEET_DIR) instead —
    # those must be visible to every rank, this need not be.
    blockstore_dir: str = os.environ.get("TFTPU_BLOCKSTORE_DIR", "")
    # Hung-dispatch watchdog (resilience/fleet.py): a dispatch — or a
    # fleet rendezvous barrier — that exceeds this wall-clock deadline
    # aborts with HungDispatchError plus a flight-recorder postmortem
    # naming the unresponsive ranks, instead of blocking forever inside
    # a collective whose peer died. 0 disables (the default: deadline
    # mode synchronizes dispatch results, trading async pipelining for
    # boundedness, so it is opt-in). Enforced in ops/executor.py and
    # parallel/distributed.py.
    dispatch_deadline_s: float = _env_float("TFTPU_DISPATCH_DEADLINE_S", 0.0)
    # Fleet heartbeat cadence: every process enrolled in a rendezvous
    # dir (TFTPU_FLEET_DIR; supervise() arms it for its children)
    # publishes a beat this often ...
    heartbeat_interval_s: float = _env_float("TFTPU_HEARTBEAT_INTERVAL_S", 0.25)
    # ... and a rank whose newest beat is older than this is declared
    # dead (stragglers are flagged at half the timeout). Must comfortably
    # exceed the longest host-side stall a healthy rank can hit (GC,
    # checkpoint fsync, XLA compile on the driving thread).
    heartbeat_timeout_s: float = _env_float("TFTPU_HEARTBEAT_TIMEOUT_S", 5.0)
    # Demote f64/i64 device columns to f32/i32 at the device boundary:
    # False = never (reference-parity precision, f64 emulated on TPU),
    # True = on TPU backends only, "always" = every backend (testing /
    # CPU measurement). Accounted for in explain(detailed=True).
    demote_x64_on_tpu: object = (
        "always"
        if os.environ.get("TFTPU_DEMOTE_X64", "").lower() == "always"
        else _env_bool("TFTPU_DEMOTE_X64", False)
    )


_config = Config()


def get_config() -> Config:
    return _config


def configure(**kwargs) -> Config:
    """Update global config fields by keyword; returns the live config."""
    for k, v in kwargs.items():
        if not hasattr(_config, k):
            raise AttributeError(f"No such config field: {k!r}")
        setattr(_config, k, v)
    return _config

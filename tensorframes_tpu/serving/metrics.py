"""Serving-layer instruments (``tftpu_serving_*``), registered at import.

The admission-control loop is only tunable if its behavior is a graph:
how deep the queue runs, why flushes fire (bucket full vs latency timer
vs drain), how much padding the bucket ladder costs, and where request
wall-clock goes (queue wait vs dispatch). Every instrument here
pre-registers at import — including every ``reason=`` label series the
batcher can emit — so an exposition always carries the full catalog
(a server that never shed load still exports ``rejected_total{...}=0``).

Label conventions follow the repo rule (TFL003): label VALUE sets are
closed and enumerated here; per-endpoint cardinality stays out of the
registry (endpoints ride flight records and trace args instead).
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

from ..observability.latency import LATENCY_BUCKETS
from ..observability.metrics import Counter, Gauge
from ..observability.metrics import counter as _counter
from ..observability.metrics import gauge as _gauge
from ..observability.metrics import histogram as _histogram

__all__ = [
    "REQUESTS", "ROWS", "REJECTED", "REJECT_REASONS", "QUEUE_DEPTH",
    "FLUSHES", "FLUSH_REASONS", "BATCH_ROWS", "PADDING_ROWS",
    "REQUEST_LATENCY", "QUEUE_WAIT", "DISPATCH_SECONDS",
    "DEADLINE_EXPIRED", "DISPATCH_ERRORS", "rejected",
    "DECODE_PHASES", "DECODE_TOKENS", "DECODE_STEPS", "DECODE_TTFT",
    "DECODE_LOOP_CPU",
    "DECODE_PREFILL_SEGMENTS",
    "DECODE_ATTN_PAGES_WALKED", "DECODE_ATTN_PAGES_GRID",
    "DECODE_SLOTS", "DECODE_FREE_PAGES", "DECODE_PREEMPTIONS",
    "DECODE_FREE_KIND_PAGES", "DECODE_PAGE_KINDS", "page_kind_gauges",
    "DECODE_ATTN_PAGES_WALKED_BY_KIND", "DECODE_ATTN_PAGES_GRID_BY_KIND",
    "DECODE_ATTN_PAGES_CONTEXT", "MOE_TOKENS_ROUTED", "MOE_EXPERT_LOAD_MAX",
    "DECODE_EVICTIONS",
    "KVSWAP_OUTS", "KVSWAP_RESUMES", "KVSWAP_FALLBACKS", "KVSWAP_BYTES",
    "PREFIX_HITS", "PREFIX_MISSES", "PREFIX_SHARED_PAGES",
    "PREFIX_EVICTIONS",
    "RESULT_CACHE_HITS", "RESULT_CACHE_MISSES",
    "RESULT_CACHE_INVALIDATIONS", "RESULT_CACHE_BYTES",
    "RESULT_CACHE_CHUNKS_FOLDED", "RESULT_CACHE_RECOMPUTES",
    "RECOMPUTE_REASONS", "result_recompute",
    "HTTP_REJECT_REASONS", "HTTP_REJECTIONS", "http_rejected",
    "IDEMPOTENT_DEDUP",
    "ROUTER_REJECT_REASONS", "ROUTER_REQUESTS", "ROUTER_REDRIVES",
    "ROUTER_REJECTED", "ROUTER_REPLICAS_LIVE", "ROUTER_REPLICA_DEAD",
    "ROUTER_REPLICA_RESTARTS", "ROUTER_DISPATCH_SECONDS",
    "ROUTER_REQUEST_LATENCY", "router_rejected",
    "REQUEST_TRACE",
]

#: Why an admission was refused (closed set — every series pre-registered).
REJECT_REASONS: Tuple[str, ...] = ("queue_full", "closed", "too_large")

#: Why a batch left the queue (closed set).
FLUSH_REASONS: Tuple[str, ...] = ("full", "timer", "drain")

#: Rows-per-flush buckets: the power-of-two ladder serving pads into.
_BATCH_BUCKETS: Tuple[float, ...] = (
    1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096,
)

REQUESTS = _counter(
    "tftpu_serving_requests_total",
    "Requests admitted into the serving queue",
)
ROWS = _counter(
    "tftpu_serving_rows_total",
    "Rows admitted into the serving queue",
)
REJECTED: Dict[str, Counter] = {
    r: _counter(
        "tftpu_serving_rejected_total",
        "Requests refused at admission, by reason (queue_full = "
        "backpressure shed, closed = server stopped/draining, "
        "too_large = request exceeds max_batch_rows)",
        labels={"reason": r},
    )
    for r in REJECT_REASONS
}
QUEUE_DEPTH = _gauge(
    "tftpu_serving_queue_depth_rows",
    "Rows currently waiting in serving queues (all endpoints)",
)
FLUSHES: Dict[str, Counter] = {
    r: _counter(
        "tftpu_serving_flushes_total",
        "Coalesced batches dispatched, by flush reason (full = bucket "
        "target reached, timer = max-latency flush, drain = shutdown)",
        labels={"reason": r},
    )
    for r in FLUSH_REASONS
}
BATCH_ROWS = _histogram(
    "tftpu_serving_batch_rows",
    "Rows per coalesced flush (pre-padding)",
    buckets=_BATCH_BUCKETS,
)
PADDING_ROWS = _counter(
    "tftpu_serving_padding_rows_total",
    "Rows added padding flushes up to the power-of-two bucket ladder",
)
REQUEST_LATENCY = _histogram(
    "tftpu_serving_request_latency_seconds",
    "Request wall-clock from submit to result ready (queue wait + "
    "dispatch) — the p50/p99 the bench serving target reports",
    buckets=LATENCY_BUCKETS,
)
QUEUE_WAIT = _histogram(
    "tftpu_serving_queue_wait_seconds",
    "Request wall-clock from submit to its flush leaving the queue",
    buckets=LATENCY_BUCKETS,
)
DISPATCH_SECONDS = _histogram(
    "tftpu_serving_dispatch_seconds",
    "Wall-clock of one coalesced flush's executor dispatch",
    buckets=LATENCY_BUCKETS,
)
DEADLINE_EXPIRED = _counter(
    "tftpu_serving_deadline_expired_total",
    "Requests failed because their deadline passed while queued",
)
REQUEST_TRACE = _counter(
    "tftpu_serving_request_trace_total",
    "Requests whose trace context crossed a process hop (Router "
    "stamped or replica adopted the X-Tftpu-Trace header) — the "
    "cross-hop tracing coverage signal (ISSUE 17)",
)
DISPATCH_ERRORS = _counter(
    "tftpu_serving_dispatch_errors_total",
    "Coalesced flushes whose dispatch raised (every member request "
    "fails with the same error)",
)


# -- iterative decode (tftpu_decode_*, ISSUE 11) ----------------------------
# The decode engine's health is a rate (tokens/sec = the tokens counter
# differentiated), a latency (TTFT — the open-loop bench gates its
# p50/p99), and three occupancy signals (running slots, free KV pages,
# and how often the pool had to preempt). Request-level latency and
# queue depth ride the shared serving instruments above — a decode
# request IS a serving request.

#: Engine phases (closed set — one executable family per phase).
DECODE_PHASES: Tuple[str, ...] = ("prefill", "decode")

DECODE_TOKENS = _counter(
    "tftpu_decode_tokens_total",
    "Newly generated tokens across all decode endpoints (replayed "
    "tokens of a preempted sequence's resume are NOT counted — they "
    "are recompute, not progress); rate = decode tokens/sec",
)
DECODE_LOOP_CPU = _counter(
    "tftpu_decode_loop_cpu_seconds_total",
    "CPU time of the decode engines' loop threads (time.thread_time, "
    "read once a loop iteration): over tftpu_decode_tokens_total, the "
    "host CPU a generated token costs; against wall time, the share of "
    "the loop spent off the CPU (waiting on the device, the GIL or the "
    "scheduler)",
)
DECODE_STEPS: Dict[str, Counter] = {
    p: _counter(
        "tftpu_decode_steps_total",
        "Engine step dispatches by phase (prefill = one prefill "
        "program's call, one prompt or a packed call of several, "
        "decode = one batched token step over the running slots)",
        labels={"phase": p},
    )
    for p in DECODE_PHASES
}
DECODE_PREFILL_SEGMENTS = _counter(
    "tftpu_decode_prefill_segments_total",
    "Prompts the prefill dispatches carried (one a dispatch, or each "
    "prompt of a packed prefill): over tftpu_decode_steps_total"
    "{phase=prefill}, the prompts a prefill dispatch packs",
    labels={"phase": "prefill"},
)
DECODE_ATTN_PAGES_WALKED = _counter(
    "tftpu_decode_attn_pages_walked_total",
    "Page-table entries covered by the chunks the decode-attention "
    "kernel folds, summed over the rows of every decode step's slot "
    "bucket (a padding row folds one chunk): follows the slots' "
    "contexts",
)
DECODE_ATTN_PAGES_GRID = _counter(
    "tftpu_decode_attn_pages_grid_total",
    "Page-table entries of every decode step's slot bucket (bucket x "
    "max pages a sequence): what a walk of the whole table costs; "
    "walked / grid is the share of it the kernel still runs",
)
DECODE_TTFT = _histogram(
    "tftpu_decode_ttft_seconds",
    "Time to first token: submit to the prompt's prefill completing "
    "(the open-loop decode bench gates p50/p99 of this)",
    buckets=LATENCY_BUCKETS,
)
DECODE_SLOTS = _gauge(
    "tftpu_decode_slot_occupancy",
    "Sequence slots currently running in the iterative decode batch",
)
DECODE_FREE_PAGES = _gauge(
    "tftpu_decode_free_pages",
    "Free pages across decode KV pools (the headroom preemption "
    "defends)",
)
#: The page kinds a served model with several may declare (closed
#: label set, registered here at import like every other: TFL003). A
#: model whose kinds carry other names is refused at ``register_decode``
#: (:func:`page_kind_gauges`); a new kind is a new name in this tuple.
DECODE_PAGE_KINDS: Tuple[str, ...] = ("full", "window")
#: free pages of a kind that is not its model's first (the first kind,
#: the one that fills, reads ``tftpu_decode_free_pages``)
DECODE_FREE_KIND_PAGES: Dict[str, Gauge] = {
    "window": _gauge(
        "tftpu_decode_free_window_pages",
        "Free pages of the decode KV pools' window kind (the ring a "
        "sequence's sliding layers write: at most ceil(window / page) "
        "+ 1 a sequence, so it fills with the sequences running, not "
        "with their lengths); tftpu_decode_free_pages reads the kind "
        "that fills",
    ),
}


def page_kind_gauges(names: Sequence[str]) -> List[Gauge]:
    """The free-pages gauge of each of a model's page kinds, in kind
    order: ``tftpu_decode_free_pages`` for the first, the kind's own for
    each further one. A model with several kinds names each from
    :data:`DECODE_PAGE_KINDS`, once, so that its per-kind series exist;
    anything else is refused here, where the pool is built."""
    names = list(names)
    bad = [n for n in names[1:] if n not in DECODE_FREE_KIND_PAGES]
    if len(names) > 1:
        bad += [n for n in names if n not in DECODE_PAGE_KINDS]
    if bad or len(set(names)) != len(names):
        raise ValueError(
            f"page kinds {names}: a served model with several kinds "
            f"names each of them once, from {list(DECODE_PAGE_KINDS)} "
            f"(serving/metrics.DECODE_PAGE_KINDS: the per-kind series "
            f"are registered at import), and only "
            f"{sorted(DECODE_FREE_KIND_PAGES)} may follow the first"
        )
    return [DECODE_FREE_PAGES] + [DECODE_FREE_KIND_PAGES[n]
                                  for n in names[1:]]


DECODE_ATTN_PAGES_WALKED_BY_KIND: Dict[str, Counter] = {
    k: _counter(
        "tftpu_decode_attn_pages_walked_total",
        "Page-table entries covered by the chunks the decode-attention "
        "kernel folds, per page kind of a model with several (a window "
        "kind's walk starts at the chunk that holds the window's first "
        "position)",
        labels={"kind": k},
    )
    for k in DECODE_PAGE_KINDS
}
DECODE_ATTN_PAGES_GRID_BY_KIND: Dict[str, Counter] = {
    k: _counter(
        "tftpu_decode_attn_pages_grid_total",
        "Page-table entries of every decode step's slot bucket, per "
        "page kind of a model with several (bucket x the kind's entries)",
        labels={"kind": k},
    )
    for k in DECODE_PAGE_KINDS
}
DECODE_ATTN_PAGES_CONTEXT: Dict[str, Counter] = {
    k: _counter(
        "tftpu_decode_attn_pages_context_total",
        "Page-table entries the running slots' contexts reach (pos // "
        "page + 1 a slot and step, window or not), per page kind: what "
        "a walk of the whole context costs; walked / context is the "
        "share of it a window kind still walks",
        labels={"kind": k},
    )
    for k in DECODE_PAGE_KINDS
}
MOE_TOKENS_ROUTED = _counter(
    "tftpu_moe_tokens_routed_total",
    "Token-expert pairs the decode steps' expert layers routed (live "
    "slots x experts a token, summed over layers and steps)",
)
MOE_EXPERT_LOAD_MAX = _counter(
    "tftpu_moe_expert_load_max_total",
    "Sum over decode steps and layers of the fullest expert's tokens: "
    "over tftpu_moe_tokens_routed_total and times the expert count it "
    "is the fullest expert's load over the mean",
)
DECODE_STEP_ALIAS_BYTES = _gauge(
    "tftpu_decode_step_alias_bytes",
    "Bytes of the widest compiled decode step's outputs that alias its "
    "donated arguments (XLA memory_analysis). Equal to the KV pool's "
    "bytes when the pool is written in place; the engine that built "
    "its step last sets it",
)
DECODE_STEP_TEMP_BYTES = _gauge(
    "tftpu_decode_step_temp_bytes",
    "Temporary device bytes the widest compiled decode step plans "
    "(XLA memory_analysis). Well under one KV pool when no program "
    "copies or converts a pool column",
)
DECODE_PREEMPTIONS = _counter(
    "tftpu_decode_preemptions_total",
    "Running sequences preempted because the KV pool had no free page "
    "(evicted, requeued at the head, resumed bit-identically later)",
)
DECODE_EVICTIONS = _counter(
    "tftpu_decode_evictions_total",
    "KV pages evicted by preemption (freed from a preempted "
    "sequence's table)",
)


# -- KV memory hierarchy (tftpu_kvswap_* / tftpu_prefix_cache_*, ISSUE 19) --
# Two page lifecycles beyond the free/owned pair: an evicted sequence's
# pages host-swapping through the block store (resume = restore, not
# recompute), and read-only prefix pages shared across requests by
# content address. The swap counters split the preemption story —
# preemptions_total keeps counting every eviction, kvswap_out_total the
# subset whose pages went to disk, and resume vs fallback says whether
# the swap actually paid off or corruption pushed the request back onto
# the replay path. The prefix counters are the cache's hit-rate and
# residency: hits/misses differentiated = how often a prompt's prefill
# was skipped, shared_pages = pages pinned read-only right now.

KVSWAP_OUTS = _counter(
    "tftpu_kvswap_out_total",
    "Preempted sequences whose KV pages were host-swapped to the "
    "block store (CRC-checked segment) instead of discarded",
)
KVSWAP_RESUMES = _counter(
    "tftpu_kvswap_resume_total",
    "Sequences resumed by restoring host-swapped pages bit-identically "
    "(no prefill or teacher-forced replay ran)",
)
KVSWAP_FALLBACKS = _counter(
    "tftpu_kvswap_fallback_total",
    "Swap-in attempts abandoned for the recompute-replay path (segment "
    "corruption or store failure — the request still completes; the "
    "store's quarantine counters name the root cause)",
)
KVSWAP_BYTES = _counter(
    "tftpu_kvswap_bytes_total",
    "Bytes of KV page payload written to the block store by "
    "per-sequence swap-out",
)
PREFIX_HITS = _counter(
    "tftpu_prefix_cache_hits_total",
    "Prompt admissions that reused at least one shared prefix page "
    "(those prefill chunks were skipped entirely)",
)
PREFIX_MISSES = _counter(
    "tftpu_prefix_cache_misses_total",
    "Prompt admissions that found no shared prefix page (cold prefill "
    "ran for the whole prompt; only counted when the cache is armed)",
)
PREFIX_SHARED_PAGES = _gauge(
    "tftpu_prefix_cache_shared_pages",
    "Pages currently published read-only in the content-addressed "
    "prefix cache (any refcount, including cached-but-unreferenced)",
)
PREFIX_EVICTIONS = _counter(
    "tftpu_prefix_cache_evictions_total",
    "Shared prefix pages reclaimed to the free list under allocation "
    "pressure (only refcount-0 pages are eligible, LRU-first)",
)


# -- registered-query result cache (tftpu_result_cache_*, ISSUE 20) --------
# A registered relational endpoint's health is a hit rate (repeat
# queries served from the (plan fingerprint, content digest) keyed
# store without executing), an invalidation rate (how often the input
# partition moved under it), and the incremental split: chunks whose
# cached partials folded vs full recomputes, BY REASON — "the cache
# degraded" must always name why. Per-endpoint cardinality stays out
# of the registry (TFL003); Server.stats() carries the per-endpoint
# rows.

#: Why a registered query ran a counted full recompute (closed set).
#: cold = first sight of this input partition (nothing cached yet);
#: invalidated = a previously-seen part changed or disappeared, so the
#: cached partials no longer describe the table; ineligible = the plan
#: declined caching or incremental maintenance (TFG114 names the
#: stage); corrupt_partial = a cached chunk partial failed CRC and
#: that chunk re-executed (quarantined, never served).
RECOMPUTE_REASONS: Tuple[str, ...] = (
    "cold", "invalidated", "ineligible", "corrupt_partial",
)

RESULT_CACHE_HITS = _counter(
    "tftpu_result_cache_hits_total",
    "Registered-query requests served from the result cache (memo or "
    "persistent store) — no plan execution, no chunk read",
)
RESULT_CACHE_MISSES = _counter(
    "tftpu_result_cache_misses_total",
    "Registered-query requests whose (plan fingerprint, content "
    "digest) key was absent from the result cache",
)
RESULT_CACHE_INVALIDATIONS = _counter(
    "tftpu_result_cache_invalidations_total",
    "Input-partition digest changes observed by registered queries "
    "(the previous cached result can no longer serve; appends refresh "
    "incrementally, rewrites/removals force full recompute)",
)
RESULT_CACHE_BYTES = _counter(
    "tftpu_result_cache_bytes_total",
    "Bytes of result/partial tables published into the persistent "
    "result store by registered queries",
)
RESULT_CACHE_CHUNKS_FOLDED = _counter(
    "tftpu_result_cache_chunks_folded_total",
    "Scan chunks whose CACHED aggregate partials were folded into a "
    "registered query's refresh instead of being re-read and "
    "re-executed (the incremental-maintenance payoff counter)",
)
RESULT_CACHE_RECOMPUTES: Dict[str, Counter] = {
    r: _counter(
        "tftpu_result_cache_recomputes_total",
        "Registered-query executions that could not serve from cached "
        "results/partials, by reason (cold = first sight of the input "
        "partition, invalidated = a seen part changed/disappeared, "
        "ineligible = the plan declined caching/incremental [TFG114 "
        "names the stage], corrupt_partial = a damaged cached partial "
        "was quarantined and its chunk re-executed)",
        labels={"reason": r},
    )
    for r in RECOMPUTE_REASONS
}


def result_recompute(reason: str) -> Counter:
    """The pre-registered recompute counter for ``reason``."""
    return RESULT_CACHE_RECOMPUTES[reason]


def rejected(reason: str) -> Counter:
    """The pre-registered rejection counter for ``reason``."""
    return REJECTED[reason]


# -- hardened HTTP ingress (tftpu_serving_rejections_total, ISSUE 13) -------
# Transport-level refusals happen BEFORE a request reaches admission
# control, so they cannot ride the admission counter above: an oversized
# body, a slow-read connection, or a connection past the concurrency
# bound never becomes a queued request. A separate counter (the name the
# fleet issue assigns) keeps the two shed layers distinguishable on a
# dashboard: rejected_total spikes mean the batcher is full,
# rejections_total spikes mean the transport is under attack/overload.

#: Why the HTTP layer refused a connection/body (closed set).
HTTP_REJECT_REASONS: Tuple[str, ...] = (
    "body_too_large", "read_timeout", "conn_limit",
)

HTTP_REJECTIONS: Dict[str, Counter] = {
    r: _counter(
        "tftpu_serving_rejections_total",
        "HTTP ingress refusals before admission, by reason "
        "(body_too_large = request body over the ingress byte limit "
        "[413], read_timeout = connection read stalled past the "
        "per-connection timeout [408/close], conn_limit = concurrent "
        "connection bound reached [503])",
        labels={"reason": r},
    )
    for r in HTTP_REJECT_REASONS
}

IDEMPOTENT_DEDUP = _counter(
    "tftpu_serving_idempotent_dedup_total",
    "Submissions deduplicated by idempotency key (a redriven or "
    "retried dispatch joined the original request's future instead of "
    "executing again)",
)


def http_rejected(reason: str) -> Counter:
    """The pre-registered ingress rejection counter for ``reason``."""
    return HTTP_REJECTIONS[reason]


# -- fleet router (tftpu_router_*, ISSUE 13) --------------------------------
# The router is the one place that sees the whole fleet: how many
# replicas are routable, how often a dispatch had to be redriven to a
# survivor, and what the client-visible latency is THROUGH failures.
# Per-replica cardinality stays out of the registry (TFL003) — ranks
# ride flight records (router.* family) and the router's healthz body.

#: Why the router refused an ingress request (closed set).
ROUTER_REJECT_REASONS: Tuple[str, ...] = ("no_replica", "deadline")

ROUTER_REQUESTS = _counter(
    "tftpu_router_requests_total",
    "Ingress requests admitted by the fleet router",
)
ROUTER_REDRIVES = _counter(
    "tftpu_router_redrives_total",
    "Dispatches redriven to a surviving replica after the chosen "
    "replica failed mid-request (same idempotency key, original "
    "deadline)",
)
ROUTER_REJECTED: Dict[str, Counter] = {
    r: _counter(
        "tftpu_router_rejected_total",
        "Ingress requests the router refused, by reason (no_replica = "
        "no live non-draining replica, deadline = the request's budget "
        "lapsed before any dispatch succeeded)",
        labels={"reason": r},
    )
    for r in ROUTER_REJECT_REASONS
}
ROUTER_REPLICAS_LIVE = _gauge(
    "tftpu_router_replicas_live",
    "Replicas the router currently considers routable (state=running, "
    "fresh heartbeat, healthz reachable)",
)
ROUTER_REPLICA_DEAD = _counter(
    "tftpu_router_replica_dead_total",
    "Replicas newly marked dead by the router/fleet (process exit, "
    "stale heartbeat, or repeated scrape failure)",
)
ROUTER_REPLICA_RESTARTS = _counter(
    "tftpu_router_replica_restarts_total",
    "Replica processes respawned by the serving fleet supervisor "
    "after a death",
)
ROUTER_DISPATCH_SECONDS = _histogram(
    "tftpu_router_dispatch_seconds",
    "Wall-clock of one router->replica dispatch attempt (successful "
    "or failed; redrives observe once per attempt)",
    buckets=LATENCY_BUCKETS,
)
ROUTER_REQUEST_LATENCY = _histogram(
    "tftpu_router_request_latency_seconds",
    "Ingress request wall-clock through the router (admission to "
    "relayed reply, including any redrives) — the fleet bench's p99 "
    "gate reads this",
    buckets=LATENCY_BUCKETS,
)


def router_rejected(reason: str) -> Counter:
    """The pre-registered router rejection counter for ``reason``."""
    return ROUTER_REJECTED[reason]

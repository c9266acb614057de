"""Online serving: continuous batching over the verb engine (ISSUE 9).

The first latency-shaped subsystem in a throughput-shaped codebase:
an async request front (:class:`Server` — ``submit()`` returns
futures; :func:`serve_http` is the thin HTTP adapter) that admits
single-row/small-batch requests against registered Programs, coalesces
them with a continuous batcher into the executor's power-of-two row
buckets (the SAME ladder ``compilecache.warmup`` precompiles, so every
flush is an AOT-cache hit), dispatches through the existing executor,
and scatters per-request results back with padding-row masking.

Guarantees, stated once:

* **bit-identity** — a coalesced request's rows equal its solo
  dispatch exactly (row-independent vmapped programs; padding rows are
  sliced off before scatter);
* **zero steady-state compiles** — a warmed server never hits XLA
  under any mix of admissible request sizes;
* **boundedness** — admission past the queue bound sheds with a
  counted rejection (never a hang), per-request deadlines follow
  ``RetryPolicy.deadline_s`` total-elapsed semantics, and shutdown
  drains gracefully;
* **observability** — ``tftpu_serving_*`` metrics, ``serving.flush``
  trace spans and ``serving.request`` async pairs, and flight-recorder
  ``serving.*``
  records ride the standard registry/tracer/black-box surfaces.

ISSUE 11 adds the **iterative decode engine** on top
(:class:`DecodeEngine` via ``Server.register_decode``): token-level
continuous batching over a block-paged int8 KV pool
(:class:`PagedKVPool`) — sequence slots join/leave the running batch
every step, the pool preempts (evict + requeue + bit-identical resume)
when full, and the same four contracts hold per token instead of per
flush. See docs/serving.md ("Iterative decode").

ISSUE 13 scales it out: :class:`ServingFleet` runs N supervised
replica servers (heartbeats, per-replica crash restart, ONE shared
compile store so restarts warm with zero XLA compiles) behind a
:class:`Router` ingress that load-balances by queue depth, routes only
to ``state=running`` replicas, and redrives failed dispatches to
survivors under the original deadline with idempotency-key dedup —
every admitted request gets exactly one response through a ``kill -9``.
See docs/serving.md ("Scale-out").

ISSUE 20 serves the relational plane itself: ``Server.register_query``
turns a lazy map→join→aggregate pipeline over a growing scan directory
into an endpoint — fronted by a (plan-fingerprint × input-content-
digest) result cache with counted invalidation, with algebraic
aggregates maintained incrementally per arriving chunk (bit-identical
to full recompute by exact associativity; anything outside the
contract degrades to a COUNTED full recompute and a TFG114
diagnostic). See docs/serving.md ("Registered queries").
"""

from __future__ import annotations

from . import metrics  # noqa: F401  (registers tftpu_serving_* at import)
from .batcher import (  # noqa: F401
    ContinuousBatcher,
    DeadlineExceededError,
    RejectedError,
    ResultFuture,
    ServingError,
)
from .decode import DecodeConfig, DecodeEngine  # noqa: F401
from .fleet import FleetDegradedError, ServingFleet  # noqa: F401
from .http import serve_http  # noqa: F401
from .replica import serve_replica  # noqa: F401
from .router import Router, RouterConfig  # noqa: F401
from .kvpool import (  # noqa: F401
    PagedKVPool,
    PoolAccountingError,
    PoolExhaustedError,
)
from .query import (  # noqa: F401
    QueryEndpoint,
    QuerySource,
    query_cache_events,
)
from .server import (  # noqa: F401
    Endpoint,
    Server,
    ServingConfig,
    UnknownEndpointError,
)

__all__ = [
    "Server",
    "ServingConfig",
    "Endpoint",
    "ContinuousBatcher",
    "ResultFuture",
    "ServingError",
    "RejectedError",
    "DeadlineExceededError",
    "UnknownEndpointError",
    "DecodeConfig",
    "DecodeEngine",
    "PagedKVPool",
    "PoolAccountingError",
    "PoolExhaustedError",
    "QueryEndpoint",
    "QuerySource",
    "query_cache_events",
    "serve_http",
    "serve_replica",
    "Router",
    "RouterConfig",
    "ServingFleet",
    "FleetDegradedError",
    "metrics",
]

"""Replica-side runner for the serving fleet (ISSUE 13).

One fleet replica = one ordinary single-process
:class:`~tensorframes_tpu.serving.Server` (PR 9/11 — continuous
batcher, warmup ladder, iterative decode) wrapped with exactly the
pieces the fleet layer above needs:

* a **heartbeat publisher** into the fleet rendezvous dir
  (``TFTPU_FLEET_DIR``; the same
  :class:`~tensorframes_tpu.resilience.fleet.Heartbeater` PR 8 fleets
  use — started BEFORE warmup, so a replica compiling for seconds reads
  alive, not dead);
* a **replica card** — one atomic JSON file publishing this replica's
  HTTP address/pid/attempt, the service-discovery record the router
  scans (heartbeats say *alive*, cards say *where*);
* the **hardened HTTP sidecar** (:func:`~tensorframes_tpu.serving.serve_http`)
  whose ``/healthz`` carries the lifecycle state the router keys on and
  whose ``/admin/drain`` is the rolling-restart hook;
* a supervised **main loop** carrying the ``serving.replica`` kill
  chaos site — a drill can SIGKILL any replica deterministically — and
  a SIGTERM handler that drains instead of dropping in-flight work.

The shared-store contract rides the environment: the fleet arms
``JAX_COMPILATION_CACHE_DIR`` for every replica, so the first replica's
warmup publishes each ladder executable once and every later (or
RESTARTED) replica's warmup is pure store hits — **zero XLA compiles**,
the property the fleet asserts over this replica's healthz process
counters.

``python -m tensorframes_tpu.serving.replica_main --demo`` runs a
deterministic built-in endpoint (``score``: ``y = tanh(x @ w)`` with
seed-0 weights, identical in every replica — a redriven request gets
the same answer from any survivor), which is what the fleet tests,
bench, and chaos drill spawn.
"""

from __future__ import annotations

import os
import signal
import threading
import time
from typing import Dict, Optional

from ..observability import context as _context
from ..observability import flight as _flight
from ..resilience.faults import kill_point
from ..resilience.fleet import (
    Heartbeater,
    read_latest_records,
    write_json_atomic,
)
from ..utils import get_logger
from .http import serve_http
from .server import Server

logger = get_logger(__name__)

__all__ = [
    "publish_card", "read_cards", "card_addr", "serve_replica",
    "demo_server", "main",
]


# ---------------------------------------------------------------------------
# replica cards (service discovery: heartbeats say alive, cards say where)
# ---------------------------------------------------------------------------

def _card_path(directory: str, run_id: str, rank: int) -> str:
    return os.path.join(directory, f"replica_{run_id}_p{rank}.json")


def publish_card(
    directory: str,
    *,
    rank: int,
    addr: str,
    port: int,
    run_id: Optional[str] = None,
    attempt: int = 0,
) -> str:
    """Atomically publish this replica's address card into the
    rendezvous dir (tmp-write + rename, like heartbeats — a router scan
    never sees a torn card). A restarted replica overwrites its rank's
    card with the new ephemeral port."""
    run_id = run_id or _context.run_id()
    rec = {
        "run_id": run_id,
        "rank": int(rank),
        "addr": str(addr),
        "port": int(port),
        "pid": os.getpid(),
        "attempt": int(attempt),
        "ts": time.time(),
    }
    os.makedirs(directory, exist_ok=True)
    return write_json_atomic(_card_path(directory, run_id, rank), rec)


def card_addr(card: dict) -> str:
    """The ``host:port`` dial address a replica card advertises — ONE
    formatting of the card schema, shared by the router's discovery
    and the fleet's drain path."""
    return f"{card.get('addr', '127.0.0.1')}:{card['port']}"


def read_cards(
    directory: str, run_id: Optional[str] = None
) -> Dict[int, dict]:
    """Every published replica card (``{rank: record}``), filtered to
    ``run_id`` when given — the same tolerant newest-per-rank read the
    heartbeat files use (one implementation, resilience/fleet.py)."""
    pattern = (
        f"replica_{run_id}_p*.json" if run_id else "replica_*_p*.json"
    )
    return read_latest_records(
        directory, pattern, run_id, rank_field="rank"
    )


# ---------------------------------------------------------------------------
# the replica main loop
# ---------------------------------------------------------------------------

def serve_replica(
    server: Server,
    *,
    addr: str = "127.0.0.1",
    port: int = 0,
    fleet_dir: Optional[str] = None,
    rank: Optional[int] = None,
    poll_s: float = 0.05,
    http_kwargs: Optional[dict] = None,
) -> int:
    """Run ``server`` as one fleet replica until it is drained or
    terminated; returns the exit code (0 = clean). Blocks the calling
    thread — this IS the replica process's main loop.

    Order matters: the heartbeat starts **before** ``server.start()``
    (warmup can take seconds; the supervisor must read the replica as
    alive-but-starting, and the router reads ``state=starting`` from
    healthz and keeps traffic away), the card publishes **after** the
    HTTP port is bound (a card must never point at an unbound port).
    SIGTERM triggers a graceful drain (in-flight + queued work
    completes, state walks ``draining`` → ``stopped``); the loop also
    exits when an external ``POST /admin/drain`` lands — either way the
    final heartbeat is a clean ``stopped`` beat. The loop carries the
    ``serving.replica`` kill site: an armed
    :class:`~tensorframes_tpu.resilience.faults.KillRank` SIGKILLs this
    replica deterministically (the fleet-chaos drill's trigger)."""
    fleet_dir = fleet_dir or os.environ.get("TFTPU_FLEET_DIR") or None
    rank = _context.process_index() if rank is None else int(rank)
    attempt = int(os.environ.get("TFTPU_FLEET_ATTEMPT", "0") or 0)
    hb: Optional[Heartbeater] = None
    if fleet_dir:
        hb = Heartbeater(fleet_dir, rank=rank).start()
    stop_evt = threading.Event()

    def _on_term(signum, frame):  # noqa: ARG001 - signal API
        logger.info("replica %d: SIGTERM — draining", rank)
        stop_evt.set()

    try:
        signal.signal(signal.SIGTERM, _on_term)
    except ValueError:  # pragma: no cover - non-main thread (tests)
        pass
    httpd = None
    rc = 0
    try:
        server.start()  # warm (store hits on a warmed fleet) + open
        httpd = serve_http(server, port=port, addr=addr,
                           **(http_kwargs or {}))
        bound_port = int(httpd.server_address[1])
        if fleet_dir:
            publish_card(
                fleet_dir, rank=rank, addr=addr, port=bound_port,
                attempt=attempt,
            )
        _flight.record(
            "serving.replica_up", rank=rank, port=bound_port,
            attempt=attempt, endpoints=server.endpoints(),
        )
        logger.info(
            "replica %d up on %s:%d (attempt %d)", rank, addr,
            bound_port, attempt,
        )
        while True:
            # the kill chaos site: armed KillRank → SIGKILL self, the
            # deterministic stand-in for an OOM-killed/preempted replica
            kill_point("serving.replica")
            if stop_evt.is_set():
                server.stop(drain=True)
                break
            if server.state == "stopped":
                break  # drained externally (POST /admin/drain)
            time.sleep(poll_s)
    except Exception as e:  # pragma: no cover - crash path
        logger.error("replica %d failed: %s", rank, e)
        _flight.record(
            "serving.replica_error", rank=rank,
            error=type(e).__name__, message=str(e),
        )
        rc = 1
    finally:
        if httpd is not None:
            httpd.shutdown()
        if hb is not None:
            # graceful final beat IFF we exited cleanly: a crash path
            # must read as dead, not departed
            hb.stop(graceful=(rc == 0))
        _flight.record("serving.replica_down", rank=rank, rc=rc)
    return rc


# ---------------------------------------------------------------------------
# the demo replica (what fleet tests / bench / drills spawn)
# ---------------------------------------------------------------------------

def demo_server(width: int = 8, max_batch_rows: int = 8,
                max_latency_s: float = 0.002,
                max_queue_rows: int = 1024) -> Server:
    """A deterministic one-endpoint server: ``score`` computes
    ``y = tanh(x @ w)`` with seed-0 weights — every replica holds the
    SAME weights, so a redriven request is answered identically by any
    survivor (the property the redrive tests pin)."""
    import jax.numpy as jnp
    import numpy as np

    import tensorframes_tpu as tfs
    from .server import ServingConfig

    rng = np.random.default_rng(0)
    w = (rng.standard_normal((width, width)) / np.sqrt(width)).astype(
        np.float32
    )
    schema = tfs.Schema([
        tfs.ColumnInfo(
            "x", tfs.dtypes.float32, tfs.Shape((tfs.Unknown, width))
        )
    ])
    holder = type("S", (), {"schema": schema})()
    prog = tfs.compile_program(
        lambda x: {"y": jnp.tanh(x @ w)}, holder, block=False
    )
    srv = Server(ServingConfig(
        max_batch_rows=max_batch_rows, max_latency_s=max_latency_s,
        max_queue_rows=max_queue_rows,
    ))
    srv.register("score", prog)
    return srv


def main(argv=None) -> int:
    """``python -m tensorframes_tpu.serving.replica_main [--demo]`` —
    run the demo replica under the current fleet environment (the entry
    lives in ``replica_main.py``, which the package never imports, so
    ``-m`` does not double-execute this module). Chaos arming via env
    (for drills — deterministic, no code in the victim):
    ``TFTPU_SERVING_CHAOS_KILL_AFTER=<n>`` SIGKILLs this replica after
    *n* main-loop beats, on attempt 0 only (the restarted incarnation
    must survive), when this rank matches
    ``TFTPU_SERVING_CHAOS_KILL_RANK`` (default 1)."""
    import argparse
    import contextlib

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--demo", action="store_true",
                        help="serve the built-in deterministic endpoint")
    parser.add_argument("--width", type=int, default=8)
    parser.add_argument("--max-batch-rows", type=int, default=8)
    parser.add_argument("--port", type=int, default=0)
    parser.add_argument("--addr", default="127.0.0.1")
    args = parser.parse_args(argv)
    if not args.demo:
        parser.error("only --demo is runnable standalone; real apps "
                     "call serve_replica(server) from their own worker")
    # an entry point that runs on the chip: place the compile cache
    # (the fleet's environment, else the checkout's fixed directory)
    from ..config import use_compile_cache

    use_compile_cache(entry_point=True)
    stack = contextlib.ExitStack()
    kill_after = int(os.environ.get("TFTPU_SERVING_CHAOS_KILL_AFTER", 0))
    kill_rank = int(os.environ.get("TFTPU_SERVING_CHAOS_KILL_RANK", 1))
    attempt = int(os.environ.get("TFTPU_FLEET_ATTEMPT", "0") or 0)
    if (kill_after > 0 and attempt == 0
            and _context.process_index() == kill_rank):
        from ..resilience import faults

        stack.enter_context(faults.inject(
            "serving.replica", faults.KillRank, after=kill_after,
            max_times=1,
        ))
    with stack:
        srv = demo_server(
            width=args.width, max_batch_rows=args.max_batch_rows,
        )
        return serve_replica(srv, addr=args.addr, port=args.port)

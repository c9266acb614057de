"""Persistent AOT executable cache + warmup (ISSUE 5).

Every process used to pay the full trace + XLA compile on the first
dispatch per feed-shape key (2.8s for Inception-299, 1.4s for
BERT-base on the bench record — 20-40s on real TPUs), and the
executor's in-memory jit cache died with the process. This subsystem
makes compiled executables durable and shareable:

* :mod:`.fingerprint` — stable content hash of (jaxpr + consts +
  feed-shape bucket + dtype policy + backend/device + donation/hoist
  flags + jax version); no Python ``hash()``, survives restarts;
* :mod:`.store` — size-bounded on-disk executable store (CRC-checked,
  fsync-then-rename publish, LRU eviction) the executor consults on
  every jit-cache miss: hit ⇒ deserialize in milliseconds instead of
  compiling; any store problem degrades to a normal compile;
* :mod:`.warmup` — ``tfs.warmup(...)`` precompiles the expected shape
  buckets ahead of traffic, optionally replaying the store's recorded
  miss manifest;
* ``python -m tensorframes_tpu.compilecache`` — stats / warm / prune /
  verify (see docs/compilecache.md).

Disabled by default; ``TFTPU_COMPILE_CACHE=/dir`` (or
``configure(compilation_cache_dir=...)``) turns it on.
"""

from .fingerprint import FORMAT_VERSION, program_fingerprint  # noqa: F401
from .store import CompileCacheStore, active_store, store_for  # noqa: F401
from .warmup import (  # noqa: F401
    WarmupReport,
    decode_slot_buckets,
    decode_warmup_grid,
    packed_prefill_buckets,
    partitioner_row_counts,
    serving_row_buckets,
    warm_program,
    warmup,
)

__all__ = [
    "FORMAT_VERSION",
    "CompileCacheStore",
    "WarmupReport",
    "active_store",
    "decode_slot_buckets",
    "decode_warmup_grid",
    "packed_prefill_buckets",
    "partitioner_row_counts",
    "program_fingerprint",
    "serving_row_buckets",
    "store_for",
    "warm_program",
    "warmup",
]

"""Size-bounded on-disk store of serialized XLA executables.

One entry per fingerprint (:mod:`.fingerprint`): a self-describing
file ``<fp>.xc`` holding a JSON header plus the payload from jax's AOT
``serialize_executable``. The executor consults the store on a
jit-cache miss **before lowering**: a hit deserializes the executable
(milliseconds) instead of paying trace-to-HLO + XLA compile (seconds
to minutes on TPU). The store is **off by default** — it activates
only when ``TFTPU_COMPILE_CACHE`` / ``configure(compilation_cache_dir=
...)`` names a directory — and every store problem degrades to a
normal compile: a cache failure must never fail a dispatch.

Durability & concurrency (same discipline as checkpoint.py):

* entries publish via write-temp → fsync → atomic ``os.replace`` —
  readers never observe a torn entry, and two processes racing to
  write the same fingerprint both succeed (last replace wins; the
  content is identical by construction);
* the payload carries a CRC32; corrupt/truncated entries are detected
  on load, counted, quarantined (unlinked), and fall back to a fresh
  compile;
* eviction is LRU by bytes (mtime, refreshed on hit) against
  ``config.compile_cache_max_bytes``.

Treedefs are not pickled: the header stores a JSON *skeleton* of the
call's in/out pytrees (dict/list/tuple of leaf markers), rebuilt into
real ``PyTreeDef``\\ s at load time — version-safe where pickling jax
internals is not. Entries whose trees cannot round-trip the skeleton
codec are never stored.

A ``manifest.jsonl`` beside the entries records the feed shapes of
every store miss, so :func:`tensorframes_tpu.compilecache.warmup` can
replay yesterday's traffic shapes ahead of today's.
"""

from __future__ import annotations

import json
import os
import struct
import threading
import time
import zlib
from typing import Dict, List, Optional, Sequence, Tuple

from ..observability.metrics import counter as _counter
from ..observability.metrics import gauge as _gauge
from ..observability.metrics import histogram as _histogram
from ..utils import get_logger
from .fingerprint import FORMAT_VERSION

logger = get_logger(__name__)

__all__ = ["CompileCacheStore", "active_store", "store_for"]

_MAGIC = b"TFXC"
_ENTRY_SUFFIX = ".xc"

# Registered at import (TFL003): a process that never enables the
# store still expositions the whole family at 0.
_HITS = _counter(
    "tftpu_compilecache_hits_total",
    "Executables served from the persistent AOT store instead of compiled",
)
_MISSES = _counter(
    "tftpu_compilecache_misses_total",
    "Store lookups that found no entry (a fresh compile follows)",
)
_LOAD_SECONDS = _histogram(
    "tftpu_compilecache_load_seconds",
    "Wall-clock to read + CRC-check + deserialize one stored executable",
)
_BYTES_WRITTEN = _counter(
    "tftpu_compilecache_bytes_total",
    "Bytes of serialized executables written to the persistent store",
)
_STORE_BYTES = _gauge(
    "tftpu_compilecache_store_bytes",
    "Current total size of the persistent store directory's entries",
)
_EVICTIONS = _counter(
    "tftpu_compilecache_evictions_total",
    "Entries removed by LRU eviction against the byte bound",
)
_FALLBACKS = {
    reason: _counter(
        "tftpu_compilecache_fallback_total",
        "Store operations abandoned in favor of a normal compile, by reason",
        labels={"reason": reason},
    )
    for reason in (
        "corrupt", "deserialize", "store_error", "tree_unsupported",
        "unavailable", "unfingerprintable",
    )
}


def note_unfingerprintable() -> None:
    """Count a dispatch that skipped the store because its program
    could not be fingerprinted — e.g. a plain-form baked const whose
    values cannot be hashed (a non-addressable multi-process global
    capture). The dispatch still AOT-compiles in-process; it just never
    publishes or hits, which on a fleet means every rank of every
    restart recompiles — this counter is how that shows up instead of
    staying a debug-level log line."""
    _FALLBACKS["unfingerprintable"].inc()

_STORE_LOCK = threading.Lock()
_STORES: Dict[Tuple[str, int], Optional["CompileCacheStore"]] = {}


# ---------------------------------------------------------------------------
# treedef ⇄ JSON skeleton codec
# ---------------------------------------------------------------------------

def _encode_skeleton(obj) -> object:
    """Pytree container skeleton → JSON-able form. Leaves become the
    marker 0; dict (str keys) / list / tuple / namedtuple / None
    containers and the package's ``QuantizedTensor`` node are supported
    — any other pytree node reads as a leaf, fails the round-trip check
    in :meth:`CompileCacheStore.put`, and the entry is not stored.
    Namedtuples (optax optimizer states — the generic ``aot_jit`` entry
    serializes whole train steps) record their importable class path
    and are reconstructed at load; a class that no longer imports
    degrades to a fresh compile like any other defect."""
    if isinstance(obj, dict):
        if not all(isinstance(k, str) for k in obj):
            raise TypeError("non-string dict keys in pytree")
        return {"t": "d", "k": sorted(obj),
                "v": [_encode_skeleton(obj[k]) for k in sorted(obj)]}
    if isinstance(obj, tuple) and hasattr(obj, "_fields"):
        cls = type(obj)
        if cls.__module__ in (None, "__main__"):
            raise TypeError(
                f"namedtuple {cls.__name__} is not importable cross-process"
            )
        return {"t": "nt", "c": f"{cls.__module__}:{cls.__qualname__}",
                "v": [_encode_skeleton(x) for x in obj]}
    if isinstance(obj, tuple):
        return {"t": "t", "v": [_encode_skeleton(x) for x in obj]}
    if isinstance(obj, list):
        return {"t": "l", "v": [_encode_skeleton(x) for x in obj]}
    if obj is None:
        return {"t": "n"}
    from ..ops.quantize import QuantizedTensor

    if isinstance(obj, QuantizedTensor):
        # the package's own pytree node: int8-quantized weight trees
        # are what the serving decode engine's executables take
        return {"t": "qt", "v": [_encode_skeleton(obj.q),
                                 _encode_skeleton(obj.scale)]}
    return 0  # leaf


def _resolve_namedtuple(path: str):
    import importlib

    mod_name, _, qual = path.partition(":")
    obj = importlib.import_module(mod_name)
    for part in qual.split("."):
        obj = getattr(obj, part)
    if not (isinstance(obj, type) and issubclass(obj, tuple)
            and hasattr(obj, "_fields")):
        raise TypeError(f"{path} is not a namedtuple class")
    return obj


def _decode_skeleton(enc) -> object:
    if enc == 0:
        return 0
    t = enc["t"]
    if t == "d":
        return {k: _decode_skeleton(v) for k, v in zip(enc["k"], enc["v"])}
    if t == "nt":
        cls = _resolve_namedtuple(enc["c"])
        return cls(*(_decode_skeleton(v) for v in enc["v"]))
    if t == "t":
        return tuple(_decode_skeleton(v) for v in enc["v"])
    if t == "l":
        return [_decode_skeleton(v) for v in enc["v"]]
    if t == "n":
        return None
    if t == "qt":
        from ..ops.quantize import QuantizedTensor

        return QuantizedTensor(*(_decode_skeleton(v) for v in enc["v"]))
    raise ValueError(f"unknown skeleton tag {t!r}")


def _treedef_to_skeleton(treedef) -> object:
    import jax

    skeleton = jax.tree_util.tree_unflatten(
        treedef, [0] * treedef.num_leaves
    )
    return _encode_skeleton(skeleton)


def _skeleton_to_treedef(enc):
    import jax

    return jax.tree_util.tree_structure(_decode_skeleton(enc))


# ---------------------------------------------------------------------------
# the store
# ---------------------------------------------------------------------------

class CompileCacheStore:
    """One directory of ``<fingerprint>.xc`` entries + manifest.jsonl."""

    def __init__(self, root: str, max_bytes: int = 0):
        self.root = root
        self.max_bytes = int(max_bytes)
        self.manifest_path = os.path.join(root, "manifest.jsonl")
        self._manifest_seen: set = set()
        self._lock = threading.Lock()
        # fingerprints whose SHARED entry failed to deserialize on this
        # rank (multi-process only): the recompile publishes under a
        # rank-scoped key instead, and later lookups prefer it — the
        # "per-rank disambiguation only where XLA partitions differ"
        # escape hatch. Fleets whose ranks load each other's entries
        # (the SPMD norm: one global module) never populate this.
        self._rank_incompatible: set = set()
        os.makedirs(root, exist_ok=True)

    # -- paths --------------------------------------------------------------

    def _path(self, fp: str) -> str:
        if not fp or any(c in fp for c in "/\\."):
            raise ValueError(f"bad fingerprint {fp!r}")
        return os.path.join(self.root, fp + _ENTRY_SUFFIX)

    def _entries(self) -> List[Tuple[str, float, int]]:
        """[(path, mtime, size)] of current entries, oldest first."""
        out = []
        try:
            names = os.listdir(self.root)
        except OSError:
            return []
        for name in names:
            if not name.endswith(_ENTRY_SUFFIX):
                continue
            p = os.path.join(self.root, name)
            try:
                st = os.stat(p)
            except OSError:
                continue  # raced with an eviction elsewhere
            out.append((p, st.st_mtime, st.st_size))
        out.sort(key=lambda e: e[1])
        return out

    # -- read ---------------------------------------------------------------

    def _read_entry(self, path: str) -> Tuple[dict, bytes]:
        """Parse + CRC-check one entry file; raises on any defect."""
        with open(path, "rb") as f:
            blob = f.read()
        if blob[:4] != _MAGIC:
            raise ValueError("bad magic")
        (version,) = struct.unpack("<I", blob[4:8])
        if version != FORMAT_VERSION:
            raise ValueError(f"format version {version}")
        (hlen,) = struct.unpack("<Q", blob[8:16])
        header = json.loads(blob[16:16 + hlen].decode("utf-8"))
        payload = blob[16 + hlen:]
        if len(payload) != header["payload_bytes"]:
            raise ValueError("truncated payload")
        if zlib.crc32(payload) != header["payload_crc32"]:
            raise ValueError("payload CRC mismatch")
        return header, payload

    @staticmethod
    def _rank_fp(fp: str, rank: int) -> str:
        return f"{fp}_r{int(rank)}"

    def get(self, fp: str, rank: Optional[int] = None):
        """Load and deserialize the executable for ``fp``. Returns the
        loaded callable or None (miss / any defect — defects are
        counted, quarantined, and never raised).

        ``rank`` (multi-process fleets pass their process index) arms
        per-rank disambiguation: a rank-scoped entry ``<fp>_r<rank>``
        is preferred when present, and a SHARED entry that fails to
        deserialize on this rank is left in place for the peers that
        CAN load it (quarantining would thrash the fleet) — this rank
        remembers the incompatibility and republishes rank-scoped."""
        if rank is not None:
            scoped = self._load_one(self._rank_fp(fp, rank), shared=False,
                                    count_miss=False)
            if scoped is not None:
                return scoped
        loaded = self._load_one(fp, shared=rank is not None)
        if loaded is None and rank is not None and os.path.exists(
            self._path(fp)
        ):
            with self._lock:
                self._rank_incompatible.add(fp)
        return loaded

    def _load_one(self, fp: str, shared: bool, count_miss: bool = True):
        path = self._path(fp)
        if not os.path.exists(path):
            if count_miss:
                _MISSES.inc()
            return None
        t0 = time.perf_counter()
        try:
            header, payload = self._read_entry(path)
        except Exception as e:
            logger.warning("compile cache entry %s unreadable (%s); "
                           "quarantining, falling back to compile",
                           os.path.basename(path), e)
            _FALLBACKS["corrupt"].inc()
            self._quarantine(path)
            return None
        try:
            from jax.experimental.serialize_executable import (
                deserialize_and_load,
            )

            import jax

            # load onto the devices the executable was compiled for:
            # the default is every local device, and a one-device
            # executable then demands one argument shard per device
            local = {int(d.id): d for d in jax.local_devices()}
            loaded = deserialize_and_load(
                payload,
                _skeleton_to_treedef(header["in_skel"]),
                _skeleton_to_treedef(header["out_skel"]),
                execution_devices=[local[i] for i in header["device_ids"]],
            )
        except Exception as e:
            # structurally sound but not loadable here (runtime drift,
            # incompatible executable): fall back. Single-process drops
            # the entry so a fresh compile re-publishes a loadable one;
            # a fleet rank leaves the shared entry for its peers and
            # goes rank-scoped instead (see get()).
            logger.warning("compile cache entry %s failed to "
                           "deserialize (%s); falling back to compile",
                           os.path.basename(path), e)
            _FALLBACKS["deserialize"].inc()
            if not shared:
                self._quarantine(path)
            return None
        _HITS.inc()
        _LOAD_SECONDS.observe(time.perf_counter() - t0)
        try:
            os.utime(path)  # LRU touch
        except OSError:
            pass
        return loaded

    def _quarantine(self, path: str) -> None:
        try:
            os.unlink(path)
        except OSError:
            pass

    # -- write --------------------------------------------------------------

    def put(self, fp: str, compiled, meta: Optional[dict] = None,
            rank: Optional[int] = None) -> bool:
        """Serialize + publish one executable. Best-effort: returns
        False (and counts the reason) instead of raising. With ``rank``
        given and ``fp`` previously observed rank-incompatible (a peer's
        shared entry would not deserialize here — see :meth:`get`), the
        entry publishes under the rank-scoped key so this rank's restart
        hits without disturbing the peers' shared entry."""
        if rank is not None:
            with self._lock:
                scoped = fp in self._rank_incompatible
            if scoped:
                fp = self._rank_fp(fp, rank)
        try:
            from jax.experimental.serialize_executable import serialize

            payload, in_tree, out_tree = serialize(compiled)
            try:
                in_skel = _treedef_to_skeleton(in_tree)
                out_skel = _treedef_to_skeleton(out_tree)
                if (_skeleton_to_treedef(in_skel) != in_tree
                        or _skeleton_to_treedef(out_skel) != out_tree):
                    raise TypeError("treedef does not round-trip")
            except Exception as e:
                logger.debug("not storing %s: %s", fp, e)
                _FALLBACKS["tree_unsupported"].inc()
                return False
            header = dict(meta or {})
            header.update({
                "fingerprint": fp,
                "created": round(time.time(), 3),
                "payload_bytes": len(payload),
                "payload_crc32": zlib.crc32(payload),
                "in_skel": in_skel,
                "out_skel": out_skel,
                "device_ids": [
                    int(d.id) for d in
                    compiled.runtime_executable().local_devices()
                ],
            })
            hbytes = json.dumps(header, sort_keys=True).encode("utf-8")
            blob = (_MAGIC + struct.pack("<I", FORMAT_VERSION)
                    + struct.pack("<Q", len(hbytes)) + hbytes + payload)
            path = self._path(fp)
            tmp = f"{path}.tmp.{os.getpid()}.{threading.get_ident()}"
            from ..checkpoint import _fsync_path

            with open(tmp, "wb") as f:
                f.write(blob)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)  # atomic publish; racing writers both win
            _fsync_path(self.root)
            _BYTES_WRITTEN.inc(len(blob))
            self._evict()
            return True
        except Exception as e:
            logger.warning("compile cache store of %s failed (%s); "
                           "continuing uncached", fp, e)
            _FALLBACKS["store_error"].inc()
            return False

    def _evict(self) -> None:
        """LRU-evict entries until total bytes fit the bound. The
        newest entry survives even when alone over the bound — evicting
        what was just published would thrash."""
        entries = self._entries()
        total = sum(s for _, _, s in entries)
        _STORE_BYTES.set(total)
        if self.max_bytes <= 0:
            return
        while total > self.max_bytes and len(entries) > 1:
            path, _, size = entries.pop(0)
            try:
                os.unlink(path)
            except OSError:
                if os.path.exists(path):
                    continue  # undeletable but still present: skip it
                # a racing process already evicted it — its bytes are
                # gone from disk either way, so the accounting must
                # drop them or we over-evict live entries
                total -= size
                continue
            total -= size
            _EVICTIONS.inc()
            logger.info("compile cache evicted %s (%d bytes; store over "
                        "%d-byte bound)", os.path.basename(path), size,
                        self.max_bytes)
        _STORE_BYTES.set(total)

    # -- manifest -----------------------------------------------------------

    def record_miss(self, kind: str,
                    inputs: Sequence[Tuple[str, Tuple[int, ...], str]],
                    donate: bool, sharded: bool = False) -> None:
        """Append one feed-shape record for warmup replay (deduped per
        process; best-effort — manifest problems never surface).
        ``sharded`` marks feeds carrying non-trivial placements: warmup
        replay skips those rows unless it can reconstruct the mesh (the
        shapes alone under-specify the executable's layout)."""
        row = {
            "kind": kind,
            "inputs": sorted([n, list(s), d] for (n, s, d) in inputs),
            "donate": bool(donate),
        }
        if sharded:
            row["sharded"] = True
        key = json.dumps(row, sort_keys=True)
        with self._lock:
            if key in self._manifest_seen:
                return
            self._manifest_seen.add(key)
        try:
            with open(self.manifest_path, "a") as f:
                f.write(key + "\n")
        except OSError as e:
            logger.debug("manifest append failed: %s", e)

    def read_manifest(self) -> List[dict]:
        rows: List[dict] = []
        try:
            with open(self.manifest_path) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        rows.append(json.loads(line))
                    except json.JSONDecodeError:
                        continue  # torn tail line from a crashed writer
        except OSError:
            pass
        return rows

    # -- ops surface (CLI) --------------------------------------------------

    def stats(self) -> dict:
        entries = []
        for path, mtime, size in self._entries():
            row = {
                "fingerprint": os.path.basename(path)[:-len(_ENTRY_SUFFIX)],
                "bytes": size,
                "mtime": round(mtime, 3),
            }
            try:
                header, _ = self._read_entry(path)
                for k in ("kind", "form", "backend", "device_kind", "jax",
                          "donate", "inputs", "created"):
                    if k in header:
                        row[k] = header[k]
            except Exception:
                row["unreadable"] = True
            entries.append(row)
        return {
            "root": self.root,
            "max_bytes": self.max_bytes,
            "entries": len(entries),
            "bytes": sum(e["bytes"] for e in entries),
            "manifest_rows": len(self.read_manifest()),
            "entry_list": entries,
        }

    def verify(self, delete_bad: bool = False) -> dict:
        """CRC + header check of every entry (no deserialization — that
        is backend-specific); optionally removes defective entries."""
        good, bad = [], []
        for path, _, _ in self._entries():
            name = os.path.basename(path)
            try:
                self._read_entry(path)
                good.append(name)
            except Exception as e:
                bad.append({"entry": name, "error": str(e)})
                if delete_bad:
                    self._quarantine(path)
        return {"ok": not bad, "good": len(good), "bad": bad,
                "deleted": len(bad) if delete_bad else 0}

    def prune(self, max_bytes: Optional[int] = None,
              clear: bool = False) -> dict:
        """Evict to ``max_bytes`` (default: the configured bound), or
        drop everything with ``clear=True``."""
        removed = 0
        if clear:
            for path, _, _ in self._entries():
                try:
                    os.unlink(path)
                    removed += 1
                except OSError:
                    pass
            try:
                os.unlink(self.manifest_path)
            except OSError:
                pass
        else:
            bound = self.max_bytes if max_bytes is None else int(max_bytes)
            entries = self._entries()
            total = sum(s for _, _, s in entries)
            while entries and total > bound:
                path, _, size = entries.pop(0)
                try:
                    os.unlink(path)
                except OSError:
                    if os.path.exists(path):
                        continue  # undeletable but present: skip it
                    total -= size  # already gone: bytes left the disk
                    continue
                total -= size
                removed += 1
        left = self._entries()
        _STORE_BYTES.set(sum(s for _, _, s in left))
        return {"removed": removed, "entries": len(left),
                "bytes": sum(s for _, _, s in left)}


def store_for(root: str, max_bytes: Optional[int] = None
              ) -> Optional["CompileCacheStore"]:
    """Store instance for an explicit directory (CLI surface); None
    when the directory cannot be created."""
    from ..config import get_config

    mb = get_config().compile_cache_max_bytes if max_bytes is None \
        else int(max_bytes)
    key = (os.path.abspath(root), mb)
    with _STORE_LOCK:
        if key not in _STORES:
            try:
                _STORES[key] = CompileCacheStore(key[0], mb)
            except OSError as e:
                logger.warning("compile cache unavailable at %s: %s",
                               root, e)
                _FALLBACKS["unavailable"].inc()
                _STORES[key] = None
        return _STORES[key]


def active_store() -> Optional["CompileCacheStore"]:
    """The config-selected store (``<compilation_cache_dir>/aot``), or
    None when the cache is disabled — the default, in which case every
    dispatch behaves exactly as if this subsystem did not exist."""
    from ..config import get_config

    root = get_config().compilation_cache_dir
    if not root:
        return None
    return store_for(os.path.join(root, "aot"))

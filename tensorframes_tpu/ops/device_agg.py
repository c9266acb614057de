"""On-device keyed aggregation for sharded frames.

The host `aggregate` path (verbs.py) gathers rows to the host and
lexsorts by key — fine single-host, but it is still the reference's
driver-shaped plan (Catalyst shuffle ≙ host sort,
DebugRowOps.scala:583). For sharded frames with integer keys this module
replaces the shuffle entirely with the TPU-native plan:

    per-shard dense segment reduction  →  one ICI collective

Each shard scatter-reduces its local rows into a dense ``[K, ...]``
bucket table (K = the mixed-radix span of the key ranges), then a single
``psum``/``pmin``/``pmax`` over the batch axis merges the tables — a
log-depth hardware collective instead of a host round-trip. Empty
buckets are dropped afterwards using the (psum-merged) per-bucket
counts. Multi-host works by construction: the collective crosses
process boundaries through ICI/DCN, and only the tiny dense table is
ever host-materialized.

Two plans, tried in order:

* **dense span** — integer keys whose mixed-radix span is small
  (``K <= 1<<20`` buckets, ``K × feature-elems <= 1<<24``): bucket ids
  come from pure device arithmetic; the keys never touch the host.
* **dictionary encoding** — arbitrary keys (strings, huge-span ints,
  composites): one host pass over the *key columns only* builds dense
  group ids via ``np.unique`` (values stay on device), then the same
  segment-reduce + collective runs with ``K = #distinct groups``. This
  removes the reference's Catalyst shuffle for any key type
  (DebugRowOps.scala:583) at the cost of one key-column transfer.

Anything else (non-algebraic fetches, ragged values, trimmed row counts
the mesh no longer divides) falls back to the host path. The dense-table
trick is the same reformulation the pallas segment kernel uses
(scatter → dense compute): on TPU, bounded dense work beats
data-dependent shuffles.
"""

from __future__ import annotations

import math
from functools import lru_cache
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ..parallel._shard_map import shard_map
from ..utils import get_logger
from .keys import group_ids, mixed_radix_strides

logger = get_logger(__name__)

_KEY_LIMIT = 1 << 20          # max dense bucket count
_TABLE_ELEM_LIMIT = 1 << 24   # max K × per-row feature elements


@lru_cache(maxsize=32)
def _agg_fn(mesh, axis: str, ops_key, K: int, strides: Tuple[int, ...]):
    """Jitted shard_map program: local dense segment-reduce + one
    collective per output. ``ops_key`` is a tuple of (name, op, ndim);
    inputs are the offset key columns (min already subtracted) and the
    value columns, all sharded over ``axis``."""

    def local(keys, vals):
        ids = keys[0] * strides[0]
        for k, s in zip(keys[1:], strides[1:]):
            ids = ids + k * s
        out = {}
        count = jax.ops.segment_sum(
            jnp.ones(ids.shape, jnp.int32), ids, num_segments=K
        )
        out["__count__"] = lax.psum(count, axis)
        for name, op, _ in ops_key:
            v = vals[name]
            if op in ("reduce_sum", "reduce_mean"):
                t = jax.ops.segment_sum(v, ids, num_segments=K)
                out[name] = lax.psum(t, axis)
            elif op == "reduce_min":
                t = jax.ops.segment_min(v, ids, num_segments=K)
                out[name] = lax.pmin(t, axis)
            elif op == "reduce_max":
                t = jax.ops.segment_max(v, ids, num_segments=K)
                out[name] = lax.pmax(t, axis)
            else:  # pragma: no cover - guarded by caller
                raise ValueError(f"unsupported op {op}")
        return out

    n_keys = len(strides)
    in_specs = (
        tuple(P(axis) for _ in range(n_keys)),
        {name: P(axis, *([None] * (ndim - 1))) for name, _, ndim in ops_key},
    )
    out_specs = {name: P() for name, _, _ in ops_key}
    out_specs["__count__"] = P()
    return jax.jit(
        shard_map(local, mesh=mesh, in_specs=in_specs, out_specs=out_specs)
    )


@jax.jit
def _stacked_minmax(*cols):
    """Per-column (min, max) pairs in one device computation / one
    transfer. Each pair keeps its column's own dtype — casting to a
    common int64 here would silently truncate to int32 when x64 is
    disabled and corrupt the range guard."""
    return tuple((c.min(), c.max()) for c in cols)


# Per-array (min, max) memo for the dense plan's span probe: device
# frame columns are immutable, but the probe's device_get is a
# device→host round trip PER aggregate CALL (the r4 follow-up:
# "aggregate's device plan pays per-call transfers").
# id()-keyed with a weakref finalizer so entries die with their array
# (ids recycle only after the finalizer has already evicted the entry).
_minmax_memo: Dict[int, tuple] = {}  # lint: guarded (benign race: concurrent writers memoize the same immutable probe; worst case one redundant device_get)

# Same lifetime discipline for the dictionary plan's encode: keyed by
# the tuple of key-column array ids; holds (staged dense ids on device,
# group key columns, K). Evicted when any key array is collected.
_dict_encode_memo: Dict[tuple, tuple] = {}  # lint: guarded (benign race: same-key writers store identical staged values)


def _placement_token() -> tuple:
    """The topology a staged upload targeted: a cached staged placement
    is only valid while the backend and visible device set are
    unchanged — keying the staged ids by this token re-stages after a
    backend/device flip instead of serving a mis-placed array."""
    return (
        jax.default_backend(),
        tuple(d.id for d in jax.local_devices()),
    )


def _cached_minmax(cols):
    import weakref

    missing = [c for c in cols if id(c) not in _minmax_memo]
    if missing:
        got = jax.device_get(_stacked_minmax(*missing))
        for c, mm in zip(missing, got):
            key = id(c)
            _minmax_memo[key] = mm
            weakref.finalize(c, _minmax_memo.pop, key, None)
    return [_minmax_memo[id(c)] for c in cols]


def _run_tables(
    frame, axis, ops, out_names, K, strides, key_feeds, main, tail, ids_tail
):
    """Shared tail of both plans: device segment-reduce + collective,
    host fold of the tiny tail block, empty-bucket drop, mean divide.
    Returns ``(sel, out_cols)`` — the surviving bucket ids (ascending,
    i.e. lexicographic key order) and the finished output columns."""
    ops_key = tuple((x, ops[x], int(main[x].ndim)) for x in out_names)
    fn = _agg_fn(frame.mesh, axis, ops_key, K, tuple(strides))
    res = fn(key_feeds, {x: main[x] for x in out_names})
    count = np.asarray(res["__count__"])
    tables = {x: np.asarray(res[x]) for x in out_names}

    # -- fold the host tail block in (≤ dp-1 rows) --------------------------
    if tail is not None and ids_tail is not None and len(ids_tail):
        np.add.at(count, ids_tail, 1)
        for x in out_names:
            v = np.asarray(tail[x], dtype=tables[x].dtype)
            if ops[x] in ("reduce_sum", "reduce_mean"):
                np.add.at(tables[x], ids_tail, v)
            elif ops[x] == "reduce_min":
                np.minimum.at(tables[x], ids_tail, v)
            else:
                np.maximum.at(tables[x], ids_tail, v)

    sel = np.flatnonzero(count > 0)
    out_cols: Dict[str, np.ndarray] = {}
    for x in out_names:
        t = tables[x][sel]
        if ops[x] == "reduce_mean":
            c = count[sel].reshape((-1,) + (1,) * (t.ndim - 1))
            t = (t / c).astype(tables[x].dtype)
        out_cols[x] = t
    return sel, out_cols


def _allgather_dicts(local_cols: List[np.ndarray]) -> Tuple[List[np.ndarray], int]:
    """Union every process's group-key dictionary columns.

    Serializes this process's dictionary (one array per key column, one
    row per LOCAL distinct group), allgathers fixed-width byte buffers in
    two phases (sizes, then padded payloads — ``process_allgather``
    requires equal shapes), and returns ``(union_cols, offset)`` where
    ``union_cols`` concatenates all processes' dictionaries in process
    order and ``offset`` is where this process's entries start."""
    import pickle

    from jax.experimental import multihost_utils as mh

    payload = np.frombuffer(
        pickle.dumps(local_cols, protocol=pickle.HIGHEST_PROTOCOL), np.uint8
    )
    sizes = np.asarray(
        mh.process_allgather(np.asarray([payload.size], np.int64))
    ).reshape(-1)
    width = int(sizes.max())
    padded = np.zeros(width, np.uint8)
    padded[: payload.size] = payload
    bufs = np.asarray(mh.process_allgather(padded)).reshape(len(sizes), width)
    # every rank received every rank's dictionary — the host-gather
    # volume the file shuffle exists to eliminate (asserted zero in the
    # shuffled-aggregate tests)
    from ..blockstore.store import HOSTGATHER_BYTES

    HOSTGATHER_BYTES.inc(float(bufs.nbytes))
    dicts = [
        pickle.loads(bufs[p, : int(sizes[p])].tobytes())
        for p in range(len(sizes))
    ]
    me = jax.process_index()
    offset = int(sum(len(d[0]) for d in dicts[:me]))
    union = [
        np.concatenate([np.asarray(d[i]) for d in dicts])
        for i in range(len(local_cols))
    ]
    return union, offset


def extract_local_rows(v):
    """This process's rows of one frame column: host lists are already
    process-local; sharded device arrays concatenate their addressable
    shards in global-index order. Returns None when no shard is
    addressable (caller must treat as ineligible). Shared by the
    dictionary plan and the generic multiprocess aggregate (verbs.py)."""
    if isinstance(v, list):
        return np.asarray(v, dtype=object)
    if isinstance(v, np.ndarray):
        return v
    shards = sorted(
        v.addressable_shards, key=lambda s: s.index[0].start or 0
    )
    if not shards:
        return None
    return np.concatenate([np.asarray(s.data) for s in shards])


def gather_local_columns(frame, names) -> Optional[Dict[str, np.ndarray]]:
    """This process's rows of every named column, concatenated across
    blocks — the local half of the distributed relational verbs (join's
    broadcast build side, sort's allgather input, VERDICT r3 #7).
    Returns None when any column has no addressable shard here; callers
    MUST vote on that with :func:`uniform_ok` before entering any
    collective, so an ineligible fleet raises everywhere instead of one
    process bailing out of an allgather its peers already entered."""
    cols: Dict[str, np.ndarray] = {}
    for name in names:
        parts = []
        for b in frame.blocks():
            lr = extract_local_rows(b[name])
            if lr is None:
                return None
            parts.append(lr)
        cols[name] = parts[0] if len(parts) == 1 else np.concatenate(parts)
    return cols


def assemble_key_cols(frame, keys, group_key_cols, sel=None):
    """Result key columns from per-key group arrays: optional group
    selection, cast device keys back to their schema dtype (host keys —
    strings — pass through). Shared result epilogue of the dictionary
    plan and the generic multiprocess aggregate (verbs.py)."""
    key_cols = {}
    for i, k in enumerate(keys):
        vals = group_key_cols[i] if sel is None else group_key_cols[i][sel]
        info = frame.schema[k]
        key_cols[k] = (
            vals.astype(info.dtype.np_dtype) if info.is_device else vals
        )
    return key_cols


def uniform_ok(ok: bool) -> bool:
    """Collective eligibility vote: every process must take the same
    branch BEFORE any further collective — one process falling back to a
    host path while the rest allgather would deadlock both groups."""
    from jax.experimental import multihost_utils as mh

    all_ok = np.asarray(
        mh.process_allgather(np.asarray([1 if ok else 0], np.int32))
    )
    return bool(int(all_ok.min()))


def _aggregate_multiprocess_dict(
    frame, keys, ops, out_names, main, feat, axis
) -> Optional[Tuple[Dict[str, np.ndarray], Dict[str, np.ndarray]]]:
    """Dictionary plan across processes: local encode → dictionary
    allgather/merge → global dense ids → shared segment plan. Key columns
    may be process-local host lists (strings) or sharded device arrays;
    value columns stay sharded throughout."""
    from jax.sharding import NamedSharding

    key_local: List[np.ndarray] = []
    ok = True
    for k in keys:
        v = extract_local_rows(main[k])
        if v is None:
            ok = False
            break
        key_local.append(v)
    n_local = len(key_local[0]) if key_local else 0
    if ok and any(len(a) != n_local for a in key_local):
        # a host key column whose local rows disagree with this process's
        # device shard rows cannot be aligned
        ok = False
    if not uniform_ok(ok):
        return None
    if n_local:
        ids_local, local_dict, k_local = group_ids(key_local)
    else:
        ids_local = np.zeros(0, np.int64)
        local_dict, k_local = [a[:0] for a in key_local], 0
    union_cols, offset = _allgather_dicts(local_dict)
    union_ids, group_key_cols, K = group_ids(union_cols)
    if K * feat > _TABLE_ELEM_LIMIT:
        logger.debug(
            "device aggregate: %d groups ×%d feat exceeds the table limit "
            "(multi-process)", K, feat,
        )
        return None
    gids_local = union_ids[offset:offset + k_local][ids_local].astype(np.int32)
    ids_global = jax.make_array_from_process_local_data(
        NamedSharding(frame.mesh, P(axis)), gids_local
    )
    sel, out_cols = _run_tables(
        frame, axis, ops, out_names, K, (1,), (ids_global,), main, None, None
    )
    return assemble_key_cols(frame, keys, group_key_cols, sel), out_cols


def try_aggregate_device(
    frame,
    keys: Sequence[str],
    seg_info,
    out_names: Sequence[str],
) -> Optional[Tuple[Dict[str, np.ndarray], Dict[str, np.ndarray]]]:
    """Attempt the sharded device plans (dense span, then dictionary
    encoding). Returns ``(key_cols, out_cols)`` with groups in
    lexicographic key order (the host path's ordering), or None when
    ineligible."""
    if not frame.is_sharded or frame.num_rows == 0:
        return None
    ops = {name: op for name, op, _ in seg_info}
    if any(ops[x] not in ("reduce_sum", "reduce_min", "reduce_max", "reduce_mean")
           for x in out_names):
        return None
    blocks = frame.blocks()
    main, tail = blocks[0], (blocks[1] if len(blocks) > 1 else None)
    for x in out_names:
        if isinstance(main[x], list):
            return None
    for k in keys:
        # ragged device key columns can't form ids; host-resident key
        # columns (strings, …) are fine — the dictionary plan handles them
        if isinstance(main[k], list) and frame.schema[k].is_device:
            return None
    # global row count reads a VALUE column: value columns are always
    # dense device arrays here, whereas a key column may be a
    # process-local host list whose length is only this process's rows
    main_rows = int(
        main[out_names[0]].shape[0]
        if out_names
        else (
            len(main[keys[0]])
            if isinstance(main[keys[0]], list)
            else main[keys[0]].shape[0]
        )
    )
    if main_rows == 0:
        return None  # everything in the tail → host path is already optimal
    axis = getattr(frame, "_axis", None) or "dp"
    dp = frame.mesh.shape.get(axis, 1)
    if main_rows % dp:
        # a trimmed map can leave a sharded frame with a row count the
        # mesh no longer divides; shard_map would reject it — host path
        # (mirrors the reduce_rows guard, verbs.py)
        return None
    feat = 0
    for x in out_names:
        cell = main[x].shape[1:]
        feat = max(feat, int(np.prod(cell)) if cell else 1)

    dense_eligible = all(
        frame.schema[k].is_device
        and np.issubdtype(frame.schema[k].dtype.np_dtype, np.integer)
        for k in keys
    )
    if dense_eligible:
        # -- plan A: dense mixed-radix span (keys never leave the device) ---
        mm = _cached_minmax([main[k] for k in keys])
        mins, ranges = [], []
        for i, k in enumerate(keys):
            lo, hi = int(mm[i][0]), int(mm[i][1])
            if tail is not None and len(tail[k]):
                t = np.asarray(tail[k])
                lo, hi = min(lo, int(t.min())), max(hi, int(t.max()))
            mins.append(lo)
            ranges.append(int(hi - lo + 1))
        # python ints: key spans near the int32/int64 limits must not wrap
        # the product and sneak past the eligibility gate
        K = math.prod(ranges)
        if K <= _KEY_LIMIT and K * feat <= _TABLE_ELEM_LIMIT:
            # keys[0] most significant → bucket order == lexicographic order
            strides = mixed_radix_strides(ranges)
            # widen BEFORE the offset subtraction: an int8 key spanning
            # -128..127 must not wrap its 255-wide offset (the negative
            # id would be silently dropped by the XLA scatter)
            keys_off = tuple(
                (main[k].astype(jnp.int32) - np.int32(mins[i]))
                if main[k].dtype.itemsize < 8
                else (main[k] - mins[i]).astype(jnp.int32)
                for i, k in enumerate(keys)
            )
            ids_tail = None
            if tail is not None:
                ids_tail = np.zeros(len(tail[keys[0]]), np.int64)
                for i, k in enumerate(keys):
                    ids_tail += (
                        np.asarray(tail[k]).astype(np.int64) - mins[i]
                    ) * strides[i]
            sel, out_cols = _run_tables(
                frame, axis, ops, out_names, K, strides, keys_off,
                main, tail, ids_tail,
            )
            key_cols: Dict[str, np.ndarray] = {}
            for i, k in enumerate(keys):
                comp = (sel // strides[i]) % ranges[i] + mins[i]
                key_cols[k] = comp.astype(frame.schema[k].dtype.np_dtype)
            return key_cols, out_cols
        logger.debug(
            "device aggregate: key span %d (×%d feat) too large for the "
            "dense plan; trying dictionary encoding", K, feat,
        )

    # -- plan B: dictionary encoding — one host pass over the KEY columns
    # only (values stay sharded on device). Arbitrary key types; K becomes
    # the number of distinct groups, not the key span. -----------------------
    if jax.process_count() > 1:
        # multi-process: each process dictionary-encodes its LOCAL key
        # rows, the per-process dictionaries union through one allgather
        # (tiny: one entry per distinct group), and the merged dense ids
        # feed the same segment plan — no process ever sees another's
        # raw key column (≙ replacing the Catalyst shuffle at
        # DebugRowOps.scala:583 with a dictionary exchange)
        if tail is not None and len(tail[out_names[0] if out_names else keys[0]]):
            # the multi-process plan has no tail fold; declining here is
            # SPMD-uniform (block structure derives from global shapes)
            return None
        return _aggregate_multiprocess_dict(
            frame, keys, ops, out_names, main, feat, axis
        )
    # repeated aggregates over the same IMMUTABLE device key columns
    # skip the per-call device_get + host encode + ids re-upload (each a
    # host↔device round trip); host-list keys stay uncached (lists are
    # mutable)
    memo_key = None
    if tail is None and all(
        not isinstance(main[k], list) for k in keys
    ):
        memo_key = tuple(id(main[k]) for k in keys)
        hit = _dict_encode_memo.get(memo_key)
        if hit is not None:
            ids_dev, group_key_cols, K = hit
            if K * feat > _TABLE_ELEM_LIMIT:
                return None
            sel, out_cols = _run_tables(
                frame, axis, ops, out_names, K, (1,), (ids_dev,),
                main, None, None,
            )
            return (
                assemble_key_cols(frame, keys, group_key_cols, sel),
                out_cols,
            )
    # host-list (e.g. STRING) keys have no stable array identity for
    # the id memo above, but the FRAME is immutable once materialized:
    # cache their dictionary encode on it (the same convention as
    # keys.frame_group_ids), so repeated string-keyed aggregates skip
    # the full hash pass over every key cell
    from .keys import frame_cache_get, frame_cache_put

    frame_ck = ("__device_dict__",) + tuple(keys)
    hit = None
    staged_ck = None
    ids_dev = None
    if memo_key is None and tail is None:
        hit = frame_cache_get(frame, frame_ck)
        # staged-placement cache (the r4 follow-up): the encode cache
        # above still paid a host->device ids upload on EVERY call; the
        # staged array is as immutable as the frame, scoped to the
        # placement it was uploaded for
        staged_ck = frame_ck + ("__staged__", _placement_token())
    if hit is not None:
        ids_all, group_key_cols, K = hit
        ids_dev = frame_cache_get(frame, staged_ck)
    else:
        key_host: List[np.ndarray] = []
        for k in keys:
            v = main[k]
            if isinstance(v, list):
                arr = np.asarray(v, dtype=object)
            else:
                arr = np.asarray(jax.device_get(v))
            if tail is not None and len(tail[k]):
                tv = tail[k]
                tarr = (
                    np.asarray(tv, dtype=object)
                    if isinstance(tv, list)
                    else np.asarray(tv)
                )
                arr = np.concatenate([arr, tarr])
            key_host.append(arr)
        # shared encoder (ops/keys.py): dense group ids, lexicographic
        # order
        ids_all, group_key_cols, K = group_ids(key_host)
        if memo_key is None and tail is None:
            frame_cache_put(frame, frame_ck, (ids_all, group_key_cols, K))
    if K * feat > _TABLE_ELEM_LIMIT:
        logger.debug(
            "device aggregate: %d groups ×%d feat exceeds the table limit; "
            "host path", K, feat,
        )
        return None
    ids_tail = ids_all[main_rows:] if tail is not None else None
    if ids_dev is None:
        ids_dev = jnp.asarray(ids_all[:main_rows].astype(np.int32))
        if staged_ck is not None:
            frame_cache_put(frame, staged_ck, ids_dev)
    if memo_key is not None:
        import weakref

        _dict_encode_memo[memo_key] = (ids_dev, group_key_cols, K)
        for k in keys:  # evict when ANY key column dies
            weakref.finalize(
                main[k], _dict_encode_memo.pop, memo_key, None
            )
    sel, out_cols = _run_tables(
        frame, axis, ops, out_names, K, (1,), (ids_dev,),
        main, tail, ids_tail,
    )
    key_cols = {}
    for i, k in enumerate(keys):
        vals = group_key_cols[i][sel]
        info = frame.schema[k]
        key_cols[k] = (
            vals.astype(info.dtype.np_dtype) if info.is_device else vals
        )
    return key_cols, out_cols

"""Data loading: batch iteration + host→device prefetch.

The reference has no loader of its own — Spark's scan pipeline feeds
partitions to executors while TF runs (implicit overlap). The TPU-native
equivalent must be explicit: ``iterate_batches`` walks a frame's columns
in minibatches on the host, and ``prefetch_to_device`` runs
``jax.device_put`` on a background thread into a bounded buffer so the
next batch's host→HBM transfer overlaps the current batch's compute —
double buffering, the standard input-pipeline recipe.
"""

from __future__ import annotations

import os
import queue
import threading
import time
from typing import Dict, Iterator, Iterable, List, Optional, Sequence, Tuple

import jax
import numpy as np

from .observability.metrics import counter as _counter
from .observability.metrics import gauge as _gauge
from .observability.metrics import histogram as _histogram
from .resilience.faults import fault_point
from .resilience.retry import RetryPolicy, retry_call
from .utils import get_logger
from .utils.npz import decode_array, encode_array

logger = get_logger(__name__)

# Prefetch pipeline telemetry (registered at import; see
# observability/metrics.py). The two wait histograms are the overlap
# diagnostic: a consumer that never waits is compute-bound (prefetch is
# doing its job); a producer that never waits means the buffer is too
# small or the loader too slow.
_PREFETCH_DEPTH = _gauge(
    "tftpu_prefetch_queue_depth",
    "Batches currently staged in the prefetch buffer",
)
_PREFETCH_BATCHES = _counter(
    "tftpu_prefetch_batches_total",
    "Batches delivered to the consumer by prefetch_to_device",
)
_PRODUCER_WAIT = _histogram(
    "tftpu_prefetch_producer_wait_seconds",
    "Time the prefetch worker blocked waiting for buffer space",
)
_CONSUMER_WAIT = _histogram(
    "tftpu_prefetch_consumer_wait_seconds",
    "Time the consumer blocked waiting for a staged batch",
)


def iterate_batches(
    frame,
    columns: Optional[Sequence[str]] = None,
    batch_size: int = 256,
    shuffle: bool = False,
    seed: int = 0,
    drop_remainder: bool = False,
) -> Iterator[Dict[str, np.ndarray]]:
    """Yield ``{col: array[batch, ...]}`` minibatches from a frame's dense
    columns (host-side)."""
    if columns is None:
        columns = [c.name for c in frame.schema.device_columns]
    else:
        columns = list(columns)
    if not columns:
        raise ValueError(
            "iterate_batches: no columns to batch (frame has no dense "
            "device columns, or an empty selection was passed)"
        )
    cols = {c: np.asarray(frame.column_values(c)) for c in columns}
    n = len(next(iter(cols.values())))
    order = np.arange(n)
    if shuffle:
        np.random.default_rng(seed).shuffle(order)
    stop = n - (n % batch_size) if drop_remainder else n
    for lo in range(0, stop, batch_size):
        idx = order[lo : lo + batch_size]
        yield {c: v[idx] for c, v in cols.items()}


_SENTINEL = object()


def prefetch_to_device(
    batches: Iterable,
    size: int = 2,
    sharding=None,
    retry: Optional[RetryPolicy] = None,
    join_timeout: float = 5.0,
) -> Iterator:
    """Wrap a batch iterator with background ``jax.device_put``.

    A worker thread stages up to ``size`` batches in HBM ahead of the
    consumer (``sharding`` optionally places them on a mesh), so transfer
    overlaps compute.

    Failure semantics (the input-pipeline leg of the resilience
    subsystem): a worker exception is parked in a side slot — never
    inside the data queue where a full buffer or a consumer drain could
    delay or drop it — and re-raised by the consumer's very next
    ``__next__`` once the already-staged good batches are exhausted. The
    consumer never blocks indefinitely: it polls worker liveness, so
    even a worker killed by a non-``Exception`` (``KeyboardInterrupt``,
    interpreter teardown) surfaces instead of hanging the training loop.
    Shutdown joins the worker with ``join_timeout`` and logs if it is
    still wedged (e.g. a stuck transfer) rather than blocking teardown
    forever. ``retry`` applies a
    :class:`~tensorframes_tpu.resilience.RetryPolicy` to each
    host→device transfer, absorbing transient device-put faults.
    """
    def put(batch):
        def xfer():
            fault_point("io.prefetch.device_put")
            if sharding is not None:
                return jax.device_put(batch, sharding)
            return jax.device_put(batch)

        return retry_call(xfer, policy=retry, describe="prefetch.device_put")

    return pipeline_iter(
        batches, stage=put, size=size, join_timeout=join_timeout,
        observe=True, thread_name="tfs-prefetch",
    )


def pipeline_iter(
    items: Iterable,
    stage=None,
    size: int = 2,
    join_timeout: float = 5.0,
    observe: bool = False,
    thread_name: str = "tfs-pipeline",
) -> Iterator:
    """The generalized double-buffered pipeline under
    :func:`prefetch_to_device`: a worker thread pulls ``items``, applies
    ``stage`` (identity by default — pure read-ahead), and stages up to
    ``size`` results for the consumer. The streaming partitioner
    (``blockstore.stream_chain``) uses it to overlap the next chunk's
    disk read/parse with the current chunk's compute; failure and
    shutdown semantics are exactly prefetch_to_device's (parked worker
    exceptions, liveness polling, bounded join). ``observe=True`` wires
    the prefetch telemetry instruments (only prefetch_to_device should
    — the histograms describe the host→device pipeline).
    """
    q: "queue.Queue" = queue.Queue(maxsize=size)
    stop = threading.Event()
    done = threading.Event()
    err: List[Optional[BaseException]] = [None]
    if stage is None:
        stage = lambda item: item  # noqa: E731 - identity read-ahead

    def enqueue(item) -> bool:
        # bounded put that aborts when the consumer is gone, so an
        # abandoned iterator can't pin the worker (and its staged HBM
        # buffers) forever
        t0 = time.perf_counter()
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                if item is not _SENTINEL and observe:
                    _PRODUCER_WAIT.observe(time.perf_counter() - t0)
                    _PREFETCH_DEPTH.set(q.qsize())
                return True
            except queue.Full:
                continue
        return False

    def worker():
        try:
            for item in items:
                if stop.is_set() or not enqueue(stage(item)):
                    return
        except BaseException as e:  # parked for the consumer thread —
            # BaseException too: a KeyboardInterrupt/SystemExit dying in
            # the worker must surface as an error, not truncate the
            # stream into a clean-looking end-of-data
            err[0] = e
        finally:
            done.set()
            enqueue(_SENTINEL)

    t = threading.Thread(target=worker, daemon=True, name=thread_name)
    t.start()

    try:
        # wait_t0 spans every empty poll until the next item lands (and
        # is re-armed after each yield resumes), so the histogram records
        # true per-batch consumer stall, not just the last 0.2s slice
        wait_t0 = time.perf_counter()
        while True:
            try:
                item = q.get(timeout=0.2)
            except queue.Empty:
                # nothing staged: if the worker is gone the stream is
                # over (error or not) — without this check a worker that
                # died before enqueueing its sentinel would hang us
                if done.is_set() or not t.is_alive():
                    try:
                        item = q.get_nowait()  # racing final enqueue
                    except queue.Empty:
                        if err[0] is not None:
                            raise err[0]
                        return
                else:
                    continue
            if item is _SENTINEL:
                if err[0] is not None:
                    raise err[0]
                return
            if observe:
                _CONSUMER_WAIT.observe(time.perf_counter() - wait_t0)
                _PREFETCH_DEPTH.set(q.qsize())
                _PREFETCH_BATCHES.inc()
            yield item
            wait_t0 = time.perf_counter()
    finally:
        # consumer finished or bailed early: release the worker, drop
        # any staged batches, and bound the shutdown wait. The depth
        # gauge goes to 0 here — a finished stream must not export
        # phantom staged batches (the sentinel, or batches a bailing
        # consumer abandoned) in an end-of-run snapshot
        stop.set()
        try:
            while True:
                q.get_nowait()
        except queue.Empty:
            pass
        if observe:
            _PREFETCH_DEPTH.set(0)
        t.join(timeout=join_timeout)
        if t.is_alive():  # pragma: no cover - requires a wedged transfer
            logger.warning(
                "%s: worker still running %.1fs after shutdown (stuck "
                "stage?); leaving daemon thread behind",
                thread_name, join_timeout,
            )


# ---------------------------------------------------------------------------
# Frame persistence
# ---------------------------------------------------------------------------
#
# The reference never persists frames itself — Spark's data sources own
# storage. A standalone framework needs its own: a directory with a JSON
# schema manifest, one compressed npz for the dense columns, and (only when
# present) a pickle for host columns (strings / binaries / ragged cells).
# Dense arrays are stored as raw bytes keyed c0, c1, … with the numpy
# dtype/shape in the manifest: npz cannot reconstruct ml_dtypes (bfloat16
# loads as void '|V2'), and npz keys must not collide with savez's own
# parameter names (a column called "file" would) — same scheme as
# checkpoint.py's npz backend.

_MANIFEST = "frame.json"
_DENSE = "columns.npz"
_HOST = "host_columns.pkl"
_FORMAT_VERSION = 1


def save_frame(frame, path: str) -> None:
    """Write a frame to ``path`` (a directory, created if needed).

    Device columns are materialized to host numpy first; block structure
    is not preserved (reload with any ``num_blocks``).
    """
    import json
    import os
    import pickle
    import shutil

    fault_point("io.save_frame")
    # fail BEFORE touching the filesystem: a multi-host global array
    # cannot be materialized by one process (and a partial directory
    # would be worse than an error)
    for b in frame.blocks():
        for name, v in b.items():
            if not getattr(v, "is_fully_addressable", True):
                raise ValueError(
                    f"save_frame: column {name!r} spans non-addressable "
                    "devices (multi-host global array); use "
                    "save_frame_sharded/load_frame_sharded instead"
                )

    dense: Dict[str, np.ndarray] = {}
    host: Dict[str, list] = {}
    cols = []
    for i, info in enumerate(frame.schema):
        vals = [b[info.name] for b in frame.blocks()]
        is_list = any(isinstance(v, list) for v in vals)
        col = {
            "name": info.name,
            "dtype": info.dtype.name,
            "block_shape": list(info.block_shape.dims),
        }
        if info.is_device and not is_list:
            arr = np.concatenate([np.asarray(v) for v in vals], axis=0)
            dense[f"c{i}"], entry = encode_array(arr)
            col["np_dtype"] = entry["dtype"]
            col["np_shape"] = entry["shape"]
        else:
            flat: list = []
            for v in vals:
                flat.extend(list(v))
            host[info.name] = flat
        cols.append(col)
    manifest = {
        "format_version": _FORMAT_VERSION,
        "num_rows": frame.num_rows,
        "columns": cols,
    }
    # atomic save: build the whole directory aside, then swap it in — a
    # crash mid-write must never pair a new manifest with stale columns.
    # normpath first: with a trailing slash the tmp dir would land INSIDE
    # the target and be destroyed by the pre-swap rmtree.
    path = os.path.normpath(path)
    tmp = f"{path}.tmp.{os.getpid()}"
    shutil.rmtree(tmp, ignore_errors=True)
    try:
        os.makedirs(tmp)
        with open(os.path.join(tmp, _MANIFEST), "w") as f:
            json.dump(manifest, f, indent=2)
        np.savez_compressed(os.path.join(tmp, _DENSE), **dense)
        if host:
            with open(os.path.join(tmp, _HOST), "wb") as f:
                pickle.dump(host, f)
        # keep a recoverable frame on disk at every instant: rename the
        # old directory aside, swap the new one in, only then delete the
        # old (rmtree-then-rename would lose the previous frame outright
        # on a crash between the two calls). The aside name is FIXED so a
        # later save — any process — can self-heal a crash that happened
        # inside the two-rename window instead of leaking the only copy.
        old = f"{path}.old"
        if os.path.isdir(old) and not os.path.isdir(path):
            os.rename(old, path)  # heal a previous crashed swap
        shutil.rmtree(old, ignore_errors=True)
        if os.path.isdir(path):
            os.rename(path, old)
        os.rename(tmp, path)
        shutil.rmtree(old, ignore_errors=True)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    logger.info(
        "save_frame: %d rows, %d dense + %d host columns -> %s",
        manifest["num_rows"], len(dense), len(host), path,
    )




def load_frame(path: str, num_blocks: Optional[int] = None):
    """Load a frame written by :func:`save_frame`."""
    import json
    import os
    import pickle

    from . import dtypes as dt
    from .frame import TensorFrame, _partition_bounds
    from .schema import ColumnInfo, Schema
    from .shape import Shape

    fault_point("io.load_frame")
    path = os.path.normpath(path)
    if not os.path.isdir(path) and os.path.isdir(f"{path}.old"):
        # a save crashed inside its two-rename swap window; the previous
        # frame is intact under the fixed aside name — read it
        path = f"{path}.old"
    with open(os.path.join(path, _MANIFEST)) as f:
        manifest = json.load(f)
    if manifest.get("format_version", 0) > _FORMAT_VERSION:
        raise ValueError(
            f"frame at {path} has format_version "
            f"{manifest['format_version']}; this build reads <= {_FORMAT_VERSION}"
        )
    raw = {}
    npz = os.path.join(path, _DENSE)
    if os.path.exists(npz):
        with np.load(npz, allow_pickle=False) as z:
            raw = {k: z[k] for k in z.files}
    host = {}
    pkl = os.path.join(path, _HOST)
    if os.path.exists(pkl):
        with open(pkl, "rb") as f:
            host = pickle.load(f)

    infos = []
    data: Dict[str, object] = {}
    for i, c in enumerate(manifest["columns"]):
        infos.append(
            ColumnInfo(c["name"], dt.by_name(c["dtype"]), Shape(c["block_shape"]))
        )
        if f"c{i}" in raw:  # dense: bytes → manifest dtype/shape
            data[c["name"]] = decode_array(
                raw[f"c{i}"], {"dtype": c["np_dtype"], "shape": c["np_shape"]}
            )
        else:
            data[c["name"]] = host[c["name"]]

    n = manifest["num_rows"]
    from .config import get_config

    k = num_blocks or min(get_config().default_num_blocks, max(1, n))
    blocks = []
    for lo, hi in _partition_bounds(n, k):
        blocks.append({name: v[lo:hi] for name, v in data.items()})
    return TensorFrame(blocks, Schema(infos))


def save_frame_sharded(frame, path: str) -> str:
    """Multi-host frame persistence: every process writes ITS OWN rows.

    A global sharded frame spans processes, so no single process can
    materialize it (``save_frame`` refuses). Instead each process writes
    the rows of its addressable shards to ``path/part-<process_index>``
    (atomic per part, via save_frame) and the set of parts reassembles
    with :func:`load_frame_sharded`. Single-process frames degrade to
    one part. Returns this process's part directory.

    All processes must call this in lockstep (standard SPMD contract);
    ``path`` is usually shared storage (NFS/GCS-fuse) in a real fleet.
    """
    import os

    import jax

    from .frame import TensorFrame
    from .schema import Schema

    pid = jax.process_index()
    local_block: Dict[str, object] = {}
    infos = []
    for info in frame.schema:
        parts = []
        for b in frame.blocks():
            v = b[info.name]
            if isinstance(v, (list, np.ndarray)):
                parts.append(v)
            elif getattr(v, "is_fully_addressable", True):
                parts.append(np.asarray(v))
            else:
                # concat this process's shards in row order, keeping ONE
                # replica per row-range: meshes with non-batch axes
                # replicate each row-shard across them (same index,
                # replica_id > 0) and must not duplicate rows
                shards = sorted(
                    (s for s in v.addressable_shards if s.replica_id == 0),
                    key=lambda s: s.index[0].start or 0,
                )
                parts.append(
                    np.concatenate([np.asarray(s.data) for s in shards], axis=0)
                )
        if isinstance(parts[0], list):
            flat: list = []
            for p in parts:
                flat.extend(list(p))
            local_block[info.name] = flat
        else:
            local_block[info.name] = np.concatenate(
                [np.asarray(p) for p in parts], axis=0
            )
        infos.append(info)
    part = os.path.join(path, f"part-{pid}")
    os.makedirs(path, exist_ok=True)
    save_frame(TensorFrame([local_block], Schema(infos)), part)
    # every process writes the identical meta so a reload under a
    # different process count fails loudly instead of dropping parts;
    # renamed into place, because a peer that is already loading must
    # never read the file truncated (it would exit and hang the rest in
    # their next collective)
    import json

    meta = os.path.join(path, "parts.json")
    with open(f"{meta}.{pid}.tmp", "w") as f:
        json.dump({"num_parts": jax.process_count()}, f)
    os.replace(f"{meta}.{pid}.tmp", meta)
    return part


def load_frame_sharded(path: str, mesh=None, axis: Optional[str] = None):
    """Load this process's ``part-<process_index>`` written by
    :func:`save_frame_sharded` and reassemble the GLOBAL sharded frame
    (``parallel.frame_from_process_local``). Host-only columns are not
    supported across processes (same rule as frame_from_process_local)."""
    import os

    import jax

    from .parallel.distributed import frame_from_process_local

    import json

    meta_path = os.path.join(path, "parts.json")
    if os.path.exists(meta_path):
        with open(meta_path) as f:
            num_parts = json.load(f)["num_parts"]
        if num_parts != jax.process_count():
            raise ValueError(
                f"load_frame_sharded: saved with {num_parts} process(es) "
                f"but loading with {jax.process_count()}; part counts must "
                "match (repartition via a single-process load_frame of "
                "each part instead)"
            )
    part = os.path.join(path, f"part-{jax.process_index()}")
    local = load_frame(part, num_blocks=1)
    [block] = local.blocks()
    data = {}
    for info in local.schema:
        v = block[info.name]
        if isinstance(v, list):
            raise TypeError(
                f"Column {info.name!r}: host-only columns cannot span "
                "processes; drop them before save_frame_sharded or load "
                "the part directly with load_frame"
            )
        data[info.name] = v
    return frame_from_process_local(data, mesh=mesh, axis=axis)


# ---------------------------------------------------------------------------
# CSV ingestion
# ---------------------------------------------------------------------------

def _infer_csv_types(sample_rows, ncols):
    """Per-column type lattice over sample fields: int ⊂ float ⊂ str;
    empty fields promote numeric columns to float (missing → NaN)."""
    kinds = ["int"] * ncols
    for fields in sample_rows:
        for j in range(ncols):
            f = fields[j] if j < len(fields) else ""
            k = kinds[j]
            if k == "str":
                continue
            if f == "":
                if k == "int":
                    kinds[j] = "float"
                continue
            try:
                int(f)
                continue
            except ValueError:
                pass
            try:
                float(f)
                kinds[j] = "float"
            except ValueError:
                kinds[j] = "str"
    return kinds


def read_csv(
    path,
    delimiter: str = ",",
    dtypes: Optional[Dict[str, str]] = None,
    num_blocks: Optional[int] = None,
    rows_per_chunk: int = 262_144,
):
    """Read a header-ed CSV into a frame: int64/float64 columns for
    numeric data (types inferred from a sample; empty numeric fields →
    NaN via float promotion), string columns host-resident.

    Unquoted files parse in ONE native C++ pass (rowpack.parse_csv — the
    data-ingestion edge of the marshalling layer); quoted files and
    builds without the native module take the csv-module path with the
    same semantics. ``dtypes`` ({column: "int64"|"float64"|"string"})
    overrides inference per column.

    ``path`` may also be a **directory or a list of part files** (each
    with its own header). Parts then ingest chunk by chunk through a
    spillable :class:`~tensorframes_tpu.blockstore.BlockStore` instead
    of materializing the whole table: peak ingest RSS is bounded by the
    largest single part plus the ``TFTPU_BLOCK_BUDGET_MB`` budget, and
    the returned frame's dense blocks are zero-read ``np.memmap`` views
    over the spilled segments (the OS page cache owns residency; host
    string columns still load eagerly). Column types are inferred from
    the FIRST part and applied to the rest — pass ``dtypes`` when parts
    could infer differently. ``num_blocks`` is honored via an explicit
    ``repartition`` (which materializes — leave it None to stay
    out-of-core; block structure then mirrors the ingest chunks).
    For frames that must never materialize at all, walk
    :func:`scan_csv` with ``blockstore.stream_chain`` instead.
    """
    if isinstance(path, (list, tuple)) or os.path.isdir(path):
        frame = _frame_via_store(
            scan_csv(
                path, delimiter=delimiter, dtypes=dtypes,
                rows_per_chunk=rows_per_chunk,
            ),
            what=f"read_csv({path!r})",
        )
        if frame is None:
            # every part was header-only: the single-file empty path
            # builds the correctly-typed zero-row frame (scan_csv
            # yields only non-empty blocks, and empty string columns
            # cannot round-trip through frame_from_arrays)
            [first, *_] = _part_files(path, (".csv", ".tsv", ".txt"))
            return _read_csv_single(
                first, delimiter=delimiter, dtypes=dtypes,
                num_blocks=num_blocks,
            )
        return frame.repartition(num_blocks) if num_blocks else frame
    return _read_csv_single(
        path, delimiter=delimiter, dtypes=dtypes, num_blocks=num_blocks
    )


def _read_csv_single(
    path: str,
    delimiter: str = ",",
    dtypes: Optional[Dict[str, str]] = None,
    num_blocks: Optional[int] = None,
):
    """One CSV file → frame (the pre-dataplane ``read_csv`` body)."""
    import csv as _csv
    import re

    from . import native
    from .frame import frame_from_arrays

    with open(path, "rb") as f:
        data = f.read()
    head, _, body = data.partition(b"\n")
    quoted = b'"' in data
    head_text = head.decode("utf-8").rstrip("\r")
    if quoted:
        # quoted files get real csv parsing everywhere, header included
        names = next(_csv.reader([head_text], delimiter=delimiter))
        names = [h.strip() for h in names]
    else:
        names = [h.strip() for h in head_text.split(delimiter)]
    ncols = len(names)

    _KIND_FOR = {"int64": "int", "float64": "float", "string": "str"}

    def apply_overrides(kinds):
        for j, n in enumerate(names):
            want = (dtypes or {}).get(n)
            if want is not None:
                if want not in _KIND_FOR:
                    raise ValueError(
                        f"read_csv: unsupported dtype {want!r} for column "
                        f"{n!r}; supported: {sorted(_KIND_FOR)}"
                    )
                kinds[j] = _KIND_FOR[want]
        return kinds

    if re.search(rb"\S", body) is None:
        # empty lists can't infer a schema; build explicit column infos
        from . import dtypes as dt
        from .frame import TensorFrame
        from .schema import ColumnInfo, Schema
        from .shape import Shape, Unknown

        kinds = apply_overrides(["float"] * ncols)
        kind_dt = {"int": "int64", "float": "float64", "str": "string"}
        infos, block = [], {}
        for n, k in zip(names, kinds):
            scalar = dt.by_name(kind_dt[k])
            infos.append(ColumnInfo(n, scalar, Shape((Unknown,))))
            block[n] = (
                [] if k == "str" else np.empty((0,), scalar.np_dtype)
            )
        return TensorFrame([block], Schema(infos))

    # sample-based inference over a bounded prefix (first 100 lines of the
    # first MiB — never materializes the whole file line-by-line), then
    # per-column override
    prefix = body[: 1 << 20]
    lines = prefix.split(b"\n")
    if len(body) > len(prefix):
        lines = lines[:-1]  # last line may be truncated mid-field
    sample_text = [
        line.decode("utf-8", "replace").rstrip("\r")
        for line in lines[:100]
        if line.strip()
    ]
    if quoted:
        sample = list(_csv.reader(sample_text, delimiter=delimiter))
    else:
        sample = [t.split(delimiter) for t in sample_text]
    kinds = apply_overrides(_infer_csv_types(sample, ncols))

    mod_ok = native.available() and not quoted and len(delimiter) == 1
    cols: Dict[str, object] = {}
    if mod_ok:
        codes = [{"int": 3, "float": 0, "str": 4}[k] for k in kinds]
        out = native._load().parse_csv(body, ord(delimiter), codes)
        nrow = out[-1]
        for j, n in enumerate(names):
            if kinds[j] == "str":
                cols[n] = out[j]
            else:
                npdt = np.int64 if kinds[j] == "int" else np.float64
                cols[n] = np.frombuffer(out[j], dtype=npdt)
        logger.debug("read_csv: native parse of %d rows", nrow)
    else:
        text = body.decode("utf-8", "replace").splitlines()
        reader = _csv.reader(text, delimiter=delimiter)
        raw: List[List[str]] = [r for r in reader if r]
        for j, n in enumerate(names):
            vals = [r[j] if j < len(r) else "" for r in raw]
            if kinds[j] == "int":
                cols[n] = np.asarray([int(v) for v in vals], np.int64)
            elif kinds[j] == "float":
                cols[n] = np.asarray(
                    [float(v) if v != "" else np.nan for v in vals], np.float64
                )
            else:
                cols[n] = vals
    return frame_from_arrays(cols, num_blocks=num_blocks)


# ---------------------------------------------------------------------------
# Chunked multi-part ingest through the block store (ROADMAP #3)
# ---------------------------------------------------------------------------

def _part_files(paths, exts) -> List[str]:
    """Resolve a directory (sorted, extension-filtered) or an explicit
    list (caller order preserved — it IS the row order) to part files."""
    if isinstance(paths, (list, tuple)):
        out = [os.fspath(p) for p in paths]
        missing = [p for p in out if not os.path.isfile(p)]
        if missing:
            raise FileNotFoundError(f"part file(s) not found: {missing}")
        if not out:
            raise ValueError("empty part-file list")
        return out
    out = []
    for name in sorted(os.listdir(paths)):
        full = os.path.join(paths, name)
        if name.startswith((".", "_")) or not os.path.isfile(full):
            continue
        if os.path.splitext(name)[1].lower() in exts:
            out.append(full)
    if not out:
        raise ValueError(
            f"no part files matching {sorted(exts)} under {paths!r}"
        )
    return out


#: Part-file extensions per scan kind (the same sets scan_csv /
#: scan_parquet filter by).
PART_EXTS: Dict[str, tuple] = {
    "csv": (".csv", ".tsv", ".txt"),
    "parquet": (".parquet", ".pq"),
}


def part_manifest(paths, kind: str = "csv") -> List[Tuple[str, str]]:
    """Chunk-arrival manifest of a growing directory (or explicit part
    list): ``[(path, signature), ...]`` in scan order. The signature is
    :func:`compilecache.fingerprint.part_signature` (basename + size +
    mtime_ns — O(#files) stat calls, no content read), so a registered
    query can decide per request whether anything arrived, changed, or
    disappeared since its cached partials were computed: appended parts
    show up as new (path, sig) rows, a rewritten part keeps its path
    but moves its signature, a removed part drops its row."""
    from .compilecache.fingerprint import part_signature

    try:
        exts = PART_EXTS[kind]
    except KeyError:
        raise ValueError(
            f"part_manifest kind must be one of {sorted(PART_EXTS)}, "
            f"got {kind!r}"
        ) from None
    return [(p, part_signature(p)) for p in _part_files(paths, exts)]


def part_frame(path: str, kind: str = "csv", delimiter: str = ",",
               dtypes: Optional[Dict[str, str]] = None):
    """ONE part file → one frame (possibly zero-row for a header-only
    CSV part). The per-chunk read of the registered-query incremental
    path: an appended part is re-read alone, never the directory.
    ``dtypes`` pins CSV column types exactly like :func:`scan_csv`'s
    first-part pinning — callers that read parts independently must pin
    from one authoritative part themselves or two chunks of one table
    could parse under different types."""
    if kind == "csv":
        return _read_csv_single(
            path, delimiter=delimiter, dtypes=(dtypes or None),
            num_blocks=1,
        )
    if kind == "parquet":
        _require_pyarrow()
        import pyarrow.parquet as pq

        return frame_from_arrow(pq.read_table(path), num_blocks=1)
    raise ValueError(
        f"part_frame kind must be one of {sorted(PART_EXTS)}, got {kind!r}"
    )


def _iter_row_chunks(block: Dict[str, object], rows_per_chunk: int):
    n = 0
    for v in block.values():
        n = len(v)
        break
    for lo in range(0, n, max(1, rows_per_chunk)):
        hi = min(n, lo + rows_per_chunk)
        yield {k: v[lo:hi] for k, v in block.items()}


def scan_csv(
    paths,
    delimiter: str = ",",
    dtypes: Optional[Dict[str, str]] = None,
    rows_per_chunk: int = 262_144,
) -> Iterator[Dict[str, object]]:
    """Chunked CSV scan: yield ``{column: array|list}`` blocks of at
    most ``rows_per_chunk`` rows from a directory / list of part files,
    one part in memory at a time — the block source for
    ``blockstore.stream_chain`` (multi-TB scans never materialize).
    Types are inferred from the first part WITH rows and pinned as
    overrides for the rest (pass ``dtypes`` to pin them yourself); a
    part whose values cannot parse under the pinned types raises —
    parts must be type-consistent. Only non-empty blocks are yielded
    (header-only parts contribute nothing)."""
    overrides: Dict[str, str] = dict(dtypes or {})
    pinned = False
    for part in _part_files(paths, (".csv", ".tsv", ".txt")):
        f = _read_csv_single(
            part, delimiter=delimiter,
            dtypes=(overrides or None), num_blocks=1,
        )
        if not pinned and f.num_rows > 0:
            # pin from the first part WITH rows: a header-only part
            # infers float64 everywhere and would poison the overrides
            for info in f.schema:
                overrides.setdefault(info.name, info.dtype.name)
            pinned = True
        if f.num_rows == 0:
            continue  # header-only part: nothing to yield (and see the
            # pinning guard above — its float defaults must not stick)
        [block] = f.blocks()
        yield from _iter_row_chunks(block, rows_per_chunk)


def scan_parquet(
    paths, rows_per_chunk: int = 262_144
) -> Iterator[Dict[str, object]]:
    """Chunked Parquet scan (via pyarrow's batch reader): yield blocks
    of at most ``rows_per_chunk`` rows from a directory / list of part
    files without materializing any full table — the block source for
    ``blockstore.stream_chain``."""
    pa = _require_pyarrow()
    import pyarrow.parquet as pq

    for part in _part_files(paths, (".parquet", ".pq")):
        pf = pq.ParquetFile(part)
        for batch in pf.iter_batches(batch_size=max(1, rows_per_chunk)):
            if batch.num_rows == 0:
                continue
            f = frame_from_arrow(
                pa.Table.from_batches([batch]), num_blocks=1
            )
            [block] = f.blocks()
            yield block


def _frame_via_store(blocks_iter, what: str):
    """Ingest a block stream through a spillable BlockStore and rebuild
    a TensorFrame over memmap views of the spilled segments. The store
    is pinned to the frame (dropped with it); ingest RSS is bounded by
    the resident budget, not the table."""
    import weakref

    from .blockstore import BlockStore
    from .blockstore.partitioner import SpilledFrame
    from .frame import frame_from_arrays

    store = BlockStore()
    refs, schema, sig = [], None, None
    try:
        for block in blocks_iter:
            f = frame_from_arrays(block, num_blocks=1)
            fsig = [(i.name, i.dtype.name) for i in f.schema]
            if schema is None:
                schema, sig = f.schema, fsig
            elif fsig != sig:
                raise ValueError(
                    f"{what}: part schema drifted — first part "
                    f"{sig}, this chunk {fsig}; pass dtypes= to pin "
                    "column types across parts"
                )
            [b] = f.blocks()
            refs.append(store.put(b))
    except BaseException:
        store.close()
        raise
    if schema is None:
        # zero non-empty chunks: the caller owns the typed empty-frame
        # fallback (scan_* yield only non-empty blocks)
        store.close()
        return None
    spilled = SpilledFrame(store, refs, schema, owns_store=True)
    frame = spilled.to_frame(mmap=True)
    # pin the spill segments to the frame's lifetime (deleted with it;
    # on Linux open memmaps stay valid over the unlink)
    frame._data_plane = spilled
    weakref.finalize(frame, spilled.drop)
    logger.info(
        "%s: ingested %d chunk(s), %d rows via block store "
        "(resident=%d spilled=%d)",
        what, len(refs), spilled.num_rows, store.resident_bytes,
        store.spilled_bytes,
    )
    return frame


def write_csv(frame, path: str, delimiter: str = ",") -> None:
    """Write a frame to a header-ed CSV (the inverse of :func:`read_csv`).

    Dense numeric columns format via numpy; string/host columns via str().
    Vector cells are rejected — CSV is a scalar-column format (same rule
    as the reference's string support: scalars only, datatypes.scala:577-581).
    """
    import csv as _csv

    cols = {}
    for info in frame.schema:
        if info.cell_shape.rank > 0:
            raise ValueError(
                f"write_csv: column {info.name!r} has cell shape "
                f"{info.cell_shape}; CSV holds scalar columns only"
            )
        v = frame.column_values(info.name)
        cols[info.name] = v
    names = list(cols)
    n = len(next(iter(cols.values()))) if names else 0
    with open(path, "w", newline="") as f:
        w = _csv.writer(f, delimiter=delimiter)
        w.writerow(names)
        for i in range(n):
            w.writerow([cols[c][i] for c in names])


# ---------------------------------------------------------------------------
# Arrow / Parquet interop (optional: gated on pyarrow)
# ---------------------------------------------------------------------------
#
# Arrow IS the columnar interchange format the reference's Row-marshalling
# layer never had: an arrow Table's numeric columns view as numpy without
# copying, so table → frame → HBM is two zero-copy hops + one DMA
# (jax.device_put). Everything here degrades with a clear ImportError if
# pyarrow is absent — it is an optional dependency.

def _require_pyarrow():
    try:
        import pyarrow  # noqa: F401

        return pyarrow
    except ImportError as e:  # pragma: no cover
        raise ImportError(
            "pyarrow is required for arrow/parquet interop "
            "(pip install pyarrow)"
        ) from e


def frame_from_arrow(table, num_blocks: Optional[int] = None):
    """Build a frame from a pyarrow Table (zero-copy for non-null numeric
    columns). Strings become host columns; list-typed columns become
    per-row cells (dense if uniform, ragged otherwise)."""
    pa = _require_pyarrow()
    from .frame import frame_from_arrays

    data: Dict[str, object] = {}
    for name in table.column_names:
        col = table.column(name)
        if isinstance(col, pa.ChunkedArray):
            col = col.combine_chunks()
        t = col.type
        if pa.types.is_integer(t) or pa.types.is_floating(t):
            if col.null_count:
                if pa.types.is_integer(t):
                    raise ValueError(
                        f"Column {name!r} has nulls; integer columns cannot "
                        "represent missing values (cast to float upstream)"
                    )
                data[name] = col.to_numpy(zero_copy_only=False)
            else:
                data[name] = col.to_numpy(zero_copy_only=True)
        elif pa.types.is_boolean(t):
            data[name] = col.to_numpy(zero_copy_only=False)
        elif pa.types.is_string(t) or pa.types.is_large_string(t):
            data[name] = col.to_pylist()
        elif pa.types.is_binary(t) or pa.types.is_large_binary(t):
            data[name] = col.to_pylist()
        elif pa.types.is_list(t) or pa.types.is_large_list(t) or (
            pa.types.is_fixed_size_list(t)
        ):
            data[name] = [
                np.asarray(cell) if cell is not None else None
                for cell in col.to_pylist()
            ]
        else:
            raise TypeError(f"Column {name!r}: unsupported arrow type {t}")
    return frame_from_arrays(data, num_blocks=num_blocks)


def frame_to_arrow(frame):
    """Frame → pyarrow Table. Scalar numeric columns are zero-copy;
    vector cells become arrow lists; host columns pass through."""
    pa = _require_pyarrow()

    arrays = {}
    for info in frame.schema:
        v = frame.column_values(info.name)
        if isinstance(v, np.ndarray) and v.dtype != object and v.ndim == 1:
            arrays[info.name] = pa.array(v)
        elif isinstance(v, np.ndarray) and v.dtype != object:
            arrays[info.name] = pa.array([row.tolist() for row in v])
        else:
            arrays[info.name] = pa.array(list(v))
    return pa.table(arrays)


def read_parquet(
    path, num_blocks: Optional[int] = None, rows_per_chunk: int = 262_144
):
    """Read a parquet file into a frame (via pyarrow).

    ``path`` may also be a directory or a list of part files: parts
    then ingest batch by batch through a spillable block store (same
    contract as the multi-part ``read_csv`` — bounded ingest RSS,
    memmap-backed dense blocks, ``num_blocks`` honored only via an
    explicit materializing repartition). For never-materialize scans,
    walk :func:`scan_parquet` with ``blockstore.stream_chain``."""
    _require_pyarrow()
    import pyarrow.parquet as pq

    if isinstance(path, (list, tuple)) or os.path.isdir(path):
        frame = _frame_via_store(
            scan_parquet(path, rows_per_chunk=rows_per_chunk),
            what=f"read_parquet({path!r})",
        )
        if frame is None:  # all parts empty: the single-file path owns
            # the typed zero-row frame (see read_csv)
            [first, *_] = _part_files(path, (".parquet", ".pq"))
            return frame_from_arrow(
                pq.read_table(first), num_blocks=num_blocks
            )
        return frame.repartition(num_blocks) if num_blocks else frame
    return frame_from_arrow(pq.read_table(path), num_blocks=num_blocks)


def write_parquet(frame, path: str) -> None:
    """Write a frame to a parquet file (via pyarrow)."""
    _require_pyarrow()
    import pyarrow.parquet as pq

    pq.write_table(frame_to_arrow(frame), path)

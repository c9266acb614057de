"""Warmup: precompile expected feed-shape buckets ahead of traffic.

``tfs.warmup(frame_or_schema, programs_or_verbs, ...)`` builds (or
disk-loads) the executor's per-shape executables for the shapes real
dispatches will use, **without executing anything** — warmed keys are
marked dispatched, so the first real dispatch at that shape is a
jit-cache hit with zero compile. Combined with a persistent store
(``TFTPU_COMPILE_CACHE``), a serving process can reach first-request
latency equal to steady-state latency.

Shape selection mirrors the dispatch paths exactly:

* **block mode** (``map_blocks``): the frame partitioner yields at most
  two block row counts (``n//k`` and ``n//k + 1``) — both are warmed;
  a materialized frame's actual distinct block sizes win over the
  estimate.
* **rows mode** (``map_rows``): lead dims are rounded through the same
  power-of-two bucket ladder the executor pads into
  (:func:`~tensorframes_tpu.ops.executor.bucket_rows`).
* an explicit ``rows=[...]`` overrides both; a recorded **shape
  manifest** (``manifest=``, appended by the executor on every store
  miss) replays yesterday's real traffic shapes.

Pass :class:`~tensorframes_tpu.program.Program` objects (from
``tfs.compile_program``) rather than bare functions when you want the
warmed in-process executables to be reused by later verb calls — a
bare function normalizes to a fresh Program per call, so its warmth
lives only in the persistent store (still skipping XLA, not the trace).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..utils import get_logger

logger = get_logger(__name__)

__all__ = [
    "WarmupReport", "warmup", "warm_program", "partitioner_row_counts",
    "serving_row_buckets", "decode_slot_buckets", "decode_warmup_grid",
    "packed_prefill_buckets",
]


@dataclasses.dataclass
class WarmupReport:
    """What a warmup pass did: one row per (program, kind, shape)."""

    entries: List[dict] = dataclasses.field(default_factory=list)

    def add(self, subject: str, kind: str, rows: Optional[int],
            status: str, detail: str = "") -> None:
        self.entries.append({
            "subject": subject, "kind": kind, "rows": rows,
            "status": status, "detail": detail,
        })

    def counts(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for e in self.entries:
            out[e["status"]] = out.get(e["status"], 0) + 1
        return out

    @property
    def compiled(self) -> int:
        return self.counts().get("compiled", 0)

    @property
    def disk_hits(self) -> int:
        return self.counts().get("disk", 0)

    def pretty(self) -> str:
        c = self.counts()
        head = "warmup: " + ", ".join(
            f"{k}={v}" for k, v in sorted(c.items())
        ) if c else "warmup: nothing to do"
        lines = [head]
        for e in self.entries:
            rows = "?" if e["rows"] is None else e["rows"]
            extra = f" ({e['detail']})" if e["detail"] else ""
            lines.append(
                f"  {e['subject']} [{e['kind']} rows={rows}]: "
                f"{e['status']}{extra}"
            )
        return "\n".join(lines)

    def __repr__(self) -> str:  # pragma: no cover - convenience
        return self.pretty()


def _as_program_list(fetches, schema, block: bool, feed_dict):
    """Normalize the ``programs_or_verbs`` argument: a single fetches
    item or a sequence of them, each becoming one Program. A list of
    DSL nodes is ONE multi-output program (verb semantics)."""
    from ..dsl.node import Node
    from ..ops.verbs import _apply_feed_dict, _normalize_program
    from ..program import Program

    if isinstance(fetches, (list, tuple)) and fetches and not all(
        isinstance(f, Node) for f in fetches
    ):
        items = list(fetches)
    else:
        items = [fetches]
    out = []
    for item in items:
        if isinstance(item, Program) and item.outputs:
            program = item
        else:
            if schema is None:
                raise ValueError(
                    "warmup() needs a frame or schema to normalize "
                    "non-Program fetches (pass tfs.compile_program "
                    "results to warm without one)"
                )
            program, _ = _normalize_program(
                item, schema, block=block, feed_dict=feed_dict
            )
        program = _apply_feed_dict(program, feed_dict)
        out.append(program)
    return out


def partitioner_row_counts(total: int, num_blocks: int) -> List[int]:
    """The at-most-two block sizes the frame partitioner yields for
    ``total`` rows in ``num_blocks`` blocks (``n//k`` and ``n//k+1``) —
    the serving-side estimate when only expected traffic volume is
    known: ``warmup(schema, prog, rows=partitioner_row_counts(n, k))``."""
    num_blocks = max(1, int(num_blocks))
    base = total // num_blocks
    sizes = {base, base + 1} if total % num_blocks else {base}
    return sorted(s for s in sizes if s > 0) or [total]


def serving_row_buckets(max_rows: int) -> List[int]:
    """The power-of-two lead-dim buckets a serving batcher's flushes
    can land on: every ladder bucket up to ``bucket_rows(max_rows)``
    (the serving layer caps any single flush at
    ``ServingConfig.max_batch_rows`` = ``max_rows``). ONE policy,
    stated once: the batcher pads flushes through
    :func:`~tensorframes_tpu.ops.executor.bucket_rows`, and
    ``warm_program(p, rows=serving_row_buckets(m), block=False)``
    precompiles exactly those keys — which is how a warmed server
    sustains zero steady-state compiles under any request-size mix."""
    from ..ops.executor import bucket_rows, bucket_table

    max_rows = int(max_rows)
    if max_rows < 1:
        raise ValueError(f"max_rows must be >= 1, got {max_rows}")
    table = bucket_table()
    if max_rows > table[-1]:
        # beyond the ladder bucket_rows falls back to EXACT counts, so
        # a batcher flushing (table[-1], max_rows] sizes would dispatch
        # never-warmed shapes — the zero-steady-state-compile contract
        # cannot hold; refuse instead of warming a false promise
        raise ValueError(
            f"max_rows={max_rows} exceeds the bucket ladder's top "
            f"({table[-1]}): flush sizes above the ladder dispatch at "
            "exact, unwarmable shapes. Raise TFTPU_MAX_BUCKET_DOUBLINGS"
            "/configure(max_bucket_doublings=) or lower "
            "ServingConfig.max_batch_rows"
        )
    top = bucket_rows(max_rows)
    return [b for b in table if b <= top]


def decode_slot_buckets(max_slots: int) -> List[int]:
    """The slot-count buckets the iterative decode engine's batched
    step can dispatch at — BY CONSTRUCTION the same power-of-two ladder
    as :func:`serving_row_buckets`, because a decode slot count is a
    vmapped lead dim like any flush's row count. ONE bucket policy,
    stated once, shared by three consumers that must never drift:

    * the flush batcher pads coalesced rows through
      ``ops.executor.bucket_rows``;
    * ``Server.start()`` warms ``serving_row_buckets(max_batch_rows)``;
    * the decode engine pads its running slot count through THIS ladder
      and warms every (phase × bucket) pair at start
      (:func:`decode_warmup_grid`).

    The delegation (not a reimplementation) is the drift guard: any
    change to the ladder — ``min_bucket``, ``max_bucket_doublings``,
    the beyond-ladder refusal — applies to rows and slots identically.
    Asserted against ``bucket_rows`` below so a future fork of either
    policy fails loudly here rather than as a steady-state compile."""
    from ..ops.executor import bucket_rows

    buckets = serving_row_buckets(max_slots)
    for n in range(1, int(max_slots) + 1):
        if bucket_rows(n) not in buckets:
            raise AssertionError(
                f"bucket policy drift: bucket_rows({n}) = "
                f"{bucket_rows(n)} is not in the warmed ladder "
                f"{buckets} — serving_row_buckets and bucket_rows no "
                "longer agree; fix the shared ladder, do not fork it"
            )
    return buckets


def packed_prefill_buckets(max_prompt_len: int, block: int) -> List[int]:
    """The total-token ladder of a packed prefill (several prompts in one
    call, each starting on a ``block`` edge): 1, 1.5, 2, 3 and 4 times
    the largest prompt's rows rounded up to a power of two, each a whole
    number of blocks, and never more buckets than the one-sequence
    ladder :func:`serving_row_buckets` has. Half-octave steps pad a call
    by at most a third (a sixth on average) where a power-of-two ladder
    pads it by up to a half; the top bucket holds four of the largest
    prompts. Each bucket is a program to load and warm at start, a large
    one (a packed prefill folds every layer's attention in a loop), so
    the ladder is short. For ``max_prompt_len`` 896 and 64-row blocks:
    1,024, 1,536, 2,048, 3,072, 4,096."""
    block = int(block)
    if block < 1:
        raise ValueError(f"block must be >= 1, got {block}")
    largest = -(-int(max_prompt_len) // block) * block
    unit = 1 << (largest - 1).bit_length()
    ladder = sorted({-(-(unit * k // 2) // block) * block
                     for k in (2, 3, 4, 6, 8)})
    return ladder[:len(serving_row_buckets(max_prompt_len))]


def decode_warmup_grid(max_slots: int, max_prompt_len: int,
                       pack_block: Optional[int] = None
                       ) -> Dict[str, List[int]]:
    """The slot-count × phase bucket grid a decode engine must warm for
    zero steady-state compiles: one decode-step executable per slot
    bucket, one prefill executable per prompt-length bucket (prompt
    lengths pad through the SAME ladder — a prefill chunk's token dim
    is a vmapped lead dim too), or, for an engine that packs prompts
    into one prefill (``pack_block`` given), one per bucket of
    :func:`packed_prefill_buckets` in that ladder's place. The engine's
    ``start()`` walks exactly this grid; tests assert no dispatch ever
    lands off it."""
    return {
        "decode": decode_slot_buckets(max_slots),
        "prefill": (serving_row_buckets(max_prompt_len) if pack_block is None
                    else packed_prefill_buckets(max_prompt_len, pack_block)),
    }


def _target_row_counts(frame, rows, block: bool) -> List[int]:
    if rows is not None:
        counts = sorted({int(r) for r in rows if int(r) > 0})
        if not counts:
            raise ValueError("warmup rows= must contain positive ints")
        return counts
    if frame is None:
        raise ValueError(
            "warmup() needs rows=[...] when no frame is given"
        )
    if frame.is_materialized:
        from ..frame import _block_num_rows

        return sorted({_block_num_rows(b) for b in frame.blocks()})
    # lazy frame: never force it — a pinned block lead dim in the
    # schema IS the block row count; otherwise give up loudly
    for col in frame.schema.columns:
        d = col.block_shape.dims[0]
        if isinstance(d, int):
            return [int(d)]
    raise ValueError(
        "warmup() cannot infer block sizes from a lazy frame with "
        "unknown row counts; pass rows=[...] (warmup never forces "
        "a pending computation)"
    )


def _abstract_feeds(program, n: int, kind: str):
    """ShapeDtypeStruct feeds at lead dim ``n``, exactly as the
    executor will see them (map_rows buckets the vmapped lead dim;
    dtypes follow the program's input specs, which gather_feeds casts
    feeds to). Returns None when an input has unknown inner dims."""
    import jax
    import jax.numpy as jnp

    from ..shape import Unknown

    feeds = {}
    for spec in program.inputs:
        dims = list(spec.shape.dims)
        if kind == "block":
            dims[0] = n
            cell = dims[1:]
        else:
            cell = dims
            dims = [n] + dims
        if any(d == Unknown for d in cell):
            return None
        # the key must match runtime exactly: run paths jnp.asarray the
        # gathered feeds, which can re-type under the x64 flag
        dtype = jnp.asarray(np.zeros((), dtype=spec.dtype.np_dtype)).dtype
        feeds[spec.name] = jax.ShapeDtypeStruct(
            tuple(int(d) for d in dims), dtype
        )
    return feeds


def _default_donate() -> bool:
    """Match the verbs' choice for host-sourced feeds: donate when the
    config asks for it and the backend implements it."""
    from ..config import get_config
    from ..ops.executor import donation_supported

    return bool(get_config().donate_inputs) and donation_supported()


def warm_program(program, rows: Sequence[int], block: bool = True,
                 donate: Optional[bool] = None,
                 report: Optional[WarmupReport] = None) -> WarmupReport:
    """Warm one analyzed Program at explicit lead-dim row counts (the
    CLI surface; :func:`warmup` is the frame-aware front door)."""
    from ..ops.executor import bucket_rows

    report = report if report is not None else WarmupReport()
    donate = _default_donate() if donate is None else bool(donate)
    kind = "block" if block else "vmap"
    subject = f"Program(inputs={program.input_names})"
    if block:
        targets = sorted({int(r) for r in rows})
    else:
        # map_rows buckets adaptively: exact shapes while the frame
        # presents few sizes (the partitioner's ≤2), power-of-two
        # buckets once shapes proliferate — warm both regimes
        targets = sorted(
            {int(r) for r in rows} | {bucket_rows(int(r)) for r in rows}
        )
    for n in targets:
        feeds = _abstract_feeds(program, n, kind)
        if feeds is None:
            report.add(subject, kind, n, "skipped",
                       "unknown inner dims (ragged cells warm per group "
                       "at dispatch)")
            continue
        status = program.compiled().warm(kind, feeds, donate=donate)
        report.add(subject, kind, n, status)
    return report


def _manifest_row_matches(program, row) -> bool:
    """A manifest row targets this program only when every recorded
    input matches the program's spec by name, dtype, AND known cell
    dims — the manifest is store-wide, and warming program A with
    program B's shapes (they often share names like 'x' or 'images')
    would burn spurious multi-second compiles on junk keys."""
    import jax.numpy as jnp

    from ..shape import Unknown

    inputs = row.get("inputs", [])
    if sorted(n for (n, _, _) in inputs) != sorted(program.input_names):
        return False
    kind = row.get("kind", "block")
    for (name, shape, dtype) in inputs:
        try:
            spec = program.input(name)
        except KeyError:
            return False
        want = jnp.asarray(np.zeros((), dtype=spec.dtype.np_dtype)).dtype
        if str(want) != str(np.dtype(dtype)):
            return False
        # recorded shapes are block-level (post-gather): lead dim is the
        # row count; the tail must fit the spec's cell dims
        cell = list(spec.shape.dims[1:]) if kind == "block" \
            else list(spec.shape.dims)
        if len(shape) != len(cell) + 1:
            return False
        for got, want_d in zip(shape[1:], cell):
            if want_d != Unknown and int(got) != int(want_d):
                return False
    return True


def _warm_from_manifest(programs, manifest_rows, report: WarmupReport,
                        donate: Optional[bool]) -> None:
    import jax

    for program in programs:
        subject = f"Program(inputs={program.input_names})"
        for row in manifest_rows:
            if not _manifest_row_matches(program, row):
                continue
            if row.get("sharded"):
                # record_miss(sharded=True) marks feeds with non-trivial
                # placements: shapes alone under-specify the executable's
                # layout, so replaying would compile (and publish) an
                # UNSHARDED key the real sharded dispatch never hits —
                # warm those via warmup(frame.to_device(mesh), ...)
                report.add(subject, row.get("kind", "block"), None,
                           "skipped", "sharded manifest row (warm via a "
                           "sharded frame instead)")
                continue
            try:
                feeds = {
                    n: jax.ShapeDtypeStruct(
                        tuple(int(d) for d in s), np.dtype(t)
                    )
                    for (n, s, t) in row["inputs"]
                }
            except (TypeError, ValueError):
                continue  # torn or stale manifest row
            d = row.get("donate", False) if donate is None else donate
            status = program.compiled().warm(
                row.get("kind", "block"), feeds,
                donate=bool(d),
            )
            lead = None
            for v in feeds.values():
                lead = int(v.shape[0]) if v.shape else None
                break
            report.add(subject, row.get("kind", "block"), lead, status,
                       "manifest")


def warmup(frame_or_schema, programs_or_verbs, *, rows=None,
           block: bool = True, feed_dict=None, donate: Optional[bool] = None,
           manifest=None) -> WarmupReport:
    """Precompile the executables real traffic will need (ISSUE 5).

    ``frame_or_schema`` — a TensorFrame (block sizes inferred from the
    partitioner contract / the materialized blocks), a Schema (pass
    ``rows=``), or None when every fetch is an analyzed Program.
    ``programs_or_verbs`` — one fetches item or a sequence: Programs,
    plain functions, or DSL nodes (a list of nodes is one program).
    ``rows=[...]`` — explicit lead-dim row counts (map_rows targets are
    rounded through the executor's power-of-two bucket ladder).
    ``manifest=`` — True (the active store's recorded miss manifest) or
    a path: replay previously-observed feed shapes instead of/in
    addition to the partitioner estimate.

    Returns a :class:`WarmupReport`; warm keys make the first real
    dispatch a jit-cache hit with zero compile (and, with a persistent
    store, zero XLA even in a fresh process).
    """
    schema = getattr(frame_or_schema, "schema", frame_or_schema)
    frame = frame_or_schema if hasattr(frame_or_schema, "schema") else None
    programs = _as_program_list(
        programs_or_verbs, schema, block=block, feed_dict=feed_dict
    )
    report = WarmupReport()

    manifest_rows = []
    if manifest:
        if manifest is True:
            from .store import active_store

            store = active_store()
            if store is None:
                raise ValueError(
                    "warmup(manifest=True) needs an active persistent "
                    "store — set TFTPU_COMPILE_CACHE or "
                    "configure(compilation_cache_dir=...), or pass the "
                    "manifest path explicitly"
                )
            manifest_rows = store.read_manifest()
        else:
            import os as _os

            if not _os.path.exists(str(manifest)):
                raise ValueError(
                    f"warmup manifest {manifest!r} does not exist — a "
                    "silently-empty warmup would leave the first "
                    "request paying the full compile"
                )
            from .store import CompileCacheStore

            probe = CompileCacheStore.__new__(CompileCacheStore)
            probe.manifest_path = str(manifest)
            manifest_rows = CompileCacheStore.read_manifest(probe)
        _warm_from_manifest(programs, manifest_rows, report, donate)

    if rows is not None or frame is not None or not manifest:
        counts = _target_row_counts(frame, rows, block)
        for program in programs:
            warm_program(program, counts, block=block, donate=donate,
                         report=report)
    return report

"""The five verbs: map_blocks, map_rows, reduce_blocks, reduce_rows,
aggregate.

Public surface parity with the reference
(``OperationsInterface``, Operations.scala:20-135; Python client
core.py:144-419). Execution is TPU-native:

* ``map_blocks`` — one jitted XLA program per block (per distinct block
  shape), replacing Session-per-partition (DebugRowOps.scala:305-400).
* ``map_rows`` — ``jax.vmap`` over the block's rows (one compiled program,
  rows batched onto the MXU), replacing the per-row Session loop
  (DebugRowOps.scala:826-864); ragged rows fall back to per-shape
  compilation (≙ per-row dynamic lead dims, TFDataOps.scala:90-103).
* ``reduce_rows`` — a ``lax.scan`` pairwise fold inside one jit per block,
  then across block partials (≙ sequential performReducePairwise,
  DebugRowOps.scala:939-979, minus the per-pair Session.run overhead).
* ``reduce_blocks`` — per-block program run, partials stacked and reduced
  once more (≙ performReduceBlock + driver pairwise RDD.reduce,
  DebugRowOps.scala:510-533 — the stack-and-rerun replaces O(blocks)
  driver round-trips).
* ``aggregate`` — keyed aggregation: a vectorized ``jax.ops.segment_*``
  fast path when the fetches are algebraic reducers, else chunked
  compaction with a bounded buffer (≙ TensorFlowUDAF's compact-every-10,
  DebugRowOps.scala:608-702).

Programs may be DSL nodes, plain Python functions over jnp, or loaded
StableHLO artifacts (see program.py).
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from .. import dtypes as dt
from ..config import get_config
from ..dsl.node import Node, compile_fetches, segment_reduce_info
from ..frame import Block, GroupedData, TensorFrame, _block_num_rows
from ..program import Program, TensorSpec, analyze_program, program_from_function
from ..schema import ColumnInfo, Schema
from ..shape import Shape, Unknown
from ..observability import events as _events
from ..utils import get_logger
from ..utils import profiling
from ..validation import (
    ValidationError,
    validate_map,
    validate_reduce_blocks,
    validate_reduce_rows,
)
from .executor import (
    block_is_ragged,
    bucket_rows,
    gather_feeds,
    make_pair_fold,
    pad_lead_dim,
    pair_fold_body,
)

logger = get_logger(__name__)

Fetches = Union[Node, Sequence[Node], Program, Callable]


def _plan_map_result(
    frame, program: Program, schema: Schema, rows: bool
) -> Optional["TensorFrame"]:
    """Record this map stage on the frame's logical plan instead of
    nesting another compute thunk (tensorframes_tpu/plan): at force
    time the whole chain lowers to one composed XLA dispatch per block.
    Returns None when planning is off (TFTPU_FUSION=0) or re-entrant
    (the lowering pass executes through these same verbs)."""
    from ..plan import ir as plan_ir

    if not plan_ir.fusion_enabled():
        return None
    node = plan_ir.PlanNode(
        "map",
        parent=plan_ir.node_for_parent(frame),
        program=program,
        rows=rows,
        out_names=[o.name for o in program.outputs],
        schema=schema,
    )

    def pending():
        from ..plan.lower import execute_plan

        return execute_plan(node)

    result = TensorFrame(None, schema, pending=pending)
    node.bind(result)
    result._plan = node
    result._produced_by_map = True
    if frame.is_sharded:
        result._mesh = frame.mesh
        result._axis = getattr(frame, "_axis", None)
    return result


def _is_pandas(obj) -> bool:
    try:
        import pandas as pd

        return isinstance(obj, pd.DataFrame)
    except ImportError:  # pragma: no cover
        return False


def _input_specs_from_schema(schema: Schema, block: bool) -> Dict[str, TensorSpec]:
    specs = {}
    for c in schema.device_columns:
        shape = c.block_shape if block else c.cell_shape
        specs[c.name] = TensorSpec(c.name, c.dtype, shape)
    return specs


class NumpyUDF:
    """A numpy UDF captured for verified lifting (``tfs.numpy_udf``).

    The wrapped function receives one *numpy* array per parameter
    (parameter name = column name, block-level) and returns arrays /
    a dict / a tuple of arrays. Capture goes one of two ways:

    * the static lifter (analysis/lifting + plan/lift) synthesizes an
      equivalent pure plan-IR Program and verifies it bit-exactly on a
      boundary-value corpus — the lifted stage fuses like any other
      (no TFG107 barrier), or
    * anything that does not verify runs as a ``jax.pure_callback``
      host stage — exactly what the user wrote, with the decline
      reason counted and surfaced via TFG112 / ``lint --lift-report``.

    Results are bit-identical either way by construction; the lift
    exists purely for speed. Block-level only: ``map_rows`` raises.
    Capture warns (TFG112) when the UDF closes over mutable state —
    the callback re-reads such state per block, so later mutations
    silently rebind its behavior (stale-closure hazard).
    """

    def __init__(self, fn: Callable):
        if not callable(fn) or isinstance(fn, (Node, Program)):
            raise TypeError(
                "numpy_udf wraps a plain Python function over numpy "
                f"arrays; got {type(fn).__name__}")
        self.fn = fn
        self._programs: Dict[tuple, Program] = {}
        self._prog_lock = threading.Lock()
        self._warn_mutable_closures()

    def _warn_mutable_closures(self) -> None:
        from ..analysis.lifting import detect_mutable_closures

        names = detect_mutable_closures(self.fn)
        if not names:
            return
        from ..analysis.diagnostics import Diagnostic, DiagnosticReport

        udf = getattr(self.fn, "__name__", "<udf>")
        DiagnosticReport([
            Diagnostic(
                code="TFG112",
                severity="warn",
                message=(
                    f"numpy_udf {udf!r} closes over mutable state "
                    f"({', '.join(sorted(names))}): the callback re-reads "
                    "it on every block, so mutating it after capture "
                    "silently rebinds the UDF's behavior (stale-closure "
                    "hazard); lifting declines it"
                ),
                subject=udf,
                fix=(
                    "snapshot the captured value into an immutable "
                    "scalar, pass it as a column, or freeze it "
                    "(tuple / float) before capture"
                ),
            )
        ])

    def _materialize(
        self,
        schema: Schema,
        block: bool,
        reduce_mode: Optional[str],
        feed_dict: Optional[Dict[str, str]],
    ) -> Program:
        if not block:
            raise ValidationError(
                "numpy_udf programs are block-level (the host callback "
                "runs once per block, and lifting targets block "
                "expressions); use map_blocks / aggregate, not map_rows"
            )
        specs = _input_specs_from_schema(schema, block)
        for ph, col in (feed_dict or {}).items():
            if col in specs and ph not in specs:
                specs[ph] = TensorSpec(ph, specs[col].dtype, specs[col].shape)
        if reduce_mode == "blocks":
            for c in schema.device_columns:
                specs[f"{c.name}_input"] = TensorSpec(
                    f"{c.name}_input", c.dtype, c.block_shape
                )
        # cache the analyzed Program per capture context so steady-state
        # calls reuse one object (and hence one memoized executable)
        key = (
            tuple(sorted(
                (n, str(s.dtype), tuple(repr(d) for d in s.shape.dims))
                for n, s in specs.items()
            )),
            reduce_mode,
            dt.demotion_active(),
            bool(get_config().udf_lifting),
        )
        with self._prog_lock:
            cached = self._programs.get(key)
        if cached is not None:
            return cached
        from ..plan import lift as plan_lift

        program = plan_lift.build_udf_program(self.fn, specs)
        with self._prog_lock:
            self._programs.setdefault(key, program)
            return self._programs[key]


def numpy_udf(fn: Callable) -> NumpyUDF:
    """Capture a numpy host function for verified lifting — see
    :class:`NumpyUDF`. Usable anywhere block-level fetches are:
    ``map_blocks(numpy_udf(f), frame)``, ``aggregate``,
    ``reduce_blocks``."""
    return NumpyUDF(fn)


def _normalize_program(
    fetches: Fetches,
    schema: Schema,
    block: bool,
    reduce_mode: Optional[str] = None,
    feed_dict: Optional[Dict[str, str]] = None,
    shape_hints: Optional[Dict[str, object]] = None,
) -> Tuple[Program, Optional[List[Tuple[str, str, str]]]]:
    """Accept DSL nodes / a python function / a Program; return an analyzed
    Program plus (for DSL reducer fetches) segment-lowering info.

    ``reduce_mode`` ('rows' | 'blocks') extends the input-spec namespace for
    plain-function fetches so parameters may follow the reduce naming
    contracts (``x_1``/``x_2``, ``x_input``) in addition to column names.
    ``feed_dict`` (placeholder → column) extends it with the renamed
    placeholders, so a function parameter may name a placeholder that a
    feed_dict maps onto a differently-named column (core.py:128-142).
    """
    seg_info = None
    if isinstance(fetches, Program):
        # already-analyzed Programs pass through untouched so their memoized
        # XLA executables (Program.compiled) survive across verb calls;
        # seg_info recorded at compile time keeps the aggregate fast path.
        if fetches.outputs:
            return fetches, getattr(fetches, "seg_info", None)
        program = fetches
    elif isinstance(fetches, Node) or (
        isinstance(fetches, (list, tuple))
        and fetches
        and all(isinstance(f, Node) for f in fetches)
    ):
        nodes = [fetches] if isinstance(fetches, Node) else list(fetches)
        program = compile_fetches(nodes)
        seg_info = segment_reduce_info(nodes)
    elif isinstance(fetches, NumpyUDF):
        # capture → lifted-or-callback Program, fully analyzed and
        # cached on the UDF (like the Program passthrough above, so the
        # memoized executable survives across verb calls — demotion is
        # applied inside the capture)
        program = fetches._materialize(schema, block, reduce_mode, feed_dict)
        return program, getattr(program, "seg_info", None)
    elif callable(fetches):
        specs = _input_specs_from_schema(schema, block)
        for ph, col in (feed_dict or {}).items():
            if col in specs and ph not in specs:
                specs[ph] = TensorSpec(ph, specs[col].dtype, specs[col].shape)
        if reduce_mode == "rows":
            for c in schema.device_columns:
                specs[f"{c.name}_1"] = TensorSpec(f"{c.name}_1", c.dtype, c.cell_shape)
                specs[f"{c.name}_2"] = TensorSpec(f"{c.name}_2", c.dtype, c.cell_shape)
        elif reduce_mode == "blocks":
            for c in schema.device_columns:
                specs[f"{c.name}_input"] = TensorSpec(
                    f"{c.name}_input", c.dtype, c.block_shape
                )
        program = program_from_function(fetches, specs)
    else:
        raise TypeError(
            "fetches must be a DSL Node, a list of Nodes, a Program, or a "
            f"callable; got {type(fetches).__name__}"
        )
    hints = (
        {k: Shape.from_any(v) for k, v in shape_hints.items()}
        if shape_hints
        else None
    )
    if dt.demotion_active():
        # x64 demotion: analyze (and hence trace/execute) the program
        # against 32-bit input specs; gather_feeds casts at the boundary
        demoted = [
            TensorSpec(s.name, dt.demote(s.dtype), s.shape)
            for s in program.inputs
        ]
        program = Program(program.fn, demoted, fetch_order=program.fetch_order)
    # analyze_program returns a copy: the role and seg_info below never
    # land on the caller's Program
    program = analyze_program(program, hints=hints)
    if reduce_mode:
        program.role = "reduce"
    program.seg_info = seg_info  # survives Program reuse via compile_program
    return program, seg_info


def _apply_feed_dict(program: Program, feed_dict: Optional[Dict[str, str]]) -> Program:
    """feed_dict: placeholder name → column name (≙ core.py:128-142).
    Placeholders not mentioned keep their own name as the column name."""
    if not feed_dict:
        return program
    unknown = [k for k in feed_dict if k not in program.input_names]
    if unknown:
        raise ValidationError(
            f"feed_dict key(s) {unknown} do not match any program input; "
            f"inputs: {program.input_names}"
        )
    return program.rename_inputs(dict(feed_dict))


def _demote_cast(v, spec: TensorSpec):
    """The x64-demotion boundary for verb paths that build feeds by hand
    (gather_feeds applies the same rule): cast a 64-bit column down to
    the program's demoted 32-bit input spec. Identity when demotion is
    inactive or dtypes already agree; works on numpy and jax arrays."""
    if (
        dt.demotion_active()
        and getattr(v, "dtype", None) != spec.dtype.np_dtype
    ):
        return v.astype(spec.dtype.np_dtype)
    return v


def _strict_lint(program: Program, frame, block_mode: Optional[bool]) -> None:
    """The verbs' ``strict=True`` hook: run the static analyzer
    (:mod:`tensorframes_tpu.analysis`) on the normalized program and
    raise :class:`~tensorframes_tpu.validation.StaticAnalysisError` on
    any error-severity diagnostic — before the first dispatch. Block
    shapes feed the recompile-storm rule only when the frame is already
    materialized (lint never forces a pending computation)."""
    from ..analysis import lint_program

    counts = None
    if getattr(frame, "is_materialized", False):
        counts = tuple(_block_num_rows(b) for b in frame.blocks())
    lint_program(
        program, block_mode=block_mode, block_row_counts=counts,
    ).raise_on_errors()


def _sorted_output_infos(program: Program, block_mode: bool) -> List[ColumnInfo]:
    """Output columns first, sorted by name (≙ DebugRowOps.scala:353-379)."""
    infos = []
    for o in sorted(program.outputs, key=lambda s: s.name):
        if block_mode:
            block_shape = o.shape if o.shape.rank > 0 else Shape((Unknown,))
            block_shape = block_shape.with_leading_unknown()
        else:
            block_shape = o.shape.prepend(Unknown)
        infos.append(ColumnInfo(o.name, o.dtype, block_shape))
    return infos


def compile_program(
    fetches: Fetches,
    frame,
    block: bool = True,
    reduce_mode: Optional[str] = None,
    feed_dict: Optional[Dict[str, str]] = None,
    shape_hints: Optional[Dict[str, object]] = None,
) -> Program:
    """Pre-compile fetches against a frame's schema into a reusable Program.

    Passing the returned Program to a verb repeatedly reuses one XLA
    executable across calls (the jit cache lives on the Program), instead
    of re-tracing per invocation — the steady-state serving path.

    ``shape_hints`` ({output name → shape}) override discovered output
    shapes wherever the hint dim is known — the per-call shape side
    channel (≙ ShapeDescription + the hint-override rule,
    TensorFlowOps.scala:126-133).
    """
    program, _ = _normalize_program(
        fetches,
        frame.schema,
        block=block,
        reduce_mode=reduce_mode,
        shape_hints=shape_hints,
    )
    return _apply_feed_dict(program, feed_dict)


# ---------------------------------------------------------------------------
# map_blocks
# ---------------------------------------------------------------------------

def _rebalance_trimmed(out_blocks, names, mesh, axis):
    """Re-split a trimmed sharded result so the mesh divides the main
    block again (SURVEY §7 hard-part 3: row-count-changing outputs across
    shards need a size exchange before reassembly — here the exchange is
    a ``device_put`` resharding, which XLA lowers to ICI collectives,
    ≙ TrimmingOperationsSuite.scala:17-47 semantics). The result obeys
    the same invariants as ``to_device``: divisible device main block +
    small host tail, so every downstream verb fast path composes."""
    import jax

    from ..parallel.mesh import batch_sharding

    if jax.process_count() > 1:
        # boundary rows can't be host-shuffled across non-addressable
        # shards; leave the blocks as produced — the verb guards decline
        # the fast paths for non-divisible shapes, so results stay correct
        return out_blocks

    dp = mesh.shape[axis]
    dev_cols = dict(out_blocks[0])
    # any further blocks are the mapped host-tail results — tiny
    tail_cols = {
        nm: np.concatenate([np.asarray(ob[nm]) for ob in out_blocks[1:]])
        for nm in names
    } if len(out_blocks) > 1 else {}
    n_dev = int(next(iter(dev_cols.values())).shape[0])
    n_tail = int(next(iter(tail_cols.values())).shape[0]) if tail_cols else 0
    n_main = ((n_dev + n_tail) // dp) * dp
    main, tailb = {}, {}
    for nm in names:
        arr = dev_cols[nm]
        if n_main <= n_dev:
            # only the <= dp-1 overflow rows leave the device; the big
            # array reshards in place via device_put (ICI on real chips)
            extra = np.asarray(arr[n_main:]) if n_main < n_dev else None
        else:
            # promote tail rows to fill the last full shard row-group
            fill = jnp.asarray(tail_cols[nm][: n_main - n_dev])
            arr = jnp.concatenate([arr, fill], axis=0)
            extra = None
        main[nm] = jax.device_put(
            arr[:n_main], batch_sharding(mesh, arr.ndim, axis)
        )
        rest = tail_cols.get(nm)
        if rest is not None:
            rest = rest[max(0, n_main - n_dev):]
        parts = [p for p in (extra, rest) if p is not None and len(p)]
        if parts:
            tailb[nm] = np.concatenate(parts)
    return [main] + ([tailb] if tailb else [])

def map_blocks(
    fetches: Fetches,
    frame,
    feed_dict: Optional[Dict[str, str]] = None,
    trim: bool = False,
    strict: bool = False,
) -> "TensorFrame":
    """Transform a frame block by block, appending one column per output
    (or replacing all columns when ``trim=True``, in which case the output
    row count may differ from the input's).

    ≙ ``tfs.map_blocks`` (core.py:267-313) → DebugRowOps.mapBlocks
    (DebugRowOps.scala:305-400); trimmed variant ≙ mapBlocksTrimmed.
    Lazy: returns a frame with a pending computation (core.py:278-279).
    ``strict=True`` additionally runs the static analyzer and raises on
    error-severity diagnostics before any dispatch.
    """
    if _is_pandas(frame):
        return _map_pandas(fetches, frame, feed_dict, block=True,
                           strict=strict)
    program, _ = _normalize_program(
        fetches, frame.schema, block=True, feed_dict=feed_dict
    )
    program = _apply_feed_dict(program, feed_dict)
    validate_map(program, frame.schema, block=True, trim=trim)
    if strict:
        _strict_lint(program, frame, block_mode=True)
    out_infos = _sorted_output_infos(program, block_mode=True)
    if trim:
        schema = Schema(out_infos)
    else:
        schema = Schema(out_infos + frame.schema.columns)
        planned = _plan_map_result(frame, program, schema, rows=False)
        if planned is not None:
            return planned
    compiled = program.compiled()
    parent = frame
    input_names = program.input_names
    sharded = frame.is_sharded

    def compute() -> List[Block]:
        from collections import deque

        out_blocks: List[Block] = []
        t0 = time.perf_counter()
        n_total = 0
        # pipelined execution: keep up to `depth` blocks in flight so block
        # k+1's host→HBM transfer and compute overlap block k's device→host
        # readback (jax dispatch is async; only np.asarray synchronizes).
        # Sharded frames skip the window — their outputs stay in HBM.
        depth = 0 if sharded else max(0, get_config().map_pipeline_depth)
        in_flight: deque = deque()

        def finish(b: Block, n: int, outs) -> None:
            if not sharded:
                outs = {k: np.asarray(v) for k, v in outs.items()}
            if trim:
                out_blocks.append({i.name: outs[i.name] for i in out_infos})
                return
            for o in program.outputs:
                got = outs[o.name].shape[0] if outs[o.name].ndim > 0 else None
                if got != n:
                    raise ValidationError(
                        f"map_blocks output {o.name!r} produced {got} rows "
                        f"for a block of {n} rows. Appending requires "
                        "matching row counts; use trim=True for "
                        "row-count-changing programs."
                    )
            nb: Block = {i.name: outs[i.name] for i in out_infos}
            nb.update(b)
            out_blocks.append(nb)

        blocks = parent.blocks()
        # host-frame path: stage upcoming blocks' feeds in HBM from a
        # background thread so block k+1's host→device transfer overlaps
        # block k's compute — the layer the reference called "very
        # simple and very inefficient" (TFDataOps.scala:32-33). Sharded
        # frames skip it: their columns already live in HBM.
        prefetch_depth = (
            0 if sharded else max(0, get_config().map_prefetch_depth)
        )
        feeds_seq = (
            gather_feeds(b, input_names, program) for b in blocks
        )
        if prefetch_depth > 0 and len(blocks) > 1:
            from .. import io as _io

            feeds_seq = _io.prefetch_to_device(feeds_seq, size=prefetch_depth)
        donate_cfg = get_config().donate_inputs
        for b, feeds in zip(blocks, feeds_seq):
            n = _block_num_rows(b)
            n_total += n
            # donate only provably-fresh buffers: every input column came
            # from host memory (the transfer above made a private device
            # copy). A device-resident frame column is the frame's own
            # storage — donating it would corrupt later reads.
            donate = donate_cfg and not any(
                isinstance(b[name], jax.Array) for name in input_names
            )
            outs = compiled.run_block(feeds, to_numpy=False, donate=donate)
            in_flight.append((b, n, outs))
            if len(in_flight) > depth:
                finish(*in_flight.popleft())
        while in_flight:
            finish(*in_flight.popleft())
        if trim and sharded and out_blocks:
            out_blocks = _rebalance_trimmed(
                out_blocks,
                [i.name for i in out_infos],
                parent.mesh,
                getattr(parent, "_axis", None) or get_config().batch_axis,
            )
        # device-resident outputs return before the TPU finishes (async
        # dispatch); label those spans distinctly so report() rows/s is
        # honest — only the host path measures completed execution
        name = "map_blocks.dispatch" if sharded else "map_blocks"
        profiling.record(name, time.perf_counter() - t0, n_total, t0=t0)
        return out_blocks

    result = TensorFrame(None, schema, pending=compute)
    result._produced_by_map = True
    if trim:
        # a row-count-changing map is a fusion barrier: downstream
        # chains re-root here (TFG107 names it when maps sit both sides)
        from ..plan import ir as plan_ir

        plan_ir.mark_barrier(
            result, "trim map_blocks (row-count-changing output)", frame
        )
    if sharded:
        result._mesh = frame.mesh
        result._axis = getattr(frame, "_axis", None)
    return result


# ---------------------------------------------------------------------------
# map_rows
# ---------------------------------------------------------------------------

# ragged staging byte cap: below it, every shape-group's feeds move in
# ONE device_put and dispatch before the first sync (transfer-latency
# win); above it, groups run one at a time so staged inputs + in-flight
# outputs can't OOM HBM on many-GB ragged blocks
_RAGGED_STAGE_BYTES = 1 << 28  # 256 MB


def _group_rows_by_shape(
    b: Dict[str, object], input_names: Sequence[str], n: int
) -> List[np.ndarray]:
    """Row indices grouped by input cell shape — the ragged dispatch
    unit. The common case (ONE 1-D ragged column) grouped VECTORIZED:
    lengths via a single fromiter, then unique/argsort, no 20k-iteration
    python dict loop; multi-input / higher-rank cells keep the general
    tuple-key path."""
    if n == 0:
        # zero rows → zero groups: np.split over an empty order array
        # would fabricate one EMPTY group whose downstream staging
        # (np.stack of nothing, est_bytes reading idx[0]) crashes
        return []
    if len(input_names) == 1:
        col = b[input_names[0]]
        cells = col if isinstance(col, list) else list(col)
        if cells and all(
            isinstance(c, np.ndarray) and c.ndim == 1 for c in cells
        ):
            lens = np.fromiter(
                (c.shape[0] for c in cells), np.int64, count=n
            )
            uniq, inv = np.unique(lens, return_inverse=True)
            order = np.argsort(inv, kind="stable")
            bounds = np.searchsorted(inv[order], np.arange(1, len(uniq)))
            return [g for g in np.split(order, bounds)]
    groups: Dict[tuple, List[int]] = {}
    for i in range(n):
        key = tuple(np.shape(b[name][i]) for name in input_names)
        groups.setdefault(key, []).append(i)
    return [np.asarray(v) for v in groups.values()]


def _stack_group(col, idx) -> np.ndarray:
    """Stack the cells ``col[i] for i in idx`` (same shape by grouping)
    into ``[len(idx), *cell]``: one native memcpy pass when available
    (np.stack pays per-element dispatch — it dominated the ragged host
    path), np.stack otherwise."""
    from .. import native

    cells = [col[i] for i in idx]
    try:
        # native.stack_cells returns None itself for unavailable /
        # non-ndarray / object-dtype / non-contiguous first cells;
        # BufferError covers a non-contiguous LATER cell (a sliced-view
        # ndarray) whose PyObject_GetBuffer fails inside rowpack.cpp —
        # np.stack handles such views fine (ADVICE r4)
        stacked = native.stack_cells(cells)
    except (ValueError, TypeError, BufferError):
        stacked = None
    if stacked is not None:
        return stacked
    return np.stack([np.asarray(c) for c in cells])


def _ragged_rows_outs(
    cols: Dict[str, list],
    input_names: Sequence[str],
    n: int,
    program: Program,
    compiled,
) -> Dict[str, object]:
    """Run a row-wise program over ``n`` ragged rows (``cols`` maps each
    input to its per-row cells): group rows by input cell shape, stage
    every group's padded feeds, move them with ONE device_put call, and
    dispatch every group before the first result sync — per-group
    transfer+sync round-trips multiply per-call link latency by the
    shape count (the r3 TPU run collapsed 23x on exactly this; VERDICT
    r3 #5; ≙ TFDataOps.scala:90-103). Returns one value per output:
    a dense ``[n, *cell]`` array (uniform cell shapes) or a per-row
    cell list (ragged outputs)."""
    if n == 0:
        # zero ragged rows: dtype/rank-correct empties (Unknown inner
        # dims degrade to 0), mirroring map_rows' empty-block branch —
        # the staging below assumes at least one row per group
        out0: Dict[str, object] = {}
        for o in program.outputs:
            dims = tuple(0 if d == Unknown else d for d in o.shape.dims)
            out0[o.name] = np.empty((0,) + dims, dtype=o.dtype.np_dtype)
        return out0
    group_list = [g for g in _group_rows_by_shape(cols, input_names, n)
                  if len(g)]
    donate_r = get_config().donate_inputs
    window = max(1, get_config().map_pipeline_depth)

    def group_feeds(idx):
        g = len(idx)
        feeds = {}
        for name in input_names:
            stacked = _stack_group(cols[name], idx)
            spec = program.input(name)
            if (
                dt.demotion_active()
                and stacked.dtype != spec.dtype.np_dtype
            ):
                # x64 demotion boundary (mirrors gather_feeds)
                stacked = stacked.astype(spec.dtype.np_dtype)
            feeds[name] = stacked
        return pad_lead_dim(feeds, g, bucket_rows(g))

    def est_bytes(idx):
        # staged size WITHOUT staging: bucket-padded rows x cell bytes
        # (post-demotion dtype) — so wave planning never materializes
        # copies it may not use
        g = bucket_rows(len(idx))
        total = 0
        for name in input_names:
            c = np.asarray(cols[name][int(idx[0])])
            item = (
                np.dtype(program.input(name).dtype.np_dtype).itemsize
                if dt.demotion_active()
                else c.dtype.itemsize
            )
            total += g * int(np.prod(c.shape)) * item
        return total

    # WAVES: consecutive groups whose staged bytes fit the cap move
    # with one device_put and dispatch before the first sync (the
    # transfer-latency win VERDICT r3 #5 demands); the next wave stages
    # only after the previous drains, so peak host memory is one wave's
    # padded copies and peak HBM is one wave's inputs plus a
    # map_pipeline_depth window of outputs. A wave always holds >= 1
    # group, so a single over-cap group still runs (the old
    # group-at-a-time over-cap behavior is the 1-group-wave case).
    waves: List[List] = [[]]
    wave_bytes = 0
    for idx in group_list:
        bts = est_bytes(idx)
        if waves[-1] and wave_bytes + bts > _RAGGED_STAGE_BYTES:
            waves.append([])
            wave_bytes = 0
        waves[-1].append(idx)
        wave_bytes += bts

    from collections import deque as _deque

    outs_list: List[Dict[str, np.ndarray]] = []
    for wave in waves:
        staged = jax.device_put([group_feeds(idx) for idx in wave])
        in_flight_r: _deque = _deque()
        for f in staged:
            # freshly-transferred private copies: donation-safe
            # (honoring the donate_inputs switch)
            in_flight_r.append(
                compiled.run_rows(f, to_numpy=False, donate=donate_r)
            )
            if len(in_flight_r) > window:
                o = in_flight_r.popleft()
                outs_list.append(
                    {k: np.asarray(v) for k, v in o.items()}
                )
        while in_flight_r:
            o = in_flight_r.popleft()
            outs_list.append({k: np.asarray(v) for k, v in o.items()})
        del staged
    # VECTORIZED scatter: a uniform output column writes whole groups
    # via index assignment — no per-row python loop, no per-row dict,
    # no final re-stack (the r1-r3 assembly spent most of the ragged
    # path's host time there). Ragged outputs (cell shapes differ
    # across groups) keep the per-row list form.
    outs: Dict[str, object] = {}
    for o in program.outputs:
        cell_shapes = {outs_g[o.name].shape[1:] for outs_g in outs_list}
        if len(cell_shapes) == 1:
            first = outs_list[0][o.name]
            dest = np.empty((n,) + first.shape[1:], dtype=first.dtype)
            for idx, outs_g in zip(group_list, outs_list):
                dest[np.asarray(idx)] = (
                    np.asarray(outs_g[o.name])[: len(idx)]
                )
            outs[o.name] = dest
        else:
            cells: List = [None] * n
            for idx, outs_g in zip(group_list, outs_list):
                og = np.asarray(outs_g[o.name])
                for j, i in enumerate(idx):
                    cells[i] = og[j]
            outs[o.name] = cells  # ragged output column
    return outs


def map_rows(
    fetches: Fetches,
    frame,
    feed_dict: Optional[Dict[str, str]] = None,
    strict: bool = False,
) -> "TensorFrame":
    """Transform a frame row by row (placeholders are cell-shaped).

    ≙ ``tfs.map_rows`` (core.py:224-265) → DebugRowOps.mapRows
    (DebugRowOps.scala:403-484). Uniform blocks run as one vmapped XLA
    program; ragged blocks fall back to per-row execution with a
    per-cell-shape compile cache.
    """
    if _is_pandas(frame):
        return _map_pandas(fetches, frame, feed_dict, block=False,
                           strict=strict)
    program, _ = _normalize_program(
        fetches, frame.schema, block=False, feed_dict=feed_dict
    )
    program = _apply_feed_dict(program, feed_dict)
    validate_map(program, frame.schema, block=False)
    if strict:
        _strict_lint(program, frame, block_mode=False)
    out_infos = _sorted_output_infos(program, block_mode=False)
    schema = Schema(out_infos + frame.schema.columns)
    planned = _plan_map_result(frame, program, schema, rows=True)
    if planned is not None:
        return planned
    compiled = program.compiled()
    parent = frame
    input_names = program.input_names

    def compute() -> List[Block]:
        t0 = time.perf_counter()
        blocks = parent.blocks()
        results: List[Optional[Block]] = [None] * len(blocks)
        ragged_entries: List[Tuple[int, Block, int]] = []
        n_total = 0
        for bi, b in enumerate(blocks):
            n = _block_num_rows(b)
            n_total += n
            if n == 0:
                nb: Block = {}
                for i in out_infos:
                    # preserve the cell rank so cross-block concatenation
                    # works; Unknown inner dims degrade to 0
                    dims = tuple(
                        0 if d == Unknown else d for d in i.cell_shape.dims
                    )
                    nb[i.name] = np.empty((0,) + dims, dtype=i.dtype.np_dtype)
                nb.update(b)
                results[bi] = nb
                continue
            if block_is_ragged(b, input_names):
                ragged_entries.append((bi, b, n))
                continue
            feeds = gather_feeds(b, input_names, program)
            if not parent.is_sharded:
                # adaptive lead-dim bucketing: the partitioner yields
                # at most two block sizes, so the first few distinct
                # shapes compile exactly (zero padded work); once the
                # vmap cache shows shape proliferation (>= 3 distinct
                # sizes — an externally-built frame), pad to
                # power-of-two buckets so compiles stay O(log n).
                # (Sharded main blocks have one stable size — and
                # padding would disturb their device layout.)
                target = n
                if compiled.cache_sizes()["vmap"] >= 3:
                    target = bucket_rows(n)
                feeds = pad_lead_dim(feeds, n, target)
                outs = compiled.run_rows(feeds, to_numpy=False)
                outs = {k: np.asarray(v[:n]) for k, v in outs.items()}
            else:
                outs = compiled.run_rows(feeds, to_numpy=False)
            nb = {i.name: outs[i.name] for i in out_infos}
            nb.update(b)
            results[bi] = nb
        if ragged_entries:
            # GLOBAL ragged pass (≙ per-row dynamic lead dim,
            # TFDataOps.scala:90-103): group rows by input cell shape
            # across EVERY ragged block at once — #dispatches (and, on
            # device backends, #transfers) is the number of DISTINCT
            # shapes, not shapes x blocks, and each group's vmap runs
            # at the largest possible batch
            merged: Dict[str, list] = {name: [] for name in input_names}
            for _, b, _ in ragged_entries:
                for name in input_names:
                    col = b[name]
                    merged[name].extend(
                        col if isinstance(col, list) else list(col)
                    )
            big_n = sum(nr for _, _, nr in ragged_entries)
            outs_global = _ragged_rows_outs(
                merged, input_names, big_n, program, compiled
            )
            off = 0
            for bi, b, nr in ragged_entries:
                nb = {
                    i.name: outs_global[i.name][off:off + nr]
                    for i in out_infos
                }
                nb.update(b)
                results[bi] = nb
                off += nr
        name = "map_rows.dispatch" if parent.is_sharded else "map_rows"
        profiling.record(name, time.perf_counter() - t0, n_total, t0=t0)
        return results

    result = TensorFrame(None, schema, pending=compute)
    result._produced_by_map = True
    if frame.is_sharded:
        result._mesh = frame.mesh
        result._axis = getattr(frame, "_axis", None)
    return result


def _map_pandas(fetches, pdf, feed_dict, block: bool, strict: bool = False):
    """Local pandas path (≙ ``_map_pd``, core.py:171-183): run the program
    on the pandas columns and append the outputs to a copy of the frame.
    ``strict`` rides through to the converted-frame map_blocks so the
    pandas interop honors the same pre-dispatch analysis gate."""
    from ..frame import frame_from_pandas

    tf_frame = frame_from_pandas(pdf, num_blocks=1)
    # the reference's _map_pd always feeds whole columns (block semantics)
    result = map_blocks(fetches, tf_frame, feed_dict=feed_dict, strict=strict)
    out = pdf.copy()
    for name in result.schema.names:
        if name not in pdf.columns:
            out[name] = list(result.column_values(name))
    return out


# ---------------------------------------------------------------------------
# reduce_rows
# ---------------------------------------------------------------------------

def _unpack_results(program: Program, finals: Dict[str, np.ndarray]):
    """Return numpy results in fetch order; single fetch unwraps
    (≙ _unpack_row, core.py:111-125)."""
    out = []
    for name in program.fetch_order or program.output_names:
        v = finals[name]
        arr = np.asarray(v)
        out.append(arr if arr.ndim > 0 else arr.item())
    return out[0] if len(out) == 1 else out


def _sharded_reduce_rows_fn(program: Program, out_names, mesh, axis):
    """One XLA program for reduce_rows over a sharded frame: each shard
    folds its local rows with ``lax.scan``, the per-shard partials
    ``all_gather`` over the batch axis, and a second scan folds them —
    no host round-trip (≙ replacing performReducePairwise + driver fold,
    DebugRowOps.scala:939-979, with on-device collectives)."""
    from ..parallel._shard_map import shard_map
    from jax.sharding import PartitionSpec as P

    pair_scan = pair_fold_body(program, out_names)

    def local(vals):
        carry = pair_scan(vals)
        gathered = {
            x: jax.lax.all_gather(carry[x], axis) for x in out_names
        }
        return pair_scan(gathered)

    in_specs = (
        {
            x: P(axis, *([None] * (program.input(f"{x}_1").shape.rank)))
            for x in out_names
        },
    )
    out_specs = {x: P() for x in out_names}
    return jax.jit(
        shard_map(local, mesh=mesh, in_specs=in_specs, out_specs=out_specs)
    )


def reduce_rows(
    fetches: Fetches, frame, strict: bool = False
) -> Union[np.ndarray, list]:
    """Pairwise-reduce all rows to a single row. Each fetch ``x`` consumes
    placeholders ``x_1``/``x_2`` (Operations.scala:83-96). Eager
    (core.py:197 "not lazy").

    Execution: within each block, a sequential ``lax.scan`` fold under one
    jit; block partials are folded the same way. On sharded frames the
    fold runs per shard with an ``all_gather`` merge — one XLA program,
    no host gather. Reduction order is unspecified by contract
    (core.py:186-187), so the split does not change the result class the
    reference supports (associative programs).
    """
    program, _ = _normalize_program(
        fetches, frame.schema, block=False, reduce_mode="rows"
    )
    validate_reduce_rows(program, frame.schema)
    if strict:
        _strict_lint(program, frame, block_mode=False)
    out_names = [o.name for o in program.outputs]
    fold = make_pair_fold(program, out_names)
    t0 = time.perf_counter()

    # whole-pipeline route: a lazy plan-carrying frame fuses its map
    # chain WITH the pairwise-fold epilogue into one program per block
    # (plan/lower.lower_reduce) — the mapped columns never materialize;
    # the per-block partials below then combine exactly as always.
    from ..plan.lower import lower_reduce

    planned = lower_reduce(frame, program, out_names, "rows")
    partials: List[Dict[str, np.ndarray]] = (
        list(planned[0]) if planned is not None else []
    )
    blocks = [] if planned is not None else frame.blocks()
    if frame.is_sharded and blocks:
        main = blocks[0]
        axis = getattr(frame, "_axis", None) or get_config().batch_axis
        dp = frame.mesh.shape.get(axis, 1)
        main_ok = all(
            not isinstance(main.get(x), list)
            and getattr(main.get(x), "ndim", 0) >= 1
            and main[x].shape[0] >= 1
            # a trimmed map can leave a sharded frame with a row count the
            # mesh no longer divides; shard_map would reject it — host path
            and main[x].shape[0] % dp == 0
            for x in out_names
        )
        if main_ok:
            cache = getattr(program, "_sharded_rr", None)
            if cache is None or cache[0] != (frame.mesh, axis):
                fn = _sharded_reduce_rows_fn(
                    program, out_names, frame.mesh, axis
                )
                program._sharded_rr = ((frame.mesh, axis), fn)
            fn = program._sharded_rr[1]
            res = fn(
                {
                    x: _demote_cast(main[x], program.input(f"{x}_1"))
                    for x in out_names
                }
            )
            partials.append({x: np.asarray(res[x]) for x in out_names})
            blocks = blocks[1:]  # tail (if any) folds in below

    for b in blocks:
        n = _block_num_rows(b)
        if n == 0:
            continue
        feeds = {}
        for x in out_names:
            v = b[x]
            if isinstance(v, list):
                spec = program.input(f"{x}_1")
                try:
                    v = np.asarray(v, dtype=spec.dtype.np_dtype)
                except (ValueError, TypeError):
                    raise ValueError(
                        f"Column {x!r} holds ragged cells; reduce_rows "
                        "needs dense blocks (run analyze() first)."
                    ) from None
            elif not isinstance(v, np.ndarray):
                # sharded columns: the pairwise fold is sequential by
                # contract, so pull the shard-split array to host rather
                # than scan over a dp-sharded lead dim (unsupported slice)
                v = np.asarray(v)
            feeds[x] = _demote_cast(v, program.input(f"{x}_1"))
        if n == 1:
            partials.append({x: np.asarray(feeds[x][0]) for x in out_names})
        else:
            res = fold({x: jnp.asarray(feeds[x]) for x in out_names})
            tracing = _events.TRACER.enabled
            t_f = time.perf_counter() if tracing else 0.0
            part = {x: np.asarray(res[x]) for x in out_names}
            if tracing:
                _events.TRACER.emit_complete(
                    "plan.reduce.fetch", t_f, time.perf_counter() - t_f,
                    args={"block": len(partials),
                          "bytes": sum(v.nbytes for v in part.values())},
                    cat="plan",
                )
            partials.append(part)
    if not partials:
        raise ValueError("reduce_rows on an empty frame")
    if len(partials) == 1:
        finals = partials[0]
    else:
        tracing = _events.TRACER.enabled
        t_c = time.perf_counter() if tracing else 0.0
        stacked = {
            x: jnp.asarray(np.stack([p[x] for p in partials])) for x in out_names
        }
        res = fold(stacked)
        finals = {x: np.asarray(res[x]) for x in out_names}
        if tracing:
            _events.TRACER.emit_complete(
                "plan.reduce.combine", t_c, time.perf_counter() - t_c,
                args={"partials": len(partials)}, cat="plan",
            )
    profiling.record(
        "reduce_rows", time.perf_counter() - t0,
        planned[1] if planned is not None else frame.num_rows, t0=t0,
    )
    return _unpack_results(program, finals)


# ---------------------------------------------------------------------------
# reduce_blocks
# ---------------------------------------------------------------------------

def reduce_blocks(
    fetches: Fetches, frame, strict: bool = False
) -> Union[np.ndarray, list]:
    """Block-reduce all rows to a single row. Each fetch ``x`` consumes a
    placeholder ``x_input`` with one extra (Unknown) leading dim
    (Operations.scala:98-108). Eager.

    Execution ≙ performReduceBlock per partition + pairwise merge
    (DebugRowOps.scala:510-533), except partials are stacked and reduced in
    one final program run instead of driver-coordinated pairwise merging.
    """
    program, _ = _normalize_program(
        fetches, frame.schema, block=True, reduce_mode="blocks"
    )
    validate_reduce_blocks(program, frame.schema)
    if strict:
        _strict_lint(program, frame, block_mode=True)
    out_names = [o.name for o in program.outputs]
    compiled = program.compiled()
    t0 = time.perf_counter()

    # whole-pipeline route: fuse the recorded map chain with the reduce
    # program into one dispatch per block (plan/lower.lower_reduce) —
    # the mapped columns never materialize; partials combine as always.
    from ..plan.lower import lower_reduce

    planned = lower_reduce(frame, program, out_names, "blocks")
    partials: List[Dict[str, np.ndarray]] = (
        list(planned[0]) if planned is not None else []
    )
    for b in ([] if planned is not None else frame.blocks()):
        if _block_num_rows(b) == 0:
            continue
        feeds = {}
        for x in out_names:
            v = b[x]
            spec = program.input(f"{x}_input")
            if isinstance(v, list):
                try:
                    v = np.asarray(v, dtype=spec.dtype.np_dtype)
                except (ValueError, TypeError):
                    raise ValueError(
                        f"Column {x!r} holds ragged cells; reduce_blocks "
                        "needs dense blocks (run analyze() first)."
                    ) from None
            else:
                v = _demote_cast(v, spec)
            feeds[f"{x}_input"] = v
        partials.append(compiled.run_block(feeds))
    if not partials:
        raise ValueError("reduce_blocks on an empty frame")
    if len(partials) == 1:
        finals = partials[0]
    else:
        tracing = _events.TRACER.enabled
        t_c = time.perf_counter() if tracing else 0.0
        feeds = {
            f"{x}_input": np.stack([p[x] for p in partials]) for x in out_names
        }
        finals = compiled.run_block(feeds)
        if tracing:
            # the stack of the partials and the combine program's run
            # (parent of that run's executor.* spans)
            _events.TRACER.emit_complete(
                "plan.reduce.combine", t_c, time.perf_counter() - t_c,
                args={"partials": len(partials)}, cat="plan",
            )
    profiling.record(
        "reduce_blocks", time.perf_counter() - t0,
        planned[1] if planned is not None else frame.num_rows, t0=t0,
    )
    return _unpack_results(program, finals)


# ---------------------------------------------------------------------------
# aggregate (keyed)
# ---------------------------------------------------------------------------

from functools import lru_cache

from .segment import segment_sum as _segment_sum


def _agg_schema_infos(schema, keys, program) -> List[ColumnInfo]:
    """Result schema of a keyed aggregate: key columns (Unknown lead)
    then the program outputs sorted by name — shared by the eager
    assemble and the plan route's lazy result frame."""
    infos: List[ColumnInfo] = []
    for k in keys:
        infos.append(schema[k].with_block_shape(
            schema[k].cell_shape.prepend(Unknown)
        ))
    for o in sorted(program.outputs, key=lambda s: s.name):
        infos.append(ColumnInfo(o.name, o.dtype, o.shape.prepend(Unknown)))
    return infos


def _empty_agg_blocks(schema) -> List[Block]:
    """The zero-row aggregate result for ``schema`` — ONE definition
    shared by the eager empty-frame branch and the plan lowering, so
    the fused and unfused empty-aggregate schemas cannot drift."""
    empty: Block = {}
    for i in schema:
        dims = tuple(0 if d == Unknown else d for d in i.cell_shape.dims)
        if i.is_device:
            empty[i.name] = np.empty((0,) + dims, dtype=i.dtype.np_dtype)
        else:
            empty[i.name] = []
    return [empty]


def _segment_reduce_best(ops_key, num_groups, val_cols, seg_ids):
    """Keyed-reduction backend dispatch, recorded as a cost-model
    decision (``plan/rules.decide_segment_reduce``): host
    ``np.bincount`` on the CPU backend for 1-D float sums/means
    (XLA:CPU's serialized scatter is ~20x slower), the fused pallas
    segment-reduce kernel on kernel-capable backends
    (``kernels/segment_reduce.py`` — ONE dispatch for every fetch),
    the jitted segment program otherwise. Values may be numpy or jax
    arrays; returns numpy columns. EVERY host-frame keyed reduction —
    the eager fast path and the plan's fused epilogues — dispatches
    here, so fused and unfused outputs stay bit-identical whichever
    backend wins (the strategy choice is deterministic per feed). A
    failure in the selected lowering raises; nothing retries on
    another one."""
    from . import segment as _segment
    from ..plan.lower import _note_decision
    from ..plan.rules import decide_segment_reduce

    decision = decide_segment_reduce(ops_key, val_cols, num_groups)
    _note_decision(decision)
    if decision.kind == "host_segment_reduce":
        return _segment.segment_reduce_host(
            ops_key, num_groups, val_cols, seg_ids
        )
    if decision.kind == "pallas_segment_reduce":
        from ..kernels import segment_reduce as _ksr

        return _ksr.segment_reduce_pallas(
            ops_key, num_groups, val_cols, seg_ids
        )
    seg_vals = {x: jnp.asarray(val_cols[x]) for x, _ in ops_key}
    # int32 ids: halves the host→HBM id-column transfer; group counts
    # can't exceed int32 — the id space is bounded by row count long
    # before 2^31
    sids = jnp.asarray(np.asarray(seg_ids).astype(np.int32))
    res = run_segment_fast(ops_key, num_groups, seg_vals, sids)
    return {x: np.asarray(res[x]) for x, _ in ops_key}


def run_segment_fast(ops_key, num_groups, seg_vals, sids):
    """One jitted segment-reduce dispatch — shared by the eager
    aggregate and the plan lowering's fused epilogues. On a TPU its
    float sums ride the one-hot pallas kernel (``ops/segment.py``); a
    kernel Mosaic refuses raises here. ``_seg_fast_for`` is looked up
    by name so tests may monkeypatch it."""
    return _seg_fast_for(ops_key, num_groups)(seg_vals, sids)


def _host_fast_aggregate(program, frame, keys, seg_info, out_names):
    """The host segment fast path over a (forced) frame: gather value
    columns, encode group keys through the per-frame dictionary cache
    (:func:`tensorframes_tpu.ops.keys.frame_group_ids` — string keys
    encode once, not per aggregate), one vectorized segment reduction
    (:func:`_segment_reduce_best` picks the backend). Returns
    ``(out_key_cols, out_cols, n_rows)``. Shared by the eager
    aggregate and the plan lowering's fallback path."""
    from .keys import frame_group_ids

    val_cols = {}
    for x in out_names:
        vals = frame.column_values(x)
        if vals.dtype == object:
            raise ValueError(
                f"Column {x!r} is ragged; aggregate requires uniform "
                "cells (run analyze() first)."
            )
        val_cols[x] = _demote_cast(vals, program.input(f"{x}_input"))
    seg_ids, group_key_cols, num_groups = frame_group_ids(frame, keys)
    ops_key = tuple((out_name, op) for out_name, op, _ in seg_info)
    out_cols = _segment_reduce_best(ops_key, num_groups, val_cols, seg_ids)
    return dict(zip(keys, group_key_cols)), out_cols, len(seg_ids)


@lru_cache(maxsize=32)
def _seg_fast_for(ops, num_groups):
    """Jitted keyed reduction: one XLA program for all fetches. ``sids``
    may arrive in ANY order — segment scatters (and the pallas one-hot
    kernel) are sortedness-agnostic, so do not add ``indices_are_sorted``
    here. ``ops`` is a tuple of (output_name, reducer_op). The LRU keeps
    repeated aggregates on one executable while bounding retained
    programs when group counts vary per batch (evicted entries free
    their XLA executables)."""

    @jax.jit
    def fn(vals, sids):
        outs = {}
        for out_name, op in ops:
            v = vals[out_name]
            if op == "reduce_mean":
                s = _segment_sum(v, sids, num_segments=num_groups)
                c = jax.ops.segment_sum(
                    jnp.ones(v.shape[:1], v.dtype), sids, num_segments=num_groups
                )
                c = c.reshape((-1,) + (1,) * (v.ndim - 1))
                # cast back: fetch dtype == input dtype by contract
                # (the generic path does this via _reducer's astype)
                outs[out_name] = (s / c).astype(v.dtype)
            else:
                outs[out_name] = _SEGMENT_OPS[op](
                    v, sids, num_segments=num_groups
                )
        return outs

    return fn


_SEGMENT_OPS = {
    # sum rides the custom pallas one-hot MXU kernel on TPU (segment.py);
    # min/max stay on XLA's segment scatter
    "reduce_sum": _segment_sum,
    "reduce_min": jax.ops.segment_min,
    "reduce_max": jax.ops.segment_max,
}


def _batched_compaction(program, val_cols, seg_ids, num_groups, out_names):
    """Arbitrary-combiner aggregation as LEVEL-BATCHED device compaction.

    ≙ TensorFlowUDAF's compact-every-bufferSize fold (DebugRowOps.scala:
    608-702): the user program is applied to row buffers of <= buf rows,
    partials stack and re-compact — the same algebraic contract. But
    instead of one program call per chunk per GROUP from a python loop
    (the round-2 shape of this path: ~100k dispatches for 1M rows / 512
    groups), every level dispatches all same-sized chunks across ALL
    groups as one vmapped XLA call: <= buf dispatches per level,
    O(buf · log_buf(max group size)) total, data device-resident between
    levels (VERDICT r2 missing #5 — the UDAF-equivalent now runs on
    device). Chunk-count lead dims are padded to power-of-two buckets so
    the vmap cache stays O(log) per chunk size; padded chunks compute
    garbage that is simply never scattered back.
    """
    if num_groups == 0:
        out = {}
        for o in program.outputs:
            dims = tuple(0 if d == Unknown else d for d in o.shape.dims)
            out[o.name] = np.empty((0,) + dims, o.dtype.np_dtype)
        return out
    buf = max(2, get_config().aggregate_buffer_size)
    compiled = program.compiled()

    order = np.argsort(seg_ids, kind="stable")
    counts = np.bincount(seg_ids, minlength=num_groups).astype(np.int64)
    cur = {
        x: jnp.asarray(np.asarray(val_cols[x])[order]) for x in out_names
    }

    def run_chunks(mat):
        """One vmapped dispatch over a [n_chunks, size] row-index matrix.
        The lead dim is bucketed by padding the HOST index matrix (repeat
        the last row) before the device gather — feeds never round-trip
        to host for padding, so levels stay device-resident."""
        n_chunks = mat.shape[0]
        target = bucket_rows(n_chunks)
        if target > n_chunks:
            mat = np.concatenate(
                [mat, np.repeat(mat[-1:], target - n_chunks, axis=0)]
            )
        idx = jnp.asarray(mat.astype(np.int32))  # halve the index upload
        feeds = {
            f"{x}_input": jnp.take(cur[x], idx, axis=0)
            for x in out_names
        }
        res = compiled.run_rows(feeds, to_numpy=False)
        return {x: res[x][:n_chunks] for x in out_names}

    while int(counts.max(initial=0)) > buf:
        starts = np.concatenate(([0], np.cumsum(counts)[:-1]))
        k, r = counts // buf, counts % buf
        new_counts = k + (r > 0)
        new_starts = np.concatenate(([0], np.cumsum(new_counts)[:-1]))
        total_new = int(new_counts.sum())
        parts = []  # (positions in the next level's flat state, results)
        if int(k.sum()):
            # all FULL buf-chunks across all groups: one dispatch
            g_of = np.repeat(np.arange(num_groups), k)
            rank = np.arange(len(g_of)) - np.repeat(np.cumsum(k) - k, k)
            base = starts[g_of] + rank * buf
            mat = base[:, None] + np.arange(buf)[None, :]
            parts.append((new_starts[g_of] + rank, run_chunks(mat)))
        for rv in np.unique(r[r > 0]):
            # remainder chunks batched by size: <= buf-1 dispatches
            sel = np.flatnonzero(r == rv)
            base = starts[sel] + k[sel] * buf
            mat = base[:, None] + np.arange(int(rv))[None, :]
            parts.append((new_starts[sel] + k[sel], run_chunks(mat)))
        nxt = {}
        for x in out_names:
            first = parts[0][1][x]
            acc = jnp.zeros((total_new,) + first.shape[1:], first.dtype)
            for pos, res in parts:
                acc = acc.at[jnp.asarray(pos)].set(res[x])
            nxt[x] = acc
        cur, counts = nxt, new_counts

    # final application — the program runs at least once per group even
    # for single-row groups (matches the UDAF's final evaluate)
    starts = np.concatenate(([0], np.cumsum(counts)[:-1]))
    finals = {x: None for x in out_names}
    for cv in np.unique(counts):
        sel = np.flatnonzero(counts == cv)
        mat = starts[sel][:, None] + np.arange(int(cv))[None, :]
        res = run_chunks(mat)
        for x in out_names:
            if finals[x] is None:
                finals[x] = jnp.zeros(
                    (num_groups,) + res[x].shape[1:], res[x].dtype
                )
            finals[x] = finals[x].at[jnp.asarray(sel)].set(res[x])
    return {x: np.asarray(finals[x]) for x in out_names}


def _allgather_rows(arr: np.ndarray, ks: Optional[np.ndarray] = None) -> np.ndarray:
    """Allgather variable-row-count per-process arrays: the local
    ``[k_p, *cell]`` partials concatenate over processes in process-index
    order (matching ``_allgather_dicts``' union ordering). Two phases —
    row counts (pass precomputed ``ks`` to skip this collective when
    gathering several same-length columns), then payloads padded to the
    max count."""
    from jax.experimental import multihost_utils as mh

    if ks is None:
        ks = np.asarray(
            mh.process_allgather(np.asarray([arr.shape[0]], np.int64))
        ).ravel()
    kmax = int(ks.max())
    padded = np.zeros((kmax,) + arr.shape[1:], arr.dtype)
    padded[: arr.shape[0]] = arr
    gathered = np.asarray(mh.process_allgather(padded))
    gathered = gathered.reshape((len(ks), kmax) + arr.shape[1:])
    from ..blockstore.store import HOSTGATHER_BYTES

    HOSTGATHER_BYTES.inc(float(gathered.nbytes))
    return np.concatenate([gathered[p, : int(ks[p])] for p in range(len(ks))])


def _aggregate_multiprocess_generic(program, frame, keys, out_names):
    """Arbitrary-combiner aggregation across processes (the UDAF merge at
    multi-host scale — closes VERDICT r2 missing #5's second half: the
    generic path previously had NO multi-process story, it raised from
    ``column_values``).

    Per process: local group-id encode + local level-batched compaction
    to ONE partial row per local group (the program's algebraic contract
    — re-applying it to stacked partials is valid, exactly the
    reference's UDAF merge assumption, DebugRowOps.scala:668-683). Then
    one small allgather of (keys, partial rows) and a final combine of
    the union — every process computes the identical replicated result.
    Returns None when ineligible (non-uniform or ragged columns, host
    tail, outputs with Unknown dims — an empty-shard process could not
    then shape its padded allgather buffer)."""
    from .device_agg import (
        _allgather_dicts,
        assemble_key_cols,
        extract_local_rows,
        uniform_ok,
    )
    from .keys import group_ids

    blocks = frame.blocks()
    main = blocks[0]
    tail = blocks[1] if len(blocks) > 1 else None

    if frame.num_rows == 0:
        # group_ids cannot encode zero rows; aggregate()'s empty-frame
        # branch (checked BEFORE its host gather) owns the layout —
        # num_rows is global, so every process returns together and no
        # collective is left dangling
        return None

    ok = True
    if tail is not None and any(
        _block_num_rows({c: tail[c]}) for c in tail
    ):
        ok = False  # host-tail rows are process-ambiguous here
    if any(d == Unknown for o in program.outputs for d in o.shape.dims):
        ok = False
    cols = {}
    if ok:
        for c in list(keys) + list(out_names):
            v = extract_local_rows(main[c])
            if v is None or (c in out_names and v.dtype == object):
                ok = False  # ragged value cells can't batch
                break
            cols[c] = v
        if ok:
            n_local = len(cols[keys[0]])
            ok = all(len(cols[c]) == n_local for c in cols)
    from .exchange import _file_shuffle_ctx

    fctx = _file_shuffle_ctx()
    if fctx is not None and fctx.nprocs != jax.process_count():
        fctx = None  # a stale/foreign shuffle dir must not hijack a fleet
    if fctx is not None and fctx.nprocs > 1:
        # the eligibility vote goes through spill files too: with the
        # file transport armed, XLA collectives may be unavailable
        # entirely (that is the transport's reason to exist)
        from ..blockstore import shuffle as _fs

        agree = _fs.vote_all(ok, name="agg.ok")
    else:
        agree = uniform_ok(ok)
    if not agree:
        return None

    if len(cols[keys[0]]):
        ids_local, local_dict, k_local = group_ids(
            [cols[k] for k in keys]
        )
    else:
        ids_local = np.zeros(0, np.int64)
        local_dict = [np.asarray(cols[k])[:0] for k in keys]
        k_local = 0
    val_local = {
        x: _demote_cast(cols[x], program.input(f"{x}_input"))
        for x in out_names
    }
    partials = _batched_compaction(
        program, val_local, ids_local, k_local, out_names,
    )
    if fctx is not None and fctx.nprocs > 1:
        # file-shuffle merge (ROADMAP #3): ZERO host-gathered partial
        # tables — partials hash-partition by group key through per-rank
        # spill files, each rank combines only its key partition, and
        # only the small finals are shared back
        return _merge_partials_shuffled(
            program, frame, keys, out_names, list(local_dict), partials,
        )
    from jax.experimental import multihost_utils as mh

    union_key_cols, _ = _allgather_dicts(list(local_dict))
    ks = np.asarray(
        mh.process_allgather(np.asarray([k_local], np.int64))
    ).ravel()  # one counts collective shared by every value column
    union_vals = {
        x: _allgather_rows(np.asarray(partials[x]), ks) for x in out_names
    }
    union_ids, group_key_cols, K = group_ids(union_key_cols)
    out_cols = _batched_compaction(
        program, union_vals, union_ids, K, out_names
    )
    return assemble_key_cols(frame, keys, group_key_cols), out_cols


def _merge_partials_shuffled(
    program, frame, keys, out_names, local_dict, partials
):
    """Merge per-rank partial aggregation tables through the file
    shuffle (blockstore.shuffle) instead of allgathering them: the
    combine work distributes over ranks, no rank ever holds every
    rank's partials, and the exchange needs no XLA collective. Returns
    the same replicated ``(key_cols, out_cols)`` as the allgather
    path, groups in lexicographic key order."""
    from ..blockstore import shuffle as _fs
    from .device_agg import assemble_key_cols
    from .exchange import partition_by_hash
    from .keys import group_ids

    key_names = [f"__k{i}" for i in range(len(local_dict))]
    table = {n: np.asarray(a) for n, a in zip(key_names, local_dict)}
    for x in out_names:
        table[x] = np.asarray(partials[x])
    nprocs = _fs.context().nprocs
    part = partition_by_hash([table[n] for n in key_names], nprocs)
    mine = _fs.shuffle_rows(table, part, name="agg.partials")
    kcols = [np.asarray(mine[n]) for n in key_names]
    if len(kcols[0]):
        ids, gk, K = group_ids(kcols)
        combined = _batched_compaction(
            program, {x: np.asarray(mine[x]) for x in out_names},
            ids.astype(np.int64), K, out_names,
        )
    else:
        gk = [a[:0] for a in kcols]
        combined = {x: np.asarray(mine[x])[:0] for x in out_names}
    final = {n: np.asarray(g) for n, g in zip(key_names, gk)}
    for x in out_names:
        final[x] = np.asarray(combined[x])
    union = _fs.allshare_table(final, name="agg.finals")
    union_key_cols = [
        np.asarray(union[n], dtype=object)
        if isinstance(union[n], list) else np.asarray(union[n])
        for n in key_names
    ]
    union_ids, group_key_cols, K = group_ids(union_key_cols)
    out_cols = _batched_compaction(
        program, {x: np.asarray(union[x]) for x in out_names},
        union_ids.astype(np.int64), K, out_names,
    )
    return assemble_key_cols(frame, keys, group_key_cols), out_cols


def aggregate(
    fetches: Fetches, grouped: GroupedData, strict: bool = False
) -> "TensorFrame":
    """Algebraic aggregation over grouped data: one output row per key.

    ≙ ``tfs.aggregate`` (core.py:401-419) → DebugRowOps.aggregate via
    ``TensorFlowUDAF`` (DebugRowOps.scala:554-599, 608-702). Fetches follow
    the ``x`` / ``x_input`` naming contract, like reduce_blocks.

    Execution order, no sorting of rows anywhere: sharded frames first
    try the on-device plans (ops/device_agg.py — per-shard segment
    reduce + one collective). Otherwise keys encode to dense group ids
    on the host (ops/keys.py; value columns are never reordered), then
    either
    (a) *segment fast path* — the fetches are recognized algebraic
    reducers and lower to one vectorized ``jax.ops.segment_*`` program
    over the whole frame fed UNSORTED ids (replacing the Catalyst
    shuffle + UDAF with a single XLA program), or
    (b) *generic path* — groups made contiguous by a stable argsort of
    the int ids, then per group chunked compaction through the user
    program with a bounded buffer (compact-every-N,
    ≙ DebugRowOps.scala:646-657), keeping the jit cache ≤ N shapes.
    """
    frame = grouped.frame
    keys = grouped.keys
    t0 = time.perf_counter()
    program, seg_info = _normalize_program(
        fetches, frame.schema, block=True, reduce_mode="blocks"
    )
    validate_reduce_blocks(program, frame.schema)
    if strict:
        _strict_lint(program, frame, block_mode=True)
    out_names = [o.name for o in program.outputs]
    unfused_reason: Optional[str] = None

    def _assemble(out_key_cols, out_cols, n_rows):
        infos = _agg_schema_infos(frame.schema, keys, program)
        block: Block = {}
        block.update(out_key_cols)
        for o in program.outputs:
            block[o.name] = out_cols[o.name]
        profiling.record("aggregate", time.perf_counter() - t0, n_rows)
        tf = TensorFrame([block], Schema(infos))
        if unfused_reason is not None:
            from ..plan import ir as plan_ir

            plan_ir.mark_unfused(tf, "aggregate", unfused_reason)
        return tf

    # -- whole-pipeline route: a lazy plan-carrying frame records an
    # `aggregate` node instead of forcing its chain — the lowering
    # composes the fused upstream maps with a segment-reduce epilogue
    # into ONE program per block (plan/lower.execute_aggregate), so
    # the mapped value columns never materialize. Sharded and
    # multi-process frames keep their explicit device/collective plans
    # below; non-algebraic fetches keep the UDAF path (and get TFG109
    # evidence recorded for lint_plan). --------------------------------
    algebraic = seg_info is not None and all(
        op in _SEGMENT_OPS or op == "reduce_mean" for _, op, _ in seg_info
    )
    from ..plan import ir as plan_ir

    if (
        getattr(frame, "_plan", None) is not None
        and not frame.is_sharded
        and plan_ir.fusion_enabled()
        and jax.process_count() == 1
    ):
        if algebraic:
            node = plan_ir.PlanNode(
                "aggregate",
                parent=plan_ir.node_for_parent(frame),
                program=program,
                out_names=out_names,
                keys=keys,
                spec=tuple(seg_info),
                schema=Schema(_agg_schema_infos(frame.schema, keys, program)),
            )
            node._extended = True  # terminal: consumers re-source on it

            def agg_pending():
                from ..plan.lower import execute_aggregate

                return execute_aggregate(node)

            result = TensorFrame(None, node.schema, pending=agg_pending)
            node.bind(result)
            result._plan = node
            return result
        unfused_reason = (
            "non-algebraic fetches (no segment lowering): the chain "
            "materializes before the generic UDAF path runs — use "
            "reduce_sum/min/max/mean DSL fetches to fuse the epilogue"
        )

    # -- sharded fast path: per-shard dense segment reduce + one ICI
    # collective (no host gather, no sort — see ops/device_agg.py) ----------
    if seg_info is not None and frame.is_sharded:
        from .device_agg import try_aggregate_device

        dev = try_aggregate_device(frame, keys, seg_info, out_names)
        if dev is not None:
            key_cols_d, out_cols_d = dev
            return _assemble(key_cols_d, out_cols_d, frame.num_rows)

    # -- multi-process generic path: local compaction + partial exchange.
    # Gate: the fetches must be safely re-appliable to stacked partials —
    # true for arbitrary non-reducer programs (the UDAF contract the user
    # opted into) and for sum/min/max reducers whose device plan
    # declined, but NOT for reduce_mean (mean of partial means is not
    # the group mean; its segment plan handles it or the host path
    # raises loudly) -----------------------------------------------------
    mean_free = seg_info is None or all(
        op != "reduce_mean" for _, op, _ in seg_info
    )
    if frame.is_sharded and jax.process_count() > 1 and mean_free:
        mp = _aggregate_multiprocess_generic(program, frame, keys, out_names)
        if mp is not None:
            key_cols_mp, out_cols_mp = mp
            return _assemble(key_cols_mp, out_cols_mp, frame.num_rows)

    # -- empty frame: build the zero-row result BEFORE any host gather —
    # column_values on a multi-process sharded frame raises for
    # non-addressable columns even when there is nothing to gather
    if frame.num_rows == 0:
        schema_e = Schema(_agg_schema_infos(frame.schema, keys, program))
        profiling.record("aggregate", time.perf_counter() - t0, 0)
        return TensorFrame(_empty_agg_blocks(schema_e), schema_e)

    # -- host paths ---------------------------------------------------------
    if algebraic:
        # -- segment fast path: gather + cached key encode + ONE
        # vectorized segment dispatch (shared with the plan lowering's
        # fallback — see _host_fast_aggregate) ------------------------------
        out_key_cols, out_cols, n = _host_fast_aggregate(
            program, frame, keys, seg_info, out_names
        )
        return _assemble(out_key_cols, out_cols, n)

    # -- generic (UDAF-equivalent) path: level-batched device
    # compaction — see _batched_compaction ----------------------------------
    from .keys import frame_group_ids

    val_cols = {}
    for x in out_names:
        vals = frame.column_values(x)
        if vals.dtype == object:
            raise ValueError(
                f"Column {x!r} is ragged; aggregate requires uniform cells "
                "(run analyze() first)."
            )
        val_cols[x] = _demote_cast(vals, program.input(f"{x}_input"))
    seg_ids, group_key_cols, num_groups = frame_group_ids(frame, keys)
    out_cols = _batched_compaction(
        program, val_cols, seg_ids, num_groups, out_names
    )
    return _assemble(dict(zip(keys, group_key_cols)), out_cols, len(seg_ids))

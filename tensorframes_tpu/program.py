"""Program capture and static analysis.

The reference ingests user programs in three forms
(project/Build.scala:102-107): the TF Python API, serialized protobuf
``GraphDef``\\ s, and a small Scala DSL — all funnelled into a byte blob that
is later *re-imported into the TF runtime* to discover inputs/outputs/
dtypes/shapes (``analyzeGraphTF``, TensorFlowOps.scala:101-141).

The TPU-native equivalents here:

* **traced Python functions** over ``jax.numpy`` (primary; ≙ the TF Python
  path — closure-captured values play the role of frozen ``tf.Variable``
  constants, core.py:42-56);
* **DSL expression graphs** (:mod:`tensorframes_tpu.dsl`), compiled to the
  same ``Program`` form;
* **serialized StableHLO** via ``jax.export`` (≙ ``GraphDef`` file loading,
  PythonInterface.scala:115-118).

Analysis is *static*: instead of loading a graph into a live runtime, we
``jax.eval_shape`` the program against abstract inputs. Unknown (batch)
dimensions are discovered by probing two distinct batch sizes and marking
every output dim that co-varies with the probe — this replaces the
reference's shape-hints workaround for dims the graph pruned
(ShapeDescription.scala:12-19). Explicit user hints still override
(the hint-override rule, TensorFlowOps.scala:126-133).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import dtypes as dt
from .shape import Shape, Unknown

# Probe batch sizes used to discover batch-covariant output dims. Coprime and
# unequal so a dim matching both probes by accident is effectively impossible.
_PROBE_A = 3
_PROBE_B = 7


class HoistedProgram:
    """A function traced to a jaxpr with its closure CONSTANTS lifted to
    runtime arguments (single shared implementation — the executor's
    per-shape cache, ``Program.cost_analysis``, and tests all use this).

    Why hoist: ``jax.jit(fn)`` embeds closure-captured weights as HLO
    literals and XLA constant-folds through them — measured round 3,
    that re-materialized int8-quantized weights as full f32 constants
    (zero byte saving) and re-embedded every model's weights into every
    per-shape HLO. Passing ``closed.consts`` as arguments keeps weights
    as runtime parameters: int8 stays ``s8`` in the executable and the
    compiler never sees a literal to fold.

    Constants are ``jax.device_put`` once at construction, where the
    calls will want them. With no ``placement`` they sit *uncommitted*
    on the default device: a one-device executable reuses those buffers
    call after call, and jax may still move them beside inputs that
    live elsewhere. An executable compiled for a mesh, though, would
    copy uncommitted constants to every device of the mesh on every
    call, so for inputs that carry a placement the executor passes
    ``placement`` — the constants fully replicated over the inputs' own
    device set — and they are *committed* there, once; ``lower()`` sees
    them with that sharding and each call is an enqueue."""

    __slots__ = (
        "jitted", "consts", "in_tree", "_flat_abstract", "_run",
        "_jitted_donate", "closed", "out_tree", "placement",
    )

    def __init__(self, fn: Callable, abstract_inputs, name: str = "run",
                 placement=None):
        from jax.core import eval_jaxpr

        closed, out_shape = jax.make_jaxpr(fn, return_shape=True)(
            abstract_inputs
        )
        out_tree = jax.tree_util.tree_structure(out_shape)
        self._flat_abstract, self.in_tree = jax.tree_util.tree_flatten(
            abstract_inputs
        )
        jaxpr = closed.jaxpr
        # kept for the persistent compile cache: the fingerprint hashes
        # the jaxpr text + const avals (values stay out of the key — in
        # this hoisted form the executable is weight-independent), and
        # the store's serialized entries reconstruct call treedefs from
        # (n_consts, input count, out_tree)
        self.closed = closed
        self.out_tree = out_tree
        self.placement = placement
        self.consts = jax.device_put(closed.consts, placement)

        def run(consts, flat_ins):
            outs = eval_jaxpr(jaxpr, consts, *flat_ins)
            return jax.tree_util.tree_unflatten(out_tree, outs)

        # the XLA module is named after the jitted callable
        # (``jit_<name>``): what a device trace calls this program
        run.__name__ = run.__qualname__ = name
        self._run = run
        self.jitted = jax.jit(run)
        self._jitted_donate = None

    def __call__(self, inputs, donate: bool = False):
        flat, tree = jax.tree_util.tree_flatten(inputs)
        if tree != self.in_tree:
            raise ValueError("input structure changed since tracing")
        if donate:
            # donate the flat INPUTS only — the hoisted consts (model
            # weights) are reused across calls and must never be donated
            if self._jitted_donate is None:
                self._jitted_donate = jax.jit(
                    self._run, donate_argnums=(1,)
                )
            return self._jitted_donate(self.consts, flat)
        return self.jitted(self.consts, flat)

    def aot_compile(self):
        """AOT-compile at the traced shapes (cost analysis, HLO text)."""
        return self.jitted.lower(self.consts, self._flat_abstract).compile()

    def const_bytes(self) -> int:
        """Total bytes of the hoisted constants — the program's true
        weight-residency footprint (QuantizedTensor-aware by summing the
        flattened leaves)."""
        return sum(
            int(np.prod(c.shape)) * c.dtype.itemsize
            for c in jax.tree_util.tree_leaves(self.consts)
        )


@dataclasses.dataclass(frozen=True)
class TensorSpec:
    """Name + dtype + (partial) shape of one program input or output.

    ≙ ``GraphNodeSummary`` (TensorFlowOps.scala:163-169).
    """

    name: str
    dtype: dt.ScalarType
    shape: Shape  # may contain Unknown dims

    def pretty(self) -> str:
        return f"{self.name}: {self.dtype.name}{self.shape}"


class Program:
    """A compiled-form user program: named inputs → named outputs.

    ``fn`` maps a dict of arrays (keyed by input name) to a dict of arrays
    (keyed by output name). It must be jit-traceable. ``inputs`` carries the
    declared dtype/shape of each input (shapes may have Unknown dims);
    ``outputs`` is filled in by :func:`analyze_program`.
    """

    def __init__(
        self,
        fn: Callable[[Dict[str, jnp.ndarray]], Dict[str, jnp.ndarray]],
        inputs: Sequence[TensorSpec],
        outputs: Optional[Sequence[TensorSpec]] = None,
        fetch_order: Optional[Sequence[str]] = None,
        role: str = "map",
    ):
        self.fn = fn
        # what the program computes in a verb, for the executable's name
        # in a device trace (``jit_tftpu_<role>_<block|rows>``): "map",
        # "reduce" (a reduce program, run per block and as the combine),
        # "fused_map" / "map_<epilogue>" (plan-fused chains)
        self.role = role
        self.inputs: List[TensorSpec] = list(inputs)
        self.outputs: List[TensorSpec] = list(outputs) if outputs else []
        # order in which the user listed fetches (defines result ordering for
        # reduce verbs returning numpy arrays)
        self.fetch_order: List[str] = (
            list(fetch_order) if fetch_order else [o.name for o in self.outputs]
        )
        self._compiled = None  # memoized CompiledProgram (ops/executor.py)

    def compiled(self):
        """Memoized jitted entrypoints. Reusing a Program across verb calls
        reuses the XLA executable — the analogue of the reference keeping
        one Session across a pairwise fold (DebugRowOps.scala:939-979), but
        across whole verb invocations."""
        if self._compiled is None:
            from .ops.executor import CompiledProgram

            self._compiled = CompiledProgram(self)
        return self._compiled

    @property
    def input_names(self) -> List[str]:
        return [s.name for s in self.inputs]

    @property
    def output_names(self) -> List[str]:
        return [s.name for s in self.outputs]

    def input(self, name: str) -> TensorSpec:
        for s in self.inputs:
            if s.name == name:
                return s
        raise KeyError(
            f"Program has no input {name!r}; inputs: {self.input_names}"
        )

    def output(self, name: str) -> TensorSpec:
        for s in self.outputs:
            if s.name == name:
                return s
        raise KeyError(
            f"Program has no output {name!r}; outputs: {self.output_names}"
        )

    def rename_inputs(self, mapping: Dict[str, str]) -> "Program":
        """Rename inputs (placeholder → column feed_dict remapping,
        ≙ core.py:128-142). ``mapping`` maps old input name → new name."""
        new_inputs = [
            TensorSpec(mapping.get(s.name, s.name), s.dtype, s.shape)
            for s in self.inputs
        ]
        inner = self.fn
        inv = {mapping.get(s.name, s.name): s.name for s in self.inputs}

        def fn(feeds: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
            return inner({inv.get(k, k): v for k, v in feeds.items()})

        renamed = Program(fn, new_inputs, self.outputs, self.fetch_order,
                          role=self.role)
        # carry the segment-lowering info (input names remapped) so the
        # aggregate fast path survives feed_dict renames
        seg = getattr(self, "seg_info", None)
        if seg is not None:
            renamed.seg_info = [
                (out, op, mapping.get(inp, inp)) for (out, op, inp) in seg
            ]
        return renamed

    def explain(self) -> str:
        ins = ", ".join(s.pretty() for s in self.inputs)
        outs = ", ".join(s.pretty() for s in self.outputs)
        extra = ""
        if self._compiled is not None:
            sizes = self._compiled.cache_sizes()
            extra = (
                f", compiled_shapes={{block: {sizes['block']}, "
                f"vmap: {sizes['vmap']}}}"
            )
        return f"Program(inputs=[{ins}], outputs=[{outs}]{extra})"

    def lint(
        self,
        probe: int = 8,
        rules: Optional[Sequence[str]] = None,
        hbm_budget_bytes: Optional[int] = None,
    ):
        """Pre-execution static diagnostics over this program's jaxpr +
        specs (:mod:`tensorframes_tpu.analysis`): recompile storms, f64
        leaks, dead inputs, donation aliasing, NaN hazards, HBM budget.
        Purely static — tracing only, zero XLA compiles, zero transfers.
        Returns a :class:`~tensorframes_tpu.analysis.DiagnosticReport`;
        chain ``.raise_on_errors()`` for strict behavior."""
        from .analysis import lint_program

        return lint_program(
            self, probe=probe, rules=rules,
            hbm_budget_bytes=hbm_budget_bytes,
        )

    def cost_analysis(self, probe: int = 8) -> Dict[str, float]:
        """XLA's compiled cost model for this program: flops, bytes
        accessed, peak memory (keys as XLA reports them). Unknown dims are
        probed at ``probe`` rows. Observability upgrade over the
        reference's log4j-only tracing (SURVEY §5): the reference could
        not ask its runtime what a graph costs without running it."""
        cache = getattr(self, "_cost_cache", None)
        if cache is None:
            cache = self._cost_cache = {}
        if probe in cache:
            return dict(cache[probe])
        abstract = _abstract_inputs(self.inputs, probe)
        compiled = None
        from .config import get_config

        if get_config().hoist_constants:
            # cost the program in the same form the executor runs it:
            # closure constants (weights) lifted to runtime parameters —
            # otherwise XLA folds through them and the model (a) misses
            # their HBM traffic and (b) un-does int8 quantization
            try:
                compiled = HoistedProgram(self.fn, abstract).aot_compile()
            except Exception:  # exotic programs: closure-capture costing
                compiled = None
        if compiled is None:
            compiled = jax.jit(self.fn).lower(abstract).compile()
        cache[probe] = dict(compiled.cost_analysis() or {})
        return dict(cache[probe])

    def flops_per_row(self, probe: int = 8) -> float:
        """Marginal model FLOPs per input row, estimated from XLA's cost
        model at two probe batch sizes (the difference removes any
        batch-independent constant work). Memoized — feeds the MFU
        column in ``profiling.report()``."""
        cached = getattr(self, "_flops_per_row", None)
        if cached is not None:
            return cached
        f1 = float(self.cost_analysis(probe).get("flops", 0.0))
        f2 = float(self.cost_analysis(2 * probe).get("flops", 0.0))
        val = max(0.0, (f2 - f1) / probe)
        self._flops_per_row = val
        return val

    def bytes_per_row(self, probe: int = 8) -> float:
        """Marginal XLA-cost-model bytes accessed per input row (same
        two-probe scheme as :meth:`flops_per_row`). Feeds the HBM GB/s
        column in ``profiling.report()`` — and makes weight-traffic
        claims (int8 quantization's 4×) checkable without hardware
        counters."""
        cached = getattr(self, "_bytes_per_row", None)
        if cached is not None:
            return cached
        b1 = float(self.cost_analysis(probe).get("bytes accessed", 0.0))
        b2 = float(self.cost_analysis(2 * probe).get("bytes accessed", 0.0))
        val = max(0.0, (b2 - b1) / probe)
        self._bytes_per_row = val
        return val

    def total_bytes_accessed(self, probe: int = 8) -> float:
        """Absolute ``bytes accessed`` at ``probe`` rows — includes the
        batch-independent weight traffic ``bytes_per_row`` differences
        away (exactly the part int8 quantization shrinks)."""
        return float(self.cost_analysis(probe).get("bytes accessed", 0.0))


def _abstract_inputs(
    inputs: Sequence[TensorSpec], probe: int
) -> Dict[str, jax.ShapeDtypeStruct]:
    out = {}
    for s in inputs:
        dims = tuple(probe if d == Unknown else d for d in s.shape.dims)
        out[s.name] = jax.ShapeDtypeStruct(dims, s.dtype.np_dtype)
    return out


def analyze_program(
    program: Program,
    hints: Optional[Dict[str, Shape]] = None,
) -> Program:
    """Static shape/dtype analysis of a Program (≙ ``analyzeGraphTF``).

    Runs ``jax.eval_shape`` with two different probe values substituted for
    Unknown input dims; output dims equal to a probe in both runs (and
    scaling with it) are marked Unknown (batch-covariant). ``hints``
    (output name → Shape) override discovered shapes wherever the hint dim
    is known — the reference's hint-override rule
    (TensorFlowOps.scala:126-133).
    """
    hints = hints or {}

    def run(probe: int):
        abstract = _abstract_inputs(program.inputs, probe)
        return jax.eval_shape(program.fn, abstract)

    res_a = run(_PROBE_A)
    if any(s.shape.has_unknown for s in program.inputs):
        res_b = run(_PROBE_B)
    else:
        res_b = res_a

    if not isinstance(res_a, dict):
        raise TypeError(
            "Program function must return a dict of named outputs; got "
            f"{type(res_a).__name__}"
        )

    outputs: List[TensorSpec] = []
    order = program.fetch_order or list(res_a.keys())
    for name in res_a:
        sa, sb = res_a[name], res_b[name]
        dims = []
        for da, db in zip(sa.shape, sb.shape):
            if da == db:
                dims.append(da)
            else:
                # dim co-varied with the probe → batch-dependent → Unknown
                dims.append(Unknown)
        shape = Shape(dims)
        if name in hints:
            shape = shape.refine(Shape.from_any(hints[name]))
        outputs.append(TensorSpec(name, dt.from_numpy(sa.dtype), shape))
    # keep fetch order where given
    by_name = {o.name: o for o in outputs}
    ordered = [by_name[n] for n in order if n in by_name] + [
        o for o in outputs if o.name not in order
    ]
    return Program(program.fn, program.inputs, ordered, order,
                   role=program.role)


# ---------------------------------------------------------------------------
# Ingestion form (a): plain Python functions
# ---------------------------------------------------------------------------

def program_from_function(
    fn: Callable,
    input_specs: Dict[str, TensorSpec],
    output_names: Optional[Sequence[str]] = None,
) -> Program:
    """Wrap a Python function whose positional args are column names.

    The function receives one array per parameter (parameter name = input
    name) and returns either a dict name→array or a single array / tuple —
    singles are named after ``output_names`` (or the function's name).
    Closure-captured arrays are compile-time constants, playing the role of
    the reference's frozen variables (core.py:42-56).
    """
    import inspect

    sig = inspect.signature(fn)
    params = [p.name for p in sig.parameters.values()
              if p.kind in (p.POSITIONAL_OR_KEYWORD, p.KEYWORD_ONLY)]
    missing = [p for p in params if p not in input_specs]
    if missing:
        raise ValueError(
            f"Function parameter(s) {missing} do not match any known input; "
            f"available: {sorted(input_specs)}"
        )
    inputs = [input_specs[p] for p in params]

    def wrapped(feeds: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
        res = fn(*[feeds[p] for p in params])
        if isinstance(res, dict):
            return res
        if isinstance(res, (tuple, list)):
            names = output_names or [f"{fn.__name__}_{i}" for i in range(len(res))]
            if len(names) != len(res):
                raise ValueError(
                    f"Function returned {len(res)} outputs but "
                    f"{len(names)} output names were given"
                )
            return dict(zip(names, res))
        name = (output_names or [fn.__name__])[0]
        return {name: res}

    return Program(wrapped, inputs, fetch_order=list(output_names or []))


# ---------------------------------------------------------------------------
# Ingestion form (c): serialized StableHLO artifacts (jax.export)
# ---------------------------------------------------------------------------

def save_program(program: Program, path: str, batch: int = 8) -> None:
    """Serialize a Program to a StableHLO artifact on disk
    (≙ writing ``proto.pb``, core.py:58-69). Unknown dims are exported as
    symbolic dimensions so the artifact stays batch-polymorphic."""
    from jax import export as jax_export

    names = [s.name for s in program.inputs]
    scopes = jax_export.SymbolicScope()
    args = []
    for s in program.inputs:
        dims = tuple(
            jax_export.symbolic_shape(f"b{i}", scope=scopes)[0]
            if d == Unknown
            else d
            for i, d in enumerate(s.shape.dims)
        )
        args.append(jax.ShapeDtypeStruct(dims, s.dtype.np_dtype))

    def positional(*xs):
        return program.fn(dict(zip(names, xs)))

    exported = jax_export.export(jax.jit(positional))(*args)
    blob = exported.serialize()
    meta = {
        "inputs": [(s.name, s.dtype.name, list(s.shape.dims)) for s in program.inputs],
        "fetch_order": program.fetch_order,
    }
    import json

    with open(path, "wb") as f:
        header = json.dumps(meta).encode("utf-8")
        f.write(len(header).to_bytes(8, "little"))
        f.write(header)
        f.write(blob)


def load_program(path: str) -> Program:
    """Load a serialized Program (≙ ``graphFromFile``,
    PythonInterface.scala:115-118)."""
    import json

    from jax import export as jax_export

    with open(path, "rb") as f:
        hlen = int.from_bytes(f.read(8), "little")
        meta = json.loads(f.read(hlen).decode("utf-8"))
        blob = f.read()
    exported = jax_export.deserialize(bytearray(blob))
    names = [n for (n, _, _) in meta["inputs"]]
    inputs = [
        TensorSpec(n, dt.by_name(t), Shape(dims)) for (n, t, dims) in meta["inputs"]
    ]

    def fn(feeds: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
        return exported.call(*[feeds[n] for n in names])

    return Program(fn, inputs, fetch_order=meta.get("fetch_order"))

"""tensorframes_tpu — a TPU-native columnar-frame compute framework.

A brand-new framework with the capabilities of TensorFrames (the reference,
databricks/tensorframes): attach numeric programs to the columns of a
distributed dataframe through five verbs — ``map_rows``, ``map_blocks``
(± trimmed), ``reduce_rows``, ``reduce_blocks``, keyed ``aggregate`` — plus
schema tooling (``analyze``, ``append_shape``, ``print_schema``).

Architecture (TPU-first, not a port — see SURVEY.md §7):

* a frame is a block-partitioned columnar container of arrays
  (host numpy and/or device ``jax.Array`` shards over a mesh), not a Spark
  DataFrame;
* a user program is a traced JAX function / expression graph
  (jaxpr / StableHLO), not a protobuf ``GraphDef`` fed to a TF Session;
* distribution is ``jax.sharding`` + ``shard_map`` with ICI collectives,
  not driver-coordinated ``RDD.reduce`` / Catalyst shuffles.
"""

from __future__ import annotations

import jax as _jax

from .config import get_config as _get_config, configure  # noqa: F401
from .config import use_compile_cache as _use_compile_cache

if _get_config().enable_x64:
    # The reference's core column types are Double/Long
    # (datatypes.scala:265-267); x64 makes those exact end-to-end.
    _jax.config.update("jax_enable_x64", True)

# persistent executable cache: a fresh process deserializes compiled XLA
# programs instead of paying the 20-40s TPU compile again. One resolver
# (config.resolve_compile_cache_dir) places it; off unless the
# environment names a directory.
_use_compile_cache()

from . import dtypes  # noqa: E402,F401
from .shape import Shape, Unknown  # noqa: E402,F401
from .schema import ColumnInfo, Schema  # noqa: E402,F401
from .frame import TensorFrame, describe, frame_from_arrays, frame_from_pandas, frame_from_rows  # noqa: E402,F401
from .frame import analyze, append_shape, print_schema, explain  # noqa: E402,F401
from .dsl import (  # noqa: E402,F401
    Node,
    abs_,
    add,
    apply_fn,
    block,
    constant,
    div,
    exp,
    fill,
    identity,
    log,
    matmul,
    mul,
    ones,
    placeholder,
    reduce_max,
    reduce_mean,
    reduce_min,
    reduce_sum,
    relu,
    row,
    scope,
    sigmoid,
    sqrt,
    square,
    sub,
    tanh,
    with_graph,
    zeros,
)
from .program import (  # noqa: E402,F401
    Program,
    TensorSpec,
    load_program,
    program_from_function,
    save_program,
)
from .graphdef import (  # noqa: E402,F401
    load_graphdef,
    load_saved_model,
    parse_graphdef,
    parse_saved_model,
    parse_saved_model_meta_graphs,
    program_from_graphdef,
)
from .bundle import restore_variables  # noqa: E402,F401
from .validation import StaticAnalysisError, ValidationError  # noqa: E402,F401
from . import analysis  # noqa: E402,F401
from .analysis import analyze_frame, lint_plan, lint_program  # noqa: E402,F401
from . import plan  # noqa: E402,F401  (registers tftpu_plan_* metrics)
from . import kernels  # noqa: E402,F401  (registers tftpu_kernels_* metrics)
from .plan import explain_plan  # noqa: E402,F401
from .ops.verbs import (  # noqa: E402,F401
    NumpyUDF,
    aggregate,
    compile_program,
    map_blocks,
    map_rows,
    numpy_udf,
    reduce_blocks,
    reduce_rows,
)
from .checkpoint import Checkpointer, CheckpointCorruptionError  # noqa: E402,F401
from .training import run_resumable  # noqa: E402,F401
from . import resilience  # noqa: E402,F401  (registers tftpu_fleet_* metrics)
from .resilience import RetryPolicy, StepGuard, supervise  # noqa: E402,F401
from . import io  # noqa: E402,F401
from .io import (  # noqa: E402,F401
    frame_from_arrow,
    frame_to_arrow,
    load_frame,
    read_csv,
    read_parquet,
    save_frame,
    write_csv,
    write_parquet,
)
from .utils import profiling  # noqa: E402,F401
from . import observability  # noqa: E402,F401
from .observability import StepTelemetry  # noqa: E402,F401
from . import compilecache  # noqa: E402,F401  (registers tftpu_compilecache_* metrics)
from .compilecache import WarmupReport, warmup  # noqa: E402,F401
from . import blockstore  # noqa: E402,F401  (registers tftpu_blockstore_* metrics)
from .blockstore import (  # noqa: E402,F401
    BlockStore,
    SpilledFrame,
    stream_chain,
)
from .io import scan_csv, scan_parquet  # noqa: E402,F401
from . import serving  # noqa: E402,F401  (registers tftpu_serving_* metrics)
from .serving import (  # noqa: E402,F401
    DecodeConfig,
    DecodeEngine,
    Server,
    ServingConfig,
    serve_http,
)

__version__ = "0.3.0"

__all__ = [
    "TensorFrame",
    "frame_from_arrays",
    "frame_from_pandas",
    "frame_from_rows",
    "Shape",
    "Unknown",
    "ColumnInfo",
    "Schema",
    "dtypes",
    "configure",
    # verbs (≙ reference __init__.py:15-21 public surface)
    "map_rows",
    "map_blocks",
    "reduce_rows",
    "reduce_blocks",
    "aggregate",
    "compile_program",
    "numpy_udf",
    "NumpyUDF",
    "analyze",
    "append_shape",
    "print_schema",
    "explain",
    "describe",
    "plan",
    "explain_plan",
    "lint_plan",
    # aux subsystems
    "serving",
    "Server",
    "ServingConfig",
    "DecodeConfig",
    "DecodeEngine",
    "serve_http",
    "Checkpointer",
    "CheckpointCorruptionError",
    "resilience",
    "RetryPolicy",
    "StepGuard",
    "supervise",
    "run_resumable",
    "profiling",
    "observability",
    "StepTelemetry",
    "io",
    "save_frame",
    "load_frame",
    "read_csv",
    "write_csv",
    "frame_from_arrow",
    "frame_to_arrow",
    "read_parquet",
    "write_parquet",
    "scan_csv",
    "scan_parquet",
    # out-of-core data plane
    "blockstore",
    "BlockStore",
    "SpilledFrame",
    "stream_chain",
    # dsl / placeholder helpers
    "Node",
    "block",
    "row",
    "placeholder",
    "constant",
    "zeros",
    "ones",
    "fill",
    "with_graph",
    "scope",
    # op catalog
    "identity",
    "add",
    "sub",
    "mul",
    "div",
    "matmul",
    "reduce_sum",
    "reduce_min",
    "reduce_max",
    "reduce_mean",
    "exp",
    "log",
    "tanh",
    "sqrt",
    "abs_",
    "square",
    "sigmoid",
    "relu",
    "apply_fn",
    # programs
    "Program",
    "TensorSpec",
    "program_from_function",
    "save_program",
    "load_program",
    "ValidationError",
    # static analysis (tfguard)
    "analysis",
    "analyze_frame",
    "lint_program",
    "StaticAnalysisError",
]

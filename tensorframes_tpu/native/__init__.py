"""Native marshalling layer: C++ kernels for the row⇄columnar hot loops.

The runtime half of the host⇄device marshalling layer (the compute half is
XLA). Plays the role of the reference's hand-unrolled Scala loops + JNI
buffer hand-off (DataOps.scala:18-167, datatypes.scala:328-565): one native
pass gathers scalar cells out of row dicts into contiguous buffers (viewed
as numpy arrays zero-copy, then `jax.device_put` to HBM), and one native
pass materializes result rows from column buffers.

The extension is compiled on demand from the bundled source with g++ (no
pybind11 — plain CPython C API) and cached next to this file, with the
source's sha256 recorded beside it: the ``.so`` is reused only while that
hash matches ``rowpack.cpp`` (mtimes do not survive a copy of the tree).
Without a compiler the pure-Python path serves; :func:`status` says which
of ``built | loaded | unavailable`` happened, and ``chip_smoke.py`` fails
on ``unavailable``. ``TFS_TPU_DISABLE_NATIVE=1`` disables it outright.
"""

from __future__ import annotations

import hashlib
import os
import subprocess
import sysconfig
import threading
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..utils import get_logger

logger = get_logger(__name__)

_DTYPE_CODES = {
    np.dtype(np.float64): 0,
    np.dtype(np.float32): 1,
    np.dtype(np.int32): 2,
    np.dtype(np.int64): 3,
}

_lock = threading.Lock()
_mod = None
_load_attempted = False
_status = "unavailable"


def _source_path() -> str:
    return os.path.join(os.path.dirname(__file__), "rowpack.cpp")


def _so_path() -> str:
    return os.path.join(os.path.dirname(__file__), "_rowpack.so")


def _hash_path() -> str:
    return _so_path() + ".sha256"


def _source_hash() -> str:
    with open(_source_path(), "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def _so_is_current() -> bool:
    """The cached ``.so`` was built from exactly this ``rowpack.cpp``."""
    try:
        with open(_hash_path()) as f:
            recorded = f.read().strip()
    except OSError:
        return False
    return os.path.exists(_so_path()) and recorded == _source_hash()


def _build() -> bool:
    """Compile rowpack.cpp → _rowpack.so with g++ and record the
    source hash beside it. Returns success."""
    include = sysconfig.get_paths()["include"]
    # build to a temp path and os.replace so an interrupted g++ can never
    # leave a truncated .so at the final path (which would otherwise look
    # newer than the source and permanently disable the native path)
    tmp = _so_path() + f".tmp{os.getpid()}"
    cmd = [
        "g++",
        "-O3",
        "-shared",
        "-fPIC",
        "-std=c++17",
        f"-I{include}",
        _source_path(),
        "-o",
        tmp,
    ]
    try:
        proc = subprocess.run(
            cmd, capture_output=True, text=True, timeout=120
        )
        if proc.returncode != 0:  # pragma: no cover
            logger.warning("native build failed:\n%s", proc.stderr[-2000:])
            return False
        os.replace(tmp, _so_path())
        with open(_hash_path(), "w") as f:
            f.write(_source_hash() + "\n")
    except (OSError, subprocess.TimeoutExpired) as e:  # pragma: no cover
        logger.warning("native build failed: %s", e)
        return False
    finally:
        if os.path.exists(tmp):  # pragma: no cover
            try:
                os.remove(tmp)
            except OSError:
                pass
    return True


def _load():
    global _mod, _load_attempted, _status
    with _lock:
        if _load_attempted:
            return _mod
        _load_attempted = True
        if os.environ.get("TFS_TPU_DISABLE_NATIVE", "") == "1":
            return None
        _status = "loaded"
        if not _so_is_current():
            if not _build():
                _status = "unavailable"
                return None
            _status = "built"
        try:
            from . import _rowpack  # type: ignore[attr-defined]

            _mod = _rowpack
        except ImportError as e:  # pragma: no cover
            # a stale/corrupt artifact: rebuild once from scratch
            logger.warning("native module failed to import (%s); rebuilding", e)
            try:
                os.remove(_so_path())
            except OSError:
                pass
            _mod = None
            if _build():
                try:
                    import importlib

                    _mod = importlib.import_module(f"{__name__}._rowpack")
                    _status = "built"
                except ImportError:
                    _mod = None
        if _mod is None:
            _status = "unavailable"
        return _mod


def available() -> bool:
    return _load() is not None


def status() -> str:
    """How this process got the extension: ``built`` (compiled now),
    ``loaded`` (a cached ``.so`` whose recorded hash matches the
    source) or ``unavailable`` (disabled, or the build failed and the
    pure-Python path serves)."""
    _load()
    return _status


def supported_dtype(np_dtype) -> bool:
    return np.dtype(np_dtype) in _DTYPE_CODES


def gather_column(
    rows: Sequence[Dict[str, object]], name: str, np_dtype
) -> Optional[np.ndarray]:
    """Pack ``rows[i][name]`` scalars into a 1-D array in one native pass.

    Returns None when the native module is unavailable; raises on missing
    keys / non-convertible cells (callers catch and fall back).
    """
    mod = _load()
    if mod is None:
        return None
    dtype = np.dtype(np_dtype)
    buf = mod.gather_column(rows, name, _DTYPE_CODES[dtype])
    # bytearray → writable ndarray view, zero-copy
    return np.frombuffer(buf, dtype=dtype)


def dict_encode(values) -> Optional[tuple]:
    """One native hash pass over arbitrary hashable cells: returns
    ``(codes int32 ndarray, uniques list)`` with codes in FIRST-APPEARANCE
    order (caller remaps to lexicographic). None when unavailable."""
    mod = _load()
    if mod is None:
        return None
    buf, uniques = mod.dict_encode(
        values if isinstance(values, (list, tuple)) else list(values)
    )
    return np.frombuffer(buf, dtype=np.int32), uniques


def stack_cells(cells: Sequence[np.ndarray]) -> Optional[np.ndarray]:
    """Stack equal-shape contiguous ndarray cells into ``[len(cells),
    *cell_shape]`` with ONE native memcpy pass — np.stack pays
    per-element numpy dispatch, which dominates the ragged map_rows
    host path at thousands of small cells per shape group. Returns
    None when unavailable or the first cell is not a supported dense
    array (callers fall back to np.stack). Mismatched cells raise
    ValueError — for shape mismatch np.stack does too, but for DTYPE
    mismatch np.stack would silently promote; a caller wanting
    promotion must catch and fall back."""
    mod = _load()
    if mod is None or len(cells) == 0:
        return None
    c0 = cells[0]
    if not isinstance(c0, np.ndarray) or c0.dtype.hasobject:
        return None
    if not c0.flags.c_contiguous:
        return None
    buf = mod.stack_cells(cells)
    return np.frombuffer(buf, dtype=c0.dtype).reshape(
        (len(cells),) + c0.shape
    )


def columns_to_rows(
    names: Sequence[str], arrays: Sequence[np.ndarray]
) -> Optional[List[Dict[str, object]]]:
    """Materialize a list of row dicts from scalar column arrays in one
    native pass. Returns None when unavailable or any column is not a
    supported 1-D numeric array.
    """
    mod = _load()
    if mod is None or not names:
        # zero-column frames keep the Python path's semantics
        return None
    bufs = []
    codes = []
    for a in arrays:
        a = np.ascontiguousarray(a)
        if a.ndim != 1 or a.dtype not in _DTYPE_CODES:
            return None
        bufs.append(a)
        codes.append(_DTYPE_CODES[a.dtype])
    return mod.scatter_rows(tuple(names), tuple(bufs), tuple(codes))

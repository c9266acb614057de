"""Stable content fingerprints for compiled-program cache keys.

The persistent executable store (:mod:`.store`) keys entries by a hash
that must survive process restarts, so nothing here may depend on
Python ``hash()`` (randomized per process), object ids, or memory
addresses. The fingerprint covers everything that changes the compiled
artifact:

* the program's **jaxpr** (pretty-printed, with ``0x…`` memory
  addresses scrubbed — a closure that traces identically in two
  processes must key identically);
* the **constants** closed over by the trace: avals always, values
  only in the *plain* (closure-capture) form where XLA bakes them into
  the executable — the hoisted form passes weights as runtime
  arguments, so different weights share one cached executable;
* the **feed-shape bucket**: sorted (name, shape, dtype) of the
  abstract inputs the executable was specialized to;
* the **input shardings**: per-argument sharding descriptors
  (mesh axis names + shape + device assignment + per-dim partition
  spec — :func:`~tensorframes_tpu.parallel.mesh.sharding_descriptor`),
  because an AOT executable is layout-specialized and XLA compiles a
  different collective schedule per layout;
* the **dtype policy** (x64 flag + demotion mode) and the fetch order;
* the **environment**: backend, device kind, device/process count, the
  process-index-independent **fleet topology** (device → process map,
  :func:`~tensorframes_tpu.parallel.distributed.process_topology` —
  every rank of an SPMD fleet computes the same key, so one rank's
  published executable is every rank's hit; resizing the fleet misses
  cleanly), ``XLA_FLAGS``, jax version, entry kind (block/vmap/fn),
  donation and hoist flags, the straggler-kernel selection state
  (:func:`tensorframes_tpu.kernels.fingerprint_token` — pallas
  enabled/switched off, force hook, interpreter mode), and the store
  format version.

``TFG108`` (analysis/rules.py) calls :func:`program_fingerprint` twice
with independent traces: a program whose fingerprint differs across
identical rebuilds (non-deterministically serialized captures) would
miss the persistent store on every process start — a miss storm.
:func:`fingerprint_components` exposes the per-component digests so the
rule can *name* the unstable component (including which input's
sharding) instead of reporting an opaque hash mismatch.
"""

from __future__ import annotations

import hashlib
import json
import os
import re
from typing import Dict, Iterable, Optional, Sequence, Tuple

import numpy as np

#: Bumped whenever the entry layout or key composition changes: old
#: entries simply miss (never mis-deserialize). v2: sharding/topology
#: axes joined the key (unified sharded/multi-process AOT dispatch).
#: v3: the straggler-kernel selection state joined the env component
#: (ISSUE 12 — a ``disable_pallas()`` flip or a ``TFTPU_PALLAS``
#: change must never serve a stale executable).
#: v4: the verified-lift state joined the env component (ISSUE 18 —
#: a ``TFTPU_LIFT`` flip or a synthesis-rule bump swaps a lifted
#: program for a callback one; the two must never share a key).
#: v5: entries record the executable's device ids and load onto exactly
#: those (PR 21 — jax 0.9's ``deserialize_and_load`` otherwise loads
#: onto EVERY local device, and a one-device executable then refuses
#: its one-shard arguments on a multi-device host).
FORMAT_VERSION = 5

__all__ = [
    "FORMAT_VERSION",
    "content_digest",
    "fingerprint_components",
    "fingerprint_from_closed",
    "frame_content_digest",
    "part_signature",
    "program_fingerprint",
]

_ADDR_RE = re.compile(r"0x[0-9a-fA-F]+")


# ---------------------------------------------------------------------------
# input-partition content digests (ISSUE 20): the OTHER half of the
# registered-query result-cache key. The plan fingerprint
# (plan/stats.chain_fingerprint) names WHAT computes; these name WHAT
# it computed OVER — a (plan_fp, content_digest) pair is hit-safe
# across process restarts because both halves are content-derived.
# ---------------------------------------------------------------------------

def part_signature(path: str) -> str:
    """Signature of one on-disk part file: sha256 over (basename, size,
    mtime_ns). A stat proxy, deliberately NOT a content hash — a
    growing-directory scan must be able to fingerprint a multi-GB table
    in O(#files) stat calls, and any rewrite bumps mtime_ns. The
    tradeoff is stated: a byte-level rewrite that preserves size and
    nanosecond mtime would serve stale (requires a deliberate
    ``touch -d``-style forgery; ordinary writes always move mtime_ns)."""
    st = os.stat(path)
    h = hashlib.sha256()
    h.update(os.path.basename(path).encode())
    h.update(b"|%d|%d" % (int(st.st_size), int(st.st_mtime_ns)))
    return h.hexdigest()[:24]


def content_digest(signatures: Iterable[str]) -> str:
    """Fold per-part signatures into one input-partition digest. Order-
    sensitive on purpose: the manifest order IS the row order, and a
    reordered directory is different input even when the part set
    matches."""
    h = hashlib.sha256(b"parts|")
    for sig in signatures:
        h.update(str(sig).encode())
        h.update(b"|")
    return h.hexdigest()[:32]


def frame_content_digest(frame) -> str:
    """Content digest of an in-memory frame (the static-source case of
    a registered query): schema + every block's bytes. Dense columns
    hash their buffer; host/object columns hash their repr — exact
    enough for cache keying (a repr collision between two DIFFERENT
    host columns would need colliding reprs, and host columns are
    strings/small objects here)."""
    h = hashlib.sha256(b"frame|")
    h.update(json.dumps(
        [(c.name, c.dtype.name) for c in frame.schema]
    ).encode())
    for block in frame.blocks():
        for name in sorted(block):
            v = block[name]
            h.update(name.encode() + b"|")
            if isinstance(v, list):
                h.update(repr(v).encode())
                continue
            arr = np.asarray(v)
            if arr.dtype == object:
                h.update(repr(arr.tolist()).encode())
            else:
                h.update(str((arr.shape, str(arr.dtype))).encode())
                h.update(np.ascontiguousarray(arr).tobytes())
    return h.hexdigest()[:32]


def _scrub(text: str) -> str:
    """Drop process-local memory addresses from jaxpr text (function
    reprs inside callback/custom-primitive params embed them)."""
    return _ADDR_RE.sub("0x", text)


def _const_digest(h, const, include_values: bool,
                  value_policy: str) -> None:
    """Feed one traced constant into the running hash. ``value_policy``
    'host_only' skips device-array values (the lint surface must not
    trigger device→host transfers); 'all' hashes every value (the
    compile path — a transfer is noise next to the XLA compile)."""
    try:
        import jax

        is_device = isinstance(const, jax.Array)
    except Exception:  # pragma: no cover - jax always importable here
        is_device = False
    try:
        if include_values and (value_policy == "all" or not is_device):
            arr = np.asarray(const)
            h.update(str((arr.shape, str(arr.dtype))).encode())
            h.update(arr.tobytes())
        else:
            shape = getattr(const, "shape", None)
            dtype = getattr(const, "dtype", None)
            h.update(str((tuple(shape) if shape is not None else None,
                          str(dtype))).encode())
    except (TypeError, ValueError):
        # non-array capture: repr is the best available identity; if it
        # embeds process-local state, TFG108 is the rule that says so
        h.update(_scrub(repr(const)).encode())


def _env_parts(kind: str, donate: bool, hoisted: bool) -> Dict[str, object]:
    import jax

    from ..config import get_config
    from ..parallel.distributed import process_topology

    from .. import kernels as _kernels
    from ..plan import lift as _lift

    cfg = get_config()
    dev = jax.devices()[0]
    return {
        "format": FORMAT_VERSION,
        # kernel-selection state: pallas on/off (config switch AND the
        # manual disable_pallas() switch), the force hook, and interpreter
        # mode — any flip invalidates every key, because the lowering
        # the cost model picks is baked into the traced program
        "kernels": _kernels.fingerprint_token(),
        # verified-lift state: enabled flag + synthesis-rule version —
        # a lifted stage and its callback original trace to different
        # programs, so a TFTPU_LIFT flip must miss cleanly
        "lift": _lift.fingerprint_token(),
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "device_kind": getattr(dev, "device_kind", "unknown"),
        "n_devices": jax.device_count(),
        # the full device→process topology, not just counts: one rank's
        # published executable must be every peer's hit, and a resized
        # or reshaped fleet must miss cleanly
        "topology": process_topology(),
        "x64": bool(jax.config.jax_enable_x64),
        "demote_x64": str(cfg.demote_x64_on_tpu),
        "xla_flags": os.environ.get("XLA_FLAGS", ""),
        "kind": kind,
        "donate": bool(donate),
        "form": "hoisted" if hoisted else "plain",
    }


def _sharding_parts(avals, shardings) -> Dict[str, object]:
    """Per-input sharding descriptors keyed by input name. ``shardings``
    maps input name → sharding (or is None); descriptors normalize the
    trivial placement to None so unsharded keys are layout-free."""
    from ..parallel.mesh import sharding_descriptor

    out: Dict[str, object] = {}
    if not shardings:
        return out
    for (name, _, _) in avals:
        desc = sharding_descriptor(shardings.get(name))
        if desc is not None:
            out[str(name)] = desc
    return out


def _key_slots(
    closed,
    avals: Sequence[Tuple[str, Tuple[int, ...], str]],
    out_names: Sequence[str],
    *,
    kind: str,
    donate: bool,
    hoisted: bool,
    value_policy: str,
    shardings: Optional[Dict[str, object]],
    extra: Optional[Dict[str, object]],
) -> Dict[str, bytes]:
    """Every slot of the cache key, serialized ONCE. The composed hash
    (:func:`fingerprint_from_closed`) and the per-component digests
    (:func:`fingerprint_components`) both derive from this dict, so a
    slot added to one pipeline can never silently miss the other —
    TFG108 would otherwise report a program stable while the real store
    key moved."""
    ch = hashlib.sha256(b"consts:%d|" % len(closed.consts))
    for c in closed.consts:
        _const_digest(ch, c, include_values=not hoisted,
                      value_policy=value_policy)
    return {
        "jaxpr": _scrub(str(closed.jaxpr)).encode(),
        "consts": ch.digest(),
        "avals": json.dumps(
            [(n, list(s), d) for (n, s, d) in avals], sort_keys=True
        ).encode(),
        "outs": json.dumps(list(out_names)).encode(),
        "shardings": json.dumps(
            _sharding_parts(avals, shardings), sort_keys=True
        ).encode(),
        "env": json.dumps(
            _env_parts(kind, donate, hoisted), sort_keys=True
        ).encode(),
        "extra": json.dumps(extra or {}, sort_keys=True).encode(),
    }


def fingerprint_components(
    closed,
    avals: Iterable[Tuple[str, Tuple[int, ...], str]],
    out_names: Sequence[str],
    *,
    kind: str = "block",
    donate: bool = False,
    hoisted: bool = False,
    value_policy: str = "all",
    shardings: Optional[Dict[str, object]] = None,
    extra: Optional[Dict[str, object]] = None,
) -> Dict[str, object]:
    """The fingerprint's per-component digests: ``jaxpr``, ``consts``,
    ``avals``, ``outs``, ``env``, ``extra`` (each a short hex digest)
    plus ``shardings`` (a dict input-name → per-input descriptor
    digest). Two traces of a stable program agree on every component;
    TFG108 diffs the dicts to name exactly what moved."""
    avals = list(avals)
    slots = _key_slots(
        closed, avals, out_names, kind=kind, donate=donate,
        hoisted=hoisted, value_policy=value_policy,
        shardings=shardings, extra=extra,
    )
    out: Dict[str, object] = {
        name: hashlib.sha256(payload).hexdigest()[:16]
        for name, payload in slots.items()
        if name != "shardings"
    }
    # shardings stay per-input so TFG108 can name WHICH input's layout
    # moved (same _sharding_parts the composed slot serializes)
    out["shardings"] = {
        name: hashlib.sha256(
            json.dumps(desc, sort_keys=True).encode()
        ).hexdigest()[:16]
        for name, desc in _sharding_parts(avals, shardings).items()
    }
    return out


def fingerprint_from_closed(
    closed,
    avals: Iterable[Tuple[str, Tuple[int, ...], str]],
    out_names: Sequence[str],
    *,
    kind: str = "block",
    donate: bool = False,
    hoisted: bool = False,
    value_policy: str = "all",
    shardings: Optional[Dict[str, object]] = None,
    extra: Optional[Dict[str, object]] = None,
) -> str:
    """Fingerprint an already-traced program.

    ``closed`` is the ``ClosedJaxpr`` of the (possibly vmapped) entry
    function; ``avals`` the sorted (name, shape, dtype-str) triples of
    the feed the executable is specialized to; ``out_names`` the fetch
    order; ``shardings`` an optional input-name → sharding map (only
    non-trivial placements enter the key). Hoisted form excludes const
    *values* from the key (they are runtime arguments of the cached
    executable). ``extra`` is a JSON-able dict folded into the key for
    entry-specific identity the other slots don't carry (``aot_jit``
    puts its declared in/out sharding trees, label, and weak-type
    flags here).
    """
    slots = _key_slots(
        closed, list(avals), out_names, kind=kind, donate=donate,
        hoisted=hoisted, value_policy=value_policy,
        shardings=shardings, extra=extra,
    )
    h = hashlib.sha256()
    for name in sorted(slots):
        h.update(name.encode() + b":")
        h.update(slots[name])
        h.update(b"|")
    return h.hexdigest()[:40]


def program_fingerprint(
    program,
    probe: int = 8,
    *,
    kind: str = "block",
    donate: bool = False,
    hoisted: bool = False,
    value_policy: str = "host_only",
    mesh=None,
    shardings: Optional[Dict[str, object]] = None,
    components: bool = False,
):
    """Trace ``program`` fresh and fingerprint it (plain form by
    default — const values in the key, exactly what the executor uses
    when constant hoisting is off). Each call re-traces, so two calls
    on one program probe rebuild stability (TFG108). ``mesh`` installs
    the ambient mesh context for the trace (a sharded program must be
    probed exactly as the executor traces it — still zero device
    transfers: tracing is abstract and ``value_policy='host_only'``
    keeps device-resident captures out of the value hash).
    ``components=True`` returns the per-component digest dict
    (:func:`fingerprint_components`) instead of the composed hash.
    Returns None when the program cannot be traced."""
    import jax

    from ..parallel._shard_map import mesh_context
    from ..program import _abstract_inputs

    abstract = _abstract_inputs(program.inputs, probe)

    def rebuilt(feeds):
        # a fresh function object per call defeats jax's trace cache
        # (keyed on fn identity + avals): each fingerprint really does
        # re-run the user's capture logic, which is the whole point of
        # the TFG108 stability probe
        return program.fn(feeds)

    try:
        with mesh_context(mesh):
            closed = jax.make_jaxpr(rebuilt)(abstract)
    except Exception:
        return None
    avals = sorted(
        (name, tuple(int(d) for d in np.shape(a)), str(a.dtype))
        for name, a in abstract.items()
    )
    outs = list(program.fetch_order or [o.name for o in program.outputs])
    fn = fingerprint_components if components else fingerprint_from_closed
    return fn(
        closed, avals, outs, kind=kind, donate=donate, hoisted=hoisted,
        value_policy=value_policy, shardings=shardings,
    )

"""Persistent AOT executable cache + warmup (ISSUE 5).

Covers the acceptance contracts: cross-process round trip (a subprocess
warms the store, the parent hits it), two-writer races on one store
dir, corrupt/truncated entries falling back to a fresh compile with the
fallback counter bumped, byte-bound eviction, fused plan Programs
hitting the same store, bit-identical outputs cache-on vs cache-off,
and the executor's split compile/first-run accounting.
"""

import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

import tensorframes_tpu as tfs
from tensorframes_tpu.compilecache import (
    active_store,
    partitioner_row_counts,
    program_fingerprint,
    store_for,
    warmup,
)
from tensorframes_tpu.observability.metrics import REGISTRY


def _metric(name, labels=()):
    for d in REGISTRY.snapshot():
        if d["name"] == name and tuple(sorted(d["labels"].items())) == tuple(
            sorted(labels)
        ):
            return d
    return {"value": 0.0, "count": 0}


def _counter_val(name, labels=()):
    return _metric(name, labels)["value"]


def _hist_count(name):
    return _metric(name)["count"]


@pytest.fixture
def store_dir(tmp_path):
    """Point the runtime at a fresh store for one test; always restore
    the disabled default afterwards."""
    d = str(tmp_path / "cc")
    tfs.configure(compilation_cache_dir=d)
    try:
        yield d
    finally:
        tfs.configure(compilation_cache_dir="")


def _entries(store_dir):
    aot = os.path.join(store_dir, "aot")
    if not os.path.isdir(aot):
        return []
    return sorted(f for f in os.listdir(aot) if f.endswith(".xc"))


# ---------------------------------------------------------------------------
# defaults + fallback guarantees
# ---------------------------------------------------------------------------

def test_disabled_by_default_no_store_no_metrics(tmp_path):
    from tensorframes_tpu.config import get_config

    # active_store honors the live config; with the field empty it is None
    prev = get_config().compilation_cache_dir
    tfs.configure(compilation_cache_dir="")
    try:
        assert active_store() is None
        h0 = _counter_val("tftpu_compilecache_hits_total")
        m0 = _counter_val("tftpu_compilecache_misses_total")
        f = tfs.frame_from_arrays({"x": np.arange(8.0)})
        tfs.map_blocks(lambda x: {"y": x * 3.0}, f).blocks()
        assert _counter_val("tftpu_compilecache_hits_total") == h0
        assert _counter_val("tftpu_compilecache_misses_total") == m0
    finally:
        tfs.configure(compilation_cache_dir=prev)


def test_store_error_never_fails_dispatch(tmp_path):
    """An unusable cache dir (a FILE where the store dir should be)
    degrades to normal compiles — the dispatch still succeeds."""
    bad = tmp_path / "not-a-dir"
    bad.write_text("occupied")
    tfs.configure(compilation_cache_dir=str(bad))
    try:
        f = tfs.frame_from_arrays({"x": np.arange(8.0)})
        out = tfs.map_blocks(lambda x: {"y": x + 0.5}, f).blocks()
        np.testing.assert_array_equal(
            np.concatenate([b["y"] for b in out]), np.arange(8.0) + 0.5
        )
    finally:
        tfs.configure(compilation_cache_dir="")


# ---------------------------------------------------------------------------
# round trips
# ---------------------------------------------------------------------------

def test_in_process_roundtrip_bit_identical(store_dir):
    """Second (fresh) Program of the same fn+shape deserializes from
    disk: zero compiles, outputs bitwise equal to the cache-off run."""
    rng = np.random.default_rng(7)
    x = rng.standard_normal(64)
    frame = tfs.frame_from_arrays({"x": x}, num_blocks=2)

    def fn(x):
        return {"y": np.float64(2.0) * x * x - x / np.float64(3.0)}

    # reference run with the cache OFF
    tfs.configure(compilation_cache_dir="")
    ref = tfs.map_blocks(tfs.compile_program(fn, frame), frame).blocks()
    tfs.configure(compilation_cache_dir=store_dir)

    p1 = tfs.compile_program(fn, frame)
    warm_out = tfs.map_blocks(p1, frame).blocks()
    assert _entries(store_dir), "first run must publish store entries"

    h0 = _counter_val("tftpu_compilecache_hits_total")
    c0 = _hist_count("tftpu_executor_compile_seconds")
    p2 = tfs.compile_program(fn, frame)
    hit_out = tfs.map_blocks(p2, frame).blocks()
    assert _counter_val("tftpu_compilecache_hits_total") > h0
    assert _hist_count("tftpu_executor_compile_seconds") == c0
    assert _hist_count("tftpu_compilecache_load_seconds") >= 1
    for a, b, c in zip(ref, warm_out, hit_out):
        assert np.array_equal(a["y"], b["y"])
        assert np.array_equal(a["y"], c["y"])  # bit-identical, cache on/off


def test_cross_process_roundtrip(store_dir, tmp_path):
    """A subprocess warms the store; the parent's identical program
    hits it — the fingerprint survives process restarts."""
    script = tmp_path / "warm_child.py"
    script.write_text(
        "import os, sys\n"
        "sys.path.insert(0, %r)\n"
        "import numpy as np\n"
        "import tensorframes_tpu as tfs\n"
        "frame = tfs.frame_from_arrays({'v': np.arange(24.0)}, num_blocks=3)\n"
        "p = tfs.compile_program(lambda v: {'w': v * 7.0 + 1.0}, frame)\n"
        "tfs.map_blocks(p, frame).blocks()\n"
        "from tensorframes_tpu.compilecache import active_store\n"
        "print('entries=', len(active_store().stats()['entry_list']))\n"
        % os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    env = {**os.environ, "TFTPU_COMPILE_CACHE": store_dir,
           "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, str(script)], env=env,
                       capture_output=True, text=True, timeout=240)
    assert r.returncode == 0, r.stderr[-2000:]
    assert _entries(store_dir), "child must have published entries"

    h0 = _counter_val("tftpu_compilecache_hits_total")
    c0 = _hist_count("tftpu_executor_compile_seconds")
    frame = tfs.frame_from_arrays({"v": np.arange(24.0)}, num_blocks=3)
    p = tfs.compile_program(lambda v: {"w": v * 7.0 + 1.0}, frame)
    out = tfs.map_blocks(p, frame).blocks()
    np.testing.assert_array_equal(out[0]["w"], np.arange(8.0) * 7.0 + 1.0)
    assert _counter_val("tftpu_compilecache_hits_total") > h0, \
        "parent must hit the child's entries"
    assert _hist_count("tftpu_executor_compile_seconds") == c0, \
        "a disk hit must not compile"


def test_fused_plan_programs_hit_store(store_dir):
    """A fused lazy chain's composed Program goes through the same
    store: an identical fresh chain deserializes instead of compiling."""
    x = np.arange(48.0)

    def build_and_force():
        frame = tfs.frame_from_arrays({"x": x}, num_blocks=2)
        f1 = tfs.map_blocks(lambda x: {"y": x * 2.0 + 1.0}, frame)
        f2 = tfs.map_blocks(lambda y: {"z": y * 0.5 - 3.0}, f1)
        return f2.select(["z"]).blocks()

    first = build_and_force()
    assert _entries(store_dir)
    h0 = _counter_val("tftpu_compilecache_hits_total")
    second = build_and_force()
    assert _counter_val("tftpu_compilecache_hits_total") > h0
    for a, b in zip(first, second):
        assert np.array_equal(a["z"], b["z"])


# ---------------------------------------------------------------------------
# durability: corruption, races, eviction
# ---------------------------------------------------------------------------

def test_corrupt_entry_falls_back_to_compile(store_dir):
    frame = tfs.frame_from_arrays({"x": np.arange(16.0)}, num_blocks=2)

    def fn(x):
        return {"y": x - 11.0}

    tfs.map_blocks(tfs.compile_program(fn, frame), frame).blocks()
    entries = _entries(store_dir)
    assert entries
    # truncate one entry and bit-flip another byte range via rewrite
    path = os.path.join(store_dir, "aot", entries[0])
    blob = open(path, "rb").read()
    with open(path, "wb") as f:
        f.write(blob[: max(8, len(blob) // 2)])

    fb0 = _counter_val("tftpu_compilecache_fallback_total",
                       (("reason", "corrupt"),))
    out = tfs.map_blocks(tfs.compile_program(fn, frame), frame).blocks()
    np.testing.assert_array_equal(out[0]["y"], np.arange(8.0) - 11.0)
    assert _counter_val("tftpu_compilecache_fallback_total",
                        (("reason", "corrupt"),)) > fb0
    # the defective entry was quarantined and re-published by the
    # fallback compile: the store heals itself
    assert _entries(store_dir)


def test_two_writer_race_same_store(store_dir):
    """Concurrent writers publishing the same and different entries
    leave a consistent store (atomic replace; no torn entries)."""
    store = store_for(os.path.join(store_dir, "aot"))
    frame = tfs.frame_from_arrays({"x": np.arange(32.0)}, num_blocks=2)
    programs = [
        tfs.compile_program((lambda k: lambda x: {"y": x + float(k)})(k),
                            frame)
        for k in range(4)
    ]

    errs = []

    def worker(p):
        try:
            for _ in range(3):
                tfs.map_blocks(p, frame).blocks()
        except Exception as e:  # pragma: no cover - the assertion target
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(p,))
               for p in programs for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    report = store.verify()
    assert report["ok"], report


def test_eviction_respects_byte_bound(store_dir):
    store = store_for(os.path.join(store_dir, "aot"))
    frame = tfs.frame_from_arrays({"x": np.arange(16.0)}, num_blocks=1)
    for k in range(4):
        p = tfs.compile_program(
            (lambda kk: lambda x: {"y": x * float(kk + 2)})(k), frame
        )
        tfs.map_blocks(p, frame).blocks()
    entries = [(os.path.join(store_dir, "aot", e),
                os.path.getsize(os.path.join(store_dir, "aot", e)))
               for e in _entries(store_dir)]
    assert len(entries) == 4
    # bound that fits roughly two entries
    bound = sum(s for _, s in entries[:2]) + 1
    ev0 = _counter_val("tftpu_compilecache_evictions_total")
    store.max_bytes = bound
    store._evict()
    left = _entries(store_dir)
    total = sum(
        os.path.getsize(os.path.join(store_dir, "aot", e)) for e in left
    )
    assert total <= bound
    assert len(left) < 4
    assert _counter_val("tftpu_compilecache_evictions_total") > ev0


# ---------------------------------------------------------------------------
# warmup
# ---------------------------------------------------------------------------

def test_warmup_precompiles_partitioner_buckets(store_dir):
    frame = tfs.frame_from_arrays({"x": np.arange(21.0)}, num_blocks=4)
    program = tfs.compile_program(lambda x: {"y": x * x}, frame)
    report = warmup(frame, program)
    # 21 rows over 4 blocks → blocks of 5 and 6 rows: both warmed
    assert {e["rows"] for e in report.entries} == {5, 6}
    assert report.compiled == 2

    c0 = _hist_count("tftpu_executor_compile_seconds")
    m0 = _counter_val("tftpu_executor_jit_cache_misses_total")
    h0 = _counter_val("tftpu_executor_jit_cache_hits_total")
    out = tfs.map_blocks(program, frame).blocks()
    assert _hist_count("tftpu_executor_compile_seconds") == c0, \
        "warmed dispatch must not compile"
    assert _counter_val("tftpu_executor_jit_cache_misses_total") == m0
    assert _counter_val("tftpu_executor_jit_cache_hits_total") > h0
    np.testing.assert_array_equal(
        np.concatenate([b["y"] for b in out]), np.arange(21.0) ** 2
    )


def test_warmup_rows_mode_buckets(store_dir):
    from tensorframes_tpu.ops.executor import bucket_rows

    frame = tfs.frame_from_arrays({"x": np.arange(10.0)}, num_blocks=1)
    program = tfs.compile_program(
        lambda x: {"s": x * 2.0}, frame, block=False
    )
    report = warmup(frame, program, block=False)
    # both regimes warmed: the exact size (adaptive pre-bucket phase)
    # and its power-of-two bucket (shape-proliferation phase)
    assert {e["rows"] for e in report.entries} == {10, bucket_rows(10)}
    c0 = _hist_count("tftpu_executor_compile_seconds")
    tfs.map_rows(program, frame).blocks()
    assert _hist_count("tftpu_executor_compile_seconds") == c0


def test_warmup_from_manifest(store_dir):
    """The executor records miss shapes; warmup replays them for a
    fresh program so a new process precompiles yesterday's traffic."""
    frame = tfs.frame_from_arrays({"x": np.arange(12.0)}, num_blocks=2)

    def fn(x):
        return {"y": x + 100.0}

    tfs.map_blocks(tfs.compile_program(fn, frame), frame).blocks()
    manifest = os.path.join(store_dir, "aot", "manifest.jsonl")
    rows = [json.loads(ln) for ln in open(manifest)]
    assert rows and rows[0]["inputs"][0][0] == "x"

    fresh = tfs.compile_program(fn, frame)
    report = warmup(None, fresh, manifest=manifest)
    assert report.entries, "manifest rows must map onto the program"
    c0 = _hist_count("tftpu_executor_compile_seconds")
    tfs.map_blocks(fresh, frame).blocks()
    assert _hist_count("tftpu_executor_compile_seconds") == c0


def test_warmup_manifest_requires_matching_dtype_and_cells(store_dir):
    """The manifest is store-wide: rows recorded for one program must
    not warm an unrelated program that happens to share input names."""
    f64 = tfs.frame_from_arrays({"x": np.arange(12.0)}, num_blocks=2)
    tfs.map_blocks(
        tfs.compile_program(lambda x: {"y": x + 1.0}, f64), f64
    ).blocks()
    manifest = os.path.join(store_dir, "aot", "manifest.jsonl")
    assert os.path.exists(manifest)

    # same input name 'x', different dtype: the recorded f64 shapes
    # must not be replayed into an i64 program
    i64 = tfs.frame_from_arrays({"x": np.arange(12)}, num_blocks=2)
    other = tfs.compile_program(lambda x: {"y": x * 2}, i64)
    report = warmup(None, other, manifest=manifest)
    assert not report.entries


def test_warmup_manifest_skips_sharded_rows(store_dir):
    """record_miss(sharded=True) rows under-specify the executable's
    layout (shapes alone carry no mesh): replaying them would burn an
    XLA compile on an UNSHARDED key the real sharded dispatch never
    hits — the replay must skip them, reported, zero compiles."""
    frame = tfs.frame_from_arrays({"x": np.arange(12.0)}, num_blocks=2)

    def fn(x):
        return {"y": x + 100.0}

    tfs.map_blocks(tfs.compile_program(fn, frame), frame).blocks()
    manifest = os.path.join(store_dir, "aot", "manifest.jsonl")
    rows = [json.loads(ln) for ln in open(manifest)]
    with open(manifest, "w") as f:
        for row in rows:
            row["sharded"] = True
            f.write(json.dumps(row) + "\n")

    fresh = tfs.compile_program(fn, frame)
    c0 = _hist_count("tftpu_executor_compile_seconds")
    report = warmup(None, fresh, manifest=manifest)
    assert _hist_count("tftpu_executor_compile_seconds") == c0
    assert report.entries and all(
        e["status"] == "skipped" and "sharded" in e["detail"]
        for e in report.entries
    )


def test_warmup_manifest_true_without_store_raises():
    tfs.configure(compilation_cache_dir="")
    frame = tfs.frame_from_arrays({"x": np.arange(4.0)})
    program = tfs.compile_program(lambda x: {"y": x}, frame)
    with pytest.raises(ValueError, match="persistent store"):
        warmup(None, program, manifest=True)
    with pytest.raises(ValueError, match="does not exist"):
        warmup(None, program, manifest="/nonexistent/manifest.jsonl")


def test_warmup_without_frame_needs_rows():
    frame = tfs.frame_from_arrays({"x": np.arange(4.0)})
    program = tfs.compile_program(lambda x: {"y": x}, frame)
    with pytest.raises(ValueError, match="rows"):
        warmup(None, program)


def test_partitioner_row_counts():
    assert partitioner_row_counts(21, 4) == [5, 6]
    assert partitioner_row_counts(20, 4) == [5]
    assert partitioner_row_counts(3, 8) == [1]


# ---------------------------------------------------------------------------
# fingerprints
# ---------------------------------------------------------------------------

def test_fingerprint_stable_across_rebuilds_and_distinct_by_shape():
    frame = tfs.frame_from_arrays({"x": np.arange(8.0)})
    p = tfs.compile_program(lambda x: {"y": x * 5.0}, frame)
    a = program_fingerprint(p, probe=8)
    b = program_fingerprint(p, probe=8)
    assert a == b
    assert program_fingerprint(p, probe=16) != a  # shape in the key
    p2 = tfs.compile_program(lambda x: {"y": x * 6.0}, frame)
    assert program_fingerprint(p2, probe=8) != a  # content in the key


def test_fingerprint_donate_and_kind_in_key():
    frame = tfs.frame_from_arrays({"x": np.arange(8.0)})
    p = tfs.compile_program(lambda x: {"y": x * 5.0}, frame)
    base = program_fingerprint(p, probe=8)
    assert program_fingerprint(p, probe=8, donate=True) != base
    assert program_fingerprint(p, probe=8, kind="vmap") != base


def test_fingerprint_kernel_selection_in_key():
    """ISSUE 12 key-axis regression: the straggler-kernel selection
    state lives in the env component, so a ``disable_pallas()`` flip,
    ``TFTPU_PALLAS=0``, or the force hook can never serve a stale
    executable from the store — and restoring the state restores the
    key (warmed entries stay warm across a no-op round trip)."""
    from tensorframes_tpu import configure
    from tensorframes_tpu.ops import segment

    frame = tfs.frame_from_arrays({"x": np.arange(8.0)})
    p = tfs.compile_program(lambda x: {"y": x * 3.0}, frame)
    base = program_fingerprint(p, probe=8)

    was = segment._pallas_disabled
    try:
        segment.disable_pallas("fingerprint key test")
        tripped = program_fingerprint(p, probe=8)
    finally:
        segment._pallas_disabled = was
    assert tripped != base  # the kill-switch is a key axis

    configure(pallas_kernels=False)
    try:
        off = program_fingerprint(p, probe=8)
    finally:
        configure(pallas_kernels=True)
    assert off != base
    # both spell 'kernels disabled' — one executable family serves them
    assert off == tripped

    configure(pallas_force=True)
    try:
        forced = program_fingerprint(p, probe=8)
    finally:
        configure(pallas_force=False)
    assert forced not in (base, off)

    # round trip: restored state keys identically (no gratuitous miss)
    assert program_fingerprint(p, probe=8) == base


# ---------------------------------------------------------------------------
# topology-fingerprinted keys (ISSUE 10 tentpole)
# ---------------------------------------------------------------------------

def _mesh_or_skip(axes=None):
    from tensorframes_tpu.parallel import device_count, make_mesh

    if device_count() < 8:
        pytest.skip("needs 8 (virtual) devices")
    return make_mesh(axes)


def test_fingerprint_sharding_axes_in_key():
    """Per-input shardings key separate executables: an AOT executable
    is layout-specialized, so mesh axis names, mesh shape, and the
    per-dim partition spec must all invalidate — while the TRIVIAL
    placement (host feeds, default device) keys exactly like no
    sharding at all (warmed host shapes must match however data
    arrives)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = _mesh_or_skip()
    frame = tfs.frame_from_arrays({"x": np.arange(64.0)})
    p = tfs.compile_program(lambda x: {"y": x * 5.0}, frame)
    base = program_fingerprint(p, probe=64)
    sharded = program_fingerprint(
        p, probe=64, shardings={"x": NamedSharding(mesh, P("dp"))}
    )
    assert sharded != base  # layout in the key
    # replicated-over-the-mesh is a different layout than dp-sharded
    repl = program_fingerprint(
        p, probe=64, shardings={"x": NamedSharding(mesh, P())}
    )
    assert repl not in (base, sharded)
    # axis NAMES are identity: same shape, renamed axis → different key
    mesh2 = _mesh_or_skip({"data": 8})
    renamed = program_fingerprint(
        p, probe=64, shardings={"x": NamedSharding(mesh2, P("data"))}
    )
    assert renamed not in (base, sharded, repl)
    # mesh SHAPE is identity: dp=2 x tp=4 keys differently from dp=8
    mesh3 = _mesh_or_skip({"dp": 2, "tp": 4})
    reshaped = program_fingerprint(
        p, probe=64, shardings={"x": NamedSharding(mesh3, P("dp"))}
    )
    assert reshaped not in (base, sharded, repl, renamed)
    # an explicit None / trivial sharding is the SAME key as no map
    assert program_fingerprint(p, probe=64, shardings={}) == base
    assert program_fingerprint(p, probe=64, shardings={"x": None}) == base


def test_fingerprint_process_topology_in_key(monkeypatch):
    """The fleet topology (device→process map) is in the env component:
    a resized fleet must miss cleanly instead of loading an executable
    compiled for the wrong collective schedule — while the key is
    process-INDEX-independent (every rank computes the same key, so one
    rank's published executable is every peer's hit)."""
    from tensorframes_tpu.compilecache import fingerprint as fp_mod
    from tensorframes_tpu.parallel import process_topology

    frame = tfs.frame_from_arrays({"x": np.arange(8.0)})
    p = tfs.compile_program(lambda x: {"y": x + 2.0}, frame)
    base = program_fingerprint(p, probe=8)
    real = process_topology()
    assert real["n_processes"] == 1  # single-process test env

    resized = dict(real, n_processes=4)
    monkeypatch.setattr(
        fp_mod, "_env_parts",
        _patched_env_parts(fp_mod._env_parts, resized),
    )
    assert program_fingerprint(p, probe=8) != base  # resize → clean miss


def _patched_env_parts(orig, topology):
    def env_parts(kind, donate, hoisted):
        parts = orig(kind, donate, hoisted)
        parts["topology"] = topology
        return parts

    return env_parts


def test_sharded_dispatch_roundtrip_bit_identical(store_dir):
    """A sharded frame's dispatch publishes its executable; a FRESH
    program instance over the same computation loads it from disk (hit
    counter, zero compile delta) and the cached result is bit-identical
    to cache-off dispatch."""
    _mesh_or_skip()

    def build():
        df = tfs.frame_from_arrays(
            {"x": np.arange(128.0, dtype=np.float32)}
        ).to_device()
        assert df.is_sharded
        return df, tfs.compile_program(
            lambda x: {"y": x * 1.5 + x.sum()}, df
        )

    # reference: cache OFF
    tfs.configure(compilation_cache_dir="")
    df, p = build()
    want = np.asarray(tfs.map_blocks(p, df).column_values("y"))

    tfs.configure(compilation_cache_dir=store_dir)
    df, p = build()
    c0 = _hist_count("tftpu_executor_compile_seconds")
    got_cold = np.asarray(tfs.map_blocks(p, df).column_values("y"))
    assert _hist_count("tftpu_executor_compile_seconds") > c0  # published
    assert _entries(store_dir)  # the sharded executable is durable

    df, p = build()  # fresh Program: its in-memory jit cache is empty
    h0 = _counter_val("tftpu_compilecache_hits_total")
    c1 = _hist_count("tftpu_executor_compile_seconds")
    got_warm = np.asarray(tfs.map_blocks(p, df).column_values("y"))
    assert _counter_val("tftpu_compilecache_hits_total") > h0
    assert _hist_count("tftpu_executor_compile_seconds") == c1  # ZERO
    np.testing.assert_array_equal(got_warm, got_cold)
    np.testing.assert_array_equal(got_warm, want)


@pytest.mark.parametrize("n_devices, keyed", [(4, True), (1, False)])
def test_fingerprint_constants_placement_in_key(n_devices, keyed,
                                                monkeypatch):
    """Constants replicated over a mesh key apart from the same program
    lowered from uncommitted constants; on the default device alone
    there is one layout and the key is what it always was."""
    import jax

    from tensorframes_tpu.ops import executor
    from tensorframes_tpu.parallel.mesh import batch_sharding, make_mesh

    _mesh_or_skip()
    w = np.linspace(1.0, 2.0, 4, dtype=np.float32)
    df = tfs.frame_from_arrays({"x": np.zeros((8, 4), np.float32)})
    compiled = tfs.compile_program(lambda x: {"y": x * w}, df).compiled()
    sharding = batch_sharding(
        make_mesh(devices=jax.devices()[:n_devices]), 2)
    abstract = {"x": jax.ShapeDtypeStruct((8, 4), np.float32,
                                          sharding=sharding)}

    def fingerprint():
        entry = executor._hoisted_for(compiled.program.fn, abstract)
        return entry, compiled._fingerprint("block", abstract, False, entry)

    placed, fp_placed = fingerprint()
    assert placed.consts[0].committed
    monkeypatch.setattr(executor, "_consts_placement", lambda shardings: None)
    unplaced, fp_unplaced = fingerprint()
    assert not unplaced.consts[0].committed
    assert fp_placed and fp_unplaced
    assert (fp_placed != fp_unplaced) == keyed


def test_entry_from_unplaced_constants_is_not_served(store_dir, monkeypatch):
    """A store entry published while hoisted constants were left
    uncommitted (the compiler then chose their layout) is not served
    once they are replicated over the feeds' mesh up front: the
    constants' placement is in the fingerprint, so the old entry misses,
    the program compiles ONCE and publishes beside it, and a fresh
    instance loads that and runs with no weight moving between devices
    on any call."""
    import jax

    from tensorframes_tpu.ops import executor

    _mesh_or_skip()
    w = np.linspace(1.0, 2.0, 4, dtype=np.float32)

    def build():
        df = tfs.frame_from_arrays(
            {"x": np.arange(512.0, dtype=np.float32).reshape(128, 4)}
        ).to_device()
        return df, tfs.compile_program(lambda x: {"y": x * w}, df)

    def compiles():
        return _hist_count("tftpu_executor_compile_seconds")

    df, p = build()
    with monkeypatch.context() as m:    # as the store's writer once was
        m.setattr(executor, "_consts_placement", lambda shardings: None)
        want = np.asarray(tfs.map_blocks(p, df).column_values("y"))
    (entry,) = p.compiled()._hoisted.values()
    assert not entry.consts[0].committed
    old = _entries(store_dir)
    assert len(old) == 1

    df, p = build()
    c0 = compiles()
    m0 = _counter_val("tftpu_compilecache_misses_total")
    got = np.asarray(tfs.map_blocks(p, df).column_values("y"))
    assert _counter_val("tftpu_compilecache_misses_total") == m0 + 1
    assert compiles() == c0 + 1
    assert len(_entries(store_dir)) == 2 and set(old) < set(_entries(store_dir))
    np.testing.assert_array_equal(got, want)

    df, p = build()
    h0 = _counter_val("tftpu_compilecache_hits_total")
    n0 = _counter_val("tftpu_executor_const_placements_total")
    with jax.transfer_guard_device_to_device("disallow"):
        for _ in range(2):
            mapped = tfs.map_blocks(p, df)
            np.testing.assert_array_equal(
                np.asarray(mapped.column_values("y")), want)
    assert _counter_val("tftpu_compilecache_hits_total") == h0 + 1
    assert compiles() == c0 + 1
    assert _counter_val("tftpu_executor_const_placements_total") == n0 + 1
    (entry,) = p.compiled()._hoisted.values()
    assert entry.consts[0].committed
    assert entry.consts[0].sharding.is_fully_replicated


def test_warm_sharded_key_makes_first_dispatch_a_hit(store_dir):
    """warm() with sharding-annotated abstract feeds precompiles the
    SHARDED placement's key: the first real sharded dispatch is a
    jit-cache hit with zero compile (the multi-process refusal is gone
    — every dispatch rides the unified AOT path the warm targets)."""
    import jax

    _mesh_or_skip()
    df = tfs.frame_from_arrays(
        {"x": np.arange(128.0, dtype=np.float32)}
    ).to_device()
    p = tfs.compile_program(lambda x: {"y": x - 2.0}, df)
    col = df.blocks()[0]["x"]
    abstract = {
        "x": jax.ShapeDtypeStruct(col.shape, col.dtype,
                                  sharding=col.sharding),
    }
    status = p.compiled().warm("block", abstract)
    assert status in ("compiled", "disk")
    h0 = _counter_val("tftpu_executor_jit_cache_hits_total")
    c0 = _hist_count("tftpu_executor_compile_seconds")
    out = tfs.map_blocks(p, df).column_values("y")
    np.testing.assert_array_equal(
        np.asarray(out), np.arange(128.0, dtype=np.float32) - 2.0
    )
    assert _counter_val("tftpu_executor_jit_cache_hits_total") > h0
    assert _hist_count("tftpu_executor_compile_seconds") == c0


def test_aot_jit_sharded_store_roundtrip(store_dir):
    """aot_jit (the unified pipeline for arbitrary pytree functions —
    what the MULTICHIP train steps dispatch through) publishes sharded
    executables a fresh instance loads from disk, bit-identically."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tensorframes_tpu.ops.executor import aot_jit

    mesh = _mesh_or_skip()
    sh = NamedSharding(mesh, P("dp"))
    x = jax.device_put(np.arange(64.0, dtype=np.float32), sh)

    def f(a):
        return a * 2.0 + a.sum()

    c0 = _hist_count("tftpu_executor_compile_seconds")
    cold = np.asarray(aot_jit(f, label="t")(x))
    assert _hist_count("tftpu_executor_compile_seconds") == c0 + 1

    h0 = _counter_val("tftpu_compilecache_hits_total")
    warm = np.asarray(aot_jit(f, label="t")(x))  # fresh instance
    assert _counter_val("tftpu_compilecache_hits_total") > h0
    assert _hist_count("tftpu_executor_compile_seconds") == c0 + 1
    np.testing.assert_array_equal(cold, warm)


def test_aot_jit_weak_type_keys_apart_and_promotes_like_jit():
    """A weak-typed 0-d array leaf (jnp.asarray(python_scalar)) must
    trace with weak_type preserved — dropping it promotes int8 + weak
    int to the weak leaf's dtype, a result the wrapped jax.jit never
    produces — and must not share an executable with a strong-typed
    leaf of the same dtype."""
    import jax
    import jax.numpy as jnp

    from tensorframes_tpu.ops.executor import aot_jit

    xi = jnp.ones((3,), jnp.int8)
    weak = jnp.asarray(1)
    strong = jnp.array(1, weak.dtype)
    assert weak.weak_type and not strong.weak_type

    f = aot_jit(lambda a, b: a + b, label="weak")
    ref = jax.jit(lambda a, b: a + b)
    assert f(xi, weak).dtype == ref(xi, weak).dtype == jnp.int8
    assert f(xi, strong).dtype == ref(xi, strong).dtype == weak.dtype
    # both variants rode the AOT path under DISTINCT keys — neither
    # fell back nor reused the other's strongly-typed executable
    assert len(f._builds.built) == 2 and not f._builds.failed


def test_aot_jit_donation_keys_and_fingerprints_apart(store_dir):
    """``aot_jit(donate_argnums=...)`` donates like ``jax.jit`` (the
    argument is deleted, the result correct), and the donating
    executable is told from the plain one of the same function in the
    in-process key AND in the store's fingerprint — a fresh donating
    instance loads the donating entry from disk, still donating."""
    import jax.numpy as jnp

    from tensorframes_tpu.ops.executor import aot_jit

    def f(a, i):
        return a.at[i].set(7.0)

    def x():
        return jnp.zeros((64, 8), jnp.float32)

    i = jnp.asarray(3, jnp.int32)
    plain = aot_jit(f, label="don")
    donating = aot_jit(f, label="don", donate_argnums=(0,))
    assert "donate_argnums" not in plain._decl
    assert donating._decl["donate_argnums"] == [0]

    a = x()
    want = np.asarray(plain(a, i))
    assert not a.is_deleted()
    m0 = _counter_val("tftpu_compilecache_misses_total")
    b = x()
    got = np.asarray(donating(b, i))
    assert b.is_deleted()
    np.testing.assert_array_equal(got, want)
    # same function, same shapes, same label: the plain entry just
    # published must NOT have served the donating instance
    assert _counter_val("tftpu_compilecache_misses_total") == m0 + 1
    (kp,), (kd,) = plain._builds.built, donating._builds.built
    assert kp != kd and kp[2:] == kd[2:]

    h0 = _counter_val("tftpu_compilecache_hits_total")
    c = x()
    again = aot_jit(f, label="don", donate_argnums=(0,))  # fresh instance
    np.testing.assert_array_equal(np.asarray(again(c, i)), want)
    assert _counter_val("tftpu_compilecache_hits_total") == h0 + 1
    assert c.is_deleted()  # the loaded executable donates too
    stats = again.executable(x(), i).memory_analysis()
    assert stats.alias_size_in_bytes == 64 * 8 * 4


# ---------------------------------------------------------------------------
# accounting split (ISSUE 5 satellite)
# ---------------------------------------------------------------------------

def test_compile_vs_first_run_split():
    """With the cache off, a fresh shape observes compile-seconds AND
    first-run-seconds exactly once each; a repeat dispatch observes
    neither."""
    tfs.configure(compilation_cache_dir="")
    frame = tfs.frame_from_arrays({"x": np.arange(8.0)}, num_blocks=1)
    program = tfs.compile_program(lambda x: {"y": x / 4.0}, frame)
    c0 = _hist_count("tftpu_executor_compile_seconds")
    r0 = _hist_count("tftpu_executor_first_run_seconds")
    tfs.map_blocks(program, frame).blocks()
    assert _hist_count("tftpu_executor_compile_seconds") == c0 + 1
    assert _hist_count("tftpu_executor_first_run_seconds") == r0 + 1
    tfs.map_blocks(program, frame).blocks()
    assert _hist_count("tftpu_executor_compile_seconds") == c0 + 1
    assert _hist_count("tftpu_executor_first_run_seconds") == r0 + 1


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_cli_stats_verify_prune(store_dir, capsys):
    from tensorframes_tpu.compilecache.cli import main

    frame = tfs.frame_from_arrays({"x": np.arange(8.0)})
    tfs.map_blocks(
        tfs.compile_program(lambda x: {"y": x * 9.0}, frame), frame
    ).blocks()
    assert _entries(store_dir)

    assert main(["--store", store_dir, "stats", "--json"]) == 0
    stats = json.loads(capsys.readouterr().out)
    assert stats["entries"] >= 1 and stats["bytes"] > 0

    assert main(["--store", store_dir, "verify", "--json"]) == 0
    report = json.loads(capsys.readouterr().out)
    assert report["ok"] and report["good"] >= 1

    # corrupt → verify fails → verify --delete-bad heals
    path = os.path.join(store_dir, "aot", _entries(store_dir)[0])
    with open(path, "r+b") as f:
        f.seek(-1, os.SEEK_END)
        last = f.read(1)
        f.seek(-1, os.SEEK_END)
        f.write(bytes([last[0] ^ 0xFF]))
    assert main(["--store", store_dir, "verify", "--json"]) == 1
    capsys.readouterr()
    assert main(["--store", store_dir, "verify", "--json",
                 "--delete-bad"]) == 1
    capsys.readouterr()
    assert main(["--store", store_dir, "verify", "--json"]) == 0
    capsys.readouterr()

    assert main(["--store", store_dir, "prune", "--clear"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["entries"] == 0
    assert not _entries(store_dir)


def test_cli_warm_bundle(store_dir, tmp_path, capsys):
    from tensorframes_tpu.compilecache.cli import main
    from tensorframes_tpu.program import save_program

    frame = tfs.frame_from_arrays({"x": np.arange(8.0)})
    program = tfs.compile_program(lambda x: {"y": x + 2.5}, frame)
    bundle = str(tmp_path / "prog.pb")
    save_program(program, bundle)
    assert main(["--store", store_dir, "warm", bundle, "--rows", "8"]) == 0
    out = capsys.readouterr().out
    assert "compiled" in out or "disk" in out
    assert _entries(store_dir), "CLI warm must populate the store"

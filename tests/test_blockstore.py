"""Out-of-core data plane (ISSUE 15): spillable block store, streaming
partitioner, chunked multi-part ingest, file-shuffle transport, TFG111.

The multi-process shuffle correctness workers (2 real OS processes,
bit-identity to the single-process oracle, kill -9 mid-shuffle) live in
tests/test_distributed.py next to the other subprocess fleets.
"""

import json
import os
import threading

import numpy as np
import pytest

import tensorframes_tpu as tfs
from tensorframes_tpu import configure
from tensorframes_tpu.config import get_config
from tensorframes_tpu.blockstore import (
    BlockCorruptionError,
    BlockStore,
    SpilledFrame,
    shuffle as fshuffle,
    stream_chain,
)
from tensorframes_tpu.blockstore.store import (
    QUARANTINES,
    RELOAD_SECONDS,
    SPILL_SECONDS,
)
from tensorframes_tpu.observability.metrics import REGISTRY
from tensorframes_tpu.resilience import inject


@pytest.fixture
def store(tmp_path):
    st = BlockStore(root=str(tmp_path / "store"), budget_bytes=1 << 16)
    yield st
    st.close()


def _mk_block(i, rows=4096):
    return {
        "x": np.arange(rows, dtype=np.float64) + i,
        "y": (np.arange(rows) % 7).astype(np.int64),
        "s": [f"r{i}-{j}" for j in range(rows)],
    }


# ---------------------------------------------------------------------------
# store: budget, spill, reload, CRC
# ---------------------------------------------------------------------------

def test_put_get_roundtrip_resident(store):
    b = _mk_block(0, rows=16)
    ref = store.put(b)
    got = store.get(ref)
    np.testing.assert_array_equal(got["x"], b["x"])
    np.testing.assert_array_equal(got["y"], b["y"])
    assert got["s"] == b["s"]
    assert ref.num_rows == 16


def test_budget_enforced_lru_spill(store):
    refs = [store.put(_mk_block(i)) for i in range(8)]
    assert store.resident_bytes <= store.budget_bytes
    assert store.spilled_bytes > 0
    # reload of a spilled block is CRC-checked and bit-identical
    for i, ref in enumerate(refs):
        got = store.get(ref)
        np.testing.assert_array_equal(got["x"], _mk_block(i)["x"])
        assert got["s"][0] == f"r{i}-0"
    # the gauges track the live store
    snap = {m["name"]: m for m in REGISTRY.snapshot()}
    assert snap["tftpu_blockstore_resident_bytes"]["value"] >= 0
    assert SPILL_SECONDS.count > 0
    assert RELOAD_SECONDS.count > 0


def test_mmap_reload_zero_copy_view(store):
    ref = store.put(_mk_block(3))
    store.spill(ref)
    got = store.get(ref, mmap=True)
    assert isinstance(got["x"], np.ndarray)
    np.testing.assert_array_equal(np.asarray(got["x"]), _mk_block(3)["x"])


def test_pinned_blocks_never_lru_spilled(store):
    pinned = store.put(_mk_block(0), pin=True)
    for i in range(1, 8):
        store.put(_mk_block(i))
    e = store._entries[pinned.block_id]
    assert e.block is not None and not e.spilled


def test_crc_corruption_quarantined_counted_and_recomputed(store):
    b = _mk_block(5)
    ref = store.put(b)
    store.spill(ref)
    # flip bytes in the dense segment behind the store's back
    seg = store._seg_dir(ref.block_id)
    with open(os.path.join(seg, "manifest.json")) as f:
        manifest = json.load(f)
    dense = [c for c in manifest["columns"] if c["kind"] == "dense"][0]
    path = os.path.join(seg, dense["file"])
    with open(path, "r+b") as f:
        f.seek(13)
        f.write(b"\xde\xad\xbe\xef")
    before = QUARANTINES.value
    with pytest.raises(BlockCorruptionError):
        store.get(ref)
    assert QUARANTINES.value == before + 1
    # the bad segment was renamed aside, never served again
    assert not os.path.isdir(seg)
    assert any(
        e.startswith(os.path.basename(seg)) and ".quarantine." in e
        for e in os.listdir(store.root)
    )
    # recompute-from-lineage heals: segment republishes, reload is clean
    healed = store.get_or_recompute(ref, lambda: _mk_block(5))
    np.testing.assert_array_equal(healed["x"], b["x"])
    np.testing.assert_array_equal(store.get(ref)["x"], b["x"])


def test_spill_fault_site_fails_the_put(store):
    with inject("blockstore.spill", OSError("disk gone")) as inj:
        with pytest.raises(OSError):
            for i in range(8):  # enough puts to cross the budget
                store.put(_mk_block(i))
    assert inj.fired >= 1


def test_drop_frees_segment_and_accounting(store):
    ref = store.put(_mk_block(1))
    store.spill(ref)
    assert store.spilled_bytes > 0
    store.drop(ref)
    assert store.spilled_bytes == 0
    with pytest.raises(KeyError):
        store.get(ref)


def test_dataplane_metrics_preregistered_at_import():
    names = {m["name"] for m in REGISTRY.snapshot()}
    for want in (
        "tftpu_blockstore_resident_bytes",
        "tftpu_blockstore_spilled_bytes",
        "tftpu_blockstore_spill_seconds",
        "tftpu_blockstore_reload_seconds",
        "tftpu_blockstore_shuffle_bytes_total",
        "tftpu_blockstore_quarantines_total",
        "tftpu_blockstore_hostgather_bytes_total",
    ):
        assert want in names, want


# ---------------------------------------------------------------------------
# streaming partitioner
# ---------------------------------------------------------------------------

def _dataset(n=20_000, seed=0):
    rng = np.random.default_rng(seed)
    return (
        rng.integers(0, 13, size=n).astype(np.int64),
        rng.integers(0, 100, size=n).astype(np.float64),
    )


def _chunks(k, v, size=1000):
    for lo in range(0, len(k), size):
        yield {"k": k[lo:lo + size], "v": v[lo:lo + size]}


def _agg(f):
    with tfs.with_graph():
        w_in = tfs.block(f, "w", tf_name="w_input")
        return tfs.aggregate(
            tfs.reduce_sum(w_in, axis=0, name="w"), f.group_by("k")
        )


def _chain(f):
    g = tfs.map_blocks(lambda v: {"w": v * 2.0}, f)
    g = g.filter(lambda w: w > 50.0)
    return _agg(g)


def test_stream_chain_fold_bit_identical_to_in_memory(tmp_path):
    k, v = _dataset()
    st = BlockStore(root=str(tmp_path / "s"), budget_bytes=1 << 14)
    res = stream_chain(_chunks(k, v), chain_fn=_chain, fold_fn=_agg, store=st)
    # the walk spilled: a tiny budget cannot hold the partials resident
    assert st.resident_bytes <= st.budget_bytes
    oracle = _chain(tfs.frame_from_arrays({"k": k, "v": v}, num_blocks=20))
    np.testing.assert_array_equal(
        res.column_values("k"), oracle.column_values("k")
    )
    np.testing.assert_array_equal(
        res.column_values("w"), oracle.column_values("w")
    )
    st.close()


def test_stream_chain_map_filter_spilled_frame_roundtrip(tmp_path):
    k, v = _dataset()

    def mf(f):
        g = tfs.map_blocks(lambda v: {"w": v * 3.0}, f)
        return g.filter(lambda w: w > 30.0)

    st = BlockStore(root=str(tmp_path / "s"), budget_bytes=1 << 14)
    sf = stream_chain(_chunks(k, v), chain_fn=mf, store=st)
    assert isinstance(sf, SpilledFrame)
    assert st.spilled_bytes > 0
    mem = mf(tfs.frame_from_arrays({"k": k, "v": v}, num_blocks=20))
    out = sf.to_frame()
    np.testing.assert_array_equal(
        out.column_values("w"), mem.column_values("w")
    )
    np.testing.assert_array_equal(
        out.column_values("k"), mem.column_values("k")
    )
    assert sf.num_rows == mem.num_rows
    sf.drop()
    st.close()


def test_stream_chain_empty_source_raises(tmp_path):
    with pytest.raises(ValueError, match="no chunks"):
        stream_chain(iter(()))


def test_spill_to_and_back(tmp_path):
    f = tfs.frame_from_arrays(
        {"a": np.arange(1000, dtype=np.float64),
         "s": [f"n{i}" for i in range(1000)]},
        num_blocks=4,
    )
    st = BlockStore(root=str(tmp_path / "s"), budget_bytes=0)
    sf = f.spill_to(st)
    assert sf.num_blocks == 4 and st.spilled_bytes > 0
    back = sf.to_frame()
    np.testing.assert_array_equal(
        back.column_values("a"), f.column_values("a")
    )
    assert list(back.column_values("s")) == list(f.column_values("s"))
    st.close()


# ---------------------------------------------------------------------------
# chunked multi-part ingest
# ---------------------------------------------------------------------------

def _write_csv_parts(d, nparts=3, rows=100):
    os.makedirs(d, exist_ok=True)
    paths = []
    for i in range(nparts):
        p = os.path.join(d, f"part-{i}.csv")
        with open(p, "w") as f:
            f.write("k,v,s\n")
            for j in range(rows):
                f.write(f"{i * rows + j},{j / 2},name{i}_{j}\n")
        paths.append(p)
    return paths


def test_read_csv_directory_chunked_through_store(tmp_path):
    d = str(tmp_path / "parts")
    _write_csv_parts(d)
    frame = tfs.read_csv(d)
    assert frame.num_rows == 300
    kv = frame.column_values("k")
    assert kv[0] == 0 and kv[-1] == 299 and kv.dtype == np.int64
    assert frame.column_values("v").dtype == np.float64
    assert frame.blocks()[0]["s"][0] == "name0_0"
    # the dense blocks are store-backed views pinned to the frame
    assert hasattr(frame, "_data_plane")


def test_read_csv_part_list_preserves_order(tmp_path):
    d = str(tmp_path / "parts")
    paths = _write_csv_parts(d)
    frame = tfs.read_csv(list(reversed(paths)))
    kv = frame.column_values("k")
    assert kv[0] == 200 and kv[-1] == 99  # caller order IS row order


def test_read_csv_single_file_unchanged(tmp_path):
    d = str(tmp_path / "parts")
    [p0, *_] = _write_csv_parts(d)
    frame = tfs.read_csv(p0)
    assert frame.num_rows == 100 and not hasattr(frame, "_data_plane")


def test_scan_csv_chunk_bound(tmp_path):
    d = str(tmp_path / "parts")
    _write_csv_parts(d, nparts=2, rows=100)
    chunks = list(tfs.scan_csv(d, rows_per_chunk=32))
    assert all(len(c["k"]) <= 32 for c in chunks)
    assert sum(len(c["k"]) for c in chunks) == 200
    # first-part inference is pinned for later parts
    assert all(c["k"].dtype == np.int64 for c in chunks)


def test_read_parquet_directory(tmp_path):
    pytest.importorskip("pyarrow")
    d = str(tmp_path / "pq")
    os.makedirs(d)
    for i in range(2):
        t = tfs.frame_from_arrays({
            "a": np.arange(50, dtype=np.int64) + i * 50,
            "b": np.linspace(0.0, 1.0, 50),
        })
        tfs.write_parquet(t, os.path.join(d, f"p{i}.parquet"))
    frame = tfs.read_parquet(d)
    assert frame.num_rows == 100
    np.testing.assert_array_equal(
        frame.column_values("a"), np.arange(100, dtype=np.int64)
    )


def test_read_csv_empty_dir_raises(tmp_path):
    d = tmp_path / "empty"
    d.mkdir()
    with pytest.raises(ValueError, match="no part files"):
        tfs.read_csv(str(d))


def test_read_csv_all_header_only_parts_gives_typed_empty_frame(tmp_path):
    d = tmp_path / "hdr"
    d.mkdir()
    for i in range(2):
        (d / f"p{i}.csv").write_text("k,v,s\n")
    frame = tfs.read_csv(str(d))
    assert frame.num_rows == 0
    assert frame.columns == ["k", "v", "s"]  # same as the 1-file path


def test_read_csv_header_only_first_part_does_not_poison_types(tmp_path):
    d = tmp_path / "mix"
    d.mkdir()
    (d / "a0.csv").write_text("k,s\n")  # header-only, sorts FIRST
    (d / "a1.csv").write_text("k,s\n1,alice\n2,bob\n")
    frame = tfs.read_csv(str(d))
    assert frame.num_rows == 2
    assert frame.column_values("k").dtype == np.int64  # not float64
    assert list(frame.column_values("s")) == ["alice", "bob"]


def test_gauges_aggregate_across_live_stores(tmp_path):
    from tensorframes_tpu.blockstore.store import RESIDENT_BYTES

    base = RESIDENT_BYTES.value
    a = BlockStore(root=str(tmp_path / "a"), budget_bytes=1 << 30)
    b = BlockStore(root=str(tmp_path / "b"), budget_bytes=1 << 30)
    a.put({"x": np.arange(1000.0)})
    b.put({"x": np.arange(500.0)})
    assert RESIDENT_BYTES.value - base == 1500 * 8
    a.close()
    assert RESIDENT_BYTES.value - base == 500 * 8  # b still counted
    b.close()
    assert RESIDENT_BYTES.value - base == 0


# ---------------------------------------------------------------------------
# TFG111 — larger-than-budget materialization
# ---------------------------------------------------------------------------

def test_tfg111_flags_oversized_to_host_with_streaming_fix():
    old = get_config().block_budget_bytes
    try:
        configure(block_budget_bytes=1 << 10)
        f = tfs.frame_from_arrays({"a": np.arange(10_000, dtype=np.float64)})
        h = tfs.map_blocks(lambda a: {"b": a * 2.0}, f).to_host()
        rep = tfs.lint_plan(h)
        finds = rep.by_code("TFG111")
        assert len(finds) == 1
        assert "stream_chain" in finds[0].fix  # names the alternative
        assert "TFTPU_BLOCK_BUDGET_MB" in finds[0].message
        assert "stream" in finds[0].explain()
        # a chain rooted on the oversized materialization flags too
        h2 = tfs.map_blocks(lambda b: {"c": b + 1.0}, h)
        assert tfs.lint_plan(h2).by_code("TFG111")
    finally:
        configure(block_budget_bytes=old)


def test_tfg111_silent_under_budget():
    f = tfs.frame_from_arrays({"a": np.arange(100, dtype=np.float64)})
    h = tfs.map_blocks(lambda a: {"b": a * 2.0}, f).to_host()
    assert not tfs.lint_plan(h).by_code("TFG111")


def test_estimated_bytes_lower_bound():
    f = tfs.frame_from_arrays({
        "a": np.arange(1000, dtype=np.float64),
        "b": np.arange(1000, dtype=np.int64),
    })
    assert f.estimated_bytes == 1000 * 16
    lazy = tfs.map_blocks(lambda a: {"c": a * 2.0}, f)
    assert lazy.estimated_bytes is not None  # maps preserve the count


# ---------------------------------------------------------------------------
# file-shuffle transport (single-rank legs; 2-process correctness +
# kill -9 live in tests/test_distributed.py)
# ---------------------------------------------------------------------------

@pytest.fixture
def shuffle_env(tmp_path, monkeypatch):
    monkeypatch.setenv("TFTPU_SHUFFLE_DIR", str(tmp_path / "shuffle"))
    monkeypatch.setenv("TFTPU_SHUFFLE_RANK", "0")
    monkeypatch.setenv("TFTPU_SHUFFLE_NPROCS", "1")
    fshuffle._reset_for_tests()
    yield
    fshuffle._reset_for_tests()


def test_exchange_rows_picks_file_transport(shuffle_env):
    from tensorframes_tpu.ops import exchange as ex

    cols = {"k": np.asarray([3, 1, 2], np.int64), "s": ["a", "b", "c"]}
    out = ex.exchange_rows(cols, np.zeros(3, np.int64))
    np.testing.assert_array_equal(out["k"], cols["k"])
    assert out["s"] == cols["s"]
    assert ex.last_exchange_stats["transport"] == "files"


def test_exchange_rows_collective_transport_without_shuffle_dir(monkeypatch):
    monkeypatch.delenv("TFTPU_SHUFFLE_DIR", raising=False)
    monkeypatch.delenv("TFTPU_FLEET_DIR", raising=False)
    fshuffle._reset_for_tests()
    assert not fshuffle.enabled()
    from tensorframes_tpu.ops import exchange as ex

    # single jax process: the collective path degenerates to identity
    cols = {"k": np.asarray([1, 2], np.int64)}
    out = ex.exchange_rows(cols, np.zeros(2, np.int64))
    np.testing.assert_array_equal(out["k"], cols["k"])
    assert "transport" not in (ex.last_exchange_stats or {})


def test_fleet_dir_fallback_requires_transport_opt_in(tmp_path, monkeypatch):
    monkeypatch.delenv("TFTPU_SHUFFLE_DIR", raising=False)
    monkeypatch.setenv("TFTPU_FLEET_DIR", str(tmp_path / "fleet"))
    monkeypatch.delenv("TFTPU_SHUFFLE_TRANSPORT", raising=False)
    fshuffle._reset_for_tests()
    assert not fshuffle.enabled()  # supervised fleets keep collectives
    monkeypatch.setenv("TFTPU_SHUFFLE_TRANSPORT", "files")
    fshuffle._reset_for_tests()
    assert fshuffle.enabled()
    assert fshuffle.shuffle_dir().endswith(os.path.join("fleet", "shuffle"))
    fshuffle._reset_for_tests()


def test_framed_read_transient_retried_then_persistent_quarantines(
    tmp_path,
):
    # (the self-partition short-circuits in memory, so single-rank
    # exchanges never read files — drive the framed read directly)
    p = str(tmp_path / "x.part")
    fshuffle._publish(p, b"payload")
    # one transient read fault: absorbed by the framed read's retries
    with inject("shuffle.exchange", OSError("torn read"),
                max_times=1) as inj:
        assert fshuffle._read_framed(p, describe="t") == b"payload"
    assert inj.fired == 1
    # persistent faults exhaust retries -> quarantine + raise
    with inject("shuffle.exchange", OSError("bad disk")):
        with pytest.raises(fshuffle.ShuffleCorruptionError):
            fshuffle._read_framed(p, describe="t")
    assert not os.path.exists(p)  # renamed aside, never served again


def test_corrupt_peer_payload_raises_and_keeps_round_lockstep(
    tmp_path, monkeypatch,
):
    """Act as rank 0 of a 2-rank fleet whose peer published a CORRUPT
    payload: the exchange quarantines it and raises — and still
    advances the local round counter, so a caller that survives the
    error stays in lockstep with the peers that completed the round."""
    monkeypatch.setenv("TFTPU_SHUFFLE_DIR", str(tmp_path / "sh"))
    monkeypatch.setenv("TFTPU_SHUFFLE_RANK", "0")
    monkeypatch.setenv("TFTPU_SHUFFLE_NPROCS", "2")
    fshuffle._reset_for_tests()
    ctx = fshuffle.context()
    rd = os.path.join(ctx.root, f"round-{ctx.rounds:06d}-rc")
    os.makedirs(rd)
    with open(os.path.join(rd, "s00001-d00000.part"), "wb") as f:
        f.write(b"garbage, not a framed payload")
    fshuffle._publish(os.path.join(rd, "src-00001.done"), b"")
    r0 = ctx.rounds
    with pytest.raises(fshuffle.ShuffleCorruptionError):
        fshuffle.exchange([b"a", b"b"], name="rc", timeout=10.0)
    assert ctx.rounds == r0 + 1  # advanced despite the failure
    fshuffle._reset_for_tests()


def test_shuffle_hang_names_missing_rank(tmp_path, monkeypatch):
    monkeypatch.setenv("TFTPU_SHUFFLE_DIR", str(tmp_path / "sh"))
    monkeypatch.setenv("TFTPU_SHUFFLE_RANK", "0")
    monkeypatch.setenv("TFTPU_SHUFFLE_NPROCS", "2")
    fshuffle._reset_for_tests()
    from tensorframes_tpu.resilience.fleet import HungDispatchError

    with pytest.raises(HungDispatchError, match=r"rank\(s\) \[1\]"):
        fshuffle.exchange([b"a", b"b"], name="hang", timeout=0.5)
    fshuffle._reset_for_tests()


def test_vote_all_and_allshare_single_rank(shuffle_env):
    assert fshuffle.vote_all(True, name="v1") is True
    assert fshuffle.vote_all(False, name="v2") is False
    t = fshuffle.allshare_table(
        {"k": np.asarray([1, 2], np.int64), "s": ["x", "y"]}, name="t"
    )
    np.testing.assert_array_equal(t["k"], [1, 2])
    assert t["s"] == ["x", "y"]


def test_distributed_aggregate_single_rank_matches_local(shuffle_env):
    k = np.asarray([2, 1, 2, 1, 3], np.int64)
    v = np.asarray([1.0, 2.0, 3.0, 4.0, 5.0])
    f = tfs.frame_from_arrays({"k": k, "v": v})

    def agg(fr):
        with tfs.with_graph():
            v_in = tfs.block(fr, "v", tf_name="v_input")
            return tfs.aggregate(
                tfs.reduce_sum(v_in, axis=0, name="v"), fr.group_by("k")
            )

    res = fshuffle.distributed_aggregate(f, ["k"], agg)
    oracle = agg(f)
    np.testing.assert_array_equal(
        res.column_values("k"), oracle.column_values("k")
    )
    np.testing.assert_array_equal(
        res.column_values("v"), oracle.column_values("v")
    )


# ---------------------------------------------------------------------------
# kv pool host-swap tier
# ---------------------------------------------------------------------------

def test_kvpool_spill_restore_bit_identical(tmp_path):
    from tensorframes_tpu.models import generation as gen
    from tensorframes_tpu.serving.kvpool import (
        PagedKVPool, PoolAccountingError,
    )

    st = BlockStore(root=str(tmp_path / "kv"), budget_bytes=0)
    pool = PagedKVPool(gen.gpt_tiny().served_model(4, 4 * 4),
                       num_pages=9, page_size=4)
    pool.alloc(1, 2)
    pool.alloc(2, 3)
    snap = pool.spill(st)
    assert st.spilled_bytes > 0  # pool snapshots are pushed to disk
    before = {k: np.asarray(v).copy() for k, v in pool.columns.items()}
    pool.free_seq(1)
    pool.free_seq(2)
    pool.restore(st, snap)
    for k in before:
        np.testing.assert_array_equal(np.asarray(pool.columns[k]), before[k])
    assert pool.owned(1) == snap["owned"][1]
    assert pool.owned(2) == snap["owned"][2]
    pool.check()
    # geometry mismatch refuses before touching anything
    other = PagedKVPool(gen.gpt_tiny().served_model(4, 4 * 4),
                       num_pages=17, page_size=4)
    with pytest.raises(PoolAccountingError):
        other.restore(st, snap)
    st.close()


def test_kvpool_refuses_snapshots_of_another_layout(tmp_path):
    """A whole-pool spill or a swap segment written by a build with
    another pool layout (the [pages, layers, heads, page, head_dim]
    columns before PR 26, or one with no shapes recorded at all) is
    refused with a clear error — its bytes are never reinterpreted —
    before any pool state is touched."""
    from tensorframes_tpu.models import generation as gen
    from tensorframes_tpu.serving.kvpool import (
        PagedKVPool, PoolAccountingError,
    )

    cfg = gen.gpt_tiny()
    st = BlockStore(root=str(tmp_path / "kv"), budget_bytes=0)
    pool = PagedKVPool(cfg.served_model(4, 4 * 4),
                       num_pages=9, page_size=4)
    pool.alloc(1, 2)
    snap = pool.spill(st)
    assert snap["page_shapes"] == pool.page_shapes() == {
        "k": [cfg.num_layers, 4, cfg.num_heads * cfg.head_dim],
        "v": [cfg.num_layers, 4, cfg.num_heads * cfg.head_dim],
        "k_scale": [cfg.num_layers, 4, 128],
        "v_scale": [cfg.num_layers, 4, 128],
    }
    old_shapes = {
        "k": [cfg.num_layers, cfg.num_heads, 4, cfg.head_dim],
        "v": [cfg.num_layers, cfg.num_heads, 4, cfg.head_dim],
        "k_scale": [cfg.num_layers, cfg.num_heads, 4, 1],
        "v_scale": [cfg.num_layers, cfg.num_heads, 4, 1],
    }
    free_before = pool.num_free
    for bad in (dict(snap, page_shapes=old_shapes),
                {k: v for k, v in snap.items() if k != "page_shapes"}):
        with pytest.raises(PoolAccountingError, match="another pool layout"):
            pool.restore(st, bad)
        assert pool.num_free == free_before and pool.owned(1)
    # per-sequence swap segments carry the shapes too
    payload = {
        k: np.asarray(v)[1:3].copy() for k, v in pool.columns.items()
    }
    seg = pool.swap_out_seq(st, 1, payload)
    assert seg["page_shapes"] == pool.page_shapes()
    with pytest.raises(PoolAccountingError, match="another pool layout"):
        pool.swap_in_seq(st, dict(seg, page_shapes=old_shapes), 2)
    assert not pool.owned(2)
    # ... and adopt_swapped skips a foreign segment instead of re-homing
    swap = BlockStore(root=str(tmp_path / "swap"), budget_bytes=0)
    folded = {"swapped": {
        "ours": dict(seg), "theirs": dict(seg, page_shapes=old_shapes),
    }}
    assert set(pool.adopt_swapped(st, folded, swap)) == {"ours"}
    pages, block = pool.swap_in_seq(st, seg, 2)
    assert len(pages) == 2
    for k in payload:
        np.testing.assert_array_equal(np.asarray(block[k]), payload[k])
    swap.close()
    st.close()


def test_kvpool_spill_folds_swap_segments(tmp_path):
    """PR 18 follow-up: per-sequence host-swap segments ride the
    whole-pool spill() snapshot (keyed by the request's cross-restart
    trace id) and adopt_swapped() re-homes them into a FRESH engine's
    swap store bit-identically — swap segments no longer die with the
    engine that wrote them."""
    from tensorframes_tpu.models import generation as gen
    from tensorframes_tpu.serving.kvpool import PagedKVPool

    st = BlockStore(root=str(tmp_path / "kv"), budget_bytes=0)
    swap = BlockStore(root=str(tmp_path / "swap"), budget_bytes=0)
    pool = PagedKVPool(gen.gpt_tiny().served_model(4, 4 * 4),
                       num_pages=9, page_size=4)
    pool.alloc(1, 2)
    payload = {
        k: np.asarray(v)[1:3].copy() for k, v in pool.columns.items()
    }
    snap1 = pool.swap_out_seq(swap, 1, payload)
    # the engine rides pos/generated/replay on the same snapshot dict
    snap1["pos"] = 7
    snap1["generated"] = [3, 1]
    snap1["replay"] = []
    whole = pool.spill(st, swaps={"tid-1": snap1}, swap_store=swap)
    assert set(whole["swapped"]) == {"tid-1"}
    # the folded entry re-published the segment into the spill store:
    # dropping the ORIGINAL swap store must not lose it
    swap.drop(snap1["ref"])
    swap.close()
    swap2 = BlockStore(root=str(tmp_path / "swap2"), budget_bytes=0)
    manifest = pool.adopt_swapped(st, whole, swap2)
    assert set(manifest) == {"tid-1"}
    entry = manifest["tid-1"]
    assert entry["pos"] == 7 and entry["generated"] == [3, 1]
    assert int(entry["pages"]) == 2
    got = swap2.get(entry["ref"])
    for k in payload:
        np.testing.assert_array_equal(np.asarray(got[k]), payload[k])
    # restore() with a swap_store returns the same manifest alongside
    # the bit-identical pool rehydration
    pool.free_seq(1)
    swap3 = BlockStore(root=str(tmp_path / "swap3"), budget_bytes=0)
    manifest2 = pool.restore(st, whole, swap_store=swap3)
    assert set(manifest2) == {"tid-1"}
    pool.check()
    for s in (st, swap2, swap3):
        s.close()


# ---------------------------------------------------------------------------
# concurrency: loader-thread puts while the consumer gets
# ---------------------------------------------------------------------------

def test_store_threaded_put_get(store):
    errs = []

    def producer():
        try:
            for i in range(16):
                store.put(_mk_block(i, rows=512))
        except Exception as e:  # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=producer) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    for ref in store.refs():
        got = store.get(ref)
        assert len(got["x"]) == 512
    assert store.resident_bytes <= store.budget_bytes

"""Multi-process distributed backend tests: N real OS processes, each
owning one CPU device, coordinate through ``init_distributed``
(jax.distributed) and run collectives across process boundaries.

This is the test the reference never had (SURVEY §4: "no multi-node test
infrastructure anywhere in the repo" — distribution was tested by
partition count only). Here the control plane (coordinator service) and
the collective path are exercised across actual process boundaries — the
single-host analogue of multi-host DCN — at 2 and at 4 processes
(the 4-way run additionally covers multi-hop collective schedules and
the sharded save/load round-trip with four writers).
"""

import os
import socket
import subprocess
import sys


_WORKER = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, {repo!r})
from tensorframes_tpu.parallel import init_distributed, is_multiprocess, process_index

NPROC = int(sys.argv[2])
init_distributed(
    coordinator_address={coord!r},
    num_processes=NPROC,
    process_id=int(sys.argv[1]),
)
assert is_multiprocess(), f"process_count={{jax.process_count()}}"
assert process_index() == int(sys.argv[1])
assert len(jax.devices()) == NPROC, jax.devices()  # every process's device visible

import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

mesh = Mesh(jax.devices(), ("dp",))
# each process contributes its own shard; the jitted sum crosses the
# process boundary through the collective
arr = jax.make_array_from_callback(
    (NPROC,), NamedSharding(mesh, P("dp")),
    lambda idx: jnp.asarray([float(process_index()) + 1.0]),
)
total = jax.jit(lambda x: x.sum(), out_shardings=NamedSharding(mesh, P()))(arr)
want_total = float(sum(range(1, NPROC + 1)))
assert float(total) == want_total, float(total)

# frame-level: each process contributes local rows; verbs run SPMD and the
# reduction crosses the host boundary (≙ partitions on N executors)
import tensorframes_tpu as tfs
from tensorframes_tpu.parallel import frame_from_process_local

pid = process_index()
local = np.asarray([10.0 * pid + 1.0, 10.0 * pid + 2.0])
frame = frame_from_process_local({{"v": local}}, mesh=mesh, axis="dp")
assert frame.num_rows == 2 * NPROC  # global rows, all processes' shards
doubled = tfs.map_blocks(lambda v: {{"w": v * 2.0}}, frame)
s = tfs.reduce_blocks(lambda w_input: {{"w": w_input.sum(axis=0)}}, doubled)
want_s = 2.0 * sum(10.0 * p + 1.0 + 10.0 * p + 2.0 for p in range(NPROC))
assert float(s) == want_s, float(s)
# keyed aggregate across processes: the sharded dense-bucket plan
# (ops/device_agg.py) reduces per shard and merges with one psum over the
# process boundary; only the tiny replicated bucket table reaches numpy,
# so the non-addressable input columns are never host-gathered
kf = frame_from_process_local(
    {{"k": np.asarray([pid, pid + 1]), "v": local}}, mesh=mesh, axis="dp"
)
with tfs.with_graph():
    v_input = tfs.block(kf, "v", tf_name="v_input")
    agg = tfs.aggregate(
        tfs.reduce_sum(v_input, axis=0, name="v"), kf.group_by("k")
    )
got = {{r["k"]: r["v"] for r in agg.collect()}}
want = {{}}
for p in range(NPROC):
    want[p] = want.get(p, 0.0) + 10.0 * p + 1.0
    want[p + 1] = want.get(p + 1, 0.0) + 10.0 * p + 2.0
assert got == want, (got, want)
# STRING keys across processes (VERDICT r2 #4): host-only key columns are
# process-local; the dictionary plan unions the per-process dictionaries
# with one allgather and reduces through the same segment plan — no
# process ever gathers another's raw keys
skf = frame_from_process_local(
    {{"k": ["shared", "p%d" % pid], "v": local}}, mesh=mesh, axis="dp"
)
with tfs.with_graph():
    v_input = tfs.block(skf, "v", tf_name="v_input")
    sagg = tfs.aggregate(
        tfs.reduce_sum(v_input, axis=0, name="v"), skf.group_by("k")
    )
sgot = {{str(r["k"]): r["v"] for r in sagg.collect()}}
swant = {{"shared": float(sum(10.0 * p + 1.0 for p in range(NPROC)))}}
for p in range(NPROC):
    swant["p%d" % p] = 10.0 * p + 2.0
assert sgot == swant, (sgot, swant)
# GENERIC (non-reducer) combiner across processes (VERDICT r2 missing #5):
# an apply_fn program is not segment-lowerable, so the device plans
# decline — the multiprocess generic path compacts locally and merges
# one partial per (process, group) through an allgather
with tfs.with_graph():
    v_input2 = tfs.block(kf, "v", tf_name="v_input")
    gagg = tfs.aggregate(
        tfs.apply_fn(lambda v: v.sum(axis=0), v_input2, name="v"),
        kf.group_by("k"),
    )
ggot = {{int(r["k"]): float(r["v"]) for r in gagg.collect()}}
assert ggot == want, (ggot, want)
# multi-process JOIN (VERDICT r3 #7 — replaces the spans-processes
# raise): broadcast hash join — every process allgathers the right
# side (device key/value columns AND a host string column), joins its
# LOCAL left rows, and holds its share of the output process-locally
rt = frame_from_process_local(
    {{"k": np.asarray([pid]), "w": np.asarray([100.0 * pid]),
      "name": ["proc%d" % pid]}},
    mesh=mesh, axis="dp",
)
joined = kf.join(rt, on="k")
jrows = joined.collect()
jwant = [(pid, 10.0 * pid + 1.0, 100.0 * pid, "proc%d" % pid)]
if pid + 1 < NPROC:
    jwant.append(
        (pid + 1, 10.0 * pid + 2.0, 100.0 * (pid + 1),
         "proc%d" % (pid + 1))
    )
jgot = [
    (int(r["k"]), float(r["v"]), float(r["w"]), str(r["name"]))
    for r in jrows
]
assert jgot == jwant, (jgot, jwant)
# multi-process FILTER: process-local subset (each process keeps its
# own passing rows; no collective)
fgot = [
    (int(r["k"]), float(r["v"]))
    for r in kf.filter(lambda v: {{"keep": v > 10.0 * pid + 1.5}}).collect()
]
assert fgot == [(pid + 1, 10.0 * pid + 2.0)], fgot
# multi-process SORT: allgather in process order -> every process holds
# the SAME replicated globally-sorted frame. EXACT sequence asserted:
# python's sorted() over the global-row-order list is stable, so equal
# keys must appear in global row order — tie stability included
sgot2 = [
    (int(r["k"]), float(r["v"]))
    for r in kf.sort_values("k").collect()
]
global_rows = []
for p in range(NPROC):
    global_rows.append((p, 10.0 * p + 1.0))
    global_rows.append((p + 1, 10.0 * p + 2.0))
swant2 = sorted(global_rows, key=lambda t: t[0])
assert sgot2 == swant2, (sgot2, swant2)
# EXCHANGE paths (VERDICT r4 #2): force the broadcast budget tiny so
# sort_values takes the RANGE exchange and join the HASH exchange —
# no process may hold the global frame. Asserted: correctness (global
# order / join values), the O(global/P) memory bound (per-process row
# share), and the disabled-exchange guard.
from jax.experimental import multihost_utils as mhx
from tensorframes_tpu.config import configure
from tensorframes_tpu.ops import exchange as xch

configure(relational_broadcast_bytes=64)
NLOC = 400
rngx = np.random.default_rng(1000 + pid)
xk = rngx.integers(0, 1000, NLOC).astype(np.int64)
xv = (xk * 2).astype(np.float64)
xf = frame_from_process_local({{"k": xk, "v": xv}}, mesh=mesh, axis="dp")
part_rows = xf.sort_values("k").collect()  # this process's key RANGE
pk = np.asarray([r["k"] for r in part_rows], np.int64)
pv = np.asarray([r["v"] for r in part_rows])
assert (np.diff(pk) >= 0).all()  # locally sorted
np.testing.assert_array_equal(pv, pk * 2.0)  # rows kept intact
lens = np.asarray(
    mhx.process_allgather(np.asarray([len(pk)], np.int64))
).reshape(-1)
assert int(lens.sum()) == NPROC * NLOC  # nothing lost or duplicated
# memory bound: no process holds the global frame (a replicating plan
# would put all NPROC*NLOC rows here); 2x over the balanced share is
# the skew allowance for random keys
assert int(lens.max()) <= max(2 * NLOC, 64), lens
# partitions form disjoint ordered ranges: concatenating processes in
# order IS the global sort (pad-allgather the variable-length parts)
W = int(lens.max())
buf = np.full(W, -1, np.int64)
buf[: len(pk)] = pk
allb = np.asarray(mhx.process_allgather(buf)).reshape(NPROC, W)
cat = np.concatenate(
    [allb[p, : int(lens[p])] for p in range(NPROC)]
)
gk = np.asarray(mhx.process_allgather(xk)).reshape(-1)
np.testing.assert_array_equal(cat, np.sort(gk, kind="stable"))
# SHUFFLE JOIN: right side over budget → hash-partition both sides
rk = np.arange(pid, 1000, NPROC).astype(np.int64)
rframe = frame_from_process_local(
    {{"k": rk, "w": (rk * 10).astype(np.float64)}}, mesh=mesh, axis="dp"
)
jrows = xf.join(rframe, on="k").collect()
for r in jrows:
    assert float(r["w"]) == int(r["k"]) * 10.0
    assert float(r["v"]) == int(r["k"]) * 2.0
jlen = np.asarray(
    mhx.process_allgather(np.asarray([len(jrows)], np.int64))
).reshape(-1)
# right side covers every key 0..999 exactly once → one output row per
# left row, spread across processes by key hash
assert int(jlen.sum()) == NPROC * NLOC, jlen
assert int(jlen.max()) <= max(2 * NLOC, 64), jlen
# OUTER join across processes rides the exchange (broadcast would
# duplicate unmatched right rows on every process): global row count =
# matched left rows + each unmatched right key exactly ONCE
orows = xf.join(
    rframe, on="k", how="outer",
    fill_value={{"v": -1.0, "w": -1.0}},
).collect()
olen = np.asarray(
    mhx.process_allgather(np.asarray([len(orows)], np.int64))
).reshape(-1)
n_distinct = len(np.unique(gk))
assert int(olen.sum()) == NPROC * NLOC + (1000 - n_distinct), (
    int(olen.sum()), NPROC * NLOC, n_distinct
)
for r in orows:  # every left row matches, so only v carries fills
    assert float(r["w"]) == int(r["k"]) * 10.0
# CO-PARTITIONING (repartition_by_key): pay the shuffle once, then
# joins run process-locally (spans=False on the local host frames) and
# the union of local joins equals the global join
lp = xf.repartition_by_key("k")
rp = rframe.repartition_by_key("k")
from tensorframes_tpu.ops.exchange import partition_by_hash
lk = np.asarray(lp.column_values("k"), np.int64)
assert (partition_by_hash([lk], NPROC) == pid).all()  # keys colocated
cj = lp.join(rp, on="k").collect()
cjlen = np.asarray(
    mhx.process_allgather(np.asarray([len(cj)], np.int64))
).reshape(-1)
assert int(cjlen.sum()) == NPROC * NLOC, cjlen
for r in cj:
    assert float(r["w"]) == int(r["k"]) * 10.0
    assert float(r["v"]) == int(r["k"]) * 2.0
# distributed drop_duplicates: duplicates COLOCATE under the hash
# exchange, so each process's local dedup is the global dedup; survivors
# carry the GLOBAL-first-occurrence row (v encodes (proc, row))
dupf = frame_from_process_local(
    {{"k": np.asarray([0, 10 + pid, 0, 10 + pid], np.int64),
      "v": np.asarray([100.0 * pid + i for i in range(4)])}},
    mesh=mesh, axis="dp",
)
surv = dupf.drop_duplicates(subset="k").collect()
for r in surv:
    kk, vv = int(r["k"]), float(r["v"])
    if kk == 0:
        assert vv == 0.0, r  # global first occurrence: proc 0, row 0
    else:
        p_src = kk - 10
        assert vv == 100.0 * p_src + 1.0, r  # proc p_src, row 1
slen = np.asarray(
    mhx.process_allgather(np.asarray([len(surv)], np.int64))
).reshape(-1)
assert int(slen.sum()) == 1 + NPROC, slen  # key 0 plus one 10+p per proc
# the round-5 review's blind spot: dedup of a process-LOCAL frame on a
# key OTHER than its partition key must still be global (the exchange
# runs for every layout) — column b duplicates span every process
pl2 = frame_from_process_local(
    {{"a": np.asarray([pid, pid], np.int64),
      "b": np.asarray([7, 7], np.int64)}},
    mesh=mesh, axis="dp",
).repartition_by_key("a")
sb = pl2.drop_duplicates(subset="b").collect()
sblen = np.asarray(
    mhx.process_allgather(np.asarray([len(sb)], np.int64))
).reshape(-1)
assert int(sblen.sum()) == 1, sblen  # one global survivor, not one/proc
# replicated-in → replicated-out (ADVICE r5): a frame built IDENTICALLY
# on every process (all columns byte-equal fleet-wide) dedups LOCALLY —
# every process keeps every unique row, instead of being converted into
# per-process hash partitions like the process-local frames above
repf = tfs.frame_from_arrays(
    {{"k": np.asarray([1, 2, 1, 3], np.int64),
      "v": np.asarray([1.0, 2.0, 3.0, 4.0])}})
rsurv = repf.drop_duplicates(subset="k").collect()
assert [int(r["k"]) for r in rsurv] == [1, 2, 3], rsurv
assert [float(r["v"]) for r in rsurv] == [1.0, 2.0, 4.0], rsurv
rlen = np.asarray(
    mhx.process_allgather(np.asarray([len(rsurv)], np.int64))
).reshape(-1)
assert (rlen == 3).all(), rlen  # replicated result on every process
# ...but a SHARDED frame whose per-process shards happen to be
# byte-identical (symmetric seed data) is NOT replicated — its global
# frame is the concatenation of the shards, so dedup must still
# exchange and collapse to ONE global survivor; the content hash alone
# would misclassify this (review r9: the layout check precedes it)
sym = frame_from_process_local(
    {{"k": np.asarray([5, 5], np.int64)}}, mesh=mesh, axis="dp",
)
ssurv = sym.drop_duplicates(subset="k").collect()
sslen = np.asarray(
    mhx.process_allgather(np.asarray([len(ssurv)], np.int64))
).reshape(-1)
assert int(sslen.sum()) == 1, sslen  # one GLOBAL survivor, not one/proc
# ... and the sort_values layout-switch tripwire (ADVICE r5) fired when
# the over-budget sort above took the range exchange (budget was 64B)
from tensorframes_tpu import frame as _frame_mod
assert _frame_mod._sort_layout_warned  # one-time warning happened
# exchange observability: the shuffle plans record their own spans
from tensorframes_tpu.utils import profiling as _prof
_rep = _prof.report()
for spanname in ("sort_values.exchange", "join.exchange", "repartition_by_key"):
    assert spanname in _rep, (spanname, _rep[-2000:])
# guard: with the exchange disabled, over-budget plans raise the
# actionable error on EVERY process instead of replicating
configure(relational_exchange=False)
for plan in (
    lambda: xf.sort_values("k").collect(),
    lambda: xf.join(rframe, on="k").collect(),
):
    try:
        plan()
        raise SystemExit("exchange guard did not fire")
    except RuntimeError as e:
        assert "relational_broadcast_bytes" in str(e), e
configure(relational_exchange=True, relational_broadcast_bytes=64 << 20)
# replication tripwire: repartitioning a REPLICATED frame (the
# under-budget sort result — every process holds the same rows) must
# warn about P-fold duplication
import logging as _lg
_msgs = []
class _CapH(_lg.Handler):
    def emit(self, r):
        _msgs.append(r.getMessage())
_h = _CapH()
_lg.getLogger("tensorframes_tpu.frame").addHandler(_h)
replicated = kf.sort_values("k")  # small -> replicated plan
_ = replicated.repartition_by_key("k")
_lg.getLogger("tensorframes_tpu.frame").removeHandler(_h)
assert any("identical" in m for m in _msgs), _msgs
# sharded persistence: each process writes its part, reloads, and the
# reassembled global frame reduces to the same total across hosts
sf_dir = {sf_dir!r}
tfs.io.save_frame_sharded(frame, sf_dir)
back = tfs.io.load_frame_sharded(sf_dir, mesh=mesh, axis="dp")
s2 = tfs.reduce_blocks(lambda v_input: {{"v": v_input.sum(axis=0)}}, back)
assert float(s2) == want_s / 2.0, float(s2)
print(f"proc {{sys.argv[1]}} OK total={{float(total)}} frame_sum={{float(s)}}", flush=True)
"""


# ---------------------------------------------------------------------------
# sharded compile-cache round trip (ISSUE 10): the same worker runs twice
# against ONE persistent store; its sharded dispatches ride the unified
# AOT path, so run 2 must load every executable from disk — zero XLA
# compiles — and produce bit-identical results. The metrics JSONL the
# worker writes is the same artifact shape CI asserts on
# (tftpu_compilecache_hits_total / tftpu_executor_compile_seconds).
# ---------------------------------------------------------------------------

_CACHE_WORKER = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
sys.path.insert(0, {repo!r})
import json
import numpy as np
import tensorframes_tpu as tfs
from tensorframes_tpu.observability import REGISTRY

df = tfs.frame_from_arrays(
    {{"x": np.arange(640, dtype=np.float32)}}
).to_device()
assert df.is_sharded, "worker needs the 8-device virtual mesh"
program = tfs.compile_program(
    lambda x: {{"y": x * 3.0 + 1.0, "z": x.sum() + x}}, df
)
out = tfs.map_blocks(program, df)
y = np.asarray(out.column_values("y"))
z = np.asarray(out.column_values("z"))
np.save(sys.argv[2], np.stack([y, z]))
REGISTRY.write_jsonl(sys.argv[1])
print("CACHE WORKER OK", flush=True)
"""


def _metric(path, name, field="value"):
    import json as _json

    total = 0.0
    for line in open(path):
        d = _json.loads(line)
        if d["name"] == name:
            total += d.get(field) or 0
    return total


def test_sharded_cache_roundtrip_across_processes(tmp_path):
    """Two fresh subprocesses share one TFTPU_COMPILE_CACHE: the second
    performs ZERO XLA compiles (all sharded executables load from the
    store) and its results are bit-identical to the first's — the
    tentpole acceptance, in-suite."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = tmp_path / "cache_worker.py"
    script.write_text(_CACHE_WORKER.format(repo=repo))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["TFTPU_COMPILE_CACHE"] = str(tmp_path / "store")
    outs = []
    for run in (1, 2):
        metrics = tmp_path / f"metrics_{run}.jsonl"
        results = tmp_path / f"results_{run}.npy"
        r = subprocess.run(
            [sys.executable, str(script), str(metrics), str(results)],
            capture_output=True, text=True, env=env, timeout=240,
        )
        assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-2000:]
        outs.append((metrics, results))
    import numpy as np

    m1, r1 = outs[0]
    m2, r2 = outs[1]
    # run 1 is the cold publisher: it compiled, and anything it read
    # from the store was published by... nobody (fresh dir)
    assert _metric(m1, "tftpu_executor_compile_seconds", "count") > 0
    # run 2 is the warm loader: disk hits, ZERO XLA compiles, and the
    # dispatch never fell back to lazy jit
    assert _metric(m2, "tftpu_compilecache_hits_total") > 0
    assert _metric(m2, "tftpu_executor_compile_seconds", "count") == 0
    assert _metric(m2, "tftpu_executor_fallback_dispatch_total") == 0
    # sharded cached results are bit-identical across the round trip
    np.testing.assert_array_equal(np.load(r1), np.load(r2))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _run_workers(tmp_path, nproc: int, timeout: float, attempts: int = 3):
    """Launch the worker fleet; retries with a FRESH coordinator port.
    The rendezvous is exposed to two load-dependent transients a retry
    cures: the _free_port bind/close/reuse race, and slow worker
    interpreter startup under a loaded machine blowing the distributed
    init window (observed as rare full-suite-only failures; round 5
    reproduced one by running a SECOND fleet concurrently — hence the
    third attempt)."""
    last = None
    for attempt in range(attempts):
        try:
            return _run_workers_once(tmp_path, nproc, timeout, attempt)
        except AssertionError as e:
            last = e
    raise last


def _run_workers_once(tmp_path, nproc: int, timeout: float, attempt: int):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    coord = f"localhost:{_free_port()}"
    script = tmp_path / f"worker_{attempt}.py"
    script.write_text(
        _WORKER.format(repo=repo, coord=coord, sf_dir=str(tmp_path / "sf"))
    )
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(i), str(nproc)],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            env=env,
        )
        for i in range(nproc)
    ]
    try:
        outs = []
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            outs.append(out)
        want_total = float(sum(range(1, nproc + 1)))
        for i, (p, out) in enumerate(zip(procs, outs)):
            assert p.returncode == 0, f"proc {i} failed:\n{out[-3000:]}"
            assert f"proc {i} OK total={want_total}" in out, out[-2000:]
    finally:
        # a hung coordinator rendezvous must not orphan workers into CI
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()


def test_two_process_psum(tmp_path):
    _run_workers(tmp_path, 2, timeout=110)


def test_four_process_psum(tmp_path):
    """4 processes ≙ 4 hosts: multi-hop collectives, 4-writer sharded
    save/load, and the device-aggregate merge at process_count=4
    (VERDICT r1 next-step 7: scale the multi-process story past 2).
    Generous timeout: each worker pays the full jax import + compile,
    and the suite may be sharing the machine."""
    _run_workers(tmp_path, 4, timeout=420)


# ---------------------------------------------------------------------------
# file-shuffle fleet (ISSUE 15): the distributed data plane WITHOUT jax
# collectives — ranks exchange hash-partitioned partial tables through
# per-rank spill files in a shared shuffle dir. Unlike the psum fleets
# above, these workers need no coordinator and no cross-process XLA
# collectives, so they run on every jaxlib (including ones whose
# multi-process CPU collectives are missing).
# ---------------------------------------------------------------------------

_SHUFFLE_WORKER = r'''
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
sys.path.insert(0, {repo!r})
rank = int(sys.argv[1])
os.environ["TFTPU_SHUFFLE_RANK"] = str(rank)
os.environ["TFTPU_SHUFFLE_NPROCS"] = "2"
import numpy as np
import tensorframes_tpu as tfs
from tensorframes_tpu.blockstore import shuffle
from tensorframes_tpu.blockstore.store import HOSTGATHER_BYTES

# the shared dataset recipe (seed-deterministic): rank r holds half the
# rows, so the union across ranks IS the oracle's frame
rng = np.random.default_rng(7)
N = 4000
k_i64 = rng.integers(0, 50, size=N).astype(np.int64)
k_i64[: N // 2] = 7  # skewed: one hot key owns half the rows
k_f64 = (k_i64 % 11).astype(np.float64) / 2.0
vals = rng.integers(0, 1000, size=N).astype(np.float64)  # int-valued: exact sums
k_str = [f"g{{int(x) % 5}}" for x in k_i64]
lo, hi = (0, N // 2) if rank == 0 else (N // 2, N)
local = tfs.frame_from_arrays(
    {{"k": k_i64[lo:hi], "kf": k_f64[lo:hi], "v": vals[lo:hi],
      "s": k_str[lo:hi]}}
)

def agg_sum(key):
    def fn(f):
        with tfs.with_graph():
            v_in = tfs.block(f, "v", tf_name="v_input")
            return tfs.aggregate(
                tfs.reduce_sum(v_in, axis=0, name="v"), f.group_by(key)
            )
    return fn

def agg_min(key):
    def fn(f):
        with tfs.with_graph():
            v_in = tfs.block(f, "v", tf_name="v_input")
            return tfs.aggregate(
                tfs.reduce_min(v_in, axis=0, name="v"), f.group_by(key)
            )
    return fn

# shuffled aggregates across every key dtype (+ the skewed int key)
r_i = shuffle.distributed_aggregate(local, ["k"], agg_sum("k"), name="a-i64")
r_f = shuffle.distributed_aggregate(local, ["kf"], agg_min("kf"), name="a-f64")
r_s = shuffle.distributed_aggregate(local, ["s"], agg_sum("s"), name="a-str")

# shuffled join: rank-local right side, union across ranks = full dim table
right = tfs.frame_from_arrays(
    {{"k": np.arange(rank * 25, rank * 25 + 25, dtype=np.int64),
      "w": np.arange(25, dtype=np.float64) + rank * 100}}
)
jcols = shuffle.distributed_join(
    local.select(["k", "v"]), right, on="k", name="j"
)

# THE acceptance gate: zero host-gathered partial tables anywhere
assert HOSTGATHER_BYTES.value == 0.0, HOSTGATHER_BYTES.value

if rank == 0:
    np.savez(
        {out!r},
        k=r_i.column_values("k"), v=r_i.column_values("v"),
        fk=r_f.column_values("kf"), fv=r_f.column_values("v"),
        sk=np.asarray(r_s.column_values("s"), dtype=object),
        sv=r_s.column_values("v"),
        jk=np.asarray(jcols["k"]), jv=np.asarray(jcols["v"]),
        jw=np.asarray(jcols["w"]),
        allow_pickle=True,
    )
print("SHUFFLE_WORKER_OK", rank, flush=True)
'''


def _shuffle_env(tmp_path):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env["TFTPU_SHUFFLE_DIR"] = str(tmp_path / "shuffle")
    env.pop("TFTPU_FLEET_DIR", None)
    return env


def test_two_process_file_shuffle_matches_single_process_oracle(tmp_path):
    """2 real OS processes, NO jax.distributed: shuffled aggregate
    (int64 / float64 / string keys, one hot key owning half the rows)
    and shuffled join, all bit-identical to the single-process oracle —
    with the host-gather metric asserted ZERO in every worker."""
    import numpy as np

    out = str(tmp_path / "rank0.npz")
    script = tmp_path / "shuffle_worker.py"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script.write_text(_SHUFFLE_WORKER.format(repo=repo, out=out))
    env = _shuffle_env(tmp_path)
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(r)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env,
        )
        for r in (0, 1)
    ]
    try:
        outs = [p.communicate(timeout=240)[0] for p in procs]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    for r, (p, o) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r} failed:\n{o[-3000:]}"
        assert f"SHUFFLE_WORKER_OK {r}" in o, o[-2000:]

    # the single-process oracle over the union of both ranks' rows
    import tensorframes_tpu as tfs

    rng = np.random.default_rng(7)
    N = 4000
    k_i64 = rng.integers(0, 50, size=N).astype(np.int64)
    k_i64[: N // 2] = 7
    k_f64 = (k_i64 % 11).astype(np.float64) / 2.0
    vals = rng.integers(0, 1000, size=N).astype(np.float64)
    k_str = [f"g{int(x) % 5}" for x in k_i64]
    full = tfs.frame_from_arrays(
        {"k": k_i64, "kf": k_f64, "v": vals, "s": k_str}
    )

    def agg(key, red):
        with tfs.with_graph():
            v_in = tfs.block(full, "v", tf_name="v_input")
            return tfs.aggregate(
                red(v_in, axis=0, name="v"), full.group_by(key)
            )

    z = np.load(str(tmp_path / "rank0.npz"), allow_pickle=True)
    oi = agg("k", tfs.reduce_sum)
    np.testing.assert_array_equal(z["k"], oi.column_values("k"))
    np.testing.assert_array_equal(z["v"], oi.column_values("v"))
    of = agg("kf", tfs.reduce_min)
    np.testing.assert_array_equal(z["fk"], of.column_values("kf"))
    np.testing.assert_array_equal(z["fv"], of.column_values("v"))
    os_ = agg("s", tfs.reduce_sum)
    assert list(z["sk"]) == list(os_.column_values("s"))
    np.testing.assert_array_equal(z["sv"], os_.column_values("v"))
    # join: same multiset of rows, bit-identical after canonical sort
    right = tfs.frame_from_arrays({
        "k": np.arange(50, dtype=np.int64),
        "w": np.concatenate(
            [np.arange(25.0), np.arange(25.0) + 100]
        ),
    })
    oj = full.select(["k", "v"]).join(right, on="k", how="inner")

    def canon(cols):
        arrs = [np.asarray(cols[c]) for c in ("k", "v", "w")]
        order = np.lexsort(arrs[::-1])
        return [a[order] for a in arrs]

    got = canon({"k": z["jk"], "v": z["jv"], "w": z["jw"]})
    want = canon({c: oj.column_values(c) for c in ("k", "v", "w")})
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


_SHUFFLE_KILL_WORKER = r'''
import os, signal, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
sys.path.insert(0, {repo!r})
rank = int(sys.argv[1])
os.environ["TFTPU_SHUFFLE_RANK"] = str(rank)
os.environ["TFTPU_SHUFFLE_NPROCS"] = "2"
from tensorframes_tpu.blockstore import shuffle
from tensorframes_tpu.resilience.fleet import HungDispatchError

if rank == 1:
    # die MID-shuffle: part files published, done-marker never lands —
    # the torn state a real kill -9 leaves behind
    _orig = shuffle._publish
    def _dying(path, payload):
        if "src-00001.done" in path:
            os.kill(os.getpid(), signal.SIGKILL)
        return _orig(path, payload)
    shuffle._publish = _dying
try:
    shuffle.exchange([b"a", b"b"], name="killdrill", timeout=10.0)
    print("NO_ABORT", flush=True)
except HungDispatchError as e:
    assert "[1]" in str(e), str(e)
    print("WATCHDOG_ABORT_NAMED", flush=True)
'''


def test_kill9_mid_shuffle_watchdog_abort_names_the_rank(tmp_path):
    """kill -9 of rank 1 between its part files and its done marker:
    rank 0's deadline-bounded wait raises HungDispatchError NAMING rank
    1 (never an indefinite hang), and the flight recorder's disk spool
    holds the shuffle.hang postmortem."""
    import glob

    script = tmp_path / "kill_worker.py"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script.write_text(_SHUFFLE_KILL_WORKER.format(repo=repo))
    env = _shuffle_env(tmp_path)
    env["TFTPU_FLIGHT_DIR"] = str(tmp_path / "flight")
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(r)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env,
        )
        for r in (0, 1)
    ]
    try:
        outs = [p.communicate(timeout=120)[0] for p in procs]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    assert procs[1].returncode == -9, outs[1][-1000:]  # really SIGKILLed
    assert procs[0].returncode == 0, outs[0][-3000:]
    assert "WATCHDOG_ABORT_NAMED" in outs[0], outs[0][-2000:]
    # the black box survived: a postmortem naming the hang is on disk
    dumps = glob.glob(str(tmp_path / "flight" / "postmortem_*.jsonl"))
    assert dumps, os.listdir(str(tmp_path / "flight"))
    joined = "".join(open(d).read() for d in dumps)
    assert "shuffle.hang" in joined

"""Iterative decode engine tests (ISSUE 11): the paged-KV contracts.

What must hold, stated in serving/decode.py: batched decode is
bit-identical per request to solo decode (and, for this formulation, to
the dense-cache ``gen.generate`` oracle); a warmed engine performs zero
steady-state XLA compiles under any join/leave mix; the pool's page
accounting never leaks or double-frees under random join/leave/evict
interleavings; an undersized pool preempts (evict + requeue + replay)
and still completes every request bit-identically; a full pool cannot
hold a request past its deadline (the pull-mode batcher's expirer
covers the slot-wait queue); and the slot/prompt bucket ladders are the
ONE serving ladder (``compilecache`` single source of truth).
"""

import json
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import tensorframes_tpu as tfs
from tensorframes_tpu.models import generation as gen
from tensorframes_tpu.models import transformer as tr
from tensorframes_tpu.serving import (
    DeadlineExceededError,
    DecodeConfig,
    DecodeEngine,
    PagedKVPool,
    PoolAccountingError,
    PoolExhaustedError,
    RejectedError,
    Server,
    ServingConfig,
    ServingError,
    serve_http,
)
from tensorframes_tpu.serving import metrics as sm
from tensorframes_tpu.validation import ValidationError


@pytest.fixture(scope="module")
def model():
    cfg = gen.gpt_tiny()
    params = tr.quantize_params(tr.init_params(cfg, seed=0))
    return cfg, params


@pytest.fixture(scope="module")
def engine(model):
    """One started engine shared by the read-only tests (compiles are
    the expensive part; every test below uses distinct prompts)."""
    cfg, params = model
    eng = DecodeEngine("t_shared", cfg, params, DecodeConfig(
        max_slots=4, page_size=8, max_prompt_len=16, max_new_tokens=8,
    ))
    eng.start()
    yield eng
    eng.stop(drain=True, timeout=120)


def _prompts(n, lo, hi, seed, vocab):
    rng = np.random.default_rng(seed)
    return [
        rng.integers(0, vocab, (int(rng.integers(lo, hi + 1)),)).astype(
            np.int32
        )
        for _ in range(n)
    ]


def _reference(model, prompt, new):
    cfg, params = model
    return np.asarray(
        gen.generate(cfg, params, prompt[None], new, kv_quant=True)
    )


def _hog_pool(pool):
    """Deterministically exhaust a pool from the outside (respecting
    the per-sequence cap) so no join can find prompt pages."""
    seqs = []
    while pool.num_free:
        seq = 10_000 + len(seqs)
        pool.alloc(seq, min(pool.num_free, pool.max_pages_per_seq))
        seqs.append(seq)
    return seqs


def _unhog_pool(pool, seqs):
    for s in seqs:
        pool.free_seq(s)


# ---------------------------------------------------------------------------
# KV pool accounting invariants
# ---------------------------------------------------------------------------

def test_kvpool_property_sweep_no_leak_no_double_free(model):
    """Random join/extend/leave/evict interleavings: after EVERY
    mutation the page partition holds (free ∪ owned = all usable pages,
    nothing in two places)."""
    cfg, _ = model
    pool = PagedKVPool(cfg.served_model(4, 4 * 4),
                       num_pages=17, page_size=4)
    rng = np.random.default_rng(7)
    live = {}
    next_seq = 0
    for _ in range(500):
        op = rng.integers(0, 3)
        if op == 0:  # join: allocate a fresh sequence's prompt pages
            n = int(rng.integers(1, 4))
            if pool.num_free >= n:
                pool.alloc(next_seq, n)
                live[next_seq] = n
                next_seq += 1
        elif op == 1 and live:  # extend a random live sequence
            seq = int(rng.choice(list(live)))
            if live[seq] < pool.max_pages_per_seq and pool.num_free:
                pool.alloc(seq, 1)
                live[seq] += 1
        elif op == 2 and live:  # leave/evict
            seq = int(rng.choice(list(live)))
            assert pool.free_seq(seq) == live.pop(seq)
        pool.check()
    for seq in list(live):
        pool.free_seq(seq)
    pool.check()
    assert pool.num_free == pool.usable_pages


def test_kvpool_exhaustion_and_double_free_raise(model):
    cfg, _ = model
    pool = PagedKVPool(cfg.served_model(4, 4 * 3),
                       num_pages=4, page_size=4)
    pool.alloc(0, 3)
    with pytest.raises(PoolExhaustedError):
        pool.alloc(1, 1)
    pool.check()
    # double free via corrupted ownership: simulate by freeing twice
    assert pool.free_seq(0) == 3
    assert pool.free_seq(0) == 0  # idempotent by absence, not an error
    pool._owned[5] = [1]          # page 1 is free: corruption
    with pytest.raises(PoolAccountingError):
        pool.free_seq(5)
    del pool._owned[5]
    pool.check()


def test_kvpool_floor_and_table(model):
    cfg, _ = model
    with pytest.raises(ValueError):
        # cannot hold the null page + one full sequence
        PagedKVPool(cfg.served_model(4, 4 * 3), num_pages=3, page_size=4)
    pool = PagedKVPool(cfg.served_model(4, 4 * 3),
                       num_pages=5, page_size=4)
    got = pool.alloc(9, 2)
    table = pool.table(9)
    assert table.shape == (3,) and table.dtype == np.int32
    assert list(table[:2]) == got and table[2] == 0
    assert not pool.null_table().any()
    fr = pool.as_frame()
    assert fr.num_rows == 5
    assert set(fr.schema.names) == {"k", "v", "k_scale", "v_scale"}
    # one physical layout: a position's heads side by side in one int8
    # row, its per-head scales in the lanes of one float32 row
    shapes = {k: tuple(v.shape) for k, v in pool.columns.items()}
    assert shapes["k"] == shapes["v"] == (
        5, cfg.num_layers, 4, cfg.num_heads * cfg.head_dim)
    assert shapes["k_scale"] == shapes["v_scale"] == (
        5, cfg.num_layers, 4, 128)


# ---------------------------------------------------------------------------
# Bucket-ladder single source of truth (satellite)
# ---------------------------------------------------------------------------

def test_decode_slot_buckets_are_the_serving_ladder():
    from tensorframes_tpu.compilecache import (
        decode_slot_buckets,
        decode_warmup_grid,
        serving_row_buckets,
    )
    from tensorframes_tpu.ops.executor import bucket_rows, bucket_table

    assert decode_slot_buckets(13) == serving_row_buckets(13)
    assert set(decode_slot_buckets(13)) <= set(bucket_table())
    for n in range(1, 14):
        assert bucket_rows(n) in decode_slot_buckets(13)
    grid = decode_warmup_grid(4, 16)
    assert grid["decode"] == serving_row_buckets(4)
    assert grid["prefill"] == serving_row_buckets(16)
    with pytest.raises(ValueError):
        decode_slot_buckets(0)


# ---------------------------------------------------------------------------
# Engine correctness: bit-identity, zero compiles
# ---------------------------------------------------------------------------

def test_batched_decode_bit_identical_to_solo_and_reference(
    model, engine
):
    cfg, _ = model
    prompts = _prompts(6, 3, 16, seed=11, vocab=cfg.vocab_size)
    futs = [engine.submit({"prompt": p}) for p in prompts]
    outs = [f.result(300)["tokens"] for f in futs]
    solo = [engine.call({"prompt": p}, timeout=300)["tokens"]
            for p in prompts]
    for i, p in enumerate(prompts):
        assert outs[i].shape == (1, 8)
        assert np.array_equal(outs[i], solo[i]), (
            f"request {i}: batched != solo (bit-identity)"
        )
        assert np.array_equal(outs[i], _reference(model, p, 8)), (
            f"request {i}: engine != dense-cache generate() oracle"
        )


def test_warmed_engine_zero_steady_state_compiles(model, engine):
    from tensorframes_tpu.ops.executor import _JIT_MISSES

    cfg, _ = model
    prompts = _prompts(10, 3, 16, seed=23, vocab=cfg.vocab_size)
    # pipeline through every phase once (module fixture already did,
    # but be independent of test order)
    engine.call({"prompt": prompts[0]}, timeout=300)
    miss0 = _JIT_MISSES.value
    futs = []
    for i, p in enumerate(prompts):  # staggered join/leave mix
        futs.append(engine.submit({"prompt": p}))
        if i % 3 == 0:
            futs[0].rows  # no-op; keep the submit loop non-uniform
            time.sleep(0.003)
    for f in futs:
        f.result(300)
    assert int(_JIT_MISSES.value - miss0) == 0, (
        "warmed decode engine hit XLA in steady state"
    )


def test_variable_max_new_tokens_per_request(model, engine):
    cfg, _ = model
    p = _prompts(1, 5, 10, seed=31, vocab=cfg.vocab_size)[0]
    out3 = engine.call({"prompt": p, "max_new_tokens": 3}, timeout=300)
    out8 = engine.call({"prompt": p, "max_new_tokens": 8}, timeout=300)
    assert out3["tokens"].shape == (1, 3)
    assert out8["tokens"].shape == (1, 8)
    # same greedy path: the shorter request is a prefix of the longer
    assert np.array_equal(out3["tokens"][0], out8["tokens"][0, :3])


# ---------------------------------------------------------------------------
# Preemption / eviction under an undersized pool (acceptance)
# ---------------------------------------------------------------------------

def test_undersized_pool_preempts_evicts_and_completes(model):
    cfg, params = model
    # horizon 16+8=24 -> 3 pages of 8; pool holds one horizon + 1 spare
    eng = DecodeEngine("t_small_pool", cfg, params, DecodeConfig(
        max_slots=4, page_size=8, num_pages=5,
        max_prompt_len=16, max_new_tokens=8,
    ))
    eng.start()
    try:
        pre0 = sm.DECODE_PREEMPTIONS.value
        ev0 = sm.DECODE_EVICTIONS.value
        tok0 = sm.DECODE_TOKENS.value
        prompts = _prompts(5, 12, 16, seed=41, vocab=cfg.vocab_size)
        futs = [eng.submit({"prompt": p}) for p in prompts]
        outs = [f.result(600)["tokens"] for f in futs]
        assert sm.DECODE_PREEMPTIONS.value - pre0 > 0, (
            "undersized pool never preempted"
        )
        assert sm.DECODE_EVICTIONS.value - ev0 > 0
        # replayed resume tokens are recompute, not progress: the
        # fresh-token counter must see exactly requests × new tokens
        # even across (repeated) preemptions
        assert sm.DECODE_TOKENS.value - tok0 == 5 * 8
        # none lost, and every preempted/resumed request is
        # bit-identical to the never-preempted oracle
        assert len(outs) == len(prompts)
        for p, o in zip(prompts, outs):
            assert np.array_equal(o, _reference(model, p, 8)), (
                "preempted request did not resume bit-identically"
            )
    finally:
        eng.stop(drain=True, timeout=300)
    eng.pool.check()
    assert eng.pool.num_free == eng.pool.usable_pages


def test_minimal_pool_forward_progress_no_livelock(model):
    cfg, params = model
    # the floor configuration: exactly one full horizon of pages —
    # maximum preemption pressure; completion proves no livelock
    eng = DecodeEngine("t_floor_pool", cfg, params, DecodeConfig(
        max_slots=3, page_size=4, num_pages=5,
        max_prompt_len=8, max_new_tokens=8,
    ))
    eng.start()
    try:
        prompts = _prompts(4, 6, 8, seed=43, vocab=cfg.vocab_size)
        futs = [eng.submit({"prompt": p}) for p in prompts]
        outs = [f.result(600)["tokens"] for f in futs]
        for p, o in zip(prompts, outs):
            assert np.array_equal(o, _reference(model, p, 8))
    finally:
        eng.stop(drain=True, timeout=300)
    eng.pool.check()


# ---------------------------------------------------------------------------
# Slot-wait deadlines + admission taxonomy (satellite)
# ---------------------------------------------------------------------------

def test_full_pool_cannot_hold_request_past_deadline(model):
    """The ISSUE 11 satellite: a request waiting for a free slot/pages
    expires on the CLOCK (the pull-mode batcher's expirer covers the
    slot-wait queue) — a full pool is not a hang."""
    cfg, params = model
    eng = DecodeEngine("t_deadline", cfg, params, DecodeConfig(
        max_slots=2, page_size=4, max_prompt_len=8, max_new_tokens=4,
    ))
    eng.start()
    try:
        # deterministically exhaust the pool from the outside while the
        # engine is idle: no join can find prompt pages
        hogs = _hog_pool(eng.pool)
        d0 = sm.DEADLINE_EXPIRED.value
        fut = eng.submit(
            {"prompt": np.arange(5, dtype=np.int32)}, deadline_s=0.2
        )
        t0 = time.perf_counter()
        with pytest.raises(DeadlineExceededError):
            fut.result(10)
        assert time.perf_counter() - t0 < 5.0
        assert sm.DEADLINE_EXPIRED.value - d0 >= 1
        # the engine is healthy: free the pages, the next request runs
        _unhog_pool(eng.pool, hogs)
        out = eng.call(
            {"prompt": np.arange(5, dtype=np.int32)}, timeout=300
        )
        assert out["tokens"].shape == (1, 4)
    finally:
        eng.stop(drain=True, timeout=120)


def test_admission_taxonomy_and_validation(model):
    cfg, params = model
    eng = DecodeEngine("t_taxonomy", cfg, params, DecodeConfig(
        max_slots=1, page_size=4, max_prompt_len=8, max_new_tokens=4,
        max_queue_requests=2, warmup=False,
    ))
    # closed before start
    with pytest.raises(RejectedError) as ri:
        eng.submit({"prompt": np.arange(3, dtype=np.int32)})
    assert ri.value.reason == "closed"
    eng.start()
    try:
        # malformed feeds
        with pytest.raises(ValidationError):
            eng.submit([1, 2, 3])
        with pytest.raises(ValidationError):
            eng.submit({"tokens": [1, 2]})
        with pytest.raises(ValidationError):
            eng.submit({"prompt": [1, 2], "temperature": 0.5})
        with pytest.raises(ValidationError):
            eng.submit({"prompt": []})
        with pytest.raises(ValidationError):
            eng.submit({"prompt": [[1, 2], [3, 4]]})
        with pytest.raises(ValidationError):
            eng.submit({"prompt": [0, cfg.vocab_size]})
        with pytest.raises(ValidationError):
            eng.submit({"prompt": [1], "max_new_tokens": 0})
        with pytest.raises(ValueError):
            eng.submit({"prompt": [1]}, deadline_s=0.0)
        # oversized prompt: too_large, counted
        with pytest.raises(RejectedError) as ri:
            eng.submit({"prompt": np.zeros(9, np.int32)})
        assert ri.value.reason == "too_large"
        # queue_full: exhaust the pool so nothing joins, then overfill
        hogs = _hog_pool(eng.pool)
        futs = [eng.submit({"prompt": np.arange(4, dtype=np.int32)})
                for _ in range(2)]
        with pytest.raises(RejectedError) as ri:
            eng.submit({"prompt": np.arange(4, dtype=np.int32)})
        assert ri.value.reason == "queue_full"
        _unhog_pool(eng.pool, hogs)
        for f in futs:
            assert f.result(300)["tokens"].shape == (1, 4)
    finally:
        eng.stop(drain=True, timeout=120)
    # closed after stop
    with pytest.raises(RejectedError) as ri:
        eng.submit({"prompt": np.arange(3, dtype=np.int32)})
    assert ri.value.reason == "closed"


def test_stop_without_drain_fails_loudly(model):
    cfg, params = model
    eng = DecodeEngine("t_nodrain", cfg, params, DecodeConfig(
        max_slots=1, page_size=4, max_prompt_len=8, max_new_tokens=4,
        warmup=False,
    ))
    _hog_pool(eng.pool)  # keep requests queued
    eng.start()
    futs = [eng.submit({"prompt": np.arange(4, dtype=np.int32)})
            for _ in range(2)]
    eng.stop(drain=False, timeout=60)
    for f in futs:
        with pytest.raises(ServingError):
            f.result(10)


def test_admission_budget_reads_the_pool_when_a_request_heads_the_queue(
    model,
):
    """The budget of one poll is the pool as it is when the head request
    is judged, not as it was when the poll was set up: pages that left
    the pool before a request was offered can never be promised to it
    (the hog idiom of these tests, from another thread)."""
    cfg, params = model
    eng = DecodeEngine("t_budget", cfg, params, DecodeConfig(
        max_slots=1, page_size=4, max_prompt_len=8, max_new_tokens=4,
        warmup=False,
    ))
    can_take = eng._admit_budget()
    hogs = _hog_pool(eng.pool)
    eng._admission.start()
    try:
        eng._admission.offer(
            eng.validate_feeds({"prompt": np.arange(4, dtype=np.int32)}),
            1, None,
        )
        assert eng._admission.poll(1, can_take=can_take) == []
        _unhog_pool(eng.pool, hogs)
        assert len(eng._admission.poll(
            1, can_take=eng._admit_budget())) == 1
    finally:
        eng._admission.stop(drain=False, timeout=10)
        eng.stop()


def test_join_failure_answers_the_request_in_hand(model):
    """A request polled out of the queue whose join raises is in no
    slot and no queue: the crash guard must still answer it."""
    cfg, params = model
    eng = DecodeEngine("t_joinfail", cfg, params, DecodeConfig(
        max_slots=1, page_size=4, max_prompt_len=8, max_new_tokens=4,
        warmup=False,
    ))

    def broken(*args):
        raise RuntimeError("prefill broke (test)")

    # the transformer's joins go through the packed prefill
    assert eng._packed_prefill is not None
    eng._prefill = eng._packed_prefill = broken
    eng.start()
    try:
        # one request: the failure closes admission, so a second submit
        # would race it (answered, or rejected as closed)
        fut = eng.submit({"prompt": np.arange(4, dtype=np.int32)})
        with pytest.raises(ServingError, match="prefill broke"):
            fut.result(10)
    finally:
        eng.stop(drain=False, timeout=60)


def test_decode_steps_leave_plan_stats_alone(model, tmp_path):
    """Serving with a compile-cache directory configured: no decode
    step creates or touches ``planstats/strategy_walls.json`` or counts
    a sidecar store (the engine times nothing to choose a kernel)."""
    from tensorframes_tpu.observability.metrics import REGISTRY
    from tensorframes_tpu.plan import stats as plan_stats

    def stores():
        return sum(
            d["value"] for d in REGISTRY.snapshot()
            if d["name"] == "tftpu_plan_reopt_sidecar_total"
            and d["labels"].get("event") == "store"
        )

    cfg, params = model
    was = tfs.configure().compilation_cache_dir
    tfs.configure(compilation_cache_dir=str(tmp_path))
    try:
        plan_stats.clear_memory()
        n0 = stores()
        eng = DecodeEngine("t_nostats", cfg, params, DecodeConfig(
            max_slots=2, page_size=4, max_prompt_len=8, max_new_tokens=4,
        ))
        eng.start()
        try:
            steps0 = sm.DECODE_STEPS["decode"].value
            for plen in (3, 5, 8):
                out = eng.call(
                    {"prompt": np.arange(plen, dtype=np.int32)}, timeout=300
                )
                assert out["tokens"].shape == (1, 4)
            assert sm.DECODE_STEPS["decode"].value - steps0 >= 9
        finally:
            eng.stop(drain=True, timeout=120)
        assert stores() == n0
        assert not (tmp_path / "planstats" / "strategy_walls.json").exists()
        assert plan_stats.strategy_walls("decode_attention") == {}
    finally:
        tfs.configure(compilation_cache_dir=was)
        plan_stats.clear_memory()


def test_engine_config_validation(model):
    cfg, params = model
    with pytest.raises(ValueError):
        DecodeEngine("t_bad", cfg, params, DecodeConfig(
            max_prompt_len=40, max_new_tokens=40,  # > max_seq_len=48
        ))
    with pytest.raises(ValueError):
        DecodeEngine("t_bad2", cfg, params, DecodeConfig(max_slots=0))


# ---------------------------------------------------------------------------
# Server integration + HTTP
# ---------------------------------------------------------------------------

def test_register_decode_server_and_http(model):
    cfg, params = model
    srv = Server(ServingConfig(max_batch_rows=8))
    eng = srv.register_decode("gen", cfg, params, DecodeConfig(
        max_slots=2, page_size=4, max_prompt_len=8, max_new_tokens=4,
    ))
    with pytest.raises(ValueError):
        srv.register_decode("gen", cfg, params)  # name collision
    srv.start()
    httpd = serve_http(srv, port=0)
    port = httpd.server_address[1]
    try:
        assert srv.endpoints() == ["gen"]
        out = srv.call("gen", {"prompt": [1, 2, 3]}, timeout=300)
        assert out["tokens"].shape == (1, 4)
        body = json.dumps({"inputs": {"prompt": [1, 2, 3]}}).encode()
        r = urllib.request.urlopen(
            urllib.request.Request(
                f"http://127.0.0.1:{port}/v1/gen", body,
                {"Content-Type": "application/json"},
            ),
            timeout=120,
        )
        assert r.status == 200
        payload = json.loads(r.read())
        # streaming-final: ONE reply carrying the whole sequence,
        # bit-identical to the in-process call
        assert payload["outputs"]["tokens"] == out["tokens"].tolist()
        h = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=30
        ).read())
        assert h["running"] is True
        assert h["decode"]["gen"]["running_slots"] == 0
        assert h["decode"]["gen"]["free_pages"] == eng.pool.usable_pages
        # 504 taxonomy on slot-wait expiry
        hogs = _hog_pool(eng.pool)
        body = json.dumps({
            "inputs": {"prompt": [1, 2, 3]}, "deadline_s": 0.2,
        }).encode()
        with pytest.raises(urllib.error.HTTPError) as he:
            urllib.request.urlopen(
                urllib.request.Request(
                    f"http://127.0.0.1:{port}/v1/gen", body,
                    {"Content-Type": "application/json"},
                ),
                timeout=120,
            )
        assert he.value.code == 504
        _unhog_pool(eng.pool, hogs)
    finally:
        httpd.shutdown()
        srv.stop(drain=True, timeout=120)


def test_register_decode_name_clash_with_flush_endpoint(model):
    cfg, params = model
    srv = Server(ServingConfig(max_batch_rows=8, warmup=False))
    schema = tfs.Schema([tfs.ColumnInfo(
        "x", tfs.dtypes.float32, tfs.Shape((tfs.Unknown, 4))
    )])
    holder = type("F", (), {"schema": schema})()
    import jax.numpy as jnp

    srv.register(
        "score", tfs.compile_program(
            lambda x: {"y": jnp.tanh(x)}, holder, block=False
        ),
    )
    with pytest.raises(ValueError):
        srv.register_decode("score", cfg, params)


# ---------------------------------------------------------------------------
# Donation: the pool is written in place (PR 26)
# ---------------------------------------------------------------------------

def test_step_donates_the_pool_columns(model):
    """The columns handed to a step are gone — deleted by the call —
    and the pool holds what came back: nothing can read a stale pool,
    and no second pool is ever resident."""
    cfg, params = model
    eng = DecodeEngine("t_donate", cfg, params, DecodeConfig(
        max_slots=2, page_size=8, max_prompt_len=16, max_new_tokens=8,
        warmup=False,
    ))
    pool = eng.pool
    old = dict(pool.columns)
    maxp = pool.max_pages_per_seq
    cols, nxt = eng._run_step(
        params, pool.columns, np.zeros(2, np.int32),
        np.zeros(2, np.int32), np.zeros((2, maxp), np.int32),
    )
    assert all(c.is_deleted() for c in old.values())
    assert not any(c.is_deleted() for c in cols.values())
    assert np.asarray(nxt).shape == (2,)
    with pytest.raises(RuntimeError):
        np.asarray(old["k"])  # "the columns you passed are gone"
    # prefill donates too
    pool.columns = cols
    pool.columns, _first = eng._prefill(
        params, pool.columns, np.zeros(8, np.int32), np.int32(1),
        pool.null_table(),
    )
    assert all(c.is_deleted() for c in cols.values())
    eng.stop()


@pytest.mark.parametrize("tiers", [
    {}, {"prefix_cache": True}, {"kv_swap": True},
    {"prefix_cache": True, "kv_swap": True},
])
def test_warmup_ladder_survives_donation(model, tiers):
    """The warm-up ladder threads the donated pool through every
    program of the grid (prefill and step buckets, suffix prefill,
    copy-on-extend, swap extract/restore): the engine comes up holding
    live columns and serves bit-identically to the oracle."""
    cfg, params = model
    eng = DecodeEngine("t_warm_donate", cfg, params, DecodeConfig(
        max_slots=2, page_size=8, max_prompt_len=16, max_new_tokens=4,
        **tiers,
    ))
    eng.start()
    try:
        assert not any(c.is_deleted() for c in eng.pool.columns.values())
        prompt = _prompts(1, 9, 15, seed=26, vocab=cfg.vocab_size)[0]
        got = eng.submit({"prompt": prompt}).result(120)["tokens"]
        np.testing.assert_array_equal(got, _reference(model, prompt, 4))
    finally:
        eng.stop(drain=True, timeout=120)
    eng.pool.check()


def test_step_memory_gauges_say_the_pool_is_aliased(model):
    """``tftpu_decode_step_alias_bytes`` = the pool's bytes (every
    column written in place) and ``tftpu_decode_step_temp_bytes`` is
    set, both read from the widest step executable's memory plan. (At
    this toy size on the CPU the interpreter's temporaries exceed the
    pool; tests/test_decode_compile.py holds the real step to "well
    under one pool".)"""
    cfg, params = model
    eng = DecodeEngine("t_gauges", cfg, params, DecodeConfig(
        max_slots=4, page_size=8, max_prompt_len=16, max_new_tokens=8,
    ))
    eng.start()
    try:
        assert eng._step_memory_bucket == eng._slot_buckets[-1]
        assert sm.DECODE_STEP_ALIAS_BYTES.value == gen.paged_kv_nbytes(
            eng.pool.columns)
        assert sm.DECODE_STEP_TEMP_BYTES.value > 0
    finally:
        eng.stop(drain=True, timeout=120)


# ---------------------------------------------------------------------------
# Observability
# ---------------------------------------------------------------------------

def test_decode_metrics_preregistered():
    from tensorframes_tpu.observability.metrics import REGISTRY

    names = {m.name for m in REGISTRY.collect()}
    for want in (
        "tftpu_decode_tokens_total",
        "tftpu_decode_steps_total",
        "tftpu_decode_ttft_seconds",
        "tftpu_decode_slot_occupancy",
        "tftpu_decode_free_pages",
        "tftpu_decode_preemptions_total",
        "tftpu_decode_evictions_total",
        "tftpu_decode_step_alias_bytes",
        "tftpu_decode_step_temp_bytes",
    ):
        assert want in names, f"{want} not pre-registered"
    assert set(sm.DECODE_STEPS) == {"prefill", "decode"}


def test_decode_flight_records(model):
    from tensorframes_tpu.observability import flight

    cfg, params = model
    eng = DecodeEngine("t_flight", cfg, params, DecodeConfig(
        max_slots=1, page_size=4, max_prompt_len=8, max_new_tokens=2,
    ))
    eng.start()
    try:
        eng.call({"prompt": [1, 2, 3]}, timeout=300)
    finally:
        eng.stop(drain=True, timeout=120)
    kinds = [r["kind"] for r in flight.RECORDER.records()
             if str(r.get("kind", "")).startswith("serving.decode")]
    for want in ("serving.decode.start", "serving.decode.join",
                 "serving.decode.finish", "serving.decode.stop"):
        assert want in kinds, f"missing flight record {want}"


# ---------------------------------------------------------------------------
# KV memory hierarchy (ISSUE 19): prefix cache + per-sequence host swap
# ---------------------------------------------------------------------------

def test_kvpool_property_sweep_swap_and_prefix_interleaved(model, tmp_path):
    """Random interleaving of swap_out/swap_in/prefix-share/copy-on-
    extend with join/extend/leave — the extended three-way partition
    (free + exclusive + shared-with-refcount) must hold after EVERY op,
    and draining everything returns the pool to fully allocatable."""
    from tensorframes_tpu.blockstore import BlockStore

    cfg, _ = model
    ps = 4
    pool = PagedKVPool(cfg.served_model(ps, ps * 6),
                       num_pages=33, page_size=ps)
    store = BlockStore(root=str(tmp_path / "swap"), budget_bytes=0)
    rng = np.random.default_rng(19)
    vocab = 40
    # joins draw from shared templates so page-granular prefixes really
    # repeat (pure random prompts would never collide at 4 tokens)
    templates = [
        rng.integers(0, vocab, (ps * 4,)).astype(np.int32)
        for _ in range(3)
    ]
    live = {}      # seq -> prompt tokens
    swapped = []   # swap snapshots (with their prompt riding along)
    next_seq = [0]

    def fresh_seq():
        next_seq[0] += 1
        return next_seq[0] - 1

    ops = 0
    hits = cows = outs = resumes = published = 0
    for _ in range(650):
        op = int(rng.integers(0, 7))
        if op == 0:  # join, riding the prefix cache when it matches
            t = templates[int(rng.integers(0, len(templates)))]
            plen = int(rng.integers(1, ps * 4 + 1))
            cut = int(rng.integers(0, plen + 1))
            tokens = np.concatenate([
                t[:cut],
                rng.integers(0, vocab, (plen - cut,)).astype(np.int32),
            ]).astype(np.int32)
            need = pool.pages_needed(plen)
            if pool.num_allocatable < need:
                continue
            seq = fresh_seq()
            matched, covered, cow, _r = pool.prefix_match(tokens)
            if matched:
                pool.prefix_acquire(seq, matched)
                hits += 1
            if cow is not None:
                pool.copy_on_extend(seq, cow)
                cows += 1
            else:
                pool.alloc(seq, need - len(matched))
            if rng.integers(0, 2):
                published += pool.publish_prefix(seq, tokens)
            live[seq] = tokens
        elif op == 1 and live:  # extend (a decode step crossed a page)
            seq = int(rng.choice(sorted(live)))
            if (len(pool.seq_pages(seq)) < pool.max_pages_per_seq
                    and pool.num_allocatable >= 1):
                pool.alloc(seq, 1)
        elif op == 2 and live:  # leave (finish / evict-without-swap)
            seq = int(rng.choice(sorted(live)))
            pool.free_seq(seq)
            del live[seq]
        elif op == 3 and live:  # preempt with host-swap
            seq = int(rng.choice(sorted(live)))
            npg = len(pool.seq_pages(seq))
            block = {"payload": np.full((npg, 3), seq, np.int32)}
            snap = pool.swap_out_seq(store, seq, block)
            assert int(snap["pages"]) == npg
            snap["tokens"] = live.pop(seq)
            swapped.append(snap)
            outs += 1
        elif op == 4 and swapped:  # swap-resume under a fresh seq id
            snap = swapped.pop(int(rng.integers(0, len(swapped))))
            if pool.num_allocatable < int(snap["pages"]):
                swapped.append(snap)
                continue
            seq = fresh_seq()
            pages, block = pool.swap_in_seq(store, snap, seq)
            assert len(pages) == int(snap["pages"])
            assert block["payload"].shape == (len(pages), 3)
            live[seq] = snap["tokens"]
            resumes += 1
        elif op == 5 and live:  # publish again (idempotent at collisions)
            seq = int(rng.choice(sorted(live)))
            published += pool.publish_prefix(seq, live[seq])
        elif op == 6 and pool.num_allocatable >= 2:  # pressure burst
            seq = fresh_seq()
            pool.alloc(seq, 2)
            live[seq] = np.zeros(0, np.int32)
        pool.check()
        ops += 1
    assert ops >= 500
    # the sweep actually exercised every new op at least once
    assert hits > 0 and cows > 0 and outs > 0 and resumes > 0
    assert published > 0
    # drain: every page comes back, swap segments drop cleanly
    for seq in sorted(live):
        pool.free_seq(seq)
    for snap in swapped:
        store.drop(snap["ref"])
    pool.check()
    assert pool.num_allocatable == pool.usable_pages
    # cached refcount-0 shared pages reclaim under real demand
    big = fresh_seq()
    pool.alloc(big, pool.max_pages_per_seq)
    pool.check()
    pool.free_seq(big)
    store.close()


def test_kvpool_swap_misuse_raises(model, tmp_path):
    from tensorframes_tpu.blockstore import BlockStore

    cfg, _ = model
    pool = PagedKVPool(cfg.served_model(4, 4 * 4),
                       num_pages=9, page_size=4)
    store = BlockStore(root=str(tmp_path / "swap"), budget_bytes=0)
    with pytest.raises(PoolAccountingError):
        pool.swap_out_seq(store, 7, {"x": np.zeros((1, 2), np.int8)})
    pool.alloc(1, 2)
    snap = pool.swap_out_seq(
        store, 1, {"x": np.zeros((2, 2), np.int8)}
    )
    other = PagedKVPool(cfg.served_model(8, 8 * 4),
                        num_pages=9, page_size=8)
    with pytest.raises(PoolAccountingError):
        other.swap_in_seq(store, snap, 1)  # page-size mismatch
    pages, _ = pool.swap_in_seq(store, snap, 2)
    assert len(pages) == 2
    pool.free_seq(2)
    pool.check()
    store.close()


def test_prefix_cache_hits_bit_identical_and_counted(model):
    """Cold -> exact repeat (copy-on-extend) -> shared-page + fresh
    suffix (suffix prefill): every reply bit-identical to the dense
    oracle, hits counted, zero steady-state compiles."""
    from tensorframes_tpu.ops.executor import _JIT_MISSES

    cfg, params = model
    eng = DecodeEngine("t_prefix", cfg, params, DecodeConfig(
        max_slots=4, page_size=8, max_prompt_len=16, max_new_tokens=8,
        prefix_cache=True,
    ))
    eng.start()
    try:
        h0 = sm.PREFIX_HITS.value
        miss0 = _JIT_MISSES.value
        rng = np.random.default_rng(53)
        shared = rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32)
        cold = eng.call({"prompt": shared}, timeout=300)["tokens"]
        assert np.array_equal(cold, _reference(model, shared, 8))
        # exact repeat: whole-prompt reuse through copy-on-extend
        hot = eng.call({"prompt": shared}, timeout=300)["tokens"]
        assert np.array_equal(hot, cold)
        # shared first page, fresh suffix: suffix-only prefill
        p2 = np.concatenate([
            shared[:8],
            rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32),
        ])
        out2 = eng.call({"prompt": p2}, timeout=300)["tokens"]
        assert np.array_equal(out2, _reference(model, p2, 8))
        assert sm.PREFIX_HITS.value - h0 >= 2
        snap = eng.counters()
        assert snap["prefix_hits"] >= 2
        assert snap["shared_pages"] > 0
        assert int(_JIT_MISSES.value - miss0) == 0, \
            "prefix-cache path compiled in steady state"
        assert eng.pool.num_shared > 0
    finally:
        eng.stop(drain=True, timeout=300)
    eng.pool.check()


def test_swap_resume_undersized_pool_bit_identical(model, tmp_path):
    """kv_swap on an undersized pool: preemptions swap out instead of
    discarding, resumes restore pages instead of replaying, and every
    request still completes bit-identically to the dense oracle."""
    cfg, params = model
    new = 8
    eng = DecodeEngine("t_swap", cfg, params, DecodeConfig(
        max_slots=4, page_size=8, num_pages=1 + 2 * 3,
        max_prompt_len=16, max_new_tokens=new,
        kv_swap=True, swap_dir=str(tmp_path / "swap"),
    ))
    eng.start()
    try:
        o0, r0 = sm.KVSWAP_OUTS.value, sm.KVSWAP_RESUMES.value
        f0 = sm.KVSWAP_FALLBACKS.value
        t0 = sm.DECODE_TOKENS.value
        prompts = _prompts(8, 9, 16, seed=61, vocab=cfg.vocab_size)
        futs = [eng.submit({"prompt": p}) for p in prompts]
        outs = [f.result(600)["tokens"] for f in futs]
        for p, o in zip(prompts, outs):
            assert np.array_equal(o, _reference(model, p, new))
        assert sm.KVSWAP_OUTS.value - o0 > 0
        assert sm.KVSWAP_RESUMES.value - r0 > 0
        assert sm.KVSWAP_FALLBACKS.value - f0 == 0
        # swap resume regenerates nothing: fresh tokens only, once each
        assert sm.DECODE_TOKENS.value - t0 == len(prompts) * new
        snap = eng.counters()
        assert snap["swap_outs"] > 0 and snap["swap_resumes"] > 0
    finally:
        eng.stop(drain=True, timeout=600)
    eng.pool.check()
    assert eng.pool.num_free == eng.pool.usable_pages


def test_corrupted_swap_segment_counted_fallback_bit_identical(
    model, tmp_path
):
    """Flip a byte in every swap segment as it lands: swap-in hits a
    real CRC failure, the engine falls back to recompute-replay (the
    counted path), and NO request is lost — outputs stay bit-identical
    to the oracle."""
    import os

    cfg, params = model
    new = 8
    eng = DecodeEngine("t_swapcorrupt", cfg, params, DecodeConfig(
        max_slots=4, page_size=8, num_pages=1 + 2 * 3,
        max_prompt_len=16, max_new_tokens=new,
        kv_swap=True, swap_dir=str(tmp_path / "swap"),
    ))
    eng.start()
    store = eng._swap_store
    orig_put = store.put_spilled

    def corrupting_put(block):
        ref = orig_put(block)
        seg = store._seg_dir(ref.block_id)
        for fn in sorted(os.listdir(seg)):
            if fn.endswith(".bin"):
                path = os.path.join(seg, fn)
                with open(path, "r+b") as f:
                    b = f.read(1)
                    f.seek(0)
                    f.write(bytes([b[0] ^ 0xFF]))
                break
        return ref

    store.put_spilled = corrupting_put
    try:
        o0 = sm.KVSWAP_OUTS.value
        f0 = sm.KVSWAP_FALLBACKS.value
        r0 = sm.KVSWAP_RESUMES.value
        prompts = _prompts(8, 9, 16, seed=67, vocab=cfg.vocab_size)
        futs = [eng.submit({"prompt": p}) for p in prompts]
        outs = [f.result(600)["tokens"] for f in futs]
        assert sm.KVSWAP_OUTS.value - o0 > 0
        assert sm.KVSWAP_FALLBACKS.value - f0 > 0, \
            "corruption never engaged the counted fallback"
        assert sm.KVSWAP_RESUMES.value - r0 == 0
        for p, o in zip(prompts, outs):
            assert np.array_equal(o, _reference(model, p, new))
        assert eng.counters()["swap_fallbacks"] > 0
    finally:
        eng.stop(drain=True, timeout=600)
    eng.pool.check()


def test_swap_segments_survive_engine_restart(model, tmp_path):
    """PR 18 follow-up: a hard stop PARKS pending keyed swap segments
    instead of dropping them, spill() folds them into the whole-pool
    snapshot, and a FRESH engine restore()s them — redriven requests
    (same trace ids) resume through the counted swap-in path and every
    output stays bit-identical to the dense oracle."""
    from tensorframes_tpu.blockstore import BlockStore
    from tensorframes_tpu.observability import context as _ctx

    cfg, params = model
    new = 8

    def mk(name, swap_dir):
        return DecodeEngine(name, cfg, params, DecodeConfig(
            max_slots=4, page_size=8, num_pages=1 + 2 * 3,
            max_prompt_len=16, max_new_tokens=new,
            kv_swap=True, swap_dir=swap_dir,
        ))

    prompts = _prompts(8, 9, 16, seed=71, vocab=cfg.vocab_size)

    def drive(eng):
        futs = []
        for i, p in enumerate(prompts):
            with _ctx.request_scope(f"restart-{i}"):
                futs.append(eng.submit({"prompt": p}))
        return futs

    # catch the engine with at least one sequence swapped out: the
    # undersized pool preempts continuously, but a swap entry is
    # transient (it rejoins), so retry the hard stop until one is
    # pending at the instant the loop sees the stop flag
    eng = None
    for attempt in range(8):
        eng = mk(f"t_swapstop{attempt}",
                 str(tmp_path / f"swap{attempt}"))
        eng.start()
        drive(eng)
        deadline = time.time() + 120
        while time.time() < deadline and not eng._swap:
            time.sleep(0.001)
        eng.stop(drain=False, timeout=300)
        if eng._swap_parked:
            break
        eng.pool.check()
    assert eng._swap_parked, \
        "never caught a pending swapped sequence across 8 hard stops"

    st = BlockStore(root=str(tmp_path / "handoff"), budget_bytes=0)
    snap = eng.spill(st)
    assert len(snap["swapped"]) == len(set(snap["swapped"]))
    assert snap["swapped"], "spill() dropped the parked segments"
    assert eng._swap_store is None  # spill() closed the donor store

    eng2 = mk("t_swaprestored", str(tmp_path / "swap-b"))
    eng2.start()
    try:
        adopted = eng2.restore(st, snap)
        assert adopted == len(snap["swapped"])
        r0 = sm.KVSWAP_RESUMES.value
        outs = [f.result(600)["tokens"] for f in drive(eng2)]
        for p, o in zip(prompts, outs):
            assert np.array_equal(o, _reference(model, p, new))
        # at least one redriven request resumed from its restored
        # segment (the rest decode fresh — their segments were
        # consumed or never swapped)
        assert sm.KVSWAP_RESUMES.value - r0 > 0
        assert not eng2._swap_restored  # all adopted entries consumed
    finally:
        eng2.stop(drain=True, timeout=600)
    eng2.pool.check()
    assert eng2.pool.num_free == eng2.pool.usable_pages
    st.close()


def test_tfg113_prefix_cache_ineligible_diagnostic(model):
    """Repeated prompt prefixes on an engine with the cache OFF leave
    store_unarmed evidence while the engine runs; lint_plan surfaces
    it as TFG113 with the arm-the-cache fix; stopping the engine
    withdraws its evidence (a stopped endpoint's config can no longer
    be fixed — and later lint tests in this process stay clean)."""
    from tensorframes_tpu.serving import decode as dec

    cfg, params = model

    def lint():
        fr = tfs.frame_from_arrays(
            {"x": np.arange(8, dtype=np.float32)}
        )
        f2 = tfs.map_blocks(lambda x: {"y": x + 1.0}, fr)
        return tfs.lint_plan(f2)

    eng = DecodeEngine("t_tfg113", cfg, params, DecodeConfig(
        max_slots=2, page_size=8, max_prompt_len=16,
        max_new_tokens=2,
    ))
    eng.start()
    try:
        rng = np.random.default_rng(59)
        p = rng.integers(0, cfg.vocab_size, (12,)).astype(np.int32)
        eng.call({"prompt": p}, timeout=300)
        # one miss is not evidence...
        assert not any(
            e["reason"] == "store_unarmed"
            and e["endpoint"] == "t_tfg113"
            for e in dec.prefix_cache_events()
        )
        eng.call({"prompt": p.copy()}, timeout=300)
        # ...an OBSERVED repeat of the first page is
        evs = dec.prefix_cache_events()
        assert any(
            e["reason"] == "store_unarmed"
            and e["endpoint"] == "t_tfg113" for e in evs
        )
        found = lint().by_code("TFG113")
        assert found, "lint_plan did not surface TFG113"
        mine = [d for d in found if d.subject == "t_tfg113"]
        assert mine, "TFG113 finding not bound to the endpoint"
        assert "prefix_cache=True" in mine[0].fix
        assert "docs/analysis.md#tfg113" in mine[0].explain()
    finally:
        eng.stop(drain=True, timeout=300)
    # stop() withdrew the endpoint's evidence: later lints are clean
    assert not any(
        e["endpoint"] == "t_tfg113" for e in dec.prefix_cache_events()
    )
    assert not any(
        d.subject == "t_tfg113" for d in lint().by_code("TFG113")
    )


def test_kvswap_prefix_metrics_preregistered():
    from tensorframes_tpu.observability.metrics import REGISTRY

    names = {m.name for m in REGISTRY.collect()}
    for want in (
        "tftpu_kvswap_out_total",
        "tftpu_kvswap_resume_total",
        "tftpu_kvswap_fallback_total",
        "tftpu_kvswap_bytes_total",
        "tftpu_prefix_cache_hits_total",
        "tftpu_prefix_cache_misses_total",
        "tftpu_prefix_cache_shared_pages",
        "tftpu_prefix_cache_evictions_total",
    ):
        assert want in names, f"{want} not pre-registered"


# ---------------------------------------------------------------------------
# Packed prefill: a poll's cold joins share one call
# ---------------------------------------------------------------------------

PACK_PAGE = 8


@pytest.fixture(params=["band", "kernel"])
def lowering(request):
    """The packed prefill's attention as each lowering gives it: the XLA
    band fold (this CPU's own) and the packed-attention kernel on the
    pallas interpreter. The program picks one where it is traced."""
    from tensorframes_tpu import kernels
    from tensorframes_tpu.config import get_config

    cfg = get_config()
    was = cfg.pallas_force, cfg.pallas_kernels
    tfs.configure(pallas_force=request.param == "kernel",
                  pallas_kernels=True)
    try:
        assert kernels.selectable("packed_attn") is (
            request.param == "kernel")
        yield request.param
    finally:
        tfs.configure(pallas_force=was[0], pallas_kernels=was[1])


def _packed(model, T, placed, segs=4):
    """Run the packed prefill on a fresh pool: ``placed`` is a list of
    ``(prompt, first row, pages)``. Returns (pool, first tokens)."""
    import jax

    cfg, params = model
    served = cfg.served_model(PACK_PAGE, 24)
    maxp = served.kinds[0].entries
    tokens = np.zeros(T, np.int32)
    start = np.zeros(segs, np.int32)
    length = np.zeros(segs, np.int32)
    tables = np.zeros((segs, maxp), np.int32)
    for b, (prompt, at, pages) in enumerate(placed):
        tokens[at:at + len(prompt)] = prompt
        start[b], length[b] = at, len(prompt)
        tables[b, :len(pages)] = pages
    pool = gen.init_paged_kv(cfg, 32, PACK_PAGE)
    pool, first = jax.jit(served.packed_prefill)(
        params, pool, tokens, start, length, tables)
    return {k: np.asarray(v) for k, v in pool.items()}, np.asarray(first)


@pytest.mark.parametrize("T,at", [(256, 64), (192, 128), (64, 0)])
def test_packed_prefill_is_the_same_wherever_a_prompt_sits(model, T, at,
                                                           lowering):
    """A prompt packed alone at row 0 and the same prompt among
    neighbours at another block edge, in the same bucket or another, give
    bit for bit the same first token and the same bytes on its pages."""
    cfg, _ = model
    a, p, c = _prompts(3, 5, 16, seed=71, vocab=cfg.vocab_size)
    block = cfg.served_model(PACK_PAGE, 24).pack_block
    mine = [3, 9]                               # p's pages
    alone_pool, alone = _packed(model, 64, [(p, 0, mine)])
    among = [(p, at, mine)]
    if at:
        among.insert(0, (a, at - block, [4]))
    if at + block < T:
        among.append((c, at + block, [11, 12]))
    pool, firsts = _packed(model, T, among)
    assert firsts[[s[1] for s in among].index(at)] == alone[0]
    for name in pool:
        assert np.array_equal(pool[name][mine], alone_pool[name][mine]), name


def test_packed_first_tokens_match_the_one_sequence_prefill(model,
                                                            lowering):
    """The packed program's first tokens are the one-sequence prefill's,
    and the KV it writes on the prompts' positions agrees to the int8
    rounding (codes within one step, scales to float tolerance)."""
    import jax

    cfg, params = model
    served = cfg.served_model(PACK_PAGE, 24)
    maxp = served.kinds[0].entries
    prompts = _prompts(4, 1, 16, seed=72, vocab=cfg.vocab_size)
    placed, pages = [], 1
    for b, p in enumerate(prompts):
        n = -(-len(p) // PACK_PAGE)
        placed.append((p, b * served.pack_block,
                       list(range(pages, pages + n))))
        pages += n
    pool, firsts = _packed(model, 256, placed)
    one = jax.jit(served.prefill)
    ref = gen.init_paged_kv(cfg, 32, PACK_PAGE)
    for b, (p, _at, pg) in enumerate(placed):
        table = np.zeros(maxp, np.int32)
        table[:len(pg)] = pg
        tokens = np.zeros(16, np.int32)
        tokens[:len(p)] = p
        ref, first = one(params, ref, tokens, np.int32(len(p)), table)
        assert int(first) == int(firsts[b])
        rows = np.arange(len(p))
        page, off = np.asarray(pg)[rows // PACK_PAGE], rows % PACK_PAGE
        for name in ("k", "v"):
            diff = (pool[name][page, :, off].astype(int)
                    - np.asarray(ref[name])[page, :, off].astype(int))
            assert np.abs(diff).max() <= 1, name
            np.testing.assert_allclose(
                pool[name + "_scale"][page, :, off],
                np.asarray(ref[name + "_scale"])[page, :, off], rtol=1e-5)


@pytest.mark.parametrize("segments,n,calls", [
    (16, 4, 2),     # a 128-row top bucket: two 64-row blocks a call
    (16, 2, 1),
    (1, 3, 3),      # one prompt a call
])
def test_a_poll_of_cold_joins_is_prefilled_in_few_calls(
        model, monkeypatch, segments, n, calls):
    """An engine whose poll returns ``n`` cold requests makes as many
    prefill dispatches as its calls need: ``PACK_SEGMENTS`` prompts, and
    the packed ladder's top bucket, a call. Read off the two counters;
    each request's first token is the dense oracle's."""
    from tensorframes_tpu.serving import decode as dec

    monkeypatch.setattr(dec, "PACK_SEGMENTS", segments)
    cfg, params = model
    eng = DecodeEngine("t_packed_poll", cfg, params, DecodeConfig(
        max_slots=4, page_size=PACK_PAGE, max_prompt_len=16,
        max_new_tokens=4, warmup=False,
    ))
    assert eng._prefill_buckets == [64, 128]
    prompts = _prompts(n, 3, 16, seed=73, vocab=cfg.vocab_size)
    eng._admission.start()
    try:
        for p in prompts:
            eng._admission.offer(eng.validate_feeds({"prompt": p}), 1, None)
        polled = eng._admission.poll(4, can_take=eng._admit_budget())
        assert len(polled) == n
        steps0 = sm.DECODE_STEPS["prefill"].value
        segs0 = sm.DECODE_PREFILL_SEGMENTS.value
        eng._join_packed(polled)
        assert sm.DECODE_STEPS["prefill"].value - steps0 == calls
        assert sm.DECODE_PREFILL_SEGMENTS.value - segs0 == n
        seated = [s for s in eng._slots if s is not None]
        assert [s.prompt.tolist() for s in seated] == [
            p.tolist() for p in prompts]
        for s, p in zip(seated, prompts):
            assert s.generated == [int(_reference(model, p, 1)[0, 0])]
    finally:
        eng._admission.stop(drain=False, timeout=10)
        eng.stop()


def test_preempted_requests_replay_through_the_packed_prefill(model):
    """Under an undersized pool, preempted requests rejoin through the
    packed prefill with their recorded tokens: the engine checks each
    replayed token (a divergence would fail the request), and every
    answer is the never-preempted oracle's."""
    from tensorframes_tpu.observability import flight

    cfg, params = model
    eng = DecodeEngine("t_packed_replay", cfg, params, DecodeConfig(
        max_slots=4, page_size=8, num_pages=5,
        max_prompt_len=16, max_new_tokens=8,
    ))
    assert eng._packed_prefill is not None
    eng.start()
    try:
        pre0 = sm.DECODE_PREEMPTIONS.value
        err0 = sm.DISPATCH_ERRORS.value
        segs0 = sm.DECODE_PREFILL_SEGMENTS.value
        prompts = _prompts(6, 10, 16, seed=74, vocab=cfg.vocab_size)
        futs = [eng.submit({"prompt": p}) for p in prompts]
        outs = [f.result(600)["tokens"] for f in futs]
        preempted = sm.DECODE_PREEMPTIONS.value - pre0
        assert preempted > 0
        # every join, first or replayed, went through the packed program
        assert sm.DECODE_PREFILL_SEGMENTS.value - segs0 == 6 + preempted
        assert sm.DISPATCH_ERRORS.value == err0
        for p, o in zip(prompts, outs):
            assert np.array_equal(o, _reference(model, p, 8))
    finally:
        eng.stop(drain=True, timeout=300)
    assert not [r for r in flight.RECORDER.records()
                if r.get("kind") == "serving.decode.replay_divergence"
                and r.get("endpoint") == "t_packed_replay"]
    eng.pool.check()


def test_a_prefix_cache_engine_joins_one_prompt_a_call(model):
    """An armed prefix cache routes joins one by one (hits, suffixes,
    copy-on-extend): that engine does not pack and warms the
    one-sequence ladder; the segments counter still grows by one a
    dispatch."""
    from tensorframes_tpu.compilecache import serving_row_buckets

    cfg, params = model
    eng = DecodeEngine("t_prefix_nopack", cfg, params, DecodeConfig(
        max_slots=2, page_size=8, max_prompt_len=16, max_new_tokens=4,
        prefix_cache=True,
    ))
    assert eng._packed_prefill is None
    assert eng._prefill_buckets == serving_row_buckets(16)
    eng.start()
    try:
        steps0 = sm.DECODE_STEPS["prefill"].value
        segs0 = sm.DECODE_PREFILL_SEGMENTS.value
        p = _prompts(1, 9, 15, seed=75, vocab=cfg.vocab_size)[0]
        for _ in range(2):
            out = eng.call({"prompt": p}, timeout=300)["tokens"]
            np.testing.assert_array_equal(out, _reference(model, p, 4))
        assert sm.DECODE_STEPS["prefill"].value - steps0 == 2
        assert sm.DECODE_PREFILL_SEGMENTS.value - segs0 == 2
    finally:
        eng.stop(drain=True, timeout=120)

"""Straggler pallas kernels (ISSUE 12): bit-identity property sweeps
against the reference lowerings (CPU pallas interpreter), cost-model
selection wiring, kernel errors surfacing to the caller (no automatic
retry on another lowering), metrics pre-registration, and the zero-row
edge pins from the bugfix sweep.

Kernel-vs-emulation gates are EXACT equality, not allclose: the
same-tiling plain-jnp emulation is bit-identical by construction, and
the order-free op classes (min/max, integer sums) are bit-identical to
the XLA scatter. The decode-attention kernel folds pages through an
online softmax, so it matches the whole-horizon XLA chain to float
tolerance and its own per-page emulation bitwise. The packed prefill's
attention kernel matches the XLA band fold to float tolerance and gives
a prompt the same bits wherever it sits in the pack.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import tensorframes_tpu as tfs
from tensorframes_tpu import configure
from tensorframes_tpu import kernels
from tensorframes_tpu.kernels import decode_attention as kda
from tensorframes_tpu.kernels import segment_reduce as ksr
from tensorframes_tpu.observability.metrics import REGISTRY
from tensorframes_tpu.ops import segment
from tensorframes_tpu.plan import rules as prules


@pytest.fixture
def forced():
    """Select the kernels on CPU (interpreter). Also pins
    ``pallas_kernels=True`` so the selection tests stay meaningful
    under the CI kernels-off smoke (``TFTPU_PALLAS=0``) — these tests
    exercise the kernels themselves; the off-smoke's point is the
    suites that merely COULD select them."""
    from tensorframes_tpu.config import get_config

    cfg = get_config()
    was_force, was_kernels = cfg.pallas_force, cfg.pallas_kernels
    configure(pallas_force=True, pallas_kernels=True)
    try:
        yield
    finally:
        configure(pallas_force=was_force, pallas_kernels=was_kernels)


def _assert_eq(a, b, msg):
    assert a.dtype == b.dtype, (msg, a.dtype, b.dtype)
    assert a.shape == b.shape, (msg, a.shape, b.shape)
    np.testing.assert_array_equal(a, b, err_msg=msg)


# ---------------------------------------------------------------------------
# segment reduce
# ---------------------------------------------------------------------------

_SWEEP_DTYPES = ("float32", "int32", "int8", "bool")


@pytest.mark.parametrize("n,s", [(0, 3), (1, 1), (37, 5), (1000, 64),
                                 (300, 1), (513, 9)])
def test_segment_reduce_sweep_bit_identical(n, s):
    """ops × dtypes × segment counts (0-row, 1-segment, tile-crossing):
    pallas == same-spec reference emulation bitwise, and == the XLA
    scatter for the order-free classes."""
    rng = np.random.default_rng(n * 31 + s)
    ids = rng.integers(0, s, n).astype(np.int32)  # unsorted by nature
    cols = {
        "f_sum": rng.standard_normal(n).astype(np.float32),
        "f_mean": rng.standard_normal(n).astype(np.float32),
        "f_min": rng.standard_normal((n, 3)).astype(np.float32),
        "i_sum": rng.integers(-50, 50, (n, 2)).astype(np.int32),
        "i_max": rng.integers(-50, 50, n).astype(np.int8),
        "b_min": rng.integers(0, 2, n).astype(bool),
    }
    ops = (
        ("f_sum", "reduce_sum"), ("f_mean", "reduce_mean"),
        ("f_min", "reduce_min"), ("i_sum", "reduce_sum"),
        ("i_max", "reduce_max"), ("b_min", "reduce_min"),
    )
    assert ksr.eligible(ops, cols, s)
    got = ksr.segment_reduce_pallas(ops, s, cols, ids, interpret=True)
    ref = ksr.segment_reduce_reference(ops, s, cols, ids)
    for k in got:
        assert np.array_equal(got[k], ref[k], equal_nan=True), k
        assert got[k].dtype == ref[k].dtype
    if n:
        # order-free classes are additionally exactly the scatter
        sidx = jnp.asarray(ids)
        _assert_eq(
            got["i_sum"],
            np.asarray(jax.ops.segment_sum(
                jnp.asarray(cols["i_sum"]), sidx, num_segments=s
            )),
            "int sum vs scatter",
        )
        _assert_eq(
            got["f_min"],
            np.asarray(jax.ops.segment_min(
                jnp.asarray(cols["f_min"]), sidx, num_segments=s
            )),
            "float min vs scatter",
        )
        _assert_eq(
            got["i_max"],
            np.asarray(jax.ops.segment_max(
                jnp.asarray(cols["i_max"]), sidx, num_segments=s
            )),
            "int8 max vs scatter",
        )


def test_segment_reduce_empty_segments_mean_is_nan():
    """Segments past the max observed id (the bucketing shape): sums
    read 0, means read NaN — and pallas matches the emulation on the
    NaN slots bit-for-bit."""
    ids = np.asarray([0, 0, 2], np.int32)
    cols = {"v": np.asarray([1.0, 3.0, 5.0], np.float32)}
    ops = (("v", "reduce_mean"),)
    got = ksr.segment_reduce_pallas(ops, 5, cols, ids, interpret=True)
    ref = ksr.segment_reduce_reference(ops, 5, cols, ids)
    assert np.array_equal(got["v"], ref["v"], equal_nan=True)
    assert got["v"][0] == pytest.approx(2.0)
    assert np.isnan(got["v"][1]) and np.isnan(got["v"][3])


def test_segment_reduce_eligibility_gates():
    f64 = {"v": np.zeros(4, np.float64)}
    assert not ksr.eligible((("v", "reduce_sum"),), f64, 2)
    i64 = {"v": np.zeros(4, np.int64)}
    assert not ksr.eligible((("v", "reduce_sum"),), i64, 2)
    ok = {"v": np.zeros(4, np.float32)}
    assert not ksr.eligible((("v", "reduce_sum"),), ok, 0)
    assert not ksr.eligible(
        (("v", "reduce_sum"),), ok, ksr.MAX_SEGMENTS + 1
    )
    # a 2-D column wider than MAX_INNER row streams is refused, and so
    # is a fetch list whose resident accumulators outgrow the budget
    wide = {"v": np.zeros((4, ksr.MAX_INNER + 1), np.float32)}
    assert not ksr.eligible((("v", "reduce_min"),), wide, 64)
    many = {f"c{i}": np.zeros((4, ksr.MAX_INNER), np.float32)
            for i in range(4)}
    assert not ksr.eligible(
        tuple((k, "reduce_sum") for k in many), many, ksr.MAX_SEGMENTS
    )
    assert ksr.eligible((("v", "reduce_min"),), ok, 64)


def test_aggregate_forced_kernel_bit_identical(forced):
    """End-to-end: the cost model selects pallas_segment_reduce under
    force, and the aggregate result is bit-identical to the unforced
    run (exact op classes: min + integer sum)."""
    before = REGISTRY.counter(
        "tftpu_plan_cost_decisions_total",
        labels={"decision": "pallas_segment_reduce"},
    ).value

    def run():
        rng = np.random.default_rng(7)
        n = 400
        frame = tfs.frame_from_arrays(
            {
                "k": rng.integers(0, 9, n),
                "v": rng.standard_normal(n).astype(np.float32),
                "w": rng.integers(-10, 10, n).astype(np.int32),
            },
            num_blocks=3,
        )
        with tfs.with_graph():
            v_input = tfs.block(frame, "v", tf_name="v_input")
            w_input = tfs.block(frame, "w", tf_name="w_input")
            agg = tfs.aggregate(
                [tfs.reduce_min(v_input, axis=0, name="v"),
                 tfs.reduce_sum(w_input, axis=0, name="w")],
                frame.group_by("k"),
            )
        return sorted(
            (int(r["k"]), float(r["v"]), int(r["w"]))
            for r in agg.collect()
        )

    forced_res = run()
    assert REGISTRY.counter(
        "tftpu_plan_cost_decisions_total",
        labels={"decision": "pallas_segment_reduce"},
    ).value > before
    configure(pallas_force=False)
    assert run() == forced_res


def test_segment_reduce_mosaic_error_surfaces(forced, monkeypatch):
    """A Mosaic failure in the selected kernel reaches the caller: no
    switch is thrown, nothing retries on the jitted scatter."""
    from tensorframes_tpu.ops import verbs

    calls = {"n": 0}

    def boom(*a, **k):
        calls["n"] += 1
        raise RuntimeError("Mosaic failed to compile TPU kernel (test)")

    monkeypatch.setattr(ksr, "segment_reduce_pallas", boom)
    rng = np.random.default_rng(3)
    cols = {"v": rng.integers(-5, 5, 64).astype(np.int32)}
    ids = rng.integers(0, 4, 64).astype(np.int32)
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        verbs._segment_reduce_best((("v", "reduce_sum"),), 4, cols, ids)
    assert calls["n"] == 1
    assert segment.pallas_enabled()  # nothing trips the manual switch


def test_run_segment_fast_mosaic_error_surfaces(monkeypatch):
    """The jitted segment program (whose float sums ride the one-hot
    pallas kernel on a TPU) has no catch-and-retry either."""
    from tensorframes_tpu.ops import verbs

    def boom(ops, num_groups):
        def fn(vals, sids):
            raise RuntimeError("Mosaic failed to compile TPU kernel (test)")
        return fn

    monkeypatch.setattr(verbs, "_seg_fast_for", boom)
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        verbs.run_segment_fast(
            (("v", "reduce_sum"),), 2,
            {"v": jnp.zeros(8, jnp.float32)}, jnp.zeros(8, jnp.int32),
        )
    assert segment.pallas_enabled()


def test_kernel_error_stays_loud(forced, monkeypatch):
    from tensorframes_tpu.ops import verbs

    def boom(*a, **k):
        raise RuntimeError("genuine bug, not a kernel-compile failure")

    monkeypatch.setattr(ksr, "segment_reduce_pallas", boom)
    with pytest.raises(RuntimeError, match="genuine bug"):
        verbs._segment_reduce_best(
            (("v", "reduce_sum"),), 2,
            {"v": np.zeros(8, np.int32)},
            np.zeros(8, np.int32),
        )
    assert segment.pallas_enabled()  # the switch must NOT trip


# -- bugfix-sweep pins: zero-row edges of the ragged fallback ---------------

def test_group_rows_by_shape_zero_rows_yields_no_groups():
    from tensorframes_tpu.ops.verbs import _group_rows_by_shape

    assert _group_rows_by_shape({"v": []}, ["v"], 0) == []


def test_ragged_rows_outs_zero_rows_returns_typed_empties():
    from tensorframes_tpu.ops.verbs import _ragged_rows_outs

    tiny = tfs.frame_from_rows(
        [{"v": np.arange(3, dtype=np.float32)}]
    )
    program = tfs.compile_program(
        lambda v: {"s": v.sum()}, tiny, block=False
    )
    outs = _ragged_rows_outs(
        {"v": []}, ["v"], 0, program, program.compiled()
    )
    assert outs["s"].shape == (0,)
    assert outs["s"].dtype == np.float32


# ---------------------------------------------------------------------------
# paged decode attention
# ---------------------------------------------------------------------------

def _paged_case(rng, S, maxp, page, nh, hd, pos=None, padding=1, L=2):
    """A random pool, distinct pages per slot, the last ``padding``
    slots padding slots (all-null table, position 0). ``pos``: the live
    slots' positions (random when None). The scale rows' padding lanes
    hold garbage: no output may read them."""
    P = maxp * S + 1
    q = jnp.asarray(rng.standard_normal((S, nh, hd)), jnp.float32)
    kp, vp = (
        jnp.asarray(rng.integers(-127, 128, (P, L, page, nh * hd)),
                    jnp.int8)
        for _ in range(2)
    )
    ks, vs = (
        jnp.asarray(
            rng.uniform(0.01, 0.1, (P, L, page, kda.SCALE_LANES)),
            jnp.float32,
        )
        for _ in range(2)
    )
    tables = jnp.asarray(
        1 + rng.permutation(P - 1).reshape(S, maxp), jnp.int32
    ).at[S - padding:].set(0)  # padding slots: all-null tables
    if pos is None:
        pos = rng.integers(0, maxp * page, S)
    pos = jnp.asarray(
        np.resize(np.asarray(pos), S), jnp.int32
    ).at[S - padding:].set(0)
    return q, kp, vp, ks, vs, tables, pos


@pytest.mark.parametrize(
    "S,maxp,page,nh,hd,pos",
    [(1, 1, 4, 2, 8, None), (5, 3, 8, 4, 16, None),
     (8, 2, 16, 2, 4, None),
     # heads that share a 128-lane block (GPT-2's 64), straddle blocks
     # (96) and fill whole blocks (128)
     (3, 2, 16, 12, 64, None), (2, 2, 8, 3, 96, None),
     (2, 2, 8, 2, 128, None),
     # the walk (page 16: chunks of 8 pages, 128 positions). A context
     # ending inside a chunk, and inside that chunk's first page
     (3, 24, 16, 2, 8, [200, 130]),
     # ... exactly on a chunk edge: the last row of a chunk, the first
     # row of the next
     (4, 24, 16, 2, 8, [127, 128, 255]),
     # ... at the full table
     (3, 16, 16, 2, 8, [255, 254]),
     # a table the chunk does not divide (8 + 2 pages), walked to its end
     (3, 10, 16, 2, 8, [159, 129]),
     # a table shorter than a chunk (page 8 asks for 16, the table has 5)
     (3, 5, 8, 2, 8, [39, 17]),
     # a bucket that is mostly padding slots: one live slot of eight
     (8, 12, 16, 2, 8, [150])],
)
def test_paged_decode_attention_bit_identical(S, maxp, page, nh, hd, pos):
    """Kernel vs its same-chunks emulation (bitwise) and vs the XLA
    gather→dequant→attend chain (float tolerance) across slot/page
    mixes and context lengths — including padding slots with all-null
    tables."""
    rng = np.random.default_rng(S * 7 + maxp)
    q, kp, vp, ks, vs, tables, pos = _paged_case(
        rng, S, maxp, page, nh, hd, pos,
        padding=1 if pos is None else S - len(pos),
    )
    for li in range(kp.shape[1]):
        got = np.asarray(kda.paged_decode_attention(
            q, kp, vp, ks, vs, li, tables, pos, interpret=True
        ))
        emu = np.asarray(kda.paged_attention_emulation(
            q, kp, vp, ks, vs, li, tables, pos
        ))
        _assert_eq(got, emu, f"layer {li} vs emulation")
        ref = np.asarray(kda.paged_attention_reference(
            q, kp, vp, ks, vs, li, tables, pos
        ))
        np.testing.assert_allclose(
            got, ref, rtol=1e-5, atol=1e-5 * np.abs(ref).max(),
            err_msg=f"layer {li} vs XLA chain",
        )


def test_paged_decode_attention_reads_no_page_beyond_the_context():
    """Pages that no live position reaches are never read: with NaN
    scales and saturated int8 rows on every such page, on the rows of a
    slot's last page beyond its position, and every table entry beyond
    a slot's last page pointing at such a page, the output is finite
    and bitwise what clean pages give. (A kernel that folds the whole
    table fails this: a masked weight of 0 times a NaN scale.)"""
    S, maxp, page, nh, hd = 5, 20, 16, 2, 8
    rng = np.random.default_rng(29)
    live_pos = [0, 15, 16, 200, 319]
    q, kp, vp, ks, vs, tables, pos = _paged_case(
        rng, S + 1, maxp, page, nh, hd, live_pos
    )
    tables, pos_np = np.array(tables), np.asarray(pos)
    reached = np.zeros(kp.shape[0], bool)
    reached[0] = True                       # the padding slot's null page
    for s, p in enumerate(pos_np[:S]):
        reached[tables[s, :p // page + 1]] = True
    spare = np.flatnonzero(~reached)
    for s, p in enumerate(pos_np):
        # beyond the context: every entry points at an unreached page
        beyond = maxp - (p // page + 1)
        tables[s, p // page + 1:] = rng.choice(spare, beyond)
    poison = ~reached
    dirty = {
        "kp": np.array(kp), "vp": np.array(vp),
        "ks": np.array(ks), "vs": np.array(vs),
    }
    dirty["kp"][poison] = 127
    dirty["vp"][poison] = -127
    dirty["ks"][poison] = np.nan
    dirty["vs"][poison] = np.nan
    for s, p in enumerate(pos_np):          # rows beyond the position
        pg = tables[s, p // page]
        dirty["ks"][pg, :, p % page + 1:] = np.nan
        dirty["vs"][pg, :, p % page + 1:] = np.nan
    tables = jnp.asarray(tables)
    for li in range(kp.shape[1]):
        clean = np.asarray(kda.paged_decode_attention(
            q, kp, vp, ks, vs, li, tables, pos, interpret=True
        ))
        got = np.asarray(kda.paged_decode_attention(
            q, *(jnp.asarray(dirty[c]) for c in ("kp", "vp", "ks", "vs")),
            li, tables, pos, interpret=True,
        ))
        assert np.isfinite(got).all(), f"layer {li}"
        _assert_eq(got, clean, f"layer {li}: poisoned vs clean pages")


@pytest.mark.parametrize(
    "page,maxp,pos,walked",
    [(16, 64, [0, 127, 128, 1023], [8, 8, 16, 64]),   # the serving cell
     (16, 10, [0, 128, 159], [8, 10, 10]),            # 8 + 2 pages
     (8, 5, [0, 39], [5, 5]),                         # table < chunk
     (4, 8, [3, 4, 31], [8, 8, 8])],                  # page 4: 32 a chunk
)
def test_pages_walked_follows_the_chunks(page, maxp, pos, walked):
    got = kda.pages_walked(np.asarray(pos), page, maxp)
    assert got.tolist() == walked
    assert kda.chunk_pages(page, maxp) == min(128 // page, maxp)


def test_ops_attention_paged_wrapper(forced):
    """The public op is the kernel where the backend can run it (here:
    the forced interpreter) and the XLA chain where it cannot."""
    from tensorframes_tpu.ops.attention import paged_decode_attention

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((2, 2, 4)), jnp.float32)
    kp = jnp.asarray(rng.integers(-5, 5, (3, 1, 4, 2 * 4)), jnp.int8)
    ks = jnp.ones((3, 1, 4, kda.SCALE_LANES), jnp.float32)
    tables = jnp.asarray([[1, 2], [0, 0]], jnp.int32)
    pos = jnp.asarray([5, 0], jnp.int32)
    args = (q, kp, kp, ks, ks, 0, tables, pos)
    _assert_eq(np.asarray(paged_decode_attention(*args)),
               np.asarray(kda.paged_attention_emulation(*args)),
               "public wrapper, kernel")
    configure(pallas_force=False)
    _assert_eq(np.asarray(paged_decode_attention(*args)),
               np.asarray(kda.paged_attention_reference(*args)),
               "public wrapper, chain")


def test_decode_engine_forced_kernel_matches_oracle(forced):
    """Slot/page mixes through the real engine with the kernel
    selected: tokens equal to the unforced engine AND to the dense
    int8-KV ``generate()`` oracle. (The kernel's online softmax is
    float-close, not bitwise, to the XLA chain; these seeded prompts
    have no argmax near-tie that the difference could flip.)"""
    from tensorframes_tpu.models import generation as gen
    from tensorframes_tpu.models import transformer as tr
    from tensorframes_tpu.serving.decode import (
        DecodeConfig, DecodeEngine,
    )

    cfg = gen.gpt_tiny()
    params = tr.init_params(cfg, seed=0)
    rng = np.random.default_rng(0)
    new = 4
    prompts = [
        rng.integers(
            0, cfg.vocab_size, (int(rng.integers(2, 9)),)
        ).astype(np.int32)
        for _ in range(4)
    ]

    def run():
        eng = DecodeEngine("kern-t", cfg, params, DecodeConfig(
            max_slots=2, page_size=4, max_prompt_len=8,
            max_new_tokens=new,
        ))
        eng.start()
        try:
            futs = [eng.submit({"prompt": p}) for p in prompts]
            return [f.result(300)["tokens"] for f in futs]
        finally:
            eng.stop(drain=True, timeout=120)

    forced_outs = run()
    assert kernels.DISPATCHES["decode_attn"].value > 0
    configure(pallas_force=False)
    base_outs = run()
    for i, p in enumerate(prompts):
        _assert_eq(forced_outs[i], base_outs[i], f"req {i} vs XLA chain")
        oracle = np.asarray(
            gen.generate(cfg, params, p[None, :], new, kv_quant=True)
        )
        _assert_eq(forced_outs[i], oracle, f"req {i} vs oracle")


def test_decode_attn_page_counters_follow_the_contexts(forced):
    """``tftpu_decode_attn_pages_walked_total`` counts the table entries
    the kernel's chunks cover at each step's positions, ``..._grid_total``
    the whole table of the step's bucket; ``decode.step`` carries the
    same two numbers. One request at a time: positions known."""
    from tensorframes_tpu.models import generation as gen
    from tensorframes_tpu.models import transformer as tr
    from tensorframes_tpu.observability import events
    from tensorframes_tpu.serving import metrics as sm
    from tensorframes_tpu.serving.decode import (
        DecodeConfig, DecodeEngine,
    )

    cfg = gen.gpt_tiny(max_seq_len=160)
    params = tr.init_params(cfg, seed=0)
    page, new, plens = 8, 8, (100, 124, 130)
    eng = DecodeEngine("kern-walk", cfg, params, DecodeConfig(
        max_slots=2, page_size=page, max_prompt_len=136,
        max_new_tokens=new,
    ))
    maxp = -(-(136 + new) // page)                       # 18 entries
    assert kda.chunk_pages(page, maxp) == 16             # 128 positions
    rng = np.random.default_rng(5)
    eng.start()
    try:
        w0 = sm.DECODE_ATTN_PAGES_WALKED.value
        g0 = sm.DECODE_ATTN_PAGES_GRID.value
        events.clear()
        events.enable()
        for plen in plens:
            eng.call({"prompt": rng.integers(
                0, cfg.vocab_size, (plen,)).astype(np.int32)}, timeout=300)
        events.disable()
        walked = sm.DECODE_ATTN_PAGES_WALKED.value - w0
        grid = sm.DECODE_ATTN_PAGES_GRID.value - g0
    finally:
        events.disable()
        eng.stop(drain=True, timeout=120)
    # the prefill gives a request's first token; each later token is one
    # step at the position it writes: plen, plen + 1, ...
    # (the other rows of the smallest slot bucket are padding: one
    # chunk each)
    positions = [plen + i for plen in plens for i in range(new - 1)]
    bucket = eng._slot_buckets[0]
    want = sum((16 if p < 128 else maxp) + (bucket - 1) * 16
               for p in positions)
    assert (walked, grid) == (want, len(positions) * bucket * maxp)
    assert 0 < walked < grid
    steps = [e["args"] for e in events.to_chrome_trace()["traceEvents"]
             if e.get("name") == "decode.step" and e.get("ph") == "X"]
    assert len(steps) == len(positions)
    assert sum(a["pages_walked"] for a in steps) == walked
    assert sum(a["pages_grid"] for a in steps) == grid


def test_decode_engine_mosaic_failure_surfaces(forced):
    """A kernel-compile failure in the decode step fails the request
    with that error: the engine does not rebuild the step on the XLA
    chain and nothing trips the manual switch."""
    from tensorframes_tpu.models import generation as gen
    from tensorframes_tpu.models import transformer as tr
    from tensorframes_tpu.serving.decode import (
        DecodeConfig, DecodeEngine,
    )

    cfg = gen.gpt_tiny()
    params = tr.init_params(cfg, seed=0)
    eng = DecodeEngine("kern-moz", cfg, params, DecodeConfig(
        max_slots=2, page_size=4, max_prompt_len=8, max_new_tokens=3,
        warmup=False,
    ))
    assert eng._attn_is_kernel

    def broken(*args):
        raise RuntimeError("Mosaic failed to compile TPU kernel (test)")

    eng._step = broken
    try:
        eng.start()
        with pytest.raises(Exception, match="Mosaic failed"):
            eng.call(
                {"prompt": np.asarray([1, 2, 3], np.int32)}, timeout=300
            )
        assert segment.pallas_enabled()
    finally:
        eng.stop(drain=False, timeout=60)


# ---------------------------------------------------------------------------
# packed prefill attention
# ---------------------------------------------------------------------------

PACK = 64


def _packed_case(rng, nh, hd, T, segments, dtype=jnp.float32):
    """q, int8 k/v with their scales over ``T`` packed rows, and
    ``first_block``: ``segments`` lists each prompt's blocks from row 0
    on; the blocks after the last prompt are padding (each its own
    first)."""
    q = jnp.asarray(rng.standard_normal((1, nh, T, hd)), dtype)
    kq, vq = (jnp.asarray(rng.integers(-127, 128, (1, nh, T, hd)),
                          jnp.int8) for _ in range(2))
    ks, vs = (jnp.asarray(rng.uniform(.002, .02, (1, nh, T)), jnp.float32)
              for _ in range(2))
    first = np.arange(T // PACK, dtype=np.int32)
    at = 0
    for n in segments:
        first[at:at + n] = at
        at += n
    assert at <= T // PACK
    return q, kq, vq, ks, vs, jnp.asarray(first)


@pytest.mark.parametrize("nh,hd,T,segments,dtype", [
    (4, 8, 64, [1], jnp.float32),                  # one 64-row prompt
    (4, 8, 512, [8], jnp.float32),                 # a prompt of 8 blocks
    (4, 8, 512, [3, 2], jnp.float32),              # 3 padding blocks
    (4, 8, 1024, [1] * 16, jnp.float32),           # 16 segments
    (4, 8, 1024, [5, 3, 1, 6], jnp.float32),       # the 1,024 bucket
    (4, 8, 4096, [20, 30, 6, 1], jnp.float32),     # the 4,096 bucket
    (12, 64, 256, [3, 1], jnp.bfloat16),           # GPT-2: two heads a tile
    (2, 128, 192, [2], jnp.bfloat16),              # one head a tile
])
def test_packed_attention_matches_the_band_fold(nh, hd, T, segments, dtype):
    """The kernel (interpreted) against ``_band_attention`` with the same
    ``first_block``, its XLA lowering and oracle: the same math, so the
    two agree to float rounding in float32 and to an ulp of the output
    in bfloat16."""
    from tensorframes_tpu.kernels import packed_attention as kpa
    from tensorframes_tpu.ops.attention import _band_attention

    rng = np.random.default_rng(T + len(segments))
    q, kq, vq, ks, vs, first = _packed_case(rng, nh, hd, T, segments,
                                            dtype)
    got = np.asarray(kpa.packed_attention(q, kq, vq, ks, vs, first, PACK,
                                          interpret=True), np.float32)
    ref = np.asarray(_band_attention(q, kq, vq, PACK, None, ks, vs, first),
                     np.float32)
    assert got.shape == ref.shape == q.shape
    tol = 2e-6 if dtype == jnp.float32 else 1e-2
    np.testing.assert_allclose(got, ref, rtol=0,
                               atol=tol * np.abs(ref).max())


@pytest.mark.parametrize("nh,hd,dtype", [(4, 8, jnp.float32),
                                         (12, 64, jnp.bfloat16)])
def test_packed_attention_gives_a_prompt_the_same_bits_anywhere(
        nh, hd, dtype):
    """A prompt's rows alone at row 0 and among neighbours at another
    block edge (other prompts before and after it, padding at the end)
    are bit for bit the same."""
    from tensorframes_tpu.kernels import packed_attention as kpa

    rng = np.random.default_rng(41)
    q, kq, vq, ks, vs, first = _packed_case(rng, nh, hd, 512, [2, 3, 1],
                                            dtype)
    mine = slice(2 * PACK, 5 * PACK)                # the second prompt
    args = [x[..., mine, :] if x.ndim == 4 else x[..., mine]
            for x in (q, kq, vq, ks, vs)]
    alone = kpa.packed_attention(*args, jnp.zeros(3, jnp.int32), PACK,
                                 interpret=True)
    among = kpa.packed_attention(q, kq, vq, ks, vs, first, PACK,
                                 interpret=True)
    _assert_eq(np.asarray(among[:, :, mine]), np.asarray(alone),
               "alone vs among neighbours")


def test_blockwise_attention_takes_the_kernel_for_block_bounds(forced):
    """``blockwise_attention`` with block bounds over int8 KV traces the
    kernel where ``packed_attn`` is selectable and the band fold where
    it is not; a windowed call never takes it."""
    from tensorframes_tpu.ops.attention import blockwise_attention

    q, kq, vq, ks, vs, first = _packed_case(
        np.random.default_rng(0), 4, 8, 256, [2, 1])

    def traced(**kw):
        return "pallas_call" in str(jax.make_jaxpr(
            lambda *a: blockwise_attention(*a, causal=True, block_size=PACK,
                                           k_scale=ks, v_scale=vs, **kw)
        )(q, kq, vq))

    assert kernels.selectable("packed_attn")
    assert traced(first_block=first)
    assert not traced(window=128)
    configure(pallas_force=False)
    assert not traced(first_block=first)


# ---------------------------------------------------------------------------
# selection, registry, and switches
# ---------------------------------------------------------------------------

def _traced_step_calls_pallas() -> bool:
    """Trace a tiny decode step (shapes only) and say whether its jaxpr
    holds a ``pallas_call``: which attention the step got."""
    from tensorframes_tpu.models import generation as gen
    from tensorframes_tpu.models import transformer as tr

    cfg = gen.gpt_tiny()
    S, page, maxp = 2, 4, 3
    params = jax.eval_shape(lambda: tr.init_params(cfg, seed=0))
    pool = jax.eval_shape(
        lambda: gen.init_paged_kv(cfg, 1 + S * maxp, page)
    )

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    jaxpr = jax.make_jaxpr(gen.paged_decode_step_fn(cfg, page, maxp))(
        params, pool, i32(S), i32(S), i32(S, maxp)
    )
    return "pallas_call" in str(jaxpr)


def test_decisions_on_cpu_default_to_non_pallas():
    cols = {"v": np.zeros(8, np.int32)}
    assert prules.decide_segment_reduce(
        (("v", "reduce_sum"),), cols, 4
    ).kind == "jit_segment_reduce"
    assert not kernels.selectable("decode_attn")
    assert not _traced_step_calls_pallas()


def test_decisions_under_force_pick_pallas(forced):
    cols = {"v": np.zeros(8, np.int32)}
    assert prules.decide_segment_reduce(
        (("v", "reduce_sum"),), cols, 4
    ).kind == "pallas_segment_reduce"
    assert kernels.selectable("decode_attn")
    assert _traced_step_calls_pallas()


@pytest.mark.parametrize("force,enabled,favoured,kernel", [
    (False, True, "pallas_decode_attn", False),   # CPU default: chain
    (True, True, "xla_decode_attn", True),        # hook: the kernel
    (True, False, "pallas_decode_attn", False),   # TFTPU_PALLAS=0 wins
])
def test_decode_attention_follows_the_backend_alone(
    forced, force, enabled, favoured, kernel
):
    """Which attention a step traces is ``kernels.selectable`` and
    nothing else: a strategy-wall table that favours the OTHER lowering
    (what used to flip the choice) changes nothing."""
    from tensorframes_tpu.plan import stats as plan_stats

    configure(pallas_force=force, pallas_kernels=enabled)
    for _ in range(4):
        for kind in ("pallas_decode_attn", "xla_decode_attn"):
            plan_stats.observe_strategy_wall(
                "decode_attention", kind,
                1e-4 if kind == favoured else 10.0,
            )
    assert len(plan_stats.strategy_walls("decode_attention")) == 2
    assert kernels.selectable("decode_attn") is kernel
    assert _traced_step_calls_pallas() is kernel


def test_host_segment_reduce_still_wins_cpu_float_sums(forced):
    """The measured CPU bincount win outranks the kernel even under
    force: 1-D float sums/means stay on the host path."""
    cols = {"v": np.zeros(8, np.float32)}
    assert prules.decide_segment_reduce(
        (("v", "reduce_mean"),), cols, 4
    ).kind == "host_segment_reduce"


def test_tftpu_pallas_off_removes_kernels_everywhere(forced):
    configure(pallas_kernels=False)
    assert not kernels.enabled()
    assert not any(kernels.selectable(k) for k in kernels.KERNELS)
    cols = {"v": np.zeros(8, np.int32)}
    assert prules.decide_segment_reduce(
        (("v", "reduce_sum"),), cols, 4
    ).kind == "jit_segment_reduce"
    # the forced fixture restores the prior switch state
    assert not _traced_step_calls_pallas()


def test_selectable_is_the_one_table(forced):
    """``kernels.selectable`` is what every call site reads: all
    kernels under the force hook, none on a CPU without it."""
    assert set(kernels.KERNELS) == {"segment_reduce", "decode_attn",
                                    "expert_matmul", "packed_attn"}
    assert all(kernels.selectable(k) for k in kernels.KERNELS)
    configure(pallas_force=False)
    assert not any(kernels.selectable(k) for k in kernels.KERNELS)
    with pytest.raises(KeyError):
        kernels.selectable("ragged_gather")


def test_every_registered_kernel_is_selectable_on_tpu(monkeypatch):
    """No registered kernel is one the target cannot select: on a TPU
    backend every name in ``KERNELS`` is selectable without the hook,
    and ``TFTPU_PALLAS=0`` still removes them all."""
    from tensorframes_tpu.config import get_config

    was = get_config().pallas_kernels
    monkeypatch.setattr(kernels, "is_tpu_backend", lambda: True)
    try:
        configure(pallas_kernels=True)
        assert not kernels.force_active()
        assert all(kernels.selectable(k) for k in kernels.KERNELS)
        assert kernels.interpret_mode() is False
        configure(pallas_kernels=False)
        assert not any(kernels.selectable(k) for k in kernels.KERNELS)
    finally:
        configure(pallas_kernels=was)


def test_interpret_mode_refuses_other_backends(monkeypatch):
    assert kernels.interpret_mode() is True  # tier-1 runs on the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="neither"):
        kernels.interpret_mode()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert kernels.interpret_mode() is False


def test_kill_switch_disables_kernels_package():
    was = segment._pallas_disabled
    try:
        segment.disable_pallas("kernels package test")
        assert not kernels.enabled()
        assert kernels.fingerprint_token()["enabled"] is False
    finally:
        segment._pallas_disabled = was


def test_kernels_metrics_preregistered():
    names = {m.name for m in REGISTRY.collect()}
    assert "tftpu_kernels_dispatch_total" in names
    assert "tftpu_kernels_interpret_fallback_total" in names
    assert "tftpu_kernels_build_seconds" in names
    labels = {
        dict(m.labels).get("kernel")
        for m in REGISTRY.collect()
        if m.name == "tftpu_kernels_dispatch_total"
    }
    assert labels == set(kernels.KERNELS) == {
        "segment_reduce", "decode_attn", "expert_matmul", "packed_attn"}

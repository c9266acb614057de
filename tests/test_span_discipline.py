"""The tracing rule (ISSUE 25): on any one thread "X" spans nest properly
or do not overlap; whatever the host does between two device programs
on the hot paths lies under a leaf span; a request's life is an async
pair, never an "X" span.

* the serving loop — a saturated ``DecodeEngine`` under tracing: stack
  discipline on the engine thread, no uncovered stretch between the
  first and the last ``decode.step``, no lifetime on the timeline,
  ``decode.request`` as matched "b"/"e" pairs that survive a merge;
* the frame pass — a sharded ``reduce_blocks(map_blocks(...))`` over
  four virtual devices emits ``executor.prepare``, ``plan.reduce.*``
  with the stated nesting;
* the serving loop's leaves — inside a join its build, prefill (its
  enqueue and fetch) and seating, inside a step its enqueue, fetch and
  count, in that order; each phase's CPU time; the loop's CPU counter;
  a ``jax.profiler`` capture holding the phases and leaves under their
  names;
* tracing off — the same runs append nothing, make no profiler
  ``TraceMe`` and read the thread's CPU clock once a turn;
* first-token time — ``ResultFuture.t_submit <= t_first_token <=
  t_done`` and ``t_first_token`` is what ``DECODE_TTFT`` observed;
* names — scope names in the lowered text of the decode step and of
  the Inception forward; three module names for the frame pass's three
  programs.
"""

import json
import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tensorframes_tpu as tfs
from tensorframes_tpu.models import generation as gen
from tensorframes_tpu.models import inception as inc
from tensorframes_tpu.models import transformer as tr
from tensorframes_tpu.observability import events, merge
from tensorframes_tpu.observability.metrics import REGISTRY
from tensorframes_tpu.program import Program, TensorSpec
from tensorframes_tpu.serving import DecodeConfig, DecodeEngine

PHASES = ("decode.admit", "decode.join", "decode.prepare", "decode.step",
          "decode.commit")
# what a packed join and a step hold, in the order it runs: every
# instant of them lies under one of these or their leaves
JOIN_PARTS = ("decode.join.build", "decode.prefill", "decode.join.seat")
PREFILL_LEAVES = ("decode.prefill.enqueue", "decode.prefill.fetch")
STEP_LEAVES = ("decode.step.enqueue", "decode.step.fetch",
               "decode.step.count")


@pytest.fixture(scope="module")
def model():
    cfg = gen.gpt_tiny()
    return cfg, tr.quantize_params(tr.init_params(cfg, seed=0))


@pytest.fixture(scope="module")
def engine(model):
    cfg, params = model
    eng = DecodeEngine("t_spans", cfg, params, DecodeConfig(
        max_slots=4, page_size=8, max_prompt_len=16, max_new_tokens=8,
    ))
    eng.start()
    yield eng
    eng.stop(drain=True, timeout=120)


@pytest.fixture
def tracing():
    was = events.TRACER.enabled
    events.clear()
    events.enable()
    yield
    events.clear()
    if not was:
        events.disable()


def _saturate(eng, vocab, n=12, seed=0):
    """Three times as many requests as slots, mixed lengths, one of them
    wanting a single token (it finishes inside its join)."""
    rng = np.random.default_rng(seed)
    futs = []
    for i in range(n):
        prompt = rng.integers(0, vocab, (int(rng.integers(3, 17)),))
        futs.append(eng.submit({
            "prompt": prompt.astype(np.int32),
            "max_new_tokens": 1 if i == 5 else int(rng.integers(2, 9)),
        }))
    for f in futs:
        f.result(120)
    return futs


def _events():
    return events.to_chrome_trace()["traceEvents"]


def _engine_spans(evs, eng):
    tid = eng._thread.ident
    return sorted(
        (e for e in evs if e.get("ph") == "X" and e["tid"] == tid),
        key=lambda e: (e["ts"], -e["dur"]),
    )


def _assert_stack_discipline(spans, slack_us=0.5):
    stack = []
    for e in spans:
        while stack and stack[-1]["ts"] + stack[-1]["dur"] <= e["ts"] + slack_us:
            stack.pop()
        if stack:
            top = stack[-1]
            assert e["ts"] + e["dur"] <= top["ts"] + top["dur"] + slack_us, (
                f"{e['name']} straddles the end of {top['name']}")
        stack.append(e)


def test_engine_thread_spans_nest_and_cover_the_loop(model, engine, tracing):
    _saturate(engine, model[0].vocab_size)
    events.disable()
    evs = _events()
    spans = _engine_spans(evs, engine)
    names = {e["name"] for e in spans}
    assert set(PHASES) | {"decode.prefill", "decode.step.enqueue",
                          "decode.finish"} <= names
    _assert_stack_discipline(spans)

    # the five phases tile the loop: between the first and the last
    # decode.step nothing longer than 1 ms is uncovered
    steps = [e for e in spans if e["name"] == "decode.step"]
    lo, hi = steps[0]["ts"], steps[-1]["ts"] + steps[-1]["dur"]
    at, worst = lo, 0.0
    for e in spans:
        if e["name"] not in PHASES or e["ts"] + e["dur"] <= lo or e["ts"] >= hi:
            continue
        worst = max(worst, e["ts"] - at)
        at = max(at, e["ts"] + e["dur"])
    assert worst <= 1000.0, f"{worst:.0f} us of the loop under no phase"

    # no lifetime on the timeline: nothing outlasts a step plus a join
    longest = (max(e["dur"] for e in steps)
               + max(e["dur"] for e in spans if e["name"] == "decode.join"))
    for e in spans:
        assert e["dur"] <= longest, (e["name"], e["dur"], longest)

    # children sit where the tables say
    def inside(child, parents):
        return any(p["ts"] - 0.5 <= child["ts"] and child["ts"] + child["dur"]
                   <= p["ts"] + p["dur"] + 0.5 for p in parents)

    joins = [e for e in spans if e["name"] == "decode.join"]
    commits = [e for e in spans if e["name"] == "decode.commit"]
    prefills = [e for e in spans if e["name"] == "decode.prefill"]
    for e in spans:
        if e["name"] == "decode.prefill":
            assert inside(e, joins)
            assert e["args"]["bucket"] >= 1
        elif e["name"] == "decode.finish":
            assert inside(e, joins + commits)
        elif e["name"] == "decode.step.enqueue":
            assert inside(e, steps)
    # the transformer packs a poll's joins: one join and one prefill a
    # packed call, the twelve prompts counted on both
    assert len(prefills) == len(joins) <= 12
    assert sum(e["args"]["segments"] for e in prefills) == 12
    assert sum(e["args"]["joins"] for e in joins) == 12
    assert len(steps) == sum(
        e["name"] == "decode.step.enqueue" for e in spans)
    assert any(e["name"] == "decode.finish" and inside(e, joins)
               for e in spans), "the one-token request finishes in its join"
    assert all(e["args"]["path"] == "packed"
               and e["args"]["bucket"] == e["args"]["tokens"]
               + e["args"]["padded"] and e["args"]["waited_s"] >= 0
               for e in joins)
    assert all({"slots", "bucket"} <= set(e["args"]) for e in steps)
    prepares = [e for e in spans if e["name"] == "decode.prepare"]
    assert all({"slots", "pages_allocated", "preempted"} <= set(e["args"])
               for e in prepares)
    assert sum(e["args"]["finished"] for e in commits) == 11
    assert sum(e["args"]["polled"] for e in spans
               if e["name"] == "decode.admit") == 12


def _inside(child, parent, slack_us=0.5):
    return (parent["ts"] - slack_us <= child["ts"]
            and child["ts"] + child["dur"]
            <= parent["ts"] + parent["dur"] + slack_us)


def _children(parent, spans, names):
    return [e for e in spans if e["name"] in names and e is not parent
            and _inside(e, parent)]


def test_every_host_instant_of_a_turn_lies_under_a_leaf(model, engine,
                                                        tracing):
    _saturate(engine, model[0].vocab_size, seed=3)
    events.disable()
    spans = _engine_spans(_events(), engine)
    _assert_stack_discipline(spans)
    by = {}
    for e in spans:
        by.setdefault(e["name"], []).append(e)
    for name in JOIN_PARTS + STEP_LEAVES + PREFILL_LEAVES:
        assert by.get(name), name

    def ordered(parent, names):
        kids = sorted(_children(parent, spans, names), key=lambda e: e["ts"])
        # in the stated order, one after the other, none overlapping
        for a, b in zip(kids, kids[1:]):
            assert a["ts"] + a["dur"] <= b["ts"] + 0.5, (a["name"], b["name"])
        return [e["name"] for e in kids]

    for join in by["decode.join"]:
        assert ordered(join, JOIN_PARTS) == list(JOIN_PARTS)
        (prefill,) = _children(join, spans, {"decode.prefill"})
        assert ordered(prefill, PREFILL_LEAVES) == list(PREFILL_LEAVES)
        # the finish of a one-token request sits in its seating
        (seat,) = _children(join, spans, {"decode.join.seat"})
        for fin in _children(join, spans, {"decode.finish"}):
            assert _inside(fin, seat)
    for step in by["decode.step"]:
        assert ordered(step, STEP_LEAVES) == list(STEP_LEAVES)
    for leaf in JOIN_PARTS:
        assert all(any(_inside(e, j) for j in by["decode.join"])
                   for e in by[leaf]), leaf
    for leaf in STEP_LEAVES:
        assert all(any(_inside(e, s) for s in by["decode.step"])
                   for e in by[leaf]), leaf
    assert len(by["decode.prefill.enqueue"]) == len(by["decode.prefill"])
    assert len(by["decode.prefill.fetch"]) == len(by["decode.prefill"])
    assert len(by["decode.step.count"]) == len(by["decode.step"])
    # the counts the step carries ride its count leaf too
    for step in by["decode.step"]:
        (count,) = _children(step, spans, {"decode.step.count"})
        assert count["args"]["pages_walked"] == step["args"]["pages_walked"]
        assert count["args"]["pages_grid"] == step["args"]["pages_grid"]

    # what a join or a step does outside its leaves is the few
    # microseconds between their boundaries
    for name, leaves in (("decode.join", JOIN_PARTS),
                         ("decode.step", STEP_LEAVES)):
        rest = sorted(
            e["dur"] - sum(c["dur"] for c in _children(e, spans, leaves))
            for e in by[name])
        assert rest[len(rest) // 2] <= 200.0, (name, rest)

    # every phase says how much of it the thread spent on the CPU
    for name in PHASES:
        for e in by[name]:
            assert 0.0 <= e["args"]["cpu_ms"] <= e["dur"] * 1e-3 + 0.5, e


def test_the_loop_counts_its_cpu_time(model, engine):
    def cpu_total():
        return sum(d["value"] for d in REGISTRY.snapshot()
                   if d["name"] == "tftpu_decode_loop_cpu_seconds_total")

    t0 = time.perf_counter()
    before = cpu_total()
    _saturate(engine, model[0].vocab_size, seed=4)
    after = cpu_total()
    wall = time.perf_counter() - t0
    # the first increment of the run may hold the CPU of the idle loop's
    # last nap before it: a few microseconds
    assert 0.0 < after - before <= wall + 0.005


def _decode_steps():
    return sum(d["value"] for d in REGISTRY.snapshot()
               if d["name"] == "tftpu_decode_steps_total"
               and dict(d["labels"]).get("phase") == "decode")


class _CountingTraceMe:
    """Stands in for the profiler's TraceMe as if a capture ran."""
    made = []
    exited = 0

    def __init__(self, name):
        type(self).made.append(name)

    @staticmethod
    def is_enabled():
        return True

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        type(self).exited += 1


def test_tracer_off_makes_no_traceme_and_reads_cpu_once_a_turn(
        model, engine, monkeypatch):
    was = events.TRACER.enabled
    events.disable()
    _CountingTraceMe.made, _CountingTraceMe.exited = [], 0
    monkeypatch.setattr(events, "_traceme_class", lambda: _CountingTraceMe)
    tid = engine._thread.ident
    reads, turns = [0], [0]
    thread_time = time.thread_time
    purge = engine._purge_resume

    def counted_thread_time():
        if threading.get_ident() == tid:
            reads[0] += 1
        return thread_time()

    def counted_purge():        # called once at the top of every turn
        turns[0] += 1
        purge()

    steps0 = _decode_steps()
    monkeypatch.setattr(time, "thread_time", counted_thread_time)
    monkeypatch.setattr(engine, "_purge_resume", counted_purge)
    try:
        _saturate(engine, model[0].vocab_size, n=6, seed=5)
        got_reads, got_turns = reads[0], turns[0]
        assert _CountingTraceMe.made == []
        assert got_turns >= _decode_steps() - steps0 > 0
        assert abs(got_reads - got_turns) <= 1, (got_reads, got_turns)
        # the same engine with the tracer on mirrors every span it writes
        events.clear()
        events.enable()
        _saturate(engine, model[0].vocab_size, n=6, seed=6)
        events.disable()
        made = set(_CountingTraceMe.made)
        assert set(PHASES) | set(JOIN_PARTS) | set(STEP_LEAVES) \
            | set(PREFILL_LEAVES) <= made
        # what opened is closed, but for the turn in hand
        time.sleep(0.1)
        assert 0 <= len(_CountingTraceMe.made) - _CountingTraceMe.exited <= 1
    finally:
        events.clear()
        if was:
            events.enable()


def test_a_profiler_capture_holds_the_engine_phases(model, engine, tmp_path):
    from jax.profiler import ProfileData

    names = ("decode.admit", "decode.join", "decode.prefill", "decode.step",
             "decode.step.enqueue", "decode.step.fetch", "decode.step.count",
             "decode.commit")
    was = events.TRACER.enabled
    events.disable()
    events.clear()
    try:
        # the tracer runs wholly inside the capture: its every span lies
        # inside it, and the edge is where tracing goes off
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # as the benchmark captures
        options.host_tracer_level = 1
        with jax.profiler.trace(str(tmp_path), profiler_options=options):
            events.enable()
            _saturate(engine, model[0].vocab_size, seed=7)
            events.disable()
        traced = _engine_spans(_events(), engine)
    finally:
        events.clear()
        if was:
            events.enable()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    native = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    native[e.name] = native.get(e.name, 0) + 1
    for name in names:
        want = sum(e["name"] == name for e in traced)
        assert want > 0, name
        assert abs(native.get(name, 0) - want) <= 1, (name, native.get(name),
                                                      want)


def test_decode_request_is_an_async_pair_and_survives_merge(
        model, engine, tracing, tmp_path):
    futs = _saturate(engine, model[0].vocab_size, n=6, seed=1)
    events.disable()
    evs = _events()
    assert not [e for e in evs if e["name"] == "decode.request"
                and e["ph"] == "X"]
    begins = {e["id"]: e for e in evs
              if e["name"] == "decode.request" and e["ph"] == "b"}
    ends = {e["id"]: e for e in evs
            if e["name"] == "decode.request" and e["ph"] == "e"}
    assert len(begins) == len(futs) and set(begins) == set(ends)
    for rid, b in begins.items():
        assert {"endpoint", "seq", "tokens", "prompt_len", "waited_s",
                "ttft_s"} <= set(b["args"])
        assert ends[rid]["ts"] >= b["ts"]
        assert b["args"]["ttft_s"] * 1e6 <= ends[rid]["ts"] - b["ts"] + 1.0
    shard = events.save_shard(str(tmp_path))
    merged = merge.merge_traces([shard])["traceEvents"]
    kept = [e for e in merged if e["name"] == "decode.request"]
    assert sorted(e["ph"] for e in kept) == ["b"] * 6 + ["e"] * 6
    assert {e["id"] for e in kept} == set(begins)
    json.dumps(merged)


def test_emit_async_drops_both_halves_or_neither():
    t = events.Tracer(max_events=3)
    t.enable()
    t.emit_async("r", "a", 1.0, 0.5, args={"k": 1})   # M + b + e
    t.emit_async("r", None, 2.0, 0.5)                  # no room for two
    evs = t.to_chrome_trace()["traceEvents"]
    assert [e["ph"] for e in evs] == ["M", "b", "e"]
    assert evs[1]["id"] == evs[2]["id"] == "a"
    assert evs[2]["ts"] - evs[1]["ts"] == pytest.approx(0.5e6)
    assert t.dropped == 2
    t2 = events.Tracer()
    t2.enable()
    t2.emit_async("r", None, 1.0, 0.1)
    t2.emit_async("r", None, 1.0, 0.1)
    ids = [e["id"] for e in t2.to_chrome_trace()["traceEvents"]
           if e["ph"] == "b"]
    assert len(set(ids)) == 2


def test_tracer_off_appends_nothing_and_reads_no_phase_clock(model, engine):
    was = events.TRACER.enabled
    events.disable()
    events.clear()
    try:
        _saturate(engine, model[0].vocab_size, n=6, seed=2)
        _frame_pass()
        assert _events() == []
        assert engine._t_mark is None
    finally:
        if was:
            events.enable()


def test_first_token_time_reaches_the_caller(model, engine):
    def ttft():
        return [d for d in REGISTRY.snapshot()
                if d["name"] == "tftpu_decode_ttft_seconds"][0]

    before = ttft()
    fut = engine.submit({"prompt": np.arange(5, dtype=np.int32)})
    assert fut.t_submit is not None
    out = fut.result(120)
    after = ttft()
    assert set(out) == {"tokens"}
    assert fut.t_submit <= fut.t_first_token <= fut.t_done
    assert after["count"] - before["count"] == 1
    assert after["sum"] - before["sum"] == pytest.approx(
        fut.t_first_token - fut.t_submit, abs=1e-9)


# -- the frame pass -------------------------------------------------------

def _frame_pass(devices=None, blocks=3, rows=8, weight=None):
    """map_blocks then reduce_blocks over a frame of ``blocks`` blocks,
    resident (and, given devices, sharded) as the benchmark's is. With
    a ``weight`` the map program closes over it, as a model over its
    parameters."""
    from tensorframes_tpu import dtypes as dt
    from tensorframes_tpu.frame import TensorFrame
    from tensorframes_tpu.parallel.mesh import batch_sharding, make_mesh
    from tensorframes_tpu.schema import ColumnInfo, Schema
    from tensorframes_tpu.shape import Shape

    devices = devices or jax.devices()[:1]
    mesh = make_mesh(devices=devices)
    sharding = batch_sharding(mesh, 2)
    data = [{"x": jax.device_put(
        np.arange(rows * 4, dtype=np.float32).reshape(rows, 4) + i, sharding)}
        for i in range(blocks)]
    frame = TensorFrame(
        data, Schema([ColumnInfo("x", dt.float32, Shape((-1, 4)))]))
    frame._mesh, frame._axis = mesh, mesh.axis_names[0]
    if weight is None:
        weight = 2.0
    score = tfs.compile_program(lambda x: {"y": x * weight + 1.0}, frame)
    total = tfs.compile_program(
        lambda y_input: {"y": y_input.sum(axis=0)},
        tfs.map_blocks(score, frame), reduce_mode="blocks")
    out = tfs.reduce_blocks(total, tfs.map_blocks(score, frame))
    want = sum((np.asarray(b["x"]) * weight + 1.0).sum(axis=0) for b in data)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-6)
    return frame, score, total


def test_sharded_frame_pass_spans_nest(tracing):
    assert len(jax.devices()) >= 4
    _frame_pass(jax.devices()[:4])          # compiles outside the check
    events.clear()
    _frame_pass(jax.devices()[:4])
    events.disable()
    tid = threading.get_ident()
    spans = sorted((e for e in _events()
                    if e.get("ph") == "X" and e["tid"] == tid),
                   key=lambda e: (e["ts"], -e["dur"]))
    _assert_stack_discipline(spans)
    by = {}
    for e in spans:
        by.setdefault(e["name"], []).append(e)

    def inside(c, p):
        return (p["ts"] - 0.5 <= c["ts"]
                and c["ts"] + c["dur"] <= p["ts"] + p["dur"] + 0.5)

    verb = by["reduce_blocks"][-1]
    assert [e["args"]["block"] for e in by["plan.reduce.gather"]] == [0, 1, 2]
    assert [e["args"]["block"] for e in by["plan.reduce.fetch"]] == [0, 1, 2]
    assert all(e["args"]["bytes"] == 16 for e in by["plan.reduce.fetch"])
    (combine,) = by["plan.reduce.combine"]
    assert combine["args"]["partials"] == 3
    # one block program per block, one combine program per call
    assert len(by["executor.prepare"]) == len(by["executor.run_block"]) == 4
    assert all({"kind", "compiled"} <= set(e["args"])
               for e in by["executor.prepare"])
    assert all("synced" in e["args"] for e in by["executor.run_block"])
    (fetch,) = by["executor.fetch"]         # the combine run's to_numpy
    assert fetch["args"]["bytes"] == 16
    for name in ("plan.reduce.gather", "plan.reduce.fetch",
                 "plan.reduce.combine", "executor.prepare",
                 "executor.run_block", "executor.fetch"):
        assert all(inside(e, verb) for e in by[name]), name
    in_combine = [e for name in ("executor.prepare", "executor.run_block",
                                 "executor.fetch")
                  for e in by[name] if inside(e, combine)]
    assert sorted(e["name"] for e in in_combine) == [
        "executor.fetch", "executor.prepare", "executor.run_block"]
    # gather, prepare, run, fetch of one block follow each other
    for g, p, r, f in zip(by["plan.reduce.gather"], by["executor.prepare"],
                          by["executor.run_block"], by["plan.reduce.fetch"]):
        assert g["ts"] <= p["ts"] <= r["ts"] <= f["ts"]


# -- where a hoisted program's constants live (ISSUE 35) --------------------

WEIGHT = np.linspace(0.5, 2.0, 4, dtype=np.float32)


def _counter(name):
    return sum(d["value"] for d in REGISTRY.snapshot() if d["name"] == name)


def _placed():
    return (_counter("tftpu_executor_const_placements_total"),
            _counter("tftpu_executor_const_placed_bytes_total"))


def _entries_with_consts(*programs):
    """Every hoisted entry that holds constants, of these programs and
    of the fused programs the plan built over them."""
    from tensorframes_tpu.plan import lower

    compiled = [p.compiled() for p in programs]
    compiled += [fused.compiled()
                 for fused, pinned in lower._FUSED_CACHE.values()
                 if any(p in pinned for p in programs)]
    return [e for c in compiled for e in c._hoisted.values()
            if e and jax.tree_util.tree_leaves(e.consts)]


def _assert_replicated_over(entries, devices):
    assert entries
    for entry in entries:
        for leaf in jax.tree_util.tree_leaves(entry.consts):
            assert leaf.committed
            assert leaf.sharding.is_fully_replicated
            assert leaf.sharding.device_set == set(devices)


def test_sharded_pass_places_constants_on_the_mesh_once(tracing):
    devices = jax.devices()[:4]
    n0, b0 = _placed()
    frame, score, total = _frame_pass(devices, blocks=2, weight=WEIGHT)
    entries = _entries_with_consts(score, total)
    _assert_replicated_over(entries, devices)
    n1, b1 = _placed()
    # the fused map_reduce program holds the weight; the reduce none
    assert n1 - n0 == len(entries) >= 1
    assert b1 - b0 == len(entries) * WEIGHT.nbytes * 4
    built = [e["args"] for e in _events()
             if e["name"] == "executor.prepare"
             and "const_bytes" in e["args"]]
    assert [a["const_bytes"] for a in built] == [WEIGHT.nbytes] * len(entries)
    for a in built:
        placement = json.loads(a["placement"])
        assert placement["spec"] == []
        assert placement["mesh"]["devices"] == [d.id for d in devices]
    # the same programs again: no weight moves between devices, the
    # buffers are the ones placed at construction, nothing is re-placed
    buffers = [id(leaf) for e in entries
               for leaf in jax.tree_util.tree_leaves(e.consts)]
    with jax.transfer_guard_device_to_device("disallow"):
        out = tfs.reduce_blocks(total, tfs.map_blocks(score, frame))
    one, one_score, one_total = _frame_pass(blocks=2, weight=WEIGHT)
    np.testing.assert_allclose(          # the unsharded pass's result
        np.asarray(out),
        np.asarray(tfs.reduce_blocks(one_total,
                                     tfs.map_blocks(one_score, one))),
        rtol=1e-6)
    assert _placed() == (n1 + 1, b1 + WEIGHT.nbytes)   # that pass's own
    assert buffers == [id(leaf) for e in entries
                       for leaf in jax.tree_util.tree_leaves(e.consts)]


def _weighted_program():
    from tensorframes_tpu import dtypes as dt
    from tensorframes_tpu.shape import Shape

    return Program(lambda feeds: {"y": feeds["x"] * WEIGHT + 1.0},
                   [TensorSpec("x", dt.float32, Shape((-1, 4)))])


@pytest.mark.parametrize("case", ["sharded", "single_device", "trivial",
                                  "donating"])
def test_constants_follow_the_feeds(case, monkeypatch):
    """One dispatch path, told apart by what the feeds carry: constants
    replicated over a mesh, committed to the one device a feed sits on,
    or uncommitted on the default device as ever."""
    from tensorframes_tpu.ops import executor
    from tensorframes_tpu.parallel.mesh import batch_sharding, make_mesh

    x = np.arange(32, dtype=np.float32).reshape(8, 4)
    want = x * WEIGHT + 1.0
    compiled = _weighted_program().compiled()
    donate = case == "donating"
    if donate:       # XLA:CPU ignores the donation; the variant is built
        monkeypatch.setattr(executor, "donation_supported", lambda: True)
    if case == "trivial":
        feed, devices = x, None
    elif case == "single_device":
        devices = [jax.devices()[2]]
        feed = jax.device_put(x, devices[0])
    else:
        devices = jax.devices()[:4]
        feed = jax.device_put(x, batch_sharding(make_mesh(devices=devices), 2))

    def run(v):
        return compiled.run_block({"x": v}, to_numpy=False, donate=donate)["y"]

    n0, _ = _placed()
    np.testing.assert_allclose(np.asarray(run(feed)), want, rtol=1e-6)
    (entry,) = compiled._hoisted.values()
    leaves = jax.tree_util.tree_leaves(entry.consts)
    if devices is None:
        assert entry.placement is None
        assert not any(leaf.committed for leaf in leaves)
        # feeds that turn up elsewhere get an entry of their own there
        other = jax.devices()[3]
        np.testing.assert_allclose(
            np.asarray(run(jax.device_put(x, other))), want, rtol=1e-6)
        _assert_replicated_over(
            [e for e in compiled._hoisted.values() if e is not entry],
            [other])
        np.testing.assert_allclose(np.asarray(run(x)), want, rtol=1e-6)
        assert _placed()[0] - n0 == 2
        return
    _assert_replicated_over([entry], devices)
    assert _placed()[0] - n0 == 1
    again = jax.device_put(x, feed.sharding)
    with jax.transfer_guard_device_to_device("disallow"):
        got = run(again)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)
    assert not any(leaf.is_deleted() for leaf in leaves)
    assert _placed()[0] - n0 == 1
    assert [id(leaf) for leaf in leaves] == [
        id(leaf) for leaf in jax.tree_util.tree_leaves(entry.consts)]


def _module_names(program):
    """The XLA module name of each executable the program holds, read
    from its lowered text."""
    names = set()
    for entry in program.compiled()._hoisted.values():
        if entry:
            text = entry.jitted.lower(
                entry.consts, entry._flat_abstract).as_text()
            names.add(re.search(r"module @(\S+)", text).group(1))
    return names


def test_frame_programs_have_distinct_module_names():
    from tensorframes_tpu import dtypes as dt
    from tensorframes_tpu.plan import lower
    from tensorframes_tpu.shape import Shape

    frame, score, total = _frame_pass()
    tfs.map_blocks(score, frame).blocks()   # the map program on its own
    assert _module_names(score) == {"jit_tftpu_map_block"}
    assert _module_names(total) == {"jit_tftpu_reduce_block"}
    fused = set()
    for program, _pinned in lower._FUSED_CACHE.values():
        if total in _pinned:
            fused |= _module_names(program)
    assert fused == {"jit_tftpu_map_reduce_block"}
    # the rows entry of the same program is another executable
    assert score.compiled().module_name("vmap") == "tftpu_map_rows"
    # a reduce labels its own analysed copy, never the caller's Program
    raw = Program(lambda feeds: {"y": feeds["y_input"].sum(axis=0)},
                  [TensorSpec("y_input", dt.float32, Shape((-1, 4)))])
    tfs.reduce_blocks(raw, tfs.map_blocks(score, frame))
    assert raw.role == "map"


def test_scope_names_in_lowered_text(model):
    cfg, params = model
    step = gen.paged_decode_step_fn(cfg, 8, 3)
    pool = gen.init_paged_kv(cfg, 4, 8)
    text = jax.jit(step).lower(
        params, pool, jnp.zeros(2, jnp.int32), jnp.zeros(2, jnp.int32),
        jnp.zeros((2, 3), jnp.int32)).as_text(debug_info=True)
    for scope in ("embed", "layer_0/attn", "layer_0/kv_write",
                  "layer_0/mlp", f"layer_{cfg.num_layers - 1}/mlp", "head"):
        assert scope in text, scope
    icfg = inc.tiny()
    iparams = inc.init_params(icfg, seed=0)
    images = jnp.zeros((1, icfg.image_size, icfg.image_size, 3), jnp.float32)
    text = jax.jit(lambda p, x: inc.forward(icfg, p, x)).lower(
        iparams, images).as_text(debug_info=True)
    for scope in ("stem", "mixed_5b", "mixed_5d", "mixed_6a", "mixed_6e",
                  "mixed_7a", "mixed_7c", "logits"):
        assert scope in text, scope

"""Resilience-subsystem tests: fault injection, retry policies, NaN
guards, checkpoint integrity (CRC manifest, truncation fallback, orphan
GC), and the fault sites wired through the executor / io / checkpoint
layers — the guarantees the reference delegated to Spark task retry
(SURVEY.md §5) re-owned natively."""

import os
import time
import zlib

import jax.numpy as jnp
import numpy as np
import pytest

import tensorframes_tpu as tfs
from tensorframes_tpu.checkpoint import Checkpointer, CheckpointCorruptionError
from tensorframes_tpu.resilience import (
    AttemptTimeout,
    NonFiniteError,
    RetryError,
    RetryPolicy,
    StepGuard,
    active_sites,
    fault_point,
    inject,
    retry_call,
    retryable,
    tree_all_finite,
)


# ---------------------------------------------------------------------------
# faults.py
# ---------------------------------------------------------------------------

def test_fault_point_noop_when_unarmed():
    fault_point("executor.run_block")  # no injection: must not raise
    assert active_sites() == ()


def test_inject_every_n_deterministic():
    with inject("t.site", OSError, every_n=3) as inj:
        outcomes = []
        for _ in range(9):
            try:
                fault_point("t.site")
                outcomes.append("ok")
            except OSError:
                outcomes.append("err")
    assert outcomes == ["ok", "ok", "err"] * 3
    assert inj.hits == 9 and inj.fired == 3
    fault_point("t.site")  # disarmed on exit


def test_inject_after_and_max_times():
    with inject("t.site", RuntimeError, every_n=1, after=2, max_times=2) as inj:
        fired = 0
        for _ in range(10):
            try:
                fault_point("t.site")
            except RuntimeError:
                fired += 1
    assert fired == 2 and inj.fired == 2
    assert inj.hits == 10


def test_inject_probabilistic_is_reproducible():
    def run():
        hits = []
        with inject("t.site", ValueError, p=0.5, seed=42):
            for _ in range(20):
                try:
                    fault_point("t.site")
                    hits.append(0)
                except ValueError:
                    hits.append(1)
        return hits

    a, b = run(), run()
    assert a == b  # seeded PRNG: bit-for-bit replay
    assert 0 < sum(a) < 20  # actually fires sometimes, not always


def test_inject_error_instance_vs_class():
    sentinel = OSError("the very one")
    with inject("t.site", sentinel):
        with pytest.raises(OSError) as ei:
            fault_point("t.site")
        assert ei.value is sentinel
    with inject("t.site", ConnectionError):
        with pytest.raises(ConnectionError, match="t.site"):
            fault_point("t.site")


def test_inject_site_isolation_and_introspection():
    with inject("t.a", OSError):
        assert active_sites() == ("t.a",)
        fault_point("t.b")  # other sites unaffected
        with pytest.raises(OSError):
            fault_point("t.a")


def test_executor_site_fires_through_verbs():
    frame = tfs.frame_from_arrays({"x": np.arange(8.0)}, num_blocks=2)
    with inject("executor.run_block", OSError, every_n=1):
        with pytest.raises(OSError):
            # verbs are lazy: materialize inside the injection scope
            tfs.map_blocks(lambda x: {"y": x * 2.0}, frame).column_values("y")
    out = tfs.map_blocks(lambda x: {"y": x * 2.0}, frame)  # disarmed
    np.testing.assert_array_equal(out.column_values("y"), np.arange(8.0) * 2)


def test_io_frame_sites_fire(tmp_path):
    frame = tfs.frame_from_arrays({"x": np.arange(4.0)})
    with inject("io.save_frame", OSError):
        with pytest.raises(OSError):
            tfs.save_frame(frame, str(tmp_path / "fr"))
    tfs.save_frame(frame, str(tmp_path / "fr"))
    with inject("io.load_frame", OSError):
        with pytest.raises(OSError):
            tfs.load_frame(str(tmp_path / "fr"))


# ---------------------------------------------------------------------------
# retry.py
# ---------------------------------------------------------------------------

def test_retry_absorbs_transient_then_succeeds():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError("transient")
        return "done"

    out = retry_call(flaky, policy=RetryPolicy(max_attempts=5, backoff=0.001))
    assert out == "done" and len(calls) == 3


def test_retry_exhaustion_raises_retry_error_with_cause():
    def always():
        raise ConnectionError("down")

    with pytest.raises(RetryError) as ei:
        retry_call(always, policy=RetryPolicy(max_attempts=3, backoff=0.001))
    assert isinstance(ei.value.__cause__, ConnectionError)


def test_retry_non_retryable_propagates_immediately():
    calls = []

    def bug():
        calls.append(1)
        raise ValueError("a real bug")

    with pytest.raises(ValueError):
        retry_call(bug, policy=RetryPolicy(max_attempts=5, backoff=0.001))
    assert len(calls) == 1  # no second attempt for a classified bug


def test_retry_backoff_schedule_is_deterministic():
    pol = RetryPolicy(backoff=0.1, backoff_max=0.5, jitter=0.5, seed=7)
    import random

    d1 = [pol.delay(k, random.Random(7)) for k in (1, 2, 3, 4)]
    d2 = [pol.delay(k, random.Random(7)) for k in (1, 2, 3, 4)]
    assert d1 == d2
    # exponential up to the cap, jitter bounded
    assert 0.1 <= d1[0] <= 0.15
    assert all(base <= d <= base * 1.5 for d, base in zip(d1, (0.1, 0.2, 0.4, 0.5)))


def test_retry_watchdog_timeout_classified_and_retried():
    slow_calls = []

    def sometimes_hangs():
        slow_calls.append(1)
        if len(slow_calls) == 1:
            time.sleep(3.0)  # wedged first attempt (abandoned by watchdog)
        return "recovered"

    out = retry_call(
        sometimes_hangs,
        policy=RetryPolicy(max_attempts=2, backoff=0.001, timeout=0.2),
    )
    assert out == "recovered"


def test_retry_watchdog_exhaustion():
    with pytest.raises(RetryError) as ei:
        retry_call(
            lambda: time.sleep(2.0),
            policy=RetryPolicy(max_attempts=2, backoff=0.001, timeout=0.05),
        )
    assert isinstance(ei.value.__cause__, AttemptTimeout)


def test_retryable_decorator():
    calls = []

    @retryable(max_attempts=4, backoff=0.001)
    def op(x):
        calls.append(x)
        if len(calls) < 2:
            raise OSError("blip")
        return x + 1

    assert op(41) == 42 and calls == [41, 41]
    with pytest.raises(ValueError):
        retryable(RetryPolicy(), max_attempts=2)  # both forms at once


def test_retry_on_retry_hook_observes_attempts():
    seen = []

    def flaky():
        if len(seen) < 2:
            raise OSError("x")
        return True

    assert retry_call(
        flaky,
        policy=RetryPolicy(max_attempts=5, backoff=0.001),
        on_retry=lambda attempt, exc: seen.append((attempt, type(exc).__name__)),
    )
    assert seen == [(1, "OSError"), (2, "OSError")]


# ---------------------------------------------------------------------------
# guards.py
# ---------------------------------------------------------------------------

def test_tree_all_finite():
    assert tree_all_finite({"a": jnp.ones(3), "b": [np.arange(2), "str"]})
    assert not tree_all_finite({"a": jnp.array([1.0, np.nan])})
    assert not tree_all_finite({"a": np.array([np.inf])})
    assert tree_all_finite({"i": np.array([1, 2], np.int64)})  # ints vacuous
    assert not tree_all_finite(
        {"b": jnp.array([1.0, np.nan], jnp.bfloat16)}
    )  # ml_dtypes leaves are checked too


def test_guard_skip_keeps_prev_state():
    g = StepGuard(policy="skip", max_consecutive=5)
    good = {"w": jnp.ones(2)}
    bad = {"w": jnp.array([1.0, np.nan])}
    state, admitted = g.admit(1, bad, {"loss": 0.5}, prev_state=good)
    assert not admitted and state is good and g.skipped == 1
    state, admitted = g.admit(2, good, {"loss": 0.4}, prev_state=good)
    assert admitted and g.admitted == 1


def test_guard_rollback_returns_last_good_snapshot():
    g = StepGuard(policy="rollback", max_consecutive=5)
    s1 = {"w": jnp.full(2, 1.0)}
    s2 = {"w": jnp.full(2, 2.0)}
    bad = {"w": jnp.full(2, np.nan)}
    g.admit(1, s1, {"loss": 1.0}, prev_state={"w": jnp.zeros(2)})
    g.admit(2, s2, {"loss": 0.9}, prev_state=s1)
    state, admitted = g.admit(3, bad, {"loss": float("nan")}, prev_state=bad)
    assert not admitted and state is s2 and g.rollbacks == 1


def test_guard_raise_policy_and_streak_escalation():
    g = StepGuard(policy="raise")
    with pytest.raises(NonFiniteError):
        g.admit(1, {"w": jnp.array([np.nan])}, {}, prev_state=None)
    g2 = StepGuard(policy="skip", max_consecutive=3)
    good = {"w": jnp.ones(1)}
    bad = {"w": jnp.array([np.nan])}
    g2.admit(1, bad, {}, prev_state=good)
    g2.admit(2, bad, {}, prev_state=good)
    with pytest.raises(NonFiniteError, match="3 consecutive"):
        g2.admit(3, bad, {}, prev_state=good)


def test_guard_metrics_only_check():
    g = StepGuard(policy="skip", check="metrics")
    bad_state = {"w": jnp.array([np.nan])}
    state, admitted = g.admit(1, bad_state, {"loss": 1.0}, prev_state=None)
    assert admitted and state is bad_state  # state not inspected
    _, admitted = g.admit(2, bad_state, {"loss": float("inf")}, prev_state={})
    assert not admitted


def test_guard_coerce_and_validation():
    assert StepGuard.coerce("skip").policy == "skip"
    g = StepGuard(policy="rollback")
    assert StepGuard.coerce(g) is g
    with pytest.raises(ValueError, match="policy"):
        StepGuard(policy="explode")
    with pytest.raises(TypeError):
        StepGuard.coerce(42)


def test_run_resumable_guard_skips_poison_batch(tmp_path):
    """A NaN batch mid-stream must cost one update, not the run: guarded
    training matches training that never saw the poison batch."""
    import jax

    from tensorframes_tpu.training import run_resumable

    @jax.jit
    def step(state, batch):
        new = {"w": state["w"] + batch}
        return new, {"loss": new["w"].sum()}

    clean = [jnp.full((2,), float(i)) for i in range(6)]
    poisoned = list(clean)
    poisoned[3] = jnp.full((2,), np.nan)

    guard = StepGuard(policy="skip", max_consecutive=3)
    got, ran = run_resumable(
        step, {"w": jnp.zeros(2)},
        Checkpointer(str(tmp_path / "a"), backend="npz"),
        poisoned, num_steps=6, save_every=0, guard=guard,
    )
    assert ran == 6 and guard.skipped == 1
    want = sum(float(i) for i in range(6) if i != 3)
    np.testing.assert_allclose(np.asarray(got["w"]), np.full(2, want))


def test_run_resumable_guard_rollback_and_escalation(tmp_path):
    import jax

    from tensorframes_tpu.training import run_resumable

    @jax.jit
    def step(state, batch):
        new = {"w": state["w"] + batch}
        return new, {"loss": new["w"].sum()}

    all_bad = [jnp.full((2,), np.nan)] * 5
    with pytest.raises(NonFiniteError):
        run_resumable(
            step, {"w": jnp.zeros(2)},
            Checkpointer(str(tmp_path / "b"), backend="npz"),
            all_bad, num_steps=5, save_every=0,
            guard=StepGuard(policy="rollback", max_consecutive=3),
        )


def test_train_on_frame_guard_plain_loop():
    """guard= works in the non-checkpointed train_on_frame path too."""
    import jax

    from tensorframes_tpu.training import train_on_frame

    frame = tfs.frame_from_arrays({"x": np.ones((32, 2), np.float32)})

    calls = []

    @jax.jit
    def _step(state, batch):
        new = {"w": state["w"] + batch["x"].sum()}
        return new, {"loss": new["w"].sum()}

    def step(state, batch):
        calls.append(1)
        if len(calls) == 2:  # poison exactly one update
            return {"w": jnp.full(2, np.nan)}, {"loss": jnp.float32(np.nan)}
        return _step(state, batch)

    guard = StepGuard(policy="skip", max_consecutive=4)
    state, ran = train_on_frame(
        step, {"w": jnp.zeros(2)}, frame, ["x"], batch_size=8,
        num_steps=4, prefetch=0, shuffle=False, guard=guard,
    )
    assert ran == 4 and guard.skipped == 1
    assert np.all(np.isfinite(np.asarray(state["w"])))


# ---------------------------------------------------------------------------
# checkpoint integrity
# ---------------------------------------------------------------------------

def _save_steps(root, steps, backend="npz"):
    ck = Checkpointer(str(root), backend=backend)
    for s in steps:
        ck.save(s, {"w": jnp.full((4,), float(s))})
    return ck


def test_manifest_records_crc_and_size(tmp_path):
    import json

    _save_steps(tmp_path / "run", [1])
    with open(tmp_path / "run" / "step_1" / "manifest.json") as f:
        manifest = json.load(f)
    entry = manifest[0]
    assert entry["nbytes"] == 4 * np.dtype(np.float64).itemsize or entry["nbytes"] > 0
    # crc matches an independent recomputation of the payload bytes
    assert entry["crc32"] == zlib.crc32(
        np.ascontiguousarray(np.full((4,), 1.0, np.dtype(entry["dtype"]))).tobytes()
    )


def test_truncated_newest_falls_back_to_previous(tmp_path):
    ck = _save_steps(tmp_path / "run", [1, 2, 3])
    payload = tmp_path / "run" / "step_3" / "arrays.npz"
    data = payload.read_bytes()
    payload.write_bytes(data[: len(data) // 2])
    got = ck.restore(like={"w": jnp.zeros(4)})
    np.testing.assert_array_equal(np.asarray(got["w"]), np.full(4, 2.0))


def test_crc_mismatch_falls_back(tmp_path):
    """A bit-rotted payload that is still a VALID zip is caught by the
    per-array CRC, not just by zipfile structure checks."""
    ck = _save_steps(tmp_path / "run", [1, 2])
    # rewrite step_2's payload with same-shape wrong bytes
    np.savez_compressed(
        tmp_path / "run" / "step_2" / "arrays.npz",
        a0=np.zeros(32, np.uint8),
    )
    got = ck.restore(like={"w": jnp.zeros(4)})
    np.testing.assert_array_equal(np.asarray(got["w"]), np.full(4, 1.0))


def test_explicit_step_corruption_raises(tmp_path):
    ck = _save_steps(tmp_path / "run", [1, 2])
    (tmp_path / "run" / "step_2" / "arrays.npz").write_bytes(b"garbage")
    with pytest.raises(CheckpointCorruptionError):
        ck.restore(step=2, like={"w": jnp.zeros(4)})
    # the older step is still explicitly restorable
    got = ck.restore(step=1, like={"w": jnp.zeros(4)})
    np.testing.assert_array_equal(np.asarray(got["w"]), np.full(4, 1.0))


def test_all_steps_corrupt_raises(tmp_path):
    ck = _save_steps(tmp_path / "run", [1, 2])
    for s in (1, 2):
        (tmp_path / "run" / f"step_{s}" / "arrays.npz").write_bytes(b"x")
    with pytest.raises(CheckpointCorruptionError, match="no intact checkpoint"):
        ck.restore(like={"w": jnp.zeros(4)})


def test_corrupt_manifest_falls_back(tmp_path):
    ck = _save_steps(tmp_path / "run", [1, 2])
    (tmp_path / "run" / "step_2" / "manifest.json").write_text("{not json")
    got = ck.restore(like={"w": jnp.zeros(4)})
    np.testing.assert_array_equal(np.asarray(got["w"]), np.full(4, 1.0))


def test_verify_audit_mode(tmp_path):
    ck = _save_steps(tmp_path / "run", [1, 2, 3])
    (tmp_path / "run" / "step_2" / "arrays.npz").write_bytes(b"zzz")
    report = ck.verify()
    assert report[1]["ok"] is True and report[3]["ok"] is True
    assert report[2]["ok"] is False and report[2]["errors"]
    assert ck.verify(2)[2]["ok"] is False
    # verify is read-only: the corrupted step is still on disk
    assert ck.all_steps() == [1, 2, 3]


def test_orphaned_tmp_gc_on_init(tmp_path):
    import subprocess
    import sys

    root = tmp_path / "run"
    _save_steps(root, [1])
    # the corpse's owner is a process that has exited and been reaped: a
    # fixed pid (this test once named 9999) may belong to a live process
    dead = subprocess.Popen([sys.executable, "-c", "pass"])
    dead.wait()
    corpse = root / f"step_5.tmp{dead.pid}"
    corpse.mkdir()
    (corpse / "arrays.npz").write_bytes(b"partial")
    ck = Checkpointer(str(root), backend="npz")
    assert not corpse.exists()
    assert ck.all_steps() == [1]  # real steps untouched


def test_save_restore_under_injected_io_faults(tmp_path):
    """Transient IO faults (fail every 2nd attempt) are absorbed by the
    retry policy: every save and restore succeeds."""
    ck = Checkpointer(
        str(tmp_path / "run"), backend="npz",
        retry=RetryPolicy(max_attempts=3, backoff=0.001),
    )
    with inject("checkpoint.save", OSError, every_n=2) as inj:
        for s in (1, 2, 3, 4):
            ck.save(s, {"w": jnp.full((2,), float(s))})
    assert inj.fired >= 1  # faults really happened
    assert ck.all_steps() == [1, 2, 3, 4]
    with inject("checkpoint.restore", OSError, every_n=2) as inj:
        for s in (1, 2, 3, 4):
            got = ck.restore(step=s, like={"w": jnp.zeros(2)})
            np.testing.assert_array_equal(
                np.asarray(got["w"]), np.full(2, float(s))
            )
    assert inj.fired >= 1


def test_unretried_fault_propagates(tmp_path):
    ck = Checkpointer(str(tmp_path / "run"), backend="npz")  # no retry
    with inject("checkpoint.save", OSError, every_n=1):
        with pytest.raises(OSError):
            ck.save(1, {"w": jnp.ones(2)})
    assert ck.all_steps() == []  # nothing published


def test_run_resumable_survives_transient_save_faults(tmp_path):
    """End-to-end: periodic checkpoint saves hit every-2nd-attempt IO
    faults; the retrying checkpointer absorbs them and training output
    matches a fault-free run."""
    import jax

    from tensorframes_tpu.training import run_resumable

    @jax.jit
    def step(state, batch):
        new = {"w": state["w"] + batch}
        return new, {"loss": new["w"].sum()}

    batches = [jnp.full((2,), float(i)) for i in range(8)]
    ck = Checkpointer(
        str(tmp_path / "run"), backend="npz",
        retry=RetryPolicy(max_attempts=3, backoff=0.001),
    )
    with inject("checkpoint.save", OSError, every_n=2) as inj:
        got, ran = run_resumable(
            step, {"w": jnp.zeros(2)}, ck, batches, num_steps=8, save_every=2
        )
    assert ran == 8 and inj.fired >= 1
    np.testing.assert_allclose(np.asarray(got["w"]), np.full(2, sum(range(8))))
    assert ck.latest_step() == 8


# ---------------------------------------------------------------------------
# prefetch device-put retry
# ---------------------------------------------------------------------------

def test_prefetch_retry_absorbs_device_put_faults():
    from tensorframes_tpu import io as tfio

    frame = tfs.frame_from_arrays({"x": np.arange(16.0)})
    with inject("io.prefetch.device_put", OSError, every_n=2) as inj:
        out = list(
            tfio.prefetch_to_device(
                tfio.iterate_batches(frame, batch_size=4),
                size=2,
                retry=RetryPolicy(max_attempts=3, backoff=0.001),
            )
        )
    assert len(out) == 4 and inj.fired >= 1
    got = np.concatenate([np.asarray(b["x"]) for b in out])
    np.testing.assert_array_equal(got, np.arange(16.0))


def test_prefetch_unretried_fault_propagates():
    from tensorframes_tpu import io as tfio

    frame = tfs.frame_from_arrays({"x": np.arange(8.0)})
    with inject("io.prefetch.device_put", OSError, every_n=1):
        with pytest.raises(OSError):
            list(
                tfio.prefetch_to_device(
                    tfio.iterate_batches(frame, batch_size=4), size=2
                )
            )


# ---------------------------------------------------------------------------
# review-fix regressions
# ---------------------------------------------------------------------------

def test_run_resumable_resumes_past_corrupted_newest(tmp_path):
    """A relaunch whose newest checkpoint is torn must fall back to the
    previous intact step and still converge to the uninterrupted result
    (restore_latest + matching batch replay)."""
    import jax

    from tensorframes_tpu.training import run_resumable

    @jax.jit
    def step(state, batch):
        new = {"w": state["w"] * 1.01 + batch}
        return new, {"loss": new["w"].sum()}

    batches = [jnp.full((2,), float(i), jnp.float32) for i in range(10)]
    init = {"w": jnp.zeros(2, jnp.float32)}
    ck = Checkpointer(str(tmp_path / "run"), backend="npz")
    run_resumable(step, init, ck, batches, num_steps=6, save_every=2)
    assert ck.latest_step() == 6
    # tear the newest step, as a crash mid-write would
    payload = tmp_path / "run" / "step_6" / "arrays.npz"
    payload.write_bytes(payload.read_bytes()[:10])
    got, ran = run_resumable(step, init, ck, batches, num_steps=10, save_every=2)
    assert ran == 6  # resumed from step 4, not 6
    ref, _ = run_resumable(
        step, init, Checkpointer(str(tmp_path / "ref"), backend="npz"),
        batches, num_steps=10, save_every=100,
    )
    np.testing.assert_array_equal(np.asarray(got["w"]), np.asarray(ref["w"]))


def test_train_on_frame_resumes_past_corrupted_newest(tmp_path):
    """The host-side replay fast-forward must skip to the step that
    actually restores (latest_intact_step), not the torn latest."""
    import jax

    import tensorframes_tpu.training as tn

    frame = tfs.frame_from_arrays(
        {"x": np.arange(64, dtype=np.float32).reshape(16, 4)}
    )

    @jax.jit
    def step(state, batch):
        new = {"w": state["w"] * 1.01 + batch["x"].sum()}
        return new, {"loss": new["w"].sum()}

    init = {"w": jnp.zeros((), jnp.float32)}
    ck = Checkpointer(str(tmp_path / "run"), backend="npz")
    tn.train_on_frame(step, init, frame, ["x"], batch_size=4, num_steps=3,
                      checkpointer=ck, save_every=1, shuffle=False, prefetch=0)
    payload = tmp_path / "run" / "step_3" / "arrays.npz"
    payload.write_bytes(payload.read_bytes()[:10])
    got, ran = tn.train_on_frame(
        step, init, frame, ["x"], batch_size=4, num_steps=4,
        checkpointer=ck, save_every=1, shuffle=False, prefetch=0,
    )
    assert ran == 2  # resumed from intact step 2, re-ran 3 and 4
    ref, _ = tn.train_on_frame(
        step, init, frame, ["x"], batch_size=4, num_steps=4,
        checkpointer=Checkpointer(str(tmp_path / "ref"), backend="npz"),
        save_every=100, shuffle=False, prefetch=0,
    )
    np.testing.assert_array_equal(np.asarray(got["w"]), np.asarray(ref["w"]))


def test_guard_raise_emergency_checkpoint_is_finite(tmp_path):
    """When the guard aborts on NaN, the save-before-raise emergency
    checkpoint must hold the last GOOD state — resuming from a poisoned
    checkpoint would recreate the crash loop forever."""
    import jax

    from tensorframes_tpu.training import run_resumable

    @jax.jit
    def step(state, batch):
        new = {"w": state["w"] + batch}
        return new, {"loss": new["w"].sum()}

    batches = [jnp.full((2,), v, jnp.float32)
               for v in (1.0, 2.0, np.nan, 4.0)]
    ck = Checkpointer(str(tmp_path / "run"), backend="npz")
    with pytest.raises(NonFiniteError):
        run_resumable(
            step, {"w": jnp.zeros(2, jnp.float32)}, ck, batches,
            num_steps=4, save_every=0, guard="raise",
        )
    assert ck.latest_step() == 2  # the last admitted step, not the NaN one
    got = ck.restore(like={"w": jnp.zeros(2, jnp.float32)})
    assert np.isfinite(np.asarray(got["w"])).all()
    np.testing.assert_array_equal(np.asarray(got["w"]), np.full(2, 3.0))


def test_retry_call_none_policy_is_plain_call():
    calls = []

    def once():
        calls.append(1)
        raise OSError("boom")

    with pytest.raises(OSError):
        retry_call(once, policy=None)
    assert len(calls) == 1  # no surprise retries without an opt-in


def test_tmp_gc_liveness_rules(tmp_path):
    """Init-time GC: spares another LIVE process's temp and this
    process's registered in-flight temp; collects dead-pid corpses AND
    same-pid temps that are not registered — a restarted pid-1 container
    reuses the pid, so unregistered same-pid temps are corpses from the
    previous incarnation, not live saves."""
    import subprocess
    import sys

    from tensorframes_tpu import checkpoint as ckp

    root = tmp_path / "run"
    root.mkdir()
    # same pid, not in the live registry: previous-incarnation corpse
    stale_same_pid = root / f"step_7.tmp{os.getpid()}_deadbeef"
    stale_same_pid.mkdir()
    # same pid, registered: a save in flight on another thread
    in_flight = root / f"step_9.tmp{os.getpid()}_cafef00d"
    in_flight.mkdir()
    ckp._live_tmps.add(str(in_flight))
    # dead foreign pid: corpse
    dead_pid = subprocess.run(
        [sys.executable, "-c", "import os; print(os.getpid())"],
        capture_output=True, text=True,
    ).stdout.strip()
    dead = root / f"step_8.tmp{dead_pid}_cafebabe"
    dead.mkdir()
    # live foreign pid: spared
    sleeper = subprocess.Popen([sys.executable, "-c", "import time; time.sleep(30)"])
    live_foreign = root / f"step_6.tmp{sleeper.pid}_beefcafe"
    live_foreign.mkdir()
    try:
        Checkpointer(str(root), backend="npz")
        assert not stale_same_pid.exists()  # pid-reuse corpse collected
        assert not dead.exists()            # dead corpse collected
        assert in_flight.exists()           # registered in-flight spared
        assert live_foreign.exists()        # live writer spared
    finally:
        ckp._live_tmps.discard(str(in_flight))
        sleeper.kill()


def test_crashed_publish_heals_on_init(tmp_path):
    """A save SIGKILLed between moving the old step aside and publishing
    the new one leaves only step_N.old; the next Checkpointer init must
    rename it back so the step is never lost."""
    root = tmp_path / "run"
    _save_steps(root, [2, 4])
    os.rename(root / "step_4", root / "step_4.old")  # simulate the window
    ck2 = Checkpointer(str(root), backend="npz")
    assert ck2.all_steps() == [2, 4]
    got = ck2.restore(like={"w": jnp.zeros(4)})
    np.testing.assert_array_equal(np.asarray(got["w"]), np.full(4, 4.0))
    # superseded refuse (both dirs present) is deleted, step kept
    _save_steps(root, [6])
    (root / "step_6.old").mkdir()
    Checkpointer(str(root), backend="npz")
    assert not (root / "step_6.old").exists()
    assert (root / "step_6").exists()


def test_resave_same_step_never_leaves_gap(tmp_path):
    """Re-saving an existing step publishes via rename-aside: at no point
    is the step unpublished, and the final content is the new save's."""
    root = tmp_path / "run"
    ck = _save_steps(root, [3])
    ck.save(3, {"w": jnp.full((4,), 99.0)})
    got = ck.restore(step=3, like={"w": jnp.zeros(4)})
    np.testing.assert_array_equal(np.asarray(got["w"]), np.full(4, 99.0))
    assert not (root / "step_3.old").exists()


def test_prefetch_worker_base_exception_surfaces():
    """A BaseException killing the worker must raise in the consumer,
    not truncate the stream into a clean-looking end (silent data loss)."""
    from tensorframes_tpu import io as tfio

    def dying_source():
        yield {"x": np.zeros(2)}
        raise KeyboardInterrupt  # BaseException, not Exception

    it = tfio.prefetch_to_device(dying_source(), size=2)
    next(it)
    with pytest.raises(KeyboardInterrupt):
        next(it)


def test_verify_returns_report_on_transient_read_errors(tmp_path, monkeypatch):
    """verify() must return its report — never raise — even when the
    payload read fails transiently (and keeps failing past the retry
    budget)."""
    _save_steps(tmp_path / "run", [1])
    ck_flaky = Checkpointer(
        str(tmp_path / "run"), backend="npz",
        retry=RetryPolicy(max_attempts=2, backoff=0.001),
    )
    monkeypatch.setattr(
        type(ck_flaky), "_read_npz_payload",
        lambda self, path: (_ for _ in ()).throw(OSError("EIO")),
    )
    report = ck_flaky.verify()
    assert report[1]["ok"] is None  # unknown, not corrupt
    assert any("transient read error" in e for e in report[1]["errors"])


def test_tree_all_finite_sharded_arrays():
    """Guards must actually inspect sharded device arrays (a guard that
    silently passes uncheckable leaves is no guard)."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:8]), ("dp",))
    sh = NamedSharding(mesh, P("dp"))
    good = jax.device_put(jnp.arange(16, dtype=jnp.float32), sh)
    bad = jax.device_put(jnp.full((16,), np.nan, jnp.float32), sh)
    assert tree_all_finite({"w": good})
    assert not tree_all_finite({"w": bad})


# ---------------------------------------------------------------------------
# retry deadline (ISSUE 8 satellite): the total-elapsed cap
# ---------------------------------------------------------------------------

def test_retry_deadline_caps_total_elapsed():
    """A huge attempt budget must not stretch past deadline_s: the cap
    is a wall-clock promise, not an attempt count."""
    calls = {"n": 0}

    def always_flaky():
        calls["n"] += 1
        raise OSError("coordinator not up")

    policy = RetryPolicy(max_attempts=10**6, backoff=0.02, deadline_s=0.4)
    t0 = time.monotonic()
    with pytest.raises(RetryError, match="deadline_s=0.4 exceeded"):
        retry_call(always_flaky, policy=policy, describe="flaky")
    elapsed = time.monotonic() - t0
    assert elapsed < 3.0
    assert calls["n"] >= 2  # it genuinely retried before giving up


def test_retry_deadline_bounds_blocked_attempt():
    """deadline_s arms a watchdog window even when per-attempt timeout
    is unset: a single blocked attempt cannot eat the whole budget and
    then some."""
    policy = RetryPolicy(max_attempts=3, backoff=0.01, deadline_s=0.3)
    t0 = time.monotonic()
    with pytest.raises(RetryError, match="deadline_s"):
        retry_call(lambda: time.sleep(30), policy=policy, describe="wedged")
    assert time.monotonic() - t0 < 5.0


def test_retry_deadline_validation_and_success_path():
    with pytest.raises(ValueError, match="deadline_s"):
        RetryPolicy(deadline_s=0)
    with pytest.raises(ValueError, match="deadline_s"):
        RetryPolicy(deadline_s=-1)
    # a call that succeeds within the deadline is unaffected
    policy = RetryPolicy(max_attempts=3, backoff=0.01, deadline_s=5.0)
    assert retry_call(lambda: 17, policy=policy) == 17


def test_retry_deadline_attempt_cap_still_wins_when_faster():
    """max_attempts exhaustion inside the deadline keeps the classic
    error (the deadline is a cap, not a reclassification)."""
    policy = RetryPolicy(max_attempts=2, backoff=0.001, deadline_s=30.0)
    with pytest.raises(RetryError, match="all 2 attempts failed"):
        retry_call(
            lambda: (_ for _ in ()).throw(OSError("x")), policy=policy,
            describe="quick",
        )


def test_init_distributed_flaky_coordinator_bounded_by_deadline(tmp_path):
    """Subprocess flaky-coordinator drill: every handshake attempt fails
    (distributed.init fault injection), the retry budget is effectively
    infinite, and deadline_s must still bound init_distributed to
    wall-clock seconds."""
    import subprocess
    import sys

    script = """
import time
from tensorframes_tpu.resilience import RetryError, RetryPolicy, inject
from tensorframes_tpu.parallel import init_distributed

t0 = time.monotonic()
with inject("distributed.init", ConnectionError("coordinator down")) as inj:
    try:
        init_distributed(
            coordinator_address="127.0.0.1:1",
            num_processes=2,
            process_id=0,
            retry=RetryPolicy(
                max_attempts=10**6, backoff=0.05, deadline_s=1.0,
            ),
        )
        raise SystemExit("init unexpectedly succeeded")
    except RetryError as e:
        print("BOUNDED", f"{time.monotonic() - t0:.2f}", flush=True)
        print("ATTEMPTS", inj.fired, flush=True)
        assert "deadline_s=1" in str(e), e
"""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    repo = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [__import__("sys").executable, "-c", script], env=env, cwd=repo,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, (
        f"rc={proc.returncode}\nstdout: {proc.stdout}\nstderr: {proc.stderr}"
    )
    assert "BOUNDED" in proc.stdout
    wall = float(proc.stdout.split("BOUNDED")[1].split()[0])
    assert wall < 10.0  # the deadline held (1.0s + scheduling slack)
    assert int(proc.stdout.split("ATTEMPTS")[1].split()[0]) >= 2


# ---------------------------------------------------------------------------
# fault-site registry drift guard (ISSUE 8 satellite): every site name
# instrumented across the package is registered AND documented
# ---------------------------------------------------------------------------

def test_fault_sites_registered_and_documented():
    import pathlib
    import re

    import tensorframes_tpu
    from tensorframes_tpu.resilience import faults as faults_mod

    registered = set(faults_mod.list_sites())
    # 1) every literal site name at an instrumentation point in the
    # package is registered (a new fault_point without register_site is
    # exactly the silent drift this guard exists to catch)
    src_root = pathlib.Path(tensorframes_tpu.__file__).parent
    pat = re.compile(
        r"(?:fault_point|delay_point|kill_point)\(\s*[\"']([\w.]+)[\"']"
    )
    instrumented = set()
    for path in src_root.rglob("*.py"):
        instrumented |= set(pat.findall(path.read_text()))
    missing = instrumented - registered
    assert not missing, (
        f"fault sites instrumented but not registered: {sorted(missing)} "
        "— add faults.register_site(...) next to the instrumentation"
    )
    # 2) the classic SITES tuple stays a subset of the registry
    assert set(faults_mod.SITES) <= registered
    # 3) every registered site is documented in docs/resilience.md
    docs = (
        pathlib.Path(__file__).parent.parent / "docs" / "resilience.md"
    ).read_text()
    undocumented = [s for s in sorted(registered) if s not in docs]
    assert not undocumented, (
        f"fault sites registered but absent from docs/resilience.md: "
        f"{undocumented}"
    )


def test_register_site_validates_and_lists_sorted():
    from tensorframes_tpu.resilience import faults as faults_mod

    with pytest.raises(ValueError):
        faults_mod.register_site("", "nowhere")
    sites = faults_mod.list_sites()
    assert list(sites) == sorted(sites)
    assert "executor.dispatch" in sites
    assert "fleet.heartbeat" in sites
    # the serving-fleet chaos sites (ISSUE 13): registered centrally in
    # faults.py so drills see them even before the serving package loads
    assert "router.dispatch" in sites
    assert "serving.replica" in sites


# ---------------------------------------------------------------------------
# delay_point / kill_point semantics
# ---------------------------------------------------------------------------

def test_delay_point_sleeps_instead_of_raising():
    from tensorframes_tpu.resilience import Delay, delay_point

    t0 = time.monotonic()
    with inject("unit.delay", Delay(0.15)):
        delay_point("unit.delay")  # must not raise
    assert time.monotonic() - t0 >= 0.14
    # a non-Delay injection still raises through delay_point
    with inject("unit.delay", RuntimeError("hard fault")):
        with pytest.raises(RuntimeError, match="hard fault"):
            delay_point("unit.delay")


def test_delay_point_noop_unarmed():
    from tensorframes_tpu.resilience import delay_point

    t0 = time.monotonic()
    delay_point("unit.delay")
    assert time.monotonic() - t0 < 0.05


def test_kill_point_sigkills_own_process():
    """kill_point + KillRank must die by SIGKILL — no exception path, no
    cleanup (subprocess-verified; in-process it would kill pytest)."""
    import signal as _signal
    import subprocess

    script = """
from tensorframes_tpu.resilience import KillRank, inject, kill_point
with inject("fleet.rank.kill", KillRank):
    kill_point()
print("SURVIVED", flush=True)
"""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    repo = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [__import__("sys").executable, "-c", script], env=env, cwd=repo,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == -_signal.SIGKILL
    assert "SURVIVED" not in proc.stdout


def test_kill_point_noop_unarmed_and_passthrough():
    from tensorframes_tpu.resilience import kill_point

    kill_point()  # un-armed: a dict check, nothing else
    with inject("fleet.rank.kill", RuntimeError("not a kill")):
        with pytest.raises(RuntimeError, match="not a kill"):
            kill_point()

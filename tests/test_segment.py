"""Custom pallas segment-sum kernel tests (interpreter mode on CPU): the
one-hot MXU formulation must agree with XLA's scatter-based segment_sum
across padding edge cases, and the dispatcher must stay correct."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorframes_tpu.ops import segment


def _ref(values, seg_ids, num_segments):
    return np.asarray(
        jax.ops.segment_sum(values, seg_ids, num_segments=num_segments)
    )


@pytest.mark.parametrize(
    "n,d,s",
    [
        (10, 3, 4),        # everything unaligned
        (256, 128, 8),     # exactly tile-aligned
        (300, 130, 9),     # crosses tile and lane boundaries
        (5, 1, 1),         # single segment, tiny
    ],
)
def test_pallas_matches_xla(n, d, s):
    rng = np.random.default_rng(n + d + s)
    values = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    seg_ids = jnp.asarray(rng.integers(0, s, n), jnp.int32)
    got = np.asarray(
        segment.segment_sum_pallas(values, seg_ids, s, interpret=True)
    )
    np.testing.assert_allclose(got, _ref(values, seg_ids, s), rtol=1e-5, atol=1e-5)


def test_empty_segments_are_zero():
    values = jnp.ones((4, 2), jnp.float32)
    seg_ids = jnp.asarray([0, 0, 3, 3], jnp.int32)
    got = np.asarray(segment.segment_sum_pallas(values, seg_ids, 5, interpret=True))
    np.testing.assert_array_equal(got[1], [0, 0])
    np.testing.assert_array_equal(got[2], [0, 0])
    np.testing.assert_array_equal(got[4], [0, 0])
    np.testing.assert_array_equal(got[0], [2, 2])


def test_unsorted_segment_ids():
    # the kernel does not require key-sorted rows
    values = jnp.asarray([[1.0], [2.0], [4.0], [8.0]], jnp.float32)
    seg_ids = jnp.asarray([1, 0, 1, 0], jnp.int32)
    got = np.asarray(segment.segment_sum_pallas(values, seg_ids, 2, interpret=True))
    np.testing.assert_array_equal(got, [[10.0], [5.0]])


def test_dispatcher_cpu_falls_back_to_xla():
    # on CPU the dispatcher must use XLA (pallas TPU kernels don't run
    # natively here) and still be correct, preserving dtype
    values = jnp.asarray(np.random.default_rng(0).standard_normal((20, 4)))
    seg_ids = jnp.asarray(np.random.default_rng(1).integers(0, 3, 20), jnp.int32)
    got = segment.segment_sum(values, seg_ids, 3)
    assert got.dtype == values.dtype
    np.testing.assert_allclose(np.asarray(got), _ref(values, seg_ids, 3), rtol=1e-6)


def test_aggregate_fast_path_still_correct():
    import tensorframes_tpu as tfs

    rng = np.random.default_rng(2)
    n = 200
    frame = tfs.frame_from_arrays(
        {
            "k": rng.integers(0, 7, n),
            "v": rng.standard_normal(n).astype(np.float32),
        },
        num_blocks=3,
    )
    with tfs.with_graph():
        v_input = tfs.block(frame, "v", tf_name="v_input")
        agg = tfs.aggregate(
            tfs.reduce_sum(v_input, axis=0, name="v"), frame.group_by("k")
        )
    got = {r["k"]: r["v"] for r in agg.collect()}
    ks = np.asarray(frame.column_values("k"))
    vs = np.asarray(frame.column_values("v"))
    for k in np.unique(ks):
        assert got[int(k)] == pytest.approx(float(vs[ks == k].sum()), rel=1e-5)


def test_disable_pallas_kill_switch():
    """The manual switch: with it thrown, segment_sum works through
    XLA's scatter path."""
    was = segment._pallas_disabled
    try:
        segment.disable_pallas("test")
        assert not segment.pallas_enabled()
        values = jnp.asarray(
            np.random.default_rng(0).standard_normal((32, 4)), jnp.float32
        )
        seg_ids = jnp.asarray(
            np.random.default_rng(1).integers(0, 5, 32), jnp.int32
        )
        got = segment.segment_sum(values, seg_ids, 5)
        np.testing.assert_allclose(
            np.asarray(got), _ref(values, seg_ids, 5), rtol=1e-6
        )
    finally:
        segment._pallas_disabled = was


def test_aggregate_surfaces_kernel_compile_failure(monkeypatch):
    """aggregate's segment fast path does not retry: a kernel-compile
    failure in the jitted segment program raises out of the verb, once,
    and the manual switch stays where it was."""
    import tensorframes_tpu as tfs
    from tensorframes_tpu.ops import verbs

    calls = {"n": 0}

    def failing(ops, num_groups):
        def wrapper(vals, sids):
            calls["n"] += 1
            raise RuntimeError("Mosaic failed to compile TPU kernel")

        return wrapper

    monkeypatch.setattr(verbs, "_seg_fast_for", failing)
    # pin the JITTED segment path: on the CPU backend float sums
    # normally take the host bincount lowering (no kernel to fail)
    monkeypatch.setattr(segment, "host_segment_eligible", lambda *a: False)
    rng = np.random.default_rng(3)
    n = 100
    frame = tfs.frame_from_arrays(
        {
            "k": rng.integers(0, 4, n),
            "v": rng.standard_normal(n).astype(np.float32),
        }
    )
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        with tfs.with_graph():
            v_input = tfs.block(frame, "v", tf_name="v_input")
            tfs.aggregate(
                tfs.reduce_sum(v_input, axis=0, name="v"),
                frame.group_by("k"),
            ).collect()
    assert calls["n"] == 1  # failed once, never retried
    assert segment.pallas_enabled()


# ---------------------------------------------------------------------------
# segment_reduce_host edge pins (ISSUE 12 bugfix sweep)
# ---------------------------------------------------------------------------

def test_host_reduce_zero_rows_returns_zeros_and_nan_means():
    """Empty feed: ``np.asarray([])`` is float64 and bincount rejects
    float ids — the host path must short-circuit instead, producing
    zeros for sums and 0/0 → NaN for means (the jitted program's exact
    empty-segment bits), in the value dtype, without warnings."""
    import warnings

    for seg_ids in (np.asarray([], np.int64), []):
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            out = segment.segment_reduce_host(
                (("a", "reduce_sum"), ("b", "reduce_mean")),
                3,
                {"a": np.asarray([], np.float32),
                 "b": np.asarray([], np.float64)},
                seg_ids,
            )
        assert out["a"].dtype == np.float32
        np.testing.assert_array_equal(out["a"], np.zeros(3, np.float32))
        assert out["b"].dtype == np.float64
        assert np.isnan(out["b"]).all()


def test_host_reduce_all_padding_segments_mean_is_silent_nan():
    """Segments past the max observed id (the bucketing shape): means
    read NaN on the padded slots without a numpy warning leaking, and
    the real slots carry the bincount answer."""
    import warnings

    with warnings.catch_warnings():
        warnings.simplefilter("error")
        out = segment.segment_reduce_host(
            (("v", "reduce_mean"),),
            6,
            {"v": np.asarray([2.0, 4.0, 10.0], np.float32)},
            np.asarray([1, 1, 3]),
        )
    assert out["v"][1] == pytest.approx(3.0)
    assert out["v"][3] == pytest.approx(10.0)
    assert np.isnan(out["v"][[0, 2, 4, 5]]).all()


def test_host_reduce_list_seg_ids_cast_to_int():
    """Python-list ids (the eager path can hand them over) bincount
    fine after the intp cast."""
    out = segment.segment_reduce_host(
        (("v", "reduce_sum"),), 2,
        {"v": np.asarray([1.5, 2.5, 4.0], np.float32)},
        [0, 1, 0],
    )
    np.testing.assert_allclose(out["v"], [5.5, 2.5])

"""Sharded on-device aggregate (ops/device_agg.py): dense per-shard
segment reduction + one collective over the 8-device virtual mesh, checked
against the host sort path on the same data."""

import numpy as np
import pytest

import tensorframes_tpu as tfs
from tensorframes_tpu.ops import device_agg


def _dsl_agg(frame, col, op, name=None):
    name = name or col
    with tfs.with_graph():
        v_input = tfs.block(frame, col, tf_name=f"{name}_input")
        fetch = op(v_input, axis=0, name=name)
        return tfs.aggregate(fetch, frame.group_by("k"))


def _rows(agg, keys=("k",)):
    return sorted(
        tuple(r[c] for c in (*keys, *sorted(set(agg.columns) - set(keys))))
        for r in agg.collect()
    )


@pytest.fixture
def data():
    rng = np.random.default_rng(0)
    n = 1000
    return {
        "k": rng.integers(-3, 12, n),
        "v": rng.standard_normal(n).astype(np.float32),
    }


def test_device_path_taken_and_matches_host(data):
    host = tfs.frame_from_arrays(dict(data))
    dev = tfs.frame_from_arrays(dict(data)).to_device()
    assert dev.is_sharded

    for op in (tfs.reduce_sum, tfs.reduce_min, tfs.reduce_max, tfs.reduce_mean):
        a_host = _dsl_agg(host, "v", op)
        a_dev = _dsl_agg(dev, "v", op)
        hk = np.asarray(a_host.column_values("k"))
        dk = np.asarray(a_dev.column_values("k"))
        np.testing.assert_array_equal(hk, dk)  # same group order (lex)
        np.testing.assert_allclose(
            np.asarray(a_dev.column_values("v")),
            np.asarray(a_host.column_values("v")),
            rtol=1e-5, atol=1e-6,
        )


def test_try_aggregate_device_is_used(data, monkeypatch):
    dev = tfs.frame_from_arrays(dict(data)).to_device()
    called = {}
    real = device_agg.try_aggregate_device

    def spy(*a, **kw):
        called["yes"] = True
        return real(*a, **kw)

    monkeypatch.setattr(device_agg, "try_aggregate_device", spy)
    _dsl_agg(dev, "v", tfs.reduce_sum)
    assert called.get("yes")


def test_tail_rows_fold_in(data):
    # 1001 rows over 8 devices → 1 host tail row; result must include it
    d = {k: np.concatenate([v, v[:1]]) for k, v in data.items()}
    host = tfs.frame_from_arrays(dict(d))
    dev = tfs.frame_from_arrays(dict(d)).to_device()
    assert dev.num_blocks == 2  # main + tail
    for op in (tfs.reduce_sum, tfs.reduce_min):
        np.testing.assert_allclose(
            np.asarray(_dsl_agg(dev, "v", op).column_values("v")),
            np.asarray(_dsl_agg(host, "v", op).column_values("v")),
            rtol=1e-5, atol=1e-6,
        )


def test_vector_values(data):
    rng = np.random.default_rng(1)
    d = {"k": data["k"], "v": rng.standard_normal((1000, 3)).astype(np.float32)}
    host = tfs.frame_from_arrays(dict(d))
    dev = tfs.frame_from_arrays(dict(d)).to_device()
    a_host = _dsl_agg(host, "v", tfs.reduce_sum)
    a_dev = _dsl_agg(dev, "v", tfs.reduce_sum)
    np.testing.assert_allclose(
        np.asarray(a_dev.column_values("v")),
        np.asarray(a_host.column_values("v")),
        rtol=1e-5, atol=1e-5,
    )


def test_multi_key_mixed_radix():
    rng = np.random.default_rng(2)
    n = 640
    d = {
        "a": rng.integers(0, 5, n),
        "b": rng.integers(10, 14, n),
        "v": rng.standard_normal(n).astype(np.float32),
    }
    host = tfs.frame_from_arrays(dict(d))
    dev = tfs.frame_from_arrays(dict(d)).to_device()

    def agg(fr):
        with tfs.with_graph():
            v_input = tfs.block(fr, "v", tf_name="v_input")
            return tfs.aggregate(
                tfs.reduce_sum(v_input, axis=0, name="v"),
                fr.group_by("a", "b"),
            )

    ah, ad = agg(host), agg(dev)
    for c in ("a", "b"):
        np.testing.assert_array_equal(
            np.asarray(ah.column_values(c)), np.asarray(ad.column_values(c))
        )
    np.testing.assert_allclose(
        np.asarray(ad.column_values("v")),
        np.asarray(ah.column_values("v")),
        rtol=1e-5, atol=1e-6,
    )


def test_huge_key_span_rides_dictionary_plan(data):
    # keys spanning > _KEY_LIMIT buckets exceed the dense plan but ride
    # the dictionary plan (K = #groups); results still match via the
    # public API
    d = dict(data)
    d["k"] = d["k"].copy()
    d["k"][0] = 5_000_000
    dev = tfs.frame_from_arrays(dict(d)).to_device()
    assert (
        device_agg.try_aggregate_device(
            dev, ["k"], ((("v"), "reduce_sum", 1),), ["v"]
        )
        is not None
    )
    a = _dsl_agg(dev, "v", tfs.reduce_sum)
    assert 5_000_000 in set(np.asarray(a.column_values("k")).tolist())


def test_wide_features_exceeding_table_limit_fall_back(data):
    """Both device plans decline when K × feature-elems exceeds the
    table limit; the host path answers."""
    n = len(data["k"])
    wide = np.ones((n, 4096), np.float32)
    d = {"k": data["k"].copy(), "v": wide}
    d["k"][0] = 5_000_000  # dense plan out (span), dict plan out (table)
    old = device_agg._TABLE_ELEM_LIMIT
    device_agg._TABLE_ELEM_LIMIT = 1 << 14
    try:
        dev = tfs.frame_from_arrays(dict(d)).to_device()
        assert (
            device_agg.try_aggregate_device(
                dev, ["k"], (("v", "reduce_sum", 2),), ["v"]
            )
            is None
        )
    finally:
        device_agg._TABLE_ELEM_LIMIT = old


def test_float_keys_fall_back():
    rng = np.random.default_rng(3)
    d = {
        "k": rng.standard_normal(64).astype(np.float32),
        "v": rng.standard_normal(64).astype(np.float32),
    }
    dev = tfs.frame_from_arrays(dict(d)).to_device()
    a = _dsl_agg(dev, "v", tfs.reduce_sum)
    assert len(a.collect()) == 64  # every float key unique → 64 groups


def test_multikey_span_overflow_rides_dictionary_plan():
    """Two huge-span key columns must not wrap the dense plan's bucket
    product past its gate (int64 overflow → K=0 'passes'); they skip to
    the dictionary plan, whose K is the distinct-group count, and the
    result matches the host path."""
    rng = np.random.default_rng(4)
    n = 64
    a = rng.integers(0, 10, n).astype(np.int64)
    b = rng.integers(0, 10, n).astype(np.int64)
    a[0], b[0] = -(2**31), -(2**31)
    a[1], b[1] = 2**31 - 1, 2**31 - 1
    d = {"a": a, "b": b, "v": np.ones(n, np.float32)}
    dev = tfs.frame_from_arrays(dict(d)).to_device()
    got = device_agg.try_aggregate_device(
        dev, ["a", "b"], (("v", "reduce_sum", 1),), ["v"]
    )
    assert got is not None
    key_cols, out_cols = got
    want = {}
    for ka, kb, v in zip(a, b, d["v"]):
        want[(int(ka), int(kb))] = want.get((int(ka), int(kb)), 0.0) + float(v)
    got_map = {
        (int(ka), int(kb)): float(v)
        for ka, kb, v in zip(key_cols["a"], key_cols["b"], out_cols["v"])
    }
    assert got_map == want

    with tfs.with_graph():
        v_input = tfs.block(dev, "v", tf_name="v_input")
        agg = tfs.aggregate(
            tfs.reduce_sum(v_input, axis=0, name="v"), dev.group_by("a", "b")
        )
    assert float(np.asarray(agg.column_values("v")).sum()) == n


def test_groupby_count_sharded():
    rng = np.random.default_rng(5)
    k = rng.integers(0, 5, 640)
    dev = tfs.frame_from_arrays(
        {"k": k, "v": rng.standard_normal(640).astype(np.float32)}
    ).to_device()
    counted = dev.group_by("k").count()
    got = {r["k"]: r["count"] for r in counted.collect()}
    for key in np.unique(k):
        assert got[int(key)] == int((k == key).sum())


def test_int8_full_span_keys_no_wrap():
    """int8 keys spanning -128..127: the 255-wide offset must widen
    before subtraction — a wrap would silently drop whole groups."""
    keys = np.array(([-128] * 8 + [127] * 8) * 100, np.int8)
    vals = np.ones(len(keys), np.float32)
    dev = tfs.frame_from_arrays({"k": keys, "v": vals}).to_device()
    got = device_agg.try_aggregate_device(
        dev, ["k"], (("v", "reduce_sum", 1),), ["v"]
    )
    assert got is not None
    key_cols, out_cols = got
    assert list(key_cols["k"]) == [-128, 127]
    assert list(out_cols["v"]) == [800.0, 800.0]


def test_repeated_aggregates_hit_memos_and_stay_correct():
    """Round 5: repeated aggregates over the same immutable device
    columns memoize the dense plan's span probe and the dictionary
    plan's encode+staged ids (each a host↔device round trip per
    call). Results must be IDENTICAL across calls and
    the memos must actually populate."""
    rng = np.random.default_rng(11)
    # dense plan (int keys): minmax memo
    di = tfs.frame_from_arrays(
        {"k": rng.integers(0, 32, 4096),
         "v": rng.standard_normal(4096).astype(np.float32)}
    ).to_device()
    first = {r["k"]: r["v"] for r in _dsl_agg(di, "v", tfs.reduce_sum).collect()}
    assert any(id(b["k"]) in device_agg._minmax_memo for b in di.blocks())
    for _ in range(3):
        again = {
            r["k"]: r["v"] for r in _dsl_agg(di, "v", tfs.reduce_sum).collect()
        }
        assert again == first
    # dictionary plan (huge-span keys): encode memo
    dk = tfs.frame_from_arrays(
        {"k": rng.integers(0, 2**40, 4096),
         "v": rng.standard_normal(4096).astype(np.float32)}
    ).to_device()
    want = {r["k"]: r["v"] for r in _dsl_agg(dk, "v", tfs.reduce_sum).collect()}
    assert any(
        id(b["k"]) in {i for key in device_agg._dict_encode_memo for i in key}
        for b in dk.blocks()
    )
    for _ in range(3):
        got = {
            r["k"]: r["v"] for r in _dsl_agg(dk, "v", tfs.reduce_sum).collect()
        }
        assert got == want

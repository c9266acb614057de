"""Integration tests of the five verbs through the full public path
(≙ BasicOperationsSuite / TrimmingOperationsSuite / core_test.py)."""

import numpy as np
import pytest

import tensorframes_tpu as tfs
from tensorframes_tpu import dtypes as dt
from tensorframes_tpu.validation import ValidationError


# -- map_blocks --------------------------------------------------------------

def test_readme_add3():
    # README.md:62-93
    df = tfs.frame_from_rows([{"x": float(x)} for x in range(10)])
    x = tfs.block(df, "x")
    z = tfs.add(x, 3, name="z")
    df2 = tfs.map_blocks(z, df)
    rows = df2.collect()
    assert [r["z"] for r in rows] == [float(x) + 3 for x in range(10)]
    assert [r["x"] for r in rows] == [float(x) for x in range(10)]


def test_map_blocks_is_lazy():
    df = tfs.frame_from_rows([{"x": 1.0}])
    x = tfs.block(df, "x")
    df2 = tfs.map_blocks((x + 1.0).named("y"), df)
    assert not df2.is_materialized
    df2.collect()
    assert df2.is_materialized


def test_map_blocks_multi_output_sorted_first():
    # output cols first, sorted by name (DebugRowOps.scala:353-379)
    df = tfs.frame_from_rows([{"x": 2.0}])
    x = tfs.block(df, "x")
    b = (x * 3.0).named("b")
    a = (x + 1.0).named("a")
    df2 = tfs.map_blocks([b, a], df)
    assert df2.columns == ["a", "b", "x"]


def test_map_blocks_feed_dict():
    # placeholder renamed onto another column (core_test.py:95-107)
    df = tfs.frame_from_rows([{"col": 5.0}])
    ph = tfs.placeholder(dt.float64, [None], name="ph")
    z = (ph + 1.0).named("z")
    df2 = tfs.map_blocks(z, df, feed_dict={"ph": "col"})
    assert df2.first()["z"] == 6.0


def test_map_blocks_trimmed_changes_row_count():
    # ≙ TrimmingOperationsSuite.scala:17-47
    df = tfs.frame_from_rows([{"x": float(i)} for i in range(8)], num_blocks=2)
    x = tfs.block(df, "x")
    # keep every other row: output rows != input rows, requires trim
    half = tfs.apply_fn(lambda v: v[::2], x, name="half")
    out = tfs.map_blocks(half, df, trim=True)
    assert out.columns == ["half"]
    assert out.num_rows == 4


def test_map_blocks_row_count_mismatch_errors_without_trim():
    df = tfs.frame_from_rows([{"x": float(i)} for i in range(8)], num_blocks=1)
    x = tfs.block(df, "x")
    half = tfs.apply_fn(lambda v: v[::2], x, name="half")
    df2 = tfs.map_blocks(half, df)
    with pytest.raises(ValidationError):
        df2.collect()


def test_map_blocks_output_collision_error():
    df = tfs.frame_from_rows([{"x": 1.0}])
    x = tfs.block(df, "x")
    clash = tfs.identity(x, name="x_out").named("x")
    with pytest.raises(ValidationError) as e:
        tfs.map_blocks(clash, df)
    assert "x" in str(e.value)


def test_map_blocks_missing_column_error_enumerates():
    df = tfs.frame_from_rows([{"x": 1.0}])
    ph = tfs.placeholder(dt.float64, [None], name="nope")
    with pytest.raises(ValidationError) as e:
        tfs.map_blocks((ph + 1.0).named("z"), df)
    msg = str(e.value)
    assert "nope" in msg and "x" in msg  # both sides enumerated


def test_map_blocks_dtype_mismatch_error():
    df = tfs.frame_from_rows([{"x": 1.0}])  # float64
    ph = tfs.placeholder(dt.float32, [None], name="x")
    with pytest.raises(ValidationError) as e:
        tfs.map_blocks((ph + 1.0).named("z"), df)
    assert "casting" in str(e.value)


def test_map_blocks_vectors():
    # 1-tensor in, 1-tensor out (BasicOperationsSuite 2-tensor cases)
    df = tfs.analyze(
        tfs.frame_from_rows([{"y": [float(i), 1.0]} for i in range(6)])
    )
    y = tfs.block(df, "y")
    z = tfs.reduce_sum(y, axis=1, name="z")
    out = tfs.map_blocks(z, df).collect()
    assert [r["z"] for r in out] == [float(i) + 1.0 for i in range(6)]


def test_map_blocks_int_types():
    df = tfs.frame_from_rows([{"x": i} for i in range(5)])
    assert df.schema["x"].dtype is dt.int64
    x = tfs.block(df, "x")
    out = tfs.map_blocks((x * 2).named("z"), df).collect()
    assert [r["z"] for r in out] == [2 * i for i in range(5)]


# -- map_rows ----------------------------------------------------------------

def test_map_rows_scalar():
    df = tfs.frame_from_rows([{"x": float(i)} for i in range(7)], num_blocks=2)
    x = tfs.row(df, "x")
    z = (x * x).named("z")
    out = tfs.map_rows(z, df).collect()
    assert [r["z"] for r in out] == [float(i * i) for i in range(7)]


@pytest.mark.parametrize("dtype", ["float32", "float64", "int32"])
def test_map_rows_ragged(dtype):
    # ragged vectors: the map_rows-only case (core.py:288-289)
    df = tfs.frame_from_rows(
        [{"y": np.arange(1, n + 1).astype(dtype)} for n in (1, 2, 3)]
    )
    df = tfs.analyze(df)
    y = tfs.row(df, "y")
    s = tfs.reduce_sum(y, axis=0, name="s")
    out = tfs.map_rows(s, df).collect()
    assert [r["s"] for r in out] == [1, 3, 6]
    # many rows in four shape groups over three blocks: every group is
    # stacked on the host, padded to its row bucket and moved in one
    # wave; each row's result equals numpy's on that row, exactly
    # (quarter-valued floats and small ints sum without rounding)
    rng = np.random.default_rng(0)
    cells = [(np.arange(n) + (0 if dtype == "int32" else 0.25)
              ).astype(dtype) for n in rng.choice([3, 5, 8, 13], 150)]
    frame = tfs.frame_from_rows([{"v": c} for c in cells], num_blocks=3)
    program = tfs.compile_program(
        lambda v: {"s": v.sum()}, frame, block=False
    )
    got = np.concatenate(
        [np.asarray(b["s"]) for b in tfs.map_rows(program, frame).blocks()]
    )
    want = np.asarray([c.sum() for c in cells])  # int32 sums widen
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(got, want)


def test_map_rows_vector_output():
    df = tfs.analyze(tfs.frame_from_rows([{"y": [1.0, 2.0]} for _ in range(3)]))
    y = tfs.row(df, "y")
    z = (y * 10.0).named("z")
    out = tfs.map_rows(z, df).collect()
    assert np.allclose(out[0]["z"], [10.0, 20.0])


# -- reduce_rows -------------------------------------------------------------

def test_reduce_rows_sum():
    df = tfs.frame_from_rows([{"x": float(i)} for i in range(1, 11)], num_blocks=3)
    x1 = tfs.placeholder(dt.float64, [], name="x_1")
    x2 = tfs.placeholder(dt.float64, [], name="x_2")
    x = tfs.add(x1, x2, name="x")
    assert tfs.reduce_rows(x, df) == 55.0


def test_reduce_rows_vector():
    df = tfs.analyze(
        tfs.frame_from_rows([{"y": [float(i), 1.0]} for i in range(4)])
    )
    y1 = tfs.placeholder(dt.float64, [2], name="y_1")
    y2 = tfs.placeholder(dt.float64, [2], name="y_2")
    y = tfs.add(y1, y2, name="y")
    res = tfs.reduce_rows(y, df)
    assert np.allclose(res, [6.0, 4.0])


def test_reduce_rows_naming_contract_error():
    df = tfs.frame_from_rows([{"x": 1.0}])
    bad = tfs.placeholder(dt.float64, [], name="x_only")
    with pytest.raises(ValidationError) as e:
        tfs.reduce_rows(tfs.identity(bad, name="x"), df)
    assert "x_1" in str(e.value) and "x_2" in str(e.value)


# -- reduce_blocks -----------------------------------------------------------

def test_readme_reduce_example():
    # README.md:98-129
    df = tfs.analyze(
        tfs.frame_from_rows([{"y": [float(y), float(-y)]} for y in range(10)])
    )
    df3 = df.alias_column("y", "z")
    y_input = tfs.block(df3, "y", tf_name="y_input")
    z_input = tfs.block(df3, "z", tf_name="z_input")
    y = tfs.reduce_sum(y_input, axis=0, name="y")
    z = tfs.reduce_min(z_input, axis=0, name="z")
    data_sum, data_min = tfs.reduce_blocks([y, z], df3)
    assert np.allclose(data_sum, [45.0, -45.0])
    assert np.allclose(data_min, [0.0, -9.0])


def test_reduce_blocks_naming_contract_error():
    df = tfs.frame_from_rows([{"x": 1.0}])
    ph = tfs.placeholder(dt.float64, [None], name="wrong_name")
    with pytest.raises(ValidationError) as e:
        tfs.reduce_blocks(tfs.reduce_sum(ph, axis=0, name="x"), df)
    assert "x_input" in str(e.value)


def test_reduce_blocks_fetch_must_be_column():
    df = tfs.frame_from_rows([{"x": 1.0}])
    ph = tfs.placeholder(dt.float64, [None], name="z_input")
    with pytest.raises(ValidationError) as e:
        tfs.reduce_blocks(tfs.reduce_sum(ph, axis=0, name="z"), df)
    assert "existing column" in str(e.value)


# -- aggregate ---------------------------------------------------------------

def test_aggregate_sum_segment_path():
    # ≙ core_test.py groupBy aggregate (:255-264)
    df = tfs.frame_from_rows(
        [{"key": i % 3, "x": float(i)} for i in range(12)], num_blocks=3
    )
    x_input = tfs.block(df, "x", tf_name="x_input")
    x = tfs.reduce_sum(x_input, axis=0, name="x")
    res = tfs.aggregate(x, df.group_by("key")).collect()
    assert res == [
        {"key": 0, "x": 18.0},
        {"key": 1, "x": 22.0},
        {"key": 2, "x": 26.0},
    ]


def test_aggregate_generic_path():
    # a non-reducer-node graph forces the generic chunked-compaction path
    # (UDAF semantics: the program must be algebraic — re-applying it to
    # partials must be valid, as with the reference's compact/merge,
    # DebugRowOps.scala:651-683). 30 rows per group exercises chunking
    # (buffer = 10).
    df = tfs.frame_from_rows(
        [{"key": i % 2, "x": float(i + 1)} for i in range(60)]
    )
    x_input = tfs.block(df, "x", tf_name="x_input")
    x = tfs.apply_fn(lambda v: v.sum(axis=0), x_input, name="x")
    res = tfs.aggregate(x, df.group_by("key")).collect()
    odd = sum(float(i + 1) for i in range(60) if i % 2 == 0)
    even = sum(float(i + 1) for i in range(60) if i % 2 == 1)
    assert res[0]["x"] == pytest.approx(odd)
    assert res[1]["x"] == pytest.approx(even)


def test_aggregate_string_keys():
    df = tfs.frame_from_rows(
        [{"k": "ab"[i % 2], "x": float(i)} for i in range(6)]
    )
    x_input = tfs.block(df, "x", tf_name="x_input")
    x = tfs.reduce_sum(x_input, axis=0, name="x")
    res = tfs.aggregate(x, df.group_by("k")).collect()
    assert res == [{"k": "a", "x": 6.0}, {"k": "b", "x": 9.0}]


def test_aggregate_vector_values():
    df = tfs.analyze(
        tfs.frame_from_rows(
            [{"key": i % 2, "v": [float(i), 1.0]} for i in range(4)]
        )
    )
    v_input = tfs.block(df, "v", tf_name="v_input")
    v = tfs.reduce_sum(v_input, axis=0, name="v")
    res = tfs.aggregate(v, df.group_by("key")).collect()
    assert np.allclose(res[0]["v"], [2.0, 2.0])
    assert np.allclose(res[1]["v"], [4.0, 2.0])


# -- python function + pandas paths -----------------------------------------

def test_function_program():
    df = tfs.frame_from_rows([{"a": float(i), "b": float(2 * i)} for i in range(6)])

    def prog(a, b):
        return {"s": a + b}

    out = tfs.map_blocks(prog, df).collect()
    assert [r["s"] for r in out] == [3.0 * i for i in range(6)]


def test_pandas_local_path():
    # ≙ core_test.py:68-79 pandas map path
    import pandas as pd

    pdf = pd.DataFrame({"x": [1.0, 2.0, 3.0]})
    ph = tfs.placeholder(dt.float64, [None], name="x")
    z = (ph + 1.0).named("z")
    out = tfs.map_blocks(z, pdf)
    assert isinstance(out, pd.DataFrame)
    assert out["z"].tolist() == [2.0, 3.0, 4.0]


def test_variablelike_closure_constants():
    # closure-captured arrays play the role of frozen tf.Variables
    # (core.py:42-56)
    df = tfs.frame_from_rows([{"x": float(i)} for i in range(4)])
    w = np.array(10.0)

    def prog(x):
        import jax.numpy as jnp

        return {"z": x * jnp.asarray(w)}

    out = tfs.map_blocks(prog, df).collect()
    assert [r["z"] for r in out] == [10.0 * i for i in range(4)]


# -- empty blocks (the reference's TODO gap, DebugRowOps.scala:386) ----------

def test_empty_block_map():
    df = tfs.frame_from_rows([{"x": 1.0}, {"x": 2.0}], num_blocks=2)
    df3 = df.repartition(4)  # creates empty blocks
    x = tfs.block(df3, "x")
    out = tfs.map_blocks((x + 1.0).named("z"), df3).collect()
    assert [r["z"] for r in out] == [2.0, 3.0]


# -- regression tests from review findings -----------------------------------

def test_reduce_rows_function_fetches():
    # plain-function programs may use the x_1/x_2 naming contract
    df = tfs.frame_from_rows([{"x": float(i)} for i in range(1, 5)])

    def pair(x_1, x_2):
        return {"x": x_1 + x_2}

    assert tfs.reduce_rows(pair, df) == 10.0


def test_reduce_blocks_function_fetches():
    df = tfs.frame_from_rows([{"x": float(i)} for i in range(1, 5)])

    def red(x_input):
        return {"x": x_input.sum(axis=0)}

    assert tfs.reduce_blocks(red, df) == 10.0


def test_reduce_rows_ragged_friendly_error():
    df = tfs.frame_from_rows(
        [{"y": [1.0]}, {"y": [1.0, 2.0]}, {"y": [3.0]}], num_blocks=1
    )
    y1 = tfs.placeholder(dt.float64, [None], name="y_1")
    y2 = tfs.placeholder(dt.float64, [None], name="y_2")
    y = tfs.add(y1, y2, name="y")
    with pytest.raises(ValueError, match="ragged"):
        tfs.reduce_rows(y, df)


def test_map_rows_empty_block_vector_output():
    df = tfs.analyze(
        tfs.frame_from_rows([{"y": [1.0, 2.0]} for _ in range(3)])
    ).repartition(4)  # creates an empty block
    y = tfs.row(df, "y")
    out = tfs.map_rows((y * 10.0).named("z"), df)
    vals = out.column_values("z")
    assert vals.shape == (3, 2)


def test_aggregate_empty_frame():
    import numpy as np

    df = tfs.frame_from_arrays(
        {"key": np.empty((0,), np.int64), "x": np.empty((0,), np.float64)},
        num_blocks=1,
    )
    x_input = tfs.placeholder(dt.float64, [None], name="x_input")
    x = tfs.reduce_sum(x_input, axis=0, name="x")
    res = tfs.aggregate(x, df.group_by("key"))
    assert res.num_rows == 0
    assert res.columns == ["key", "x"]


def test_aggregate_mean_preserves_int_dtype():
    df = tfs.frame_from_rows([{"key": i % 2, "x": i} for i in range(8)])
    assert df.schema["x"].dtype is dt.int64
    x_input = tfs.block(df, "x", tf_name="x_input")
    x = tfs.reduce_mean(x_input, axis=0, name="x")
    res = tfs.aggregate(x, df.group_by("key"))
    assert res.schema["x"].dtype is dt.int64
    vals = res.column_values("x")
    assert vals.dtype == np.int64


def test_map_blocks_pipeline_depths_agree():
    """The pipelined in-flight window produces identical results to the
    synchronous path at every depth."""
    import numpy as np

    from tensorframes_tpu.config import configure, get_config

    df = tfs.frame_from_arrays({"x": np.arange(1000.0)}, num_blocks=7)
    old = get_config().map_pipeline_depth
    results = {}
    try:
        for depth in (0, 1, 3):
            configure(map_pipeline_depth=depth)
            out = tfs.map_blocks(lambda x: {"y": x * 2.0 + 1.0}, df)
            results[depth] = out.column_values("y")
    finally:
        configure(map_pipeline_depth=old)
    for depth, got in results.items():
        np.testing.assert_array_equal(got, np.arange(1000.0) * 2.0 + 1.0)


def test_map_blocks_prefetch_depths_agree():
    """Background host→device feed staging (io.prefetch_to_device wired
    into the map_blocks host path, VERDICT r3 #2) is a pure overlap
    optimization: results match the unstaged path at every depth, and
    non-input columns ride along untouched."""
    import numpy as np

    from tensorframes_tpu.config import configure, get_config

    df = tfs.frame_from_arrays(
        {"x": np.arange(2000.0), "tag": np.arange(2000)}, num_blocks=5
    )
    old = get_config().map_prefetch_depth
    results = {}
    try:
        for depth in (0, 1, 4):
            configure(map_prefetch_depth=depth)
            out = tfs.map_blocks(lambda x: {"y": x * 3.0 - 1.0}, df)
            results[depth] = (
                out.column_values("y"), out.column_values("tag")
            )
    finally:
        configure(map_prefetch_depth=old)
    for depth, (y, tag) in results.items():
        np.testing.assert_array_equal(y, np.arange(2000.0) * 3.0 - 1.0)
        np.testing.assert_array_equal(tag, np.arange(2000))


def test_run_block_donate_flag_safe_everywhere():
    """donate=True must be correctness-neutral: gated off on XLA:CPU
    (which doesn't implement donation), and never applied to
    device-resident frame columns — a device frame maps twice with
    identical results while donation config is on."""
    import numpy as np

    from tensorframes_tpu.config import configure, get_config
    from tensorframes_tpu.ops.executor import donation_supported

    assert donation_supported() is False  # suite runs on the cpu mesh

    old = get_config().donate_inputs
    try:
        configure(donate_inputs=True)
        # host frame: the donate branch is exercised (and gated off)
        df = tfs.frame_from_arrays({"x": np.arange(100.0)}, num_blocks=4)
        out = tfs.map_blocks(lambda x: {"y": x + 1.0}, df)
        np.testing.assert_array_equal(
            out.column_values("y"), np.arange(100.0) + 1.0
        )
        # device frame mapped TWICE: columns must survive the first map
        dev = tfs.frame_from_arrays({"x": np.arange(64.0)}).to_device()
        a = tfs.map_blocks(lambda x: {"y": x * 2.0}, dev)
        _ = a.column_values("y")
        b = tfs.map_blocks(lambda x: {"z": x * 5.0}, dev)
        np.testing.assert_array_equal(
            np.asarray(b.column_values("z")), np.arange(64.0) * 5.0
        )
    finally:
        configure(donate_inputs=old)


def test_aggregate_string_keys_plain_fn():
    """groupBy on a host string column (≙ Catalyst groupBy on strings —
    keys never touch the device; values aggregate on it)."""
    fr = tfs.frame_from_rows(
        [{"k": ["a", "b", "a", "c", "b"][i], "v": float(i)} for i in range(5)]
    )
    agg = fr.group_by("k").aggregate(lambda v_input: {"v": v_input.sum(0)})
    assert {r["k"]: r["v"] for r in agg.collect()} == {
        "a": 2.0, "b": 5.0, "c": 3.0
    }


def test_aggregate_multiple_keys():
    """Composite group keys (≙ groupBy(col1, col2))."""
    import numpy as np

    fr = tfs.frame_from_arrays(
        {
            "a": np.array([1, 1, 1, 2, 2]),
            "b": np.array([0, 0, 1, 0, 1]),
            "v": np.array([1.0, 2.0, 4.0, 8.0, 16.0]),
        }
    )
    agg = fr.group_by("a", "b").aggregate(
        lambda v_input: {"v": v_input.sum(0)}
    )
    got = {(r["a"], r["b"]): r["v"] for r in agg.collect()}
    assert got == {(1, 0): 3.0, (1, 1): 4.0, (2, 0): 8.0, (2, 1): 16.0}


def test_aggregate_int8_full_span_host_path():
    """Host-path grouping must widen narrow int keys before the offset
    subtraction (int8 -128..127 wraps otherwise)."""
    df = tfs.frame_from_rows(
        [{"k": np.int8([-128, 127][i % 2]), "v": float(i)} for i in range(10)]
    )
    res = tfs.aggregate(
        lambda v_input: {"v": v_input.sum(0)}, df.group_by("k")
    ).collect()
    assert {int(r["k"]): r["v"] for r in res} == {-128: 20.0, 127: 25.0}


def test_aggregate_nan_keys_group_together():
    """NaN float keys form ONE group — the Catalyst/Spark groupBy
    convention (NaNs compare equal for grouping); pinned intentionally."""
    df = tfs.frame_from_arrays(
        {
            "k": np.array([1.0, np.nan, 2.0, np.nan, 1.0]),
            "v": np.arange(5, dtype=np.float64),
        }
    )
    res = tfs.aggregate(
        lambda v_input: {"v": v_input.sum(0)}, df.group_by("k")
    ).collect()
    by_key = {("nan" if np.isnan(r["k"]) else r["k"]): r["v"] for r in res}
    assert by_key == {1.0: 4.0, 2.0: 2.0, "nan": 4.0}

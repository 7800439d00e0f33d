"""Tests proving the engine actually selects the device (JAX) execution path.

VERDICT r1 item #1: the planner must emit Device*Agg nodes and the executor must
run them on device; ops/counters.py records real device batches so these tests
fail if the path silently falls back to host.
"""

import numpy as np
import pytest

import daft_tpu
from daft_tpu import col
from daft_tpu.config import execution_config_ctx
from daft_tpu.ops import counters
from daft_tpu.plan import physical as pp


def _plan(df):
    from daft_tpu.plan.physical import translate

    return translate(df._builder.optimize()._plan)


def _q6_df():
    rng = np.random.default_rng(0)
    n = 10_000
    return daft_tpu.from_pydict({
        "l_quantity": rng.uniform(1, 50, n).tolist(),
        "l_extendedprice": rng.uniform(100, 10000, n).tolist(),
        "l_discount": rng.uniform(0.0, 0.1, n).tolist(),
    })


def _q6_query(df):
    return (
        df.where((col("l_discount") >= 0.05) & (col("l_discount") <= 0.07)
                 & (col("l_quantity") < 24.0))
        .agg((col("l_extendedprice") * col("l_discount")).sum().alias("revenue"))
    )


def test_planner_emits_device_filter_agg():
    with execution_config_ctx(device_mode="on"):
        plan = _plan(_q6_query(_q6_df()))
    assert any(isinstance(n, pp.DeviceFilterAgg) for n in plan.walk()), plan.display()


def test_planner_emits_device_grouped_agg():
    df = daft_tpu.from_pydict({"k": ["a", "b", "a"], "v": [1.0, 2.0, 3.0]})
    q = df.groupby("k").agg(col("v").sum())
    with execution_config_ctx(device_mode="on"):
        plan = _plan(q)
    assert any(isinstance(n, pp.DeviceGroupedAgg) for n in plan.walk()), plan.display()


def test_planner_device_off_no_device_nodes():
    with execution_config_ctx(device_mode="off"):
        plan = _plan(_q6_query(_q6_df()))
    assert not any(isinstance(n, (pp.DeviceFilterAgg, pp.DeviceGroupedAgg))
                   for n in plan.walk())


def test_q6_runs_on_device_and_matches_host():
    df = _q6_df()
    counters.reset()
    with execution_config_ctx(device_mode="on"):
        dev_out = _q6_query(df).to_pydict()
    assert counters.device_stage_batches > 0, "device stage never fed"
    assert counters.device_stage_runs > 0
    with execution_config_ctx(device_mode="off"):
        host_out = _q6_query(df).to_pydict()
    # device compute dtype is f32 (f64 is TPU-emulated; see ops/stage.py) -> ~1e-7 rel
    np.testing.assert_allclose(dev_out["revenue"], host_out["revenue"], rtol=1e-5)


def test_grouped_agg_device_matches_host_string_keys():
    rng = np.random.default_rng(1)
    n = 5000
    df = daft_tpu.from_pydict({
        "flag": rng.choice(["A", "N", "R"], n).tolist(),
        "status": rng.choice(["O", "F"], n).tolist(),
        "qty": rng.uniform(1, 50, n).tolist(),
        "price": rng.uniform(1, 1000, n).tolist(),
    })

    def q(d):
        return (d.groupby("flag", "status")
                .agg(col("qty").sum().alias("sum_qty"),
                     col("price").mean().alias("avg_price"),
                     col("qty").min().alias("min_qty"),
                     col("qty").max().alias("max_qty"),
                     col("qty").count().alias("n"))
                .sort(["flag", "status"]))

    counters.reset()
    with execution_config_ctx(device_mode="on"):
        dev_out = q(df).to_pydict()
    assert counters.device_grouped_batches > 0, "device grouped stage never fed"
    with execution_config_ctx(device_mode="off"):
        host_out = q(df).to_pydict()
    assert dev_out["flag"] == host_out["flag"]
    assert dev_out["status"] == host_out["status"]
    for c in ("sum_qty", "avg_price", "min_qty", "max_qty"):
        np.testing.assert_allclose(dev_out[c], host_out[c], rtol=1e-5)
    assert dev_out["n"] == host_out["n"]


def test_grouped_agg_device_with_filter_and_nulls():
    df = daft_tpu.from_pydict({
        "k": ["x", "y", "x", "y", "x", None],
        "v": [1.0, 2.0, None, 4.0, 5.0, 6.0],
        "w": [10.0, 20.0, 30.0, 40.0, 50.0, 60.0],
    })
    q = lambda d: (d.where(col("w") > 15.0)
                   .groupby("k")
                   .agg(col("v").sum().alias("s"), col("v").count().alias("c"))
                   .sort("k"))
    counters.reset()
    with execution_config_ctx(device_mode="on"):
        dev_out = q(df).to_pydict()
    assert counters.device_grouped_batches > 0
    with execution_config_ctx(device_mode="off"):
        host_out = q(df).to_pydict()
    assert dev_out == host_out


def test_device_count_modes_match_host():
    df = daft_tpu.from_pydict({"v": [1.0, None, 3.0, None, 5.0]})
    q = lambda d: d.agg(
        col("v").count().alias("c_valid"),
        col("v").sum().alias("s"),
        col("v").mean().alias("m"),
        col("v").min().alias("lo"),
        col("v").max().alias("hi"),
    )
    counters.reset()
    with execution_config_ctx(device_mode="on"):
        dev_out = q(df).to_pydict()
    assert counters.device_stage_runs > 0
    with execution_config_ctx(device_mode="off"):
        host_out = q(df).to_pydict()
    assert dev_out == host_out


def test_device_auto_small_input_stays_on_host():
    df = _q6_df()
    counters.reset()
    with execution_config_ctx(device_mode="auto", device_min_rows=10**9):
        out = _q6_query(df).to_pydict()
    assert counters.device_stage_batches == 0
    assert len(out["revenue"]) == 1


def test_device_int_sums_exact():
    df = daft_tpu.from_pydict({"k": ["a", "a", "b"], "v": [2**60, 7, 11]})
    q = lambda d: d.groupby("k").agg(col("v").sum().alias("s")).sort("k")
    with execution_config_ctx(device_mode="on"):
        dev_out = q(df).to_pydict()
    with execution_config_ctx(device_mode="off"):
        host_out = q(df).to_pydict()
    assert dev_out == host_out


def test_abandoned_run_does_not_corrupt_next_run():
    """ADVICE r2 (high): cached stages must not carry accumulator state across
    runs — an interrupted run (exception between feed and finalize) previously
    leaked partials into the next run of the same query (106.0 instead of 6.0)."""
    df = daft_tpu.from_pydict({"v": [1.0, 2.0, 3.0]})
    q = lambda d: d.agg(col("v").sum().alias("s"))
    with execution_config_ctx(device_mode="on"):
        # simulate a run that fed batches then died before finalize
        from daft_tpu.ops.stage import try_build_filter_agg_stage

        plan = _plan(q(df))
        node = next(n for n in plan.walk() if isinstance(n, pp.DeviceFilterAgg))
        stage = try_build_filter_agg_stage(node.input.schema, node.predicate,
                                           node.aggregations)
        run = stage.start_run()
        for part in node.input.partitions:
            for b in part.batches:
                run.feed_batch(b)
        # (no finalize — abandoned)
        out = q(df).to_pydict()
    assert out["s"] == [6.0]


def test_abandoned_grouped_run_does_not_corrupt_next_run():
    df = daft_tpu.from_pydict({"k": ["a", "b", "a"], "v": [1.0, 2.0, 3.0]})
    q = lambda d: d.groupby("k").agg(col("v").sum().alias("s")).sort("k")
    with execution_config_ctx(device_mode="on"):
        from daft_tpu.ops.grouped_stage import try_build_grouped_agg_stage

        plan = _plan(q(df))
        node = next(n for n in plan.walk() if isinstance(n, pp.DeviceGroupedAgg))
        stage = try_build_grouped_agg_stage(node.input.schema, node.predicate,
                                            node.groupby, node.aggregations)
        run = stage.start_run()
        for part in node.input.partitions:
            for b in part.batches:
                run.feed_batch(b)
        out = q(df).to_pydict()
    assert out["k"] == ["a", "b"]
    assert out["s"] == [4.0, 2.0]


def test_grouped_device_int_min_max_exact():
    """ADVICE r2: int min/max must accumulate in int64, not float64 (2^53 cliff)."""
    big = 2**53 + 1
    df = daft_tpu.from_pydict({"k": ["a", "a", "b"], "v": [big, big + 2, 5]})
    q = lambda d: (d.groupby("k")
                   .agg(col("v").min().alias("lo"), col("v").max().alias("hi"))
                   .sort("k"))
    with execution_config_ctx(device_mode="on"):
        dev_out = q(df).to_pydict()
    with execution_config_ctx(device_mode="off"):
        host_out = q(df).to_pydict()
    assert dev_out == host_out
    assert dev_out["hi"][0] == big + 2


def test_tpch_q1_shape_device_matches_host():
    rng = np.random.default_rng(2)
    n = 20_000
    df = daft_tpu.from_pydict({
        "l_returnflag": rng.choice(["A", "N", "R"], n).tolist(),
        "l_linestatus": rng.choice(["O", "F"], n).tolist(),
        "l_quantity": rng.uniform(1, 50, n).tolist(),
        "l_extendedprice": rng.uniform(900, 105000, n).tolist(),
        "l_discount": rng.uniform(0, 0.1, n).tolist(),
        "l_tax": rng.uniform(0, 0.08, n).tolist(),
        "l_shipdate_days": rng.integers(8000, 10000, n).tolist(),
    })

    def q1(d):
        disc_price = col("l_extendedprice") * (1 - col("l_discount"))
        charge = disc_price * (1 + col("l_tax"))
        return (
            d.where(col("l_shipdate_days") <= 9190)
            .groupby("l_returnflag", "l_linestatus")
            .agg(
                col("l_quantity").sum().alias("sum_qty"),
                col("l_extendedprice").sum().alias("sum_base_price"),
                disc_price.sum().alias("sum_disc_price"),
                charge.sum().alias("sum_charge"),
                col("l_quantity").mean().alias("avg_qty"),
                col("l_extendedprice").mean().alias("avg_price"),
                col("l_discount").mean().alias("avg_disc"),
                col("l_quantity").count().alias("count_order"),
            )
            .sort(["l_returnflag", "l_linestatus"])
        )

    counters.reset()
    with execution_config_ctx(device_mode="on"):
        dev_out = q1(df).to_pydict()
    assert counters.device_grouped_batches > 0
    with execution_config_ctx(device_mode="off"):
        host_out = q1(df).to_pydict()
    for k in host_out:
        if isinstance(host_out[k][0], float):
            np.testing.assert_allclose(dev_out[k], host_out[k], rtol=1e-5)
        else:
            assert dev_out[k] == host_out[k], k


def test_high_cardinality_groupby_falls_back_to_host():
    """The one-hot matmul kernel must never see unbounded segment counts: keys
    beyond MAX_MATMUL_SEGMENTS raise DeviceFallback pre-dispatch and the
    executor reruns the stage on host with identical results."""
    n = 20_000  # > MAX_MATMUL_SEGMENTS distinct keys
    df = daft_tpu.from_pydict({
        "k": list(range(n)),
        "v": [float(i % 97) for i in range(n)],
    })
    q = lambda d: d.groupby("k").agg(col("v").sum().alias("s"))
    counters.reset()
    with execution_config_ctx(device_mode="on"):
        dev_out = q(df).to_pydict()
    with execution_config_ctx(device_mode="off"):
        host_out = q(df).to_pydict()
    assert dev_out == host_out


def test_high_cardinality_grouped_agg_sort_path():
    """cap > MAX_MATMUL_SEGMENTS groupbys run on device via the sort-based
    segmented-reduction path (r3 VERDICT item #3: the 4096-segment ceiling),
    matching the host result exactly."""
    rng = np.random.default_rng(7)
    n = 200_000
    n_groups = 20_000  # > MAX_MATMUL_SEGMENTS
    df = daft_tpu.from_pydict({
        "k": rng.integers(0, n_groups, n).tolist(),
        "v": rng.uniform(0, 100, n).tolist(),
        "q": rng.integers(0, 1000, n).tolist(),
    })

    def q(d):
        return (d.groupby("k")
                .agg(col("v").sum().alias("sv"),
                     col("q").sum().alias("sq"),
                     col("q").max().alias("mq"),
                     col("v").count().alias("cv"))
                .sort("k"))

    with execution_config_ctx(device_mode="off"):
        host = q(df).to_pydict()
    counters.reset()
    with execution_config_ctx(device_mode="on"):
        dev_out = q(df).to_pydict()
    assert counters.device_grouped_batches > 0, "sort path never dispatched"
    assert dev_out["k"] == host["k"]
    assert dev_out["sq"] == host["sq"]
    assert dev_out["mq"] == host["mq"]
    assert dev_out["cv"] == host["cv"]
    np.testing.assert_allclose(dev_out["sv"], host["sv"], rtol=1e-6)


def test_sort_path_with_predicate_and_nulls():
    rng = np.random.default_rng(3)
    n = 60_000
    vals = rng.uniform(0, 10, n)
    v = [None if i % 17 == 0 else float(vals[i]) for i in range(n)]
    df = daft_tpu.from_pydict({
        "k": rng.integers(0, 9000, n).tolist(),
        "v": v,
        "w": rng.uniform(0, 1, n).tolist(),
    })

    def q(d):
        return (d.where(col("w") < 0.8)
                .groupby("k")
                .agg(col("v").sum().alias("s"), col("v").count().alias("c"),
                     col("v").min().alias("mn"))
                .sort("k"))

    with execution_config_ctx(device_mode="off"):
        host = q(df).to_pydict()
    with execution_config_ctx(device_mode="on"):
        dev_out = q(df).to_pydict()
    assert dev_out["k"] == host["k"]
    assert dev_out["c"] == host["c"]
    np.testing.assert_allclose(np.array(dev_out["s"], dtype=float),
                               np.array(host["s"], dtype=float),
                               rtol=1e-6, atol=1e-9)
    np.testing.assert_allclose(np.array(dev_out["mn"], dtype=float),
                               np.array(host["mn"], dtype=float), rtol=1e-12)


def test_config_rejects_unknown_modes():
    """DAFT_TPU_DEVICE=force used to silently disable the device while looking
    like an opt-in; unknown mode strings must raise (ADVICE r4 / VERDICT r4)."""
    import pytest

    from daft_tpu.config import ExecutionConfig, execution_config_ctx

    with pytest.raises(ValueError, match="device_mode"):
        ExecutionConfig(device_mode="force")
    with pytest.raises(ValueError, match="pipeline_mode"):
        ExecutionConfig(pipeline_mode="auto")
    with pytest.raises(ValueError, match="device_mode"):
        with execution_config_ctx(device_mode="always"):
            pass
    # valid values construct fine
    ExecutionConfig(device_mode="on", pipeline_mode="force")


# ---- the one-hot tier's program: planes evaluated inside the chunk loop (PR 31) ----


def _device_and_host(q, df):
    counters.reset()
    with execution_config_ctx(device_mode="on"):
        dev_out = q(df).to_pydict()
    assert counters.device_grouped_batches > 0, "device grouped stage never fed"
    forms = (counters.device_grouped_reduce_select,
             counters.device_grouped_reduce_matmul)
    with execution_config_ctx(device_mode="off"):
        host_out = q(df).to_pydict()
    return dev_out, host_out, forms


@pytest.mark.parametrize("groups,form", [(1, "select"), (8, "select"),
                                         (16, "select"), (32, "matmul"),
                                         (4096, "matmul")])
def test_grouped_device_equals_host_at_group_counts(groups, form):
    """Every kind of plane (float sum, exact integer sum, count, a date-like
    extreme in float64, a 64-bit extreme by scatter) at each size of group
    table; the counters say which reduce form served it."""
    from daft_tpu.datatype import DataType

    rng = np.random.default_rng(groups)
    n = 40_000
    df = daft_tpu.from_pydict({
        "k": (rng.permutation(n) % groups).tolist(),
        "v": rng.uniform(-50, 50, n).tolist(),
        "q": rng.integers(-1000, 1000, n).tolist(),
        "w": (rng.integers(-(1 << 60), 1 << 60, n) | 1).tolist(),
    })

    def q(d):
        return (d.groupby("k")
                .agg(col("v").sum().alias("sv"), col("v").mean().alias("mv"),
                     col("q").sum().alias("sq"), col("v").count().alias("cv"),
                     col("q").cast(DataType.int32()).min().alias("lo"),
                     col("w").max().alias("hi"))
                .sort("k"))

    dev_out, host_out, (n_select, n_matmul) = _device_and_host(q, df)
    assert (n_select > 0, n_matmul > 0) == (form == "select", form == "matmul")
    for name in ("k", "sq", "cv", "lo", "hi"):
        assert dev_out[name] == host_out[name], name
    np.testing.assert_allclose(dev_out["sv"], host_out["sv"], rtol=1e-5, atol=1e-3)
    np.testing.assert_allclose(dev_out["mv"], host_out["mv"], rtol=1e-5, atol=1e-5)


def test_grouped_nulls_and_a_chunk_the_filter_empties():
    """200,000 rows are four chunks of 65,536: the filter keeps no row of the
    first, and the children carry nulls."""
    rng = np.random.default_rng(11)
    n = 200_000
    vals = rng.uniform(0, 10, n)
    df = daft_tpu.from_pydict({
        "i": list(range(n)),
        "k": rng.choice(["a", "b", "c", None], n).tolist(),
        "v": [None if j % 7 == 0 else float(vals[j]) for j in range(n)],
        "q": [None if j % 5 == 0 else int(j % 1000) for j in range(n)],
    })

    def q(d):
        return (d.where(col("i") >= 70_000).groupby("k")
                .agg(col("v").sum().alias("sv"), col("v").count().alias("cv"),
                     col("q").sum().alias("sq"), col("q").mean().alias("mq"),
                     col("q").count().alias("cq"))
                .sort("k"))

    dev_out, host_out, _forms = _device_and_host(q, df)
    for name in ("k", "cv", "sq", "cq"):
        assert dev_out[name] == host_out[name], name
    np.testing.assert_allclose(dev_out["sv"], host_out["sv"], rtol=1e-6)
    np.testing.assert_allclose(dev_out["mq"], host_out["mq"], rtol=1e-12)


def test_grouped_integer_sums_past_2_24_are_exact():
    rng = np.random.default_rng(12)
    n = 150_000
    df = daft_tpu.from_pydict({
        "k": rng.integers(0, 5, n).tolist(),
        "q": rng.integers(-(1 << 40), 1 << 40, n).tolist(),
        "one": [1] * n,
    })
    q = lambda d: (d.groupby("k")
                   .agg(col("q").sum().alias("s"), col("one").sum().alias("ones"))
                   .sort("k"))
    dev_out, host_out, _forms = _device_and_host(q, df)
    assert dev_out == host_out
    assert min(dev_out["ones"]) > 1 << 14 and max(map(abs, dev_out["s"])) > 1 << 24


def test_grouped_float_sums_of_200k_rows_against_float64():
    """float32 planes, float32 partials of at most 65,536 rows, combined in
    float64: within 1e-6 of the sums taken in float64 throughout."""
    rng = np.random.default_rng(13)
    n = 200_000
    k = rng.integers(0, 6, n)
    price = rng.uniform(900, 105000, n)
    disc = rng.uniform(0, 0.1, n)
    df = daft_tpu.from_pydict({"k": k.tolist(), "price": price.tolist(),
                               "disc": disc.tolist()})
    q = lambda d: (d.groupby("k")
                   .agg(col("price").sum().alias("sp"),
                        (col("price") * (1 - col("disc"))).sum().alias("sd"))
                   .sort("k"))
    counters.reset()
    with execution_config_ctx(device_mode="on"):
        dev_out = q(df).to_pydict()
    assert counters.device_grouped_batches > 0
    # the device sees the float32 of each value, as the configuration states
    np.testing.assert_allclose(
        dev_out["sp"], np.bincount(k, weights=price), rtol=1e-6)
    np.testing.assert_allclose(
        dev_out["sd"], np.bincount(k, weights=price * (1 - disc)), rtol=1e-6)


def _two_batches():
    """Batch one, 150,000 rows: group "a" throughout; "z" at row 5 (which the
    filter drops) and from row 140,000 on (the third chunk); "b" only in rows
    the filter drops. Batch two, 1,000 rows: "y", then "b", "z", "a"."""
    from daft_tpu.core.recordbatch import RecordBatch
    from daft_tpu.core.series import Series
    from daft_tpu.datatype import DataType
    from daft_tpu.schema import Schema

    schema = Schema.from_pydict({"k": DataType.string(), "v": DataType.float64()})

    def batch(keys, vals):
        cols = [Series.from_pylist(keys, "k"),
                Series.from_numpy(np.asarray(vals, dtype=np.float64), "v",
                                  DataType.float64())]
        return RecordBatch(schema, cols, len(keys))

    n1 = 150_000
    keys = ["a"] * n1
    vals = np.ones(n1)
    keys[5], vals[5] = "z", -1.0
    for j in range(140_000, n1, 2):
        keys[j] = "z"
    for j in range(100, 200):
        keys[j], vals[j] = "b", -1.0
    keys2 = (["y", "b", "z", "a"] * 250)
    return schema, [batch(keys, vals), batch(keys2, np.full(1000, 2.0))]


@pytest.mark.parametrize("form", ["select", "matmul"])
def test_group_order_is_first_kept_occurrence_across_batches(form):
    from daft_tpu.ops.grouped_stage import GroupedAggStage
    from daft_tpu.ops.stage import stage_literals

    schema, batches = _two_batches()
    stage = GroupedAggStage(schema, col("v") > 0, [col("k")],
                            [("s", col("v").sum()), ("n", col("v").count(mode="all"))])
    stage._jitted[8] = stage._build(8, form=form)
    run = stage.start_run(stage_literals(col("v") > 0, []))
    for b in batches:
        run.feed_batch(b)
    key_rows, results = run.finalize()
    assert key_rows == [("a",), ("z",), ("y",), ("b",)]
    sums, counts = results[0][0], results[1][0]
    assert list(counts) == [150_000 - 5_000 - 101 + 250, 5_000 + 250, 250, 250]
    np.testing.assert_allclose(sums, [144_899 + 500, 5_000 + 500, 500, 500])


@pytest.mark.parametrize("form", ["select", "matmul"])
def test_aggs_that_share_a_child_share_their_planes(form):
    """sum, mean and count of one child read one count plane and one sum
    plane; count(mode="all") reads the kept-rows plane."""
    from daft_tpu.ops.grouped_stage import GroupedAggStage

    schema, batches = _two_batches()
    stage = GroupedAggStage(schema, None, [col("k")], [
        ("s", col("v").sum()), ("m", col("v").mean()), ("c", col("v").count()),
        ("n", col("v").count(mode="all"))])
    assert len(stage._mm_specs) == 3
    stage._jitted[8] = stage._build(8, form=form)
    run = stage.start_run()
    run.feed_batch(batches[1])
    key_rows, results = run.finalize()
    assert key_rows == [("y",), ("b",), ("z",), ("a",)]
    (s, _), (m, _), (c, _), (n_all, _) = results
    assert list(c) == list(n_all) == [250] * 4
    np.testing.assert_allclose(s, [500.0] * 4)
    np.testing.assert_allclose(m, [2.0] * 4)


def test_count_all_counts_rows_with_null_children():
    df = daft_tpu.from_pydict({
        "k": ["x", "y", "x", "y", "x"],
        "v": [1.0, None, None, 4.0, 5.0],
    })
    q = lambda d: (d.groupby("k")
                   .agg(col("v").count(mode="all").alias("n"),
                        col("v").count().alias("c"), col("v").sum().alias("s"))
                   .sort("k"))
    dev_out, host_out, _forms = _device_and_host(q, df)
    assert dev_out == host_out
    assert dev_out["n"] == [3, 2] and dev_out["c"] == [2, 1]

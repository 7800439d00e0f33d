"""Out-of-core execution: blocking operators must respect memory_limit_bytes
by spilling (Grace hash partitions for agg/join, range-bucketed runs for sort)
and produce results identical to the unbounded in-memory paths."""

import numpy as np
import pytest

import daft_tpu
from daft_tpu import col
from daft_tpu.config import execution_config_ctx
from daft_tpu import memory as mem
from daft_tpu.observability.metrics import registry


@pytest.fixture
def data():
    rng = np.random.default_rng(42)
    n = 50_000
    return daft_tpu.from_pydict({
        "k": rng.integers(0, 500, n).tolist(),
        "s": rng.choice(["aa", "bb", "cc", None, "dd"], n).tolist(),
        "v": [None if i % 17 == 0 else float(i % 1009) for i in range(n)],
    })


def _with_and_without_cap(q):
    mem.reset_counters()
    with execution_config_ctx(memory_limit_bytes=64 * 1024, device_mode="off"):
        capped = q().to_pydict()
    assert registry().get("spill_batches") > 0, "memory cap never triggered a spill"
    mem.reset_counters()
    with execution_config_ctx(memory_limit_bytes=0, device_mode="off"):
        unbounded = q().to_pydict()
    assert registry().get("spill_batches") == 0
    return capped, unbounded


def test_grouped_agg_spills_and_matches(data):
    def q():
        return (data.groupby("k")
                .agg(col("v").sum().alias("sv"), col("v").mean().alias("mv"),
                     col("v").count().alias("c"), col("v").min().alias("lo"),
                     col("v").max().alias("hi"))
                .sort("k"))

    capped, unbounded = _with_and_without_cap(q)
    assert capped["k"] == unbounded["k"]
    assert capped["c"] == unbounded["c"]
    for c in ("sv", "mv", "lo", "hi"):
        np.testing.assert_allclose(capped[c], unbounded[c], rtol=1e-12)


def test_grouped_agg_string_keys_with_nulls_spills(data):
    def q():
        return (data.groupby("s").agg(col("v").sum().alias("sv")).sort("s"))

    capped, unbounded = _with_and_without_cap(q)
    assert capped == unbounded


def test_count_distinct_grace_raw_spill(data):
    """Unsplittable aggs (count_distinct) Grace-partition raw rows by key."""
    def q():
        return (data.groupby("k")
                .agg(col("v").count_distinct().alias("cd"))
                .sort("k"))

    capped, unbounded = _with_and_without_cap(q)
    assert capped == unbounded


def test_external_sort_matches(data):
    def q():
        return data.sort(["v", "k"])

    capped, unbounded = _with_and_without_cap(q)
    assert capped == unbounded


def test_external_sort_descending_nulls(data):
    def q():
        return data.sort(["v"], desc=True)

    capped, unbounded = _with_and_without_cap(q)
    assert capped == unbounded


def test_external_sort_string_key(data):
    def q():
        return data.sort(["s", "v"])

    capped, unbounded = _with_and_without_cap(q)
    assert capped == unbounded


def test_grace_join_matches(data):
    rng = np.random.default_rng(7)
    other = daft_tpu.from_pydict({
        "k": rng.integers(0, 500, 30_000).tolist(),
        "w": rng.uniform(0, 1, 30_000).tolist(),
    })

    def q():
        return (data.join(other, on="k")
                .groupby("k").agg(col("w").sum().alias("sw"))
                .sort("k"))

    capped, unbounded = _with_and_without_cap(q)
    assert capped["k"] == unbounded["k"]
    np.testing.assert_allclose(capped["sw"], unbounded["sw"], rtol=1e-12)


def test_grace_outer_join_matches(data):
    left = daft_tpu.from_pydict({
        "k": list(range(20_000)),
        "x": [float(i) for i in range(20_000)],
    })
    right = daft_tpu.from_pydict({
        "k": list(range(10_000, 30_000)),
        "y": [float(i) for i in range(10_000, 30_000)],
    })

    def q():
        return left.join(right, on="k", how="outer").sort("k")

    capped, unbounded = _with_and_without_cap(q)
    assert capped == unbounded


def test_tpch_q1_under_memory_cap():
    """A TPC-H pipeline completes under an enforced memory cap with exact results."""
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from benchmarking.tpch.datagen import load_dataframes
    from benchmarking.tpch.queries import ALL_QUERIES

    tables = {k: v.collect() for k, v in load_dataframes(sf=0.05, seed=0).items()}
    mem.reset_counters()
    with execution_config_ctx(memory_limit_bytes=256 * 1024, device_mode="off"):
        capped = ALL_QUERIES[1](tables).to_pydict()
    assert registry().get("spill_batches") > 0
    with execution_config_ctx(memory_limit_bytes=0, device_mode="off"):
        unbounded = ALL_QUERIES[1](tables).to_pydict()
    assert capped["l_returnflag"] == unbounded["l_returnflag"]
    for c in capped:
        if isinstance(capped[c][0], float):
            np.testing.assert_allclose(capped[c], unbounded[c], rtol=1e-12)
        else:
            assert capped[c] == unbounded[c]


def test_external_sort_presorted_input_resplits():
    """Already-sorted input defeats prefix boundary sampling (everything lands
    in the last bucket); the bucket re-splits recursively from its own data
    instead of materializing the whole dataset."""
    n = 60_000
    df = daft_tpu.from_pydict({"v": [float(i) for i in range(n)]})

    def q():
        return df.sort(["v"])

    capped, unbounded = _with_and_without_cap(q)
    assert capped == unbounded


def test_window_spills_and_matches(data):
    """Out-of-core window: over budget, the stream Grace-partitions by the
    PARTITION BY keys and each spill partition evaluates independently
    (reference: sinks/window_partition_only.rs)."""
    from daft_tpu import Window
    from daft_tpu.functions import rank

    w = Window().partition_by("k").order_by("v")

    def q():
        return (data.select(
            col("k"), col("v"),
            col("v").sum().over(w).alias("ws"),
            rank().over(w).alias("wr"),
        ).sort(["k", "v", "ws"]))

    capped, unbounded = _with_and_without_cap(q)
    assert capped == unbounded


def test_global_window_over_budget_still_exact(data):
    from daft_tpu import Window

    w = Window().order_by("v")

    def q():
        return data.select(col("v"), col("v").sum().over(w).alias("c")).sort(["v", "c"])

    mem.reset_counters()
    with execution_config_ctx(memory_limit_bytes=64 * 1024, device_mode="off"):
        capped = q().to_pydict()
    with execution_config_ctx(memory_limit_bytes=0, device_mode="off"):
        unbounded = q().to_pydict()
    assert capped == unbounded


def test_count_distinct_spills_and_matches(data):
    """Unsplittable ungrouped aggs over budget spill the raw stream once and
    Grace-partition each count_distinct's value column — no unbounded buffer."""
    def q():
        return data.agg(
            col("s").count_distinct().alias("ds"),
            col("v").count_distinct().alias("dv"),
            col("v").sum().alias("sv"),
        )

    capped, unbounded = _with_and_without_cap(q)
    assert capped == unbounded


def test_streaming_dedup_incremental_matches(data):
    """Dedup keeps first occurrences via the amortized probe-table path; force
    several rebuilds with a small input stream by distinct-ing a high-dup col."""
    def q():
        return data.distinct("k").sort("k")

    with execution_config_ctx(device_mode="off"):
        out = q().to_pydict()
    ks = [k for k in out["k"]]
    assert len(ks) == len(set(ks))
    assert sorted(set(data.to_pydict()["k"])) == sorted(ks)


def test_sort_merge_join_strategy_matches_hash(data):
    dim = daft_tpu.from_pydict({"k": list(range(0, 500, 3)),
                                "w": [float(i) for i in range(0, 500, 3)]})
    for how in ("inner", "left", "semi", "anti", "right", "outer"):
        sm = (data.join(dim, on="k", how=how, strategy="sort_merge")
              .sort(["k", "v"]).limit(200).to_pydict())
        hj = data.join(dim, on="k", how=how).sort(["k", "v"]).limit(200).to_pydict()
        assert sm == hj, how


def test_sort_merge_algorithm_kernel_parity():
    """join_indices(algorithm='sort_merge') (order-preserving encode + sorted
    merge) must produce the same pairs as the hash algorithm."""
    import numpy as np

    from daft_tpu.core.kernels.join import join_indices
    from daft_tpu.core.series import Series

    rng = np.random.default_rng(5)
    l = [Series.from_pylist([int(x) if x % 7 else None for x in rng.integers(0, 40, 200)], "a")]
    r = [Series.from_pylist([int(x) if x % 5 else None for x in rng.integers(0, 40, 80)], "a")]
    for how in ("inner", "left", "semi", "anti", "outer"):
        for nen in (False, True):
            h = join_indices(l, r, how, nen)
            s = join_indices(l, r, how, nen, algorithm="sort_merge")
            assert np.array_equal(h[0], s[0]) and np.array_equal(h[1], s[1]), (how, nen)


def test_streaming_dedup_rebuild_path():
    """Enough distinct keys to cross the 64k rebuild threshold: the amortized
    ProbeTable build+probe branch must run and stay exact (keep-first)."""
    n = 150_000
    df = daft_tpu.from_pydict({
        "k": [i % 140_000 for i in range(n)],
        "v": list(range(n)),
    })
    with execution_config_ctx(device_mode="off", pipeline_mode="off"):
        # multiple batches so later batches PROBE the rebuilt table
        out = df.into_batches(32 * 1024).select(col("k")).distinct("k").to_pydict()
    assert len(out["k"]) == 140_000
    assert out["k"][:5] == [0, 1, 2, 3, 4]  # keep-first preserves stream order

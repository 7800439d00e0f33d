"""Device join+aggregate fusion (ops/device_join.py): the gather-network join
must produce EXACTLY the host engine's results — nulls, filtered dims, chained
dims, string predicates, and fallbacks included. device_mode="on" forces the
device path (these tests run it on the CPU backend, where jit semantics are
identical to TPU)."""

import numpy as np
import pytest

import daft_tpu
from daft_tpu import col, lit
from daft_tpu.config import execution_config_ctx
from daft_tpu.ops import counters


def _both(q):
    with execution_config_ctx(device_mode="off"):
        host = q().to_pydict()
    counters.reset()
    with execution_config_ctx(device_mode="on"):
        dev = q().to_pydict()
    return host, dev, counters.device_join_batches


def _assert_close(host, dev):
    assert list(host.keys()) == list(dev.keys())
    for c in host:
        hv, dv = host[c], dev[c]
        assert len(hv) == len(dv), (c, len(hv), len(dv))
        for a, b in zip(hv, dv):
            if isinstance(a, float) and isinstance(b, float):
                assert abs(a - b) <= 1e-6 * max(1.0, abs(a)), (c, a, b)
            else:
                assert a == b, (c, a, b)


@pytest.fixture
def one_bucket(monkeypatch):
    """A join dispatch over a resident fact covers ONE bucket, as every
    dispatch did before a resident run's morsels were held to
    DISPATCH_SEGMENTS of them: the tests that count a dispatch a morsel."""
    import daft_tpu.ops.grouped_stage as gs

    monkeypatch.setattr(gs, "DISPATCH_SEGMENTS", 1)


@pytest.fixture(params=[1, 8], ids=["one_bucket", "segments"])
def segments(request, monkeypatch):
    """Both lengths of a join dispatch over a resident fact: one bucket, and
    the shipped DISPATCH_SEGMENTS buckets walked as segments."""
    import daft_tpu.ops.grouped_stage as gs

    assert gs.DISPATCH_SEGMENTS == 8
    monkeypatch.setattr(gs, "DISPATCH_SEGMENTS", request.param)
    return request.param


def _dispatches(morsels, segments, shards=1):
    """Join dispatches over a resident fact of `morsels` morsels: `segments`
    buckets a shard each, fewer where the fact is short (a dispatch is never
    the whole fact: batching.resident_dispatch_segments)."""
    from daft_tpu.execution.batching import resident_dispatch_segments

    segments = min(segments, resident_dispatch_segments(-(-morsels // shards)))
    return -(-morsels // (segments * shards))


@pytest.fixture(scope="module")
def star():
    rng = np.random.default_rng(9)
    n = 20_000
    fact = daft_tpu.from_pydict({
        "f_k1": [int(x) if x % 37 else None for x in rng.integers(0, 500, n)],
        "f_v": rng.uniform(0, 100, n).tolist(),
        "f_tag": rng.choice(["aa", "bb", "cc", "dd"], n).tolist(),
        "f_q": rng.integers(1, 50, n).tolist(),
    }).collect()
    d1 = daft_tpu.from_pydict({           # keyed dim with a chained FK
        "d1_k": list(range(500)),
        "d1_grp": [f"g{i % 7}" for i in range(500)],
        "d1_w": [float(i % 13) for i in range(500)],
        "d1_k2": [i % 40 for i in range(500)],
    }).collect()
    d2 = daft_tpu.from_pydict({           # second-hop dim
        "d2_k": list(range(40)),
        "d2_name": [f"n{i % 5}" for i in range(40)],
        "d2_flag": [i % 3 == 0 for i in range(40)],
    }).collect()
    return fact, d1, d2


def test_single_dim_grouped_matches(star):
    fact, d1, _ = star

    def q():
        return (fact.join(d1, left_on="f_k1", right_on="d1_k")
                .groupby("d1_grp")
                .agg(col("f_v").sum().alias("sv"),
                     (col("f_v") * col("d1_w")).sum().alias("svw"),
                     col("f_v").count().alias("c"))
                .sort("d1_grp"))

    host, dev, jb = _both(q)
    assert jb > 0, "device join path never ran"
    _assert_close(host, dev)


def test_chained_dims_and_dim_filter(star):
    fact, d1, d2 = star

    def q():
        return (fact.join(d1, left_on="f_k1", right_on="d1_k")
                .join(d2, left_on="d1_k2", right_on="d2_k")
                .where(col("d2_flag") == lit(True))
                .groupby("d2_name")
                .agg(col("f_v").sum().alias("sv"))
                .sort("d2_name"))

    host, dev, jb = _both(q)
    assert jb > 0
    _assert_close(host, dev)


def test_fact_string_predicate_lowered_to_codes(star):
    fact, d1, _ = star

    def q():
        return (fact.where(col("f_tag").is_in(["aa", "cc"]))
                .join(d1, left_on="f_k1", right_on="d1_k")
                .groupby("d1_grp")
                .agg(col("f_q").sum().alias("sq"))
                .sort("d1_grp"))

    host, dev, jb = _both(q)
    assert jb > 0
    _assert_close(host, dev)


def test_fact_string_group_key_with_dim_math(star):
    fact, d1, _ = star

    def q():
        return (fact.join(d1, left_on="f_k1", right_on="d1_k")
                .groupby("f_tag")
                .agg((col("f_v") * (1 - col("d1_w") / 100)).sum().alias("rev"))
                .sort("f_tag"))

    host, dev, jb = _both(q)
    assert jb > 0
    _assert_close(host, dev)


def test_ungrouped_join_agg(star):
    fact, d1, _ = star

    def q():
        return (fact.join(d1, left_on="f_k1", right_on="d1_k")
                .where(col("d1_grp").is_in(["g1", "g3"]))
                .agg(col("f_v").sum().alias("s"), col("f_v").mean().alias("m"),
                     col("f_v").count().alias("c")))

    host, dev, jb = _both(q)
    assert jb > 0
    _assert_close(host, dev)


def test_null_fact_keys_never_match(star):
    fact, d1, _ = star
    # ~1/37 of f_k1 are null; inner-join must drop them on both paths

    def q():
        return (fact.join(d1, left_on="f_k1", right_on="d1_k")
                .agg(col("f_v").count().alias("c")))

    host, dev, jb = _both(q)
    assert jb > 0
    _assert_close(host, dev)
    with execution_config_ctx(device_mode="off"):
        total = fact.count_rows()
    assert host["c"][0] < total  # nulls really were dropped


def test_non_unique_dim_key_falls_back_to_host(star):
    fact, _, _ = star
    dup = daft_tpu.from_pydict({
        "d_k": [1, 2, 2, 3], "d_w": [1.0, 2.0, 3.0, 4.0]}).collect()

    def q():
        return (fact.join(dup, left_on="f_k1", right_on="d_k")
                .agg(col("d_w").sum().alias("s")))

    host, dev, jb = _both(q)
    assert jb == 0, "non-unique dim keys must not take the device join"
    _assert_close(host, dev)


def test_high_cardinality_groups_fall_back(star):
    fact, _, _ = star
    big_dim = daft_tpu.from_pydict({
        "b_k": list(range(500)),
        "b_id": [f"id{i}" for i in range(500)],
    }).collect()

    def q():
        # group by (b_id x f_q): cardinality 500*49 >> 4096 matmul ceiling
        return (fact.join(big_dim, left_on="f_k1", right_on="b_k")
                .groupby("b_id", "f_q")
                .agg(col("f_v").sum().alias("s"))
                .sort(["b_id", "f_q"]).limit(50))

    host, dev, _jb = _both(q)
    _assert_close(host, dev)


def test_tpch_device_join_sweep():
    """All 22 TPC-H queries with device_mode=on match host exactly, and the
    star-join queries actually ride the device join path."""
    from benchmarking.tpch.datagen import load_dataframes
    from benchmarking.tpch.queries import ALL_QUERIES

    tables = {k: v.collect() for k, v in load_dataframes(sf=0.01, seed=0).items()}
    rode_device = []
    for qn in range(1, 23):
        with execution_config_ctx(device_mode="off"):
            host = ALL_QUERIES[qn](tables).to_pydict()
        counters.reset()
        with execution_config_ctx(device_mode="on"):
            dev = ALL_QUERIES[qn](tables).to_pydict()
        if counters.device_join_batches:
            rode_device.append(qn)
        _assert_close(host, dev)
    assert set(rode_device) >= {3, 5, 10, 12, 14, 19}, rode_device


def test_tpch_q3_q10_ride_device_topn():
    """The ORDER BY + LIMIT tails of q3/q10 fuse into the device program
    (DeviceJoinTopN): group tables never leave the device, only K winner rows
    are fetched — the shape that makes orderkey-cardinality groupbys
    device-viable (VERDICT r4 next #1/#4)."""
    from benchmarking.tpch.datagen import load_dataframes
    from benchmarking.tpch.queries import ALL_QUERIES

    tables = {k: v.collect() for k, v in load_dataframes(sf=0.01, seed=0).items()}
    for qn in (3, 10):
        with execution_config_ctx(device_mode="off"):
            host = ALL_QUERIES[qn](tables).to_pydict()
        counters.reset()
        with execution_config_ctx(device_mode="on"):
            dev = ALL_QUERIES[qn](tables).to_pydict()
        assert counters.device_topn_runs == 1, \
            (qn, counters.device_topn_runs, counters.rejections)
        _assert_close(host, dev)


def test_wide_int_dim_planes_exact_past_2_24(star):
    """Dim-side int64/int32/uint32 columns ride the packed f32 gather as digit
    planes and recombine exactly inside the traced provisioning program — and
    must STAY exact through the stage compiler (ADVICE r5 high: the f64
    recombine was downcast to f32 by fcast, quantizing values past 2^24).
    SUM/MIN/MAX over wide int dim columns must match the host bit-for-bit:
    past 2^24, negative, null, just under 2^53 and, unsigned, past 2^31."""
    fact, _, _ = star
    top = (1 << 53) - 1
    wide = daft_tpu.from_pydict({
        "w_k": list(range(500)),
        # int64 values far past 2^24 (and sums past 2^32)
        "w_big": [300_266_000_000 + i * 7_919 for i in range(500)],
        # int32 values past 2^24 (f32 quantizes these)
        "w_mid": np.asarray([16_777_216 + i * 3 for i in range(500)],
                            dtype=np.int32),
        # int64: negative digits, nulls, and the last value f64 still holds
        "w_neg": [None if i % 11 == 0 else
                  (top - i if i % 3 == 0 else -(1 << 40) * (i + 1) - i)
                  for i in range(500)],
        # int32 down to its minimum
        "w_neg32": np.asarray([-(1 << 31) + i * 5 if i % 2 else -(16_777_217 + i)
                               for i in range(500)], dtype=np.int32),
        # uint32 past 2^31 (no int32 holds these)
        "w_u32": np.asarray([(1 << 31) + 12_345 + i * 1_000_003 if i % 2
                             else (1 << 32) - 1 - i for i in range(500)],
                            dtype=np.uint32),
        "w_grp": [f"g{i % 5}" for i in range(500)],
    }).collect()
    assert wide.schema["w_neg"].dtype == daft_tpu.DataType.int64()
    assert wide.schema["w_u32"].dtype == daft_tpu.DataType.uint32()

    def q():
        return (fact.join(wide, left_on="f_k1", right_on="w_k")
                .groupby("w_grp")
                .agg(col("w_big").sum().alias("s64"),
                     col("w_big").min().alias("mn64"),
                     col("w_big").max().alias("mx64"),
                     col("w_mid").sum().alias("s32"),
                     col("w_mid").min().alias("mn32"),
                     col("w_mid").max().alias("mx32"),
                     col("w_neg").min().alias("mnneg"),
                     col("w_neg").max().alias("mxneg"),
                     col("w_neg").count().alias("cneg"),
                     col("w_neg32").sum().alias("sneg32"),
                     col("w_neg32").min().alias("mnneg32"),
                     col("w_u32").sum().alias("su32"),
                     col("w_u32").min().alias("mnu32"),
                     col("w_u32").max().alias("mxu32"))
                .sort("w_grp"))

    host, dev, jb = _both(q)          # _both zeroes the counters before the device run
    assert jb > 0, "device join path never ran"
    assert counters.join_provision_calls == jb, "the traced program served every dispatch"
    assert max(host["mxneg"]) >= top - 500 and min(host["mnneg"]) < -(1 << 48)
    assert max(host["mxu32"]) > (1 << 31)
    # bit-for-bit integer equality — no float tolerance
    for c in host:
        assert host[c] == dev[c], (c, host[c], dev[c])


# ---- the five routes into _JoinContext.provision all take the traced program ----------


def _route_dict(fact, d1, cut):
    return (fact.join(d1, left_on="f_k1", right_on="d1_k")
            .where((col("f_q") > cut) & (col("d1_w") < float(cut)))
            .groupby("d1_grp")
            .agg(col("f_v").sum().alias("sv"), (col("f_v") * col("d1_w")).sum().alias("svw"))
            .sort("d1_grp"))


def _route_host(fact, d1, cut):
    # dictionary product 500 x 500 is past the matmul ceiling; the true count (500) is not
    return (fact.join(d1, left_on="f_k1", right_on="d1_k")
            .where(col("f_q") > cut)
            .groupby("f_k1", "d1_k")
            .agg(col("f_v").sum().alias("sv"), col("d1_w").sum().alias("sw"))
            .sort("f_k1"))


def _route_host_permuted(fact, d1, cut):
    # some 13,000 true groups: past the ceiling, so rows go group-sorted
    return (fact.join(d1, left_on="f_k1", right_on="d1_k")
            .where(col("d1_w") < float(cut))
            .groupby("f_k1", "f_q")
            .agg(col("f_v").sum().alias("sv"), col("d1_w").sum().alias("sw"))
            .sort(["f_k1", "f_q"]))


def _route_topn(fact, d1, cut):
    return (fact.join(d1, left_on="f_k1", right_on="d1_k")
            .where(col("f_q") > cut)
            .groupby("f_k1", "d1_k2")
            .agg((col("f_v") * col("d1_w")).sum().alias("rev"))
            .sort(["rev", "f_k1"], desc=[True, False]).limit(7))


def _route_ungrouped(fact, d1, cut):
    return (fact.join(d1, left_on="f_k1", right_on="d1_k")
            .where((col("f_q") > cut) & col("d1_grp").is_in(["g1", "g3"]))
            .agg(col("f_v").sum().alias("s"), col("d1_w").sum().alias("w"),
                 col("f_v").count().alias("c")))


@pytest.mark.parametrize("shape,route", [
    (_route_dict, ("grouped", True, False)),
    (_route_host, ("grouped", False, False)),
    (_route_host_permuted, ("grouped", False, True)),
    (_route_topn, ("topn", False, False)),
    (_route_ungrouped, ("ungrouped", False, False)),
], ids=["dict_codes", "host_codes", "host_codes_permuted", "topn", "ungrouped"])
def test_every_route_provisions_through_one_traced_program(star, monkeypatch, shape, route):
    """The same star query twice, then with another filter literal: the
    provisioning program is traced on the first run only (a literal is no
    part of its key), serves every join dispatch, and the answers are the
    host tier's."""
    from daft_tpu.ops import device_join as dj

    fact, d1, _ = star
    seen = []
    real = dj._JoinContext.provision

    def spy(self, batch, bucket, needed, codes=None, perm=None):
        seen.append((codes is not None, perm is not None))
        return real(self, batch, bucket, needed, codes=codes, perm=perm)

    monkeypatch.setattr(dj._JoinContext, "provision", spy)
    dj._provision_program.cache_clear()      # other tests may have traced this layout
    traced = []
    for cut in (10, 10, 25):
        q = lambda: shape(fact, d1, cut)
        with execution_config_ctx(device_mode="off"):
            host = q().to_pydict()
        counters.reset()
        with execution_config_ctx(device_mode="on"):
            dev = q().to_pydict()
        assert counters.device_join_batches > 0, counters.rejections
        assert counters.join_provision_calls == counters.device_join_batches
        kind, by_dict, permuted = route
        assert set(seen) == {(by_dict, permuted)}, seen
        assert (counters.device_topn_runs > 0) == (kind == "topn")
        assert (counters.device_stage_batches > 0) == (kind == "ungrouped")
        traced.append(counters.join_provision_traces)
        _assert_close(host, dev)
    assert traced[0] >= 1 and traced[1:] == [0, 0], traced


@pytest.mark.parametrize("shape,reduce", [
    (_route_dict, "select"),            # 7 groups from a dictionary: cap 8
    (_route_host, "matmul"),            # 500 groups from the host's codes: cap 512
    (_route_host_permuted, None),       # the local-dense program (_jit_local)
], ids=["dict_cap8", "host_cap512", "host_permuted_local"])
def test_join_agg_routes_keep_their_answers(star, shape, reduce):
    """The join's grouped routes share GroupedAggStage's programs: the
    one-hot tier's (_program_for: planes evaluated inside the chunk loop, the
    reduce form a function of the group capacity) and the local-dense one. Each gives
    the host tier's answer, and the counters say which reduce served it."""
    fact, d1, _ = star
    host, dev, batches = _both(lambda: shape(fact, d1, 10))
    assert batches > 0, counters.rejections
    ran = {"select": counters.device_grouped_reduce_select,
           "matmul": counters.device_grouped_reduce_matmul}
    assert {f for f, n in ran.items() if n} == ({reduce} if reduce else set()), ran
    if reduce:
        assert ran[reduce] == batches
    _assert_close(host, dev)


def test_auto_mode_cpu_backend_stays_on_host(star):
    """auto mode on a CPU backend must run the host plan AND record why
    (rejection log, VERDICT r4 next #1) — device joins only engage on a real
    accelerator via the measured cost model."""
    fact, d1, _ = star

    def q():
        return (fact.join(d1, left_on="f_k1", right_on="d1_k")
                .groupby("d1_grp").agg(col("f_v").sum().alias("s")).sort("d1_grp"))

    counters.reset()
    with execution_config_ctx(device_mode="auto", device_min_rows=1):
        out = q().to_pydict()
    assert counters.device_join_batches == 0
    assert any("cpu backend" in k for k in counters.rejections), \
        counters.rejections
    with execution_config_ctx(device_mode="off"):
        assert out == q().to_pydict()


# ---- repeat queries over morselized resident tables: slots follow the data ------------
#
# q3- and q5-shaped star joins (benchmark/queries/tpch.py) over tables that the
# pipeline cuts into morsels on every query: a fact of four morsels, an
# `orders` dim over two morsels (cut by its Project and glued together again).

_MORSEL = 2048


def _days(y, m, d):
    import datetime

    return datetime.date(y, m, d)


def _tpch_like(seed=5, orders_shuffle=None, n_l=7000):
    import datetime

    rng = np.random.default_rng(seed)
    n_o, n_c, n_s = 5000, 500, 100
    assert n_l > 3 * _MORSEL and n_o > 2 * _MORSEL
    day0 = datetime.date(1994, 1, 1)
    dates = lambda n: [day0 + datetime.timedelta(days=int(x)) for x in rng.integers(0, 730, n)]
    o_custkey = rng.integers(0, n_c, n_o)
    if orders_shuffle is not None:      # equal shape, the keys dealt out differently
        o_custkey = np.random.default_rng(orders_shuffle).permutation(o_custkey)
    t = {
        "region": {"r_regionkey": list(range(5)),
                   "r_name": ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]},
        "nation": {"n_nationkey": list(range(25)), "n_name": [f"N{i:02d}" for i in range(25)],
                   "n_regionkey": [i % 5 for i in range(25)]},
        "customer": {"c_custkey": list(range(n_c)),
                     "c_mktsegment": rng.choice(["BUILDING", "MACHINERY", "AUTOMOBILE"], n_c).tolist(),
                     "c_nationkey": rng.integers(0, 25, n_c).tolist()},
        "supplier": {"s_suppkey": list(range(n_s)),
                     "s_nationkey": rng.integers(0, 25, n_s).tolist()},
        "orders": {"o_orderkey": list(range(n_o)), "o_custkey": o_custkey.tolist(),
                   "o_orderdate": dates(n_o),
                   "o_shippriority": rng.integers(0, 2, n_o).tolist()},
        "lineitem": {"l_orderkey": np.sort(rng.integers(0, n_o, n_l)).tolist(),
                     "l_suppkey": rng.integers(0, n_s, n_l).tolist(),
                     "l_extendedprice": rng.uniform(900, 90000, n_l).round(2).tolist(),
                     "l_discount": (rng.integers(0, 11, n_l) / 100).tolist(),
                     "l_shipdate": dates(n_l)},
    }
    return {name: daft_tpu.from_pydict(cols).collect() for name, cols in t.items()}


def _q3_shaped(t, segment="BUILDING", cut=(1995, 3, 15)):
    return (t["customer"].where(col("c_mktsegment") == segment)
            .join(t["orders"], left_on="c_custkey", right_on="o_custkey")
            .where(col("o_orderdate") < _days(*cut))
            .join(t["lineitem"], left_on="o_orderkey", right_on="l_orderkey")
            .where(col("l_shipdate") > _days(*cut))
            .groupby(col("o_orderkey").alias("l_orderkey"), "o_orderdate", "o_shippriority")
            .agg((col("l_extendedprice") * (1 - col("l_discount"))).sum().alias("revenue"))
            .select("l_orderkey", "revenue", "o_orderdate", "o_shippriority")
            .sort(["revenue", "o_orderdate"], desc=[True, False])
            .limit(10))


def _q5_shaped(t, region="ASIA", cut=(1994, 1, 1)):
    return (t["region"].where(col("r_name") == region)
            .join(t["nation"], left_on="r_regionkey", right_on="n_regionkey")
            .join(t["customer"], left_on="n_nationkey", right_on="c_nationkey")
            .join(t["orders"], left_on="c_custkey", right_on="o_custkey")
            .where((col("o_orderdate") >= _days(*cut)) & (col("o_orderdate") < _days(1995, 1, 1)))
            .join(t["lineitem"], left_on="o_orderkey", right_on="l_orderkey")
            .join(t["supplier"], left_on=["l_suppkey", "n_nationkey"],
                  right_on=["s_suppkey", "s_nationkey"])
            .groupby("n_name")
            .agg((col("l_extendedprice") * (1 - col("l_discount"))).sum().alias("revenue"))
            .sort("revenue", desc=True))


def _morselized(device_mode):
    return execution_config_ctx(device_mode=device_mode, morsel_size_rows=_MORSEL,
                                pipeline_mode="force")


def _host_answer(q):
    with _morselized("off"):
        return q().to_pydict()


_WARM = ("hbm_cache_misses", "hbm_h2d_bytes", "hbm_lineage_hits")


def _device_run(q):
    """(answer, registry deltas, join dispatches) of one forced device run."""
    from daft_tpu.observability.metrics import registry

    before = {k: registry().get(k) for k in _WARM}
    jb = counters.device_join_batches
    with _morselized("on"):
        out = q().to_pydict()
    return (out, {k: registry().get(k) - before[k] for k in _WARM},
            counters.device_join_batches - jb)


@pytest.fixture(scope="module")
def tpch_like():
    return _tpch_like()


@pytest.mark.parametrize("shape", [_q3_shaped, _q5_shaped], ids=["q3", "q5"])
def test_morselized_repeat_query_hits_every_slot(tpch_like, shape, segments):
    from daft_tpu.device.residency import manager

    manager().clear()
    q = lambda: shape(tpch_like)
    host = _host_answer(q)
    first, cold, dispatches = _device_run(q)
    # one join dispatch per fact morsel, or one for every two of the four, glued
    assert dispatches == _dispatches(4, segments)
    assert cold["hbm_cache_misses"] > 0 and cold["hbm_h2d_bytes"] > 0
    _assert_close(host, first)
    for _ in range(2):      # from the second execution on: nothing built, nothing uploaded
        again, warm, d = _device_run(q)
        assert warm["hbm_cache_misses"] == 0 and warm["hbm_h2d_bytes"] == 0, warm
        if segments == 1:
            assert warm["hbm_lineage_hits"] > 0, "fresh morsel objects found their rows' slots"
        assert d == dispatches, "the dispatch shape is what it was"
        assert again == first
    manager().clear()


def test_morselized_dim_keeps_its_columns(tpch_like, monkeypatch):
    """A dim over two morsels is cut by its Project and glued together again:
    the join context sees the table's own columns, not copies."""
    from daft_tpu.ops.device_join import _JoinContext

    seen = []
    real = _JoinContext.__init__

    def spy(self, spec, dim_batches):
        seen.append(dim_batches)
        real(self, spec, dim_batches)

    monkeypatch.setattr(_JoinContext, "__init__", spy)
    with _morselized("on"):
        _q3_shaped(tpch_like).to_pydict()
    orders = tpch_like["orders"]._result[0].batches[0]
    assert orders.num_rows > 2 * _MORSEL, "the dim must be large enough to be morselized"
    (dim,) = [b for b in seen[-1].values() if b.num_rows == orders.num_rows]
    for name in dim.column_names():
        assert dim.get_column(name) is orders.get_column(name)


@pytest.mark.parametrize("shape", [_q3_shaped, _q5_shaped], ids=["q3", "q5"])
def test_rebound_dim_of_equal_shape_gives_its_own_answer(shape):
    """The stale-answer guard: `orders` rebound to a table of equal shape and
    schema whose customer keys differ must never be served the first table's
    join indices."""
    from daft_tpu.device.residency import manager

    manager().clear()
    t = _tpch_like()
    first, _, _ = _device_run(lambda: shape(t))
    _device_run(lambda: shape(t))                           # warm: every slot resident
    t2 = dict(t, orders=_tpch_like(orders_shuffle=99)["orders"])
    assert t2["orders"].count_rows() == t["orders"].count_rows()
    host2 = _host_answer(lambda: shape(t2))
    dev2, _, _ = _device_run(lambda: shape(t2))
    _assert_close(host2, dev2)
    assert dev2 != first, "the fixture must make the two answers differ"
    back, _, _ = _device_run(lambda: shape(t))              # and the first table's again
    assert back == first
    manager().clear()


@pytest.mark.parametrize("shape,variants", [
    (_q3_shaped, [dict(segment="MACHINERY"), dict(cut=(1995, 6, 1)), dict()]),
    (_q5_shaped, [dict(region="EUROPE"), dict(cut=(1994, 6, 1)), dict()]),
], ids=["q3", "q5"])
def test_changed_literal_gives_the_new_answer(tpch_like, shape, variants):
    from daft_tpu.device.residency import manager

    manager().clear()
    base, _, _ = _device_run(lambda: shape(tpch_like))
    _device_run(lambda: shape(tpch_like))
    answers = []
    for kw in variants:
        q = lambda: shape(tpch_like, **kw)
        dev, _, _ = _device_run(q)
        _assert_close(_host_answer(q), dev)
        answers.append(dev)
    assert answers[0] != base and answers[1] != base and answers[2] == base
    manager().clear()


# ---- a dim filter's values are arguments ------------------------------------------------
#
# The dim filters of a star join take their literal values as arguments of the
# subtree's visibility program: a pack holds no value, a string comparison is a
# comparison of dictionary codes on the device, and the verdict plane belongs
# to the query. What a new value may cost: one call of a program traced before.

_VALUE_COUNTERS = ("hbm_literal_rebuilds", "join_filter_program_traces",
                   "join_provision_traces", "device_stage_program_traces",
                   "hbm_cache_misses", "hbm_h2d_bytes")


def _value_run(q):
    """(answer, what the run counted of _VALUE_COUNTERS and the filter's
    literal arguments) of one forced device run."""
    from daft_tpu.observability.metrics import registry

    names = _VALUE_COUNTERS + ("join_filter_literal_args",)
    before = {k: registry().get(k) for k in names}
    jb = counters.device_join_batches
    with _morselized("on"):
        out = q().to_pydict()
    assert counters.device_join_batches > jb, counters.rejections
    return out, {k: registry().get(k) - before[k] for k in names}


def _with_a_null_segment(t):
    """`t` with every seventh customer's segment null."""
    c = t["customer"].to_pydict()
    c["c_mktsegment"] = [None if i % 7 == 0 else v for i, v in enumerate(c["c_mktsegment"])]
    return dict(t, customer=daft_tpu.from_pydict(c).collect())


def _q3_where(t, segment_filter, cut=(1995, 3, 15)):
    """_q3_shaped with any filter over `customer` in the segment's place."""
    return (t["customer"].where(segment_filter)
            .join(t["orders"], left_on="c_custkey", right_on="o_custkey")
            .where(col("o_orderdate") < _days(*cut))
            .join(t["lineitem"], left_on="o_orderkey", right_on="l_orderkey")
            .where(col("l_shipdate") > _days(*cut))
            .groupby(col("o_orderkey").alias("l_orderkey"), "o_orderdate", "o_shippriority")
            .agg((col("l_extendedprice") * (1 - col("l_discount"))).sum().alias("revenue"))
            .select("l_orderkey", "revenue", "o_orderdate", "o_shippriority")
            .sort(["revenue", "o_orderdate"], desc=[True, False])
            .limit(10))


_SEG = col("c_mktsegment")


@pytest.mark.parametrize("case, nulls, first, then, args, empty", [
    # a value the dictionary lacks: a code no row has, an empty answer, no rebuild
    ("absent_value", False, lambda: _SEG == "BUILDING", lambda: _SEG == "NO SUCH SEGMENT", 2, True),
    # a null in the filtered column fails `==`, and `!=` too, as on the host
    ("null_eq", True, lambda: _SEG == "BUILDING", lambda: _SEG == "MACHINERY", 2, False),
    ("null_neq", True, lambda: _SEG != "BUILDING", lambda: _SEG != "AUTOMOBILE", 3, False),
    ("neq_absent", True, lambda: _SEG != "BUILDING", lambda: _SEG != "NO SUCH SEGMENT", 3, False),
    # is_in of two segments: one skeleton a length, two codes
    ("is_in_two", True, lambda: _SEG.is_in(["BUILDING", "MACHINERY"]),
     lambda: _SEG.is_in(["AUTOMOBILE", "NO SUCH SEGMENT"]), 3, False),
    # the literal on the left
    ("lit_left", False, lambda: lit("BUILDING") == _SEG, lambda: lit("AUTOMOBILE") == _SEG, 2, False),
], ids=lambda v: v if isinstance(v, str) else None)
def test_a_string_filter_on_a_dim_is_a_comparison_of_codes(tpch_like, case, nulls, first, then,
                                                           args, empty):
    """`col == lit`, `!=` and `is_in` over a dimension's string column run as
    comparisons of its dictionary codes inside the visibility program: the
    host tier's answer, the literal's code an argument (with the date, and
    for `!=` the nulls' code), and the second value set costs no rebuild, no
    trace, no build and no upload."""
    from daft_tpu.device.residency import manager

    manager().clear()
    t = _with_a_null_segment(tpch_like) if nulls else tpch_like
    for k, make in enumerate((first, then, first)):
        q = lambda: _q3_where(t, make())
        dev, counted = _value_run(q)
        _assert_close(_host_answer(q), dev)
        assert counted["join_filter_literal_args"] == args, (case, counted)
        if k:
            assert not any(counted[c] for c in _VALUE_COUNTERS), (case, k, counted)
        if k == 1:
            assert (len(dev["l_orderkey"]) == 0) == empty, (case, dev)
    manager().clear()


def test_a_filter_two_links_from_the_fact_takes_its_value_as_an_argument(tpch_like):
    """q5's `r_name == REGION` lies on `region`, chained to the fact through
    `nation`, `customer` and `orders`: its codes are carried to `orders`' rows
    once, and each REGION and DATE is an argument after."""
    from daft_tpu.device.residency import manager

    manager().clear()
    answers = []
    for k, kw in enumerate([dict(), dict(region="EUROPE"), dict(region="AFRICA", cut=(1994, 6, 1)),
                            dict(region="ATLANTIS"), dict()]):
        q = lambda: _q5_shaped(tpch_like, **kw)
        dev, counted = _value_run(q)
        _assert_close(_host_answer(q), dev)
        assert counted["join_filter_literal_args"] == 3       # two dates and the region's code
        if k:
            assert not any(counted[c] for c in _VALUE_COUNTERS), (kw, counted)
        answers.append(dev)
    assert answers[0] == answers[4] and answers[0] != answers[1] != answers[2]
    assert answers[3] == {"n_name": [], "revenue": []}
    manager().clear()


def test_a_host_filter_keeps_its_slot_and_is_counted_as_a_rebuild(tpch_like):
    """A filter no program takes the values of (a LIKE) stays on the host: its
    visibility is kept under its skeleton with its values, and another value
    rebuilds it in place, which `hbm_literal_rebuilds` counts. The pack and
    the device filters' planes beside it hold no value and are not rebuilt."""
    from daft_tpu.device.residency import manager

    manager().clear()
    counts = []
    for prefix in ("BUILD", "MACH", "MACH"):
        q = lambda: _q3_where(tpch_like, _SEG.str.startswith(prefix))
        dev, counted = _value_run(q)
        _assert_close(_host_answer(q), dev)
        assert counted["join_filter_literal_args"] == 1       # the date alone
        counts.append(counted)
    assert counts[0]["hbm_literal_rebuilds"] == 0
    assert counts[1]["hbm_literal_rebuilds"] == 2             # the host's plane, and its upload
    assert counts[2]["hbm_literal_rebuilds"] == 0 and counts[2]["hbm_cache_misses"] == 0
    assert all(c["join_filter_program_traces"] == 0 for c in counts[1:])
    manager().clear()


def test_two_queries_with_different_values_at_once_each_get_their_own_answer(tpch_like):
    """Two threads run q3 with different SEGMENTs and DATEs over the same
    tables, again and again, at the same time: they share the pack and every
    filter plane, each makes a verdict of its own, and neither ever reads the
    other's."""
    import threading

    from daft_tpu.device.residency import manager

    manager().clear()
    variants = [dict(segment="BUILDING", cut=(1995, 3, 15)),
                dict(segment="MACHINERY", cut=(1994, 9, 1))]
    want = [_host_answer(lambda kw=kw: _q3_shaped(tpch_like, **kw)) for kw in variants]
    assert want[0] != want[1]
    start = threading.Barrier(2)
    got, failed = [[], []], []

    def client(k):
        try:
            start.wait()
            for _ in range(6):
                got[k].append(_q3_shaped(tpch_like, **variants[k]).to_pydict())
        except BaseException as e:  # noqa: BLE001 - reported by the asserting thread
            failed.append(e)

    jb = counters.device_join_batches
    with _morselized("on"):     # (the configuration is the process's: both threads run under it)
        _q3_shaped(tpch_like, **variants[0]).to_pydict()       # warm: the slots are resident
        threads = [threading.Thread(target=client, args=(k,)) for k in (0, 1)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
    assert not failed, failed
    assert counters.device_join_batches - jb >= 13, counters.rejections
    for k in (0, 1):
        assert len(got[k]) == 6
        for answer in got[k]:
            _assert_close(want[k], answer)
    manager().clear()


def test_a_join_verdict_is_keyed_on_the_predicates_skeleton_not_its_values(tpch_like, star):
    """Two DATEs of q3's `l_shipdate > DATE` share one placement verdict
    (plan/stats.selectivity reads the operators, never a value); an `is_in`
    of another length is another skeleton, and another decision."""
    from daft_tpu.config import execution_config as get_config
    from daft_tpu.execution import executor
    from daft_tpu.plan import physical as pp

    def node_of(df, kind):
        with _morselized("on"):
            plan = pp.translate(df._builder.optimize()._plan, get_config())
        found, todo = [], [plan]
        while todo:
            n = todo.pop()
            if isinstance(n, kind):
                found.append(n)
            todo.extend(n.children())
        assert len(found) == 1
        return found[0]

    cfg, layout = get_config(), (1, _MORSEL)
    # the plans hold the same tables: their dims' identity tokens agree too
    key = lambda node, topn: executor._decision_key(node, _MORSEL, cfg, topn, layout)
    march, june = (node_of(_q3_shaped(tpch_like, cut=cut), pp.DeviceJoinTopN)
                   for cut in ((1995, 3, 15), (1995, 6, 1)))
    assert repr(march.spec.predicate) != repr(june.spec.predicate)
    assert key(march, True) == key(june, True)

    fact, d1, _d2 = star

    def by_quantities(qs):
        return node_of(fact.where(col("f_q").is_in(qs))
                       .join(d1, left_on="f_k1", right_on="d1_k")
                       .groupby("d1_grp").agg(col("f_v").sum().alias("sv")), pp.DeviceJoinAgg)

    two, other_two, three = (by_quantities(qs) for qs in ([1, 2], [3, 4], [1, 2, 3]))
    assert repr(two.spec.predicate) != repr(other_two.spec.predicate)
    assert key(two, False) == key(other_two, False) != key(three, False)


# ---- the fused TopN over a fact of any number of batches ------------------------------
#
# q3- and q10-shaped star joins whose group-by spans one dimension's key
# space: the group ids are that dimension's rows, one set of tables stays on
# the device for the run, and only the limit's rows come back.


def _topn_tables(n_l, seed=11):
    """Tables for the TopN shapes: a fact sorted by order key (q3's ids are
    then locally dense, q10's customer ids are not), revenues that tie (whole
    prices, discounts of 0 or a half), order dates that grow with the key (a
    date cut leaves the last batches with nothing kept), order keys the dim
    lacks and customer keys the dim lacks (join misses)."""
    import datetime

    rng = np.random.default_rng(seed)
    n_o, n_c = max(n_l // 3, 64), 97
    day0 = datetime.date(1994, 1, 1)
    t = {
        "nation": {"n_nationkey": list(range(25)), "n_name": [f"N{i:02d}" for i in range(25)]},
        "customer": {"c_custkey": list(range(n_c)),
                     "c_name": [f"Customer#{i:05d}" for i in range(n_c)],
                     "c_acctbal": rng.uniform(-999, 9999, n_c).round(2).tolist(),
                     "c_mktsegment": rng.choice(["BUILDING", "MACHINERY"], n_c).tolist(),
                     "c_nationkey": rng.integers(0, 25, n_c).tolist()},
        # every seventh order key is missing; a few orders name no customer
        "orders": {"o_orderkey": [k for k in range(n_o) if k % 7 != 3],
                   "o_custkey": [int(c) for k, c in enumerate(rng.integers(0, n_c + 5, n_o))
                                 if k % 7 != 3],
                   "o_orderdate": [day0 + datetime.timedelta(days=int(730 * k / n_o))
                                   for k in range(n_o) if k % 7 != 3],
                   "o_shippriority": [int(k % 2) for k in range(n_o) if k % 7 != 3]},
        "lineitem": {"l_orderkey": np.sort(rng.integers(0, n_o, n_l)).tolist(),
                     "l_extendedprice": rng.integers(1, 6, n_l).astype(float).tolist(),
                     "l_discount": (rng.integers(0, 2, n_l) * 0.5).tolist(),
                     "l_returnflag": rng.choice(["R", "A", "N"], n_l).tolist(),
                     "l_shipdate": [day0 + datetime.timedelta(days=int(x))
                                    for x in rng.integers(0, 730, n_l)]},
    }
    return {name: daft_tpu.from_pydict(cols).collect() for name, cols in t.items()}


def _topn_q3(t, offset=0, cut=(1995, 3, 15)):
    q = (t["customer"].where(col("c_mktsegment") == "BUILDING")
         .join(t["orders"], left_on="c_custkey", right_on="o_custkey")
         .where(col("o_orderdate") < _days(*cut))
         .join(t["lineitem"], left_on="o_orderkey", right_on="l_orderkey")
         .where(col("l_shipdate") > _days(1994, 2, 1))
         .groupby(col("o_orderkey").alias("l_orderkey"), "o_orderdate", "o_shippriority")
         .agg((col("l_extendedprice") * (1 - col("l_discount"))).sum().alias("revenue"))
         .select("l_orderkey", "revenue", "o_orderdate", "o_shippriority")
         .sort(["revenue", "o_shippriority"], desc=[True, False]))
    return (q.offset(offset) if offset else q).limit(10)


def _topn_q10(t, offset=0, cut=(1995, 3, 15)):
    q = (t["orders"].where(col("o_orderdate") < _days(*cut))
         .join(t["lineitem"].where(col("l_returnflag") == "R"),
               left_on="o_orderkey", right_on="l_orderkey")
         .join(t["customer"], left_on="o_custkey", right_on="c_custkey")
         .join(t["nation"], left_on="c_nationkey", right_on="n_nationkey")
         .groupby(col("o_custkey").alias("c_custkey"), "c_name", "c_acctbal", "n_name")
         .agg((col("l_extendedprice") * (1 - col("l_discount"))).sum().alias("revenue"))
         .select("c_custkey", "c_name", "revenue", "c_acctbal", "n_name")
         # whole revenues tie: the order of ties is the host engine's stable sort's
         .sort(["revenue"], desc=[True]))
    return (q.offset(offset) if offset else q).limit(20)


_TOPN_COUNTERS = ("device_topn_runs", "device_join_topn_batches",
                  "device_topn_fetched_rows", "device_topn_table_bytes")


@pytest.mark.parametrize("batches", [1, 3, 7])
@pytest.mark.parametrize("shape,limit", [(_topn_q3, 10), (_topn_q10, 20)], ids=["q3", "q10"])
def test_fused_topn_takes_a_fact_of_any_number_of_batches(shape, limit, batches, segments):
    """The fused TopN over 1, 3 and 7 fact batches gives the host engine's
    rows in the host engine's order (ties included), fetches no more rows
    than its limit and counts every dispatch (a batch each, or several of
    them glued, never all of a fact of several); a repeat builds nothing."""
    from daft_tpu.observability.metrics import registry

    t = _topn_tables(n_l=_MORSEL * batches - 100)
    host = _host_answer(lambda: shape(t))
    revenues = host["revenue"]
    assert len(revenues) == limit and len(set(revenues)) < len(revenues), \
        "the tables are made so that the sort key ties"
    for rep in range(2):
        before = {k: registry().get(k) for k in _TOPN_COUNTERS + ("hbm_cache_misses",)}
        counters.rejections.clear()
        with _morselized("on"):
            dev = shape(t).to_pydict()
        d = {k: registry().get(k) - before[k] for k in before}
        assert d["device_topn_runs"] == 1, counters.rejections
        assert d["device_join_topn_batches"] == _dispatches(batches, segments)
        assert d["device_topn_fetched_rows"] == limit
        assert d["device_topn_table_bytes"] > 0
        assert not any("multi-batch" in k for k in counters.rejections)
        if rep:
            assert d["hbm_cache_misses"] == 0, "a repeat query builds no slot"
        _assert_close(host, dev)


@pytest.mark.parametrize("shape", [_topn_q3, _topn_q10], ids=["q3", "q10"])
def test_fused_topn_offset_and_a_filter_that_empties_batches(shape, segments):
    """An offset skips the first winners; a date cut that keeps only the
    first fifth of the orders leaves the later batches with no kept row."""
    t = _topn_tables(n_l=_MORSEL * 5 - 100)
    for offset, cut in ((3, (1995, 3, 15)), (0, (1994, 5, 1)), (4, (1994, 5, 1))):
        q = lambda: shape(t, offset=offset, cut=cut)
        host = _host_answer(q)
        assert host["revenue"], "the cut keeps some groups"
        counters.reset()
        with _morselized("on"):
            dev = q().to_pydict()
        assert counters.device_topn_runs == 1, counters.rejections
        assert counters.device_join_topn_batches == _dispatches(5, segments)
        _assert_close(host, dev)


def test_fused_topn_scatter_and_dense_forms_agree(segments):
    """q3's ids (a fact sorted by the order key) take the dense form in every
    segment; the same rows shuffled take the scatter form, to the same answer."""
    import daft_tpu.ops.device_join as dj

    t = _topn_tables(n_l=_MORSEL * 7 - 100)    # more orders than a chunk's window is wide
    seen = []
    real = dj.DeviceJoinTopNRun._finalize_run_wide

    def spy(self):
        tables = self._tables
        seen.append((self._batches, None if tables is None else int(tables["dense"])))
        return real(self)

    dj.DeviceJoinTopNRun._finalize_run_wide = spy
    try:
        with _morselized("on"):
            sorted_answer = _topn_q3(t).to_pydict()
        li = t["lineitem"].to_pydict()
        perm = np.random.default_rng(3).permutation(len(li["l_orderkey"]))
        shuffled = dict(t, lineitem=daft_tpu.from_pydict(
            {c: [v[i] for i in perm] for c, v in li.items()}).collect())
        host = _host_answer(lambda: _topn_q3(shuffled))
        with _morselized("on"):
            shuffled_answer = _topn_q3(shuffled).to_pydict()
    finally:
        dj.DeviceJoinTopNRun._finalize_run_wide = real
    # (dispatches, segments that took the dense form)
    assert seen == [(_dispatches(7, segments), 7), (_dispatches(7, segments), 0)], seen
    _assert_close(_host_answer(lambda: _topn_q3(t)), sorted_answer)
    _assert_close(host, shuffled_answer)


# ---- a dispatch that keeps few rows compacts them before its scatters -------------------
#
# The run-wide program's third form (GroupedAggStage._build_run_wide): not
# locally dense, and at most a bucket's 1 / COMPACT_SHARE rows kept (128 of a
# 2,048-row morsel here). The fact below is q3's with its rows in no order,
# full-mantissa prices, and a ship date that only chosen rows pass: per
# batch exactly the asked-for number of rows reach a group.

_K = _MORSEL // 16


def _compaction_fact(kept_per_batch, one_line=False, seed=23):
    """(tables, per-batch kept ids): `_topn_tables` with a lineitem of
    len(kept_per_batch) batches of a morsel's rows whose order keys are
    shuffled and of which exactly kept_per_batch[b] rows of batch b join
    through and pass q3's filters (the lowest and the highest joining id
    among them, so that no such batch is locally dense); `one_line` puts a
    batch's kept rows side by side, from the start of a 128-row line."""
    import datetime

    rng = np.random.default_rng(seed)
    n_b = len(kept_per_batch)
    n_l = _MORSEL * n_b
    t = _topn_tables(n_l=_MORSEL * 7 - 100)      # orders over more ids than a chunk is wide
    o, c = t["orders"].to_pydict(), t["customer"].to_pydict()
    building = {k for k, s in zip(c["c_custkey"], c["c_mktsegment"]) if s == "BUILDING"}
    joins = sorted(k for k, cust, d in zip(o["o_orderkey"], o["o_custkey"], o["o_orderdate"])
                   if cust in building and d < _days(1995, 3, 15))
    assert joins[-1] - joins[0] > _MORSEL, "ids wider than a chunk's window"
    n_o = max(o["o_orderkey"]) + 1
    keys = rng.integers(0, n_o, n_l)
    late = np.zeros(n_l, dtype=bool)
    kept_ids = []
    for b, n in enumerate(kept_per_batch):
        lo = b * _MORSEL
        if one_line:
            rows = lo + 384 + np.arange(n)
        else:
            rows = lo + np.sort(rng.choice(_MORSEL, n, replace=False))
        # ids that repeat (some three times and more), the two ends among them
        ids = rng.choice(joins[1:-1], n) if n else np.empty(0, dtype=np.int64)
        ids[:2] = (joins[0], joins[-1])[:n]
        keys[rows], late[rows] = ids, True
        kept_ids.append(ids)
    day0 = datetime.date(1994, 1, 1)
    li = {"l_orderkey": keys.tolist(),
          "l_extendedprice": rng.uniform(1, 1e5, n_l).astype(np.float32).astype(float).tolist(),
          "l_discount": (rng.integers(0, 11, n_l) / 100.0).tolist(),
          "l_returnflag": ["R"] * n_l,
          "l_shipdate": [day0 + datetime.timedelta(days=400 if x else 0) for x in late]}
    return dict(t, lineitem=daft_tpu.from_pydict(li).collect()), kept_ids


def _spy_run_wide_tables(monkeypatch, seen, partials=None):
    """Every run-wide finalize leaves (batches, its tables on the host) in
    `seen`, and every dispatch whether the sparse segments' partial read all
    zeros after it in `partials`."""
    import jax

    import daft_tpu.ops.device_join as dj

    real = dj.DeviceJoinTopNRun._finalize_run_wide

    def spy(self):
        seen.append((self._batches, jax.device_get(self._tables)))
        return real(self)

    monkeypatch.setattr(dj.DeviceJoinTopNRun, "_finalize_run_wide", spy)
    if partials is None:
        return
    feed = dj.DeviceJoinTopNRun._feed_run_wide

    def fed(self, batch):
        feed(self, batch)
        partials.append(not any(np.any(np.asarray(p)) for p in self._tables["part"]))

    monkeypatch.setattr(dj.DeviceJoinTopNRun, "_feed_run_wide", fed)


def _forms_of(kept_ids):
    """(dense, compact, scatter) dispatches, as the program decides them (a
    morsel is one chunk: dense where its kept ids lie within one of each other)."""
    dense = sum(1 for ids in kept_ids if not len(ids) or ids.max() - ids.min() < _MORSEL)
    compact = sum(1 for ids in kept_ids
                  if len(ids) and ids.max() - ids.min() >= _MORSEL and len(ids) <= _K)
    return dense, compact, len(kept_ids) - dense - compact


def _by_dispatch(kept_ids, segments):
    """A run's segments' kept ids, dispatch by dispatch (_dispatches)."""
    from daft_tpu.execution.batching import resident_dispatch_segments

    step = min(segments, resident_dispatch_segments(len(kept_ids)))
    return [kept_ids[lo:lo + step] for lo in range(0, len(kept_ids), step)]


def _holds_sparse(group):
    """Whether one dispatch's segments hold one that is not dense: the
    dispatch then folds its partial into the sums, once."""
    return _forms_of(group)[0] < len(group)


def _folds_of(kept_ids, segments):
    """The dispatches of a run that fold."""
    return sum(map(_holds_sparse, _by_dispatch(kept_ids, segments)))


def _assert_tables_agree(got, want, kept_ids, segments):
    """The compact form's tables against the scatter form's: first-row
    positions exactly, sums bit for bit where an id has at most two kept rows
    in every dispatch (a dispatch's sparse segments meet in one float32
    partial), within a float32 ulp a dispatch of the sum otherwise."""
    np.testing.assert_array_equal(got["first"], want["first"])
    most = {}
    groups = _by_dispatch(kept_ids, segments)
    for group in groups:
        for i, n in zip(*np.unique(np.concatenate(group), return_counts=True)):
            most[int(i)] = max(most.get(int(i), 0), int(n))
    many = np.array([i for i, n in most.items() if n > 2], dtype=np.int64)
    for plane in ("hi", "lo"):
        for g, w in zip(got[plane], want[plane]):
            g, w = np.asarray(g), np.asarray(w)
            few = np.ones(len(g), dtype=bool)
            few[many] = False
            np.testing.assert_array_equal(g[few].view(np.int32), w[few].view(np.int32))
    for gh, gl, wh, wl in zip(got["hi"], got["lo"], want["hi"], want["lo"]):
        total = lambda h, l: np.asarray(h, np.float64)[many] + np.asarray(l, np.float64)[many]
        room = len(groups) * np.spacing(np.abs(np.asarray(wh)[many]).astype(np.float32))
        assert (np.abs(total(gh, gl) - total(wh, wl)) <= room).all()


_COMPACT_CASES = {
    "under_k": ([40, 90, _K - 1], False),
    "exactly_k": ([_K, _K, _K], False),
    "k_plus_one": ([_K + 1, _K + 1, _K + 1], False),
    "none_kept": ([0, 0, 0], False),
    "one_line": ([_K, 100, _K], True),
    "mixed": ([40, _K + 1, 0, _K, 600], False),
}


def _run_spanned(q):
    """(answer, the run's join.topn_select span)."""
    from daft_tpu.observability.runtime_stats import SpanRecorder, set_spans

    rec = SpanRecorder()
    set_spans(rec)
    try:
        with _morselized("on"):
            answer = q().to_pydict()
    finally:
        set_spans(None)
    (select,) = [s for s in rec.drain() if s["name"] == "join.topn_select"]
    return answer, select


@pytest.mark.parametrize("case", list(_COMPACT_CASES))
def test_fused_topn_compact_and_scatter_forms_agree(case, monkeypatch, segments):
    """A segment whose kept rows fit K scatters K compacted indices, one
    with K + 1 the whole segment, and one with nothing kept is locally
    dense as before: the same tables as the scatter form alone leaves, the
    host engine's answer, and counters that say which form ran. A segment is
    a dispatch of its own or, glued with the other morsels of its table, one
    of a dispatch whose segments each choose their form ("mixed": a dispatch
    of four segments, one dense, two compacted and one scattered, and the
    table's last morsel, scattered, in a dispatch of its own). The sparse
    segments of a dispatch add into one float32 partial, which the dispatch
    folds into the sums once and leaves all zeros: `join_topn_folds` counts
    the dispatches that held one ("none_kept": every segment dense, 0)."""
    import daft_tpu.ops.grouped_stage as gs

    kept, one_line = _COMPACT_CASES[case]
    t, kept_ids = _compaction_fact(kept, one_line)
    dense, compact, scatter = _forms_of(kept_ids)
    assert dense + compact + scatter == len(kept)
    folds = _folds_of(kept_ids, segments)
    assert folds == {"none_kept": 0, "mixed": 2 if segments > 1 else 4}.get(
        case, _dispatches(len(kept), segments))
    host = _host_answer(lambda: _topn_q3(t))
    seen, partials = [], []
    _spy_run_wide_tables(monkeypatch, seen, partials)

    counters.reset()
    answer, select = _run_spanned(lambda: _topn_q3(t))
    assert counters.device_topn_runs == 1, counters.rejections
    assert counters.device_join_topn_batches == _dispatches(len(kept), segments)
    assert counters.join_topn_compact_batches == compact
    assert counters.join_topn_folds == folds
    assert select["args"]["compact_batches"] == compact
    assert select["args"]["dense_batches"] == dense
    assert select["args"]["folds"] == folds
    assert partials == [True] * _dispatches(len(kept), segments)
    _assert_close(host, answer)

    # the scatter form alone: no dispatch is few enough
    monkeypatch.setattr(gs, "COMPACT_SHARE", 1 << 30)
    gs._STAGE_CACHE.clear()
    try:
        counters.reset()
        with _morselized("on"):
            plain = _topn_q3(t).to_pydict()
        assert counters.join_topn_compact_batches == 0
    finally:
        gs._STAGE_CACHE.clear()
    assert plain == answer
    (batches, got), (_b, want) = seen
    assert batches == _dispatches(len(kept), segments)
    assert int(got["dense"]) == int(want["dense"]) == dense
    assert int(got["compact"]) == compact
    assert int(got["folds"]) == folds and int(want["folds"]) == _folds_of(kept_ids, segments)
    _assert_tables_agree(got, want, kept_ids, segments)


def test_a_dispatch_folds_its_sparse_segments_into_the_sums_once(monkeypatch):
    """Eight compacted segments in ONE dispatch whose ids meet again from
    segment to segment: the rows of all eight add into one float32 partial,
    which the dispatch folds into the double-single sums once (one fold,
    eight compacted segments) and leaves all zeros. First rows exactly and
    sums within a float32 ulp of the sum against a float64 reference over the
    kept rows, against the scatter form alone, and the host engine's answer."""
    import daft_tpu.ops.grouped_stage as gs
    from daft_tpu.execution.batching import resident_dispatch_segments

    kept = [_K, 40, _K - 1, 90, _K, 7, 100, _K] * 2
    assert resident_dispatch_segments(len(kept)) == gs.DISPATCH_SEGMENTS == 8
    t, kept_ids = _compaction_fact(kept)
    assert _forms_of(kept_ids) == (0, len(kept), 0)
    shared = set(kept_ids[0].tolist())
    assert all(shared & set(ids.tolist()) for ids in kept_ids[1:8]), "segments share ids"
    host = _host_answer(lambda: _topn_q3(t))
    seen, partials = [], []
    _spy_run_wide_tables(monkeypatch, seen, partials)

    counters.reset()
    answer, select = _run_spanned(lambda: _topn_q3(t))
    assert counters.device_topn_runs == 1, counters.rejections
    assert counters.device_join_topn_batches == 2
    assert (counters.join_topn_compact_batches, counters.join_topn_folds) == (len(kept), 2)
    assert (select["args"]["compact_batches"], select["args"]["folds"]) == (len(kept), 2)
    assert partials == [True, True]
    _assert_close(host, answer)

    monkeypatch.setattr(gs, "COMPACT_SHARE", 1 << 30)       # the scatter form alone
    monkeypatch.setattr(gs, "_STAGE_CACHE", {})
    with _morselized("on"):
        assert _topn_q3(t).to_pydict() == answer
    (_b, got), (_b, want) = seen
    assert (int(want["compact"]), int(want["folds"])) == (0, 2)
    _assert_tables_agree(got, want, kept_ids, 8)

    # a float64 reference over the kept rows: rows, revenue, the first row an id
    li, o = t["lineitem"].to_pydict(), t["orders"].to_pydict()
    late = np.array([d > _days(1994, 2, 1) for d in li["l_shipdate"]])
    assert late.sum() == sum(kept)
    row_of = {k: i for i, k in enumerate(o["o_orderkey"])}
    ids = np.array([row_of.get(k, 0) for k in li["l_orderkey"]])
    revenue = np.asarray(li["l_extendedprice"]) * (1 - np.asarray(li["l_discount"]))
    rows, sums, first = _dense_reference(ids, late, revenue, len(got["first"]))
    np.testing.assert_array_equal(np.asarray(got["first"], np.int64), first)
    total = lambda k: np.asarray(got["hi"][k], np.float64) + np.asarray(got["lo"][k], np.float64)
    np.testing.assert_array_equal(total(0), rows)
    # (a row's revenue is rounded to float32 on the way in, and a dispatch's
    # rows of an id meet in float32: an ulp of the sum a dispatch and a row)
    np.testing.assert_allclose(total(2), sums, rtol=2.0 ** -20, atol=0)


_JAX_EVENTS = None       # where the one registered listener writes, while a test listens


def _jax_events():
    """A list that every duration event of JAX's (a trace, a lowering, a
    backend compile) is appended to from now on, by name."""
    global _JAX_EVENTS
    import jax.monitoring

    if _JAX_EVENTS is None:
        jax.monitoring.register_event_duration_secs_listener(
            lambda name, _secs, **_kw: _JAX_EVENTS.append(name))
    _JAX_EVENTS = []
    return _JAX_EVENTS


@pytest.mark.parametrize("mesh", [1, 4])
def test_a_repeat_run_wide_topn_traces_and_compiles_nothing(mesh):
    """The run's tables are made by one jitted launch (`run_wide_tables`),
    one chip or four: the stage keeps that program beside the accumulate
    programs, so a template's second execution, dense, compacted and
    scattered segments among its dispatches, builds no program at all."""
    t, _kept_ids = _compaction_fact([40, _K + 1, 0, _K, 600, 3, 9, 0, 5, 7])
    run = lambda: _topn_q3(t).to_pydict()
    with execution_config_ctx(device_mode="on", mesh_devices=mesh,
                              morsel_size_rows=_MORSEL, pipeline_mode="force"):
        first = run()
        events = _jax_events()
        counters.reset()
        assert run() == first
    assert counters.device_topn_runs == 1 and counters.join_topn_folds, counters.rejections
    assert events == []


def test_the_select_program_is_handed_the_leaves_it_was_compiled_for(monkeypatch):
    """The accumulate program's tables carry the compacted and the ordered
    segments' counts, the dispatches that folded and the sparse segments'
    partial beside the select's four leaves; the select program (25-28 s to compile
    at SF10, served by the persistent cache while its text stands) is handed
    those four and nothing else."""
    import daft_tpu.ops.device_join as dj

    handed = []
    real = dj.DeviceJoinTopNRun._select_program

    def spy(self, k):
        prog = real(self, k)

        def call(tables, ranks):
            handed.append(sorted(tables))
            return prog(tables, ranks)
        return call

    monkeypatch.setattr(dj.DeviceJoinTopNRun, "_select_program", spy)
    seen = []
    _spy_run_wide_tables(monkeypatch, seen)
    t = _topn_tables(n_l=_MORSEL * 3 - 100)
    with _morselized("on"):
        _topn_q10(t).to_pydict()
    assert handed == [["dense", "first", "hi", "lo"]]
    assert sorted(seen[0][1]) == ["compact", "dense", "first", "folds", "hi", "lo",
                                  "ordered", "part"]


# ---- the dense form: a chunk's id window in two digits -----------------------------------
#
# GroupedAggStage._build_run_wide's dense form never builds a chunk's whole
# one-hot: a local id is two digits (_digit_product), and where a segment's
# kept ids never decrease an id's first row rides the same product. The facts
# below are q3's in which EVERY order joins through and an order's row is its
# key, so a lineitem row's id is its l_orderkey and the ship date alone says
# whether it is kept.

_DENSE_ORDERS = 9000        # more ids than two chunks' windows are wide


def _dense_fact(keys, kept, whole_prices=False, seed=31):
    """(tables, revenue a row) for lineitem rows of order keys `keys` of
    which exactly those of `kept` reach a group. Discounts of 0 or a half, so
    a revenue is exact in float32: prices of all 24 bits, or whole ones that tie."""
    import datetime

    rng = np.random.default_rng(seed)
    n_l, n_o, n_c = len(keys), _DENSE_ORDERS, 5
    day0 = datetime.date(1994, 1, 1)
    price = rng.integers(1, 6, n_l).astype(np.float32) if whole_prices \
        else rng.uniform(1, 1e5, n_l).astype(np.float32)
    discount = rng.integers(0, 2, n_l) * 0.5
    t = {
        "customer": {"c_custkey": list(range(n_c)),
                     "c_name": [f"Customer#{i:05d}" for i in range(n_c)],
                     "c_acctbal": [0.0] * n_c, "c_mktsegment": ["BUILDING"] * n_c,
                     "c_nationkey": [0] * n_c},
        "orders": {"o_orderkey": list(range(n_o)), "o_custkey": [k % n_c for k in range(n_o)],
                   "o_orderdate": [day0 + datetime.timedelta(days=k % 300) for k in range(n_o)],
                   "o_shippriority": [k % 2 for k in range(n_o)]},
        "lineitem": {"l_orderkey": [int(k) for k in keys],
                     "l_extendedprice": price.astype(float).tolist(),
                     "l_discount": discount.tolist(), "l_returnflag": ["R"] * n_l,
                     "l_shipdate": [day0 + datetime.timedelta(days=200 if x else 0)
                                    for x in kept]},
    }
    return ({name: daft_tpu.from_pydict(cols).collect() for name, cols in t.items()},
            price.astype(np.float64) * (1 - discount))


def _dense_rows(case, morsels, chunk, seed=37):
    """(keys, kept) of `morsels` morsels whose every `chunk` rows hold kept
    ids within `chunk` of each other. The rows that are not kept carry keys
    from anywhere."""
    rng = np.random.default_rng(seed)
    n_l = _MORSEL * morsels
    kept = rng.random(n_l) < 0.4
    if case in ("straddle", "straddle_unordered"):
        # a window that starts off a 128-boundary, its first and last id and
        # the ids on both sides of its 128-boundaries among the kept ones
        keys = np.empty(n_l, dtype=np.int64)
        for c in range(n_l // chunk):
            lo = 77 + 300 * c
            edges = [lo, lo + 127, lo + 128, lo + chunk - 129, lo + chunk - 128, lo + chunk - 1]
            ids = np.concatenate([np.repeat(edges, 2), rng.integers(lo, lo + chunk, chunk - 12)])
            keys[c * chunk:(c + 1) * chunk] = np.sort(ids)
            # (each edge id is kept at its second row, not always at its first)
            where = c * chunk + np.searchsorted(np.sort(ids), edges) + 1
            kept[where] = True
    else:
        keys = np.sort(rng.integers(0, min(n_l // 3, _DENSE_ORDERS), n_l))
    if case == "empty_and_one":
        kept[:_MORSEL] = False                      # a morsel with nothing kept,
        kept[_MORSEL:2 * _MORSEL] = False           # one with a single kept row,
        kept[_MORSEL + 700] = True
        kept[2 * _MORSEL:3 * _MORSEL] = False       # one that keeps its first and its last
        kept[[2 * _MORSEL, 3 * _MORSEL - 1]] = True
    if case.endswith("unordered"):
        for c in range(n_l // chunk):
            block = slice(c * chunk, (c + 1) * chunk)
            perm = rng.permutation(chunk)
            keys[block], kept[block] = keys[block][perm], kept[block][perm]
    keys = np.where(kept, keys, rng.integers(0, _DENSE_ORDERS, n_l))
    return keys, kept


def _dense_verdicts(keys, kept, chunk):
    """(dense, ordered) segments, a morsel each, as the program decides them."""
    dense = ordered = 0
    for m in range(len(keys) // _MORSEL):
        chunks = [keys[lo:lo + chunk][kept[lo:lo + chunk]]
                  for lo in range(m * _MORSEL, (m + 1) * _MORSEL, chunk)]
        if all(not len(ids) or ids.max() - ids.min() < chunk for ids in chunks):
            dense += 1
            ordered += all((np.diff(ids) >= 0).all() for ids in chunks)
    return dense, ordered


def _dense_reference(keys, kept, revenue, length):
    """float64 (rows, revenue, first row) an id of tables `length` long."""
    from daft_tpu.ops.grouped_stage import _NO_ROW

    rows, sums = np.zeros(length), np.zeros(length)
    first = np.full(length, _NO_ROW, dtype=np.int64)
    np.add.at(rows, keys[kept], 1.0)
    np.add.at(sums, keys[kept], revenue[kept])
    np.minimum.at(first, keys[kept], np.flatnonzero(kept))
    return rows, sums, first


@pytest.fixture(params=[_MORSEL, 512], ids=["one_chunk", "four_chunks"])
def chunk_rows(request, monkeypatch):
    """A segment of one chunk (a morsel here is shorter than CHUNK_LOCAL) and
    of four, each with an id window of its own."""
    import daft_tpu.ops.grouped_stage as gs

    monkeypatch.setattr(gs, "CHUNK_LOCAL", request.param)
    monkeypatch.setattr(gs, "_STAGE_CACHE", {})      # (the stages built for it go with the test)
    return request.param


_DENSE_CASES = ("ordered", "unordered", "straddle", "straddle_unordered", "empty_and_one")


@pytest.mark.parametrize("case", _DENSE_CASES)
def test_the_dense_forms_tables_against_float64(case, chunk_rows, segments, monkeypatch):
    """The dense form's tables against a float64 numpy reference: sorted ids
    (first rows through the product), ids within a window in no order (the
    masked minimum), ids on both sides of a 128-boundary and at both ends of
    a window that starts off one, a segment with nothing kept, with one kept
    row, and with its first and last row alone. Counts exactly, sums to a
    float32's rounding, first rows exactly; the counts say which segments'
    first rows rode the product."""
    morsels = 5
    keys, kept = _dense_rows(case, morsels, chunk_rows)
    dense, ordered = _dense_verdicts(keys, kept, chunk_rows)
    assert dense == morsels
    assert ordered == {"unordered": 0, "straddle_unordered": 0}.get(case, morsels)
    t, revenue = _dense_fact(keys, kept)
    host = _host_answer(lambda: _topn_q3(t))
    seen, partials = [], []
    _spy_run_wide_tables(monkeypatch, seen, partials)
    counters.reset()
    answer, select = _run_spanned(lambda: _topn_q3(t))
    assert counters.device_topn_runs == 1, counters.rejections
    assert counters.device_join_topn_batches == _dispatches(morsels, segments)
    assert counters.join_topn_ordered_batches == ordered
    assert counters.join_topn_compact_batches == 0
    # (a dispatch of dense segments writes no partial and folds none)
    assert counters.join_topn_folds == 0
    assert (select["args"]["dense_batches"], select["args"]["ordered_batches"],
            select["args"]["compact_batches"], select["args"]["folds"]) == (dense, ordered, 0, 0)
    assert partials == [True] * _dispatches(morsels, segments)
    _assert_close(host, answer)

    (_batches, got), = seen
    assert (int(got["dense"]), int(got["ordered"]), int(got["compact"]), int(got["folds"])) \
        == (dense, ordered, 0, 0)
    rows, sums, first = _dense_reference(keys, kept, revenue, len(got["first"]))
    total = lambda k: np.asarray(got["hi"][k], np.float64) + np.asarray(got["lo"][k], np.float64)
    # the planes: kept rows, counted values, revenue (stage._mm_specs)
    np.testing.assert_array_equal(total(0), rows)
    np.testing.assert_array_equal(total(1), rows)
    np.testing.assert_allclose(total(2), sums, rtol=2.0 ** -22, atol=0)
    np.testing.assert_array_equal(np.asarray(got["first"], np.int64), first)


@pytest.mark.parametrize("case", ["ordered", "unordered"])
def test_first_rows_decide_ties_as_the_host_engines_sort_does(case, chunk_rows, segments):
    """Whole revenues tie: the winners' order among equals is the order their
    groups were first seen in, whichever way the dense form found the first rows."""
    keys, kept = _dense_rows(case, 5, chunk_rows)
    t, _revenue = _dense_fact(keys, kept, whole_prices=True)
    host = _host_answer(lambda: _topn_q3(t))
    assert len(set(zip(host["revenue"], host["o_shippriority"]))) < len(host["revenue"]), \
        "the sort keys tie among the winners"
    counters.reset()
    with _morselized("on"):
        answer = _topn_q3(t).to_pydict()
    assert counters.device_topn_runs == 1, counters.rejections
    assert counters.join_topn_ordered_batches == (5 if case == "ordered" else 0)
    assert answer == host


@pytest.mark.parametrize("shape", [_topn_q3, _topn_q10], ids=["q3", "q10"])
def test_the_ordered_count_follows_what_the_ids_are(shape, segments, monkeypatch):
    """q3's ids (the fact is sorted by the order's key) are dense and in order
    in every segment; q10's customer ids are neither (a chunk is held to 64
    rows here, under the tables' 97 customers), and nothing of the dense form
    runs for them."""
    import daft_tpu.ops.grouped_stage as gs

    morsels = 7
    t = _topn_tables(n_l=_MORSEL * morsels - 100)
    monkeypatch.setattr(gs, "CHUNK_LOCAL", 64)
    monkeypatch.setattr(gs, "_STAGE_CACHE", {})      # (the stages built for it go with the test)
    q = lambda: shape(t, cut=(1996, 6, 1))      # (every order: no segment is left empty)
    counters.reset()
    answer, select = _run_spanned(q)
    assert counters.device_topn_runs == 1, counters.rejections
    dense = morsels if shape is _topn_q3 else 0
    assert (select["args"]["dense_batches"], select["args"]["ordered_batches"]) == (dense, dense)
    assert counters.join_topn_ordered_batches == dense
    _assert_close(_host_answer(q), answer)


def test_digit_product_is_the_one_hot_product():
    """_digit_product against `one_hot(ids).T @ terms` in numpy, bit for bit,
    for windows of one line of lanes and less up to CHUNK_LOCAL; _max_before
    against a running maximum."""
    import jax.numpy as jnp
    from daft_tpu.ops.grouped_stage import CHUNK_LOCAL, _digit_product, _max_before

    rng = np.random.default_rng(2)
    for chunk in (CHUNK_LOCAL, 2048, 512, 128, 64):
        local = rng.integers(0, chunk + 1, chunk).astype(np.int32)      # `chunk`: no id
        local[:4] = (0, 127 % chunk, chunk - 1, chunk)
        terms = jnp.asarray(rng.integers(-64, 64, (chunk, 5)), jnp.bfloat16)
        got = np.asarray(_digit_product(jnp.asarray(local), terms))
        one_hot = (local[:, None] == np.arange(chunk)[None, :]).astype(np.float32)
        np.testing.assert_array_equal(got, (one_hot.T @ np.asarray(terms, np.float32)).T)
    for shape in ((3, 1), (3, 2), (4, 512)):
        x = rng.integers(-1, 40, shape).astype(np.int32)
        want = np.concatenate([np.full((shape[0], 1), -1),
                               np.maximum.accumulate(x, axis=1)[:, :-1]], axis=1)
        np.testing.assert_array_equal(np.asarray(_max_before(jnp.asarray(x))), want)


# ---- a dispatch over a resident fact covers several buckets ------------------------------
#
# A resident table is read as zero-copy ranges of itself, DISPATCH_SEGMENTS
# buckets each, cut by the join driver on its own thread (a fact that comes
# through the pipeline has its contiguous morsels glued back to the same
# ranges by the coalescer); the programs walk a range a segment (a morsel's
# bucket) at a time.


def _coalesce_counters():
    from daft_tpu.observability.metrics import registry

    names = ("coalesce_morsels_in", "dispatch_coalesced", "device_join_batches",
             "join_resident_ranges", "join_resident_dims",
             "hbm_cache_misses", "hbm_h2d_bytes")
    return {k: registry().get(k) for k in names}


def _counted(q):
    before = _coalesce_counters()
    with _morselized("on"):
        out = q().to_pydict()
    after = _coalesce_counters()
    return out, {k: after[k] - before[k] for k in before}


_LONG_MORSELS = 19      # two dispatches of eight and a tail of three: its bucket holds four segments


def _long_query(shape, morsels=_LONG_MORSELS):
    """(tables, query) of a q3-, q10- or q5-shaped join over a resident fact
    of `morsels` morsels less a hundred rows."""
    n_l = _MORSEL * morsels - 100
    if shape == "q5":
        t = _tpch_like(n_l=n_l)
        return t, lambda: _q5_shaped(t)
    t = _topn_tables(n_l=n_l)
    return t, lambda: {"q3": _topn_q3, "q10": _topn_q10}[shape](t)


@pytest.mark.parametrize("shape", ["q3", "q10", "q5"])
def test_a_long_dispatch_gives_the_one_bucket_answer(shape, monkeypatch):
    """A run-wide TopN (q3 dense, q10 sparse) and a grouped join (q5, dictionary
    codes) over a resident fact of 19 morsels: at DISPATCH_SEGMENTS buckets a
    dispatch (8, 8, and a tail of 3 whose bucket's last segment is all
    padding and whose third is part full) the host engine's answer, and the
    answer of a bucket a dispatch (the TopN's to the bit: a segment adds what
    a dispatch of its own added, in the same order); 3 ranges of the table
    for its 19 morsels, handed on by the driver itself (no morsel reaches a
    coalescer); and a repeat query misses no slot and uploads nothing."""
    import daft_tpu.ops.grouped_stage as gs
    from daft_tpu.device.residency import manager

    manager().clear()
    _t, q = _long_query(shape)
    host = _host_answer(q)
    assert gs.DISPATCH_SEGMENTS == 8
    first, cold = _counted(q)
    assert cold["coalesce_morsels_in"] == cold["dispatch_coalesced"] == 0
    assert cold["join_resident_ranges"] == cold["device_join_batches"] == 3
    assert cold["hbm_cache_misses"] > 0
    _assert_close(host, first)
    again, warm = _counted(q)
    assert again == first
    assert warm["hbm_cache_misses"] == 0 and warm["hbm_h2d_bytes"] == 0, warm
    assert warm["join_resident_ranges"] == 3 and warm["coalesce_morsels_in"] == 0
    monkeypatch.setattr(gs, "DISPATCH_SEGMENTS", 1)
    one, c1 = _counted(q)
    assert c1["join_resident_ranges"] == c1["device_join_batches"] == _LONG_MORSELS
    assert c1["coalesce_morsels_in"] == 0
    if shape == "q5":
        _assert_close(one, first)
    else:
        assert one == first
    manager().clear()


def _stage_threads_left(within=5.0):
    """Names of the `daft-stage` threads still alive after `within` seconds."""
    import threading
    import time

    stages = lambda: [th.name for th in threading.enumerate() if th.name.startswith("daft-stage")]
    deadline = time.time() + within
    while stages() and time.time() < deadline:
        time.sleep(0.01)
    return stages()


def _spy_fed_batches(monkeypatch, fed):
    """Append every batch a join run is fed to `fed`."""
    import daft_tpu.ops.device_join as dj

    for cls in (dj.DeviceJoinTopNRun, dj.DeviceJoinGroupedRun, dj.DeviceJoinUngroupedRun):
        def feed_batch(self, batch, _real=cls.feed_batch):
            fed.append(batch)
            return _real(self, batch)
        monkeypatch.setattr(cls, "feed_batch", feed_batch)


def _views_of(table, batches):
    """[(start, rows)] of `batches`, each of which must view those rows of
    `table`'s one batch in every column."""
    (whole,) = table._result[0].batches
    out = []
    for b in batches:
        spans = set()
        for name in b.column_names():
            root, off = b.get_column(name).lineage()
            assert root is whole.get_column(name), name
            spans.add((off, b.num_rows))
        (span,) = spans
        out.append(span)
    return out


@pytest.mark.parametrize("shape", ["q3", "q10", "q5"])
def test_the_driver_cuts_the_ranges_the_coalescer_flushed(shape, monkeypatch):
    """The ranges of a resident fact the join driver hands to feed_batch are,
    start for start and length for length, what a DispatchCoalescer at the
    resident target makes of the table's morsels: views of the table's own
    columns (nothing copied), eight morsels, eight, and the tail."""
    t, q = _long_query(shape)
    fed = []
    _spy_fed_batches(monkeypatch, fed)
    with _morselized("on"):
        q().to_pydict()
    glued = []
    coal = _coalescer(glued, t["lineitem"].count_rows())
    for m in _resident_morsels(t["lineitem"], _MORSEL):
        coal.add(m)
    coal.close()
    want = _views_of(t["lineitem"], glued)
    assert want == [(0, 8 * _MORSEL), (8 * _MORSEL, 8 * _MORSEL),
                    (16 * _MORSEL, 3 * _MORSEL - 100)]
    assert _views_of(t["lineitem"], fed) == want


def _spy_pipeline(monkeypatch):
    """(stage nodes spawned, pool fan-outs started) while the spy stands."""
    from daft_tpu.execution import pipeline as pl

    spawned, fanned = [], []
    real_spawn, real_pmap = pl.spawn_stage, pl.pmap_stream

    def spawn_stage(gen, maxsize=4, node=None):
        spawned.append(type(node).__name__)
        return real_spawn(gen, maxsize=maxsize, node=node)

    def pmap_stream(stream, fn, window=0, strategy=None):
        fanned.append(fn)
        return real_pmap(stream, fn, window=window, strategy=strategy)

    monkeypatch.setattr(pl, "spawn_stage", spawn_stage)
    monkeypatch.setattr(pl, "pmap_stream", pmap_stream)
    return spawned, fanned


@pytest.mark.parametrize("shape", ["q3", "q10", "q5"])
def test_a_select_over_a_resident_table_starts_no_stage_and_no_pool_task(shape, monkeypatch):
    """The fact and the dimensions of a captured join are selects over
    in-memory tables: the driver reads them on its own thread, so no
    `daft-stage` thread starts for a Project and nothing is fanned out over
    the pool (q5's sort above the join is a stage of its own, as before)."""
    t, q = _long_query(shape)
    with _morselized("on"):
        q().to_pydict()     # the cold run may hash and encode on the pool
    spawned, fanned = _spy_pipeline(monkeypatch)
    _answer, c = _counted(q)
    assert "Project" not in spawned and spawned == (["PhysSort"] if shape == "q5" else [])
    assert fanned == []
    assert c["join_resident_ranges"] == 3
    # the dimensions behind a Project are taken whole; a bare scan yields its
    # partitions as it always did
    assert c["join_resident_dims"] == {"q3": 1, "q10": 2, "q5": 2}[shape]


def _q3_over(t, lineitem):
    return _topn_q3({**t, "lineitem": lineitem})


_NO_SELECT = {
    # (the fact, morsels in, dispatches): what the pipeline and the coalescer
    # made of it before the driver read tables itself
    "computed": (lambda t: t["lineitem"].with_column(
        "l_extendedprice", col("l_extendedprice") * 2.0), 19, 19),
    "concat": (lambda t: t["lineitem"].concat(
        _topn_tables(n_l=_MORSEL * 9)["lineitem"]), 28, 5),
}


@pytest.mark.parametrize("case", list(_NO_SELECT))
def test_a_fact_that_is_no_select_over_one_table_takes_the_pipeline(case, monkeypatch):
    """A computed projection (its filter stays above the scan) and two
    concatenated tables are no select over one table: the fact comes through
    the pipeline's stages and the coalescer, morsel for morsel and dispatch
    for dispatch as it did, and the answer is the host engine's."""
    t, _q = _long_query("q3")
    make, morsels_in, dispatches = _NO_SELECT[case]
    q = lambda: _q3_over(t, make(t))
    host = _host_answer(q)
    spawned, fanned = _spy_pipeline(monkeypatch)
    answer, c = _counted(q)
    assert c["join_resident_ranges"] == 0
    assert c["coalesce_morsels_in"] == morsels_in
    assert c["dispatch_coalesced"] == c["device_join_batches"] == dispatches
    assert "Project" in spawned and fanned
    _assert_close(host, answer)


def _physical(df):
    from daft_tpu.config import execution_config as get_config
    from daft_tpu.plan import physical as pp

    return pp.translate(df._builder.optimize()._plan, get_config())


@pytest.mark.parametrize("case", ["select", "alias", "two_selects", "computed", "filter",
                                  "concat", "bare_scan", "parquet"])
def test_resident_select_answers_for_a_select_over_one_table_only(case, tmp_path):
    """executor._resident_select: the table's partitions with the projection
    applied where the plan is Projects of column references (or aliases of
    them) over one InMemoryScan, the columns the table's own; None for
    anything else, which keeps the pipeline."""
    from daft_tpu.execution import executor
    from daft_tpu.plan import physical as pp

    n = _MORSEL * 5
    df = daft_tpu.from_pydict({"k": list(range(n)), "v": [float(i) for i in range(n)],
                               "w": [i % 3 for i in range(n)]}).collect()
    (whole,) = df._result[0].batches
    if case == "select":
        plan, names = _physical(df.select("v", "k")), {"v": "v", "k": "k"}
    elif case == "alias":
        plan, names = _physical(df.select(col("v").alias("x"), "k")), {"x": "v", "k": "k"}
    elif case == "two_selects":
        inner = _physical(df.select("v", "k"))
        plan = pp.Project(inner, [col("k")], _physical(df.select("k")).schema)
        names = {"k": "k"}
    elif case == "computed":
        plan, names = _physical(df.select((col("v") * 2).alias("v2"), "k")), None
    elif case == "filter":
        plan, names = _physical(df.where(col("w") == 1).select("v", "k")), None
    elif case == "concat":
        plan, names = _physical(df.select("v", "k").concat(df.select("v", "k"))), None
    elif case == "bare_scan":
        plan, names = _physical(df), None
    else:
        df.write_parquet(str(tmp_path))
        plan, names = _physical(daft_tpu.read_parquet(str(tmp_path) + "/*.parquet").select("v", "k")), None
    got = executor._resident_select(plan)
    if names is None:
        assert got is None
        return
    assert isinstance(plan, pp.Project)
    (part,) = got
    (batch,) = part.batches
    assert batch.column_names() == list(names) and batch.num_rows == n
    for out, src in names.items():
        if out == src:
            assert batch.get_column(out) is whole.get_column(src), "the table's own column"
        else:
            assert batch.get_column(out).to_arrow() is whole.get_column(src).to_arrow() \
                or batch.get_column(out).to_arrow().equals(whole.get_column(src).to_arrow())


@pytest.mark.parametrize("morsels", [1, 2, 19])
def test_the_decision_reads_what_it_read(morsels, monkeypatch):
    """What `auto` decides from is unchanged by the road the fact takes: the
    decision key, the first morsel's layout and the rows tested against
    device_min_rows are, for a fact of one morsel, of two and of nineteen,
    what the pipeline's first two morsels gave (the road a plan that is no
    select still takes). The horizon is each road's own: a fact read as
    ranges is priced at the range it is dispatched in (eight morsels of
    nineteen), a fact through the pipeline at what its leading morsels
    promise of the coalescer, as before."""
    import jax
    from daft_tpu.execution import executor

    t = _topn_tables(n_l=_MORSEL * morsels)
    q = lambda: _topn_q3(t)
    host = _host_answer(q)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    seen = []
    real_key, real_layout = executor._decision_key, executor._batch_layout

    def decision_key(node, rows, cfg, topn, layout):
        key = real_key(node, rows, cfg, topn, layout)
        seen.append(("key", key))
        return key

    def batch_layout(part):
        seen.append(("layout", real_layout(part), part.num_rows))
        return real_layout(part)

    def join_device_wins(node, ctx, batch, rows, grouped, stage, **kw):
        seen.append(("wins", batch.num_rows, rows, kw["coalesce"], kw["mesh_ndev"],
                     kw["mesh_coalesce"], kw["topn"]))
        return False, None      # the host's answer, whatever was priced

    monkeypatch.setattr(executor, "_decision_key", decision_key)
    monkeypatch.setattr(executor, "_batch_layout", batch_layout)
    monkeypatch.setattr(executor, "_join_device_wins", join_device_wins)

    def decided():
        seen.clear()
        executor._DECISION_CACHE.clear()
        with execution_config_ctx(device_mode="auto", device_min_rows=_MORSEL // 2,
                                  morsel_size_rows=_MORSEL, pipeline_mode="force"):
            out = q().to_pydict()
        executor._DECISION_CACHE.clear()
        return out, list(seen)

    direct, mine = decided()
    monkeypatch.setattr(executor, "_resident_select", lambda plan: None)
    piped, theirs = decided()
    assert direct == piped == host
    # (the fused TopN's decision, then that of the join-aggregate its host
    # plan holds)
    assert [s[0] for s in mine] == ["layout", "key", "wins"] * 2

    def less_the_horizon(s):
        return s[:3] + s[4:5] + s[6:] if s[0] == "wins" else s

    assert [less_the_horizon(s) for s in mine] == [less_the_horizon(s) for s in theirs]
    ranged = [s[3] for s in mine if s[0] == "wins"]
    streamed = [s[3] for s in theirs if s[0] == "wins"]
    if morsels <= 2:        # a table of two morsels is not cut: one road, one price
        assert ranged == streamed
    else:
        # (the join-aggregate of the host plan factorizes its group ids on
        # the host a morsel at a time: its dispatch is one morsel, and so is
        # its price)
        assert ranged == [8.0, 1.0] and all(h <= 2.0 for h in streamed)
    first_rows = _MORSEL * morsels if morsels <= 2 else _MORSEL
    assert {s[2] for s in mine if s[0] != "key"} == {first_rows}, \
        "a morsel is what the decision sees"


def test_a_resident_fact_under_device_min_rows_goes_to_the_host(monkeypatch):
    """device_min_rows is tested against the first MORSEL of a table read
    directly, as it was against the pipeline's: a fact whose first morsel is
    shorter runs the host plan and dispatches nothing."""
    import jax

    t = _topn_tables(n_l=_MORSEL * 19)
    host = _host_answer(lambda: _topn_q3(t))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    counters.reset()
    with execution_config_ctx(device_mode="auto", device_min_rows=_MORSEL + 1,
                              morsel_size_rows=_MORSEL, pipeline_mode="force"):
        out = _topn_q3(t).to_pydict()
    assert out == host
    assert counters.device_join_batches == 0 and counters.join_resident_ranges == 0
    assert any("below device_min_rows" in k for k in counters.rejections), counters.rejections


@pytest.mark.parametrize("shape", ["q3", "q5"])
def test_a_fallback_after_some_ranges_answers_from_the_host_plan(shape, monkeypatch):
    """A DeviceFallback raised at the third range of a resident fact: the
    host plan's answer, the rejection on record, and nothing left behind
    (no stage was started for the fact, so there is none to close)."""
    import daft_tpu.ops.device_join as dj
    from daft_tpu.ops.grouped_stage import DeviceFallback

    t, q = _long_query(shape)
    host = _host_answer(q)
    cls = dj.DeviceJoinTopNRun if shape == "q3" else dj.DeviceJoinGroupedRun
    real, fed = cls.feed_batch, []

    def feed_batch(self, batch):
        if len(fed) == 2:
            raise DeviceFallback("the third range will not go")
        fed.append(batch.num_rows)
        return real(self, batch)

    monkeypatch.setattr(cls, "feed_batch", feed_batch)
    counters.reset()
    with _morselized("on"):
        answer = q().to_pydict()
    assert fed == [8 * _MORSEL, 8 * _MORSEL]
    if shape == "q5":   # (q3's host plan holds a join-aggregate that reads the table again)
        assert counters.join_resident_ranges == 2
    assert any("device fallback" in k for k in counters.rejections), counters.rejections
    _assert_close(host, answer)
    assert not _stage_threads_left()


def _resident_morsels(table, rows):
    """The morsels a pipeline cuts of a collected table: zero-copy views."""
    (batch,) = table._result[0].batches
    return [batch.slice(at, min(at + rows, batch.num_rows))
            for at in range(0, batch.num_rows, rows)]


def _coalescer(fed, rows, segments=8, shards=1, morsel=_MORSEL):
    """A join run's coalescer over a resident fact of `rows` rows, which
    makes a dispatch `segments` buckets a shard long."""
    from daft_tpu.config import execution_config
    from daft_tpu.execution.batching import coalesce_target_rows
    from daft_tpu.execution.executor import _make_coalescer

    with execution_config_ctx(morsel_size_rows=morsel):
        cfg = execution_config()
        assert coalesce_target_rows(cfg, shards, resident_rows=rows) \
            == (segments * shards - 1) * morsel + morsel // 2
        return _make_coalescer(fed.append, cfg, shards, resident_rows=rows)


@pytest.mark.parametrize("fact_buckets,segments", [
    (1, 1), (2, 1), (3, 2), (4, 2), (5, 4), (8, 4), (9, 8), (16, 8), (46, 8), (458, 8)])
def test_a_dispatch_is_never_the_whole_fact(fact_buckets, segments):
    """A join dispatch over a resident fact covers DISPATCH_SEGMENTS buckets a
    device, or the largest power of two under the fact's own length where it
    is shorter: a fact of several morsels is a run of several dispatches."""
    from daft_tpu.config import execution_config
    from daft_tpu.execution.batching import coalesce_target_rows, resident_dispatch_segments

    assert resident_dispatch_segments(fact_buckets) == segments
    assert segments == 1 or -(-fact_buckets // segments) > 1
    for shards in (1, 4):
        with execution_config_ctx(morsel_size_rows=_MORSEL):
            rows = fact_buckets * shards * _MORSEL - 100
            assert coalesce_target_rows(execution_config(), shards, resident_rows=rows) \
                == (segments * shards - 1) * _MORSEL + _MORSEL // 2


def test_the_coalescer_glues_a_resident_run_at_no_copy():
    """Contiguous views of one resident table are held to the join's length
    and handed on as ONE view of the table's rows (every column's lineage is a
    range of the table's own column: nothing was copied); the table's last
    morsels go as they are when the table ends."""
    t = daft_tpu.from_pydict({"k": list(range(_MORSEL * 19 - 100)),
                              "v": [float(i) for i in range(_MORSEL * 19 - 100)],
                              "s": [f"s{i % 7}" for i in range(_MORSEL * 19 - 100)]}).collect()
    (whole,) = t._result[0].batches
    fed = []
    coal = _coalescer(fed, whole.num_rows)
    for i, m in enumerate(_resident_morsels(t, _MORSEL)):
        coal.add(m)
        assert len(fed) == (i + 1) // 8 if i < 18 else 3, "a flush every eighth morsel, and at the table's end"
    coal.close()
    assert [b.num_rows for b in fed] == [8 * _MORSEL, 8 * _MORSEL, 3 * _MORSEL - 100]
    at = 0
    for b in fed:
        for name in b.column_names():
            root, off = b.get_column(name).lineage()
            assert root is whole.get_column(name) and off == at
        at += b.num_rows


@pytest.mark.parametrize("case", ["streamed", "gap", "another_table", "a_computed_column"])
def test_morsels_that_are_no_resident_run_flush_at_the_threshold_they_had(case):
    """What is not a run of contiguous views of one resident table keeps
    batch_fill_target of a bucket: morsels that are tables of their own (a
    stream's), views with a gap between them, views of another table, and
    views beside a computed column flush one by one as they did."""
    n = _MORSEL * 6
    make = lambda: daft_tpu.from_pydict({"k": list(range(n)), "v": [float(i) for i in range(n)]}).collect()
    t = make()
    morsels = _resident_morsels(t, _MORSEL)
    if case == "streamed":
        morsels = [daft_tpu.from_pydict(m.to_pydict()).collect()._result[0].batches[0] for m in morsels]
        want = [_MORSEL] * 6
    elif case == "gap":
        morsels = morsels[:2] + morsels[3:]
        # (the run that ends at the gap goes as it is, the next one starts anew)
        want = [2 * _MORSEL, 3 * _MORSEL]
    elif case == "another_table":
        morsels = morsels[:2] + _resident_morsels(make(), _MORSEL)[2:4] + morsels[4:]
        want = [2 * _MORSEL, 2 * _MORSEL, 2 * _MORSEL]
    else:
        from daft_tpu.core.recordbatch import RecordBatch
        from daft_tpu.core.series import Series
        from daft_tpu.schema import Schema

        def with_computed(m):
            cols = [m.get_column("k"), m.get_column("v"),
                    Series.from_numpy(np.asarray(m.get_column("v").to_numpy()) * 2, "v2")]
            return RecordBatch(Schema([c.field() for c in cols]), cols, m.num_rows)
        morsels = [with_computed(m) for m in morsels]
        want = [_MORSEL] * 6
    fed = []
    coal = _coalescer(fed, n, segments=4)     # (a table of six morsels: four a dispatch)
    for m in morsels:
        coal.add(m)
    coal.close()
    assert [b.num_rows for b in fed] == want


@pytest.mark.parametrize("shards", [1, 4])
def test_the_priced_horizon_is_held_where_it_was_and_never_over_what_runs(shards):
    """Over a resident fact that comes through the PIPELINE the coalescer
    delivers DISPATCH_SEGMENTS morsels a shard a dispatch; _coalesce_horizon,
    which such a fact's tiers are priced with, stays at the plain threshold's
    factor (batch_fill_target of the last shard's bucket), so its verdict is
    what it was and the price never promises more than runs. Both read
    coalesce_target_rows. (A fact read as ranges of its table is priced at
    the range itself: test_a_join_is_priced_at_the_dispatch_the_run_delivers.)"""
    from daft_tpu.config import execution_config
    from daft_tpu.core.micropartition import MicroPartition
    from daft_tpu.execution.batching import coalesce_target_rows
    from daft_tpu.execution.executor import _coalesce_horizon

    n_morsels = 16 * shards
    n = _MORSEL * n_morsels
    t = daft_tpu.from_pydict({"k": list(range(n)), "v": [float(i) for i in range(n)]}).collect()
    morsels = _resident_morsels(t, _MORSEL)
    parts = [MicroPartition(m.schema, [m]) for m in morsels[:2]]
    fed = []
    coal = _coalescer(fed, n, shards=shards)
    for m in morsels:
        coal.add(m)
    coal.close()
    delivered = n_morsels / len(fed)
    assert delivered == 8 * shards
    with execution_config_ctx(morsel_size_rows=_MORSEL):
        priced = _coalesce_horizon(parts, shards=shards, stream_rows=n)
        assert priced == max(coalesce_target_rows(execution_config(), shards) / _MORSEL, 1.0)
        assert priced == max(shards - 0.5, 1) <= delivered


def test_topn_group_by_outside_a_dimension_keeps_the_one_batch_form():
    """A group-by that holds a fact column has no run-wide id space: one
    batch rides the fused program as before, a second one sends the query to
    the host plan, and the rejection says why."""
    t = _topn_tables(n_l=_MORSEL * 3 - 100)

    def q():
        return (t["orders"].join(t["lineitem"], left_on="o_orderkey", right_on="l_orderkey")
                .groupby("l_orderkey", "o_shippriority")
                .agg(col("l_extendedprice").sum().alias("s"))
                .sort(["s", "l_orderkey"], desc=[True, False]).limit(5))

    host = _host_answer(q)
    counters.reset()
    with _morselized("on"):
        dev = q().to_pydict()
    assert counters.device_topn_runs == 0
    reasons = [k for k in counters.rejections if "multi-batch fact" in k]
    assert reasons and "no run-wide group ids" in reasons[0], counters.rejections
    assert any("a group-by column is the fact's" in why
               for _site, why in counters.rejection_log), counters.rejection_log
    _assert_close(host, dev)
    # one batch: the fused program, on the host-factorized ids
    counters.reset()
    with execution_config_ctx(device_mode="on"):
        dev = q().to_pydict()
    assert counters.device_topn_runs == 1 and counters.device_join_topn_batches == 1
    assert counters.device_topn_fetched_rows == 5
    assert counters.device_topn_table_bytes == 0, "no run-wide table was built"
    _assert_close(host, dev)


def test_run_wide_groups_names_the_dimension_or_says_why():
    from daft_tpu.ops.device_join import run_wide_groups, try_capture_join_topn

    t = _topn_tables(n_l=500)

    def spec_of(df):
        return try_capture_join_topn(df._builder.optimize()._plan)[0]

    g3, why = run_wide_groups(spec_of(_topn_q3(t)))
    assert why == "" and g3.dim.key_col == "o_orderkey"
    g10, why = run_wide_groups(spec_of(_topn_q10(t)))
    # q10 groups by o_custkey, which the join made equal to customer's key;
    # n_name is a dimension's chained from customer
    assert why == "" and g10.dim.key_col == "c_custkey"
    assert [c for _d, c in g10.cols] == ["c_custkey", "c_name", "c_acctbal", "n_name"]
    no_key = (t["orders"].join(t["lineitem"], left_on="o_orderkey", right_on="l_orderkey")
              .groupby("o_orderdate").agg(col("l_discount").sum().alias("s"))
              .sort("s").limit(3))
    g, why = run_wide_groups(spec_of(no_key))
    assert g is None and "no dimension's key" in why


def test_a_topn_verdict_is_keyed_on_the_whole_fact_not_its_first_partition():
    """Two q3-shaped plans whose first fact partition looks the same (one
    morsel of the same rows) but whose facts are 1 and 3 batches long get
    different decision keys: a cached verdict of one never serves the other.
    So does a join that is no TopN, since a resident fact's length sets the
    dispatch its tiers are priced at (executor._resident_horizon)."""
    from daft_tpu.config import execution_config as get_config
    from daft_tpu.execution import executor
    from daft_tpu.plan import physical as pp

    def topn_node(n_l):
        t = _topn_tables(n_l=n_l)
        with _morselized("on"):
            plan = pp.translate(_topn_q3(t)._builder.optimize()._plan, get_config())
        found, todo = [], [plan]
        while todo:
            n = todo.pop()
            if isinstance(n, pp.DeviceJoinTopN):
                found.append(n)
            todo.extend(n.children())
        assert len(found) == 1
        return found[0]

    one, three = topn_node(_MORSEL), topn_node(_MORSEL * 3)
    assert executor._resident_rows(one.fact) == _MORSEL
    assert executor._resident_rows(three.fact) == _MORSEL * 3
    cfg, layout = get_config(), (1, _MORSEL)
    # the dims are other objects in the two plans: compare the part of the
    # key that comes before their identity tokens
    key = lambda node, topn: executor._decision_key(node, _MORSEL, cfg, topn, layout)[:-1]
    assert key(one, True) != key(three, True)
    assert key(one, True) == key(topn_node(_MORSEL), True)
    assert key(one, False) != key(three, False)
    assert key(one, False) == key(topn_node(_MORSEL), False)


def test_run_wide_sums_are_double_singles():
    """What the run-wide tables hold: a sum as two float32 planes that a
    two-sum keeps exact to about 48 bits over hundreds of additions (a
    float32 alone drifts by 1e-6), infinities staying what they are; and a
    float32 as three bfloat16 terms that add up to all 24 of its bits."""
    import jax.numpy as jnp
    from daft_tpu.ops.grouped_stage import _bfloat16_terms, _two_sum_add

    rng = np.random.default_rng(5)
    parts = (rng.random((458, 64)) * 1e5).astype(np.float32)
    hi, lo = jnp.zeros(64, jnp.float32), jnp.zeros(64, jnp.float32)
    alone = jnp.zeros(64, jnp.float32)
    for x in parts:
        hi, lo = _two_sum_add(hi, lo, jnp.asarray(x))
        alone = alone + jnp.asarray(x)
    want = parts.astype(np.float64).sum(axis=0)
    got = np.asarray(hi, np.float64) + np.asarray(lo, np.float64)
    assert np.max(np.abs(got - want) / want) < 1e-12
    assert np.max(np.abs(np.asarray(alone, np.float64) - want) / want) > 1e-8
    h, l = _two_sum_add(jnp.asarray([1.0, np.inf], jnp.float32), jnp.zeros(2, jnp.float32),
                        jnp.asarray([np.inf, 1.0], jnp.float32))
    assert np.isinf(np.asarray(h)).all() and not np.asarray(l).any()

    vals = jnp.asarray((rng.random((1000, 2)) * 1e4).astype(np.float32))
    terms = np.asarray(_bfloat16_terms(vals).astype(jnp.float32), np.float64)
    back = terms[:, :2] + terms[:, 2:4] + terms[:, 4:]
    assert np.max(np.abs(back - np.asarray(vals, np.float64)) / np.asarray(vals)) < 2.0 ** -22
    assert np.max(np.abs(terms[:, :2] - np.asarray(vals, np.float64)) / np.asarray(vals)) > 1e-4


def test_select_top_is_a_stable_multi_key_sort():
    import jax
    import jax.numpy as jnp
    from daft_tpu.ops.device_join import select_top

    rng = np.random.default_rng(0)
    n = 1 << 15
    a = jnp.asarray(rng.integers(0, 4, n).astype(np.int32))
    b = jnp.asarray(-rng.integers(0, 50, n).astype(np.float64))
    c = jnp.asarray(rng.permutation(n).astype(np.int32))
    gid = jnp.arange(n, dtype=jnp.int32)
    for k in (1, 20, 1500):
        want = jax.lax.sort((a, b, c, gid), num_keys=3)[-1][:k]
        got = select_top((a, b, c, gid), 3, k)[-1]
        assert np.array_equal(np.asarray(want), np.asarray(got)), k


# ---- a dimension's key lookup is made once, not once a fact batch ---------------------


def _key_series(keys):
    from daft_tpu.core.series import Series

    return Series.from_pylist(list(keys), "k")


@pytest.mark.parametrize("form", ["dense", "hash", "sorted"])
def test_unique_key_index_probes_a_lookup_built_once(form, monkeypatch):
    """idx[i] is the dimension's row of probe key i or -1 (a miss, a null
    probe, a null key), in the three forms a key column can take; what is as
    long as the dimension (the uniqueness check, the table) is built once a
    key column however many batches probe it."""
    import daft_tpu.native as native
    import daft_tpu.ops.device_join as dj
    from daft_tpu import DataType

    rng = np.random.default_rng(5)
    if form == "dense":
        keys = [int(k) for k in rng.permutation(3000)[:2500]]
    else:
        keys = [int(k) for k in rng.choice(10**12, 2500, replace=False)]
        if form == "sorted":
            monkeypatch.setattr(native, "native_i64_map_build", lambda vv: None)
    keys[17] = None                         # a null key joins nothing
    s = _key_series(keys)
    row_of = {k: i for i, k in enumerate(keys) if k is not None}
    builds = []
    real = dj._build_key_lookup
    monkeypatch.setattr(dj, "_build_key_lookup",
                        lambda *a: builds.append(1) or real(*a))
    for batch in range(5):
        present = rng.choice([k for k in keys if k is not None], 300)
        probe = np.concatenate([present, rng.integers(-50, 10**12, 100)]).astype(np.int64)
        valid = rng.random(len(probe)) > 0.1
        idx = dj.unique_key_index(s, probe, valid, DataType.int64())
        want = [row_of.get(int(p), -1) if v else -1 for p, v in zip(probe, valid)]
        assert idx.dtype == np.int32 and idx.tolist() == want, (form, batch)
    assert len(builds) == 1
    assert dj.unique_key_lookup(s, DataType.int64()).form == form


def test_unique_key_lookup_refuses_keys_that_repeat():
    import daft_tpu.ops.device_join as dj
    from daft_tpu import DataType
    from daft_tpu.ops.grouped_stage import DeviceFallback

    with pytest.raises(DeviceFallback, match="not unique"):
        dj.unique_key_index(_key_series([1, 2, 2]), np.array([2]), np.array([True]),
                            DataType.int64())
    empty = dj.unique_key_index(_key_series([]).cast(DataType.int64()), np.array([2, 3]),
                                np.array([True, True]), DataType.int64())
    assert empty.tolist() == [-1, -1]


# ---- a dispatch gathers from the window of the dimension its batch points into --------

_WIN_DIM = 8000                 # its pack pads to 8,192 rows: four windows of a morsel
_WIN_FACT = 4 * _MORSEL         # four dispatches, batch b = rows [b * _MORSEL, (b + 1) * _MORSEL)
# the group-sorted layout wants a batch of over 4,096 groups: morsels of 8,192 rows
_PERM_MORSEL, _PERM_DIM, _PERM_FACT = 8192, 20_000, 4 * 8192


def _win_keys_sorted(rng):
    """Every key of the dim one to three times, in key order: a batch's
    matched rows lie within some 1,024 of each other."""
    return np.repeat(np.arange(_WIN_DIM), rng.integers(1, 4, _WIN_DIM))[:_WIN_FACT].tolist()


def _win_keys_shuffled(rng):
    return rng.permutation(_win_keys_sorted(rng)).tolist()


def _win_keys_edge_misses(rng):
    keys = _win_keys_sorted(rng)
    for b in range(0, _WIN_FACT, _MORSEL):
        keys[b] = keys[b + 1] = keys[b + _MORSEL - 1] = None          # a null probe
        keys[b + 2] = keys[b + _MORSEL - 2] = _WIN_DIM + 1000          # a key the dim lacks
    return keys


def _win_keys_all_miss_batch(rng):
    keys = _win_keys_sorted(rng)
    keys[_MORSEL:2 * _MORSEL] = [_WIN_DIM + 7] * _MORSEL
    return keys


def _win_keys_at_the_end(rng):
    # the last batches' least row lies past pack rows - window: the slice is clamped
    keys = np.repeat(np.arange(_WIN_DIM - _WIN_FACT // 2, _WIN_DIM), 2).tolist()
    assert min(keys[-_MORSEL:]) > 8192 - _MORSEL
    return keys


def _win_keys_span_exactly_w(rng):
    keys = _win_keys_sorted(rng)
    first = [100] + sorted(rng.integers(101, 100 + _MORSEL, _MORSEL - 2).tolist()) + [100 + _MORSEL]
    second = [3000] + sorted(rng.integers(3000, 2999 + _MORSEL, _MORSEL - 2).tolist()) \
        + [2999 + _MORSEL]
    keys[:_MORSEL], keys[_MORSEL:2 * _MORSEL] = first, second       # spans W and W - 1
    return keys


def _win_keys_wide_batches_sorted(rng):
    # four batches of 8,192 rows, each over some 4,100 consecutive rows of a 20,000-row dim
    return np.repeat(np.arange(3000, _PERM_DIM),
                     rng.integers(1, 4, _PERM_DIM - 3000))[:_PERM_FACT].tolist()


def _win_keys_wide_batches_shuffled(rng):
    return rng.integers(0, _PERM_DIM, _PERM_FACT).tolist()


def _win_tables(keys, dim_rows):
    rng = np.random.default_rng(39)
    n = len(keys)
    fact = daft_tpu.from_pydict({
        "f_k": keys,
        "f_s": rng.integers(0, 300, n).tolist(),
        "f_q": rng.integers(0, 4, n).tolist(),
        "f_v": rng.uniform(0, 100, n).round(3).tolist(),
    }).collect()
    dim = daft_tpu.from_pydict({
        "d_k": list(range(dim_rows)),
        "d_grp": [f"g{i % 7}" for i in range(dim_rows)],
        "d_w": [float(i % 13) if i % 29 else None for i in range(dim_rows)],
        "d_big": [300_266_000_000 + i * 7_919 for i in range(dim_rows)],       # int64 past 2^24
        "d_mid": np.asarray([16_777_216 + i * 3 for i in range(dim_rows)], dtype=np.int32),
    }).collect()
    small = daft_tpu.from_pydict({        # no longer than a window: always the plain gather
        "s_k": list(range(300)), "s_w": [float(i % 5) for i in range(300)]}).collect()
    return fact, dim, small


def _win_q_codes(fact, dim, small):
    return (fact.join(dim, left_on="f_k", right_on="d_k")
            .join(small, left_on="f_s", right_on="s_k")
            .where(col("d_w") < 11.0)
            .groupby("d_grp")
            .agg(col("f_v").sum().alias("sv"), (col("f_v") * col("d_w")).sum().alias("svw"),
                 col("s_w").sum().alias("sw"), col("f_v").count().alias("c"))
            .sort("d_grp"))


def _win_q_wide(fact, dim, small):
    return (fact.join(dim, left_on="f_k", right_on="d_k")
            .groupby("d_grp")
            .agg(col("d_big").sum().alias("s64"), col("d_big").min().alias("mn64"),
                 col("d_big").max().alias("mx64"), col("d_mid").sum().alias("s32"),
                 col("d_mid").max().alias("mx32"))
            .sort("d_grp"))


def _win_q_one_row(fact, dim, small):
    # the dim gives a filter and no column: what is gathered from is the verdict's one row alone
    return (fact.join(dim, left_on="f_k", right_on="d_k")
            .where(col("d_w") < 11.0)
            .groupby("f_q")
            .agg(col("f_v").sum().alias("sv"), col("f_v").count().alias("c"))
            .sort("f_q"))


def _win_q_permuted(fact, dim, small):
    # some 7,000 true groups a batch: past the one-hot ceiling, so its rows go group-sorted
    return (fact.join(dim, left_on="f_k", right_on="d_k")
            .where(col("d_w") < 11.0)
            .groupby("f_k", "f_q")
            .agg(col("f_v").sum().alias("sv"), col("d_w").sum().alias("sw"))
            .sort(["f_k", "f_q"]))


@pytest.mark.parametrize("keys,shape,engaged", [
    (_win_keys_sorted, _win_q_codes, [True] * 4),
    (_win_keys_shuffled, _win_q_codes, [False] * 4),
    (_win_keys_edge_misses, _win_q_codes, [True] * 4),
    (_win_keys_all_miss_batch, _win_q_codes, [True] * 4),
    (_win_keys_at_the_end, _win_q_codes, [True] * 4),
    (_win_keys_span_exactly_w, _win_q_codes, [False, True, True, True]),
    (_win_keys_sorted, _win_q_wide, [True] * 4),
    (_win_keys_edge_misses, _win_q_one_row, [True] * 4),
    (_win_keys_shuffled, _win_q_one_row, [False] * 4),
    (_win_keys_wide_batches_sorted, _win_q_permuted, [True] * 4),
    (_win_keys_wide_batches_shuffled, _win_q_permuted, [False] * 4),
], ids=["sorted", "shuffled", "misses_at_both_edges", "all_miss_batch", "clamp_at_the_end",
        "span_exactly_w", "wide_digit_rows", "one_row_pack", "one_row_pack_shuffled",
        "perm_folded", "perm_folded_shuffled"])
def test_windowed_gather_is_the_plain_gather_bit_for_bit(monkeypatch, keys, shape, engaged,
                                                        segments):
    """A batch whose matched rows of a dimension lie within one batch length
    of each other gathers from a window of that dimension's pack, any other
    batch from the whole of it; the verdict comes from the batch's own index.
    Two morsels glued into one dispatch (the fact is four: a dispatch is
    never all of it) are gathered a segment at a time, each from its own
    window, where BOTH segments' rows lie that close (the host-permuted
    layout keeps a bucket a dispatch: its ids are a batch's).
    Every dispatch that engages a window is run again here through the plain
    program of the same layout: the gathered planes, `__join_ok__` and the
    combined codes are the same bits, misses included. `join_window_gathers`
    counts the windowed gathers, and a repeat query traces nothing."""
    import dataclasses

    import jax
    from daft_tpu.device.residency import manager
    from daft_tpu.ops import device_join as dj

    manager().clear()
    permuted = shape is _win_q_permuted
    fact, dim, small = _win_tables(keys(np.random.default_rng(7)),
                                   _PERM_DIM if permuted else _WIN_DIM)
    morsel = _PERM_MORSEL if permuted else _MORSEL
    glued = segments > 1 and not permuted
    if glued:
        engaged = [all(engaged[:2]), all(engaged[2:])]
    config = dict(morsel_size_rows=morsel, pipeline_mode="force")
    real = dj._provision_program
    windows, perms = [], []

    def both(layout):
        def prog(mats, idxs, fact_codes):
            windows.append(layout.windows)
            # (a pack gathered whole by an unordered batch comes as lines: its rows ride the layout)
            pack_rows = layout.lines[0] if layout.lines and layout.lines[0] else mats[0].shape[0]
            assert (pack_rows == 1) == (shape is _win_q_one_row)
            got = real(layout)(mats, idxs, fact_codes)
            if any(layout.windows):
                plain = real(dataclasses.replace(
                    layout, windows=(False,) * len(layout.windows)))(mats, idxs, fact_codes)
                assert jax.tree_util.tree_structure(got) == jax.tree_util.tree_structure(plain)
                for g, p in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(plain)):
                    assert g.dtype == p.dtype and np.asarray(g).tobytes() == np.asarray(p).tobytes()
            return got
        return prog

    real_provision = dj._JoinContext.provision

    def spy(self, batch, bucket, needed, codes=None, perm=None):
        perms.append(perm is not None)
        assert bucket == (2 * morsel if glued else morsel)
        return real_provision(self, batch, bucket, needed, codes=codes, perm=perm)

    monkeypatch.setattr(dj, "_provision_program", both)
    monkeypatch.setattr(dj._JoinContext, "provision", spy)
    q = lambda: shape(fact, dim, small)
    with execution_config_ctx(device_mode="off", **config):
        host = q().to_pydict()
    for rep in range(2):
        del windows[:], perms[:]
        counters.reset()
        with execution_config_ctx(device_mode="on", **config):
            dev = q().to_pydict()
        assert counters.device_join_batches == len(engaged), counters.rejections
        # the big dim is the first adjacent one; `small` (300 rows) never has a window
        assert [w[0] for w in windows] == engaged, windows
        assert all(not any(w[1:]) for w in windows)
        assert set(perms) == {permuted}
        assert counters.join_window_gathers == sum(engaged)
        assert counters.join_provision_calls == len(engaged)
        if rep:
            assert counters.join_provision_traces == 0, "a repeat query traces no program"
        if shape is _win_q_wide:
            assert dev == host          # integers: no tolerance
        else:
            _assert_close(host, dev)
    manager().clear()


@pytest.mark.parametrize("idx,span", [
    ([5, 9, -1, 7], 4), ([-1, -1], -1), ([], -1), ([3], 0), ([-1, 0, 2047, -1], 2047),
], ids=["some", "all_miss", "empty", "one", "edges"])
def test_index_span_is_the_matched_rows_reach(idx, span):
    from daft_tpu.ops.device_join import _index_span

    assert _index_span(np.asarray(idx, dtype=np.int32)) == span


@pytest.mark.parametrize("rows", [1, 3], ids=["one_row_lanes", "rows"])
def test_windowed_gather_keeps_every_bit(rows):
    """NaN payloads, -0.0, infinities and denormals come through the window
    as the plain gather hands them on: the one-row form picks a lane by its
    bits and never multiplies or adds a float."""
    import jax.numpy as jnp
    from daft_tpu.ops.device_join import _gather_rows

    rng = np.random.default_rng(3)
    n, w = 8192, 1024
    bits = rng.integers(0, 2**32, (rows, n), dtype=np.uint64).astype(np.uint32)   # every kind of float32
    bits[:, ::5] = np.float32(-0.0).view(np.uint32)
    bits[:, 1::5] = np.uint32(0x7FC01234)                                          # a NaN with a payload
    mat = jnp.asarray(bits.view(np.float32))
    for start in (0, 3000, n - w // 2):          # the last: a window clamped to the pack's end
        idx = np.sort(rng.integers(start, min(start + w, n), w)).astype(np.int32)
        idx[[0, 1, w - 1]] = -1
        got = np.asarray(_gather_rows(mat, jnp.asarray(idx), True))
        want = np.asarray(_gather_rows(mat, jnp.asarray(idx), False))
        assert got.tobytes() == want.tobytes(), start


@pytest.mark.parametrize("rows", [1, 2, 3, 15, 16])
@pytest.mark.parametrize("segment", [0, 1024], ids=["at_once", "in_segments"])
def test_a_pack_laid_as_lines_gathers_the_plain_gathers_bits(rows, segment, monkeypatch):
    """A pack of 1, 2, 3, 15 or 16 rows laid as lines of one lane width (a
    dimension row's values side by side, padded to a power of two) hands on,
    for unordered indices with misses among them, the bits the plain gather
    of the [P, N] pack hands on: NaN payloads, -0.0, infinities and
    denormals, at once and a segment at a time."""
    import jax
    import jax.numpy as jnp
    from daft_tpu.ops import device_join as dj
    from daft_tpu.ops.device_join import _gather_lines, _gather_rows, _lane_width, _pack_lines

    rng = np.random.default_rng(5)
    n, count = 8192, 4096
    bits = rng.integers(0, 2**32, (rows, n), dtype=np.uint64).astype(np.uint32)
    bits[:, ::5] = np.float32(-0.0).view(np.uint32)
    bits[:, 1::5] = np.uint32(0x7FC01234)
    mat = jnp.asarray(bits.view(np.float32))
    lines = _pack_lines(mat)
    assert lines.shape == (n * _lane_width(rows) // 128, 128)
    # laid eight pieces at a time, the same lines
    monkeypatch.setattr(dj, "_LINES_PIECE", n // 8)
    assert np.asarray(jax.jit(_pack_lines.__wrapped__)(mat)).tobytes() == np.asarray(lines).tobytes()
    idx = rng.integers(0, n, count).astype(np.int32)
    idx[[0, 7, count - 1]] = -1
    idx[[1, 2]] = [0, n - 1]
    got = np.asarray(_gather_lines(lines, jnp.asarray(idx), rows, segment))
    want = np.asarray(_gather_rows(mat, jnp.asarray(idx), False))
    assert got.shape == want.shape == (rows, count) and got.tobytes() == want.tobytes()


# ---- filtered joins: q12-, q14- and q19-shaped, priced at the delivered dispatch ---------

_PART_ROWS = 5000       # longer than a window of one morsel: an unordered gather reads it whole


def _filtered_like(morsels, seed=41, tail=100, part_rows=_PART_ROWS):
    """`orders`, `part` and a `lineitem` of `morsels` morsels less `tail` rows
    that follows `orders` (l_orderkey never decreases) and draws l_partkey
    uniformly over `part`, with the columns q12, q14 and q19 read."""
    import datetime

    rng = np.random.default_rng(seed)
    n_l = _MORSEL * morsels - tail
    n_o = max(n_l // 4, 100)
    day0 = datetime.date(1994, 1, 1)
    days = lambda lo, hi, n: [day0 + datetime.timedelta(days=int(x)) for x in rng.integers(lo, hi, n)]
    containers = [f"{a} {b}" for a in ("SM", "MED", "LG") for b in ("CASE", "BOX", "PACK", "PKG", "BAG")]
    t = {
        "orders": {"o_orderkey": list(range(n_o)),
                   "o_orderpriority": rng.choice(["1-URGENT", "2-HIGH", "3-MEDIUM", "5-LOW"], n_o).tolist()},
        "part": {"p_partkey": list(range(part_rows)),
                 "p_brand": rng.choice(["Brand#12", "Brand#23", "Brand#34", "Brand#45"], part_rows).tolist(),
                 "p_container": rng.choice(containers, part_rows).tolist(),
                 "p_size": rng.integers(1, 51, part_rows).tolist(),
                 "p_type": rng.choice(["PROMO TIN", "STANDARD TIN", "PROMO BRASS", "SMALL STEEL"],
                                      part_rows).tolist()},
        "lineitem": {"l_orderkey": np.sort(rng.integers(0, n_o, n_l)).tolist(),
                     "l_partkey": rng.integers(0, part_rows, n_l).tolist(),
                     "l_shipmode": rng.choice(["MAIL", "SHIP", "AIR", "REG AIR", "RAIL"], n_l).tolist(),
                     "l_shipinstruct": rng.choice(["DELIVER IN PERSON", "COLLECT COD", "NONE"], n_l).tolist(),
                     "l_quantity": rng.integers(1, 51, n_l).astype(float).tolist(),
                     "l_extendedprice": rng.uniform(900, 90000, n_l).round(2).tolist(),
                     "l_discount": (rng.integers(0, 11, n_l) / 100).tolist(),
                     "l_shipdate": days(0, 300, n_l), "l_commitdate": days(100, 400, n_l),
                     "l_receiptdate": days(200, 500, n_l)},
    }
    return {name: daft_tpu.from_pydict(cols).collect() for name, cols in t.items()}


def _q12_shaped(t):
    high = col("o_orderpriority").is_in(["1-URGENT", "2-HIGH"])
    return (t["lineitem"].where(
        col("l_shipmode").is_in(["MAIL", "SHIP"])
        & (col("l_commitdate") < col("l_receiptdate")) & (col("l_shipdate") < col("l_commitdate"))
        & (col("l_receiptdate") >= _days(1994, 9, 1)) & (col("l_receiptdate") < _days(1995, 3, 1)))
        .join(t["orders"], left_on="l_orderkey", right_on="o_orderkey")
        .with_column("high_line", high.if_else(lit(1), lit(0)))
        .with_column("low_line", (~high).if_else(lit(1), lit(0)))
        .groupby("l_shipmode")
        .agg(col("high_line").sum().alias("high_line_count"),
             col("low_line").sum().alias("low_line_count"))
        .sort("l_shipmode"))


def _q14_shaped(t):
    return (t["lineitem"].where((col("l_shipdate") >= _days(1994, 3, 1))
                                & (col("l_shipdate") < _days(1994, 6, 1)))
            .join(t["part"], left_on="l_partkey", right_on="p_partkey")
            .with_column("revenue", col("l_extendedprice") * (1 - col("l_discount")))
            .with_column("promo", col("p_type").str.startswith("PROMO").if_else(col("revenue"), lit(0.0)))
            .agg(col("promo").sum().alias("promo_sum"), col("revenue").sum().alias("total_sum"))
            .select((lit(100.0) * col("promo_sum") / col("total_sum")).alias("promo_revenue")))


def _q19_shaped(t):
    joined = t["lineitem"].where(
        col("l_shipmode").is_in(["AIR", "REG AIR"]) & (col("l_shipinstruct") == "DELIVER IN PERSON")
    ).join(t["part"], left_on="l_partkey", right_on="p_partkey")
    sm = (col("p_brand") == "Brand#12") & col("p_container").is_in(
        ["SM CASE", "SM BOX", "SM PACK", "SM PKG"]
    ) & (col("l_quantity") >= 1) & (col("l_quantity") <= 11) & (col("p_size") <= 5)
    med = (col("p_brand") == "Brand#23") & col("p_container").is_in(
        ["MED BAG", "MED BOX", "MED PKG", "MED PACK"]
    ) & (col("l_quantity") >= 10) & (col("l_quantity") <= 20) & (col("p_size") <= 10)
    lg = (col("p_brand") == "Brand#34") & col("p_container").is_in(
        ["LG CASE", "LG BOX", "LG PACK", "LG PKG"]
    ) & (col("l_quantity") >= 20) & (col("l_quantity") <= 30) & (col("p_size") <= 15)
    return (joined.where((col("p_size") >= 1) & (sm | med | lg))
            .agg((col("l_extendedprice") * (1 - col("l_discount"))).sum().alias("revenue")))


_FILTERED = {"q12": _q12_shaped, "q14": _q14_shaped, "q19": _q19_shaped}


@pytest.mark.parametrize("shape", ["q12", "q19"])
@pytest.mark.parametrize("morsels, shards", [(1, 1), (6, 1), (58, 1), (58, 4)],
                         ids=["1_bucket", "6_buckets", "58_buckets", "58_over_a_mesh_of_four"])
def test_a_join_is_priced_at_the_dispatch_the_run_delivers(shape, morsels, shards, monkeypatch):
    """The rows a dispatch a join's chosen device arm is priced at
    (`join_priced_dispatch_rows`, and `priced_rows` on the placement record)
    are the rows of the ranges the driver then hands to feed_batch: the
    whole fact where it is one bucket, four morsels of six (a dispatch is
    never the whole fact), eight of fifty-eight, and eight a shard over a
    mesh of four. A grouped join on fact-side codes (q12's shape) and a global
    aggregate (q19's); the forced tier priced anyway so that the decision
    runs off the chip."""
    from daft_tpu.observability import placement

    # (a fact of one bucket beside dimensions shorter than itself)
    t = _filtered_like(morsels) if morsels > 1 else _filtered_like(1, tail=0, part_rows=1000)
    q = lambda: _FILTERED[shape](t)
    host = _host_answer(q)
    fed = []
    _spy_fed_batches(monkeypatch, fed)
    monkeypatch.setenv("DAFT_TPU_PLACEMENT_PRICE_FORCED", "1")
    priced0 = counters.join_priced_dispatch_rows
    with placement.query_scope() as scope, execution_config_ctx(
            device_mode="on", morsel_size_rows=_MORSEL, pipeline_mode="force",
            mesh_devices=shards if shards > 1 else 1):
        got = q().to_pydict()
    _assert_close(host, got)
    priced = counters.join_priced_dispatch_rows - priced0
    (rec,) = [r for r in scope.records() if r.priced_rows]
    assert rec.priced_rows == priced and rec.to_dict()["priced_rows"] == priced
    assert rec.chosen == ("mesh" if shards > 1 else "device")
    delivered = [b.num_rows for b in fed]
    want = {(1, 1): [_MORSEL], (6, 1): [4 * _MORSEL, 2 * _MORSEL - 100],
            (58, 1): [8 * _MORSEL] * 7 + [2 * _MORSEL - 100],
            (58, 4): [32 * _MORSEL, 26 * _MORSEL - 100]}[(morsels, shards)]
    assert delivered == want
    assert priced == max(delivered) and all(rows == priced for rows in delivered[:-1])


def test_a_fact_through_the_pipeline_is_priced_as_it_was(monkeypatch):
    """A fact that is no select over one table (a computed column under the
    join) comes through the pipeline and the coalescer, and its tiers are
    priced by _coalesce_horizon as before: what its leading morsels promise,
    never the resident range."""
    t = _filtered_like(19)
    computed = dict(t, lineitem=t["lineitem"].with_column(
        "l_quantity", col("l_quantity") + 0.0))
    q = lambda: _q19_shaped(computed)
    host = _host_answer(q)
    monkeypatch.setenv("DAFT_TPU_PLACEMENT_PRICE_FORCED", "1")
    priced0 = counters.join_priced_dispatch_rows
    with _morselized("on"):
        got = q().to_pydict()
    _assert_close(host, got)
    assert 0 < counters.join_priced_dispatch_rows - priced0 <= 2 * _MORSEL


@pytest.mark.parametrize("shape, unwindowed", [("q12", False), ("q14", True), ("q19", True),
                                               ("q3", False), ("q5", False)])
def test_unwindowed_gathers_count_a_fact_its_dimension_is_not_ordered_by(shape, unwindowed):
    """`join_unwindowed_gathers` is the complement of `join_window_gathers`:
    l_partkey is uniform over a `part` longer than a window, so every
    dispatch of a q14- or q19-shaped join reads the whole pack (one a
    dispatch); `lineitem` follows `orders`, so q12's, q3's and q5's gathers
    read a window and count none (q5's `supplier` pack is shorter than a
    window: neither counter)."""
    if shape in _FILTERED:
        t = _filtered_like(19)
        q = lambda: _FILTERED[shape](t)
    else:
        _t, q = _long_query(shape)
    host = _host_answer(q)
    before = {k: getattr(counters, k) for k in
              ("join_unwindowed_gathers", "join_window_gathers", "device_join_batches")}
    with _morselized("on"):
        got = q().to_pydict()
    _assert_close(host, got)
    grown = {k: getattr(counters, k) - v for k, v in before.items()}
    assert grown["device_join_batches"] == 3
    assert grown["join_unwindowed_gathers"] == (3 if unwindowed else 0)
    assert grown["join_window_gathers"] == (0 if unwindowed else 3)


@pytest.mark.parametrize("fast_pack_bytes", [None, 0], ids=["as_the_pack", "as_lines"])
@pytest.mark.parametrize("shape", ["q12", "q14", "q19"])
def test_filtered_joins_over_eight_segment_ranges_give_the_host_engines_answers(
        shape, segments, fast_pack_bytes, monkeypatch):
    """q12's fact-coded groups beside a windowed `orders` pack, q14's
    synthetic dimension column and q19's disjunction hoisted over fact and
    dimension with two fact-side membership planes, over a resident fact of
    19 morsels: at a bucket a dispatch and at eight segments a dispatch the
    host engine's answer, and a repeat misses no slot and uploads nothing;
    `part`'s whole pack gathered as the [P, N] matrix it is (a test's pack
    fits any fast memory) and, the threshold taken away, laid as lines: the
    same answer to the bit."""
    import daft_tpu.ops.device_join as dj
    from daft_tpu.device.residency import manager

    if fast_pack_bytes is not None:
        monkeypatch.setattr(dj, "_FAST_PACK_BYTES", fast_pack_bytes)
    laid = []
    real_lines_of = dj._JoinContext._lines_of
    monkeypatch.setattr(dj._JoinContext, "_lines_of", lambda self, adj, mat: (
        laid.append(adj.name), real_lines_of(self, adj, mat))[1])
    manager().clear()
    t = _filtered_like(19)
    q = lambda: _FILTERED[shape](t)
    host = _host_answer(q)
    first, _deltas, dispatches = _device_run(q)
    _assert_close(host, first)
    assert dispatches == _dispatches(19, segments)
    again, warm, _ = _device_run(q)
    assert again == first
    assert warm["hbm_cache_misses"] == 0 and warm["hbm_h2d_bytes"] == 0, warm
    # the lines are laid once a query, for the unordered dimension alone
    assert len(laid) == (2 * dispatches if fast_pack_bytes == 0 and shape != "q12" else 0)
    if fast_pack_bytes == 0:
        monkeypatch.setattr(dj, "_FAST_PACK_BYTES", 1 << 40)
        plain, _w, _d = _device_run(q)
        assert plain == first, "the lines hand on the plain gather's bits"
    manager().clear()


def test_a_membership_look_up_has_a_span_that_says_whether_it_hit():
    """`join.membership` spans `_fact_membership_plane`'s look-up a plane a
    dispatch: `hit` false where the plane was built, true on a repeat."""
    from daft_tpu.device.residency import manager
    from daft_tpu.observability.runtime_stats import SpanRecorder, set_spans

    manager().clear()
    t = _filtered_like(19)
    seen = []
    for _ in (1, 2):
        rec = SpanRecorder()
        set_spans(rec)
        try:
            with _morselized("on"):
                _q19_shaped(t).to_pydict()
        finally:
            set_spans(None)
        spans = [s for s in rec.drain() if s["name"] == "join.membership"]
        assert len(spans) == 2 * 3      # two planes a dispatch, three dispatches
        assert all(s["args"]["rows"] > 0 for s in spans)
        seen.append({s["args"]["hit"] for s in spans})
    assert seen == [{False}, {True}]
    manager().clear()

"""The filter-aggregate and grouped-aggregate stages over a mesh of local
devices (ops/stage.py, ops/grouped_stage.py with ``mesh_devices`` > 1), under
8 forced host devices.

A sharded run is the single chip's program on every shard: the answers equal
the plain reference's (benchmark/reference/tpch.py, loaded by path) inside
the four-chip configuration's limits and the single chip's group order; the
shards' partial tables add up to the single chip's table; nothing float64 or
int64 is as long as the rows; a repeat query uploads and builds nothing; the
counters say how many devices a dispatch spanned. Then the tier decision
(mesh must WIN its placement), the loud fallback of a forced mesh wider than
the host, and the off switch. Run standalone via `make test-mesh`.
"""

import importlib.util
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import daft_tpu
from daft_tpu import col
from daft_tpu.config import execution_config_ctx
from daft_tpu.observability.metrics import registry
from daft_tpu.observability.runtime_stats import SpanRecorder, set_spans
from daft_tpu.ops import counters

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 (virtual) devices — see conftest")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
WIDTHS = [2, 4]


def _bench_module(rel):
    """A file of the benchmark by path: it imports nothing of daft_tpu but
    the client API (queries) or nothing at all (reference, datagen, compare)."""
    name = "mesh_test_" + rel.replace("/", "_").replace(".py", "")
    spec = importlib.util.spec_from_file_location(name, os.path.join(BENCH, rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tpch():
    """lineitem at SF0.002 from a seed (about 12,000 rows: no multiple of a
    shard's bucket), loaded; the reference, the queries, the comparison and
    the four-chip configuration's limits."""
    datagen = _bench_module("datagen/tpch.py")
    arrow = datagen.generate(0.002, 2320001, ["lineitem"])
    with open(os.path.join(BENCH, "configs", "tpch-sf30-4chip.json")) as f:
        config = json.load(f)
    return {"arrow": arrow,
            "tables": {"lineitem": daft_tpu.from_arrow(arrow["lineitem"]).collect()},
            "reference": _bench_module("reference/tpch.py"),
            "queries": _bench_module("queries/tpch.py"),
            "compare": _bench_module("compare.py"), "config": config}


def _groupby_query(d):
    return (d.where(col("w") < 900)
            .groupby("k")
            .agg(col("v").sum().alias("s"), col("v").mean().alias("m"),
                 col("v").min().alias("lo"), col("v").max().alias("hi"),
                 col("v").count().alias("c"), col("big").sum().alias("bs"))
            .sort("k"))


@pytest.fixture(scope="module")
def df():
    rng = np.random.default_rng(7)
    n = 5003  # no multiple of any mesh
    return daft_tpu.from_pydict({
        "k": rng.choice(["a", "b", "c", None, "d"], n).tolist(),
        "v": [None if i % 13 == 0 else float(i % 101) for i in range(n)],
        "w": rng.integers(0, 1000, n).tolist(),
        "big": (2**40 + rng.integers(0, 1000, n)).tolist(),
        "g": (np.arange(n) % 40).tolist(),
        "pos": np.arange(n).tolist(),
    })


# ---- answers ---------------------------------------------------------------------------


@pytest.mark.parametrize("ndev", WIDTHS)
@pytest.mark.parametrize("template", ["q1", "q6"])
def test_tpch_answers_equal_the_plain_reference(tpch, template, ndev):
    """q1 and q6 as the benchmark writes them, through the DataFrame API over
    a forced mesh, against benchmark/reference/tpch.py inside the limits of
    configs/tpch-sf30-4chip.json; every dispatch spans `ndev` devices."""
    counters.reset()
    with execution_config_ctx(device_mode="on", mesh_devices=ndev,
                              device_min_rows=1):
        got = tpch["queries"].TEMPLATES[template]["program"](tpch["tables"]).to_pydict()
    ref = tpch["reference"].answer(template, tpch["arrow"])
    cmp = tpch["compare"]
    numbers = cmp.compare(ref, got)
    assert cmp.within(numbers, cmp.limits(tpch["config"], template)), numbers
    assert counters.device_mesh_batches == 1
    assert counters.device_mesh_shards == ndev
    assert counters.device_grouped_batches + counters.device_stage_batches == 1


@pytest.mark.parametrize("ndev", WIDTHS + [8])
def test_grouped_parity_mesh_vs_single_vs_host(df, ndev):
    """Nulls in key and values, a dictionary key, float extremes (the f64
    mode): the sharded stage gives the single chip's answer, value for
    value, and the host's within f64 rounding."""
    counters.reset()
    with execution_config_ctx(device_mode="on", mesh_devices=ndev):
        mesh_out = _groupby_query(df).to_pydict()
    assert counters.mesh_grouped_runs > 0
    assert counters.mesh_dispatches > 0
    assert counters.device_grouped_batches > 0
    counters.reset()
    with execution_config_ctx(device_mode="on", mesh_devices=1):
        single_out = _groupby_query(df).to_pydict()
    assert counters.mesh_dispatches == 0, "mesh_devices=1 must stay single-chip"
    assert counters.device_mesh_batches == 0
    assert counters.device_grouped_batches > 0
    with execution_config_ctx(device_mode="off"):
        host_out = _groupby_query(df).to_pydict()
    for c in ("k", "c", "bs", "lo", "hi"):
        assert mesh_out[c] == single_out[c] == host_out[c], c
    for c in ("s", "m"):
        np.testing.assert_allclose(
            np.array(mesh_out[c], dtype=float),
            np.array(host_out[c], dtype=float), rtol=1e-12)


@pytest.mark.parametrize("ndev", WIDTHS)
def test_ungrouped_parity_mesh_vs_host(df, ndev):
    def q(d):
        return d.where(col("w") < 900).agg(
            col("v").sum().alias("s"), col("v").count().alias("c"),
            col("v").min().alias("lo"), col("v").mean().alias("m"),
            col("big").sum().alias("bs"))

    counters.reset()
    with execution_config_ctx(device_mode="on", mesh_devices=ndev):
        mesh_out = q(df).to_pydict()
    assert counters.device_mesh_batches == 1
    assert counters.device_stage_batches == 1
    with execution_config_ctx(device_mode="off"):
        host_out = q(df).to_pydict()
    assert mesh_out["c"] == host_out["c"]
    assert mesh_out["bs"] == host_out["bs"]
    np.testing.assert_allclose(mesh_out["s"], host_out["s"], rtol=1e-12)
    np.testing.assert_allclose(mesh_out["m"], host_out["m"], rtol=1e-12)
    np.testing.assert_allclose(mesh_out["lo"], host_out["lo"])


@pytest.mark.parametrize("ndev", WIDTHS)
def test_a_predicate_that_empties_a_shard(df, ndev):
    """`pos` rises with the rows, so the first shards keep no row at all:
    their tables are empty and the answer is the later shards' alone."""
    def q(d):
        return (d.where(col("pos") >= 4000).groupby("k")
                .agg(col("v").sum().alias("s"), col("pos").min().alias("first"))
                .sort("k"))

    with execution_config_ctx(device_mode="on", mesh_devices=ndev):
        mesh_out = q(df).to_pydict()
        flat = df.where(col("pos") >= 4000).agg(col("v").sum().alias("s")).to_pydict()
    with execution_config_ctx(device_mode="off"):
        host_out = q(df).to_pydict()
        host_flat = df.where(col("pos") >= 4000).agg(col("v").sum().alias("s")).to_pydict()
    assert mesh_out["k"] == host_out["k"] and mesh_out["first"] == host_out["first"]
    np.testing.assert_allclose(mesh_out["s"], host_out["s"], rtol=1e-6)
    np.testing.assert_allclose(flat["s"], host_flat["s"], rtol=1e-6)


@pytest.mark.parametrize("ndev", WIDTHS)
def test_more_than_sixteen_groups_take_the_matmul_form(df, ndev):
    def q(d):
        return d.groupby("g").agg(col("v").sum().alias("s"),
                                  col("v").count().alias("c")).sort("g")

    counters.reset()
    with execution_config_ctx(device_mode="on", mesh_devices=ndev):
        mesh_out = q(df).to_pydict()
    assert counters.device_grouped_reduce_matmul == 1
    assert counters.device_mesh_shards == ndev
    with execution_config_ctx(device_mode="off"):
        host_out = q(df).to_pydict()
    assert mesh_out["g"] == host_out["g"] and mesh_out["c"] == host_out["c"]
    np.testing.assert_allclose(mesh_out["s"], host_out["s"], rtol=1e-6)


@pytest.mark.parametrize("ndev", WIDTHS)
def test_an_expression_key_is_factorized_and_sharded(df, ndev):
    """Keys that are no bare columns factorize on the host (as on one chip);
    the segment-id plane is then sharded like the rest."""
    def q(d):
        return (d.groupby((col("g") % 7).alias("r"))
                .agg(col("v").sum().alias("s")).sort("r"))

    counters.reset()
    with execution_config_ctx(device_mode="on", mesh_devices=ndev):
        mesh_out = q(df).to_pydict()
    assert counters.device_mesh_batches == 1
    with execution_config_ctx(device_mode="off"):
        host_out = q(df).to_pydict()
    assert mesh_out["r"] == host_out["r"]
    np.testing.assert_allclose(mesh_out["s"], host_out["s"], rtol=1e-6)


@pytest.mark.parametrize("ndev", WIDTHS)
def test_group_order_is_the_single_chips(df, ndev):
    """No sort: groups come in the order of their first row in the table,
    which a shard knows only with the rows of the shards before it."""
    def q(d):
        return d.where(col("w") < 900).groupby("k", "g").agg(col("v").count().alias("c"))

    with execution_config_ctx(device_mode="on", mesh_devices=ndev):
        mesh_out = q(df).to_pydict()
    with execution_config_ctx(device_mode="on", mesh_devices=1):
        single_out = q(df).to_pydict()
    with execution_config_ctx(device_mode="off"):
        host_out = q(df).to_pydict()
    assert len(mesh_out["k"]) > 100
    assert mesh_out == single_out == host_out


def test_mesh_empty_after_filter():
    df = daft_tpu.from_pydict({"k": ["a", "b"], "v": [1.0, 2.0], "w": [1, 2]})
    with execution_config_ctx(device_mode="on", mesh_devices=8):
        out = (df.where(col("w") > 100).groupby("k")
               .agg(col("v").sum().alias("s")).to_pydict())
    assert out == {"k": [], "s": []}


# ---- the program -----------------------------------------------------------------------


def _q1_shaped_stage(tpch):
    """q1's stage, as the executor builds it, the literal values of this q1
    (a run takes them), and lineitem's one batch."""
    from daft_tpu.ops.grouped_stage import try_build_grouped_agg_stage
    from daft_tpu.ops.stage import stage_literals

    node = _device_agg_node(tpch["queries"].q1(tpch["tables"]))
    stage = try_build_grouped_agg_stage(node.input.schema, node.predicate,
                                        node.groupby, node.aggregations)
    assert stage is not None and stage.dict_keys and not stage._use_f64
    (part,) = node.input.partitions
    (batch,) = part.batches
    return stage, stage_literals(node.predicate, node.aggregations), batch


def _device_agg_node(df):
    """The DeviceFilterAgg / DeviceGroupedAgg node of a query's physical plan."""
    from daft_tpu.plan import physical as pp

    with execution_config_ctx(device_mode="on", device_min_rows=1):
        plan = pp.translate(df._builder.optimize()._plan)
    return next(n for n in plan.walk()
                if isinstance(n, (pp.DeviceFilterAgg, pp.DeviceGroupedAgg)))


@pytest.mark.parametrize("ndev", WIDTHS)
def test_shard_tables_add_up_to_the_single_chips(tpch, ndev):
    """The sharded dispatch returns one [cap, planes] table a shard; summed
    they are the single chip's table over the same rows (the count planes to
    the row, the float sums to f32 rounding), and each shard's first-row
    positions lie inside that shard's stretch of the batch."""
    from daft_tpu.ops.stage import mesh_total

    stage, literals, batch = _q1_shaped_stage(tpch)
    run = stage.start_run(literals, mesh_devices=ndev)
    run.feed_batch(batch)
    (out, decode), = run._pending
    one = stage.start_run(literals)
    one.feed_batch(batch)
    (single, _), = one._pending
    mm, single_mm = np.asarray(out["mm"]), np.asarray(single["mm"])
    assert decode.shards == ndev
    assert mm.shape == (ndev,) + single_mm.shape
    np.testing.assert_array_equal(mm[:, :, 0].sum(axis=0), single_mm[:, 0])
    np.testing.assert_allclose(mm.sum(axis=0), single_mm, rtol=2e-6)
    per = mesh_total(batch.num_rows, ndev) // ndev
    first = np.asarray(out["ext"][0])
    for s in range(ndev):
        seen = first[s][np.isfinite(first[s])]
        assert ((seen >= s * per) & (seen < (s + 1) * per)).all()
    np.testing.assert_array_equal(first.min(axis=0), np.asarray(single["ext"][0]))
    got = run.finalize()
    want = one.finalize()
    assert got[0] == want[0]
    for (gv, gok), (wv, wok) in zip(got[1], want[1]):
        assert gok.tolist() == wok.tolist()
        np.testing.assert_allclose(gv.astype(float), wv.astype(float), rtol=2e-6)


@pytest.mark.parametrize("ndev", WIDTHS)
def test_no_operand_at_row_width_is_float64_or_int64(tpch, ndev):
    """What the sharded q1 and q6 read: f32 values, int32 dates and codes,
    bool validity and masks. Read from the arrays alive after the queries
    (the resident planes are the programs' operands) and from q1's jaxpr."""
    from daft_tpu.device.residency import manager

    manager().clear()
    n = tpch["arrow"]["lineitem"].num_rows
    with execution_config_ctx(device_mode="on", mesh_devices=ndev,
                              device_min_rows=1):
        for t in ("q1", "q6"):
            tpch["queries"].TEMPLATES[t]["program"](tpch["tables"]).to_pydict()
    long = [a for a in jax.live_arrays() if a.ndim and a.shape[0] >= n]
    assert len(long) >= 7
    wide = [(a.shape, a.dtype) for a in long
            if a.dtype in (jnp.float64, jnp.int64, jnp.uint64)]
    assert wide == []
    sharded = [a for a in long if len(a.sharding.device_set) == ndev]
    assert len(sharded) >= 7, "the planes are not sharded over the mesh"

    stage, literals, batch = _q1_shaped_stage(tpch)
    run = stage.start_run(literals, mesh_devices=ndev)
    seen = {}
    real = stage._program_for

    def spy(*args):
        prog, form = real(*args)

        def call(*operands):
            seen["jaxpr"] = jax.make_jaxpr(prog)(*operands)
            return prog(*operands)
        return call, form

    stage._program_for = spy
    try:
        run.feed_batch(batch)
    finally:
        del stage._program_for
    run.finalize()
    for v in seen["jaxpr"].jaxpr.invars:
        if v.aval.shape and v.aval.shape[0] >= n:
            assert v.aval.dtype not in (jnp.float64, jnp.int64), v.aval
    assert "psum" not in str(seen["jaxpr"]) and "all_gather" not in str(seen["jaxpr"])


# ---- coalesced feed ------------------------------------------------------------------


def test_coalesced_feed_into_mesh_stage():
    """The DispatchCoalescer in front of a sharded run merges N morsels into
    one super-batch => ONE multi-device dispatch covering them all."""
    from daft_tpu.ops.grouped_stage import try_build_grouped_agg_stage
    from daft_tpu.ops.stage import DispatchCoalescer

    df = daft_tpu.from_pydict({"k": (np.arange(4000) % 3).tolist(),
                               "v": np.arange(4000, dtype=float).tolist()}).collect()
    batch = df._result[0].batches[0]
    morsels = [batch.slice(s, s + 500) for s in range(0, 4000, 500)]
    stage = try_build_grouped_agg_stage(
        df.schema, None, [col("k")], [col("v").sum().alias("s")])
    run = stage.start_run(mesh_devices=8)
    coal = DispatchCoalescer(run.feed_batch, target_rows=100_000, latency_s=60.0)
    d0 = counters.mesh_dispatches
    for m in morsels:
        coal.add(m)
    coal.close()
    keys, results = run.finalize()
    assert counters.mesh_dispatches - d0 == 1, "morsels were not coalesced"
    got = dict(zip((k[0] for k in keys), results[0][0].tolist()))
    arr = np.arange(4000, dtype=float)
    for k in range(3):
        np.testing.assert_allclose(got[k], arr[np.arange(4000) % 3 == k].sum())


def test_successive_batches_keep_their_order_over_a_mesh():
    """Two dispatches of one run: the second's row offset is the first's rows,
    each shard adds its own, and the groups come in the table's order."""
    from daft_tpu.ops.grouped_stage import try_build_grouped_agg_stage

    keys = ["z", "y"] * 300 + ["x", "w"] * 300
    df = daft_tpu.from_pydict({"k": keys, "v": [1.0] * len(keys)}).collect()
    batch = df._result[0].batches[0]
    stage = try_build_grouped_agg_stage(
        df.schema, None, [col("k")], [col("v").sum().alias("s")])
    run = stage.start_run(mesh_devices=4)
    run.feed_batch(batch.slice(0, 700))
    run.feed_batch(batch.slice(700, 1200))
    out_keys, results = run.finalize()
    assert [k[0] for k in out_keys] == ["z", "y", "x", "w"]
    assert results[0][0].tolist() == [300.0, 300.0, 300.0, 300.0]


# ---- sharded resident planes ---------------------------------------------------------


@pytest.mark.parametrize("ndev", WIDTHS)
def test_repeat_mesh_query_uploads_and_builds_nothing(tpch, ndev):
    """Second identical sharded query reads the resident shards: zero new h2d
    bytes, no residency.build, no device.upload; the first built the planes in
    the f32 sharded layout only, and the sharded slots publish in the
    residency digest (the heartbeat vocabulary) like any other plane."""
    from daft_tpu.device.residency import manager

    manager().clear()
    q1 = tpch["queries"].TEMPLATES["q1"]["program"]
    with execution_config_ctx(device_mode="on", mesh_devices=ndev,
                              device_min_rows=1):
        first = q1(tpch["tables"]).to_pydict()
        h1 = registry().get("hbm_h2d_bytes")
        m1 = registry().get("hbm_cache_misses")
        rec = SpanRecorder()
        set_spans(rec)
        try:
            second = q1(tpch["tables"]).to_pydict()
        finally:
            set_spans(None)
    assert first == second
    assert registry().get("hbm_h2d_bytes") == h1
    assert registry().get("hbm_cache_misses") == m1
    spans = rec.drain()
    names = [s["name"] for s in spans]
    assert "residency.build" not in names and "device.upload" not in names
    launch, = [s for s in spans if s["name"] == "device.launch"]
    assert launch["args"]["devices"] == ndev
    # the single chip's names, nested as on one chip (xtrace.GAP_SPANS reads them)
    for name in ("device.dispatch", "device.d2h", "stage.finalize"):
        assert name in names
    assert not [n for n in names if n.startswith("device.mesh_")]
    assert len(manager().digest()) > 0, "sharded slots missing from digest"
    batch = tpch["tables"]["lineitem"]._result[0].batches[0]
    from daft_tpu.ops.stage import mesh_total, pad_bucket

    s = batch.get_column("l_quantity")
    assert s.is_device_resident(mesh_total(len(s), ndev), f32=True, mesh_devices=ndev)
    assert not s.is_device_resident(pad_bucket(len(s)), f32=True)
    assert not s.is_device_resident(mesh_total(len(s), ndev), f32=False,
                                    mesh_devices=ndev)


def test_mesh_planes_pin_under_tiny_hbm_budget():
    """Sharded planes built inside a query pin via the executor's pin_scope:
    a budget far below the working set must not thrash them mid-query."""
    df = daft_tpu.from_pydict({"k": (np.arange(6000) % 7).tolist(),
                               "v": (np.arange(6000) % 101).astype(float).tolist()})

    def q(d):
        return d.groupby("k").agg(col("v").sum().alias("s"),
                                  col("v").count().alias("c")).sort("k")

    with execution_config_ctx(device_mode="off"):
        host_out = q(df).to_pydict()
    counters.reset()
    with execution_config_ctx(device_mode="on", mesh_devices=8,
                              hbm_budget_bytes=1024):
        mesh_out = q(df).to_pydict()
    assert counters.mesh_grouped_runs > 0
    assert counters.hbm_pins > 0, "mesh planes never pinned"
    assert mesh_out["k"] == host_out["k"] and mesh_out["c"] == host_out["c"]
    np.testing.assert_allclose(mesh_out["s"], host_out["s"], rtol=1e-6)


@pytest.mark.parametrize("ndev", [1, 2, 8])
def test_the_budget_reckons_a_sharded_plane_per_device(ndev):
    """The HBM budget is one device's: a plane row-sharded over N devices
    holds 1/N of its bytes on each, a replicated one a whole copy."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from daft_tpu.device.residency import device_nbytes
    from daft_tpu.parallel.distributed import default_mesh

    host = np.zeros(8 * 1024, np.float32)
    mesh = default_mesh(ndev)
    sharded = jax.device_put(host, NamedSharding(mesh, P("dp")))
    replicated = jax.device_put(host, NamedSharding(mesh, P()))
    assert device_nbytes(sharded) == host.nbytes // ndev
    assert device_nbytes(replicated) == host.nbytes
    assert device_nbytes((sharded, {"x": replicated})) \
        == host.nbytes // ndev + host.nbytes


# ---- the tier decision ---------------------------------------------------------------


_PINNED = {
    "DAFT_TPU_COST_RTT": "0.001", "DAFT_TPU_COST_H2D": "1e12",
    "DAFT_TPU_COST_D2H": "1e9", "DAFT_TPU_COST_MM_RATE": "1e9",
    "DAFT_TPU_COST_MM_CELL_RATE": "3e7", "DAFT_TPU_COST_MESH_DISPATCH": "0.05",
    "DAFT_TPU_COST_ICI": "1e12", "DAFT_TPU_COST_HOST_AGG": "1e3",
    "DAFT_TPU_COST_HOST_FACT": "1e9",
}


def test_auto_tier_flips_at_calibrated_boundary():
    """mesh_devices=0: the decision cache picks the mesh for a large-shape
    stage and rejects it for a tiny one — mesh must WIN its placement. Cost
    knobs are env-pinned so the boundary is deterministic on any host."""
    from daft_tpu.execution import executor
    from daft_tpu.ops import costmodel

    os.environ.update(_PINNED)
    costmodel.reset_calibration()
    executor._MESH_TIER_CACHE.clear()
    try:
        big = daft_tpu.from_pydict({
            "k": (np.arange(200_000) % 5).tolist(),
            "v": (np.arange(200_000) % 97).astype(float).tolist()})
        small = daft_tpu.from_pydict({
            "k": (np.arange(2_000) % 5).tolist(),
            "v": (np.arange(2_000) % 97).astype(float).tolist()})

        def q(d):
            return d.groupby("k").agg(col("v").sum().alias("s")).sort("k")

        counters.reset()
        with execution_config_ctx(device_mode="on", mesh_devices=0,
                                  device_min_rows=1):
            big_out = q(big).to_pydict()
        assert counters.mesh_grouped_runs > 0, "auto tier rejected the big shape"
        assert counters.device_mesh_shards == 8 * counters.device_mesh_batches
        counters.reset()
        with execution_config_ctx(device_mode="on", mesh_devices=0,
                                  device_min_rows=1):
            q(small).to_pydict()
        assert counters.mesh_grouped_runs == 0, "auto tier took a tiny shape"
        assert counters.device_grouped_batches > 0
        with execution_config_ctx(device_mode="off"):
            host_out = q(big).to_pydict()
        assert big_out["k"] == host_out["k"]
        np.testing.assert_allclose(big_out["s"], host_out["s"], rtol=1e-6)
    finally:
        for k in _PINNED:
            os.environ.pop(k, None)
        costmodel.reset_calibration()
        executor._MESH_TIER_CACHE.clear()


@pytest.mark.parametrize("grouped", [False, True])
def test_the_tier_decision_prices_the_layout_the_stage_asks_for(grouped):
    """_mesh_wins prices each arm's planes in that arm's own layout and the
    stage's dtype (f32): after a sharded run the mesh arm's record carries
    the residency credit and no upload, the single-chip arm still the
    upload; the mesh arm's compute is the single chip's at a shard's rows,
    and it has no factorize rows of its own nor an ICI term."""
    from daft_tpu.execution import executor

    n = 40_000
    df = daft_tpu.from_pydict({"k": (np.arange(n) % 5).astype(str).tolist(),
                               "v": (np.arange(n) % 97).astype(float).tolist()}).collect()

    def q(d):
        return d.groupby("k").agg(col("v").sum().alias("s")) if grouped \
            else d.agg(col("v").sum().alias("s"))

    with execution_config_ctx(device_mode="on", mesh_devices=4, device_min_rows=1):
        q(df).to_pydict()
    node = _device_agg_node(q(df))
    (part,) = node.input.partitions
    _wins, rec = executor._mesh_wins(node, part, grouped, 4)
    mesh, single = rec.mesh, rec.device
    assert "ici" not in mesh and mesh["mesh_dispatch"] > 0
    assert mesh.get("h2d", 0.0) == 0.0, "a warm sharded query priced as an upload"
    assert mesh.get("note_residency_credit_s", 0.0) > 0.0
    assert single.get("h2d", 0.0) > 0.0
    assert mesh["compute"] == pytest.approx(single["compute"] / 4, rel=0.01)
    assert mesh.get("factorize", 0.0) == single.get("factorize", 0.0)


def test_mesh_cost_functions_scale():
    """Unit sanity on the sharded arm: it divides the single chip's compute
    by the mesh width but pays the dispatch premium and the fetch of a table
    a shard."""
    from daft_tpu.ops import costmodel

    cal = costmodel.Calibration(
        rtt_s=0.001, h2d_bytes_per_s=1e9, d2h_bytes_per_s=1e9,
        mm_plane_rows_per_s=1e9, mm_cell_rate=5e10, scatter_rows_per_s=1e8,
        ext_cell_rate=5e9, host_agg_rate=1.5e8, host_factorize_rate=8e6,
        host_probe_rate=3e7, ici_bytes_per_s=4.5e10, mesh_dispatch_s=2e-3)
    small = costmodel.over_mesh(
        costmodel.device_ungrouped_cost(cal, 10_000 // 8, 0, 2), cal, 8, 32)
    single_small = costmodel.device_ungrouped_cost(cal, 10_000, 0, 2)
    assert small > single_small, "tiny shapes must not prefer the mesh"
    assert {"mesh_dispatch", "combine", "compute"} <= set(small.terms)
    big_single = costmodel.device_grouped_cost(
        cal, 500_000_000, 0, n_mm=4, n_ext=1, n_sct=0, cap=1024, factorize_rows=0)
    big_mesh = costmodel.over_mesh(costmodel.device_grouped_cost(
        cal, 500_000_000 // 8, 0, n_mm=4, n_ext=1, n_sct=0, cap=1024,
        factorize_rows=0), cal, 8, 1024 * 5 * 8)
    assert big_mesh < big_single, "huge shapes must amortize across the mesh"
    assert big_mesh.terms["combine"] == pytest.approx(7 * 1024 * 5 * 8 / 1e9)


# ---- forced-mesh fallback + config ---------------------------------------------------


def test_forced_mesh_over_device_count_falls_back_loudly():
    df = daft_tpu.from_pydict({"k": ["a", "b"] * 100,
                               "v": list(range(200))})
    counters.reset()
    with execution_config_ctx(device_mode="on", mesh_devices=16):
        out = df.groupby("k").agg(col("v").sum().alias("s")).sort("k").to_pydict()
    assert counters.mesh_unavailable_fallbacks > 0
    assert counters.mesh_grouped_runs == 0
    assert counters.device_mesh_batches == 0
    assert counters.device_grouped_batches > 0, "fallback must still run device"
    assert out["s"] == [sum(range(0, 200, 2)), sum(range(1, 200, 2))]


def test_default_mesh_rejects_oversized_request():
    from daft_tpu.parallel.distributed import default_mesh

    with pytest.raises(ValueError, match="devices"):
        default_mesh(len(jax.devices()) + 1)


def test_config_rejects_negative_mesh_devices():
    from daft_tpu.config import ExecutionConfig

    with pytest.raises(ValueError, match="mesh_devices"):
        ExecutionConfig(mesh_devices=-1)


# ---- zero-overhead guard -------------------------------------------------------------


def test_mesh_off_means_no_mesh_imports():
    """mesh_devices=1 (the off switch): a device query builds no mesh. And
    there is no mesh module to import: the stages over a mesh are the single
    chip's (ops/stage.py, ops/grouped_stage.py, ops/device_join.py with
    ``mesh_devices`` > 1); the fused mesh join tier and its steps are gone."""
    from daft_tpu.parallel import distributed

    distributed._MESH_CACHE.clear()
    df = daft_tpu.from_pydict({"k": ["a", "b"] * 50, "v": list(range(100))})
    with execution_config_ctx(device_mode="on", mesh_devices=1):
        df.groupby("k").agg(col("v").sum().alias("s")).to_pydict()
        df.agg(col("v").sum().alias("s")).to_pydict()
    assert not distributed._MESH_CACHE, "a mesh was built with the mesh disabled"
    assert importlib.util.find_spec("daft_tpu.ops.mesh_stage") is None
    for gone in ("sharded_gather_step", "sharded_join_agg_step", "_joined_cols",
                 "_keep_mask", "sharded_join_ungrouped_stage_step",
                 "sharded_join_grouped_stage_step"):
        assert not hasattr(distributed, gone), gone


# ---- EXPLAIN ANALYZE -----------------------------------------------------------------


def test_explain_analyze_renders_mesh_line():
    df = daft_tpu.from_pydict({"k": (np.arange(2000) % 4).tolist(),
                               "v": np.arange(2000, dtype=float).tolist()})
    with execution_config_ctx(device_mode="on", mesh_devices=8):
        report = (df.groupby("k").agg(col("v").sum().alias("s"))
                  .explain_analyze())
    assert "mesh: 8 devices" in report
    assert "mesh_dispatches" in report  # engine-counter delta table

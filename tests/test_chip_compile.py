"""Ask the v5e's own compiler, with no chip attached, whether it accepts the
device programs of the TPC-H main path at SF10 shapes.

Interpret mode cannot show what Mosaic or the TPU's 64-bit rewriter refuse
(an int64 block index, a python-int constant, a slice that cuts the tiling,
a 64-bit bitcast): these compiles can. This is the only file that describes
the chip, and it does so inside a fixture: only one process may hold libtpu,
and every xdist worker imports every test file. Nothing runs here, so a pass
says "lowers", never "right" or "fast".
"""

import os
import re

import numpy as np
import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp

from daft_tpu.utils import jax_setup  # noqa: F401,E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from daft_tpu.ops import pallas_kernels as pk  # noqa: E402

# SF10 shapes: the Pallas phase of chip_smoke.py feeds 2^20-row buckets; the
# suppkey grouping has 100,000 groups (cap 2^17) and 13 digit/count planes;
# orders and part pad to 2^24 and 2^21 probe slots.
ROWS = 1 << 20
PLANES = 13


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu here: nothing to ask
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep it out
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes, **static):
    compiled = fn.lower(*shapes, **static).compile()
    return compiled.as_text()


def _s(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("cap", [2048, 8192, pk.PALLAS_MAX_SEGMENTS])
def test_windowed_segment_sum_lowers(one_chip, cap):
    text = _compile(pk.segment_sum_planes_windowed,
                    _s(one_chip, (ROWS, PLANES), jnp.float32),
                    _s(one_chip, (ROWS,), jnp.int32), cap=cap)
    assert "tpu_custom_call" in text
    # the cross-window combine outside the kernel stays f64
    assert "f64" in text


@pytest.mark.parametrize("op", ["min", "max"])
def test_segment_extremes_lower(one_chip, op):
    text = _compile(pk.segment_extreme_planes,
                    _s(one_chip, (ROWS, 2), jnp.float32),
                    _s(one_chip, (ROWS,), jnp.int32),
                    cap=pk.PALLAS_MAX_SEGMENTS, op=op)
    assert "tpu_custom_call" in text


def test_int64_extremes_lower(one_chip):
    text = _compile(pk.segment_extreme_int64,
                    _s(one_chip, (ROWS,), jnp.int64),
                    _s(one_chip, (ROWS,), jnp.bool_),
                    _s(one_chip, (ROWS,), jnp.int32),
                    cap=pk.PALLAS_MAX_SEGMENTS, op="max")
    assert text.count("tpu_custom_call") >= 3  # one launch per digit plane


@pytest.mark.parametrize("slots", [1 << 17, 1 << 21, 1 << 24],
                         ids=["supplier", "part", "orders"])
def test_hash_probe_index_lowers(one_chip, slots):
    fact = _s(one_chip, (ROWS,), jnp.int32)
    key = _s(one_chip, (1, slots), jnp.int32)
    text = _compile(pk.hash_probe_index, fact, fact, key, key,
                    _s(one_chip, (1, slots), jnp.float32))
    assert "tpu_custom_call" in text


def test_fused_probe_reduce_lowers(one_chip):
    slots, planes = 1 << 21, 4
    fact = _s(one_chip, (ROWS,), jnp.int32)
    key = _s(one_chip, (1, slots), jnp.int32)
    text = _compile(pk.hash_probe_segment_sum, fact, fact, fact, key, key,
                    _s(one_chip, (1, slots), jnp.float32),
                    _s(one_chip, (slots, planes), jnp.float32), cap=128)
    assert "tpu_custom_call" in text


def test_q6_filter_agg_program_lowers_at_8m_rows(one_chip):
    """The flagship fused filter-aggregate (TPC-H Q6) at an 8M-row bucket,
    f64 as the engine runs it."""
    import __graft_entry__ as graft

    fn, _example = graft.entry()
    n = 1 << 23
    col = (_s(one_chip, (n,), jnp.float64), _s(one_chip, (n,), jnp.bool_))
    compiled = jax.jit(fn).lower(*col, *col, *col).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= 3 * n * 8


def test_ring_permute_lowers_on_four_chip_mesh(topo):
    """The in-kernel ICI ring permute inside its shard_map repartition
    program: one Mosaic kernel, no standalone all-to-all."""
    from daft_tpu.parallel.distributed import sharded_ring_repartition_step

    mesh = Mesh(np.array(topo.devices), ("dp",))
    sharded = NamedSharding(mesh, P("dp"))
    total = 4 * 4096
    # f64 planes cross as their uint64 host view (executor._mesh_repartition_exchange)
    dtypes = [np.int64, np.bool_, np.uint64, np.bool_, np.int32, np.bool_]
    step = sharded_ring_repartition_step(mesh, dtypes)
    shapes = [_s(sharded, (total,), np.int64), _s(sharded, (total,), np.bool_)]
    shapes += [_s(sharded, (total,), dt) for dt in dtypes]
    text = step.lower(*shapes).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert "all-to-all" not in text


@pytest.mark.parametrize("dtype", [np.int64, np.float64, np.int32])
def test_mesh_extreme_collective_lowers(topo, dtype):
    """The cross-shard min/max of the mesh grouped and join steps. The chip's
    compiler lowers a 64-bit all-reduce for sums only, so _pextreme must not
    hand it a 64-bit pmin/pmax."""
    from daft_tpu.parallel import distributed as dist

    mesh = Mesh(np.array(topo.devices), ("dp",))

    def local(x):
        return (dist._pextreme(jnp.max(x), "dp", is_min=False),
                dist._pextreme(jnp.min(x, keepdims=True), "dp", is_min=True))

    step = jax.jit(dist._shard_map(local, mesh, (P("dp"),), (P(), P())))
    step.lower(_s(NamedSharding(mesh, P("dp")), (4 * 1024,), dtype)).compile()


# ---- the sharded stages at tpch-sf30-4chip's shapes --------------------------------------
# 180 M lineitem rows over four chips: a 2^26-row bucket a chip, the bucket
# one chip pads SF10 to, so every chip runs the one-chip cell's tile shape.
SHARD_ROWS = 1 << 26
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "collective-permute",
               "all-to-all")


def _literal_args(stage, sharding):
    """The stage's literal argument (one uint32 array of the values' words;
    a float64 stage's float64 values in a second), whole on every device of
    `sharding`."""
    return tuple(_s(sharding, shape, dt) for shape, dt in stage.slots.arg_shapes())


def _lineitem_planes(stage, sharding, rows):
    ints = {"l_shipdate", "l_suppkey"}
    return {name: (_s(sharding, (rows,), jnp.int32 if name in ints else jnp.float32),
                   _s(sharding, (rows,), jnp.bool_)) for name in stage._input_cols}


def test_sharded_q1_program_is_the_single_chips_on_every_shard(topo, one_chip):
    """q1's grouped program over the 2x2 mesh at SF30: what each chip holds
    and runs is what the one chip holds and runs at SF10 (same arguments a
    device, the same [16, 512, 128] tiles, no temporary as long as the
    rows), and no collective: the shards' tables leave as they are."""
    import test_grouped_stage_program as tg

    stage = tg._q1_stage()
    mesh = Mesh(np.array(topo.devices), ("dp",))
    rows = NamedSharding(mesh, P("dp"))
    total = 4 * SHARD_ROWS
    over = stage._build(8, radices=(2, 1), mesh=mesh).lower(
        _lineitem_planes(stage, rows, total),
        (_s(rows, (total,), jnp.int32),) * 2, _s(rows, (total,), jnp.bool_),
        _literal_args(stage, NamedSharding(mesh, P()))).compile()
    single = stage._build(8, radices=(2, 1)).lower(
        _lineitem_planes(stage, one_chip, SHARD_ROWS),
        (_s(one_chip, (SHARD_ROWS,), jnp.int32),) * 2,
        _s(one_chip, (SHARD_ROWS,), jnp.bool_), _literal_args(stage, one_chip)).compile()
    mem, mem1 = over.memory_analysis(), single.memory_analysis()
    assert mem.argument_size_in_bytes == mem1.argument_size_in_bytes
    assert mem.temp_size_in_bytes <= max(mem1.temp_size_in_bytes, 1 << 20)
    # one [cap, planes] f64 table and its [cap] companions a shard
    assert mem.output_size_in_bytes < 64 * 1024
    text = over.as_text()
    assert not [c for c in COLLECTIVES if c in text]
    assert "[1,16,512,128]" in text and "f64[67108864]" not in text \
        and "s64[67108864]" not in text


def test_sharded_q6_program_lowers_without_a_collective(topo):
    import datetime

    import test_grouped_stage_program as tg
    from daft_tpu import col, lit
    from daft_tpu.ops.stage import try_build_filter_agg_stage

    def day(y, m, d):
        return lit(datetime.date(y, m, d))

    pred = ((col("l_shipdate") >= day(1994, 1, 1)) & (col("l_shipdate") < day(1995, 1, 1))
            & (col("l_discount") >= 0.05) & (col("l_discount") <= 0.07)
            & (col("l_quantity") < 24))
    stage = try_build_filter_agg_stage(
        tg._schema(), pred, [(col("l_extendedprice") * col("l_discount")).sum().alias("revenue")])
    assert stage is not None and not stage._use_f64
    mesh = Mesh(np.array(topo.devices), ("dp",))
    rows = NamedSharding(mesh, P("dp"))
    total = 4 * SHARD_ROWS
    compiled = stage._build(mesh).lower(
        _lineitem_planes(stage, rows, total), _s(rows, (total,), jnp.bool_),
        _literal_args(stage, NamedSharding(mesh, P()))).compile()
    mem = compiled.memory_analysis()
    # four columns' f32 planes and validity, and the row mask, a shard; and
    # the five literals' values whole, as one array of 32-bit words (two
    # dates, two floats, and two words of an int64), padded to the chip's
    # least allocation
    assert stage.slots.arg_shapes() == (((6,), np.dtype("uint32")),)
    assert 0 < mem.argument_size_in_bytes - SHARD_ROWS * (4 * 5 + 1) <= 1024
    text = compiled.as_text()
    assert not [c for c in COLLECTIVES if c in text]
    assert "f64[67108864]" not in text


# ---- the run-wide join TopN at tpch-sf10-joins-1chip's shapes ------------------------------
# q3 over 458 batches of 131,072 lineitem rows: the group ids are orders' rows
# (15 M, padded to 2^24), one set of tables for the run.
JOIN_BATCH = 1 << 17
ORDERS_CAP = 1 << 24


def _q3_join_stage():
    """The stage of a q3-shaped star join (built from tables of a few rows:
    a stage is its expressions' structure, not its data)."""
    import datetime

    import daft_tpu
    from daft_tpu import col
    from daft_tpu.ops.device_join import build_join_stage, try_capture_join_topn

    day = datetime.date(1995, 3, 15)
    t = {"customer": {"c_custkey": [1, 2], "c_mktsegment": ["BUILDING", "X"]},
         "orders": {"o_orderkey": [1, 2], "o_custkey": [1, 2], "o_orderdate": [day, day],
                    "o_shippriority": [0, 0]},
         "lineitem": {"l_orderkey": [1, 2, 2], "l_extendedprice": [1.0, 2.0, 3.0],
                      "l_discount": [0.0, 0.1, 0.2], "l_shipdate": [day, day, day]}}
    t = {n: daft_tpu.from_pydict(c) for n, c in t.items()}
    q = (t["customer"].where(col("c_mktsegment") == "BUILDING")
         .join(t["orders"], left_on="c_custkey", right_on="o_custkey")
         .where(col("o_orderdate") < day)
         .join(t["lineitem"], left_on="o_orderkey", right_on="l_orderkey")
         .where(col("l_shipdate") > day)
         .groupby(col("o_orderkey").alias("l_orderkey"), "o_orderdate", "o_shippriority")
         .agg((col("l_extendedprice") * (1 - col("l_discount"))).sum().alias("revenue"))
         .sort(["revenue", "o_orderdate"], desc=[True, False]).limit(10))
    spec, topn, _out = try_capture_join_topn(q._builder.optimize()._plan)
    stage, grouped = build_join_stage(spec)
    assert grouped and stage.run_wide_reason() is None
    return stage, topn


def _run_wide_tables(stage, sharding, cap):
    shapes = jax.eval_shape(lambda: stage.run_wide_tables(cap))
    return jax.tree_util.tree_map(lambda x: _s(sharding, x.shape, x.dtype), shapes)


# the accumulate program's branches, as jax names them in an operation's
# metadata: cond(dense, dense_form, cond(few kept, compact_form, scatter_form))
_SCATTER_FORM = "cond/branch_0_fun/cond/branch_0_fun/"
_COMPACT_FORM = "cond/branch_0_fun/cond/branch_1_fun/"


def _scatter_index_counts(text, branch):
    """How many indices each scatter of one branch of a compiled program takes."""
    counts = []
    for ops, rest in re.findall(r" scatter\(([^)]*)\)(.*)", text):
        if branch not in rest:
            continue
        indices = ops.split(",")[1].strip().lstrip("%")
        shape = re.search(r"%" + re.escape(indices) + r" = s32\[(\d+)", text)
        counts.append(int(shape.group(1)))
    return counts


def _assert_compacts_without_sort_or_scan(text, n_planes):
    """The accumulate program's sparse forms, as the chip's compiler left
    them: the scatter form's scatters (a plane each and the first-row table)
    take a batch's 131,072 indices, the compact form's K = a sixteenth of
    them; the compaction itself is compares, gathers of whole lines of 128 lanes
    and selects: no sort and no loop (the compiler sorts the scatter
    form's indices itself where the table outgrows fast memory, and the dense
    form scans its chunks: neither is the compact form's)."""
    from daft_tpu.ops.grouped_stage import COMPACT_SHARE

    k = JOIN_BATCH // COMPACT_SHARE
    assert _scatter_index_counts(text, _SCATTER_FORM) == [JOIN_BATCH] * (n_planes + 1)
    assert _scatter_index_counts(text, _COMPACT_FORM) == [k] * (n_planes + 1)
    mine = [line for line in text.splitlines() if _COMPACT_FORM in line]
    assert not [line for line in mine if re.search(r" (sort|while)\(", line)]
    gathers = [re.search(r"= s32\[([\d,]+)\]", line).group(1)
               for line in mine if " gather(" in line]
    # (a row's place, its id and each value plane: the k lines of each)
    assert gathers == [f"{k},128"] * (n_planes + 2), gathers


# the dense form, and inside it the masked minimum that finds the first rows
# of a segment whose ids are in no order: cond(dense, dense_form, ...) and
# cond(ordered, nothing, firsts_by_minimum)
_DENSE_FORM = re.compile(r'op_name="(?:(?!cond/)[^"])*cond/branch_1_fun/')   # (the outermost cond's)
_UNORDERED_FIRSTS = "cond/branch_1_fun/cond/branch_0_fun/"


def _assert_dense_form_in_two_digits(text, n_planes):
    """The accumulate program's dense form, as the chip's compiler left it: a
    chunk's product is [terms x 32 high digits, 4,096 rows] x [rows, 128 low
    digits], three bfloat16 terms a plane and three more columns for the
    first rows that ride it; an operand of 4,096 x 4,096 cells exists for the
    masked minimum of a segment whose ids are in no order, and nowhere else."""
    from daft_tpu.ops.grouped_stage import CHUNK_LOCAL, DENSE_DIGITS

    high, low = DENSE_DIGITS
    assert (high, low) == (32, 128) and high * low == CHUNK_LOCAL
    whole = [line for line in text.splitlines() if f"[{CHUNK_LOCAL},{CHUNK_LOCAL}]" in line]
    assert whole and all(_UNORDERED_FIRSTS in line for line in whole)
    products = [re.search(r"= f32\[([\d,]+)\]", line).group(1) for line in text.splitlines()
                if " convolution(" in line and _DENSE_FORM.search(line)]
    assert products and set(products) == {f"{(3 * n_planes + 3) * high},{low}"}, products


def _assert_folds_once_a_dispatch(text, length, n_planes):
    """The table-long two-sums of a dispatch of eight segments, as the chip's
    compiler left them (a two-sum is where `is-finite` is taken): none over
    the tables' length inside the segment loop's body, whose sparse forms
    scatter into the dispatch's partial and leave the sums alone, and one a
    plane after the loop, in the branch that only a dispatch which wrote the
    partial takes; and no table copied whole anywhere (a branch that handed
    the partial on untouched was given a copy of it, a segment: the dense
    form writes one element of it)."""
    long = [line for line in text.splitlines()
            if " is-finite(" in line and f"[{length}]" in line]
    assert len(long) == n_planes, long
    assert not [line for line in long if "/while/body/" in line]
    # (over a mesh the branch stands inside the shard's scope)
    after = re.compile(r'op_name="jit\((?:stage|on_shard)\)/(?:shard_map/)?cond/branch_1_fun/is_finite"')
    assert all(after.search(line) for line in long), long
    assert not re.findall(rf"= [fs]32\[{length}\]\S* copy\(", text)


# a join dispatch over a resident fact: DISPATCH_SEGMENTS morsels' rows (PR 43)
SEGMENTS = [1, 8]
SEGMENT_IDS = ["one_bucket", "eight_segments"]


@pytest.mark.parametrize("segments", SEGMENTS, ids=SEGMENT_IDS)
@pytest.mark.parametrize("cap", [1 << 21, ORDERS_CAP], ids=["customer_ids", "order_ids"])
def test_run_wide_topn_accumulate_lowers_at_sf10(one_chip, cap, segments):
    """One dispatch of the run-wide program: a batch of 131,072 rows, or
    eight such segments walked one after the other, into tables of 2^21
    customer ids (q10's) and 2^24 order ids (q3's), the three forms in one
    program, the tables donated (no second copy of them among the
    temporaries, loop or no loop: the sparse forms scatter into the
    dispatch's partial, a leaf of the tables, so not even one plane's),
    a segment's scatters and compaction the length they had when it was a
    dispatch of its own, and the table-long two-sums once a dispatch."""
    stage, _topn = _q3_join_stage()
    tables = _run_wide_tables(stage, one_chip, cap)
    ints = {"l_shipdate"}
    rows = segments * JOIN_BATCH
    cols = {name: (_s(one_chip, (rows,), jnp.bool_ if name == "__join_ok__"
                      else jnp.int32 if name in ints else jnp.float32),
                   _s(one_chip, (rows,), jnp.bool_)) for name in stage._input_cols}
    compiled = stage._build_run_wide(cap, segment=JOIN_BATCH).lower(
        tables, cols, _s(one_chip, (rows,), jnp.int32),
        _s(one_chip, (rows,), jnp.bool_), _literal_args(stage, one_chip)).compile()
    mem = compiled.memory_analysis()
    table_bytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                      for x in jax.tree_util.tree_leaves(tables))
    # (hi, lo and the dispatch's partial a plane, the first rows; the counts
    # of dense, compacted and ordered segments and of folds are the + 16;
    # without the partial and the last three the leaves are the select
    # program's: PR 42's, unchanged)
    assert sorted(tables) == ["compact", "dense", "first", "folds", "hi", "lo", "ordered", "part"]
    assert table_bytes == (len(stage._mm_specs) * 3 * 4 + 4) * (cap + 4096) + 16
    assert mem.argument_size_in_bytes >= table_bytes
    # never a copy of the run's tables, and no table of a scatter form's own:
    # the temporaries are a segment's, under ONE plane of the tables
    assert mem.temp_size_in_bytes < 4 * (cap + 4096)
    assert mem.alias_size_in_bytes >= table_bytes - 16
    text = compiled.as_text()
    _assert_compacts_without_sort_or_scan(text, len(stage._mm_specs))
    _assert_dense_form_in_two_digits(text, len(stage._mm_specs))
    if segments > 1:
        _assert_folds_once_a_dispatch(text, cap + 4096, len(stage._mm_specs))


def test_run_wide_topn_select_lowers_at_sf10(one_chip):
    """q3's finalize over tables of 2^24 ids: sorts of blocks of 256 that each
    hand on their first 10, level after level, then one sort of 1,000
    survivors, never a sort of 2^24."""
    from daft_tpu.ops.device_join import select_top

    def select(absent, revenue, rank, first):
        gid = jnp.arange(ORDERS_CAP, dtype=jnp.int32)
        return select_top((absent, -revenue, rank, first, gid), 4, 10)

    args = (_s(one_chip, (ORDERS_CAP,), jnp.int32), _s(one_chip, (ORDERS_CAP,), jnp.float64),
            _s(one_chip, (ORDERS_CAP,), jnp.int32), _s(one_chip, (ORDERS_CAP,), jnp.int32))
    compiled = jax.jit(select).lower(*args).compile()
    text = compiled.as_text()
    assert "sort" in text
    assert compiled.memory_analysis().output_size_in_bytes < 4096


def test_the_select_program_takes_the_four_leaves_it_took(one_chip):
    """The one chip's select program is traced over the sums, the first rows
    and the dense count, as before the tables counted anything else: what
    the accumulate program returns beside them (`compact`, `ordered`) is
    popped before the call, so the select's text, and with it its key in the
    persistent cache (25-28 s of compile at SF10), stands."""
    import daft_tpu.ops.device_join as dj
    from daft_tpu.ops.grouped_stage import _RUN_WIDE_COUNTS

    stage, topn = _q3_join_stage()

    class _Ctx:     # what _select_program reads of the join context
        mesh = None

        def _mesh_key(self):
            return ()

    run = dj.DeviceJoinTopNRun.__new__(dj.DeviceJoinTopNRun)
    run.stage, run._cap, run.topn, run.mesh_devices, run.ctx = \
        stage, ORDERS_CAP, topn, 1, _Ctx()
    tables = _run_wide_tables(stage, one_chip, ORDERS_CAP)
    assert set(tables) - {"hi", "lo", "first", "part"} == set(_RUN_WIDE_COUNTS)
    for leaf in ("compact", "ordered", "folds", "part"):
        tables.pop(leaf)
    ranks = tuple(_s(one_chip, (ORDERS_CAP,), jnp.int32)
                  for kind, *_rest in topn.keys if kind == "group")
    lowered = run._select_program(10).lower(tables, ranks)
    (handed, _ranks), _kw = lowered.in_tree.unflatten(
        list(range(lowered.in_tree.num_leaves)))
    assert sorted(handed) == ["dense", "first", "hi", "lo"]
    # hi and lo a plane each, first, dense, and the rank planes
    assert lowered.in_tree.num_leaves == 2 * len(stage._mm_specs) + 2 + len(ranks)


def test_bfloat16_terms_survive_the_chips_compiler(one_chip):
    """The three bfloat16 terms of a float32 are cut with reduce_precision,
    which the chip's compiler keeps; a float32 -> bfloat16 -> float32 round
    trip it may drop as excess precision, and then the first term is the
    whole value and the other two are zero (q3 read 1.1e-3 off, PR 38)."""
    from daft_tpu.ops.grouped_stage import _bfloat16_terms

    compiled = jax.jit(_bfloat16_terms).lower(_s(one_chip, (4096, 3), jnp.float32)).compile()
    assert compiled.as_text().count("reduce-precision(") >= 2


def _gather_operand_shapes(text):
    """The shape of what each gather of an optimized HLO reads from."""
    import re

    out = []
    for block in text.split("\n\n"):
        shapes = {}
        for line in block.splitlines():
            m = re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = \w+\[([\d,]*)\]", line)
            if m:
                shapes[m.group(1)] = [int(d) for d in m.group(2).split(",") if d]
        for line in block.splitlines():
            m = re.search(r" gather\((%[\w.\-]+),", line)
            if m:
                out.append(tuple(shapes[m.group(1)]))
    return out


@pytest.mark.parametrize("segments", SEGMENTS, ids=SEGMENT_IDS)
@pytest.mark.parametrize("rows", [6, 1], ids=["q5_six_rows", "q3_q10_one_row"])
def test_windowed_provisioning_gathers_from_a_batch_long_window(one_chip, rows, segments):
    """The join's provisioning program at `tpch_sf10.joins`' shapes: `orders`'
    pack of [6, 2^24] (q5) or [1, 2^24] (q3, q10) and 131,072 indices, or
    eight segments of as many, each gathered from a window of its own. Where
    the batch's verdict says window, no gather of the optimized program reads
    from 2^24 rows: the slice stays apart from the gather (a compiler that
    folded it back would leave the dispatch at its 3.3 ms, and no CPU test
    would see it), and the one-row pack is gathered as rows of 128 lanes. The
    plain program of the same layout is the control."""
    import dataclasses

    from daft_tpu.ops.device_join import _ProvisionLayout, _provision_program

    if rows == 6:       # a value with its validity, a code row, the ok row; `supplier` beside it
        layout = _ProvisionLayout(
            packs=(5, 2), windows=(True, False),
            columns=(("c_nationkey", 0, (0,), 1), ("o_total", 0, (2,), 3),
                     ("s_nationkey", 1, (0,), 1)),
            codes=((0, 4, 1),), cap=32, segment=JOIN_BATCH)
        mats = (_s(one_chip, (6, ORDERS_CAP), jnp.float32),
                _s(one_chip, (3, JOIN_BATCH), jnp.float32))
    else:
        layout = _ProvisionLayout(packs=(0,), windows=(True,), columns=(), codes=(), cap=0,
                                  segment=JOIN_BATCH)
        mats = (_s(one_chip, (1, ORDERS_CAP), jnp.float32),)
    idxs = tuple(_s(one_chip, (segments * JOIN_BATCH,), jnp.int32) for _ in mats)
    windowed = _gather_operand_shapes(_compile(_provision_program(layout), mats, idxs, ()))
    plain = _gather_operand_shapes(_compile(_provision_program(dataclasses.replace(
        layout, windows=(False,) * len(mats))), mats, idxs, ()))
    # a windowed gather a segment; the plain ones take the dispatch's indices at once
    assert len(plain) == len(mats) and len(windowed) == segments + len(mats) - 1
    assert max(max(shape) for shape in plain) == ORDERS_CAP, \
        "the control: the plain gather reads the whole pack"
    assert all(int(np.prod(shape)) <= rows * JOIN_BATCH for shape in windowed), windowed
    if rows == 1:   # rows of one lane width, not 131,072 single values (0.93 ms on the chip)
        assert windowed == [(JOIN_BATCH // 128, 128)] * segments


def _star_join_layout(one_chip, query):
    """(layout, packs) of q3's, q10's or q5's provisioning program at
    `tpch_sf10.joins`' shapes, as `_JoinContext._provision` makes them: every
    fact-adjacent dimension longer than a window is gathered windowed."""
    from daft_tpu.ops.device_join import _ProvisionLayout

    if query == "q5":
        return _ProvisionLayout(
            packs=(5, 2), windows=(True, False),
            columns=(("c_nationkey", 0, (0,), 1), ("o_total", 0, (2,), 3),
                     ("s_nationkey", 1, (0,), 1)),
            codes=((0, 4, 1),), cap=32, segment=JOIN_BATCH), (
            _s(one_chip, (6, ORDERS_CAP), jnp.float32),
            _s(one_chip, (3, JOIN_BATCH), jnp.float32))
    return _ProvisionLayout(packs=(0,), windows=(True,), columns=(), codes=(), cap=0,
                            segment=JOIN_BATCH), (_s(one_chip, (1, ORDERS_CAP), jnp.float32),)


@pytest.mark.parametrize("query", ["q3", "q5", "q10"])
def test_the_star_joins_provisioning_gathers_a_packs_rows_together(one_chip, query):
    """q3's, q5's and q10's provisioning programs at a dispatch of 2^20 rows
    (eight segments): a windowed dimension's rows are gathered TOGETHER, a
    segment at a time, out of its window, and no plane of the dispatch's
    length is re-laid on the way. PR 48's tree took the rows out of their
    packs and gathered them a plane at a time for the sake of an unordered
    dimension, and the ledger read it as two dispatch-long copies of a
    `[1, 1048576]` plane and +1.93 ms a dispatch in the three join cells:
    what an unordered dimension needs is chosen for THAT dimension, and a
    layout whose dimensions are all windowed keeps this program."""
    layout, mats = _star_join_layout(one_chip, query)
    idxs = tuple(_s(one_chip, (8 * JOIN_BATCH,), jnp.int32) for _ in mats)
    from daft_tpu.ops.device_join import _provision_program

    text = _compile(_provision_program(layout), mats, idxs, ())
    relaid = re.findall(r"= (\w+)\[1,1048576\]\{[^}]*\} copy\(", text)
    # (q5's `supplier` pack is shorter than a window: its three rows are
    # gathered whole and handed on a row each, as they were)
    assert relaid == (["f32"] * 3 if query == "q5" else []), relaid
    read = _gather_operand_shapes(text)
    if query == "q5":
        # six rows of `orders`' window a segment, and `supplier`'s short pack whole
        assert sorted(read) == sorted([(6, JOIN_BATCH)] * 8 + [(3, JOIN_BATCH)])
    else:       # the one-row pack as rows of a lane width, a segment at a time
        assert read == [(JOIN_BATCH // 128, 128)] * 8


PART_CAP = 1 << 21      # part's 2 M rows at SF10, padded (customer's 1.5 M too)


def _unwindowed_layout(query, lines):
    """q14's or q19's provisioning layout at `tpch_sf10.filtered_joins`'
    shapes and the rows of `part`'s pack it gathers from: [2, 2^21] for q14's
    prefix flag, [16, 2^21] for q19's six flags, `p_size` in two digits and
    the verdict."""
    from daft_tpu.ops.device_join import _ProvisionLayout

    if query == "q14":
        return 2, _ProvisionLayout(
            packs=(None,), windows=(False,), columns=(("promo", 0, (0,), 1),), codes=(),
            cap=0, segment=JOIN_BATCH, lines=(2,) if lines else ())
    flags = tuple((f"flag{i}", 0, (at,), at + 1) for i, at in enumerate((0, 2, 7, 9, 11, 13)))
    return 16, _ProvisionLayout(
        packs=(15,), windows=(False,), columns=flags[:2] + (("p_size", 0, (4, 5), 6),)
        + flags[2:], codes=(), cap=0, segment=JOIN_BATCH, lines=(16,) if lines else ())


def test_an_unordered_dimensions_pack_is_gathered_whole_from_fast_memory_where_it_fits(one_chip):
    """`l_partkey` is uniform over `part`, so a dispatch's 2^20 indices fit no
    window and the gather reads `part`'s whole pack. q14's (16 MB) the chip's
    compiler moves into fast memory ahead of the gather, and so it does any
    pack up to `_FAST_PACK_BYTES`; q19's (128 MB) it reads from HBM, which is
    why that one is laid as lines (`_JoinContext._gathers_lines`)."""
    from daft_tpu.ops.device_join import _FAST_PACK_BYTES, _provision_program

    idxs = (_s(one_chip, (8 * JOIN_BATCH,), jnp.int32),)
    for query, prefetched in (("q14", True), ("q19", False)):
        rows, layout = _unwindowed_layout(query, lines=False)
        assert (4 * rows * PART_CAP <= _FAST_PACK_BYTES) == prefetched
        text = _compile(_provision_program(layout), (_s(one_chip, (rows, PART_CAP), jnp.float32),),
                        idxs, ())
        assert ("cross_program_prefetch_index" in text) == prefetched, query
        assert max(max(shape) for shape in _gather_operand_shapes(text)) == PART_CAP
    # the threshold itself: the longest pack of `part`'s length that is still moved
    gather = jax.jit(lambda mat, idx: mat[:, jnp.clip(idx, 0, PART_CAP - 1)])
    for rows in (8, 12):
        text = _compile(gather, _s(one_chip, (rows, PART_CAP), jnp.float32), idxs[0])
        assert ("cross_program_prefetch_index" in text) == (4 * rows * PART_CAP <= _FAST_PACK_BYTES)


def test_a_pack_laid_as_lines_lowers_at_sf10(one_chip):
    """q19's provisioning program at `tpch_sf10.filtered_joins`' shapes, its
    pack laid as lines of one lane width with a dimension row's values side
    by side (`_pack_lines`). The chip's compiler accepts both programs: the
    one that lays the lines (once a query, a piece at a time: it holds no
    whole padded transpose) and the one that gathers a line an index, a
    segment at a time, and hands on what the layout names."""
    from daft_tpu.ops.device_join import (_LINES_PIECE, _lane_width, _pack_lines,
                                          _provision_program)

    rows, layout = _unwindowed_layout("q19", lines=True)
    laid = _pack_lines.lower(_s(one_chip, (rows, PART_CAP), jnp.float32)).compile()
    lines_shape = (PART_CAP * _lane_width(rows) // 128, 128)
    memory = laid.memory_analysis()
    assert memory.output_size_in_bytes == 4 * lines_shape[0] * 128
    # a piece's rows padded to a lane width and the pieces before they are glued
    assert memory.temp_size_in_bytes <= memory.output_size_in_bytes + 2 * 4 * _LINES_PIECE * 128
    mats = (_s(one_chip, lines_shape, jnp.float32),)
    idxs = (_s(one_chip, (8 * JOIN_BATCH,), jnp.int32),)
    compiled = _provision_program(layout).lower(mats, idxs, ()).compile()
    # eight gathers of a segment's lines, each out of the whole pack
    assert _gather_operand_shapes(compiled.as_text()) == [lines_shape] * 8
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes == 4 * (lines_shape[0] * 128 + 8 * JOIN_BATCH)
    # (the gathered lines of the segments, and the joined columns with their validity)
    assert memory.temp_size_in_bytes + memory.output_size_in_bytes \
        <= 9 * 4 * JOIN_BATCH * 128 + 2 * 4 * rows * 8 * JOIN_BATCH


CUSTOMER_CAP = 1 << 21      # customer's 1.5 M rows at SF10, padded


@pytest.mark.parametrize("query", ["q3", "q5"])
def test_visibility_program_is_one_pass_over_the_dimension(one_chip, query):
    """The join's visibility program at `tpch_sf10.adhoc_joins`' shapes: the
    filters of q3's `orders` subtree (`o_orderdate < DATE` and `customer`'s
    `c_mktsegment == SEGMENT`, its codes carried to `orders`' rows) or q5's
    (two dates and `region`'s `r_name == REGION`, three links down), their
    values one small argument. It compiles for the chip with NO gather (the
    chain is walked when the carried planes are built, not a query) and gives
    one float32 plane as long as `orders` padded."""
    import datetime
    import types

    from daft_tpu import col, lit
    from daft_tpu.datatype import DataType, Field
    from daft_tpu.device.residency import exprs_structure
    from daft_tpu.expressions.expressions import BinaryOp, ColumnRef, Literal
    from daft_tpu.ops import device_join as dj
    from daft_tpu.schema import Schema

    day = lit(datetime.date(1995, 3, 15))
    code = dj._code_column("c_mktsegment" if query == "q3" else "r_name")
    by_code = BinaryOp("eq", ColumnRef(code), Literal(2, DataType.int32()))
    dates = col("o_orderdate") < day if query == "q3" \
        else (col("o_orderdate") >= day) & (col("o_orderdate") < day)
    filters = [dates, by_code]
    orders = dj.DimSpec(base=types.SimpleNamespace(schema=Schema(
        [Field("o_orderdate", DataType.date()), Field(code, DataType.int32())])),
        filters=filters, key_col="o_orderkey", parent=("fact", "l_orderkey"), name="d0")
    program, slots = dj._visibility_program(filters, exprs_structure(filters), [orders])
    plane = lambda dt: _s(one_chip, (ORDERS_CAP,), dt)
    cols = {"o_orderdate": (plane(jnp.float32), plane(jnp.bool_)),
            code: (plane(jnp.int32), None)}
    lit_args = tuple(jax.ShapeDtypeStruct(shape, dt) for shape, dt in slots.arg_shapes())
    compiled = program.lower(cols, plane(jnp.bool_), None, lit_args).compile()
    text = compiled.as_text()
    assert " gather(" not in text
    assert slots.n_args == (2 if query == "q3" else 3)
    assert compiled.memory_analysis().output_size_in_bytes == 4 * ORDERS_CAP


# ---- the join dispatch on every shard of the 2x2 mesh, at tpch_sf30_mesh4.joins' shapes ----

ORDERS_CAP_SF30 = 1 << 26     # orders' 45 M rows, padded
MESH_CHIPS = 4


def _mesh_shardings(topo):
    mesh = Mesh(np.array(topo.devices), ("dp",))
    return mesh, NamedSharding(mesh, P("dp")), NamedSharding(mesh, P())


def _sharded_run_wide_tables(stage, rows, cap):
    """A set of tables a chip, end to end (GroupedAggStage.run_wide_tables)."""
    length = MESH_CHIPS * (cap + 4096)
    planes = tuple(_s(rows, (length,), jnp.float32) for _ in stage._mm_specs)
    return {"hi": planes, "lo": planes, "first": _s(rows, (length,), jnp.int32),
            "dense": _s(rows, (MESH_CHIPS,), jnp.int32)}


def _sharded_accumulate_tables(stage, rows, cap):
    """What the accumulate program takes and returns: the select's four
    leaves, the dispatches' partial a plane, and the compacted and the
    ordered segments' and the folds' counts beside them."""
    tables = _sharded_run_wide_tables(stage, rows, cap)
    return dict(tables, part=tables["hi"],
                **{count: _s(rows, (MESH_CHIPS,), jnp.int32)
                   for count in ("compact", "ordered", "folds")})


@pytest.mark.parametrize("segments", SEGMENTS, ids=SEGMENT_IDS)
def test_sharded_run_wide_accumulate_lowers_at_sf30(topo, segments):
    """One dispatch of q3's run-wide program over the mesh: 131,072 rows a
    chip, or eight such segments a chip, into a chip's own tables of 2^26
    order ids, donated, and no collective: a dispatch leaves the chips'
    tables apart, and a chip folds its own partial into its own sums, once."""
    stage, _topn = _q3_join_stage()
    mesh, rows, whole = _mesh_shardings(topo)
    total = MESH_CHIPS * segments * JOIN_BATCH
    tables = _sharded_accumulate_tables(stage, rows, ORDERS_CAP_SF30)
    ints = {"l_shipdate"}
    cols = {name: (_s(rows, (total,), jnp.bool_ if name == "__join_ok__"
                      else jnp.int32 if name in ints else jnp.float32),
                   _s(rows, (total,), jnp.bool_)) for name in stage._input_cols}
    compiled = stage._build_run_wide(ORDERS_CAP_SF30, mesh, JOIN_BATCH).lower(
        tables, cols, _s(rows, (total,), jnp.int32), _s(rows, (total,), jnp.bool_),
        _literal_args(stage, whole)).compile()
    mem = compiled.memory_analysis()
    a_chips = (len(stage._mm_specs) * 3 * 4 + 4) * (ORDERS_CAP_SF30 + 4096)
    assert a_chips <= mem.argument_size_in_bytes < a_chips + (64 << 20)
    assert mem.alias_size_in_bytes >= a_chips - 8       # donated: no second copy
    assert mem.temp_size_in_bytes < 4 * (ORDERS_CAP_SF30 + 4096)    # (under one plane)
    text = compiled.as_text()
    assert not [c for c in COLLECTIVES if c in text]
    # a shard's program is the one chip's: its own 131,072 rows, its own K,
    # its own chunks' windows in two digits
    _assert_compacts_without_sort_or_scan(text, len(stage._mm_specs))
    _assert_dense_form_in_two_digits(text, len(stage._mm_specs))
    if segments > 1:
        _assert_folds_once_a_dispatch(text, ORDERS_CAP_SF30 + 4096, len(stage._mm_specs))


def test_sharded_run_wide_combine_and_select_lowers_at_sf30(topo):
    """The run's end on the mesh: every chip's tables of 2^26 ids in, an
    all-to-all that hands a chip its 2^24 ids of each chip's tables, the sum,
    the select over the slice, K rows a chip out. A chip's temporaries stay
    under its tables' size, so tables, temporaries and what stays resident
    fit its 16 GB."""
    import daft_tpu.ops.device_join as dj

    stage, topn = _q3_join_stage()
    mesh, rows, _whole = _mesh_shardings(topo)

    class _Ctx:     # what _select_program reads of the join context
        def _mesh_key(self):
            return ("mesh", MESH_CHIPS, "dp")

    ctx = _Ctx()
    ctx.mesh = mesh
    run = dj.DeviceJoinTopNRun.__new__(dj.DeviceJoinTopNRun)
    run.stage, run._cap, run.topn, run.mesh_devices, run.ctx = \
        stage, ORDERS_CAP_SF30, topn, MESH_CHIPS, ctx
    ranks = tuple(_s(rows, (ORDERS_CAP_SF30,), jnp.int32)
                  for kind, *_rest in topn.keys if kind == "group")
    compiled = run._select_program(10).lower(
        _sharded_run_wide_tables(stage, rows, ORDERS_CAP_SF30), ranks).compile()
    text = compiled.as_text()
    assert "all-to-all" in text and "sort" in text
    mem = compiled.memory_analysis()
    a_chips = (len(stage._mm_specs) * 2 * 4 + 4) * (ORDERS_CAP_SF30 + 4096)
    assert mem.temp_size_in_bytes < a_chips
    assert mem.output_size_in_bytes < 64 * 1024  # K rows and their sort operands a chip


@pytest.mark.parametrize("segments", SEGMENTS, ids=SEGMENT_IDS)
@pytest.mark.parametrize("rows_of_pack", [6, 1], ids=["q5_six_rows", "q3_q10_one_row"])
def test_sharded_provisioning_gathers_from_a_shards_own_window(topo, monkeypatch, rows_of_pack,
                                                               segments):
    """The provisioning program on every shard: `orders`' pack of [6, 2^26]
    (q5) or [1, 2^26] (q3, q10) whole on each chip, 131,072 indices a chip or
    eight segments of as many. A windowed gather reads a window of a
    segment's length, never the 2^26-row pack, and the program runs no
    collective."""
    import daft_tpu.ops.device_join as dj

    mesh, rows, whole = _mesh_shardings(topo)
    monkeypatch.setattr(dj, "local_mesh", lambda n: mesh)
    total = MESH_CHIPS * segments * JOIN_BATCH
    if rows_of_pack == 6:
        layout = dj._ProvisionLayout(
            packs=(5, 2), windows=(True, False),
            columns=(("c_nationkey", 0, (0,), 1), ("o_total", 0, (2,), 3),
                     ("s_nationkey", 1, (0,), 1)),
            codes=((0, 4, 1),), cap=32, devices=MESH_CHIPS, segment=JOIN_BATCH)
        mats = (_s(whole, (6, ORDERS_CAP_SF30), jnp.float32),
                _s(whole, (3, 1 << 19), jnp.float32))
    else:
        layout = dj._ProvisionLayout(packs=(0,), windows=(True,), columns=(), codes=(),
                                     cap=0, devices=MESH_CHIPS, segment=JOIN_BATCH)
        mats = (_s(whole, (1, ORDERS_CAP_SF30), jnp.float32),)
    idxs = tuple(_s(rows, (total,), jnp.int32) for _ in mats)
    try:
        text = _compile(dj._provision_program(layout), mats, idxs, ())
    finally:
        dj._provision_program.cache_clear()     # a program bound to the described mesh
    shapes = _gather_operand_shapes(text)
    assert len(shapes) == segments + len(mats) - 1      # (a windowed gather a segment)
    assert (JOIN_BATCH // 128, 128) in shapes if rows_of_pack == 1 \
        else (6, JOIN_BATCH) in shapes, shapes
    assert max(max(shape) for shape in shapes) < ORDERS_CAP_SF30
    assert not [c for c in COLLECTIVES if c in text]

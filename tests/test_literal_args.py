"""A query's literal values are arguments of the aggregate stages' programs
(ops/device_eval.build_device_expr, ops/stage.py, ops/grouped_stage.py): a new
date or discount is a new argument and not a new XLA program.

On the CPU, `device_mode="on"`, seeded TPC-H data at SF0.02: for every value
of Q1's substitution domain and a seeded sample of Q6's (benchmark/
adhoc_params.py: the specification's), the device answer equals the plain
reference's (benchmark/reference/tpch_adhoc.py, numpy in float64, independent
of daft_tpu) within the ad-hoc configuration's limits, while both stage
caches keep one entry a query shape, the mesh-tier decision cache does not
grow with the values and `device_stage_program_traces` stands still after the
first value; the same over a mesh of host devices; and what is part of a
shape (a literal's dtype, a null literal, the number of an IsIn's items)
against what is not (the items, a literal inside an aggregate's input, a
value equal to a row's).
"""

import datetime
import importlib.util
import json
import os
import random
import sys

import numpy as np
import pytest

import jax

import daft_tpu
from daft_tpu import col, lit
from daft_tpu.config import execution_config_ctx
from daft_tpu.execution import executor
from daft_tpu.ops import counters, grouped_stage, stage

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)  # the suite's files import adhoc_params by name

import adhoc_params  # noqa: E402


def _bench_module(rel):
    name = "literal_test_" + rel.replace("/", "_").replace(".py", "")
    spec = importlib.util.spec_from_file_location(name, os.path.join(BENCH, rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tpch():
    """lineitem at SF0.02 from a seed (about 120,000 rows), loaded; the
    ad-hoc suite's queries and reference, the comparison and the
    configuration's limits."""
    arrow = _bench_module("datagen/tpch.py").generate(0.02, 3400001, ["lineitem"])
    with open(os.path.join(BENCH, "configs", "tpch-sf10-adhoc-1chip.json")) as f:
        config = json.load(f)
    return {"arrow": arrow,
            "tables": {"lineitem": daft_tpu.from_arrow(arrow["lineitem"]).collect()},
            "queries": _bench_module("queries/tpch_adhoc.py"),
            "reference": _bench_module("reference/tpch_adhoc.py"),
            "compare": _bench_module("compare.py"), "config": config}


def _q6_sample(n, seed=34):
    domain = [adhoc_params.Q6(y, d, q) for y in adhoc_params.Q6_YEARS
              for d in adhoc_params.Q6_DISCOUNTS for q in adhoc_params.Q6_QUANTITIES]
    return random.Random(seed).sample(domain, n)


def _values(query, n=None):
    if query == "q1":
        values = [adhoc_params.Q1(d) for d in adhoc_params.Q1_DELTAS]
        return values if n is None else random.Random(34).sample(values, n)
    return _q6_sample(20 if n is None else n)


_COMPILES = [0]


def _on_compile(event, _secs, **_kw):
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILES[0] += 1


jax.monitoring.register_event_duration_secs_listener(_on_compile)


def _caches():
    return (len(stage._STAGE_CACHE), len(grouped_stage._STAGE_CACHE),
            len(executor._MESH_TIER_CACHE), len(executor._DECISION_CACHE))


def _check(tpch, query, params, got):
    ref = tpch["reference"].answer_for(query, params, tpch["arrow"])
    numbers = tpch["compare"].compare(ref, got)
    limits = tpch["compare"].limits(tpch["config"], f"{query}.p00")
    assert tpch["compare"].within(numbers, limits), (params, numbers, limits)


def _run_values(tpch, query, values, mesh_devices):
    """Every value through the device stages; returns (traces, dispatches,
    literal args, cache sizes, XLA compiles of the process) after each."""
    program = getattr(tpch["queries"], query)
    seen = []
    with execution_config_ctx(device_mode="on", device_min_rows=1,
                              mesh_devices=mesh_devices):
        for params in values:
            got = program(tpch["tables"], params).to_pydict()
            _check(tpch, query, params, got)
            seen.append((counters.device_stage_program_traces,
                         counters.device_stage_batches + counters.device_grouped_batches,
                         counters.device_literal_args, _caches(), _COMPILES[0]))
    return seen


@pytest.mark.parametrize("query, args", [("q1", 4), ("q6", 5)])
def test_a_new_value_is_a_new_argument_and_not_a_new_program(tpch, query, args):
    """Every DELTA of Q1's domain, 20 of Q6's 80 tuples: one program, one
    cache entry, one placement verdict; 4 or 5 values travel with a launch."""
    values = _values(query)
    assert len(set(values)) == len(values) == (61 if query == "q1" else 20)
    before = (counters.device_stage_program_traces, _caches())
    seen = _run_values(tpch, query, values, mesh_devices=0)
    traces, dispatches, literal_args, caches, compiles = zip(*seen)
    # the first value traced the query's program (unless an earlier test had)
    assert traces[0] - before[0] <= 1
    assert set(traces[1:]) == {traces[0]}, "a value traced a program"
    assert set(compiles[1:]) == {compiles[0]}, "a value compiled a program"
    assert set(caches[1:]) == {caches[0]}, "a cache grew with the values"
    assert all(a - b <= 1 for a, b in zip(caches[0], before[1]))
    assert np.diff(dispatches).tolist() == [1] * (len(values) - 1)
    assert np.diff(literal_args).tolist() == [args] * (len(values) - 1)


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 (virtual) devices — see conftest")
@pytest.mark.parametrize("query", ["q1", "q6"])
def test_a_new_value_is_a_new_argument_over_a_mesh(tpch, query):
    """The same over four host devices (stage.over_shards: the values go to
    every shard whole): the first value traces the sharded program, the rest
    none, and every dispatch spans the four."""
    d0 = (counters.device_mesh_batches, counters.device_mesh_shards)
    seen = _run_values(tpch, query, _values(query, 6), mesh_devices=4)
    traces, _dispatches, _args, caches, compiles = zip(*seen)
    assert set(traces[1:]) == {traces[0]} and set(caches[1:]) == {caches[0]}
    assert set(compiles[1:]) == {compiles[0]}
    assert counters.device_mesh_batches - d0[0] == 6
    assert counters.device_mesh_shards - d0[1] == 24


def test_the_stage_caches_hold_no_value(tpch):
    """What a compiled stage is cached under names no literal's value, and
    neither does the mesh tier's verdict."""
    # what this test's own queries cache: another file's entries in the same
    # process (a scan's date, a fill target of 0.0) are not what is held here
    for cache in (stage._STAGE_CACHE, grouped_stage._STAGE_CACHE, executor._MESH_TIER_CACHE):
        cache.clear()
    _run_values(tpch, "q6", _q6_sample(2, seed=5), mesh_devices=0)
    keys = list(stage._STAGE_CACHE) + list(grouped_stage._STAGE_CACHE) \
        + list(executor._MESH_TIER_CACHE._d)
    text = repr(keys)
    assert "lit('?')" in text
    assert "datetime.date" not in text and "0.0" not in text


# ---- what is part of a shape, and what is a value -------------------------------------

def _table():
    rng = np.random.default_rng(34)
    n = 4000
    return daft_tpu.from_pydict({
        "k": rng.integers(0, 3, n).tolist(),
        "x": rng.integers(0, 10, n).tolist(),
        "d": np.round(rng.integers(0, 11, n) / 100, 2).tolist(),
        "v": np.round(rng.uniform(1, 100, n), 2).tolist(),
    }).collect()


def _pair(make, a, b):
    """Run make(a) then make(b) on the device; returns (programs traced by
    the second, stage-cache entries it added, both answers, both host answers)."""
    df = _table()
    with execution_config_ctx(device_mode="off"):
        host = [make(df, a).to_pydict(), make(df, b).to_pydict()]
    with execution_config_ctx(device_mode="on", device_min_rows=1, mesh_devices=1):
        first = make(df, a).to_pydict()
        traces = counters.device_stage_program_traces
        entries = len(stage._STAGE_CACHE) + len(grouped_stage._STAGE_CACHE)
        second = make(df, b).to_pydict()
        return (counters.device_stage_program_traces - traces,
                len(stage._STAGE_CACHE) + len(grouped_stage._STAGE_CACHE) - entries,
                [first, second], host)


def _sum_where(pred):
    return lambda df, value: df.where(pred(value)).agg(col("v").sum().alias("s"),
                                                       col("v").count().alias("n"))


CASES = {
    # another int: the same program
    "another_int": (_sum_where(lambda v: col("x") < v), 5, 7, 0),
    # an int against a float literal: the slot's dtype is part of the shape
    "int_against_float": (_sum_where(lambda v: col("x") < v), 5, 5.0, 1),
    # a null literal is a constant of its program, not a slot
    "null_literal": (_sum_where(lambda v: col("x") < lit(v)), 5, None, 1),
    # an IsIn of other items: the same shape
    "is_in_other_items": (_sum_where(lambda v: col("x").is_in(v)), [1, 2, 3], [4, 5, 9], 0),
    # an IsIn of another length: another shape
    "is_in_another_length": (_sum_where(lambda v: col("x").is_in(v)), [1, 2, 3], [4, 5], 1),
    # a literal inside an aggregate's input
    "literal_in_an_aggregates_input": (
        lambda df, v: df.where(col("x") < 8).agg((col("v") * (1 - col("d")) * v).sum().alias("s")),
        2, 3, 0),
    # the same, grouped: the planes of two inputs stay apart while they differ
    "grouped_inputs_with_literals": (
        lambda df, v: df.groupby("k").agg((col("v") * v[0]).sum().alias("a"),
                                          (col("v") * v[1]).sum().alias("b")).sort("k"),
        (2, 3), (5, 7), 0),
    # rows whose d equals a bound are kept by >= and <=: the bound written to
    # two places is the rows' own value, in float32 as in float64
    "a_row_equal_to_a_bound": (
        _sum_where(lambda v: (col("d") >= v[0]) & (col("d") <= v[1])),
        (0.05, 0.07), (round(0.06 + 0.01, 2), round(0.08 + 0.01, 2)), 0),
    # a date: converted to days on the host, then an argument
    "another_date": (
        lambda df, v: df.with_column("day", lit(datetime.date(1995, 1, 1)))
        .where(col("day") <= lit(v)).agg(col("v").sum().alias("s")),
        datetime.date(1994, 12, 31), datetime.date(1995, 1, 1), 0),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_what_is_part_of_a_shape_and_what_is_a_value(case):
    make, a, b, new_programs = CASES[case]
    traced, entries, device, host = _pair(make, a, b)
    assert traced == new_programs and entries == new_programs
    for got, want in zip(device, host):
        assert list(got) == list(want)
        for name in want:
            for g, w in zip(got[name], want[name]):
                if isinstance(w, float):
                    assert g == pytest.approx(w, rel=2e-6)
                else:
                    assert g == w
    if case == "a_row_equal_to_a_bound":
        # the rows at 0.05 and 0.07 (0.07 and 0.09) are counted
        d = np.asarray(_table().to_pydict()["d"])
        assert device[0]["n"] == [int(((d >= 0.05) & (d <= 0.07)).sum())]
        assert device[1]["n"] == [int(((d >= 0.07) & (d <= 0.09)).sum())]
        assert (d == 0.07).sum() > 0


def test_inputs_that_become_the_same_expression_are_another_shape():
    """Two aggregates whose inputs are the same expression share their planes
    (grouped_stage._classify_planes); whether they are is part of the cache
    key, so inputs that differ in a value never read a shared plane."""
    def make(df, v):
        return df.groupby("k").agg((col("v") * v[0]).sum().alias("same"),
                                   (col("v") * v[1]).sum().alias("or_not")).sort("k")

    traced, entries, device, host = _pair(make, (2, 2), (2, 3))
    assert traced == 1 and entries == 1
    for got, want in zip(device, host):
        np.testing.assert_allclose(got["same"], want["same"], rtol=2e-6)
        np.testing.assert_allclose(got["or_not"], want["or_not"], rtol=2e-6)
    assert device[0]["same"] == device[0]["or_not"]
    assert device[1]["same"] != device[1]["or_not"]


def test_a_run_is_told_when_its_values_do_not_fit_the_program():
    schema = _table().schema
    built = stage.try_build_filter_agg_stage(
        schema, col("x") < 5, [col("v").sum().alias("s")])
    assert len(built.slots) == 1 and built.slots.n_args == 1
    with pytest.raises(ValueError, match="literal slots"):
        built.start_run().literals.args()
    with pytest.raises(ValueError, match="literal slots"):
        built.start_run(stage.stage_literals(col("x") < lit(None), [])).literals.args()


def test_a_filter_runs_values_cross_to_the_device_once_where_it_dispatches_again():
    """A launch carries the values as host arrays. A filter-aggregate run's
    are the same at every dispatch: on a single device its second dispatch
    puts them there and every later launch passes those arrays; over a mesh
    (a committed array would compile the program again) and in a grouped run
    (the row offset rides with them, another number a launch) every launch
    carries a host array."""
    schema = _table().schema
    pred, aggs = col("x") < 5, [(col("v") * 2.0).sum().alias("s")]
    built = stage.try_build_filter_agg_stage(schema, pred, aggs)
    run = built.start_run(stage.stage_literals(pred, aggs))
    first, second, third = (run.literals.args() for _ in range(3))
    assert len(first) == 1 and isinstance(first[0], np.ndarray)
    assert isinstance(second[0], jax.Array) and third[0] is second[0]
    np.testing.assert_array_equal(np.asarray(second[0]), first[0])
    mesh = stage.local_mesh(4)
    over = built.start_run(stage.stage_literals(pred, aggs), mesh_devices=4)
    assert all(isinstance(over.literals.args(mesh=mesh)[0], np.ndarray) for _ in range(3))

    grun = grouped_stage.try_build_grouped_agg_stage(
        schema, pred, [col("k")], aggs).start_run(stage.stage_literals(pred, aggs))
    a, b = grun.literals.args((0,)), grun.literals.args((4096,))
    assert isinstance(a[0], np.ndarray) and a[0] is not b[0]
    assert list(a[0][:-2]) == list(b[0][:-2]) and list(b[0][-2:]) == [4096, 0]


def test_a_run_of_many_dispatches_answers_as_a_run_of_one():
    """The device arrays of a run's later launches give what the first
    launch's host arrays give, and trace no program: a filter-aggregate over
    five batches, a dispatch each, against the five runs of one batch."""
    table = _table()
    pred, aggs = (col("x") < 40) & (col("v") > -1.5), [(col("v") * 2.0).sum().alias("s"),
                                                       col("x").max().alias("hi")]
    built = stage.try_build_filter_agg_stage(table.schema, pred, aggs)
    literals = stage.stage_literals(pred, aggs)
    batches = [b for part in table.into_partitions(5).iter_partitions() for b in part.batches]
    assert len(batches) == 5
    singles = []
    for b in batches:
        one = built.start_run(literals)
        one.feed_batch(b)
        singles.append(one.finalize())
    traces, dispatched = counters.device_stage_program_traces, counters.device_stage_batches
    run = built.start_run(literals)
    for b in batches:
        run.feed_batch(b)
    got = run.finalize()
    assert counters.device_stage_batches - dispatched == 5
    assert counters.device_stage_program_traces == traces
    assert isinstance(run.literals.args()[0], jax.Array)
    assert got["hi"] == max(s["hi"] for s in singles)
    assert got["s"] == pytest.approx(sum(s["s"] for s in singles), rel=1e-12)


def test_walk_order_is_the_one_order():
    """The literals of expr_structure, the slots of a compiled expression and
    the nodes LiteralSlots reads are one order (device/residency.literal_nodes),
    also where one Literal object stands at two places."""
    from daft_tpu.device.residency import expr_structure, literal_nodes

    five = lit(5)
    e = ((col("x") < five) & col("x").is_in([1, 2]) & (col("v") * 2.5 > five)
         & col("x").between(lit(0), lit(9)))
    skeleton, lits = expr_structure(e)
    assert [v for _dt, v in lits] == [5, 1, 2, 2.5, 5, 0, 9]
    assert [n.value for n in literal_nodes(e)] == [v for _dt, v in lits]
    assert skeleton.count("lit('?')") == 7


# ---- how the values travel -------------------------------------------------------------

@pytest.mark.parametrize("fdt", ["float32", "float64"])
def test_every_dtype_comes_back_exact_from_its_words(fdt):
    """One uint32 array a launch (and one float64 array in a float64 stage):
    every slot's value is rebuilt exactly, in the program's dtype, and the
    run values behind them."""
    import jax.numpy as jnp

    from daft_tpu.datatype import DataType
    from daft_tpu.device.residency import exprs_structure
    from daft_tpu.expressions.expressions import Literal
    from daft_tpu.ops import device_eval as dev

    stamp = datetime.datetime(2001, 2, 3, 4, 5, 6, 789000)
    values = [(-7, DataType.int8()), (40000, DataType.uint16()), (-2**31, DataType.int32()),
              (2**32 - 1, DataType.uint32()), (-2**53 - 1, DataType.int64()),
              (2**64 - 3, DataType.uint64()), (True, DataType.bool()), (False, DataType.bool()),
              (0.07, DataType.float64()), (-1.5e-30, DataType.float32()),
              (datetime.date(1969, 12, 30), DataType.date()), (None, DataType.int64()),
              (stamp, DataType.timestamp("us"))]
    e = col("x").is_in([Literal(v, dt) for v, dt in values])
    slots = dev.LiteralSlots([e], jnp.dtype(fdt), run_values=2)
    assert len(slots) == 13 and slots.n_args == 12
    args = slots.with_run_values(slots.pack(exprs_structure([e])[1]), (2**40 + 5, 0))
    assert [(a.shape, a.dtype) for a in args] == list(slots.arg_shapes())
    assert len(args) == (2 if fdt == "float64" else 1) and args[0].dtype == np.uint32
    got = jax.jit(lambda a: (slots.unpack(a), slots.run_value(a, 0), slots.run_value(a, 1)))(args)
    lits, first, second = got
    assert int(first) == 2**40 + 5 and int(second) == 0 and first.dtype == jnp.int64
    want = [np.int8(-7), np.uint16(40000), np.int32(-2**31), np.uint32(2**32 - 1),
            np.int64(-2**53 - 1), np.uint64(2**64 - 3), np.bool_(True), np.bool_(False),
            np.dtype(fdt).type(0.07), np.dtype(fdt).type(-1.5e-30),  # floats compute in fdt
            np.int32(-2), None,
            np.int64((stamp - datetime.datetime(1970, 1, 1)) // datetime.timedelta(microseconds=1))]
    for g, w in zip(lits, want):
        if w is None:
            assert g is None
        else:
            assert g.dtype == w.dtype and g.shape == () and g == w, (g, w)

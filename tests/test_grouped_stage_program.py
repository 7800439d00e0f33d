"""What GroupedAggStage._build's program is made of, read from its jaxpr.

The one-hot tier's program reads each input plane once: predicate, agg
children and planes are evaluated for a tile of rows inside the loop that
reduces them. These tests walk the traced program and refuse what the program
had before: an equation outside the loop that writes an array as long as the
bucket (the stacked planes, `keep`, `seg`, a child's values), and a float64
value at row width that the configuration did not ask for (the first-row
index). Nothing runs here but the tracer.
"""

import datetime

import numpy as np
import pytest

from daft_tpu.utils import jax_setup  # noqa: F401
import jax
import jax.numpy as jnp

from daft_tpu import col, lit
from daft_tpu.datatype import DataType
from daft_tpu.ops import grouped_stage as gs
from daft_tpu.schema import Schema

# primitives that hand on a view of their operand (no new values)
_VIEWS = {"reshape", "squeeze", "expand_dims", "broadcast_in_dim"}
_LOOPS = {"scan", "while"}


def _schema():
    return Schema.from_pydict({
        "l_returnflag": DataType.string(), "l_linestatus": DataType.string(),
        "l_quantity": DataType.float64(), "l_extendedprice": DataType.float64(),
        "l_discount": DataType.float64(), "l_tax": DataType.float64(),
        "l_shipdate": DataType.date(), "l_suppkey": DataType.int32()})


def _shipped():
    return col("l_shipdate") <= lit(datetime.date(1998, 9, 2))


def _q1_stage():
    disc = col("l_extendedprice") * (1 - col("l_discount"))
    aggs = [col("l_quantity").sum().alias("sum_qty"),
            col("l_extendedprice").sum().alias("sum_base_price"),
            disc.sum().alias("sum_disc_price"),
            (disc * (1 + col("l_tax"))).sum().alias("sum_charge"),
            col("l_quantity").mean().alias("avg_qty"),
            col("l_extendedprice").mean().alias("avg_price"),
            col("l_discount").mean().alias("avg_disc"),
            col("l_quantity").count().alias("count_order")]
    return gs.try_build_grouped_agg_stage(
        _schema(), _shipped(), [col("l_returnflag"), col("l_linestatus")], aggs)


def _three_plane_stage():
    revenue = (col("l_extendedprice") * (1 - col("l_discount"))).sum()
    return gs.try_build_grouped_agg_stage(
        _schema(), _shipped(), [col("l_suppkey")], [revenue.alias("revenue")])


def _date_extreme_stage():
    return gs.try_build_grouped_agg_stage(
        _schema(), None, [col("l_returnflag")],
        [col("l_shipdate").max().alias("last")])


def _float_extreme_stage():
    return gs.try_build_grouped_agg_stage(
        _schema(), None, [col("l_returnflag")],
        [col("l_quantity").min().alias("least")])


def _args(stage, bucket):
    def s(dt):
        return jax.ShapeDtypeStruct((bucket,), dt)

    fdt = jnp.float64 if stage._use_f64 else jnp.float32
    ints = {"l_shipdate", "l_suppkey"}
    cols = {name: (s(jnp.int32 if name in ints else fdt), s(jnp.bool_))
            for name in stage._input_cols}
    lits = tuple(jax.ShapeDtypeStruct(shape, dt) for shape, dt in stage.slots.arg_shapes())
    return cols, s(jnp.int32), s(jnp.bool_), lits


def _program_jaxpr(fn, *args):
    """The jaxpr of a jitted function's body (under the pjit equation)."""
    outer = jax.make_jaxpr(fn)(*args).jaxpr
    (eqn,) = [e for e in outer.eqns if e.primitive.name in ("pjit", "jit")]
    return eqn.params["jaxpr"].jaxpr


def _size(v):
    return int(np.prod(v.aval.shape)) if hasattr(v.aval, "shape") else 0


def _sub_jaxprs(eqn):
    for p in eqn.params.values():
        for q in (p if isinstance(p, (list, tuple)) else (p,)):
            inner = getattr(q, "jaxpr", q)
            if hasattr(inner, "eqns"):
                yield inner


def _all_eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for sub in _sub_jaxprs(e):
            yield from _all_eqns(sub)


def row_wide_outside_loop(jaxpr, bucket):
    """Equations of the program's top level (calls followed, loops not) whose
    result has `bucket` or more elements and is not a view of an input."""
    views = {id(v) for v in jaxpr.invars}
    bad = []

    def walk(jp):
        for e in jp.eqns:
            name = e.primitive.name
            if name in _LOOPS:
                continue
            subs = list(_sub_jaxprs(e))
            if subs:
                for sub in subs:
                    walk(sub)
                continue
            if name in _VIEWS and all(id(v) in views for v in e.invars
                                      if hasattr(v, "aval") and _size(v) > 1):
                views.update(id(v) for v in e.outvars)
                continue
            if any(_size(v) >= bucket for v in e.outvars):
                bad.append(f"{name} -> {[str(v.aval) for v in e.outvars]}")

    walk(jaxpr)
    return bad


def row_wide_f64(jaxpr, bucket):
    """Every float64 value with `bucket` or more elements, loops included."""
    return [f"{e.primitive.name} -> {v.aval}" for e in _all_eqns(jaxpr)
            for v in e.outvars
            if _size(v) >= bucket and v.aval.dtype == jnp.float64]


def test_the_walker_sees_a_plane_built_outside_a_loop():
    """The guard guards: a program of the old shape (planes stacked at row
    width, a float64 row index, then a scan over them) is refused."""
    bucket = 1 << 14

    @jax.jit
    def old_shape(v, codes):
        keep = v > 0
        idx = jnp.arange(bucket, dtype=jnp.float64)
        xs = jnp.stack([keep.astype(jnp.float32), jnp.where(keep, v, 0.0)], -1)
        xs = xs.reshape(16, bucket // 16, 2)

        def body(acc, x):
            return acc + x.sum(axis=0), None

        acc, _ = jax.lax.scan(body, jnp.zeros(2, jnp.float32), xs)
        return acc, idx.min(), codes.reshape(16, -1).sum()

    jp = _program_jaxpr(old_shape, jax.ShapeDtypeStruct((bucket,), jnp.float32),
                        jax.ShapeDtypeStruct((bucket,), jnp.int32))
    bad = row_wide_outside_loop(jp, bucket)
    assert any(b.startswith("concatenate") for b in bad), bad
    assert not any("int32" in b for b in bad), bad     # the view of `codes`
    assert row_wide_f64(jp, bucket)


CASES = {
    "q1_cap8_select": (_q1_stage, 8, 1 << 21, "select"),
    "q1_cap64_matmul": (_q1_stage, 64, 1 << 18, "matmul"),
    "three_planes_cap4096_matmul": (_three_plane_stage, 4096, 1 << 17, "matmul"),
    "three_planes_cap8_select": (_three_plane_stage, 8, 1 << 21, "select"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_no_plane_is_built_outside_the_loop(case):
    make, cap, bucket, form = CASES[case]
    stage = make()
    assert gs._reduce_form(cap) == form
    jp = _program_jaxpr(stage._build(cap), *_args(stage, bucket))
    assert [e for e in jp.eqns if e.primitive.name in _LOOPS], "no loop at all"
    assert row_wide_outside_loop(jp, bucket) == []


@pytest.mark.parametrize("case", sorted(CASES))
def test_nothing_at_row_width_is_float64(case):
    make, cap, bucket, _form = CASES[case]
    stage = make()
    assert not stage._use_f64
    jp = _program_jaxpr(stage._build(cap), *_args(stage, bucket))
    assert row_wide_f64(jp, bucket) == []
    # the first-row index leaves the loop as int32 and is widened at [cap]
    wide = [v.aval for e in jp.eqns if e.primitive.name not in _LOOPS
            for v in e.outvars if v.aval.dtype == jnp.float64]
    assert wide and all(int(np.prod(a.shape)) <= cap * len(stage._mm_specs)
                        for a in wide), wide


@pytest.mark.parametrize("form", ["select", "matmul"])
def test_declared_float64_keeps_its_precision(form):
    """What the stage declares float64 stays float64 inside the loop's tile:
    a date extreme's plane (use_f64 of _ext_specs), and every plane of a
    stage in _use_f64 mode (an exact float extreme). Neither at row width."""
    bucket = 1 << 21
    for make in (_date_extreme_stage, _float_extreme_stage):
        stage = make()
        assert stage._use_f64 or stage._ext_specs[1][2]
        jp = _program_jaxpr(stage._build(8, form=form), *_args(stage, bucket))
        assert row_wide_outside_loop(jp, bucket) == []
        if not stage._use_f64:      # else the inputs themselves are float64
            assert row_wide_f64(jp, bucket) == []
        assert any(v.aval.dtype == jnp.float64 and _size(v) >= 512
                   for e in _all_eqns(jp) for v in e.outvars)


def test_the_planes_of_q1_are_reduced_once():
    """16 planes before: rows, a count a agg, a sum a sum or mean. Equal
    children share: count(l_quantity) serves sum_qty, avg_qty and count_order,
    sum(l_quantity) serves sum_qty and avg_qty."""
    stage = _q1_stage()
    assert len(stage._mm_specs) == 11
    slots = {name: s for (name, _agg), s in zip(stage.aggs, stage._agg_slots)}
    assert slots["sum_qty"]["sum"] == slots["avg_qty"]["sum"]
    assert slots["sum_qty"]["count"] == slots["avg_qty"]["count"] \
        == slots["count_order"]["count"]
    assert slots["sum_base_price"]["sum"] == slots["avg_price"]["sum"]
    assert slots["sum_base_price"]["sum"] != slots["sum_qty"]["sum"]
    planes = [s[k][1] for s in stage._agg_slots for k in s]
    assert set(planes) == set(range(1, 11))     # every plane but `rows` is read


def test_count_all_reads_the_rows_plane():
    stage = gs.try_build_grouped_agg_stage(
        _schema(), None, [col("l_returnflag")],
        [col("l_quantity").count(mode="all").alias("n"),
         col("l_quantity").count().alias("c")])
    assert stage._mm_specs == [(-1, "rows"), (1, "count")]
    assert stage._agg_slots[0]["count"] == ("mm", 0)
    assert stage._agg_slots[1]["count"] == ("mm", 1)


def test_integer_sums_share_their_digit_planes():
    stage = gs.try_build_grouped_agg_stage(
        _schema(), None, [col("l_returnflag")],
        [col("l_suppkey").sum().alias("s"), col("l_suppkey").mean().alias("m")])
    assert stage._agg_slots[0]["sum"] == stage._agg_slots[1]["sum"]
    assert stage._agg_slots[0]["sum"][0] == "imm"
    assert len(stage._mm_specs) == 2 + stage._agg_slots[0]["sum"][2]


@pytest.mark.parametrize("cap,form", [(8, "select"), (16, "select"),
                                      (32, "matmul"), (256, "matmul"),
                                      (4096, "matmul")])
def test_reduce_form_is_a_function_of_the_capacity(cap, form):
    assert gs._reduce_form(cap) == form


@pytest.mark.parametrize("cap,chunk", [(8, 65536), (64, 65536), (128, 32768),
                                       (1024, 4096), (4096, 1024)])
def test_chunk_rows(cap, chunk):
    assert gs._chunk_for(1 << 20, cap) == chunk
    assert gs._chunk_for(512, cap) == 512

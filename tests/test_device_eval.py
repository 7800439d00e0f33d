"""Device (JAX) expression evaluation must agree with host evaluation exactly,
including null semantics — the property the stage compiler relies on."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from daft_tpu import DataType, RecordBatch
from daft_tpu.expressions import col, lit
from daft_tpu.ops.device_eval import build_constant_device_expr, device_agg, is_device_evaluable


def run_both(batch: RecordBatch, expr):
    """Evaluate expr on host and on device (under jit); return (host, device) pylists."""
    host = batch.eval_expression(expr)
    schema = batch.schema
    names = expr.referenced_columns()
    cols = {n: batch.get_column(n).to_device() for n in names}
    fn = build_constant_device_expr(expr, schema)
    jitted = jax.jit(lambda c: fn(c))
    vals, valid = jitted(cols)
    vals = np.asarray(vals)
    valid = np.asarray(valid)
    if valid.ndim == 0:
        valid = np.full(len(vals), bool(valid))
    dev = [vals[i].item() if valid[i] else None for i in range(len(vals))]
    return host.to_pylist(), dev


CASES = [
    (col("a") + col("b")),
    (col("a") - 3),
    (col("a") * col("b") + 1),
    (col("a") / col("b")),
    (col("a") // col("b")),
    (col("a") % col("b")),
    (col("a") > col("b")),
    (col("a") <= 3),
    (col("a") == col("b")),
    ((col("a") > 1) & (col("b") > 1)),
    ((col("a") > 1) | (col("b") > 1)),
    (~(col("a") > 2)),
    (-col("a")),
    (col("a").abs()),
    (col("a").is_null()),
    (col("a").not_null()),
    (col("a").fill_null(0)),
    (col("a").between(1, 3)),
    (col("a").is_in([1, 4])),
    ((col("a") > 2).if_else(col("a"), col("b"))),
    (col("f").sqrt()),
    (col("f").exp()),
    (col("f").log()),
    (col("f").floor()),
    (col("f").ceil()),
    (col("f").round(1)),
    (col("f").float.is_nan()),
    (col("f").float.fill_nan(9.0)),
    (col("a").cast(DataType.float64()) * 2.5),
]


@pytest.mark.parametrize("expr", CASES, ids=[repr(e) for e in CASES])
def test_device_matches_host(expr):
    b = RecordBatch.from_pydict({
        "a": [1, 2, None, 4, 0],
        "b": [2, 0, 2, None, 3],
        "f": [1.5, float("nan"), None, 4.0, 0.25],
    })
    assert is_device_evaluable(expr, b.schema), f"{expr!r} should be device-evaluable"
    host, dev = run_both(b, expr)
    assert len(host) == len(dev)
    for h, d in zip(host, dev):
        if h is None or d is None:
            assert h is None and d is None, (host, dev)
        elif isinstance(h, float):
            if np.isnan(h):
                assert np.isnan(d)
            else:
                assert abs(h - d) < 1e-9, (host, dev)
        else:
            assert bool(h == d), (host, dev)


def test_not_device_evaluable():
    b = RecordBatch.from_pydict({"s": ["x", "y"], "a": [1, 2]})
    assert not is_device_evaluable(col("s").str.upper(), b.schema)
    assert not is_device_evaluable(col("s") + col("s"), b.schema)
    assert is_device_evaluable(col("a") + 1, b.schema)


def test_device_agg_matches_host():
    b = RecordBatch.from_pydict({"x": [1.0, 2.0, None, 4.0]})
    v, m = b.get_column("x").to_device(pad_to=8)
    for op, expected in [("sum", 7.0), ("mean", 7.0 / 3), ("min", 1.0), ("max", 4.0), ("count", 3)]:
        val, valid = jax.jit(lambda v, m, op=op: device_agg(op, v, m))(v, m)
        assert bool(valid)
        assert abs(float(val) - expected) < 1e-9, op


def test_device_agg_all_null():
    b = RecordBatch.from_pydict({"x": [None, None]})
    v, m = b.get_column("x").cast(DataType.float64()).to_device()
    val, valid = device_agg("sum", v, m)
    assert not bool(valid)
    val, valid = device_agg("count", v, m)
    assert bool(valid) and int(val) == 0


def test_padding_invariance():
    """Padded rows must not change live-row results — the static-shape convention.

    Row liveness is tracked by the stage compiler separately from validity (ops like
    fill_null can validly mark padding rows non-null); here we assert the live
    prefix is unaffected by padding.
    """
    b = RecordBatch.from_pydict({"a": [1, 2, None, 4, 0]})
    expr = (col("a") * 2 + 1).fill_null(-1)
    fn = build_constant_device_expr(expr, b.schema)
    v8 = fn({"a": b.get_column("a").to_device(pad_to=8)})
    v5 = fn({"a": b.get_column("a").to_device()})
    np.testing.assert_array_equal(np.asarray(v8[0])[:5], np.asarray(v5[0]))
    np.testing.assert_array_equal(np.asarray(v8[1])[:5], np.asarray(v5[1]))


def test_device_agg_float_sum_uses_f64_accumulation():
    """Float sums must accumulate in f64: an f32 whole-bucket reduction carries
    only ~7 significant digits, corrupting partials before the host combine."""
    n = 200_000
    v = jnp.concatenate([jnp.asarray([1e8], jnp.float32),
                         jnp.full((n,), 0.25, jnp.float32)])
    m = jnp.ones((n + 1,), jnp.bool_)
    val, valid = jax.jit(lambda v, m: device_agg("sum", v, m))(v, m)
    assert bool(valid)
    expect = 1e8 + 0.25 * n
    assert abs(float(val) - expect) < 1.0  # f32 accumulation would be off by ~50k

"""The benchmark's arithmetic: executions -> end-to-end metrics."""

import math

import pytest

import arith


def runs(*triples):
    return [{"template": t, "start": s, "end": e, "failed": False} for t, s, e in triples]


def test_percentile_interpolates_and_counts():
    value, n = arith.percentile([10, 20, 30, 40, 50], 95)
    assert n == 5
    assert value == pytest.approx(48.0)
    assert arith.percentile([7.0], 95) == (7.0, 1)
    assert arith.percentile(range(1, 102), 95)[0] == pytest.approx(96.0)


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        arith.percentile([], 95)


def test_geomean():
    assert arith.geomean([4.0, 9.0]) == pytest.approx(6.0)
    assert arith.geomean([5.0]) == pytest.approx(5.0)
    with pytest.raises(ValueError):
        arith.geomean([1.0, 0.0])
    with pytest.raises(ValueError):
        arith.geomean([])


def test_query_ms_geomean_is_over_template_medians():
    ex = runs(("a", 0.0, 0.010), ("b", 0.010, 0.110), ("a", 0.110, 0.130),
              ("b", 0.130, 0.230), ("a", 0.230, 0.260))
    # medians: a = 20 ms (10, 20, 30), b = 100 ms
    assert arith.query_ms_geomean(ex, ["a", "b"]) == pytest.approx(math.sqrt(20 * 100))


def test_query_ms_geomean_refuses_a_template_that_never_completed():
    ex = runs(("a", 0.0, 0.010))
    with pytest.raises(ValueError, match="b"):
        arith.query_ms_geomean(ex, ["a", "b"])


def test_failed_executions_carry_no_time_and_no_rows():
    ex = runs(("a", 0.0, 1.0), ("a", 1.0, 2.0))
    ex[1]["failed"] = True
    assert arith.durations_ms(ex) == {"a": [1000.0]}
    assert arith.scan_rows_per_s(ex, {"a": 500}) == pytest.approx(500.0)


def test_query_ms_p95_is_over_every_execution_with_its_count():
    ex = runs(*[("a", i, i + 0.001 * (i + 1)) for i in range(100)])
    value, n = arith.query_ms_p95(ex)
    assert n == 100
    assert value == pytest.approx(95.05)


def test_scan_rows_per_s_runs_to_the_last_completion():
    ex = runs(("a", 0.0, 1.0), ("b", 1.0, 4.0))
    # 100 + 300 rows over the 4.0 s from the start of the window to the last end
    assert arith.scan_rows_per_s(ex, {"a": 100, "b": 300}) == pytest.approx(100.0)
    with pytest.raises(ValueError):
        arith.scan_rows_per_s([], {"a": 1})

"""The comparison that decides `correct`, and the bytes function."""

import datetime

import pytest

import compare
import scanbytes

REF = {"k": ["a", "b"], "n": [3, 4], "d": [datetime.date(1995, 1, 1)] * 2,
       "x": [100.0, 0.5]}


def got(**changes):
    out = {c: list(v) for c, v in REF.items()}
    out.update(changes)
    return out


@pytest.mark.parametrize("answer, numbers", [
    (got(), {"shape": 0, "exact_mismatches": 0, "float_rel_gap": 0.0}),
    (got(x=[100.001, 0.5]), {"shape": 0, "exact_mismatches": 0, "float_rel_gap": 1e-5}),
    # below 1 the gap is absolute: a sum near zero is not held to its own size
    (got(x=[100.0, 0.5001]), {"shape": 0, "exact_mismatches": 0, "float_rel_gap": 1e-4}),
    (got(n=[3, 5]), {"shape": 0, "exact_mismatches": 1, "float_rel_gap": 0.0}),
    (got(k=["b", "a"]), {"shape": 0, "exact_mismatches": 2, "float_rel_gap": 0.0}),
    (got(d=[datetime.date(1995, 1, 2), datetime.date(1995, 1, 1)]),
     {"shape": 0, "exact_mismatches": 1, "float_rel_gap": 0.0}),
    (got(x=[None, 0.5]), {"shape": 0, "exact_mismatches": 1, "float_rel_gap": 0.0}),
    (got(x=[float("nan"), 0.5]), {"shape": 0, "exact_mismatches": 1, "float_rel_gap": 0.0}),
    (got(x=[100.0]), {"shape": 1}),
    ({"n": REF["n"], "k": REF["k"], "d": REF["d"], "x": REF["x"]}, {"shape": 1}),
])
def test_compare(answer, numbers):
    out = compare.compare(REF, answer)
    for key, want in numbers.items():
        assert out[key] == pytest.approx(want, rel=1e-3), key


def test_each_number_has_its_own_limit():
    lim = compare.limits({"float_rel_limit": {"q1": 1e-6}}, "q1")
    with pytest.raises(KeyError):
        compare.limits({"float_rel_limit": {"q1": 1e-6}}, "q6")
    assert lim == {"shape": 0, "exact_mismatches": 0, "float_rel_gap": 1e-6}
    assert compare.within({"shape": 0, "exact_mismatches": 0, "float_rel_gap": 9e-7}, lim)
    assert not compare.within({"shape": 0, "exact_mismatches": 0, "float_rel_gap": 2e-6}, lim)
    assert not compare.within({"shape": 0, "exact_mismatches": 1, "float_rel_gap": 0.0}, lim)
    assert not compare.within({"shape": 1, "exact_mismatches": 0, "float_rel_gap": 0.0}, lim)


def test_scan_bytes_counts_value_planes_by_their_own_nbytes():
    rows = 1 << 20
    arrays = [((rows,), "float32", 4 * rows)] * 4 + [((rows,), "int32", 4 * rows)] \
        + [((rows,), "bool", rows)] * 6 + [((2 * rows,), "float32", 8 * rows)]
    assert scanbytes.plane_nbytes(arrays) == 4 * rows
    assert scanbytes.scan_bytes(7, arrays) == 7 * 4 * rows
    with pytest.raises(ValueError):
        scanbytes.plane_nbytes([((rows,), "bool", rows)])

import numpy as np
import pyarrow as pa
import pytest

from daft_tpu import DataType, Series


def test_from_pylist_infer():
    s = Series.from_pylist([1, 2, 3], "x")
    assert s.dtype == DataType.int64()
    assert s.to_pylist() == [1, 2, 3]
    s = Series.from_pylist([1.5, None, 2.5], "x")
    assert s.dtype == DataType.float64()
    assert s.to_pylist() == [1.5, None, 2.5]
    assert s.null_count() == 1
    s = Series.from_pylist(["a", "b", None], "x")
    assert s.dtype == DataType.string()


def test_python_fallback():
    class Obj:
        pass

    o = Obj()
    s = Series.from_pylist([o, None, o], "objs")
    assert s.dtype == DataType.python()
    assert s.to_pylist()[0] is o
    assert s.null_count() == 1
    assert len(s.filter(Series.from_pylist([True, False, True]))) == 2


def test_arithmetic():
    a = Series.from_pylist([1, 2, None], "a")
    b = Series.from_pylist([10, 20, 30], "b")
    assert (a + b).to_pylist() == [11, 22, None]
    assert (a - b).to_pylist() == [-9, -18, None]
    assert (a * b).to_pylist() == [10, 40, None]
    assert (b / a).to_pylist() == [10.0, 10.0, None]
    assert (-a).to_pylist() == [-1, -2, None]
    assert a.abs().to_pylist() == [1, 2, None]


def test_division_by_zero_is_null():
    a = Series.from_pylist([1.0, 2.0], "a")
    z = Series.from_pylist([0.0, 1.0], "z")
    assert (a / z).to_pylist() == [None, 2.0]
    ai = Series.from_pylist([7, 8], "a")
    zi = Series.from_pylist([0, 2], "z")
    assert (ai % zi).to_pylist() == [None, 0]
    assert (ai // zi).to_pylist() == [None, 4]


def test_broadcast_scalar():
    a = Series.from_pylist([1, 2, 3], "a")
    one = Series.from_pylist([10], "b")
    assert (a + one).to_pylist() == [11, 12, 13]
    assert (one * a).to_pylist() == [10, 20, 30]


def test_comparisons_and_logic():
    a = Series.from_pylist([1, 2, None], "a")
    b = Series.from_pylist([2, 2, 2], "b")
    assert (a < b).to_pylist() == [True, False, None]
    assert (a == b).to_pylist() == [False, True, None]
    assert (a != b).to_pylist() == [True, False, None]
    t = Series.from_pylist([True, False, None], "t")
    u = Series.from_pylist([True, True, True], "u")
    assert (t & u).to_pylist() == [True, False, None]
    assert (t | u).to_pylist() == [True, True, True]
    assert (~t).to_pylist() == [False, True, None]


def test_string_concat_add():
    a = Series.from_pylist(["a", "b"], "a")
    b = Series.from_pylist(["x", "y"], "b")
    assert (a + b).to_pylist() == ["ax", "by"]


def test_cast():
    s = Series.from_pylist([1, 2, 3], "x")
    assert s.cast(DataType.float32()).dtype == DataType.float32()
    assert s.cast(DataType.string()).to_pylist() == ["1", "2", "3"]
    s2 = Series.from_pylist(["1", "2"], "x")
    assert s2.cast(DataType.int64()).to_pylist() == [1, 2]


def test_filter_take_slice_concat():
    s = Series.from_pylist([10, 20, 30, 40], "x")
    assert s.filter(Series.from_pylist([True, False, True, None])).to_pylist() == [10, 30]
    assert s.take([3, 0]).to_pylist() == [40, 10]
    assert s.slice(1, 3).to_pylist() == [20, 30]
    c = Series.concat([s, s.slice(0, 1)])
    assert c.to_pylist() == [10, 20, 30, 40, 10]


def test_null_ops():
    s = Series.from_pylist([1, None, 3], "x")
    assert s.is_null().to_pylist() == [False, True, False]
    assert s.not_null().to_pylist() == [True, False, True]
    assert s.fill_null(Series.from_pylist([0])).to_pylist() == [1, 0, 3]
    assert s.drop_nulls().to_pylist() == [1, 3]


def test_sort_argsort():
    s = Series.from_pylist([3, 1, None, 2], "x")
    assert s.sort().to_pylist() == [1, 2, 3, None]
    assert s.sort(descending=True).to_pylist() == [None, 3, 2, 1]
    assert s.sort(descending=True, nulls_first=False).to_pylist() == [3, 2, 1, None]


def test_aggregations():
    s = Series.from_pylist([1, 2, 3, None], "x")
    assert s.sum().to_pylist() == [6]
    assert s.mean().to_pylist() == [2.0]
    assert s.min().to_pylist() == [1]
    assert s.max().to_pylist() == [3]
    assert s.count().to_pylist() == [3]
    assert s.count("null").to_pylist() == [1]
    assert s.count("all").to_pylist() == [4]
    assert s.count_distinct().to_pylist() == [3]
    assert s.sum().dtype == DataType.int64()
    b = Series.from_pylist([True, True, None], "b")
    assert b.bool_and().to_pylist() == [True]
    assert b.bool_or().to_pylist() == [True]
    assert s.agg_list().to_pylist() == [[1, 2, 3, None]]


def test_stddev_var():
    s = Series.from_pylist([1.0, 2.0, 3.0, 4.0], "x")
    assert abs(s.var().to_pylist()[0] - 1.25) < 1e-9
    assert abs(s.stddev().to_pylist()[0] - 1.25**0.5) < 1e-9


def test_hash_deterministic_and_null():
    s = Series.from_pylist([1, 2, 1, None], "x")
    h = s.hash().to_pylist()
    assert h[0] == h[2]
    assert h[0] != h[1]
    s2 = Series.from_pylist(["abc", "abd", "abc", None, ""], "x")
    h2 = s2.hash().to_pylist()
    assert h2[0] == h2[2]
    assert h2[0] != h2[1]
    assert h2[3] != h2[4]  # null differs from empty string
    # float canonicalization: -0.0 == 0.0, int 1 pattern vs float different ok
    f = Series.from_pylist([0.0, -0.0, float("nan"), float("nan")], "f")
    hf = f.hash().to_pylist()
    assert hf[0] == hf[1]
    assert hf[2] == hf[3]


def test_is_in_between_if_else():
    s = Series.from_pylist([1, 2, 3, None], "x")
    assert s.is_in(Series.from_pylist([2, 3])).to_pylist() == [False, True, True, False]
    assert s.between(Series.from_pylist([2]), Series.from_pylist([3])).to_pylist() == [False, True, True, None]
    p = Series.from_pylist([True, False, True], "p")
    t = Series.from_pylist([1, 1, 1], "t")
    f = Series.from_pylist([0, 0, 0], "f")
    assert Series.if_else(p, t, f).to_pylist() == [1, 0, 1]


def test_approx_count_distinct():
    s = Series.from_pylist(list(range(1000)) * 2, "x")
    est = s.approx_count_distinct().to_pylist()[0]
    assert abs(est - 1000) / 1000 < 0.05


def test_embedding_series_from_numpy():
    arr = np.arange(12, dtype=np.float32).reshape(3, 4)
    s = Series.from_numpy(arr, "emb", DataType.embedding(DataType.float32(), 4))
    assert s.dtype == DataType.embedding(DataType.float32(), 4)
    out = s.to_numpy()
    assert out.shape == (3, 4)
    np.testing.assert_array_equal(out, arr)


def test_to_device_padding():
    s = Series.from_pylist([1.0, None, 3.0], "x")
    vals, validity = s.to_device(pad_to=8)
    assert vals.shape == (8,)
    assert validity.tolist() == [True, False, True, False, False, False, False, False]


# ---- lineage of zero-copy views (Series.slice) and concat's glue ----------------------

def _root(n=100, nulls=False, strings=False):
    if strings:
        vals = [None if nulls and i % 7 == 0 else f"v{i % 13}" for i in range(n)]
    else:
        vals = [None if nulls and i % 7 == 0 else i * 3 for i in range(n)]
    return Series.from_pylist(vals, "c")


def test_slice_records_lineage_and_composes():
    root = _root()
    assert root.lineage() == (root, 0)           # a column is its own root
    v = root.slice(10, 60)
    assert v.lineage() == (root, 10)
    vv = v.slice(5, 20)                          # a slice of a slice: same root
    assert vv.lineage() == (root, 15) and len(vv) == 15
    assert vv.to_pylist() == root.to_pylist()[15:30]
    assert root.head(7).lineage() == (root, 0)


@pytest.mark.parametrize("make", [
    lambda r: r.take([3, 4, 5]),
    lambda r: r.filter(Series.from_pylist([i % 2 == 0 for i in range(len(r))])),
    lambda r: r.cast(DataType.float64()),
    lambda r: r + r,
    lambda r: r.rename("other"),
    lambda r: Series.from_pylist([object(), object()], "p", DataType.python()).slice(0, 1),
], ids=["take", "filter", "cast", "computed", "rename", "pyobjs_slice"])
def test_new_data_has_no_lineage(make):
    out = make(_root())
    assert out.lineage() == (out, 0)


def test_lineage_dropped_on_pickle():
    import pickle

    root = _root()
    v = pickle.loads(pickle.dumps(root.slice(10, 20)))
    assert v.lineage() == (v, 0)
    assert v.to_pylist() == root.to_pylist()[10:20]


# (name, the parts cut from a root `r` of 100 rows (`o`: another root of equal
# content), expected: "root", a (start, end)
# range of the root, or None for a copy)
_CONCAT_CASES = [
    ("covers_root", lambda r, o: [r.slice(0, 40), r.slice(40, 70), r.slice(70, 100)], "root"),
    ("inner_range", lambda r, o: [r.slice(10, 40), r.slice(40, 55)], (10, 55)),
    ("slices_of_slices", lambda r, o: [r.slice(20, 80).slice(0, 30), r.slice(50, 100)], (20, 100)),
    ("with_empty_view", lambda r, o: [r.slice(0, 50), r.slice(50, 50), r.slice(50, 100)], "root"),
    ("single_view", lambda r, o: [r.slice(5, 9)], (5, 9)),
    ("out_of_order", lambda r, o: [r.slice(40, 100), r.slice(0, 40)], None),
    ("gap", lambda r, o: [r.slice(0, 40), r.slice(50, 100)], None),
    ("overlap", lambda r, o: [r.slice(0, 50), r.slice(40, 100)], None),
    ("two_roots", lambda r, o: [r.slice(0, 50), o.slice(50, 100)], None),
    ("non_view_part", lambda r, o: [r.slice(0, 50), r.slice(50, 100).take(list(range(50)))], None),
    ("root_twice", lambda r, o: [r, r], None),
]


@pytest.mark.parametrize("kind", ["ints", "nulls", "strings"])
@pytest.mark.parametrize("name,cut,expect", _CONCAT_CASES,
                         ids=[c[0] for c in _CONCAT_CASES])
def test_concat_glues_contiguous_views(name, cut, expect, kind):
    root = _root(nulls=kind != "ints", strings=kind == "strings")
    other = _root(nulls=kind != "ints", strings=kind == "strings")  # equal content
    parts = cut(root, other)
    out = Series.concat(parts)
    want = [x for p in parts for x in p.to_pylist()]
    # values, validity and dtype are the same whichever way it went
    assert out.to_pylist() == want
    assert out.dtype == root.dtype and out.name == "c"
    assert out.null_count() == sum(x is None for x in want)
    if expect == "root":
        assert out is root
    elif expect is None:
        assert out.lineage() == (out, 0), "a copy is new data"
        assert out is not root
    else:
        assert out.lineage() == (root, expect[0]) and len(out) == expect[1] - expect[0]
        # zero-copy: the values buffer is the root's own
        assert out.to_arrow().buffers()[-1].address == root.to_arrow().buffers()[-1].address


def test_concat_of_views_keeps_dict_codes():
    root = _root(strings=True)
    codes = root.dict_codes()
    out = Series.concat([root.slice(0, 33), root.slice(33, 100)])
    assert out is root and out.dict_codes() is codes

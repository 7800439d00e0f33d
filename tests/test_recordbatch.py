import numpy as np
import pyarrow as pa
import pytest

from daft_tpu import DataType, RecordBatch, MicroPartition, Schema, Series
from daft_tpu.core.kernels.groupby import make_groups
from daft_tpu.core.kernels.join import join_indices
from daft_tpu.core.kernels.sort import multi_argsort


def test_from_pydict_roundtrip():
    b = RecordBatch.from_pydict({"a": [1, 2, 3], "b": ["x", "y", None]})
    assert b.num_rows == 3
    assert b.column_names() == ["a", "b"]
    assert b.to_pydict() == {"a": [1, 2, 3], "b": ["x", "y", None]}
    t = b.to_arrow()
    assert t.num_rows == 3
    b2 = RecordBatch.from_arrow(t)
    assert b2.to_pydict() == b.to_pydict()


def test_row_ops():
    b = RecordBatch.from_pydict({"a": [1, 2, 3, 4], "b": ["w", "x", "y", "z"]})
    assert b.slice(1, 3).to_pydict() == {"a": [2, 3], "b": ["x", "y"]}
    assert b.take(np.array([3, 0])).to_pydict() == {"a": [4, 1], "b": ["z", "w"]}
    mask = Series.from_pylist([True, False, True, False])
    assert b.filter_by_mask(mask).to_pydict() == {"a": [1, 3], "b": ["w", "y"]}
    c = RecordBatch.concat([b, b.slice(0, 1)])
    assert c.num_rows == 5


def test_multi_sort():
    b = RecordBatch.from_pydict({"g": ["b", "a", "b", "a"], "v": [1, 4, 3, 2]})
    idx = multi_argsort([b.get_column("g"), b.get_column("v")], [False, True])
    out = b.take(idx).to_pydict()
    assert out["g"] == ["a", "a", "b", "b"]
    assert out["v"] == [4, 2, 3, 1]


def test_multi_sort_nulls():
    b = RecordBatch.from_pydict({"v": [10.5, 20.0, None, 5.25]})
    asc = b.take(multi_argsort([b.get_column("v")], [False])).to_pydict()["v"]
    assert asc == [5.25, 10.5, 20.0, None]
    desc = b.take(multi_argsort([b.get_column("v")], [True])).to_pydict()["v"]
    assert desc == [None, 20.0, 10.5, 5.25]
    desc_nl = b.take(multi_argsort([b.get_column("v")], [True], [False])).to_pydict()["v"]
    assert desc_nl == [20.0, 10.5, 5.25, None]


def test_make_groups():
    keys = [Series.from_pylist(["a", "b", "a", None, "b", None], "k")]
    first_idx, gids, counts = make_groups(keys)
    assert list(first_idx) == [0, 1, 3]
    assert list(gids) == [0, 1, 0, 2, 1, 2]
    assert list(counts) == [2, 2, 2]


def test_join_indices_inner():
    l = [Series.from_pylist([1, 2, 3, None], "k")]
    r = [Series.from_pylist([2, 2, 4, None], "k")]
    lidx, ridx = join_indices(l, r, "inner")
    pairs = sorted(zip(lidx.tolist(), ridx.tolist()))
    assert pairs == [(1, 0), (1, 1)]


def test_join_indices_left_outer():
    l = [Series.from_pylist([1, 2], "k")]
    r = [Series.from_pylist([2, 3], "k")]
    lidx, ridx = join_indices(l, r, "left")
    assert set(zip(lidx.tolist(), ridx.tolist())) == {(1, 0), (0, -1)}
    lidx, ridx = join_indices(l, r, "outer")
    assert set(zip(lidx.tolist(), ridx.tolist())) == {(1, 0), (0, -1), (-1, 1)}


def test_join_semi_anti():
    l = [Series.from_pylist([1, 2, 3], "k")]
    r = [Series.from_pylist([2], "k")]
    lidx, _ = join_indices(l, r, "semi")
    assert lidx.tolist() == [1]
    lidx, _ = join_indices(l, r, "anti")
    assert lidx.tolist() == [0, 2]


def test_multicol_join():
    l = [Series.from_pylist([1, 1, 2], "a"), Series.from_pylist(["x", "y", "x"], "b")]
    r = [Series.from_pylist([1, 2], "a"), Series.from_pylist(["y", "x"], "b")]
    lidx, ridx = join_indices(l, r, "inner")
    assert sorted(zip(lidx.tolist(), ridx.tolist())) == [(1, 0), (2, 1)]


def test_partition_by_hash():
    b = RecordBatch.from_pydict({"k": list(range(100)), "v": list(range(100))})
    parts = b.partition_by_hash([b.get_column("k")], 4)
    assert len(parts) == 4
    assert sum(p.num_rows for p in parts) == 100
    all_k = sorted(v for p in parts for v in p.to_pydict()["k"])
    assert all_k == list(range(100))
    # same key always goes to same partition
    parts2 = b.partition_by_hash([b.get_column("k")], 4)
    assert [p.to_pydict() for p in parts] == [p.to_pydict() for p in parts2]


def test_partition_by_range():
    b = RecordBatch.from_pydict({"k": [5, 1, 9, 3, 7]})
    boundaries = RecordBatch.from_pydict({"k": [4, 8]})
    parts = b.partition_by_range([b.get_column("k")], boundaries, [False])
    assert len(parts) == 3
    assert sorted(parts[0].to_pydict()["k"]) == [1, 3]
    assert sorted(parts[1].to_pydict()["k"]) == [5, 7]
    assert sorted(parts[2].to_pydict()["k"]) == [9]


def test_partition_by_value():
    b = RecordBatch.from_pydict({"k": ["a", "b", "a"], "v": [1, 2, 3]})
    parts, keys = b.partition_by_value([b.get_column("k")])
    assert len(parts) == 2
    assert keys.to_pydict() == {"k": ["a", "b"]}
    assert parts[0].to_pydict() == {"k": ["a", "a"], "v": [1, 3]}


def test_micropartition():
    b1 = RecordBatch.from_pydict({"a": [1, 2]})
    b2 = RecordBatch.from_pydict({"a": [3]})
    mp = MicroPartition.from_batches([b1, b2])
    assert len(mp) == 3
    assert mp.to_pydict() == {"a": [1, 2, 3]}
    assert mp.head(2).to_pydict() == {"a": [1, 2]}
    assert mp.slice(1, 3).to_pydict() == {"a": [2, 3]}
    stats = mp.statistics()
    assert stats.columns["a"].min == 1
    assert stats.columns["a"].max == 3
    morsels = mp.split_into_batches(1)
    assert len(morsels) == 3


def test_cast_to_schema():
    b = RecordBatch.from_pydict({"a": [1, 2]})
    target = Schema.from_pydict({"a": DataType.float64(), "b": DataType.string()})
    out = b.cast_to_schema(target)
    assert out.to_pydict() == {"a": [1.0, 2.0], "b": [None, None]}


# ---- RecordBatch.concat of morsel views (what _concat_parts does to a dim) ------------

def _table(n=90):
    return RecordBatch.from_pydict({
        "k": list(range(n)),
        "s": [None if i % 5 == 0 else f"s{i % 4}" for i in range(n)],
        "f": [i / 2 for i in range(n)],
    })


@pytest.mark.parametrize("bounds,glued", [
    ([(0, 30), (30, 60), (60, 90)], "root"),     # the morsels of a whole table
    ([(30, 60), (60, 90)], "range"),             # a contiguous part of it
    ([(60, 90), (0, 60)], None),                 # out of order
    ([(0, 30), (60, 90)], None),                 # a gap
    ([(0, 40), (30, 90)], None),                 # overlapping
], ids=["covers", "range", "out_of_order", "gap", "overlap"])
def test_concat_of_morsel_views(bounds, glued):
    table = _table()
    parts = [table.slice(a, b) for a, b in bounds]
    out = RecordBatch.concat(parts)
    want = {c: [x for p in parts for x in p.to_pydict()[c]] for c in table.column_names()}
    assert out.to_pydict() == want
    assert out.schema == table.schema and out.num_rows == sum(b - a for a, b in bounds)
    for c in table.column_names():
        col, root = out.get_column(c), table.get_column(c)
        if glued == "root":
            assert col is root                   # the table's own column: identity kept
        elif glued == "range":
            assert col.lineage() == (root, bounds[0][0])
        else:
            assert col.lineage() == (col, 0)     # copied as before


def test_concat_mixed_views_and_new_data_copies():
    table = _table()
    parts = [table.slice(0, 45), table.slice(45, 90).take(np.arange(45))]
    out = RecordBatch.concat(parts)
    assert out.to_pydict() == table.to_pydict()
    assert all(out.get_column(c) is not table.get_column(c) for c in table.column_names())

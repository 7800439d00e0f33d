"""`Series.content_fingerprint`: a pure function of a column's content
(dtype, length, values, validity), read over the Arrow buffers where they lie
and in fixed chunks across the compute pool."""

import pickle
import threading

import numpy as np
import pyarrow as pa
import pytest

import daft_tpu.core.series as series_mod
from daft_tpu.core.series import Series
from daft_tpu.observability.metrics import registry
from daft_tpu.utils import pool as pool_mod

N = 1000
_COUNTERS = ("content_hash_bytes", "content_hash_inplace", "content_hash_copied")


def _values(kind: str, n: int = N, seed: int = 0) -> list:
    rng = np.random.default_rng(seed)
    if kind == "float64":
        return rng.random(n).tolist()
    if kind == "int64":
        return rng.integers(0, 1 << 40, n).tolist()
    if kind == "date32":
        return rng.integers(0, 20000, n).tolist()
    if kind == "bool":
        return rng.integers(0, 2, n).astype(bool).tolist()
    return [f"s{i % 97}-{v}" for i, v in enumerate(rng.integers(0, 50, n))]


_ARROW = {"float64": pa.float64(), "int64": pa.int64(), "date32": pa.date32(),
          "bool": pa.bool_(), "large_string": pa.large_string()}
KINDS = tuple(_ARROW)


def _array(kind: str, nulls: bool, n: int = N, seed: int = 0) -> pa.Array:
    vals = _values(kind, n, seed)
    if nulls:
        mask = np.random.default_rng(seed + 1).random(n) < 0.2
        vals = [None if m else v for v, m in zip(vals, mask)]
    return pa.array(vals, type=_ARROW[kind])


def _fp(arr) -> int:
    fp = Series.from_arrow(arr, "c").content_fingerprint()
    assert fp is not None
    return fp


def _delta(fn) -> dict:
    before = {c: registry().get(c) for c in _COUNTERS}
    fn()
    return {c: registry().get(c) - before[c] for c in _COUNTERS}


# a slice at an offset that is neither 0 nor a multiple of 8 (its validity
# bitmap starts inside a byte, a string's offsets at its parent's data)
_OFF, _LEN = 13, 501


def _layout(arr: pa.Array, layout: str):
    rows = arr.slice(_OFF, _LEN)
    if layout == "whole":
        return pa.array(arr.to_pylist(), type=arr.type), arr
    fresh = pa.array(rows.to_pylist(), type=arr.type)
    if layout == "slice":
        return rows, fresh
    if layout == "pickled_slice":
        return pickle.loads(pickle.dumps(rows)), fresh
    assert layout == "chunked"
    return pa.chunked_array([rows.slice(0, 100), rows.slice(100, 7),
                             rows.slice(107)]), fresh


@pytest.mark.parametrize("nulls", [False, True], ids=["dense", "nulls"])
@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("layout", ["whole", "slice", "pickled_slice", "chunked"])
def test_equal_content_equal_fingerprint_across_layouts(layout, kind, nulls):
    got, fresh = _layout(_array(kind, nulls), layout)
    assert _fp(got) == _fp(fresh)


@pytest.mark.parametrize("nulls", [False, True], ids=["dense", "nulls"])
@pytest.mark.parametrize("kind", KINDS)
def test_series_pickle_round_trip_keeps_the_fingerprint(kind, nulls):
    s = Series.from_arrow(_array(kind, nulls), "c").slice(_OFF, _OFF + _LEN)
    copy = pickle.loads(pickle.dumps(s))
    assert copy.content_fingerprint() == s.content_fingerprint() is not None
    assert s.rename("other").content_fingerprint() == s.content_fingerprint()


def _changed(kind: str, arr: pa.Array, what: str) -> pa.Array:
    vals = arr.to_pylist()
    i = next(i for i, v in enumerate(vals) if v is not None)
    if what == "value":
        vals[i] = next(v for v in _values(kind, 8, seed=9)
                       if pa.scalar(v, type=arr.type) != arr[i])
    elif what == "validity":
        vals[i] = None
    else:
        assert what == "length"
        vals = vals[:-1]
    return pa.array(vals, type=arr.type)


@pytest.mark.parametrize("nulls", [False, True], ids=["dense", "nulls"])
@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("what", ["value", "validity", "length"])
def test_different_content_different_fingerprint(what, kind, nulls):
    arr = _array(kind, nulls)
    assert _fp(_changed(kind, arr, what)) != _fp(arr)


@pytest.mark.parametrize("a, b", [
    (pa.int64(), pa.float64()), (pa.int64(), pa.uint64()),
    (pa.int32(), pa.date32()), (pa.large_string(), pa.large_binary()),
], ids=str)
def test_same_bytes_under_another_dtype_differ(a, b):
    if pa.types.is_large_string(a):
        left = pa.array(["x", "yz", ""], type=a)
        right = pa.array([b"x", b"yz", b""], type=b)
    else:
        raw = np.arange(64, dtype=np.int64 if a.bit_width == 64 else np.int32)
        left = pa.array(raw, type=a)
        right = pa.Array.from_buffers(b, len(raw), left.buffers())
    assert _fp(left) != _fp(right)


def _with_null_slots(kind: str, under_nulls):
    """Four rows, rows 1 and 3 null, with `under_nulls` in the buffers there."""
    validity = pa.py_buffer(bytes([0b0101]))
    if kind == "float64":
        values = np.array([1.5, under_nulls, 2.5, under_nulls])
        return pa.Array.from_buffers(pa.float64(), 4,
                                     [validity, pa.py_buffer(values.tobytes())])
    junk = under_nulls.encode()
    data = b"ab" + junk + b"cd" + junk
    ends = np.cumsum([0, 2, len(junk), 2, len(junk)]).astype(np.int64)
    return pa.Array.from_buffers(pa.large_string(), 4,
                                 [validity, pa.py_buffer(ends.tobytes()),
                                  pa.py_buffer(data)])


@pytest.mark.parametrize("kind, one, other", [
    ("float64", 0.0, 77.25), ("large_string", "", "junk")])
def test_bytes_under_null_slots_do_not_count(kind, one, other):
    a, b = _with_null_slots(kind, one), _with_null_slots(kind, other)
    assert a.to_pylist() == b.to_pylist() and a.null_count == 2
    assert _fp(a) == _fp(b)
    clean = pa.array(a.to_pylist(), type=a.type)
    assert _fp(clean) == _fp(a)


@pytest.fixture
def small_chunks(monkeypatch):
    monkeypatch.setattr(series_mod, "_HASH_CHUNK_BYTES", 1024)


@pytest.fixture
def pool_maps(monkeypatch):
    """The real compute pool, with every `map` handed to it noted."""
    real = pool_mod.compute_pool()
    calls = []

    class Noting:
        def map(self, fn, items):
            items = list(items)
            calls.append(len(items))
            return real.map(fn, items)

    monkeypatch.setattr(pool_mod, "compute_pool", lambda: Noting())
    return real, calls


@pytest.mark.parametrize("nulls", [False, True], ids=["dense", "nulls"])
@pytest.mark.parametrize("kind", KINDS)
def test_pool_and_inline_agree_over_many_chunks(kind, nulls, small_chunks,
                                                pool_maps, monkeypatch):
    _real, calls = pool_maps
    arr = _array(kind, nulls, n=3000 if kind != "bool" else 30000)
    pooled = _fp(arr)
    assert len(calls) == 1 and calls[0] > 2      # more than two chunks, on the pool
    monkeypatch.setattr(pool_mod, "on_pool_thread", lambda: True)
    assert _fp(arr) == pooled and len(calls) == 1


def test_the_chunking_is_part_of_the_value(monkeypatch):
    """Chunk digests are folded, so the constant is part of the fingerprint's
    definition: every process of a build shares it."""
    arr = _array("float64", False, n=3000)
    whole = _fp(arr)
    monkeypatch.setattr(series_mod, "_HASH_CHUNK_BYTES", 1024)
    assert _fp(arr) != whole


def test_a_column_under_one_chunk_is_hashed_inline(pool_maps):
    _real, calls = pool_maps
    _fp(_array("large_string", False))
    _fp(_array("float64", True))
    assert calls == []


@pytest.mark.parametrize("kind", ["float64", "large_string"])
def test_a_pool_thread_hashes_inline(kind, small_chunks, pool_maps):
    real, calls = pool_maps
    arr = _array(kind, False, n=3000)
    expect = _fp(arr)
    assert len(calls) == 1
    s = Series.from_arrow(arr, "c")
    where = []

    def on_the_pool():
        where.append((threading.current_thread().name, pool_mod.on_pool_thread()))
        return s.content_fingerprint()

    assert real.submit(on_the_pool).result(timeout=60) == expect
    assert where[0][0].startswith("daft-compute") and where[0][1] is True
    assert len(calls) == 1                        # nothing more went to the pool
    assert pool_mod.on_pool_thread() is False


@pytest.mark.parametrize("nulls", [False, True], ids=["dense", "nulls"])
@pytest.mark.parametrize("kind", ["large_string", "large_binary"])
def test_a_string_column_is_never_made_python_objects(kind, nulls, monkeypatch):
    vals = _array("large_string", nulls).to_pylist()
    if kind == "large_binary":
        vals = [None if v is None else v.encode() for v in vals]
    arr = pa.array(vals, type=getattr(pa, kind)())

    def no(*_a, **_k):
        raise AssertionError("a string column was asked for its numpy form")

    monkeypatch.setattr(Series, "to_numpy", no)
    monkeypatch.setattr(Series, "to_pylist", no)
    assert _fp(arr.slice(_OFF, _LEN)) == _fp(pa.array(vals[_OFF:_OFF + _LEN], type=arr.type))


def _expected_bytes(kind: str, arr: pa.Array) -> int:
    validity = (len(arr) + 7) // 8 if arr.null_count else 0
    if kind == "large_string":
        text = sum(len(v.encode()) for v in arr.to_pylist() if v is not None)
        return 8 * (len(arr) + 1) + text + validity
    if kind == "bool":
        return len(arr) + validity                # the dense numpy form, a byte a row
    return arr.type.bit_width // 8 * len(arr) + validity


@pytest.mark.parametrize("nulls", [False, True], ids=["dense", "nulls"])
@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("sliced", [False, True], ids=["whole", "slice"])
def test_counters_say_how_a_column_was_read(sliced, kind, nulls):
    arr = _array(kind, nulls)
    if sliced:
        arr = arr.slice(_OFF, _LEN)
    s = Series.from_arrow(arr, "c")
    d = _delta(s.content_fingerprint)
    # in place: no nulls, not a boolean, and a string's offsets starting at 0
    inplace = not nulls and kind != "bool" \
        and not (sliced and kind == "large_string")
    assert d == {"content_hash_bytes": _expected_bytes(kind, arr),
                 "content_hash_inplace": int(inplace),
                 "content_hash_copied": int(not inplace)}
    assert _delta(s.content_fingerprint) == dict.fromkeys(_COUNTERS, 0)  # cached


def test_no_stable_identity_counts_nothing():
    s = Series.from_pylist([object(), object()], "o")
    d = _delta(lambda: s.content_fingerprint())
    assert s.content_fingerprint() is None and d == dict.fromkeys(_COUNTERS, 0)


@pytest.mark.parametrize("kind", KINDS)
def test_an_empty_column_has_a_fingerprint_of_its_dtype(kind):
    empty = pa.array([], type=_ARROW[kind])
    assert _fp(empty) == _fp(_array(kind, False).slice(5, 0))
    others = {_fp(pa.array([], type=t)) for k, t in _ARROW.items() if k != kind}
    assert _fp(empty) not in others

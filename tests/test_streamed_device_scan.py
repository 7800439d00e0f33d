"""A grouped aggregate fed from a file scan, on the device tier (PR 28): what
kept it slower than the host and therefore off the device under `auto`. The
morsels of a stream are uploaded without a content fingerprint, the group
keys a Parquet file stores dictionary-encoded keep the file's codes, a string
key is encoded by Arrow alone, and the cost decision prices that. Device
paths run with device_mode="on" on the CPU backend."""

import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import daft_tpu as dt
from daft_tpu import col
from daft_tpu.config import execution_config_ctx
from daft_tpu.core.series import Series
from daft_tpu.device.residency import manager
from daft_tpu.observability.metrics import registry
from daft_tpu.observability.runtime_stats import SpanRecorder, set_spans

ROWS = 6_000


def write_files(where, n_files=3, rows=ROWS):
    rng = np.random.default_rng(5)
    paths = []
    for k in range(n_files):
        t = pa.table({
            "flag": pa.array([("A", "N", "R")[i % 3] for i in range(rows)], pa.large_string()),
            "status": pa.array([("F", "O")[(i // 7) % 2] for i in range(rows)], pa.string()),
            "note": pa.array([f"note {k}-{i}" for i in range(rows)], pa.large_string()),
            "qty": pa.array(rng.integers(1, 50, rows).astype(np.float64)),
            "price": pa.array(rng.uniform(1, 1000, rows)),
        })
        path = os.path.join(str(where), f"part.{k:04d}.parquet")
        pq.write_table(t, path)
        paths.append(path)
    return paths


def grouped(paths):
    return (dt.read_parquet(paths).groupby("flag", "status")
            .agg(col("qty").sum().alias("q"), col("price").mean().alias("p"),
                 col("qty").count().alias("n"))
            .sort(["flag", "status"]))


# ---- a stream's planes are not fingerprinted -------------------------------------------


def test_a_grouped_stage_over_files_hashes_no_column_and_encodes_no_key(tmp_path, monkeypatch):
    """The test that would have caught it: forced onto the device, q1's shape
    over Parquet files spent 650 ms of 1,500 in blake2b over every morsel's
    columns (a slot no later anchor can find) and 480 ms hashing two key
    columns the files store as dictionaries."""
    paths = write_files(tmp_path)
    hashed = []
    monkeypatch.setattr(Series, "content_fingerprint",
                        lambda self: hashed.append(self.name) or 1)
    with execution_config_ctx(device_mode="off"):
        want = grouped(paths).to_pydict()
    rec = SpanRecorder()
    reg = registry()
    before = {k: reg.get(k) for k in ("hbm_cache_misses", "hbm_stable_rehits")}
    # no coalescing: morsels the coalescer concatenates are new columns,
    # which Arrow encodes again (PERF.md section 7)
    with execution_config_ctx(device_mode="on", device_min_rows=1, batch_fill_target=0.0):
        set_spans(rec)
        try:
            got = grouped(paths).to_pydict()
        finally:
            set_spans(None)
    assert got["flag"] == want["flag"] and got["status"] == want["status"]
    assert got["n"] == want["n"]
    assert got["q"] == pytest.approx(want["q"], rel=1e-5)
    assert got["p"] == pytest.approx(want["p"], rel=1e-5)
    names = [s["name"] for s in rec.drain()]
    assert names.count("device.dispatch") >= 1            # it did run on the device tier
    assert reg.get("hbm_cache_misses") > before["hbm_cache_misses"]  # planes were built
    assert hashed == []                                   # and none was fingerprinted
    assert "series.dict_encode" not in names              # the files' own codes were kept


def test_a_resident_tables_planes_are_still_fingerprinted(monkeypatch):
    """`transient` is the stream's alone: an in-memory table's slots keep the
    content key that lets an equal column under a new identity rebind."""
    hashed = []
    real = Series.content_fingerprint
    monkeypatch.setattr(Series, "content_fingerprint",
                        lambda self: hashed.append(self.name) or real(self))
    df = dt.from_pydict({"g": ["a", "b"] * 500, "v": [float(i) for i in range(1000)]}).collect()
    with execution_config_ctx(device_mode="on", device_min_rows=1):
        df.groupby("g").agg(col("v").sum().alias("s")).to_pydict()
    assert "v" in hashed


def test_transient_scope_nests_and_restores(monkeypatch):
    import daft_tpu.device.residency as r

    m = manager()
    s = Series.from_pylist([1.0, 2.0, 3.0], "x")
    calls = []
    real = r.stable_slot_key
    monkeypatch.setattr(r, "stable_slot_key", lambda a, k: calls.append(k) or real(a, k))
    with m.pin_scope(transient=True):
        with m.pin_scope():            # an inner scope of a stream is the stream's
            m.get_or_build(s, ("t", 1), (), lambda: "v1")
        m.get_or_build(s, ("t", 2), (), lambda: "v2")
    assert calls == []
    with m.pin_scope():
        m.get_or_build(s, ("t", 3), (), lambda: "v3")
    assert calls == [("t", 3)]
    # found again by identity, fingerprinted or not
    with m.pin_scope(transient=True):
        assert m.get_or_build(s, ("t", 1), (), lambda: "other") == "v1"


# ---- dictionaries ----------------------------------------------------------------------


def general_codes(s):
    """What `dict_codes` gave before PR 28, for every column: make_groups."""
    from daft_tpu.core.kernels.groupby import make_groups

    first_idx, group_ids, _ = make_groups([s])
    return group_ids.astype(np.int32), s.take(first_idx).to_pylist()


@pytest.mark.parametrize("arr", [
    pa.array(["b", "a", "b", "c", "a", "b"], pa.large_string()),
    pa.array(["x"] * 5, pa.string()),
    pa.array([b"k1", b"k0", b"k1"], pa.large_binary()),
    pa.array([], pa.large_string()),
    pa.chunked_array([pa.array(["q", "p"], pa.large_string()),
                      pa.array(["p", "r"], pa.large_string())]),
], ids=["strings", "one-value", "binary", "empty", "chunked"])
def test_arrows_own_encoding_equals_the_general_path(arr):
    s = Series.from_arrow(arr, "k")
    codes, values, k = s.dict_codes()
    want_codes, want_values = general_codes(Series.from_arrow(arr, "k"))
    assert codes.dtype == np.int32 and codes.tolist() == want_codes.tolist()
    assert values == want_values and k == len(want_values)
    assert s._arrow_dict_codes() is not None


@pytest.mark.parametrize("arr", [
    pa.array(["b", None, "b"], pa.large_string()),     # nulls take the general path
    pa.array([3, 1, 3], pa.int64()),
    pa.array([1.5, 1.5], pa.float64()),
], ids=["nulls", "ints", "floats"])
def test_other_columns_take_the_general_path_as_before(arr):
    s = Series.from_arrow(arr, "k")
    assert s._arrow_dict_codes() is None
    codes, values, k = s.dict_codes()
    want_codes, want_values = general_codes(Series.from_arrow(arr, "k"))
    assert codes.tolist() == want_codes.tolist() and values == want_values


def test_a_column_that_arrives_dictionary_encoded_keeps_its_codes():
    # the file's dictionary is in the file's order and may hold values no row uses
    enc = pa.DictionaryArray.from_arrays(pa.array([2, 0, 2, 1, 0], pa.int32()),
                                         pa.array(["x", "y", "z", "unused"]))
    s = Series.from_arrow(enc, "k")
    assert s.to_pylist() == ["z", "x", "z", "y", "x"] and s.dtype.is_string()
    kept = s._dict_codes
    assert kept[0].tolist() == [0, 1, 0, 2, 1] and kept[1] == ["z", "x", "y"] and kept[2] == 3
    plain = Series.from_arrow(pa.array(["z", "x", "z", "y", "x"]), "k").dict_codes()
    assert kept[0].tolist() == plain[0].tolist() and kept[1] == plain[1]
    # nulls among the rows, and a dictionary that repeats a value: nothing is kept
    with_null = pa.DictionaryArray.from_arrays(pa.array([0, None, 1], pa.int32()),
                                               pa.array(["a", "b"]))
    assert getattr(Series.from_arrow(with_null, "k"), "_dict_codes", None) is None
    repeats = pa.DictionaryArray.from_arrays(pa.array([0, 1, 2], pa.int32()),
                                             pa.array(["a", "b", "a"]))
    r = Series.from_arrow(repeats, "k")
    assert getattr(r, "_dict_codes", None) is None and r.dict_codes()[1] == ["a", "b"]
    # integers under a dictionary are decoded as before and keep nothing
    ints = pa.DictionaryArray.from_arrays(pa.array([1, 0], pa.int32()), pa.array([7, 9]))
    i = Series.from_arrow(ints, "k")
    assert i.to_pylist() == [9, 7] and getattr(i, "_dict_codes", None) is None


def scanned_columns(paths):
    parts = list(dt.read_parquet(paths).iter_partitions())
    return {name: [b.get_column(name) for p in parts for b in p.batches]
            for name in parts[0].schema.column_names()}


def test_the_readers_take_low_cardinality_string_columns_as_dictionaries(tmp_path):
    from daft_tpu.io import parquet as pio

    paths = write_files(tmp_path, n_files=2)
    md = pq.ParquetFile(paths[0]).metadata
    schema = dt.read_parquet(paths).schema
    assert pio._dictionary_candidates(None, schema) == ["flag", "note", "status"]
    assert pio._dictionary_candidates(["flag", "qty"], schema) == ["flag"]
    assert pio._dictionary_candidates(None, schema, frozenset({"flag"})) == ["note", "status"]
    # `note` is unique a row: its pages are far over two bytes a value
    assert pio._dictionary_columns(md, ["flag", "note", "status"]) == ["flag", "status"]
    assert pio._dictionary_columns(None, ["flag"]) == []
    assert pio._dictionary_columns(md, ["absent"]) == []

    cols = scanned_columns(paths)
    for name in ("flag", "status"):
        for s in cols[name]:
            assert s.dtype.is_string() and s._dict_codes[2] == (3 if name == "flag" else 2)
            assert [s._dict_codes[1][c] for c in s._dict_codes[0][:50]] == s.to_pylist()[:50]
    assert all(getattr(s, "_dict_codes", None) is None for s in cols["note"])
    # a column the pushed-down filter reads is left to the scanner as it is
    import pyarrow.dataset as pads

    read = pio._make_reader(paths[0], ["flag", "status"], pads.field("flag") == "A", None,
                            schema.select(["flag", "status"]),
                            filter_columns=frozenset({"flag"}))
    batches = [b for part in read() for b in part.batches]
    assert sum(b.num_rows for b in batches) == ROWS // 3
    assert all(getattr(b.get_column("flag"), "_dict_codes", None) is None for b in batches)
    assert all(b.get_column("status")._dict_codes[2] == 2 for b in batches)


def test_row_group_split_tasks_read_dictionaries_too(tmp_path):
    t = pa.table({"flag": pa.array([("A", "N", "R")[i % 3] for i in range(4_000)],
                                   pa.large_string()),
                  "v": pa.array([float(i) for i in range(4_000)])})
    path = os.path.join(str(tmp_path), "one.parquet")
    pq.write_table(t, path, row_group_size=1_000)
    df = dt.read_parquet(path, row_groups_per_task=1)
    cols = [b.get_column("flag") for p in df.iter_partitions() for b in p.batches]
    assert sum(len(s) for s in cols) == 4_000
    assert all(s._dict_codes[2] == 3 for s in cols)
    assert [x for s in cols for x in s.to_pylist()] == t.column("flag").to_pylist()


# ---- the decision ----------------------------------------------------------------------


def test_the_decision_prices_an_arrow_encoded_key_at_its_own_rate():
    from daft_tpu.execution.executor import _dict_build_rows
    from daft_tpu.ops import costmodel

    cal = costmodel.Calibration(
        rtt_s=1e-3, h2d_bytes_per_s=1e9, d2h_bytes_per_s=1e9, mm_plane_rows_per_s=5e9,
        mm_cell_rate=5e10, scatter_rows_per_s=1e8, ext_cell_rate=5e9, host_agg_rate=1.5e8,
        host_factorize_rate=8e6, host_probe_rate=3e7)
    assert cal.host_dict_encode_rate == 4e7
    strings = Series.from_arrow(pa.array(["a", "b"] * 500, pa.large_string()), "s")
    with_null = Series.from_arrow(pa.array(["a", None] * 500, pa.large_string()), "n")
    ints = Series.from_pylist(list(range(1000)), "i")
    assert _dict_build_rows([ints], 1000, cal) == 1000
    assert _dict_build_rows([with_null], 1000, cal) == 1000
    assert _dict_build_rows([strings], 1000, cal) == 200      # 8e6 / 4e7 of the rows
    assert _dict_build_rows([strings, ints], 1000, cal) == 1200
    strings.dict_codes()                                       # cached: nothing left to build
    assert _dict_build_rows([strings], 1000, cal) == 0


# ---- a morsel's planes go to the device in one transfer (PR 35) -------------------------


def ungrouped(paths):
    return (dt.read_parquet(paths).where(col("qty") < 25.0)
            .agg(col("price").sum().alias("p"), col("qty").mean().alias("q"),
                 col("price").count().alias("n")))


H2D = ("h2d_transfers", "h2d_planes", "hbm_h2d_bytes", "hbm_cache_misses", "hbm_pins")


def on_the_device(query, paths):
    """(answer, counter deltas, dispatches, `device.upload` spans) of `query`
    over `paths`, forced onto the device tier, a dispatch a file."""
    from daft_tpu.ops import counters

    reg = registry()
    before = {k: reg.get(k) for k in H2D}
    d0 = counters.device_grouped_batches + counters.device_stage_batches
    rec = SpanRecorder()
    with execution_config_ctx(device_mode="on", device_min_rows=1, batch_fill_target=0.0):
        set_spans(rec)
        try:
            got = query(paths).to_pydict()
        finally:
            set_spans(None)
    uploads = [s for s in rec.drain() if s["name"] == "device.upload"]
    return (got, {k: reg.get(k) - before[k] for k in H2D},
            counters.device_grouped_batches + counters.device_stage_batches - d0, uploads)


# the scanner applies `ungrouped`'s filter: about 2,900 of a file's 6,000 rows reach the stage
@pytest.mark.parametrize("query, values, codes, bucket", [(grouped, 2, 2, 8192), (ungrouped, 2, 0, 4096)],
                         ids=["grouped", "ungrouped"])
def test_a_streamed_dispatch_makes_one_transfer_and_uploads_no_validity(tmp_path, query, values, codes,
                                                                        bucket):
    """No column of the files has a null: every dispatch moves its value
    planes (and the grouped stage's two code planes) in one transfer, no
    validity plane among them, and `hbm_h2d_bytes` counts the value planes."""
    paths = write_files(tmp_path)
    got, delta, dispatches, uploads = on_the_device(query, paths)
    assert dispatches == len(paths)
    assert delta["h2d_transfers"] == dispatches
    assert delta["h2d_planes"] == (values + codes) * dispatches
    assert delta["hbm_h2d_bytes"] == values * dispatches * bucket * 4   # float32 value planes alone
    # a plane is still a slot of its own in the manager, pinned for the query
    assert delta["hbm_cache_misses"] == delta["hbm_pins"] == (values + codes) * dispatches
    assert [s["args"]["planes"] for s in uploads] == [values + codes] * dispatches
    assert sum(s["args"]["bytes"] for s in uploads) == (values + codes) * dispatches * bucket * 4
    with execution_config_ctx(device_mode="off"):
        want = query(paths).to_pydict()
    assert got["n"] == want["n"]
    assert got["p"] == pytest.approx(want["p"], rel=1e-5)
    assert got["q"] == pytest.approx(want["q"], rel=1e-5)


def write_nullable(where, case):
    """Two files of the grouped/ungrouped queries' columns; `case` says where
    the nulls are and whether a file's rows fill their bucket."""
    rows = 8192 if case == "fills_bucket" else 5000
    rng = np.random.default_rng(11)
    paths = []
    for k in range(2):
        qty = rng.integers(1, 50, rows).astype(np.float64)
        price = rng.uniform(1, 1000, rows)
        qty_mask = price_mask = None
        if case == "some_nulls":
            qty_mask, price_mask = rng.random(rows) < 0.2, rng.random(rows) < 0.3
        elif case == "all_nulls":
            price_mask = np.ones(rows, dtype=bool)
        t = pa.table({
            "flag": pa.array([("A", "N", "R")[i % 3] for i in range(rows)], pa.large_string()),
            "status": pa.array([("F", "O")[(i // 7) % 2] for i in range(rows)], pa.string()),
            "qty": pa.array(qty, mask=qty_mask),
            "price": pa.array(price, mask=price_mask),
        })
        path = os.path.join(str(where), f"{case}.{k}.parquet")
        pq.write_table(t, path)
        paths.append(path)
    return paths


@pytest.mark.parametrize("query", [grouped, ungrouped], ids=["grouped", "ungrouped"])
@pytest.mark.parametrize("case", ["fills_bucket", "shorter_than_bucket", "some_nulls", "all_nulls"])
def test_a_streamed_stage_answers_as_the_host_tier_does(tmp_path, case, query):
    """A column without nulls reads the dispatch's row mask as its validity, a
    column with nulls its own plane from the same transfer; padding rows count
    for nothing either way."""
    paths = write_nullable(tmp_path, case)
    with execution_config_ctx(device_mode="off"):
        want = query(paths).to_pydict()
    got, delta, dispatches, _uploads = on_the_device(query, paths)
    assert dispatches == len(paths) and delta["h2d_transfers"] == dispatches
    # the scanner's `qty < 25` drops the rows whose qty is null: what reaches
    # the ungrouped stage has nulls in `price` alone, and only that is asked
    nullable = {"some_nulls": 2 if query is grouped else 1, "all_nulls": 1}.get(case, 0)
    codes = 2 if query is grouped else 0
    assert delta["h2d_planes"] == (2 + nullable + codes) * dispatches
    assert list(got) == list(want)
    for name in want:
        assert len(got[name]) == len(want[name])
        for g, w in zip(got[name], want[name]):
            if isinstance(w, float):
                assert g == pytest.approx(w, rel=1e-5), (name, got[name], want[name])
            else:
                assert g == w, (name, got[name], want[name])

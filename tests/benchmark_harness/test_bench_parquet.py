"""The `tpch_parquet` suite (`tpch_sf1.parquet_scan`): the files its generator
writes are a lossless copy of the tables, lie outside the checkout and go
with the process; a scan over several of them is correct through the harness;
the suite refuses a program that keeps the streamed q1 off the device; and
the cell's per-layer readers on hand-made executions. On the CPU at a size a
test can hold: nothing here is a measurement."""

import os
import subprocess
import sys

import pyarrow.parquet as pq
import pytest

import parquet_store
import run
from bench_helpers import BENCH, REPO, add_cell


def reader(name):
    return run.load_module(os.path.join(BENCH, "layer_metrics", name + ".py"))


def suite(kind):
    return run.load_module(os.path.join(BENCH, kind, "tpch_parquet.py"))


def test_the_files_read_back_equal_to_the_tables_value_for_value_and_type_for_type():
    arrow = suite("datagen").generate(0.02, 2**31 + 5, ["lineitem"], rows_per_file=50_000)
    table = arrow["lineitem"]
    files = parquet_store.paths("lineitem")
    assert len(files) == -(-table.num_rows // 50_000) >= 3
    assert files == sorted(files) and all(os.path.dirname(f) == parquet_store.directory()
                                          for f in files)
    start = 0
    for path in files:
        back = pq.read_table(path)
        want = table.slice(start, 50_000)
        assert back.schema.equals(table.schema), path   # type for type
        assert back.equals(want), path                  # value for value
        assert pq.ParquetFile(path).metadata.num_row_groups == 1
        start += back.num_rows
    assert start == table.num_rows
    # the default is the configuration's 1,048,576 rows a file
    assert parquet_store.ROWS_PER_FILE == 1_048_576
    parquet_store.discard()
    with pytest.raises(RuntimeError, match="lineitem"):
        parquet_store.paths("lineitem")


def test_a_new_write_replaces_the_old_files_and_lies_outside_the_checkout():
    gen = suite("datagen")
    gen.generate(0.002, 1, ["lineitem"])
    first = parquet_store.directory()
    gen.generate(0.002, 2, ["lineitem"])
    second = parquet_store.directory()
    assert first != second and not os.path.exists(first) and os.path.isdir(second)
    assert not os.path.abspath(second).startswith(REPO + os.sep)
    parquet_store.discard()
    assert not os.path.exists(second) and parquet_store.directory() is None


def test_the_directory_is_gone_after_the_process():
    code = ("import sys; sys.path.insert(0, %r)\n"
            "import pyarrow as pa, parquet_store\n"
            "print(parquet_store.write({'t': pa.table({'a': [1, 2, 3]})}, 2))\n"
            "import os; print(len(os.listdir(parquet_store.directory())))\n" % BENCH)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                         timeout=120, cwd=REPO)
    assert out.returncode == 0, out.stderr
    where, files = out.stdout.split()
    assert files == "2" and not os.path.exists(where)
    assert not os.path.abspath(where).startswith(REPO + os.sep)


def test_a_scan_over_several_files_is_correct_through_the_harness(bench_root, monkeypatch):
    """`lineitem` at SF0.02 cut into files of 25,000 rows: the multi-file scan
    of the cell, through `run.run_cell`, against the plain reference."""
    monkeypatch.setattr(parquet_store, "ROWS_PER_FILE", 25_000)
    add_cell(bench_root, "tiny.parquet_scan", "tiny", "parquet_scan", scale_factor=0.02)
    before = set(os.listdir(bench_root))
    result = run.run_cell(bench_root, "tiny.parquet_scan", seed=2**31 + 11, seconds=0.5,
                          trace=False, require_tpu=False)
    assert len(parquet_store.paths("lineitem")) >= 4
    assert result["correct"] is True and result["failed"] == 0, result
    assert result["attempted"] >= 2
    assert set(result["metrics"]) == {"query_ms.geomean", "scan_rows_per_s", "setup_s"}
    assert set(os.listdir(bench_root)) == before  # nothing is left in the checkout
    parquet_store.discard()


def test_the_cells_traffic_runs_and_is_correct_at_a_test_size(bench_root):
    """`test_bench_cells.py`'s case for the other cells, for this one (that file is the
    benchmark's and is not edited)."""
    add_cell(bench_root, "tiny.parquet_scan", "tiny", "parquet_scan", scale_factor=0.02)
    result = run.run_cell(bench_root, "tiny.parquet_scan", seed=2**31 + 3, seconds=0.5,
                          trace=False, require_tpu=False)
    assert result["correct"] is True, result
    assert result["failed"] == 0 and result["attempted"] >= 2
    assert result["metrics"]["query_ms.geomean"]["unit"] == "ms"
    assert result["metrics"]["scan_rows_per_s"]["value"] > 0
    assert set(result["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    parquet_store.discard()


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_in_bfloat16_is_not_correct(seed):
    """The reference with its float columns stored in bfloat16, put in the program's place,
    fails a float limit of the cell's own configuration (at a test's scale; the chip readings
    at the cell's own scale are in the configuration's file and PERF.md section 2)."""
    import compare

    cell = run.Cell(REPO, "tpch_sf1.parquet_scan")
    arrow = cell.datagen.generate(0.05, seed, cell.tables_read())
    verdicts = []
    for name in cell.templates:
        lim = compare.limits(cell.config, name)
        ref = cell.reference.answer(name, arrow)
        low = cell.reference.answer(name, arrow, cell.reference.to_bfloat16)
        assert compare.within(compare.compare(ref, ref), lim)
        verdicts.append(compare.within(compare.compare(ref, low), lim))
    assert not all(verdicts), verdicts
    parquet_store.discard()


def test_the_templates_are_the_scan_cells_own_over_read_parquet():
    queries = suite("queries")
    plain = run.load_module(os.path.join(BENCH, "queries", "tpch.py"))
    assert set(queries.TEMPLATES) == {"q1", "q6"}
    for name, t in queries.TEMPLATES.items():
        assert t["tables"] == ("lineitem",)
        assert t["scan_columns"] == plain.TEMPLATES[name]["scan_columns"]
    arrow = suite("datagen").generate(0.002, 3, ["lineitem"], rows_per_file=5_000)
    import daft_tpu as dt

    collected = {"lineitem": dt.from_arrow(arrow["lineitem"]).collect()}
    ref = suite("reference")
    for name in ("q1", "q6"):
        over_files = queries.TEMPLATES[name]["program"](None).to_pydict()  # ignores what it is handed
        assert list(over_files) == list(plain.TEMPLATES[name]["program"](collected).to_pydict())
        assert list(over_files) == list(ref.answer(name, arrow))
    parquet_store.discard()


def test_on_a_tpu_a_q1_that_never_dispatched_on_the_device_ends_the_run(monkeypatch, capsys):
    """What the parent of PR 28 does: `auto` keeps the streamed q1 on the host
    tier. The suite exits 1 with the reason when q1 is built the second time;
    where a dispatch was counted, and on any other backend, it goes on."""
    import jax

    suite("datagen").generate(0.002, 4, ["lineitem"])
    for backend, dispatched, ends in (("cpu", False, False), ("tpu", True, False),
                                      ("tpu", False, True)):
        queries = suite("queries")  # a fresh module: a fresh count of builds
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        batches = iter([0, 0] if not dispatched else [0, 46])
        monkeypatch.setattr(queries, "_device_batches", lambda it=batches: next(it))
        queries.TEMPLATES["q6"]["program"](None)
        queries.TEMPLATES["q1"]["program"](None)
        if ends:
            with pytest.raises(SystemExit) as e:
                queries.TEMPLATES["q1"]["program"](None)
            assert e.value.code == 1
            out = capsys.readouterr()
            assert "host tier" in out.out and "host tier" in out.err
        else:
            queries.TEMPLATES["q1"]["program"](None)
            queries.TEMPLATES["q1"]["program"](None)  # checked once only
    parquet_store.discard()


# one q1 over two files as the program records it since PR 28 (seconds on the
# spans' clock): the plan on the main thread, two tasks decoding on pool
# threads while the stage uploads and dispatches; then a q6 the host answers
SCAN_SPANS = [
    ("query", 0.0, 98.0),
    ("scan.plan", 1.0, 2.0), ("scan.plan", 2.0, 2.5), ("scan.plan", 3.0, 6.0),
    ("op.DeviceGroupedAgg", 7.0, 97.0),
    ("scan.stream", 8.0, 60.0), ("scan.stream", 8.0, 70.0),       # two pool threads
    ("scan.decode", 8.0, 20.0), ("scan.decode", 21.0, 30.0),      # thread 1: 21 s
    ("scan.decode", 8.0, 24.0), ("scan.decode", 40.0, 44.0),      # thread 2: 20 s
    ("device.coalesce_flush", 25.0, 50.0),
    ("device.h2d", 26.0, 40.0),
    ("residency.build", 27.0, 33.0), ("device.upload", 28.0, 32.0),
    ("residency.build", 34.0, 39.0), ("device.upload", 34.5, 38.5),
    ("device.dispatch", 41.0, 45.0), ("device.launch", 42.0, 44.0),
    ("query", 100.0, 148.0),
    ("scan.plan", 101.0, 103.0),
    ("scan.stream", 104.0, 140.0), ("scan.decode", 104.0, 139.0),
]
SCAN_RUNS = [
    {"template": "q1", "unix_start": 0.0, "unix_end": 99.0, "start": 0.0, "end": 99.0,
     "failed": False, "counters": {"hbm_cache_misses": 14, "scan_decoded_bytes": 4_100,
                                   "scan_file_bytes": 2_000, "hbm_h2d_bytes": 640}},
    {"template": "q6", "unix_start": 100.0, "unix_end": 150.0, "start": 100.0, "end": 150.0,
     "failed": False, "counters": {"scan_decoded_bytes": 3_500, "scan_file_bytes": 2_000}},
]
# the parent's program: the span tree of PR 25, no scan.* spans, no scan counters
PARENT_SPANS = [s for s in SCAN_SPANS if not s[0].startswith(("scan.plan", "scan.decode"))]
PARENT_RUNS = [dict(r, counters={k: v for k, v in r["counters"].items()
                                 if not k.startswith("scan_")}) for r in SCAN_RUNS]


def ctx_of(spans, runs):
    return {"spans": list(spans), "executions": list(runs), "to_trace": 0.0,
            "window": (0.0, 150.0), "busy": []}


@pytest.mark.parametrize("name, want", [
    ("scan.plan_ms", 1e3 * (1.0 + 0.5 + 3.0 + 2.0) / 2),
    ("scan.decode_ms", 1e3 * (12.0 + 9.0 + 16.0 + 4.0 + 35.0) / 2),     # summed over threads
    ("scan.decoded_bytes_per_s", (4_100 + 3_500) / (12.0 + 9.0 + 16.0 + 4.0 + 35.0)),
    # h2d 26..40 less the builds inside it (3 s), and the two uploads (8 s)
    ("scan.h2d_ms", 1e3 * (3.0 + 8.0) / 2),
    ("scan.residency_builds_per_query", 14 / 2),
])
def test_the_cells_readers_on_a_hand_made_window(name, want):
    assert reader(name).read(ctx_of(SCAN_SPANS, SCAN_RUNS)) == pytest.approx(want)


@pytest.mark.parametrize("name", ["scan.plan_ms", "scan.decode_ms",
                                  "scan.decoded_bytes_per_s"])
def test_a_program_without_the_scan_spans_gives_nothing_to_read(name):
    """The parent of PR 28: the reader returns None and does not raise, so the
    result line leaves the metric out."""
    assert reader(name).read(ctx_of(PARENT_SPANS, PARENT_RUNS)) is None
    assert reader(name).read(ctx_of([], PARENT_RUNS)) is None


def test_spans_outside_every_execution_are_not_counted():
    late = SCAN_SPANS + [("scan.decode", 160.0, 170.0), ("device.upload", 161.0, 169.0),
                         ("scan.plan", 155.0, 158.0)]
    for name in ("scan.plan_ms", "scan.decode_ms", "scan.h2d_ms"):
        assert reader(name).read(ctx_of(late, SCAN_RUNS)) == pytest.approx(
            reader(name).read(ctx_of(SCAN_SPANS, SCAN_RUNS)))
    # a host-tier window uploads nothing: nothing to read, not a zero
    host_only = [s for s in SCAN_SPANS if not s[0].startswith(("device.", "residency."))]
    assert reader("scan.h2d_ms").read(ctx_of(host_only, SCAN_RUNS)) is None
    assert reader("scan.residency_builds_per_query").read(ctx_of(host_only, PARENT_RUNS[1:])) == 0

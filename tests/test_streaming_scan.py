"""Streaming parquet scans: StreamingScan translation, row-group split
planning, small-file merging, ledger-keyed backpressure, and bit-identity
with the pushdowns applied."""

import os

import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import daft_tpu as dt
from daft_tpu import col
from daft_tpu.config import execution_config_ctx
from daft_tpu.memory import manager
from daft_tpu.observability.metrics import registry
from daft_tpu.plan import physical as pp

N_ROWS = 40_000


@pytest.fixture(autouse=True)
def _clean():
    from daft_tpu import memory as mem

    mem.reset_counters()
    manager().clear()
    yield
    manager().clear()


def _physical(df):
    from daft_tpu.plan.physical import translate

    return translate(df._builder.optimize().plan)


def _streaming_scans(phys):
    return [n for n in phys.walk() if isinstance(n, pp.StreamingScan)]


@pytest.fixture
def big_file(tmp_path):
    t = pa.table({
        "a": list(range(N_ROWS)),
        "v": [float(i % 1009) for i in range(N_ROWS)],
        "s": [f"x{i % 97}" for i in range(N_ROWS)],
    })
    path = str(tmp_path / "big.parquet")
    pq.write_table(t, path, row_group_size=4000)  # 10 row groups
    return path, t


def test_translates_to_streaming_scan(big_file):
    path, _ = big_file
    assert _streaming_scans(_physical(dt.read_parquet(path)))


def test_row_group_split_planning(big_file):
    path, t = big_file
    size = os.path.getsize(path)
    with execution_config_ctx(scan_split_bytes=max(size // 5, 1)):
        df = dt.read_parquet(path)
        scan = _streaming_scans(_physical(df))[0]
        assert len(scan.tasks) > 1, "large file never split by row groups"
        assert registry().get("scan_tasks_split") >= len(scan.tasks)
        out = df.to_pydict()
    assert out["a"] == t.column("a").to_pylist()  # order + content preserved
    assert registry().get("scan_batches") > 0
    assert registry().get("scan_rows") == N_ROWS


def test_split_disabled_keeps_one_task_per_file(big_file):
    path, _ = big_file
    with execution_config_ctx(scan_split_bytes=0):
        scan = _streaming_scans(_physical(dt.read_parquet(path)))[0]
        assert len(scan.tasks) == 1


def test_split_with_filter_pushdown_matches(big_file):
    """Split tasks don't evaluate the arrow predicate (filters_applied is
    False); the executor re-applies it — results must match exactly, and
    zone maps drop fully-excluded row groups at plan time."""
    path, _ = big_file
    size = os.path.getsize(path)
    with execution_config_ctx(scan_split_bytes=max(size // 5, 1),
                              device_mode="off"):
        df = dt.read_parquet(path).where(col("a") >= 35_000)
        scan = _streaming_scans(_physical(df))[0]
        # row groups 0..7 (a < 32000) are provably excluded by the zone map
        assert sum(t.num_rows or 0 for t in scan.tasks) <= 2 * 4000
        out = df.to_pydict()
    assert sorted(out["a"]) == list(range(35_000, N_ROWS))


def test_projection_pushdown_through_split(big_file):
    path, _ = big_file
    size = os.path.getsize(path)
    with execution_config_ctx(scan_split_bytes=max(size // 5, 1)):
        out = dt.read_parquet(path).select("a").to_pydict()
    assert out["a"] == list(range(N_ROWS))


def test_limit_pushdown_streaming(big_file):
    path, _ = big_file
    with execution_config_ctx(scan_split_bytes=0):
        assert dt.read_parquet(path).limit(7).count_rows() == 7


def test_small_file_merge(tmp_path):
    d = tmp_path / "many"
    d.mkdir()
    n_files, rows = 8, 1000
    for i in range(n_files):
        t = pa.table({"a": list(range(i * rows, (i + 1) * rows))})
        pq.write_table(t, d / f"f{i:02d}.parquet")
    with execution_config_ctx(scan_split_bytes=1 << 30):
        df = dt.read_parquet(str(d))
        scan = _streaming_scans(_physical(df))[0]
        assert len(scan.tasks) == 1, "tiny files never merged"
        assert registry().get("scan_tasks_merged") >= n_files - 1
        out = df.to_pydict()
    assert out["a"] == list(range(n_files * rows))  # order preserved


MIB = 1 << 20
DEFAULT_SPLIT = 128 * MIB


@pytest.mark.parametrize("sizes, window, split, expected", [
    # files that a pool thread each can take stay a task each ...
    pytest.param([48] * 6, 8, 128, [48] * 6, id="six-equal-files-window-8"),
    # ... and on a narrow pool they merge in balance, up to scan_split_bytes
    pytest.param([48] * 6, 2, 128, [96, 96, 96], id="six-equal-files-window-2"),
    pytest.param([27] * 6, 2, 128, [81, 81], id="six-equal-files-a-share-each-window-2"),
    # five full files and a shorter last one, as `write_table` cuts a table
    pytest.param([27] * 5 + [19], 8, 128, [27] * 5 + [19], id="cut-table-window-8"),
    pytest.param([27] * 5 + [19], 2, 128, [54, 54, 46], id="cut-table-window-2"),
    pytest.param([27] * 5 + [19], 1, 128, [108, 46], id="cut-table-window-1-is-the-old-rule"),
    # tiny files merge whatever the width: a task under the floor buys nothing
    pytest.param([1 / 128] * 8, 8, 1024, [1 / 16], id="eight-tiny-files"),
    pytest.param([1] * 16, 8, 128, [4, 4, 4, 4], id="floor-holds-against-the-window"),
    # many small files: balanced groups of a window's share, none over the bound
    pytest.param([1] * 1000, 8, 128, [125] * 8, id="thousand-files-window-8"),
    pytest.param([1] * 1000, 2, 128, [128] * 7 + [104], id="thousand-files-window-2-bound-holds"),
    pytest.param([10] * 100, 1, 64, [60] * 16 + [40], id="scan-split-bytes-is-the-upper-bound"),
    pytest.param([1] * 8, 8, 0, [1] * 8, id="zero-turns-merge-off"),
    # a file at or over the target is a task of its own and ends the run
    pytest.param([1, 1, 200, 1, 1], 4, 128, [2, 200, 2], id="large-file-between-small"),
    pytest.param([1, None, 1, 1], 8, 128, [1, None, 2], id="unknown-size-never-merges"),
])
def test_merge_plan_keeps_the_scan_as_wide_as_its_window(sizes, window, split, expected):
    """`merge_small_tasks` as a function of (file sizes, window, scan_split_bytes),
    sizes in MiB: the plan's task sizes, in order."""
    from daft_tpu.io.scan import ScanTask, merge_small_tasks
    from daft_tpu.schema import Schema

    schema = Schema([])
    tasks = [ScanTask(read=lambda k=k: iter([k]), schema=schema,
                      size_bytes=None if mib is None else int(mib * MIB),
                      num_rows=10, source_label=f"f{k}")
             for k, mib in enumerate(sizes)]
    merged = merge_small_tasks(tasks, int(split * MIB), window)
    assert [t.size_bytes for t in merged] == \
        [None if mib is None else int(mib * MIB) for mib in expected]
    assert all(t.size_bytes <= split * MIB for t in merged if "merged" in t.source_label)
    # every file once, in order
    assert [k for t in merged for k in t.read()] == list(range(len(sizes)))
    assert sum(t.num_rows for t in merged) == 10 * len(sizes)


@pytest.mark.parametrize("flags", [
    [True, False, True, False],
    [True, True, False, False],
])
def test_merge_never_crosses_filters_applied(flags):
    from daft_tpu.io.scan import ScanTask, merge_small_tasks
    from daft_tpu.schema import Schema

    schema = Schema([])
    tasks = [ScanTask(read=lambda: iter(()), schema=schema, size_bytes=1000,
                      filters_applied=f) for f in flags]
    merged = merge_small_tasks(tasks, DEFAULT_SPLIT, 8)
    runs = [f for k, f in enumerate(flags) if k == 0 or flags[k - 1] != f]
    assert [t.filters_applied for t in merged] == runs


@pytest.fixture
def pool_of_eight(monkeypatch):
    """The scan's window is the compute pool's width, which is the host's core
    count: pin it, so the plan below is the same on every machine."""
    from concurrent.futures import ThreadPoolExecutor

    from daft_tpu.utils import pool

    eight = ThreadPoolExecutor(max_workers=8, thread_name_prefix="daft-compute")
    monkeypatch.setattr(pool, "_POOL", eight)
    yield
    eight.shutdown(wait=True)


def test_a_directory_of_files_is_a_task_a_file_under_the_defaults(tmp_path, pool_of_eight):
    """Files a thread can be kept busy with (over the merge floor, under
    scan_split_bytes) are read a task each, in file order."""
    from daft_tpu.io.scan import MERGE_FLOOR_BYTES

    n_files, rows = 6, 64
    for i in range(n_files):
        t = pa.table({"k": list(range(i * rows, (i + 1) * rows)),
                      # incompressible, so the file is as large as its rows
                      "pad": pa.array([os.urandom(MERGE_FLOOR_BYTES // rows + 4096)
                                       for _ in range(rows)], pa.binary())})
        pq.write_table(t, tmp_path / f"part-{i:03d}.parquet")
        assert MERGE_FLOOR_BYTES < os.path.getsize(tmp_path / f"part-{i:03d}.parquet") \
            < DEFAULT_SPLIT // n_files
    reg = registry()
    before = {n: reg.get(n) for n in ("scan_tasks", "scan_tasks_merged")}
    df = dt.read_parquet(str(tmp_path)).select("k")
    assert len(_streaming_scans(_physical(df))[0].tasks) == n_files
    out = df.to_pydict()
    assert out["k"] == list(range(n_files * rows))
    assert reg.get("scan_tasks") - before["scan_tasks"] == n_files
    assert reg.get("scan_tasks_merged") - before["scan_tasks_merged"] == 0


def test_scan_backpressure_stalls_bounded(big_file):
    """A saturated ledger makes the scan stall (counted) but NEVER deadlock:
    the wait is bounded pacing, so the query still completes exactly."""
    path, _ = big_file
    m = manager()
    with execution_config_ctx(memory_limit_bytes=1 << 20, memory_pressure=0.5,
                              device_mode="off"):
        m.track(1 << 20)  # someone else holds the whole budget
        try:
            out = dt.read_parquet(path).select("a").to_pydict()
        finally:
            m.release(1 << 20)
    assert out["a"] == list(range(N_ROWS))
    assert registry().get("scan_backpressure_stalls") > 0
    assert registry().get("scan_stall_ms") > 0


def test_unbudgeted_scan_skips_sizing_and_ledger_reads(big_file, monkeypatch):
    """Zero-overhead guard for the unbudgeted fast path: with the ledger
    unbounded the scan must not size morsels (the arrow-buffer walk behind
    size_bytes), must never consult the ledger's admit/stall surface, and
    must flush its batch/row counts per TASK, not per morsel (no per-morsel
    registry lock traffic). scan_bytes stays zero — it is only meaningful
    when a budget makes morsel sizing load-bearing."""
    from daft_tpu.core.micropartition import MicroPartition

    path, t = big_file
    size = os.path.getsize(path)
    m = manager()
    calls = {"size_bytes": 0, "under_pressure": 0, "wait_for_headroom": 0}
    orig_size = MicroPartition.size_bytes

    def counting_size(self):
        calls["size_bytes"] += 1
        return orig_size(self)

    monkeypatch.setattr(MicroPartition, "size_bytes", counting_size)
    monkeypatch.setattr(m, "under_pressure", lambda: (
        calls.__setitem__("under_pressure", calls["under_pressure"] + 1)
        or False))
    monkeypatch.setattr(m, "wait_for_headroom", lambda *a, **k: (
        calls.__setitem__("wait_for_headroom",
                          calls["wait_for_headroom"] + 1)))
    inc_names = []
    reg = registry()
    orig_inc = reg.inc

    def counting_inc(name, n=1):
        inc_names.append(name)
        orig_inc(name, n)

    monkeypatch.setattr(reg, "inc", counting_inc)
    with execution_config_ctx(memory_limit_bytes=0,
                              scan_split_bytes=max(size // 5, 1),
                              device_mode="off"):
        df = dt.read_parquet(path)
        n_tasks = len(_streaming_scans(_physical(df))[0].tasks)
        out = df.to_pydict()
    assert out["a"] == t.column("a").to_pylist()
    assert calls["size_bytes"] == 0, "unbudgeted scan walked arrow buffers"
    assert calls["under_pressure"] == 0 and calls["wait_for_headroom"] == 0, \
        "unbudgeted scan consulted the ledger per morsel"
    assert registry().get("scan_bytes") == 0
    # 10 row groups split across n_tasks: flush granularity is per task
    scan_incs = inc_names.count("scan_rows")
    assert 0 < scan_incs <= n_tasks + 1, \
        f"{scan_incs} scan_rows incs for {n_tasks} tasks — per-morsel flush?"
    assert registry().get("scan_rows") == N_ROWS


def test_unbudgeted_scan_distributed_path_skips_sizing(big_file, tmp_path):
    """The fast-path guard extended to the distributed engine: worker-side
    scans with an unbounded ledger never size morsels, so the per-task
    engine-counter deltas propagated to the driver land scan_rows with
    scan_bytes == 0 (sizing only happens when a budget makes it
    load-bearing — monkeypatching cannot cross the spawn boundary, so the
    propagated counters ARE the assertion surface)."""
    import json

    import daft_tpu.runners as runners
    from daft_tpu.distributed import DistributedRunner
    from daft_tpu.observability.event_log import (disable_event_log,
                                                  enable_event_log)

    path, t = big_file
    p = str(tmp_path / "scan_events.jsonl")
    r = DistributedRunner(num_workers=1, n_partitions=2)
    native = runners.NativeRunner()
    sub = enable_event_log(p)
    runners.set_runner(r)
    try:
        # a groupby ships the scan inside the shuffle-map tasks — a bare
        # scan+select short-circuits on the driver and tests nothing
        out = (dt.read_parquet(path).groupby("s")
               .agg(col("a").count().alias("c")).to_pydict())
    finally:
        runners.set_runner(native)
        disable_event_log(sub)
        r.shutdown()
    assert sum(out["c"]) == N_ROWS
    events = [json.loads(l) for l in open(p)]
    task_counters = [dict(e["engine_counters"]) for e in events
                     if e["event"] == "task_stats"]
    assert task_counters, "no task stats propagated from the workers"
    scanned = sum(c.get("scan_rows", 0) for c in task_counters)
    assert scanned == N_ROWS, \
        f"worker-side scans reported {scanned} rows via engine counters"
    assert all(c.get("scan_bytes", 0) == 0 for c in task_counters), \
        "unbudgeted distributed scan sized morsels (scan_bytes != 0)"
    ends = [e for e in events if e["event"] == "query_end"]
    assert all(e["metrics"].get("scan_bytes", 0) == 0 for e in ends)


def test_streaming_scan_feeds_spilling_sort_exactly(big_file):
    """End-to-end out-of-core pipeline: streaming scan -> external sort under
    a budget far below the file size, bit-identical to unbudgeted."""
    path, _ = big_file
    size = os.path.getsize(path)

    def q():
        return dt.read_parquet(path).sort(["v", "a"])

    with execution_config_ctx(scan_split_bytes=max(size // 5, 1),
                              memory_limit_bytes=128 * 1024,
                              device_mode="off"):
        capped = q().to_pydict()
    assert registry().get("spill_runs") > 0
    with execution_config_ctx(memory_limit_bytes=0, device_mode="off"):
        unbudgeted = q().to_pydict()
    assert capped == unbudgeted

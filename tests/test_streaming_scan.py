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

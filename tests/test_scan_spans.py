"""The Parquet scan path as a query over files sees it: every execution
reads the files as they lie (`freshness`), the scan's spans hang in the
query's span tree on both arms of `_streaming_scan`, and with spans off the
path costs the shared no-op only."""

import os
import time

import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import daft_tpu as dt
from daft_tpu import col
from daft_tpu.config import execution_config_ctx
from daft_tpu.observability import runtime_stats as rs
from daft_tpu.observability.metrics import registry
from daft_tpu.observability.runtime_stats import SpanRecorder, set_spans

ROWS = 5_000


def write_files(where, n_files, scale=1.0, rows=ROWS, row_group_size=None):
    paths = []
    for k in range(n_files):
        lo = k * rows
        t = pa.table({"k": pa.array(range(lo, lo + rows), pa.int64()),
                      "v": pa.array([scale * (i % 7) for i in range(rows)], pa.float64()),
                      "s": pa.array([f"s{i % 3}" for i in range(rows)], pa.large_string())})
        path = os.path.join(str(where), f"part.{k:04d}.parquet")
        pq.write_table(t, path, row_group_size=row_group_size or rows)
        paths.append(path)
    return paths


def total(paths):
    return dt.read_parquet(paths).where(col("k") >= 10).agg(
        col("v").sum().alias("total")).to_pydict()["total"][0]


def test_an_execution_answers_from_the_files_as_they_lie_when_it_starts(tmp_path):
    """The configuration's `freshness` guarantee: rewrite a file under the
    same path between two executions in one process, and the second answers
    from the new rows; every execution reads every file's bytes again."""
    paths = write_files(tmp_path, 3)
    expected = 3 * sum(i % 7 for i in range(ROWS)) - sum(i % 7 for i in range(10))
    reg = registry()
    read = []
    for _ in range(2):
        before = reg.get("scan_file_bytes")
        assert total(paths) == pytest.approx(expected)
        read.append(reg.get("scan_file_bytes") - before)
    assert read == [sum(os.path.getsize(p) for p in paths)] * 2

    # other rows, same path (and another size: nothing cached may answer)
    time.sleep(0.01)
    t = pa.table({"k": pa.array(range(ROWS, ROWS + 100), pa.int64()),
                  "v": pa.array([1000.0] * 100, pa.float64()),
                  "s": pa.array(["x"] * 100, pa.large_string())})
    pq.write_table(t, paths[1])
    new = expected - sum(i % 7 for i in range(ROWS)) + 100 * 1000.0
    before = reg.get("scan_file_bytes")
    assert total(paths) == pytest.approx(new)
    assert reg.get("scan_file_bytes") - before == sum(os.path.getsize(p) for p in paths)
    # a DataFrame built before the rewrite answers from the files at execution
    df = dt.read_parquet(paths).agg(col("v").sum().alias("total"))
    pq.write_table(t.slice(0, 50), paths[1])
    assert df.to_pydict()["total"][0] == pytest.approx(
        2 * sum(i % 7 for i in range(ROWS)) + 50 * 1000.0)


def recorded(fn):
    rec = SpanRecorder()
    set_spans(rec)
    try:
        out = fn()
    finally:
        set_spans(None)
    return out, rec.drain()


@pytest.mark.parametrize("arm, config", [
    ("sequential", dict(pipeline_mode="off", scan_split_bytes=0)),
    ("io_parallel", dict(pipeline_mode="force", scan_split_bytes=0)),
    ("merged_tasks", dict(pipeline_mode="force")),
])
def test_decode_spans_hang_under_their_tasks_stream_in_the_querys_tree(tmp_path, arm, config):
    """Both arms of `_streaming_scan`: one task after the other on the
    caller's thread, and one future a task on `compute_pool` threads. Every
    `scan.decode` has a `scan.stream` parent, every `scan.stream` hangs under
    the scan's operator, and all carry the query's qid."""
    paths = write_files(tmp_path, 4)
    with execution_config_ctx(device_mode="off", **config):
        got, spans = recorded(lambda: total(paths))
    assert got == pytest.approx(4 * sum(i % 7 for i in range(ROWS)) - sum(i % 7 for i in range(10)))
    by_id = {s["args"]["id"]: s for s in spans}
    query = next(s for s in spans if s["name"] == "query")
    qid = query["args"]["qid"]
    assert qid
    streams = [s for s in spans if s["name"] == "scan.stream"]
    decodes = [s for s in spans if s["name"] == "scan.decode"]
    assert len(streams) == (1 if arm == "merged_tasks" else 4)
    # a batch a file, and the pull that finds each file exhausted
    assert sum(1 for s in decodes if "rows" in s["args"]) == 4
    assert sum(s["args"].get("rows", 0) for s in decodes) == 4 * ROWS - 10
    for s in decodes:
        assert s["args"]["qid"] == qid
        assert by_id[s["args"]["parent"]]["name"] == "scan.stream"
    for s in streams:
        assert s["args"]["qid"] == qid
        assert by_id[s["args"]["parent"]]["name"].startswith("op.StreamingScan")
    plans = [s for s in spans if s["name"] == "scan.plan"]
    assert sorted(s["args"]["step"] for s in plans) == ["glob", "schema", "tasks"]
    tasks = next(s for s in plans if s["args"]["step"] == "tasks")
    assert tasks["args"]["files"] == 4 and tasks["args"]["row_groups_pruned"] == 0
    assert tasks["args"]["qid"] == qid
    assert by_id[tasks["args"]["parent"]]["name"] == "plan.translate"


@pytest.mark.parametrize("config, tasks", [
    (dict(pipeline_mode="off", scan_split_bytes=0), 4),
    (dict(pipeline_mode="force", scan_split_bytes=0), 4),
    (dict(pipeline_mode="force"), 1),  # four files under the merge floor: one task
])
def test_scan_tasks_counts_the_tasks_a_streaming_scan_was_given(tmp_path, config, tasks):
    """Counted once a streaming scan, on both arms: as many as the query has
    `scan.stream` spans, pre-declared, so `QueryEnd.metrics` carries it."""
    from daft_tpu.observability import Subscriber, attach_subscriber, detach_subscriber
    from daft_tpu.observability.metrics import DECLARED_COUNTERS

    assert "scan_tasks" in DECLARED_COUNTERS

    class Ends(Subscriber):
        def __init__(self):
            self.ends = []

        def on_query_end(self, e):
            self.ends.append(e)

    paths = write_files(tmp_path, 4)
    reg = registry()
    before = reg.get("scan_tasks")
    sub = Ends()
    attach_subscriber(sub)
    try:
        with execution_config_ctx(device_mode="off", **config):
            _, spans = recorded(lambda: total(paths))
    finally:
        detach_subscriber(sub)
    assert reg.get("scan_tasks") - before == tasks
    assert sum(1 for s in spans if s["name"] == "scan.stream") == tasks
    assert [e.metrics.get("scan_tasks") for e in sub.ends] == [tasks]


def test_scan_counters_count_bytes_row_groups_and_what_zone_maps_pruned(tmp_path):
    # two row groups a file, so that a file splits by `row_groups_per_task`
    paths = write_files(tmp_path, 3, row_group_size=ROWS // 2)
    reg = registry()
    names = ("scan_file_bytes", "scan_decoded_bytes", "scan_row_groups",
             "scan_row_groups_pruned")
    before = {n: reg.get(n) for n in names}
    # k >= 2 * ROWS: the zone maps of files 0 and 1 prove them empty
    out, spans = recorded(lambda: dt.read_parquet(paths).where(col("k") >= 2 * ROWS).agg(
        col("v").sum().alias("t")).to_pydict())
    got = {n: reg.get(n) - before[n] for n in names}
    assert out["t"][0] == pytest.approx(sum(i % 7 for i in range(ROWS)))
    assert got["scan_row_groups_pruned"] == 4 and got["scan_row_groups"] == 2
    assert got["scan_file_bytes"] == os.path.getsize(paths[2])
    decoded = sum(s["args"].get("bytes", 0) for s in spans if s["name"] == "scan.decode")
    assert got["scan_decoded_bytes"] == decoded > 0
    tasks = next(s for s in spans if s["name"] == "scan.plan" and s["args"]["step"] == "tasks")
    assert tasks["args"]["row_groups_pruned"] == 4 and tasks["args"]["tasks"] == 1

    # a file split by row group: the group the zone map excludes is not read
    before = {n: reg.get(n) for n in names}
    out = dt.read_parquet(paths[2:], row_groups_per_task=1).where(
        col("k") >= 2 * ROWS + ROWS // 2).agg(col("v").count().alias("n")).to_pydict()
    got = {n: reg.get(n) - before[n] for n in names}
    assert out["n"] == [ROWS // 2]
    # one group left: the file cannot split, so its one task reads both groups
    assert got["scan_row_groups"] == 2 and got["scan_row_groups_pruned"] == 0
    (tmp_path / "four").mkdir()
    paths4 = write_files(tmp_path / "four", 1, row_group_size=ROWS // 4)
    before = {n: reg.get(n) for n in names}
    out = dt.read_parquet(paths4, row_groups_per_task=1).where(
        col("k") >= ROWS // 2).agg(col("v").count().alias("n")).to_pydict()
    got = {n: reg.get(n) - before[n] for n in names}
    assert out["n"] == [ROWS // 2]
    assert got["scan_row_groups"] == 2 and got["scan_row_groups_pruned"] == 2
    assert 0 < got["scan_file_bytes"] < os.path.getsize(paths4[0])


def test_with_spans_off_the_scan_path_meets_the_shared_no_op_only(tmp_path, monkeypatch):
    """The overhead guard of PR 1 and PR 25 on the scan path: no recorder, so
    no `_Span` is ever built, no context is copied for a pool thread, and
    `runtime_stats` never reads the clock."""
    paths = write_files(tmp_path, 3)
    assert rs.current_spans() is None

    def refuse(*_a, **_k):
        raise AssertionError("a span was built with no recorder")

    class _Clock:
        perf_counter = staticmethod(time.perf_counter)

        @staticmethod
        def time():
            raise AssertionError("time.time() read on the off path")

    import contextvars

    from daft_tpu.execution import executor

    class _NoCopy:
        ContextVar = contextvars.ContextVar

        @staticmethod
        def copy_context():
            raise AssertionError("a context was copied with no recorder")

    monkeypatch.setattr(rs, "_Span", refuse)
    monkeypatch.setattr(rs, "time", _Clock)
    monkeypatch.setattr(executor, "contextvars", _NoCopy)
    for config in (dict(pipeline_mode="off"), dict(pipeline_mode="force", scan_split_bytes=0)):
        with execution_config_ctx(device_mode="off", **config):
            assert total(paths) == pytest.approx(
                3 * sum(i % 7 for i in range(ROWS)) - sum(i % 7 for i in range(10)))

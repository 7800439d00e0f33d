"""Observability: subscriber lifecycle events, per-operator runtime stats,
EXPLAIN ANALYZE (reference: tests/test_subscribers.py / test_events.py)."""

import numpy as np
import pytest

import daft_tpu
from daft_tpu import col
from daft_tpu.observability import (
    OperatorStats,
    QueryEnd,
    QueryOptimized,
    QueryStart,
    Subscriber,
    attach_subscriber,
    detach_subscriber,
)


class Recorder(Subscriber):
    def __init__(self):
        self.events = []

    def on_query_start(self, e):
        self.events.append(("start", e))

    def on_query_optimized(self, e):
        self.events.append(("optimized", e))

    def on_operator_stats(self, qid, s):
        self.events.append(("op", s))

    def on_query_end(self, e):
        self.events.append(("end", e))


@pytest.fixture
def recorder():
    r = Recorder()
    attach_subscriber(r)
    yield r
    detach_subscriber(r)


def test_event_sequence_and_contents(recorder):
    df = daft_tpu.from_pydict({"a": list(range(100)), "b": ["x", "y"] * 50})
    out = df.where(col("a") >= 50).select("a").to_pydict()
    assert len(out["a"]) == 50

    kinds = [k for k, _ in recorder.events]
    assert kinds[0] == "start"
    assert kinds[1] == "optimized"
    assert kinds[-1] == "end"
    assert "op" in kinds

    start = recorder.events[0][1]
    assert isinstance(start, QueryStart) and start.query_id
    optimized = recorder.events[1][1]
    assert isinstance(optimized, QueryOptimized)
    assert "Filter" in start.unoptimized_plan
    assert optimized.physical_plan  # physical display present
    end = recorder.events[-1][1]
    assert isinstance(end, QueryEnd)
    assert end.rows == 50
    assert end.error is None
    assert end.query_id == start.query_id
    # operator stats cover the pipeline with real row counts
    ops = {s.name: s for k, s in recorder.events if k == "op"}
    assert any(s.rows_out == 50 for s in ops.values()), ops


def test_error_reported_in_query_end(recorder):
    df = daft_tpu.from_pydict({"a": [1, 2, 3]})

    @daft_tpu.func
    def boom(x: int) -> int:
        raise ValueError("nope")

    with pytest.raises(Exception):
        df.select(boom(col("a"))).to_pydict()
    end = recorder.events[-1][1]
    assert isinstance(end, QueryEnd)
    assert end.error is not None and ("nope" in end.error or "ValueError" in end.error)


def test_broken_subscriber_never_fails_query():
    class Broken(Subscriber):
        def on_query_start(self, e):
            raise RuntimeError("subscriber bug")

    b = Broken()
    attach_subscriber(b)
    try:
        out = daft_tpu.from_pydict({"a": [1]}).to_pydict()
        assert out == {"a": [1]}
    finally:
        detach_subscriber(b)


def test_no_subscribers_no_overhead_path():
    """Without subscribers the collector stays None (zero-overhead path)."""
    from daft_tpu.observability.runtime_stats import current_collector

    daft_tpu.from_pydict({"a": [1, 2]}).where(col("a") > 1).to_pydict()
    assert current_collector() is None


def test_overhead_guard_zero_subscribers_zero_instrumentation(monkeypatch):
    """Tier-1 overhead guard: with no subscribers attached, a query must take
    the zero-overhead path — no StatsCollector wrapping anywhere in the
    executor, no timeline span recording, no stall-clock reads on the
    pipeline channels, and the metrics registry untouched — so observability
    can never silently tax the hot path."""
    from daft_tpu.execution import pipeline
    from daft_tpu.observability import runtime_stats
    from daft_tpu.observability.metrics import registry
    from daft_tpu.observability.subscribers import subscribers_active

    assert not subscribers_active(), \
        "leaked subscriber from another test would invalidate this guard"
    assert runtime_stats.current_spans() is None, \
        "leaked span recorder from another test would invalidate this guard"

    def _forbidden_wrap(self, node, iterator):
        raise AssertionError("StatsCollector.wrap called on the zero-overhead path")

    def _forbidden_span(self, *a, **k):
        raise AssertionError("SpanRecorder.record called on the zero-overhead path")

    def _forbidden_stall(self, *a, **k):
        raise AssertionError("stall attribution ran on the zero-overhead path")

    monkeypatch.setattr(runtime_stats.StatsCollector, "wrap", _forbidden_wrap)
    monkeypatch.setattr(runtime_stats.SpanRecorder, "record", _forbidden_span)
    monkeypatch.setattr(runtime_stats.StatsCollector, "note_starve",
                        _forbidden_stall)
    monkeypatch.setattr(runtime_stats.StatsCollector, "note_blocked",
                        _forbidden_stall)

    # every stage channel must be UNPROFILED with no collector active
    orig_channel_init = pipeline.Channel.__init__

    def _checked_init(self, maxsize=4, profile=None):
        assert profile is None, "profiled Channel on the zero-overhead path"
        orig_channel_init(self, maxsize, profile)

    monkeypatch.setattr(pipeline.Channel, "__init__", _checked_init)
    before = registry().snapshot()
    df = daft_tpu.from_pydict({"a": list(range(1000)), "b": ["x", "y"] * 500})
    out = (df.where(col("a") >= 500)
           .groupby("b").agg(col("a").sum().alias("s")).to_pydict())
    assert len(out["b"]) == 2
    # a query counts its own wall time (one `inc`) and nothing else
    assert set(registry().diff(before)) == {"query_wall_us"}, \
        "registry touched with no observers"


def test_stats_collector_nested_self_time():
    """Self-time attribution with nested operators: the parent's attributed
    time excludes its child's production time (runtime_stats contract)."""
    import time as _time

    from daft_tpu.observability.runtime_stats import StatsCollector

    class FakeNode:
        def __init__(self, name):
            self._name = name

        def name(self):
            return self._name

    class Part:
        num_rows = 1

    child_node, parent_node = FakeNode("child"), FakeNode("parent")
    c = StatsCollector()

    def child_gen():
        for _ in range(3):
            _time.sleep(0.02)  # child production time
            yield Part()

    child_stream = c.wrap(child_node, child_gen())

    def parent_gen():
        for part in child_stream:
            _time.sleep(0.005)  # parent's own work per batch
            yield part

    parent_stream = c.wrap(parent_node, parent_gen())
    assert sum(p.num_rows for p in parent_stream) == 3
    stats = {s.name: s for s in c.finish()}
    assert stats["child"].rows_out == 3 and stats["parent"].rows_out == 3
    # child self time ~3*20ms; parent self time ~3*5ms and must NOT include
    # the child's 60ms of production time
    assert stats["child"].seconds >= 0.05
    assert stats["parent"].seconds < stats["child"].seconds
    assert stats["parent"].seconds < 0.045


def test_otlp_trace_id_stable_and_derived_from_query_id():
    """The OTLP trace id is a pure function of the query id (hash scheme
    shared with the distributed task stamping), so repeated encodes of the
    same query land in the same trace."""
    from daft_tpu.observability.otlp import _span_id, _trace_id

    assert _trace_id("abc") == _trace_id("abc")
    assert _trace_id("abc") != _trace_id("abd")
    assert len(_trace_id("abc")) == 32
    assert _span_id("abc", "task", "t0") == _span_id("abc", "task", "t0")
    assert len(_span_id("abc", "task", "t0")) == 16


def test_explain_analyze_reports_operators():
    rng = np.random.default_rng(0)
    df = daft_tpu.from_pydict({
        "k": rng.choice(["a", "b", "c"], 10_000).tolist(),
        "v": rng.uniform(0, 1, 10_000).tolist(),
    })
    report = (df.where(col("v") > 0.5)
              .groupby("k").agg(col("v").sum().alias("s"))
              .sort("k")
              .explain_analyze())
    assert "== Physical Plan ==" in report
    assert "== Runtime Stats ==" in report
    assert "rows out" in report
    assert "PhysSort" in report or "Sort" in report
    # the final sort emits exactly 3 groups
    assert " 3 " in report or "3" in report


def test_dashboard_serves_query_history():
    import json
    import urllib.request

    import daft_tpu
    from daft_tpu import col
    from daft_tpu.observability.dashboard import launch

    dash = launch()
    try:
        daft_tpu.from_pydict({"a": list(range(10))}).where(col("a") > 4).to_pydict()
        with urllib.request.urlopen(dash.url + "/api/queries", timeout=5) as r:
            data = json.loads(r.read())
        assert data and data[0]["done"] and data[0]["rows"] == 5
        assert data[0]["operators"], "no operator stats recorded"
        with urllib.request.urlopen(dash.url + "/", timeout=5) as r:
            assert b"daft_tpu" in r.read()
    finally:
        dash.shutdown()


def test_event_log_writes_jsonl(tmp_path):
    import json as _json

    import daft_tpu
    from daft_tpu import col
    from daft_tpu.observability.event_log import disable_event_log, enable_event_log

    p = str(tmp_path / "events.jsonl")
    sub = enable_event_log(p)
    try:
        daft_tpu.from_pydict({"a": [1, 2, 3]}).where(col("a") > 1).to_pydict()
    finally:
        disable_event_log(sub)
    events = [_json.loads(l) for l in open(p)]
    kinds = [e["event"] for e in events]
    assert kinds[0] == "query_start" and kinds[-1] == "query_end"
    assert "operator_stats" in kinds
    assert events[-1]["rows"] == 2


def test_otlp_subscriber_exports_span_tree():
    """OTLP/HTTP JSON export: one root query span with optimize + operator
    children, asserted against a mock collector (reference:
    common/tracing/src/config.rs OTLP exporter)."""
    import json as _json
    import threading
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    received = []

    class Handler(BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_POST(self):
            body = self.rfile.read(int(self.headers["Content-Length"]))
            received.append((self.path, _json.loads(body)))
            self.send_response(200)
            self.send_header("Content-Length", "2")
            self.end_headers()
            self.wfile.write(b"{}")

    srv = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        import daft_tpu
        from daft_tpu import col
        from daft_tpu.observability.otlp import OTLPSubscriber
        from daft_tpu.observability.subscribers import (attach_subscriber,
                                                        detach_subscriber)

        sub = OTLPSubscriber(f"http://127.0.0.1:{srv.server_address[1]}",
                             asynchronous=False)
        attach_subscriber(sub)
        try:
            df = daft_tpu.from_pydict({"a": list(range(100))})
            df.where(col("a") % 2 == 0).select((col("a") * 3).alias("b")).to_pydict()
        finally:
            detach_subscriber(sub)

        assert sub.exported == 1 and sub.last_error is None
        path, payload = received[0]
        assert path == "/v1/traces"
        rs = payload["resourceSpans"][0]
        svc = {a["key"]: a["value"] for a in rs["resource"]["attributes"]}
        assert svc["service.name"]["stringValue"] == "daft_tpu"
        spans = rs["scopeSpans"][0]["spans"]
        roots = [s for s in spans if "parentSpanId" not in s]
        assert len(roots) == 1 and roots[0]["name"] == "daft.query"
        root = roots[0]
        children = [s for s in spans if s.get("parentSpanId") == root["spanId"]]
        names = {s["name"] for s in children}
        assert "daft.optimize" in names
        assert any(n.startswith("daft.operator:") for n in names)
        assert all(s["traceId"] == root["traceId"] for s in spans)
        # timing sanity: children end within the root span
        assert all(int(s["endTimeUnixNano"]) <= int(root["endTimeUnixNano"]) + 10**9
                   for s in children)
    finally:
        srv.shutdown()


def test_dashboard_detail_and_engine_endpoints():
    """Per-query DAG detail (/api/query/{id}) and live engine counters
    (/api/engine) — the reference dashboard's live query-DAG surface
    (daft-dashboard/src/lib.rs)."""
    import json as _json
    import urllib.request

    import daft_tpu
    from daft_tpu import col
    from daft_tpu.observability.dashboard import launch

    dash = launch()
    try:
        df = daft_tpu.from_pydict({"a": list(range(50))})
        df.where(col("a") > 5).groupby(col("a") % 3).agg(
            col("a").sum().alias("s")).to_pydict()
        with urllib.request.urlopen(dash.url + "/api/queries", timeout=5) as r:
            queries = _json.loads(r.read())
        assert queries and queries[0]["done"]
        qid = queries[0]["query_id"]
        with urllib.request.urlopen(dash.url + f"/api/query/{qid}", timeout=5) as r:
            detail = _json.loads(r.read())
        assert detail["query_id"] == qid
        assert "physical_plan" in detail and detail["operators"]
        assert any(o["rows_out"] > 0 for o in detail["operators"])
        with urllib.request.urlopen(dash.url + "/api/engine", timeout=5) as r:
            eng = _json.loads(r.read())
        assert "device_join_batches" in eng
        with urllib.request.urlopen(dash.url + "/", timeout=5) as r:
            html = r.read().decode()
        assert "physical plan" in html and "/api/engine" in html
        with urllib.request.urlopen(dash.url + "/api/query/nope", timeout=5) as r:
            assert _json.loads(r.read())["error_404"] is True
    finally:
        dash.shutdown()

"""Query timeline profiler: stall-attributed operator time, Chrome-trace
export with per-worker task lanes and device spans, the Prometheus /metrics
surface, straggler detection and spill-counter registry plumbing."""

import json
import os
import time
import urllib.request

import numpy as np
import pytest

import daft_tpu
import daft_tpu.runners as runners
from daft_tpu import col
from daft_tpu.config import execution_config_ctx
from daft_tpu.observability.events import OperatorStats, TaskStats
from daft_tpu.observability.runtime_stats import (SpanRecorder, StatsCollector,
                                                 current_spans, profile_span,
                                                 set_collector, set_spans)


class _FakeNode:
    def __init__(self, name):
        self._name = name

    def name(self):
        return self._name


class _Part:
    num_rows = 1


# ---------------------------------------------------------------------------
# Stall attribution: starve / blocked split through the pipeline channels
# ---------------------------------------------------------------------------

def test_channel_starve_attributed_to_consumer():
    """A slow producer starves its consumer: the wait shows up as the
    consumer's starve_seconds, and compute+starve+blocked == seconds."""
    from daft_tpu.execution.pipeline import spawn_stage

    c = StatsCollector()
    producer, consumer = _FakeNode("producer"), _FakeNode("consumer")

    def produce():
        for _ in range(3):
            time.sleep(0.03)
            yield _Part()

    set_collector(c)
    try:
        upstream = spawn_stage(c.wrap(producer, produce()), node=producer)

        def consume():
            for part in upstream:
                yield part

        n = sum(p.num_rows for p in c.wrap(consumer, consume()))
    finally:
        set_collector(None)
    assert n == 3
    stats = {s.name: s for s in c.finish()}
    cons = stats["consumer"]
    assert cons.starve_seconds > 0.05, cons
    assert cons.compute_seconds < cons.starve_seconds
    for s in stats.values():
        assert s.seconds == pytest.approx(
            s.compute_seconds + s.starve_seconds + s.blocked_seconds)


def test_channel_blocked_attributed_to_producer():
    """A slow consumer backpressures the producer through the bounded
    channel: the producer's blocked_seconds captures the put-side waits."""
    from daft_tpu.execution.pipeline import spawn_stage

    c = StatsCollector()
    producer = _FakeNode("producer")

    def produce():
        for _ in range(8):
            yield _Part()

    set_collector(c)
    try:
        upstream = spawn_stage(c.wrap(producer, produce()), maxsize=1,
                               node=producer)
        n = 0
        for part in upstream:
            time.sleep(0.02)  # slow consumer -> full channel upstream
            n += part.num_rows
    finally:
        set_collector(None)
    assert n == 8
    prod = {s.name: s for s in c.finish()}["producer"]
    assert prod.blocked_seconds > 0.03, prod
    assert prod.seconds == pytest.approx(
        prod.compute_seconds + prod.starve_seconds + prod.blocked_seconds)


def test_stable_node_ids_survive_id_reuse():
    """Sequential node ids: two distinct nodes never share stats even if
    CPython hands the second the first's recycled id() (the collector anchors
    every wrapped node, making reuse impossible while it is alive)."""
    c = StatsCollector()
    ids = set()
    for i in range(50):
        # no reference kept by the caller — without anchoring, id() reuse
        # across iterations would be near-certain here
        nid = c.node_id(_FakeNode(f"n{i}"))
        assert nid not in ids
        ids.add(nid)
    assert ids == set(range(1, 51))


def test_explain_analyze_shows_stall_columns():
    rng = np.random.default_rng(0)
    df = daft_tpu.from_pydict({
        "k": rng.choice(["a", "b", "c"], 20_000).tolist(),
        "v": rng.uniform(0, 1, 20_000).tolist(),
    })
    report = (df.where(col("v") > 0.25)
              .groupby("k").agg(col("v").sum().alias("s"))
              .explain_analyze())
    assert "compute" in report and "starve" in report and "blocked" in report
    assert "== Runtime Stats ==" in report


# ---------------------------------------------------------------------------
# SpanRecorder + device spans
# ---------------------------------------------------------------------------

def test_span_recorder_profile_span_and_cap():
    rec = SpanRecorder(cap=2)
    set_spans(rec)
    try:
        with profile_span("a", "device", rows=5):
            pass
        with profile_span("b", "io"):
            pass
        with profile_span("c", "io"):  # over cap -> dropped, not grown
            pass
    finally:
        set_spans(None)
    assert current_spans() is None
    spans = rec.drain()
    assert [s["name"] for s in spans] == ["a", "b"]
    # the caller's arguments, plus the span's place in the tree (ISSUE 25)
    assert spans[0]["args"] == {"rows": 5, "id": spans[0]["args"]["id"],
                                "parent": 0, "qid": ""}
    assert rec.dropped == 1
    # no recorder active: profile_span must not record anywhere
    with profile_span("ghost", "device"):
        pass
    assert rec.drain() == []


def test_device_stage_records_dispatch_spans():
    """DAFT_TPU_DEVICE=on (JAX CPU backend): the device agg path emits
    h2d/dispatch/d2h spans while a recorder is installed."""
    rng = np.random.default_rng(1)
    df = daft_tpu.from_pydict({
        "k": rng.integers(0, 8, 30_000).tolist(),
        "v": rng.uniform(0, 100, 30_000).tolist(),
    })
    rec = SpanRecorder()
    set_spans(rec)
    try:
        with execution_config_ctx(device_mode="on"):
            out = df.groupby("k").agg(col("v").sum().alias("s")).to_pydict()
    finally:
        set_spans(None)
    assert len(out["k"]) == 8
    names = {s["name"] for s in rec.drain()}
    assert "device.dispatch" in names, names
    assert "device.d2h" in names, names


# ---------------------------------------------------------------------------
# The span tree (ISSUE 25): id / parent / qid, the query's life, the inside of
# the join dispatch, the off path, the profiler's own clock
# ---------------------------------------------------------------------------

def _star_join_frames(n=20_000):
    rng = np.random.default_rng(7)
    fact = daft_tpu.from_pydict({
        "fk": rng.integers(0, 100, n).tolist(),
        "v": rng.uniform(0, 1, n).tolist()}).collect()
    dim = daft_tpu.from_pydict({
        "k": list(range(100)), "g": [i % 5 for i in range(100)]}).collect()
    return fact, dim


def _star_join(fact, dim):
    return (fact.join(dim, left_on="fk", right_on="k").groupby("g")
            .agg(col("v").sum().alias("s")).sort("g"))


def _record_star_join(monkeypatch, pipeline_mode):
    """The spans of one forced-device star join (second run: planes resident,
    programs compiled), priced although forced so that a decider runs."""
    monkeypatch.setenv("DAFT_TPU_PLACEMENT_PRICE_FORCED", "1")
    fact, dim = _star_join_frames()
    rec = SpanRecorder()
    with execution_config_ctx(device_mode="on", device_min_rows=1,
                              mesh_devices=1, pipeline_mode=pipeline_mode):
        expect = _star_join(fact, dim).to_pydict()
        set_spans(rec)
        try:
            got = _star_join(fact, dim).to_pydict()
        finally:
            set_spans(None)
    assert got == expect
    assert rec.dropped == 0
    return rec.drain()


@pytest.mark.parametrize("pipeline_mode", ["off", "force"])
def test_star_join_span_tree(monkeypatch, pipeline_mode):
    """One `query` root; its descendants are the query's life and the inside
    of the join dispatch; every span carries qid, id and parent, and a child
    lies within its parent. Under pipeline_mode="force" the operators run on
    stage threads, which inherit the context that started them."""
    spans = _record_star_join(monkeypatch, pipeline_mode)
    by_id = {s["args"]["id"]: s for s in spans}
    assert len(by_id) == len(spans)
    for s in spans:
        assert {"id", "parent", "qid"} <= set(s["args"]), s
    roots = [s for s in spans if s["args"]["parent"] == 0]
    assert sorted(s["name"] for s in roots) == ["query", "result.encode"]
    query = next(s for s in roots if s["name"] == "query")
    qid = query["args"]["qid"]
    assert qid and all(s["args"]["qid"] == qid for s in spans)
    assert query["args"]["rows"] == 5 and "error" not in query["args"]

    def ancestors(s):
        while s["args"]["parent"]:
            s = by_id[s["args"]["parent"]]
            yield s

    eps = 1e-6  # time.time() pairs taken microseconds apart
    for s in spans:
        if s["args"]["parent"]:
            p = by_id[s["args"]["parent"]]
            assert p["ts"] - eps <= s["ts"], (s, p)
            assert s["ts"] + s["dur"] <= p["ts"] + p["dur"] + eps, (s, p)
    under_query = {s["name"] for s in spans
                   if any(a is query for a in ancestors(s))}
    assert {"plan.optimize", "plan.translate", "placement.decide",
            "device.dispatch", "join.codes", "join.index", "join.gather",
            "device.launch", "stage.finalize", "device.d2h"} <= under_query
    assert any(n.startswith("op.DeviceJoinAgg") for n in under_query)
    # the inside of the dispatch hangs under the dispatch
    dispatch = next(s for s in spans if s["name"] == "device.dispatch")
    inside = {s["name"] for s in spans
              if any(a is dispatch for a in ancestors(s))}
    assert {"join.codes", "join.index", "join.gather", "device.launch"} <= inside
    decide = next(s for s in spans if s["name"] == "placement.decide")
    assert decide["args"]["decider"] == "_join_device_wins"
    assert {"tier", "cached"} <= set(decide["args"])
    finalize = next(s for s in spans if s["name"] == "stage.finalize")
    assert finalize["args"]["groups"] == 5
    d2h = next(s for s in spans if s["name"] == "device.d2h")
    assert d2h["args"]["parent"] == finalize["args"]["id"]
    encode = next(s for s in roots if s["name"] == "result.encode")
    assert encode["args"]["rows"] == 5 and encode["ts"] >= query["ts"] + query["dur"] - eps


@pytest.mark.parametrize("keys", [("g",), ("fk", "k")], ids=["dict_codes", "host_codes"])
def test_every_join_dispatch_holds_its_parts(keys):
    """What `benchmark/layer_metrics/join.*_ms.py` and `stages.launch_ms` read:
    every `device.dispatch` of the join path holds `join.codes`, `join.index`,
    `join.gather` and `device.launch` at least once, by parent chain and by
    containment in time (the harness's sink keeps no ids), with `join.index`
    inside `join.gather` now that the gather's span covers the look-ups and
    the one call of the traced program."""
    fact, dim = _star_join_frames()
    # a column the query leaves out: the pruning Project is what the pipeline cuts into morsels
    fact = fact.with_column("unused", col("v") * 2).collect()
    q = lambda: (fact.join(dim, left_on="fk", right_on="k").groupby(*keys)
                 .agg(col("v").sum().alias("s")).sort(list(keys)))
    rec = SpanRecorder()
    # (40 morsels: five dispatches of DISPATCH_SEGMENTS where the codes are the
    # dictionaries', a dispatch a morsel where the host factorizes every batch)
    with execution_config_ctx(device_mode="on", device_min_rows=1, mesh_devices=1,
                              morsel_size_rows=512, pipeline_mode="force"):
        expect = q().to_pydict()
        set_spans(rec)
        try:
            got = q().to_pydict()
        finally:
            set_spans(None)
    assert got == expect and rec.dropped == 0
    spans = rec.drain()
    by_id = {s["args"]["id"]: s for s in spans}

    def under(s, top):
        while s["args"]["parent"]:
            s = by_id[s["args"]["parent"]]
            if s is top:
                return True
        return False

    eps = 1e-6
    dispatches = [s for s in spans if s["name"] == "device.dispatch"]
    assert len(dispatches) == (5 if keys == ("g",) else 40), "several join dispatches a query"
    parts = ("join.codes", "join.index", "join.gather", "device.launch")
    for d in dispatches:
        inside = [s for s in spans if under(s, d)]
        names = [s["name"] for s in inside]
        for part in parts:
            assert part in names, (part, names)
        assert names.count("join.gather") == 1 and names.count("device.launch") == 1
        for s in inside:
            assert d["ts"] - eps <= s["ts"] and s["ts"] + s["dur"] <= d["ts"] + d["dur"] + eps
        gather = next(s for s in inside if s["name"] == "join.gather")
        assert any(s["name"] == "join.index" and under(s, gather) for s in inside)
    # no part of a join dispatch outside one
    for s in spans:
        if s["name"] in ("join.gather", "device.launch"):
            assert any(under(s, d) for d in dispatches), s


def test_first_touch_spans_and_counters():
    """A first run over fresh tables uploads planes and dictionary-encodes
    the group key: `device.upload`, `series.dict_encode` and `residency.build`
    spans (with bytes and the slot kind), and the two always-on counters."""
    from daft_tpu.observability.metrics import registry

    df = daft_tpu.from_pydict({
        "k": [f"k{i % 7}" for i in range(5_000)],
        "v": [float(i) for i in range(5_000)]}).collect()
    rec = SpanRecorder()
    before = registry().snapshot()
    set_spans(rec)
    try:
        with execution_config_ctx(device_mode="on", device_min_rows=1,
                                  mesh_devices=1):
            df.groupby("k").agg(col("v").sum().alias("s")).to_pydict()
    finally:
        set_spans(None)
    spans = rec.drain()
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    up = by_name["device.upload"][0]["args"]
    assert up["rows"] == 5_000 and up["bytes"] > 0 and up["dtype"]
    enc = by_name["series.dict_encode"][0]["args"]
    assert enc["rows"] == 5_000 and enc["cardinality"] == 7
    builds = {s["args"]["slot"] for s in by_name["residency.build"]}
    assert "col" in builds
    assert all(s["args"]["bytes"] >= 0 for s in by_name["residency.build"])
    # an upload is the child of the residency build that missed
    build_ids = {s["args"]["id"] for s in by_name["residency.build"]}
    assert all(s["args"]["parent"] in build_ids for s in by_name["device.upload"])
    diff = registry().diff(before)
    assert diff["h2d_upload_us"] > 0 and diff["dict_encode_us"] > 0


NEW_SPAN_NAMES = [
    "query", "plan.optimize", "plan.translate", "placement.decide",
    "op.DeviceJoinAgg(1 dims)", "stage.finalize", "result.encode",
    "join.codes", "join.index", "join.gather", "device.launch",
    "residency.build", "device.upload", "series.dict_encode", "xla.compile",
]


@pytest.mark.parametrize("name", NEW_SPAN_NAMES)
def test_new_span_names_are_not_cost_model_terms(name):
    """placement.feedback sums device.*h2d|dispatch|d2h spans into observed
    seconds: a span nested inside one of those must never map to a term, or
    its time would be counted twice."""
    from daft_tpu.observability.placement import _span_term

    assert _span_term(name) is None


def test_feedback_tee_keeps_only_priced_spans():
    """The placement feedback's own recorder stores what it prices and
    forwards everything: the join's inner spans cannot fill its cap."""
    from daft_tpu.observability.placement import _TeeSpans

    outer = SpanRecorder()
    tee = _TeeSpans(outer)
    for name in ("device.dispatch", "join.gather", "device.launch", "device.h2d"):
        tee.record(name, "device", 1.0, 2.0, {"id": 1})
    assert [s["name"] for s in tee.drain()] == ["device.dispatch", "device.h2d"]
    assert len(outer.drain()) == 4


def test_off_path_no_clock_no_record_no_counter(monkeypatch):
    """No recorder: profile_span hands out ONE shared no-op, span_iter hands
    back its input, runtime_stats never reads time.time(), and a host-only
    query adds its wall time to the registry and nothing else."""
    from daft_tpu.observability import runtime_stats as rs
    from daft_tpu.observability.metrics import registry

    assert current_spans() is None
    a = profile_span("a", "device", rows=1)
    assert a is profile_span("b", "io") is rs._NO_SPAN
    with a as sp:
        assert sp is None
    stream = iter([_Part()])
    assert rs.span_iter("op.X", "host", stream) is stream

    class _Clock:
        perf_counter = staticmethod(time.perf_counter)

        @staticmethod
        def time():
            raise AssertionError("time.time() read on the off path")

    monkeypatch.setattr(rs, "time", _Clock)
    df = daft_tpu.from_pydict({"k": [1, 2, 1, 2], "v": [1.0, 2.0, 3.0, 4.0]})
    before = registry().snapshot()
    with execution_config_ctx(device_mode="off"):
        out = df.where(col("v") > 1).groupby("k").agg(
            col("v").sum().alias("s")).sort("k").to_pydict()
    assert out == {"k": [1, 2], "s": [3.0, 6.0]}
    assert set(registry().diff(before)) <= {"h2d_upload_us", "dict_encode_us",
                                            "query_wall_us"}


def test_span_iter_records_error_and_closes_upstream():
    closed = []

    def inner():
        try:
            yield _Part()
            raise ValueError("boom")
        finally:
            closed.append(True)

    rec = SpanRecorder()
    set_spans(rec)
    try:
        from daft_tpu.observability.runtime_stats import span_iter

        with pytest.raises(ValueError):
            list(span_iter("op.Bad", "host", inner(), tag="x"))
        # a consumer that leaves early unwinds the upstream generator
        it = span_iter("op.Early", "host", inner())
        next(it)
        it.close()
    finally:
        set_spans(None)
    bad, early = rec.drain()
    assert bad["args"]["error"] == "ValueError" and bad["args"]["tag"] == "x"
    assert bad["args"]["rows"] == 1 and bad["args"]["batches"] == 1
    assert "error" not in early["args"] and early["args"]["rows"] == 1
    assert closed == [True, True]


def test_timed_span_measures_without_a_recorder_and_feeds_query_optimized():
    """QueryOptimized.seconds comes from the plan.* extents, taken whether or
    not a recorder is installed."""
    from daft_tpu.observability import attach_subscriber, detach_subscriber
    from daft_tpu.observability.runtime_stats import timed_span
    from daft_tpu.observability.subscribers import Subscriber

    assert current_spans() is None
    with timed_span("plan.optimize", "plan") as sp:
        time.sleep(0.002)
    assert sp.seconds >= 0.002

    class Sub(Subscriber):
        def __init__(self):
            self.optimized = []

        def on_query_optimized(self, ev):
            self.optimized.append(ev)

    sub = Sub()
    attach_subscriber(sub)
    try:
        daft_tpu.from_pydict({"a": [1, 2, 3]}).where(col("a") > 1).to_pydict()
    finally:
        detach_subscriber(sub)
    assert len(sub.optimized) == 1 and 0 < sub.optimized[0].optimize_seconds < 5


def test_xla_compile_span_under_a_recorder():
    """A compilation while a recorder is installed leaves an `xla.compile`
    span (JAX's own duration, ending when the listener hears of it) under the
    span that was open."""
    import jax
    import jax.numpy as jnp

    from daft_tpu.utils import jax_setup  # noqa: F401  (registers the listener)

    rec = SpanRecorder()
    set_spans(rec)
    try:
        with profile_span("device.dispatch", "device") as outer:
            jax.jit(lambda x: x * 3 + 17)(jnp.arange(7)).block_until_ready()
    finally:
        set_spans(None)
    spans = rec.drain()
    compiles = [s for s in spans if s["name"] == "xla.compile"]
    assert compiles, [s["name"] for s in spans]
    assert all(s["args"]["parent"] == outer.args["id"] and s["dur"] > 0
               for s in compiles)
    # off: the listener records nowhere
    jax.jit(lambda x: x * 5 + 19)(jnp.arange(7)).block_until_ready()
    assert rec.drain() == []


def test_profiler_capture_holds_the_programs_spans(tmp_path):
    """Under jax.profiler (CPU backend) the written .xplane.pb holds the
    program's spans as events on the profiler's own clock."""
    import glob

    import jax
    from jax.profiler import ProfileData

    rng = np.random.default_rng(3)
    df = daft_tpu.from_pydict({
        "k": rng.integers(0, 4, 10_000).tolist(),
        "v": rng.uniform(0, 1, 10_000).tolist()}).collect()
    q = lambda: df.groupby("k").agg(col("v").sum().alias("s")).to_pydict()  # noqa: E731
    with execution_config_ctx(device_mode="on", device_min_rows=1, mesh_devices=1):
        q()
        rec = SpanRecorder()
        set_spans(rec)
        jax.profiler.start_trace(str(tmp_path))
        try:
            q()
        finally:
            jax.profiler.stop_trace()
            set_spans(None)
    recorded = {s["name"] for s in rec.drain()}
    files = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    assert files
    events = {e.name for plane in ProfileData.from_file(files[-1]).planes
              for line in plane.lines for e in line.events}
    assert "device.dispatch" in events
    assert {"query", "plan.optimize", "device.launch", "stage.finalize",
            "result.encode"} <= events & recorded


# ---------------------------------------------------------------------------
# Chrome trace export
# ---------------------------------------------------------------------------

def _mk_task(stage, task_id, worker, started, exec_s, ops=(), **kw):
    return TaskStats(stage_id=stage, task_id=task_id, worker_id=worker,
                     queue_wait_s=0.0, schedule_latency_s=0.0, exec_s=exec_s,
                     rows_out=10, bytes_out=100, retries=0,
                     started_at=started, operator_stats=tuple(ops), **kw)


def test_chrome_trace_synthetic_lanes_and_offsets():
    from daft_tpu.distributed.trace import QueryTrace

    tr = QueryTrace("qtest")
    t0 = tr.started_wall
    op = OperatorStats(node_id=1, name="PhysAgg", rows_out=10, batches_out=1,
                      seconds=0.3, compute_seconds=0.1, starve_seconds=0.15,
                      blocked_seconds=0.05)
    tr.tasks.append(_mk_task("s0", "t0", "worker-0", t0 + 0.1, 0.5, [op]))
    tr.tasks.append(_mk_task("s0", "t1", "worker-1", t0 + 0.1, 0.4))
    tr.task_spans["t0"] = [{"name": "device.dispatch", "cat": "device",
                            "ts": t0 + 0.2, "dur": 0.05,
                            "args": {"rows": 10}}]
    # heartbeats: worker-1's clock runs 2s behind the driver
    tr.add_heartbeat({"worker_id": "worker-1", "ts": t0 - 2.0,
                      "recv_ts": t0 + 0.001})
    tr.add_heartbeat({"worker_id": "worker-1", "ts": t0 - 1.5,
                      "recv_ts": t0 + 0.6})
    offs = tr.clock_offsets()
    assert offs["worker-1"] == pytest.approx(2.001, abs=1e-6)

    data = tr.to_chrome_trace(total_seconds=1.0)
    evs = data["traceEvents"]
    assert all(isinstance(e["pid"], int) or e["ph"] == "M" for e in evs)
    xs = [e for e in evs if e["ph"] == "X"]
    assert all(e["dur"] >= 0 and isinstance(e["ts"], float) for e in xs)
    # two worker processes with task slices
    task_pids = {e["pid"] for e in xs if e["cat"] == "task"}
    assert len(task_pids) == 2
    # the device span landed on worker-0's device/io lane at a real offset
    disp = [e for e in xs if e["name"] == "device.dispatch"]
    assert len(disp) == 1 and disp[0]["ts"] == pytest.approx(0.2e6, abs=1e3)
    # operator + stall slices
    assert any(e["cat"] == "operator" and e["name"] == "PhysAgg" for e in xs)
    assert any(e["name"] == "starve:PhysAgg" for e in xs)
    # stage + query slices on the driver (pid 0)
    assert any(e["cat"] == "stage" and e["pid"] == 0 for e in xs)
    assert any(e["cat"] == "query" and e["pid"] == 0 for e in xs)
    assert data["metadata"]["clock_offsets_s"]["worker-1"] > 1.9
    json.dumps(data)  # wholly serializable


def test_straggler_report_thresholds(monkeypatch):
    from daft_tpu.distributed.trace import QueryTrace

    tr = QueryTrace("qs")
    tr._stage_order.append("s0")   # normally set by record_task
    tr._shuffle["s0"] = {}
    for i in range(4):
        tr.tasks.append(_mk_task("s0", f"t{i}", "w0", 0.0, 0.1))
    tr.tasks.append(_mk_task("s0", "slow", "w1", 0.0, 1.0))
    flagged = tr.straggler_report(threshold=2.0)
    assert [r["task_id"] for r in flagged] == ["slow"]
    assert flagged[0]["ratio"] == pytest.approx(10.0)
    assert tr.straggler_report(threshold=20.0) == []
    # env knob steers the default
    monkeypatch.setenv("DAFT_TPU_STRAGGLER_K", "20")
    assert tr.straggler_report() == []
    monkeypatch.setenv("DAFT_TPU_STRAGGLER_K", "2")
    rep = tr.straggler_report()
    assert len(rep) == 1
    # and the EXPLAIN ANALYZE render names it
    assert "stragglers" in tr.render() and "slow" in tr.render()


def test_distributed_groupby_join_chrome_trace_e2e(tmp_path):
    """Acceptance: a 2-worker distributed groupby-join query with device
    leases produces a Chrome trace with task lanes from both workers and at
    least one device-dispatch slice, via explain_analyze(profile=...)."""
    from daft_tpu.distributed.runner import DistributedRunner

    rng = np.random.default_rng(7)
    n = 40_000
    fact = daft_tpu.from_pydict({
        "k": rng.integers(0, 40, n).tolist(),
        "v": rng.uniform(0, 100, n).tolist(),
    })
    dim = daft_tpu.from_pydict({
        "k": list(range(40)),
        "grp": [i % 5 for i in range(40)],
    })
    q = (fact.join(dim, on="k")
         .groupby("grp").agg(col("v").sum().alias("s"))
         .sort("grp"))

    path = str(tmp_path / "trace.json")
    native = runners.NativeRunner()
    with execution_config_ctx(device_mode="on"):
        r = DistributedRunner(num_workers=2, n_partitions=2, device_workers=2)
        try:
            runners.set_runner(r)
            report = q.explain_analyze(profile=path)
        finally:
            runners.set_runner(native)
            r.shutdown()
    assert "== Distributed Stages ==" in report
    with open(path) as f:
        data = json.load(f)
    evs = data["traceEvents"]
    xs = [e for e in evs if e["ph"] == "X"]
    # task lanes from >= 2 workers
    workers = {e["args"]["worker_id"] for e in xs if e["cat"] == "task"}
    assert len(workers) >= 2, workers
    # >= 1 device-dispatch slice shipped back from a device-leased worker
    assert any(e["name"] == "device.dispatch" for e in xs), \
        sorted({e["name"] for e in xs})
    # per-operator stall split rides along and reconciles
    ops = [e for e in xs if e["cat"] == "operator"]
    assert ops
    for e in ops:
        a = e["args"]
        assert a["compute_s"] >= 0 and a["starve_s"] >= 0 and a["blocked_s"] >= 0


# ---------------------------------------------------------------------------
# Dashboard HTTP surface: /metrics + trace download + JSON endpoints
# ---------------------------------------------------------------------------

def _get(url, timeout=5):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.headers.get("Content-Type", ""), r.read()


def _parse_prometheus(text):
    """{"name": value} for plain samples; histogram samples keep labels."""
    out = {}
    types = {}
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("# TYPE"):
            _, _, name, typ = line.split()
            types[name] = typ
            continue
        assert not line.startswith("#"), line
        name, val = line.rsplit(" ", 1)
        out[name] = float(val)
    return out, types


def test_metrics_endpoint_prometheus_format():
    from daft_tpu.observability.dashboard import launch

    dash = launch()
    try:
        daft_tpu.from_pydict({"a": list(range(100))}).where(
            col("a") > 10).to_pydict()
        ctype, body = _get(dash.url + "/metrics")
        assert ctype.startswith("text/plain")
        samples, types = _parse_prometheus(body.decode())
        # acceptance: hbm_bytes_resident served as a gauge
        assert "daft_tpu_hbm_bytes_resident" in samples
        assert types["daft_tpu_hbm_bytes_resident"] == "gauge"
        # engine counters exported with counter TYPE
        assert types.get("daft_tpu_device_stage_batches") == "counter"
        # spill counters reach the scrape surface (registry-backed)
        assert "daft_tpu_spill_batches" in samples
        # query-latency histogram: count >= 1, cumulative buckets monotone,
        # +Inf bucket == count
        assert types["daft_tpu_query_latency_seconds"] == "histogram"
        assert samples["daft_tpu_query_latency_seconds_count"] >= 1
        buckets = [(k, v) for k, v in samples.items()
                   if k.startswith("daft_tpu_query_latency_seconds_bucket")]
        assert buckets
        vals = [v for _, v in buckets]
        assert vals == sorted(vals)
        assert vals[-1] == samples["daft_tpu_query_latency_seconds_count"]
    finally:
        dash.shutdown()


def test_histogram_quantiles():
    from daft_tpu.observability.metrics import Histogram

    h = Histogram()
    for _ in range(90):
        h.observe(0.02)
    for _ in range(10):
        h.observe(4.0)
    assert h.quantile(0.5) == 0.025   # bucket upper bound containing p50
    assert h.quantile(0.99) == 5.0
    lines = h.prometheus_lines("m")
    assert lines[0] == "# TYPE m histogram"
    assert 'm_bucket{le="+Inf"} 100' in lines
    assert "m_count 100" in lines


def test_dashboard_trace_download_and_endpoints():
    """Distributed query through an attached dashboard: every JSON endpoint
    answers with the right shape and /api/query/<id>/trace serves the
    Chrome-trace download."""
    from daft_tpu.distributed.runner import DistributedRunner
    from daft_tpu.observability.dashboard import launch

    rng = np.random.default_rng(3)
    df = daft_tpu.from_pydict({
        "k": rng.integers(0, 20, 10_000).tolist(),
        "v": rng.uniform(0, 1, 10_000).tolist(),
    })
    dash = launch()
    native = runners.NativeRunner()
    r = DistributedRunner(num_workers=2, n_partitions=2)
    try:
        runners.set_runner(r)
        out = df.groupby("k").agg(col("v").sum().alias("s")).to_pydict()
        assert len(out["k"]) == 20
        _, body = _get(dash.url + "/api/queries")
        queries = json.loads(body)
        assert queries and queries[0]["done"]
        qid = queries[0]["query_id"]
        _, body = _get(dash.url + f"/api/query/{qid}")
        assert json.loads(body)["query_id"] == qid
        _, body = _get(dash.url + f"/api/query/{qid}/trace")
        trace = json.loads(body)
        assert trace["traceEvents"], trace.get("error_404")
        assert any(e.get("cat") == "task" for e in trace["traceEvents"])
        _, body = _get(dash.url + "/api/query/nope/trace")
        assert json.loads(body)["error_404"] is True
        _, body = _get(dash.url + "/api/engine")
        assert "device_stage_batches" in json.loads(body)
        _, body = _get(dash.url + "/api/workers")
        workers = json.loads(body)
        assert isinstance(workers, dict)
        for w in workers.values():
            assert "busy_fraction" in w and "hbm_bytes" in w
    finally:
        runners.set_runner(native)
        r.shutdown()
        dash.shutdown()


# ---------------------------------------------------------------------------
# Spill counters in the registry (satellite)
# ---------------------------------------------------------------------------

def test_spill_counters_flow_through_registry():
    from daft_tpu import memory as mem
    from daft_tpu.observability.metrics import registry

    rng = np.random.default_rng(5)
    df = daft_tpu.from_pydict({
        "k": rng.integers(0, 500, 50_000).tolist(),
        "v": rng.uniform(0, 1, 50_000).tolist(),
    })
    mem.reset_counters()
    before = registry().snapshot()
    with execution_config_ctx(memory_limit_bytes=64 * 1024, device_mode="off"):
        df.groupby("k").agg(col("v").sum().alias("s")).to_pydict()
    diff = registry().diff(before)
    assert diff.get("spill_batches", 0) > 0, diff
    assert diff.get("spill_bytes", 0) > 0, diff
    # reset_counters zeroes the spill vocabulary in the registry
    mem.reset_counters()
    assert registry().get("spill_batches") == 0
    assert registry().get("spill_bytes") == 0


# ---------------------------------------------------------------------------
# Event log schema round trip (satellite)
# ---------------------------------------------------------------------------

def test_event_log_round_trip(tmp_path):
    from daft_tpu.observability.event_log import (SCHEMA_VERSION,
                                                  disable_event_log,
                                                  enable_event_log)

    assert SCHEMA_VERSION == 11
    p = str(tmp_path / "ev.jsonl")
    sub = enable_event_log(p)
    try:
        daft_tpu.from_pydict({"a": list(range(100))}).where(
            col("a") > 4).to_pydict()
    finally:
        disable_event_log(sub)
    events = [json.loads(l) for l in open(p)]
    assert events and all(e["schema_version"] == 11 for e in events)
    ops = [e for e in events if e["event"] == "operator_stats"]
    assert ops
    for o in ops:
        for f in ("compute_seconds", "starve_seconds", "blocked_seconds"):
            assert f in o, o
        assert o["seconds"] == pytest.approx(
            o["compute_seconds"] + o["starve_seconds"] + o["blocked_seconds"])

"""The program's spans as a tree by containment (`benchmark/spantree.py`), the
per-layer readers that stand on it, and the builder's report of one traced
run (`benchmark/spanreport.py`). Hand-made spans: nothing here is a
measurement."""

import glob
import json
import os

import pytest

import run
import spanreport
import spantree
from bench_helpers import BENCH, REPO


def reader(name):
    return run.load_module(os.path.join(BENCH, "layer_metrics", name + ".py"))


# one execution of a join query, 0..100 on the spans' clock, as the program
# records it: main thread, with a stage thread's operator overlapping it
JOIN_SPANS = [
    ("query", 1.0, 90.0),
    ("plan.optimize", 2.0, 4.0),
    ("plan.translate", 4.0, 5.0),
    ("op.PhysSort", 6.0, 89.0),
    ("op.DeviceJoinAgg(2 dims)", 7.0, 88.0),       # stage thread
    ("placement.decide", 8.0, 12.0),
    ("placement.decide", 9.0, 11.0),                # a decider inside a decider
    ("join.codes", 13.0, 14.0),                     # the dictionary product, before the dispatch
    ("device.dispatch", 14.0, 54.0),
    ("join.gather", 15.0, 40.0),
    ("join.index", 16.0, 20.0),
    ("residency.build", 17.0, 19.0),                # deeper than the join's parts
    ("join.codes", 40.0, 46.0),
    ("join.index", 41.0, 43.0),                     # the probe inside the factorize
    ("device.launch", 47.0, 53.0),
    ("stage.finalize", 60.0, 80.0),
    ("device.d2h", 62.0, 70.0),
    ("op.InMemoryScan", 7.5, 30.0),                 # another thread, overlapping the dispatch
    ("result.encode", 91.0, 94.0),
]
JOIN_RUNS = [{"template": "q3", "unix_start": 0.0, "unix_end": 100.0, "start": 0.0,
              "end": 100.0, "failed": False,
              "counters": {"hbm_cache_misses": 3, "h2d_upload_us": 250_000}},
             {"template": "q12", "unix_start": 100.0, "unix_end": 110.0, "start": 100.0,
              "end": 110.0, "failed": False, "counters": {}}]


def ctx_of(spans, runs, **more):
    return dict({"spans": list(spans), "executions": list(runs), "to_trace": 0.0,
                 "window": (0.0, 110.0), "busy": []}, **more)


def test_owner_is_the_span_that_began_last_and_every_moment_has_one():
    spans = [("a", 0.0, 10.0), ("b", 2.0, 6.0), ("c", 3.0, 4.0),
             ("t", 5.0, 12.0)]  # `t`: another thread, running past `a`
    assert spantree.owners(spans) == [
        (0.0, 2.0, "a"), (2.0, 3.0, "b"), (3.0, 4.0, "c"), (4.0, 5.0, "b"),
        (5.0, 12.0, "t")]
    own = spantree.self_seconds(spans)
    assert own == pytest.approx({"a": 2.0, "b": 2.0, "c": 1.0, "t": 7.0})
    assert sum(own.values()) == pytest.approx(12.0)  # the union, never more
    # among two names only: what nests deeper stays with the span around it
    assert spantree.self_seconds(spans, ("a", "b")) == pytest.approx({"a": 6.0, "b": 4.0})
    assert spantree.self_seconds([]) == {}
    assert spantree.self_seconds([("z", 3.0, 3.0)]) == {}  # an empty span owns nothing


def test_an_owner_that_ends_hands_back_to_the_span_still_open():
    # `b` began last but ends first; then `a` again; nothing open in 10..11
    spans = [("a", 0.0, 10.0), ("b", 1.0, 2.0), ("b", 4.0, 5.0), ("d", 11.0, 12.0)]
    assert spantree.owners(spans) == [
        (0.0, 1.0, "a"), (1.0, 2.0, "b"), (2.0, 4.0, "a"), (4.0, 5.0, "b"),
        (5.0, 10.0, "a"), (11.0, 12.0, "d")]


def test_covered_counts_nested_and_overlapping_spans_once():
    spans = [("p", 8.0, 12.0), ("p", 9.0, 11.0), ("p", 11.5, 13.0), ("q", 0.0, 1.0)]
    assert spantree.covered(spans, ("p",)) == [(8.0, 13.0)]
    assert spantree.covered_seconds(spans, ("p",)) == pytest.approx(5.0)
    assert spantree.covered_seconds(spans) == pytest.approx(6.0)
    assert spantree.covered(spans, lambda n: n != "p") == [(0.0, 1.0)]


def test_in_window_keeps_the_spans_an_execution_holds_whole():
    runs = [{"unix_start": 10.0, "unix_end": 20.0}, {"unix_start": 30.0, "unix_end": 40.0}]
    spans = [("warmup", 1.0, 2.0), ("a", 10.0, 20.0), ("b", 19.0, 21.0),
             ("c", 31.0, 32.0), ("between", 22.0, 29.0)]
    assert [s[0] for s in spantree.in_window(spans, runs)] == ["a", "c"]


def test_join_dispatches_are_the_dispatch_spans_that_hold_a_join_span():
    spans = JOIN_SPANS + [("device.dispatch", 101.0, 102.0)]  # a scan stage's dispatch
    assert spantree.join_dispatches(spans) == [("device.dispatch", 14.0, 54.0)]


EXPECTED_ON_THE_JOIN = {
    # (2 + 1) s of plan over 2 executions, in ms
    "plan.plan_ms": 1500.0,
    # the nested decider counts once: 4 s
    "placement.decide_ms": 2000.0,
    # op.* self time: Sort 6-7, 88-89; Join 7-7.5 (then the scan began later and owns
    # up to 8), 30-60 less decide 8-12 (before 30), codes, dispatch: 54-60, and 80-88
    "host.ops_ms": 1e3 * (2.0 + 0.5 + 6.0 + 8.0 + (8.0 - 7.5) + (13.0 - 12.0)) / 2,
    # finalize 20 s less the 8 s fetch
    "stages.finalize_ms": 6000.0,
    "result.encode_ms": 1500.0,
    "stages.launch_ms": 6000.0,
    # per join dispatch (one): codes 1 + (6 - 2) s; index 4 + 2 s (the residency build
    # inside stays with it); gather 25 - 4 s
    "join.codes_ms": 5000.0,
    "join.index_ms": 6000.0,
    "join.gather_ms": 21000.0,
    "residency.misses_per_query": 1.5,
}


@pytest.mark.parametrize("name", sorted(EXPECTED_ON_THE_JOIN))
def test_reader_on_a_hand_made_join_execution(name):
    got = reader(name).read(ctx_of(JOIN_SPANS, JOIN_RUNS))
    assert got == pytest.approx(EXPECTED_ON_THE_JOIN[name]), name


def test_the_host_ops_expectation_is_the_owner_sweep_by_hand():
    """The stretches the `op.*` spans own in JOIN_SPANS, one by one."""
    own = [(a, b, n) for a, b, n in spantree.owners(JOIN_SPANS) if n.startswith("op.")]
    assert own == [
        (6.0, 7.0, "op.PhysSort"), (7.0, 7.5, "op.DeviceJoinAgg(2 dims)"),
        (7.5, 8.0, "op.InMemoryScan"), (12.0, 13.0, "op.InMemoryScan"),
        (54.0, 60.0, "op.DeviceJoinAgg(2 dims)"), (80.0, 88.0, "op.DeviceJoinAgg(2 dims)"),
        (88.0, 89.0, "op.PhysSort")]


PARENT_SPANS = [("device.h2d", 14.0, 15.0), ("device.dispatch", 15.0, 54.0),
                ("device.d2h", 62.0, 70.0)]
SPAN_READERS = ["plan.plan_ms", "placement.decide_ms", "host.ops_ms", "stages.finalize_ms",
                "result.encode_ms", "stages.launch_ms", "join.codes_ms", "join.index_ms",
                "join.gather_ms"]


@pytest.mark.parametrize("name", SPAN_READERS)
def test_span_reader_has_nothing_to_read_from_a_program_without_the_tree(name):
    """The parent's spans (device.* only): None, never an exception, so the
    result line leaves the metric out."""
    assert reader(name).read(ctx_of(PARENT_SPANS, JOIN_RUNS)) is None
    assert reader(name).read(ctx_of([], JOIN_RUNS)) is None


@pytest.mark.parametrize("name", ["join.codes_ms", "join.index_ms", "join.gather_ms"])
def test_join_reader_is_none_where_no_join_dispatched(name):
    scan = [("query", 1.0, 9.0), ("plan.optimize", 1.0, 2.0), ("device.dispatch", 3.0, 4.0),
            ("device.launch", 3.2, 3.8), ("stage.finalize", 5.0, 6.0)]
    runs = [dict(JOIN_RUNS[0], unix_end=10.0)]
    assert reader(name).read(ctx_of(scan, runs)) is None
    assert reader("stages.launch_ms").read(ctx_of(scan, runs)) == pytest.approx(600.0)
    assert reader("placement.decide_ms").read(ctx_of(scan, runs)) == 0.0


def test_launch_reader_is_none_where_nothing_launched():
    host_only = [("query", 1.0, 9.0), ("op.PhysAgg", 2.0, 8.0)]
    assert reader("stages.launch_ms").read(ctx_of(host_only, JOIN_RUNS[:1])) is None
    assert reader("host.ops_ms").read(ctx_of(host_only, JOIN_RUNS[:1])) == pytest.approx(6000.0)


def test_first_touch_is_the_process_total_less_the_window(monkeypatch):
    from daft_tpu.ops import counters

    monkeypatch.setattr(counters, "snapshot", lambda: {
        "h2d_upload_us": 31_250_000, "dict_encode_us": 2_000_000, "hbm_h2d_bytes": 7})
    got = reader("setup.first_touch_s").read(ctx_of(JOIN_SPANS, JOIN_RUNS))
    assert got == pytest.approx(33.0)
    # a program without the counters (the parent): nothing to read
    monkeypatch.setattr(counters, "snapshot", lambda: {"hbm_h2d_bytes": 7})
    assert reader("setup.first_touch_s").read(ctx_of(JOIN_SPANS, JOIN_RUNS)) is None


def test_unattributed_idle_is_the_idle_no_span_but_the_root_covers():
    read = reader("idle.unattributed_share").read
    spans = [("query", 0.0, 10.0), ("plan.optimize", 1.0, 2.0), ("device.dispatch", 4.0, 8.0)]
    # busy 5..7: idle 0..5 and 7..10 = 8 s; named idle: 1..2, 4..5, 7..8 = 3 s
    ctx = ctx_of(spans, JOIN_RUNS, busy=[(5.0, 7.0)], window=(0.0, 10.0))
    assert read(ctx) == pytest.approx(100.0 * 5.0 / 8.0)
    # the spans are moved onto the trace's clock
    moved = [(n, a - 100.0, b - 100.0) for n, a, b in spans]
    assert read(dict(ctx, spans=moved, to_trace=100.0)) == pytest.approx(62.5)
    # the parent's device.* spans read as its `host.other` share did
    assert read(dict(ctx, spans=[("device.dispatch", 4.0, 8.0)])) == pytest.approx(75.0)
    # the root alone names nothing; no span at all is nothing to read
    assert read(dict(ctx, spans=[("query", 0.0, 10.0)])) is None
    assert read(dict(ctx, spans=[])) is None
    # a window with no idle in it
    assert read(dict(ctx, busy=[(0.0, 10.0)])) == 0.0


def test_every_new_metric_names_the_cells_it_reads_in():
    """The entries this PR appends: a `workloads` list each, the join's three
    in the join cell only."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    both = ["tpch_sf10.scanagg", "tpch_sf1.joins"]
    for name in SPAN_READERS + ["residency.misses_per_query", "setup.first_touch_s",
                                "idle.unattributed_share"]:
        want = ["tpch_sf1.joins"] if name.startswith("join.") else both
        assert per_layer[name]["workloads"] == want, name
        assert per_layer[name]["better"] == "lower"


def test_span_report_of_a_recorded_cpu_profile(tmp_path):
    """`spanreport.py` on a directory as `run.py --trace 1` leaves it: the
    split by template, the split of the join dispatch, and the skew between
    a span's `time.time()` start and its annotation in the profile."""
    import time

    import jax

    from daft_tpu.observability.runtime_stats import SpanRecorder, profile_span, set_spans

    log_dir = str(tmp_path / "cell-1")
    rec = SpanRecorder()
    set_spans(rec)
    jax.profiler.start_trace(log_dir)
    sync_unix = time.time()
    with jax.profiler.TraceAnnotation("bench.sync"):
        pass
    runs = []
    try:
        for k in range(3):
            t0 = time.time()
            with profile_span("query", "query"):
                with profile_span("device.dispatch", "device"):
                    with profile_span("join.gather", "device"):
                        time.sleep(0.004)
                    with profile_span("device.launch", "device"):
                        time.sleep(0.001)
            runs.append({"template": "q3", "unix_start": t0, "unix_end": time.time(),
                         "start": 0.01 * k, "end": 0.01 * k + 0.006, "failed": False})
    finally:
        jax.profiler.stop_trace()
        set_spans(None)
    import xtrace as tr

    sync_s = tr.read_xplane(tr.find_xplane(log_dir))["sync_s"]
    spans = [(s["name"], s["ts"], s["ts"] + s["dur"]) for s in rec.drain()]
    with open(os.path.join(log_dir, "reduced.json"), "w") as f:
        json.dump({"spans": spans, "executions": runs, "to_trace": sync_s - sync_unix}, f)
    assert glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))

    out = spanreport.report(log_dir)
    q3 = out["templates"]["q3"]
    assert q3["executions"] == 3 and q3["median_ms"] == pytest.approx(6.0)
    assert q3["self_ms_per_execution"]["join.gather"] >= 4.0
    share = out["join_dispatch"]["share"]
    assert out["join_dispatch"]["dispatches"] == 3
    assert share["join.gather"] > share["device.launch"] > share["device.dispatch"]
    assert sum(share.values()) == pytest.approx(1.0)
    skew = out["clock_skew_us"]
    assert skew["spans"] == skew["annotations"] == 3
    assert skew["max_abs"] < 5_000  # the two clocks read microseconds apart
    assert spanreport.report(log_dir, skew_span="no.such.span")["clock_skew_us"] is None

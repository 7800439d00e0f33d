"""The cold sites: work that only a first execution does, timed where it
happens (`timed_span(counter=...)`, `utils/jax_setup._build_report`,
`query_wall_us`), so that set-up can be read from counters alone.

On the CPU with the device path forced: nothing here is a measurement."""

import types

import pytest

import daft_tpu
from benchmarking.tpch.datagen import load_dataframes
from benchmarking.tpch.queries import ALL_QUERIES
from daft_tpu import col
from daft_tpu.config import execution_config_ctx
from daft_tpu.observability import runtime_stats as rs
from daft_tpu.observability.metrics import DECLARED_COUNTERS, registry
from daft_tpu.observability.runtime_stats import (SpanRecorder, profile_span, set_spans,
                                                   timed_span)

# every counter a cold site adds to; `query_wall_us` is the one a warm query moves
COLD = ("h2d_upload_us", "h2d_prepare_us", "dict_encode_us", "content_hash_us",
        "residency_build_us", "jax_trace_us", "jax_lower_us", "xla_compile_us", "calibrate_us")
DEVICE = dict(device_mode="on", device_min_rows=1, mesh_devices=1)


def test_the_cold_counters_are_declared():
    assert set(COLD + ("query_wall_us",)) <= set(DECLARED_COUNTERS)


def test_the_helper_counts_with_no_recorder_and_records_the_same_extent_with_one():
    name = "dict_encode_us"  # any declared counter: the helper does not care which
    assert rs.current_spans() is None
    before = registry().get(name)
    with timed_span("series.dict_encode", "host", counter=name, rows=3) as sp:
        sum(range(20_000))
    counted = registry().get(name) - before
    assert sp.seconds > 0 and counted == int(sp.seconds * 1e6)

    rec = SpanRecorder()
    set_spans(rec)
    try:
        before = registry().get(name)
        with timed_span("series.dict_encode", "host", counter=name, rows=3) as sp:
            sum(range(20_000))
        counted = registry().get(name) - before
    finally:
        set_spans(None)
    (span,) = rec.drain()
    assert span["name"] == "series.dict_encode" and span["args"]["rows"] == 3
    # the span and the counter hold the same extent, to the microsecond
    assert counted == int(span["dur"] * 1e6) == int(sp.seconds * 1e6)
    # without a counter the helper counts nothing
    before = registry().snapshot()
    with timed_span("plan.optimize", "plan"):
        pass
    assert registry().diff(before) == {}


def test_cold_counters_are_self_times_and_a_part_takes_nothing():
    import time

    outer_c, inner_c, part_c = "residency_build_us", "h2d_upload_us", "h2d_prepare_us"
    before = registry().snapshot()
    with timed_span("residency.build", "device", counter=outer_c) as outer:
        sum(range(400_000))
        with timed_span("device.upload", "device", counter=inner_c) as inner:
            with timed_span("device.upload.prepare", "host", counter=part_c, part=True) as part:
                sum(range(20_000))
            sum(range(20_000))
        time.sleep(0.001)
        now = time.time()  # a program's build after the upload, reported as it ended
        reported = rs.cold_self_seconds(now - 0.0001, now)
    diff = registry().diff(before)
    assert diff[part_c] == int(part.seconds * 1e6) > 0
    assert diff[inner_c] == int(inner.seconds * 1e6) > diff[part_c]  # its part left it whole
    assert reported == pytest.approx(0.0001, abs=1e-6)
    assert diff[outer_c] == pytest.approx((outer.seconds - inner.seconds - 0.0001) * 1e6, abs=2)
    # the outer site now stands for what it held: a site around it would count none of it
    assert rs._local.cold[-1] == (outer._t0, outer._t0 + outer.seconds)


def test_a_cold_span_inside_a_build_report_is_counted_once(monkeypatch):
    """An encode or an upload run while JAX traces a program: the trace's
    report, heard as it ends, leaves the span's seconds to the span."""
    from daft_tpu.utils import jax_setup

    clock = [5000.0]
    fake = types.SimpleNamespace(time=lambda: clock[0])
    monkeypatch.setattr(jax_setup, "time", fake)
    monkeypatch.setattr(rs, "time", fake)
    monkeypatch.setattr(rs._local, "cold", [], raising=False)
    trace = next(iter(jax_setup._BUILD_EVENTS))
    before = registry().snapshot()
    clock[0] = 5001.0  # the trace began at 5000
    with timed_span("series.dict_encode", "host", counter="dict_encode_us"):
        clock[0] = 5003.0
    clock[0] = 5004.0
    jax_setup._build_report(trace, 4.0)
    diff = registry().diff(before)
    assert diff == {"dict_encode_us": 2_000_000, "jax_trace_us": 2_000_000}


@pytest.fixture(scope="module")
def repeats():
    """{query: (what its first execution over collected tables added to the
    registry, what its second added)}."""
    tables = {k: v.collect() for k, v in load_dataframes(sf=0.01, seed=36).items()}
    out = {}
    with execution_config_ctx(**DEVICE):
        for q in (1, 6):
            deltas = []
            for _ in range(2):
                before = registry().snapshot()
                ALL_QUERIES[q](tables).to_pydict()
                deltas.append(registry().diff(before))
            out[q] = tuple(deltas)
    return out


def test_a_first_execution_reaches_the_cold_sites(repeats):
    """Or the guard below would hold of sites nothing runs."""
    first, _second = repeats[1]
    # the sites a table's own data reaches (a program may have been built by
    # another test of the process: the launch test below builds its own)
    data_bound = ("h2d_upload_us", "h2d_prepare_us", "dict_encode_us", "content_hash_us",
                  "residency_build_us")
    assert all(first.get(name, 0) > 0 for name in data_bound), first
    assert first["h2d_prepare_us"] <= first["h2d_upload_us"]  # prepare lies inside upload
    assert first["query_wall_us"] >= first["h2d_upload_us"] + first["dict_encode_us"]


@pytest.mark.parametrize("name", COLD)
@pytest.mark.parametrize("query", (1, 6))
def test_a_second_execution_moves_no_cold_counter(repeats, query, name):
    """Cold only: a site may name a counter only if a warm execution over a
    resident table never reaches it."""
    _first, second = repeats[query]
    assert second.get(name, 0) == 0, second
    assert second["query_wall_us"] > 0  # the one counter a warm query moves


def test_calibration_is_a_cold_site_with_the_terms_it_probed():
    from daft_tpu.ops import costmodel

    costmodel.reset_calibration()
    rec = SpanRecorder()
    set_spans(rec)
    builds = ("jax_trace_us", "jax_lower_us", "xla_compile_us")
    try:
        before = registry().snapshot()
        costmodel.calibrate()
        diff = registry().diff(before)
        costmodel.calibrate()  # calibrated: the site is not reached again
        assert registry().diff(before) == diff
    finally:
        set_spans(None)
        costmodel.reset_calibration()
    spans = rec.drain()
    (cal,) = [s for s in spans if s["name"] == "placement.calibrate"]
    # self times: the calibration counts its extent less the programs its
    # probes built, which count themselves, so the counters add up to it
    built = sum(diff[c] for c in builds)
    assert 0 < diff["calibrate_us"] < int(cal["dur"] * 1e6) and built > 0
    assert diff["calibrate_us"] + built == pytest.approx(cal["dur"] * 1e6, abs=100)
    # the CPU has links to time and no mesh to probe
    assert cal["args"]["probed"] == "rtt,h2d,d2h"
    # the probes' own programs are leaves under the calibration
    built = [s for s in spans if s["name"].startswith("xla.")]
    assert built and all(s["args"]["parent"] == cal["args"]["id"] for s in built)


def test_program_build_spans_hang_under_the_launch_that_compiled():
    df = daft_tpu.from_pydict({"cold_k": [i % 5 for i in range(4_000)],
                               "cold_v": [float(i) for i in range(4_000)]}).collect()
    rec = SpanRecorder()
    set_spans(rec)
    before = registry().snapshot()
    try:
        with execution_config_ctx(**DEVICE):
            # a shape over a schema no other test compiles
            v = col("cold_v")
            df.groupby("cold_k").agg(((v * 3 + 0.36) * v - v).max().alias("m"),
                                     (v / 36).min().alias("n")).to_pydict()
    finally:
        set_spans(None)
    spans = rec.drain()
    diff = registry().diff(before)
    assert all(diff[c] > 0 for c in ("jax_trace_us", "jax_lower_us", "xla_compile_us"))
    launches = {s["args"]["id"]: s for s in spans if s["name"] == "device.launch"}
    for name in ("xla.trace", "xla.lower", "xla.compile"):
        under = [s for s in spans if s["name"] == name and s["args"]["parent"] in launches]
        assert under, (name, sorted({s["name"] for s in spans}))
        for s in under:  # a leaf, inside its launch's extent (to the listener's latency)
            launch = launches[s["args"]["parent"]]
            assert s["ts"] >= launch["ts"] - 1e-3
            assert s["ts"] + s["dur"] <= launch["ts"] + launch["dur"] + 1e-3
            assert s["args"]["qid"] == launch["args"]["qid"] != ""


def test_nested_build_reports_count_every_second_once(monkeypatch):
    """JAX reports a duration as the work ends and the reports nest: a
    counter takes an extent less what was reported inside it."""
    from daft_tpu.utils import jax_setup

    clock = [1000.0]
    monkeypatch.setattr(jax_setup, "time", types.SimpleNamespace(time=lambda: clock[0]))
    monkeypatch.setattr(rs._local, "cold", [], raising=False)
    trace, lower, comp = list(jax_setup._BUILD_EVENTS)
    names = [c for _span, c in jax_setup._BUILD_EVENTS.values()]
    before = {n: registry().get(n) for n in names}

    def report(event, start, end):
        clock[0] = end
        jax_setup._build_report(event, end - start)

    report(trace, 1.0, 2.0)      # a jnp function traced inside the program's trace
    report(comp, 2.5, 3.0)       # a constant computed eagerly inside it
    report(trace, 0.0, 4.0)      # the program's own trace: 4 s, 1.5 of them counted
    report(lower, 4.0, 5.0)
    report(comp, 5.0, 7.0)
    report("/jax/some/other/event", 0.0, 100.0)
    got = {n: registry().get(n) - before[n] for n in names}
    assert got == {"jax_trace_us": 3_500_000, "jax_lower_us": 1_000_000,
                   "xla_compile_us": 2_500_000}
    assert sum(got.values()) == 7_000_000  # the wall time the reports cover


def test_an_upload_has_its_preparation_inside_it():
    df = daft_tpu.from_pydict({"k": ["a", "b"] * 1_500,
                               "v": [float(i) for i in range(3_000)]}).collect()
    rec = SpanRecorder()
    set_spans(rec)
    before = registry().snapshot()
    try:
        with execution_config_ctx(**DEVICE):
            df.groupby("k").agg(col("v").sum().alias("s")).to_pydict()
    finally:
        set_spans(None)
    spans, diff = rec.drain(), registry().diff(before)
    uploads = {s["args"]["id"]: s for s in spans if s["name"] == "device.upload"}
    prepares = [s for s in spans if s["name"] == "device.upload.prepare"]
    assert uploads and len(prepares) == len(uploads)
    for p in prepares:
        up = uploads[p["args"]["parent"]]
        assert p["args"]["rows"] == up["args"]["rows"] == 3_000
        assert p["args"]["bytes"] == up["args"]["bytes"] > 0 and p["args"]["pad_to"] >= 3_000
        assert p["dur"] <= up["dur"]
    # a residency build counts what is left of it: its uploads count themselves
    assert 0 <= diff["residency_build_us"] <= sum(
        int(s["dur"] * 1e6) for s in spans if s["name"] == "residency.build") \
        - diff["h2d_upload_us"] + 100
    # a column's content is hashed for its stable slot key, once, under the build that missed
    prints = [s for s in spans if s["name"] == "series.fingerprint"]
    builds = {s["args"]["id"] for s in spans if s["name"] == "residency.build"}
    assert {s["args"]["rows"] for s in prints} == {3_000}
    assert not any(s["args"]["parent"] in builds for s in prints)  # before the build, at the probe


def test_a_querys_wall_time_reaches_its_query_end_record():
    from daft_tpu.observability import attach_subscriber, detach_subscriber

    class Sub:
        ends = []

        def on_query_end(self, ev):
            self.ends.append(ev)

    sub = Sub()
    attach_subscriber(sub)
    try:
        daft_tpu.from_pydict({"a": [1, 2, 3]}).where(col("a") > 1).to_pydict()
    finally:
        detach_subscriber(sub)
    (end,) = sub.ends
    assert end.metrics["query_wall_us"] == int(end.seconds * 1e6) > 0


def test_from_arrow_is_a_span_under_a_recorder_and_nothing_without():
    import pyarrow as pa

    table = pa.table({"a": pa.chunked_array([[1, 2], [3]])})
    assert profile_span("x", "host") is rs._NO_SPAN
    daft_tpu.from_arrow(table)
    rec = SpanRecorder()
    set_spans(rec)
    try:
        daft_tpu.from_arrow(table)
    finally:
        set_spans(None)
    (span,) = rec.drain()
    assert span["name"] == "load.from_arrow"
    assert span["args"]["rows"] == 3 and span["args"]["bytes"] == table.nbytes

"""The spans that name what the `query` root and a join's first dispatch did in
the dark (PR 51): `query.open`, `query.plan_key`, `query.close` inside the
root, also when the consumer leaves early or the plan raises; `join.tables`
once a TopN run, `join.pack_lines` and `join.query_pack` once a query; and the
off path, where every one of them is the shared no-op."""

import time

import numpy as np
import pytest

import daft_tpu
import test_device_join as tj
from daft_tpu import col
from daft_tpu.config import execution_config_ctx
from daft_tpu.observability import runtime_stats as rs
from daft_tpu.observability.runtime_stats import SpanRecorder, current_spans, set_spans
from daft_tpu.runners import get_or_create_runner

QUERY_LIFE = ["query.open", "plan.optimize", "plan.translate", "query.plan_key", "query.close"]


@pytest.fixture(scope="module")
def lineitem():
    rng = np.random.default_rng(51)
    n = 6000
    return daft_tpu.from_pydict({
        "l_quantity": rng.integers(1, 51, n).astype(float).tolist(),
        "l_extendedprice": rng.uniform(900, 100000, n).round(2).tolist(),
        "l_discount": (rng.integers(0, 11, n) / 100).tolist(),
        "l_shipdate": rng.integers(0, 2500, n).tolist()}).collect()


def _q6(df):
    return (df.where((col("l_shipdate") >= 365) & (col("l_shipdate") < 730)
                     & (col("l_discount") >= 0.05) & (col("l_discount") <= 0.07)
                     & (col("l_quantity") < 24))
            .agg((col("l_extendedprice") * col("l_discount")).sum().alias("revenue")))


def _recorded(run):
    rec = SpanRecorder()
    set_spans(rec)
    try:
        out = run()
    finally:
        set_spans(None)
    assert rec.dropped == 0
    return out, rec.drain()


def _children_of_the_one_root(spans):
    """The `query` root (exactly one) and the names of its children in the
    order they began, every one within the root's extent and under its qid."""
    roots = [s for s in spans if s["name"] == "query"]
    assert len(roots) == 1, [s["name"] for s in spans]
    root = roots[0]
    kids = sorted((s for s in spans if s["args"]["parent"] == root["args"]["id"]),
                  key=lambda s: s["ts"])
    eps = 1e-6  # time.time() pairs taken microseconds apart
    for s in kids:
        assert s["args"]["qid"] == root["args"]["qid"] != ""
        assert root["ts"] - eps <= s["ts"]
        assert s["ts"] + s["dur"] <= root["ts"] + root["dur"] + eps
    return root, [s["name"] for s in kids], {s["name"]: s for s in kids}


@pytest.mark.parametrize("device_mode", ["off", "on"])
def test_a_warm_q6_records_the_querys_life_under_its_root(lineitem, device_mode):
    with execution_config_ctx(device_mode=device_mode, device_min_rows=1, mesh_devices=1):
        want = _q6(lineitem).to_pydict()                    # warm: planes, programs
        got, spans = _recorded(lambda: _q6(lineitem).to_pydict())
    assert got == want
    root, names, kids = _children_of_the_one_root(spans)
    assert [n for n in names if not n.startswith("op.")] == QUERY_LIFE
    assert names[-1] == "query.close" and names[:3] == QUERY_LIFE[:3]
    # the flight recorder is on by default: the query is recorded, nobody observes it
    assert kids["query.open"]["args"]["observed"] is False
    assert kids["query.open"]["args"]["recorded"] is True
    assert kids["query.close"]["args"]["rows"] == root["args"]["rows"] == 1
    assert "error" not in kids["query.close"]["args"]
    # what is left to the bare root is less than what the three took
    named = sum(kids[n]["dur"] for n in ("query.open", "query.plan_key", "query.close"))
    assert named > 0.0


def test_an_observed_query_says_so_and_still_closes_under_the_root(lineitem):
    from daft_tpu.observability import attach_subscriber, detach_subscriber
    from daft_tpu.observability.subscribers import Subscriber

    class Sub(Subscriber):
        ended = None

        def on_query_end(self, event):
            self.ended = event

    sub = Sub()
    attach_subscriber(sub)
    try:
        with execution_config_ctx(device_mode="off"):
            _out, spans = _recorded(lambda: _q6(lineitem).to_pydict())
    finally:
        detach_subscriber(sub)
    root, names, kids = _children_of_the_one_root(spans)
    assert [n for n in names if not n.startswith("op.")] == QUERY_LIFE
    assert kids["query.open"]["args"]["observed"] is True
    assert sub.ended is not None and sub.ended.query_id == root["args"]["qid"]


def test_a_consumer_that_stops_early_still_closes_the_query_under_the_root():
    """`run_iter`'s stream closed after its first partition: the root's own
    close unwinds `_run_iter` INSIDE the root (`runtime_stats._span_iter`), so
    its `finally` is still `query.close`, a child of the root, with the rows
    that got out."""
    df = daft_tpu.from_pydict({"v": list(range(4000))}).into_partitions(4).collect()

    def run():
        with execution_config_ctx(device_mode="off"):
            stream = get_or_create_runner().run_iter(df.where(col("v") >= 0)._builder)
            first = next(stream)
            stream.close()
            return first.num_rows

    rows, spans = _recorded(run)
    root, names, kids = _children_of_the_one_root(spans)
    assert [n for n in names if not n.startswith("op.")] == QUERY_LIFE
    assert 0 < rows < 4000
    assert kids["query.close"]["args"]["rows"] == root["args"]["rows"] == rows
    assert "error" not in root["args"] and "error" not in kids["query.close"]["args"]


def test_a_plan_that_raises_still_closes_the_query_under_the_root(monkeypatch):
    import daft_tpu.execution.executor as executor

    def boom(_phys):
        yield from ()
        raise RuntimeError("the plan raises")

    monkeypatch.setattr(executor, "execute_plan", boom)
    df = daft_tpu.from_pydict({"v": [1.0, 2.0, 3.0]})

    def run():
        with pytest.raises(RuntimeError, match="the plan raises"):
            df.where(col("v") > 1).to_pydict()

    _none, spans = _recorded(run)
    root, names, kids = _children_of_the_one_root(spans)
    assert names == QUERY_LIFE
    assert root["args"]["error"] == "RuntimeError" and root["args"]["rows"] == 0
    assert kids["query.close"]["args"]["rows"] == 0
    assert "error" not in kids["query.close"]["args"]    # the close itself did not raise


def _by_id(spans):
    return {s["args"]["id"]: s for s in spans}


def _inside(span, ancestor, by_id):
    while span["args"]["parent"]:
        span = by_id[span["args"]["parent"]]
        if span is ancestor:
            return True
    return False


@pytest.mark.parametrize("shape", [tj._topn_q3, tj._topn_q10], ids=["q3", "q10"])
def test_a_topn_run_makes_its_tables_once_inside_its_first_dispatch(shape, monkeypatch):
    """Five dispatches a run (a bucket each); the run-wide tables are made at
    the first, under `join.tables` inside that `device.dispatch`, and no
    dispatch after it has one."""
    import daft_tpu.ops.grouped_stage as gs

    monkeypatch.setattr(gs, "DISPATCH_SEGMENTS", 1)
    t = tj._topn_tables(n_l=tj._MORSEL * 5 - 100)
    with tj._morselized("on"):
        want = shape(t).to_pydict()
        got, spans = _recorded(lambda: shape(t).to_pydict())
    assert got == want
    by_id = _by_id(spans)
    dispatches = sorted((s for s in spans if s["name"] == "device.dispatch"
                         and s["args"].get("op") == "join_topn"), key=lambda s: s["ts"])
    assert len(dispatches) == 5
    tables = [s for s in spans if s["name"] == "join.tables"]
    assert len(tables) == 1
    made = tables[0]
    assert by_id[made["args"]["parent"]] is dispatches[0]
    assert made["args"]["devices"] == 1 and made["args"]["cap"] > 0
    assert made["args"]["bytes"] > 0
    launch = next(s for s in spans if s["name"] == "device.launch"
                  and s["args"]["parent"] == dispatches[0]["args"]["id"])
    assert made["ts"] + made["dur"] <= launch["ts"] + 1e-6   # made before the first launch
    # the query's verdict laid under a pack: once a query and pack, inside the
    # first dispatch's provisioning, never in a later dispatch
    packs = [s for s in spans if s["name"] == "join.query_pack"]
    assert packs and all(_inside(s, dispatches[0], by_id) for s in packs)
    assert all(by_id[s["args"]["parent"]]["name"] == "join.gather" for s in packs)
    assert len({s["args"]["dim"] for s in packs}) == len(packs)
    _root, names, _kids = _children_of_the_one_root(spans)
    assert [n for n in names if not n.startswith("op.")] == QUERY_LIFE


def test_a_long_unordered_pack_is_laid_as_lines_once_a_query(monkeypatch):
    """q19's `part` pack, the fast-memory threshold taken away so that a
    test's pack is laid as lines: one `join.pack_lines` a query, inside the
    first dispatch's `join.gather`, with the pack's rows and bytes."""
    import daft_tpu.ops.device_join as dj

    monkeypatch.setattr(dj, "_FAST_PACK_BYTES", 0)
    t = tj._filtered_like(19)
    with tj._morselized("on"):
        want = tj._FILTERED["q19"](t).to_pydict()
        got, spans = _recorded(lambda: tj._FILTERED["q19"](t).to_pydict())
    assert got == want
    by_id = _by_id(spans)
    dispatches = sorted((s for s in spans if s["name"] == "device.dispatch"),
                        key=lambda s: s["ts"])
    assert len(dispatches) > 1
    lines = [s for s in spans if s["name"] == "join.pack_lines"]
    assert len(lines) == 1
    assert by_id[lines[0]["args"]["parent"]]["name"] == "join.gather"
    assert _inside(lines[0], dispatches[0], by_id)
    assert lines[0]["args"]["rows"] > 0 and lines[0]["args"]["bytes"] > 0


def test_with_no_recorder_every_new_site_is_the_shared_no_op(lineitem, monkeypatch):
    """No recorder: `profile_span` hands the one shared no-op to every new
    site (no clock read: `time.time` raises here), and nothing is recorded.
    The placement ledger is switched off for it: while it is on (the default)
    `placement.feedback` puts its own tee around a device stage run to price
    the `device.*` spans, and the sites inside the run are then live spans
    that the tee drops (once a run or a query each, for the new ones)."""
    from daft_tpu.observability import placement

    monkeypatch.setattr(placement.ledger(), "cap", 0)
    assert current_spans() is None
    asked = []
    real = rs.profile_span

    def spy(name, cat, **args):
        got = real(name, cat, **args)
        asked.append((name, got))
        return got

    class _Clock:
        perf_counter = staticmethod(time.perf_counter)

        @staticmethod
        def time():
            raise AssertionError("time.time() read on the off path")

    import daft_tpu.ops.device_join as dj
    import daft_tpu.ops.grouped_stage as gs

    monkeypatch.setattr(rs, "profile_span", spy)     # `_run_iter` imports it a query
    monkeypatch.setattr(dj, "profile_span", spy)
    monkeypatch.setattr(dj, "_FAST_PACK_BYTES", 0)
    monkeypatch.setattr(gs, "DISPATCH_SEGMENTS", 1)
    t3, t19 = tj._topn_tables(n_l=tj._MORSEL * 3 - 100), tj._filtered_like(19)
    with tj._morselized("on"):
        tj._topn_q3(t3).to_pydict()                  # warm, so that no cold site reads a clock
        tj._FILTERED["q19"](t19).to_pydict()
        monkeypatch.setattr(rs, "time", _Clock)
        tj._topn_q3(t3).to_pydict()
        tj._FILTERED["q19"](t19).to_pydict()
    with execution_config_ctx(device_mode="off"):
        _q6(lineitem).to_pydict()
    names = {name for name, _got in asked}
    assert {"query.open", "query.plan_key", "query.close", "join.tables", "join.pack_lines",
            "join.query_pack"} <= names
    assert all(got is rs._NO_SPAN for _name, got in asked)

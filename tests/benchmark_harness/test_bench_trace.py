"""Reduction of a profiler trace: busy time, idle share, gaps and their owners."""

import json
import os

import pytest

import xtrace as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def one_plane(*ops):
    return {"device": {"/device:TPU:0": {"XLA Ops": list(ops), "Steps": [("1", 0.0, 99.0)]}},
            "sync_s": 0.0}


def test_union_merges_overlap_and_touching_and_drops_empty():
    assert tr.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5), (7, 7)]) == [(0, 2.5), (3, 4)]
    assert tr.union([(0, 10), (2, 3)]) == [(0, 10)]
    assert tr.union([]) == []
    assert tr.length([(0, 2.5), (3, 4)]) == pytest.approx(3.5)


def test_clip_keeps_what_lies_in_the_window():
    assert tr.clip([(0, 2), (3, 5), (6, 7)], 1, 4) == [(1, 2), (3, 4)]


def test_busy_and_idle_share_use_the_op_line_only():
    t = one_plane(("fusion.1", 1.0, 1.0), ("fusion.2", 1.5, 1.0), ("copy", 4.0, 0.5))
    # busy: [1, 2.5] and [4, 4.5] = 2.0 s of the window [0, 5]; the Steps line is not work
    assert tr.busy_seconds(t, (0.0, 5.0)) == pytest.approx(2.0)
    assert tr.idle_share(t, (0.0, 5.0)) == pytest.approx(0.6)
    # a window that cuts an op counts the part inside
    assert tr.busy_seconds(t, (2.0, 5.0)) == pytest.approx(1.0)
    assert tr.busy_in(tr.busy_union(t), (1.2, 1.4)) == pytest.approx(0.2)
    assert tr.busy_in(tr.busy_union(t), (0.0, 4.2)) == pytest.approx(1.7)
    assert tr.busy_in(tr.busy_union(t), (2.6, 3.9)) == 0.0


def test_busy_is_averaged_over_device_planes():
    t = {"device": {"/device:TPU:0": {"XLA Ops": [("a", 0.0, 2.0)]},
                    "/device:TPU:1": {"XLA Ops": [("a", 0.0, 1.0)]}}, "sync_s": 0.0}
    assert tr.busy_seconds(t, (0.0, 4.0)) == pytest.approx(1.5)


def test_modules_line_stands_in_where_there_is_no_op_line():
    t = {"device": {"/device:TPU:0": {"XLA Modules": [("jit_f", 1.0, 1.0)]}}, "sync_s": 0.0}
    assert tr.busy_seconds(t, (0.0, 4.0)) == pytest.approx(1.0)


def test_a_trace_without_a_device_plane_is_an_error():
    with pytest.raises(ValueError):
        tr.busy_seconds({"device": {}, "sync_s": 0.0}, (0.0, 1.0))


def test_gaps_are_the_window_less_the_busy_intervals():
    assert tr.gaps([(1, 2), (3, 4)], (0, 5)) == [(0, 1), (2, 3), (4, 5)]
    assert tr.gaps([(0, 5)], (0, 5)) == []
    assert tr.gaps([], (0, 5)) == [(0, 5)]
    assert tr.gaps([(-1, 1), (4, 9)], (0, 5)) == [(1, 4)]


def test_gap_goes_to_the_innermost_open_span_else_to_host_other():
    spans = [("device.dispatch", 1.0, 4.0), ("device.h2d", 2.0, 3.0),
             ("not.a.layer", 0.0, 10.0)]
    out = tr.attribute_gaps([(0.0, 5.0)], spans)
    assert out == pytest.approx({"host.other": 2.0, "device.dispatch": 2.0, "device.h2d": 1.0})
    assert sum(out.values()) == pytest.approx(5.0)
    # several gaps, some with no span over them at all
    out = tr.attribute_gaps([(0.0, 0.5), (1.5, 2.5), (6.0, 7.0)], spans)
    assert out == pytest.approx({"host.other": 1.5, "device.dispatch": 0.5, "device.h2d": 0.5})
    assert tr.attribute_gaps([(0.0, 1.0)], []) == {"host.other": 1.0}


def test_top_ops_sums_by_name_inside_the_window():
    t = one_plane(("fusion.1", 0.0, 1.0), ("fusion.1", 2.0, 1.0), ("copy", 4.0, 2.0))
    assert tr.top_ops(t, (0.0, 5.0)) == [("fusion.1", 2.0), ("copy", 1.0)]
    assert tr.top_ops(t, (0.0, 5.0), n=1) == [("fusion.1", 2.0)]


def test_recorded_chip_trace_reduces_to_the_numbers_read_by_hand():
    """The first second of a traced window of the join cell on a TPU v5e (q12
    on the host, then the first dispatches of q5 on the device; recorded by
    PR 24). The expectations were taken from the whole trace before it was
    cut down; the `XLA Modules` line, which the reduction does not read where
    there is an op line, gives the busy time a second way."""
    with open(os.path.join(DATA, "recorded_trace.json")) as f:
        rec = json.load(f)
    trace, window = rec["trace"], tuple(rec["window"])
    trace["device"] = {p: {ln: [tuple(e) for e in ev] for ln, ev in lines.items()}
                       for p, lines in trace["device"].items()}
    assert tr.busy_seconds(trace, window) == pytest.approx(rec["expect"]["busy_s"], rel=1e-9)
    assert tr.idle_share(trace, window) == pytest.approx(rec["expect"]["idle_share"], rel=1e-9)
    assert tr.top_ops(trace, window, n=1)[0][0] == rec["expect"]["top_op"]
    busy = tr.busy_union(trace)
    idle = tr.attribute_gaps(tr.gaps(busy, window), [tuple(s) for s in rec["spans"]])
    assert sum(idle.values()) == pytest.approx(window[1] - window[0] - rec["expect"]["busy_s"])
    assert max(idle, key=idle.get) == rec["expect"]["widest_gap_owner"]
    assert idle == pytest.approx(rec["expect"]["idle_by_owner"])
    modules = next(iter(trace["device"].values()))["XLA Modules"]
    whole_programs = tr.length(tr.union(tr.clip([(s, s + d) for _n, s, d in modules], *window)))
    assert whole_programs == pytest.approx(rec["expect"]["busy_s"], rel=0.02)

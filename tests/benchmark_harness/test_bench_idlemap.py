"""The device's idle seconds by layer of the program (`benchmark/idlemap.py`),
its twelve readers `idle.<layer>_ms` and their entries in `BENCHMARK.json`
(PR 51). Hand-made windows and the recorded second of a v5e's trace: nothing
here is a measurement."""

import glob
import json
import os
import re

import pytest

import idlemap
import run
import spantree
import xtrace as tr
from bench_helpers import BENCH, REPO

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
KEYS = list(idlemap.LAYERS)
LAYER_IN_SPEC = {
    "plan": "Plan, optimize", "runner": "Plan, optimize", "placement": "Placement",
    "host_ops": "Host kernels", "scan": "Scan, decode", "h2d": "h2d, residency",
    "dispatch": "Device stages", "launch": "Device stages", "d2h": "Device stages",
    "finalize": "Device stages", "api": "Result", "client": "Device"}
LISTED = ["tpch_sf10.scanagg", "tpch_sf1.joins", "tpch_sf1.parquet_scan", "tpch_sf10.joins",
          "tpch_sf30_mesh4.joins"]
CLOSED_SETS = ["tpch_sf10.adhoc_scanagg", "tpch_sf30_mesh4.scanagg", "tpch_sf10.adhoc_joins",
               "tpch_sf10.filtered_joins"]


def reader(key):
    return run.load_module(os.path.join(BENCH, "layer_metrics", f"idle.{key}_ms.py"))


# ---- a hand-made window ------------------------------------------------------------------
# Ten seconds of the trace's clock (10..20). The spans and the executions carry
# unix time, 100 s behind it (`to_trace`). Two chips: A works 11-12 and 15-16,
# B 11.5-12.5 and 18-18.5, so nothing runs in 10-11, 12.5-15, 16-18, 18.5-20.

SHIFT = 100.0
WINDOW = (10.0, 20.0)
PLANE_A = [("fusion.1", 11.0, 1.0), ("fusion.2", 15.0, 1.0)]
PLANE_B = [("fusion.1", 11.5, 1.0), ("fusion.3", 18.0, 0.5)]


def trace_of(*planes):
    return {"sync_s": 0.0, "device": {
        f"/device:TPU:{k}": {"XLA Ops": list(ops), "Steps": []} for k, ops in enumerate(planes)}}


def unix(spans):
    return [(name, a - SHIFT, b - SHIFT) for name, a, b in spans]


# as they lie on the trace's clock; two executions, each with a bare head and
# tail inside its bounds, half a second of the client between them
SPANS = unix([
    ("query", 10.7, 13.9),
    ("plan.optimize", 10.7, 10.9),
    ("op.Aggregate", 11.0, 13.8),
    ("device.dispatch", 11.0, 11.4),        # over a busy device: owns no idle time
    ("device.d2h", 12.0, 13.0),             # its first half second is a wait, the rest exposed
    ("pipeline.morsel", 12.8, 13.5),        # another thread: began later, so it owns from 12.8
    ("query", 14.6, 18.9),
    ("device.h2d", 14.7, 15.2),
    ("stage.finalize", 16.0, 17.0),
    ("device.d2h", 16.2, 16.6),             # the innermost: cuts the finalize in two
    ("result.encode", 18.92, 18.97),        # after the root, inside the execution
])
RUNS = [{"template": "q1", "unix_start": 10.5 - SHIFT, "unix_end": 14.0 - SHIFT},
        {"template": "q6", "unix_start": 14.5 - SHIFT, "unix_end": 19.0 - SHIFT}]
OWNERS = {
    idlemap.CLIENT: 0.5 + 0.5 + 1.0,                    # 10-10.5, 14-14.5, 19-20
    idlemap.API: 0.2 + 0.1 + 0.1 + 0.02 + 0.03,         # heads and tails under no span
    "plan.optimize": 0.2, "query": 0.1 + 0.1 + 0.1 + 1.0 + 0.4,
    "device.d2h": 0.3 + 0.4, "pipeline.morsel": 0.7, "op.Aggregate": 0.3,
    "device.h2d": 0.3, "stage.finalize": 0.2 + 0.4, "result.encode": 0.05}
BY_LAYER = {"plan": 0.2, "runner": 1.7, "placement": 0.0, "host_ops": 1.0, "scan": 0.0,
            "h2d": 0.3, "dispatch": 0.0, "launch": 0.0, "d2h": 0.7, "finalize": 0.6,
            "api": 0.5, "client": 2.0}


def ctx_of(trace, spans=SPANS, runs=RUNS, window=WINDOW, to_trace=SHIFT):
    return {"trace": trace, "busy": tr.busy_union(trace), "window": window,
            "to_trace": to_trace, "spans": list(spans), "executions": list(runs),
            "window_s": window[1] - window[0]}


def test_idle_is_where_no_plane_works():
    two = ctx_of(trace_of(PLANE_A, PLANE_B))
    assert idlemap.idle(two) == [(10.0, 11.0), (12.5, 15.0), (16.0, 18.0), (18.5, 20.0)]
    # one chip: what `device.idle_share` reads, from the harness's own `busy`
    one = ctx_of(trace_of(PLANE_A))
    assert idlemap.idle(one) == tr.gaps(one["busy"], WINDOW)
    assert tr.length(idlemap.idle(one)) / 10.0 == pytest.approx(tr.idle_share(one["trace"], WINDOW))
    # four chips, two of them at work through 12.5-15: that stretch is no longer
    # idle, though the planes' mean (`device.idle_share`) still counts half of it
    four = ctx_of(trace_of(PLANE_A, PLANE_B, [("fusion.4", 12.5, 2.5)], [("fusion.4", 12.5, 2.5)]))
    assert idlemap.idle(four) == [(10.0, 11.0), (16.0, 18.0), (18.5, 20.0)]
    assert tr.length(idlemap.idle(four)) == pytest.approx(4.5)
    assert tr.idle_share(four["trace"], WINDOW) * 10.0 == pytest.approx((8.0 + 8.5 + 7.5 + 7.5) / 4)


def test_every_idle_stretch_goes_to_the_innermost_span_or_to_api_or_client():
    ctx = ctx_of(trace_of(PLANE_A, PLANE_B))
    own = idlemap.by_owner(ctx)
    assert own == pytest.approx(OWNERS, abs=1e-9)
    assert "device.dispatch" not in own          # its time lay over a busy device
    assert sum(own.values()) == pytest.approx(tr.length(idlemap.idle(ctx)), abs=1e-9)


def test_the_layers_add_up_to_the_idle_seconds():
    ctx = ctx_of(trace_of(PLANE_A, PLANE_B))
    got = idlemap.layers(ctx)
    assert list(got) == KEYS
    assert got == pytest.approx(BY_LAYER, abs=1e-9)
    assert sum(got.values()) == pytest.approx(7.0, abs=1e-9)
    assert idlemap.layers(ctx) is got           # made once a run, twelve readers ask


def test_api_and_client_split_at_the_executions_bounds():
    # no span but the roots: everything outside them is the API's inside an
    # execution and the client's outside one
    roots = [s for s in SPANS if s[0] == "query"]
    got = idlemap.layers(ctx_of(trace_of(PLANE_A, PLANE_B), spans=roots))
    assert got["client"] == pytest.approx(2.0, abs=1e-9)
    assert got["api"] == pytest.approx(0.2 + 0.1 + 0.1 + 0.1, abs=1e-9)
    assert got["runner"] == pytest.approx(7.0 - 2.0 - 0.5, abs=1e-9)
    # a window that starts before the first execution and ends after the last
    wide = idlemap.layers(ctx_of(trace_of(PLANE_A, PLANE_B), spans=roots, window=(8.0, 22.0)))
    assert wide["client"] == pytest.approx(2.0 + 2.0 + 2.0, abs=1e-9)
    assert sum(wide.values()) == pytest.approx(11.0, abs=1e-9)


def test_a_run_without_the_span_tree_has_nothing_to_read():
    ctx = ctx_of(trace_of(PLANE_A), spans=[s for s in SPANS if s[0] != "query"])
    assert idlemap.layers(ctx) is None


@pytest.mark.parametrize("key", KEYS)
def test_a_reader_on_the_hand_made_window(key):
    """Milliseconds an execution; 0.0, not None, where the tree is there and
    the layer owned nothing; None only without the tree."""
    got = reader(key).read(ctx_of(trace_of(PLANE_A, PLANE_B)))
    assert isinstance(got, float)
    assert got == pytest.approx(1e3 * BY_LAYER[key] / 2, abs=1e-6)
    bare = ctx_of(trace_of(PLANE_A, PLANE_B), spans=[s for s in SPANS if s[0] != "query"])
    assert reader(key).read(bare) is None


# ---- every span name has a layer ---------------------------------------------------------

def _spelled_in_the_program():
    """Every name a `profile_span`/`timed_span`/`span_iter`/`record_span` call
    under `daft_tpu/` spells (a name built from a prefix gives the prefix), and
    the names of the program builds, which reach `record_span` as a variable."""
    call = re.compile(r"\b(?:_?profile_span|timed_span|span_iter|record_span|span)\(\s*\"([^\"]+)\"")
    names = set()
    for path in glob.glob(os.path.join(REPO, "daft_tpu", "**", "*.py"), recursive=True):
        with open(path) as f:
            names.update(call.findall(f.read()))
    from daft_tpu.utils.jax_setup import _BUILD_EVENTS

    names.update(name for name, _counter in _BUILD_EVENTS.values())
    return sorted(names)


def _listed_in_perf_md():
    """The span names of `PERF.md` section 3's table: those of its names that
    are no counters (a span's name has a dot, or is the root's), cut at a `<`
    or `*` (`op.<Node>`, `device.udf_*`)."""
    from test_docs_truth import perf_md_spans_and_counters

    return sorted({re.split(r"[*<]", n)[0] for n in perf_md_spans_and_counters()
                   if "." in n or n == spantree.ROOT})


SPELLED, DOCUMENTED = _spelled_in_the_program(), _listed_in_perf_md()


def test_the_program_spells_and_perf_md_lists_what_is_expected():
    assert len(SPELLED) > 50 and len(DOCUMENTED) > 40
    for name in ("query", "query.open", "query.plan_key", "query.close", "join.tables",
                 "join.pack_lines", "join.query_pack", "op.", "xla.compile", "spill.read"):
        assert name in SPELLED, name
        assert name in DOCUMENTED, name
    assert "device.udf_" in DOCUMENTED and "device.udf_h2d" in SPELLED


@pytest.mark.parametrize("name", sorted(set(SPELLED) | set(DOCUMENTED)))
def test_every_span_name_has_a_layer(name):
    """A span added later must be given a layer: a name `LAYER_OF` does not
    know raises, it cannot fall silently into a remainder."""
    assert idlemap.layer_of(name) in idlemap.LAYERS


def test_a_name_the_table_does_not_know_raises_and_an_operator_is_host_ops():
    with pytest.raises(KeyError, match="no layer for the span 'cache.lookup'"):
        idlemap.layer_of("cache.lookup")
    with pytest.raises(KeyError):
        idlemap.layer_of("query.something_new")
    assert idlemap.layer_of("op.DeviceJoinAgg(2 dims)") == "host_ops"
    assert idlemap.layer_of("device.udf_dispatch") == "dispatch"
    assert idlemap.layer_of("device.upload.prepare") == "h2d"
    assert idlemap.layer_of("spill.grace_join") == "host_ops"
    assert set(idlemap.LAYER_OF.values()) == set(idlemap.LAYERS)
    with pytest.raises(KeyError):                # and so does a reading that meets one
        idlemap.layers(ctx_of(trace_of(PLANE_A), spans=SPANS + unix([("cache.lookup", 13.0, 13.5)])))


# ---- the recorded second of a v5e's trace ------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    """PR 24's recorded window (one chip; 211 `pipeline.morsel`,
    `device.dispatch` and `device.coalesce_flush` spans, already on the trace's
    clock, and no `query` root) with a root and two executions laid over it."""
    with open(os.path.join(DATA, "recorded_trace.json")) as f:
        rec = json.load(f)
    trace, (lo, hi) = rec["trace"], rec["window"]
    trace["device"] = {p: {ln: [tuple(e) for e in ev] for ln, ev in lines.items()}
                       for p, lines in trace["device"].items()}
    mid = (lo + hi) / 2
    runs = [{"template": "q1", "unix_start": lo + 0.001, "unix_end": mid - 0.002},
            {"template": "q6", "unix_start": mid, "unix_end": hi - 0.001}]
    spans = [tuple(s) for s in rec["spans"]]
    assert len(spans) == 211 and not spantree.has_tree(spans)
    spans += [("query", r["unix_start"] + 0.0005, r["unix_end"] - 0.0005) for r in runs]
    return rec, ctx_of(trace, spans, runs, window=(lo, hi), to_trace=0.0)


@pytest.mark.parametrize("key", KEYS)
def test_a_reader_on_the_recorded_chip_trace(recorded, key):
    rec, ctx = recorded
    got = reader(key).read(ctx)
    assert isinstance(got, float) and got >= 0.0
    own = idlemap.by_owner(ctx)
    want = sum(s for name, s in own.items() if idlemap.layer_of(name) == key)
    assert got == pytest.approx(1e3 * want / 2, abs=1e-9)
    if key in ("runner", "host_ops", "dispatch", "h2d", "api", "client"):
        assert got > 0.0        # the roots, the morsels, the dispatches, the flushes, the bare ends
    else:
        assert got == 0.0       # PR 24's program had no such span


def test_the_readers_sum_is_the_recorded_windows_idle_time(recorded):
    rec, ctx = recorded
    lo, hi = ctx["window"]
    total = sum(reader(key).read(ctx) for key in KEYS) * len(ctx["executions"]) / 1e3
    assert total == pytest.approx(rec["expect"]["idle_share"] * (hi - lo), abs=1e-9)
    assert total == pytest.approx(tr.length(idlemap.idle(ctx)), abs=1e-9)
    # what `breakdown.idle_gaps` calls `device.dispatch` is this reading's too
    # (the morsels that began inside a dispatch take their part of it)
    own = idlemap.by_owner(ctx)
    assert own["device.dispatch"] <= rec["expect"]["idle_by_owner"]["device.dispatch"] + 1e-9
    # the client's four milliseconds around the executions, less what lay over a busy device
    assert 0.0 < own[idlemap.CLIENT] <= 0.001 + 0.002 + 0.001 + 1e-9


# ---- the entries -------------------------------------------------------------------------

@pytest.fixture(scope="module")
def spec():
    return run.load_json(os.path.join(REPO, "BENCHMARK.json"))


@pytest.mark.parametrize("key", KEYS)
def test_an_entry_by_name(spec, key):
    per_layer = {m["name"]: m for m in spec["per_layer"]}
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    cells = [w["name"] for w in spec["workloads"]]
    assert per_layer[f"idle.{key}_ms"] == {
        "name": f"idle.{key}_ms", "unit": "ms", "better": "lower", "source": "device_trace",
        "layer": LAYER_IN_SPEC[key], "moves": "query_ms.geomean", "workloads": LISTED}
    assert all(cell in e2e["query_ms.geomean"].get("workloads", cells) for cell in LISTED)
    assert os.path.isfile(os.path.join(BENCH, "layer_metrics", f"idle.{key}_ms.py"))
    # appended after PR 49's last entry, in the layers' order
    names = [m["name"] for m in spec["per_layer"]]
    assert names.index(f"idle.{key}_ms") \
        == names.index("filteredjoin.join_hbm_share") + 1 + KEYS.index(key)


@pytest.mark.parametrize("cell", LISTED + CLOSED_SETS)
def test_which_cells_report_the_twelve(cell):
    """The five listed cells report all twelve; the four whose per-layer set
    the benchmark's own tests hold closed report none of them."""
    mine = {m["name"] for m in run.Cell(REPO, cell).metrics("per_layer")}
    twelve = {f"idle.{key}_ms" for key in KEYS}
    assert (twelve <= mine) if cell in LISTED else not (twelve & mine)
    assert "device.idle_share" in mine     # the share the twelve split, in every cell


# ---- a traced run, end to end ------------------------------------------------------------

def test_a_traced_run_of_a_listed_cell_reports_the_twelve_and_they_add_up(bench_root, monkeypatch):
    """`run.py --trace 1` over a tiny copy of the join cell, on the CPU: the
    program's own spans (whatever names it emits must have a layer), the
    profiler's clock tie, and in place of the chip's plane, which a CPU trace
    lacks, a device that works 2 ms in every 5: the result line carries all
    twelve, and they are the window's idle seconds."""
    from bench_helpers import add_cell

    spec = add_cell(bench_root, "tiny.joins", "tiny", "joins", scale_factor=0.02)
    for m in spec["per_layer"]:
        if m["name"] in {f"idle.{key}_ms" for key in KEYS}:
            m["workloads"].append("tiny.joins")
    with open(os.path.join(bench_root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    seen = {}
    read_xplane = tr.read_xplane

    def with_a_device(path):
        out = read_xplane(path)
        out["device"]["/device:TPU:0"] = {"XLA Ops": [
            ("fusion.1", out["sync_s"] + 0.005 * k, 0.002) for k in range(20_000)]}
        seen["trace"] = out
        return out

    monkeypatch.setattr(tr, "read_xplane", with_a_device)
    result = run.run_cell(bench_root, "tiny.joins", seed=2**31 + 5, seconds=0.5,
                          trace=True, require_tpu=False)
    assert result["correct"] is True, result
    got = {key: result["metrics"][f"idle.{key}_ms"] for key in KEYS}
    assert all(m["unit"] == "ms" and m["value"] >= 0.0 for m in got.values())
    window_s, busy_s = result["device"]["window_s"], result["device"]["busy_s"]
    total = sum(m["value"] for m in got.values()) * result["attempted"] / 1e3
    assert total == pytest.approx(window_s - busy_s, abs=1e-6)
    assert total / window_s == pytest.approx(
        result["metrics"]["device.idle_share"]["value"] / 100, abs=1e-3)
    # the host tier's query: its operators, its plan, the runner and the API own the wait
    for key in ("host_ops", "plan", "runner", "api", "client"):
        assert got[key]["value"] > 0.0, key

"""The `tpch_joins_mesh` suite (`tpch_sf30_mesh4.joins`): the cell's traffic is
correct through the harness at a test's size, and on the device tier over
four (virtual) devices; the bfloat16 control is not correct; the suite
refuses a program without the mesh join's counters and one whose join
dispatches did not each span the four chips or whose q3 or q10 did not keep
its TopN on them; and the `meshjoin.*` readers on hand-made executions and a
hand-made four-plane trace (a roofline share that cannot pass 100%), each
None where there is nothing to read. On the CPU: nothing here is a
measurement."""

import json
import os

import pytest

import compare
import run
from bench_helpers import BENCH, REPO, add_cell

CELL = "tpch_sf30_mesh4.joins"
METRICS = ["meshjoin.shards_per_dispatch", "meshjoin.batches_per_query",
           "meshjoin.dispatch_host_ms", "meshjoin.shard_ms", "meshjoin.launch_ms",
           "meshjoin.combine_ms", "meshjoin.select_ms", "meshjoin.fetched_rows_per_query",
           "meshjoin.residency_misses", "meshjoin.shard_skew_share",
           "meshjoin.collective_share", "meshjoin.join_hbm_share"]
TWINS = {"meshjoin.dispatch_host_ms": "stages.dispatch_host_ms",
         "meshjoin.launch_ms": "mesh.launch_ms", "meshjoin.select_ms": "jointopn.select_ms",
         "meshjoin.fetched_rows_per_query": "jointopn.fetched_rows_per_query",
         "meshjoin.residency_misses": "residency.misses_per_query",
         "meshjoin.shard_skew_share": "mesh.shard_skew_share",
         "meshjoin.collective_share": "mesh.collective_share"}
NEW_COUNTERS = ("device_join_mesh_batches", "device_join_mesh_shards",
                "device_topn_combine_bytes")


def reader(name):
    return run.load_module(os.path.join(BENCH, "layer_metrics", name + ".py"))


def suite(kind):
    return run.load_module(os.path.join(BENCH, kind, "tpch_joins_mesh.py"))


# ---- the entries and the configuration ---------------------------------------------------

def test_the_cell_and_its_metrics_are_entries_by_name():
    spec = run.load_json(os.path.join(REPO, "BENCHMARK.json"))
    by_name = {w["name"]: w for w in spec["workloads"]}
    assert by_name[CELL] == dict(by_name[CELL], config="tpch-sf30-joins-4chip",
                                 traffic="joins_mesh", chips=4)
    # two of seven cells take four chips; three may (half of seven, rounded down)
    four = [w["name"] for w in spec["workloads"] if w["chips"] == 4]
    assert four == ["tpch_sf30_mesh4.scanagg", CELL] and len(four) <= len(spec["workloads"]) // 2
    cell = run.Cell(REPO, CELL)
    assert [m["name"] for m in cell.metrics("end_to_end")] \
        == ["query_ms.geomean", "scan_rows_per_s", "setup_s"]
    mine = [m for m in spec["per_layer"] if m["name"].startswith("meshjoin.")]
    assert [m["name"] for m in mine] == METRICS
    assert all(m["workloads"] == [CELL] and m["moves"] == "query_ms.geomean" for m in mine)
    per_layer = {m["name"]: m for m in spec["per_layer"]}
    for new, old in TWINS.items():   # a twin keeps its reader's layer, unit and direction
        assert {k: per_layer[new][k] for k in ("unit", "better", "source", "layer")} \
            == {k: per_layer[old][k] for k in ("unit", "better", "source", "layer")}
    assert per_layer["meshjoin.join_hbm_share"]["unit"] == "%"
    reported = {m["name"] for m in cell.metrics("per_layer")}
    assert {"placement.device_query_share", "h2d.bytes_per_query", "stages.dispatches_per_query",
            "device.idle_share", "compile.window_compiles", "compile.setup_compile_s"} <= reported
    assert not {"mesh.collective_share", "jointopn.select_ms", "join.codes_ms"} & reported
    for m in mine:
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", m["name"] + ".py"))


def test_the_one_chip_join_cells_entries_are_as_they_were():
    """What test_bench_joins10.py's entry test holds besides its count of
    four-chip cells (one then, two now: tests/conftest.py), kept by name."""
    spec = run.load_json(os.path.join(REPO, "BENCHMARK.json"))
    one = next(w for w in spec["workloads"] if w["name"] == "tpch_sf10.joins")
    assert (one["config"], one["traffic"], one["chips"]) \
        == ("tpch-sf10-joins-1chip", "joins_sf10", 1)
    cell = run.Cell(REPO, "tpch_sf10.joins")
    assert [m["name"] for m in cell.metrics("end_to_end")] \
        == ["query_ms.geomean", "scan_rows_per_s", "setup_s"]
    theirs = [m for m in spec["per_layer"] if m["name"].startswith("jointopn.")]
    assert len(theirs) == 11 and all(m["workloads"] == ["tpch_sf10.joins"] for m in theirs)
    reported = {m["name"] for m in cell.metrics("per_layer")}
    assert not {m for m in reported if m.startswith(("meshjoin.", "mesh."))}
    # the accepted entries stand where they stood: this PR's follow them
    names = [m["name"] for m in spec["per_layer"]]
    assert names.index("jointopn.join_hbm_share") + 1 == names.index(METRICS[0])
    assert [w["name"] for w in spec["workloads"]][-2:] == ["tpch_sf10.joins", CELL]
    assert [c["name"] for c in spec["configs"]][-2:] \
        == ["tpch-sf10-joins-1chip", "tpch-sf30-joins-4chip"]


def test_the_four_chip_scan_cells_entries_are_as_they_were():
    """`test_bench_setup.py`'s test of that name, without its count of
    four-chip cells (tests/conftest.py)."""
    import test_bench_setup as setup

    spec = run.load_json(os.path.join(REPO, "BENCHMARK.json"))
    mesh_cell = "tpch_sf30_mesh4.scanagg"
    scan = next(w for w in spec["workloads"] if w["name"] == mesh_cell)
    assert (scan["config"], scan["traffic"], scan["chips"]) \
        == ("tpch-sf30-4chip", "scanagg_mesh", 4)
    mesh = [m for m in spec["per_layer"] if m["name"].startswith("mesh.")]
    assert [m["name"] for m in mesh] == [
        "mesh.shards_per_dispatch", "mesh.launch_ms", "mesh.shard_skew_share",
        "mesh.collective_share", "mesh.scan_hbm_share"]
    assert all(m["workloads"] == [mesh_cell] and m["layer"] == "Mesh" for m in mesh)
    reported = {m["name"] for m in run.Cell(REPO, mesh_cell).metrics("per_layer")}
    assert reported == {m["name"] for m in mesh} | set(setup.SETUP_METRICS) | {
        "placement.device_query_share", "h2d.bytes_per_query", "stages.dispatches_per_query",
        "device.idle_share", "compile.window_compiles", "compile.setup_compile_s"}
    # the nine setup.* readers' lists are the benchmark's: they lack the new cell
    assert all(CELL not in m["workloads"] for m in spec["per_layer"]
               if m["name"] in setup.SETUP_METRICS)


def test_the_configuration_states_the_deployment():
    cfg = run.Cell(REPO, CELL).config
    one = run.load_json(os.path.join(BENCH, "configs", "tpch-sf10-joins-1chip.json"))
    assert cfg["suite"] == "tpch_joins_mesh" and cfg["scale_factor"] == 30 and cfg["chips"] == 4
    assert cfg["source_scale_factor"] == 100 and list(cfg["reduced"]) == ["scale_factor"]
    # tpch-sf10-joins-1chip's three guarantees, word for word but for the reference's file
    assert cfg["guarantees"]["exact"] == one["guarantees"]["exact"]
    assert cfg["guarantees"]["floats"] == one["guarantees"]["floats"]
    assert cfg["guarantees"]["answers"] == one["guarantees"]["answers"].replace(
        "reference/tpch_joins10.py", "reference/tpch_joins_mesh.py")
    assert set(cfg["float_rel_limit"]) == {"q3", "q5", "q10"}
    assert set(cfg["float_rel_limit_why"]) >= {"readings", "q3", "q5", "q10"}
    for clause in ("2.4.3", "2.4.5", "2.4.10", "4.1.3.1", "1.2", "4.2.3"):
        assert clause in cfg["source"]
    for word in ("SHARDED", "WHOLE ON EVERY CHIP", "COMBINED ON THE CHIPS", "all-to-all"):
        assert word in cfg["deployment"]
    assert cfg["assumed"][0] == one["assumed"][0] and len(cfg["assumed"]) == 3
    traffic = run.load_json(os.path.join(BENCH, "traffic", "joins_mesh.json"))
    assert traffic["templates"] == ["q3", "q5", "q10"] and traffic["clients"] == 1
    assert traffic["suite"] == "tpch_joins_mesh" and traffic["trace_seconds"] == 6
    assert traffic["loop"] == "closed"


def test_the_templates_the_reference_and_the_generator_are_the_one_chip_cells_own():
    queries = suite("queries")
    j10 = run.load_module(os.path.join(BENCH, "queries", "tpch_joins10.py"))
    assert list(queries.TEMPLATES) == ["q3", "q5", "q10"]
    for name, tpl in queries.TEMPLATES.items():
        theirs = j10.TEMPLATES[name]
        assert {k: v for k, v in tpl.items() if k != "program"} \
            == {k: v for k, v in theirs.items() if k != "program"}
    # the programs are the same functions under this suite's own check
    assert queries._QUERIES == {"q3": j10._tpch.q3, "q5": j10._tpch.q5, "q10": j10.q10} \
        or [f.__code__.co_code for f in queries._QUERIES.values()] \
        == [j10._tpch.q3.__code__.co_code, j10._tpch.q5.__code__.co_code, j10.q10.__code__.co_code]
    tables = ["region", "nation", "customer", "orders", "lineitem", "supplier"]
    arrow = suite("datagen").generate(0.002, 5, tables)
    ref10 = run.load_module(os.path.join(BENCH, "reference", "tpch_joins10.py"))
    theirs = run.load_module(os.path.join(BENCH, "datagen", "tpch_joins10.py")).generate(
        0.002, 5, tables)
    assert all(arrow[t].equals(theirs[t]) for t in tables)
    for name in queries.TEMPLATES:
        assert suite("reference").answer(name, arrow) == ref10.answer(name, arrow)


# ---- through the harness ---------------------------------------------------------------------

@pytest.mark.parametrize("seed", [2**31 + 41, 11])
def test_the_cells_traffic_runs_and_is_correct_at_a_test_size(bench_root, seed):
    add_cell(bench_root, "tiny.joinsmesh", "tiny", "joins_mesh", scale_factor=0.05,
             float_rel_limit=run.Cell(REPO, CELL).config["float_rel_limit"])
    result = run.run_cell(bench_root, "tiny.joinsmesh", seed=seed, seconds=0.5,
                          trace=False, require_tpu=False)
    assert result["correct"] is True, result
    assert result["failed"] == 0 and result["attempted"] >= 3
    assert set(result["metrics"]) == {"query_ms.geomean", "scan_rows_per_s", "setup_s"}


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_in_bfloat16_is_not_correct(seed):
    """The reference with its float columns stored in bfloat16, put in the
    program's place, fails the cell's own float limits through q3 or q10."""
    cell = run.Cell(REPO, CELL)
    arrow = cell.datagen.generate(0.05, seed, cell.tables_read())
    verdicts = {}
    for name in cell.templates:
        lim = compare.limits(cell.config, name)
        ref = cell.reference.answer(name, arrow)
        low = cell.reference.answer(name, arrow, cell.reference.to_bfloat16)
        assert compare.within(compare.compare(ref, ref), lim)
        verdicts[name] = compare.within(compare.compare(ref, low), lim)
    assert not (verdicts["q3"] and verdicts["q10"]), verdicts


def test_the_program_answers_the_suite_over_four_devices():
    """The three templates forced onto four (virtual) devices at a test's
    size, morsels small enough that a query takes several sharded dispatches:
    the reference's answers, every join dispatch spanning the four, q3 and
    q10 one run-wide TopN each that fetched K rows a device."""
    import daft_tpu as dt
    from daft_tpu.config import execution_config_ctx
    from daft_tpu.ops import counters

    cell = run.Cell(REPO, CELL)
    queries = suite("queries")   # a fresh module: nothing is checked off the TPU
    arrow = cell.datagen.generate(0.01, 7, cell.tables_read())
    tables = {n: dt.from_arrow(t).collect() for n, t in arrow.items()}
    for name in queries.TEMPLATES:
        counters.reset()
        with execution_config_ctx(device_mode="on", morsel_size_rows=4096,
                                  pipeline_mode="force", mesh_devices=4):
            got = queries.TEMPLATES[name]["program"](tables).to_pydict()
        numbers = compare.compare(cell.reference.answer(name, arrow), got)
        assert compare.within(numbers, compare.limits(cell.config, name)), (name, numbers)
        snap = counters.snapshot()
        counts = {c: snap.get(c, 0) for c in queries._CHECKED}
        assert counts["device_join_batches"] > 1, (name, counters.rejections)
        assert queries._why_not(name, counts) == "", (name, counts)
        if name in ("q3", "q10"):
            assert snap["device_topn_fetched_rows"] == 4 * queries._TOPN_LIMIT[name]
            assert snap["device_topn_combine_bytes"] > 0


# ---- the suite's own check -------------------------------------------------------------------

def test_a_program_without_the_counters_ends_the_run_at_import(monkeypatch, capsys):
    """The parent of the PR that added the cell: the suite exits 1 as it is
    imported, before any data is made, naming what is missing."""
    from daft_tpu.observability import metrics

    suite("queries")  # this program declares them
    monkeypatch.setattr(metrics, "DEVICE_COUNTER_NAMES", tuple(
        c for c in metrics.DEVICE_COUNTER_NAMES if c not in NEW_COUNTERS))
    with pytest.raises(SystemExit) as e:
        suite("queries")
    assert e.value.code == 1
    out = capsys.readouterr()
    assert "device_join_mesh_batches" in out.out and "device_topn_combine_bytes" in out.err
    assert "four chips" in out.err
    with pytest.raises(SystemExit):
        run.Cell(REPO, CELL)  # the harness finds the cell's files first of all


# growth of (join batches, mesh batches, mesh shards, topn runs, topn batches, fetched rows)
# over q3's, q5's and q10's first executions
_DEPLOYED = [(344, 344, 1376, 1, 344, 40), (344, 344, 1376, 0, 0, 0), (344, 344, 1376, 1, 344, 80)]


def _with(template, **changed):
    i = ["q3", "q5", "q10"].index(template)
    names = ("joins", "spanned", "shards", "runs", "batches", "fetched")
    row = dict(zip(names, _DEPLOYED[i]), **changed)
    return _DEPLOYED[:i] + [tuple(row[n] for n in names)] + _DEPLOYED[i + 1:]


@pytest.mark.parametrize("backend, devices, deltas, ends", [
    ("cpu", 8, [(0,) * 6] * 3, None),                    # tier-1 tests: nothing is checked
    ("tpu", 1, [(458, 0, 0, 1, 458, 10)] * 3, None),     # one chip is not where the cell runs
    ("tpu", 4, _DEPLOYED, None),                         # the deployment
    ("tpu", 4, _with("q3", fetched=7), None),            # fewer winners than the limit
    ("tpu", 4, _with("q3", joins=0, spanned=0, shards=0, runs=0, batches=0, fetched=0), "q3"),
    ("tpu", 4, _with("q5", spanned=0, shards=0), "q5"),  # the join stayed on one chip
    ("tpu", 4, _with("q5", shards=688), "q5"),           # dispatches spanned two chips
    ("tpu", 4, _with("q10", spanned=343, shards=1372), "q10"),   # one dispatch did not span
    ("tpu", 4, _with("q10", runs=0, batches=0, fetched=0), "q10"),  # per-batch tables
    ("tpu", 4, _with("q3", batches=1), "q3"),            # a TopN of one batch
    ("tpu", 4, _with("q10", fetched=81), "q10"),         # more than K rows a chip
    ("tpu", 4, _with("q3", fetched=1 << 20), "q3"),      # a table fetched, not its winners
])
def test_the_first_execution_has_to_be_the_mesh_join(monkeypatch, capsys, backend, devices,
                                                     deltas, ends):
    import daft_tpu as dt
    import jax

    queries = suite("queries")  # a fresh module: a fresh count of builds
    arrow = suite("datagen").generate(0.002, 6, ["region", "nation", "customer", "orders",
                                                 "lineitem", "supplier"])
    tables = {n: dt.from_arrow(t).collect() for n, t in arrow.items()}
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(jax, "devices", lambda *a: [object()] * devices)
    base = (3, 0, 0, 3, 100, 50)
    for name, delta in zip(("q3", "q5", "q10"), deltas):
        counts = iter([base, tuple(b + d for b, d in zip(base, delta))])
        monkeypatch.setattr(queries, "_counts", lambda: next(counts))
        program = queries.TEMPLATES[name]["program"]
        program(tables)  # built, never executed
        if name == ends:
            with pytest.raises(SystemExit) as e:
                program(tables)
            assert e.value.code == 1
            out = capsys.readouterr()
            assert f"{name}'s first execution" in out.out
            assert "tpch-sf30-joins-4chip" in out.err
            return
        program(tables)
        program(tables)  # a third build checks nothing and reads no counter
    assert ends is None


# ---- the readers -----------------------------------------------------------------------------

def plane(*ops):
    return {"XLA Ops": [(name, start, dur) for name, start, dur in ops]}


# three executions on four chips: a q3 (0..10 s), a q5 (10..20 s), a q10
# (20..30 s); chip 3 is the slowest in q3, chip 0 in q5; every chip ends q3 and
# q10 in an all-to-all
TRACE = {"sync_s": 0.0, "device": {
    "/device:TPU:0": plane(("while.5", 1.0, 4.0), ("all-to-all.1", 8.0, 0.5),
                           ("fusion.3", 11.0, 3.0), ("while.7", 21.0, 2.0),
                           ("all-to-all.2", 27.0, 0.5)),
    "/device:TPU:1": plane(("while.5", 1.0, 4.0), ("all-to-all.1", 8.0, 0.5),
                           ("fusion.3", 11.0, 2.0), ("while.7", 21.0, 2.0),
                           ("all-to-all.2", 27.0, 0.5)),
    "/device:TPU:2": plane(("while.5", 1.0, 3.0), ("all-to-all.1", 8.0, 0.5),
                           ("fusion.3", 11.0, 2.0), ("while.7", 21.0, 2.0),
                           ("all-to-all.2", 27.0, 0.5)),
    "/device:TPU:3": plane(("while.5", 1.0, 5.0), ("all-to-all.1", 8.0, 0.5),
                           ("fusion.3", 11.0, 2.0), ("while.7", 21.0, 2.5),
                           ("all-to-all.2", 27.0, 0.5)),
}}
ONE_CHIP = {"sync_s": 0.0, "device": {"/device:TPU:0": TRACE["device"]["/device:TPU:0"]}}


def _counters(topn_rows=None):
    c = {"device_join_batches": 344, "device_grouped_batches": 344,
         "device_join_mesh_batches": 344, "device_join_mesh_shards": 1376,
         "device_mesh_batches": 344, "device_mesh_shards": 1376}
    if topn_rows:
        c.update(device_topn_runs=1, device_join_topn_batches=344,
                 device_topn_fetched_rows=topn_rows, device_topn_combine_bytes=1 << 30)
    return c


RUNS = [
    {"template": "q3", "unix_start": 0.0, "unix_end": 10.0, "start": 0.0, "end": 10.0,
     "failed": False, "counters": _counters(40)},
    {"template": "q5", "unix_start": 10.0, "unix_end": 20.0, "start": 10.0, "end": 20.0,
     "failed": False, "counters": _counters()},
    {"template": "q10", "unix_start": 20.0, "unix_end": 30.0, "start": 20.0, "end": 30.0,
     "failed": False, "counters": _counters(80)},
]
ONE_CHIP_RUNS = [dict(r, counters={k: v for k, v in r["counters"].items()
                                   if "mesh" not in k and "combine" not in k}) for r in RUNS]
# two join dispatches in q3 (one holds a join.shard), one in q5; q3 and q10 end in a combine
SPANS = [("query", 0.0, 10.0),
         ("device.dispatch", 0.5, 1.5), ("join.gather", 0.6, 1.0), ("join.shard", 0.7, 0.9),
         ("device.launch", 1.1, 1.4),
         ("device.dispatch", 2.0, 2.5), ("join.gather", 2.1, 2.3), ("device.launch", 2.3, 2.4),
         ("stage.finalize", 8.0, 9.5), ("join.topn_select", 8.1, 9.1),
         ("join.combine", 8.2, 8.8), ("device.d2h", 8.8, 9.0),
         ("query", 10.0, 20.0),
         ("device.dispatch", 10.5, 11.0), ("join.gather", 10.6, 10.8), ("join.shard", 10.6, 10.7),
         ("device.launch", 10.8, 10.9),
         ("query", 20.0, 30.0), ("stage.finalize", 27.0, 29.0), ("join.topn_select", 27.5, 28.0),
         ("join.combine", 27.5, 27.9)]
PLANE_BYTES = 4 * (1 << 19)     # a dispatch's plane over its four shards


def ctx_of(trace=TRACE, runs=RUNS, spans=SPANS, hbm_bytes_per_s=1e6):
    import xtrace as tr

    return {"executions": list(runs), "spans": list(spans), "trace": trace,
            "busy": tr.busy_union(trace), "window": (0.0, 30.0), "to_trace": 0.0,
            "window_s": 30.0, "queries": suite("queries").TEMPLATES,
            "peaks": {"hbm_bytes_per_s": hbm_bytes_per_s, "f32_flops_per_s": 1e12}}


@pytest.mark.parametrize("name, want", [
    ("meshjoin.shards_per_dispatch", 4.0),
    ("meshjoin.batches_per_query", 344.0),
    ("meshjoin.fetched_rows_per_query", 60.0),
    ("meshjoin.residency_misses", 0.0),
    ("meshjoin.dispatch_host_ms", 1e3 * (1.0 + 0.5 + 0.5) / 3),
    ("meshjoin.launch_ms", 1e3 * (0.3 + 0.1 + 0.1) / 3),
    # join.shard seconds over the window's join dispatches
    ("meshjoin.shard_ms", 1e3 * (0.2 + 0.1) / 3),
    ("meshjoin.combine_ms", 1e3 * (0.6 + 0.4) / 2),
    ("meshjoin.select_ms", 1e3 * (1.0 + 0.5) / 2),
    # busy seconds by plane: 10.0, 9.0, 8.0, 10.5
    ("meshjoin.shard_skew_share", 100.0 * (1 - 8.0 / 10.5)),
    # the busiest chip spent 1 of its 10.5 busy seconds in the all-to-alls
    ("meshjoin.collective_share", 100.0 * 1.0 / 10.5),
])
def test_the_meshjoin_readers_on_hand_made_executions_and_a_four_plane_trace(name, want):
    assert reader(name).read(ctx_of()) == pytest.approx(want)


def test_the_twins_are_their_accepted_readers():
    ctx = ctx_of()
    for new, old in TWINS.items():
        assert reader(new).read(ctx) == reader(old).read(ctx), new


def _live(monkeypatch, arrays):
    import joinbytes

    monkeypatch.setattr(joinbytes, "live_planes", lambda: arrays)


ARRAYS = [((1 << 19,), "float32", PLANE_BYTES)] * 9 + [((1 << 19,), "bool", 1 << 19)] * 20 \
    + [((4 * ((1 << 26) + 4096),), "float32", 16 * ((1 << 26) + 4096))] * 3


def test_the_roofline_share_counts_a_dispatchs_planes_over_the_four_chips(monkeypatch, capsys):
    """q3 reads 5 planes a dispatch, q5 7, q10 5 (benchmark/joinbytes.py), each
    a plane of the dispatch's global length, 344 dispatches an execution, over
    four chips' bandwidth, against the busiest chip of each execution."""
    _live(monkeypatch, ARRAYS)
    share = reader("meshjoin.join_hbm_share").read(ctx_of())
    least = 344 * (5 + 7 + 5) * PLANE_BYTES / (4 * 1e6)
    busiest = (5.0 + 0.5) + 3.0 + (2.5 + 0.5)
    assert share == pytest.approx(100.0 * least / busiest)
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    said = {x["phase"]: x for x in lines}
    assert said["roofline"]["devices"] == 4 and said["roofline"]["bound"] == "hbm"
    assert said["roofline"]["join_least_bytes"] == 344 * 17 * PLANE_BYTES
    assert said["memory"]["peak_rss_bytes"] > 0
    assert len(said["memory"]["peak_hbm_bytes_by_device"]) >= 1


def test_the_roofline_share_cannot_pass_100_percent(monkeypatch):
    """A chip cannot read its shard faster than its HBM gives it: with every
    chip busy exactly the least time its shard of q5's dispatches takes, the
    share is 100%; it divides by the busiest chip, so an idle chip cannot
    flatter it; and it counts the devices the trace shows, not a constant."""
    _live(monkeypatch, ARRAYS)
    shard_s = 344 * 7 * PLANE_BYTES / 4 / 1e6
    even = {"sync_s": 0.0, "device": {f"/device:TPU:{i}": plane(("while", 10.5, shard_s))
                                      for i in range(4)}}
    rd = reader("meshjoin.join_hbm_share")
    long_q5 = [dict(RUNS[1], unix_end=10.0 + 2 * shard_s + 10, end=10.0 + 2 * shard_s + 10)]
    assert rd.read(ctx_of(even, long_q5)) == pytest.approx(100.0)
    skewed = json.loads(json.dumps(even))
    skewed["device"]["/device:TPU:1"]["XLA Ops"] = []
    assert rd.read(ctx_of(skewed, long_q5)) == pytest.approx(100.0)
    skewed["device"]["/device:TPU:2"]["XLA Ops"] = [["while", 10.5, 2 * shard_s]]
    assert rd.read(ctx_of(skewed, long_q5)) == pytest.approx(50.0)
    two = {"sync_s": 0.0, "device": {k: v for k, v in list(even["device"].items())[:2]}}
    assert rd.read(ctx_of(two, long_q5)) == pytest.approx(200.0)  # miscounted bytes show


@pytest.mark.parametrize("name", METRICS)
def test_a_one_chip_run_gives_the_readers_nothing_to_read_or_its_own_number(monkeypatch, name):
    """One device plane, no mesh counters and no mesh spans (the one-chip join
    cell, or the parent's program): the readers that need a mesh return None
    and do not raise, so the result line leaves the metric out; the twins of
    one-chip readers read what those read."""
    _live(monkeypatch, ARRAYS)
    spans = [s for s in SPANS if s[0] not in ("join.shard", "join.combine")]
    got = reader(name).read(ctx_of(ONE_CHIP, ONE_CHIP_RUNS, spans))
    one_chip_twins = {"meshjoin.dispatch_host_ms", "meshjoin.select_ms",
                      "meshjoin.fetched_rows_per_query", "meshjoin.residency_misses"}
    assert (got is not None) == (name in one_chip_twins), (name, got)


def test_the_roofline_share_has_nothing_to_read_without_planes_or_busy_chips(monkeypatch):
    _live(monkeypatch, [])
    assert reader("meshjoin.join_hbm_share").read(ctx_of()) is None
    _live(monkeypatch, ARRAYS)
    idle = {"sync_s": 0.0, "device": {f"/device:TPU:{i}": plane() for i in range(4)}}
    assert reader("meshjoin.join_hbm_share").read(ctx_of(idle)) is None
    failed = [dict(r, failed=True) for r in RUNS]
    assert reader("meshjoin.join_hbm_share").read(ctx_of(runs=failed)) is None


def test_the_list_less_readers_read_the_cells_window_true():
    """100% of the queries on the device, 344 dispatches each, nothing
    uploaded, the four planes' mean for the idle share."""
    ctx = ctx_of()
    assert reader("placement.device_query_share").read(ctx) == 100.0
    assert reader("stages.dispatches_per_query").read(ctx) == 344.0
    assert reader("h2d.bytes_per_query").read(ctx) == 0
    busy = (10.0 + 9.0 + 8.0 + 10.5) / 4
    assert reader("device.idle_share").read(ctx) == pytest.approx(100.0 * (1 - busy / 30.0))

"""The `tpch_mesh` suite (`tpch_sf30_mesh4.scanagg`): the cell's traffic is
correct through the harness at a test's size; the bfloat16 control is not;
the suite refuses a program without the mesh counters and one whose
dispatches did not span the four chips; and the `mesh.*` readers on a
hand-made four-plane trace (skew, collective share, a roofline share that
cannot pass 100%), each None on a one-chip run. On the CPU: nothing here is a
measurement."""

import json
import os

import pytest

import run
from bench_helpers import BENCH, REPO, add_cell

CELL = "tpch_sf30_mesh4.scanagg"
MESH_METRICS = ["mesh.shards_per_dispatch", "mesh.launch_ms", "mesh.shard_skew_share",
                "mesh.collective_share", "mesh.scan_hbm_share"]


def reader(name):
    return run.load_module(os.path.join(BENCH, "layer_metrics", name + ".py"))


def suite(kind):
    return run.load_module(os.path.join(BENCH, kind, "tpch_mesh.py"))


# ---- the entries and the configuration ---------------------------------------------------

def test_the_cell_is_the_benchmarks_one_four_chip_cell():
    spec = run.load_json(os.path.join(REPO, "BENCHMARK.json"))
    four = [w for w in spec["workloads"] if w["chips"] == 4]
    assert [w["name"] for w in four] == [CELL]
    assert four[0]["config"] == "tpch-sf30-4chip" and four[0]["traffic"] == "scanagg_mesh"
    assert spec["workloads"][-1] == four[0] and spec["configs"][-1]["name"] == "tpch-sf30-4chip"
    cell = run.Cell(REPO, CELL)
    assert [m["name"] for m in cell.metrics("end_to_end")] \
        == ["query_ms.geomean", "scan_rows_per_s", "setup_s"]
    mesh = [m for m in spec["per_layer"] if m["name"].startswith("mesh.")]
    assert [m["name"] for m in mesh] == MESH_METRICS == [m["name"] for m in spec["per_layer"][-5:]]
    assert all(m["workloads"] == [CELL] and m["layer"] == "Mesh" for m in mesh)
    # the list-less metrics apply to the cell as they are
    reported = {m["name"] for m in cell.metrics("per_layer")}
    assert {"placement.device_query_share", "h2d.bytes_per_query", "stages.dispatches_per_query",
            "device.idle_share", "compile.window_compiles", "compile.setup_compile_s"} <= reported
    assert "kernels.scan_hbm_share" not in reported and "query_ms.p95" not in \
        {m["name"] for m in cell.metrics("end_to_end")}


def test_the_configuration_states_the_deployment():
    cell = run.Cell(REPO, CELL)
    cfg, scan = cell.config, run.load_json(os.path.join(BENCH, "configs", "tpch-sf10-1chip.json"))
    assert cfg["suite"] == "tpch_mesh" and cfg["scale_factor"] == 30 and cfg["chips"] == 4
    assert cfg["source_scale_factor"] == 100 and list(cfg["reduced"]) == ["scale_factor"]
    assert set(cfg["guarantees"]) == set(scan["guarantees"]) == {"answers", "exact", "floats"}
    assert cfg["guarantees"]["exact"] == scan["guarantees"]["exact"]
    assert cfg["guarantees"]["floats"] == scan["guarantees"]["floats"]
    assert cfg["assumed"][:2] == scan["assumed"] and len(cfg["assumed"]) == 3
    assert set(cfg["float_rel_limit"]) == {"q1", "q6"}
    # no looser than the one-chip scan cell's limits
    assert all(cfg["float_rel_limit"][t] <= scan["float_rel_limit"][t] for t in ("q1", "q6"))
    traffic = run.load_json(os.path.join(BENCH, "traffic", "scanagg_mesh.json"))
    plain = run.load_json(os.path.join(BENCH, "traffic", "scanagg.json"))
    assert traffic == dict(plain, suite="tpch_mesh")


def test_the_templates_the_reference_and_the_generator_are_the_scan_cells_own():
    queries, plain = suite("queries"), run.load_module(os.path.join(BENCH, "queries", "tpch.py"))
    assert set(queries.TEMPLATES) == {"q1", "q6"}
    for name, t in queries.TEMPLATES.items():
        assert {k: v for k, v in t.items() if k != "program"} \
            == {k: v for k, v in plain.TEMPLATES[name].items() if k != "program"}
    arrow = suite("datagen").generate(0.002, 5, ["lineitem"])
    same = run.load_module(os.path.join(BENCH, "datagen", "tpch.py")).generate(
        0.002, 5, ["lineitem"])
    assert arrow["lineitem"].equals(same["lineitem"])
    import daft_tpu as dt

    tables = {"lineitem": dt.from_arrow(arrow["lineitem"]).collect()}
    ref = suite("reference")
    for name in ("q1", "q6"):
        got = queries.TEMPLATES[name]["program"](tables).to_pydict()
        assert got == plain.TEMPLATES[name]["program"](tables).to_pydict()
        assert list(got) == list(ref.answer(name, arrow))


# ---- through the harness ---------------------------------------------------------------

@pytest.mark.parametrize("seed", [2**31 + 3, 7])
def test_the_cells_traffic_runs_and_is_correct_at_a_test_size(bench_root, seed):
    """`test_bench_cells.py`'s case for the other cells, for this one (that
    file is the benchmark's and is not edited): SF0.05 through `run_cell`."""
    add_cell(bench_root, "tiny.scanagg_mesh", "tiny", "scanagg_mesh", scale_factor=0.05,
             float_rel_limit=run.Cell(REPO, CELL).config["float_rel_limit"])
    result = run.run_cell(bench_root, "tiny.scanagg_mesh", seed=seed, seconds=0.5,
                          trace=False, require_tpu=False)
    assert result["correct"] is True, result
    assert result["failed"] == 0 and result["attempted"] >= 2
    assert set(result["metrics"]) == {"query_ms.geomean", "scan_rows_per_s", "setup_s"}
    assert result["metrics"]["scan_rows_per_s"]["value"] > 0


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_in_bfloat16_is_not_correct(seed):
    """The reference with its float columns stored in bfloat16, put in the
    program's place, fails the limits of the cell's own configuration: q6's
    and q1's (at a test's scale; the readings at SF30 are in the
    configuration's file and PERF.md section 2)."""
    import compare

    cell = run.Cell(REPO, CELL)
    arrow = cell.datagen.generate(0.05, seed, cell.tables_read())
    verdicts = {}
    for name in cell.templates:
        lim = compare.limits(cell.config, name)
        ref = cell.reference.answer(name, arrow)
        low = cell.reference.answer(name, arrow, cell.reference.to_bfloat16)
        assert compare.within(compare.compare(ref, ref), lim)
        verdicts[name] = compare.within(compare.compare(ref, low), lim)
    assert verdicts["q6"] is False, verdicts


# ---- the suite's own check -------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_tables():
    import daft_tpu as dt

    arrow = suite("datagen").generate(0.002, 6, ["lineitem"])
    return {"lineitem": dt.from_arrow(arrow["lineitem"]).collect()}


def test_a_program_without_the_counters_ends_the_run_at_import(monkeypatch, capsys):
    """The parent of the PR that added the cell: the suite exits 1 as it is
    imported, before any data is made, naming what is missing."""
    from daft_tpu.observability import metrics

    suite("queries")  # this program declares them
    monkeypatch.setattr(metrics, "DEVICE_COUNTER_NAMES", tuple(
        c for c in metrics.DEVICE_COUNTER_NAMES if not c.startswith("device_mesh_")))
    with pytest.raises(SystemExit) as e:
        suite("queries")
    assert e.value.code == 1
    out = capsys.readouterr()
    assert "device_mesh_batches" in out.out and "device_mesh_batches" in out.err
    with pytest.raises(SystemExit):
        run.Cell(REPO, CELL)  # the harness finds the cell's files first of all


@pytest.mark.parametrize("backend, devices, counts, ends", [
    ("cpu", 8, [(0, 0), (0, 0)], False),          # tier-1 tests: nothing is checked
    ("tpu", 1, [(0, 0), (0, 0)], False),          # not the four-chip machine
    ("tpu", 4, [(3, 12), (4, 16)], False),        # one dispatch over four chips
    ("tpu", 4, [(0, 0), (2, 8)], False),          # two dispatches, four chips each
    ("tpu", 4, [(5, 20), (5, 20)], True),         # one chip or the host: no mesh dispatch
    ("tpu", 4, [(0, 0), (1, 2)], True),           # a mesh of two
    ("tpu", 8, [(0, 0), (1, 8)], True),           # not this deployment's four
])
def test_the_first_execution_has_to_span_the_four_chips(monkeypatch, capsys, tiny_tables,
                                                        backend, devices, counts, ends):
    import jax

    queries = suite("queries")  # a fresh module: a fresh count of builds
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(jax, "devices", lambda *a: [object()] * devices)
    it = iter(counts)
    monkeypatch.setattr(queries, "_mesh_counts", lambda: next(it))
    built = queries.TEMPLATES["q1"]["program"](tiny_tables)  # built, never executed
    assert built.schema.column_names()[:2] == ["l_returnflag", "l_linestatus"]
    if ends:
        with pytest.raises(SystemExit) as e:
            queries.TEMPLATES["q1"]["program"](tiny_tables)
        assert e.value.code == 1
        out = capsys.readouterr()
        assert "device_mesh_shards" in out.out and "four chips" in out.err
    else:
        queries.TEMPLATES["q1"]["program"](tiny_tables)
        queries.TEMPLATES["q1"]["program"](tiny_tables)  # checked once only


# ---- the readers -----------------------------------------------------------------------

def plane(*ops):
    return {"XLA Ops": [(name, start, dur) for name, start, dur in ops]}


# two executions (q1 0..10 s, q6 10..20 s) on four chips; chip 3 is the
# slowest in q1, chip 0 in q6; chip 2 spends a second of q1 in an all-reduce
TRACE = {"sync_s": 0.0, "device": {
    "/device:TPU:0": plane(("while.5", 1.0, 2.0), ("fusion.3", 11.0, 3.0)),
    "/device:TPU:1": plane(("while.5", 1.0, 2.5), ("fusion.3", 11.0, 1.0)),
    "/device:TPU:2": plane(("while.5", 1.0, 2.0), ("all-reduce.1", 3.0, 1.0),
                           ("fusion.3", 11.0, 1.0)),
    "/device:TPU:3": plane(("while.5", 1.0, 4.0), ("fusion.3", 11.0, 1.0)),
}}
ONE_CHIP = {"sync_s": 0.0, "device": {"/device:TPU:0": TRACE["device"]["/device:TPU:0"]}}
RUNS = [
    {"template": "q1", "unix_start": 0.0, "unix_end": 10.0, "start": 0.0, "end": 10.0,
     "failed": False, "counters": {"device_grouped_batches": 1, "device_mesh_batches": 1,
                                   "device_mesh_shards": 4}},
    {"template": "q6", "unix_start": 10.0, "unix_end": 20.0, "start": 10.0, "end": 20.0,
     "failed": False, "counters": {"device_stage_batches": 1, "device_mesh_batches": 1,
                                   "device_mesh_shards": 4}},
]
ONE_CHIP_RUNS = [dict(r, counters={k: v for k, v in r["counters"].items()
                                   if not k.startswith("device_mesh_")}) for r in RUNS]
SPANS = [("query", 0.0, 10.0), ("device.dispatch", 0.5, 1.0), ("device.launch", 0.6, 0.9),
         ("query", 10.0, 20.0), ("device.dispatch", 10.5, 11.0), ("device.launch", 10.6, 10.7)]
PLANE_BYTES = 4 * (1 << 20)
QUERIES = {"q1": {"scan_columns": ("a",) * 7}, "q6": {"scan_columns": ("a",) * 4}}


def ctx_of(trace, runs, hbm_bytes_per_s=1e6):
    return {"trace": trace, "executions": list(runs), "spans": list(SPANS), "to_trace": 0.0,
            "window": (0.0, 20.0), "queries": QUERIES,
            "big_arrays": [((1 << 20,), "float32", PLANE_BYTES)] * 7
            + [((1 << 20,), "bool", 1 << 20)] * 7,
            "peaks": {"hbm_bytes_per_s": hbm_bytes_per_s, "f32_flops_per_s": 1e12}}


@pytest.mark.parametrize("name, want", [
    ("mesh.shards_per_dispatch", 4.0),
    ("mesh.launch_ms", 1e3 * (0.3 + 0.1) / 2),
    # busy seconds by plane: 5.0, 3.5, 4.0, 5.0
    ("mesh.shard_skew_share", 100.0 * (1 - 3.5 / 5.0)),
    # the busiest plane is the first of the two that were busy 5 s: no collective on it
    ("mesh.collective_share", 0.0),
    # 11 planes' bytes over 4 chips' bandwidth, against the busiest chip of each execution
    ("mesh.scan_hbm_share", 100.0 * (11 * PLANE_BYTES / 4e6) / (4.0 + 3.0)),
])
def test_the_mesh_readers_on_a_hand_made_four_plane_trace(name, want):
    assert reader(name).read(ctx_of(TRACE, RUNS)) == pytest.approx(want)


def test_the_collective_share_is_of_the_busiest_chip():
    trace = json.loads(json.dumps(TRACE))
    trace["device"]["/device:TPU:2"]["XLA Ops"].append(["all-gather.2", 15.0, 2.0])
    # chip 2 is now busy 6 s, 3 of them in collectives
    assert reader("mesh.collective_share").read(ctx_of(trace, RUNS)) == pytest.approx(50.0)
    assert reader("mesh.shard_skew_share").read(ctx_of(trace, RUNS)) \
        == pytest.approx(100.0 * (1 - 3.5 / 6.0))


def test_the_roofline_share_cannot_pass_100_percent():
    """A chip cannot read its shard faster than its HBM gives it: with every
    chip busy exactly the least time its shard takes, the share is 100%; it
    divides by the busiest chip, so an idle chip cannot flatter it; and it
    counts the devices the trace shows, not a constant."""
    shard_s = 7 * PLANE_BYTES / 4 / 1e6
    even = {"sync_s": 0.0, "device": {f"/device:TPU:{i}": plane(("while", 1.0, shard_s))
                                      for i in range(4)}}
    rd = reader("mesh.scan_hbm_share")
    long_q1 = [dict(RUNS[0], unix_end=100.0, end=100.0)]
    assert rd.read(ctx_of(even, long_q1)) == pytest.approx(100.0)
    skewed = json.loads(json.dumps(even))
    skewed["device"]["/device:TPU:1"]["XLA Ops"] = []
    assert rd.read(ctx_of(skewed, long_q1)) == pytest.approx(100.0)
    skewed["device"]["/device:TPU:2"]["XLA Ops"] = [["while", 1.0, 2 * shard_s]]
    assert rd.read(ctx_of(skewed, long_q1)) == pytest.approx(50.0)
    two = {"sync_s": 0.0, "device": {k: v for k, v in list(even["device"].items())[:2]}}
    assert rd.read(ctx_of(two, long_q1)) == pytest.approx(200.0)  # miscounted bytes show


@pytest.mark.parametrize("name", MESH_METRICS)
def test_a_one_chip_run_gives_the_mesh_readers_nothing_to_read(name):
    """One device plane and no mesh counters (a one-chip cell, or the parent's
    program): None, not a raise, so the result line leaves the metric out."""
    assert reader(name).read(ctx_of(ONE_CHIP, ONE_CHIP_RUNS)) is None


def test_the_list_less_readers_read_a_four_chip_window_true():
    """100% of the queries on the device, one dispatch each, the four planes'
    mean for the idle share."""
    import xtrace

    ctx = ctx_of(TRACE, RUNS)
    assert reader("placement.device_query_share").read(ctx) == 100.0
    assert reader("stages.dispatches_per_query").read(ctx) == 1.0
    assert reader("h2d.bytes_per_query").read(ctx) == 0
    assert xtrace.busy_seconds(TRACE, (0.0, 20.0)) == pytest.approx((5.0 + 3.5 + 4.0 + 5.0) / 4)
    assert reader("device.idle_share").read(ctx) == pytest.approx(100.0 * (1 - 4.375 / 20.0))
